"""A configuration, a traffic mix, a generator, a cell kind and a
per-layer metric are found by name: adding one is adding files."""
import json
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))
import perfbench_tiny  # noqa: E402
from harness import spec  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.kind().run
        assert cell.generator().generate
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert cell.metric_reader(m["name"]).read


def test_new_files_are_found_by_name(tmp_path):
    root = perfbench_tiny.make_root(tmp_path)
    b = root / "bench"
    (b / "generators" / "ring.py").write_text(
        "import numpy as np\n"
        "def generate(cfg, seed):\n"
        "    n = cfg['num_vertices']\n"
        "    u = np.arange(n, dtype=np.int32); v = (u + 1) % n\n"
        "    w = np.ones(n, np.float32)\n"
        "    return n, np.r_[u, v], np.r_[v, u], np.r_[w, w]\n")
    (b / "configs" / "ring.json").write_text(json.dumps(
        {"generator": "ring", "num_vertices": 10, "graph_seed": 0,
         "index": {"l_cap": 64}}))
    (b / "traffic" / "bursts.json").write_text(json.dumps(
        {"kind": "replay", "arrivals": "bursts"}))
    (b / "kinds" / "replay.py").write_text("def run(ctx):\n    return 'ran'\n")
    (b / "cells" / "ring.bursts.json").write_text('{"rate_qps": 3.0}')
    (b / "metrics" / "ring.edges_per_vertex.py").write_text(
        "def read(layer):\n    return layer.edges / layer.vertices\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ring", "source": "test",
                             "file": "bench/configs/ring.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "ring.bursts", "config": "ring",
                               "traffic": "bursts", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "ring_s", "unit": "s",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["ring.bursts"]})
    bench["per_layer"].append({"name": "ring.edges_per_vertex", "unit": "1",
                               "better": "higher",
                               "source": "program_counter", "layer": "ring",
                               "moves": "ring_s",
                               "workloads": ["ring.bursts"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("ring.bursts", root=root, bench_dir=b)
    assert cell.config["generator"] == "ring"
    assert cell.params == {"rate_qps": 3.0}
    assert cell.kind().run(None) == "ran"
    n, src, dst, w = cell.generator().generate(cell.config, 0)
    assert n == 10 and len(src) == 20
    assert sorted(m["name"] for m in cell.end_to_end) == ["ring_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["ring.edges_per_vertex"]
    reader = cell.metric_reader("ring.edges_per_vertex")
    assert reader.read(types.SimpleNamespace(edges=20, vertices=10)) == 2.0
    # the tiny cells beside it still load, untouched
    assert spec.load_cell("tiny-kron.build", root=root, bench_dir=b).per_layer


def test_a_name_without_a_file_is_an_error(tmp_path):
    root = perfbench_tiny.make_root(tmp_path)
    (root / "bench" / "traffic" / "build.json").unlink()
    with pytest.raises(spec.SpecError):
        spec.load_cell("tiny-kron.build", root=root,
                       bench_dir=root / "bench")
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such.cell", root=root, bench_dir=root / "bench")
