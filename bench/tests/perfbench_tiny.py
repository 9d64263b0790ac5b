"""A copy of the benchmark with tiny cells, for tests on the CPU."""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
# the program under test: the checkout's, whatever the working directory
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

TINY_CONFIG = {
    "name": "tiny-kron", "source": "test", "generator": "kronecker",
    "scale": 10, "edge_factor": 16, "initiator": [0.57, 0.19, 0.19, 0.05],
    "max_weight": 4, "graph_seed": 1, "index": {"l_cap": 64},
}
TINY_CELLS = {"tiny-kron.build": ("build", {})}
BUILD = ["tiny-kron.build"]
# (name, unit, better, moves, cells): the metrics the readers in
# bench/metrics/ and the cell kinds report
END_TO_END = [("build_s", "s", "lower", None, BUILD),
              ("setup_s", "s", "lower", None, None)]     # every cell
PER_LAYER = [("device.idle_pct.build", "%", "lower", "build_s", BUILD),
             ("build.peel_s", "s", "lower", "build_s", BUILD),
             ("build.label_s", "s", "lower", "build_s", BUILD)]


def make_root(tmp: Path) -> Path:
    """A checkout-like directory: the benchmark's code and data plus a
    tiny configuration and its cells in BENCHMARK.json."""
    root = Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    (root / "bench" / "configs" / "tiny-kron.json").write_text(
        json.dumps(TINY_CONFIG))
    (root / "bench" / "cells").mkdir(exist_ok=True)
    for name, (_, params) in TINY_CELLS.items():
        (root / "bench" / "cells" / f"{name}.json").write_text(
            json.dumps(params))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-kron", "source": "test",
                         "file": "bench/configs/tiny-kron.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": n, "config": "tiny-kron", "traffic": t,
                           "chips": 1, "why": "test"}
                          for n, (t, _) in TINY_CELLS.items()]
    bench["end_to_end"] = [
        {"name": n, "unit": u, "better": b, "bound": 0.1,
         "source": "host_clock", **({"workloads": cells} if cells else {})}
        for n, u, b, _, cells in END_TO_END]
    bench["per_layer"] = [
        {"name": n, "unit": u, "better": b, "source": "device_trace",
         "layer": n.split(".")[0], "moves": moves, "workloads": cells}
        for n, u, b, moves, cells in PER_LAYER]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the CPU has no published peaks: a stand-in row so traced runs of
    # the tiny cells can be reduced
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["cpu"] = {"source": "test stand-in", "flops_per_s": 1e12,
                    "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    return root


def load_file(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = load_file(BENCH / "run.py", "perfbench_run_entry")


def run_cell(root, capsys, cell, trace=0, seed=2**31 + 17, seconds=2):
    """One run of a tiny cell past the look for a chip; its result line."""
    capsys.readouterr()
    rc = RUN.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  require_chip=False, root=root, t_start=time.monotonic())
    captured = capsys.readouterr()
    assert rc == 0
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert captured.err.strip().splitlines()[-1].startswith("check ")
    return line
