"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness loads ``configs/<config>.json``, ``traffic/<traffic>.json`` and
``cells/<cell>.json`` as data, the generator ``generators/<generator>.py``
the configuration names, the cell kind ``kinds/<kind>.py`` the mix names,
and one reader ``metrics/<metric>.py`` per per-layer metric. A new cell,
configuration, mix or metric is new files and entries; no file here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


class SpecError(Exception):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def load_module(path: Path, name: str):
    """Import one file by path (metric files carry dots in their names)."""
    if not path.is_file():
        raise SpecError(f"no file {path}")
    mod = sys.modules.get(name)
    if mod is not None and Path(mod.__file__) == path:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    params: dict                 # cells/<cell>.json, {} when absent
    end_to_end: list             # the end-to-end metric entries it reports
    per_layer: list              # the per-layer metric entries it reports
    bench_dir: Path

    def generator(self):
        return load_module(
            self.bench_dir / "generators" / f"{self.config['generator']}.py",
            f"bench_generator_{self.config['generator']}")

    def kind(self):
        return load_module(self.bench_dir / "kinds" / f"{self.traffic['kind']}.py",
                           f"bench_kind_{self.traffic['kind']}")

    def metric_reader(self, name: str):
        return load_module(self.bench_dir / "metrics" / f"{name}.py",
                           "bench_metric_" + name.replace(".", "_"))


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path | None = None,
              bench_dir: Path | None = None) -> Cell:
    """Load the cell ``name`` from ``<root>/BENCHMARK.json``; the data
    and code it names are looked up under ``bench_dir``."""
    bench_dir = BENCH_DIR if bench_dir is None else Path(bench_dir)
    root = bench_dir.parent if root is None else Path(root)
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in reported
                 and ("workloads" not in m or name in m["workloads"])]
    cell_file = bench_dir / "cells" / f"{name}.json"
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=read_json(root / cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        params=read_json(cell_file) if cell_file.is_file() else {},
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)
