"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix; the traffic mix names the entry that drives one round; each metric's
base name (the part before the first ``.``) names its reader.  Adding any
of them is adding a file here and an entry to ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def _module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def base_name(metric: str) -> str:
    return metric.split(".", 1)[0]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    entry: object          # module with run_round(...)
    end_to_end: list       # metric entries of BENCHMARK.json for this cell
    per_layer: list

    def reader(self, metric: dict):
        return _module(BENCH / "metrics" / f"{base_name(metric['name'])}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = load_spec() if spec is None else spec
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    cfg = [c for c in spec["configs"] if c["name"] == w["config"]][0]
    config = _json(ROOT / cfg["file"])
    traffic = _json(BENCH / "traffic" / f"{w['traffic']}.json")
    entry = _module(BENCH / "entries" / f"{traffic['entry']}.py")
    return Cell(name, w["chips"], config, traffic, entry,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])
