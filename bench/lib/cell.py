"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything particular to one configuration, one traffic mix or one per-layer
metric is a file of its own, found by name:

- ``configs[].file``: the configuration's sizes; its ``family`` names the
  program adapter ``bench/models/<family>.py`` and the plain reference
  ``bench/reference/<family>.py``;
- ``bench/traffic/<traffic>.json``: the traffic mix's parameters;
- ``bench/metrics/<metric>.py``: a reader with ``read(run) -> float | None``,
  for an end-to-end metric as for a per-layer one.

So a cell is added by files and a ``workloads`` entry alone.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, List


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_modules(config: dict):
    """(program adapter, plain reference) modules of a configuration."""
    fam = config["family"]
    return (importlib.import_module(f"bench.models.{fam}"),
            importlib.import_module(f"bench.reference.{fam}"))


def _reports(metric: dict, workload: str, by_name: dict) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    moved = by_name[metric["moves"]]
    return "workloads" not in moved or workload in moved["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its files."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    config["name"] = cfg_entry["name"]
    bench = root / bm["paths"][0]
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())

    def reader(m: dict) -> Metric:
        mod = _load_module(bench / "metrics" / f"{m['name']}.py",
                           f"bench_metric_{m['name']}")
        return Metric(m["name"], m["unit"], mod.read)

    by_name = {m["name"]: m for m in bm["end_to_end"]}
    e2e = [reader(m) for m in bm["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    per_layer = [reader(m) for m in bm["per_layer"]
                 if _reports(m, workload, by_name)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)
