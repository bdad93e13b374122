"""Resolve a cell of BENCHMARK.json to the files that define it, by name.

A cell names a configuration and a traffic mix; each lives in a file of its
own under ``bench/``, and each per-layer metric has a reader of its own, so
a new model, mix or metric is a new file and a new entry, never an edit:

* configuration: the ``file`` its ``configs`` entry names
* traffic mix:   ``bench/traffic/<traffic>.json``
* limits:        ``bench/limits/<workload>.json`` (what ``correct`` allows)
* metric reader: ``bench/metrics/<metric name>.py``
* work counter:  ``bench/counters/<kind>.py`` (operations and bytes)
* model family:  the configuration's ``family`` names the step's ops,
  ``bench/ops/<family>.py``, and the plain reference that decides
  ``correct``, ``bench/reference/<family>.py``; a new architecture is a
  new pair of files

A new kind of traffic (a training step, an open loop with arrivals) is
code in ``harness/cell.py``; every other addition is files.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cells(metrics: list, workload: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    traffic: dict           # the traffic mix file
    limits: dict            # the limits file
    end_to_end: list        # BENCHMARK.json entries that this cell reports
    per_layer: list
    readers: dict = field(default_factory=dict)   # metric name -> module

    @property
    def run(self) -> dict:
        return self.config["run"]

    @property
    def ops(self):
        return family("ops", self.config["family"])

    @property
    def reference(self):
        return family("reference", self.config["family"])


def resolve(workload: str, benchmark: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(benchmark)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"names {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    per_layer = _cells(spec["per_layer"], workload)
    cell = Cell(
        name=workload, chips=w["chips"],
        config=load_json(ROOT / cfg_entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{workload}.json"),
        end_to_end=_cells(spec["end_to_end"], workload),
        per_layer=per_layer)
    for m in per_layer:
        cell.readers[m["name"]] = load_module(
            BENCH / "metrics" / f"{m['name']}.py",
            "bench_metric_" + m["name"].replace(".", "_"))
    return cell


_MODULES = {}


def _cached(path: Path, name: str):
    if name not in _MODULES:
        _MODULES[name] = load_module(path, name)
    return _MODULES[name]


def counter(kind: str):
    """The operations-and-bytes counter of one kind of work."""
    return _cached(BENCH / "counters" / f"{kind}.py", f"bench_counter_{kind}")


def family(role: str, name: str):
    """A model family's module of one role: "ops" (the work of a step) or
    "reference" (the plain forward that decides ``correct``)."""
    return _cached(BENCH / role / f"{name}.py", f"bench_{role}_{name}")
