"""Where the benchmark finds each piece: by name, under ``bench/``.

``BENCHMARK.json`` (at the checkout's root) names cells, configurations,
traffic mixes and metrics.  Everything that belongs to one of them sits in a
file of its own, found by that name alone; nothing lists the files:

* ``bench/configs/<config>.json``   a deployment: its source, cuts and
                                   assumptions, its ``kind``, and the
                                   settings it runs with;
* ``bench/traffic/<traffic>.json``  a traffic mix: parameters that the
                                   runner's generator reads;
* ``bench/runners/<kind>.py``       the runner of one kind of configuration
                                   (``run(ctx) -> dict``);
* ``bench/metrics/<metric>.py``     the reader of one per-layer metric
                                   (``read(trace, counters) -> float|None``).

So a later cell, configuration, metric or kind of runner is added as files
only.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _json(bench: str, sub: str, name: str) -> Dict:
    with open(os.path.join(bench, sub, f"{name}.json")) as fh:
        return json.load(fh)


def _module(bench: str, sub: str, name: str) -> ModuleType:
    path = os.path.join(bench, sub, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {sub[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it resolves to."""

    def __init__(self, name: str, benchmark: Optional[Dict] = None,
                 bench: str = BENCH):
        self.bench = bench
        self.benchmark = benchmark if benchmark is not None else \
            load_benchmark(os.path.dirname(bench))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; one of {sorted(cells)}")
        self.name = name
        self.workload = cells[name]
        self.chips = int(self.workload["chips"])
        self.config = _json(bench, "configs", self.workload["config"])
        self.traffic = _json(bench, "traffic", self.workload["traffic"])

    def runner(self) -> ModuleType:
        return _module(self.bench, "runners", self.config["kind"])

    def end_to_end(self) -> List[Dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.benchmark["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict]:
        """The per-layer metrics whose code reads something in this cell:
        those that list it, or that list no cells and move an end-to-end
        metric the cell reports."""
        moves = {m["name"] for m in self.end_to_end()}
        return [m for m in self.benchmark["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in moves)]

    def reader(self, metric: str) -> ModuleType:
        return _module(self.bench, "metrics", metric)
