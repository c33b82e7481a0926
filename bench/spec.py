"""Resolve a cell of `BENCHMARK.json` into its data files.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
`BENCHMARK.json` gives it:

    bench/configs/<config>.json    deployment: workload module, sizes, costs
    bench/traffic/<traffic>.json   scenario, engine, replicas per call
    bench/drivers/<workload>.py    how one workload module is driven
    bench/metrics/<metric>.py      reader of one per-layer metric
"""
from __future__ import annotations

import json
import os
from typing import NamedTuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict       # bench/configs/<config>.json, plus its "name"
    traffic: dict      # bench/traffic/<traffic>.json, plus its "name"
    end_to_end: list   # BENCHMARK.json metric entries; every cell reports
    per_layer: list    # all of them


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`; unknown names raise
    with the list of cells."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    config = dict(_load(os.path.join(BENCH_DIR, "configs",
                                     w["config"] + ".json")),
                  name=w["config"])
    traffic = dict(_load(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json")),
                   name=w["traffic"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
