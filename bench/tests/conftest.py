"""Cells of the benchmark cut to sizes a CPU test run can hold.

Run by path: `PYTHONPATH=src python -m pytest bench/tests` (the
repository's own test run collects `tests/` only).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402


def small_cell(name: str) -> spec.Cell:
    """`name` from BENCHMARK.json with its configuration shrunk to 8
    agents and a 128-node graph in 16 chunks.  Scenario, engine and
    replicas unchanged."""
    c = spec.load_cell(name)
    cfg = dict(c.config, n_wgs=8, n_chunks_max=32, chunk_cap=8,
               graph_nodes=128)
    return c._replace(config=cfg)
