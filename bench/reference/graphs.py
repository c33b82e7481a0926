"""The benchmark's own copy of the synthetic collaboration-graph generator.

Preferential attachment (Barabasi-Albert style), symmetrized, deduplicated,
self loops dropped: the stand-in the simulator uses for the paper's
DIMACS10 cond-mat-2003 input.  Copied from the program's generator so that
the reference draws its inputs from the seed itself; only node degrees
are needed.
"""
from __future__ import annotations

import numpy as np


def collab_degrees(n: int, m: int, seed: int) -> np.ndarray:
    """[n] int32 degrees of the undirected collab_like(n, m, seed) graph."""
    rng = np.random.default_rng(seed)
    src, dst = [], []
    repeated: list[int] = list(range(m))
    for v in range(m, n):
        picks = rng.choice(len(repeated), size=m, replace=True)
        chosen = {repeated[p] for p in picks}
        for t in chosen:
            src.append(v)
            dst.append(t)
            repeated.append(t)
            repeated.append(v)
    u = np.concatenate([src, dst]).astype(np.int64)
    w = np.concatenate([dst, src]).astype(np.int64)
    keep = u != w
    edges = np.unique(u[keep] * n + w[keep])
    return np.bincount(edges // n, minlength=n).astype(np.int32)
