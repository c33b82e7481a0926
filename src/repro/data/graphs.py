"""Synthetic CSR graphs standing in for the paper's DIMACS inputs (§5.1).

This environment is offline, so we generate graphs with the same structural
character as the ones the paper uses:

  * ``collab_like``  — power-law collaboration network (cond-mat-2003; used
    by PageRank in the paper): preferential attachment, heavy-tailed degree
    distribution -> strong per-chunk work imbalance.
  * ``road_like``    — sparse near-planar grid with a small fraction of
    shortcut edges (USA-road-d.BAY; used by SSSP).  Shortcuts keep the
    diameter (== SSSP round count) manageable in the offline simulator;
    the substitution is documented in EXPERIMENTS.md.
  * ``router_like``  — power-law with lower attachment (caidaRouterLevel;
    used by MIS).

All graphs are undirected (symmetrized), weights uniform in [1, 16).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class CSRGraph(NamedTuple):
    indptr: np.ndarray   # [n+1] int32
    indices: np.ndarray  # [nnz] int32
    weights: np.ndarray  # [nnz] int32
    name: str

    @property
    def n(self) -> int:
        return len(self.indptr) - 1

    @property
    def nnz(self) -> int:
        return len(self.indices)

    @property
    def degrees(self) -> np.ndarray:
        return (self.indptr[1:] - self.indptr[:-1]).astype(np.int32)


def _to_csr(n: int, src: np.ndarray, dst: np.ndarray, rng, name: str) -> CSRGraph:
    # symmetrize + dedup + drop self loops
    u = np.concatenate([src, dst])
    v = np.concatenate([dst, src])
    keep = u != v
    u, v = u[keep], v[keep]
    key = u.astype(np.int64) * n + v
    _, idx = np.unique(key, return_index=True)
    u, v = u[idx], v[idx]
    order = np.argsort(u, kind="stable")
    u, v = u[order], v[order]
    indptr = np.zeros(n + 1, np.int32)
    np.add.at(indptr, u + 1, 1)
    indptr = np.cumsum(indptr, dtype=np.int64).astype(np.int32)
    w = rng.integers(1, 16, size=len(v)).astype(np.int32)
    return CSRGraph(indptr, v.astype(np.int32), w, name)


class Draws(NamedTuple):
    words: int       # raw 64-bit PCG64 outputs the draws consumed
    rejections: int  # 32-bit draws Lemire's rule rejected


_MASK32 = 0xFFFFFFFF
_SLACK_WORDS = 64    # first draw's room for rejections; refills past it


def _half_words(bg, count: int):
    """PCG64's 32-bit draws in `next_uint32` order: the low half of each
    raw word, then its high half.  Draws `count` words, then more as
    needed."""
    while True:
        w = bg.random_raw(count)
        yield from np.stack([w & _MASK32, w >> 32], 1).ravel().tolist()
        count = 1024


def _attach(n: int, m: int, seed: int):
    """Preferential attachment from the raw PCG64 stream (DESIGN.md §15).

    Returns (`repeated`, rng, Draws), bit for bit what a per-node
    `rng.choice(len(repeated), size=m)` loop gives: numpy decodes each
    bounded draw from one 32-bit half-word with Lemire's multiply-shift,
    and the rng is left where that loop would leave it.  `repeated` is
    range(m), then (target, source) for each edge in order."""
    rng = np.random.default_rng(seed)
    bg = rng.bit_generator
    if not isinstance(bg, np.random.PCG64):
        raise TypeError(f"collab graphs decode PCG64's stream, not "
                        f"{type(bg).__name__}'s")
    saved = bg.state
    repeated: list[int] = list(range(m))
    v0 = m
    if m == 1 and n > 1:     # choice(1, ...) draws nothing
        repeated += [0, 1]
        v0 = 2
    draws = m * max(n - v0, 0)
    nxt = _half_words(bg, -(-draws // 2) + _SLACK_WORDS).__next__
    rejections = 0
    per_node = range(m)
    for v in range(v0, n):
        k = len(repeated)
        chosen = set()
        for _ in per_node:
            x = nxt() * k
            if (x & _MASK32) < k:
                threshold = ((1 << 32) - k) % k
                while (x & _MASK32) < threshold:
                    x = nxt() * k
                    rejections += 1
            chosen.add(repeated[x >> 32])
        for t in chosen:
            repeated.append(t)
            repeated.append(v)
    # rewind to what the draws consumed; an odd count leaves a high half
    # in the bit generator's 32-bit buffer
    halves = draws + rejections
    bg.state = saved
    bg.advance(halves // 2)
    if halves % 2:
        high = int(bg.random_raw()) >> 32
        state = bg.state
        state["has_uint32"] = 1
        state["uinteger"] = high
        bg.state = state
    return repeated, rng, Draws(-(-halves // 2), rejections)


def collab_like(n: int = 8192, m: int = 6, seed: int = 0) -> CSRGraph:
    """Preferential-attachment graph (Barabási–Albert style)."""
    repeated, rng, _ = _attach(n, m, seed)
    edges = np.array(repeated, np.int64)
    return _to_csr(n, edges[m + 1::2], edges[m::2], rng,
                   f"collab_like_n{n}")


def collab_degrees(n: int, m: int, seed: int) -> tuple[np.ndarray, Draws]:
    """([n] int32 degrees of collab_like(n, m, seed), its Draws).  Each
    edge (v, t) has t < v and t from a set, so the edges are unique and
    loop-free: a node's degree is how often `repeated` names it."""
    repeated, _, draws = _attach(n, m, seed)
    deg = np.bincount(np.array(repeated[m:], np.int64), minlength=n)
    return deg.astype(np.int32), draws


def router_like(n: int = 8192, seed: int = 1) -> CSRGraph:
    g = collab_like(n, m=2, seed=seed)
    return g._replace(name=f"router_like_n{n}")


def road_like(n: int = 16384, shortcut_frac: float = 0.01, seed: int = 2) -> CSRGraph:
    """Grid road network with a few express shortcuts (keeps diameter small)."""
    rng = np.random.default_rng(seed)
    side = int(np.sqrt(n))
    n = side * side
    ids = np.arange(n).reshape(side, side)
    src, dst = [], []
    # 4-neighborhood with 10% random removals (non-grid irregularity)
    right = np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()], 1)
    down = np.stack([ids[:-1, :].ravel(), ids[1:, :].ravel()], 1)
    edges = np.concatenate([right, down], 0)
    keep = rng.random(len(edges)) > 0.1
    edges = edges[keep]
    src, dst = edges[:, 0], edges[:, 1]
    # express shortcuts
    k = int(n * shortcut_frac)
    s = rng.integers(0, n, k)
    d = rng.integers(0, n, k)
    return _to_csr(n, np.concatenate([src, s]).astype(np.int64),
                   np.concatenate([dst, d]).astype(np.int64), rng,
                   f"road_like_n{n}")


GRAPHS = {
    "collab_like": collab_like,
    "router_like": router_like,
    "road_like": road_like,
}
