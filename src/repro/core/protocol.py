"""Functional model of the sRSP / RSP scoped-synchronization protocols (paper §2–4).

The memory system is modeled at *block granularity* over a shared L2 (the
global synchronization point) and N private L1 caches, the write-combining,
no-allocate hierarchy of the paper's Table 1.  The layout is block-major
(DESIGN.md §1): every array is shaped so that one cache block is one
contiguous row, which turns the flush machinery into single gather/scatter
ops instead of per-word dynamic slices:

    Store.l2      [n_blocks, block_words]            word values at L2
    Store.l1      [n_caches, n_blocks, block_words]  per-cache cached values
    Store.wvalid  [n_caches, n_blocks * L]           local copy is readable
    Store.wdirty  [n_caches, n_blocks * L]           local copy not written back
    Store.fifo    batched SFifo        dirty-block FIFO  (QuickRelease)
    Store.lr      batched LRTbl        sRSP local-release table (set-assoc)
    Store.pa      batched PATbl        sRSP promoted-acquire table (set-assoc)

A flat word address `addr` maps to (addr // block_words, addr % block_words).

The per-word metadata planes `wvalid`/`wdirty` are **packed uint32
word-bitmasks** (`core/bitmask.py`, DESIGN.md §8): bit `o % 32` of lane
`o // 32` tracks block offset `o`, so the planes carry 1 bit per word
instead of the boolean layout's byte — the in-loop scatters that bound the
batched engine at n_wgs=256 shrink with them.  `REPRO_NO_PACK=1` (read
once at import, mirroring REPRO_NO_DONATE) falls back to boolean flags,
one lane per word (L = W).  Either way a plane is stored lane-dense,
`[n_caches, n_blocks * L]` with L = `ProtoConfig.meta_lanes`: lane `w` of
block `b` is column `b * L + w`, and no axis of extent 1 is left for a
TPU layout to pad to 128 (DESIGN.md §8).  All plane access goes through
the `_pl_*`/`_rows_*` helpers below, which are the only layout-aware
code.

All operations are pure `(store, ...) -> (store', ...)` functions and fully
jittable; the cost model charges cycles/L2-transactions as a side channel in
`store.counters`.  Stale data is *really modeled*: an L1 may hold an old
copy of a word while L2 has moved on — a protocol bug shows up as a wrong
value read by a work-stealer, which the integration tests catch end-to-end.

Two API layers (DESIGN.md §3):

  * the classic single-cache ops (`load`, `store_word`, `local_acquire`, …)
    take a scalar `cid` and are what the protocol tests and the serial
    work-steal engine use;
  * the batched multi-cache ops (`b_load`, `b_store_word`,
    `local_acquire_b`, …) take an `active [n_caches]` mask plus per-cache
    operand vectors and execute one op *per cache* in a single set of array
    ops.  They are only semantics-preserving when the active caches touch
    pairwise-disjoint L2 words (the batched scheduler in worksteal.py
    guarantees this); cross-cache writeback merges resolve block-level
    false sharing deterministically (highest cache id wins per word, which
    matches the serial engine's ascending-j drain order).

Workload code should not bind these functions directly: the
scope-parametric instruction layer `repro.core.ops`
(`acquire/release/load/store(..., scope=LOCAL|REMOTE|GLOBAL)`,
DESIGN.md §9) dispatches into a registered `Protocol`'s per-scope op
table, including the batched address-disjoint remote twins
(`srsp_remote_acquire_b`/`srsp_remote_release_b`) that let the harness
co-schedule non-conflicting remote turns.

Invariant maintained (checked by property tests): every dirty word's block
is present in that cache's sFIFO, so a FIFO drain is a complete flush.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bitmask, sfifo, tables
from repro.core.costmodel import CostParams, Counters, make_counters
from repro.kernels import common
from repro.kernels.fused_turn import plane_commit
from repro.kernels.selective_flush.ops import drain_writeback
from repro.obs import trace as obs

INVALID = jnp.int32(-1)
# Public drain-everything sentinel for the `pos` argument of the drain ops
# (any seq is <= it, so the whole sFIFO drains).  `_DRAIN_ALL` is the
# historical private alias.
DRAIN_ALL = jnp.int32(2**30)
_DRAIN_ALL = DRAIN_ALL

# Metadata layout toggle, read once at import (the jitted schedulers are
# module-level, so the flag must be process-wide; the sweep A/Bs it in
# subprocesses).  Default: packed uint32 word-bitmasks (DESIGN.md §8).
PACKED = os.environ.get("REPRO_NO_PACK", "0") != "1"


@dataclasses.dataclass(frozen=True)
class ProtoConfig:
    n_caches: int
    n_words: int
    block_words: int = 16      # 64B block / 4B word (Table 1)
    fifo_cap: int = 16         # L1 sFIFO entries (Table 1)
    lr_tbl: tables.TableGeometry = tables.LR_GEOMETRY   # sets × ways
    pa_tbl: tables.TableGeometry = tables.PA_GEOMETRY   # sets × ways
    params: CostParams = dataclasses.field(default_factory=CostParams)

    @property
    def n_blocks(self) -> int:
        return (self.n_words + self.block_words - 1) // self.block_words

    @property
    def meta_lanes(self) -> int:
        """Plane columns per block in this layout (L): ceil(W/32) packed
        words, or W boolean flags."""
        return bitmask.n_lanes(self.block_words) if PACKED \
            else self.block_words


class Lease(NamedTuple):
    """Clock-stamped sync-word lease, one per cache (elastic alive-set PR).

    `addr[i]` is the L2 sync word cache i's last acquire targeted (INVALID
    once released) and `stamp[i]` the per-cache cycle clock at that
    acquire.  The scoped ISA (`repro.core.ops`) stamps these on every
    acquire/release as pure bookkeeping — no cycles, no counters — so the
    zero-churn schedule stays bitwise identical.  `b_recover` reads the
    lease to release a dead holder's sync word after its lease expires."""
    addr: jnp.ndarray      # [n_caches] i32 held sync word, INVALID if none
    stamp: jnp.ndarray     # [n_caches] f32 cycle clock at acquire


def lease_make(n_caches: int) -> Lease:
    return Lease(addr=jnp.full((n_caches,), INVALID),
                 stamp=jnp.zeros((n_caches,), jnp.float32))


def lease_stamp(st: "Store", active, addrs) -> "Store":
    """Record an acquire: active lanes now hold `addrs` as of their clock."""
    active = jnp.asarray(active, bool)
    return st._replace(lease=Lease(
        addr=jnp.where(active, jnp.asarray(addrs, jnp.int32), st.lease.addr),
        stamp=jnp.where(active, st.counters.cycles, st.lease.stamp)))


def lease_clear(st: "Store", active) -> "Store":
    """Record a release: active lanes hold nothing."""
    active = jnp.asarray(active, bool)
    return st._replace(lease=Lease(
        addr=jnp.where(active, INVALID, st.lease.addr),
        stamp=jnp.where(active, 0.0, st.lease.stamp)))


class Store(NamedTuple):
    l2: jnp.ndarray        # [n_blocks, W]
    l1: jnp.ndarray        # [n_caches, n_blocks, W]
    wvalid: jnp.ndarray    # [n_caches, n_blocks * meta_lanes] (see PACKED)
    wdirty: jnp.ndarray    # [n_caches, n_blocks * meta_lanes]
    fifo: sfifo.SFifo      # leaves have leading [n_caches]
    lr: tables.LRTbl
    pa: tables.PATbl
    lease: Lease           # clock-stamped sync-word leases (crash recovery)
    counters: Counters
    trace: obs.TraceLog    # event ring + latency hists; empty unless traced


def make_store(cfg: ProtoConfig) -> Store:
    n, nb, w = cfg.n_caches, cfg.n_blocks, cfg.block_words
    plane = jnp.zeros((n, nb * cfg.meta_lanes),
                      jnp.uint32 if PACKED else jnp.bool_)
    stack = lambda t: jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), t)
    return Store(
        l2=jnp.zeros((nb, w), jnp.int32),
        l1=jnp.zeros((n, nb, w), jnp.int32),
        wvalid=plane,
        wdirty=plane.copy(),
        fifo=stack(sfifo.make(cfg.fifo_cap)),
        lr=stack(tables.lr_make(cfg.lr_tbl)),
        pa=stack(tables.pa_make(cfg.pa_tbl)),
        lease=lease_make(n),
        counters=make_counters(n),
        trace=obs.make(obs.default_cap(), n),
    )


# --------------------------------------------------------------------------
# batched sub-structure helpers
# --------------------------------------------------------------------------

def _get(tree, cid):
    return jax.tree.map(lambda x: x[cid], tree)


def _set(tree, cid, sub):
    return jax.tree.map(lambda b, s: b.at[cid].set(s), tree, sub)


def _mask_tree(pred, new, old):
    """Select `new` where pred else `old` (same structure)."""
    return jax.tree.map(lambda n, o: jnp.where(pred, n, o), new, old)


def _mask_tree_rows(pred, new, old):
    """Per-cache select: pred [n_caches], leaves have leading [n_caches]."""
    def sel(n, o):
        p = pred.reshape(pred.shape + (1,) * (n.ndim - 1))
        return jnp.where(p, n, o)
    return jax.tree.map(sel, new, old)


def _blk(cfg: ProtoConfig, addr):
    return addr // cfg.block_words


def _split(cfg: ProtoConfig, addr):
    addr = jnp.asarray(addr, jnp.int32)
    return addr // cfg.block_words, addr % cfg.block_words


def _one_hot(cfg: ProtoConfig, cid):
    return jnp.arange(cfg.n_caches, dtype=jnp.int32) == jnp.asarray(cid, jnp.int32)


def _fill(cfg: ProtoConfig, val):
    return jnp.full((cfg.n_caches,), val, jnp.int32)


# --------------------------------------------------------------------------
# metadata-plane access — the ONLY layout-aware code (packed vs boolean)
# --------------------------------------------------------------------------

def _pl_col(cfg: ProtoConfig, b, o):
    """Plane column holding word offset `o` of block `b`: b * L + the
    offset's lane (its packed word, or the offset itself unpacked)."""
    w = bitmask.word_index(o) if PACKED else jnp.asarray(o, jnp.int32)
    return jnp.asarray(b, jnp.int32) * cfg.meta_lanes + w


def _pl_get(cfg: ProtoConfig, plane, lane, b, o):
    """Per-lane flag read: flags[lane, b, o] -> bool [n]."""
    words = plane[lane, _pl_col(cfg, b, o)]
    return bitmask.test_word(words, o) if PACKED else words


def _pl_clear(cfg: ProtoConfig, plane, lane, b, o, off):
    """Per-lane flag clear: flags[lane, b, o] &= ~off ((lane, b) pairs
    are distinct, so the scatter is safe)."""
    c = _pl_col(cfg, b, o)
    if PACKED:
        mask = jnp.where(jnp.asarray(off, bool), bitmask.word_bit(o),
                         jnp.uint32(0))
        return plane.at[lane, c].set(plane[lane, c] & ~mask)
    return plane.at[lane, c].set(plane[lane, c] & ~off)


def _row_cols(cfg: ProtoConfig, blks):
    """Plane columns of whole blocks: [..., L] for blks [...]; a block
    >= n_blocks maps past the plane, so a scatter there drops."""
    return (jnp.asarray(blks, jnp.int32)[..., None] * cfg.meta_lanes
            + jnp.arange(cfg.meta_lanes, dtype=jnp.int32))


def _rows_get(cfg: ProtoConfig, plane, lane, blks):
    """Metadata rows of block blks[...] in cache lane[...]: [..., L]."""
    return plane[lane[..., None], _row_cols(cfg, blks)]


def _rows_put(cfg: ProtoConfig, plane, lane, blks, rows):
    """Write rows [..., L] back to block blks[...] of cache lane[...];
    blocks >= n_blocks drop.

    A plane past the kernels' VMEM budget (`kernels/common.py`) takes
    the (lane, column) pairs in scatters of at most `_SCATTER_CHUNK`: a
    scatter of more (a drain's n x cap pairs) makes XLA relay the whole
    plane into a flat tiling and back, 510 MB of traffic at the tpcc
    cell's size for a few words (DESIGN.md §14).  The pairs are
    distinct, so the chunks commute."""
    cols = _row_cols(cfg, blks)
    if common.fits_vmem(plane):
        return plane.at[lane[..., None], cols].set(rows, mode="drop")
    lanes = jnp.broadcast_to(lane[..., None], cols.shape).reshape(-1)
    cols, rows = cols.reshape(-1), rows.reshape(-1)
    for k in range(0, lanes.shape[0], _SCATTER_CHUNK):
        part = slice(k, k + _SCATTER_CHUNK)
        plane = plane.at[lanes[part], cols[part]].set(rows[part],
                                                      mode="drop")
    return plane


# the most (lane, column) pairs one scatter into a large plane takes
# (`_rows_put`): compiled for a v5e, 256 update in place, 1,024 do not
_SCATTER_CHUNK = 256


def _rows_where(g, rows):
    """Row select under a guard: rows where g[...] else all-clear.  Works
    on boolean [..., W] and packed [..., L] rows alike."""
    return jnp.where(g[..., None], rows, jnp.zeros((), rows.dtype))


def _rows_any(rows):
    """Per-row any-flag-set; layout-independent (bool != 0 is identity)."""
    return jnp.any(rows != 0, axis=-1)


def plane_scatter_set(cfg: ProtoConfig, plane, lane, b, o):
    """Bulk flag OR over index triples (the write-combining bulk-store
    path, e.g. worksteal's enqueue scatter).  Triples must be distinct;
    out-of-range b drops.  Packed lanes accumulate by add, which equals OR
    exactly because each (lane, b, o) bit appears at most once."""
    c = _pl_col(cfg, b, o)
    if PACKED:
        pattern = jnp.zeros_like(plane).at[lane, c].add(
            bitmask.word_bit(o), mode="drop")
        return plane | pattern
    return plane.at[lane, c].set(True, mode="drop")


def _plane_bool(st: Store, plane) -> jnp.ndarray:
    n, nb, w = st.l1.shape
    rows = plane.reshape(n, nb, -1)
    return bitmask.unpack(rows, w) if PACKED else rows


def wvalid_bool(st: Store) -> jnp.ndarray:
    """Boolean [n_caches, n_blocks, W] view of wvalid (tests/debug)."""
    return _plane_bool(st, st.wvalid)


def wdirty_bool(st: Store) -> jnp.ndarray:
    """Boolean [n_caches, n_blocks, W] view of wdirty (tests/debug)."""
    return _plane_bool(st, st.wdirty)


# --------------------------------------------------------------------------
# batched block writeback / drain core  (önbellek-temizleme machinery, §2.2)
# --------------------------------------------------------------------------

def b_writeback(cfg: ProtoConfig, st: Store, blks, guard) -> Tuple[Store, jnp.ndarray]:
    """Write back one block per cache: cache i flushes the dirty words of
    block `blks[i]` (skip where guard[i] is False or blks[i] < 0).

    Cross-cache collisions on the same block merge per word, highest cache
    id winning (matches the serial ascending-j order; see module docstring).
    Returns (store', did [n_caches] f32 — 1.0 where any word moved)."""
    n, nb = cfg.n_caches, cfg.n_blocks
    blks = jnp.asarray(blks, jnp.int32)
    g = jnp.asarray(guard, bool) & (blks >= 0)
    safe = jnp.clip(blks, 0)
    lane = jnp.arange(n)
    rows = st.l1[lane, safe]                                # [n, W]
    dirty_rows = _rows_get(cfg, st.wdirty, lane, safe)      # [n, L]
    sel = _rows_where(g, dirty_rows)
    idx = jnp.where(g, safe, nb)
    l2 = drain_writeback(st.l2, rows, sel, idx)
    wdirty = _rows_put(cfg, st.wdirty, lane, idx, dirty_rows & ~sel)
    did = _rows_any(sel).astype(jnp.float32)
    tot = jnp.sum(did)
    c = st.counters
    c = c._replace(l2_accesses=c.l2_accesses + tot, wb_blocks=c.wb_blocks + tot)
    return st._replace(l2=l2, wdirty=wdirty, counters=c), did


def b_drain(cfg: ProtoConfig, st: Store, pos, charge) -> Tuple[Store, jnp.ndarray]:
    """Selective flush, all caches at once: cache i drains its sFIFO up to
    seq `pos[i]` (§4.2 step 3; pos<0 drains nothing, big pos drains all) and
    writes every drained block back to L2 in one masked scatter.

    `charge[i]` mirrors the serial engine's per-call accounting: a charged
    cache pays l2_lat + n_wb*wb_per_block even when it drained nothing.
    Returns (store', n_wb [n_caches] f32)."""
    n, nb, W = cfg.n_caches, cfg.n_blocks, cfg.block_words
    pos = jnp.asarray(pos, jnp.int32)
    f2, drained, _ = jax.vmap(sfifo.drain_upto)(st.fifo, pos)   # drained [n, cap]
    st = st._replace(fifo=f2)
    cap = drained.shape[1]
    g = drained >= 0
    safe = jnp.clip(drained, 0)
    crow = jnp.broadcast_to(jnp.arange(n)[:, None], (n, cap))
    rows = st.l1[crow, safe]                                    # [n, cap, W]
    held = _rows_get(cfg, st.wdirty, crow, safe)                # [n, cap, L]
    dirty_rows = _rows_where(g, held)
    idx = jnp.where(g, drained, nb)
    # cache-major flatten: later caches override earlier on (racy) collisions
    l2 = drain_writeback(st.l2, rows.reshape(n * cap, W),
                         dirty_rows.reshape(n * cap, cfg.meta_lanes),
                         idx.reshape(n * cap))
    wdirty = _rows_put(cfg, st.wdirty, crow, idx, held & ~dirty_rows)
    did = _rows_any(dirty_rows)                                 # [n, cap]
    n_wb = jnp.sum(did, axis=1).astype(jnp.float32)
    tot = jnp.sum(n_wb)
    p = cfg.params
    charge = jnp.asarray(charge, bool)
    cyc = jnp.where(charge, p.l2_lat + n_wb * p.wb_per_block, 0.0)
    c = st.counters
    c = c._replace(cycles=c.cycles + cyc,
                   l2_accesses=c.l2_accesses + tot,
                   wb_blocks=c.wb_blocks + tot)
    return st._replace(l2=l2, wdirty=wdirty, counters=c), n_wb


def b_invalidate(cfg: ProtoConfig, st: Store, mask) -> Store:
    """Whole-cache invalidate of every cache in `mask`: flush dirty first
    (§2.2), flash-invalidate, clear LR-TBL and PA-TBL (§4.4).

    The flash-invalidate is a masked select over the whole wvalid plane.
    A plane past the kernels' VMEM budget (`kernels/common.py`) makes that
    pass the cost of every local acquire, which invalidates only the
    lanes that promote, so there it runs only when some cache in `mask`
    invalidates.  With `mask` all-False the op is the identity either
    way (every counter adds +0.0), so both forms give the same bits."""
    mask = jnp.asarray(mask, bool)
    if common.fits_vmem(st.wvalid):
        return _invalidate(cfg, st, mask)
    return lax.cond(jnp.any(mask), lambda s: _invalidate(cfg, s, mask),
                    lambda s: s, st)


def _invalidate(cfg: ProtoConfig, st: Store, mask) -> Store:
    st, _ = b_drain(cfg, st, jnp.where(mask, _DRAIN_ALL, INVALID), mask)
    wvalid = jnp.where(mask[:, None],
                       jnp.zeros((), st.wvalid.dtype), st.wvalid)
    # geometry-deriving resets (full_like on the live tables): a custom
    # TableGeometry survives every invalidate
    lr = _mask_tree_rows(mask, jax.vmap(tables.lr_reset)(st.lr), st.lr)
    pa = _mask_tree_rows(mask, jax.vmap(tables.pa_reset)(st.pa), st.pa)
    p = cfg.params
    fmask = mask.astype(jnp.float32)
    c = st.counters
    c = c._replace(cycles=c.cycles + fmask * p.inv_flash,
                   inv_full=c.inv_full + jnp.sum(fmask),
                   inv_per_cache=c.inv_per_cache + fmask)
    return st._replace(wvalid=wvalid, lr=lr, pa=pa, counters=c)


def b_recover(cfg: ProtoConfig, st: Store, mask) -> Store:
    """Crash-recovery drain for every cache in `mask` (dead agents whose
    lease expired — elastic alive-set PR, DESIGN.md §10):

      1. reclaim the dead cache's dirty words: full drain + writeback via
         the existing flush machinery, then flash-invalidate and clear its
         LR/PA entries (`b_invalidate` — a dead agent must never again be
         probed as a sharer or promoted);
      2. force-release its leased sync word at L2 (ST 0) so waiting remote
         acquirers stop CAS-failing against a dead holder;
      3. clear the lease and count one recovery per reclaimed cache.

    With `mask` all-False this is value-preserving except for +0.0 counter
    adds, but the elastic schedulers additionally guard the call under a
    `lax.cond` so zero-churn runs never execute it at all."""
    mask = jnp.asarray(mask, bool)
    clock0 = st.counters.cycles
    la = st.lease.addr
    st = b_invalidate(cfg, st, mask)
    rel = mask & (la >= 0)
    st, _ = b_atomic_l2(cfg, st, rel, jnp.clip(la, 0),
                        _fill(cfg, 0), _fill(cfg, 0), False)
    st = lease_clear(st, mask)
    c = st.counters
    c = c._replace(recoveries=c.recoveries
                   + jnp.sum(mask.astype(jnp.float32)))
    st = st._replace(counters=c)
    # observability: one RECOVER event per reclaimed cache, stamped with
    # the leased address the drain force-released (identity when off)
    return obs.record_event(st, mask, obs.RECOVER, obs.OC_RECOVER,
                            addr=la, clock=clock0,
                            cycles=st.counters.cycles - clock0)


# --------------------------------------------------------------------------
# single-cache wrappers (classic API, used by tests + serial engine)
# --------------------------------------------------------------------------

def writeback_block(cfg: ProtoConfig, st: Store, cid, b, guard=True
                    ) -> Tuple[Store, jnp.ndarray]:
    """Write back the dirty words of block `b` of cache `cid` to L2.

    Returns (store', did_wb) where did_wb is 1.0 if any word moved.
    With guard=False or b<0 this is a no-op (used in padded batches)."""
    hot = _one_hot(cfg, cid)
    blks = jnp.where(hot, jnp.asarray(b, jnp.int32), INVALID)
    st, did = b_writeback(cfg, st, blks, hot & jnp.asarray(guard, bool))
    return st, jnp.sum(did)


def drain_fifo(cfg: ProtoConfig, st: Store, cid, pos) -> Tuple[Store, jnp.ndarray]:
    """Selective flush: drain cache `cid`'s sFIFO up to seq `pos` (§4.2 step
    3), writing each drained block back to L2.  pos<0 drains nothing;
    pos=+inf (use drain_fifo_all) drains everything.

    Returns (store', n_blocks_written)."""
    hot = _one_hot(cfg, cid)
    st, n_wb = b_drain(cfg, st, jnp.where(hot, jnp.asarray(pos, jnp.int32),
                                          INVALID), hot)
    return st, jnp.sum(n_wb)


def drain_fifo_all(cfg: ProtoConfig, st: Store, cid) -> Tuple[Store, jnp.ndarray]:
    return drain_fifo(cfg, st, cid, _DRAIN_ALL)


def invalidate_cache(cfg: ProtoConfig, st: Store, cid) -> Store:
    return b_invalidate(cfg, st, _one_hot(cfg, cid))


# --------------------------------------------------------------------------
# plain loads / stores through the cache — batched core + scalar wrappers
# --------------------------------------------------------------------------

def b_load(cfg: ProtoConfig, st: Store, active, addrs
           ) -> Tuple[Store, jnp.ndarray]:
    """Ordinary read, one per active cache.  L1 hit or fill-from-L2
    (read-allocate).  addrs [n_caches] must be valid even for inactive
    lanes (they are read but not written)."""
    n = cfg.n_caches
    active = jnp.asarray(active, bool)
    b, o = _split(cfg, addrs)
    lane = jnp.arange(n)
    # fused metadata front-end (kernels/fused_turn, DESIGN.md §12): the
    # pre-op valid bit (the L1 hit — also ops.load's OC_HIT/OC_MISS
    # classification) and the plane OR come from one plane_commit pass
    wvalid, _, hit, _ = plane_commit(st.wvalid, st.wdirty, b, o,
                                     active, None, lanes=cfg.meta_lanes)
    val = jnp.where(hit, st.l1[lane, b, o], st.l2[b, o])
    l1 = st.l1.at[lane, b, o].set(jnp.where(active, val, st.l1[lane, b, o]))
    p = cfg.params
    miss = active & ~hit
    c = st.counters
    c = c._replace(
        cycles=c.cycles + jnp.where(
            active, jnp.where(hit, p.l1_lat, p.l1_lat + p.l2_lat), 0.0),
        l1_hits=c.l1_hits + jnp.sum((active & hit).astype(jnp.float32)),
        l1_misses=c.l1_misses + jnp.sum(miss.astype(jnp.float32)),
        l2_accesses=c.l2_accesses + jnp.sum(miss.astype(jnp.float32)),
    )
    return st._replace(l1=l1, wvalid=wvalid, counters=c), val


def b_store_word(cfg: ProtoConfig, st: Store, active, addrs, vals,
                 force_tail=False) -> Tuple[Store, jnp.ndarray]:
    """Ordinary write (write-combining, no-allocate), one per active cache:
    update local copy, mark dirty, record the block in the sFIFO.  Capacity
    eviction writes the oldest block back (§2.2).
    Returns (store', fifo_pos_of_block [n_caches])."""
    n = cfg.n_caches
    active = jnp.asarray(active, bool)
    b, o = _split(cfg, addrs)
    lane = jnp.arange(n)
    l1 = st.l1.at[lane, b, o].set(
        jnp.where(active, jnp.asarray(vals, jnp.int32), st.l1[lane, b, o]))
    # both plane scatters fused into one plane_commit pass (the packed
    # Pallas kernel on TPU; the was_dirty pre-state it also returns is
    # ops.store's write-combining classification bit)
    wvalid, wdirty, _, _ = plane_commit(st.wvalid, st.wdirty, b, o,
                                        active, active,
                                        lanes=cfg.meta_lanes)
    st = st._replace(l1=l1, wvalid=wvalid, wdirty=wdirty)

    ft = jnp.broadcast_to(jnp.asarray(force_tail, bool), (n,))
    f2, evicted, pos = jax.vmap(sfifo.push)(st.fifo, b, ft)
    fifo = _mask_tree_rows(active, f2, st.fifo)
    evicted = jnp.where(active, evicted, INVALID)
    st = st._replace(fifo=fifo)
    st, n_evwb = b_writeback(cfg, st, evicted, evicted >= 0)
    p = cfg.params
    c = st.counters
    c = c._replace(cycles=c.cycles + jnp.where(
        active, p.l1_lat + n_evwb * p.wb_per_block, 0.0))
    return st._replace(counters=c), pos


def load(cfg: ProtoConfig, st: Store, cid, addr) -> Tuple[Store, jnp.ndarray]:
    """Ordinary read.  L1 hit or fill-from-L2 (read-allocate)."""
    st, vals = b_load(cfg, st, _one_hot(cfg, cid), _fill(cfg, addr))
    return st, vals[cid]


def store_word(cfg: ProtoConfig, st: Store, cid, addr, val, *, force_tail=False,
               guard=True) -> Tuple[Store, jnp.ndarray]:
    """Ordinary write through cache `cid`.  Returns (store', fifo_pos)."""
    hot = _one_hot(cfg, cid) & jnp.asarray(guard, bool)
    st, pos = b_store_word(cfg, st, hot, _fill(cfg, addr),
                           jnp.broadcast_to(jnp.asarray(val, jnp.int32),
                                            (cfg.n_caches,)),
                           force_tail)
    return st, pos[cid]


# --------------------------------------------------------------------------
# atomics
# --------------------------------------------------------------------------

def b_atomic_l1(cfg, st: Store, active, addrs, expect, new, is_cas
                ) -> Tuple[Store, jnp.ndarray]:
    """Atomic executed at the L1 (local scope), one per active cache.
    Returns (store', old_values [n_caches])."""
    st, cur = b_load(cfg, st, active, addrs)
    success = jnp.where(is_cas, cur == expect, True)
    st, _ = b_store_word(cfg, st, jnp.asarray(active, bool) & success, addrs,
                         jnp.where(success, new, cur))
    return st, cur


def b_atomic_l2(cfg, st: Store, active, addrs, expect, new, is_cas
                ) -> Tuple[Store, jnp.ndarray]:
    """Atomic executed at the L2 (global sync point), one per active cache.
    Active lanes must target pairwise-distinct words.  Returns (store', old)."""
    n, nb = cfg.n_caches, cfg.n_blocks
    active = jnp.asarray(active, bool)
    b, o = _split(cfg, addrs)
    lane = jnp.arange(n)
    cur = st.l2[b, o]
    success = jnp.where(is_cas, cur == expect, True)
    write = active & success
    l2 = st.l2.at[jnp.where(write, b, nb), o].set(
        jnp.where(success, jnp.asarray(new, jnp.int32), cur), mode="drop")
    # local copy of this word is no longer authoritative
    wvalid = _pl_clear(cfg, st.wvalid, lane, b, o, active)
    wdirty = _pl_clear(cfg, st.wdirty, lane, b, o, active)
    p = cfg.params
    fact = active.astype(jnp.float32)
    c = st.counters
    c = c._replace(cycles=c.cycles + fact * p.l2_lat,
                   l2_accesses=c.l2_accesses + jnp.sum(fact))
    return st._replace(l2=l2, wvalid=wvalid, wdirty=wdirty, counters=c), cur


def _atomic_l1(cfg, st: Store, cid, addr, expect, new, is_cas
               ) -> Tuple[Store, jnp.ndarray]:
    st, old = b_atomic_l1(cfg, st, _one_hot(cfg, cid), _fill(cfg, addr),
                          expect, new, is_cas)
    return st, old[cid]


def _atomic_l2(cfg, st: Store, cid, addr, expect, new, is_cas
               ) -> Tuple[Store, jnp.ndarray]:
    st, old = b_atomic_l2(cfg, st, _one_hot(cfg, cid), _fill(cfg, addr),
                          expect, new, is_cas)
    return st, old[cid]


# --------------------------------------------------------------------------
# scoped synchronization — local (work-group) scope, §4.1 / §4.4
# --------------------------------------------------------------------------

def local_release_b(cfg: ProtoConfig, st: Store, active, addrs, vals) -> Store:
    """atomic_ST_rel_wg for every active cache: push the sync block to the
    sFIFO tail, record (addr -> pos) in the LR-TBL, atomic executes in L1."""
    active = jnp.asarray(active, bool)
    st, pos = b_store_word(cfg, st, active, addrs, vals, force_tail=True)
    addrs32 = jnp.asarray(addrs, jnp.int32)
    lr2, ev_addr, ev_ptr = jax.vmap(tables.lr_insert)(st.lr, addrs32, pos)
    st = st._replace(lr=_mask_tree_rows(active, lr2, st.lr))
    # conservative overflow policy: an evicted LR record forces a drain up to
    # its recorded position so no release is silently lost (DESIGN.md §2)
    ev = jnp.where(active & (ev_addr >= 0), ev_ptr, INVALID)
    st, _ = b_drain(cfg, st, ev, active)
    p = cfg.params
    fact = active.astype(jnp.float32)
    c = st.counters
    c = c._replace(cycles=c.cycles + fact * p.tbl_lat,
                   local_syncs=c.local_syncs + jnp.sum(fact))
    return st._replace(counters=c)


def local_acquire_b(cfg: ProtoConfig, st: Store, active, addrs, expect, new
                    ) -> Tuple[Store, jnp.ndarray]:
    """atomic_CAS_acq_wg for every active cache (§4.4).  Lanes whose PA-TBL
    holds the address are promoted: full invalidate + CAS at L2.  Others do
    a cheap L1 CAS.  Both paths execute masked (no lane-level cond)."""
    active = jnp.asarray(active, bool)
    addrs32 = jnp.asarray(addrs, jnp.int32)
    promote = jax.vmap(tables.pa_contains)(st.pa, addrs32) & active
    st = b_invalidate(cfg, st, promote)
    st, old_l2 = b_atomic_l2(cfg, st, promote, addrs, expect, new, True)
    st, old_l1 = b_atomic_l1(cfg, st, active & ~promote, addrs, expect, new,
                             True)
    old = jnp.where(promote, old_l2, old_l1)
    p = cfg.params
    fact = active.astype(jnp.float32)
    c = st.counters
    c = c._replace(cycles=c.cycles + fact * p.tbl_lat,
                   local_syncs=c.local_syncs + jnp.sum(fact),
                   promotions=c.promotions
                   + jnp.sum(promote.astype(jnp.float32)))
    return st._replace(counters=c), old


def local_release(cfg: ProtoConfig, st: Store, cid, addr, val) -> Store:
    return local_release_b(cfg, st, _one_hot(cfg, cid), _fill(cfg, addr),
                           jnp.broadcast_to(jnp.asarray(val, jnp.int32),
                                            (cfg.n_caches,)))


def local_acquire(cfg: ProtoConfig, st: Store, cid, addr, expect, new
                  ) -> Tuple[Store, jnp.ndarray]:
    st, old = local_acquire_b(cfg, st, _one_hot(cfg, cid), _fill(cfg, addr),
                              expect, new)
    return st, old[cid]


# --------------------------------------------------------------------------
# global (device/cmp) scope — the heavyweight ops used by Baseline/Steal-only
# --------------------------------------------------------------------------

def global_release_b(cfg: ProtoConfig, st: Store, active, addrs, vals) -> Store:
    active = jnp.asarray(active, bool)
    st, _ = b_drain(cfg, st, jnp.where(active, _DRAIN_ALL, INVALID), active)
    st, _ = b_atomic_l2(cfg, st, active, addrs, 0, vals, False)
    c = st.counters
    return st._replace(counters=c._replace(
        global_syncs=c.global_syncs + jnp.sum(active.astype(jnp.float32))))


def global_acquire_b(cfg: ProtoConfig, st: Store, active, addrs, expect, new
                     ) -> Tuple[Store, jnp.ndarray]:
    active = jnp.asarray(active, bool)
    st = b_invalidate(cfg, st, active)
    st, old = b_atomic_l2(cfg, st, active, addrs, expect, new, True)
    c = st.counters
    return st._replace(counters=c._replace(
        global_syncs=c.global_syncs
        + jnp.sum(active.astype(jnp.float32)))), old


def global_release(cfg: ProtoConfig, st: Store, cid, addr, val) -> Store:
    return global_release_b(cfg, st, _one_hot(cfg, cid), _fill(cfg, addr),
                            jnp.broadcast_to(jnp.asarray(val, jnp.int32),
                                             (cfg.n_caches,)))


def global_acquire(cfg: ProtoConfig, st: Store, cid, addr, expect, new
                   ) -> Tuple[Store, jnp.ndarray]:
    st, old = global_acquire_b(cfg, st, _one_hot(cfg, cid), _fill(cfg, addr),
                               expect, new)
    return st, old[cid]


# --------------------------------------------------------------------------
# remote scope promotion — sRSP (§4.2, §4.3) and original RSP (§3) variants
# --------------------------------------------------------------------------

def _probe_and_selective_flush(cfg: ProtoConfig, st: Store, cid, addr) -> Store:
    """Broadcast a selective-flush(addr) probe via L2 to every L1 (§4.2 step
    2).  Only caches with an LR-TBL entry for addr drain — up to the
    recorded position — then move addr into their PA-TBL.  Everyone else
    NACKs.  One vmapped table sweep + one masked drain-scatter; no scan.

    Charging (DESIGN.md §2, refined): a NACKing cache pays only the LR-CAM
    lookup (`tbl_lat`) — the probe is *filtered*, its L1 is never busied —
    and the issuer collects the parallel NACKs in one hop instead of
    serializing a wait per cache.  Only actual sharers charge flush time
    (theirs, and the issuer's wait for their writebacks to land at L2).
    This is the paper's scalability claim made literal: the rare remote
    path costs O(actual sharers), not O(n_caches)."""
    p = cfg.params
    n = cfg.n_caches
    addr32 = jnp.asarray(addr, jnp.int32)
    ptrs = jax.vmap(tables.lr_lookup, in_axes=(0, None))(st.lr, addr32)
    others = jnp.arange(n) != jnp.asarray(cid, jnp.int32)
    has = (ptrs >= 0) & others
    st, n_wb = b_drain(cfg, st, jnp.where(has, ptrs, INVALID), has)
    lr2 = jax.vmap(tables.lr_remove, in_axes=(0, None))(st.lr, addr32)
    pa2 = jax.vmap(tables.pa_insert, in_axes=(0, None))(st.pa, addr32)
    st = st._replace(lr=_mask_tree_rows(has, lr2, st.lr),
                     pa=_mask_tree_rows(has, pa2, st.pa))
    wait = jnp.sum(jnp.where(has, p.l2_lat + n_wb * p.wb_per_block, 0.0)) + 1.0
    c = st.counters
    nack = jnp.where(others & ~has, p.tbl_lat, 0.0)
    c = c._replace(cycles=(c.cycles + nack).at[cid].add(
                       p.probe_lat + p.l2_lat + wait),
                   probes=c.probes + jnp.float32(n - 1))
    return st._replace(counters=c)


def srsp_remote_acquire(cfg: ProtoConfig, st: Store, cid, addr, expect, new
                        ) -> Tuple[Store, jnp.ndarray]:
    """atomic_CAS_rem_acq_cmp under sRSP (§4.2)."""
    own_ptr = tables.lr_lookup(_get(st.lr, cid), addr)

    def same_cu(s):
        # §4.2: local sharer on the same CU — both use this L1; no promotion,
        # just make the releases globally ordered and CAS at L2.
        s, _ = drain_fifo(cfg, s, cid, own_ptr)
        lr_c = tables.lr_remove(_get(s.lr, cid), addr)
        s = s._replace(lr=_set(s.lr, cid, lr_c))
        return _atomic_l2(cfg, s, cid, addr, expect, new, True)

    def cross_cu(s):
        s = _probe_and_selective_flush(cfg, s, cid, addr)
        s = invalidate_cache(cfg, s, cid)          # own global-acquire part
        return _atomic_l2(cfg, s, cid, addr, expect, new, True)

    st, old = lax.cond(own_ptr >= 0, same_cu, cross_cu, st)
    c = st.counters
    return st._replace(counters=c._replace(remote_syncs=c.remote_syncs + 1.0)), old


def srsp_remote_release(cfg: ProtoConfig, st: Store, cid, addr, val) -> Store:
    """atomic_ST_rem_rel_cmp under sRSP (§4.3): flush own cache, ST at L2,
    broadcast selective-invalidate(addr) -> every PA-TBL records addr.

    The broadcast's acks are collected in parallel (one hop for the
    issuer); each receiving cache pays only the PA-CAM insert (`tbl_lat`)
    — O(1) per cache, O(actual contention) for the issuer (DESIGN.md §2)."""
    p = cfg.params
    st, _ = drain_fifo_all(cfg, st, cid)
    st, _ = _atomic_l2(cfg, st, cid, addr, 0, val, False)
    pa = jax.vmap(tables.pa_insert, in_axes=(0, None))(
        st.pa, jnp.asarray(addr, jnp.int32))
    st = st._replace(pa=pa)
    c = st.counters
    others = jnp.arange(cfg.n_caches) != jnp.asarray(cid, jnp.int32)
    recv = jnp.where(others, p.tbl_lat, 0.0)
    c = c._replace(cycles=(c.cycles + recv).at[cid].add(p.probe_lat + 1.0),
                   probes=c.probes + jnp.float32(cfg.n_caches),
                   remote_syncs=c.remote_syncs + 1.0)
    return st._replace(counters=c)


def rsp_remote_acquire(cfg: ProtoConfig, st: Store, cid, addr, expect, new
                       ) -> Tuple[Store, jnp.ndarray]:
    """Original RSP (§3): promote by flushing EVERY L1 — cost scales with the
    number of caches.  The caller then invalidates its own L1 and CASes at
    L2.  The flush-all is one batched drain-scatter instead of a scan."""
    p = cfg.params
    n = cfg.n_caches
    st, n_wb = b_drain(cfg, st, jnp.full((n,), _DRAIN_ALL),
                       jnp.ones((n,), bool))
    wait = jnp.sum(p.l2_lat + n_wb * p.wb_per_block)  # serialized at L2 port
    c = st.counters
    c = c._replace(cycles=c.cycles.at[cid].add(p.probe_lat + wait),
                   probes=c.probes + jnp.float32(n - 1))
    st = st._replace(counters=c)
    st = invalidate_cache(cfg, st, cid)
    st, old = _atomic_l2(cfg, st, cid, addr, expect, new, True)
    c = st.counters
    return st._replace(counters=c._replace(remote_syncs=c.remote_syncs + 1.0)), old


def rsp_remote_release(cfg: ProtoConfig, st: Store, cid, addr, val) -> Store:
    """Original RSP: flush own, ST at L2, then INVALIDATE every L1 (flush-all
    + flash-invalidate each — the unscalable part)."""
    p = cfg.params
    n = cfg.n_caches
    st, _ = drain_fifo_all(cfg, st, cid)
    st, _ = _atomic_l2(cfg, st, cid, addr, 0, val, False)
    st = b_invalidate(cfg, st, jnp.ones((n,), bool))
    wait = jnp.float32(n) * p.l2_lat  # ack per cache through L2
    c = st.counters
    c = c._replace(cycles=c.cycles.at[cid].add(p.probe_lat + wait),
                   probes=c.probes + jnp.float32(n),
                   remote_syncs=c.remote_syncs + 1.0)
    return st._replace(counters=c)


# --------------------------------------------------------------------------
# batched remote twins — address-disjoint remote ops in one masked round
# --------------------------------------------------------------------------

def srsp_remote_acquire_b(cfg: ProtoConfig, st: Store, active, addrs, expect,
                          new) -> Tuple[Store, jnp.ndarray]:
    """Masked multi-issuer twin of `srsp_remote_acquire` (DESIGN.md §9).

    One sRSP remote acquire per active lane in a single set of masked
    array stages: all probe rounds share ONE vmapped LR sweep (an
    [n_caches, n_lanes] lookup matrix) and all selective flushes merge
    into one drain-scatter, instead of a serialized scan per issuer.

    Bitwise-equal to serializing the active lanes in ascending order iff
    the batch is **address-disjoint** (the caller's obligation, enforced
    by the harness co-scheduling rule): active addrs pairwise distinct,
    no cache holds LR state or dirty words for more than one batch
    address, and no batch issuer holds LR state or dirty words for
    another issuer's address.  A one-hot batch is trivially
    address-disjoint and equals the scalar op exactly
    (tests/test_ops.py)."""
    p = cfg.params
    n = cfg.n_caches
    active = jnp.asarray(active, bool)
    addrs32 = jnp.asarray(addrs, jnp.int32)
    lanes = jnp.arange(n, dtype=jnp.int32)

    # §4.2 fork, per lane: a local sharer on the same CU skips promotion
    own_ptr = jax.vmap(tables.lr_lookup)(st.lr, addrs32)
    same = active & (own_ptr >= 0)
    cross = active & (own_ptr < 0)

    # same-CU lanes: make own releases globally ordered, then CAS at L2
    st, _ = b_drain(cfg, st, jnp.where(same, own_ptr, INVALID), same)
    lr_rm = jax.vmap(tables.lr_remove)(st.lr, addrs32)
    st = st._replace(lr=_mask_tree_rows(same, lr_rm, st.lr))

    # cross-CU lanes: one probe round for the whole batch
    ptrs = jax.vmap(lambda t: jax.vmap(
        lambda a: tables.lr_lookup(t, a))(addrs32))(st.lr)   # [cache, lane]
    probed = cross[None, :] & (lanes[:, None] != lanes[None, :])
    has = (ptrs >= 0) & probed
    sharer = jnp.any(has, axis=1)
    drain_pos = jnp.max(jnp.where(has, ptrs, INVALID), axis=1)
    st, n_wb = b_drain(cfg, st, jnp.where(sharer, drain_pos, INVALID), sharer)
    # move each sharer's (unique, under disjointness) probed addr LR -> PA
    shared_addr = addrs32[jnp.argmax(has, axis=1)]
    lr2 = jax.vmap(tables.lr_remove)(st.lr, shared_addr)
    pa2 = jax.vmap(tables.pa_insert)(st.pa, shared_addr)
    st = st._replace(lr=_mask_tree_rows(sharer, lr2, st.lr),
                     pa=_mask_tree_rows(sharer, pa2, st.pa))
    # charging (DESIGN.md §2): a NACKing cache pays one CAM lookup per
    # probe it filtered; each issuer waits for its own sharers only
    nack = jnp.sum((probed & ~has).astype(jnp.float32), axis=1) * p.tbl_lat
    wait = jnp.sum(jnp.where(has, (p.l2_lat + n_wb * p.wb_per_block)[:, None],
                             0.0), axis=0) + 1.0
    c = st.counters
    c = c._replace(
        cycles=c.cycles + nack
        + jnp.where(cross, p.probe_lat + p.l2_lat + wait, 0.0),
        probes=c.probes
        + jnp.sum(cross.astype(jnp.float32)) * jnp.float32(n - 1))
    st = st._replace(counters=c)

    # own global-acquire part for promoting lanes, then CAS at L2 for all
    st = b_invalidate(cfg, st, cross)
    st, old = b_atomic_l2(cfg, st, active, addrs32, expect, new, True)
    c = st.counters
    return st._replace(counters=c._replace(
        remote_syncs=c.remote_syncs
        + jnp.sum(active.astype(jnp.float32)))), old


def srsp_remote_release_b(cfg: ProtoConfig, st: Store, active, addrs,
                          vals) -> Store:
    """Masked multi-issuer twin of `srsp_remote_release` (DESIGN.md §9):
    all active lanes flush their own caches in one drain-scatter and ST at
    L2 in one masked atomic; the selective-invalidate broadcasts run as an
    ascending-lane scan (PA ages are insertion-order sensitive), matching
    the serialized order exactly.  Same address-disjointness obligation as
    `srsp_remote_acquire_b`."""
    p = cfg.params
    n = cfg.n_caches
    active = jnp.asarray(active, bool)
    addrs32 = jnp.asarray(addrs, jnp.int32)
    st, _ = b_drain(cfg, st, jnp.where(active, DRAIN_ALL, INVALID), active)
    st, _ = b_atomic_l2(cfg, st, active, addrs32, 0, vals, False)

    def ins(pa, xi):
        a, on = xi
        pa2 = jax.vmap(tables.pa_insert, in_axes=(0, None))(pa, a)
        return jax.tree.map(lambda nw, od: jnp.where(on, nw, od), pa2, pa), None

    pa, _ = lax.scan(ins, st.pa, (addrs32, active))
    st = st._replace(pa=pa)
    tot = jnp.sum(active.astype(jnp.float32))
    recv = (tot - active.astype(jnp.float32)) * p.tbl_lat
    c = st.counters
    c = c._replace(cycles=c.cycles + recv
                   + jnp.where(active, p.probe_lat + 1.0, 0.0),
                   probes=c.probes + tot * jnp.float32(n),
                   remote_syncs=c.remote_syncs + tot)
    return st._replace(counters=c)


# --------------------------------------------------------------------------
# protocol bundles
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Protocol:
    """A registered scope-parametric op table (DESIGN.md §9).

    The paper's interface is an ISA of *scoped* atomics
    (`atomic_*_loc/rem/glob`, §2.1); a Protocol is one translation of
    that ISA onto the memory system — per scope, an acquire/release pair
    in two forms: a **masked multi-agent** op (`*_b`, active-mask
    signature — what both schedulers and `repro.core.ops` dispatch into)
    and the scalar single-cache reference the protocol unit tests pin
    against.  The mapping is the protocol's whole identity: `global`
    realizes even LOCAL-scope requests as heavyweight global sync
    (the paper's baseline), `local` realizes even REMOTE-scope requests
    as unsafe local sync (the staleness demo), and rsp/srsp differ only
    in their REMOTE realization (flush-everyone vs selective promotion).

    Capability declaration: `acquire_rem_b`/`release_rem_b` are the
    *batched address-disjoint remote twins*.  A protocol that carries
    them (`remote_batchable`) lets the harness co-schedule
    non-conflicting remote turns in one trip; protocols whose remote op
    inherently touches every cache (original RSP) declare None and their
    remote turns serialize, which is exactly the paper's scalability
    distinction surfacing as an API capability.

    Instances are looked up by name through the registry
    (`get_protocol` / `protocols()`); `register_protocol` adds one.
    Derived (e.g. fault-injected) protocols come from
    `workloads.faults.derive` and stay unregistered."""
    name: str
    # local (work-group) scope — the cheap common-case ops
    acquire_loc_b: callable   # (cfg, st, active, addrs, expect, new) -> (st, old)
    release_loc_b: callable   # (cfg, st, active, addrs, vals) -> st
    acquire_loc: callable     # (cfg, st, cid, addr, expect, new) -> (st, old)
    release_loc: callable     # (cfg, st, cid, addr, val) -> st
    # remote scope — the rare cross-agent ops (scalar = serializing ref)
    acquire_rem: callable
    release_rem: callable
    # global (device) scope — the heavyweight everyone-pays ops
    acquire_glob_b: callable
    release_glob_b: callable
    acquire_glob: callable
    release_glob: callable
    # batched address-disjoint remote twins (capability; None = cannot)
    acquire_rem_b: callable = None
    release_rem_b: callable = None
    # crash-recovery drain (capability; None = dead holders never recover):
    # (cfg, st, mask) -> st — reclaim dirty words, force-release leased
    # sync words, invalidate LR/PA of every masked (dead) cache.
    recover_b: callable = None
    # crash fault injection (faults.crash_holding_lock): (victim, at) —
    # once cycles[victim] >= at, the victim's *synchronization*
    # instructions (and their lease bookkeeping) stop executing, modeling
    # death mid-turn inside a critical section: the lock stays held, the
    # turn's data writes stay stranded dirty in its L1.  None = healthy.
    crash_gate: tuple = None

    @property
    def remote_batchable(self) -> bool:
        """True when the protocol can run address-disjoint remote ops of
        several agents in one masked round (DESIGN.md §9)."""
        return self.acquire_rem_b is not None \
            and self.release_rem_b is not None


class UnknownNameError(KeyError, ValueError):
    """Registry miss.  Subclasses BOTH KeyError (it is a mapping miss)
    and ValueError (what the pre-registry `runner()`/`WorkStealSim`
    checks raised), so existing handlers of either keep working."""


class Registry(dict):
    """name -> object mapping whose misses name every registered key —
    the `PROTOCOLS[...]`-style bare KeyError replacement (ISSUE 4)."""

    def __init__(self, kind: str):
        super().__init__()
        self.kind = kind

    def __missing__(self, key):
        raise UnknownNameError(f"unknown {self.kind} {key!r}; "
                               f"registered: {sorted(self)}")


# The protocol registry.  Indexing an unknown name raises with the list
# of registered names; `PROTOCOLS` stays importable for existing callers.
PROTOCOLS = Registry("protocol")


def register_protocol(proto: Protocol) -> Protocol:
    """Register `proto` under its name (usable as a decorator-style
    wrapper: ``SRSP = register_protocol(Protocol(...))``)."""
    PROTOCOLS[proto.name] = proto
    return proto


def protocols() -> tuple:
    """Names of every registered protocol, sorted."""
    return tuple(sorted(PROTOCOLS))


def get_protocol(name: str) -> Protocol:
    """Registered protocol by name; unknown names raise with the
    registered list."""
    return PROTOCOLS[name]


SRSP = register_protocol(Protocol(
    name="srsp",
    acquire_loc_b=local_acquire_b, release_loc_b=local_release_b,
    acquire_loc=local_acquire, release_loc=local_release,
    acquire_rem=srsp_remote_acquire, release_rem=srsp_remote_release,
    acquire_glob_b=global_acquire_b, release_glob_b=global_release_b,
    acquire_glob=global_acquire, release_glob=global_release,
    acquire_rem_b=srsp_remote_acquire_b,
    release_rem_b=srsp_remote_release_b,
    recover_b=b_recover))

# Original RSP's remote promotion flushes/invalidates EVERY cache, so two
# remote turns never commute: no batched remote twin, by declaration.
RSP = register_protocol(Protocol(
    name="rsp",
    acquire_loc_b=local_acquire_b, release_loc_b=local_release_b,
    acquire_loc=local_acquire, release_loc=local_release,
    acquire_rem=rsp_remote_acquire, release_rem=rsp_remote_release,
    acquire_glob_b=global_acquire_b, release_glob_b=global_release_b,
    acquire_glob=global_acquire, release_glob=global_release,
    recover_b=b_recover))

# Baseline: every scope realized as global sync — remote twins are the
# plain masked global ops (trivially address-disjoint-batchable).
GLOBAL = register_protocol(Protocol(
    name="global",
    acquire_loc_b=global_acquire_b, release_loc_b=global_release_b,
    acquire_loc=global_acquire, release_loc=global_release,
    acquire_rem=global_acquire, release_rem=global_release,
    acquire_glob_b=global_acquire_b, release_glob_b=global_release_b,
    acquire_glob=global_acquire, release_glob=global_release,
    acquire_rem_b=global_acquire_b, release_rem_b=global_release_b,
    recover_b=b_recover))

# NOT remote-safe — realizes REMOTE scope as local sync (staleness demo).
LOCAL_ONLY = register_protocol(Protocol(
    name="local",
    acquire_loc_b=local_acquire_b, release_loc_b=local_release_b,
    acquire_loc=local_acquire, release_loc=local_release,
    acquire_rem=local_acquire, release_rem=local_release,
    acquire_glob_b=global_acquire_b, release_glob_b=global_release_b,
    acquire_glob=global_acquire, release_glob=global_release,
    acquire_rem_b=local_acquire_b, release_rem_b=local_release_b,
    recover_b=b_recover))
