"""sRSP-style asymmetric cross-pod synchronization (the paper's technique as
a framework feature — DESIGN.md §2).

Scope mapping: within-pod gradient sync is "local scope" (cheap, every
step, implicit in pjit).  Cross-pod sync is deferred local-SGD style; each
pod is the *local sharer* of the parameter blocks its batch actually
touched.  A remote acquire (periodic global sync, eval, checkpoint,
elastic rejoin) performs the *selective flush*: only blocks dirtied since
the last release are compacted (Pallas selective_flush = the sFIFO drain)
and exchanged over the 'pod' axis, instead of a full-parameter all-reduce
(the RSP-baseline analogue).  A PA-TBL-style promotion mask marks blocks
that must be re-fetched from global scope on next use.

Where it wins: sparsely-updated banks — MoE expert weights (each pod's
batch routes to a subset of experts) and embedding rows.  Dense layers mark
everything dirty and selective sync degrades gracefully to a full sync
(tracked and reported, like RSP == sRSP when every cache line is dirty).

All ops are pure and run under shard_map over the 'pod' mesh axis; the same
code drives the byte-accounting benchmark (benchmarks/delta_sync_bench.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import compress as CMP
from repro.kernels.selective_flush.ref import (selective_flush_ref,
                                               selective_apply_ref)
from repro.kernels.selective_flush import selective_flush


class BankSyncState(NamedTuple):
    """Per-pod state for one parameter bank [n_blocks, block_size]."""
    ref: jnp.ndarray          # snapshot at last global sync ("L2 copy")
    ef: jnp.ndarray           # error-feedback residual (compression)
    promoted: jnp.ndarray     # [n_blocks] bool — PA-TBL analogue
    syncs: jnp.ndarray        # [] i32 global syncs performed
    bytes_selective: jnp.ndarray  # [] f32 bytes a selective sync moved
    bytes_full: jnp.ndarray       # [] f32 bytes a full sync would move


def bank_init(bank: jnp.ndarray) -> BankSyncState:
    n, b = bank.shape
    z = jnp.float32(0.0)
    return BankSyncState(ref=bank.astype(jnp.float32),
                         ef=jnp.zeros((n, b), jnp.float32),
                         promoted=jnp.zeros((n,), bool),
                         syncs=jnp.int32(0),
                         bytes_selective=z, bytes_full=z)


def dirty_mask(bank: jnp.ndarray, st: BankSyncState, tol: float = 0.0
               ) -> jnp.ndarray:
    d = jnp.abs(bank.astype(jnp.float32) - st.ref)
    return jnp.max(d, axis=-1) > tol


def selective_global_sync(bank: jnp.ndarray, st: BankSyncState,
                          *, axis_name: str = "pod", max_dirty: int,
                          use_int8: bool = False, use_pallas: bool = False
                          ) -> Tuple[jnp.ndarray, BankSyncState]:
    """The remote acquire: union dirty set across pods, flush only those
    blocks, average deltas, promote.  Runs inside shard_map over `axis_name`.

    bank [n_blocks, bs] — this pod's current values."""
    n_blocks, bs = bank.shape
    n_pods = jax.lax.psum(1, axis_name)
    delta = bank.astype(jnp.float32) - st.ref

    mine = dirty_mask(bank, st)
    union = jax.lax.psum(mine.astype(jnp.int32), axis_name) > 0   # probe bcast
    # deterministic shared index list (same on every pod): first max_dirty
    # union-dirty block ids, -1 padded.  Overflow -> sticky full sync.
    order = jnp.argsort(~union, stable=True)          # dirty ids first
    idx = jnp.where(jnp.arange(n_blocks) < max_dirty, order, -1)[:max_dirty]
    idx = jnp.where(union[jnp.clip(idx, 0, n_blocks - 1)], idx, -1)
    overflow = jnp.sum(union) > max_dirty

    flush = selective_flush if use_pallas else (
        lambda b, i: selective_flush_ref(b, i))
    if use_int8:
        q, scale, ef_state = CMP.compress_blocks(
            delta, CMP.EFState(st.ef), idx)
        q_sum = jax.lax.psum(dequant := CMP.dequantize_int8(q, scale),
                             axis_name)
        payload = q_sum / n_pods
        ef = ef_state.err
        moved = q.size * 1 + scale.size * 4
    else:
        payload = jax.lax.psum(flush(delta, idx), axis_name) / n_pods
        ef = st.ef
        moved = payload.size * 4

    # fall back to full sync on overflow (conservative, like LR-TBL eviction)
    full_mean = st.ref + jax.lax.psum(delta, axis_name) / n_pods
    merged = selective_apply_ref(st.ref, st.ref[jnp.clip(idx, 0, n_blocks - 1)]
                                 + payload, idx)
    new_bank = jnp.where(overflow, full_mean, merged)
    moved_bytes = jnp.where(overflow, jnp.float32(delta.size * 4),
                            jnp.float32(moved + n_blocks // 8))

    new_st = BankSyncState(
        ref=new_bank,
        ef=ef,
        promoted=union,  # PA-TBL: these blocks were remotely written
        syncs=st.syncs + 1,
        bytes_selective=st.bytes_selective + moved_bytes,
        bytes_full=st.bytes_full + jnp.float32(delta.size * 4),
    )
    return new_bank.astype(bank.dtype), new_st


def full_global_sync(bank: jnp.ndarray, st: BankSyncState,
                     *, axis_name: str = "pod"
                     ) -> Tuple[jnp.ndarray, BankSyncState]:
    """RSP-baseline analogue: always move the whole bank."""
    n_pods = jax.lax.psum(1, axis_name)
    delta = bank.astype(jnp.float32) - st.ref
    new_bank = st.ref + jax.lax.psum(delta, axis_name) / n_pods
    sz = jnp.float32(delta.size * 4)
    return new_bank.astype(bank.dtype), st._replace(
        ref=new_bank, syncs=st.syncs + 1,
        bytes_selective=st.bytes_selective + sz,
        bytes_full=st.bytes_full + sz)


def make_pod_sync(mesh: Mesh, n_blocks: int, block_size: int,
                  *, max_dirty: int, use_int8: bool = False,
                  selective: bool = True):
    """shard_map-wrapped sync over the 'pod' axis: bank/state are per-pod
    (sharded on a leading pod dim)."""
    fn = functools.partial(
        selective_global_sync if selective else full_global_sync,
        axis_name="pod",
        **({"max_dirty": max_dirty, "use_int8": use_int8} if selective else {}))

    state_specs = BankSyncState(
        ref=P("pod", None, None), ef=P("pod", None, None),
        promoted=P("pod", None), syncs=P("pod"),
        bytes_selective=P("pod"), bytes_full=P("pod"))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("pod", None, None), state_specs),
        out_specs=(P("pod", None, None), state_specs), check_vma=False)
    def sync(bank_stacked, st_stacked):
        bank = bank_stacked[0]
        st = jax.tree.map(lambda x: x[0], st_stacked)
        new_bank, new_st = fn(bank, st)
        return (new_bank[None],
                jax.tree.map(lambda x: x[None], new_st))

    return sync
