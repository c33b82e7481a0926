"""Pallas TPU kernels for the fused batched trip (DESIGN.md §12).

Two kernels, matching the two fusion surfaces of `ref.py`:

  * `trip_plan_pallas` — the whole select-commuting-pops decision in ONE
    kernel invocation: masked first-argmin reductions, the clock-lex
    batch rule with the future-first-remote fence, and (when the
    workload declares the remote-batching capability) the n×n address
    dedup of the co-schedulable remote batch.  Everything lives in VMEM
    as [1, n] rows (plus [n, 1] column copies for the dedup matrix);
    reductions are branch-free min/where chains so the VPU never leaves
    the kernel for a scheduling decision.

  * `plane_commit_pallas` — the wvalid/wdirty plane update of
    `protocol.b_store_word`/`b_load` for every cache lane in one vector
    pass: the planes are the Store's lane-dense [n, nb * L] rows (int32
    inside the kernel), each lane's target flag is a (column, bit) pair,
    and a `broadcasted_iota` compare builds the pattern plane that both
    reads the pre-op bits and ORs the new ones (`core/bitmask.py`
    semantics; no unpacked plane ever materializes).  Both planes are
    input/output-aliased.

TPU tiling: every block is a whole array, so the (8, 128) block rule
holds at any n, nb and W, alone and under `jax.vmap` (which adds a
squeezed leading grid axis).

The jnp references in `ref.py` are the CPU fast path AND the oracle the
interpret-mode unit tests pin these kernels against
(tests/test_kernels.py) — same discipline as `selective_flush`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.fused_turn.ref import TripPlan

# ref.BIG as a Python scalar: Pallas kernels cannot capture device
# constants, and a literal folds into the kernel body
BIG = 3e38


def _first_min(vals, mask, idx, n):
    """(min, first-argmin-index) over masked lanes — first index holding
    the min, 0 when the mask is empty (matching `jnp.argmin` over a
    BIG-filled row, the `_batched_trip` convention; assumes real clocks
    stay < BIG, which f32 cycle accumulators do)."""
    m = jnp.min(jnp.where(mask, vals, BIG))
    j = jnp.min(jnp.where(mask & (vals == m), idx, n))
    return m, jnp.where(j == n, 0, j).astype(jnp.int32)


def _plan_kernel(clocks_ref, can_l_ref, can_r_ref, bound_ref, raddr_ref,
                 hor_ref, clocks_c_ref, can_r_c_ref, raddr_c_ref,
                 lmask_ref, rmask_ref, wg_ref, *, remote_cap):
    n = clocks_ref.shape[-1]
    idx = lax.broadcasted_iota(jnp.int32, (1, n), 1)
    clocks = clocks_ref[...]
    can_l = can_l_ref[...] != 0
    can_r = can_r_ref[...] != 0
    hor = hor_ref[0, 0]

    cand = can_l | can_r
    _, wg = _first_min(clocks, cand, idx, n)
    ms, js = _first_min(clocks, can_r, idx, n)
    fence = jnp.min(jnp.where(can_l, clocks + bound_ref[...], BIG))
    lex = (clocks < ms) | ((clocks == ms) & (idx < js))
    batch = can_l & lex & (clocks <= fence) & (clocks < hor)
    any_b = jnp.any(batch)
    lmask = batch | (~any_b & (idx == wg) & can_l)

    if remote_cap:
        ml, jl = _first_min(clocks, can_l, idx, n)

        def first_remote(clk, cr, ix):
            lexr = (clk < ml) | ((clk == ml) & (ix < jl))
            return cr & lexr & (clk < hor)

        # the n x n dedup is laid out [other j (sublanes), self i (lanes)]
        # from the [n, 1] column copies of the inputs, so no in-kernel
        # transpose is needed and the any() over j lands back on a row
        r0 = first_remote(clocks, can_r, idx)
        clk_c = clocks_c_ref[...]
        idx_c = lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        r0_c = first_remote(clk_c, can_r_c_ref[...] != 0, idx_c)
        collide = r0_c & r0 & (raddr_c_ref[...] == raddr_ref[...])
        earlier = (clk_c < clocks) | ((clk_c == clocks) & (idx_c < idx))
        lost = jnp.max((collide & earlier).astype(jnp.int32), axis=0,
                       keepdims=True)
        rmask = r0 & (lost == 0)
    else:
        rmask = jnp.zeros((1, n), bool)

    lmask_ref[...] = lmask.astype(jnp.int32)
    rmask_ref[...] = rmask.astype(jnp.int32)
    wg_ref[...] = jnp.full((1, 1), wg, jnp.int32)


@functools.partial(jax.jit, static_argnames=("remote_cap", "interpret"))
def trip_plan_pallas(clocks, can_l, can_r, bound, raddr, horizon,
                     *, remote_cap: bool, interpret: bool = False
                     ) -> TripPlan:
    """One-kernel batched-trip plan; bitwise `ref.trip_plan_ref`.

    Scalar `horizon` must be a concrete value (pass BIG for the plain
    engines' no-fence trips); `raddr` is ignored when remote_cap=False
    (pass zeros).  The remote dedup also reads [n, 1] column copies of
    clocks/can_r/raddr (an XLA reshape outside the kernel)."""
    n = clocks.shape[0]
    row = lambda x, dt: jnp.asarray(x, dt).reshape(1, n)
    col = lambda x, dt: jnp.asarray(x, dt).reshape(n, 1)
    hor = jnp.asarray(horizon, jnp.float32).reshape(1, 1)
    lmask, rmask, wg = pl.pallas_call(
        functools.partial(_plan_kernel, remote_cap=remote_cap),
        out_shape=(jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        interpret=interpret,
    )(row(clocks, jnp.float32), row(can_l, jnp.int32), row(can_r, jnp.int32),
      row(bound, jnp.float32), row(raddr, jnp.int32), hor,
      col(clocks, jnp.float32), col(can_r, jnp.int32), col(raddr, jnp.int32))
    return TripPlan(lmask=lmask[0] != 0, rmask=rmask[0] != 0, wg=wg[0, 0])


def _commit_kernel(wv_ref, wd_ref, col_ref, bit_ref, sv_ref, sd_ref,
                   wv_out, wd_out, wasv_ref, wasd_ref):
    """Both planes as lane-dense [n, C] rows, one row per cache lane:
    lane i's flag is bit pattern `bit[i]` of column `col[i]`.  The
    pattern plane is a broadcasted-iota compare, so every lane commits
    in the same vector pass (no grid, no per-lane DMA)."""
    cols = lax.broadcasted_iota(jnp.int32, wv_ref.shape, 1)
    pattern = jnp.where(cols == col_ref[...], bit_ref[...], 0)
    rv = wv_ref[...]
    rd = wd_ref[...]
    hit = lambda plane: jnp.max(((plane & pattern) != 0).astype(jnp.int32),
                                axis=1, keepdims=True)
    wasv_ref[...] = hit(rv)
    wasd_ref[...] = hit(rd)
    wv_out[...] = rv | jnp.where(sv_ref[...] != 0, pattern, 0)
    wd_out[...] = rd | jnp.where(sd_ref[...] != 0, pattern, 0)


@functools.partial(jax.jit, static_argnames=("lanes", "interpret"))
def plane_commit_pallas(wvalid, wdirty, b, o, set_valid, set_dirty,
                        *, lanes: int, interpret: bool = False):
    """Fused metadata-plane commit; bitwise `ref.plane_commit_ref`.

    wvalid/wdirty [n, nb * lanes] uint32 packed or bool, the Store's
    lane-dense planes; b/o [n] i32; set_valid/set_dirty [n] bool.  The
    planes enter the kernel as int32 rows of the same shape (a bitcast or
    a 0/1 cast): lane i's flag sits in column b*lanes + (o >> 5) at bit
    1 << (o & 31) (packed, `core/bitmask.py` semantics) or in column
    b*lanes + o at bit 1 (boolean).  Whole-array VMEM blocks, both
    planes aliased in place.  Returns (wvalid', wdirty', was_valid,
    was_dirty)."""
    n, cols = wvalid.shape
    packed = wvalid.dtype != jnp.bool_
    b32 = jnp.clip(jnp.asarray(b, jnp.int32), 0, cols // lanes - 1)
    o32 = jnp.asarray(o, jnp.int32)
    if packed:
        col = b32 * lanes + (o32 >> 5)
        bit = lax.bitcast_convert_type(
            jnp.uint32(1) << (o32.astype(jnp.uint32) & jnp.uint32(31)),
            jnp.int32)
        to_rows = lambda p: lax.bitcast_convert_type(p, jnp.int32)  # noqa: E731
        back = lambda r: lax.bitcast_convert_type(r, jnp.uint32)  # noqa: E731
    else:
        col = b32 * lanes + o32
        bit = jnp.ones((n,), jnp.int32)
        to_rows = lambda p: p.astype(jnp.int32)  # noqa: E731
        back = lambda r: r != 0  # noqa: E731
    lane_col = lambda x: jnp.asarray(x, jnp.int32).reshape(n, 1)  # noqa: E731
    wv2, wd2, wasv, wasd = pl.pallas_call(
        _commit_kernel,
        out_shape=(jax.ShapeDtypeStruct((n, cols), jnp.int32),
                   jax.ShapeDtypeStruct((n, cols), jnp.int32),
                   jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n, 1), jnp.int32)),
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(to_rows(wvalid), to_rows(wdirty), lane_col(col), lane_col(bit),
      lane_col(set_valid), lane_col(set_dirty))
    return back(wv2), back(wd2), wasv[:, 0] != 0, wasd[:, 0] != 0
