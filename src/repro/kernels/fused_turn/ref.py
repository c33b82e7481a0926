"""Pure-jnp oracle for the fused-turn megakernel (DESIGN.md §12).

Two fusion surfaces, each with the exact semantics of the code it
replaces — the reference IS the pre-fusion `_batched_trip` path, so the
cross-engine equivalence suites pin the kernel against the very math the
batched engine has always run:

  * `trip_plan_ref` — the select-commuting-pops decision of
    `harness._batched_trip`: local batch mask (clock-lex against every
    remote candidate + the future-first-remote fence), the co-schedulable
    remote batch (clock-lex against every local candidate, address
    dedup), and the serial-fallback agent.  The formulas are transcribed
    verbatim; only the *execution* structure differs (the fused engine
    runs ONE masked `local_turn` covering both the batch and the
    serial-local fallback — the equivalence argument is in DESIGN.md
    §12).
  * `plane_commit_ref` — the metadata-plane front-end of
    `protocol.b_load`/`b_store_word`: read the pre-op wvalid/wdirty bits
    (the trace classification of `ops.load`/`ops.store` — OC_HIT vs
    OC_MISS) and OR in the new bits, both planes in one pass.  Packed
    (uint32 word-bitmask, DESIGN.md §8) and boolean layouts are told
    apart by dtype, like `selective_flush.drain_writeback`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core import bitmask

BIG = jnp.float32(3e38)


class TripPlan(NamedTuple):
    """One batched-trip scheduling decision (all lanes, no state)."""
    lmask: jnp.ndarray   # [n] bool  agents whose local turn executes
    rmask: jnp.ndarray   # [n] bool  co-schedulable remote batch (only
    #                      consulted when lmask is all-False)
    wg: jnp.ndarray      # []  i32   serial-fallback agent (first argmin)


def trip_plan_ref(clocks, can_l, can_r, bound, raddr, horizon) -> TripPlan:
    """The `_batched_trip` selection math, verbatim.

    clocks [n] f32 per-agent cycle clocks; can_l/can_r [n] bool readiness;
    bound [n] f32 `remote_bound` lower bounds; raddr [n] i32 next-remote
    target addresses (pass None when the workload has no remote-batching
    capability — the dedup math is skipped statically); horizon [] f32 or
    None — the elastic event fence (None compiles the masking away).

    lmask = batch                      when the batch is nonempty
          = one_hot(wg) & can_l[wg]    otherwise (the serial local case)
    rmask = the address-deduped remote batch (raw — DESIGN.md §12 proves
            it is empty whenever lmask is nonempty, so no extra masking)
    """
    n = clocks.shape[0]
    wgs = jnp.arange(n, dtype=jnp.int32)
    cand = can_l | can_r
    masked = jnp.where(cand, clocks, BIG)
    wg = jnp.argmin(masked).astype(jnp.int32)
    sclk = jnp.where(can_r, clocks, BIG)
    ms = jnp.min(sclk)
    js = jnp.argmin(sclk).astype(jnp.int32)
    fence = jnp.min(jnp.where(can_l, clocks + bound, BIG))
    lex = (clocks < ms) | ((clocks == ms) & (wgs < js))
    batch = can_l & lex & (clocks <= fence)
    if horizon is not None:
        batch = batch & (clocks < horizon)
    any_b = jnp.any(batch)
    # serial fallback folded into the SAME masked local turn: when the
    # batch is empty and the first-argmin candidate has a local turn,
    # one-hot it (≡ `_serial_turn`'s local branch — DESIGN.md §12)
    lmask = batch | (~any_b & can_l[wg] & (wgs == wg))

    if raddr is None:
        rmask = jnp.zeros((n,), bool)
        return TripPlan(lmask=lmask, rmask=rmask, wg=wg)

    # remote candidates preceding every local candidate (lex mirrored),
    # minus address collisions with an earlier (clock, idx) lane —
    # `_batched_trip.do_remote_or_serial`, verbatim
    lclk = jnp.where(can_l, clocks, BIG)
    ml = jnp.min(lclk)
    jl = jnp.argmin(lclk).astype(jnp.int32)
    lexr = (clocks < ml) | ((clocks == ml) & (wgs < jl))
    r0 = can_r & lexr
    if horizon is not None:
        r0 = r0 & (clocks < horizon)
    collide = r0[:, None] & r0[None, :] & (raddr[:, None] == raddr[None, :])
    earlier = (clocks[None, :] < clocks[:, None]) \
        | ((clocks[None, :] == clocks[:, None]) & (wgs[None, :] < wgs[:, None]))
    rmask = r0 & ~jnp.any(collide & earlier, axis=1)
    return TripPlan(lmask=lmask, rmask=rmask, wg=wg)


def plane_commit_ref(wvalid, wdirty, b, o, set_valid, set_dirty, lanes):
    """Fused wvalid/wdirty front-end: pre-op bit reads + per-lane flag OR,
    both planes in one pass.

    wvalid/wdirty [n, nb * lanes], uint32 packed or bool, stored
    lane-dense: lane w of block b is column b * lanes + w (DESIGN.md §8);
    b/o [n] i32 per-lane (block, word-offset) targets; set_valid/set_dirty
    [n] bool OR masks (set_dirty=None skips the wdirty update statically —
    the `b_load` shape).  Returns (wvalid', wdirty', was_valid,
    was_dirty): the was_* bits are the PRE-update flags — exactly the
    OC_HIT/OC_MISS (load) and write-combining (store) classification bits
    of `ops._l1_state`.  (lane, b) pairs are distinct by construction
    (lane is the cache id), so the scatters are safe."""
    n = wvalid.shape[0]
    lane = jnp.arange(n)
    b = jnp.asarray(b, jnp.int32)
    if wvalid.dtype != jnp.bool_:
        col = b * lanes + bitmask.word_index(o)
        bit = bitmask.word_bit(o)
        sv = jnp.where(jnp.asarray(set_valid, bool), bit, jnp.uint32(0))
        sd = None if set_dirty is None else \
            jnp.where(jnp.asarray(set_dirty, bool), bit, jnp.uint32(0))
        test = lambda words: (words & bit) != 0  # noqa: E731
    else:
        col = b * lanes + jnp.asarray(o, jnp.int32)
        sv, sd, test = set_valid, set_dirty, lambda words: words  # noqa: E731
    wv = wvalid[lane, col]
    wd = wdirty[lane, col]
    wvalid = wvalid.at[lane, col].set(wv | sv)
    if sd is not None:
        wdirty = wdirty.at[lane, col].set(wd | sd)
    return wvalid, wdirty, test(wv), test(wd)
