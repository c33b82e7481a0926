"""Public entry points for the fused-turn kernels (DESIGN.md §12).

Same dispatch discipline as `selective_flush.drain_writeback`: the Pallas
kernels run when the process-wide `kernel_mode()` says so (TPU, or forced
interpret for debugging); on CPU the jnp references in `ref.py` are both
the fast path and the oracle — interpret-mode Pallas is reserved for the
kernel equivalence tests, never a silent benchmark path
(`kernels/common.py`).

`plane_commit` takes the kernel on TPU for every call shape: both
metadata layouts (packed uint32 and the boolean REPRO_NO_PACK=1 planes)
and the `b_load` shape (`set_dirty=None`, served as an all-False dirty
mask, which leaves wdirty bitwise unchanged).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.fused_turn import ref
from repro.kernels.fused_turn.kernel import (plane_commit_pallas,
                                             trip_plan_pallas)
from repro.kernels.fused_turn.ref import BIG, TripPlan  # noqa: F401


def trip_plan(clocks, can_l, can_r, bound, raddr, horizon, *,
              remote_cap: bool, use_pallas: bool | None = None,
              interpret: bool | None = None) -> TripPlan:
    """One batched-trip scheduling decision (select-commuting-pops +
    remote co-schedule dedup) — `ref.trip_plan_ref`'s contract.  `raddr`
    may be None when remote_cap=False; `horizon` None means no event
    fence (the plain engines)."""
    if use_pallas is None:
        use_pallas = common.use_pallas()
    if not use_pallas:
        return ref.trip_plan_ref(clocks, can_l, can_r, bound,
                                 raddr if remote_cap else None, horizon)
    if interpret is None:
        interpret = common.interpret()
    if raddr is None:
        raddr = jnp.zeros_like(clocks, jnp.int32)
    hor = BIG if horizon is None else horizon
    return trip_plan_pallas(clocks, can_l, can_r, bound, raddr, hor,
                            remote_cap=remote_cap, interpret=interpret)


def plane_commit(wvalid, wdirty, b, o, set_valid, set_dirty, *, lanes: int,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None):
    """Fused metadata-plane front-end: pre-op wvalid/wdirty bit reads +
    per-lane flag OR, one pass over both planes, which are lane-dense
    [n, nb * lanes] (`lanes` = the layout's columns per block).  Returns
    (wvalid', wdirty', was_valid, was_dirty) — see `ref.plane_commit_ref`.
    `set_dirty=None` is the `b_load` shape: the reference skips the
    wdirty update statically, the kernel ORs an all-False mask — wdirty
    comes back bitwise unchanged either way."""
    if use_pallas is None:
        use_pallas = common.use_pallas()
    if not use_pallas:
        return ref.plane_commit_ref(wvalid, wdirty, b, o,
                                    set_valid, set_dirty, lanes)
    if interpret is None:
        interpret = common.interpret()
    if set_dirty is None:
        set_dirty = jnp.zeros_like(jnp.asarray(set_valid, bool))
    return plane_commit_pallas(wvalid, wdirty, b, o, set_valid, set_dirty,
                               lanes=lanes, interpret=interpret)
