"""Scope-parametric synchronization ISA — one masked op surface.

The paper's interface (§2.1) is an ISA of scoped atomics:
`atomic_CAS_acq_wg`, `atomic_ST_rem_rel_cmp`, … — scope is an *operand*
of the instruction, not a property of the caller.  This module is that
surface for the simulated machine: four masked multi-agent entry points

    acquire(proto, cfg, st, active, addrs, expect, new, scope=LOCAL)
    release(proto, cfg, st, active, addrs, vals,        scope=LOCAL)
    load(cfg, st, active, addrs,                        scope=LOCAL)
    store(cfg, st, active, addrs, vals,                 scope=LOCAL)

where `active` is an [n_caches] participation mask and `scope` is either
a static Python int or a per-agent {LOCAL, REMOTE, GLOBAL} int array —
one call can carry a mixed-scope bundle, e.g. owners acquiring at LOCAL
scope while a thief acquires at REMOTE scope in the same instruction.

Dispatch (DESIGN.md §9) goes into the *protocol's* per-scope op table —
the scenario mapping (baseline realizes LOCAL as global sync, scope_only
realizes REMOTE as unsafe local sync) lives entirely in the registered
`Protocol` object, never in workload code.  REMOTE-scope lanes use the
protocol's batched address-disjoint remote twin when it declares one
(`Protocol.remote_batchable`); otherwise they fall back to the scalar
serializing op, which supports at most ONE active remote lane per call —
the harness never co-schedules remote turns without the capability.

Data ops (`load`/`store`) accept `scope` for ISA uniformity but are
scope-invariant in this memory model: ordinary accesses always route
through the issuing agent's L1 (write-combining, no-allocate) and the
scope of the *synchronization* ops alone decides when that data becomes
visible remotely.  That asymmetry is the paper's point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import protocol as P
from repro.core import tables
from repro.obs import trace as T

# Scope codes of the ISA.  LOCAL is wg ("local") scope, and both REMOTE
# and GLOBAL are realizations of cmp ("global") scope visibility
# (core/scopes.py): GLOBAL pays the full flush/invalidate on every op,
# REMOTE is the paper's promoted flavor — cheap until a remote sharer
# actually appears.  They are distinct ISA operands because protocols
# translate them differently.
LOCAL = 0    # own-L1 synchronization (atomic_*_wg)
REMOTE = 1   # promoted cross-agent synchronization (atomic_*_rem_cmp)
GLOBAL = 2   # heavyweight everyone-pays synchronization (atomic_*_cmp)

SCOPES = (LOCAL, REMOTE, GLOBAL)
SCOPE_NAMES = {LOCAL: "loc", REMOTE: "rem", GLOBAL: "glob"}


def _check_static(scope: int) -> None:
    if scope not in SCOPE_NAMES:
        raise ValueError(f"unknown scope {scope!r}; "
                         f"valid: {sorted(SCOPE_NAMES)} "
                         f"(ops.LOCAL / ops.REMOTE / ops.GLOBAL)")


def _bcast(x, n: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32), (n,))


def _scope_label(scope) -> str:
    # unknown static ints still reach _check_static's ValueError below
    return SCOPE_NAMES.get(scope, "invalid") if isinstance(scope, int) \
        else "mixed"


def _acquire_outcome(cfg, st: P.Store, addrs, scope):
    """Pre-dispatch trace outcome per lane (only traced when tracing is
    on): LOCAL lanes promote iff their PA-TBL holds the address, REMOTE
    lanes probe iff any OTHER cache's LR-TBL records it (else the probe
    round all-NACKs), GLOBAL lanes always pay the full invalidate."""
    n = cfg.n_caches
    promote = jax.vmap(tables.pa_contains)(st.pa, addrs)
    ptrs = jax.vmap(lambda t: jax.vmap(
        lambda a: tables.lr_lookup(t, a))(addrs))(st.lr)   # [cache, lane]
    others = jnp.arange(n)[:, None] != jnp.arange(n)[None, :]
    sharer = jnp.any((ptrs >= 0) & others, axis=0)
    scope_arr = jnp.broadcast_to(jnp.asarray(scope, jnp.int32), (n,))
    loc = jnp.where(promote, T.OC_PROMOTE, T.OC_HIT)
    rem = jnp.where(sharer, T.OC_PROBE, T.OC_NACK)
    return jnp.where(scope_arr == LOCAL, loc,
                     jnp.where(scope_arr == REMOTE, rem, T.OC_GLOBAL))


def _release_outcome(cfg, scope):
    scope_arr = jnp.broadcast_to(jnp.asarray(scope, jnp.int32),
                                 (cfg.n_caches,))
    return jnp.where(scope_arr == LOCAL, T.OC_HIT,
                     jnp.where(scope_arr == REMOTE, T.OC_PROBE,
                               T.OC_GLOBAL))


def _gate_crashed(proto: P.Protocol, st: P.Store, active):
    """Crash-fault lane kill (Protocol.crash_gate): once the victim's
    clock passes the crash time, its *release* instructions never execute
    — including their lease clears, so the lease taken at acquire
    survives for the recovery drain to act on.  Acquires stay live: the
    dying agent keeps entering critical sections it can never exit, which
    is exactly the die-holding-lock state.  Static no-op when the
    protocol is healthy."""
    if proto.crash_gate is None:
        return active
    victim, at = proto.crash_gate
    n = st.counters.cycles.shape[0]
    dying = (jnp.arange(n, dtype=jnp.int32) == victim) \
        & (st.counters.cycles >= jnp.float32(at))
    return jnp.asarray(active, bool) & ~dying


def _acquire_rem(proto: P.Protocol, cfg, st, rem, addrs, expect, new):
    """REMOTE-scope acquire lanes: batched twin when the protocol declares
    one, else the scalar serializing op (at most one active lane)."""
    if proto.acquire_rem_b is not None:
        return proto.acquire_rem_b(cfg, st, rem, addrs, expect, new)
    n = cfg.n_caches
    rem = jnp.asarray(rem, bool)
    addrs32, expect, new = (_bcast(a, n) for a in (addrs, expect, new))
    cid = jnp.argmax(rem).astype(jnp.int32)

    def do(s):
        return proto.acquire_rem(cfg, s, cid, addrs32[cid], expect[cid],
                                 new[cid])

    def skip(s):
        return s, jnp.int32(0)

    st, old_c = lax.cond(jnp.any(rem), do, skip, st)
    lanes = jnp.arange(n, dtype=jnp.int32)
    return st, jnp.where(lanes == cid, old_c, jnp.int32(0))


def _release_rem(proto: P.Protocol, cfg, st, rem, addrs, vals):
    if proto.release_rem_b is not None:
        return proto.release_rem_b(cfg, st, rem, addrs, vals)
    n = cfg.n_caches
    rem = jnp.asarray(rem, bool)
    addrs32, vals = (_bcast(a, n) for a in (addrs, vals))
    cid = jnp.argmax(rem).astype(jnp.int32)
    return lax.cond(
        jnp.any(rem),
        lambda s: proto.release_rem(cfg, s, cid, addrs32[cid], vals[cid]),
        lambda s: s, st)


def acquire(proto: P.Protocol, cfg: P.ProtoConfig, st: P.Store, active,
            addrs, expect, new, scope=LOCAL):
    """Scoped acquire, one per active agent: CAS(expect -> new) on
    `addrs[i]` at `scope[i]` for every active lane i, through `proto`'s
    translation of that scope.  Returns (store', old [n_caches]);
    inactive lanes' old values are unspecified.

    A static int `scope` compiles to exactly the one table entry; a
    per-agent array dispatches each scope class masked (REMOTE lanes
    must be address-disjoint — the harness's obligation)."""
    addrs, expect, new = (_bcast(a, cfg.n_caches)
                          for a in (addrs, expect, new))
    traced = T.enabled(st.trace)
    if traced:
        clock0 = st.counters.cycles
        outcome = _acquire_outcome(cfg, st, addrs, scope)
    with jax.named_scope(f"ops.acquire.{_scope_label(scope)}"):
        if isinstance(scope, int):
            _check_static(scope)
            if scope == LOCAL:
                st, old = proto.acquire_loc_b(cfg, st, active, addrs,
                                              expect, new)
            elif scope == GLOBAL:
                st, old = proto.acquire_glob_b(cfg, st, active, addrs,
                                               expect, new)
            else:
                st, old = _acquire_rem(proto, cfg, st, active, addrs,
                                       expect, new)
        else:
            scope_a = jnp.asarray(scope, jnp.int32)
            active = jnp.asarray(active, bool)
            loc = active & (scope_a == LOCAL)
            rem = active & (scope_a == REMOTE)
            glob = active & (scope_a == GLOBAL)
            st, old_l = proto.acquire_loc_b(cfg, st, loc, addrs, expect,
                                            new)
            st, old_g = proto.acquire_glob_b(cfg, st, glob, addrs, expect,
                                             new)
            st, old_r = _acquire_rem(proto, cfg, st, rem, addrs, expect,
                                     new)
            old = jnp.where(rem, old_r, jnp.where(glob, old_g, old_l))
    # clock-stamped lease bookkeeping (crash recovery, DESIGN.md §10):
    # pure metadata, charges nothing — zero-churn schedules unchanged
    st = P.lease_stamp(st, active, addrs)
    if traced:
        st = T.record_op(st, active, T.ACQUIRE, scope, addrs, clock0,
                         outcome)
    return st, old


def release(proto: P.Protocol, cfg: P.ProtoConfig, st: P.Store, active,
            addrs, vals, scope=LOCAL):
    """Scoped release, one per active agent: store `vals[i]` to
    `addrs[i]` with release semantics at `scope[i]`.  Returns store'."""
    addrs, vals = (_bcast(a, cfg.n_caches) for a in (addrs, vals))
    active = _gate_crashed(proto, st, active)
    traced = T.enabled(st.trace)
    if traced:
        clock0 = st.counters.cycles
    with jax.named_scope(f"ops.release.{_scope_label(scope)}"):
        if isinstance(scope, int):
            _check_static(scope)
            if scope == LOCAL:
                st = proto.release_loc_b(cfg, st, active, addrs, vals)
            elif scope == GLOBAL:
                st = proto.release_glob_b(cfg, st, active, addrs, vals)
            else:
                st = _release_rem(proto, cfg, st, active, addrs, vals)
        else:
            scope_a = jnp.asarray(scope, jnp.int32)
            active = jnp.asarray(active, bool)
            st = proto.release_loc_b(cfg, st, active & (scope_a == LOCAL),
                                     addrs, vals)
            st = proto.release_glob_b(cfg, st, active & (scope_a == GLOBAL),
                                      addrs, vals)
            st = _release_rem(proto, cfg, st, active & (scope_a == REMOTE),
                              addrs, vals)
    # lease bookkeeping mirror of `acquire` (pure metadata)
    st = P.lease_clear(st, active)
    if traced:
        st = T.record_op(st, active, T.RELEASE, scope, addrs, clock0,
                         _release_outcome(cfg, scope))
    return st


def _l1_state(cfg, st, addrs, plane):
    """Pre-op L1 metadata bit per lane at `addrs` (trace classification)."""
    b, o = P._split(cfg, _bcast(addrs, cfg.n_caches))
    return P._pl_get(cfg, plane, jnp.arange(cfg.n_caches), b, o)


def load(cfg: P.ProtoConfig, st: P.Store, active, addrs, scope=LOCAL):
    """Ordinary scoped read, one per active agent (scope-invariant: data
    always routes through the issuing agent's L1 — module docstring)."""
    if isinstance(scope, int):
        _check_static(scope)
    traced = T.enabled(st.trace)
    if traced:
        clock0 = st.counters.cycles
        hit = _l1_state(cfg, st, addrs, st.wvalid)
    st, val = P.b_load(cfg, st, active, addrs)
    if traced:
        st = T.record_op(st, active, T.LOAD, scope, addrs, clock0,
                         jnp.where(hit, T.OC_HIT, T.OC_MISS))
    return st, val


def store(cfg: P.ProtoConfig, st: P.Store, active, addrs, vals,
          scope=LOCAL, *, force_tail=False):
    """Ordinary scoped write, one per active agent (scope-invariant)."""
    if isinstance(scope, int):
        _check_static(scope)
    traced = T.enabled(st.trace)
    if traced:
        clock0 = st.counters.cycles
        # write-combining: a "hit" merges into an already-dirty word
        combined = _l1_state(cfg, st, addrs, st.wdirty)
    st, pos = P.b_store_word(cfg, st, active, addrs, vals, force_tail)
    if traced:
        st = T.record_op(st, active, T.STORE, scope, addrs, clock0,
                         jnp.where(combined, T.OC_HIT, T.OC_MISS))
    return st, pos
