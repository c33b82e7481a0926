"""Workload-agnostic asymmetric-sharing harness (DESIGN.md §7).

The paper evaluates sRSP on exactly one driver — Cederman–Tsigas
work-stealing — but the protocol's claim is about *asymmetric sharing* in
general: many cheap local-scope operations on privately-owned data,
punctuated by rare remote-scope operations that observe another agent's
state.  This module extracts the two schedulers that used to live inside
`core/worksteal.py` into a generic pair that any workload can bind
against, so new sharing shapes (producer/consumer drains, reader-heavy
locks, directory lookups, …) plug in as declarative specs instead of
forked engines.

A workload is a `Workload` — a frozen, hashable bundle of module-level
functions plus its static config and protocol op-table:

  can_local(wl, s, *ops)     -> [n] bool  agents with a commuting turn ready
  can_remote(wl, s, *ops)    -> [n] bool  agents whose next turn conflicts
  local_turn(wl, s, mask, *ops) -> s'     execute one turn for every masked
                                          agent at once, via the masked
                                          multi-cache protocol ops
  remote_turn(wl, s, wg, *ops) -> s'      one serializing turn for agent wg
                                          (must internally no-op when
                                          can_remote[wg] is False)
  remote_bound(wl, s, *ops)  -> [n] f32   lower bound on extra cycles before
                                          agent i's *next* remote turn (BIG
                                          for agents that never go remote)
  live(wl, s, *ops)          -> bool      while-loop guard (work remains and
                                          the event budget isn't exhausted)

The state `s` is an arbitrary NamedTuple whose first field is the protocol
`Store` (the harness reads per-agent clocks from
`s.store.counters.cycles`); everything else — queue occupancy, quotas,
bookkeeping ground truth for the workload's self-check — is workload
private.

Scheduling contract (identical to the work-steal engines it was extracted
from; proofs in DESIGN.md §4/§7):

* `run_serial` is the reference: one turn per `lax.while_loop` trip, the
  candidate with the smallest cycle clock acts next, ties to the lowest
  index.  A candidate with a local turn runs `local_turn` with a one-hot
  mask; otherwise `remote_turn`.
* `run_batched` executes every local turn that *provably precedes* —
  in the serial order — every remote turn that could observe it: batch
  agent i iff `can_local[i]` and its clock beats every currently
  remote-capable clock (argmin-index tie-break) and every future
  first-remote lower bound `clock[j] + remote_bound[j]`.  Local turns of
  distinct agents must commute (pairwise-disjoint L2 words — that is the
  workload's declarative obligation), so the batched schedule is a
  reordering of the serial one within commuting spans and final states
  are bitwise identical.
* when a workload declares the remote-batching capability
  (`remote_turn_b` + `remote_addr`) AND its protocol declares batched
  address-disjoint remote twins (`Protocol.remote_batchable`),
  `run_batched` additionally co-schedules non-conflicting remote turns:
  all remote-capable agents that precede every local candidate
  (clock-lex) and target pairwise-distinct addresses run in ONE masked
  remote turn (DESIGN.md §9 has the commutation rule and its hazard
  argument).  Protocols without the capability — original RSP, whose
  remote op flushes every cache — serialize exactly as before.

Buffer donation (ROADMAP open item: n_wgs=256 is memory-bound): the
harness entry points donate the state argument, so XLA may alias the
~O(n_caches · n_words) Store buffers through the jit boundary instead of
copying them per call.  Set REPRO_NO_DONATE=1 before import to disable
(used by the sweep's before/after measurement).  Callers must not reuse a
state object after passing it in.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import protocol as P
from repro.kernels import fused_turn
from repro.obs import spans
from repro.obs import trace as T

BIG = jnp.float32(3e38)

# Op-name scopes of every engine (metadata only; the benchmark's trace
# readers find them in the compiled program): TRIP_SCOPE holds a
# while-loop trip's condition and body, PLAN_SCOPE the trip plan, the
# ops that decide who acts this trip and in which batch.
TRIP_SCOPE, PLAN_SCOPE = "harness.trip", "harness.plan"

# scenario -> protocol name, subsystem-wide (the paper's §5 mapping;
# worksteal additionally flags which scenarios steal).  A registry: an
# unknown scenario or protocol name raises with the registered list.
SCENARIO_PROTOCOLS = P.Registry("scenario")


def register_scenario(name: str, proto_name: str) -> None:
    """Map a scenario name onto a registered protocol name."""
    if proto_name not in P.PROTOCOLS:
        raise KeyError(f"cannot register scenario {name!r}: unknown "
                       f"protocol {proto_name!r}; registered protocols: "
                       f"{sorted(P.PROTOCOLS)}")
    SCENARIO_PROTOCOLS[name] = proto_name


def scenarios() -> tuple:
    """Names of every registered scenario, sorted."""
    return tuple(sorted(SCENARIO_PROTOCOLS))


register_scenario("baseline", "global")
register_scenario("scope_only", "local")  # NOT remote-safe — staleness demo
register_scenario("steal_only", "global")
register_scenario("rsp", "rsp")
register_scenario("srsp", "srsp")


def resolve_proto(scenario: str, proto: P.Protocol = None) -> P.Protocol:
    """Scenario's protocol table, overridable for fault injection.
    Unknown scenario names raise with the registered list."""
    if proto is not None:
        return proto
    return P.get_protocol(SCENARIO_PROTOCOLS[scenario])


class Bench(NamedTuple):
    """Uniform handle the sweep/tests drive a workload through."""
    wl: "Workload"
    state: Any              # initial state (fresh per engine run — donation!)
    ops: tuple              # extra operand arrays for the scheduler fns
    check: Callable         # (final_state) -> dict (ok, check_fails, ...)


def make_bench(cfg, build_workload, init_state, self_check, scenario,
               seed=0, proto: P.Protocol = None) -> Bench:
    """The standard build() body shared by the jnp-pure workloads."""
    wl = build_workload(cfg, resolve_proto(scenario, proto))
    return Bench(wl, init_state(wl, seed), (),
                 lambda final: self_check(wl, final))

# Donation toggle is read once at import: the jitted entry points below are
# module-level, so the flag must be process-wide (the sweep A/B-tests it in
# subprocesses).
DONATE = os.environ.get("REPRO_NO_DONATE", "0") != "1"
_don = {"donate_argnums": (1,)} if DONATE else {}

# Fused-trip escape hatch (DESIGN.md §12), read once at import like the
# donation/packing flags: REPRO_NO_FUSE=1 makes `engine="fused"` execute
# the plain `_batched_trip` path (the jnp reference the fused plan is
# pinned against), so a kernel suspect can be excluded in one env var
# without touching any engine-name plumbing.
FUSE = os.environ.get("REPRO_NO_FUSE", "0") != "1"


@dataclasses.dataclass(frozen=True)
class Workload:
    """Declarative workload spec bound to a config and a protocol.

    Instances are jit static arguments: keep `cfg` a frozen dataclass and
    every function a module-level def so two equal-valued Workloads hash
    equal and share compiled schedulers.

    `remote_turn_b`/`remote_addr` are the optional remote-batching
    capability (DESIGN.md §9): `remote_turn_b(wl, s, mask, *ops)`
    executes one remote turn for every masked agent at once (through the
    protocol's batched remote twins), and `remote_addr(wl, s, *ops)`
    names the L2 sync address agent i's next remote turn will target.
    Declaring them asserts the workload's remote-commutation obligations
    (§9): remote turns of distinct agents on distinct addresses must be
    pairwise commuting, with target choice and capability derived from
    per-agent-private bookkeeping.  The harness only co-schedules when
    the bound protocol also declares `remote_batchable`."""
    name: str
    cfg: Any                    # frozen workload config (hashable)
    proto: P.Protocol           # registered scope-parametric op table
    has_remote: bool            # False => every turn commutes (static)
    can_local: Callable
    can_remote: Callable
    local_turn: Callable
    remote_turn: Callable
    remote_bound: Callable
    live: Callable
    remote_turn_b: Callable = None   # masked multi-agent remote turn
    remote_addr: Callable = None     # [n] i32 next-remote target address
    # elastic alive-set hooks (DESIGN.md §10).  `retire(wl, s, dead, *ops)
    # -> s'` forgives a dying agent's remaining obligations in the
    # bookkeeping ground truth (quotas := done) so the run terminates and
    # the self-check scores survivors only; it must be a bitwise identity
    # when `dead` is all-False.  `admit(wl, s, joined, *ops) -> s'`
    # optionally assigns new work to re-admitted agents.
    retire: Callable = None          # masked retirement bookkeeping
    admit: Callable = None           # masked (re-)admission bookkeeping


def one_hot(n: int, wg) -> jnp.ndarray:
    return jnp.arange(n, dtype=jnp.int32) == jnp.asarray(wg, jnp.int32)


def charge(st: P.Store, mask, cycles) -> P.Store:
    """Add per-agent compute cycles outside the protocol ops (task work)."""
    c = st.counters
    return st._replace(counters=c._replace(
        cycles=c.cycles + jnp.where(mask, jnp.float32(cycles), 0.0)))


def _note_turn(s0, s1):
    """Close one scheduler trip: count it in the trace's `trips` (always,
    traced or not) and, when tracing is on, bucket each agent's charged
    cycles across the trip into the per-turn latency histogram
    (DESIGN.md §11).  The count is engine-dependent; the bitwise
    contracts compare through `T.strip`, which zeroes it."""
    st = T.record_trip(s1.store)
    if T.enabled(st.trace):
        st = T.record_turn(st, s0.store.counters.cycles)
    return s1._replace(store=st)


def _while(cond, body, state):
    """`lax.while_loop` with the condition and body under TRIP_SCOPE."""

    def scoped(fn):
        def run(s):
            with jax.named_scope(TRIP_SCOPE):
                return fn(s)
        return run

    return lax.while_loop(scoped(cond), scoped(body), state)


def _serial_turn(wl: Workload, s, wg, can_l, ops):
    n = s.store.counters.cycles.shape[0]
    hot = one_hot(n, wg)
    return lax.cond(
        can_l[wg],
        lambda st: wl.local_turn(wl, st, hot, *ops),
        lambda st: wl.remote_turn(wl, st, wg, *ops),
        s)


@partial(jax.jit, static_argnums=(0,), **_don)
def run_serial(wl: Workload, state, *ops):
    """Event-driven reference scheduler: smallest clock acts next."""

    def cond(s):
        return wl.live(wl, s, *ops)

    def body(s):
        with jax.named_scope(PLAN_SCOPE):
            can_l = wl.can_local(wl, s, *ops)
            can_r = wl.can_remote(wl, s, *ops)
            cand = can_l | can_r
            clocks = jnp.where(cand, s.store.counters.cycles, BIG)
            wg = jnp.argmin(clocks).astype(jnp.int32)
        return _note_turn(s, _serial_turn(wl, s, wg, can_l, ops))

    return _while(cond, body, state)


def _batched_trip(wl: Workload, s, can_l, can_r, horizon, ops):
    """One `run_batched` trip, given the trip's readiness masks.

    `horizon` is the elastic engines' event fence: a turn at clock >=
    horizon must not execute this trip (a churn event or lease expiry
    fires first — DESIGN.md §10).  The plain engines pass None and the
    masking disappears at trace time, keeping their schedule untouched."""
    n = s.store.counters.cycles.shape[0]
    wgs = jnp.arange(n, dtype=jnp.int32)
    remote_cap = (wl.remote_turn_b is not None
                  and wl.remote_addr is not None
                  and wl.proto.remote_batchable)
    clocks_all = s.store.counters.cycles
    if not wl.has_remote:
        # nothing ever conflicts: every ready agent acts each trip
        if horizon is not None:
            with jax.named_scope(PLAN_SCOPE):
                can_l = can_l & (clocks_all < horizon)
        return wl.local_turn(wl, s, can_l, *ops)
    with jax.named_scope(PLAN_SCOPE):
        cand = can_l | can_r
        clocks = jnp.where(cand, clocks_all, BIG)
        wg_min = jnp.argmin(clocks).astype(jnp.int32)
        sclk = jnp.where(can_r, clocks_all, BIG)
        ms = jnp.min(sclk)
        js = jnp.argmin(sclk).astype(jnp.int32)
        fence = jnp.min(jnp.where(can_l,
                                  clocks_all + wl.remote_bound(wl, s, *ops),
                                  BIG))
        lex = (clocks_all < ms) | ((clocks_all == ms) & (wgs < js))
        batch = can_l & lex & (clocks_all <= fence)
        if horizon is not None:
            batch = batch & (clocks_all < horizon)

    def do_batch(st):
        return wl.local_turn(wl, st, batch, *ops)

    def do_serial(st):
        return _serial_turn(wl, st, wg_min, can_l, ops)

    if remote_cap:
        def do_remote_or_serial(st):
            with jax.named_scope(PLAN_SCOPE):
                # remote candidates preceding every local candidate's
                # clock (same lex pattern as the local batch, mirrored)
                lclk = jnp.where(can_l, clocks_all, BIG)
                ml = jnp.min(lclk)
                jl = jnp.argmin(lclk).astype(jnp.int32)
                lexr = (clocks_all < ml) | ((clocks_all == ml) & (wgs < jl))
                r0 = can_r & lexr
                if horizon is not None:
                    r0 = r0 & (clocks_all < horizon)
                raddr = wl.remote_addr(wl, st, *ops)
                # address dedup: drop a lane iff an earlier (clock, idx)
                # candidate targets the same address
                collide = r0[:, None] & r0[None, :] \
                    & (raddr[:, None] == raddr[None, :])
                earlier = (clocks_all[None, :] < clocks_all[:, None]) \
                    | ((clocks_all[None, :] == clocks_all[:, None])
                       & (wgs[None, :] < wgs[:, None]))
                rbatch = r0 & ~jnp.any(collide & earlier, axis=1)
            return lax.cond(
                jnp.any(rbatch),
                lambda s2: wl.remote_turn_b(wl, s2, rbatch, *ops),
                do_serial, st)

        fallback = do_remote_or_serial
    else:
        fallback = do_serial

    return lax.cond(jnp.any(batch), do_batch, fallback, s)


@partial(jax.jit, static_argnums=(0,), **_don)
def run_batched(wl: Workload, state, *ops):
    """Vectorized scheduler: every provably-commuting local turn per trip.

    Batch rule (DESIGN.md §4): agent i's local turn joins the batch iff
    its clock precedes (a) every currently remote-capable agent's clock,
    with the serial argmin-index tie-break, and (b) every future
    first-remote lower bound clock[j] + remote_bound[j].

    Remote co-scheduling (DESIGN.md §9): when the local batch is empty
    and both the workload (`remote_turn_b`/`remote_addr`) and the
    protocol (`remote_batchable`) declare the capability, every
    remote-capable agent whose clock precedes every local candidate's
    clock (argmin-index tie-break) joins a remote batch — minus any lane
    whose target address collides with an earlier-clock batch member
    (the earlier lane keeps it; the later retries next trip).  Otherwise
    the trip falls back to one serial turn — remote turns execute alone,
    exactly at their serial position."""

    def cond(s):
        return wl.live(wl, s, *ops)

    def body(s):
        with jax.named_scope(PLAN_SCOPE):
            can_l = wl.can_local(wl, s, *ops)
            can_r = wl.can_remote(wl, s, *ops) if wl.has_remote else None
        return _note_turn(s, _batched_trip(wl, s, can_l, can_r, None, ops))

    return _while(cond, body, state)


@partial(jax.jit, static_argnums=(0,), **_don)
def run_batched_many(wl: Workload, states, *ops):
    """vmap of `run_batched` over a leading replica axis of `states`.

    One compilation covers every replica of a (workload, protocol, size)
    cell — the sweep's few-compilations path.  Finished replicas no-op
    (every turn is internally guarded) while stragglers drain."""
    return jax.vmap(lambda s: run_batched.__wrapped__(wl, s, *ops))(states)


def _fused_trip(wl: Workload, s, can_l, can_r, horizon, ops):
    """`_batched_trip` with the scheduling decision fused into one
    kernel-shaped plan and the turn execution restructured (DESIGN.md
    §12, bitwise-equivalence argument there):

      * the whole select-commuting-pops decision — batch lex/fence
        masks, remote co-schedule address dedup, serial-fallback agent —
        is ONE `fused_turn.trip_plan` call (the Pallas megakernel on
        TPU; its jnp reference, extracted verbatim from `_batched_trip`,
        on CPU);
      * the serial LOCAL fallback is folded into the SAME masked
        `local_turn` as the batch (`plan.lmask` one-hots the argmin
        agent when the batch is empty and it has a local turn) — §12
        proves the remote batch is necessarily empty in that case, so
        the trip runs `local_turn` ONCE instead of twice.  Under vmap
        (`run_fused_many`, the sweep path) `lax.cond` lowers to
        executing both branches, so this halves the local-turn work per
        trip per replica — the fused engine's steady-state win.

    Costmodel charging and trace events stay OUTSIDE the kernel
    boundary: only readiness masks, clocks, bounds and addresses cross
    into the plan, and the turns charge/record exactly as in
    `_batched_trip` — the trace-stripped equivalence suites hold."""
    if not wl.has_remote:
        return _batched_trip(wl, s, can_l, can_r, horizon, ops)
    remote_cap = (wl.remote_turn_b is not None
                  and wl.remote_addr is not None
                  and wl.proto.remote_batchable)
    with jax.named_scope(PLAN_SCOPE):
        raddr = wl.remote_addr(wl, s, *ops) if remote_cap else None
        plan = fused_turn.trip_plan(
            s.store.counters.cycles, can_l, can_r,
            wl.remote_bound(wl, s, *ops), raddr, horizon,
            remote_cap=remote_cap)

    def do_local(st):
        return wl.local_turn(wl, st, plan.lmask, *ops)

    if remote_cap:
        def do_remote(st):
            return lax.cond(
                jnp.any(plan.rmask),
                lambda s2: wl.remote_turn_b(wl, s2, plan.rmask, *ops),
                lambda s2: wl.remote_turn(wl, s2, plan.wg, *ops), st)
    else:
        def do_remote(st):
            return wl.remote_turn(wl, st, plan.wg, *ops)

    return lax.cond(jnp.any(plan.lmask), do_local, do_remote, s)


@partial(jax.jit, static_argnums=(0,), **_don)
def run_fused(wl: Workload, state, *ops):
    """`run_batched` with the fused trip (DESIGN.md §12): bitwise the
    same schedule and final state, one fused plan + at most one masked
    local turn per trip.  REPRO_NO_FUSE=1 (read at import) swaps the
    body back to `_batched_trip` — the engine name keeps resolving, the
    fused math never runs."""
    trip = _fused_trip if FUSE else _batched_trip

    def cond(s):
        return wl.live(wl, s, *ops)

    def body(s):
        with jax.named_scope(PLAN_SCOPE):
            can_l = wl.can_local(wl, s, *ops)
            can_r = wl.can_remote(wl, s, *ops) if wl.has_remote else None
        return _note_turn(s, trip(wl, s, can_l, can_r, None, ops))

    return _while(cond, body, state)


@partial(jax.jit, static_argnums=(0,), **_don)
def run_fused_many(wl: Workload, states, *ops):
    """vmap of `run_fused` over a leading replica axis (the sweep's
    few-compilations path, mirroring `run_batched_many`)."""
    return jax.vmap(lambda s: run_fused.__wrapped__(wl, s, *ops))(states)


# --------------------------------------------------------------------------
# Elastic alive-set scheduling (DESIGN.md §10).
#
# The plain engines assume a static agent set; production sharing tiers see
# churn.  The elastic engines wrap any workload state in an `ElasticState`
# carrying an alive mask, and replay a seeded `ChurnSchedule` of
# join/leave/crash events against it mid-run:
#
#   * a churn event at clock T serializes against every turn at clock >= T
#     — in BOTH engines, so serial/batched stay bitwise identical under
#     churn.  The batched trip simply fences its batch at the event
#     horizon (`_batched_trip(horizon=...)`).
#   * LEAVE retires the agent (the workload's `retire` hook forgives its
#     remaining obligations) and reclaims its caches immediately.
#   * CRASH retires the agent but the directory may only reclaim once the
#     agent's clock-stamped lease (ops.acquire/release stamp it) expires:
#     recovery fires at T + lease via `Protocol.recover_b` — drain the dead
#     agent's dirty words through the existing writeback machinery,
#     force-release its leased sync word at L2, invalidate its PA/LR
#     entries.  A protocol with `recover_b=None` (faults.lease_never_expires)
#     never reclaims: survivors observe whatever the crash stranded.
#   * JOIN re-admits the agent (the workload's `admit` hook may assign it
#     new work).  Schedule JOINs for crashed agents only after their lease
#     expired — re-admitting an unreclaimed cache is the operator's hazard.
#
# Zero churn is bitwise-exact: an empty schedule keeps every event horizon
# at BIG, the fences reduce to `clock < BIG` (always true for f32 cycle
# clocks), the alive mask stays all-True (`can & True == can`), and the
# fire branch of the lax.cond never executes.
# --------------------------------------------------------------------------

LEAVE, CRASH, JOIN = 0, 1, 2
KIND_CODES = {"leave": LEAVE, "crash": CRASH, "join": JOIN}


class ChurnSchedule(NamedTuple):
    """Seeded churn event stream, carried as a scheduler op (traced)."""
    clock: jnp.ndarray   # [k] f32 fire time (BIG = padding, never fires)
    agent: jnp.ndarray   # [k] i32 subject agent
    kind: jnp.ndarray    # [k] i32 LEAVE / CRASH / JOIN
    lease: jnp.ndarray   # [] f32 promotion/lock-hold lease (cycles)


class ElasticState(NamedTuple):
    """Workload state + alive-set bookkeeping threaded through the run."""
    s: Any                   # workload state (first field is the Store)
    alive: jnp.ndarray       # [n] bool scheduling-eligible agents
    recover_at: jnp.ndarray  # [n] f32 pending reclaim clock (BIG = none)
    fired: jnp.ndarray       # [k] bool churn events already replayed


def make_churn(events=(), lease=0.0) -> ChurnSchedule:
    """Build a schedule from (clock, agent, kind) triples; kind is a
    KIND_CODES string or int code.  Always at least one (inert) entry so
    the event-horizon reductions never see a zero-length axis."""
    k = max(len(events), 1)
    clock = [float(BIG)] * k
    agent = [0] * k
    kind = [LEAVE] * k
    for j, (t, a, kd) in enumerate(events):
        clock[j] = float(t)
        agent[j] = int(a)
        kind[j] = KIND_CODES[kd] if isinstance(kd, str) else int(kd)
    return ChurnSchedule(clock=jnp.asarray(clock, jnp.float32),
                         agent=jnp.asarray(agent, jnp.int32),
                         kind=jnp.asarray(kind, jnp.int32),
                         lease=jnp.asarray(float(lease), jnp.float32))


def make_elastic(bench: Bench, events=(), lease=0.0) -> Bench:
    """Wrap a Bench for the elastic engines: ElasticState state, the
    churn schedule prepended to ops, check unwrapped to the inner state."""
    sched = make_churn(events, lease)
    n = bench.state.store.counters.cycles.shape[0]
    es = ElasticState(s=bench.state,
                      alive=jnp.ones((n,), bool),
                      recover_at=jnp.full((n,), BIG),
                      fired=sched.clock >= BIG)
    return Bench(bench.wl, es, (sched,) + bench.ops,
                 lambda final: bench.check(final.s))


def _elastic_ready(wl: Workload, es: ElasticState, ops):
    """Alive-masked readiness: dead agents never act (can_r all-False for
    workloads without remote turns)."""
    can_l = wl.can_local(wl, es.s, *ops) & es.alive
    if wl.has_remote:
        can_r = wl.can_remote(wl, es.s, *ops) & es.alive
    else:
        can_r = jnp.zeros_like(es.alive)
    return can_l, can_r


def _event_horizon(sched: ChurnSchedule, es: ElasticState) -> jnp.ndarray:
    """Earliest unfired churn event or pending lease reclaim (BIG: none)."""
    ec = jnp.min(jnp.where(es.fired, BIG, sched.clock))
    return jnp.minimum(ec, jnp.min(es.recover_at))


def _fire_events(wl: Workload, sched: ChurnSchedule, es: ElasticState,
                 mcc, ops) -> ElasticState:
    """Replay every churn event and lease reclaim due at clock <= `mcc`
    (the next turn's clock).  Events replay in schedule order — the same
    deterministic position in both engines."""
    s, alive, recover_at, fired = es
    n = alive.shape[0]
    due = ~fired & (sched.clock <= mcc)

    def step(carry, j):
        s, alive, recover_at = carry
        hot = one_hot(n, sched.agent[j]) & due[j]
        kind = sched.kind[j]
        dead = hot & (kind != JOIN)
        join = hot & (kind == JOIN)
        if wl.retire is not None:
            s = wl.retire(wl, s, dead, *ops)
        if wl.admit is not None:
            s = wl.admit(wl, s, join, *ops)
        if T.enabled(s.store.trace):
            # churn event per affected lane, stamped with the schedule
            # clock; the harness LEAVE/CRASH/JOIN code rides the outcome
            s = s._replace(store=T.record_event(
                s.store, hot, T.CHURN, kind, clock=sched.clock[j]))
        alive = (alive & ~dead) | join
        # a clean LEAVE may be reclaimed at once; a CRASH's promotion
        # lease must first expire before the directory touches its state
        due_at = jnp.where(kind == CRASH, sched.clock[j] + sched.lease,
                           sched.clock[j])
        recover_at = jnp.where(dead, due_at, recover_at)
        return (s, alive, recover_at), None

    (s, alive, recover_at), _ = lax.scan(
        step, (s, alive, recover_at),
        jnp.arange(sched.clock.shape[0]))
    fired = fired | due
    reclaim = (recover_at <= mcc) & (recover_at < BIG)
    if wl.proto.recover_b is not None:
        s = lax.cond(
            jnp.any(reclaim),
            lambda st: st._replace(store=wl.proto.recover_b(
                wl.cfg.proto_cfg(), st.store, reclaim)),
            lambda st: st, s)
    # cleared even when recover_b is None: the reclaim point passed and
    # nothing happened — that IS the lease_never_expires semantics, and
    # leaving it pending would spin the scheduler forever
    recover_at = jnp.where(reclaim, BIG, recover_at)
    return ElasticState(s, alive, recover_at, fired)


def _note_fire(es: ElasticState) -> ElasticState:
    """Close a trip that fired churn events: it counts as a trip and, as
    no agent took a turn, buckets nothing."""
    return es._replace(s=es.s._replace(store=T.record_trip(es.s.store)))


def _elastic_cond(wl: Workload, sched: ChurnSchedule, es: ElasticState,
                  ops):
    """Loop guard: work remains AND progress is possible — a live agent
    can act, or an event/reclaim is still due to fire.  Unlike the plain
    engines this cannot rely on `live` alone: a crashed agent's
    unforgivable leftovers (e.g. a dead queue nobody may steal from)
    would otherwise wedge the loop; here the run terminates and the
    self-check reports the loss instead."""
    can_l, can_r = _elastic_ready(wl, es, ops)
    pending = _event_horizon(sched, es) < BIG
    return wl.live(wl, es.s, *ops) & (jnp.any(can_l | can_r) | pending)


@partial(jax.jit, static_argnums=(0,), **_don)
def run_serial_elastic(wl: Workload, es: ElasticState,
                       sched: ChurnSchedule, *ops):
    """`run_serial` over a churn-varying alive-set.  With an empty
    schedule the trip sequence is bitwise identical to `run_serial`."""

    def cond(e):
        return _elastic_cond(wl, sched, e, ops)

    def body(e):
        with jax.named_scope(PLAN_SCOPE):
            can_l, can_r = _elastic_ready(wl, e, ops)
            clocks = jnp.where(can_l | can_r, e.s.store.counters.cycles,
                               BIG)
            mcc = jnp.min(clocks)
            wg = jnp.argmin(clocks).astype(jnp.int32)
            ec = _event_horizon(sched, e)
        return lax.cond(
            (ec <= mcc) & (ec < BIG),
            lambda e2: _note_fire(_fire_events(wl, sched, e2, mcc, ops)),
            lambda e2: e2._replace(
                s=_note_turn(e2.s, _serial_turn(wl, e2.s, wg, can_l,
                                                ops))),
            e)

    return _while(cond, body, es)


@partial(jax.jit, static_argnums=(0,), **_don)
def run_batched_elastic(wl: Workload, es: ElasticState,
                        sched: ChurnSchedule, *ops):
    """`run_batched` over a churn-varying alive-set: the trip is fenced
    at the event horizon so no turn at clock >= the next event executes
    before the event fires — the reordering argument of DESIGN.md §4/§9
    then applies span-by-span between events.  With an empty schedule the
    trip sequence is bitwise identical to `run_batched`."""

    def cond(e):
        return _elastic_cond(wl, sched, e, ops)

    def body(e):
        with jax.named_scope(PLAN_SCOPE):
            can_l, can_r = _elastic_ready(wl, e, ops)
            clocks = jnp.where(can_l | can_r, e.s.store.counters.cycles,
                               BIG)
            mcc = jnp.min(clocks)
            ec = _event_horizon(sched, e)
        cr = can_r if wl.has_remote else None
        return lax.cond(
            (ec <= mcc) & (ec < BIG),
            lambda e2: _note_fire(_fire_events(wl, sched, e2, mcc, ops)),
            lambda e2: e2._replace(
                s=_note_turn(e2.s, _batched_trip(wl, e2.s, can_l, cr, ec,
                                                 ops))),
            e)

    return _while(cond, body, es)


# Engine registry: unknown names raise with the registered list.
ENGINES = P.Registry("engine")


def register_engine(name: str, fn: Callable) -> Callable:
    ENGINES[name] = fn
    return fn


def engines() -> tuple:
    """Names of every registered engine, sorted."""
    return tuple(sorted(ENGINES))


register_engine("serial", run_serial)
register_engine("batched", run_batched)
register_engine("fused", run_fused)
register_engine("serial_elastic", run_serial_elastic)
register_engine("batched_elastic", run_batched_elastic)

# Vmapped (replicated) twins for the engines the sweep packs replicas
# through — one compiled `run_*_many` per (workload, protocol, size) cell.
ENGINES_MANY = P.Registry("vmapped engine")
ENGINES_MANY["batched"] = run_batched_many
ENGINES_MANY["fused"] = run_fused_many


class Runner:
    """A registered engine as `runner`/`runner_many` hand it out: calls
    the same jitted program, inside an `engine.call` span that carries a
    call sequence number, and records the output's trip count (one per
    replica for a vmapped engine) in `obs.spans` under that number.  The
    count is recorded unfetched, its copy to the host started, so a call
    never waits for the device.  `lower` is the program's own."""

    def __init__(self, fn: Callable):
        self.fn = fn
        self.lower = fn.lower

    def __call__(self, wl: Workload, state, *ops):
        seq = spans.next_seq()
        with spans.span("engine.call", seq=seq):
            out = self.fn(wl, state, *ops)
        trips = T.trip_count(out)
        trips.copy_to_host_async()
        spans.record("engine.trips", seq, trips)
        return out


def runner(engine: str) -> Runner:
    """Registered scheduler by name; unknown names raise with the list."""
    return Runner(ENGINES[engine])


def runner_many(engine: str) -> Runner:
    """Vmapped scheduler twin by engine name (sweep replica packing)."""
    return Runner(ENGINES_MANY[engine])


def drain_all(cfg: P.ProtoConfig, st: P.Store) -> P.Store:
    """Flush every cache completely (post-run memory audits)."""
    n = cfg.n_caches
    st, _ = P.b_drain(cfg, st, jnp.full((n,), P.DRAIN_ALL),
                      jnp.ones((n,), bool))
    return st


def counters_dict(st: P.Store) -> dict:
    """The standard counter summary every workload reports (run_app's set)."""
    from repro.core import costmodel
    c = st.counters
    return {
        "makespan": float(costmodel.makespan(c)),
        "l2_accesses": float(c.l2_accesses),
        "wb_blocks": float(c.wb_blocks),
        "inv_full": float(c.inv_full),
        "probes": float(c.probes),
        "promotions": float(c.promotions),
        "local_syncs": float(c.local_syncs),
        "remote_syncs": float(c.remote_syncs),
        "global_syncs": float(c.global_syncs),
        "steals": float(c.steals),
        "l1_hits": float(c.l1_hits),
        "l1_misses": float(c.l1_misses),
        "recoveries": float(c.recoveries),
    }
