"""In-engine event tracing: a fixed-capacity ring buffer carried in the
protocol `Store` (DESIGN.md §11).

`TraceLog` is a pytree of parallel [cap] event columns plus per-scope /
per-agent cycle histograms and a per-agent turn-latency histogram, all
updated with masked scatters inside the jitted schedulers:

* every scoped-ISA op (`repro.core.ops`) appends one event per active
  lane — clock (the lane's cycle counter when the op issued), agent,
  op kind, scope, address, cycles charged to that lane, and a protocol
  outcome (hit / promote / probe / NACK / …) classified from the
  pre-dispatch table state;
* the elastic engines append churn (leave/crash/join) and recovery
  events; the engines bucket each agent's per-turn charged cycles.

Beside the ring, `trips` counts the engine's `while_loop` trips.  It is
kept in every log, the disabled one too: it is a property of the engine
(serial, batched and fused run the same simulation in different numbers
of trips), so `strip` zeroes it with the rest of the trace.

Ring overflow policy: `head` is a monotonic event count and an event's
slot is `(position % cap)`, so the buffer always holds the NEWEST `cap`
events; the oldest are overwritten and `dropped = max(head - cap, 0)`
is reported by the decoder — overflow loses history, never corrupts.

Enablement is carried by SHAPE, not by a runtime flag: a disabled log
has zero-capacity columns and every record_* helper but `record_trip`
returns its input unchanged via a trace-time Python conditional — the
disabled path is *provably* absent from the compiled program, and the
trip counter, which is, is stripped with the trace by every bitwise
equivalence suite.  `REPRO_TRACE=1`
(read once at import, mirroring REPRO_NO_PACK) makes `make_store`
allocate `REPRO_TRACE_CAP` (default 4096) slots; `with_trace` enables
tracing on an existing state in-process (tests, the report demo).
"""
from __future__ import annotations

import os
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import costmodel
from repro.obs import metrics

TRACE = os.environ.get("REPRO_TRACE", "0") == "1"
DEFAULT_CAP = int(os.environ.get("REPRO_TRACE_CAP", "4096"))

# event kinds
ACQUIRE, RELEASE, LOAD, STORE, CHURN, RECOVER = range(6)
KIND_NAMES = {ACQUIRE: "acquire", RELEASE: "release", LOAD: "load",
              STORE: "store", CHURN: "churn", RECOVER: "recover"}

# op outcomes (CHURN events carry the harness LEAVE/CRASH/JOIN code
# in the outcome column instead — decode dispatches on kind)
OC_NONE, OC_HIT, OC_PROMOTE, OC_PROBE, OC_NACK, OC_GLOBAL, OC_MISS, \
    OC_RECOVER = range(8)
OUTCOME_NAMES = {OC_NONE: "none", OC_HIT: "hit", OC_PROMOTE: "promote",
                 OC_PROBE: "probe", OC_NACK: "nack", OC_GLOBAL: "global",
                 OC_MISS: "miss", OC_RECOVER: "recover"}
CHURN_NAMES = {0: "leave", 1: "crash", 2: "join"}   # harness.KIND_CODES


class TraceLog(NamedTuple):
    """Ring-buffer event log + latency histograms (all leaves jit-carried).

    cap == 0 (the `clock` extent) IS the disabled state; the histogram
    agent axis collapses to 0 with it so a disabled log is empty."""
    head: jnp.ndarray       # [] i32 monotonic event count
    trips: jnp.ndarray      # [] i32 engine while-loop trips (always kept)
    clock: jnp.ndarray      # [cap] f32 issuing lane's cycles at issue
    agent: jnp.ndarray      # [cap] i32
    kind: jnp.ndarray       # [cap] i32 ACQUIRE..RECOVER
    scope: jnp.ndarray      # [cap] i32 ops.LOCAL/REMOTE/GLOBAL
    addr: jnp.ndarray       # [cap] i32 (-1: no address)
    cycles: jnp.ndarray     # [cap] f32 charged to the lane by the op
    outcome: jnp.ndarray    # [cap] i32 OC_* (or churn code for CHURN)
    op_hist: jnp.ndarray    # [3, n, B] i32 per-scope/agent charged cycles
    turn_hist: jnp.ndarray  # [n, B] i32 per-agent per-turn latency


def make(cap: int, n_agents: int) -> TraceLog:
    b = metrics.N_BUCKETS
    m = n_agents if cap else 0
    return TraceLog(
        head=jnp.zeros((), jnp.int32),
        trips=jnp.zeros((), jnp.int32),
        clock=jnp.zeros((cap,), jnp.float32),
        agent=jnp.full((cap,), -1, jnp.int32),
        kind=jnp.full((cap,), -1, jnp.int32),
        scope=jnp.zeros((cap,), jnp.int32),
        addr=jnp.full((cap,), -1, jnp.int32),
        cycles=jnp.zeros((cap,), jnp.float32),
        outcome=jnp.zeros((cap,), jnp.int32),
        op_hist=jnp.zeros((3, m, b), jnp.int32),
        turn_hist=jnp.zeros((m, b), jnp.int32),
    )


def default_cap() -> int:
    return DEFAULT_CAP if TRACE else 0


def enabled(tl: TraceLog) -> bool:
    """Static (shape-level) enablement — safe to branch on in Python."""
    return tl.clock.shape[0] > 0


def capacity(tl: TraceLog) -> int:
    return tl.clock.shape[0]


def with_trace(state, cap: int = None):
    """Enable (or resize) tracing on a Store / workload / elastic state."""
    cap = DEFAULT_CAP if cap is None else cap
    if hasattr(state, "trace") and hasattr(state, "counters"):  # Store
        n = state.counters.cycles.shape[0]
        return state._replace(trace=make(cap, n))
    if hasattr(state, "store"):
        return state._replace(store=with_trace(state.store, cap))
    return state._replace(s=with_trace(state.s, cap))   # ElasticState


def strip(state):
    """Replace the trace with the disabled log — for bitwise comparisons
    across paths whose event ORDER legitimately differs (serial and
    batched run the same ops at the same costs in different calls), or
    whose trip counts do."""
    return with_trace(state, 0)


def trip_count(state):
    """The `trips` leaf of a Store / workload / elastic state."""
    if hasattr(state, "trace") and hasattr(state, "counters"):  # Store
        return state.trace.trips
    if hasattr(state, "store"):
        return trip_count(state.store)
    return trip_count(state.s)                          # ElasticState


# --------------------------------------------------------------------------
# jit-side recording (every helper but record_trip is a Python-level
# identity when disabled)
# --------------------------------------------------------------------------

def _append(tl: TraceLog, active, *, clock, agent, kind, scope, addr,
            cycles, outcome) -> TraceLog:
    """Masked ring append: one event per active lane, lane order."""
    cap = tl.clock.shape[0]
    n = active.shape[0]
    active = jnp.asarray(active, bool)
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1
    cnt = jnp.sum(active.astype(jnp.int32))
    # inactive lanes target index `cap`, dropped by the scatter mode
    idx = jnp.where(active, (tl.head + rank) % cap, cap)

    def put(buf, vals):
        vals = jnp.broadcast_to(jnp.asarray(vals, buf.dtype), (n,))
        return buf.at[idx].set(vals, mode="drop")

    return tl._replace(
        head=tl.head + cnt,
        clock=put(tl.clock, clock), agent=put(tl.agent, agent),
        kind=put(tl.kind, kind), scope=put(tl.scope, scope),
        addr=put(tl.addr, addr), cycles=put(tl.cycles, cycles),
        outcome=put(tl.outcome, outcome))


def record_op(st, active, kind, scope, addrs, clock0, outcome):
    """Append one sync/data-op event per active lane and bucket its
    charged cycles into the per-scope histogram.  `clock0` is the
    per-lane cycle vector captured BEFORE dispatch; the charge is the
    lane's own delta across the op.  Identity when tracing is off."""
    tl = st.trace
    if not enabled(tl):
        return st
    n = st.counters.cycles.shape[0]
    active = jnp.asarray(active, bool)
    delta = costmodel.charged_since(st.counters, clock0)
    scope_arr = jnp.clip(jnp.broadcast_to(
        jnp.asarray(scope, jnp.int32), (n,)), 0, 2)
    tl = _append(tl, active, clock=clock0,
                 agent=jnp.arange(n, dtype=jnp.int32), kind=kind,
                 scope=scope_arr, addr=addrs, cycles=delta, outcome=outcome)
    lanes = jnp.arange(n, dtype=jnp.int32)
    tl = tl._replace(op_hist=tl.op_hist.at[
        scope_arr, lanes, metrics.bucket_index(delta)]
        .add(active.astype(jnp.int32)))
    return st._replace(trace=tl)


def record_event(st, mask, kind, outcome, *, addr=None, clock=None,
                 cycles=0.0):
    """Append a scheduler event (churn, recovery) per masked lane.
    Identity when tracing is off."""
    tl = st.trace
    if not enabled(tl):
        return st
    n = st.counters.cycles.shape[0]
    tl = _append(tl, jnp.asarray(mask, bool),
                 clock=st.counters.cycles if clock is None else clock,
                 agent=jnp.arange(n, dtype=jnp.int32), kind=kind,
                 scope=0, addr=-1 if addr is None else addr,
                 cycles=cycles, outcome=outcome)
    return st._replace(trace=tl)


def record_trip(st):
    """Count one engine trip.  Runs whether tracing is on or off."""
    tl = st.trace
    return st._replace(trace=tl._replace(trips=tl.trips + 1))


def record_turn(st, clock0):
    """Bucket each agent's charged cycles for one scheduler turn/trip
    (lanes whose clock didn't move didn't act).  Identity when off."""
    tl = st.trace
    if not enabled(tl):
        return st
    n = st.counters.cycles.shape[0]
    delta = costmodel.charged_since(st.counters, clock0)
    acted = delta > 0
    lanes = jnp.arange(n, dtype=jnp.int32)
    return st._replace(trace=tl._replace(
        turn_hist=tl.turn_hist.at[lanes, metrics.bucket_index(delta)]
        .add(acted.astype(jnp.int32))))


# --------------------------------------------------------------------------
# host-side summaries (sweep columns)
# --------------------------------------------------------------------------

def dropped(tl: TraceLog) -> int:
    return max(int(tl.head) - capacity(tl), 0)


def summary(store) -> dict:
    """Schema-v6 latency columns for one run's final store: conservative
    upper-edge percentiles of the pooled per-turn latency histogram,
    plus ring occupancy.  All-None/zero when tracing is off."""
    tl = store.trace
    if not enabled(tl):
        return {"latency_p50": None, "latency_p95": None,
                "latency_p99": None, "latency_turns": 0,
                "trace_events": 0, "trace_dropped": 0}
    pooled = np.asarray(tl.turn_hist, np.int64).sum(axis=0)
    s = metrics.summarize(pooled)
    return {"latency_p50": s["p50"], "latency_p95": s["p95"],
            "latency_p99": s["p99"], "latency_turns": s["count"],
            "trace_events": int(tl.head), "trace_dropped": dropped(tl)}
