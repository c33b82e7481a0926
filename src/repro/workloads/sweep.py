"""Protocol × workload × size sweep — the paper's Fig. 5/6 comparisons
generalized across every registered asymmetric-sharing workload.

Grid: workload × scenario (baseline / scope_only / rsp / srsp) × n_agents,
batched engine.  Emits BENCH_workloads.json (schema: benchmarks/SCHEMA.md,
version 2) with **compile time reported separately from steady state**:

  * compile_s           first-call wall time (jit trace+compile + 1st run)
  * steady_s_per_run    mean wall of subsequent full runs (fresh states,
                        same shapes → jit cache hits)
  * steady_s_per_replica  the vmapped path packs `--seeds` seed-varied
                        replicas into ONE compiled `run_batched_many` call
                        per (workload, protocol, size) cell — compilation
                        count stays at one per cell no matter how many
                        replicas run (the "as few compilations as
                        possible" contract).  Per-replica cost divides by
                        the batch width.

Protocol comparisons use *modeled makespan* (max per-agent cycles — the
paper's metric), not wall clock; wall clock measures the simulator
engine, makespan measures the protocol.  `scope_only` is expected to
FAIL self-checks on workloads with remote turns (local-scope remote sync
is the paper's staleness demo) — `check_ok: false` in those rows is the
workload subsystem working, not a bug.

The sweep is one process and starts no child: a process that has
imported JAX holds the chip, so a child that needs it would fail.  The
toggles read at import (REPRO_NO_DONATE, REPRO_NO_PACK) are measured as
separate top-level invocations (`env REPRO_NO_PACK=1 python -m
repro.workloads.sweep ...`).

Schema v3 additions (benchmarks/SCHEMA.md): per-run `table_geometry`
(LR/PA sets×ways) and top-level `packed_metadata`.

Schema v5 additions (elastic alive-set PR, DESIGN.md §10): per-run
churn columns (`churn_events`, `churn_rate`, `recovered`,
`lost_updates`) plus ONE churned robustness cell — the worksteal srsp
bench under a pinned die-holding-lock crash on the batched elastic
engine, which must complete via the lease-expiry recovery drain with
zero lost updates among survivors.  Every cell also runs under a
per-cell hang watchdog (runtime/fault.py StepTimer + Heartbeat +
interrupt timer; `REPRO_NO_WATCHDOG=1` disables).

Schema v6 additions (observability PR, DESIGN.md §11): per-run
`latency_p50/p95/p99` / `latency_turns` (conservative upper-edge
percentiles of the per-turn modeled-latency histogram) and
`trace_events`/`trace_dropped` (event-ring occupancy) — populated only
under `REPRO_TRACE=1`; tracing charges no cycles, so every other column
is bitwise unchanged by the flag.  One traced srsp cell is additionally
exported as Perfetto-loadable Chrome-trace JSON (`--trace-out`), and
top-level `stragglers` lists watchdog-flagged slow cells.

Schema v7 additions (fused megakernel PR, DESIGN.md §12): grid rows for
`engine="fused"` (the one-kernel batched trip) on the srsp scenario by
default (`--fused-scenarios`), a per-run + top-level `kernel_mode`
column ("pallas" / "ref" / "interpret" — chosen once per process,
`kernels/common.py`) so an interpret-mode timing can never masquerade as
a measurement, and the `fuse_ab` section: the vmapped kv_directory srsp
cell run engine="fused" vs engine="batched" in one process at
`--fuse-sizes` (the vmapped path is where the fusion win lives — the
batched engine's cond branches all execute under vmap, the fused engine
runs ONE masked local turn).  The A/B asserts identical modeled
makespans (the §12 equivalence argument in vivo) and reports
`steady_speedup_fused`.

Schema v8 additions (traffic subsystem PR, DESIGN.md §13): per-run
`offered_load` / `completed` / `zipf_s` / `burstiness` columns (None on
self-driven workloads) and `latency_source` — trace-driven rows
(kv_serving) fill the latency percentiles from their per-REQUEST
completion-latency histogram (state-resident, populated with tracing
compiled off; pooled across replicas), self-driven rows keep the §11
per-turn trace source.  Plus the `serving` section: kv_serving at
`--serving-sizes` under Zipf skew `--serving-zipf` (s ∈ {0.9, 1.2}),
srsp batched vs srsp fused (asserted: same makespan, completed count and
latency histogram — the same generated trace replayed bitwise across
engines) vs rsp batched, reporting `srsp_vs_rsp_makespan` and
`srsp_vs_rsp_p99` per skew (auto-gated by benchmarks/compare.py), and
ONE serving-scale cell: >= 1e6 simulated requests replayed through the
vmapped fused path per scenario (srsp/rsp/baseline), self-checks green.
A second churned robustness cell runs kv_serving under the pinned
crash_holding_lock + CRASH-event recovery (tests/test_kv_serving.py pins
the same numbers).

Schema v4 additions (scope-parametric ISA PR, DESIGN.md §9): per-run
`api` ("scoped" — every workload issues ops through `repro.core.ops`)
and `remote_batch` (whether the workload×protocol pair can co-schedule
address-disjoint remote turns), plus the `remote_batch_ab` section: the
multi-consumer producer/consumer cell run with the batched remote twins
vs with `faults.serialize_remote` (scalar serialized remote turns), in
one process — the capability is carried by the Protocol object, not an
env flag.  The A/B asserts identical modeled makespans (the §9
commutation rule holding in vivo) and reports the wall-clock effect.

Usage:
  PYTHONPATH=src python -m repro.workloads.sweep \
      [--workloads all] [--scenarios baseline scope_only rsp srsp]
      [--sizes 16 64] [--seeds 2] [--iters 2] [--no-remote-batch-ab]
      [--no-churn] [--fused-scenarios srsp] [--no-fuse-ab] [--fuse-sizes 64 256] [--out BENCH_workloads.json]
"""
from __future__ import annotations

import _thread
import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

# allow `python src/repro/workloads/sweep.py` without PYTHONPATH
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp

from repro import workloads
from repro.core import protocol as P
from repro.kernels import common as kcommon
from repro.obs import export as obs_export, metrics, spans, trace as T
from repro.runtime import compile_cache, fault as rtfault
from repro.traffic.samplers import TrafficConfig
from repro.workloads import faults, harness

SCHEMA_VERSION = 8
DEFAULT_SCENARIOS = ["baseline", "scope_only", "rsp", "srsp"]

# per-cell hang budget for the watchdog (seconds)
WATCHDOG_S = float(os.environ.get("REPRO_WATCHDOG_S", "600"))


class CellWatchdog:
    """Per-cell hang watchdog — runtime/fault.py wired into the sweep.

    A `Heartbeat` file records sweep liveness for outside watchers, a
    `StepTimer` flags straggler cells (z-score over the cell history),
    and a `threading.Timer` interrupts the main thread if a single cell
    exceeds WATCHDOG_S — a wedged `while_loop` (e.g. a crash injection
    without its recovery drain) fails the sweep loudly instead of
    hanging CI.  `REPRO_NO_WATCHDOG=1` disables everything (debuggers,
    profilers, very slow boxes)."""

    def __init__(self, heartbeat_path: str = None):
        if heartbeat_path is None:
            # per-process path in the tmpdir: a fixed repo-local filename
            # collides across concurrent sweeps (and a crashed run's
            # stale file would impersonate the next one)
            heartbeat_path = os.path.join(
                tempfile.gettempdir(), f"sweep_heartbeat.{os.getpid()}")
        self.enabled = os.environ.get("REPRO_NO_WATCHDOG", "0") != "1"
        self.timer = rtfault.StepTimer(window=50, z_thresh=3.0)
        self.hb = rtfault.Heartbeat(heartbeat_path, interval=5.0)
        self.cells = 0
        self.label = "?"
        self.stragglers = []   # [{cell, wall_s}] — surfaced in the bench
        self._t = None

    def start(self, label: str):
        self.label = label
        if not self.enabled:
            return
        self.timer.start()
        self.hb.beat(self.cells)
        self._t = threading.Timer(WATCHDOG_S, self._fire)
        self._t.daemon = True
        self._t.start()

    def _fire(self):
        print(f"WATCHDOG: cell {self.label} exceeded {WATCHDOG_S:.0f}s "
              f"budget — interrupting the sweep", file=sys.stderr, flush=True)
        _thread.interrupt_main()

    def stop(self):
        self.cells += 1
        if not self.enabled:
            return
        self._t.cancel()
        dt, straggler = self.timer.stop()
        if straggler:
            self.stragglers.append({"cell": self.label,
                                    "wall_s": round(dt, 2)})
            print(f"watchdog: straggler cell {self.label} ({dt:.1f}s, "
                  f"z>{self.timer.z_thresh})", flush=True)

    def close(self):
        """End of sweep: cancel any pending interrupt, remove the
        heartbeat file (stale liveness files alias later runs)."""
        if self._t is not None:
            self._t.cancel()
        self.hb.stop()


def _lane0(tree):
    return jax.tree.map(lambda x: x[0], tree)


def _geometry(wl) -> dict:
    """Schema-v3 table-geometry column: the LR/PA sets×ways this cell ran
    with (derived from the workload's protocol config, not literals)."""
    pc = wl.cfg.proto_cfg()
    return {"lr": str(pc.lr_tbl), "pa": str(pc.pa_tbl)}


def _api_cols(wl) -> dict:
    """Schema-v4 columns: the op surface (always the scoped ISA since the
    cutover) and whether this workload×protocol pair co-schedules
    address-disjoint remote turns (DESIGN.md §9)."""
    return {"api": "scoped",
            "remote_batch": bool(wl.remote_turn_b is not None
                                 and wl.remote_addr is not None
                                 and wl.proto.remote_batchable)}


def _churn_cols(churn_events=0, makespan=0.0, recovered=0.0,
                lost_updates=0) -> dict:
    """Schema-v5 columns (DESIGN.md §10): churn_events fired during the
    run, churn_rate per 1k modeled cycles, agents reclaimed by recovery
    drains, and updates lost among survivors (must be 0 when recovery is
    on).  Zero-churn grid cells carry literal zeros."""
    rate = 1e3 * churn_events / makespan if makespan else 0.0
    return {"churn_events": int(churn_events),
            "churn_rate": round(rate, 5),
            "recovered": float(recovered),
            "lost_updates": int(lost_updates)}


def _latency_cols(store) -> dict:
    """Schema-v6 columns (DESIGN.md §11): conservative upper-edge
    p50/p95/p99 of the per-turn modeled-latency histogram plus trace
    ring occupancy — all None/0 unless the sweep runs under
    REPRO_TRACE=1 (tracing charges nothing, so every other column is
    bitwise unchanged by the flag)."""
    return T.summary(store)


def _traffic_cols(wl, checks) -> dict:
    """Schema-v8 columns (DESIGN.md §13): offered vs completed request
    totals (summed across replicas) and the traffic shape that generated
    them.  Self-driven workloads carry None — the column distinguishes
    'no traffic model' from 'zero requests'."""
    if not checks or "offered" not in checks[0]:
        return {"offered_load": None, "completed": None,
                "zipf_s": None, "burstiness": None}
    tc = wl.cfg.traffic
    return {"offered_load": int(sum(c["offered"] for c in checks)),
            "completed": int(sum(c["completed"] for c in checks)),
            "zipf_s": tc.zipf_s, "burstiness": tc.burstiness}


def _request_latency(rec, checks) -> None:
    """Trace-driven rows (schema v8) report latency percentiles of the
    per-REQUEST completion histogram — state-resident, so populated even
    with tracing compiled off — pooled across replicas.  Self-driven
    rows keep the §11 per-turn trace source (when REPRO_TRACE=1)."""
    if checks and "latency_hist" in checks[0]:
        pooled = np.sum([np.asarray(c["latency_hist"], np.int64)
                         for c in checks], axis=0)
        lat = metrics.summarize(pooled)
        rec.update({"latency_p50": lat["p50"], "latency_p95": lat["p95"],
                    "latency_p99": lat["p99"],
                    "latency_turns": lat["count"],
                    "latency_source": "requests"})
    else:
        rec["latency_source"] = "turns" if rec.get("trace_events") \
            else None


def measure_vmapped(mod, name, scenario, n_agents, n_seeds, iters,
                    engine="batched", build_kw=None):
    """One compiled `runner_many(engine)` call per cell; replicas ride
    the vmap.  engine="fused" times the one-kernel batched trip
    (schema v7, DESIGN.md §12).  `build_kw` overrides workload-config
    fields (the v8 serving section's traffic shapes)."""
    run_many = harness.runner_many(engine)
    bench = mod.build(scenario, n_agents, seed=0, **(build_kw or {}))
    wl = bench.wl

    def states(base):
        seeds = jnp.arange(base, base + n_seeds, dtype=jnp.int32)
        return jax.vmap(lambda s: mod.init_state(wl, s))(seeds)

    t0 = time.perf_counter()
    out = run_many(wl, states(0))
    jax.block_until_ready(out.store.counters.cycles)
    compile_s = time.perf_counter() - t0

    times = []
    for it in range(max(1, iters)):
        st = states((it + 1) * n_seeds)
        t0 = time.perf_counter()
        out = run_many(wl, st)
        jax.block_until_ready(out.store.counters.cycles)
        times.append(time.perf_counter() - t0)

    # self-check EVERY replica (cheap, host-side) — seed-jittered lanes
    # can exercise failure modes lane 0 doesn't
    checks = [mod.self_check(wl, jax.tree.map(lambda x: x[k], out))
              for k in range(n_seeds)]
    lane = _lane0(out)
    counters = harness.counters_dict(lane.store)
    steady = float(np.mean(times))
    rec = {
        "workload": name, "scenario": scenario, "n_agents": n_agents,
        "engine": engine, "kernel_mode": kcommon.kernel_mode(),
        "vmapped": True, "n_replicas": n_seeds,
        "table_geometry": _geometry(wl), **_api_cols(wl),
        "iters_timed": iters,
        "compile_s": round(compile_s, 4),
        "steady_s_per_run": round(steady, 5),
        "steady_s_per_replica": round(steady / n_seeds, 5),
        **_churn_cols(), **_latency_cols(lane.store),
        **_traffic_cols(wl, checks),
        "events": int(lane.rounds),
        "check_ok": all(c["ok"] for c in checks),
        "check_fails": int(sum(c["check_fails"] for c in checks)),
        "makespan": counters["makespan"],
        "counters": counters,
        "_trace_store": lane.store,
    }
    _request_latency(rec, checks)
    return rec


def measure_host_init(mod, name, scenario, n_agents, iters,
                      engine="batched"):
    """Non-vmappable workloads (worksteal: host-side enqueue): fresh
    state per run, shared jit cache across runs."""
    run = harness.runner(engine)
    bench = mod.build(scenario, n_agents, seed=0)
    t0 = time.perf_counter()
    out = run(bench.wl, bench.state, *bench.ops)
    jax.block_until_ready(out.store.counters.cycles)
    compile_s = time.perf_counter() - t0

    times = []
    for it in range(max(1, iters)):
        b = mod.build(scenario, n_agents, seed=it + 1)
        t0 = time.perf_counter()
        out = run(b.wl, b.state, *b.ops)
        jax.block_until_ready(out.store.counters.cycles)
        times.append(time.perf_counter() - t0)
        check = b.check(out)

    counters = harness.counters_dict(out.store)
    rec = {
        "workload": name, "scenario": scenario, "n_agents": n_agents,
        "engine": engine, "kernel_mode": kcommon.kernel_mode(),
        "vmapped": False, "n_replicas": 1,
        "table_geometry": _geometry(bench.wl), **_api_cols(bench.wl),
        "iters_timed": iters,
        "compile_s": round(compile_s, 4),
        "steady_s_per_run": round(float(np.mean(times)), 5),
        "steady_s_per_replica": round(float(np.mean(times)), 5),
        **_churn_cols(), **_latency_cols(out.store),
        **_traffic_cols(bench.wl, [check]),
        "events": int(out.rounds),
        "check_ok": bool(check["ok"]),
        "check_fails": int(check["check_fails"]),
        "makespan": counters["makespan"],
        "counters": counters,
        "_trace_store": out.store,
    }
    _request_latency(rec, [check])
    return rec


# ---------------- churned robustness cell (schema v5, DESIGN.md §10) -------

def measure_churned_cell(iters):
    """The worksteal srsp bench with a pinned die-holding-lock crash
    (faults.crash_holding_lock, victim 0 at clock 5; CRASH churn event at
    clock 400 — tests/test_churn.py pins the same numbers) run on the
    batched ELASTIC engine.  srsp must COMPLETE despite the crash: the
    lease-expiry recovery drain reclaims the dead owner's dirty words and
    force-releases its leased lock, after which thieves drain its queue.
    `recovered` counts reclaimed agents, `lost_updates` check failures
    among survivors (must be 0 with recovery on)."""
    mod = workloads.get("worksteal")
    victim, at, evt = 0, 5.0, 400.0
    proto = faults.crash_holding_lock(P.get_protocol("srsp"), victim, at)

    def one():
        b = mod.build("srsp", 4, seed=3, proto=proto, n_chunks_max=12)
        eb = harness.make_elastic(b, events=[(evt, victim, "crash")])
        fin = harness.run_batched_elastic(eb.wl, eb.state, *eb.ops)
        jax.block_until_ready(fin.s.store.counters.cycles)
        return b.wl, fin, eb.check(fin)

    t0 = time.perf_counter()
    wl, fin, check = one()
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        wl, fin, check = one()
        times.append(time.perf_counter() - t0)

    counters = harness.counters_dict(fin.s.store)
    recovered = float(np.sum(np.asarray(fin.s.store.counters.recoveries)))
    rec = {
        "workload": "worksteal", "scenario": "srsp", "n_agents": 4,
        "engine": "batched_elastic", "kernel_mode": kcommon.kernel_mode(),
        "vmapped": False, "n_replicas": 1,
        "table_geometry": _geometry(wl), **_api_cols(wl),
        "iters_timed": iters,
        "compile_s": round(compile_s, 4),
        "steady_s_per_run": round(float(np.mean(times)), 5),
        "steady_s_per_replica": round(float(np.mean(times)), 5),
        **_churn_cols(churn_events=1, makespan=counters["makespan"],
                      recovered=recovered,
                      lost_updates=check["check_fails"]),
        **_latency_cols(fin.s.store), **_traffic_cols(wl, []),
        "events": int(check["events"]),
        "check_ok": bool(check["ok"]),
        "check_fails": int(check["check_fails"]),
        "makespan": counters["makespan"],
        "counters": counters,
        "_trace_store": fin.s.store,
    }
    _request_latency(rec, [])
    return rec


# -------- churned serving cell (schema v8, DESIGN.md §13 + §10) ------------

def measure_churned_serving(iters):
    """kv_serving under the pinned die-holding-lock crash
    (crash_holding_lock victim 0 at clock 30; CRASH churn event at clock
    180 — tests/test_kv_serving.py pins the same numbers) on the batched
    elastic engine, single page per agent so the wedged victim strands
    exactly one lock.  The recovery drain must write back the victim's
    committed pages and force-release the stranded lock, after which the
    survivors' Zipf-skewed lookups of the dead shard's hot page complete
    — self-check clean, no lost pages, no stale reads."""
    mod = workloads.get("kv_serving")
    victim, at, evt = 0, 30.0, 180.0
    proto = faults.crash_holding_lock(P.get_protocol("srsp"), victim, at)

    def one():
        b = mod.build("srsp", 4, seed=3, proto=proto, pages_per_agent=1)
        eb = harness.make_elastic(b, events=[(evt, victim, "crash")])
        fin = harness.run_batched_elastic(eb.wl, eb.state, *eb.ops)
        jax.block_until_ready(fin.s.store.counters.cycles)
        return b.wl, fin, eb.check(fin)

    t0 = time.perf_counter()
    wl, fin, check = one()
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        wl, fin, check = one()
        times.append(time.perf_counter() - t0)

    counters = harness.counters_dict(fin.s.store)
    recovered = float(np.sum(np.asarray(fin.s.store.counters.recoveries)))
    rec = {
        "workload": "kv_serving", "scenario": "srsp", "n_agents": 4,
        "engine": "batched_elastic", "kernel_mode": kcommon.kernel_mode(),
        "vmapped": False, "n_replicas": 1,
        "table_geometry": _geometry(wl), **_api_cols(wl),
        "iters_timed": iters,
        "compile_s": round(compile_s, 4),
        "steady_s_per_run": round(float(np.mean(times)), 5),
        "steady_s_per_replica": round(float(np.mean(times)), 5),
        **_churn_cols(churn_events=1, makespan=counters["makespan"],
                      recovered=recovered,
                      lost_updates=check["check_fails"]),
        **_latency_cols(fin.s.store), **_traffic_cols(wl, [check]),
        "events": int(check["events"]),
        "check_ok": bool(check["ok"]),
        "check_fails": int(check["check_fails"]),
        "makespan": counters["makespan"],
        "counters": counters,
        "_trace_store": fin.s.store,
    }
    _request_latency(rec, [check])
    return rec


# ---------------- remote-batch A/B (schema v4, DESIGN.md §9) ---------------

def measure_remote_batch(n_agents, n_seeds, iters, batched: bool):
    """producer_consumer_mc srsp cell with the batched remote twins vs
    with `faults.serialize_remote` (remote turns serialized).  In-process:
    the capability rides on the Protocol object, so the two arms compile
    as distinct static keys.  Modeled makespans must be IDENTICAL (the §9
    commutation rule); wall clock measures the co-scheduling win."""
    mod = workloads.get("producer_consumer_mc")
    proto = None if batched else faults.serialize_remote(
        P.get_protocol("srsp"))
    bench = mod.build("srsp", n_agents, seed=0, proto=proto)
    wl = bench.wl

    def states(base):
        seeds = jnp.arange(base, base + n_seeds, dtype=jnp.int32)
        return jax.vmap(lambda s: mod.init_state(wl, s))(seeds)

    t0 = time.perf_counter()
    out = harness.run_batched_many(wl, states(0))
    jax.block_until_ready(out.store.counters.cycles)
    compile_s = time.perf_counter() - t0
    times = []
    for it in range(max(1, iters)):
        st = states((it + 1) * n_seeds)
        t0 = time.perf_counter()
        out = harness.run_batched_many(wl, st)
        jax.block_until_ready(out.store.counters.cycles)
        times.append(time.perf_counter() - t0)
    checks = [mod.self_check(wl, jax.tree.map(lambda x: x[k], out))
              for k in range(n_seeds)]
    lane = _lane0(out)
    return {
        "workload": "producer_consumer_mc", "scenario": "srsp",
        "n_agents": n_agents, "engine": "batched", "n_replicas": n_seeds,
        "remote_batch": batched,
        "compile_s": round(compile_s, 4),
        "steady_s_per_run": round(float(np.mean(times)), 5),
        "events": int(lane.rounds),
        "check_ok": all(c["ok"] for c in checks),
        "makespan": float(harness.counters_dict(lane.store)["makespan"]),
    }


# ---------------- fused-engine A/B (schema v7, DESIGN.md §12) --------------

def measure_fuse(n_agents, n_seeds, iters, engine):
    """kv_directory srsp vmapped cell, engine="fused" vs "batched" in one
    process (engine selection is a function lookup, not an import-time
    flag, so both arms compile as distinct jit keys honestly).  The
    vmapped path is where the fusion win lives: under vmap the batched
    engine's cond branches ALL execute (two local turns + both remote
    forms per trip), the fused engine runs ONE masked local turn.
    Modeled makespans must be IDENTICAL (§12 equivalence in vivo)."""
    mod = workloads.get("kv_directory")
    run_many = harness.runner_many(engine)
    bench = mod.build("srsp", n_agents, seed=0)
    wl = bench.wl

    def states(base):
        seeds = jnp.arange(base, base + n_seeds, dtype=jnp.int32)
        return jax.vmap(lambda s: mod.init_state(wl, s))(seeds)

    t0 = time.perf_counter()
    out = run_many(wl, states(0))
    jax.block_until_ready(out.store.counters.cycles)
    compile_s = time.perf_counter() - t0
    times = []
    for it in range(max(1, iters)):
        st = states((it + 1) * n_seeds)
        t0 = time.perf_counter()
        out = run_many(wl, st)
        jax.block_until_ready(out.store.counters.cycles)
        times.append(time.perf_counter() - t0)
    checks = [mod.self_check(wl, jax.tree.map(lambda x: x[k], out))
              for k in range(n_seeds)]
    lane = _lane0(out)
    return {
        "workload": "kv_directory", "scenario": "srsp",
        "n_agents": n_agents, "engine": engine,
        "kernel_mode": kcommon.kernel_mode(), "n_replicas": n_seeds,
        "compile_s": round(compile_s, 4),
        "steady_s_per_run": round(float(np.mean(times)), 5),
        "events": int(lane.rounds),
        "check_ok": all(c["ok"] for c in checks),
        "makespan": float(harness.counters_dict(lane.store)["makespan"]),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workloads", nargs="+", default=["all"])
    ap.add_argument("--scenarios", nargs="+", default=DEFAULT_SCENARIOS)
    ap.add_argument("--sizes", nargs="+", type=int, default=[16, 64])
    ap.add_argument("--seeds", type=int, default=2,
                    help="replicas per vmapped cell (one compilation)")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--no-remote-batch-ab", action="store_true",
                    help="skip the batched-vs-serialized remote-turn A/B")
    ap.add_argument("--remote-batch-sizes", nargs="+", type=int,
                    default=[16, 64])
    ap.add_argument("--fused-scenarios", nargs="+", default=["srsp"],
                    help="scenarios that also get engine=fused grid rows "
                         "(schema v7; 'none' disables)")
    ap.add_argument("--no-fuse-ab", action="store_true",
                    help="skip the fused-vs-batched engine A/B")
    ap.add_argument("--fuse-sizes", nargs="+", type=int, default=[64, 256])
    ap.add_argument("--no-churn", action="store_true",
                    help="skip the churned crash-recovery cell")
    ap.add_argument("--no-serving", action="store_true",
                    help="skip the trace-driven serving sections "
                         "(schema v8: skewed-traffic comparison + scale "
                         "cell; the grid kv_serving rows still run)")
    ap.add_argument("--serving-sizes", nargs="+", type=int, default=[64])
    ap.add_argument("--serving-zipf", nargs="+", type=float,
                    default=[0.9, 1.2],
                    help="Zipf skew exponents for the serving comparison")
    ap.add_argument("--serving-requests", type=int, default=256,
                    help="requests per agent in each serving cell")
    ap.add_argument("--serving-seeds", type=int, default=2,
                    help="replicas per serving comparison cell")
    ap.add_argument("--serving-scale-replicas", type=int, default=64,
                    help="replicas for the >=1e6-request scale cell "
                         "(0 disables; 64 x n=64 x 256 req = 1,048,576 "
                         "simulated requests per scenario)")
    ap.add_argument("--trace-out", default="TRACE_sweep.json",
                    help="Perfetto trace JSON for one traced srsp cell "
                         "(only written under REPRO_TRACE=1)")
    ap.add_argument("--out", default="BENCH_workloads.json")
    args = ap.parse_args(argv)
    compile_cache.enable()

    names = workloads.available() if args.workloads == ["all"] \
        else args.workloads
    wd = CellWatchdog()

    runs = []
    trace_store, trace_label = None, None

    def harvest(rec, label):
        """Pop the stashed final store; keep the first traced srsp cell
        for the Perfetto export."""
        nonlocal trace_store, trace_label
        store = rec.pop("_trace_store", None)
        if (store is not None and trace_store is None
                and rec["scenario"] == "srsp" and rec["trace_events"]):
            trace_store, trace_label = store, label

    fused_scens = [] if args.fused_scenarios == ["none"] \
        else args.fused_scenarios
    for name in names:
        mod = workloads.get(name)
        for n_agents in args.sizes:
            for scen in args.scenarios:
                engines = ["batched"] + (["fused"] if scen in fused_scens
                                         else [])
                for engine in engines:
                    label = f"{name}/{scen}/n={n_agents}/{engine}"
                    t0 = time.perf_counter()
                    wd.start(label)
                    with spans.span(f"cell:{label}"):
                        if mod.VMAPPABLE:
                            rec = measure_vmapped(mod, name, scen, n_agents,
                                                  args.seeds, args.iters,
                                                  engine)
                        else:
                            rec = measure_host_init(mod, name, scen,
                                                    n_agents, args.iters,
                                                    engine)
                    wd.stop()
                    harvest(rec, label)
                    rec["bench_wall_s"] = round(time.perf_counter() - t0, 2)
                    runs.append(rec)
                    print(f"{label}: "
                          f"compile={rec['compile_s']:.2f}s "
                          f"steady={rec['steady_s_per_run'] * 1e3:.1f}ms "
                          f"makespan={rec['makespan']:.0f} "
                          f"check_ok={rec['check_ok']}", flush=True)
            jax.clear_caches()   # per-size programs are large on CPU

    if not args.no_churn:
        label = "worksteal/srsp+crash/churned"
        wd.start(label)
        with spans.span(f"cell:{label}"):
            rec = measure_churned_cell(args.iters)
        wd.stop()
        harvest(rec, label)
        runs.append(rec)
        print(f"churned worksteal/srsp (crash victim 0): "
              f"check_ok={rec['check_ok']} recovered={rec['recovered']:.0f} "
              f"lost_updates={rec['lost_updates']} "
              f"churn_rate={rec['churn_rate']}/kcycle", flush=True)
        jax.clear_caches()

    if not args.no_churn and "kv_serving" in names:
        label = "kv_serving/srsp+crash/churned"
        wd.start(label)
        with spans.span(f"cell:{label}"):
            rec = measure_churned_serving(args.iters)
        wd.stop()
        harvest(rec, label)
        runs.append(rec)
        print(f"churned kv_serving/srsp (crash victim 0): "
              f"check_ok={rec['check_ok']} recovered={rec['recovered']:.0f} "
              f"lost_updates={rec['lost_updates']} "
              f"completed={rec['completed']}/{rec['offered_load']}",
              flush=True)
        jax.clear_caches()

    # ---- trace-driven serving sections (schema v8, DESIGN.md §13) ----
    serving = []
    serving_comparisons = {}
    if not args.no_serving:
        kv_mod = workloads.get("kv_serving")
        for n in args.serving_sizes:
            for s in args.serving_zipf:
                tc = TrafficConfig(requests_per_agent=args.serving_requests,
                                   zipf_s=s, gap_mean=8.0, burstiness=4.0,
                                   remote_frac=0.03)
                cell = {}
                for scen, engine in (("srsp", "batched"), ("srsp", "fused"),
                                     ("rsp", "batched")):
                    label = (f"serving/kv_serving/{scen}/zipf={s}"
                             f"/n={n}/{engine}")
                    t0 = time.perf_counter()
                    wd.start(label)
                    with spans.span(f"cell:{label}"):
                        rec = measure_vmapped(
                            kv_mod, "kv_serving", scen, n,
                            args.serving_seeds, args.iters, engine,
                            build_kw={"traffic": tc})
                    wd.stop()
                    rec.pop("_trace_store", None)
                    rec["bench_wall_s"] = round(time.perf_counter() - t0, 2)
                    serving.append(rec)
                    cell[(scen, engine)] = rec
                    print(f"{label}: "
                          f"steady={rec['steady_s_per_run']:.2f}s "
                          f"completed={rec['completed']}"
                          f"/{rec['offered_load']} "
                          f"p99={rec['latency_p99']} "
                          f"check_ok={rec['check_ok']}", flush=True)
                jax.clear_caches()
                sb = cell[("srsp", "batched")]
                sf = cell[("srsp", "fused")]
                rb = cell[("rsp", "batched")]
                # same (seed, config) trace replayed through both engines:
                # the fused trip is bitwise the batched schedule, so every
                # modeled column must agree exactly
                assert sf["makespan"] == sb["makespan"], (sf, sb)
                assert sf["completed"] == sb["completed"], (sf, sb)
                assert sf["latency_p99"] == sb["latency_p99"], (sf, sb)
                serving_comparisons[f"serving/kv_serving/zipf={s}/n={n}"] = {
                    "srsp_vs_rsp_makespan": round(
                        rb["makespan"] / sb["makespan"], 3),
                    "srsp_vs_rsp_p99": round(
                        rb["latency_p99"] / max(sb["latency_p99"], 1.0), 3),
                    "engines_bitwise": True,
                    "offered_load": sb["offered_load"],
                    "completed": sb["completed"]}

        if args.serving_scale_replicas > 0:
            tc = TrafficConfig(requests_per_agent=256, zipf_s=1.2,
                               gap_mean=8.0, burstiness=4.0,
                               remote_frac=0.01)
            n = 64
            scale = {}
            for scen in ("srsp", "rsp", "baseline"):
                label = f"serving-scale/kv_serving/{scen}/n={n}/fused"
                t0 = time.perf_counter()
                wd.start(label)
                with spans.span(f"cell:{label}"):
                    rec = measure_vmapped(
                        kv_mod, "kv_serving", scen, n,
                        args.serving_scale_replicas, 1, "fused",
                        build_kw={"traffic": tc})
                wd.stop()
                rec.pop("_trace_store", None)
                rec["bench_wall_s"] = round(time.perf_counter() - t0, 2)
                serving.append(rec)
                scale[scen] = rec
                wall = rec["steady_s_per_run"]
                print(f"{label}: {rec['completed']}/{rec['offered_load']} "
                      f"requests in {wall:.1f}s "
                      f"({rec['completed'] / max(wall, 1e-9):,.0f} req/s) "
                      f"p99={rec['latency_p99']} "
                      f"check_ok={rec['check_ok']}", flush=True)
                jax.clear_caches()
            assert all(r["check_ok"] for r in scale.values()), scale
            serving_comparisons[f"serving_scale/kv_serving/zipf=1.2/n={n}"] \
                = {"offered_load": scale["srsp"]["offered_load"],
                   "completed": scale["srsp"]["completed"],
                   "all_checks_ok": True,
                   "srsp_vs_rsp_makespan": round(
                       scale["rsp"]["makespan"]
                       / scale["srsp"]["makespan"], 3),
                   "srsp_vs_rsp_p99": round(
                       scale["rsp"]["latency_p99"]
                       / max(scale["srsp"]["latency_p99"], 1.0), 3),
                   "srsp_vs_baseline_makespan": round(
                       scale["baseline"]["makespan"]
                       / scale["srsp"]["makespan"], 3)}

    trace_file = None
    if trace_store is not None and args.trace_out:
        obs_export.write_trace(args.trace_out, trace_store,
                               label=trace_label,
                               stragglers=wd.stragglers)
        trace_file = args.trace_out
        print(f"wrote {args.trace_out} (traced cell: {trace_label})")

    def find(name, scen, n, engine="batched"):
        for r in runs:
            if (r["workload"], r["scenario"], r["n_agents"],
                    r["engine"]) == (name, scen, n, engine) \
                    and not r["churn_events"]:
                return r
        return None

    # paper-style protocol comparisons on modeled makespan + L2 traffic
    comparisons = {}
    comparisons.update(serving_comparisons)
    churned = [r for r in runs if r["churn_events"]]
    for r in churned:
        comparisons[f"churn/{r['workload']}/n={r['n_agents']}"] = {
            "completes_under_crash": bool(r["check_ok"]),
            "recovered": r["recovered"],
            "lost_updates": r["lost_updates"]}
    for name in names:
        for n in args.sizes:
            srsp = find(name, "srsp", n)
            rsp = find(name, "rsp", n)
            base = find(name, "baseline", n)
            if not srsp:
                continue
            entry = {}
            if rsp:
                entry["srsp_vs_rsp_makespan"] = round(
                    rsp["makespan"] / srsp["makespan"], 3)
                entry["srsp_vs_rsp_l2"] = round(
                    rsp["counters"]["l2_accesses"]
                    / max(srsp["counters"]["l2_accesses"], 1.0), 3)
            if base:
                entry["srsp_vs_baseline_makespan"] = round(
                    base["makespan"] / srsp["makespan"], 3)
            comparisons[f"{name}/n={n}"] = entry

    # fused grid rows: the fused engine is bitwise the batched schedule
    # (tests/test_engine_equivalence.py) — a diverging makespan here is a
    # broken build, not a data point
    for name in names:
        for n in args.sizes:
            for scen in fused_scens:
                fus = find(name, scen, n, "fused")
                bat = find(name, scen, n, "batched")
                if not fus or not bat:
                    continue
                assert fus["makespan"] == bat["makespan"], (fus, bat)
                comparisons[f"fused/{name}/{scen}/n={n}"] = {
                    "makespan_equal": True,
                    "steady_speedup_fused": round(
                        bat["steady_s_per_run"]
                        / fus["steady_s_per_run"], 3)}

    remote_batch_ab = []
    if not args.no_remote_batch_ab:
        for n in args.remote_batch_sizes:
            for batched in (True, False):
                rec = measure_remote_batch(n, args.seeds, args.iters,
                                           batched)
                remote_batch_ab.append(rec)
                print(f"remote_batch n={n} batched={batched}: "
                      f"steady={rec['steady_s_per_run'] * 1e3:.1f}ms "
                      f"makespan={rec['makespan']:.0f} "
                      f"check_ok={rec['check_ok']}", flush=True)
            jax.clear_caches()
        for n in args.remote_batch_sizes:
            on = next(r for r in remote_batch_ab
                      if r["n_agents"] == n and r["remote_batch"])
            off = next(r for r in remote_batch_ab
                       if r["n_agents"] == n and not r["remote_batch"])
            # §9 commutation rule holding in vivo: co-scheduled remote
            # turns must not change the modeled schedule at all
            assert on["makespan"] == off["makespan"], (on, off)
            comparisons[f"remote_batch/n={n}"] = {
                "makespan_equal": True,
                "steady_speedup_batched": round(
                    off["steady_s_per_run"] / on["steady_s_per_run"], 3)}

    fuse_ab = []
    if not args.no_fuse_ab:
        for n in args.fuse_sizes:
            for engine in ("fused", "batched"):
                rec = measure_fuse(n, args.seeds, args.iters, engine)
                fuse_ab.append(rec)
                print(f"fuse n={n} engine={engine}: "
                      f"steady={rec['steady_s_per_run'] * 1e3:.1f}ms "
                      f"makespan={rec['makespan']:.0f} "
                      f"check_ok={rec['check_ok']}", flush=True)
            jax.clear_caches()
        for n in args.fuse_sizes:
            on = next(r for r in fuse_ab
                      if r["n_agents"] == n and r["engine"] == "fused")
            off = next(r for r in fuse_ab
                       if r["n_agents"] == n and r["engine"] == "batched")
            # §12 equivalence in vivo: the fused trip must not change the
            # modeled schedule at all
            assert on["makespan"] == off["makespan"], (on, off)
            comparisons[f"fuse/n={n}"] = {
                "makespan_equal": True,
                "steady_speedup_fused": round(
                    off["steady_s_per_run"] / on["steady_s_per_run"], 3)}

    doc = {
        "bench": "workloads_sweep",
        "schema_version": SCHEMA_VERSION,
        "metric_note": "compile_s is jit trace+compile+first run, reported "
                       "separately from steady_s_per_run (fresh states, "
                       "cached program). Protocol comparisons use modeled "
                       "makespan (max per-agent cycles), the paper's "
                       "metric; wall clock measures the engine. scope_only "
                       "check_ok=false on remote-turn workloads is the "
                       "expected staleness demo. Every workload issues "
                       "ops through the scoped ISA (api=scoped, DESIGN.md "
                       "SS9). srsp>rsp holds on every workload and widens "
                       "with n_agents (the paper's claim). With the "
                       "set-associative aging PA-TBL and the "
                       "filtered-probe charging rule (DESIGN.md SS8), "
                       "srsp>=baseline on kv_directory, reader_lock and "
                       "worksteal. producer_consumer stays below baseline "
                       "by construction: its always-hot drainers pay "
                       "srsp's probe round on their critical path in BOTH "
                       "scenarios. The multi-consumer variant "
                       "(producer_consumer_mc: partitioned victims, "
                       "drains co-scheduled via the batched remote twins) "
                       "parallelizes the remote work itself — makespan "
                       "goes ~flat in n (4072 at n=64 vs 31680 "
                       "single-consumer) and the srsp/baseline ratio "
                       "improves 0.87->0.94 at n=64 — but does NOT reach "
                       "parity: co-scheduling removes the drain "
                       "serialization, not the per-drain probe overhead, "
                       "which remains additive on each drainer (ROADMAP "
                       "follow-up outcome, recorded either way). "
                       "remote_batch_ab asserts batched and serialized "
                       "remote turns produce IDENTICAL makespans (the SS9 "
                       "commutation rule in vivo); its wall-clock "
                       "steady_speedup_batched is CPU-simulator noise "
                       "prone (fewer while-trips vs per-trip dedup "
                       "overhead; ~1.8x at n=16, ~1.0x at n=64 here). "
                       "Schema v5 (DESIGN.md SS10): churn_events/"
                       "churn_rate/recovered/lost_updates columns; the "
                       "engine=batched_elastic cell injects a "
                       "die-holding-lock crash and srsp completes via the "
                       "lease-expiry recovery drain with lost_updates=0 "
                       "among survivors; zero-churn cells are bitwise "
                       "identical to the plain engines (tests/"
                       "test_churn.py). Schema v6 (DESIGN.md SS11): "
                       "latency_p50/p95/p99/latency_turns are "
                       "conservative upper-edge percentiles of the "
                       "per-turn modeled-latency histogram and "
                       "trace_events/trace_dropped the event-ring "
                       "occupancy, populated only under REPRO_TRACE=1 "
                       "(tracing charges nothing: every other column is "
                       "bitwise unchanged by the flag); stragglers lists "
                       "watchdog-flagged slow cells and one traced srsp "
                       "cell is exported as Perfetto JSON (--trace-out). "
                       "Schema v7 (DESIGN.md SS12): engine=fused grid rows "
                       "time the one-kernel batched trip (bitwise the "
                       "batched schedule — asserted on every fused cell "
                       "and in fuse_ab); kernel_mode records the "
                       "once-per-process kernel dispatch (pallas/ref/"
                       "interpret) so an interpret-mode number can never "
                       "masquerade as a measurement. The fusion win is "
                       "structural on the vmapped path (batched executes "
                       "both cond branches under vmap, fused runs ONE "
                       "masked local turn). The unvmapped CPU rows "
                       "(worksteal) trade the other way: lax.cond "
                       "branches are lazy there, so the batched engine "
                       "skips the n x n remote-dedup math whenever a "
                       "local batch exists while the fused plan computes "
                       "it every trip — those rows can dip below 1.0x "
                       "(0.80x at n=64); the vmapped rows and fuse_ab "
                       "carry the perf claim. Schema v8 (DESIGN.md SS13): "
                       "offered_load/completed/zipf_s/burstiness columns "
                       "on trace-driven cells (null elsewhere) and "
                       "latency_source marks whether latency_p50/p95/p99 "
                       "summarize per-request completion latency "
                       "(='requests', always on for trace-driven cells: "
                       "completion clock minus arrival clock from the "
                       "replayed trace) or the per-turn REPRO_TRACE "
                       "histogram (='turns'). The serving section replays "
                       "the SAME (seed, config) Zipf+bursty trace through "
                       "the batched and fused engines (asserted equal "
                       "makespan/completed/p99) and reports "
                       "srsp_vs_rsp_makespan and srsp_vs_rsp_p99 under "
                       "skew s in {0.9, 1.2}; the scale cell pushes "
                       ">=1e6 simulated requests per scenario through the "
                       "vmapped fused path with self-checks green on "
                       "srsp/rsp/baseline. The churned kv_serving cell "
                       "crashes a shard owner holding its page lock "
                       "mid-trace: the lease recovery drain must "
                       "force-release it and survivors finish with no "
                       "lost pages and no stale reads.",
        "backend": jax.default_backend(),
        "donate_buffers": harness.DONATE,
        "packed_metadata": P.PACKED,
        "kernel_mode": kcommon.kernel_mode(),
        "fuse_enabled": harness.FUSE,
        "trace": {"enabled": T.TRACE, "capacity": T.default_cap(),
                  "file": trace_file, "cell": trace_label},
        "stragglers": wd.stragglers,
        "config": {"workloads": names, "scenarios": args.scenarios,
                   "sizes": args.sizes, "seeds": args.seeds,
                   "iters": args.iters,
                   "serving": None if args.no_serving else {
                       "sizes": args.serving_sizes,
                       "zipf": args.serving_zipf,
                       "requests_per_agent": args.serving_requests,
                       "seeds": args.serving_seeds,
                       "scale_replicas": args.serving_scale_replicas,
                       "gap_mean": 8.0, "burstiness": 4.0}},
        "runs": runs,
        "serving": serving,
        "remote_batch_ab": remote_batch_ab,
        "fuse_ab": fuse_ab,
        "comparisons": comparisons,
    }
    wd.close()
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out}")
    for k, v in comparisons.items():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
