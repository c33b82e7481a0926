"""Work-stealing load balancing — the first registered workload.

This is the paper's evaluation harness (§5.1): a lock-free-style
work-stealing runtime (Cederman & Tsigas [10]) where each work-group owns a
task queue; owners dequeue from the tail with *local-scope* synchronization
and thieves steal from the head with *remote-scope* (or global-scope)
synchronization.  Queue words — lock, head, tail, task entries — live inside
the protocol's simulated memory, so a protocol bug produces stale task ids /
lost or duplicated chunks, which the harness detects (``proc_errors``).

Five scenarios (paper §5.1):
    baseline     no stealing, global-scope sync on every queue op
    scope_only   no stealing, local-scope sync (cheap but imbalanced)
    steal_only   stealing with global-scope sync everywhere
    rsp          local sync for owners; original flush-all/inv-all RSP
                 promotion for steals
    srsp         local sync for owners; this paper's selective promotion

Tasks are chunks of graph nodes; per-chunk work cycles follow the cost
model (task_base + per_edge * chunk_edges) and chunk outputs are written
through the simulated memory so flush traffic is real.

Scheduling is delegated to the workload-agnostic harness
(`workloads/harness.py`, extracted from this module verbatim — DESIGN.md
§4/§7): pop turns of distinct owners commute (`local_turn`), steals
serialize (`remote_turn`), and the batched engine's fence uses `rem` —
the summed base work still queued per owner — as the lower bound before
an owner can turn thief.  Counters and solutions are bitwise identical
between engines and unchanged from the pre-extraction engine
(tests/test_engine_equivalence.py).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import ops as O
from repro.core import protocol as P
from repro.core import costmodel, sfifo, tables
from repro.data.graphs import CSRGraph, collab_degrees
from repro.obs import spans
from repro.workloads import harness

QMETA = 16  # words reserved at the head of each queue (lock/head/tail block)

VMAPPABLE = False   # init_state enqueues host-side (numpy chunking)


@dataclasses.dataclass(frozen=True)
class WSConfig:
    n_wgs: int = 64
    chunk_cap: int = 32          # nodes per task chunk
    n_chunks_max: int = 512      # static bound on chunks per iteration
    fifo_cap: int = 16
    lr_tbl: tables.TableGeometry = tables.LR_GEOMETRY
    pa_tbl: tables.TableGeometry = tables.PA_GEOMETRY
    cold_factor: float = 1.0     # refill penalty scale after an invalidation
    params: costmodel.CostParams = dataclasses.field(default_factory=costmodel.CostParams)

    @property
    def qcap(self) -> int:
        return self.n_chunks_max  # worst-case skew bound

    @property
    def qstride(self) -> int:
        s = QMETA + self.qcap
        return (s + 15) // 16 * 16

    @property
    def data_base(self) -> int:
        return self.n_wgs * self.qstride

    @property
    def n_words(self) -> int:
        w = self.data_base + self.n_chunks_max * self.chunk_cap
        return (w + 15) // 16 * 16

    def proto_cfg(self) -> P.ProtoConfig:
        return P.ProtoConfig(n_caches=self.n_wgs, n_words=self.n_words,
                             fifo_cap=self.fifo_cap, lr_tbl=self.lr_tbl,
                             pa_tbl=self.pa_tbl, params=self.params)


# name -> (protocol, steal?).  A registry: unknown scenario names raise
# with the registered list instead of a bare KeyError.
SCENARIOS = P.Registry("worksteal scenario")
SCENARIOS.update({
    "baseline":   ("global", False),
    "scope_only": ("local", False),
    "steal_only": ("global", True),
    "rsp":        ("rsp", True),
    "srsp":       ("srsp", True),
})


class SimState(NamedTuple):
    store: P.Store
    qsize: jnp.ndarray      # [n_wgs] i32 bookkeeping occupancy
    processed: jnp.ndarray  # [n_chunks_max] i32 — from values read THROUGH the store
    last_inv: jnp.ndarray   # [n_wgs] f32 inv_per_cache snapshot at last processed chunk
    rounds: jnp.ndarray     # [] i32
    rem: jnp.ndarray        # [n_wgs] f32 Σ base work of chunks still in queue —
                            # a lower bound on cycles before this wg can steal
                            # (drives the batched scheduler's fence, DESIGN.md §4)


ENGINES = ("batched", "serial", "fused")


# --------------------------------------------------------------------------
# workload spec functions (module-level so Workloads hash/compare by value)
# --------------------------------------------------------------------------

def _max_events(ws: WSConfig) -> int:
    return 2 * ws.n_chunks_max + 4 * ws.n_wgs


def _can_pop(wl, s: SimState, chunk_count, chunk_edges):
    return s.qsize > 0


def _can_steal(wl, s: SimState, chunk_count, chunk_edges):
    if not wl.has_remote:
        return jnp.zeros_like(s.qsize, bool)
    return (s.qsize == 0) & (jnp.sum(s.qsize) > 0)


def _steal_bound(wl, s: SimState, chunk_count, chunk_edges):
    return s.rem


def _live(wl, s: SimState, chunk_count, chunk_edges):
    return (jnp.sum(s.qsize) > 0) & (s.rounds < _max_events(wl.cfg))


def _steal_or_idle_turn(wl, state: SimState, wg, chunk_count, chunk_edges
                        ) -> SimState:
    """One serial turn for a work-group with an empty queue: steal from the
    fullest victim (remote-scope sync) or idle.  Steals broadcast probes /
    flushes to other caches, so they never batch (DESIGN.md §4)."""
    ws, proto = wl.cfg, wl.proto
    cfg = ws.proto_cfg()
    p = cfg.params
    sizes_others = state.qsize.at[wg].set(0)
    victim = jnp.argmax(sizes_others).astype(jnp.int32)
    can_steal = jnp.asarray(wl.has_remote) & (sizes_others[victim] > 0)

    def do_steal(st):
        lock = victim * ws.qstride
        hot = harness.one_hot(ws.n_wgs, wg)
        st, oldv = O.acquire(proto, cfg, st, hot, lock, 0, 1, scope=O.REMOTE)
        # lock-sensitive: a steal that loses the CAS takes nothing and
        # leaves the queue intact.  Healthy runs never lose it — turns are
        # atomic, so every lock is free between turns — but a crashed
        # owner's stuck lock (faults.crash_holding_lock) fences thieves
        # out until the recovery drain force-releases it (DESIGN.md §10).
        got = oldv[wg] == 0
        st, head = P.load(cfg, st, wg, lock + 1)
        st, tail = P.load(cfg, st, wg, lock + 2)
        has = got & (head < tail)
        slot = jnp.clip(head, 0, ws.qcap - 1)
        st, task = P.load(cfg, st, wg, lock + QMETA + slot)
        st, _ = P.store_word(cfg, st, wg, lock + 1, head + 1, guard=has)
        st = O.release(proto, cfg, st, hot & got, lock, 0, scope=O.REMOTE)
        c = st.counters
        st = st._replace(counters=c._replace(
            steals=c.steals + has.astype(jnp.float32)))
        return st, jnp.where(has, task - 1, -1)

    def do_idle(st):
        return st, jnp.int32(-1)

    store, chunk = lax.cond(can_steal, do_steal, do_idle, state.store)
    # bookkeeping shrinks only on an actual take (chunk >= 0): a lock-fenced
    # steal must not hide the stuck chunks from future thieves
    qsize = state.qsize.at[victim].add(jnp.where(can_steal & (chunk >= 0),
                                                 -1, 0))
    qsize = jnp.maximum(qsize, 0)

    # ------- process the stolen chunk (thief pays, victim's queue shrinks) --
    valid = (chunk >= 0) & (chunk < ws.n_chunks_max)
    safe = jnp.clip(chunk, 0, ws.n_chunks_max - 1)
    processed = state.processed.at[safe].add(valid.astype(jnp.int32))
    count = jnp.where(valid, chunk_count[safe], 0)
    edges = jnp.where(valid, chunk_edges[safe], 0.0)
    base_work = p.task_base + p.per_edge * edges
    # the stolen chunk leaves the victim's queue: maintain the remaining-work
    # lower bound the batched scheduler fences on
    rem = state.rem.at[victim].add(-jnp.where(valid, base_work, 0.0))
    rem = jnp.maximum(rem, 0.0)
    # cold-cache refill penalty if the thief's L1 was invalidated since its
    # last chunk (models the post-invalidate miss storm, DESIGN.md §2)
    inv_now = store.counters.inv_per_cache[wg]
    was_cold = inv_now > state.last_inv[wg]
    touched_lines = count.astype(jnp.float32) + edges / 4.0
    work = base_work + jnp.where(was_cold, ws.cold_factor
                                 * touched_lines * (p.l2_lat / 4.0), 0.0)
    c = store.counters
    c = c._replace(cycles=c.cycles.at[wg].add(jnp.where(valid, work, 0.0)))
    store = store._replace(counters=c)
    last_inv = state.last_inv.at[wg].set(
        jnp.where(valid, inv_now, state.last_inv[wg]))

    # chunk output writes go through the memory system (flushable dirt)
    dblk = ws.chunk_cap // 16 + 1

    def wr(st, kk):
        a = ws.data_base + safe * ws.chunk_cap + kk * 16
        g = valid & ((kk * 16) < count)
        st, _ = P.store_word(cfg, st, wg, jnp.clip(a, 0, cfg.n_words - 1),
                             chunk, guard=g)
        return st, None

    store, _ = lax.scan(wr, store, jnp.arange(dblk, dtype=jnp.int32))
    return SimState(store, qsize, processed, last_inv, state.rounds + 1, rem)


def _pop_batch_turn(wl, state: SimState, mask, chunk_count, chunk_edges
                    ) -> SimState:
    """Execute one pop turn for every work-group in `mask` at once.
    Identical per-lane op order to the serial pop branch; every op is a
    masked multi-cache protocol op, so a batch of k pops costs one set of
    array ops instead of k while-loop trips."""
    ws, proto = wl.cfg, wl.proto
    cfg = ws.proto_cfg()
    p = cfg.params
    n = ws.n_wgs
    wgs = jnp.arange(n, dtype=jnp.int32)
    locks = wgs * ws.qstride

    st = state.store
    st, oldv = O.acquire(proto, cfg, st, mask, locks, 0, 1, scope=O.LOCAL)
    # lock-sensitive pops (see _steal_or_idle_turn): a lane that loses its
    # own-queue CAS — impossible healthy, real once a crash strands the
    # lock at 1 — takes nothing and releases nothing
    got = mask & (oldv == 0)
    st, tail = O.load(cfg, st, mask, locks + 2)
    st, head = O.load(cfg, st, mask, locks + 1)
    has = got & (head < tail)
    slot = jnp.clip(tail - 1, 0, ws.qcap - 1)
    st, task = O.load(cfg, st, mask, locks + QMETA + slot)
    st, _ = O.store(cfg, st, has, locks + 2, tail - 1)
    st = O.release(proto, cfg, st, got, locks, 0, scope=O.LOCAL)
    chunk = jnp.where(has, task - 1, -1)

    qsize = jnp.maximum(state.qsize - has.astype(jnp.int32), 0)

    # ------- process the chunks -------
    valid = (chunk >= 0) & (chunk < ws.n_chunks_max)
    safe = jnp.clip(chunk, 0, ws.n_chunks_max - 1)
    processed = state.processed.at[safe].add(valid.astype(jnp.int32))
    count = jnp.where(valid, chunk_count[safe], 0)
    edges = jnp.where(valid, chunk_edges[safe], 0.0)
    base_work = p.task_base + p.per_edge * edges
    rem = jnp.maximum(state.rem - jnp.where(valid, base_work, 0.0), 0.0)
    inv_now = st.counters.inv_per_cache
    was_cold = inv_now > state.last_inv
    touched_lines = count.astype(jnp.float32) + edges / 4.0
    work = base_work + jnp.where(was_cold, ws.cold_factor * touched_lines
                                 * (p.l2_lat / 4.0), 0.0)
    c = st.counters
    c = c._replace(cycles=c.cycles + jnp.where(valid, work, 0.0))
    st = st._replace(counters=c)
    last_inv = jnp.where(valid, inv_now, state.last_inv)

    # chunk output writes go through the memory system (flushable dirt)
    dblk = ws.chunk_cap // 16 + 1
    for kk in range(dblk):
        a = ws.data_base + safe * ws.chunk_cap + kk * 16
        g = valid & ((kk * 16) < count)
        st, _ = P.b_store_word(cfg, st, g,
                               jnp.clip(a, 0, cfg.n_words - 1), chunk)
    rounds = state.rounds + jnp.sum(mask.astype(jnp.int32))
    return SimState(st, qsize, processed, last_inv, rounds, rem)


def build_workload(ws: WSConfig, proto: P.Protocol, steal: bool
                   ) -> harness.Workload:
    """Bind the work-steal spec: pops commute, steals serialize, `rem`
    fences future thieves (DESIGN.md §4)."""
    return harness.Workload(
        name="worksteal", cfg=ws, proto=proto, has_remote=steal,
        can_local=_can_pop, can_remote=_can_steal,
        local_turn=_pop_batch_turn, remote_turn=_steal_or_idle_turn,
        remote_bound=_steal_bound, live=_live)


class WorkStealSim:
    """Round-based simulator for one scenario.

    The jit-compiled programs live at module level with *fine-grained*
    static keys, so they are shared wherever the traced computation is
    identical: two sims with the same WSConfig share the enqueue program
    whenever their owner-side protocol matches (srsp/rsp/scope_only all use
    local-scope owners; baseline/steal_only use global), across instances,
    apps and engines."""

    def __init__(self, ws: WSConfig, scenario: str, engine: str = "batched"):
        if scenario not in SCENARIOS:
            raise ValueError(f"unknown scenario {scenario!r}; "
                             f"registered: {sorted(SCENARIOS)}")
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r}; "
                             f"registered: {sorted(ENGINES)}")
        self.ws = ws
        self.scenario = scenario
        self.engine = engine
        proto_name, steal = SCENARIOS[scenario]
        self.proto = P.get_protocol(proto_name)
        self.steal = steal
        self.cfg = ws.proto_cfg()
        self._enqueue = partial(_enqueue_jit, ws, self.proto.acquire_loc_b,
                                self.proto.release_loc_b)
        self.workload = build_workload(ws, self.proto, steal)
        self._run_rounds = partial(harness.runner(engine), self.workload)

    def make_store(self) -> P.Store:
        return P.make_store(self.cfg)

    # ---------------- per-iteration driver ----------------
    def run_iteration(self, store: P.Store, frontier_nodes: np.ndarray,
                      degrees: np.ndarray, last_inv: jnp.ndarray):
        """Distribute `frontier_nodes` as chunks, enqueue, run rounds.

        Returns (store', last_inv', proc_errors, n_chunks)."""
        ws = self.ws
        n = len(degrees)
        plan = _chunk_plan(ws, frontier_nodes, degrees,
                           # ownership by node range
                           lambda c, sel, nc: int(sel[0]) * ws.n_wgs // n)
        store = self._enqueue(store, jnp.asarray(plan.owner),
                              jnp.asarray(plan.slot), jnp.asarray(plan.valid),
                              jnp.asarray(plan.n_enq))
        state = SimState(store=store, qsize=jnp.asarray(plan.n_enq),
                         processed=jnp.zeros(ws.n_chunks_max, jnp.int32),
                         last_inv=last_inv, rounds=jnp.int32(0),
                         rem=jnp.asarray(plan.rem))
        state = self._run_rounds(state, jnp.asarray(plan.count),
                                 jnp.asarray(plan.edges))
        proc = np.asarray(state.processed)
        errors = int(np.abs(proc[plan.valid] - 1).sum()
                     + proc[~plan.valid].sum())
        return state.store, state.last_inv, errors, plan.n_chunks


class ChunkPlan(NamedTuple):
    owner: np.ndarray
    slot: np.ndarray
    count: np.ndarray
    edges: np.ndarray   # f32
    valid: np.ndarray
    n_enq: np.ndarray
    rem: np.ndarray     # f32 per-owner Σ base work (the batched fence bound)
    n_chunks: int


def _chunk_plan(ws: WSConfig, frontier_nodes: np.ndarray, degrees: np.ndarray,
                owner_of) -> ChunkPlan:
    """Host-side chunking shared by run_iteration and the Bench contract;
    `owner_of(c, sel, n_chunks)` is the ownership policy."""
    nf = len(frontier_nodes)
    n_chunks = min((nf + ws.chunk_cap - 1) // ws.chunk_cap, ws.n_chunks_max)
    owner = np.zeros(ws.n_chunks_max, np.int32)
    count = np.zeros(ws.n_chunks_max, np.int32)
    edges = np.zeros(ws.n_chunks_max, np.float32)
    valid = np.zeros(ws.n_chunks_max, bool)
    for c in range(n_chunks):
        sel = frontier_nodes[c * ws.chunk_cap:(c + 1) * ws.chunk_cap]
        owner[c] = owner_of(c, sel, n_chunks)
        count[c] = len(sel)
        edges[c] = float(degrees[sel].sum())
        valid[c] = True
    # slot index within owner's queue
    slot = np.zeros(ws.n_chunks_max, np.int32)
    n_enq = np.zeros(ws.n_wgs, np.int32)
    for c in range(n_chunks):
        slot[c] = n_enq[owner[c]]
        n_enq[owner[c]] += 1
    p = ws.params
    # f32 arithmetic to match the engine's per-pop decrements exactly
    base_work = np.where(valid, np.float32(p.task_base)
                         + np.float32(p.per_edge) * edges, np.float32(0))
    rem = np.zeros(ws.n_wgs, np.float32)
    np.add.at(rem, owner, base_work.astype(np.float32))
    return ChunkPlan(owner, slot, count, edges.astype(np.float32), valid,
                     n_enq, rem, n_chunks)


# ---------------- enqueue (batch, one critical section per owner) ----------
@partial(jax.jit, static_argnums=(0, 1, 2),
         **({"donate_argnums": (3,)} if harness.DONATE else {}))
def _enqueue_jit(ws: WSConfig, oacq_b, orel_b, store: P.Store, enq_owner,
                 enq_slot, enq_valid, n_enq):
    """All owners enqueue at once: each work-group's critical section
    touches only its own queue words and its own cache, so every owner-side
    op runs as one masked multi-cache op.  The task-word sFIFO `touch` walk
    is a scan over *block offsets* (a handful) with all work-groups pushing
    in lockstep, not a scan over work-groups.

    Static key = (config, LOCAL-scope acquire/release table entries):
    scenarios whose protocols share the local-scope realization share
    this compiled program (srsp/rsp/scope_only, and baseline/steal_only),
    which a full-Protocol key would needlessly split."""
    cfg = ws.proto_cfg()
    n = ws.n_wgs
    W = cfg.block_words
    chunk_ids = jnp.arange(ws.n_chunks_max, dtype=jnp.int32)
    max_blk = ws.qcap // 16 + 2
    wgs = jnp.arange(n, dtype=jnp.int32)
    locks = wgs * ws.qstride
    every = jnp.ones((n,), bool)

    # acquire FIRST: a promoted acquire invalidates this cache, so
    # the task-word writes must land inside the critical section
    # (writing before the acquire broke the dirty⊆sFIFO invariant
    # and produced stale task reads — see tests/test_worksteal.py)
    st, _ = oacq_b(cfg, store, every, locks, 0, 1)
    # scatter every wg's task words (write-combining bulk store)
    addr = jnp.where(enq_valid, enq_owner * ws.qstride + QMETA + enq_slot,
                     jnp.int32(cfg.n_blocks * W))  # out of range -> drop
    ab, ao = addr // W, addr % W
    st = st._replace(
        l1=st.l1.at[enq_owner, ab, ao].set(chunk_ids + 1, mode="drop"),
        wvalid=P.plane_scatter_set(cfg, st.wvalid, enq_owner, ab, ao),
        wdirty=P.plane_scatter_set(cfg, st.wdirty, enq_owner, ab, ao))
    # record the task-word blocks in the sFIFO (write-combining path)
    first_blk = (locks + QMETA) // W
    no_tail = jnp.zeros((n,), bool)

    def touch(st, i):
        guard = (i * W) < n_enq
        f2, evicted, _ = jax.vmap(sfifo.push)(st.fifo, first_blk + i, no_tail)
        st = st._replace(fifo=P._mask_tree_rows(guard, f2, st.fifo))
        evicted = jnp.where(guard, evicted, jnp.int32(-1))
        st, _ = P.b_writeback(cfg, st, evicted, evicted >= 0)
        return st, None

    st, _ = lax.scan(touch, st, jnp.arange(max_blk, dtype=jnp.int32))
    st, _ = P.b_store_word(cfg, st, every, locks + 1, jnp.zeros((n,), jnp.int32))
    st, _ = P.b_store_word(cfg, st, every, locks + 2, n_enq)
    st = orel_b(cfg, st, every, locks, 0)
    c = st.counters
    c = c._replace(cycles=c.cycles
                   + n_enq.astype(jnp.float32) * cfg.params.l1_lat)
    return st._replace(counters=c)


# --------------------------------------------------------------------------
# applications (paper §5.1: PageRank, SSSP; MIS also mentioned)
# --------------------------------------------------------------------------

class AppResult(NamedTuple):
    name: str
    scenario: str
    makespan: float
    counters: dict
    proc_errors: int
    iterations: int
    wall_s: float
    solution: np.ndarray


def _edge_arrays(g: CSRGraph):
    rows = np.repeat(np.arange(g.n, dtype=np.int32), g.degrees)
    return rows, g.indices, g.weights


def run_app(app: str, g: CSRGraph, scenario: str, ws: WSConfig,
            max_iters: int = 8, seed: int = 0,
            engine: str = "batched") -> AppResult:
    sim = WorkStealSim(ws, scenario, engine)
    store = sim.make_store()
    last_inv = jnp.zeros((ws.n_wgs,), jnp.float32)
    rows, cols, w = _edge_arrays(g)
    rows_j, cols_j, w_j = jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(w)
    deg = jnp.asarray(np.maximum(g.degrees, 1))
    n = g.n
    t0 = time.perf_counter()
    errors = 0
    iters = 0

    if app == "pagerank":
        ranks = jnp.full((n,), 1.0 / n, jnp.float32)

        @jax.jit
        def bulk(r):
            contrib = r[cols_j] / deg[cols_j]
            s = jnp.zeros((n,), jnp.float32).at[rows_j].add(contrib)
            return 0.15 / n + 0.85 * s

        frontier = np.arange(n, dtype=np.int32)
        for it in range(max_iters):
            store, last_inv, e, _ = sim.run_iteration(store, frontier,
                                                      g.degrees, last_inv)
            errors += e
            ranks = bulk(ranks)
            iters += 1
        solution = np.asarray(ranks)

    elif app == "sssp":
        INF = np.int32(2**30)
        dist = jnp.full((n,), INF, jnp.int32).at[0].set(0)

        @jax.jit
        def bulk(d, fmask):
            cand = d[rows_j] + w_j
            cand = jnp.where(fmask[rows_j], cand, INF)
            nd = d.at[cols_j].min(cand)
            return nd, nd < d

        frontier_mask = np.zeros(n, bool)
        frontier_mask[0] = True
        dist_j = dist
        for it in range(max_iters):
            fnodes = np.nonzero(frontier_mask)[0].astype(np.int32)
            if len(fnodes) == 0:
                break
            store, last_inv, e, _ = sim.run_iteration(store, fnodes,
                                                      g.degrees, last_inv)
            errors += e
            dist_j, improved = bulk(dist_j, jnp.asarray(frontier_mask))
            frontier_mask = np.asarray(improved)
            iters += 1
        solution = np.asarray(dist_j)

    elif app == "mis":
        # Luby's algorithm: 0 undecided / 1 in MIS / 2 excluded
        status = jnp.zeros((n,), jnp.int32)
        key = jax.random.PRNGKey(seed)

        @jax.jit
        def bulk(st, k):
            und = st == 0
            prio = jax.random.uniform(k, (n,)) + jnp.where(und, 0.0, -10.0)
            nb_max = jnp.full((n,), -20.0).at[rows_j].max(
                jnp.where(und[cols_j], prio[cols_j], -20.0))
            join = und & (prio > nb_max)
            st = jnp.where(join, 1, st)
            excl = jnp.zeros((n,), bool).at[rows_j].max(join[cols_j])
            st = jnp.where((st == 0) & excl, 2, st)
            return st

        for it in range(max_iters * 3):
            und_nodes = np.nonzero(np.asarray(status) == 0)[0].astype(np.int32)
            if len(und_nodes) == 0:
                break
            store, last_inv, e, _ = sim.run_iteration(store, und_nodes,
                                                      g.degrees, last_inv)
            errors += e
            key, sub = jax.random.split(key)
            status = bulk(status, sub)
            iters += 1
        solution = np.asarray(status)
    else:
        raise ValueError(f"unknown app {app!r}")

    wall = time.perf_counter() - t0
    counters = harness.counters_dict(store)
    return AppResult(app, scenario, counters["makespan"], counters, errors,
                     iters, wall, solution)


def reference_solution(app: str, g: CSRGraph, max_iters: int = 8,
                       seed: int = 0) -> np.ndarray:
    """Single-threaded oracle — identical bulk math, no scheduler/protocol."""
    rows, cols, w = _edge_arrays(g)
    n = g.n
    deg = np.maximum(g.degrees, 1)
    if app == "pagerank":
        r = np.full(n, 1.0 / n, np.float32)
        for _ in range(max_iters):
            s = np.zeros(n, np.float32)
            np.add.at(s, rows, r[cols] / deg[cols])
            r = (0.15 / n + 0.85 * s).astype(np.float32)
        return r
    if app == "sssp":
        INF = np.int64(2**30)
        d = np.full(n, INF, np.int64)
        d[0] = 0
        fmask = np.zeros(n, bool)
        fmask[0] = True
        for _ in range(max_iters):
            if not fmask.any():
                break
            cand = np.where(fmask[rows], d[rows] + w, INF)
            nd = d.copy()
            np.minimum.at(nd, cols, cand)
            fmask = nd < d
            d = nd
        return d.astype(np.int32)
    if app == "mis":
        # same PRNG sequence as run_app's bulk
        status = jnp.zeros((n,), jnp.int32)
        key = jax.random.PRNGKey(seed)
        rows_j, cols_j = jnp.asarray(rows), jnp.asarray(cols)

        @jax.jit
        def bulk(st, k):
            und = st == 0
            prio = jax.random.uniform(k, (n,)) + jnp.where(und, 0.0, -10.0)
            nb_max = jnp.full((n,), -20.0).at[rows_j].max(
                jnp.where(und[cols_j], prio[cols_j], -20.0))
            join = und & (prio > nb_max)
            st = jnp.where(join, 1, st)
            excl = jnp.zeros((n,), bool).at[rows_j].max(join[cols_j])
            st = jnp.where((st == 0) & excl, 2, st)
            return st

        for _ in range(max_iters * 3):
            if not (np.asarray(status) == 0).any():
                break
            key, sub = jax.random.split(key)
            status = bulk(status, sub)
        return np.asarray(status)
    raise ValueError(app)


# --------------------------------------------------------------------------
# registry contract (workloads/__init__.py): build / init_state / self_check
# --------------------------------------------------------------------------

Bench = harness.Bench


def build(scenario: str, n_agents: int, seed: int = 0, *,
          proto: P.Protocol = None, **kw) -> harness.Bench:
    """Standard-contract bench: a one-iteration work-steal round over a
    synthetic collab graph sized so queues start half-full (steals happen).
    `proto` overrides the scenario's protocol table (fault injection)."""
    _, steal = SCENARIOS[scenario]
    p = harness.resolve_proto(scenario, proto)
    kw.setdefault("chunk_cap", 8)
    kw.setdefault("n_chunks_max", max(2 * n_agents, 8))
    ws = WSConfig(n_wgs=n_agents, **kw)
    with spans.span("inputs.graph") as sp:
        degrees, draws = collab_degrees(
            n=ws.n_chunks_max * ws.chunk_cap // 2, m=3, seed=1 + seed)
        sp.set_metadata(words=draws.words, rejections=draws.rejections)
    wl = build_workload(ws, p, steal)

    frontier = np.arange(len(degrees), dtype=np.int32)
    with spans.span("inputs.plan"):
        # skewed ownership: agent 0 owns half the chunks, the rest spread
        # round-robin — guarantees the imbalance that makes steals happen
        plan = _chunk_plan(ws, frontier, degrees,
                           lambda c, sel, nc: (0 if c < nc // 2
                                               else c % ws.n_wgs))
    with spans.span("inputs.store"):
        store = P.make_store(ws.proto_cfg())
        owner, slot, valid, n_enq, rem, count, edges = map(
            jnp.asarray, (plan.owner, plan.slot, plan.valid, plan.n_enq,
                          plan.rem, plan.count, plan.edges))
        processed = jnp.zeros(ws.n_chunks_max, jnp.int32)
        last_inv = jnp.zeros((ws.n_wgs,), jnp.float32)
    with spans.span("inputs.enqueue"):
        store = _enqueue_jit(ws, p.acquire_loc_b, p.release_loc_b, store,
                             owner, slot, valid, n_enq)
    state = SimState(store=store, qsize=n_enq, processed=processed,
                     last_inv=last_inv, rounds=jnp.int32(0), rem=rem)
    ops = (count, edges)

    def check(final: SimState) -> dict:
        proc = np.asarray(final.processed)
        fails = int(np.abs(proc[plan.valid] - 1).sum()
                    + proc[~plan.valid].sum())
        return {"ok": fails == 0, "check_fails": fails,
                "events": int(final.rounds)}

    return Bench(wl, state, ops, check)
