"""Old-vs-new engine equivalence (ISSUE 1 acceptance).

The serial engine (`engine="serial"`) preserves the seed engine's exact
event order — one work-group turn per while-loop trip, smallest clock acts
next — while the batched engine executes provably-commuting pop turns
simultaneously.  These tests pin the contract: identical `proc_errors`,
app solutions, and sync counters (bitwise, not approximately) across all
five paper scenarios, plus the dirty⊆sFIFO flush-completeness invariant
surviving the block-major refactor (hypothesis-free here; the hypothesis
sweep lives in test_protocol.py), plus the Pallas drain-writeback kernel
against its jnp reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocol as P
from repro.data.graphs import collab_like, road_like
from repro.kernels.selective_flush.kernel import drain_writeback_pallas
from repro.kernels.selective_flush.ref import drain_writeback_ref
from repro.workloads.worksteal import WSConfig, run_app

WS = WSConfig(n_wgs=4, chunk_cap=32, n_chunks_max=8)
G = collab_like(n=256, m=3, seed=1)

# counters the acceptance criteria name explicitly; the assertion below
# still compares every counter (they must all match bitwise)
KEY_COUNTERS = ("promotions", "probes", "inv_full", "global_syncs")


def _assert_equivalent(app, g, scenario, max_iters):
    ser = run_app(app, g, scenario, WS, max_iters=max_iters, engine="serial")
    bat = run_app(app, g, scenario, WS, max_iters=max_iters, engine="batched")
    assert ser.proc_errors == 0 and bat.proc_errors == 0, scenario
    np.testing.assert_array_equal(ser.solution, bat.solution)
    for k in KEY_COUNTERS:
        assert ser.counters[k] == bat.counters[k], (scenario, k, ser.counters,
                                                    bat.counters)
    mismatched = {k: (ser.counters[k], bat.counters[k])
                  for k in ser.counters if ser.counters[k] != bat.counters[k]}
    assert not mismatched, (scenario, mismatched)
    jax.clear_caches()


@pytest.mark.parametrize("scenario", [
    "srsp",
    pytest.param("steal_only", marks=pytest.mark.slow),
    pytest.param("rsp", marks=pytest.mark.slow),
    pytest.param("baseline", marks=pytest.mark.slow),
    pytest.param("scope_only", marks=pytest.mark.slow),
])
def test_engines_bitwise_equivalent_pagerank(scenario):
    _assert_equivalent("pagerank", G, scenario, max_iters=2)


@pytest.mark.slow
def test_engines_equivalent_sssp_and_mis():
    _assert_equivalent("sssp", road_like(n=256, seed=3), "srsp", max_iters=4)
    _assert_equivalent("mis", G, "rsp", max_iters=2)


# --------------------------------------------------------------------------
# zero-churn elastic pin (ISSUE 6): the alive-set machinery with an empty
# churn schedule must be bitwise invisible on the paper's main workload
# --------------------------------------------------------------------------

def test_zero_churn_elastic_pin_worksteal():
    from repro import workloads
    from repro.workloads import harness
    for plain, elastic in (("serial", "serial_elastic"),
                           ("batched", "batched_elastic")):
        b = workloads.get("worksteal").build("srsp", 4, seed=3)
        ref = harness.runner(plain)(b.wl, b.state, *b.ops)
        b2 = workloads.get("worksteal").build("srsp", 4, seed=3)
        eb = harness.make_elastic(b2)
        fin = harness.runner(elastic)(eb.wl, eb.state, *eb.ops)
        for la, lb in zip(jax.tree.leaves(ref), jax.tree.leaves(fin.s)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                          err_msg=plain)
        assert bool(np.asarray(fin.alive).all())
    jax.clear_caches()


# --------------------------------------------------------------------------
# observability (ISSUE 7): tracing must never perturb protocol results
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", [
    "producer_consumer",
    pytest.param("reader_lock", marks=pytest.mark.slow),
    pytest.param("kv_directory", marks=pytest.mark.slow),
    pytest.param("worksteal", marks=pytest.mark.slow),
])
def test_trace_on_preserves_results(name):
    """Running a workload with the trace ring enabled must leave every
    non-trace leaf bitwise identical to the trace-off run, and must have
    actually recorded events — the observer-effect contract DESIGN.md
    §11 promises (trace state is carried beside the protocol state and
    written with pure scatters; it never feeds back)."""
    from repro import workloads
    from repro.obs import trace as T
    from repro.workloads import harness
    b = workloads.get(name).build("srsp", 4, seed=3)
    off = harness.run_batched(b.wl, T.strip(b.state), *b.ops)
    b2 = workloads.get(name).build("srsp", 4, seed=3)
    on = harness.run_batched(b2.wl, T.with_trace(b2.state, 512), *b2.ops)
    assert int(on.store.trace.head) > 0, name      # tracing really ran
    for la, lb in zip(jax.tree.leaves(T.strip(off)),
                      jax.tree.leaves(T.strip(on))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=name)
    assert b2.check(on)["ok"], name
    jax.clear_caches()


# --------------------------------------------------------------------------
# fused megakernel engine (DESIGN.md §12): its equivalence to the serial
# engine on every workload and scenario is the grid of
# tests/test_engine_grid_*.py; the trace-on pin stays here
# --------------------------------------------------------------------------

def test_fused_engine_trace_on_preserves_results():
    """Observer-effect contract on the fused engine: the trace ring live
    must leave every non-trace leaf bitwise identical (the plan kernel
    sits outside the charge/record path — DESIGN.md §12)."""
    from repro import workloads
    from repro.obs import trace as T
    from repro.workloads import harness
    b = workloads.get("producer_consumer").build("srsp", 4, seed=3)
    off = harness.run_fused(b.wl, T.strip(b.state), *b.ops)
    b2 = workloads.get("producer_consumer").build("srsp", 4, seed=3)
    on = harness.run_fused(b2.wl, T.with_trace(b2.state, 512), *b2.ops)
    assert int(on.store.trace.head) > 0
    for la, lb in zip(jax.tree.leaves(T.strip(off)),
                      jax.tree.leaves(T.strip(on))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    jax.clear_caches()


# --------------------------------------------------------------------------
# dirty ⊆ sFIFO invariant through the block-major batched ops
# --------------------------------------------------------------------------

CFG = P.ProtoConfig(n_caches=4, n_words=256)


def _dirty_blocks(st, c):
    return set(np.nonzero(np.asarray(P.wdirty_bool(st)[c]).any(axis=-1))[0])


def _fifo_blocks(st, c):
    return set(int(a) for a in np.asarray(st.fifo.addrs[c]) if a >= 0)


def test_dirty_subset_of_fifo_survives_block_major_ops():
    """Random op soup over BOTH API layers (scalar and masked-batch ops);
    after every op each cache's dirty blocks are a subset of its sFIFO, so
    a drain is always a complete flush."""
    rng = np.random.default_rng(7)
    st = P.make_store(CFG)
    n = CFG.n_caches
    for step in range(30):
        op = rng.integers(0, 7)
        cid = int(rng.integers(0, n))
        addr = jnp.int32(int(rng.integers(0, 15)) * 16 + int(rng.integers(0, 16)))
        if op == 0:
            st, _ = P.store_word(CFG, st, cid, addr, step)
        elif op == 1:
            st, _ = P.load(CFG, st, cid, addr)
        elif op == 2:
            st = P.local_release(CFG, st, cid, addr, 1)
        elif op == 3:
            st, _ = P.local_acquire(CFG, st, cid, addr, 0, 1)
        elif op == 4:
            st, _ = P.srsp_remote_acquire(CFG, st, cid, addr, 0, 1)
        elif op == 5:
            # masked multi-cache store: disjoint per-cache addresses
            mask = jnp.asarray(rng.integers(0, 2, n).astype(bool))
            addrs = jnp.asarray((rng.permutation(n) * 64 + 3).astype(np.int32))
            st, _ = P.b_store_word(CFG, st, mask, addrs,
                                   jnp.full((n,), step, jnp.int32))
        else:
            mask = jnp.asarray(rng.integers(0, 2, n).astype(bool))
            addrs = jnp.asarray((rng.permutation(n) * 64 + 5).astype(np.int32))
            st, _ = P.local_acquire_b(CFG, st, mask, addrs, 0, 1)
        for c in range(n):
            assert _dirty_blocks(st, c) <= _fifo_blocks(st, c), (step, op, c)
    for c in range(n):
        st, _ = P.drain_fifo_all(CFG, st, c)
    assert not bool(np.asarray(P.wdirty_bool(st)).any())


def test_batched_ops_match_scalar_ops_single_lane():
    """A batched op with a one-hot mask must equal the scalar-cid op."""
    ops_scalar = P.make_store(CFG)
    ops_batch = P.make_store(CFG)
    rng = np.random.default_rng(3)
    for step in range(20):
        cid = int(rng.integers(0, CFG.n_caches))
        addr = int(rng.integers(0, CFG.n_words))
        hot = jnp.arange(CFG.n_caches) == cid
        addrs = jnp.full((CFG.n_caches,), addr, jnp.int32)
        vals = jnp.full((CFG.n_caches,), step, jnp.int32)
        which = rng.integers(0, 3)
        if which == 0:
            ops_scalar, _ = P.store_word(CFG, ops_scalar, cid, addr, step)
            ops_batch, _ = P.b_store_word(CFG, ops_batch, hot, addrs, vals)
        elif which == 1:
            ops_scalar, a = P.load(CFG, ops_scalar, cid, addr)
            ops_batch, b = P.b_load(CFG, ops_batch, hot, addrs)
            assert int(a) == int(b[cid])
        else:
            ops_scalar = P.local_release(CFG, ops_scalar, cid, addr, step)
            ops_batch = P.local_release_b(CFG, ops_batch, hot, addrs, vals)
    for a, b in zip(jax.tree.leaves(ops_scalar), jax.tree.leaves(ops_batch)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# Pallas drain-writeback kernel vs jnp reference
# --------------------------------------------------------------------------

def test_drain_writeback_pallas_matches_ref():
    rng = np.random.default_rng(0)
    nb, W, m = 32, 16, 12
    l2 = jnp.asarray(rng.integers(0, 100, (nb, W)), jnp.int32)
    rows = jnp.asarray(rng.integers(100, 200, (m, W)), jnp.int32)
    dirty = jnp.asarray(rng.integers(0, 2, (m, W)).astype(bool))
    # unique destinations plus -1 padding
    idx = np.full(m, -1, np.int32)
    idx[:8] = rng.choice(nb, size=8, replace=False)
    got = drain_writeback_pallas(l2, rows, dirty, jnp.asarray(idx),
                                 interpret=True)
    want = drain_writeback_ref(l2, rows, dirty, jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_drain_writeback_packed_mask_matches_boolean():
    """The packed uint32 word-bitmask dirty rows (DESIGN.md §8) must drive
    the scatter identically to the boolean rows they encode — kernel and
    jnp reference, including word-boundary lanes (W not divisible by 32)."""
    from repro.core import bitmask
    rng = np.random.default_rng(5)
    for W in (16, 40):          # 1 lane ragged / 2 lanes ragged
        nb, m = 16, 10
        l2 = jnp.asarray(rng.integers(0, 100, (nb, W)), jnp.int32)
        rows = jnp.asarray(rng.integers(100, 200, (m, W)), jnp.int32)
        dirty = jnp.asarray(rng.integers(0, 2, (m, W)).astype(bool))
        idx = np.full(m, -1, np.int32)
        idx[:7] = rng.choice(nb, size=7, replace=False)
        idx = jnp.asarray(idx)
        packed = bitmask.pack(dirty)
        want = drain_writeback_ref(l2, rows, dirty, idx)
        np.testing.assert_array_equal(
            np.asarray(drain_writeback_ref(l2, rows, packed, idx)),
            np.asarray(want), err_msg=f"packed ref W={W}")
        np.testing.assert_array_equal(
            np.asarray(drain_writeback_pallas(l2, rows, packed, idx,
                                              interpret=True)),
            np.asarray(want), err_msg=f"packed pallas W={W}")


def test_drain_writeback_duplicate_disjoint_dirty():
    """Two caches flushing different words of the same block (block-level
    false sharing) must both land; order only matters for overlapping dirty
    words, which a well-synchronized program never produces."""
    nb, W = 4, 16
    l2 = jnp.zeros((nb, W), jnp.int32)
    rows = jnp.stack([jnp.full((W,), 7, jnp.int32),
                      jnp.full((W,), 9, jnp.int32)])
    dirty = jnp.stack([jnp.arange(W) < 8, jnp.arange(W) >= 8])
    idx = jnp.asarray([2, 2], jnp.int32)
    want = drain_writeback_ref(l2, rows, dirty, idx)
    got = drain_writeback_pallas(l2, rows, dirty, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(want[2]),
                                  np.asarray(jnp.where(jnp.arange(W) < 8, 7, 9)))


@pytest.mark.parametrize("packed", [False, True])
def test_drain_writeback_rows_matches_ref(packed):
    """The list-side merge that banks past the kernels' VMEM budget take
    (DESIGN.md §14): the reference's bits, with repeated destinations
    (overlapping dirty words, last entry wins), out-of-range entries and
    -1 padding."""
    from repro.core import bitmask
    from repro.kernels.selective_flush.ref import drain_writeback_rows
    rng = np.random.default_rng(9)
    for trial in range(20):
        nb, m = int(rng.integers(1, 40)), int(rng.integers(1, 64))
        l2 = jnp.asarray(rng.integers(0, 100, (nb, 16)), jnp.int32)
        rows = jnp.asarray(rng.integers(100, 200, (m, 16)), jnp.int32)
        dirty = jnp.asarray(rng.random((m, 16)) < 0.4)
        idx = jnp.asarray(rng.integers(-3, nb + 3, m), jnp.int32)
        mask = bitmask.pack(dirty) if packed else dirty
        np.testing.assert_array_equal(
            np.asarray(drain_writeback_rows(l2, rows, mask, idx)),
            np.asarray(drain_writeback_ref(l2, rows, dirty, idx)),
            err_msg=f"trial {trial}")
