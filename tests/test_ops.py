"""Scope-parametric ISA tests (ISSUE 4 acceptance).

Contracts:

1. **scoped dispatch == legacy paths** — `ops.acquire/release` with a
   one-hot mask and a static scope must be bitwise-equal to the legacy
   scalar op it replaced (`local_acquire`, `srsp_remote_acquire`,
   `global_acquire`, …), for every registered protocol, on every store
   leaf.  At workload level: each workload run through the scoped
   surface with the batched remote twins must equal the same run with
   the twins stripped (`faults.serialize_remote` — the legacy
   serialized-scalar path).  The REPRO_NO_PACK / REPRO_NO_DONATE
   metadata layouts are covered by the CI escape-hatch matrix running
   this whole file under each flag.
2. **disjoint-addr remote batch == serialized order** — a single
   batched remote op (acquire-only or release-only) over lanes with
   pairwise-distinct addresses and disjoint sharer sets is bitwise-equal
   to issuing the scalar op per lane in ascending order (DESIGN.md §9).
3. **registry ergonomics** — unknown protocol/engine/scenario names
   raise with the list of registered names.

Plus the multi-consumer producer/consumer equivalence: co-scheduled
remote turns (a TRUE multi-lane remote batch) reproduce the serial
engine bitwise on every leaf except the PA-TBL age/content metadata,
where the batch is a documented cost-conservative superset (§9).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import workloads
from repro.core import ops as O
from repro.core import protocol as P
from repro.obs import trace as T
from repro.workloads import faults, harness

CFG = P.ProtoConfig(n_caches=4, n_words=256)


def _hot(cid):
    return jnp.arange(CFG.n_caches) == cid


def _fill(v):
    return jnp.full((CFG.n_caches,), v, jnp.int32)


def _assert_stores_equal(a, b, ctx):
    # trace stripped: the scoped surface records events the raw protocol
    # ops (and the serialized legacy path) don't, and event order differs
    # across engines — the trace contract has its own suite (test_obs.py,
    # test_engine_equivalence.py::test_trace_on_preserves_results)
    a, b = T.strip(a), T.strip(b)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=str(ctx))


def _seed_store():
    """A store with LR entries, PA entries and dirty data in play."""
    st = P.make_store(CFG)
    st, _ = P.store_word(CFG, st, 0, 17, 41)
    st, _ = P.store_word(CFG, st, 1, 49, 43)
    st = P.local_release(CFG, st, 0, 16, 7)    # LR entry: cache 0, addr 16
    st = P.local_release(CFG, st, 1, 48, 9)    # LR entry: cache 1, addr 48
    st, _ = P.store_word(CFG, st, 2, 130, 45)
    st = P.srsp_remote_release(CFG, st, 3, 64, 5)  # PA entries everywhere
    return st


# --------------------------------------------------------------------------
# 1. scoped dispatch == legacy scalar paths
# --------------------------------------------------------------------------

@pytest.mark.parametrize("pname", ["srsp", "rsp", "global", "local"])
def test_scoped_dispatch_matches_legacy_scalar_ops(pname):
    """One-hot ops.acquire/release at each scope vs the scalar op table
    entry it routes to — bitwise, for every registered protocol."""
    proto = P.get_protocol(pname)
    scalar = {O.LOCAL: (proto.acquire_loc, proto.release_loc),
              O.REMOTE: (proto.acquire_rem, proto.release_rem),
              O.GLOBAL: (proto.acquire_glob, proto.release_glob)}
    for scope in O.SCOPES:
        sa = _seed_store()
        sb = _seed_store()
        acq, rel = scalar[scope]
        sa, old_a = acq(CFG, sa, 2, 16, 0, 1)
        sb, old_b = O.acquire(proto, CFG, sb, _hot(2), _fill(16), 0, 1,
                              scope=scope)
        np.testing.assert_array_equal(int(old_a), int(old_b[2]),
                                      err_msg=(pname, scope, "old"))
        sa = rel(CFG, sa, 2, 16, 0)
        sb = O.release(proto, CFG, sb, _hot(2), _fill(16), 0, scope=scope)
        _assert_stores_equal(sa, sb, (pname, scope))
    jax.clear_caches()


@pytest.mark.parametrize("name", ["producer_consumer", "reader_lock",
                                  "kv_directory", "worksteal"])
def test_workload_scoped_vs_serialized_remote_path(name):
    """Each workload through the batched remote twins vs through the
    stripped-capability protocol (the legacy serialized scalar path) —
    bitwise on every leaf, batched engine."""
    a = workloads.get(name).build("srsp", 4, seed=3)
    fa = harness.run_batched(a.wl, a.state, *a.ops)
    b = workloads.get(name).build(
        "srsp", 4, seed=3,
        proto=faults.serialize_remote(P.get_protocol("srsp")))
    fb = harness.run_batched(b.wl, b.state, *b.ops)
    _assert_stores_equal(fa, fb, name)
    assert a.check(fa)["ok"], name
    jax.clear_caches()


def test_mixed_scope_vector_dispatch():
    """A per-agent scope array carries one mixed-scope bundle; dispatch
    order is loc, glob, rem (documented), matching the manual calls."""
    proto = P.get_protocol("srsp")
    addrs = jnp.asarray([16, 96, 48, 128], jnp.int32)
    scope = jnp.asarray([O.LOCAL, O.LOCAL, O.REMOTE, O.GLOBAL], jnp.int32)
    active = jnp.ones((4,), bool)
    sa = _seed_store()
    sa, old_a = O.acquire(proto, CFG, sa, active, addrs, 0, 1, scope=scope)
    sb = _seed_store()
    loc = jnp.asarray([True, True, False, False])
    sb, old_l = proto.acquire_loc_b(CFG, sb, loc, addrs, _fill(0), _fill(1))
    glob = jnp.asarray([False, False, False, True])
    sb, old_g = proto.acquire_glob_b(CFG, sb, glob, addrs, _fill(0),
                                     _fill(1))
    rem = jnp.asarray([False, False, True, False])
    sb, old_r = proto.acquire_rem_b(CFG, sb, rem, addrs, _fill(0), _fill(1))
    # ops.acquire = scope dispatch + clock-stamped lease bookkeeping
    # (DESIGN.md §10); apply the same stamp to the manual reference
    sb = P.lease_stamp(sb, active, addrs)
    _assert_stores_equal(sa, sb, "mixed-scope")
    want = jnp.where(rem, old_r, jnp.where(glob, old_g, old_l))
    np.testing.assert_array_equal(np.asarray(old_a), np.asarray(want))
    jax.clear_caches()


def test_unknown_scope_raises():
    with pytest.raises(ValueError, match="unknown scope"):
        O.acquire(P.get_protocol("srsp"), CFG, _seed_store(),
                  _hot(0), _fill(0), 0, 1, scope=7)


# --------------------------------------------------------------------------
# 2. disjoint-addr remote batch == serialized remote order
# --------------------------------------------------------------------------

@pytest.mark.parametrize("pname", ["srsp", "global"])
def test_disjoint_remote_acquire_batch_equals_serialized(pname):
    """Two issuers, distinct addrs, disjoint sharer sets: one batched
    remote acquire == the two scalar acquires in ascending lane order."""
    proto = P.get_protocol(pname)
    sa = _seed_store()
    sa, old2 = proto.acquire_rem(CFG, sa, 2, 16, 0, 1)
    sa, old3 = proto.acquire_rem(CFG, sa, 3, 48, 0, 1)
    sb = _seed_store()
    active = jnp.asarray([False, False, True, True])
    addrs = jnp.asarray([0, 0, 16, 48], jnp.int32)
    sb, old_b = proto.acquire_rem_b(CFG, sb, active, addrs, _fill(0),
                                    _fill(1))
    _assert_stores_equal(sa, sb, pname)
    assert int(old2) == int(old_b[2]) and int(old3) == int(old_b[3])
    jax.clear_caches()


@pytest.mark.parametrize("pname", ["srsp", "global"])
def test_disjoint_remote_release_batch_equals_serialized(pname):
    proto = P.get_protocol(pname)
    sa = _seed_store()
    sa = proto.release_rem(CFG, sa, 2, 16, 11)
    sa = proto.release_rem(CFG, sa, 3, 48, 13)
    sb = _seed_store()
    active = jnp.asarray([False, False, True, True])
    addrs = jnp.asarray([0, 0, 16, 48], jnp.int32)
    vals = jnp.asarray([0, 0, 11, 13], jnp.int32)
    sb = proto.release_rem_b(CFG, sb, active, addrs, vals)
    _assert_stores_equal(sa, sb, pname)
    jax.clear_caches()


def test_same_cu_remote_acquire_one_hot_equals_scalar():
    """The §4.2 same-CU fork (issuer holds its own LR entry) through the
    batched twin — the scalar op's lax.cond branch, mask-executed."""
    sa = _seed_store()
    sa, old_a = P.srsp_remote_acquire(CFG, sa, 0, 16, 7, 2)  # own LR entry
    sb = _seed_store()
    sb, old_b = P.srsp_remote_acquire_b(CFG, sb, _hot(0), _fill(16),
                                        _fill(7), _fill(2))
    _assert_stores_equal(sa, sb, "same-cu")
    assert int(old_a) == int(old_b[0])
    jax.clear_caches()


# --------------------------------------------------------------------------
# 3. registry ergonomics
# --------------------------------------------------------------------------

def test_unknown_names_raise_with_registered_list():
    with pytest.raises(KeyError, match="registered.*srsp"):
        P.get_protocol("nope")
    # registry misses stay catchable as ValueError too (the pre-registry
    # runner()/WorkStealSim checks raised ValueError)
    with pytest.raises(ValueError):
        harness.runner("nope")
    with pytest.raises(KeyError, match="registered.*srsp"):
        P.PROTOCOLS["nope"]
    with pytest.raises(KeyError, match="registered.*batched"):
        harness.runner("nope")
    with pytest.raises(KeyError, match="registered.*srsp"):
        harness.resolve_proto("nope")
    from repro.workloads import worksteal
    with pytest.raises(ValueError, match="registered.*srsp"):
        worksteal.WorkStealSim(worksteal.WSConfig(n_wgs=2), "nope")
    assert "srsp" in P.protocols()
    assert set(harness.engines()) == {
        "serial", "batched", "fused", "serial_elastic", "batched_elastic"}
    assert "baseline" in harness.scenarios()


def test_drain_all_sentinel_is_public():
    assert int(P.DRAIN_ALL) == int(P._DRAIN_ALL)
    st = _seed_store()
    st = harness.drain_all(CFG, st)
    assert not bool(np.asarray(P.wdirty_bool(st)).any())


def test_protocol_capability_declaration():
    assert P.get_protocol("srsp").remote_batchable
    assert P.get_protocol("global").remote_batchable
    assert P.get_protocol("local").remote_batchable
    assert not P.get_protocol("rsp").remote_batchable       # flush-all
    assert not faults.serialize_remote(
        P.get_protocol("srsp")).remote_batchable
    assert not faults.no_promotion(
        P.get_protocol("srsp")).remote_batchable


# --------------------------------------------------------------------------
# multi-consumer producer/consumer: TRUE co-scheduled remote batches
# --------------------------------------------------------------------------

def _pa_addr_sets(st):
    a = np.asarray(st.pa.addrs)
    return [set(int(x) for x in a[c].ravel() if x >= 0)
            for c in range(a.shape[0])]


def test_multi_consumer_serial_batched_equivalent():
    """Serial vs batched engines on producer_consumer_mc (srsp): the
    batched engine co-schedules disjoint drains.  Everything observable
    — counters, solutions, bookkeeping, self-check — is bitwise equal;
    the PA-TBL metadata is exempt: a co-scheduled batch permutes
    same-trip PA insertions, leaving a documented cost-conservative
    SUPERSET of the serial content (DESIGN.md §9)."""
    mod = workloads.get("producer_consumer_mc")
    a = mod.build("srsp", 8, seed=1)
    ser = harness.run_serial(a.wl, a.state, *a.ops)
    b = mod.build("srsp", 8, seed=1)
    bat = harness.run_batched(b.wl, b.state, *b.ops)
    _assert_stores_equal(ser._replace(store=ser.store._replace(pa=None)),
                         bat._replace(store=bat.store._replace(pa=None)),
                         "mc")
    for c, (sa, sb) in enumerate(zip(_pa_addr_sets(ser.store),
                                     _pa_addr_sets(bat.store))):
        assert sa <= sb, (c, sa, sb)
    assert a.check(ser)["ok"]
    assert b.check(bat)["ok"]
    jax.clear_caches()


def test_multi_consumer_remote_turn_b_really_batches():
    """A 2-hot remote batch through the workload's remote_turn_b equals
    the two one-hot turns (up to the §9 PA exemption) — the co-scheduled
    drain is semantically the serialized pair, executed in one turn."""
    import repro.workloads.producer_consumer as pc
    mod = workloads.get("producer_consumer_mc")
    bench = mod.build("srsp", 8, seed=1)
    wl = bench.wl
    s = bench.state
    # burn scratch credit so both consumers are drain-ready
    for _ in range(wl.cfg.warmup):
        s = pc._local_turn(wl, s, pc._can_local(wl, s))
    can_r = np.asarray(pc._can_remote(wl, s))
    assert can_r[0] and can_r[1], can_r
    addr = np.asarray(pc._remote_addr(wl, s))
    assert addr[0] != addr[1]                 # partitioned victims
    both = pc._remote_turn_b(wl, s, jnp.asarray([True, True] + [False] * 6))
    mod2 = mod.build("srsp", 8, seed=1)
    s2 = mod2.state
    for _ in range(wl.cfg.warmup):
        s2 = pc._local_turn(wl, s2, pc._can_local(wl, s2))
    s2 = pc._remote_turn(wl, s2, 0)
    s2 = pc._remote_turn(wl, s2, 1)
    _assert_stores_equal(both._replace(store=both.store._replace(pa=None)),
                         s2._replace(store=s2.store._replace(pa=None)),
                         "2-hot remote batch")
    jax.clear_caches()


def test_multi_consumer_defaults_clamp_to_tiny_machines():
    """producer_consumer_mc must build at every n_agents its siblings
    accept — n_agents=2 degrades to one consumer instead of raising."""
    import repro.workloads.producer_consumer_mc as mc
    assert mc.default_consumers(2) == 1
    assert mc.default_consumers(8) == 2
    assert mc.default_consumers(64) == 8
    b = mc.build("srsp", 2, seed=0)
    assert b.wl.cfg.n_consumers == 1


def test_multi_consumer_weakened_protocol_is_caught():
    mod = workloads.get("producer_consumer_mc")
    b = mod.build("srsp", 8, seed=1,
                  proto=faults.no_promotion(P.get_protocol("srsp")))
    final = harness.run_batched(b.wl, b.state, *b.ops)
    res = b.check(final)
    assert not res["ok"] and res["check_fails"] > 0, res
    jax.clear_caches()
