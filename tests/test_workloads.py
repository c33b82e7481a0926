"""Pluggable workload subsystem tests (ISSUE 2 acceptance).

Contracts per registered workload (engine equivalence, serial against
every fast engine, scalar and vmapped, is the grid of
tests/test_engine_grid_*.py):

1. **self-check soundness** — each workload's consistency check is green
   under the correct protocols (srsp/rsp/baseline).
2. **self-check power** — a deliberately weakened protocol (remote
   acquire skipping promotion — the bug class sRSP exists to prevent)
   must be CAUGHT by every workload's check, and scope_only (local-scope
   remote ops, the paper's staleness demo) must be caught by every
   workload with remote turns.
"""
import jax
import numpy as np
import pytest

from repro import workloads
from repro.core import protocol as P
from repro.core import tables
from repro.obs import trace as T
from repro.workloads import faults, harness

NEW_WORKLOADS = ["producer_consumer", "reader_lock", "kv_directory"]
N_AGENTS = 4
SEED = 3


def _run(name, scenario, engine, seed=SEED, proto=None):
    """Fresh state per run: harness entry points donate their input."""
    b = workloads.get(name).build(scenario, N_AGENTS, seed=seed, proto=proto)
    final = harness.runner(engine)(b.wl, b.state, *b.ops)
    return final, b.check


def _assert_bitwise_equal(a, b, ctx):
    # trace leaves are stripped: serial and batched engines issue the same
    # ops at the same costs but in different calls, so event ORDER differs
    # by design (the strip-equality contract lives in
    # tests/test_engine_equivalence.py::test_trace_on_preserves_results)
    a, b = T.strip(a), T.strip(b)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=str(ctx))


@pytest.mark.parametrize("name", NEW_WORKLOADS)
def test_zero_churn_elastic_pin(name):
    """ISSUE 6 acceptance: wrapping a bench in the elastic alive-set
    machinery with an EMPTY churn schedule must be bitwise invisible —
    same final state as the plain batched engine, every leaf."""
    b = workloads.get(name).build("srsp", N_AGENTS, seed=SEED)
    ref = harness.run_batched(b.wl, b.state, *b.ops)
    b2 = workloads.get(name).build("srsp", N_AGENTS, seed=SEED)
    eb = harness.make_elastic(b2)
    fin = harness.run_batched_elastic(eb.wl, eb.state, *eb.ops)
    _assert_bitwise_equal(ref, fin.s, (name, "zero-churn"))
    assert bool(np.all(np.asarray(fin.alive))), name
    jax.clear_caches()


@pytest.mark.parametrize("name", NEW_WORKLOADS)
def test_weakened_protocol_is_caught(name):
    """Remote acquire without promotion (faults.no_promotion) leaves the
    owners' released writes stranded in their L1s; every workload's
    self-check must flag the resulting stale reads."""
    broken = faults.no_promotion(P.get_protocol("srsp"))
    final, check = _run(name, "srsp", "batched", proto=broken)
    res = check(final)
    assert not res["ok"], (name, res)
    assert res["check_fails"] > 0, (name, res)
    jax.clear_caches()


@pytest.mark.parametrize("name", ["kv_directory", "reader_lock"])
def test_tiny_pa_geometry_still_correct(name):
    """Stress the PA-TBL's LRU eviction (DESIGN.md §8): with a 1×2 PA
    table — two entries total — the self-checks must STAY green: an
    evicted record raises its set's overflow bit, so the next local
    acquire of any address of the set promotes."""
    geom = tables.TableGeometry(sets=1, ways=2)
    b = workloads.get(name).build("srsp", N_AGENTS, seed=SEED, pa_tbl=geom)
    final = harness.run_batched(b.wl, b.state, *b.ops)
    res = b.check(final)
    assert res["ok"], (name, res)
    jax.clear_caches()


@pytest.mark.slow
def test_weakened_protocol_caught_by_worksteal_too():
    final, check = _run("worksteal", "srsp", "batched",
                        proto=faults.no_promotion(P.get_protocol("srsp")))
    assert not check(final)["ok"]
    jax.clear_caches()


@pytest.mark.slow
@pytest.mark.parametrize("name", NEW_WORKLOADS)
def test_scope_only_staleness_is_caught(name):
    """Local-scope sync for remote ops is the paper's staleness demo —
    the checks must see it."""
    final, check = _run(name, "scope_only", "batched")
    assert not check(final)["ok"], name
    jax.clear_caches()


def test_worksteal_bench_contract():
    """The first registered workload drives through the same contract."""
    b = workloads.get("worksteal").build("srsp", N_AGENTS, seed=0)
    final = harness.run_batched(b.wl, b.state, *b.ops)
    res = b.check(final)
    assert res["ok"], res
    assert float(final.store.counters.steals) > 0  # stealing really happened
    jax.clear_caches()


def test_registry_lists_all_workloads():
    names = workloads.available()
    assert set(NEW_WORKLOADS) <= set(names)
    assert "worksteal" in names
    assert "producer_consumer_mc" in names   # the multi-consumer variant
    for n in names:
        m = workloads.get(n)
        assert hasattr(m, "build") and hasattr(m, "VMAPPABLE")
