"""The engine-equivalence grid: every fast engine against the serial one.

`serial` is the reference engine (one turn per trip, smallest clock acts
next).  `batched` and `fused` are the fast engines, and
`runner_many("batched" | "fused")` are their vmapped replica twins.
Each must reproduce the serial engine's final state bitwise, every leaf
(counters included), after `trace.strip`: the engines issue the same ops
at the same costs in different calls, so only the trace's event ORDER
and trip counts may differ (the trace-on pins in
tests/test_engine_equivalence.py hold the trace to the results).  The
one documented exception is the PA-TBL under co-scheduled remote
batches (`PA_SUPERSET` below).

The grid is split into one file per workload
(`tests/test_engine_grid_<workload>.py`) so a multi-worker run spreads
it; this module holds the shared body.  Each file runs the serial engine
once per (workload, scenario, seed) through the module-scoped
`serial_runs` fixture.  `tpcc` is not in the grid: its engine pins run
at their own reduced sizes in tests/test_tpcc.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import workloads
from repro.obs import trace as T
from repro.workloads import harness

N_AGENTS = 4
SEED = 3
SCENARIOS = ("baseline", "scope_only", "rsp", "srsp")
MANY_SCENARIOS = ("baseline", "rsp", "srsp")
FAST_ENGINES = ("batched", "fused")
# A co-scheduled multi-lane remote batch may leave an issuer's PA-TBL a
# SUPERSET of the serial content (DESIGN.md §9, "The PA-age reordering
# hazard").  Only sRSP keeps a PA-TBL.  producer_consumer_mc's
# partitioned drains batch whenever two consumers are ready, so there the
# PA planes are held to the superset relation and every other leaf stays
# bitwise.  kv_serving batches too, but at this size its PA-TBL stays
# bitwise, and the grid holds it to that.
PA_SUPERSET = {("producer_consumer_mc", "srsp")}


def build(name, scenario, seed=SEED):
    """Fresh bench per run: harness entry points donate their input."""
    return workloads.get(name).build(scenario, N_AGENTS, seed=seed)


@pytest.fixture(scope="module")
def serial_runs():
    """(workload, scenario, seed) -> (serial final state, check), each
    run once per module."""
    runs = {}

    def get(name, scenario, seed=SEED):
        key = (name, scenario, seed)
        if key not in runs:
            b = build(name, scenario, seed)
            runs[key] = (harness.run_serial(b.wl, b.state, *b.ops), b.check)
        return runs[key]
    return get


def assert_bitwise_equal(want, got, ctx, pa_superset=False):
    want, got = T.strip(want), T.strip(got)
    if pa_superset:
        for c, (w, g) in enumerate(zip(_pa_addr_sets(want),
                                       _pa_addr_sets(got))):
            assert w <= g, (ctx, c, w, g)
        want = want._replace(store=want.store._replace(pa=None))
        got = got._replace(store=got.store._replace(pa=None))
    assert jax.tree.structure(want) == jax.tree.structure(got), ctx
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(ctx))


def _pa_addr_sets(state):
    a = np.asarray(state.store.pa.addrs)
    return [set(int(x) for x in a[c].ravel() if x >= 0)
            for c in range(a.shape[0])]


def check_fast_engine(serial_runs, name, scenario, engine):
    """`harness.runner(engine)` equals the serial run bitwise."""
    ref, _ = serial_runs(name, scenario)
    b = build(name, scenario)
    fast = harness.runner(engine)(b.wl, b.state, *b.ops)
    assert_bitwise_equal(ref, fast, (name, scenario, engine),
                         (name, scenario) in PA_SUPERSET)
    # scope_only is the paper's unsafe staleness demo: its engines must
    # still agree, but its self-check is owned by the red tests
    if scenario != "scope_only":
        res = b.check(fast)
        assert res["ok"], (name, scenario, engine, res)


def check_fast_engine_many(serial_runs, name, scenario, engine):
    """`harness.runner_many(engine)` over replicas at SEED and SEED + 1:
    each replica equals its own solo serial run.  `rounds` is zeroed,
    because finished replicas idle while the others run."""
    mod = workloads.get(name)
    wl = build(name, scenario).wl
    seeds = jnp.asarray([SEED, SEED + 1], jnp.int32)
    states = jax.vmap(lambda s: mod.init_state(wl, s))(seeds)
    outs = harness.runner_many(engine)(wl, states)
    for k, seed in enumerate((SEED, SEED + 1)):
        ref, check = serial_runs(name, scenario, seed)
        lane = jax.tree.map(lambda x: x[k], outs)
        ctx = (name, scenario, engine, seed)
        assert_bitwise_equal(ref._replace(rounds=jnp.int32(0)),
                             lane._replace(rounds=jnp.int32(0)), ctx,
                             (name, scenario) in PA_SUPERSET)
        res = check(lane)
        assert res["ok"], (ctx, res)
