"""Host spans, the engine trip counter and the scheduler's device scopes
(DESIGN.md §11): `repro.obs.spans`, `TraceLog.trips`, `harness.runner`.

The trip counter is a property of the engine, not of the simulation: one
turn per trip on the serial engine, several on the batched and fused
ones.  It is stripped with the trace and leaves every other leaf alone.
"""
import glob

import jax
import numpy as np
import pytest

from repro import workloads
from repro.obs import spans
from repro.obs import trace as T
from repro.workloads import harness

N_AGENTS = 4
SEED = 3
INPUT_SPANS = ["inputs.graph", "inputs.plan", "inputs.store",
               "inputs.enqueue"]


def _bench(seed=SEED):
    return workloads.get("worksteal").build("srsp", N_AGENTS, seed=seed)


def _run(engine, seed=SEED):
    b = _bench(seed)
    return harness.runner(engine)(b.wl, b.state, *b.ops), b


def test_serial_engine_runs_one_turn_per_trip():
    out, b = _run("serial")
    assert b.check(out)["ok"]
    assert int(T.trip_count(out)) == int(out.rounds) > 0


@pytest.mark.parametrize("engine", ["batched", "fused"])
def test_batched_engines_run_several_turns_per_trip(engine):
    out, b = _run(engine)
    assert b.check(out)["ok"]
    assert 0 < int(T.trip_count(out)) <= int(out.rounds)


def test_elastic_engines_count_event_trips_too():
    """A trip that fires churn events runs no turn but is a trip: the
    serial elastic engine runs one trip per turn plus one per firing."""
    events = [(50.0, 2, "leave"), (150.0, 2, "join")]
    for evs, fire_trips in (([], 0), (events, 2)):
        eb = harness.make_elastic(_bench(), events=evs)
        fin = harness.runner("serial_elastic")(eb.wl, eb.state, *eb.ops)
        assert eb.check(fin)["ok"]
        assert int(T.trip_count(fin)) == int(fin.s.rounds) + fire_trips


def test_strip_zeroes_trips_and_counting_leaves_every_other_leaf(
        monkeypatch):
    on, _ = _run("batched")
    assert int(T.trip_count(on)) > 0
    assert int(T.trip_count(T.strip(on))) == 0
    jax.clear_caches()
    monkeypatch.setattr(T, "record_trip", lambda st: st)
    off, _ = _run("batched")
    jax.clear_caches()
    assert int(T.trip_count(off)) == 0
    for la, lb in zip(jax.tree.leaves(T.strip(on)),
                      jax.tree.leaves(T.strip(off))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_runner_records_each_call_in_order_and_survives_donation():
    run = harness.runner("batched")
    spans.reset()
    outs = [_run_with(run, seed) for seed in (1, 2)]
    snap = [(name, seq, int(v)) for name, seq, v in spans.snapshot()]
    assert [name for name, _, _ in snap] == ["engine.trips"] * 2
    assert snap[1][1] == snap[0][1] + 1
    assert [v for _, _, v in snap] == [int(T.trip_count(out))
                                       for out, _ in outs]
    # handing a result back to a donating engine call deletes its buffers,
    # the recorded trip count among them: the registry then skips it
    out, b = outs[0]
    run(b.wl, out, *b.ops)
    assert T.trip_count(out).is_deleted() == harness.DONATE
    seqs = [seq for _, seq, _ in spans.snapshot()]
    first = [] if harness.DONATE else [snap[0][1]]
    assert seqs == first + [snap[1][1], snap[1][1] + 1]
    spans.reset()
    assert spans.snapshot() == []


def _run_with(run, seed):
    b = _bench(seed)
    return run(b.wl, b.state, *b.ops), b


@pytest.mark.parametrize("engine", ["serial", "batched", "fused"])
def test_compiled_engine_names_trip_and_plan_scopes(engine):
    b = _bench()
    text = harness.runner(engine).lower(b.wl, b.state,
                                        *b.ops).compile().as_text()
    assert 'op_name="jit(run_' in text
    assert "harness.trip" in text and "harness.plan" in text


def test_profiler_session_holds_input_and_engine_spans(tmp_path):
    spans.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        b = _bench()
        out = harness.runner("batched")(b.wl, b.state, *b.ops)
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = sorted(
        (e.start_ns, e.name, dict(e.stats))
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(("inputs.", "engine.")))
    assert [name for _, name, _ in events] == INPUT_SPANS + ["engine.call"]
    ((_, seq, _),) = spans.snapshot()
    assert events[-1][2]["seq"] == seq
