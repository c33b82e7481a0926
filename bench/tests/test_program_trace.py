"""The readers of the program's own spans and trip counts.

A synthetic record: two whole calls on one device, each an input build
(with an `inputs.graph` span) and one engine call of a few trips, and the
HLO of those ops with the scopes the engines give them.  Microseconds
are written as ns * 1000.
"""
import pytest

from bench import metrics, program_trace

US = 1000.0
MOD = "jit_run_batched"
HLO = f"""HloModule {MOD}, is_scheduled=true

ENTRY %main {{
  %fusion.1 = s32[4]{{0}} fusion(s32[4]{{0}} %p.0), kind=kLoop, calls=%f.1, metadata={{op_name="jit(run_batched)/while/body/harness.trip/harness.plan/lt"}}
  %fusion.2 = s32[4]{{0}} fusion(s32[4]{{0}} %p.0), kind=kLoop, calls=%f.2, metadata={{op_name="jit(run_batched)/while/body/harness.trip/cond/branch_1_fun/ops.acquire.rem/scatter"}}
  %trip_plan_pallas.3 = s32[4,1]{{1,0}} custom-call(s32[4]{{0}} %p.0), custom_call_target="tpu_custom_call", metadata={{op_name="jit(run_batched)/while/body/harness.trip/harness.plan/pallas_call"}}
  %fusion.4 = s32[4]{{0}} fusion(s32[4]{{0}} %p.0), kind=kLoop, calls=%f.4, metadata={{op_name="jit(run_batched)/while/body/harness.trip/harness.plan/ops.acquire.loc/and"}}
  %while.5 = (s32[4]{{0}}) while((s32[4]{{0}}) %t.0), condition=%c.5, body=%b.5, metadata={{op_name="jit(run_batched)/while"}}
  %copy.6 = s32[4]{{0}} copy(s32[4]{{0}} %p.0)
  %fusion.9 = s32[4]{{0}} fusion(s32[4]{{0}} %p.0), kind=kLoop, calls=%f.9, metadata={{op_name="jit(run_batched)/other"}}
}}
"""


def _op(name, start, end):
    return [MOD + "(1)", name, start * US, (end - start) * US]


OPS = [
    _op("fusion.9", 100, 110),             # eager op inside inputs.graph
    _op("fusion.9", 320, 380),             # the enqueue
    _op("while.5", 410, 710),              # call A's loop: wraps the rest
    _op("fusion.1", 420, 440),
    _op("fusion.2", 450, 500),
    _op("trip_plan_pallas.3", 500, 510),
    _op("fusion.1", 600, 620),
    _op("fusion.2", 630, 700),
    _op("copy.6", 720, 730),               # after the loop, no trip scope
    _op("while.5", 1310, 1730),            # call B
    _op("fusion.1", 1320, 1340),
    _op("fusion.4", 1350, 1360),
    _op("fusion.2", 1400, 1500),
    _op("fusion.1", 1600, 1610),
    _op("fusion.2", 1620, 1660),
    _op("fusion.1", 1700, 1720),
]
BENCH_SPANS = [["bench.init", 0.0, 400 * US],
               ["bench.call", 400 * US, 600 * US],
               ["bench.init", 1000 * US, 300 * US],
               ["bench.call", 1300 * US, 700 * US]]


def _span(name, start, end, seq=None):
    return [name, start * US, (end - start) * US, seq]


PROGRAM = program_trace.Program(
    spans=[_span("inputs.graph", 10, 200), _span("inputs.plan", 200, 210),
           _span("inputs.store", 210, 300), _span("inputs.enqueue", 300, 320),
           _span("engine.call", 400, 405, seq=7),
           _span("inputs.graph", 1010, 1100),
           _span("engine.call", 1300, 1305, seq=8)],
    trips={5: 40, 7: 2, 8: 3})             # 5: the untraced warm-up call


def _ctx(hlo=HLO, program=PROGRAM):
    ctx = metrics.Context(spans=[], compile_s=1.0, trace_dir=None,
                          hlo_texts=[hlo],
                          rec={"ops": [OPS], "spans": BENCH_SPANS})
    ctx.program = program
    return ctx


NEW = ("trips_per_call", "trip_us", "ops_per_trip", "plan_share",
       "graph_idle_share")


def _read(ctx):
    return {k: v["value"] for k, v in metrics.read_all(
        [{"name": n, "unit": "x"} for n in NEW], ctx).items()}


def test_trips_are_paired_with_traced_calls_by_sequence_number():
    assert _read(_ctx())["trips_per_call"] == pytest.approx(2.5)


def test_trip_time_and_ops_per_trip():
    out = _read(_ctx())
    # first to last op under harness.trip: 420-700 and 1320-1720 us
    assert out["trip_us"] == pytest.approx((280 + 400) / 5)
    # leaf ops under harness.trip: 5 and 6; the loops and the copy are out
    assert out["ops_per_trip"] == pytest.approx(11 / 5)


def test_plan_share_leaves_out_protocol_ops_and_kernels():
    # plan ops outside ops.* and not kernels: fusion.1, 90 us of 450 busy
    assert _read(_ctx())["plan_share"] == pytest.approx(100 * 90 / 450)


def test_idle_inside_the_graph_span_counts_and_outside_does_not():
    ctx = _ctx()
    # idle in [10, 200] around the eager op: 90 + 90 us; in [1010, 1100]:
    # 90 us.  The idle gaps in inputs.store and between the calls are out.
    assert _read(ctx)["graph_idle_share"] == pytest.approx(100 * 270 / 2000)
    store = program_trace.named(ctx, "inputs.store")
    assert program_trace.idle_inside(ctx, store) == pytest.approx(90e-6)


def test_a_program_without_spans_or_scopes_reads_nothing():
    hlo = HLO.replace("harness.trip/", "").replace("harness.plan/", "")
    ctx = _ctx(hlo, program_trace.Program(spans=[], trips={}))
    assert _read(ctx) == {}


def test_loading_from_an_empty_trace_dir(tmp_path):
    assert program_trace.load_spans(str(tmp_path)) == []
