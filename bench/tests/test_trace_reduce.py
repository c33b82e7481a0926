"""The reduction from a trace and the compiled HLO to per-layer numbers.

`data/hlo_excerpt.txt` holds instructions copied from the kv_serving RSP
batched engine compiled for a TPU v5e: a drain_writeback call, a plane_commit
call, a fusion under `ops.acquire.loc`, one outside the protocol ops,
and a while loop.  The record below places ops of those names on one
device's timeline (microseconds written as ns * 1000).
"""
import os

import pytest

from bench import hlo, metrics, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MOD = "jit_run_batched_many"
US = 1000.0


def _op(name, start, dur):
    return [MOD + "(12)", name, start * US, dur * US]


REC = {
    "ops": [[
        _op("while.484", 0, 1000),                  # wraps the rest
        _op("fusion.964", 100, 50),                 # protocol op
        _op("drain_writeback_pallas.128", 150, 1),  # kernel
        _op("plane_commit_pallas", 300, 200),       # kernel
        _op("fusion.1230", 450, 100),               # overlaps the kernel
        _op("fusion.964", 700, 100),
        _op("fusion.1230", 1200, 50),               # after the window
    ]],
    "spans": [["bench.init", 0.0, 90 * US], ["bench.call", 90 * US, 910 * US]],
}


@pytest.fixture(scope="module")
def ctx():
    with open(os.path.join(DATA, "hlo_excerpt.txt")) as f:
        text = f.read()
    return metrics.Context(spans=[(0.0, 0.09e-3, 1.0e-3)], compile_s=12.5,
                           trace_dir=None, hlo_texts=[text], rec=REC)


def test_hlo_instructions():
    with open(os.path.join(DATA, "hlo_excerpt.txt")) as f:
        module, table = hlo.parse(f.read())
    assert module == MOD
    assert table["drain_writeback_pallas.128"].kernel == "drain_writeback"
    assert table["plane_commit_pallas"].kernel == "plane_commit"
    assert table["while.484"].opcode == "while"
    assert "ops.acquire.loc" in table["fusion.964"].op_name
    assert not table["fusion.1230"].kernel


def test_tpu_event_names_give_the_instruction():
    """A TPU names an op's event by the instruction's whole text."""
    assert trace.instr_name(
        "%while.46 = (s32[12352,16]{1,0:T(8,128)}, s32[64]{0}) while("
        "(s32[12352,16]{1,0:T(8,128)}, s32[64]{0}) %tuple.3)") == "while.46"
    assert trace.instr_name("fusion.12") == "fusion.12"


def test_busy_idle_and_gaps(ctx):
    # busy: [100,151] + [300,550] + [700,800] us of the [0,1000] window
    assert ctx.window_s == pytest.approx(1000e-6)
    assert ctx.busy_s == pytest.approx(401e-6)
    idle = metrics.read_all([{"name": "device_idle_share", "unit": "%"}],
                            ctx)["device_idle_share"]["value"]
    assert idle == pytest.approx(59.9)
    gaps = ctx.breakdown()["idle_gaps"]
    assert gaps[0] == ["bench.call", pytest.approx(200e-6)]
    assert ["bench.init", pytest.approx(100e-6)] in gaps


def test_shares(ctx):
    out = metrics.read_all([{"name": n, "unit": "%"} for n in (
        "kernel_share", "protocol_ops_share")], ctx)
    assert out["kernel_share"]["value"] == pytest.approx(100 * 201 / 401)
    assert out["protocol_ops_share"]["value"] == pytest.approx(
        100 * 150 / 401)


def test_host_spans_give_init_share(ctx):
    out = metrics.read_all([{"name": "init_share", "unit": "%"}], ctx)
    assert out["init_share"]["value"] == pytest.approx(9.0)


def test_ops_the_programs_do_not_hold_leave_every_trace_metric_out():
    """A trace whose op names miss the HLO tables (another module, or
    renamed instructions) reads nothing, rather than 0% shares and a
    busy union that a wrapping while loop fills."""
    with open(os.path.join(DATA, "hlo_excerpt.txt")) as f:
        text = f.read()
    rec = {"ops": [[["jit_other(3)", o[1], o[2], o[3]] if k % 2 else
                    o[:1] + [o[1] + "_renamed"] + o[2:]
                    for k, o in enumerate(REC["ops"][0])]],
           "spans": REC["spans"]}
    ctx = metrics.Context(spans=[(0.0, 0.09e-3, 1.0e-3)], compile_s=1.0,
                          trace_dir=None, hlo_texts=[text], rec=rec)
    assert ctx.unmatched_share > metrics.MAX_UNMATCHED
    assert ctx.busy_s is None
    out = metrics.read_all([{"name": n, "unit": "%"} for n in (
        "device_idle_share", "kernel_share", "protocol_ops_share")], ctx)
    assert out == {}


def test_matched_trace_reads_no_unmatched_time(ctx):
    assert ctx.unmatched_share == 0.0
