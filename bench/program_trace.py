"""The program's own spans and counters, for the per-layer readers.

The program opens host spans through `repro.obs.spans`: `inputs.graph`,
`inputs.plan`, `inputs.store` and `inputs.enqueue` around the parts of
worksteal's input build, and `engine.call` around each engine dispatch,
with the call's sequence number as the stat `seq`.  They sit in the
profiler's `.xplane.pb` beside the device ops, on the same clock.  Each
engine call's trip count is in the registry of `repro.obs.spans`, under
the same number, in this process.

`of(ctx)` loads both once per `metrics.Context`: the host spans of the
newest trace under `<root>/.bench_trace`, and the registry.  A program
without them gives an empty `Program`, and the readers then find nothing.

Device ops are placed by the scopes of their HLO `op_name`: `harness.trip`
holds every op of an engine's loop condition and body, `harness.plan` the
ops that decide who acts in a trip.  A call's device ops are the ops under
`harness.trip` that start after its `engine.call` span opens and before
the next one opens (or the traced window ends).
"""
from __future__ import annotations

import glob
import os
from typing import NamedTuple

from bench import trace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(BENCH_DIR), ".bench_trace")
PREFIXES = ("inputs.", "engine.")
TRIP, PLAN = "harness.trip", "harness.plan"


class Program(NamedTuple):
    spans: list   # [[name, start_ns, dur_ns, seq or None]], by start
    trips: dict   # {seq: trips of that engine call}


def load_spans(trace_dir: str = TRACE_DIR) -> list:
    """The program's host spans in the newest trace under `trace_dir`."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    out = []
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    seq = dict(e.stats).get("seq")
                    out.append([e.name, float(e.start_ns),
                                float(e.duration_ns),
                                None if seq is None else int(seq)])
    return sorted(out, key=lambda s: s[1])


def load_trips() -> dict:
    """{seq: trips} of the engine calls this process made; a vmapped call
    counts the trips of its longest replica, which the loop ran."""
    try:
        from repro.obs import spans
    except ImportError:
        return {}
    return {seq: int(v.max()) for name, seq, v in spans.snapshot()
            if name == "engine.trips"}


def of(ctx) -> Program:
    """The program's spans and trip counts for `ctx`, loaded once."""
    prog = getattr(ctx, "program", None)
    if prog is None:
        prog = ctx.program = Program(load_spans(), load_trips())
    return prog


def named(ctx, name: str) -> list:
    """[start, end] of the program's spans called `name` in the window."""
    if ctx.win is None:
        return []
    return trace.clip([[s[1], s[1] + s[2]] for s in of(ctx).spans
                       if s[0] == name], *ctx.win)


def idle_inside(ctx, intervals) -> float:
    """Device-idle seconds of the traced window inside `intervals`, mean
    over devices."""
    if not ctx.devices:
        return 0.0
    iv = trace.union(intervals)
    total = 0.0
    for _, busy in ctx.devices:
        for a, b in trace.gaps(busy, ctx.win):
            total += sum(max(0.0, min(b, d) - max(a, c)) for c, d in iv)
    return total / len(ctx.devices) / 1e9


def calls(ctx) -> list:
    """[(trips, [device ops under harness.trip] per device)] for every
    traced engine call whose trip count is known."""
    if ctx.win is None:
        return []
    prog = of(ctx)
    starts = [(s[1], s[3]) for s in prog.spans
              if s[0] == "engine.call" and s[3] in prog.trips
              and ctx.win[0] <= s[1] < ctx.win[1]]
    out = []
    for k, (t0, seq) in enumerate(starts):
        t1 = starts[k + 1][0] if k + 1 < len(starts) else ctx.win[1]
        out.append((prog.trips[seq], [
            [o for o in ops if t0 <= o[2] < t1
             and TRIP in ctx.instr(o).op_name]
            for ops, _ in ctx.devices]))
    return out
