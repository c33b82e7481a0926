"""Device time per engine trip, in microseconds (device trace + counter).

Layer: scheduler, workload turns.  Moves sim_events_per_s.  For each
traced engine call, first to last device op under `harness.trip`;
summed over the calls and divided by their trips.  Idle gaps between
trips count: they are the launch gaps a trip of many small ops pays.
"""
from bench import program_trace


def read(ctx):
    if ctx.busy_s is None:
        return None
    span_ns = trips = 0
    for n, per_device in program_trace.calls(ctx):
        for ops in per_device:
            if ops:
                span_ns += max(o[2] + o[3] for o in ops) - min(o[2]
                                                               for o in ops)
                trips += n
    if not trips:
        return None
    return span_ns / trips / 1e3
