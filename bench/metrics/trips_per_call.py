"""Engine trips per traced call (program counter).

Layer: scheduler, workload turns (`workloads/harness.py`).  Moves
sim_events_per_s.  The mean of `TraceLog.trips` over the engine calls
whose `engine.call` spans the trace holds, paired by sequence number
with the counts the program recorded (`bench/program_trace.py`).
"""
from bench import program_trace


def read(ctx):
    calls = program_trace.calls(ctx)
    if not calls:
        return None
    return sum(trips for trips, _ in calls) / len(calls)
