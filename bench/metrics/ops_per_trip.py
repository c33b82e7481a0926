"""Device ops executed per engine trip (device trace + counter).

Layer: device.  Moves sim_events_per_s.  Executions of leaf device ops
(control flow left out) under `harness.trip` in the traced engine calls,
over those calls' trips: the launches a trip pays for.
"""
from bench import program_trace


def read(ctx):
    if ctx.busy_s is None:
        return None
    ops = trips = 0
    for n, per_device in program_trace.calls(ctx):
        for dev_ops in per_device:
            if dev_ops:
                ops += len(dev_ops)
                trips += n
    if not trips:
        return None
    return ops / trips
