"""Share of the traced window in which the device is idle while the host
builds the input graph (device trace + host span).

Layer: inputs (`data/graphs.py` `collab_like`, under the `inputs.graph`
span of `worksteal.build`).  Moves sim_events_per_s.  Idle gaps of the
window (`bench/trace.py`) that fall inside `inputs.graph` spans, over the
window.
"""
from bench import program_trace


def read(ctx):
    if ctx.busy_s is None:
        return None
    graph = program_trace.named(ctx, "inputs.graph")
    if not graph:
        return None
    return 100.0 * program_trace.idle_inside(ctx, graph) / ctx.window_s
