"""Share of the window's call time spent making inputs (host clock).

Layer: inputs (`traffic/` and `init_state`, or worksteal's `build`).
Moves sim_events_per_s.  Sum of the `bench.init` spans over the sum of
whole-call spans (inputs plus engine), every call of the window.
"""


def read(ctx):
    total = sum(t2 - t0 for t0, _, t2 in ctx.spans)
    if not total:
        return None
    return 100.0 * sum(t1 - t0 for t0, t1, _ in ctx.spans) / total
