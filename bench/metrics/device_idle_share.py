"""Share of the traced window in which no op ran on the device.

Layer: device (one TPU).  Moves sim_events_per_s.  1 - busy / window,
busy being the union of device-op intervals in the traced whole calls.
"""


def read(ctx):
    if not ctx.window_s or ctx.busy_s is None:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
