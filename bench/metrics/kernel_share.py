"""Device time of the Pallas kernels over device busy time (device trace).

Layer: kernels (`selective_flush` drain_writeback, `fused_turn`
trip_plan and plane_commit).  Moves sim_events_per_s.
"""


def read(ctx):
    return ctx.share_of_busy(lambda ins: bool(ins.kernel))
