"""Self time of the protocol ops over device busy time (device trace).

Layer: protocol ops (`core/ops.py` acquire/release, named scopes
`ops.acquire.*` / `ops.release.*`).  Moves sim_events_per_s.  Ops under
those scopes that are Pallas kernels are left out: kernel_share counts
them.
"""


def _protocol(ins) -> bool:
    return not ins.kernel and ("ops.acquire." in ins.op_name
                               or "ops.release." in ins.op_name)


def read(ctx):
    return ctx.share_of_busy(_protocol)
