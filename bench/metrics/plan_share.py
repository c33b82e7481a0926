"""Self time of the trip plan over device busy time (device trace).

Layer: scheduler, workload turns (`harness.plan`: readiness masks, the
fence, the lex batch, the remote address dedup, the fused `trip_plan`).
Moves sim_events_per_s.  Ops under `harness.plan` and under no `ops.*`
scope; Pallas kernels are left out, as kernel_share counts them.
"""
from bench import program_trace


def _plan(ins) -> bool:
    return (not ins.kernel and program_trace.PLAN in ins.op_name
            and "/ops." not in "/" + ins.op_name)


def read(ctx):
    if not any(program_trace.PLAN in ins.op_name
               for ins in ctx.table.values()):
        return None
    return ctx.share_of_busy(_plan)
