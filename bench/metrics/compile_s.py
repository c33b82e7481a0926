"""Host seconds around `lower().compile()` of the cell's programs, from
JAX's persistent cache or not.  Layer: set-up.  Moves setup_s."""


def read(ctx):
    return ctx.compile_s
