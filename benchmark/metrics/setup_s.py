"""Process start to the start of the window: imports, weights, server,
compiles or cache reads, the correctness check and the warm-up."""


def read(ctx):
    return ctx.setup_seconds
