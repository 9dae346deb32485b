"""Device time of one decode iteration: the traced `_paged_decode_chunk`
executions over the iterations (`k`) their dispatch records count.  Only
executions that `trace.steps` admits: joined to a record of the same program
that they fill, so that no execution is divided by another one's `k`."""

from benchmark import trace

PROGRAM = "_paged_decode_chunk"


def read(ctx):
    if ctx.trace is None:
        return None
    mods = trace.steps(ctx.trace, (PROGRAM,))
    iters = sum(m["dispatch"]["k"] for m in mods)
    return 1000.0 * sum(m["seconds"] for m in mods) / iters if iters else None
