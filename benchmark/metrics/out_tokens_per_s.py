"""Output tokens of the requests that completed inside the window, over the window."""


def read(ctx):
    n = ctx.stats.completed_tokens(ctx.records, ctx.t0, ctx.t0 + ctx.seconds)
    return n / ctx.seconds
