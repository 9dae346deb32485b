"""95th percentile over requests of (last-token time - first-token time) /
(output tokens - 1)."""


def read(ctx):
    return ctx.stats.percentile(ctx.stats.tpots(ctx.records, ctx.seconds), 95)
