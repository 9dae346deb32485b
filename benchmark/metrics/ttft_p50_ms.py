"""Median over the window's requests of first-token time from when each was due."""


def read(ctx):
    return ctx.stats.percentile(ctx.stats.ttfts(ctx.records, ctx.seconds), 50)
