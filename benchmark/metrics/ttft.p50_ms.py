"""Median over the window's requests of first-token time from when each was
due.  What an interactive user feels first; per layer and without a bound
since PR 26, because from seed to seed it spreads by more than half of the
widest bound there is (PERF.md, section 2)."""


def read(ctx):
    return ctx.stats.percentile(ctx.stats.ttfts(ctx.records, ctx.seconds), 50)
