"""95th percentile of first-token time from when each request was due; a
failed, refused or hung request counts as the window length."""


def read(ctx):
    return ctx.stats.percentile(ctx.stats.ttfts(ctx.records, ctx.seconds), 95)
