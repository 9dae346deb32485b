"""Device-to-host fetches per emitted token, over the window and its drain."""


def read(ctx):
    d = lambda k: ctx.counters1[k] - ctx.counters0[k]  # noqa: E731
    tokens = d("emitted_tokens_total")
    return d("host_syncs_total") / tokens if tokens > 0 else None
