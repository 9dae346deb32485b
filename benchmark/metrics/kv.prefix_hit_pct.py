"""Share of admitted prompt tokens served from cached prefix blocks."""


def read(ctx):
    d = lambda k: ctx.counters1[k] - ctx.counters0[k]  # noqa: E731
    prompt = d("prompt_tokens_total")
    return 100.0 * d("prefix_hit_tokens_total") / prompt if prompt > 0 else None
