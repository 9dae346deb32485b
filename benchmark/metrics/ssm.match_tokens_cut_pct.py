"""What the radix match gave up for want of a state snapshot, over the window
and its drain: 100 x `ssm_match_tokens_cut_total` / (`prefix_hit_tokens_total`
+ cut).  A prefix hit on a model with recurrent state layers ends at the
deepest cached block that carries a snapshot; the cached tokens behind it are
prefilled again.  0 when every match ends on a snapshot; a program without the
counter reads nothing."""


def read(ctx):
    if "ssm_match_tokens_cut_total" not in ctx.counters1:
        return None
    d = lambda k: ctx.counters1.get(k, 0) - ctx.counters0.get(k, 0)  # noqa: E731
    cut = d("ssm_match_tokens_cut_total")
    seen = d("prefix_hit_tokens_total") + cut
    return 100.0 * cut / seen if seen > 0 else None
