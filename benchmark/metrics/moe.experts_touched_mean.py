"""Distinct experts an expert-layer call touched, mean over the calls of the
window and its drain: `llm_moe_experts_touched_total / llm_moe_layer_calls_total`
(the router's own output, returned with the loop's packed fetch).  A call is one
layer of one forward: a decode iteration of up to 8 rows, or a prompt chunk."""


def read(ctx):
    d = lambda k: ctx.counters1.get(k, 0) - ctx.counters0.get(k, 0)  # noqa: E731
    calls = d("moe_layer_calls_total")
    return d("moe_experts_touched_total") / calls if calls > 0 else None
