"""The straggler expert's share of an expert-layer call's work: the largest
per-expert token count summed over the calls, over all (token, expert) pairs
(`llm_moe_max_load_total / llm_moe_assignments_total`).  Even routing over 128
experts reads 0.8 % on a large call; a decode call of 8 rows x 6 reads at least
1/48 = 2.1 % however even."""


def read(ctx):
    d = lambda k: ctx.counters1.get(k, 0) - ctx.counters0.get(k, 0)  # noqa: E731
    pairs = d("moe_assignments_total")
    return 100.0 * d("moe_max_load_total") / pairs if pairs > 0 else None
