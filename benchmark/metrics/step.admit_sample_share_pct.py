"""Share of the device's busy time inside the fused dispatch's admission
sample, the scope `admit.sample` (`jax_llama_tpu/serving.py`:
`_admission_sample` — the one-token head product of a prompt that completes in
the dispatch, the first token's argmax or draw, its logprob and finite check),
by self time of the traced operations (`benchmark/scopes.py`).  The scope is
the program's, not a block's, so every cell whose window runs `_fused_chunk`
reads it.  A program without the scope reads nothing."""

from benchmark import hostspans, scopes

SCOPE = "admit.sample"


def read(ctx):
    if ctx.trace is None:
        return None
    from benchmark import run

    path = hostspans.newest_xplane(str(run.OUT))
    by_scope = scopes.self_seconds_by_scope(path, (SCOPE,)) if path else None
    total = sum((by_scope or {}).values())
    if total <= 0 or by_scope.get(SCOPE, 0.0) <= 0:
        return None
    return {"value": 100.0 * by_scope[SCOPE] / total,
            "note": {"busy_self_s": total, "admit_sample_s": by_scope[SCOPE]}}
