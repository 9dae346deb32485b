"""The least time the chunked scans of the prompt chunks could take over the
device time they took (`ops/ssm.py`: `ssd_scan`, the matrix-a-head recurrence
as chunked matmuls in XLA, one call a layer a prompt chunk).

Least time, over the `_fused_chunk` executions that `trace.steps` admits: the
LARGER of the scan's operations over peak FLOP/s and its bytes over the memory
bandwidth (`benchmark/roofline_falcon_h1.py`: one pass a matmul, operands in
the activation type, the state once in and once out), times the layers.  Time
taken: the SELF time of every traced operation under the scope `ssm.scan`, cut
executions included, so the share errs low twice over.  The decode
iteration's step is under `ssm.step` and not in it.  A program without the
scope, or a configuration of another block, reads nothing.
"""

from benchmark import hostspans, roofline, roofline_falcon_h1 as rf, scopes, trace

PROGRAM = "_fused_chunk"
# `ssm.scan` and `ssm.step` lie inside `ssm.mix`, so `ssm.mix` is not asked for:
# a traced operation counts under the first of these its scope path names.
PREFIXES = ("ssm.scan", "ssm.step", "attn.", "dense.", "head")


def scope_seconds(ctx):
    """{scope: self seconds} of the run's newest trace (every other
    operation under ""), or None."""
    if ctx.trace is None:
        return None
    from benchmark import run

    path = hostspans.newest_xplane(str(run.OUT))
    return scopes.self_seconds_by_scope(path, PREFIXES) if path else None


def share(ctx, scope):
    """100 x self seconds under `scope` / busy seconds for a configuration of
    this block; the note lists each scope.  What `step.ssm_step_share_pct`
    and `step.head_share_pct` return."""
    if ctx.config.get("reference") != "falcon_h1":
        return None
    by_scope = scope_seconds(ctx)
    total = sum((by_scope or {}).values())
    if total <= 0 or by_scope.get(scope, 0.0) <= 0:
        return None
    return {"value": 100.0 * by_scope[scope] / total,
            "note": {"busy_self_s": total,
                     "seconds_by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))}}


def read(ctx):
    if ctx.peaks is None or ctx.config.get("reference") != "falcon_h1":
        return None
    took = (scope_seconds(ctx) or {}).get("ssm.scan", 0.0)
    if took <= 0:
        return None
    layers = ctx.config["num_hidden_layers"]
    least = 0.0
    n = 0
    for m in trace.steps(ctx.trace, (PROGRAM,)):
        tokens = int(m["dispatch"].get("prefill_tokens") or 0)
        if tokens:
            t, _ = roofline.least_seconds(
                layers * rf.ssd_scan_flops(ctx.config, tokens),
                layers * rf.ssd_scan_bytes(ctx.config, tokens), ctx.peaks, ctx.chips)
            least += t
            n += 1
    if least <= 0:
        return None
    return {"value": 100.0 * least / took,
            "note": {"executions": n, "least_s": least, "took_s": took}}
