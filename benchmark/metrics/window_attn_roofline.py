"""The least time the window attention layers' sub-blocks could take over the
device time inside their scope, `attn.window` (`models/afmoe.py`: projections,
norms, rope, the kernel and the gate of a window layer).

Least time, over the `_fused_chunk` and `_paged_decode_chunk` executions that
`trace.steps` admits: a prompt chunk's operations in the window layers
(projections for every token, attention of the chunk on itself at the window)
over peak FLOP/s, plus each decode iteration's bytes there (the projections
once, each riding row's keys and values at the window) over the memory
bandwidth (`benchmark/roofline_afmoe.py`).  Time taken: the SELF time of every
traced operation under `attn.window`, cut executions included, so the share
errs low twice over.  A program without the scope reads nothing.
"""

import importlib

from benchmark import hostspans, roofline, roofline_afmoe as rf, scopes, trace

PROGRAMS = ("_fused_chunk", "_paged_decode_chunk")
SCOPE = "attn.window"
PREFIXES = ("attn.", "moe.", "dense.")
_fused = importlib.import_module("benchmark.metrics.afmoe_fused_dispatch_roofline")


def scope_seconds(ctx):
    """{scope: self seconds} of the run's newest trace, or None."""
    if ctx.trace is None:
        return None
    from benchmark import run

    path = hostspans.newest_xplane(str(run.OUT))
    return scopes.self_seconds_by_scope(path, PREFIXES) if path else None


def share(ctx, scope):
    """100 x self seconds under `scope` / busy seconds; the note lists each
    scope.  What the two `step.*_attn_share_pct` readers return."""
    by_scope = scope_seconds(ctx)
    total = sum((by_scope or {}).values())
    if total <= 0 or by_scope.get(scope, 0.0) <= 0:
        return None
    return {"value": 100.0 * by_scope[scope] / total,
            "note": {"busy_self_s": total,
                     "seconds_by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))}}


def read(ctx):
    if ctx.peaks is None or ctx.config.get("reference") != "afmoe":
        return None
    by_scope = scope_seconds(ctx)
    took = (by_scope or {}).get(SCOPE, 0.0)
    if took <= 0:
        return None
    by_rid = _fused.rows_by_rid(ctx)
    flops = bytes_ = 0.0
    n = 0
    for m in trace.steps(ctx.trace, PROGRAMS):
        d = m["dispatch"]
        flops += rf.window_chunk_flops(ctx.config, int(d.get("prefill_tokens") or 0))
        bytes_ += d["k"] * rf.window_decode_iter_bytes(ctx.config, _fused.contexts_of(d, by_rid))
        n += 1
    t_c, _ = roofline.least_seconds(flops, 0.0, ctx.peaks, ctx.chips)
    t_m, _ = roofline.least_seconds(0.0, bytes_, ctx.peaks, ctx.chips)
    if t_c + t_m <= 0:
        return None
    return {"value": 100.0 * (t_c + t_m) / took,
            "note": {"executions": n, "chunk_s": t_c, "decode_s": t_m, "took_s": took}}
