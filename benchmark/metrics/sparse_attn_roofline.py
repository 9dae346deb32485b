"""The least time attention over the chosen keys could take over the device
time inside its scope, `attn.sparse` (`models/dsa_moe.py`: a decode row's
gather of its chosen slots from the pool and attention over them, a prompt
chunk's flash kernel under the mask).  The q/k/v and output projections, the
per-head norms and rope run under `attn.proj` and are no part of it: a kernel
for the gather moves this share undiluted.

Least time, over the `_fused_chunk` and `_paged_decode_chunk` executions that
`trace.steps` admits: attention of a prompt chunk on itself (a query's keys
capped at `topk`) over peak FLOP/s, plus each decode iteration's chosen slots'
keys and values (2 KiB a slot a layer, never the dense context) over the
memory bandwidth (`benchmark/roofline_dsa_moe.py`).  Time taken: the SELF time
of every traced operation under the scope, cut executions included, so the
share errs low twice over.  A program without the scope reads nothing.
"""

import importlib

from benchmark import hostspans, roofline, roofline_dsa_moe as rf, scopes, trace

PROGRAMS = ("_fused_chunk", "_paged_decode_chunk")
PREFIXES = ("attn.", "moe.", "dense.", "head", "admit.")
_fused = importlib.import_module("benchmark.metrics.dsamoe_fused_dispatch_roofline")


def scope_seconds(ctx):
    """{scope: self seconds} of the run's newest trace, or None."""
    if ctx.trace is None or ctx.config.get("reference") != "dsa_moe":
        return None
    from benchmark import run

    path = hostspans.newest_xplane(str(run.OUT))
    return scopes.self_seconds_by_scope(path, PREFIXES) if path else None


def share(ctx, names):
    """100 x self seconds under the scopes `names` / busy seconds; the note
    lists each scope (`attn.proj`, the projections around attention, too)."""
    by_scope = scope_seconds(ctx)
    total = sum((by_scope or {}).values())
    mine = sum((by_scope or {}).get(n, 0.0) for n in names)
    if total <= 0 or mine <= 0:
        return None
    return {"value": 100.0 * mine / total,
            "note": {"busy_self_s": total,
                     "seconds_by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))}}


def roofline_of(ctx, names, chunk_flops, decode_iter_bytes):
    """100 x least seconds / self seconds under the scopes `names`."""
    if ctx.peaks is None:
        return None
    by_scope = scope_seconds(ctx)
    took = sum((by_scope or {}).get(n, 0.0) for n in names)
    if took <= 0:
        return None
    by_rid = _fused.rows_by_rid(ctx)
    flops = bytes_ = 0.0
    n = 0
    for m in trace.steps(ctx.trace, PROGRAMS):
        d = m["dispatch"]
        flops += chunk_flops(ctx.config, int(d.get("prefill_tokens") or 0))
        bytes_ += d["k"] * decode_iter_bytes(ctx.config, _fused.contexts_of(d, by_rid))
        n += 1
    t_c, _ = roofline.least_seconds(flops, 0.0, ctx.peaks, ctx.chips)
    t_m, _ = roofline.least_seconds(0.0, bytes_, ctx.peaks, ctx.chips)
    if t_c + t_m <= 0:
        return None
    return {"value": 100.0 * (t_c + t_m) / took,
            "note": {"executions": n, "chunk_s": t_c, "decode_s": t_m, "took_s": took}}


def read(ctx):
    return roofline_of(ctx, ("attn.sparse",), rf.sparse_chunk_flops, rf.sparse_decode_iter_bytes)
