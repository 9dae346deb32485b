"""The least time the mHC units could take over the device time inside their
scopes, `hc.coeff` + `hc.pre` + `hc.post` (`jax_llama_tpu/ops/mhc.py`: the
stream's norm, its projection onto the coefficients, the sigmoids and Sinkhorn;
the mix into the inner function's input; the mix of its output back into the
streams).

Least time, over the `_fused_chunk` and `_paged_decode_chunk` executions that
`trace.steps` admits: the layer scan's carry — the n streams read once and
written once a LAYER, 2 n C values a token — for the chunk's tokens and for a
row an iteration, plus each unit's `phi` once a pass, over the memory bandwidth
(`benchmark/roofline_mhc_mla_moe.hc_floor_bytes`).  NOT (2n + 2) C a token a
unit: on the v5e XLA keeps a chunk's streams in vector memory between a layer's
units, and a full chunk's operations under `hc.*` take about what that larger
count gives over the HBM bandwidth (PR 51's traced run), so against it a window
of full chunks would read ~100 % with nothing miscounted.  The coefficient path
(a [nC, 24] projection, 20 normalisations of a 4 x 4 matrix a token) is counted
as free.  Time taken: the SELF time of every traced operation under the three
scopes, cut executions included — what XLA fuses of a unit into its neighbours
(`pre` into the inner function's first product, part of `post` into its last)
runs under THEIR scopes and is not in it.  A program without the scopes reads
nothing.
"""

import importlib

from benchmark import hostspans, roofline, roofline_mhc_mla_moe as rf, scopes, trace

PROGRAMS = ("_fused_chunk", "_paged_decode_chunk")
PREFIXES = ("hc.", "mla.", "moe.", "dense.", "head", "admit.")
_afmoe = importlib.import_module("benchmark.metrics.afmoe_fused_dispatch_roofline")


def scope_seconds(ctx):
    """{scope: self seconds} of the run's newest trace, or None."""
    if ctx.trace is None or ctx.config.get("reference") != "mhc_mla_moe":
        return None
    from benchmark import run

    path = hostspans.newest_xplane(str(run.OUT))
    return scopes.self_seconds_by_scope(path, PREFIXES) if path else None


def share(ctx, prefix):
    """100 x self seconds under scopes starting with `prefix` / busy seconds;
    the note lists each scope."""
    by_scope = scope_seconds(ctx)
    total = sum((by_scope or {}).values())
    mine = sum(v for k, v in (by_scope or {}).items() if k.startswith(prefix))
    if total <= 0 or mine <= 0:
        return None
    return {"value": 100.0 * mine / total,
            "note": {"busy_self_s": total,
                     "seconds_by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))}}


def read(ctx):
    if ctx.peaks is None:
        return None
    by_scope = scope_seconds(ctx)
    took = sum(v for k, v in (by_scope or {}).items() if k.startswith("hc."))
    if took <= 0:
        return None
    by_rid = _afmoe.rows_by_rid(ctx)
    bytes_ = 0.0
    n = tokens = 0
    for m in trace.steps(ctx.trace, PROGRAMS):
        d = m["dispatch"]
        chunk = int(d.get("prefill_tokens") or 0)
        rows = len(_afmoe.contexts_of(d, by_rid))
        bytes_ += rf.hc_floor_bytes(ctx.config, chunk + d["k"] * rows, d["k"] + (1 if chunk else 0))
        tokens += chunk + d["k"] * rows
        n += 1
    least, _ = roofline.least_seconds(0.0, bytes_, ctx.peaks, ctx.chips)
    if least <= 0:
        return None
    return {"value": 100.0 * least / took,
            "note": {"executions": n, "tokens": tokens, "least_s": least, "took_s": took,
                     "seconds_by_scope": {k: v for k, v in by_scope.items() if k.startswith("hc.")}}}
