"""The least time a fused prefill-decode dispatch could take over the time it
took, for the `mhc_mla_moe` block (`benchmark/roofline_mhc_mla_moe.py`).

Least time: each of the dispatch's `k` decode iterations by its bytes over the
chip's memory bandwidth (weights outside the experts once — the low-rank query's
two projections among them — the experts the router's counters say the
iterations touched, the cached latent of each riding row's context, the mHC
units' parameters and the rows' streams), plus the prompt chunk by its
operations over peak FLOP/s.  Time taken: the device time of the `_fused_chunk`
executions that `trace.steps` admits.  Every count errs low, as
`fused_dispatch_roofline` counts for the block with the plain residual; the
chunk's stream mixes, which are bytes beside its operations, are left out here
and stand in `hc_mix_roofline`.  A dispatch record without the counters or a
configuration of another block reads nothing.
"""

import importlib

from benchmark import roofline, roofline_mhc_mla_moe as rf, trace

PROGRAM = "_fused_chunk"
_afmoe = importlib.import_module("benchmark.metrics.afmoe_fused_dispatch_roofline")
contexts_of, rows_by_rid = _afmoe.contexts_of, _afmoe.rows_by_rid


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or ctx.config.get("reference") != "mhc_mla_moe":
        return None
    by_rid = rows_by_rid(ctx)
    least = took = 0.0
    n = chunk_tokens = iters = rows = 0
    for m in trace.steps(ctx.trace, (PROGRAM,)):
        d = m["dispatch"]
        if "moe" not in d:
            return None
        contexts = contexts_of(d, by_rid)
        tokens = int(d["prefill_tokens"])
        touched = max(0, d["moe"]["experts_touched"] - rf.chunk_experts_touched_max(ctx.config, tokens))
        t_iter, _ = roofline.least_seconds(
            0.0, rf.decode_iter_bytes(ctx.config, contexts, touched / max(d["k"], 1)),
            ctx.peaks, ctx.chips)
        t_chunk, _ = roofline.least_seconds(rf.chunk_flops(ctx.config, tokens), 0.0, ctx.peaks, ctx.chips)
        least += t_iter * d["k"] + t_chunk
        took += m["seconds"]
        n += 1
        chunk_tokens += tokens
        iters += d["k"]
        rows += len(contexts)
    if took <= 0:
        return None
    return {"value": 100.0 * least / took,
            "note": {"dispatches": n, "prompt_tokens": chunk_tokens, "iterations": iters,
                     "rows_counted": rows, "least_s": least, "took_s": took}}
