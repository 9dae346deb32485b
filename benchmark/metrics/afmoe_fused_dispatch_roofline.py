"""The least time a fused prefill-decode dispatch could take over the time it
took, for the `afmoe` block (`benchmark/roofline_afmoe.py`).

Least time: each of the dispatch's `k` decode iterations by its bytes over the
chip's memory bandwidth (weights outside the experts once, the experts the
router's counters say the iterations touched, the keys and values of each
riding row's context — at the window in the window layers), plus the prompt
chunk by its operations over peak FLOP/s.  Time taken: the device time of the
`_fused_chunk` executions that `trace.steps` admits.  Every count errs low:
rows are counted as `decode_iter_roofline` counts them; the chunk attends only
itself, at the window; of the dispatch's experts-touched counter the most the
chunk can have touched is taken off, and what is left is spread over the `k`
iterations; nothing is re-read.  A dispatch record without the counters (a
program without them) or a configuration of another block reads nothing.
"""

import importlib

from benchmark import roofline, roofline_afmoe as rf, trace

PROGRAM = "_fused_chunk"
# the rows of a dispatch, counted as the dense block's decode roofline counts them
contexts_of = importlib.import_module("benchmark.metrics.decode_iter_roofline").contexts_of


def rows_by_rid(ctx):
    by_id = {r["id"]: r for r in ctx.records}
    return {rid: by_id[i] for i, tl in ctx.timelines.items() if i in by_id
            for rid in tl.get("rids") or ()}


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or ctx.config.get("reference") != "afmoe":
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
