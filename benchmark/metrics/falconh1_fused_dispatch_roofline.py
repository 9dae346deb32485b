"""The least time a fused prefill-decode dispatch could take over the time it
took, for the `falcon_h1` block (`benchmark/roofline_falcon_h1.py`): the whole
step's share.

Least time: each of the dispatch's `k` decode iterations by its bytes over the
chip's memory bandwidth (the layers and the head once, each riding row's state
read and written, its keys and values at its depth), plus the prompt chunk by
its operations over peak FLOP/s.  Time taken: the device time of the
`_fused_chunk` executions that `trace.steps` admits.  Every count errs low (see
`roofline_falcon_h1.py`); rows are counted as `decode_iter_roofline` counts
them.  A configuration of another block reads nothing.
"""

import importlib

from benchmark import roofline, roofline_falcon_h1 as rf, trace

PROGRAM = "_fused_chunk"
_fused = importlib.import_module("benchmark.metrics.afmoe_fused_dispatch_roofline")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or ctx.config.get("reference") != "falcon_h1":
        return None
    by_rid = _fused.rows_by_rid(ctx)
    least = took = 0.0
    n = chunk_tokens = iters = rows = 0
    for m in trace.steps(ctx.trace, (PROGRAM,)):
        d = m["dispatch"]
        contexts = _fused.contexts_of(d, by_rid)
        tokens = int(d["prefill_tokens"])
        t_iter, _ = roofline.least_seconds(
            0.0, rf.decode_iter_bytes(ctx.config, contexts), ctx.peaks, ctx.chips)
        t_chunk, _ = roofline.least_seconds(
            rf.chunk_flops(ctx.config, tokens), 0.0, ctx.peaks, ctx.chips)
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
