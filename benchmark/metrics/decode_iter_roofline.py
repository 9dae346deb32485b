"""The least time a decode iteration could take over the time it took.

Least time: the bytes the iteration must read (weights once, plus keys and
values of each riding row's context; `roofline.py`) over the chip's memory
bandwidth, or its operations over peak FLOP/s where that is larger.  Time
taken: the device time of the `_paged_decode_chunk` executions that
`trace.steps` admits (the same ones `step.decode_iter_ms` reads), each over
the `k` of its own dispatch record.

Every count errs low, so that the share can pass 100 % only if the time or `k`
is wrong, never because bytes were counted that did not move:
- the rows are the dispatch record's own (`rids`), found in the load
  generator's records through the request timelines; a row that cannot be
  found counts nothing;
- a row counts only once its first token has reached the client, with its
  prompt plus the tokens the client had by the dispatch's start, less two
  chunks of slack (tokens arrive a chunk at a time, not evenly);
- the kernel reads whole 128-token blocks and the program moves activations
  and the sampler's logits besides: none of that is counted.
"""

from benchmark import roofline, trace

PROGRAM = "_paged_decode_chunk"


def contexts_of(dispatch, by_rid):
    """Lower bounds of the contexts of the rows that rode `dispatch`."""
    out = []
    for rid in dispatch.get("rids") or ():
        r = by_rid.get(rid)
        if r is None or r["first"] is None or r["first"] > dispatch["start"]:
            continue
        span = max(r["last"] - r["first"], 1e-9)
        had = r["n_tokens"] * min(dispatch["start"] - r["first"], span) / span
        out.append(r["prompt_tokens"] + max(had - 2 * dispatch["k"], 0.0))
    return out


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    by_id = {r["id"]: r for r in ctx.records}
    by_rid = {rid: by_id[i] for i, tl in ctx.timelines.items() if i in by_id
              for rid in tl.get("rids") or ()}
    least = took = 0.0
    iters = rows = 0
    bounds = {}
    for m in trace.steps(ctx.trace, (PROGRAM,)):
        d = m["dispatch"]
        contexts = contexts_of(d, by_rid)
        t, bound = roofline.least_seconds(
            roofline.decode_iter_flops(ctx.config, contexts),
            roofline.decode_iter_bytes(ctx.config, contexts), ctx.peaks, ctx.chips,
        )
        least += t * d["k"]
        took += m["seconds"]
        iters += d["k"]
        rows += len(contexts)
        bounds[bound] = bounds.get(bound, 0) + 1
    if took <= 0:
        return None
    seen = sum(1 for m in ctx.trace["modules"] if m["program"] == PROGRAM)
    return {"value": 100.0 * least / took,
            "note": {"bound": bounds, "executions_in_trace": seen, "iterations": iters,
                     "rows_counted": rows, "least_s": least, "took_s": took}}
