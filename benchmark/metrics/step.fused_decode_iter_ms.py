"""Device time of one decode iteration inside a fused dispatch: self time
under `lane.decode` inside the `_fused_chunk` executions `trace.steps`
admits, over the iterations they ran there (`k` of the record, or `k - 1`
behind a mixed pass, which ran the first under `lane.mixed`;
`benchmark/lanes.py`).  `step.decode_iter_ms` reads `_paged_decode_chunk`
alone; a closed-loop cell's window is almost all fused dispatches.  The note
splits by K, `[executions, iterations, ms an iteration]`, and gives the mean
occupancy of the records.  A program without lanes (the parent of PR 53)
reads nothing."""

from benchmark import lanes


def read(ctx):
    got = lanes.fused_executions(ctx)
    if got is None:
        return None
    rows = [(rec, lanes.decode_iters(rec), 1e3 * by.get("decode", 0.0))
            for rec, by in got["admitted"]]
    rows = [r for r in rows if r[1] > 0]
    iters = sum(n for _, n, _ in rows)
    if not iters:
        return None
    by_k = {}
    for rec, n, ms in rows:
        e = by_k.setdefault(str(rec["k"]), [0, 0, 0.0])
        e[0] += 1
        e[1] += n
        e[2] += ms
    return {"value": sum(ms for _, _, ms in rows) / iters,
            "note": {"held": got["held"], "admitted": len(got["admitted"]),
                     "occupancy_mean": sum(rec["occupancy"] for rec, _, _ in rows) / len(rows),
                     "by_k": {k: [n, i, ms / i] for k, (n, i, ms) in sorted(by_k.items())}}}
