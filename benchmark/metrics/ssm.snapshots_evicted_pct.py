"""State snapshots the snapshot pool took back from their radix nodes for
room, over the window and its drain: 100 x `ssm_snapshots_evicted_total` /
`ssm_snapshots_taken_total`.  0 while the pool holds every snapshot the
documents in flight hang on it; near 100 when each new one costs an old one (a
re-ask of the old one's document then finds its match cut:
`ssm.match_tokens_cut_pct`).  A program without the counters, or a window that
took none, reads nothing."""


def read(ctx):
    names = ("ssm_snapshots_evicted_total", "ssm_snapshots_taken_total")
    if any(k not in ctx.counters1 for k in names):
        return None
    evicted, taken = (ctx.counters1[k] - ctx.counters0.get(k, 0) for k in names)
    return 100.0 * evicted / taken if taken > 0 else None
