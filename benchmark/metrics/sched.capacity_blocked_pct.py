"""Why a free slot stayed free: of the window's decode and fused records
that met a queue (`queued > 0`), weighted by the time each stands for
(`wall_ms + gap_ms - idle`), the share whose `blocked` is `capacity` (the
queue's head waits for pool blocks).  The note gives the share of each reason
(`lane`, `capacity`, `slot`, `restoring`) and the queued share of all steps."""

from benchmark import hostspans


def read(ctx):
    recs = [r for r in hostspans.steps(ctx) if "blocked" in r]
    if not recs:
        return None
    weight = lambda r: r["wall_ms"] + hostspans.busy_gap_ms(r)  # noqa: E731
    queued = [r for r in recs if r.get("queued", 0) > 0]
    total = sum(map(weight, queued))
    by_reason = {}
    for r in queued:
        by_reason[str(r["blocked"])] = by_reason.get(str(r["blocked"]), 0.0) + weight(r)
    share = {k: 100.0 * v / total for k, v in sorted(by_reason.items(), key=lambda kv: -kv[1])}
    return {
        "value": share.get("capacity", 0.0),
        "note": {"records": len(recs), "queued_records": len(queued), "pct_by_reason": share,
                 "queued_pct_of_step_time": 100.0 * total / sum(map(weight, recs))},
    }
