"""Host time before a dispatch that carries a prompt chunk: the mean of
`gap_ms - host_ms.idle` over the window's `fused` records.  The note gives the
ms a record by part (every child span, `admit.self` / `prep.self` and the
phases without spans; `submit_ms` is inside the dispatch, not the gap), and
the same for the `decode` records."""

from benchmark import hostspans, spans


def read(ctx):
    recs = spans.in_window(ctx)
    fused = [r for r in recs or () if r["kind"] == "fused"]
    if not fused:
        return None
    decode = [r for r in recs if r["kind"].startswith("decode")]
    return {
        "value": sum(map(hostspans.busy_gap_ms, fused)) / len(fused),
        "note": {"fused_records": len(fused), "ms_by_part": spans.part_means(fused),
                 "decode_records": len(decode), "decode_ms_by_part": spans.part_means(decode)},
    }
