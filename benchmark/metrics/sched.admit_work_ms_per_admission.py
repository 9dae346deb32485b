"""The CPU work of one admission that touches host state only: `admit.hash`
+ `admit.match` + `admit.alloc` + `admit.self` (mirror writes, the stop row,
the slot) summed over the window's records, per record that carries an
`admit.upload` or `admit.insert` span (one admission each).  The note gives
each part, and `admit.evict` (inside `admit.alloc`) with its count."""

from benchmark import spans


def read(ctx):
    recs = spans.in_window(ctx)
    if recs is None:
        return None
    admissions = [r for r in recs if any(s in r.get("span_ms", {}) for s in spans.ADMISSION)]
    if not admissions:
        return None
    n = len(admissions)
    parts = spans.ADMIT_WORK + ("admit.evict",) + spans.ADMISSION
    return {
        "value": spans.part_sum_ms(recs, spans.ADMIT_WORK) / n,
        "note": {"admissions": n, "records": len(recs),
                 "ms_per_admission": {p: spans.part_sum_ms(recs, (p,)) / n for p in parts},
                 "counts": spans.span_counts(recs, [p for p in parts if p in spans.PARENT])},
    }
