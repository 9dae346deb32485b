"""Host time spent making device operands beside a dispatch: `admit.upload`
(the prefill lane's six arrays) + `prep.sync_rows` (the dirty rows' ten
uploads and their scatter) + `prep.snapshots` (two scalars) summed over the
window's records, per `fused` record: what one packed upload a dispatch could
remove.  The note gives each part per fused record and its count."""

from benchmark import spans


def read(ctx):
    recs = spans.in_window(ctx)
    fused = [r for r in recs or () if r["kind"] == "fused"]
    if not fused:
        return None
    n = len(fused)
    return {
        "value": spans.part_sum_ms(recs, spans.UPLOADS) / n,
        "note": {"fused_records": n,
                 "ms_per_fused": {p: spans.part_sum_ms(recs, (p,)) / n for p in spans.UPLOADS},
                 "counts": spans.span_counts(recs, spans.UPLOADS)},
    }
