"""Host time between two serving steps: the mean, over the window's decode
and fused dispatch records, of the gap that led to each (`gap_ms`, end of the
previous record to its own start on the loop thread's clock) less the part
of it the loop was blocked on an empty inbox (`host_ms.idle`).  The note gives
the ms per phase, and the mean inside and outside the profiler's sub-window
(inside: records a traced execution was joined to), which is what the
profiler costs the host."""

from benchmark import hostspans


def read(ctx):
    recs = hostspans.steps(ctx)
    if not recs:
        return None

    def mean(rs):
        return sum(map(hostspans.busy_gap_ms, rs)) / len(rs) if rs else None

    traced = hostspans.traced_seqs(ctx)
    return {
        "value": mean(recs),
        "note": {"records": len(recs), "ms_by_phase": hostspans.phase_means(recs),
                 "inside_trace_ms": mean([r for r in recs if r["seq"] in traced]),
                 "outside_trace_ms": mean([r for r in recs if r["seq"] not in traced]),
                 "compiles_in_gaps": sum(r.get("compiles", 0) for r in recs)},
    }
