"""The host's share of the loop thread's time, over the whole window:
100 x sum(gap - idle) / sum(gap - idle + wall_ms) over every dispatch record
of the window.  The host-clock twin of the device's idle share, from 51 s of
records where the profiler gives 3 s."""

from benchmark import hostspans


def read(ctx):
    recs = hostspans.in_window(ctx)
    gap = sum(map(hostspans.busy_gap_ms, recs))
    total = gap + sum(r["wall_ms"] for r in recs)
    if total <= 0:
        return None
    return {"value": 100.0 * gap / total,
            "note": {"records": len(recs), "gap_less_idle_ms": gap,
                     "ms_by_phase": hostspans.phase_means(recs)}}
