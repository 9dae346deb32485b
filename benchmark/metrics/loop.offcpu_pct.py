"""Share of the host time between serving steps in which the loop thread was
not running: 100 x sum(gap - idle - gap_cpu_ms) / sum(gap - idle) over the
window's decode and fused records (`gap_cpu_ms` is `time.thread_time()` over
the gap).  Time the thread was runnable or blocked but off the CPU: the GIL
held by a handler or load-generator thread, a descheduled core, the
profiler.  Idle polls burn almost no CPU, so their CPU time is not taken out."""

from benchmark import hostspans


def read(ctx):
    recs = [r for r in hostspans.steps(ctx) if "gap_cpu_ms" in r]
    busy = sum(map(hostspans.busy_gap_ms, recs))
    if busy <= 0:
        return None
    cpu = sum(r["gap_cpu_ms"] for r in recs)
    return {"value": 100.0 * max(busy - cpu, 0.0) / busy,
            "note": {"records": len(recs), "gap_less_idle_ms": busy, "cpu_ms": cpu}}
