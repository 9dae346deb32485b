"""Share of the device's idle time that the serving loop's own annotations
name: the idle gaps of 20 us or more on the first device (`trace.gaps` over
its `XLA Ops`, from the newest trace under the benchmark's output directory)
split by overlap with the `llm.loop.<phase>` and `llm.dispatch` host events
of the same file; value = named seconds / idle seconds.  Both are on the
profiler's clock: no clock join.  The note gives the seconds per phase, inside
dispatches and unnamed, and the same host time read both ways (seconds under
`llm.loop.*` events against the `host_ms` of the dispatch records they belong
to).  A trace without annotations reads 0 and says so."""

from benchmark import hostspans, run, trace


def read(ctx):
    if ctx.trace is None:
        return None
    path = hostspans.newest_xplane(str(run.OUT))
    if path is None:
        return None
    planes = trace.read_planes(path)
    idle = hostspans.named_idle(planes)
    if idle is None:
        return None
    total = sum(idle.values())
    named = total - idle.get(hostspans.UNNAMED, 0.0)
    note = {"idle_s": total, "seconds_by_phase": dict(sorted(idle.items(), key=lambda kv: -kv[1]))}
    if not hostspans.loop_events(planes):
        note["annotations"] = "none: the program emits no llm.loop / llm.dispatch events"
    note["two_clocks"] = hostspans.two_clocks(planes, ctx)
    return {"value": 100.0 * named / total if total > 0 else 0.0, "note": note}
