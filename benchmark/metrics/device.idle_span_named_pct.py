"""Share of the device's idle time that a child span of the serving loop
names: the idle gaps `device.idle_named_pct` takes (20 us or more on the first
device, newest trace), each part given to the innermost `llm.span.<name>` host
event over it, else to its `llm.loop.<phase>` event, else `in dispatch`, else
`unnamed`; value = seconds under a span / idle seconds.  Both clocks are the
profiler's: no clock join.  The note accounts for every idle second: by span,
by phase remainder, the `in dispatch` remainder (the wait for the device and
the fetch), `unnamed` — and what `hostspans.named_idle` leaves unnamed on the
same planes."""

from benchmark import hostspans, run, spans, trace


def read(ctx):
    if ctx.trace is None:
        return None
    path = hostspans.newest_xplane(str(run.OUT))
    if path is None:
        return None
    planes = trace.read_planes(path)
    idle = spans.named_idle(planes)
    if idle is None:
        return None
    total = sum(idle.values())
    order = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))  # noqa: E731
    by_span = {k: v for k, v in idle.items() if k in spans.PARENT}
    rest = {k: v for k, v in idle.items()
            if k not in spans.PARENT and k not in (hostspans.IN_DISPATCH, hostspans.UNNAMED)}
    return {
        "value": 100.0 * sum(by_span.values()) / total if total > 0 else 0.0,
        "note": {"idle_s": total, "seconds_by_span": order(by_span), "seconds_by_phase_remainder": order(rest),
                 "in_dispatch_remainder_s": idle.get(hostspans.IN_DISPATCH, 0.0),
                 "unnamed_s": idle.get(hostspans.UNNAMED, 0.0),
                 "unnamed_by_phase_s": hostspans.named_idle(planes).get(hostspans.UNNAMED, 0.0)},
    }
