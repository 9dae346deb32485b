"""What the host did between two dispatches, read from the program's own spans.

Since PR 24 the serving loop names what it does between dispatch records
(`obs.LOOP_PHASES`), and every record carries the gap that led to it:
`gap_ms` (end of the previous record to its own start), `host_ms` (that gap
by phase; `idle` is the loop blocked on an empty inbox, which is waiting for
work and is left out of every reading here), `gap_cpu_ms` (the loop thread's
CPU time over the gap).  The same phases and dispatches are in the profiler's
trace as `llm.loop.<phase>` and `llm.dispatch` host events, on the clock of
the device events.  A request's timeline starts with a `received` span at
the POST.

Against a program without them (the parent of PR 24) every reader here finds
nothing and returns None, or, for the trace, reads that nothing was named.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace

LOOP_PREFIX = "llm.loop."
DISPATCH_EVENT = "llm.dispatch"
IN_DISPATCH = "in dispatch"
UNNAMED = "unnamed"
SCHEDULER = ("barrier", "admit", "prep", "emit")   # serving.py's phases
SERVER = ("control", "intake", "deliver")           # server.py's, less `idle`

Named = Tuple[str, float, float]  # label, start_s, end_s


def in_window(ctx) -> List[dict]:
    """The window's dispatch records that carry their gap."""
    return [d for d in ctx.dispatches
            if "gap_ms" in d and "host_ms" in d
            and ctx.t0 <= d["start"] <= ctx.t0 + ctx.seconds]


def steps(ctx) -> List[dict]:
    """Those of them that are serving steps (decode or fused chunks)."""
    return [d for d in in_window(ctx)
            if d["kind"].startswith("decode") or d["kind"] == "fused"]


def busy_gap_ms(rec: dict) -> float:
    return rec["gap_ms"] - rec["host_ms"].get("idle", 0.0)


def phase_sum_ms(recs: Iterable[dict], phases: Sequence[str]) -> float:
    return sum(r["host_ms"].get(p, 0.0) for r in recs for p in phases)


def per_dispatch(ctx, phases: Sequence[str]) -> Optional[float]:
    recs = steps(ctx)
    return phase_sum_ms(recs, phases) / len(recs) if recs else None


def phase_means(recs: Sequence[dict]) -> Dict[str, float]:
    """ms per record, by phase, `idle` left out."""
    names = sorted({p for r in recs for p in r["host_ms"]} - {"idle"})
    return {p: phase_sum_ms(recs, (p,)) / len(recs) for p in names}


def traced_seqs(ctx) -> set:
    """Ring numbers of the records the profiler's sub-window holds: those a
    traced execution was joined to."""
    mods = (ctx.trace or {}).get("modules") or ()
    return {m["dispatch"]["seq"] for m in mods if m.get("dispatch")}


def span_p50_ms(ctx, state: str) -> Optional[float]:
    """Median over the window's requests of the time their timeline spent in
    `state` (a request can enter a state more than once)."""
    sums = []
    for tl in ctx.timelines.values():
        d = [sp["duration_ms"] for sp in tl["spans"]
             if sp["state"] == state and sp["duration_ms"] is not None]
        if d:
            sums.append(sum(d))
    return ctx.stats.percentile(sums, 50)


# -- the same spans on the profiler's clock ----------------------------------

def newest_xplane(out_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return paths[-1] if paths else None


def loop_events(planes: Dict[str, Dict[str, List[trace.Event]]]) -> List[Named]:
    """The serving loop's own host events, labelled by phase (`llm.loop.emit`
    -> `emit`) or `in dispatch`, in order of start."""
    out = []
    for pname, lines in planes.items():
        if trace.is_device(pname):
            continue
        for evs in lines.values():
            for name, s, e in evs:
                if name == DISPATCH_EVENT:
                    out.append((IN_DISPATCH, s, e))
                elif name.startswith(LOOP_PREFIX):
                    out.append((name[len(LOOP_PREFIX):], s, e))
    return sorted(out, key=lambda ev: ev[1])


def device_ops(planes) -> List[trace.Event]:
    dev = sorted(n for n, l in planes.items() if trace.is_device(n) and trace.OPS_LINE in l)
    return planes[dev[0]][trace.OPS_LINE] if dev else []


def split_by_overlap(gaps: Sequence[Tuple[float, float]], named: Sequence[Named]) -> Dict[str, float]:
    """Seconds of `gaps` by the label of the `named` interval that covers
    them (one thread's events: they do not overlap); the rest `unnamed`."""
    out: Dict[str, float] = {}
    starts = [n[1] for n in named]
    for gs, ge in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, gs) - 1)
        while i < len(named) and named[i][1] < ge:
            label, s, e = named[i]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[label] = out.get(label, 0.0) + ov
                covered += ov
            i += 1
        if ge - gs - covered > 1e-12:
            out[UNNAMED] = out.get(UNNAMED, 0.0) + ge - gs - covered
    return out


def named_idle(planes) -> Optional[Dict[str, float]]:
    """Device idle gaps of `trace.MIN_GAP_S` or more (the first device's
    `XLA Ops`), split by what the loop thread was doing.  None without a
    device plane."""
    ops = device_ops(planes)
    if not ops:
        return None
    gaps = [(s, e) for s, e in trace.gaps((s, e) for _, s, e in ops) if e - s >= trace.MIN_GAP_S]
    return split_by_overlap(gaps, loop_events(planes))


def two_clocks(planes, ctx) -> Optional[dict]:
    """The same host time from both records of it: seconds under `llm.loop.*`
    events between the trace's first and last `llm.dispatch` event, and the
    `host_ms` of the dispatch records those events belong to.  An event is
    matched to its record by identity: a traced execution lies inside one
    `llm.dispatch` event and was joined to one record, and both are numbered
    in the loop thread's order."""
    events = loop_events(planes)
    disp = [ev for ev in events if ev[0] == IN_DISPATCH]
    if len(disp) < 2:
        return None
    first_seq = None
    for m in (ctx.trace or {}).get("modules") or ():
        rec = m.get("dispatch")
        if rec is None:
            continue
        mid = m["start_s"] + m["seconds"] / 2
        for j, (_, s, e) in enumerate(disp):
            if s <= mid <= e:
                first_seq = rec["seq"] - j
                break
        if first_seq is not None:
            break
    if first_seq is None:
        return None
    lo, hi = disp[0][2], disp[-1][1]
    loop_s = sum(min(e, hi) - max(s, lo) for label, s, e in events
                 if label != IN_DISPATCH and min(e, hi) > max(s, lo))
    seqs = range(first_seq + 1, first_seq + len(disp))
    by_seq = {d["seq"]: d for d in ctx.dispatches}
    recs = [by_seq[q] for q in seqs if q in by_seq and "host_ms" in by_seq[q]]
    if len(recs) != len(seqs):
        return None
    host_s = sum(r["gap_ms"] for r in recs) / 1000.0
    return {"gaps": len(recs), "loop_events_s": loop_s, "records_host_s": host_s,
            "differ_pct": 100.0 * abs(loop_s - host_s) / host_s if host_s else None}
