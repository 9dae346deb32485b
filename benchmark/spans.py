"""The parts of the host's gap, read from the program's child spans.

Since PR 38 the serving loop times the parts of its phases and of a
dispatch's own host time (`obs.LOOP_SPANS`), and every dispatch record carries
them beside the phases: `span_ms` / `span_n` (`{span: ms}` / `{span: count}`
closed since the previous record; a nested span's time is in its parent's
too; `host_ms` keeps its keys and its tiling), `submit_ms` (`dispatch_begin`
to the return of the jitted call) and, beside `queued`, `blocked`: why the
queue's head stayed queued.  What a phase spends outside its spans is its
self time, `<phase>.self` here.  The same spans are in the profiler's trace as
`llm.span.<name>` host events, nested inside the `llm.loop.<phase>` and
`llm.dispatch` events `hostspans` reads.

Against a program without them (the parent of PR 38) every reader here finds
nothing and returns None.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence

from . import hostspans, trace

SPAN_PREFIX = "llm.span."
# span -> what it is recorded under: a phase, `dispatch`, or another span.
# The benchmark's own copy (`tests/test_spans.py` holds it to the program's).
PARENT = {
    "admit.restore": "admit", "admit.hash": "admit", "admit.match": "admit",
    "admit.alloc": "admit", "admit.evict": "admit.alloc", "admit.upload": "admit",
    "admit.insert": "admit", "prep.sync_rows": "prep", "prep.snapshots": "prep",
    "dispatch.submit": "dispatch", "dispatch.publish": "dispatch",
    "emit.replay": "emit", "emit.free": "emit.replay",
}
ADMIT_WORK = ("admit.hash", "admit.match", "admit.alloc", "admit.self")
UPLOADS = ("admit.upload", "prep.sync_rows", "prep.snapshots")
ADMISSION = ("admit.upload", "admit.insert")   # a record with one is one admission


def in_window(ctx) -> Optional[List[dict]]:
    """The window's dispatch records with their gap, if the program writes
    the span fields; None where it does not."""
    recs = hostspans.in_window(ctx)
    return recs if any("span_ms" in r for r in recs) else None


def parts_ms(rec: dict) -> Dict[str, float]:
    """One record's gap and host time by part: every span, every phase but
    `idle` (a phase that has spans as `<phase>.self`: what is left of it outside
    them)."""
    spans = rec.get("span_ms", {})
    out = dict(spans)
    for phase, ms in rec["host_ms"].items():
        if phase == "idle":
            continue
        kids = sum(ms_ for s, ms_ in spans.items() if PARENT.get(s) == phase)
        out[phase + ".self" if phase in PARENT.values() else phase] = ms - kids
    return out


def part_sum_ms(recs: Iterable[dict], names: Sequence[str]) -> float:
    return sum(parts.get(n, 0.0) for parts in map(parts_ms, recs) for n in names)


def part_means(recs: Sequence[dict]) -> Dict[str, float]:
    """ms a record by part, largest first."""
    sums: Dict[str, float] = {}
    for parts in map(parts_ms, recs):
        for name, ms in parts.items():
            sums[name] = sums.get(name, 0.0) + ms
    return {n: v / len(recs) for n, v in sorted(sums.items(), key=lambda kv: -kv[1])}


def span_counts(recs: Iterable[dict], names: Sequence[str]) -> Dict[str, int]:
    return {n: sum(r.get("span_n", {}).get(n, 0) for r in recs) for n in names}


# -- the same spans on the profiler's clock -----------------------------------

def span_events(planes) -> List[hostspans.Named]:
    out = []
    for pname, lines in planes.items():
        if trace.is_device(pname):
            continue
        for evs in lines.values():
            out += [(name[len(SPAN_PREFIX):], s, e) for name, s, e in evs
                    if name.startswith(SPAN_PREFIX)]
    return out


def innermost(events: Sequence[hostspans.Named]) -> List[hostspans.Named]:
    """One thread's nested events cut into pieces that do not overlap, each
    labelled by the innermost event over it (a child is clipped to its
    parent)."""
    out: List[hostspans.Named] = []
    stack: List[list] = []  # [label, end], outermost first
    cur = 0.0
    for label, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])) + [("", float("inf"), 0.0)]:
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if end > cur:
                out.append((top, cur, end))
                cur = end
        if stack:
            if s > cur:
                out.append((stack[-1][0], cur, s))
            e = min(e, stack[-1][1])
        cur = s
        stack.append([label, e])
    return out


def named_idle(planes) -> Optional[Dict[str, float]]:
    """`hostspans.named_idle`'s gaps, each part given to the innermost
    `llm.span.*` event over it, else its phase, else `in dispatch`, else
    `unnamed`.  None without a device plane or without a span event."""
    spans = span_events(planes)
    ops = hostspans.device_ops(planes)
    if not ops or not spans:
        return None
    gaps = [(s, e) for s, e in trace.gaps((s, e) for _, s, e in ops) if e - s >= trace.MIN_GAP_S]
    return hostspans.split_by_overlap(gaps, innermost(hostspans.loop_events(planes) + spans))
