"""From a profiler trace to device busy time, time per jitted program and a
breakdown.  Read with `jax.profiler.ProfileData` alone.

A trace is the `.xplane.pb` the JAX profiler writes.  A device plane
(`/device:TPU:<n>`) has a line of operations (`XLA Ops`) and a line of whole
executables (`XLA Modules`, one event per run of a jitted program).

- busy: the UNION of the operation intervals of a device, never their sum
  (operations nest, e.g. a `while` around a layer's operations); `busy_s` is
  its mean over the device planes; `window_s` is the span from the first to
  the last device event, which trims the profiler's own start and stop.
- programs: the `XLA Modules` events by program name (`jit_` prefix and run
  ids stripped), each joined to the host's dispatch record that covers it.
- breakdown: operations by SELF time (an event's duration less its children's,
  so a `while` does not hide its body) and idle gaps named by what the host
  was doing: `in:<kind>` inside a dispatch record (submit through fetch),
  `after:<kind>` just behind one, else `no dispatch`.

Host and trace clocks are joined by `TraceAnnotation`s the tracer emits, each
between two readings of `time.monotonic()`; the one whose readings lie closest
together is used, because a thread switch between the reading and the
annotation shifts every join by its length.  A module event is joined to a
dispatch record only if the record names the same program and the event lies
inside it; `aligned_share` says how many of the module events of a millisecond
or more (serving steps, not helpers) were joined.  A reader of time per
iteration or per token takes `steps()`, which leaves out the events the
trace's two ends cut short.
"""

from __future__ import annotations

import glob
import os
import re
import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SYNC_NAME = "benchmark_clock_sync"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_RE = re.compile(r"^(?:jit_)?(.+?)(?:\(\d+\))?(?:\.\d+)?$")
AFTER_S = 0.005     # a gap this close behind a dispatch record is `after:<kind>`
MIN_GAP_S = 20e-6   # shorter gaps are the device's own turn-around

STEP_S = 1e-3       # a module event this long is a serving step, not a helper
JOIN_TOL_S = 2e-3   # a joined module event may stick out of its record by this much
# steps(): how far a record's wall time may pass its event's time (the host's
# submit and fetch, ~2 ms as read on the v5e): HOST_S, or HOST_SHARE of the record.
HOST_S = 4e-3
HOST_SHARE = 0.05
SYNC_MARKS = 8      # clock-sync annotations per trace
_OP_RE = re.compile(r"^(%?[\w.\-]+) = (?:\()?([a-z0-9]+\[[0-9,]*\])?")

Event = Tuple[str, float, float]  # name, start_s, end_s


def op_name(event_name: str) -> str:
    """`%fusion.7 = bf16[16,4096]{...} fusion(...)` -> `%fusion.7 bf16[16,4096]`:
    an operation's name and result shape, without layouts and operands."""
    m = _OP_RE.match(event_name)
    if not m:
        return event_name[:80]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:80]


def program_name(event_name: str) -> str:
    return _MODULE_RE.match(event_name).group(1)


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def self_seconds(events: Sequence[Event]) -> Dict[str, float]:
    """Total self time by event name: duration less the children nested in it."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1]))):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [(event name, start_s, end_s)]}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                s = ev.start_ns * 1e-9
                evs.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def find_sync(planes: Dict[str, Dict[str, List[Event]]], mark: int = 0) -> Optional[float]:
    """Trace-clock start of the tracer's sync annotation number `mark`, on a
    host plane.  Mark 0 is also found under the bare name."""
    names = {f"{SYNC_NAME}_{mark}"} | ({SYNC_NAME} if mark == 0 else set())
    for pname, lines in planes.items():
        if is_device(pname):
            continue
        for evs in lines.values():
            for name, s, _ in evs:
                if name in names:
                    return s
    return None


def joins(seconds: float, start_host: float, program: str, rec: dict) -> bool:
    """Whether a module event may be joined to a dispatch record: the record
    names the same program (where it names one) and the event lies inside it.
    A dispatch is submit through fetch on one thread, so its program runs
    inside its record."""
    if rec.get("program") not in (None, program):
        return False
    return (start_host >= rec["start"] - JOIN_TOL_S
            and start_host + seconds <= rec["end"] + JOIN_TOL_S)


def steps(reduced: dict, programs: Sequence[str]) -> List[dict]:
    """The joined module events of `programs` that fill their record: its
    wall time is the event's time plus the host's submit and fetch.  The
    profiler cuts the events that run when it starts and stops (they begin
    with the trace's first operation, or end with its last, at any length),
    and a cut event's seconds would be divided by iterations and tokens it
    ran outside the trace; one joined to a longer neighbour likewise."""
    out = []
    for m in reduced["modules"]:
        d = m["dispatch"]
        if m["program"] not in programs or d is None:
            continue
        wall = d["end"] - d["start"]
        if wall - m["seconds"] <= max(HOST_S, HOST_SHARE * wall):
            out.append(m)
    return out


def reduce(planes: Dict[str, Dict[str, List[Event]]], dispatches: Sequence[dict],
           sync_host_s: Optional[float], sync_mark: int = 0) -> dict:
    """See the module docstring.  `dispatches` carry `start`/`end` on the host
    clock, `kind`, `k`, `occupancy`, `prefill_tokens`, and `program` where the
    program gave it.  `sync_host_s` is the host clock at the start of sync
    annotation number `sync_mark`."""
    dev = {n: l for n, l in planes.items() if is_device(n) and (OPS_LINE in l or MODULES_LINE in l)}
    if not dev:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0, "modules": [],
                "aligned_share": None, "breakdown": {"device_ops": [], "idle_gaps": []},
                "planes": {n: sorted(l) for n, l in planes.items()}}
    busy, lo, hi = [], float("inf"), float("-inf")
    for lines in dev.values():
        ops = lines.get(OPS_LINE) or lines[MODULES_LINE]
        busy.append(union_seconds((s, e) for _, s, e in ops))
        lo = min(lo, min(s for _, s, _ in ops))
        hi = max(hi, max(e for _, _, e in ops))
    first = dev[sorted(dev)[0]]
    ops0 = first.get(OPS_LINE) or first[MODULES_LINE]
    sync_trace_s = find_sync(planes, sync_mark)
    offset = None  # host clock = trace clock + offset
    if sync_trace_s is not None and sync_host_s is not None:
        offset = sync_host_s - sync_trace_s
    recs = sorted(dispatches, key=lambda d: d["start"])

    def covering(t_host: float) -> Optional[dict]:
        for d in recs:  # a few hundred records: a scan is fine
            if d["start"] <= t_host <= d["end"]:
                return d
        return None

    modules, aligned, steps = [], 0, 0
    for name, s, e in first.get(MODULES_LINE, []):
        rec = covering((s + e) / 2 + offset) if offset is not None else None
        if rec is not None and not joins(e - s, s + offset, program_name(name), rec):
            rec = None
        if e - s >= STEP_S:
            steps += 1
            aligned += rec is not None
        modules.append({"program": program_name(name), "start_s": s, "seconds": e - s,
                        "dispatch": rec})
    by_self = sorted(
        self_seconds([(op_name(n), s, e) for n, s, e in ops0]).items(), key=lambda kv: -kv[1]
    )
    idle: Dict[str, float] = {}
    for s, e in gaps((s, e) for _, s, e in ops0):
        if e - s < MIN_GAP_S:
            name = "under 20us"
        elif offset is None:
            name = "clocks not joined"
        else:
            mid = (s + e) / 2 + offset
            rec = covering(mid)
            if rec is not None:
                name = f"in:{rec['kind']}"
            else:
                before = [d for d in recs if d["end"] <= mid]
                name = (f"after:{before[-1]['kind']}"
                        if before and mid - before[-1]["end"] <= AFTER_S else "no dispatch")
        idle[name] = idle.get(name, 0.0) + (e - s)
    programs: Dict[str, List[float]] = {}
    for m in modules:
        p = programs.setdefault(m["program"], [0, 0.0])
        p[0] += 1
        p[1] += m["seconds"]
    return {
        "busy_s": sum(busy) / len(busy), "window_s": hi - lo, "devices": len(dev),
        "busy_s_by_device": busy, "modules": modules, "programs": programs,
        "lines": {n: {ln: len(evs) for ln, evs in l.items()} for n, l in planes.items()},
        "aligned_share": aligned / steps if steps else None,
        "breakdown": {
            "device_ops": [[n, v] for n, v in by_self[:10]],
            "idle_gaps": [[n, v] for n, v in sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
        },
    }


def reduce_dir(trace_dir: str, dispatches: Sequence[dict],
               sync_host_s: Optional[float], sync_mark: int = 0) -> dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise SystemExit(f"the profiler left no trace under {trace_dir}")
    return reduce(read_planes(paths[-1]), dispatches, sync_host_s, sync_mark)


class Tracer:
    """Profiles `seconds` of the window from a side thread, starting
    `start_after_s` after `start(t0)`."""

    def __init__(self, log_dir: str, start_after_s: float, seconds: float):
        self.log_dir, self.start_after_s, self.seconds = log_dir, start_after_s, seconds
        self.sync: Optional[float] = None   # host clock at annotation `sync_mark`
        self.sync_mark = 0
        self.sync_slack_s: Optional[float] = None  # half the distance of its two readings
        self._thread: Optional[threading.Thread] = None

    def _run(self, t0: float) -> None:
        import jax

        delay = t0 + self.start_after_s - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        jax.profiler.start_trace(self.log_dir)
        try:
            for i in range(SYNC_MARKS):
                a = time.monotonic()
                with jax.profiler.TraceAnnotation(f"{SYNC_NAME}_{i}"):
                    b = time.monotonic()
                    time.sleep(0.001)
                if self.sync_slack_s is None or (b - a) / 2 < self.sync_slack_s:
                    self.sync, self.sync_mark, self.sync_slack_s = (a + b) / 2, i, (b - a) / 2
                time.sleep(0.002)
            time.sleep(self.seconds)
        finally:
            jax.profiler.stop_trace()

    def start(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0,), daemon=True)
        self._thread.start()

    def join(self) -> None:
        self._thread.join()
