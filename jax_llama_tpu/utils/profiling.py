"""Tracing / profiling / throughput observability.

The reference has none of this (SURVEY.md §5: "Tracing / profiling: Absent
— only leftover debug prints", ``/root/reference/jax_llama/model.py:636``);
this module provides the TPU-native equivalents the survey prescribes:
``jax.profiler`` xplane traces viewable in TensorBoard/XProf, wall-clock
timers that block on device work, and tokens/sec/chip decode counters (the
BASELINE.json metric).
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import os
import re
import time
from typing import Dict, Iterable, Iterator, List, Sequence, Tuple

import jax


@contextlib.contextmanager
def trace(log_dir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace (xplane format) into ``log_dir``.

    View with TensorBoard's profile plugin or xprof.  Wrap the steady-state
    region only — include one warm-up call outside the context so compile
    time does not dominate the trace.
    """
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Timer:
    """Wall-clock timer that waits for in-flight device work on both edges,
    so the measured window covers exactly the enclosed computation."""

    elapsed_s: float = 0.0

    def __enter__(self) -> "Timer":
        _block_on_pending()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        _block_on_pending()
        self.elapsed_s = time.perf_counter() - self._t0


def _block_on_pending() -> None:
    # effects_barrier waits for all dispatched-but-unfinished computations.
    jax.effects_barrier()


# Event-name spellings that carry a jitted-program identity in an
# xplane capture: the host plane's python line traces dispatch frames
# as ``PjitFunction(<name>)``, and device planes' "XLA Modules" line
# names executables ``jit_<name>`` (with a ``(<run id>)`` and sometimes
# a ``.N`` specialization suffix).
_PJIT_RE = re.compile(r"^PjitFunction\((.+)\)$")
_JIT_MODULE_RE = re.compile(r"^jit_(.+?)(?:\(\d+\))?(?:\.\d+)?$")

# The serving loop's own annotations (obs.Observability.loop_phase /
# dispatch_begin): one host event per phase and per dispatch, on the
# loop thread, on the clock the device events are on.
LOOP_PREFIX = "llm.loop."
DISPATCH_EVENT = "llm.dispatch"
# ... and the child spans of those (obs.Observability.loop_span), which
# nest inside a phase event, a dispatch event or one another.
SPAN_PREFIX = "llm.span."

Interval = Tuple[float, float]


def normalize_program_name(event_name: str):
    """The serving-program name behind an xplane event name, or None
    for events that are not jitted-program roots (individual HLO ops,
    host syscalls, ...)."""
    m = _PJIT_RE.match(event_name)
    if m:
        return m.group(1)
    m = _JIT_MODULE_RE.match(event_name)
    if m:
        return m.group(1)
    return None


def busy_and_gaps(intervals: Iterable[Interval]) -> Tuple[float, List[Interval]]:
    """The union of ``intervals`` (they nest: a ``while`` around its
    body) and the gaps inside it, first start to last end."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
                gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def split_by_overlap(
    gaps: Sequence[Interval], named: Sequence[Tuple[str, float, float]],
    rest: str = "unnamed",
) -> Dict[str, float]:
    """Each gap's length by the ``named`` intervals ``(label, start,
    end)`` it overlaps (they do not overlap one another: one thread's
    phases); what none covers goes to ``rest``."""
    out: Dict[str, float] = collections.defaultdict(float)
    named = sorted(named, key=lambda n: n[1])
    starts = [n[1] for n in named]
    for gs, ge in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, gs) - 1)
        while i < len(named) and named[i][1] < ge:
            label, s, e = named[i]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[label] += ov
                covered += ov
            i += 1
        if ge - gs > covered:
            out[rest] += ge - gs - covered
    return dict(out)


def innermost(
    events: Sequence[Tuple[str, float, float]],
) -> List[Tuple[str, float, float]]:
    """One thread's nested events ``(label, start, end)`` cut into pieces
    that do not overlap, each labelled by the innermost event over it."""
    out: List[Tuple[str, float, float]] = []
    stack: List[Tuple[str, float]] = []  # (label, end), outermost first
    cur = 0.0

    def close(upto: float) -> float:
        t = cur
        while stack and stack[-1][1] <= upto:
            label, end = stack.pop()
            if end > t:
                out.append((label, t, end))
                t = end
        return t

    for label, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        cur = close(s)
        if stack:
            if s > cur:
                out.append((stack[-1][0], cur, s))
            e = min(e, stack[-1][1])  # a child never outlives its parent
        cur = s
        stack.append((label, e))
    close(float("inf"))
    return out


def lane_and_scope(tf_op: str) -> Tuple[str, str]:
    """One device operation's (lane, leaf scope) from its scope path — the
    ``tf_op`` stat of its event metadata,
    ``jit(_fused_chunk)/lane.decode/while/body/moe.experts/...`` — by the
    closed set ``obs.DEVICE_SCOPES``: the LAST lane of the path
    (``lane.mixed`` opens inside ``lane.chunk``) without its prefix, else
    the program's name (an insert program's module name is its lane),
    else ``none``; and the INNERMOST leaf scope (``admit.sample/.../head``
    is ``head``), else ``unscoped``."""
    from ..obs import DEVICE_LANES, DEVICE_SCOPES

    parts = [p.rstrip(":") for p in tf_op.split("/")]
    lane = next(
        (p[len("lane."):] for p in reversed(parts) if p in DEVICE_LANES),
        None,
    )
    if lane is None and parts[0].startswith("jit(") and parts[0][-1] == ")":
        lane = parts[0][len("jit("):-1]
    scope = next(
        (p for p in reversed(parts)
         if p in DEVICE_SCOPES and p not in DEVICE_LANES),
        "unscoped",
    )
    return lane or "none", scope


def busy_by_lane_and_scope(path: str):
    """({lane: ms}, {leaf scope: ms}) of the first device's "XLA Ops" in
    the capture ``path``, by SELF time (an operation's duration less the
    operations nested in it: a ``while`` counts nothing of its body), so
    each adds up to the device's busy time.  The scope path is not in
    what ``jax.profiler.ProfileData`` shows: this reads the file with the
    profiler's protos, and returns None where they do not import."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in sorted(space.planes, key=lambda p: p.name):
        if not any(t in plane.name for t in ("TPU", "GPU")):
            continue
        line = next((ln for ln in plane.lines if ln.name == "XLA Ops"), None)
        if line is None:
            continue
        stat = {k: v.name for k, v in plane.stat_metadata.items()}
        label = {}
        for k, md in plane.event_metadata.items():
            tf_op = next(
                (st.str_value for st in md.stats
                 if stat.get(st.metadata_id) == "tf_op"), "",
            )
            label[k] = "\x00".join(lane_and_scope(tf_op))
        lanes: Dict[str, float] = collections.defaultdict(float)
        scopes: Dict[str, float] = collections.defaultdict(float)
        # offsets in picoseconds: exact, whatever the line's own start
        for name, s, e in innermost([
            (label.get(ev.metadata_id, "none\x00unscoped"), ev.offset_ps,
             ev.offset_ps + ev.duration_ps) for ev in line.events
        ]):
            lane, scope = name.split("\x00")
            lanes[lane] += (e - s) / 1e9
            scopes[scope] += (e - s) / 1e9
        return dict(lanes), dict(scopes)
    return None


def summarize_xplane(log_dir: str) -> Dict[str, object]:
    """Aggregate the newest xplane capture under ``log_dir``, read with
    ``jax.profiler.ProfileData`` alone.

    Per jitted program: device planes (name contains TPU/GPU)
    attribute their "XLA Modules" line — executable-granular device
    time; the host plane's ``PjitFunction`` frames attribute host-side
    dispatch time (on a CPU-only capture that is the only signal, and
    it still answers "which program").  For the first device:
    ``busy_ms`` / ``idle_ms`` (the union of its "XLA Ops" intervals and
    the gaps in it) and ``idle_by_phase_ms`` — each idle gap split by
    overlap with the serving loop's own annotations in the same file
    (``llm.loop.<phase>`` by phase, ``llm.dispatch`` as ``in
    dispatch``, the rest ``unnamed``), so the attribution of device
    idle time to host work comes from the running server with no clock
    join — and ``idle_by_span_ms``, the same gaps with each part given
    to the innermost ``llm.span.<name>`` event over it (by span name),
    else to its phase, else ``in dispatch``, else ``unnamed``.  Where the
    profiler's protos import, also ``busy_by_lane_ms`` and
    ``busy_by_scope_ms``: the first device's busy time by the lane and by
    the leaf scope of its operations (:func:`busy_by_lane_and_scope`);
    both are left out where they do not.  Raises FileNotFoundError when
    ``log_dir`` holds no capture — the /debug/profile/summary endpoint
    maps it to a clean 404.
    """
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise FileNotFoundError(
            f"no .xplane.pb capture under {log_dir!r}"
        )
    path = paths[-1]
    device_ms: Dict[str, float] = collections.defaultdict(float)
    host_ms: Dict[str, float] = collections.defaultdict(float)
    loop: List[Tuple[str, float, float]] = []
    spans: List[Tuple[str, float, float]] = []
    ops: List[Interval] = []  # of the first device plane that has any
    for plane in ProfileData.from_file(path).planes:
        is_device = any(t in plane.name for t in ("TPU", "GPU"))
        for line in plane.lines:
            if is_device and line.name == "XLA Ops" and not ops:
                ops = [
                    (e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                ]
            if is_device and line.name != "XLA Modules":
                continue  # per-op lines double-count their module
            sink = device_ms if is_device else host_ms
            for e in line.events:
                name = e.name
                label = None
                if name == DISPATCH_EVENT:
                    label = "in dispatch"
                elif name.startswith(LOOP_PREFIX):
                    label = name[len(LOOP_PREFIX):]
                if label is not None and not is_device:
                    loop.append(
                        (label, e.start_ns, e.start_ns + e.duration_ns)
                    )
                    continue
                if name.startswith(SPAN_PREFIX) and not is_device:
                    spans.append((
                        name[len(SPAN_PREFIX):], e.start_ns,
                        e.start_ns + e.duration_ns,
                    ))
                    continue
                prog = normalize_program_name(name)
                if prog is not None:
                    sink[prog] += e.duration_ns / 1e6
    busy_ns, gaps = busy_and_gaps(ops)
    programs = sorted(set(device_ms) | set(host_ms))
    by = busy_by_lane_and_scope(path) if ops else None
    named = {} if by is None else {
        key: {k: round(v, 3) for k, v in sorted(d.items())}
        for key, d in zip(("busy_by_lane_ms", "busy_by_scope_ms"), by)
    }
    return {
        **named,
        "xplane": path,
        "programs": {
            p: {
                "device_ms": round(device_ms.get(p, 0.0), 3),
                "host_ms": round(host_ms.get(p, 0.0), 3),
            }
            for p in programs
        },
        "total_device_ms": round(sum(device_ms.values()), 3),
        "total_host_ms": round(sum(host_ms.values()), 3),
        "busy_ms": round(busy_ns / 1e6, 3),
        "idle_ms": round(sum(e - s for s, e in gaps) / 1e6, 3),
        "idle_by_phase_ms": {
            k: round(v / 1e6, 3)
            for k, v in sorted(split_by_overlap(gaps, loop).items())
        },
        "idle_by_span_ms": {
            k: round(v / 1e6, 3)
            for k, v in sorted(
                split_by_overlap(gaps, innermost(loop + spans)).items()
            )
        },
    }


@dataclasses.dataclass
class DecodeStats:
    """Throughput accounting for one generation call.

    tokens/sec figures are per chip: divide by ``n_devices`` so multi-chip
    meshes report the BASELINE.json metric (tokens/sec/chip) directly.
    """

    batch: int
    prompt_len: int
    new_tokens: int
    prefill_s: float
    decode_s: float
    n_devices: int = 1

    @property
    def decode_tokens_per_s(self) -> float:
        return self.batch * self.new_tokens / max(self.decode_s, 1e-9)

    @property
    def decode_tokens_per_s_per_chip(self) -> float:
        return self.decode_tokens_per_s / self.n_devices

    @property
    def prefill_tokens_per_s(self) -> float:
        return self.batch * self.prompt_len / max(self.prefill_s, 1e-9)

    @property
    def per_token_latency_ms(self) -> float:
        return 1e3 * self.decode_s / max(self.new_tokens, 1)

    def summary(self) -> str:
        prefill = (
            f"prefill {self.prefill_tokens_per_s:,.0f} tok/s | "
            if self.prefill_s > 0
            else ""
        )
        return (
            f"{prefill}decode "
            f"{self.decode_tokens_per_s_per_chip:,.1f} tok/s/chip "
            f"({self.per_token_latency_ms:.2f} ms/tok, batch {self.batch})"
        )
