"""Device time inside the program's `jax.named_scope`s, from a profiler trace.

An operation's scope is in the `tf_op` stat of its event METADATA in the
`.xplane.pb` (`jit(_fused_chunk)/.../mla.attend_decode/dot_general:`), which
`jax.profiler.ProfileData` does not show, so this file reads the trace with the
profiler's own protos.  Time is SELF time (`trace.self_seconds`' rule: an
event's duration less the events nested in it), so a `while` around a layer
scan counts nothing of its body, and the shares of disjoint scopes add up to at
most 100 % of the device's busy time.  A program without such scopes, a trace
without the stat, or an installation without the protos reads None.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

from . import hostspans, trace


def scope_of(tf_op: str, prefixes: Sequence[str]) -> Optional[str]:
    """The first path element of `tf_op` that starts with one of `prefixes`."""
    for part in tf_op.split("/"):
        if part.startswith(tuple(prefixes)):
            return part.rstrip(":")
    return None


def self_seconds_by_scope(path: str, prefixes: Sequence[str]) -> Optional[Dict[str, float]]:
    """{scope: self seconds} over the first device's `XLA Ops`, every other
    operation under "" (so the values sum to the device's busy time)."""
    space = _space(path)
    if space is None:
        return None
    planes = sorted((p for p in space.planes if trace.is_device(p.name)), key=lambda p: p.name)
    for plane in planes:
        line = next((ln for ln in plane.lines if ln.name == trace.OPS_LINE), None)
        if line is None:
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        scope: Dict[int, str] = {}
        for k, md in plane.event_metadata.items():
            tf_op = next((st.str_value for st in md.stats
                          if stat_names.get(st.metadata_id) == "tf_op"), "")
            scope[k] = scope_of(tf_op, prefixes) or ""
        base = line.timestamp_ns * 1e-9
        events = []
        for i, ev in enumerate(line.events):
            s = base + ev.offset_ps * 1e-12
            # a name per event: self_seconds nests by time, sums by name
            events.append((f"{scope.get(ev.metadata_id, '')}\x00{i}", s, s + ev.duration_ps * 1e-12))
        out: Dict[str, float] = {}
        for name, v in trace.self_seconds(events).items():
            key = name.split("\x00")[0]
            out[key] = out.get(key, 0.0) + v
        return out
    return None


def _space(path: str):
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def share_pct(ctx, prefix: str):
    """100 x self seconds under scopes starting with `prefix` / busy seconds,
    from the newest trace of the run; the note lists each scope."""
    if ctx.trace is None:
        return None
    from . import run

    path = hostspans.newest_xplane(str(run.OUT))
    if path is None:
        return None
    by_scope = self_seconds_by_scope(path, ("mla.", "moe.", "dense."))
    if not by_scope:
        return None
    total = sum(by_scope.values())
    mine = {k: v for k, v in by_scope.items() if k.startswith(prefix)}
    if total <= 0 or not mine:
        return None
    return {"value": 100.0 * sum(mine.values()) / total,
            "note": {"busy_self_s": total,
                     "seconds_by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))}}
