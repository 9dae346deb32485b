"""Device time by lane and by leaf scope, from a profiler trace.

Since PR 53 every `jax.named_scope` of the program is one of a closed set
(`obs.DEVICE_SCOPES`).  Three of them are LANES and say which half of a
dispatch an operation served: `lane.chunk` (the in-flight admission of
`_fused_chunk`), `lane.mixed` (the mixed branch's shared pass, opened inside
`lane.chunk`) and `lane.decode` (the decode scan of `_fused_chunk` and
`_paged_decode_chunk`).  The insert programs open no lane: their module name
is their lane.  The others are LEAF scopes and say what the operation did.

An operation's path is the `tf_op` stat of its event metadata
(`jit(_fused_chunk)/lane.decode/while/body/moe.experts/...`; read with the
profiler's protos, as `scopes.py` does).  Its lane is the LAST `lane.*`
element of the path, its leaf scope the INNERMOST element that is one
(`admit.sample/.../head/dot_general` is `head`), its time its SELF time
(`trace.self_seconds`' rule), so lanes, scopes and the lane x scope table
each add up to the device's busy time.  An execution's operations are those
of the `XLA Ops` line that start inside its `XLA Modules` event.

Against a program without lanes (the parent of PR 53), without the protos
or without a trace, `table()` is None and every reader returns None.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from . import hostspans, scopes, trace

LANES = ("lane.chunk", "lane.mixed", "lane.decode")
# The benchmark's own copy of the program's leaf scopes
# (`tests/test_lanes.py` holds both to `obs.DEVICE_SCOPES`).
LEAVES = frozenset({
    "cache.gather", "cache.land", "cache.write", "state.move", "sample", "emit",
    "admit.sample", "embed", "layers", "head", "dense.attention", "dense.ffn",
    "mla.project", "mla.attend_decode", "mla.attend_prefill",
    "hc.coeff", "hc.pre", "hc.post", "moe.route", "moe.experts", "moe.shared",
    "attn.window", "attn.full", "attn.cross", "attn.proj", "attn.index",
    "attn.select", "attn.sparse", "ssm.mix", "ssm.scan", "ssm.step", "gmu.mix",
})
INSERTS = ("jit(_paged_insert)", "jit(_paged_suffix_insert)")
FUSED = "_fused_chunk"
NO_LANE, INSERT_LANE, UNSCOPED = "none", "insert", ""
CLASSES = ((256, "le256"), (1024, "le1024"), (float("inf"), "gt1024"))
JOIN_S = 1e-4   # a traced execution and `trace.reduce`'s record of it start this close


def lane_and_leaf(tf_op: str) -> Tuple[str, str]:
    """(`chunk` / `mixed` / `decode` / `insert` / `none`, the innermost leaf
    scope or "") of one operation's path."""
    parts = [p.rstrip(":") for p in tf_op.split("/")]
    lane = next((p[len("lane."):] for p in reversed(parts) if p in LANES), None)
    if lane is None:
        lane = INSERT_LANE if parts[0] in INSERTS else NO_LANE
    leaf = next((p for p in reversed(parts) if p in LEAVES), UNSCOPED)
    return lane, leaf


def table(path: Optional[str]) -> Optional[dict]:
    """The first device's operations by lane and leaf scope:

    busy_s; by_lane, by_leaf and by_lane_leaf (`lane|leaf`): self seconds;
    unscoped: `trace.op_name` -> self seconds of the operations with no leaf
    scope; executions: one entry for each `XLA Modules` event, (program, start
    on the trace clock, {lane: self seconds}).  None where nothing carries a
    lane."""
    space = scopes._space(path) if path else None
    if space is None:
        return None
    planes = sorted((p for p in space.planes if trace.is_device(p.name)), key=lambda p: p.name)
    for plane in planes:
        ops = next((ln for ln in plane.lines if ln.name == trace.OPS_LINE), None)
        if ops is None:
            continue
        return _reduce(plane, ops)
    return None


def _reduce(plane, ops) -> Optional[dict]:
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    meta: Dict[int, Tuple[str, str, str]] = {}
    for k, md in plane.event_metadata.items():
        tf_op = next((st.str_value for st in md.stats
                      if stat_names.get(st.metadata_id) == "tf_op"), "")
        meta[k] = lane_and_leaf(tf_op) + (trace.op_name(md.name),)
    if not any(lane in ("chunk", "mixed", "decode") for lane, _, _ in meta.values()):
        return None
    # Seconds from the operations' own line start: a sum of small differences
    # of large numbers otherwise.
    events = [(str(i), ev.offset_ps * 1e-12, (ev.offset_ps + ev.duration_ps) * 1e-12)
              for i, ev in enumerate(ops.events)]
    own = trace.self_seconds(events)
    mods = next((ln for ln in plane.lines if ln.name == trace.MODULES_LINE), None)
    executions: List[list] = []
    if mods is not None:
        shift = (mods.timestamp_ns - ops.timestamp_ns) * 1e-9
        for ev in sorted(mods.events, key=lambda e: e.offset_ps):
            executions.append([
                trace.program_name(plane.event_metadata[ev.metadata_id].name),
                mods.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12,
                shift + ev.offset_ps * 1e-12, shift + (ev.offset_ps + ev.duration_ps) * 1e-12, {},
            ])
    starts = [e[2] for e in executions]
    out = {"busy_s": 0.0, "by_lane": {}, "by_leaf": {}, "by_lane_leaf": {}, "unscoped": {}}

    def add(d: dict, key: str, v: float) -> None:
        d[key] = d.get(key, 0.0) + v

    for i, ev in enumerate(ops.events):
        v = own.get(str(i), 0.0)
        lane, leaf, name = meta.get(ev.metadata_id, (NO_LANE, UNSCOPED, "?"))
        out["busy_s"] += v
        add(out["by_lane"], lane, v)
        add(out["by_leaf"], leaf, v)
        add(out["by_lane_leaf"], f"{lane}|{leaf}", v)
        if leaf == UNSCOPED:
            add(out["unscoped"], name, v)
        j = bisect.bisect_right(starts, events[i][1]) - 1
        if j >= 0 and events[i][1] < executions[j][3]:
            add(executions[j][4], lane, v)
    out["executions"] = [(e[0], e[1], e[4]) for e in executions]
    return out


_CACHE: Dict[str, Optional[dict]] = {}


def of_run(ctx) -> Optional[dict]:
    """`table()` of the run's newest trace, read once for the four readers."""
    if ctx.trace is None:
        return None
    from . import run

    path = hostspans.newest_xplane(str(run.OUT))
    if path not in _CACHE:
        _CACHE.clear()
        _CACHE[path] = table(path)
    return _CACHE[path]


def top(d: Dict[str, float], n: Optional[int] = None) -> Dict[str, float]:
    return dict(sorted(d.items(), key=lambda kv: -kv[1])[:n])


def fused_executions(ctx) -> Optional[dict]:
    """The `_fused_chunk` executions `trace.steps` admits, each with its
    record and its seconds by lane: {"held": executions of the program in
    the trace, "admitted": [(record, {lane: s})]}."""
    tab = of_run(ctx)
    if tab is None:
        return None
    mine = [(s, by) for prog, s, by in tab["executions"] if prog == FUSED]
    starts = [s for s, _ in mine]
    admitted = []
    for m in trace.steps(ctx.trace, (FUSED,)):
        j = bisect.bisect_left(starts, m["start_s"] - JOIN_S)
        if j < len(mine) and abs(mine[j][0] - m["start_s"]) <= JOIN_S:
            admitted.append((m["dispatch"], mine[j][1]))
    return {"held": len(mine), "admitted": admitted}


def chunk_class(tokens: int) -> str:
    return next(name for limit, name in CLASSES if tokens <= limit)


def decode_iters(rec: dict) -> int:
    """Iterations a fused record ran under `lane.decode`: `k`, or `k - 1`
    behind a mixed pass, which ran the first (the record then carries
    `merged_rows`, be it 0)."""
    return rec["k"] - (1 if "merged_rows" in rec else 0)
