"""The readers of the program's loop-phase spans (PR 24), on synthetic dispatch
records, timelines and planes; then one rehearsal of a cell end to end.

    python -m pytest benchmark/tests/test_hostspans.py -q -p no:cacheprovider
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import hostspans, stats, trace
from benchmark.run import Context, load_reader

ROOT = Path(__file__).resolve().parents[2]


def rec(seq, kind, start, wall_ms, host_ms=None, cpu_ms=None, **more):
    """A dispatch record as `run.py` hands it to readers: the program's
    fields plus `start` / `end` on the host clock."""
    d = {"seq": seq, "kind": kind, "k": 8, "wall_ms": wall_ms, "start": start,
         "end": start + wall_ms / 1000.0, **more}
    if host_ms is not None:
        d.update(host_ms=host_ms, gap_ms=sum(host_ms.values()))
    if cpu_ms is not None:
        d["gap_cpu_ms"] = cpu_ms
    return d


def window():
    """t0 = 100, 10 s.  Seq 0 has no gap (an Observability's first record), seq
    3 waited 50 ms on an empty inbox, seq 4 is an insert, seq 5 falls behind
    the window's end."""
    return [
        rec(0, "decode", 100.0, 150.0),
        rec(1, "decode", 100.170, 150.0, {"emit": 2.0, "deliver": 8.0, "intake": 3.0, "admit": 4.0, "prep": 3.0}, 12.0),
        rec(2, "fused", 100.350, 150.0, {"emit": 2.0, "deliver": 10.0, "control": 1.0, "intake": 3.0, "admit": 10.0, "prep": 4.0}, 15.0),
        rec(3, "decode:stock-paged", 100.580, 150.0,
            {"emit": 2.0, "deliver": 8.0, "intake": 4.0, "idle": 50.0, "admit": 3.0, "barrier": 1.0, "prep": 2.0}, 10.0),
        rec(4, "insert", 100.740, 40.0, {"admit": 10.0}, 9.0),
        rec(5, "decode", 111.0, 150.0, {"emit": 500.0}, 1.0),
    ]


def ctx_of(dispatches, **kw):
    return Context(dispatches=dispatches, t0=100.0, seconds=10.0, trace=None, timelines={}, stats=stats, **kw)


def test_gap_readers_leave_idle_out_and_take_serving_steps_only():
    ctx = ctx_of(window())
    assert [d["seq"] for d in hostspans.in_window(ctx)] == [1, 2, 3, 4]
    assert [d["seq"] for d in hostspans.steps(ctx)] == [1, 2, 3]
    got = load_reader("loop.gap_ms_per_dispatch")(ctx)
    assert got["value"] == pytest.approx((20.0 + 30.0 + 20.0) / 3)
    by_phase = got["note"]["ms_by_phase"]
    assert "idle" not in by_phase
    assert by_phase["deliver"] == pytest.approx(26.0 / 3) and by_phase["barrier"] == pytest.approx(1.0 / 3)
    assert sum(by_phase.values()) == pytest.approx(got["value"])
    assert got["note"]["inside_trace_ms"] is None      # no trace: nothing inside
    assert got["note"]["outside_trace_ms"] == pytest.approx(got["value"])
    assert load_reader("sched.loop_ms_per_dispatch")(ctx) == pytest.approx((9.0 + 16.0 + 8.0) / 3)
    assert load_reader("server.loop_ms_per_dispatch")(ctx) == pytest.approx((11.0 + 14.0 + 12.0) / 3)


def test_inside_and_outside_the_profilers_sub_window():
    d = window()
    ctx = ctx_of(d)
    ctx.trace = {"modules": [{"program": "_fused_chunk", "start_s": 1.0, "seconds": 0.1, "dispatch": d[2]},
                             {"program": "x", "start_s": 2.0, "seconds": 0.1, "dispatch": None}]}
    note = load_reader("loop.gap_ms_per_dispatch")(ctx)["note"]
    assert note["inside_trace_ms"] == pytest.approx(30.0)
    assert note["outside_trace_ms"] == pytest.approx(20.0)


def test_offcpu_share_and_gap_share():
    ctx = ctx_of(window())
    off = load_reader("loop.offcpu_pct")(ctx)
    assert off["value"] == pytest.approx(100.0 * (70.0 - 37.0) / 70.0)
    share = load_reader("loop.gap_share_pct")(ctx)   # every kind of record, the insert too
    assert share["value"] == pytest.approx(100.0 * 80.0 / (80.0 + 490.0))
    assert share["note"]["records"] == 4


def test_a_program_without_the_spans_reads_nothing():
    """The parent of PR 24: records without gap fields, timelines without
    `received`.  Every reader returns None and none raises."""
    old = [rec(i, "decode", 100.0 + 0.2 * i, 150.0) for i in range(5)]
    ctx = ctx_of(old)
    ctx.timelines = {"a": {"spans": [{"state": "queued", "duration_ms": 3.0}]}}
    for name in ("loop.gap_ms_per_dispatch", "sched.loop_ms_per_dispatch", "server.loop_ms_per_dispatch",
                 "loop.offcpu_pct", "loop.gap_share_pct", "ttft.received_p50_ms", "device.idle_named_pct"):
        assert load_reader(name)(ctx) is None, name
    assert load_reader("ttft.queued_p50_ms")(ctx) == 3.0


def test_span_medians_sum_a_state_entered_twice():
    span = lambda state, ms: {"state": state, "duration_ms": ms}  # noqa: E731
    ctx = ctx_of([])
    ctx.timelines = {
        "a": {"spans": [span("received", 80.0), span("queued", 1.0), span("prefilling", 150.0), span("decoding", None)]},
        "b": {"spans": [span("received", 40.0), span("queued", 100.0), span("restoring", 30.0), span("queued", 20.0),
                        span("prefilling", 170.0), span("decoding", 900.0)]},
        "c": {"spans": [span("received", 60.0), span("queued", 5.0), span("prefilling", 160.0)]},
        "refused": {"spans": [span("queued", 0.0)]},
    }
    assert load_reader("ttft.received_p50_ms")(ctx) == 60.0
    assert load_reader("ttft.queued_p50_ms")(ctx) == pytest.approx(3.0)   # 0, 1, 5, 120
    assert load_reader("ttft.prefilling_p50_ms")(ctx) == 160.0


# -- the same spans on the profiler's clock ----------------------------------

def planes(annotated=True):
    """Device busy 1.0-1.4, 1.5-1.9, 2.0-2.4; idle 1.4-1.5 and 1.9-2.0 (and a
    5 us turn-around inside the first chunk, under the 20 us floor)."""
    ops = [("fusion", 1.0, 1.2), ("fusion", 1.200005, 1.4), ("fusion", 1.5, 1.9), ("fusion", 2.0, 2.4)]
    mods = [("jit__paged_decode_chunk(7)", 1.0, 1.4), ("jit__fused_chunk(8)", 1.5, 1.9),
            ("jit__paged_decode_chunk(7)", 2.0, 2.4)]
    host = [(trace.SYNC_NAME, 0.5, 0.501), ("PjitFunction(_fused_chunk)", 1.46, 1.47)]
    if annotated:
        host += [
            ("llm.dispatch", 0.99, 1.41), ("llm.loop.emit", 1.41, 1.43), ("llm.loop.deliver", 1.43, 1.45),
            ("llm.loop.admit", 1.45, 1.46), ("llm.dispatch", 1.46, 1.91), ("llm.loop.emit", 1.91, 1.95),
            ("llm.loop.deliver", 1.95, 1.98), ("llm.dispatch", 1.98, 2.41),
        ]
    return {"/device:TPU:0": {trace.OPS_LINE: ops, trace.MODULES_LINE: mods},
            "/host:CPU": {"python3": host, "worker": [("llm.other", 0.0, 9.0)]}}


def test_idle_gaps_are_named_by_overlap():
    idle = hostspans.named_idle(planes())
    assert idle == pytest.approx({
        hostspans.IN_DISPATCH: 0.01 + 0.04 + 0.01 + 0.02,   # tails and heads of the dispatch events
        "emit": 0.02 + 0.04, "deliver": 0.02 + 0.03, "admit": 0.01,
    })
    assert sum(idle.values()) == pytest.approx(0.2)          # the 5 us gap is not counted
    assert hostspans.named_idle({"/host:CPU": {}}) is None
    # A gap no event covers is unnamed, whole or in part.
    assert hostspans.split_by_overlap([(0.0, 1.0)], [("emit", 0.25, 0.5)]) == pytest.approx(
        {"emit": 0.25, hostspans.UNNAMED: 0.75})


def test_idle_named_pct_reads_the_newest_trace(monkeypatch):
    d = [rec(10, "decode", 100.99, 420.0), rec(11, "fused", 101.46, 450.0, {"emit": 20.0, "deliver": 20.0, "admit": 10.0}),
         rec(12, "decode", 101.98, 430.0, {"emit": 40.0, "deliver": 30.0})]
    ctx = ctx_of(d)
    assert load_reader("device.idle_named_pct")(ctx) is None       # --trace 0
    monkeypatch.setattr(hostspans, "newest_xplane", lambda out: "some.xplane.pb")
    monkeypatch.setattr(trace, "read_planes", lambda path: planes())
    ctx.trace = trace.reduce(planes(), d, sync_host_s=100.5)
    got = load_reader("device.idle_named_pct")(ctx)
    assert got["value"] == pytest.approx(100.0)
    assert got["note"]["idle_s"] == pytest.approx(0.2)
    assert "annotations" not in got["note"]
    both = got["note"]["two_clocks"]        # the gaps before seq 11 and 12, read both ways
    assert both["gaps"] == 2
    assert both["loop_events_s"] == pytest.approx(0.12) and both["records_host_s"] == pytest.approx(0.12)
    assert both["differ_pct"] == pytest.approx(0.0, abs=1e-6)
    # A program that emits no annotations: nothing is named, and the note says so.
    monkeypatch.setattr(trace, "read_planes", lambda path: planes(annotated=False))
    got = load_reader("device.idle_named_pct")(ctx)
    assert got["value"] == 0.0
    assert got["note"]["seconds_by_phase"] == pytest.approx({hostspans.UNNAMED: 0.2})
    assert got["note"]["annotations"].startswith("none") and got["note"]["two_clocks"] is None


def test_the_new_metrics_are_declared_for_their_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"][:10]}    # PR 23's
    chat = ["loop.gap_ms_per_dispatch", "sched.loop_ms_per_dispatch", "server.loop_ms_per_dispatch", "loop.offcpu_pct",
            "ttft.received_p50_ms", "ttft.queued_p50_ms", "ttft.prefilling_p50_ms", "device.idle_named_pct"]
    for name in chat + ["loop.gap_share_pct"]:
        m = by_name[name]
        assert (ROOT / "benchmark" / "metrics" / f"{name}.py").exists()
        assert m["layer"] in layers and m["workloads"] == (
            ["mistral7b-docqa-batch"] if name == "loop.gap_share_pct" else ["mistral7b-chat-rate80"])
        assert m["source"] == ("device_trace" if name == "device.idle_named_pct" else "program_span")


def test_rehearsal_prints_the_phase_note():
    """One cell end to end on the CPU at the tiny size (about two minutes):
    the traced run prints the note of `loop.gap_ms_per_dispatch` and a value
    for every new metric that needs no device plane."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "mistral7b-chat-rate80", "--seed", "2147483659",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith('{"bench"')]
    note = next(l["note"] for l in lines if l["bench"] == "metric_note" and l["name"] == "loop.gap_ms_per_dispatch")
    assert {"admit", "prep", "emit", "deliver", "intake"} <= set(note["ms_by_phase"])
    result = lines[-1]["result"]
    assert result["correct"] is True
    for name in ("loop.gap_ms_per_dispatch", "sched.loop_ms_per_dispatch", "server.loop_ms_per_dispatch",
                 "loop.offcpu_pct", "ttft.received_p50_ms", "ttft.queued_p50_ms", "ttft.prefilling_p50_ms"):
        assert name in result["metrics"], name
    assert "device.idle_named_pct" not in result["metrics"]     # the CPU has no device plane
