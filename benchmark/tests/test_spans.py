"""The readers of the program's child spans (PR 38), on hand-made dispatch
records and a hand-made plane set.

    python -m pytest benchmark/tests/test_spans.py -q -p no:cacheprovider
"""

import json
from pathlib import Path

import pytest

from benchmark import hostspans, spans, trace
from benchmark.run import load_reader
from benchmark.tests.test_hostspans import ctx_of, planes, rec

ROOT = Path(__file__).resolve().parents[2]
NEW = ("sched.gap_ms_per_fused", "sched.admit_work_ms_per_admission", "sched.upload_ms_per_fused",
       "dispatch.submit_ms_per_dispatch", "device.idle_span_named_pct", "sched.capacity_blocked_pct")
CELLS = ["mistral7b-docqa-batch", "kanana2-docqa-long", "trinitymini-docqa-mixed", "phi4flash-reason-sessions"]


def window():
    """t0 = 100, 10 s.  Seq 1 is a decode step behind a queue (the lane is
    taken), seq 2 a fused step with an admission in its gap (an eviction in
    its allocation), seq 3 a decode step that waited 50 ms on an empty inbox,
    seq 4 an insert with its own admission, seq 5 a fused step of the same
    prompt (no admission) behind a queue that waits for blocks, seq 6 falls
    behind the window's end."""
    return [
        rec(0, "decode", 100.0, 150.0, span_ms={"dispatch.submit": 2.0}, span_n={"dispatch.submit": 1}, submit_ms=2.0),
        rec(1, "decode", 100.170, 150.0, {"emit": 2.0, "deliver": 8.0, "admit": 1.0, "prep": 1.0}, k=4,
            span_ms={"emit.replay": 1.5, "emit.free": 0.5, "dispatch.submit": 2.0},
            span_n={"emit.replay": 1, "emit.free": 1, "dispatch.submit": 1}, submit_ms=2.0, queued=2, blocked="lane"),
        rec(2, "fused", 100.350, 150.0, {"emit": 2.0, "deliver": 10.0, "admit": 10.0, "prep": 4.0}, k=2,
            span_ms={"emit.replay": 1.0, "admit.hash": 2.0, "admit.match": 1.0, "admit.alloc": 3.0, "admit.evict": 2.0,
                     "admit.upload": 2.5, "prep.sync_rows": 2.0, "prep.snapshots": 0.5, "dispatch.submit": 4.0,
                     "dispatch.publish": 1.0},
            span_n={"emit.replay": 1, "admit.hash": 1, "admit.match": 1, "admit.alloc": 1, "admit.evict": 1,
                    "admit.upload": 1, "prep.sync_rows": 1, "prep.snapshots": 1, "dispatch.submit": 1,
                    "dispatch.publish": 1}, submit_ms=4.0, queued=0, blocked=None),
        rec(3, "decode", 100.580, 150.0, {"emit": 2.0, "deliver": 8.0, "idle": 50.0, "admit": 1.0, "prep": 1.0}, k=8,
            span_ms={"emit.replay": 1.0, "dispatch.submit": 3.0}, span_n={"emit.replay": 1, "dispatch.submit": 1},
            submit_ms=3.0, queued=0, blocked=None),
        rec(4, "insert", 100.740, 40.0, {"emit": 1.0, "admit": 9.0},
            span_ms={"emit.replay": 1.0, "admit.hash": 1.0, "admit.match": 1.0, "admit.alloc": 1.0, "admit.insert": 4.0,
                     "dispatch.submit": 30.0},
            span_n={"emit.replay": 1, "admit.hash": 1, "admit.match": 1, "admit.alloc": 1, "admit.insert": 1,
                    "dispatch.submit": 1}, submit_ms=30.0),
        rec(5, "fused", 100.800, 100.0, {"emit": 2.0, "admit": 1.0, "prep": 3.0}, k=2,
            span_ms={"emit.replay": 1.0, "prep.snapshots": 0.5, "dispatch.submit": 6.0},
            span_n={"emit.replay": 1, "prep.snapshots": 1, "dispatch.submit": 1}, submit_ms=6.0, queued=1,
            blocked="capacity"),
        rec(6, "decode", 111.0, 150.0, {"emit": 500.0}, span_ms={"emit.replay": 499.0}, span_n={"emit.replay": 1},
            submit_ms=9.0, queued=1, blocked="slot"),
    ]


def test_parts_of_a_record_sum_to_its_gap_and_keep_nested_time_in_the_parent():
    d = window()[2]
    parts = spans.parts_ms(d)
    assert parts["admit.self"] == pytest.approx(10.0 - 2.0 - 1.0 - 3.0 - 2.5)   # evict is inside alloc
    assert parts["prep.self"] == pytest.approx(4.0 - 2.0 - 0.5) and parts["emit.self"] == pytest.approx(1.0)
    assert parts["deliver"] == 10.0 and "admit" not in parts and "idle" not in spans.parts_ms(window()[3])
    gap_side = {k: v for k, v in parts.items() if spans.PARENT.get(k, "") not in ("dispatch", "admit.alloc", "emit.replay")}
    assert sum(gap_side.values()) == pytest.approx(hostspans.busy_gap_ms(d))
    # A record whose phase had no span in it: all of it is self time.
    assert spans.parts_ms(window()[1])["admit.self"] == 1.0


def test_the_six_readers_on_hand_made_records():
    ctx = ctx_of(window())
    got = load_reader("sched.gap_ms_per_fused")(ctx)
    assert got["value"] == pytest.approx((26.0 + 6.0) / 2)
    assert got["note"]["fused_records"] == 2 and got["note"]["decode_records"] == 2
    by_part = got["note"]["ms_by_part"]
    assert by_part["admit.self"] == pytest.approx((1.5 + 1.0) / 2) and by_part["prep.snapshots"] == pytest.approx(0.5)
    assert list(by_part.values()) == sorted(by_part.values(), reverse=True)   # largest first
    assert got["note"]["decode_ms_by_part"]["emit.replay"] == pytest.approx(1.25)

    got = load_reader("sched.admit_work_ms_per_admission")(ctx)   # seq 2 and the insert
    # hash 2+1, match 1+1, alloc 3+1; admit.self of every record: 1 + 1.5 + 1 + 2 + 1
    assert got["value"] == pytest.approx((3.0 + 2.0 + 4.0 + 6.5) / 2)
    assert got["note"]["admissions"] == 2 and got["note"]["records"] == 5
    assert got["note"]["ms_per_admission"]["admit.evict"] == pytest.approx(1.0)
    assert got["note"]["counts"]["admit.evict"] == 1 and got["note"]["counts"]["admit.hash"] == 2

    got = load_reader("sched.upload_ms_per_fused")(ctx)
    assert got["value"] == pytest.approx((2.5 + 2.0 + 0.5 + 0.5) / 2)
    assert got["note"]["counts"] == {"admit.upload": 1, "prep.sync_rows": 1, "prep.snapshots": 2}

    got = load_reader("dispatch.submit_ms_per_dispatch")(ctx)    # steps only: the insert's 30 ms stay out
    assert got["value"] == pytest.approx((2.0 + 4.0 + 3.0 + 6.0) / 4)
    assert got["note"]["ms_by_kind_and_k"]["fused k=2"] == {"n": 2, "ms": pytest.approx(5.0)}

    got = load_reader("sched.capacity_blocked_pct")(ctx)         # seq 1 (lane, 162 ms) and seq 5 (capacity, 106 ms)
    assert got["value"] == pytest.approx(100.0 * 106.0 / 268.0)
    assert got["note"]["pct_by_reason"] == pytest.approx({"lane": 100.0 * 162.0 / 268.0, "capacity": 100.0 * 106.0 / 268.0})
    assert got["note"]["queued_records"] == 2 and got["note"]["records"] == 4
    # Nothing queued in the window: no time was blocked on the pool.
    calm = [dict(d, queued=0, blocked=None) if "queued" in d else d for d in window()]
    assert load_reader("sched.capacity_blocked_pct")(ctx_of(calm))["value"] == 0.0


def test_a_program_without_the_fields_reads_nothing(monkeypatch):
    """The parent of PR 38: records with `host_ms` and `queued`, planes with
    `llm.loop.*` and `llm.dispatch` events, nothing else.  Every new reader
    returns None and none raises; the old readers read what they read."""
    keep = ("seq", "kind", "k", "wall_ms", "start", "end", "host_ms", "gap_ms", "queued")
    old = [{k: v for k, v in d.items() if k in keep} for d in window()]
    ctx = ctx_of(old)
    monkeypatch.setattr(hostspans, "newest_xplane", lambda out: "some.xplane.pb")
    monkeypatch.setattr(trace, "read_planes", lambda path: planes())
    ctx.trace = trace.reduce(planes(), old, sync_host_s=100.5)
    for name in NEW:
        assert load_reader(name)(ctx) is None, name
    assert load_reader("device.idle_named_pct")(ctx)["value"] == pytest.approx(100.0)
    assert load_reader("loop.gap_share_pct")(ctx)["value"] == load_reader("loop.gap_share_pct")(ctx_of(window()))["value"]


def spanned_planes():
    """`test_hostspans.planes()` with child spans on the loop thread: the
    admit before the fused dispatch holds a match (and 2 ms of self time),
    the dispatches their submits, the emits their replays (a free inside the
    second)."""
    p = planes()
    p["/host:CPU"]["python3"] += [
        ("llm.span.dispatch.submit", 0.99, 0.995), ("llm.span.emit.replay", 1.41, 1.425),
        ("llm.span.admit.match", 1.452, 1.46), ("llm.span.dispatch.submit", 1.46, 1.49),
        ("llm.span.emit.replay", 1.91, 1.94), ("llm.span.emit.free", 1.92, 1.93),
        ("llm.span.dispatch.submit", 1.98, 1.995),
    ]
    return p


def test_an_idle_gap_is_counted_once_for_the_innermost_event():
    idle = spans.named_idle(spanned_planes())
    assert idle == pytest.approx({
        "emit.replay": 0.015 + 0.01 + 0.01, "emit.free": 0.01, "emit": 0.005 + 0.01, "deliver": 0.02 + 0.03,
        "admit.match": 0.008, "admit": 0.002, "dispatch.submit": 0.03 + 0.015,
        hostspans.IN_DISPATCH: 0.01 + 0.01 + 0.01 + 0.005,
    })
    assert sum(idle.values()) == pytest.approx(0.2)            # every idle second, once
    # The readers of PR 24 see the events they saw: spans are no phases.
    assert hostspans.named_idle(spanned_planes()) == pytest.approx(hostspans.named_idle(planes()))
    assert hostspans.loop_events(spanned_planes()) == hostspans.loop_events(planes())
    assert spans.named_idle(planes()) is None                  # no span event: the parent
    assert spans.named_idle({"/host:CPU": {}}) is None
    assert spans.innermost([("a", 0.0, 4.0), ("a.b", 1.0, 5.0), ("c", 6.0, 7.0)]) == [
        ("a", 0.0, 1.0), ("a.b", 1.0, 4.0), ("c", 6.0, 7.0)]   # a child is clipped to its parent


def test_idle_span_named_pct_accounts_for_every_idle_second(monkeypatch):
    d = [rec(10, "decode", 100.99, 420.0), rec(11, "fused", 101.46, 450.0, {"emit": 20.0, "deliver": 20.0, "admit": 10.0}),
         rec(12, "decode", 101.98, 430.0, {"emit": 40.0, "deliver": 30.0})]
    ctx = ctx_of(d)
    assert load_reader("device.idle_span_named_pct")(ctx) is None      # --trace 0
    monkeypatch.setattr(hostspans, "newest_xplane", lambda out: "some.xplane.pb")
    monkeypatch.setattr(trace, "read_planes", lambda path: spanned_planes())
    ctx.trace = trace.reduce(spanned_planes(), d, sync_host_s=100.5)
    got = load_reader("device.idle_span_named_pct")(ctx)
    note = got["note"]
    by_span = sum(note["seconds_by_span"].values())
    assert got["value"] == pytest.approx(100.0 * by_span / 0.2) and by_span == pytest.approx(0.098)
    assert set(note["seconds_by_span"]) == {"emit.replay", "emit.free", "admit.match", "dispatch.submit"}
    assert set(note["seconds_by_phase_remainder"]) == {"emit", "deliver", "admit"}
    assert (by_span + sum(note["seconds_by_phase_remainder"].values()) + note["in_dispatch_remainder_s"]
            + note["unnamed_s"]) == pytest.approx(note["idle_s"]) == pytest.approx(0.2)
    assert note["unnamed_s"] <= note["unnamed_by_phase_s"] == 0.0
    # The whole-phase reader reads the same with and without the span events.
    with_spans = load_reader("device.idle_named_pct")(ctx)
    monkeypatch.setattr(trace, "read_planes", lambda path: planes())
    assert load_reader("device.idle_named_pct")(ctx) == with_spans


def test_the_table_of_parents_is_the_programs():
    from jax_llama_tpu import obs

    assert spans.PARENT == obs.LOOP_SPANS
    assert set(spans.ADMIT_WORK + spans.UPLOADS + spans.ADMISSION) - {"admit.self"} <= set(obs.LOOP_SPANS)


def test_the_new_metrics_are_declared_for_the_four_closed_loop_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(NEW)
    layers = {m["layer"] for m in bench["per_layer"][:-6]}
    for m in bench["per_layer"][-6:]:
        assert (ROOT / "benchmark" / "metrics" / f"{m['name']}.py").exists()
        assert m["workloads"] == CELLS and m["moves"] == "out_tokens_per_s" and m["layer"] in layers
        assert m["source"] == ("device_trace" if m["name"].startswith("device.") else "program_span")
        assert m["layer"] == ("jitted programs" if m["name"].startswith("dispatch.") else "scheduler and KV manager")
