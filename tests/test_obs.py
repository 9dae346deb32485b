"""Observability layer (obs.py): request span timelines through the
admission state machine (classic / fused / spec / restoring), dispatch
spans causally linked to the requests they carried, Prometheus
histogram bucket math, SLO accounting, and the Chrome/Perfetto
``trace_event`` export schema.

The unit tests drive :class:`Observability` with an injectable clock;
the integration tests run the real tiny-model ``ContinuousBatcher`` and
assert the timelines the serving loop recorded — including the
acceptance-criterion drill: a request served through a FUSED admission
after a radix host-tier RESTORE owns a queued/restoring/prefilling/
decoding timeline whose span links resolve to real dispatch spans, and
the whole window exports as loadable ``trace_event`` JSON."""

import json

import jax
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.obs import (
    ADMIT_BLOCKED,
    HISTOGRAMS,
    LABELED_HISTOGRAMS,
    LOOP_PHASES,
    LOOP_SPANS,
    METRICS,
    Histogram,
    Observability,
    StructuredLogger,
    metric_meta,
)
from jax_llama_tpu.serving import ContinuousBatcher

pytestmark = pytest.mark.obs

BS = 16  # block size for the tier drills (matches test_kvcache)

CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=128, dtype="float32", param_dtype="float32",
)


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


# ---------------------------------------------------------------------------
# Histogram bucket math
# ---------------------------------------------------------------------------

def test_histogram_bucket_math():
    h = Histogram("x_ms", "help", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 5.0, 7.0):
        h.observe(v)
    # le is LESS-THAN-OR-EQUAL: a value on a bound lands in that bucket.
    assert h.cumulative() == [
        ("1", 2), ("2", 3), ("5", 4), ("+Inf", 5),
    ]
    assert h.count == 5
    assert h.sum == pytest.approx(15.0)


def test_histogram_exposition_format():
    h = Histogram("lat_ms", "latency help", buckets=(10.0, 100.0))
    h.observe(3.0)
    h.observe(250.0)
    lines = h.expose("llm_")
    assert lines[0] == "# HELP llm_lat_ms latency help"
    assert lines[1] == "# TYPE llm_lat_ms histogram"
    assert 'llm_lat_ms_bucket{le="10"} 1' in lines
    assert 'llm_lat_ms_bucket{le="+Inf"} 2' in lines
    assert "llm_lat_ms_sum 253.0" in lines
    assert "llm_lat_ms_count 2" in lines
    # The +Inf bucket always equals _count (Prometheus invariant).
    inf = [ln for ln in lines if 'le="+Inf"' in ln][0]
    cnt = [ln for ln in lines if ln.endswith("_count 2")][0]
    assert inf.rsplit(" ", 1)[1] == cnt.rsplit(" ", 1)[1]


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        Histogram("bad", "h", buckets=(5.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("bad", "h", buckets=(1.0, 1.0, 2.0))


def test_metric_registry_shape():
    """Every registered metric carries a valid type and a non-empty
    HELP; the names the exposition derives families from are covered."""
    for name, (kind, help_text) in METRICS.items():
        assert kind in ("counter", "gauge"), name
        assert help_text, name
    assert metric_meta("emitted_tokens_total") == METRICS[
        "emitted_tokens_total"
    ]
    assert metric_meta("definitely_not_registered") is None
    # radix_nodes_total is the deliberate counter-convention exception.
    assert METRICS["radix_nodes_total"][0] == "gauge"
    assert set(HISTOGRAMS) == {
        "ttft_ms", "itl_ms", "queue_wait_ms", "prefill_chunk_ms",
        "swap_in_ms", "compile_ms", "dispatch_ms",
        "prefix_hit_depth_tokens", "session_kv_blocks",
    }
    # dispatch_ms renders as one labeled series per dispatch kind.
    assert LABELED_HISTOGRAMS == {"dispatch_ms"}
    # The labeled attribution families are registered too.
    for fam in ("jit_cache_entries", "program_compiles_total",
                "compiles_total"):
        assert metric_meta(fam) is not None, fam
    # The cost-model gauges are gone from the registry (PR 30).
    for fam in ("mxu_utilization", "hbm_utilization",
                "host_overhead_ratio"):
        assert metric_meta(fam) is None, fam


# ---------------------------------------------------------------------------
# Loop phases: the gap before a dispatch record, by what the loop thread did
# ---------------------------------------------------------------------------

def _assert_tiles(recs, tol=0.01):
    """Every record after the first carries its gap; the phases sum to
    it, and it is this record's start less the previous record's end."""
    assert "gap_ms" not in recs[0] and "host_ms" not in recs[0]
    for prev, rec in zip(recs, recs[1:]):
        assert abs(sum(rec["host_ms"].values()) - rec["gap_ms"]) <= tol, rec
        prev_end = prev["start_ms"] + prev["wall_ms"]
        assert abs(rec["start_ms"] - prev_end - rec["gap_ms"]) <= tol, rec
        assert set(rec["host_ms"]) <= LOOP_PHASES


def _one_iteration(obs, clk, kind="decode", idle=0.0):
    """One serving-loop iteration on a fake clock: 1 ms in each server
    phase, 2 ms in each scheduler phase, a 100 ms dispatch."""
    for phase in ("control", "intake"):
        obs.loop_phase(phase)
        clk.advance(0.001)
    if idle:
        obs.loop_phase("idle")
        clk.advance(idle)
        obs.loop_phase("intake")
    for phase in ("admit", "prep"):
        obs.loop_phase(phase)
        clk.advance(0.002)
    obs.dispatch_begin(kind, "_paged_decode_chunk", 8)
    clk.advance(0.1)
    seq = obs.record_dispatch(kind, k=8, wall_ms=100.0, then="emit")
    clk.advance(0.002)
    obs.loop_phase("deliver")
    clk.advance(0.001)
    return seq


def test_loop_phases_tile_the_gap():
    clk = FakeClock()
    obs = Observability(clock=clk)
    _one_iteration(obs, clk)
    _one_iteration(obs, clk, idle=0.05)
    _one_iteration(obs, clk, kind="fused")
    recs = obs.dispatches_json()["dispatches"]
    _assert_tiles(recs)
    assert recs[1]["host_ms"] == pytest.approx({
        "emit": 2.0, "deliver": 1.0, "control": 1.0, "intake": 1.0,
        "idle": 50.0, "admit": 2.0, "prep": 2.0,
    })
    assert recs[1]["gap_ms"] == pytest.approx(59.0)
    # Idle is part of the tiling and absent where the loop never waited.
    assert "idle" not in recs[2]["host_ms"]
    assert recs[2]["gap_ms"] == pytest.approx(9.0)
    # The loop thread's CPU time over the gap rides along (a fake clock
    # does not move it: real, tiny, never negative).
    assert 0.0 <= recs[2]["gap_cpu_ms"] < 50.0
    assert recs[2]["compiles"] == 0
    # Every consumer of the record sees the same fields.
    seen = []
    obs.on_dispatch = seen.append
    _one_iteration(obs, clk)
    assert seen[0]["host_ms"] and seen[0]["gap_ms"] == pytest.approx(9.0)


def test_compiles_since_the_previous_record_ride_the_record():
    clk = FakeClock()
    obs = Observability(clock=clk)
    _one_iteration(obs, clk)
    obs.record_compile("_fused_chunk", 1200.0)
    obs.record_compile("_fused_chunk", 800.0)
    _one_iteration(obs, clk)
    _one_iteration(obs, clk)
    assert [d["compiles"] for d in obs.dispatches] == [0, 2, 0]


@pytest.mark.parametrize("call", [
    lambda o: o.loop_phase("deliverr"),
    lambda o: o.record_dispatch("decode", then="emitt"),
])
def test_unknown_loop_phase_raises(call):
    """A typo must not mint a phantom phase (as record_dispatch's kinds)."""
    with pytest.raises(ValueError, match="unknown loop phase"):
        call(Observability(clock=FakeClock()))


def test_record_without_dispatch_begin_still_tiles():
    """A direct caller of record_dispatch (no dispatch_begin): the open
    phase ran up to the record's start, wall_ms before now."""
    clk = FakeClock()
    obs = Observability(clock=clk)
    obs.record_dispatch("insert", wall_ms=1.0)
    obs.loop_phase("admit")
    clk.advance(0.010)
    obs.record_dispatch("insert", wall_ms=4.0)
    clk.advance(0.003)
    obs.loop_phase("prep")
    clk.advance(0.007)
    obs.record_dispatch("decode", wall_ms=5.0, then="emit")
    first, second, third = obs.dispatches
    assert "gap_ms" not in first  # nothing before it
    assert second["host_ms"] == pytest.approx({"admit": 6.0})
    assert third["host_ms"] == pytest.approx({"admit": 3.0, "prep": 2.0})
    assert third["gap_ms"] == pytest.approx(5.0)


def test_abandoned_dispatch_returns_its_time_to_the_phase():
    """A dispatch that raised never records: the time since its begin
    goes back to the phase it interrupted, and the tiling holds."""
    clk = FakeClock()
    obs = Observability(clock=clk)
    _one_iteration(obs, clk)
    obs.loop_phase("prep")
    clk.advance(0.002)
    obs.dispatch_begin("decode", "_paged_decode_chunk", 8)
    clk.advance(0.030)          # ... and the dispatch raises
    obs.loop_phase("control")   # the server's recovery
    clk.advance(0.004)
    _one_iteration(obs, clk)
    recs = obs.dispatches_json()["dispatches"]
    _assert_tiles(recs)
    assert recs[1]["host_ms"]["prep"] == pytest.approx(2.0 + 30.0 + 2.0)
    assert recs[1]["host_ms"]["control"] == pytest.approx(4.0 + 1.0)


def test_loop_metrics_registered_and_folded_at_records():
    for fam in ("loop_phase_ms_total", "loop_gap_ms_total",
                "loop_gap_cpu_ms_total"):
        assert metric_meta(fam)[0] == "counter", fam
    clk = FakeClock()
    obs = Observability(clock=clk)
    _one_iteration(obs, clk)
    _one_iteration(obs, clk, idle=0.02)
    m = obs.metrics()
    assert m["loop_gap_ms_total"] == pytest.approx(29.0)
    assert m["loop_gap_cpu_ms_total"] >= 0.0
    phases = {
        lab["phase"]: v for fam, lab, v in obs.loop_phase_metrics()
        if fam == "loop_phase_ms_total"
    }
    assert phases["idle"] == pytest.approx(20.0)
    assert sum(phases.values()) == pytest.approx(m["loop_gap_ms_total"])


def test_trace_json_serving_loop_track():
    clk = FakeClock()
    obs = Observability(clock=clk)
    _one_iteration(obs, clk)
    _one_iteration(obs, clk)
    doc = obs.trace_json()
    tracks = {
        e["args"]["name"]: e["tid"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert "serving loop" in tracks
    loop = [e for e in doc["traceEvents"] if e.get("cat") == "loop"]
    assert {e["tid"] for e in loop} == {tracks["serving loop"]}
    assert {e["name"] for e in loop} >= {
        "control", "intake", "admit", "prep", "emit", "deliver",
    }
    # The phases lie between the dispatch spans, never inside one.
    disp = sorted(
        (e["ts"], e["ts"] + e["dur"]) for e in doc["traceEvents"]
        if e.get("cat") == "dispatch"
    )
    for e in loop:
        assert not any(
            a < e["ts"] + e["dur"] / 2 < b for a, b in disp
        ), e
    # The dispatch spans carry the gap too.
    args = [e["args"] for e in doc["traceEvents"]
            if e.get("cat") == "dispatch"]
    assert "gap_ms" in args[1] and "host_ms" in args[1]
    # An old-enough horizon drops the first iteration's phases.
    clk.advance(10.0)
    _one_iteration(obs, clk)
    recent = [e for e in obs.trace_json(window_ms=5000.0)["traceEvents"]
              if e.get("cat") == "loop"]
    assert 0 < len(recent) < len(loop)


# ---------------------------------------------------------------------------
# Loop spans: the parts of a phase, and of a dispatch's own host time
# ---------------------------------------------------------------------------

def _spanned_iteration(obs, clk, kind="fused", rid=7, blocked=None):
    """One iteration with an admission in it: admit = 1 self + hash 2 +
    match 1 + alloc 4 (evict 3 inside) + upload 2; prep = 1 self +
    sync_rows 2; the dispatch = submit 3 + publish 1 of 100 ms; emit =
    replay 2 (free 1 inside) + 1 self."""
    obs.loop_phase("admit")
    clk.advance(0.001)
    with obs.loop_span("admit.hash", rid=rid):
        clk.advance(0.002)
    with obs.loop_span("admit.match", rid=rid):
        clk.advance(0.001)
    with obs.loop_span("admit.alloc", rid=rid):
        clk.advance(0.001)
        with obs.loop_span("admit.evict"):
            clk.advance(0.003)
    with obs.loop_span("admit.upload", rid=rid):
        clk.advance(0.002)
    obs.loop_phase("prep")
    clk.advance(0.001)
    with obs.loop_span("prep.sync_rows"):
        clk.advance(0.002)
    obs.dispatch_begin(kind, "_fused_chunk", 2)
    with obs.loop_span("dispatch.submit"):
        clk.advance(0.003)
    with obs.loop_span("dispatch.publish", rid=rid):
        clk.advance(0.001)
    clk.advance(0.096)
    seq = obs.record_dispatch(kind, k=2, wall_ms=100.0, then="emit",
                              queued=1, blocked=blocked)
    with obs.loop_span("emit.replay"):
        clk.advance(0.001)
        with obs.loop_span("emit.free"):
            clk.advance(0.001)
    clk.advance(0.001)
    return seq


def _parent_ms(rec, name):
    """What a span's time is part of: its parent span's, its phase's, or
    the dispatch's wall time."""
    parent = LOOP_SPANS[name]
    if parent == "dispatch":
        return rec["wall_ms"]
    if parent in LOOP_SPANS:
        return rec["span_ms"][parent]
    return rec["host_ms"][parent]


def test_spans_ride_the_record_and_leave_the_tiling_alone():
    clk = FakeClock()
    obs = Observability(clock=clk)
    _one_iteration(obs, clk)
    first = _spanned_iteration(obs, clk, blocked="lane")
    second = _spanned_iteration(obs, clk)
    recs = obs.dispatches_json()["dispatches"]
    _assert_tiles(recs)  # host_ms: the phases' keys, summing to gap_ms
    assert set(LOOP_SPANS.values()) - set(LOOP_SPANS) <= (
        LOOP_PHASES | {"dispatch"}
    )
    rec = recs[first]
    assert rec["host_ms"] == pytest.approx(
        {"emit": 2.0, "deliver": 1.0, "admit": 10.0, "prep": 3.0})
    assert rec["span_ms"] == pytest.approx({
        "admit.hash": 2.0, "admit.match": 1.0, "admit.alloc": 4.0,
        "admit.evict": 3.0, "admit.upload": 2.0, "prep.sync_rows": 2.0,
        "dispatch.submit": 3.0, "dispatch.publish": 1.0,
    })
    assert rec["submit_ms"] == pytest.approx(3.0)
    assert set(rec["span_n"].values()) == {1}
    assert (rec["queued"], rec["blocked"]) == (1, "lane")
    # The emit spans closed after `first` landed: they led to `second`.
    rec = recs[second]
    assert rec["span_ms"]["emit.replay"] == pytest.approx(2.0)
    assert rec["span_ms"]["emit.free"] == pytest.approx(1.0)
    assert rec["host_ms"]["emit"] == pytest.approx(3.0)
    assert rec["blocked"] is None
    for r in recs[1:]:
        for name, ms in r["span_ms"].items():
            assert ms <= _parent_ms(r, name) + 1e-6, (name, r)
    # The first record of an Observability has no gap and still its submit.
    assert recs[0]["span_ms"] == {} and "host_ms" not in recs[0]
    # The ring: cause, request and the record each span led to.
    ring = obs.loop_spans_json()
    got = {(s["name"], s["seq"]): s for s in ring}
    assert got["admit.hash", first]["parent"] == "admit"
    assert got["admit.evict", first]["parent"] == "admit.alloc"
    assert got["admit.evict", first]["rid"] == 7  # its parent's
    assert got["dispatch.submit", first]["parent"] == "dispatch"
    assert got["emit.free", second]["parent"] == "emit.replay"
    assert got["admit.match", second]["duration_ms"] == pytest.approx(1.0)
    assert {s["rid"] for s in obs.loop_spans_json(rids=[7])} == {7}


@pytest.mark.parametrize("call", [
    lambda o: o.loop_span("admit.mach"),
    lambda o: o.loop_span("admit"),
    lambda o: o.admit_blocked("lanes"),
    lambda o: o.record_dispatch("decode", queued=1, blocked="pool"),
], ids=["span-typo", "a-phase-is-no-span", "reason-typo", "record-reason"])
def test_unknown_span_or_reason_raises(call):
    with pytest.raises(ValueError, match="unknown (loop span|blocked)"):
        call(Observability(clock=FakeClock()))


def test_a_span_outside_its_parent_records_nothing():
    """``_free_slot`` from a cancel (phase ``intake``) is no
    ``emit.free``; ``_alloc_blocks`` from a handoff import (phase
    ``control``) no ``admit.evict``: the time stays with the phase."""
    clk = FakeClock()
    obs = Observability(clock=clk)
    _one_iteration(obs, clk)
    obs.loop_phase("intake")
    with obs.loop_span("emit.free"):
        clk.advance(0.004)
    obs.loop_phase("admit")
    with obs.loop_span("admit.evict"):      # not under admit.alloc
        clk.advance(0.002)
    with obs.loop_span("dispatch.submit"):  # no dispatch is open
        clk.advance(0.001)
    _one_iteration(obs, clk)
    rec = obs.dispatches[-1]
    assert set(rec["span_ms"]) == set() and not obs._sp_open
    assert rec["host_ms"]["intake"] == pytest.approx(4.0 + 1.0)
    assert rec["host_ms"]["admit"] == pytest.approx(3.0 + 2.0)


def test_a_span_ends_at_its_exit_or_at_the_next_dispatch():
    """A body that raises closes its span on the way out; a span left
    open (entered by hand, never exited) is closed at dispatch_begin,
    one inside the dispatch at the record; an abandoned dispatch takes
    its spans' time back with its own."""
    clk = FakeClock()
    obs = Observability(clock=clk)
    _one_iteration(obs, clk)
    obs.loop_phase("admit")
    with pytest.raises(RuntimeError):
        with obs.loop_span("admit.alloc"):
            clk.advance(0.002)
            with obs.loop_span("admit.evict"):
                clk.advance(0.001)
                raise RuntimeError("injected")
    assert not obs._sp_open
    left_open = obs.loop_span("admit.insert", rid=3)
    left_open.__enter__()
    clk.advance(0.004)
    obs.dispatch_begin("insert", "_paged_insert", 1)
    assert not obs._sp_open
    obs.loop_span("dispatch.submit").__enter__()
    clk.advance(0.005)
    obs.record_dispatch("insert", wall_ms=5.0)
    assert not obs._sp_open
    left_open.__exit__(None, None, None)  # closed already: nothing twice
    rec = obs.dispatches[-1]
    assert rec["span_ms"] == pytest.approx({
        "admit.alloc": 3.0, "admit.evict": 1.0, "admit.insert": 4.0,
        "dispatch.submit": 5.0,
    })
    assert rec["host_ms"]["admit"] == pytest.approx(7.0)
    # A dispatch that raised after its submit began.
    obs.loop_phase("prep")
    clk.advance(0.001)
    obs.dispatch_begin("decode", "_paged_decode_chunk", 8)
    with pytest.raises(RuntimeError):
        with obs.loop_span("dispatch.submit"):
            clk.advance(0.030)
            raise RuntimeError("injected")
    _one_iteration(obs, clk)
    rec = obs.dispatches[-1]
    assert "dispatch.submit" not in rec["span_ms"] and "submit_ms" not in rec
    assert rec["host_ms"]["prep"] == pytest.approx(1.0 + 30.0 + 2.0)
    _assert_tiles(obs.dispatches_json()["dispatches"])


def test_span_metrics_registered_and_folded_at_records():
    for fam in ("loop_span_ms_total", "loop_span_total",
                "dispatch_submit_ms_total", "admit_blocked_total"):
        assert metric_meta(fam)[0] == "counter", fam
    clk = FakeClock()
    obs = Observability(clock=clk)
    _spanned_iteration(obs, clk)
    _spanned_iteration(obs, clk)
    obs.admit_blocked("lane")
    obs.admit_blocked("lane")
    obs.admit_blocked("capacity")
    assert obs.metrics()["dispatch_submit_ms_total"] == pytest.approx(6.0)
    fams = {}
    for fam, lab, v in obs.loop_span_metrics():
        fams.setdefault(fam, {})[next(iter(lab.values()))] = v
    assert fams["loop_span_ms_total"]["admit.alloc"] == pytest.approx(8.0)
    assert fams["loop_span_total"]["admit.evict"] == 2
    # emit.replay of the second iteration waits for a third record.
    assert fams["loop_span_total"]["emit.replay"] == 1
    assert fams["admit_blocked_total"] == {"lane": 2, "capacity": 1}
    assert set(fams["admit_blocked_total"]) <= set(ADMIT_BLOCKED)


def test_trace_json_nests_the_spans_on_the_serving_loop_track():
    clk = FakeClock()
    obs = Observability(clock=clk)
    _one_iteration(obs, clk)
    _spanned_iteration(obs, clk)
    evs = obs.trace_json()["traceEvents"]
    spans = [e for e in evs if e.get("cat") == "loop_span"]
    assert {e["name"] for e in spans} >= {
        "admit.hash", "admit.evict", "dispatch.submit", "emit.free"}
    phases = [e for e in evs if e.get("cat") == "loop"]
    assert {e["tid"] for e in spans} == {phases[0]["tid"]}
    hash_ev = next(e for e in spans if e["name"] == "admit.hash")
    assert hash_ev["args"] == {"parent": "admit", "rid": 7, "seq": 1}
    assert any(  # under its phase on the track
        p["name"] == "admit" and p["ts"] <= hash_ev["ts"]
        and hash_ev["ts"] + hash_ev["dur"] <= p["ts"] + p["dur"] + 1
        for p in phases
    )
    disp = [e for e in evs if e.get("cat") == "dispatch"]
    assert "span_ms" in disp[-1]["args"] and "submit_ms" in disp[-1]["args"]


def test_received_span_opens_the_timeline():
    """submit(received_at=...) starts the timeline where the server's
    TTFT clock does; ``queued`` keeps its meaning and its histogram."""
    clk = FakeClock()
    obs = Observability(clock=clk)
    t_post = clk()
    clk.advance(0.075)  # inbox + class queue
    obs.request_queued(3, prompt_tokens=8, received_at=t_post)
    clk.advance(0.020)
    obs.begin_span(3, "prefilling")
    clk.advance(0.150)
    obs.begin_span(3, "decoding")
    spans = obs.timeline_json("r3")["spans"]
    assert [sp["state"] for sp in spans] == [
        "received", "queued", "prefilling", "decoding",
    ]
    assert [sp["duration_ms"] for sp in spans[:3]] == pytest.approx(
        [75.0, 20.0, 150.0]
    )
    assert spans[0]["end_ms"] == spans[1]["start_ms"]
    assert obs.hist["queue_wait_ms"].sum == pytest.approx(20.0)
    # Without received_at (a direct submit) nothing changes.
    obs.request_queued(4, prompt_tokens=8)
    assert [sp["state"] for sp in obs.timeline_json("r4")["spans"]] == [
        "queued"
    ]


# ---------------------------------------------------------------------------
# Span lifecycle / binding / rings (fake clock)
# ---------------------------------------------------------------------------

def test_span_lifecycle_and_dispatch_links():
    clk = FakeClock()
    obs = Observability(clock=clk)
    obs.request_queued(7, prompt_tokens=12)
    clk.advance(0.050)
    obs.begin_span(7, "prefilling")
    seq = obs.record_dispatch(
        kind="insert", k=1, occupancy=1, prefill_tokens=12,
        wall_ms=5.0, fetch_ms=1.0, rids=[7],
    )
    clk.advance(0.010)
    obs.begin_span(7, "decoding")
    seq2 = obs.record_dispatch(kind="decode", k=4, occupancy=1,
                               wall_ms=2.0, rids=[7])
    clk.advance(0.008)
    obs.request_end(7, "finished")

    obs.bind(7, "ext-abc")
    tl = obs.timeline_json("ext-abc")
    assert tl is not None
    assert tl["request_id"] == "ext-abc" and tl["rids"] == [7]
    assert tl["prompt_tokens"] == 12
    assert tl["outcome"] == "finished" and tl["error"] is None
    states = [sp["state"] for sp in tl["spans"]]
    assert states == ["queued", "prefilling", "decoding"]
    q, pf, dec = tl["spans"]
    assert q["duration_ms"] == pytest.approx(50.0)
    assert pf["dispatches"] == [seq]
    assert dec["dispatches"] == [seq2]
    # Every linked seq resolves to a real record in the payload.
    linked = {d["seq"] for d in tl["dispatch_spans"]}
    assert linked == {seq, seq2}
    # The queued->prefilling edge fed the queue-wait histogram.
    assert obs.hist["queue_wait_ms"].count == 1
    assert obs.hist["queue_wait_ms"].sum == pytest.approx(50.0)
    # dispatch_ms saw both (one per-kind series each);
    # prefill_chunk_ms only the insert.
    assert obs.hist_dispatch["insert"].count == 1
    assert obs.hist_dispatch["decode"].count == 1
    assert obs.hist["prefill_chunk_ms"].count == 1
    # Lookup also works by provisional id and bare rid.
    assert obs.timeline_json("7")["request_id"] == "ext-abc"


def test_a_fused_record_says_what_its_admission_sample_cost():
    """``first_sample`` rides a record as it was handed in (``skipped``,
    ``greedy`` or ``drawn``: the program's two branch inputs, known to the
    host before the submit) and only the records that were handed one."""
    obs = Observability(clock=FakeClock())
    for cost in ("skipped", "greedy", "drawn"):
        obs.record_dispatch(kind="fused", k=2, prefill_tokens=16,
                            first_sample=cost)
    obs.record_dispatch(kind="decode", k=4)
    assert [d.get("first_sample") for d in obs.dispatches] == [
        "skipped", "greedy", "drawn", None]
    assert "first_sample" not in obs.dispatches[-1]
    assert obs.dispatches_json()["dispatches"][1]["first_sample"] == "greedy"


def test_bind_before_spans_and_unknown_rid_is_noop():
    obs = Observability(clock=FakeClock())
    obs.bind(99, "never-queued")  # unknown rid: no crash, no timeline
    assert obs.timeline_json("never-queued") is None
    obs.begin_span(42, "decoding")  # unknown rid: no-op
    obs.request_end(42, "finished")
    assert obs.requests_json()["requests"] == []


def test_bind_replay_folds_into_existing_timeline():
    """Crash-recovery replay: the fresh rid (and its queued span) fold
    into the external id's existing timeline — one continuous story."""
    clk = FakeClock()
    obs = Observability(clock=clk)
    obs.request_queued(1, 8)
    obs.bind(1, "cli-id")
    obs.begin_span(1, "decoding")
    clk.advance(0.010)
    # crash: replay resubmits under a fresh rid
    obs.request_queued(2, 8)
    obs.bind(2, "cli-id", replay=True)
    clk.advance(0.005)
    obs.begin_span(2, "decoding")
    obs.request_end(2, "finished")
    tl = obs.timeline_json("cli-id")
    assert tl["rids"] == [1, 2]
    assert tl["outcome"] == "finished"
    states = [sp["state"] for sp in tl["spans"]]
    assert states == ["queued", "decoding", "queued", "decoding"]
    assert tl["spans"][2]["note"] == "replay"
    # The rid-2 lookups now resolve to the folded timeline too.
    assert obs.timeline_json("2")["request_id"] == "cli-id"


def test_bind_id_collision_keeps_separate_timelines():
    """A NON-replay bind onto an id another request owns (a client
    reusing X-Request-Id) must not merge the two: the live timeline
    keeps its state, the new request stays addressable by rid."""
    clk = FakeClock()
    obs = Observability(clock=clk)
    obs.request_queued(1, 4)
    obs.bind(1, "reused-id")
    obs.begin_span(1, "decoding")
    obs.request_queued(2, 9)  # different request, same client id
    obs.bind(2, "reused-id")
    tl = obs.timeline_json("reused-id")
    assert tl["rids"] == [1] and tl["prompt_tokens"] == 4
    tl2 = obs.timeline_json("2")
    assert tl2["request_id"] == "r2" and tl2["prompt_tokens"] == 9
    obs.request_end(1, "finished")
    assert obs.timeline_json("reused-id")["outcome"] == "finished"


def test_bind_replay_rid_index_bounded():
    """Folded replay rids are capped: only the most recent
    incarnations stay in the by-rid index (a crash-looping request
    cannot grow its timeline's index entries without bound)."""
    from jax_llama_tpu.obs import _MAX_RIDS

    obs = Observability(clock=FakeClock())
    obs.request_queued(0, 4)
    obs.bind(0, "storm")
    for rid in range(1, 3 * _MAX_RIDS):
        obs.request_queued(rid, 4)
        obs.bind(rid, "storm", replay=True)
    tl = obs.timeline_json("storm")
    assert len(tl["rids"]) == _MAX_RIDS
    assert tl["rids"][-1] == 3 * _MAX_RIDS - 1
    # Aged-out rids no longer resolve; recent ones do.
    assert obs.timeline_json("0") is None
    assert obs.timeline_json(str(3 * _MAX_RIDS - 1)) is not None


def test_timeline_lru_eviction_and_dispatch_ring_bound():
    obs = Observability(max_timelines=4, ring=8, clock=FakeClock())
    for rid in range(10):
        obs.request_queued(rid, 4)
    assert len(obs.requests_json(64)["requests"]) == 4
    assert obs.timeline_json("r0") is None          # evicted
    assert obs.timeline_json("r9") is not None      # newest retained
    for i in range(20):
        obs.record_dispatch(kind="decode", k=1, wall_ms=1.0)
    d = obs.dispatches_json(128)["dispatches"]
    assert len(d) == 8
    assert d[-1]["seq"] == 19  # seq is ring-global, not index
    # n <= 0 returns nothing, never the whole store ([-0:] trap).
    assert obs.dispatches_json(0)["dispatches"] == []
    assert obs.requests_json(-3)["requests"] == []


def test_timeline_eviction_prefers_terminal_over_live():
    """A long-running LIVE request must survive a burst of newer
    finished requests: terminal timelines evict first, so its
    request_end still lands (the finished counter never undercounts a
    request the server is actively serving)."""
    obs = Observability(max_timelines=4, clock=FakeClock())
    obs.request_queued(0, 4)            # the long-running stream
    obs.begin_span(0, "decoding")
    for rid in range(1, 10):            # newer, all finished
        obs.request_queued(rid, 4)
        obs.request_end(rid, "finished")
    assert obs.timeline_json("r0") is not None   # live: kept
    obs.request_end(0, "finished")
    assert obs.timeline_json("r0")["outcome"] == "finished"
    assert obs.requests_finished_total == 10
    # All-live pathology: the hard bound still holds.
    obs2 = Observability(max_timelines=3, clock=FakeClock())
    for rid in range(8):
        obs2.request_queued(rid, 4)
    assert len(obs2.requests_json(64)["requests"]) == 3


def test_slo_accounting_gauges_and_goodput():
    obs = Observability(slo_ttft_ms=100.0, slo_itl_ms=50.0,
                        clock=FakeClock())
    assert obs.slo_account(80.0, 40.0, tokens=10) is True
    assert obs.slo_account(150.0, 40.0, tokens=7) is False   # ttft miss
    assert obs.slo_account(80.0, 90.0, tokens=7) is False    # itl miss
    assert obs.slo_account(None, None, tokens=0) is False    # no token
    assert obs.slo_account(80.0, 40.0, tokens=9,
                           completed=False) is False         # failed
    m = obs.metrics()
    assert m["requests_slo_ok_total"] == 1
    assert m["goodput_tokens_total"] == 10
    # ttft passes rows 1,3 (the no-token row fails a configured TTFT);
    # itl passes rows 1,2,4 (no-token trivially passes ITL); the
    # completed=False row passes neither.
    assert m["slo_ttft_attainment"] == pytest.approx(2 / 5)
    assert m["slo_itl_attainment"] == pytest.approx(3 / 5)
    assert m["slo_attainment"] == pytest.approx(1 / 5)
    assert m["slo_ttft_ms"] == 100.0 and m["slo_itl_ms"] == 50.0


def test_slo_unconfigured_dimensions_always_pass():
    obs = Observability(clock=FakeClock())  # no SLOs set
    assert obs.slo_account(9999.0, 9999.0, tokens=5) is True
    assert obs.slo_account(None, None, tokens=3) is True
    m = obs.metrics()
    assert m["slo_attainment"] == 1.0
    assert m["goodput_tokens_total"] == 8  # == delivered tokens
    # One configured dimension scores independently of the other.
    obs2 = Observability(slo_itl_ms=50.0, clock=FakeClock())
    assert obs2.slo_account(99999.0, 10.0, tokens=1) is True
    assert obs2.slo_account(None, 90.0, tokens=1) is False


def test_request_rejected_records_terminal_timeline():
    """A pre-admission 504 (no batcher rid ever existed) still gets a
    terminal timeline under its external id and counts as failed, so
    the overload failure signals (/debug + requests_failed_total +
    SLO attainment) agree instead of contradicting."""
    obs = Observability(clock=FakeClock())
    obs.request_rejected("overload-1", "timed out before admission")
    tl = obs.timeline_json("overload-1")
    assert tl["outcome"] == "failed" and tl["rids"] == []
    assert tl["spans"][0]["state"] == "queued"
    assert tl["spans"][0]["end_ms"] is not None
    assert obs.requests_failed_total == 1
    # Id reuse keeps the existing (richer) record — but the failure
    # still COUNTS (every 504 the client saw is a failure).
    obs.request_queued(1, 4)
    obs.bind(1, "live-id")
    obs.request_rejected("live-id", "should not clobber")
    assert obs.timeline_json("live-id")["outcome"] is None
    assert obs.requests_failed_total == 2


def test_request_kv_merge_semantics_and_timeline_field():
    """Per-session KV accounting: gauge-like fields set-latest,
    ledger fields (swap bytes, evictions suffered) accumulate, and the
    merged dict rides /debug/requests/<id> as ``kv``."""
    obs = Observability(clock=FakeClock())
    obs.request_queued(1, prompt_tokens=64)
    obs.bind(1, "kv-req")
    obs.request_kv(1, blocks_held=4, prefix_hit_tokens=32)
    obs.request_kv(1, evictions_suffered=2)
    obs.request_kv(1, swap_in_bytes=1000, evictions_suffered=1)
    obs.request_kv(1, blocks_held=6)       # set-latest
    obs.request_kv(1, swap_in_bytes=500)   # accumulates
    tl = obs.timeline_json("kv-req")
    assert tl["kv"] == {
        "blocks_held": 6, "prefix_hit_tokens": 32,
        "evictions_suffered": 3, "swap_in_bytes": 1500,
    }
    # Unknown rid is a no-op, never a KeyError.
    obs.request_kv(99, blocks_held=1)
    # A timeline that never saw KV traffic exposes an empty dict.
    obs.request_queued(2, prompt_tokens=8)
    obs.bind(2, "kv-none")
    assert obs.timeline_json("kv-none")["kv"] == {}


def test_observe_kv_histograms_token_block_buckets():
    """prefix_hit_depth_tokens / session_kv_blocks are pow2 TOKEN and
    BLOCK histograms (not ms): 0-depth cold admissions land in the
    first bucket, the families render into the exposition."""
    obs = Observability(clock=FakeClock())
    obs.observe_kv(hit_depth_tokens=0)
    obs.observe_kv(hit_depth_tokens=32)
    obs.observe_kv(session_blocks=3)
    h = obs.hist["prefix_hit_depth_tokens"]
    assert h.buckets[0] == 1.0 and h.buckets[-1] == 16384.0
    assert h.count == 2
    cum = dict(h.cumulative())
    assert cum["1"] == 1 and cum["32"] == 2
    hb = obs.hist["session_kv_blocks"]
    assert hb.buckets[-1] == 1024.0 and hb.count == 1
    lines = obs.expose_histograms("llm_")
    assert any(
        ln.startswith("llm_prefix_hit_depth_tokens_bucket")
        for ln in lines
    )
    assert "llm_session_kv_blocks_count 1" in lines


def test_trace_json_kv_track():
    """KV-cache events (tier transitions, swap-ins, handoff
    export/import) render on their own named track, instant-linked to
    the owning request via their args; non-KV annotations stay on the
    dispatch track."""
    clk = FakeClock()
    obs = Observability(clock=clk)
    obs.request_queued(1, prompt_tokens=32)
    clk.advance(0.01)
    obs.annotate("kv_demote", block=3, depth=2)
    obs.annotate("fault", site="step")  # non-KV control
    obs.annotate("prefix_export", blocks=2, request_id="sess-1")
    obs.record_swap_in(12.5, blocks=2)  # emits kv_swap_in
    doc = obs.trace_json()
    names = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert "kv cache" in names
    kv_tid = next(
        e["tid"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
        and e["args"]["name"] == "kv cache"
    )
    inst = {
        e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "i"
    }
    for nm in ("kv_demote", "prefix_export", "kv_swap_in"):
        assert inst[nm]["tid"] == kv_tid, nm
    assert inst["fault"]["tid"] == 1  # non-KV stays on dispatches
    # The request link: args carry the emitter's request id.
    assert inst["prefix_export"]["args"]["request_id"] == "sess-1"
    # KV track never collides with a request track.
    req_tids = {
        e["tid"] for e in doc["traceEvents"]
        if e.get("cat") == "request"
    }
    assert kv_tid not in req_tids


def test_annotation_ring_bounded():
    obs = Observability(max_events=4, clock=FakeClock())
    for i in range(10):
        obs.annotate("fault_injected", site="step", kind="error", call=i)
    assert len(obs.events) == 4
    assert obs.events[-1]["fields"]["call"] == 9


def test_evict_locked_ring_pressure_no_orphans_and_decision_join():
    """SATELLITE PIN (ISSUE 15): timelines evicted under ring pressure
    — including LIVE ones in the pathological all-live branch — must
    leave no orphaned ``_by_rid`` entries, make every later touch of
    the evicted rid a clean no-op (no resurrection, no miscount), and
    never corrupt the decision join by request_id (the join degrades
    to decisions-only for an evicted timeline)."""
    obs = Observability(max_timelines=8, clock=FakeClock())
    # 16 LIVE timelines: the terminal-preference scan finds none, so
    # the oldest live ones go — the hard-bound branch.
    for rid in range(16):
        obs.request_queued(rid, prompt_tokens=4)
        obs.bind(rid, f"req-{rid}")
    assert len(obs._timelines) == 8
    # No orphans: every rid index entry points at a timeline that is
    # still reachable under its request_id.
    for rid, tl in obs._by_rid.items():
        assert obs._timelines.get(tl.request_id) is tl
    assert obs.timeline_json("req-0") is None     # evicted
    assert obs.timeline_json("req-15") is not None
    # A dispatch naming an evicted rid neither crashes nor resurrects
    # it; spans of retained timelines still link.
    obs.record_dispatch("decode", rids=[0, 15])
    assert 0 not in obs._by_rid
    tl15 = obs.timeline_json("req-15")
    assert tl15["spans"][0]["dispatches"], "live span keeps its link"
    # request_end on the evicted rid is a clean no-op — the finished
    # counter must not move for a request /debug can no longer name.
    fin0 = obs.requests_finished_total
    obs.request_end(0, "finished")
    assert obs.requests_finished_total == fin0
    # Decision join under eviction: decisions recorded for the evicted
    # id still answer by request_id (decisions-only degradation).
    obs.decisions.record("route", request_id="req-0", replica=1)
    joined = obs.decisions.for_request("req-0")
    assert len(joined) == 1 and joined[0]["replica"] == 1
    # Terminal preference: once terminal timelines exist they are
    # evicted FIRST, keeping every live (debuggable) one resident.
    obs.request_end(8, "finished")
    obs.request_end(9, "failed", "boom")
    for rid in range(16, 18):
        obs.request_queued(rid, prompt_tokens=4)
        obs.bind(rid, f"req-{rid}")
    assert "req-8" not in obs._timelines
    assert "req-9" not in obs._timelines
    for live in (10, 11, 17):
        assert f"req-{live}" in obs._timelines
    for rid, tl in obs._by_rid.items():
        assert obs._timelines.get(tl.request_id) is tl


def test_metric_snapshot_ring_bounded_and_stamped():
    obs = Observability(max_snapshots=4, clock=FakeClock())
    for i in range(10):
        obs.record_metrics_snapshot({"emitted_tokens_total": i})
    snaps = obs.metric_snapshots_json()
    assert len(snaps) == 4
    assert snaps[-1]["emitted_tokens_total"] == 9
    assert "t_ms" in snaps[-1] and "unix_s" in snaps[-1]


def test_structured_logger_tail_ring(capsys):
    log = StructuredLogger(quiet=True, ring=3)
    for i in range(5):
        log.log("event", index=i)
    assert capsys.readouterr().out == ""  # quiet: ring only
    tail = log.tail()
    assert len(tail) == 3 and tail[-1] == "event index=4"
    assert log.tail(1) == ["event index=4"]


# ---------------------------------------------------------------------------
# Perfetto / Chrome trace_event export schema
# ---------------------------------------------------------------------------

def test_trace_json_schema():
    clk = FakeClock()
    obs = Observability(clock=clk)
    obs.request_queued(1, 4)
    obs.bind(1, "req-a")
    clk.advance(0.020)
    obs.begin_span(1, "decoding")
    obs.record_dispatch(kind="decode", k=4, occupancy=1, wall_ms=3.0,
                        rids=[1])
    obs.annotate("quarantine_transition", feature="flash_attention",
                 state="quarantined")
    clk.advance(0.010)
    obs.request_end(1, "finished")

    doc = json.loads(json.dumps(obs.trace_json()))  # JSON round-trips
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    assert doc["displayTimeUnit"] == "ms"
    for ev in evs:
        assert ev["ph"] in ("M", "X", "i")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert "name" in ev
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 1  # us, integer-safe
        if ev["ph"] == "i":
            assert ev["s"] == "g"
    # One metadata track for dispatches, one per request.
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert "dispatches" in names and "req req-a" in names
    # Request lifecycle slices carry their dispatch links.
    req_slices = [e for e in evs if e.get("cat") == "request"]
    assert any(e["args"]["dispatches"] for e in req_slices)
    annos = [e for e in evs if e.get("cat") == "annotation"]
    assert annos and annos[0]["args"]["feature"] == "flash_attention"


def test_trace_json_window_filters_old_events():
    clk = FakeClock()
    obs = Observability(clock=clk)
    obs.record_dispatch(kind="decode", k=1, wall_ms=1.0)
    clk.advance(10.0)
    obs.record_dispatch(kind="decode", k=2, wall_ms=1.0)
    evs = obs.trace_json(window_ms=1000.0)["traceEvents"]
    dispatch = [e for e in evs if e.get("cat") == "dispatch"]
    assert len(dispatch) == 1 and dispatch[0]["args"]["seq"] == 1


# ---------------------------------------------------------------------------
# Per-kind dispatch histograms and compile attribution
# ---------------------------------------------------------------------------

def test_per_kind_dispatch_histograms_and_utilization():
    """Dispatches split into per-kind labeled dispatch_ms series; the
    record names its program and carries no cost-model field."""
    obs = Observability()
    obs.record_dispatch(kind="decode", k=4, wall_ms=10.0,
                        program="_paged_decode_chunk")
    obs.record_dispatch(kind="spec", k=2, wall_ms=5.0)
    rec = list(obs.dispatches)[0]
    assert rec["program"] == "_paged_decode_chunk"
    assert not {"flops", "bytes_accessed", "device_est_ms"} & set(rec)
    assert obs.hist_dispatch["decode"].count == 1
    assert obs.hist_dispatch["spec"].count == 1
    lines = obs.expose_histograms()
    # ONE family header, labeled series per kind.
    assert lines.count("# TYPE llm_dispatch_ms histogram") == 1
    assert any(
        ln.startswith('llm_dispatch_ms_bucket{kind="decode",le=')
        for ln in lines
    )
    assert 'llm_dispatch_ms_count{kind="spec"} 1' in lines
    # A kind serving.py does not record is refused, not minted.
    with pytest.raises(ValueError, match="unknown dispatch kind"):
        obs.record_dispatch(kind="decode:stock-paged", wall_ms=1.0)


def test_compile_recording_spans_and_counters():
    """record_compile (the jax.monitoring listener's sink) feeds the
    compile_ms histogram, the per-program counters, and a span on the
    trace's dedicated 'jit compiles' track; the trace carries the
    wall-clock anchor the fleet merge normalizes with."""
    clk = FakeClock()
    obs = Observability(clock=clk)
    clk.advance(0.100)
    obs.record_compile("_fused_chunk", 40.0)
    obs.record_compile("_fused_chunk", 10.0)
    obs.record_compile("_paged_insert", 5.0)
    assert obs.hist["compile_ms"].count == 3
    assert obs.metrics()["compiles_total"] == 3
    assert obs.compiles_by_program == {
        "_fused_chunk": 2, "_paged_insert": 1,
    }
    assert (
        "program_compiles_total", {"program": "_fused_chunk"}, 2,
    ) in obs.compile_metrics()
    doc = obs.trace_json()
    assert doc["t0_unix_s"] > 0
    compiles = [
        e for e in doc["traceEvents"] if e.get("cat") == "compile"
    ]
    assert len(compiles) == 3
    assert compiles[0]["name"] == "compile _fused_chunk"
    assert compiles[0]["tid"] == 0  # its own track
    assert compiles[0]["dur"] == 40000  # us


# ---------------------------------------------------------------------------
# Structured logging
# ---------------------------------------------------------------------------

def test_structured_logger_json_and_text(capsys):
    StructuredLogger(json_mode=True).log(
        "request_failed", "nan guard", request_id="abc", rid=3,
        skipped=None,
    )
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["event"] == "request_failed"
    assert rec["message"] == "nan guard"
    assert rec["request_id"] == "abc" and rec["rid"] == 3
    assert "skipped" not in rec and "ts" in rec
    StructuredLogger(json_mode=False).log(
        "serving", address="http://x", endpoints="a, b"
    )
    line = capsys.readouterr().out.strip()
    assert line.startswith("serving ") and "address=http://x" in line


# ---------------------------------------------------------------------------
# Integration: the real serving loop's timelines (tiny model, CPU)
# ---------------------------------------------------------------------------

def _timeline(cb, rid):
    tl = cb.obs.timeline_json(str(rid))
    assert tl is not None, f"no timeline for rid {rid}"
    return tl


def _assert_links_resolve(cb, tl):
    """Every span's dispatch links resolve to real records of the
    global ring, and each linked record lists this request's rid."""
    ring = {d["seq"]: d for d in cb.obs.dispatches_json(4096)["dispatches"]}
    rids = set(tl["rids"])
    linked = [s for sp in tl["spans"] for s in sp["dispatches"]]
    assert linked, "expected at least one dispatch link"
    for seq in linked:
        assert seq in ring, f"span links dispatch {seq} not in ring"
        assert rids & set(ring[seq]["rids"])


def test_classic_admission_span_lifecycle(model):
    """prefill_budget=0: whole-prompt insert admission.  Timeline is
    queued -> prefilling -> decoding -> finished, the prefilling span
    links the classic ``insert`` dispatch, decoding links decode
    chunks."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           decode_chunk=4, prefill_budget=0)
    rid = cb.submit([5, 6, 7, 8], max_new_tokens=8)
    cb.run_to_completion()
    tl = _timeline(cb, rid)
    assert [sp["state"] for sp in tl["spans"]] == [
        "queued", "prefilling", "decoding",
    ]
    assert tl["outcome"] == "finished"
    _assert_links_resolve(cb, tl)
    kinds = {d["kind"] for d in tl["dispatch_spans"]}
    assert "insert" in kinds and "decode" in kinds
    ins = [d for d in tl["dispatch_spans"] if d["kind"] == "insert"][0]
    assert ins["prefill_tokens"] == 4
    assert sum(
        h.count for h in cb.obs.hist_dispatch.values()
    ) >= len(tl["dispatch_spans"])


def test_fused_admission_span_lifecycle(model):
    """A warm-pool admission rides the fused prefill lane: its
    prefilling span links prefill-carrying chunk dispatches (kind
    ``fused``, prefill_tokens > 0) and decode rows kept emitting."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           decode_chunk=4, prefill_budget=32)
    cb.submit(list(np.random.RandomState(0).randint(1, 128, 9)),
              max_new_tokens=60)
    for _ in range(4):
        cb.step()  # get row 0 into steady decode
    rid = cb.submit(list(np.random.RandomState(1).randint(1, 128, 40)),
                    max_new_tokens=4)
    cb.run_to_completion()
    tl = _timeline(cb, rid)
    states = [sp["state"] for sp in tl["spans"]]
    assert states == ["queued", "prefilling", "decoding"]
    assert tl["outcome"] == "finished"
    _assert_links_resolve(cb, tl)
    pf_span = tl["spans"][1]
    fused = [
        d for d in tl["dispatch_spans"]
        if d["seq"] in pf_span["dispatches"]
    ]
    assert fused and all(d["prefill_tokens"] > 0 for d in fused)
    assert any(d["kind"] == "fused" for d in fused)
    # The fused dispatches carried decode rows too (occupancy >= 2).
    assert all(d["occupancy"] >= 2 for d in fused)


# slow (r17 budget rebalance, ~10 s): the span/dispatch-link contract
# stays tier-1-pinned by the classic and fused lifecycle drills above,
# and the spec path's observability surface stays tier-1-pinned by
# test_perf_smoke.py::test_spec_metrics_surface (gauges) and
# test_spec_steady_state_host_sync_discipline (per-dispatch counters);
# the spec span drill rides slow (unfiltered suite runs it).
@pytest.mark.slow
def test_spec_admission_span_lifecycle(model):
    """Speculative serving records ``spec`` dispatch spans; the
    request's decoding span links them."""
    params, config = model
    draft_config = get_config(
        "tiny", **{**CFG, "dim": 32, "n_layers": 1, "n_heads": 2,
                   "n_kv_heads": 1}
    )
    draft_params = init_params(jax.random.PRNGKey(1), draft_config)
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64,
                           draft_params=draft_params,
                           draft_config=draft_config,
                           n_draft=2, spec_rounds=4)
    rid = cb.submit([4, 5, 6], max_new_tokens=10)
    cb.run_to_completion()
    tl = _timeline(cb, rid)
    assert tl["outcome"] == "finished"
    assert [sp["state"] for sp in tl["spans"]] == [
        "queued", "prefilling", "decoding",
    ]
    _assert_links_resolve(cb, tl)
    dec = tl["spans"][2]
    spec = [
        d for d in tl["dispatch_spans"]
        if d["seq"] in dec["dispatches"]
    ]
    assert spec and all(d["kind"] == "spec" for d in spec)


@pytest.mark.parametrize("budget", [32, 0], ids=["fused", "classic"])
def test_batcher_marks_the_scheduler_phases(model, budget):
    """Driven without a server, the batcher's own phases tile every
    gap between its dispatch records (real clock): ``admit``, ``prep``
    and ``emit`` are in every steady record, and the same phases are
    marked whether a prompt rides the fused lane or a classic insert."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           decode_chunk=4, prefill_budget=budget)
    cb.submit(list(np.random.RandomState(0).randint(1, 128, 9)),
              max_new_tokens=40)
    for _ in range(3):
        cb.step()
    cb.submit(list(np.random.RandomState(1).randint(1, 128, 40)),
              max_new_tokens=6)
    cb.run_to_completion()
    recs = cb.obs.dispatches_json(512)["dispatches"]
    _assert_tiles(recs)
    kinds = {d["kind"] for d in recs}
    assert ("fused" in kinds) == bool(budget) and "insert" in kinds
    steady = [  # a chunk behind a chunk: emit, admit, prep, dispatch
        b for a, b in zip(recs, recs[1:])
        if {a["kind"], b["kind"]} <= {"decode", "fused"}
    ]
    assert steady
    for d in steady:
        assert {"admit", "prep", "emit"} <= set(d["host_ms"]), d
        assert "gap_cpu_ms" in d and "compiles" in d
    # No server drove this batcher: none of its phases appear.
    assert not {"control", "intake", "idle", "deliver"} & {
        p for d in recs[1:] for p in d["host_ms"]
    }
    # A chunk right behind a classic insert pays the error barrier.
    after_insert = [
        b for a, b in zip(recs, recs[1:])
        if a["kind"] == "insert" and b["kind"] == "decode"
    ]
    assert all("barrier" in d["host_ms"] for d in after_insert)


def test_profiler_capture_holds_the_loop_on_the_trace_clock(model, tmp_path):
    """With a jax.profiler session open, every phase and dispatch is a
    host event of the capture: ``llm.dispatch`` carries the ring
    number of its record (joined by identity, not by time) and the
    ``llm.loop.*`` events lie between the dispatch events."""
    from jax.profiler import ProfileData

    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           decode_chunk=4, prefill_budget=32)
    cb.submit([5, 6, 7, 8, 9], max_new_tokens=40)
    for _ in range(3):
        cb.step()  # compiled and steady before the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        first = cb.obs.dispatches[-1]["seq"] + 1
        for _ in range(4):
            cb.step()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    events = [
        e for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("llm.")
    ]
    disp = sorted(
        (e for e in events if e.name == "llm.dispatch"),
        key=lambda e: e.start_ns,
    )
    ring = {d["seq"]: d for d in cb.obs.dispatches}
    assert [dict(e.stats)["seq"] for e in disp] == list(
        range(first, first + 4)
    )
    for e in disp:
        st = dict(e.stats)
        rec = ring[st["seq"]]
        assert (st["kind"], st["program"], st["k"]) == (
            rec["kind"], rec["program"], rec["k"]
        )
        # One clock for both: the event is as long as its record.
        assert e.duration_ns / 1e6 == pytest.approx(rec["wall_ms"], abs=1.0)
    phases = [e for e in events if e.name.startswith("llm.loop.")]
    assert {e.name for e in phases} >= {
        "llm.loop.admit", "llm.loop.prep", "llm.loop.emit",
    }
    for e in phases:
        mid = e.start_ns + e.duration_ns / 2
        assert not any(
            d.start_ns < mid < d.start_ns + d.duration_ns for d in disp
        )
    # The child spans: ``llm.span.<name>``, never ``llm.loop.``, each
    # inside its phase's or its dispatch's event, with the ring number of
    # the record it led to.
    spans = [e for e in events if e.name.startswith("llm.span.")]
    assert {e.name for e in spans} == {
        "llm.span.dispatch.submit", "llm.span.emit.replay",
    }
    assert {e.name for e in phases} <= {
        "llm.loop." + p for p in LOOP_PHASES}
    for e in spans:
        outer = disp if "dispatch" in e.name else [
            p for p in phases if p.name == "llm.loop.emit"]
        assert any(
            o.start_ns <= e.start_ns and e.start_ns + e.duration_ns
            <= o.start_ns + o.duration_ns for o in outer
        ), e.name
        assert dict(e.stats)["seq"] in ring or (
            dict(e.stats)["seq"] == first + 4)  # the last emit's


def test_a_served_workload_yields_every_span_with_its_request_and_record():
    """Recurrent state layers over a pool of 18 blocks: an insert on the
    idle server, a 105-token document through the fused lane (snapshots,
    publish), a re-ask that hits its prefix, and a last prompt that finds
    the free list dry and evicts.  Every span but ``admit.restore`` (the
    host tier, which this block refuses: the restoring drill below holds
    it) closes at least once; an admission's spans carry its rid and the
    ring number of the record they led to, and every span's time is part
    of its parent's."""
    import json as _json
    from pathlib import Path

    import jax_llama_tpu as jlt
    from jax_llama_tpu import config as config_mod

    raw = _json.loads((
        Path(__file__).resolve().parent.parent / "benchmark" / "configs"
        / "Phi-4-mini-flash-reasoning.json"
    ).read_text())
    raw.update(
        hidden_size=64, intermediate_size=128, num_attention_heads=8,
        num_key_value_heads=4, num_hidden_layers=8, vocab_size=512,
        sliding_window=24, torch_dtype="float32",
    )
    bookkeeping = ("source", "architecture", "reference", "reduced",
                   "assumed", "deployment")
    cfg = config_mod.from_published(
        {k: v for k, v in raw.items() if k not in bookkeeping},
        max_seq_len=256, attn_impl="auto")
    params = jlt.init_params(jax.random.PRNGKey(3), cfg)
    cb = ContinuousBatcher(
        params, cfg, n_slots=3, block_size=BS, n_blocks=18,
        decode_chunk=4, prefill_budget=32)
    rng = np.random.RandomState(4)
    draw = lambda n: [int(t) for t in rng.randint(0, 512, size=n)]  # noqa: E731
    doc = draw(100)

    def steps(n):
        for _ in range(n):
            cb.step()

    cb.submit(draw(20), max_new_tokens=120)     # the holder: an insert
    steps(3)
    first = cb.submit(doc + draw(5), max_new_tokens=4)   # 7 blocks, fused
    while any(s is not None and s.request_id == first
              for s in cb.slots.values()) or cb.queue:
        steps(1)
    hit = cb.submit(doc + draw(9), max_new_tokens=4)     # hits 96 tokens
    steps(8)
    assert cb.obs.timeline_json(hit)["kv"]["prefix_hit_tokens"] == 96
    cold = cb.submit(draw(100), max_new_tokens=4)        # evicts the doc
    cb.run_to_completion()

    recs = {d["seq"]: d for d in cb.obs.dispatches}
    _assert_tiles(list(recs.values()))
    spans = cb.obs.loop_spans_json()
    names = {s["name"] for s in spans}
    assert names == set(LOOP_SPANS) - {"admit.restore"}, (
        set(LOOP_SPANS) - names)
    assert {n for d in recs.values() for n in d["span_ms"]} == names
    for sp in spans:
        assert sp["parent"] == LOOP_SPANS[sp["name"]]
        if sp["seq"] > max(recs):   # the last emit leads to no record
            assert sp["name"] in ("emit.replay", "emit.free")
            continue
        rec = recs[sp["seq"]]       # the record its gap led to
        assert sp["name"] in rec["span_ms"]
        assert sp["end_ms"] <= rec["start_ms"] + rec["wall_ms"] + 0.01
    for d in recs.values():
        for name, ms in d["span_ms"].items():
            if "host_ms" in d or name.startswith("dispatch."):
                assert ms <= _parent_ms(d, name) + 0.01, (name, d)
        if d["kind"] in ("decode", "fused", "insert"):
            assert 0.0 < d["submit_ms"] <= d["wall_ms"]
    # The admissions: each with its rid, on the record it led to.
    for rid, kind in ((first, "fused"), (hit, "fused"), (cold, "fused")):
        own = cb.obs.timeline_json(rid)["loop_spans"]
        assert {s["rid"] for s in own} == {rid}
        by_name = {s["name"]: s for s in own}
        assert {"admit.hash", "admit.match", "admit.alloc", "admit.upload",
                "prep.snapshots", "dispatch.publish"} <= set(by_name), rid
        led_to = recs[by_name["admit.upload"]["seq"]]
        assert led_to["kind"] == kind and rid in led_to["rids"]
        assert by_name["admit.hash"]["seq"] == led_to["seq"]
    assert "admit.evict" in {
        s["name"] for s in cb.obs.timeline_json(cold)["loop_spans"]}
    assert not [s for s in cb.obs.timeline_json(hit)["loop_spans"]
                if s["name"] == "admit.evict"]
    assert "admit.insert" in recs[min(recs)]["span_ms"]   # the holder's
    assert cb.stats()["host_syncs_per_token"] < 1


def test_failed_request_timeline_records_error(model):
    """cancel() closes the timeline as cancelled; the non-finite path
    is covered by the faults suite — here we pin the terminal record."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    rid = cb.submit([4, 5, 6], max_new_tokens=40)
    cb.step()
    assert cb.cancel(rid)
    tl = _timeline(cb, rid)
    assert tl["outcome"] == "cancelled"
    assert tl["spans"][-1]["end_ms"] is not None
    # The server's deadline reaper passes outcome="failed" so timeouts
    # count under requests_failed_total, never as cancellations.
    rid2 = cb.submit([7, 8, 9], max_new_tokens=40)
    cb.step()
    assert cb.cancel(rid2, outcome="failed", error="generation timed out")
    tl2 = _timeline(cb, rid2)
    assert tl2["outcome"] == "failed"
    assert tl2["error"] == "generation timed out"
    assert cb.obs.requests_failed_total == 1
    assert cb.obs.requests_cancelled_total == 1


def test_restoring_fused_admission_full_timeline(model):
    """THE acceptance-criterion drill: a session whose radix prefix was
    demoted to the host tier comes back while another row decodes — it
    admits through restoring (async swap-in overlapped on the decode
    chunk) and then the FUSED prefill lane.  Its timeline holds all
    four lifecycle states, every span links real dispatch spans (the
    restoring span links the ``adopt`` scatter), the swap-in histogram
    saw the restore, and the whole window exports as Perfetto-loadable
    trace_event JSON."""
    params, config = model
    rng = np.random.RandomState(41)
    session = rng.randint(1, 128, size=40).tolist()
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, block_size=BS,
        n_blocks=8, prefix_cache=True, host_kv_blocks=4,
        decode_chunk=4, prefill_budget=32,
    )
    # Seed the session chain (2 keyed blocks), then demote it to the
    # host tier explicitly.
    cb.submit(list(session), max_new_tokens=4)
    cb.run_to_completion()
    assert cb.demote_idle(2) == 2
    assert cb.stats()["host_tier_blocks"] == 2
    # A long-running decode occupies a row, so the session's revisit
    # must overlap its swap-in with live decode chunks and admit fused.
    # Geometry: the filler reserves 4 of 8 blocks (9+40 -> 64 padded),
    # leaving 4 free — enough for the 2-block restore staging plus the
    # session's suffix, so the swap really does fly WHILE the filler
    # decodes (a bigger filler would starve the restore of fresh
    # blocks and the session would fall back to a cold-pool suffix
    # admission after the filler finished).
    cb.submit(rng.randint(1, 128, size=9).tolist(), max_new_tokens=40)
    for _ in range(4):
        cb.step()
    cb.swap_poll_min = 2  # hold the restore window open >= 2 polls
    rid = cb.submit(list(session), max_new_tokens=4)
    saw_restoring = False
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 300
        cb.step()
        saw_restoring = saw_restoring or bool(cb._restoring)
    assert saw_restoring
    st = cb.stats()
    assert st["swap_ins_total"] == 1
    # The swap-in's polling and its admission are the admit.restore span
    # (the one span the recurrent workload above cannot reach).
    restores = [s for s in cb.obs.loop_spans_json()
                if s["name"] == "admit.restore"]
    assert restores and {s["parent"] for s in restores} == {"admit"}

    tl = _timeline(cb, rid)
    assert tl["outcome"] == "finished"
    states = [sp["state"] for sp in tl["spans"]]
    # queued -> restoring -> queued(restored) -> prefilling -> decoding
    assert set(states) >= {"queued", "restoring", "prefilling",
                           "decoding"}
    assert states[0] == "queued" and states[1] == "restoring"
    assert states[-1] == "decoding"
    restored = tl["spans"][2]
    assert restored["state"] == "queued" and restored["note"] == "restored"
    _assert_links_resolve(cb, tl)
    # The restoring span links the adoption scatter dispatch.
    rest_span = tl["spans"][1]
    adopt = [
        d for d in tl["dispatch_spans"]
        if d["seq"] in rest_span["dispatches"]
    ]
    assert adopt and adopt[-1]["kind"] == "adopt"
    # The fused prefill rode dispatches that also carried the decode row.
    pf_span = tl["spans"][states.index("prefilling")]
    carried = [
        d for d in tl["dispatch_spans"]
        if d["seq"] in pf_span["dispatches"]
    ]
    assert carried and all(d["occupancy"] >= 2 for d in carried)
    # Swap-in latency landed in its histogram + the annotation ring.
    assert cb.obs.hist["swap_in_ms"].count == 1
    assert any(e["name"] == "kv_swap_in" for e in cb.obs.events)
    assert any(e["name"] == "kv_demote" for e in cb.obs.events)
    # The serving window exports as valid trace_event JSON.
    doc = json.loads(json.dumps(cb.obs.trace_json()))
    evs = doc["traceEvents"]
    assert any(
        e.get("cat") == "request" and e["name"] == "restoring"
        for e in evs
    )
    assert any(
        e.get("cat") == "dispatch" and e["name"].startswith("adopt")
        for e in evs
    )


def test_obs_survives_rebuild_one_continuous_trace(model):
    """rebuild() (the crash-recovery primitive) reuses the SAME
    Observability via the captured ctor kwargs: timelines and dispatch
    seqs continue instead of resetting."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    cb.submit([4, 5, 6], max_new_tokens=4)
    cb.run_to_completion()
    seq_before = cb.obs._seq
    cb2 = cb.rebuild()
    assert cb2.obs is cb.obs
    rid = cb2.submit([7, 8, 9], max_new_tokens=4)
    cb2.run_to_completion()
    tl = cb2.obs.timeline_json(str(rid))
    assert tl["outcome"] == "finished"
    assert min(
        s for sp in tl["spans"] for s in sp["dispatches"]
    ) >= seq_before


def test_fault_injection_annotated_in_trace(model):
    """An injected fault lands as an instant event in the annotation
    ring (the batcher wires injector.trace_sink at construction), so a
    chaos drill's fault is explainable next to the dispatch spans it
    killed."""
    from jax_llama_tpu.faults import FaultInjector, InjectedFault

    params, config = model
    inj = FaultInjector("step@1:error")
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64,
                           fault_injector=inj)
    cb.submit([4, 5, 6], max_new_tokens=8)
    with pytest.raises(InjectedFault):
        for _ in range(8):
            cb.step()
    faults = [e for e in cb.obs.events if e["name"] == "fault_injected"]
    assert faults and faults[0]["fields"]["site"] == "step"
    assert faults[0]["fields"]["kind"] == "error"
