"""Overload control (overload.py + the server wiring):

  * the brownout ladder escalates one rung at a time under sustained
    SLO pressure (dwell), recovers one rung at a time after calm
    (cooldown), and holds inside the hysteresis band;
  * admission is deadline-aware (a request whose timeout_s provably
    cannot be met is refused 503 with a load-derived Retry-After) and
    class-aware (strict interactive-first ordering; batch suspended at
    brownout-2, queued batch shed at 'shed' — cleanly, never a hang);
  * the flood drill: an open-loop Poisson mixed-class flood leaves
    zero hung clients, every 503 carries Retry-After, and the ladder
    steps back down to normal after the flood;
  * controller state (rung, knobs) survives crash-recovery rebuilds.

The ladder/admission units drive an injected clock — no sleeping; the
server drills use the same tiny CPU model as test_server.py.
"""

import json
import threading
import time
import types
import urllib.error
import urllib.request

import jax
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.faults import FaultInjector
from jax_llama_tpu.overload import (
    OverloadController,
    open_loop_flood,
    poisson_schedule,
    summarize_flood,
)
from jax_llama_tpu.server import LLMServer
from jax_llama_tpu.serving import ContinuousBatcher

pytestmark = pytest.mark.overload

CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=256, dtype="float32",
    param_dtype="float32",
)


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _controller(clock, **kw):
    kw.setdefault("dwell_s", 1.0)
    kw.setdefault("cooldown_s", 2.0)
    kw.setdefault("signal_window_s", 5.0)
    kw.setdefault("min_signal_samples", 2)
    return OverloadController(clock=clock, **kw)


def _miss(c, n=4):
    for _ in range(n):
        c.note_slo("interactive", False, True, False)


def _entry(priority="interactive", cost=10, deadline=None,
           disconnected=False):
    return types.SimpleNamespace(
        priority=priority, cost_tokens=cost, deadline=deadline,
        disconnected=disconnected,
    )


# ---------------------------------------------------------------------------
# Ladder state machine (injected clock, no server)
# ---------------------------------------------------------------------------

def test_ladder_escalates_with_dwell_and_one_rung_at_a_time():
    clock = Clock()
    c = _controller(clock)
    _miss(c)
    # Pressure just started: the dwell must elapse first.
    assert c.tick() is None
    assert c.rung == "normal"
    clock.advance(0.5)
    _miss(c)
    assert c.tick() is None  # 0.5s < dwell_s=1
    clock.advance(0.6)
    _miss(c)
    assert c.tick() == ("normal", "elevated")
    # The dwell re-arms after each transition — no straight-to-shed.
    assert c.tick() is None
    for expect in ("brownout-1", "brownout-2", "shed"):
        clock.advance(1.1)
        _miss(c)
        old, new = c.tick()
        assert new == expect
    # Top rung: sustained pressure holds, never overflows.
    clock.advance(1.1)
    _miss(c)
    assert c.tick() is None
    assert c.rung == "shed"


def test_ladder_recovers_after_cooldown_and_reports_knobs():
    clock = Clock()
    c = _controller(clock, batch_max_new=64, demote_blocks=8)
    c.force_rung("shed")
    kn = c.knobs()
    assert kn.shed_batch and not kn.admit_batch
    assert kn.prefill_budget_scale == 0.25
    assert kn.batch_max_new_cap == 16  # 64 halved twice past brownout-1
    # Old misses age out of the signal window -> calm; each recovery
    # step needs its own cooldown (hysteresis in time).
    _miss(c)
    clock.advance(6.0)  # > signal_window_s: samples gone
    assert c.tick() is None  # calm begins; cooldown not yet elapsed
    for expect in ("brownout-2", "brownout-1", "elevated", "normal"):
        clock.advance(2.1)
        old, new = c.tick()
        assert new == expect
    clock.advance(2.1)
    assert c.tick() is None  # at normal: nothing below to step to
    assert c.knobs().prefill_budget_scale == 1.0
    assert c.transitions_total == 4


def test_ladder_hysteresis_band_holds_the_rung():
    clock = Clock()
    c = _controller(clock, enter_attainment=0.80, exit_attainment=0.95)
    c.force_rung("elevated")
    # Attainment 0.9: above enter (no pressure), below exit (not
    # calm) — the band.  The rung must hold however long it lasts.
    for _ in range(20):
        for _ in range(9):
            c.note_slo("interactive", True, True, True)
        c.note_slo("interactive", False, True, False)
        clock.advance(3.0)
        assert c.tick() is None
    assert c.rung == "elevated"


def test_ladder_queue_wait_pressure_escalates():
    clock = Clock()
    c = _controller(clock, queue_wait_ms=100.0)
    for _ in range(4):
        c.observe_queue_wait(500.0)  # p90 far above the bar
    assert c.tick() is None  # pressure starts; dwell not yet elapsed
    clock.advance(1.1)
    for _ in range(4):
        c.observe_queue_wait(500.0)
    assert c.tick() == ("normal", "elevated")


def test_bad_hysteresis_config_refused():
    with pytest.raises(ValueError):
        OverloadController(enter_attainment=0.9, exit_attainment=0.8)


# ---------------------------------------------------------------------------
# Admission: deadline proof, backlog backstop, class gate
# ---------------------------------------------------------------------------

def test_admission_deadline_refusal_needs_evidence():
    clock = Clock()
    c = _controller(clock, max_queue=100)
    # No throughput evidence: a refusal must be provable, never
    # guessed — everything admits.
    assert c.admit("interactive", 10**6, 0.001, depth=0) is None
    # The admitted request lands in a queue and is then submitted
    # (push + pop release its backlog footprint, as the loop would).
    c.push(_entry("interactive", cost=10**6))
    assert c.pop() is not None
    # 1000 tokens/s observed prefill throughput.
    c.on_dispatch({"kind": "fused", "prefill_tokens": 1000,
                   "wall_ms": 1000.0, "k": 1, "occupancy": 1})
    r = c.admit("interactive", 10_000, 5.0, depth=0)
    assert r is not None and r.kind == "deadline"
    assert r.retry_after_s >= 1
    assert "timeout_s" in r.reason
    # The same prompt with a meetable deadline admits.
    assert c.admit("interactive", 10_000, 20.0, depth=0) is None
    # No timeout_s -> no deadline to prove against.
    assert c.admit("interactive", 10**6, None, depth=0) is None
    assert c.refused_deadline_total == 1


def test_admission_deadline_sees_inflight_admissions():
    """Admitted requests still in transit through the server inbox
    (admit() ran, the loop has not yet drained them into a class
    queue) must count toward the next request's backlog estimate —
    a one-dispatch-long burst is exactly the overload window."""
    c = _controller(Clock())
    c.on_dispatch({"kind": "fused", "prefill_tokens": 1000,
                   "wall_ms": 1000.0, "k": 1, "occupancy": 1})
    for _ in range(5):
        assert c.admit("interactive", 2000, 60.0, depth=0) is None
    # The sixth sees the burst's 10k in-flight tokens: est ~12 s.
    r = c.admit("interactive", 2000, 5.0, depth=0)
    assert r is not None and r.kind == "deadline"
    # Draining the inbox into the queues releases the reservations
    # (the tokens move to the queued footprint, then pop clears it).
    for _ in range(5):
        c.push(_entry("interactive", cost=2000))
    while c.pop() is not None:
        pass
    assert c.admit("interactive", 2000, 5.0, depth=0) is None


def test_admission_deadline_counts_backlog_ahead():
    clock = Clock()
    c = _controller(clock)
    c.on_dispatch({"kind": "fused", "prefill_tokens": 1000,
                   "wall_ms": 1000.0, "k": 1, "occupancy": 1})
    # 4000 interactive tokens queued ahead: a batch request sees them
    # all; its own 100 tokens alone would be fine.
    for _ in range(4):
        c.push(_entry("interactive", cost=1000))
    assert c.admit("batch", 100, 2.0, depth=4) is not None
    assert c.admit("batch", 100, 10.0, depth=4) is None
    c.push(_entry("batch", cost=100))  # the admitted batch request
    # Interactive-first ordering means interactive backlog only sees
    # the interactive queue — batch tokens ahead are irrelevant to it.
    c.push(_entry("batch", cost=50_000))
    assert c.admit("interactive", 100, 6.0, depth=6) is None


def test_admission_backlog_backstop_applies_even_when_disabled():
    c = OverloadController(enabled=False, max_queue=4)
    r = c.admit("interactive", 1, None, depth=4)
    assert r is not None and r.kind == "backlog"
    assert r.retry_after_s >= 1
    assert "overloaded" in r.reason
    # Disabled controller: no ladder, no deadline proof.
    assert c.tick() is None
    assert c.admit("batch", 10**6, 0.001, depth=0) is None


def test_admission_class_gate_at_brownout_2():
    clock = Clock()
    c = _controller(clock)
    c.force_rung("brownout-2")
    r = c.admit("batch", 10, None, depth=0)
    assert r is not None and r.kind == "class"
    # Interactive is the protected class — admitted at every rung.
    c.force_rung("shed")
    assert c.admit("interactive", 10, None, depth=0) is None
    assert c.refused_batch_total == 1


def test_retry_after_is_load_derived():
    clock = Clock()
    c = _controller(clock)
    c.on_dispatch({"kind": "insert", "prefill_tokens": 1000,
                   "wall_ms": 1000.0, "k": 1, "occupancy": 1})
    for _ in range(10):
        c.push(_entry("batch", cost=1000))
    # 10k tokens of backlog at 1k tokens/s -> ~10s (+1 rounding).
    assert 10 <= c.retry_after_s() <= 12
    # And it caps at 60 however deep the backlog.
    for _ in range(100):
        c.push(_entry("batch", cost=10_000))
    assert c.retry_after_s() == 60


# ---------------------------------------------------------------------------
# Queues: ordering, shedding, reaping
# ---------------------------------------------------------------------------

def test_disabled_controller_is_plain_fifo():
    """priority_classes=off must be the genuinely pre-ladder behavior:
    one queue, arrival order — not interactive-first in disguise (the
    bench harness's static A/B arm depends on this)."""
    c = OverloadController(enabled=False, max_queue=100)
    b1, i1, b2 = _entry("batch"), _entry("interactive"), _entry("batch")
    for e in (b1, i1, b2):
        c.push(e)
    assert [c.pop() for _ in range(3)] == [b1, i1, b2]


def test_queue_strict_interactive_first_fifo_within_class():
    c = _controller(Clock())
    b1, b2 = _entry("batch"), _entry("batch")
    i1, i2 = _entry("interactive"), _entry("interactive")
    for e in (b1, b2, i1, b_last := _entry("batch"), i2):
        c.push(e)
    assert [c.pop() for _ in range(5)] == [i1, i2, b1, b2, b_last]
    assert c.pop() is None


def test_shed_batch_only_at_shed_rung_and_only_batch():
    c = _controller(Clock())
    b1, b2, i1 = _entry("batch"), _entry("batch"), _entry("interactive")
    for e in (b1, i1, b2):
        c.push(e)
    assert c.shed_batch() == []  # normal rung: nothing shed
    c.force_rung("shed")
    assert c.shed_batch() == [b1, b2]
    assert c.sheds_total == 2
    assert c.pop() is i1  # interactive untouched
    assert c.queued_total() == 0


def test_reap_pulls_expired_and_disconnected():
    clock = Clock(100.0)
    c = _controller(clock)
    live = _entry("interactive", deadline=200.0)
    dead = _entry("interactive", deadline=99.0)
    gone = _entry("batch", disconnected=True)
    for e in (live, dead, gone):
        c.push(e)
    expired, disconnected = c.reap()
    assert expired == [dead] and disconnected == [gone]
    assert c.pop() is live and c.queued_total() == 0


def test_drain_all_empties_every_class():
    c = _controller(Clock())
    entries = [_entry("batch"), _entry("interactive"), _entry("batch")]
    for e in entries:
        c.push(e)
    assert set(map(id, c.drain_all())) == set(map(id, entries))
    assert c.queued_total() == 0


# ---------------------------------------------------------------------------
# Poisson schedule
# ---------------------------------------------------------------------------

def test_poisson_schedule_rate_and_determinism():
    a = poisson_schedule(100.0, 10.0, seed=7)
    b = poisson_schedule(100.0, 10.0, seed=7)
    assert a == b  # seeded -> reproducible sweeps
    assert a == sorted(a) and all(0 <= t < 10.0 for t in a)
    # ~1000 arrivals, 4 sigma tolerance (sigma = sqrt(1000) ~ 32).
    assert 870 <= len(a) <= 1130
    assert poisson_schedule(0.0, 10.0) == []
    assert poisson_schedule(10.0, 0.0) == []


# ---------------------------------------------------------------------------
# Server integration (tiny CPU model)
# ---------------------------------------------------------------------------

def _post(url, payload, timeout=300):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read()), dict(r.headers)


def test_http_priority_validation_and_batch_cap(model):
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    with LLMServer(cb, brownout_batch_max_new=4) as srv:
        # Junk priority is the client's defect: 400, not a silent
        # default.
        for junk in ("urgent", 3, [], {"a": 1}):
            try:
                _post(srv.address, {"prompt": [1, 2], "priority": junk})
                assert False, f"expected 400 for priority={junk!r}"
            except urllib.error.HTTPError as e:
                assert e.code == 400
                assert "priority" in json.loads(e.read())["error"]
        # Valid classes admit; at brownout-1 the batch budget clamps
        # to the cap while interactive is untouched.
        srv.overload.force_rung("brownout-1")
        s, body, _ = _post(
            srv.address,
            {"prompt": [1, 2, 3], "max_new_tokens": 10,
             "priority": "batch"},
        )
        assert s == 200 and len(body["tokens"]) == 4  # capped
        s, body, _ = _post(
            srv.address,
            {"prompt": [1, 2, 3], "max_new_tokens": 10,
             "priority": "interactive"},
        )
        assert s == 200 and len(body["tokens"]) == 10
        srv.overload.force_rung("normal")


def test_http_batch_refused_at_brownout_2_with_retry_after(model):
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    with LLMServer(cb) as srv:
        srv.overload.force_rung("brownout-2")
        try:
            _post(srv.address,
                  {"prompt": [1, 2], "max_new_tokens": 2,
                   "priority": "batch"})
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert int(e.headers["Retry-After"]) >= 1
            assert "batch" in json.loads(e.read())["error"]
        # Interactive still served at the same rung.
        s, body, _ = _post(
            srv.address,
            {"prompt": [1, 2], "max_new_tokens": 2,
             "priority": "interactive"},
        )
        assert s == 200 and len(body["tokens"]) == 2
        srv.overload.force_rung("normal")


def test_http_priority_inversion_interactive_admits_first(model):
    """A full batch backlog is queued behind a busy slot; a later
    interactive request must be admitted (and finish) ahead of it."""
    params, config = model
    # A 20 ms injected delay per step dispatch pins the resident in
    # its slot for ~2 s — the tiny model alone decodes too fast to
    # sequence the queue deterministically.
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=256,
        fault_injector=FaultInjector("step~1.0:delay=0.02"),
    )
    with LLMServer(cb) as srv:
        # Warm the compile caches so queue residency, not compilation,
        # dominates the timeline below.
        _post(srv.address, {"prompt": [9, 9], "max_new_tokens": 2})

        done_at = {}
        threads = []

        def call(name, payload):
            def run():
                _post(srv.address, payload, timeout=300)
                done_at[name] = time.monotonic()
            t = threading.Thread(target=run)
            t.start()
            threads.append(t)

        # Occupy the single slot long enough to stack the queue.
        call("resident", {"prompt": [3, 4], "max_new_tokens": 100})
        time.sleep(0.4)  # resident admitted, slot busy
        for j in range(3):
            call(f"batch{j}", {"prompt": [5 + j, 6], "max_new_tokens": 2,
                               "priority": "batch"})
        time.sleep(0.2)  # batch backlog queued (free slots = 0)
        call("inter", {"prompt": [8, 8], "max_new_tokens": 2,
                       "priority": "interactive"})
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        assert done_at["inter"] < min(
            done_at[f"batch{j}"] for j in range(3)
        ), f"interactive finished after batch backlog: {done_at}"


def test_http_queued_batch_shed_cleanly_with_retry_after(model):
    """A batch request already queued behind a busy slot is shed when
    the ladder reaches 'shed': a clean 503 + Retry-After, never a
    hang — including for a STREAMING client, which gets a real 503
    status because no token ever flowed."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=256,
        fault_injector=FaultInjector("step~1.0:delay=0.02"),
    )
    with LLMServer(cb) as srv:
        _post(srv.address, {"prompt": [9, 9], "max_new_tokens": 2})
        results = {}
        threads = []

        def call(name, payload):
            def run():
                try:
                    results[name] = _post(srv.address, payload,
                                          timeout=120)
                except urllib.error.HTTPError as e:
                    results[name] = (
                        e.code, json.loads(e.read()), dict(e.headers)
                    )
                except Exception as e:  # surface in the assert below
                    results[name] = (-1, {"error": repr(e)}, {})
            t = threading.Thread(target=run)
            t.start()
            threads.append(t)

        call("resident", {"prompt": [3, 4], "max_new_tokens": 100})
        time.sleep(0.4)
        call("blocking", {"prompt": [5, 6], "max_new_tokens": 2,
                          "priority": "batch"})
        call("streaming", {"prompt": [6, 7], "max_new_tokens": 2,
                           "priority": "batch", "stream": True})
        time.sleep(0.3)  # both queued (slot busy)
        srv.overload.force_rung("shed")
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)  # nobody hangs
        assert results["resident"][0] == 200  # in-flight untouched
        for name in ("blocking", "streaming"):
            code, body, headers = results[name]
            assert code == 503, (name, results[name])
            assert "shed" in body["error"]
            assert int(headers["Retry-After"]) >= 1
        srv.overload.force_rung("normal")


def test_controller_state_survives_crash_recovery(model):
    """A crash-recovery rebuild must keep the controller's rung AND
    re-apply its knobs to the fresh batcher (which starts from the
    base ctor's prefill budget)."""
    params, config = model
    inj = FaultInjector("step@2:error")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, block_size=16,
        prefill_budget=16, fault_injector=inj,
    )
    with LLMServer(cb) as srv:
        srv.overload.force_rung("brownout-2")
        srv.overload.transitions_total = 3
        srv._apply_overload_knobs()
        assert srv.batcher.prefill_budget == 4  # 16 * 0.25
        # The 2nd step dispatch faults -> rebuild + replay; the
        # request still completes.
        s, body, _ = _post(
            srv.address, {"prompt": [1, 2, 3], "max_new_tokens": 6}
        )
        assert s == 200 and len(body["tokens"]) == 6
        assert srv.recoveries_total == 1
        # Controller state intact, knobs re-applied post-rebuild.
        assert srv.overload.rung == "brownout-2"
        assert srv.overload.transitions_total == 3
        assert srv.batcher.prefill_budget == 4
        srv.overload.force_rung("normal")


def _flood_server(params, config, **ctl_kw):
    """A tiny server + drill-scale controller for the flood tests."""
    from jax_llama_tpu.obs import Observability

    slo = ctl_kw.pop("slo_ttft_ms", 150.0)
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        obs=Observability(slo_ttft_ms=slo),
    )
    ctl = OverloadController(
        enabled=True, max_queue=ctl_kw.pop("max_queue", 8),
        slo_ttft_ms=slo, dwell_s=0.05, cooldown_s=0.2,
        signal_window_s=ctl_kw.pop("signal_window_s", 2.0),
        min_signal_samples=2, **ctl_kw,
    )
    return LLMServer(cb, overload=ctl)


def _run_flood(srv, n, rate_hz, seed=0):
    sched = poisson_schedule(rate_hz, n / rate_hz, seed=seed)[:n]

    def payload_fn(i):
        if i % 2 == 0:
            return {"prompt": [1 + i % 60, 2], "max_new_tokens": 3,
                    "priority": "interactive", "stream": True,
                    "timeout_s": 20.0}
        return {"prompt": list(range(1, 33)), "max_new_tokens": 8,
                "priority": "batch", "stream": True, "timeout_s": 20.0}

    return open_loop_flood(
        srv.address, sched, payload_fn, timeout_s=60.0,
        join_timeout_s=120.0,
    )


# slow (r17 budget rebalance, ~13 s): tier-1 keeps an open-loop Poisson
# flood via test_flood_escalates_ladder_then_recovers_to_normal and the
# 503/Retry-After well-formedness pin via
# test_http_queued_batch_shed_cleanly_with_retry_after; this zero-hangs
# flood joins the slow acceptance drill below (`make overload` runs
# the file unfiltered).
@pytest.mark.slow
def test_flood_drill_zero_hangs_all_503s_well_formed(model):
    """The flood drill: an open-loop Poisson mixed-class flood
    against a 2-slot server with a depth-8 backstop.  Every client
    gets a terminal outcome (zero hangs), every refusal is a 503
    carrying Retry-After, and the server still serves afterwards."""
    params, config = model
    with _flood_server(params, config) as srv:
        # Warm the compile caches (both request shapes).
        _post(srv.address, {"prompt": [1, 2], "max_new_tokens": 3})
        _post(srv.address,
              {"prompt": list(range(1, 33)), "max_new_tokens": 8})
        records = _run_flood(srv, n=30, rate_hz=30.0)
        summary = summarize_flood(records, slo_ttft_ms=150.0)
        assert summary["hung_total"] == 0, summary
        statuses = {r["status"] for r in records}
        assert statuses <= {200, 503, 504}, statuses
        for cls in ("interactive", "batch"):
            s = summary[cls]
            assert s["errors"] == 0, (cls, s)
            assert s["refused_503"] == s["refused_with_retry_after"], (
                cls, s,
            )
        assert sum(
            summary[c]["served"]
            for c in ("interactive", "batch")
        ) > 0
        # The server is healthy after the flood: a fresh request works.
        s, body, _ = _post(
            srv.address, {"prompt": [7, 7], "max_new_tokens": 2}
        )
        assert s == 200 and len(body["tokens"]) == 2


def test_flood_escalates_ladder_then_recovers_to_normal(model):
    """Sustained overload escalates the ladder (visible in /healthz +
    /metrics + the structured annotation ring); once the flood stops,
    the ladder steps back down to normal — hysteresis proven end to
    end, not just in the clock-injected unit."""
    params, config = model
    # An unmeetable TTFT SLO (0.01 ms) makes every served request a
    # miss — deterministic pressure without timing sensitivity.  The
    # signal window is 8 s, not the other drills' 2 s: the ladder needs
    # two misses inside one window, and beside five busy workers two
    # interactive requests can finish more than 2 s apart (it then never
    # moved at all; ROADMAP C12).
    with _flood_server(
        params, config, slo_ttft_ms=0.01, signal_window_s=8.0,
    ) as srv:
        _post(srv.address, {"prompt": [1, 2], "max_new_tokens": 3})
        _run_flood(srv, n=16, rate_hz=20.0)
        # The escalation is read from what the run leaves behind, not
        # from the rung at this instant: with a 0.2 s cool-down the
        # ladder may have risen and come back down before the flood has
        # been joined.
        deadline = time.monotonic() + 60.0
        while (srv.overload.transitions_total < 1
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert srv.overload.transitions_total >= 1, (
            "ladder never escalated under the flood: "
            f"{srv.overload.health()}"
        )
        with urllib.request.urlopen(srv.address + "/healthz") as r:
            h = json.loads(r.read())
        assert h["overload"]["enabled"] is True
        # Escalations are annotated into the obs event ring.
        assert any(
            e["name"] == "overload_transition"
            for e in list(srv.obs.events)
        )
        # Flood over: the signal window drains (8 s) and the ladder
        # walks back down one cooldown (0.2 s) per rung.
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if srv.overload.rung == "normal":
                break
            time.sleep(0.1)
        assert srv.overload.rung == "normal", (
            "ladder failed to recover after the flood: "
            f"{srv.overload.health()}"
        )
        # /metrics carries the story: transitions happened, the rung
        # gauge is back at 0.
        with urllib.request.urlopen(srv.address + "/metrics") as r:
            text = r.read().decode()
        lines = dict(
            ln.split(" ", 1) for ln in text.splitlines()
            if ln and not ln.startswith("#")
        )
        assert float(lines["llm_overload_rung"]) == 0.0
        assert float(lines["llm_overload_transitions_total"]) >= 2


@pytest.mark.slow
def test_acceptance_drill_interactive_held_at_2x_sustainable(model):
    """The acceptance drill (ISSUE 9): a Poisson mixed-class flood at
    >= 2x the measured sustainable rate.  With the ladder + priority
    classes on: interactive TTFT SLO attainment stays >= 0.5 while
    batch is refused/shed; every refused/shed request receives a
    well-formed 503 + Retry-After; zero hung clients; and the ladder
    steps back down to normal after the flood."""
    params, config = model
    with _flood_server(params, config, slo_ttft_ms=2000.0) as srv:
        _post(srv.address, {"prompt": [1, 2], "max_new_tokens": 3})
        _post(srv.address,
              {"prompt": list(range(1, 33)), "max_new_tokens": 8})
        # Sustainable rate: a closed-loop burst of 8 mixed requests.
        t0 = time.monotonic()
        _run_flood(srv, n=8, rate_hz=1000.0, seed=3)
        sustainable = 8.0 / (time.monotonic() - t0)

    with _flood_server(params, config, slo_ttft_ms=2000.0) as srv:
        _post(srv.address, {"prompt": [1, 2], "max_new_tokens": 3})
        _post(srv.address,
              {"prompt": list(range(1, 33)), "max_new_tokens": 8})
        rate = max(2.0 * sustainable, 4.0)
        records = _run_flood(srv, n=60, rate_hz=rate, seed=4)
        summary = summarize_flood(records, slo_ttft_ms=2000.0)
        assert summary["hung_total"] == 0, summary
        ia = summary["interactive"]["slo_attainment"]
        assert ia is not None and ia >= 0.5, summary
        # Batch pays: refused (backlog/class) or shed or slower.
        b = summary["batch"]
        assert b["refused_503"] == b["refused_with_retry_after"]
        i = summary["interactive"]
        assert i["refused_503"] == i["refused_with_retry_after"]
        # The ladder moved under the flood (backlog pressure) and
        # recovers afterwards.
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            if srv.overload.rung == "normal":
                break
            time.sleep(0.1)
        assert srv.overload.rung == "normal", srv.overload.health()
