"""Graceful degradation: kernel quarantine with XLA fallback, the
non-finite guard, and drain-on-signal shutdown.

The invariants pinned here:
  * a feature that keeps failing (Pallas paged/flash kernel, speculative
    decode, prefix cache) is QUARANTINED onto its always-correct
    fallback after N attributable failures — the server stays up, every
    request completes, and greedy outputs are token-identical to the
    healthy path;
  * /healthz reports the full degraded state and the feature recovers
    via a probe rebuild after the cooldown;
  * quarantine does NOT consume the crash-recovery budget (degrading
    removes the crash cause; the breaker is for unexplained failures);
  * non-finite logits fail only the offending request with a clean HTTP
    500 — other requests and the server itself are untouched;
  * drain mode finishes in-flight requests, 503s new ones with
    Retry-After, and exits the loop — bounded by the drain timeout.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.degrade import FEATURES, DegradeManager
from jax_llama_tpu.faults import FaultInjector, InjectedFault
from jax_llama_tpu.server import LLMServer
from jax_llama_tpu.serving import ContinuousBatcher

pytestmark = pytest.mark.faults

CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=128, dtype="float32", param_dtype="float32",
)

PROMPTS = [[5, 17, 99, 3], [7, 8, 9], [11, 12, 13], [2, 3, 4]]
MAX_NEW = 8


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


@pytest.fixture(scope="module")
def reference(model):
    """Fault-free greedy outputs for PROMPTS (the identity oracle)."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    rids = [cb.submit(list(p), max_new_tokens=MAX_NEW) for p in PROMPTS]
    out = cb.run_to_completion()
    return [out[r] for r in rids]


def _post(url, payload, timeout=300):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(url, path, timeout=60):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return r.status, r.read().decode()


def _health(url):
    try:
        _, body = _get(url, "/healthz")
    except urllib.error.HTTPError as e:
        body = e.read().decode()
    return json.loads(body)


# ---------------------------------------------------------------------------
# DegradeManager state machine (no jax involved)
# ---------------------------------------------------------------------------

def test_state_machine_threshold_window_probe():
    clock = [0.0]
    m = DegradeManager(
        threshold=3, window_s=10.0, cooldown_s=5.0, clock=lambda: clock[0]
    )
    f = "paged_kernel"
    assert m.enabled(f) and not m.degraded()
    assert m.record_failure(f) is False
    assert m.record_failure(f) is False
    assert m.enabled(f)                      # below threshold
    assert m.record_failure(f) is True       # 3rd inside window: quarantine
    assert not m.enabled(f) and m.degraded()
    assert m.quarantined() == (f,)
    assert m.due_probes() == []
    clock[0] = 5.0                           # cooldown elapsed
    assert m.due_probes() == [f]
    m.start_probe(f)
    assert m.enabled(f)                      # probing counts as enabled
    assert m.snapshot()[f]["state"] == "probing"
    # Probe failure: straight back to quarantine, cooldown restarts.
    assert m.record_failure(f) is True
    assert not m.enabled(f)
    clock[0] = 9.9
    assert m.due_probes() == []
    clock[0] = 10.0
    m.start_probe(f)
    assert m.record_success(f) is True       # probe passed
    assert m.enabled(f) and not m.degraded()
    assert m.snapshot()[f]["state"] == "healthy"
    st = m.snapshot()[f]
    assert st["failures_total"] == 4 and st["quarantines_total"] == 2
    assert st["probes_total"] == 2


def test_state_machine_window_expires_failures():
    clock = [0.0]
    m = DegradeManager(
        threshold=2, window_s=1.0, cooldown_s=1.0, clock=lambda: clock[0]
    )
    assert m.record_failure("spec_decode") is False
    clock[0] = 2.0                           # first failure aged out
    assert m.record_failure("spec_decode") is False
    clock[0] = 2.5
    assert m.record_failure("spec_decode") is True


def test_state_machine_rejects_unknown_feature():
    m = DegradeManager()
    with pytest.raises(KeyError):
        m.record_failure("nosuch")
    # success outside probing is a no-op, never a transition
    assert m.record_success(FEATURES[0]) is False


def test_manager_stats_and_snapshot_shapes():
    m = DegradeManager()
    snap, stats = m.snapshot(), m.stats()
    for f in FEATURES:
        assert snap[f]["state"] == "healthy"
        assert stats[f"feature_quarantined_{f}"] == 0


# ---------------------------------------------------------------------------
# Kernel quarantine: repeated kernel faults -> XLA fallback, server stays up
# ---------------------------------------------------------------------------

def test_paged_kernel_quarantine_keeps_serving_identically(
    model, reference
):
    """Every decode step on the kernel path faults: after the threshold
    the feature is quarantined, the batcher rebuilds onto the
    gathered-view XLA fallback, and every request completes with greedy
    outputs token-identical to the healthy path.  The crash-recovery
    breaker does NOT trip (quarantining forgives the budget)."""
    params, config = model
    inj = FaultInjector("paged_kernel~1.0:error")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, fault_injector=inj
    )
    results = {}
    with LLMServer(
        cb, max_recoveries=3, quarantine_threshold=3,
        quarantine_cooldown_s=3600.0,  # no probe during this test
    ) as srv:
        def call(i):
            try:
                _, body = _post(
                    srv.address,
                    {"prompt": PROMPTS[i], "max_new_tokens": MAX_NEW},
                )
                results[i] = body["tokens"]
            except Exception as e:  # noqa: BLE001
                results[i] = f"{type(e).__name__}: {e}"

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        for i in range(len(PROMPTS)):
            assert results[i] == reference[i], i

        h = _health(srv.address)
        assert h["ok"] is True                    # degraded, NOT down
        assert h["degraded"] is True
        assert h["quarantined"] == ["paged_kernel"]
        feat = h["features"]["paged_kernel"]
        assert feat["state"] == "quarantined"
        assert feat["quarantines_total"] == 1
        assert feat["probe_in_s"] > 0
        assert srv.quarantine_rebuilds_total == 1
        assert inj.injected["paged_kernel"] == 3  # threshold, then silent
        _, mtext = _get(srv.address, "/metrics")
        assert "llm_feature_quarantined_paged_kernel 1" in mtext
        assert "llm_quarantine_rebuilds_total 1" in mtext


def test_quarantined_kernel_recovers_after_cooldown(model, reference):
    """Indexed faults kill the first three kernel steps; after the
    cooldown the loop probes (rebuild with the kernel re-enabled), the
    probe step succeeds, and /healthz reports the feature healthy —
    with requests before, during, and after all token-identical."""
    params, config = model
    inj = FaultInjector(
        "paged_kernel@0:error,paged_kernel@1:error,paged_kernel@2:error"
    )
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, fault_injector=inj
    )
    with LLMServer(
        cb, quarantine_threshold=3, quarantine_cooldown_s=0.5
    ) as srv:
        _, body = _post(
            srv.address, {"prompt": PROMPTS[0], "max_new_tokens": MAX_NEW}
        )
        assert body["tokens"] == reference[0]
        assert _health(srv.address)["quarantined"] == ["paged_kernel"]
        time.sleep(0.7)  # past the cooldown; the probe needs a step
        _, body = _post(
            srv.address, {"prompt": PROMPTS[1], "max_new_tokens": MAX_NEW}
        )
        assert body["tokens"] == reference[1]
        h = _health(srv.address)
        assert h["features"]["paged_kernel"]["state"] == "healthy"
        assert h["degraded"] is False and h["ok"] is True
        assert srv.probe_rebuilds_total == 1
        _, mtext = _get(srv.address, "/metrics")
        assert "llm_feature_quarantined_paged_kernel 0" in mtext
        assert "llm_probe_rebuilds_total 1" in mtext


def test_spec_decode_quarantine_falls_back_to_plain(model, reference):
    """A speculative batcher whose every round faults quarantines
    spec_decode and rebuilds WITHOUT the draft model — greedy outputs
    are token-identical (the draft only ever changes speed)."""
    params, config = model
    inj = FaultInjector("spec_decode~1.0:error")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64,
        draft_params=params, draft_config=config, n_draft=2,
        fault_injector=inj,
    )
    with LLMServer(
        cb, quarantine_threshold=2, quarantine_cooldown_s=3600.0
    ) as srv:
        _, body = _post(
            srv.address, {"prompt": PROMPTS[0], "max_new_tokens": MAX_NEW}
        )
        assert body["tokens"] == reference[0]
        h = _health(srv.address)
        assert h["quarantined"] == ["spec_decode"]
        assert not srv.batcher.spec  # the fallback batcher is plain
        # A follow-up request runs entirely on the plain path.
        _, body = _post(
            srv.address, {"prompt": PROMPTS[1], "max_new_tokens": MAX_NEW}
        )
        assert body["tokens"] == reference[1]


def test_flash_attention_quarantine_rebuilds_onto_xla(model):
    """attn_impl='auto' prefills through the Pallas flash kernel; when
    every flash dispatch faults the feature quarantines and the batcher
    rebuilds with attn_impl='xla' — outputs identical to a pure-xla
    batcher (after quarantine every completed token IS the xla path)."""
    params, config = model
    auto_cfg = config.replace(attn_impl="auto")
    cold = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    rid = cold.submit(list(PROMPTS[0]), max_new_tokens=MAX_NEW)
    want = cold.run_to_completion()[rid]

    inj = FaultInjector("flash_kernel~1.0:error")
    cb = ContinuousBatcher(
        params, auto_cfg, n_slots=1, max_len=64, fault_injector=inj
    )
    with LLMServer(
        cb, quarantine_threshold=2, quarantine_cooldown_s=3600.0
    ) as srv:
        _, body = _post(
            srv.address, {"prompt": PROMPTS[0], "max_new_tokens": MAX_NEW}
        )
        assert body["tokens"] == want
        h = _health(srv.address)
        assert h["quarantined"] == ["flash_attention"]
        assert srv.batcher.config.attn_impl == "xla"


# slow (r06 budget rebalance, ~12 s): still in `make faults` / `make
# chaos`; the cheap flash-quarantine cells above keep tier-1 coverage.
@pytest.mark.slow
def test_flash_quarantine_during_fused_prefill_keeps_admission(model):
    """flash_kernel faults during FUSED prefill chunks (attn auto, a
    >8-token chunk riding the decode dispatch) quarantine
    flash_attention: the batcher rebuilds onto attn_impl='xla', the
    mid-prefill admission replays instead of dropping, and fused
    scheduling keeps running on the gathered path afterwards."""
    params, config = model
    auto_cfg = config.replace(attn_impl="auto")
    long_prompt = np.random.RandomState(3).randint(1, 128, 40).tolist()
    cb0 = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, block_size=8,
    )
    ra = cb0.submit(list(PROMPTS[0]), max_new_tokens=24)
    rb = cb0.submit(list(long_prompt), max_new_tokens=MAX_NEW)
    rc = cb0.submit(list(PROMPTS[1]), max_new_tokens=MAX_NEW)
    out0 = cb0.run_to_completion()
    want_a, want_b, want_c = out0[ra], out0[rb], out0[rc]

    # block_size=8 keeps the resident's COLD 8-token classic prefill on
    # the xla path (flash needs a >8-token chunk), so the ONLY flash
    # dispatches are the fused prefill chunks (budget 16 > 8).
    inj = FaultInjector("flash_kernel~1.0:error")
    cb = ContinuousBatcher(
        params, auto_cfg, n_slots=2, max_len=64, block_size=8,
        decode_chunk=4, prefill_budget=16, fault_injector=inj,
    )
    with LLMServer(
        cb, quarantine_threshold=1, quarantine_cooldown_s=3600.0
    ) as srv:
        req = urllib.request.Request(
            srv.address + "/generate",
            data=json.dumps({
                "prompt": PROMPTS[0], "max_new_tokens": 24,
                "stream": True,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as resp:
            first = json.loads(resp.readline())
            assert "token" in first
            # Admits mid-decode -> fused prefill on flash -> fault ->
            # flash_attention quarantined, admission replayed.
            _, body = _post(
                srv.address,
                {"prompt": long_prompt, "max_new_tokens": MAX_NEW},
            )
            assert body["tokens"] == want_b  # admission NOT dropped
            assert srv.degrade.quarantined() == ("flash_attention",)
            assert srv.batcher.config.attn_impl == "xla"
            # Fused scheduling survived the rebuild; a follow-up warm
            # admission rides it on the gathered/xla path.
            assert srv.batcher.prefill_budget == 16
            _, body2 = _post(
                srv.address,
                {"prompt": PROMPTS[1], "max_new_tokens": MAX_NEW},
            )
            assert body2["tokens"] == want_c
            lines = [first] + [
                json.loads(ln) for ln in resp.read().splitlines()
            ]
        streamed = [ln["token"] for ln in lines[:-1]]
        assert streamed == want_a  # resident: no dup, no gap
        assert inj.injected_total >= 1


def test_prefix_cache_quarantine_serves_cold(model):
    """Every prefix-cache-hit suffix dispatch faults: the feature
    quarantines and later sharers admit through cold full prefill —
    token-identical (a hit changes what is computed, never what is
    emitted)."""
    params, config = model
    rng = np.random.RandomState(3)
    base = rng.randint(1, 128, size=40).tolist()  # 2 full keyed blocks
    variants = [base + [3], base + [9, 4], base + [6]]

    cb0 = ContinuousBatcher(params, config, n_slots=1, max_len=128,
                            block_size=16, prefix_cache=False)
    want = []
    for p in variants:
        r = cb0.submit(list(p), max_new_tokens=6)
        want.append(cb0.run_to_completion()[r])

    inj = FaultInjector("suffix_insert~1.0:error")
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=128,
                           block_size=16, fault_injector=inj)
    with LLMServer(
        cb, quarantine_threshold=2, quarantine_cooldown_s=3600.0
    ) as srv:
        got = []
        for p in variants:
            _, body = _post(
                srv.address, {"prompt": p, "max_new_tokens": 6}
            )
            got.append(body["tokens"])
        assert got == want
        h = _health(srv.address)
        assert h["quarantined"] == ["prefix_cache"]
        assert not srv.batcher.prefix_cache_enabled


def test_unattributable_faults_still_trip_the_breaker(model):
    """Generic step faults (no feature attribution) must keep PR 1's
    hard-drain contract: past max_recoveries the loop gives up and
    clients get 503 — quarantine never swallows an unexplained crash
    loop."""
    params, config = model
    inj = FaultInjector("step~1.0:error")
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=64, fault_injector=inj
    )
    with LLMServer(cb, max_recoveries=1, recovery_window_s=60.0) as srv:
        try:
            _post(srv.address, {"prompt": [1, 2], "max_new_tokens": 2})
            assert False, "expected HTTP 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
        h = _health(srv.address)
        assert h["loop_alive"] is False
        assert h["degraded"] is False  # nothing was quarantined


# ---------------------------------------------------------------------------
# Non-finite guard
# ---------------------------------------------------------------------------

def test_nonfinite_logits_fail_only_that_request(model, reference):
    """An armed ``nan`` fault poisons one row mid-decode: that request
    gets a clean 500, every other request completes identically, and
    the server stays healthy."""
    params, config = model
    inj = FaultInjector("step@2:nan")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, fault_injector=inj
    )
    results = {}
    with LLMServer(cb) as srv:
        def call(i):
            try:
                _, body = _post(
                    srv.address,
                    {"prompt": PROMPTS[i], "max_new_tokens": MAX_NEW},
                )
                results[i] = body["tokens"]
            except urllib.error.HTTPError as e:
                results[i] = (e.code, json.loads(e.read())["error"])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        codes = [r for r in results.values() if isinstance(r, tuple)]
        toks = [r for r in results.values() if isinstance(r, list)]
        assert len(codes) == 1 and len(toks) == 1, results
        code, msg = codes[0]
        assert code == 500 and "non-finite" in msg
        assert toks[0] in reference  # the survivor is exact
        assert srv.nonfinite_failed_total == 1
        h = _health(srv.address)
        assert h["ok"] is True  # one bad request never degrades health
        _, mtext = _get(srv.address, "/metrics")
        assert "llm_nonfinite_requests_failed_total 1" in mtext
        assert "llm_nonfinite_rows_total 1" in mtext


def test_real_nan_params_fail_requests_cleanly(model):
    """Genuinely non-finite weights (NaN lm head — the real failure the
    guard exists for): every request fails with a clean 500, nothing
    streams garbage, and the serving loop survives."""
    params, config = model
    bad = dict(params)
    bad["lm_head"] = params["lm_head"] * float("nan")
    cb = ContinuousBatcher(bad, config, n_slots=2, max_len=64)
    with LLMServer(cb) as srv:
        for p in PROMPTS[:2]:
            try:
                _post(srv.address, {"prompt": p, "max_new_tokens": 4})
                assert False, "expected HTTP 500"
            except urllib.error.HTTPError as e:
                assert e.code == 500
                assert "non-finite" in json.loads(e.read())["error"]
        assert _health(srv.address)["ok"] is True
        assert srv.nonfinite_failed_total == 2
        assert not srv.batcher.pending()  # slots and blocks all freed


def test_nonfinite_spec_round_fails_request(model):
    """The speculative verify path's guard: NaN target logits abort the
    request without committing the poisoned round."""
    params, config = model
    bad = dict(params)
    bad["lm_head"] = params["lm_head"] * float("nan")
    cb = ContinuousBatcher(
        bad, config, n_slots=1, max_len=64,
        draft_params=params, draft_config=config, n_draft=2,
    )
    rid = cb.submit(list(PROMPTS[0]), max_new_tokens=4)
    out = cb.run_to_completion()
    failed = cb.pop_failed()
    assert rid not in out
    assert failed and failed[0][0] == rid
    assert not cb.pending()


def test_nonfinite_prompt_blocks_never_enter_prefix_cache(model):
    """A poisoned request's freshly prefilled blocks must be unpublished
    from the prefix index — a later identical prompt on healed weights
    must not hit KV written by the NaN run."""
    params, config = model
    bad = dict(params)
    bad["lm_head"] = params["lm_head"] * float("nan")
    prompt = list(np.random.RandomState(5).randint(1, 128, size=40))
    cb = ContinuousBatcher(bad, config, n_slots=1, max_len=128,
                           block_size=16)
    rid = cb.submit(prompt, max_new_tokens=4)
    cb.run_to_completion()
    assert cb.pop_failed()[0][0] == rid
    assert cb.stats()["radix_nodes_total"] == 0  # nothing published
    assert len(cb.free_blocks) == cb.n_blocks  # everything returned


# ---------------------------------------------------------------------------
# Drain-on-signal
# ---------------------------------------------------------------------------

def test_drain_finishes_inflight_and_503s_new(model, reference):
    """begin_drain with a stream mid-flight: the stream runs to
    completion token-identically, a new POST gets 503 + Retry-After,
    /healthz flips to draining, and the loop exits on its own."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    srv = LLMServer(cb, drain_timeout_s=60.0).start()
    try:
        # Warm the compile caches so the drained request finishes fast.
        _post(srv.address, {"prompt": [4, 5], "max_new_tokens": 2})
        result = {}

        def call():
            result["r"] = _post(
                srv.address,
                {"prompt": PROMPTS[0], "max_new_tokens": MAX_NEW},
            )

        t = threading.Thread(target=call)
        t.start()
        # Mid-flight means admitted: on a starved core the POST can take
        # longer than any fixed sleep to reach the loop, and a drain
        # that starts first finds the server idle and closes it.
        deadline = time.monotonic() + 60.0
        while not srv._active and time.monotonic() < deadline:
            time.sleep(0.005)
        srv.begin_drain()
        try:
            _post(srv.address, {"prompt": [1, 2], "max_new_tokens": 2})
            assert False, "expected HTTP 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert int(e.headers["Retry-After"]) >= 1
            assert "drain" in json.loads(e.read())["error"]
        h = _health(srv.address)
        assert h["draining"] is True and h["ok"] is False
        assert h["drain_remaining_s"] is not None
        t.join(timeout=300)
        assert not t.is_alive()
        status, body = result["r"]
        assert status == 200 and body["tokens"] == reference[0]
        assert srv.wait_drained(60)
    finally:
        srv.stop()


def test_drain_timeout_bounds_shutdown(model):
    """A drain deadline in the past: the in-flight request is failed
    with 503 instead of holding shutdown hostage.  An injected step
    delay holds the request mid-generation so the drain deterministically
    catches it in flight."""
    params, config = model
    inj = FaultInjector("step@0:delay=1.5")
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=128, fault_injector=inj
    )
    srv = LLMServer(cb).start()
    try:
        result = {}

        def call():
            try:
                result["r"] = _post(
                    srv.address,
                    {"prompt": [7, 8, 9], "max_new_tokens": 100},
                )
            except urllib.error.HTTPError as e:
                result["r"] = (e.code, json.loads(e.read())["error"])

        t = threading.Thread(target=call)
        t.start()
        time.sleep(0.3)  # inside compile or the held step
        srv.begin_drain(timeout_s=0.0)
        t.join(timeout=300)
        assert not t.is_alive()
        code, msg = result["r"]
        assert code == 503 and "drain timeout" in msg
        assert srv.wait_drained(60)
    finally:
        srv.stop()


def test_drain_idempotent_and_immediate_when_idle(model):
    """Draining an idle server exits the loop promptly; a second
    begin_drain keeps the first deadline."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    srv = LLMServer(cb, drain_timeout_s=30.0).start()
    try:
        srv.begin_drain(timeout_s=10.0)
        dl = srv._drain_deadline
        srv.begin_drain(timeout_s=99999.0)
        assert srv._drain_deadline == dl
        assert srv.wait_drained(30)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Trace-time kernel hooks + run.py wiring
# ---------------------------------------------------------------------------

def test_kernel_trace_hooks_fire_and_carry_site():
    """One faults.install_trace_hook arms every kernel entry point; the
    raised fault carries the site name (the attribution key)."""
    import jax.numpy as jnp

    from jax_llama_tpu import spec_decode as sd
    from jax_llama_tpu.faults import install_trace_hook
    from jax_llama_tpu.ops import paged_attention as pa
    from jax_llama_tpu.ops.flash_attention import flash_attention

    inj = FaultInjector(
        "flash_kernel@0:error,paged_kernel@0:error,spec_decode@0:error"
    )
    install_trace_hook(inj.fire)
    try:
        q = jnp.zeros((1, 8, 2, 8), jnp.float32)
        kv = jnp.zeros((1, 8, 2, 8), jnp.float32)
        pos = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(InjectedFault) as ei:
            flash_attention(q, kv, kv, pos, pos)
        assert ei.value.site == "flash_kernel"
        with pytest.raises(InjectedFault) as ei:
            pa.paged_pool_attention(
                jnp.zeros((1, 2, 2, 8), jnp.float32),
                jnp.zeros((2, 2, 4, 8, 8), jnp.float32),
                jnp.zeros((2, 2, 4, 8, 8), jnp.float32),
                jnp.zeros((4, 8), jnp.int32),
                jnp.zeros((1, 2), jnp.int32),
                jnp.zeros((1,), jnp.int32),
            )
        assert ei.value.site == "paged_kernel"
        with pytest.raises(InjectedFault) as ei:
            sd.generate_speculative(
                None, None, None, None,
                target_config=None, draft_config=None, gen_config=None,
            )
        assert ei.value.site == "spec_decode"
    finally:
        install_trace_hook(None)
    assert inj.calls["flash_kernel"] == 1
    assert inj.calls["paged_kernel"] == 1
    assert inj.calls["spec_decode"] == 1


@pytest.mark.slow
def test_run_cli_degrade_flags(tmp_path, capsys, monkeypatch):
    """The CLI wires --quarantine-*/--drain-timeout-s into the server
    and a kernel-fault drill degrades (quarantine visible in /healthz)
    instead of draining; the trace hooks are uninstalled afterwards.

    Slow tier (PR-10 budget rebalance: tier-1 measured at its 870 s
    ceiling): quarantine/degradation behavior itself stays pinned
    tier-1 by the rest of this module; this cell is the end-to-end
    CLI flag-threading drill (checkpoint restore + live server), and
    runs in the unfiltered suite and `make chaos`."""
    import sys

    import jax_llama_tpu.run as run_cli
    from jax_llama_tpu import faults as faults_mod
    from jax_llama_tpu.convert.checkpoint import save_checkpoint

    config = get_config(
        "tiny", vocab_size=512, dim=64, n_layers=2, n_heads=4,
        n_kv_heads=2, multiple_of=32, max_seq_len=64,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), params, config)

    hits = {}

    def hook(srv):
        assert srv.drain_timeout_s == 5.0
        assert srv.degrade.threshold == 2
        _, body = _post(
            srv.address,
            {"text": "hi", "max_new_tokens": 6, "temperature": 0.0},
        )
        hits["gen"] = body
        hits["health"] = _health(srv.address)
        hits["metrics"] = _get(srv.address, "/metrics")[1]

    orig = run_cli._serve_http
    monkeypatch.setattr(
        run_cli, "_serve_http",
        lambda *a, **kw: orig(*a, **kw, _test_hook=hook),
    )
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(ckpt), "--byte-tokenizer",
         "--tensor", "2", "--http", "0", "--max-gen-len", "8",
         "--temperature", "0.0",
         "--inject-faults", "paged_kernel~1.0:error",
         "--quarantine-threshold", "2", "--quarantine-cooldown-s", "600",
         "--drain-timeout-s", "5"],
    )
    run_cli.main()
    out = capsys.readouterr().out
    assert "faults_armed" in out  # the StructuredLogger line
    assert len(hits["gen"]["tokens"]) == 6
    assert hits["health"]["ok"] is True
    assert hits["health"]["quarantined"] == ["paged_kernel"]
    assert "llm_feature_quarantined_paged_kernel 1" in hits["metrics"]
    # hook cleared on exit — later traces must not feed a dead drill
    assert faults_mod._trace_hook is None


# ---------------------------------------------------------------------------
# Full chaos drill (make chaos): every site in one server lifetime
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_drill_all_sites(tmp_path, capsys, monkeypatch):
    """run.py --inject-faults over every site — the generic ones (step /
    insert / alloc recover, suffix_insert feeds prefix_cache) and the
    kernel sites (flash via --attn auto prefill, paged via decode) —
    in one server lifetime: every request completes, the server ends
    degraded-but-ok, and the counters account for every injection."""
    import sys

    import jax_llama_tpu.run as run_cli
    from jax_llama_tpu.convert.checkpoint import save_checkpoint

    config = get_config(
        "tiny", vocab_size=512, dim=64, n_layers=2, n_heads=4,
        n_kv_heads=2, multiple_of=32, max_seq_len=128,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), params, config)

    spec = ",".join([
        "insert@1:error",        # a later batched prefill dispatch
        "step@6:error",          # mid-decode
        "alloc@5:oom",           # a block allocation
        "suffix_insert@0:error",  # prefix-cache hit admission
        "flash_kernel@4:error",  # flash prefill (attn auto)
        "paged_kernel@9:error",  # kernel decode step
        "step@14:nan",           # non-finite guard
    ])
    hits = {}

    def hook(srv):
        base = [int(t) for t in
                np.random.RandomState(0).randint(1, 500, size=40)]
        prompts = (
            [[5, 17, 99, 3], base + [3], base + [9]]
            + [[7 + i, 8, 9] for i in range(5)]
        )
        results = []
        for p in prompts:
            try:
                results.append(_post(
                    srv.address, {"prompt": p, "max_new_tokens": 6},
                )[1]["tokens"])
            except urllib.error.HTTPError as e:
                results.append((e.code, json.loads(e.read())["error"]))
        hits["results"] = results
        hits["health"] = _health(srv.address)
        hits["metrics"] = _get(srv.address, "/metrics")[1]

    orig = run_cli._serve_http
    monkeypatch.setattr(
        run_cli, "_serve_http",
        lambda *a, **kw: orig(*a, **kw, _test_hook=hook),
    )
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(ckpt), "--byte-tokenizer",
         "--tensor", "2", "--http", "0", "--attn", "auto",
         "--max-gen-len", "8",
         "--temperature", "0.0", "--inject-faults", spec,
         "--max-recoveries", "10",
         "--quarantine-threshold", "3", "--watchdog-s", "30"],
    )
    run_cli.main()
    assert "faults_armed" in capsys.readouterr().out
    ok = [r for r in hits["results"] if isinstance(r, list)]
    failed = [r for r in hits["results"] if not isinstance(r, list)]
    # Every request either completed with its full budget or was the
    # nan-poisoned one (clean 500) — never a hang, never a 503 drain.
    assert all(len(r) == 6 for r in ok)
    assert all(code == 500 and "non-finite" in msg
               for code, msg in failed)
    assert len(failed) <= 1
    h = hits["health"]
    assert h["loop_alive"] is True
    m = hits["metrics"]
    assert "llm_faults_injected_total" in m
    total = next(
        float(line.split()[1]) for line in m.splitlines()
        if line.startswith("llm_faults_injected_total ")
    )
    assert total >= 5  # error/oom injections all fired
    assert "llm_fault_nans_armed_total 1" in m
