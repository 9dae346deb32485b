"""Host-boundary discipline of chunked decode AND chunked speculative
serving (make perf-smoke; tier-1-safe, CPU).

The whole point of decode_chunk / spec_rounds > 1 is amortizing
host<->device traffic: steady-state decode must pay AT MOST ONE
device->host sync (the packed token block) and ZERO host->device state
uploads per chunk dispatch — whether the chunk carries K plain decode
iterations or R speculative draft+verify rounds.  These tests assert
that contract through the batcher's instrumented counters
(``host_syncs_total`` / ``state_uploads_total`` count every np.asarray
fetch and every ``_scatter_rows`` state-sync dispatch the serving loop
performs; the ``spec_*`` twins attribute the speculative path's share),
plus the adaptive-K/R policy around admissions."""

import jax
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.obs import Observability
from jax_llama_tpu.serving import ContinuousBatcher

CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=128, dtype="float32", param_dtype="float32",
)


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


def test_flight_recorder_zero_overhead(model):
    """ACCEPTANCE PIN (ISSUE 15): the control-plane recorder is
    host-side bookkeeping only — steady-state chunk dispatches keep
    the exact 1-fetch / 0-upload contract while decisions are being
    recorded and EVERY flight-recorder surface (the decision log's
    json, the metric-snapshot ring, the config snapshot) is scraped
    mid-decode, exactly as /debug/decisions and /debug/bundle handler
    threads would."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=4,
        block_size=16,
    )
    cb.submit(list(np.random.RandomState(7).randint(1, 128, 40)),
              max_new_tokens=40)
    cb.step(); cb.step()  # admission + chunk ramp
    s0, u0, d0 = (
        cb.host_syncs_total, cb.state_uploads_total,
        cb.decode_dispatches_total,
    )
    for i in range(4):
        cb.step()
        # Record + scrape the recorder surfaces mid-decode.
        cb.obs.decisions.record(
            "route", request_id=f"r{i}", replica=0,
            policy="least-loaded",
        )
        cb.obs.record_metrics_snapshot(
            {"emitted_tokens_total": int(cb.emitted_total)}
        )
        doc = cb.obs.decisions.json(n=8)
        assert doc["events_total"] == i + 1
        assert len(cb.obs.metric_snapshots_json()) == i + 1
        assert cb.describe()["decode_chunk"] == 4
    dispatches = cb.decode_dispatches_total - d0
    assert dispatches == 4
    # Bit-identical steady-state contract with the recorder live:
    # 1 fetch per chunk, 0 uploads, no extra dispatches from any of
    # the recording or scraping above.
    assert cb.host_syncs_total - s0 == dispatches
    assert cb.state_uploads_total == u0


def test_steady_state_host_sync_discipline(model):
    """Steady-state chunk dispatches: exactly 1 device->host sync each,
    0 host->device state uploads (state is device-resident; only
    admission/free/cancel may upload, and only the rows they touched)."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=4,
    )
    cb.submit(list(np.random.RandomState(0).randint(1, 128, 9)),
              max_new_tokens=40)
    cb.step()   # admission (K=1) + the one state sync it owes
    cb.step()   # chunk-size ramp
    assert cb.state_uploads_total == 1  # the admission's row sync
    s0, u0, d0 = (
        cb.host_syncs_total, cb.state_uploads_total,
        cb.decode_dispatches_total,
    )
    for _ in range(4):
        cb.step()
    dispatches = cb.decode_dispatches_total - d0
    assert dispatches == 4
    # <= 1 sync per dispatch (exactly 1: the packed token block)...
    assert cb.host_syncs_total - s0 == dispatches
    # ...and ZERO steady-state state uploads.
    assert cb.state_uploads_total == u0
    # The steady-state chunks ran fused (K > 1).
    assert cb.decode_chunk_last == 4


def test_chunk_size_adapts_around_admissions(model):
    """K drops to 1 right after an admission (TTFT), stays clamped at
    <= _QUEUED_CHUNK_CAP while the queue holds capacity-blocked
    requests (bounded slot turnaround WITHOUT reverting to per-token
    dispatches under saturation), then ramps to the configured chunk,
    clamped pow2 by the remaining budget."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=128, decode_chunk=8,
    )
    cb.submit([4, 5, 6], max_new_tokens=20)
    cb.submit([7, 8, 9], max_new_tokens=20)  # queued behind slot 0
    cb.step()
    assert cb.decode_chunk_last == 1   # admission step
    cb.step()
    # Queue capacity-blocked: clamped small but still > 1 (saturation
    # must keep amortizing dispatches).
    assert cb.decode_chunk_last == cb._QUEUED_CHUNK_CAP
    # Drain request 0; once the queue empties and request 1 is steady,
    # chunks ramp to 8.
    seen = set()
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 200
        cb.step()
        seen.add(cb.decode_chunk_last)
    assert 8 in seen
    # Tail-of-budget clamping keeps K a power of two <= remaining.
    assert seen <= {1, 2, 4, 8}


def test_logprobs_mode_single_packed_fetch(model):
    """logprobs ride the packed block (bitcast int32): logprobs mode
    must not add a second per-chunk fetch."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=128, decode_chunk=4,
        logprobs=True,
    )
    cb.submit([5, 17, 99], max_new_tokens=24)
    cb.step(); cb.step()
    s0, d0 = cb.host_syncs_total, cb.decode_dispatches_total
    events = []
    for _ in range(3):
        events += cb.step()
    assert cb.host_syncs_total - s0 == cb.decode_dispatches_total - d0
    # And the logprobs delivered through the packed path are real.
    assert all(len(ev) == 4 and np.isfinite(ev[3]) for ev in events)


def test_metrics_surface(model):
    """The chunked-decode observability counters are in stats() (and
    therefore in the HTTP /metrics exposition)."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=64, decode_chunk=4,
    )
    cb.submit([4, 5, 6], max_new_tokens=6)
    cb.run_to_completion()
    stats = cb.stats()
    for key in (
        "decode_chunk_size", "decode_dispatches_total",
        "host_syncs_total", "state_uploads_total",
        "host_syncs_per_token",
    ):
        assert key in stats, key
    assert stats["decode_dispatches_total"] > 0
    assert 0 < stats["host_syncs_per_token"] <= 1.5


def test_kv_digest_zero_overhead(model):
    """ACCEPTANCE PIN (PR 13): chain-digest maintenance is host-side
    bookkeeping only — steady-state chunk dispatches keep the exact
    1-fetch / 0-upload contract with the digest live, the digest does
    not mutate during steady decode (content edits happen only at
    admission/free boundaries), and READING every digest surface
    (/debug/kv walk, summary, the stats() gauges) performs zero device
    dispatches and zero host syncs."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=4,
        block_size=16,
    )
    cb.submit(list(np.random.RandomState(1).randint(1, 128, 40)),
              max_new_tokens=40)
    cb.step(); cb.step()  # admission + ramp
    v0 = cb.kv_digest.summary()["version"]
    assert v0 >= 2  # the admission published its chain
    s0, u0, d0 = (
        cb.host_syncs_total, cb.state_uploads_total,
        cb.decode_dispatches_total,
    )
    for _ in range(4):
        cb.step()
        # Scrape every digest surface mid-decode, as /metrics and
        # /debug/kv handler threads would.
        walk = cb.kv_debug_json()
        assert walk["summary"]["version"] == v0  # steady: no edits
        assert cb.stats()["kv_digest_version"] == v0
    dispatches = cb.decode_dispatches_total - d0
    assert dispatches == 4
    # The steady-state contract is bit-identical with the digest (and
    # its readers) live: 1 fetch per chunk, 0 uploads, no extra
    # dispatches from any of the reads above.
    assert cb.host_syncs_total - s0 == dispatches
    assert cb.state_uploads_total == u0


# ---------------------------------------------------------------------------
# Fused prefill-decode scheduling owes the same discipline
# ---------------------------------------------------------------------------

def test_fused_admission_host_sync_discipline(model):
    """A fused admission's whole prefill pays ONE state upload (its
    admission-time dirty-row sync; the suffix/walk buffers upload once
    and are not state syncs) and every chunk dispatch — prefill riding
    or not — pays exactly 1 device->host fetch: no per-prefill-chunk
    host sync, the satellite contract of stall-free admission."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=4,
        block_size=16, prefill_budget=16,
    )
    cb.submit(list(np.random.RandomState(0).randint(1, 128, 9)),
              max_new_tokens=60)
    cb.step()   # cold pool: classic admission (nobody to stall)
    cb.step()   # chunk ramp
    assert cb.fused_admissions_total == 0
    s0, u0, d0 = (
        cb.host_syncs_total, cb.state_uploads_total,
        cb.decode_dispatches_total,
    )
    # 60-token prompt at a 16-token budget: 4 prefill-carrying chunks.
    cb.submit(list(np.random.RandomState(1).randint(1, 128, 60)),
              max_new_tokens=8)
    steps = 0
    while cb._pf is not None or cb.prefill_chunks_total == 0:
        cb.step()
        steps += 1
        assert steps < 10
    assert cb.fused_admissions_total == 1
    assert cb.prefill_chunks_total == 4
    dispatches = cb.decode_dispatches_total - d0
    # Exactly 1 fetch per chunk dispatch (the packed token block) —
    # fused admission added NO insert barrier and NO per-chunk sync...
    assert cb.host_syncs_total - s0 == dispatches
    # ...and exactly ONE state upload for the whole admission.
    assert cb.state_uploads_total - u0 == 1
    while cb.pending():
        cb.step()


def test_fused_prefill_does_not_collapse_chunk_size(model):
    """_pick_chunk no longer resets K to 1 when an admission rides the
    fused path (the first token comes out of the dispatch chain itself,
    so there is no TTFT reason to shrink the chunk), and decode rows
    keep emitting through every mid-prefill dispatch — zero
    full-prefill stalls."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=4,
        block_size=16, prefill_budget=16,
    )
    r0 = cb.submit(list(np.random.RandomState(0).randint(1, 128, 9)),
                   max_new_tokens=60)
    cb.step(); cb.step(); cb.step()
    assert cb.decode_chunk_last == 4  # steady before the admission
    cb.submit(list(np.random.RandomState(1).randint(1, 128, 60)),
              max_new_tokens=8)
    steps = 0
    while cb._pf is not None or cb.prefill_chunks_total == 0:
        evs = cb.step()
        steps += 1
        assert steps < 10
        # The fused dispatch kept a fused-K scan AND the resident row
        # kept emitting (the classic path would have reset to K=1 and,
        # worse, stalled the row for the whole-prompt insert).
        assert cb.decode_chunk_last == 4
        assert any(ev[0] == r0 for ev in evs)
    while cb.pending():
        cb.step()


def test_fused_metrics_surface(model):
    """The fused-scheduling observability gauges are in stats() (and
    therefore in the HTTP /metrics exposition)."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=4,
        block_size=16, prefill_budget=16,
    )
    cb.submit([4, 5, 6], max_new_tokens=20)
    cb.step(); cb.step()
    cb.submit(list(np.random.RandomState(1).randint(1, 128, 40)),
              max_new_tokens=4)
    cb.run_to_completion()
    stats = cb.stats()
    for key in (
        "prefill_budget", "prefill_tokens_inflight",
        "prefill_chunks_total", "fused_admissions_total",
        "decode_stall_ms_total",
    ):
        assert key in stats, key
    assert stats["prefill_budget"] == 16
    assert stats["fused_admissions_total"] == 1
    assert stats["prefill_chunks_total"] >= 2
    assert stats["prefill_tokens_inflight"] == 0  # drained


# ---------------------------------------------------------------------------
# The speculative path (spec_rounds > 1) owes the same discipline
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spec_models(model):
    params, config = model
    draft_config = get_config(
        "tiny", **{**CFG, "dim": 32, "n_layers": 1, "n_heads": 2,
                   "n_kv_heads": 1}
    )
    draft_params = init_params(jax.random.PRNGKey(1), draft_config)
    return params, config, draft_params, draft_config


def test_spec_steady_state_host_sync_discipline(spec_models):
    """Steady-state fused-spec dispatches: exactly 1 device->host fetch
    (the packed [B, R, W] block) and ZERO host->device state uploads
    per R-round dispatch — the classic loop paid 2-3 fetches + a
    5-array mirror upload PER ROUND."""
    params, config, draft_params, draft_config = spec_models
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128,
        draft_params=draft_params, draft_config=draft_config,
        n_draft=2, spec_rounds=4,
    )
    cb.submit(list(np.random.RandomState(0).randint(1, 128, 9)),
              max_new_tokens=90)
    cb.step()   # admission (R=1) + the one state sync it owes
    cb.step()   # round-count ramp
    assert cb.state_uploads_total == 1  # the admission's row sync
    s0, u0, d0 = (
        cb.host_syncs_total, cb.state_uploads_total,
        cb.spec_dispatches_total,
    )
    for _ in range(4):
        cb.step()
    dispatches = cb.spec_dispatches_total - d0
    assert dispatches == 4
    # Exactly 1 sync per dispatch (the packed token/acc/logprob block)...
    assert cb.host_syncs_total - s0 == dispatches
    # ...and ZERO steady-state state uploads.
    assert cb.state_uploads_total == u0
    # The steady-state chunks ran fused (R > 1).
    assert cb.spec_rounds_last == 4
    while cb.pending():
        cb.step()


# slow (r17 budget rebalance, ~15 s): R follows the SAME ``_pick_chunk``
# policy the plain chunked path follows (the docstring's own claim) —
# tier-1 pins the policy via test_chunk_size_adapts_around_admissions
# and the spec path's host-sync discipline + gauges via
# test_spec_steady_state_host_sync_discipline / test_spec_metrics_surface;
# the spec-R adaptivity drill rides slow (unfiltered suite runs it).
@pytest.mark.slow
def test_spec_rounds_adapt_around_admissions(spec_models):
    """R drops to 1 right after an admission (TTFT), stays clamped at
    <= _QUEUED_CHUNK_CAP while the queue holds capacity-blocked
    requests, then ramps to the configured spec_rounds — the same
    _pick_chunk policy the plain chunked path follows."""
    params, config, draft_params, draft_config = spec_models
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=128,
        draft_params=draft_params, draft_config=draft_config,
        n_draft=2, spec_rounds=8,
    )
    cb.submit([4, 5, 6], max_new_tokens=40)
    cb.submit([7, 8, 9], max_new_tokens=40)  # queued behind slot 0
    cb.step()
    assert cb.spec_rounds_last == 1   # admission step
    cb.step()
    # Queue capacity-blocked: clamped small but still > 1.
    assert cb.spec_rounds_last == cb._QUEUED_CHUNK_CAP
    seen = set()
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 200
        cb.step()
        seen.add(cb.spec_rounds_last)
    assert 8 in seen
    assert seen <= {1, 2, 4, 8}


def test_spec_metrics_surface(spec_models):
    """The speculative observability gauges are in stats() (and
    therefore in the HTTP /metrics exposition)."""
    params, config, draft_params, draft_config = spec_models
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=64,
        draft_params=draft_params, draft_config=draft_config,
        n_draft=2, spec_rounds=4,
    )
    cb.submit([4, 5, 6], max_new_tokens=8)
    cb.run_to_completion()
    stats = cb.stats()
    for key in (
        "spec_rounds_per_dispatch", "spec_dispatches_total",
        "spec_host_syncs_per_token", "spec_window_acceptance_rate",
    ):
        assert key in stats, key
    assert stats["spec_dispatches_total"] > 0
    # Fused rounds amortize: well under the classic loop's >= 2
    # fetches per round (>= 2 per token at acceptance 0).
    assert 0 < stats["spec_host_syncs_per_token"] <= 1.5
    assert 0.0 <= stats["spec_window_acceptance_rate"] <= 1.0


# ---------------------------------------------------------------------------
# Observability overhead (obs.py): tracing is ALWAYS ON, so its cost
# contract — zero device dispatches, zero extra host syncs — is proven
# by the same instrumented counters the chunk discipline uses.
# ---------------------------------------------------------------------------


@pytest.mark.obs
def test_tracing_adds_zero_device_dispatches_and_host_syncs(model):
    """Dispatch-span recording is pure host bookkeeping at boundaries
    the loop already crosses: steady-state chunks still pay EXACTLY one
    device->host sync and zero state uploads each, every counted
    dispatch owns exactly one span in the obs ring (1:1 — a span that
    cost its own dispatch would break the equality from the other
    side), and recording never fetches (fetch_ms is measured around the
    loop's OWN packed np.asarray, not a second one)."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=4,
    )
    cb.submit(list(np.random.RandomState(0).randint(1, 128, 9)),
              max_new_tokens=40)
    cb.step()   # admission + its one owed state sync
    cb.step()   # chunk-size ramp
    s0, u0, d0 = (
        cb.host_syncs_total, cb.state_uploads_total,
        cb.decode_dispatches_total,
    )
    seq0 = cb.obs._seq
    for _ in range(4):
        cb.step()
    dispatches = cb.decode_dispatches_total - d0
    assert dispatches == 4
    # The 1-fetch/0-upload steady state is bit-identical with tracing
    # on (it cannot be turned off — this IS the with-tracing number,
    # and the pre-obs suites above pin the same constants).
    assert cb.host_syncs_total - s0 == dispatches
    assert cb.state_uploads_total == u0
    # Exactly one dispatch span per counted dispatch, no extras.
    assert cb.obs._seq - seq0 == dispatches
    spans = list(cb.obs.dispatches)[-dispatches:]
    assert all(sp["kind"] == "decode" and sp["k"] == 4 for sp in spans)
    # The span's fetch wraps the loop's own sync: bounded by wall.
    assert all(0.0 <= sp["fetch_ms"] <= sp["wall_ms"] for sp in spans)


@pytest.mark.obs
def test_attribution_adds_zero_device_dispatches_and_host_syncs(model):
    """Compile attribution rides the existing one-fetch-per-chunk
    boundary: steady-state chunks pay exactly one device->host sync and
    zero state uploads each, every dispatch span carries its program
    name, the compiles of the ramp are booked onto that program, and a
    steady chunk compiles nothing."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=4,
    )
    cb.submit(list(np.random.RandomState(3).randint(1, 128, 9)),
              max_new_tokens=40)
    cb.step()   # admission + its one owed state sync
    cb.step()   # chunk-size ramp (K=1,2 compile here)
    cb.step()
    s0, u0, d0 = (
        cb.host_syncs_total, cb.state_uploads_total,
        cb.decode_dispatches_total,
    )
    compiles0 = cb.obs.compiles_total
    for _ in range(4):
        cb.step()
    dispatches = cb.decode_dispatches_total - d0
    assert dispatches == 4
    assert cb.host_syncs_total - s0 == dispatches
    assert cb.state_uploads_total == u0
    spans = list(cb.obs.dispatches)[-dispatches:]
    assert all(
        sp["program"] == "_paged_decode_chunk" and sp["compiles"] == 0
        for sp in spans
    )
    assert cb.obs.compiles_total == compiles0
    booked = dict(
        (lab["program"], n) for _, lab, n in cb.obs.compile_metrics()
    )
    assert set(booked) <= {
        "_paged_insert", "_paged_decode_chunk", "_scatter_rows",
    }


@pytest.mark.obs
def test_tracing_overhead_fused_admission_budget_unchanged(model):
    """A fused admission's host-boundary budget (<= 1 state upload for
    the whole prefill, 1 fetch per chunk dispatch) is unchanged by the
    span bookkeeping riding those dispatches, and the admission's
    prefill-carrying dispatches each recorded a span linked to the
    admitted request."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=4,
        prefill_budget=32,
    )
    rid0 = cb.submit(
        list(np.random.RandomState(1).randint(1, 128, 9)),
        max_new_tokens=48,
    )
    for _ in range(6):
        cb.step()
    s0, u0, d0 = (
        cb.host_syncs_total, cb.state_uploads_total,
        cb.decode_dispatches_total,
    )
    seq0 = cb.obs._seq
    rid = cb.submit(
        list(np.random.RandomState(2).randint(1, 128, 40)),
        max_new_tokens=4,
    )
    while any(
        s is not None and s.request_id == rid0
        for s in cb.slots.values()
    ) and cb.pending():
        cb.step()
    dispatches = cb.decode_dispatches_total - d0
    # One fetch per dispatch, and the fused admission's single upload.
    assert cb.host_syncs_total - s0 == dispatches
    assert cb.state_uploads_total - u0 <= 1
    assert cb.obs._seq - seq0 == dispatches
    fused = [
        sp for sp in cb.obs.dispatches
        if sp["seq"] >= seq0 and sp["prefill_tokens"] > 0
    ]
    assert fused, "expected prefill-carrying dispatch spans"
    assert all(rid in sp["rids"] for sp in fused)
