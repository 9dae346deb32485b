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
plus the adaptive-K/R policy around admissions.

The upload contract (PR 39; ``obs.host_uploads_total``, a dispatch
record's ``uploads``: host->device copies the loop thread makes OUTSIDE a
jitted call, counted at the site).  What crosses, and when:

  * a fused admission — ONE copy, when the prefill starts: the packed
    int32 vector (``serving.pack_prefill``: row, base, suffix length, the
    request key's two words, the zero offset, then the padded suffix
    tokens).  ``_fused_chunk`` unpacks it, advances its offset word and
    hands it back (a donated carry), so later chunks cross nothing;
  * a row sync (admission / free / cancel) — no copy of its own: the
    dirty rows' index and ten fields travel as ONE int32 matrix
    (``serving.pack_rows``), a host operand of the ``_scatter_rows``
    dispatch (``state_uploads_total`` counts those dispatches, as ever).
    A stop table that grew re-uploads the whole twin first: one copy;
  * a chunk of the recurrent block — its two snapshot ids as ONE int32 [2]
    host operand of the ``_fused_chunk`` call itself; an eviction batch —
    its block ids as a host operand of ``_release_blocks``;
  * a fused or decode dispatch without an admission — nothing: 0 copies
    (19 an admission and 2 a recurrent chunk before PR 39);
  * the classic whole-prompt / suffix inserts still copy their operands
    one by one (8-10 a dispatch, counted): once a window in the cells."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params, serving
from jax_llama_tpu.obs import Observability, metric_meta
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
    # The upload contract: the admission's record carries its one copy
    # (the packed vector; <= 2), every other chunk record none — and the
    # classic insert before them its operands, one by one.
    recs = list(cb.obs.dispatches)
    assert [r["uploads"] for r in recs if r["kind"] == "insert"] == [8]
    fused = [r for r in recs if r["kind"] == "fused"]
    assert [r["uploads"] for r in fused] == [1, 0, 0, 0]
    # ... and what each dispatch's admission sample cost rides the record
    # as host bookkeeping: nothing was fetched or uploaded to know it.
    assert [r["first_sample"] for r in fused] == [
        "skipped", "skipped", "skipped", "greedy"]
    assert {r["uploads"] for r in recs if r["kind"] == "decode"} == {0}
    assert cb.obs.host_uploads_total == 9 == cb.obs.metrics()[
        "host_uploads_total"]
    assert metric_meta("host_uploads_total")[0] == "counter"


def _mirrors(rng, rows, mb, width):
    """Host mirrors of ``rows`` slots, the float fields holding the values
    a conversion would lose (-0.0, a denormal-sized 1e-7, top_p 1.0)."""
    edge = np.array([-0.0, 1e-7, 1.0, 0.7, 1e-38, 0.95], np.float32)
    return dict(
        table=rng.randint(0, 64, (rows, mb)).astype(np.int32),
        n_alloc=rng.randint(0, mb, rows).astype(np.int32),
        fill=rng.randint(0, 99, rows).astype(np.int32),
        pos=rng.randint(0, 99, rows).astype(np.int32),
        active=rng.rand(rows) < 0.5,
        temps=rng.permutation(edge)[:rows],
        top_ps=rng.permutation(edge)[:rows],
        top_ks=rng.randint(0, 50, rows).astype(np.int32),
        remaining=rng.randint(0, 300, rows).astype(np.int32),
        stops=rng.randint(-1, 128, (rows, width)).astype(np.int32),
    )


@pytest.mark.parametrize(
    "dirty", [[2], [0, 3, 5], [1, 2, 3, 4]],
    ids=["one-row", "three-rows-and-a-pad", "four-rows"],
)
def test_scatter_rows_over_the_packed_matrix_is_the_field_by_field_form(dirty):
    """``_scatter_rows`` over ``pack_rows``' ONE int32 matrix against the
    form it replaces — each field's rows uploaded and scattered on their
    own: every twin bit for bit (the float columns travel by their bits),
    the pad row dropped, no other row touched."""
    rows, mb, width = 6, 4, 2
    order = ("table", "n_alloc", "fill", "pos", "active", "temps", "top_ps",
             "top_ks", "remaining", "stops")
    was = _mirrors(np.random.RandomState(0), rows, mb, width)
    host = _mirrors(np.random.RandomState(1), rows, mb, width)
    rb = serving.pow2_bucket(len(dirty))
    packed = serving.pack_rows(
        dirty, rb, rows, *(host[n] for n in order))
    assert packed.dtype == np.int32 and packed.shape == (rb, 9 + mb + width)
    assert (packed[len(dirty):, 0] == rows).all()       # pads: out of range
    got = serving._scatter_rows(
        tuple(jnp.asarray(was[n]) for n in order), packed)
    idx = np.full((rb,), rows, np.int32)
    idx[:len(dirty)] = dirty
    for name, twin in zip(order, got):
        take = np.zeros((rb,) + host[name].shape[1:], host[name].dtype)
        take[:len(dirty)] = host[name][dirty]
        want = jnp.asarray(was[name]).at[jnp.asarray(idx)].set(
            jnp.asarray(take), mode="drop")
        assert twin.dtype == want.dtype and twin.shape == want.shape, name
        bits = lambda a: np.asarray(a).view(  # noqa: E731
            np.int32 if a.dtype == jnp.float32 else np.asarray(a).dtype)
        assert np.array_equal(bits(twin), bits(want)), name
        clean = [r for r in range(rows) if r not in dirty]
        assert np.array_equal(np.asarray(twin)[clean], was[name][clean]), name
        assert np.array_equal(np.asarray(twin)[dirty], host[name][dirty]), name


def test_the_packed_admission_vector_round_trips():
    """``pack_prefill`` -> ``_unpack_prefill``: row, base, suffix length,
    the key's two words (a seed past 2**31, so the uint32 view matters),
    the zero offset and the tokens behind the header, types as the program
    used to get them one by one."""
    toks = np.random.RandomState(2).randint(0, 2**31 - 1, 40).tolist()
    key = np.array([0xFFFFFFFE, 2**31 + 12345], np.uint32)
    vec = serving.pack_prefill(5, 48, len(toks), key, toks, 64)
    assert vec.dtype == np.int32 and vec.shape == (serving._PF_HEADER + 64,)
    row, base, plen, pf_key, off, pf_toks = jax.jit(serving._unpack_prefill)(
        jnp.asarray(vec))
    assert (int(row), int(base), int(plen), int(off)) == (5, 48, 40, 0)
    assert pf_key.dtype == jnp.uint32 and np.array_equal(np.asarray(pf_key), key)
    assert pf_toks.dtype == jnp.int32 and pf_toks.shape == (64,)
    assert np.asarray(pf_toks).tolist() == toks + [0] * 24


def test_a_seeded_run_through_the_lane_is_the_classic_run_and_stops_regrow(model):
    """Served: sampled requests whose seeds lie past 2**31 (the key words
    cross as int32 and come back uint32) emit through the fused lane what
    the whole-prompt insert emits, and a request with a wider stop set
    than any before regrows the stop table: the device twin is rebuilt
    (one more copy on that admission's record: 2) and holds the host's."""
    params, config = model
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 128, n).tolist() for n in (9, 50, 37)]
    stops = (3, 5, 7, 11, 13)

    def run(budget):
        cb = ContinuousBatcher(
            params, config, n_slots=3, max_len=128, decode_chunk=4,
            block_size=16, prefill_budget=budget,
        )
        out = {}

        def steps(n):
            for _ in range(n):
                for rid, tok, *_ in cb.step():
                    out.setdefault(rid, []).append(tok)

        rids = [cb.submit(prompts[0], max_new_tokens=40, temperature=0.9,
                          seed=2**31 + 7)]
        steps(2)
        rids.append(cb.submit(prompts[1], max_new_tokens=12, temperature=0.8,
                              top_p=0.9, seed=2**32 - 5))
        steps(4)
        width = cb.d_stops.shape
        rids.append(cb.submit(prompts[2], max_new_tokens=12, temperature=0.7,
                              seed=2**31, stop_tokens=stops))
        steps(1)
        twin = (np.asarray(cb.d_stops), cb.stop_tab.copy())
        while cb.pending():
            steps(1)
        return cb, width, twin, [out[r] for r in rids]

    cb, width, (twin, mirror), fused = run(16)
    assert cb.fused_admissions_total == 2
    assert width == (3, 1) and twin.shape == (3, 8)
    assert np.array_equal(twin, mirror) and set(stops) < set(twin[2].tolist())
    admits = [r["uploads"] for r in cb.obs.dispatches
              if r["kind"] == "fused" and r["uploads"]]
    assert admits == [1, 2]
    *_, classic = run(0)
    assert fused == classic


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
    # What the admission sample cost, a fused dispatch: the three totals
    # add up to the dispatches, and one of them completed the prompt.
    firsts = {
        k: stats[f"first_sample_{k}_total"]
        for k in ("skipped", "greedy", "drawn")
    }
    assert sum(firsts.values()) == stats["prefill_chunks_total"]
    assert firsts["greedy"] == 1 and firsts["drawn"] == 0
    for k in firsts:
        assert metric_meta(f"first_sample_{k}_total")[0] == "counter"


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
    per R-round dispatch."""
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
    # Fused rounds amortize: a fetch a dispatch of up to R rounds, each
    # of which emits a token at least.
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
