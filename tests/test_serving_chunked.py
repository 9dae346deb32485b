"""Chunked decode (decode_chunk > 1) must be TOKEN-IDENTICAL to the
K=1 per-token loop — which existing tests pin against standalone
``engine.generate`` — across greedy and seeded-sampled policies, stop
tokens and max_new landing mid-chunk, logprobs on/off, the int8-KV
pool, and the gathered-view fallback; and the crash-recovery /
non-finite-guard / quarantine semantics proven for K=1 must hold with
chunking enabled (fault sites fire per chunk dispatch, replay works
from delivered tokens, NaN isolation stays per-request)."""

import json
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.faults import FaultInjector
from jax_llama_tpu.server import LLMServer
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


def _drain(cb, want_lp=False):
    """Run to completion collecting per-request tokens (and logprobs)."""
    toks, lps = {}, {}
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 500
        for ev in cb.step():
            toks.setdefault(ev[0], []).append(ev[1])
            if want_lp:
                lps.setdefault(ev[0], []).append(ev[3])
    return toks, lps


def _run_matrix(params, config, K, *, logprobs=False, stop=(), **cb_kw):
    """The shared request mix: greedy finishing mid-chunk (max_new 5),
    greedy full-budget, and two seeded sampled policies — 4 requests
    over 2 slots, so the chunk size also ramps around queue-driven
    admissions."""
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 128, size=n).tolist() for n in (5, 9, 14, 6)]
    policies = [
        dict(max_new_tokens=5),
        dict(max_new_tokens=11),
        dict(max_new_tokens=9, temperature=0.9, seed=11),
        dict(max_new_tokens=12, temperature=0.7, top_p=0.8, seed=12),
    ]
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=K,
        logprobs=logprobs, stop_tokens=stop, **cb_kw,
    )
    rids = [cb.submit(p, **pol) for p, pol in zip(prompts, policies)]
    toks, lps = _drain(cb, want_lp=logprobs)
    return (
        [toks[r] for r in rids],
        [lps.get(r) for r in rids],
    )


# K=8 is every benchmark cell's `decode_chunk`: tier-1 since PR 30.
@pytest.mark.parametrize("K", [4, 8])
def test_chunk_token_identity_greedy_and_sampled(model, K):
    """K ∈ {4, 8} × {greedy, sampled} × max_new mid-chunk: identical to
    the K=1 loop (which test_serving.py pins against engine.generate)."""
    params, config = model
    base, _ = _run_matrix(params, config, 1)
    got, _ = _run_matrix(params, config, K)
    assert got == base


@pytest.mark.parametrize("K", [4, 8])
def test_chunk_token_identity_stop_token_mid_chunk(model, K):
    """A stop token landing mid-chunk ends the request at exactly that
    token: the on-device stop set must agree with the host's."""
    params, config = model
    prompt = [5, 17, 99, 3, 42]

    def run(K, stop=()):
        cb = ContinuousBatcher(
            params, config, n_slots=1, max_len=64, decode_chunk=K,
            stop_tokens=stop,
        )
        rid = cb.submit(prompt, max_new_tokens=16)
        return cb.run_to_completion()[rid]

    free = run(1)
    j = next(
        i for i in range(1, len(free)) if free[i] not in free[:i]
    )
    stop = free[j]
    want = run(1, stop=(stop,))
    got = run(K, stop=(stop,))
    assert want == free[:j + 1]
    assert got == want


@pytest.mark.slow
def test_chunk_token_identity_logprobs(model):
    """logprobs mode: the packed (bitcast) per-token logprob block must
    deliver the same values the K=1 loop reports, token for token.

    Slow tier (r14 budget rebalance, ~11 s of logprobs-program
    compiles): chunked logprob identity stays tier-1-pinned by
    test_serving_fused's identity cells, which assert the same packed
    logprob block allclose against the classic oracle on every
    tier-1 run."""
    params, config = model
    base, base_lp = _run_matrix(params, config, 1, logprobs=True)
    got, got_lp = _run_matrix(params, config, 4, logprobs=True)
    assert got == base
    for a, b in zip(got_lp, base_lp):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_chunk_token_identity_int8_kv(model):
    """The int8 pool's quantized scan branches (per-iteration scale
    plane writes inside the chunk) must match their K=1 emissions.

    Slow tier (r14 budget rebalance, ~13 s: the int8 config compiles
    its own oracle AND chunk executables): int8-KV identity stays
    tier-1-pinned by test_kvcache's int8 chunk-matched-oracle parity
    cells and test_serving_spec's int8 cell."""
    params, config = model
    import dataclasses
    qconfig = dataclasses.replace(config, kv_cache_dtype="int8")
    base, _ = _run_matrix(params, qconfig, 1, block_size=16)
    got, _ = _run_matrix(params, qconfig, 4, block_size=16)
    assert got == base


def test_chunk_token_identity_gathered_fallback(model):
    """What a ``paged_kernel`` quarantine lands on (tier-1 since PR 30,
    ~4 s).  The gathered-view fallback (use_pallas_kernel=False) chunks
    identically — the scan body's gather/scatter path is per-iteration
    the same program as one K=1 dispatch."""
    params, config = model
    base, _ = _run_matrix(params, config, 1, use_pallas_kernel=False)
    got, _ = _run_matrix(params, config, 4, use_pallas_kernel=False)
    assert got == base


# ---------------------------------------------------------------------------
# Fault-tolerance semantics with chunking enabled
# ---------------------------------------------------------------------------

PROMPTS = [[5, 17, 99, 3], [7, 8, 9], [11, 12, 13]]
MAX_NEW = 12


def _post(url, payload, timeout=300):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _stream_lines(url, payload, timeout=300):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.status == 200
        return [json.loads(line) for line in r.read().splitlines()]


@pytest.fixture(scope="module")
def reference(model):
    """Fault-free K=1 greedy outputs (the identity oracle)."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    rids = [cb.submit(list(p), max_new_tokens=MAX_NEW) for p in PROMPTS]
    out = cb.run_to_completion()
    return [out[r] for r in rids]


@pytest.mark.faults
def test_chunked_step_fault_recovers_token_exact(model, reference):
    """A step fault mid-chunked-decode (the 'step' site fires once per
    CHUNK dispatch): recovery rebuilds a chunked batcher and replays
    from delivered tokens — greedy outputs identical to the fault-free
    K=1 run, streaming clients see each token exactly once even though
    tokens now arrive in chunk-sized bursts."""
    params, config = model
    inj = FaultInjector("step@2:error")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        fault_injector=inj,
    )
    results = {}
    with LLMServer(cb) as srv:
        def call(i):
            try:
                if i == 0:  # one streaming client
                    results[i] = _stream_lines(
                        srv.address,
                        {"prompt": PROMPTS[i], "max_new_tokens": MAX_NEW,
                         "stream": True},
                    )
                else:
                    _, body = _post(
                        srv.address,
                        {"prompt": PROMPTS[i], "max_new_tokens": MAX_NEW},
                    )
                    results[i] = body["tokens"]
            except Exception as e:  # noqa: BLE001 — fail the test, not the thread
                results[i] = f"{type(e).__name__}: {e}"

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)

        lines = results[0]
        assert isinstance(lines, list), lines
        streamed = [ln["token"] for ln in lines[:-1]]
        assert streamed == reference[0]          # no dup, no gap
        assert lines[-1]["done"] is True
        assert lines[-1]["tokens"] == reference[0]
        for i in range(1, len(PROMPTS)):
            assert results[i] == reference[i], i
        assert inj.injected_total == 1
        assert srv.recoveries_total == 1


@pytest.mark.faults
def test_chunked_nan_isolation_per_request(model, reference):
    """An armed nan poison under chunking fails exactly one request
    with a clean 500 (its chunk tokens are discarded, never streamed);
    the neighbor slot completes token-identically."""
    params, config = model
    inj = FaultInjector("step@2:nan")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        fault_injector=inj,
    )
    results = {}
    with LLMServer(cb) as srv:
        def call(i):
            try:
                results[i] = _post(
                    srv.address,
                    {"prompt": PROMPTS[i], "max_new_tokens": MAX_NEW},
                )[1]["tokens"]
            except urllib.error.HTTPError as e:
                results[i] = (e.code, json.loads(e.read())["error"])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    failed = [r for r in results.values() if isinstance(r, tuple)]
    ok = {i: r for i, r in results.items() if isinstance(r, list)}
    assert len(failed) == 1
    code, msg = failed[0]
    assert code == 500 and "non-finite" in msg
    assert len(ok) == 1
    (i, toks), = ok.items()
    assert toks == reference[i]
    assert inj.nans_armed_total == 1


@pytest.mark.faults
def test_chunked_paged_kernel_quarantine_falls_back(model, reference):
    """paged_kernel faults fire once per CHUNK dispatch and quarantine
    attribution still works: past the threshold the batcher rebuilds
    onto the gathered-view fallback WITH chunking preserved, requests
    replay token-identically, and the server reports degraded-but-ok."""
    params, config = model
    inj = FaultInjector("paged_kernel~1.0:error")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        fault_injector=inj,
    )
    with LLMServer(
        cb, quarantine_threshold=2, quarantine_cooldown_s=600.0
    ) as srv:
        _, body = _post(
            srv.address,
            {"prompt": PROMPTS[0], "max_new_tokens": MAX_NEW},
        )
        assert body["tokens"] == reference[0]
        assert srv.degrade.quarantined() == ("paged_kernel",)
        # The fallback batcher keeps the chunk configuration.
        assert srv.batcher.decode_chunk == 4
        assert srv.batcher.use_pallas_kernel is False
        # And keeps serving: a second request completes on the fallback.
        _, body2 = _post(
            srv.address,
            {"prompt": PROMPTS[1], "max_new_tokens": MAX_NEW},
        )
        assert body2["tokens"] == reference[1]
