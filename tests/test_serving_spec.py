"""Speculative decoding inside the continuous batcher: output must be
token-identical to the plain greedy batcher — the draft model only changes
speed (acceptance), never content.

One program (``_spec_rounds_chunk``) serves every ``spec_rounds``: R = 1
is held to the standalone references (``engine.generate``,
``engine.score``, ``spec_decode.generate_speculative``), and R > 1 must
be token-identical to R = 1 — including the ACCEPTANCE PATTERN (drafts
proposed/accepted) and per-token logprobs — across greedy/seeded-sampled
policies, stop tokens and max_new landing mid-chunk, non-finite logits
mid-chunk, and the int8-KV pool; and the crash-recovery /
non-finite-guard / quarantine semantics must hold with round fusion
(fault sites fire once per R-round chunk dispatch, replay works from
delivered tokens, quarantine falls back to plain CHUNKED decode with
the decode_chunk / spec_rounds configuration preserved)."""

import dataclasses
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
def models():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    draft_config = get_config(
        "tiny", **{**CFG, "dim": 32, "n_layers": 1, "n_heads": 2,
                   "n_kv_heads": 1}
    )
    draft_params = init_params(jax.random.PRNGKey(1), draft_config)
    return params, config, draft_params, draft_config


def _plain(params, config, prompts, max_new, stop=()):
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64,
                           stop_tokens=stop)
    rids = [cb.submit(p, max_new_tokens=max_new) for p in prompts]
    return rids, cb.run_to_completion()


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_spec_batcher_matches_plain_greedy(models):
    params, config, draft_params, draft_config = models
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 128, size=rng.randint(3, 12)).tolist()
               for _ in range(5)]
    prids, pres = _plain(params, config, prompts, 12)

    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64,
        draft_params=draft_params, draft_config=draft_config, n_draft=3,
    )
    rids = [cb.submit(p, max_new_tokens=12) for p in prompts]
    results = cb.run_to_completion()
    for rid, prid in zip(rids, prids):
        assert results[rid] == pres[prid]
    assert cb.drafts_proposed > 0
    assert 0.0 <= cb.acceptance_rate() <= 1.0


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_spec_batcher_self_draft_accepts_everything(models):
    """With the target as its own draft, greedy proposals always match —
    acceptance must be 100% and each request finishes in ~max_new/(G+1)
    rounds instead of max_new steps."""
    params, config, _, _ = models
    prompt = [5, 17, 99, 3, 42]
    _, pres = _plain(params, config, [prompt], 12)

    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=64,
        draft_params=params, draft_config=config, n_draft=3,
    )
    rid = cb.submit(prompt, max_new_tokens=12)
    results = cb.run_to_completion()
    assert results[rid] == pres[0]
    assert cb.acceptance_rate() == 1.0
    # 1 emission step + ceil(11 / 4) spec rounds, not 12 steps.
    assert cb.steps_total <= 4


def test_spec_batcher_stop_tokens(models):
    params, config, draft_params, draft_config = models
    prompt = [5, 17, 99, 3, 42]
    _, pres = _plain(params, config, [prompt], 16)
    stop = pres[0][4]  # 5th emitted token becomes the stop
    _, pres_stop = _plain(params, config, [prompt], 16, stop=(stop,))

    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=64, stop_tokens=(stop,),
        draft_params=draft_params, draft_config=draft_config, n_draft=4,
    )
    rid = cb.submit(prompt, max_new_tokens=16)
    results = cb.run_to_completion()
    assert results[rid] == pres_stop[0]
    assert not cb.pending()
    assert sorted(cb.free_blocks) == list(range(cb.n_blocks))


def test_one_round_a_dispatch_is_the_chunk_program_and_reads_engine_generate(models):
    """``spec_rounds=1`` (the constructor's default) is
    ``_spec_rounds_chunk`` at ``n_rounds=1``: every speculative dispatch
    is that program at k = 1 and pays its one packed fetch — nothing a
    round beside it — and the tokens are ``engine.generate``'s greedy
    ones."""
    import jax.numpy as jnp

    from jax_llama_tpu.engine import GenerationConfig, generate

    params, config, draft_params, draft_config = models
    prompt, max_new = [5, 17, 99, 3, 42, 8, 61, 2], 10
    cb = ContinuousBatcher(
        params, config, n_slots=1, max_len=64,
        draft_params=draft_params, draft_config=draft_config, n_draft=3,
    )
    assert cb.spec_rounds == 1
    rid = cb.submit(prompt, max_new_tokens=max_new)
    got = [t for (_, t, *_) in cb.step()]      # the admission's dispatch
    fetched, dispatched = cb.spec_host_syncs_total, cb.spec_dispatches_total
    while cb.pending():
        got += [t for (_, t, *_) in cb.step()]
    spec = [d for d in cb.obs.dispatches if d["kind"] == "spec"]
    assert len(spec) == cb.spec_dispatches_total > 2
    assert {(d["program"], d["k"]) for d in spec} == {("_spec_rounds_chunk", 1)}
    # past the admission's error barrier: a fetch a dispatch
    assert (cb.spec_host_syncs_total - fetched
            == cb.spec_dispatches_total - dispatched > 0)
    assert cb.drafts_proposed > 0

    gc = GenerationConfig(max_new_tokens=max_new, temperature=0.0,
                          stop_tokens=(), pad_id=0)
    want = np.asarray(generate(
        params, jnp.asarray([prompt], jnp.int32),
        jnp.ones((1, len(prompt)), bool), jax.random.PRNGKey(0),
        config=config, gen_config=gc))[0, len(prompt):]
    assert got == want.tolist()
    assert rid == 0 and not cb.pending()


def test_one_round_a_dispatch_logprobs_are_engine_scores_and_stay_on_the_device(models):
    """Logprobs at ``spec_rounds=1``: every emitted token's is
    ``engine.score``'s at its position, and the pending token's never
    leaves the device outside the packed fetch — the admission counts the
    one fetch it counts without logprobs (the prompt lengths'), and a whole
    run as many fetches as without."""
    import jax.numpy as jnp

    from jax_llama_tpu.engine import score

    params, config, draft_params, draft_config = models
    prompt, max_new = [5, 17, 99, 3, 42, 8, 61, 2], 8

    def run(logprobs):
        cb = ContinuousBatcher(
            params, config, n_slots=1, max_len=64, logprobs=logprobs,
            draft_params=draft_params, draft_config=draft_config, n_draft=3,
        )
        cb.submit(prompt, max_new_tokens=max_new)
        cb._admit()
        admission = cb.host_syncs_total
        toks, lps = [], []
        while cb.pending():
            for _, tok, _, *rest in cb.step():
                toks.append(tok)
                lps += rest
        return admission, cb.host_syncs_total, toks, lps

    admission, total, toks, lps = run(True)
    plain_admission, plain_total, plain_toks, _ = run(False)
    assert admission == plain_admission == 1
    assert total == plain_total
    assert toks == plain_toks and len(lps) == len(toks) == max_new
    sc = np.asarray(score(
        params, jnp.asarray([prompt + toks], jnp.int32), config=config))[0]
    want = [float(sc[len(prompt) + i - 1]) for i in range(len(toks))]
    np.testing.assert_allclose(lps, want, atol=1e-4, rtol=1e-4)


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_spec_batcher_sampled_matches_standalone(models):
    """Sampled speculative serving: a sampled slot must emit BIT-identical
    tokens to a standalone seeded ``generate_speculative`` of the same
    request (same key-split topology, same warp math), while a greedy slot
    sharing the batch stays token-identical to the plain greedy batcher."""
    import jax.numpy as jnp

    from jax_llama_tpu.engine import GenerationConfig
    from jax_llama_tpu.spec_decode import generate_speculative

    params, config, draft_params, draft_config = models
    rng = np.random.RandomState(5)
    sampled_prompt = rng.randint(1, 128, size=7).tolist()
    greedy_prompt = rng.randint(1, 128, size=5).tolist()

    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64,
        draft_params=draft_params, draft_config=draft_config, n_draft=3,
    )
    r0 = cb.submit(
        sampled_prompt, max_new_tokens=10, temperature=0.9, top_p=0.8,
        seed=123,
    )
    r1 = cb.submit(greedy_prompt, max_new_tokens=10)
    results = cb.run_to_completion()

    # Greedy slot: unchanged vs the plain (non-spec) greedy batcher.
    _, pres = _plain(params, config, [greedy_prompt], 10)
    assert results[r1] == list(pres.values())[0]

    # Sampled slot: bit-identical to the standalone engine with its seed.
    gc = GenerationConfig(
        max_new_tokens=10, temperature=0.9, top_p=0.8, top_k=None,
        stop_tokens=(), pad_id=0,
    )
    P = len(sampled_prompt)
    buf, _ = generate_speculative(
        params, draft_params,
        jnp.asarray([sampled_prompt], jnp.int32),
        jnp.ones((1, P), bool),
        jax.random.PRNGKey(123),
        target_config=config, draft_config=draft_config, gen_config=gc,
        n_draft=3, mesh=None,
    )
    want = np.asarray(buf)[0, P:P + 10].tolist()
    assert results[r0] == want


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_spec_batcher_logprobs_match_engine_score(models):
    """logprobs=True composes with speculative decoding: every emitted
    token's logprob equals ``engine.score``'s teacher-forced
    log p(token | prefix) at the same position — for greedy AND sampled
    slots, whether the token was emitted from an accepted draft prefix,
    a rejection replacement/bonus, or the carried tau.  Tokens themselves
    stay identical to the logprobs=False batcher (the logprob read is
    pure observation)."""
    import jax.numpy as jnp

    from jax_llama_tpu.engine import score

    params, config, draft_params, draft_config = models
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 128, size=n).tolist() for n in (6, 9)]

    def run(logprobs):
        cb = ContinuousBatcher(
            params, config, n_slots=2, max_len=64, logprobs=logprobs,
            draft_params=draft_params, draft_config=draft_config,
            n_draft=3,
        )
        r0 = cb.submit(prompts[0], max_new_tokens=8)  # greedy
        r1 = cb.submit(
            prompts[1], max_new_tokens=8, temperature=0.7, top_p=0.9,
            seed=7,
        )
        got, lps = {}, {}
        while cb.pending():
            for rid, tok, done, *rest in cb.step():
                got.setdefault(rid, []).append(tok)
                if rest:
                    lps.setdefault(rid, []).append(rest[0])
        return r0, r1, got, lps

    r0, r1, got, lps = run(True)
    p0, p1, got_plain, _ = run(False)
    assert got[r0] == got_plain[p0] and got[r1] == got_plain[p1]

    for rid, prompt in ((r0, prompts[0]), (r1, prompts[1])):
        toks = got[rid]
        assert len(lps[rid]) == len(toks)
        full = jnp.asarray([prompt + toks], jnp.int32)
        sc = np.asarray(score(params, full, config=config))[0]
        want = [float(sc[len(prompt) + i - 1]) for i in range(len(toks))]
        np.testing.assert_allclose(lps[rid], want, atol=1e-4, rtol=1e-4)


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_spec_batcher_sampled_only_batch(models):
    """Two sampled slots with different seeds/policies, no greedy rows:
    each must reproduce its standalone seeded run."""
    import jax.numpy as jnp

    from jax_llama_tpu.engine import GenerationConfig
    from jax_llama_tpu.spec_decode import generate_speculative

    params, config, draft_params, draft_config = models
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 128, size=6).tolist(),
               rng.randint(1, 128, size=9).tolist()]
    policies = [dict(temperature=0.7, top_p=1.0, seed=7),
                dict(temperature=1.3, top_p=0.9, top_k=20, seed=8)]

    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64,
        draft_params=draft_params, draft_config=draft_config, n_draft=2,
    )
    rids = [
        cb.submit(p, max_new_tokens=8, **pol)
        for p, pol in zip(prompts, policies)
    ]
    results = cb.run_to_completion()

    for p, pol, rid in zip(prompts, policies, rids):
        gc = GenerationConfig(
            max_new_tokens=8, temperature=pol["temperature"],
            top_p=pol["top_p"], top_k=pol.get("top_k"),
            stop_tokens=(), pad_id=0,
        )
        P = len(p)
        buf, _ = generate_speculative(
            params, draft_params, jnp.asarray([p], jnp.int32),
            jnp.ones((1, P), bool), jax.random.PRNGKey(pol["seed"]),
            target_config=config, draft_config=draft_config,
            gen_config=gc, n_draft=2, mesh=None,
        )
        want = np.asarray(buf)[0, P:P + 8].tolist()
        assert results[rid] == want, f"slot {rid}"


# ---------------------------------------------------------------------------
# Fused R-round chunking (spec_rounds > 1): CPU parity matrix
# ---------------------------------------------------------------------------

def _spec_matrix(models, R, *, logprobs=False, stop=(), int8=False,
                 self_draft=False, **cb_kw):
    """The shared request mix — greedy finishing mid-chunk (max_new 5),
    greedy full-budget, two seeded sampled policies — 4 requests over
    2 slots, so R also ramps around queue-driven admissions.  Returns
    (per-request tokens, per-request logprobs, the acceptance pattern
    (proposed, accepted))."""
    params, config, draft_params, draft_config = models
    if self_draft:
        draft_params, draft_config = params, config
    if int8:
        config = dataclasses.replace(config, kv_cache_dtype="int8")
        draft_config = dataclasses.replace(
            draft_config, kv_cache_dtype="int8"
        )
        cb_kw.setdefault("block_size", 16)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 128, size=n).tolist() for n in (5, 9, 14, 6)]
    policies = [
        dict(max_new_tokens=5),
        dict(max_new_tokens=11),
        dict(max_new_tokens=9, temperature=0.9, seed=11),
        dict(max_new_tokens=12, temperature=0.7, top_p=0.8, seed=12),
    ]
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, spec_rounds=R,
        draft_params=draft_params, draft_config=draft_config, n_draft=3,
        logprobs=logprobs, stop_tokens=stop, **cb_kw,
    )
    rids = [cb.submit(p, **pol) for p, pol in zip(prompts, policies)]
    toks, lps = {}, {}
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 500
        for ev in cb.step():
            toks.setdefault(ev[0], []).append(ev[1])
            if logprobs:
                lps.setdefault(ev[0], []).append(ev[3])
    return (
        [toks[r] for r in rids],
        [lps.get(r) for r in rids],
        (cb.drafts_proposed, cb.drafts_accepted),
    )


# Both cells ride the slow tier (r06 rebalanced R=2 out; r08 moved
# R=4 too — at ~30 s it was the single heaviest tier-1 test while the
# suite sat within 1% of its 870 s budget).  The R>1 ≡ R=1
# identity class keeps tier-1 coverage through the stop-token /
# non-finite mid-chunk cells below and the perf-smoke spec matrix;
# the full greedy+sampled+acceptance-pattern matrix still runs in the
# unfiltered suite (plain `pytest tests/`, `make chaos`).
@pytest.mark.parametrize("R", [
    pytest.param(2, marks=pytest.mark.slow),
    pytest.param(4, marks=pytest.mark.slow),
])
def test_spec_rounds_token_identity_greedy_and_sampled(models, R):
    """R ∈ {2, 4} × {greedy, seeded-sampled} × max_new mid-chunk:
    tokens AND the acceptance pattern identical to one round a
    dispatch (which the tests above pin against standalone
    engine/spec oracles)."""
    base, _, base_acc = _spec_matrix(models, 1)
    got, _, got_acc = _spec_matrix(models, R)
    assert got == base
    assert got_acc == base_acc


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_spec_rounds_token_identity_logprobs(models):
    """logprobs ride the packed fetch bitcast: same values as one round
    a dispatch, token for token, for carried-tau, accepted-draft and
    replacement/bonus emissions alike."""
    base, base_lp, _ = _spec_matrix(models, 1, logprobs=True)
    got, got_lp, _ = _spec_matrix(models, 4, logprobs=True)
    assert got == base
    for a, b in zip(got_lp, base_lp):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# slow (r17 budget rebalance, ~9 s): the two composing contracts keep
# tier-1 pins — the spec stop set via test_spec_batcher_stop_tokens,
# mid-chunk stop truncation via test_serving_chunked.py's stop cells —
# so the composed drill rides slow (unfiltered suite runs it).
@pytest.mark.slow
def test_spec_rounds_stop_token_mid_chunk(models):
    """A stop token landing INSIDE a round's accepted prefix, inside a
    fused chunk (self-draft => high acceptance => multi-token
    prefixes): the on-device accepted-prefix emit fold must end the
    request at exactly the token the host loop would."""
    params, config, _, _ = models
    prompt = [5, 17, 99, 3, 42]

    def run(R, stop=()):
        cb = ContinuousBatcher(
            params, config, n_slots=1, max_len=64, stop_tokens=stop,
            draft_params=params, draft_config=config, n_draft=3,
            spec_rounds=R,
        )
        rid = cb.submit(prompt, max_new_tokens=16)
        return cb.run_to_completion()[rid]

    free = run(1)
    j = next(i for i in range(1, len(free)) if free[i] not in free[:i])
    stop = free[j]
    want = run(1, stop=(stop,))
    got = run(4, stop=(stop,))
    assert want == free[:j + 1]
    assert got == want


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_spec_rounds_int8_kv(models):
    """The int8 pools' quantized branches (per-round scale-plane writes
    for BOTH the target and draft pools inside the scan) must match
    their emissions at one round a dispatch."""
    base, _, base_acc = _spec_matrix(models, 1, int8=True)
    got, _, got_acc = _spec_matrix(models, 4, int8=True)
    assert got == base
    assert got_acc == base_acc


def test_spec_rounds_nonfinite_mid_chunk(models):
    """NaN target logits under round fusion: the verify's -1 acceptance
    sentinel folds the row out mid-chunk, the round is never committed,
    and exactly that request fails."""
    params, config, _, _ = models
    bad = dict(params)
    bad["lm_head"] = params["lm_head"] * float("nan")
    cb = ContinuousBatcher(
        bad, config, n_slots=1, max_len=64,
        draft_params=params, draft_config=config, n_draft=2,
        spec_rounds=4,
    )
    rid = cb.submit([5, 17, 99, 3], max_new_tokens=8)
    out = cb.run_to_completion()
    failed = cb.pop_failed()
    assert rid not in out
    assert failed and failed[0][0] == rid
    assert not cb.pending()
    assert sorted(cb.free_blocks) == list(range(cb.n_blocks))


# ---------------------------------------------------------------------------
# Fault-tolerance semantics with round fusion enabled
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
def reference(models):
    """Fault-free plain-greedy outputs (the identity oracle — the draft
    only ever changes speed)."""
    params, config, _, _ = models
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    rids = [cb.submit(list(p), max_new_tokens=MAX_NEW) for p in PROMPTS]
    out = cb.run_to_completion()
    return [out[r] for r in rids]


@pytest.mark.faults
# slow (r06 budget rebalance, ~12 s): still in `make faults` / `make
# chaos`; the R = 1 spec drills (tests/test_degrade.py) keep tier-1 coverage.
@pytest.mark.slow
def test_chunked_spec_fault_recovers_token_exact(models, reference):
    """A spec_decode-site fault mid-chunk (the site fires once per
    R-round dispatch): recovery rebuilds a fused-spec batcher and
    replays from delivered tokens — greedy outputs identical to the
    fault-free plain run, and a streaming client sees each token
    exactly once even though tokens now arrive in R-round bursts."""
    params, config, draft_params, draft_config = models
    inj = FaultInjector("spec_decode@2:error")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64,
        draft_params=draft_params, draft_config=draft_config, n_draft=2,
        spec_rounds=4, fault_injector=inj,
    )
    results = {}
    # The spec_decode site is attributable: use a threshold ABOVE the
    # faults this drill injects so the drill exercises rebuild+replay,
    # not quarantine.
    with LLMServer(cb, quarantine_threshold=5) as srv:
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
        # The rebuilt batcher still runs fused speculative serving.
        assert srv.batcher.spec and srv.batcher.spec_rounds == 4


@pytest.mark.faults
@pytest.mark.slow
def test_chunked_spec_nan_isolation_per_request(models, reference):
    """An armed nan poison under round fusion fails exactly one request
    with a clean 500 (its chunk tokens are discarded, never streamed);
    the neighbor slot completes token-identically.

    Slow tier (r14 budget rebalance, ~11 s server-backed drill; still
    in `make chaos`/`make faults` via its faults marker): the spec
    non-finite fold-out semantics stay tier-1-pinned by
    test_spec_rounds_nonfinite_mid_chunk, and per-request nan
    isolation at serving level by test_degrade's guard-isolation
    drills on the chunked path."""
    params, config, draft_params, draft_config = models
    inj = FaultInjector("step@1:nan")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64,
        draft_params=draft_params, draft_config=draft_config, n_draft=2,
        spec_rounds=4, fault_injector=inj,
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
def test_chunked_spec_quarantine_falls_back_to_chunked_decode(
    models, reference
):
    """spec_decode faults past the threshold quarantine the feature and
    the batcher rebuilds WITHOUT the draft model but WITH the original
    decode_chunk / spec_rounds configuration — degraded speculative
    serving lands on plain CHUNKED decode, not the per-token loop, and
    requests replay token-identically."""
    params, config, draft_params, draft_config = models
    inj = FaultInjector("spec_decode~1.0:error")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        draft_params=draft_params, draft_config=draft_config, n_draft=2,
        spec_rounds=4, fault_injector=inj,
    )
    with LLMServer(
        cb, quarantine_threshold=2, quarantine_cooldown_s=600.0
    ) as srv:
        _, body = _post(
            srv.address,
            {"prompt": PROMPTS[0], "max_new_tokens": MAX_NEW},
        )
        assert body["tokens"] == reference[0]
        assert srv.degrade.quarantined() == ("spec_decode",)
        # The fallback batcher is plain (no draft) but keeps the whole
        # chunk configuration for the day spec_decode probes healthy.
        assert not srv.batcher.spec
        assert srv.batcher.decode_chunk == 4
        assert srv.batcher.spec_rounds == 4
        # And keeps serving: a second request completes on the fallback.
        _, body2 = _post(
            srv.address,
            {"prompt": PROMPTS[1], "max_new_tokens": MAX_NEW},
        )
        assert body2["tokens"] == reference[1]


def test_spec_batcher_staggered_admission(models):
    """Requests entering mid-flight under overcommit must still match the
    plain batcher exactly."""
    params, config, draft_params, draft_config = models
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 128, size=rng.randint(3, 10)).tolist()
               for _ in range(4)]
    prids, pres = _plain(params, config, prompts, 10)

    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, block_size=16, n_blocks=5,
        draft_params=draft_params, draft_config=draft_config, n_draft=2,
    )
    rids = {}
    results = {}
    rids[cb.submit(prompts[0], max_new_tokens=10)] = 0
    submitted = 1
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 300
        for rid, tok, done in cb.step():
            results.setdefault(rid, []).append(tok)
        if submitted < len(prompts):
            rids[cb.submit(prompts[submitted], max_new_tokens=10)] = submitted
            submitted += 1
    for rid, pi in rids.items():
        assert results[rid] == pres[prids[pi]], f"prompt {pi}"
