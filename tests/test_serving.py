"""Continuous batching: requests entering/leaving slots independently must
each reproduce exactly what a standalone greedy generate produces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.engine import GenerationConfig, generate
from jax_llama_tpu.serving import ContinuousBatcher

CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=128, dtype="float32", param_dtype="float32",
)


def _reference(params, config, prompt, max_new, stop=()):
    """Standalone greedy generate for one prompt, trimmed like the batcher:
    tokens up to and including the stop token / max_new."""
    P = len(prompt)
    Pp = 1 << max(P - 1, 1).bit_length()
    toks = np.zeros((1, Pp), np.int32)
    mask = np.zeros((1, Pp), bool)
    toks[0, Pp - P:] = prompt
    mask[0, Pp - P:] = True
    gc = GenerationConfig(
        max_new_tokens=max_new, temperature=0.0, stop_tokens=tuple(stop),
        pad_id=0,
    )
    out = np.asarray(
        generate(params, jnp.asarray(toks), jnp.asarray(mask),
                 jax.random.PRNGKey(0), config=config, gen_config=gc)
    )[0, Pp:]
    emitted = []
    for t in out.tolist():
        emitted.append(t)
        if t in stop or len(emitted) >= max_new:
            break
    return emitted


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


def test_single_request_matches_generate(model):
    params, config = model
    prompt = [5, 17, 99, 3, 42]
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    rid = cb.submit(prompt, max_new_tokens=16)
    results = cb.run_to_completion()
    assert results[rid] == _reference(params, config, prompt, 16)


def test_staggered_requests_match_generate(model):
    """Requests submitted mid-flight (while other slots are decoding) must
    be unaffected by their neighbors."""
    params, config = model
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 128, size=rng.randint(3, 12)).tolist()
               for _ in range(6)]
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    rids = {}
    results = {}
    # two initial requests; submit the rest as steps proceed
    rids[cb.submit(prompts[0], max_new_tokens=10)] = 0
    rids[cb.submit(prompts[1], max_new_tokens=7)] = 1
    submitted = 2
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 500
        for rid, tok, done in cb.step():
            results.setdefault(rid, []).append(tok)
        if submitted < len(prompts):
            rids[cb.submit(prompts[submitted],
                           max_new_tokens=5 + submitted)] = submitted
            submitted += 1
    assert len(results) == len(prompts)
    for rid, pi in rids.items():
        want = _reference(params, config, prompts[pi],
                          5 + pi if pi >= 2 else (10 if pi == 0 else 7))
        assert results[rid] == want, f"prompt {pi}"


def test_stop_tokens_free_slot(model):
    params, config = model
    prompt = [5, 17, 99, 3, 42]
    free_run = _reference(params, config, prompt, 16)
    # First token value that does not also occur earlier in the run
    # becomes the stop (so truncation-at-first-occurrence is unambiguous).
    j = next(
        i for i in range(1, len(free_run)) if free_run[i] not in free_run[:i]
    )
    stop = free_run[j]
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64,
                           stop_tokens=(stop,))
    rid = cb.submit(prompt, max_new_tokens=16)
    results = cb.run_to_completion()
    assert results[rid] == free_run[:j + 1]
    assert not cb.pending()


def test_capacity_validation(model):
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=32)
    with pytest.raises(ValueError, match="capacity"):
        cb.submit(list(range(1, 30)), max_new_tokens=16)


def test_queue_overflow_waits(model):
    """More requests than slots: the queue drains as slots free."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64)
    r1 = cb.submit([4, 5, 6], max_new_tokens=4)
    r2 = cb.submit([7, 8, 9], max_new_tokens=4)
    results = cb.run_to_completion()
    assert set(results) == {r1, r2}
    assert results[r1] == _reference(params, config, [4, 5, 6], 4)
    assert results[r2] == _reference(params, config, [7, 8, 9], 4)


def test_capacity_check_uses_block_padded_length(model):
    """A 33-token prompt pads to the next block multiple (48 at block 16);
    with max_len=56 and max_new=16 the padded start (48) + 16 > 56 must be
    rejected up front — accepting it would silently drop decode KV writes
    past the reservation."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=56,
                           block_size=16)
    assert cb.block_size == 16
    with pytest.raises(ValueError, match="padded"):
        cb.submit(list(range(1, 34)), max_new_tokens=16)
    # 33 -> 48, 48 + 8 = 56 fits exactly
    rid = cb.submit(list(range(1, 34)), max_new_tokens=8)
    results = cb.run_to_completion()
    assert results[rid] == _reference(params, config, list(range(1, 34)), 8)


def test_no_pow2_waste(model):
    """Block padding reserves ceil((padded+max_new)/block) blocks — a
    65-token prompt at block 16 reserves 96 slots of KV (not the 128 a
    pow2 bucket would), so two such requests fit a 12-block pool."""
    params, config = model
    prompt = list(np.random.RandomState(1).randint(1, 128, size=65))
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           block_size=16, n_blocks=12)
    r1 = cb.submit(prompt, max_new_tokens=8)
    r2 = cb.submit(prompt[:10], max_new_tokens=8)
    cb._admit()  # submit only queues; admission is a step-boundary batch
    # 65 -> 80 padded, +8 -> 88 -> 6 blocks; 10 -> 16, +8 -> 24 -> 2 blocks
    assert cb.slots[0] is not None and cb.slots[1] is not None
    results = cb.run_to_completion()
    assert results[r1] == _reference(params, config, prompt, 8)
    assert results[r2] == _reference(params, config, prompt[:10], 8)


def test_overcommit_pool_queues_until_blocks_free(model):
    """The pool may be smaller than n_slots x max_len (overcommit):
    requests whose reservation doesn't fit wait in the queue and run once
    completions free blocks — with contiguous per-slot regions this
    workload could not be configured at all."""
    params, config = model
    # 2 slots x max_len 96 would need 192 contiguous slots; pool holds 96.
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=96,
                           block_size=16, n_blocks=6)
    prompts = [[4, 5, 6], [7, 8, 9], [10, 11, 12]]
    rids = [cb.submit(p, max_new_tokens=30) for p in prompts]
    cb._admit()  # submit only queues; admission is a step-boundary batch
    # each request reserves ceil((16+30)/16) = 3 blocks; only two fit at
    # once, the third queues.
    assert sum(s is not None for s in cb.slots.values()) == 2
    assert len(cb.queue) == 1
    results = cb.run_to_completion()
    for rid, p in zip(rids, prompts):
        assert results[rid] == _reference(params, config, p, 30)
    assert sorted(cb.free_blocks) == list(range(6))


def test_oversized_reservation_rejected(model):
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=96,
                           block_size=16, n_blocks=3)
    with pytest.raises(ValueError, match="blocks"):
        cb.submit([1, 2, 3], max_new_tokens=70)


def _reference_sampled(params, config, prompt, max_new, seed, temperature,
                       top_p=None, top_k=None):
    """Standalone SAMPLED generate for one prompt (B=1), trimmed like the
    batcher."""
    P = len(prompt)
    Pp = 1 << max(P - 1, 1).bit_length()
    toks = np.zeros((1, Pp), np.int32)
    mask = np.zeros((1, Pp), bool)
    toks[0, Pp - P:] = prompt
    mask[0, Pp - P:] = True
    gc = GenerationConfig(
        max_new_tokens=max_new, temperature=temperature, top_p=top_p,
        top_k=top_k, stop_tokens=(), pad_id=0,
    )
    out = np.asarray(
        generate(params, jnp.asarray(toks), jnp.asarray(mask),
                 jax.random.PRNGKey(seed), config=config, gen_config=gc)
    )[0, Pp:]
    return out[:max_new].tolist()


def test_per_request_sampling_matches_standalone(model):
    """Each slot's (seed, temperature, top_p, top_k) must reproduce the
    standalone seeded engine.generate of that request exactly, even while
    sharing decode steps with slots running different policies."""
    params, config = model
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 128, size=rng.randint(3, 9)).tolist()
               for _ in range(4)]
    policies = [
        dict(temperature=0.0),
        dict(temperature=0.9, seed=11),
        dict(temperature=0.7, top_p=0.8, seed=12),
        dict(temperature=1.1, top_k=20, seed=13),
    ]
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    rids = [cb.submit(p, max_new_tokens=8, **pol)
            for p, pol in zip(prompts, policies)]
    results = cb.run_to_completion()
    for rid, p, pol in zip(rids, prompts, policies):
        t = pol["temperature"]
        if t == 0.0:
            want = _reference(params, config, p, 8)
        else:
            want = _reference_sampled(
                params, config, p, 8, pol["seed"], t,
                pol.get("top_p"), pol.get("top_k"),
            )
        assert results[rid] == want, pol


def test_sampled_pool_runs_and_varies(model):
    """temperature > 0: the pool samples; different seeds give different
    outputs (overwhelmingly), same seed reproduces."""
    params, config = model
    prompt = [5, 17, 99, 3, 42]

    def run(seed):
        cb = ContinuousBatcher(params, config, n_slots=2, max_len=64,
                               temperature=0.9, seed=seed)
        rid = cb.submit(prompt, max_new_tokens=12)
        return cb.run_to_completion()[rid]

    a, b, c = run(0), run(0), run(1)
    assert a == b            # deterministic per seed
    assert a != c            # varies across seeds
    assert all(0 <= t < 128 for t in a)


def test_int8_kv_paged_batcher(model):
    """The paged pool's quantized branches (scale gather/scatter through
    block tables) must produce the same tokens as the standalone int8-KV
    generate path."""
    params, config = model
    import dataclasses
    qconfig = dataclasses.replace(config, kv_cache_dtype="int8")
    prompt = [5, 17, 99, 3, 42]
    cb = ContinuousBatcher(params, qconfig, n_slots=2, max_len=64,
                           block_size=16)
    assert cb.pool.quantized
    rid = cb.submit(prompt, max_new_tokens=12)
    got = cb.run_to_completion()[rid]
    want = _reference(params, qconfig, prompt, 12)
    assert got == want
    # int8 quantization changes numerics vs fp32 but stays plausible
    assert all(0 <= t < 128 for t in got)


def test_chunked_admission_matches_single_shot(model):
    """Batcher prefill in chunks must yield identical completions."""
    params, config = model
    prompt = list(np.random.RandomState(3).randint(1, 128, size=23))
    want = _reference(params, config, prompt, 10)
    for chunk in (8, 16, None):
        cb = ContinuousBatcher(params, config, n_slots=1, max_len=64,
                               prefill_chunk=chunk)
        rid = cb.submit(prompt, max_new_tokens=10)
        assert cb.run_to_completion()[rid] == want, f"chunk={chunk}"


def test_logprobs_match_engine_score():
    """With logprobs=True the batcher's per-token logprob equals
    engine.score's teacher-forced log p(token | prefix) at the same
    position — for greedy AND sampled slots (the definition is the raw
    model distribution, temperature-independent)."""
    from jax_llama_tpu.engine import score

    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    rng = np.random.RandomState(21)
    prompts = [list(rng.randint(1, 128, n)) for n in (6, 17)]

    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, block_size=16, logprobs=True,
    )
    r0 = cb.submit(prompts[0], max_new_tokens=8)                  # greedy
    r1 = cb.submit(prompts[1], max_new_tokens=8, temperature=0.7,
                   top_p=0.9, seed=5)                             # sampled
    got: dict = {}
    lps: dict = {}
    while cb.pending():
        for rid, tok, done, lp in cb.step():
            got.setdefault(rid, []).append(tok)
            lps.setdefault(rid, []).append(lp)

    for rid, prompt in ((r0, prompts[0]), (r1, prompts[1])):
        toks = got[rid]
        full = jnp.asarray([prompt + toks], jnp.int32)
        # score[t] = log p(full[t+1] | full[:t+1]); emitted token i sits
        # at full position len(prompt)+i, so its score index is
        # len(prompt)+i-1.
        sc = np.asarray(score(params, full, config=config))[0]
        want = [float(sc[len(prompt) + i - 1]) for i in range(len(toks))]
        np.testing.assert_allclose(lps[rid], want, atol=1e-4, rtol=1e-4)




def test_host_threefry_key_layout():
    """_admit builds each request's PRNG key on the host as
    [0, seed & 0xFFFFFFFF] instead of fetching jax.random.PRNGKey from
    the device (a device->host round-trip per admission).  Pin the layout equivalence so a PRNG-impl or
    canonicalization change can't silently fork the batcher's sampled
    outputs from standalone seeded generates."""
    for seed in (0, 1, 7, 2**31 - 1, -1, -12345, (123 << 32) | 7):
        expect = np.asarray(jax.random.PRNGKey(seed))
        host = np.array([0, seed & 0xFFFFFFFF], np.uint32)
        assert (expect == host).all(), (seed, expect, host)


def test_block_size_tiered_default():
    """The default block size trades allocation granularity for kernel
    DMA efficiency as capacity grows (on-chip swept r4: 16k serving
    decode 8.9 -> 5.8 ms/step going 128 -> 512); explicit block_size
    still wins."""
    cfg = get_config(
        "tiny", dim=64, n_layers=2, n_heads=2, n_kv_heads=1,
        vocab_size=128, max_seq_len=16384,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    for max_len, expect in ((512, 32), (2048, 128), (8192, 512),
                            (16384, 512)):
        cb = ContinuousBatcher(params, cfg, n_slots=1, max_len=max_len)
        assert cb.block_size == expect, (max_len, cb.block_size)
    cb = ContinuousBatcher(params, cfg, n_slots=1, max_len=16384,
                           block_size=64)
    assert cb.block_size == 64


def test_describe_names_the_resolved_kernels(model):
    """/debug/bundle's batcher section says which attention path the
    batcher runs and whether the paged kernel can run at this geometry —
    what chip_smoke.py reads to refuse a run that never touched Pallas."""
    params, config = model
    d = ContinuousBatcher(params, config, n_slots=2, max_len=64).describe()
    assert d["attn_impl"] == config.attn_impl
    assert d["use_pallas_kernel"] is True
    assert d["paged_kernel_eligible"] is True
    for gone in ("prefill_kernel", "decode_kernel", "cost_models"):
        assert gone not in d
    g = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, use_pallas_kernel=False,
    ).describe()
    assert g["use_pallas_kernel"] is False
    odd = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, block_size=12,
    ).describe()
    assert odd["paged_kernel_eligible"] is False   # 12 % 8 != 0
