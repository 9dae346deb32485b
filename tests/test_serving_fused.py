"""Fused prefill-decode scheduling (``prefill_budget`` > 0) must be
TOKEN- and logprob-IDENTICAL to the classic admit-then-decode path —
the acceptance matrix of the fused scheduler: prefill_budget ∈
{1 block, 2 blocks, ∞} × {greedy, seeded-sampled} × {prefix-cache
hit/miss} × {int8-KV}, including a row whose first sampled token is
emitted by the SAME dispatch that finished its prefill, and the
stall-free property itself (decode rows keep emitting while a long
prompt is mid-prefill).

The scenario intentionally admits the probe request MID-DECODE — the
only regime where the fused path engages (a cold pool still admits
through the classic batched insert; there is nobody to stall)."""

import dataclasses

import jax
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.serving import ContinuousBatcher

CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=128, dtype="float32", param_dtype="float32",
)
BLOCK = 16


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


def _scenario(
    params, config, budget, *, sampled=False, prefix=False,
    logprobs=True, oracle_prefill_chunk=None, **cb_kw,
):
    """The shared request shape: r0 decodes (admitted cold -> classic
    path either way), then r1 — a 2.5-block prompt — submits mid-decode
    and, with ``budget`` > 0, rides the fused prefill.  ``prefix=True``
    first runs a sharer to warm the prefix cache so r1's chunk walk
    starts at fill0.  Returns ((r0, r1) token lists, (r0, r1) logprob
    lists, batcher)."""
    cb_kw.setdefault("block_size", BLOCK)
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        prefill_budget=budget, logprobs=logprobs,
        prefill_chunk=oracle_prefill_chunk, **cb_kw,
    )
    toks, lps = {}, {}

    def pump(n=None):
        guard = 0
        while True:
            guard += 1
            assert guard < 500
            for ev in cb.step():
                toks.setdefault(ev[0], []).append(ev[1])
                if logprobs:
                    lps.setdefault(ev[0], []).append(ev[3])
            if n is not None and guard >= n:
                return
            if n is None and not cb.pending():
                return

    rng = np.random.RandomState(3)
    shared = rng.randint(1, 128, size=34).tolist()  # 2 full keyed blocks
    if prefix:
        cb.submit(shared + [7], max_new_tokens=2)
        pump()
    pol0 = (
        dict(max_new_tokens=9, temperature=0.8, seed=7)
        if sampled else dict(max_new_tokens=9)
    )
    pol1 = (
        dict(max_new_tokens=6, temperature=0.7, top_p=0.9, seed=12)
        if sampled else dict(max_new_tokens=6)
    )
    r0 = cb.submit([5, 17, 99, 3], **pol0)
    pump(2)  # r0 admitted and mid-decode
    r1 = cb.submit(shared + [9, 11], **pol1)
    pump()
    return (toks[r0], toks[r1]), (lps.get(r0), lps.get(r1)), cb


@pytest.fixture(scope="module")
def classic_oracle(model):
    """Memoized classic-path (budget 0) runs: each (sampled, prefix)
    cell of the matrix shares ONE oracle run across the three budget
    parametrizations instead of recomputing it per test."""
    params, config = model
    cache = {}

    def get(sampled, prefix):
        key = (sampled, prefix)
        if key not in cache:
            t, l, cb0 = _scenario(
                params, config, 0, sampled=sampled, prefix=prefix,
            )
            assert cb0.fused_admissions_total == 0
            cache[key] = (t, l)
        return cache[key]

    return get


_SLOW = pytest.mark.slow
@pytest.mark.parametrize(
    "budget,sampled,prefix",
    [
        # Tier-1 slice (r14 budget rebalance, narrowed again in r17 with
        # the suite back AT its 870 s ceiling): the block-budget greedy
        # cell stays as THE tier-1 fused-identity pin.  The ∞-budget
        # sampled cell joined the slow tier in r17 (~16 s): sampled-
        # policy chunked identity stays tier-1-pinned by
        # test_serving_chunked's sampled cells and test_kvcache's
        # sampled radix smoke, and the fused scheduling contract by
        # test_first_token_emitted_by_prefill_completion_dispatch below.
        # The prefix-hit fused cells ride the slow tier because
        # fused×prefix-hit token identity is ALREADY tier-1-pinned by
        # test_kvcache's {fused, classic} × hit-depth parity matrix
        # (PR 6) — this file's hit cells re-proved the same contract at
        # ~18 s of compile-bound cost.  The FULL
        # {block, 2·block, ∞} × {greedy, sampled} × {hit, miss} cross
        # runs in the unfiltered suite (slow marks).
        (BLOCK, False, False),
        pytest.param(4096, True, False, marks=_SLOW),
        pytest.param(BLOCK, True, True, marks=_SLOW),
        pytest.param(4096, False, True, marks=_SLOW),
        pytest.param(BLOCK, True, False, marks=_SLOW),
        pytest.param(BLOCK, False, True, marks=_SLOW),
        pytest.param(4096, False, False, marks=_SLOW),
        pytest.param(4096, True, True, marks=_SLOW),
        pytest.param(2 * BLOCK, False, False, marks=_SLOW),
        pytest.param(2 * BLOCK, True, False, marks=_SLOW),
        pytest.param(2 * BLOCK, False, True, marks=_SLOW),
        pytest.param(2 * BLOCK, True, True, marks=_SLOW),
    ],
)
def test_fused_token_and_logprob_identity(
    model, classic_oracle, budget, sampled, prefix,
):
    """The core matrix: every budget (one block per dispatch, two, the
    whole prompt in one chunk) emits exactly what the classic
    admit-then-decode path emits — tokens exact, logprobs to fp32
    noise — for greedy and seeded-sampled policies, cold and
    prefix-cache-hit admissions."""
    params, config = model
    base_t, base_l = classic_oracle(sampled, prefix)
    got_t, got_l, cb1 = _scenario(
        params, config, budget, sampled=sampled, prefix=prefix,
    )
    assert cb1.fused_admissions_total >= 1  # r1 rode the fused path
    assert cb1.prefill_chunks_total >= 1
    assert got_t == base_t
    for a, b in zip(got_l, base_l):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    if prefix:
        # The fused admission reused the warmed chain (fill0 walk).
        assert cb1.prefix_requests_hit >= 1


# slow (r06 budget rebalance, ~23 s): int8 chunked identity stays in
# tier-1 via test_serving_chunked's int8 cell; the fused int8 cell
# runs in the full suite / pytest -m slow.
@pytest.mark.slow
def test_fused_token_identity_int8_kv(model):
    """int8-KV pools quantize a chunk's KV when it lands, so WHERE the
    chunk boundaries fall is part of the numerics: the oracle is the
    classic path with the SAME prefill chunking
    (``prefill_chunk=budget``), against which the fused path is
    token-exact and logprob-identical to fp32 noise.  Seeded-sampled
    policies (the stricter cell: they consume the key chains greedy
    never touches)."""
    params, config = model
    qconfig = dataclasses.replace(config, kv_cache_dtype="int8")
    budget = 2 * BLOCK
    base_t, base_l, _ = _scenario(
        params, qconfig, 0, sampled=True,
        oracle_prefill_chunk=budget,
    )
    got_t, got_l, cb1 = _scenario(
        params, qconfig, budget, sampled=True,
    )
    assert cb1.fused_admissions_total >= 1
    assert got_t == base_t
    for a, b in zip(got_l, base_l):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_fused_token_identity_flash_prefill(model):
    """attn_impl='auto' with a >8-token budget runs the PREFILL half of
    the fused program through the flash kernel (the view's scalar write
    index keeps it off the must-xla path) — still token-identical to
    the classic xla admit-then-decode path.  block_size=8 keeps the
    cold classic admissions on xla, so flash only ever runs inside
    ``_fused_chunk`` here.  Every benchmark cell's prefill path:
    tier-1 since PR 30 (~18 s, most of it the interpret-mode flash
    trace)."""
    params, config = model
    auto_cfg = config.replace(attn_impl="auto")
    base_t, _, _ = _scenario(
        params, config, 0, sampled=True, logprobs=False, block_size=8,
    )
    got_t, _, cb1 = _scenario(
        params, auto_cfg, 16, sampled=True, logprobs=False,
        block_size=8,
    )
    assert cb1.fused_admissions_total >= 1
    assert cb1.prefill_chunks_total >= 2  # 36-token prompt, 16/chunk
    assert got_t == base_t


def test_fused_token_identity_gathered_fallback(model):
    """use_pallas_kernel=False: the decode half of the fused program
    runs the gathered-view scan and the prefill half is unchanged —
    still identical to the classic path on the same fallback: what a
    ``paged_kernel`` quarantine lands on (tier-1 since PR 30, ~9 s)."""
    params, config = model
    base_t, _, _ = _scenario(
        params, config, 0, use_pallas_kernel=False, logprobs=False,
    )
    got_t, _, cb1 = _scenario(
        params, config, 2 * BLOCK, use_pallas_kernel=False,
        logprobs=False,
    )
    assert cb1.fused_admissions_total >= 1
    assert got_t == base_t


def test_first_token_emitted_by_prefill_completion_dispatch(model):
    """The tentpole's latency contract: the dispatch whose prefill
    chunk lands the LAST prompt token also emits the row's first
    sampled token (the row folds into the decode mask mid-dispatch) —
    and while the prompt is mid-prefill, the resident decode row keeps
    emitting every dispatch (zero full-prefill stalls) at a chunk size
    that did NOT collapse to 1."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        block_size=BLOCK, prefill_budget=BLOCK,
    )
    r0 = cb.submit([5, 17, 99, 3], max_new_tokens=40)
    cb.step()
    cb.step()
    rng = np.random.RandomState(3)
    r1 = cb.submit(rng.randint(1, 128, size=40).tolist(), max_new_tokens=6)
    completion_events = None
    mid_prefill_steps = 0
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 300
        mid_before = cb._pf is not None
        if mid_before:
            assert cb.stats()["prefill_tokens_inflight"] > 0
        evs = cb.step()
        if mid_before and cb._pf is None and completion_events is None:
            completion_events = evs
        elif mid_before and cb._pf is not None:
            mid_prefill_steps += 1
            # Stall-free: the decode row emitted THIS dispatch, at an
            # un-collapsed chunk size, and r1 (mid-prefill) did not.
            assert any(ev[0] == r0 for ev in evs)
            assert not any(ev[0] == r1 for ev in evs)
            assert cb.decode_chunk_last > 1
    # 40 tokens at a 16-token budget: at least one genuinely
    # mid-prefill dispatch before the completing one.
    assert mid_prefill_steps >= 1
    assert completion_events is not None
    assert any(ev[0] == r1 for ev in completion_events)


def test_cancel_mid_prefill_frees_admission(model):
    """Cancelling the in-flight admission mid-prefill drops it cleanly:
    its blocks free, no fused dispatches reference it afterwards, and
    the next queued request admits."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        block_size=BLOCK, prefill_budget=BLOCK,
    )
    toks: dict = {}

    def pump(n):
        for _ in range(n):
            for ev in cb.step():
                toks.setdefault(ev[0], []).append(ev[1])

    r0 = cb.submit([5, 17, 99, 3], max_new_tokens=16)
    pump(2)
    rng = np.random.RandomState(3)
    r1 = cb.submit(rng.randint(1, 128, size=40).tolist(), max_new_tokens=6)
    r2 = cb.submit([7, 8, 9], max_new_tokens=4)
    pump(1)  # r1's prefill starts (40 tokens > one 16-token chunk)
    assert cb._pf is not None and cb._pf.req.rid == r1
    free_before = len(cb.free_blocks)
    assert cb.cancel(r1)
    assert cb._pf is None
    assert len(cb.free_blocks) > free_before
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 300
        pump(1)
    assert r1 not in toks
    assert len(toks[r2]) == 4  # the next queued request admitted fine
    assert len(toks[r0]) == 16


def test_rebuild_drops_inflight_prefill(model):
    """Crash-recovery rebuild: the fresh batcher has no prefill in
    flight; resubmitting the mid-prefill request (the server's replay
    contract) regenerates it token-identically."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        block_size=BLOCK, prefill_budget=BLOCK,
    )
    oracle = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        block_size=BLOCK,
    )
    prompt = np.random.RandomState(3).randint(1, 128, size=40).tolist()
    ro = oracle.submit(list(prompt), max_new_tokens=6)
    want = oracle.run_to_completion()[ro]

    cb.submit([5, 17, 99, 3], max_new_tokens=12)
    cb.step()
    cb.step()
    cb.submit(list(prompt), max_new_tokens=6)
    cb.step()
    assert cb._pf is not None  # mid-prefill "crash" point
    cb2 = cb.rebuild()
    assert cb2._pf is None and cb2.prefill_budget == cb.prefill_budget
    r = cb2.submit(list(prompt), max_new_tokens=6)
    assert cb2.run_to_completion()[r] == want
