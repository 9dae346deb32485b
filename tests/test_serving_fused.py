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
import jax.numpy as jnp
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params, serving
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


def test_a_row_that_ends_inside_a_prefill_leaves_k_alone(model):
    """With a prefill in flight and nothing decoding (the riding row's
    last token fell inside it) the fused dispatch keeps the K of every
    other fused dispatch: no K=1 variant of a chunk shape that this state
    alone would reach (such a variant compiled inside a served window,
    PERF.md section 6, PR 32).  The tokens are the classic path's."""
    params, config = model
    prompt = np.random.RandomState(3).randint(1, 128, size=56).tolist()

    def serve(budget):
        cb = ContinuousBatcher(
            params, config, n_slots=2, max_len=80, decode_chunk=4,
            block_size=BLOCK, prefill_budget=budget,
        )
        r0 = cb.submit([5, 17, 99, 3], max_new_tokens=6)
        cb.step()
        r1 = cb.submit(prompt, max_new_tokens=6)
        toks, alone = {}, []
        while cb.pending():
            lonely = cb._pf is not None and not bool(np.any(cb.active))
            for ev in cb.step():
                toks.setdefault(ev[0], []).append(ev[1])
            if lonely:
                alone.append(cb.decode_chunk_last)
        return toks[r0], toks[r1], alone

    fused0, fused1, alone = serve(BLOCK)
    classic0, classic1, _ = serve(0)
    assert (fused0, fused1) == (classic0, classic1)
    # 56 tokens at a 16-token budget outlast r0's 6 tokens
    assert alone and all(k == 4 for k in alone), alone


def _serve_a_queue(params, config, budget):
    """A holder decodes throughout; one prompt rides the lane ALONE, then
    four arrive at once on the two free slots of three, so the lane has a
    queue behind it, then every slot is taken with requests still queued,
    then the last prompt rides the lane with nobody behind it.  Returns
    (tokens by submit order, batcher)."""
    cb = ContinuousBatcher(
        params, config, n_slots=3, max_len=128, decode_chunk=8,
        block_size=BLOCK, prefill_budget=budget,
    )
    rng = np.random.RandomState(5)
    draw = lambda n: [int(t) for t in rng.randint(1, 128, size=n)]
    toks = {}

    def pump(until):
        for _ in range(300):
            if until():
                return
            for ev in cb.step():
                toks.setdefault(ev[0], []).append(ev[1])
        raise AssertionError("did not get there")

    rids = [cb.submit(draw(4), max_new_tokens=110)]    # idle server: an insert
    pump(lambda: len(toks.get(rids[0], ())) >= 2)
    rids.append(cb.submit(draw(20), max_new_tokens=8))  # two chunks, alone
    pump(lambda: len(toks.get(rids[1], ())) == 8)
    # three chunks each; the first two decode long enough to fill the slots
    rids += [cb.submit(draw(36), max_new_tokens=n) for n in (30, 30, 8, 8)]
    pump(lambda: not cb.pending())
    return [toks[r] for r in rids], cb


@pytest.fixture(scope="module")
def queued_run(model):
    params, config = model
    toks, cb = _serve_a_queue(params, config, BLOCK)
    chunks = [d for d in cb.obs.dispatches if d["kind"] in ("fused", "decode")]
    return toks, cb, chunks


@pytest.mark.parametrize(
    "kind,queued,cap",
    [
        ("fused", True, "_QUEUED_LANE_CAP"),
        ("fused", False, "decode_chunk"),
        ("decode", True, "_QUEUED_CHUNK_CAP"),
    ],
    ids=["lane-with-a-queue", "lane-alone", "decode-with-a-queue"],
)
def test_k_under_a_queue_follows_what_the_queue_waits_for(
    queued_run, kind, queued, cap,
):
    """While requests queue, a dispatch that carries a prompt chunk runs
    the lane's clamp (the queue waits for the lane, which moves once a
    dispatch) and a plain decode dispatch the slot clamp; with nobody
    queued a fused dispatch runs ``decode_chunk``.  The holder's budget is
    never the limit here."""
    _, cb, chunks = queued_run
    assert (cb._QUEUED_LANE_CAP, cb._QUEUED_CHUNK_CAP, cb.decode_chunk) == (
        2, 4, 8)
    ks = [
        d["k"] for d in chunks
        if d["kind"] == kind and (d["queued"] > 0) == queued
    ]
    assert len(ks) >= 3, chunks
    assert set(ks) == {getattr(cb, cap)}, ks


def test_tokens_behind_a_queued_lane_are_the_classic_path_s(model, queued_run):
    """Three requests queued behind the lane, then behind full slots: every
    request's tokens are what classic admission (``prefill_budget=0``)
    serves.  K decides when a token arrives, never which."""
    params, config = model
    toks, cb, chunks = queued_run
    assert max(d["queued"] for d in chunks if d["kind"] == "fused") >= 3
    classic, cb0 = _serve_a_queue(params, config, 0)
    assert cb0.fused_admissions_total == 0
    assert cb0.stats()["fused_dispatches_queued_total"] == 0
    assert cb.fused_admissions_total == 5
    assert [len(t) for t in toks] == [110, 8, 30, 30, 8, 8]
    assert toks == classic


def test_the_record_says_queued_and_the_counters_count_it(queued_run):
    """Every chunk dispatch's record carries the queue length at its submit
    (an insert's does not), and ``fused_dispatches_queued_total`` over
    ``fused_dispatches_total`` is the share of prompt-carrying dispatches
    that met a queue."""
    from jax_llama_tpu.obs import metric_meta

    _, cb, chunks = queued_run
    assert all("queued" in d for d in chunks)
    assert all(
        "queued" not in d for d in cb.obs.dispatches if d["kind"] == "insert")
    fused = [d["queued"] for d in chunks if d["kind"] == "fused"]
    stats = cb.stats()
    # two chunks alone, then four prompts of three: the last has no queue
    assert fused == [0, 0, 3, 3, 3, 2, 2, 2, 1, 1, 1, 0, 0, 0]
    assert stats["fused_dispatches_total"] == 14 == stats["prefill_chunks_total"]
    assert stats["fused_dispatches_queued_total"] == 9
    for name in ("fused_dispatches_total", "fused_dispatches_queued_total"):
        assert metric_meta(name)[0] == "counter"


@pytest.fixture(scope="module")
def starved_run(model):
    """A pool of 8 blocks, 7 of them the holder's reservation: a 20-token
    prompt (2 blocks) waits with two slots free until the holder is done."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=3, max_len=128, decode_chunk=8,
        block_size=BLOCK, prefill_budget=BLOCK, n_blocks=8,
    )
    rng = np.random.RandomState(6)
    cb.submit([int(t) for t in rng.randint(1, 128, size=4)],
              max_new_tokens=100)
    for _ in range(2):
        cb.step()
    waiting = cb.submit([int(t) for t in rng.randint(1, 128, size=20)],
                        max_new_tokens=4)
    out = cb.run_to_completion()
    assert len(out[waiting]) == 4
    return cb, [d for d in cb.obs.dispatches if "queued" in d]


@pytest.mark.parametrize(
    "run,kind,queued,blocked",
    [
        ("queued_run", "fused", True, "lane"),
        ("queued_run", "decode", True, "slot"),
        ("starved_run", "decode", True, "capacity"),
        ("queued_run", "fused", False, None),
    ],
    ids=["lane", "slot", "capacity", "nothing-waits"],
)
def test_the_record_says_why_the_head_stayed_queued(
    request, run, kind, queued, blocked,
):
    """``blocked`` beside ``queued``: the reason the admission pass before
    the submit left the queue's head where it was, decided where ``_admit``
    decides and counted there; None when nothing waits."""
    cb, chunks = request.getfixturevalue(run)[-2:]
    got = [
        d["blocked"] for d in chunks
        if d["kind"] == kind and (d["queued"] > 0) == queued
    ]
    assert len(got) >= 3 and set(got) == {blocked}, got
    counted = cb.obs.admit_blocked_total
    if blocked is not None:
        # Two admission passes a step; the record carries the later one.
        assert counted[blocked] >= len(got)
    assert all(d["blocked"] is None for d in chunks if not d["queued"])
    assert set(counted) <= {"lane", "slot", "capacity"}


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


# ---------------------------------------------------------------------------
# The prompt chunk's write: whole blocks (``_land_chunk``) against the pair
# form (``_scatter_back``), the form every other writer keeps
# ---------------------------------------------------------------------------

_FUSED_STATIC = (
    "config", "n_iter", "pf_chunk", "all_greedy", "mesh", "allow_kernel",
    "with_logprobs", "placed",
)
W_BLK, W_MB, W_ROWS, W_CHUNK = 16, 4, 2, 32   # 2 rows x 4 blocks = the pool
W_NB = W_ROWS * W_MB
# (reserved blocks of the prefilling row, pf_base, pf_off, pf_len)
_WRITE_GEOMETRIES = {
    # columns 0-1 of a whole reservation
    "full-aligned-chunk": (4, 0, 0, 64),
    # last chunk of a 40-token suffix: column 2 is the row's last block,
    # column 3's table entry is the sentinel (a clamped write hits NB - 1)
    "tail-past-reservation": (3, 0, 32, 40),
    # behind a 2-block prefix hit, ending on the view's last column
    "ends-at-column-MB": (4, 32, 0, 32),
}


_PAIR_FORM_TRACED = []


def _pair_form(pool, view, table_r, write_at, C):
    """The parent's write of the prompt chunk: C (block, offset) pairs."""
    _PAIR_FORM_TRACED.append(pool.k.shape)
    return serving._scatter_back(
        pool, view, table_r, write_at[None], jnp.ones((1,), bool), T=C
    )


def _fused_with_pairs(*args, **kwargs):
    # Patched while it is TRACED: a function of its own, so jit's cache
    # can never hand it the block form's trace.
    with pytest.MonkeyPatch.context() as m:
        m.setattr(serving, "_land_chunk", _pair_form)
        return serving._fused_chunk.__wrapped__(*args, **kwargs)


# Undonated, so the pool handed in can be compared with what comes back.
_BLOCK_FORM = jax.jit(
    serving._fused_chunk.__wrapped__, static_argnames=_FUSED_STATIC
)
_PAIR_FORM = jax.jit(_fused_with_pairs, static_argnames=_FUSED_STATIC)


@pytest.fixture(scope="module")
def latent_model():
    """The latent-attention block at test_mla_moe's tiny widths."""
    import json

    from test_mla_moe import BOOKKEEPING, CONFIG_FILE, TINY

    from jax_llama_tpu import config as config_mod

    raw = dict(json.loads(CONFIG_FILE.read_text()), **TINY)
    cfg = config_mod.from_published(
        {k: v for k, v in raw.items() if k not in BOOKKEEPING},
        max_seq_len=W_MB * W_BLK, attn_impl="auto")
    return init_params(jax.random.PRNGKey(3), cfg), cfg


def fused_chunk_operand_shapes(sds, rows, mb, chunk):
    """``_fused_chunk``'s 14 operands after ``params`` and ``pool`` — the
    thirteen of the decode state and the admission's packed vector — as
    ``sds(shape, dtype)`` makes them (tests/test_chip_compile.py places
    them on a described chip)."""
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    return (
        sds((rows, mb), i32), sds((rows,), i32), sds((rows,), i32),
        sds((rows,), i32), sds((rows,), f32), sds((rows,), i32),
        sds((rows,), jnp.bool_), sds((rows,), i32), sds((rows, 1), i32),
        sds((rows, 2), u32), sds((rows,), f32), sds((rows,), f32),
        sds((rows,), i32),
        sds((serving._PF_HEADER + chunk,), i32),
    )


def _write_case(params, config, geometry, seed=0):
    """(args, kwargs) of one ``_fused_chunk`` dispatch over a FULL pool
    whose every slot holds a seeded pattern: row 0 prefills (blocks 0-3),
    row 1 decodes at fill 20 and owns blocks 4-7 — block NB - 1 is its."""
    held, base, off, plen = _WRITE_GEOMETRIES[geometry]
    rng = np.random.RandomState(seed)
    empty = serving.init_pool(config, W_NB, W_BLK)

    def pattern(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.randint(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.uniform(0.1, 1.0, a.shape), a.dtype)

    pos = np.full((W_NB, W_BLK), -1, np.int32)
    pos.reshape(-1)[:base] = np.arange(base)                  # row 0's prefix
    pos[4:6].reshape(-1)[:20] = np.arange(20)                 # row 1's context
    pos[W_NB - 1] = 1000 + np.arange(W_BLK)                   # the canary's
    pool = dataclasses.replace(
        empty, pos=jnp.asarray(pos), **serving._map_planes(pattern, empty)
    )
    table = np.full((W_ROWS, W_MB), W_NB, np.int32)
    table[0, :held] = np.arange(held)
    table[1] = 4 + np.arange(W_MB)
    i32, f32 = jnp.int32, jnp.float32
    toks = rng.randint(1, config.vocab_size, size=64).astype(np.int32)
    vec = serving.pack_prefill(0, base, plen, np.zeros(2, np.uint32), toks, 64)
    vec[serving._PF_OFF] = off
    args = (
        params, pool, jnp.asarray(table), jnp.asarray([held, W_MB], i32),
        jnp.asarray([0, 20], i32), jnp.asarray([0, 5], i32),
        jnp.zeros((W_ROWS,), f32), jnp.asarray([0, 20], i32),
        jnp.asarray([False, True]), jnp.asarray([6, 6], i32),
        jnp.full((W_ROWS, 1), -1, i32), jnp.zeros((W_ROWS, 2), jnp.uint32),
        jnp.zeros((W_ROWS,), f32), jnp.ones((W_ROWS,), f32),
        jnp.zeros((W_ROWS,), i32),
        jnp.asarray(vec),
    )
    kwargs = dict(
        config=config, n_iter=2, pf_chunk=W_CHUNK, all_greedy=True,
        allow_kernel=False,
    )
    first = (base + off) // W_BLK
    live = [b for b in range(first, first + W_CHUNK // W_BLK) if b < held]
    return args, kwargs, live


@pytest.mark.parametrize("geometry", list(_WRITE_GEOMETRIES))
@pytest.mark.parametrize("kind", ["dense", "int8", "latent"])
def test_chunk_lands_by_blocks_bit_equal_to_the_pair_form(
    model, latent_model, kind, geometry,
):
    """``_fused_chunk`` as it is (``_land_chunk``: C // BLK whole-block
    slabs) against the same program with the pair form in its place
    (``_scatter_back``, T = C): every plane the pool has and ``pos`` are
    bit-equal, only the chunk's live blocks and the decoding row's one
    changed, and block NB - 1 — another row's, where a clamped dead write
    would land — is untouched."""
    params, config = latent_model if kind == "latent" else model
    if kind == "int8":
        config = config.replace(kv_cache_dtype="int8")
    args, kwargs, live = _write_case(params, config, geometry)
    pool0 = args[1]

    blocks = _BLOCK_FORM(*args, **kwargs)[8]
    pairs = _PAIR_FORM(*args, **kwargs)[8]
    assert pool0.k.shape in _PAIR_FORM_TRACED  # the oracle IS the pair form
    planes = [n for n in serving._PLANES if getattr(pool0, n) is not None]
    assert planes == {
        "dense": ["k", "v"], "int8": ["k", "v", "k_scale", "v_scale"],
        "latent": ["k"],
    }[kind]
    untouched = [b for b in range(W_NB) if b not in live + [5]]
    assert W_NB - 1 in untouched and live
    for name in planes + ["pos"]:
        got, want, was = (
            np.asarray(getattr(p, name)) for p in (blocks, pairs, pool0)
        )
        assert np.array_equal(got, want), name
        ax = 0 if name == "pos" else 2
        assert np.array_equal(
            np.take(got, untouched, axis=ax), np.take(was, untouched, axis=ax)
        ), name
        for b in live:  # and the write did land
            assert not np.array_equal(
                np.take(got, b, axis=ax), np.take(was, b, axis=ax)
            ), (name, b)


def test_fused_chunk_traces_no_scatter_on_a_pool_plane(model):
    """A 512-token chunk — twice ``_POOL_WRITE_UNROLL_MAX`` pairs, where
    the pair form takes the batched scatter and XLA:TPU relayouts the
    whole pool for it — traces no ``scatter`` whose operand is a pool
    plane: the chunk is eight 64-token slabs."""
    params, config = model
    config = config.replace(max_seq_len=1024)
    rows, blk, chunk = 2, 64, 512
    mb = config.max_seq_len // blk
    pool = jax.eval_shape(lambda: serving.init_pool(config, rows * mb, blk))
    traced = serving._fused_chunk.trace(
        params, pool,
        *fused_chunk_operand_shapes(jax.ShapeDtypeStruct, rows, mb, chunk),
        config=config, n_iter=2, pf_chunk=chunk, all_greedy=True,
        allow_kernel=False,
    )
    plane_shapes = {pool.k.shape, pool.pos.shape}

    found = [
        eqn.invars[0].aval.shape
        for name, _, eqn in _eqns(traced.jaxpr.jaxpr)
        if name.startswith("scatter") and eqn.invars[0].aval.shape in plane_shapes
    ]
    assert not found, found


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_served_fused_admission_is_engine_generate_and_counts_its_writes(
    model, latent_model, kind,
):
    """A prompt admitted through the fused lane while a row decodes, then
    re-asked with a question appended (a 4-block prefix hit, again through
    the fused lane), serves an unbatched ``engine.generate``'s tokens — and
    the two write counters say what landed: whole blocks from the fused
    lane, a last chunk's two blocks past the reservation not counted; token
    slots from the idle server's whole-prompt insert."""
    from jax_llama_tpu.engine import GenerationConfig, generate
    from jax_llama_tpu.obs import metric_meta

    params, config = latent_model if kind == "latent" else model
    config = config.replace(attn_impl="auto", max_seq_len=64)
    rng = np.random.RandomState(11)
    draw = lambda n: [int(t) for t in rng.randint(1, config.vocab_size, size=n)]
    cb = ContinuousBatcher(
        params, config, n_slots=3, block_size=8, decode_chunk=4,
        prefill_budget=32)
    toks = {}

    def pump(until):
        for _ in range(200):
            if until():
                return
            for ev in cb.step():
                toks.setdefault(ev[0], []).append(ev[1])
        raise AssertionError("did not get there")

    holder = cb.submit(draw(8), max_new_tokens=54)   # idle server: an insert
    pump(lambda: len(toks.get(holder, ())) >= 8)
    doc = draw(33)      # 40 padded + 4: 6 blocks held, two 4-block chunks
    first = cb.submit(doc, max_new_tokens=4)
    pump(lambda: len(toks.get(first, ())) == 4)
    asked = doc + draw(8)            # hits 4 blocks; 9 tokens in one chunk
    again = cb.submit(asked, max_new_tokens=4)
    pump(lambda: len(toks.get(again, ())) == 4)
    assert len(toks[holder]) < 54    # a row decoded throughout
    stats = cb.stats()
    assert stats["fused_admissions_total"] == 2
    assert cb.prefix_requests_hit == 1
    assert stats["prefill_chunks_total"] == 3
    writes = [d["prefill_write"] for d in cb.obs.dispatches if "prefill_write" in d]
    assert writes == [
        {"pairs": 8}, {"blocks": 4}, {"blocks": 2}, {"blocks": 2},
    ]
    assert stats["prefill_blocks_written_total"] == 8
    assert stats["prefill_pairs_written_total"] == 8
    for name in ("prefill_blocks_written_total", "prefill_pairs_written_total"):
        assert metric_meta(name)[0] == "counter"
    for rid, prompt in ((first, doc), (again, asked)):
        alone = generate(
            params, jnp.asarray([prompt]), jnp.ones((1, len(prompt)), bool),
            jax.random.PRNGKey(0), config=config,
            gen_config=GenerationConfig(max_new_tokens=4, temperature=0.0))
        assert toks[rid] == [int(t) for t in np.asarray(alone)[0, len(prompt):]]


# ---------------------------------------------------------------------------
# The admission's first-token sample (``_admission_sample``): the head and the
# draw only in the dispatch that consumes them, an argmax for a greedy request
# ---------------------------------------------------------------------------

def _eqns(jaxpr, inside=()):
    """(primitive name, the control-flow primitives around it, eqn) of
    every equation of ``jaxpr``, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside + (eqn.primitive.name,))


def _trace_fused(params, config, mixed, all_greedy):
    """``_fused_chunk``'s jaxpr at this file's geometry: two rows, a
    32-token chunk, K = 2; the mixed pass needs the paged kernel."""
    rows, mb = W_ROWS, W_MB
    pool = jax.eval_shape(lambda: serving.init_pool(config, rows * mb, W_BLK))
    assert serving._mixed_pass(config, False, None, mixed, 2) == mixed
    return serving._fused_chunk.trace(
        params, pool,
        *fused_chunk_operand_shapes(jax.ShapeDtypeStruct, rows, mb, W_CHUNK),
        config=config, n_iter=2, pf_chunk=W_CHUNK, all_greedy=all_greedy,
        allow_kernel=mixed,
    ).jaxpr.jaxpr


@pytest.mark.parametrize("mixed", [False, True], ids=["two-pass", "mixed"])
def test_a_greedy_fused_chunk_holds_no_sort_and_heads_the_prompt_under_cond(
    model, mixed,
):
    """Every row greedy: no ``sort`` anywhere in the program, in either
    form.  The two-pass form's one-row head product — the only [1, 1, V]
    product there is — sits inside the ``cond`` on "the prompt completes
    in this dispatch"; the mixed form's head product is shared with the
    decode rows ([1, 1 + B, V]) and stays outside."""
    params, config = model
    found = list(_eqns(_trace_fused(params, config, mixed, True)))
    assert not [n for n, _, _ in found if n == "sort"]
    V = config.vocab_size
    heads = {
        (eqn.outvars[0].aval.shape, "cond" in inside)
        for name, inside, eqn in found
        if name == "dot_general" and eqn.outvars[0].aval.shape[-1] == V
    }
    if mixed:
        # the shared product, and the scan's one other iteration
        assert heads == {((1, 1 + W_ROWS, V), False), ((W_ROWS, 1, V), False)}
    else:
        assert heads == {((1, 1, V), True), ((W_ROWS, 1, V), False)}


def test_a_sampling_fused_chunk_sorts_for_the_admission_under_two_conds(model):
    """Beside sampling rows (``all_greedy=False``) the admission's warp —
    the only sorts over ONE row — lies under the ``cond`` on completion AND
    the one on the row's own temperature, whose other branch has none."""
    params, config = model
    found = list(_eqns(_trace_fused(params, config, False, False)))
    one_row = [
        inside for name, inside, eqn in found
        if name == "sort" and eqn.outvars[0].aval.shape[0] == 1
    ]
    assert len(one_row) == 2 and all(
        inside.count("cond") == 2 for inside in one_row)
    by_value = [
        eqn for name, inside, eqn in found
        if name == "cond" and inside.count("cond") == 1
    ]
    assert len(by_value) == 1
    sorts = [
        sum(n == "sort" for n, _, _ in _eqns(branch.jaxpr))
        for branch in by_value[0].params["branches"]
    ]
    assert sorted(sorts) == [0, 2]


_WALK_POLICIES = {
    # (the decoding holder's policy, the admitted request's)
    "greedy": ({}, {}),
    "sampled": ({}, dict(temperature=0.8, top_p=0.9, top_k=40, seed=12)),
    "greedy-beside-sampling": (dict(temperature=0.8, seed=7), {}),
}


def _walk(params, config, budget, policy, logprobs, on_step=None):
    """A holder decodes, then a 36-token prompt is admitted beside it
    (three 16-token chunks through the fused lane): (token streams,
    logprob streams, batcher).  ``on_step(cb, seen)`` runs once at the
    start (``seen`` None) and after every step, handed what it returned
    the time before."""
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4,
        block_size=BLOCK, prefill_budget=budget, logprobs=logprobs,
    )
    toks, lps = {}, {}

    seen = on_step and on_step(cb, None)

    def pump(n=None):
        nonlocal seen
        for i in range(200):
            if (n is not None and i >= n) or (n is None and not cb.pending()):
                return
            for ev in cb.step():
                toks.setdefault(ev[0], []).append(ev[1])
                if logprobs:
                    lps.setdefault(ev[0], []).append(ev[3])
            seen = on_step and on_step(cb, seen)
        raise AssertionError("did not finish")

    holder_policy, policy = _WALK_POLICIES[policy]
    r0 = cb.submit([5, 17, 99, 3], max_new_tokens=24, **holder_policy)
    pump(2)
    prompt = np.random.RandomState(3).randint(1, 128, size=36).tolist()
    r1 = cb.submit(prompt, max_new_tokens=6, **policy)
    pump()
    return [toks[r0], toks[r1]], [lps.get(r0), lps.get(r1)], cb


@pytest.mark.parametrize("logprobs", [False, True], ids=["tokens", "logprobs"])
@pytest.mark.parametrize("policy", list(_WALK_POLICIES))
def test_a_three_chunk_walk_samples_once_and_serves_the_classic_stream(
    model, policy, logprobs,
):
    """A dispatch that does not complete the prompt leaves the prefilling
    row's ``tau``, ``active`` and (every row greedy: nothing splits)
    ``keys`` as it found them; the one that does samples once.  The streams — a greedy request, a sampled one, a
    greedy one admitted beside a sampling row (the value branch) — are the
    classic admit-then-decode path's token for token (logprobs to float32
    noise), and the records say what each dispatch's sample cost."""
    params, config = model
    want_t, want_l, cb0 = _walk(params, config, 0, policy, logprobs)
    assert cb0.fused_admissions_total == 0
    non_final = []

    def row_state(cb, before):
        state = [np.asarray(a) for a in (cb.tau, cb.keys, cb.d_active)]
        pf = cb._pf
        if before is not None and pf is not None and pf.off:
            # a fused dispatch ran and its prompt is not complete
            assert cb.obs.dispatches[-1]["kind"] == "fused"
            for name, was, now in zip(("tau", "keys", "active"), before, state):
                # beside a sampling row the scan splits every row's key once
                # an iteration, masked rows' too; the fold overwrites it
                if name != "keys" or policy == "greedy":
                    assert np.array_equal(was[pf.slot], now[pf.slot]), name
            assert not state[2][pf.slot]
            non_final.append(pf.slot)
        return state

    got_t, got_l, cb = _walk(params, config, BLOCK, policy, logprobs, row_state)
    assert len(non_final) == 2
    assert got_t == want_t
    if logprobs:
        for a, b in zip(got_l, want_l):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    last = "drawn" if policy == "sampled" else "greedy"
    fused = [d for d in cb.obs.dispatches if d["kind"] == "fused"]
    assert [d["first_sample"] for d in fused] == ["skipped", "skipped", last]
    assert not any(
        "first_sample" in d for d in cb.obs.dispatches if d["kind"] != "fused")
    stats = cb.stats()
    assert stats["first_sample_skipped_total"] == 2
    assert stats[f"first_sample_{last}_total"] == 1
    assert sum(
        stats[f"first_sample_{k}_total"] for k in serving._FIRST_SAMPLE
    ) == stats["prefill_chunks_total"] == 3


def test_a_greedy_admission_beside_a_sampling_row_emits_the_argmax(model):
    """One dispatch, handed to the program: row 0's prompt completes, row
    1 decodes at temperature 0.8, so the program is the sampling variant
    (``all_greedy=False``) and row 0's temperature — zero, a value —
    chooses the argmax: its first token is the all-greedy variant's, and a
    key that would draw another token changes nothing."""
    params, config = model
    args, kwargs, _ = _write_case(params, config, "tail-past-reservation")
    want = np.asarray(_BLOCK_FORM(*args, **kwargs)[0][0])
    assert want[0, 0] >= 0                      # row 0 folded in and emitted
    args = list(args)
    args[12] = jnp.asarray([0.0, 0.8], jnp.float32)
    kwargs = dict(kwargs, all_greedy=False)
    firsts = set()
    for key in (0, 1, 2):
        vec = np.asarray(args[15]).copy()
        vec[3:5] = np.asarray(jax.random.PRNGKey(key), np.uint32).view(np.int32)
        args[15] = jnp.asarray(vec)
        firsts.add(int(np.asarray(_BLOCK_FORM(*args, **kwargs)[0][0])[0, 0]))
    assert firsts == {int(want[0, 0])}
    # ... where a sampling row 0 does draw by its key
    args[12] = jnp.asarray([1.5, 0.8], jnp.float32)
    drawn = set()
    for key in range(6):
        vec = np.asarray(args[15]).copy()
        vec[3:5] = np.asarray(jax.random.PRNGKey(key), np.uint32).view(np.int32)
        args[15] = jnp.asarray(vec)
        drawn.add(int(np.asarray(_BLOCK_FORM(*args, **kwargs)[0][0])[0, 0]))
    assert len(drawn) > 1


def test_nan_logits_on_the_final_chunk_fail_only_that_request(model):
    """A prompt whose last chunk holds a token with a NaN embedding: the
    dispatch that completes it folds the -1 sentinel into ``tau`` (the
    guard lives inside the ``cond`` with the sample), the host fails that
    request alone, and the holder beside it serves what it serves alone."""
    params, config = model
    holder = [5, 17, 99, 3]

    def serve(params, prompt=None):
        cb = ContinuousBatcher(
            params, config, n_slots=2, max_len=64, decode_chunk=4,
            block_size=BLOCK, prefill_budget=BLOCK,
        )
        r0 = cb.submit(holder, max_new_tokens=24)
        cb.step()
        cb.step()
        r1 = None if prompt is None else cb.submit(prompt, max_new_tokens=6)
        out = cb.run_to_completion()
        return out, cb, r0, r1

    alone, _, r0, _ = serve(params)
    poison = next(
        t for t in range(1, 128) if t not in holder and t not in alone[r0])
    bad = dict(params, embed=dict(params["embed"]))
    bad["embed"]["embedding"] = (
        params["embed"]["embedding"].at[poison].set(jnp.nan))
    prompt = [
        t for t in np.random.RandomState(3).randint(1, 128, size=60).tolist()
        if t != poison][:35] + [poison]
    out, cb, r0, r1 = serve(bad, prompt)
    failed = cb.pop_failed()
    assert [rid for rid, _ in failed] == [r1] and "non-finite" in failed[0][1]
    assert r1 not in out and out[r0] == alone[r0]
    fused = [d for d in cb.obs.dispatches if d["kind"] == "fused"]
    assert [d["first_sample"] for d in fused] == ["skipped", "skipped", "greedy"]
