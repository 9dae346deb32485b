"""The mixed pass: a fused dispatch's first decode iteration rides its
prompt chunk's pass over the weights (``serving._fused_chunk`` →
``models.llama.mixed_forward``; paged kernel, K >= 2; the dense block, and
the two blocks with a recurrent state: a mixer beside attention in every layer
through ``models.falcon_h1.mixed_forward``, mixer layers between window, full
and cross attention layers through ``models.sambay.mixed_forward``).

Three levels, each against the two-pass form it replaces: the model's
(``mixed_forward`` against a chunk ``forward`` and a paged decode
``forward``), the program's (``_fused_chunk`` as it is against itself
traced with ``_mixed_pass`` answering no) and the scheduler's (token and
logprob streams against classic admit-then-decode).  Then the counter, and
that the two other blocks' ``_fused_chunk`` holds no such pass."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_serving_fused import _FUSED_STATIC, fused_chunk_operand_shapes

from jax_llama_tpu import get_config, init_params, serving
from jax_llama_tpu.engine import window_positions
from jax_llama_tpu.models import llama
from jax_llama_tpu.serving import ContinuousBatcher

CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=128, dtype="float32", param_dtype="float32",
)
BLK, MB, ROWS, CHUNK = 16, 4, 4, 32
NB = ROWS * MB
TOL = dict(rtol=2e-5, atol=2e-6)
SNAPS = 4
_STATE_PLANES = serving._STATE + tuple("snap_" + n for n in serving._STATE)


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    return init_params(jax.random.PRNGKey(0), config), config


@pytest.fixture(scope="module")
def mixer_model():
    """The block with a mixer beside attention in every layer, at
    tests/test_falcon_h1.py's tiny widths."""
    config = _tiny_block("parallel-mixer")
    return init_params(jax.random.PRNGKey(1), config), config


@pytest.fixture(scope="module")
def recurrent_model():
    """The block with mixer layers between window, full and cross attention
    layers, at tests/test_sambay.py's tiny widths (a window of 24 over
    blocks of 16)."""
    config = _tiny_block("recurrent")
    return init_params(jax.random.PRNGKey(1), config), config


_STATEFUL = {"parallel-mixer": "mixer_model", "recurrent": "recurrent_model"}


# ---------------------------------------------------------------------------
# One dispatch: row 0 prefills, row 1 decodes, row 2 emits its last token at
# emit 1, row 3 is masked
# ---------------------------------------------------------------------------

def _case(params, config, last, sampled=False, seed=0):
    """(args, kwargs) of one ``_fused_chunk`` dispatch over a pool whose
    every slot holds a seeded pattern.  Row 0 prefills a 64-token prompt's
    first chunk, or (``last``) the second and last of a 40-token one; row 1
    decodes at position 20, row 2 at 9 with ONE token of budget left (it
    finishes at emit 1 and rides the pass masked), row 3 holds nothing.
    With recurrent state layers every slot and each of ``SNAPS`` snapshots
    holds a pattern too, and the call's ``pf_snap`` follows the operands:
    the walk's first chunk starts from snapshot 2, and either chunk's end
    state is kept as snapshot 1."""
    rng = np.random.RandomState(seed)
    stateful = config.recurrent_state
    empty = (serving.init_pool(config, NB, BLK, n_slots=ROWS, n_snapshots=SNAPS)
             if stateful else serving.init_pool(config, NB, BLK))
    pattern = lambda a: jnp.asarray(rng.uniform(-1.0, 1.0, a.shape), a.dtype)
    off, plen = (32, 40) if last else (0, 64)
    pos = np.full((NB, BLK), -1, np.int32)
    pos[0:4].reshape(-1)[:off] = np.arange(off)      # row 0's earlier chunk
    pos[4:6].reshape(-1)[:20] = np.arange(20)        # row 1's context
    pos[8].reshape(-1)[:9] = np.arange(9)            # row 2's
    pool = dataclasses.replace(
        empty, pos=jnp.asarray(pos), **serving._map_planes(pattern, empty),
        **{n: pattern(getattr(empty, n)) for n in _STATE_PLANES if stateful})
    table = np.arange(NB, dtype=np.int32).reshape(ROWS, MB)
    i32, f32 = jnp.int32, jnp.float32
    toks = rng.randint(1, config.vocab_size, size=64).astype(np.int32)
    vec = serving.pack_prefill(
        0, 0, plen, np.asarray(jax.random.PRNGKey(3), np.uint32), toks, 64)
    vec[serving._PF_OFF] = off
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(ROWS) + 7)
    temp = [0.8, 0.7, 0.9, 0.0] if sampled else [0.0] * ROWS
    args = (
        params, pool, jnp.asarray(table), jnp.full((ROWS,), MB, i32),
        jnp.asarray([0, 20, 9, 0], i32),                    # fill
        jnp.asarray([0, 5, 11, 0], i32),                    # tau
        jnp.asarray([0.0, -0.5, -0.25, 0.0], f32),          # tau_lp
        jnp.asarray([0, 20, 9, 0], i32),                    # pos
        jnp.asarray([False, True, True, False]),            # active
        jnp.asarray([6, 6, 1, 0], i32),                     # remaining
        jnp.full((ROWS, 1), -1, i32), keys.astype(jnp.uint32),
        jnp.asarray(temp, f32), jnp.ones((ROWS,), f32),
        jnp.full((ROWS,), config.vocab_size, i32),
        jnp.asarray(vec),
    ) + ((jnp.asarray([2, 1], i32),) if stateful else ())
    kwargs = dict(
        config=config, n_iter=2, pf_chunk=CHUNK, all_greedy=not sampled,
        allow_kernel=True, with_logprobs=True,
    )
    return args, kwargs


_TWO_PASS_TRACED = []


def _fused_in_two_passes(*args, **kwargs):
    # Patched while it is TRACED, through a function object of its own:
    # jit's cache can never hand it the mixed form's trace.
    def never(*a):
        _TWO_PASS_TRACED.append(a[-1])
        return False

    with pytest.MonkeyPatch.context() as m:
        m.setattr(serving, "_mixed_pass", never)
        return serving._fused_chunk.__wrapped__(*args, **kwargs)


# Undonated, so one set of operands serves both forms.
_MIXED = jax.jit(serving._fused_chunk.__wrapped__, static_argnames=_FUSED_STATIC)
_TWO_PASS = jax.jit(_fused_in_two_passes, static_argnames=_FUSED_STATIC)
_OUT = ("packed", "tau", "tau_lp", "fill", "pos", "active", "remaining",
        "keys", "pool", "pf_vec")


@pytest.mark.parametrize("impl", ["xla", "auto"], ids=["chunk-xla", "chunk-flash"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_a_chunk_that_is_not_the_last_leaves_what_two_passes_leave(
    model, impl, sampled,
):
    """Mid-prompt nothing folds in, so the whole dispatch is comparable:
    the packed block (tokens exact, logprobs to float32 noise), every
    carried row state, and every pool plane — the chunk's blocks, the
    decoding row's two new slots, and nothing else."""
    params, config = model
    args, kwargs = _case(params, config.replace(attn_impl=impl), False, sampled)
    got = dict(zip(_OUT, _MIXED(*args, **kwargs)))
    want = dict(zip(_OUT, _TWO_PASS(*args, **kwargs)))
    assert _TWO_PASS_TRACED and _TWO_PASS_TRACED[-1] == 2
    toks = np.asarray(got["packed"][0])
    assert np.array_equal(toks, np.asarray(want["packed"][0]))
    pad = serving._CHUNK_PAD
    # row 1 emits twice, row 2 its last token at emit 1, rows 0 and 3 nothing
    assert (toks != pad).tolist() == [
        [False, False], [True, True], [True, False], [False, False]]
    np.testing.assert_allclose(
        np.asarray(got["packed"][1]).view(np.float32)[toks != pad],
        np.asarray(want["packed"][1]).view(np.float32)[toks != pad], **TOL)
    for name in ("tau", "fill", "pos", "active", "remaining", "keys", "pf_vec"):
        assert np.array_equal(np.asarray(got[name]), np.asarray(want[name])), name
    assert np.asarray(got["fill"]).tolist() == [0, 22, 9, 0]
    was = args[1]
    for name in ("k", "v"):
        g, w, o = (np.asarray(getattr(p, name)) for p in (got["pool"], want["pool"], was))
        np.testing.assert_allclose(g, w, **TOL, err_msg=name)
        changed = {int(b) for b in np.nonzero((g != o).any(axis=(0, 1, 3, 4)))[0]}
        assert changed == {0, 1, 5}, changed
    assert np.array_equal(
        np.asarray(got["pool"].pos), np.asarray(want["pool"].pos))


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_the_last_chunk_folds_its_row_in_one_column_later(model, sampled):
    """The dispatch that lands the prompt's last token: the other rows'
    columns and state are the two-pass form's; the folded row's first token
    (and logprob) is the two-pass form's, in column 1 behind a pad, its key
    chain one split behind; the prompt's blocks hold the same KV."""
    params, config = model
    args, kwargs = _case(params, config, True, sampled)
    got = dict(zip(_OUT, _MIXED(*args, **kwargs)))
    want = dict(zip(_OUT, _TWO_PASS(*args, **kwargs)))
    gt, wt = np.asarray(got["packed"][0]), np.asarray(want["packed"][0])
    glp, wlp = (np.asarray(p["packed"][1]).view(np.float32) for p in (got, want))
    assert np.array_equal(gt[1:], wt[1:])
    assert gt[0, 0] == serving._CHUNK_PAD and gt[0, 1] == wt[0, 0] >= 0
    np.testing.assert_allclose(glp[0, 1], wlp[0, 0], **TOL)
    np.testing.assert_allclose(glp[1], wlp[1], **TOL)
    for name in ("tau", "fill", "pos", "active", "remaining", "keys"):
        assert np.array_equal(
            np.asarray(got[name])[1:], np.asarray(want[name])[1:]), name
    assert np.asarray(got["active"]).tolist() == [True, True, False, False]
    # 40 tokens in 3 blocks; the two-pass form decoded the row once more
    assert int(got["fill"][0]) == 48 + 1 and int(want["fill"][0]) == 48 + 2
    assert int(got["tau"][0]) == wt[0, 1]
    if sampled:
        assert np.array_equal(
            np.asarray(jax.random.split(got["keys"][0])[0]),
            np.asarray(want["keys"][0]))
    for name in ("k", "v"):
        g, w = (np.asarray(getattr(p["pool"], name))[:, :, :3] for p in (got, want))
        np.testing.assert_allclose(g, w, **TOL, err_msg=name)
    assert np.array_equal(
        np.asarray(got["pool"].pos)[:3], np.asarray(want["pool"].pos)[:3])


@pytest.mark.parametrize("block", list(_STATEFUL))
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("last", [False, True], ids=["mid-prompt", "last-chunk"])
def test_a_mixer_beside_attention_leaves_what_its_two_passes_leave(
    request, block, last, sampled,
):
    """Either block with a recurrent state (a mixer beside attention in
    every layer; mixer layers between attention layers), mid-prompt
    and on the chunk that completes the prompt: what the two tests above
    hold the dense block to, and the recurrent state.  Every decoding
    slot's state is the two-pass form's and the idle slot's is untouched,
    bit for bit; the chunk's end state — from snapshot 2 on the walk's
    first chunk, from the row's slot on a later one — stands in snapshot 1
    and (mid-prompt, where the row does not decode yet) in the row's slot;
    no other snapshot moves."""
    params, config = request.getfixturevalue(_STATEFUL[block])
    args, kwargs = _case(params, config, last, sampled)
    got = dict(zip(_OUT, _MIXED(*args, **kwargs)))
    want = dict(zip(_OUT, _TWO_PASS(*args, **kwargs)))
    assert _TWO_PASS_TRACED and _TWO_PASS_TRACED[-1] == 2
    pad = serving._CHUNK_PAD
    gt, wt = np.asarray(got["packed"][0]), np.asarray(want["packed"][0])
    glp, wlp = (np.asarray(p["packed"][1]).view(np.float32) for p in (got, want))
    assert np.array_equal(gt[1:], wt[1:])
    assert (gt[1:] != pad).tolist() == [[True, True], [True, False], [False, False]]
    np.testing.assert_allclose(glp[1:][gt[1:] != pad], wlp[1:][gt[1:] != pad], **TOL)
    if last:  # the folded row: its first token one column later
        assert gt[0, 0] == pad and gt[0, 1] == wt[0, 0] >= 0
        np.testing.assert_allclose(glp[0, 1], wlp[0, 0], **TOL)
        assert int(got["tau"][0]) == wt[0, 1]
    else:
        assert np.array_equal(gt[0], wt[0]) and (gt[0] == pad).all()
    rows = slice(1, None) if last else slice(None)
    for name in ("tau", "fill", "pos", "active", "remaining", "keys"):
        assert np.array_equal(
            np.asarray(got[name])[rows], np.asarray(want[name])[rows]), name
    was = args[1]
    blocks = slice(0, 3) if last else slice(None)  # the prompt's, or all
    for name in ("k", "v"):
        g, w = (np.asarray(getattr(p["pool"], name))[:, :, blocks] for p in (got, want))
        np.testing.assert_allclose(g, w, **TOL, err_msg=name)
    assert np.array_equal(
        np.asarray(got["pool"].pos)[blocks], np.asarray(want["pool"].pos)[blocks])
    for name in ("conv", "ssm"):
        g, w, o = (np.asarray(getattr(p, name)) for p in (got["pool"], want["pool"], was))
        np.testing.assert_allclose(g[:, rows], w[:, rows], **TOL, err_msg=name)
        assert np.array_equal(g[:, 3], o[:, 3]), name       # the idle slot
        assert not np.array_equal(g[:, 1], o[:, 1]), name   # a decoding one
        gs, ws, os_ = (np.asarray(getattr(p, "snap_" + name))
                       for p in (got["pool"], want["pool"], was))
        np.testing.assert_allclose(gs, ws, **TOL, err_msg="snap_" + name)
        assert np.array_equal(gs[:, [0, 2, 3]], os_[:, [0, 2, 3]]), name
        assert not np.array_equal(gs[:, 1], os_[:, 1]), name
        if not last:
            assert np.array_equal(g[:, 0], gs[:, 1]), name  # the chunk's end state
    assert np.array_equal(
        np.asarray(got["pool"].stats), np.asarray(want["pool"].stats))


@pytest.mark.parametrize("block", ["dense", *_STATEFUL])
@pytest.mark.parametrize(
    "impl,scan", [("xla", True), ("auto", True), ("xla", False)],
    ids=["chunk-xla", "chunk-flash", "unrolled"],
)
def test_mixed_forward_is_the_chunk_forward_and_the_paged_decode_forward(
    request, block, impl, scan,
):
    """The model's half alone: hidden states of the chunk's rows, logits of
    the riding rows (a masked row's are nobody's), the row's view and the
    pool planes against ``forward`` over the view and ``forward`` over the
    paged cache.  The dense block on a prompt's first chunk; the two blocks
    with a recurrent state on a later one (8 live tokens of 32 behind 32 in
    the cache), with the view's and the slots' recurrent state — a masked
    rider's, the prefilling row's own slot among them, bit for bit."""
    stateful = block in _STATEFUL
    params, config = request.getfixturevalue(_STATEFUL.get(block, "model"))
    config = config.replace(attn_impl=impl, scan_layers=scan)
    args, _ = _case(params, config, stateful)
    off, plen = (32, 40) if stateful else (0, 64)
    pool, table, fill = args[1], args[2], args[4]
    tau, active = args[5], args[8]
    view = serving._gather_cache(
        pool, table[:1], jnp.asarray([MB]), fill[:1],
        state=(pool.conv[:, :1], pool.ssm[:, :1]) if stateful else None)
    view = dataclasses.replace(view, index=jnp.asarray(off, jnp.int32))
    toks_c = args[15][None, serving._PF_HEADER:][:, off:off + CHUNK]
    positions, real = window_positions(0, off, CHUNK, plen)
    rider_pos = jnp.where(active, args[7], -1)
    pcache = serving._pool_as_cache(pool, table, fill)

    hidden, got_view, got_pool = jax.jit(
        lambda: llama.mixed_forward(
            params, toks_c, positions, config, view, real, tau, rider_pos,
            pcache))()
    (_, want_view, aux), (want_logits, want_pool) = jax.jit(lambda: (
        llama.forward(
            params, toks_c, positions, config, cache=view, attn_mask=real,
            compute_logits=False, output_last_hidden=True),
        llama.forward(
            params, tau[:, None], rider_pos[:, None], config, cache=pcache,
            attn_mask=active[:, None])))()
    assert hidden.shape == (1, CHUNK + ROWS, config.dim)
    live = np.asarray(real[0])
    assert live.sum() == min(CHUNK, plen - off)
    np.testing.assert_allclose(
        np.asarray(hidden[0, :CHUNK])[live],
        np.asarray(aux.last_hidden_state[0])[live], **TOL)
    got_logits = llama.lm_head_logits(
        params, hidden[:, CHUNK:], config, normed=True)[0]
    riding = np.asarray(active)
    np.testing.assert_allclose(
        np.asarray(got_logits)[riding], np.asarray(want_logits)[riding, 0],
        **TOL)
    for name in ("k", "v", "pos") + (("conv", "ssm") if stateful else ()):
        np.testing.assert_allclose(
            np.asarray(getattr(got_view, name)),
            np.asarray(getattr(want_view, name)), **TOL, err_msg=name)
        np.testing.assert_allclose(
            np.asarray(getattr(got_pool, name)),
            np.asarray(getattr(want_pool, name)), **TOL, err_msg=name)
    assert int(got_view.index) == off + CHUNK == int(want_view.index)
    if stateful:
        for name in ("conv", "ssm"):
            got, was = (np.asarray(getattr(p, name)) for p in (got_pool, pool))
            assert np.array_equal(got[:, ~riding], was[:, ~riding]), name
            assert not np.array_equal(got[:, 1], was[:, 1]), name
            assert not np.array_equal(
                np.asarray(getattr(got_view, name)), was[:, :1]), name
        # the riders' kernel steps, counted once: the pool's count is the
        # paged forward's
        assert np.array_equal(
            np.asarray(got_pool.stats), np.asarray(want_pool.stats))
        assert int(got_pool.stats[-1]) > 0


@pytest.mark.parametrize("rank", [5, 4, 2], ids=["payload", "scale", "pos"])
def test_the_rolled_pool_write_is_the_unrolled_chain(rank):
    """``paged_pool_write(rolled=True)`` — the riders' landing in the mixed
    pass, one traced step in a ``fori_loop`` — against the unrolled chain,
    for each plane rank: bit-equal, a sentinel pair dropped in place (it
    would clamp onto block NB - 1)."""
    rng = np.random.RandomState(rank)
    lead, tail = {5: ((2, 3), (8,)), 4: ((2, 3), ()), 2: ((), ())}[rank]
    plane = jnp.asarray(rng.uniform(size=lead + (6, 4) + tail), jnp.float32)
    upd = jnp.asarray(rng.uniform(size=lead + (3, 2) + tail), jnp.float32)
    blk = jnp.asarray([[0, 0], [6, 6], [5, 2]], jnp.int32)   # row 1: sentinel
    off = jnp.asarray([[1, 2], [3, 0], [3, 0]], jnp.int32)
    write = jax.jit(llama.paged_pool_write, static_argnames="rolled")
    got = np.asarray(write(plane, upd, blk, off, rolled=True))
    want = np.asarray(write(plane, upd, blk, off, rolled=False))
    assert np.array_equal(got, want)
    ax = 0 if rank == 2 else 2
    assert np.array_equal(  # blocks 1, 3, 4 untouched, 5 written once
        np.take(got, [1, 3, 4], axis=ax), np.take(np.asarray(plane), [1, 3, 4], axis=ax))
    assert not np.array_equal(np.take(got, 5, axis=ax), np.take(np.asarray(plane), 5, axis=ax))


# ---------------------------------------------------------------------------
# The scheduler: streams against classic admission
# ---------------------------------------------------------------------------

def _serve(params, config, budget, k, *, sampled=False, holder_new=9,
           holder_stop=(), probe_new=6, **cb_kw):
    """A holder decodes (admitted cold: an insert either way), then a
    40-token prompt arrives mid-decode and, with ``budget`` > 0, rides the
    lane in three chunks.  Returns ({rid: tokens}, {rid: logprobs}, the
    steps' (events, record) log, batcher)."""
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=k,
        block_size=BLK, prefill_budget=budget, logprobs=True, **cb_kw)
    toks, lps, log = {}, {}, []

    def pump(n=None):
        for i in range(400):
            if (n is not None and i >= n) or (n is None and not cb.pending()):
                return
            evs = cb.step()
            log.append((evs, cb.obs.dispatches[-1]))
            for rid, tok, _, lp in evs:
                toks.setdefault(rid, []).append(tok)
                lps.setdefault(rid, []).append(lp)
        raise AssertionError("did not finish")

    pol = lambda t, seed: dict(temperature=t, seed=seed) if sampled else {}
    r0 = cb.submit([5, 17, 99, 3], max_new_tokens=holder_new,
                   stop_tokens=holder_stop, **pol(0.8, 7))
    pump(2)
    prompt = np.random.RandomState(3).randint(1, 128, size=40).tolist()
    r1 = cb.submit(prompt, max_new_tokens=probe_new, **pol(0.7, 12))
    pump()
    return (toks[r0], toks[r1]), (lps[r0], lps[r1]), log, cb


@pytest.fixture(scope="module")
def classic(model):
    params, config = model
    memo = {}

    def get(sampled, **kw):
        key = (sampled, tuple(sorted(kw.items())))
        if key not in memo:
            t, l, _, cb = _serve(params, config, 0, 4, sampled=sampled, **kw)
            assert cb.fused_admissions_total == 0
            memo[key] = (t, l)
        return memo[key]

    return get


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_streams_are_classic_admission_s_at_every_k(model, classic, k, sampled):
    """Token and logprob streams of both requests equal classic
    admit-then-decode's, whatever K; at K >= 2 every fused dispatch took the
    mixed pass, at K = 1 (chunk, then emit: the first token still leaves
    with the dispatch that completes the prompt) none did; and the
    completing dispatch hands the folded row's first token to the host."""
    params, config = model
    # budgets that outlast three dispatches of 8, so K is never clamped
    sizes = dict(holder_new=40, probe_new=10)
    want_t, want_l = classic(sampled, **sizes)
    got_t, got_l, log, cb = _serve(
        params, config, BLK, k, sampled=sampled, **sizes)
    assert got_t == want_t
    for a, b in zip(got_l, want_l):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    stats = cb.stats()
    assert stats["fused_dispatches_total"] == 3
    assert stats["fused_dispatches_merged_total"] == (3 if k >= 2 else 0)
    fused = [(evs, rec) for evs, rec in log if rec["kind"] == "fused"]
    assert [rec["k"] for _, rec in fused] == [k] * 3
    probe = max(rid for evs, _ in log for rid, *_ in evs)
    firsts = [sum(ev[0] == probe for ev in evs) for evs, _ in fused]
    # nothing while the prompt is mid-prefill; the completing dispatch
    # delivers K tokens in two passes, K - 1 behind a mixed pass
    assert firsts == [0, 0, k - 1 if k >= 2 else 1]


@pytest.mark.parametrize(
    "how", ["budget", "stop"], ids=["remaining-1-at-emit-1", "stop-at-emit-1"])
def test_a_row_that_ends_at_emit_1_of_a_mixed_dispatch(model, classic, how):
    """The holder's last token — its budget's, or a stop token — leaves at
    column 0 of a dispatch that carries a prompt chunk: the row rides the
    mixed pass masked, its slot frees, and both streams are classic
    admission's.  Swept over the holder's length so that one run surely
    puts the end on emit 1."""
    params, config = model
    base, _ = classic(False, holder_new=12)
    at_emit_1 = 0
    for n in (3, 4, 5):
        kw = (dict(holder_new=n) if how == "budget"
              else dict(holder_new=12, holder_stop=(base[0][n - 1],)))
        want_t, want_l = classic(False, **kw)
        got_t, got_l, log, cb = _serve(params, config, BLK, 2, **kw)
        assert got_t == want_t and len(got_t[0]) <= n
        for a, b in zip(got_l, want_l):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        for evs, rec in log:
            mine = [ev for ev in evs if ev[0] == 0]
            if rec.get("merged_rows") and mine and mine[0][2]:
                at_emit_1 += 1
                assert len(mine) == 1
    assert at_emit_1 >= 1


def test_cancel_and_rebuild_mid_prefill_behind_mixed_dispatches(model):
    """Mid-prefill, after a mixed dispatch: a cancel frees the admission
    and the holder's stream goes on as if the prompt had never come; a
    rebuilt batcher (the crash-recovery contract) serves the replayed
    prompt's classic tokens."""
    params, config = model
    prompt = np.random.RandomState(3).randint(1, 128, size=40).tolist()

    def start():
        cb = ContinuousBatcher(
            params, config, n_slots=2, max_len=64, decode_chunk=4,
            block_size=BLK, prefill_budget=BLK)
        toks = {}
        r0 = cb.submit([5, 17, 99, 3], max_new_tokens=14)
        for _ in range(2):
            for ev in cb.step():
                toks.setdefault(ev[0], []).append(ev[1])
        r1 = cb.submit(list(prompt), max_new_tokens=6)
        for ev in cb.step():
            toks.setdefault(ev[0], []).append(ev[1])
        assert cb._pf is not None and cb._pf.req.rid == r1
        assert cb.stats()["fused_dispatches_merged_total"] == 1
        return cb, toks, r0, r1

    alone = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_chunk=4, block_size=BLK)
    ra = alone.submit([5, 17, 99, 3], max_new_tokens=14)
    rb = alone.submit(list(prompt), max_new_tokens=6)
    want = alone.run_to_completion()

    cb, toks, r0, r1 = start()
    assert cb.cancel(r1) and cb._pf is None
    while cb.pending():
        for ev in cb.step():
            toks.setdefault(ev[0], []).append(ev[1])
    assert toks[r0] == want[ra] and r1 not in toks

    cb, _, _, _ = start()
    cb2 = cb.rebuild()
    assert cb2._pf is None
    r = cb2.submit(list(prompt), max_new_tokens=6)
    assert cb2.run_to_completion()[r] == want[rb]


def test_a_mixer_beside_attention_is_served_as_classic_admission_serves_it(
    mixer_model,
):
    """The block with a mixer through ``ContinuousBatcher``, chunks of 32
    over blocks of 16 beside a decoding holder: a 105-token request rides
    the lane in four mixed dispatches (snapshots at 32, 64, 96), a re-ask
    restores the snapshot at 96, and a third prompt is cancelled mid-prefill
    behind a mixed dispatch.  Every stream is classic admit-then-decode's (``prefill_budget=0``: whole
    prompts through ``_paged_insert``, no hit)."""
    params, config = mixer_model
    rng = np.random.RandomState(4)
    draw = lambda n: [int(t) for t in rng.randint(0, config.vocab_size, n)]  # noqa: E731
    doc = draw(100)
    holder, cancelled = draw(6), draw(70)
    asks = [doc + draw(n) for n in (5, 9)]

    def batcher(budget):
        # the geometry of the last test of this file: one set of programs
        return ContinuousBatcher(
            params, config, n_slots=3, max_len=128, block_size=BLK,
            decode_chunk=4, prefill_budget=budget)

    alone = batcher(0)
    rids = [alone.submit(holder, max_new_tokens=40)] + [
        alone.submit(p, max_new_tokens=6) for p in asks]
    done = alone.run_to_completion()
    want = [done[r] for r in rids]
    assert alone.fused_admissions_total == 0

    cb = batcher(32)
    out = {}

    def steps(n=None):
        for i in range(400):
            if (n is not None and i >= n) or (n is None and not cb.pending()):
                return
            for rid, tok, *_ in cb.step():
                out.setdefault(rid, []).append(tok)
        raise AssertionError("did not finish")

    h = cb.submit(holder, max_new_tokens=40)
    steps(2)
    a = cb.submit(asks[0], max_new_tokens=6)
    steps(5)
    assert cb.stats()["ssm_snapshots_taken_total"] == 3
    b = cb.submit(asks[1], max_new_tokens=6)        # restores the one at 96
    steps(3)
    assert cb.stats()["ssm_snapshots_restored_total"] == 1
    assert cb.prefix_hit_tokens_total == 96
    c = cb.submit(cancelled, max_new_tokens=4)
    steps(1)
    assert cb._pf is not None and cb._pf.req.rid == c
    assert cb.obs.dispatches[-1]["merged_rows"] >= 1
    assert cb.cancel(c) and cb._pf is None
    steps()
    assert [out[r] for r in (h, a, b)] == want and c not in out
    stats = cb.stats()
    fused = [r for r in cb.obs.dispatches if r["kind"] == "fused"]
    assert len(fused) == stats["fused_dispatches_total"] == 6
    assert all(("merged_rows" in r) == (r["k"] >= 2) for r in fused)
    assert stats["fused_dispatches_merged_total"] >= 5
    assert stats["fused_merged_rows_total"] >= 5


@pytest.fixture(scope="module")
def recurrent_classic(recurrent_model):
    """{sampled: the scenario's streams under classic admit-then-decode}."""
    # conftest's per-module clearing, once more inside this long module: under
    # every program of the tests above the XLA:CPU compiler segfaulted three
    # block configurations later (reproduced twice; nothing below reuses them).
    jax.clear_caches()
    memo = {}

    def get(sampled):
        if sampled not in memo:
            memo[sampled] = _serve_sessions(*recurrent_model, 0, 4, sampled)[0]
        return memo[sampled]

    return get


def _serve_sessions(params, config, budget, k, sampled):
    """A holder decodes; a 105-token request arrives (with ``budget`` 32: four
    chunks through the lane, the last one 9 live tokens that end the prompt,
    snapshots at 32, 64 and 96), then a re-ask of its first 100 tokens with
    another tail.  Returns ([the three streams], batcher)."""
    rng = np.random.RandomState(4)
    draw = lambda n: [int(t) for t in rng.randint(0, config.vocab_size, n)]  # noqa: E731
    doc, holder = draw(100), draw(6)
    asks = [doc + draw(n) for n in (5, 9)]
    pol = lambda t, seed: dict(temperature=t, seed=seed) if sampled else {}  # noqa: E731
    cb = ContinuousBatcher(
        params, config, n_slots=3, max_len=128, block_size=BLK,
        decode_chunk=k, prefill_budget=budget)
    out = {}

    def steps(n=None):
        for i in range(400):
            if (n is not None and i >= n) or (n is None and not cb.pending()):
                return
            for rid, tok, *_ in cb.step():
                out.setdefault(rid, []).append(tok)
        raise AssertionError("did not finish")

    rids = [cb.submit(holder, max_new_tokens=64, **pol(0.8, 7))]
    steps(2)
    rids.append(cb.submit(asks[0], max_new_tokens=8, **pol(0.7, 12)))
    steps(5)
    rids.append(cb.submit(asks[1], max_new_tokens=8, **pol(0.9, 13)))
    steps()
    return [out[r] for r in rids], cb


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("k", [2, 8])
def test_mixer_layers_between_attention_layers_are_served_as_classic_admission_serves_them(
    recurrent_model, recurrent_classic, k, sampled,
):
    """The block with mixer layers between window, full and cross attention
    layers through ``ContinuousBatcher``, chunks of 32 over blocks of 16
    beside a holder that decodes past the window of 24: every fused
    dispatch runs K iterations and takes the mixed pass, the re-ask
    restores the snapshot at 96, and every stream — greedy, or drawn from
    the rows' own keys — is classic admit-then-decode's
    (``prefill_budget=0``: whole prompts through ``_paged_insert``)."""
    params, config = recurrent_model
    got, cb = _serve_sessions(params, config, 32, k, sampled)
    assert got == recurrent_classic(sampled)
    stats = cb.stats()
    assert stats["ssm_snapshots_taken_total"] == 3
    assert stats["ssm_snapshots_restored_total"] == 1
    assert cb.prefix_hit_tokens_total == 96
    fused = [r for r in cb.obs.dispatches if r["kind"] == "fused"]
    assert [r["k"] for r in fused] == [k] * 5
    assert [r["prefill_tokens"] for r in fused] == [32, 32, 32, 9, 13]
    assert all(r["merged_rows"] >= 1 for r in fused)
    assert stats["fused_dispatches_merged_total"] == 5
    assert stats["fused_merged_rows_total"] == sum(r["merged_rows"] for r in fused)


# ---------------------------------------------------------------------------
# The counter, and the blocks the pass was not ported to
# ---------------------------------------------------------------------------

def test_the_counter_counts_dispatches_and_rows_and_the_record_says_so(model):
    """``fused_dispatches_merged_total`` of ``fused_dispatches_total`` took
    the mixed pass and ``fused_merged_rows_total`` decoding rows rode it;
    a fused record that took it carries ``merged_rows``, no other record
    does.  A gathered fallback, an int8 pool and K = 1 keep two passes."""
    from jax_llama_tpu.obs import metric_meta

    params, config = model
    _, _, log, cb = _serve(params, config, BLK, 4)
    stats = cb.stats()
    fused = [rec for _, rec in log if rec["kind"] == "fused"]
    # the holder's ninth and last token leaves with the first of them: the
    # other two run their mixed pass with nobody riding
    assert [rec.get("merged_rows") for rec in fused] == [1, 0, 0]
    assert all("merged_rows" not in rec for _, rec in log if rec["kind"] != "fused")
    assert stats["fused_dispatches_merged_total"] == 3 == stats["fused_dispatches_total"]
    assert stats["fused_merged_rows_total"] == 1
    for name in ("fused_dispatches_merged_total", "fused_merged_rows_total"):
        assert metric_meta(name)[0] == "counter"
    for kw in (dict(use_pallas_kernel=False),
               dict(config=config.replace(kv_cache_dtype="int8"))):
        _, _, log, cb = _serve(
            params, kw.pop("config", config), BLK, 4, **kw)
        assert cb.stats()["fused_dispatches_total"] == 3
        assert cb.stats()["fused_dispatches_merged_total"] == 0
        assert cb.stats()["fused_merged_rows_total"] == 0
        assert not any("merged_rows" in rec for _, rec in log)


def test_a_one_device_mesh_takes_the_mixed_pass(model, classic):
    """The server hands the batcher a mesh even on one chip (every axis
    1): the pass is taken under it, through the kernels' ``shard_map``
    forms, and the streams are classic admission's."""
    from jax_llama_tpu.parallel.mesh import make_mesh

    params, config = model
    mesh = make_mesh(data=1, fsdp=1, tensor=1, devices=jax.devices()[:1])
    got_t, _, _, cb = _serve(params, config, BLK, 4, mesh=mesh)
    assert got_t == classic(False)[0]
    assert cb.stats()["fused_dispatches_merged_total"] == 3


def _tiny_block(kind):
    """A tiny configuration of one of the four other blocks, from the
    published keys of its benchmark configuration."""
    import test_afmoe
    import test_falcon_h1
    import test_mla_moe
    import test_sambay

    from jax_llama_tpu import config as config_mod

    mod = {"latent": test_mla_moe, "windowed": test_afmoe,
           "recurrent": test_sambay, "parallel-mixer": test_falcon_h1}[kind]
    raw = dict(json.loads(mod.CONFIG_FILE.read_text()), **mod.TINY)
    return config_mod.from_published(
        {k: v for k, v in raw.items() if k not in mod.BOOKKEEPING},
        max_seq_len=128, attn_impl="auto")


def _products(jaxpr, rows, head, times=1):
    """(products with a [1, ``rows``, ...] operand, head products counted
    once for every time they run) in a traced program.  A pass over the
    weights ends in one head product — an operand of the head's shape —,
    and one inside a scan runs ``length`` times."""
    mixed, passes = 0, 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes = [v.aval.shape for v in eqn.invars]
            mixed += any(s[:2] == (1, rows) for s in shapes)
            passes += times * any(s in head for s in shapes)
        inner = times * (
            eqn.params["length"] if eqn.primitive.name == "scan" else 1)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            m, p = _products(sub, rows, head, inner)
            mixed, passes = mixed + m, passes + p
    return mixed, passes


@pytest.mark.parametrize(
    "kind", ["dense", "parallel-mixer", "latent", "windowed", "recurrent"])
def test_only_the_blocks_the_pass_was_ported_to_hold_it_in_their_fused_chunk(
    model, kind,
):
    """Traced at K = 4 over 4 rows and a 32-token chunk: the dense
    program, and those of the two blocks with a recurrent state, have
    products over C + B = 36 rows and K passes over the weights; the two
    blocks with routed experts have no such product and K + 1 passes (the
    chunk's, and the decode scan's K).  Served, the ported blocks' counters
    read what their records say, and the two others' read 0."""
    if kind == "dense":
        params, config = model
    else:
        config = _tiny_block(kind)  # the stateful blocks': their fixtures' own
        params = init_params(jax.random.PRNGKey(1), config)
    rows, chunk, n_iter = 4, 32, 4
    mb = config.max_seq_len // BLK
    pool = jax.eval_shape(lambda: serving.init_pool(
        config, rows * mb, BLK, n_slots=rows, n_snapshots=rows))
    extra = ((jax.ShapeDtypeStruct((2,), jnp.int32),)
             if config.recurrent_state else ())
    traced = serving._fused_chunk.trace(
        params, pool,
        *fused_chunk_operand_shapes(jax.ShapeDtypeStruct, rows, mb, chunk),
        *extra, config=config, n_iter=n_iter, pf_chunk=chunk, all_greedy=True,
        allow_kernel=True,
    )
    head = {(config.dim, config.vocab_size), (config.vocab_size, config.dim)}
    mixed, passes = _products(traced.jaxpr.jaxpr, chunk + rows, head)
    ported = kind in ("dense", *_STATEFUL)
    if ported:
        assert mixed > 0 and passes == n_iter
        if kind == "dense":  # served: the tests above
            return
    else:
        assert mixed == 0 and passes == n_iter + 1
    cb = ContinuousBatcher(
        params, config, n_slots=3, max_len=128, decode_chunk=4,
        block_size=BLK, prefill_budget=2 * BLK)
    rng = np.random.RandomState(4)
    cb.submit([int(t) for t in rng.randint(1, config.vocab_size, 6)],
              max_new_tokens=24)
    cb.step()
    cb.step()
    cb.submit([int(t) for t in rng.randint(1, config.vocab_size, 70)],
              max_new_tokens=4)
    cb.run_to_completion()
    stats = cb.stats()
    assert stats["fused_dispatches_total"] >= 2
    if ported:
        rode = [d["merged_rows"] for d in cb.obs.dispatches if "merged_rows" in d]
        assert len(rode) == stats["fused_dispatches_merged_total"] >= 2
        assert sum(rode) == stats["fused_merged_rows_total"] > 0
        return
    assert stats["fused_dispatches_merged_total"] == 0
    assert stats["fused_merged_rows_total"] == 0
    assert not any("merged_rows" in d for d in cb.obs.dispatches)
