"""The learned-sparse-attention block with softmax-routed experts
(models/dsa_moe.py) against its plain reference,
`benchmark/references/dsa_moe.py`, loaded by path: one reference, the one the
benchmark's `correct` uses.

Tiny widths, seeded float32 weights, CPU.  The selection keeps 16 keys over
blocks of 16 and prompts of ~100 (six times `topk`), so a selection that is
dense, newest-first, off by one or blind to the cached index keys fails the
float32 tolerances — which a bfloat16 compute would fail too.
"""

import importlib
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from paged_steps import decode_row

import jax_llama_tpu as jlt
from jax_llama_tpu import config as config_mod
from jax_llama_tpu import serving
from jax_llama_tpu.models import dsa_moe
from jax_llama_tpu.ops import key_selection, moe
from jax_llama_tpu.ops.attention import attention_bias, sdpa

fa = importlib.import_module("jax_llama_tpu.ops.flash_attention")

ROOT = Path(__file__).resolve().parent.parent
CONFIG_FILE = ROOT / "benchmark" / "configs" / "Keye-VL-2.0-30B-A3B.json"
BOOKKEEPING = ("source", "architecture", "reference", "reduced", "assumed", "deployment")
TOPK, BLK = 16, 16
TINY = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_local_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, vocab_size=512, num_hidden_layers=2,
    torch_dtype="float32",
    sa_config={"indexer_head_dim": 16, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
               "kv_chunk_size": 512, "q_chunk_size": 512, "topk": TOPK},
)
# float32 on the CPU against a float32 reference: what is left is the order
# of sums (1e-6 of a logit of ~0.7); a wrong key in a selection of 16 moves
# logits by ~0.1-0.9 (the controls of the first test)
TOL = 1e-4


def _reference():
    path = ROOT / "benchmark" / "references" / "dsa_moe.py"
    spec = importlib.util.spec_from_file_location("reference_dsa_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _published(**over):
    raw = dict(json.loads(CONFIG_FILE.read_text()), **over)
    return {k: v for k, v in raw.items() if k not in BOOKKEEPING}


def _build(**over):
    raw = {**json.loads(CONFIG_FILE.read_text()), **TINY, **over}
    cfg = config_mod.from_published(
        {k: v for k, v in raw.items() if k not in BOOKKEEPING},
        max_seq_len=256, attn_impl="auto")
    cfg.validate()
    return raw, cfg, jlt.init_params(jax.random.PRNGKey(3), cfg)


@pytest.fixture(scope="module")
def tiny():
    """(file-style dict, program config, seeded params) at tiny widths."""
    return _build()


def _tokens(b, t, seed=0):
    toks = np.random.RandomState(seed).randint(0, TINY["vocab_size"], size=(b, t))
    return jnp.asarray(toks), jnp.tile(jnp.arange(t)[None], (b, 1))


def _deficit(params, raw, prompt, served):
    full = jnp.asarray([list(prompt) + list(served)])
    ref = np.asarray(_reference().logits(params, full, raw, len(prompt) - 1))[0, :len(served)]
    return ref.max(axis=1) - ref[np.arange(len(served)), served]


# --- (a) the served paths against the reference ------------------------------

@pytest.mark.parametrize("attn", ["auto", "xla"])
def test_forward_matches_the_plain_reference_and_neither_control(tiny, attn):
    """The whole prompt in one forward (the mask inside the flash kernel, or
    as a bias of the XLA attention), at six times `topk`."""
    raw, cfg, params = tiny
    toks, pos = _tokens(2, 100)
    mine = np.asarray(jlt.forward(params, toks, pos, cfg.replace(attn_impl=attn))[0])
    ref = _reference()
    true = np.asarray(ref.logits(params, toks, raw, 0))
    assert np.abs(mine - true).max() < TOL * np.abs(true).max()
    for control in ("dense", "newest"):
        other = np.asarray(ref.logits(params, toks, raw, 0, select=control))
        assert np.abs(mine - other).max() > 0.1 * np.abs(true).max(), control


def test_a_long_prompt_is_walked_in_query_tiles(tiny, monkeypatch):
    """More queries than one selection pass holds: `Q_TILE` at a time, the
    last tile padded; and several key tiles a pass."""
    raw, cfg, params = tiny
    monkeypatch.setattr(dsa_moe, "Q_TILE", 32)
    monkeypatch.setattr(key_selection, "K_TILE", 16)
    toks, pos = _tokens(1, 100, seed=5)
    mine = np.asarray(jlt.forward(params, toks, pos, cfg)[0])
    true = np.asarray(_reference().logits(params, toks, raw, 0))
    assert np.abs(mine - true).max() < TOL * np.abs(true).max()


@pytest.mark.parametrize("use_kernel", [True, False], ids=["paged", "gathered-view"])
def test_prefill_then_decode_through_the_paged_cache(tiny, use_kernel):
    """A 96-token prompt through `_paged_insert` (the whole-prompt insert),
    eight tokens through `_paged_decode_chunk`: index scores over the row's
    own blocks, an exact top-16, the chosen slots gathered from the pool."""
    raw, cfg, params = tiny
    NB, P, G = 16, 96, 8
    toks, _ = _tokens(1, P, seed=1)
    pool = serving.init_pool(cfg, NB, BLK)
    ids = jnp.arange(P // BLK, dtype=jnp.int32)[None]
    keys = jnp.zeros((1, 2), jnp.uint32)
    f32, i32 = jnp.float32, jnp.int32
    one = lambda v, dt: jnp.full((1,), v, dt)  # noqa: E731
    tau, _, plen, keys, pool = serving._paged_insert(
        params, pool, ids, toks, jnp.ones((1, P), bool), keys,
        one(0.0, f32), one(1.0, f32), one(0, i32), config=cfg)
    table = jnp.full((1, 8), NB, i32).at[0, :7].set(jnp.arange(7))
    # the decode program ranks by the kernel, under its scope, exactly when
    # it runs over the paged cache
    text = serving._paged_decode_chunk.lower(
        params, pool, table, one(7, i32), one(P, i32), tau, one(0.0, f32),
        one(P, i32), jnp.ones((1,), bool), one(G, i32), jnp.full((1, 1), -1, i32),
        keys, one(0.0, f32), one(1.0, f32), one(0, i32), config=cfg, n_iter=1,
        all_greedy=True, allow_kernel=use_kernel).as_text(debug_info=True)
    assert ('"attn.select/jit(paged_index_select)"' in text) == use_kernel
    served, _, stats = decode_row(
        params, cfg, pool, table, 7, P, int(tau[0]), G - 1, use_kernel=use_kernel)
    assert _deficit(params, raw, [int(t) for t in toks[0]], served).max() < TOL
    selected, candidates, dense_rows, steps_run, steps_table, tie_rows = stats[-6:]
    if use_kernel:
        # two of the fourteen row-layers find their k-th value at 0.0, the
        # score of every key all four index heads clip: shared, and counted
        assert tie_rows == 2
        # 7 iterations x 2 layers, contexts 97..103, 16 chosen of each
        assert selected == 7 * 2 * TOPK and dense_rows == 0
        assert candidates == 2 * sum(range(97, 104))
        # the eight table entries are one grid step of the ranking kernel
        assert steps_run == steps_table == 7 * 2
    else:
        assert selected == candidates == dense_rows == steps_run == steps_table == tie_rows == 0


@pytest.mark.parametrize("use_kernel", [True, False], ids=["paged", "gathered-view"])
def test_served_through_the_fused_lane_and_a_prefix_hit(tiny, use_kernel):
    """Through `ContinuousBatcher`: a 101-token request admitted alone (the
    whole-prompt insert), one admitted beside it through `_fused_chunk` in
    four 32-token chunks, and a re-ask that finds 96 cached tokens (and their
    index keys) and prefills its suffix over them.  Every served token is the
    reference's own argmax over prompt + served tokens."""
    raw, cfg, params = tiny
    rng = np.random.RandomState(4)
    doc = [int(t) for t in rng.randint(0, 512, size=96)]
    asks = [doc + [int(t) for t in rng.randint(0, 512, size=5)] for _ in range(2)]
    cb = jlt.ContinuousBatcher(
        params, cfg, n_slots=2, block_size=BLK, decode_chunk=4, prefill_budget=32,
        use_pallas_kernel=use_kernel)
    a = cb.submit(asks[0], max_new_tokens=24)
    early = [t for _ in range(2) for (_, t, *_) in cb.step()]
    b = cb.submit(asks[1][::-1], max_new_tokens=6)      # shares no prefix
    out = cb.run_to_completion()
    out[a] = early + out[a]
    c = cb.submit(asks[1], max_new_tokens=8)
    out.update(cb.run_to_completion())
    assert cb.prefix_hit_tokens_total == 96
    kinds = {d["kind"] for d in cb.obs.dispatches}
    assert {"insert", "fused", "suffix_insert", "decode"} <= kinds
    fused = [d for d in cb.obs.dispatches if d["kind"] == "fused"]
    assert sum(d["prefill_tokens"] for d in fused) >= 101 and len(fused) >= 4
    # the block keeps two passes a fused dispatch, as the other expert blocks
    assert cb.stats()["fused_dispatches_merged_total"] == 0
    for rid, prompt in ((a, asks[0]), (b, asks[1][::-1]), (c, asks[1])):
        assert _deficit(params, raw, prompt, out[rid]).max() < TOL, rid
    stats = cb.stats()
    from jax_llama_tpu.obs import metric_meta

    for name in ("attn_selected_slots_total", "attn_candidate_slots_total",
                 "attn_select_dense_rows_total", "attn_index_steps_run_total",
                 "attn_index_steps_table_total", "attn_select_tie_rows_total",
                 "moe_assignments_total"):
        assert metric_meta(name)[0] == "counter" and name in stats
    assert stats["moe_layer_calls_total"] > 0
    if use_kernel:
        assert 0 < stats["attn_selected_slots_total"] < stats["attn_candidate_slots_total"]
        assert stats["attn_select_dense_rows_total"] == 0   # every context past 16
        # the 16 table entries are two grid steps of the ranking kernel and
        # most contexts end in the first (an idle row counts on neither side)
        rows = stats["attn_selected_slots_total"] // TOPK           # x layers
        assert stats["attn_index_steps_table_total"] == 2 * rows
        assert rows <= stats["attn_index_steps_run_total"] < 2 * rows
        # seeded scores tie at the k-th place only at 0.0, the score of a key
        # all four index heads clip: a row or two of these (x layers)
        assert 0 <= stats["attn_select_tie_rows_total"] < rows / 10
    else:
        assert stats["attn_candidate_slots_total"] == stats["attn_index_steps_table_total"] == 0
        assert stats["attn_select_tie_rows_total"] == 0
    assert stats["host_syncs_per_token"] < 1


def test_the_index_key_plane_of_a_cached_block_equals_a_fresh_prefills(tiny):
    """The same 96 tokens once through the whole-prompt insert and once
    through the fused lane beside a holder: the index keys (and K, V) of every
    block are the same values wherever the block was written from."""
    _, cfg, params = tiny
    rng = np.random.RandomState(7)
    doc = [int(t) for t in rng.randint(0, 512, size=96)]

    def blocks_of(cb, rid_blocks):
        return [np.asarray(getattr(cb.pool, n)[:, :, rid_blocks]) for n in ("idx", "k", "v")]

    alone = jlt.ContinuousBatcher(params, cfg, n_slots=2, block_size=BLK,
                                  decode_chunk=4, prefill_budget=32)
    alone.submit(doc + [1], max_new_tokens=2)
    alone.run_to_completion()
    beside = jlt.ContinuousBatcher(params, cfg, n_slots=2, block_size=BLK,
                                   decode_chunk=4, prefill_budget=32)
    beside.submit(doc[::-1] + [2], max_new_tokens=40)
    for _ in range(2):
        beside.step()
    beside.submit(doc + [1], max_new_tokens=2)
    beside.run_to_completion()
    assert {"fused"} <= {d["kind"] for d in beside.obs.dispatches}

    def chain(cb):
        return cb._store.match(cb._chain_keys(doc + [1], BLK)).blocks

    a, b = chain(alone)[:6], chain(beside)[:6]
    assert len(a) == len(b) == 6
    for x, y in zip(blocks_of(alone, np.array(a)), blocks_of(beside, np.array(b))):
        assert np.abs(x - y).max() < 1e-5


# --- (b) the selection itself --------------------------------------------------

def _layer_inputs(tiny, T, seed=2):
    """(normed layer input a [T, D], layer 0's weights, index queries, keys,
    weights as the program computes them) for one sequence."""
    raw, cfg, params = tiny
    toks, pos = _tokens(1, T, seed=seed)
    lp = jax.tree.map(lambda x: x[0], params["moe_layers"])
    x = jnp.take(params["embed"]["embedding"], toks[0], axis=0)
    from jax_llama_tpu.ops.norm import rms_norm
    from jax_llama_tpu.ops.rope import rope_rows
    from jax_llama_tpu.ops.norm import layer_norm

    a = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    cos, sin = rope_rows(pos, cfg.index_head_dim // 2, cfg.rope_theta)
    q_idx = dsa_moe._rope_half(jnp.einsum("td,dhk->thk", a, lp["index_q"])[None], cos, sin)
    k_idx = layer_norm(a @ lp["index_k"], lp["index_k_norm"], lp["index_k_bias"], cfg.rms_norm_eps)
    k_idx = dsa_moe._rope_half(k_idx[None, :, None, :], cos, sin)[:, :, 0]
    w = (a @ lp["index_w"])[None]
    return raw, a, lp, q_idx, k_idx, w, pos


@pytest.mark.parametrize("T", [8, 16, 17, 96])
def test_the_chosen_sets_are_the_references(tiny, T):
    """Both forms of the rule — the bit-search mask of a prompt chunk and the
    top-k slots of decode rows — choose the reference's keys, as sets."""
    raw, a, lp, q_idx, k_idx, w, pos = _layer_inputs(tiny, T)
    want = np.asarray(_reference().selection(a, lp, raw))
    mask = np.asarray(key_selection.select_mask(q_idx, w, k_idx, pos, pos, TOPK))[0]
    assert (mask == want).all()
    assert (mask.sum(axis=1) == np.minimum(np.arange(T) + 1, TOPK)).all()
    scores = key_selection.index_scores(q_idx, w, k_idx)
    live = jnp.asarray(np.tril(np.ones((T, T), bool)))[None]
    chosen, chosen_live = key_selection.select_slots(scores, live, TOPK)
    for t in range(T):
        got = set(np.asarray(chosen[0, t])[np.asarray(chosen_live[0, t])].tolist())
        assert got == set(np.nonzero(want[t])[0].tolist()), t


def test_a_context_no_longer_than_topk_is_dense_attention(tiny):
    """16 tokens under `topk` 16: the mask is the causal mask and the logits
    are those of the reference's dense control."""
    raw, cfg, params = tiny
    toks, pos = _tokens(2, TOPK, seed=9)
    mine = np.asarray(jlt.forward(params, toks, pos, cfg)[0])
    dense = np.asarray(_reference().logits(params, toks, raw, 0, select="dense"))
    assert np.abs(mine - dense).max() < TOL * np.abs(dense).max()
    _, _, _, q_idx, k_idx, w, p = _layer_inputs(tiny, TOPK)
    mask = np.asarray(key_selection.select_mask(q_idx, w, k_idx, p, p, TOPK))[0]
    assert (mask == np.tril(np.ones((TOPK, TOPK), bool))).all()


def test_ties_go_to_the_lower_index():
    """Equal index scores at the k-th place: index keys that repeat give
    exactly equal scores, and both forms keep the lower positions."""
    T, S, k = 2, 12, 4
    rng = np.random.RandomState(0)
    base = rng.standard_normal((3, 8)).astype(np.float32)
    k_idx = jnp.asarray(base[[0, 1, 1, 2, 1, 0, 1, 2, 1, 1, 0, 2]])[None]      # [1, S, 8]
    q_idx = jnp.asarray(rng.standard_normal((1, T, 2, 8)), jnp.float32)
    w = jnp.asarray(np.abs(rng.standard_normal((1, T, 2))), jnp.float32)
    q_pos = jnp.asarray([[S - 1, S - 1]], jnp.int32)
    kv_pos = jnp.arange(S, dtype=jnp.int32)[None]
    scores = np.asarray(key_selection.index_scores(q_idx, w, k_idx))[0]
    mask = np.asarray(key_selection.select_mask(q_idx, w, k_idx, q_pos, kv_pos, k))[0]
    chosen, _ = key_selection.select_slots(
        jnp.asarray(scores)[None], jnp.ones((1, T, S), bool), k)
    for t in range(T):
        order = np.argsort(-scores[t], kind="stable")[:k]
        assert set(np.nonzero(mask[t])[0]) == set(order.tolist()) == set(np.asarray(chosen[0, t]).tolist())
        assert mask[t].sum() == k
    # the k-th value IS tied: a rule that took every equal score would pass k
    kth = np.sort(scores[0])[::-1][k - 1]
    assert (scores[0] == kth).sum() > 1 or (scores[1] == np.sort(scores[1])[::-1][k - 1]).sum() > 1


def _paged_rows(ctx, blk, mb, di, seed=0, keys_of=None):
    """A one-layer-of-two index plane under a table of `mb` entries a row for
    rows of `ctx` cached tokens (None: an inactive row), blocks dealt in
    turn so that a row's blocks are not consecutive; unused entries hold the
    sentinel.  (plane, pool_pos, table, q_pos)."""
    rng = np.random.RandomState(seed)
    B = len(ctx)
    nb = B * mb
    plane = rng.standard_normal((2, 1, nb, blk, di)).astype(np.float32)
    table = np.full((B, mb), nb, np.int32)
    pos = np.full((nb, blk), -1, np.int32)
    for b, c in enumerate(ctx):
        for j in range(-(-(c or 0) // blk)):
            table[b, j] = j * B + b
            n = min(blk, c - j * blk)
            pos[j * B + b, :n] = np.arange(j * blk, j * blk + n)
            if keys_of is not None:
                plane[1, 0, j * B + b, :n] = keys_of(b, np.arange(j * blk, j * blk + n))
    q_pos = np.asarray([-1 if c is None else c for c in ctx], np.int32)
    return jnp.asarray(plane), jnp.asarray(pos), jnp.asarray(table), jnp.asarray(q_pos)


def _xla_ranking(q_idx, w, k_idx, plane, pos, table, q_pos, layer):
    """The decode rows' candidates as the XLA stages make them: bit images
    [B, 1, S + 1] with 0 for a slot that is not live."""
    from jax_llama_tpu.ops.paged_attention import paged_rows, paged_slot_positions

    keys = jnp.concatenate([paged_rows(plane, table, layer), k_idx], axis=1)
    cand = jnp.concatenate([paged_slot_positions(pos, table), q_pos[:, None]], axis=1)
    live = (cand >= 0) & (cand <= q_pos[:, None])
    return jnp.where(live[:, None], key_selection._sortable(
        key_selection.index_scores(q_idx, w, keys)), 0)


def _images_as_xla(u, n_slots):
    """`paged_index_scores`' int32 images [B, R, C] as `_sortable`'s uint32
    ones over the table's slots [B, S] (0: not live)."""
    u = jax.lax.bitcast_convert_type(u, jnp.uint32) ^ jnp.uint32(1 << 31)
    return u.reshape(u.shape[0], -1)[:, :n_slots]


def _xla_selection(images, own, topk):
    """`_kth_value`, `_mask_of` and `_compact` over candidates [B, S] and the
    own token's image [B]: (chosen [B, k], live [B, k], kth, above, equal,
    over), the rule the list kernel is held to."""
    mine = jnp.concatenate([images, own[:, None]], axis=1)[:, None]
    count = lambda pred, ref: jnp.sum(pred(mine, ref[..., None]), axis=-1, dtype=jnp.int32)  # noqa: E731
    kth, above, over = key_selection._kth_value(mine, topk, count)
    chosen, live = key_selection._compact(
        key_selection._mask_of(mine, topk, kth, above, over), min(topk, mine.shape[-1]))
    return tuple(np.asarray(a[:, 0]) for a in (
        chosen, live, kth, above, count(jnp.equal, kth), over))


@pytest.mark.parametrize("step_tokens", [32, 4096], ids=["two-entries-a-step", "one-step-a-row"])
def test_the_ranking_kernel_scores_live_blocks_and_finds_the_kth_value(monkeypatch, step_tokens):
    """The two kernels on rows of different contexts in one call — one
    shorter than `topk` (every live slot), one ending mid-block, one
    inactive, one whose table is full — against `index_scores`, `_kth_value`,
    `_mask_of` and `_compact` over the row's whole table: the same bit images
    (not live where a slot is not, and in every step the grid never visits),
    the same k-th value and counts, the same list element for element."""
    monkeypatch.setattr(key_selection, "INDEX_STEP_TOKENS", step_tokens)
    BLKS, MB, DI, HI, LAYER = 16, 6, 16, 4, 1
    ctx = [9, 53, None, 96, 70]
    plane, pos, table, q_pos = _paged_rows(ctx, BLKS, MB, DI)
    B, S = len(ctx), MB * BLKS
    rng = np.random.RandomState(1)
    q_idx = jnp.asarray(rng.standard_normal((B, 1, HI, DI)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, 1, HI)), jnp.float32)   # either sign
    k_idx = jnp.asarray(rng.standard_normal((B, 1, DI)), jnp.float32)
    plan = key_selection.index_plan(pos, table, q_pos)
    entries = 2 if step_tokens == 32 else MB
    assert plan[4].shape[1] == entries and key_selection.plan_row_steps(plan, B) == MB // entries
    # live steps and one for the inactive row
    assert int(plan[0]) == sum(-(-(c or 1) // (entries * BLKS)) for c in ctx)

    want = _xla_ranking(q_idx, w, k_idx, plane, pos, table, q_pos, LAYER)
    own = want[:, 0, S]
    images = key_selection.paged_index_scores(
        q_idx[:, 0], w[:, 0], plane, plan, q_pos, LAYER)
    assert images.shape == (B, MB, BLKS) and images.dtype == jnp.int32
    u = _images_as_xla(images, S)
    got, want = np.asarray(u).astype(np.int64), np.asarray(want[:, 0]).astype(np.int64)
    assert ((got == 0) == (want[:, :S] == 0)).all()
    # the images are float32 bits: the order of a sum of four heads is all
    # that may differ
    assert np.abs(got - want[:, :S]).max() <= 2
    assert (got[2] == 0).all() and (got[0] != 0).sum() == 9 and (got[4] != 0).sum() == 70

    # the search and the list in VMEM against XLA's stages over the kernel's
    # own images
    chosen, chosen_live, tied = key_selection.paged_select_slots(
        q_idx, w, k_idx, plane, table, plan, q_pos, LAYER, TOPK)
    _, _, kth, above, equal = key_selection.paged_index_select(
        images, own, topk=TOPK, n_slots=S)
    ref, ref_live, kth_x, above_x, equal_x, over_x = _xla_selection(u, own, TOPK)
    assert (np.asarray(kth) == kth_x).all() and (np.asarray(above) == above_x).all()
    assert (np.asarray(equal) == equal_x).all() and (np.asarray(tied) == over_x).all()
    assert int(kth[0]) == int(kth[2]) == 0 and (np.asarray(kth)[[1, 3, 4]] > 0).all()
    assert (np.asarray(chosen_live[:, 0]) == ref_live).all()
    assert (np.asarray(chosen[:, 0]) == ref)[ref_live].all()
    # a place past the row's count holds the own token's id
    assert (np.asarray(chosen[:, 0])[~ref_live] == S).all()
    assert np.asarray(chosen_live).sum(axis=-1)[:, 0].tolist() == [10, TOPK, 0, TOPK, TOPK]
    # the step's own token is candidate S, and the shortest row takes it
    assert int(chosen[0, 0, 9]) == S


def _synthetic_images(B, R, C, MB, topk, n_values, seed):
    """Images [B, R, C] int32 of scores drawn from `n_values` distinct ones
    (few: ties at every rank, inside a line, across lines, with the own
    token) for rows of random contexts: the first full, the second three
    short of `topk`, the third inactive."""
    rng = np.random.RandomState(seed)
    S = MB * C
    values = np.sort(rng.standard_normal(n_values).astype(np.float32))
    bits = values[rng.randint(0, n_values, size=(B, R, C))].view(np.int32)
    ctx = rng.randint(1, S + 1, size=B)
    ctx[:3] = S, max(1, topk - 3), 0
    live = (np.arange(R * C)[None] < ctx[:, None]).reshape(B, R, C)
    u = np.where(live, np.where(bits < 0, bits ^ 0x7FFFFFFF, bits), -(1 << 31)).astype(np.int32)
    own = np.asarray(key_selection._sortable(jnp.asarray(values[rng.randint(0, n_values, size=B)])))
    return jnp.asarray(u), jnp.asarray(np.where(ctx > 0, own, 0).astype(np.uint32)), S


@pytest.mark.parametrize("B,R,C,MB,topk,n_values", [
    (5, 6, 16, 6, 16, 5), (5, 6, 16, 5, 16, 3), (4, 3, 8, 3, 4, 2), (5, 4, 512, 4, 256, 7),
    (4, 2, 512, 2, 2048, 50), (3, 8, 256, 7, 600, 4), (4, 4, 128, 4, 100, 1 << 20),
], ids=["blocks-of-16", "a-padded-table", "blocks-of-8-two-values", "two-sub-lines-a-line",
        "topk-past-the-table", "several-tiles-of-the-list", "no-ties"])
def test_the_list_kernel_is_the_mask_and_the_compaction_element_for_element(B, R, C, MB, topk, n_values):
    """`paged_index_select` on images with ties everywhere — at the k-th
    value inside one line, across lines and with the own token among them —
    over block lengths that are one sub-line, two, or not 512 at all, a
    table the plan padded (R > MB), `topk` past the table, a list of more
    than one tile: `_compact(_mask_of(...))`'s list and liveness element for
    element, and `_kth_value`'s answers."""
    tied = 0
    for seed in range(2):
        u, own, S = _synthetic_images(B, R, C, MB, topk, n_values, seed)
        chosen, live, kth, above, equal = key_selection.paged_index_select(
            u, own, topk=topk, n_slots=S)
        ref, ref_live, kth_x, above_x, equal_x, over_x = _xla_selection(
            _images_as_xla(u, S), own, topk)
        assert chosen.shape == live.shape == (B, min(topk, S + 1))
        assert (np.asarray(kth) == kth_x).all() and (np.asarray(above) == above_x).all()
        # (a row that takes every live candidate has no k-th value, and the
        # count "at" 0 is of the slots that are not live, padded lines too)
        assert (np.asarray(equal) == equal_x)[kth_x > 0].all()
        assert (np.asarray(live) == ref_live).all()
        assert (np.asarray(chosen) == ref)[ref_live].all()
        assert (np.asarray(chosen)[~ref_live] == S).all()
        assert not ref_live[2].any() and ref_live[1].sum() == min(S, max(1, topk - 3)) + 1
        tied += over_x.sum()
    assert (tied > 0) == (n_values < 1000 and topk <= S)


def test_ties_go_to_the_lower_slot_through_the_ranking_kernel():
    """Index keys that repeat in the pool give exactly equal scores in the
    kernel too, and the chosen are the lowest slots among the equals."""
    BLKS, MB, DI, HI, k = 8, 3, 8, 2, 4
    rng = np.random.RandomState(0)
    base = rng.standard_normal((3, DI)).astype(np.float32)
    which = np.asarray([0, 1, 1, 2, 1, 0, 1, 2, 1, 1, 0, 2, 1, 1, 0, 2, 1, 0, 2])
    plane, pos, table, q_pos = _paged_rows(
        [19, 12], BLKS, MB, DI, keys_of=lambda b, at: base[which[at]])
    q_idx = jnp.asarray(rng.standard_normal((2, 1, HI, DI)), jnp.float32)
    w = jnp.asarray(np.abs(rng.standard_normal((2, 1, HI))), jnp.float32)
    # the step's own token is scored by another product (XLA's, outside the
    # kernel): it competes, and ties with nothing
    own = rng.standard_normal((1, DI)).astype(np.float32)
    k_idx = jnp.asarray(own[[0, 0]])[:, None]
    plan = key_selection.index_plan(pos, table, q_pos)
    chosen, chosen_live, tie_rows = key_selection.paged_select_slots(
        q_idx, w, k_idx, plane, table, plan, q_pos, 1, k)
    assert np.asarray(chosen_live).all()
    tied = 0
    for b, n in enumerate((19, 12)):
        keys = jnp.asarray(np.concatenate([base[which[:n]], own]))[None]
        scores = np.asarray(key_selection.index_scores(q_idx[b:b + 1], w[b:b + 1], keys))[0, 0]
        order = np.argsort(-scores, kind="stable")[:k]
        # candidate ids: a slot's place in the table, MB * BLKS the own token
        want = sorted(MB * BLKS if i == n else int(i) for i in order)
        assert np.asarray(chosen[b, 0]).tolist() == want, b
        tied += (scores == np.sort(scores)[::-1][k - 1]).sum() > 1
    assert tied    # the k-th value IS tied: taking every equal score would pass k
    assert np.asarray(tie_rows).sum() == tied      # and the counter says so


@pytest.mark.parametrize("n,k", [(5, 3), (40, 16), (1100, 64), (1024, 2048)])
def test_the_chosen_are_listed_in_order_without_a_sort(n, k):
    """`_compact`: the first k set entries of a mask by running counts at two
    levels (groups of 512), against numpy's own listing."""
    rng = np.random.RandomState(n)
    mask = rng.random_sample((2, 3, n)) < 0.3
    mask[0, 0] = False                       # a row with nothing chosen
    mask[1, 2] = True                        # and one with everything
    idx, exists = key_selection._compact(jnp.asarray(mask), k)
    for b in range(2):
        for t in range(3):
            want = np.nonzero(mask[b, t])[0][:k]
            assert np.asarray(exists[b, t]).sum() == len(want)
            assert (np.asarray(idx[b, t])[:len(want)] == want).all()


def test_the_flash_kernels_mask_operand_is_the_xla_bias():
    rng = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    T, S = 40, 100
    q, k, v = f(2, T, 8, 16), f(2, S, 2, 16), f(2, S, 2, 16)
    q_pos = jnp.tile(jnp.arange(S - T, S, dtype=jnp.int32)[None], (2, 1))
    kv_pos = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (2, 1))
    mask = jnp.asarray(rng.random_sample((2, T, S)) < 0.4) | (kv_pos[:, None] == q_pos[:, :, None])
    want = sdpa(q, k, v, jnp.where(
        mask, attention_bias(q_pos, kv_pos)[:, 0], jnp.finfo(jnp.float32).min)[:, None])
    for bq, bk in ((2048, 2048), (16, 32), (8, 64)):
        got = fa.flash_attention(q, k, v, q_pos, kv_pos, block_q=bq, block_k=bk,
                                 mask=mask.astype(jnp.int8))
        assert np.abs(np.asarray(got - want)).max() < 1e-5, (bq, bk)


def test_the_softmax_routers_weights_sum_to_one_and_are_the_references(tiny):
    raw, cfg, params = tiny
    h = jnp.asarray(np.random.RandomState(3).standard_normal((40, 64)), jnp.float32)
    router = params["moe_layers"]["router"][0]
    idx, w = moe.route(h, router, None, top_k=2, scale=1.0, score_func="softmax")
    assert np.abs(np.asarray(w).sum(axis=1) - 1.0).max() < 1e-6
    want = np.asarray(_reference().route(h, router, raw))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=1)
    assert np.abs(got - want).max() < 1e-6
    # the sigmoid form is the other value of the same argument
    _, ws = moe.route(h, router, jnp.zeros((8,)), top_k=2, scale=1.0)
    assert np.abs(np.asarray(ws) - np.asarray(w)).max() > 1e-3


# --- (c) the published keys -----------------------------------------------------

def test_the_file_maps_to_its_published_sizes():
    cfg = config_mod.from_published(_published(), max_seq_len=32768, attn_impl="auto")
    cfg.validate()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (2048, 6, 32, 4, 128)
    assert (cfg.n_routed_experts, cfg.n_experts_per_tok, cfg.moe_intermediate_size) == (128, 8, 768)
    assert (cfg.index_topk, cfg.index_n_heads, cfg.index_head_dim) == (2048, 16, 64)
    assert cfg.moe_score_func == "softmax" and cfg.vocab_size == 151936
    assert cfg.rope_theta == 10000000 and not cfg.tie_word_embeddings
    assert cfg.sparse_attention and cfg.expert_block == "learned sparse attention"
    raw = json.loads(CONFIG_FILE.read_text())
    assert raw["reduced"]["num_hidden_layers"]["published"] == 48
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Keye-VL-2.0-30B-A3B"' in line) if Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() else None
    if row is not None:
        assert raw["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert raw[key] == value or key == "num_hidden_layers", key


def _sa(**over):
    return dict(json.loads(CONFIG_FILE.read_text())["sa_config"], **over)


@pytest.mark.parametrize("key,value,named", [
    ("sa_config", _sa(topk=0), "sa_config.topk"),
    ("sa_config", _sa(topk=-5), "sa_config.topk"),
    ("sa_config", _sa(indexer_num_kv_heads=2), "sa_config.indexer_num_kv_heads"),
    ("sa_config", _sa(q_chunk_size=256), "sa_config.q_chunk_size"),
    ("sa_config", {k: v for k, v in _sa().items() if k != "indexer_head_dim"}, "sa_config.indexer_head_dim"),
    ("rope_scaling", None, "rope_scaling"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("rope_scaling", {"mrope_section": [24, 20, 20], "rope_type": "default", "type": "default"}, "rope_scaling"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("sliding_window", 4096, "sliding_window"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("attention_bias", True, "attention_bias"),
    ("num_local_experts", 64, "num_local_experts"),
    ("model_type", "qwen3_moe", "model_type"),
    ("shared_expert_intermediate_size", 768, "shared_expert_intermediate_size"),
    ("kv_lora_rank", 512, "two blocks"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_a_changed_or_unknown_key_is_refused_by_name(key, value, named):
    with pytest.raises(ValueError, match=named):
        config_mod.from_published(_published(**{key: value}), max_seq_len=4096, attn_impl="auto")


def test_a_missing_key_is_refused_by_name():
    raw = _published()
    del raw["num_experts_per_tok"]
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        config_mod.from_published(raw, max_seq_len=4096, attn_impl="auto")


def test_the_other_blocks_files_map_as_before():
    for name, marker in (("Trinity-Mini", "windowed_attention"),
                         ("kanana-2-30b-a3b-instruct-2601", "latent_attention"),
                         ("mistral-7b-v0.3", None)):
        raw = json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())
        cfg = config_mod.from_published(
            {k: v for k, v in raw.items() if k not in BOOKKEEPING},
            max_seq_len=4096, attn_impl="auto")
        assert not cfg.sparse_attention and cfg.moe_score_func == "sigmoid"
        assert marker is None or getattr(cfg, marker)


def _refuse_tensor(cfg, params):
    from jax_llama_tpu.parallel.mesh import make_mesh
    from jax_llama_tpu.parallel.partition import validate_tp

    validate_tp(cfg, make_mesh(data=1, fsdp=1, tensor=2, devices=jax.devices()[:2]))


def _refuse_serve_mesh(cfg, params):
    from jax_llama_tpu.parallel.mesh import make_mesh

    jlt.ContinuousBatcher(
        params, cfg, n_slots=2, block_size=BLK,
        mesh=make_mesh(data=1, fsdp=1, tensor=2, devices=jax.devices()[:2]))


def _refuse_int8_kv(cfg, params):
    cfg.replace(kv_cache_dtype="int8").validate()


def _refuse_ring(cfg, params):
    cfg.replace(attn_impl="ring").validate()


def _refuse_speculation(cfg, params):
    jlt.ContinuousBatcher(params, cfg, n_slots=2, block_size=BLK,
                          draft_params=params, draft_config=cfg)


def _refuse_train(cfg, params):
    from jax_llama_tpu.train import init_train_state, make_optimizer, train_step

    opt = make_optimizer()
    train_step(init_train_state(params, opt), jnp.zeros((1, 8), jnp.int32), cfg, opt)


def _refuse_host_tier(cfg, params):
    jlt.ContinuousBatcher(params, cfg, n_slots=2, block_size=BLK, host_kv_blocks=4)


def _refuse_a_dense_layer(cfg, params):
    cfg.replace(first_k_dense=1).validate()


@pytest.mark.parametrize("attempt,named", [
    (_refuse_tensor, "one chip"), (_refuse_serve_mesh, "serve-mesh"),
    (_refuse_int8_kv, "int8"), (_refuse_ring, "ring"),
    (_refuse_speculation, "speculative"), (_refuse_train, "training step"),
    (_refuse_host_tier, "host tier"), (_refuse_a_dense_layer, "first_k_dense"),
], ids=["tensor", "serve-mesh", "int8-kv", "ring", "speculation", "train", "host-tier", "dense-layer"])
def test_unsupported_combination_is_refused_by_name(tiny, attempt, named):
    _, cfg, params = tiny
    with pytest.raises((ValueError, NotImplementedError), match=named):
        attempt(cfg, params)


# --- tracing, sharding rules, the cache ---------------------------------------

def test_scopes_are_in_the_lowered_programs(tiny):
    """The named scopes a device trace is read by, in the program text, for a
    prompt chunk and for a paged decode step."""
    _, cfg, params = tiny
    toks, pos = _tokens(1, 32)
    text = jax.jit(lambda p, t, q: jlt.forward(p, t, q, cfg)[0]).lower(
        params, toks, pos).as_text(debug_info=True)
    for scope in ("attn.proj", "attn.index", "attn.select", "attn.sparse", "moe.route", "moe.experts", "head"):
        assert scope in text, scope
    assert "moe.shared" not in text and "dense.ffn" not in text
    pool = serving.init_pool(cfg, 8, BLK)
    cache = serving._pool_as_cache(pool, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32))
    text = jax.jit(lambda p, t, q, c: jlt.forward(p, t, q, cfg, cache=c)[0]).lower(
        params, toks[:, :1], pos[:, :1], cache).as_text(debug_info=True)
    for scope in ("attn.proj", "attn.index", "attn.select", "attn.sparse"):
        assert scope in text, scope
    # the scoring kernel is one call under the index scope, the search and
    # the list one under the selection's; the XLA stages they replace (the
    # gather of the table's index keys, the running counts of `_compact`) are
    # not in the step
    assert '"attn.index/jit(paged_index_scores)"' in text
    assert '"attn.select/jit(paged_index_select)"' in text
    assert '"attn.index/jit(_take)"' not in text and '"attn.select/jit(cumsum)"' not in text
    # several tokens a row over the pool keep the XLA stages
    text = jax.jit(lambda p, t, q, c: jlt.forward(p, t, q, cfg, cache=c)[0]).lower(
        params, toks[:, :2], pos[:, :2], cache).as_text(debug_info=True)
    assert "paged_index_" not in text
    assert '"attn.index/jit(_take)"' in text and '"attn.select/jit(cumsum)"' in text


def test_every_parameter_has_a_partition_rule(tiny):
    _, cfg, params = tiny
    from jax_llama_tpu.parallel.mesh import make_mesh
    from jax_llama_tpu.parallel.partition import shard_abstract

    mesh = make_mesh(data=1, fsdp=1, tensor=1, devices=jax.devices()[:1])
    shapes = jax.eval_shape(lambda: params)
    placed = shard_abstract(shapes, mesh, cfg)
    assert jax.tree.structure(placed) == jax.tree.structure(shapes)


def test_the_pool_has_a_third_plane_under_the_same_table(tiny):
    """K and V as the dense block's and the one-head index-key plane beside
    them; the selection counts behind the routing and the step counts."""
    _, cfg, _ = tiny
    pool = serving.init_pool(cfg, 8, BLK)
    # a token's two KV heads side by side in one row: a gather costs by the row
    assert pool.k.shape == pool.v.shape == (2, 1, 8, BLK, 32)
    assert pool.idx.shape == (2, 1, 8, BLK, 16)
    assert pool.k_scale is None and pool.stats.shape == (dsa_moe.N_STATS,) == (12,)
    cache = jlt.init_cache(cfg, batch=2, max_len=32)
    assert cache.k.shape == (2, 2, 32, 1, 32) and cache.idx.shape == (2, 2, 32, 1, 16)
    from jax_llama_tpu.kvcache import pool_block_bytes

    assert pool_block_bytes(pool) == BLK * (2 * (2 * 2 * 16 + 16) * 4 + 4)
    dense = jlt.get_config("tiny")
    assert serving.init_pool(dense, 8, BLK).idx is None and jlt.init_cache(dense, 1).idx is None
