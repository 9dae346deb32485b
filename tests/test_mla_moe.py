"""The latent-attention block with routed experts (models/mla_moe.py,
ops/moe.py) against its plain reference, `benchmark/references/mla_moe.py`,
loaded by path: one reference, the one the benchmark's `correct` uses.

Tiny widths, seeded float32 weights, CPU.  What the served path must hold:
the full forward and prefill-then-decode through the paged latent cache give
the reference's logits; absorbed and decompressed attention agree; the pool
has one latent plane; the router selects with its bias and weighs without it
and drops no token; a request served by `ContinuousBatcher` finds its prefix
in latent blocks; what the block does not get yet is refused by name.
"""

import dataclasses
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
from jax_llama_tpu.models import mla_moe
from jax_llama_tpu.ops import moe

ROOT = Path(__file__).resolve().parent.parent
CONFIG_FILE = ROOT / "benchmark" / "configs" / "kanana-2-30b-a3b-instruct-2601.json"
BOOKKEEPING = ("source", "architecture", "reference", "reduced", "assumed", "deployment")
TINY = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16, n_routed_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32, num_hidden_layers=3,
    vocab_size=512, torch_dtype="float32",
)


def _reference():
    path = ROOT / "benchmark" / "references" / "mla_moe.py"
    spec = importlib.util.spec_from_file_location("reference_mla_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _published(**over):
    raw = dict(json.loads(CONFIG_FILE.read_text()), **over)
    return {k: v for k, v in raw.items() if k not in BOOKKEEPING}


@pytest.fixture(scope="module")
def tiny():
    """(file-style dict, program config, seeded params) at tiny widths."""
    raw = dict(json.loads(CONFIG_FILE.read_text()), **TINY)
    cfg = config_mod.from_published(
        {k: v for k, v in raw.items() if k not in BOOKKEEPING},
        max_seq_len=128, attn_impl="auto")
    cfg.validate()
    return raw, cfg, jlt.init_params(jax.random.PRNGKey(3), cfg)


def _tokens(b, t, seed=0):
    toks = np.random.RandomState(seed).randint(0, TINY["vocab_size"], size=(b, t))
    return jnp.asarray(toks), jnp.tile(jnp.arange(t)[None], (b, 1))


def test_forward_matches_the_plain_reference(tiny):
    raw, cfg, params = tiny
    toks, pos = _tokens(2, 40)
    mine = np.asarray(jlt.forward(params, toks, pos, cfg)[0])
    ref = np.asarray(_reference().logits(params, toks, raw, 0))
    assert np.abs(mine - ref).max() < 1e-4 * np.abs(ref).max()


@pytest.mark.parametrize("use_kernel", [True, False], ids=["paged-kernel", "gathered-view"])
def test_prefill_then_decode_through_the_paged_latent_cache(tiny, use_kernel):
    """Prompt through `_paged_insert`, six tokens through `_paged_decode_chunk`
    (the absorbed form over the latent pool), each step's logits recomputed by
    the reference's full forward over prompt + served tokens."""
    raw, cfg, params = tiny
    BLK, NB, P, G = 8, 16, 24, 6
    toks, _ = _tokens(1, P, seed=1)
    pool = serving.init_pool(cfg, NB, BLK)
    ids = jnp.arange(P // BLK, dtype=jnp.int32)[None]
    keys = jnp.zeros((1, 2), jnp.uint32)
    f32, i32 = jnp.float32, jnp.int32
    one = lambda v, dt: jnp.full((1,), v, dt)  # noqa: E731
    tau, _, plen, keys, pool = serving._paged_insert(
        params, pool, ids, toks, jnp.ones((1, P), bool), keys,
        one(0.0, f32), one(1.0, f32), one(0, i32), config=cfg)
    table = jnp.full((1, 8), NB, i32).at[0, :5].set(jnp.arange(5))
    served, _, _ = decode_row(
        params, cfg, pool, table, 5, P, int(tau[0]), G - 1, use_kernel=use_kernel)
    full = jnp.concatenate([toks, jnp.asarray([served], toks.dtype)], axis=1)
    ref = np.asarray(_reference().logits(params, full, raw, P - 1))[0, :G]
    deficit = ref.max(axis=1) - ref[np.arange(G), served]
    assert deficit.max() < 1e-4, deficit


def test_absorbed_equals_decompressed_attention():
    """The same attention in its two forms, to float32 rounding."""
    rng = np.random.RandomState(0)
    cfg = config_mod.LLaMAConfig(
        n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, attn_softmax_dtype="float32")
    B, T, S, H, r, dn, dr, dv = 2, 3, 20, 4, 32, 16, 8, 16
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q_nope, q_rope, kv_b = f(B, T, H, dn), f(B, T, H, dr), f(H, r, dn + dv) / 6
    latent = mla_moe._pad_last(f(B, S, r + dr), cfg.cache_width)
    q_pos = jnp.tile(jnp.arange(S - T, S)[None], (B, 1))
    kv_pos = jnp.tile(jnp.arange(S)[None], (B, 1))
    from jax_llama_tpu.ops.attention import attention_bias

    bias = attention_bias(q_pos, kv_pos, kv_pos >= 0)
    dec = mla_moe.attend_decompressed(
        q_nope, q_rope, latent, kv_b, q_pos, kv_pos, bias, cfg, use_flash=False)
    q_abs = mla_moe.absorb_query(q_nope, q_rope, kv_b, dn, cfg.cache_width)
    o_lat = mla_moe.attend_absorbed(q_abs, latent, bias, r, (dn + dr) ** -0.5)
    absorbed = jnp.einsum("bthc,hck->bthk", o_lat, kv_b[..., dn:])
    assert np.abs(np.asarray(dec - absorbed)).max() < 2e-5


# --- prefill attention over the live context only (PR 29) -------------------

TILE, VIEW, CHUNK = 16, 60, 10   # a view that is no multiple of the tile


def _walk_avals(jaxpr):
    """Every array shape in a jaxpr, sub-programs (scan, while, jit, kernel)
    included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_avals(sub)


def _context(cfg, params, index, seed):
    """A `VIEW`-slot cache whose first `index` slots hold a real context
    (written by the plain XLA form) and whose dead slots hold large finite
    garbage at position -1, and the tokens / positions of the next chunk."""
    toks, pos = _tokens(1, index + CHUNK, seed=seed)
    cache = jlt.init_cache(cfg, 1, VIEW)
    if index:
        _, cache = jlt.forward(
            params, toks[:, :index], pos[:, :index], cfg.replace(attn_impl="xla"), cache=cache)
    cache = dataclasses.replace(cache, k=cache.k.at[:, :, index:].set(1e4))
    return cache, toks[:, index:], pos[:, index:]


def _variant(name, tiny):
    """(config, params) of the walk's parity cases: the file's tiny block; the
    same with a value as wide as the whole key (24 = 16 + 8, the widest the
    XLA form's padded value takes) or narrower than its nope part (8); the `streams` tiny block of tests/test_mhc_mla_moe.py
    (four residual streams, a low-rank query, YaRN's temperature folded into
    the query)."""
    if name == "tiny":
        return tiny[1], tiny[2]
    if name == "yarn-streams":
        import test_mhc_mla_moe as streams

        raw = dict(json.loads(streams.CONFIG_FILE.read_text()), **streams.TINY)
        bookkeeping = streams.BOOKKEEPING
    else:
        raw = dict(json.loads(CONFIG_FILE.read_text()),
                   **dict(TINY, v_head_dim={"wide-value": 24, "narrow-value": 8}[name]))
        bookkeeping = BOOKKEEPING
    cfg = config_mod.from_published(
        {k: v for k, v in raw.items() if k not in bookkeeping},
        max_seq_len=128, attn_impl="auto")
    cfg.validate()
    return cfg, jlt.init_params(jax.random.PRNGKey(3), cfg)


@pytest.mark.parametrize("block,index,pad", [
    ("tiny", 0, 0), ("tiny", TILE - 1, 0), ("tiny", TILE, 0),
    ("tiny", TILE + TILE // 2, 3), ("tiny", VIEW - CHUNK, 0), ("tiny", VIEW - CHUNK, 3),
    ("tiny", 2 * TILE, 3), ("wide-value", 0, 0), ("wide-value", TILE + 3, 0),
    ("narrow-value", VIEW - CHUNK, 3), ("yarn-streams", 0, 0),
    ("yarn-streams", TILE + TILE // 2, 3), ("yarn-streams", VIEW - CHUNK, 0),
], ids=[
    "empty", "tile-1", "one-tile", "mid-second-tile-padded-tail", "whole-view",
    "whole-view-padded-tail", "two-tiles-exactly-padded-tail", "wide-value-empty",
    "wide-value-second-tile", "narrow-value-whole-view-padded-tail", "yarn-streams-empty",
    "yarn-streams-mid-second-tile-padded-tail", "yarn-streams-whole-view",
])
def test_tiled_prefill_equals_the_one_piece_form(tiny, monkeypatch, block, index, pad):
    """A chunk behind a scalar-index cache: the flash form, one kernel that
    walks the live context by tiles and then the chunk's own rows, against
    the plain XLA form (float32 softmax) over the whole view — logits of the
    real rows and the rows written.  A read of a dead slot (1e4) would show;
    so would a slot past the view, where the last tile hangs over it
    (`whole-view`: 50 live slots, tiles of 16, a view of 60), a value that
    did not keep its own width, or a temperature left out of one of the
    score's two products."""
    cfg, params = _variant(block, tiny)
    monkeypatch.setattr(mla_moe, "CTX_TILE", TILE)
    cache, toks, pos = _context(cfg, params, index, seed=index)
    real = jnp.arange(CHUNK)[None] < CHUNK - pad
    pos = jnp.where(real, pos, -1)
    got, got_cache = jlt.forward(params, toks, pos, cfg, cache=cache, attn_mask=real)
    ref, ref_cache = jlt.forward(
        params, toks, pos, cfg.replace(attn_impl="xla"), cache=cache, attn_mask=real)
    n = CHUNK - pad
    got, ref = np.asarray(got)[:, :n], np.asarray(ref)[:, :n]
    assert np.abs(got - ref).max() < 1e-4 * np.abs(ref).max()
    wrote = lambda c: np.asarray(c.k[:, :, index:index + n])  # noqa: E731
    assert np.abs(wrote(got_cache) - wrote(ref_cache)).max() < 1e-4 * np.abs(wrote(ref_cache)).max()
    assert int(got_cache.index) == index + CHUNK
    np.testing.assert_array_equal(np.asarray(got_cache.pos), np.asarray(ref_cache.pos))


@pytest.mark.parametrize("index", [0, 32, 50, 96, 112], ids=[
    "no-context", "one-tile", "mid-second-tile", "three-tiles", "into-the-overhang"])
def test_latent_kernel_over_blocks_rows_and_diagonals(index):
    """`latent_flash_attention` itself at blocks the chunk tests do not
    reach: two rows, 64 queries in two blocks of 32 (so the new rows' own
    tiles are swept to a causal bound a q block, and the diagonal tiles take
    the ragged body: 32 / 4 = 8 rows a sub-tile), behind a 112-slot view in
    tiles of 32 whose last hangs 16 slots over it, at a layer that is a
    value.  Against the one-piece XLA form with a float32 softmax."""
    from jax_llama_tpu.ops.attention import attention_bias
    from jax_llama_tpu.ops.flash_attention import latent_flash_attention

    rng = np.random.RandomState(index)
    cfg = config_mod.LLaMAConfig(
        n_heads=2, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=20, attn_softmax_dtype="float32")
    B, T, H, r, dn, dr, dv, view, layers = 2, 64, 2, 32, 16, 8, 20, 112, 3
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q_nope, q_rope, kv_b = f(B, T, H, dn), f(B, T, H, dr), f(H, r, dn + dv) / 6
    rows = mla_moe._pad_last(f(B, T, r + dr), cfg.cache_width)
    plane = mla_moe._pad_last(f(layers, B, view, r + dr), cfg.cache_width)
    live = jnp.arange(view)[None] < index
    plane = jnp.where(live[None, :, :, None], plane, 1e4)   # dead slots: garbage
    ctx_pos = jnp.where(live, jnp.arange(view)[None], -1).astype(jnp.int32)
    ctx_pos = jnp.tile(ctx_pos, (B, 1))
    q_pos = jnp.tile(index + jnp.arange(T, dtype=jnp.int32)[None], (B, 1))
    new_pos = q_pos.at[1, T - 5:].set(-1)                   # row 1: a padded tail
    got = jax.jit(lambda layer, tiles: latent_flash_attention(
        q_nope, q_rope, rows, kv_b, q_pos, new_pos, ctx=plane, ctx_pos=ctx_pos,
        layer=layer, ctx_tiles=tiles, block=32, ctx_tile=32))(
            jnp.int32(1), jnp.int32(-(-index // 32)))
    seen = jnp.concatenate([plane[1], rows], axis=1)
    kv_pos = jnp.concatenate([ctx_pos, new_pos], axis=1)
    ref = mla_moe.attend_decompressed(
        q_nope, q_rope, seen, kv_b, q_pos, kv_pos,
        attention_bias(q_pos, kv_pos, kv_pos >= 0), cfg, use_flash=False)
    real = np.asarray(new_pos >= 0)
    assert got.shape == (B, T, H, dv)
    assert np.abs(np.asarray(got - ref))[real].max() < 2e-5


def test_tile_rule_is_one_for_the_loop_and_the_counters(monkeypatch):
    monkeypatch.setattr(mla_moe, "CTX_TILE", TILE)
    assert [mla_moe.ctx_tiles(i, VIEW) for i in (0, 1, 16, 17, 50)] == [
        (16, 0), (16, 1), (16, 1), (16, 2), (16, 4)]
    assert mla_moe.ctx_tiles(7, 12) == (12, 1)       # a view under one tile
    tile, trips = mla_moe.ctx_tiles(jnp.int32(33), VIEW)   # the traced form
    assert (tile, int(trips)) == (16, 3)


def test_nothing_of_the_views_width_is_decompressed(tiny, monkeypatch):
    """Shapes of the traced program: the flash form behind a scalar-index
    cache holds no array of the view's slots (with or without the chunk's)
    times the heads; the one-piece XLA form, walked the same way, does."""
    _, cfg, params = tiny
    monkeypatch.setattr(mla_moe, "CTX_TILE", TILE)
    view, H = 64, cfg.n_heads
    toks, pos = _tokens(1, CHUNK)

    def wide(c):
        jaxpr = jax.make_jaxpr(
            lambda p, t, q, kv: jlt.forward(p, t, q, c, cache=kv)[0])(
                params, toks, pos + 20, jlt.init_cache(c, 1, view))
        return {s for s in _walk_avals(jaxpr.jaxpr)
                if H in s and any(d >= view for d in s)}

    assert wide(cfg) == set()
    assert any(view + CHUNK in s for s in wide(cfg.replace(attn_impl="xla")))


def test_dense_prefill_program_does_not_see_the_latent_flash_entry(tiny):
    """The dense block's prefill lowers `flash_attention` as it did: the
    latent walk's kernel (`latent_flash_attention`) is an entry of its own
    beside it, not a form of it, and only the latent block's programs hold
    it — one call a layer stack, with no log-sum-exp beside its output."""
    from jax_llama_tpu.ops.flash_attention import flash_attention, latent_flash_attention

    dense = jlt.get_config("tiny", attn_impl="auto")
    dp = jlt.init_params(jax.random.PRNGKey(0), dense)
    toks, pos = _tokens(1, 16)
    text = jax.jit(lambda p, t, q: jlt.forward(p, t, q, dense)[0]).lower(
        dp, toks % dense.vocab_size, pos).as_text()
    assert "@flash_attention(" in text and "latent_flash_attention" not in text
    _, cfg, params = tiny
    text = jax.jit(lambda p, t, q, c: jlt.forward(p, t, q, cfg, cache=c)[0]).lower(
        params, toks, pos, jlt.init_cache(cfg, 1, 64)).as_text()
    assert "@latent_flash_attention(" in text and "@flash_attention(" not in text
    q = jnp.zeros((1, 16, 4, 16), jnp.float32)
    p = jnp.tile(jnp.arange(16)[None], (1, 1))
    n_out = lambda f, *a: len(jax.tree.leaves(jax.eval_shape(f, *a)))  # noqa: E731
    rows, kv_b = jnp.zeros((1, 16, 128), jnp.float32), jnp.zeros((4, 32, 16 + 8), jnp.float32)
    assert n_out(flash_attention, q, q, q, p, p) == 1
    assert n_out(latent_flash_attention, q, q[..., :8], rows, kv_b, p, p) == 1
    # q = 0 and a value of 1 in every column: every score is 0, and a row's
    # output is the mean of ones over the slots it attends, whatever their count
    kv_b = kv_b.at[:, 0, 16:].set(1.0)
    out = latent_flash_attention(q, q[..., :8], rows.at[..., 0].set(1.0), kv_b, p, p)
    assert out.shape == (1, 16, 4, 8) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-6)


@pytest.mark.parametrize("block", ["latent", "dense"])
def test_prefill_context_counters_follow_the_tile_rule(tiny, monkeypatch, block):
    """One long prompt through the fused lane: `attended` is the sum over
    its chunks of ceil(index / tile) x tile for the latent block (the rule
    the device's loop runs: same function), the whole view for the dense
    block; both on `stats()`, `/metrics`' registry and the dispatch record —
    and the served tokens are an unbatched `engine.generate`'s."""
    from jax_llama_tpu.engine import GenerationConfig, generate
    from jax_llama_tpu.obs import metric_meta

    monkeypatch.setattr(mla_moe, "CTX_TILE", TILE)
    if block == "latent":
        # its own max_seq_len: a jit key no other test of the file traced
        # with another tile
        cfg, params = tiny[1].replace(max_seq_len=96), tiny[2]
    else:
        cfg = jlt.get_config("tiny", max_seq_len=96, dtype="float32", attn_impl="auto")
        params = jlt.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(7)
    prompt = [int(t) for t in rng.randint(0, cfg.vocab_size, size=75)]
    cb = jlt.ContinuousBatcher(
        params, cfg, n_slots=2, block_size=8, decode_chunk=4, prefill_budget=16)
    # a row in steady decode first: an idle server admits whole prompts
    cb.submit([int(t) for t in rng.randint(0, cfg.vocab_size, size=9)], max_new_tokens=60)
    for _ in range(4):
        cb.step()
    rid = cb.submit(prompt, max_new_tokens=4)
    out = cb.run_to_completion()
    stats = cb.stats()
    view, chunks = 96, [16 * i for i in range(5)]   # the write index of each chunk
    assert stats["prefill_chunks_total"] == len(chunks)
    assert stats["prefill_ctx_slots_view_total"] == view * len(chunks)
    want = sum(-(-i // TILE) * TILE for i in chunks) if block == "latent" else view * len(chunks)
    assert stats["prefill_ctx_slots_attended_total"] == want <= view * len(chunks)
    recs = [d["prefill_ctx"] for d in cb.obs.dispatches if "prefill_ctx" in d]
    assert [r["view"] for r in recs] == [view] * len(chunks)
    assert sum(r["attended"] for r in recs) == want
    for name in ("prefill_ctx_slots_attended_total", "prefill_ctx_slots_view_total"):
        assert metric_meta(name)[0] == "counter"
    alone = generate(
        params, jnp.asarray([prompt]), jnp.ones((1, len(prompt)), bool),
        jax.random.PRNGKey(0), config=cfg,
        gen_config=GenerationConfig(max_new_tokens=4, temperature=0.0))
    assert out[rid] == [int(t) for t in np.asarray(alone)[0, len(prompt):]]


def test_the_pool_is_one_latent_plane(tiny):
    """576 values a token a layer at the published widths (512 normed latent
    + 64 rotated shared key) in ONE cache head, stored lane-aligned, and no
    per-head plane: no `v`, no scales."""
    cfg = config_mod.from_published(_published(), max_seq_len=256, attn_impl="auto")
    assert (cfg.latent_dim, cfg.cache_heads, cfg.cache_width) == (576, 1, 640)
    pool = serving.init_pool(cfg, 4, 128)
    assert pool.k.shape == (8, 1, 4, 128, 640)
    assert pool.v is None and pool.k_scale is None and pool.v_scale is None
    from jax_llama_tpu.kvcache import _pool_names, pool_block_bytes

    assert _pool_names(pool) == ("k", "pos")
    assert pool_block_bytes(pool) == 8 * 128 * 640 * 2 + 128 * 4
    # what a forward writes behind the 576 values is zero
    _, tcfg, params = tiny
    toks, pos = _tokens(1, 8)
    _, cache = jlt.forward(params, toks, pos, tcfg, cache=jlt.init_cache(tcfg, 1, 16))
    assert cache.v is None and cache.k.shape[-2:] == (1, tcfg.cache_width)
    assert float(jnp.abs(cache.k[:, :, :8, :, :tcfg.latent_dim]).min()) > 0
    assert float(jnp.abs(cache.k[..., tcfg.latent_dim:]).max()) == 0


def test_router_selects_with_the_bias_and_weighs_without_it():
    h = jnp.asarray(np.random.RandomState(0).standard_normal((5, 16)), jnp.float32)
    w_gate = jnp.asarray(np.random.RandomState(1).standard_normal((16, 8)), jnp.float32)
    idx0, w0 = moe.route(h, w_gate, jnp.zeros((8,)), top_k=2, scale=2.448)
    np.testing.assert_allclose(np.asarray(w0.sum(axis=1)), 2.448, rtol=1e-6)
    # a bias that lifts expert 7 above every score selects it everywhere ...
    bias = jnp.zeros((8,)).at[7].set(10.0)
    idx1, w1 = moe.route(h, w_gate, bias, top_k=2, scale=2.448)
    assert bool(jnp.all(jnp.any(idx1 == 7, axis=1)))
    np.testing.assert_allclose(np.asarray(w1.sum(axis=1)), 2.448, rtol=1e-6)
    # ... and its weight is still its own sigmoid score, not score + bias
    s = jax.nn.sigmoid(h @ w_gate)
    picked = jnp.take_along_axis(s, idx1, axis=1)
    np.testing.assert_allclose(
        np.asarray(w1), np.asarray(picked / picked.sum(1, keepdims=True) * 2.448), rtol=1e-5)


def test_no_token_is_dropped_when_all_pick_the_same_experts():
    """Every token forced onto the same two experts (no capacity factor): the
    result is still each token's own weighted sum, and the counters say so."""
    rng = np.random.RandomState(0)
    N, D, E, F, k = 24, 16, 8, 12, 2
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    h, w_gate, gate_up, down = f(N, D), f(D, E) * 0.01, f(E, D, 2 * F) / 4, f(E, F, D) / 4
    layers = lambda w: jnp.stack([jnp.zeros_like(w), w])  # noqa: E731 (the experts are layer 1 of 2)
    bias = jnp.zeros((E,)).at[jnp.asarray([2, 5])].set(10.0)
    out, stats = moe.routed_experts(
        h, None, w_gate, bias, layers(gate_up), layers(down), jnp.int32(1), top_k=k, scale=2.448)
    idx, w = moe.route(h, w_gate, bias, top_k=k, scale=2.448)
    assert set(np.asarray(idx).ravel()) == {2, 5}
    want = np.zeros((N, D), np.float32)
    for n in range(N):
        for j in range(k):
            e = int(idx[n, j])
            gu = h[n] @ gate_up[e]
            want[n] += float(w[n, j]) * np.asarray((jax.nn.silu(gu[:F]) * gu[F:]) @ down[e])
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)
    assert [int(v) for v in stats] == [N * k, 2, 1, N]
    # rows that are not tokens reach no expert and count nowhere
    valid = jnp.arange(N) < 10
    out_v, stats_v = moe.routed_experts(
        h, valid, w_gate, bias, layers(gate_up), layers(down), jnp.int32(1), top_k=k, scale=2.448)
    np.testing.assert_allclose(np.asarray(out_v[:10]), want[:10], rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(out_v[10:]).max()) == 0
    assert [int(v) for v in stats_v] == [10 * k, 2, 1, 10]


def test_served_request_hits_its_prefix_in_latent_blocks(tiny):
    """Through `ContinuousBatcher`: a document asked twice.  The second ask
    finds the document's latent blocks in the radix store, and both answers
    are the tokens of an unbatched `engine.generate`."""
    _, cfg, params = tiny
    from jax_llama_tpu.engine import GenerationConfig, generate

    rng = np.random.RandomState(4)
    doc = [int(t) for t in rng.randint(0, 512, size=40)]
    asks = [doc + [int(t) for t in rng.randint(0, 512, size=5)] for _ in range(2)]
    cb = jlt.ContinuousBatcher(
        params, cfg, n_slots=2, block_size=8, decode_chunk=4, prefill_budget=16)
    first = cb.submit(asks[0], max_new_tokens=6)
    out = cb.run_to_completion()
    second = cb.submit(asks[1], max_new_tokens=6)
    out.update(cb.run_to_completion())
    stats = cb.stats()
    assert cb.prefix_hit_tokens_total == 40
    for rid, prompt in ((first, asks[0]), (second, asks[1])):
        alone = generate(
            params, jnp.asarray([prompt]), jnp.ones((1, len(prompt)), bool),
            jax.random.PRNGKey(0), config=cfg,
            gen_config=GenerationConfig(max_new_tokens=6, temperature=0.0),
        )
        assert out[rid] == [int(t) for t in np.asarray(alone)[0, len(prompt):]]
    # the router's counters came back with the loop's packed fetches
    pairs = (len(asks[0]) + (len(asks[1]) - 40) + 2 * 5) * 2 * 2
    assert stats["moe_assignments_total"] == pairs
    assert stats["moe_layer_calls_total"] > 0
    assert 1 <= stats["moe_experts_touched_total"] / stats["moe_layer_calls_total"] <= 8
    assert stats["moe_max_load_total"] <= stats["moe_assignments_total"]
    recs = [d for d in cb.obs.dispatches if "moe" in d]
    assert recs and sum(d["moe"]["assignments"] for d in recs) == pairs
    # /metrics renders every key of stats() as llm_<key>, by its registration
    from jax_llama_tpu.obs import metric_meta

    for name in ("assignments", "experts_touched", "layer_calls", "max_load"):
        assert metric_meta(f"moe_{name}_total")[0] == "counter"
        assert f"moe_{name}_total" in stats


def test_scopes_are_in_the_lowered_programs(tiny):
    """The named scopes a device trace is read by, in the program text."""
    _, cfg, params = tiny
    toks, pos = _tokens(1, 16)
    text = jax.jit(lambda p, t, q: jlt.forward(p, t, q, cfg)[0]).lower(
        params, toks, pos).as_text(debug_info=True)
    for scope in ("mla.project", "mla.attend_prefill", "moe.route", "moe.experts",
                  "moe.shared", "dense.ffn"):
        assert scope in text, scope
    step = jax.jit(lambda p, t, q, c: jlt.forward(p, t, q, cfg, cache=c)[0])
    text = step.lower(params, toks[:, :1], pos[:, :1], jlt.init_cache(cfg, 1, 16)).as_text(
        debug_info=True)
    assert "mla.attend_decode" in text
    dense = jlt.get_config("tiny")
    dp = jlt.init_params(jax.random.PRNGKey(0), dense)
    dtoks = jnp.zeros((1, 8), jnp.int32)
    text = jax.jit(lambda p, t, q: jlt.forward(p, t, q, dense)[0]).lower(
        dp, dtoks, pos[:, :8]).as_text(debug_info=True)
    assert "dense.attention" in text and "dense.ffn" in text


def test_every_parameter_has_a_partition_rule(tiny):
    _, cfg, params = tiny
    from jax_llama_tpu.parallel.mesh import make_mesh
    from jax_llama_tpu.parallel.partition import shard_abstract, validate_tp

    mesh = make_mesh(data=1, fsdp=1, tensor=1, devices=jax.devices()[:1])
    shapes = jax.eval_shape(lambda: params)
    placed = shard_abstract(shapes, mesh, cfg)
    assert jax.tree.structure(placed) == jax.tree.structure(shapes)
    with pytest.raises(ValueError, match="one chip"):
        validate_tp(cfg, make_mesh(data=1, fsdp=1, tensor=2, devices=jax.devices()[:2]))


# --- the published-key map -------------------------------------------------

def test_mistral_keys_give_the_benchmarks_own_object():
    from benchmark.published import from_published as benchmarks_copy

    raw = json.loads((ROOT / "benchmark" / "configs" / "mistral-7b-v0.3.json").read_text())
    pub = {k: v for k, v in raw.items() if k not in BOOKKEEPING}
    for kw in (dict(max_seq_len=2048, attn_impl="auto"), dict(max_seq_len=4096, attn_impl="xla")):
        assert config_mod.from_published(pub, **kw) == benchmarks_copy(pub, **kw)


def test_kanana_file_maps_to_its_published_sizes():
    cfg = config_mod.from_published(_published(), max_seq_len=16384, attn_impl="auto")
    cfg.validate()
    assert (cfg.dim, cfg.n_heads, cfg.n_layers, cfg.vocab_size) == (2048, 32, 8, 128256)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_routed_experts, cfg.n_experts_per_tok, cfg.n_shared_experts) == (128, 6, 2)
    assert (cfg.moe_intermediate_size, cfg.ffn_dim, cfg.first_k_dense) == (768, 6144, 1)
    assert cfg.routed_scaling_factor == 2.448 and cfg.rms_norm_eps == 1e-6
    shapes = jax.eval_shape(lambda: jlt.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(n - 5.07e9) < 0.01e9  # 10.14 GB in bfloat16


@pytest.mark.parametrize("key,value,named", [
    ("index_topk", 16, "index_topk"),                 # a key no block knows
    ("sliding_window", 4096, "sliding_window"),
    ("q_lora_rank", 1536, "q_lora_rank"),             # known keys at a value
    ("scoring_func", "softmax", "scoring_func"),      # the block does not compute
    ("topk_method", "greedy", "topk_method"),
    ("n_group", 8, "n_group"),
    ("topk_group", 4, "topk_group"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("rope_interleave", False, "rope_interleave"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("hidden_act", "gelu", "hidden_act"),
    ("attention_bias", True, "attention_bias"),
    ("model_type", "deepseek_v2", "model_type"),
    ("qk_head_dim", 256, "qk_head_dim"),
    ("torch_dtype", "float16", "torch_dtype"),
])
def test_a_changed_or_unknown_key_is_refused_by_name(key, value, named):
    with pytest.raises(ValueError, match=named):
        config_mod.from_published(_published(**{key: value}), max_seq_len=256, attn_impl="auto")


@pytest.mark.parametrize("key", ["kv_lora_rank", "n_routed_experts", "first_k_dense_replace"])
def test_a_latent_key_on_its_own_is_not_the_dense_block(key):
    """A dense file that gains one key of the other block is refused, never
    served as the dense block of the same hidden size."""
    raw = json.loads((ROOT / "benchmark" / "configs" / "mistral-7b-v0.3.json").read_text())
    pub = {k: v for k, v in raw.items() if k not in BOOKKEEPING}
    with pytest.raises(ValueError):
        config_mod.from_published(dict(pub, **{key: 4}), max_seq_len=256, attn_impl="auto")


# --- what the block does not get yet is refused at start ---------------------

def _refuse_int8_kv(cfg, params):
    cfg.replace(kv_cache_dtype="int8").validate()


def _refuse_ring(cfg, params):
    cfg.replace(attn_impl="ring").validate()


def _refuse_quantize(cfg, params):
    from jax_llama_tpu.ops.quant import quantize_params

    dense = jlt.get_config("tiny")
    q = quantize_params(jlt.init_params(jax.random.PRNGKey(0), dense))
    jlt.ContinuousBatcher(dict(params, lm_head=q["lm_head"]), cfg, n_slots=1)


def _refuse_speculation(cfg, params):
    jlt.ContinuousBatcher(params, cfg, n_slots=1, draft_params=params, draft_config=cfg)


def _refuse_serve_mesh(cfg, params):
    from jax_llama_tpu.parallel.serve_mesh import ServeMeshSpec, build_serve_mesh

    mesh = build_serve_mesh(ServeMeshSpec(data=1, tensor=2), devices=jax.devices()[:2])
    jlt.ContinuousBatcher(params, cfg, n_slots=2, mesh=mesh)


def _refuse_train(cfg, params):
    from jax_llama_tpu.train import init_train_state, make_optimizer, train_step

    opt = make_optimizer()
    train_step(init_train_state(params, opt), jnp.zeros((1, 8), jnp.int32), cfg, opt)


@pytest.mark.parametrize("attempt,named", [
    (_refuse_int8_kv, "int8"), (_refuse_ring, "ring"), (_refuse_quantize, "quantize"),
    (_refuse_speculation, "speculative"), (_refuse_serve_mesh, "serve-mesh"),
    (_refuse_train, "training step"),
], ids=["int8-kv", "ring", "quantize", "speculation", "serve-mesh", "train"])
def test_unsupported_combination_is_refused_at_start(tiny, attempt, named):
    _, cfg, params = tiny
    with pytest.raises((ValueError, NotImplementedError), match=named):
        attempt(cfg, params)


def test_host_kv_blocks_help_takes_the_block_size_from_the_pool():
    """`--host-kv-blocks`' help no longer states a formula of the dense block."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "jax_llama_tpu.run", "--help"], capture_output=True,
        text=True, cwd=ROOT, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert "pool_block_bytes" in out.stdout and "kv_heads*block_size*head_dim" not in out.stdout
