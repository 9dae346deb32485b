"""The latent-attention block with a multi-stream residual (models/mla_moe.py at
`hc_mult` 4, ops/mhc.py; a low-rank query, YaRN rope) against its plain
reference, `benchmark/references/mhc_mla_moe.py`, loaded by path: one
reference, the one the benchmark's `correct` uses.

Tiny widths with n = 4 streams, seeded float32 weights, CPU.  Tolerances: the
program and the reference compute the same float32 sums in another order, so
logits agree to 1e-4 of the largest logit, and a served token's reference logit
lies within 1e-4 of the reference's largest (the greedy token, but for exact
ties).  What the served path must hold: the full forward, prefill then decode
through the paged latent cache, a re-ask over a prefix hit and the fused chunk
beside riding decode rows give the reference's logits; the unit's coefficients
have their published properties; the published-key map is strict; what the
block does not get yet is refused by name.
"""

import dataclasses
import importlib.util
import json
import math
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
from jax_llama_tpu.ops import mhc, rope

ROOT = Path(__file__).resolve().parent.parent
CONFIG_FILE = ROOT / "benchmark" / "configs" / "Xing4.0-29B-A4B.json"
KANANA_FILE = ROOT / "benchmark" / "configs" / "kanana-2-30b-a3b-instruct-2601.json"
BOOKKEEPING = ("source", "architecture", "reference", "reduced", "assumed", "deployment")
TINY = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32, num_hidden_layers=3,
    vocab_size=512, torch_dtype="float32",
)
UNITS = 2 * TINY["num_hidden_layers"]


def _reference():
    path = ROOT / "benchmark" / "references" / "mhc_mla_moe.py"
    spec = importlib.util.spec_from_file_location("reference_mhc_mla_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _published(file=CONFIG_FILE, **over):
    raw = dict(json.loads(file.read_text()), **over)
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


def _deficits(raw, params, prompt, served):
    """Reference max logit minus the served token's, at each served position."""
    full = jnp.asarray([list(prompt) + list(served)], jnp.int32)
    ref = _reference().logits(params, full, raw, len(prompt) - 1)[0, :len(served)]
    return ref.max(axis=1) - ref[np.arange(len(served)), served]


# --- the program against the plain reference --------------------------------

def test_forward_matches_the_plain_reference(tiny):
    raw, cfg, params = tiny
    assert (cfg.hc_mult, cfg.q_lora_rank, cfg.rope_yarn) == (4, 24, (64.0, 4096.0, 32.0, 1.0, 1.0))
    toks, pos = _tokens(2, 40)
    mine = np.asarray(jlt.forward(params, toks, pos, cfg)[0])
    ref = _reference().logits(params, toks, raw, 0)
    assert np.abs(mine - ref).max() < 1e-4 * np.abs(ref).max()
    # each wrong reference of the benchmark's control is another function
    for fault in _reference().FAULTS:
        wrong = _reference().logits(params, toks, raw, 0, fault=fault)
        assert np.abs(wrong - ref).max() > 20 * np.abs(mine - ref).max(), fault


@pytest.mark.parametrize("use_kernel", [True, False], ids=["paged-kernel", "gathered-view"])
def test_prefill_then_decode_through_the_paged_latent_cache(tiny, use_kernel):
    """Prompt through `_paged_insert`, six tokens through `_paged_decode_chunk`
    (four streams around the absorbed form over the latent pool), each step's
    logits recomputed by the reference's full forward over prompt + served
    tokens; the units' counters ride the packed fetch behind the router's."""
    raw, cfg, params = tiny
    BLK, NB, P, G = 8, 16, 24, 6
    toks, _ = _tokens(1, P, seed=1)
    pool = serving.init_pool(cfg, NB, BLK)
    assert pool.stats.shape == (mla_moe.n_stats(cfg),) == (6,)
    ids = jnp.arange(P // BLK, dtype=jnp.int32)[None]
    keys = jnp.zeros((1, 2), jnp.uint32)
    f32, i32 = jnp.float32, jnp.int32
    one = lambda v, dt: jnp.full((1,), v, dt)  # noqa: E731
    tau, _, plen, keys, pool = serving._paged_insert(
        params, pool, ids, toks, jnp.ones((1, P), bool), keys,
        one(0.0, f32), one(1.0, f32), one(0, i32), config=cfg)
    table = jnp.full((1, 8), NB, i32).at[0, :5].set(jnp.arange(5))
    served, _, stats = decode_row(
        params, cfg, pool, table, 5, P, int(tau[0]), G - 1, use_kernel=use_kernel)
    deficit = _deficits(raw, params, np.asarray(toks[0]), served)
    assert deficit.max() < 1e-4, deficit
    # the insert's 24 tokens and 5 decode iterations, six units each
    assert int(stats[5]) == (P + G - 1) * UNITS and 0 <= int(stats[4]) <= int(stats[5])


def test_served_requests_take_the_fused_chunk_and_the_prefix_hit(tiny):
    """Through `ContinuousBatcher`: beside a holder in steady decode a long
    prompt is admitted through the fused chunk (five chunks with riding decode
    rows), then asked again with another ending over its prefix hit.  Every
    served token's reference logit is the reference's largest, the tokens are
    an unbatched `engine.generate`'s, and the units' counters reach `stats()`
    and the dispatch records as `hc`."""
    raw, cfg, params = tiny
    from jax_llama_tpu.engine import GenerationConfig, generate
    from jax_llama_tpu.obs import metric_meta

    rng = np.random.RandomState(7)
    draw = lambda k: [int(t) for t in rng.randint(0, cfg.vocab_size, size=k)]  # noqa: E731
    doc = draw(72)
    asks = [doc + draw(5), doc + draw(6)]
    cb = jlt.ContinuousBatcher(
        params, cfg, n_slots=2, block_size=8, decode_chunk=4, prefill_budget=16)
    hold_prompt = draw(9)
    hold = cb.submit(hold_prompt, max_new_tokens=60)
    out = {}

    def step():
        for rid, tok, *_ in cb.step():
            out.setdefault(rid, []).append(tok)

    for _ in range(4):
        step()
    first = cb.submit(asks[0], max_new_tokens=6)
    while len(out.get(first, ())) < 6:      # the holder keeps decoding beside it
        step()
    second = cb.submit(asks[1], max_new_tokens=6)
    for rid, toks in cb.run_to_completion().items():
        out.setdefault(rid, []).extend(toks)
    stats = cb.stats()
    assert stats["prefill_chunks_total"] >= 5 and cb.prefix_hit_tokens_total == 72
    for rid, prompt in ((first, asks[0]), (second, asks[1]), (hold, hold_prompt)):
        assert _deficits(raw, params, prompt, out[rid]).max() < 1e-4
    alone = generate(
        params, jnp.asarray([asks[1]]), jnp.ones((1, len(asks[1])), bool),
        jax.random.PRNGKey(0), config=cfg,
        gen_config=GenerationConfig(max_new_tokens=6, temperature=0.0))
    assert out[second] == [int(t) for t in np.asarray(alone)[0, len(asks[1]):]]
    # every token that went through a forward passed six units: the prompts
    # (less the second ask's 72 cached tokens) and all but a request's last token
    tokens = len(hold_prompt) + len(asks[0]) + len(asks[1]) - 72 + (60 - 1) + 2 * (6 - 1)
    assert stats["hc_units_total"] == tokens * UNITS
    assert 0 <= stats["hc_unconverged_total"] <= 0.1 * stats["hc_units_total"]
    recs = [d["hc"] for d in cb.obs.dispatches if "hc" in d]
    assert recs and sum(r["units"] for r in recs) == stats["hc_units_total"]
    assert stats["attn_window_kv_steps_total"] == 0     # the tail is the units', not the window's
    for name in ("hc_unconverged_total", "hc_units_total"):
        assert metric_meta(name)[0] == "counter"


# --- the unit's coefficients --------------------------------------------------

def _unit_params(n=4, C=32, **over):
    hp = jax.tree.map(lambda a: a[0], mhc.init_unit(jax.random.PRNGKey(5), 1, n, C))
    return dict(hp, **over)


def _coefficients(X, hp, iters=20, clamp=(-30.0, 30.0)):
    return mhc.coefficients(X, hp, iters=iters, eps=1e-6, clamp=clamp)


def test_h_res_is_doubly_stochastic_after_twenty_rounds_and_not_after_one():
    X = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 4, 32))
    hp = _unit_params()
    off = {}
    for iters in (1, 20):
        _, _, h_res, stats = _coefficients(X, hp, iters=iters)
        h = np.asarray(h_res)
        assert h.min() > 0
        off[iters] = np.maximum(np.abs(h.sum(-1) - 1).max(-1), np.abs(h.sum(-2) - 1).max(-1))
        # the counter is the same reading, taken on the device
        assert (int(stats[0]), int(stats[1])) == (int((off[iters] > 1e-3).sum()), 128)
    assert np.quantile(off[20], 0.98) < 1e-3 and np.median(off[1]) > 1e-2
    # neither the identity nor uniform, and it moves with the token
    h = np.asarray(_coefficients(X, hp)[2])
    mass = 1 - np.trace(h, axis1=-2, axis2=-1) / 4
    assert 0.3 < mass.mean() < 0.8 and mass.min() > 0.01 and mass.max() < 0.995 and mass.std() > 0.05


def test_the_clamp_is_reached_and_holds():
    """Logits far outside +-30: every one is clipped before exp, so exp stays
    finite in float32 and H_res a proper matrix; at a clamp of +-2 the same
    stream gives another matrix, and counts only the tokens it is told to."""
    X = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 4, 32))
    hp = _unit_params()
    hp = dict(hp, alpha=hp["alpha"].at[2].set(400.0))
    _, _, h_res, _ = _coefficients(X, hp)
    h = np.asarray(h_res)
    assert np.isfinite(h).all() and h.min() > 0 and h.max() <= 1 + 1e-6
    # logits of +-30 and little between: an entry clipped at -30 is nothing
    # beside one clipped at +30 in its row and column, yet no entry is 0 ...
    assert (h < 1e-6).mean() > 0.25
    # ... and without the clamp the same logits leave float32
    assert not np.isfinite(np.asarray(_coefficients(X, hp, clamp=(-1e9, 1e9))[2])).all()
    narrow = np.asarray(_coefficients(X, hp, clamp=(-2.0, 2.0))[2])
    # e^-2 / (e^-2 + ... ) at the least: nothing under ~1e-3 survives a +-2 clamp
    assert narrow.min() > 1e-3 and np.abs(narrow - h).max() > 0.1
    valid = jnp.arange(16)[None] < 10
    stats = mhc.coefficients(X, hp, iters=20, eps=1e-6, clamp=(-30.0, 30.0), valid=valid)[3]
    assert int(stats[1]) == 10


def test_h_pre_and_h_post_ranges_and_the_mixes():
    X = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 4, 32))
    hp = _unit_params()
    h_pre, h_post, h_res, _ = _coefficients(X, hp)
    assert 0 < float(h_pre.min()) and float(h_pre.max()) < 1
    assert 0 < float(h_post.min()) and float(h_post.max()) < 2 and float(h_post.max()) > 1
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    np.testing.assert_allclose(
        np.asarray(mhc.pre(X, h_pre)), np.einsum("bti,btic->btc", h_pre, X), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(mhc.post(X, y, h_res, h_post)),
        np.einsum("btij,btjc->btic", h_res, X) + np.asarray(h_post)[..., None] * np.asarray(y)[:, :, None],
        atol=1e-5)
    # the reference's coefficients are these
    ref = _reference().coefficients(
        X[0], hp, {"hc_eps": 1e-6, "hc_sinkhorn_iters": 20,
                   "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30})
    for mine, theirs in zip((h_pre[0], h_post[0], h_res[0]), ref):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), atol=2e-6)


def test_the_seeded_gates_are_a_route_through_the_streams(tiny):
    """Unit u (two a layer through the stack: attention 2l, FFN 2l + 1) reads
    mostly stream u mod n and feeds mostly stream (u + 2) mod n, so that what a
    unit wrote reaches the next one's stream through H_res alone — the same
    route whatever the seed."""
    _, cfg, params = tiny
    other = jlt.init_params(jax.random.PRNGKey(11), cfg)
    n = cfg.hc_mult
    for tree, first in (("dense_layers", 0), ("moe_layers", cfg.first_k_dense)):
        for kind, name in enumerate(("hc_attn", "hc_ffn")):
            for p in (params, other):
                b = np.asarray(p[tree][name]["b"])
                u = 2 * (first + np.arange(b.shape[0])) + kind
                np.testing.assert_array_equal(b[:, :n].argmax(1), u % n)
                np.testing.assert_array_equal(b[:, n:2 * n].argmax(1), (u + 2) % n)
                assert (np.sort(b[:, :2 * n].reshape(-1, n), axis=1)[:, -2] < 0).all()


def test_the_sinkhorn_kernel_is_the_loop():
    """The chip's form (one Pallas kernel, interpreted here, a token a lane)
    against the XLA loop the CPU takes, at a token count that fills no tile."""
    logits = jax.random.normal(jax.random.PRNGKey(4), (4, 4, 1100)) * 3
    loop = mhc._sinkhorn_xla(logits, 3, 1e-6)
    kernel = mhc._sinkhorn_pallas(logits, 3, 1e-6, interpret=True)
    assert kernel.shape == (4, 4, 1100)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(loop), rtol=2e-6)


def test_a_bfloat16_stream_is_projected_with_float32_phi():
    """phi in three bfloat16 parts against the stream as stored: the float32
    product of the SAME bfloat16 stream, far closer than phi rounded once."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.standard_normal((8, 512)), jnp.bfloat16)
    phi = jnp.asarray(rng.standard_normal((512, 24)) / 23, jnp.float32)
    exact = np.asarray(x, np.float64) @ np.asarray(phi, np.float64)
    split = np.abs(np.asarray(mhc._project(x, phi)) - exact).max()
    once = np.abs(np.asarray(x, np.float64) @ np.asarray(phi.astype(jnp.bfloat16), np.float64) - exact).max()
    assert split < 1e-5 and once > 50 * split


# --- YaRN and the low-rank query ---------------------------------------------

def test_yarn_frequencies_and_scale_at_the_published_numbers():
    """d = 64, base 10000, factor 64, L0 4096, beta_fast 32, beta_slow 1.  By
    hand: d ln(L0 / (2 pi 32)) / (2 ln base) = 64 x ln(20.372) / 18.4207 =
    64 x 3.01417 / 18.4207 = 10.47 -> low = 10; d ln(L0 / (2 pi)) / (2 ln base) =
    64 x ln(651.90) / 18.4207 = 64 x 6.47990 / 18.4207 = 22.51 -> high = 23.  So
    pairs 0..10 keep f_i = 10000^(-i/32), pairs 23..31 are f_i / 64, and pair i
    between has ramp (i - 10) / 13.  m = 0.1 ln 64 + 1 = 1.41589, m^2 = 2.00474."""
    inv = rope.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    f = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], f[23:] / 64, rtol=1e-12)
    ramp = (np.arange(11, 23) - 10) / 13.0
    np.testing.assert_allclose(inv[11:23], f[11:23] / 64 * ramp + f[11:23] * (1 - ramp), rtol=1e-12)
    np.testing.assert_allclose(inv, _reference().yarn_inv_freq(
        64, 10000.0, _published()["rope_scaling"]), rtol=1e-12)
    assert abs(rope.yarn_mscale(64.0, 1.0) - 1.41589) < 1e-5
    cfg = config_mod.from_published(_published(), max_seq_len=256, attn_impl="auto")
    assert abs(mla_moe.softmax_scale(cfg) * math.sqrt(192) - 2.00474) < 1e-5
    assert abs(_reference().softmax_scale(_published()) - mla_moe.softmax_scale(cfg)) < 1e-12
    # the tables a forward rotates by are these frequencies'
    cos, _ = mla_moe._yarn_tables(cfg, 512)
    np.testing.assert_allclose(cos[7], np.cos(7 * inv), atol=1e-6)
    kanana = config_mod.from_published(_published(KANANA_FILE), max_seq_len=256, attn_impl="auto")
    assert mla_moe.softmax_scale(kanana) == 1.0 / math.sqrt(192)


def test_the_low_rank_query_replaces_the_full_one():
    """`q_lora_rank` null (kanana) against 768 (Xing4.0), at published sizes."""
    def attention_leaves(file):
        cfg = config_mod.from_published(_published(file), max_seq_len=256, attn_impl="auto")
        shapes = jax.eval_shape(lambda: jlt.init_params(jax.random.PRNGKey(0), cfg))
        leaves = {k: jax.tree.map(lambda a: a.shape, v) for k, v in shapes["moe_layers"].items()
                  if k.startswith(("q", "hc_"))}
        return cfg, leaves, shapes

    kanana, leaves, _ = attention_leaves(KANANA_FILE)
    assert kanana.q_lora_rank == 0 and leaves == {"q": (7, 32, 2048, 192)}
    cfg, leaves, shapes = attention_leaves(CONFIG_FILE)
    assert cfg.q_lora_rank == 768
    unit = {"phi": (5, 14336, 24), "b": (5, 24), "alpha": (5, 3)}
    assert leaves == {"q_a": (5, 3584, 768), "q_a_norm": (5, 768), "q_b": (5, 32, 768, 192),
                      "hc_attn": unit, "hc_ffn": unit}
    assert shapes["moe_layers"]["hc_attn"]["phi"].dtype == jnp.float32
    # ISSUE 51's arithmetic: 9.585 GB of bfloat16 + the units' float32
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(n - 4.7925e9) < 0.005e9


def test_one_stream_is_the_plain_residual_and_traces_no_unit(monkeypatch):
    """`hc_mult` 1 (the kanana preset): no unit is traced — the functions of
    ops/mhc.py may raise — the tree has no unit, the counters are the
    router's four, and the program text names no `hc.` scope."""
    raw = dict(json.loads(KANANA_FILE.read_text()),
               hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16, n_routed_experts=8,
               num_experts_per_tok=2, moe_intermediate_size=32, num_hidden_layers=3,
               vocab_size=512, torch_dtype="float32")
    cfg = config_mod.from_published(
        {k: v for k, v in raw.items() if k not in BOOKKEEPING}, max_seq_len=128, attn_impl="auto")
    assert (cfg.hc_mult, cfg.q_lora_rank, cfg.rope_yarn) == (1, 0, None)
    params = jlt.init_params(jax.random.PRNGKey(3), cfg)
    assert not [k for k in params["moe_layers"] if k.startswith(("hc_", "q_"))]
    toks, pos = _tokens(2, 24)
    before = np.asarray(jlt.forward(params, toks, pos, cfg)[0])

    def never(*a, **kw):
        raise AssertionError("an mHC unit was traced for one stream")

    for name in ("coefficients", "pre", "post"):
        monkeypatch.setattr(mhc, name, never)
    fwd = jax.jit(lambda p, t, q, c: jlt.forward(p, t, q, cfg, cache=c))
    cache = jlt.init_cache(cfg, 2, 32)
    assert cache.stats.shape == (4,)
    text = fwd.lower(params, toks, pos, cache).as_text(debug_info=True)
    assert not [scope for scope in ("hc.coeff", "hc.pre", "hc.post") if scope in text]
    after, cache = fwd(params, toks, pos, cache)
    np.testing.assert_array_equal(np.asarray(after), before)   # bitwise: one program
    assert cache.stats.shape == (4,)


def test_scopes_are_in_the_lowered_programs(tiny):
    _, cfg, params = tiny
    toks, pos = _tokens(1, 16)
    text = jax.jit(lambda p, t, q: jlt.forward(p, t, q, cfg)[0]).lower(
        params, toks, pos).as_text(debug_info=True)
    for scope in ("hc.coeff", "hc.pre", "hc.post", "mla.project", "mla.attend_prefill",
                  "moe.route", "moe.experts", "moe.shared", "dense.ffn"):
        assert scope in text, scope
    # the low-rank query's two products lie under the projections' scope
    assert "mla.project/btd,dr->btr" in text and "mla.project/btr,hrk->bthk" in text


def test_every_parameter_has_a_partition_rule(tiny):
    _, cfg, params = tiny
    from jax_llama_tpu.parallel.mesh import make_mesh
    from jax_llama_tpu.parallel.partition import param_partition_specs, shard_abstract, validate_tp

    mesh = make_mesh(data=1, fsdp=1, tensor=1, devices=jax.devices()[:1])
    shapes = jax.eval_shape(lambda: params)
    placed = shard_abstract(shapes, mesh, cfg)
    assert jax.tree.structure(placed) == jax.tree.structure(shapes)
    specs = param_partition_specs(cfg)
    for tree in ("dense_layers", "moe_layers"):
        assert set(specs[tree]) == set(params[tree])
        assert set(specs[tree]["hc_attn"]) == set(specs[tree]["hc_ffn"]) == {"phi", "b", "alpha"}
    with pytest.raises(ValueError, match="one chip"):
        validate_tp(cfg, make_mesh(data=1, fsdp=1, tensor=2, devices=jax.devices()[:2]))


# --- the published-key map -------------------------------------------------

def test_the_file_maps_to_its_published_sizes():
    cfg = config_mod.from_published(_published(), max_seq_len=8192, attn_impl="auto")
    cfg.validate()
    assert (cfg.dim, cfg.n_heads, cfg.n_layers, cfg.vocab_size) == (3584, 32, 6, 131072)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 768, 128, 64, 128)
    assert (cfg.n_routed_experts, cfg.n_experts_per_tok, cfg.n_shared_experts) == (64, 4, 1)
    assert (cfg.moe_intermediate_size, cfg.ffn_dim, cfg.first_k_dense) == (1024, 9216, 1)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.rope_yarn == (64.0, 4096.0, 32.0, 1.0, 1.0) and cfg.routed_scaling_factor == 2
    assert (cfg.latent_dim, cfg.cache_heads, cfg.cache_width) == (576, 1, 640)
    # a configuration survives its checkpoint's config.json (tuples come back lists)
    back = config_mod.LLaMAConfig(**json.loads(json.dumps(dataclasses.asdict(cfg))))
    assert back == cfg and hash(back) == hash(cfg)


YARN = _published()["rope_scaling"] if CONFIG_FILE.exists() else {}


@pytest.mark.parametrize("key,value,named", [
    ("hc_mult", 0, "hc_mult"), ("hc_mult", 2.5, "hc_mult"),
    ("hc_sinkhorn_iters", 0, "hc_sinkhorn_iters"),
    ("hc_eps", 0, "hc_eps"), ("hc_eps", "1e-6", "hc_eps"),
    ("mhc_h_res_clamp_min", 40, "mhc_h_res_clamp_max"),
    ("mhc_h_res_clamp_max", -30, "mhc_h_res_clamp_max"),
    ("q_lora_rank", None, "q_lora_rank"), ("q_lora_rank", 0, "q_lora_rank"),
    ("rope_scaling", None, "rope_scaling"),
    ("rope_scaling", dict(YARN, type="linear"), "rope_scaling"),
    ("rope_scaling", dict(YARN, mscale=0.707), "rope_scaling"),
    ("rope_scaling", dict(YARN, factor=0.5), "rope_scaling"),
    ("rope_scaling", {k: v for k, v in YARN.items() if k != "beta_fast"}, "beta_fast"),
    ("rope_scaling", dict(YARN, truncate=False), "truncate"),
    ("num_nextn_predict_layers", 2, "num_nextn_predict_layers"),
    ("ep_size", 8, "ep_size"),
    ("model_type", "xing5_0", "model_type"),
    ("scoring_func", "softmax", "scoring_func"),
    ("topk_method", "greedy", "topk_method"),
    ("n_group", 8, "n_group"), ("topk_group", 4, "topk_group"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("hidden_act", "gelu", "hidden_act"), ("attention_bias", True, "attention_bias"),
    ("torch_dtype", "float16", "torch_dtype"),
    ("hc_gate", True, "hc_gate"),                     # a key no block knows
    ("index_topk", 16, "index_topk"),
])
def test_a_changed_or_unknown_key_is_refused_by_name(key, value, named):
    with pytest.raises(ValueError, match=named):
        config_mod.from_published(_published(**{key: value}), max_seq_len=256, attn_impl="auto")


@pytest.mark.parametrize("key", [
    "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
    "q_lora_rank", "rope_scaling", "kv_lora_rank", "first_k_dense_replace", "torch_dtype"])
def test_a_missing_key_is_refused_by_name(key):
    raw = _published()
    del raw[key]
    with pytest.raises(ValueError, match=key if key != "kv_lora_rank" else "hc_mult|kv_lora_rank"):
        config_mod.from_published(raw, max_seq_len=256, attn_impl="auto")


def test_the_streams_keys_belong_to_their_model_type_only():
    """A deepseek_v3 file that gains the streams' keys is refused, never served
    with one stream under the model's name; so is a dense file."""
    with pytest.raises(ValueError, match="hc_mult"):
        config_mod.from_published(_published(KANANA_FILE, hc_mult=4), max_seq_len=256, attn_impl="auto")
    with pytest.raises(ValueError, match="q_lora_rank"):
        config_mod.from_published(_published(KANANA_FILE, q_lora_rank=768), max_seq_len=256, attn_impl="auto")
    dense = _published(ROOT / "benchmark" / "configs" / "mistral-7b-v0.3.json", hc_mult=4)
    with pytest.raises(ValueError, match="hc_mult"):
        config_mod.from_published(dense, max_seq_len=256, attn_impl="auto")


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


def _refuse_bad_streams(cfg, params):
    cfg.replace(hc_sinkhorn_iters=0).validate()


@pytest.mark.parametrize("attempt,named", [
    (_refuse_int8_kv, "int8"), (_refuse_ring, "ring"), (_refuse_quantize, "quantize"),
    (_refuse_speculation, "speculative"), (_refuse_serve_mesh, "serve-mesh"),
    (_refuse_train, "training step"), (_refuse_bad_streams, "hc_sinkhorn_iters"),
], ids=["int8-kv", "ring", "quantize", "speculation", "serve-mesh", "train", "streams"])
def test_unsupported_combination_is_refused_at_start(tiny, attempt, named):
    _, cfg, params = tiny
    with pytest.raises((ValueError, NotImplementedError), match=named):
        attempt(cfg, params)
