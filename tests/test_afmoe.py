"""The window-and-full-attention block with routed experts (models/afmoe.py)
against its plain reference, `benchmark/references/afmoe.py`, loaded by path:
one reference, the one the benchmark's `correct` uses.

Tiny widths, seeded float32 weights, CPU.  The window is 24 tokens over blocks
of 16 (no multiple of the block) and prompts of 100 (four windows deep), so a
missing or off-by-one window mask, a rope on the full layer or a missing gate
fails the float32 tolerances — which a bfloat16 compute would fail too.
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
from jax_llama_tpu.models import afmoe
from jax_llama_tpu.ops.attention import attention_bias, sdpa

# By module NAME: `jax_llama_tpu.ops` re-exports functions of these names.
fa = importlib.import_module("jax_llama_tpu.ops.flash_attention")
pa = importlib.import_module("jax_llama_tpu.ops.paged_attention")

ROOT = Path(__file__).resolve().parent.parent
CONFIG_FILE = ROOT / "benchmark" / "configs" / "Trinity-Mini.json"
BOOKKEEPING = ("source", "architecture", "reference", "reduced", "assumed", "deployment")
W, BLK = 24, 16
TINY = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, vocab_size=512, sliding_window=W,
    torch_dtype="float32",
)


def _reference():
    path = ROOT / "benchmark" / "references" / "afmoe.py"
    spec = importlib.util.spec_from_file_location("reference_afmoe", path)
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
    """(file-style dict, program config, seeded params) at tiny widths: five
    layers [sliding, sliding, sliding, full, sliding], the first dense."""
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
def test_forward_matches_the_plain_reference(tiny, attn):
    raw, cfg, params = tiny
    toks, pos = _tokens(2, 100)
    mine = np.asarray(jlt.forward(params, toks, pos, cfg.replace(attn_impl=attn))[0])
    ref = np.asarray(_reference().logits(params, toks, raw, 0))
    assert np.abs(mine - ref).max() < 1e-4 * np.abs(ref).max()


@pytest.mark.parametrize("use_kernel", [True, False], ids=["paged-kernel", "gathered-view"])
def test_prefill_then_decode_through_the_paged_cache(tiny, use_kernel):
    """A 96-token prompt (four windows) through `_paged_insert`, eight tokens
    through `_paged_decode_chunk`, each step's logits recomputed by the
    reference's full forward over prompt + served tokens."""
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
    served, _, stats = decode_row(
        params, cfg, pool, table, 7, P, int(tau[0]), G - 1, use_kernel=use_kernel)
    assert _deficit(params, raw, [int(t) for t in toks[0]], served).max() < 1e-4
    # the kernel's step counts rode the packed fetch; the gathered view has none
    steps = stats[-2:]
    assert (steps > 0).all() if use_kernel else (steps == 0).all()


@pytest.mark.parametrize("use_kernel", [True, False], ids=["paged-kernel", "gathered-view"])
def test_served_through_the_fused_lane_and_a_prefix_hit_deeper_than_the_window(tiny, use_kernel):
    """Through `ContinuousBatcher`: a 101-token request admitted alone (the
    whole-prompt insert), one admitted beside it through `_fused_chunk` in four
    32-token chunks, and a re-ask that finds 96 cached tokens — four windows
    deep — and prefills its suffix over them.  Every served token is the
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
    for rid, prompt in ((a, asks[0]), (b, asks[1][::-1]), (c, asks[1])):
        assert _deficit(params, raw, prompt, out[rid]).max() < 1e-4, rid
    # counters: the routing counts as for the latent block, the kernel's step
    # counts by layer kind; all registered, all in stats()
    stats = cb.stats()
    from jax_llama_tpu.obs import metric_meta

    for name in ("attn_window_kv_steps_total", "attn_full_kv_steps_total",
                 "moe_assignments_total", "moe_experts_touched_total"):
        assert metric_meta(name)[0] == "counter" and name in stats
    assert stats["moe_layer_calls_total"] > 0
    if use_kernel:
        # four window layers to one full layer; at these sizes a row is one
        # grid step whatever the window
        assert stats["attn_window_kv_steps_total"] == 4 * stats["attn_full_kv_steps_total"] > 0
    else:
        assert stats["attn_window_kv_steps_total"] == stats["attn_full_kv_steps_total"] == 0
    # one fetch a chunk still: the counters ride the packed fetch
    assert stats["host_syncs_per_token"] < 1


# --- (b) each kernel against the XLA mask at the window's edge ---------------

def _edge_case(T=40, S=64, H=4, KVH=2, d=16, seed=0):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k, v = f(1, T, H, d), f(1, S, KVH, d), f(1, S, KVH, d)
    q_pos = jnp.arange(S - T, S, dtype=jnp.int32)[None]
    kv_pos = jnp.arange(S, dtype=jnp.int32)[None]
    return q, k, v, q_pos, kv_pos


def test_xla_mask_sees_the_query_and_the_window_minus_one_before_it():
    q_pos = jnp.asarray([[30]])
    kv_pos = jnp.arange(40)[None]
    bias = np.asarray(attention_bias(q_pos, kv_pos, kv_pos >= 0, window=jnp.int32(W)))[0, 0, 0]
    seen = np.flatnonzero(bias == 0.0)
    assert seen.min() == 30 - (W - 1) and seen.max() == 30 and len(seen) == W
    assert np.asarray(attention_bias(q_pos, kv_pos, kv_pos >= 0))[0, 0, 0, 0] == 0.0


@pytest.mark.parametrize("blocks", [(16, 16), (40, 64), (8, 32)], ids=str)
def test_flash_kernel_at_the_windows_edge(blocks):
    """interpret=True against the XLA mask; a key at distance W - 1 moves the
    output, a key at distance W does not."""
    q, k, v, q_pos, kv_pos = _edge_case()
    bq, bk = blocks
    run = lambda k_, v_: np.asarray(fa.flash_attention(  # noqa: E731
        q, k_, v_, q_pos, kv_pos, block_q=bq, block_k=bk, interpret=True,
        window=jnp.int32(W)))
    want = np.asarray(sdpa(q, k, v, attention_bias(q_pos, kv_pos, kv_pos >= 0, window=jnp.int32(W))))
    got = run(k, v)
    assert np.abs(got - want).max() < 2e-5
    last = int(q_pos[0, -1])                  # the last query's position
    inside, outside = last - (W - 1), last - W
    assert np.abs(run(k, v.at[0, inside].add(5.0))[0, -1] - got[0, -1]).max() > 1e-3
    assert np.abs(run(k, v.at[0, outside].add(5.0))[0, -1] - got[0, -1]).max() == 0.0


def _paged_case(seed=0, ctx=(70, 37, 5), KVH=2, G=4, d=16, MB=6):
    """A pool whose rows hold `ctx` consecutive positions; (q, k_new, v_new,
    pool_k, pool_v, pool_pos, table, q_pos) of one decode step."""
    rng = np.random.RandomState(seed)
    B, NB = len(ctx), len(ctx) * MB
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    pool_k, pool_v = f(1, KVH, NB, BLK, d), f(1, KVH, NB, BLK, d)
    pos = np.full((NB, BLK), -1, np.int32)
    table = np.full((B, MB), NB, np.int32)
    for b, n in enumerate(ctx):
        held = -(-n // BLK)                        # row b owns blocks b*MB ..
        table[b, :held] = b * MB + np.arange(held)
        pos[b * MB:(b + 1) * MB].reshape(-1)[:n] = np.arange(n)
    q, k_new, v_new = f(B, 1, KVH * G, d), f(B, 1, KVH, d), f(B, 1, KVH, d)
    return (q, k_new, v_new, pool_k, pool_v, jnp.asarray(pos), jnp.asarray(table),
            jnp.asarray(ctx, jnp.int32))


def _paged_dense(q, k_new, v_new, pool_k, pool_v, pos, table, q_pos, window):
    """The same step with a dense mask, row by row."""
    B, _, H, d = q.shape
    KVH = k_new.shape[2]
    out = []
    for b in range(B):
        ids = [int(t) for t in np.asarray(table[b]) if t < pos.shape[0]]
        k = jnp.concatenate([pool_k[0][:, ids].reshape(KVH, -1, d), k_new[b].swapaxes(0, 1)], 1)
        v = jnp.concatenate([pool_v[0][:, ids].reshape(KVH, -1, d), v_new[b].swapaxes(0, 1)], 1)
        kp = jnp.concatenate([pos[jnp.asarray(ids)].reshape(-1), q_pos[b:b + 1]])
        bias = attention_bias(q_pos[b:b + 1, None], kp[None], kp[None] >= 0,
                              window=None if window is None else jnp.int32(window))
        out.append(sdpa(q[b:b + 1], k.swapaxes(0, 1)[None], v.swapaxes(0, 1)[None], bias))
    return np.asarray(jnp.concatenate(out))


@pytest.mark.parametrize("window", [W, 16, 1, None], ids=lambda w: f"window-{w}")
def test_paged_kernel_at_the_windows_edge(window):
    """interpret=True against the dense mask over rows of 70, 37 and 5 cached
    tokens; a cached key at distance W - 1 moves the output, one at W does not."""
    case = _paged_case()
    q, k_new, v_new, pool_k, pool_v, pos, table, q_pos = case
    kw = {} if window is None else {"window": jnp.int32(window)}
    run = lambda pv: np.asarray(pa.paged_decode_attention(  # noqa: E731
        q, k_new, v_new, pool_k, pv, pos, table, q_pos, layer=jnp.int32(0),
        interpret=True, **kw))
    got = run(pool_v)
    assert np.abs(got - _paged_dense(*case, window)).max() < 2e-5
    if window is None or window < 2:
        return
    slot = lambda p: (int(table[0, p // BLK]), p % BLK)  # noqa: E731
    inside, outside = slot(70 - (window - 1)), slot(70 - window)
    bump = lambda s: pool_v.at[0, :, s[0], s[1]].add(5.0)  # noqa: E731
    assert np.abs(run(bump(inside))[0] - got[0]).max() > 1e-3
    assert np.abs(run(bump(outside))[0] - got[0]).max() == 0.0


# --- (c) the skip is real: no block wholly outside the window is visited -----

@pytest.mark.parametrize("q_first,T,S,bq,bk", [
    (200, 32, 256, 32, 16), (0, 32, 256, 32, 16), (100, 48, 160, 16, 32),
    (1000, 64, 1088, 64, 64),
])
def test_flash_sweep_visits_only_blocks_that_overlap_the_window(q_first, T, S, bq, bk):
    """`_window_bounds`' [start, bound) a q block, counted: every block in it
    holds a key some query of the block sees, no block outside it does, and
    the count is what the window and the chunk span, not the context."""
    q_pos = jnp.arange(q_first, q_first + T, dtype=jnp.int32)[None]
    kv = np.arange(S, dtype=np.int32)
    kv_pos = jnp.asarray(np.where(kv < q_first + T, kv, np.iinfo(np.int32).max))[None]
    start, bound = (np.asarray(a)[0] for a in fa._window_bounds(
        q_pos, kv_pos, T, bq, bk, jnp.int32(W)))
    for qi in range(T // bq):
        lo = q_first + qi * bq - (W - 1)          # first key the block's first query sees
        hi = q_first + (qi + 1) * bq - 1          # last key its last query sees
        want = [ki for ki in range(S // bk) if ki * bk <= hi and (ki + 1) * bk - 1 >= max(lo, 0)]
        assert list(range(start[qi], bound[qi])) == want, (qi, start, bound)
        assert bound[qi] - start[qi] <= -(-(bq + W - 1) // bk) + 1
    # without a window the same sweep starts at block 0
    full = -(-(q_first + T) // bk)
    assert full > bound[0] - start[0] or q_first < W


def test_paged_plan_lists_only_steps_inside_the_window():
    """`_fetch_plan` with one block a step: a window layer's live steps are
    the blocks that overlap [q - W + 1, q], a full layer's every block below
    q; `plan_live_steps` counts them."""
    *_, pos, table, q_pos = _paged_case(ctx=(70, 37, 5, 90), MB=6)
    q_pos = q_pos.at[3].set(-1)                   # an inactive row: no live step
    full = pa._fetch_plan(pos, table, q_pos, 1, 1)
    win = pa._fetch_plan(pos, table, q_pos, 1, 1, jnp.int32(W))
    MB = table.shape[1]
    for plan, lo_of in ((full, lambda q: 0), (win, lambda q: max(q - W + 1, 0))):
        n_steps, fetch, flags, src, kpos = (np.asarray(a) for a in plan)
        live = {}
        for t in range(int(n_steps)):
            if flags[t] & pa._LIVE:
                live.setdefault(int(src[t]) // MB, []).append(int(src[t]) % MB)
                assert fetch[t] >= 0
        for b, q in enumerate((70, 37, 5)):
            want = [j for j in range(MB) if j * BLK <= q - 1 and (j + 1) * BLK - 1 >= lo_of(q)]
            assert live.get(b, []) == want, (b, live)
        assert 3 not in live
        assert int(pa.plan_live_steps(plan)) == sum(map(len, live.values()))
    assert int(pa.plan_live_steps(win)) == 3 + 3 + 1      # ceil((W + BLK) / BLK) a row at most
    assert int(pa.plan_live_steps(full)) == 5 + 3 + 1


# --- (d) where the position enters ------------------------------------------

def test_positions_enter_through_rope_on_the_window_layers_only():
    """Shifting every position by a constant moves nothing (rope is relative,
    the masks depend on i - j).  Stretching the gaps between positions moves a
    model of full layers not at all — they carry no position, and order alone
    makes their mask — and moves a model with window layers."""
    toks, pos = _tokens(1, 20, seed=2)
    stretched = jnp.cumsum(jnp.asarray([[1, 2, 1, 3] * 5]), axis=1) - 1   # increasing, gaps 1-3
    for kinds, moves in ((["full_attention"] * 5, False), (None, True)):
        over = {} if kinds is None else {"layer_types": kinds, "global_attn_every_n_layers": 1}
        _, cfg, params = _build(sliding_window=64, **over)
        base = np.asarray(jlt.forward(params, toks, pos, cfg)[0])
        shifted = np.asarray(jlt.forward(params, toks, pos + 37, cfg)[0])
        assert np.abs(shifted - base).max() < 2e-5
        gaps = np.abs(np.asarray(jlt.forward(params, toks, stretched, cfg)[0]) - base).max()
        assert (gaps > 1e-3) if moves else (gaps < 2e-5), (kinds, gaps)


# --- (e) the published-key map ----------------------------------------------

def test_trinity_mini_file_maps_to_its_published_sizes():
    cfg = config_mod.from_published(_published(), max_seq_len=32768, attn_impl="auto")
    cfg.validate()
    assert (cfg.dim, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (2048, 32, 4, 128)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.vocab_size) == (5, 1, 200192)
    assert cfg.window_layers == (True, True, True, False, True) and cfg.sliding_window == 2048
    assert (cfg.n_routed_experts, cfg.n_experts_per_tok, cfg.n_shared_experts) == (128, 8, 1)
    assert (cfg.moe_intermediate_size, cfg.ffn_dim, cfg.routed_scaling_factor) == (1024, 6144, 2.826)
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.tie_word_embeddings) == (10000, 1e-5, False)
    assert (cfg.cache_heads, cfg.cache_width) == (4, 128)       # 2,048 B a token a layer in bf16
    shapes = jax.eval_shape(lambda: jlt.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(n - 4.2415e9) < 0.001e9   # 8.48 GB in bfloat16


def test_the_other_blocks_files_map_as_before():
    for name, latent in (("mistral-7b-v0.3", False), ("kanana-2-30b-a3b-instruct-2601", True)):
        raw = json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())
        cfg = config_mod.from_published(
            {k: v for k, v in raw.items() if k not in BOOKKEEPING}, max_seq_len=2048, attn_impl="auto")
        assert cfg.window_layers is None and cfg.sliding_window == 0 and cfg.head_size is None
        assert cfg.latent_attention is latent and not cfg.windowed_attention
        assert cfg.head_dim == raw["head_dim"]


def test_the_dense_block_honours_a_head_size_of_its_own():
    """`LLaMAConfig.head_size` is the dense block's too; a published dense
    FILE that says so is still refused (the benchmark's tests hold the map to
    that), as is one that gives no `head_dim` and does not divide."""
    cfg = jlt.get_config("tiny", dim=64, n_heads=8, n_kv_heads=2, head_size=16, n_layers=2)
    cfg.validate()
    assert cfg.head_dim == 16 and cfg.n_heads * cfg.head_dim == 128
    params = jlt.init_params(jax.random.PRNGKey(0), cfg)
    assert params["layers"]["qkv"].shape == (2, 2, 6, 64, 16)
    toks, pos = jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None]
    assert np.isfinite(np.asarray(jlt.forward(params, toks, pos, cfg)[0])).all()
    raw = json.loads((ROOT / "benchmark" / "configs" / "mistral-7b-v0.3.json").read_text())
    pub = {k: v for k, v in raw.items() if k not in BOOKKEEPING}
    with pytest.raises(ValueError, match="head_dim"):
        config_mod.from_published(dict(pub, head_dim=64), max_seq_len=64, attn_impl="xla")
    with pytest.raises(ValueError, match="head_dim"):
        pub2 = {k: v for k, v in pub.items() if k != "head_dim"}
        config_mod.from_published(dict(pub2, num_attention_heads=33), max_seq_len=64, attn_impl="xla")


@pytest.mark.parametrize("key,value,named", [
    ("index_topk", 16, "index_topk"),                 # a key no block knows
    ("model_type", "deepseek_v3", "model_type"),      # known keys at a value the
    ("hidden_act", "gelu", "hidden_act"),             # block does not compute
    ("score_func", "softmax", "score_func"),
    ("route_norm", False, "route_norm"),
    ("mup_enabled", False, "mup_enabled"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("n_group", 8, "n_group"),
    ("topk_group", 4, "topk_group"),
    ("num_expert_groups", 2, "num_expert_groups"),
    ("num_limited_groups", 2, "num_limited_groups"),
    ("global_attn_every_n_layers", 2, "global_attn_every_n_layers"),
    ("sliding_window", None, "sliding_window"),
    ("sliding_window", 0, "sliding_window"),
    ("layer_types", ["sliding_attention"] * 4, "layer_types"),          # not num_hidden_layers
    ("layer_types", ["sliding_attention"] * 4 + ["chunked_attention"], "layer_types"),
    ("head_dim", 127, "head_dim"),
    ("kv_lora_rank", 512, "kv_lora_rank"),
    ("torch_dtype", "float16", "torch_dtype"),
])
def test_a_changed_or_unknown_key_is_refused_by_name(key, value, named):
    with pytest.raises(ValueError, match=named):
        config_mod.from_published(_published(**{key: value}), max_seq_len=256, attn_impl="auto")


@pytest.mark.parametrize("key", ["load_balance_coeff", "use_grouped_mm"])
def test_training_and_implementation_switches_are_accepted_unused(key):
    a = config_mod.from_published(_published(), max_seq_len=256, attn_impl="auto")
    b = config_mod.from_published(_published(**{key: 0}), max_seq_len=256, attn_impl="auto")
    assert a == b


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("num_experts", 8), ("layer_types", ["full_attention"] * 24)])
def test_a_window_key_on_its_own_is_not_the_dense_block(key, value):
    raw = json.loads((ROOT / "benchmark" / "configs" / "mistral-7b-v0.3.json").read_text())
    pub = {k: v for k, v in raw.items() if k not in BOOKKEEPING}
    with pytest.raises(ValueError):
        config_mod.from_published(dict(pub, **{key: value}), max_seq_len=256, attn_impl="auto")


# --- (f) what the block does not get yet is refused by name ------------------

def _refuse_tensor(cfg, params):
    from jax_llama_tpu.parallel.mesh import make_mesh
    from jax_llama_tpu.parallel.partition import validate_tp

    validate_tp(cfg, make_mesh(data=1, fsdp=1, tensor=2, devices=jax.devices()[:2]))


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


def _refuse_a_window_of_no_length(cfg, params):
    cfg.replace(sliding_window=0).validate()


@pytest.mark.parametrize("attempt,named", [
    (_refuse_tensor, "one chip"), (_refuse_int8_kv, "int8"), (_refuse_ring, "ring"),
    (_refuse_quantize, "quantize"), (_refuse_speculation, "speculative"),
    (_refuse_serve_mesh, "serve-mesh"), (_refuse_train, "training step"),
    (_refuse_a_window_of_no_length, "sliding_window > 0"),
], ids=["tensor", "int8-kv", "ring", "quantize", "speculation", "serve-mesh", "train", "two-windows"])
def test_unsupported_combination_is_refused_by_name(tiny, attempt, named):
    _, cfg, params = tiny
    with pytest.raises((ValueError, NotImplementedError), match=named):
        attempt(cfg, params)


# --- tracing, sharding rules, the cache ---------------------------------------

def test_scopes_are_in_the_lowered_programs(tiny):
    """The named scopes a device trace is read by, in the program text: each
    attention kind under its own, the experts under the latent block's."""
    _, cfg, params = tiny
    toks, pos = _tokens(1, 16)
    text = jax.jit(lambda p, t, q: jlt.forward(p, t, q, cfg)[0]).lower(
        params, toks, pos).as_text(debug_info=True)
    for scope in ("attn.window", "attn.full", "moe.route", "moe.experts", "moe.shared", "dense.ffn"):
        assert scope in text, scope
    pool = serving.init_pool(cfg, 8, BLK)
    cache = serving._pool_as_cache(pool, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32))
    text = jax.jit(lambda p, t, q, c: jlt.forward(p, t, q, cfg, cache=c)[0]).lower(
        params, toks[:, :1], pos[:, :1], cache).as_text(debug_info=True)
    assert "attn.window" in text and "attn.full" in text


def test_every_parameter_has_a_partition_rule(tiny):
    _, cfg, params = tiny
    from jax_llama_tpu.parallel.mesh import make_mesh
    from jax_llama_tpu.parallel.partition import shard_abstract

    mesh = make_mesh(data=1, fsdp=1, tensor=1, devices=jax.devices()[:1])
    shapes = jax.eval_shape(lambda: params)
    placed = shard_abstract(shapes, mesh, cfg)
    assert jax.tree.structure(placed) == jax.tree.structure(shapes)


def test_the_pool_is_one_plane_set_for_every_layer(tiny):
    """K and V of every layer, window or full: [L, KVH, NB, BLK, hd] twice, as
    the dense block's; the counters behind the routing counts."""
    _, cfg, _ = tiny
    pool = serving.init_pool(cfg, 8, BLK)
    assert pool.k.shape == pool.v.shape == (5, 2, 8, BLK, 16)
    assert pool.k_scale is None and pool.stats.shape == (afmoe.N_STATS,) == (6,)
    cache = jlt.init_cache(cfg, batch=2, max_len=32)
    assert cache.k.shape == cache.v.shape == (5, 2, 32, 2, 16) and cache.stats.shape == (6,)
    dense = jlt.get_config("tiny")
    assert serving.init_pool(dense, 8, BLK).stats is None and jlt.init_cache(dense, 1).stats is None
