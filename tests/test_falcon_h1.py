"""The block with a mixer beside attention in every layer (models/falcon_h1.py,
`ops/ssm.py`'s `ssd_step` / `ssd_scan`) against its plain reference,
`benchmark/references/falcon_h1.py`, loaded by path: one reference, the one the
benchmark's `correct` uses.

Tiny widths, seeded float32 weights, CPU: two layers, 4 query heads over 2 KV
heads of 16, a mixer of 4 heads of 16 with state 16 in 2 groups, scan chunks of
16 over blocks of 16 and prompts of ~100 (no multiple of either), so a state
that advances on a masked token, a snapshot one block off, a multiplier in the
wrong place or a missing branch fails the float32 tolerances.
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
from jax_llama_tpu.models import falcon_h1
from jax_llama_tpu.ops import ssm

ROOT = Path(__file__).resolve().parent.parent
CONFIGS = ROOT / "benchmark" / "configs"
CONFIG_FILE = CONFIGS / "Falcon-H1-34B-Instruct.json"
BOOKKEEPING = ("source", "architecture", "reference", "reduced", "assumed", "deployment")
BLK = 16
TINY = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=2, vocab_size=512,
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
    mamba_n_groups=2, mamba_chunk_size=16, torch_dtype="float32",
)
# float32 on the CPU: the program and the reference differ by the order of
# their sums only (a chunked scan, a joint softmax over cache and step)
TOL = 1e-4
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
    "attention_in_multiplier", "attention_out_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers.0", "ssm_multipliers.1",
    "ssm_multipliers.2", "ssm_multipliers.3", "ssm_multipliers.4",
    "mlp_multipliers.0", "mlp_multipliers.1",
)


def _reference():
    path = ROOT / "benchmark" / "references" / "falcon_h1.py"
    spec = importlib.util.spec_from_file_location("reference_falcon_h1", path)
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
    return _build()


def _tokens(b, t, seed=0):
    toks = np.random.RandomState(seed).randint(0, TINY["vocab_size"], size=(b, t))
    return jnp.asarray(toks), jnp.tile(jnp.arange(t)[None], (b, 1))


def _deficit(params, raw, prompt, served):
    full = jnp.asarray([list(prompt) + list(served)])
    ref = np.asarray(_reference().logits(params, full, raw, len(prompt) - 1))[0, :len(served)]
    return ref.max(axis=1) - ref[np.arange(len(served)), served]


# --- (1), (2) the served paths against the reference -------------------------

@pytest.mark.parametrize("attn", ["auto", "xla"])
def test_forward_matches_the_plain_reference(tiny, attn):
    raw, cfg, params = tiny
    toks, pos = _tokens(2, 100)
    mine = np.asarray(jlt.forward(params, toks, pos, cfg.replace(attn_impl=attn))[0])
    ref = np.asarray(_reference().logits(params, toks, raw, 0))
    assert np.abs(ref).max() > 2.0      # the head's 0.0078 flattens nothing
    assert np.abs(mine - ref).max() < TOL * np.abs(ref).max()


def test_prefill_in_chunks_then_decode_through_the_paged_cache_logits(tiny):
    """A 96-token prompt through `_paged_insert` in three 32-token chunks (the
    state handed from chunk to chunk), then eight given tokens through
    `forward` over the pool as a paged cache and the per-slot state: each
    step's LOGITS are the reference's full forward pass's."""
    raw, cfg, params = tiny
    NB, P, G = 16, 96, 8
    toks, _ = _tokens(1, P + G, seed=1)
    pool = serving.init_pool(cfg, NB, BLK, n_slots=1)
    ids = jnp.arange(P // BLK, dtype=jnp.int32)[None]
    f32, i32 = jnp.float32, jnp.int32
    one = lambda v, dt: jnp.full((1,), v, dt)  # noqa: E731
    *_, pool = serving._paged_insert(
        params, pool, ids, toks[:, :P], jnp.ones((1, P), bool), jnp.zeros((1, 2), jnp.uint32),
        one(0.0, f32), one(1.0, f32), one(0, i32), one(0, i32), config=cfg,
        prefill_chunk=32)
    assert float(jnp.abs(pool.ssm).max()) > 0 and float(jnp.abs(pool.conv).max()) > 0
    table = jnp.full((1, 8), NB, i32).at[0, :7].set(jnp.arange(7))
    step = jax.jit(lambda pool, tok, at: jlt.forward(
        params, tok, at, cfg, cache=serving._pool_as_cache(pool, table, at[:, 0])))
    got = []
    for i in range(P, P + G):
        lg, cache = step(pool, toks[:, i:i + 1], jnp.full((1, 1), i, i32))
        pool = serving._cache_into_pool(pool, cache)
        got.append(np.asarray(lg[0, 0]))
    ref = np.asarray(_reference().logits(params, toks, raw, P))[0]
    assert np.abs(np.stack(got) - ref).max() < TOL * np.abs(ref).max()
    # full-attention steps only, one list a layer
    steps = np.asarray(pool.stats)[-2:]
    assert steps[0] == 0 and steps[1] > 0 and steps[1] % cfg.n_layers == 0


def test_decode_through_the_gathered_view_reads_the_references_tokens(tiny):
    raw, cfg, params = tiny
    NB, P, G = 16, 96, 6
    toks, _ = _tokens(1, P, seed=2)
    pool = serving.init_pool(cfg, NB, BLK, n_slots=1)
    ids = jnp.arange(P // BLK, dtype=jnp.int32)[None]
    keys = jnp.zeros((1, 2), jnp.uint32)
    f32, i32 = jnp.float32, jnp.int32
    one = lambda v, dt: jnp.full((1,), v, dt)  # noqa: E731
    tau, _, _, keys, pool = serving._paged_insert(
        params, pool, ids, toks, jnp.ones((1, P), bool), keys,
        one(0.0, f32), one(1.0, f32), one(0, i32), one(0, i32), config=cfg,
        prefill_chunk=32)
    table = jnp.full((1, 8), NB, i32).at[0, :7].set(jnp.arange(7))
    served, _, _ = decode_row(
        params, cfg, pool, table, 7, P, int(tau[0]), G - 1, use_kernel=False)
    assert _deficit(params, raw, [int(t) for t in toks[0]], served).max() < TOL


# --- (3) the scan in its forms ------------------------------------------------

def _scan_case(B=2, T=32, Hm=4, P=8, N=16, G=2, seed=0):
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s).astype(np.float32))  # noqa: E731
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(3e-1), (B, T, Hm))).astype(np.float32))
    A = -jnp.asarray(r.uniform(1.0, 16.0, (Hm,)).astype(np.float32))
    return f(B, Hm, P, N), f(B, T, Hm, P), dt, f(B, T, G, N), f(B, T, G, N), A


@pytest.mark.parametrize("T", [32, 37])
def test_scan_is_steps_is_two_half_chunks(T):
    """`ssd_scan` over T tokens == T x `ssd_step` == the chunk split in two
    with the state handed over, at a T that is no multiple of the scan's
    chunk of 16 too."""
    h0, x, dt, Bm, Cm, A = _scan_case(T=T)
    B = x.shape[0]
    full = jnp.full((B,), T, jnp.int32)
    y, hT = ssm.ssd_scan(h0, x, dt, Bm, Cm, A, full, chunk=16)
    h, ys = h0, []
    for t in range(T):
        y_t, h = ssm.ssd_step(h, x[:, t], dt[:, t], Bm[:, t], Cm[:, t], A, jnp.ones((B,), bool))
        ys.append(y_t)
    assert np.abs(np.asarray(y) - np.stack(ys, 1)).max() < 2e-5
    assert np.abs(np.asarray(hT) - np.asarray(h)).max() < 2e-5
    cut = T // 2
    y1, h1 = ssm.ssd_scan(h0, *(a[:, :cut] for a in (x, dt, Bm, Cm)), A,
                          jnp.full((B,), cut, jnp.int32), chunk=16)
    y2, h2 = ssm.ssd_scan(h1, *(a[:, cut:] for a in (x, dt, Bm, Cm)), A,
                          jnp.full((B,), T - cut, jnp.int32), chunk=16)
    assert np.abs(np.asarray(jnp.concatenate([y1, y2], 1)) - np.asarray(y)).max() < 2e-5
    assert np.abs(np.asarray(h2) - np.asarray(hT)).max() < 2e-5


def test_scan_stops_at_a_rows_live_length_and_a_dead_step_keeps_the_state():
    """Tokens past `lengths[b]` update nothing: the state that leaves is the
    state after the row's last live token; a row with none keeps its own, bit
    for bit, from the scan and from a step that is not live."""
    h0, x, dt, Bm, Cm, A = _scan_case(B=3)
    lengths = jnp.asarray([32, 11, 0], jnp.int32)
    y, hT = ssm.ssd_scan(h0, x, dt, Bm, Cm, A, lengths, chunk=16)
    y11, h11 = ssm.ssd_scan(h0[1:2], x[1:2, :11], dt[1:2, :11], Bm[1:2, :11], Cm[1:2, :11],
                            A, jnp.asarray([11], jnp.int32), chunk=16)
    assert np.abs(np.asarray(hT[1]) - np.asarray(h11[0])).max() < 2e-5
    assert np.abs(np.asarray(y[1, :11]) - np.asarray(y11[0])).max() < 2e-5
    assert np.array_equal(np.asarray(hT[2]), np.asarray(h0[2]))
    _, h = ssm.ssd_step(h0, x[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A,
                        jnp.asarray([True, False, False]))
    assert np.array_equal(np.asarray(h[1:]), np.asarray(h0[1:]))
    assert not np.array_equal(np.asarray(h[0]), np.asarray(h0[0]))


# --- (4) masked iterations and idle rows --------------------------------------

@pytest.mark.parametrize("use_kernel", [True, False], ids=["paged-kernel", "gathered-view"])
def test_a_masked_iteration_and_an_idle_row_leave_the_state_bit_for_bit(tiny, use_kernel):
    """`_paged_decode_chunk` of 4 iterations over three slots: one decodes all
    four, one has a budget of 2 (its last two iterations run masked), one is
    idle.  The idle slot's state is untouched; the short row's state is what
    two single iterations leave; both bit for bit."""
    _, cfg, params = tiny
    NB, MB, B = 24, 8, 3
    i32, f32 = jnp.int32, jnp.float32
    rng = np.random.RandomState(7)
    pool = serving.init_pool(cfg, NB, BLK, n_slots=B)
    pool = dataclasses.replace(
        pool, conv=jnp.asarray(rng.randn(*pool.conv.shape).astype(np.float32)),
        ssm=jnp.asarray(rng.randn(*pool.ssm.shape).astype(np.float32)))
    table = jnp.arange(B * MB, dtype=i32).reshape(B, MB)
    args = lambda remaining, active: dict(  # noqa: E731
        table=table, n_alloc=jnp.full((B,), MB, i32), fill=jnp.zeros((B,), i32),
        tau=jnp.asarray([5, 9, 11], i32), tau_lp=jnp.zeros((B,), f32),
        pos=jnp.zeros((B,), i32), active=jnp.asarray(active),
        remaining=jnp.asarray(remaining, i32), stops=jnp.full((B, 1), -1, i32),
        keys=jnp.zeros((B, 2), jnp.uint32), temperature=jnp.zeros((B,), f32),
        top_p=jnp.ones((B,), f32), top_k=jnp.zeros((B,), i32))
    copy = lambda p: jax.tree.map(jnp.array, p)  # noqa: E731
    run = lambda p, n, **kw: serving._paged_decode_chunk(  # noqa: E731
        params, copy(p), **kw, config=cfg, n_iter=n, all_greedy=True,
        allow_kernel=use_kernel)[-1]
    four = run(pool, 4, **args([9, 3, 0], [True, True, False]))
    two = run(pool, 2, **args([9, 9, 0], [True, True, False]))
    for name in ("conv", "ssm"):
        before, after, short = (np.asarray(getattr(p, name)) for p in (pool, four, two))
        assert np.array_equal(after[:, 2], before[:, 2]), name          # the idle slot
        assert np.array_equal(after[:, 1], short[:, 1]), name           # the masked tail
        assert not np.array_equal(after[:, 0], short[:, 0]), name       # the live row went on
        assert not np.array_equal(after[:, 1], before[:, 1]), name


# --- (5) snapshots under the radix store --------------------------------------

def test_a_reask_restores_a_snapshot_and_an_evicted_one_shortens_the_match(tiny):
    """Through `ContinuousBatcher`, chunks of 32 over blocks of 16: a holder
    admitted alone (the whole-prompt insert, which takes no snapshot), a
    105-token request beside it through `_fused_chunk` (snapshots at 32, 64,
    96), a re-ask that restores the one at 96 though 6 blocks are cached; with
    the deepest snapshot evicted the match ends at 64 and the tokens between
    are counted as cut.  Every served token is the reference's own argmax."""
    raw, cfg, params = tiny
    rng = np.random.RandomState(4)
    draw = lambda n: [int(t) for t in rng.randint(0, 512, size=n)]  # noqa: E731
    doc = draw(100)
    asks = [doc + draw(n) for n in (5, 9, 3)]
    # The XLA forms throughout (the kernels have their tests above): the
    # scheduler, the snapshots and the state's hand-over are what is driven.
    cb = jlt.ContinuousBatcher(
        params, cfg.replace(attn_impl="xla"), n_slots=3, block_size=BLK, decode_chunk=2,
        prefill_budget=32, use_pallas_kernel=False)
    # eight a slot; the K/V pool's bytes would hold more of them at this size
    assert cb.n_snapshots == 24 and cb.pool.snap_ssm.shape == (2, 24, 4, 16, 16)
    out = {}

    def steps(n):
        for _ in range(n):
            for rid, tok, *_ in cb.step():
                out.setdefault(rid, []).append(tok)

    def drain():
        while cb.pending():
            steps(1)

    taken = lambda: cb.stats()["ssm_snapshots_taken_total"]  # noqa: E731
    cb.submit(draw(40), max_new_tokens=40)          # a holder keeps a row decoding
    steps(3)
    before = taken()                                # the insert took none
    a = cb.submit(asks[0], max_new_tokens=6)        # snapshots at 32, 64, 96
    steps(6)
    assert (before, taken()) == (0, 3)
    b = cb.submit(asks[1], max_new_tokens=6)        # a re-ask beside it: restores 96
    drain()
    assert cb.prefix_hit_tokens_total == 96 and cb.stats()["ssm_snapshots_restored_total"] == 1
    assert cb.obs.timeline_json(b)["kv"]["prefix_hit_tokens"] == 96
    # evict the snapshot at 96 (its node keeps its block): the match ends at 64
    node = cb._store._by_key[cb._chain_keys(asks[2], BLK)[5]]
    cb._store._drop_snapshot(node)
    d = cb.submit(asks[2], max_new_tokens=6)
    drain()
    stats = cb.stats()
    assert cb.obs.timeline_json(d)["kv"]["prefix_hit_tokens"] == 64
    assert stats["ssm_match_tokens_cut_total"] == 32 and stats["ssm_snapshots_in_use"] >= 3
    assert {r["kind"] for r in cb.obs.dispatches} == {"insert", "fused", "decode"}
    for rid, prompt in ((a, asks[0]), (b, asks[1]), (d, asks[2])):
        assert _deficit(params, raw, prompt, out[rid]).max() < TOL, rid
    # the gauges that turn counts into bytes
    from jax_llama_tpu.obs import metric_meta

    per_slot = 2 * (3 * 128 * 4 + 4 * 16 * 16 * 4)
    for where in (stats, cb.describe()):
        assert where["ssm_state_bytes_per_slot"] == per_slot == cfg.state_bytes_per_row
        assert where["ssm_snapshot_bytes"] == 24 * per_slot
    assert metric_meta("ssm_state_bytes_per_slot")[0] == "gauge"
    assert metric_meta("ssm_snapshot_bytes")[0] == "gauge"
    assert stats["host_syncs_per_token"] < 1


def test_the_snapshot_rule_follows_from_bytes():
    """Eight a slot, but no more bytes than the K/V pool: 192 for the
    per-channel state's cell as ever, 63 for this block's at 32 rows x 4096
    (47 under the issue's retreat to 24 rows), nothing for a dense block."""
    build = lambda name: config_mod.from_published(  # noqa: E731
        {k: v for k, v in json.loads((CONFIGS / f"{name}.json").read_text()).items()
         if k not in BOOKKEEPING}, max_seq_len=4096, attn_impl="auto")
    phi, mine = build("Phi-4-mini-flash-reasoning"), build("Falcon-H1-34B-Instruct")
    assert serving.snapshot_pool_size(phi, 24, 768, 128) == 192
    assert phi.state_bytes_per_row == 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert mine.state_bytes_per_row == 25_350_144
    kv = 1024 * 128 * 12 * 1024
    assert serving.snapshot_pool_size(mine, 32, 1024, 128) == 63 == kv // 25_350_144
    assert 63 * mine.state_bytes_per_row <= kv < 64 * mine.state_bytes_per_row
    assert serving.snapshot_pool_size(mine, 24, 768, 128) == 47
    assert build("mistral-7b-v0.3").state_bytes_per_row == 0


# --- (6) every multiplied path, the rope and both branches carry weight -------

def _without(cfg, what, monkeypatch):
    """The program's configuration with one thing wrong."""
    if what == "rope":
        monkeypatch.setattr(falcon_h1, "apply_rope_rows", lambda x, cos, sin: x)
        return cfg
    if what in ("mixer", "attention"):
        return cfg.replace(**{
            "ssm_out_multiplier" if what == "mixer" else "attention_out_multiplier": 0.0})
    name, _, i = what.partition(".")
    value = getattr(cfg, name)
    if i:
        value = tuple(2.0 * v if j == int(i) else v for j, v in enumerate(value))
    return cfg.replace(**{name: value if i else 2.0 * value})


@pytest.fixture(scope="module")
def reference_logits(tiny):
    """The reference's logits of a short prompt, which the sound program
    reads within the tolerance (the layer stack unrolled, as the cases run
    it: eager, so that a case compiles nothing but its scan)."""
    raw, cfg, params = tiny
    toks, pos = _tokens(1, 24, seed=5)
    ref = np.asarray(_reference().logits(params, toks, raw, 0))
    sound = np.asarray(jlt.forward(params, toks, pos, cfg.replace(scan_layers=False))[0])
    assert np.abs(sound - ref).max() < TOL * np.abs(ref).max()
    return ref


@pytest.mark.parametrize("what", MULTIPLIERS + ("rope", "mixer", "attention"))
def test_one_thing_wrong_fails_the_comparison(tiny, reference_logits, what, monkeypatch):
    """Each of the fourteen multipliers doubled (`attention_in_multiplier` is
    1 as published, so "set to 1" would test nothing), the rope dropped, and
    the mixer or the attention branch left out: the program's logits leave the
    reference's by far more than the comparison's tolerance."""
    _, cfg, params = tiny
    toks, pos = _tokens(1, 24, seed=5)
    ref = reference_logits
    wrong = _without(cfg.replace(scan_layers=False), what, monkeypatch)
    got = np.asarray(jlt.forward(params, toks, pos, wrong)[0])
    assert np.abs(got - ref).max() > 100 * TOL * np.abs(ref).max(), what


# --- (7) the published keys ---------------------------------------------------

def test_the_file_maps_to_its_published_sizes():
    cfg = config_mod.from_published(_published(), max_seq_len=4096, attn_impl="auto")
    cfg.validate()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (5120, 6, 20, 4, 128)
    assert (cfg.ffn_dim, cfg.vocab_size, cfg.rope_theta) == (21504, 261120, 1e11)
    assert (cfg.mamba_d_ssm, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_chunk_size, cfg.mamba_conv_dim) == (
                4096, 32, 128, 256, 2, 128, 5120)
    assert (cfg.cache_layers, cfg.state_layers, cfg.cache_heads, cfg.cache_width) == (6, 6, 4, 128)
    assert cfg.layer_kinds == ("mixer+full",) * 6 and cfg.recurrent_state and cfg.parallel_mixer
    assert cfg.state_shapes == (("conv", (15360,), "bfloat16"), ("ssm", (32, 128, 256), "float32"))
    assert (cfg.lm_head_multiplier, cfg.key_multiplier, cfg.attention_in_multiplier) == (
        0.0078125, 0.011048543456039804, 1)
    assert len(cfg.ssm_multipliers) == 5 and cfg.mlp_multipliers == (0.1767766952966369, 0.011160714285714284)
    assert not cfg.tie_word_embeddings and cfg.dtype == "bfloat16"
    shapes = jax.eval_shape(lambda: jlt.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 5_254_594_112           # 10.509 GB in bfloat16
    layer = sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(shapes["layers"]))
    assert round(layer / 1e6, 2) == 430.12


@pytest.mark.parametrize("key,value,named", [
    ("model_type", "falcon_mamba", "model_type"), ("hidden_act", "gelu", "hidden_act"),
    ("attention_bias", True, "attention_bias"), ("attn_layer_indices", [0, 2], "attn_layer_indices"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"), ("num_logits_to_keep", 0, "num_logits_to_keep"),
    ("mamba_conv_bias", False, "mamba_conv_bias"), ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("mamba_rms_norm", False, "mamba_rms_norm"), ("mamba_norm_before_gate", True, "mamba_norm_before_gate"),
    ("mamba_use_mlp", False, "mamba_use_mlp"), ("projectors_bias", True, "projectors_bias"),
    ("mlp_bias", True, "mlp_bias"), ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("mamba_expand", 4, "mamba_expand"), ("mlp_expansion_factor", 4, "mlp_expansion_factor"),
    ("mamba_d_conv", 8, "mamba_d_conv"), ("ssm_multipliers", [1.0, 1.0], "ssm_multipliers"),
    ("mlp_multipliers", 0.5, "mlp_multipliers"), ("key_multiplier", "small", "key_multiplier"),
    ("mamba_n_heads", 30, "mamba_d_ssm"), ("mamba_n_groups", 3, "mamba_n_groups"),
    ("num_key_value_heads", 3, "num_key_value_heads"), ("head_dim", 127, "head_dim"),
    ("torch_dtype", "float16", "torch_dtype"), ("num_experts", 8, "num_experts"),
    ("sliding_window", 512, "sliding_window"), ("mb_per_layer", 2, "two blocks"),
    ("kv_lora_rank", 512, "two blocks"),
], ids=lambda v: str(v)[:24])
def test_a_changed_or_unknown_key_is_refused_by_name(key, value, named):
    with pytest.raises(ValueError, match=named):
        config_mod.from_published(
            _published(**{key: value}), max_seq_len=4096, attn_impl="auto").validate()


@pytest.mark.parametrize("key", ["mamba_d_state", "lm_head_multiplier", "mamba_rms_norm",
                                 "rope_theta", "torch_dtype", "ssm_multipliers"])
def test_a_missing_key_is_refused_by_name(key):
    raw = _published()
    del raw[key]
    with pytest.raises(ValueError, match=key):
        config_mod.from_published(raw, max_seq_len=4096, attn_impl="auto")


# What `from_published` made of the four older files on the parent commit:
# every field that is not the dataclass's default.
_AS_BEFORE = {
    "mistral-7b-v0.3": dict(
        vocab_size=32768, n_layers=24, n_kv_heads=8, intermediate_size=14336,
        max_seq_len=4096, rope_theta=1000000.0, param_dtype="bfloat16", attn_impl="auto"),
    "kanana-2-30b-a3b-instruct-2601": dict(
        vocab_size=128256, dim=2048, n_layers=8, n_kv_heads=32, intermediate_size=6144,
        max_seq_len=4096, rms_norm_eps=1e-06, rope_theta=1000000, param_dtype="bfloat16",
        attn_impl="auto", kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, n_routed_experts=128, n_experts_per_tok=6, n_shared_experts=2,
        moe_intermediate_size=768, routed_scaling_factor=2.448, first_k_dense=1),
    "Trinity-Mini": dict(
        vocab_size=200192, dim=2048, n_layers=5, n_kv_heads=4, head_size=128,
        intermediate_size=6144, max_seq_len=4096, param_dtype="bfloat16", attn_impl="auto",
        n_routed_experts=128, n_experts_per_tok=8, n_shared_experts=1,
        moe_intermediate_size=1024, routed_scaling_factor=2.826, first_k_dense=1,
        window_layers=(True, True, True, False, True), sliding_window=2048),
    "Phi-4-mini-flash-reasoning": dict(
        vocab_size=200064, dim=2560, n_heads=40, n_kv_heads=20, intermediate_size=10240,
        max_seq_len=4096, tie_word_embeddings=True, param_dtype="bfloat16", attn_impl="auto",
        sliding_window=512, mb_per_layer=2),
}


@pytest.mark.parametrize("name", sorted(_AS_BEFORE))
def test_the_other_four_files_map_exactly_as_before(name):
    raw = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg = config_mod.from_published(
        {k: v for k, v in raw.items() if k not in BOOKKEEPING},
        max_seq_len=4096, attn_impl="auto")
    assert cfg == config_mod.LLaMAConfig(**_AS_BEFORE[name])
    assert not cfg.parallel_mixer
    recurrent = name.startswith("Phi")
    assert cfg.recurrent_state == recurrent
    assert cfg.cache_layers == (9 if recurrent else cfg.n_layers)
    assert cfg.state_shapes == ((("conv", (15360,), "bfloat16"), ("ssm", (16, 5120), "float32"))
                                if recurrent else ())
    assert (cfg.cache_heads, cfg.cache_width) == {
        "mistral-7b-v0.3": (8, 128), "kanana-2-30b-a3b-instruct-2601": (1, 640),
        "Trinity-Mini": (4, 128), "Phi-4-mini-flash-reasoning": (10, 128)}[name]


# --- (8) refusals by name -----------------------------------------------------

def _refuse_tensor(cfg, params):
    from jax_llama_tpu.parallel.mesh import make_mesh
    from jax_llama_tpu.parallel.partition import validate_tp

    validate_tp(cfg, make_mesh(data=1, fsdp=1, tensor=2, devices=jax.devices()[:2]))


def _refuse_quantize(cfg, params):
    from jax_llama_tpu.ops.quant import quantize_params

    dense = jlt.get_config("tiny")
    q = quantize_params(jlt.init_params(jax.random.PRNGKey(0), dense))
    jlt.ContinuousBatcher(dict(params, lm_head=q["lm_head"]), cfg, n_slots=1)


def _refuse_serve_mesh(cfg, params):
    from jax_llama_tpu.parallel.serve_mesh import ServeMeshSpec, build_serve_mesh

    mesh = build_serve_mesh(ServeMeshSpec(data=1, tensor=2), devices=jax.devices()[:2])
    jlt.ContinuousBatcher(params, cfg, n_slots=2, mesh=mesh)


def _refuse_train(cfg, params):
    from jax_llama_tpu.train import init_train_state, make_optimizer, train_step

    opt = make_optimizer()
    train_step(init_train_state(params, opt), jnp.zeros((1, 8), jnp.int32), cfg, opt)


@pytest.mark.parametrize("attempt,named", [
    (_refuse_tensor, "parallel mixer and attention layers runs on one chip"),
    (lambda cfg, p: cfg.replace(kv_cache_dtype="int8").validate(), "int8"),
    (lambda cfg, p: cfg.replace(attn_impl="ring").validate(), "ring"),
    (_refuse_quantize, "quantize"),
    (lambda cfg, p: jlt.ContinuousBatcher(p, cfg, n_slots=1, draft_params=p, draft_config=cfg),
     "speculative"),
    (_refuse_serve_mesh, "serve-mesh"), (_refuse_train, "training step"),
    (lambda cfg, p: jlt.ContinuousBatcher(p, cfg, n_slots=1, host_kv_blocks=4), "host tier"),
    (lambda cfg, p: cfg.replace(tie_word_embeddings=True).validate(), "untied"),
    (lambda cfg, p: cfg.replace(mb_per_layer=2).validate(), "two blocks"),
    (lambda cfg, p: cfg.replace(mamba_n_groups=3).validate(), "mamba_n_groups"),
    (lambda cfg, p: cfg.replace(ssm_multipliers=(1.0,)).validate(), "ssm_multipliers"),
    (lambda cfg, p: cfg.replace(use_scaled_rope=True).validate(), "use_scaled_rope"),
    (lambda cfg, p: serving.init_pool(cfg, 8, BLK), "n_slots"),
    (lambda cfg, p: jlt.forward(p, jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None], cfg,
                                dropout_rng=jax.random.PRNGKey(0)), "served, not trained"),
], ids=["tensor", "int8-kv", "ring", "quantize", "speculation", "serve-mesh", "train",
        "host-tier", "tied", "two-blocks", "groups", "zones", "scaled-rope",
        "pool-without-slots", "dropout"])
def test_unsupported_combination_is_refused_by_name(tiny, attempt, named):
    _, cfg, params = tiny
    with pytest.raises((ValueError, NotImplementedError), match=named):
        attempt(cfg, params)


# --- tracing, sharding rules, the cache ---------------------------------------

def test_scopes_are_in_the_lowered_programs(tiny):
    _, cfg, params = tiny
    toks, pos = _tokens(1, 24)
    text = jax.jit(lambda p, t, q: jlt.forward(p, t, q, cfg)[0]).lower(
        params, toks, pos).as_text(debug_info=True)
    named = lambda text, scope: f'"{scope}/' in text or f"/{scope}/" in text  # noqa: E731
    for scope in ("ssm.mix", "ssm.scan", "attn.full", "dense.ffn", "head"):
        assert named(text, scope), scope
    assert named(text, "ssm.mix/ssm.scan") and "ssm.step" not in text
    pool = serving.init_pool(cfg, 8, BLK, n_slots=1)
    cache = serving._pool_as_cache(pool, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32))
    text = jax.jit(lambda p, t, q, c: jlt.forward(p, t, q, cfg, cache=c)[0]).lower(
        params, toks[:, :1], pos[:, :1], cache).as_text(debug_info=True)
    assert named(text, "ssm.mix/ssm.step") and "ssm.scan" not in text
    assert named(text, "attn.full") and named(text, "head")


def test_the_mixed_pass_keeps_the_scan_and_the_step_under_their_scopes(tiny):
    """`mixed_forward` (a prompt chunk and one token a decode row in one pass
    over the weights): the chunk's scan under `ssm.mix/ssm.scan` and the
    riders' step under `ssm.mix/ssm.step`, as the benchmark's readers find
    them, and one attention and one FFN scope for both halves."""
    from jax_llama_tpu.models import llama

    _, cfg, params = tiny
    i32 = jnp.int32
    pool = serving.init_pool(cfg, 8, BLK, n_slots=2)
    table, fill = jnp.arange(8, dtype=i32).reshape(2, 4), jnp.zeros((2,), i32)
    view = serving._gather_cache(
        pool, table[:1], jnp.asarray([4]), fill[:1],
        state=(pool.conv[:, :1], pool.ssm[:, :1]))
    view = dataclasses.replace(view, index=jnp.asarray(0, i32))
    toks, pos = _tokens(1, 32)
    text = jax.jit(lambda: llama.mixed_forward(
        params, toks, pos, cfg, view, pos >= 0, jnp.zeros((2,), i32),
        jnp.asarray([-1, 3], i32), serving._pool_as_cache(pool, table, fill))[0]
    ).lower().as_text(debug_info=True)
    named = lambda scope: f'"{scope}/' in text or f"/{scope}/" in text  # noqa: E731
    for scope in ("ssm.mix/ssm.scan", "ssm.mix/ssm.step", "attn.full", "dense.ffn"):
        assert named(scope), scope


def test_every_parameter_has_a_partition_rule(tiny):
    _, cfg, params = tiny
    from jax.sharding import PartitionSpec
    from jax_llama_tpu.parallel.mesh import make_mesh
    from jax_llama_tpu.parallel.partition import param_partition_specs, shard_abstract

    mesh = make_mesh(data=1, fsdp=1, tensor=1, devices=jax.devices()[:1])
    shapes = jax.eval_shape(lambda: params)
    placed = shard_abstract(shapes, mesh, cfg)
    assert jax.tree.structure(placed) == jax.tree.structure(shapes)
    specs = param_partition_specs(cfg)
    is_spec = lambda s: isinstance(s, PartitionSpec)  # noqa: E731
    assert jax.tree.structure(specs, is_leaf=is_spec) == jax.tree.structure(shapes)
    assert all(all(axis is None for axis in s)           # whole on its chip
               for s in jax.tree.leaves(specs, is_leaf=is_spec))


def test_the_cache_is_planes_and_a_state_for_every_layer(tiny):
    """Every layer owns K/V planes (ordinary GQA rows of the head size) AND a
    per-row state, `N` minor; the snapshot pool beside the slots' state; the
    dense block has neither."""
    _, cfg, _ = tiny
    pool = serving.init_pool(cfg, 8, BLK, n_slots=4, n_snapshots=6)
    assert pool.k.shape == pool.v.shape == (2, 2, 8, BLK, 16)
    assert pool.conv.shape == (2, 4, 3 * 128) and pool.ssm.shape == (2, 4, 4, 16, 16)
    assert pool.snap_conv.shape == (2, 6, 3 * 128) and pool.snap_ssm.shape == (2, 6, 4, 16, 16)
    assert pool.ssm.dtype == jnp.float32 and pool.stats.shape == (6,)
    cache = jlt.init_cache(cfg, batch=2, max_len=32)
    assert cache.k.shape == (2, 2, 32, 2, 16) and cache.ssm.shape == (2, 2, 4, 16, 16)
    dense = jlt.get_config("tiny")
    assert serving.init_pool(dense, 8, BLK).conv is None and jlt.init_cache(dense, 1).ssm is None


def test_a_checkpoint_of_the_block_loads_as_run_py_loads_it(tiny, tmp_path):
    """`save_checkpoint` -> `load_checkpoint` (what `run.py --ckpt-dir` reads):
    the configuration comes back with its block and its multipliers (tuples
    that JSON hands back as lists), the weights to the bit."""
    from jax_llama_tpu.convert.checkpoint import load_checkpoint, save_checkpoint

    _, cfg, params = tiny
    save_checkpoint(str(tmp_path / "ckpt"), params, cfg)
    back, cfg2 = load_checkpoint(str(tmp_path / "ckpt"))
    assert cfg2 == cfg and hash(cfg2) == hash(cfg) and cfg2.parallel_mixer
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)))


# --- a walk's chunks run ONE program, whatever holds the weights ---------------

@pytest.mark.parametrize("held", ["uncommitted", "one-device", "one-device-mesh"])
def test_a_walks_first_chunk_runs_the_program_of_its_later_chunks(held):
    """The admission's packed vector is a fresh copy at a walk's first chunk
    and the program's own (donated) output after.  Beside COMMITTED weights —
    a server's, born in their shards — an uncommitted copy selected another
    executable than the handed-back one, so every (buffer length, K) pair
    compiled twice, the second time wherever a window first met it (my chip
    run, PR 41: one `_fused_chunk` compile inside a measured window).
    `ContinuousBatcher._upload` commits the copy where the weights are."""
    from jax.sharding import NamedSharding, PartitionSpec
    from jax_llama_tpu.parallel.mesh import make_mesh

    cfg = jlt.get_config("tiny", max_seq_len=256)
    params = jlt.init_params(jax.random.PRNGKey(0), cfg)
    if held == "one-device":
        params = jax.device_put(params, jax.devices()[0])
    elif held == "one-device-mesh":
        mesh = make_mesh(data=1, fsdp=1, tensor=1, devices=jax.devices()[:1])
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    cb = jlt.ContinuousBatcher(
        params, cfg, n_slots=2, block_size=BLK, decode_chunk=2, prefill_budget=32,
        use_pallas_kernel=False)
    assert (cb._upload_to is None) == (held == "uncommitted")
    rng = np.random.RandomState(11)
    draw = lambda n: [int(t) for t in rng.randint(0, 256, size=n)]  # noqa: E731
    cb.submit(draw(20), max_new_tokens=40)          # a holder keeps a row decoding
    for _ in range(3):
        cb.step()
    before = serving._fused_chunk._cache_size()
    cb.submit(draw(100), max_new_tokens=2)          # four chunks of 32 beside it
    sizes = []
    while cb.queue or cb._pf is not None:
        cb.step()
        sizes.append(serving._fused_chunk._cache_size() - before)
    assert len(sizes) >= 4 and set(sizes) == {1}, sizes
