"""The block with recurrent state layers (models/sambay.py, ops/ssm.py) against
its plain reference, `benchmark/references/sambay.py`, loaded by path: one
reference, the one the benchmark's `correct` uses.

Tiny widths, seeded float32 weights, CPU: eight layers (mixer, window, mixer,
window, the publishing mixer, the full layer, a memory unit, a cross layer),
a window of 24 tokens over blocks of 16 (no multiple of the block) and prompts
of ~100, so a state that advances on a masked token, a snapshot one block off,
a wrong plane for the cross layers or a missing pair combine fails the float32
tolerances — which a bfloat16 compute would fail too.
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
from jax_llama_tpu.models import sambay
from jax_llama_tpu.ops import ssm

ROOT = Path(__file__).resolve().parent.parent
CONFIG_FILE = ROOT / "benchmark" / "configs" / "Phi-4-mini-flash-reasoning.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
BOOKKEEPING = ("source", "architecture", "reference", "reduced", "assumed", "deployment")
W, BLK = 24, 16
TINY = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=8,
    num_key_value_heads=4, num_hidden_layers=8, vocab_size=512,
    sliding_window=W, torch_dtype="float32",
)
# float32 on the CPU: the program and the reference differ by the order of
# their sums only (a chunked scan, a joint softmax over cache and step)
TOL = 1e-4


def _reference():
    path = ROOT / "benchmark" / "references" / "sambay.py"
    spec = importlib.util.spec_from_file_location("reference_sambay", path)
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
    assert np.abs(mine - ref).max() < TOL * np.abs(ref).max()


@pytest.mark.parametrize("use_kernel", [True, False], ids=["paged-kernel", "gathered-view"])
def test_prefill_in_chunks_then_decode_through_the_paged_cache(tiny, use_kernel):
    """A 96-token prompt through `_paged_insert` in three 32-token chunks (the
    state handed from chunk to chunk), eight tokens through
    `_paged_decode_chunk` over the pool and the per-slot state, each step's
    logits recomputed by the reference's full forward."""
    raw, cfg, params = tiny
    NB, P, G = 16, 96, 8
    toks, _ = _tokens(1, P, seed=1)
    pool = serving.init_pool(cfg, NB, BLK, n_slots=1)
    ids = jnp.arange(P // BLK, dtype=jnp.int32)[None]
    keys = jnp.zeros((1, 2), jnp.uint32)
    f32, i32 = jnp.float32, jnp.int32
    one = lambda v, dt: jnp.full((1,), v, dt)  # noqa: E731
    tau, _, _, keys, pool = serving._paged_insert(
        params, pool, ids, toks, jnp.ones((1, P), bool), keys,
        one(0.0, f32), one(1.0, f32), one(0, i32), one(0, i32), config=cfg,
        prefill_chunk=32)
    assert float(jnp.abs(pool.ssm).max()) > 0 and float(jnp.abs(pool.conv).max()) > 0
    table = jnp.full((1, 8), NB, i32).at[0, :7].set(jnp.arange(7))
    served, _, stats = decode_row(
        params, cfg, pool, table, 7, P, int(tau[0]), G - 1, use_kernel=use_kernel)
    assert _deficit(params, raw, [int(t) for t in toks[0]], served).max() < TOL
    # window and full steps, the cross layers with the full one: 2 and 2 here
    steps = stats[-2:]
    assert (steps > 0).all() and steps[0] == steps[1] if use_kernel else (steps == 0).all()


# --- (3) the scan in its forms ------------------------------------------------

def _scan_case(B=2, T=32, Di=1024, N=4, seed=0):
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s).astype(np.float32))  # noqa: E731
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (B, T, Di))).astype(np.float32))
    A = -jnp.asarray(np.tile(np.arange(1, N + 1, dtype=np.float32)[:, None], (1, Di)))
    return f(B, N, Di), f(B, T, Di), dt, f(B, T, N), f(B, T, N), A


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_scan_is_steps_is_two_half_chunks(impl):
    """`ssm_scan` over T tokens == T x `ssm_step` == the chunk split in two
    with the state handed over; the kernel in interpret mode."""
    h0, c, dt, Bm, Cm, A = _scan_case()
    B, T, _ = c.shape
    full = jnp.full((B,), T, jnp.int32)
    y, hT = ssm.ssm_scan(h0, c, dt, Bm, Cm, A, full, impl=impl, interpret=True)
    h, ys = h0, []
    for t in range(T):
        y_t, h = ssm.ssm_step(h, c[:, t], dt[:, t], Bm[:, t], Cm[:, t], A, jnp.ones((B,), bool))
        ys.append(y_t)
    assert np.abs(np.asarray(y) - np.stack(ys, 1)).max() < 1e-5
    assert np.abs(np.asarray(hT) - np.asarray(h)).max() < 1e-5
    half = jnp.full((B,), T // 2, jnp.int32)
    cut = lambda a, lo: a[:, lo:lo + T // 2]  # noqa: E731
    y1, h1 = ssm.ssm_scan(h0, *(cut(a, 0) for a in (c, dt, Bm, Cm)), A, half,
                          impl=impl, interpret=True)
    y2, h2 = ssm.ssm_scan(h1, *(cut(a, T // 2) for a in (c, dt, Bm, Cm)), A, half,
                          impl=impl, interpret=True)
    assert np.abs(np.asarray(jnp.concatenate([y1, y2], 1)) - np.asarray(y)).max() < 1e-5
    assert np.abs(np.asarray(h2) - np.asarray(hT)).max() < 1e-5


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_scan_stops_at_a_rows_live_length(impl):
    """Tokens past `lengths[b]` update nothing: the state that leaves is the
    state after the row's last live token; a row with none keeps its own."""
    h0, c, dt, Bm, Cm, A = _scan_case(B=3)
    lengths = jnp.asarray([32, 11, 0], jnp.int32)
    y, hT = ssm.ssm_scan(h0, c, dt, Bm, Cm, A, lengths, impl=impl, interpret=True)
    y11, h11 = ssm.ssm_scan(h0[1:2], c[1:2, :16], dt[1:2, :16], Bm[1:2, :16], Cm[1:2, :16],
                            A, jnp.asarray([11], jnp.int32), impl="xla")
    assert np.abs(np.asarray(hT[1]) - np.asarray(h11[0])).max() < 1e-5
    assert np.abs(np.asarray(y[1, :11]) - np.asarray(y11[0, :11])).max() < 1e-5
    assert np.array_equal(np.asarray(hT[2]), np.asarray(h0[2]))


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
    pool = pool.__class__(**{**vars(pool),
        "conv": jnp.asarray(rng.randn(*pool.conv.shape).astype(np.float32)),
        "ssm": jnp.asarray(rng.randn(*pool.ssm.shape).astype(np.float32))})
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
    # a budget of 3 emits three tokens and runs two forwards: the row folds
    # out when its last token is emitted
    four = run(pool, 4, **args([9, 3, 0], [True, True, False]))
    two = run(pool, 2, **args([9, 9, 0], [True, True, False]))
    for name in ("conv", "ssm"):
        before, after, short = (np.asarray(getattr(p, name)) for p in (pool, four, two))
        assert np.array_equal(after[:, 2], before[:, 2]), name          # the idle slot
        assert np.array_equal(after[:, 1], short[:, 1]), name           # the masked tail
        assert not np.array_equal(after[:, 0], short[:, 0]), name       # the live row went on
        assert not np.array_equal(after[:, 1], before[:, 1]), name


# --- (5) snapshots under the radix store --------------------------------------

@pytest.mark.parametrize("use_kernel", [True, False], ids=["paged-kernel", "gathered-view"])
def test_a_reask_restores_a_snapshot_and_an_evicted_one_shortens_the_match(tiny, use_kernel):
    """Through `ContinuousBatcher`, chunks of 32 over blocks of 16: a request
    admitted alone (the whole-prompt insert, which takes no snapshot), a
    105-token one beside it through `_fused_chunk` (snapshots at 32, 64, 96),
    a re-ask that restores the one at 96 though 6 blocks are cached, one on
    the idle server, which takes the lane too (only its chunks restore a
    snapshot); with the deepest snapshot evicted the match ends at 64 and the
    tokens between are counted as cut.  Every served token is the reference's
    own argmax."""
    raw, cfg, params = tiny
    rng = np.random.RandomState(4)
    draw = lambda n: [int(t) for t in rng.randint(0, 512, size=n)]  # noqa: E731
    doc = draw(100)
    asks = [doc + draw(n) for n in (5, 9, 7, 3)]
    cb = jlt.ContinuousBatcher(
        params, cfg, n_slots=3, block_size=BLK, decode_chunk=4, prefill_budget=32,
        use_pallas_kernel=use_kernel)
    # eight a slot would be 24; the tiny K/V pool's bytes hold 20 of these
    # states (`serving.snapshot_pool_size`: the cell's own sizes give 192)
    assert cb.n_snapshots == 20 and cb.pool.snap_ssm.shape[1] == 20
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
    c = cb.submit(asks[2], max_new_tokens=6)        # the idle server: the lane still
    drain()
    assert cb.prefix_hit_tokens_total == 192 and cb.stats()["ssm_snapshots_restored_total"] == 2
    # evict the snapshot at 96 (its node keeps its block): the match ends at 64
    node = cb._store._by_key[cb._chain_keys(asks[3], BLK)[5]]
    cb._store._drop_snapshot(node)
    d = cb.submit(asks[3], max_new_tokens=6)
    drain()
    stats = cb.stats()
    assert cb.obs.timeline_json(d)["kv"]["prefix_hit_tokens"] == 64
    assert stats["ssm_match_tokens_cut_total"] == 32 and stats["ssm_snapshots_in_use"] >= 3
    assert {r["kind"] for r in cb.obs.dispatches} == {"insert", "fused", "decode"}
    fused = [r for r in cb.obs.dispatches if r["kind"] == "fused"]
    assert sum(r["ssm"]["taken"] for r in fused) >= 3
    assert sum(r["ssm"]["restored"] for r in fused) == 3
    for rid, prompt in ((a, asks[0]), (b, asks[1]), (c, asks[2]), (d, asks[3])):
        assert _deficit(params, raw, prompt, out[rid]).max() < TOL, rid
    from jax_llama_tpu.obs import metric_meta

    for name in ("ssm_snapshots_taken_total", "ssm_snapshots_restored_total",
                 "ssm_snapshots_evicted_total", "ssm_match_tokens_cut_total"):
        assert metric_meta(name)[0] == "counter" and name in stats
    assert metric_meta("ssm_snapshots_in_use")[0] == "gauge"
    assert stats["host_syncs_per_token"] < 1


def test_without_the_lane_a_match_is_cut_whole_and_stays_correct(tiny):
    """`prefill_budget` 0 (the classic path: whole-prompt inserts, which take
    no snapshot): a re-ask finds its blocks cached and no snapshot behind
    them, is prefilled whole, and reads the reference's tokens."""
    raw, cfg, params = tiny
    rng = np.random.RandomState(5)
    draw = lambda n: [int(t) for t in rng.randint(0, 512, size=n)]  # noqa: E731
    doc = draw(100)
    cb = jlt.ContinuousBatcher(params, cfg, n_slots=2, block_size=BLK, decode_chunk=4)
    out = {}
    for ask in (doc + draw(5), doc + draw(7)):
        rid = cb.submit(ask, max_new_tokens=6)
        out.update(cb.run_to_completion())
        assert _deficit(params, raw, ask, out[rid]).max() < TOL
    assert cb.prefix_hit_tokens_total == 0 and cb.stats()["ssm_match_tokens_cut_total"] == 96
    assert {r["kind"] for r in cb.obs.dispatches} == {"insert", "decode"}


def test_the_snapshot_pool_evicts_its_least_recently_used_and_frees_with_the_node():
    from jax_llama_tpu.kvcache import RadixPrefixStore

    store = RadixPrefixStore()
    store.enable_snapshots(2)
    keys = [bytes([i]) for i in range(4)]
    store.publish(keys, [10, 11, 12, 13])
    s0, s1 = store.alloc_snapshot(), store.alloc_snapshot()
    assert store.attach_snapshot(keys[0], s0) and store.attach_snapshot(keys[2], s1)
    assert not store.attach_snapshot(keys[2], 99)            # has one already
    assert store.match(keys[:2]).blocks == [10] and store.match(keys[:2]).cut == 1
    m = store.match(keys)
    assert (m.blocks, m.snap, m.cut) == ([10, 11, 12], s1, 1)
    # the pool is full: the next id is the least recently used one's (keys[2]'s
    # was matched last, so keys[0]'s goes), and its node keeps its block
    assert store.alloc_snapshot() == s0 and store.snapshots_evicted_total == 1
    assert store.match(keys[:2]).blocks == [] and store.match(keys[:2]).cut == 2
    assert store.match(keys).snap == s1
    # a node that goes takes its snapshot with it
    store.retain([10, 11, 12, 13])
    freed = store.unpublish(12)
    assert sorted(freed) == [12, 13] and store.snapshots_in_use() == 0
    assert store.alloc_snapshot() == s1


# --- (6) the head-pair form ---------------------------------------------------

def test_the_head_pair_form_is_differential_attention_with_narrow_heads():
    """GQA at head size 2hd over padded queries and `[k1 | k2]` rows, then the
    pair combine == differential attention written with hd-wide heads."""
    from jax_llama_tpu.ops.attention import attention_bias, sdpa

    B, T, H, KVH, hd = 2, 20, 8, 4, 16
    r = np.random.RandomState(2)
    f = lambda *s: jnp.asarray(r.randn(*s).astype(np.float32))  # noqa: E731
    q, k, v = f(B, T, H, hd), f(B, T, KVH, hd), f(B, T, KVH, hd)
    lam, lam0, subln = 0.37, 0.2, f(2 * hd)
    pos = jnp.tile(jnp.arange(T)[None], (B, 1))
    bias = attention_bias(pos, pos, window=jnp.int32(7))
    wide = sdpa(sambay.pad_query_pairs(q * np.sqrt(2.0)),
                k.reshape(B, T, KVH // 2, 2 * hd), v.reshape(B, T, KVH // 2, 2 * hd), bias)
    got = np.asarray(sambay.combine_pairs(wide, lam, lam0, subln))
    seen = np.asarray(bias[:, 0] == 0)
    want = np.zeros((B, T, H // 2, 2 * hd), np.float32)
    for p in range(H // 2):
        c = p // ((H // 2) // (KVH // 2))
        vv = np.concatenate([v[:, :, 2 * c], v[:, :, 2 * c + 1]], -1)
        o = []
        for qh, kh in ((2 * p, 2 * c), (2 * p + 1, 2 * c + 1)):
            s = np.einsum("btd,bsd->bts", q[:, :, qh], k[:, :, kh]) / np.sqrt(hd)
            s = np.where(seen, s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            o.append(np.einsum("bts,bsd->btd", w / w.sum(-1, keepdims=True), vv))
        d = o[0] - lam * o[1]
        d = d / np.sqrt((d * d).mean(-1, keepdims=True) + sambay.SUBLN_EPS)
        want[:, :, p] = d * np.asarray(subln) * (1 - lam0)
    assert np.abs(got - want).max() < 1e-5


# --- (7) the published keys ---------------------------------------------------

@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_every_catalog_key_at_its_published_value():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Phi-4-mini-flash-reasoning")
    raw = json.loads(CONFIG_FILE.read_text())
    assert raw["source"] == row["source_url"] and raw["reduced"] == {}
    assert all(raw.get(k, "absent") == v for k, v in row["config"].items())


def test_the_file_maps_to_its_published_sizes():
    cfg = config_mod.from_published(_published(), max_seq_len=4096, attn_impl="auto")
    cfg.validate()
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (2560, 32, 40, 20, 64)
    assert (cfg.ffn_dim, cfg.vocab_size, cfg.sliding_window) == (10240, 200064, 512)
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.dt_rank) == (5120, 16, 160)
    assert (cfg.cache_layers, cfg.state_layers, cfg.cache_heads, cfg.cache_width) == (9, 9, 10, 128)
    kinds = cfg.layer_kinds
    assert kinds[:4] == ("mamba", "window", "mamba", "window") and len(kinds) == 32
    assert kinds[16:20] == ("mamba_pub", "full_pub", "gmu", "cross") and kinds[-1] == "cross"
    assert cfg.tie_word_embeddings and cfg.dtype == "bfloat16"
    shapes = jax.eval_shape(lambda: jlt.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 3852.0e6 < n < 3853.5e6          # 3,852.6 M: 7.71 GB in bfloat16


@pytest.mark.parametrize("key,value,named", [
    ("mb_per_layer", 4, "mb_per_layer"), ("model_type", "phi3", "model_type"),
    ("hidden_act", "gelu", "hidden_act"), ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("mlp_bias", True, "mlp_bias"), ("lm_head_bias", True, "lm_head_bias"),
    ("embd_pdrop", 0.1, "embd_pdrop"), ("resid_pdrop", 0.1, "resid_pdrop"),
    ("sliding_window", None, "sliding_window"), ("sliding_window", [512], "sliding_window"),
    ("num_hidden_layers", 30, "num_hidden_layers"), ("torch_dtype", "float16", "torch_dtype"),
    ("rope_theta", 10000.0, "rope_theta"), ("num_experts", 8, "num_experts"),
    ("mamba_dt_rank", "big", "mamba_dt_rank"), ("mamba_d_conv", 8, "mamba_d_conv"),
], ids=lambda v: str(v))
def test_a_changed_or_unknown_key_is_refused_by_name(key, value, named):
    with pytest.raises(ValueError, match=named):
        config_mod.from_published(
            _published(**{key: value}), max_seq_len=4096, attn_impl="auto").validate()


def test_a_missing_key_is_refused_by_name_and_the_other_blocks_map_as_before():
    raw = _published()
    del raw["layer_norm_eps"]
    with pytest.raises(ValueError, match="layer_norm_eps"):
        config_mod.from_published(raw, max_seq_len=4096, attn_impl="auto")
    for name, block in (("mistral-7b-v0.3", None), ("Trinity-Mini", "window attention layers"),
                        ("kanana-2-30b-a3b-instruct-2601", "latent attention")):
        other = json.loads((CONFIG_FILE.parent / f"{name}.json").read_text())
        cfg = config_mod.from_published(
            {k: v for k, v in other.items() if k not in BOOKKEEPING},
            max_seq_len=4096, attn_impl="auto")
        assert cfg.expert_block == block and not cfg.recurrent_state
        assert cfg.cache_layers == cfg.n_layers


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
    (_refuse_tensor, "one chip"),
    (lambda cfg, p: cfg.replace(kv_cache_dtype="int8").validate(), "int8"),
    (lambda cfg, p: cfg.replace(attn_impl="ring").validate(), "ring"),
    (_refuse_quantize, "quantize"),
    (lambda cfg, p: jlt.ContinuousBatcher(p, cfg, n_slots=1, draft_params=p, draft_config=cfg),
     "speculative"),
    (_refuse_serve_mesh, "serve-mesh"), (_refuse_train, "training step"),
    (lambda cfg, p: jlt.ContinuousBatcher(p, cfg, n_slots=1, host_kv_blocks=4), "host tier"),
    (lambda cfg, p: cfg.replace(sliding_window=0).validate(), "sliding_window > 0"),
    (lambda cfg, p: cfg.replace(tie_word_embeddings=False).validate(), "tied"),
    (lambda cfg, p: cfg.replace(n_layers=6).validate(), "multiple of 4"),
    (lambda cfg, p: serving.init_pool(cfg, 8, BLK), "n_slots"),
], ids=["tensor", "int8-kv", "ring", "quantize", "speculation", "serve-mesh", "train",
        "host-tier", "no-window", "untied", "odd-depth", "pool-without-slots"])
def test_unsupported_combination_is_refused_by_name(tiny, attempt, named):
    _, cfg, params = tiny
    with pytest.raises((ValueError, NotImplementedError), match=named):
        attempt(cfg, params)


# --- tracing, sharding rules, the cache ---------------------------------------

def test_scopes_are_in_the_lowered_programs(tiny):
    _, cfg, params = tiny
    toks, pos = _tokens(1, 16)
    text = jax.jit(lambda p, t, q: jlt.forward(p, t, q, cfg)[0]).lower(
        params, toks, pos).as_text(debug_info=True)
    for scope in ("ssm.mix", "ssm.scan", "gmu.mix", "attn.window", "attn.full",
                  "attn.cross", "dense.ffn"):
        assert scope in text, scope
    pool = serving.init_pool(cfg, 8, BLK, n_slots=1)
    cache = serving._pool_as_cache(pool, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32))
    text = jax.jit(lambda p, t, q, c: jlt.forward(p, t, q, cfg, cache=c)[0]).lower(
        params, toks[:, :1], pos[:, :1], cache).as_text(debug_info=True)
    assert "ssm.scan" in text and "attn.cross" in text
    # the mixed pass (a prompt chunk and two decode rows, one of them masked)
    # keeps every scope, so the `step.*_share_pct` readers keep reading
    from jax_llama_tpu.models import llama

    pool = serving.init_pool(cfg, 8, BLK, n_slots=2)
    table, fill = jnp.arange(8, dtype=jnp.int32).reshape(2, 4), jnp.zeros((2,), jnp.int32)
    view = serving._gather_cache(
        pool, table[:1], jnp.asarray([4]), fill[:1],
        state=(pool.conv[:, :1], pool.ssm[:, :1]))
    view = dataclasses.replace(view, index=jnp.int32(0))
    text = jax.jit(lambda p, t, q, c, pc: llama.mixed_forward(
        p, t, q, cfg, c, q >= 0, jnp.asarray([5, 7]), jnp.asarray([3, -1]), pc)[0]
    ).lower(params, toks, pos, view, serving._pool_as_cache(pool, table, fill)
            ).as_text(debug_info=True)
    for scope in ("ssm.mix", "ssm.scan", "gmu.mix", "attn.window", "attn.full",
                  "attn.cross", "dense.ffn"):
        assert scope in text, scope


def test_every_parameter_has_a_partition_rule(tiny):
    _, cfg, params = tiny
    from jax_llama_tpu.parallel.mesh import make_mesh
    from jax_llama_tpu.parallel.partition import shard_abstract

    mesh = make_mesh(data=1, fsdp=1, tensor=1, devices=jax.devices()[:1])
    shapes = jax.eval_shape(lambda: params)
    placed = shard_abstract(shapes, mesh, cfg)
    assert jax.tree.structure(placed) == jax.tree.structure(shapes)


def test_the_cache_is_planes_for_the_owning_layers_and_a_state_a_row(tiny):
    """K/V planes for the 3 of 8 layers that own keys (two window layers and
    the full one, head pairs of 2hd), and beside them `conv` / `ssm` for the 3
    mixers a row, channels minor; the snapshot pool beside the slots' state."""
    _, cfg, _ = tiny
    pool = serving.init_pool(cfg, 8, BLK, n_slots=4, n_snapshots=6)
    assert pool.k.shape == pool.v.shape == (3, 2, 8, BLK, 16)
    assert pool.conv.shape == (3, 4, 3 * 128) and pool.ssm.shape == (3, 4, 16, 128)
    assert pool.snap_conv.shape == (3, 6, 3 * 128) and pool.snap_ssm.shape == (3, 6, 16, 128)
    assert pool.ssm.dtype == jnp.float32 and pool.stats.shape == (6,)
    cache = jlt.init_cache(cfg, batch=2, max_len=32)
    assert cache.k.shape == (3, 2, 32, 2, 16) and cache.ssm.shape == (3, 2, 16, 128)
    dense = jlt.get_config("tiny")
    assert serving.init_pool(dense, 8, BLK).conv is None and jlt.init_cache(dense, 1).ssm is None


def test_a_snapshot_copy_or_a_state_reset_adds_no_fetch_and_no_retrace(tiny, monkeypatch):
    """The perf-smoke pin on this block: every dispatch of a fused admission
    (first chunk from the empty state, middle chunks that leave a snapshot,
    the last that leaves none) and of a re-ask that restores one pays ONE
    packed fetch, the admission one state upload and ONE host->device copy
    (its packed vector, on its first record; no chunk after it copies
    anything), and all of them run one compiled `_fused_chunk` a (K, chunk)
    pair: the snapshot ids are values — one int32 [2] HOST operand of the
    call itself, every chunk, where two device scalars were copied."""
    _, cfg, params = tiny
    rng = np.random.RandomState(9)
    draw = lambda n: [int(t) for t in rng.randint(0, 512, size=n)]  # noqa: E731
    doc = draw(100)
    cb = jlt.ContinuousBatcher(
        params, cfg, n_slots=3, block_size=BLK, decode_chunk=4, prefill_budget=32)
    cb.submit(draw(40), max_new_tokens=60)
    for _ in range(3):
        cb.step()
    snaps, fused = [], serving._fused_chunk

    def spy(*args, **kwargs):
        snaps.append(args[-1])
        return fused(*args, **kwargs)

    monkeypatch.setattr(serving, "_fused_chunk", spy)
    for ask in (doc + draw(5), doc + draw(6)):
        cb.submit(ask, max_new_tokens=4)
        uploads, copies, programs = cb.state_uploads_total, cb.obs.host_uploads_total, None
        first = len(cb.obs.dispatches)
        while cb.queue or cb._pf is not None:
            syncs = cb.host_syncs_total
            cb.step()
            assert cb.host_syncs_total - syncs == 1
            if cb._pf is not None:      # the walk's chunks share one program
                programs = programs or fused._cache_size()
                assert fused._cache_size() == programs
        assert cb.state_uploads_total - uploads == 1
        assert cb.obs.host_uploads_total - copies == 1
        walk = list(cb.obs.dispatches)[first:]
        assert {r["kind"] for r in walk} == {"fused"}
        assert [r["uploads"] for r in walk] == [1] + [0] * (len(walk) - 1)
        while any(s is not None and s.max_new == 4 for s in cb.slots.values()):
            cb.step()
    assert cb.stats()["ssm_snapshots_restored_total"] == 1
    assert cb.stats()["ssm_snapshots_taken_total"] == 3
    assert all(type(s) is np.ndarray and s.dtype == np.int32 and s.shape == (2,) for s in snaps)
    # (from, to): the fresh walk leaves three, the re-ask starts from the deepest
    *left, last, reask = (s.tolist() for s in snaps)
    assert [s[0] for s in left] == [-1] * 3 and len({s[1] for s in left}) == 3
    assert last == [-1, -1] and reask == [left[-1][1], -1]


def test_a_checkpoint_of_the_block_loads_as_run_py_loads_it(tiny, tmp_path):
    """`save_checkpoint` -> `load_checkpoint` (what `run.py --ckpt-dir` reads):
    the configuration comes back with its block, the weights to the bit."""
    from jax_llama_tpu.convert.checkpoint import load_checkpoint, save_checkpoint

    _, cfg, params = tiny
    save_checkpoint(str(tmp_path / "ckpt"), params, cfg)
    back, cfg2 = load_checkpoint(str(tmp_path / "ckpt"))
    assert cfg2 == cfg and cfg2.recurrent_state and cfg2.layer_kinds == cfg.layer_kinds
    assert jax.tree.structure(back) == jax.tree.structure(params)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)))


def test_a_short_suffix_keeps_the_whole_chunk_so_snapshots_stay_on_one_grid(tiny):
    """`_pf_chunk` of this block does not shrink to a short suffix's own
    bucket (the dense block's does): chunk ends, where snapshots stand, stay
    at the hit + multiples of the budget, and a rare length class gets no
    program variant of its own.  The view-fit halving still holds."""
    _, cfg, params = tiny
    cb = jlt.ContinuousBatcher(params, cfg, n_slots=2, block_size=BLK, prefill_budget=64)
    assert [cb._pf_chunk(n, 4) for n in (5, 17, 40, 64, 130)] == [64] * 5
    assert cb._pf_chunk(100, cb.blocks_per_slot - 8) == 64
    assert cb._pf_chunk(100, cb.blocks_per_slot - 7) == 16     # 7 blocks of view left: halved to fit
    dense = jlt.get_config("tiny", attn_impl="auto")
    cd = jlt.ContinuousBatcher(jlt.init_params(jax.random.PRNGKey(0), dense), dense,
                               n_slots=2, block_size=BLK, prefill_budget=64)
    assert [cd._pf_chunk(n, 0) for n in (5, 17, 40, 64)] == [16, 32, 64, 64]
