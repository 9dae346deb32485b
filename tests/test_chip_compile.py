"""Rehearsal 3 as a test: the main path's kernels compile for a v5e.

The TPU compiler is installed on CPU-only machines and compiles for a
chip that is *described*, not attached (on-chip-measurement guide,
section 2).  Each case lowers one Pallas kernel at Llama-3-8B head
geometry (32 query / 8 KV heads, head_dim 128, bf16; the geometry
chip_smoke.py serves) with ``interpret=False`` onto a described
``v5e:2x2`` device and asserts the Mosaic custom call is in the compiled
program.  Interpret-mode tests cannot see what this sees: unaligned
slices, VMEM limits, lowering errors.  Nothing executes — a compile that
passes is not a chip run.

The topology is described inside a module-scoped fixture, never at
import, in a skipif or in parametrize arguments: only one process may
load libtpu, and every xdist worker imports this file.  All such tests
live in THIS file (a second file could land on another worker, whose
fixture would then skip), and compile in the test's own process.
"""

import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, KVH, D = 32, 8, 128          # llama3-8b heads
B, BLK, MB, L = 8, 128, 16, 2   # decode rows, block, blocks/row, pool layers
NB = B * MB
S = 2048                        # prefill length


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around these.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placing every operand on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _assert_mosaic(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def _flash_operands(sds):
    return (
        sds((1, S, H, D), jnp.bfloat16), sds((1, S, KVH, D), jnp.bfloat16),
        sds((1, S, KVH, D), jnp.bfloat16), sds((1, S), jnp.int32),
        sds((1, S), jnp.int32),
    )


def test_flash_forward_compiles_for_v5e(sds):
    from jax_llama_tpu.ops.flash_attention import flash_attention

    _assert_mosaic(flash_attention.lower(*_flash_operands(sds), interpret=False))


def test_flash_vjp_compiles_for_v5e(sds):
    from jax_llama_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v, q_pos, kv_pos):
        out = flash_attention(q, k, v, q_pos, kv_pos, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    _assert_mosaic(grad.lower(*_flash_operands(sds)))


# (rows, blocks a row, local KV heads, pool layers): the smoke's geometry,
# the benchmark's two cells (24-layer Mistral-7B pool of 256 blocks:
# 16 slots x 2048 and 8 slots x 4096), and what one of four chips holds
# of Codestral-22B under --serve-mesh 1,4 (8 KV heads / 4, 56 layers).
_PAGED_SHAPES = {
    "smoke": (B, MB, KVH, L),
    "cell1": (16, 16, 8, 24),
    "cell2": (8, 32, 8, 24),
    "tp4": (16, 16, 2, 56),
}


@pytest.mark.parametrize(
    "shape,quantized,t_tokens",
    [
        ("smoke", False, 1), ("smoke", True, 1), ("smoke", False, 5),
        ("cell1", False, 1), ("cell2", False, 1), ("tp4", False, 1),
        ("cell1", True, 1), ("cell1", False, 5), ("tp4", True, 5),
    ],
    ids=[
        "bf16", "int8", "bf16-verify-t5",
        "cell1-b16xmb16", "cell2-b8xmb32", "tp4-kvh2-l56",
        "cell1-int8", "cell1-verify-t5", "tp4-int8-verify-t5",
    ],
)
def test_paged_decode_compiles_for_v5e(sds, shape, quantized, t_tokens):
    """The custom paged kernel over a layer-indexed pool: bf16, int8
    (in-kernel scale folding), and the multi-token speculative-verify
    sweep (t_tokens > 1).  A grid step holds several blocks of K and V,
    double-buffered, and how many follows from the shapes: the VMEM that
    takes is what interpret mode cannot see, so every served geometry
    compiles here."""
    from jax_llama_tpu.ops.paged_attention import paged_pool_attention

    rows, mb, kvh, layers = _PAGED_SHAPES[shape]
    nb = rows * mb
    G = H // KVH
    pool_dtype = jnp.int8 if quantized else jnp.bfloat16
    pool = sds((layers, kvh, nb, BLK, D), pool_dtype)
    scale = sds((layers, kvh, nb, BLK), jnp.float32) if quantized else None
    lowered = paged_pool_attention.lower(
        sds((rows, kvh, t_tokens * G, D), jnp.bfloat16), pool, pool,
        sds((nb, BLK), jnp.int32), sds((rows, mb), jnp.int32),
        sds((rows,), jnp.int32), k_scale=scale, v_scale=scale,
        t_tokens=t_tokens, layer=sds((), jnp.int32), interpret=False,
    )
    _assert_mosaic(lowered)


@pytest.mark.parametrize("view", [2048, 4096], ids=["cell1-view2048", "cell2-view4096"])
def test_flash_fused_chunk_compiles_for_v5e(sds, view):
    """The flash kernel as `_fused_chunk`'s prompt lane calls it in the two
    Mistral-7B cells: a `prefill_budget` of 512 queries over the row's
    whole view (`max_seq_len` 2048 and 4096), 32 query / 8 KV heads."""
    from jax_llama_tpu.ops.flash_attention import flash_attention

    chunk = 512
    kv = sds((1, view, KVH, D), jnp.bfloat16)
    _assert_mosaic(flash_attention.lower(
        sds((1, chunk, H, D), jnp.bfloat16), kv, kv,
        sds((1, chunk), jnp.int32), sds((1, view), jnp.int32), interpret=False,
    ))


@pytest.mark.parametrize(
    "queries,keys,view",
    [(2048, 2048, 16384), (16384, 16384, 16384), (2048, 16384 + 2048, 0)],
    ids=["chunk-over-8-tile-view", "whole-prompt-insert", "suffix-over-gathered-view"],
)
def test_latent_flash_compiles_for_v5e(sds, queries, keys, view):
    """`latent_flash_attention` at kanana-2-30b-a3b's widths (32 heads,
    r 512, 128 nope + 64 rope, values 128, cache row 640) in the forms
    `models/mla_moe.py` gives it in `kanana2-docqa-long`: a 2,048-token
    chunk over a 16,384-slot view of 8 tiles with its live-tile count and
    its layer traced values (`attend_tiled` in `_fused_chunk`); a whole
    prompt of the 16,384 bucket behind an empty cache of its own length
    (`_paged_insert`, the window's first request); and, with no cache
    operand, a 2,048-token suffix over a row's whole gathered view and
    itself as new rows (`attend_decompressed` in `_paged_suffix_insert`).
    K and V of a tile are rebuilt inside the kernel: its vector memory is
    what this compile holds to the limit."""
    from jax_llama_tpu.ops.flash_attention import latent_flash_attention

    bf16, i32 = jnp.bfloat16, jnp.int32
    cache = dict(
        ctx=sds((8, 1, view, 640), bf16), ctx_pos=sds((1, view), i32),
        layer=sds((), i32), ctx_tiles=sds((), i32)) if view else {}
    fn = jax.jit(functools.partial(latent_flash_attention, interpret=False))
    lowered = fn.lower(
        sds((1, queries, 32, 128), bf16), sds((1, queries, 32, 64), bf16),
        sds((1, keys, 640), bf16), sds((32, 512, 256), bf16),
        sds((1, queries), i32), sds((1, keys), i32), **cache)
    _assert_mosaic(lowered)
    # the latent rows are operands of the kernel itself: no slice of the
    # cache, no K/V of the heads' width in HBM
    assert f"{max(view, keys)},32" not in lowered.as_text()


def test_latent_paged_decode_compiles_for_v5e(sds):
    """The paged kernel over a LATENT pool at kanana-2-30b-a3b's geometry
    and the benchmark cell's (8 rows x 32 blocks of 512, 8 layers): one
    cache head whose 640-wide row (512 latent + 64 rope + lane padding)
    is key and, in its first 512 columns, value; 32 query heads share it."""
    from jax_llama_tpu.ops.paged_attention import paged_pool_attention

    rows, mb, blk, layers, width = 8, 32, 512, 8, 640
    nb = rows * mb
    lowered = paged_pool_attention.lower(
        sds((rows, 1, 32, width), jnp.bfloat16),
        sds((layers, 1, nb, blk, width), jnp.bfloat16), None,
        sds((nb, blk), jnp.int32), sds((rows, mb), jnp.int32),
        sds((rows,), jnp.int32), t_tokens=1, layer=sds((), jnp.int32),
        interpret=False, v_width=512, scale=192 ** -0.5,
    )
    _assert_mosaic(lowered)


@pytest.mark.parametrize(
    "m,k,n,groups",
    [
        (128, 2048, 1536, 7 * 128), (12416, 2048, 1536, 7 * 128),
        (12416, 768, 2048, 7 * 128),
        (128, 3584, 2048, 5 * 64), (8192, 3584, 2048, 5 * 64),
        (128, 1024, 3584, 5 * 64), (8192, 1024, 3584, 5 * 64),
        (2048, 3584, 2048, 5 * 64), (2048, 1024, 3584, 5 * 64),
        (128, 2048, 2048, 4 * 128), (16384, 2048, 2048, 4 * 128),
        (128, 1024, 2048, 4 * 128), (16384, 1024, 2048, 4 * 128),
        (4096, 2048, 2048, 4 * 128), (3072, 768, 2048, 7 * 128),
    ],
    ids=[
        "decode-gate_up", "chunk-gate_up", "chunk-down",
        "xing4-decode-gate_up", "xing4-chunk-gate_up",
        "xing4-decode-down", "xing4-chunk-down",
        "xing4-chunk512-gate_up", "xing4-chunk512-down",
        "trinity-decode-gate_up", "trinity-chunk-gate_up",
        "trinity-decode-down", "trinity-chunk-down",
        "trinity-chunk512-gate_up", "kanana-chunk512-down",
    ],
)
def test_grouped_expert_matmul_compiles_for_v5e(sds, m, k, n, groups):
    """The grouped matmul of ops/moe.py (upstream megablox) at the tiles
    `_tiling` picks for the published experts, all layers' experts handed
    to the kernel: kanana-2-30b-a3b's (128 of 2048 x 1536 and 768 x 2048
    in each of 7 layers; 48 decode rows padded to one tile, a 2048-token
    chunk's 12,336 pairs; Keye-VL-2.0's are the same products),
    Xing4.0-29B-A4B's (64 of 3584 x 2048 and 1024 x 3584 in 5 layers; 4
    pairs a token) and Trinity-Mini's (128 of 2048 x 2048 and 1024 x 2048
    in 4 layers; 8 pairs a token), with a 512-token chunk's rows.  The
    compile sees the vector-memory limit the rule's budget stands under."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from jax_llama_tpu.ops.moe import _tiling

    fn = jax.jit(lambda x, w, g: gmm(
        x, w, g, preferred_element_type=jnp.bfloat16,
        tiling=_tiling(k, n, jnp.bfloat16)))
    _assert_mosaic(fn.lower(
        sds((m, k), jnp.bfloat16), sds((groups, k, n), jnp.bfloat16),
        sds((groups,), jnp.int32),
    ))


def test_latent_tiled_prefill_compiles_for_v5e(sds, monkeypatch):
    """`mla_moe.attend_tiled` at kanana-2-30b-a3b's widths and the benchmark
    cell's view (16,384 slots, a 2048-token chunk, 8 layers): one latent
    flash kernel whose live-tile count is a value, and no operand of the
    view's width times the heads in the program."""
    from jax_llama_tpu import config as config_mod
    from jax_llama_tpu.models import mla_moe
    from jax_llama_tpu.models.llama import KVCache

    # `interpret=None` asks the default backend, the CPU here: steer it.
    # (`ops.flash_attention` the attribute is the function, not the module.)
    monkeypatch.setattr(
        sys.modules["jax_llama_tpu.ops.flash_attention"], "_resolve_interpret",
        lambda _: False)
    cfg = config_mod.LLaMAConfig(
        n_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, dtype="bfloat16")
    layers, view, chunk, width = 8, 16384, 2048, cfg.cache_width
    bf16, i32 = jnp.bfloat16, jnp.int32

    def attend(q_nope, q_rope, latent, kv_b, q_pos, new_pos, k, pos, index, layer):
        cache = KVCache(k=k, v=None, pos=pos, index=index)
        return mla_moe.attend_tiled(
            q_nope, q_rope, latent, kv_b, q_pos, new_pos, cache, layer, cfg)

    lowered = jax.jit(attend).lower(
        sds((1, chunk, 32, 128), bf16), sds((1, chunk, 32, 64), bf16),
        sds((1, chunk, width), bf16), sds((32, 512, 256), bf16),
        sds((1, chunk), i32), sds((1, chunk), i32),
        sds((layers, 1, view, 1, width), bf16), sds((1, view), i32),
        sds((), i32), sds((), i32),
    )
    _assert_mosaic(lowered)
    assert f"{view + chunk},32" not in lowered.as_text()


@pytest.mark.parametrize(
    "rows,view", [(16, 2048), (8, 4096)],
    ids=["cell1-16rows", "cell2-8rows"],
)
def test_fused_chunk_no_pool_copies_for_v5e(sds, monkeypatch, rows, view):
    """`_fused_chunk` whole, at the two Mistral-7B cells' widths: a
    [L, 8, 256, 128, 128] bf16 pool of 32,768 tokens under 16 rows x 2048 and
    8 rows x 4096, `pf_chunk` 512 (four 128-token blocks), 8 decode
    iterations.  Depth is cut to 2 so the case stays in seconds; a relayout
    of the pool does not depend on depth.  The prompt chunk lands by whole
    blocks (`paged_pool_write_blocks`), so no pool-sized `copy` may stand in
    the compiled program: the pair form's scatter left four (ledger, PRs
    25-30: `%copy.18x bf16[24,8,256,128,128]`, 15 % of cell 2's busy time)."""
    from test_serving_fused import fused_chunk_operand_shapes
    from test_tpu_compiled import _pool_copy_offenders

    from jax_llama_tpu import get_config, init_params, serving

    # By module NAME: `jax_llama_tpu.ops` re-exports functions of these names.
    for name in ("flash_attention", "paged_attention"):
        monkeypatch.setattr(
            importlib.import_module(f"jax_llama_tpu.ops.{name}"),
            "_resolve_interpret", lambda _=None: False)
    layers, blk, chunk = 2, 128, 512
    cfg = get_config(
        "tiny", dim=4096, n_layers=layers, n_heads=H, n_kv_heads=KVH,
        intermediate_size=14336, vocab_size=32768, max_seq_len=view,
        dtype="bfloat16", param_dtype="bfloat16", attn_impl="auto",
    )
    mb = view // blk
    nb = rows * mb
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pool = place(jax.eval_shape(lambda: serving.init_pool(cfg, nb, blk)))
    assert pool.k.shape == (layers, KVH, 256, blk, D)
    lowered = serving._fused_chunk.lower(
        params, pool, *fused_chunk_operand_shapes(sds, rows, mb, chunk),
        config=cfg, n_iter=8, pf_chunk=chunk, all_greedy=True, mesh=None,
        allow_kernel=True, with_logprobs=False,
    )
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # flash and the paged kernel
    offenders = _pool_copy_offenders(text, pool.k.shape)
    assert not offenders, (len(offenders), offenders)


# --- the window forms of the two kernels (Trinity-Mini's geometry) -----------

@pytest.mark.parametrize(
    "queries,keys", [(2048, 32768), (512, 32768), (8192, 8192)],
    ids=["chunk-over-view", "short-chunk-over-view", "whole-prompt-insert"],
)
def test_flash_window_compiles_for_v5e(sds, queries, keys):
    """The flash kernel with its window operand (a second scalar-prefetched
    bound a q block and the window itself) as `trinitymini-docqa-mixed` calls
    it: 32 query / 4 KV heads of 128, a `prefill_budget` chunk over the row's
    32,768-slot view, and a whole prompt over itself."""
    from jax_llama_tpu.ops.flash_attention import flash_attention

    kv = sds((1, keys, 4, D), jnp.bfloat16)
    _assert_mosaic(flash_attention.lower(
        sds((1, queries, 32, D), jnp.bfloat16), kv, kv,
        sds((1, queries), jnp.int32), sds((1, keys), jnp.int32), interpret=False,
        window=sds((), jnp.int32),
    ))


@pytest.mark.parametrize("t_tokens", [1, 5], ids=["decode", "verify-t5"])
def test_paged_window_decode_compiles_for_v5e(sds, t_tokens):
    """The paged kernel with its window operand over the cell's pool: 8 rows
    x 64 blocks of 512 tokens, 4 KV heads of 128 under 32 query heads, 5
    layers, the step list handed in (`fetch_plan`) as the block derives it
    outside its layer scan."""
    from jax_llama_tpu.ops.paged_attention import fetch_plan, paged_pool_attention

    rows, mb, kvh, blk, layers = 8, 64, 4, 512, 5
    nb = rows * mb
    pool = sds((layers, kvh, nb, blk, D), jnp.bfloat16)
    pos, table, q_pos = sds((nb, blk), jnp.int32), sds((rows, mb), jnp.int32), sds((rows,), jnp.int32)
    window = sds((), jnp.int32)

    def attend(q, pool_k, pool_v, pos, table, q_pos, layer, window):
        plan = fetch_plan(pool_k, pos, table, q_pos, t_tokens, window)
        return paged_pool_attention(
            q, pool_k, pool_v, pos, table, q_pos, t_tokens=t_tokens, layer=layer,
            interpret=False, window=window, plan=plan)

    _assert_mosaic(jax.jit(attend).lower(
        sds((rows, kvh, t_tokens * 8, D), jnp.bfloat16), pool, pool, pos, table,
        q_pos, sds((), jnp.int32), window))


def test_paged_index_scores_compile_for_v5e(sds):
    """The decode rows' scoring kernel at Keye-VL-2.0-30B-A3B's indexer
    geometry and the benchmark cell's pool: 8 rows x 64 blocks of 512 tokens,
    16 index heads of 64 over a one-head bfloat16 plane of 6 layers; 8 table
    entries a grid step, the step list derived outside."""
    from jax_llama_tpu.ops import key_selection as ks

    rows, mb, blk, layers, hi, di = 8, 64, 512, 6, 16, 64
    nb = rows * mb

    def scores(q, w, plane, pos, table, q_pos, layer):
        plan = ks.index_plan(pos, table, q_pos)
        assert plan[4].shape == (rows * 8, 8, blk)
        return ks.paged_index_scores(q, w, plane, plan, q_pos, layer, interpret=False)

    _assert_mosaic(jax.jit(scores).lower(
        sds((rows, hi, di), jnp.bfloat16), sds((rows, hi), jnp.float32),
        sds((layers, 1, nb, blk, di), jnp.bfloat16),
        sds((nb, blk), jnp.int32), sds((rows, mb), jnp.int32),
        sds((rows,), jnp.int32), sds((), jnp.int32)))


def test_paged_index_select_compiles_for_v5e(sds):
    """The search for the k-th value, the mask and the chosen list as one
    kernel over the cell's images: 8 rows x 64 lines of 512 slots in VMEM,
    top-2048 (lines of two 256-slot sub-lines, four tiles of the list)."""
    from jax_llama_tpu.ops import key_selection as ks

    rows, mb, blk = 8, 64, 512
    lowered = jax.jit(functools.partial(
        ks.paged_index_select, topk=2048, n_slots=mb * blk, interpret=False,
    )).lower(sds((rows, mb, blk), jnp.int32), sds((rows,), jnp.uint32))
    _assert_mosaic(lowered)


def test_sparse_decode_chunk_copies_no_index_plane_an_iteration_for_v5e(sds, monkeypatch):
    """`_paged_decode_chunk` of the learned-sparse-attention block at the
    cell's widths (depth 2), 8 iterations over 8 rows x 64 blocks of 512: the
    ranking kernel reads the index plane as the decode scan carries it, so
    the only plane-sized copies are the pool argument's at entry and exit
    (the device keeps it with the longer dimension minor) — none in the
    scan's body, where the XLA gather it replaces made one an iteration."""
    import json
    import re
    from pathlib import Path

    from test_serving_fused import fused_chunk_operand_shapes

    from jax_llama_tpu import config as config_mod, init_params, serving

    for name in ("flash_attention", "paged_attention", "key_selection"):
        monkeypatch.setattr(
            importlib.import_module(f"jax_llama_tpu.ops.{name}"),
            "_resolve_interpret", lambda _=None: False)
    raw = json.loads((Path(__file__).resolve().parent.parent / "benchmark" / "configs"
                      / "Keye-VL-2.0-30B-A3B.json").read_text())
    keys = {k: v for k, v in raw.items() if k not in (
        "source", "architecture", "reference", "reduced", "assumed", "deployment")}
    layers, rows, mb, blk = 2, 8, 64, 512
    cfg = config_mod.from_published(
        dict(keys, num_hidden_layers=layers), max_seq_len=mb * blk, attn_impl="auto")
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pool = place(jax.eval_shape(lambda: serving.init_pool(cfg, rows * mb, blk)))
    text = serving._paged_decode_chunk.lower(
        params, pool, *fused_chunk_operand_shapes(sds, rows, mb, 2048)[:13],
        config=cfg, n_iter=8, all_greedy=True, mesh=None,
        allow_kernel=True, with_logprobs=False,
    ).compile().as_text()
    assert "tpu_custom_call" in text
    plane = re.escape(f"bf16[{layers},1,{rows * mb},{blk},64]")
    copies = [line.strip()[:120] for line in text.splitlines()
              if re.search(rf"= {plane}\S* copy\(", line)]
    entry = text[text.index("\nENTRY "):]
    assert all(line[:40] in entry for line in copies) and len(copies) <= 2, copies


# --- the recurrent block at its cell's shapes (phi4flash-reason-sessions) ----

def _recurrent_cell(sds, monkeypatch, layers):
    """(config, params, pool) shapes of the cell on a described chip: every
    published width, 24 slots x 4096 over 128-token blocks (768 blocks),
    192 snapshots; depth `layers`.  The kernels compile, not interpret."""
    import json
    from pathlib import Path

    from jax_llama_tpu import config as config_mod, init_params, serving

    for name in ("flash_attention", "paged_attention", "ssm"):
        monkeypatch.setattr(
            importlib.import_module(f"jax_llama_tpu.ops.{name}"),
            "_resolve_interpret", lambda _=None: False)
    raw = json.loads((Path(__file__).resolve().parent.parent / "benchmark" / "configs"
                      / "Phi-4-mini-flash-reasoning.json").read_text())
    keys = {k: v for k, v in raw.items() if k not in (
        "source", "architecture", "reference", "reduced", "assumed", "deployment")}
    cfg = config_mod.from_published(
        dict(keys, num_hidden_layers=layers), max_seq_len=4096, attn_impl="auto")
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pool = place(jax.eval_shape(
        lambda: serving.init_pool(cfg, 768, 128, n_slots=24, n_snapshots=192)))
    assert pool.k.shape == (layers // 4 + 1, 10, 768, 128, 128)
    assert pool.ssm.shape == (layers // 4 + 1, 24, 16, 5120)
    return cfg, params, pool


def _state_temporaries(text, tokens):
    """Lines of a compiled program that hold the recurrence a token: a
    [T, N, Di] / [T, Di, N] (or channel-folded) array of the chunk's
    tokens, which the scan kernel exists never to materialise."""
    import re

    shapes = (rf"{tokens},16,5120", rf"{tokens},5120,16", rf"{tokens},16,40,128")
    return [line.strip()[:140] for line in text.splitlines()
            if re.search(rf"\[(1,)?({'|'.join(shapes)})\]", line)]


def test_recurrent_fused_chunk_for_v5e(sds, monkeypatch):
    """`_fused_chunk` at the cell's widths (depth 8: two mixer + window pairs,
    the publishing pair, one memory unit + cross pair), `pf_chunk` 512, 8
    decode iterations: the flash, paged and scan kernels are in the program,
    no pool-sized copy and no [T, N, Di] temporary stand in it."""
    from test_serving_fused import fused_chunk_operand_shapes
    from test_tpu_compiled import _pool_copy_offenders

    from jax_llama_tpu import serving

    cfg, params, pool = _recurrent_cell(sds, monkeypatch, 8)
    lowered = serving._fused_chunk.lower(
        params, pool, *fused_chunk_operand_shapes(sds, 24, 32, 512),
        sds((2,), jnp.int32),
        config=cfg, n_iter=8, pf_chunk=512, all_greedy=True, mesh=None,
        allow_kernel=True, with_logprobs=False,
    )
    assert "ssm_scan" in lowered.as_text()
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 3   # flash, the paged kernel, the scan
    offenders = _pool_copy_offenders(text, pool.k.shape)
    assert not offenders, (len(offenders), offenders)
    assert not _state_temporaries(text, 512)


def test_recurrent_decode_chunk_for_v5e(sds, monkeypatch):
    """`_paged_decode_chunk` at the cell's widths (depth 8), 8 iterations over
    24 rows: the paged kernel is in the program and no pool-sized copy."""
    from test_serving_fused import fused_chunk_operand_shapes
    from test_tpu_compiled import _pool_copy_offenders

    from jax_llama_tpu import serving

    cfg, params, pool = _recurrent_cell(sds, monkeypatch, 8)
    lowered = serving._paged_decode_chunk.lower(
        params, pool, *fused_chunk_operand_shapes(sds, 24, 32, 512)[:13],
        config=cfg, n_iter=8, all_greedy=True, mesh=None,
        allow_kernel=True, with_logprobs=False,
    )
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    offenders = _pool_copy_offenders(text, pool.k.shape)
    assert not offenders, (len(offenders), offenders)


# --- the block with a mixer beside attention at its cell's shapes
# (falconh1-assist-sessions): a GQA group of 5 at head size 128 ---------------

def test_flash_and_paged_kernels_take_a_gqa_group_of_five_for_v5e(sds):
    """20 query heads over 4 KV heads of 128, which no other cell puts
    through the kernels: the flash kernel as the cell's prompt lane calls it
    (512 queries over a 4096-slot view) and the paged decode kernel over the
    cell's pool (32 rows x 32 blocks of 128, 6 layers)."""
    from jax_llama_tpu.ops.flash_attention import flash_attention
    from jax_llama_tpu.ops.paged_attention import paged_pool_attention

    heads, kvh, rows, mb, layers, chunk, view = 20, 4, 32, 32, 6, 512, 4096
    kv = sds((1, view, kvh, D), jnp.bfloat16)
    _assert_mosaic(flash_attention.lower(
        sds((1, chunk, heads, D), jnp.bfloat16), kv, kv,
        sds((1, chunk), jnp.int32), sds((1, view), jnp.int32), interpret=False,
    ))
    nb = rows * mb
    pool = sds((layers, kvh, nb, BLK, D), jnp.bfloat16)
    _assert_mosaic(paged_pool_attention.lower(
        sds((rows, kvh, heads // kvh, D), jnp.bfloat16), pool, pool,
        sds((nb, BLK), jnp.int32), sds((rows, mb), jnp.int32),
        sds((rows,), jnp.int32), k_scale=None, v_scale=None,
        t_tokens=1, layer=sds((), jnp.int32), interpret=False,
    ))


def test_parallel_mixer_fused_chunk_for_v5e(sds, monkeypatch):
    """`_fused_chunk` at the cell's widths (depth 2 of the 6 it runs), 32
    slots x 4096 over 128-token blocks, 63 snapshots, `pf_chunk` 512, 8 decode
    iterations: the flash and paged kernels are in the program, no pool-sized
    copy stands in it, no [T, Hm, P, N] temporary of the chunk's tokens, and
    the per-slot state is not copied whole beside itself (it rides the layer
    scan's carry and is written back a layer's slab at a time)."""
    import json
    import re
    from pathlib import Path

    from test_serving_fused import fused_chunk_operand_shapes
    from test_tpu_compiled import _pool_copy_offenders

    from jax_llama_tpu import config as config_mod, init_params, serving

    for name in ("flash_attention", "paged_attention"):
        monkeypatch.setattr(
            importlib.import_module(f"jax_llama_tpu.ops.{name}"),
            "_resolve_interpret", lambda _=None: False)
    raw = json.loads((Path(__file__).resolve().parent.parent / "benchmark" / "configs"
                      / "Falcon-H1-34B-Instruct.json").read_text())
    keys = {k: v for k, v in raw.items() if k not in (
        "source", "architecture", "reference", "reduced", "assumed", "deployment")}
    layers, rows, nb = 2, 32, 1024
    cfg = config_mod.from_published(
        dict(keys, num_hidden_layers=layers), max_seq_len=4096, attn_impl="auto")
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pool = place(jax.eval_shape(
        lambda: serving.init_pool(cfg, nb, 128, n_slots=rows, n_snapshots=63)))
    assert pool.k.shape == (layers, 4, nb, 128, 128)
    assert pool.ssm.shape == (layers, rows, 32, 128, 256)
    lowered = serving._fused_chunk.lower(
        params, pool, *fused_chunk_operand_shapes(sds, rows, 32, 512),
        sds((2,), jnp.int32),
        config=cfg, n_iter=8, pf_chunk=512, all_greedy=True, mesh=None,
        allow_kernel=True, with_logprobs=False,
    )
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 2   # flash and the paged kernel
    offenders = _pool_copy_offenders(text, pool.k.shape)
    assert not offenders, (len(offenders), offenders)
    # the recurrence a token of the chunk: [512, 32, 128, 256] in any order
    assert not [l[:140] for l in text.splitlines()
                if re.search(r"f32\[(1,)?(512,32,128,256|32,512,128,256|32,128,512,256)\]", l)]
    whole = rf"f32\[{layers},{rows},32,128,256\]"
    copies = [l.strip()[:140] for l in text.splitlines()
              if re.search(rf" = {whole}\S* copy\(", l)]
    assert not copies, copies


# --- the multi-stream residual's unit at its cell's shapes
# (xing4-freshdocs-asks): 4 streams of 3584 --------------------------------

@pytest.mark.parametrize("tokens", [16, 2048], ids=["decode-rows", "prompt-chunk"])
def test_a_streams_unit_compiles_for_v5e(sds, monkeypatch, tokens):
    """One mHC unit (`ops/mhc.py`) around a stand-in inner product at
    Xing4.0's widths: Sinkhorn's 20 rounds are ONE Mosaic call, and no
    float32 array of the stream's size stands in the compiled program."""
    import re

    from jax_llama_tpu.ops import mhc

    monkeypatch.setattr(mhc, "_resolve_interpret", lambda _=None: False)
    n, C = 4, 3584
    K = n * n + 2 * n

    def unit(X, hp, w):
        h_pre, h_post, h_res, stats = mhc.coefficients(
            X, hp, iters=20, eps=1e-6, clamp=(-30.0, 30.0))
        y = jnp.einsum("btc,cd->btd", mhc.pre(X, h_pre), w)
        return mhc.post(X, y, h_res, h_post), stats

    hp = {"phi": sds((n * C, K), jnp.float32), "b": sds((K,), jnp.float32),
          "alpha": sds((3,), jnp.float32)}
    text = jax.jit(unit).lower(
        sds((1, tokens, n, C), jnp.bfloat16), hp, sds((C, C), jnp.bfloat16)).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "hc_sinkhorn" in text
    # what a fusion, a copy or a product WRITES (a convert inside a fusion
    # lives in registers)
    wide = [line.strip()[:120] for line in text.splitlines()
            if re.match(rf"\s*(ROOT )?%\S+ = f32\[(1,)?({tokens},)?(4,)?({tokens},)?({C}|{n * C})\]", line)
            and re.search(r" (fusion|copy|convolution|custom-call)\(", line)]
    assert not wide, wide
