"""LLaMA model — pure-functional JAX, TPU-first.

Capability parity with the reference Flax model (``/root/reference/jax_llama/
model.py``): token embedding, pre-norm residual blocks (GQA attention with
RoPE + SwiGLU MLP), final RMSNorm, tied-or-untied LM head, fixed-size KV
cache for autoregressive decode.

Architectural departures (deliberate, TPU-first):
  * **No module framework, no HF shell.**  Params are a plain pytree of
    arrays; the forward pass is a function.  This keeps the decode engine a
    clean ``lax.while_loop`` over explicit state (the reference routes its
    cache through Flax mutable collections and HF's generation mixin,
    model.py:402-546).
  * **Stacked layer params + ``lax.scan``** instead of the reference's
    Python-unrolled block list (model.py:579-592): compile time is O(1) in
    depth — 80-layer Llama-3-70B traces as fast as the 4-layer test config.
  * **No materialized [1,1,S,S] causal mask** (reference model.py:154).
    Masking derives from per-slot absolute positions stored alongside the
    cache, which also subsumes the reference's left-pad handling
    (generation.py:55-60): pad slots carry position -1 and are never
    attended.
  * fp32 islands: RMSNorm statistics, RoPE rotation, softmax, and logits run
    in float32; matmuls run in the activation dtype (bf16 on TPU) with fp32
    MXU accumulation.

Param tree layout (all layers stacked on a leading L axis):

    {"embed":  {"embedding": [V, D]},
     "layers": {"attn_norm": [L, D],
                "qkv": [L, KVH, G+2, D, hd],   # G = H // KVH (GQA group)
                "o": [L, H, hd, D],
                "mlp_norm": [L, D],
                "gate_up": [L, 2, D, F], "down": [L, F, D]},
     "final_norm": [D],
     "lm_head": [D, V]}            # absent when tie_word_embeddings

The q/k/v projections are stored FUSED as one weight (and gate/up as
another): decode is HBM-bandwidth-bound, and one [D, KVH*(G+2)*hd]
matmul streams the same bytes as three separate ones but pays one
fusion's fixed cost instead of three and keeps the DMA pipeline in a
single long burst (xplane-measured: the three separate projections ran
at ~80% of the bandwidth roofline vs ~90%+ for the large MLP matmuls —
the reference also runs them separately,
``/root/reference/jax_llama/model.py:210-214``).  Slot layout along
the G+2 axis of ``qkv``: [q_0..q_{G-1}, k, v] per KV head, so the
merged query-head order is h = kvh*G + g — identical to the GQA packing
contract the flash/paged kernels already use, and tensor-parallelism
shards the KVH axis exactly like the separate layout did.

Axis ORDER within the fused weights is chosen for the layer scan, not
for reading aloud: ``qkv`` stores [KVH, G+2, D, hd] and ``gate_up``
[2, D, F] — the contracted D axis SECOND-from-last — because that is
the operand layout XLA:TPU assigns the decode matmuls.  With D leading
(the r3 layout) each ``lax.scan`` iteration's dynamic-slice of the
stacked weight relayouted into the matmul's layout: an xplane-profiled
~175us/step of pure weight-copy traffic (two kLoop relayout fusions per
layer step); with matching axis order the slice is a free view
(A/B-measured on chip, see ROADMAP).  ``fuse_params`` migrates both the
separate-q/k/v layout and the r3 D-first fused layout.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ..config import LLaMAConfig
from ..ops.attention import attention_bias, dropout as _dropout, sdpa, sdpa_cached
from ..ops.flash_attention import flash_attention, flash_attention_sharded
from ..ops.norm import rms_norm
from ..ops.quant import QuantizedTensor as _QuantizedTensor
from ..ops.quant import matmul as _quant_matmul
from ..ops.rope import apply_rope, rope_table
from ..parallel.mesh import constrain, current_mesh

Params = Dict[str, Any]


def qeinsum(
    x: jnp.ndarray,
    w: Any,
    eq: str,
    dtype: Optional[jnp.dtype] = None,
    preferred_element_type: Optional[jnp.dtype] = None,
) -> jnp.ndarray:
    """Projection einsum that transparently handles int8 weights.

    QuantizedTensor weights route through ``ops.quant.matmul`` (the
    int8 dequant-fused contraction); plain arrays run the einsum HERE
    so the xplane source attribution lands on this file.  Before this
    split, a per-source breakdown of device op time charged every bf16/fp32
    projection matmul to ``quant.py`` (the thin wrapper's frame), which
    made the breakdown's largest bucket unreadable — "quant.py
    2,572 µs/step" was the plain weight stream, not quantization work.
    Now ``quant.py`` in a trace means actual int8 dequant math.
    """
    dtype = dtype or x.dtype
    if isinstance(w, _QuantizedTensor):
        return _quant_matmul(x, w, eq, dtype, preferred_element_type)
    y = jnp.einsum(
        eq, x, w.astype(dtype),
        preferred_element_type=preferred_element_type,
    )
    return y if preferred_element_type else y.astype(dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v", "pos", "index", "k_scale", "v_scale", "stats",
                 "conv", "ssm", "idx"],
    meta_fields=[],
)
@dataclasses.dataclass
class KVCache:
    """Fixed-capacity per-layer KV cache with per-slot absolute positions.

    k, v:  [L, B, S_max, KVH, head_dim] — activation dtype, or int8 when
           the cache is quantized (config.kv_cache_dtype == "int8").
    pos:   [B, S_max] int32 — absolute position written into each slot;
           -1 marks an invalid (padding / unwritten) slot.
    index: int32 — next write offset: scalar (lockstep decode) or [B]
           vector (per-row offsets, continuous batching; xla path only).
    k_scale, v_scale: [L, B, S_max, KVH] fp32 per-slot-per-head dequant
           scales (int8 cache only; None otherwise).  Scales are constant
           along head_dim, so dequantization commutes with the attention
           contractions — sdpa_cached folds them into scores/weights and
           the int8 payload is never materialized at full precision.

    A cache is described by the planes it has.  Latent attention
    (``config.latent_attention``, models/mla_moe.py) keeps ONE plane: ``k``
    is [L, B, S_max, 1, kv_lora_rank + qk_rope_head_dim], the normed latent
    beside the rotated shared key, and ``v`` is None — nothing per head.
    stats: [ops.moe.N_STATS] int32 routing counts a forward adds to (routed
           experts only; None otherwise).

    ... and by the per-ROW state it has.  Recurrent state layers
    (``config.recurrent_state``, models/sambay.py) keep K/V planes for the
    ``config.cache_layers`` layers that own keys (``L`` above), and beside
    them ``conv`` [Ls, B, 3 * Di] (the mixers' last conv inputs) and ``ssm``
    [Ls, B, N, Di] float32 (their state-space state): fixed-size, advanced by
    a row's live tokens only, never paged.  None for every other block.

    Learned sparse attention (``config.sparse_attention``,
    models/dsa_moe.py) keeps a THIRD plane beside ``k`` and ``v``: ``idx``
    [L, B, S_max, 1, index_head_dim], the indexer's one key a token a layer
    (normed, its leading half rotated), by which a query ranks the keys it
    may attend.  None for every other block.
    """

    k: jnp.ndarray
    v: Optional[jnp.ndarray]
    pos: jnp.ndarray
    index: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    stats: Optional[jnp.ndarray] = None
    conv: Optional[jnp.ndarray] = None
    ssm: Optional[jnp.ndarray] = None
    idx: Optional[jnp.ndarray] = None

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def per_row_index(self) -> bool:
        """True when ``index`` is a [B] vector — each batch row writes at
        its own offset (continuous batching).  Scalar = classic lockstep
        decode.  Vector indices require the xla attention path."""
        return self.index.ndim == 1


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "k", "v", "pos", "table", "fill", "k_scale", "v_scale", "stats",
        "conv", "ssm", "idx",
    ],
    meta_fields=[],
)
@dataclasses.dataclass
class PagedKVCache:
    """Paged (block-table) KV cache for continuous-batching decode.

    The serving pool's own layout, consumed directly by ``paged_forward``
    via the Pallas paged-attention kernel (``ops.paged_attention``) — the
    kernel's index maps chase ``table``, so no gathered contiguous view
    is ever materialized.

    k, v:  [L, KVH, NB, BLK, head_dim] — KV-head-major so one
           (head, block) tile is a clean (BLK, head_dim) VMEM page;
           int8 when the pool is quantized.
    pos:   [NB, BLK] int32 absolute position per slot; -1 invalid.
    table: [B, MB] int32 physical block ids in sequence order; NB marks
           an unused entry.
    fill:  [B] int32 per-row next write offset in tokens (the host
           advances it after each step, like the gathered-view path).
    k_scale, v_scale: [L, KVH, NB, BLK] fp32 per-slot-per-head dequant
           scales (int8 pool only; None otherwise) — folded in-kernel.
    Latent attention: ``k`` is the one latent plane [L, 1, NB, BLK, w] and
    ``v`` is None; ``stats`` as in ``KVCache``.  Recurrent state layers:
    ``conv`` / ``ssm`` as in ``KVCache``, a row of them a table row.
    Learned sparse attention: ``idx`` [L, 1, NB, BLK, index_head_dim], the
    index-key plane under the same block table as ``k`` and ``v``.
    """

    k: jnp.ndarray
    v: Optional[jnp.ndarray]
    pos: jnp.ndarray
    table: jnp.ndarray
    fill: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    stats: Optional[jnp.ndarray] = None
    conv: Optional[jnp.ndarray] = None
    ssm: Optional[jnp.ndarray] = None
    idx: Optional[jnp.ndarray] = None

    @property
    def n_blocks(self) -> int:
        return self.k.shape[2]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def paged_write_indices(
    table: jnp.ndarray,      # [B, MB] physical block ids (sentinel = NB)
    fill: jnp.ndarray,       # [B] per-row write offset (tokens)
    active: jnp.ndarray,     # [B] bool
    T: int,
    n_blocks: int,
    block_size: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Physical (block, offset) pairs for landing T new per-row entries.

    THE paged write-back contract, shared by ``paged_forward`` and
    ``serving._scatter_back`` so the two paths cannot drift: row b's
    token j goes to block ``table[b, (fill[b]+j) // BLK]`` at offset
    ``(fill[b]+j) % BLK``; inactive rows and columns past the row's
    reserved capacity resolve to the sentinel block id ``n_blocks``
    (callers scatter with ``mode="drop"``).

    Returns (blk [B, T], off [B, T], cols [B, T]) int32 — ``cols`` is
    the clamped per-row view column each (blk, off) pair corresponds to,
    so callers that read values out of a virtually-contiguous view use
    the same clamping as the slot derivation.
    """
    MB = table.shape[1]
    cols = fill[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    safe = jnp.minimum(cols, MB * block_size - 1)
    blk = jnp.take_along_axis(table, safe // block_size, axis=1)
    blk = jnp.where(
        active[:, None] & (cols < MB * block_size), blk, n_blocks
    )
    return blk, safe % block_size, safe


def _remat(fn, config: LLaMAConfig):
    """Per-block rematerialization with the configured recompute policy.

    "dots" keeps matmul outputs (no batch-dim contractions = the QKV /
    attention / MLP projections) and recomputes only elementwise work in
    the backward pass — measured +13% train-step throughput over full
    recompute on chip (1B bf16, B=4 x S=2048, flash VJP) at a modest
    activation-memory cost; "full" recomputes everything (the reference's
    flag, `/root/reference/jax_llama/model.py:556-558`, maps to flax's
    equivalent full-remat transform — which nothing there exercises).
    """
    if config.remat_policy == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    return jax.checkpoint(fn)


# Above this many (row, token) pairs paged_pool_write switches from the
# unrolled dynamic_update_slice chain to the batched scatter — see its
# docstring for the measured crossover.
_POOL_WRITE_UNROLL_MAX = 256

# attn_impl="auto" resolves to the Pallas flash kernel only for blocks
# LONGER than this many tokens (decode-sized steps stay on the
# append-free xla path, where flash's one-row grid loses).  Exported
# because serving keeps HOST mirrors of the resolution — the classic
# batched-prefill flash gate and the fused prefill chunk's
# (serving._Prefill.flash) fault-site / quarantine attribution — which
# must never drift from what forward() actually runs.
FLASH_MIN_SEQ = 8


def _constrain_heads(x: Optional[jnp.ndarray], axis: int):
    """Pin one array's (KV-)head axis to ``tensor`` when the active
    mesh's tensor size divides it; no-op otherwise (no mesh, head
    count not divisible, tensor == 1).  Left unconstrained, GSPMD's
    propagation is free to resolve conflicts by REPLICATING cached KV
    operands — a full-pool/full-view all-gather inside every decode
    iteration, which the comms-budget contracts (analysis/comms.py)
    treat as a hard finding."""
    mesh = current_mesh()
    if mesh is None or x is None:
        return x
    tp = int(mesh.shape.get("tensor", 1))
    if tp <= 1 or x.shape[axis] % tp:
        return x
    names: list = [None] * x.ndim
    names[axis] = "tensor"
    return constrain(x, *names)


def _constrain_pool_plane(plane: jnp.ndarray) -> jnp.ndarray:
    """Pin a paged-pool KV plane ``[L, KVH, NB, BLK(, d)]`` to the
    serving placement's KV-head-over-``tensor`` sharding.  No-op
    without an active mesh, for 2-dim pos planes, and when ``tensor``
    does not divide the head axis (off-envelope meshes keep legacy
    propagation).  See :func:`_constrain_heads` for why."""
    if plane.ndim < 4:
        return plane
    return _constrain_heads(plane, 1)


def _pin_pool_layout(plane: jnp.ndarray) -> jnp.ndarray:
    """Pin a written KV plane to the default (row-major) device layout.

    Inside the chunk programs the pool is a ``lax.scan`` carry, whose
    layout the compiler is free to choose: XLA:TPU (jaxlib 0.9) picks the
    one the slab writes like — KVH next to d — for the whole loop, and
    since the Pallas paged-attention kernel's operand must be row-major
    it then copies BOTH full planes back on every decode iteration
    (compiled for a v5e at [16, 8, 64, 128, 128] bf16: six pool-sized
    copies in ``_paged_decode_chunk`` and a whole pool of temp memory;
    none with the pin — tests/test_tpu_compiled.py
    ``*_no_full_pool_copies_compiled``).  [NB, BLK] position planes are
    small and stay free."""
    if plane.ndim < 4:
        return plane
    return with_layout_constraint(
        plane, Layout(major_to_minor=tuple(range(plane.ndim)))
    )


def paged_pool_write(
    plane: jnp.ndarray,
    upd: jnp.ndarray,
    blk: jnp.ndarray,
    off: jnp.ndarray,
    rolled: bool = False,
) -> jnp.ndarray:
    """Land per-(row, token) pool updates via an unrolled chain of
    ``dynamic_update_slice`` ops instead of one batched scatter.

    Why not ``plane.at[:, :, blk, off].set(upd, mode="drop")``: XLA:TPU's
    scatter emitter assigns the [L, KVH, NB, BLK, d] operand a KVH-minor
    layout (the scattered [L, KVH, d] slabs become contiguous), and since
    the rest of the program — the Pallas paged-attention kernel included —
    wants the default layout, every decode step materialized FOUR
    full-pool layout copies (in + back, k and v): ~3.2 ms/step on the
    bench pool, dwarfing the attention kernel itself (xplane-measured,
    r4).  B*T unrolled dynamic_update_slices keep the pool in its default
    layout, update in place on the donated buffer, and move only the
    ~tens of KB actually being written.

    Drop semantics: ``paged_write_indices`` marks dead (row, token) pairs
    with the sentinel block id NB, which a scatter would drop but
    ``dynamic_update_slice`` silently CLAMPS.  Each update therefore
    re-reads the (clamped) target slab and selects it back for dead
    pairs — ``dynamic_slice`` clamps identically, so the dead write is an
    exact in-place no-op.

    Slot-count bound: the chain is B*T sequential ops — op count, trace
    and compile time all grow linearly, so past ``_POOL_WRITE_UNROLL_MAX``
    total (row, token) pairs this falls back to the batched scatter and
    eats its layout copies.  Measured on chip (bench pool, [16, 8, 64,
    128, 128] bf16, xplane device time): chain 0.86/1.11/1.97 ms at
    B*T = 8/64/256 vs scatter flat ~2.5 ms — crossover ~B*T = 360; the
    threshold sits below it because per-plane trace size (5 planes when
    int8) is the binding cost before device time is.

    Who takes which form: this PAIR form serves the writers that are per
    token or per row — the decode iteration (T=1: always the chain), the
    speculative verify, ``paged_forward`` and
    ``serving._paged_suffix_insert`` (off the steady window; a wide one
    falls to the scatter).  A writer whose entries are whole blocks of
    one row — ``serving._fused_chunk``'s prompt chunk — takes
    :func:`paged_pool_write_blocks` and never the scatter.

    ``rolled``: the same chain as a ``lax.fori_loop`` over the pairs —
    one traced step, not B*T.  For a SECOND chain in a program that
    already unrolls one (``mixed_forward`` beside the decode scan's body:
    16 rows x 3 planes unrolled again cost every ``_fused_chunk`` variant
    ~0.5 s more to trace and lower, 8 s of a cell's warm set-up; PERF.md
    section 6, PR 37).  Compiled for a v5e it leaves no pool-sized copy
    either (tests/test_chip_compile.py); on the device it is B*T loop
    trips of a few microseconds, once a dispatch.

    plane: [L, KVH, NB, BLK, d] payload, [L, KVH, NB, BLK] scale, or
      [NB, BLK] position plane — the (NB, BLK) axes sit at (-3, -2),
      (-2, -1) and (0, 1) respectively, derived from ndim.
    upd: matching [L, KVH, B, T, d] / [L, KVH, B, T] / [B, T].
    blk, off: [B, T] int32 physical coordinates (sentinel NB = drop).
    """
    with jax.named_scope("cache.write"):
        B, T = blk.shape
        plane = _constrain_pool_plane(plane)
        # The update slabs carry the same [L, KVH, ...] head axis: pin them
        # too, or their (replicated) sharding drags the slab re-reads — and
        # with them the whole plane — replicated through the `where`.
        upd = _constrain_pool_plane(upd)
        if B * T > _POOL_WRITE_UNROLL_MAX:
            # Batched scatter: mode="drop" discards the sentinel NB pairs,
            # matching the chain's contract exactly.
            if plane.ndim == 5 or plane.ndim == 4:
                return _constrain_pool_plane(plane.at[:, :, blk, off].set(
                    upd.astype(plane.dtype), mode="drop"
                ))
            return plane.at[blk, off].set(upd.astype(plane.dtype), mode="drop")
        if plane.ndim == 5:
            L, KVH, NB, BLK, d = plane.shape
            nb_ax, slab = 2, (L, KVH, 1, 1, d)
            pick = lambda b, t: upd[:, :, b, t][:, :, None, None, :]
        elif plane.ndim == 4:
            L, KVH, NB, BLK = plane.shape
            nb_ax, slab = 2, (L, KVH, 1, 1)
            pick = lambda b, t: upd[:, :, b, t][:, :, None, None]
        else:
            NB, BLK = plane.shape
            nb_ax, slab = 0, (1, 1)
            pick = lambda b, t: upd[b, t][None, None]
        live = blk < NB  # off is always in range (contract above)
        zero = jnp.int32(0)
        if rolled:
            def one(i, plane):
                b, t = i // T, i % T
                at = lambda *bt: (
                    (zero,) * nb_ax + bt + (zero,) * (plane.ndim - nb_ax - 2)
                )
                start = at(blk[b, t], off[b, t])
                cur = lax.dynamic_slice(plane, start, slab)
                new = lax.dynamic_slice(upd, at(b, t), slab)
                u = jnp.where(live[b, t], new.astype(plane.dtype), cur)
                return _constrain_pool_plane(
                    lax.dynamic_update_slice(plane, u, start)
                )

            return _pin_pool_layout(lax.fori_loop(0, B * T, one, plane))
        for b in range(B):
            for t in range(T):
                start = (
                    (zero,) * nb_ax + (blk[b, t], off[b, t])
                    + (zero,) * (plane.ndim - nb_ax - 2)
                )
                cur = lax.dynamic_slice(plane, start, slab)
                u = jnp.where(live[b, t], pick(b, t).astype(plane.dtype), cur)
                plane = _constrain_pool_plane(
                    lax.dynamic_update_slice(plane, u, start)
                )
        return _pin_pool_layout(plane)


def paged_pool_write_blocks(
    plane: jnp.ndarray,
    upd: jnp.ndarray,
    blk: jnp.ndarray,
) -> jnp.ndarray:
    """Land ``n`` WHOLE blocks: one ``dynamic_update_slice`` of a
    ``[L, KVH, 1, BLK, d]`` slab a block, a chain of ``n`` (static).

    The block form of :func:`paged_pool_write` for a writer whose new
    entries are whole, block-aligned blocks of one row — the prompt chunk
    of ``serving._fused_chunk`` (``_pf_chunk`` hands out whole blocks and
    a chunk walk starts on a block boundary).  ``C`` tokens are ``C //
    BLK`` slabs, not ``C`` (block, offset) pairs: no batched scatter at
    any chunk size, so the pool keeps its row-major layout and none of
    the scatter's four pool-sized relayout copies appear (compiled for a
    v5e at [L, 8, 256, 128, 128] bf16, ``pf_chunk`` 512: four with the
    pair form's scatter, none here — tests/test_chip_compile.py).

    Drop semantics are the pair chain's: a dead block carries the
    sentinel id NB (past the row's reservation, past the table's last
    column), ``dynamic_update_slice`` would CLAMP it onto block NB - 1,
    so each write re-reads the (identically clamped) target slab and
    selects it back — an exact in-place no-op on whatever row owns that
    block.

    plane: [L, KVH, NB, BLK, d] payload, [L, KVH, NB, BLK] scale, or
      [NB, BLK] position plane.
    upd: matching [L, KVH, n, BLK, d] / [L, KVH, n, BLK] / [n, BLK].
    blk: [n] int32 physical block ids (sentinel NB = drop).
    """
    with jax.named_scope("cache.write"):
        (n,) = blk.shape
        plane = _constrain_pool_plane(plane)
        upd = _constrain_pool_plane(upd)  # see paged_pool_write
        nb_ax = 0 if plane.ndim == 2 else 2
        live = blk < plane.shape[nb_ax]
        zero = jnp.int32(0)
        for j in range(n):
            start = (
                (zero,) * nb_ax + (blk[j],)
                + (zero,) * (plane.ndim - nb_ax - 1)
            )
            new = lax.slice_in_dim(upd, j, j + 1, axis=nb_ax)
            cur = lax.dynamic_slice(plane, start, new.shape)
            u = jnp.where(live[j], new.astype(plane.dtype), cur)
            plane = _constrain_pool_plane(
                lax.dynamic_update_slice(plane, u, start)
            )
        return _pin_pool_layout(plane)


def layer_scan(body, carry, xs, **kwargs):
    """``lax.scan`` over a stack of layers — every block's — under the
    scope ``layers``: what the scan itself does a layer (the slices of
    the stacked weights and cache planes it hands ``body``, the stacking
    of what ``body`` returns) and what a layer does outside its
    sub-blocks' own scopes (norms, residual adds) read under it; the
    sub-blocks keep theirs, which are innermost."""
    with jax.named_scope("layers"):
        return lax.scan(body, carry, xs, **kwargs)


def embed_tokens(params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    """The token embedding lookup of every block's forward, [..., D] in
    the table's dtype, under the scope ``embed``."""
    with jax.named_scope("embed"):
        return jnp.take(params["embed"]["embedding"], tokens, axis=0)


def lm_head_logits(
    params: Params, x: jnp.ndarray, config: LLaMAConfig, normed: bool = False
) -> jnp.ndarray:
    """Final RMSNorm + (tied or untied) LM head — the one logits path
    every forward variant shares.  x: [B, T, D] -> [B, T, V] in
    config.logits_dtype (fp32 island, reference model.py:732-736).
    ``normed=True`` means x is already the post-final-norm hidden state
    (callers that also emit it as an aux output norm exactly once)."""
    with jax.named_scope("head"):
        if not normed:
            x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
        if config.tie_word_embeddings:
            kernel = params["embed"]["embedding"].T
        else:
            kernel = params["lm_head"]
        logits = qeinsum(
            x, kernel, "btd,dv->btv", config.activation_dtype,
            preferred_element_type=jnp.dtype(config.logits_dtype),
        ).astype(config.logits_dtype)
        if config.lm_head_multiplier != 1.0:
            logits = logits * config.lm_head_multiplier
    return constrain(logits, "data", "seq", "tensor")


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 over the trailing head_dim: x [..., hd] ->
    (int8 [..., hd], fp32 scale [...]).

    Every int8-KV path quantizes INCREMENTALLY with this function — only
    the step's newly appended projections ([L, B, T, KVH, hd]; T=1 in
    decode) ever pass through it, with their per-slot-per-head scales
    cached alongside the int8 payload (KVCache.k_scale / BlockPool
    scale planes).  The stored pool is never round-tripped through
    re-quantization: attention folds the cached scales at the
    scores/probability level (sdpa_cached, flash/paged kernels) so the
    int8 bytes stream from HBM untouched.  The single fp32 cast below is
    shared by the amax and the rounding (one materialization, not two).
    """
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127).astype(
        jnp.int8
    )
    return q, scale


def cache_stats_zero(config: LLaMAConfig) -> Optional[jnp.ndarray]:
    """Empty counters of a cache or a pool: the routing counts
    (``ops.moe.STATS``) of a block with routed experts, then the window
    block's attention step counts (``afmoe.ATTN_STATS``); None for the
    dense block, which counts nothing on the device.  The sparse-attention
    block appends its selection counts (``dsa_moe.SELECT_STATS``); the latent
    block with a multi-stream residual its units' (``ops.mhc.STATS``) right
    behind the routing counts."""
    if config.sparse_attention:
        from .dsa_moe import N_STATS

        return jnp.zeros((N_STATS,), jnp.int32)
    if config.windowed_attention or config.recurrent_state:
        # The recurrent block counts its attention steps in the window
        # block's layout; its routing counts stay zero.
        from .afmoe import N_STATS

        return jnp.zeros((N_STATS,), jnp.int32)
    if config.latent_attention:
        from .mla_moe import n_stats

        return jnp.zeros((n_stats(config),), jnp.int32)
    return None


def init_state(config: LLaMAConfig, rows: int) -> Tuple[jnp.ndarray, ...]:
    """Empty per-row recurrent state of `rows` rows, by
    `config.state_shapes`: (`conv` [Ls, rows, ...], `ssm` [Ls, rows, ...]
    float32) for a block with recurrent layers, () for every other."""
    return tuple(
        jnp.zeros((config.state_layers, rows) + shape, jnp.dtype(dtype))
        for _, shape, dtype in config.state_shapes)


def init_cache(
    config: LLaMAConfig,
    batch: int,
    max_len: Optional[int] = None,
    dtype: Optional[jnp.dtype] = None,
) -> KVCache:
    """Allocate an empty cache (parity: reference ``init_cache``,
    model.py:459-476 — but as a plain pytree, not a Flax collection)."""
    config.validate()
    max_len = max_len or config.max_seq_len
    int8_kv = config.kv_cache_dtype == "int8" and dtype is None
    dtype = jnp.int8 if int8_kv else (dtype or config.activation_dtype)
    shape = (
        config.cache_layers, batch, max_len, config.cache_heads,
        config.cache_width,
    )
    latent = config.latent_attention
    return KVCache(
        **dict(zip(("conv", "ssm"), init_state(config, batch))),
        k=jnp.zeros(shape, dtype=dtype),
        v=None if latent else jnp.zeros(shape, dtype=dtype),
        stats=cache_stats_zero(config),
        pos=jnp.full((batch, max_len), -1, dtype=jnp.int32),
        index=jnp.zeros((), dtype=jnp.int32),
        k_scale=jnp.zeros(shape[:-1], jnp.float32) if int8_kv else None,
        v_scale=jnp.zeros(shape[:-1], jnp.float32) if int8_kv else None,
        idx=(jnp.zeros(shape[:3] + (1, config.index_head_dim), dtype)
             if config.sparse_attention else None),
    )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, config: LLaMAConfig) -> Params:
    """Random init matching standard LLaMA scaling (normal, 0.02 std for
    embeddings; Lecun-style fan-in scaling for projections).  The block
    follows from the configuration (see ``forward``)."""
    config.validate()
    if config.sparse_attention:
        from . import dsa_moe

        return dsa_moe.init_params(rng, config)
    if config.latent_attention:
        from . import mla_moe

        return mla_moe.init_params(rng, config)
    if config.windowed_attention:
        from . import afmoe

        return afmoe.init_params(rng, config)
    if config.parallel_mixer:
        from . import falcon_h1

        return falcon_h1.init_params(rng, config)
    if config.recurrent_state:
        from . import sambay

        return sambay.init_params(rng, config)
    D, H, KVH, hd, F, V, L = (
        config.dim, config.n_heads, config.kv_heads, config.head_dim,
        config.ffn_dim, config.vocab_size, config.n_layers,
    )
    wd = config.weight_dtype
    keys = jax.random.split(rng, 10)

    def dense(key, shape, fan_in):
        scale = fan_in ** -0.5
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(wd)

    def stacked(key, shape, fan_in):
        return dense(key, (L,) + shape, fan_in)

    G = H // KVH
    params: Params = {
        "embed": {
            "embedding": (
                jax.random.normal(keys[0], (V, D), dtype=jnp.float32) * 0.02
            ).astype(wd)
        },
        "layers": {
            "attn_norm": jnp.ones((L, D), dtype=wd),
            "qkv": stacked(keys[1], (KVH, G + 2, D, hd), D),
            "o": stacked(keys[4], (H, hd, D), D),
            "mlp_norm": jnp.ones((L, D), dtype=wd),
            "gate_up": stacked(keys[5], (2, D, F), D),
            "down": stacked(keys[7], (F, D), F),
        },
        "final_norm": jnp.ones((D,), dtype=wd),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = dense(keys[8], (D, V), D)
    return params


def rope_permute(w: jnp.ndarray, inverse: bool = False) -> jnp.ndarray:
    """Permute a projection weight's trailing head_dim axis between Meta's
    interleaved RoPE feature order and the runtime half-split order
    (``ops.rope`` module docstring): forward maps Meta feature 2i -> i and
    2i+1 -> i + hd/2, so ``apply_rope``'s contiguous-half rotation equals
    the reference's interleaved complex rotation exactly.  Works on any
    array whose LAST axis is head_dim (numpy or jax)."""
    *lead, hd = w.shape
    if inverse:
        # [.., hd] viewed [.., 2, hd/2] -> swap -> [.., hd/2, 2] -> flat
        return w.reshape(*lead, 2, hd // 2).swapaxes(-1, -2).reshape(w.shape)
    return w.reshape(*lead, hd // 2, 2).swapaxes(-1, -2).reshape(w.shape)


def fuse_qkv(
    q: jnp.ndarray,  # [L, D, H, hd] (or [D, H, hd]), Meta feature order
    k: jnp.ndarray,  # [L, D, KVH, hd]
    v: jnp.ndarray,  # [L, D, KVH, hd]
) -> jnp.ndarray:
    """Pack separate q/k/v projection weights (Meta interleaved-RoPE
    feature order) into the fused [..., KVH, G+2, D, hd] runtime layout:
    slots [q_0..q_{G-1}, k, v] per KV head (query head order h = kvh*G +
    g, the kernels' GQA contract), with q/k head_dim features permuted to
    the half-split RoPE order (``rope_permute``; v is not rotated and
    keeps Meta order).  D sits second-from-last (see module docstring:
    the scan-slice layout contract)."""
    *lead, D, H, hd = q.shape
    KVH = k.shape[-2]
    G = H // KVH
    qg = jnp.moveaxis(
        rope_permute(q).reshape(*lead, D, KVH, G, hd), -4, -2
    )  # [..., KVH, G, D, hd]
    kk = jnp.swapaxes(rope_permute(k), -3, -2)[..., :, None, :, :]
    vv = jnp.swapaxes(v, -3, -2)[..., :, None, :, :]
    return jnp.concatenate([qg, kk, vv], axis=-3)


def split_qkv(
    qkv: jnp.ndarray,  # [..., KVH, G+2, D, hd]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Inverse of ``fuse_qkv``: (q [..., D, H, hd], k, v [..., D, KVH, hd])
    in Meta interleaved-RoPE feature order."""
    *lead, KVH, g2, D, hd = qkv.shape
    G = g2 - 2
    q = jnp.moveaxis(qkv[..., :G, :, :], -2, -4).reshape(
        *lead, D, KVH * G, hd
    )
    return (
        rope_permute(q, inverse=True),
        rope_permute(jnp.swapaxes(qkv[..., G, :, :], -3, -2), inverse=True),
        jnp.swapaxes(qkv[..., G + 1, :, :], -3, -2),
    )


def permute_d_axis(lp: Dict[str, Any], to_d_first: bool) -> Dict[str, Any]:
    """THE current-layout <-> r3 D-first axis contract, in one place
    (qkv: D between -2 and -4; gate_up: D between -2 and -3) — used by
    ``fuse_params`` and the checkpoint restore-time migration.
    QuantizedTensor leaves permute payload AND scale together (the scale
    keeps size-1 contracted dims in the same axis positions, so the
    transform is exact for int8 trees too)."""
    from ..ops.quant import QuantizedTensor

    def mv(x, src, dst):
        if isinstance(x, QuantizedTensor):
            return QuantizedTensor(
                q=jnp.moveaxis(x.q, src, dst),
                scale=jnp.moveaxis(x.scale, src, dst),
            )
        return jnp.moveaxis(x, src, dst)

    lp = dict(lp)
    if to_d_first:
        lp["qkv"] = mv(lp["qkv"], -2, -4)
        lp["gate_up"] = mv(lp["gate_up"], -2, -3)
    else:
        lp["qkv"] = mv(lp["qkv"], -4, -2)
        lp["gate_up"] = mv(lp["gate_up"], -3, -2)
    return lp


def fuse_params(params: Params) -> Params:
    """Migrate an old-layout param tree to the current fused layout:
    either separate q/k/v + gate/up (rounds 1-2 Orbax checkpoints) or the
    r3 D-first fused layout (qkv [L, D, KVH, G+2, hd], gate_up
    [L, D, 2, F]).  No-op when already current.  Quantized trees must be
    re-quantized from the full-precision source instead (scales do not
    concatenate)."""
    lp = dict(params["layers"])
    if "qkv" in lp:
        d_model = lp["attn_norm"].shape[-1]
        if (lp["qkv"].shape[-4] == d_model
                and lp["gate_up"].shape[-3] == d_model):
            # r3 D-first fused layout: move D to second-from-last.
            # (D == KVH cannot alias: KVH is a head count, D the model dim.)
            out = dict(params)
            out["layers"] = permute_d_axis(lp, to_d_first=False)
            return out
        return params
    lp["qkv"] = fuse_qkv(lp.pop("q"), lp.pop("k"), lp.pop("v"))
    lp["gate_up"] = jnp.stack([lp.pop("gate"), lp.pop("up")], axis=-3)
    out = dict(params)
    out["layers"] = lp
    return out


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _swiglu(h: jnp.ndarray, gate_up: Any, down: Any) -> jnp.ndarray:
    """SwiGLU of normed hidden states h [B, T, D] through a fused
    gate_up [2, D, F] and down [F, D] — the dense block's FFN, a leading
    dense layer's and a shared expert's (models/mla_moe.py)."""
    adt = h.dtype
    gu = qeinsum(h, gate_up, "btd,cdf->btcf", adt)
    gu = constrain(gu, "data", "seq", None, "tensor")
    hidden = jax.nn.silu(gu[..., 0, :]) * gu[..., 1, :]
    out = qeinsum(hidden, down, "btf,fd->btd", adt)
    return constrain(out, "data", "seq", None)


@functools.lru_cache(maxsize=8)
def _rope_tables(head_dim: int, max_positions: int, theta: float, scaled: bool):
    return rope_table(head_dim, max_positions, theta, use_scaled_rope=scaled)


def _block(
    x: jnp.ndarray,
    lp: Dict[str, jnp.ndarray],
    cache_k: Optional[jnp.ndarray],
    cache_v: Optional[jnp.ndarray],
    cache_k_scale: Optional[jnp.ndarray] = None,
    cache_v_scale: Optional[jnp.ndarray] = None,
    dropout_rng: Optional[jax.Array] = None,
    *,
    config: LLaMAConfig,
    positions: jnp.ndarray,
    bias: Optional[jnp.ndarray],
    slot_pos: jnp.ndarray,
    cache_index: Optional[jnp.ndarray],
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    bias_new: Optional[jnp.ndarray] = None,
    impl: str = "xla",
    paged_pos: Optional[jnp.ndarray] = None,
    paged_table: Optional[jnp.ndarray] = None,
    paged_qpos: Optional[jnp.ndarray] = None,
    paged_pools: Optional[Tuple[jnp.ndarray, ...]] = None,
    paged_layer: Optional[jnp.ndarray] = None,
    ring_new_pos: Optional[jnp.ndarray] = None,
    output_attentions: bool = False,
    n_riders: int = 0,
) -> Tuple[jnp.ndarray, ...]:
    """One pre-norm transformer block. x: [B, T, D].  ``impl`` is the
    RESOLVED attention implementation (forward maps "auto" to "flash" or
    "xla" per call based on T).

    ``n_riders`` > 0 (``mixed_forward``): x is [1, T, D] and its last
    ``n_riders`` rows are decode rows riding a prompt chunk's pass over
    the weights.  Norms, the QKV product, rope, the output product and
    the FFN see one activation; only attention splits — the chunk's rows
    take ``impl`` over ``cache_k`` / ``cache_v`` as below, the riders take
    the paged kernel over ``paged_pools`` — and the riders' projections
    [n_riders, 1, KVH, hd] are appended to the result.

    Returns (x, cache_k, cache_v, cache_k_scale, cache_v_scale), plus a
    trailing [B, H, T, S] post-softmax probability array when
    ``output_attentions`` (xla path only — the flash/ring/paged kernels
    never materialize the weights; forward routes accordingly).  On the
    xla cached path cache_k/v are just this step's new projections (the
    caller writes them once, outside the layer scan) and the scales pass
    through untouched; on the flash cached path they are the fully
    updated per-layer cache (+ updated scales when int8)."""
    B, T, D = x.shape
    adt = x.dtype
    if output_attentions and impl != "xla":
        raise NotImplementedError(
            f"output_attentions requires the xla attention path "
            f"(got impl={impl!r}); forward() forces it when asked"
        )
    attn_weights = None

    # --- attention ---
    with jax.named_scope("dense.attention"):
        h = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        # One fused QKV matmul (see module docstring): [B,T,KVH,G+2,hd],
        # slots [q_0..q_{G-1}, k, v] per KV head.  Sharded over KVH on
        # "tensor", so the slice/reshape below are shard-local.
        G = config.n_heads // config.kv_heads
        qkv = qeinsum(h, lp["qkv"], "btd,cgdk->btcgk", adt)
        qkv = constrain(qkv, "data", "seq", "tensor", None, None)
        q = qkv[..., :G, :].reshape(B, T, config.n_heads, config.head_dim)
        k = qkv[..., G, :]
        v = qkv[..., G + 1, :]
        q = constrain(q, "data", "seq", "tensor", None)
        k = constrain(k, "data", "seq", "tensor", None)
        v = constrain(v, "data", "seq", "tensor", None)

        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)

        if n_riders:
            from ..ops.paged_attention import paged_decode_attention

            def rows(a):  # [1, T, h, hd] -> the riders', [n_riders, 1, h, hd]
                return jnp.swapaxes(a[:, T - n_riders:], 0, 1)

            rider_k, rider_v = rows(k), rows(v)
            rider_attn = paged_decode_attention(
                rows(q), rider_k, rider_v, *paged_pools[:2], paged_pos,
                paged_table, paged_qpos, layer=paged_layer,
            )
            q, k, v, positions = (
                a[:, :T - n_riders] for a in (q, k, v, positions)
            )

        softmax_dtype = jnp.dtype(config.attn_softmax_dtype)
        if cache_k is not None and impl == "ring_decode":
            # Seq-sharded cached decode: the cache never moves (each seq
            # shard reduces its own slots; one pmax + two psums combine) and
            # stays immutable through the layer scan — same append-free
            # contract as the xla path below.  ``slot_pos`` here is the
            # PRE-step cache positions; the step's own tokens merge at the
            # softmax level inside ring_decode via ``ring_new_pos``.
            from ..parallel.ring import ring_decode

            if cache_k_scale is not None:
                # int8 seq-sharded cache: payload + scales stay int8/fp32 in
                # HBM, sharded along S; scales fold per shard inside the body.
                attn = ring_decode(
                    q, cache_k, cache_v, slot_pos, k, v, positions,
                    ring_new_pos, softmax_dtype=softmax_dtype,
                    k_scale=cache_k_scale, v_scale=cache_v_scale,
                )
            else:
                attn = ring_decode(
                    q, cache_k.astype(adt), cache_v.astype(adt), slot_pos,
                    k, v, positions, ring_new_pos, softmax_dtype=softmax_dtype,
                )
            cache_k, cache_v = k, v
        elif cache_k is not None and impl == "xla":
            # Append-free decode: the cache stays immutable through the layer
            # scan; sdpa_cached softmaxes jointly over (cache slots, new
            # tokens) at the scores level, and the caller applies ONE in-place
            # dynamic-update-slice per step after the scan.  Mutating the
            # cache per layer inside scan/while forced XLA into a full-cache
            # double-buffer copy every decode step.  GQA replication stays
            # inside the attention op, after the cache (parity with reference
            # model.py:269-270).  ``bias`` masks the cache (unwritten slots
            # carry pos -1), ``bias_new`` masks/causes the new tokens.
            if cache_k_scale is not None:
                attn = sdpa_cached(
                    q, cache_k, cache_v, k, v, bias, bias_new,
                    softmax_dtype=softmax_dtype,
                    k_scale=cache_k_scale, v_scale=cache_v_scale,
                    return_weights=output_attentions,
                )
            else:
                attn = sdpa_cached(
                    q, cache_k.astype(adt), cache_v.astype(adt), k, v,
                    bias, bias_new, softmax_dtype=softmax_dtype,
                    return_weights=output_attentions,
                )
            if output_attentions:
                attn, attn_weights = attn
            # ys: just this step's projections; forward writes them into the
            # cache once, outside the scan.
            cache_k, cache_v = k, v
        elif impl == "paged":
            # Paged decode: ``paged_pools`` is the FULL [L, KVH, NB, BLK, hd]
            # block pool (+ scales when int8) bound once outside the layer
            # scan, and ``paged_layer`` (the scan's loop index) selects the
            # plane inside the kernel's index maps — slicing pool[i] here
            # would materialize each layer's whole plane as the custom-call
            # operand, ~3x the kernel's own time at 16k contexts (r4,
            # xplane).  The new token's slot merges at the softmax level.
            # Pool stays immutable through the scan — paged_forward scatters
            # the ys once per step.  int8 pools fold their scales in-kernel;
            # the step's projections get quantized for the scatter but merge
            # at full precision (matching sdpa_cached's treatment of
            # same-step tokens).
            pool_k, pool_v, pool_ks, pool_vs = paged_pools
            from ..ops.paged_attention import paged_decode_attention

            attn = paged_decode_attention(
                q, k, v, pool_k, pool_v, paged_pos, paged_table,
                paged_qpos, k_scale=pool_ks, v_scale=pool_vs,
                layer=paged_layer,
            )
            if pool_ks is not None:
                k, cache_k_scale = quantize_kv(k)
                v, cache_v_scale = quantize_kv(v)
            cache_k, cache_v = k, v
        elif cache_k is not None and cache_k_scale is not None:
            # int8 cache on the flash path: quantize this chunk's projections,
            # land payload + scales at [cache_index, cache_index+T), and
            # attend the whole cache with in-kernel scale folding — the int8
            # bytes stream straight from HBM, never dequantized in memory.
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            cache_k = lax.dynamic_update_slice(
                cache_k, kq, (0, cache_index, 0, 0)
            )
            cache_v = lax.dynamic_update_slice(
                cache_v, vq, (0, cache_index, 0, 0)
            )
            cache_k_scale = lax.dynamic_update_slice(
                cache_k_scale, ks, (0, cache_index, 0)
            )
            cache_v_scale = lax.dynamic_update_slice(
                cache_v_scale, vs, (0, cache_index, 0)
            )
            attn = flash_attention_sharded(
                q, cache_k, cache_v, positions, slot_pos,
                k_scale=cache_k_scale, v_scale=cache_v_scale,
            )
        else:
            if cache_k is not None:
                # Flash path: write the T new KV entries at
                # [cache_index, cache_index+T), then attend the full cache.
                cache_k = lax.dynamic_update_slice(
                    cache_k, k.astype(cache_k.dtype), (0, cache_index, 0, 0)
                )
                cache_v = lax.dynamic_update_slice(
                    cache_v, v.astype(cache_v.dtype), (0, cache_index, 0, 0)
                )
                kk, vv = cache_k.astype(adt), cache_v.astype(adt)
            else:
                kk, vv = k, v
            if impl == "ring" and cache_k is None:
                # Sequence-parallel path (training / scoring / cache-free
                # prefill): ring over the seq mesh axis.  attn_pdrop composes:
                # the mask is a position-keyed counter hash (ring.dropout_keep)
                # — invariant to chunking and ring layout by construction.
                from ..parallel.ring import ring_sdpa

                attn = ring_sdpa(
                    q, kk, vv, positions, slot_pos,
                    dropout_rng=(
                        jax.random.fold_in(dropout_rng, 0)
                        if dropout_rng is not None and config.attn_pdrop > 0.0
                        else None
                    ),
                    dropout_rate=config.attn_pdrop,
                )
            elif impl in ("flash", "ring"):
                if dropout_rng is not None and config.attn_pdrop > 0.0:
                    # In-kernel probability dropout: the mask is generated
                    # blockwise inside the flash forward AND rebuilt
                    # bit-identically in the backward kernels — O(S·d) memory
                    # stands, so attention-dropout training works at long
                    # context (the xla path materializes [B, H, T, S]).
                    attn = flash_attention(
                        q, kk, vv, positions, slot_pos,
                        dropout_rate=config.attn_pdrop,
                        dropout_seed=jax.random.bits(
                            jax.random.fold_in(dropout_rng, 0), (2,), "uint32"
                        ),
                    )
                else:
                    attn = flash_attention_sharded(
                        q, kk, vv, positions, slot_pos
                    )
            else:
                attn = sdpa(
                    q, kk, vv, bias, softmax_dtype=softmax_dtype,
                    dropout_rng=(
                        jax.random.fold_in(dropout_rng, 0)
                        if dropout_rng is not None and config.attn_pdrop > 0.0
                        else None
                    ),
                    dropout_rate=config.attn_pdrop,
                    return_weights=output_attentions,
                )
                if output_attentions:
                    attn, attn_weights = attn

        if n_riders:
            attn = jnp.concatenate(
                [attn, jnp.swapaxes(rider_attn, 0, 1)], axis=1
            )
        attn_out = qeinsum(attn, lp["o"], "bthk,hkd->btd", adt)
        attn_out = constrain(attn_out, "data", "seq", None)
    if dropout_rng is not None and config.resid_pdrop > 0.0:
        attn_out = _dropout(
            jax.random.fold_in(dropout_rng, 1), attn_out, config.resid_pdrop
        )
    x = x + attn_out

    # --- SwiGLU MLP (fused gate+up matmul: one weight stream, one
    # fusion — the F axis stays "tensor"-sharded like the separate
    # layout) ---
    h = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
    with jax.named_scope("dense.ffn"):
        down = _swiglu(h, lp["gate_up"], lp["down"])
    if dropout_rng is not None and config.resid_pdrop > 0.0:
        down = _dropout(
            jax.random.fold_in(dropout_rng, 2), down, config.resid_pdrop
        )
    x = x + down
    if output_attentions:
        return x, cache_k, cache_v, cache_k_scale, cache_v_scale, attn_weights
    if n_riders:
        return (x, cache_k, cache_v, cache_k_scale, cache_v_scale,
                rider_k, rider_v)
    return x, cache_k, cache_v, cache_k_scale, cache_v_scale


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["hidden_states", "last_hidden_state", "attentions"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class AuxOutput:
    """Optional eval/interp outputs of ``forward`` — capability parity
    with the reference's ``output_hidden_states`` / ``output_attentions``
    (reference model.py:488-494) and its head-less ``FlaxLLaMAModel``
    (model.py:745).

    hidden_states: [L+1, B, T, D] (or None).  Entries 0..L-1 are each
      block's INPUT (entry 0 = the embedding output), entry L is the
      POST-final-norm hidden state — the reference's exact collection
      points (model.py:580-581 per-block, :663-666 final norm appended).
      Stacked into one array rather than a Python tuple: TPU-idiomatic
      (one transfer), and ``aux.hidden_states[i]`` reads the same way.
    last_hidden_state: [B, T, D] post-final-norm hidden state
      (== hidden_states[-1]) — what the reference's base model without
      the LM head returns.  Present whenever aux is requested, so
      ``forward(..., compute_logits=False, output_hidden_states=True)``
      IS the head-less model call.
    attentions: [L, B, H, T, S] post-softmax attention probabilities
      (or None unless ``output_attentions``).  S spans the cache slots
      then the step's new tokens on the cached path.
    """

    hidden_states: Optional[jnp.ndarray]
    last_hidden_state: jnp.ndarray
    attentions: Optional[jnp.ndarray]


def forward(
    params: Params,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    config: LLaMAConfig,
    cache: Optional[KVCache] = None,
    attn_mask: Optional[jnp.ndarray] = None,
    compute_logits: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    output_hidden_states: bool = False,
    output_attentions: bool = False,
    output_last_hidden: bool = False,
):
    """Run the transformer.

    Args:
      params: pytree from `init_params` / the checkpoint loader.
      tokens: [B, T] int32 token ids.
      positions: [B, T] int32 absolute positions.  Padding tokens carry -1;
        they are clamped to 0 for RoPE/query purposes and recorded as -1
        (permanently masked) in the cache.
      config: model config.
      cache: optional KVCache.  When given, the T tokens are appended at
        `cache.index` and attention runs over the whole cache; when None,
        plain causal attention over the T tokens (training / parity path).
        Callers must keep `cache.index + T <= cache.max_len`:
        `dynamic_update_slice` clamps out-of-range writes silently (the
        decode engine enforces this bound statically).
      attn_mask: optional [B, T] bool, False for padding.  Defaults to
        positions >= 0.
      compute_logits: False skips final-norm + lm_head and returns
        (None, cache) — for cache-building forwards (e.g. non-final
        prefill chunks) whose [B, T, V] fp32 logits would be thrown away.
      dropout_rng: optional PRNG key enabling dropout (training only —
        requires cache=None) at the config's embd/resid/attn_pdrop rates
        (reference capability: config.py:85-87, model.py:166-168,296-299).
        None, or all rates zero, means fully deterministic.
      output_hidden_states / output_attentions: ALSO return an
        ``AuxOutput`` (see its docstring) — the eval/interp/debug
        surface, parity with the reference's flags (model.py:488-494).
        The layer stack unrolls for the collection (compile time O(L),
        per-layer arrays are real outputs — not the hot path), and
        ``output_attentions`` forces the xla attention path (the
        flash/ring/paged kernels never materialize the [B, H, T, S]
        weights; the xla path is the one that computes them anyway).
        Not supported on paged caches (a serving path) or stage > 1
        (pipeline) meshes.
      output_last_hidden: ALSO return an ``AuxOutput`` holding ONLY
        ``last_hidden_state`` (post-final-norm [B, T, D]).  Unlike the
        collect flags above this is a hot-path surface: the scan stack
        (and the pipeline stack) runs unchanged — nothing per-layer is
        stacked — so the fused training loss uses it with
        ``compute_logits=False`` to take the head matmul chunkwise
        (``ops.loss``) instead of materializing [B, T, V] logits.
        Subsumed by the collect flags when both are set.
    Returns:
      (logits [B, T, V] in config.logits_dtype, updated cache or None);
      logits is None when compute_logits=False.  When any output
      flag is set, a third ``AuxOutput`` element is appended:
      (logits, cache, aux).
    """
    if (config.latent_attention or config.windowed_attention
            or config.recurrent_state or config.sparse_attention):
        # The block follows from the configuration: latent attention over
        # a latent cache, window and full attention layers over the K/V
        # cache (routed experts behind leading dense layers), or recurrent
        # state layers beside window / full / cross attention, or a mixer
        # beside attention in every layer, or a learned key selection
        # inside attention over routed experts.
        from . import afmoe, dsa_moe, falcon_h1, mla_moe, sambay

        block = (dsa_moe if config.sparse_attention
                 else falcon_h1 if config.parallel_mixer
                 else sambay if config.recurrent_state
                 else mla_moe if config.latent_attention else afmoe)
        return block.forward(
            params, tokens, positions, config, cache=cache,
            attn_mask=attn_mask, compute_logits=compute_logits,
            dropout_rng=dropout_rng,
            output_hidden_states=output_hidden_states,
            output_attentions=output_attentions,
            output_last_hidden=output_last_hidden,
        )
    collect = output_hidden_states or output_attentions
    if isinstance(cache, PagedKVCache):
        if dropout_rng is not None:
            raise ValueError("dropout_rng is training-only (paged decode)")
        if collect or output_last_hidden:
            raise NotImplementedError(
                "output_hidden_states/output_attentions/output_last_hidden "
                "are not supported on the paged (serving) path; use a "
                "plain KVCache or a cache-free forward"
            )
        return paged_forward(
            params, tokens, positions, config, cache,
            attn_mask=attn_mask, compute_logits=compute_logits,
        )
    B, T = tokens.shape
    adt = config.activation_dtype
    if dropout_rng is not None and not (
        config.embd_pdrop > 0.0 or config.resid_pdrop > 0.0
        or config.attn_pdrop > 0.0
    ):
        dropout_rng = None  # all rates zero: identical trace either way
    if dropout_rng is not None and cache is not None:
        raise ValueError(
            "dropout_rng is training-only; cached decode is deterministic "
            "(pass dropout_rng=None)"
        )
    if attn_mask is None:
        attn_mask = positions >= 0
    q_positions = jnp.maximum(positions, 0)

    # Size the RoPE table to cover the largest reachable position: a cache
    # longer than max_seq_len (long-context decode) would otherwise run off
    # the table and jnp.take's clipping would silently repeat the last angle.
    max_positions = max(
        2 * config.max_seq_len, cache.max_len if cache is not None else 0
    )
    cos, sin = _rope_tables(
        config.head_dim, max_positions, config.rope_theta,
        config.use_scaled_rope,
    )

    x = embed_tokens(params, tokens).astype(adt)
    x = constrain(x, "data", "seq", None)

    layers_rng = None
    if dropout_rng is not None:
        emb_rng, rest_rng = jax.random.split(dropout_rng)
        if config.embd_pdrop > 0.0:
            x = _dropout(emb_rng, x, config.embd_pdrop)
        if config.resid_pdrop > 0.0 or config.attn_pdrop > 0.0:
            # Embedding-only dropout needs no per-layer rng threading (and
            # therefore composes with every layer-stack execution path).
            layers_rng = rest_rng

    if config.attn_impl not in ("xla", "flash", "ring", "auto"):
        raise NotImplementedError(f"attn_impl={config.attn_impl!r}")
    # "auto": Pallas flash for prefill/long blocks (no dense [B,1,T,S] bias,
    # O(S*d) memory), append-free xla path for decode-sized steps (T small)
    # where flash's one-row grid and in-scan cache writes lose.
    impl = config.attn_impl
    if impl == "auto":
        # Per-row indices are only supported on the xla path, so "auto"
        # resolves there regardless of T in that case.  (int8 caches and
        # attention dropout run on both: the flash kernel folds dequant
        # scales — and generates dropout masks — in-kernel.)
        must_xla = cache is not None and cache.per_row_index
        impl = "flash" if T > FLASH_MIN_SEQ and not must_xla else "xla"
    if output_attentions:
        if impl == "ring":
            raise NotImplementedError(
                "output_attentions does not compose with ring "
                "(seq-sharded) attention — the chunked accumulation "
                "never materializes the weights; use "
                "attn_impl='xla'/'auto'/'flash'"
            )
        impl = "xla"  # the only path that materializes [B, H, T, S]
    bias_new = None
    ring_cached = False
    if cache is not None and impl == "ring":
        from ..parallel.mesh import current_mesh as _cm

        _m = _cm()
        if _m is not None and _m.shape.get("seq", 1) > 1:
            # Seq-sharded cached decode (ring_decode): the cache shards
            # stay put and partial softmax stats combine over `seq` —
            # context is bounded by the mesh's combined HBM, not one
            # chip's.  Long prompts should prefill in chunks
            # (GenerationConfig.prefill_chunk): the step's own-token
            # merge is O(T_chunk²).
            if cache.per_row_index:
                raise NotImplementedError(
                    "seq-sharded decode needs a lockstep (scalar) cache "
                    "index; continuous batching uses seq == 1 meshes"
                )
            ring_cached = True
            impl = "ring_decode"
    xla_cached = cache is not None and impl == "xla"

    # Slot positions / masking state are layer-independent: compute once,
    # close over them.  The dense [B,1,T,S] bias is only materialized on the
    # XLA reference path — the flash kernel recomputes masks blockwise from
    # the positions and never holds an S×S buffer.
    new_slot_pos = jnp.where(attn_mask, q_positions, -1).astype(jnp.int32)
    if cache is not None and cache.per_row_index:
        if not xla_cached:
            raise NotImplementedError(
                "per-row cache indices (continuous batching) require the "
                "xla attention path"
            )
        # Scatter the T new slot positions at each row's own offset;
        # rows whose offset would run past the cache drop the write.
        _rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        _cols = cache.index[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        slot_pos = cache.pos.at[_rows, _cols].set(new_slot_pos, mode="drop")
    elif cache is not None:
        slot_pos = lax.dynamic_update_slice(
            cache.pos, new_slot_pos, (0, cache.index)
        )
    else:
        slot_pos = new_slot_pos
    if impl in ("flash", "ring", "ring_decode"):
        bias = None  # positional masks are built inside the kernels/bodies
    elif xla_cached:
        # Append-free decode (see _block): the cache bias masks the OLD
        # cache contents (unwritten slots hold pos -1), the new tokens get
        # their own within-step causal/padding bias.
        bias = attention_bias(q_positions, cache.pos, cache.pos >= 0)
        bias_new = attention_bias(q_positions, new_slot_pos, attn_mask)
    else:
        bias = attention_bias(q_positions, slot_pos, slot_pos >= 0)

    block = functools.partial(
        _block,
        config=config,
        positions=q_positions,
        bias=bias,
        # ring_decode attends the PRE-step cache (its own tokens merge at
        # the softmax level via ring_new_pos); every other cached path
        # sees the updated slot positions.
        slot_pos=cache.pos if ring_cached else slot_pos,
        cache_index=cache.index if cache is not None else None,
        cos=cos,
        sin=sin,
        bias_new=bias_new,
        impl=impl,
        ring_new_pos=new_slot_pos if ring_cached else None,
    )
    if config.remat:
        block = _remat(block, config)

    lp = params["layers"]
    from ..parallel.mesh import current_mesh

    _mesh = current_mesh()
    pp_stages = _mesh.shape.get("stage", 1) if _mesh is not None else 1
    if pp_stages > 1 and cache is not None:
        # shard_params on a stage>1 mesh stores each layer's weights only on
        # its stage group; running the plain scan over that layout would
        # silently all-gather every layer's weights per decode step.
        raise NotImplementedError(
            "decode with a KV cache is not supported on a stage > 1 mesh; "
            "generation meshes keep stage == 1 (use data/tensor axes)"
        )
    if collect and pp_stages > 1:
        raise NotImplementedError(
            "output_hidden_states/output_attentions are not supported on "
            "stage > 1 (pipeline) meshes — per-layer outputs live on "
            "their stage group; run the eval forward on a stage == 1 mesh"
        )
    if pp_stages > 1:
        # Pipeline-parallel block stack (training / scoring).  Embed, final
        # norm, and the LM head stay outside — auto-sharded, replicated
        # over the stage axis.  Decode-over-cache under pipeline
        # parallelism is not supported (the cache would need to live
        # per-stage); generation meshes keep stage == 1.
        from ..parallel.pipeline import pipeline_blocks

        if _mesh.shape.get("seq", 1) > 1:
            raise NotImplementedError(
                "stage > 1 does not compose with seq > 1 (ring attention "
                "nests a second shard_map); use stage*tensor*data/fsdp "
                "meshes for pipeline training"
            )

        # Per-layer dropout keys ride the staged tree ([L] leaves reshape
        # to [S, L/S] like the weights); each stage folds the current
        # microbatch index in, so every (layer, microbatch) pair draws an
        # independent mask — stage-1 semantics, microbatched.
        with_drop = layers_rng is not None
        stage_tree = (
            (lp, jax.random.split(layers_rng, config.n_layers))
            if with_drop else lp
        )

        def stage_fn(stage_layers, xx, pos, spos, mb_index):
            sbias = (
                None
                if impl in ("flash", "ring")
                else attention_bias(pos, spos, spos >= 0)
            )

            def one(carry, xs):
                if with_drop:
                    lp_i, key_i = xs
                    rng_i = jax.random.fold_in(key_i, mb_index)
                else:
                    lp_i, rng_i = xs, None
                y, *_ = _block(
                    carry, lp_i, None, None, None, None, rng_i,
                    config=config, positions=pos, bias=sbias,
                    slot_pos=spos, cache_index=None, cos=cos, sin=sin,
                    impl=impl,
                )
                return y, None

            if config.remat:
                one = _remat(one, config)
            y, _ = layer_scan(one, xx, stage_layers)
            return y

        x = pipeline_blocks(
            stage_fn, stage_tree, x, q_positions, slot_pos,
            mesh=_mesh,
            n_microbatches=config.pp_microbatches or pp_stages,
        )
    new_k_scale = cache.k_scale if cache is not None else None
    new_v_scale = cache.v_scale if cache is not None else None
    hs: list = []     # per-block inputs (collect only)
    attns: list = []  # per-block attention probabilities (collect only)
    # Collection runs on the UNROLLED stack: per-layer arrays are real
    # outputs, so a scan would have to carry them as ys anyway — and the
    # O(L) compile is fine for an eval/interp surface.
    if config.scan_layers and pp_stages <= 1 and not collect:
        if cache is not None and cache.quantized:
            # Scales ride the scan alongside the int8 payload.  On the
            # xla path the returned ck/cv are this step's projections and
            # the scales pass through unchanged (forward quantizes after
            # the scan); on the flash path they are the updated int8
            # cache + scales per layer.
            def scan_fn(carry, xs):
                layer_params, ck, cv, cks, cvs = xs
                # Per-layer cache slices [B, S, KVH(, hd)]: keep the
                # KV-head axis sharded through the scan's xs slicing.
                y, ck, cv, cks, cvs = block(
                    carry, layer_params,
                    _constrain_heads(ck, 2), _constrain_heads(cv, 2),
                    _constrain_heads(cks, 2), _constrain_heads(cvs, 2),
                )
                return y, (ck, cv, cks, cvs)

            x, (new_k, new_v, nks, nvs) = layer_scan(
                scan_fn, x,
                (lp, cache.k, cache.v, cache.k_scale, cache.v_scale),
                unroll=config.scan_unroll,
            )
            if not xla_cached:
                new_k_scale, new_v_scale = nks, nvs
        elif cache is not None:
            # On the xla_cached path the cache rides xs READ-ONLY and the
            # ys are just each layer's new [B,T,KVH,hd] projections —
            # rebuilding the full cache as ys would force a whole-cache
            # double-buffer copy per decode step inside scan/while.
            def scan_fn(carry, xs):
                layer_params, ck, cv = xs
                # Per-layer cache slices [B, S, KVH, hd]: keep the
                # KV-head axis sharded through the scan's xs slicing.
                y, ck, cv, _, _ = block(
                    carry, layer_params,
                    _constrain_heads(ck, 2), _constrain_heads(cv, 2),
                )
                return y, (ck, cv)

            x, (new_k, new_v) = layer_scan(
                scan_fn, x, (lp, cache.k, cache.v),
                unroll=config.scan_unroll,
            )
        elif layers_rng is not None:
            # Per-layer dropout keys ride the scan as xs alongside the
            # stacked weights.
            layer_rngs = jax.random.split(layers_rng, config.n_layers)

            def scan_fn(carry, xs):
                layer_params, rng_i = xs
                y, *_ = block(
                    carry, layer_params, None, None, None, None, rng_i
                )
                return y, None

            x, _ = layer_scan(
                scan_fn, x, (lp, layer_rngs), unroll=config.scan_unroll
            )
        else:
            def scan_fn(carry, layer_params):
                y, *_ = block(carry, layer_params, None, None)
                return y, None

            x, _ = layer_scan(scan_fn, x, lp, unroll=config.scan_unroll)
    elif pp_stages <= 1:
        unroll_rngs = (
            jax.random.split(layers_rng, config.n_layers)
            if layers_rng is not None else None
        )
        new_ks, new_vs, new_kss, new_vss = [], [], [], []
        for i in range(config.n_layers):
            layer_params = jax.tree.map(lambda a: a[i], lp)
            ck = cache.k[i] if cache is not None else None
            cv = cache.v[i] if cache is not None else None
            cks = cache.k_scale[i] if cache is not None and cache.quantized else None
            cvs = cache.v_scale[i] if cache is not None and cache.quantized else None
            if output_hidden_states:
                hs.append(x)
            x, ck, cv, cks, cvs, *aw = block(
                x, layer_params, ck, cv, cks, cvs,
                unroll_rngs[i] if unroll_rngs is not None else None,
                output_attentions=output_attentions,
            )
            if output_attentions:
                attns.append(aw[0])
            new_ks.append(ck)
            new_vs.append(cv)
            new_kss.append(cks)
            new_vss.append(cvs)
        if cache is not None:
            new_k = jnp.stack(new_ks)
            new_v = jnp.stack(new_vs)
            if cache.quantized and not xla_cached:
                new_k_scale = jnp.stack(new_kss)
                new_v_scale = jnp.stack(new_vss)
    if cache is not None and (xla_cached or ring_cached):
        # new_k/new_v hold the per-layer NEW projections [L, B, T, KVH, hd];
        # one in-place write (per array) lands them all in the cache —
        # quantizing first when the cache is int8.  Scalar index: a
        # dynamic-update-slice at the shared offset.  Per-row index
        # (continuous batching): a scatter at each row's own offset —
        # advanced indices on the contiguous (B, S) axes keep the update
        # shape [L, B, T, KVH, hd]; out-of-capacity rows drop the write.
        if cache.quantized:
            new_k, k_s = quantize_kv(new_k)
            new_v, v_s = quantize_kv(new_v)
        if cache.per_row_index:
            rows = jnp.arange(B, dtype=jnp.int32)[:, None]
            cols = (
                cache.index[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
            )
            if cache.quantized:
                new_k_scale = cache.k_scale.at[:, rows, cols].set(
                    k_s, mode="drop"
                )
                new_v_scale = cache.v_scale.at[:, rows, cols].set(
                    v_s, mode="drop"
                )
            new_k = cache.k.at[:, rows, cols].set(
                new_k.astype(cache.k.dtype), mode="drop"
            )
            new_v = cache.v.at[:, rows, cols].set(
                new_v.astype(cache.v.dtype), mode="drop"
            )
        else:
            if cache.quantized:
                new_k_scale = lax.dynamic_update_slice(
                    cache.k_scale, k_s, (0, 0, cache.index, 0)
                )
                new_v_scale = lax.dynamic_update_slice(
                    cache.v_scale, v_s, (0, 0, cache.index, 0)
                )
            new_k = lax.dynamic_update_slice(
                cache.k, new_k.astype(cache.k.dtype), (0, 0, cache.index, 0, 0)
            )
            new_v = lax.dynamic_update_slice(
                cache.v, new_v.astype(cache.v.dtype), (0, 0, cache.index, 0, 0)
            )
    if ring_cached:
        # Keep the cache sharded along S over `seq` across steps (GSPMD
        # applies the tiny T-token update per shard; no gather).  S must
        # be divisible by the seq axis size.
        new_k = constrain(new_k, None, "data", "seq", "tensor", None)
        new_v = constrain(new_v, None, "data", "seq", "tensor", None)
        slot_pos = constrain(slot_pos, "data", "seq")
        if cache.quantized:
            new_k_scale = constrain(new_k_scale, None, "data", "seq", "tensor")
            new_v_scale = constrain(new_v_scale, None, "data", "seq", "tensor")

    aux = None
    with_aux = collect or output_last_hidden
    if with_aux:
        final_h = rms_norm(x, params["final_norm"], config.rms_norm_eps)
        aux = AuxOutput(
            hidden_states=(
                jnp.stack(hs + [final_h]) if output_hidden_states else None
            ),
            last_hidden_state=final_h,
            attentions=jnp.stack(attns) if output_attentions else None,
        )
    logits = (
        lm_head_logits(
            params, final_h if with_aux else x, config, normed=with_aux
        )
        if compute_logits else None
    )

    if cache is not None:
        new_cache = KVCache(
            k=new_k, v=new_v, pos=slot_pos, index=cache.index + T,
            k_scale=new_k_scale, v_scale=new_v_scale,
        )
        return (logits, new_cache, aux) if with_aux else (logits, new_cache)
    return (logits, None, aux) if with_aux else (logits, None)


def paged_forward(
    params: Params,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    config: LLaMAConfig,
    cache: PagedKVCache,
    attn_mask: Optional[jnp.ndarray] = None,
    compute_logits: bool = True,
) -> Tuple[Optional[jnp.ndarray], PagedKVCache]:
    """One decode step of T tokens per row over a paged block pool
    (continuous batching; T=1 is plain decode, T=G+1 is speculative
    verify).

    The Pallas paged-attention kernel chases ``cache.table`` inside its
    BlockSpec index maps, so each layer's pool is read ONCE per step —
    for ALL T tokens of a row — and no gathered contiguous view exists
    (the pool bytes previously moved three times per step: gather read,
    gather write, attention read).  The pool rides the layer scan
    immutably; the step's new K/V land via one scatter per array
    afterwards, mirroring the xla_cached contract.

    Contract for T > 1 (the kernel derives per-token masks from a
    sublane iota): each active row's positions are CONSECUTIVE —
    ``positions[:, t] == positions[:, 0] + t`` — and a row is active or
    inactive as a whole (``attn_mask`` constant along T).  Speculative
    rounds satisfy both by construction; a row violating either is
    folded to inactive (enforced below) rather than trusted.

    Rows with ``attn_mask`` False (or position -1) are inactive: they
    attend nothing, their logits are garbage the host ignores, and their
    scatter resolves to the sentinel block id and is dropped.
    """
    B, T = tokens.shape
    adt = config.activation_dtype
    if attn_mask is None:
        attn_mask = positions >= 0
    q_positions = jnp.maximum(positions, 0)
    NB, BLK = cache.pos.shape
    MB = cache.table.shape[1]

    max_positions = max(2 * config.max_seq_len, MB * BLK)
    cos, sin = _rope_tables(
        config.head_dim, max_positions, config.rope_theta,
        config.use_scaled_rope,
    )

    x = embed_tokens(params, tokens).astype(adt)
    # The kernel derives token t's mask/position from positions[:, 0] + t
    # (sublane iota) and treats a row as live or dead as a whole, so the
    # T > 1 contract above is enforced by DEFINITION rather than trust:
    # a row violating it (mixed attn_mask, non-consecutive positions) is
    # folded to inactive — attends nothing, writes nothing — instead of
    # silently corrupting the pool.  [B, T] integer ops, free next to the
    # forward; speculative rounds conform by construction.
    row_active = attn_mask[:, 0]
    if T > 1:
        uniform = jnp.all(attn_mask == attn_mask[:, :1], axis=1)
        consecutive = jnp.all(
            positions
            == positions[:, :1] + jnp.arange(T, dtype=positions.dtype),
            axis=1,
        )
        row_active = row_active & uniform & consecutive
    q_pos_row = jnp.where(row_active, positions[:, 0], -1).astype(jnp.int32)

    block = functools.partial(
        _block,
        config=config,
        positions=q_positions,
        bias=None,
        slot_pos=cache.pos,
        cache_index=None,
        cos=cos,
        sin=sin,
        impl="paged",
        paged_pos=cache.pos,
        paged_table=cache.table,
        paged_qpos=q_pos_row,
        # The FULL pool rides the scan as an invariant closure operand;
        # the kernel selects its layer plane via the scan index below
        # (slicing per layer here materialized each plane as a copy —
        # see the paged branch of _block).
        paged_pools=(cache.k, cache.v, cache.k_scale, cache.v_scale),
    )

    lp = params["layers"]
    nks = nvs = None
    layer_idx = jnp.arange(config.n_layers, dtype=jnp.int32)
    if config.scan_layers:
        def scan_fn(carry, xs):
            layer_params, li = xs
            y, ck, cv, cks, cvs = block(
                carry, layer_params, None, None, paged_layer=li
            )
            ys = (ck, cv, cks, cvs) if cache.quantized else (ck, cv)
            return y, ys

        x, ys = layer_scan(
            scan_fn, x, (lp, layer_idx), unroll=config.scan_unroll
        )
        if cache.quantized:
            new_k, new_v, nks, nvs = ys
        else:
            new_k, new_v = ys
    else:
        new_ks, new_vs, sks, svs = [], [], [], []
        for i in range(config.n_layers):
            layer_params = jax.tree.map(lambda a: a[i], lp)
            x, ck, cv, cks, cvs = block(
                x, layer_params, None, None, paged_layer=layer_idx[i]
            )
            new_ks.append(ck)
            new_vs.append(cv)
            sks.append(cks)
            svs.append(cvs)
        new_k, new_v = jnp.stack(new_ks), jnp.stack(new_vs)
        if cache.quantized:
            nks, nvs = jnp.stack(sks), jnp.stack(svs)

    logits = lm_head_logits(params, x, config) if compute_logits else None

    return logits, _paged_land(
        cache, new_k, new_v, nks, nvs, row_active, positions
    )


def _paged_land(
    cache: PagedKVCache, new_k, new_v, nks, nvs, active, positions,
    rolled: bool = False,
) -> PagedKVCache:
    """Land a step's projections ``new_k`` / ``new_v`` [L, B, T, KVH, hd]
    (int8 pools: payload, with scales ``nks`` / ``nvs`` [L, B, T, KVH]) and
    its ``positions`` [B, T] in the pool via the shared write-back
    contract (paged_write_indices — same function serving's gathered-view
    scatter uses, so the two paths cannot drift).  Rows not ``active``
    resolve to the sentinel block id and drop."""
    NB, BLK = cache.pos.shape
    T = positions.shape[1]
    blk_idx, off, _ = paged_write_indices(
        cache.table, cache.fill, active, T, NB, BLK
    )  # [B, T] each
    upd_k = jnp.moveaxis(new_k, 3, 1)  # [L, B, T, KVH, hd] -> [L, KVH, B, T, hd]
    upd_v = jnp.moveaxis(new_v, 3, 1)
    new_cache = dataclasses.replace(
        cache,
        k=paged_pool_write(cache.k, upd_k, blk_idx, off, rolled),
        v=paged_pool_write(cache.v, upd_v, blk_idx, off, rolled),
        pos=paged_pool_write(
            cache.pos, jnp.where(active[:, None], positions, -1),
            blk_idx, off, rolled,
        ),
    )
    if cache.quantized:
        # ys carried each layer's new int8 payload + its scales.
        new_cache = dataclasses.replace(
            new_cache,
            k_scale=paged_pool_write(
                cache.k_scale, jnp.moveaxis(nks, 3, 1), blk_idx, off
            ),
            v_scale=paged_pool_write(
                cache.v_scale, jnp.moveaxis(nvs, 3, 1), blk_idx, off
            ),
        )
    return new_cache


def mixed_forward(
    params: Params,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    config: LLaMAConfig,
    cache: KVCache,
    attn_mask: jnp.ndarray,
    rider_tokens: jnp.ndarray,
    rider_positions: jnp.ndarray,
    pool: PagedKVCache,
) -> Tuple[jnp.ndarray, KVCache, PagedKVCache]:
    """ONE pass over the weights for a prompt chunk and a decode step: the
    chunk's [1, C] ``tokens`` (``positions`` / ``attn_mask`` as ``forward``
    takes them, appended to the one row's ``cache`` at its scalar index)
    and the B ``rider_tokens`` — one token a decode row, at
    ``rider_positions`` [B], -1 for a row that rides masked — go through
    the embedding and every layer as one [1, C + B, D] activation.  Only
    attention splits (``_block``, ``n_riders``): the chunk's rows do what
    ``forward`` does with them (flash over the cache, or the append-free
    xla form; "auto" resolves by C), the riders what ``paged_forward``
    does (the paged kernel over ``pool``, bound outside the layer scan,
    and one ``paged_pool_write`` a plane after it).  The riders' blocks
    and the chunk's row are different rows' blocks, so neither half reads
    what the other writes.

    Returns (post-final-norm hidden states [1, C + B, D] — the chunk's
    rows, then the riders' —, the updated ``cache``, the updated ``pool``).
    For a float pool and no sharded mesh; the head is the caller's.

    The two blocks with a recurrent state have their own, where the
    mixers' recurrence splits as attention does: a mixer beside attention
    in every layer (``falcon_h1.mixed_forward``), and mixer layers between
    window, full and cross attention layers (``sambay.mixed_forward``).
    The two blocks with routed experts (latent attention, ``mla_moe``;
    window attention layers, ``afmoe``) have none and keep two passes
    (``serving._mixed_pass``).
    """
    if config.recurrent_state:
        from . import falcon_h1, sambay

        block = falcon_h1 if config.parallel_mixer else sambay
        return block.mixed_forward(
            params, tokens, positions, config, cache, attn_mask,
            rider_tokens, rider_positions, pool,
        )
    if cache.quantized or cache.per_row_index or pool.quantized:
        raise NotImplementedError(
            "mixed_forward: a float cache with a scalar index and a float "
            "pool"
        )
    C = tokens.shape[1]
    B = rider_tokens.shape[0]
    q_positions = jnp.maximum(positions, 0)

    cos, sin = _rope_tables(
        config.head_dim, max(2 * config.max_seq_len, cache.max_len),
        config.rope_theta, config.use_scaled_rope,
    )
    x = embed_tokens(
        params, jnp.concatenate([tokens, rider_tokens[None]], axis=1)
    ).astype(config.activation_dtype)

    impl = config.attn_impl
    if impl == "auto":
        impl = "flash" if C > FLASH_MIN_SEQ else "xla"
    xla_cached = impl == "xla"
    # The chunk's masking state, as ``forward`` builds it for a cache with
    # a scalar index.
    new_slot_pos = jnp.where(attn_mask, q_positions, -1).astype(jnp.int32)
    bias = bias_new = None
    if xla_cached:
        bias = attention_bias(q_positions, cache.pos, cache.pos >= 0)
        bias_new = attention_bias(q_positions, new_slot_pos, attn_mask)
    slot_pos = lax.dynamic_update_slice(
        cache.pos, new_slot_pos, (0, cache.index)
    )
    block = functools.partial(
        _block,
        config=config,
        positions=jnp.concatenate(
            [q_positions, jnp.maximum(rider_positions, 0)[None]], axis=1
        ),
        bias=bias,
        slot_pos=slot_pos,
        cache_index=cache.index,
        cos=cos,
        sin=sin,
        bias_new=bias_new,
        impl=impl,
        paged_pos=pool.pos,
        paged_table=pool.table,
        paged_qpos=rider_positions.astype(jnp.int32),
        paged_pools=(pool.k, pool.v, None, None),
        n_riders=B,
    )

    def layer(x, xs):
        layer_params, ck, cv, li = xs
        y, ck, cv, _, _, rk, rv = block(
            x, layer_params, ck, cv, paged_layer=li
        )
        return y, (ck, cv, rk, rv)

    xs = (
        params["layers"], cache.k, cache.v,
        jnp.arange(config.n_layers, dtype=jnp.int32),
    )
    if config.scan_layers:
        x, ys = layer_scan(layer, x, xs, unroll=config.scan_unroll)
    else:
        per_layer = []
        for i in range(config.n_layers):
            x, y = layer(x, jax.tree.map(lambda a: a[i], xs))
            per_layer.append(y)
        ys = jax.tree.map(lambda *a: jnp.stack(a), *per_layer)
    new_k, new_v, rider_k, rider_v = ys
    if xla_cached:
        # The ys are the chunk's projections [L, 1, C, KVH, hd]: one
        # in-place write a plane, after the scan (see ``forward``).
        new_k = lax.dynamic_update_slice(
            cache.k, new_k.astype(cache.k.dtype), (0, 0, cache.index, 0, 0)
        )
        new_v = lax.dynamic_update_slice(
            cache.v, new_v.astype(cache.v.dtype), (0, 0, cache.index, 0, 0)
        )
    return (
        rms_norm(x, params["final_norm"], config.rms_norm_eps),
        dataclasses.replace(
            cache, k=new_k, v=new_v, pos=slot_pos, index=cache.index + C
        ),
        _paged_land(
            pool, rider_k, rider_v, None, None, rider_positions >= 0,
            rider_positions[:, None], rolled=True,
        ),
    )
