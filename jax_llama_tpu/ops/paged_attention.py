"""Pallas TPU paged-attention decode kernel — walks the block table
in-kernel.

The serving pool stores KV in fixed-size physical blocks
(``serving.BlockPool``); before this kernel, every decode step gathered
each row's blocks into a virtually-contiguous cache view and ran
``sdpa_cached`` over it — the pool bytes moved three times per step
(gather read, gather write, attention read).  Here the kernel's index
maps chase the block table directly via scalar prefetch, so the pool is
read ONCE and nothing contiguous is ever materialized (the vLLM
paged-attention idea, executed the Pallas way: the table lookup lives in
the BlockSpec index_map, the DMA pipeline does the pointer-chasing).

Layout contract: the pool is [KVH, NB, BLK, hd] per layer — KV-head
major, so a block's tile is a clean ``(KVH, BLK, hd)`` VMEM page.
Grid is ``(B, MB)`` with the per-row block sweep innermost; ONE grid
cell covers all KV heads of a block via a statically-unrolled in-kernel
loop (a finer (B, KVH, MB) grid was measured SLOWER than the gathered
view it replaces — per-cell overhead beat the bandwidth saving).
Online softmax state lives in VMEM scratch across the sweep, exactly
like ``ops.flash_attention``.  GQA: the ``group`` query heads of each
KV head ride the sublane axis of that head's q rows (padded to 8), so
decode reads each KV block once — never per query head.

The kernel attends the POOL only and emits a normalized output plus the
row logsumexp; the caller merges the current step's own K/V (one slot,
always attendable) at the scores level — the same two-source softmax
split as ``ops.attention.sdpa_cached``, so the pool stays immutable
through the layer scan and the decode step applies one scatter per step.

Scan compatibility: everything dynamic the kernel consumes — the block
table, per-row query positions, the derived live-block grid bounds, the
layer index, and the pool planes themselves — enters as traced operands
(scalar-prefetch or BlockSpec-mapped), so the whole op nests inside
``lax.scan`` loops without re-tracing: the model's layer scan selects
planes via ``layer``, and serving's fused decode chunk
(``serving._paged_decode_chunk``) additionally scans K decode
iterations around the layer scan, re-deriving positions/bounds per
iteration on device.  Under a mesh the shard_map wrapper nests inside
those scans the same way.

Fused prefill-decode scheduling (``serving._fused_chunk``) runs this
kernel's decode scan WHILE an admission's prompt is mid-prefill in the
same dispatch: the prefilling row rides the decode grid masked (its
query position is -1 until its last prompt chunk lands, so it attends
nothing and its write-back resolves to the sentinel block and drops) —
the standard idle-row contract, no new kernel case.  Its partially
written blocks are safe for the OTHER rows by construction: the table
walk only visits each row's own blocks.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (
    MASK_VALUE,
    _LANES,
    _SUBLANES,
    _resolve_interpret,
)

def _maybe_fault() -> None:
    """Chaos-drill hook: fires faults.py's trace-time registry (site
    "paged_kernel") — the paged twin of ops.flash_attention's hook."""
    from ..faults import fire_trace

    fire_trace("paged_kernel")


def _paged_kernel(
    tbl_ref,    # [B * MB] int32 scalar-prefetch: physical block id (NB = dead)
    qpos_ref,   # [B] int32 scalar-prefetch: FIRST token's query position
    #             (-1 = inactive row; token t sits at qpos + t)
    bound_ref,  # [B] int32 scalar-prefetch: live-block grid bound per row
    layer_ref,  # [1] int32 scalar-prefetch: pool layer this call reads
    q_ref,      # [1, KVH, TG8, d] — sublane row r = t*group + g
    k_ref,      # [1, KVH, 1, BLK, d] (int8 when quantized)
    v_ref,      # [1, KVH, 1, BLK, d] (int8 when quantized)
    pos_ref,    # [1, 1, BLK] int32 slot positions of the block
    *rest,      # [k_scale_ref, v_scale_ref] when quantized
    #             ([1, KVH, 1, 1, BLK] fp32); o_ref; lse_ref; scratch
    scale: float,
    n_blocks: int,
    kvh: int,
    tg8: int,
    t_tokens: int,
    group: int,
    quantized: bool = False,
):
    """Online-softmax sweep of one row's pool blocks.

    ``t_tokens`` queries per (row, query head) ride the sublane axis
    (row r = t*group + g); their positions are CONSECUTIVE — token t at
    ``qpos + t`` — so per-token masks derive from a sublane iota and no
    per-token position plane is needed.  T=1 keeps the original
    whole-tile skip for fully-masked tiles; T>1 additionally zeroes
    masked probabilities explicitly, because one tile can be live for a
    late token but fully masked for an early one (the skip guard is
    per-tile, not per-sublane).
    """
    if quantized:
        k_scale_ref, v_scale_ref, *rest = rest
    else:
        k_scale_ref = v_scale_ref = None
    o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    mb = pl.program_id(1)
    nmb = pl.num_programs(1)

    @pl.when(mb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qp = qpos_ref[b]
    qp_last = qp + t_tokens - 1
    kp = pos_ref[0, :1, :]  # [1, BLK]
    # Three dead-block guards, all mandatory:
    #   * mb >= bound: past the row's last attendable block — the index
    #     maps clamped the fetch (no new DMA); the tile is a repeat.
    #   * table sentinel / inactive row.
    #   * all-masked tile (min live kp > last token's position):
    #     processing it would add p = exp(MASK - MASK) = 1 garbage into
    #     l/acc — the block must be SKIPPED, not merely masked (same
    #     invariant as flash block_live).
    live_kp = jnp.where(kp >= 0, kp, jnp.iinfo(jnp.int32).max)
    live = (
        (mb < bound_ref[b])
        & (tbl_ref[b * nmb + mb] < n_blocks)
        & (qp >= 0)
        & (jnp.min(live_kp) <= qp_last)
    )

    if t_tokens > 1:
        # Per-sublane query position: row r holds token r // group.
        # (Pad rows past t_tokens*group get later tokens' looser masks;
        # their q rows are zero-padding and their outputs are sliced off.)
        qp_rows = qp + jax.lax.broadcasted_iota(
            jnp.int32, (tg8, 1), 0
        ) // group  # [TG8, 1]
    else:
        qp_rows = None

    @pl.when(live)
    def _compute():
        # One grid cell covers ALL KV heads of the block (the loop
        # unrolls statically): grid cells are B × MB, not B × KVH × MB —
        # measured ~1 µs of per-cell overhead made the finer grid SLOWER
        # than the gathered-view fallback it replaces.
        for h in range(kvh):
            sl = slice(h * tg8, (h + 1) * tg8)
            q = q_ref[0, h]
            if quantized:
                # int8 pool: cast the tile in VMEM (int8 magnitudes are
                # exact in bf16) and fold the per-slot dequant scales at
                # the scores / probability level — the same commuting
                # trick as flash_attention_quantized, so HBM streams the
                # int8 bytes.
                k = k_ref[0, h, 0].astype(q.dtype)
                ksc = k_scale_ref[0, h, 0, :1, :]  # [1, BLK] fp32
            else:
                k = k_ref[0, h, 0]
                ksc = None
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [TG8, BLK]
            if quantized:
                s = s * ksc
            if t_tokens > 1:
                allowed = (kp >= 0) & (kp <= qp_rows)  # [TG8, BLK]
            else:
                allowed = (kp >= 0) & (kp <= qp)       # [1, BLK]
            s = jnp.where(allowed, s, MASK_VALUE)
            m_prev = m_ref[sl, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(s, axis=-1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if t_tokens > 1:
                # A tile can be live for token T-1 yet fully masked for
                # token 0: that token's m_new stays MASK_VALUE and
                # exp(MASK - MASK) = 1 would poison l/acc — zero masked
                # probabilities explicitly (the T=1 path never hits this:
                # its one qp makes tile-liveness == row-liveness).
                p = jnp.where(allowed, p, 0.0)
            l_ref[sl] = jnp.broadcast_to(
                alpha * l_ref[sl, :1] + jnp.sum(p, axis=-1, keepdims=True),
                (tg8, l_ref.shape[1]),
            )
            if quantized:
                pv = (p * v_scale_ref[0, h, 0, :1, :]).astype(q.dtype)
                vb = v_ref[0, h, 0].astype(q.dtype)
            else:
                pv = p.astype(v_ref.dtype)
                vb = v_ref[0, h, 0]
            acc_ref[sl] = alpha * acc_ref[sl] + jax.lax.dot_general(
                pv, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[sl] = jnp.broadcast_to(m_new, (tg8, m_ref.shape[1]))

    @pl.when(mb == nmb - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (
            acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
        ).reshape(kvh, tg8, -1).astype(o_ref.dtype)
        # lse stays ~MASK_VALUE for rows that attended nothing, so the
        # caller's merge weight exp(lse - m_tot) underflows to exactly 0.
        lse_ref[0] = (
            m_ref[:] + jnp.log(jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:]))
        ).reshape(kvh, tg8, -1)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("t_tokens", "interpret"))
def paged_pool_attention(
    q: jnp.ndarray,        # [B, KVH, T*G, d]  (packed queries, r = t*G + g)
    k_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d] (or [KVH, NB, BLK, d])
    v_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d]
    pool_pos: jnp.ndarray,  # [NB, BLK] int32 (-1 = invalid slot)
    table: jnp.ndarray,    # [B, MB] int32 physical block ids (NB = unused)
    q_pos: jnp.ndarray,    # [B] int32 first token's position (-1 = inactive)
    k_scale: Optional[jnp.ndarray] = None,  # [L, KVH, NB, BLK] fp32 (int8)
    v_scale: Optional[jnp.ndarray] = None,
    t_tokens: int = 1,
    layer: Optional[jnp.ndarray] = None,    # int32 layer index into L
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Attend each row's table-mapped pool blocks; no gather, pool read once.

    The pool carries its LAYER axis and ``layer`` (a traced scalar — the
    layer scan's loop index) selects the plane inside the kernel's index
    maps.  Slicing ``pool[layer]`` at the caller instead would
    materialize a full copy of the layer's plane as the custom-call
    operand — 2 planes × L layers × plane-bytes of pure copy traffic per
    decode step, which at 16k context cost ~3× the kernel itself
    (xplane-measured r4: 4.7 of 9.3 ms/step).  A 4-D pool (single plane)
    is accepted for compatibility and reads layer 0.

    With ``t_tokens`` > 1 each row carries T queries at CONSECUTIVE
    positions (token t at ``q_pos + t`` — the speculative-verify /
    multi-token decode shape); they ride the sublane axis packed
    ``r = t*G + g``, so the pool still streams ONCE for the whole
    (row, T) group.  With ``k_scale``/``v_scale`` the pool is int8 and
    the per-slot dequant scales fold in-kernel (scores-level for K,
    probability-level for V) — the pool streams at one byte per element
    plus fp32 scales.

    Returns (out [B, KVH, T*G, d] fp32, normalized over the pool slots,
    lse [B, KVH, T*G] fp32 row logsumexp) for the caller's
    new-token merge (fp32 end-to-end through the merge — see the
    out_shape note in the kernel call).
    """
    _maybe_fault()
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = None
    # A multi-layer pool without a layer index would silently attend
    # layer 0 everywhere — fail at trace time instead.  ValueError, not
    # assert: unlike the adjacent shape asserts (whose mistakes surface
    # immediately as shape errors), this guard protects against silently
    # WRONG results and must survive `python -O`.
    if k_pool.shape[0] != 1 and layer is None:
        raise ValueError(
            "multi-layer pool requires the `layer` index (a 5-D pool with "
            "layer=None would attend layer 0 for every layer)"
        )
    layer_arr = (
        jnp.zeros((1,), jnp.int32) if layer is None
        else jnp.asarray(layer, jnp.int32).reshape(1)
    )
    B, KVH, TG, d = q.shape
    NB, BLK = pool_pos.shape
    MB = table.shape[1]
    L = k_pool.shape[0]
    assert k_pool.shape == (L, KVH, NB, BLK, d), (
        k_pool.shape, (L, KVH, NB, BLK, d)
    )
    assert TG % t_tokens == 0, (TG, t_tokens)
    group = TG // t_tokens
    quantized = k_scale is not None
    interpret = _resolve_interpret(interpret)
    TG8 = _round_up(TG, _SUBLANES)
    qg = jnp.pad(q, ((0, 0), (0, 0), (0, TG8 - TG), (0, 0)))
    scale = 1.0 / (d ** 0.5)

    # Narrow-sublane position plane [NB, 1, BLK]: a free expand_dims
    # view — Mosaic accepts 1-row tiles here (verified compiled), so no
    # sublane replication and no per-step materialization is needed.
    pos_r = pool_pos[:, None, :]
    tbl_flat = table.astype(jnp.int32).reshape(B * MB)
    q_pos = q_pos.astype(jnp.int32)
    qp_last = q_pos + (t_tokens - 1)

    # Per-row live-block grid bound: 1 + the last table slot whose block
    # holds any slot this row's LAST query may attend.  Blocks at/after
    # the bound (reserved-but-unwritten tail, sentinel entries) are
    # clamped in the index maps — consecutive grid steps fetch the SAME
    # tile, so the pipeline skips the DMA — and the kernel skips their
    # compute.
    blk_min = jnp.min(
        jnp.where(pool_pos >= 0, pool_pos, jnp.iinfo(jnp.int32).max),
        axis=1,
    )  # [NB] min live position per physical block
    blk_min = jnp.concatenate(
        [blk_min, jnp.full((1,), jnp.iinfo(jnp.int32).max, jnp.int32)]
    )  # sentinel id NB -> never attendable
    row_min = blk_min[jnp.minimum(table, NB)]  # [B, MB]
    attendable = row_min <= qp_last[:, None]
    bound = 1 + jnp.max(
        jnp.where(
            attendable, jnp.arange(MB, dtype=jnp.int32)[None, :], -1
        ),
        axis=1,
    )  # [B] in [0, MB]

    def _clamp_mb(b, mb, tbl, bound):
        mb = jnp.minimum(mb, jnp.maximum(bound[b] - 1, 0))
        return jnp.minimum(tbl[b * MB + mb], NB - 1)

    def kv_map(b, mb, tbl, qpos, bound, layer):
        return (layer[0], 0, _clamp_mb(b, mb, tbl, bound), 0, 0)

    def pos_map(b, mb, tbl, qpos, bound, layer):
        return (_clamp_mb(b, mb, tbl, bound), 0, 0)

    def q_map(b, mb, tbl, qpos, bound, layer):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, KVH, TG8, d), q_map),
        pl.BlockSpec((1, KVH, 1, BLK, d), kv_map),
        pl.BlockSpec((1, KVH, 1, BLK, d), kv_map),
        pl.BlockSpec((1, 1, BLK), pos_map),
    ]
    operands = [qg, k_pool, v_pool, pos_r]
    if quantized:
        # Narrow-sublane scale planes [L, KVH, NB, 1, BLK]: free
        # expand_dims views of the long-lived pool scales — NOT sublane-
        # replicated copies, which would re-materialize (and stream) 8x
        # the scale bytes per layer per step on the path this kernel
        # exists to make bandwidth-lean.
        def scale_map(b, mb, tbl, qpos, bound, layer):
            return (layer[0], 0, _clamp_mb(b, mb, tbl, bound), 0, 0)

        scale_spec = pl.BlockSpec((1, KVH, 1, 1, BLK), scale_map)
        in_specs += [scale_spec, scale_spec]
        operands += [
            k_scale.astype(jnp.float32)[:, :, :, None, :],
            v_scale.astype(jnp.float32)[:, :, :, None, :],
        ]

    out, lse = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, n_blocks=NB, kvh=KVH, tg8=TG8,
            t_tokens=t_tokens, group=group, quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, MB),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, KVH, TG8, d), q_map),
                pl.BlockSpec((1, KVH, TG8, _LANES), q_map),
            ),
            scratch_shapes=[
                pltpu.VMEM((KVH * TG8, _LANES), jnp.float32),
                pltpu.VMEM((KVH * TG8, _LANES), jnp.float32),
                pltpu.VMEM((KVH * TG8, d), jnp.float32),
            ],
        ),
        out_shape=(
            # fp32: the caller's new-token merge rescales this by
            # exp(lse - m_tot) and divides by the joint denominator — a
            # bf16 round HERE is one more rounding than the gathered
            # path's single joint softmax takes, and it measurably
            # widens the T=1-vs-T=G+1 numerical gap that flips greedy
            # argmax at near-ties (speculative self-draft acceptance).
            # Decode-sized output: the extra bytes are noise.
            jax.ShapeDtypeStruct((B, KVH, TG8, d), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, TG8, _LANES), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tbl_flat, q_pos, bound, layer_arr, *operands)
    return out[:, :, :TG, :], lse[:, :, :TG, 0]


def paged_decode_attention(
    q: jnp.ndarray,        # [B, T, H, d] — this step's queries
    k_new: jnp.ndarray,    # [B, T, KVH, d] — this step's projections
    v_new: jnp.ndarray,    # [B, T, KVH, d]
    k_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d] (or [KVH, NB, BLK, d])
    v_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d]
    pool_pos: jnp.ndarray,  # [NB, BLK]
    table: jnp.ndarray,    # [B, MB]
    q_pos: jnp.ndarray,    # [B] FIRST token's position (-1 = inactive row)
    k_scale: Optional[jnp.ndarray] = None,  # [L, KVH, NB, BLK] (int8 pool)
    v_scale: Optional[jnp.ndarray] = None,
    layer: Optional[jnp.ndarray] = None,    # int32 index into L
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """One decode step of attention over (pool blocks ∪ the step's T new
    slots).

    The pool pass runs in the Pallas kernel (T consecutive-position
    queries per row share ONE pool sweep — the speculative-verify shape);
    the step's own T tokens (token t attends new slots j <= t, plus
    itself) merge at the softmax level outside, keeping the pool
    immutable through the layer scan (same append-free contract as
    ``sdpa_cached``; the new tokens' K/V enter the merge at full
    precision, also matching sdpa_cached — only POOL reads see int8).
    Token t's position is ``q_pos + t`` for active rows (consecutive —
    the T>1 kernel's contract).  Returns [B, T, H, d].
    """
    B, T, H, d = q.shape
    KVH = k_new.shape[2]

    # Tensor/data-parallel serving: a pallas_call is not partitioned by
    # GSPMD, so under an active mesh the whole op runs per-shard inside
    # shard_map — KV heads split over "tensor" (the head layout
    # h = kvh*G + g makes contiguous H chunks == contiguous KVH chunks),
    # rows over the batch axes ("data", "fsdp") — the same pair the
    # model's `constrain` shards batch over, so an fsdp-only mesh also
    # routes through shard_map rather than leaving a GSPMD-sharded
    # pallas_call.  The pool shards on its leading KVH axis; the table
    # and q_pos shard with the rows; only pool_pos is replicated.  No
    # collectives are needed: every (row, kv head) pair is independent;
    # the caller's o-projection all-reduce (GSPMD) recombines heads
    # exactly as on the xla path.
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        tp = mesh.shape.get("tensor", 1)
        row_axes = tuple(
            a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1
        )
        rp = int(np.prod([mesh.shape[a] for a in row_axes])) if row_axes else 1
        if tp > 1 or rp > 1:
            if KVH % tp != 0 or B % rp != 0:
                raise NotImplementedError(
                    f"paged kernel sharding needs kv_heads % tensor == 0 "
                    f"and n_slots % (data*fsdp) == 0 (got KVH={KVH}, "
                    f"tp={tp}, B={B}, rows={rp}); use a compatible mesh "
                    f"or the gathered-view path"
                )
            rows = row_axes if row_axes else None
            tens = "tensor" if tp > 1 else None
            head4 = P(rows, None, tens, None)
            pooled = (
                P(None, tens, None, None, None) if k_pool.ndim == 5
                else P(tens, None, None, None)
            )
            scale_spec = (
                P(None, tens, None, None) if k_pool.ndim == 5
                else P(tens, None, None)
            )
            layer_op = (
                jnp.zeros((), jnp.int32) if layer is None
                else jnp.asarray(layer, jnp.int32).reshape(())
            )
            args = [
                q, k_new, v_new, k_pool, v_pool, pool_pos, table, q_pos,
                layer_op,
            ]
            in_specs = [
                head4, head4, head4, pooled, pooled, P(None, None),
                P(rows, None), P(rows), P(),
            ]
            if k_scale is not None:
                args += [k_scale, v_scale]
                in_specs += [scale_spec, scale_spec]

            def body(q, k_new, v_new, k_pool, v_pool, pool_pos, table,
                     q_pos, layer, k_scale=None, v_scale=None):
                return _paged_decode_local(
                    q, k_new, v_new, k_pool, v_pool, pool_pos, table,
                    q_pos, k_scale, v_scale, layer, interpret,
                )

            fn = jax.shard_map(
                body, mesh=mesh, in_specs=tuple(in_specs),
                out_specs=head4, check_vma=False,
            )
            return fn(*args)

    return _paged_decode_local(
        q, k_new, v_new, k_pool, v_pool, pool_pos, table, q_pos,
        k_scale, v_scale, layer, interpret,
    )


def _paged_decode_local(
    q, k_new, v_new, k_pool, v_pool, pool_pos, table, q_pos,
    k_scale, v_scale, layer, interpret,
):
    """Single-shard body of ``paged_decode_attention`` (also the whole op
    when no mesh is active)."""
    B, T, H, d = q.shape
    KVH = k_new.shape[2]
    G = H // KVH
    scale = 1.0 / (d ** 0.5)

    # Head layout h = kvh * G + g (same contract as flash GQA packing);
    # kernel sublane packing r = t*G + g.
    q5 = q.reshape(B, T, KVH, G, d)
    qg = jnp.swapaxes(q5, 1, 2).reshape(B, KVH, T * G, d)
    out_pool, lse = paged_pool_attention(
        qg, k_pool, v_pool, pool_pos, table, q_pos,
        k_scale=k_scale, v_scale=v_scale, t_tokens=T, layer=layer,
        interpret=interpret,
    )
    out_pool = out_pool.reshape(B, KVH, T, G, d)
    lse = lse.reshape(B, KVH, T, G)

    # New-slot scores [B, KVH, T, G, T]: token t attends the step's own
    # slots j <= t (a token may attend itself; positions are consecutive
    # so j <= t IS the positional mask).
    s_new = jnp.einsum(
        "btkgd,bjkd->bktgj", q5, k_new,
        preferred_element_type=jnp.float32,
    ) * scale
    t_idx = jnp.arange(T, dtype=jnp.int32)
    causal = t_idx[:, None] >= t_idx[None, :]  # [T(t), T(j)]
    s_new = jnp.where(causal[None, None, :, None, :], s_new, MASK_VALUE)

    m_tot = jnp.maximum(lse, jnp.max(s_new, axis=-1))  # [B, KVH, T, G]
    w_pool = jnp.exp(lse - m_tot)
    p_new = jnp.exp(s_new - m_tot[..., None])          # [B, KVH, T, G, T]
    p_new = jnp.where(causal[None, None, :, None, :], p_new, 0.0)
    denom = w_pool + jnp.sum(p_new, axis=-1)
    new_contrib = jnp.einsum(
        "bktgj,bjkd->bktgd", p_new, v_new.astype(jnp.float32),
    )
    out = (
        out_pool.astype(jnp.float32) * w_pool[..., None] + new_contrib
    ) / denom[..., None]
    out = jnp.swapaxes(out, 1, 2).reshape(B, T, H, d)
    return out.astype(q.dtype)
