"""Pallas TPU flash attention — blockwise online-softmax, GQA-aware.

This is the long-context answer to the reference's O(S²) attention (the
reference materializes a ``[1,1,S,S]`` causal mask at module setup,
``/root/reference/jax_llama/model.py:154``, and full ``[B,H,S,S]`` attention
weights, model.py:277-288).  Here scores only ever exist one
``[block_q, block_k]`` tile at a time in VMEM; masking is recomputed from
absolute positions inside the kernel, so memory is O(S·d) and sequence
length is bounded by HBM, not by the S×S buffer.

Algorithm: standard flash attention (online softmax).  Grid is
``(batch, q_heads, q_blocks, k_blocks)`` with the k axis innermost — TPU
executes the grid sequentially, so VMEM scratch (running max ``m``, running
denominator ``l``, fp32 accumulator ``acc``) persists across the k-block
sweep of each q block.  The output tile is written once, on the last
k step.

Masking is positional, matching ``ops.attention.attention_bias``:
a kv slot is attendable iff ``kv_pos <= q_pos`` (causality) and
``kv_pos >= 0`` (-1 marks padding / unwritten cache slots).  GQA is folded
into the index map — query head ``h`` reads KV head ``h // group`` — so KV
blocks are never replicated in memory (parity with the reference's
repeat-after-cache semantics, model.py:269-270, with zero copies).

Chunk-windowed prefill contract (fused prefill-decode scheduling,
``serving._fused_chunk``): because masking is purely positional, the
kernel needs NO special case to prefill a WINDOW of a prompt into an
existing cache row at a nonzero base offset — the queries arrive as a
[1, C] chunk whose positions start at ``base + off`` (``base`` = fill0
for prefix-cache hit rows, which begin their chunk walk there), and the
kv side is the row's gathered view where slots below the write offset
carry earlier chunks' (or the reused prefix's) real positions and
everything above carries -1.  Causality + the -1 rule then yield
exactly the window's attention set; the only caller obligation is the
scalar cache index (the per-row-index vector form routes to the XLA
path before reaching this kernel) and the view-capacity clamp on the
write window (``serving.ContinuousBatcher._pf_chunk``).  The serving
fault drills exercise this path through the same ``_maybe_fault``
trace hook as ordinary prefill.

The latent-attention block's prompt chunks have a kernel of their own,
``latent_flash_attention``: the same online softmax over LATENT rows, a
head's K/V rebuilt in vector memory a key tile at a time, walking a cache's
live tiles in place and then the chunk's own rows (models/mla_moe.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite stand-in for -inf: fully-masked tiles then accumulate a bogus-but-
# finite (l, acc) that the online-softmax rescale zeroes out the moment a
# real score arrives (exp(MASK - real) == 0), and rows that stay fully
# masked divide by a nonzero l instead of producing NaN.
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)

_LANES = 128  # TPU lane width
_SUBLANES = 8  # TPU sublane width (fp32/int32)
# Forward-kernel k-tile sub-tiling factor (software pipeline: sub-tile
# i+1's MXU dot overlaps sub-tile i's VPU exp/mask work).  Swept on chip;
# tiles not divisible by this fall back to a single sub-tile.
_KSUB = 4

def _maybe_fault() -> None:
    """Chaos-drill hook: fires faults.py's trace-time registry (site
    "flash_kernel") at the kernel entry points' trace time — where a
    Mosaic compile failure would surface on real hardware."""
    from ..faults import fire_trace

    fire_trace("flash_kernel")


def _mix32(x):
    """splitmix32 finalizer: a bijective avalanche mix on uint32.

    The dropout mask must be regenerated bit-identically in THREE kernels
    (forward, dQ sweep, dK/dV sweep) whose grids visit tiles in different
    orders, and must run both compiled (Mosaic) and interpreted (CPU test
    meshes) — ``pltpu.prng_seed`` has no interpret-mode lowering in this
    JAX version, so the mask comes from a counter-based hash of the global
    (row, column) indices instead of hardware PRNG state.  uint32 wraparound
    is the modular arithmetic the constants were designed for.
    """
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _dropout_keep(seed_lo, seed_hi, b, h, row0, col0, bq, bk, rate):
    """Deterministic keep-mask tile [bq, bk] for probability dropout.
    ``row0``/``col0`` are the tile's GLOBAL element offsets (callers pass
    tile_index * tile_size — plus any sub-tile offset), so the hash is a
    pure function of global (row, column) and every tiling of the same
    plane draws identical bits.

    Keyed on (seed, batch, head, global row, global column) so any kernel
    that knows its tile coordinates rebuilds the exact same Bernoulli draw;
    element (r, c) keeps with probability 1 - rate.  The per-call seed is
    TWO uint32 words (64 bits): a single word birthday-collides across
    ~65k training steps per layer, silently reusing whole mask planes.
    Crucially the two words are NOT folded into one 32-bit base (that
    would re-create the same 32-bit birthday horizon, just decorrelated
    across planes): ``seed_lo`` keys the per-ROW words and ``seed_hi``
    the per-COLUMN words, so a repeated mask plane needs both 32-bit
    bases to collide simultaneously — a 64-bit event.  Cost: one extra
    per-column mix [1, bk]; the elementwise [bq, bk] hash is unchanged.

    Row and column enter the element hash JOINTLY (xor of two
    independently mixed words, not ``mix(row_word + col)``): an additive
    column would make every row a shifted window into one 1-D keep
    sequence, so row pairs whose mixed words land within S of each other
    would share diagonal runs of mask bits.
    """
    plane = _mix32(
        b.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
        + h.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
        + jnp.uint32(1)
    )
    base_lo = _mix32(seed_lo ^ plane)
    # The lane constant keeps base_hi independent of base_lo when
    # seed_hi == seed_lo (e.g. a widened legacy seed of 0).
    base_hi = _mix32(seed_hi ^ plane ^ jnp.uint32(0x85EBCA6B))
    rows = jax.lax.broadcasted_iota(jnp.uint32, (bq, 1), 0) + jnp.asarray(
        row0
    ).astype(jnp.uint32)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (1, bk), 1) + jnp.asarray(
        col0
    ).astype(jnp.uint32)
    bits = _mix32(
        _mix32(base_lo ^ rows)
        ^ _mix32(base_hi ^ (cols * jnp.uint32(0x9E3779B9)))
    )
    threshold = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return bits >= threshold


def _tri_ok(bq_s, bk_s, quantized=False) -> bool:
    """`_tri_gate`'s static half: the tile shapes the ragged bodies take."""
    return (
        not quantized
        and _KSUB >= 2  # the safety fold is vacuous at 1 sub-tile
        and bk_s % _KSUB == 0 and bk_s > _KSUB
        and bq_s % _KSUB == 0 and bq_s > _KSUB
        and (bq_s // _KSUB) % _SUBLANES == 0
        and (bk_s // _KSUB) % _SUBLANES == 0
    )


def _tri_safe(q_pos_p, kv_pos_p, bq_s, bk_s):
    """`_tri_gate`'s dynamic half for every (q block, kv block) at once, from
    the padded position planes [B, Tp] / [B, Sp] (dead kv slots at +INT_MAX):
    [B, nq, nk] bool, for a kernel that gates on scalars."""
    B = q_pos_p.shape[0]
    q_sub = q_pos_p.reshape(B, -1, _KSUB, bq_s // _KSUB).max(axis=3)
    k_sub = kv_pos_p.reshape(B, -1, _KSUB, bk_s // _KSUB).min(axis=3)
    q_upto = jax.lax.cummax(q_sub, axis=2)  # max(qp[:(i + 1) * rq])
    return jnp.all(
        q_upto[:, :, None, :-1] < k_sub[:, None, :, 1:], axis=3)


def _tri_gate(qp, kp, bq_s, bk_s, quantized=False):
    """Shared gate for the three kernels' ragged diagonal bodies:
    ``(tri_ok, safe)`` where ``tri_ok`` is the STATIC shape check (sub-
    tilable both axes, and both sub-tile granularities sublane-aligned —
    the ragged bodies slice k/q tiles and store scratch row/column
    blocks at those granularities) and ``safe`` the DYNAMIC triangle-
    safety fold, ``None`` when ``tri_ok`` is False.

    One predicate serves all three kernels: the forward/dQ bodies skip
    (row block j) × (k sub-tile i) for j < i and the dK/dV body skips
    (q sub-tile i) × (column suffix past i) — both skip sets reduce to
    the same pairwise condition max(qp[block j]) < min(kp[block c]) for
    every j < c, which the prefix-max fold below checks exactly.
    (+INT_MAX padding slots never lower a block min, so padding can
    never unsoundly enable a skip.)
    """
    if not _tri_ok(bq_s, bk_s, quantized):
        return False, None
    rq = bq_s // _KSUB
    ksub = bk_s // _KSUB
    safe = None
    for i in range(1, _KSUB):
        cond = jnp.max(qp[: i * rq]) < jnp.min(
            kp[:, i * ksub:(i + 1) * ksub]
        )
        safe = cond if safe is None else (safe & cond)
    return True, safe


def _flash_tri_tile_update(
    q_ref, k_ref, v_ref, seed_ref,
    m_ref, l_ref, acc_ref, qp, kp, bi, hi, qi, ki,
    *, scale, dropout_rate, window=None, mask_ref=None,
):
    """Diagonal-crossing tile update with RAGGED sub-tile dots: k sub-tile
    ``i`` computes only query rows ``[i·rq:]`` — ``_KSUB`` shrinking dots
    (bq, bq−rq, … rows) whose union is exactly the live trapezoid plus
    the sub-diagonal halves, skipping the 37.5% of the tile's MXU work
    that the uniform body burned on fully-masked rows.  Correct only
    when the skipped (row-block j < sub-tile i) regions are provably
    dead — the caller guards with a dynamic triangle-safety predicate
    (ascending positions make it true for every causal crossing tile)
    and falls back to the full masked body otherwise.  State lands
    per row-block through static scratch slices (no ragged concat of
    the accumulator).  bf16-only (the quantized path keeps the
    single-tile body).
    """
    q = q_ref[0, 0]  # [bq, d]
    bq = q.shape[0]
    bk = k_ref.shape[2]
    nsub = _KSUB
    ksub = bk // nsub
    rq = bq // nsub
    allowed = kp <= qp  # [bq, bk]
    if window is not None:
        # The window masks rows the ragged body still computes; what it
        # skips is skipped on causal grounds alone.
        allowed = allowed & (kp > qp - window)
    if mask_ref is not None:
        # The selection only removes pairs: the ragged body's causal skips
        # stay sound.
        allowed = allowed & (mask_ref[0].astype(jnp.int32) != 0)
    m_prev = m_ref[:, :1]  # [bq, 1]

    s_parts = []  # s_i: [bq - i*rq, ksub]
    m_parts = []  # row maxes, ragged
    for i in range(nsub):
        cols = slice(i * ksub, (i + 1) * ksub)
        kb = k_ref[0, 0, cols, :]
        s_i = jax.lax.dot_general(
            q[i * rq:], kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [(bq - i*rq), ksub], base-2 domain
        s_i = jnp.where(allowed[i * rq:, cols], s_i, MASK_VALUE)
        s_parts.append(s_i)
        m_parts.append(s_i.max(axis=-1, keepdims=True))

    # Per-row-block joint max: row block j is touched by sub-tiles
    # i <= j; m_parts[i]'s rows start at global row i*rq.
    m_blocks = []
    for j in range(nsub):
        mj = m_prev[j * rq:(j + 1) * rq]
        for i in range(j + 1):
            mj = jnp.maximum(
                mj, m_parts[i][(j - i) * rq:(j - i + 1) * rq]
            )
        m_blocks.append(mj)

    inv = 1.0 / (1.0 - dropout_rate) if dropout_rate > 0.0 else None
    # exp2 + rowsum + PV per sub-tile (rows [i*rq:] only), then land
    # each row block's state once.
    r_parts = []  # [bq - i*rq, 1] rowsums
    d_parts = []  # [bq - i*rq, d] fp32 PV partials
    for i in range(nsub):
        cols = slice(i * ksub, (i + 1) * ksub)
        m_rows = jnp.concatenate(m_blocks[i:], axis=0)
        p = jnp.exp2(s_parts[i] - m_rows)
        r_parts.append(jnp.sum(p, axis=-1, keepdims=True))
        if dropout_rate > 0.0:
            keep = _dropout_keep(
                seed_ref[0], seed_ref[1], bi, hi,
                qi * bq + i * rq, ki * bk + i * ksub,
                bq - i * rq, ksub, dropout_rate,
            )
            p_acc = jnp.where(keep, p, 0.0) * inv
        else:
            p_acc = p
        d_parts.append(jax.lax.dot_general(
            p_acc.astype(v_ref.dtype), v_ref[0, 0, cols, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))

    for j in range(nsub):
        rows = slice(j * rq, (j + 1) * rq)
        alpha_j = jnp.exp2(m_prev[rows] - m_blocks[j])
        l_j = alpha_j * l_ref[rows, :1]
        acc_j = alpha_j * acc_ref[rows]
        for i in range(j + 1):
            sub = slice((j - i) * rq, (j - i + 1) * rq)
            l_j = l_j + r_parts[i][sub]
            acc_j = acc_j + d_parts[i][sub]
        acc_ref[rows] = acc_j
        m_ref[rows] = jnp.broadcast_to(m_blocks[j], (rq, m_ref.shape[1]))
        l_ref[rows] = jnp.broadcast_to(l_j, (rq, l_ref.shape[1]))


def _flash_kernel(
    kv_bound_ref,  # [B * nq] int32 scalar-prefetch: kv-block grid bound
    *args,  # [seed_ref] when dropout; q_pos/kv_pos/q/k/v refs;
    #         [k_scale_ref, v_scale_ref] when quantized; o_ref;
    #         (lse_ref,) when with_lse; then m/l/acc scratch
    scale: float,
    with_lse: bool,
    quantized: bool = False,
    dropout_rate: float = 0.0,
    windowed: bool = False,
    masked: bool = False,
):
    if windowed:
        # [B * nq] int32: the first kv block of this q block's sweep, and
        # [1] int32: the window (a query sees its own position and the
        # window - 1 before it).  Grid step ki visits block start + ki.
        kv_start_ref, window_ref, *args = args
    if dropout_rate > 0.0:
        seed_ref, *args = args  # [2] uint32 scalar-prefetch (64-bit seed)
    else:
        seed_ref = None
    q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref, *rest = args
    if masked:
        # [1, bq, bk] int8: nonzero where the query's selection holds the key
        mask_ref, *rest = rest
    else:
        mask_ref = None
    # q_pos_ref: [1, bq, 1] int32 (narrow-lane view)
    # kv_pos_ref: [1, 1, bk] int32 (narrow-sublane view)
    # q_ref: [1, 1, bq, d]; k_ref/v_ref: [1, 1, bk, d] (int8 when quantized)
    if quantized:
        k_scale_ref, v_scale_ref, *rest = rest  # [1, 1, SUBLANES, bk] fp32
    else:
        k_scale_ref = v_scale_ref = None
    o_ref, *rest = rest
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        (m_ref, l_ref, acc_ref), lse_ref = rest, None
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    # program_id must be read OUTSIDE pl.when bodies (no interpret-mode
    # lowering inside the cond branch); the dropout hash closes over these.
    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Narrow-sublane/lane position views (1-row tiles compile fine on
    # Mosaic — no replicated copies, no extra HBM traffic).
    qp = q_pos_ref[0, :, :1]  # [bq, 1]
    kp = kv_pos_ref[0, :1, :]  # [1, bk]

    # Grid-level dead-block skip: past this q block's kv bound the index
    # maps clamp to the boundary block (already-fetched — no new DMA) and
    # the tile must not be processed again.
    row_block = pl.program_id(0) * pl.num_programs(2) + pl.program_id(2)
    if windowed:
        window = window_ref[0]
        in_bound = kv_start_ref[row_block] + ki < kv_bound_ref[row_block]
    else:
        window = None
        in_bound = ki < kv_bound_ref[row_block]
    # Block-level causal skip: if the smallest kv position in this block
    # exceeds every query position, no (q, kv) pair is attendable and
    # both dots + the softmax update can be skipped — for standard causal
    # prefill that halves the MXU work (every block above the diagonal).
    # Padding slots carry +INT_MAX here (the wrappers remap the public -1
    # convention before the kernel), so they exclude themselves from this
    # min AND from the single `kp <= qp` compare below — the kernel's
    # per-element mask chain is one compare + one select, not two
    # compares + and + select.  An all-padding block is skipped too (the
    # finalize guards l == 0 for rows that never attend).
    block_live = in_bound & (jnp.min(kp) <= jnp.max(qp))

    # r5: diagonal-crossing tiles take a RAGGED body that skips the dead
    # upper-triangle MXU work (see _flash_tri_tile_update) — the one
    # lever that moved after r4's sub-tile pipeline.  Gated statically
    # on shapes (sub-tilable, row blocks sublane-aligned, bf16) and
    # dynamically on triangle safety: the ragged body skips row block
    # j < sub-tile i entirely, sound iff max(qp[:i·rq]) < min(kp of
    # sub-tile i) for every i — true on every crossing tile of an
    # ascending position layout (causal prefill, cache layouts), false
    # for interior tiles and exotic layouts, which take the uniform
    # masked body below.  (+INT_MAX padding slots never lower the min.)
    # Negative results, xplane kernel-only at 16k vs the 8.35 ms / 66.8%
    # r4 baseline: a maskless interior-tile body variant measured
    # SLOWER (8.52 ms — the per-element mask select was already
    # overlapped; three bodies cost more than the select), as did
    # per-sub-tile exp bases with a correction tail (13.98 ms — holding
    # nsub [bq, d] fp32 PV partials wrecks Mosaic's schedule) and
    # hoisting the row-max reduces into the dot loop (exactly neutral —
    # the r4 "joint-max barrier" hypothesis is closed: it never cost
    # anything).
    tri_ok, safe = _tri_gate(
        qp, kp, q_ref.shape[2], k_ref.shape[2], quantized=quantized
    )
    if tri_ok:
        tri_live = block_live & safe
        full_live = block_live & jnp.logical_not(safe)

        @pl.when(tri_live)
        def _compute_tri():
            _flash_tri_tile_update(
                q_ref, k_ref, v_ref, seed_ref,
                m_ref, l_ref, acc_ref, qp, kp, bi, hi, qi, ki,
                scale=scale, dropout_rate=dropout_rate, window=window,
                mask_ref=mask_ref,
            )
    else:
        full_live = block_live

    @pl.when(full_live)
    def _compute():
        q = q_ref[0, 0]  # [bq, d]
        bq = q.shape[0]
        bk = k_ref.shape[2]
        # Software pipeline: the k tile is processed as ``nsub`` sub-tiles
        # so VPU softmax work and MXU dots of DIFFERENT sub-tiles are
        # dataflow-independent and Mosaic can overlap them — the r3
        # single-tile body serialized dot -> mask/max/exp -> dot, idling
        # the MXU through every exp sweep (kernel-only ~56% MXU at 16k
        # while the model's plain matmuls run ~90%).  Structure: all QK
        # dots issue first (each sub-tile's mask/scale select overlaps the
        # NEXT sub-tile's dot), one joint row max (same m as the
        # single-tile form — the online-softmax state update stays
        # once-per-tile), then each sub-tile's exp2 overlaps the previous
        # sub-tile's PV dot.
        # Quantized keeps the single-tile body: the per-sub-tile [1, ksub]
        # dequant-scale slices hit the same unsupported Mosaic layout as
        # narrow position slices, and the int8 path is inference
        # long-context decode — the pipeline win targets bf16
        # prefill/training.
        nsub = (
            _KSUB
            if (bk % _KSUB == 0 and bk > _KSUB and not quantized)
            else 1
        )
        ksub = bk // nsub
        if quantized:
            # int8 KV: cast the payload tile to the compute dtype in VMEM
            # (int8 magnitudes <= 127 are exact in bf16) and fold the
            # per-slot dequant scale into the SCORES — constant along d,
            # it commutes with the contraction, so HBM only ever streams
            # the int8 bytes (half the cache traffic of bf16).
            # NB: folding the scale into q outside the kernel was tried
            # and measured ~15% SLOWER on v5e (A/B, min-of-5
            # differencing) — the fused multiply here rides the MXU
            # output for free.
            ksc = k_scale_ref[0, 0, :1, :]  # [1, bk] fp32
        else:
            ksc = None
        # The online softmax runs in BASE 2: log2(e) is pre-folded into
        # `scale` (see _flash_forward), so the per-element transcendental
        # is a bare exp2 — the VPU's native exponent — instead of exp's
        # exp2(x·log2e) with its extra wide multiply.  exp2(s2 - m2)
        # equals exp(s - m) exactly in the mask limit too (MASK_VALUE is
        # a huge negative in either base).
        # Full-width mask compare once (narrow sub-tile broadcasts of the
        # 1-row position plane hit unsupported Mosaic layouts), sliced
        # per sub-tile below.
        allowed = kp <= qp  # [bq, bk]
        if windowed:
            allowed = allowed & (kp > qp - window)
        if masked:
            allowed = allowed & (mask_ref[0].astype(jnp.int32) != 0)
        s_parts = []
        for i in range(nsub):
            cols = slice(i * ksub, (i + 1) * ksub)
            kb = k_ref[0, 0, cols, :]
            if quantized:
                kb = kb.astype(q.dtype)
            s_i = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [bq, ksub], base-2 domain
            if quantized:
                s_i = s_i * ksc[:, cols]
            s_parts.append(
                jnp.where(allowed[:, cols], s_i, MASK_VALUE)
            )

        m_prev = m_ref[:, :1]  # [bq, 1]
        m_cur = s_parts[0].max(axis=-1, keepdims=True)
        for s_i in s_parts[1:]:
            m_cur = jnp.maximum(m_cur, s_i.max(axis=-1, keepdims=True))
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp2(m_prev - m_new)  # [bq, 1] rescale of old state

        l_add = None
        acc_add = None
        for i in range(nsub):
            cols = slice(i * ksub, (i + 1) * ksub)
            p = jnp.exp2(s_parts[i] - m_new)  # [bq, ksub]
            ps = jnp.sum(p, axis=-1, keepdims=True)
            l_add = ps if l_add is None else l_add + ps
            if dropout_rate > 0.0:
                # Probability dropout (training): the final output is
                # acc / l, so zeroing entries of the acc-side p while
                # keeping the denominator's p intact is EXACTLY inverted
                # dropout applied to the post-softmax weights w = p / l —
                # the xla path's semantics (ops.attention.sdpa),
                # blockwise.  Global element offsets key the hash, so the
                # sub-tiling draws the identical bits the (untiled)
                # backward kernels rebuild.
                keep = _dropout_keep(
                    seed_ref[0], seed_ref[1], bi, hi,
                    qi * bq, ki * bk + i * ksub, bq, ksub, dropout_rate,
                )
                p_acc = jnp.where(keep, p, 0.0) * (
                    1.0 / (1.0 - dropout_rate)
                )
            else:
                p_acc = p
            if quantized:
                # v_scale folds into the (tiny) probabilities, mirroring
                # sdpa_cached's weights-level folding on the XLA path.
                pv = (p_acc * v_scale_ref[0, 0, :1, cols]).astype(q.dtype)
                vb = v_ref[0, 0, cols, :].astype(q.dtype)
            else:
                pv = p_acc.astype(v_ref.dtype)
                vb = v_ref[0, 0, cols, :]
            d_i = jax.lax.dot_general(
                pv, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_add = d_i if acc_add is None else acc_add + d_i

        l_new = alpha * l_ref[:, :1] + l_add
        acc_ref[:] = alpha * acc_ref[:] + acc_add
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        # l == 0 iff the row never saw a live kv slot (every block skipped);
        # emit 0 instead of 0/0 NaN.
        o_ref[0, 0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype
        )
        if with_lse:
            # Row logsumexp of the (scaled, masked) scores — the backward
            # kernels rebuild P = exp(s - lse) from it without storing
            # any S×S tensor.  Narrow-lane [bq, 1] (the lane-replicated
            # form cost 128x the lse bytes at long context).  m/l live in
            # the base-2 domain (see _compute); convert once per row so
            # the backward kernels stay in natural log.
            lse_ref[0, 0] = (
                m_ref[:, :1] + jnp.log2(
                    jnp.where(l_ref[:, :1] == 0.0, 1.0, l_ref[:, :1])
                )
            ) * float(np.log(2.0))


def _normalize_seed(dropout_seed) -> jnp.ndarray:
    """Widen a scalar / [1] / [2] uint32 seed to the kernels' [2]-word
    (64-bit) layout; legacy single-word callers get a zero high word."""
    seed = jnp.asarray(dropout_seed, jnp.uint32).reshape(-1)
    if seed.size == 1:
        return jnp.concatenate([seed, jnp.zeros((1,), jnp.uint32)])
    if seed.size != 2:
        raise ValueError(
            f"dropout_seed must hold 1 or 2 uint32 words, got {seed.size}"
        )
    return seed


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "interpret", "dropout_rate"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    block_q: int = 2048,
    block_k: int = 2048,
    interpret: Optional[bool] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jnp.ndarray] = None,
    window: Optional[jnp.ndarray] = None,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Blockwise attention; drop-in for ``ops.attention.sdpa`` + bias.

    Differentiable end-to-end in O(S·d) memory: the forward kernel saves
    the per-row logsumexp, and the backward runs two Pallas kernels
    (dQ sweep and dK/dV sweep) that rebuild probabilities tile-by-tile —
    no [T, S] score matrix exists in either direction, so 32k+ training
    contexts fit.

    Args:
      q: [B, T, H, d].
      k, v: [B, S, KVH, d], H % KVH == 0 (GQA).
      q_pos: [B, T] int32 absolute query positions (pre-clamped >= 0).
      kv_pos: [B, S] int32 kv slot positions, -1 for padding/unwritten.
      block_q, block_k: tile sizes (clamped to T / S).  Swept on a v5e
        with xplane device-time measurement (r4): with the sub-tiled
        software pipeline (_KSUB) and the 64 MiB scoped-vmem budget,
        (2048, 2048) runs the 16k forward at 66% MXU vs 56.5% for the r3
        (1024, 2048) default, and wins the fwd+bwd step too; larger
        tiles ((1024, 4096)+) lose it again — diagonal dead work and DMA
        overtake the per-step saving.
      dropout_rate: attention-probability dropout (training; parity with
        the reference's attn_pdrop, model.py:276-288, and with
        ``ops.attention.sdpa``'s inverted-dropout semantics).  The mask is
        generated *inside* the kernels from a counter-based hash — never
        materialized at [T, S] — and the backward kernels rebuild the
        identical mask, so gradients see exactly the forward's draw.
      dropout_seed: [2] uint32 seed words (64 bits; scalar / [1] inputs
        are widened with a zero high word); required when
        dropout_rate > 0.  Derive per call site, e.g. via
        ``jax.random.bits(key, (2,), "uint32")``.
      window: optional int32 scalar, a VALUE (a layer's own inside a layer
        scan): a query at position i sees the keys at i - window + 1 .. i.
        A window layer does a window's work: per q block the k sweep
        starts at the first kv block holding a slot inside some query's
        window (``kv_start``, the lower twin of ``kv_bound``), so blocks
        wholly before it are neither fetched nor computed.  Inference
        only (no VJP, no dropout).  None: the program without a window,
        unchanged.
      mask: optional [B, T, S] int8 operand, nonzero where query t may see
        slot s BESIDE the positional rule (a learned selection,
        models/dsa_moe.py): every head of a query shares its row.  The k
        sweep and its bounds stay the causal ones; a tile of the mask rides
        each (q block, k block) step.  Inference only.  None: the program
        without one, unchanged.
    Returns:
      [B, T, H, d] in q.dtype.
    """
    _maybe_fault()
    H, KVH = q.shape[2], k.shape[2]
    assert H % KVH == 0, (H, KVH)
    group = H // KVH
    if (window is not None or mask is not None) and dropout_rate > 0.0:
        raise ValueError(
            "the window and mask forms of flash_attention are inference-only")
    if window is not None and mask is not None:
        raise ValueError("flash_attention takes a window or a mask, not both")
    if not 0.0 <= dropout_rate < 1.0:
        # Validate BEFORE the >0 branch: a negative rate must raise, not
        # silently train without dropout.
        raise ValueError(f"dropout_rate={dropout_rate} not in [0, 1)")
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        seed = _normalize_seed(dropout_seed)
    else:
        seed = jnp.zeros((2,), jnp.uint32)
    if group > 1:
        # GQA query packing: fold the `group` query heads of each KV head
        # into the query-row axis, so the kernel grid runs over KV heads
        # and each KV block streams from HBM *once* per KV head instead of
        # once per query head (group x less KV-cache traffic — dominant in
        # long-context decode).  Masking is purely positional, so packing
        # is just a relayout: row r = g*T + t keeps position q_pos[t].
        # Dropout keys off the PACKED row index, so each (head, query)
        # pair still draws independently.
        B, T = q.shape[:2]
        qp = jnp.moveaxis(
            q.reshape(B, T, KVH, group, -1), 3, 1
        ).reshape(B, group * T, KVH, -1)
        pos_p = jnp.tile(q_pos, (1, group))
        if window is not None or mask is not None:
            out = _flash_forward(
                qp, k, v, pos_p, kv_pos, block_q, block_k, interpret,
                window=window, mask=mask,
            )
        else:
            out = _flash(
                qp, k, v, pos_p, kv_pos, seed, block_q, block_k, interpret,
                dropout_rate,
            )
        out = jnp.moveaxis(
            out.reshape(B, group, T, KVH, -1), 1, 3
        ).reshape(B, T, H, -1)
        return out
    if window is not None or mask is not None:
        return _flash_forward(
            q, k, v, q_pos, kv_pos, block_q, block_k, interpret,
            window=window, mask=mask,
        )
    return _flash(
        q, k, v, q_pos, kv_pos, seed, block_q, block_k, interpret,
        dropout_rate,
    )


def _sub_tiles(bk: int):
    """(count, width) of a key tile's sub-tiles: `_flash_kernel`'s rule."""
    nsub = _KSUB if (bk % _KSUB == 0 and bk > _KSUB) else 1
    return nsub, bk // nsub


def _latent_rebuild(rows_ref, kvb_ref, k_ref, kr_ref, v_ref, n_valid=None):
    """K and V of this head for one key tile, rebuilt in vector memory from
    the tile's latent rows [bk, w] (c | rope key | lane padding) into the
    scratch the softmax bodies read: ``c @ kv_b[h]`` on the MXU a sub-tile
    at a time, rounded to the activation dtype, split into the nope key and
    the value; the rows' rope columns beside them.  ``n_valid`` (a value):
    rows of the tile at or past it lie outside the array, and their values
    are zeroed (their scores are masked by position)."""
    bk, r = rows_ref.shape[0], kvb_ref.shape[0]
    dn, dr = k_ref.shape[1], kr_ref.shape[1]
    nsub, ksub = _sub_tiles(bk)
    for i in range(nsub):
        cols = slice(i * ksub, (i + 1) * ksub)
        kv = jax.lax.dot_general(
            rows_ref[cols, :r].astype(k_ref.dtype), kvb_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ).astype(k_ref.dtype)  # [ksub, nope | dv]
        v_i = kv[:, dn:]
        if n_valid is not None:
            inside = i * ksub + jax.lax.broadcasted_iota(
                jnp.int32, (ksub, 1), 0) < n_valid
            v_i = jnp.where(inside, v_i, jnp.zeros_like(v_i))
        k_ref[cols, :] = kv[:, :dn]
        v_ref[cols, :] = v_i
        kr_ref[cols, :] = rows_ref[cols, r:r + dr].astype(kr_ref.dtype)


def _latent_tile_update(
    qn_ref, qr_ref, k_ref, kr_ref, v_ref, m_ref, l_ref, acc_ref, qp, kp,
    *, scale, ragged,
):
    """The online-softmax update of one key tile whose K/V stand rebuilt in
    scratch (rows [:bk], bk = kp's width).  The score is two products, nope
    against the rebuilt key and rope against the tile's one rope key, and
    the value keeps its own width.  ``_flash_kernel``'s two bodies in one:
    uniform (every query row against every sub-tile, one joint row max) or,
    with ``ragged``, ``_flash_tri_tile_update``'s shrinking dots on a
    triangle-safe diagonal tile (sub-tile i computes query rows [i*rq:]
    only)."""
    qn, qr = qn_ref[...], qr_ref[...]
    bq, bk = qn.shape[0], kp.shape[1]
    nsub, ksub = _sub_tiles(bk)
    rq = bq // nsub if ragged else 0  # sub-tile i starts at query row i*rq
    nt = (((1,), (1,)), ((), ()))
    allowed = kp <= qp  # [bq, bk]

    s_parts, m_parts = [], []
    for i in range(nsub):
        cols = slice(i * ksub, (i + 1) * ksub)
        s_i = jax.lax.dot_general(
            qn[i * rq:], k_ref[cols, :], nt,
            preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            qr[i * rq:], kr_ref[cols, :], nt,
            preferred_element_type=jnp.float32,
        )  # [bq - i*rq, ksub]
        s_i = jnp.where(allowed[i * rq:, cols], s_i * scale, MASK_VALUE)
        s_parts.append(s_i)
        m_parts.append(s_i.max(axis=-1, keepdims=True))

    # Row blocks (lo, hi, the sub-tiles that touch them): one block under
    # every sub-tile, or block j under the sub-tiles i <= j.
    if ragged:
        blocks = [(j * rq, (j + 1) * rq, range(j + 1)) for j in range(nsub)]
    else:
        blocks = [(0, bq, range(nsub))]
    m_prev = m_ref[:, :1]
    m_blocks = []
    for lo, hi, parts in blocks:
        mj = m_prev[lo:hi]
        for i in parts:
            mj = jnp.maximum(mj, m_parts[i][lo - i * rq:hi - i * rq])
        m_blocks.append(mj)

    r_parts, d_parts = [], []  # row sums, fp32 PV partials of rows [i*rq:]
    for i in range(nsub):
        cols = slice(i * ksub, (i + 1) * ksub)
        m_rows = jnp.concatenate(m_blocks[i:], axis=0) if ragged else m_blocks[0]
        p = jnp.exp2(s_parts[i] - m_rows)
        r_i = jnp.sum(p, axis=-1, keepdims=True)
        d_i = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[cols, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if ragged or i == 0:
            r_parts.append(r_i)
            d_parts.append(d_i)
        else:
            # One row block: summed as they come (nsub [bq, dv] fp32
            # partials held to the end cost `_flash_kernel` its schedule).
            r_parts[0], d_parts[0] = r_parts[0] + r_i, d_parts[0] + d_i
    if not ragged:
        blocks = [(0, bq, range(1))]

    for (lo, hi, parts), mj in zip(blocks, m_blocks):
        alpha = jnp.exp2(m_prev[lo:hi] - mj)
        l_j = alpha * l_ref[lo:hi, :1]
        acc_j = alpha * acc_ref[lo:hi]
        for i in parts:
            sub = slice(lo - i * rq, hi - i * rq)
            l_j = l_j + r_parts[i][sub]
            acc_j = acc_j + d_parts[i][sub]
        acc_ref[lo:hi] = acc_j
        m_ref[lo:hi] = jnp.broadcast_to(mj, (hi - lo, m_ref.shape[1]))
        l_ref[lo:hi] = jnp.broadcast_to(l_j, (hi - lo, l_ref.shape[1]))


def _latent_kernel(
    sched_ref,  # [2] int32: the cache's layer, its live context tiles
    bound_ref,  # [B * nq] int32: new-row key blocks of a q block's sweep
    qmax_ref,   # [B * nq] int32: a q block's largest position
    kmin_ref,   # [B * (n_ctx + nk)] int32: a key block's smallest position
    safe_ref,   # [B * nq * nk] int32: `_tri_gate`'s fold, (q block, new block)
    *args,  # q_pos, kv_pos, q_nope, q_rope, kv_b, rows, [ctx_pos, ctx] refs;
    #         o_ref; then k/kr/v and m/l/acc scratch
    scale: float,
    n_ctx: int,
    ctx_rows: int,
    tri_ok: bool,
):
    """Grid (B, H, q blocks, n_ctx + key blocks), the key axis innermost:
    steps below ``n_ctx`` walk the cached context's tiles, live while below
    ``sched_ref[1]``; the rest walk the new rows' blocks, live while below
    the q block's causal bound.  A live step rebuilds its tile's K/V into
    scratch, from the cache's tile or from the new rows', and runs ONE
    softmax body on the scratch whichever it came from: the diagonal's
    ragged body or the uniform one (with a third whole-tile body, one a
    source, the diagonal's ran 11 times slower on a v5e: PERF.md section 6,
    PR 54).  Dead steps clamp their index maps (no DMA) and skip everything
    on scalars alone; m, l and the accumulator stay in scratch from the
    first step to the last, which normalises and writes once."""
    q_pos_ref, kv_pos_ref, qn_ref, qr_ref, kvb_ref, rows_ref, *args = args
    if n_ctx:
        ctx_pos_ref, ctx_ref, *args = args
    o_ref, k_ref, kr_ref, v_ref, m_ref, l_ref, acc_ref = args
    bi, ki = pl.program_id(0), pl.program_id(3)
    nk = pl.num_programs(3) - n_ctx
    row_block = bi * pl.num_programs(2) + pl.program_id(2)
    qmax = qmax_ref[row_block]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qp = q_pos_ref[:, :1]  # [bq, 1]
    kp = kv_pos_ref[:1, :]  # [1, bk]
    bk = kp.shape[1]
    rebuild = functools.partial(
        _latent_rebuild, kvb_ref=kvb_ref, k_ref=k_ref, kr_ref=kr_ref,
        v_ref=v_ref)
    update = functools.partial(
        _latent_tile_update, qn_ref, qr_ref, k_ref, kr_ref, v_ref,
        m_ref, l_ref, acc_ref, qp, scale=scale)

    # A block none of whose slots a query may attend (all past the q block's
    # last position, or all dead: +INT_MAX) is skipped like one out of bound.
    si = jnp.clip(ki - n_ctx, 0, nk - 1)
    new_live = (ki >= n_ctx) & (si < bound_ref[row_block]) & (
        kmin_ref[bi * (n_ctx + nk) + n_ctx + si] <= qmax)

    @pl.when(new_live)
    def _rebuild_new():
        rebuild(rows_ref)

    if tri_ok:
        safe = safe_ref[row_block * nk + si] != 0

        @pl.when(new_live & safe)
        def _diagonal():
            update(kp, ragged=True)

        new_live = new_live & jnp.logical_not(safe)

    if n_ctx:
        cp = ctx_pos_ref[:1, :]  # [1, tile]
        tile = cp.shape[1]
        ctx_live = (ki < sched_ref[1]) & (
            kmin_ref[bi * (n_ctx + nk) + jnp.minimum(ki, n_ctx - 1)] <= qmax)
        # A view that is no multiple of the tile: the last tile's rows past
        # the view are whatever the buffer held.
        n_valid = ctx_rows - ki * tile if ctx_rows % tile else None

        @pl.when(ctx_live)
        def _rebuild_context():
            rebuild(ctx_ref, n_valid=n_valid)

        if tile == bk:
            kp = jnp.where(ki < n_ctx, cp, kp)
            new_live = new_live | ctx_live
        else:
            @pl.when(ctx_live)
            def _context():
                update(cp, ragged=False)

    @pl.when(new_live)
    def _uniform():
        update(kp, ragged=False)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[:, :1]
        # l == 0: the row never saw a live slot; 0, not 0/0.
        o_ref[...] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block", "ctx_tile", "interpret")
)
def latent_flash_attention(
    q_nope: jnp.ndarray,
    q_rope: jnp.ndarray,
    rows: jnp.ndarray,
    kv_b: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    ctx: Optional[jnp.ndarray] = None,
    ctx_pos: Optional[jnp.ndarray] = None,
    layer=0,
    ctx_tiles=0,
    block: int = 2048,
    ctx_tile: int = 2048,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Prefill attention of the latent-attention block (models/mla_moe.py)
    over LATENT rows, in one kernel: per head, K and V of a key tile are
    rebuilt in vector memory (``c @ kv_b[h]``, never in HBM), the score is
    ``q_nope . k_nope + q_rope . k_rope`` with the rows' one rope key under
    every head, the value keeps its own width, and the running softmax is
    carried in scratch over the cached context's live tiles and then the
    new rows.  Inference only.  The scores are divided by sqrt(nope + rope);
    any further temperature is the caller's, folded into the query.

    Args:
      q_nope [B, T, H, nope], q_rope [B, T, H, rope] (rotated): the queries.
      rows: [B, S, w] latent rows of the new keys: ``c`` in columns [:r],
        the rotated rope key in [r:r + rope], lane padding behind.
      kv_b: [H, r, nope + dv] in the activation dtype.
      q_pos [B, T], kv_pos [B, S]: positions, -1 a dead key (``flash_attention``).
      ctx: [L, B, view, w] a cache's latent plane, read in place a tile at
        a time; ``ctx_pos`` [B, view] its positions; ``layer`` and
        ``ctx_tiles`` int32 VALUES: the plane's layer, and how many tiles of
        ``ctx_tile`` slots from slot 0 are walked (dead slots inside them
        are masked by their position; a tile at or past the count costs
        neither a copy nor compute).  None: the new rows alone.
      block: query rows and new-key rows a grid step.
    Returns:
      [B, T, H, dv] in q_nope.dtype.
    """
    _maybe_fault()
    B, T, H, dn = q_nope.shape
    S, w = rows.shape[1:]
    dr, dv = q_rope.shape[-1], kv_b.shape[-1] - dn
    interpret = _resolve_interpret(interpret)
    scale = (1.0 / ((dn + dr) ** 0.5)) * float(np.log2(np.e))
    block_q, block_k = _clamp_blocks(T, S, block, block, interpret)
    imax = jnp.iinfo(jnp.int32).max

    def dead_to_imax(pos, mult):
        pos = _pad_to(pos.astype(jnp.int32), 1, mult, value=-1)
        return jnp.where(pos < 0, imax, pos)

    # The new rows are padded to whole blocks here (a few rows of [.., w]);
    # the cache is not: it is read where it lies.
    qn = _pad_to(jnp.swapaxes(q_nope, 1, 2), 2, block_q)  # [B, H, Tp, nope]
    qr = _pad_to(jnp.swapaxes(q_rope, 1, 2), 2, block_q)
    rows_p = _pad_to(rows, 1, block_k)
    q_pos_p = _pad_to(q_pos.astype(jnp.int32), 1, block_q)
    kv_pos_p = dead_to_imax(kv_pos, block_k)
    Tp, Sp = qn.shape[2], rows_p.shape[1]
    nq, nk = Tp // block_q, Sp // block_k

    # What the grid's dead steps are skipped on, as scalars: the live
    # context tiles, a q block's causal bound over the new rows' blocks and
    # its largest position, a key block's smallest, and `_tri_gate`'s fold.
    qmax = jnp.max(q_pos_p.reshape(B, nq, block_q), axis=2)
    kmin_new = jnp.min(kv_pos_p.reshape(B, nk, block_k), axis=2)
    if ctx is None:
        n_ctx = view = 0
        kmin = kmin_new
    else:
        view = ctx.shape[2]
        ctx_tile = min(ctx_tile, view)
        n_ctx = -(-view // ctx_tile)
        ctx_pos_p = dead_to_imax(ctx_pos, ctx_tile)
        kmin = jnp.concatenate([
            jnp.min(ctx_pos_p.reshape(B, n_ctx, ctx_tile), axis=2), kmin_new,
        ], axis=1)  # [B, n_ctx + nk]
    sched = jnp.stack([
        jnp.asarray(layer, jnp.int32),
        jnp.minimum(jnp.asarray(ctx_tiles, jnp.int32), n_ctx),
    ])
    bound = 1 + jnp.max(
        jnp.where(
            kmin_new[:, None, :] <= qmax[:, :, None],
            jnp.arange(nk, dtype=jnp.int32)[None, None, :], -1,
        ),
        axis=2,
    )
    tri_ok = _tri_ok(block_q, block_k)
    safe = (
        _tri_safe(q_pos_p, kv_pos_p, block_q, block_k) if tri_ok
        else jnp.zeros((1,), jnp.int32)
    )
    prefetch = [sched, bound.reshape(-1), qmax.reshape(-1), kmin.reshape(-1),
                safe.reshape(-1).astype(jnp.int32)]

    def ctx_block(ki, sched):
        return jnp.minimum(ki, jnp.maximum(sched[1] - 1, 0))

    def new_block(b, qi, ki, bound):
        return jnp.clip(ki - n_ctx, 0, jnp.maximum(bound[b * nq + qi] - 1, 0))

    def q_row(b, h, qi, ki, *_):
        return (b, h, qi, 0)

    in_specs = [
        pl.BlockSpec((None, block_q, 1), lambda b, h, qi, ki, *_: (b, qi, 0)),
        pl.BlockSpec(
            (None, 1, block_k),
            lambda b, h, qi, ki, sched, bound, *_: (
                b, 0, new_block(b, qi, ki, bound))),
        pl.BlockSpec((None, None, block_q, dn), q_row),
        pl.BlockSpec((None, None, block_q, dr), q_row),
        pl.BlockSpec(
            (None,) + kv_b.shape[1:], lambda b, h, qi, ki, *_: (h, 0, 0)),
        pl.BlockSpec(
            (None, block_k, w),
            lambda b, h, qi, ki, sched, bound, *_: (
                b, new_block(b, qi, ki, bound), 0)),
    ]
    operands = [q_pos_p[:, :, None], kv_pos_p[:, None, :], qn, qr, kv_b, rows_p]
    if n_ctx:
        in_specs += [
            pl.BlockSpec(
                (None, 1, ctx_tile),
                lambda b, h, qi, ki, sched, *_: (b, 0, ctx_block(ki, sched))),
            pl.BlockSpec(
                (None, None, ctx_tile, w),
                lambda b, h, qi, ki, sched, *_: (
                    sched[0], b, ctx_block(ki, sched), 0)),
        ]
        operands += [ctx_pos_p[:, None, :], ctx]

    adt = q_nope.dtype
    key_rows = max(block_k, ctx_tile if n_ctx else 0)
    out = pl.pallas_call(
        functools.partial(
            _latent_kernel, scale=scale, n_ctx=n_ctx, ctx_rows=view,
            tri_ok=tri_ok),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, H, nq, n_ctx + nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, None, block_q, dv), q_row),
            scratch_shapes=[
                pltpu.VMEM((key_rows, dn), adt),
                pltpu.VMEM((key_rows, dr), adt),
                pltpu.VMEM((key_rows, dv), adt),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, dv), adt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"),
            # as `_flash_forward`'s, plus the two latent tiles' buffers
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        interpret=interpret,
        name="latent_flash_attention",
    )(*prefetch, *operands)
    return jnp.swapaxes(out[:, :, :T, :], 1, 2)  # [B, T, H, dv]


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret")
)
def flash_attention_quantized(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_scale: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    block_q: int = 1024,
    block_k: int = 2048,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention over an int8 KV cache (inference-only, no VJP).

    Same semantics as ``flash_attention`` with
    ``k[b,s,h,:] * k_scale[b,s,h]`` / ``v * v_scale`` as the effective
    keys/values — but the dequantization happens inside the kernel
    (scores-level for K, probability-level for V, matching
    ``ops.attention.sdpa_cached``'s folding), so HBM streams the int8
    payload, never a dequantized copy.

    Default tiles stay at the r3-swept (1024, 2048): the int8 body is
    excluded from the bf16 path's sub-tiled software pipeline (narrow
    scale slices hit an unsupported Mosaic layout), so the (2048, 2048)
    default that pipeline justified does not transfer — larger q tiles
    only add diagonal dead work to the unpipelined body.

    Args:
      q: [B, T, H, d] activation dtype.
      k, v: [B, S, KVH, d] int8.
      k_scale, v_scale: [B, S, KVH] fp32 per-slot-per-head scales.
      q_pos, kv_pos, block_q, block_k: as in ``flash_attention``.
    """
    _maybe_fault()
    H, KVH = q.shape[2], k.shape[2]
    assert H % KVH == 0, (H, KVH)
    group = H // KVH
    if group > 1:
        # Same GQA query packing as flash_attention: scales are per KV
        # head, so they need no relayout.
        B, T = q.shape[:2]
        qp = jnp.moveaxis(
            q.reshape(B, T, KVH, group, -1), 3, 1
        ).reshape(B, group * T, KVH, -1)
        pos_p = jnp.tile(q_pos, (1, group))
        out = _flash_forward(
            qp, k, v, pos_p, kv_pos, block_q, block_k, interpret,
            k_scale=k_scale, v_scale=v_scale,
        )
        out = jnp.moveaxis(
            out.reshape(B, group, T, KVH, -1), 1, 3
        ).reshape(B, T, H, -1)
        return out
    return _flash_forward(
        q, k, v, q_pos, kv_pos, block_q, block_k, interpret,
        k_scale=k_scale, v_scale=v_scale,
    )


def flash_attention_sharded(
    q: jnp.ndarray,        # [B, T, H, d]
    k: jnp.ndarray,        # [B, S, KVH, d] (int8 with scales)
    v: jnp.ndarray,
    q_pos: jnp.ndarray,    # [B, T]
    kv_pos: jnp.ndarray,   # [B, S]
    k_scale: Optional[jnp.ndarray] = None,   # [B, S, KVH] fp32 (int8 KV)
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Mesh-aware entry point for the inference (no-dropout) flash
    kernels — the prefill path of ``models.forward``.

    A Mosaic kernel is not partitioned by GSPMD ("Mosaic kernels cannot
    be automatically partitioned" — what the TPU compiler answers for a
    bare ``flash_attention`` under ``--tensor 4`` / ``--serve-mesh 1,4``;
    interpret mode on the CPU is plain HLO and never showed it), so under
    an active mesh the kernel runs per-shard inside ``shard_map``: heads
    over "tensor" (contiguous H chunks == contiguous KVH chunks under
    the h = kvh*G + g layout), rows over the batch axes when they divide
    — the same placement as the paged kernel.  No
    collectives: every (row, head) is independent and the caller's
    o-projection all-reduce recombines heads.  Meshes the placement does
    not cover (seq/stage axes, heads not divisible) call the kernel
    directly, as before.
    """
    from ..parallel.mesh import current_mesh

    def call(q, k, v, q_pos, kv_pos, *scales):
        if scales:
            return flash_attention_quantized(
                q, k, v, scales[0], scales[1], q_pos, kv_pos
            )
        return flash_attention(q, k, v, q_pos, kv_pos)

    scales = () if k_scale is None else (k_scale, v_scale)
    mesh = current_mesh()
    if (
        mesh is None
        or mesh.shape.get("seq", 1) > 1
        or mesh.shape.get("stage", 1) > 1
    ):
        return call(q, k, v, q_pos, kv_pos, *scales)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape.get("tensor", 1)
    heads_ok = tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0
    row_axes = tuple(
        a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1
    )
    n_rows = int(np.prod([mesh.shape[a] for a in row_axes]))
    rows = row_axes if row_axes and q.shape[0] % n_rows == 0 else None
    if not heads_ok and rows is None:
        return call(q, k, v, q_pos, kv_pos, *scales)
    tens = "tensor" if heads_ok else None
    head4 = P(rows, None, tens, None)
    pos2 = P(rows, None)
    fn = jax.shard_map(
        call, mesh=mesh,
        in_specs=(head4, head4, head4, pos2, pos2)
        + (P(rows, None, tens),) * len(scales),
        out_specs=head4, check_vma=False,
    )
    return fn(q, k, v, q_pos, kv_pos, *scales)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash(q, k, v, q_pos, kv_pos, seed, block_q, block_k, interpret,
           dropout_rate=0.0):
    return _flash_forward(
        q, k, v, q_pos, kv_pos, block_q, block_k, interpret,
        dropout_rate=dropout_rate, dropout_seed=seed,
    )


def _flash_fwd(q, k, v, q_pos, kv_pos, seed, block_q, block_k, interpret,
               dropout_rate=0.0):
    out, lse = _flash_forward(
        q, k, v, q_pos, kv_pos, block_q, block_k, interpret, need_lse=True,
        dropout_rate=dropout_rate, dropout_seed=seed,
    )
    return out, (q, k, v, q_pos, kv_pos, seed, out, lse)


def _flash_bwd(block_q, block_k, interpret, dropout_rate, res, g):
    q, k, v, q_pos, kv_pos, seed, out, lse = res
    dq, dk, dv = _flash_backward(
        q, k, v, q_pos, kv_pos, out, lse, g, block_q, block_k, interpret,
        dropout_rate=dropout_rate, dropout_seed=seed,
    )
    # Integer primals take float0 cotangents.
    zq = np.zeros(q_pos.shape, jax.dtypes.float0)
    zk = np.zeros(kv_pos.shape, jax.dtypes.float0)
    zs = np.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, zq, zk, zs


_flash.defvjp(_flash_fwd, _flash_bwd)


def _resolve_interpret(interpret):
    if interpret is None:
        # Mosaic only targets TPU; everywhere else (CPU test meshes) run the
        # kernel interpreted.  default_backend() is concrete at trace time.
        interpret = jax.default_backend() != "tpu"
    return interpret


def _clamp_blocks(T, S, block_q, block_k, interpret):
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    if not interpret:
        # Mosaic tiling: a non-full block's last dim must be a multiple of
        # 128 and its second-to-last a multiple of 8.  block_q only ever
        # appears as a sublane dim (q/o/q_pos tiles) — 8-align it; block_k
        # is the lane dim of the kv_pos tile — 128-align it.
        if block_q < T:
            block_q = -(-block_q // _SUBLANES) * _SUBLANES
        if block_k < S:
            block_k = -(-block_k // _LANES) * _LANES
        block_q, block_k = min(block_q, T), min(block_k, S)
    return block_q, block_k


def _window_bounds(q_pos_p, kv_pos_p, T, block_q, block_k, window):
    """(kv_start, kv_bound) [B, nq] int32 of the window form: per q block
    the kv blocks [start, bound) are those holding a live slot that some
    query of the block may attend — not after its last query (the causal
    side, ``kv_bound`` alone without a window) and not wholly before its
    first query's window.  ``q_pos_p`` [B, Tp] / ``kv_pos_p`` [B, Sp] are
    the padded planes (dead kv slots at +INT_MAX; query rows past ``T``
    are padding and bound nothing).  A q block with no such block gets
    ``start == bound`` and sweeps nothing."""
    B, Tp = q_pos_p.shape
    nq, nk = Tp // block_q, kv_pos_p.shape[1] // block_k
    imax = jnp.iinfo(jnp.int32).max
    real = jnp.arange(Tp, dtype=jnp.int32)[None, :] < T
    qmax = jnp.max(
        jnp.where(real, q_pos_p, -1).reshape(B, nq, block_q), axis=2)
    qmin = jnp.min(
        jnp.where(real, q_pos_p, imax).reshape(B, nq, block_q), axis=2)
    kmin = jnp.min(kv_pos_p.reshape(B, nk, block_k), axis=2)
    kmax = jnp.max(
        jnp.where(kv_pos_p == imax, -1, kv_pos_p).reshape(B, nk, block_k),
        axis=2,
    )
    # qmin may be imax (an all-padding q block): subtract in a way that
    # cannot wrap, window >= 1.
    lo = jnp.maximum(qmin, window - 1) - (window - 1)  # first seen position
    live = (kmin[:, None, :] <= qmax[:, :, None]) & (
        kmax[:, None, :] >= lo[:, :, None]
    )  # [B, nq, nk]
    idx = jnp.arange(nk, dtype=jnp.int32)[None, None, :]
    bound = 1 + jnp.max(jnp.where(live, idx, -1), axis=2)
    start = jnp.min(jnp.where(live, idx, nk), axis=2)
    return jnp.minimum(start, bound).astype(jnp.int32), bound.astype(jnp.int32)


def _flash_forward(
    q, k, v, q_pos, kv_pos, block_q, block_k, interpret, need_lse=False,
    k_scale=None, v_scale=None, dropout_rate=0.0, dropout_seed=None,
    window=None, mask=None,
):
    B, T, H, d = q.shape
    S, KVH = k.shape[1], k.shape[2]
    assert H % KVH == 0, (H, KVH)
    group = H // KVH
    quantized = k_scale is not None
    with_dropout = dropout_rate > 0.0
    assert not (with_dropout and quantized), (
        "dropout is training-only; the int8-KV path is inference-only"
    )
    windowed = window is not None
    assert not (windowed and (quantized or with_dropout)), (
        "the window form is bf16/f32 inference only"
    )
    # log2(e) folded into the score scale: the kernel's online softmax
    # runs in base 2 (bare VPU exp2 per element, no hidden wide multiply).
    scale = (1.0 / (d ** 0.5)) * float(np.log2(np.e))
    interpret = _resolve_interpret(interpret)
    masked = mask is not None
    assert not (masked and (quantized or with_dropout or windowed)), (
        "the mask form is bf16/f32 inference only, without a window"
    )
    mask_blocks = None
    if masked:
        # The mask has one row a QUERY; the packed rows (GQA: group x Tm,
        # row g * Tm + t) share it.  Where a q block never spans two heads
        # (block_q divides Tm) the mask's row block is qi modulo its own
        # count; otherwise the mask is tiled to the packed rows.
        Tm = mask.shape[1]
        bq = min(block_q, Tm)
        if Tm % bq == 0 and (interpret or bq % _SUBLANES == 0 or bq == T):
            block_q, mask_blocks = bq, Tm // bq
        else:
            mask = jnp.tile(mask, (1, T // Tm, 1))
    block_q, block_k = _clamp_blocks(T, S, block_q, block_k, interpret)

    # Pad sequence axes up to tile multiples OUTSIDE the kernel: Pallas
    # out-of-bounds tile reads are undefined, so padded kv slots must carry
    # a real sentinel position for the in-kernel mask to exclude them.
    # Invalid slots (public contract: -1) are remapped to +INT_MAX here so
    # the kernel's per-element mask is ONE compare (`kp <= qp` excludes
    # padding by magnitude) instead of two compares + and.
    qt = _pad_to(jnp.swapaxes(q, 1, 2), 2, block_q)  # [B, H, Tp, d]
    kt = _pad_to(jnp.swapaxes(k, 1, 2), 2, block_k)  # [B, KVH, Sp, d]
    vt = _pad_to(jnp.swapaxes(v, 1, 2), 2, block_k)
    q_pos_p = _pad_to(q_pos.astype(jnp.int32), 1, block_q)
    kv_pos_p = _pad_to(kv_pos.astype(jnp.int32), 1, block_k, value=-1)
    kv_pos_p = jnp.where(
        kv_pos_p < 0, jnp.iinfo(jnp.int32).max, kv_pos_p
    )
    Tp, Sp = qt.shape[2], kt.shape[2]
    nq, nk = Tp // block_q, Sp // block_k
    # Narrow-lane/sublane position views (free expand_dims, no copies).
    q_pos_r = q_pos_p[:, :, None]
    kv_pos_r = kv_pos_p[:, None, :]

    grid = (B, H, nq, nk)

    # Per-(batch, q-block) kv grid bound: 1 + the last kv block holding any
    # live slot some query in the q block may attend.  Blocks at/after the
    # bound are clamped in the index maps below — consecutive grid steps
    # then request the SAME tile, and the Pallas pipeline skips the DMA —
    # and the kernel skips their compute via the prefetched bound.  For
    # causal prefill this removes the dead upper-triangle K/V traffic that
    # the in-kernel block_live check alone still paid bandwidth for.
    if windowed:
        kv_start, kv_bound = _window_bounds(
            q_pos_p, kv_pos_p, T, block_q, block_k, window
        )
    else:
        qmax = jnp.max(q_pos_p.reshape(B, nq, block_q), axis=2)
        kmin = jnp.min(kv_pos_p.reshape(B, nk, block_k), axis=2)
        attendable = kmin[:, None, :] <= qmax[:, :, None]  # [B, nq, nk]
        kv_bound = 1 + jnp.max(
            jnp.where(
                attendable,
                jnp.arange(nk, dtype=jnp.int32)[None, None, :], -1,
            ),
            axis=2,
        )  # [B, nq], values in [0, nk]
    kv_bound_flat = kv_bound.reshape(B * nq)

    # Index maps take trailing *_ so the same lambdas serve both prefetch
    # layouts (kv_bound alone, or kv_bound + dropout seed).
    def _clamp_ki(b, qi, ki, bound, *pre):
        if windowed:
            # grid step ki visits block start + ki of the q block's sweep
            ki = ki + pre[0][b * nq + qi]
        return jnp.minimum(ki, jnp.maximum(bound[b * nq + qi] - 1, 0))

    def q_row(b, h, qi, ki, bound, *_):
        return (b, h, qi, 0)

    out_shape = jax.ShapeDtypeStruct((B, H, Tp, d), q.dtype)
    out_spec = pl.BlockSpec((1, 1, block_q, d), q_row)
    if need_lse:
        # Narrow-lane row logsumexp for the backward kernels.
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((B, H, Tp, 1), jnp.float32),
        )
        out_spec = (
            out_spec,
            pl.BlockSpec((1, 1, block_q, 1), q_row),
        )
    in_specs = [
        pl.BlockSpec(
            (1, block_q, 1), lambda b, h, qi, ki, bound, *_: (b, qi, 0)
        ),
        pl.BlockSpec(
            (1, 1, block_k),
            lambda b, h, qi, ki, bound, *_: (
                b, 0, _clamp_ki(b, qi, ki, bound, *_)
            ),
        ),
        pl.BlockSpec((1, 1, block_q, d), q_row),
        pl.BlockSpec(
            (1, 1, block_k, d),
            lambda b, h, qi, ki, bound, *_: (
                b, h // group, _clamp_ki(b, qi, ki, bound, *_), 0
            ),
        ),
        pl.BlockSpec(
            (1, 1, block_k, d),
            lambda b, h, qi, ki, bound, *_: (
                b, h // group, _clamp_ki(b, qi, ki, bound, *_), 0
            ),
        ),
    ]
    operands = [q_pos_r, kv_pos_r, qt, kt, vt]
    if masked:
        mask_p = _pad_to(_pad_to(mask.astype(jnp.int8), 1, block_q), 2, block_k)
        n_mask = mask_blocks or nq
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, h, qi, ki, bound, *_: (
                b, qi % n_mask, _clamp_ki(b, qi, ki, bound, *_)
            ),
        ))
        operands.append(mask_p)
    if quantized:
        # Narrow-sublane per-slot scale views [B, KVH, 1, Sp] — free
        # expand_dims, blocked along the kv axis like kv_pos.
        def _scale_plane(s):
            st = _pad_to(jnp.moveaxis(s, 2, 1).astype(jnp.float32), 2, block_k)
            return st[:, :, None, :]

        scale_spec = pl.BlockSpec(
            (1, 1, 1, block_k),
            lambda b, h, qi, ki, bound, *_: (
                b, h // group, 0, _clamp_ki(b, qi, ki, bound, *_)
            ),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [_scale_plane(k_scale), _scale_plane(v_scale)]
    prefetch = [kv_bound_flat]
    if windowed:
        prefetch += [
            kv_start.reshape(B * nq),
            jnp.asarray(window, jnp.int32).reshape(1),
        ]
    if with_dropout:
        prefetch.append(_normalize_seed(dropout_seed))
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, with_lse=need_lse,
            quantized=quantized, dropout_rate=dropout_rate,
            windowed=windowed, masked=masked,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        # batch/head/q-block are independent ("parallel"); only the k sweep
        # carries state through scratch ("arbitrary").  Without this hint
        # Mosaic treats the whole grid as sequential and cannot pipeline
        # block DMA against compute — measured ~4x slower at 16k context.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            # The default 16 MiB scoped-vmem budget blocks the larger
            # tiles (s lives at [block_q, block_k] fp32); v5e VMEM is
            # 128 MiB, and 64 MiB leaves ample room for the pipeline's
            # double buffers while unlocking (1024, 4096)-class tiles —
            # fewer grid steps, less per-step overhead.
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
    )(*prefetch, *operands)
    if need_lse:
        out, lse = out
        return jnp.swapaxes(out[:, :, :T, :], 1, 2), lse
    return jnp.swapaxes(out[:, :, :T, :], 1, 2)  # [B, T, H, d]


# ---------------------------------------------------------------------------
# Backward: blockwise dQ / dK / dV with recomputed probabilities.
#
# Standard flash-attention backward split into two kernels so each output
# has a clean accumulation sweep (never an S×S tensor in memory):
#   * dQ kernel: grid (B, H, nq, nk) — for each q block, sweep kv blocks,
#     accumulating dQ_i += scale · dS_ij · K_j.
#   * dK/dV kernel: grid (B, H, nk, nq) — for each kv block, sweep q
#     blocks, accumulating dV_j += P_ijᵀ · dO_i and
#     dK_j += scale · dS_ijᵀ · Q_i.
# with P = exp(S − lse) rebuilt per tile from the forward's saved row
# logsumexp, dP = dO · Vᵀ, D = rowsum(dO ∘ O), dS = P ∘ (dP − D).
#
# GQA needs no extra handling: the public wrapper packs the `group` query
# heads of each KV head into the row axis before the custom_vjp boundary,
# so these kernels always see H == KVH and the sum over a KV head's query
# group happens naturally in the q-row sweep of the dK/dV kernel.
# ---------------------------------------------------------------------------


def _flash_dq_kernel(
    *args, scale: float, dropout_rate: float = 0.0,
):
    # With dropout a [2] uint32 seed_ref leads; lse_ref/delta_ref are
    # narrow-lane [1, 1, bq, 1] rows.
    if dropout_rate > 0.0:
        seed_ref, *args = args
    (q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
     dq_ref, dq_acc) = args
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    qp = q_pos_ref[0, :, :1]  # [bq, 1]
    kp = kv_pos_ref[0, :1, :]  # [1, bk] (+INT_MAX on padding slots)
    block_live = jnp.min(kp) <= jnp.max(qp)

    def _dq_body(ragged):
        """Sub-tiled dQ tile update (r5).  Unlike the forward there is no
        online-softmax state between sub-tiles — lse is FIXED — so the
        nsub chains (dot -> exp -> ds -> dot) are fully independent and
        Mosaic overlaps sub-tile i's VPU work with i±1's dots.  With
        ``ragged`` (diagonal-crossing tiles, triangle-safety-guarded by
        the caller like the forward's tri body), k sub-tile i computes
        only query rows [i·rq:] — on a causal crossing tile the uniform
        body burned ~50% of its dots on fully-masked rows, which capped
        useful MXU at ~45% at training scale (S=2048) even though the
        MXU was ~90% busy; tile-size sweeps could not fix it (smaller
        tiles hit a ~4.5 µs/step grid-overhead floor).
        """
        qb, gb = q_ref[0, 0], g_ref[0, 0]
        bq = qb.shape[0]
        bk = k_ref.shape[2]
        nsub = _KSUB if (bk % _KSUB == 0 and bk > _KSUB) else 1
        ksub = bk // nsub
        rq = bq // nsub if ragged else 0
        # Full-width mask compare once — narrow [1, ksub] sub-slices of
        # the 1-row position plane hit unsupported Mosaic layouts (the
        # same trap the forward documents); 2-D slices of the [bq, bk]
        # compare are fine.
        allowed = kp <= qp
        lse_row = lse_ref[0, 0][:, :1]
        delta_row = delta_ref[0, 0][:, :1]
        inv = 1.0 / (1.0 - dropout_rate) if dropout_rate > 0.0 else None
        c_parts = []
        for i in range(nsub):
            cols = slice(i * ksub, (i + 1) * ksub)
            r0 = i * rq  # 0 when not ragged
            kb_i = k_ref[0, 0, cols, :]
            s_i = jax.lax.dot_general(
                qb[r0:], kb_i, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            p_i = jnp.where(
                allowed[r0:, cols], jnp.exp(s_i - lse_row[r0:]), 0.0
            )
            dp_i = jax.lax.dot_general(
                gb[r0:], v_ref[0, 0, cols, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dropout_rate > 0.0:
                # Forward: out = (D ∘ w) V with w = softmax(s), D the
                # inverted-dropout mask.  Chain rule gives dw = D ∘ dp,
                # and the softmax Jacobian's weighted sum
                # Σ_k w_k (D_k dp_k) is exactly rowsum(dO ∘ O) — the
                # SAME delta as the no-dropout case — so only dp needs
                # masking.  The mask is rebuilt bit-identically from
                # GLOBAL element offsets (tiling-independent hash).
                keep = _dropout_keep(
                    seed_ref[0], seed_ref[1], bi, hi,
                    qi * bq + r0, ki * bk + i * ksub,
                    bq - r0, ksub, dropout_rate,
                )
                dp_i = jnp.where(keep, dp_i, 0.0) * inv
            ds_i = p_i * (dp_i - delta_row[r0:]) * scale
            c_parts.append(jax.lax.dot_general(
                ds_i.astype(kb_i.dtype), kb_i, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
        if not ragged:
            acc = c_parts[0]
            for c_i in c_parts[1:]:
                acc = acc + c_i
            dq_acc[:] += acc
        else:
            # Row block j collects contributions from sub-tiles i <= j
            # (c_parts[i] starts at global row i*rq).
            for j in range(nsub):
                rows = slice(j * rq, (j + 1) * rq)
                add = None
                for i in range(j + 1):
                    piece = c_parts[i][(j - i) * rq:(j - i + 1) * rq]
                    add = piece if add is None else add + piece
                dq_acc[rows] += add

    tri_ok, safe = _tri_gate(qp, kp, q_ref.shape[2], k_ref.shape[2])
    if tri_ok:
        @pl.when(block_live & safe)
        def _compute_tri():
            _dq_body(ragged=True)

        @pl.when(block_live & jnp.logical_not(safe))
        def _compute():
            _dq_body(ragged=False)
    else:
        @pl.when(block_live)
        def _compute():
            _dq_body(ragged=False)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(
    *args, scale: float, dropout_rate: float = 0.0,
):
    if dropout_rate > 0.0:
        seed_ref, *args = args
    (q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_acc, dv_acc) = args
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    bi, hi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    qp = q_pos_ref[0, :, :1]  # [bq, 1]
    kp = kv_pos_ref[0, :1, :]  # [1, bk] (+INT_MAX on padding slots)
    block_live = jnp.min(kp) <= jnp.max(qp)

    def _dkv_body(ragged):
        """Sub-tiled dK/dV tile update (r5), over the Q-ROW axis (the
        kernel's within-tile reduction axis): lse is fixed, so the nsub
        chains are fully independent and their dots/VPU work pipeline —
        see the dQ kernel note.  With ``ragged`` (diagonal-crossing
        tiles), q-row sub-tile i computes only kv columns
        [0:(i+1)·csub] — GROWING widths, the column-side mirror of the
        dQ kernel's shrinking rows — and contributions land per column
        block through static scratch slices."""
        kb, vb = k_ref[0, 0], v_ref[0, 0]
        bq = q_ref.shape[2]
        bk = kb.shape[0]
        nsub = (
            _KSUB
            if (bq % _KSUB == 0 and bq > _KSUB
                and (bq // _KSUB) % _SUBLANES == 0)
            else 1
        )
        qsub = bq // nsub
        csub = bk // nsub if ragged else 0
        # Full-width compare + full narrow-lane loads once; 2-D row
        # slices of them are Mosaic-safe (see the dQ kernel note).
        allowed = kp <= qp
        lse_rows = lse_ref[0, 0][:, :1]
        delta_rows = delta_ref[0, 0][:, :1]
        inv = 1.0 / (1.0 - dropout_rate) if dropout_rate > 0.0 else None
        dv_parts = []  # [(i+1)*csub, d] when ragged, else [bk, d]
        dk_parts = []
        for i in range(nsub):
            rows = slice(i * qsub, (i + 1) * qsub)
            cols = slice(0, (i + 1) * csub) if ragged else slice(0, bk)
            wk = (i + 1) * csub if ragged else bk
            qb_i = q_ref[0, 0, rows, :]
            gb_i = g_ref[0, 0, rows, :]
            s_i = jax.lax.dot_general(
                qb_i, kb[cols], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [qsub, wk]
            p_i = jnp.where(
                allowed[rows, cols], jnp.exp(s_i - lse_rows[rows]), 0.0
            )
            if dropout_rate > 0.0:
                # Same global element offsets as the forward/dQ kernels —
                # NOTE the grid here is (B, H, nk, nq), so qi/ki swap
                # program ids.
                keep = _dropout_keep(
                    seed_ref[0], seed_ref[1], bi, hi,
                    qi * bq + i * qsub, ki * bk, qsub, wk, dropout_rate,
                )
                p_v = jnp.where(keep, p_i, 0.0) * inv
                dp_mask = lambda dp, _k=keep: jnp.where(_k, dp, 0.0) * inv
            else:
                p_v = p_i
                dp_mask = lambda dp: dp
            # dV_j += (D ∘ P)_ijᵀ dO_i: contract the q-row axis.
            dv_parts.append(jax.lax.dot_general(
                p_v.astype(gb_i.dtype), gb_i, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
            dp_i = dp_mask(jax.lax.dot_general(
                gb_i, vb[cols], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
            ds_i = p_i * (dp_i - delta_rows[rows]) * scale
            dk_parts.append(jax.lax.dot_general(
                ds_i.astype(qb_i.dtype), qb_i, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
        if not ragged:
            dv_add = dv_parts[0]
            dk_add = dk_parts[0]
            for dv_i, dk_i in zip(dv_parts[1:], dk_parts[1:]):
                dv_add = dv_add + dv_i
                dk_add = dk_add + dk_i
            dv_acc[:] += dv_add
            dk_acc[:] += dk_add
        else:
            # Column block c collects contributions from q sub-tiles
            # i >= c (sub-tile i's parts cover columns [0:(i+1)*csub]).
            for c in range(nsub):
                cols_c = slice(c * csub, (c + 1) * csub)
                dv_add = None
                dk_add = None
                for i in range(c, nsub):
                    dv_p = dv_parts[i][cols_c]
                    dk_p = dk_parts[i][cols_c]
                    dv_add = dv_p if dv_add is None else dv_add + dv_p
                    dk_add = dk_p if dk_add is None else dk_add + dk_p
                dv_acc[cols_c] += dv_add
                dk_acc[cols_c] += dk_add

    # One shared gate: the dK/dV skip set (q sub-tile i × column suffix
    # past i) reduces to the same pairwise max(qp block) < min(kp block)
    # condition as the forward/dQ row-skips — see _tri_gate.
    tri_ok, safe = _tri_gate(qp, kp, q_ref.shape[2], k_ref.shape[2])
    if tri_ok:
        @pl.when(block_live & safe)
        def _compute_tri():
            _dkv_body(ragged=True)

        @pl.when(block_live & jnp.logical_not(safe))
        def _compute():
            _dkv_body(ragged=False)
    else:
        @pl.when(block_live)
        def _compute():
            _dkv_body(ragged=False)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, q_pos, kv_pos, out, lse, g, block_q, block_k, interpret,
    dropout_rate=0.0, dropout_seed=None,
):
    """Blockwise VJP.  Memory is O(S·d) per head (plus narrow-lane
    lse/Δ rows) — replacing the r1 dense-recompute fallback whose backward
    materialized the full [B, H, T, S] score matrix."""
    B, T, H, d = q.shape
    S = k.shape[1]
    assert k.shape[2] == H, "custom_vjp operates on GQA-packed operands"
    scale = 1.0 / (d ** 0.5)
    interpret = _resolve_interpret(interpret)
    block_q, block_k = _clamp_blocks(T, S, block_q, block_k, interpret)
    with_dropout = dropout_rate > 0.0
    seed_ops = (
        (_normalize_seed(dropout_seed),) if with_dropout else ()
    )

    # Δ = rowsum(dO ∘ O): tiny elementwise pass outside the kernels.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [B, T, H]

    qt = _pad_to(jnp.swapaxes(q, 1, 2), 2, block_q)  # [B, H, Tp, d]
    kt = _pad_to(jnp.swapaxes(k, 1, 2), 2, block_k)  # [B, H, Sp, d]
    vt = _pad_to(jnp.swapaxes(v, 1, 2), 2, block_k)
    gt = _pad_to(jnp.swapaxes(g, 1, 2), 2, block_q)  # dO; pad rows are 0 so
    #   padded-q contributions to every gradient vanish (Δ is 0 there too).
    q_pos_p = _pad_to(q_pos.astype(jnp.int32), 1, block_q)
    # Same +INT_MAX invalid-slot remap as the forward (single-compare mask).
    kv_pos_p = _pad_to(kv_pos.astype(jnp.int32), 1, block_k, value=-1)
    kv_pos_p = jnp.where(
        kv_pos_p < 0, jnp.iinfo(jnp.int32).max, kv_pos_p
    )
    Tp, Sp = qt.shape[2], kt.shape[2]
    nq, nk = Tp // block_q, Sp // block_k
    q_pos_r = q_pos_p[:, :, None]
    kv_pos_r = kv_pos_p[:, None, :]
    delta_r = _pad_to(jnp.moveaxis(delta, 2, 1), 2, block_q)[..., None]
    # lse comes from the forward already padded, narrow-lane [B, H, Tp, 1].

    pos_specs = [
        pl.BlockSpec((1, block_q, 1), lambda b, h, qi, ki, *_: (b, qi, 0)),
        pl.BlockSpec((1, 1, block_k), lambda b, h, qi, ki, *_: (b, 0, ki)),
    ]
    q_row_specs = [
        pl.BlockSpec(
            (1, 1, block_q, d), lambda b, h, qi, ki, *_: (b, h, qi, 0)
        ),
    ]
    kv_specs = [
        pl.BlockSpec(
            (1, 1, block_k, d), lambda b, h, qi, ki, *_: (b, h, ki, 0)
        ),
        pl.BlockSpec(
            (1, 1, block_k, d), lambda b, h, qi, ki, *_: (b, h, ki, 0)
        ),
    ]
    row_aux_specs = [
        pl.BlockSpec(
            (1, 1, block_q, 1), lambda b, h, qi, ki, *_: (b, h, qi, 0)
        ),
        pl.BlockSpec(
            (1, 1, block_q, 1), lambda b, h, qi, ki, *_: (b, h, qi, 0)
        ),
    ]

    def _call(kernel, grid, in_specs, out_specs, out_shape, scratch_shapes):
        # Dropout threads the [1] uint32 seed as a scalar-prefetch operand
        # (the mask hash needs it before tile compute); the no-dropout
        # trace is unchanged.
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(seed_ops),
                grid=grid,
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=scratch_shapes,
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(
                    "parallel", "parallel", "parallel", "arbitrary"
                ),
                # Same raised scoped-vmem budget as the forward: the
                # (2048, 2048) default tiles exceed the 16 MiB default
                # here too (s/p intermediates at [block_q, block_k] fp32).
                vmem_limit_bytes=64 * 1024 * 1024,
            ),
            interpret=interpret,
        )

    dq = _call(
        functools.partial(
            _flash_dq_kernel, scale=scale, dropout_rate=dropout_rate
        ),
        (B, H, nq, nk),
        pos_specs + q_row_specs + kv_specs + q_row_specs + row_aux_specs,
        pl.BlockSpec(
            (1, 1, block_q, d), lambda b, h, qi, ki, *_: (b, h, qi, 0)
        ),
        jax.ShapeDtypeStruct((B, H, Tp, d), q.dtype),
        [pltpu.VMEM((block_q, d), jnp.float32)],
    )(*seed_ops, q_pos_r, kv_pos_r, qt, kt, vt, gt, lse, delta_r)

    # dK/dV kernel: kv blocks third, q sweep innermost.
    def qrow(b, h, ki, qi, *_):
        return (b, h, qi, 0)

    def kvrow(b, h, ki, qi, *_):
        return (b, h, ki, 0)

    dkv_specs = [
        pl.BlockSpec((1, block_q, 1), lambda b, h, ki, qi, *_: (b, qi, 0)),
        pl.BlockSpec((1, 1, block_k), lambda b, h, ki, qi, *_: (b, 0, ki)),
        pl.BlockSpec((1, 1, block_q, d), qrow),
        pl.BlockSpec((1, 1, block_k, d), kvrow),
        pl.BlockSpec((1, 1, block_k, d), kvrow),
        pl.BlockSpec((1, 1, block_q, d), qrow),
        pl.BlockSpec((1, 1, block_q, 1), qrow),
        pl.BlockSpec((1, 1, block_q, 1), qrow),
    ]
    dk, dv = _call(
        functools.partial(
            _flash_dkv_kernel, scale=scale, dropout_rate=dropout_rate
        ),
        (B, H, nk, nq),
        dkv_specs,
        (
            pl.BlockSpec((1, 1, block_k, d), kvrow),
            pl.BlockSpec((1, 1, block_k, d), kvrow),
        ),
        (
            jax.ShapeDtypeStruct((B, H, Sp, d), k.dtype),
            jax.ShapeDtypeStruct((B, H, Sp, d), v.dtype),
        ),
        [
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
    )(*seed_ops, q_pos_r, kv_pos_r, qt, kt, vt, gt, lse, delta_r)

    dq = jnp.swapaxes(dq[:, :, :T, :], 1, 2)
    dk = jnp.swapaxes(dk[:, :, :S, :], 1, 2)
    dv = jnp.swapaxes(dv[:, :, :S, :], 1, 2)
    return dq, dk, dv
