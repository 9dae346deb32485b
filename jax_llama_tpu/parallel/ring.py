"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

The reference has no sequence parallelism at all (SURVEY.md §2.13b: full-
sequence attention with a materialized S×S mask, ``/root/reference/
jax_llama/model.py:154``) — its context length is capped by one device's
memory.  Here the sequence axis is sharded over the ``seq`` mesh axis and
attention runs as a ring: each device holds one KV shard, computes blockwise
attention of its local queries against the shard it currently holds while
accumulating online-softmax state (running max ``m``, denominator ``l``,
fp32 accumulator), then rotates the KV shard to its ring neighbor with
``lax.ppermute``.  After ``n`` steps every query has seen every key, no
device ever held more than ``S/n`` keys, and the rotation rides ICI
point-to-point links, overlapping with the local compute under XLA's
latency-hiding scheduler.

Within a rotation the shard is folded CHUNKWISE (``lax.scan`` over
fixed-size kv chunks with the same online-softmax update): peak per-device
attention memory is O(B·H·T_local·chunk), not O(T_local·S/n) — the
[B, H, T, S/n] probability tensor the first implementation materialized
per rotation is gone, which is what makes 32k+ contexts per shard real.
Each chunk update is ``jax.checkpoint``ed, so the backward pass recomputes
chunk probabilities instead of saving them (same recompute-not-store deal
as the Pallas flash backward).

Masking is positional (same contract as ``ops.attention.attention_bias`` /
the flash kernel): slot attendable iff ``kv_pos <= q_pos`` and
``kv_pos >= 0``.  Because masks derive from absolute positions carried with
the shards, causality is layout-independent — no zig-zag reordering games
are needed for correctness (contiguous sharding does leave the usual causal
load imbalance; acceptable at this stage).

Decode (``ring_decode``) does NOT rotate: the KV cache stays sharded over
``seq`` (each device owns S/n slots permanently) and the tiny [B, T]
queries are replicated; every device computes its shard's partial
online-softmax statistics and ONE pmax + two psums over ``seq`` combine
them exactly.  The step's own new tokens merge at the softmax level
afterwards (the ``sdpa_cached`` append-free contract), so the cache rides
the layer scan immutably and generation context is bounded by the MESH's
combined HBM, not one chip's.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention_bias, repeat_kv, sdpa
from ..ops.flash_attention import MASK_VALUE, _mix32, _normalize_seed
from .mesh import current_mesh

BATCH_AXES = ("data", "fsdp")

# kv-chunk length of the inner accumulation scan: MXU-friendly (multiple
# of 128 lanes) and small enough that [B, H, T_local, RING_CHUNK] fp32
# stays a rounding error next to the activations.
RING_CHUNK = 512


def dropout_base(seed, B, H, b_off, h_off):
    """Per-(global batch, global head) hash bases [2, B, H] uint32 — the
    same keying scheme as the flash kernels' ``_dropout_keep``: the
    64-bit seed's low word keys the row-side base plane and its high
    word the column-side plane, so a repeated mask plane needs BOTH
    32-bit bases to collide — a 64-bit birthday event, not the old
    single-word ~65k-step horizon.  Global indices are supplied by the
    caller so every device of a data/fsdp/tensor-sharded mesh draws an
    independent plane.  ``seed``: [2] uint32 (scalar / [1] legacy inputs
    widen with a zero high word, validated by ``_normalize_seed``)."""
    s = _normalize_seed(seed)
    gb = (
        jnp.asarray(b_off, jnp.uint32)
        + jnp.arange(B, dtype=jnp.uint32)[:, None]
    )
    gh = (
        jnp.asarray(h_off, jnp.uint32)
        + jnp.arange(H, dtype=jnp.uint32)[None, :]
    )
    plane = _mix32(
        gb * jnp.uint32(0x9E3779B9)
        + gh * jnp.uint32(0x85EBCA6B)
        + jnp.uint32(1)
    )
    return jnp.stack([
        _mix32(s[0] ^ plane),
        # Same lane constant as _dropout_keep: keeps the two bases
        # independent when the seed words coincide.
        _mix32(s[1] ^ plane ^ jnp.uint32(0x85EBCA6B)),
    ])


def dropout_keep(base, q_pos, kv_pos, rate):
    """Deterministic keep mask [B, H, T, C] for attention-probability
    dropout under ring attention.

    Keyed on ABSOLUTE (query position, kv position) — the coordinates
    that ride the shards — so the mask is a pure function of the global
    (row, column) pair and survives chunking, ring rotation, and any
    seq-mesh layout by construction (the property the flash kernels get
    from global tile indices).  Row and column enter the element hash
    jointly (xor of two independently mixed words), same rationale — and
    the same two-base seed split — as ``_dropout_keep``.
    base: [2, B, H] (``dropout_base``); q_pos: [B, T]; kv_pos: [B, C].
    """
    rows = q_pos.astype(jnp.uint32)[:, None, :, None]
    cols = kv_pos.astype(jnp.uint32)[:, None, None, :]
    bits = _mix32(
        _mix32(base[0][:, :, None, None] ^ rows)
        ^ _mix32(
            base[1][:, :, None, None] ^ (cols * jnp.uint32(0x9E3779B9))
        )
    )
    threshold = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return bits >= threshold


def _fold_chunk(qt, q_pos, kc, vc, pc, m, l, acc, *, scale,
                dropout_rate=0.0, drop_base=None):
    """Fold one kv chunk into the running online-softmax state.

    qt: [B, H, T, d]; kc, vc: [B, C, KVH, d]; pc: [B, C];
    m, l: [B, H, T] f32; acc: [B, H, T, d] f32.

    With ``dropout_rate`` > 0 the acc-side probabilities are
    inverted-dropout masked (``dropout_keep``) while ``l`` keeps the full
    sum — exactly dropout applied to the post-softmax weights w = p / l,
    the flash kernels' (and sdpa's) semantics, chunkwise.
    """
    group = qt.shape[1] // kc.shape[2]
    kr = repeat_kv(kc, group)  # [B, C, H, d]
    vr = repeat_kv(vc, group)
    s = jnp.einsum(
        "bhtd,bshd->bhts", qt, kr, preferred_element_type=jnp.float32
    ) * scale
    allowed = (pc[:, None, None, :] <= q_pos[:, None, :, None]) & (
        pc >= 0
    )[:, None, None, :]
    s = jnp.where(allowed, s, MASK_VALUE)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)  # [B, H, T]
    p = jnp.exp(s - m_new[..., None])  # [B, H, T, C] f32
    l = alpha * l + jnp.sum(p, axis=-1)
    if dropout_rate > 0.0:
        keep = dropout_keep(drop_base, q_pos, pc, dropout_rate)
        p_acc = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
    else:
        p_acc = p
    acc = alpha[..., None] * acc + jnp.einsum(
        "bhts,bshd->bhtd", p_acc.astype(vr.dtype), vr,
        preferred_element_type=jnp.float32,
    )
    return m_new, l, acc


def _accumulate(qt, q_pos, k, v, kv_pos, m, l, acc, *, scale,
                chunk: int = RING_CHUNK, dropout_rate=0.0, drop_base=None):
    """Fold one KV shard into the running state, chunk by chunk.

    Memory: O(B·H·T·chunk) per step of the scan (the dense predecessor
    held the full [B, H, T, S_shard] probability tensor).  Each chunk is
    rematerialized in the backward pass (jax.checkpoint), so residuals
    are O(S_shard·d), not O(T·S_shard).

    NB the FIRST chunk folded for a live query must contain an attendable
    slot before any fully-masked chunk can be skipped-by-zero: the ring
    starts with the query's own shard and positions ascend within it, so
    chunk 0 always contains the query's own slot — after which
    exp(MASK - finite m) underflows to exactly 0 for masked chunks.
    (Padding queries accumulate garbage that is masked downstream, same
    as the dense version.)
    """
    B, S = k.shape[0], k.shape[1]
    C = min(chunk, S)
    pad = (-S) % C
    if pad:
        widths = [(0, 0)] * k.ndim
        widths[1] = (0, pad)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    nc = k.shape[1] // C

    def to_chunks(a):  # [B, nc*C, ...] -> [nc, B, C, ...]
        return jnp.moveaxis(
            a.reshape((a.shape[0], nc, C) + a.shape[2:]), 1, 0
        )

    @jax.checkpoint
    def body(carry, xs):
        m, l, acc = carry
        kc, vc, pc = xs
        # The dropout mask is a pure function of (base, positions), so the
        # checkpointed backward rebuilds it bit-identically for free.
        m, l, acc = _fold_chunk(
            qt, q_pos, kc, vc, pc, m, l, acc, scale=scale,
            dropout_rate=dropout_rate, drop_base=drop_base,
        )
        return (m, l, acc), None

    (m, l, acc), _ = lax.scan(
        body, (m, l, acc), (to_chunks(k), to_chunks(v), to_chunks(kv_pos))
    )
    return m, l, acc


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    *,
    axis_name: str = "seq",
    axis_size: int,
    dropout_rate: float = 0.0,
    dropout_seed=None,
    b_off=0,
    h_off=0,
) -> jnp.ndarray:
    """Per-device body (call under shard_map): local q attends to all KV
    shards as they rotate around the ring.

    q: [B, T_local, H, d]; k, v: [B, S_local, KVH, d];
    q_pos: [B, T_local]; kv_pos: [B, S_local].  Returns [B, T_local, H, d].

    ``dropout_rate`` > 0 (training): attention-probability dropout via a
    position-keyed counter hash (``dropout_keep``) — the mask depends only
    on (seed, global batch/head, absolute row/column position), so it is
    identical for every chunking and every ring layout; ``b_off``/``h_off``
    are this device's global batch/head offsets (0 off-mesh).
    """
    B, T, H, d = q.shape
    scale = 1.0 / (d ** 0.5)
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, T, d]
    m = jnp.full((B, H, T), MASK_VALUE, dtype=jnp.float32)
    l = jnp.zeros((B, H, T), dtype=jnp.float32)
    acc = jnp.zeros((B, H, T, d), dtype=jnp.float32)
    drop_base = (
        dropout_base(dropout_seed, B, H, b_off, h_off)
        if dropout_rate > 0.0 else None
    )

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def body(_, carry):
        k, v, kv_pos, m, l, acc = carry
        m, l, acc = _accumulate(
            qt, q_pos, k, v, kv_pos, m, l, acc, scale=scale,
            dropout_rate=dropout_rate, drop_base=drop_base,
        )
        k, v, kv_pos = (
            lax.ppermute(x, axis_name, perm) for x in (k, v, kv_pos)
        )
        return k, v, kv_pos, m, l, acc

    # n-1 rotations; the last shard is folded in without a trailing permute.
    # (axis_size 1: no rotation, no collective — the body is also valid
    # outside shard_map, which the 32k memory test exploits.)
    if axis_size > 1:
        k, v, kv_pos, m, l, acc = lax.fori_loop(
            0, axis_size - 1, body, (k, v, kv_pos, m, l, acc)
        )
    m, l, acc = _accumulate(
        qt, q_pos, k, v, kv_pos, m, l, acc, scale=scale,
        dropout_rate=dropout_rate, drop_base=drop_base,
    )

    out = acc / l[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # [B, T, H, d]


def ring_sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    *,
    axis_name: str = "seq",
    dropout_rng=None,
    dropout_rate: float = 0.0,
) -> jnp.ndarray:
    """Mesh-aware entry point: shard_map over the active mesh's ``seq`` axis
    (batch over data/fsdp, heads over tensor stay local per device).  Falls
    back to dense sdpa when no mesh is active or seq == 1.

    ``dropout_rng`` + ``dropout_rate`` > 0 enable attention-probability
    dropout (training).  On a seq > 1 mesh the mask is the position-keyed
    counter hash (``dropout_keep``) — sharding-layout-invariant; the
    seq == 1 fallback uses ``sdpa``'s jax.random mask (different draw,
    same distribution — masks are not required to match across meshes,
    only within one program's fwd/bwd, which both schemes guarantee).
    """
    mesh = current_mesh()
    n = mesh.shape.get(axis_name, 1) if mesh is not None else 1
    if n == 1:
        bias = attention_bias(q_pos, kv_pos, kv_pos >= 0)
        return sdpa(
            q, k, v, bias,
            dropout_rng=dropout_rng if dropout_rate > 0.0 else None,
            dropout_rate=dropout_rate,
        )

    with_drop = dropout_rng is not None and dropout_rate > 0.0
    B, _, H, _ = q.shape
    b_local = B
    for a in BATCH_AXES:
        b_local //= mesh.shape.get(a, 1)
    h_local = H // mesh.shape.get("tensor", 1)

    def body(q, k, v, q_pos, kv_pos, seed):
        if with_drop:
            # Global batch/head offsets of this device's shard — mesh
            # axes are all manual under shard_map, so axis_index is
            # available whether or not the axis is sharded here (0 when
            # the axis is absent from a custom mesh entirely).
            def _idx(a):
                return (
                    lax.axis_index(a) if a in mesh.axis_names
                    else jnp.zeros((), jnp.int32)
                )

            bi = _idx(BATCH_AXES[0]) * mesh.shape.get(
                BATCH_AXES[1], 1
            ) + _idx(BATCH_AXES[1])
            b_off = bi * b_local
            h_off = _idx("tensor") * h_local
        else:
            b_off = h_off = 0
        return ring_attention(
            q, k, v, q_pos, kv_pos, axis_name=axis_name, axis_size=n,
            dropout_rate=dropout_rate if with_drop else 0.0,
            dropout_seed=seed, b_off=b_off, h_off=h_off,
        )

    seed = (
        jax.random.bits(dropout_rng, (2,), "uint32")
        if with_drop else jnp.zeros((2,), jnp.uint32)
    )
    spec4 = P(BATCH_AXES, axis_name, "tensor", None)
    spec2 = P(BATCH_AXES, axis_name)
    # check_vma=False: the fori_loop carry starts from freshly-created
    # (device-invariant) accumulators and becomes device-varying after the
    # first ppermute, which the varying-manual-axes checker rejects even
    # though the program is correct.
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec4, spec4, spec4, spec2, spec2, P(None)),
        out_specs=spec4,
        check_vma=False,
    )
    return fn(q, k, v, q_pos, kv_pos, seed)


# ---------------------------------------------------------------------------
# Seq-sharded cached decode
# ---------------------------------------------------------------------------

def _scale_rows(sc: jnp.ndarray, group: int) -> jnp.ndarray:
    """Per-slot dequant scales [B, S, KVH] -> [B, H, 1, S] for folding
    into scores/probabilities (constant along d, so they commute with the
    attention contractions — the sdpa_cached trick, ring-sharded)."""
    scr = repeat_kv(sc[..., None], group)[..., 0]  # [B, S, H]
    return jnp.transpose(scr, (0, 2, 1))[:, :, None, :]


def _ring_decode_body(
    q, kc, vc, sp, kn, vn, qp, npos, *args, axis_name: str, scale: float,
    softmax_dtype, quantized: bool = False,
):
    """Per-device body: partial softmax over the LOCAL cache shard, exact
    combine over ``seq``, then the step's own new tokens merge at the
    softmax level (replicated arithmetic, no collective).

    q: [B, T, H, d]; kc, vc: [B, S_local, KVH, d] (int8 when quantized);
    sp: [B, S_local]; kn, vn: [B, T, KVH, d]; qp, npos: [B, T]; with
    ``quantized``, *args carries (k_scale, v_scale) [B, S_local, KVH] fp32
    local shards — folded at the scores/probability level, so the int8
    payload is never dequantized in memory (the new tokens merge at full
    precision, matching sdpa_cached's same-step treatment).
    """
    B, T, H, d = q.shape
    group = H // kc.shape[2]
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, T, d]

    if quantized:
        k_scale, v_scale = args
        kc = kc.astype(q.dtype)
        vc = vc.astype(q.dtype)
    kr = repeat_kv(kc, group)
    vr = repeat_kv(vc, group)
    s = jnp.einsum(
        "bhtd,bshd->bhts", qt, kr, preferred_element_type=softmax_dtype
    ) * scale
    if quantized:
        s = s * _scale_rows(k_scale, group)
    allowed = (sp[:, None, None, :] <= qp[:, None, :, None]) & (
        sp >= 0
    )[:, None, None, :]
    s = jnp.where(allowed, s, MASK_VALUE)
    m_i = jnp.max(s, axis=-1)                      # [B, H, T]
    p = jnp.exp(s - m_i[..., None])
    p = jnp.where(allowed, p, 0.0)                 # all-masked shard: l_i = 0
    l_i = jnp.sum(p, axis=-1)
    if quantized:
        # v_scale folds into the (tiny) probabilities, AFTER l_i: the
        # denominator must sum the unscaled p.
        pv = (p * _scale_rows(v_scale, group)).astype(vr.dtype)
    else:
        pv = p.astype(vr.dtype)
    o_i = jnp.einsum(
        "bhts,bshd->bhtd", pv, vr,
        preferred_element_type=softmax_dtype,
    )

    if axis_name is None:
        # Single-shard (no mesh / seq == 1): the local stats are global.
        m, l, o = m_i, l_i, o_i
    else:
        # Exact combine across the seq shards: one pmax + two psums of
        # [B, H, T(, d)] — decode-sized, so the collectives are tiny.
        m = lax.pmax(m_i, axis_name)
        w = jnp.exp(m_i - m)
        l = lax.psum(l_i * w, axis_name)
        o = lax.psum(o_i * w[..., None], axis_name)

    # New-token merge (same two-source softmax split as sdpa_cached):
    # token t attends new slot j iff npos[j] <= qp[t] (and j valid).
    s_new = jnp.einsum(
        "bhtd,bjhd->bhtj", qt, repeat_kv(kn, group),
        preferred_element_type=softmax_dtype,
    ) * scale
    allowed_new = (
        npos[:, None, None, :] <= qp[:, None, :, None]
    ) & (npos >= 0)[:, None, None, :]
    s_new = jnp.where(allowed_new, s_new, MASK_VALUE)
    m_tot = jnp.maximum(m, jnp.max(s_new, axis=-1))
    p_new = jnp.exp(s_new - m_tot[..., None])
    p_new = jnp.where(allowed_new, p_new, 0.0)
    w_old = jnp.exp(m - m_tot)
    denom = l * w_old + jnp.sum(p_new, axis=-1)
    denom = jnp.where(denom == 0.0, 1.0, denom)
    out = (
        o * (w_old / denom)[..., None]
        + jnp.einsum(
            "bhtj,bjhd->bhtd", p_new.astype(vn.dtype), repeat_kv(vn, group),
            preferred_element_type=softmax_dtype,
        ) / denom[..., None]
    )
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def ring_decode(
    q: jnp.ndarray,        # [B, T, H, d] — this step's queries
    k_cache: jnp.ndarray,  # [B, S, KVH, d] — seq-sharded KV cache (layer)
    v_cache: jnp.ndarray,
    slot_pos: jnp.ndarray,  # [B, S] int32 (-1 = invalid slot)
    k_new: jnp.ndarray,    # [B, T, KVH, d] — this step's projections
    v_new: jnp.ndarray,
    q_pos: jnp.ndarray,    # [B, T] query positions (clamped >= 0)
    new_pos: jnp.ndarray,  # [B, T] new-slot positions (-1 = padding)
    *,
    softmax_dtype=jnp.float32,
    axis_name: str = "seq",
    k_scale: Optional[jnp.ndarray] = None,  # [B, S, KVH] fp32 (int8 cache)
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Cached decode over a KV cache sharded along S over the ``seq`` mesh
    axis: generation context is bounded by the mesh's combined HBM.

    The cache never moves: each device reduces its own shard and the
    partial softmax statistics combine with one pmax + two psums of
    decode-sized tensors.  The cache stays immutable through the layer
    scan; the caller lands the new K/V afterwards (the ``sdpa_cached``
    append-free contract — so this is the drop-in seq>1 counterpart of
    the xla decode path).  S must be divisible by the seq axis size.

    int8 caches pass ``k_scale``/``v_scale`` per-slot dequant planes; the
    scales shard along S with the payload and fold at the scores /
    probability level per shard (``k_new``/``v_new`` stay full-precision —
    same-step tokens merge unquantized, like sdpa_cached).
    """
    mesh = current_mesh()
    n = mesh.shape.get(axis_name, 1) if mesh is not None else 1
    scale = 1.0 / (q.shape[-1] ** 0.5)
    quantized = k_scale is not None
    scale_ops = (k_scale, v_scale) if quantized else ()
    if n == 1:
        return _ring_decode_body(
            q, k_cache, v_cache, slot_pos, k_new, v_new, q_pos, new_pos,
            *scale_ops,
            axis_name=None, scale=scale, softmax_dtype=softmax_dtype,
            quantized=quantized,
        )

    head4 = P(BATCH_AXES, None, "tensor", None)
    cache4 = P(BATCH_AXES, axis_name, "tensor", None)
    scale3 = P(BATCH_AXES, axis_name, "tensor")
    fn = jax.shard_map(
        functools.partial(
            _ring_decode_body, axis_name=axis_name, scale=scale,
            softmax_dtype=softmax_dtype, quantized=quantized,
        ),
        mesh=mesh,
        in_specs=(
            head4, cache4, cache4, P(BATCH_AXES, axis_name), head4, head4,
            P(BATCH_AXES, None), P(BATCH_AXES, None),
        ) + ((scale3, scale3) if quantized else ()),
        out_specs=head4,
        check_vma=False,
    )
    return fn(
        q, k_cache, v_cache, slot_pos, k_new, v_new, q_pos, new_pos,
        *scale_ops,
    )
