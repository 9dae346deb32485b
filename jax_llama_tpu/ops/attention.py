"""Grouped-query scaled-dot-product attention — XLA reference path.

Capability parity with the reference attention core (``/root/reference/
jax_llama/model.py:94-300``): GQA with KV-head replication *after* the cache
(the cache stays small, replication is per-step), causal + padding masking as
an additive fp32 bias, fp32 softmax.

TPU-first differences from the reference:
  * No materialized [1,1,S,S] causal-mask buffer (reference model.py:154) —
    masks are computed from position indices on the fly, so memory is
    O(T·S) per block at most, and the Pallas flash path (ops/flash_attention)
    never materializes scores at all.
  * einsum contractions keep [B, T, H, D] layout with explicit
    `preferred_element_type=float32` so the MXU accumulates in fp32.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = float(jnp.finfo(jnp.float32).min)


def dropout(rng: jax.Array, x: jnp.ndarray, rate: float) -> jnp.ndarray:
    """Inverted dropout (expectation-preserving), shared by the attention
    probabilities path and the model's embedding/residual sites."""
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Broadcast KV heads to match query heads for GQA.

    x: [B, S, KVH, D] -> [B, S, KVH * n_rep, D].
    """
    if n_rep == 1:
        return x
    b, s, kvh, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kvh, n_rep, d))
    return x.reshape(b, s, kvh * n_rep, d)


def attention_bias(
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    kv_valid: Optional[jnp.ndarray] = None,
    window=None,
) -> jnp.ndarray:
    """Additive fp32 attention bias combining causality and padding.

    Args:
      q_positions: [B, T] absolute positions of the query tokens.
      kv_positions: [B, S] absolute positions of the key/value slots.
      kv_valid: optional [B, S] bool — False for padding / unwritten cache
        slots.
      window: optional int32 scalar (a value, e.g. a layer's own in a layer
        scan): a query at position i sees the keys at i - window + 1 .. i,
        itself and the window - 1 before it.  None: the whole causal past.
    Returns:
      [B, 1, T, S] bias, 0 where attendable, finfo.min where masked.
    """
    allowed = kv_positions[:, None, :] <= q_positions[:, :, None]  # [B, T, S]
    if window is not None:
        allowed = jnp.logical_and(
            allowed,
            q_positions[:, :, None] - kv_positions[:, None, :] < window,
        )
    if kv_valid is not None:
        allowed = jnp.logical_and(allowed, kv_valid[:, None, :])
    bias = jnp.where(allowed, 0.0, NEG_INF).astype(jnp.float32)
    return bias[:, None, :, :]


def sdpa_cached(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    bias_cache: jnp.ndarray,
    bias_new: jnp.ndarray,
    softmax_dtype: jnp.dtype = jnp.float32,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    return_weights: bool = False,
):
    """Append-free cached attention: softmax over the (immutable) cache and
    the step's new KV jointly, concatenated at the *scores* level.

    Equivalent to writing the new KV into the cache first and attending the
    whole buffer, but the cache is never mutated inside the layer stack —
    so the decode engine can apply ONE in-place dynamic-update-slice per
    step after the scan instead of rewriting the cache per layer, which
    costs a full-cache double-buffer copy every step inside lax.scan/while.

    Args:
      q: [B, T, H, D].
      k_cache, v_cache: [B, S, KVH, D] — previously written slots only
        (unwritten slots must be masked by ``bias_cache``); int8 when
        ``k_scale``/``v_scale`` are given.
      k_new, v_new: [B, T, KVH, D] — this step's projections.
      bias_cache: [B, 1, T, S] additive bias over the cache slots.
      bias_new: [B, 1, T, T] additive bias over the new tokens
        (within-step causality + padding).
      k_scale, v_scale: optional [B, S, KVH] fp32 dequant scales for an
        int8 cache.  Scales are constant along D, so they commute with
        both contractions: QK scores are rescaled after the dot, and
        v_scale folds into the softmax weights before the PV dot — the
        int8 payload goes straight into the MXU, never dequantized in HBM.
      return_weights: also return the post-softmax probabilities
        [B, H, T, S + T] (columns: cache slots then the step's new
        tokens; pre-v_scale-fold) — the eval/interp surface, parity with
        the reference's ``output_attentions`` (model.py:299).
    Returns:
      [B, T, H, D] in q.dtype; ``(out, weights)`` with return_weights.
    """
    b, t, h, d = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, t, kvh, g, d)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=jnp.float32))
    kc = k_cache if k_scale is None else k_cache.astype(q.dtype)
    s1 = jnp.einsum(
        "btkgd,bskd->bkgts", qg, kc, preferred_element_type=jnp.float32
    ) * scale
    if k_scale is not None:
        s1 = s1 * jnp.transpose(k_scale, (0, 2, 1))[:, :, None, None, :]
    s1 = s1 + bias_cache[:, :, None]
    s2 = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k_new, preferred_element_type=jnp.float32
    ) * scale + bias_new[:, :, None]
    s = jnp.concatenate([s1, s2], axis=-1).astype(softmax_dtype)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    w1, w2 = w[..., : s1.shape[-1]], w[..., s1.shape[-1]:]
    vc = v_cache
    if v_scale is not None:
        # Fold the dequant scale into the (tiny) weights, not the cache.
        w1 = (
            w1.astype(jnp.float32)
            * jnp.transpose(v_scale, (0, 2, 1))[:, :, None, None, :]
        ).astype(q.dtype)
        vc = v_cache.astype(q.dtype)
    out = jnp.einsum(
        "bkgts,bskd->btkgd", w1, vc, preferred_element_type=jnp.float32
    ) + jnp.einsum(
        "bkgts,bskd->btkgd", w2, v_new, preferred_element_type=jnp.float32
    )
    out = out.reshape(b, t, h, d).astype(q.dtype)
    if return_weights:
        return out, w.reshape(b, h, t, w.shape[-1])
    return out


def sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    softmax_dtype: jnp.dtype = jnp.float32,
    dropout_rng: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    return_weights: bool = False,
):
    """Scaled dot-product attention with GQA.

    Args:
      q: [B, T, H, D].
      k, v: [B, S, KVH, D] with H % KVH == 0.
      bias: optional [B, 1, T, S] additive bias (fp32).
      dropout_rng, dropout_rate: attention-probability dropout (training
        only; parity with the reference's attn_pdrop, model.py:276-288).
        Inverted scaling keeps the expectation unchanged.
      return_weights: also return the post-softmax (pre-dropout)
        probabilities [B, H, T, S] — the eval/interp surface, parity
        with the reference's ``output_attentions`` (model.py:299).
    Returns:
      [B, T, H, D] in q.dtype; ``(out, weights)`` with return_weights.
    """
    b, t, h, d = q.shape
    kvh = k.shape[2]
    assert h % kvh == 0, (h, kvh)
    g = h // kvh

    # Grouped einsum instead of repeat_kv(k/v): a materialized KV broadcast
    # would cost g× the cache's HBM traffic per step (and XLA:TPU was
    # observed to materialize it in fp32 — ~4× again).  Folding the group
    # dim into the contraction keeps K/V at their stored size and dtype;
    # only the (tiny) scores/weights carry the replication.
    qg = q.reshape(b, t, kvh, g, d)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=jnp.float32))
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        scores = scores + bias[:, :, None]  # [B,1,T,S] -> [B,1,1,T,S]
    scores = scores.astype(softmax_dtype)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    probs = weights
    if dropout_rng is not None and dropout_rate > 0.0:
        weights = dropout(dropout_rng, weights, dropout_rate)
    out = jnp.einsum(
        "bkgts,bskd->btkgd", weights, v, preferred_element_type=jnp.float32
    )
    out = out.reshape(b, t, h, d).astype(q.dtype)
    if return_weights:
        return out, probs.reshape(b, h, t, probs.shape[-1])
    return out
