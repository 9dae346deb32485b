"""Plain reference of one block: the dense pre-norm decoder with rotary
grouped-query attention and SwiGLU.  A configuration file asks for it with
`"reference": "dense_gqa"`; `benchmark/reference.py` loads it by that name and
holds the served tokens to `logits` under the two limits below.

Architecture (Mistral-7B-v0.3 and Codestral-22B, as published): token
embedding; per layer a pre-norm block, x += Attn(RMSNorm(x)) then
x += SwiGLU(RMSNorm(x)), with rotary grouped-query causal attention
(rotate-half pairing, as in the published implementation) and no sliding
window; final RMSNorm; untied output head.

Departures, each forced by where the weights come from: the weights are the
program's own seeded tree, so this file reads the program's parameter LAYOUT
(`layers.qkv` [L, KVH, G+2, D, hd] with slots q_0..q_{G-1}, k, v per KV head;
`layers.gate_up` [L, 2, D, F]; `layers.o` [L, H, hd, D]), but none of its
code.  Weights are upcast from the served bfloat16 to float32 one layer at a
time, because a float32 copy of the model does not fit beside the served one.

The limits.  With these seeded weights logits are about N(0, 1); the largest
of 32,768 is about 4.2.  Serving in bfloat16 (8 mantissa bits, rounding in
every layer) moves a logit by a few hundredths against float32.  On the chip,
over some 2,500 positions of 22 runs, the largest deficit was 0.056 and a
run's mean deficit 0.0003-0.0010 (PERF.md).  `MAX_DEFICIT` is under three times
the first and `MEAN_DEFICIT` five times the second.  The mean is the gate on
precision: weights or a KV cache kept in eight bits move every logit by
several hundredths to a tenth, flip many more near-ties and raise the mean
several-fold (`tests/` shows an 8-bit cast failing it); a wrong rope base, a
dropped KV head, a wrong mask, block table or shard makes the served token an
arbitrary one for the reference, about 4 under the maximum, and fails both.
"""

from __future__ import annotations

import math
from typing import Any, Dict

MAX_DEFICIT = 0.15
MEAN_DEFICIT = 0.005


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * scale


def _rope(x, theta: float):
    """x [B, T, H, hd]; pair i is (x[i], x[i + hd/2]), angle t * theta^(-2i/hd)."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _layer(x, lp, cfg: Dict[str, Any]):
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    H, KVH, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    G = H // KVH
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms_norm(x, lp["attn_norm"], eps)
    qkv = jnp.einsum("btd,cgdk->btcgk", h, lp["qkv"])
    q = qkv[..., :G, :].reshape(B, T, H, hd)
    k = jnp.repeat(qkv[..., G, :], G, axis=2)
    v = jnp.repeat(qkv[..., G + 1, :], G, axis=2)
    q, k = _rope(q, theta), _rope(k, theta)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("bthk,hkd->btd", a, lp["o"])
    h = _rms_norm(x, lp["mlp_norm"], eps)
    gu = jnp.einsum("btd,cdf->btcf", h, lp["gate_up"])
    return x + (jax.nn.silu(gu[..., 0, :]) * gu[..., 1, :]) @ lp["down"]


def logits(params, tokens, cfg: Dict[str, Any], first: int):
    """Reference logits [B, T - first, V] at positions first..T-1 of
    `tokens` [B, T] (all rows full length, no padding)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    @jax.jit
    def embed(table, toks):
        return jnp.take(table, toks, axis=0).astype(f32)

    @jax.jit
    def layer(x, layers, i):
        lp = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False).astype(f32),
            layers,
        )
        return _layer(x, lp, cfg)

    @jax.jit
    def head(x, norm, w):
        x = _rms_norm(x[:, first:], norm.astype(f32), cfg["rms_norm_eps"])
        return x @ w.astype(f32)

    with jax.default_matmul_precision("highest"):
        x = embed(params["embed"]["embedding"], tokens)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, params["layers"], jnp.int32(i))
        w = (params["embed"]["embedding"].T if cfg["tie_word_embeddings"]
             else params["lm_head"])
        return head(x, params["final_norm"], w)
