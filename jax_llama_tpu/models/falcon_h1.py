"""The block with a state-space mixer BESIDE attention in every layer
(falcon_h1-style), on the same `forward` / `init_params` / `init_cache`
surface as the dense block of `llama.py`, which dispatches here when
`config.parallel_mixer`.

    x = E[tokens] * embedding_multiplier
    layer:  a = RMSNorm_in(x)
            x = x + Mixer(a * ssm_in_multiplier) * ssm_out_multiplier
                  + Attn(a * attention_in_multiplier) * attention_out_multiplier
            f = RMSNorm_ff(x)
            x = x + ((f W_up) * silu((f W_gate) * mlp_multipliers[0])) W_down * mlp_multipliers[1]
    logits = (RMSNorm_final(x) W_head) * lm_head_multiplier         untied head

    Attn(u):  q = u Wq;  k = (u Wk) * key_multiplier;  v = u Wv;  rope (rotate-half,
              the whole head) on q, k;  causal GQA, query head h reads KV head h // (H / KVH)
    Mixer(u): [z | xBC | dt] = (u W_in) * m       m: `ssm_multipliers` spread over the
                                                  zones z, x, B, C, dt
              xBC = silu(causal depthwise conv(xBC) + b)     width 4 over x, B and C
              dt = softplus(dt + dt_bias);  A = -exp(A_log)  one scalar a head
              h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t   h [Hm, P, N] float32 (`ops/ssm.py`)
              y_t = h_t C_t + Dskip x_t
              y = GroupRMSNorm(y * silu(z));  out = y W_out  gate first, then the norm
                                                             a group of d_ssm / G values

Every layer is the same kind, so the parameters are ONE stacked tree and the
stack ONE scan.  Every layer owns K/V planes AND a per-row recurrent state:

    cache / pool planes   [L, B, S, KVH, hd]  or  [L, KVH, NB, BLK, hd] paged
    conv                  [L, rows, 3 * (d_ssm + 2 G N)]   activation type
    ssm                   [L, rows, Hm, P, N]              float32

A row whose tokens are all masked leaves both bit for bit; a row's live tokens
are a PREFIX of the call's `T` (right padding), which every caller keeps.  The
state rides the layer scan's CARRY and is written back a layer's slab at a
time, so a call holds one copy of it (0.8 GB at 32 rows of the published
widths), not a read-only one beside a stacked output.

    {"embed": {"embedding": [V, D]},
     "layers": {"in_norm", "ffn_norm" [L, D],
                "qkv" [L, KVH, G+2, D, hd] (slots q_0..q_{G-1}, k, v a KV head, as the
                dense block's), "o" [L, H, hd, D],
                "in_proj" [L, D, 2 d_ssm + 2 G N + Hm] (z | x | B | C | dt),
                "conv_w" [L, 4, d_ssm + 2 G N], "conv_b" [L, d_ssm + 2 G N],
                "dt_bias", "A_log", "D" [L, Hm] f32, "mixer_norm" [L, d_ssm],
                "out_proj" [L, d_ssm, D], "gate_up" [L, 2, D, F], "down" [L, F, D]},
     "final_norm": [D], "lm_head": [D, V]}

Every call counts into the cache's `stats` in `afmoe`'s layout (the routing
and window counts stay zero): the paged decode kernel's live grid steps.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import LLaMAConfig
from ..ops import moe, ssm
from ..ops.attention import attention_bias, sdpa, sdpa_cached
from ..ops.flash_attention import flash_attention
from ..ops.norm import rms_norm
from ..ops.rope import apply_rope_rows, rope_rows
from .afmoe import ATTN_STATS

Params = Dict[str, Any]

# What a sub-block adds to a residual stream of unit RMS, by the seeded
# initialisers below.
RESIDUAL_SHARE = 0.5
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


def zone_multipliers(config: LLaMAConfig) -> jnp.ndarray:
    """`ssm_multipliers` spread over the columns of `in_proj`: one value a
    zone, z [d_ssm], x [d_ssm], B [G N], C [G N], dt [Hm], float32."""
    GN = config.mamba_n_groups * config.mamba_d_state
    widths = (config.mamba_d_ssm, config.mamba_d_ssm, GN, GN, config.mamba_n_heads)
    return jnp.concatenate([
        jnp.full((w,), m, jnp.float32)
        for w, m in zip(widths, config.ssm_multipliers)])


def init_params(rng: jax.Array, config: LLaMAConfig) -> Params:
    """Seeded weights such that every multiplied path carries weight in the
    logits.  A projection is N(0, s^2) with `s = target / (multiplier *
    sqrt(fan_in))`, the multiplier being the product of those that stand
    between its normed input and its output: `target` is 1 for what feeds a
    non-linearity (q, k, v, the mixer's zones, the FFN's gate and up: unit
    RMS), `RESIDUAL_SHARE` for what is added to the residual stream (`o`,
    `out_proj`, `down`), 1 for the logits and for the embedding after its
    multiplier.  With N(0, 0.02^2) everywhere the head's 0.0078 and the keys'
    0.011 flatten logits and attention, and a check passes anything.  The conv
    weight uniform in +-fan_in^-0.5 and its bias small and non-zero, `A`
    uniform in [1, 16] a head, `dt_bias` the inverse softplus of a
    log-uniform dt in [1e-3, 1e-1], `D` 1 (the family's initialisers, as
    `models/sambay.py` takes them for its own: a random `A` or `dt_bias`
    makes the state explode or vanish); norms 1."""
    config.validate()
    D, H, KVH, hd, V, F, L = (config.dim, config.n_heads, config.kv_heads,
                              config.head_dim, config.vocab_size,
                              config.ffn_dim, config.n_layers)
    G = H // KVH
    Ds, Hm, Cd, K = (config.mamba_d_ssm, config.mamba_n_heads,
                     config.mamba_conv_dim, config.mamba_d_conv)
    wd = config.weight_dtype
    f32 = jnp.float32

    def dense(key, shape, fan_in, multiplier=1.0, target=1.0):
        std = target / (jnp.asarray(multiplier, f32) * math.sqrt(fan_in))
        return (jax.random.normal(key, shape, f32) * std).astype(wd)

    k = jax.random.split(rng, 12)
    a_in = config.attention_in_multiplier
    # q and v behind the attention input's multiplier, k behind the keys' too.
    slot = jnp.asarray([a_in] * G + [a_in * config.key_multiplier, a_in], f32)
    dt = jnp.exp(jax.random.uniform(k[5], (L, Hm), f32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    layers = {
        "in_norm": jnp.ones((L, D), wd), "ffn_norm": jnp.ones((L, D), wd),
        "qkv": dense(k[1], (L, KVH, G + 2, D, hd), D, slot[None, None, :, None, None]),
        "o": dense(k[2], (L, H, hd, D), H * hd,
                   config.attention_out_multiplier, RESIDUAL_SHARE),
        "in_proj": dense(k[3], (L, D, Ds + Cd + Hm), D,
                         config.ssm_in_multiplier * zone_multipliers(config)),
        "conv_w": jax.random.uniform(
            k[4], (L, K, Cd), f32, -(K ** -0.5), K ** -0.5).astype(wd),
        "conv_b": (jax.random.normal(k[6], (L, Cd), f32) * 0.02).astype(wd),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k[7], (L, Hm), f32, A_MIN, A_MAX)),
        "D": jnp.ones((L, Hm), f32),
        "mixer_norm": jnp.ones((L, Ds), wd),
        "out_proj": dense(k[8], (L, Ds, D), Ds,
                          config.ssm_out_multiplier, RESIDUAL_SHARE),
        "gate_up": dense(k[9], (L, 2, D, F), D, jnp.asarray(
            [config.mlp_multipliers[0], 1.0], f32)[None, :, None, None]),
        "down": dense(k[10], (L, F, D), F,
                      config.mlp_multipliers[1], RESIDUAL_SHARE),
    }
    return {
        "embed": {"embedding": dense(k[0], (V, D), 1, config.embedding_multiplier)},
        "layers": layers,
        "final_norm": jnp.ones((D,), wd),
        "lm_head": dense(k[11], (D, V), D, config.lm_head_multiplier),
    }


def forward(
    params: Params,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    config: LLaMAConfig,
    cache=None,
    attn_mask: Optional[jnp.ndarray] = None,
    compute_logits: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    output_hidden_states: bool = False,
    output_attentions: bool = False,
    output_last_hidden: bool = False,
):
    """`llama.forward`'s contract for the block with parallel mixer and
    attention layers: cache-free (the state starts at zero), over a `KVCache`
    (scalar or per-row index) or over a `PagedKVCache`, each with its `conv`
    / `ssm` state."""
    from .llama import (
        FLASH_MIN_SEQ, AuxOutput, KVCache, PagedKVCache, init_state,
        lm_head_logits, paged_pool_write, paged_write_indices, qeinsum,
    )

    if dropout_rng is not None:
        raise NotImplementedError(
            "the block with parallel mixer and attention layers is served, "
            "not trained: dropout_rng (the training step) is not supported")
    if output_hidden_states or output_attentions:
        raise NotImplementedError(
            "output_hidden_states / output_attentions are not supported by "
            "the block with parallel mixer and attention layers")
    B, T = tokens.shape
    adt = config.activation_dtype
    f32 = jnp.float32
    H, KVH, hd = config.n_heads, config.kv_heads, config.head_dim
    G = H // KVH
    Ds, Hm, P, N, Gm, Cd = (
        config.mamba_d_ssm, config.mamba_n_heads, config.mamba_d_head,
        config.mamba_d_state, config.mamba_n_groups, config.mamba_conv_dim)
    eps = config.rms_norm_eps
    softmax_dtype = jnp.dtype(config.attn_softmax_dtype)
    paged = isinstance(cache, PagedKVCache)
    if attn_mask is None:
        attn_mask = positions >= 0
    q_positions = jnp.maximum(positions, 0)
    new_pos = jnp.where(attn_mask, q_positions, -1).astype(jnp.int32)
    # A row's live tokens are a prefix of T (right padding): what the mixers
    # advance their state by.
    lengths = jnp.sum(attn_mask.astype(jnp.int32), axis=1)

    use_flash = (not paged and T > FLASH_MIN_SEQ
                 and config.attn_impl in ("flash", "auto")
                 and not (cache is not None and cache.per_row_index))
    attn_stats = jnp.zeros((len(ATTN_STATS),), jnp.int32)
    if paged:
        from ..ops.paged_attention import (
            fetch_plan, paged_decode_attention, plan_live_steps,
        )

        NB, BLK = cache.pos.shape
        row_active = attn_mask[:, 0]
        if T > 1:  # the kernel's T > 1 contract (see `llama.paged_forward`)
            row_active = (
                row_active & jnp.all(attn_mask == attn_mask[:, :1], axis=1)
                & jnp.all(positions == positions[:, :1]
                          + jnp.arange(T, dtype=positions.dtype), axis=1))
        q_pos_row = jnp.where(row_active, positions[:, 0], -1).astype(jnp.int32)
        lengths = jnp.where(row_active, T, 0).astype(jnp.int32)
        plan = fetch_plan(cache.k, cache.pos, cache.table, q_pos_row, T, None)
        attn_stats = jnp.stack([
            jnp.int32(0), config.n_layers * plan_live_steps(plan),
        ]).astype(jnp.int32)
    elif cache is not None:
        slot_pos = (
            cache.pos.at[
                jnp.arange(B, dtype=jnp.int32)[:, None],
                cache.index[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :],
            ].set(new_pos, mode="drop")
            if cache.per_row_index
            else lax.dynamic_update_slice(cache.pos, new_pos, (0, cache.index))
        )
    else:
        slot_pos = new_pos
    conv0, ssm0 = (init_state(config, B) if cache is None
                   else (cache.conv, cache.ssm))
    row_live = lengths > 0
    cos, sin = rope_rows(q_positions, hd, config.rope_theta)
    zones = zone_multipliers(config)

    def attend(q, k, v, ck, cv, li):
        if paged:
            return paged_decode_attention(
                q, k, v, cache.k, cache.v, cache.pos, cache.table, q_pos_row,
                layer=li, window=None, plan=plan)
        if use_flash:
            if ck is None:
                return flash_attention(q, k, v, q_positions, new_pos)
            # Scalar index: the new entries stand at [index, index + T) of
            # the layer's slices for the kernel's one sweep (`afmoe.forward`).
            at = (0, cache.index, 0, 0)
            return flash_attention(
                q, lax.dynamic_update_slice(ck, k.astype(ck.dtype), at).astype(adt),
                lax.dynamic_update_slice(cv, v.astype(cv.dtype), at).astype(adt),
                q_positions, slot_pos)
        bias_new = attention_bias(q_positions, new_pos, attn_mask)
        if ck is None:
            return sdpa(q, k, v, bias_new, softmax_dtype=softmax_dtype)
        bias = attention_bias(q_positions, cache.pos, cache.pos >= 0)
        return sdpa_cached(
            q, ck.astype(adt), cv.astype(adt), k, v, bias, bias_new,
            softmax_dtype=softmax_dtype)

    def scaled(x, m: float):
        """`x * m` in float32, back in the activation type; nothing at 1."""
        return x if m == 1.0 else (x.astype(f32) * m).astype(adt)

    def attention(a, lp, ck, cv, li):
        with jax.named_scope("attn.full"):
            u = scaled(a, config.attention_in_multiplier)
            qkv = qeinsum(u, lp["qkv"], "btd,cgdk->btcgk", adt)
            q = qkv[..., :G, :].reshape(B, T, H, hd)
            k = scaled(qkv[..., G, :], config.key_multiplier)
            v = qkv[..., G + 1, :]
            q = apply_rope_rows(q, cos, sin)
            k = apply_rope_rows(k, cos, sin)
            out = attend(q, k, v, ck, cv, li)
            out = qeinsum(out, lp["o"], "bthk,hkd->btd", adt)
            return scaled(out, config.attention_out_multiplier), k, v

    def mixer(a, lp, li, conv_all, ssm_all):
        """One layer's mixer: (its output, the conv and ssm state of every
        layer with this layer's slabs advanced).  The slabs are taken from
        and put back into the carried state INSIDE the scopes, so that a
        trace charges the state's traffic to the step or the scan."""
        pick = lambda s: lax.dynamic_index_in_dim(s, li, 0, keepdims=False)  # noqa: E731
        put = lax.dynamic_update_index_in_dim
        with jax.named_scope("ssm.mix"):
            u = scaled(a, config.ssm_in_multiplier)
            p = qeinsum(u, lp["in_proj"], "btd,de->bte", adt,
                        preferred_element_type=f32) * zones
            z, xbc, dt = p[..., :Ds], p[..., Ds:Ds + Cd].astype(adt), p[..., Ds + Cd:]
            seen = jnp.concatenate(
                [pick(conv_all).reshape(B, 3, Cd).astype(adt), xbc], axis=1)
            w = lp["conv_w"].astype(f32)
            c = sum(w[j] * seen[:, j:j + T].astype(f32) for j in range(4))
            c = jax.nn.silu(c + lp["conv_b"].astype(f32)).astype(adt)
            # The last 3 inputs a row has seen: columns lengths .. lengths + 2
            # of [state | chunk]; a row with nothing live keeps its own.
            at = lengths[:, None] + jnp.arange(3, dtype=jnp.int32)[None, :]
            new_conv = jnp.take_along_axis(seen, at[:, :, None], axis=1)
            conv_all = put(
                conv_all, new_conv.reshape(B, 3 * Cd).astype(conv_all.dtype), li, 0)
            xs = c[..., :Ds].reshape(B, T, Hm, P)
            Bm = c[..., Ds:Ds + Gm * N].reshape(B, T, Gm, N)
            Cm = c[..., Ds + Gm * N:].reshape(B, T, Gm, N)
            dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
            A = -jnp.exp(lp["A_log"].astype(f32))
            if T == 1:
                with jax.named_scope("ssm.step"):
                    y, new_ssm = ssm.ssd_step(
                        pick(ssm_all), xs[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A,
                        row_live)
                    y = y[:, None]
                    ssm_all = put(ssm_all, new_ssm, li, 0)
            else:
                with jax.named_scope("ssm.scan"):
                    y, new_ssm = ssm.ssd_scan(
                        pick(ssm_all), xs, dt, Bm, Cm, A, lengths,
                        chunk=config.mamba_chunk_size)
                    ssm_all = put(ssm_all, new_ssm, li, 0)
            y = y + lp["D"].astype(f32)[:, None] * xs.astype(f32)
            # Gate first, then the norm a group of d_ssm / G values.
            g = (y.reshape(B, T, Ds) * jax.nn.silu(z)).reshape(B, T, Gm, Ds // Gm)
            g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            g = (g.reshape(B, T, Ds) * lp["mixer_norm"].astype(f32)).astype(adt)
            out = qeinsum(g, lp["out_proj"], "bte,ed->btd", adt)
            return scaled(out, config.ssm_out_multiplier), conv_all, ssm_all

    def ffn(x, lp):
        with jax.named_scope("dense.ffn"):
            m = rms_norm(x, lp["ffn_norm"], eps)
            gu = qeinsum(m, lp["gate_up"], "btd,cdf->btcf", adt)
            hidden = jax.nn.silu(scaled(gu[..., 0, :], config.mlp_multipliers[0])) * gu[..., 1, :]
            out = qeinsum(hidden, lp["down"], "btf,fd->btd", adt)
            return x + scaled(out, config.mlp_multipliers[1])

    cached = cache is not None and not paged

    def layer(carry, xs):
        x, conv_all, ssm_all = carry
        lp, li, *kv = xs
        a = rms_norm(x, lp["in_norm"], eps)
        mixed, conv_all, ssm_all = mixer(a, lp, li, conv_all, ssm_all)
        attended, k, v = attention(a, lp, *(kv or (None, None)), li)
        return (ffn(x + mixed + attended, lp), conv_all, ssm_all), (k, v)

    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    x = scaled(x.astype(adt), config.embedding_multiplier)
    xs = (params["layers"], jnp.arange(config.n_layers, dtype=jnp.int32))
    if cached:  # read-only through the scan: one write after it
        xs += (cache.k, cache.v)
    carry = (x, conv0, ssm0)
    if config.scan_layers:
        carry, (new_k, new_v) = lax.scan(layer, carry, xs, unroll=config.scan_unroll)
    else:
        outs = []
        for i in range(config.n_layers):
            carry, kv = layer(carry, jax.tree.map(lambda a: a[i], xs))
            outs.append(kv)
        new_k, new_v = jax.tree.map(lambda *a: jnp.stack(a), *outs)
    x, new_conv, new_ssm = carry
    stats = jnp.concatenate([jnp.zeros((moe.N_STATS,), jnp.int32), attn_stats])

    final_h = rms_norm(x, params["final_norm"], eps)
    aux = (AuxOutput(hidden_states=None, last_hidden_state=final_h, attentions=None)
           if output_last_hidden else None)
    logits = (lm_head_logits(params, final_h, config, normed=True)
              if compute_logits else None)
    if cache is None:
        return (logits, None, aux) if aux is not None else (logits, None)

    total = stats if cache.stats is None else cache.stats + stats
    if paged:
        blk, off, _ = paged_write_indices(
            cache.table, cache.fill, row_active, T, NB, BLK)
        new_cache = dataclasses.replace(
            cache,
            k=paged_pool_write(cache.k, jnp.moveaxis(new_k, 3, 1), blk, off),
            v=paged_pool_write(cache.v, jnp.moveaxis(new_v, 3, 1), blk, off),
            pos=paged_pool_write(
                cache.pos, jnp.where(row_active[:, None], positions, -1), blk, off),
            conv=new_conv, ssm=new_ssm, stats=total,
        )
    else:
        new_k, new_v = new_k.astype(cache.k.dtype), new_v.astype(cache.v.dtype)
        if cache.per_row_index:
            rows = jnp.arange(B, dtype=jnp.int32)[:, None]
            cols = cache.index[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
            new_k = cache.k.at[:, rows, cols].set(new_k, mode="drop")
            new_v = cache.v.at[:, rows, cols].set(new_v, mode="drop")
        else:
            new_k = lax.dynamic_update_slice(cache.k, new_k, (0, 0, cache.index, 0, 0))
            new_v = lax.dynamic_update_slice(cache.v, new_v, (0, 0, cache.index, 0, 0))
        new_cache = KVCache(
            k=new_k, v=new_v, pos=slot_pos, index=cache.index + T,
            conv=new_conv, ssm=new_ssm, stats=total)
    return (logits, new_cache, aux) if aux is not None else (logits, new_cache)
