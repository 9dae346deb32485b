"""The block with a state-space mixer BESIDE attention in every layer
(falcon_h1-style), on the same `forward` / `mixed_forward` / `init_params` /
`init_cache` surface as the dense block of `llama.py`, which dispatches here
when `config.parallel_mixer`.

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
widths), not a read-only one beside a stacked output.  `mixed_forward` (a
prompt chunk and one token a decode row in ONE pass over the weights) carries
two such states the same way: the chunk's one row, and every slot's.  The
layer's mathematics stands once, in the helpers both share; what differs is
which rows attend where and whose state a recurrence advances.

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
from .llama import embed_tokens, layer_scan, qeinsum  # `llama` reaches this module inside its functions only

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


def _scaled(x, m: float, adt):
    """`x * m` in float32, back in the activation type; nothing at 1."""
    return x if m == 1.0 else (x.astype(jnp.float32) * m).astype(adt)


def _pick(state, li):
    return lax.dynamic_index_in_dim(state, li, 0, keepdims=False)


_put = lax.dynamic_update_index_in_dim


def _attend_rows(config, cache, q_positions, new_pos, attn_mask, slot_pos,
                 use_flash):
    """`attend(q, k, v, ck, cv)` of one call: causal attention of its new
    tokens over themselves and, with `ck` / `cv` (one layer's slices of the
    `KVCache` `cache`), over what the cache holds — the flash kernel's one
    sweep, or the append-free xla form."""
    adt = config.activation_dtype
    softmax_dtype = jnp.dtype(config.attn_softmax_dtype)

    def attend(q, k, v, ck, cv):
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

    return attend


def _attention(a, lp, config, cos, sin, attend):
    """One layer's attention over `a` [B, T, D]: (its output, the new keys
    and values [B, T, KVH, hd]).  `attend(q, k, v)` is the caller's: over a
    cache's slices, over the paged pool, or the two side by side."""
    adt = config.activation_dtype
    B, T = a.shape[:2]
    H, hd = config.n_heads, config.head_dim
    G = H // config.kv_heads
    with jax.named_scope("attn.full"):
        u = _scaled(a, config.attention_in_multiplier, adt)
        qkv = qeinsum(u, lp["qkv"], "btd,cgdk->btcgk", adt)
        q = qkv[..., :G, :].reshape(B, T, H, hd)
        k = _scaled(qkv[..., G, :], config.key_multiplier, adt)
        v = qkv[..., G + 1, :]
        q = apply_rope_rows(q, cos, sin)
        k = apply_rope_rows(k, cos, sin)
        out = attend(q, k, v)
        out = qeinsum(out, lp["o"], "bthk,hkd->btd", adt)
        return _scaled(out, config.attention_out_multiplier, adt), k, v


def _mixer_in(a, lp, config, zones):
    """`in_proj` over `a` [B, T, D], float32 accumulation, each zone times
    its multiplier: (z [B, T, d_ssm] float32, xBC [B, T, Cd] in the
    activation type, dt [B, T, Hm] float32, raw)."""
    adt = config.activation_dtype
    Ds, Cd = config.mamba_d_ssm, config.mamba_conv_dim
    u = _scaled(a, config.ssm_in_multiplier, adt)
    p = qeinsum(u, lp["in_proj"], "btd,de->bte", adt,
                preferred_element_type=jnp.float32) * zones
    return p[..., :Ds], p[..., Ds:Ds + Cd].astype(adt), p[..., Ds + Cd:]


def _conv(xbc, held, lengths, lp):
    """The causal depthwise conv (width 4, bias, silu) of `xbc` [R, T, Cd]
    behind the 3 inputs each row `held` [R, 3 * Cd]: (its output [R, T, Cd],
    the last 3 inputs each row has seen after its `lengths` live tokens, as
    `held` holds them)."""
    f32 = jnp.float32
    R, T, Cd = xbc.shape
    seen = jnp.concatenate(
        [held.reshape(R, 3, Cd).astype(xbc.dtype), xbc], axis=1)
    w = lp["conv_w"].astype(f32)
    c = sum(w[j] * seen[:, j:j + T].astype(f32) for j in range(4))
    c = jax.nn.silu(c + lp["conv_b"].astype(f32)).astype(xbc.dtype)
    # Columns lengths .. lengths + 2 of [state | chunk]; a row with nothing
    # live keeps its own.
    at = lengths[:, None] + jnp.arange(3, dtype=jnp.int32)[None, :]
    new = jnp.take_along_axis(seen, at[:, :, None], axis=1)
    return c, new.reshape(R, 3 * Cd).astype(held.dtype)


def _recur(c, dt, lp, ssm_all, li, lengths, row_live, config):
    """The recurrence of the conv's output `c` [R, T, Cd] at the raw `dt`
    [R, T, Hm] over layer `li`'s slab of `ssm_all` [L, R, Hm, P, N]: (y
    [R, T, Hm, P] float32, x by heads, `ssm_all` with the slab advanced by
    each row's `lengths` live tokens; `row_live` is `lengths > 0`).  One
    token a row is a step, more are a scan; the slab is taken from and put
    back into the carried state INSIDE the scopes, so that a trace charges
    the state's traffic to the step or the scan."""
    f32 = jnp.float32
    R, T = c.shape[:2]
    Ds, Hm, P, N, Gm = (
        config.mamba_d_ssm, config.mamba_n_heads, config.mamba_d_head,
        config.mamba_d_state, config.mamba_n_groups)
    xs = c[..., :Ds].reshape(R, T, Hm, P)
    Bm = c[..., Ds:Ds + Gm * N].reshape(R, T, Gm, N)
    Cm = c[..., Ds + Gm * N:].reshape(R, T, Gm, N)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
    A = -jnp.exp(lp["A_log"].astype(f32))
    if T == 1:
        with jax.named_scope("ssm.step"):
            y, new_ssm = ssm.ssd_step(
                _pick(ssm_all, li), xs[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A,
                row_live)
            y = y[:, None]
            ssm_all = _put(ssm_all, new_ssm, li, 0)
    else:
        with jax.named_scope("ssm.scan"):
            y, new_ssm = ssm.ssd_scan(
                _pick(ssm_all, li), xs, dt, Bm, Cm, A, lengths,
                chunk=config.mamba_chunk_size)
            ssm_all = _put(ssm_all, new_ssm, li, 0)
    return y, xs, ssm_all


def _mixer_out(y, xs, z, lp, config):
    """What follows the recurrence, over [B, T]: the skip `D x`, the gate,
    then the norm a group of d_ssm / G values, `out_proj`."""
    f32 = jnp.float32
    adt = config.activation_dtype
    B, T = y.shape[:2]
    Ds, Gm = config.mamba_d_ssm, config.mamba_n_groups
    y = y + lp["D"].astype(f32)[:, None] * xs.astype(f32)
    g = (y.reshape(B, T, Ds) * jax.nn.silu(z)).reshape(B, T, Gm, Ds // Gm)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + config.rms_norm_eps)
    g = (g.reshape(B, T, Ds) * lp["mixer_norm"].astype(f32)).astype(adt)
    out = qeinsum(g, lp["out_proj"], "bte,ed->btd", adt)
    return _scaled(out, config.ssm_out_multiplier, adt)


def _ffn(x, lp, config):
    adt = config.activation_dtype
    m0, m1 = config.mlp_multipliers
    with jax.named_scope("dense.ffn"):
        m = rms_norm(x, lp["ffn_norm"], config.rms_norm_eps)
        gu = qeinsum(m, lp["gate_up"], "btd,cdf->btcf", adt)
        hidden = jax.nn.silu(_scaled(gu[..., 0, :], m0, adt)) * gu[..., 1, :]
        out = qeinsum(hidden, lp["down"], "btf,fd->btd", adt)
        return x + _scaled(out, m1, adt)


def _scan_layers(layer, carry, xs, config):
    """The stack as ONE scan of `layer`, or unrolled (`scan_layers` off)."""
    if config.scan_layers:
        return layer_scan(layer, carry, xs, unroll=config.scan_unroll)
    outs = []
    for i in range(config.n_layers):
        carry, y = layer(carry, jax.tree.map(lambda a: a[i], xs))
        outs.append(y)
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *outs)


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
        lm_head_logits, paged_pool_write, paged_write_indices,
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
    eps = config.rms_norm_eps
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
    slot_pos = None
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
    cos, sin = rope_rows(q_positions, config.head_dim, config.rope_theta)
    zones = zone_multipliers(config)

    attend_rows = _attend_rows(
        config, cache, q_positions, new_pos, attn_mask, slot_pos, use_flash)
    cached = cache is not None and not paged

    def layer(carry, per_layer):
        x, conv_all, ssm_all = carry
        lp, li, *kv = per_layer
        ck, cv = kv or (None, None)
        a = rms_norm(x, lp["in_norm"], eps)
        with jax.named_scope("ssm.mix"):
            z, xbc, dt = _mixer_in(a, lp, config, zones)
            c, held = _conv(xbc, _pick(conv_all, li), lengths, lp)
            conv_all = _put(conv_all, held, li, 0)
            y, xs, ssm_all = _recur(
                c, dt, lp, ssm_all, li, lengths, row_live, config)
            mixed = _mixer_out(y, xs, z, lp, config)

        def attend(q, k, v):
            if paged:
                return paged_decode_attention(
                    q, k, v, cache.k, cache.v, cache.pos, cache.table,
                    q_pos_row, layer=li, window=None, plan=plan)
            return attend_rows(q, k, v, ck, cv)

        attended, k, v = _attention(a, lp, config, cos, sin, attend)
        return (_ffn(x + mixed + attended, lp, config), conv_all, ssm_all), (k, v)

    x = embed_tokens(params, tokens)
    x = _scaled(x.astype(adt), config.embedding_multiplier, adt)
    per_layer = (params["layers"], jnp.arange(config.n_layers, dtype=jnp.int32))
    if cached:  # read-only through the scan: one write after it
        per_layer += (cache.k, cache.v)
    (x, new_conv, new_ssm), (new_k, new_v) = _scan_layers(
        layer, (x, conv0, ssm0), per_layer, config)
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


def mixed_forward(
    params: Params,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    config: LLaMAConfig,
    cache,
    attn_mask: jnp.ndarray,
    rider_tokens: jnp.ndarray,
    rider_positions: jnp.ndarray,
    pool,
):
    """`llama.mixed_forward`'s contract for the block with parallel mixer
    and attention layers: the chunk's [1, C] `tokens` over the one-row
    `cache` (a `KVCache` with a scalar index and the row's `conv` / `ssm`)
    and one token a decode row (`rider_tokens` [B] at `rider_positions`, -1
    for a row that rides masked) over `pool` (a `PagedKVCache` with every
    slot's state) go through every layer as ONE [1, C + B, D] activation.
    A layer splits in two places.  Attention: the chunk's rows attend over
    the cache's slices as `forward` does, the riders through the paged
    kernel (its plan bound outside the scan) and land once a plane after
    the scan.  The mixer's recurrence, which is a row's own: behind the
    shared `in_proj` the chunk's columns take the conv and `ssd_scan` over
    the cache's state, the riders' the conv and `ssd_step` over the pool's;
    both states ride the scan's carry.  A masked rider leaves its slot's
    state bit for bit.

    Returns (post-final-norm hidden states [1, C + B, D] — the chunk's rows,
    then the riders' —, the updated `cache`, the updated `pool`); the call's
    counts are added to the `stats` of both (a caller that folds one cache
    into the other keeps one).  The head is the caller's."""
    from ..ops.paged_attention import (
        fetch_plan, paged_decode_attention, plan_live_steps,
    )
    from .llama import FLASH_MIN_SEQ, _paged_land

    if cache.per_row_index:
        raise NotImplementedError("mixed_forward: a cache with a scalar index")
    C = tokens.shape[1]
    adt = config.activation_dtype
    eps = config.rms_norm_eps
    q_positions = jnp.maximum(positions, 0)
    new_pos = jnp.where(attn_mask, q_positions, -1).astype(jnp.int32)
    lengths = jnp.sum(attn_mask.astype(jnp.int32), axis=1)
    slot_pos = lax.dynamic_update_slice(cache.pos, new_pos, (0, cache.index))
    use_flash = C > FLASH_MIN_SEQ and config.attn_impl in ("flash", "auto")
    rider_qpos = rider_positions.astype(jnp.int32)
    riding = rider_qpos >= 0
    rider_lengths = riding.astype(jnp.int32)
    plan = fetch_plan(pool.k, pool.pos, pool.table, rider_qpos, 1, None)
    cos, sin = rope_rows(
        jnp.concatenate([q_positions, jnp.maximum(rider_qpos, 0)[None]], axis=1),
        config.head_dim, config.rope_theta)
    zones = zone_multipliers(config)
    attend_rows = _attend_rows(
        config, cache, q_positions, new_pos, attn_mask, slot_pos, use_flash)
    chunk_live = lengths > 0

    def riders(a):  # [1, C + B, ...] -> the riders' columns as rows, [B, 1, ...]
        return jnp.swapaxes(a[:, C:], 0, 1)

    def rejoin(chunk, rode):  # [1, C, ...] and [B, 1, ...] -> [1, C + B, ...]
        return jnp.concatenate([chunk, jnp.swapaxes(rode, 0, 1)], axis=1)

    def layer(carry, per_layer):
        x, conv_c, ssm_c, conv_r, ssm_r = carry
        lp, li, ck, cv = per_layer
        a = rms_norm(x, lp["in_norm"], eps)
        with jax.named_scope("ssm.mix"):
            z, xbc, dt = _mixer_in(a, lp, config, zones)
            c_c, held = _conv(xbc[:, :C], _pick(conv_c, li), lengths, lp)
            conv_c = _put(conv_c, held, li, 0)
            c_r, held = _conv(riders(xbc), _pick(conv_r, li), rider_lengths, lp)
            conv_r = _put(conv_r, held, li, 0)
            y_c, xs_c, ssm_c = _recur(
                c_c, dt[:, :C], lp, ssm_c, li, lengths, chunk_live, config)
            y_r, xs_r, ssm_r = _recur(
                c_r, riders(dt), lp, ssm_r, li, rider_lengths, riding, config)
            mixed = _mixer_out(
                rejoin(y_c, y_r), rejoin(xs_c, xs_r), z, lp, config)

        def attend(q, k, v):
            chunk = attend_rows(q[:, :C], k[:, :C], v[:, :C], ck, cv)
            rode = paged_decode_attention(
                riders(q), riders(k), riders(v), pool.k, pool.v, pool.pos,
                pool.table, rider_qpos, layer=li, window=None, plan=plan)
            return rejoin(chunk, rode)

        attended, k, v = _attention(a, lp, config, cos, sin, attend)
        x = _ffn(x + mixed + attended, lp, config)
        return ((x, conv_c, ssm_c, conv_r, ssm_r),
                (k[:, :C], v[:, :C], riders(k), riders(v)))

    x = embed_tokens(
        params, jnp.concatenate([tokens, rider_tokens[None]], axis=1))
    x = _scaled(x.astype(adt), config.embedding_multiplier, adt)
    per_layer = (params["layers"], jnp.arange(config.n_layers, dtype=jnp.int32),
                 cache.k, cache.v)
    (x, conv_c, ssm_c, conv_r, ssm_r), (new_k, new_v, rider_k, rider_v) = (
        _scan_layers(
            layer, (x, cache.conv, cache.ssm, pool.conv, pool.ssm), per_layer,
            config))
    stats = jnp.concatenate([
        jnp.zeros((moe.N_STATS,), jnp.int32),
        jnp.stack([jnp.int32(0), config.n_layers * plan_live_steps(plan)]
                  ).astype(jnp.int32)])
    counted = lambda c: stats if c.stats is None else c.stats + stats  # noqa: E731
    at = (0, 0, cache.index, 0, 0)
    return (
        rms_norm(x, params["final_norm"], eps),
        dataclasses.replace(
            cache,
            k=lax.dynamic_update_slice(cache.k, new_k.astype(cache.k.dtype), at),
            v=lax.dynamic_update_slice(cache.v, new_v.astype(cache.v.dtype), at),
            pos=slot_pos, index=cache.index + C, conv=conv_c, ssm=ssm_c,
            stats=counted(cache)),
        dataclasses.replace(
            _paged_land(pool, rider_k, rider_v, None, None, riding,
                        rider_qpos[:, None], rolled=True),
            conv=conv_r, ssm=ssm_r, stats=counted(pool)),
    )
