"""The latent-attention block with routed experts (deepseek_v3-style), on
the same `forward` / `init_params` / `init_cache` surface as the dense block
of `llama.py`, which dispatches here when `config.latent_attention`.

Per layer: x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)).  The FFN of the
leading `first_k_dense` layers is the dense block's SwiGLU (`llama._swiglu`);
every later layer's is `ops.moe.routed_experts` plus a shared SwiGLU.

With `config.hc_mult` = n > 1 (`model_type: xing4_0`) the residual is n
streams: the layer stack carries `[B, T, n, C]`, begun as n copies of the
embedding and summed over n before `final_norm`, and each of a layer's two
`+=` is one mHC unit (`ops/mhc.py`) around the same inner function F:

    H_pre, H_post, H_res = coefficients(X)       per token, float32
    y = F(sum_i H_pre[i] X[i])                   F = MLA(RMSNorm(.)) or FFN(RMSNorm(.))
    X[i] <- sum_j H_res[i, j] X[j] + H_post[i] y

At n = 1 nothing of this is traced: the block lowers to x + F(norm(x)).

Attention (H heads, q/k width nope + rope, v width dv, latent rank r):

    q = h W_q -> [H, nope | rope]        h W_kva -> [r | rope]
    (`q_lora_rank` rq > 0:  q = RMSNorm(h W_qa; q_a_norm) W_qb)
    c = RMSNorm(h W_kva[:r])             k_rope = rope(h W_kva[r:])  ONE head
    c W_kvb -> [H, nope | dv] = k_nope | v per head
    score_n = (q_nope_n . k_nope_n + rope(q_rope_n) . k_rope) / sqrt(nope + rope)

The cache keeps `c ‖ k_rope`, r + rope values a token a layer and nothing
per head, in the `k` plane of the cache types of `llama.py` with ONE cache
head, the row zero-padded to the 128 lanes (`config.cache_width`); there is
no `v` plane.  Two forms of the same attention:

- decompressed (prompt chunks): k_nope and v of every attendable slot are
  rebuilt from the cached latent, then ordinary multi-head attention — a
  head's K/V a tile at a time in vector memory, inside the flash kernel
  that consumes the latent rows (`ops.flash_attention.latent_flash_attention`),
  or whole in plain XLA.  Behind a cache with a scalar index the flash form
  walks the live context only, tile by tile from the cache where it lies,
  for a trip count that is a value (`attend_tiled`);
- absorbed (decode): W_kvb's key half is folded into the query and its
  value half applied after the sum, so the 32 heads attend the latent rows
  themselves as one shared key/value head (`paged_decode_attention` with
  `v_width`): decode reads r + rope values a slot and never a per-head K/V.

`config.rope_yarn`: the rope tables take `ops.rope.yarn_inv_freq` and the
softmax scale `yarn_mscale`^2 (`softmax_scale`).

Rotary pairing: column i of a rope part pairs with column i + rope/2
(`ops.rope`, as everywhere in the program).  The published layout stores the
pair as adjacent columns (`rope_interleave`); a converter permutes the rope
columns of W_q and W_kva once at load, as `rope_permute` does for Meta
checkpoints, and the scores are the same.

Parameters are two stacked trees, one per layer kind, each scanned:

    {"embed": {"embedding": [V, D]},
     "dense_layers": {<attention>, "mlp_norm", "gate_up" [Ld,2,D,F], "down"},
     "moe_layers":   {<attention>, "mlp_norm", "router" [Lm,D,E],
                      "router_bias" [Lm,E] f32,
                      "experts_gate_up" [Lm,E,D,2Fe], "experts_down" [Lm,E,Fe,D],
                      "shared_gate_up" [Lm,2,D,Fs], "shared_down" [Lm,Fs,D]},
     "final_norm": [D], "lm_head": [D, V]}
    <attention> = "attn_norm" [L,D], "q" [L,H,D,nope+rope], "kv_a" [L,D,r+rope],
                  "kv_norm" [L,r], "kv_b" [L,H,r,nope+dv], "o" [L,H,dv,D]
    with `q_lora_rank`, in place of "q":
                  "q_a" [L,D,rq], "q_a_norm" [L,rq], "q_b" [L,H,rq,nope+rope]
    with `hc_mult` n > 1, in both layer trees, float32 (`ops.mhc.init_unit`):
                  "hc_attn", "hc_ffn" = {"phi" [L,nC,n*n+2n], "b" [L,n*n+2n],
                                         "alpha" [L,3]}

Every call also counts its routing (`ops.moe.N_STATS` int32) into the
cache's `stats`, which the serving loop returns with its packed fetch; with
`hc_mult` > 1 the units' `ops.mhc.STATS` follow them there (`n_stats`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import LLaMAConfig
from ..ops import mhc, moe
from ..ops.attention import attention_bias, sdpa
from ..ops.flash_attention import latent_flash_attention
from ..ops.norm import rms_norm
from ..ops.rope import apply_rope, rope_table, yarn_inv_freq, yarn_mscale

Params = Dict[str, Any]

# Seeded weights: every projection and the embedding are N(0, INIT_STD^2), the
# family's published initializer (`initializer_range` 0.02 of the HF
# deepseek_v3 config), not the fan-in scaling of `llama.init_params`.  At
# these widths the two differ where the fan-in is far from 2048: fan-in scaling
# makes a routed expert's output (fan-in 768) twice as large and the attention
# and dense outputs smaller, so that ONE sixth-against-seventh expert flipped by
# a bfloat16 hidden state moves a token's logits by several tenths and bfloat16
# serving reads like a fault against the float32 reference (v5e, PERF.md
# section 6, PR 27).  The router's selection-only bias is small and non-zero, so
# that selection with it and weights without it are both exercised.
INIT_STD = 0.02
ROUTER_BIAS_STD = 0.02


def init_params(rng: jax.Array, config: LLaMAConfig) -> Params:
    """Seeded weights, N(0, INIT_STD^2) (see `INIT_STD`)."""
    config.validate()
    D, H, V = config.dim, config.n_heads, config.vocab_size
    r, dn, dr, dv = (config.kv_lora_rank, config.qk_nope_head_dim,
                     config.qk_rope_head_dim, config.v_head_dim)
    E, Fe = config.n_routed_experts, config.moe_intermediate_size
    Fs = max(config.n_shared_experts, 1) * Fe
    Ld, Lm = config.first_k_dense, config.n_layers - config.first_k_dense
    wd = config.weight_dtype

    def dense(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * INIT_STD).astype(wd)

    rq, n = config.q_lora_rank, config.hc_mult

    def attention(key, L, first_layer):
        k = jax.random.split(key, 4)
        tree = {
            "attn_norm": jnp.ones((L, D), wd),
            "q": dense(k[0], (L, H, D, dn + dr)),
            "kv_a": dense(k[1], (L, D, r + dr)),
            "kv_norm": jnp.ones((L, r), wd),
            "kv_b": dense(k[2], (L, H, r, dn + dv)),
            "o": dense(k[3], (L, H, dv, D)),
            "mlp_norm": jnp.ones((L, D), wd),
        }
        if rq:
            ka, kb = jax.random.split(k[0])
            del tree["q"]
            tree.update(q_a=dense(ka, (L, D, rq)), q_a_norm=jnp.ones((L, rq), wd),
                        q_b=dense(kb, (L, H, rq, dn + dr)))
        if n > 1:
            ka, kf = jax.random.split(jax.random.fold_in(key, 1))
            tree.update(hc_attn=mhc.init_unit(ka, L, n, D, 2 * first_layer),
                        hc_ffn=mhc.init_unit(kf, L, n, D, 2 * first_layer + 1))
        return tree

    keys = jax.random.split(rng, 12)
    F = config.ffn_dim
    params: Params = {
        "embed": {"embedding": dense(keys[0], (V, D))},
        "dense_layers": dict(
            attention(keys[1], Ld, 0),
            gate_up=dense(keys[2], (Ld, 2, D, F)),
            down=dense(keys[3], (Ld, F, D)),
        ),
        "moe_layers": dict(
            attention(keys[4], Lm, Ld),
            router=dense(keys[5], (Lm, D, E)),
            router_bias=jax.random.normal(keys[6], (Lm, E), jnp.float32) * ROUTER_BIAS_STD,
            experts_gate_up=dense(keys[7], (Lm, E, D, 2 * Fe)),
            experts_down=dense(keys[8], (Lm, E, Fe, D)),
            shared_gate_up=dense(keys[9], (Lm, 2, D, Fs)),
            shared_down=dense(keys[10], (Lm, Fs, D)),
        ),
        "final_norm": jnp.ones((D,), wd),
        "lm_head": dense(keys[11], (D, V)),
    }
    if not config.n_shared_experts:
        del params["moe_layers"]["shared_gate_up"], params["moe_layers"]["shared_down"]
    return params


def n_stats(config: LLaMAConfig) -> int:
    """Counters a forward adds to a cache's `stats`: the routing counts, and
    behind them the mHC units' where the residual has streams."""
    return moe.N_STATS + (mhc.N_STATS if config.hc_mult > 1 else 0)


def softmax_scale(config: LLaMAConfig) -> float:
    """(nope + rope)^(-1/2), times YaRN's `mscale_all_dim` temperature squared."""
    scale = 1.0 / math.sqrt(config.qk_head_dim)
    if config.rope_yarn is not None:
        factor, _, _, _, mscale_all_dim = config.rope_yarn
        scale *= yarn_mscale(factor, mscale_all_dim) ** 2
    return scale


def _yarn_tables(config: LLaMAConfig, max_positions: int):
    factor, original, beta_fast, beta_slow, _ = config.rope_yarn
    return rope_table(
        config.rope_dim, max_positions, config.rope_theta,
        inv_freq=yarn_inv_freq(config.rope_dim, config.rope_theta, factor,
                               int(original), beta_fast, beta_slow))


def _pad_last(x: jnp.ndarray, width: int) -> jnp.ndarray:
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def absorb_query(q_nope, q_rope, kv_b, dn: int, width: int):
    """[B,T,H,nope] queries folded through W_kvb's key half into the latent:
    [B,T,H,width] (zeros behind r + rope), to score against cached rows."""
    q_lat = jnp.einsum("bthk,hck->bthc", q_nope, kv_b[..., :dn].astype(q_nope.dtype))
    return _pad_last(jnp.concatenate([q_lat, q_rope], axis=-1), width)


def attend_absorbed(q_abs, latent, bias, r: int, scale: float):
    """Absorbed attention in plain XLA: q_abs [B,T,H,w] over latent rows
    [B,S,w] under `bias` [B,1,T,S]; the weighted latent [B,T,H,r]."""
    s = jnp.einsum("bthc,bsc->bhts", q_abs, latent,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s + bias, axis=-1)
    return jnp.einsum("bhts,bsc->bthc", p.astype(latent.dtype), latent[..., :r])


def _decompress(latent, kv_b, config: LLaMAConfig):
    """Per-head keys [B,S,H,nope+rope] and values [B,S,H,dv] rebuilt from
    latent rows [B,S,w]: k_nope | v = c W_kvb, the shared rope key on every head."""
    r, dn = config.kv_lora_rank, config.qk_nope_head_dim
    H, dr = config.n_heads, config.qk_rope_head_dim
    kv = jnp.einsum("bsc,hck->bshk", latent[..., :r], kv_b.astype(latent.dtype))
    k_rope = jnp.broadcast_to(
        latent[:, :, None, r:r + dr], latent.shape[:2] + (H, dr))
    return jnp.concatenate([kv[..., :dn], k_rope], axis=-1), kv[..., dn:]


def _fold_temperature(q, config: LLaMAConfig):
    """A query part for the decompressed forms, whose kernels divide by
    sqrt(nope + rope) themselves: what `softmax_scale` has beyond that (YaRN's
    temperature) is folded into the query."""
    if config.rope_yarn is None:
        return q
    gain = softmax_scale(config) * math.sqrt(config.qk_head_dim)
    return (q.astype(jnp.float32) * gain).astype(q.dtype)


def attend_decompressed(q_nope, q_rope, latent, kv_b, q_pos, kv_pos, bias,
                        config: LLaMAConfig, use_flash: bool):
    """Multi-head attention over K/V rebuilt from latent rows [B,S,w];
    [B,T,H,dv].  `bias` is used by the XLA path, the positions by flash,
    which rebuilds a head's K/V a tile at a time inside its kernel."""
    q_nope, q_rope = (_fold_temperature(q, config) for q in (q_nope, q_rope))
    if use_flash:
        return latent_flash_attention(
            q_nope, q_rope, latent, kv_b.astype(latent.dtype), q_pos, kv_pos)
    k, v = _decompress(latent, kv_b, config)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # sdpa takes one width for q, k and v: the value zero-padded to the q/k
    # width, which is also the width the published scale divides by.
    return sdpa(q, k, _pad_last(v, q.shape[-1]), bias,
                softmax_dtype=jnp.dtype(config.attn_softmax_dtype)
                )[..., :config.v_head_dim]


# The context walk's tile: the latent kernel's key block.
CTX_TILE = 2048


def ctx_tiles(index, view: int):
    """(tile, trips) of the walk over a cached context: fixed tiles of
    `CTX_TILE` slots (the whole view where that is narrower), as many as
    hold a slot below `index`.  One rule for the device's kernel (`index`
    traced) and the host's counters (`index` an int)."""
    tile = min(CTX_TILE, view)
    return tile, (index + tile - 1) // tile


def attend_tiled(q_nope, q_rope, latent, kv_b, q_pos, new_pos, cache, layer,
                 config: LLaMAConfig):
    """`attend_decompressed`'s flash form for a chunk [B,T] behind a cache
    with a scalar index, doing work for the live context only, in one
    kernel: the cached rows below `cache.index` are read from the cache a
    tile at a time for `ctx_tiles` trips, a value, then the chunk's own
    rows, under one running softmax.  Nothing of the view's width is
    rebuilt; the dead slots of the last tile are masked by their position,
    -1."""
    tile, trips = ctx_tiles(cache.index, cache.max_len)
    planes, B, view = cache.k.shape[:3]
    return latent_flash_attention(
        _fold_temperature(q_nope, config), _fold_temperature(q_rope, config),
        latent, kv_b.astype(latent.dtype), q_pos, new_pos,
        ctx=cache.k.reshape(planes, B, view, -1), ctx_pos=cache.pos,
        layer=layer, ctx_tiles=trips, ctx_tile=tile)


def routed_ffn(h, lp, experts, layer, valid, config: LLaMAConfig):
    """`experts` are ALL expert layers' (gate_up, down), not this layer's
    slice: the grouped matmul picks `layer` itself (`ops.moe.grouped_matmul`)."""
    from .llama import _swiglu

    B, T, D = h.shape
    routed, stats = moe.routed_experts(
        h.reshape(B * T, D), None if valid is None else valid.reshape(B * T),
        lp["router"], lp.get("router_bias"), *experts, layer,
        top_k=config.n_experts_per_tok, scale=config.routed_scaling_factor,
        score_func=config.moe_score_func,
    )
    out = routed.reshape(B, T, D)
    if "shared_gate_up" in lp:
        with jax.named_scope("moe.shared"):
            out = out + _swiglu(h, lp["shared_gate_up"], lp["shared_down"])
    return out, stats


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
    """`llama.forward`'s contract for the latent-attention block: cache-free,
    over a `KVCache` (scalar or per-row index) or over a `PagedKVCache`."""
    from .llama import (
        FLASH_MIN_SEQ, AuxOutput, KVCache, PagedKVCache, _rope_tables, _swiglu,
        embed_tokens, layer_scan, lm_head_logits, paged_pool_write,
        paged_write_indices, qeinsum,
    )

    if dropout_rng is not None:
        raise NotImplementedError(
            "the latent-attention block is served, not trained: dropout_rng "
            "(the training step) is not supported")
    if output_hidden_states or output_attentions:
        raise NotImplementedError(
            "output_hidden_states / output_attentions are not supported by "
            "the latent-attention block")
    B, T = tokens.shape
    adt = config.activation_dtype
    r, dn = config.kv_lora_rank, config.qk_nope_head_dim
    scale = softmax_scale(config)
    paged = isinstance(cache, PagedKVCache)
    if attn_mask is None:
        attn_mask = positions >= 0
    q_positions = jnp.maximum(positions, 0)
    new_pos = jnp.where(attn_mask, q_positions, -1).astype(jnp.int32)

    if paged:
        NB, BLK = cache.pos.shape
        span = cache.table.shape[1] * BLK
        # The kernel's T > 1 contract, enforced by definition (see
        # `llama.paged_forward`): a row is live as a whole, at consecutive
        # positions, or it is folded to inactive.
        row_active = attn_mask[:, 0]
        if T > 1:
            row_active = (
                row_active & jnp.all(attn_mask == attn_mask[:, :1], axis=1)
                & jnp.all(positions == positions[:, :1]
                          + jnp.arange(T, dtype=positions.dtype), axis=1))
        q_pos_row = jnp.where(row_active, positions[:, 0], -1).astype(jnp.int32)
        valid = jnp.broadcast_to(row_active[:, None], (B, T))
    else:
        span = cache.max_len if cache is not None else 0
        valid = attn_mask
    n_positions = max(2 * config.max_seq_len, span)
    if config.rope_yarn is None:
        cos, sin = _rope_tables(config.rope_dim, n_positions, config.rope_theta, False)
    else:
        cos, sin = _yarn_tables(config, n_positions)

    # What the new tokens attend besides themselves, layer-independent.
    absorbed = cache is not None and T <= FLASH_MIN_SEQ
    use_flash = (not absorbed and T > FLASH_MIN_SEQ
                 and config.attn_impl in ("flash", "auto"))
    # A scalar index says which cached slots are live: the walk by tiles.
    tiled = use_flash and not paged and cache is not None and not cache.per_row_index
    if not paged and not tiled:
        kv_pos = new_pos if cache is None else jnp.concatenate(
            [cache.pos, new_pos], axis=1)
        bias = None if use_flash else attention_bias(q_positions, kv_pos, kv_pos >= 0)

    x = embed_tokens(params, tokens).astype(adt)
    n_streams = config.hc_mult
    if n_streams > 1:
        x = jnp.broadcast_to(x[:, :, None, :], (B, T, n_streams, x.shape[-1]))

    def unit(x, hp, inner, scope=None):
        """One residual step around `inner` (u -> (y, aux)): x + y on one
        stream (the add under `scope`, where the parent put it), an mHC unit
        on several.  Returns (x, aux, the unit's counters or None)."""
        if n_streams == 1:
            y, aux = inner(x)
            with jax.named_scope(scope) if scope else contextlib.nullcontext():
                return x + y, aux, None
        h_pre, h_post, h_res, counts = mhc.coefficients(
            x, hp, iters=config.hc_sinkhorn_iters, eps=config.hc_eps,
            clamp=config.hc_clamp, valid=valid)
        y, aux = inner(mhc.pre(x, h_pre))
        return mhc.post(x, y, h_res, h_post), aux, counts

    def attention(x, lp, li):
        h = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        with jax.named_scope("mla.project"):
            if config.q_lora_rank:
                q_a = rms_norm(qeinsum(h, lp["q_a"], "btd,dr->btr", adt),
                               lp["q_a_norm"], config.rms_norm_eps)
                q = qeinsum(q_a, lp["q_b"], "btr,hrk->bthk", adt)
            else:
                q = qeinsum(h, lp["q"], "btd,hdk->bthk", adt)
            kva = qeinsum(h, lp["kv_a"], "btd,dk->btk", adt)
            c = rms_norm(kva[..., :r], lp["kv_norm"], config.rms_norm_eps)
            k_rope = apply_rope(kva[:, :, None, r:], cos, sin, q_positions)[:, :, 0]
            q_nope = q[..., :dn]
            q_rope = apply_rope(q[..., dn:], cos, sin, q_positions)
            latent = _pad_last(
                jnp.concatenate([c, k_rope], axis=-1), config.cache_width)
            kv_b = lp["kv_b"]
        if paged:
            from ..ops.paged_attention import paged_decode_attention

            with jax.named_scope("mla.attend_decode"):
                o_lat = paged_decode_attention(
                    absorb_query(q_nope, q_rope, kv_b, dn, config.cache_width), latent[:, :, None, :],
                    None, cache.k, None, cache.pos, cache.table, q_pos_row,
                    layer=li, v_width=r, scale=scale)
        elif tiled:
            with jax.named_scope("mla.attend_prefill"):
                attn = attend_tiled(
                    q_nope, q_rope, latent, kv_b, q_positions, new_pos,
                    cache, li, config)
        else:
            seen = latent if cache is None else jnp.concatenate([
                lax.dynamic_index_in_dim(cache.k, li, 0, keepdims=False)[:, :, 0]
                .astype(adt), latent], axis=1)
            if absorbed:
                with jax.named_scope("mla.attend_decode"):
                    o_lat = attend_absorbed(
                        absorb_query(q_nope, q_rope, kv_b, dn, config.cache_width), seen, bias, r, scale)
            else:
                with jax.named_scope("mla.attend_prefill"):
                    attn = attend_decompressed(
                        q_nope, q_rope, seen, kv_b, q_positions, kv_pos, bias,
                        config, use_flash)
        if paged or absorbed:
            with jax.named_scope("mla.project"):
                attn = jnp.einsum("bthc,hck->bthk", o_lat, kv_b[..., dn:].astype(adt))
        with jax.named_scope("mla.project"):
            return qeinsum(attn, lp["o"], "bthk,hkd->btd", adt), latent

    def layer(x, lp, li, ffn):
        x, latent, hc_a = unit(
            x, lp.get("hc_attn"), lambda u: attention(u, lp, li), "mla.project")
        x, stats, hc_f = unit(
            x, lp.get("hc_ffn"),
            lambda u: ffn(rms_norm(u, lp["mlp_norm"], config.rms_norm_eps), lp, li))
        if n_streams > 1:
            stats = jnp.concatenate([stats, hc_a + hc_f])
        return x, latent, stats

    def stack(x, lp, first: int, ffn):
        n = next(iter(lp.values())).shape[0]

        def body(carry, xs):
            lp_i, li = xs
            y, latent, stats = layer(carry, lp_i, li, ffn)
            return y, (latent, stats)

        if config.scan_layers:
            return layer_scan(
                body, x, (lp, first + jnp.arange(n, dtype=jnp.int32)),
                unroll=config.scan_unroll)
        outs = []
        for i in range(n):
            x, ys = body(x, (jax.tree.map(lambda a: a[i], lp), jnp.int32(first + i)))
            outs.append(ys)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)

    no_stats = jnp.zeros((moe.N_STATS,), jnp.int32)

    def ffn_dense(h, lp, li):
        with jax.named_scope("dense.ffn"):
            return _swiglu(h, lp["gate_up"], lp["down"]), no_stats

    # The experts stay out of the scanned tree: a scan slices its xs a layer
    # at a time, and a slice of 128 experts is a copy (see ops/moe.py).
    scanned = dict(params["moe_layers"])
    experts = (scanned.pop("experts_gate_up"), scanned.pop("experts_down"))

    def ffn_moe(h, lp, li):
        return routed_ffn(h, lp, experts, li - config.first_k_dense, valid, config)

    x, (lat_d, stats_d) = stack(x, params["dense_layers"], 0, ffn_dense)
    x, (lat_m, stats) = stack(x, scanned, config.first_k_dense, ffn_moe)
    new_lat = jnp.concatenate([lat_d, lat_m], axis=0)            # [L, B, T, w]
    stats = jnp.sum(stats, axis=0)  # every statistic adds up over layer calls
    if n_streams > 1:
        # the dense layers route nothing, and count their units
        stats = stats + jnp.sum(stats_d, axis=0)
        x = jnp.sum(x.astype(jnp.float32), axis=2).astype(adt)

    aux = None
    if output_last_hidden:
        final_h = rms_norm(x, params["final_norm"], config.rms_norm_eps)
        aux = AuxOutput(hidden_states=None, last_hidden_state=final_h, attentions=None)
    logits = (
        lm_head_logits(params, final_h if aux is not None else x, config,
                       normed=aux is not None)
        if compute_logits else None
    )
    if cache is None:
        return (logits, None, aux) if aux is not None else (logits, None)

    total = stats if cache.stats is None else cache.stats + stats
    if paged:
        blk, off, _ = paged_write_indices(
            cache.table, cache.fill, row_active, T, NB, BLK)
        new_cache = dataclasses.replace(
            cache,
            k=paged_pool_write(cache.k, new_lat[:, None], blk, off),
            pos=paged_pool_write(
                cache.pos, jnp.where(row_active[:, None], positions, -1), blk, off),
            stats=total,
        )
    else:
        upd = new_lat[:, :, :, None, :].astype(cache.k.dtype)    # [L, B, T, 1, w]
        if cache.per_row_index:
            rows = jnp.arange(B, dtype=jnp.int32)[:, None]
            cols = cache.index[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
            new_k = cache.k.at[:, rows, cols].set(upd, mode="drop")
            pos = cache.pos.at[rows, cols].set(new_pos, mode="drop")
        else:
            new_k = lax.dynamic_update_slice(cache.k, upd, (0, 0, cache.index, 0, 0))
            pos = lax.dynamic_update_slice(cache.pos, new_pos, (0, cache.index))
        new_cache = KVCache(k=new_k, v=None, pos=pos, index=cache.index + T, stats=total)
    return (logits, new_cache, aux) if aux is not None else (logits, new_cache)
