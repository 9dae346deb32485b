"""The window-and-full-attention block with routed experts (afmoe-style), on
the same `forward` / `init_params` / `init_cache` surface as the dense block
of `llama.py`, which dispatches here when `config.windowed_attention`.

    x0 = E[tokens] * sqrt(dim)
    per layer (a norm on BOTH sides of each sub-block, four weights a layer):
      a = RMSNorm_in(x)
      q = a Wq -> [H, hd]   k = a Wk -> [KVH, hd]   v = a Wv   g = a Wg -> [H, hd]
      q, k = RMSNorm_q(q), RMSNorm_k(k)          over hd, one weight [hd] each
      window layer (window_layers[i], W = sliding_window):  q, k = rope(q, k);
                    key j is seen by query i  iff  0 <= i - j < W
      full layer   (not window_layers[i]):      no rope, no position at all;
                    key j is seen by query i  iff  j <= i
      o = softmax(q k^T / sqrt(hd)) v           GQA, H / KVH query heads a KV head
      x = x + RMSNorm_post_attn((o * sigmoid(g)) Wo)
      m = RMSNorm_pre_mlp(x)
      f = SwiGLU(m)                                         i <  first_k_dense
        = shared SwiGLU(m) + ops.moe.routed_experts(m)      otherwise
      x = x + RMSNorm_post_mlp(f)
    logits = RMSNorm_final(x) W_head

`config.head_dim` is a size of its own beside `dim` (`head_size`).

The layer kind inside a stack is a per-layer VALUE carried through the layer
scan (`window_layers[i]`), on which the attention sub-block branches
(`lax.cond`): a stack of one FFN kind is one stacked tree and one scan
whatever its pattern of kinds, each kind's branch is static about its window
and its rope, and its operations carry the kind's own scope (`attn.window` /
`attn.full`) into a device trace.  The kernels take the window as an operand:
the flash kernel's k sweep starts at the first key block inside it, the paged
decode kernel's grid holds the steps that overlap it
(`ops/flash_attention.py`, `ops/paged_attention.py`); a full layer runs the
kernels without one.  The paged step list depends on the kind, not the layer:
both kinds' are derived once an iteration OUTSIDE the scan.

The cache is the dense block's: K and V planes of `kv_heads` heads of
`head_dim`, every layer's kept ([L, KVH, NB, BLK, hd] twice in the pool), so
a cached prefix block stays valid whatever its depth.  Keys are cached as
attended: normed, and rotated on the window layers.

Parameters are two stacked trees, one per FFN kind, each scanned:

    {"embed": {"embedding": [V, D]},
     "dense_layers": {<attention>, "gate_up" [Ld,2,D,F], "down" [Ld,F,D]},
     "moe_layers":   {<attention>, "router" [Lm,D,E], "router_bias" [Lm,E] f32,
                      "experts_gate_up" [Lm,E,D,2Fe], "experts_down" [Lm,E,Fe,D],
                      "shared_gate_up" [Lm,2,D,Fs], "shared_down" [Lm,Fs,D]},
     "final_norm": [D], "lm_head": [D, V]}
    <attention> = "attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm" [L,D],
                  "qkv" [L,KVH,G+2,D,hd] (slots q_0..q_{G-1}, k, v a KV head, as
                  the dense block's), "gate" [L,H,D,hd], "q_norm", "k_norm" [L,hd],
                  "o" [L,H,hd,D]

Every call counts into the cache's `stats` (`N_STATS` int32): the routing
counts of `ops.moe.STATS`, then `ATTN_STATS` — the paged decode kernel's live
grid steps, summed over rows and the layers of each kind.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import LLaMAConfig
from ..ops import moe
from ..ops.attention import attention_bias, sdpa, sdpa_cached
from ..ops.flash_attention import flash_attention
from ..ops.norm import rms_norm
from ..ops.rope import apply_rope_rows, rope_rows
from .mla_moe import INIT_STD, ROUTER_BIAS_STD, routed_ffn

Params = Dict[str, Any]

# What a call counts of its paged decode attention, after `ops.moe.STATS`.
ATTN_STATS = ("window_kv_steps", "full_kv_steps")
N_STATS = moe.N_STATS + len(ATTN_STATS)


def init_params(rng: jax.Array, config: LLaMAConfig) -> Params:
    """Seeded weights, N(0, INIT_STD^2) as the latent block's (the family's
    `initializer_range`; see `mla_moe.INIT_STD`), the router's selection-only
    bias small and non-zero so that it is exercised."""
    config.validate()
    D, H, KVH, hd, V = (config.dim, config.n_heads, config.kv_heads,
                        config.head_dim, config.vocab_size)
    G = H // KVH
    E, Fe, F = config.n_routed_experts, config.moe_intermediate_size, config.ffn_dim
    Fs = max(config.n_shared_experts, 1) * Fe
    Ld, Lm = config.first_k_dense, config.n_layers - config.first_k_dense
    wd = config.weight_dtype

    def dense(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * INIT_STD).astype(wd)

    def attention(key, L):
        k = jax.random.split(key, 3)
        ones = lambda n: jnp.ones((L, n), wd)  # noqa: E731
        return {
            "attn_norm": ones(D), "post_attn_norm": ones(D),
            "mlp_norm": ones(D), "post_mlp_norm": ones(D),
            "qkv": dense(k[0], (L, KVH, G + 2, D, hd)),
            "gate": dense(k[1], (L, H, D, hd)),
            "q_norm": ones(hd), "k_norm": ones(hd),
            "o": dense(k[2], (L, H, hd, D)),
        }

    keys = jax.random.split(rng, 12)
    params: Params = {
        "embed": {"embedding": dense(keys[0], (V, D))},
        "dense_layers": dict(
            attention(keys[1], Ld),
            gate_up=dense(keys[2], (Ld, 2, D, F)),
            down=dense(keys[3], (Ld, F, D)),
        ),
        "moe_layers": dict(
            attention(keys[4], Lm),
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
    """`llama.forward`'s contract for the window-and-full-attention block:
    cache-free, over a `KVCache` (scalar or per-row index) or over a
    `PagedKVCache`."""
    from .llama import (
        FLASH_MIN_SEQ, AuxOutput, KVCache, PagedKVCache, _swiglu,
        embed_tokens, layer_scan, lm_head_logits, paged_pool_write,
        paged_write_indices, qeinsum,
    )

    if dropout_rng is not None:
        raise NotImplementedError(
            "the window-attention block is served, not trained: dropout_rng "
            "(the training step) is not supported")
    if output_hidden_states or output_attentions:
        raise NotImplementedError(
            "output_hidden_states / output_attentions are not supported by "
            "the window-attention block")
    B, T = tokens.shape
    adt = config.activation_dtype
    H, KVH, hd = config.n_heads, config.kv_heads, config.head_dim
    G = H // KVH
    eps = config.rms_norm_eps
    softmax_dtype = jnp.dtype(config.attn_softmax_dtype)
    paged = isinstance(cache, PagedKVCache)
    if attn_mask is None:
        attn_mask = positions >= 0
    q_positions = jnp.maximum(positions, 0)
    new_pos = jnp.where(attn_mask, q_positions, -1).astype(jnp.int32)

    # How the new tokens attend, layer-independent (the dense block's rule).
    use_flash = (not paged and T > FLASH_MIN_SEQ
                 and config.attn_impl in ("flash", "auto")
                 and not (cache is not None and cache.per_row_index))
    attn_stats = jnp.zeros((len(ATTN_STATS),), jnp.int32)
    # The window length is the kernels' operand; what a layer carries
    # through the scan is only which kind it is.
    n_window = sum(config.window_layers)
    n_full = config.n_layers - n_window
    window = jnp.int32(config.sliding_window) if n_window else None
    if paged:
        from ..ops.paged_attention import (
            fetch_plan, paged_decode_attention, plan_live_steps,
        )

        NB, BLK = cache.pos.shape
        # The kernel's T > 1 contract, enforced by definition (see
        # `llama.paged_forward`).
        row_active = attn_mask[:, 0]
        if T > 1:
            row_active = (
                row_active & jnp.all(attn_mask == attn_mask[:, :1], axis=1)
                & jnp.all(positions == positions[:, :1]
                          + jnp.arange(T, dtype=positions.dtype), axis=1))
        q_pos_row = jnp.where(row_active, positions[:, 0], -1).astype(jnp.int32)
        valid = jnp.broadcast_to(row_active[:, None], (B, T))
        # One step list a layer KIND (keyed: windowed?), derived here, outside
        # the layer scans; each kind's live steps count once a layer of it.
        plans = {
            windowed: fetch_plan(cache.k, cache.pos, cache.table, q_pos_row, T,
                                 window if windowed else None)
            for windowed, n in ((True, n_window), (False, n_full)) if n
        }
        attn_stats = jnp.stack([
            n * plan_live_steps(plans[windowed]) if n else jnp.int32(0)
            for windowed, n in ((True, n_window), (False, n_full))
        ]).astype(jnp.int32)
    else:
        valid = attn_mask
        if cache is not None:
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
    # The tokens' rotary rows, once a call, outside the scans and branches.
    cos, sin = rope_rows(q_positions, hd, config.rope_theta)

    x = embed_tokens(params, tokens)
    x = (x.astype(jnp.float32) * math.sqrt(config.dim)).astype(adt)

    def attend(q, k, v, ck, cv, li, window):
        """Attention [B,T,H,hd] of one layer; `window` is the layer kind's
        own, static inside its branch: an int32 length, or None (full)."""
        if paged:
            return paged_decode_attention(
                q, k, v, cache.k, cache.v, cache.pos, cache.table, q_pos_row,
                layer=li, window=window, plan=plans[window is not None])
        if use_flash:
            if ck is None:
                return flash_attention(q, k, v, q_positions, new_pos, window=window)
            # Scalar index: the new entries stand at [index, index + T) of
            # the layer's slices for the kernel's one sweep, which visits
            # the key blocks the window leaves (`_window_bounds`) and no
            # others.  The cache itself is read-only through the scan.
            at = (0, cache.index, 0, 0)
            return flash_attention(
                q, lax.dynamic_update_slice(ck, k.astype(ck.dtype), at).astype(adt),
                lax.dynamic_update_slice(cv, v.astype(cv.dtype), at).astype(adt),
                q_positions, slot_pos, window=window)
        if ck is None:
            bias = attention_bias(q_positions, new_pos, attn_mask, window=window)
            return sdpa(q, k, v, bias, softmax_dtype=softmax_dtype)
        # Decode-sized steps and per-row indices: cache and step softmaxed
        # jointly at the scores level (`sdpa_cached`).
        bias = attention_bias(q_positions, cache.pos, cache.pos >= 0, window=window)
        bias_new = attention_bias(q_positions, new_pos, attn_mask, window=window)
        return sdpa_cached(
            q, ck.astype(adt), cv.astype(adt), k, v, bias, bias_new,
            softmax_dtype=softmax_dtype)

    def attention(scope: str, window):
        """One kind's attention sub-block, under the kind's own scope: the
        layer scan takes one of the two by the layer's VALUE (`lax.cond`)."""
        def run(x, lp, ck, cv, li):
            with jax.named_scope(scope):
                a = rms_norm(x, lp["attn_norm"], eps)
                qkv = qeinsum(a, lp["qkv"], "btd,cgdk->btcgk", adt)
                q = qkv[..., :G, :].reshape(B, T, H, hd)
                k, v = qkv[..., G, :], qkv[..., G + 1, :]
                gate = qeinsum(a, lp["gate"], "btd,hdk->bthk", adt)
                q = rms_norm(q, lp["q_norm"], eps)
                k = rms_norm(k, lp["k_norm"], eps)
                if window is not None:  # rope on the window layers only
                    q = apply_rope_rows(q, cos, sin)
                    k = apply_rope_rows(k, cos, sin)
                out = attend(q, k, v, ck, cv, li, window)
                out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(adt)
                out = qeinsum(out, lp["o"], "bthk,hkd->btd", adt)
                return rms_norm(out, lp["post_attn_norm"], eps), k, v
        return run

    attend_window = attention("attn.window", window)
    attend_full = attention("attn.full", None)

    def layer(x, lp, ck, cv, li, w, ffn):
        out, k, v = lax.cond(w, attend_window, attend_full, x, lp, ck, cv, li)
        x = x + out
        m = rms_norm(x, lp["mlp_norm"], eps)
        f, stats = ffn(m, lp, li)
        return x + rms_norm(f, lp["post_mlp_norm"], eps), (k, v), stats

    windows = jnp.asarray(config.window_layers, jnp.bool_)
    cached = cache is not None and not paged

    def stack(x, lp, first: int, ffn):
        n = next(iter(lp.values())).shape[0]
        li = first + jnp.arange(n, dtype=jnp.int32)
        xs = (lp, li, windows[first:first + n])
        if cached:  # read-only through the scan: one write after it
            xs += (cache.k[first:first + n], cache.v[first:first + n])

        def body(carry, xs):
            lp_i, li_i, w_i, *kv = xs
            y, kept, stats = layer(carry, lp_i, *(kv or (None, None)), li_i, w_i, ffn)
            return y, (kept, stats)

        if config.scan_layers:
            return layer_scan(body, x, xs, unroll=config.scan_unroll)
        outs = []
        for i in range(n):
            x, ys = body(x, jax.tree.map(lambda a: a[i], xs))
            outs.append(ys)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)

    no_stats = jnp.zeros((moe.N_STATS,), jnp.int32)

    def ffn_dense(h, lp, li):
        with jax.named_scope("dense.ffn"):
            return _swiglu(h, lp["gate_up"], lp["down"]), no_stats

    # The experts stay out of the scanned tree (see mla_moe.forward).
    scanned = dict(params["moe_layers"])
    experts = (scanned.pop("experts_gate_up"), scanned.pop("experts_down"))

    def ffn_moe(h, lp, li):
        return routed_ffn(h, lp, experts, li - config.first_k_dense, valid, config)

    x, ((k_d, v_d), _) = stack(x, params["dense_layers"], 0, ffn_dense)
    x, ((k_m, v_m), stats) = stack(x, scanned, config.first_k_dense, ffn_moe)
    new_k = jnp.concatenate([k_d, k_m], axis=0)   # [L, B, T, KVH, hd]
    new_v = jnp.concatenate([v_d, v_m], axis=0)
    stats = jnp.concatenate([jnp.sum(stats, axis=0), attn_stats])

    aux = None
    if output_last_hidden:
        final_h = rms_norm(x, params["final_norm"], eps)
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
            k=paged_pool_write(cache.k, jnp.moveaxis(new_k, 3, 1), blk, off),
            v=paged_pool_write(cache.v, jnp.moveaxis(new_v, 3, 1), blk, off),
            pos=paged_pool_write(
                cache.pos, jnp.where(row_active[:, None], positions, -1), blk, off),
            stats=total,
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
            k=new_k, v=new_v, pos=slot_pos, index=cache.index + T, stats=total)
    return (logits, new_cache, aux) if aux is not None else (logits, new_cache)
