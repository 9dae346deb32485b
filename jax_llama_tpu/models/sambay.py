"""The block with recurrent state layers (phi4flash-style "SambaY"), on the
same `forward` / `init_params` / `init_cache` surface as the dense block of
`llama.py`, which dispatches here when `config.recurrent_state`.

    x = E[tokens]                                   no scale, no position encoding
    layer i:  a = LN_in(x);  x = x + mix_i(a);  x = x + SwiGLU(LN_post(x))
    logits = LN_final(x) E^T                        tied head

`mix_i` by `config.layer_kinds` (L layers, halves of mixer / attention pairs):

    mamba      Mamba-1 mixer: in-projection, causal depthwise conv (width 4),
               selective scan (`ops/ssm.py`), gate, out-projection.  Carries a
               per-ROW state: the last 3 conv inputs and `h` [N, Di] float32.
    window     differential attention over its own K/V, `sliding_window` keys
    mamba_pub  a mixer whose scan output (before the gate) is kept as `m`
    full_pub   differential attention over its own K/V, causal; the ONLY K/V
               the second half reads
    gmu        (silu(a W1) * m) W2: no state, no cache
    cross      differential attention, a query projection only, over
               `full_pub`'s K/V

Differential attention runs as ordinary GQA at head size `2 hd` (the
head-pair form): query head pair (2p, 2p+1) becomes two padded queries
`[q1 | 0]`, `[0 | q2]`, a KV head pair is cached as ONE row `[k1 | k2]` /
`[v1 | v2]`, so `q~1 K^T = q1 k1^T` and `q~2 K^T = q2 k2^T` exactly and both
attend `[v1 | v2]`: the flash and paged kernels and their window operand as
the other blocks run them, 128-lane rows.  The queries are scaled by sqrt(2)
so that the kernels' `1 / sqrt(2 hd)` is the published `1 / sqrt(hd)`.  Then
`o = RMSNorm(o1 - lam o2) (1 - lam0)` a pair.  It doubles the score product's
FLOPs (PERF.md section 7).

The cache.  K/V planes exist for the `config.cache_layers` layers that own
keys (the window layers, then `full_pub`: plane index `L/4`), `[Lc, B, S,
KVH/2, 2hd]` or `[Lc, KVH/2, NB, BLK, 2hd]` paged; beside them a per-row state
for the `config.state_layers` mixers: `conv` `[Ls, B, 3 Di]` in the activation
type and `ssm` `[Ls, B, N, Di]` float32 (channels minor: see `ops/ssm.py`).
A row whose tokens are all masked leaves both bit-for-bit; a row's live tokens
are a PREFIX of the call's `T` (right padding), which every caller keeps.

Parameters are three stacked trees, each a scan of PAIRS so that compile time
is that of a few layers:

    {"embed": {"embedding": [V, D]},
     "self_layers":  {"mixer": <mamba> [L/4, ...], "attn": <attention> [L/4, ...]},
     "mid_layers":   {"mixer": <mamba> [1, ...],   "attn": <attention> [1, ...]},
     "cross_layers": {"mixer": <gmu> [L/4-1, ...], "attn": <attention, no kv> [L/4-1, ...]},
     "final_norm": [D], "final_norm_bias": [D]}
    every layer: "in_norm", "in_norm_bias", "post_norm", "post_norm_bias" [D],
                 "gate_up" [2, D, F], "down" [F, D]
    <mamba>:     "in_proj" [2, D, Di], "conv_w" [4, Di], "conv_b" [Di],
                 "x_proj" [Di, R+2N], "dt_proj" [R, Di], "dt_bias" [Di] f32,
                 "A_log" [Di, N] f32, "D" [Di] f32, "out_proj" [Di, D]
    <gmu>:       "in_proj" [D, Di], "out_proj" [Di, D]
    <attention>: "q" [H, D, hd], "q_bias" [H, hd], "kv" [KVH/2, 2, D, 2hd],
                 "kv_bias" [KVH/2, 2, 2hd], "o" [H/2, 2hd, D], "o_bias" [D],
                 "lambda" [4, hd] f32 (lq1, lk1, lq2, lk2), "subln" [2hd]

Every call counts into the cache's `stats` in `afmoe`'s layout (the routing
counts stay zero): the paged decode kernel's live grid steps by layer kind,
the cross layers with the full one.
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
from .afmoe import ATTN_STATS
from .mla_moe import INIT_STD

Params = Dict[str, Any]

SUBLN_EPS = 1e-5
LAMBDA_STD = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1


def init_params(rng: jax.Array, config: LLaMAConfig) -> Params:
    """Seeded weights by the family's initialisers: N(0, INIT_STD^2) for the
    projections and the embedding; the conv and `dt_proj` uniform in
    +-fan_in^-0.5; `A_log = log(1..N)` a channel, `dt_bias` the inverse
    softplus of a log-uniform dt in [1e-3, 1e-1], `D` 1 (a random `A` or
    `dt_bias` makes the state explode or vanish); the lambda vectors
    N(0, 0.1^2); LayerNorm 1 / 0; the projection and conv biases small and
    non-zero so that they are exercised."""
    config.validate()
    D, H, KVH, hd, V, F = (config.dim, config.n_heads, config.kv_heads,
                           config.head_dim, config.vocab_size, config.ffn_dim)
    Di, N, R, K = (config.mamba_d_inner, config.mamba_d_state, config.dt_rank,
                   config.mamba_d_conv)
    P1 = config.n_layers // 4
    wd = config.weight_dtype
    f32 = jnp.float32

    def dense(key, shape):
        return (jax.random.normal(key, shape, f32) * INIT_STD).astype(wd)

    def uniform(key, shape, bound):
        return jax.random.uniform(key, shape, f32, -bound, bound).astype(wd)

    def common(key, n):
        k = jax.random.split(key, 2)
        ones, zeros = jnp.ones((n, D), wd), jnp.zeros((n, D), wd)
        return {"in_norm": ones, "in_norm_bias": zeros, "post_norm": ones,
                "post_norm_bias": zeros, "gate_up": dense(k[0], (n, 2, D, F)),
                "down": dense(k[1], (n, F, D))}

    def mamba(key, n):
        k = jax.random.split(key, 8)
        dt = jnp.exp(jax.random.uniform(k[5], (n, Di), f32)
                     * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
        return dict(
            common(k[6], n),
            in_proj=dense(k[0], (n, 2, D, Di)),
            conv_w=uniform(k[1], (n, K, Di), K ** -0.5),
            conv_b=dense(k[7], (n, Di)),
            x_proj=dense(k[2], (n, Di, R + 2 * N)),
            dt_proj=uniform(k[3], (n, R, Di), R ** -0.5),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=f32)), (n, Di, N)),
            D=jnp.ones((n, Di), f32),
            out_proj=dense(k[4], (n, Di, D)),
        )

    def gmu(key, n):
        k = jax.random.split(key, 3)
        return dict(common(k[2], n), in_proj=dense(k[0], (n, D, Di)),
                    out_proj=dense(k[1], (n, Di, D)))

    def attention(key, n, own_kv=True):
        k = jax.random.split(key, 8)
        out = dict(
            common(k[4], n),
            q=dense(k[0], (n, H, D, hd)), q_bias=dense(k[5], (n, H, hd)),
            o=dense(k[2], (n, H // 2, 2 * hd, D)), o_bias=dense(k[6], (n, D)),
            subln=jnp.ones((n, 2 * hd), wd),
        )
        out["lambda"] = jax.random.normal(k[3], (n, 4, hd), f32) * LAMBDA_STD
        if own_kv:
            out["kv"] = dense(k[1], (n, KVH // 2, 2, D, 2 * hd))
            out["kv_bias"] = dense(k[7], (n, KVH // 2, 2, 2 * hd))
        return out

    keys = jax.random.split(rng, 7)
    return {
        "embed": {"embedding": dense(keys[0], (V, D))},
        "self_layers": {"mixer": mamba(keys[1], P1), "attn": attention(keys[2], P1)},
        "mid_layers": {"mixer": mamba(keys[3], 1), "attn": attention(keys[4], 1)},
        "cross_layers": {"mixer": gmu(keys[5], P1 - 1),
                         "attn": attention(keys[6], P1 - 1, own_kv=False)},
        "final_norm": jnp.ones((D,), wd), "final_norm_bias": jnp.zeros((D,), wd),
    }


def layer_norm(x, w, b, eps):
    """LayerNorm over the last axis with weight and bias, a float32 island."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    out = xc * lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)
    return out.astype(x.dtype)


def pad_query_pairs(q):
    """[B, T, H, hd] -> [B, T, H, 2hd]: even heads `[q | 0]`, odd `[0 | q]`."""
    B, T, H, hd = q.shape
    q = q.reshape(B, T, H // 2, 2, hd)
    z = jnp.zeros_like(q[..., 0, :])
    return jnp.stack([jnp.concatenate([q[..., 0, :], z], axis=-1),
                      jnp.concatenate([z, q[..., 1, :]], axis=-1)],
                     axis=3).reshape(B, T, H, 2 * hd)


def combine_pairs(out, lam, lam0, subln):
    """The pair combine of differential attention: `out` [B, T, H, 2hd] of
    the padded heads -> RMSNorm(o1 - lam o2) (1 - lam0), [B, T, H/2, 2hd]."""
    B, T, H, w = out.shape
    o = out.astype(jnp.float32).reshape(B, T, H // 2, 2, w)
    d = o[..., 0, :] - lam * o[..., 1, :]
    d = d * lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + SUBLN_EPS)
    return (d * subln.astype(jnp.float32) * (1.0 - lam0)).astype(out.dtype)


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
    """`llama.forward`'s contract for the block with recurrent state layers:
    cache-free (the state starts at zero), over a `KVCache` (scalar or per-row
    index) or over a `PagedKVCache`, each with its `conv` / `ssm` state."""
    from .llama import (
        FLASH_MIN_SEQ, AuxOutput, KVCache, PagedKVCache, _swiglu, init_state,
        lm_head_logits, paged_pool_write, paged_write_indices, qeinsum,
    )

    if dropout_rng is not None:
        raise NotImplementedError(
            "the block with recurrent state layers is served, not trained: "
            "dropout_rng (the training step) is not supported")
    if output_hidden_states or output_attentions:
        raise NotImplementedError(
            "output_hidden_states / output_attentions are not supported by "
            "the block with recurrent state layers")
    B, T = tokens.shape
    adt = config.activation_dtype
    f32 = jnp.float32
    Di, N, R = config.mamba_d_inner, config.mamba_d_state, config.dt_rank
    P1 = config.n_layers // 4
    half = config.n_layers // 2
    eps = config.layer_norm_eps
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
    use_scan_kernel = (T > 1 and ssm.kernel_eligible(T, Di)
                       and not ssm._resolve_interpret())
    window = jnp.int32(config.sliding_window)
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
        plans = {
            windowed: fetch_plan(cache.k, cache.pos, cache.table, q_pos_row, T,
                                 window if windowed else None)
            for windowed in (True, False)
        }
        # The cross layers sweep the full layer's plane once each.
        attn_stats = jnp.stack([
            P1 * plan_live_steps(plans[True]), P1 * plan_live_steps(plans[False]),
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
    if cache is None:
        conv0, ssm0 = init_state(config, B)
    else:
        conv0, ssm0 = cache.conv, cache.ssm
    row_live = lengths > 0

    def attender(k, v, ck, cv, plane, windowed: bool):
        """How queries attend ONE owner's keys: its step's `k`, `v`
        [B, T, KVH/2, 2hd] and, cached, its planes `ck`, `cv` (or the paged
        pool's plane index).  Built once an owner: the cross layers share
        the full layer's."""
        w = window if windowed else None
        if paged:
            return lambda q: paged_decode_attention(
                q, k, v, cache.k, cache.v, cache.pos, cache.table, q_pos_row,
                layer=plane, window=w, plan=plans[windowed])
        if use_flash:
            if ck is None:
                return lambda q: flash_attention(q, k, v, q_positions, new_pos, window=w)
            # Scalar index: the new entries stand at [index, index + T) of
            # the owner's slices for the kernel's one sweep (`afmoe.forward`).
            at = (0, cache.index, 0, 0)
            kf = lax.dynamic_update_slice(ck, k.astype(ck.dtype), at).astype(adt)
            vf = lax.dynamic_update_slice(cv, v.astype(cv.dtype), at).astype(adt)
            return lambda q: flash_attention(q, kf, vf, q_positions, slot_pos, window=w)
        bias_new = attention_bias(q_positions, new_pos, attn_mask, window=w)
        if ck is None:
            return lambda q: sdpa(q, k, v, bias_new, softmax_dtype=softmax_dtype)
        bias = attention_bias(q_positions, cache.pos, cache.pos >= 0, window=w)
        return lambda q: sdpa_cached(
            q, ck.astype(adt), cv.astype(adt), k, v, bias, bias_new,
            softmax_dtype=softmax_dtype)

    def normed(x, lp):
        return layer_norm(x, lp["in_norm"], lp["in_norm_bias"], eps)

    def ffn(x, lp):
        with jax.named_scope("dense.ffn"):
            m = layer_norm(x, lp["post_norm"], lp["post_norm_bias"], eps)
            return x + _swiglu(m, lp["gate_up"], lp["down"])

    def mixer(x, lp, conv_s, ssm_s):
        """One Mamba layer: (x, the scan's output before the gate, the new
        conv state, the new ssm state)."""
        with jax.named_scope("ssm.mix"):
            a = normed(x, lp)
            uz = qeinsum(a, lp["in_proj"], "btd,cde->btce", adt)
            u, z = uz[..., 0, :], uz[..., 1, :]
            seen = jnp.concatenate([conv_s.reshape(B, 3, Di).astype(adt), u], axis=1)
            w = lp["conv_w"].astype(f32)
            c = sum(w[k] * seen[:, k:k + T].astype(f32) for k in range(4))
            c = jax.nn.silu(c + lp["conv_b"].astype(f32)).astype(adt)
            # The last 3 inputs a row has seen: columns lengths .. lengths + 2
            # of [state | chunk]; a row with nothing live keeps its own.
            at = lengths[:, None] + jnp.arange(3, dtype=jnp.int32)[None, :]
            new_conv = jnp.take_along_axis(seen, at[:, :, None], axis=1)
            new_conv = new_conv.reshape(B, 3 * Di).astype(conv_s.dtype)
            xp = qeinsum(c, lp["x_proj"], "bte,er->btr", adt, preferred_element_type=f32)
            r, Bm, Cm = xp[..., :R], xp[..., R:R + N], xp[..., R + N:]
            dt = qeinsum(r.astype(adt), lp["dt_proj"], "btr,re->bte", adt,
                         preferred_element_type=f32)
            dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
            A = -jnp.exp(lp["A_log"].astype(f32)).T                    # [N, Di]
            with jax.named_scope("ssm.scan"):
                if T == 1:
                    y, new_ssm = ssm.ssm_step(
                        ssm_s, c[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A, row_live)
                    y = y[:, None]
                else:
                    y, new_ssm = ssm.ssm_scan(
                        ssm_s, c, dt, Bm, Cm, A, lengths,
                        impl="pallas" if use_scan_kernel else "xla")
            y = y + lp["D"].astype(f32) * c.astype(f32)
            gated = (y * jax.nn.silu(z.astype(f32))).astype(adt)
            out = qeinsum(gated, lp["out_proj"], "bte,ed->btd", adt)
        return ffn(x + out, lp), y.astype(adt), new_conv, new_ssm

    def queries(a, lp):
        q = qeinsum(a, lp["q"], "btd,hdk->bthk", adt, preferred_element_type=f32)
        q = (q + lp["q_bias"].astype(f32)) * math.sqrt(2.0)
        return pad_query_pairs(q.astype(adt))

    def attention(x, lp, li, attend):
        """An attention layer's mixer once its keys' `attend` stands (its
        FFN follows outside the kind's scope, under its own)."""
        a = normed(x, lp)
        lq1, lk1, lq2, lk2 = lp["lambda"].astype(f32)
        lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * li.astype(f32))
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
        o = combine_pairs(attend(queries(a, lp)), lam, lam0, lp["subln"])
        out = qeinsum(o, lp["o"], "btpk,pkd->btd", adt, preferred_element_type=f32)
        return x + (out + lp["o_bias"].astype(f32)).astype(adt)

    def own_keys(x, lp):
        kv = qeinsum(normed(x, lp), lp["kv"], "btd,csdk->btcsk", adt,
                     preferred_element_type=f32)
        kv = (kv + lp["kv_bias"].astype(f32)).astype(adt)
        return kv[..., 0, :], kv[..., 1, :]

    cached = cache is not None and not paged

    def scan(body, x, xs):
        if config.scan_layers:
            return lax.scan(body, x, xs, unroll=config.scan_unroll)
        n = jax.tree_util.tree_leaves(xs)[0].shape[0]
        outs = []
        for i in range(n):
            x, ys = body(x, jax.tree.map(lambda a: a[i], xs))
            outs.append(ys)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)

    def pair(x, xs, windowed: bool, first: int):
        """A (mixer, attention over own keys) pair, layers `first + 2j` and
        `first + 2j + 1`: (x, what the cache keeps of it, the mixer's scan
        output, how its keys are attended)."""
        lp, j, conv_s, ssm_s, *kv = xs
        x, m, new_conv, new_ssm = mixer(x, lp["mixer"], conv_s, ssm_s)
        with jax.named_scope("attn.window" if windowed else "attn.full"):
            k, v = own_keys(x, lp["attn"])
            attend = attender(k, v, *(kv or (None, None)), j + first // 2, windowed)
            x = attention(x, lp["attn"], first + 2 * j + 1, attend)
        return ffn(x, lp["attn"]), (k, v, new_conv, new_ssm), m, attend

    def stacked(lp, lo: int, n: int):
        xs = (lp, jnp.arange(n, dtype=jnp.int32), conv0[lo:lo + n], ssm0[lo:lo + n])
        if cached:
            xs += (cache.k[lo:lo + n], cache.v[lo:lo + n])
        return xs

    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(adt)

    x, (k_s, v_s, conv_s, ssm_s) = scan(
        lambda x, xs: pair(x, xs, True, 0)[:2], x,
        stacked(params["self_layers"], 0, P1))
    # The publishing pair, once: its scan output `m` and its `attend` (over
    # the one K/V the second half reads) are carried, not stored per layer.
    mid = jax.tree.map(lambda a: a[0], stacked(params["mid_layers"], P1, 1))
    x, (k_f, v_f, conv_f, ssm_f), m, attend_full = pair(x, mid, False, half)

    def cross_pair(x, xs):
        lp, j = xs
        with jax.named_scope("gmu.mix"):
            g = lp["mixer"]
            a = normed(x, g)
            gate = jax.nn.silu(qeinsum(a, g["in_proj"], "btd,de->bte", adt))
            x = x + qeinsum(gate * m, g["out_proj"], "bte,ed->btd", adt)
        x = ffn(x, g)
        with jax.named_scope("attn.cross"):
            x = attention(x, lp["attn"], half + 2 * j + 3, attend_full)
        return ffn(x, lp["attn"]), None

    x, _ = scan(cross_pair, x,
                (params["cross_layers"], jnp.arange(P1 - 1, dtype=jnp.int32)))

    new_k = jnp.concatenate([k_s, k_f[None]], axis=0)     # [Lc, B, T, KVH/2, 2hd]
    new_v = jnp.concatenate([v_s, v_f[None]], axis=0)
    new_conv = jnp.concatenate([conv_s, conv_f[None]], axis=0)
    new_ssm = jnp.concatenate([ssm_s, ssm_f[None]], axis=0)
    stats = jnp.concatenate([jnp.zeros((moe.N_STATS,), jnp.int32), attn_stats])

    final_h = layer_norm(x, params["final_norm"], params["final_norm_bias"], eps)
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
