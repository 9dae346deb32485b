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
from ..ops.norm import layer_norm
from .afmoe import ATTN_STATS
from .falcon_h1 import _conv  # the causal depthwise conv behind a row's held inputs
from .llama import _swiglu, embed_tokens, layer_scan, qeinsum  # `llama` reaches this module inside its functions only
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


def _normed(x, lp, config):
    return layer_norm(x, lp["in_norm"], lp["in_norm_bias"], config.layer_norm_eps)


def _ffn(x, lp, config):
    with jax.named_scope("dense.ffn"):
        m = layer_norm(x, lp["post_norm"], lp["post_norm_bias"], config.layer_norm_eps)
        return x + _swiglu(m, lp["gate_up"], lp["down"])


def _recur(h, c, dt, Bm, Cm, A, lengths, live, use_kernel: bool):
    """The recurrence of `c` [R, T, Di] from each row's state `h` [R, N, Di]:
    (y [R, T, Di] float32, the state after each row's `lengths` live tokens;
    `live` is `lengths > 0`).  One token a row is a step, more are a scan
    (the Pallas one with `use_kernel`)."""
    if c.shape[1] == 1:
        y, h = ssm.ssm_step(h, c[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A, live)
        return y[:, None], h
    return ssm.ssm_scan(h, c, dt, Bm, Cm, A, lengths,
                        impl="pallas" if use_kernel else "xla")


def _mixer(x, lp, config, convolve, recur):
    """One Mamba layer over `x` [B, T, D] around the two things that are a
    row's own, which the caller hands in: `convolve(u)` -> (the conv's
    output [B, T, Di], the new conv state) and `recur(c, dt, Bm, Cm, A)` ->
    (y [B, T, Di] float32, the new ssm state).  Returns (x, the scan's
    output before the gate, the new conv state, the new ssm state)."""
    adt = config.activation_dtype
    f32 = jnp.float32
    N, R = config.mamba_d_state, config.dt_rank
    with jax.named_scope("ssm.mix"):
        a = _normed(x, lp, config)
        uz = qeinsum(a, lp["in_proj"], "btd,cde->btce", adt)
        u, z = uz[..., 0, :], uz[..., 1, :]
        c, new_conv = convolve(u)
        xp = qeinsum(c, lp["x_proj"], "bte,er->btr", adt, preferred_element_type=f32)
        r, Bm, Cm = xp[..., :R], xp[..., R:R + N], xp[..., R + N:]
        dt = qeinsum(r.astype(adt), lp["dt_proj"], "btr,re->bte", adt,
                     preferred_element_type=f32)
        dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["A_log"].astype(f32)).T                    # [N, Di]
        with jax.named_scope("ssm.scan"):
            y, new_ssm = recur(c, dt, Bm, Cm, A)
        y = y + lp["D"].astype(f32) * c.astype(f32)
        gated = (y * jax.nn.silu(z.astype(f32))).astype(adt)
        out = qeinsum(gated, lp["out_proj"], "bte,ed->btd", adt)
    return _ffn(x + out, lp, config), y.astype(adt), new_conv, new_ssm


def _own_keys(x, lp, config):
    adt = config.activation_dtype
    f32 = jnp.float32
    kv = qeinsum(_normed(x, lp, config), lp["kv"], "btd,csdk->btcsk", adt,
                 preferred_element_type=f32)
    kv = (kv + lp["kv_bias"].astype(f32)).astype(adt)
    return kv[..., 0, :], kv[..., 1, :]


def _attention(x, lp, li, attend, config):
    """An attention layer's mixer once its keys' `attend` stands (its FFN
    follows outside the kind's scope, under its own)."""
    adt = config.activation_dtype
    f32 = jnp.float32
    a = _normed(x, lp, config)
    lq1, lk1, lq2, lk2 = lp["lambda"].astype(f32)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * li.astype(f32))
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    q = qeinsum(a, lp["q"], "btd,hdk->bthk", adt, preferred_element_type=f32)
    q = (q + lp["q_bias"].astype(f32)) * math.sqrt(2.0)
    o = combine_pairs(attend(pad_query_pairs(q.astype(adt))), lam, lam0, lp["subln"])
    out = qeinsum(o, lp["o"], "btpk,pkd->btd", adt, preferred_element_type=f32)
    return x + (out + lp["o_bias"].astype(f32)).astype(adt)


def _attend_rows(config, cache, q_positions, new_pos, attn_mask, slot_pos,
                 use_flash: bool):
    """`attender(k, v, ck, cv, windowed)` of one call: how its queries
    attend ONE owner's keys — the step's `k`, `v` [B, T, KVH/2, 2hd] and,
    with `ck` / `cv` (the owner's slices of the `KVCache` `cache`), what
    the cache holds — through the flash kernel's one sweep, or the
    append-free xla form."""
    adt = config.activation_dtype
    softmax_dtype = jnp.dtype(config.attn_softmax_dtype)
    window = jnp.int32(config.sliding_window)

    def attender(k, v, ck, cv, windowed: bool):
        w = window if windowed else None
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

    return attender


def _attend_paged(config, cache, q_pos_row, T: int):
    """(`attender(k, v, plane, windowed)` of one call over the `PagedKVCache`
    `cache` — the paged kernel over the pool's plane index `plane`, its two
    fetch plans (window, full) bound here, outside the scans —, the call's
    `ATTN_STATS`: the cross layers sweep the full layer's plane once each)."""
    from ..ops.paged_attention import (
        fetch_plan, paged_decode_attention, plan_live_steps,
    )

    P1 = config.n_layers // 4
    window = jnp.int32(config.sliding_window)
    plans = {
        windowed: fetch_plan(cache.k, cache.pos, cache.table, q_pos_row, T,
                             window if windowed else None)
        for windowed in (True, False)
    }
    attn_stats = jnp.stack([
        P1 * plan_live_steps(plans[True]), P1 * plan_live_steps(plans[False]),
    ]).astype(jnp.int32)

    def attender(k, v, plane, windowed: bool):
        return lambda q: paged_decode_attention(
            q, k, v, cache.k, cache.v, cache.pos, cache.table, q_pos_row,
            layer=plane, window=window if windowed else None,
            plan=plans[windowed])

    return attender, attn_stats


def _layers(params, x, config, state, planes, mixer, attender):
    """The stack over `x` [B, T, D] as three scans of PAIRS: the (mixer,
    window attention) pairs, the publishing pair once, the (memory unit,
    cross attention) pairs.  `state` is a tuple of arrays [Ls, ...] and
    `planes` one of arrays [Lc, ...] (or empty), each handed on a layer at a
    time: `mixer(x, lp, *state)` -> (x, the scan's output before the gate,
    the new state, a tuple like `state`) and `attender(k, v, *planes (or
    None, None), plane index, windowed)` -> (how queries attend that owner's
    keys, what the caller keeps of `k`, `v`: a tuple).  Returns (x, what was
    kept [Lc, ...], the new state [Ls, ...])."""
    adt = config.activation_dtype
    P1 = config.n_layers // 4
    half = config.n_layers // 2

    def scan(body, x, xs):
        if config.scan_layers:
            return layer_scan(body, x, xs, unroll=config.scan_unroll)
        n = jax.tree_util.tree_leaves(xs)[0].shape[0]
        outs = []
        for i in range(n):
            x, ys = body(x, jax.tree.map(lambda a: a[i], xs))
            outs.append(ys)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)

    def pair(x, xs, windowed: bool, first: int):
        """A (mixer, attention over own keys) pair, layers `first + 2j` and
        `first + 2j + 1`: (x, (what is kept of its keys, its new state), the
        mixer's scan output, how its keys are attended)."""
        lp, j, state_l, planes_l = xs
        x, m, new_state = mixer(x, lp["mixer"], *state_l)
        with jax.named_scope("attn.window" if windowed else "attn.full"):
            k, v = _own_keys(x, lp["attn"], config)
            attend, kept = attender(
                k, v, *(planes_l or (None, None)), j + first // 2, windowed)
            x = _attention(x, lp["attn"], first + 2 * j + 1, attend, config)
        return _ffn(x, lp["attn"], config), (kept, new_state), m, attend

    def stacked(lp, lo: int, n: int):
        return (lp, jnp.arange(n, dtype=jnp.int32),
                tuple(a[lo:lo + n] for a in state),
                tuple(a[lo:lo + n] for a in planes))

    x, (kept_s, state_s) = scan(
        lambda x, xs: pair(x, xs, True, 0)[:2], x,
        stacked(params["self_layers"], 0, P1))
    # The publishing pair, once: its scan output `m` and its `attend` (over
    # the one K/V the second half reads) are carried, not stored per layer.
    mid = jax.tree.map(lambda a: a[0], stacked(params["mid_layers"], P1, 1))
    x, (kept_f, state_f), m, attend_full = pair(x, mid, False, half)

    def cross_pair(x, xs):
        lp, j = xs
        with jax.named_scope("gmu.mix"):
            g = lp["mixer"]
            a = _normed(x, g, config)
            gate = jax.nn.silu(qeinsum(a, g["in_proj"], "btd,de->bte", adt))
            x = x + qeinsum(gate * m, g["out_proj"], "bte,ed->btd", adt)
        x = _ffn(x, g, config)
        with jax.named_scope("attn.cross"):
            x = _attention(x, lp["attn"], half + 2 * j + 3, attend_full, config)
        return _ffn(x, lp["attn"], config), None

    x, _ = scan(cross_pair, x,
                (params["cross_layers"], jnp.arange(P1 - 1, dtype=jnp.int32)))
    join = lambda s, f: jnp.concatenate([s, f[None]], axis=0)  # noqa: E731
    return (x, jax.tree.map(join, kept_s, kept_f),
            jax.tree.map(join, state_s, state_f))


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
        FLASH_MIN_SEQ, AuxOutput, KVCache, PagedKVCache, init_state,
        lm_head_logits, paged_pool_write, paged_write_indices,
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
    eps = config.layer_norm_eps
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
    use_scan_kernel = (T > 1 and ssm.kernel_eligible(T, config.mamba_d_inner)
                       and not ssm._resolve_interpret())
    attn_stats = jnp.zeros((len(ATTN_STATS),), jnp.int32)
    if paged:
        NB, BLK = cache.pos.shape
        row_active = attn_mask[:, 0]
        if T > 1:  # the kernel's T > 1 contract (see `llama.paged_forward`)
            row_active = (
                row_active & jnp.all(attn_mask == attn_mask[:, :1], axis=1)
                & jnp.all(positions == positions[:, :1]
                          + jnp.arange(T, dtype=positions.dtype), axis=1))
        q_pos_row = jnp.where(row_active, positions[:, 0], -1).astype(jnp.int32)
        lengths = jnp.where(row_active, T, 0).astype(jnp.int32)
        attend_paged, attn_stats = _attend_paged(config, cache, q_pos_row, T)
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
    if not paged:
        attend_rows = _attend_rows(
            config, cache, q_positions, new_pos, attn_mask, slot_pos, use_flash)

    def mixer(x, lp, conv_s, ssm_s):
        x, m, new_conv, new_ssm = _mixer(
            x, lp, config,
            lambda u: _conv(u, conv_s, lengths, lp),
            lambda *operands: _recur(
                ssm_s, *operands, lengths, row_live, use_scan_kernel))
        return x, m, (new_conv, new_ssm)

    def attender(k, v, ck, cv, plane, windowed: bool):
        """Built once an owner: the cross layers share the full layer's."""
        if paged:
            return attend_paged(k, v, plane, windowed), (k, v)
        return attend_rows(k, v, ck, cv, windowed), (k, v)

    cached = cache is not None and not paged
    x = embed_tokens(params, tokens).astype(adt)
    x, (new_k, new_v), (new_conv, new_ssm) = _layers(
        params, x, config, (conv0, ssm0),
        (cache.k, cache.v) if cached else (), mixer, attender)
    # new_k, new_v [Lc, B, T, KVH/2, 2hd]
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
    """`llama.mixed_forward`'s contract for the block with recurrent state
    layers: the chunk's [1, C] `tokens` over the one-row `cache` (a
    `KVCache` with a scalar index and the row's `conv` / `ssm`) and one
    token a decode row (`rider_tokens` [B] at `rider_positions`, -1 for a
    row that rides masked) over `pool` (a `PagedKVCache` with every slot's
    state) go through the embedding and all three scans of `_layers` as ONE
    [1, C + B, D] activation.  It splits where a quantity is a row's own.
    A mixer's recurrence: behind the shared `in_proj` the chunk's columns
    take the conv and `ssm_scan` over the cache's state, the riders' the
    conv and `ssm_step` over the pool's; `x_proj`, `dt_proj`, the gate and
    `out_proj` see every column, and the publishing mixer's scan output
    reaches the memory units as one [1, C + B, Di] tensor.  Attention, once
    an owner: the chunk's queries attend over the cache's slices as
    `forward` does (flash or xla by C, window or full), the riders' through
    the paged kernel, its plans bound outside the scans; the cross layers
    reuse the full layer's pair.  The riders' keys land once a plane after
    the scans.  A masked rider leaves its slot's state bit for bit.

    Returns (post-final-norm hidden states [1, C + B, D] — the chunk's rows,
    then the riders' —, the updated `cache`, the updated `pool`); the call's
    counts are added to the `stats` of both (a caller that folds one cache
    into the other keeps one).  The head is the caller's."""
    from .llama import FLASH_MIN_SEQ, _paged_land

    if cache.per_row_index:
        raise NotImplementedError("mixed_forward: a cache with a scalar index")
    C = tokens.shape[1]
    q_positions = jnp.maximum(positions, 0)
    new_pos = jnp.where(attn_mask, q_positions, -1).astype(jnp.int32)
    lengths = jnp.sum(attn_mask.astype(jnp.int32), axis=1)
    chunk_live = lengths > 0
    slot_pos = lax.dynamic_update_slice(cache.pos, new_pos, (0, cache.index))
    use_flash = C > FLASH_MIN_SEQ and config.attn_impl in ("flash", "auto")
    use_scan_kernel = (C > 1 and ssm.kernel_eligible(C, config.mamba_d_inner)
                       and not ssm._resolve_interpret())
    rider_qpos = rider_positions.astype(jnp.int32)
    riding = rider_qpos >= 0
    rider_lengths = riding.astype(jnp.int32)
    attend_rows = _attend_rows(
        config, cache, q_positions, new_pos, attn_mask, slot_pos, use_flash)
    attend_paged, attn_stats = _attend_paged(config, pool, rider_qpos, 1)

    def riders(a):  # [1, C + B, ...] -> the riders' columns as rows, [B, 1, ...]
        return jnp.swapaxes(a[:, C:], 0, 1)

    def rejoin(chunk, rode):  # [1, C, ...] and [B, 1, ...] -> [1, C + B, ...]
        return jnp.concatenate([chunk, jnp.swapaxes(rode, 0, 1)], axis=1)

    def mixer(x, lp, conv_c, ssm_c, conv_r, ssm_r):
        def convolve(u):
            c_c, held_c = _conv(u[:, :C], conv_c, lengths, lp)
            c_r, held_r = _conv(riders(u), conv_r, rider_lengths, lp)
            return rejoin(c_c, c_r), (held_c, held_r)

        def recur(c, dt, Bm, Cm, A):
            cols = (c, dt, Bm, Cm)
            y_c, h_c = _recur(ssm_c, *(a[:, :C] for a in cols), A, lengths,
                              chunk_live, use_scan_kernel)
            y_r, h_r = _recur(ssm_r, *(riders(a) for a in cols), A,
                              rider_lengths, riding, False)
            return rejoin(y_c, y_r), (h_c, h_r)

        x, m, (held_c, held_r), (h_c, h_r) = _mixer(x, lp, config, convolve, recur)
        return x, m, (held_c, h_c, held_r, h_r)

    def attender(k, v, ck, cv, plane, windowed: bool):
        """Built once an owner, for both halves."""
        kept = (k[:, :C], v[:, :C], riders(k), riders(v))
        chunk = attend_rows(*kept[:2], ck, cv, windowed)
        rode = attend_paged(*kept[2:], plane, windowed)
        return lambda q: rejoin(chunk(q[:, :C]), rode(riders(q))), kept

    x = embed_tokens(
        params, jnp.concatenate([tokens, rider_tokens[None]], axis=1),
    ).astype(config.activation_dtype)
    x, (new_k, new_v, rider_k, rider_v), (conv_c, ssm_c, conv_r, ssm_r) = _layers(
        params, x, config, (cache.conv, cache.ssm, pool.conv, pool.ssm),
        (cache.k, cache.v), mixer, attender)
    stats = jnp.concatenate([jnp.zeros((moe.N_STATS,), jnp.int32), attn_stats])
    counted = lambda c: stats if c.stats is None else c.stats + stats  # noqa: E731
    at = (0, 0, cache.index, 0, 0)
    return (
        layer_norm(x, params["final_norm"], params["final_norm_bias"],
                   config.layer_norm_eps),
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
