"""The learned-sparse-attention block with softmax-routed experts (KeyeVL2's
language model: DeepSeek-Sparse-Attention inside rotary GQA), on the same
`forward` / `init_params` / `init_cache` surface as the dense block of
`llama.py`, which dispatches here when `config.sparse_attention`.

    x0 = E[tokens]
    per layer (every layer is this one; no dense layer, no shared expert):
      a = RMSNorm(x)
      q = RMSNorm_h(a Wq) [H, hd]   k = RMSNorm_h(a Wk) [KVH, hd]   v = a Wv
      q, k = rope(q, k)
      indexer:  qI = a WqI [Hi, di]   kI = LayerNorm(a WkI) [di]   w = a Ww [Hi]
                rope on the leading half of qI and kI
                I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])      s <= t
                S_t = the min(topk, t + 1) keys of largest I[t, .], ties to
                      the lower position                 (`ops/key_selection.py`)
      o_t = softmax_{s in S_t}(q_t . k_s / sqrt(hd)) v_s        GQA
      x = x + o Wo
      m = RMSNorm(x)
      x = x + ops.moe.routed_experts(m)     softmax scores, top-k, renormalised
    logits = RMSNorm_final(x) W_head

The cache has THREE planes a layer, all under one block table: K and V, a
token's KV heads side by side in ONE row ([L, 1, NB, BLK, KVH * hd] in the
pool: a decode row gathers chosen slots, and a gather costs by the row), and
`idx`, the index keys ([L, 1, NB, BLK, di], one head).  A cached prefix block
brings its index keys, and a re-ask never recomputes them.  Keys are cached as
attended and as ranked: normed, rotated.

How the new tokens attend:

* a prompt chunk (more than `FLASH_MIN_SEQ` tokens, flash allowed): the
  selection is a MASK operand of the flash kernel (`flash_attention(mask=)`),
  made `Q_TILE` queries at a time from the index scores of every live key
  (`key_selection.select_mask`); a chunk of more queries is walked in tiles
  (`lax.map`), so a 32,768-token whole-prompt insert holds one tile's scores.
* decode rows over the paged pool: each row ranks its own LIVE blocks' index
  keys in two Pallas kernels a layer (`key_selection.paged_select_slots`: the
  one-head plane read in place by the block table and scored on the MXU;
  then the k-th-value search over all rows' scores, the mask and the list of
  the chosen slots, all in VMEM: an exact top-k that leaves the chip as a
  list), and attends the chosen slots gathered from the pool by XLA
  (`ops.paged_attention.paged_sparse_attention`: no kernel there yet).
  Several tokens a row over the pool, which no cell dispatches, keep the XLA
  ranking over the row's whole table (`paged_rows`, `select_slots`).
* everything else (cache-free, decode-sized steps over a `KVCache`): the
  mask as an additive bias of the XLA attention.

A context of at most `topk` keys selects all of them, and every form is then
dense causal attention.

Parameters are one stacked tree, scanned:

    {"embed": {"embedding": [V, D]},
     "moe_layers": {"attn_norm", "mlp_norm" [L,D],
                    "qkv" [L,KVH,G+2,D,hd] (slots q_0..q_{G-1}, k, v a KV head),
                    "q_norm", "k_norm" [L,hd], "o" [L,H,hd,D],
                    "index_q" [L,D,Hi,di], "index_k" [L,D,di], "index_w" [L,D,Hi],
                    "index_k_norm", "index_k_bias" [L,di],
                    "router" [L,D,E],
                    "experts_gate_up" [L,E,D,2Fe], "experts_down" [L,E,Fe,D]},
     "final_norm": [D], "lm_head": [D, V]}

Every call counts into the cache's `stats` (`N_STATS` int32): the routing
counts of `ops.moe.STATS`, the window block's two step counts (zero here: the
layout of the fetch's tail is shared), then `SELECT_STATS` of the paged decode
rows, summed over rows and layers (the scoring kernel's live grid steps and
the steps the rows' whole tables would take; last the rows whose k-th value
was shared by more candidates than it had room for, the one count the layers
make themselves).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import LLaMAConfig
from ..ops.attention import NEG_INF, sdpa
from ..ops.flash_attention import flash_attention
from ..ops.key_selection import (
    index_plan, index_scores, paged_select_slots, plan_row_steps, select_mask,
    select_slots,
)
from ..ops.norm import layer_norm, rms_norm
from ..ops.rope import apply_rope_rows, rope_rows
from . import afmoe
from .mla_moe import INIT_STD, routed_ffn

Params = Dict[str, Any]

# What a call counts of its paged decode rows' selection, after the window
# block's counters: slots attended, live slots they were chosen from, and
# rows whose context was no longer than `topk` (which took all of it); then
# the grid steps the ranking kernel ran and those a full table would take;
# last the rows whose k-th value was shared by more candidates than it had
# room for (the list kernel's ties by slot order decided something).
SELECT_STATS = ("selected_slots", "candidate_slots", "select_dense_rows",
                "index_steps_run", "index_steps_table", "select_tie_rows")
N_STATS = afmoe.N_STATS + len(SELECT_STATS)

# Queries a selection pass: one mask tile of the flash kernel's q block.
Q_TILE = 2048


def init_params(rng: jax.Array, config: LLaMAConfig) -> Params:
    """Seeded weights, N(0, INIT_STD^2) as the other expert blocks' (see
    `mla_moe.INIT_STD`); every norm at weight one, the index key's LayerNorm
    at bias zero.  Unit q / k norms make seeded attention logits about
    N(0, 1), and WHICH keys are attended shows in the logits all the same:
    the reference's two controls fail (benchmark/references/dsa_moe.py has
    the readings, and those of norms at 1.25 and 1.5, where a bfloat16
    system's own deficits grow faster than the controls')."""
    config.validate()
    D, H, KVH, hd, V = (config.dim, config.n_heads, config.kv_heads,
                        config.head_dim, config.vocab_size)
    G = H // KVH
    Hi, di = config.index_n_heads, config.index_head_dim
    E, Fe, L = config.n_routed_experts, config.moe_intermediate_size, config.n_layers
    wd = config.weight_dtype

    def dense(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * INIT_STD).astype(wd)

    keys = jax.random.split(rng, 10)
    return {
        "embed": {"embedding": dense(keys[0], (V, D))},
        "moe_layers": {
            "attn_norm": jnp.ones((L, D), wd), "mlp_norm": jnp.ones((L, D), wd),
            "qkv": dense(keys[1], (L, KVH, G + 2, D, hd)),
            "q_norm": jnp.ones((L, hd), wd), "k_norm": jnp.ones((L, hd), wd),
            "o": dense(keys[2], (L, H, hd, D)),
            "index_q": dense(keys[3], (L, D, Hi, di)),
            "index_k": dense(keys[4], (L, D, di)),
            "index_w": dense(keys[5], (L, D, Hi)),
            "index_k_norm": jnp.ones((L, di), wd),
            "index_k_bias": jnp.zeros((L, di), wd),
            "router": dense(keys[6], (L, D, E)),
            "experts_gate_up": dense(keys[7], (L, E, D, 2 * Fe)),
            "experts_down": dense(keys[8], (L, E, Fe, D)),
        },
        "final_norm": jnp.ones((D,), wd),
        "lm_head": dense(keys[9], (D, V)),
    }


def _rope_half(x, cos, sin):
    """Rotate the leading half of the last axis of x [B, T, heads, d]."""
    half = x.shape[-1] // 2
    return jnp.concatenate(
        [apply_rope_rows(x[..., :half], cos, sin), x[..., half:]], axis=-1)


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
    """`llama.forward`'s contract for the sparse-attention block: cache-free,
    over a `KVCache` (scalar or per-row index) or over a `PagedKVCache`."""
    from ..ops.paged_attention import (
        paged_rows, paged_slot_positions, paged_sparse_attention,
        plan_live_steps,
    )
    from .llama import (
        FLASH_MIN_SEQ, AuxOutput, KVCache, PagedKVCache, embed_tokens,
        layer_scan, lm_head_logits, paged_pool_write, paged_write_indices,
        qeinsum,
    )

    if dropout_rng is not None:
        raise NotImplementedError(
            "the sparse-attention block is served, not trained: dropout_rng "
            "(the training step) is not supported")
    if output_hidden_states or output_attentions:
        raise NotImplementedError(
            "output_hidden_states / output_attentions are not supported by "
            "the sparse-attention block")
    B, T = tokens.shape
    adt = config.activation_dtype
    H, KVH, hd = config.n_heads, config.kv_heads, config.head_dim
    G = H // KVH
    di, topk = config.index_head_dim, config.index_topk
    eps = config.rms_norm_eps
    softmax_dtype = jnp.dtype(config.attn_softmax_dtype)
    paged = isinstance(cache, PagedKVCache)
    if attn_mask is None:
        attn_mask = positions >= 0
    q_positions = jnp.maximum(positions, 0)
    new_pos = jnp.where(attn_mask, q_positions, -1).astype(jnp.int32)

    use_flash = (not paged and T > FLASH_MIN_SEQ
                 and config.attn_impl in ("flash", "auto")
                 and not (cache is not None and cache.per_row_index))
    # All of `SELECT_STATS` but the last follow from shapes and positions;
    # the ties are the layers' own count.
    select_stats = jnp.zeros((len(SELECT_STATS) - 1,), jnp.int32)
    if paged:
        NB, BLK = cache.pos.shape
        # A row is active or not as a whole (see `llama.paged_forward`).
        row_active = attn_mask[:, 0] & jnp.all(attn_mask == attn_mask[:, :1], axis=1)
        valid = jnp.broadcast_to(row_active[:, None], (B, T))
        q_pos = jnp.where(valid, positions, -1).astype(jnp.int32)
        # Each row's candidates: the slots of its table in sequence order,
        # then the step's own tokens; live where not after the query.
        slot_pos = paged_slot_positions(cache.pos, cache.table)
        cand_pos = jnp.concatenate([slot_pos, q_pos], axis=1)       # [B, S + T]
        cand_live = (cand_pos[:, None, :] >= 0) & (cand_pos[:, None, :] <= q_pos[:, :, None])
        n_live = jnp.sum(cand_live, axis=-1)                         # [B, T]
        index_steps = (0, 0)
        if T == 1:
            # One token a row (what the serving programs dispatch) ranks by
            # the kernels; their step list does not depend on the layer.
            rank_plan = index_plan(cache.pos, cache.table, q_pos[:, 0])
            index_steps = (plan_live_steps(rank_plan),
                           jnp.sum(row_active) * plan_row_steps(rank_plan, B))
        select_stats = config.n_layers * jnp.stack([
            jnp.sum(jnp.minimum(n_live, topk)), jnp.sum(n_live),
            jnp.sum(valid & (n_live <= topk)), *index_steps,
        ]).astype(jnp.int32)
    else:
        valid = attn_mask
        if cache is not None:
            rows = jnp.arange(B, dtype=jnp.int32)[:, None]
            cols = (cache.index[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
                    if cache.per_row_index else None)
            slot_pos = (
                cache.pos.at[rows, cols].set(new_pos, mode="drop")
                if cache.per_row_index
                else lax.dynamic_update_slice(cache.pos, new_pos, (0, cache.index))
            )
        else:
            slot_pos = new_pos
    cos, sin = rope_rows(q_positions, hd, config.rope_theta)
    cos_i, sin_i = rope_rows(q_positions, di // 2, config.rope_theta)

    x = embed_tokens(params, tokens).astype(adt)

    def with_new(old, new):
        """A layer's cache slice [B, S, ...] (one head: [B, S, width]) with
        this call's entries [B, T, ...] in, in the entries' own shape."""
        if old is None:
            return new
        old = old.reshape(old.shape[:2] + new.shape[2:])
        new = new.astype(old.dtype)
        if cache.per_row_index:
            return old.at[rows, cols].set(new, mode="drop")
        return lax.dynamic_update_slice(
            old, new, (0, cache.index) + (0,) * (old.ndim - 2))

    def attend_masked(q, q_idx, w, k_all, v_all, i_all):
        """Dense attention under the selection's mask, [B, Tq, H, hd], for
        any number of queries: `Q_TILE` at a time."""
        def tile(q, q_idx, w, qp):
            mask = select_mask(q_idx, w, i_all, qp, slot_pos, topk)
            with jax.named_scope("attn.sparse"):
                if use_flash:
                    return flash_attention(
                        q, k_all.astype(adt), v_all.astype(adt),
                        jnp.maximum(qp, 0), slot_pos, mask=mask.astype(jnp.int8))
                bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)[:, None]
                return sdpa(q, k_all.astype(adt), v_all.astype(adt), bias,
                            softmax_dtype=softmax_dtype)

        if T <= Q_TILE:
            return tile(q, q_idx, w, new_pos)
        n = -(-T // Q_TILE)

        def tiles(a, fill=0):  # [B, T, ...] -> [n, B, Q_TILE, ...]
            a = jnp.pad(a, ((0, 0), (0, n * Q_TILE - T)) + ((0, 0),) * (a.ndim - 2),
                        constant_values=fill)
            return jnp.moveaxis(a.reshape((B, n, Q_TILE) + a.shape[2:]), 1, 0)

        out = lax.map(lambda xs: tile(*xs),
                      (tiles(q), tiles(q_idx), tiles(w), tiles(new_pos, -1)))
        return jnp.moveaxis(out, 0, 1).reshape((B, n * Q_TILE) + out.shape[3:])[:, :T]

    def attention(x, lp, ck, cv, ci, li):
        a = rms_norm(x, lp["attn_norm"], eps)
        with jax.named_scope("attn.proj"):
            qkv = qeinsum(a, lp["qkv"], "btd,cgdk->btcgk", adt)
            q = qkv[..., :G, :].reshape(B, T, H, hd)
            k, v = qkv[..., G, :], qkv[..., G + 1, :]
            q = apply_rope_rows(rms_norm(q, lp["q_norm"], eps), cos, sin)
            k = apply_rope_rows(rms_norm(k, lp["k_norm"], eps), cos, sin)
        with jax.named_scope("attn.index"):
            q_idx = _rope_half(
                qeinsum(a, lp["index_q"], "btd,dhk->bthk", adt), cos_i, sin_i)
            k_idx = layer_norm(
                qeinsum(a, lp["index_k"], "btd,dk->btk", adt),
                lp["index_k_norm"], lp["index_k_bias"], eps)
            k_idx = _rope_half(k_idx[:, :, None, :], cos_i, sin_i)[:, :, 0]
            w = qeinsum(a, lp["index_w"], "btd,dh->bth", adt).astype(jnp.float32)
        ties = jnp.int32(0)
        if paged:
            if T == 1:
                chosen, chosen_live, tied = paged_select_slots(
                    q_idx, w, k_idx, cache.idx, cache.table, rank_plan,
                    q_pos[:, 0], li, topk)
                ties = jnp.sum(tied, dtype=jnp.int32)
            else:
                with jax.named_scope("attn.index"):
                    keys = jnp.concatenate(
                        [paged_rows(cache.idx, cache.table, li).astype(adt), k_idx],
                        axis=1)
                    scores = index_scores(q_idx, w, keys)
                chosen, chosen_live = select_slots(scores, cand_live, topk)
            with jax.named_scope("attn.sparse"):
                out = paged_sparse_attention(
                    q, k, v, chosen, chosen_live, cache.k, cache.v,
                    cache.table, li)
        else:
            out = attend_masked(
                q, q_idx, w, with_new(ck, k), with_new(cv, v), with_new(ci, k_idx))
        with jax.named_scope("attn.proj"):
            out = qeinsum(out, lp["o"], "bthk,hkd->btd", adt)
        return out, (k, v, k_idx), ties

    # The experts stay out of the scanned tree (see mla_moe.forward).
    scanned = dict(params["moe_layers"])
    experts = (scanned.pop("experts_gate_up"), scanned.pop("experts_down"))
    cached = cache is not None and not paged
    L = config.n_layers
    xs = (scanned, jnp.arange(L, dtype=jnp.int32))
    if cached:  # read-only through the scan: one write after it
        xs += (cache.k, cache.v, cache.idx)

    def body(x, xs):
        lp, li, *planes = xs
        out, kept, ties = attention(x, lp, *(planes or (None, None, None)), li)
        x = x + out
        f, stats = routed_ffn(rms_norm(x, lp["mlp_norm"], eps), lp, experts, li,
                            valid, config)
        return x + f, (kept, stats, ties)

    if config.scan_layers:
        x, ((new_k, new_v, new_i), stats, ties) = layer_scan(
            body, x, xs, unroll=config.scan_unroll)
    else:
        outs = []
        for i in range(L):
            x, ys = body(x, jax.tree.map(lambda a: a[i], xs))
            outs.append(ys)
        (new_k, new_v, new_i), stats, ties = jax.tree.map(lambda *a: jnp.stack(a), *outs)
    stats = jnp.concatenate([
        jnp.sum(stats, axis=0),
        jnp.zeros((len(afmoe.ATTN_STATS),), jnp.int32), select_stats,
        jnp.sum(ties, keepdims=True)])
    # One head a plane: [L, B, T, 1, width].
    new_k, new_v, new_i = (
        a.reshape(a.shape[:3] + (1, -1)) for a in (new_k, new_v, new_i))

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
            **{name: paged_pool_write(
                getattr(cache, name), jnp.moveaxis(new, 3, 1), blk, off)
               for name, new in (("k", new_k), ("v", new_v), ("idx", new_i))},
            pos=paged_pool_write(cache.pos, q_pos, blk, off),
            stats=total,
        )
    else:
        planes = {}
        for name, new in (("k", new_k), ("v", new_v), ("idx", new_i)):
            old = getattr(cache, name)
            new = new.astype(old.dtype)
            planes[name] = (
                old.at[:, rows, cols].set(new, mode="drop")
                if cache.per_row_index
                else lax.dynamic_update_slice(old, new, (0, 0, cache.index, 0, 0)))
        new_cache = KVCache(
            **planes, pos=slot_pos, index=cache.index + T, stats=total)
    return (logits, new_cache, aux) if aux is not None else (logits, new_cache)
