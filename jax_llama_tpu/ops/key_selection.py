"""Learned key selection (DeepSeek-Sparse-Attention's indexer): which keys a
query attends.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        float32
    S_t     = the min(k, live) live keys of largest I[t, .], ties to the
              lower slot (slots of a row stand in position order)

Two forms of one rule, both EXACT (`approx_max_k` is not the model):

* `select_mask`, for a block of queries (a prompt chunk): the k-th largest
  score of each query is found by a search over the scores' float BITS (32
  compare-and-count passes over a monotone uint32 image of the scores, no
  sort: a sort of 2,048 x 32,768 scores a layer a chunk is some 100 ms on a
  v5e, the 32 passes 10 ms), and the selection is the mask `I > kth`, with
  ties at the k-th value taken from the lowest slot up.  The scores live in
  tiles of `K_TILE` keys, and every pass walks the tiles up to the last one
  any query of the block may see: the count is a value, so a chunk early in a
  32,768-slot view pays for its context and not for the view.
* `select_slots`, for decode rows: the same search over each row's candidate
  scores, then the chosen compacted to a list of slots for a gather, by
  running counts at two levels (no sort, no scatter).

A context no longer than k selects every live key, and the mask is the
causal mask.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

K_TILE = 2048    # keys a step of a pass over the scores


def index_scores(
    q_idx: jnp.ndarray,    # [B, T, Hi, di] index queries (rotated)
    w: jnp.ndarray,        # [B, T, Hi] float32 head weights
    k_idx: jnp.ndarray,    # [B, S, di] index keys (normed, rotated)
) -> jnp.ndarray:
    """I [B, T, S] float32.  Positive scale factors (the published
    `n_heads^-1/2`, `d^-1/2`) do not change a top-k and are left out."""
    s = jnp.einsum("bthd,bsd->bths", q_idx, k_idx.astype(q_idx.dtype),
                   preferred_element_type=jnp.float32)
    out = jnp.einsum("bths,bth->bts", jax.nn.relu(s), w.astype(jnp.float32))
    # -0.0 and 0.0 are one score (sixteen clipped heads under weights of
    # either sign give both), and the bit image below would order them.
    return jnp.where(out == 0.0, 0.0, out)


def _sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32, order-preserving; no finite value maps to 0."""
    b = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _topk_mask(u: jnp.ndarray, topk: int, count) -> jnp.ndarray:
    """[..., N] bool: the `topk` largest live entries of the bit images `u`
    (0: not live), equal values from the lowest index up; every live entry
    of a row that has no more than `topk`.  `count(pred, ref)` says how many
    entries of each row satisfy `pred(u, ref[..., None])`."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(jnp.greater_equal, cand) >= topk, cand, t)
    # The largest value that `topk` live scores reach: the k-th largest (0
    # for a row with fewer, which then takes every live entry).
    kth = lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))
    above = count(jnp.greater, kth)
    over = (kth > 0) & (above + count(jnp.equal, kth) > topk)

    def by_rank():
        # Equal scores at the k-th value: the lowest indices first.  Behind a
        # cond because float32 scores all but never tie there and a decode
        # row's ranking is a cumsum a layer: 0.26 ms the search with it
        # skipped, 0.29 ms with it always taken, for eight rows of 32,769
        # candidates on a v5e (a 2048-query chunk reads 19.7 / 19.2 ms:
        # nothing either way; PERF.md section 6, PR 48).
        tie = u == kth[..., None]
        rank = jnp.cumsum(tie, axis=-1, dtype=jnp.int32)
        return (u > kth[..., None]) | (tie & (rank <= (topk - above)[..., None]))

    return lax.cond(jnp.any(over), by_rank, lambda: u >= kth[..., None]) & (u > 0)


def select_mask(
    q_idx: jnp.ndarray,    # [B, T, Hi, di]
    w: jnp.ndarray,        # [B, T, Hi]
    k_idx: jnp.ndarray,    # [B, S, di]
    q_pos: jnp.ndarray,    # [B, T] int32 query positions; -1: no query
    kv_pos: jnp.ndarray,   # [B, S] int32 slot positions; -1: no key
    topk: int,
) -> jnp.ndarray:
    """[B, T, S] bool: query t attends slot s.  A slot is live for a query
    when it holds a position not after the query's; a query with no more
    than `topk` live slots takes them all."""
    B, T = q_pos.shape
    S = kv_pos.shape[1]
    # A step of a pass holds about K_TILE x K_TILE scores whatever the block
    # of queries: few queries take wide steps, or the loop's own overhead
    # (some 20 us a step, 33 sweeps of the tiles a layer) is all there is.
    kt = min(K_TILE * max(1, K_TILE // T), -(-S // K_TILE) * K_TILE) if S > K_TILE else S
    Sp = -(-S // kt) * kt
    if Sp != S:
        k_idx = jnp.pad(k_idx, ((0, 0), (0, Sp - S), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, Sp - S)), constant_values=-1)
    qp = q_pos[:, :, None]

    # Tiles up to the last slot some query of the block may see.
    seen = (kv_pos >= 0) & (kv_pos <= jnp.max(q_pos, axis=1, keepdims=True))
    last = jnp.max(jnp.where(seen, jnp.arange(Sp, dtype=jnp.int32), -1))
    n_tiles = (last + kt) // kt

    def fill(j, u):
        kp = lax.dynamic_slice_in_dim(kv_pos, j * kt, kt, axis=1)[:, None, :]
        keys = lax.dynamic_slice_in_dim(k_idx, j * kt, kt, axis=1)
        live = (kp >= 0) & (kp <= qp)
        bits = jnp.where(live, _sortable(index_scores(q_idx, w, keys)), 0)
        return lax.dynamic_update_slice_in_dim(u, bits, j * kt, axis=2)

    with jax.named_scope("attn.index"):
        # 0: not live (and every tile past `n_tiles`).
        u = lax.fori_loop(0, n_tiles, fill, jnp.zeros((B, T, Sp), jnp.uint32))

    def count(pred, ref):
        def tile(j, acc):
            part = lax.dynamic_slice_in_dim(u, j * kt, kt, axis=2)
            return acc + jnp.sum(pred(part, ref[..., None]), axis=-1, dtype=jnp.int32)
        return lax.fori_loop(0, n_tiles, tile, jnp.zeros((B, T), jnp.int32))

    with jax.named_scope("attn.select"):
        n_live = jnp.sum(seen, axis=1)
        mask = lax.cond(jnp.max(n_live) <= topk, lambda: u > 0,
                        lambda: _topk_mask(u, topk, count))
    return mask[:, :, :S]


_GROUP = 512     # candidates a group of the compaction below


def _compact(mask: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(indices [..., k] int32 of the first k set entries of `mask`
    [..., N] in ascending order, which of the k exist [..., k]): no sort and
    no scatter.  Two levels: the group of `_GROUP` entries that holds the
    j-th set entry by the groups' running counts, then its place inside the
    group by the group's own running count."""
    N = mask.shape[-1]
    g = min(_GROUP, N)
    n = -(-N // g)
    m = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, n * g - N)])
    m = m.reshape(mask.shape[:-1] + (n, g))
    inside = jnp.cumsum(m, axis=-1, dtype=jnp.int32)               # [..., n, g]
    end = jnp.cumsum(inside[..., -1], axis=-1)                     # [..., n]
    j = jnp.arange(k, dtype=jnp.int32)
    grp = jnp.sum(end[..., None, :] <= j[:, None], axis=-1)        # [..., k]
    exists = grp < n
    grp = jnp.minimum(grp, n - 1)
    before = jnp.take_along_axis(end - inside[..., -1], grp, axis=-1)
    rows = jnp.take_along_axis(inside, grp[..., None], axis=-2)    # [..., k, g]
    place = jnp.sum(rows <= (j - before)[..., None], axis=-1)
    return (grp * g + jnp.minimum(place, g - 1)).astype(jnp.int32), exists


def select_slots(
    scores: jnp.ndarray,   # [B, T, N] float32 candidate scores
    live: jnp.ndarray,     # [B, T, N] bool
    topk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(chosen candidates [B, T, k] int32 in ascending order, which of them
    are live [B, T, k]), k = min(topk, N): the live candidates of largest
    score, the lower index among equals.  The rule of `select_mask` by the
    same bit search, then the chosen compacted to a list for a gather: a
    `lax.top_k` of 32,768 candidates is a sort on a TPU, 2.8 ms a layer for
    eight rows where this is a few passes over 1 MB (v5e, PERF.md section 6,
    PR 48)."""
    with jax.named_scope("attn.select"):
        u = jnp.where(live, _sortable(scores), 0)
        count = lambda pred, ref: jnp.sum(  # noqa: E731
            pred(u, ref[..., None]), axis=-1, dtype=jnp.int32)
        return _compact(_topk_mask(u, topk, count), min(topk, scores.shape[-1]))
