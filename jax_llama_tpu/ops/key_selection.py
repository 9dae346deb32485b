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
* `paged_select_slots`, for decode rows over the paged pool: two Pallas
  kernels a layer.  `paged_index_scores` reads the index-key plane in place
  by the block table, live blocks only (`paged_attention._fetch_plan`'s step
  list, several blocks a grid step), and scores them on the MXU;
  `paged_index_select` holds every row's bit images in VMEM, runs the same
  search there, and on the images it just read makes the mask from the k-th
  value (ties by the slots' order) and compacts the chosen to a list of slots
  for a gather: running counts as products with 0/1 triangles on the MXU, a
  place's slot by compare-and-count, no sort and no scatter (`_list_row`).
  The list leaves the chip's VMEM as a list: as XLA stages over HBM the mask
  and the compaction cost 0.30 ms a layer for eight rows of 32,768 slots, a
  33.5 MB intermediate among them; in the kernel 0.017 (v5e, PERF.md
  section 6, PR 50; the scores and the search: PR 49).
* `select_slots`: the decode rows' rule over candidate scores that are
  already an array, in XLA (`_mask_of`, `_compact`): several tokens a row
  over the pool, which no cell dispatches, and the tests' reference for the
  kernels.

A context no longer than k selects every live key, and the mask is the
causal mask.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _SUBLANES, _resolve_interpret
from .paged_attention import _FIRST, _LIVE, _fetch_plan

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


def _kth_value(u: jnp.ndarray, topk: int, count):
    """(kth, above, over) of the bit images `u` [..., N] (0: not live): the
    largest value that `topk` live entries of a row reach (0 for a row with
    fewer, which then takes every live entry), how many entries lie above it,
    and whether the row has more entries at it than `topk` leaves room for.
    `count(pred, ref)` says how many entries of each row satisfy
    `pred(u, ref[..., None])`."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(jnp.greater_equal, cand) >= topk, cand, t)
    kth = lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))
    above = count(jnp.greater, kth)
    return kth, above, (kth > 0) & (above + count(jnp.equal, kth) > topk)


def _mask_of(u: jnp.ndarray, topk: int, kth, above, over) -> jnp.ndarray:
    """[..., N] bool from `_kth_value`'s answers: the `topk` largest live
    entries of `u`, equal values from the lowest index up; every live entry
    of a row that has no more than `topk`."""
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


def _topk_mask(u: jnp.ndarray, topk: int, count) -> jnp.ndarray:
    """`_mask_of` the `_kth_value` of `u`: both halves, in XLA."""
    return _mask_of(u, topk, *_kth_value(u, topk, count))


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


# ---------------------------------------------------------------------------
# Decode rows over the paged pool: the ranking as two Pallas kernels a layer.
# ---------------------------------------------------------------------------

# Tokens a grid step of the scoring kernel holds.  One 512-token block of the
# index plane is 64 KiB, 0.08 us of HBM time beside the ~1-1.5 us a grid step
# costs (ops/paged_attention.py's header), so a step takes several table
# entries: 8 at 512-token blocks, 512 KiB a step.  The unroll cap bounds the
# kernel body (one product an entry).
INDEX_STEP_TOKENS = 4096
_INDEX_STEP_UNROLL = 8

_INT_MIN = -(1 << 31)


def _index_entries(blk: int, mb: int) -> int:
    """Table entries a grid step of the ranking kernel covers, evened out
    over the steps a row takes (as `paged_attention._blocks_per_step`)."""
    p = max(1, min(-(-INDEX_STEP_TOKENS // blk), _INDEX_STEP_UNROLL, mb))
    return -(-mb // -(-mb // p))


def index_plan(pool_pos, table, q_pos):
    """The ranking kernel's step list for one-token rows at `q_pos` [B] (-1:
    an inactive row): `paged_attention._fetch_plan` at this kernel's entries
    a step.  It does not depend on the layer: derive it once an iteration,
    outside the layer scan."""
    entries = _index_entries(pool_pos.shape[1], table.shape[1])
    return _fetch_plan(pool_pos, table, q_pos.astype(jnp.int32), 1, entries)


def plan_row_steps(plan, n_rows: int) -> int:
    """Steps a row with every table entry live would take under `plan`."""
    return plan[4].shape[0] // n_rows


def _score_kernel(
    fetch_ref,  # [S * P] int32 scalar-prefetch: `_fetch_plan`'s, as the paged
    flag_ref,   # [S]       attention kernel reads them
    src_ref,    # [S]
    qpos_ref,   # [B] int32 the row's query position (-1: inactive)
    layer_ref,  # [1] int32 pool layer
    q_ref,      # [1, Hi, di] index queries
    w_ref,      # [1, Hi, 1] float32 head weights
    *rest,      # P key refs [1, 1, 1, BLK, di]; pos ref [1, P, BLK] int32
    #             (-1: a dead entry's slots); u_ref [1, NS * P, BLK] int32
    n_entries: int,
    row_steps: int,
):
    """One row's index scores over its live table entries, `n_entries` a grid
    step, as order-preserving int32 images in the row's output block, which
    stays in VMEM through the row's steps and leaves once.

    An image is the score's float32 bits made monotone under SIGNED compare
    (negative floats have their magnitude bits flipped); `_INT_MIN`, below
    every score, is "not live".  XOR with the sign bit gives `_sortable`'s
    uint32 image (0: not live)."""
    P = n_entries
    k_refs, pos_ref, u_ref = rest[:P], rest[P], rest[P + 1]
    step = pl.program_id(0)
    flags = flag_ref[step]
    src = src_ref[step]

    @pl.when(flags & _FIRST != 0)
    def _init():
        u_ref[...] = jnp.full_like(u_ref, _INT_MIN)

    # The grid holds a row's live steps (and one step of a row that has
    # none); a dead entry inside a live step holds whatever block its operand
    # fetched last, and its positions are all -1.
    @pl.when(flags & _LIVE != 0)
    def _score():
        qp = qpos_ref[src // row_steps]
        q = q_ref[0]
        w = w_ref[0]
        images = []
        for j in range(P):
            s = lax.dot_general(
                q, k_refs[j][0, 0, 0].astype(q.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)               # [Hi, BLK]
            score = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
            # -0.0 and 0.0 are one score (`index_scores`).
            score = jnp.where(score == 0.0, 0.0, score)
            b = lax.bitcast_convert_type(score, jnp.int32)
            kp = pos_ref[0, j:j + 1, :]
            images.append(jnp.where(
                (kp >= 0) & (kp <= qp),
                jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b), _INT_MIN))
        at = pl.multiple_of((src % row_steps) * P, P)
        u_ref[0, pl.ds(at, P), :] = jnp.concatenate(images, axis=0)


# Slots a sub-line of the list kernel: a line (one table entry, BLK slots) is
# walked in sub-lines whose running count, at most 256, is exact in bfloat16.
_SUB = 256
_LIST_TILE = 512     # places of the list a step: [_SUB, _LIST_TILE] temporaries


def _kth_images(u, own, topk: int):
    """`_kth_value` over int32 images `u` [B, R, C] and `own` [B, 1, 1] (see
    `_score_kernel`), all rows at once: (kth, above, equal) [B, 1, 1], the
    k-th value as `_sortable`'s uint32 bits."""
    def count(pred, ref):                                          # [B, 1, 1]
        n = jnp.sum(pred(u, ref).astype(jnp.int32), axis=1, keepdims=True)
        return jnp.sum(n, axis=2, keepdims=True) + pred(own, ref).astype(jnp.int32)

    # The candidate is built in the uint32 image's domain, compared in int32's.
    def bit(i, t):
        cand = t | (jnp.int32(1) << (31 - i))
        enough = count(jnp.greater_equal, cand ^ _INT_MIN) >= topk
        return jnp.where(enough, cand, t)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros(own.shape, jnp.int32))
    return (kth, count(jnp.greater, kth ^ _INT_MIN),
            count(jnp.equal, kth ^ _INT_MIN))


def _ones(cond):
    """A 0/1 bfloat16 operand of the MXU from a mask."""
    return jnp.where(cond, 1.0, 0.0).astype(jnp.bfloat16)


def _count(a, b):
    """a @ b of 0/1 (or small whole-number) bfloat16 operands, float32."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _lanes(row, n: int):
    """A lane-replicated [1, 128] row at `n` lanes (Mosaic broadcasts along
    one dimension at a time: a [1, 1] value does not meet a [R, C] one)."""
    return row[:, :n] if n <= _LANES else jnp.tile(row, (1, n // _LANES))


def _list_row(u, found, tri_c, tri_w, *, topk: int, n_slots: int, n_places: int):
    """One row's chosen list, `_mask_of` + `_compact` on values in VMEM: `u`
    [R, C] int32 images (a line is a table entry), `found` [8, 128] the
    row's lines of `_select_kernel`'s found_ref; `tri_c` [C, C] and `tri_w`
    [W, W] the 0/1 triangles (below).  Returns (ids, live) [1, n_places]
    int32, `n_places` whole tiles of `_LIST_TILE`.

    Running counts are products with 0/1 triangles on the MXU (bfloat16
    operands, float32 sums: exact).  The mask: an image above the k-th value,
    and those AT it in slot order while room is left, by the ties' own running
    count (a line's, plus the lines' before it); the own token is the last
    candidate.  The list: the lines are cut into sub-lines of W <= 256 slots,
    stacked [R * C / W, W]; place j belongs to the sub-line whose running
    total passes j (`hot`, one compare pair a sub-line a place), a product of
    the sub-lines' running counts with `hot` fetches that sub-line's counts
    for every place of a tile, and the slot is how many of them are <= the
    place's rank inside the sub-line.  No sort, no scatter, and nothing the
    size of [k, C] outlives a tile."""
    R, C = u.shape
    W = tri_w.shape[0]
    f32 = jnp.float32
    lane_sum = lambda a: jnp.sum(a, axis=1, keepdims=True)   # noqa: E731
    all_sum = lambda a: jnp.sum(lane_sum(a), axis=0, keepdims=True)  # noqa: E731
    lower = _ones(lax.broadcasted_iota(jnp.int32, (R, R), 1)
                  < lax.broadcasted_iota(jnp.int32, (R, R), 0))    # [r, r']: r' < r
    kth, own = found[0:1] ^ _INT_MIN, found[3:4]                   # [1, 128] images
    room = (topk - found[1:2]).astype(f32)

    tie = (u == _lanes(kth, C)) & (u != _INT_MIN)
    tie_b = _ones(tie)
    rank = lane_sum(_count(lower, tie_b)) + _count(tie_b, tri_c)   # [R, C]
    mask = (u > _lanes(kth, C)) | (tie & (rank <= _lanes(room, C)))
    own_in = (own > kth) | ((own == kth) & (own != _INT_MIN)
                            & (all_sum(tie_b.astype(f32)) + 1.0 <= room))
    m = _ones(mask)
    total = all_sum(m.astype(f32)) + jnp.zeros_like(room)          # [1, 128]
    n_live = _lanes(total + jnp.where(own_in, 1.0, 0.0), _LIST_TILE)
    total = _lanes(total, _LIST_TILE)

    # Sub-lines, stacked half by half: sub-line h of line r is row h * R + r.
    halves = [m[:, h * W:(h + 1) * W] for h in range(C // W)]
    before = lane_sum(_count(lower, m))                            # [R, 1] lines
    starts, ends, firsts = [], [], []
    line = lax.broadcasted_iota(jnp.int32, (R, 1), 0).astype(f32) * C
    for h, half in enumerate(halves):
        starts.append(before)
        before = before + lane_sum(half.astype(f32))
        ends.append(before)
        firsts.append(line + h * W)
    start, end, first = (jnp.concatenate(a, axis=0) for a in (starts, ends, firsts))
    # inside[c, s]: set entries of sub-line s at or before slot c.
    inside = lax.dot_general(
        tri_w, jnp.concatenate(halves, axis=0), (((1,), (1,)), ((), ())),
        preferred_element_type=f32).astype(jnp.bfloat16)           # [W, R * C / W]

    ids, live = [], []
    for t in range(n_places // _LIST_TILE):
        j = (lax.broadcasted_iota(jnp.int32, (1, _LIST_TILE), 1)
             + t * _LIST_TILE).astype(f32)
        hot = (start <= j) & (j < end)                             # [subs, tile]
        down = lambda a: jnp.sum(jnp.where(hot, a, 0.0), axis=0, keepdims=True)  # noqa: E731
        counts = _count(inside, _ones(hot))                        # [W, tile]
        slot = down(first) + jnp.sum(
            jnp.where(counts <= down(j - start), 1.0, 0.0), axis=0, keepdims=True)
        ids.append(jnp.where(j < total, slot, float(n_slots)))
        live.append(j < n_live)
    return tuple(jnp.concatenate(a, axis=1).astype(jnp.int32) for a in (ids, live))


def _select_kernel(u_ref, own_ref, found_ref, chosen_ref, live_ref,
                   tri_c_ref, tri_w_ref, *, topk: int, n_slots: int):
    """The decode rows' selection from their images, all of it in VMEM:
    u_ref [B, R, C] int32 images (`_score_kernel`'s), own_ref [B, 1, 1] the
    image of each row's own token.  First `_kth_value`'s 32 compare-and-count
    passes and its two counts over every row at once (found_ref [B, 8, 128]
    int32, lines 0 to 3 of a row its k-th value as `_sortable`'s uint32
    bits, the count above it, the count at it and the own token's image);
    then a row at a time the chosen list (`_list_row`): chosen_ref /
    live_ref [B, K] int32, K the list's length rounded up to whole tiles."""
    own = own_ref[...]
    kth, above, equal = _kth_images(u_ref[...], own, topk)
    line = lax.broadcasted_iota(jnp.int32, found_ref.shape, 1)
    found_ref[...] = jnp.where(
        line == 0, kth, jnp.where(line == 1, above, jnp.where(line == 2, equal, own)))

    C, W = tri_c_ref.shape[0], tri_w_ref.shape[0]
    iota = lambda n, d: lax.broadcasted_iota(jnp.int32, (n, n), d)   # noqa: E731
    tri_c_ref[...] = _ones(iota(C, 0) <= iota(C, 1))    # [c', c]: c' <= c
    tri_w_ref[...] = _ones(iota(W, 1) <= iota(W, 0))    # [c, c']: c' <= c

    def row(b, carry):
        chosen_ref[pl.ds(b, 1), :], live_ref[pl.ds(b, 1), :] = _list_row(
            u_ref[b], found_ref[b], tri_c_ref[...], tri_w_ref[...],
            topk=topk, n_slots=n_slots, n_places=chosen_ref.shape[1])
        return carry

    lax.fori_loop(0, u_ref.shape[0], row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_index_scores(
    q_idx: jnp.ndarray,    # [B, Hi, di] index queries (rotated), one a row
    w: jnp.ndarray,        # [B, Hi] float32 head weights
    plane: jnp.ndarray,    # [L, 1, NB, BLK, di] the index-key plane, in place
    plan,                  # `index_plan`'s
    q_pos: jnp.ndarray,    # [B] int32 query positions; -1: inactive row
    layer,                 # int32 pool layer
    *,
    interpret=None,
) -> jnp.ndarray:
    """[B, R, BLK] int32 images (`_score_kernel`) of
    `index_scores(...)` over every slot of each row's table in sequence
    order, a table entry a line (R >= MB: the plan's last step may pad the
    table, with lines that are not live), `_INT_MIN` where the slot is not
    live for the row.

    The kernel walks the plane through the block table, live entries only,
    several a grid step, and never copies it."""
    B, Hi, di = q_idx.shape
    BLK = plane.shape[3]
    n_steps, fetch, flags, src, kpos = plan
    P = kpos.shape[1]
    NS = plan_row_steps(plan, B)

    def row_map(t, fetch, flags, src, *_):
        return (src[t] // NS, 0, 0)

    def key_map(j):
        def index(t, fetch, flags, src, qpos, layer):
            f = fetch[t * P + j]
            return (layer[0], 0, jnp.where(f < 0, -1 - f, f), 0, 0)
        return index

    def pos_map(t, fetch, flags, src, *_):
        return (src[t], 0, 0)

    # The plane goes in as it is declared, [.., BLK, di], which is the layout
    # the serving programs' loops carry it in (the pool writes decide that): a
    # block is then a 128-lane-padded tile.  Handing in the transposed view
    # [.., di, BLK] — the device's own layout for the pool argument, 64 KiB a
    # block and a plain product — makes XLA copy the whole plane once a decode
    # iteration to get from one to the other, as the parent's gather did
    # (compiled for a described v5e, PERF.md section 6, PR 49).
    return pl.pallas_call(
        functools.partial(_score_kernel, n_entries=P, row_steps=NS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_steps,),
            in_specs=[
                pl.BlockSpec((1, Hi, di), row_map),
                pl.BlockSpec((1, Hi, 1), row_map),
                *[pl.BlockSpec((1, 1, 1, BLK, di), key_map(j)) for j in range(P)],
                pl.BlockSpec((1, P, BLK), pos_map),
            ],
            out_specs=pl.BlockSpec((1, NS * P, BLK), row_map),
        ),
        out_shape=jax.ShapeDtypeStruct((B, NS * P, BLK), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_resolve_interpret(interpret),
        name="paged_index_scores",
    )(fetch, flags, src, q_pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q_idx, w.astype(jnp.float32)[:, :, None], *[plane] * P, kpos)


@functools.partial(jax.jit, static_argnames=("topk", "n_slots", "interpret"))
def paged_index_select(
    u: jnp.ndarray,        # [B, R, BLK] int32 images (`paged_index_scores`)
    own: jnp.ndarray,      # [B] uint32 image of the row's own token's score
    #                        (candidate `n_slots`; 0 for an inactive row)
    *,
    topk: int,
    n_slots: int,          # S = MB * BLK: the slots of a row's table
    interpret=None,
):
    """(chosen [B, k] int32, chosen_live [B, k] bool, kth [B] uint32, above
    [B] int32, equal [B] int32), k = min(topk, S + 1): `_kth_value`'s answers
    over the images and `own`, and `_compact(_mask_of(...), k)` of them — the
    chosen candidates in ascending order, `n_slots` the own token — in ONE
    grid-less kernel that holds every row's images (1 MB for eight rows of
    32,768 slots) in VMEM.  A pass of the search is one compare-and-count
    over all rows, and nothing leaves the vector unit between passes: 12 us
    where XLA's passes over HBM take 81 (PERF.md section 6, PR 49); the mask
    and the list follow on the same images a row at a time.  A place past the
    row's count holds `n_slots` where `_compact` holds its clamp: compare
    under `chosen_live`."""
    B, R, C = u.shape
    if C > _SUB and C % _SUB:
        raise ValueError(f"a block of {C} slots is not whole sub-lines of {_SUB}")
    k = min(topk, n_slots + 1)
    K = -(-k // _LIST_TILE) * _LIST_TILE
    found, chosen, live = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, n_slots=n_slots),
        out_shape=[jax.ShapeDtypeStruct((B, _SUBLANES, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((B, K), jnp.int32),
                   jax.ShapeDtypeStruct((B, K), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((C, C), jnp.bfloat16),
                        pltpu.VMEM((min(C, _SUB),) * 2, jnp.bfloat16)],
        interpret=_resolve_interpret(interpret),
        name="paged_index_select",
    )(u, lax.bitcast_convert_type(own ^ jnp.uint32(1 << 31), jnp.int32).reshape(B, 1, 1))
    return (chosen[:, :k], live[:, :k] != 0,
            lax.bitcast_convert_type(found[:, 0, 0], jnp.uint32),
            found[:, 1, 0], found[:, 2, 0])


def paged_select_slots(
    q_idx: jnp.ndarray,    # [B, 1, Hi, di]
    w: jnp.ndarray,        # [B, 1, Hi] float32
    k_idx: jnp.ndarray,    # [B, 1, di] the step's own index keys
    plane: jnp.ndarray,    # [L, 1, NB, BLK, di]
    table: jnp.ndarray,    # [B, MB]
    plan,                  # `index_plan`'s
    q_pos: jnp.ndarray,    # [B] int32; -1: inactive row
    layer,
    topk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`select_slots` for one-token rows over the paged pool: (chosen
    candidates [B, 1, k] int32 in ascending order, which of them are live,
    the rows [B] whose k-th value more candidates shared than it had room
    for), k = min(topk, S + 1).  Candidate ids below S = MB * BLK are the
    slots of the row's table in sequence order, S is the step's own token
    (always live for an active row)."""
    S = table.shape[1] * plane.shape[3]
    with jax.named_scope("attn.index"):
        own = jnp.where(
            q_pos >= 0, _sortable(index_scores(q_idx, w, k_idx)[:, 0, 0]), 0)
        u = paged_index_scores(q_idx[:, 0], w[:, 0], plane, plan, q_pos, layer)
    with jax.named_scope("attn.select"):
        chosen, chosen_live, kth, above, equal = paged_index_select(
            u, own, topk=topk, n_slots=S)
        return (chosen[:, None], chosen_live[:, None],
                (kth > 0) & (above + equal > topk))
