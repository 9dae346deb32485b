"""Pallas TPU paged-attention decode kernel — walks the block table
in-kernel.

The serving pool stores KV in fixed-size physical blocks
(``serving.BlockPool``); before this kernel, every decode step gathered
each row's blocks into a virtually-contiguous cache view and ran
``sdpa_cached`` over it — the pool bytes moved three times per step
(gather read, gather write, attention read).  Here the kernel's index
maps chase the block table directly via scalar prefetch, so the pool is
read ONCE and nothing contiguous is ever materialized (the vLLM
paged-attention idea, executed the Pallas way: the table lookup lives in
the BlockSpec index_map, the DMA pipeline does the pointer-chasing).

Layout contract: the pool is [KVH, NB, BLK, hd] per layer — KV-head
major, so a block's tile is a clean ``(KVH, BLK, hd)`` VMEM page.
The grid is ONE dimension whose length is a value, not a shape: the
live steps of each row in turn, a step being ``P`` consecutive table
entries of the row (``_blocks_per_step``: about 512 tokens, so 4 at
128-token blocks and 1 at 512).  The pool is passed ``P`` times, entry
``j`` of a step through operand ``j``, so Pallas's own pipeline still
does the pointer chasing and the double buffering; ``_fetch_plan``
builds the step list and what each operand names in every step, and a
dead entry names the block its operand already holds, so no dead block
is ever fetched.  A call therefore costs what its live blocks cost — a
``(B, MB)`` grid of one block a step paid ~0.39 µs for each of its dead
steps and ~1.55 µs for a live one whose DMA is 0.64 µs (v5e, PERF.md
section 6, PR 25).  One grid step covers all KV heads of its blocks via
statically unrolled in-kernel loops (a finer (B, KVH, MB) grid was
measured SLOWER than the gathered view it replaces — per-cell overhead
beat the bandwidth saving).  Online softmax state lives in VMEM scratch
across a row's steps, exactly like ``ops.flash_attention``, and moves
once per (KV head, step).  GQA: the ``group`` query heads of each KV
head ride the sublane axis of that head's q rows (padded to 8), so
decode reads each KV block once — never per query head.

The kernel attends the POOL only and emits a normalized output plus the
row logsumexp; the caller merges the current step's own K/V (one slot,
always attendable) at the scores level — the same two-source softmax
split as ``ops.attention.sdpa_cached``, so the pool stays immutable
through the layer scan and the decode step applies one scatter per step.

Scan compatibility: everything dynamic the kernel consumes — the step
list derived from the block table and the per-row query positions, its
length (the grid bound), the layer index, and the pool planes
themselves — enters as traced operands (scalar-prefetch, grid bound or
BlockSpec-mapped), so the whole op nests inside ``lax.scan`` loops
without re-tracing: the model's layer scan selects planes via
``layer`` (the step list does not depend on it, so XLA hoists its
derivation out of that scan), and serving's fused decode chunk
(``serving._paged_decode_chunk``) additionally scans K decode
iterations around the layer scan, re-deriving positions and the step
list per iteration on device.  Under a mesh the shard_map wrapper nests
inside those scans the same way.

Fused prefill-decode scheduling (``serving._fused_chunk``) runs this
kernel's decode scan WHILE an admission's prompt is mid-prefill in the
same dispatch: the prefilling row rides the decode grid masked (its
query position is -1 until its last prompt chunk lands, so it attends
nothing and its write-back resolves to the sentinel block and drops) —
the standard idle-row contract, no new kernel case.  Its partially
written blocks are safe for the OTHER rows by construction: only a
row's own blocks ever carry weight in its softmax (a dead entry may
hold another row's block in VMEM, masked to zero and dropped).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (
    MASK_VALUE,
    _LANES,
    _SUBLANES,
    _resolve_interpret,
)

def _maybe_fault() -> None:
    """Chaos-drill hook: fires faults.py's trace-time registry (site
    "paged_kernel") — the paged twin of ops.flash_attention's hook."""
    from ..faults import fire_trace

    fire_trace("paged_kernel")


# One grid step covers several table entries of a row.  Each step pays a
# fixed cost (grid bookkeeping, every operand's index map and DMA issue,
# the softmax state's read-modify-write) that at one 128-token block a
# step was twice the block's own HBM time, so a step should hold about
# this many tokens.  More is worse: the dead entries of a row's last
# step are computed (and masked), so short rows pay for the width — at
# 128-token blocks 4 a step measured best from 2 to 8 KV heads, 16 a
# step twice as slow (PERF.md section 6, PR 25).  The unroll cap bounds
# the kernel body (KV heads x entries dots of each kind), the buffer cap
# the VMEM the pipeline's double buffers take (twice this).
_STEP_TOKENS = 512
_STEP_BUFFER_BYTES = 4 << 20
_STEP_UNROLL = 32


def _blocks_per_step(blk: int, mb: int, kvh: int, d: int, itemsize: int) -> int:
    """Table entries ``P`` one grid step covers, from the shapes alone.

    Enough blocks that a step holds ``_STEP_TOKENS`` tokens, capped by
    the unrolled body, by VMEM and by the row's table; then evened out
    over the ``ceil(mb / P)`` steps a row takes, so the sentinel padding
    of the last step is the least it can be.  128-token blocks give 4; a
    512-token block gives 1 (the one-block step this kernel had before,
    already near its streaming floor there).
    """
    block_bytes = 2 * kvh * blk * d * itemsize
    cap = min(_STEP_UNROLL // kvh, _STEP_BUFFER_BYTES // block_bytes, mb)
    p = max(1, min(-(-_STEP_TOKENS // blk), cap))
    return -(-mb // -(-mb // p))


# Scalar-prefetched per-step flags (``_fetch_plan``).
_FIRST, _LAST, _LIVE = 1, 2, 4


def _paged_kernel(
    fetch_ref,  # [S * P] int32 scalar-prefetch: block id of entry j of grid
    #             step t if >= 0; -1 - (the block that operand already
    #             holds) if the entry is dead
    flag_ref,   # [S] int32 scalar-prefetch: _FIRST | _LAST step of its row,
    #             _LIVE if any entry is live
    src_ref,    # [S] int32 scalar-prefetch: b * NS + s, the step's row and
    #             its place in the row (and in the position plane)
    qpos_ref,   # [B] int32 scalar-prefetch: FIRST token's query position
    #             (-1 = inactive row; token t sits at qpos + t)
    layer_ref,  # [1] int32 scalar-prefetch: pool layer this call reads
    *rest,      # [window_ref [1] int32 scalar-prefetch when windowed;]
    #             q_ref [1, KVH, TG8, d] — sublane row r = t*group + g;
    #             P k refs, P v refs [1, KVH, 1, BLK, d] (int8 when
    #             quantized); pos ref [1, P, BLK] int32 (-1 = masked slot);
    #             when quantized P k-scale and P v-scale refs
    #             [1, KVH, 1, 1, BLK] fp32; o_ref; lse_ref; scratch m, l, acc
    scale: float,
    n_entries: int,
    row_steps: int,
    kvh: int,
    tg8: int,
    t_tokens: int,
    group: int,
    quantized: bool = False,
    v_width: int = 0,
    windowed: bool = False,
):
    """Online-softmax sweep of one row's pool blocks, ``n_entries`` table
    entries a grid step.

    ``windowed``: a query at position p sees the slots at p - window + 1 ..
    p only (``window`` a scalar-prefetched value); the grid then holds the
    steps ``_fetch_plan`` found a slot inside the window in, and masked
    probabilities are zeroed explicitly as for T > 1.

    ``v_width`` > 0 is the latent-attention row: there are no v refs, and a
    slot's value is the first ``v_width`` columns of its key row (the normed
    latent; the rotated shared key behind it only scores).

    The softmax state moves once per (KV head, step): the step's entries
    share one running max, one ``exp`` rescale of ``acc`` and one write
    of the three scratch planes.  A dead entry inside a live step (past
    the row's last attendable block, a sentinel, an all-masked block)
    holds whatever block its operand fetched last — possibly another
    row's — so it is masked to exactly zero weight and its P·V product
    is dropped whole (0 x a non-finite stale value would not be 0).

    ``t_tokens`` queries per (row, query head) ride the sublane axis
    (row r = t*group + g); their positions are CONSECUTIVE — token t at
    ``qpos + t`` — so per-token masks derive from a sublane iota and no
    per-token position plane is needed.  T=1 needs no explicit zeroing:
    a live step holds a slot its one query may attend, so the step's max
    is a real score and every masked ``exp`` underflows to 0.  T>1 zeroes
    masked probabilities explicitly, because an entry can be live for a
    late token but fully masked for an early one.
    """
    P = n_entries
    if windowed:
        window_ref, *rest = rest
    q_ref, *rest = rest
    if v_width:
        k_refs, v_refs, pos_ref, rest = rest[:P], None, rest[P], rest[P + 1:]
    else:
        k_refs, v_refs, pos_ref = rest[:P], rest[P:2 * P], rest[2 * P]
        rest = rest[2 * P + 1:]
    if quantized:
        k_scale_refs, v_scale_refs, rest = rest[:P], rest[P:2 * P], rest[2 * P:]
    o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    step = pl.program_id(0)
    flags = flag_ref[step]

    @pl.when(flags & _FIRST != 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # The grid holds a row's live steps only (and one step of a row that
    # has none, which stops at this scalar compare).  Skipping is
    # mandatory, not an optimisation: a step with no attendable slot
    # would add p = exp(MASK - MASK) = 1 garbage into l/acc (same
    # invariant as flash block_live).
    @pl.when(flags & _LIVE != 0)
    def _compute():
        qp = qpos_ref[src_ref[step] // row_steps]
        if t_tokens > 1:
            # Per-sublane query position: row r holds token r // group.
            # (Pad rows past t_tokens*group get later tokens' looser
            # masks; their q rows are zero-padding, outputs sliced off.)
            qp = qp + jax.lax.broadcasted_iota(
                jnp.int32, (tg8, 1), 0
            ) // group  # [TG8, 1]
        entry_ok, allowed = [], []
        for j in range(P):
            kp = pos_ref[0, j:j + 1, :]  # [1, BLK]; a dead entry's is all -1
            entry_ok.append(fetch_ref[step * P + j] >= 0)
            ok = (kp >= 0) & (kp <= qp)  # [1 | TG8, BLK]
            if windowed:
                ok = ok & (kp > qp - window_ref[0])
            allowed.append(ok)
        # One grid step covers ALL KV heads of its blocks (the loops
        # unroll statically): measured ~1 µs of per-cell overhead made a
        # (B, KVH, MB) grid SLOWER than the gathered-view fallback.
        for h in range(kvh):
            sl = slice(h * tg8, (h + 1) * tg8)
            q = q_ref[0, h]
            scores = []
            for j in range(P):
                if quantized:
                    # int8 pool: cast the tile in VMEM (int8 magnitudes
                    # are exact in bf16) and fold the per-slot dequant
                    # scales at the scores / probability level — the
                    # same commuting trick as flash_attention_quantized,
                    # so HBM streams the int8 bytes.
                    k = k_refs[j][0, h, 0].astype(q.dtype)
                else:
                    k = k_refs[j][0, h, 0]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [TG8, BLK]
                if quantized:
                    s = s * k_scale_refs[j][0, h, 0, :1, :]
                scores.append(jnp.where(allowed[j], s, MASK_VALUE))
            m_prev = m_ref[sl, :1]
            m_new = jnp.maximum(
                m_prev,
                jnp.max(
                    functools.reduce(jnp.maximum, scores),
                    axis=-1, keepdims=True,
                ),
            )
            alpha = jnp.exp(m_prev - m_new)
            probs, pv = [], None
            for j in range(P):
                p = jnp.exp(scores[j] - m_new)
                if t_tokens > 1 or windowed:
                    p = jnp.where(allowed[j], p, 0.0)
                probs.append(p)
                if quantized:
                    pj = (p * v_scale_refs[j][0, h, 0, :1, :]).astype(q.dtype)
                    vb = v_refs[j][0, h, 0].astype(q.dtype)
                elif v_width:
                    pj = p.astype(k_refs[j].dtype)
                    vb = k_refs[j][0, h, 0][:, :v_width]
                else:
                    pj = p.astype(v_refs[j].dtype)
                    vb = v_refs[j][0, h, 0]
                term = jax.lax.dot_general(
                    pj, vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if P > 1:
                    term = jnp.where(entry_ok[j], term, 0.0)
                pv = term if pv is None else pv + term
            l_ref[sl] = jnp.broadcast_to(
                alpha * l_ref[sl, :1] + jnp.sum(
                    functools.reduce(jnp.add, probs), axis=-1, keepdims=True
                ),
                (tg8, l_ref.shape[1]),
            )
            acc_ref[sl] = alpha * acc_ref[sl] + pv
            m_ref[sl] = jnp.broadcast_to(m_new, (tg8, m_ref.shape[1]))

    @pl.when(flags & _LAST != 0)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (
            acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
        ).reshape(kvh, tg8, -1).astype(o_ref.dtype)
        # lse stays ~MASK_VALUE for rows that attended nothing, so the
        # caller's merge weight exp(lse - m_tot) underflows to exactly 0.
        lse_ref[0] = (
            m_ref[:] + jnp.log(jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:]))
        ).reshape(kvh, tg8, -1)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _fetch_plan(pool_pos, table, q_pos, t_tokens: int, n_entries: int,
                window=None):
    """The grid of one call: which (row, step) pairs run, in order, and
    what each fetches.  Depends on ``(pool_pos, table, q_pos)`` only, not
    on the layer, so XLA computes it once per decode iteration, outside
    the model's layer scan.

    ``window`` (an int32 value; None: no window) is a second bound on the
    same liveness: an entry is live only if its block also holds a slot at
    or after the FIRST query's ``q_pos - window + 1``, so a window layer's
    grid is the steps that overlap the window and no others — at 512-token
    blocks and a 2048 window, 4-5 a row whatever the context.

    A table entry is live when its block holds a slot the row's LAST
    query may attend (so sentinel entries, the reserved-but-unwritten
    tail, all-masked blocks and every entry of an inactive row are
    dead); a step — ``P`` consecutive entries of a row — is live when
    one of its entries is.  The grid runs the live steps and the first
    step of every row (a row with no live step still has to write its
    empty output), rows in order: its length ``n_steps`` is a value, not
    a shape, so a call costs what its live steps cost.

    The kernel takes entry ``j`` of every step through operand ``j``,
    whose pipeline skips the DMA when two consecutive grid steps name
    the same block: a dead entry therefore names the block that operand
    fetched LAST — in this row or an earlier one — encoded as
    ``-1 - block`` so the kernel also reads the entry's liveness from
    it.  No dead block is ever fetched (operands with no live entry yet
    at the head of the grid name block 0 once).

    Returns ``(n_steps, fetch [S*P], flags [S], src [S], kpos [S, P,
    BLK])`` with ``S = B * NS`` the most steps a call can take; entries
    past ``n_steps`` are never read.  ``src`` is the step's place
    ``b * NS + s`` before the dead steps were dropped: it names the row
    and indexes ``kpos``, which holds -1 in every slot of a dead entry.
    """
    NB, BLK = pool_pos.shape
    B, MB = table.shape
    P = n_entries
    NS = -(-MB // P)
    S = B * NS
    imax = jnp.iinfo(jnp.int32).max
    blk_min = jnp.min(jnp.where(pool_pos >= 0, pool_pos, imax), axis=1)
    blk_min = jnp.concatenate(
        [blk_min, jnp.full((1,), imax, jnp.int32)]
    )  # [NB + 1] min live position per block; sentinel NB never attendable
    table = jnp.pad(
        jnp.minimum(table.astype(jnp.int32), NB),
        ((0, 0), (0, NS * P - MB)), constant_values=NB,
    ).reshape(S, P)
    qp = jnp.repeat(q_pos, NS)[:, None]
    ok = (blk_min[table] <= qp + (t_tokens - 1)) & (qp >= 0)
    if window is not None:
        blk_max = jnp.concatenate(
            [jnp.max(pool_pos, axis=1), jnp.full((1,), -1, jnp.int32)]
        )  # [NB + 1] max position per block (-1: none live)
        ok = ok & (blk_max[table] > qp - window)
    kpos = jnp.where(
        ok[:, :, None], pool_pos[jnp.minimum(table, NB - 1)], -1
    )
    step = jnp.arange(S, dtype=jnp.int32)
    last_fetch = jax.lax.cummax(jnp.where(ok, step[:, None], -1), axis=0)
    held = jnp.where(
        last_fetch >= 0,
        jnp.take_along_axis(table, jnp.maximum(last_fetch, 0), axis=0),
        0,
    )
    fetch = jnp.where(ok, table, -1 - held)

    live = jnp.any(ok, axis=1)
    first = step % NS == 0
    keep = live | first
    # The row's last kept step: no kept step follows it inside the row.
    kept_s = jnp.where(keep, step % NS, -1).reshape(B, NS)
    last = (step % NS) == jnp.repeat(jnp.max(kept_s, axis=1), NS)
    flags = (
        jnp.where(first, _FIRST, 0) | jnp.where(last, _LAST, 0)
        | jnp.where(live, _LIVE, 0)
    )
    # Compaction: grid step t is the kept step of rank t.  S is at most a
    # few hundred, so an S x S compare-and-sum is one small fusion (a
    # sort of 64 keys measured 47 µs on the v5e, PERF.md section 6).
    rank = jnp.cumsum(keep, dtype=jnp.int32) - 1
    src = jnp.sum(
        jnp.where(keep[None, :] & (rank[None, :] == step[:, None]), step, 0),
        axis=1,
    )
    return (
        jnp.sum(keep, dtype=jnp.int32), fetch[src].reshape(-1), flags[src],
        src, kpos,
    )


def fetch_plan(k_pool, pool_pos, table, q_pos, t_tokens: int = 1,
               window=None):
    """The step list ``paged_decode_attention`` would derive for these
    operands (``_fetch_plan`` at the pool's own entries-per-step), as a
    tuple it takes back through ``plan=``: a caller whose layers differ in
    ``window`` derives one plan per kind OUTSIDE its layer scan and hands
    each layer its kind's, so the derivation still happens once an
    iteration and not once a layer.  ``plan[0]`` is the grid length: the
    steps the call will run."""
    KVH, _, BLK, d = k_pool.shape[-4:]
    P = _blocks_per_step(BLK, table.shape[1], KVH, d, k_pool.dtype.itemsize)
    return _fetch_plan(
        pool_pos, table, q_pos.astype(jnp.int32), t_tokens, P, window)


def plan_live_steps(plan) -> jnp.ndarray:
    """Grid steps of ``plan`` that hold a slot some query attends (a row
    with none still runs one, dead, to write its empty output)."""
    n_steps, _, flags = plan[:3]
    ran = jnp.arange(flags.shape[0], dtype=jnp.int32) < n_steps
    return jnp.sum(ran & (flags & _LIVE != 0), dtype=jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("t_tokens", "interpret", "v_width", "scale")
)
def paged_pool_attention(
    q: jnp.ndarray,        # [B, KVH, T*G, d]  (packed queries, r = t*G + g)
    k_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d] (or [KVH, NB, BLK, d])
    v_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d]
    pool_pos: jnp.ndarray,  # [NB, BLK] int32 (-1 = invalid slot)
    table: jnp.ndarray,    # [B, MB] int32 physical block ids (NB = unused)
    q_pos: jnp.ndarray,    # [B] int32 first token's position (-1 = inactive)
    k_scale: Optional[jnp.ndarray] = None,  # [L, KVH, NB, BLK] fp32 (int8)
    v_scale: Optional[jnp.ndarray] = None,
    t_tokens: int = 1,
    layer: Optional[jnp.ndarray] = None,    # int32 layer index into L
    interpret: Optional[bool] = None,
    v_width: int = 0,
    scale: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,   # int32 value; None: no window
    plan=None,                              # ``fetch_plan``'s, for `window`
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Attend each row's table-mapped pool blocks; no gather, pool read once.

    ``window``: a query at position p sees the slots at p - window + 1 .. p,
    and the grid holds only the steps with such a slot (``_fetch_plan``).
    ``plan`` hands in the step list derived elsewhere for the same operands
    and window (``fetch_plan``).  With neither, the program is the one
    without a window, unchanged.

    ``v_width`` > 0 (latent attention): ``v_pool`` is None, a slot's value
    is the first ``v_width`` columns of its ``k_pool`` row, the output is
    ``[B, KVH, T*G, v_width]`` and the pool streams once, not twice.
    ``scale`` overrides the ``1 / sqrt(d)`` score scale (the latent row's
    width is not the head size the published scale divides by).

    The pool carries its LAYER axis and ``layer`` (a traced scalar — the
    layer scan's loop index) selects the plane inside the kernel's index
    maps.  Slicing ``pool[layer]`` at the caller instead would
    materialize a full copy of the layer's plane as the custom-call
    operand — 2 planes × L layers × plane-bytes of pure copy traffic per
    decode step, which at 16k context cost ~3× the kernel itself
    (xplane-measured r4: 4.7 of 9.3 ms/step).  A 4-D pool (single plane)
    is accepted for compatibility and reads layer 0.

    With ``t_tokens`` > 1 each row carries T queries at CONSECUTIVE
    positions (token t at ``q_pos + t`` — the speculative-verify /
    multi-token decode shape); they ride the sublane axis packed
    ``r = t*G + g``, so the pool still streams ONCE for the whole
    (row, T) group.  With ``k_scale``/``v_scale`` the pool is int8 and
    the per-slot dequant scales fold in-kernel (scores-level for K,
    probability-level for V) — the pool streams at one byte per element
    plus fp32 scales.

    Returns (out [B, KVH, T*G, d] fp32, normalized over the pool slots,
    lse [B, KVH, T*G] fp32 row logsumexp) for the caller's
    new-token merge (fp32 end-to-end through the merge — see the
    out_shape note in the kernel call).
    """
    _maybe_fault()
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], None if v_pool is None else v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = None
    # A multi-layer pool without a layer index would silently attend
    # layer 0 everywhere — fail at trace time instead.  ValueError, not
    # assert: unlike the adjacent shape asserts (whose mistakes surface
    # immediately as shape errors), this guard protects against silently
    # WRONG results and must survive `python -O`.
    if k_pool.shape[0] != 1 and layer is None:
        raise ValueError(
            "multi-layer pool requires the `layer` index (a 5-D pool with "
            "layer=None would attend layer 0 for every layer)"
        )
    layer_arr = (
        jnp.zeros((1,), jnp.int32) if layer is None
        else jnp.asarray(layer, jnp.int32).reshape(1)
    )
    B, KVH, TG, d = q.shape
    NB, BLK = pool_pos.shape
    MB = table.shape[1]
    L = k_pool.shape[0]
    assert k_pool.shape == (L, KVH, NB, BLK, d), (
        k_pool.shape, (L, KVH, NB, BLK, d)
    )
    assert TG % t_tokens == 0, (TG, t_tokens)
    group = TG // t_tokens
    quantized = k_scale is not None
    interpret = _resolve_interpret(interpret)
    TG8 = _round_up(TG, _SUBLANES)
    qg = jnp.pad(q, ((0, 0), (0, 0), (0, TG8 - TG), (0, 0)))
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    assert not (v_width and quantized), "the latent row has no int8 form"
    dv = v_width or d

    P = _blocks_per_step(BLK, MB, KVH, d, k_pool.dtype.itemsize)
    q_pos = q_pos.astype(jnp.int32)
    NS = -(-MB // P)
    windowed = window is not None
    assert not (windowed and quantized), "the int8 pool takes no window"
    if plan is None:
        plan = _fetch_plan(pool_pos, table, q_pos, t_tokens, P, window)
    n_steps, fetch, flags, src, kpos = plan

    # Index maps; the scalar-prefetch refs follow the grid index in the
    # kernel's order: fetch, flags, src, qpos, layer[, window].
    def row_map(t, fetch, flags, src, *_):
        return (src[t] // NS, 0, 0, 0)

    def kv_map(j):
        def index(t, fetch, flags, src, qpos, layer, *_):
            f = fetch[t * P + j]
            return (layer[0], 0, jnp.where(f < 0, -1 - f, f), 0, 0)
        return index

    def pos_map(t, fetch, flags, src, *_):
        return (src[t], 0, 0)

    kv_specs = [
        pl.BlockSpec((1, KVH, 1, BLK, d), kv_map(j)) for j in range(P)
    ]
    v_specs = [] if v_width else kv_specs
    in_specs = [
        pl.BlockSpec((1, KVH, TG8, d), row_map), *kv_specs, *v_specs,
        pl.BlockSpec((1, P, BLK), pos_map),
    ]
    operands = [qg, *[k_pool] * P, *[v_pool] * len(v_specs), kpos]
    if quantized:
        # Narrow-sublane scale planes [L, KVH, NB, 1, BLK]: free
        # expand_dims views of the long-lived pool scales — NOT sublane-
        # replicated copies, which would re-materialize (and stream) 8x
        # the scale bytes per layer per step on the path this kernel
        # exists to make bandwidth-lean.
        scale_specs = [
            pl.BlockSpec((1, KVH, 1, 1, BLK), kv_map(j)) for j in range(P)
        ]
        in_specs += [*scale_specs, *scale_specs]
        operands += [
            *[k_scale.astype(jnp.float32)[:, :, :, None, :]] * P,
            *[v_scale.astype(jnp.float32)[:, :, :, None, :]] * P,
        ]

    out, lse = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, n_entries=P, row_steps=NS, kvh=KVH,
            tg8=TG8,
            t_tokens=t_tokens, group=group, quantized=quantized,
            v_width=v_width, windowed=windowed,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 + windowed,
            grid=(n_steps,),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, KVH, TG8, dv), row_map),
                pl.BlockSpec((1, KVH, TG8, _LANES), row_map),
            ),
            scratch_shapes=[
                pltpu.VMEM((KVH * TG8, _LANES), jnp.float32),
                pltpu.VMEM((KVH * TG8, _LANES), jnp.float32),
                pltpu.VMEM((KVH * TG8, dv), jnp.float32),
            ],
        ),
        out_shape=(
            # fp32: the caller's new-token merge rescales this by
            # exp(lse - m_tot) and divides by the joint denominator — a
            # bf16 round HERE is one more rounding than the gathered
            # path's single joint softmax takes, and it measurably
            # widens the T=1-vs-T=G+1 numerical gap that flips greedy
            # argmax at near-ties (speculative self-draft acceptance).
            # Decode-sized output: the extra bytes are noise.
            jax.ShapeDtypeStruct((B, KVH, TG8, dv), jnp.float32),
            jax.ShapeDtypeStruct((B, KVH, TG8, _LANES), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(fetch, flags, src, q_pos, layer_arr,
      *((jnp.asarray(window, jnp.int32).reshape(1),) if windowed else ()),
      *operands)
    return out[:, :, :TG, :], lse[:, :, :TG, 0]


def paged_decode_attention(
    q: jnp.ndarray,        # [B, T, H, d] — this step's queries
    k_new: jnp.ndarray,    # [B, T, KVH, d] — this step's projections
    v_new: jnp.ndarray,    # [B, T, KVH, d]
    k_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d] (or [KVH, NB, BLK, d])
    v_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d]
    pool_pos: jnp.ndarray,  # [NB, BLK]
    table: jnp.ndarray,    # [B, MB]
    q_pos: jnp.ndarray,    # [B] FIRST token's position (-1 = inactive row)
    k_scale: Optional[jnp.ndarray] = None,  # [L, KVH, NB, BLK] (int8 pool)
    v_scale: Optional[jnp.ndarray] = None,
    layer: Optional[jnp.ndarray] = None,    # int32 index into L
    interpret: Optional[bool] = None,
    v_width: int = 0,
    scale: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    plan=None,
) -> jnp.ndarray:
    """One decode step of attention over (pool blocks ∪ the step's T new
    slots).

    ``window`` (an int32 value) / ``plan``: see ``paged_pool_attention``;
    the step's own slots are masked by the same window.  One program, not
    the mesh's, as the latent form.

    Latent attention (``v_width`` > 0): ``v_new`` and ``v_pool`` are None,
    every slot's value is the first ``v_width`` columns of its key row, and
    the result is ``[B, T, H, v_width]``; ``scale`` is the published score
    scale (the row's width is not the head size).  One program, not the
    mesh's: the latent block is refused under a sharded mesh.

    The pool pass runs in the Pallas kernel (T consecutive-position
    queries per row share ONE pool sweep — the speculative-verify shape);
    the step's own T tokens (token t attends new slots j <= t, plus
    itself) merge at the softmax level outside, keeping the pool
    immutable through the layer scan (same append-free contract as
    ``sdpa_cached``; the new tokens' K/V enter the merge at full
    precision, also matching sdpa_cached — only POOL reads see int8).
    Token t's position is ``q_pos + t`` for active rows (consecutive —
    the T>1 kernel's contract).  Returns [B, T, H, d].
    """
    B, T, H, d = q.shape
    KVH = k_new.shape[2]

    # Tensor/data-parallel serving: a pallas_call is not partitioned by
    # GSPMD, so under an active mesh the whole op runs per-shard inside
    # shard_map — KV heads split over "tensor" (the head layout
    # h = kvh*G + g makes contiguous H chunks == contiguous KVH chunks),
    # rows over the batch axes ("data", "fsdp") — the same pair the
    # model's `constrain` shards batch over, so an fsdp-only mesh also
    # routes through shard_map rather than leaving a GSPMD-sharded
    # pallas_call.  The pool shards on its leading KVH axis; the table
    # and q_pos shard with the rows; only pool_pos is replicated.  No
    # collectives are needed: every (row, kv head) pair is independent;
    # the caller's o-projection all-reduce (GSPMD) recombines heads
    # exactly as on the xla path.
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None and not v_width and window is None and plan is None:
        from jax.sharding import PartitionSpec as P

        tp = mesh.shape.get("tensor", 1)
        row_axes = tuple(
            a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1
        )
        rp = int(np.prod([mesh.shape[a] for a in row_axes])) if row_axes else 1
        if tp > 1 or rp > 1:
            if KVH % tp != 0 or B % rp != 0:
                raise NotImplementedError(
                    f"paged kernel sharding needs kv_heads % tensor == 0 "
                    f"and n_slots % (data*fsdp) == 0 (got KVH={KVH}, "
                    f"tp={tp}, B={B}, rows={rp}); use a compatible mesh "
                    f"or the gathered-view path"
                )
            rows = row_axes if row_axes else None
            tens = "tensor" if tp > 1 else None
            head4 = P(rows, None, tens, None)
            pooled = (
                P(None, tens, None, None, None) if k_pool.ndim == 5
                else P(tens, None, None, None)
            )
            scale_spec = (
                P(None, tens, None, None) if k_pool.ndim == 5
                else P(tens, None, None)
            )
            layer_op = (
                jnp.zeros((), jnp.int32) if layer is None
                else jnp.asarray(layer, jnp.int32).reshape(())
            )
            args = [
                q, k_new, v_new, k_pool, v_pool, pool_pos, table, q_pos,
                layer_op,
            ]
            in_specs = [
                head4, head4, head4, pooled, pooled, P(None, None),
                P(rows, None), P(rows), P(),
            ]
            if k_scale is not None:
                args += [k_scale, v_scale]
                in_specs += [scale_spec, scale_spec]

            def body(q, k_new, v_new, k_pool, v_pool, pool_pos, table,
                     q_pos, layer, k_scale=None, v_scale=None):
                return _paged_decode_local(
                    q, k_new, v_new, k_pool, v_pool, pool_pos, table,
                    q_pos, k_scale, v_scale, layer, interpret,
                )

            fn = jax.shard_map(
                body, mesh=mesh, in_specs=tuple(in_specs),
                out_specs=head4, check_vma=False,
            )
            return fn(*args)

    return _paged_decode_local(
        q, k_new, v_new, k_pool, v_pool, pool_pos, table, q_pos,
        k_scale, v_scale, layer, interpret, v_width, scale, window, plan,
    )


def _paged_decode_local(
    q, k_new, v_new, k_pool, v_pool, pool_pos, table, q_pos,
    k_scale, v_scale, layer, interpret, v_width=0, scale=None,
    window=None, plan=None,
):
    """Single-shard body of ``paged_decode_attention`` (also the whole op
    when no mesh is active)."""
    B, T, H, d = q.shape
    KVH = k_new.shape[2]
    G = H // KVH
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if v_width:
        v_new = k_new[..., :v_width]
    dv = v_width or d

    # Head layout h = kvh * G + g (same contract as flash GQA packing);
    # kernel sublane packing r = t*G + g.
    q5 = q.reshape(B, T, KVH, G, d)
    qg = jnp.swapaxes(q5, 1, 2).reshape(B, KVH, T * G, d)
    out_pool, lse = paged_pool_attention(
        qg, k_pool, v_pool, pool_pos, table, q_pos,
        k_scale=k_scale, v_scale=v_scale, t_tokens=T, layer=layer,
        interpret=interpret, v_width=v_width, scale=scale,
        **({} if window is None and plan is None
           else {"window": window, "plan": plan}),
    )
    out_pool = out_pool.reshape(B, KVH, T, G, dv)
    lse = lse.reshape(B, KVH, T, G)

    # New-slot scores [B, KVH, T, G, T]: token t attends the step's own
    # slots j <= t (a token may attend itself; positions are consecutive
    # so j <= t IS the positional mask).
    s_new = jnp.einsum(
        "btkgd,bjkd->bktgj", q5, k_new,
        preferred_element_type=jnp.float32,
    ) * scale
    t_idx = jnp.arange(T, dtype=jnp.int32)
    causal = t_idx[:, None] >= t_idx[None, :]  # [T(t), T(j)]
    if window is not None:
        causal = causal & (t_idx[:, None] - t_idx[None, :] < window)
    s_new = jnp.where(causal[None, None, :, None, :], s_new, MASK_VALUE)

    m_tot = jnp.maximum(lse, jnp.max(s_new, axis=-1))  # [B, KVH, T, G]
    w_pool = jnp.exp(lse - m_tot)
    p_new = jnp.exp(s_new - m_tot[..., None])          # [B, KVH, T, G, T]
    p_new = jnp.where(causal[None, None, :, None, :], p_new, 0.0)
    denom = w_pool + jnp.sum(p_new, axis=-1)
    new_contrib = jnp.einsum(
        "bktgj,bjkd->bktgd", p_new, v_new.astype(jnp.float32),
    )
    out = (
        out_pool.astype(jnp.float32) * w_pool[..., None] + new_contrib
    ) / denom[..., None]
    out = jnp.swapaxes(out, 1, 2).reshape(B, T, H, dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention over SELECTED slots of the pool (learned sparse attention,
# models/dsa_moe.py): no kernel.  A row attends `topk` slots wherever they
# lie in its blocks, so what is read is a gather of that many K and V rows
# from the pool — 2,048 x 2 KiB a row a layer whatever the context —
# and the table and the window bound no longer decide alone what a decode
# iteration reads.
# ---------------------------------------------------------------------------

def paged_rows(plane: jnp.ndarray, table: jnp.ndarray, layer) -> jnp.ndarray:
    """One layer's rows of a ONE-head pool plane [L, 1, NB, BLK, d] for each
    table row [B, MB], in sequence order: [B, MB * BLK, d].  An unused table
    entry (the sentinel NB) reads a real block; the caller masks by
    position."""
    L, _, NB, BLK, d = plane.shape
    ids = layer * NB + jnp.minimum(table, NB - 1)
    got = jnp.take(plane.reshape(L * NB, BLK, d), ids, axis=0)
    return got.reshape(table.shape[0], -1, d)


def paged_slot_positions(pool_pos: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """[B, MB * BLK] int32 position of each slot of each table row, -1 for
    an empty slot and for every slot of an unused table entry."""
    NB, BLK = pool_pos.shape
    pos = jnp.take(pool_pos, jnp.minimum(table, NB - 1), axis=0)
    pos = jnp.where((table < NB)[:, :, None], pos, -1)
    return pos.reshape(table.shape[0], -1)


def paged_sparse_attention(
    q: jnp.ndarray,           # [B, T, H, hd]
    k_new: jnp.ndarray,       # [B, T, KVH, hd] this step's own keys
    v_new: jnp.ndarray,
    chosen: jnp.ndarray,      # [B, T, k] int32 candidate ids (see below)
    chosen_live: jnp.ndarray,  # [B, T, k] bool
    k_pool: jnp.ndarray,      # [L, 1, NB, BLK, KVH * hd]: a token's heads in one row
    v_pool: jnp.ndarray,
    table: jnp.ndarray,       # [B, MB]
    layer,
) -> jnp.ndarray:
    """softmax(q . K_chosen / sqrt(hd)) V_chosen, [B, T, H, hd].  A
    candidate id below MB * BLK is that slot of the row's table (sequence
    order); MB * BLK + j is the step's own token j.  Dead choices (a row
    with fewer live candidates than k) carry no weight."""
    B, T, H, hd = q.shape
    L, _, NB, BLK, width = k_pool.shape
    KVH = width // hd
    S = table.shape[1] * BLK
    G = H // KVH
    own = chosen >= S
    slot = jnp.minimum(chosen, S - 1)
    blk = jnp.take_along_axis(
        jnp.minimum(table, NB - 1)[:, None, :], slot // BLK, axis=2)
    ids = (layer * NB + blk) * BLK + slot % BLK                   # [B, T, k]
    j = jnp.clip(chosen - S, 0, T - 1)[..., None]

    def rows(pool, new):
        got = jnp.take(pool.reshape(L * NB * BLK, width), ids, axis=0)
        mine = jnp.take_along_axis(new.reshape(B, 1, T, width), j, axis=2)
        got = jnp.where(own[..., None], mine.astype(got.dtype), got)
        return got.reshape(B, T, -1, KVH, hd)

    kc, vc = rows(k_pool, k_new), rows(v_pool, v_new)
    qg = q.reshape(B, T, KVH, G, hd)
    s = jnp.einsum("btcgd,btkcd->btcgk", qg, kc.astype(q.dtype),
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    s = jnp.where(chosen_live[:, :, None, None, :], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("btcgk,btkcd->btcgd", p, vc.astype(q.dtype))
    return out.reshape(B, T, H, hd)
