"""Continuous batching over a paged (block-table) KV cache.

Beyond the reference's capability surface (its only serving mode is one
batch of same-length prompts through `LLaMA.generate`, reference
``generation.py:22-45``) — a production decode loop where requests enter
and leave a fixed pool of batch slots independently, vLLM-style, so the
TPU never idles waiting for the longest generation in a batch.

TPU-native mechanics:
  * **Static shapes everywhere.**  The pool is ``n_slots`` rows; every
    decode step is one jitted [B=n_slots, T=1] forward.  A burst of k
    admissible requests is admitted as ONE [k', Pmax] batched prefill
    (k' = k rounded to a power of two with inactive pad rows, Pmax the
    group's max block-padded prompt length), so the jit cache holds
    O(log2(n_slots) · max_len / block_size) prefill programs + 1 decode
    program, and a k-request burst pays one dispatch instead of k.
  * **Paged KV.**  KV lives in a pool of fixed-size blocks
    ([L, KVH, n_blocks, block_size, hd], KV-head-major — the paged
    kernel's layout); each slot holds a block table
    (physical block ids in sequence order).  Admission *reserves* the
    blocks a request can ever need (ceil((prompt_padded + max_new) /
    block_size)); completion frees them.  The pool may be sized smaller
    than n_slots × max_len (overcommit): requests whose reservation does
    not fit wait in the queue, giving natural backpressure instead of the
    per-slot contiguous regions + power-of-two bucketing this replaces.
  * **Decode via the Pallas paged-attention kernel.**  Each step runs
    ``models.paged_forward``: the kernel's BlockSpec index maps chase the
    block table directly (scalar prefetch), so the pool is read ONCE per
    step and no contiguous view is ever materialized (int8 pools fold
    their dequant scales in-kernel).  Speculative rounds run the same
    kernel, always at the verify shape: every draft-chain step replays
    the growing block through one T = n_draft+1 multi-token pass over
    the base pool, and the verify is one more.  A gathered-view
    fallback (per-row virtually-contiguous cache + the model's
    per-row-offset forward) remains for kernel-incompatible meshes
    (kv_heads % tensor != 0, n_slots % (data*fsdp) != 0, or active
    seq/stage axes) and non-8-multiple block sizes.
  * **Per-request sampling.**  temperature/top-p/top-k and the PRNG
    chain are per-slot device arrays; each row samples with its own key
    (same warp math as ``ops.sampling.sample``, dynamic per-row), so a
    slot reproduces exactly what a standalone seeded ``engine.generate``
    of its request would emit.
  * **Idle slots cost nothing semantically**: their gathered positions
    are -1 (masked), their sampled token is ignored by the host, and
    their cache write-back is dropped (sentinel block id, scatter mode
    "drop").
  * **Chunked decode (Orca-style iteration batching).**  The
    non-speculative step fuses K <= ``decode_chunk`` decode
    iterations into ONE jitted ``lax.scan`` program
    (``_paged_decode_chunk``): stop-token sets, per-row max_new budgets
    and the non-finite -1 sentinel are evaluated ON DEVICE (finished
    rows fold out of the active mask mid-chunk — they stop attending and
    writing), and the host gets the whole [B, K] token block (+ bitcast
    [B, K] logprobs when enabled) back in ONE ``np.asarray``.  Batcher
    state (block table, fills, positions, active mask, sampling
    policies, budgets, stop sets) is device-resident: admission / free /
    cancel mark rows dirty and one ``_scatter_rows`` dispatch over one
    packed host matrix (``pack_rows``) syncs them
    before the next chunk — steady-state decode performs zero
    host->device state uploads and one device->host fetch per K tokens
    per slot.  K adapts (1 right after a classic admission; while
    requests queue, clamped to 4 on a plain decode dispatch, where they
    wait for a slot, and to 2 on one that carries a prompt chunk, where
    they wait for the prefill lane; pow2 up to ``decode_chunk`` once the
    queue is empty) so admission latency and
    time-to-first-token match the K=1 loop while saturated load keeps
    amortizing dispatches.  Chunked output is
    token-identical to K=1 under greedy and seeded sampling — per-row
    key chains split once per iteration exactly as one K=1 dispatch
    would (pinned by tests/test_serving_chunked.py).
  * **Chunked speculative serving.**  The speculative path gets the
    same treatment: up to ``spec_rounds`` draft+verify rounds fuse
    into ONE jitted ``lax.scan`` program (``_spec_rounds_chunk``, each
    iteration one ``_spec_round_core``), with the per-round host work
    on device — the pending-tau emit, the accepted-prefix emit scan
    with stop-token / max_new / non-finite folding
    (``spec_decode.accepted_emit_counts``), the fill rewind to
    ``+acc+1`` after each verify, and mid-chunk fold-out of finished
    rows.  Host-boundary accounting: ONE packed [B, R, G+2(+G+1)]
    fetch per R rounds and zero steady-state uploads — both the target
    and draft pools and all per-slot decode state are device-resident
    via the same ``d_*`` twins / dirty-row ``_scatter_rows`` sync the
    plain chunked path uses.  R adapts exactly like K (1 after an
    admission, clamped while capacity-blocked, pow2 up to
    ``spec_rounds``; ``spec_rounds=1`` is the same program at one
    round a dispatch), and output is token-identical at every R and to
    the standalone ``spec_decode.generate_speculative`` — including
    the acceptance pattern and per-token logprobs (pinned by
    tests/test_serving_spec.py).
  * **Fused prefill-decode scheduling (stall-free admission).**  With
    ``prefill_budget`` > 0 (run.py
    ``--prefill-budget``, on by default there) the batched-prefill
    bullet above only describes the COLD pool: once any row is
    mid-decode, an admission no longer runs as a separate whole-prompt
    dispatch at a step boundary — it moves through queued ->
    prefilling(offset) -> decoding, advancing up to ``prefill_budget``
    prompt tokens per chunk dispatch INSIDE ``_fused_chunk`` (the
    K-iteration decode scan plus one bounded prefill chunk over the
    row's gathered view: flash when the chunk exceeds 8 tokens,
    gathered-XLA as the quarantine fallback; prefix-cache hit rows
    start their chunk walk at fill0).  At most one admission is in
    flight; its row rides the scan masked until the dispatch its last
    prompt chunk lands, where it samples its first token (one key
    split, exactly the classic insert's) and folds INTO the decode
    mask mid-dispatch — first token out of the same dispatch.  For
    the dense block and the two with a recurrent state, over the paged
    kernel, the dispatch is a hybrid batch in
    Sarathi's sense: the chunk's tokens and the decode rows' first
    iteration go through ONE pass over the weights (``_mixed_pass``,
    ``models.llama.mixed_forward``; K passes a dispatch, not K + 1),
    and a row that folds in emits from the second iteration on.  The
    two blocks with routed experts run the chunk's pass and then the K
    iterations' (ROADMAP A1 (e) ports the pass to them).  Host
    boundary: the whole prefill pays ONE admission-time upload (the
    dirty-row sync + the one-off suffix/walk-scalar buffers) and the
    usual one packed fetch per chunk — no per-prefill-chunk host
    syncs; decode rows never stall and ``_pick_chunk`` no longer
    collapses K to 1 on (fused) admissions.  Output is token- and
    logprob-identical to the classic admit-then-decode path (pinned by
    tests/test_serving_fused.py; on int8-KV pools the oracle is the
    classic path at the SAME prefill chunking — chunk boundaries
    decide where prompt KV quantizes, so identity to a single-shot
    classic prefill holds only up to quantization noise there);
    ``prefill_budget=0`` (the ctor default) and speculative batchers
    keep classic admission everywhere.
  * **KV capacity: radix prefix index + host-DRAM block tier**
    (``kvcache.py``).  The prefix cache's index is a block-granular
    radix/trie over token chains (``prefix_cache=False`` disables
    matching and retention): an admission
    claims the longest shared block prefix across ALL cached chains,
    divergent chains share their common prefix nodes by construction,
    and eviction is leaves-first.  With ``host_kv_blocks`` > 0 cold
    (refcount-0, LRU-expired) blocks demote INTO a bounded host-DRAM
    tier instead of being freed, staying matchable; admitting a
    session whose matched prefix includes demoted blocks parks it in
    a new ``restoring`` state: the slabs ``jax.device_put`` into
    staging buffers (async H2D, deliberately OFF the pool's
    dependency chain so in-flight decode chunks never wait on PCIe),
    readiness is polled non-blockingly at step boundaries, and one
    jitted scatter (``kvcache.adopt_into_pool`` — the block-migration
    generalization of the dirty-row ``_scatter_rows`` sync) lands the
    blocks before the session admits as a plain prefix hit.  Host
    boundary of the swap path: demotion pays one D2H slab fetch per
    evicted block (admission-time, off the decode hot path; counted
    in ``swap_out_blocks_total``, never in ``host_syncs_total``),
    swap-in pays one async H2D staging transfer + one adoption
    dispatch per restored session and ZERO per-chunk traffic — decode
    rows never stall while a swap-in is in flight, and a restored
    admission pays the same ≤ 1 dirty-row state upload as any fused
    admission (asserted by ``make perf-smoke``).  A swap-in failure
    (fault site ``kv_swap``) fails only the restoring request with
    its blocks unpinned; the index/tier rebuild empty on crash
    recovery and replayed requests re-prefill cold, token-identically.
  * **Serving-mesh sharding** (``parallel/serve_mesh.py``; run.py
    ``--serve-mesh dp,tp``).  On a data x tensor serving mesh inside
    the placement envelope (tensor divides KV heads, data*fsdp
    divides ``n_slots``, no seq/stage axes) the batcher places its
    state SHARDED at construction — the KV pool(s) split their
    KV-head axis over ``tensor`` (the paged kernel's own shard_map
    layout), the per-slot device twins split rows over the batch
    axes — and every chunk program re-constrains its outputs to the
    same specs, so each donated leaf aliases shard-locally from the
    first dispatch (no per-dispatch GSPMD reshard, no silent
    donation copy; proven per program by the lowering auditor's mesh
    pass).  Host boundary under sharding: the packed per-chunk fetch
    is replicated-out (one [1-2, B, K] block regardless of mesh
    size — ``np.asarray`` gathers the addressable shards), dirty-row
    ``_scatter_rows`` uploads are one small host matrix GSPMD scatters
    to the row shards, and host-tier swap slabs stage PRE-SHARDED with
    the pool's layout (``kvcache.stage_restore`` placements) so the
    adoption scatter is shard-local.  The radix prefix index stays
    host-global: block ids are global, only the KV-head slice
    differs per shard.  Sharded chunk output is token-identical to
    single-chip (logprobs to cross-shard-reduction tolerance),
    pinned by tests/test_serve_mesh.py.  Data parallelism ACROSS
    batchers — replica routing, health-driven re-route, and the
    prefill/decode disaggregation handoff (``export_prefix`` /
    ``import_prefix``: the host-tier fetch/adopt primitives pointed
    across replicas) — lives in ``router.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .config import LLaMAConfig
from .engine import (
    finite_rows, pow2_bucket, prompt_positions, window_positions,
)
from .faults import FaultInjector, InjectedFault
from .kvcache import (
    MatchResult,
    adopt_into_pool,
    fetch_slab,
    make_prefix_store,
    pool_block_bytes,
    restore_ready,
    stage_restore,
)
from . import obs as _obs_mod
from .obs import Observability
from .models.llama import (
    FLASH_MIN_SEQ,
    KVCache,
    PagedKVCache,
    forward,
    init_cache,
    init_state,
    lm_head_logits,
    mixed_forward,
    cache_stats_zero,
    paged_pool_write,
    paged_pool_write_blocks,
    paged_write_indices,
)
from .models.mla_moe import ctx_tiles
from .ops.moe import STATS as _MOE_STATS
from .ops.mhc import STATS as _HC_STATS
from .models.afmoe import ATTN_STATS as _ATTN_STATS
from .models.dsa_moe import SELECT_STATS as _SELECT_STATS
from .ops.attention import NEG_INF
from .ops.sampling import stop_token_hits
from .parallel.mesh import use_mesh
from .parallel import serve_mesh as smesh
from .router import chain_keys as _router_chain_keys
from .spec_decode import (
    accepted_emit_counts,
    draft_categorical,
    leviathan_verify,
    place_extra,
)


# ---------------------------------------------------------------------------
# Paged KV pool
# ---------------------------------------------------------------------------

@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v", "pos", "k_scale", "v_scale", "stats",
                 "conv", "ssm", "snap_conv", "snap_ssm", "idx"],
    meta_fields=[],
)
@dataclasses.dataclass
class BlockPool:
    """Paged KV storage shared by all slots.

    k, v: [L, KVH, n_blocks, block_size, hd] (activation dtype or int8) —
          KV-head-major, the Pallas paged-attention kernel's layout (one
          (head, block) tile is a clean (block_size, hd) VMEM page).
    pos:  [n_blocks, block_size] int32 absolute position per cache slot;
          -1 marks invalid (free block / unwritten / rolled back).
    k_scale, v_scale: [L, KVH, n_blocks, block_size] fp32 (int8 pool only).

    A pool is described by the planes it has (``_PLANES``; a field that is
    None is a plane the pool lacks).  Latent attention keeps ONE: ``k`` is
    [L, 1, n_blocks, block_size, kv_lora_rank + qk_rope_head_dim] — the
    normed latent beside the rotated shared key, nothing per head — and
    ``v`` is None.  The allocator, the block tables and the prefix store
    see blocks, not planes, and are the same for both.
    stats: [ops.moe.N_STATS] int32 routing counts since the last packed
          fetch took them (routed-expert configurations only).

    ... and by the per-SLOT state beside them.  Recurrent state layers
    (models/sambay.py) keep planes for the ``config.cache_layers`` layers
    that own keys, and ``conv`` [Ls, n_slots, 3 * Di] / ``ssm`` [Ls, n_slots,
    N, Di] float32: a slot's recurrent state, fixed-size, never paged,
    advanced by its row's live tokens only.  ``snap_conv`` / ``snap_ssm``
    [Ls, n_snap, ...] are the snapshot pool: a row's state copied out at a
    block boundary of its prompt under an id the prefix store hangs on that
    block's radix node, copied back in by a prefix hit that ends there.
    None for every other block.

    Learned sparse attention (models/dsa_moe.py) keeps a third plane:
    ``idx`` [L, 1, n_blocks, block_size, index_head_dim], the indexer's one
    key a token a layer, under the same block table as ``k`` and ``v``: a
    cached prefix block brings its index keys, and every program that writes
    K/V writes them (they are one of ``_PLANES``).  None for every other.
    """

    k: jnp.ndarray
    v: Optional[jnp.ndarray]
    pos: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    stats: Optional[jnp.ndarray] = None
    conv: Optional[jnp.ndarray] = None
    ssm: Optional[jnp.ndarray] = None
    snap_conv: Optional[jnp.ndarray] = None
    snap_ssm: Optional[jnp.ndarray] = None
    idx: Optional[jnp.ndarray] = None

    @property
    def n_blocks(self) -> int:
        return self.k.shape[2]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def init_pool(
    config: LLaMAConfig, n_blocks: int, block_size: int,
    n_slots: int = 0, n_snapshots: int = 0,
) -> BlockPool:
    """``n_slots`` / ``n_snapshots`` size the per-slot state and the
    snapshot pool of a configuration with recurrent state layers (which
    needs the first); every other pool has neither."""
    config.validate()
    int8_kv = config.kv_cache_dtype == "int8"
    dtype = jnp.int8 if int8_kv else config.activation_dtype
    shape = (
        config.cache_layers, config.cache_heads, n_blocks, block_size,
        config.cache_width,
    )
    latent = config.latent_attention
    state = {}
    if config.recurrent_state:
        if n_slots <= 0:
            raise ValueError(
                "a pool for recurrent state layers needs n_slots > 0")
        state["conv"], state["ssm"] = init_state(config, n_slots)
        state["snap_conv"], state["snap_ssm"] = init_state(
            config, max(1, n_snapshots))
    if config.sparse_attention:
        state["idx"] = jnp.zeros(
            (shape[0], 1) + shape[2:4] + (config.index_head_dim,), dtype)
    return BlockPool(
        **state,
        k=jnp.zeros(shape, dtype=dtype),
        v=None if latent else jnp.zeros(shape, dtype=dtype),
        pos=jnp.full((n_blocks, block_size), -1, jnp.int32),
        k_scale=jnp.zeros(shape[:-1], jnp.float32) if int8_kv else None,
        v_scale=jnp.zeros(shape[:-1], jnp.float32) if int8_kv else None,
        stats=cache_stats_zero(config),
    )


# The per-(layer, head) planes a pool may have; ``pos`` is per block only.
_PLANES = ("k", "v", "k_scale", "v_scale", "idx")
# State snapshots a slot (recurrent state layers): the snapshot pool holds
# this many times n_slots, but no more bytes than the K/V pool beside it
# (``snapshot_pool_size``).
_SNAPSHOTS_PER_SLOT = 8


def snapshot_pool_size(
    config: LLaMAConfig, n_slots: int, n_blocks: int, block_size: int,
) -> int:
    """How many state snapshots the radix store gets: eight a slot (a
    4,096-token row at a 512-token chunk), but no more bytes than the K/V
    pool holds.  A snapshot of a per-channel state is a few MB and eight a
    slot stand (192 beside 24 slots and 4.5 GB of planes); one of a
    matrix-a-head state is tens of MB, and eight a slot would be four times
    the planes' bytes (256 x 25 MB beside 1.6 GB: 63 stand)."""
    # A block with a recurrent state keeps K and V planes in the activation
    # type (a latent or an int8 cache beside one is refused at `validate`).
    kv_bytes = (
        n_blocks * block_size * 2 * config.cache_layers * config.cache_heads
        * config.cache_width * config.activation_dtype.itemsize)
    return max(1, min(_SNAPSHOTS_PER_SLOT * n_slots,
                      kv_bytes // config.state_bytes_per_row))


def _map_planes(fn, pool_like, *others):
    """{name: fn(plane, *others' planes)} over the planes ``pool_like`` has
    (a BlockPool or a cache of the same field names)."""
    return {
        n: fn(getattr(pool_like, n), *(getattr(o, n) for o in others))
        for n in _PLANES if getattr(pool_like, n) is not None
    }


_STATE = ("conv", "ssm")


def _snapshot_rows(pool: BlockPool, ids: jnp.ndarray):
    """(conv, ssm) [Ls, k, ...] of the snapshots ``ids`` [k]; an id < 0 is
    no snapshot: the empty state a fresh prompt starts from."""
    def take(a):
        got = jnp.take(a, jnp.maximum(ids, 0), axis=1, mode="clip")
        live = (ids >= 0).reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.where(live, got, jnp.zeros_like(got))

    with jax.named_scope("state.move"):
        return take(pool.snap_conv), take(pool.snap_ssm)


def _state_into_rows(pool: BlockPool, view, rows: Optional[jnp.ndarray]):
    """{conv, ssm} of ``pool`` with the view's rows written back: every
    slot's (``rows`` None: the view IS the slots) or the slots ``rows``
    [k] (an index past the last slot drops: a pad row)."""
    if pool.conv is None:
        return {}
    if rows is None:
        return {"conv": view.conv, "ssm": view.ssm}
    with jax.named_scope("state.move"):
        return {
            n: getattr(pool, n).at[:, rows].set(getattr(view, n), mode="drop")
            for n in _STATE
        }


def _gather_cache(
    pool: BlockPool,
    table: jnp.ndarray,     # [B, MB] int32 physical block ids (NB = invalid)
    n_alloc: jnp.ndarray,   # [B] int32 allocated blocks per row
    fill: jnp.ndarray,      # [B] int32 per-row write offset (tokens)
    placed: bool = False,   # pin the view's KVH axis (serving mesh)
    state=None,             # (conv, ssm) of the B rows (recurrent layers)
) -> KVCache:
    """Materialize the per-row virtually-contiguous cache view.

    Out-of-range table entries (sentinel n_blocks) clip on gather; their
    positions are forced to -1 via n_alloc so the garbage is never
    attended.
    """
    L, KVH, NB, BLK, hd = pool.k.shape
    B, MB = table.shape
    # mode="clip": sentinel (out-of-range) table entries gather a real
    # block's finite values — the default "fill" mode would inject NaN,
    # which survives the additive -inf mask (NaN + -inf = NaN) and poisons
    # the softmax.  Clipped garbage is masked via n_alloc below.
    with jax.named_scope("cache.gather"):
        take = functools.partial(jnp.take, mode="clip")

        def g(a):  # [L, KVH, NB, BLK, ...] -> [L, B, MB*BLK, KVH, ...]
            out = take(a, table, axis=2)  # [L, KVH, B, MB, BLK, ...]
            out = out.reshape(a.shape[:2] + (B, MB * BLK) + a.shape[4:])
            return jnp.moveaxis(out, 1, 3)

        posg = take(pool.pos, table, axis=0).reshape(B, MB * BLK)
        valid = jnp.arange(MB, dtype=jnp.int32)[None, :] < n_alloc[:, None]
        posg = jnp.where(jnp.repeat(valid, BLK, axis=1), posg, -1)
        view = KVCache(
            **{"v": None, **_map_planes(g, pool)}, pos=posg, index=fill,
            stats=pool.stats, **dict(zip(_STATE, state or ())),
        )
    if placed:
        # Pin the gathered view to the pool's own KV-head sharding:
        # left unconstrained, GSPMD may satisfy the block gather by
        # REPLICATING the pool first — a full-pool all-gather inside
        # every scan iteration, which the comms-budget contracts
        # (analysis/comms.py) treat as a hard finding.
        view = smesh.constrain_view(view)
    return view


def _scatter_back(
    pool: BlockPool,
    view: KVCache,
    table: jnp.ndarray,
    fill: jnp.ndarray,
    active: jnp.ndarray,
    T: int,
) -> BlockPool:
    """Write the T new entries per row from the gathered view back into
    their physical blocks.  Inactive rows and out-of-reservation columns
    resolve to the sentinel block id and are dropped.  A view's recurrent
    state goes back whole: its rows are the slots.

    The PAIR form — B*T (block, offset) pairs through
    ``paged_pool_write`` — for the writers that are per token or per
    row: ``_chunk_scan`` (T=1), the speculative verify,
    ``_paged_suffix_insert``.  ``_fused_chunk``'s prompt chunk is whole
    blocks of one row and takes the block form, ``_land_chunk``, whose
    oracle this is (tests/test_serving_fused.py)."""
    NB, BLK = pool.pos.shape
    B, MB = table.shape
    with jax.named_scope("cache.land"):
        rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        # Shared write-back contract (same function paged_forward uses);
        # safe_cols is the matching clamped view column for each slot.
        blk, off, safe_cols = paged_write_indices(
            table, fill, active, T, NB, BLK
        )
        npos = view.pos[rows, safe_cols]       # [B, T]
        # view slices are [L, B, T, KVH, ...]; the pool wants KVH-major
        # ([L, KVH, B, T, ...]).  paged_pool_write = unrolled in-place
        # dynamic_update_slices; the batched scatter form forced four
        # full-pool layout copies per step (see its docstring).
        return dataclasses.replace(
            pool,
            **_map_planes(
                lambda plane, seen: paged_pool_write(
                    plane, jnp.moveaxis(seen[:, rows, safe_cols], 3, 1),
                    blk, off,
                ),
                pool, view,
            ),
            pos=paged_pool_write(pool.pos, npos, blk, off),
            stats=view.stats,
            **_state_into_rows(pool, view, None),
        )


def _land_chunk(
    pool: BlockPool,
    view: KVCache,
    table_r: jnp.ndarray,
    write_at: jnp.ndarray,
    C: int,
) -> BlockPool:
    """Write the C new columns of ONE row's gathered view, from the
    block-aligned column ``write_at``, back into the pool as ``C // BLK``
    whole blocks (``paged_pool_write_blocks``): the block form of
    ``_scatter_back(pool, view, table_r, write_at[None], ones, T=C)``
    and bit-equal to it, plane by plane.  ``_pf_chunk`` makes C whole
    blocks and keeps ``write_at + C`` inside the view; table entries
    past the row's reservation hold the sentinel and drop."""
    NB, BLK = pool.pos.shape
    with jax.named_scope("cache.land"):
        n = C // BLK
        col = write_at // BLK + jnp.arange(n, dtype=jnp.int32)
        blk = jnp.take(table_r[0], col, mode="fill", fill_value=NB)

        def land(plane, seen):
            # [L, 1, MB*BLK, KVH, ...] -> the chunk, [L, KVH, n, BLK, ...]
            new = lax.dynamic_slice_in_dim(seen[:, 0], write_at, C, axis=1)
            new = new.reshape(new.shape[:1] + (n, BLK) + new.shape[2:])
            return paged_pool_write_blocks(plane, jnp.moveaxis(new, 3, 1), blk)

        npos = lax.dynamic_slice_in_dim(
            view.pos[0], write_at, C).reshape(n, BLK)
        return dataclasses.replace(
            pool,
            **_map_planes(land, pool, view),
            pos=paged_pool_write_blocks(pool.pos, npos, blk),
            stats=view.stats,
        )


# ---------------------------------------------------------------------------
# Per-row sampling (dynamic policies)
# ---------------------------------------------------------------------------

def _warp_rows(
    logits: jnp.ndarray,       # [B, V] or [B, T, V]
    temperature: jnp.ndarray,  # [B] fp32 (> 0 rows meaningful)
    top_p: jnp.ndarray,        # [B] fp32; 1.0 = off
    top_k: jnp.ndarray,        # [B] int32; V (or 0) = off
) -> jnp.ndarray:
    """Per-row warped LOGITS — the single source of truth for the warp
    math shared by ``sample_rows`` (which draws from it) and
    ``warped_probs_rows`` (which softmaxes it).  Row-wise identical to
    ``ops.sampling``'s static filters: scale by temperature, threshold at
    the k-th largest, nucleus threshold (same tie handling).  The
    speculative bit-identity contract depends on every consumer warping
    through THIS function.
    """
    V = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    bshape = (logits.shape[0],) + (1,) * (lg.ndim - 1)
    t = jnp.maximum(temperature, 1e-6).reshape(bshape)
    scaled = lg / t
    # top-k: threshold at the k-th largest (k==V keeps everything, matching
    # the static filter's no-op when top_k is None).
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    k = jnp.clip(jnp.where(top_k <= 0, V, top_k), 1, V).reshape(bshape)
    kth = jnp.take_along_axis(
        sorted_desc, jnp.broadcast_to(k - 1, lg.shape[:-1] + (1,)), axis=-1
    )
    scaled = jnp.where(scaled >= kth, scaled, NEG_INF)
    # top-p: same construction as ops.sampling.top_p_filter, p per-row.
    sorted2 = jnp.sort(scaled, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p.reshape(bshape)
    thr = jnp.min(
        jnp.where(keep, sorted2, jnp.inf), axis=-1, keepdims=True
    )
    thr = jnp.minimum(thr, jnp.max(scaled, axis=-1, keepdims=True))
    nucleus = jnp.where(top_p.reshape(bshape) < 1.0, thr, -jnp.inf)
    return jnp.where(scaled >= nucleus, scaled, NEG_INF)


def sample_rows(
    keys: jnp.ndarray,         # [B, 2] uint32 PRNG keys (one per row)
    logits: jnp.ndarray,       # [B, V]
    temperature: jnp.ndarray,  # [B] fp32; 0 = greedy
    top_p: jnp.ndarray,        # [B] fp32; 1.0 = off
    top_k: jnp.ndarray,        # [B] int32; V (or 0) = off
) -> jnp.ndarray:
    """Per-row ``ops.sampling.sample`` with *traced* per-row policies.

    Applies the identical warp math (``_warp_rows``) row-wise so a row
    with policy (t, p, k) and its own key chain draws bit-identically to
    ``sample(key, row[None], t, p, k)``.
    """
    lg = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    scaled = _warp_rows(logits, temperature, top_p, top_k)
    sampled = jax.vmap(
        lambda key, row: jax.random.categorical(key, row)
    )(keys, scaled).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled)


def _split_rows(keys: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[B, 2] keys -> (carried [B, 2], subkeys [B, 2]) — the row-wise
    mirror of ``rng, sub = jax.random.split(rng)``."""
    out = jax.vmap(lambda key: jax.random.split(key))(keys)  # [B, 2, 2]
    return out[:, 0], out[:, 1]


def warped_probs_rows(
    logits: jnp.ndarray,       # [B, V] or [B, T, V]
    temperature: jnp.ndarray,  # [B] fp32 (> 0 rows meaningful)
    top_p: jnp.ndarray,        # [B] fp32; 1.0 = off
    top_k: jnp.ndarray,        # [B] int32; V (or 0) = off
) -> jnp.ndarray:
    """Per-row ``ops.sampling.warped_probs`` with *traced* policies.

    Identical warp math to ``sample_rows`` (shared ``_warp_rows``),
    returning the full post-warp distribution instead of a draw — the p
    and q of speculative accept/resample.  A row with policy (t, p, k)
    gets bit-identically ``warped_probs(row, t, p, k)``.
    """
    return jax.nn.softmax(
        _warp_rows(logits, temperature, top_p, top_k), axis=-1
    )


# ---------------------------------------------------------------------------
# Jitted step programs
# ---------------------------------------------------------------------------

def _kernel_eligible(block_size, mesh, kv_heads, n_rows, draft_config=None):
    """THE paged-kernel eligibility predicate, shared by the in-jit decode
    step and the host-side speculative gate so the two cannot drift:
    Mosaic's 8-sublane tiling on the block axis, and (under a mesh) KV
    heads dividing `tensor`, rows dividing data*fsdp, no seq/stage axes.
    """
    ok = block_size % 8 == 0
    if mesh is not None:
        rows = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        ok = ok and (
            kv_heads % mesh.shape.get("tensor", 1) == 0
            and n_rows % rows == 0
            and mesh.shape.get("seq", 1) == 1
            and mesh.shape.get("stage", 1) == 1
        )
        if draft_config is not None:
            ok = ok and (
                draft_config.kv_heads % mesh.shape.get("tensor", 1) == 0
            )
    return bool(ok)


def _mixed_pass(config, quantized_pool, mesh, use_kernel, n_iter) -> bool:
    """Whether a fused dispatch's first decode iteration rides its prompt
    chunk's pass over the weights (``_fused_chunk``) — shared by the
    program and the host's counter so the two cannot drift.  By the
    block: the dense one and the two with a recurrent state — a mixer
    beside attention in every layer, and mixer layers between attention
    layers — take it (``models.llama.mixed_forward``, which hands the
    two to ``models.falcon_h1.mixed_forward`` and
    ``models.sambay.mixed_forward``); the two with routed experts
    (latent attention, window attention layers) keep two passes until
    the mechanism is ported to them (ROADMAP A1 (e)).
    By the decode half: the paged kernel's (``use_kernel``: allowed and
    ``_kernel_eligible``).  And by what the trace sees of the operands:
    a float pool (an int8 pool quantizes a chunk where it lands, and the
    two halves would do so in two places), one device (the mixed
    activation is [1, C + B, D]: the riders have no batch axis to shard
    over "data"), and a second iteration for a row that folds in to emit
    its first token from this dispatch (K = 1 keeps chunk-then-emit)."""
    return bool(
        use_kernel and n_iter >= 2 and not quantized_pool
        and (mesh is None or mesh.size == 1)
        and not (config.latent_attention or config.windowed_attention
                 or config.sparse_attention)
    )


def _decode_step_core(
    params, pool, table, n_alloc, fill, tau, pos, active, keys,
    temperature, top_p, top_k, *, config, all_greedy, use_kernel,
    with_logprobs, placed=False,
):
    """One [n_slots, 1] decode iteration over the paged pool — the body
    of each ``lax.scan`` iteration of ``_chunk_scan`` (``_paged_decode_chunk``
    and the decode half of ``_fused_chunk``).

    tau: [B] current token per slot; pos: [B] its absolute position;
    active: [B] bool.  Inactive rows run masked (position -1, write-back
    dropped, sampled token ignored by the host).

    ``all_greedy`` is static: when every active slot is greedy the step
    compiles to a pure argmax — no sorts/softmax/key-splits on the hot
    path (the host flips to the sampling variant the moment a sampled
    request is admitted; greedy rows' key chains are never consumed, so
    skipping the split here is unobservable).

    Attention path (``use_kernel``, resolved by the caller): the Pallas
    paged kernel walks the block table in-kernel (pool read once per
    step; int8 pools fold their dequant scales in-kernel).  Under a mesh
    the op itself shard_maps over the tensor (KV heads) and data (rows)
    axes.  Fallbacks to the gathered contiguous view: block sizes that
    break Mosaic's 8-sublane tiling, and meshes the kernel sharding
    cannot cover (kv_heads % tensor != 0, n_slots % data != 0, or active
    seq/stage axes).

    Returns (next token [B] with the -1 non-finite sentinel folded in,
    its model logprob or None, carried keys, updated pool)."""
    positions = jnp.where(active, pos, -1)[:, None]
    if use_kernel:
        logits, pcache = forward(
            params, tau[:, None], positions, config,
            cache=_pool_as_cache(pool, table, fill),
            attn_mask=active[:, None],
        )
        pool = _cache_into_pool(pool, pcache)
    else:
        view = _gather_cache(
            pool, table, n_alloc, fill, placed=placed,
            state=None if pool.conv is None else (pool.conv, pool.ssm),
        )
        logits, view = forward(
            params, tau[:, None], positions, config, cache=view,
            attn_mask=active[:, None],
        )
        pool = _scatter_back(pool, view, table, fill, active, T=1)
    nxt, lp, keys = _sample_step(
        logits, keys, temperature, top_p, top_k,
        all_greedy=all_greedy, with_logprobs=with_logprobs,
    )
    return nxt, lp, keys, pool


def _sample_step(
    logits, keys, temperature, top_p, top_k, *, all_greedy, with_logprobs,
):
    """A decode iteration's draw from the last of its rows' ``logits``
    [B, T, V]: (next token [B] with the -1 non-finite sentinel folded in,
    its model logprob or None, carried keys).  Key chains split once an
    iteration for all B rows whatever their liveness (never under
    ``all_greedy``)."""
    with jax.named_scope("sample"):
        if all_greedy:
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        else:
            keys, subs = _split_rows(keys)
            nxt = sample_rows(subs, logits[:, -1], temperature, top_p, top_k)
        # with_logprobs is static (trace-time specialization, like
        # all_greedy): without it the fp32 [B, V] cast + logsumexp never
        # enter the compiled program.
        lp = _token_logprob(logits[:, -1], nxt) if with_logprobs else None
        # Non-finite guard: a row whose raw logits contain NaN/Inf gets
        # the -1 token sentinel instead of a draw from garbage; the host
        # emit scan fails just that request (tokens are never negative,
        # so the sentinel cannot collide).  Folding the flag into tau
        # keeps the guard free of extra device->host fetches.
        nxt = jnp.where(finite_rows(logits[:, -1]), nxt, -1)
        return nxt, lp, keys


def _admission_sample(
    logits_of, done, sub, temperature, top_p, top_k, *, all_greedy,
    with_logprobs,
):
    """The first token of ``_fused_chunk``'s admission, at the cost of what
    its result needs: (token [1] with the -1 non-finite sentinel folded in,
    its model logprob [1] or None).  ``logits_of()`` builds the [1, V]
    logits of the prompt's last token and is called only under ``done``
    (the prompt completes this dispatch), so a non-final chunk reads no
    head, sorts nothing, and returns zeros nobody reads.  A greedy
    admission is ``sample_rows``' own argmax and nothing else: the whole
    branch under static ``all_greedy`` (as in ``_sample_step``), else by
    the row's temperature as a value, so a greedy request admitted beside
    sampling rows skips the warp and the draw too.  ``sub`` is the row's
    subkey [1, 2]; the policies are the row's own, [1] each."""

    def greedy(logits):
        return jnp.argmax(
            logits.astype(jnp.float32), axis=-1
        ).astype(jnp.int32)

    def consume():
        logits = logits_of()
        if all_greedy:
            first = greedy(logits)
        else:
            first = lax.cond(
                temperature[0] <= 0.0, greedy,
                lambda lg: sample_rows(sub, lg, temperature, top_p, top_k),
                logits,
            )
        lp = _token_logprob(logits, first) if with_logprobs else None
        # Non-finite guard (see _paged_insert): the -1 sentinel rides
        # tau into the scan's emit, which fails just this request.
        return jnp.where(finite_rows(logits), first, -1), lp

    def skip():
        return (
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.float32) if with_logprobs else None,
        )

    with jax.named_scope("admit.sample"):
        return lax.cond(done, consume, skip)


# "No token emitted this chunk column" marker in the [B, K] token block
# (the row was already inactive).  Distinct from the -1 non-finite
# sentinel: real tokens are never negative, so both are unambiguous.
_CHUNK_PAD = -2

# What a fused dispatch's admission sample cost (``_admission_sample``):
# nothing on a non-final chunk, an argmax for a greedy request, the warp
# and the draw otherwise.  The dispatch record's ``first_sample``.
_FIRST_SAMPLE = ("skipped", "greedy", "drawn")


@functools.partial(
    jax.jit,
    static_argnames=(
        "config", "n_iter", "mesh", "all_greedy", "allow_kernel",
        "with_logprobs", "placed",
    ),
    donate_argnames=(
        "pool", "fill", "tau", "tau_lp", "pos", "active", "remaining",
        "keys",
    ),
)
def _paged_decode_chunk(
    params, pool, table, n_alloc, fill, tau, tau_lp, pos, active,
    remaining, stops, keys, temperature, top_p, top_k, *,
    config, n_iter, all_greedy=False, mesh=None, allow_kernel=True,
    with_logprobs=False, placed=False,
):
    """``n_iter`` fused decode iterations in ONE jitted program — the
    chunked-decode hot path.  Each ``lax.scan`` iteration replays the
    host's K=1 contract exactly, ON DEVICE:

      1. *emit* the pending token ``tau`` into the output block
         (column i), recording -1 for a non-finite-sentinel row and
         ``_CHUNK_PAD`` for rows that were already inactive;
      2. *stop-detect*: a row whose emitted token is in its stop set
         (``stops``, a [B, S] -1-padded per-row table) or whose
         ``remaining`` generation budget is exhausted (or whose tau
         carries the -1 sentinel) folds out of ``active`` — it stops
         attending and writing for the REST of the chunk, exactly as the
         host frees the slot before the next K=1 dispatch;
      3. run one ``_decode_step_core`` iteration for the surviving rows
         (same keys-split topology per iteration as one K=1 dispatch, so
         sampled streams are bit-identical) and advance fill/pos.

    The host touches the device once per CHUNK, not per token: the token
    block (and, under ``with_logprobs``, the per-token logprobs,
    bitcast to int32) comes back as ONE packed int32 array
    [1 or 2, B, n_iter], and all decode state (fill/pos/active/remaining/
    tau/tau_lp/keys + the pool) stays resident — returned as fresh
    donated buffers, never re-uploaded from numpy.

    Token-identity with K=1 (pinned by tests/test_serving_chunked.py):
    iteration i's sample sees exactly the state a K=1 dispatch sequence
    would have, and key chains split once per iteration regardless of
    liveness — the same [B]-wide split a K=1 dispatch performs.

    Iterations after every row has folded out run MASKED rather than
    being lax.cond-skipped: guarding a cached decode forward with a
    cond was measured to cost more than the wasted forward (the
    branch-merge forced full-cache relayout copies — see the engine
    while-loop's note, engine.py).  The host bounds the waste anyway:
    ``_pick_chunk`` clamps K to the largest remaining budget, so a
    fully-dead tail only arises from stop tokens landing early.
    """
    with use_mesh(mesh):
        # Sub-128 (narrow-lane) block sizes are verified compiled on
        # hardware — bf16 and int8 kernels match interpret mode exactly at
        # BLK 8/16/32/64/128 on a v5e chip (regression-tested in
        # tests/test_tpu_compiled.py).
        use_kernel = allow_kernel and _kernel_eligible(
            pool.block_size, mesh, config.kv_heads, tau.shape[0]
        )
        return _chunk_scan(
            params, pool, table, n_alloc, fill, tau, tau_lp, pos,
            active, remaining, stops, keys, temperature, top_p, top_k,
            config=config, n_iter=n_iter, all_greedy=all_greedy,
            use_kernel=use_kernel, with_logprobs=with_logprobs,
            placed=placed,
        )


def _emit(tau, tau_lp, active, remaining, stops):
    """The host emit scan, on device — steps 1 and 2 of an iteration
    (``_paged_decode_chunk``): (this column's tokens [B], their logprobs,
    ``active`` with the rows that just ended folded out, ``remaining``)."""
    with jax.named_scope("emit"):
        nonfinite = tau < 0
        hit_stop = stop_token_hits(tau, stops)
        out_tok = jnp.where(
            active,
            jnp.where(nonfinite, -1, tau),
            _CHUNK_PAD,
        ).astype(jnp.int32)
        out_lp = tau_lp
        done = active & (nonfinite | hit_stop | (remaining <= 1))
        remaining = remaining - active.astype(jnp.int32)
        active = active & ~done
        return out_tok, out_lp, active, remaining


def _advance(tau, tau_lp, fill, pos, active, nxt, lp):
    """Step 3's tail: the surviving rows take their draw (``lp`` None:
    no logprobs) and move one slot on."""
    with jax.named_scope("emit"):
        tau = jnp.where(active, nxt, tau)
        if lp is not None:
            tau_lp = jnp.where(active, lp, tau_lp)
        return tau, tau_lp, fill + active, pos + active


def _chunk_scan(
    params, pool, table, n_alloc, fill, tau, tau_lp, pos, active,
    remaining, stops, keys, temperature, top_p, top_k, *,
    config, n_iter, all_greedy, use_kernel, with_logprobs,
    placed=False, emitted=None,
):
    """The shared K-iteration fused decode scan — the body of
    ``_paged_decode_chunk`` AND the decode half of ``_fused_chunk`` (the
    fused prefill-decode program), factored out so the two cannot drift
    (the same discipline ``_decode_step_core`` enforces one level down).
    See ``_paged_decode_chunk``'s docstring for the full contract;
    callers resolve ``use_kernel`` and enter the mesh.  ``emitted``: the
    (tokens [B], logprobs [B]) column of an iteration the caller ran
    itself (``_fused_chunk``'s mixed pass); it goes first in the packed
    block, ahead of the scan's ``n_iter`` columns."""

    def body(carry, _):
        pool, tau, tau_lp, fill, pos, active, remaining, keys = carry
        out_tok, out_lp, active, remaining = _emit(
            tau, tau_lp, active, remaining, stops
        )
        # --- one decode iteration for the surviving rows ---
        nxt, lp, keys, pool = _decode_step_core(
            params, pool, table, n_alloc, fill, tau, pos, active,
            keys, temperature, top_p, top_k, config=config,
            all_greedy=all_greedy, use_kernel=use_kernel,
            with_logprobs=with_logprobs, placed=placed,
        )
        tau, tau_lp, fill, pos = _advance(
            tau, tau_lp, fill, pos, active, nxt, lp
        )
        return (
            (pool, tau, tau_lp, fill, pos, active, remaining, keys),
            (out_tok, out_lp),
        )

    # One lane for the scan and for the packing of what it emitted: every
    # operation of a decode dispatch, and of a fused one past its chunk.
    with jax.named_scope("lane.decode"):
        carry, (toks, lps) = lax.scan(
            body,
            (pool, tau, tau_lp, fill, pos, active, remaining, keys),
            None,
            length=n_iter,
        )
        pool, tau, tau_lp, fill, pos, active, remaining, keys = carry
        if emitted is not None:
            with jax.named_scope("emit"):
                toks, lps = (
                    jnp.concatenate([first[None], rest])
                    for first, rest in zip(emitted, (toks, lps))
                )
        # Serving-mesh placement (parallel/serve_mesh.py): pin the carried
        # state and pool outputs to their canonical shardings so the
        # donated inputs (placed the same way at construction) alias
        # shard-locally instead of resharding per dispatch.  ``placed``
        # is the CTOR's placement decision threaded through as a static
        # arg — every program a batcher dispatches constrains (or not)
        # consistently, so pool sharding can never ping-pong between an
        # insert and a chunk dispatch.  Trace-time no-op when False.
        if placed:
            (tau, tau_lp, fill, pos, active, remaining,
             keys) = smesh.constrain_rows(
                tau, tau_lp, fill, pos, active, remaining, keys
            )
            pool = smesh.constrain_pool(pool)
        with jax.named_scope("emit"):
            toks = jnp.swapaxes(toks, 0, 1)  # [B, K]
            if with_logprobs:
                # One packed transfer: fp32 logprobs ride bitcast to int32
                # alongside the tokens, so logprobs mode still pays exactly
                # one device->host fetch per chunk.
                lp_bits = lax.bitcast_convert_type(
                    jnp.swapaxes(lps, 0, 1).astype(jnp.float32), jnp.int32
                )
                packed = jnp.stack([toks, lp_bits])  # [2, B, K]
            else:
                packed = toks[None]  # [1, B, K]
        packed, pool = _pack_stats(packed, pool)
    return (
        packed, tau, tau_lp, fill, pos, active, remaining, keys, pool
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "config", "n_iter", "pf_chunk", "all_greedy", "mesh",
        "allow_kernel", "with_logprobs", "placed",
    ),
    donate_argnames=(
        "pool", "fill", "tau", "tau_lp", "pos", "active", "remaining",
        "keys", "pf_vec",
    ),
)
def _fused_chunk(
    params, pool, table, n_alloc, fill, tau, tau_lp, pos, active,
    remaining, stops, keys, temperature, top_p, top_k,
    pf_vec, pf_snap=None, *,
    config, n_iter, pf_chunk, all_greedy=False, mesh=None,
    allow_kernel=True, with_logprobs=False, placed=False,
):
    """The fused prefill-decode program: ONE jitted dispatch that
    advances up to ``pf_chunk`` prompt tokens of the single in-flight
    admission AND runs ``n_iter`` decode iterations — so admissions
    never stall decode (stall-free chunked prefill, piggybacked on the
    device-resident decode chunk).

    Two forms, chosen by ``_mixed_pass`` from what the trace sees.  The
    mixed pass (the dense block, or either block with a recurrent
    state — a mixer beside attention in every layer, or mixer layers
    between attention layers —, over the paged kernel, K >= 2): the
    first iteration's emit and stop-detect run ahead of the chunk, then
    its forward rides the chunk's pass over the weights — C prompt
    tokens and B decode tokens as one [1, C + B, D] activation, split
    for attention and for a mixer's recurrence, which are a row's own,
    and for nothing else (``models.llama.mixed_forward``) — one head
    product serves the chunk's last hidden state and the B rows, the
    rows draw their next tokens, and the scan below runs the other
    ``n_iter - 1`` iterations: ``n_iter`` passes a dispatch.  A row
    whose prompt completes folds in behind the mixed pass and emits its
    first token at the second iteration (column 1 of the packed block,
    a pad in column 0): still from THIS dispatch.  Everywhere else
    (the two blocks with routed experts: latent attention, and window
    attention layers; an int8 pool, a sharded mesh, the gathered
    fallback) the chunk runs as a forward of its own ahead of the scan
    (``n_iter + 1`` passes; a row that folds in emits from column 0),
    as the paragraphs below describe; K = 1 keeps that order so the
    completing dispatch still hands the first token over.

    Prefill half: the admitted row's gathered view is cut from the pool
    (``_gather_cache`` over its table row) with a SCALAR write index
    ``pf_base + pf_off`` — scalar, not per-row, so ``forward``'s "auto"
    resolution may run the Pallas flash kernel over the chunk
    (pf_chunk > 8) with the gathered XLA path as the quarantine/debug
    fallback; prefix-cache-hit rows start their chunk walk at
    fill0 = ``pf_base`` and attend the reused KV through the same view.
    The chunk's KV lands in the row's reserved blocks by whole blocks
    (``_land_chunk``; the bytes ``_scatter_back``'s pairs would write).
    The last prompt token's hidden state is gathered every chunk (O(D)),
    but its [1, V] head product and the first token's sample run only in
    the dispatch where ``pf_off + pf_chunk >= pf_len``, which CONSUMES
    them (``_admission_sample``, a ``lax.cond`` on that value under the
    scope ``admit.sample``; at 261,120 x 5,120 the head alone is 2.67 GB
    of reads): the row's key chain splits exactly once (the
    ``_paged_insert`` split the classic path performs), the first token
    is sampled with the row's own policy — an argmax and nothing else
    for a greedy request, by the static ``all_greedy`` or by the row's
    temperature as a value; the warp and the draw of ``sample_rows``
    otherwise — (non-finite guard folds the -1 sentinel exactly as
    admission does), and the row folds INTO the
    decode state mid-dispatch — active/fill/pos/tau/tau_lp/keys all
    flip on device — so the decode scan below emits its first sampled
    token from THIS dispatch, not a later one.  Non-final chunks read no
    head, draw nothing (the mixed pass's shared head product carries the
    chunk's row either way; only its draw is skipped) and leave the key
    chain untouched (``pf_key`` is the same two header words every
    dispatch, so the chain starts exactly where a classic
    ``_paged_insert`` of the request would).

    Decode half: the unchanged ``_chunk_scan`` (shared with
    ``_paged_decode_chunk``, so the fused program cannot drift from the
    plain one; the mixed pass's iteration is built from the same
    ``_emit`` and ``_sample_step``).  The prefilling row rides the scan
    masked (position -1, writes dropped) until its activation dispatch.

    Host boundary: identical to ``_paged_decode_chunk`` — ONE packed
    [1 or 2, B, K] fetch, zero steady-state uploads.  All prefill state
    stays resident in ``pf_vec``, the admission's ONE upload
    (``pack_prefill``: row, base, suffix length, the request key's two
    words and the walk's offset — zero at admission — in a
    ``_PF_HEADER``-long int32 header, the padded suffix tokens behind it;
    unpacked here at static offsets).  It is a donated carry: the program
    advances the offset word in place and hands the vector back, so a
    32-chunk 16k prefill costs zero per-chunk host->device transfers
    beyond the dispatch itself.

    Recurrent state layers (``pool.conv`` / ``pool.ssm``): the prefilling
    row's state enters the chunk and leaves it in its slot (behind a
    mixed pass too: the row's slot rides that pass masked and is
    written after it).  ``pf_snap``
    is int32 [2], a host operand of the call itself: the walk's FIRST
    chunk (``pf_off`` 0) starts from snapshot ``pf_snap[0]`` — the
    prefix hit's, or the empty state with id -1 — and a chunk's end state
    is copied into snapshot ``pf_snap[1]`` (-1: none; the host asks for
    one when the chunk ends on a block boundary of the prompt).  None for
    every other block.

    Returns ``_chunk_scan``'s tuple + ``pf_vec`` with its offset advanced.
    """
    with use_mesh(mesh):
        B = tau.shape[0]
        C = pf_chunk
        NB, BLK = pool.pos.shape
        # Everything the in-flight admission costs is one lane; the mixed
        # branch's shared pass opens its own inside it (a reader takes a
        # path's LAST lane), the decode scan its own below.
        with jax.named_scope("lane.chunk"):
            (pf_row, pf_base, pf_len, pf_key, pf_off,
             pf_toks) = _unpack_prefill(pf_vec)
            if pf_snap is not None:
                pf_snap_in, pf_snap_out = pf_snap[0], pf_snap[1]
            # ---- one bounded prefill chunk for the in-flight admission ----
            table_r = lax.dynamic_slice_in_dim(table, pf_row, 1, axis=0)
            n_alloc_r = lax.dynamic_slice_in_dim(n_alloc, pf_row, 1, axis=0)
            write_at = (pf_base + pf_off).astype(jnp.int32)
            state = None
            if pool.conv is not None:
                with jax.named_scope("state.move"):
                    state = tuple(
                        jnp.where(
                            pf_off == 0, start,
                            lax.dynamic_slice_in_dim(held, pf_row, 1, axis=1))
                        for start, held in zip(
                            _snapshot_rows(pool, pf_snap_in[None]),
                            (pool.conv, pool.ssm))
                    )
            view = _gather_cache(
                pool, table_r, n_alloc_r, write_at[None], placed=placed,
                state=state,
            )
            # Scalar index (ONE prefilling row): keeps the view off the
            # per-row-index must-xla path, so "auto" runs flash over the
            # chunk; the host-side _pf_chunk clamp guarantees
            # write_at + C <= MB * BLK (dynamic_update_slice would otherwise
            # clamp its start and scribble over the reused prefix KV — the
            # _suffix_pad hazard).
            view = dataclasses.replace(view, index=write_at)
            toks_c = lax.dynamic_slice_in_dim(pf_toks, pf_off, C)[None]
            positions, real = window_positions(pf_base, pf_off, C, pf_len)
            use_kernel = allow_kernel and _kernel_eligible(
                pool.block_size, mesh, config.kv_heads, B
            )
            mixed = _mixed_pass(
                config, pool.quantized, mesh, use_kernel, n_iter)
            emitted = None
            if mixed:
                with jax.named_scope("lane.mixed"):
                    # Iteration 1 of the decode scan, its forward merged
                    # into the chunk's: emit and stop-detect first (``_emit``
                    # does not depend on the chunk), then ONE pass over the
                    # weights for the chunk's C tokens and the B decode
                    # tokens, one head product over the chunk's last hidden
                    # state and the decode rows'.
                    *emitted, active, remaining = _emit(
                        tau, tau_lp, active, remaining, stops
                    )
                    hidden, view, pcache = mixed_forward(
                        params, toks_c, positions, config, view, real,
                        tau, jnp.where(active, pos, -1),
                        _pool_as_cache(pool, table, fill),
                    )
                    pool = _cache_into_pool(pool, pcache)
                    idx = pf_len - 1 - pf_off  # as below
                    h_last = jnp.take_along_axis(
                        hidden, jnp.clip(idx, 0, C - 1)[None, None, None],
                        axis=1,
                    )
                    logits = lm_head_logits(
                        params,
                        jnp.concatenate([h_last, hidden[:, C:]], axis=1),
                        config, normed=True,
                    )[0]

                    def logits_last():  # a row of the shared head product
                        return logits[:1]

                    nxt, lp, keys = _sample_step(
                        logits[1:, None], keys, temperature, top_p, top_k,
                        all_greedy=all_greedy, with_logprobs=with_logprobs,
                    )
                    tau, tau_lp, fill, pos = _advance(
                        tau, tau_lp, fill, pos, active, nxt, lp
                    )
            else:
                _, view, aux = forward(
                    params, toks_c, positions, config, cache=view,
                    attn_mask=real, compute_logits=False,
                    output_last_hidden=True,
                )
                idx = pf_len - 1 - pf_off  # in [0, C) iff the last chunk
                h_last = jnp.take_along_axis(
                    aux.last_hidden_state,
                    jnp.clip(idx, 0, C - 1)[None, None, None], axis=1,
                )[:, 0]

                def logits_last():
                    return lm_head_logits(
                        params, h_last[:, None], config, normed=True
                    )[:, 0]
            pool = _land_chunk(pool, view, table_r, write_at, C)
            if pool.conv is not None:
                # The chunk's end state into the row's slot, and into snapshot
                # ``pf_snap_out``; with no snapshot asked for, the slab at the
                # (clamped) id is written back as read.
                put = lax.dynamic_update_slice_in_dim
                with jax.named_scope("state.move"):
                    keep, at = pf_snap_out >= 0, jnp.maximum(pf_snap_out, 0)
                    pool = dataclasses.replace(
                        pool,
                        **{n: put(getattr(pool, n), getattr(view, n), pf_row,
                                  axis=1)
                           for n in _STATE},
                        **{"snap_" + n: put(
                            snaps,
                            jnp.where(
                                keep, getattr(view, n),
                                lax.dynamic_slice_in_dim(
                                    snaps, at, 1, axis=1)),
                            at, axis=1)
                           for n, snaps in (("conv", pool.snap_conv),
                                            ("ssm", pool.snap_ssm))},
                    )
            # The admission sample — evaluated only in the dispatch where the
            # prompt completes, and persisted below (the split/sample topology
            # is exactly _paged_insert's, so the row's stream is bit-identical
            # to the classic admit-then-decode path).
            done = pf_off + C >= pf_len
            kc, sub = _split_rows(pf_key[None])
            first, first_lp = _admission_sample(
                logits_last, done, sub,
                *(lax.dynamic_slice_in_dim(a, pf_row, 1, axis=0)
                  for a in (temperature, top_p, top_k)),
                all_greedy=all_greedy, with_logprobs=with_logprobs,
            )
            fold = (jnp.arange(B, dtype=jnp.int32) == pf_row) & done
            active = active | fold
            tau = jnp.where(fold, first[0], tau)
            if with_logprobs:
                tau_lp = jnp.where(fold, first_lp[0], tau_lp)
            fill_done = pf_base + ((pf_len + BLK - 1) // BLK) * BLK
            fill = jnp.where(fold, fill_done, fill)
            pos = jnp.where(fold, pf_base + pf_len, pos)
            keys = jnp.where(fold[:, None], kc, keys)
            pf_vec = lax.dynamic_update_slice_in_dim(
                pf_vec, (pf_off + C)[None], _PF_OFF, axis=0
            )
        # ---- the standard decode scan: K iterations, or the K - 1 after
        # the mixed pass's ----
        out = _chunk_scan(
            params, pool, table, n_alloc, fill, tau, tau_lp, pos,
            active, remaining, stops, keys, temperature, top_p, top_k,
            config=config, n_iter=n_iter - 1 if mixed else n_iter,
            all_greedy=all_greedy,
            use_kernel=use_kernel, with_logprobs=with_logprobs,
            placed=placed, emitted=emitted,
        )
        return out + (pf_vec,)


# The admission's one upload (``_fused_chunk``'s ``pf_vec``): an int32
# header — row, base, suffix length, the request key's two uint32 words
# viewed as int32, the walk's offset (the program's carry; zero at
# admission) — then the padded suffix tokens.  The header is one row of
# 128 lanes, so a chunk's token slice starts lane-aligned as it did when
# the tokens were an array of their own.
_PF_HEADER = 128
_PF_OFF = 5


def pack_prefill(
    row: int, base: int, suffix_len: int, key: np.ndarray,
    suffix: Sequence[int], buf_len: int,
) -> np.ndarray:
    """The host buffer of one fused admission: header + ``buf_len`` token
    slots (whole chunks; trailing zeros are masked and never dispatched)."""
    vec = np.zeros((_PF_HEADER + buf_len,), np.int32)
    vec[:3] = row, base, suffix_len
    vec[3:5] = np.asarray(key, np.uint32).view(np.int32)
    vec[_PF_HEADER:_PF_HEADER + len(suffix)] = suffix
    return vec


def _unpack_prefill(pf_vec):
    """(row, base, suffix length, key [2] uint32, offset, tokens) of
    ``pack_prefill``'s buffer, on the device."""
    return (
        pf_vec[0], pf_vec[1], pf_vec[2],
        lax.bitcast_convert_type(pf_vec[3:5], jnp.uint32),
        pf_vec[_PF_OFF], pf_vec[_PF_HEADER:],
    )


# Per-row scalars of a row sync, in the column order of ``pack_rows``'s
# matrix; the row's table and stop set follow as column ranges.
_ROW_FIELDS = (
    "idx", "n_alloc", "fill", "pos", "active", "temps", "top_ps",
    "top_ks", "remaining",
)


def pack_rows(
    rows: Sequence[int], n_padded: int, sentinel: int,
    table, n_alloc, fill, pos, active, temps, top_ps, top_ks, remaining,
    stops,
) -> np.ndarray:
    """The host buffer of one row sync: int32 [n_padded, 9 + MB + S], row
    r of it the dirty slot ``rows[r]`` of the host mirrors — its index,
    the eight scalars (float32 ``temps`` / ``top_ps`` by their bits,
    ``active`` as 0 / 1), its table row and its stop row.  Pad rows carry
    the out-of-range index ``sentinel`` and zeros."""
    rows = list(rows)
    n, mb = len(_ROW_FIELDS), table.shape[1]
    mat = np.zeros((n_padded, n + mb + stops.shape[1]), np.int32)
    mat[:, 0] = sentinel
    live = mat[:len(rows)]
    live[:, 0] = rows
    for c, a in enumerate((
        n_alloc, fill, pos, active, temps.view(np.int32),
        top_ps.view(np.int32), top_ks, remaining,
    ), 1):
        live[:, c] = a[rows]
    live[:, n:n + mb] = table[rows]
    live[:, n + mb:] = stops[rows]
    return mat


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(state, packed):
    """Update per-slot device-resident decode state for the (padded,
    pow2-bucketed) dirty rows of ``packed`` (``pack_rows``) in ONE
    dispatch — the admission/free/cancel sync primitive of the chunked
    path.  ``state``: (table, n_alloc, fill, pos, active, temps, top_ps,
    top_ks, remaining, stops).  Pad rows carry the out-of-range index
    n_slots and drop.  The float columns come back by their bits."""
    n = len(_ROW_FIELDS)
    mb = state[0].shape[1]
    col = {name: packed[:, c] for c, name in enumerate(_ROW_FIELDS)}
    rows = (
        packed[:, n:n + mb], col["n_alloc"], col["fill"], col["pos"],
        col["active"] != 0,
        lax.bitcast_convert_type(col["temps"], jnp.float32),
        lax.bitcast_convert_type(col["top_ps"], jnp.float32),
        col["top_ks"], col["remaining"], packed[:, n + mb:],
    )
    return tuple(
        a.at[col["idx"]].set(v, mode="drop") for a, v in zip(state, rows)
    )


def _token_logprob(logits: jnp.ndarray, tok: jnp.ndarray) -> jnp.ndarray:
    """Model log-probability of ``tok`` under fp32 log-softmax of the raw
    logits — temperature/top-p independent (the standard serving-API
    definition), identical to what ``engine.score`` reports for the same
    position.  logits: [B, V]; tok: [B] -> [B] fp32."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    return jnp.take_along_axis(lg, tok[:, None].astype(jnp.int32), axis=1)[
        :, 0
    ] - lse


@functools.partial(
    jax.jit,
    static_argnames=(
        "config", "mesh", "prefill_chunk", "with_logprobs", "placed",
    ),
    donate_argnames=("pool",),
)
def _paged_insert(
    params, pool, block_ids, prompt_tokens, prompt_mask, keys,
    temperature, top_p, top_k, state_rows=None, *,
    config, prefill_chunk=None, mesh=None, with_logprobs=False,
    placed=False,
):
    """Prefill a batch of k admitted requests and land their KV in their
    reserved blocks.

    prompt_tokens/prompt_mask: [k, P] RIGHT-padded to the GROUP's max
    block-multiple length (a burst of admissions shares ONE prefill
    dispatch — previously each request paid its own B=1 prefill, and a
    burst of k paid k serialized dispatches).  Right padding (r5; was
    left) places every row's token j at view column j, so a prompt's
    block CONTENT is a pure function of its tokens — the invariant the
    prefix cache keys on; padding is masked either way, so each row
    emits bit-identically to a standalone B=1 insert of its request.
    block_ids: [k, P // block_size] physical blocks per row, TRAILING
    entries set to the sentinel (n_blocks) for rows with P_b < P — the
    pool scatter drops them, so only the row's own P_b-span lands (P and
    every P_b are block multiples, so the alignment is exact).
    Inactive (padding) rows, if any, carry all-sentinel block_ids and an
    all-False mask.
    state_rows: [k] the rows' slots (recurrent state layers only; a pad
    row carries n_slots, which drops): each row's state after its last
    real token lands in its slot.  This path takes no snapshots.
    Returns (sampled tokens [k], their model logprobs [k], prompt
    lengths [k], carried keys [k, 2], updated pool).
    """
    with use_mesh(mesh):
        k_rows, P = prompt_tokens.shape
        BLK = pool.block_size
        sub = init_cache(config, k_rows, max_len=P)
        positions = prompt_positions(prompt_mask)
        plen = jnp.sum(prompt_mask.astype(jnp.int32), axis=-1)
        chunk = prefill_chunk if prefill_chunk and prefill_chunk < P else P
        # Right padding means a row's LAST real token can sit in any
        # chunk, so instead of taking the final chunk's [k, chunk, V]
        # logits, gather each row's last-token HIDDEN state as chunks
        # stream by (output_last_hidden is head-free and O(k·D)) and run
        # ONE [k, D] head matmul at the end — cheaper than the old full
        # final-chunk head at every geometry.
        h_last = None
        for start in range(0, P, chunk):
            end = min(start + chunk, P)
            _, sub, aux = forward(
                params, prompt_tokens[:, start:end],
                positions[:, start:end], config, cache=sub,
                attn_mask=prompt_mask[:, start:end],
                compute_logits=False, output_last_hidden=True,
            )
            idx = plen - 1 - start  # [k] last-token offset in this chunk
            in_chunk = (idx >= 0) & (idx < end - start)
            g = jnp.take_along_axis(
                aux.last_hidden_state,
                jnp.clip(idx, 0, end - start - 1)[:, None, None],
                axis=1,
            )[:, 0]
            h_last = (
                g if h_last is None
                else jnp.where(in_chunk[:, None], g, h_last)
            )
        logits_last = lm_head_logits(
            params, h_last[:, None], config, normed=True
        )[:, 0]
        with jax.named_scope("sample"):
            keys, subkeys = _split_rows(keys)
            tau = sample_rows(
                subkeys, logits_last, temperature, top_p, top_k)
            tau_lp = (
                _token_logprob(logits_last, tau) if with_logprobs else None
            )
            # Non-finite guard (see _sample_step): -1 sentinel rows are
            # failed by the host at the next emit boundary.
            tau = jnp.where(finite_rows(logits_last), tau, -1)

        nb = P // BLK

        def land(plane, a):
            # [L, k, P, KVH, ...] -> [L, KVH, k, nb, BLK, ...]; block_ids is
            # [k, nb] and its sentinel entries (NB) drop their update.
            return plane.at[:, :, block_ids].set(
                jnp.moveaxis(a, 3, 1).reshape(
                    plane.shape[:2] + (k_rows, nb, BLK) + a.shape[4:]
                ),
                mode="drop",
            )

        with jax.named_scope("cache.land"):
            pool = dataclasses.replace(
                pool,
                **_map_planes(land, pool, sub),
                pos=pool.pos.at[block_ids].set(
                    sub.pos.reshape(k_rows, nb, BLK), mode="drop"
                ),
                stats=_add_stats(pool.stats, sub.stats),
                **_state_into_rows(pool, sub, state_rows),
            )
        # Serving-mesh placement: the donated pool leaves the insert
        # with the same canonical sharding it arrived with (``placed``
        # is the ctor's decision — the SAME predicate every other
        # program uses, so insert and chunk dispatches can never
        # disagree about the pool's sharding).
        if placed:
            pool = smesh.constrain_pool(pool)
        return tau, tau_lp, plen, keys, pool


@functools.partial(
    jax.jit,
    static_argnames=(
        "config", "mesh", "prefill_chunk", "with_logprobs", "placed",
    ),
    donate_argnames=("pool",),
)
def _paged_suffix_insert(
    params, pool, table_row, n_alloc_row, fill0, suffix_tokens,
    suffix_mask, keys, temperature, top_p, top_k, *,
    config, prefill_chunk=None, mesh=None, with_logprobs=False,
    placed=False,
):
    """Prefill k requests' prompt SUFFIXES over the paged pool — the
    prefix-cache admission path: the leading ``fill0[i]`` positions of
    each row's table already hold a reused cached prefix, so only the
    suffixes run through the model, attending the prefix KV through the
    rows' gathered views (``paged_forward``'s multi-token kernel
    contract requires uniform activity along T, which right-padded
    suffixes violate — the gather/scatter cost is the rows'
    reservations, paid once per admission).  Hit requests sharing a
    padded suffix length are admitted as ONE call (per-row fill0
    offsets differ freely), so a burst of identical /chat prompts is
    one dispatch and one host sync instead of k serialized ones.

    table_row: [k, MB]; n_alloc_row, fill0: [k] int32 (fill0 = shared
    prefix length in tokens, a block multiple); suffix_tokens/mask:
    [k, T] right-padded to a block multiple.
    Never dispatched for recurrent state layers: their prefix hits end at
    state snapshots, which only the fused lane takes and restores.
    Returns (tau [k], tau logprobs, carried keys, updated pool).
    """
    with use_mesh(mesh):
        B1, T = suffix_tokens.shape
        view = _gather_cache(
            pool, table_row, n_alloc_row, fill0, placed=placed
        )
        slen = jnp.sum(suffix_mask.astype(jnp.int32), axis=1)  # [k]
        positions = jnp.where(
            suffix_mask,
            fill0[:, None]
            + jnp.cumsum(suffix_mask.astype(jnp.int32), axis=1) - 1,
            -1,
        )
        chunk = prefill_chunk if prefill_chunk and prefill_chunk < T else T
        h_last = None
        for start in range(0, T, chunk):
            end = min(start + chunk, T)
            _, view, aux = forward(
                params, suffix_tokens[:, start:end],
                positions[:, start:end], config, cache=view,
                attn_mask=suffix_mask[:, start:end],
                compute_logits=False, output_last_hidden=True,
            )
            idx = slen - 1 - start
            in_chunk = (idx >= 0) & (idx < end - start)
            g = jnp.take_along_axis(
                aux.last_hidden_state,
                jnp.clip(idx, 0, end - start - 1)[:, None, None],
                axis=1,
            )[:, 0]
            h_last = (
                g if h_last is None
                else jnp.where(in_chunk[:, None], g, h_last)
            )
        logits_last = lm_head_logits(
            params, h_last[:, None], config, normed=True
        )[:, 0]
        pool = _scatter_back(
            pool, view, table_row, fill0, jnp.ones((B1,), bool), T
        )
        with jax.named_scope("sample"):
            keys, sub = _split_rows(keys)
            tau = sample_rows(sub, logits_last, temperature, top_p, top_k)
            lp = _token_logprob(logits_last, tau) if with_logprobs else None
            # Non-finite guard (see _sample_step): -1 sentinel rows are
            # failed by the host at the next emit boundary.
            tau = jnp.where(finite_rows(logits_last), tau, -1)
        # Serving-mesh placement: see _paged_insert's epilogue.
        if placed:
            pool = smesh.constrain_pool(pool)
        return tau, lp, keys, pool


@functools.partial(jax.jit, donate_argnames=("pos",))
def _release_blocks(pos, block_ids):
    """Invalidate freed blocks' positions (block_ids padded with the
    out-of-range sentinel; those drop)."""
    return pos.at[block_ids].set(-1, mode="drop")


def _pool_as_cache(pool: BlockPool, table, fill) -> PagedKVCache:
    return PagedKVCache(
        k=pool.k, v=pool.v, pos=pool.pos, table=table, fill=fill,
        k_scale=pool.k_scale, v_scale=pool.v_scale, stats=pool.stats,
        conv=pool.conv, ssm=pool.ssm, idx=pool.idx,
    )


def _cache_into_pool(pool: BlockPool, pcache: PagedKVCache) -> BlockPool:
    return dataclasses.replace(
        pool, k=pcache.k, v=pcache.v, pos=pcache.pos,
        k_scale=pcache.k_scale, v_scale=pcache.v_scale, stats=pcache.stats,
        conv=pcache.conv, ssm=pcache.ssm, idx=pcache.idx,
    )


def _refuse_block_extras(params, draft_params, mesh, block: str) -> None:
    """What a block beside the dense one (``block``: latent attention,
    window attention layers, recurrent state layers) does not get yet is
    refused at server start, by name, never served wrongly."""
    from .ops.quant import QuantizedTensor

    if draft_params is not None:
        raise ValueError(
            f"speculative decoding (--draft-*) is not supported with {block}"
        )
    if any(
        isinstance(x, QuantizedTensor) for x in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    ):
        raise ValueError(
            f"--quantize (int8 weights) is not supported with {block}"
        )
    if mesh is not None and any(n > 1 for n in mesh.shape.values()):
        raise ValueError(
            f"--serve-mesh / tensor sharding is not supported with {block}: "
            f"it runs on one chip (mesh {dict(mesh.shape)})"
        )


def _add_stats(a, b):
    return None if a is None else a + b


def _pack_stats(packed: jnp.ndarray, pool: BlockPool):
    """Routed-expert configurations: the routing counts since the last
    fetch ride the chunk's ONE packed fetch as trailing int32 planes (as
    many [B, K] planes as N_STATS values need), and the pool's counters
    start again from zero.  Every other pool: unchanged."""
    if pool.stats is None:
        return packed, pool
    with jax.named_scope("emit"):
        _, B, K = packed.shape
        n = -(-pool.stats.shape[0] // (B * K))
        planes = jnp.pad(
            pool.stats, (0, n * B * K - pool.stats.shape[0])
        ).reshape(n, B, K)
        return (
            jnp.concatenate([packed, planes], axis=0),
            dataclasses.replace(pool, stats=jnp.zeros_like(pool.stats)),
        )


def _spec_round_core(
    t_params, d_params, t_pool, d_pool, table, n_alloc, fill, tau, pos,
    active, keys, temperature, top_p, top_k, *,
    t_config, d_config, n_draft, all_greedy, use_kernel, mesh=None,
    with_logprobs=False, placed=False,
):
    """One speculative round for every active slot — greedy or sampled
    verification, per-row policies.  The row-wise draft/verify body of
    each ``lax.scan`` iteration of the R-round chunk program
    (``_spec_rounds_chunk``) — to speculation what ``_decode_step_core``
    is to plain decode.

    Draft proposes ``n_draft`` tokens autoregressively, the target
    verifies them in ONE [B, n_draft+1] forward (weights stream once per
    round — the whole point on HBM-bound TPU decode), and the accepted
    prefix is committed.  Both models share the block geometry, so one
    table/fill serves the two pools.

    ``use_kernel`` (static) routes every forward through the Pallas
    paged-attention kernel, always at the verify shape: each draft-chain
    step is one T=G+1 multi-token kernel pass replaying the growing
    block over the BASE pool, and the verify is one more — so neither
    pool is ever gathered into a contiguous view (the gathered path
    moved both pools' bytes 3× per round).  The gathered fallback
    remains for kernel-incompatible meshes / block sizes.

    ``all_greedy`` (static) compiles the pure-argmax verification with no
    RNG traffic.  Otherwise verification is per-row Leviathan rejection
    sampling — the SAME ``spec_decode.leviathan_verify`` /
    ``draft_categorical`` / ``place_extra`` implementation the standalone
    engine traces, with traced per-row policies and per-row key chains
    (vmapped draws): each sampled row consumes its keys exactly as a
    standalone B=1 seeded ``generate_speculative`` of that request would
    — same split topology, same warp math — so its emitted tokens are
    bit-identical (pinned by tests/test_serving_spec.py); greedy rows
    (temperature 0) take the exact-argmax path inside the same program.

    Returns (outs [B, G+1], acc [B], lps, carried keys [B, 2], pools):
    the host emits ``outs[:acc+1]`` per row and rewinds fill to +acc+1,
    so rejected drafts cost no pool capacity.  ``with_logprobs`` (static)
    additionally returns lps [B, G+1] — the fp32 log-softmax of the raw
    TARGET logits at each emitted offset (``_token_logprob``'s
    definition; the verify pass already computes every position's
    logits, so this is one gather + logsumexp, no extra forward) —
    otherwise lps is None.
    """
    G = n_draft
    B = tau.shape[0]
    V = t_config.vocab_size
    with use_mesh(mesh):
        NB, BLK = t_pool.pos.shape
        if all_greedy:
            keys_out = keys
            k_draft = k_accept = k_extra = keys  # unused
        else:
            # Row-wise mirror of _spec_impl's per-round
            # ``rng, k_draft, k_accept, k_extra = jax.random.split(rng, 4)``.
            splits = jax.vmap(lambda k: jax.random.split(k, 4))(keys)
            keys_out, k_draft, k_accept, k_extra = (
                splits[:, 0], splits[:, 1], splits[:, 2], splits[:, 3]
            )

        if not use_kernel:
            t_view = _gather_cache(
                t_pool, table, n_alloc, fill, placed=placed
            )
            d_view = _gather_cache(
                d_pool, table, n_alloc, fill, placed=placed
            )

        # --- 1. draft chain: propose d_1 .. d_G by REPLAYING the block ---
        # Every chain step re-processes the growing block
        # [tau, d_1..d_j, pads] through ONE verify-shaped T=G+1 forward
        # over the BASE pool (read-only — fill unchanged, returned cache
        # discarded, so the writes are dead code XLA eliminates): token
        # j's logits come from the same program shape and the same
        # softmax source split (pool slots via the kernel ∪ in-step
        # tokens via the merge) as the target verify below.  In
        # self-draft the chain is then the SAME compiled function of the
        # same pool bytes as the verify, so greedy acceptance is exact —
        # the r3 T=1 incremental chain's tile shapes wobbled ~1 bf16
        # ulp/layer against the T=G+1 verify (shape-dependent merge
        # einsum tilings; the pool kernel itself is bit-exact across T),
        # flipping near-tie argmaxes: measured 0.92-0.95 kernel-path
        # acceptance vs 0.97-0.99 gathered.  Cost is a wash: G drafting
        # forwards + one KV-landing pass (below) replaces G incremental
        # steps + the d_G catch-up step, and the kernel's padded query
        # tile (TG8) is the same geometry for T=1 and T=G+1.
        jj = jnp.arange(G + 1, dtype=jnp.int32)[None, :]
        block_pos = jnp.where(
            active[:, None], pos[:, None] + jj, -1
        ).astype(jnp.int32)
        block0 = jnp.concatenate(
            [tau[:, None], jnp.zeros((B, G), jnp.int32)], axis=1
        )

        def draft_step(carry, j):
            buf, kd = carry
            # The WHOLE block runs live every step (positions consecutive,
            # mask uniform — paged_forward's T>1 contract; mixed-liveness
            # rows would be folded to inactive).  Correctness: row j
            # attends only tokens 0..j (causal), so the not-yet-drafted
            # placeholder tokens beyond j cannot reach row j's logits —
            # and the uniform mask makes each chain step the literally
            # identical program to the verify pass below.
            step_mask = jnp.broadcast_to(active[:, None], buf.shape)
            if use_kernel:
                pcache = _pool_as_cache(d_pool, table, fill)
                lg, _ = forward(
                    d_params, buf, block_pos, d_config, cache=pcache,
                    attn_mask=step_mask,
                )
            else:
                lg, _ = forward(
                    d_params, buf, block_pos, d_config, cache=d_view,
                    attn_mask=step_mask,
                )
            lgj = lax.dynamic_slice_in_dim(lg, j, 1, axis=1)[:, 0]  # [B, V]
            greedy_nxt = jnp.argmax(lgj, axis=-1).astype(jnp.int32)
            if all_greedy:
                nxt = greedy_nxt
                q = jnp.zeros((B, V), jnp.float32)  # unused
            else:
                # Row-wise _spec_impl.draft_one: key, sub = split(key);
                # draft_categorical(sub, q).
                kd, sub = _split_rows(kd)
                q = warped_probs_rows(lgj, temperature, top_p, top_k)
                sampled_nxt = jax.vmap(draft_categorical)(sub, q)
                nxt = jnp.where(temperature <= 0.0, greedy_nxt, sampled_nxt)
            buf = lax.dynamic_update_slice(buf, nxt[:, None], (0, j + 1))
            return (buf, kd), q

        (block, _), qprobs = jax.lax.scan(
            draft_step, (block0, k_draft), jnp.arange(G, dtype=jnp.int32)
        )
        drafts = block[:, 1:]                 # [B, G]
        qprobs = jnp.swapaxes(qprobs, 0, 1)   # [B, G, V]
        # Land the block's KV in the draft pool: one verify-shaped pass
        # (replaces the old per-step writes + d_G catch-up step).
        if use_kernel:
            pcache = _pool_as_cache(d_pool, table, fill)
            _, pcache = forward(
                d_params, block, block_pos, d_config, cache=pcache,
                attn_mask=jnp.broadcast_to(active[:, None], block.shape),
                compute_logits=False,
            )
            d_pool = _cache_into_pool(d_pool, pcache)
        else:
            _, d_view = forward(
                d_params, block, block_pos, d_config, cache=d_view,
                attn_mask=jnp.broadcast_to(active[:, None], block.shape),
                compute_logits=False,
            )

        # --- 2. one target pass over [tau, d_1 .. d_G] ---
        j = jj
        if use_kernel:
            # The T=G+1 multi-token kernel pass: the target pool streams
            # ONCE for the whole verify.
            pcache = _pool_as_cache(t_pool, table, fill)
            t_logits, pcache = forward(
                t_params, block, block_pos, t_config, cache=pcache,
                attn_mask=jnp.broadcast_to(active[:, None], block.shape),
            )
            t_pool = _cache_into_pool(t_pool, pcache)
        else:
            t_logits, t_view = forward(
                t_params, block, block_pos, t_config, cache=t_view,
                attn_mask=jnp.broadcast_to(active[:, None], block.shape),
            )

        # --- 3. verification ---
        greedy_outs = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
        greedy_match = drafts == greedy_outs[:, :G]
        greedy_acc = jnp.sum(
            jnp.cumprod(greedy_match.astype(jnp.int32), axis=1), axis=1
        )
        if all_greedy:
            outs, acc = greedy_outs, greedy_acc
        else:
            # Per-row Leviathan rejection sampling — the shared
            # spec_decode core with traced policies and vmapped draws;
            # greedy rows selected per-row below.
            pprobs = warped_probs_rows(t_logits, temperature, top_p, top_k)
            u = jax.vmap(lambda k: jax.random.uniform(k, (G,)))(k_accept)
            acc_s, dist = leviathan_verify(pprobs, qprobs, drafts, u)
            extra = jax.vmap(draft_categorical)(k_extra, dist)
            outs_s = place_extra(drafts, acc_s, extra)
            is_greedy = temperature <= 0.0
            outs = jnp.where(is_greedy[:, None], greedy_outs, outs_s)
            acc = jnp.where(is_greedy, greedy_acc, acc_s)
        # Non-finite guard: a row whose target logits contain NaN/Inf
        # anywhere in the verify block gets acc = -1 — the commit below
        # then invalidates every slot this round wrote for the row, and
        # the host fails just that request (acc is never negative
        # otherwise, so the sentinel cannot collide).
        acc = jnp.where(jnp.all(finite_rows(t_logits), axis=-1), acc, -1)

        if with_logprobs:
            # t_logits[:, j] is the target's raw distribution the token
            # emitted at offset j was drawn/verified from.
            lps = _token_logprob(
                t_logits.reshape(B * (G + 1), V), outs.reshape(-1)
            ).reshape(B, G + 1)
        else:
            lps = None

        # --- 4. commit: invalidate rejected slots.  Slot j holds
        # block[j] (= tau for j=0, d_j after), valid iff j <= acc; the
        # host rewinds fill to +acc+1 so rejected slots are reused, not
        # wasted.
        valid = j <= acc[:, None]
        patched = jnp.where(valid, block_pos, -1)
        if use_kernel:
            blk_i, off_i, _ = paged_write_indices(
                table, fill, active, G + 1, NB, BLK
            )
            t_pool = dataclasses.replace(
                t_pool,
                pos=paged_pool_write(t_pool.pos, patched, blk_i, off_i),
            )
            d_pool = dataclasses.replace(
                d_pool,
                pos=paged_pool_write(d_pool.pos, patched, blk_i, off_i),
            )
        else:
            rows = jnp.arange(B, dtype=jnp.int32)[:, None]
            cols = fill[:, None] + j
            t_view = dataclasses.replace(
                t_view,
                pos=t_view.pos.at[rows, cols].set(patched, mode="drop"),
            )
            d_view = dataclasses.replace(
                d_view,
                pos=d_view.pos.at[rows, cols].set(patched, mode="drop"),
            )
            t_pool = _scatter_back(
                t_pool, t_view, table, fill, active, T=G + 1
            )
            d_pool = _scatter_back(
                d_pool, d_view, table, fill, active, T=G + 1
            )
        return outs, acc, lps, keys_out, t_pool, d_pool


@functools.partial(
    jax.jit,
    static_argnames=(
        "t_config", "d_config", "n_draft", "n_rounds", "all_greedy",
        "use_kernel", "mesh", "with_logprobs", "placed",
    ),
    donate_argnames=(
        "t_pool", "d_pool", "fill", "tau", "tau_lp", "pos", "active",
        "remaining", "keys",
    ),
)
def _spec_rounds_chunk(
    t_params, d_params, t_pool, d_pool, table, n_alloc, fill, tau,
    tau_lp, pos, active, remaining, stops, keys, temperature, top_p,
    top_k, *, t_config, d_config, n_draft, n_rounds, all_greedy,
    use_kernel, mesh=None, with_logprobs=False, placed=False,
):
    """``n_rounds`` fused speculative rounds in ONE jitted program — the
    speculative twin of ``_paged_decode_chunk`` (``n_rounds=1`` is to it
    what ``n_iter=1`` is to that program).  Each ``lax.scan`` iteration
    is one whole round, ON DEVICE:

      1. *emit* the pending token ``tau`` into the round's output row
         (column 0), recording -1 for a non-finite-sentinel row and
         ``_CHUNK_PAD`` for rows already inactive; a row whose tau hits
         its stop set / exhausts its budget folds out of ``active``
         before the round runs, so it never pays for a discarded
         draft+verify;
      2. run one ``_spec_round_core`` draft+verify for the surviving
         rows (per-round key-split topology, warp math and
         commit/rewind as a standalone ``generate_speculative``);
      3. the accepted-prefix emit scan
         (``spec_decode.accepted_emit_counts``): tokens ``outs[:acc]``
         emit into columns 1..acc until a stop token or the max_new
         budget lands mid-prefix, the fill/pos rewind to ``+acc+1``
         happens in-carry for rows that continue, ``outs[acc]`` becomes
         the next pending tau, and finished / non-finite rows fold out
         of the active mask for the REST of the chunk.

    The host touches the device once per CHUNK of R rounds, not once
    per round: the packed int32 block [B, R, W] carries each round's
    G+1 token columns, its acceptance count (-1 = the verify's
    non-finite sentinel, ``_CHUNK_PAD`` = row inactive that round) and,
    under ``with_logprobs``, the G+1 bitcast fp32 target logprobs —
    ONE ``np.asarray`` fetch a dispatch and no upload.  All speculative
    decode state (tau/tau_lp/fill/pos/active/remaining/keys + BOTH
    pools) stays device-resident between chunks.

    Token-identity across R and with the standalone
    ``spec_decode.generate_speculative`` — including the acceptance
    pattern and per-token logprobs — is pinned by
    tests/test_serving_spec.py; rounds after every row has folded out
    run masked rather than cond-skipped (same trade as
    ``_paged_decode_chunk`` — the host clamps R to the largest
    remaining budget, which bounds the dead tail)."""
    G = n_draft
    with use_mesh(mesh):

        def body(carry, _):
            (t_pool, d_pool, tau, tau_lp, fill, pos, active, remaining,
             keys) = carry
            # --- emit the pending tau ---
            nonfinite = tau < 0
            hit_stop = stop_token_hits(tau, stops)
            out0 = jnp.where(
                active, jnp.where(nonfinite, -1, tau), _CHUNK_PAD
            ).astype(jnp.int32)
            out0_lp = tau_lp
            done0 = active & (nonfinite | hit_stop | (remaining <= 1))
            remaining = remaining - active.astype(jnp.int32)
            active = active & ~done0
            # --- one draft+verify round for the surviving rows ---
            outs, acc, lps_r, keys, t_pool, d_pool = _spec_round_core(
                t_params, d_params, t_pool, d_pool, table, n_alloc,
                fill, tau, pos, active, keys, temperature, top_p,
                top_k, t_config=t_config, d_config=d_config,
                n_draft=G, all_greedy=all_greedy, use_kernel=use_kernel,
                mesh=mesh, with_logprobs=with_logprobs, placed=placed,
            )
            # --- the accepted-prefix emit scan ---
            verify_nan = active & (acc < 0)
            acc_c = jnp.clip(acc, 0, G)
            stop_hits = stop_token_hits(outs[:, :G], stops)  # [B, G]
            e, any_done = accepted_emit_counts(
                acc_c, stop_hits, remaining
            )
            i = jnp.arange(G, dtype=jnp.int32)[None, :]
            emit = (
                (i < e[:, None]) & active[:, None]
                & ~verify_nan[:, None]
            )
            out_rest = jnp.where(
                emit, outs[:, :G], _CHUNK_PAD
            ).astype(jnp.int32)
            acc_out = jnp.where(
                active, jnp.where(verify_nan, -1, acc_c), _CHUNK_PAD
            ).astype(jnp.int32)
            # --- advance / fold-out: the fill/pos += acc+1 rewind and
            # the finished rows' exit, in-carry ---
            cont = active & ~verify_nan & ~any_done
            adv = jnp.where(cont, acc_c + 1, 0)
            fill = fill + adv
            pos = pos + adv
            remaining = remaining - jnp.where(
                active & ~verify_nan, e, 0
            )
            new_tau = jnp.take_along_axis(
                outs, acc_c[:, None], axis=1
            )[:, 0]
            tau = jnp.where(cont, new_tau, tau)
            if with_logprobs:
                out_lp = jnp.concatenate(
                    [out0_lp[:, None], lps_r[:, :G]], axis=1
                )
                new_lp = jnp.take_along_axis(
                    lps_r, acc_c[:, None], axis=1
                )[:, 0]
                tau_lp = jnp.where(cont, new_lp, tau_lp)
            else:
                # Unused lane: keeps the scan's ys pytree shape static
                # across the with_logprobs specializations.
                out_lp = jnp.zeros((tau.shape[0], G + 1), jnp.float32)
            active = cont
            out_tok = jnp.concatenate([out0[:, None], out_rest], axis=1)
            return (
                (t_pool, d_pool, tau, tau_lp, fill, pos, active,
                 remaining, keys),
                (out_tok, acc_out, out_lp),
            )

        carry, (toks, accs, lps) = lax.scan(
            body,
            (t_pool, d_pool, tau, tau_lp, fill, pos, active, remaining,
             keys),
            None,
            length=n_rounds,
        )
        (t_pool, d_pool, tau, tau_lp, fill, pos, active, remaining,
         keys) = carry
        # Serving-mesh placement: see _chunk_scan's epilogue (the
        # ctor's placement decision already required BOTH pools inside
        # the envelope — the draft pool shards its own KV-head axis).
        if placed:
            (tau, tau_lp, fill, pos, active, remaining,
             keys) = smesh.constrain_rows(
                tau, tau_lp, fill, pos, active, remaining, keys
            )
            t_pool = smesh.constrain_pool(t_pool)
            d_pool = smesh.constrain_pool(d_pool)
        toks = jnp.moveaxis(toks, 0, 1)   # [B, R, G+1]
        accs = jnp.swapaxes(accs, 0, 1)   # [B, R]
        if with_logprobs:
            # fp32 logprobs ride bitcast to int32 alongside the tokens
            # and acceptance counts: logprobs mode still pays exactly
            # one device->host fetch per chunk.
            lp_bits = lax.bitcast_convert_type(
                jnp.moveaxis(lps, 0, 1).astype(jnp.float32), jnp.int32
            )
            packed = jnp.concatenate(
                [toks, accs[:, :, None], lp_bits], axis=2
            )  # [B, R, 2G+3]
        else:
            packed = jnp.concatenate(
                [toks, accs[:, :, None]], axis=2
            )  # [B, R, G+2]
        return (
            packed, tau, tau_lp, fill, pos, active, remaining, keys,
            t_pool, d_pool,
        )


# ---------------------------------------------------------------------------
# Jit-cache observability: the registered serving programs
# ---------------------------------------------------------------------------

# Every jitted program the serving stack dispatches (the same eight the
# analysis lowering contracts audit), by name — the source for the
# per-program ``jit_cache_entries`` gauge (/metrics).
# ``_cache_size()`` is jax's own per-function executable
# cache; a runaway entry count here is a bucketing bug re-specializing
# a program per request (the stall that used to be invisible).
def _programs() -> Dict[str, Any]:
    from .kvcache import _adopt_jit
    return {
        "_paged_decode_chunk": _paged_decode_chunk,
        "_fused_chunk": _fused_chunk,
        "_spec_rounds_chunk": _spec_rounds_chunk,
        "_paged_insert": _paged_insert,
        "_paged_suffix_insert": _paged_suffix_insert,
        "_scatter_rows": _scatter_rows,
        "_release_blocks": _release_blocks,
        "_adopt_jit": _adopt_jit,
    }


def jit_cache_entries() -> Dict[str, int]:
    """Live jit-cache entry count per registered program (-1 when the
    jax version hides the cache) — scrape-time host work only."""
    out: Dict[str, int] = {}
    for name, fn in _programs().items():
        try:
            out[name] = int(fn._cache_size())
        except Exception:
            out[name] = -1
    return out


# ---------------------------------------------------------------------------
# Host-side batcher
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Slot:
    request_id: int
    emitted: List[int]
    max_new: int
    stop_tokens: frozenset
    blocks: List[int]
    # Leading blocks[:shared] were REUSED prefix-cache hits (KV written
    # by earlier healthy dispatches); blocks[shared:] are this request's
    # own writes — the distinction the non-finite guard needs to
    # unpublish only suspect KV.
    shared: int = 0


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclasses.dataclass
class _Prefill:
    """Host view of the single in-flight fused admission (queued ->
    prefilling(off) -> decoding).  ``d_vec`` is the admission's ONE
    upload, made when the prefill starts (``pack_prefill``: the walk's
    scalars, key words and offset in front of the suffix tokens), and a
    donated carry from then on: the fused program advances its offset
    word and hands it back.  ``off`` is the host's deterministic replay
    of that word (off advances by exactly ``chunk`` per dispatch, so
    completion is host-computable without a fetch)."""

    slot: int
    req: "_Request"
    chain: List[bytes]
    n_share: int          # leading prefix-cache-hit blocks
    base: int             # fill0 in tokens (block multiple)
    suffix_len: int       # real suffix tokens still to prefill at start
    chunk: int            # C: prompt tokens advanced per dispatch
    off: int = 0          # suffix tokens already dispatched
    d_vec: Any = None     # [_PF_HEADER + buf] int32, donated carry
    # Recurrent state layers: the snapshot the walk starts from (-1: the
    # empty state) and the (depth in blocks, id) snapshots its chunks have
    # taken, hung on the chain's nodes once it is published.
    snap_in: int = -1
    snaps: List[Tuple[int, int]] = dataclasses.field(default_factory=list)

    @property
    def remaining_tokens(self) -> int:
        return max(0, self.suffix_len - self.off)

    @property
    def flash(self) -> bool:
        """Host mirror of the prefill half's "auto" resolution: the
        chunk runs the flash kernel iff it is wider than
        ``FLASH_MIN_SEQ`` tokens and the config allows flash (the
        view's index is scalar, so the per-row-index must-xla rule
        never triggers here) — the shared constant keeps this mirror,
        and therefore flash_kernel fault-site firing and quarantine
        attribution, in lockstep with forward()'s actual resolution."""
        return self.chunk > FLASH_MIN_SEQ


@dataclasses.dataclass
class _Restore:
    """Host view of one in-flight swap-in (the ``restoring`` admission
    state): the request left the queue, its matched path's RESIDENT
    blocks are claimed (refcounted — eviction cannot take them), its
    demoted nodes are pinned in the host tier, fresh HBM blocks are
    allocated, and the slabs are mid-flight in ``staged``
    (``jax.device_put`` staging buffers — see ``kvcache.stage_restore``
    for why staging, not a direct pool write, is what makes the decode
    overlap real).  ``_poll_restores`` adopts the blocks into the pool
    and hands the request to ``_restored_ready`` once the transfer
    lands; decode chunks keep dispatching the whole time."""

    req: "_Request"
    chain: List[bytes]
    path: List[Any]          # kvcache.RadixNode path (resident + demoted)
    restore: List[Any]       # the demoted nodes being swapped in
    resident: List[int]      # the path's HBM-resident blocks, CLAIMED at
    #                          begin — recorded by id, not recomputed
    #                          from the nodes (a concurrent non-finite
    #                          subtree drop may null node.block)
    fresh: List[int]         # their freshly allocated HBM blocks
    staged: Dict[str, Any]   # kvcache.stage_restore buffers
    t0: float
    polls: int = 0


@dataclasses.dataclass
class _Request:
    rid: int
    tokens: List[int]
    max_new: int
    stops: frozenset
    temperature: float
    top_p: float
    top_k: int
    seed: Optional[int]

    def blocks_needed(self, block_size: int) -> int:
        padded = _round_up(len(self.tokens), block_size)
        return -(-(padded + self.max_new) // block_size)


class ContinuousBatcher:
    """Host-side slot manager around the jitted paged step programs.

    Usage:
        cb = ContinuousBatcher(params, config, n_slots=8, max_len=2048)
        rid = cb.submit([1, 5, 9, ...], max_new_tokens=128)
        while cb.pending():
            for request_id, token, done in cb.step():
                ...stream token to the caller...

    ``n_blocks`` sizes the KV pool; the default matches contiguous
    capacity (n_slots × max_len).  A smaller pool overcommits: admission
    reserves ceil((padded_prompt + max_new) / block_size) blocks and
    requests queue until their reservation fits.

    ``decode_chunk`` fuses up to that many decode iterations per jitted
    dispatch (module docstring, "Chunked decode"): each ``step()`` call
    may emit up to K tokens per slot, token-identically to the K=1 loop,
    at one host round-trip per chunk.  1 (the default) is the same
    program at one iteration a dispatch; serving entry points
    (run.py ``--decode-chunk``) default higher.

    Passing ``draft_params``/``draft_config`` turns on speculative
    decoding inside the batcher: each step drafts ``n_draft`` tokens per
    slot and verifies them in one target forward.  Greedy slots emit
    token-identically to the plain greedy batcher; sampled slots emit
    bit-identically to a standalone seeded ``generate_speculative`` of
    the same request (per-row Leviathan rejection sampling with per-slot
    key chains) — the draft only ever changes speed, never content (see
    ``acceptance_rate()``).

    ``spec_rounds`` is ``decode_chunk``'s speculative twin: up to that
    many draft+verify ROUNDS fuse into one jitted dispatch (module
    docstring, "Chunked speculative serving"), token-identically at
    every R — one ``step()`` may then emit up to R * (n_draft + 1)
    tokens per slot at one host round-trip per chunk.  1 (the default)
    is the same program at one round a dispatch; serving entry points
    (run.py ``--spec-rounds``) default higher.

    ``prefill_budget`` turns on fused prefill-decode scheduling (module
    docstring, "Fused prefill-decode scheduling"): warm admissions
    advance up to that many prompt tokens per chunk dispatch inside the
    decode chunk itself instead of stalling every decoding row for a
    whole-prompt prefill dispatch — token-identical to the classic
    path, first sampled token emitted by the dispatch that finishes the
    prefill.  0 (the default) keeps classic admission; serving entry
    points (run.py ``--prefill-budget``) default it on.  Ignored by
    speculative batchers.

    ``prefix_cache`` (module docstring, "KV capacity") shares partial
    prefixes across ALL cached chains through a block-granular radix
    trie; False disables matching and retention (``prefix_index``, the
    derived attribute the server reports, reads ``"radix"`` or
    ``"off"``).  ``host_kv_blocks`` > 0 (with the cache on) attaches
    the host-DRAM block tier: cold blocks demote into it instead of
    being freed, and
    admissions whose matched prefix was demoted swap it back in
    asynchronously through the ``restoring`` state — decode rows never
    stall on a swap-in (run.py ``--host-kv-blocks``).
    """

    def __init__(
        self,
        params: Any,
        config: LLaMAConfig,
        n_slots: int = 8,
        max_len: Optional[int] = None,
        stop_tokens: Tuple[int, ...] = (),
        temperature: float = 0.0,
        top_p: Optional[float] = None,
        top_k: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        seed: int = 0,
        block_size: Optional[int] = None,
        n_blocks: Optional[int] = None,
        draft_params: Any = None,
        draft_config: Optional[LLaMAConfig] = None,
        n_draft: int = 4,
        mesh=None,
        use_pallas_kernel: bool = True,
        logprobs: bool = False,
        prefix_cache: bool = True,
        fault_injector: Optional[FaultInjector] = None,
        decode_chunk: int = 1,
        spec_rounds: int = 1,
        prefill_budget: int = 0,
        host_kv_blocks: int = 0,
        obs: Optional[Observability] = None,
    ):
        # Raw construction arguments, captured before any derivation so
        # ``rebuild()`` (crash recovery) reproduces this batcher exactly
        # — fresh pool + host state, same geometry and policies.  The
        # injector is shared across rebuilds so its call counters index
        # the process's dispatches, not one incarnation's.
        self._ctor_kwargs = dict(
            n_slots=n_slots, max_len=max_len, stop_tokens=stop_tokens,
            temperature=temperature, top_p=top_p, top_k=top_k,
            prefill_chunk=prefill_chunk, seed=seed, block_size=block_size,
            n_blocks=n_blocks, draft_params=draft_params,
            draft_config=draft_config, n_draft=n_draft, mesh=mesh,
            use_pallas_kernel=use_pallas_kernel, logprobs=logprobs,
            prefix_cache=prefix_cache, fault_injector=fault_injector,
            decode_chunk=decode_chunk, spec_rounds=spec_rounds,
            prefill_budget=prefill_budget, host_kv_blocks=host_kv_blocks,
            obs=obs,
        )
        # Compile attribution (obs.py, the jax.monitoring listener) is
        # always on: it is two thread-local writes per dispatch.
        _obs_mod.install_compile_listener()
        # Observability sink (obs.py): request span timelines, dispatch
        # spans, latency histograms, SLO accounting.  Always on — pure
        # host-side bookkeeping at boundaries the loop already crosses,
        # zero device dispatches / host syncs of its own (asserted by
        # make perf-smoke).  Shared across rebuilds like the injector:
        # the created instance replaces the ctor arg in _ctor_kwargs so
        # crash recovery keeps one continuous trace.
        self.obs = obs if obs is not None else Observability()
        self._ctor_kwargs["obs"] = self.obs
        self.fault_injector = fault_injector
        if fault_injector is not None and getattr(
            fault_injector, "trace_sink", None
        ) is None:
            # Injections land in the trace's annotation ring, so a
            # chaos drill's fault is visible next to the dispatch spans
            # it killed.
            fault_injector.trace_sink = self.obs.annotate
        if config.attn_impl not in ("xla", "auto"):
            raise ValueError(
                "continuous batching requires attn_impl 'xla' or 'auto' "
                "(per-row cache offsets run on the xla path)"
            )
        self.spec = draft_params is not None
        self.logprobs = logprobs
        # Where ``_upload`` commits its copies: where the weights are, if
        # they are committed to ONE device — replicated on their own
        # one-device mesh if they carry one, which is how a program hands
        # such an operand back (None: uncommitted weights, or several
        # devices, where an unplaced copy is replicated as ever).
        leaf = next((x for x in jax.tree_util.tree_leaves(params)
                     if isinstance(x, jax.Array)), None)
        self._upload_to = None
        if leaf is not None and leaf.committed and len(leaf.devices()) == 1:
            held = leaf.sharding
            self._upload_to = (
                jax.sharding.NamedSharding(
                    held.mesh, jax.sharding.PartitionSpec())
                if isinstance(held, jax.sharding.NamedSharding)
                else jax.sharding.SingleDeviceSharding(
                    next(iter(leaf.devices())))
            )
        if config.expert_block:
            _refuse_block_extras(
                params, draft_params, mesh, config.expert_block
            )
        if self.spec:
            if draft_config is None:
                raise ValueError("draft_params requires draft_config")
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError("target and draft must share a vocabulary")
            if n_draft < 1:
                raise ValueError("n_draft must be >= 1")
        self.draft_params = draft_params
        self.draft_config = draft_config
        self.n_draft = n_draft
        self.params = params
        self.config = config
        self.mesh = mesh
        # False forces the gathered-view attention everywhere the kernel
        # would run: the paged_kernel quarantine's lever
        # (server.LLMServer._build_batcher).
        self.use_pallas_kernel = use_pallas_kernel
        self.n_slots = n_slots
        self.max_len = max_len or config.max_seq_len
        if block_size is None:
            # Larger blocks raise the kernel's DMA efficiency (it
            # fetches one [KVH, BLK, d] tile per table entry; on-chip
            # sweeps measured the decode step at a 16k context going
            # 8.9 -> 5.8 ms/step from 128 -> 512 blocks, and 5.5 -> 4.3
            # at 8k) at the cost of allocation granularity.  Default:
            # capacity-friendly 128-and-down short, bandwidth-friendly
            # 512 at >= 8k.  Granularity trade at the default: prompts
            # pad to a block multiple, so the longest admissible prompt
            # is max_len rounded DOWN to the block size minus max_new —
            # a request within 512 tokens of capacity needs an explicit
            # smaller block_size.
            if self.max_len >= 8192:
                block_size = 512
            else:
                block_size = min(128, max(16, self.max_len // 16))
        self.block_size = block_size
        self.blocks_per_slot = -(-self.max_len // self.block_size)
        self.n_blocks = n_blocks or n_slots * self.blocks_per_slot
        self.default_stop = frozenset(int(s) for s in stop_tokens)
        self.temperature = float(temperature)
        self.top_p = 1.0 if top_p is None else float(top_p)
        self.top_k = 0 if top_k is None else int(top_k)
        self.prefill_chunk = prefill_chunk
        self.seed = seed
        # Recurrent state layers: a per-slot state beside the pool and a
        # pool of state snapshots under the radix store, eight a slot (a
        # 4,096-token row at a 512-token chunk), none without the store.
        self.recurrent = config.recurrent_state
        if self.recurrent and host_kv_blocks > 0:
            raise ValueError(
                "--host-kv-blocks (the host tier) is not supported with "
                f"{config.expert_block}: a demoted node's state snapshot "
                "does not demote with it")
        if config.sparse_attention and host_kv_blocks > 0:
            raise ValueError(
                "--host-kv-blocks (the host tier) is not supported with "
                f"{config.expert_block}: the index-key plane has not been "
                "through a demotion and a restore")
        self.n_snapshots = (
            snapshot_pool_size(
                self.config, n_slots, self.n_blocks, self.block_size)
            if self.recurrent and prefix_cache else 0
        )
        # What a slot's state (and one snapshot of it) holds, and the
        # snapshot pool: gauges that turn the ssm_* counts into bytes.
        self.ssm_state_bytes_per_slot = self.config.state_bytes_per_row
        self.ssm_snapshot_bytes = (
            self.n_snapshots * self.ssm_state_bytes_per_slot)
        self.pool = init_pool(
            self.config, self.n_blocks, self.block_size,
            n_slots=n_slots, n_snapshots=self.n_snapshots,
        )
        self.draft_pool = (
            init_pool(self.draft_config, self.n_blocks, self.block_size)
            if self.spec else None
        )
        # Serving-mesh placement (parallel/serve_mesh.py): on a
        # data x tensor serving mesh inside the placement envelope, the
        # KV pool(s) shard their KV-head axis over `tensor` and the
        # per-slot device twins shard rows over the batch axes, AT
        # CONSTRUCTION — matching the output constraints the chunk
        # programs apply, so every donated leaf aliases shard-locally
        # from the first dispatch (no per-dispatch GSPMD reshard, no
        # silent donation copy).  Meshes outside the envelope (seq or
        # stage axes, non-dividing tensor/rows) keep legacy placement
        # — GSPMD still serves them through propagation.
        self._mesh_placed = smesh.placement_ok(
            config, mesh, n_slots,
            draft_config=draft_config if self.spec else None,
        )
        if self._mesh_placed:
            self.pool = smesh.shard_pool(self.pool, mesh)
            if self.draft_pool is not None:
                self.draft_pool = smesh.shard_pool(self.draft_pool, mesh)
        self.free_blocks: List[int] = list(range(self.n_blocks))
        # Prefix cache (vLLM-style, r5): full prompt blocks are keyed by
        # a position-invariant chain hash of their tokens; admission
        # reuses a cached chain's blocks (refcounted) instead of
        # re-prefilling them, and completed requests RETAIN their keyed
        # blocks in the store's idle LRU until allocation pressure
        # evicts them — so the /chat pattern of identical system prompts
        # across sequential requests skips the shared prefill entirely.
        # Hits are token-identical to a cold batcher in the tested
        # (CPU fp32) configurations — the suffix path computes its
        # activations in a differently-shaped dispatch than a cold full
        # prefill, so on-chip bf16 identity is a parity test away, not a
        # theorem.  Enabled by default; ``prefix_cache=False`` disables
        # matching
        # and retention (refcounts still maintained — the mechanism is
        # the same, it just never hits).
        #
        # The INDEX behind the cache lives in kvcache.py
        # (``prefix_index``, what the server reports: "radix" —
        # block-granular trie, partial-prefix hits shared across all
        # chains, leaves-first eviction, host-tier residency; "off").
        # ``host_kv_blocks`` > 0 attaches the host-DRAM tier (inert with
        # the cache off, see kvcache.make_prefix_store): cold blocks
        # demote into it instead of being freed, and admissions whose
        # matched prefix includes demoted blocks swap them back in
        # asynchronously through the ``restoring`` admission state
        # (module docstring, "KV capacity").
        self.prefix_index = "radix" if prefix_cache else "off"
        self.host_kv_blocks = max(0, int(host_kv_blocks))
        self.prefix_cache_enabled = bool(prefix_cache)
        self._store = make_prefix_store(
            self.prefix_index, host_blocks=self.host_kv_blocks,
            on_event=self.obs.annotate,
        )
        # The store's chain digest, surfaced as a batcher attribute so
        # HTTP handler threads (/debug/kv, /healthz, /metrics) can read
        # it WITHOUT touching the thread-confined ``_store`` — the
        # digest carries its own leaf lock (kvcache.KvDigest; lockcheck
        # registered), making it the one piece of KV state that is
        # legitimately cross-thread.
        self.kv_digest = self._store.digest
        if self.n_snapshots:
            self._store.enable_snapshots(self.n_snapshots)
        # Recurrent-state snapshots: taken (a chunk's end state copied
        # out), restored (a prefix hit resumed from one), and the prompt
        # tokens a match gave up because no snapshot stood behind them.
        self.ssm_snapshots_taken_total = 0
        self.ssm_snapshots_restored_total = 0
        self.ssm_match_tokens_cut_total = 0
        # Bytes one pool block occupies (k+v+pos+scales, draft twins
        # included) — the duplicate-chain accounting unit the router's
        # fleet cache view multiplies by.  Ctor-stable.
        self.block_bytes = pool_block_bytes(self.pool) + (
            pool_block_bytes(self.draft_pool) if self.spec else 0
        )
        self._block_refs: Dict[int, int] = {}    # block -> active users
        # In-flight swap-ins (the ``restoring`` admission state) and
        # completed ones awaiting a free slot.  ``swap_poll_min`` is a
        # determinism lever for drills/tests: it holds a READY swap-in
        # for at least that many poll intervals so the restoring
        # window is observable (0 = adopt as soon as the transfer
        # lands).
        self._restoring: List[_Restore] = []
        self._restored_ready: List[
            Tuple[_Request, List[bytes], List[int]]
        ] = []
        self.swap_poll_min = 0
        # Non-finite-guard channel: (request_id, message) pairs for
        # requests whose dispatch produced NaN/Inf logits — the slot is
        # freed immediately and the server fails just that request with
        # a clean error instead of streaming garbage (``pop_failed``).
        self.failed: List[Tuple[int, str]] = []
        self.nonfinite_rows_total = 0
        # Degradation attribution: the features (degrade.FEATURES names)
        # in play for the most recent jitted dispatch, and the union over
        # the current step() call.  The server reads the former to
        # attribute a dispatch exception and the latter to credit
        # probe successes.
        self.last_dispatch_features: Tuple[str, ...] = ()
        self.last_step_features: set = set()
        # Observability counters (exposed via the HTTP /metrics endpoint).
        self.emitted_total = 0
        self.steps_total = 0
        self.drafts_proposed = 0
        self.drafts_accepted = 0
        self.prefix_requests_hit = 0
        self.prefix_blocks_reused = 0
        # KV-capacity observability: prompt tokens admitted vs prompt
        # tokens served from cached prefix blocks (the
        # prefix_hit_tokens_ratio numerator/denominator), plus the
        # host-tier swap counters (blocks demoted D2H, blocks restored
        # H2D, cumulative swap-in latency, clean swap failures).
        self.prompt_tokens_total = 0
        self.prefix_hit_tokens_total = 0
        self.swap_out_blocks_total = 0
        self.swap_in_blocks_total = 0
        self.swap_ins_total = 0
        self.swap_in_ms_total = 0.0
        self.swap_failures_total = 0
        # Disaggregation handoff (export_prefix / import_prefix):
        # prefix blocks shipped to / landed from peer replicas, plus
        # the handoff EVENT counts (calls that moved >= 1 block — the
        # per-event ledger the KV telemetry layer exports next to the
        # digest's publish/evict/demote/restore counters).
        self.kv_export_blocks_total = 0
        self.kv_import_blocks_total = 0
        self.kv_export_events_total = 0
        self.kv_import_events_total = 0
        # Handoff hardening (r14): imports that hit the wall timeout
        # and unwound cleanly, and exported blocks demoted/dropped at
        # the source so the migration deduplicates instead of copying.
        self.kv_handoff_aborted_total = 0
        self.kv_export_demoted_blocks_total = 0
        # Host-side numpy mirrors of the per-slot decode state — the
        # AUTHORITATIVE copy for all host bookkeeping (admission
        # capacity, slot frees, replay).  The chunked decode path keeps
        # DEVICE-RESIDENT twins (``d_*`` below) that are written
        # incrementally at admission/free/cancel time via ``_scatter_rows``
        # (one dispatch per batch of dirty rows) and advanced ON DEVICE
        # by ``_paged_decode_chunk`` / ``_spec_rounds_chunk`` —
        # steady-state decode uploads nothing and fetches one packed
        # token block per chunk.
        B, MB = n_slots, self.blocks_per_slot
        # Row placer: the mesh-sharded upload for [B, ...] per-slot
        # device arrays (plain jnp.asarray without placement).
        self._rows = (
            functools.partial(smesh.place_rows, mesh)
            if self._mesh_placed else jnp.asarray
        )
        self.table = np.full((B, MB), self.n_blocks, np.int32)
        self.n_alloc = np.zeros((B,), np.int32)
        self.fill = np.zeros((B,), np.int32)
        self.tau = self._rows(jnp.zeros((B,), jnp.int32))
        self.pos = np.zeros((B,), np.int32)
        self.active = np.zeros((B,), bool)
        self.keys = self._rows(jnp.zeros((B, 2), jnp.uint32))
        self.temp_arr = np.zeros((B,), np.float32)
        self.top_p_arr = np.ones((B,), np.float32)
        self.top_k_arr = np.zeros((B,), np.int32)
        # Per-slot generation budget (max_new - emitted) and -1-padded
        # per-slot stop sets — the on-device stop detection's inputs.
        # The stop table's width grows in pow2 buckets as requests with
        # larger stop sets arrive (bounded jit-cache growth).
        self.remaining = np.zeros((B,), np.int32)
        w0 = pow2_bucket(len(self.default_stop))
        self.stop_tab = np.full((B, w0), -1, np.int32)
        # decode_chunk: max fused decode iterations per dispatch (the
        # effective K per dispatch adapts — see _pick_chunk — and is
        # always a power of two <= this).  1 = one dispatch a token.
        self.decode_chunk = max(1, int(decode_chunk))
        # spec_rounds: max fused speculative draft+verify ROUNDS per
        # dispatch (the speculative twin of decode_chunk; the effective
        # R adapts through the same _pick_chunk policy).  1 = one
        # dispatch a round.
        self.spec_rounds = max(1, int(spec_rounds))
        # prefill_budget: fused prefill-decode scheduling.  > 0 admits
        # prompts that would stall mid-decode rows through _fused_chunk
        # instead of a whole-prompt _paged_insert dispatch: each chunk
        # dispatch also advances up to this many prompt tokens of at
        # most ONE in-flight admission (queued -> prefilling(off) ->
        # decoding), with the admitted row folding into the decode mask
        # the dispatch its last chunk lands.  0 (the ctor default)
        # keeps every admission on the classic whole-prompt path — the
        # parity oracle; the serving entry points (run.py
        # --prefill-budget) default it on.  A COLD pool (no row
        # mid-decode, no prefill in flight) still admits through the
        # classic batched insert even when fused: there is nobody to
        # stall, and a k-request cold burst pays one dispatch, not k
        # chunk walks.  Speculative batchers keep classic admission
        # (the spec round program has no prefill lane).
        self.prefill_budget = max(0, int(prefill_budget))
        self._pf: Optional[_Prefill] = None
        # Why the last ``_admit`` left the queue's head queued (one of
        # obs.ADMIT_BLOCKED; None: it did not).  The next chunk record's
        # ``blocked``.
        self._blocked: Optional[str] = None
        # Device-resident twins; row-sharded on a
        # placed serving mesh (see _mesh_placed above).
        self.d_table = self._rows(self.table)
        self.d_n_alloc = self._rows(self.n_alloc)
        self.d_fill = self._rows(self.fill)
        self.d_pos = self._rows(self.pos)
        self.d_active = self._rows(self.active)
        self.d_temps = self._rows(self.temp_arr)
        self.d_top_ps = self._rows(self.top_p_arr)
        self.d_top_ks = self._rows(self.top_k_arr)
        self.d_remaining = self._rows(self.remaining)
        self.d_stops = self._rows(self.stop_tab)
        # Model logprob of each slot's pending tau (valid while active),
        # carried through the chunk programs.
        self.d_tau_lp = self._rows(jnp.zeros((B,), jnp.float32))
        # Rows whose mirrors changed since the last device sync
        # (admission / free / cancel); flushed in one _scatter_rows
        # dispatch before the next chunk.
        self._dirty_rows: set = set()
        # Host-boundary instrumentation (asserted by make perf-smoke):
        # device->host fetches and host->device state-sync dispatches
        # performed by step()/admission — the quantities chunked decode
        # exists to amortize.
        self.host_syncs_total = 0
        self.state_uploads_total = 0
        self.decode_dispatches_total = 0
        self.decode_chunk_last = 0
        self._admit_dispatches = 0
        self._admits_at_last_chunk = 0
        # Speculative-path observability: the effective R of the most
        # recent spec dispatch, its dispatch/sync/token counters (the
        # spec twin of host_syncs_per_token), and a window of recent
        # per-dispatch (proposed, accepted) pairs so /metrics can report
        # a CURRENT acceptance rate (the lifetime ratio hides a draft
        # going stale mid-run).
        self.spec_rounds_last = 0
        self.spec_dispatches_total = 0
        self.spec_host_syncs_total = 0
        self.spec_emitted_total = 0
        self._accept_window: deque = deque(maxlen=64)
        # Fused prefill-decode observability: chunk dispatches that
        # carried a prefill lane, admissions routed through the fused
        # path, and the wall time classic whole-prompt admission
        # dispatches spent while >= 1 row was mid-decode — the decode
        # stall fused scheduling exists to eliminate (stays ~0 with
        # prefill_budget > 0; approximate on the suffix path, whose
        # dispatch is async).
        self.prefill_chunks_total = 0
        # Routed experts: ``ops.moe.STATS`` from the router's own output,
        # summed over the chunk fetches that brought them.  Zero on a
        # configuration without.
        self.moe_totals = dict.fromkeys(_MOE_STATS, 0)
        # Window and full attention layers: the paged decode kernel's live
        # grid steps by layer kind (``afmoe.ATTN_STATS``), behind the
        # routing counts in the same fetch.  Zero on a configuration without.
        # Learned sparse attention: what the paged decode rows' selection
        # chose from (``dsa_moe.SELECT_STATS``), behind those again.
        self.attn_step_totals = dict.fromkeys(_ATTN_STATS + _SELECT_STATS, 0)
        # A multi-stream residual: tokens x mHC units whose mixing matrix
        # ended off doubly stochastic, and those counted (``ops.mhc.STATS``),
        # right behind the routing counts in that block's fetch.
        self.hc_totals = dict.fromkeys(_HC_STATS, 0)
        self.prefill_ctx_slots_attended_total = 0
        self.prefill_ctx_slots_view_total = 0
        self.prefill_blocks_written_total = 0
        self.prefill_pairs_written_total = 0
        # Fused dispatches submitted while requests queued for the lane
        # (the state _QUEUED_LANE_CAP answers); over
        # ``prefill_chunks_total``, which counts every fused dispatch.
        self.fused_dispatches_queued_total = 0
        self.fused_dispatches_merged_total = 0
        self.fused_merged_rows_total = 0
        # What each fused dispatch's admission sample cost
        # (``_admission_sample``; they add up to ``prefill_chunks_total``).
        self.first_sample_totals = dict.fromkeys(_FIRST_SAMPLE, 0)
        self.fused_admissions_total = 0
        self.decode_stall_ms_total = 0.0

        self.slots: Dict[int, Optional[_Slot]] = {
            b: None for b in range(n_slots)
        }
        self.queue: List[_Request] = []
        self._next_id = 0

    # -- public API ---------------------------------------------------------

    def rebuild(self) -> "ContinuousBatcher":
        """Fresh batcher with this one's construction: new KV pool and
        host-side slot/queue/cache state from the still-held params (the
        jitted step programs are cached per-function, so no recompile).
        The crash-recovery path: after a dispatch exception the old
        instance's device state is suspect; callers resubmit every
        in-flight request (prompt + delivered tokens as the new prompt)
        against the rebuilt instance and drop this one.  (The
        degradation layer does NOT go through this method — it rebuilds
        from the server-retained original ctor state with fallback
        substitutions, see ``LLMServer._build_batcher``.)"""
        return ContinuousBatcher(
            self.params, self.config, **self._ctor_kwargs
        )

    def default_seed(self, rid: int) -> int:
        """The PRNG seed a request without an explicit one derives from
        the pool seed and its id (the exact mix ``_request_key`` uses).
        Exposed so a recovery layer can pin a replayed request to its
        original chain start instead of a new id's derivation."""
        return (self.seed * 1000003 + rid) & 0x7FFFFFFF

    def _fault(self, site: str) -> None:
        """Named fault-injection hook (no-op without an injector)."""
        if self.fault_injector is not None:
            self.fault_injector.fire(site)

    def _record_dispatch(self, features: Sequence[str]) -> None:
        """Note which degradable features the NEXT jitted dispatch
        exercises (set before the site hooks fire, so an exception out
        of either the hook or the dispatch itself is attributable)."""
        self.last_dispatch_features = tuple(features)
        self.last_step_features.update(features)

    def _take_nan(self) -> bool:
        """Consume an armed ``nan`` fault (the non-finite guard's test
        lever); no-op without an injector."""
        return (
            self.fault_injector is not None
            and self.fault_injector.take_nan()
        )

    def pop_failed(self) -> List[Tuple[int, str]]:
        """Drain (request_id, message) for requests failed by the
        non-finite guard since the last call.  Their slots and blocks
        are already freed; the server maps these to per-request HTTP
        errors."""
        out, self.failed = self.failed, []
        return out

    def _fail_slot(
        self, b: int, message: str, device_done: bool = False
    ) -> None:
        """Fail slot ``b``'s request with ``message``: record it for
        ``pop_failed`` and free the slot.  The request's freshly written
        prompt blocks are UNPUBLISHED from the prefix index first — KV
        produced by a dispatch that emitted non-finite logits must never
        be retained for future cache hits.  Reused hit blocks
        (``slot.shared`` leading ones) hold earlier healthy dispatches'
        KV and stay published — dropping a popular shared system
        prompt's chain over one poisoned suffix would cold-prefill the
        whole fleet.  ``device_done`` — see ``_free_slot``."""
        slot = self.slots[b]
        assert slot is not None
        stranded: List[int] = []
        for blk in slot.blocks[slot.shared:]:
            stranded.extend(self._store.unpublish(blk))
        # A radix unpublish drops the node's SUBTREE too (deeper shared
        # chain blocks only reachable through the suspect node); idle
        # retained blocks stranded by that go back to the free list.
        self._invalidate_and_free(stranded)
        self.failed.append((slot.request_id, message))
        self.nonfinite_rows_total += 1
        self.obs.request_end(slot.request_id, "failed", message)
        self._free_slot(b, device_done=device_done)

    def submit(
        self,
        prompt_tokens: Sequence[int],
        max_new_tokens: int = 256,
        stop_tokens: Optional[Tuple[int, ...]] = None,
        temperature: Optional[float] = None,
        top_p: Optional[float] = None,
        top_k: Optional[int] = None,
        seed: Optional[int] = None,
        received_at: Optional[float] = None,
    ) -> int:
        """Queue a request; returns its id.  Tokens only — tokenize first.

        temperature/top_p/top_k default to the pool-level policy; ``seed``
        starts the request's own PRNG chain (default: derived from the
        pool seed and request id).  ``received_at`` (``time.monotonic()``
        when the caller accepted the request — the server's POST
        arrival) starts the request's timeline there, with a
        ``received`` span ahead of ``queued``.
        """
        if not prompt_tokens:
            raise ValueError("empty prompt")
        # Capacity covers the BLOCK-PADDED prompt: admission pads the
        # prompt to a block multiple and the row's write offset starts
        # there.
        padded = _round_up(len(prompt_tokens), self.block_size)
        if padded + max_new_tokens > self.max_len:
            # Name the padding lever: near-capacity requests that fit
            # unpadded are admissible with a smaller explicit block_size
            # (the >= 8k default is 512 for DMA efficiency — see
            # __init__), and users must be able to self-diagnose that.
            raise ValueError(
                f"prompt ({len(prompt_tokens)} tokens, padded to {padded} "
                f"= a multiple of block_size={self.block_size}) + "
                f"max_new ({max_new_tokens}) exceeds per-request capacity "
                f"{self.max_len}"
                + (
                    "; the unpadded request fits - construct the batcher "
                    "with a smaller block_size to admit it"
                    if len(prompt_tokens) + max_new_tokens <= self.max_len
                    else ""
                )
            )
        rid = self._next_id
        self._next_id += 1
        req = _Request(
            rid=rid,
            tokens=list(prompt_tokens),
            max_new=max_new_tokens,
            stops=(
                self.default_stop if stop_tokens is None
                else frozenset(int(s) for s in stop_tokens)
            ),
            temperature=(
                self.temperature if temperature is None
                else float(temperature)
            ),
            top_p=self.top_p if top_p is None else float(top_p),
            top_k=self.top_k if top_k is None else int(top_k),
            seed=seed,
        )
        if req.blocks_needed(self.block_size) > self.n_blocks:
            raise ValueError(
                f"request needs {req.blocks_needed(self.block_size)} "
                f"blocks; the pool has {self.n_blocks} total"
            )
        # Queue only — admission happens at the next step() boundary, so
        # a burst of submits is admitted as ONE batched prefill dispatch
        # instead of k serialized ones.
        self.queue.append(req)
        self.obs.request_queued(rid, len(req.tokens), received_at)
        return rid

    def pending(self) -> bool:
        return (
            bool(self.queue)
            or bool(self._restoring)
            or bool(self._restored_ready)
            or any(s is not None for s in self.slots.values())
        )

    def cancel(self, request_id: int, outcome: str = "cancelled",
               error: Optional[str] = None) -> bool:
        """Abort a request: dequeue it, or free its slot and blocks
        mid-generation.  Returns False if the id is unknown (already
        finished or never submitted).

        ``outcome`` names the terminal state the request's timeline
        records — "cancelled" (default; client disconnects and explicit
        cancels) or "failed" (the server's deadline reaper passes it
        for timeouts, which the metric registry counts as failures,
        never cancellations).

        Like every batcher method, this must be called from the thread
        that owns the batcher (the serving loop); the HTTP server's
        handler threads never call it directly — they set a flag the
        loop's reap scan acts on.
        """
        for i, req in enumerate(self.queue):
            if req.rid == request_id:
                del self.queue[i]
                self.obs.request_end(request_id, outcome, error)
                return True
        for r in self._restoring:
            if r.req.rid == request_id:
                # Mid-swap cancel: the staged transfer may still be in
                # flight, but nothing was scattered into the pool yet —
                # release the claims, return the fresh blocks, and let
                # the nodes fall back to host residency (slab intact).
                self._restoring.remove(r)
                self._abort_restore(r)
                self.obs.request_end(request_id, outcome, error)
                return True
        for i, (req, chain, hits) in enumerate(self._restored_ready):
            if req.rid == request_id:
                # Restored but not yet admitted: blocks are adopted and
                # claimed — unclaim them back into the idle LRU.
                del self._restored_ready[i]
                self._unclaim_blocks(hits)
                self.obs.request_end(request_id, outcome, error)
                return True
        for b, slot in self.slots.items():
            if slot is not None and slot.request_id == request_id:
                self._free_slot(b)
                self.obs.request_end(request_id, outcome, error)
                return True
        return False

    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens accepted (speculative mode)."""
        if not self.drafts_proposed:
            return 0.0
        return self.drafts_accepted / self.drafts_proposed

    def describe(self) -> Dict[str, Any]:
        """Ctor-stable configuration snapshot — the ``config`` section
        of the ``/debug/bundle`` flight-recorder artifact (server.py).
        Reads only geometry/policy values fixed at construction (the
        mutable knobs — live prefill_budget under a brownout, live
        occupancy — belong to stats()/healthz), so it is safe from any
        thread without a pragma."""
        kw = self._ctor_kwargs
        return {
            "n_slots": self.n_slots,
            "max_len": self.max_len,
            "block_size": self.block_size,
            "n_blocks": self.n_blocks,
            "block_bytes": self.block_bytes,
            "decode_chunk": int(kw["decode_chunk"]),
            "spec_rounds": int(kw["spec_rounds"]),
            "speculative": self.spec,
            "n_draft": self.n_draft if self.spec else 0,
            "prefill_budget": int(kw["prefill_budget"]),
            "prefix_index": self.prefix_index,
            "host_kv_blocks": self.host_kv_blocks,
            # Recurrent state layers (0 without): what a slot's state and
            # one snapshot of it hold, and the snapshot pool's size.
            "n_snapshots": self.n_snapshots,
            "ssm_state_bytes_per_slot": self.ssm_state_bytes_per_slot,
            "ssm_snapshot_bytes": self.ssm_snapshot_bytes,
            "logprobs": self.logprobs,
            "use_pallas_kernel": bool(self.use_pallas_kernel),
            # The attention path every dispatch of this batcher traces
            # against, and whether the paged decode kernel can run at
            # this geometry at all (else the gathered XLA view serves).
            "attn_impl": self.config.attn_impl,
            "paged_kernel_eligible": _kernel_eligible(
                self.block_size, self.mesh, self.config.kv_heads,
                self.n_slots,
                draft_config=self.draft_config if self.spec else None,
            ),
            "serve_mesh": smesh.mesh_shape(
                self.mesh if self._mesh_placed else None
            ),
        }

    def stats(self) -> Dict[str, float]:
        """Counters for observability (the HTTP /metrics endpoint).

        Runs on HTTP handler threads while the serving loop owns the
        batcher: every read below is a point-in-time snapshot of
        single-writer state (GIL-consistent; a scrape may be one step
        stale, never torn).  ``_pf`` is snapshotted into a local first
        — the loop can null it between a check and a dereference (the
        TOCTOU the lock-discipline checker flagged)."""
        # audit: racy-read(point-in-time /metrics snapshot of
        # single-writer loop state; stale by <= 1 step, never torn)
        pf = self._pf
        dg = self.kv_digest.summary()  # lock-guarded, O(1)
        out: Dict[str, float] = {} if self.fault_injector is None else (
            dict(self.fault_injector.stats())
        )
        # audit: racy-read(point-in-time /metrics snapshot of
        # single-writer loop state; stale by <= 1 step, never torn)
        out.update({
            "emitted_tokens_total": self.emitted_total,
            "decode_steps_total": self.steps_total,
            "active_slots": sum(
                s is not None for s in self.slots.values()
            ),
            "queued_requests": len(self.queue),
            "free_blocks": len(self.free_blocks),
            "total_blocks": self.n_blocks,
            "drafts_proposed_total": self.drafts_proposed,
            "drafts_accepted_total": self.drafts_accepted,
            "draft_acceptance_rate": self.acceptance_rate(),
            # "prefix_cached_blocks" predates the radix index and is
            # KEPT as an alias of the store's idle resident count so
            # existing dashboards don't break.
            "prefix_cached_blocks": self._store.cached_blocks(),
            "prefix_requests_hit_total": self.prefix_requests_hit,
            "prefix_blocks_reused_total": self.prefix_blocks_reused,
            # KV-capacity subsystem (kvcache.py): radix index size,
            # the fraction of admitted prompt tokens served from
            # cached prefix blocks, and the host-tier swap ledger
            # (blocks demoted D2H / restored H2D, in-flight swap-ins,
            # cumulative swap-in wall time, clean per-request swap
            # failures).
            "radix_nodes_total": self._store.nodes_total(),
            "prefix_hit_tokens_ratio": (
                self.prefix_hit_tokens_total
                / max(1, self.prompt_tokens_total)
            ),
            "host_kv_blocks": self.host_kv_blocks,
            "host_tier_blocks": self._store.host_blocks(),
            "swap_queue_depth": len(self._restoring),
            "swap_ins_total": self.swap_ins_total,
            "swap_in_blocks_total": self.swap_in_blocks_total,
            "swap_out_blocks_total": self.swap_out_blocks_total,
            "swap_in_ms_total": round(self.swap_in_ms_total, 3),
            "swap_failures_total": self.swap_failures_total,
            # Chain-digest surface (kvcache.KvDigest, its own leaf
            # lock): digest versions for staleness detection plus the
            # per-event publish/evict/demote/restore ledger — the
            # replica half of the fleet cache view.
            "kv_digest_version": dg["version"],
            "kv_digest_loss_version": dg["loss_version"],
            "kv_publish_events_total": dg["publishes_total"],
            "kv_evict_events_total": dg["evictions_total"],
            "kv_demote_events_total": dg["demotions_total"],
            "kv_restore_events_total": dg["restores_total"],
            "kv_host_evict_events_total": dg["host_evictions_total"],
            "kv_block_bytes": self.block_bytes,
            # Disaggregation handoff ledger + serving-mesh shape (1/1
            # off-mesh AND on unplaced meshes — the gauge reports the
            # sharding actually ACTIVE, not the mesh the batcher was
            # handed; the router's aggregate view labels these per
            # replica).
            "kv_export_blocks_total": self.kv_export_blocks_total,
            "kv_import_blocks_total": self.kv_import_blocks_total,
            "kv_export_events_total": self.kv_export_events_total,
            "kv_import_events_total": self.kv_import_events_total,
            "kv_handoff_aborted_total": self.kv_handoff_aborted_total,
            "kv_export_demoted_blocks_total": (
                self.kv_export_demoted_blocks_total
            ),
            "serve_mesh_data": (
                smesh.mesh_shape(self.mesh)["data"]
                if self._mesh_placed else 1
            ),
            "serve_mesh_tensor": (
                smesh.mesh_shape(self.mesh)["tensor"]
                if self._mesh_placed else 1
            ),
            "nonfinite_rows_total": self.nonfinite_rows_total,
            # Chunked-decode observability: the effective K of the most
            # recent chunk dispatch, dispatch count, and the host-
            # boundary traffic the chunking amortizes (syncs per emitted
            # token trends toward 1/K in steady state).
            "decode_chunk_size": self.decode_chunk_last,
            "decode_dispatches_total": self.decode_dispatches_total,
            "host_syncs_total": self.host_syncs_total,
            "state_uploads_total": self.state_uploads_total,
            "host_syncs_per_token": (
                self.host_syncs_total / max(1, self.emitted_total)
            ),
            # Speculative-path observability (zero / empty when the
            # batcher has no draft model): the effective R of the most
            # recent fused spec dispatch, its host-boundary cost per
            # emitted token, and the acceptance rate over the recent
            # dispatch window (the lifetime draft_acceptance_rate above
            # cannot show a draft going stale mid-run).
            "spec_rounds_per_dispatch": self.spec_rounds_last,
            "spec_dispatches_total": self.spec_dispatches_total,
            "spec_host_syncs_per_token": (
                self.spec_host_syncs_total
                / max(1, self.spec_emitted_total)
            ),
            "spec_window_acceptance_rate": self._window_acceptance(),
            # Fused prefill-decode scheduling (zero / empty with
            # prefill_budget=0): prompt tokens of the in-flight
            # admission still to prefill, chunk dispatches that carried
            # a prefill lane, admissions routed through the fused path,
            # and the cumulative decode stall classic whole-prompt
            # admissions cost (≈0 once fused scheduling is on).
            "prefill_budget": self.prefill_budget,
            "prefill_tokens_inflight": (
                pf.remaining_tokens if pf is not None else 0
            ),
            "prefill_chunks_total": self.prefill_chunks_total,
            "prefill_ctx_slots_attended_total": (
                self.prefill_ctx_slots_attended_total
            ),
            "prefill_ctx_slots_view_total": self.prefill_ctx_slots_view_total,
            "prefill_blocks_written_total": self.prefill_blocks_written_total,
            "prefill_pairs_written_total": self.prefill_pairs_written_total,
            "fused_dispatches_total": self.prefill_chunks_total,
            "fused_dispatches_queued_total": (
                self.fused_dispatches_queued_total
            ),
            **{f"moe_{k}_total": v for k, v in self.moe_totals.items()},
            **{f"attn_{k}_total": v for k, v in self.attn_step_totals.items()},
            **{f"hc_{k}_total": v for k, v in self.hc_totals.items()},
            "ssm_snapshots_taken_total": self.ssm_snapshots_taken_total,
            "ssm_snapshots_restored_total": self.ssm_snapshots_restored_total,
            "ssm_snapshots_evicted_total": getattr(
                self._store, "snapshots_evicted_total", 0),
            "ssm_match_tokens_cut_total": self.ssm_match_tokens_cut_total,
            "ssm_snapshots_in_use": (
                self._store.snapshots_in_use() if self.n_snapshots else 0),
            "ssm_state_bytes_per_slot": self.ssm_state_bytes_per_slot,
            "ssm_snapshot_bytes": self.ssm_snapshot_bytes,
            "fused_admissions_total": self.fused_admissions_total,
            # Fused dispatches whose first decode iteration rode the
            # prompt chunk's pass over the weights (``_mixed_pass``), and
            # the decoding rows that rode it.
            "fused_dispatches_merged_total": (
                self.fused_dispatches_merged_total
            ),
            "fused_merged_rows_total": self.fused_merged_rows_total,
            **{f"first_sample_{k}_total": v
               for k, v in self.first_sample_totals.items()},
            "decode_stall_ms_total": round(self.decode_stall_ms_total, 3),
        })
        return out

    def _window_acceptance(self) -> float:
        """Acceptance rate over the recent spec-dispatch window.

        Called from /metrics handler threads: iterating the live deque
        while the loop appends raises RuntimeError mid-scrape, so take
        an atomic ``list()`` snapshot first (C-level copy under the
        GIL) — the race the lock-discipline checker flagged."""
        # audit: racy-read(atomic list() snapshot of the single-writer
        # window; a scrape may miss the newest dispatch, never crash)
        window = list(self._accept_window)
        proposed = sum(p for p, _ in window)
        if not proposed:
            return 0.0
        return sum(a for _, a in window) / proposed

    def kv_debug_json(self, depth: Optional[int] = None,
                      max_nodes: int = 2048,
                      since: Optional[int] = None) -> Dict[str, Any]:
        """The ``GET /debug/kv[?since=V]`` payload: the chain digest's
        bounded tree walk (per-node chain-prefix hash / depth /
        residency tier / refcount flag / recency) plus the O(1)
        summary with this replica's cache geometry.  With ``since``,
        the INCREMENTAL form: the digest's journaled mutations past
        version V (``{"events": [...], "version": V2}``) so the
        router's global radix index syncs at O(changes) per poll; when
        the bounded journal cannot prove completeness (consumer too
        far behind, or a rebuild reset the digest) the reply falls
        back to the full walk tagged ``"resync": true``.  Safe from
        HTTP handler threads: it reads ONLY the lock-guarded digest
        (kvcache.KvDigest) and ctor-stable geometry scalars, plus two
        single-writer token counters whose point-in-time reads are the
        same /metrics snapshot contract ``stats()`` documents — never
        the thread-confined store or pool."""
        if since is not None:
            got = self.kv_digest.events_since(since)
            if got is not None:
                events, version = got
                out: Dict[str, Any] = {
                    "version": version, "since": since,
                    "events": events,
                }
                out["summary"] = self._kv_summary()
                return out
        out = self.kv_digest.nodes_json(depth=depth, max_nodes=max_nodes)
        if since is not None:
            out["resync"] = True
        out["summary"] = self._kv_summary()
        return out

    def _kv_summary(self) -> Dict[str, Any]:
        """The /debug/kv ``summary`` section: digest aggregates plus
        ctor-stable cache geometry (same cross-thread safety argument
        as ``kv_debug_json``)."""
        summary = self.kv_digest.summary()
        summary.update({
            "prefix_index": self.prefix_index,
            "block_size": self.block_size,
            "block_bytes": self.block_bytes,
            "total_blocks": self.n_blocks,
            "host_kv_blocks": self.host_kv_blocks,
            # audit: racy-read(point-in-time snapshot of single-writer
            # hit counters; stale by <= 1 admission, never torn — the
            # fleet view's hit-ratio numerator/denominator)
            "prefix_hit_tokens_total": self.prefix_hit_tokens_total,
            "prompt_tokens_total": self.prompt_tokens_total,
        })
        return summary

    def step(self) -> List[Tuple]:
        """One decode dispatch for every active slot.

        Returns [(request_id, token, done)] for tokens emitted this call
        — up to the effective chunk size K per slot, up to
        R * (``n_draft`` + 1) per slot in speculative mode.  With
        ``logprobs=True`` each tuple carries a
        4th element: the token's model logprob (fp32 log-softmax of the
        raw logits — what ``engine.score`` reports for the position).
        Finished slots free their blocks and queued requests are
        admitted for the NEXT step.

        Chunked decode contract (non-speculative path): one call runs K
        fused decode iterations inside a single jitted program
        (``_paged_decode_chunk``), with stop-token / max_new / non-finite
        handling ON DEVICE, and pays exactly one device->host fetch (the
        packed token block).  Batcher state lives device-resident; the
        host mirrors advance by replaying the block.  K adapts: 1 when
        a classic admission just landed; while requests queue,
        <= _QUEUED_CHUNK_CAP on a plain decode dispatch (they wait for a
        SLOT: slot turnaround) and <= _QUEUED_LANE_CAP on a dispatch that
        carries a prompt chunk (a slot is free and they wait for the one
        prefill LANE, which every iteration past the first holds up);
        up to ``decode_chunk`` (pow2, clamped to the largest remaining
        budget) once the queue is empty.
        """
        self.last_step_features = set()
        # Fused scheduling routes warm admissions through the chunk
        # dispatch itself (no insert program), so the deferred-error
        # barrier below — which exists to keep attribution on a CLASSIC
        # insert dispatch — must not fire for them: it would re-add the
        # per-dispatch host sync chunking removed.
        classic_admission_possible = not (
            self._fused_scheduling()
            and (self._pf is not None or bool(np.any(self.active)))
        )
        if (
            classic_admission_possible
            and (self.queue or self._restoring or self._restored_ready)
            and any(s is not None for s in self.slots.values())
            and any(s is None for s in self.slots.values())
        ):
            # Deferred-error barrier, only when _admit is about to
            # record NEW dispatches: jax dispatch is async, so the
            # previous step's device error can surface at the next host
            # sync — which must happen while ``last_dispatch_features``
            # still names the dispatch that produced it, not after
            # admission overwrites the attribution record.  Admissions
            # are rare relative to steps, so the extra [B] fetch stays
            # off the steady-state hot path.  A completed swap-in can
            # admit through the same classic insert program even with
            # the queue empty (``_restored_ready``), so in-flight and
            # landed restores arm the barrier too.
            self.obs.loop_phase("barrier")
            # audit: host-fetch(deferred-error barrier before admission
            # overwrites dispatch attribution; counted)
            np.asarray(self.tau)
            self.host_syncs_total += 1
        self._admit()
        if not any(s is not None for s in self.slots.values()):
            return []
        if self.spec:
            return self._step_spec()
        return self._step_chunked()

    _NONFINITE_MSG = (
        "non-finite logits: the model produced NaN/Inf for "
        "this request; it was aborted (server healthy)"
    )

    # Chunk clamp of a PLAIN decode dispatch while requests queue: every
    # slot is taken, the queue waits for one to finish, and the host only
    # learns that at a chunk boundary.  Small enough that a finishing slot
    # is detected within a few iterations (bounded admission latency for
    # the queue head), large enough that a SATURATED server — the normal
    # high-throughput regime, where the queue is never empty — still
    # amortizes the per-dispatch host overhead instead of reverting to
    # one dispatch per token.
    _QUEUED_CHUNK_CAP = 4
    # Chunk clamp of a dispatch that CARRIES A PROMPT CHUNK while requests
    # queue: a slot is free (the server hands the batcher only as many
    # requests as it has free slots), so the queue waits for the one
    # prefill lane, and the lane advances one chunk a dispatch however
    # many decode iterations ride behind it.  Every iteration past the
    # first is a whole pass over the weights for the few rows the slots
    # hold so far, and keeps the next prompt out of an empty one; once
    # the slots are full the batcher's queue is empty and K is
    # ``decode_chunk`` again, which slows the lane — so a clamp that
    # fills the slots too eagerly loses where a prompt chunk costs many
    # iterations.  One constant for every block and cell (v5e, closed
    # loops of 16 clients on 8 slots; PERF.md section 6, PR 33): the 7B
    # dense cell reads 130 tokens/s at 4, 159 at 2, 168-171 at 1; the
    # latent-attention cell 240 at 4 and 217-232 at 1.
    _QUEUED_LANE_CAP = 2

    def _pick_chunk(self, admitted: bool, cap: Optional[int] = None) -> int:
        """Effective K for the next chunk dispatch.  K=1 right after a
        classic admission (the fresh request's first token should not
        wait out a full chunk).  While the queue holds requests the
        clamp follows what they wait for: K <= _QUEUED_CHUNK_CAP on a
        plain decode dispatch (no slot is free; their admission waits on
        one finishing, which the host only learns at a chunk boundary),
        K <= _QUEUED_LANE_CAP on a dispatch that carries a prompt chunk
        (``self._pf``: a slot is free and they wait for the prefill
        lane, which moves once a dispatch).  Otherwise the largest power
        of two <= min(cap, max remaining budget) — pow2 throughout, so
        the jit cache holds O(log cap) chunk programs.  A prefilling row
        has emitted nothing, so while a chunk rides the budget clamp is
        its ``max_new`` at least and a fused dispatch's K is two-valued:
        the lane cap under a queue, ``decode_chunk`` without.  ``cap``
        defaults to ``decode_chunk``; the speculative path passes
        ``spec_rounds`` (each round emits at least one token, so
        clamping R by the token budget bounds the dead masked tail the
        same way it does for K; it has no prefill lane, so R only ever
        meets _QUEUED_CHUNK_CAP).

        ``admitted`` only counts CLASSIC whole-prompt admissions: a
        fused admission's first token is sampled inside the chunk
        dispatch chain itself, so K no longer collapses to 1 while a
        prefill rides along — exactly when a burst is hammering the
        server (the queued clamps still bound the queue head's wait, on
        a finishing slot or on the lane)."""
        cap = self.decode_chunk if cap is None else cap
        if cap <= 1 or admitted:
            return 1
        rem = max(
            s.max_new - len(s.emitted)
            for s in self.slots.values() if s is not None
        )
        k = max(1, min(cap, rem))
        if self.queue:
            k = min(
                k,
                self._QUEUED_CHUNK_CAP if self._pf is None
                else self._QUEUED_LANE_CAP,
            )
        return 1 << (k.bit_length() - 1)

    def _upload(self, host: np.ndarray) -> jnp.ndarray:
        """One host->device copy by the loop thread outside a jitted
        call, counted (``host_uploads_total``, the next record's
        ``uploads``).  Replicated under a mesh, like every unplaced
        operand.  Where the weights are COMMITTED to one device the copy is
        committed there too (``_upload_to``): beside committed operands an
        uncommitted one selects another executable than the same operand
        once a program has handed it back (the donated ``pf_vec``), and a
        walk's first chunk must run the program of its later chunks — one
        compile a (buffer length, K) pair, none left for a rare later
        chunk to meet first."""
        self.obs.count_upload()
        # audit: host-upload(the counted copy outside a jitted call: a
        # fused admission's packed vector, once an admission)
        return (jnp.asarray(host) if self._upload_to is None
                else jax.device_put(host, self._upload_to))

    def _sync_device_rows(self) -> None:
        """Flush host-side per-row state changes (admission / free /
        cancel) to the device-resident twins in ONE ``_scatter_rows``
        dispatch over ONE host buffer (``pack_rows``: the dirty rows'
        index and ten fields as an int32 matrix, unpacked on the device),
        handed to the call as it is: the dispatch is the sync's one
        crossing, no copy beside it (on a v5e a ``jnp.asarray`` costs the
        loop thread 0.3 ms alone and 1.1-1.8 ms beside 16-32 handler
        threads, whatever its size; a host operand of a call it makes
        anyway adds ~0.05: PERF.md section 6, PR 39).  No dirty rows (the
        steady state) -> no dispatch.  A stop table that grew re-uploads
        the whole twin first (a copy of its own, rare)."""
        if not self._dirty_rows:
            return
        with self.obs.loop_span("prep.sync_rows"):
            if self.d_stops.shape != self.stop_tab.shape:
                # Stop-table width grew (pow2-bucketed): rebuild the device
                # twin wholesale before the row scatter — admission-time
                # only, and the array is [B, S] ints.
                self.d_stops = self._rows(self.stop_tab)
                self.obs.count_upload()
            rows = sorted(self._dirty_rows)
            self._dirty_rows.clear()
            Rb = pow2_bucket(len(rows))  # pow2 jit-cache bucket
            packed = pack_rows(
                rows, Rb, self.n_slots,  # pad rows drop
                self.table, self.n_alloc, self.fill, self.pos, self.active,
                self.temp_arr, self.top_p_arr, self.top_k_arr,
                self.remaining, self.stop_tab,
            )
            state = (
                self.d_table, self.d_n_alloc, self.d_fill, self.d_pos,
                self.d_active, self.d_temps, self.d_top_ps, self.d_top_ks,
                self.d_remaining, self.d_stops,
            )
            _obs_mod.attribute_compiles(self.obs, "_scatter_rows")
            # audit: host-upload(the packed dirty rows as a HOST operand
            # of the sync's own dispatch, once an admission / free /
            # cancel batch; no copy beside it)
            (self.d_table, self.d_n_alloc, self.d_fill, self.d_pos,
             self.d_active, self.d_temps, self.d_top_ps, self.d_top_ks,
             self.d_remaining, self.d_stops) = _scatter_rows(state, packed)
            self.state_uploads_total += 1

    def _step_chunked(self) -> List[Tuple]:
        """Non-speculative step: one fused K-iteration chunk dispatch,
        one packed fetch, then the host replays the block to advance its
        mirrors and emit events.  While an admission is mid-prefill
        (``self._pf``) the dispatch is ``_fused_chunk`` — the same K
        decode iterations PLUS one bounded prefill chunk, same packed
        fetch — so decoding rows keep emitting while the prompt lands,
        and K does NOT collapse to 1 (fused admissions never set the
        ``admitted`` reset; the first token rides this dispatch chain
        regardless of K).  While requests queue behind the lane such a
        dispatch runs K <= _QUEUED_LANE_CAP (``_pick_chunk``); the record
        carries ``queued`` and ``fused_dispatches_queued_total`` counts
        how often that held."""
        # CLASSIC admissions since the last chunk dispatch — including
        # one this step() performed at the PREVIOUS call's trailing
        # _admit().  Fused admissions perform no insert dispatch, so
        # they neither owe the error barrier nor reset K.
        admitted = self._admit_dispatches > self._admits_at_last_chunk
        if admitted:
            # Surface any async admission-dispatch error NOW, while
            # last_dispatch_features still names the insert (the chunk's
            # _record_dispatch below would otherwise steal attribution).
            self.obs.loop_phase("barrier")
            # audit: host-fetch(post-admission error barrier; counted)
            np.asarray(self.tau)
            self.host_syncs_total += 1
        self.obs.loop_phase("prep")
        self._admits_at_last_chunk = self._admit_dispatches
        pf = self._pf
        # While a prefill is in flight and nothing decodes (a row's last
        # token fell inside another's prefill) the scan half is K
        # all-masked iterations.  That state gets no K of its own: a
        # program variant that it alone reaches is one no warm-up is sure
        # to meet, and such a one compiled inside a served window (4.5 s,
        # v5e; PERF.md section 6, PR 32).
        K = self._pick_chunk(admitted)
        self._sync_device_rows()
        # Injection site "step": fires BEFORE the chunk dispatch; an
        # exception out of the dispatch (or its packed fetch below)
        # reaches the caller with nothing appended to slot.emitted or
        # delivered — recovery replays from the server's delivered-token
        # record, exactly as in the K=1 contract (a mid-prefill request
        # replays from its prompt + delivered tokens like any other).
        # The paged_kernel site fires once per CHUNK dispatch, not per
        # token; when a prefill chunk rides along on the flash path the
        # flash_kernel site fires too (same dispatch, finer
        # attribution — a flash quarantine rebuilds onto attn_impl=xla
        # and the replayed admission continues on the gathered path).
        feats: List[str] = []
        if self.use_pallas_kernel and _kernel_eligible(
            self.block_size, self.mesh, self.config.kv_heads,
            self.n_slots,
        ):
            feats.append("paged_kernel")
        pf_flash = (
            pf is not None and pf.flash
            and self.config.attn_impl in ("auto", "flash")
        )
        if pf_flash:
            feats.append("flash_attention")
        self._record_dispatch(feats)
        self._fault("step")
        if pf is not None:
            # Site "prefill_chunk": indexes prefill-CARRYING dispatches
            # only, so drills can land a fault mid-prefill
            # deterministically (plain decode chunks do not advance its
            # counter).
            self._fault("prefill_chunk")
        if pf_flash:
            self._fault("flash_kernel")
        if "paged_kernel" in feats:
            self._fault("paged_kernel")
        self.steps_total += K
        self.decode_dispatches_total += 1
        self.decode_chunk_last = K
        # Dispatch-span bookkeeping (obs.py): capture the riding rids,
        # prompt tokens this dispatch will advance, and the wall clock
        # BEFORE the dispatch — recorded after the packed fetch, so the
        # span covers submit through sync (pure host bookkeeping; the
        # 1-fetch/0-upload contract is unchanged).
        obs_rids = [
            s.request_id for s in self.slots.values() if s is not None
        ]
        pf_adv = 0 if pf is None else min(pf.chunk, pf.remaining_tokens)
        pf_ctx = None if pf is None else self._pf_ctx_slots(pf, pf_flash)
        pf_write = (
            None if pf is None else {"blocks": self._pf_live_blocks(pf)}
        )
        queued = len(self.queue)
        # Rows whose first iteration rides the chunk's weight pass: the
        # rows decoding at the submit, when the program takes the mixed
        # pass (the predicate it traces by); None when it does not.
        merged_rows = None
        if pf is not None and _mixed_pass(
            self.config, self.pool.quantized, self.mesh,
            "paged_kernel" in feats, K,
        ):
            merged_rows = int(np.sum(self.active))
        pf_done_rid: Optional[int] = None
        pf_ssm = None
        if pf is not None and self.recurrent:
            with self.obs.loop_span("prep.snapshots", rid=pf.req.rid):
                pf_ssm = self._pf_snapshots(pf)
        all_greedy = bool(np.all(self.temp_arr[self.active] == 0.0))
        first_sample = None
        if pf is not None:
            # The prefilling request samples inside the program, so the
            # greedy specialization must account for its policy too.
            all_greedy = all_greedy and pf.req.temperature <= 0.0
            # What the program's admission sample will cost, from the two
            # inputs it branches on (``_admission_sample``).
            first_sample = (
                "skipped" if pf.off + pf.chunk < pf.suffix_len
                else "greedy" if pf.req.temperature <= 0.0 else "drawn"
            )
        # Compile attribution (obs.py): named BEFORE the dispatch so a
        # jit-cache miss books onto the right program.
        prog = "_paged_decode_chunk" if pf is None else "_fused_chunk"
        _obs_mod.attribute_compiles(self.obs, prog)
        kind = "decode" if pf_adv == 0 else "fused"
        self.obs.dispatch_begin(kind, prog, K)
        t0_obs = time.monotonic()
        with self.obs.loop_span("dispatch.submit"):
            if pf is None:
                (packed, self.tau, self.d_tau_lp, self.d_fill, self.d_pos,
                 self.d_active, self.d_remaining, self.keys,
                 self.pool) = _paged_decode_chunk(
                    self.params, self.pool, self.d_table, self.d_n_alloc,
                    self.d_fill, self.tau, self.d_tau_lp, self.d_pos,
                    self.d_active, self.d_remaining, self.d_stops, self.keys,
                    self.d_temps, self.d_top_ps, self.d_top_ks,
                    config=self.config, n_iter=K, all_greedy=all_greedy,
                    mesh=self.mesh, allow_kernel=self.use_pallas_kernel,
                    with_logprobs=self.logprobs, placed=self._mesh_placed,
                )
            else:
                (packed, self.tau, self.d_tau_lp, self.d_fill, self.d_pos,
                 self.d_active, self.d_remaining, self.keys, self.pool,
                 pf.d_vec) = _fused_chunk(
                    self.params, self.pool, self.d_table, self.d_n_alloc,
                    self.d_fill, self.tau, self.d_tau_lp, self.d_pos,
                    self.d_active, self.d_remaining, self.d_stops, self.keys,
                    self.d_temps, self.d_top_ps, self.d_top_ks,
                    pf.d_vec, *(pf_ssm or ((), None))[0],
                    config=self.config, n_iter=K, pf_chunk=pf.chunk,
                    all_greedy=all_greedy, mesh=self.mesh,
                    allow_kernel=self.use_pallas_kernel,
                    with_logprobs=self.logprobs, placed=self._mesh_placed,
                )
        if pf is not None:
            self.prefill_chunks_total += 1
            self.first_sample_totals[first_sample] += 1
            self.fused_dispatches_queued_total += queued > 0
            if merged_rows is not None:
                self.fused_dispatches_merged_total += 1
                self.fused_merged_rows_total += merged_rows
            pf.off += pf.chunk
            if pf.off >= pf.suffix_len:
                # Prefill complete: the device already folded the row
                # into the decode state mid-dispatch (and the scan below
                # emitted its first token); catch the host mirrors up —
                # device_done semantics, no dirty marking — and publish
                # the request's freshly written full prompt blocks
                # (only now do they hold the whole chain's KV).
                b = pf.slot
                self.fill[b] = _round_up(
                    len(pf.req.tokens), self.block_size
                )
                self.pos[b] = len(pf.req.tokens)
                self.active[b] = True
                slot = self.slots[b]
                # FULL chain, not the suffix: the radix publish walk
                # starts at the root, so a suffix-only publication
                # after a partial hit would parent the new nodes at
                # the root under mid-chain keys — unreachable for
                # matching (extensions never hit) and depth-wrong in
                # the digest.  The hit prefix re-publishes as a no-op
                # (existing resident nodes keep their block) and
                # supplies the correct parent chain.
                with self.obs.loop_span("dispatch.publish", rid=pf.req.rid):
                    self._register_chain(
                        slot.blocks[: len(pf.chain)], pf.chain,
                    )
                    self._hang_snapshots(pf)
                pf_done_rid = pf.req.rid
                self._pf = None
        # THE one device->host sync of the chunk: tokens (+ bitcast
        # logprobs) in a single packed array.
        tf_obs = time.monotonic()
        # audit: host-fetch(the one packed [B, K] fetch per chunk; counted)
        arr = np.asarray(packed)
        self.host_syncs_total += 1
        now_obs = time.monotonic()
        moe_counts = hc_counts = None
        if self.pool.stats is not None:
            # Trailing planes of the same fetch (``_pack_stats``).
            counts = [
                int(v) for v in arr[2 if self.logprobs else 1:]
                .reshape(-1)[:self.pool.stats.shape[0]]
            ]
            moe_counts = counts[:len(_MOE_STATS)]
            for name, v in zip(_MOE_STATS, moe_counts):
                self.moe_totals[name] += v
            tail = counts[len(_MOE_STATS):]
            if self.config.hc_mult > 1:
                hc_counts = tail
                for name, v in zip(_HC_STATS, tail):
                    self.hc_totals[name] += v
            else:
                for name, v in zip(self.attn_step_totals, tail):
                    self.attn_step_totals[name] += v
        if pf_ctx is not None:
            self.prefill_ctx_slots_attended_total += pf_ctx[0]
            self.prefill_ctx_slots_view_total += pf_ctx[1]
            self.prefill_blocks_written_total += pf_write["blocks"]
        self.obs.record_dispatch(
            kind=kind,
            k=K, occupancy=len(obs_rids), prefill_tokens=pf_adv,
            wall_ms=(now_obs - t0_obs) * 1000.0,
            fetch_ms=(now_obs - tf_obs) * 1000.0,
            swap_inflight=len(self._restoring), rids=obs_rids,
            program=prog, then="emit", moe=moe_counts, hc=hc_counts,
            prefill_ctx=pf_ctx,
            prefill_write=pf_write,
            queued=queued,
            blocked=self._blocked if queued else None,
            ssm=None if pf_ssm is None else pf_ssm[1],
            merged_rows=merged_rows,
            first_sample=first_sample,
        )
        if pf_done_rid is not None:
            # The prefill's last chunk linked into the prefilling span
            # above; the first token it sampled opens the decoding span.
            self.obs.begin_span(pf_done_rid, "decoding")
        toks = arr[0]
        lps = arr[1].view(np.float32) if self.logprobs else None

        out: List[Tuple] = []
        forced_nan = self._take_nan()
        with self.obs.loop_span("emit.replay"):
            for b, slot in self.slots.items():
                if slot is None:
                    continue
                if forced_nan:
                    # An armed ``nan`` fault (chaos drills) poisons the
                    # first active row, exactly like the K=1 emit scan; the
                    # row's chunk tokens are discarded (the request fails
                    # with a clean error either way).
                    forced_nan = False
                    self._fail_slot(b, self._NONFINITE_MSG)
                    continue
                advanced = 0
                ended = False
                for i in range(toks.shape[1]):
                    tok = int(toks[b, i])
                    if tok == _CHUNK_PAD:
                        # Not decoding at this column.  A row that ended
                        # left the loop at its last token; one that folded
                        # in behind a mixed pass emits from column 1 on.
                        continue
                    if tok < 0:
                        # On-device non-finite sentinel: the device already
                        # folded the row out of the chunk; fail just this
                        # request (tokens before the sentinel were emitted).
                        self._fail_slot(
                            b, self._NONFINITE_MSG, device_done=True
                        )
                        ended = True
                        break
                    slot.emitted.append(tok)
                    self.emitted_total += 1
                    done = (
                        tok in slot.stop_tokens
                        or len(slot.emitted) >= slot.max_new
                    )
                    if self.logprobs:
                        out.append((
                            slot.request_id, tok, done, float(lps[b, i])
                        ))
                    else:
                        out.append((slot.request_id, tok, done))
                    if done:
                        # The device made the same call mid-chunk (stop set
                        # and budget live on device), so the row is already
                        # inactive there — no deactivation upload needed.
                        self.obs.request_end(slot.request_id, "finished")
                        with self.obs.loop_span("emit.free"):
                            self._free_slot(b, device_done=True)
                        ended = True
                        break
                    advanced += 1
                if not ended:
                    # Mirror advance by replay: the device ran one forward
                    # per emitted-and-continued token.
                    self.fill[b] += advanced
                    self.pos[b] += advanced
                    self.remaining[b] = slot.max_new - len(slot.emitted)
        self._admit()
        return out

    def _step_spec(self) -> List[Tuple]:
        """Speculative step: ONE ``_spec_rounds_chunk`` dispatch
        runs R draft+verify rounds with the pending-tau emit, the
        accepted-prefix emit scan, stop/max_new/non-finite folding and
        the fill rewind all ON DEVICE; the host gets one packed
        [B, R, W] block (each round's G+1 token columns + its
        acceptance count + bitcast logprobs) in ONE fetch and replays
        it to advance the mirrors and produce the caller's events.
        Both pools and all per-slot decode state are device-resident
        via the ``d_*`` twins; admission /
        free / cancel sync dirty rows exactly as in ``_step_chunked``,
        so steady state = 1 fetch + 0 uploads per R rounds."""
        admitted = self._admit_dispatches > self._admits_at_last_chunk
        if admitted:
            # Surface any async admission-dispatch error NOW, while
            # last_dispatch_features still names the insert (see
            # _step_chunked).
            self.obs.loop_phase("barrier")
            # audit: host-fetch(post-admission error barrier; counted)
            np.asarray(self.tau)
            self.host_syncs_total += 1
            self.spec_host_syncs_total += 1
        self.obs.loop_phase("prep")
        self._admits_at_last_chunk = self._admit_dispatches
        R = self._pick_chunk(admitted, cap=self.spec_rounds)
        self._sync_device_rows()
        # Fault sites and dispatch attribution fire once per CHUNK
        # dispatch, not once per round — an aborted chunk delivers
        # nothing, so recovery replays all R rounds from the server's
        # delivered-token record, exactly as in the chunked-decode
        # contract.
        feats: List[str] = ["spec_decode"]
        if self._spec_kernel_ok():
            feats.append("paged_kernel")
        self._record_dispatch(feats)
        self._fault("step")
        self._fault("spec_decode")
        if "paged_kernel" in feats:
            self._fault("paged_kernel")
        self.steps_total += R
        self.decode_dispatches_total += 1
        self.spec_dispatches_total += 1
        self.decode_chunk_last = R
        self.spec_rounds_last = R
        obs_rids = [
            s.request_id for s in self.slots.values() if s is not None
        ]
        all_greedy = bool(np.all(self.temp_arr[self.active] == 0.0))
        _obs_mod.attribute_compiles(self.obs, "_spec_rounds_chunk")
        self.obs.dispatch_begin("spec", "_spec_rounds_chunk", R)
        t0_obs = time.monotonic()
        with self.obs.loop_span("dispatch.submit"):
            (packed, self.tau, self.d_tau_lp, self.d_fill, self.d_pos,
             self.d_active, self.d_remaining, self.keys, self.pool,
             self.draft_pool) = _spec_rounds_chunk(
                self.params, self.draft_params, self.pool, self.draft_pool,
                self.d_table, self.d_n_alloc, self.d_fill, self.tau,
                self.d_tau_lp, self.d_pos, self.d_active, self.d_remaining,
                self.d_stops, self.keys, self.d_temps, self.d_top_ps,
                self.d_top_ks,
                t_config=self.config, d_config=self.draft_config,
                n_draft=self.n_draft, n_rounds=R, all_greedy=all_greedy,
                use_kernel=self._spec_kernel_ok(), mesh=self.mesh,
                with_logprobs=self.logprobs, placed=self._mesh_placed,
            )
        # THE one device->host sync of the chunk: tokens, acceptance
        # counts and (bitcast) logprobs in a single packed array.
        tf_obs = time.monotonic()
        # audit: host-fetch(the one packed [B, R, W] fetch per spec
        # chunk; counted)
        arr = np.asarray(packed)  # [B, R, W]
        self.host_syncs_total += 1
        self.spec_host_syncs_total += 1
        now_obs = time.monotonic()
        self.obs.record_dispatch(
            kind="spec", k=R, occupancy=len(obs_rids),
            wall_ms=(now_obs - t0_obs) * 1000.0,
            fetch_ms=(now_obs - tf_obs) * 1000.0,
            swap_inflight=len(self._restoring), rids=obs_rids,
            program="_spec_rounds_chunk", then="emit",
        )
        G = self.n_draft
        toks = arr[:, :, : G + 1]
        accs = arr[:, :, G + 1]
        lps = arr[:, :, G + 2:].view(np.float32) if self.logprobs else None

        out: List[Tuple] = []
        round_proposed = round_accepted = 0
        forced_nan = self._take_nan()
        for b, slot in self.slots.items():
            if slot is None:
                continue
            if forced_nan:
                # An armed ``nan`` fault poisons the first active row,
                # exactly like ``_step_chunked``'s emit scan; the row's
                # chunk tokens are discarded.
                forced_nan = False
                self._fail_slot(b, self._NONFINITE_MSG)
                continue
            fill_adv = 0
            ended = False
            for r in range(R):
                # Column 0: the round's pending-tau emit.
                tok0 = int(toks[b, r, 0])
                if tok0 == _CHUNK_PAD:
                    # Row folded out before this round (every later
                    # round is PAD too).
                    break
                if tok0 < 0:
                    # On-device non-finite sentinel on the pending
                    # token (admission produced NaN/Inf logits).
                    self._fail_slot(
                        b, self._NONFINITE_MSG, device_done=True
                    )
                    ended = True
                    break
                slot.emitted.append(tok0)
                self.emitted_total += 1
                self.spec_emitted_total += 1
                done = (
                    tok0 in slot.stop_tokens
                    or len(slot.emitted) >= slot.max_new
                )
                if self.logprobs:
                    out.append((
                        slot.request_id, tok0, done, float(lps[b, r, 0])
                    ))
                else:
                    out.append((slot.request_id, tok0, done))
                if done:
                    # The device made the same call before running the
                    # round (stop set and budget live on device), so
                    # the row is already inactive there.
                    self.obs.request_end(slot.request_id, "finished")
                    self._free_slot(b, device_done=True)
                    ended = True
                    break
                a = int(accs[b, r])
                assert a >= -1, (b, r, a)  # PAD here would mean the
                # device and host disagreed on liveness — impossible
                # while both fold on the same stop/budget inputs.
                if a < 0:
                    # _spec_rounds_chunk's verify non-finite sentinel:
                    # the round was never committed (all written slots
                    # invalidated in-jit) — fail just this request.
                    self._fail_slot(
                        b, self._NONFINITE_MSG, device_done=True
                    )
                    ended = True
                    break
                self.drafts_proposed += G
                self.drafts_accepted += a
                round_proposed += G
                round_accepted += a
                # Columns 1..a: the round's accepted drafts (the device
                # already blanked everything past a mid-prefix
                # stop/budget hit to _CHUNK_PAD; the host re-detects
                # done from its own stop sets, exactly like
                # _step_chunked's replay).
                for i in range(a):
                    tok = int(toks[b, r, 1 + i])
                    if tok == _CHUNK_PAD:
                        break
                    slot.emitted.append(tok)
                    self.emitted_total += 1
                    self.spec_emitted_total += 1
                    done = (
                        tok in slot.stop_tokens
                        or len(slot.emitted) >= slot.max_new
                    )
                    if self.logprobs:
                        out.append((
                            slot.request_id, tok, done,
                            float(lps[b, r, 1 + i]),
                        ))
                    else:
                        out.append((slot.request_id, tok, done))
                    if done:
                        self.obs.request_end(
                            slot.request_id, "finished"
                        )
                        self._free_slot(b, device_done=True)
                        ended = True
                        break
                if ended:
                    break
                # The round committed a+1 pool slots (tau + accepted
                # drafts; outs[a] is the next pending tau) — the fill
                # rewind the device already applied in-carry.
                fill_adv += a + 1
            if not ended:
                self.fill[b] += fill_adv
                self.pos[b] += fill_adv
                self.remaining[b] = slot.max_new - len(slot.emitted)
        if round_proposed:
            self._accept_window.append((round_proposed, round_accepted))
        self._admit()
        return out

    def _spec_kernel_ok(self) -> bool:
        """Same kernel-eligibility gate as _paged_decode_chunk — literally:
        both call ``_kernel_eligible`` (the T>1 verify adds no
        constraints, it shards identically; the draft model adds its own
        KV-head divisibility)."""
        return self.use_pallas_kernel and _kernel_eligible(
            self.block_size, self.mesh, self.config.kv_heads,
            self.n_slots, draft_config=self.draft_config,
        )

    def run_to_completion(self) -> Dict[int, List[int]]:
        """Drain everything; returns {request_id: emitted tokens}."""
        results: Dict[int, List[int]] = {}
        while self.pending():
            for rid, tok, *_ in self.step():
                results.setdefault(rid, []).append(tok)
        return results

    # -- internals ----------------------------------------------------------

    def _capacity(self) -> int:
        """Allocatable blocks: truly free + evictable cached prefixes."""
        return len(self.free_blocks) + self._store.evictable()

    def _demote_block(self, blk: int) -> Dict[str, Any]:
        """Host-tier demotion D2H: fetch block ``blk``'s KV image (plus
        the draft pool's twin under speculative serving) to host numpy
        BEFORE the allocator invalidates its positions.  Admission-path
        only — never on the decode hot path — and counted separately
        from ``host_syncs_total`` (that counter is the chunked decode
        loop's contract; demotion is capacity traffic)."""
        slab = fetch_slab(self.pool, blk)
        if self.spec:
            slab.update(fetch_slab(self.draft_pool, blk, prefix="d_"))
        self.swap_out_blocks_total += 1
        return slab

    def _alloc_blocks(self, n: int) -> List[int]:
        """Pop n blocks, evicting LRU cached-prefix blocks when the free
        list runs dry — into the host-DRAM tier when one is attached
        (the block's KV demotes and its radix node stays matchable),
        dropped outright otherwise.  Evicted blocks' POSITIONS are
        invalidated here: retained blocks keep valid pos (future
        reusers need them), but a block re-purposed as part of a DECODE
        reservation is only overwritten up to the prompt span — a stale
        pos >= 0 in the beyond-the-prompt region would be attended as a
        live slot."""
        self._fault("alloc")
        out: List[int] = []
        while len(out) < n and self.free_blocks:
            out.append(self.free_blocks.pop(0))
        if len(out) < n:
            with self.obs.loop_span("admit.evict"):
                self._evict_into(out, n)
        return out

    def _evict_into(self, out: List[int], n: int) -> None:
        """``_alloc_blocks`` once the free list is dry: fill ``out`` up to
        ``n`` blocks from the idle LRU (and from what a forced subtree drop
        hands back), then invalidate the evicted blocks' positions."""
        evicted: List[int] = []
        while len(out) < n:
            if self.free_blocks:
                out.append(self.free_blocks.pop(0))
            else:
                blk, extra = self._store.pop_evictable(self._demote_block)
                assert blk is not None, "allocation past capacity"
                evicted.append(blk)
                out.append(blk)
                if extra:
                    # A forced subtree drop (host-LRU victim / stranded
                    # suffix) orphaned additional idle blocks: back to
                    # the free list, positions invalidated.
                    self._invalidate_and_free(extra)
        if evicted:
            # More evictions than one slot's span is impossible in one
            # call (n <= blocks_per_slot), but stay defensive.
            self._invalidate_evicted(evicted)

    def _invalidate_evicted(self, evicted: List[int]) -> None:
        """Invalidate repurposed blocks' pool positions (batched; pads
        drop) — AFTER any demotion fetch, which needs them live."""
        for start in range(0, len(evicted), self.blocks_per_slot):
            ids = np.full(
                (self.blocks_per_slot,), self.n_blocks, np.int32
            )
            chunk = evicted[start:start + self.blocks_per_slot]
            ids[: len(chunk)] = chunk
            _obs_mod.attribute_compiles(self.obs, "_release_blocks")
            # audit: host-upload(the eviction batch's ids as a HOST
            # operand of the release dispatch's own call — and of the
            # draft pool's twin; admission/capacity path, never per-token)
            self.pool = dataclasses.replace(
                self.pool, pos=_release_blocks(self.pool.pos, ids),
            )
            if self.spec:
                self.draft_pool = dataclasses.replace(
                    self.draft_pool,
                    pos=_release_blocks(self.draft_pool.pos, ids),
                )

    def demote_idle(self, n: int) -> int:
        """Proactively demote up to ``n`` idle cached-prefix blocks into
        the host tier, freeing HBM without dropping cache content (the
        pressure path does the same thing lazily inside
        ``_alloc_blocks``; this is the operational lever — and the
        deterministic one for drills).  No-op without a tier; returns
        the number of blocks demoted."""
        if self.host_kv_blocks <= 0 or self._store.kind != "radix":
            return 0
        count = 0
        drained: List[int] = []
        for _ in range(n):
            if not self._store.evictable():
                break
            blk, extra = self._store.pop_evictable(self._demote_block)
            if blk is None:
                break
            drained.append(blk)
            drained.extend(extra)
            count += 1
        # One batched invalidation for the whole sweep instead of one
        # _release_blocks dispatch per demoted block.
        self._invalidate_and_free(drained)
        return count

    def _invalidate_and_free(self, blocks: List[int]) -> None:
        """Return blocks to the free list with their pool positions
        invalidated (a stale pos >= 0 in a re-purposed block's
        beyond-the-prompt region would be attended as live KV)."""
        if not blocks:
            return
        self._invalidate_evicted(blocks)
        self.free_blocks.extend(blocks)

    # -- prefill/decode disaggregation handoff ------------------------------

    def resident_chain_keys(self) -> List[List[bytes]]:
        """Every maximal HBM-resident cached chain, as ordered key
        lists in the shared ``chain_keys`` schema — the drain
        enumeration surface: a scale-down controller asks the victim
        (via ``call_on_loop``) what it holds, then ``export_prefix``-es
        each returned chain to a survivor.  Pure host bookkeeping
        (store tree walk, no device ops), but thread-confined like
        everything on the batcher."""
        if not self.prefix_cache_enabled:
            return []
        return self._store.resident_chains()

    def export_prefix(
        self, tokens: Optional[Sequence[int]] = None,
        request_id: Optional[str] = None,
        *,
        keys: Optional[Sequence[bytes]] = None,
        max_bytes: Optional[int] = None,
        demote_after_export: bool = False,
    ) -> Tuple[List[bytes], List[Dict[str, Any]]]:
        """Disaggregation handoff, PREFILL side: the longest
        HBM-resident cached chain prefix of ``tokens`` fetched as host
        slabs (``kvcache.fetch_slab``; the draft pool's twins ride
        along under speculative serving).  A prefill replica serves a
        request once (publishing its chain), exports here, and a
        decode replica ``import_prefix``-es the slabs so the session's
        next turn admits there as a plain prefix hit — the same
        fetch/adopt primitives the host-DRAM tier uses, pointed across
        replicas instead of across memory tiers (router.py owns the
        orchestration).  Returns ``(chain_keys, slabs)``; empty when
        the prefix cache is off or nothing is resident.

        ``keys`` passes precomputed chain-prefix keys instead of
        tokens (the router schedules handoffs from its global radix
        index, which speaks keys — ``router.chain_keys`` is the shared
        schema).  ``max_bytes`` bounds the slab payload (block-aligned
        truncation from the root — a partial prefix is still a valid
        chain).  ``demote_after_export=True`` demotes the exported
        chain's IDLE blocks to the host tier (or drops idle leaf
        blocks with no tier) so a migration *reduces* fleet duplicate
        KV bytes instead of growing them; claimed blocks never move
        (radix index only — the exact oracle keeps its chains).

        Must run on the thread that owns this batcher (the D2H fetch
        is admission-class traffic, like demotion — never on the
        decode hot path)."""
        if not self.prefix_cache_enabled:
            return [], []
        if keys is None:
            assert tokens is not None, "export_prefix needs tokens or keys"
            keys = self._chain_keys(tokens, self.block_size)
        else:
            keys = list(keys)
        match = self._match_prefix(keys)
        blocks = match.blocks
        if max_bytes is not None and self.block_bytes > 0:
            blocks = blocks[: max(0, max_bytes // self.block_bytes)]
        slabs: List[Dict[str, Any]] = []
        for blk in blocks:
            slab = fetch_slab(self.pool, blk)
            if self.spec:
                slab.update(fetch_slab(self.draft_pool, blk, prefix="d_"))
            slabs.append(slab)
        self.kv_export_blocks_total += len(slabs)
        if slabs:
            self.kv_export_events_total += 1
        if demote_after_export and slabs:
            self.demote_exported(
                keys[: len(slabs)], slabs, request_id=request_id,
            )
        # Fleet-trace link: the instant event carries the EXTERNAL
        # request id (when the handoff orchestrator knows it), so the
        # router's merged /debug/trace ties this replica's export to
        # the peer's import of the same session.
        self.obs.annotate(
            "prefix_export", blocks=len(slabs), request_id=request_id,
        )
        return list(keys[: len(slabs)]), slabs

    def demote_exported(
        self, keys: Sequence[bytes],
        slabs: Optional[Sequence[Dict[str, Any]]] = None,
        request_id: Optional[str] = None,
    ) -> int:
        """Deduplicate after handoff: demote the exported chain's IDLE
        blocks to the host tier (or drop idle leaf blocks with no
        tier) so the migration *reduces* fleet duplicate KV bytes.
        The router's scheduler calls this as its OWN control step only
        after the copy landed on the peer — decoupled from the export
        so an abandoned or failed handoff never costs the fleet its
        only HBM-resident copy.  ``slabs`` are the export's already-
        fetched host images, reused for tier insertion instead of a
        second D2H fetch of the identical blocks.  Radix index only
        (the exact oracle keeps its chains); claimed blocks never
        move.  Returns the number of blocks that left HBM."""
        if not self.prefix_cache_enabled or self._store.kind != "radix":
            return 0
        keys = list(keys)
        slab_by_key: Dict[bytes, Dict[str, Any]] = (
            dict(zip(keys, slabs)) if slabs else {}
        )

        def fetch(blk: int) -> Dict[str, Any]:
            node = self._store._by_block.get(blk)
            slab = (
                slab_by_key.get(node.key) if node is not None else None
            )
            if slab is not None:
                self.swap_out_blocks_total += 1
                return slab
            return self._demote_block(blk)

        freed = self._store.demote_keys(
            keys, fetch if self.host_kv_blocks > 0 else None,
        )
        self.kv_export_demoted_blocks_total += len(freed)
        self._invalidate_and_free(freed)
        if freed:
            self.obs.annotate(
                "prefix_demote_after_export", blocks=len(freed),
                request_id=request_id,
            )
        return len(freed)

    def import_prefix(
        self, keys: Sequence[bytes], slabs: Sequence[Dict[str, Any]],
        request_id: Optional[str] = None,
        *,
        max_bytes: Optional[int] = None,
        timeout_s: Optional[float] = None,
    ) -> int:
        """Disaggregation handoff, DECODE side: land exported slabs in
        this batcher's pool (alloc + ``kvcache.stage_restore`` +
        ``adopt_into_pool`` — the host-tier swap-in path with the slabs
        arriving from a peer instead of this replica's own tier) and
        publish the chain, so the next admission of those tokens is a
        prefix hit.  Blocks already resident here are skipped;
        truncates to pool capacity (and to ``max_bytes`` when given —
        block-aligned from the root, so a partial landing is still a
        valid chain prefix).  Synchronous (admission-class, on the
        owning thread); returns the number of blocks landed.

        ``timeout_s`` bounds the staged H2D transfer wall time: past
        the deadline the import UNWINDS cleanly — fresh blocks freed
        with positions invalidated, matched blocks unclaimed, NOTHING
        published (a partial publish would advertise KV that never
        landed) — ``kv_handoff_aborted_total`` counts it, and
        :class:`TimeoutError` raises so the scheduler can tell an
        abort from the benign already-resident no-op (return 0).
        Without the bound a wedged transfer would hold allocated
        blocks indefinitely."""
        if not self.prefix_cache_enabled or not slabs:
            return 0
        keys = list(keys)[: len(slabs)]
        have = self._store.match(keys).blocks
        todo = list(slabs)[len(have):len(keys)]
        if max_bytes is not None and self.block_bytes > 0:
            todo = todo[: max(0, max_bytes // self.block_bytes)]
        if not todo:
            return 0
        # Claim the matched resident blocks BEFORE allocating — the
        # same discipline every admission path follows: idle matched
        # blocks are exactly what _alloc_blocks evicts first, and an
        # evicted-then-republished id would bind the old chain key to
        # another chain's KV (silent wrong-token corruption).
        self._claim_blocks(have)
        try:
            cap = self._capacity()
            if len(todo) > cap:
                todo = todo[:cap]
            if not todo:
                return 0
            fresh = self._alloc_blocks(len(todo))
            staged = stage_restore(
                todo, fresh, self.n_blocks,
                placements=(
                    smesh.staging_shardings(self.mesh, list(todo[0]))
                    if self._mesh_placed else None
                ),
            )
            if timeout_s is not None:
                # Bounded wait: poll the staged transfers (non-blocking
                # is_ready, the swap-in path's own probe) against the
                # wall deadline; a wedge unwinds instead of pinning
                # the allocation forever.  Raises (rather than
                # returning 0) so the scheduler can tell an ABORT from
                # the benign already-resident/no-capacity no-op.
                deadline = time.monotonic() + timeout_s
                while not restore_ready(staged):
                    if time.monotonic() >= deadline:
                        self.kv_handoff_aborted_total += 1
                        self._invalidate_and_free(fresh)
                        self.obs.annotate(
                            "prefix_import_aborted",
                            blocks=len(todo),
                            request_id=request_id,
                            timeout_s=timeout_s,
                        )
                        raise TimeoutError(
                            f"prefix import: staged transfer of "
                            f"{len(todo)} block(s) not ready within "
                            f"{timeout_s}s (unwound cleanly)"
                        )
                    time.sleep(0.001)
            # audit: host-fetch(blocking handoff import: synchronous
            # admission-class landing of peer slabs — nothing is
            # decoding on behalf of this not-yet-admitted session)
            jax.block_until_ready(list(staged.values()))
            self.pool = adopt_into_pool(self.pool, staged)
            if self.spec:
                self.draft_pool = adopt_into_pool(
                    self.draft_pool, staged, prefix="d_"
                )
            self._store.publish(
                keys[: len(have) + len(todo)], have + fresh
            )
            # A node mid-swap-in (restoring) refuses the published
            # copy: its fresh block stays unkeyed — free it instead
            # of leaking.
            adopted = [b for b in fresh if self._store.is_keyed(b)]
            self._store.retain(adopted)
            self._invalidate_and_free(
                [b for b in fresh if b not in adopted]
            )
            self.kv_import_blocks_total += len(adopted)
            if adopted:
                self.kv_import_events_total += 1
            # Fleet-trace link (see export_prefix).
            self.obs.annotate(
                "prefix_import", blocks=len(adopted),
                request_id=request_id,
            )
            return len(adopted)
        finally:
            # Matched blocks return to the idle LRU (nobody is using
            # them yet — the claim only protected them from this
            # call's own allocation).
            self._unclaim_blocks(have)

    # Chain hash per FULL prompt block: key_j = H(key_{j-1}, block-j
    # tokens), so a hit at block j certifies the whole prefix up to
    # it.  The implementation lives in router.chain_keys — the ONE
    # shared key schema the router-side global radix index must agree
    # with (router.py stays jax-free, so the pure helper lives there).
    _chain_keys = staticmethod(_router_chain_keys)

    def _match_prefix(self, keys: List[bytes]) -> MatchResult:
        """Longest cached chain prefix across ALL cached chains (the
        radix walk; the exact store degenerates to the flat-map walk).
        ``.blocks`` are the HBM-resident hits; a nonempty ``.restore``
        names demoted nodes a host-tier swap-in could bring back."""
        return self._store.match(keys)

    def _hash_and_match(
        self, req: "_Request",
    ) -> Tuple[List[bytes], MatchResult]:
        """``req``'s chain keys (none for a user who opted out of the
        prefix cache: do not hash their prompt) and their match."""
        chain: List[bytes] = []
        if self.prefix_cache_enabled:
            with self.obs.loop_span("admit.hash", rid=req.rid):
                chain = self._chain_keys(req.tokens, self.block_size)
        with self.obs.loop_span("admit.match", rid=req.rid):
            return chain, self._match_prefix(chain)

    def _claim_blocks(self, blocks: List[int]) -> None:
        self._store.on_claim(blocks)
        for blk in blocks:
            self._block_refs[blk] = self._block_refs.get(blk, 0) + 1

    def _unclaim_blocks(self, blocks: List[int]) -> None:
        """Reverse of ``_claim_blocks`` for admissions that never landed
        (aborted/cancelled swap-ins): drop the refs and push keyed
        blocks whose last user this was back into the idle LRU."""
        retained: List[int] = []
        plain: List[int] = []
        for blk in blocks:
            refs = self._block_refs.get(blk, 1) - 1
            if refs > 0:
                self._block_refs[blk] = refs
                continue
            self._block_refs.pop(blk, None)
            if self.prefix_cache_enabled and self._store.is_keyed(blk):
                retained.append(blk)
            else:
                plain.append(blk)
        self._store.retain(retained)
        self._invalidate_and_free(plain)

    def _register_chain(self, blocks: List[int], keys: List[bytes]) -> None:
        """Publish a request's freshly prefilled full prompt blocks into
        the prefix index.

        Radix: divergent chains share their common prefix NODES by
        construction — a duplicate publication leaves the existing
        node's block in place and the publisher's copy stays private
        (plain-freed with its slot).  Exact (the legacy oracle): a
        duplicate publication SUPERSEDES — the store returns the old
        idle blocks, freed here in one batch (per-block frees would be
        one jitted _release_blocks dispatch each)."""
        if not self.prefix_cache_enabled:
            return
        self._invalidate_and_free(self._store.publish(keys, blocks))

    def _free_slot(self, b: int, device_done: bool = False) -> None:
        """Free slot ``b``.  ``device_done=True`` means the chunk program
        already folded the row out of its on-device active mask (stop /
        budget / non-finite detected in-jit), so no deactivation upload
        is owed; a HOST-initiated free (cancel, forced-nan drill) must
        mark the row dirty so the next chunk dispatch deactivates it on
        device — a stale device-active row would keep decoding into
        blocks the allocator may hand to someone else."""
        slot = self.slots[b]
        assert slot is not None
        if self._pf is not None and self._pf.slot == b:
            # Mid-prefill free (cancel / forced-nan drill): drop the
            # in-flight admission — no further fused dispatches reference
            # it, and device ordering makes the already-enqueued chunk
            # writes land before any re-allocation of its blocks.  The
            # chain was never published (publication happens at
            # completion), so nothing to unpublish beyond _fail_slot's
            # usual scan.  Its chunks' state snapshots hang on no node yet.
            for _, sid in self._pf.snaps:
                self._store.release_snapshot(sid)
            self._pf = None
        # Keyed blocks with no remaining users are RETAINED (prefix
        # cache) — their positions must stay valid for future reusers —
        # handed to the store in chain order (it reverses, so chains
        # enter the idle LRU leaves-first and evict back-to-front).
        plain: List[int] = []
        retained: List[int] = []
        for blk in slot.blocks:
            refs = self._block_refs.get(blk, 1) - 1
            if refs > 0:
                self._block_refs[blk] = refs
                continue
            self._block_refs.pop(blk, None)
            if self.prefix_cache_enabled and self._store.is_keyed(blk):
                retained.append(blk)
            else:
                plain.append(blk)
        self._store.retain(retained)
        self._invalidate_and_free(plain)
        # Session KV footprint at teardown (peak blocks held).
        self.obs.observe_kv(session_blocks=len(slot.blocks))
        self.slots[b] = None
        self.table[b] = self.n_blocks
        self.n_alloc[b] = 0
        self.fill[b] = 0
        self.active[b] = False
        self.remaining[b] = 0
        self.stop_tab[b, :] = -1
        if not device_done:
            self._dirty_rows.add(b)

    def _suffix_pad(self, n_suffix_tokens: int, n_share: int) -> int:
        """Padded suffix length for the grouped suffix-insert: round to a
        block multiple, then bucket the BLOCK COUNT to a power of two —
        the same jit-cache-key discipline admission row counts already
        follow — so diverse /chat prompt lengths compile a bounded
        O(log2(max_len / block_size)) set of ``_paged_suffix_insert``
        executables instead of one per distinct suffix length.  The
        extra padding is masked compute (positions -1, mask False), and
        POOL write columns past a row's reservation resolve to sentinel
        table entries and drop (the ``paged_write_indices`` contract).
        The hard bound is the gathered VIEW: its width is
        blocks_per_slot x block_size and the in-forward cache write
        starts at fill0 = n_share blocks — a bucket past the remaining
        view columns would make that dynamic-update clamp its start and
        scribble over the reused prefix KV, so clamp the bucket to the
        columns the row actually has (admissibility guarantees the
        un-bucketed count fits, so the clamp never shrinks below it)."""
        nb = max(1, -(-n_suffix_tokens // self.block_size))
        nb_b = pow2_bucket(nb)
        cap = self.blocks_per_slot - n_share
        return (min(nb_b, cap) if cap >= nb else nb) * self.block_size

    def _ensure_stop_width(self, n: int) -> None:
        """Grow the -1-padded per-slot stop table to hold ``n`` stops
        (pow2-bucketed width, so the chunk program's jit cache sees
        O(log max_stops) shapes).  The device twin is rebuilt wholesale
        at the next ``_sync_device_rows``."""
        if n <= self.stop_tab.shape[1]:
            return
        w = pow2_bucket(n)
        tab = np.full((self.n_slots, w), -1, np.int32)
        tab[:, : self.stop_tab.shape[1]] = self.stop_tab
        self.stop_tab = tab

    def _set_stop_row(self, b: int, stops: frozenset) -> None:
        """Write slot ``b``'s stop set into the on-device stop table's
        host mirror (order irrelevant — membership test only)."""
        self._ensure_stop_width(max(1, len(stops)))
        self.stop_tab[b, :] = -1
        if stops:
            self.stop_tab[b, : len(stops)] = sorted(stops)

    def _row_bucket(self, reqs: List["_Request"]):
        """Shared admission-row-bucket setup: the pow2 row count (jit
        cache key discipline — both admission paths must bucket the same
        way) plus the per-row key/sampling-parameter arrays."""
        k = len(reqs)
        kb = pow2_bucket(k)
        keys = np.zeros((kb, 2), np.uint32)
        temps = np.zeros((kb,), np.float32)
        top_ps = np.ones((kb,), np.float32)
        top_ks = np.zeros((kb,), np.int32)
        for i, req in enumerate(reqs):
            keys[i] = self._request_key(req)
            temps[i] = req.temperature
            top_ps[i] = req.top_p
            top_ks[i] = req.top_k
        return kb, keys, temps, top_ps, top_ks

    def _request_key(self, req: "_Request") -> np.ndarray:
        """Host-built threefry key words for a request.  The obvious
        np.asarray(jax.random.PRNGKey(seed)) is a device dispatch and a
        blocking device->host fetch PER REQUEST on the admission path
        (cost on a local chip: not measured).  Under the
        default (x64-disabled) canonicalization PRNGKey(seed) is exactly
        [0, seed & 0xFFFFFFFF] (parity-tested); with x64 enabled
        threefry_seed keeps the high word too, so mirror it — otherwise
        an embedding application that flips jax_enable_x64 would
        silently fork the batcher's sampled streams from standalone
        seeded generates.  (Seed mix: a stable multiply, NOT Python's
        hash() — its tuple algorithm is an interpreter detail that would
        change sampled outputs across Python versions.)"""
        seed = (
            req.seed if req.seed is not None
            else self.default_seed(req.rid)
        )
        kw = np.zeros((2,), np.uint32)
        if jax.config.jax_enable_x64:
            kw[0] = np.uint32((seed >> 32) & 0xFFFFFFFF)
        kw[1] = np.uint32(seed & 0xFFFFFFFF)
        return kw

    def _admit_shared_group(
        self,
        grp: List[Tuple["_Request", List[bytes], List[int]]],
        slots: List[int],
    ) -> None:
        """Admit a group of prefix-cache-hit requests sharing one padded
        suffix length: reuse the cached blocks (already claimed by
        _admit) and prefill only the suffixes through the rows' gathered
        views in ONE dispatch (per-row fill offsets differ freely).
        Each request's own freshly prefilled full prompt blocks extend
        the published chain, so a follow-up with a longer shared prefix
        hits deeper."""
        bs = self.block_size
        k = len(grp)
        rid = grp[0][0].rid
        with self.obs.loop_span("admit.alloc", rid=rid):
            row_fresh = [
                self._alloc_blocks(req.blocks_needed(bs) - len(hits))
                for req, _, hits in grp
            ]
        with self.obs.loop_span("admit.insert", rid=rid):
            kb, keysA, temps, top_ps, top_ks = self._row_bucket(
                [r for r, _, _ in grp]
            )
            T = self._suffix_pad(
                len(grp[0][0].tokens) - len(grp[0][2]) * bs, len(grp[0][2])
            )
            st = np.zeros((kb, T), np.int32)
            sm = np.zeros((kb, T), bool)
            table_rows = np.full((kb, self.blocks_per_slot), self.n_blocks,
                                 np.int32)
            n_alloc_arr = np.zeros((kb,), np.int32)
            fill0s = np.zeros((kb,), np.int32)
            row_blocks: List[List[int]] = []
            for i, (req, chain, hits) in enumerate(grp):
                n_share = len(hits)
                L0 = n_share * bs
                blocks = hits + row_fresh[i]
                row_blocks.append(blocks)
                suffix = req.tokens[L0:]
                st[i, : len(suffix)] = suffix
                sm[i, : len(suffix)] = True
                table_rows[i, : len(blocks)] = blocks
                n_alloc_arr[i] = len(blocks)
                fill0s[i] = L0
        # No flash here regardless of T: the gathered view carries
        # PER-ROW cache offsets (fill0 is a vector), which forces
        # forward()'s must_xla path — "auto" resolves to XLA for every
        # suffix chunk.  Claiming flash would fire the wrong fault site
        # and, worse, credit a probing flash kernel with a success it
        # never executed.
        for req, _, _ in grp:
            self.obs.begin_span(req.rid, "prefilling")
        _obs_mod.attribute_compiles(self.obs, "_paged_suffix_insert")
        self.obs.dispatch_begin("suffix_insert", "_paged_suffix_insert", k)
        t0_obs = time.monotonic()
        self._record_dispatch(["prefix_cache"])
        self._fault("suffix_insert")
        self._admit_dispatches += 1
        # nine operands and the slot index; the draft twin's again
        self.obs.count_upload(10 + 9 * self.spec)
        with self.obs.loop_span("dispatch.submit"):
            tau, tau_lp, keys_out, self.pool = _paged_suffix_insert(
                self.params, self.pool, jnp.asarray(table_rows),
                jnp.asarray(n_alloc_arr), jnp.asarray(fill0s),
                jnp.asarray(st), jnp.asarray(sm), jnp.asarray(keysA),
                jnp.asarray(temps), jnp.asarray(top_ps), jnp.asarray(top_ks),
                config=self.config, prefill_chunk=self.prefill_chunk,
                mesh=self.mesh, with_logprobs=self.logprobs,
                placed=self._mesh_placed,
            )
            if self.spec:
                # Draft pool: the shared blocks hold the DRAFT model's KV
                # for the same tokens (written when the chain was first
                # admitted under this batcher), so only the suffixes run
                # here too; sampled tokens are discarded.
                _, _, _, self.draft_pool = _paged_suffix_insert(
                    self.draft_params, self.draft_pool,
                    jnp.asarray(table_rows), jnp.asarray(n_alloc_arr),
                    jnp.asarray(fill0s), jnp.asarray(st), jnp.asarray(sm),
                    jnp.asarray(keysA),
                    jnp.zeros((kb,), jnp.float32),
                    jnp.ones((kb,), jnp.float32),
                    jnp.zeros((kb,), jnp.int32),
                    config=self.draft_config,
                    prefill_chunk=self.prefill_chunk, mesh=self.mesh,
                    placed=self._mesh_placed,
                )
        # Live (block, offset) pairs ``_scatter_back`` lands: each row's T
        # columns from fill0, less those past its reservation.
        pairs = int(np.minimum(T, n_alloc_arr * bs - fill0s)[:k].sum())
        self.prefill_pairs_written_total += pairs
        # Dispatch span (async submit — wall covers dispatch time only,
        # the suffix path's known undercount); linked into each
        # request's prefilling span, which then closes into decoding.
        self.obs.record_dispatch(
            kind="suffix_insert", k=k,
            occupancy=sum(s is not None for s in self.slots.values()),
            prefill_tokens=sum(
                len(r.tokens) - len(h) * bs for r, _, h in grp
            ),
            wall_ms=(time.monotonic() - t0_obs) * 1000.0,
            swap_inflight=len(self._restoring),
            rids=[r.rid for r, _, _ in grp],
            program="_paged_suffix_insert",
            prefill_write={"pairs": pairs},
        )
        idx = jnp.asarray(np.asarray(slots, np.int32))
        self.tau = self.tau.at[idx].set(tau[:k])
        if self.logprobs:
            self.d_tau_lp = self.d_tau_lp.at[idx].set(tau_lp[:k])
        self.keys = self.keys.at[idx].set(keys_out[:k])
        for i, (req, chain, hits) in enumerate(grp):
            b = slots[i]
            blocks = row_blocks[i]
            n_share = len(hits)
            self.pos[b] = len(req.tokens)
            self.fill[b] = _round_up(len(req.tokens), bs)
            self.active[b] = True
            self.table[b] = self.n_blocks
            self.table[b, : len(blocks)] = blocks
            self.n_alloc[b] = len(blocks)
            self.temp_arr[b] = req.temperature
            self.top_p_arr[b] = req.top_p
            self.top_k_arr[b] = req.top_k
            self.remaining[b] = req.max_new
            self._set_stop_row(b, req.stops)
            self._dirty_rows.add(b)
            self.slots[b] = _Slot(
                request_id=req.rid, emitted=[], max_new=req.max_new,
                stop_tokens=req.stops, blocks=blocks, shared=n_share,
            )
            self._claim_blocks(row_fresh[i])
            # Extend the published chain with this request's own full
            # prompt blocks (indices n_share..len(chain)-1 are fresh).
            # FULL chain, not the suffix: a suffix-only radix publish
            # would mis-root the extension at the tree root under
            # mid-chain keys (unreachable for future matches) — the
            # hit prefix re-publishes as a no-op and parents the
            # fresh nodes correctly.
            self._register_chain(blocks[: len(chain)], chain)
            self.prefix_requests_hit += 1
            self.prefix_blocks_reused += n_share
            self.prompt_tokens_total += len(req.tokens)
            self.prefix_hit_tokens_total += n_share * bs
            self.obs.begin_span(req.rid, "decoding")
            # Per-session KV accounting: blocks reserved + hit depth
            # onto the timeline, hit depth into its histogram.
            self.obs.request_kv(
                req.rid, blocks_held=len(blocks),
                prefix_hit_tokens=n_share * bs,
            )
            self.obs.observe_kv(hit_depth_tokens=n_share * bs)

    def _state_operands(self, slots: List[int]) -> Tuple:
        """Recurrent state layers: the whole-prompt insert's extra operand —
        the rows' slots, padded to the admission's row bucket
        (``_row_bucket``'s) with n_slots (which drops).  Nothing for every
        other block."""
        if not self.recurrent:
            return ()
        rows = np.full((pow2_bucket(len(slots)),), self.n_slots, np.int32)
        rows[: len(slots)] = slots
        return (jnp.asarray(rows),)

    def _block(self, reason: str) -> None:
        """``_admit`` says why it leaves the queue's head queued: the
        first reason of a pass stands (it is the one that decided), and
        is counted."""
        if self.queue and self._blocked is None:
            self._blocked = reason
            self.obs.admit_blocked(reason)

    def _fused_scheduling(self) -> bool:
        """Fused prefill-decode scheduling is in force for this batcher
        (spec batchers keep classic admission — the round program has no
        prefill lane; quarantine off spec_decode lands on a plain
        chunked batcher where it IS in force)."""
        return self.prefill_budget > 0 and not self.spec

    def _admit(self) -> None:
        """Admit queued requests.

        Swap path first: in-flight swap-ins are POLLED (non-blocking
        while anything is decoding — the overlap contract) and
        completed ones admitted as plain prefix hits with FIFO
        priority.  Then the classic path (``prefill_budget=0``,
        speculative batchers, or a COLD pool with nothing mid-decode):
        whole-prompt batched prefill dispatches at the step boundary —
        see ``_admit_classic``.  Fused path (``prefill_budget`` > 0
        while any row is mid-decode): the queue head is moved to
        ``prefilling`` state (blocks reserved, prompt uploaded once,
        row visible-but-inactive) and its prompt advances INSIDE the
        subsequent ``_fused_chunk`` dispatches — at most one admission
        is in flight at a time, FIFO; the rest of the queue waits
        exactly as it would for capacity.  A queue head whose matched
        prefix includes host-tier blocks moves to ``restoring``
        instead (either path) — later queue entries keep admitting
        while its swap-in flies."""
        self.obs.loop_phase("admit")
        self._blocked = None
        if self._restoring or self._restored_ready:
            with self.obs.loop_span("admit.restore"):
                self._poll_restores()
                self._admit_restored_ready()
        if self._fused_scheduling():
            if self._pf is not None:
                self._block("lane")
                return  # one in-flight admission at a time
            if bool(np.any(self.active)):
                if self.queue:
                    self._begin_fused_prefill()
                return
            # Cold pool: nobody to stall — classic batched admission (but a
            # prefix hit on recurrent state layers: ``_admit_classic_impl``).
        self._admit_classic()

    # -- host-tier swap-ins (the ``restoring`` admission state) -------------

    def _begin_restore(
        self, req: "_Request", chain: List[bytes], match: MatchResult
    ) -> bool:
        """Start an async swap-in for a request whose matched prefix
        includes demoted (host-tier) blocks: claim the path's resident
        blocks, pin the demoted nodes, allocate their fresh HBM blocks,
        and ``jax.device_put`` the slabs into staging buffers — then
        park the request in ``restoring``.  No pool dependency is
        created here, so decode chunks dispatched while the transfer
        flies never wait on it.

        Fault site ``kv_swap`` fires before the transfer; an injected
        fault (or injected allocation OOM) fails ONLY this request —
        claims released, fresh blocks returned, nodes unpinned and
        host-resident again — and returns False (the server maps the
        ``pop_failed`` entry to a clean HTTP 500)."""
        resident = [n.block for n in match.path if n.block is not None]
        self._claim_blocks(resident)
        self._store.pin_restoring(match.restore)
        fresh: List[int] = []
        try:
            self._fault("kv_swap")
            fresh = self._alloc_blocks(len(match.restore))
            staged = stage_restore(
                [n.host for n in match.restore], fresh, self.n_blocks,
                placements=(
                    smesh.staging_shardings(
                        self.mesh, list(match.restore[0].host)
                    ) if self._mesh_placed else None
                ),
            )
        except InjectedFault as e:
            self._store.unpin_restoring(match.restore)
            self._unclaim_blocks(resident)
            if fresh:
                self._invalidate_and_free(fresh)
            msg = (
                f"kv swap-in failed: {e} (request aborted; host-tier "
                f"blocks unpinned, server healthy)"
            )
            self.failed.append((req.rid, msg))
            self.swap_failures_total += 1
            self.obs.request_end(req.rid, "failed", msg)
            return False
        self._claim_blocks(fresh)
        self._restoring.append(_Restore(
            req=req, chain=chain, path=match.path,
            restore=match.restore, resident=resident, fresh=fresh,
            staged=staged, t0=time.monotonic(),
        ))
        self.swap_ins_total += 1
        self.obs.begin_span(req.rid, "restoring")
        # The evictions this session SUFFERED: matched prefix nodes
        # that had been demoted out of HBM, forcing this swap-in.
        self.obs.request_kv(
            req.rid, evictions_suffered=len(match.restore),
        )
        return True

    def _abort_restore(self, r: "_Restore") -> None:
        """Unwind an in-flight swap-in (cancel / broken path): release
        every claim — both the resident hits and the fresh blocks were
        CLAIMED at begin, so both go through ``_unclaim_blocks`` (a
        plain ``_invalidate_and_free`` of claimed blocks would strand
        their refcounts and leak pool capacity) — and the nodes fall
        back to host residency (the slabs were read, not moved; the
        staging copy is simply dropped).  Nothing was scattered into
        the pool, so no pool state needs undoing."""
        self._store.unpin_restoring(r.restore)
        self._unclaim_blocks(r.resident)
        self._unclaim_blocks(r.fresh)

    def _poll_restores(self) -> None:
        """Advance in-flight swap-ins WITHOUT stalling decode: readiness
        is ``jax.Array.is_ready`` on the staging buffers (non-blocking);
        only when nothing at all is decoding (no active row, no
        in-flight prefill — nobody to stall) does the poll block on the
        transfer.  A ready swap-in pays ONE jitted adoption scatter
        (``kvcache.adopt_into_pool``; both pools under speculative
        serving) and moves the request to ``_restored_ready``."""
        if not self._restoring:
            return
        idle = not bool(np.any(self.active)) and self._pf is None
        for r in list(self._restoring):
            r.polls += 1
            # A concurrent non-finite subtree drop (``_fail_slot`` ->
            # ``unpublish``) may have severed the matched path while
            # the transfer flew — its KV is suspect, and the nulled
            # node.block entries would otherwise crash admission.
            # Unwind the claims and requeue the request at the head:
            # it re-admits through a clean cold prefill,
            # token-identically.
            broken = any(
                (not n.restoring) if n in r.restore else
                (n.block is None)
                for n in r.path
            )
            if broken:
                self._restoring.remove(r)
                self._abort_restore(r)
                self.queue.insert(0, r.req)
                self.obs.begin_span(
                    r.req.rid, "queued", note="swap aborted"
                )
                continue
            ready = restore_ready(r.staged)
            if not ready and idle:
                # audit: host-fetch(blocking swap-in wait ONLY when
                # nothing is decoding — nobody to stall)
                jax.block_until_ready(list(r.staged.values()))
                ready = True
            if not ready or r.polls <= self.swap_poll_min:
                continue
            _obs_mod.attribute_compiles(self.obs, "_adopt_jit")
            self.obs.dispatch_begin("adopt", "_adopt_jit", len(r.fresh))
            t_adopt = time.monotonic()
            self.pool = adopt_into_pool(self.pool, r.staged)
            if self.spec:
                self.draft_pool = adopt_into_pool(
                    self.draft_pool, r.staged, prefix="d_"
                )
            adopt_ms = (time.monotonic() - t_adopt) * 1000.0
            self._store.complete_restore(r.restore, r.fresh)
            self.swap_in_blocks_total += len(r.fresh)
            swap_ms = (time.monotonic() - r.t0) * 1000.0
            self.swap_in_ms_total += swap_ms
            self._restoring.remove(r)
            self._restored_ready.append(
                (r.req, r.chain, [n.block for n in r.path])
            )
            # The adoption scatter is a real device dispatch: span it
            # (linked into the request's restoring span) and feed the
            # swap-in histogram.  wall covers the async submit only
            # (blocking on the scatter here would ADD the host sync
            # the overlap design exists to avoid — the suffix path's
            # documented undercount applies).
            self.obs.record_swap_in(swap_ms, len(r.fresh))
            # Swap bytes moved for this session (host metadata
            # arithmetic on the staged buffers — no sync).
            self.obs.request_kv(
                r.req.rid,
                swap_in_bytes=sum(
                    int(a.nbytes) for a in r.staged.values()
                ),
            )
            self.obs.record_dispatch(
                kind="adopt", k=len(r.fresh),
                occupancy=sum(
                    s is not None for s in self.slots.values()
                ),
                wall_ms=adopt_ms,
                swap_inflight=len(self._restoring),
                rids=(r.req.rid,),
                program="_adopt_jit",
            )
            self.obs.begin_span(r.req.rid, "queued", note="restored")

    def _admit_restored_ready(self) -> None:
        """Admit completed swap-ins as plain prefix hits (their path
        blocks are already claimed): through the fused prefill lane
        when rows are decoding (the chunk walk starts at the matched
        depth — no stall), through one grouped suffix-insert dispatch
        otherwise.  FIFO among themselves; each still needs a free
        slot and capacity for the rest of its reservation."""
        while self._restored_ready:
            req, chain, hits = self._restored_ready[0]
            free = [b for b, s in self.slots.items() if s is None]
            if not free:
                return
            if (req.blocks_needed(self.block_size) - len(hits)
                    > self._capacity()):
                return
            if self._fused_scheduling() and bool(np.any(self.active)):
                if self._pf is not None:
                    return
                self._restored_ready.pop(0)
                self._setup_fused_prefill(req, chain, hits, claimed=True)
                self._block("restoring")
            else:
                self._restored_ready.pop(0)
                self._admit_shared_group(
                    [(req, chain, hits)], [free[0]]
                )

    def _pf_ctx_slots(self, pf: _Prefill, flash: bool) -> Tuple[int, int]:
        """(attended, view) slots of the row's gathered view for the fused
        dispatch about to advance ``pf``: what prefill attention does work
        for besides the chunk itself, and the view's width.  Host mirror
        of ``forward``'s resolution, from numbers the scheduler holds: the
        latent block's flash form walks the context below the write index
        ``base + off`` by ``mla_moe.ctx_tiles`` (the device's own rule);
        every other form takes the whole view."""
        view = self.blocks_per_slot * self.block_size
        if not (flash and self.config.latent_attention):
            return view, view
        tile, trips = ctx_tiles(pf.base + pf.off, view)
        return min(trips * tile, view), view

    def _pf_live_blocks(self, pf: _Prefill) -> int:
        """Blocks the fused dispatch about to advance ``pf`` lands in the
        pool (``_land_chunk``): the chunk's ``chunk // block_size`` table
        columns from ``(base + off) // block_size``, less those past the
        row's reservation, whose sentinel entries drop.  Host mirror, from
        numbers the scheduler holds."""
        bs = self.block_size
        first = (pf.base + pf.off) // bs
        held = len(self.slots[pf.slot].blocks)
        return max(0, min(pf.chunk // bs, held - first))

    def _pf_snapshots(self, pf: _Prefill):
        """Recurrent state layers: the snapshot operands of the fused
        dispatch about to advance ``pf`` and its record's ``ssm`` field.
        The walk's first chunk starts from ``pf.snap_in``; a chunk whose
        tokens are all the prompt's (so it ends on a block boundary the
        chain holds) has its end state copied out under a fresh id, hung on
        that block's node once the chain is published."""
        out = -1
        end = pf.off + pf.chunk
        # Blocks the chain keys: those strictly before the last token.
        depth = (pf.base + end) // self.block_size
        if self.n_snapshots and end <= pf.suffix_len and depth <= len(pf.chain):
            sid = self._store.alloc_snapshot()
            if sid is not None:
                out = sid
                pf.snaps.append((depth, sid))
        restored = pf.off == 0 and pf.snap_in >= 0
        self.ssm_snapshots_restored_total += restored
        # audit: host-upload(which snapshot the chunk starts from and
        # which it leaves: int32 [2], a HOST operand of the fused
        # dispatch's own call, every chunk of the recurrent block; no copy
        # beside it, no state array crosses the host)
        ops = (np.array([pf.snap_in, out], np.int32),)
        return ops, {"taken": int(out >= 0), "restored": int(restored)}

    def _hang_snapshots(self, pf: _Prefill) -> None:
        """The finished walk's snapshots onto its (now published) chain's
        nodes; one whose node has a snapshot already, or lost its block,
        goes back to the pool."""
        for depth, sid in pf.snaps:
            if self._store.attach_snapshot(pf.chain[depth - 1], sid):
                self.ssm_snapshots_taken_total += 1
            else:
                self._store.release_snapshot(sid)
        pf.snaps = []

    def _pf_chunk(self, suffix_len: int, n_share: int) -> int:
        """Prompt tokens per fused dispatch: ``prefill_budget`` rounded
        DOWN to a pow2 block count (jit-cache discipline that still
        honors the flag as an upper bound — rounding up would let a
        640-token budget ride 1024 tokens of prefill per dispatch,
        inflating exactly the per-dispatch ITL the flag caps; the floor
        is one block), clamped to the suffix's own pow2 bucket (not for
        recurrent state layers: below), then halved until the LAST chunk's write window fits the row's
        remaining gathered-view columns — the ``_suffix_pad`` clamp
        hazard: the in-forward cache write is a scalar-start
        dynamic-update that would silently clamp and scribble over the
        reused prefix KV.  Terminates at one block, where admissibility
        guarantees the fit."""
        bs = self.block_size
        nbb = max(1, self.prefill_budget // bs)
        nbb = 1 << (nbb.bit_length() - 1)
        nbs = pow2_bucket(max(1, -(-suffix_len // bs)))
        # Recurrent state layers keep the whole budget for a short suffix
        # too: a walk's chunk ends are where its state snapshots stand, so
        # they stay on one grid (the hit + multiples of the budget), and a
        # length class that one request in a hundred falls in gets no
        # program variant of its own for a warm-up to miss.
        c_blocks = nbb if self.recurrent else min(nbb, nbs)
        view_blocks = self.blocks_per_slot - n_share
        while c_blocks > 1 and (
            -(-suffix_len // (c_blocks * bs)) * c_blocks > view_blocks
        ):
            c_blocks //= 2
        return c_blocks * bs

    def _begin_fused_prefill(self) -> None:
        """Move the queue head into ``prefilling`` state: reserve its
        blocks (claiming prefix-cache hits — hit rows start their chunk
        walk at fill0 = the matched depth), set up the host mirrors
        with the row VISIBLE BUT INACTIVE (the fused program activates
        it on device the dispatch its last chunk lands), and upload the
        suffix tokens + walk scalars ONCE, as one vector — later chunks
        are pure dispatches, zero per-chunk host->device state traffic.  No
        model dispatch happens here; the prefill itself rides
        ``_fused_chunk``.  A head whose matched prefix includes
        host-tier blocks moves to ``restoring`` instead, and the NEXT
        head gets the prefill lane — swap-ins never block admission."""
        free = [b for b, s in self.slots.items() if s is None]
        if not free:
            self._block("slot")
            return
        while self.queue:
            req = self.queue[0]
            need = req.blocks_needed(self.block_size)
            if need > self._capacity():
                self._block("capacity")
                return  # head-of-line blocking (FIFO fairness): wait
            chain, m = self._hash_and_match(req)
            if m.restore:
                del self.queue[0]
                # Restoring (or cleanly failed on an injected swap
                # fault) — either way the prefill lane is still open
                # for the next head.
                self._begin_restore(req, chain, m)
                continue
            del self.queue[0]
            self.ssm_match_tokens_cut_total += m.cut * self.block_size
            self._setup_fused_prefill(
                req, chain, m.blocks, claimed=False,
                snap_in=-1 if m.snap is None else m.snap,
            )
            self._block("lane")  # what the next head waits for
            return

    def _setup_fused_prefill(
        self, req: "_Request", chain: List[bytes], hits: List[int],
        claimed: bool = False, snap_in: int = -1,
    ) -> None:
        """The ``prefilling``-state setup shared by fresh admissions and
        completed swap-ins (``claimed=True``: the hit blocks were
        claimed at restore begin)."""
        b = next(b for b, s in self.slots.items() if s is None)
        n_share = len(hits)
        base = n_share * self.block_size
        with self.obs.loop_span("admit.alloc", rid=req.rid):
            if not claimed:
                self._claim_blocks(hits)
            fresh = self._alloc_blocks(
                req.blocks_needed(self.block_size) - n_share
            )
            self._claim_blocks(fresh)
        blocks = hits + fresh
        suffix = req.tokens[base:]
        C = self._pf_chunk(len(suffix), n_share)
        # Token buffer in whole chunks, chunk count pow2-bucketed (the
        # buffer length is a jit cache key of _fused_chunk); trailing
        # zeros are masked and never dispatched.
        n_chunks = pow2_bucket(max(1, -(-len(suffix) // C)))
        buf_len = n_chunks * C
        # Host mirrors: full reservation visible, row inactive; the
        # admission-time dirty sync is the ONE state upload the whole
        # prefill pays.
        self.table[b] = self.n_blocks
        self.table[b, : len(blocks)] = blocks
        self.n_alloc[b] = len(blocks)
        self.fill[b] = 0
        self.pos[b] = 0
        self.active[b] = False
        self.temp_arr[b] = req.temperature
        self.top_p_arr[b] = req.top_p
        self.top_k_arr[b] = req.top_k
        self.remaining[b] = req.max_new
        self._set_stop_row(b, req.stops)
        self._dirty_rows.add(b)
        self.slots[b] = _Slot(
            request_id=req.rid, emitted=[], max_new=req.max_new,
            stop_tokens=req.stops, blocks=blocks, shared=n_share,
        )
        with self.obs.loop_span("admit.upload", rid=req.rid):
            # audit: host-upload(the admission's ONE copy: the walk's
            # scalars, key words and zero offset in front of the suffix
            # tokens, once an admission; later chunks cross nothing)
            self._pf = _Prefill(
                slot=b, req=req, chain=chain, n_share=n_share, base=base,
                suffix_len=len(suffix), chunk=C,
                d_vec=self._upload(pack_prefill(
                    b, base, len(suffix), self._request_key(req), suffix,
                    buf_len,
                )),
                snap_in=snap_in,
            )
        self.fused_admissions_total += 1
        self.prompt_tokens_total += len(req.tokens)
        self.obs.begin_span(req.rid, "prefilling")
        if n_share:
            self.prefix_requests_hit += 1
            self.prefix_blocks_reused += n_share
            self.prefix_hit_tokens_total += base
        # Per-session KV accounting (fused lane): reservation + hit
        # depth onto the timeline and the hit-depth histogram.
        self.obs.request_kv(
            req.rid, blocks_held=len(blocks), prefix_hit_tokens=base,
        )
        self.obs.observe_kv(hit_depth_tokens=base)

    def _admit_classic(self) -> None:
        """Classic admission with the decode-stall clock around it: the
        wall time whole-prompt admission dispatches spend while >= 1
        row is mid-decode accumulates into ``decode_stall_ms_total``
        (the batched-prefill path's plens fetch blocks, so the timing is
        real there; the suffix path's dispatch is async and
        undercounts)."""
        before = self._admit_dispatches
        decoding = bool(np.any(self.active))
        t0 = time.monotonic()
        try:
            self._admit_classic_impl()
        finally:
            if decoding and self._admit_dispatches > before:
                self.decode_stall_ms_total += (
                    (time.monotonic() - t0) * 1000.0
                )

    def _admit_classic_impl(self) -> None:
        """Admit queued requests into free slots.

        A burst of k admissible requests without prefix-cache hits
        shares ONE [k', P] prefill dispatch (k' = k rounded up to a
        power of two with inactive pad rows, P = the group's max
        block-padded prompt length) instead of k serialized B=1
        dispatches (fewer dispatches and host syncs per admitted
        request, and a wider prefill matmul).  Requests whose
        leading full blocks hit the prefix cache are admitted through
        ``_paged_suffix_insert``, grouped by padded suffix length so a
        burst of similar /chat prompts is ONE dispatch too (per-row
        fill0 offsets differ freely within a group — the gathered view
        and scatter-back are per-row already).  Per-row right-padding and
        per-row key chains keep every request's output bit-identical to
        one-at-a-time admission; head-of-line FIFO blocking on block
        reservations is preserved (budget stays the FULL reservation
        even for hits — shared blocks change compute, not the
        conservative capacity accounting).
        """
        while True:
            free_slots = [b for b, s in self.slots.items() if s is None]
            if not free_slots or not self.queue:
                self._block("slot")
                return
            # Head-of-line swap-ins: a queue HEAD whose matched prefix
            # includes host-tier blocks parks in ``restoring`` (async
            # swap-in overlapped on decode) instead of cold-prefilling
            # the demoted span; later entries keep admitting below.
            # Non-head entries with demoted prefixes stay FIFO-honest:
            # they admit now using only their HBM-resident hit depth.
            # Only a radix store with a tier can ever report demoted
            # hits, so the no-tier common case skips the scan entirely;
            # the head's (chain, hits) carries into the pick loop so
            # its prompt is hashed and matched once, not twice.
            head_match: Optional[Tuple[int, List[bytes], List[int]]] = None
            if self.host_kv_blocks > 0 and self._store.kind == "radix":
                while self.queue:
                    req = self.queue[0]
                    chain0, m0 = self._hash_and_match(req)
                    if not m0.restore:
                        head_match = (req.rid, chain0, m0.blocks)
                        break
                    if req.blocks_needed(self.block_size) > self._capacity():
                        self._block("capacity")
                        return  # FIFO: wait for capacity
                    del self.queue[0]
                    self._begin_restore(req, chain0, m0)
            picked: List[Tuple[_Request, List[bytes], List[int]]] = []
            lane = False
            budget = self._capacity()
            for req in self.queue:
                if len(picked) >= len(free_slots):
                    break
                need = req.blocks_needed(self.block_size)
                if need > budget:
                    # Head-of-line blocking (FIFO fairness): wait.
                    if not picked:
                        self._block("capacity")
                    break
                budget -= need
                if head_match is not None and head_match[0] == req.rid:
                    chain, hits = head_match[1], head_match[2]
                else:
                    chain, m = self._hash_and_match(req)
                    hits = m.blocks
                    if hits and self.recurrent:
                        # A hit on recurrent state layers resumes from a
                        # state snapshot, which only the fused lane takes
                        # and restores (``_paged_suffix_insert`` is never
                        # dispatched for them: its wide scatter would need
                        # pool-sized temporaries beside a pool their cells
                        # fill the chip with).  It waits for the lane: now,
                        # if it heads the queue, else behind the rows this
                        # round admits.
                        lane = True
                        break
                    self.ssm_match_tokens_cut_total += m.cut * self.block_size
                # Claim hits at SELECTION time: a later allocation in
                # this same admission round must not evict them.
                with self.obs.loop_span("admit.alloc", rid=req.rid):
                    self._claim_blocks(hits)
                picked.append((req, chain, hits))
            if not picked:
                if lane:
                    self._begin_fused_prefill()
                return
            del self.queue[:len(picked)]
            slot_iter = iter(free_slots)
            shared = [(r, c, h) for r, c, h in picked if h]
            batch = [r for r, c, h in picked if not h]
            chains = {r.rid: c for r, c, h in picked}
            # Hit requests group by padded suffix length: each group is
            # ONE suffix-insert dispatch (identical /chat prompts in a
            # burst land in the same group).
            groups: Dict[int, List[Tuple[_Request, List[bytes], List[int]]]] = {}
            for req, chain, hits in shared:
                T = self._suffix_pad(
                    len(req.tokens) - len(hits) * self.block_size,
                    len(hits),
                )
                groups.setdefault(T, []).append((req, chain, hits))
            for grp in groups.values():
                self._admit_shared_group(
                    grp, [next(slot_iter) for _ in grp]
                )
            if not batch:
                continue
            k = len(batch)
            with self.obs.loop_span("admit.alloc", rid=batch[0].rid):
                row_blocks = [
                    self._alloc_blocks(r.blocks_needed(self.block_size))
                    for r in batch
                ]
            with self.obs.loop_span("admit.insert", rid=batch[0].rid):
                kb, keys, temps, top_ps, top_ks = self._row_bucket(batch)
                # Group width: the max block-padded prompt length, its
                # BLOCK COUNT pow2-bucketed (clamped to the reservation
                # cap, which admissibility guarantees covers every row) —
                # the same jit-cache-key discipline the suffix path
                # (_suffix_pad) and admission row counts already follow.
                # Un-bucketed, diverse prompt lengths compiled one
                # _paged_insert executable per distinct block count
                # (O(max_len / block_size) cache keys — the over-wide
                # trace-key domain analysis/retrace.py flags); the extra
                # padding is masked compute and sentinel block ids drop.
                nb = min(
                    pow2_bucket(max(
                        _round_up(len(r.tokens), self.block_size)
                        for r in batch
                    ) // self.block_size),
                    self.blocks_per_slot,
                )
                P = nb * self.block_size
                pt = np.zeros((kb, P), np.int32)
                pm = np.zeros((kb, P), bool)
                bid = np.full((kb, nb), self.n_blocks, np.int32)
                for i, req in enumerate(batch):
                    Pb = _round_up(len(req.tokens), self.block_size)
                    need = req.blocks_needed(self.block_size)
                    blocks = row_blocks[i]
                    self.prompt_tokens_total += len(req.tokens)
                    # Per-session KV accounting (cold batched prefill):
                    # full reservation, zero hit depth.
                    self.obs.request_kv(
                        req.rid, blocks_held=need, prefix_hit_tokens=0,
                    )
                    self.obs.observe_kv(hit_depth_tokens=0)
                    # RIGHT padding (r5): token j at view column j, so block
                    # content is a pure function of the tokens (the prefix
                    # cache's keying invariant).  Trailing sentinels cover
                    # the group padding past this row's block-padded length.
                    pt[i, :len(req.tokens)] = req.tokens
                    pm[i, :len(req.tokens)] = True
                    bid[i, : Pb // self.block_size] = blocks[
                        : Pb // self.block_size
                    ]
                # Host mirror of forward()'s "auto" resolution for the
                # batched prefill: flash runs iff a chunk exceeds 8 tokens
                # (the chunked loop forwards ``chunk`` tokens at a time, so
                # prefill_chunk <= 8 keeps every chunk on XLA; the batch
                # cache is a fresh scalar-index init_cache, so must_xla
                # never triggers here).
                chunk = (
                    self.prefill_chunk
                    if self.prefill_chunk and self.prefill_chunk < P else P
                )
                flash = (
                    self.config.attn_impl in ("auto", "flash")
                    and chunk > FLASH_MIN_SEQ
                )
            for req in batch:
                self.obs.begin_span(req.rid, "prefilling")
            _obs_mod.attribute_compiles(self.obs, "_paged_insert")
            self.obs.dispatch_begin("insert", "_paged_insert", k)
            t0_obs = time.monotonic()
            self._record_dispatch(["flash_attention"] if flash else [])
            self._fault("insert")
            if flash:
                self._fault("flash_kernel")
            self._admit_dispatches += 1
            slot_ids = [next(slot_iter) for _ in range(k)]
            # seven operands (the state rows an eighth), the slot index;
            # the draft twin's seven again
            self.obs.count_upload(8 + self.recurrent + 7 * self.spec)
            with self.obs.loop_span("dispatch.submit"):
                taus, tau_lps, plens, keys_out, self.pool = _paged_insert(
                    # audit: host-upload(admission-time prompt/state upload
                    # for the whole batch — once per admission round, never
                    # per-token)
                    self.params, self.pool, jnp.asarray(bid),
                    jnp.asarray(pt), jnp.asarray(pm), jnp.asarray(keys),
                    jnp.asarray(temps), jnp.asarray(top_ps),
                    jnp.asarray(top_ks), *self._state_operands(slot_ids),
                    config=self.config, prefill_chunk=self.prefill_chunk,
                    mesh=self.mesh, with_logprobs=self.logprobs,
                    placed=self._mesh_placed,
                )
                if self.spec:
                    # Prefill the draft pool over the same reserved blocks
                    # (its sampled tokens are discarded — the target picks
                    # tau, and each row's key chain carries from the TARGET
                    # insert only).
                    _, _, _, _, self.draft_pool = _paged_insert(
                        # audit: host-upload(draft-pool twin of the
                        # admission-time upload above)
                        self.draft_params, self.draft_pool, jnp.asarray(bid),
                        jnp.asarray(pt), jnp.asarray(pm), jnp.asarray(keys),
                        jnp.zeros((kb,), jnp.float32),
                        jnp.ones((kb,), jnp.float32),
                        jnp.zeros((kb,), jnp.int32),
                        config=self.draft_config,
                        prefill_chunk=self.prefill_chunk, mesh=self.mesh,
                        placed=self._mesh_placed,
                    )
            # audit: host-upload(slot-index upload, once per admission)
            idx = jnp.asarray(np.asarray(slot_ids, np.int32))
            self.tau = self.tau.at[idx].set(taus[:k])
            if self.logprobs:
                self.d_tau_lp = self.d_tau_lp.at[idx].set(tau_lps[:k])
            self.keys = self.keys.at[idx].set(keys_out[:k])
            tf_obs = time.monotonic()
            # audit: host-fetch(admission-path prompt-length fetch —
            # blocks on the batched prefill; counted — was an
            # uncounted sync until the host-boundary lint flagged it)
            plens_np = np.asarray(plens)
            self.host_syncs_total += 1
            now_obs = time.monotonic()
            # Token slots of the live entries of ``bid``: what the insert
            # lands, a (block, offset) pair each.
            pairs = int((bid < self.n_blocks).sum()) * self.block_size
            self.prefill_pairs_written_total += pairs
            # Whole-prompt insert dispatch span: the plens fetch blocks
            # on the prefill, so wall here is the real admission cost
            # (what decode_stall_ms_total clocks); linked into each
            # request's prefilling span.
            self.obs.record_dispatch(
                kind="insert", k=k,
                occupancy=sum(
                    s is not None for s in self.slots.values()
                ),
                prefill_tokens=sum(len(r.tokens) for r in batch),
                wall_ms=(now_obs - t0_obs) * 1000.0,
                fetch_ms=(now_obs - tf_obs) * 1000.0,
                swap_inflight=len(self._restoring),
                rids=[r.rid for r in batch],
                program="_paged_insert",
                prefill_write={"pairs": pairs},
            )
            for i, req in enumerate(batch):
                b = slot_ids[i]
                blocks = row_blocks[i]
                self.pos[b] = int(plens_np[i])
                self.fill[b] = _round_up(len(req.tokens), self.block_size)
                self.active[b] = True
                self.table[b] = self.n_blocks
                self.table[b, : len(blocks)] = blocks
                self.n_alloc[b] = len(blocks)
                self.temp_arr[b] = req.temperature
                self.top_p_arr[b] = req.top_p
                self.top_k_arr[b] = req.top_k
                self.remaining[b] = req.max_new
                self._set_stop_row(b, req.stops)
                self._dirty_rows.add(b)
                self.slots[b] = _Slot(
                    request_id=req.rid, emitted=[], max_new=req.max_new,
                    stop_tokens=req.stops, blocks=blocks,
                )
                # Every block now has an active user; the freshly
                # prefilled full prompt blocks join the prefix index.
                self._claim_blocks(blocks)
                chain = chains[req.rid]
                self._register_chain(blocks[: len(chain)], chain)
                self.obs.begin_span(req.rid, "decoding")
