"""Pluggable attention-kernel selection layer + the two stock-Pallas
kernels it lands: splash-mha prefill and stock paged-attention decode.

Why a selection layer: BENCH_r05 put our custom flash prefill at
~149-160 TFLOPs (~78% of MXU peak) while plain matmuls hit ~90% — the
VPU softmax serializes against the MXU k-sweep, the exact pipelining
problem the upstream splash kernel family solves with tuned
``BlockSizes``.  Rather than rewriting ``ops/flash_attention.py``
in-place (and losing the known-good baseline), prefill and decode
attention become PLUGGABLE: config names a kernel per role, serving
resolves "auto" once at batcher construction (ctor-stable — no
per-dispatch cache-key churn), and each alternative kernel quarantines
back to the *custom* kernel it A/Bs against, never straight to XLA.

Roles and ladders (see README "Kernels"):

  prefill: splash -> flash -> xla
      ``splash`` = upstream ``make_splash_mha_single_device`` with a
      pure ``CausalMask`` offset per prefill chunk.  It lands on the
      whole-prompt / chunked-classic insert path only
      (``serving._paged_insert``): there the chunk's base offset is a
      PYTHON int (the insert's chunk-loop variable), which is what a
      splash mask needs — splash masks are built at trace time from
      static ints.  The fused prefill-decode chunk
      (``serving._fused_chunk``) keeps the custom flash kernel: its
      window base ``pf_base + pf_off`` is a TRACED scalar, outside
      splash's static mask surface (the ISSUE's measure-and-decide
      OR-clause, resolved structurally: no mask re-build per step can
      express a traced offset).
  decode: stock-paged -> paged -> gathered
      ``stock-paged`` = the upstream Pallas paged-attention kernel
      body, launched through a vendored wrapper that keeps the (m, l)
      softmax state the public entry point discards — our decode
      contract merges the step's own K/V at the softmax level against
      an immutable pool, so the kernel must return its logsumexp.
      T == 1 dispatches only (speculative verify keeps the custom
      kernel's native multi-token sweep); int8 pools stay on the
      custom kernel (in-kernel scale folding is its feature).

Every kernel here registers a ``ProgramContract`` + ``CommsBudget``
(analysis/contracts.py), a degrade.py feature site, and a faults.py
trace-time hook — the PR-11/12 landing checklist.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _resolve_interpret

# ---------------------------------------------------------------------------
# Selection registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One selectable attention kernel.

    ``fallback`` is the kernel quarantine rebuilds select (None = this
    IS the baseline for its role); ``feature`` / ``fault_site`` are the
    degrade.py and faults.py names wired for it (None = covered by the
    baseline's existing sites).
    """

    name: str
    role: str                      # "prefill" | "decode"
    fallback: Optional[str] = None
    feature: Optional[str] = None  # degrade.py FEATURES entry
    fault_site: Optional[str] = None  # faults.py SITES entry


PREFILL_KERNELS = {
    "flash": KernelSpec(
        "flash", "prefill",
        feature="flash_attention", fault_site="flash_kernel",
    ),
    "splash": KernelSpec(
        "splash", "prefill", fallback="flash",
        feature="splash_prefill", fault_site="splash_kernel",
    ),
}

DECODE_KERNELS = {
    "paged": KernelSpec(
        "paged", "decode",
        feature="paged_kernel", fault_site="paged_kernel",
    ),
    "stock-paged": KernelSpec(
        "stock-paged", "decode", fallback="paged",
        feature="stock_paged", fault_site="stock_paged_kernel",
    ),
    # The gathered view is not a kernel: it is the paged kernel's own
    # fallback (use_pallas_kernel=False), listed so the CLI surface and
    # the fallback ladder are complete.
    "gathered": KernelSpec("gathered", "decode"),
}


def resolve_prefill_kernel(name: Optional[str], config) -> str:
    """Map a CLI/ctor prefill-kernel name ("auto" included) to a
    concrete kernel name.  Auto policy: splash wherever its structural
    requirements can EVER hold (lane-aligned head_dim, full-precision
    cache) — per-call shape eligibility still gates each chunk, so an
    auto-splash config silently runs flash for non-128-multiple chunks.
    """
    name = name or "auto"
    if config.latent_attention:
        # q/k and v widths differ and the key is rebuilt from a latent:
        # the custom flash kernel only.
        if name not in ("auto", "flash"):
            raise ValueError(
                f"prefill kernel {name!r} is not supported with latent "
                "attention; it runs 'flash'"
            )
        return "flash"
    if name == "auto":
        return (
            "splash"
            if config.head_dim % 128 == 0
            and config.kv_cache_dtype != "int8"
            else "flash"
        )
    if name not in PREFILL_KERNELS:
        raise ValueError(
            f"unknown prefill kernel {name!r}; "
            f"have {sorted(PREFILL_KERNELS)} or 'auto'"
        )
    return name


def resolve_decode_kernel(name: Optional[str], config) -> str:
    """Map a CLI/ctor decode-kernel name to a concrete kernel name.
    Auto resolves to the custom paged kernel: it keeps int8 pools and
    multi-token (speculative verify) sweeps, and its grid of live
    multi-block steps measured level with or ahead of stock-paged at
    every served geometry but an all-idle batch (v5e, PERF.md section 6,
    PR 25); stock-paged stays as the A/B alternative until ROADMAP C4
    decides."""
    name = name or "auto"
    if config.latent_attention and name not in ("auto", "paged"):
        raise ValueError(
            f"decode kernel {name!r} is not supported with latent "
            "attention; its one shared key/value row runs 'paged'"
        )
    if name == "auto":
        return "paged"
    if name not in DECODE_KERNELS:
        raise ValueError(
            f"unknown decode kernel {name!r}; "
            f"have {sorted(DECODE_KERNELS)} or 'auto'"
        )
    return name


def splash_eligible(
    config,
    *,
    batch: int,
    q_len: int,
    kv_len: int,
    chunk_offset: Optional[int],
    quantized: bool = False,
    mesh=None,
) -> bool:
    """Static per-call predicate: can THIS prefill chunk run splash?

    Everything here is trace-time static (shapes, config, the mesh, the
    chunk's Python-int offset), so ``models._block`` decides per chunk
    with zero runtime cost, and serving's host mirror replicates the
    decision exactly (it passes the same arguments).  Splash needs
    lane-aligned geometry (head_dim and both sequence lengths multiples
    of 128 — the kernel's grid/lane tiling), a static mask offset, and
    a full-precision cache; under a mesh it runs per-shard (heads over
    "tensor", rows over the batch axes), so the same divisibility the
    paged kernel requires applies.
    """
    if config.prefill_kernel != "splash":
        return False
    if chunk_offset is None or quantized:
        return False
    d = config.head_dim
    if d % 128 != 0 or q_len % 128 != 0 or kv_len % 128 != 0:
        return False
    if mesh is None:
        from ..parallel.mesh import current_mesh

        mesh = current_mesh()
    if mesh is not None:
        if mesh.shape.get("seq", 1) > 1 or mesh.shape.get("stage", 1) > 1:
            return False
        tp = mesh.shape.get("tensor", 1)
        rp = int(
            np.prod([
                mesh.shape.get(a, 1) for a in ("data", "fsdp")
            ])
        )
        if config.kv_heads % tp != 0 or batch % rp != 0:
            return False
    return True


# ---------------------------------------------------------------------------
# Splash-mha prefill
# ---------------------------------------------------------------------------


def _maybe_fault_splash() -> None:
    """Chaos-drill hook: faults.py trace-time registry, site
    "splash_kernel" (the splash twin of ops.flash_attention's hook)."""
    from ..faults import fire_trace

    fire_trace("splash_kernel")


def _splash_block_sizes(T: int, S: int):
    """Tuned-enough BlockSizes: 512 where the length allows (the MXU
    pipelining win splash exists for), 128 otherwise (the kernel's lane
    minimum; eligibility already guarantees 128-multiples)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    bq = 512 if T % 512 == 0 else 128
    bkv = 512 if S % 512 == 0 else 128
    return sk.BlockSizes(block_q=bq, block_kv=bkv, block_kv_compute=bkv)


@functools.partial(
    jax.jit, static_argnames=("chunk_offset", "interpret")
)
def splash_prefill(
    q: jnp.ndarray,   # [B, T, H, d] — this chunk's queries
    k: jnp.ndarray,   # [B, S, KVH, d] — the FULL post-write cache view
    v: jnp.ndarray,   # [B, S, KVH, d]
    *,
    chunk_offset: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Upstream splash-mha over one prefill chunk of a right-padded
    insert.

    Query row t sits at absolute position ``chunk_offset + t``; cache
    column j holds position j (the insert path's slot-index == position
    contract).  A pure ``CausalMask((T, S), offset=chunk_offset)``
    (semantics: query t attends j <= t + offset) is therefore EXACTLY
    the insert contract, with no SegmentIds: right padding means every
    column below a real token is real, so real queries only ever attend
    real written columns; padding queries attend padding columns and
    produce finite garbage that nothing consumes (the last-token gather
    indexes real rows only, and padding slots land in the pool carrying
    pos -1, which every decode kernel masks).  Columns at/after
    ``chunk_offset + T`` are unwritten cache tail — masked by causality.

    GQA is native (q [H, T, d] vs k/v [KVH, T, d] per row); the caller
    contract pre-scales q AND k by d**-0.25 (splash applies no scale;
    splitting the scale keeps both operands in comfortable bf16 range).
    Returns [B, T, H, d] in q's dtype.
    """
    _maybe_fault_splash()
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    B, T, H, d = q.shape
    S = k.shape[1]
    interpret = _resolve_interpret(interpret)
    mask = sm.MultiHeadMask(
        masks=[sm.CausalMask(shape=(T, S), offset=chunk_offset)] * H
    )
    kernel = sk.make_splash_mha_single_device(
        mask,
        block_sizes=_splash_block_sizes(T, S),
        interpret=interpret,
    )
    scale = d ** -0.25
    qs = jnp.swapaxes(q * scale, 1, 2)               # [B, H, T, d]
    ks = jnp.swapaxes(k * scale, 1, 2)               # [B, KVH, S, d]
    vs = jnp.swapaxes(v, 1, 2)
    out = jax.vmap(kernel)(qs, ks, vs)               # [B, H, T, d]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def splash_prefill_attention(
    q: jnp.ndarray,   # [B, T, H, d]
    k: jnp.ndarray,   # [B, S, KVH, d]
    v: jnp.ndarray,   # [B, S, KVH, d]
    *,
    chunk_offset: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Mesh-aware entry point for the splash prefill kernel.

    A pallas_call is not partitioned by GSPMD, so under an active mesh
    the kernel runs per-shard inside shard_map — heads over "tensor"
    (contiguous H chunks == contiguous KVH chunks under the
    h = kvh*G + g layout), rows over the batch axes — the same
    placement as ``ops.paged_attention``; each shard builds its own
    (local-head-count) mask.  No collectives: every (row, head) is
    independent; the caller's o-projection all-reduce recombines heads.
    ``splash_eligible`` already vetted the divisibility, so unlike the
    paged wrapper there is no raise path here.
    """
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        tp = mesh.shape.get("tensor", 1)
        row_axes = tuple(
            a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1
        )
        if tp > 1 or row_axes:
            rows = row_axes if row_axes else None
            tens = "tensor" if tp > 1 else None
            spec = P(rows, None, tens, None)

            def body(q, k, v):
                # audit: trace-domain(chunk_offset is the insert
                # loop's PYTHON-int chunk base — multiples of the
                # fixed prefill chunk inside the pow2-bucketed group
                # width, O(blocks_per_slot) values, bounded where
                # serving constructs it; interpret is
                # platform-derived and ctor-stable, one value per
                # process)
                return splash_prefill(
                    q, k, v, chunk_offset=chunk_offset,
                    interpret=interpret,
                )

            fn = jax.shard_map(
                body, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )
            return fn(q, k, v)
    # audit: trace-domain(same bounds as the shard_map body above:
    # chunk_offset is serving's bounded Python-int chunk base,
    # interpret is platform-derived)
    return splash_prefill(
        q, k, v, chunk_offset=chunk_offset, interpret=interpret
    )


# ---------------------------------------------------------------------------
# Stock Pallas paged-attention decode
# ---------------------------------------------------------------------------


def _maybe_fault_stock() -> None:
    """Chaos-drill hook: faults.py trace-time registry, site
    "stock_paged_kernel" (the stock twin of ops.paged_attention's)."""
    from ..faults import fire_trace

    fire_trace("stock_paged_kernel")


def _pages_per_compute_block(mb: int) -> int:
    """Largest divisor of the per-row page count that is <= 8 — the
    stock kernel requires pages_per_sequence % pages_per_compute_block
    == 0, and ~8 pages per flash block keeps its VMEM double-buffer
    modest at every geometry we serve."""
    return max(d for d in range(1, min(mb, 8) + 1) if mb % d == 0)


def _stock_launch(
    q: jnp.ndarray,            # [B, G, d] — ONE kv head's query group
    k_pages: jnp.ndarray,      # [1, NP, BLK, d] flat page view
    v_pages: jnp.ndarray,      # [1, NP, BLK, d]
    lengths: jnp.ndarray,      # [B] int32
    page_indices: jnp.ndarray,  # [B, MB] int32 FLAT page ids
    *,
    pages_per_compute_block: int,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Vendored launch of the stock paged-attention kernel body.

    This mirrors the upstream ``paged_attention`` entry point's
    non-quantized / megacore=None / inline_seq_dim branch exactly (same
    grid, specs, scratch, scalar prefetch), with two deliberate
    differences: (a) it RETURNS the kernel's (out, m, l) instead of
    discarding m/l — our decode contract merges the step's own K/V at
    the softmax level against an immutable pool, which needs the pool
    logsumexp; and (b) ``interpret`` reaches the pallas_call, making
    the kernel CPU-testable (the upstream wrapper never exposes it).
    The kernel body itself is imported from jax, not copied.

    Returns (out [B, G, d] fp32/q-dtype NORMALIZED over the attended
    slots, m [B, G], l [B, G]); rows with length 0 keep the kernel's
    zero-init (m = -inf, l = 0, out = 0).
    """
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention_kernel as stock,
    )

    B, G, d = q.shape
    MB = page_indices.shape[1]
    page_size = k_pages.shape[2]
    if G % 8 != 0:
        # Upstream layout hint: reshape to [B, G, 1, d] and launch fp32
        # so XLA picks a <1x128> layout for the sub-8-sublane q tile.
        q4 = q.reshape(B, G, 1, d)
        q_block_spec = pl.BlockSpec(
            (None, G, None, d), lambda core, b, h, *_: (b, h, 0, 0)
        )
        q_dtype = jnp.float32
        launch_q = q4
    else:
        q_block_spec = pl.BlockSpec(
            (None, G, d), lambda core, b, h, *_: (b, h, 0)
        )
        q_dtype = q.dtype
        launch_q = q
    grid = (1, B, 1)  # (num_cores, batch, kv heads) — one head per call
    in_specs = [
        q_block_spec,
        pl.BlockSpec(memory_space=pl.ANY),
        None,
        pl.BlockSpec(memory_space=pl.ANY),
        None,
    ]
    scratch_shapes = (
        pltpu.VMEM(
            (2, pages_per_compute_block, page_size, d), k_pages.dtype
        ),
        None,
        pltpu.VMEM(
            (2, pages_per_compute_block, page_size, d), v_pages.dtype
        ),
        None,
        pltpu.SemaphoreType.DMA((2,)),  # k_sems
        pltpu.SemaphoreType.DMA((2,)),  # v_sems
    )
    out, m, l = pl.pallas_call(
        functools.partial(
            stock.paged_flash_attention_kernel_inline_seq_dim,
            pages_per_sequence=MB,
            batch_size=B,
            pages_per_compute_block=pages_per_compute_block,
            mask_value=stock.DEFAULT_MASK_VALUE,
            attn_logits_soft_cap=None,
            megacore_mode=None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=in_specs,
            out_specs=[q_block_spec, q_block_spec, q_block_spec],
            grid=grid,
            scratch_shapes=scratch_shapes,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        out_shape=[
            jax.ShapeDtypeStruct(launch_q.shape, q_dtype),
            jax.ShapeDtypeStruct((*launch_q.shape[:-1], 1), jnp.float32),
            jax.ShapeDtypeStruct((*launch_q.shape[:-1], 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        lengths,
        page_indices.reshape(-1),
        jnp.zeros((1,), jnp.int32),  # buffer index
        jnp.ones((1,), jnp.int32),  # init flag
        launch_q.astype(q_dtype),
        k_pages,
        None,
        v_pages,
        None,
    )
    return (
        out.reshape(B, G, d),
        m.reshape(B, G),
        l.reshape(B, G),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def stock_paged_decode(
    q: jnp.ndarray,        # [B, 1, H, d] — this step's queries
    k_new: jnp.ndarray,    # [B, 1, KVH, d] — this step's projections
    v_new: jnp.ndarray,    # [B, 1, KVH, d]
    k_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d] (or [KVH, NB, BLK, d])
    v_pool: jnp.ndarray,
    table: jnp.ndarray,    # [B, MB] int32 block ids (NB = sentinel)
    q_pos: jnp.ndarray,    # [B] int32 token position (-1 = inactive row)
    layer: Optional[jnp.ndarray] = None,  # int32 index into L
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """One T=1 decode step over (pool blocks ∪ the step's new slot)
    using the STOCK Pallas paged-attention kernel body.

    Same contract as ``ops.paged_attention.paged_decode_attention``
    restricted to T == 1 and full-precision pools: the pool stays
    immutable through the layer scan, the step's own K/V merges at the
    softmax level, and the row's query position IS the pool fill
    (slot index == position on the insert path), so
    ``lengths = max(q_pos, 0)`` — inactive rows (q_pos -1) attend
    nothing (the kernel's zero-init leaves lse = -inf, the merge weight
    underflows to exactly 0, and the row's finite-garbage output drops
    at write-back), with NO extra serving plumbing.

    Layer/head plane selection rides the PAGE INDICES instead of the
    kernel (the stock kernel has no layer axis): the [L, KVH, NB, ...]
    pool reshapes — free, row-major — to one flat [1, L*KVH*NB, ...]
    page array, and each (traced) layer + (static) local kv head offsets
    the row's table by ``(layer*KVH + h) * NB``; sentinel entries clamp
    to page 0, which ``lengths`` guarantees is never attended (fill
    only covers allocated blocks).  A per-KV-head Python loop launches
    the kernel with num_kv_heads == 1 — KVH/shard is small everywhere
    we serve, and the alternative (a transposed [KVH, L*NB, ...] view)
    would materialize a full pool copy per step, the exact copy-traffic
    the custom kernel's in-kernel layer select exists to avoid.

    Numerics note (documented, A/B-relevant): the stock kernel casts
    K/V tiles to bf16 in-kernel regardless of pool dtype, so fp32
    pools see one extra rounding vs the custom kernel.  Returns
    [B, 1, H, d] in q's dtype.
    """
    _maybe_fault_stock()
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
        layer = None
    if k_pool.shape[0] != 1 and layer is None:
        raise ValueError(
            "multi-layer pool requires the `layer` index (a 5-D pool "
            "with layer=None would attend layer 0 for every layer)"
        )
    B, T, H, d = q.shape
    if T != 1:
        raise NotImplementedError(
            "stock-paged decode is T == 1 only; multi-token (speculative "
            "verify) dispatches use the custom paged kernel"
        )
    L, KVH, NB, BLK, _ = k_pool.shape
    MB = table.shape[1]
    G = H // KVH
    interpret = _resolve_interpret(interpret)
    ppcb = _pages_per_compute_block(MB)
    scale = 1.0 / (d ** 0.5)

    # Free flat views: [L, KVH, NB, BLK, d] -> [1, L*KVH*NB, BLK, d]
    # (row-major reshape; plane (l, h) starts at page (l*KVH + h)*NB).
    k_flat = k_pool.reshape(1, L * KVH * NB, BLK, d)
    v_flat = v_pool.reshape(1, L * KVH * NB, BLK, d)
    layer_idx = (
        jnp.zeros((), jnp.int32) if layer is None
        else jnp.asarray(layer, jnp.int32).reshape(())
    )
    lengths = jnp.maximum(q_pos.astype(jnp.int32), 0)
    # The kernel pre-applies no softmax scale: fold 1/sqrt(d) into q
    # once (scores-level; the new-slot merge below scales explicitly).
    q3 = (q[:, 0] * scale).astype(q.dtype)  # [B, H, d]

    outs, lses = [], []
    for h in range(KVH):
        flat_tbl = jnp.where(
            table < NB,
            table.astype(jnp.int32) + (layer_idx * KVH + h) * NB,
            0,
        )
        o_h, m_h, l_h = _stock_launch(
            q3[:, h * G:(h + 1) * G, :], k_flat, v_flat,
            lengths, flat_tbl,
            pages_per_compute_block=ppcb, interpret=interpret,
        )
        # lse = m + log(l); length-0 rows keep m=-inf/l=0 -> lse=-inf,
        # so the merge weight exp(lse - m_tot) is exactly 0 (no NaN:
        # the new-slot score below is always finite).
        lse_h = jnp.where(
            l_h > 0.0,
            m_h + jnp.log(jnp.where(l_h > 0.0, l_h, 1.0)),
            -jnp.inf,
        )
        outs.append(o_h.astype(jnp.float32))
        lses.append(lse_h)
    out_pool = jnp.stack(outs, axis=1)   # [B, KVH, G, d] normalized
    lse = jnp.stack(lses, axis=1)        # [B, KVH, G]

    # Softmax-level merge of the step's own slot (token attends itself;
    # same math as _paged_decode_local's T=1 case).
    q4 = q[:, 0].reshape(B, KVH, G, d).astype(jnp.float32)
    s_new = jnp.einsum(
        "bkgd,bkd->bkg", q4, k_new[:, 0].astype(jnp.float32)
    ) * scale
    m_tot = jnp.maximum(lse, s_new)
    w_pool = jnp.exp(lse - m_tot)
    p_new = jnp.exp(s_new - m_tot)
    denom = w_pool + p_new
    out = (
        out_pool * w_pool[..., None]
        + p_new[..., None] * v_new[:, 0].astype(jnp.float32)[:, :, None, :]
    ) / denom[..., None]
    return out.reshape(B, 1, H, d).astype(q.dtype)


def stock_paged_decode_attention(
    q: jnp.ndarray,        # [B, 1, H, d]
    k_new: jnp.ndarray,    # [B, 1, KVH, d]
    v_new: jnp.ndarray,    # [B, 1, KVH, d]
    k_pool: jnp.ndarray,   # [L, KVH, NB, BLK, d]
    v_pool: jnp.ndarray,
    table: jnp.ndarray,    # [B, MB]
    q_pos: jnp.ndarray,    # [B]
    layer: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Mesh-aware entry point for the stock paged decode kernel —
    the drop-in twin of ``paged_decode_attention`` (minus int8, minus
    T > 1).  Under a mesh the KV heads split over "tensor" and rows
    over the batch axes inside shard_map, the KV-head-over-"tensor"
    layout serve_mesh.py already places, so the flat-page offsets
    inside ``stock_paged_decode`` see the LOCAL head count.  The
    divisibility requirements (and the error text) match the custom
    kernel's — serving's ``_kernel_eligible`` host check already vets
    exactly these before enabling either paged kernel."""
    B = q.shape[0]
    KVH = k_new.shape[2]
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        tp = mesh.shape.get("tensor", 1)
        row_axes = tuple(
            a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1
        )
        rp = (
            int(np.prod([mesh.shape[a] for a in row_axes]))
            if row_axes else 1
        )
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
            layer_op = (
                jnp.zeros((), jnp.int32) if layer is None
                else jnp.asarray(layer, jnp.int32).reshape(())
            )

            def body(q, k_new, v_new, k_pool, v_pool, table, q_pos, layer):
                # audit: trace-domain(interpret is platform-derived
                # and ctor-stable — one value per process)
                return stock_paged_decode(
                    q, k_new, v_new, k_pool, v_pool, table, q_pos,
                    layer, interpret=interpret,
                )

            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(
                    head4, head4, head4, pooled, pooled,
                    P(rows, None), P(rows), P(),
                ),
                out_specs=head4, check_vma=False,
            )
            return fn(
                q, k_new, v_new, k_pool, v_pool, table, q_pos, layer_op
            )

    # audit: trace-domain(interpret is platform-derived and
    # ctor-stable — one value per process)
    return stock_paged_decode(
        q, k_new, v_new, k_pool, v_pool, table, q_pos, layer,
        interpret=interpret,
    )
