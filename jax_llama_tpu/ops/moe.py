"""Routed experts: a router in one of two forms behind one argument, top-k
with a selection-only bias, and a grouped matmul over the experts a batch
touches.

The layer (deepseek_v3's and KeyeVL2's, as `config.from_published` maps them):

    s = sigmoid(h @ W_g)  or  softmax(h @ W_g)    float32, [N, E]; `score_func`,
                                              which a configuration's block fixes
    selected = top_k(s + b)                   b moves the SELECTION only (None: no bias)
    w = s[selected] / sum(s[selected]) * routed_scaling_factor
    y = sum_e w_e * down_e(silu(gate_e h) * up_e h)

No capacity factor and no dropped token: the (token, expert) pairs are sorted
by expert and each expert multiplies exactly the rows routed to it, however
uneven the split (`group_sizes` is a value, not a shape).  On a TPU the
product is the upstream Pallas grouped matmul (`megablox.gmm`), which visits
only the non-empty groups, so a decode batch of 8 rows reads the ~40 experts
it touches and not all 128; everywhere else it is `lax.ragged_dot`.

The kernel's tile follows from the product's shape (`_tiling`): the whole
contraction and the widest slice of the output whose blocks fit a budget of
vector memory.  Its grid walks, for each slice of N, the (row tile, expert)
pairs in order with the contraction innermost; with ONE step of K the pairs
of one expert ask for the same `[K, tn]` weight block one after another and
the pipeline copies it once, so an expert's weights cross HBM once a product
however many row tiles its rows straddle, in N / tn large copies.

Rows that are not tokens (a masked decode row, prompt padding) are routed to
no expert: they sort behind every real pair, belong to no group, and get a
zero result.  They count in none of the routing statistics.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Rows of one grouped-matmul tile.  Every (non-empty expert, row tile) pair
# is one product with that expert's resident weight block, computed at the
# full tile whatever the rows it holds: at ~96-128 rows an expert (a
# 2048-token chunk) a wider tile multiplies mostly masked rows.
_TILE_M = 128
# Bytes of vector memory a grid step's blocks may take (`_block_bytes`):
# three quarters of the 16 MiB a kernel gets on a v5e without asking for
# more, which the upstream kernel does not.
_BLOCK_BUDGET = 12 * 2 ** 20
# What a call counts of its routing, in this order: (token, expert) pairs,
# distinct experts touched, the call itself, and the largest per-expert
# count — each summed over calls by whoever accumulates them.
STATS = ("assignments", "experts_touched", "layer_calls", "max_load")
N_STATS = len(STATS)


def route(
    h: jnp.ndarray,            # [N, D]
    w_gate: jnp.ndarray,       # [D, E]
    bias: Optional[jnp.ndarray],  # [E] selection-only correction, or None
    *,
    top_k: int,
    scale: float,
    score_func: str = "sigmoid",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(expert ids [N, k] int32, weights [N, k] float32).  Scores and
    weights are float32 whatever the activations are: the sixth and seventh
    scores of 128 lie close, and a bfloat16 sigmoid would pick by rounding.
    `score_func`: "sigmoid", or "softmax" over the experts (the weights are
    the chosen probabilities renormalised, `norm_topk_prob`)."""
    score = {"sigmoid": jax.nn.sigmoid,
             "softmax": lambda x: jax.nn.softmax(x, axis=-1)}[score_func]
    s = score(jnp.einsum(
        "nd,de->ne", h.astype(jnp.float32), w_gate.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ))
    _, idx = lax.top_k(s if bias is None else s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True) * scale
    return idx.astype(jnp.int32), w


def _block_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What one grid step of the grouped matmul holds in vector memory: the
    weight, row and output blocks twice (the pipeline's two buffers) and
    the float32 accumulator."""
    return 2 * (tk * tn + tm * tk + tm * tn) * itemsize + 4 * tm * tn


def _tiling(k: int, n: int, dtype) -> Tuple[int, int, int]:
    """The tile (tm, tk, tn) of a grouped product [., k] x [k, n], from its
    shape alone: the whole contraction and the widest output tile (a divisor
    of n by 128s) whose blocks fit `_BLOCK_BUDGET`; a K too long for that at
    any width by the largest of the old ladder that divides it.  The rows
    do not enter: one row tile of a decode iteration and a chunk's 64-128
    want the same tile (v5e, `tests/tools/gmm_tiles.py`; PERF.md section 6,
    PR 52)."""
    itemsize = jnp.dtype(dtype).itemsize
    widths = [t for t in range(n - n % 128, 0, -128) if n % t == 0] or [n]
    depths = [k] + [t for t in (1024, 768, 512, 256, 128) if t < k and k % t == 0]
    for tk in depths:
        for tn in widths:
            if _block_bytes(_TILE_M, tk, tn, itemsize) <= _BLOCK_BUDGET:
                return _TILE_M, tk, tn
    return _TILE_M, depths[-1], widths[-1]


def grouped_matmul(
    x: jnp.ndarray,            # [M, K], rows sorted by group
    w: jnp.ndarray,            # [L, E, K, N]: every expert layer's experts
    layer: jnp.ndarray,        # int32: which of the L layers
    group_sizes: jnp.ndarray,  # [E] int32, sum <= M
) -> jnp.ndarray:
    """x[rows of group e] @ w[layer, e] for every non-empty group; rows past
    the last group are unspecified (the caller masks them).

    The kernel is handed ALL layers' experts as one [L*E, K, N] operand (a
    free reshape of the stacked weight) and group sizes that are zero outside
    `layer`: it visits the non-empty groups only, so it reads what this layer
    touched.  Slicing `w[layer]` inside the layer scan instead materializes
    the layer's 128 experts as the custom call's operand, a 1.2 GB copy a
    layer a call — half the device's time in the first traced run of
    `kanana2-docqa-long` (v5e, PERF.md section 6, PR 27)."""
    L, E = w.shape[:2]
    if jax.default_backend() != "tpu":
        return lax.ragged_dot(
            x, lax.dynamic_index_in_dim(w, layer, 0, keepdims=False), group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    sizes = lax.dynamic_update_slice(
        jnp.zeros((L * E,), jnp.int32), group_sizes, (layer * E,))
    return gmm(
        x, w.reshape((L * E,) + w.shape[2:]), sizes,
        preferred_element_type=x.dtype,
        tiling=_tiling(w.shape[2], w.shape[3], x.dtype),
    )


def routed_experts(
    h: jnp.ndarray,            # [N, D] normed hidden states
    valid: Optional[jnp.ndarray],  # [N] bool: rows that are tokens
    w_gate: jnp.ndarray,       # [D, E]
    bias: Optional[jnp.ndarray],  # [E], or None
    gate_up: jnp.ndarray,      # [L, E, D, 2F]  (gate | up on the last axis)
    down: jnp.ndarray,         # [L, E, F, D]
    layer: jnp.ndarray,        # int32: which of the L expert layers
    *,
    top_k: int,
    scale: float,
    score_func: str = "sigmoid",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The routed experts' sum [N, D] and the call's routing statistics
    [N_STATS] int32 (see `STATS`)."""
    N, D = h.shape
    E = w_gate.shape[1]
    F = down.shape[2]
    with jax.named_scope("moe.route"):
        idx, w = route(h, w_gate, bias, top_k=top_k, scale=scale,
                       score_func=score_func)
        if valid is not None:
            idx = jnp.where(valid[:, None], idx, E)  # no expert: sorts last
        flat = idx.reshape(-1)                               # [N*k]
        order = jnp.argsort(flat, stable=True)
        group_sizes = jnp.bincount(flat, length=E + 1)[:E].astype(jnp.int32)
        stats = jnp.stack([
            jnp.sum(group_sizes), jnp.sum(group_sizes > 0),
            jnp.ones((), jnp.int32), jnp.max(group_sizes),
        ]).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        M = N * top_k
        Mp = -(-M // _TILE_M) * _TILE_M
        rows = jnp.pad(order // top_k, (0, Mp - M))
        x = jnp.take(h, rows, axis=0)                        # [Mp, D]
        gu = grouped_matmul(x, gate_up, layer, group_sizes)  # [Mp, 2F]
        act = (jax.nn.silu(gu[:, :F]) * gu[:, F:]).astype(h.dtype)
        y = grouped_matmul(act, down, layer, group_sizes)[:M]  # [M, D]
        y = jnp.take(y, jnp.argsort(order), axis=0).reshape(N, top_k, D)
        if valid is not None:
            w = jnp.where(valid[:, None], w, 0.0)
        # a row of no group holds whatever the product left there
        y = jnp.where((w > 0)[..., None], y.astype(jnp.float32), 0.0)
        out = jnp.sum(y * w[..., None], axis=1).astype(h.dtype)
    return out, stats
