"""Manifold-constrained hyper-connections (mHC; Xie et al., arXiv 2512.24880,
on Zhu et al.'s hyper-connections, arXiv 2409.19606): the residual path as
`n` streams `X` [B, T, n, C], mixed around an inner function F (attention or
an FFN on one [B, T, C] stream) by three token-dependent coefficient sets:

    x' = vec(X) / sqrt(mean(vec(X)^2) + eps)        no learned gain, over n*C
    m  = x' phi                                     phi [n*C, n + n + n*n]
    H_pre  = sigmoid(alpha_pre m[:n] + b[:n])                       (0, 1)^n
    H_post = 2 sigmoid(alpha_post m[n:2n] + b[n:2n])                (0, 2)^n
    H_res  = Sinkhorn(exp(clip(alpha_res mat(m[2n:]) + mat(b[2n:]))))  [n, n]
    u = sum_i H_pre[i] X[i];   y = F(u)
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

Sinkhorn: `iters` times, columns then rows, `M /= sum + eps`; H_res is doubly
stochastic to ~1e-3, which is what keeps the streams' sum an identity path.

Three scopes a device trace is read by: `hc.coeff` (norm, projection,
sigmoid, Sinkhorn), `hc.pre`, `hc.post`.  The coefficient path is float32:
the projection of a bfloat16 stream multiplies it, as stored, with phi cut
into three bfloat16 parts side by side (one pass of the MXU over 3 x 24
columns, summed in float32), so the stream is never converted to float32 in
memory.  Between the projection and the mixes the coefficients live
TRANSPOSED, tokens minor (`[n, n, N]`), and Sinkhorn's forty normalisations
are ONE Pallas kernel a unit on the chip (`sinkhorn`): a token a lane, an
entry of the matrix a vector register, the rounds unrolled in registers — not
a dispatch a normalisation, which is what 20 dependent rounds of reduce and
divide lower to in XLA (three fusions a round, compiled for a v5e).  The
mixes accumulate in float32 and store the stream in its own type.
"""

from __future__ import annotations

import functools
import operator
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_LANES, _SUBLANES = 128, 8
_TILE_TOKENS = _LANES * _SUBLANES   # tokens a grid step of the Sinkhorn kernel

# What a unit counts, summed over units by whoever accumulates them: tokens
# whose H_res ended with a row or column sum farther than `TOLERANCE` from 1,
# and the tokens counted.
STATS = ("unconverged", "units")
N_STATS = len(STATS)
TOLERANCE = 1e-3

# Seeded parameters (`init_unit`).  phi ~ N(0, 1 / (n C)) so that m ~ N(0, 1)
# a token at every size; alpha = (pre, post, res) below.  H_res preserves the
# streams' SUM, which is also the model's output, so it counts only where a
# unit reads and feeds its streams unevenly; with even gates a wrong H_res is
# read by nothing downstream (measured: PERF.md section 6, PR 51).  So the gates
# are seeded as a ROUTE: unit u (two a layer, counted through the stack) reads
# mostly stream u mod n and feeds mostly stream (u + 2) mod n — b_pre / b_post
# are +GATE there and -GATE elsewhere, + N(0, B_STD_GATES^2) — and what a unit
# wrote reaches the next unit's stream through H_res alone.  The route is the
# same for every seed: drawn freely, the gates made one seed's check read ten
# times another's.  b_res = B_DIAG I + N(0, B_STD_RES^2): H_res keeps a
# stream's larger part in place, is no identity and moves with the token — a
# token's off-diagonal mass (1 - trace / n) is 0.60 in the mean, 0.13-0.98 over
# tokens (identity 0, uniform 0.75), and ~0.2 % of tokens end 20 rounds farther
# than `TOLERANCE` from doubly stochastic (20,000 draws of the logits).
ALPHA = (0.8, 1.2, 1.0)
GATE = 1.5
B_STD_GATES = 0.3
B_STD_RES = 0.5
B_DIAG = 1.0


def init_unit(key: jax.Array, layers: int, n: int, C: int, first_unit: int = 0,
              stride: int = 2) -> Dict[str, jnp.ndarray]:
    """Seeded parameters of `layers` stacked units, float32 (see above); the
    stack's units are numbered `first_unit`, `first_unit + stride`, ..."""
    k = jax.random.split(key, 2)
    K = n * n + 2 * n
    u = first_unit + stride * jnp.arange(layers)[:, None]
    streams = jnp.arange(n)[None, :]
    route = jnp.concatenate([
        jnp.where(streams == u % n, GATE, -GATE),           # b_pre: reads
        jnp.where(streams == (u + 2) % n, GATE, -GATE),     # b_post: feeds
        jnp.tile(B_DIAG * jnp.eye(n).reshape(-1), (layers, 1)),
    ], axis=1)
    std = jnp.concatenate([jnp.full((2 * n,), B_STD_GATES), jnp.full((n * n,), B_STD_RES)])
    return {
        "phi": jax.random.normal(k[0], (layers, n * C, K), jnp.float32) * (n * C) ** -0.5,
        "b": jax.random.normal(k[1], (layers, K), jnp.float32) * std + route,
        "alpha": jnp.tile(jnp.asarray(ALPHA, jnp.float32), (layers, 1)),
    }


def _add(parts):
    return functools.reduce(operator.add, parts)


def _project(x: jnp.ndarray, phi: jnp.ndarray) -> jnp.ndarray:
    """x [N, nC] (as stored) times phi [nC, K] float32 -> [N, K] float32."""
    if x.dtype != jnp.bfloat16:
        return jnp.dot(x.astype(jnp.float32), phi, precision=jax.lax.Precision.HIGHEST)
    K, parts, rest = phi.shape[1], [], phi
    for _ in range(3):          # 3 x 8 mantissa bits: phi to float32's own
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(jnp.float32)
    m = jnp.dot(x, jnp.concatenate(parts, axis=1), preferred_element_type=jnp.float32)
    return m[:, :K] + m[:, K:2 * K] + m[:, 2 * K:]


def _resolve_interpret(interpret=None):
    if interpret is None:
        # Mosaic only targets TPU (see ops/flash_attention.py).
        interpret = jax.default_backend() != "tpu"
    return interpret


def _sinkhorn_xla(logits: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    def round_(_, M):
        M = M / (jnp.sum(M, axis=0, keepdims=True) + eps)
        return M / (jnp.sum(M, axis=1, keepdims=True) + eps)

    return lax.fori_loop(0, iters, round_, jnp.exp(logits))


def _sinkhorn_kernel(a_ref, o_ref, *, n: int, iters: int, eps: float):
    """One tile of 1,024 tokens: each of the n x n entries is ONE vector
    register [8, 128] of tokens, every sum an add of registers, the `iters`
    rounds unrolled — nothing crosses lanes or leaves the registers."""
    M = [[jnp.exp(a_ref[i * n + j]) for j in range(n)] for i in range(n)]
    for _ in range(iters):
        col = [_add([M[i][j] for i in range(n)]) + eps for j in range(n)]
        M = [[M[i][j] / col[j] for j in range(n)] for i in range(n)]
        row = [_add(M[i]) + eps for i in range(n)]
        M = [[M[i][j] / row[i] for j in range(n)] for i in range(n)]
    for i in range(n):
        for j in range(n):
            o_ref[i * n + j] = M[i][j]


def _sinkhorn_pallas(logits: jnp.ndarray, iters: int, eps: float,
                     interpret: bool) -> jnp.ndarray:
    from jax.experimental import pallas as pl

    n, _, N = logits.shape
    a = jnp.pad(logits.reshape(n * n, N), ((0, 0), (0, -N % _TILE_TOKENS)))
    a = a.reshape(n * n, -1, _LANES)                  # [n*n, 8 * tiles, 128]
    block = pl.BlockSpec((n * n, _SUBLANES, _LANES), lambda t: (0, t, 0))
    out = pl.pallas_call(
        functools.partial(_sinkhorn_kernel, n=n, iters=iters, eps=eps),
        grid=(a.shape[1] // _SUBLANES,), in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        interpret=interpret, name="hc_sinkhorn",
    )(a)
    return out.reshape(n * n, -1)[:, :N].reshape(n, n, N)


def sinkhorn(logits: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    """exp(logits) [n, n, N] float32 normalised `iters` times, columns (sums
    over axis 0) then rows (axis 1).  On the chip ONE kernel a call
    (`_sinkhorn_kernel`: the whole chain in registers, a token a lane); off
    it the same rounds as an XLA loop — a kernel of 20 unrolled rounds costs
    the CPU's compiler ~14 s a unit, interpreted or not."""
    if _resolve_interpret():
        return _sinkhorn_xla(logits, iters, eps)
    return _sinkhorn_pallas(logits, iters, eps, interpret=False)


def coefficients(
    X: jnp.ndarray,                     # [B, T, n, C]
    hp: Dict[str, jnp.ndarray],         # one unit's phi [nC, K], b [K], alpha [3]
    *,
    iters: int,
    eps: float,
    clamp: Tuple[float, float],
    valid: Optional[jnp.ndarray] = None,   # [B, T] tokens the counters count
):
    """(H_pre [B,T,n], H_post [B,T,n], H_res [B,T,n,n]) float32 of the
    stream, and the unit's `STATS` [2] int32."""
    B, T, n, C = X.shape
    N = B * T
    with jax.named_scope("hc.coeff"):
        x = X.reshape(N, n * C)
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1)
        m = _project(x, hp["phi"].astype(jnp.float32)) * jax.lax.rsqrt(ms + eps)[:, None]
        # tokens minor from here on
        a = m.T                                                    # [K, N]
        b = hp["b"].astype(jnp.float32)[:, None]
        alpha = hp["alpha"].astype(jnp.float32)
        h_pre = jax.nn.sigmoid(alpha[0] * a[:n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * a[n:2 * n] + b[n:2 * n])
        res = jnp.clip(alpha[2] * a[2 * n:] + b[2 * n:], clamp[0], clamp[1])
        h_res = sinkhorn(res.reshape(n, n, N), iters, eps)
        off = jnp.maximum(
            jnp.max(jnp.abs(_add([h_res[i] for i in range(n)]) - 1.0), axis=0),
            jnp.max(jnp.abs(_add([h_res[:, j] for j in range(n)]) - 1.0), axis=0))
        counted = jnp.ones((N,), bool) if valid is None else valid.reshape(N)
        stats = jnp.stack([
            jnp.sum(counted & (off > TOLERANCE), dtype=jnp.int32),
            jnp.sum(counted, dtype=jnp.int32)])
        return (h_pre.T.reshape(B, T, n), h_post.T.reshape(B, T, n),
                h_res.transpose(2, 0, 1).reshape(B, T, n, n), stats)


def pre(X: jnp.ndarray, h_pre: jnp.ndarray) -> jnp.ndarray:
    """The inner function's input [B, T, C]: sum_i H_pre[i] X[i]."""
    with jax.named_scope("hc.pre"):
        u = _add([h_pre[..., i, None] * X[..., i, :].astype(jnp.float32)
                  for i in range(X.shape[2])])
        return u.astype(X.dtype)


def post(X: jnp.ndarray, y: jnp.ndarray, h_res: jnp.ndarray,
         h_post: jnp.ndarray) -> jnp.ndarray:
    """The next stream [B, T, n, C]: H_res X + H_post y."""
    with jax.named_scope("hc.post"):
        n = X.shape[2]
        xs = [X[..., j, :].astype(jnp.float32) for j in range(n)]
        yf = y.astype(jnp.float32)
        out = [_add([h_res[..., i, j, None] * xs[j] for j in range(n)])
               + h_post[..., i, None] * yf for i in range(n)]
        return jnp.stack(out, axis=2).astype(X.dtype)
