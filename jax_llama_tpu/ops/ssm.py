"""The selective state-space recurrences, each in the two forms serving
needs.  First the per-channel one (Mamba-1: `ssm_step`, `ssm_scan`), then
the matrix-a-head one (Mamba-2 / SSD: `ssd_step`, `ssd_scan`; its own
section below).

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * c_t) B_t^T        h [N, Di] float32
    y_t = sum_n h_t[n] * C_t[n]                               (the caller adds Dskip * c_t)

`ssm_step` advances every row by ONE token (the decode iteration): the state
is read, updated and written; a row that is not `live` keeps its state
bit-for-bit.  `ssm_scan` takes a row's state through the `T` tokens of a
prompt chunk: the state enters, the first `lengths[b]` tokens update it, the
state leaves.  Its XLA form is a `lax.scan` over tokens (the CPU tests' and
the oracle of the kernel); its Pallas form keeps `h` in vector registers
across a tile of tokens and in VMEM across the chunk, so no `[T, N, Di]`
temporary exists (671 MB a layer at a 2,048-token chunk).

Layouts.  The state is `[B, N, Di]`, channels minor: with `N` (16) minor an
XLA:TPU array pads its 16 lanes to 128 and the per-slot state and every
snapshot grow eightfold.  `A` is `[N, Di]` likewise (`-exp(A_log)^T`).
Everything here is float32.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_LANES = 128
_SUBLANES = 8
_CHANNEL_TILE = _SUBLANES * _LANES   # channels a kernel instance holds: one vreg a state row
_TOKEN_TILE = 256


def _resolve_interpret(interpret=None):
    if interpret is None:
        # Mosaic only targets TPU (see ops/flash_attention.py).
        interpret = jax.default_backend() != "tpu"
    return interpret


def kernel_eligible(T: int, Di: int) -> bool:
    """The Pallas form's shapes: whole channel tiles, and a token count its
    token tile divides (or that is one tile)."""
    return (Di % _CHANNEL_TILE == 0 and T >= _SUBLANES
            and (T % _TOKEN_TILE == 0 or T < _TOKEN_TILE))


def ssm_step(
    h: jnp.ndarray,      # [B, N, Di] float32
    c: jnp.ndarray,      # [B, Di]
    dt: jnp.ndarray,     # [B, Di]
    Bm: jnp.ndarray,     # [B, N]
    Cm: jnp.ndarray,     # [B, N]
    A: jnp.ndarray,      # [N, Di]
    live: jnp.ndarray,   # [B] bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row: (y [B, Di], the new state).  A row that is not
    `live` returns its state unchanged, bit for bit."""
    f32 = jnp.float32
    c, dt, Bm, Cm = (a.astype(f32) for a in (c, dt, Bm, Cm))
    new = (jnp.exp(dt[:, None, :] * A[None]) * h
           + (dt * c)[:, None, :] * Bm[:, :, None])
    y = jnp.sum(new * Cm[:, :, None], axis=1)
    return y, jnp.where(live[:, None, None], new, h)


def ssm_scan(
    h0: jnp.ndarray,       # [B, N, Di] float32
    c: jnp.ndarray,        # [B, T, Di]
    dt: jnp.ndarray,       # [B, T, Di]
    Bm: jnp.ndarray,       # [B, T, N]
    Cm: jnp.ndarray,       # [B, T, N]
    A: jnp.ndarray,        # [N, Di]
    lengths: jnp.ndarray,  # [B] int32: the row's live tokens, a prefix of T
    impl: str = "xla",
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`T` tokens a row: (y [B, T, Di], the state after the row's last live
    token).  Tokens past `lengths[b]` update nothing (their `dt` is taken as
    zero, under which the recurrence is the identity); their `y` is not
    meaningful."""
    f32 = jnp.float32
    T = c.shape[1]
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
    dt = jnp.where(live[:, :, None], dt.astype(f32), 0.0)
    x = dt * c.astype(f32)
    Bm, Cm = Bm.astype(f32), Cm.astype(f32)
    if impl == "pallas":
        return _scan_pallas(h0, x, dt, Bm, Cm, A, _resolve_interpret(interpret))

    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs
        h = jnp.exp(dt_t[:, None, :] * A[None]) * h + x_t[:, None, :] * B_t[:, :, None]
        return h, jnp.sum(h * C_t[:, :, None], axis=1)

    hT, y = lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), hT


def _scan_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, h0_ref, y_ref, hT_ref, h_scr,
                 *, n_state: int, tile: int):
    """One (row, channel tile, token tile): the state rows are `n_state`
    [8, 128] vregs carried through the tile's tokens in registers, through
    the chunk's token tiles in `h_scr`.  `B_t[n]` and `C_t[n]` are scalars
    (SMEM) against whole vregs, so nothing reduces across lanes."""
    from jax.experimental import pallas as pl

    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        h_scr[...] = h0_ref[0]

    a = [a_ref[n] for n in range(n_state)]

    def token(i, h):
        dt_i, x_i = dt_ref[0, i], x_ref[0, i]
        y = jnp.zeros_like(dt_i)
        out = []
        for n in range(n_state):
            h_n = jnp.exp(dt_i * a[n]) * h[n] + x_i * b_ref[0, i, n]
            y = y + h_n * c_ref[0, i, n]
            out.append(h_n)
        y_ref[0, i] = y
        return tuple(out)

    h = lax.fori_loop(0, tile, token, tuple(h_scr[n] for n in range(n_state)))
    for n in range(n_state):
        h_scr[n] = h[n]

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        hT_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_pallas(h0, x, dt, Bm, Cm, A, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, Di = x.shape
    N = A.shape[0]
    assert kernel_eligible(T, Di), (T, Di)
    tile = min(_TOKEN_TILE, T)
    nd = Di // _CHANNEL_TILE
    fold = lambda a: a.reshape(a.shape[:-1] + (Di // _LANES, _LANES))  # noqa: E731
    tok = pl.BlockSpec((1, tile, _SUBLANES, _LANES), lambda b, d, t: (b, t, d, 0))
    state = pl.BlockSpec((1, N, _SUBLANES, _LANES), lambda b, d, t: (b, 0, d, 0))
    scalars = pl.BlockSpec((1, tile, N), lambda b, d, t: (b, t, 0),
                           memory_space=pltpu.SMEM)
    y, hT = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=N, tile=tile),
        grid=(B, nd, T // tile),
        in_specs=[
            scalars, scalars, tok, tok,
            pl.BlockSpec((N, _SUBLANES, _LANES), lambda b, d, t: (0, d, 0)),
            state,
        ],
        out_specs=[tok, state],
        out_shape=[
            jax.ShapeDtypeStruct(fold(x).shape, jnp.float32),
            jax.ShapeDtypeStruct(fold(h0).shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, _SUBLANES, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(Bm, Cm, fold(x), fold(dt), fold(A), fold(h0))
    return y.reshape(B, T, Di), hT.reshape(B, N, Di)


# ---------------------------------------------------------------------------
# The matrix-a-head recurrence (Mamba-2, "SSD"): one scalar decay a head a
# token, a state [Hm, P, N] a row, `B` / `C` shared by groups of heads.
#
#     h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        h [Hm, P, N] float32
#     y_t = h_t C_t                                       (the caller adds Dskip * x_t)
#
# `ssd_step` advances every row by ONE token; a row that is not `live` keeps
# its state bit for bit.  `ssd_scan` takes a row's state through the `T`
# tokens of a prompt chunk in chunks of `chunk` tokens: inside a chunk
# `Y = (L o (C B^T)) X` with `L[t, s] = exp(sum_{s<r<=t} dt_r A)` for
# `s <= t`, plus `C_t` against the chunk's entry state decayed to `t`; between
# chunks the state carries through a `lax.scan`.  Everything a chunk needs is
# a matmul ([Q, Q] scores a head, [Q, N] x [N, P] against the state), no
# `[T, Hm, P, N]` temporary exists, and the same code is the CPU tests' form.
#
# Layout.  The state is `[B, Hm, P, N]`, `N` minor (256 here: two lane
# tiles), `P` on the sublanes.  Everything is float32, and the matmuls run
# at the highest precision: the state compounds over thousands of tokens,
# and the scan is well under a hundredth of a prompt chunk's operations.
# ---------------------------------------------------------------------------

_HIGHEST = lax.Precision.HIGHEST


def _by_head(g: jnp.ndarray, heads: int) -> jnp.ndarray:
    """[..., G, N] of the groups -> [..., Hm, N] of the heads (head h reads
    group h // (Hm / G))."""
    return jnp.repeat(g, heads // g.shape[-2], axis=-2)


def ssd_step(
    h: jnp.ndarray,      # [B, Hm, P, N] float32
    x: jnp.ndarray,      # [B, Hm, P]
    dt: jnp.ndarray,     # [B, Hm]
    Bm: jnp.ndarray,     # [B, G, N]
    Cm: jnp.ndarray,     # [B, G, N]
    A: jnp.ndarray,      # [Hm]
    live: jnp.ndarray,   # [B] bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row: (y [B, Hm, P], the new state).  A row that is not
    `live` returns its state unchanged, bit for bit."""
    f32 = jnp.float32
    x, dt, Bm, Cm = (a.astype(f32) for a in (x, dt, Bm, Cm))
    Hm = h.shape[1]
    decay = jnp.exp(dt * A)[:, :, None, None]
    new = decay * h + (dt[:, :, None] * x)[..., None] * _by_head(Bm, Hm)[:, :, None, :]
    kept = jnp.where(live[:, None, None, None], new, h)
    # `y` reads what is written (a dead row's `y` is nobody's): one pass over
    # the slab yields both.
    return jnp.sum(kept * _by_head(Cm, Hm)[:, :, None, :], axis=-1), kept


def ssd_scan(
    h0: jnp.ndarray,       # [B, Hm, P, N] float32
    x: jnp.ndarray,        # [B, T, Hm, P]
    dt: jnp.ndarray,       # [B, T, Hm]
    Bm: jnp.ndarray,       # [B, T, G, N]
    Cm: jnp.ndarray,       # [B, T, G, N]
    A: jnp.ndarray,        # [Hm]
    lengths: jnp.ndarray,  # [B] int32: the row's live tokens, a prefix of T
    chunk: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`T` tokens a row: (y [B, T, Hm, P], the state after the row's last
    live token).  Tokens past `lengths[b]` update nothing (their `dt` is
    taken as zero, under which the recurrence is the identity); their `y` is
    not meaningful.  `T` need not be a multiple of `chunk`: the tail is
    padded with such tokens."""
    f32 = jnp.float32
    B, T, Hm, P = x.shape
    G, N = Bm.shape[2:]
    Q = min(chunk, T)
    n = -(-T // Q)
    per = Hm // G
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
    dt = jnp.where(live[:, :, None], dt.astype(f32), 0.0)

    def chunks(a):
        """[B, T, H, ...] -> [n, B, H, Q, ...]: heads (or groups) major, so
        that every product below is a matmul batched over them."""
        a = jnp.pad(a.astype(f32), ((0, 0), (0, n * Q - T)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, Q) + a.shape[2:]), (1, 3), (0, 2))

    causal = jnp.tril(jnp.ones((Q, Q), jnp.bool_))

    def step(h, xs):
        x_c, dt_c, B_c, C_c = xs          # [B, Hm, Q, P], [B, Hm, Q], [B, G, Q, N] x 2
        cum = jnp.cumsum(dt_c * A[None, :, None], axis=2)         # [B, Hm, Q], <= 0
        # L[t, s] = exp(cum_t - cum_s) for s <= t: the decay from s to t.
        seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf)
        CB = jnp.einsum("bgtn,bgsn->bgts", C_c, B_c, precision=_HIGHEST)
        W = jnp.exp(seg) * jnp.repeat(CB, per, axis=1)            # [B, Hm, Q, Q]
        xdt = x_c * dt_c[..., None]                               # [B, Hm, Q, P]
        y = jnp.einsum("bhts,bhsp->bhtp", W, xdt, precision=_HIGHEST)
        # The entry state, decayed to t, read by C_t.
        hg = h.reshape(B, G, per, P, N)
        y_in = jnp.einsum("bgtn,bgepn->bgetp", C_c, hg, precision=_HIGHEST)
        y = y + jnp.exp(cum)[..., None] * y_in.reshape(B, Hm, Q, P)
        # The state at the chunk's end.
        tail = jnp.exp(cum[..., -1:] - cum)                       # [B, Hm, Q]
        xg = (xdt * tail[..., None]).reshape(B, G, per, Q, P)
        add = jnp.einsum("bgesp,bgsn->bgepn", xg, B_c, precision=_HIGHEST)
        h = jnp.exp(cum[..., -1])[..., None, None] * h + add.reshape(h.shape)
        return h, y

    hT, y = lax.scan(step, h0, tuple(chunks(a) for a in (x, dt, Bm, Cm)))
    # [n, B, Hm, Q, P] -> [B, T, Hm, P]
    return jnp.moveaxis(y, (0, 2), (1, 3)).reshape(B, n * Q, Hm, P)[:, :T], hT
