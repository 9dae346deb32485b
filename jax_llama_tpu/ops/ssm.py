"""The selective state-space recurrence (Mamba-1), in the two forms serving
needs.

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
