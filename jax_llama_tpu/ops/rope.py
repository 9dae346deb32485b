"""Rotary position embeddings — half-split (rotate-half) runtime layout,
numerically identical to Meta's interleaved complex form.

The reference applies RoPE in complex arithmetic over interleaved pairs
``(x[2i], x[2i+1])`` (``/root/reference/jax_llama/model.py:50-92``).  Complex
dtypes are poison for the TPU vector unit, and the *interleaved* real-valued
form is nearly as bad: the strided even/odd slices and the re-interleave at
the end each lower to a lane-shuffling relayout copy (xplane-measured ~3µs
per decode layer at 1B scale).  So the runtime uses the HF-style half-split
pairing — pair i is ``(x[i], x[i + hd/2])``:

    out[i]        = x[i]*cos(t·w_i) - x[i+hd/2]*sin(t·w_i)
    out[i+hd/2]   = x[i]*sin(t·w_i) + x[i+hd/2]*cos(t·w_i)

i.e. contiguous half-lane slices, no shuffles.  Equivalence with the Meta
convention is exact — not approximate — because the q/k projection weights
are stored with their head_dim axis PERMUTED even-first at load time
(``models.llama.fuse_qkv``; the converter applies the same permutation):
feature i of the runtime layout is Meta feature 2i, feature i + hd/2 is
Meta feature 2i+1, so the half-split rotation of the permuted vector IS the
interleaved rotation of the original, and attention scores are invariant
because q and k share the permutation.  ``models.llama.split_qkv`` inverts
it, which is what the parity tests check token-for-token.

Tables are precomputed in float32 and rotation runs in float32 regardless
of activation dtype.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np


def llama3_scale_inv_freq(
    inv_freq: np.ndarray,
    scale_factor: float = 8.0,
    low_freq_factor: float = 1.0,
    high_freq_factor: float = 4.0,
    original_max_len: int = 8192,
) -> np.ndarray:
    """Llama-3.1 frequency scaling for context extension (the published
    ``use_scaled_rope`` rule): high-frequency components (short wavelengths)
    are kept, low-frequency components are divided by ``scale_factor``, and
    the band between is linearly interpolated in wavelength space."""
    wavelen = 2.0 * np.pi / inv_freq
    low_wl = original_max_len / low_freq_factor
    high_wl = original_max_len / high_freq_factor
    smooth = (original_max_len / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor
    )
    mid = ((1.0 - smooth) / scale_factor + smooth) * inv_freq
    out = np.where(wavelen > low_wl, inv_freq / scale_factor, inv_freq)
    in_band = (wavelen <= low_wl) & (wavelen >= high_wl)
    return np.where(in_band, mid, out)


def yarn_inv_freq(
    head_dim: int,
    theta: float,
    factor: float,
    original_max_len: int,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
) -> np.ndarray:
    """YaRN's inverse frequencies (the HF deepseek_v3 form): pair i keeps its
    frequency f_i = theta^(-2i/d) below `low`, is divided by `factor` above
    `high`, and is blended linearly between — `low` / `high` the pair indices
    that make `beta_fast` / `beta_slow` turns over `original_max_len`
    positions, floored / ceiled and clipped to [0, d - 1]."""
    f = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))

    def turns(beta):
        return head_dim * math.log(original_max_len / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), head_dim - 1)
    span = (high - low) or 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low) / span, 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 mscale ln(factor) + 1 (1 at
    `factor` <= 1).  With `mscale_all_dim` the softmax scale is multiplied by
    its square."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_table(
    head_dim: int,
    max_positions: int,
    theta: float = 10000.0,
    use_scaled_rope: bool = False,
    inv_freq: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Precompute (cos, sin) tables, each [max_positions, head_dim // 2], fp32.

    Computed and returned on host in **numpy** (like the reference's
    host-side precompute, model.py:156-161): bit-stable across backends, and
    safe to memoize — a cached jnp array created inside a jit trace would
    leak a tracer into later traces; a numpy array is a fresh constant in
    every trace.  `inv_freq` replaces the plain theta^(-2i/d) frequencies
    (`yarn_inv_freq`).
    """
    assert head_dim % 2 == 0
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    if use_scaled_rope:
        inv_freq = llama3_scale_inv_freq(inv_freq)
    t = np.arange(max_positions, dtype=np.float64)
    angles = np.outer(t, inv_freq)  # [P, head_dim/2]
    return (
        np.cos(angles).astype(np.float32),
        np.sin(angles).astype(np.float32),
    )


def rope_rows(
    positions: jnp.ndarray, head_dim: int, theta: float = 10000.0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin), each [batch, seq, head_dim // 2] fp32, of `positions`
    [batch, seq], computed on the device: position x inverse frequency in
    float32, then cos / sin, as the published implementations compute them.
    No table: a table is a constant of the program (numpy, see
    `rope_table`), copied into every loop body and branch that rotates — at
    65,536 positions x 64 pairs, 33 MB a copy of generated code held on
    the device by every compiled variant (compiled for a v5e, PR 32).
    `apply_rope_rows` takes them."""
    assert head_dim % 2 == 0
    inv_freq = 1.0 / (
        theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    )
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq, jnp.float32
    )
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray,
) -> jnp.ndarray:
    """Rotate q or k by position-dependent angles.

    Args:
      x: [batch, seq, heads, head_dim] in the half-split feature layout
        (see module docstring — projections are stored pre-permuted).
      cos, sin: [max_positions, head_dim // 2] fp32 tables from `rope_table`.
      positions: [batch, seq] int32 absolute position ids.
    Returns:
      Rotated tensor, same shape/dtype as x.
    """
    return _rotate(x, cos, sin, lambda table: jnp.take(table, positions, axis=0))


def apply_rope_rows(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """`apply_rope` given the tokens' own rows [batch, seq, head_dim // 2]
    (`rope_rows`) in place of the tables and the positions."""
    return _rotate(x, cos, sin, lambda rows: rows)


def _rotate(x, cos, sin, rows) -> jnp.ndarray:
    orig_dtype = x.dtype
    d2 = x.shape[-1] // 2
    # Slice the halves BEFORE the fp32 cast (elementwise-identical to
    # casting first, so numerics are bit-exact): a whole-tensor
    # x.astype(f32) materializes an fp32 copy of q that XLA then layout-
    # copies across the fused-QKV -> attention seam — ~11.6 ms per 16k
    # prefill (xplane).  Sliced converts fuse straight into the rotation
    # multiplies and the seam relayout happens on bf16 (or not at all).
    x1 = x[..., :d2].astype(jnp.float32)  # [B, S, H, D/2] — lane halves
    x2 = x[..., d2:].astype(jnp.float32)
    c = rows(cos)[:, :, None, :]  # [B, S, 1, D/2]
    s = rows(sin)[:, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(orig_dtype)
