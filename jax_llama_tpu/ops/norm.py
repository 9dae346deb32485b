"""RMSNorm and LayerNorm with an fp32 accumulation island.

Capability parity with the reference RMSNorm (``/root/reference/jax_llama/
model.py:28-48``): y = x * rsqrt(mean(x^2) + eps) * scale.  TPU numerics
policy: the mean/rsqrt runs in float32 regardless of the activation dtype
(bf16 squaring loses too much precision), and the result is cast back to the
input dtype after the scale multiply.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Root-mean-square layer norm over the last axis.

    Args:
      x: [..., dim] activations, any float dtype.
      scale: [dim] learned gain (stored dtype preserved).
      eps: variance epsilon.
    """
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * lax.rsqrt(ms + eps)
    out = normed * scale.astype(jnp.float32)
    return out.astype(orig_dtype)


def layer_norm(x, w, b, eps):
    """LayerNorm over the last axis with weight and bias, a float32 island."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    out = xc * lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)
    return out.astype(x.dtype)
