"""Weight-only int8 quantization for serving.

The reference has no quantization story (it serves fp32/bf16 weights,
``/root/reference/jax_llama/model.py`` throughout).  On TPU, autoregressive
decode is HBM-bandwidth-bound: every step streams the full weight set
through the MXU, so weight bytes ~= step time.  Storing projections as int8
(+ per-output-channel fp32 scales) halves that traffic vs bf16 and roughly
doubles steady-state decode throughput, at <0.5% typical quality cost.

Scheme: symmetric per-output-channel.  For a weight ``W`` contracted over
its input dims, ``scale[c] = max|W[:, c]| / 127`` and ``Wq = round(W /
scale)``.  The matmul computes ``(x @ Wq) * scale`` — exact algebra, because
the scale is constant along every contracted dim — so the int8→bf16 convert
is the only op XLA must fuse into the dot's operand read, and the fp32
rescale touches only the (small) output.

A ``QuantizedTensor`` is a pytree node, so quantized param trees flow
through ``jax.jit`` / ``lax.scan`` / Orbax / ``shard_map`` untouched; the
scale leaf keeps the weight's rank (contracted dims squeezed to 1) so a
stacked-layer scan can slice both leaves along the leading L axis.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["q", "scale"],
    meta_fields=[],
)
@dataclasses.dataclass
class QuantizedTensor:
    """int8 weight + fp32 per-output-channel scale.

    q:     int8, original weight shape.
    scale: fp32, same rank; contracted (input) dims are size 1.
    """

    q: jnp.ndarray
    scale: jnp.ndarray

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim

    def dequantize(self, dtype=jnp.float32) -> jnp.ndarray:
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def _quantize_impl(w: jnp.ndarray, contract_axes: Tuple[int, ...]) -> QuantizedTensor:
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=contract_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q=q, scale=scale)


_quantize_jit = jax.jit(_quantize_impl, static_argnames=("contract_axes",))
_quantize_jit_donate = jax.jit(
    _quantize_impl, static_argnames=("contract_axes",), donate_argnums=(0,)
)


def quantize(
    w: jnp.ndarray, contract_axes: Tuple[int, ...], *, donate: bool = False
) -> QuantizedTensor:
    """Symmetric int8 quantization, per-channel over non-contracted dims.

    Runs under jit so XLA streams abs/max/round/clip into the int8 output
    without materializing full-size fp32 temporaries — eager execution
    would hold ~3x the weight in fp32 at peak, which OOMs a 70B
    quantize-on-load.  ``donate=True`` additionally releases the source
    buffer (the original array becomes invalid) so peak memory during a
    quantize-on-load never holds both precisions of the full model.
    """
    fn = _quantize_jit_donate if donate else _quantize_jit
    return fn(jnp.asarray(w), tuple(contract_axes))


def matmul(
    x: jnp.ndarray,
    w: Any,
    eq: str,
    dtype: Optional[jnp.dtype] = None,
    preferred_element_type: Optional[jnp.dtype] = None,
) -> jnp.ndarray:
    """``einsum(eq, x, w)`` that transparently handles QuantizedTensor.

    The einsum must list the weight's non-contracted dims in the output in
    the same relative order they hold in the weight (true for every
    projection in this model), so the scale broadcasts over the leading
    batch/seq dims of the output.

    Profile-attribution note: the model's hot path
    (``models.llama.qeinsum``) calls this only for QuantizedTensor
    weights and runs the plain-array einsum in its own frame — so a
    ``quant.py`` bucket in an xplane source breakdown (a per-source
    breakdown of device op time) now measures real int8 dequant work, not the
    bf16 weight stream it used to swallow.
    """
    dtype = dtype or x.dtype
    if isinstance(w, QuantizedTensor):
        y = jnp.einsum(
            eq, x, w.q.astype(dtype),
            preferred_element_type=preferred_element_type or jnp.float32,
        )
        out_scale = w.scale.reshape(
            tuple(d for d in w.scale.shape if d != 1) or (1,)
        )
        y = y.astype(jnp.float32) * out_scale
        return y.astype(preferred_element_type or dtype)
    y = jnp.einsum(
        eq, x, w.astype(dtype),
        preferred_element_type=preferred_element_type,
    )
    return y if preferred_element_type else y.astype(dtype)


# Contraction axes of each quantizable projection, in the *per-layer* shape
# (the stacked tree adds a leading L axis — axes shift by one):
#   qkv [KVH, G+2, D, hd] contract D; o [H, hd, D] contract (H, hd);
#   gate_up [2, D, F] contract D; down [F, D] contract F; lm_head [D, V]
#   contract D.
_LAYER_CONTRACT = {
    "qkv": (2,), "o": (0, 1),
    "gate_up": (1,), "down": (0,),
}


def quantize_params(params: Any, *, donate: bool = False) -> Any:
    """Quantize every projection matrix in a model param tree to int8.

    Norm scales and the token embedding stay in their original dtype (the
    embedding is a gather, not a matmul; when it is tied as the LM head the
    tied path stays unquantized too).  ``donate=True`` frees each source
    weight as it is quantized — use for quantize-on-load, where the full-
    precision tree is not needed afterwards.
    """
    out = dict(params)
    lp = dict(params["layers"])
    for name, axes in _LAYER_CONTRACT.items():
        stacked_axes = tuple(a + 1 for a in axes)  # leading L axis
        lp[name] = quantize(lp[name], stacked_axes, donate=donate)
    out["layers"] = lp
    if "lm_head" in params:
        out["lm_head"] = quantize(params["lm_head"], (0,), donate=donate)
    return out


def is_quantized(params: Any) -> bool:
    return any(
        isinstance(l, QuantizedTensor)
        for l in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        )
    )
