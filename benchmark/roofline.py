"""What a kernel or step must move and compute, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change it.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

_BYTES = {"bfloat16": 2, "float32": 4}


def layer_params(cfg: Dict[str, Any]) -> int:
    """Parameters of one layer of the dense GQA + SwiGLU block, and of no
    other: a block with its own reference brings its own count."""
    if cfg.get("reference") != "dense_gqa":
        raise ValueError(
            f"roofline.layer_params counts the dense_gqa block, not {cfg.get('reference')!r}"
        )
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KVH, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return D * (H + 2 * KVH) * hd + H * hd * D + 3 * D * F + 2 * D


def decode_weight_bytes(cfg: Dict[str, Any]) -> int:
    """Weights one decode iteration must read: every layer once, the final
    norm and the output head.  The embedding table is read a row per
    sequence, which is nothing beside them, unless it IS the head."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    n = cfg["num_hidden_layers"] * layer_params(cfg) + D + D * V
    return n * _BYTES[cfg["torch_dtype"]]


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"]
            * _BYTES[cfg["torch_dtype"]])


def decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float]) -> float:
    """Bytes one decode iteration must read: the weights once, and the keys
    and values of each live row's context."""
    return decode_weight_bytes(cfg) + kv_bytes_per_token(cfg) * float(sum(contexts))


def decode_iter_flops(cfg: Dict[str, Any], contexts: Sequence[float]) -> float:
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    dense = 2 * (cfg["num_hidden_layers"] * (layer_params(cfg) - 2 * D) + D * V) * len(contexts)
    attn = 4 * cfg["num_hidden_layers"] * H * hd * float(sum(contexts))
    return dense + attn


def least_seconds(flops: float, bytes_: float, peaks: Dict[str, float], chips: int):
    """(seconds, which bound): the larger of operations over peak FLOP/s and
    bytes over peak bytes/s, with the work spread evenly over the chips."""
    t_c = flops / (peaks["bf16_flops_per_s"] * chips)
    t_m = bytes_ / (peaks["hbm_bytes_per_s"] * chips)
    return (t_c, "compute") if t_c > t_m else (t_m, "memory")
