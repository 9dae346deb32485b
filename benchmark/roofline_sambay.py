"""What a step of the `sambay` block must move and compute, from shapes and
the dispatch records.  Kept with the benchmark, beside `roofline.py` (the
`dense_gqa` block), `roofline_mla_moe.py` and `roofline_afmoe.py`.

Every count errs LOW, so that a share can pass 100 % only if a time or a `k`
is wrong, never because bytes or operations were counted that did not happen:
attention is counted at the published 64-wide heads (the program's head-pair
form computes twice the score product), a window layer's at the window, the
cache by the token and not by the 128-token block the kernel fetches, the
state and the scan's operands for the riding rows only (the program steps
every slot), the scan's `dt` in the activation type (the program hands the
kernel float32), nothing as read twice, activations not at all.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

_BYTES = {"bfloat16": 2, "float32": 4}
_N, _CONV, _EXPAND = 16, 4, 2       # the configuration file's `assumed` sizes


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of the block's parts (norms and biases left out) and
    its layer counts."""
    if cfg.get("reference") != "sambay":
        raise ValueError(f"roofline_sambay counts the sambay block, not {cfg.get('reference')!r}")
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = D // H
    Di, R = _EXPAND * D, -(-D // 16)
    quarter = cfg["num_hidden_layers"] // 4
    return {
        "ffn": 3 * D * F,
        "mamba": D * 2 * Di + _CONV * Di + Di * (R + 2 * _N) + R * Di + Di * _N + Di * D,
        "attention": D * H * hd + 2 * D * KVH * hd + H * hd * D,
        "cross": 2 * D * H * hd,
        "gmu": 2 * D * Di,
        "head": D * V,
        "n_mamba": quarter + 1, "n_window": quarter, "n_cross": quarter - 1,
        "d_inner": Di,
    }


def parameters(cfg: Dict[str, Any]) -> int:
    n = sizes(cfg)
    return (cfg["num_hidden_layers"] * n["ffn"] + n["n_mamba"] * n["mamba"]
            + (n["n_window"] + 1) * n["attention"] + n["n_cross"] * (n["cross"] + n["gmu"])
            + n["head"])


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """Keys and values ONE owning layer holds a token."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * hd * _BYTES[cfg["torch_dtype"]]


def state_bytes_per_row(cfg: Dict[str, Any]) -> int:
    """A row's recurrent state, ONE mixer: h float32 and the conv inputs."""
    Di = sizes(cfg)["d_inner"]
    return Di * _N * 4 + (_CONV - 1) * Di * _BYTES[cfg["torch_dtype"]]


def decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float]) -> float:
    """One decode iteration: the weights once, the full layer's keys and values
    once for itself and once a cross layer at each riding row's depth, the
    window layers' at the window, each riding row's state read and written."""
    n = sizes(cfg)
    W = cfg["sliding_window"]
    full = float(sum(contexts))
    win = float(sum(min(c, W) for c in contexts))
    return (parameters(cfg) * _BYTES[cfg["torch_dtype"]]
            + kv_bytes_per_token(cfg) * ((1 + n["n_cross"]) * full + n["n_window"] * win)
            + 2 * n["n_mamba"] * state_bytes_per_row(cfg) * len(contexts))


def chunk_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """A prompt chunk of `tokens`: twice the parameters a token passes through,
    attention of the chunk on itself only (64-wide heads; the window layers at
    the window), the recurrence's multiply-adds.  The head runs for one token."""
    n = sizes(cfg)
    H = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // H
    W = cfg["sliding_window"]
    causal = sum(i + 1 for i in range(tokens))
    windowed = sum(min(i + 1, W) for i in range(tokens))
    # per pair of query heads: two score products of hd and two value products of 2hd
    attention = H * hd * 3.0 * ((1 + n["n_cross"]) * causal + n["n_window"] * windowed)
    scan = n["n_mamba"] * tokens * n["d_inner"] * _N * 6.0
    return 2.0 * (parameters(cfg) - n["head"]) * tokens + attention + scan + 2.0 * n["head"]


def scan_step_bytes(cfg: Dict[str, Any]) -> float:
    """One row's recurrence step, ONE mixer: the state read and written, and
    `c`, `dt`, `B`, `C` in, `y` out."""
    Di = sizes(cfg)["d_inner"]
    a = _BYTES[cfg["torch_dtype"]]
    return 2.0 * Di * _N * 4 + Di * 3 * a + 2 * _N * a


def scan_chunk_bytes(cfg: Dict[str, Any], tokens: int) -> float:
    """A chunk's scan, ONE mixer: its inputs and its output a token, the
    state once in and once out."""
    Di = sizes(cfg)["d_inner"]
    a = _BYTES[cfg["torch_dtype"]]
    return tokens * (Di * 3 * a + 2 * _N * a) + 2.0 * Di * _N * 4
