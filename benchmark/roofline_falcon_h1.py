"""What a step of the `falcon_h1` block must move and compute, from shapes and
the dispatch records.  Kept with the benchmark, beside `roofline.py` (the
`dense_gqa` block), `roofline_mla_moe.py`, `roofline_afmoe.py` and
`roofline_sambay.py`.

Every count errs LOW, so that a share can pass 100 % only if a time or a `k`
is wrong, never because bytes or operations were counted that did not happen:
the cache by the token and not by the 128-token block the kernel fetches, the
state and the scan's operands for the riding rows only (the program steps
every slot), the embedding a row a token and never the table, the scan's
operands in the activation type and its matmuls one pass each (the program
hands them over in float32 and asks for the highest precision), nothing as
read twice, activations not at all.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

_BYTES = {"bfloat16": 2, "float32": 4}


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of the block's parts (a layer's two norms in `layer`,
    the mixer's small vectors in `mixer`) and the mixer's widths."""
    if cfg.get("reference") != "falcon_h1":
        raise ValueError(f"roofline_falcon_h1 counts the falcon_h1 block, not {cfg.get('reference')!r}")
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, KVH, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    Ds, Hm = cfg["mamba_d_ssm"], cfg["mamba_n_heads"]
    GN = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    conv_dim = Ds + 2 * GN
    attention = D * H * hd + 2 * D * KVH * hd + H * hd * D
    mixer = (D * (Ds + conv_dim + Hm) + Ds * D
             + cfg["mamba_d_conv"] * conv_dim + conv_dim + 3 * Hm + Ds)
    ffn = 3 * D * F
    return {
        "attention": attention, "mixer": mixer, "ffn": ffn,
        "layer": attention + mixer + ffn + 2 * D,
        "head": D * V, "embedding": V * D,
        "conv_dim": conv_dim, "d_ssm": Ds,
    }


def parameters(cfg: Dict[str, Any]) -> int:
    n = sizes(cfg)
    return (cfg["num_hidden_layers"] * n["layer"] + n["head"] + n["embedding"]
            + cfg["hidden_size"])


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """Keys and values every layer holds a token."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"]
            * _BYTES[cfg["torch_dtype"]])


def state_bytes_per_row(cfg: Dict[str, Any]) -> int:
    """A row's recurrent state over all layers: h [Hm, P, N] float32 and the
    last 3 conv inputs a layer."""
    n = sizes(cfg)
    return cfg["num_hidden_layers"] * (
        n["d_ssm"] * cfg["mamba_d_state"] * 4
        + (cfg["mamba_d_conv"] - 1) * n["conv_dim"] * _BYTES[cfg["torch_dtype"]])


def decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float]) -> float:
    """One decode iteration: every layer's weights, the final norm and the
    head once, an embedding row a riding row; each riding row's state read
    and written; its keys and values at its depth."""
    n = sizes(cfg)
    a = _BYTES[cfg["torch_dtype"]]
    rows = len(contexts)
    return ((cfg["num_hidden_layers"] * n["layer"] + n["head"] + cfg["hidden_size"]) * a
            + rows * cfg["hidden_size"] * a
            + 2 * rows * state_bytes_per_row(cfg)
            + kv_bytes_per_token(cfg) * float(sum(contexts)))


def ssd_scan_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """A chunk's scan, ONE mixer, as chunked matmuls of `mamba_chunk_size`
    tokens: C B^T a group inside each chunk (causal half), the weighted
    scores against x a head (causal half), C against the entry state and the
    state's update, each of Hm x P x N multiply-adds a token."""
    Q = min(cfg["mamba_chunk_size"], max(tokens, 1))
    Ds, N, G = cfg["mamba_d_ssm"], cfg["mamba_d_state"], cfg["mamba_n_groups"]
    pairs = tokens * (Q + 1) / 2.0          # (t, s <= t) inside a chunk
    return 2.0 * (pairs * G * N + pairs * Ds + 2.0 * tokens * Ds * N)


def ssd_scan_bytes(cfg: Dict[str, Any], tokens: int) -> float:
    """A chunk's scan, ONE mixer: x in and y out a token in the activation
    type, B, C and dt, the state once in and once out."""
    a = _BYTES[cfg["torch_dtype"]]
    Ds, N = cfg["mamba_d_ssm"], cfg["mamba_d_state"]
    GN = cfg["mamba_n_groups"] * N
    return tokens * (2 * Ds + 2 * GN + cfg["mamba_n_heads"]) * a + 2.0 * Ds * N * 4


def chunk_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """A prompt chunk of `tokens`: twice the layer parameters a token passes
    through, attention of the chunk on itself only, the scans.  The head runs
    for one token."""
    n = sizes(cfg)
    H, hd, L = cfg["num_attention_heads"], cfg["head_dim"], cfg["num_hidden_layers"]
    causal = tokens * (tokens + 1) / 2.0
    attention = L * H * hd * 4.0 * causal
    return (2.0 * L * n["layer"] * tokens + attention
            + L * ssd_scan_flops(cfg, tokens) + 2.0 * n["head"])
