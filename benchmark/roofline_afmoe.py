"""What a step of the `afmoe` block must move and compute, from shapes, the
dispatch records and the router's counters.  Kept with the benchmark, beside
`roofline.py` (the `dense_gqa` block) and `roofline_mla_moe.py`.

Every count errs LOW, so that a share can pass 100 % only if a time or a `k`
is wrong, never because bytes or operations were counted that did not happen:
a window layer's attention is counted AT the window (a row's context, or a
chunk's own tokens, capped at `sliding_window`), experts by the router's
touched counter, nothing is counted as read twice, and the cache is counted by
the token, not by the 512-token block the kernel fetches.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

_BYTES = {"bfloat16": 2, "float32": 4}


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of the block's parts (norms left out)."""
    if cfg.get("reference") != "afmoe":
        raise ValueError(f"roofline_afmoe counts the afmoe block, not {cfg.get('reference')!r}")
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KVH, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    Fe = cfg["moe_intermediate_size"]
    return {
        # q, k, v, the output gate, o
        "attention": D * H * hd + 2 * D * KVH * hd + D * H * hd + H * hd * D,
        "dense_ffn": 3 * D * cfg["intermediate_size"],
        "shared": 3 * D * Fe * cfg["num_shared_experts"],
        "router": D * cfg["num_experts"],
        "expert": 3 * D * Fe,
        "head": D * V,
    }


def layers(cfg: Dict[str, Any]):
    """(dense, expert) layers; (window, full) layers."""
    dense = cfg["num_dense_layers"]
    window = sum(1 for t in cfg["layer_types"] if t == "sliding_attention")
    return (dense, cfg["num_hidden_layers"] - dense), (window, len(cfg["layer_types"]) - window)


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """Keys and values a decode step must read, a token a layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * _BYTES[cfg["torch_dtype"]]


def cache_tokens(cfg: Dict[str, Any], contexts: Sequence[float]):
    """(window, full): cached tokens ONE layer of each kind must read for the
    rows of a decode iteration: a window layer the last `sliding_window` of a
    row's context, a full layer all of it."""
    W = cfg["sliding_window"]
    return float(sum(min(c, W) for c in contexts)), float(sum(contexts))


def decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float], experts_touched: float) -> float:
    """One decode iteration: attention, shared, router, dense-layer and head
    weights once, the experts the router's counters say were touched (summed
    over the expert layers), and each riding row's keys and values, at the
    window in the window layers."""
    n = sizes(cfg)
    (Ld, Lm), (Lw, Lf) = layers(cfg)
    weights = (
        (Ld + Lm) * n["attention"] + Ld * n["dense_ffn"]
        + Lm * (n["shared"] + n["router"]) + n["head"] + experts_touched * n["expert"]
    )
    win, full = cache_tokens(cfg, contexts)
    return weights * _BYTES[cfg["torch_dtype"]] + kv_bytes_per_token(cfg) * (Lw * win + Lf * full)


def chunk_attention_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """Scores and values of a prompt chunk on ITSELF, ONE layer: the causal
    half, each query's keys capped at the window; as if no context lay before
    the chunk, in either kind of layer."""
    H, hd, W = cfg["num_attention_heads"], cfg["head_dim"], cfg["sliding_window"]
    pairs = sum(min(i + 1, W) for i in range(tokens))
    return 4.0 * H * hd * pairs


def chunk_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """A prompt chunk of `tokens`: twice the parameters a token passes through,
    and attention of the chunk on itself only.  The head runs for one token."""
    n = sizes(cfg)
    (Ld, Lm), _ = layers(cfg)
    per_token = (
        (Ld + Lm) * n["attention"] + Ld * n["dense_ffn"]
        + Lm * (n["shared"] + n["router"] + cfg["num_experts_per_tok"] * n["expert"])
    )
    return 2.0 * per_token * tokens + (Ld + Lm) * chunk_attention_flops(cfg, tokens) + 2.0 * n["head"]


def chunk_experts_touched_max(cfg: Dict[str, Any], tokens: int) -> int:
    """The most experts a prompt chunk can have touched, over the expert
    layers: what is taken OFF a dispatch's counter to leave a lower bound of
    what its decode iterations touched."""
    (_, Lm), _ = layers(cfg)
    return Lm * min(cfg["num_experts"], tokens * cfg["num_experts_per_tok"])


def window_chunk_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """What the window layers' attention sub-blocks compute for a prompt chunk:
    their projections for every token, attention of the chunk on itself."""
    _, (Lw, _) = layers(cfg)
    return Lw * (2.0 * sizes(cfg)["attention"] * tokens + chunk_attention_flops(cfg, tokens))


def window_decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float]) -> float:
    """What the window layers' attention sub-blocks read in one decode
    iteration: their projections once, each row's keys and values at the
    window."""
    _, (Lw, _) = layers(cfg)
    win, _ = cache_tokens(cfg, contexts)
    return Lw * (sizes(cfg)["attention"] * _BYTES[cfg["torch_dtype"]] + kv_bytes_per_token(cfg) * win)
