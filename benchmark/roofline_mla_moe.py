"""What a step of the `mla_moe` block must move and compute, from shapes, the
dispatch records and the router's counters.  Kept with the benchmark, beside
`roofline.py` (which counts the `dense_gqa` block and raises for this one).

Every count errs LOW, so that a share can pass 100 % only if a time or a `k`
is wrong, never because bytes or operations were counted that did not happen.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

_BYTES = {"bfloat16": 2, "float32": 4}


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of the block's parts (norms left out)."""
    if cfg.get("reference") != "mla_moe":
        raise ValueError(f"roofline_mla_moe counts the mla_moe block, not {cfg.get('reference')!r}")
    D, H, V = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    Fe = cfg["moe_intermediate_size"]
    return {
        "attention": D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D,
        "dense_ffn": 3 * D * cfg["intermediate_size"],
        "shared": 3 * D * Fe * cfg["n_shared_experts"],
        "router": D * cfg["n_routed_experts"],
        "expert": 3 * D * Fe,
        "head": D * V,
    }


def layers(cfg: Dict[str, Any]):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def latent_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """The cached latent a decode step must read, a token a layer: the values,
    not the lane padding the pool stores them in."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * _BYTES[cfg["torch_dtype"]]


def decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float], experts_touched: float) -> float:
    """One decode iteration: attention, shared, router, dense-layer and head
    weights once, the experts the router's counters say were touched (summed
    over the expert layers), and the latent of each live row's context."""
    n = sizes(cfg)
    Ld, Lm = layers(cfg)
    weights = (
        (Ld + Lm) * n["attention"] + Ld * n["dense_ffn"]
        + Lm * (n["shared"] + n["router"]) + n["head"] + experts_touched * n["expert"]
    )
    return (weights * _BYTES[cfg["torch_dtype"]]
            + (Ld + Lm) * latent_bytes_per_token(cfg) * float(sum(contexts)))


def chunk_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """A prompt chunk of `tokens`: twice the parameters a token passes through,
    and attention of the chunk on ITSELF only (causal half), as if it had no
    context before it and no latent to decompress.  The head runs for one token."""
    n = sizes(cfg)
    Ld, Lm = layers(cfg)
    per_token = (
        (Ld + Lm) * n["attention"] + Ld * n["dense_ffn"]
        + Lm * (n["shared"] + n["router"] + cfg["num_experts_per_tok"] * n["expert"])
    )
    H = cfg["num_attention_heads"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    attn = (Ld + Lm) * 2 * H * width * tokens * (tokens + 1) / 2
    return 2.0 * per_token * tokens + attn + 2.0 * n["head"]


def chunk_experts_touched_max(cfg: Dict[str, Any], tokens: int) -> int:
    """The most experts a prompt chunk can have touched, over the expert
    layers: what is taken OFF a dispatch's counter to leave a lower bound of
    what its decode iterations touched."""
    _, Lm = layers(cfg)
    return Lm * min(cfg["n_routed_experts"], tokens * cfg["num_experts_per_tok"])
