"""What a step of the `mhc_mla_moe` block must move and compute, from shapes,
the dispatch records and the router's counters: the latent-attention block with
a low-rank query whose residual is `hc_mult` streams (mHC units around every
attention and FFN).  Kept with the benchmark, beside `roofline_mla_moe.py`
(which counts the block with the plain residual and raises for this one).

Every count errs LOW, so that a share can pass 100 % only if a time or a `k`
is wrong, never because bytes or operations were counted that did not happen.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

# the same block's counts that the streams do not change
from .roofline_mla_moe import (  # noqa: F401
    _BYTES, chunk_experts_touched_max, latent_bytes_per_token, layers,
)


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of the block's parts (norms left out)."""
    if cfg.get("reference") != "mhc_mla_moe":
        raise ValueError(
            f"roofline_mhc_mla_moe counts the mhc_mla_moe block, not {cfg.get('reference')!r}")
    D, H, V = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rq, Fe, n = cfg["q_lora_rank"], cfg["moe_intermediate_size"], cfg["hc_mult"]
    return {
        "attention": (D * rq + rq * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv)
                      + H * dv * D),
        "dense_ffn": 3 * D * cfg["intermediate_size"],
        "shared": 3 * D * Fe * cfg["n_shared_experts"],
        "router": D * cfg["n_routed_experts"],
        "expert": 3 * D * Fe,
        "head": D * V,
        # one unit's phi [nC, n*n + 2n]; float32 (b and alpha left out)
        "hc_unit": n * D * (n * n + 2 * n),
    }


def units(cfg: Dict[str, Any]) -> int:
    """mHC units a token passes: two a layer."""
    return 2 * cfg["num_hidden_layers"]


def hc_mix_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """The least one mHC unit moves for one token: the n-stream residual read
    once and written once, the inner function's input written and its output
    read — (2n + 2) C values in the activation type.  A unit that reads the
    stream once more for its norm, its projection or its second mix, or keeps
    a float32 copy, moves more."""
    n, C = cfg["hc_mult"], cfg["hidden_size"]
    return (2 * n + 2) * C * _BYTES[cfg["torch_dtype"]]


def hc_bytes(cfg: Dict[str, Any], tokens: float, passes: float) -> float:
    """All units over `tokens` tokens in `passes` passes over the weights: the
    streams' least bytes, and phi (float32) once a unit a pass."""
    return units(cfg) * (hc_mix_bytes_per_token(cfg) * float(tokens)
                         + sizes(cfg)["hc_unit"] * 4 * float(passes))


def hc_carry_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """What the streams must cross the chip's memory for, a token a LAYER, where
    a layer's two units keep them on the chip between them: the layer scan's
    carry read once and written once, 2 n C values.  Measured (PR 51, v5e): XLA
    places a 2048-token chunk's streams (58 MB) in the chip's 128 MiB of vector
    memory, and the operations under `hc.*` of a full chunk take about what
    `hc_mix_bytes_per_token` gives over the HBM bandwidth — a share against
    that count reads ~100 % for a window of full chunks through no fault of
    counting, so the share is taken against this lower count."""
    return 2 * cfg["hc_mult"] * cfg["hidden_size"] * _BYTES[cfg["torch_dtype"]]


def hc_floor_bytes(cfg: Dict[str, Any], tokens: float, passes: float) -> float:
    """The carry's bytes of every layer over `tokens` tokens, and each unit's
    phi (float32) once a pass."""
    return (cfg["num_hidden_layers"] * hc_carry_bytes_per_token(cfg) * float(tokens)
            + units(cfg) * sizes(cfg)["hc_unit"] * 4 * float(passes))


def decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float], experts_touched: float) -> float:
    """One decode iteration: attention (q_a, q_b, kv_a, kv_b, o), shared,
    router, dense-layer and head weights once, the experts the router's counters
    say were touched (summed over the expert layers), the latent of each live
    row's context, and the units' parameters and their rows' streams."""
    n = sizes(cfg)
    Ld, Lm = layers(cfg)
    weights = (
        (Ld + Lm) * n["attention"] + Ld * n["dense_ffn"]
        + Lm * (n["shared"] + n["router"]) + n["head"] + experts_touched * n["expert"]
    )
    return (weights * _BYTES[cfg["torch_dtype"]]
            + (Ld + Lm) * latent_bytes_per_token(cfg) * float(sum(contexts))
            + hc_bytes(cfg, len(contexts), 1))


def chunk_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """A prompt chunk of `tokens`: twice the parameters a token passes through
    (the units' projections too), and attention of the chunk on ITSELF only
    (causal half), as if it had no context before it and no latent to
    decompress.  The mixes are counted as bytes, not here.  The head runs for
    one token."""
    n = sizes(cfg)
    Ld, Lm = layers(cfg)
    per_token = (
        (Ld + Lm) * n["attention"] + Ld * n["dense_ffn"]
        + Lm * (n["shared"] + n["router"] + cfg["num_experts_per_tok"] * n["expert"])
        + units(cfg) * n["hc_unit"]
    )
    H = cfg["num_attention_heads"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    attn = (Ld + Lm) * 2 * H * width * tokens * (tokens + 1) / 2
    return 2.0 * per_token * tokens + attn + 2.0 * n["head"]
