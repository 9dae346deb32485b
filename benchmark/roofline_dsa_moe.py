"""What a step of the `dsa_moe` block must move and compute, from shapes, the
dispatch records and the router's counters.  Kept with the benchmark, beside
`roofline.py` (the `dense_gqa` block) and the other blocks' files.

Every count errs LOW, so that a share can pass 100 % only if a time or a `k`
is wrong, never because bytes or operations were counted that did not happen.
A decode row reads the index keys of its WHOLE context (64 values a token a
layer: the selection has to rank them all) and the keys and values of the
`topk` slots it chose — never the dense context; a prompt chunk scores and
attends only itself, each query's attended keys capped at `topk`, as if no
context lay before it; experts by the router's touched counter; nothing is
counted as read twice, and the cache by the token, not by the block.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

_BYTES = {"bfloat16": 2, "float32": 4}


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of the block's parts (norms left out)."""
    if cfg.get("reference") != "dsa_moe":
        raise ValueError(f"roofline_dsa_moe counts the dsa_moe block, not {cfg.get('reference')!r}")
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KVH, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sa = cfg["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {
        "attention": D * H * hd + 2 * D * KVH * hd + H * hd * D,   # q, k, v, o
        "indexer": D * Hi * di + D * di + D * Hi,                  # index q, the one key, head weights
        "router": D * cfg["num_experts"],
        "expert": 3 * D * cfg["moe_intermediate_size"],
        "head": D * V,
    }


def index_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """The index key a decode row must read, a token of context a layer."""
    return cfg["sa_config"]["indexer_head_dim"] * _BYTES[cfg["torch_dtype"]]


def kv_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """Key and value of one chosen slot, a layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * _BYTES[cfg["torch_dtype"]]


def chosen_slots(cfg: Dict[str, Any], contexts: Sequence[float]) -> float:
    """Slots ONE layer's selection attends for the rows of an iteration."""
    topk = cfg["sa_config"]["topk"]
    return float(sum(min(c, topk) for c in contexts))


def index_decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float]) -> float:
    """What the indexer and the selection read in one decode iteration: the
    index projections once a layer, every row's index keys for its whole
    context."""
    L, b = cfg["num_hidden_layers"], _BYTES[cfg["torch_dtype"]]
    return L * (sizes(cfg)["indexer"] * b + index_bytes_per_token(cfg) * float(sum(contexts)))


def sparse_decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float]) -> float:
    """What attention over the chosen reads in one decode iteration: the
    chosen slots' keys and values, and nothing else (the q/k/v/o projections
    run under a scope of their own and are counted with the whole step)."""
    return cfg["num_hidden_layers"] * kv_bytes_per_slot(cfg) * chosen_slots(cfg, contexts)


def decode_iter_bytes(cfg: Dict[str, Any], contexts: Sequence[float], experts_touched: float) -> float:
    """One decode iteration: attention, indexer, router and head weights
    once, the experts the router's counters say were touched (summed over
    the layers), index keys for the whole context and the chosen K/V rows."""
    n = sizes(cfg)
    b = _BYTES[cfg["torch_dtype"]]
    return (
        index_decode_iter_bytes(cfg, contexts) + sparse_decode_iter_bytes(cfg, contexts)
        + (cfg["num_hidden_layers"] * (n["attention"] + n["router"]) + n["head"]
           + experts_touched * n["expert"]) * b
    )


def chunk_index_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """Index scores of a prompt chunk on ITSELF, ONE layer: the causal half,
    every index head against the one key."""
    sa = cfg["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * tokens * (tokens + 1) / 2


def chunk_attention_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """Scores and values of a prompt chunk on ITSELF, ONE layer: the causal
    half, each query's keys capped at `topk`."""
    H, hd, topk = cfg["num_attention_heads"], cfg["head_dim"], cfg["sa_config"]["topk"]
    full = min(tokens, topk)
    pairs = full * (full + 1) / 2 + (tokens - full) * topk
    return 4.0 * H * hd * pairs


def index_chunk_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """What the indexer computes for a prompt chunk: its projections for
    every token, scores of the chunk on itself."""
    return cfg["num_hidden_layers"] * (
        2.0 * sizes(cfg)["indexer"] * tokens + chunk_index_flops(cfg, tokens))


def sparse_chunk_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """What attention computes for a prompt chunk: the chunk on itself under
    the selection's cap (its projections are counted with the whole step)."""
    return cfg["num_hidden_layers"] * chunk_attention_flops(cfg, tokens)


def chunk_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """A prompt chunk of `tokens`: twice the parameters a token passes
    through, index scores and attention of the chunk on itself only.  The
    head runs for one token."""
    n = sizes(cfg)
    passed = cfg["num_hidden_layers"] * (
        n["attention"] + n["router"] + cfg["num_experts_per_tok"] * n["expert"])
    return (index_chunk_flops(cfg, tokens) + sparse_chunk_flops(cfg, tokens)
            + 2.0 * passed * tokens + 2.0 * n["head"])


def chunk_experts_touched_max(cfg: Dict[str, Any], tokens: int) -> int:
    """The most experts a prompt chunk can have touched, over the layers:
    what is taken OFF a dispatch's counter to leave a lower bound of what its
    decode iterations touched."""
    return cfg["num_hidden_layers"] * min(cfg["num_experts"], tokens * cfg["num_experts_per_tok"])
