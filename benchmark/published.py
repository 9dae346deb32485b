"""A model's published `config.json` keys -> the program's configuration.

This function is the program's to own: which published keys it understands,
and which block and weights they give, is the program's decision, and a new
block brings new keys with it.  `system.load_config` calls
`jax_llama_tpu.config.from_published` where the program has one and this copy
only where it has none (it has none yet: a `benchmark` PR may not add program
code; PERF.md section 7).  Whoever maps the keys, the guard against a wrong
map is the plain reference, which reads the configuration FILE and never the
object made here.

Strict: a key this map does not know is refused by name, never dropped.  A
file that carries an expert count, a latent rank or a window would otherwise
be served as the dense block of the same hidden size, under the model's name.
"""

from __future__ import annotations

from typing import Any, Dict

# published key -> configuration field
_FIELDS = {
    "hidden_size": "dim", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "intermediate_size", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_word_embeddings",
}
_DTYPES = ("bfloat16", "float32")
# checked or accepted below, mapped to no field of their own
_OTHER = ("head_dim", "torch_dtype", "sliding_window", "max_position_embeddings")


def from_published(raw: Dict[str, Any], *, max_seq_len: int, attn_impl: str):
    """The `LLaMAConfig` of published keys `raw`, or `ValueError` naming the
    key that stands in the way.  `max_position_embeddings` is accepted and
    unused: a server serves at its own `max_seq_len`."""
    from jax_llama_tpu.config import LLaMAConfig

    unknown = sorted(set(raw) - set(_FIELDS) - set(_OTHER))
    if unknown:
        raise ValueError(f"the program understands no published key {', '.join(map(repr, unknown))}")
    missing = sorted(k for k in (*_FIELDS, "torch_dtype") if k not in raw)
    if missing:
        raise ValueError(f"published key {missing[0]!r} is missing")
    if raw.get("sliding_window") is not None:
        raise ValueError("sliding_window: sliding-window attention is not in the program")
    heads, hidden = raw["num_attention_heads"], raw["hidden_size"]
    if raw.get("head_dim", hidden // heads) * heads != hidden:
        raise ValueError("head_dim * heads != hidden_size; the program has no separate head size")
    if raw["torch_dtype"] not in _DTYPES:
        raise ValueError(f"torch_dtype {raw['torch_dtype']!r} is not one the program serves in")
    return LLaMAConfig(
        **{ours: raw[theirs] for theirs, ours in _FIELDS.items()},
        dtype=raw["torch_dtype"], param_dtype=raw["torch_dtype"],
        max_seq_len=max_seq_len, attn_impl=attn_impl,
    )
