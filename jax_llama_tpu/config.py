"""Model configuration for the TPU-native LLaMA framework.

Plain frozen dataclass — no HuggingFace ``PretrainedConfig`` baggage.  Covers
the capability surface of the reference config (``/root/reference/jax_llama/
config.py:26-116``: vocab/hidden/layers/heads/GQA/rope_theta/max-seq/eps/
tying) plus the SwiGLU intermediate-size derivation rule the reference keeps
in its converter (``/root/reference/jax_llama/convert_weights.py:36-39``),
which belongs with the config.

TPU-first additions: explicit ``dtype``/``param_dtype`` policy (bf16 compute,
fp32 islands for norm/softmax/logits), ``scan_layers`` (lax.scan over a
stacked layer pytree instead of a Python-unrolled stack, keeping 80-layer
compile times flat), ``remat`` policy, and ``attn_impl`` selecting the XLA
reference attention or the Pallas flash kernel.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp


def swiglu_hidden_size(
    dim: int,
    multiple_of: int = 256,
    ffn_dim_multiplier: Optional[float] = None,
) -> int:
    """Meta's SwiGLU FFN sizing rule.

    Start from 4*dim, take 2/3 of it (SwiGLU has 3 matrices instead of 2),
    optionally scale (Llama-3 uses 1.3), and round up to ``multiple_of``.
    """
    hidden = int(2 * (4 * dim) / 3)
    if ffn_dim_multiplier is not None:
        hidden = int(ffn_dim_multiplier * hidden)
    return multiple_of * math.ceil(hidden / multiple_of)


@dataclasses.dataclass(frozen=True)
class LLaMAConfig:
    """Architecture + numerics configuration for a LLaMA-family model."""

    vocab_size: int = 32000
    dim: int = 4096                       # hidden size
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: Optional[int] = None      # None -> n_heads (no GQA)
    head_size: Optional[int] = None       # None -> dim // n_heads; a head
                                          #   size of its own beside the
                                          #   hidden size (q/k/v project
                                          #   D -> H*head_size, o back)
    intermediate_size: Optional[int] = None  # None -> swiglu_hidden_size(...)
    multiple_of: int = 256
    ffn_dim_multiplier: Optional[float] = None
    max_seq_len: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    use_scaled_rope: bool = False         # Llama-3.1 context-extension RoPE
    tie_word_embeddings: bool = False

    # --- training regularization (reference config.py:85-87 capability).
    # Applied only when a dropout_rng is passed to forward/train_step;
    # inference paths stay deterministic regardless.
    resid_pdrop: float = 0.0              # after attention out and MLP out
    embd_pdrop: float = 0.0               # on token embeddings
    attn_pdrop: float = 0.0               # on attention probabilities
                                          #   (xla attention path only)

    # --- numerics / execution policy (TPU-first) ---
    dtype: str = "bfloat16"               # activation/compute dtype
    param_dtype: str = "float32"          # parameter storage dtype
    scan_layers: bool = True              # lax.scan over stacked layers
    scan_unroll: int = 1                  # lax.scan unroll factor (layers
                                          # per scan iteration; lets XLA
                                          # pipeline DMAs across layers)
    remat: bool = False                   # jax.checkpoint each block
    remat_policy: str = "dots"            # "dots": save matmul outputs,
                                          #   recompute elementwise only
                                          #   (+13% train step vs "full"
                                          #   on chip at 1B/bf16/S=2048);
                                          # "full": recompute everything
                                          #   (minimum memory)
    attn_impl: str = "xla"                # "xla" | "flash" (Pallas) | "ring"
                                          #   (seq-parallel ring attention) |
                                          #   "auto" (flash for prefill /
                                          #   long blocks, xla append-free
                                          #   path for decode steps)
    pp_microbatches: Optional[int] = None # GPipe microbatch count when the
                                          #   mesh has stage > 1 (None -> S)
    attn_softmax_dtype: str = "float32"   # fp32 softmax island
    logits_dtype: str = "float32"         # fp32 logits island
    kv_cache_dtype: str = "auto"          # "auto" (= activation dtype) |
                                          #   "int8" (per-slot-per-head
                                          #   scales; halves cache HBM
                                          #   traffic/memory; xla path)

    # --- latent attention (MLA) and routed experts.  All zero: the dense
    # GQA + SwiGLU block.  kv_lora_rank > 0 selects the block of
    # models/mla_moe.py (deepseek_v3-style: one cached latent row of
    # kv_lora_rank + qk_rope_head_dim values a token a layer, a leading
    # run of dense layers, then sigmoid-routed experts beside shared ones).
    kv_lora_rank: int = 0                 # width of the normed KV latent
    qk_nope_head_dim: int = 0             # per-head q/k width without rope
    qk_rope_head_dim: int = 0             # rotated width (ONE shared key head)
    v_head_dim: int = 0                   # per-head value width
    n_routed_experts: int = 0
    n_experts_per_tok: int = 0
    n_shared_experts: int = 0             # one SwiGLU of this many widths
    moe_intermediate_size: int = 0        # width of one expert
    routed_scaling_factor: float = 1.0
    first_k_dense: int = 0                # leading layers with a dense FFN
    q_lora_rank: int = 0                  # > 0: q = RMSNorm(h W_qa) W_qb
    # YaRN rope for the latent block (`ops.rope.yarn_inv_freq`): (factor,
    # original_max_position_embeddings, beta_fast, beta_slow,
    # mscale_all_dim); the softmax scale takes `yarn_mscale`^2.  None: plain.
    rope_yarn: Optional[Tuple[float, ...]] = None
    # The residual of the latent block as `hc_mult` streams mixed around every
    # attention and FFN by manifold-constrained hyper-connections
    # (`ops/mhc.py`).  1: x + F(norm(x)) on one stream.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)

    # --- window and full attention layers in one stack.  None: every layer
    # attends its whole causal context.  A tuple of n_layers flags selects
    # the block of models/afmoe.py (afmoe-style): a True layer sees key j
    # from query i iff 0 <= i - j < sliding_window and carries rope; a
    # False one is a full layer, which carries no position at all.  Gated
    # attention output, per-head q/k norms, a norm on both sides of each
    # sub-block, the embedding scaled by sqrt(dim), and the routed experts
    # of the fields above behind `first_k_dense` layers.
    window_layers: Optional[Tuple[bool, ...]] = None
    sliding_window: int = 0

    # --- recurrent (state-space) layers beside attention.  mb_per_layer 0:
    # none.  2 selects the block of models/sambay.py (phi4flash-style): even
    # layers of the first half are Mamba-1 mixers with a per-row convolution
    # and state-space state, odd ones window attention (`sliding_window`);
    # layer L/2 is a mixer that publishes its scan output, layer L/2 + 1 a
    # full-attention layer whose K/V is the only cache the second half reads:
    # there even layers are gated memory units over the published output
    # (no state, no cache), odd ones cross attention over that K/V.
    # Differential attention on adjacent head pairs, LayerNorm with bias,
    # no position encoding, tied head (`layer_kinds`).
    mb_per_layer: int = 0
    mamba_d_state: int = 16               # N: state values a channel
    mamba_d_conv: int = 4                 # causal depthwise conv width
    mamba_expand: int = 2                 # Di = expand * dim
    mamba_dt_rank: int = 0                # 0 -> ceil(dim / 16)
    layer_norm_eps: float = 1e-5

    # --- a state-space mixer BESIDE attention in every layer.  mamba_d_ssm
    # 0: none.  > 0 selects the block of models/falcon_h1.py (falcon_h1-
    # style): every layer runs a Mamba-2 mixer (one scalar decay a head a
    # token, a state [mamba_n_heads, mamba_d_head, mamba_d_state] float32 a
    # row, `B` / `C` shared by `mamba_n_groups` groups of heads, a chunked
    # matmul scan over `mamba_chunk_size` tokens) AND rotary GQA attention on
    # the same normed input and adds both to the residual, so every layer
    # owns K/V planes and a recurrent state at once.  RMSNorm, SwiGLU, an
    # untied head, and the muP multipliers below (scalars; `ssm_multipliers`
    # over the mixer projection's zones z, x, B, C, dt; `mlp_multipliers` on
    # the FFN's gate and its down projection).
    mamba_d_ssm: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)

    # --- learned sparse attention: a query attends the `index_topk` keys an
    # indexer scores highest.  0: none.  > 0 selects the block of
    # models/dsa_moe.py (KeyeVL2's language model: DeepSeek-Sparse-Attention
    # inside rotary GQA with per-head q/k norms): beside q, k, v every layer
    # projects `index_n_heads` index queries of `index_head_dim`, ONE index
    # key and a weight a head; I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
    # ranks the causal keys and attention is the softmax over the top
    # `index_topk` (ties to the lower position; every key while the context
    # is no longer than that).  The index keys are a third cache plane a
    # layer beside K and V.  Every layer's FFN is routed experts
    # (`first_k_dense` 0, no shared expert), scored by `moe_score_func`.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    moe_score_func: str = "sigmoid"       # "sigmoid" | "softmax" (ops/moe.route)

    def __post_init__(self):
        # JSON (a checkpoint's config.json) hands a tuple back as a list,
        # which neither compares equal nor hashes as a static argument.
        for name in ("window_layers", "ssm_multipliers", "mlp_multipliers",
                     "rope_yarn", "hc_clamp"):
            value = getattr(self, name)
            if isinstance(value, list):
                object.__setattr__(self, name, tuple(value))

    @property
    def parallel_mixer(self) -> bool:
        """The block whose every layer is a mixer beside attention."""
        return self.mamba_d_ssm > 0

    @property
    def recurrent_state(self) -> bool:
        """A per-row recurrent state beside the K/V planes: either block."""
        return self.mb_per_layer > 0 or self.parallel_mixer

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the parallel mixer's convolution: x beside B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def state_shapes(self) -> Tuple[Tuple[str, Tuple[int, ...], str], ...]:
        """(name, shape a layer a row, dtype) of a row's recurrent state, in
        the order caches and pools carry it (`conv`, `ssm`): the mixers' last
        conv inputs in the activation type and their state-space state in
        float32, minor axis lane-friendly (see `ops/ssm.py`).  Empty without
        recurrent layers."""
        if not self.recurrent_state:
            return ()
        keep = self.mamba_d_conv - 1
        if self.parallel_mixer:
            return (
                ("conv", (keep * self.mamba_conv_dim,), self.dtype),
                ("ssm", (self.mamba_n_heads, self.mamba_d_head,
                         self.mamba_d_state), "float32"),
            )
        return (
            ("conv", (keep * self.mamba_d_inner,), self.dtype),
            ("ssm", (self.mamba_d_state, self.mamba_d_inner), "float32"),
        )

    @property
    def state_bytes_per_row(self) -> int:
        """Bytes of one row's recurrent state over all its layers: what a
        slot holds beside its blocks, and what one snapshot costs."""
        return self.state_layers * sum(
            math.prod(shape) * jnp.dtype(dtype).itemsize
            for _, shape, dtype in self.state_shapes)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.dim // 16)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """A recurrent block's layer kinds by index (see above)."""
        if self.parallel_mixer:
            return ("mixer+full",) * self.n_layers
        half = self.n_layers // 2
        first = ("mamba", "window") * (half // 2)
        second = ("gmu", "cross") * ((half - 2) // 2)
        return first + ("mamba_pub", "full_pub") + second

    @property
    def state_layers(self) -> int:
        """Layers that carry a recurrent state a row: the mixers."""
        if self.parallel_mixer:
            return self.n_layers
        return self.n_layers // 4 + 1 if self.recurrent_state else 0

    @property
    def cache_layers(self) -> int:
        """Layers that OWN K/V planes (or the latent plane) in a cache: all
        of them, but in the alternating recurrent block the window layers
        and the one full-attention layer, whose plane the cross layers read."""
        return self.n_layers // 4 + 1 if self.mb_per_layer > 0 else self.n_layers

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def head_dim(self) -> int:
        if self.head_size is not None:
            return self.head_size
        assert self.dim % self.n_heads == 0
        return self.dim // self.n_heads

    @property
    def windowed_attention(self) -> bool:
        return self.window_layers is not None

    @property
    def sparse_attention(self) -> bool:
        """Learned key selection: an index-key plane beside K and V."""
        return self.index_topk > 0

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_heads(self) -> int:
        """Heads of one cached row: the KV heads, 1 for the latent and for
        sparse attention, or the KV head PAIRS of differential attention
        (`[k1 | k2]` a row)."""
        if self.mb_per_layer > 0:
            return self.kv_heads // 2
        if self.sparse_attention:
            return 1      # a token's KV heads side by side: see `cache_width`
        return 1 if self.latent_attention else self.kv_heads

    @property
    def cache_width(self) -> int:
        """Width of a cache head's row in the `k` plane: the head size, or
        the normed latent beside the rotated shared key (`latent_dim`
        values; the latent cache has no `v` plane: the value is the latent
        itself)."""
        if self.latent_attention:
            # Stored lane-aligned (576 -> 640 here), zeros behind the values:
            # a row that is no multiple of the 128 lanes makes XLA:TPU lay
            # the pool out block-size-minor, and the paged kernel's row-major
            # operand then costs a copy of the whole pool in and out of
            # every dispatch (compiled for a v5e, PR 27).
            return -(-self.latent_dim // 128) * 128
        if self.mb_per_layer > 0:
            return 2 * self.head_dim
        if self.sparse_attention:
            # One row a token holds every KV head's key (or value): a decode
            # row gathers the slots its selection chose, and a gather costs by
            # the row — 2,048 rows of 1 KiB here where head-major planes would
            # make it 8,192 of 256 B (v5e: ~10 ns a row, PERF.md section 6).
            return self.kv_heads * self.head_dim
        return self.head_dim

    @property
    def latent_dim(self) -> int:
        """Values a token a layer the latent cache holds."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def rope_dim(self) -> int:
        return self.qk_rope_head_dim if self.latent_attention else self.head_dim

    @property
    def ffn_dim(self) -> int:
        if self.intermediate_size is not None:
            return self.intermediate_size
        return swiglu_hidden_size(self.dim, self.multiple_of, self.ffn_dim_multiplier)

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def weight_dtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "LLaMAConfig":
        return dataclasses.replace(self, **kw)

    def validate(self) -> None:
        assert self.head_size is not None or self.dim % self.n_heads == 0, (
            "n_heads must divide dim (or head_size be given)"
        )
        if self.sparse_attention:
            self._validate_sparse()
        elif self.parallel_mixer:
            self._validate_parallel_mixer()
        elif self.recurrent_state:
            self._validate_recurrent()
        elif self.windowed_attention:
            self._validate_windowed()
        elif self.latent_attention:
            self._validate_latent()
        assert self.n_heads % self.kv_heads == 0, (
            "n_heads must be a multiple of n_kv_heads (GQA group size)"
        )
        if self.attn_impl not in ("xla", "flash", "ring", "auto"):
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}")
        if self.remat_policy not in ("dots", "full"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r}; "
                "expected 'dots' or 'full'"
            )
        for name in ("resid_pdrop", "embd_pdrop", "attn_pdrop"):
            p = getattr(self, name)
            if not 0.0 <= p < 1.0:
                raise ValueError(f"{name}={p} must be in [0, 1)")
        if self.kv_cache_dtype not in ("auto", "int8"):
            # A typo here would silently fall back to the full-precision
            # cache; fail instead.
            raise ValueError(
                f"unknown kv_cache_dtype {self.kv_cache_dtype!r}; "
                "expected 'auto' or 'int8'"
            )

    @property
    def expert_block(self) -> Optional[str]:
        """The block beside the dense one a configuration selects, as error
        messages name it; None for the dense block.  None of them gets a
        mesh, int8, speculation or the train step yet."""
        if self.sparse_attention:
            return "learned sparse attention"
        if self.parallel_mixer:
            return "parallel mixer and attention layers"
        if self.recurrent_state:
            return "recurrent state layers"
        if self.latent_attention:
            return "latent attention"
        return "window attention layers" if self.windowed_attention else None

    def _validate_experts(self, needs=()) -> None:
        """What both expert blocks need, and what neither gets yet — refused
        by name here, never served wrongly."""
        block = self.expert_block
        for name in (*needs, "n_routed_experts", "n_experts_per_tok",
                     "moe_intermediate_size"):
            if getattr(self, name) <= 0:
                raise ValueError(f"{block} needs {name} > 0")
        if not 0 < self.first_k_dense < self.n_layers:
            raise ValueError(
                f"the block with {block} has a leading run of dense "
                f"layers then expert layers; first_k_dense={self.first_k_dense} "
                f"of n_layers={self.n_layers} leaves one of them empty"
            )
        if self.n_experts_per_tok > self.n_routed_experts:
            raise ValueError("n_experts_per_tok exceeds n_routed_experts")
        if self.kv_cache_dtype == "int8":
            raise ValueError(
                f"kv_cache_dtype='int8' is not supported with {block}: the "
                "latent row has no per-head scale, the int8 kernels no window"
            )
        if self.attn_impl == "ring":
            raise ValueError(
                f"attn_impl='ring' is not supported with {block}"
            )
        if self.tie_word_embeddings or self.use_scaled_rope:
            raise ValueError(
                "tie_word_embeddings / use_scaled_rope are not supported "
                f"with {block}"
            )

    def _validate_sparse(self) -> None:
        """The block with learned sparse attention: what it needs, and what
        it does not get yet — refused by name here, never served wrongly."""
        block = self.expert_block
        if (self.recurrent_state or self.latent_attention
                or self.windowed_attention):
            raise ValueError(
                "index_topk beside mamba_d_ssm / mb_per_layer / kv_lora_rank / "
                "window_layers: two blocks in one configuration")
        for name in ("index_n_heads", "index_head_dim", "n_routed_experts",
                     "n_experts_per_tok", "moe_intermediate_size"):
            if getattr(self, name) <= 0:
                raise ValueError(f"{block} needs {name} > 0")
        if self.index_head_dim % 4:
            raise ValueError(
                f"index_head_dim: {self.index_head_dim!r}; the leading half "
                "of an index head is rotated (a multiple of 4)")
        if self.first_k_dense or self.n_shared_experts:
            raise ValueError(
                f"the block with {block} has routed experts in every layer "
                "and no shared expert (first_k_dense and n_shared_experts 0)")
        if self.n_experts_per_tok > self.n_routed_experts:
            raise ValueError("n_experts_per_tok exceeds n_routed_experts")
        if self.moe_score_func not in ("sigmoid", "softmax"):
            raise ValueError(
                f"moe_score_func: {self.moe_score_func!r} is not in the "
                "program; a router scores by 'sigmoid' or 'softmax'")
        if self.kv_cache_dtype == "int8":
            raise ValueError(
                f"kv_cache_dtype='int8' is not supported with {block}: the "
                "index-key plane has no int8 form")
        if self.attn_impl == "ring":
            raise ValueError(f"attn_impl='ring' is not supported with {block}")
        if self.tie_word_embeddings or self.use_scaled_rope:
            raise ValueError(
                "tie_word_embeddings / use_scaled_rope are not supported "
                f"with {block}")

    def _validate_recurrent(self) -> None:
        """The block with recurrent state layers: what it needs, and what
        it does not get yet — refused by name here, never served wrongly."""
        block = self.expert_block
        if self.latent_attention or self.windowed_attention or self.n_routed_experts:
            raise ValueError(
                "mb_per_layer beside kv_lora_rank / window_layers / routed "
                "experts: two blocks in one configuration")
        if self.mb_per_layer != 2:
            raise ValueError(
                f"mb_per_layer: {self.mb_per_layer!r} is not in the program; "
                "its block alternates one mixer and one attention layer (2)")
        if self.n_layers < 8 or self.n_layers % 4:
            raise ValueError(
                f"{block} need n_layers a multiple of 4 and >= 8 (two halves "
                f"of mixer / attention pairs), got {self.n_layers}")
        if self.sliding_window <= 0:
            raise ValueError(
                f"{block}: the window layers need a sliding_window > 0, got "
                f"{self.sliding_window!r}")
        if self.n_heads % 2 or self.kv_heads % 2 or self.n_heads % self.kv_heads:
            raise ValueError(
                "differential attention pairs adjacent heads: n_heads and "
                f"n_kv_heads must be even ({self.n_heads}, {self.kv_heads})")
        if self.head_size is not None:
            raise ValueError(f"head_size is not supported with {block}")
        if self.mamba_d_conv != 4:
            raise ValueError(
                f"mamba_d_conv: {self.mamba_d_conv!r} is not in the program; "
                "the convolution state holds 3 inputs (width 4)")
        for name in ("mamba_d_state", "mamba_expand"):
            if getattr(self, name) <= 0:
                raise ValueError(f"{block} need {name} > 0")
        if not self.tie_word_embeddings:
            raise ValueError(f"{block}: the head is tied (tie_word_embeddings)")
        if self.kv_cache_dtype == "int8":
            raise ValueError(
                f"kv_cache_dtype='int8' is not supported with {block}: the "
                "head-pair row has no per-head scale")
        if self.attn_impl == "ring":
            raise ValueError(f"attn_impl='ring' is not supported with {block}")
        if self.use_scaled_rope:
            raise ValueError(
                f"use_scaled_rope is not supported with {block}: the block "
                "carries no position encoding")

    def _validate_parallel_mixer(self) -> None:
        """The block with a mixer beside attention in every layer: what it
        needs, and what it does not get yet — refused by name here, never
        served wrongly."""
        block = self.expert_block
        if (self.mb_per_layer or self.latent_attention
                or self.windowed_attention or self.n_routed_experts):
            raise ValueError(
                "mamba_d_ssm beside mb_per_layer / kv_lora_rank / "
                "window_layers / routed experts: two blocks in one "
                "configuration")
        for name in ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
                     "mamba_n_groups", "mamba_chunk_size"):
            if getattr(self, name) <= 0:
                raise ValueError(f"{block} need {name} > 0")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError(
                f"mamba_n_heads * mamba_d_head = {self.mamba_n_heads} * "
                f"{self.mamba_d_head} is not mamba_d_ssm = {self.mamba_d_ssm}")
        if (self.mamba_n_heads % self.mamba_n_groups
                or self.mamba_d_ssm % self.mamba_n_groups):
            raise ValueError(
                f"mamba_n_groups = {self.mamba_n_groups} must divide "
                f"mamba_n_heads = {self.mamba_n_heads} (heads share a "
                "group's B and C, the gated norm runs a group)")
        if self.mamba_d_conv != 4:
            raise ValueError(
                f"mamba_d_conv: {self.mamba_d_conv!r} is not in the program; "
                "the convolution state holds 3 inputs (width 4)")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError(
                "ssm_multipliers has one value a zone of the mixer's "
                "projection (z, x, B, C, dt: 5) and mlp_multipliers one for "
                "the gate and one for the down projection (2), got "
                f"{len(self.ssm_multipliers)} and {len(self.mlp_multipliers)}")
        if self.tie_word_embeddings:
            raise ValueError(f"{block}: the head is untied (tie_word_embeddings)")
        if self.kv_cache_dtype == "int8":
            raise ValueError(
                f"kv_cache_dtype='int8' is not supported with {block}: the "
                "recurrent state beside the planes has no int8 form")
        if self.attn_impl == "ring":
            raise ValueError(f"attn_impl='ring' is not supported with {block}")
        if self.use_scaled_rope:
            raise ValueError(f"use_scaled_rope is not supported with {block}")

    def _validate_windowed(self) -> None:
        """The window-and-full-attention block (see `_validate_experts`)."""
        if self.latent_attention:
            raise ValueError("window_layers and latent attention are two blocks")
        if len(self.window_layers) != self.n_layers:
            raise ValueError(
                f"window_layers needs n_layers={self.n_layers} flags, got "
                f"{self.window_layers!r}")
        if any(self.window_layers) and self.sliding_window <= 0:
            raise ValueError(
                "the window layers need a sliding_window > 0, got "
                f"{self.sliding_window!r}")
        self._validate_experts()

    def _validate_latent(self) -> None:
        """The latent-attention block (see `_validate_experts`)."""
        self._validate_experts(
            needs=("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        if self.q_lora_rank < 0:
            raise ValueError(f"q_lora_rank={self.q_lora_rank} must be >= 0")
        if self.rope_yarn is not None and (
                len(self.rope_yarn) != 5 or self.rope_yarn[0] < 1
                or self.rope_yarn[1] <= 0):
            raise ValueError(
                f"rope_yarn={self.rope_yarn!r} is not (factor >= 1, original "
                "length > 0, beta_fast, beta_slow, mscale_all_dim)")
        if self.hc_mult < 1:
            raise ValueError(f"hc_mult={self.hc_mult} must be >= 1")
        if self.hc_mult > 1 and not (
                self.hc_sinkhorn_iters >= 1 and self.hc_eps > 0
                and len(self.hc_clamp) == 2 and self.hc_clamp[0] < self.hc_clamp[1]):
            raise ValueError(
                "hc_mult > 1 needs hc_sinkhorn_iters >= 1, hc_eps > 0 and "
                f"hc_clamp (min < max); got {self.hc_sinkhorn_iters}, "
                f"{self.hc_eps}, {self.hc_clamp!r}")


# ---------------------------------------------------------------------------
# Published config.json keys -> LLaMAConfig.  Strict: a key this map does
# not know is refused by name, never dropped — a file that carries an expert
# count, a latent rank or a window would otherwise be served as the dense
# block of the same hidden size, under the model's name.  The guard against
# a wrong map is the benchmark's plain reference, which reads the
# configuration FILE and never the object made here.
# ---------------------------------------------------------------------------

# published key -> configuration field (every block)
_PUBLISHED = {
    "hidden_size": "dim", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "intermediate_size", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_word_embeddings",
}
_PUBLISHED_DTYPES = ("bfloat16", "float32")
# checked or accepted below, mapped to no field of their own
_PUBLISHED_OTHER = ("head_dim", "torch_dtype", "sliding_window",
                    "max_position_embeddings")
# the deepseek_v3 block (latent attention, routed + shared experts)
_PUBLISHED_LATENT = {
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "n_routed_experts": "n_routed_experts",
    "num_experts_per_tok": "n_experts_per_tok",
    "n_shared_experts": "n_shared_experts",
    "moe_intermediate_size": "moe_intermediate_size",
    "routed_scaling_factor": "routed_scaling_factor",
    "first_k_dense_replace": "first_k_dense",
}
# its keys with ONE accepted value: the block as the program computes it
_PUBLISHED_LATENT_FIXED = {
    "model_type": "deepseek_v3", "attention_bias": False, "hidden_act": "silu",
    "moe_layer_freq": 1, "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "q_lora_rank": None, "rope_interleave": True, "rope_scaling": None,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
}

# the same block under `model_type: xing4_0`: a low-rank query, YaRN rope and
# the residual as `hc_mult` streams (manifold-constrained hyper-connections).
# Mapped by `_latent_variant`; these have ONE accepted value.
_PUBLISHED_MHC_FIXED = dict(
    {k: v for k, v in _PUBLISHED_LATENT_FIXED.items()
     if k not in ("q_lora_rank", "rope_scaling")},
    model_type="xing4_0",
    # a drafting / training head the next-token logits do not read: built
    # nowhere, accepted at the published count only
    num_nextn_predict_layers=1,
    ep_size=1,
)
_PUBLISHED_MHC = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                  "mhc_h_res_clamp_max", "q_lora_rank", "rope_scaling")
_PUBLISHED_YARN = ("type", "factor", "original_max_position_embeddings",
                   "beta_fast", "beta_slow", "mscale", "mscale_all_dim")


def _latent_variant(raw) -> dict:
    """The fields of `model_type: xing4_0`'s own keys, each held to what the
    block computes and refused by name otherwise."""
    def number(key, ok, what, whole=False):
        v = raw[key]
        if isinstance(v, bool) or not isinstance(v, int if whole else (int, float)) or not ok(v):
            raise ValueError(f"{key}: {v!r} is not {what}")
        return v

    scaling = raw["rope_scaling"]
    if not isinstance(scaling, dict) or scaling.get("type") != "yarn":
        raise ValueError(
            f"rope_scaling: {scaling!r} is not in the program; with model_type "
            "'xing4_0' its latent-attention block computes type 'yarn' only")
    odd = sorted(set(scaling) ^ set(_PUBLISHED_YARN))
    if odd:
        raise ValueError(f"rope_scaling: key {odd[0]!r} is missing or not understood")
    yarn = [scaling[k] for k in _PUBLISHED_YARN[1:]]
    if any(isinstance(v, bool) or not isinstance(v, (int, float)) for v in yarn) or (
            yarn[0] < 1 or yarn[1] <= 0):
        raise ValueError(f"rope_scaling: {scaling!r} is not a YaRN factor >= 1 over a length > 0")
    if scaling["mscale"] != scaling["mscale_all_dim"]:
        raise ValueError(
            "rope_scaling: mscale != mscale_all_dim (a factor on cos / sin) is "
            "not in the program")
    lo = number("mhc_h_res_clamp_min", lambda v: True, "a number")
    hi = number("mhc_h_res_clamp_max", lambda v: v > lo, "above mhc_h_res_clamp_min")
    return dict(
        hc_mult=number("hc_mult", lambda v: v >= 1, "a stream count >= 1", whole=True),
        hc_sinkhorn_iters=number("hc_sinkhorn_iters", lambda v: v >= 1,
                                 "an iteration count >= 1", whole=True),
        hc_eps=float(number("hc_eps", lambda v: v > 0, "a number > 0")),
        hc_clamp=(float(lo), float(hi)),
        q_lora_rank=number("q_lora_rank", lambda v: v > 0, "a rank > 0", whole=True),
        rope_yarn=tuple(float(v) for v in (*yarn[:4], scaling["mscale_all_dim"])),
    )


# the afmoe block (window and full attention layers, routed + shared experts)
_PUBLISHED_WINDOWED = {
    "num_experts": "n_routed_experts",
    "num_experts_per_tok": "n_experts_per_tok",
    "num_shared_experts": "n_shared_experts",
    "moe_intermediate_size": "moe_intermediate_size",
    "route_scale": "routed_scaling_factor",
    "num_dense_layers": "first_k_dense",
}
# its keys with ONE accepted value: the block as the program computes it
_PUBLISHED_WINDOWED_FIXED = {
    "model_type": "afmoe", "hidden_act": "silu", "score_func": "sigmoid",
    "route_norm": True, "mup_enabled": True, "rope_scaling": None,
    "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
    "num_limited_groups": 1,
}
# training and implementation switches: accepted at any value, unused
_PUBLISHED_WINDOWED_UNUSED = ("load_balance_coeff", "use_grouped_mm")

# the phi4flash block (recurrent state layers beside window / full / cross
# attention): published key -> field.  It has no rope, no RMSNorm eps and no
# routed experts, so it takes none of `_PUBLISHED`'s keys it does not name.
_PUBLISHED_RECURRENT = {
    "hidden_size": "dim", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "intermediate_size", "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_word_embeddings",
    "layer_norm_eps": "layer_norm_eps", "mb_per_layer": "mb_per_layer",
    "sliding_window": "sliding_window",
}
# sizes the published file may leave to the modelling code's defaults
_PUBLISHED_RECURRENT_OPTIONAL = ("mamba_d_state", "mamba_d_conv", "mamba_expand")
# its keys with ONE accepted value: the block as the program computes it
_PUBLISHED_RECURRENT_FIXED = {
    "model_type": "phi4flash", "hidden_act": "silu", "mb_per_layer": 2,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "embd_pdrop": 0, "resid_pdrop": 0,
}


def _from_published_recurrent(raw, *, max_seq_len: int, attn_impl: str) -> "LLaMAConfig":
    """`from_published` for a file with `mb_per_layer` (the phi4flash
    block), as strict as the others."""
    fields = _PUBLISHED_RECURRENT
    known = (set(fields) | set(_PUBLISHED_RECURRENT_OPTIONAL)
             | set(_PUBLISHED_RECURRENT_FIXED)
             | {"torch_dtype", "max_position_embeddings", "mamba_dt_rank"})
    unknown = sorted(set(raw) - known)
    if unknown:
        raise ValueError(f"the program understands no published key {', '.join(map(repr, unknown))}")
    missing = sorted(k for k in (*fields, "torch_dtype") if k not in raw)
    if missing:
        raise ValueError(
            f"published key {missing[0]!r} is missing (a file with "
            "'mb_per_layer' is the block with recurrent state layers, "
            "which needs it)")
    for key, only in _PUBLISHED_RECURRENT_FIXED.items():
        if key in raw and (raw[key] != only or isinstance(raw[key], bool) != isinstance(only, bool)):
            raise ValueError(
                f"{key}: {raw[key]!r} is not in the program; its block with "
                f"recurrent state layers computes {only!r} only")
    window = raw["sliding_window"]
    if not isinstance(window, int) or isinstance(window, bool) or window <= 0:
        raise ValueError(
            f"sliding_window: {window!r}; the window layers need an int > 0")
    layers = raw["num_hidden_layers"]
    if not isinstance(layers, int) or layers < 8 or layers % 4:
        raise ValueError(
            f"num_hidden_layers: {layers!r} is not a multiple of 4 (>= 8): "
            "two halves of mixer / attention pairs")
    if raw["hidden_size"] % raw["num_attention_heads"]:
        raise ValueError("num_attention_heads does not divide hidden_size")
    if raw["torch_dtype"] not in _PUBLISHED_DTYPES:
        raise ValueError(f"torch_dtype {raw['torch_dtype']!r} is not one the program serves in")
    extra = {k: raw[k] for k in _PUBLISHED_RECURRENT_OPTIONAL if k in raw}
    rank = raw.get("mamba_dt_rank", "auto")
    if rank != "auto":
        if not isinstance(rank, int) or isinstance(rank, bool) or rank <= 0:
            raise ValueError(f"mamba_dt_rank: {rank!r} is neither 'auto' nor an int > 0")
        extra["mamba_dt_rank"] = rank
    return LLaMAConfig(
        **{ours: raw[theirs] for theirs, ours in fields.items()}, **extra,
        dtype=raw["torch_dtype"], param_dtype=raw["torch_dtype"],
        max_seq_len=max_seq_len, attn_impl=attn_impl,
    )


# the falcon_h1 block (a Mamba-2 mixer beside rotary GQA attention in every
# layer, muP multipliers): published key -> field.  Every key is needed.
_PUBLISHED_PARALLEL = {
    "hidden_size": "dim", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_size",
    "intermediate_size": "intermediate_size", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_word_embeddings",
    "mamba_d_ssm": "mamba_d_ssm", "mamba_n_heads": "mamba_n_heads",
    "mamba_d_head": "mamba_d_head", "mamba_d_state": "mamba_d_state",
    "mamba_n_groups": "mamba_n_groups", "mamba_d_conv": "mamba_d_conv",
    "mamba_chunk_size": "mamba_chunk_size",
    "embedding_multiplier": "embedding_multiplier",
    "lm_head_multiplier": "lm_head_multiplier",
    "key_multiplier": "key_multiplier",
    "attention_in_multiplier": "attention_in_multiplier",
    "attention_out_multiplier": "attention_out_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "ssm_multipliers": "ssm_multipliers", "mlp_multipliers": "mlp_multipliers",
}
# its keys with ONE accepted value: the block as the program computes it
# (`mamba_expand` and `mlp_expansion_factor` are unused where `mamba_d_ssm`
# and `intermediate_size` are given, and accepted as published only)
_PUBLISHED_PARALLEL_FIXED = {
    "model_type": "falcon_h1", "hidden_act": "silu", "attention_bias": False,
    "attn_layer_indices": None, "rope_scaling": None, "num_logits_to_keep": 1,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "projectors_bias": False, "mlp_bias": False, "tie_word_embeddings": False,
    "mamba_expand": 2, "mlp_expansion_factor": 8, "mamba_d_conv": 4,
}
_PUBLISHED_PARALLEL_LISTS = {"ssm_multipliers": 5, "mlp_multipliers": 2}


# the KeyeVL2 block (learned sparse attention inside rotary GQA, softmax-routed
# experts in every layer): published key -> field.  Every key is needed.
_PUBLISHED_SPARSE = {
    "hidden_size": "dim", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_size", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_word_embeddings",
    "num_experts": "n_routed_experts",
    "num_experts_per_tok": "n_experts_per_tok",
    "moe_intermediate_size": "moe_intermediate_size",
}
# `sa_config`'s keys -> fields; its tiling keys are accepted as published
_PUBLISHED_SPARSE_INDEXER = {
    "topk": "index_topk", "indexer_num_heads": "index_n_heads",
    "indexer_head_dim": "index_head_dim",
}
_PUBLISHED_SPARSE_INDEXER_FIXED = {
    "indexer_num_kv_heads": 1, "q_chunk_size": 512, "kv_chunk_size": 512,
}
# its keys with ONE accepted value: the block as the program computes it.
# With text positions the three rope streams of `mrope_section` are equal, so
# the published `rope_scaling` object is plain rope and is accepted at
# exactly that value; `intermediate_size` is the width of the dense layers
# `mlp_only_layers` would name, of which there are none.
_PUBLISHED_SPARSE_FIXED = {
    "model_type": "KeyeVL2", "hidden_act": "silu", "attention_bias": False,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "tie_word_embeddings": False, "sliding_window": None,
    "use_sliding_window": False,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
}
# accepted at any value, unused: the server's own `max_seq_len` bounds a row,
# no layer is a window layer (`use_sliding_window` false) or a dense one
_PUBLISHED_SPARSE_UNUSED = ("max_position_embeddings", "max_window_layers",
                            "intermediate_size")


def _from_published_sparse(raw, *, max_seq_len: int, attn_impl: str) -> "LLaMAConfig":
    """`from_published` for a file with `sa_config` (the KeyeVL2 block), as
    strict as the others."""
    fields = _PUBLISHED_SPARSE
    known = (set(fields) | set(_PUBLISHED_SPARSE_FIXED)
             | set(_PUBLISHED_SPARSE_UNUSED)
             | {"sa_config", "num_local_experts", "torch_dtype"})
    unknown = sorted(set(raw) - known)
    if unknown:
        raise ValueError(f"the program understands no published key {', '.join(map(repr, unknown))}")
    missing = sorted(k for k in (*fields, *_PUBLISHED_SPARSE_FIXED, "sa_config",
                                 "torch_dtype") if k not in raw)
    if missing:
        raise ValueError(
            f"published key {missing[0]!r} is missing (a file with "
            "'sa_config' is the block with learned sparse attention, which "
            "needs it)")
    for key, only in _PUBLISHED_SPARSE_FIXED.items():
        if raw[key] != only or isinstance(raw[key], bool) != isinstance(only, bool):
            raise ValueError(
                f"{key}: {raw[key]!r} is not in the program; its block with "
                f"learned sparse attention computes {only!r} only")
    sa = raw["sa_config"]
    if not isinstance(sa, dict):
        raise ValueError(f"sa_config: {sa!r} is not an object")
    sa_known = set(_PUBLISHED_SPARSE_INDEXER) | set(_PUBLISHED_SPARSE_INDEXER_FIXED)
    if set(sa) != sa_known:
        odd = sorted(set(sa) ^ sa_known)
        raise ValueError(
            f"sa_config.{odd[0]}: the indexer is given by exactly "
            f"{sorted(sa_known)}")
    for key, only in _PUBLISHED_SPARSE_INDEXER_FIXED.items():
        if sa[key] != only or isinstance(sa[key], bool):
            raise ValueError(
                f"sa_config.{key}: {sa[key]!r} is not in the program; its "
                f"indexer computes {only!r} only")
    for where, keys in ((raw, ("hidden_size", "num_hidden_layers",
                               "num_attention_heads", "num_key_value_heads",
                               "head_dim", "vocab_size", "num_experts",
                               "num_experts_per_tok", "moe_intermediate_size")),
                        (sa, tuple(_PUBLISHED_SPARSE_INDEXER))):
        for key in keys:
            v = where[key]
            if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
                name = key if where is raw else f"sa_config.{key}"
                raise ValueError(f"{name}: {v!r} is not an int > 0")
    if raw.get("num_local_experts", raw["num_experts"]) != raw["num_experts"]:
        raise ValueError(
            f"num_local_experts: {raw['num_local_experts']!r} is not "
            f"num_experts = {raw['num_experts']!r}")
    if raw["head_dim"] % 2:
        raise ValueError(f"head_dim: {raw['head_dim']!r} is not an even size > 0")
    if raw["num_attention_heads"] % raw["num_key_value_heads"]:
        raise ValueError("num_key_value_heads does not divide num_attention_heads")
    if raw["torch_dtype"] not in _PUBLISHED_DTYPES:
        raise ValueError(f"torch_dtype {raw['torch_dtype']!r} is not one the program serves in")
    return LLaMAConfig(
        **{ours: raw[theirs] for theirs, ours in fields.items()},
        **{ours: sa[theirs] for theirs, ours in _PUBLISHED_SPARSE_INDEXER.items()},
        moe_score_func="softmax",
        dtype=raw["torch_dtype"], param_dtype=raw["torch_dtype"],
        max_seq_len=max_seq_len, attn_impl=attn_impl,
    )


def _is_number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _from_published_parallel(raw, *, max_seq_len: int, attn_impl: str) -> "LLaMAConfig":
    """`from_published` for a file with `mamba_d_ssm` (the falcon_h1
    block), as strict as the others.  How the mixer's sizes must fit one
    another is `LLaMAConfig._validate_parallel_mixer`'s to say: its fields
    carry the published names."""
    fields = _PUBLISHED_PARALLEL
    known = (set(fields) | set(_PUBLISHED_PARALLEL_FIXED)
             | {"torch_dtype", "max_position_embeddings"})
    unknown = sorted(set(raw) - known)
    if unknown:
        raise ValueError(f"the program understands no published key {', '.join(map(repr, unknown))}")
    missing = sorted(k for k in (*fields, *_PUBLISHED_PARALLEL_FIXED, "torch_dtype")
                     if k not in raw)
    if missing:
        raise ValueError(
            f"published key {missing[0]!r} is missing (a file with "
            "'mamba_d_ssm' is the block with parallel mixer and attention "
            "layers, which needs it)")
    for key, only in _PUBLISHED_PARALLEL_FIXED.items():
        if raw[key] != only or isinstance(raw[key], bool) != isinstance(only, bool):
            raise ValueError(
                f"{key}: {raw[key]!r} is not in the program; its block with "
                f"parallel mixer and attention layers computes {only!r} only")
    for key, n in _PUBLISHED_PARALLEL_LISTS.items():
        v = raw[key]
        if not isinstance(v, (list, tuple)) or len(v) != n or not all(map(_is_number, v)):
            raise ValueError(f"{key}: {v!r} is not a list of {n} numbers")
    for key in fields:
        if key.endswith("_multiplier") and not _is_number(raw[key]):
            raise ValueError(f"{key}: {raw[key]!r} is not a number")
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "intermediate_size",
                "vocab_size", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_n_groups", "mamba_chunk_size"):
        v = raw[key]
        if not isinstance(v, int) or isinstance(v, bool) or v <= 0:
            raise ValueError(f"{key}: {v!r} is not an int > 0")
    if raw["head_dim"] % 2:
        raise ValueError(f"head_dim: {raw['head_dim']!r} is not an even size > 0")
    if raw["num_attention_heads"] % raw["num_key_value_heads"]:
        raise ValueError("num_key_value_heads does not divide num_attention_heads")
    if raw["torch_dtype"] not in _PUBLISHED_DTYPES:
        raise ValueError(f"torch_dtype {raw['torch_dtype']!r} is not one the program serves in")
    values = {ours: raw[theirs] for theirs, ours in fields.items()}
    for key in _PUBLISHED_PARALLEL_LISTS:
        values[key] = tuple(float(v) for v in raw[key])
    return LLaMAConfig(
        **values, dtype=raw["torch_dtype"], param_dtype=raw["torch_dtype"],
        max_seq_len=max_seq_len, attn_impl=attn_impl,
    )


_LAYER_TYPES = ("sliding_attention", "full_attention")


def _window_layers(raw) -> dict:
    """`layer_types` + `sliding_window` -> which layers are window layers,
    and the window's length."""
    types, window = raw["layer_types"], raw.get("sliding_window")
    if not isinstance(window, int) or isinstance(window, bool) or window <= 0:
        raise ValueError(
            f"sliding_window: {window!r} beside layer_types; the window "
            "layers need a length > 0")
    if not isinstance(types, (list, tuple)) or len(types) != raw["num_hidden_layers"]:
        raise ValueError(
            f"layer_types: needs num_hidden_layers={raw['num_hidden_layers']} "
            f"entries, got {len(types) if isinstance(types, (list, tuple)) else types!r}")
    other = [t for t in types if t not in _LAYER_TYPES]
    if other:
        raise ValueError(
            f"layer_types: {other[0]!r} is not in the program; a layer is "
            f"one of {_LAYER_TYPES}")
    every = raw.get("global_attn_every_n_layers")
    if every is not None and any(
            (t == "full_attention") != ((i + 1) % every == 0)
            for i, t in enumerate(types)):
        raise ValueError(
            f"global_attn_every_n_layers: {every!r} is not what layer_types says")
    return {"window_layers": tuple(t == "sliding_attention" for t in types),
            "sliding_window": window}


def from_published(raw, *, max_seq_len: int, attn_impl: str) -> LLaMAConfig:
    """The `LLaMAConfig` of a model's published `config.json` keys `raw`, or
    `ValueError` naming the key that stands in the way.
    `max_position_embeddings` is accepted and unused: a server serves at its
    own `max_seq_len`.  A file with `kv_lora_rank` is the deepseek_v3 block
    (under `model_type: xing4_0` with a low-rank query, YaRN rope and a
    multi-stream residual),
    one with `layer_types` the afmoe block, one with `mb_per_layer` the
    phi4flash block, one with `mamba_d_ssm` the falcon_h1 block, one with
    `sa_config` the KeyeVL2 block; every other file is the dense block."""
    latent = "kv_lora_rank" in raw
    windowed = "layer_types" in raw
    if "sa_config" in raw or raw.get("model_type") == "KeyeVL2":
        if latent or windowed or "mb_per_layer" in raw or "mamba_d_ssm" in raw:
            raise ValueError(
                "sa_config beside kv_lora_rank / layer_types / mb_per_layer / "
                "mamba_d_ssm: two blocks in one file")
        return _from_published_sparse(raw, max_seq_len=max_seq_len, attn_impl=attn_impl)
    if "mamba_d_ssm" in raw or raw.get("model_type") == "falcon_h1":
        if latent or windowed or "mb_per_layer" in raw:
            raise ValueError(
                "mamba_d_ssm beside kv_lora_rank / layer_types / mb_per_layer: "
                "two blocks in one file")
        return _from_published_parallel(raw, max_seq_len=max_seq_len, attn_impl=attn_impl)
    if "mb_per_layer" in raw or raw.get("model_type") == "phi4flash":
        if latent or windowed:
            raise ValueError("mb_per_layer beside kv_lora_rank / layer_types: two blocks in one file")
        return _from_published_recurrent(raw, max_seq_len=max_seq_len, attn_impl=attn_impl)
    if latent and windowed:
        raise ValueError("layer_types beside kv_lora_rank: two blocks in one file")
    fields = dict(_PUBLISHED, **(_PUBLISHED_LATENT if latent else {}),
                  **(_PUBLISHED_WINDOWED if windowed else {}))
    known = set(fields) | set(_PUBLISHED_OTHER)
    if latent and raw.get("model_type", "deepseek_v3") not in ("deepseek_v3", "xing4_0"):
        raise ValueError(
            f"model_type: {raw['model_type']!r} is not in the program; its "
            "latent-attention block computes 'deepseek_v3' and 'xing4_0' only")
    mhc = latent and raw.get("model_type") == "xing4_0"
    latent_fixed = _PUBLISHED_MHC_FIXED if mhc else _PUBLISHED_LATENT_FIXED
    if latent:
        known |= set(latent_fixed) | {"qk_head_dim"} | set(_PUBLISHED_MHC if mhc else ())
    if windowed:
        known |= (set(_PUBLISHED_WINDOWED_FIXED) | set(_PUBLISHED_WINDOWED_UNUSED)
                  | {"layer_types", "global_attn_every_n_layers"})
    unknown = sorted(set(raw) - known)
    if unknown:
        raise ValueError(f"the program understands no published key {', '.join(map(repr, unknown))}")
    missing = sorted(k for k in (*fields, "torch_dtype", *(_PUBLISHED_MHC if mhc else ()))
                     if k not in raw)
    if missing:
        raise ValueError(
            f"published key {missing[0]!r} is missing" + (
                " (a file with 'kv_lora_rank' is the latent-attention "
                "block, which needs it)" if latent else
                " (a file with 'layer_types' is the window-attention "
                "block, which needs it)" if windowed else ""))
    if raw.get("sliding_window") is not None and not windowed:
        raise ValueError(
            "sliding_window: a window without layer_types (which layers it "
            "holds for) is not in the program")
    heads, hidden = raw["num_attention_heads"], raw["hidden_size"]
    if "head_dim" not in raw and hidden % heads:
        raise ValueError(
            "head_dim * heads != hidden_size: the file gives no head_dim and "
            "num_attention_heads does not divide hidden_size")
    if not windowed and raw.get("head_dim", hidden // heads) * heads != hidden:
        # `LLaMAConfig.head_size` would run it; a published file of the
        # dense or the latent block that says so is more likely a slip than
        # a model, and the benchmark's own tests hold this map to refusing it.
        raise ValueError(
            "head_dim * heads != hidden_size; only a file with layer_types "
            "may give a head size of its own")
    head_dim = raw.get("head_dim")
    if isinstance(head_dim, bool) or not isinstance(head_dim, (int, type(None))) or (
            head_dim is not None and (head_dim <= 0 or head_dim % 2)):
        raise ValueError(f"head_dim: {head_dim!r} is not an even size > 0")
    if raw["torch_dtype"] not in _PUBLISHED_DTYPES:
        raise ValueError(f"torch_dtype {raw['torch_dtype']!r} is not one the program serves in")
    if latent:
        for key, only in latent_fixed.items():
            if key in raw and raw[key] != only:
                raise ValueError(
                    f"{key}: {raw[key]!r} is not in the program; its "
                    f"latent-attention block computes {only!r} only"
                )
        if raw.get("qk_head_dim", raw["qk_nope_head_dim"] + raw["qk_rope_head_dim"]) != (
                raw["qk_nope_head_dim"] + raw["qk_rope_head_dim"]):
            raise ValueError("qk_head_dim != qk_nope_head_dim + qk_rope_head_dim")
        if raw["num_key_value_heads"] != heads:
            raise ValueError("num_key_value_heads != num_attention_heads under latent attention")
    extra = _latent_variant(raw) if mhc else {}
    if windowed:
        for key, only in _PUBLISHED_WINDOWED_FIXED.items():
            if key in raw and raw[key] != only:
                raise ValueError(
                    f"{key}: {raw[key]!r} is not in the program; its "
                    f"window-attention block computes {only!r} only"
                )
        extra.update(_window_layers(raw))
    if head_dim is not None and head_dim * heads != hidden:
        extra["head_size"] = head_dim
    return LLaMAConfig(
        **{ours: raw[theirs] for theirs, ours in fields.items()}, **extra,
        dtype=raw["torch_dtype"], param_dtype=raw["torch_dtype"],
        max_seq_len=max_seq_len, attn_impl=attn_impl,
    )


# ---------------------------------------------------------------------------
# Presets.  Sizes follow the published Meta architectures; these are
# architecture constants, not tuned values.
# ---------------------------------------------------------------------------

def tiny(**kw) -> LLaMAConfig:
    """Tiny config for unit tests (mirrors the reference's test config scale:
    /root/reference/jax_test.py:28-41)."""
    base = dict(
        vocab_size=256, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=64, rope_theta=10000.0,
        rms_norm_eps=1e-5, dtype="float32", param_dtype="float32",
    )
    base.update(kw)
    return LLaMAConfig(**base)


def llama2_7b(**kw) -> LLaMAConfig:
    base = dict(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=None,
        multiple_of=256, max_seq_len=4096, rope_theta=10000.0,
        rms_norm_eps=1e-5,
    )
    base.update(kw)
    return LLaMAConfig(**base)


def llama2_13b(**kw) -> LLaMAConfig:
    base = dict(
        vocab_size=32000, dim=5120, n_layers=40, n_heads=40, n_kv_heads=None,
        multiple_of=256, max_seq_len=4096, rope_theta=10000.0,
        rms_norm_eps=1e-5,
    )
    base.update(kw)
    return LLaMAConfig(**base)


def llama2_70b(**kw) -> LLaMAConfig:
    base = dict(
        vocab_size=32000, dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        multiple_of=4096, ffn_dim_multiplier=1.3, max_seq_len=4096,
        rope_theta=10000.0, rms_norm_eps=1e-5,
    )
    base.update(kw)
    return LLaMAConfig(**base)


def llama3_8b(**kw) -> LLaMAConfig:
    base = dict(
        vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        multiple_of=1024, ffn_dim_multiplier=1.3, max_seq_len=8192,
        rope_theta=500000.0, rms_norm_eps=1e-5,
    )
    base.update(kw)
    return LLaMAConfig(**base)


def llama3_70b(**kw) -> LLaMAConfig:
    base = dict(
        vocab_size=128256, dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        multiple_of=4096, ffn_dim_multiplier=1.3, max_seq_len=8192,
        rope_theta=500000.0, rms_norm_eps=1e-5,
    )
    base.update(kw)
    return LLaMAConfig(**base)


def llama3_1_8b(**kw) -> LLaMAConfig:
    base = dict(use_scaled_rope=True, max_seq_len=131072)
    base.update(kw)
    return llama3_8b(**base)


def llama3_1_70b(**kw) -> LLaMAConfig:
    base = dict(use_scaled_rope=True, max_seq_len=131072)
    base.update(kw)
    return llama3_70b(**base)


PRESETS = {
    "tiny": tiny,
    "llama2-7b": llama2_7b,
    "llama2-13b": llama2_13b,
    "llama2-70b": llama2_70b,
    "llama3-8b": llama3_8b,
    "llama3-70b": llama3_70b,
    "llama3.1-8b": llama3_1_8b,
    "llama3.1-70b": llama3_1_70b,
}


def get_config(name: str, **kw) -> LLaMAConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown config preset {name!r}; have {sorted(PRESETS)}")
    return PRESETS[name](**kw)
