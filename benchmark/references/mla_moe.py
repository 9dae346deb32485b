"""Plain reference of one block: the deepseek_v3 decoder layer, latent
attention (MLA) with routed and shared experts behind leading dense layers.  A
configuration file asks for it with `"reference": "mla_moe"`;
`benchmark/reference.py` loads it by that name and holds the served tokens to
`logits` under the two limits below.  Nothing here is imported from the
program: it reads the configuration FILE's keys and the program's parameter
LAYOUT, and none of its code.

Architecture (kanana-2-30b-a3b-instruct-2601, `model_type: deepseek_v3`, as
published): token embedding; per layer x += Attn(RMSNorm(x)) then
x += FFN(RMSNorm(x)); final RMSNorm; untied output head; no biases.

- Attention, in its DECOMPRESSED form (the served decode path computes the
  absorbed form; they are the same sum in another order).  h = RMSNorm(x);
  q = h W_q -> [H, nope | rope]; h W_kva -> [c_raw (kv_lora_rank) | k_rope_raw
  (rope)]; c = RMSNorm(c_raw; kv_a_layernorm); c W_kvb -> [H, nope | v] = k_nope
  and v per head.  Rotary on q_rope per head and on k_rope, ONE head shared by
  all H.  Score of head n: (q_nope_n . k_nope_n + q_rope_n . k_rope) /
  sqrt(nope + rope), causal softmax, o_n = sum p v_n, out = concat(o_n) W_o.  No
  softmax-scale correction (`rope_scaling` null).
- FFN of the first `first_k_dense_replace` layers: SwiGLU of width
  `intermediate_size`.
- FFN of every later layer: s = sigmoid(h W_g) over `n_routed_experts`;
  selected = top-`num_experts_per_tok` of s + b (`e_score_correction_bias`,
  selection only; with `n_group` 1 the grouped selection is a plain top-k);
  w = s[selected] / sum(s[selected]) * `routed_scaling_factor`;
  y = sum_e w_e down_e(silu(gate_e h) * up_e h) + shared(h), `shared` one SwiGLU
  of width `n_shared_experts` * `moe_intermediate_size`.  Here: a plain loop over
  ALL experts, each applied to every token and weighted by w_e or by zero.

Departures, each forced by where the weights come from.
- The weights are the program's own seeded tree, so this file reads its layout:
  `dense_layers` / `moe_layers`, each stacked on a leading layer axis, with
  `q` [L,H,D,nope+rope], `kv_a` [L,D,r+rope], `kv_norm` [L,r], `kv_b`
  [L,H,r,nope+v], `o` [L,H,v,D], `gate_up` [L,2,D,F], `down` [L,F,D], `router`
  [L,D,E], `router_bias` [L,E], `experts_gate_up` [L,E,D,2Fe] (gate | up),
  `experts_down` [L,E,Fe,D], `shared_gate_up` [L,2,D,Fs], `shared_down` [L,Fs,D].
- Rotary pairing: column i of a rope part pairs with column i + rope/2, angle
  t * theta^(-2i/rope).  `rope_interleave: true` says the published checkpoint
  stores the pair as ADJACENT columns (2i, 2i+1); the program permutes the rope
  columns of W_q and W_kva once at load (as it does for Meta's checkpoints) and
  rotates half against half, which gives the same scores.  With seeded weights
  a column permutation changes nothing, so both sides use the half pairing.
- The router runs in float32 here AND in the program (`ops/moe.py`): a bfloat16
  sigmoid would pick the sixth against the seventh expert by rounding.
- Weights are upcast from the served bfloat16 to float32 a projection or an
  expert at a time, and the batch is walked a sequence at a time, so that the
  reference fits beside the 11.5 GB the served model holds on the chip.

The limits.  Set as PERF.md section 3 says, from readings on the v5e (my chip
runs, PR 27; PERF.md section 6 lists the seeds), each over the check's 256
positions (2 fresh + 2 re-asked prompts of 8,192 tokens, 64 served tokens each).
With these seeded weights (every projection N(0, 0.02^2), the family's
`initializer_range`) logits are about N(0, 0.9^2) and the largest of 128,256 is
about 4.
- The sound bfloat16 system, 20 readings on 9 seeds: a run's mean deficit
  0.037-0.071, its largest deficit 0.93-1.87.
- The same served tokens held to THIS reference computed with float8_e4m3
  weights (3 mantissa bits, a power-of-two scale a tensor), two seeds: mean 0.343
  and 0.389, largest 1.55 and 1.96.
`MEAN_DEFICIT` 0.15 lies twice above the largest sound mean and twice below the
smallest float8 one: it is the gate on precision.  `MAX_DEFICIT` 3.0 is 1.6
times the largest sound reading and does NOT refuse float8 (its largest
deficits are the sound system's): it refuses what makes single tokens arbitrary
for the reference (about 4 under the maximum), which a wrong rope, mask, block
table, latent norm, routing weight or dropped expert does at most positions,
failing both.
Why the sound readings are fifty times the dense block's (`dense_gqa`: mean
0.0003-0.0010).  The served hidden state is bfloat16; where the float32 router's
sixth and seventh scores lie within its rounding, the served layer takes the
other expert, and from there the two hidden states differ by one whole expert's
output.  Emulated with this file's own layer functions (hidden state rounded to
bfloat16 after every sub-block; 2,048 tokens), the share of tokens whose six
experts differ from float32's grows by layer: 5, 12, 19, 29, 38, 46, 55 % over the
seven expert layers.  How far one flip moves the logits depends on how large a
routed expert's output is beside the rest of the residual stream, which is the
seeded weights' scale: under the repo's fan-in scaling (a routed expert's output
twice as large) the sound system read mean 0.22-0.31 and largest 2.9-4.4 over
five readings, like a fault; under the family's own initializer it reads as
above.  A trained router separates its experts further; these are seeded weights.
"""

from __future__ import annotations

import math
from typing import Any, Dict

MAX_DEFICIT = 3.0
MEAN_DEFICIT = 0.15


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * scale


def _rope(x, theta: float):
    """x [T, H, d]; pair i is (x[i], x[i + d/2]), angle t * theta^(-2i/d)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _swiglu(h, gate_up, down):
    import jax

    return (jax.nn.silu(h @ gate_up[0]) * (h @ gate_up[1])) @ down


def _attention(x, lp, cfg: Dict[str, Any]):
    """x [T, D] float32 -> the attention output [T, D], one head at a time."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T = x.shape[0]
    r, dn, dr = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    h = _rms_norm(x, lp["attn_norm"].astype(f32), eps)
    kva = h @ lp["kv_a"].astype(f32)                                   # [T, r + rope]
    c = _rms_norm(kva[:, :r], lp["kv_norm"].astype(f32), eps)
    k_rope = _rope(kva[:, None, r:], theta)[:, 0]                      # [T, rope]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(acc, w):
        wq, wkvb, wo = (a.astype(f32) for a in w)                      # [D,nope+rope] [r,nope+v] [v,D]
        q = h @ wq
        q_rope = _rope(q[:, None, dn:], theta)[:, 0]
        kv = c @ wkvb
        s = (q[:, :dn] @ kv[:, :dn].T + q_rope @ k_rope.T) / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return acc + (p @ kv[:, dn:]) @ wo, None

    out, _ = jax.lax.scan(head, jnp.zeros_like(x), (lp["q"], lp["kv_b"], lp["o"]))
    return out


def _experts(h, lp, cfg: Dict[str, Any]):
    """Routed experts [T, D] by a plain loop over every expert, plus shared."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    k = cfg["num_experts_per_tok"]
    Fe = cfg["moe_intermediate_size"]
    s = jax.nn.sigmoid(h @ lp["router"].astype(f32))                   # [T, E]
    _, sel = jax.lax.top_k(s + lp["router_bias"].astype(f32), k)
    picked = jnp.take_along_axis(s, sel, axis=1)
    picked = picked / jnp.sum(picked, axis=1, keepdims=True) * cfg["routed_scaling_factor"]
    w = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], sel].set(picked)

    def expert(acc, xs):
        gate_up, down, w_e = xs
        gu = h @ gate_up.astype(f32)
        y = (jax.nn.silu(gu[:, :Fe]) * gu[:, Fe:]) @ down.astype(f32)
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h), (lp["experts_gate_up"], lp["experts_down"], w.T))
    if cfg["n_shared_experts"]:
        out = out + _swiglu(h, lp["shared_gate_up"].astype(f32), lp["shared_down"].astype(f32))
    return out


def logits(params, tokens, cfg: Dict[str, Any], first: int):
    """Reference logits [B, T - first, V] at positions first..T-1 of
    `tokens` [B, T] (all rows full length, no padding)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = cfg["rms_norm_eps"]
    n_dense = cfg["first_k_dense_replace"]

    def pick(tree, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)

    @jax.jit
    def embed(table, toks):
        return jnp.take(table, toks, axis=0).astype(f32)

    @jax.jit
    def attention(x, layers, i):
        return x + _attention(x, pick(layers, i), cfg)

    @jax.jit
    def ffn_dense(x, layers, i):
        lp = pick(layers, i)
        h = _rms_norm(x, lp["mlp_norm"].astype(f32), eps)
        return x + _swiglu(h, lp["gate_up"].astype(f32), lp["down"].astype(f32))

    @jax.jit
    def ffn_experts(x, layers, i):
        lp = pick(layers, i)
        return x + _experts(_rms_norm(x, lp["mlp_norm"].astype(f32), eps), lp, cfg)

    @jax.jit
    def head(x, norm, w):
        return _rms_norm(x[first:], norm.astype(f32), eps) @ w.astype(f32)

    out = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x = embed(params["embed"]["embedding"], tokens[b])
            for i in range(cfg["num_hidden_layers"]):
                if i < n_dense:
                    x = attention(x, params["dense_layers"], jnp.int32(i))
                    x = ffn_dense(x, params["dense_layers"], jnp.int32(i))
                else:
                    x = attention(x, params["moe_layers"], jnp.int32(i - n_dense))
                    x = ffn_experts(x, params["moe_layers"], jnp.int32(i - n_dense))
            out.append(head(x, params["final_norm"], params["lm_head"]))
        return jnp.stack(out)
