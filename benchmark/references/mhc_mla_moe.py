"""Plain reference of one block: the deepseek_v3 decoder layer (latent
attention with a low-rank query and YaRN rope, routed and shared experts behind
leading dense layers) whose residual is FOUR streams mixed around every
attention and FFN by manifold-constrained hyper-connections (mHC; Xie et al.,
arXiv 2512.24880, on Zhu et al.'s hyper-connections, arXiv 2409.19606).  A
configuration file asks for it with `"reference": "mhc_mla_moe"`;
`benchmark/reference.py` loads it by that name and holds the served tokens to
`logits` under the two limits below.  Nothing here is imported from the program:
it reads the configuration FILE's keys and the program's parameter LAYOUT, and
none of its code.

Architecture (Xing4.0-29B-A4B, `model_type: xing4_0`, as published).  Sizes
C = `hidden_size`, n = `hc_mult`.  Token embedding e; X_0 = [e, e, e, e] ([n, C]);
per layer two units, attention then FFN; x_out = sum_i X_L[i]; final RMSNorm;
untied output head; no biases.

- One mHC unit around F (F_attn(u) = MLA(RMSNorm(u; attn_norm)), F_ffn(u) =
  FFN(RMSNorm(u; mlp_norm))), parameters phi [nC, n*n + 2n], b [n*n + 2n],
  alpha [3], everything float32:
      x' = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)             (no learned gain)
      m = x' phi                                               [n | n | n*n]
      H_pre  = sigmoid(alpha_pre m[:n] + b[:n])
      H_post = 2 sigmoid(alpha_post m[n:2n] + b[n:2n])
      A = clip(alpha_res mat(m[2n:]) + mat(b[2n:]), mhc_h_res_clamp_min, _max)
      M = exp(A); hc_sinkhorn_iters times: M /= colsum(M) + hc_eps; M /= rowsum(M) + hc_eps
      u = sum_i H_pre[i] X[i];  y = F(u);  X'[i] = sum_j M[i, j] X[j] + H_post[i] y
- Attention, in its DECOMPRESSED form (the served decode path computes the
  absorbed form; the same sum in another order).  h = RMSNorm(u);
  q = RMSNorm(h W_qa; q_a_norm) W_qb -> [H, nope | rope]; h W_kva -> [c_raw
  (kv_lora_rank) | k_rope_raw (rope)]; c = RMSNorm(c_raw; kv_norm); c W_kvb ->
  [H, nope | v].  Rotary on q_rope per head and on k_rope, ONE head shared by all
  H, with YaRN's frequencies (HF deepseek_v3 form): f_i = theta^(-2i/d),
  low = floor(d ln(L0 / (2 pi beta_fast)) / (2 ln theta)), high = ceil(d ln(L0 /
  (2 pi beta_slow)) / (2 ln theta)) clipped to [0, d - 1], ramp_i = clip((i - low) /
  (high - low), 0, 1), inv_freq_i = f_i / factor ramp_i + f_i (1 - ramp_i).
  `mscale` = `mscale_all_dim`, so cos / sin carry no factor and the score of head
  n is (q_nope_n . k_nope_n + q_rope_n . k_rope) (nope + rope)^(-1/2) m^2 with
  m = 0.1 mscale_all_dim ln(factor) + 1; causal softmax; out = concat(o_n) W_o.
- FFN of the first `first_k_dense_replace` layers: SwiGLU of width
  `intermediate_size`.  Every later layer: s = sigmoid(h W_g) over
  `n_routed_experts`; selected = top-`num_experts_per_tok` of s + b
  (`e_score_correction_bias`, selection only; `n_group` 1: a plain top-k);
  w = s[selected] / sum(s[selected]) * `routed_scaling_factor`; a plain loop over
  ALL experts, each applied to every token and weighted by w_e or zero; plus one
  shared SwiGLU of width `n_shared_experts` * `moe_intermediate_size`.
- `num_nextn_predict_layers`: the extra layer is a drafting / training head the
  next-token logits do not read; nothing of it is here (the HF deepseek_v3 loader
  drops it too).

Departures, each forced by where the weights come from.
- The weights are the program's own seeded tree, so this file reads its layout:
  `dense_layers` / `moe_layers`, each stacked on a leading layer axis, with `q_a`
  [L,D,rq], `q_a_norm` [L,rq], `q_b` [L,H,rq,nope+rope], `kv_a` [L,D,r+rope],
  `kv_norm` [L,r], `kv_b` [L,H,r,nope+v], `o` [L,H,v,D], `hc_attn` / `hc_ffn` =
  {`phi` [L,nC,n*n+2n], `b`, `alpha`}, `gate_up` [L,2,D,F], `down` [L,F,D],
  `router` [L,D,E], `router_bias` [L,E], `experts_gate_up` [L,E,D,2Fe] (gate | up),
  `experts_down` [L,E,Fe,D], `shared_gate_up` [L,2,D,Fs], `shared_down` [L,Fs,D].
- Where the streams begin and end is not a key of the row: n copies of the
  embedding, and their sum (hyper-connections' own convention).
- Rotary pairing: column i of a rope part pairs with column i + rope/2 (the
  program permutes the published adjacent pairs once at load; with seeded
  weights a column permutation changes nothing, so both sides pair by halves).
- The router runs in float32 here AND in the program, as for `mla_moe`.
- Weights are upcast from the served bfloat16 to float32 a projection or an
  expert at a time, the batch is walked a sequence at a time, and each
  sequence's logits go to the host, so that the reference fits beside the
  10.6 GB the served model and its pool hold on the chip.

`FAULTS`: five wrong references, one a mechanism, for `benchmark/hc_control.py`
(`logits(..., fault=)`): `sinkhorn_1` (one normalisation round in place of
`hc_sinkhorn_iters`), `h_res_identity` (M = I: the streams never mix),
`h_post_unscaled` (sigmoid without its factor 2), `no_yarn_mscale` (the softmax
scale without m^2), `no_q_a_norm` (q = h W_qa W_qb).  Limits that one of them
passes gate nothing of that mechanism.

The limits.  Set as PERF.md section 3 says, from readings on the v5e (my chip
runs, PR 51; PERF.md section 6 lists the seeds).  With these seeded weights
logits are about N(0, 1.2^2), the largest of 131,072 about 5.4 above the mean, and
a served reply of 64 tokens holds 64 distinct tokens (no collapse).
- The sound bfloat16 system, over the first check of 4 fresh + 4 re-asked prompts
  of 4,096 tokens x 64 served tokens (512 positions), nine seeds: a run's mean
  deficit 0.030-0.060 (0.0299, 0.0341, 0.0397, 0.0432, 0.0450, 0.0467, 0.0485,
  0.0520, 0.0601), its largest 1.50-3.60; one SEQUENCE's mean 0.006-0.093 inside
  one run, so the check was doubled to 8 + 8 prompts (1,024 positions): three
  more seeds read 0.0481 (largest 2.43), 0.0466 (2.25) and 0.0448 (1.92).
- The same served tokens held to THIS reference with one part wrong (`FAULTS`,
  `benchmark/hc_control.py`), seeds 2000000011 / 3000000019, mean (largest):
  `sinkhorn_1` 0.125 (1.93) / 0.198 (2.98); `h_res_identity` 0.483 (3.95) /
  0.869 (4.29); `h_post_unscaled` 1.117 (4.94) / 1.529 (4.89); `no_yarn_mscale`
  1.061 (4.27) / 1.518 (4.75); `no_q_a_norm` 0.462 (3.85) / 0.765 (4.09) at 512
  positions; at 1,024 (the final check, `hc_control` exit 0 on both seeds):
  `sinkhorn_1` 0.147 (1.96) / 0.194 (3.57); `h_res_identity` 0.527 (3.95) / 0.870
  (4.19); `h_post_unscaled` 1.183 (5.85) / 1.590 (4.91); `no_yarn_mscale` 1.125
  (5.29) / 1.497 (5.49); `no_q_a_norm` 0.519 (4.17) / 0.757 (4.09), beside a sound
  0.0481 / 0.0466.
- float8_e4m3 weights (3 mantissa bits, a power-of-two scale a tensor;
  `benchmark/float8_control.py`), seed 2246822519, 1,024 positions: mean 0.430,
  largest 3.81, beside the sound 0.0448 (1.92); exit 0.  (With the first draw of
  the gates, 512 positions, seed 3000000019: 0.527 beside 0.074.)
`MEAN_DEFICIT` 0.09 is the gate: 1.5 times the largest sound mean of twelve seeds
(over four of their standard deviations, 0.009, above their mean, 0.045) and 1.4-1.6
times below the smallest reading of the subtlest wrong reference (one Sinkhorn round in
place of 20: H_res still row-stochastic and within ~0.2 of doubly stochastic);
every other wrong reference and float8 read five to thirty times the sound mean.
`MAX_DEFICIT` 6.0 does NOT gate precision or the units (a sound run's largest
deficit reached 3.60 in nine and a wrong unit's are no larger than that): it
refuses what makes single tokens arbitrary for the reference, about 5.4 under the
maximum, which a wrong rope, mask, block table, latent norm or dropped expert does
at most positions, failing both.
Why these seeded gates (`ops/mhc.init_unit`: a route — a unit reads mostly one
stream and feeds mostly another — the same for every seed).  H_res is doubly
stochastic, so it preserves the SUM of the streams, which is also the model's
output: with even gates a wrong H_res is read by nothing downstream.  Gates drawn
N(0, 0.5^2), H_res near the identity (the first draw): sound 0.032 / 0.074
(largest 1.36 / 4.50), `sinkhorn_1` 0.051 and `h_res_identity` 0.108 on seed
2000000011 - 1.6 and 3.4 times the sound reading, under any limit a sound run
passes.  Gates drawn N(0, 1.5^2): `sinkhorn_1` 0.031 / 0.266 and `h_res_identity`
0.075 / 0.960 - 2-3 and 7 times their seed's sound reading - but the sound readings
themselves 0.0106 and 0.131, twelve times apart: a lottery of 24 gate biases a
unit.  As a route: the readings above.  A trained model has its own gates; these
are seeded weights, and the check says what a seeded check can.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

MAX_DEFICIT = 6.0
MEAN_DEFICIT = 0.09
FAULTS = ("sinkhorn_1", "h_res_identity", "h_post_unscaled", "no_yarn_mscale", "no_q_a_norm")


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * scale


def yarn_inv_freq(d: int, theta: float, scaling: Dict[str, Any]):
    """[d / 2] inverse frequencies (the docstring's rule), float64 numpy."""
    import numpy as np

    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    L0 = scaling["original_max_position_embeddings"]

    def turns(beta):
        return d * math.log(L0 / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns(scaling["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / ((high - low) or 0.001), 0.0, 1.0)
    return f / scaling["factor"] * ramp + f * (1.0 - ramp)


def softmax_scale(cfg: Dict[str, Any], fault: Optional[str] = None) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg["rope_scaling"]
    if fault == "no_yarn_mscale" or scaling["factor"] <= 1:
        return scale
    return scale * (0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0) ** 2


def _rope(x, inv_freq):
    """x [T, H, d]; pair i is (x[i], x[i + d/2]), angle t * inv_freq[i]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _swiglu(h, gate_up, down):
    import jax

    return (jax.nn.silu(h @ gate_up[0]) * (h @ gate_up[1])) @ down


def coefficients(X, hp, cfg: Dict[str, Any], fault: Optional[str] = None):
    """X [T, n, C] float32 -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T, n, _ = X.shape
    eps = cfg["hc_eps"]
    x = X.reshape(T, -1)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    m = x @ hp["phi"].astype(f32)
    b, alpha = hp["b"].astype(f32), hp["alpha"].astype(f32)
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    if fault != "h_post_unscaled":
        h_post = 2.0 * h_post
    a = jnp.clip((alpha[2] * m[:, 2 * n:] + b[2 * n:]).reshape(T, n, n),
                 cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    M = jnp.exp(a)
    for _ in range(1 if fault == "sinkhorn_1" else cfg["hc_sinkhorn_iters"]):
        M = M / (jnp.sum(M, axis=1, keepdims=True) + eps)     # columns: over i
        M = M / (jnp.sum(M, axis=2, keepdims=True) + eps)     # rows: over j
    if fault == "h_res_identity":
        M = jnp.broadcast_to(jnp.eye(n, dtype=f32), M.shape)
    return h_pre, h_post, M


def _unit(X, hp, inner, cfg, fault):
    import jax.numpy as jnp

    h_pre, h_post, M = coefficients(X, hp, cfg, fault)
    y = inner(jnp.einsum("ti,tic->tc", h_pre, X))
    return jnp.einsum("tij,tjc->tic", M, X) + h_post[:, :, None] * y[:, None, :]


def _attention(u, lp, cfg: Dict[str, Any], fault):
    """u [T, D] float32 -> the attention output [T, D], one head at a time."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T = u.shape[0]
    r, dn, dr = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    eps = cfg["rms_norm_eps"]
    inv_freq = yarn_inv_freq(dr, float(cfg["rope_theta"]), cfg["rope_scaling"])
    scale = softmax_scale(cfg, fault)
    h = _rms_norm(u, lp["attn_norm"].astype(f32), eps)
    q_a = h @ lp["q_a"].astype(f32)
    if fault != "no_q_a_norm":
        q_a = _rms_norm(q_a, lp["q_a_norm"].astype(f32), eps)
    kva = h @ lp["kv_a"].astype(f32)                                   # [T, r + rope]
    c = _rms_norm(kva[:, :r], lp["kv_norm"].astype(f32), eps)
    k_rope = _rope(kva[:, None, r:], inv_freq)[:, 0]                   # [T, rope]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(acc, w):
        wq, wkvb, wo = (a.astype(f32) for a in w)                      # [rq,nope+rope] [r,nope+v] [v,D]
        q = q_a @ wq
        q_rope = _rope(q[:, None, dn:], inv_freq)[:, 0]
        kv = c @ wkvb
        s = (q[:, :dn] @ kv[:, :dn].T + q_rope @ k_rope.T) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return acc + (p @ kv[:, dn:]) @ wo, None

    out, _ = jax.lax.scan(head, jnp.zeros_like(u), (lp["q_b"], lp["kv_b"], lp["o"]))
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


def logits(params, tokens, cfg: Dict[str, Any], first: int, fault: Optional[str] = None):
    """Reference logits [B, T - first, V] (numpy) at positions first..T-1 of
    `tokens` [B, T] (all rows full length, no padding).  `fault`: one of
    `FAULTS`, a wrong reference for the control."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; have {FAULTS}")
    f32 = jnp.float32
    eps = cfg["rms_norm_eps"]
    n_dense, n = cfg["first_k_dense_replace"], cfg["hc_mult"]

    def pick(tree, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)

    @jax.jit
    def embed(table, toks):
        e = jnp.take(table, toks, axis=0).astype(f32)
        return jnp.broadcast_to(e[:, None, :], (e.shape[0], n, e.shape[1]))

    @jax.jit
    def attention(X, layers, i):
        lp = pick(layers, i)
        return _unit(X, lp["hc_attn"], lambda u: _attention(u, lp, cfg, fault), cfg, fault)

    def ffn(X, lp, inner):
        return _unit(
            X, lp["hc_ffn"],
            lambda u: inner(_rms_norm(u, lp["mlp_norm"].astype(f32), eps)), cfg, fault)

    @jax.jit
    def ffn_dense(X, layers, i):
        lp = pick(layers, i)
        return ffn(X, lp, lambda h: _swiglu(h, lp["gate_up"].astype(f32), lp["down"].astype(f32)))

    @jax.jit
    def ffn_experts(X, layers, i):
        lp = pick(layers, i)
        return ffn(X, lp, functools.partial(_experts, lp=lp, cfg=cfg))

    @jax.jit
    def head(X, norm, w):
        x = jnp.sum(X[first:], axis=1)
        return _rms_norm(x, norm.astype(f32), eps) @ w.astype(f32)

    out = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            X = embed(params["embed"]["embedding"], tokens[b])
            for i in range(cfg["num_hidden_layers"]):
                if i < n_dense:
                    X = attention(X, params["dense_layers"], jnp.int32(i))
                    X = ffn_dense(X, params["dense_layers"], jnp.int32(i))
                else:
                    X = attention(X, params["moe_layers"], jnp.int32(i - n_dense))
                    X = ffn_experts(X, params["moe_layers"], jnp.int32(i - n_dense))
            out.append(np.asarray(head(X, params["final_norm"], params["lm_head"])))
    return np.stack(out)
