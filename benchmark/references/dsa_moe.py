"""Plain reference of one block: rotary GQA attention under a learned key
selection (a DeepSeek-Sparse-Attention indexer: each query attends the 2,048
keys its indexer scores highest), over softmax-routed experts in every layer.
A configuration file asks for it with `"reference": "dsa_moe"`;
`benchmark/reference.py` loads it by that name and holds the served tokens to
`logits` under the two limits below.  Nothing here is imported from the
program: it reads the configuration FILE's keys and the program's parameter
LAYOUT, and none of its code.

Architecture (Keye-VL-2.0-30B-A3B's language model, `model_type: KeyeVL2`).
From the catalog row's `config` keys and its `described_as` ("GQA 32Q/4KV with
DeepSeek-Sparse-Attention indexer (sa_config topk 2048)", "128 experts, top-8,
0 shared"); what no key states is marked "(assumed)" below and listed under
`assumed` in the configuration file, each with its ground.  Every layer is
this one (`mlp_only_layers` [], `decoder_sparse_step` 1):

    x0 = E[tokens]
    a = RMSNorm(x)                                       eps `rms_norm_eps`
    q = RMSNorm_h(a Wq) [`num_attention_heads`, `head_dim`]
    k = RMSNorm_h(a Wk) [`num_key_value_heads`, `head_dim`]     v = a Wv
        per head over `head_dim`, one learned weight each (assumed: the
        config's keys are Qwen3-MoE's, whose attention has it)
    q, k = rope(q, k; `rope_theta`)      `rope_scaling.mrope_section` splits the
        rotary pairs over three position streams; with text positions the
        three are equal and this is plain rope
    indexer (`sa_config`; inputs from the layer's normed input `a`: assumed,
      Keye has no low-rank query latent, DeepSeek-V3.2's source):
      qI = a WqI [`indexer_num_heads`, `indexer_head_dim`]
      kI = LayerNorm(a WkI) [`indexer_head_dim`]     ONE key head
           (`indexer_num_kv_heads` 1); weight and bias, eps `rms_norm_eps`
           (assumed: DeepSeek-V3.2-Exp's published indexer)
      w  = a Ww [`indexer_num_heads`]
      rope on the leading half of qI and kI (assumed: the same source)
      I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          s <= t
      S_t = the min(`topk`, t + 1) keys of largest I[t, .]; equal scores go
            to the lower position
      (the published positive factors `n_heads^-1/2` and `d^-1/2` on I do not
      change a top-k and are left out; `q_chunk_size` / `kv_chunk_size` are
      read as the published kernels' tiling, no part of the equations: assumed)
    o_t = softmax_{s in S_t}(q_t . k_s / sqrt(`head_dim`)) v_s     GQA
    x = x + o Wo
    m = RMSNorm(x)
    p = softmax(m Wr) in float32 over `num_experts`; top-`num_experts_per_tok`;
        w_e = p_e / sum_chosen p   (`norm_topk_prob` true)
    x = x + sum_e w_e * Wdown_e(silu(Wgate_e m) * Wup_e m)
    logits = RMSNorm_final(x) W_head              untied, no biases but kI's

The selection is a dense boolean [T, T] mask built from the FULL score matrix
and a stable sort; the experts are a plain loop over ALL of them, each applied
to every token and weighted by w_e or by zero.  No cache, no kernel, nothing
skipped.  The vision tower is not here: the catalog gives it no widths.

`logits(..., select=)` also computes the two CONTROLS of the limits, which vary
this reference and never the program: `"dense"` attends every causal key, and
`"newest"` the newest `topk` keys in place of the top `topk`
(`benchmark/selection_control.py`).

Departures, each forced by where the weights come from.
- The weights are the program's own seeded tree, so this file reads its layout:
  `moe_layers`, stacked on a leading layer axis, with `qkv` [L,KVH,G+2,D,hd]
  (slots q_0..q_{G-1}, k, v a KV head; query head h = kvh * G + g), `q_norm` /
  `k_norm` [L,hd], `o` [L,H,hd,D], `attn_norm` / `mlp_norm` [L,D], `index_q`
  [L,D,Hi,di], `index_k` [L,D,di], `index_w` [L,D,Hi], `index_k_norm` /
  `index_k_bias` [L,di], `router` [L,D,E], `experts_gate_up` [L,E,D,2Fe]
  (gate | up), `experts_down` [L,E,Fe,D].
- The seeded initialisation (`models/dsa_moe.init_params`): every projection
  and the embedding N(0, 0.02^2), every norm weight one, the index key's
  LayerNorm bias zero: the plain one.  With it the attention logits of seeded
  projections are about N(0, 1), and which keys are attended still shows in
  the logits: both controls below fail.  q / k norm weights above one (a
  sharper softmax) were tried to make them fail harder, and do the opposite:
  bfloat16's rounding of sharper logits raises the sound system's own deficits
  faster than the controls' (my chip runs, PR 48, on the first session's check
  of 2 prompts: at 1.25 a sound mean of 0.0225 beside float8 0.080 and dense
  0.245; at 1.5 sound means 0.027-0.092 on four readings beside float8 0.161
  and dense 0.229).
- Rotary pairing: column i pairs with column i + d/2 of the rotated width, as
  everywhere in the program; with seeded weights a column permutation changes
  nothing.
- Weights are upcast from the served bfloat16 a projection or an expert at a
  time, attention runs a KV head's query group at a time and `_Q_BLOCK` queries
  at a time, the batch is walked a sequence at a time and each sequence's
  logits go to the host before the next, so that the reference of 32 prompts
  of 8k tokens fits beside the 12 GB the served model holds.

The limits.  Set as PERF.md section 3 says, from readings on the v5e (my chip
runs, PR 48; PERF.md section 6 lists every seed), each over the check's 2,048
positions: 16 fresh + 16 re-asked prompts of 8,192 tokens (four times `topk`;
a re-ask shares 7,680 tokens with its partner), 64 served tokens each, so
prefill under the mask, the index-key plane of a cached prefix, the re-ask's
chunk over it and sparse decode all stand in them.  With these seeded weights
logits are about N(0, 0.9^2) and the largest of 151,936 is about 4.

Why 32 sequences and not the four the other blocks' cells check.  A greedy
reply of seeded weights collapses: the 64 (or 256) served tokens of a sequence
are 1 to 12 distinct ones (median 2), because the attention output over
thousands of random-token keys is nearly one vector a sequence and outweighs
the current token's embedding.  So a SEQUENCE is one reading, not 64: its
positions share their two best tokens and agree or disagree together (the
variance between sequence means is 7-12 times what independent positions
would give; 256 served tokens a sequence read what 64 do), a re-ask repeats
its partner's reading, and about one sequence in five reads 0 under every
reference (one token, far ahead).  The first session's check of 2 + 2
sequences read sound means of 0.0000-0.0169 on 13 seeds, float8 0.0237-0.0731
and `dense` 0.0264-0.1437 on four: no fixed limit separated them.  Over 16
pairs the heavy tail averages out:
- The sound bfloat16 system, 7 seeds (2000000011, 3000000019, 2246822519,
  1357924681, 2468013579, and 4011223343, 2876543201 from `git archive
  $(git write-tree)`): a run's mean deficit 0.0033, 0.0037, 0.0037, 0.0042,
  0.0044, 0.0048 and 0.0057; its largest deficit 0.246-0.311; the served token
  is the reference's argmax at 89-93 % of positions.  (The exploration that sized the check, 8
  pairs x 256 tokens on the first two seeds: 0.0036 and 0.0042, largest 0.26
  and 0.46 among 4,096 positions.)
- The same served tokens held to THIS reference computed with float8_e4m3
  weights (`benchmark/float8_control.py`), three seeds: mean 0.0193, 0.0317 and
  0.0319 (5.8, 5.6 and 8.6 times the sound reading of the same seed), largest
  0.49-0.65.  (8 pairs: 0.0238 and 0.0258.)
- The same served tokens held to this reference under the two CONTROLS of the
  selection (`benchmark/selection_control.py`): `dense` (every causal key),
  three seeds, mean 0.0458, 0.0605 and 0.1032, largest 0.57-0.82 (8 pairs:
  0.0519 and 0.0551); `newest` (the newest 2,048 keys), one seed, mean 3.91,
  largest 6.97, no token agreeing (2 pairs, four seeds: 3.55-4.77, 4.2-5.8).
`MEAN_DEFICIT` 0.01 is the gate on precision and on the selection: it lies 1.8
times above the largest sound mean and 1.9 times below the smallest float8
one (4.6 below the smallest `dense` one), and the smallest float8 reading of
any seed is 3.4 times the largest sound reading of any seed.  The room is the
afmoe block's (2.2 and 2.9) and not more because a pair's reading is
heavy-tailed whatever the count: halving either margin again takes four times
the prompts, and the check is 175 s of set-up as it is.
`MAX_DEFICIT` 1.5 refuses what makes single tokens arbitrary for the reference
(about 4 under the maximum), which `newest` does at every position (as a wrong
rope, norm, block table, routing weight or dropped expert would), failing
both limits.  It lies 4.8 times above the largest sound reading of 2,048
positions (3.3 above the 0.46 seen once among 4,096: a single deficit is an
extreme value and grows with the count, which is why it is not the first
session's 0.6) and 4.6 times below `newest`'s; float8's and `dense`'s largest
pass it, as float8 passes the other expert blocks' (PERF.md section 3): they
fail by the mean.  Why `dense` fails by so much less than `newest`: the 2,048
keys a seeded indexer picks of 8,192 are a fair sample of them, and a flat
softmax over a fair sample is close to the softmax over all; the newest 2,048
are not a fair sample of positions under rope.  A trained indexer picks the
keys that carry weight; these are seeded weights.
"""

from __future__ import annotations

import math
from typing import Any, Dict

MAX_DEFICIT = 1.5
MEAN_DEFICIT = 0.01

_Q_BLOCK = 512    # queries ranked and attended at a time (memory, not mathematics)
SELECTIONS = ("topk", "dense", "newest")


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * scale


def _layer_norm(x, scale, bias, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rope(x, theta: float, width: int):
    """x [T, H, d]: rotate the leading `width` columns; pair i is
    (x[i], x[i + width/2]), angle t * theta^(-2i/width)."""
    import jax.numpy as jnp

    inv = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : width // 2], x[..., width // 2: width]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., width:]], axis=-1)


def selection(a, lp, cfg: Dict[str, Any], select: str = "topk"):
    """[T, T] bool: query t (row) attends key s (column), from the layer's
    normed input a [T, D] float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T = a.shape[0]
    sa = cfg["sa_config"]
    topk, di = sa["topk"], sa["indexer_head_dim"]
    t = jnp.arange(T)[:, None]
    s = jnp.arange(T)[None, :]
    causal = s <= t
    if select == "dense":
        return causal
    if select == "newest":
        return causal & (t - s < topk)
    theta, eps = float(cfg["rope_theta"]), cfg["rms_norm_eps"]
    qi = _rope(jnp.einsum("td,dhk->thk", a, lp["index_q"].astype(f32)), theta, di // 2)
    ki = _layer_norm(a @ lp["index_k"].astype(f32), lp["index_k_norm"].astype(f32),
                     lp["index_k_bias"].astype(f32), eps)
    ki = _rope(ki[:, None, :], theta, di // 2)[:, 0]
    w = a @ lp["index_w"].astype(f32)                                   # [T, Hi]
    rows = []
    for b in range(0, T, _Q_BLOCK):          # the FULL matrix, a block of rows at a time
        seen = causal[b:b + _Q_BLOCK]
        score = jnp.einsum(
            "th,ths->ts", w[b:b + _Q_BLOCK],
            jax.nn.relu(jnp.einsum("thk,sk->ths", qi[b:b + _Q_BLOCK], ki)))
        score = jnp.where(seen, score, -jnp.inf)
        # A stable sort by descending score keeps the lower position first
        # among equals; a key's rank is its place in that order.
        order = jnp.argsort(-score, axis=1, stable=True)
        n = score.shape[0]
        rank = jnp.zeros((n, T), jnp.int32).at[jnp.arange(n)[:, None], order].set(
            jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (n, T)))
        rows.append(seen & (rank < topk))
    return jnp.concatenate(rows, axis=0)


def _attention(x, lp, cfg: Dict[str, Any], select: str):
    """x [T, D] float32 -> the attention output [T, D]."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T = x.shape[0]
    H, KVH, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    G = H // KVH
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    a = _rms_norm(x, lp["attn_norm"].astype(f32), eps)
    mask = selection(a, lp, cfg, select)
    qn, kn = lp["q_norm"].astype(f32), lp["k_norm"].astype(f32)

    def kv_head(acc, w):
        wqkv, wo = (t.astype(f32) for t in w)            # [G+2,D,hd] [G,hd,D]
        q = jnp.einsum("td,gdk->tgk", a, wqkv[:G])       # [T, G, hd]
        k, v = a @ wqkv[G], a @ wqkv[G + 1]              # [T, hd]
        q = _rope(_rms_norm(q, qn, eps), theta, hd)
        k = _rope(_rms_norm(k, kn, eps)[:, None], theta, hd)[:, 0]
        outs = []
        for s in range(0, T, _Q_BLOCK):
            sc = jnp.einsum("tgk,sk->gts", q[s:s + _Q_BLOCK], k) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(mask[None, s:s + _Q_BLOCK], sc, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("gts,sk->tgk", p, v))
        return acc + jnp.einsum("tgk,gkd->td", jnp.concatenate(outs, axis=0), wo), None

    out, _ = jax.lax.scan(
        kv_head, jnp.zeros_like(x),
        (lp["qkv"], lp["o"].reshape((KVH, G) + lp["o"].shape[1:])))
    return out


def route(h, router, cfg: Dict[str, Any]):
    """[T, E] float32: the weight of each expert for each token, zero for
    the experts a token does not go to."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
    _, sel = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(p, sel, axis=1)
    picked = picked / jnp.sum(picked, axis=1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(h.shape[0])[:, None], sel].set(picked)


def _experts(h, lp, cfg: Dict[str, Any]):
    """Routed experts [T, D] by a plain loop over every expert."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    Fe = cfg["moe_intermediate_size"]
    w = route(h, lp["router"], cfg)

    def expert(acc, xs):
        gate_up, down, w_e = xs
        gu = h @ gate_up.astype(f32)
        y = (jax.nn.silu(gu[:, :Fe]) * gu[:, Fe:]) @ down.astype(f32)
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h), (lp["experts_gate_up"], lp["experts_down"], w.T))
    return out


def logits(params, tokens, cfg: Dict[str, Any], first: int, select: str = "topk"):
    """Reference logits [B, T - first, V] at positions first..T-1 of
    `tokens` [B, T] (all rows full length, no padding).  `select`: which
    keys a query attends, the model's `"topk"` or a control (`SELECTIONS`)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    if select not in SELECTIONS:
        raise ValueError(f"select: {select!r} is not one of {SELECTIONS}")
    f32 = jnp.float32
    eps = cfg["rms_norm_eps"]
    layers = params["moe_layers"]

    def pick(tree, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)

    @jax.jit
    def embed(table, toks):
        return jnp.take(table, toks, axis=0).astype(f32)

    @functools.partial(jax.jit, static_argnames="select")
    def attention(x, layers, i, select):
        return x + _attention(x, pick(layers, i), cfg, select)

    @jax.jit
    def ffn(x, layers, i):
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
                x = attention(x, layers, jnp.int32(i), select=select)
                x = ffn(x, layers, jnp.int32(i))
            # To the host a sequence at a time: the chip holds one
            # sequence's logits beside the served model, however many are checked.
            out.append(np.asarray(head(x, params["final_norm"], params["lm_head"])))
        return np.stack(out)
