"""Speculative decoding: draft-model proposal + single-pass greedy verify.

Beyond the reference's capability surface (its decode is strictly
one-token-at-a-time through HF's mixin, SURVEY.md §1) — speculative decoding
trades cheap draft-model FLOPs for target-model HBM bandwidth, the binding
resource of TPU decode: the target runs ONE forward over ``n_draft + 1``
positions per round (weights stream once) instead of one forward per token.

Greedy verification (temperature 0) is exact: the emitted sequence equals
plain greedy decode of the target model token-for-token, regardless of the
draft model's quality — the draft only controls speed (acceptance rate),
never content.  Sampled verification (temperature > 0) is Leviathan-style
rejection sampling and is distribution-preserving: the emitted tokens are
drawn from exactly the target's (warped) sampling distribution.  Both
invariants are what the tests assert.

TPU-native mechanics worth noting:
  * **No cache rollback.**  Attention masking in this framework is purely
    positional (``KVCache.pos``; -1 = invalid), so rejected draft entries
    are simply re-marked ``pos=-1`` after verification — the slots are
    wasted, never rolled back, and the whole round stays inside one jitted
    ``lax.while_loop`` with static shapes.
  * **Per-row acceptance with a shared cache index.**  Rows accept
    different prefix lengths; each row's surviving slots keep their own
    absolute positions, everything else is masked.  Batch rows never
    synchronize on acceptance.
  * Memory trade-off: caches are sized for the worst case (every round
    accepts 0 drafts): ``P + max_new * (n_draft + 1)`` target slots.  Use
    for latency-bound serving (small batch, good draft), not max-batch
    throughput.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import LLaMAConfig
from .engine import GenerationConfig, _is_stop, prompt_positions
from .models.llama import forward, init_cache
from .ops.sampling import sample, warped_probs
from .parallel.mesh import use_mesh

def _maybe_fault() -> None:
    """Chaos-drill hook: fires faults.py's trace-time registry (site
    "spec_decode") at ``generate_speculative``'s trace time.  The
    serving batcher's per-round injection is the batcher-side site of
    the same name (serving.ContinuousBatcher.step)."""
    from .faults import fire_trace

    fire_trace("spec_decode")


@functools.partial(
    jax.jit,
    static_argnames=("target_config", "draft_config", "gen_config",
                     "n_draft", "mesh"),
)
def generate_speculative(
    target_params,
    draft_params,
    prompt_tokens: jnp.ndarray,
    prompt_mask: jnp.ndarray,
    rng: Optional[jax.Array] = None,
    *,
    target_config: LLaMAConfig,
    draft_config: LLaMAConfig,
    gen_config: GenerationConfig,
    n_draft: int = 4,
    mesh=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Speculative decode — greedy or sampled verification.

    temperature == 0.0: exact greedy verification; output is token-for-token
    identical to plain greedy decode of the target.  temperature > 0:
    Leviathan-style rejection sampling — draft token ``d ~ q`` is accepted
    with probability ``min(1, p(d)/q(d))``; on rejection the replacement is
    drawn from ``norm(relu(p - q))``; a fully-accepted round draws a bonus
    token from ``p``.  Both p and q carry the SAME temperature/top-p/top-k
    warping as ``ops.sampling.sample``, so the emitted distribution equals
    plain sampled decode of the target (the draft only changes speed).

    Args:
      target_params / draft_params: param trees; models must share the
        vocabulary (draft proposes token ids the target verifies).
      prompt_tokens: [B, P] int32, left-padded.
      prompt_mask: [B, P] bool.
      rng: PRNG key — required when temperature > 0.
      gen_config: sampling/stopping policy (matches ``engine.generate``).
      n_draft: draft tokens proposed per round (>= 1).
    Returns:
      (tokens [B, P + max_new_tokens] int32 — prompt then generated, pad
       after stop; accept_counts [B] int32 — total accepted draft tokens
       per row, for observability/acceptance-rate monitoring).
    """
    _maybe_fault()
    gc = gen_config
    if gc.temperature != 0.0 and rng is None:
        raise ValueError(
            "generate_speculative: rng is required when temperature > 0"
        )
    if n_draft < 1:
        raise ValueError("n_draft must be >= 1")
    if target_config.vocab_size != draft_config.vocab_size:
        raise ValueError("target and draft must share a vocabulary")
    from .parallel.mesh import current_mesh

    if mesh is None and current_mesh() is not None:
        # Same trap engine.generate guards: an ambient use_mesh(...) is not
        # part of the jit cache key, so silently tracing under use_mesh(None)
        # here would disable every sharding constraint.
        raise ValueError(
            "generate_speculative: pass mesh= explicitly (it is part of "
            "the jit cache key); an ambient use_mesh(...) context is not "
            "seen by the compiled executable on later calls"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)  # unused on the greedy path
    with use_mesh(mesh):
        return _spec_impl(
            target_params, draft_params, prompt_tokens, prompt_mask, rng,
            target_config, draft_config, gc, n_draft,
        )


def _greedy(logits: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Shared speculative math
#
# ONE implementation of the Leviathan draft-draw / accept / residual rules,
# traced into both jit contexts that need it: the standalone engine below
# (B-wide keys, static policies) and the serving batcher's
# ``_spec_round_core`` (per-row key chains, traced per-row policies,
# vmapped draws).  Sharing the
# math is what makes a sampled serving slot emit bit-identically to a
# standalone B=1 seeded ``generate_speculative`` of the same request — the
# equivalence is pinned by tests/test_serving_spec.py.
# ---------------------------------------------------------------------------

def draft_categorical(key, probs):
    """One categorical draw from a post-warp distribution — the draft
    proposal and replacement/bonus draw.  ``log(probs + 1e-30)`` keeps
    zero-probability (warped-out) tokens unreachable without -inf NaN
    traps.  Works B-wide (probs [B, V], one key) and under vmap (probs
    [V], per-row key) — ``jax.random.categorical`` draws the same bits
    for both shapes, which the serving bit-identity relies on."""
    return jax.random.categorical(
        key, jnp.log(probs + 1e-30), axis=-1
    ).astype(jnp.int32)


def leviathan_verify(pprobs, qprobs, drafts, u):
    """Leviathan-style rejection of a drafted block.

    pprobs: [B, G+1, V] post-warp target distributions (position j is the
      distribution AFTER consuming block token j, i.e. the one draft j+1
      was checked against; position G is the bonus distribution).
    qprobs: [B, G, V] post-warp draft distributions.
    drafts: [B, G] proposed tokens.  u: [B, G] uniforms.

    Draft ``d ~ q`` is accepted iff ``u * q(d) < p(d)`` (probability
    min(1, p/q)); ``acc`` is the length of the accepted prefix.  Returns
    (acc [B], dist [B, V]) where ``dist`` is the distribution for the
    token at offset ``acc``: the residual ``norm(relu(p - q))`` at the
    first rejection, or the bonus ``p_G`` on full acceptance.  Residual
    mass 0 means p <= q everywhere (p == q): rejection was probability-0
    but float rounding can reach it — fall back to p.
    """
    G = drafts.shape[1]
    p_d = jnp.take_along_axis(
        pprobs[:, :G], drafts[..., None], axis=-1
    )[..., 0]  # [B, G]
    q_d = jnp.take_along_axis(qprobs, drafts[..., None], axis=-1)[..., 0]
    accept = u * q_d < p_d
    acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
    resid = jnp.maximum(pprobs[:, :G] - qprobs, 0.0)  # [B, G, V]
    cand = jnp.concatenate([resid, pprobs[:, G:]], axis=1)
    dist = jnp.take_along_axis(cand, acc[:, None, None], axis=1)[:, 0]
    mass = jnp.sum(dist, axis=-1, keepdims=True)
    p_at = jnp.take_along_axis(pprobs, acc[:, None, None], axis=1)[:, 0]
    dist = jnp.where(mass > 1e-12, dist, p_at)
    return acc, dist


def place_extra(drafts, acc, extra):
    """Emitted block [B, G+1]: accepted drafts at offsets j < acc, the
    replacement/bonus token at offset acc (offsets past acc are dead —
    callers only consume outs[:, :acc+1])."""
    B = drafts.shape[0]
    outs = jnp.concatenate(
        [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1
    )
    return outs.at[jnp.arange(B), acc].set(extra)


def accepted_emit_counts(acc, stop_hits, remaining):
    """How many of a round's accepted tokens the serving host's emit
    scan would actually deliver — the ON-DEVICE form of its
    token-by-token stop/budget walk over ``outs[:acc]``
    (``serving.ContinuousBatcher._step_spec``), so the R-round chunk
    program can fold slot completion mid-chunk without a host
    round-trip.

    acc: [B] int32 accepted-prefix lengths (clipped to >= 0).
    stop_hits: [B, G] bool, per-position stop-set membership of the
      round's ``outs[:, :G]`` (``ops.sampling.stop_token_hits``).
    remaining: [B] int32 generation budget AFTER the round's
      pending-tau emit (the host checks ``len(emitted) >= max_new``
      after appending each token; emitting outs token i makes that
      ``i + 1 >= remaining``).
    Returns (e [B], done [B]): tokens ``outs[0..e-1]`` are emitted —
    ``e == acc`` when the row sails through, ``first_done + 1`` when
    token ``first_done`` hits a stop or exhausts the budget — and
    ``done`` marks rows whose request finished mid-prefix (their slot
    frees; fill never advances for them, exactly as on the host)."""
    G = stop_hits.shape[1]
    i = jnp.arange(G, dtype=jnp.int32)[None, :]
    cand = i < acc[:, None]
    done_at = cand & (stop_hits | ((i + 1) >= remaining[:, None]))
    done = jnp.any(done_at, axis=1)
    first = jnp.argmax(done_at, axis=1)
    return jnp.where(done, first + 1, acc), done


def _spec_impl(tp, dp, prompt_tokens, prompt_mask, rng, tc, dc, gc, G):
    B, P = prompt_tokens.shape
    N = gc.max_new_tokens
    total = P + N
    positions = prompt_positions(prompt_mask)
    prompt_lens = jnp.sum(prompt_mask.astype(jnp.int32), axis=-1)  # [B]

    # Worst case: every round accepts 0 drafts -> N rounds, G+1 (target) /
    # G (draft) slots burned per round.
    t_cache = init_cache(tc, B, max_len=P + N * (G + 1))
    d_cache = init_cache(dc, B, max_len=P + N * (G + 1))

    sampled = gc.temperature != 0.0  # static: picked at trace time
    t_logits, t_cache = forward(
        tp, prompt_tokens, positions, tc, cache=t_cache, attn_mask=prompt_mask
    )
    _, d_cache = forward(
        dp, prompt_tokens, positions, dc, cache=d_cache, attn_mask=prompt_mask
    )
    if sampled:
        rng, sub = jax.random.split(rng)
        tau = sample(sub, t_logits[:, -1], gc.temperature, gc.top_p, gc.top_k)
    else:
        tau = _greedy(t_logits[:, -1])  # [B] first generated token

    buf = jnp.full((B, total), gc.pad_id, dtype=jnp.int32)
    buf = lax.dynamic_update_slice(buf, prompt_tokens.astype(jnp.int32), (0, 0))
    buf = buf.at[jnp.arange(B), P].set(
        jnp.where(prompt_lens > 0, tau, gc.pad_id)
    )
    done = _is_stop(tau, gc.stop_tokens)  # [B]
    count = jnp.ones((B,), jnp.int32)     # generated tokens so far (tau)
    accepted_total = jnp.zeros((B,), jnp.int32)

    # (round, buf, t_cache, d_cache, tau, count, done, accepted_total, rng)
    init = (jnp.zeros((), jnp.int32), buf, t_cache, d_cache, tau, count,
            done, accepted_total, rng)

    def cond(state):
        rnd, _, _, _, _, count, done, _, _ = state
        return jnp.logical_and(
            rnd < N, ~jnp.all(jnp.logical_or(done, count >= N))
        )

    def body(state):
        (rnd, buf, t_cache, d_cache, tau, count, done, accepted_total,
         rng) = state
        rng, k_draft, k_accept, k_extra = jax.random.split(rng, 4)
        # tau sits at per-row position p = prompt_len + count - 1.
        p = prompt_lens + count - 1  # [B]

        # --- 1. draft G tokens autoregressively ---
        def draft_one(carry, j):
            d_cache, tok, key = carry
            pos = (p + j)[:, None]
            lg, d_cache = forward(
                dp, tok[:, None], pos, dc, cache=d_cache,
                attn_mask=jnp.ones((B, 1), bool),
            )
            if sampled:
                key, sub = jax.random.split(key)
                q = warped_probs(lg[:, -1], gc.temperature, gc.top_p, gc.top_k)
                nxt = draft_categorical(sub, q)
            else:
                q = jnp.zeros((B, dc.vocab_size), jnp.float32)  # unused
                nxt = _greedy(lg[:, -1])
            return (d_cache, nxt, key), (nxt, q)

        (d_cache, d_last, _), (drafts, qprobs) = lax.scan(
            draft_one, (d_cache, tau, k_draft), jnp.arange(G, dtype=jnp.int32)
        )
        drafts = jnp.swapaxes(drafts, 0, 1)   # [B, G]
        qprobs = jnp.swapaxes(qprobs, 0, 1)   # [B, G, V]
        # Feed d_G once more (logits discarded) so its KV lands in the
        # draft cache: the scan only cached inputs [tau, d_1..d_{G-1}], and
        # on a fully-accepted round the next tau is the *bonus* token at
        # p+G+1 — without this, position p+G stays a permanent hole that
        # corrupts every later draft forward and collapses acceptance in
        # exactly the high-acceptance regime.
        _, d_cache = forward(
            dp, d_last[:, None], (p + G)[:, None], dc, cache=d_cache,
            attn_mask=jnp.ones((B, 1), bool),
        )

        # --- 2. one target pass over [tau, d_1 .. d_G] ---
        block = jnp.concatenate([tau[:, None], drafts], axis=1)  # [B, G+1]
        block_pos = p[:, None] + jnp.arange(G + 1, dtype=jnp.int32)[None, :]
        t_idx = t_cache.index
        t_logits, t_cache = forward(
            tp, block, block_pos, tc, cache=t_cache,
            attn_mask=jnp.ones((B, G + 1), bool),
        )
        # --- 3. verification ---
        if sampled:
            # Leviathan rejection sampling (shared core).  pprobs/qprobs
            # are both post-warp, so acceptance min(1, p/q) + residual
            # resampling reproduce the target's sampled distribution
            # exactly.
            pprobs = warped_probs(
                t_logits, gc.temperature, gc.top_p, gc.top_k
            )  # [B, G+1, V]
            u = jax.random.uniform(k_accept, (B, G))
            acc, dist = leviathan_verify(pprobs, qprobs, drafts, u)
            extra = draft_categorical(k_extra, dist)
            outs = place_extra(drafts, acc, extra)
        else:
            outs = _greedy(t_logits)  # [B, G+1]; outs[:, j] follows block[:, j]
            # Accept the matching draft prefix (+1 correction/bonus).
            match = (drafts == outs[:, :G])                   # [B, G]
            acc = jnp.sum(
                jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
            )
        # Emitted candidates this round: outs[:, 0..acc] (acc+1 tokens).
        j = jnp.arange(G + 1, dtype=jnp.int32)[None, :]       # [1, G+1]
        in_prefix = j <= acc[:, None]
        stopped_before = jnp.cumsum(
            _is_stop(outs, gc.stop_tokens).astype(jnp.int32), axis=1
        ) - _is_stop(outs, gc.stop_tokens).astype(jnp.int32) > 0
        emit = (
            in_prefix
            & ~stopped_before
            & ~done[:, None]
            & ((count[:, None] + j) < N)
        )

        # --- 4. write emitted tokens at per-row columns ---
        cols = jnp.where(emit, P + count[:, None] + j, total)  # OOB -> drop
        buf = buf.at[jnp.arange(B)[:, None], cols].set(outs, mode="drop")

        n_emit = jnp.sum(emit.astype(jnp.int32), axis=1)       # [B]
        # Last emitted token per row becomes the next tau.
        last_j = jnp.maximum(n_emit - 1, 0)
        new_tau = jnp.take_along_axis(outs, last_j[:, None], axis=1)[:, 0]
        tau = jnp.where(n_emit > 0, new_tau, tau)

        stopped = jnp.any(_is_stop(outs, gc.stop_tokens) & emit, axis=1)
        count = count + n_emit
        done = done | stopped | (count >= N)
        accepted_total = accepted_total + jnp.minimum(acc, jnp.maximum(n_emit - 1, 0))

        # --- 5. invalidate rejected slots (positional masking: no rollback)
        # Target wrote G+1 slots at t_idx: tau (always valid) + G drafts,
        # valid iff accepted.  (Validity beyond emission is harmless for
        # done rows — their buf writes are suppressed.)
        t_valid = j <= acc[:, None]                            # [B, G+1]
        t_patch = jnp.where(t_valid, block_pos, -1).astype(jnp.int32)
        t_cache = dataclasses.replace(
            t_cache,
            pos=lax.dynamic_update_slice(t_cache.pos, t_patch, (0, t_idx)),
        )
        # Draft wrote G+1 slots: [tau, d_1 .. d_G] — slot j holds the token
        # at position p+j, valid iff j <= acc (d_G survives exactly on a
        # fully-accepted round, when the next round needs it).
        d_idx = d_cache.index - (G + 1)
        jd = jnp.arange(G + 1, dtype=jnp.int32)[None, :]
        d_valid = jd <= acc[:, None]
        d_patch = jnp.where(
            d_valid, p[:, None] + jd, -1
        ).astype(jnp.int32)
        d_cache = dataclasses.replace(
            d_cache,
            pos=lax.dynamic_update_slice(d_cache.pos, d_patch, (0, d_idx)),
        )

        return (rnd + 1, buf, t_cache, d_cache, tau, count, done,
                accepted_total, rng)

    _, buf, _, _, _, _, _, accepted_total, _ = lax.while_loop(
        cond, body, init
    )
    return buf, accepted_total
