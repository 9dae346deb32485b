"""Training: LM loss + optax train step, mesh-shardable.

The reference is inference-only (SURVEY.md: no optimizer, no training loop;
its ``gradient_checkpointing`` flag exists but nothing exercises it).  This
framework makes training a first-class capability: a masked next-token
cross-entropy loss and a jitted ``train_step`` that runs under any
data/fsdp/tensor mesh — gradients and optimizer states inherit the param
shardings, XLA inserts the DP/FSDP collectives.  ``config.remat=True``
enables per-block rematerialization (jax.checkpoint) for memory-bound
training.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from .config import LLaMAConfig
from .models.llama import forward


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["params", "opt_state", "step"],
    meta_fields=[],
)
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray


def make_optimizer(
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 0,
    total_steps: Optional[int] = None,
) -> optax.GradientTransformation:
    """AdamW with the usual LLM hyperparameters: global-norm clipping and an
    optional linear-warmup + cosine-decay schedule."""
    if warmup_steps or total_steps:
        schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=learning_rate,
            warmup_steps=max(warmup_steps, 1),
            decay_steps=max(total_steps or warmup_steps * 10, 2),
        )
    else:
        schedule = learning_rate
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def init_train_state(params: Any, optimizer: optax.GradientTransformation) -> TrainState:
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
    )


def lm_loss(
    params: Any,
    tokens: jnp.ndarray,
    config: LLaMAConfig,
    loss_mask: Optional[jnp.ndarray] = None,
    dropout_rng: Optional[jnp.ndarray] = None,
    fused: bool = True,
) -> jnp.ndarray:
    """Masked next-token cross-entropy.

    tokens: [B, T] int32; position t predicts token t+1.
    loss_mask: optional [B, T] bool, query-position-indexed: mask[:, t]
      gates the loss term predicting token t+1 from position t (the
      convention `data.pack_documents` emits; the final position has no
      in-row target, so mask[:, -1] is never consumed).  Defaults to all
      positions.
    fused: take the LM head + softmax cross-entropy CHUNKWISE
      (``ops.loss.chunked_softmax_xent``) over the forward's last hidden
      state — never materializing the [B, T, V] logits or the fp32
      log-softmax (~1.5 GB at B=4 × S=2048 × V=32000) the dense path
      holds.  False runs the dense reference path (same value to
      reduction-order noise; kept as the parity oracle).
    """
    B, T = tokens.shape
    targets = tokens[:, 1:]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    # Forward over the full T (not T-1): sequence-parallel meshes need the
    # model-visible length to stay divisible by the seq axis; the final
    # position's loss rows are simply dropped.
    if fused:
        from .ops.loss import chunked_softmax_xent

        _, _, aux = forward(
            params, tokens, positions, config, dropout_rng=dropout_rng,
            compute_logits=False, output_last_hidden=True,
        )
        h = aux.last_hidden_state[:, :-1]  # [B, T-1, D] post-final-norm
        if config.tie_word_embeddings:
            head, head_t = params["embed"]["embedding"], True
        else:
            head, head_t = params["lm_head"], False
        w = (
            loss_mask[:, :-1].astype(jnp.float32)
            if loss_mask is not None
            else jnp.ones((B, T - 1), jnp.float32)
        )
        tot, wsum = chunked_softmax_xent(
            h.reshape(B * (T - 1), -1),
            head,
            targets.reshape(-1),
            w.reshape(-1),
            head_transposed=head_t,
        )
        return tot / jnp.maximum(wsum, 1.0)
    logits, _ = forward(
        params, tokens, positions, config, dropout_rng=dropout_rng
    )
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[:, :, None], axis=-1)[..., 0]
    if loss_mask is not None:
        # Query-indexed: mask[:, t] aligns with nll[:, t] (the loss for
        # target tokens[:, t+1]); drop the final, target-less position.
        m = loss_mask[:, :-1].astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


@functools.partial(
    jax.jit,
    static_argnames=("config", "optimizer", "mesh"),
    donate_argnames=("state",),
)
def train_step(
    state: TrainState,
    tokens: jnp.ndarray,
    config: LLaMAConfig,
    optimizer: optax.GradientTransformation,
    loss_mask: Optional[jnp.ndarray] = None,
    mesh=None,
    dropout_rng: Optional[jnp.ndarray] = None,
) -> Tuple[TrainState, jnp.ndarray]:
    """One optimizer step.  `optimizer` must be a hashable static (module-
    level) GradientTransformation; under a mesh the donated state keeps
    params/opt-state sharded in place.

    `mesh` must be passed explicitly (it is part of the jit cache key):
    sharding constraints and ring attention read the active mesh at trace
    time, so relying on the caller's thread-local ``use_mesh`` would bake
    whatever mesh was active at first call into the cached executable.
    """
    from .parallel.mesh import current_mesh, use_mesh

    if config.expert_block:
        raise NotImplementedError(
            f"the training step is not supported with {config.expert_block}: "
            "the block is served, not trained (no router balance loss, no "
            "grouped-matmul gradient, no gradient of the scan kernel)"
        )
    if mesh is None and current_mesh() is not None:
        # Entering use_mesh(None) here would silently disable every
        # sharding constraint the ambient mesh was meant to drive; fail
        # loudly instead of training unsharded.
        raise ValueError(
            "train_step: pass mesh= explicitly (it is part of the jit "
            "cache key); an ambient use_mesh(...) context is not seen by "
            "the compiled executable on later calls"
        )
    with use_mesh(mesh):
        # One base key serves the whole run: folding in the step count
        # gives every step fresh masks without the caller re-splitting.
        step_rng = (
            jax.random.fold_in(dropout_rng, state.step)
            if dropout_rng is not None else None
        )
        loss, grads = jax.value_and_grad(lm_loss)(
            state.params, tokens, config, loss_mask, step_rng
        )
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss
