"""Pipeline parallelism — GPipe microbatch schedule over the ``stage`` axis.

The reference has no pipeline parallelism (SURVEY.md §2.13b: its layer stack
is a plain Python loop, ``/root/reference/jax_llama/model.py:579-592``); this
module adds it the TPU way: no per-stage processes or send/recv threads, one
SPMD program in which the ``stage`` mesh axis holds ``L / n_stages`` layers
per device group and activations rotate stage→stage+1 with ``lax.ppermute``
over ICI/DCN point-to-point links.

Schedule: classic GPipe.  The batch splits into M microbatches; the pipeline
runs ``M + S - 1`` ticks; at tick ``t`` stage ``s`` runs microbatch
``t - s`` (when in range).  Bubble fraction is ``(S-1)/(M+S-1)`` — callers
pick M per memory/efficiency trade-off (default M = S).

Composition: the shard_map is *manual only over* ``stage``
(``axis_names={"stage"}``); data/fsdp/tensor stay auto, so the blocks'
internal sharding constraints (tensor-parallel activations, batch sharding)
keep working inside each stage — GSPMD still inserts the TP collectives
per-stage.  Ring (seq>1) attention nests a second shard_map and is not
composable with the pipeline; callers must keep seq == 1 when stage > 1.

Because each microbatch's positions ride the ring alongside its
activations, masking stays correct for left-padded rows without any global
coordination.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


StageFn = Callable[
    [Any, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray
]


def pipeline_blocks(
    stage_fn: StageFn,
    layer_params: Any,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    slot_pos: jnp.ndarray,
    *,
    mesh,
    n_microbatches: int,
    axis_name: str = "stage",
) -> jnp.ndarray:
    """Run the stacked layer params as a pipeline over the ``stage`` axis.

    Args:
      stage_fn: ``(stage_layers, x, positions, slot_pos, mb_index) -> x``
        applying one stage's layers to one microbatch (``stage_layers``
        leaves keep a leading ``L/S`` axis for the caller's own scan).
        ``mb_index`` is the int32 index of the microbatch this stage is
        processing this tick (clamped during fill/drain bubble ticks,
        whose outputs are discarded) — dropout callers fold it into their
        per-layer keys so every (layer, microbatch) draws independently.
      layer_params: pytree of stacked layer params, leading axis L.
      x: [B, T, D] embeddings.
      positions: [B, T] int32 query positions (clamped >= 0).
      slot_pos: [B, T] int32 kv slot positions (-1 padding).
      mesh: the active Mesh (must contain ``stage``).
      n_microbatches: M; must divide B.
    Returns:
      [B, T, D] block-stack output.

    Call under ``jax.jit`` (as every engine/train entry point does): in
    eager mode the shard_map's auto-axes/out_specs interaction trips a
    strictness check even though the jitted program is valid.
    """
    S = mesh.shape[axis_name]
    M = n_microbatches
    B, T, D = x.shape
    L = jax.tree.leaves(layer_params)[0].shape[0]
    if L % S:
        raise ValueError(f"n_layers={L} not divisible by stage={S}")
    if B % M:
        raise ValueError(f"batch={B} not divisible by microbatches={M}")
    mb = B // M

    staged = jax.tree.map(
        lambda a: a.reshape((S, L // S) + a.shape[1:]), layer_params
    )
    x_mb = x.reshape(M, mb, T, D)
    pos_mb = positions.reshape(M, mb, T)
    spos_mb = slot_pos.reshape(M, mb, T)

    perm = [(i, (i + 1) % S) for i in range(S)]

    def per_stage(staged, x_mb, pos_mb, spos_mb):
        # Local views: staged leaves [1, L/S, ...]; the rest replicated.
        stage = lax.axis_index(axis_name)
        layers = jax.tree.map(lambda a: a[0], staged)
        state = jnp.zeros((mb, T, D), x_mb.dtype)
        state_pos = jnp.zeros((mb, T), pos_mb.dtype)
        state_spos = jnp.full((mb, T), -1, spos_mb.dtype)
        outs = jnp.zeros((1, M, mb, T, D), x_mb.dtype)

        for t in range(M + S - 1):
            # Stage 0 injects microbatch t (clamped during drain ticks —
            # drained garbage can never reach the last stage in time).
            inject = min(t, M - 1)
            is_first = stage == 0
            xx = jnp.where(is_first, x_mb[inject], state)
            pos = jnp.where(is_first, pos_mb[inject], state_pos)
            spos = jnp.where(is_first, spos_mb[inject], state_spos)

            # Microbatch index at this stage this tick (GPipe: stage s runs
            # microbatch t - s); clamped on bubble ticks, whose compute is
            # discarded.
            mb_index = jnp.clip(t - stage, 0, M - 1).astype(jnp.int32)
            y = stage_fn(layers, xx, pos, spos, mb_index)

            # The last stage finished microbatch t - (S-1) this tick; every
            # stage writes uniformly (SPMD), only the last stage's buffer is
            # read back outside.
            m = t - (S - 1)
            if 0 <= m < M:
                outs = outs.at[0, m].set(y)
            if t < M + S - 2:
                state, state_pos, state_spos = (
                    lax.ppermute(v, axis_name, perm)
                    for v in (y, pos, spos)
                )
        return outs

    out = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(axis_name), P(), P(), P()),
        out_specs=P(axis_name),
        axis_names={axis_name},
        # The rotating carries flip between stage-invariant (initial zeros)
        # and stage-varying (post-ppermute); the varying-manual-axes checker
        # rejects the mix although the program is correct (same situation as
        # ring attention).
        check_vma=False,
    )(staged, x_mb, pos_mb, spos_mb)
    return out[-1].reshape(B, T, D)
