"""Scale-out serving: mesh-sharded placement for the serving stack.

The model side of this repo has been mesh-capable since the seed
(``partition.py`` weight specs, GSPMD ``constrain`` calls through
``models/llama.py``, the paged-attention op's own fully-manual
``shard_map`` over the tensor/data axes) — but the *serving* stack the
batcher owns stayed single-chip: ``init_pool`` built the KV block pool
on the default device, the per-slot device twins (``d_*``) were plain
``jnp.asarray`` uploads, and the first sharded dispatch paid a GSPMD
reshard of every one of them (worse: donation aliasing only holds when
a donated input's sharding matches its carried output's, so an
unplaced pool silently COPIES on its first mesh dispatch instead of
being reused).

This module is the missing placement layer (ROADMAP item 2 — "the
millions-of-users scaling step"):

  * **Serving-mesh geometry** (:class:`ServeMeshSpec` /
    :func:`parse_serve_mesh` / :func:`build_serve_mesh`): a serving
    mesh is ``data x tensor`` (seq/stage axes stay 1 — ring/pipeline
    constructs do not apply to cached decode; ``fsdp`` may ride along
    as a second row axis).  ``run.py --serve-mesh dp,tp`` parses here.
  * **Canonical shardings** (:func:`pool_pspec` / :func:`row_pspec` /
    :func:`shard_pool` / :func:`place_rows`): the KV block pool shards
    its KV-head axis over ``tensor`` (each shard holds its heads'
    blocks — the same per-shard contents the paged kernel's manual
    sharding expects, so the kernel's ``shard_map`` never reshards);
    ``pos`` planes replicate (every row indexes them); per-slot state
    rows shard over the batch axes (``data``/``fsdp``).  The batcher
    places its pool, draft-pool and ``d_*`` twins through these at
    construction, and the chunk programs re-CONSTRAIN their outputs to
    the same specs (:func:`constrain_pool` / :func:`constrain_rows`) —
    input placement + output constraint is what makes donated-leaf
    aliasing STABLE under sharding (proven per-program by the
    PR-8 lowering auditor's mesh pass, ``analysis/lowering.py``).
  * **Sharded swap staging** (:func:`staging_shardings`): host-tier
    slabs restore through ``kvcache.stage_restore`` staging buffers
    placed with the pool's own specs, so ``adopt_into_pool``'s
    donated-pool scatter is shard-local (no cross-shard reshard on
    the adoption dispatch).  The radix prefix index itself stays
    host-global — one tree indexes the sharded pool, because block
    ids are global and every shard holds the same block GEOMETRY
    (only the KV-head slice differs).

Data parallelism ACROSS meshes — N independent batcher replicas, each
owning a mesh (slice), fronted by least-loaded/affinity routing and
the prefill/decode disaggregation handoff — lives one layer up in
``jax_llama_tpu/router.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import current_mesh, make_mesh

# Per-slot state rows shard over the batch axes — the same pair the
# model's `constrain` shards activation batch over and the paged
# kernel's shard_map shards rows over, so state never reshards between
# the program body and the op.
ROW_AXES = ("data", "fsdp")


@dataclasses.dataclass(frozen=True)
class ServeMeshSpec:
    """Serving-mesh geometry: ``data`` replicas-worth of row sharding
    INSIDE one batcher x ``tensor``-way model/KV sharding.  (Replica
    data-parallelism across batchers is the router's axis, not this
    one's.)"""

    data: int = 1
    tensor: int = 1

    @property
    def n_devices(self) -> int:
        return self.data * self.tensor

    def __post_init__(self):
        if self.data < 1 or self.tensor < 1:
            raise ValueError(
                f"serve mesh axes must be >= 1 (got data={self.data}, "
                f"tensor={self.tensor})"
            )


def parse_serve_mesh(text: str) -> ServeMeshSpec:
    """Parse run.py's ``--serve-mesh dp,tp`` (also accepts a bare
    ``tp``, sugar for ``1,tp``)."""
    parts = [p.strip() for p in str(text).split(",") if p.strip()]
    try:
        nums = [int(p) for p in parts]
    except ValueError:
        nums = []
    if len(nums) == 1:
        return ServeMeshSpec(data=1, tensor=nums[0])
    if len(nums) == 2:
        return ServeMeshSpec(data=nums[0], tensor=nums[1])
    raise ValueError(
        f"bad --serve-mesh {text!r}: expected 'dp,tp' (two positive "
        "ints, e.g. '2,4') or a bare 'tp'"
    )


def build_serve_mesh(
    spec: ServeMeshSpec,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Materialize the serving mesh on ``spec.n_devices`` devices
    (default: the first n of ``jax.devices()``)."""
    if devices is None:
        devices = jax.devices()[: spec.n_devices]
    if len(devices) != spec.n_devices:
        raise ValueError(
            f"serve mesh {spec.data}x{spec.tensor} needs "
            f"{spec.n_devices} devices, got {len(devices)}"
        )
    return make_mesh(data=spec.data, tensor=spec.tensor, devices=devices)


def is_serving_mesh(mesh: Optional[Mesh]) -> bool:
    """A mesh the serving placement layer covers: no seq/stage axes
    (ring/pipeline constructs do not apply to cached paged decode)."""
    return (
        mesh is not None
        and mesh.shape.get("seq", 1) == 1
        and mesh.shape.get("stage", 1) == 1
    )


def row_shards(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape.get(a, 1) for a in ROW_AXES]))


def placement_ok(
    config, mesh: Optional[Mesh], n_slots: int, draft_config=None
) -> bool:
    """Whether the canonical sharded placement applies: a serving mesh
    whose tensor axis divides the KV heads (pool shards head-wise) and
    whose row axes divide ``n_slots``.  Meshes outside this envelope
    keep the legacy unplaced behavior (GSPMD still reshards them
    correctly through the gathered fallback — just without the
    placement guarantees)."""
    if not is_serving_mesh(mesh):
        return False
    tp = mesh.shape.get("tensor", 1)
    if config.kv_heads % tp or config.n_heads % tp:
        return False
    if draft_config is not None and draft_config.kv_heads % tp:
        return False
    return n_slots % row_shards(mesh) == 0


def validate_serve_mesh(
    config, mesh: Mesh, n_slots: int, draft_config=None
) -> None:
    """Hard-error version of :func:`placement_ok` for explicit
    ``--serve-mesh`` requests — a clear refusal at startup beats a
    silently unplaced mesh."""
    if not is_serving_mesh(mesh):
        raise ValueError(
            "serving mesh must not carry seq/stage axes "
            f"(got {dict(mesh.shape)})"
        )
    tp = mesh.shape.get("tensor", 1)
    if config.kv_heads % tp:
        raise ValueError(
            f"serve-mesh tensor={tp} must divide n_kv_heads="
            f"{config.kv_heads} (the KV pool shards head-wise)"
        )
    if config.n_heads % tp:
        raise ValueError(
            f"serve-mesh tensor={tp} must divide n_heads={config.n_heads}"
        )
    if draft_config is not None and draft_config.kv_heads % tp:
        raise ValueError(
            f"serve-mesh tensor={tp} must divide the draft model's "
            f"n_kv_heads={draft_config.kv_heads}"
        )
    rows = row_shards(mesh)
    if n_slots % rows:
        raise ValueError(
            f"serve-mesh row shards (data*fsdp={rows}) must divide "
            f"n_slots={n_slots}"
        )


def mesh_shape(mesh: Optional[Mesh]) -> Dict[str, int]:
    """The mesh's non-trivial axis sizes — the /metrics ``serve_mesh_*``
    gauges and /healthz ``replicas`` section read this."""
    if mesh is None:
        return {"data": 1, "tensor": 1, "devices": 1}
    return {
        "data": int(mesh.shape.get("data", 1))
        * int(mesh.shape.get("fsdp", 1)),
        "tensor": int(mesh.shape.get("tensor", 1)),
        "devices": int(np.prod(list(mesh.shape.values()))),
    }


# ---------------------------------------------------------------------------
# Canonical partition specs
# ---------------------------------------------------------------------------

# The leaves of a BlockPool that are placed (its routing counts replicate).
_POOL_LEAVES = ("k", "v", "pos", "k_scale", "v_scale")


def pool_pspec(name: str, ndim: int) -> P:
    """Spec for one BlockPool leaf (or its staged-restore twin, which
    shares the layout): k/v ``[L, KVH, NB, BLK, hd]`` and scales
    ``[L, KVH, NB, BLK]`` shard the KV-head axis over ``tensor``;
    ``pos`` planes ``[NB, BLK]`` replicate (every row's table indexes
    them; 2 ints per cache slot — replication is noise next to the KV
    bytes)."""
    if name.endswith("pos"):
        return P()
    return P(*((None, "tensor") + (None,) * (ndim - 2)))


def row_pspec(ndim: int) -> P:
    """Spec for one per-slot state leaf ``[B, ...]``: rows shard over
    the batch axes, trailing dims replicate."""
    return P(*((ROW_AXES,) + (None,) * (ndim - 1)))


def shard_pool(pool, mesh: Mesh):
    """Place a BlockPool's leaves with the canonical specs (ctor-time;
    the chunk programs' output constraints keep them there, so the
    donated pool aliases shard-local from the first dispatch on)."""
    def put(name):
        arr = getattr(pool, name)
        if arr is None:
            return None
        return jax.device_put(
            arr, NamedSharding(mesh, pool_pspec(name, arr.ndim))
        )

    return dataclasses.replace(pool, **{n: put(n) for n in _POOL_LEAVES})


def place_rows(mesh: Optional[Mesh], x) -> jax.Array:
    """Upload/replace one per-slot array with rows sharded over the
    mesh's batch axes; plain ``jnp.asarray`` semantics when no mesh."""
    import jax.numpy as jnp

    if mesh is None:
        return jnp.asarray(x)
    x = np.asarray(x) if not isinstance(x, jax.Array) else x
    return jax.device_put(x, NamedSharding(mesh, row_pspec(x.ndim)))


def staging_shardings(
    mesh: Optional[Mesh], slab_names: Sequence[str]
) -> Optional[Dict[str, Any]]:
    """Shardings for ``kvcache.stage_restore`` staging buffers: each
    staged field takes the pool leaf's own spec (the stacked block axis
    sits where NB does), so the adoption scatter lands shard-local —
    each tensor shard restores ITS head slice of the slab, no
    cross-shard reshard on the adopt dispatch.  ``ids`` replicates.
    None (no mesh) keeps default placement."""
    if mesh is None:
        return None
    out: Dict[str, Any] = {"ids": NamedSharding(mesh, P())}
    for name in slab_names:
        # Staged k/v: [L, KVH, nb, BLK(, hd)]; staged pos: [nb, BLK].
        ndim = 2 if name.endswith("pos") else (
            4 if name.endswith("_scale") else 5
        )
        out[name] = NamedSharding(mesh, pool_pspec(name, ndim))
    return out


# ---------------------------------------------------------------------------
# In-program output constraints (trace-time; no-op without a mesh)
# ---------------------------------------------------------------------------

def constraints_apply(kv_heads: int, n_rows: int) -> bool:
    """Trace-time guard for the output constraints: the ACTIVE mesh is
    a serving mesh the canonical placement covers (tensor divides the
    pool's KV heads, row axes divide the slot count).  Meshes outside
    the envelope — seq/stage axes, non-dividing tensor — keep the
    legacy propagation behavior; constraining there would be a
    lowering error, not a slow path."""
    mesh = current_mesh()
    if not is_serving_mesh(mesh):
        return False
    tp = mesh.shape.get("tensor", 1)
    return kv_heads % tp == 0 and n_rows % row_shards(mesh) == 0


def _constrain(x, spec: P):
    mesh = current_mesh()
    if mesh is None or x is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_view(view):
    """Pin a gathered per-row cache view (``_gather_cache`` output:
    k/v ``[L, B, W, KVH, hd]``, scales ``[L, B, W, KVH]``, pos
    ``[B, W]``) to KV-heads-over-``tensor`` — the same head slice the
    pool itself shards — with rows over the batch axes when they
    divide.  Without this pin GSPMD is free to satisfy the gather by
    REPLICATING the source pool first: a full-pool all-gather inside
    every scan iteration (the silent reshard the comms-budget pass
    exists to catch), instead of the shard-local block gather the
    placement implies.  No-op when no serving mesh is active or the
    head axis does not divide."""
    mesh = current_mesh()
    if not is_serving_mesh(mesh):
        return view
    tp = mesh.shape.get("tensor", 1)
    kvh = int(view.k.shape[3])
    if tp == 1 or kvh % tp:
        return view
    rows = (
        ROW_AXES if int(view.k.shape[1]) % row_shards(mesh) == 0
        else None
    )
    spec_kv = P(None, rows, None, "tensor", None)
    spec_scale = P(None, rows, None, "tensor")
    return dataclasses.replace(
        view,
        k=_constrain(view.k, spec_kv),
        v=None if view.v is None else _constrain(view.v, spec_kv),
        pos=_constrain(view.pos, P(rows, None)),
        k_scale=(
            None if view.k_scale is None
            else _constrain(view.k_scale, spec_scale)
        ),
        v_scale=(
            None if view.v_scale is None
            else _constrain(view.v_scale, spec_scale)
        ),
    )


def constrain_pool(pool):
    """Pin a program's output pool to the canonical pool specs — called
    inside the jitted programs under ``use_mesh``, so the donated input
    pool (placed the same way at ctor) aliases instead of resharding.
    No-op when no mesh is active (the single-chip trace is unchanged)."""
    if current_mesh() is None:
        return pool
    # A pool is described by the planes it has (a latent pool: k and pos).
    return dataclasses.replace(pool, **{
        name: _constrain(plane, pool_pspec(name, plane.ndim))
        for name in _POOL_LEAVES
        for plane in [getattr(pool, name)] if plane is not None
    })


def constrain_rows(*arrays) -> Tuple:
    """Pin per-slot state outputs (``[B, ...]`` leaves) to the
    canonical row sharding; identity without an active mesh."""
    if current_mesh() is None:
        return arrays
    return tuple(
        None if a is None else _constrain(a, row_pspec(a.ndim))
        for a in arrays
    )
