"""Device mesh construction and activation-sharding helpers.

The reference builds a ``(1, n_devices)`` mesh with axes ``('dp','mp')``
(``/root/reference/jax_example.py:12-13``) and gates its sharding-constraint
helper on a deprecated global-mesh API (``/root/reference/jax_llama/
partition.py:83-98``).  Here the mesh is an explicit context with four axes:

    data    — data parallel (batch), rides DCN between slices
    stage   — pipeline parallel (GPipe microbatches, parallel.pipeline);
              stage→stage+1 ppermute traffic is point-to-point, so outer
              ICI / DCN links suffice
    fsdp    — ZeRO-style param sharding (batch-combined with `data` for
              activations), inner ICI
    seq     — sequence/context parallel (ring attention), ICI
    tensor  — Megatron-style tensor parallel, innermost ICI

Axis sizes of 1 are free, so a single config covers 1-chip dev runs through
multi-host pods.  ``constrain`` translates *logical* axis names to mesh axes
and no-ops when no mesh is active, so model code stays mesh-agnostic.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "stage", "fsdp", "seq", "tensor")

# Logical-name -> mesh-axis translation for activation constraints.  The
# batch dimension is sharded over both data-parallel axes (pure-DP inference
# and FSDP training both land batch there).
LOGICAL_RULES = {
    "data": ("data", "fsdp"),
    "fsdp": "fsdp",
    "seq": "seq",
    "tensor": "tensor",
    None: None,
}

_local = threading.local()


def make_mesh(
    data: int = 1,
    stage: int = 1,
    fsdp: int = 1,
    seq: int = 1,
    tensor: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a 5-axis mesh.  Total axis product must equal device count.

    Axis order places `tensor` innermost so TP collectives ride the
    highest-bandwidth ICI links, `data` outermost so DP gradients/batches
    cross DCN, `stage` next-outermost (pipeline hops are point-to-point)
    (cf. the scaling-book mesh recipe).
    """
    devices = list(devices if devices is not None else jax.devices())
    want = data * stage * fsdp * seq * tensor
    if want != len(devices):
        raise ValueError(
            f"mesh {data}x{stage}x{fsdp}x{seq}x{tensor}={want} "
            f"!= {len(devices)} devices"
        )
    arr = np.asarray(devices).reshape(data, stage, fsdp, seq, tensor)
    return Mesh(arr, AXES)


def auto_mesh(tensor: Optional[int] = None) -> Mesh:
    """All local devices on the tensor axis (single-host TP), unless told
    otherwise."""
    n = len(jax.devices())
    tensor = tensor or n
    return make_mesh(data=n // tensor, tensor=tensor)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Activate a mesh for `constrain`/`shard_params` in this thread."""
    prev = getattr(_local, "mesh", None)
    _local.mesh = mesh
    try:
        yield mesh
    finally:
        _local.mesh = prev


def current_mesh() -> Optional[Mesh]:
    return getattr(_local, "mesh", None)


def logical_to_spec(*logical) -> P:
    """Translate logical axis names to a PartitionSpec."""
    return P(*(LOGICAL_RULES.get(name, name) for name in logical))


def constrain(x: jax.Array, *logical) -> jax.Array:
    """Apply a sharding constraint in logical-axis terms.

    No-ops when no mesh is active (single-device dev loop, parity tests) —
    the reference's equivalent no-op gate is partition.py:88-93, built on a
    deprecated API.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(*logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
