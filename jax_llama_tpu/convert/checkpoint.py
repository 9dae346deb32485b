"""Orbax checkpoint save/restore with sharding-aware loading.

The reference has **no** checkpoint-save path at all — it re-runs the torch
conversion into host RAM on every process start (SURVEY.md §5
"Checkpoint/resume": load-only, convert.sh broken).  Here conversion is a
one-time offline step; serving restores directly from an Orbax checkpoint,
and when a mesh is given each host reads only the shards it owns
(``ocp.StandardCheckpointer`` + sharded abstract tree), so a 70B restore
never materializes the full model on one host.

Layout on disk:
    <dir>/params/...     Orbax tree of arrays
    <dir>/config.json    LLaMAConfig fields
    <dir>/manifest.json  per-file sha256 + size, verified on restore

Saves are ATOMIC: the checkpoint is assembled in a temp sibling
directory and renamed into place, so a crash mid-save never leaves a
half-written tree at the target path (a pre-existing checkpoint is
swapped aside and removed only after the new tree has landed).  The
manifest is written over the finished tree at save time; restore
verifies every listed file's size and sha256 first, so a truncated or
bit-flipped shard fails loudly before serving starts instead of
surfacing as silent garbage logits.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import shutil
import tempfile
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import orbax.checkpoint as ocp
from jax.sharding import Mesh, NamedSharding

from ..config import LLaMAConfig
from ..models.llama import init_params
from ..ops.quant import is_quantized, quantize_params

MANIFEST_NAME = "manifest.json"


def _sha256_file(path: Path) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _write_manifest(root: Path) -> None:
    """Record every file under ``root`` (sha256 + byte size), manifest
    excluded, keyed by POSIX-relative path."""
    files: Dict[str, Dict[str, Any]] = {}
    for p in sorted(root.rglob("*")):
        if p.is_file() and p.name != MANIFEST_NAME:
            files[p.relative_to(root).as_posix()] = {
                "sha256": _sha256_file(p),
                "bytes": p.stat().st_size,
            }
    with open(root / MANIFEST_NAME, "w") as f:
        json.dump({"version": 1, "files": files}, f, indent=2)


def verify_manifest(path: str) -> bool:
    """Verify every manifest-listed file's existence, size, and sha256.

    Returns False (nothing to verify) for pre-manifest checkpoints;
    raises ValueError naming every bad shard otherwise.  Size is checked
    before hashing so plain truncation is reported as truncation, not as
    a hash mismatch.
    """
    root = Path(path).absolute()
    mf = root / MANIFEST_NAME
    if not mf.exists():
        return False
    with open(mf) as f:
        manifest = json.load(f)
    errors = []
    for rel, want in manifest.get("files", {}).items():
        p = root / rel
        if not p.is_file():
            errors.append(f"{rel}: missing")
            continue
        size = p.stat().st_size
        if size != want["bytes"]:
            errors.append(
                f"{rel}: truncated/resized ({size} bytes, "
                f"recorded {want['bytes']})"
            )
            continue
        if _sha256_file(p) != want["sha256"]:
            errors.append(f"{rel}: sha256 mismatch (corrupted shard)")
    if errors:
        raise ValueError(
            f"checkpoint {root} failed integrity verification — "
            "refusing to restore corrupt weights: " + "; ".join(errors)
        )
    return True


def _promote(tmp: Path, path: Path) -> None:
    """Rename the finished tree into place — atomic when ``path`` does
    not exist; otherwise the old checkpoint is swapped aside first and
    removed only after the new tree has landed, so no crash point
    leaves ``path`` holding a partial tree (worst case: ``path``
    briefly absent with the old tree intact in a ``.trash`` sibling)."""
    if path.exists():
        trash = path.parent / f".{path.name}.trash-{os.getpid()}"
        if trash.exists():
            shutil.rmtree(trash)
        os.rename(path, trash)
        os.rename(tmp, path)
        shutil.rmtree(trash)
    else:
        os.rename(tmp, path)


def _atomic_save(path: Path, write: Callable[[Path], None]) -> None:
    """Assemble a checkpoint via ``write(tmp_dir)`` then promote it
    into ``path`` (see ``_promote``).

    Multi-process programs (jax.process_count() > 1, shared storage —
    the only topology Orbax multi-host saves support) must all hand
    Orbax the SAME directory, so the temp dir name is deterministic
    there; process 0 clears any stale one, every process syncs before
    writing and after Orbax finishes, and only process 0 hashes the
    manifest and performs the rename.  Single-process saves use a
    random temp dir (no collision with a concurrent saver) and clean it
    up on failure."""
    multi = jax.process_count() > 1
    path.parent.mkdir(parents=True, exist_ok=True)
    if multi:
        from jax.experimental import multihost_utils

        tmp = path.parent / f".{path.name}.tmp-save"
        if jax.process_index() == 0 and tmp.exists():
            shutil.rmtree(tmp)
        multihost_utils.sync_global_devices(f"ckpt-clear:{path.name}")
        tmp.mkdir(exist_ok=True)
    else:
        tmp = Path(tempfile.mkdtemp(
            prefix=f".{path.name}.tmp-", dir=path.parent
        ))
        # mkdtemp creates 0700 (private), and _promote's rename would
        # keep that — restore umask-default perms so a checkpoint saved
        # by one user stays restorable by another on shared storage
        # (matching the old path.mkdir behavior).
        um = os.umask(0)
        os.umask(um)
        os.chmod(tmp, 0o777 & ~um)
    try:
        write(tmp)
        if multi:
            multihost_utils.sync_global_devices(
                f"ckpt-written:{path.name}"
            )
        if jax.process_index() == 0:
            _write_manifest(tmp)
            _promote(tmp, path)
        if multi:
            multihost_utils.sync_global_devices(
                f"ckpt-promoted:{path.name}"
            )
    except BaseException:
        if not multi:
            shutil.rmtree(tmp, ignore_errors=True)
        raise


def save_checkpoint(path: str, params: Any, config: LLaMAConfig) -> None:
    """Write params + config to `path` — atomically, with an integrity
    manifest (module docstring).

    Quantized trees (``quantize_params`` output) round-trip: a marker in
    config.json tells ``load_checkpoint`` to build the matching abstract
    tree on restore.
    """
    final = Path(path).absolute()
    meta = dict(dataclasses.asdict(config), _quantized=is_quantized(params))

    def write(tmp: Path) -> None:
        with open(tmp / "config.json", "w") as f:
            json.dump(meta, f, indent=2)
        ckptr = ocp.StandardCheckpointer()
        ckptr.save(tmp / "params", params, force=True)
        ckptr.wait_until_finished()

    _atomic_save(final, write)


def load_config(path: str) -> Tuple[LLaMAConfig, bool]:
    config, quantized, is_train = _load_meta(path)
    return config, quantized


def _load_meta(path: str) -> Tuple[LLaMAConfig, bool, bool]:
    with open(Path(path) / "config.json") as f:
        meta = json.load(f)
    quantized = meta.pop("_quantized", False)
    is_train = meta.pop("_train_state", False)
    # Retired config fields that every checkpoint saved before their
    # removal still carries; any other unknown key still fails below.
    for key in ("prefill_kernel", "decode_kernel"):
        meta.pop(key, None)
    return LLaMAConfig(**meta), quantized, is_train


def load_checkpoint(
    path: str,
    mesh: Optional[Mesh] = None,
    *,
    fsdp: bool = False,
    verify: bool = True,
) -> Tuple[Any, LLaMAConfig]:
    """Restore (params, config).

    With ``mesh``: arrays are restored directly into their NamedSharding —
    per-host partial reads, no full-model host copy (this replaces the
    reference's convert-into-RAM-then-device_put startup, jax_example.py:
    21-26).  Without: plain host restore.

    ``verify`` (default True) checks the integrity manifest first — a
    truncated/corrupted shard raises before serving starts.  It re-reads
    every checkpoint byte to hash it; pass ``verify=False`` when restore
    I/O dominates startup and the storage layer already guarantees
    integrity.  Pre-manifest checkpoints skip the check silently.
    """
    path = Path(path).absolute()
    if verify:
        verify_manifest(path)
    config, quantized, is_train = _load_meta(path)
    if is_train:
        raise ValueError(
            f"{path} is a training checkpoint (params + optimizer state); "
            "restore it with load_train_state, or save serving weights "
            "with save_checkpoint(state.params, ...)"
        )

    def build():
        params = init_params(jax.random.PRNGKey(0), config)
        return quantize_params(params) if quantized else params

    shapes = jax.eval_shape(build)
    if mesh is not None:
        from ..parallel.partition import shard_abstract

        abstract = shard_abstract(shapes, mesh, config, fsdp=fsdp)
    else:
        abstract = shapes
    ckptr = ocp.StandardCheckpointer()
    layout = _saved_layout(ckptr, path / "params", config)
    if layout != "current":
        params = _restore_old_layout(
            ckptr, path, config, quantized, mesh, fsdp, layout
        )
    else:
        # Current layout (or metadata unavailable): restore directly,
        # letting any real failure (truncated files, version mismatch,
        # OOM) propagate as itself — a restore error must never be
        # mis-diagnosed as "old layout".
        params = ckptr.restore(path / "params", abstract)
    return params, config


def _saved_layout(ckptr, item_path: Path, config: LLaMAConfig) -> str:
    """Which param layout the checkpoint was saved in, decided from its
    own tree metadata (cheap — no array reads): "separate" (rounds 1-2
    q/k/v/gate/up), "d_first" (the r3 fused layout with the contracted D
    axis leading), or "current".  Unreadable metadata counts as current.
    """
    try:
        md = ckptr.metadata(item_path)
        # Orbax version skew: .metadata() has returned an object with
        # .item_metadata.tree, an object with .tree, and (current image)
        # the raw tree dict itself.  Accept all three shapes.
        tree = getattr(md, "item_metadata", md)
        tree = getattr(tree, "tree", tree)
        layers = tree.get("layers", {})
        if "q" in layers and "qkv" not in layers:
            return "separate"
        qkv_md = layers["qkv"]
        if isinstance(qkv_md, dict):  # QuantizedTensor: {q, scale} subtree
            qkv_md = qkv_md["q"]
        qkv_shape = tuple(qkv_md.shape)
    except Exception as e:
        # Fall back to "current", but say so: if the checkpoint really is
        # a legacy layout whose metadata read transiently failed, the
        # restore below will die with an Orbax shape mismatch — this line
        # is what points the reader at the metadata problem instead of at
        # a "corrupt checkpoint".
        logging.getLogger(__name__).warning(
            "checkpoint layout detection skipped (metadata read failed: "
            "%s: %s); assuming current layout — if restore now fails "
            "with a shape mismatch, the checkpoint may be a legacy "
            "layout whose metadata could not be read",
            type(e).__name__,
            e,
        )
        return "current"
    if len(qkv_shape) == 5 and qkv_shape[1] == config.dim:
        return "d_first"
    return "current"


def _to_d_first(lp: dict) -> dict:
    from ..models.llama import permute_d_axis

    return permute_d_axis(lp, to_d_first=True)


def _from_d_first(lp: dict) -> dict:
    from ..models.llama import permute_d_axis

    return permute_d_axis(lp, to_d_first=False)


def _old_layout_shapes(config: LLaMAConfig, layout: str, quantized: bool) -> Any:
    """Abstract param tree in a historical layout: "separate" (rounds 1-2
    q/k/v + gate/up) or "d_first" (r3 fused, D leading)."""
    from ..models.llama import split_qkv
    from ..ops.quant import quantize_params

    def build():
        params = init_params(jax.random.PRNGKey(0), config)
        if quantized:
            params = quantize_params(params)
        lp = dict(params["layers"])
        if layout == "d_first":
            lp = _to_d_first(lp)
        else:
            q, k, v = split_qkv(lp.pop("qkv"))
            gate_up = lp.pop("gate_up")
            lp.update(
                q=q, k=k, v=v, gate=gate_up[:, 0], up=gate_up[:, 1]
            )
        out = dict(params)
        out["layers"] = lp
        return out

    return jax.eval_shape(build)


def _restore_old_layout(ckptr, path, config, quantized, mesh, fsdp, layout):
    """Fallback for checkpoints saved in a historical layout: restore the
    old tree on host, migrate, then shard onto the mesh if one was given.

    The d_first→current migration is a pure axis permutation, exact for
    full-precision AND int8 trees (payload and scale permute together).
    Quantized SEPARATE-layout checkpoints (rounds 1-2) are refused:
    fusing them needs a quantized fuse_qkv (feature permutation + slot
    concat on payload and scales) that is not implemented — re-quantize
    from the full-precision source instead."""
    from ..models.llama import fuse_params

    if quantized and layout != "d_first":
        raise ValueError(
            f"{path} is an int8-quantized checkpoint in the old separate "
            "q/k/v layout; migrating it is not implemented — re-quantize "
            "from the full-precision checkpoint with quantize_params and "
            "save again"
        )
    old = ckptr.restore(
        path / "params", _old_layout_shapes(config, layout, quantized)
    )
    if layout == "d_first":
        params = dict(old)
        params["layers"] = _from_d_first(old["layers"])
    else:
        params = fuse_params(old)
    if mesh is not None:
        from ..parallel.partition import shard_params

        params = shard_params(params, mesh, config, fsdp=fsdp)
    return params


# ---------------------------------------------------------------------------
# Training checkpoint / resume
# ---------------------------------------------------------------------------

def save_train_state(path: str, state: Any, config: LLaMAConfig) -> None:
    """Write a full TrainState (params + optimizer state + step) + config.

    The reference cannot resume anything (SURVEY.md §5: checkpointing is
    load-only and its convert CLI is broken); this is the training half of
    the checkpoint story: crash-safe resume with optimizer moments intact.
    Atomic + manifest-verified like ``save_checkpoint`` — a periodic
    save that crashes mid-write must never destroy the previous good
    resume point.
    """
    final = Path(path).absolute()
    meta = dict(dataclasses.asdict(config), _train_state=True)

    def write(tmp: Path) -> None:
        with open(tmp / "config.json", "w") as f:
            json.dump(meta, f, indent=2)
        ckptr = ocp.StandardCheckpointer()
        ckptr.save(tmp / "state", state, force=True)
        ckptr.wait_until_finished()

    _atomic_save(final, write)


def _suffix_sharding_tree(abstract: Any, abstract_params: Any, mesh: Mesh) -> Any:
    """Assign shardings to an arbitrary state tree by param-path suffix.

    Optimizer moments (Adam mu/nu) are param-shaped subtrees nested inside
    optax's state tuples; their leaf paths END with the corresponding param
    path (e.g. ``(..., 'mu', 'layers', 'q')``).  Each state leaf whose path
    suffix + shape matches a param leaf inherits that param's sharding;
    everything else (counts, scalars) is replicated.
    """
    from jax.sharding import PartitionSpec as P

    param_leaves = [
        (tuple(_key_str(k) for k in kp), leaf.sharding, leaf.shape)
        for kp, leaf in jax.tree_util.tree_leaves_with_path(abstract_params)
    ]
    replicated = NamedSharding(mesh, P())

    def assign(kp, leaf):
        path = tuple(_key_str(k) for k in kp)
        for ppath, sharding, shape in param_leaves:
            if len(path) >= len(ppath) and path[-len(ppath):] == ppath \
                    and leaf.shape == shape:
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                            sharding=sharding)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=replicated)

    return jax.tree_util.tree_map_with_path(assign, abstract)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "name"):
        return str(k.name)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def load_train_state(
    path: str,
    optimizer: Any,
    mesh: Optional[Mesh] = None,
    *,
    fsdp: bool = False,
    verify: bool = True,
) -> Tuple[Any, LLaMAConfig]:
    """Restore (TrainState, config) for training resume.

    With ``mesh``: params and the param-shaped optimizer moments restore
    straight into their NamedShardings (per-host partial reads); scalar
    state (step, Adam count) is replicated.  ``verify`` as in
    ``load_checkpoint``.
    """
    from ..train import init_train_state

    path = Path(path).absolute()
    if verify:
        verify_manifest(path)
    config, _, is_train = _load_meta(path)
    if not is_train:
        raise ValueError(
            f"{path} is a serving checkpoint (params only); restore it "
            "with load_checkpoint"
        )

    shapes = jax.eval_shape(
        lambda: init_train_state(
            init_params(jax.random.PRNGKey(0), config), optimizer
        )
    )
    if mesh is not None:
        from ..parallel.partition import shard_abstract

        param_shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), config)
        )
        abstract_params = shard_abstract(param_shapes, mesh, config, fsdp=fsdp)
        abstract = _suffix_sharding_tree(shapes, abstract_params, mesh)
    else:
        abstract = shapes
    ckptr = ocp.StandardCheckpointer()
    state = ckptr.restore(path / "state", abstract)
    return state, config
