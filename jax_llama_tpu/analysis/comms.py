"""Comms-budget contracts: collective counts for sharded lowerings.

PR 10's mesh pass proves donated buffers keep their sharding across a
dispatch (no reshard of the CARRIES), but it is blind to what GSPMD
does INSIDE the program: a gathered-view or write-back slab whose
sharding propagation loses the KV-head axis compiles to a full-pool
``all-gather`` in every scan iteration — token-identical, invisible to
every parity test, and it silently eats the tensor-sharding win on a
real interconnect.  (Exactly this was live when this pass landed: the
paged write-back replicated the pool 4x per decode body and 36x per
fused body until the view/plane sharding pins in serving.py /
models/llama.py fixed it.)

This pass walks each mesh-registered program's SHARDED lowering at two
levels and checks the contract's :class:`~.contracts.CommsBudget`:

  * the traced **jaxpr** (recursing into scan/while/cond bodies) for
    explicit collective primitives — ``psum``/``all_gather``-class ops
    that shard_map kernels emit directly; and
  * the **compiled module** text — GSPMD inserts the partition-time
    collectives nowhere earlier, so the compiled HLO is the only
    ground truth for propagation-chosen reshards.

Checks, hardest first:

  * ``pool-collective``: any collective whose RESULT is full-pool- or
    one-plane-shaped (the contract's forbidden shapes) is a hard
    finding — never budgetable.
  * ``comms-bytes``: the largest single collective result must fit
    ``max_bytes`` (activation-sized per-layer reductions pass; a
    pool-scale reshard is 1-2 orders larger at any geometry).
  * ``comms-count``: per-kind instruction counts within
    ``max_count`` (a kind absent from the budget allows zero).
  * ``no-comms-budget``: a mesh-registered program without a declared
    budget.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Tuple

from .common import Finding
from .contracts import REGISTRY, ProgramContract, pool_shapes
from .lowering import _resolve_program, _walk_jaxprs

CHECKER = "comms"

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    "all-to-all",
)

# jaxpr primitive name -> collective kind (explicit shard_map-style
# collectives; GSPMD's own live only in the compiled module).
JAXPR_COLLECTIVES = {
    "all_gather": "all-gather",
    "all_gather_invariant": "all-gather",
    "psum": "all-reduce",
    "psum2": "all-reduce",
    "all_reduce": "all-reduce",
    "psum_scatter": "reduce-scatter",
    "reduce_scatter": "reduce-scatter",
    "ppermute": "collective-permute",
    "all_to_all": "all-to-all",
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

# `%name = f32[2,8,16]{...} all-gather(...)` — single-array result.
_COLLECTIVE_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\s"
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(",
)
# `%name = (f32[2,8,16]{...}, s32[4]{0}) all-gather(...)` — variadic/
# combined and async collectives carry TUPLE results; missing them
# would let a full-pool reshard hide inside a combiner-merged op.
_TUPLE_COLLECTIVE_RE = re.compile(
    r"=\s*\(([^)]*)\)[^=]*?\s"
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _entry(dtype: str, dims: str) -> Tuple[Tuple[int, ...], int]:
    shape = tuple(int(d) for d in dims.split(",") if d)
    return shape, int(math.prod(shape)) * _DTYPE_BYTES.get(dtype, 4)


def collectives_in_text(
    text: str,
) -> List[Tuple[str, List[Tuple[Tuple[int, ...], int]]]]:
    """[(kind, [(result shape, result bytes), ...])] — one entry per
    collective INSTRUCTION in a compiled HLO module text, with every
    element of a tuple result listed.  Async pairs count the
    ``-start`` only (the ``-done`` carries no new transfer)."""
    out: List[Tuple[str, List[Tuple[Tuple[int, ...], int]]]] = []
    for line in text.splitlines():
        if "-done(" in line:
            continue
        m = _COLLECTIVE_RE.search(line)
        if m is not None:
            out.append((m.group(3), [_entry(m.group(1), m.group(2))]))
            continue
        m = _TUPLE_COLLECTIVE_RE.search(line)
        if m is not None:
            results = [
                _entry(d, dims)
                for d, dims in _SHAPE_RE.findall(m.group(1))
            ]
            if results:
                out.append((m.group(2), results))
    return out


def collectives_in_jaxpr(
    jaxpr: Any,
) -> List[Tuple[str, Tuple[int, ...], int]]:
    """Explicit collective equations in a (Closed)Jaxpr, recursing
    into scan/while/cond bodies.  Used ONLY for the pool-shape hard
    finding, never for budget counts: every jaxpr collective appears
    in the compiled module too (counting both would double-charge
    shard_map kernels), but a Pallas/custom-call body can hide its
    collectives from the HLO text — the jaxpr walk is the safety net
    for those."""
    out: List[Tuple[str, Tuple[int, ...], int]] = []
    for eqn in _walk_jaxprs(jaxpr):
        prim = getattr(eqn.primitive, "name", str(eqn.primitive))
        kind = JAXPR_COLLECTIVES.get(prim)
        if kind is None:
            continue
        for outvar in eqn.outvars:
            aval = getattr(outvar, "aval", None)
            shape = tuple(getattr(aval, "shape", ()))
            itemsize = getattr(
                getattr(aval, "dtype", None), "itemsize", 4
            )
            out.append(
                (kind, shape, int(math.prod(shape)) * int(itemsize))
            )
    return out


def _forbidden_shapes(
    contract: ProgramContract, argnames: Tuple[str, ...], args: tuple,
) -> set:
    import jax.tree_util as jtu

    shapes = set()
    if contract.forbidden_shapes is not None:
        shapes.update(tuple(s) for s in contract.forbidden_shapes(args))
    for name, arg in zip(argnames, args):
        for leaf in jtu.tree_leaves(
            arg,
            is_leaf=lambda x: hasattr(x, "block_size") and hasattr(x, "k"),
        ):
            if hasattr(leaf, "block_size") and hasattr(leaf, "k"):
                shapes.update(pool_shapes(leaf))
    return shapes


def check_comms(
    contract: ProgramContract,
    path_hint: Optional[str] = None,
) -> List[Finding]:
    """Audit one contract's sharded lowering against its comms budget."""
    findings: List[Finding] = []
    path = path_hint or contract.module.replace(".", "/") + ".py"
    if contract.mesh_build is None:
        return findings
    if contract.comms is None:
        findings.append(Finding(
            checker=CHECKER, rule="no-comms-budget", path=path, line=0,
            message=(
                f"{contract.name}: mesh-registered program declares no "
                "CommsBudget — every sharded program must bound its "
                "collective footprint (see ProgramContract.comms)"
            ),
        ))
        return findings
    program = _resolve_program(contract)
    argnames, args, kwargs = contract.mesh_build()
    traced = program.trace(*args, **kwargs)
    compiled = traced.lower().compile()
    texts = compiled.as_text()
    text = "\n".join(texts) if isinstance(texts, (list, tuple)) else texts

    forbidden = _forbidden_shapes(contract, argnames, args)
    budget = contract.comms
    counts: Dict[str, int] = {}
    worst: Dict[str, Tuple[int, Tuple[int, ...]]] = {}

    def check_result(kind: str, shape: Tuple[int, ...],
                     nbytes: int) -> None:
        if shape in forbidden:
            findings.append(Finding(
                checker=CHECKER, rule="pool-collective",
                path=path, line=0,
                message=(
                    f"{contract.name} [mesh]: {kind} produces the "
                    f"pool shape {shape} — a full-pool reshard inside "
                    "the program (hard finding; never budgetable). "
                    "Pin the operand's sharding "
                    "(serve_mesh.constrain_view / "
                    "llama._constrain_heads) instead"
                ),
            ))
        elif nbytes > budget.max_bytes:
            findings.append(Finding(
                checker=CHECKER, rule="comms-bytes",
                path=path, line=0,
                message=(
                    f"{contract.name} [mesh]: {kind} of {shape} moves "
                    f"{nbytes} B (budget: {budget.max_bytes} B per "
                    "collective) — bigger than any per-layer reduction "
                    "the matmul sharding implies; a reshard is hiding "
                    "in the lowering"
                ),
            ))

    # Budget counts come from the COMPILED text only (one count per
    # instruction, tuple results included); the jaxpr walk below adds
    # only the pool-shape hard finding for collectives a custom-call
    # body might hide from the HLO text.
    for kind, results in collectives_in_text(text):
        counts[kind] = counts.get(kind, 0) + 1
        for shape, nbytes in results:
            if kind not in worst or nbytes > worst[kind][0]:
                worst[kind] = (nbytes, shape)
            check_result(kind, shape, nbytes)
    for kind, shape, nbytes in collectives_in_jaxpr(traced.jaxpr):
        if shape in forbidden:
            check_result(kind, shape, nbytes)
    for kind, n in sorted(counts.items()):
        allowed = budget.max_count.get(kind, 0)
        if n > allowed:
            findings.append(Finding(
                checker=CHECKER, rule="comms-count",
                path=path, line=0,
                message=(
                    f"{contract.name} [mesh]: {n} {kind} instructions "
                    f"in the compiled module (budget: {allowed}) — "
                    "the sharded lowering grew collectives beyond the "
                    "per-layer set the contract sanctions (worst "
                    f"operand: {worst[kind][1]}, {worst[kind][0]} B)"
                ),
            ))
    return findings


def check_package(
    registry: Dict[str, ProgramContract] = REGISTRY,
) -> List[Finding]:
    findings: List[Finding] = []
    for name in sorted(registry):
        findings.extend(check_comms(registry[name]))
    return findings
