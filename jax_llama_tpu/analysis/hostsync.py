"""Host-boundary lint: device→host syncs, uploads-in-loops, tracer flow.

The serving stack's throughput story is a host-boundary budget — ONE
packed ``np.asarray`` fetch and ZERO steady-state uploads per chunk
dispatch (serving.py module docstring; asserted at runtime by
``make perf-smoke``).  That budget is easy to regress silently: a stray
``np.asarray`` on a device value, a ``float()`` on a tracer, or a
``jnp.*`` construction inside a per-token loop each re-introduce the
per-token host syncs and dispatches chunked decode exists to amortize
(their cost on a local chip: not measured) — and nothing fails until a
bench round notices.

This checker makes every crossing explicit.  It walks each audited
module's AST with a simple per-function taint analysis:

  * **taint sources** — ``self.<attr>`` for attributes in the module's
    device-state registry (:data:`DEVICE_SELF_ATTRS`) or with the
    ``d_`` device-twin prefix (any base object: ``pf.d_off``), results
    of ``jnp.*`` / ``jax.*`` / ``lax.*`` calls and of the registered
    jitted serving programs (:data:`DEVICE_RETURNING`), and parameters
    with conventional device names (:data:`DEVICE_PARAM_NAMES`);
    taint propagates through assignment (tuple unpacks taint every
    target), subscripts, attribute chains and arithmetic;
  * **sinks** — ``np.asarray``/``np.array`` on a tainted value,
    ``float``/``int``/``bool`` on a tainted value, ``.item()`` /
    ``.tolist()`` on a tainted value, and ``jax.device_get`` /
    ``block_until_ready`` unconditionally (rule ``host-fetch``);
    ``if``/``while`` tests referencing a tainted value (rule
    ``device-flow`` — Python truthiness on a device value is both a
    sync and a latent tracer error); ``jnp.*`` array construction /
    ``jax.device_put`` lexically inside a ``for``/``while`` loop
    (rule ``host-upload`` — a per-iteration H2D upload).

Each sanctioned crossing carries an ``# audit: <kind>(<reason>)``
pragma (common.py) — the allowlist IS the documentation: grep for
``audit: host-fetch`` and you have every device→host sync the serving
stack performs, with its justification.

``audit: host-upload`` likewise names every host→device crossing of the
serving loop, in its two forms: a COPY outside a jitted call
(``ContinuousBatcher._upload``: a fused admission's packed vector, once
an admission; the classic inserts' operands — counted by
``host_uploads_total`` and a dispatch record's ``uploads``) and a HOST
OPERAND of a dispatch's own call (numpy handed to the jitted program: a
row sync's packed matrix, the recurrent block's snapshot ids every chunk,
an eviction batch's block ids — no call of their own, so the rule above,
which looks for ``jnp.*`` constructions, does not see them; their pragmas
are the record).  A fused or decode dispatch without an admission makes
no copy; ``tests/test_perf_smoke.py`` pins the counts.

Functions that only execute at trace time (the jitted programs
themselves, and module-level helpers reachable ONLY from them) skip
the ``host-upload`` rule: a ``jnp.*`` call in a Python loop there is
loop unrolling inside one compiled program, not a runtime upload.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .common import (
    Finding, Pragmas, def_line_span, dotted_name as _dotted,
    iter_package_sources, jit_decorations, node_span, parse_module,
    pragma_findings,
)

CHECKER = "host-boundary"

# Modules under audit: the serving stack, where the host-boundary
# budget is load-bearing.  (Model/ops/engine code is device-side or
# offline; extend this list when a new module joins the serving path.)
AUDITED_MODULES = (
    "serving", "kvcache", "server", "obs", "degrade", "faults",
)

# Per-module device-state registry: ``self.<attr>`` names that hold
# jax arrays (device residency).  The generic ``d_`` prefix rule covers
# the device twins on ANY object; these are the exceptions that don't
# carry the prefix.
DEVICE_SELF_ATTRS: Dict[str, Set[str]] = {
    "serving": {
        "pool", "draft_pool", "tau", "keys", "params", "draft_params",
    },
    "kvcache": set(),
    "server": set(),
    "obs": set(),
    "degrade": set(),
    "faults": set(),
}

# Attribute names that hold device values on ANY base object
# (dataclass carriers like serving._Prefill / _Restore).
DEVICE_ANY_ATTRS = frozenset({"staged", "pool", "draft_pool"})

# Parameters with these names seed taint (module-level device helpers:
# kvcache.fetch_slab(pool, ...), adopt_into_pool(pool, staged), ...).
DEVICE_PARAM_NAMES = frozenset({
    "pool", "draft_pool", "t_pool", "d_pool", "params", "draft_params",
    "t_params", "d_params", "staged", "pool_arrays",
})

# Module-level callables whose results live on device (the jitted
# serving programs plus the device-returning kvcache helpers).  The
# lowering auditor's contract registry is the authority for the jitted
# subset; this adds the non-jit wrappers.
DEVICE_RETURNING = frozenset({
    "_paged_decode_chunk", "_fused_chunk", "_spec_rounds_chunk",
    "_paged_insert", "_paged_suffix_insert", "_scatter_rows",
    "_release_blocks", "_adopt_jit", "adopt_into_pool", "stage_restore",
    "init_pool", "_gather_cache", "_scatter_back", "_pool_as_cache",
    # recurrent state layers: the per-slot state and the snapshot pool are
    # fields of ``pool`` (covered above); these cut rows out of them and
    # put them back, on the device: a snapshot copy or a state reset that
    # fetched either would be a finding
    "_snapshot_rows", "_state_into_rows", "init_state",
})

# Metadata attributes of device arrays — host-resident, never a sync.
_METADATA_ATTRS = frozenset({
    "shape", "dtype", "ndim", "size", "sharding", "block_size",
    "n_blocks", "quantized",
})

_FETCH_NP_FUNCS = frozenset({"asarray", "array"})
_FETCH_BUILTINS = frozenset({"float", "int", "bool"})
_FETCH_METHODS = frozenset({"item", "tolist"})
_UPLOAD_JNP_FUNCS = frozenset({
    "asarray", "array", "zeros", "ones", "full", "arange", "eye",
    "zeros_like", "ones_like", "full_like",
})


def _jit_function_names(tree: ast.Module) -> Set[str]:
    """Module-level defs wrapped in jax.jit (common.jit_decorations —
    shared with the lowering auditor's coverage gate)."""
    return set(jit_decorations(tree))


def _trace_time_functions(tree: ast.Module, jitted: Set[str]) -> Set[str]:
    """Module-level functions whose EVERY intra-module caller is
    trace-time — their bodies run at trace time, so ``jnp.*``-in-a-loop
    there is unrolling, not a runtime upload.

    Fixpoint over the caller relation: a function is trace-time iff it
    is jitted, or it has at least one caller and all of them are
    trace-time (so two-level helper chains under a jitted program stay
    exempt).  Calls from class methods / nested defs count as HOST
    callers, and an uncalled function is host by default (it may be an
    external entry point)."""
    funcs: Dict[str, ast.FunctionDef] = {
        n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)
    }

    # callers[f] = module-function names calling f; None marks a call
    # from host context (a method or a nested/class scope).
    callers: Dict[str, Set[Optional[str]]] = {n: set() for n in funcs}

    def record(caller: Optional[str], fn: ast.AST) -> None:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = _dotted(node.func)
                if name in callers:
                    callers[name].add(caller)

    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            record(node.name, node)
        elif isinstance(node, ast.ClassDef):
            record(None, node)

    trace_time = set(jitted)
    changed = True
    while changed:
        changed = False
        for name in funcs:
            if name in trace_time:
                continue
            cs = callers[name]
            if cs and all(c is not None and c in trace_time
                          for c in cs):
                trace_time.add(name)
                changed = True
    return trace_time


class _FunctionLint(ast.NodeVisitor):
    """Taint + sink walk of one function body."""

    def __init__(self, module: str, path: str, fn: ast.FunctionDef,
                 pragmas: Pragmas, trace_time: bool):
        self.module = module
        self.path = path
        self.fn = fn
        self.pragmas = pragmas
        self.trace_time = trace_time
        self.findings: List[Finding] = []
        self.tainted: Set[str] = {
            a.arg for a in (
                list(fn.args.posonlyargs) + list(fn.args.args)
                + list(fn.args.kwonlyargs)
            )
            if a.arg in DEVICE_PARAM_NAMES
        }
        self.loop_depth = 0
        self._stmt_stack: List[ast.stmt] = []

    # -- taint ---------------------------------------------------------------

    def _is_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _METADATA_ATTRS:
                return False
            if node.attr.startswith("d_") or node.attr in DEVICE_ANY_ATTRS:
                return True
            base = _dotted(node.value)
            if base == "self":
                return node.attr in DEVICE_SELF_ATTRS.get(
                    self.module, set()
                )
            return self._is_tainted(node.value)
        if isinstance(node, ast.Call):
            return self._call_returns_device(node)
        if isinstance(node, ast.Subscript):
            return self._is_tainted(node.value)
        if isinstance(node, (ast.BinOp, ast.BoolOp, ast.Compare,
                             ast.UnaryOp, ast.IfExp, ast.Starred,
                             ast.Tuple, ast.List)):
            return any(
                self._is_tainted(c) for c in ast.iter_child_nodes(node)
                if isinstance(c, ast.expr)
            )
        return False

    def _call_returns_device(self, call: ast.Call) -> bool:
        name = _dotted(call.func) or ""
        head = name.split(".", 1)[0]
        leaf = name.rsplit(".", 1)[-1]
        if name == "getattr" and call.args and self._is_tainted(
            call.args[0]
        ):
            return True
        if head in ("jnp", "lax"):
            return True
        if head == "jax" and leaf not in ("device_get",):
            return True
        if leaf in DEVICE_RETURNING:
            return True
        if isinstance(call.func, ast.Attribute):
            # method chains on device values (x.at[i].set(...), .astype)
            return self._is_tainted(call.func.value)
        return False

    # -- findings ------------------------------------------------------------

    def _spans(self, node: ast.AST) -> Tuple[Tuple[int, int], ...]:
        spans = [node_span(node), def_line_span(self.fn)]
        if self._stmt_stack:
            spans.append(node_span(self._stmt_stack[-1]))
        return tuple(spans)

    def _flag(self, node: ast.AST, rule: str, message: str) -> None:
        if self.pragmas.allows(rule, *self._spans(node)):
            return
        self.findings.append(Finding(
            checker=CHECKER, rule=rule, path=self.path,
            line=getattr(node, "lineno", 0), message=message,
            sanctionable=True,
        ))

    # -- visitors ------------------------------------------------------------

    def visit(self, node: ast.AST):
        if isinstance(node, ast.stmt):
            self._stmt_stack.append(node)
            try:
                return super().visit(node)
            finally:
                self._stmt_stack.pop()
        return super().visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef):
        if node is self.fn:
            self.generic_visit(node)
        # nested defs are linted separately (fresh scope)

    visit_AsyncFunctionDef = visit_FunctionDef

    @staticmethod
    def _target_names(target: ast.AST) -> List[str]:
        """Plain-Name assignment targets only: ``pf.d_off = ...`` must
        not taint ``pf`` itself."""
        if isinstance(target, ast.Name):
            return [target.id]
        if isinstance(target, (ast.Tuple, ast.List)):
            out: List[str] = []
            for elt in target.elts:
                out.extend(_FunctionLint._target_names(elt))
            return out
        if isinstance(target, ast.Starred):
            return _FunctionLint._target_names(target.value)
        return []

    def visit_Assign(self, node: ast.Assign):
        self.generic_visit(node)
        tainted = self._is_tainted(node.value)
        for target in node.targets:
            for name in self._target_names(target):
                if tainted:
                    self.tainted.add(name)
                else:
                    self.tainted.discard(name)

    def visit_AugAssign(self, node: ast.AugAssign):
        self.generic_visit(node)
        if isinstance(node.target, ast.Name) and self._is_tainted(
            node.value
        ):
            self.tainted.add(node.target.id)

    def visit_For(self, node: ast.For):
        self.loop_depth += 1
        try:
            self.generic_visit(node)
        finally:
            self.loop_depth -= 1

    @staticmethod
    def _identity_test(test: ast.AST) -> bool:
        """``x is None`` / ``x is not None`` never call ``__bool__`` on
        the operand — host-safe even on a device value."""
        return isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
        )

    def visit_While(self, node: ast.While):
        if not self._identity_test(node.test) and self._is_tainted(
            node.test
        ):
            self._flag(
                node.test, "device-flow",
                "while-loop condition evaluates a device value on the "
                "host (implicit sync; tracer error under jit)",
            )
        self.loop_depth += 1
        try:
            self.generic_visit(node)
        finally:
            self.loop_depth -= 1

    def visit_If(self, node: ast.If):
        if not self._identity_test(node.test) and self._is_tainted(
            node.test
        ):
            self._flag(
                node.test, "device-flow",
                "branch condition evaluates a device value on the host "
                "(implicit sync; tracer error under jit)",
            )
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        self.generic_visit(node)
        name = _dotted(node.func) or ""
        head, _, rest = name.partition(".")
        leaf = name.rsplit(".", 1)[-1]

        # Unconditional syncs.
        if name == "jax.device_get" or leaf == "block_until_ready":
            self._flag(
                node, "host-fetch",
                f"{leaf}() is an unconditional device sync",
            )
            return
        # np.asarray / np.array on a device value.
        if head in ("np", "numpy") and rest in _FETCH_NP_FUNCS:
            if any(self._is_tainted(a) for a in node.args):
                self._flag(
                    node, "host-fetch",
                    f"np.{rest}() on a device value is a blocking "
                    "device->host fetch",
                )
            return
        # float()/int()/bool() on a device value.
        if name in _FETCH_BUILTINS and node.args and self._is_tainted(
            node.args[0]
        ):
            self._flag(
                node, "host-fetch",
                f"{name}() on a device value is a blocking scalar "
                "device->host fetch",
            )
            return
        # .item() / .tolist() on a device value.
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _FETCH_METHODS
            and self._is_tainted(node.func.value)
        ):
            self._flag(
                node, "host-fetch",
                f".{node.func.attr}() on a device value is a blocking "
                "device->host fetch",
            )
            return
        # jnp construction / device_put inside a host loop.
        is_upload = (
            (head == "jnp" and rest in _UPLOAD_JNP_FUNCS)
            or name == "jax.device_put"
        )
        if is_upload and self.loop_depth > 0 and not self.trace_time:
            self._flag(
                node, "host-upload",
                f"{name}() inside a loop is a per-iteration "
                "host->device upload",
            )


class HostBoundaryChecker:
    """Run the lint over source text / the audited package modules."""

    def check_source(self, path: str, source: str,
                     module: Optional[str] = None) -> List[Finding]:
        module = module or path.rsplit("/", 1)[-1].replace(".py", "")
        tree, findings = parse_module(path, source, CHECKER)
        if tree is None:
            return findings
        pragmas = Pragmas.scan(source)
        findings.extend(pragma_findings(path, pragmas, CHECKER))
        jitted = _jit_function_names(tree)
        trace_time = _trace_time_functions(tree, jitted)

        def lint_fn(fn: ast.FunctionDef, in_class: bool) -> None:
            is_trace = (not in_class) and fn.name in trace_time
            # Pass 1 computes the function's final taint set (so taint
            # assigned late in a loop body still covers early sinks on
            # the next iteration); pass 2 reports with it pre-seeded.
            seed = _FunctionLint(
                module, path, fn, pragmas, trace_time=is_trace
            )
            seed.visit(fn)
            walker = _FunctionLint(
                module, path, fn, pragmas, trace_time=is_trace
            )
            walker.tainted |= seed.tainted
            walker.visit(fn)
            findings.extend(walker.findings)

        def lint_tree(fn: ast.FunctionDef, in_class: bool) -> None:
            lint_fn(fn, in_class)
            # Nested defs (closures, handler classes defined inside
            # methods) get their own fresh scope — host-side always.
            for sub in ast.walk(fn):
                if sub is not fn and isinstance(sub, ast.FunctionDef):
                    lint_fn(sub, in_class=True)

        for node in tree.body:
            if isinstance(node, ast.FunctionDef):
                lint_tree(node, in_class=False)
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, ast.FunctionDef):
                        lint_tree(sub, in_class=True)
        return findings

    def check_package(
        self, modules: Sequence[str] = AUDITED_MODULES
    ) -> List[Finding]:
        out: List[Finding] = []
        for path, source in iter_package_sources(only=modules):
            out.extend(self.check_source(path, source))
        return out
