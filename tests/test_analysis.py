"""Invariant auditor (jax_llama_tpu.analysis) — ``pytest -m analysis``.

Two halves:

  * **Fixture tests**: synthetic modules that deliberately violate each
    rule class (stray device->host sync, undonated pool arg, full-pool
    copy via a non-donated carry, unguarded field write, cross-thread
    holder access, upload-in-loop, device control flow) assert each
    checker catches its class — and that the matching ``# audit:``
    pragma sanctions it.
  * **Package-cleanliness gates** (tier-1): the REAL package must be
    clean under every static layer, and every jitted program the
    batcher dispatches must hold a registered lowering contract.  The
    abstract-trace layer (lowers all eight programs at a tiny geometry)
    is ``slow``-marked — ``make lint-invariants`` runs it on every
    lint invocation; tier-1 keeps the fast static gates.
"""

import os
import subprocess
import sys

import pytest

from jax_llama_tpu.analysis import run_all
from jax_llama_tpu.analysis.common import Pragmas
from jax_llama_tpu.analysis.hostsync import HostBoundaryChecker
from jax_llama_tpu.analysis.lockcheck import (
    CONFINEMENTS, LOCK_GUARDS, LockDisciplineChecker, LockGuard,
    ThreadConfinement,
)
from jax_llama_tpu.analysis.lowering import (
    check_lowering, check_static, check_traces,
)
from jax_llama_tpu.analysis.contracts import (
    REGISTRY, ProgramContract, clear_examples,
)
from jax_llama_tpu.analysis.__main__ import main as cli_main

pytestmark = pytest.mark.analysis


def rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# Pragma grammar
# ---------------------------------------------------------------------------

class TestPragmas:
    def test_single_line(self):
        p = Pragmas.scan("x = 1  # audit: host-fetch(the one fetch)\n")
        assert p.allows("host-fetch", (1, 1))
        assert not p.allows("host-upload", (1, 1))
        assert not p.bad_lines

    def test_multi_line_reason(self):
        src = (
            "# audit: racy-read(a reason that wraps\n"
            "# across two comment lines)\n"
            "x = 1\n"
        )
        p = Pragmas.scan(src)
        assert p.allows("racy-read", (3, 3))  # preceding-line rule
        assert not p.bad_lines

    def test_unknown_kind_is_bad(self):
        p = Pragmas.scan("# audit: host-fetchh(typo)\nx = 1\n")
        assert p.bad_lines
        assert not p.allows("host-fetch", (2, 2))

    def test_missing_reason_is_bad(self):
        p = Pragmas.scan("# audit: host-fetch()\nx = 1\n")
        assert p.bad_lines

    def test_bad_pragma_is_a_finding(self):
        fs = HostBoundaryChecker().check_source(
            "serving.py", "# audit: host-fetchh(typo)\nx = 1\n"
        )
        assert rules(fs) == ["bad-pragma"]


# ---------------------------------------------------------------------------
# Host-boundary lint fixtures
# ---------------------------------------------------------------------------

FETCH_FIXTURE = """
import numpy as np
import jax.numpy as jnp

class B:
    def step(self):
        packed = jnp.zeros((4,))
        return np.asarray(packed)
"""

FETCH_PRAGMA_FIXTURE = """
import numpy as np
import jax.numpy as jnp

class B:
    def step(self):
        packed = jnp.zeros((4,))
        # audit: host-fetch(the one packed fetch per chunk)
        return np.asarray(packed)
"""

SCALAR_FIXTURE = """
class B:
    def peek(self):
        return float(self.tau[0]), self.tau.item()
"""

FLOW_FIXTURE = """
class B:
    def step(self):
        if self.d_active.any():
            return 1
        while self.tau > 0:
            pass
"""

UPLOAD_FIXTURE = """
import jax.numpy as jnp

class B:
    def admit(self, rows):
        for r in rows:
            self.d_table = jnp.asarray(r)
"""

TRACE_TIME_FIXTURE = """
import functools
import jax
import jax.numpy as jnp

def helper(n):
    out = []
    for i in range(n):
        out.append(jnp.zeros((4,)))
    return out

@functools.partial(jax.jit, static_argnames=("n",))
def program(x, *, n):
    return sum(helper(n)) + x
"""

BLOCKING_FIXTURE = """
import jax

class B:
    def wait(self, staged):
        jax.block_until_ready(staged)
        jax.device_get(staged)
"""


class TestHostBoundary:
    def check(self, src, module="serving"):
        return HostBoundaryChecker().check_source(
            f"{module}.py", src, module=module
        )

    def test_stray_fetch_caught(self):
        assert rules(self.check(FETCH_FIXTURE)) == ["host-fetch"]

    def test_pragma_sanctions_fetch(self):
        assert self.check(FETCH_PRAGMA_FIXTURE) == []

    def test_scalar_fetches_caught(self):
        fs = self.check(SCALAR_FIXTURE)
        assert rules(fs) == ["host-fetch"] and len(fs) == 2

    def test_device_control_flow_caught(self):
        fs = self.check(FLOW_FIXTURE)
        assert rules(fs) == ["device-flow"] and len(fs) == 2

    def test_upload_in_loop_caught(self):
        assert rules(self.check(UPLOAD_FIXTURE)) == ["host-upload"]

    def test_trace_time_unrolling_not_flagged(self):
        # jnp-in-a-loop inside a helper reachable ONLY from a jitted
        # program is loop unrolling, not a runtime upload.
        assert self.check(TRACE_TIME_FIXTURE) == []

    def test_unconditional_syncs_caught(self):
        fs = self.check(BLOCKING_FIXTURE)
        assert rules(fs) == ["host-fetch"] and len(fs) == 2

    def test_numpy_mirror_not_flagged(self):
        # self.remaining is a numpy mirror: np.asarray on it is free.
        src = (
            "import numpy as np\n"
            "class B:\n"
            "    def f(self):\n"
            "        return np.asarray(self.remaining)\n"
        )
        assert self.check(src) == []

    def test_is_none_test_not_flagged(self):
        src = (
            "class B:\n"
            "    def f(self):\n"
            "        if self.pool is not None:\n"
            "            return 1\n"
        )
        assert self.check(src) == []

    def test_package_clean(self):
        assert HostBoundaryChecker().check_package() == []


# ---------------------------------------------------------------------------
# Lock-discipline fixtures
# ---------------------------------------------------------------------------

LOCK_FIXTURE = """
import threading

class Obs:
    def __init__(self):
        self._lock = threading.Lock()
        self.ring = []

    def good(self):
        with self._lock:
            self.ring.append(1)

    def bad(self):
        self.ring.append(2)

    def _drain_locked(self):
        self.ring.clear()

    def annotated(self):
        # audit: locked(caller holds self._lock)
        self.ring.append(3)
"""

CONFINED_FIXTURE = """
class Batcher:
    def step(self):
        self.table[0] = 1  # owner method: fine

    def stats(self):
        return len(self.table)  # foreign method, no pragma

class Server:
    def handler(self):
        return server.batcher.table  # holder access, no pragma
"""


def fixture_lock_registry():
    return LockDisciplineChecker(
        lock_guards=(LockGuard(
            module="fix", cls="Obs", lock="_lock",
            fields=frozenset({"ring"}),
        ),),
        confinements=(ThreadConfinement(
            module="fix", cls="Batcher", owner="the loop thread",
            fields=frozenset({"table"}),
            foreign_methods=frozenset({"stats"}),
            holders=frozenset({"batcher"}),
        ),),
    )


class TestLockDiscipline:
    def test_unguarded_write_caught_conventions_respected(self):
        fs = fixture_lock_registry().check_source(
            "fix.py", LOCK_FIXTURE, module="fix"
        )
        # exactly ONE finding: bad(); good()/_drain_locked()/annotated()
        # are sanctioned by with-block, naming convention, and pragma.
        assert rules(fs) == ["unlocked-access"]
        assert len(fs) == 1 and fs[0].line == 14

    def test_confinement_and_holder_caught(self):
        fs = fixture_lock_registry().check_source(
            "fix.py", CONFINED_FIXTURE, module="fix"
        )
        assert rules(fs) == ["foreign-thread-access"]
        assert len(fs) == 2  # stats() read + holder access; step() fine

    def test_stale_foreign_method_is_a_finding(self):
        checker = LockDisciplineChecker(
            lock_guards=(),
            confinements=(ThreadConfinement(
                module="fix", cls="Batcher", owner="loop",
                fields=frozenset({"table"}),
                foreign_methods=frozenset({"gone"}),
            ),),
        )
        fs = checker.check_source("fix.py", CONFINED_FIXTURE,
                                  module="fix")
        assert "stale-registry" in rules(fs)

    def test_registry_covers_the_stack(self):
        guarded = {(g.module, g.cls) for g in LOCK_GUARDS}
        confined = {(c.module, c.cls) for c in CONFINEMENTS}
        assert ("obs", "Observability") in guarded
        assert ("degrade", "DegradeManager") in guarded
        assert ("serving", "ContinuousBatcher") in confined
        assert ("server", "LLMServer") in confined

    def test_package_clean(self):
        assert LockDisciplineChecker().check_package() == []


# ---------------------------------------------------------------------------
# Lowering auditor
# ---------------------------------------------------------------------------

class TestLoweringStatic:
    def test_package_static_clean(self):
        assert check_static() == []

    def test_every_dispatched_program_registered(self):
        # The acceptance bar: every jitted program the batcher
        # dispatches holds a contract.  check_static() fails on any
        # unregistered jit-decorated function in serving/kvcache; the
        # dispatch sites are a subset of those.
        for name in (
            "_paged_decode_chunk", "_fused_chunk", "_spec_rounds_chunk",
            "_paged_insert", "_paged_suffix_insert", "_scatter_rows",
            "_release_blocks", "_adopt_jit",
        ):
            assert name in REGISTRY, f"{name} lost its contract"

    def test_every_registered_program_is_dispatched(self):
        # The converse: a registered program (a contract, a retrace
        # domain, a host-sync entry, a /metrics label) is one the
        # serving path calls.  One that only tests reach is a program
        # every operand change still has to thread through.
        import ast
        import inspect

        from jax_llama_tpu import kvcache, serving

        called = set()
        for mod in (serving, kvcache):
            for node in ast.walk(ast.parse(inspect.getsource(mod))):
                if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Name
                ):
                    called.add(node.func.id)
        programs = set(serving._programs())
        assert programs == set(REGISTRY)
        assert len(programs) == 8
        assert programs <= called, sorted(programs - called)

    def test_unregistered_program_caught(self):
        registry = {
            k: v for k, v in REGISTRY.items() if k != "_fused_chunk"
        }
        fs = check_static(registry=registry)
        assert rules(fs) == ["unregistered-program"]
        assert "_fused_chunk" in fs[0].message

    def test_stale_contract_caught(self):
        import dataclasses as dc

        registry = dict(REGISTRY)
        registry["_ghost_program"] = dc.replace(
            REGISTRY["_paged_insert"], name="_ghost_program"
        )
        assert "stale-contract" in rules(check_static(registry=registry))

    def test_aliased_jit_decorator_recognized(self):
        # `from jax import jit; @partial(jit, ...)` must not bypass
        # the coverage gate (or the host lint's trace-time exemption).
        from jax_llama_tpu.analysis.common import jit_decorations
        import ast as _ast

        src = (
            "import functools\n"
            "from jax import jit\n"
            "@functools.partial(jit, donate_argnames=('pool',))\n"
            "def sneaky(pool, x):\n"
            "    return pool, x\n"
            "@jit\n"
            "def bare(x):\n"
            "    return x\n"
        )
        assert set(jit_decorations(_ast.parse(src))) == {
            "sneaky", "bare",
        }

    def test_cli_lowering_with_paths_is_usage_error(self, capsys):
        assert cli_main(
            ["--checker", "lowering", "tests/test_analysis.py"]
        ) == 2
        assert "does not take file paths" in capsys.readouterr().err

    def test_donation_decorator_mismatch_caught(self):
        import dataclasses as dc

        registry = dict(REGISTRY)
        registry["_paged_insert"] = dc.replace(
            REGISTRY["_paged_insert"], donated=("pool", "keys")
        )
        fs = check_static(registry=registry)
        assert rules(fs) == ["donation-mismatch"]


# -- trace-layer fixtures (tiny standalone programs; no model) --------------

def _fixture_contract(fn_name, module, donated, live, bpr, build,
                      forbid_pool_shapes=False):
    # fixture contracts default the pool-shape rule OFF (their args are
    # bare arrays; a contract with it on and no derivable shapes is
    # itself a finding — see test_vacuous_shape_set_is_a_finding)
    return ProgramContract(
        name=fn_name, module=module, donated=donated,
        max_live_outputs=live, max_fetch_bytes_per_row=bpr,
        build=build, forbid_pool_shapes=forbid_pool_shapes,
    )


@pytest.fixture(scope="module")
def fixture_programs():
    """A module-like namespace with tiny jitted programs: one donates
    its pool correctly, one forgot, one materializes a full-pool copy
    through a non-donated carry."""
    import functools
    import sys
    import types

    import jax
    import jax.numpy as jnp

    mod = types.ModuleType("_analysis_fixture_programs")

    @functools.partial(jax.jit, donate_argnames=("pool",))
    def good(pool, x):
        return pool.at[0, 0].add(x.sum()), x * 2

    @jax.jit
    def undonated(pool, x):  # forgot donate_argnames
        return pool.at[0, 0].add(x.sum()), x * 2

    @functools.partial(jax.jit, donate_argnames=("pool",))
    def leaky(pool, x):
        # the classic regression: a pool-sized broadcast materializes
        # a full-pool copy (and an extra live pool-sized output)
        ghost = jnp.broadcast_to(x[0], pool.shape) + pool
        return pool.at[0, 0].add(x.sum()), ghost

    mod.good, mod.undonated, mod.leaky = good, undonated, leaky
    sys.modules[mod.__name__] = mod
    yield mod
    del sys.modules[mod.__name__]


def _args_builder():
    import jax.numpy as jnp

    pool = jnp.zeros((2, 2, 4, 8, 4), jnp.float32)
    x = jnp.ones((2,), jnp.float32)
    return ("pool", "x"), (pool, x), {}


def _pooled_args_builder():
    # wrap the pool in a BlockPool-shaped carrier so pool_shapes()
    # derives the forbidden shapes (registered as a pytree so jit can
    # flatten it)
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    @dc.dataclass(frozen=True)
    class MiniPool:
        k: object
        v: object
        pos: object
        k_scale: object = None
        v_scale: object = None

        @property
        def block_size(self):
            return 8

    jax.tree_util.register_pytree_node(
        MiniPool,
        lambda p: ((p.k, p.v, p.pos), None),
        lambda aux, ch: MiniPool(k=ch[0], v=ch[1], pos=ch[2]),
    )
    k = jnp.zeros((2, 2, 4, 8, 4), jnp.float32)
    pool = MiniPool(k=k, v=k, pos=jnp.zeros((4, 8), jnp.int32))
    x = jnp.ones((2,), jnp.float32)
    return ("pool", "x"), (pool, x), {}


@pytest.mark.slow
class TestLoweringTraceFixtures:
    def test_good_program_clean(self, fixture_programs):
        c = _fixture_contract(
            "good", fixture_programs.__name__, ("pool",), 1, 8,
            _args_builder,
        )
        assert check_lowering(c) == []

    def test_forgotten_donation_caught(self, fixture_programs):
        c = _fixture_contract(
            "undonated", fixture_programs.__name__, ("pool",), 1, 8,
            _args_builder,
        )
        fs = check_lowering(c)
        assert "donation-not-applied" in rules(fs)

    def test_full_pool_copy_and_fetch_surface_caught(
        self, fixture_programs
    ):
        import functools
        import jax
        import jax.numpy as jnp
        import sys
        import types

        mod = types.ModuleType("_analysis_fixture_pool_copy")

        @functools.partial(jax.jit, donate_argnames=())
        def copying(pool, x):
            # non-donated carry: returning pool broadcast-shaped
            plane = jnp.broadcast_to(x.sum(), tuple(pool.k.shape))
            return plane + pool.k, x * 2

        mod.copying = copying
        sys.modules[mod.__name__] = mod
        try:
            c = _fixture_contract(
                "copying", mod.__name__, (), 2, 8,
                _pooled_args_builder, forbid_pool_shapes=True,
            )
            fs = check_lowering(c)
            assert "full-pool-copy" in rules(fs)
            # the pool-sized live output also blows the byte budget
            assert "fetch-bytes" in rules(fs)
        finally:
            del sys.modules[mod.__name__]

    def test_vacuous_shape_set_is_a_finding(self, fixture_programs):
        # forbid_pool_shapes with nothing derivable must NOT pass
        # silently (the silent-cap failure mode).
        c = _fixture_contract(
            "good", fixture_programs.__name__, ("pool",), 1, 8,
            _args_builder, forbid_pool_shapes=True,
        )
        assert "no-forbidden-shapes" in rules(check_lowering(c))

    def test_live_output_count_enforced(self, fixture_programs):
        c = _fixture_contract(
            "good", fixture_programs.__name__, ("pool",), 0, 8,
            _args_builder,
        )
        fs = check_lowering(c)
        assert "fetch-count" in rules(fs)


@pytest.mark.slow
class TestLoweringTracePackage:
    def test_all_contracts_trace_clean(self):
        # Lowers all eight registered programs at the tiny example
        # geometry: donation resolves, fetch surface within budget,
        # no pool-shaped copy-class equations.  ~30 s cold.
        clear_examples()
        assert check_traces() == []

    def test_mesh_contracts_trace_clean(self):
        # The serving-mesh pass: every contract with a mesh_build
        # lowers its SHARDED variant (donor attributes present for all
        # donated leaves) and runs it once proving sharding stability
        # (donated inputs leave with the sharding they entered with).
        from jax_llama_tpu.analysis.lowering import check_mesh_traces

        clear_examples()
        assert check_mesh_traces() == []


def test_mesh_contract_registry_consistent():
    """Cheap (tier-1) registry hygiene for the mesh pass: the two
    chunk programs carry mesh variants, every mesh_aliases key is a
    declared donated arg, and alias positions are unique."""
    from jax_llama_tpu.analysis.contracts import REGISTRY

    with_mesh = {
        n: c for n, c in REGISTRY.items() if c.mesh_build is not None
    }
    assert {"_paged_decode_chunk", "_fused_chunk"} <= set(with_mesh)
    for name, c in with_mesh.items():
        assert c.mesh_aliases, name
        assert set(c.mesh_aliases) <= set(c.donated), name
        positions = list(c.mesh_aliases.values())
        assert len(positions) == len(set(positions)), name


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCLI:
    def test_clean_package_exits_zero(self, capsys):
        assert cli_main(["--no-trace"]) == 0
        assert "clean" in capsys.readouterr().out

    def test_violating_file_exits_nonzero(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "import numpy as np\nimport jax.numpy as jnp\n"
            "class B:\n"
            "    def f(self):\n"
            "        v = jnp.zeros((2,))\n"
            "        return np.asarray(v)\n"
        )
        assert cli_main([str(bad)]) == 1
        assert "host-fetch" in capsys.readouterr().out

    def test_lock_fixture_exits_nonzero(self, tmp_path, capsys):
        # the generic d_-twin rule needs no registry: an obs-module
        # fixture exercising the serving registry instead
        bad = tmp_path / "serving.py"
        bad.write_text(
            "class ContinuousBatcher:\n"
            "    def stats(self):\n"
            "        return len(self.queue)\n"
        )
        assert cli_main([str(bad)]) == 1
        assert "foreign-thread-access" in capsys.readouterr().out

    def test_json_output(self, tmp_path, capsys):
        import json as _json

        bad = tmp_path / "bad.py"
        bad.write_text(
            "import jax\nclass B:\n"
            "    def f(self, staged):\n"
            "        jax.block_until_ready(staged)\n"
        )
        # --json uses the per-pass stable exit codes (host-boundary =
        # 10); findings objects carry the machine-readable fields.
        assert cli_main(["--json", str(bad)]) == 10
        payload = _json.loads(capsys.readouterr().out)
        assert payload and payload[0]["rule"] == "host-fetch"
        assert payload[0]["checker"] == "host-boundary"
        assert payload[0]["severity"] == "error"
        assert payload[0]["sanctionable"] in (True, False)

    @pytest.mark.slow
    def test_cli_contracts_hook_donation_and_pool_copy(
        self, fixture_programs, capsys
    ):
        """The acceptance-criteria fixture classes through the CLI:
        a forgotten donation and a full-pool copy each exit non-zero
        via ``--contracts`` (an external fixture REGISTRY)."""
        import sys as _sys
        import types

        reg = types.ModuleType("_analysis_fixture_registry")
        reg.REGISTRY = {
            "undonated": _fixture_contract(
                "undonated", fixture_programs.__name__, ("pool",), 1,
                8, _args_builder,
            ),
        }
        _sys.modules[reg.__name__] = reg
        try:
            rc = cli_main(
                ["--checker", "lowering", "--contracts", reg.__name__]
            )
            out = capsys.readouterr().out
            assert rc == 1 and "donation-not-applied" in out
        finally:
            del _sys.modules[reg.__name__]

    @pytest.mark.slow
    def test_module_entrypoint_subprocess(self):
        # the acceptance-criteria invocation, end to end.  CPU ONLY: a
        # child that imports jax from a process that already holds it is
        # harmless here and hangs or fails on a chip (one process per
        # chip) — never copy this into anything that runs there.
        proc = subprocess.run(
            [sys.executable, "-m", "jax_llama_tpu.analysis",
             "--no-trace"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# run_all: the tier-1 cleanliness gate
# ---------------------------------------------------------------------------

def test_package_clean_static_gate():
    """The PR gate: every checker's static layer is clean on the
    package — a stray sync / unguarded access / contract drift fails
    tier-1 here before any bench round notices."""
    findings = run_all(trace=False)
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# Retrace auditor (analysis/retrace.py)
# ---------------------------------------------------------------------------

_RETRACE_FIXTURE = '''
import functools, jax
import numpy as np
import jax.numpy as jnp
from jax_llama_tpu.engine import pow2_bucket

@functools.partial(jax.jit, static_argnames=("width",))
def _prog(x, *, width):
    return x[:width]

class Batcher:
    def __init__(self):
        self.cap = 8
    def good(self, req):
        w = pow2_bucket(len(req))
        buf = np.zeros((w,), np.int32)
        return _prog(jnp.asarray(buf), width=min(len(req), self.cap))
    def bad_static(self, req):
        buf = np.zeros((self.cap,), np.int32)
        return _prog(jnp.asarray(buf), width=len(req))
    def bad_shape(self, req):
        buf = np.zeros((len(req),), np.int32)
        return _prog(jnp.asarray(buf), width=self.cap)
    def sanctioned(self, req):
        buf = np.zeros((len(req),), np.int32)  # audit: trace-domain(fixture: caller guarantees <= 4 lengths)
        # audit: trace-domain(fixture: caller-bounded)
        return _prog(jnp.asarray(buf), width=len(req))
'''


class TestRetraceStatic:
    def _registry(self, max_cache_keys=4):
        return {"_prog": ProgramContract(
            name="_prog", module="retrace_fixture", donated=(),
            max_live_outputs=1, max_fetch_bytes_per_row=1 << 20,
            max_cache_keys=max_cache_keys,
        )}

    def _check(self):
        from jax_llama_tpu.analysis.retrace import check_module_source

        return check_module_source(
            "retrace_fixture.py", _RETRACE_FIXTURE,
            registry=self._registry(),
        )

    def test_unbounded_static_arg_caught(self):
        fs = self._check()
        assert any(
            f.rule == "unbounded-trace-domain" and "bad_static" in
            f.message and "static arg" in f.message for f in fs
        ), [f.render() for f in fs]

    def test_unbounded_array_dim_caught(self):
        fs = self._check()
        assert any(
            f.rule == "unbounded-trace-domain" and "bad_shape" in
            f.message for f in fs
        ), [f.render() for f in fs]

    def test_bounded_and_sanctioned_paths_clean(self):
        fs = self._check()
        assert not any(
            "good" in f.message or "sanctioned" in f.message
            for f in fs
        ), [f.render() for f in fs]
        # the findings are pragma-sanctionable and say so
        assert all(f.sanctionable for f in fs)

    def test_missing_cache_key_budget_is_finding(self):
        from jax_llama_tpu.analysis.retrace import check_static

        fs = check_static(registry=self._registry(max_cache_keys=None))
        assert any(f.rule == "no-cache-key-budget" for f in fs)

    def test_every_contract_declares_cache_key_budget(self):
        assert all(
            c.max_cache_keys is not None for c in REGISTRY.values()
        ), "registered programs must bound their jit-cache domains"

    def test_package_retrace_static_clean(self):
        from jax_llama_tpu.analysis.retrace import check_static

        fs = check_static()
        assert fs == [], "\n".join(f.render() for f in fs)


@pytest.mark.slow
def test_retrace_runtime_drill_within_contract():
    """The jit-cache drill: a real admission sweep must stay within
    every contract's max_cache_keys (the runtime half of the retrace
    contract; ~60 s of tiny-model compiles)."""
    from jax_llama_tpu.analysis.retrace import check_runtime

    fs = check_runtime()
    assert fs == [], "\n".join(f.render() for f in fs)


@pytest.mark.slow
def test_classic_insert_width_is_bucketed():
    """Regression pin for the over-wide _paged_insert trace-key domain
    the retrace pass surfaced: whole-prompt admissions in DIFFERENT
    raw block counts but the same pow2 bucket must share ONE compiled
    executable (pre-fix: P was only block-rounded, one cache entry per
    distinct prompt block count)."""
    import numpy as np

    from jax_llama_tpu import serving
    from jax_llama_tpu.analysis.contracts import (
        _MAXLEN, _VOCAB, _tiny_config_params,
    )
    from jax_llama_tpu.serving import ContinuousBatcher

    cfg, params = _tiny_config_params()
    cb = ContinuousBatcher(
        params, cfg, n_slots=2, max_len=_MAXLEN, block_size=8,
        prefix_cache=False,
    )
    rng = np.random.RandomState(3)
    before = serving.jit_cache_entries()["_paged_insert"]
    if before < 0:
        pytest.skip("jax hides the executable cache")
    # 20 tokens = 3 blocks and 28 tokens = 4 blocks, both bucket to 4
    for n in (20, 28):
        cb.submit(list(rng.randint(1, _VOCAB, n)), max_new_tokens=2)
        cb.run_to_completion()
    after = serving.jit_cache_entries()["_paged_insert"]
    assert after - before == 1, (
        f"two same-bucket admissions compiled {after - before} "
        "_paged_insert variants (want 1: the pow2 group width)"
    )


# ---------------------------------------------------------------------------
# Schedule explorer (analysis/schedules.py)
# ---------------------------------------------------------------------------

class TestSchedules:
    def _toctou_model(self, safe):
        from jax_llama_tpu.analysis.schedules import Op, ScheduleModel

        def make():
            class PF:
                remaining = 7

            class S:
                pass

            s = S()
            s.pf = PF()
            return s

        def racy_reader(s):
            if s.pf is not None:
                return s.pf.remaining
            return 0

        def safe_reader(s):
            pf = s.pf
            if pf is not None:
                return pf.remaining
            return 0

        return ScheduleModel(
            name="fixture-toctou", module="x", func="reader",
            claim="snapshot", make=make,
            writers={"loop": (
                Op("null", lambda s, c: setattr(s, "pf", None),
                   frozenset({"pf"})),
            )},
            reader=safe_reader if safe else racy_reader,
            trace_fn="safe_reader" if safe else "racy_reader",
        )

    def test_toctou_reader_fails_with_counterexample(self):
        from jax_llama_tpu.analysis.schedules import explore

        fails = explore(self._toctou_model(safe=False))
        assert fails and "AttributeError" in fails[0], fails

    def test_snapshot_safe_reader_passes(self):
        from jax_llama_tpu.analysis.schedules import explore

        assert explore(self._toctou_model(safe=True)) == []

    def test_single_writer_violation_is_structural(self):
        from jax_llama_tpu.analysis.schedules import (
            Op, ScheduleModel, explore,
        )

        m = ScheduleModel(
            name="two-writers", module="x", func="f",
            claim="single-writer",
            make=lambda: type("S", (), {"n": 0})(),
            writers={
                "a": (Op("wa", lambda s, c: setattr(s, "n", 1),
                         frozenset({"n"})),),
                "b": (Op("wb", lambda s, c: setattr(s, "n", 2),
                         frozenset({"n"})),),
            },
        )
        fails = explore(m)
        assert fails and "single-writer claim is structurally void" in \
            fails[0]

    def test_happens_before_edge_enforced(self):
        from jax_llama_tpu.analysis.schedules import (
            Op, ScheduleModel, explore,
        )

        def make():
            s = type("S", (), {})()
            s.x = None
            return s

        def read(s, c):
            assert s.x is not None, "read before write"

        write = Op("write", lambda s, c: setattr(s, "x", c),
                   frozenset({"x"}))
        base = dict(
            name="hb", module="x", func="f", claim="happens-before",
            make=make,
            writers={"main": (write,), "loop": (Op("read", read),)},
        )
        # without the edge some interleaving reads first...
        assert explore(ScheduleModel(**base)) != []
        # ...the declared edge makes every schedule safe
        assert explore(ScheduleModel(
            **base, after={"loop": ("main", "write")}
        )) == []

    def test_unmodeled_pragma_is_finding(self):
        from jax_llama_tpu.analysis.schedules import check_package

        src = (
            "class C:\n"
            "    def f(self):\n"
            "        # audit: racy-read(nobody modeled this)\n"
            "        return self.x\n"
        )
        fs = check_package(models=[], sources=[("fixmod.py", src)])
        assert [f.rule for f in fs] == ["unmodeled-pragma"]

    def test_stale_model_is_finding(self):
        from jax_llama_tpu.analysis.schedules import (
            ScheduleModel, check_package,
        )

        ghost = ScheduleModel(
            name="ghost", module="serving", func="no_such_method",
            claim="owner-thread", make=lambda: object(), writers={},
        )
        fs = check_package(models=[ghost])
        assert any(f.rule == "stale-model" for f in fs)

    def test_every_pragma_site_has_a_passing_model(self):
        """The tier-1 gate: every racy-read/unguarded pragma in the
        package resolves to a schedule model and every model's
        exploration passes (sub-second: the explorers preempt real
        stats()/_health() readers line-by-line)."""
        from jax_llama_tpu.analysis.schedules import check_package

        fs = check_package()
        assert fs == [], "\n".join(f.render() for f in fs)

    def test_pragma_sites_found(self):
        from jax_llama_tpu.analysis.schedules import pragma_sites

        keys = {(s.module, s.func) for s in pragma_sites()}
        # the load-bearing cross-thread surfaces must be in the scan
        assert ("serving", "stats") in keys
        assert ("serving", "_window_acceptance") in keys
        assert ("server", "_health") in keys
        assert ("server", "_watchdog") in keys


# ---------------------------------------------------------------------------
# Metrics-registry lint (analysis/metricscheck.py)
# ---------------------------------------------------------------------------

class TestMetricsLint:
    def test_package_metrics_clean(self):
        from jax_llama_tpu.analysis.metricscheck import check_package

        fs = check_package()
        assert fs == [], "\n".join(f.render() for f in fs)

    def test_ghost_registration_caught(self):
        from jax_llama_tpu import obs
        from jax_llama_tpu.analysis.metricscheck import check_package

        reg = dict(obs.METRICS)
        reg["ghost_gauge_total"] = ("counter", "never emitted")
        fs = check_package(registry=reg)
        assert any(
            f.rule == "unemitted-metric" and "ghost_gauge_total" in
            f.message for f in fs
        )

    def test_unregistered_emission_caught(self):
        from jax_llama_tpu.analysis.metricscheck import check_package

        src = (
            "class P:\n"
            "    def stats(self):\n"
            "        return {'rogue_scalar': 1}\n"
        )
        fs = check_package(
            registry={"known": ("gauge", "k")},
            sources=[("provider_mod.py", src)],
            providers=(("provider_mod", "P", "stats"),),
        )
        assert any(
            f.rule == "unregistered-metric" and "rogue_scalar" in
            f.message for f in fs
        )

    def test_templated_family_matches_registration(self):
        from jax_llama_tpu.analysis.metricscheck import check_package

        src = (
            "SITES = ('a',)\n"
            "class P:\n"
            "    def stats(self):\n"
            "        out = {}\n"
            "        for s in SITES:\n"
            "            out[f'faults_injected_{s}_total'] = 1\n"
            "        return out\n"
        )
        fs = check_package(
            registry={"faults_injected_step_total": ("counter", "x")},
            sources=[("provider_mod.py", src)],
            providers=(("provider_mod", "P", "stats"),),
        )
        assert not any(f.rule == "unregistered-metric" for f in fs), \
            [f.render() for f in fs]

    def test_router_registry_package_clean(self):
        from jax_llama_tpu.analysis.metricscheck import (
            check_router_registry,
        )

        fs = check_router_registry()
        assert fs == [], "\n".join(f.render() for f in fs)

    def test_router_registry_drift_fixtures(self):
        """Both router-audit directions bite: a registered family
        nothing emits, a fam() header with no registration, and a raw
        sample line minting an unregistered family — while the clean
        family passes and docstring/registry mentions are NOT
        evidence."""
        from jax_llama_tpu.analysis.metricscheck import (
            check_router_registry,
        )

        src = (
            '"""Docstring naming llm_router_doc_only_total is not '
            'emission evidence."""\n'
            'ROUTER_METRICS = {\n'
            '    "llm_router_emitted_total": ("counter", "ok"),\n'
            '    "llm_router_ghost_total": ("counter", "never"),\n'
            '}\n'
            'def fam(name):\n'
            '    pass\n'
            'def render(lines, n):\n'
            '    fam("llm_router_emitted_total")\n'
            '    fam("llm_router_undeclared_total")\n'
            '    lines.append(f"llm_router_emitted_total {n}")\n'
            '    lines.append(f"llm_fleet_raw_gauge {n}")\n'
        )
        registry = {
            "llm_router_emitted_total": ("counter", "ok"),
            "llm_router_ghost_total": ("counter", "never"),
        }
        fs = check_router_registry(
            registry=registry, source=src, path="fixture_router.py"
        )
        unemitted = [
            f for f in fs if f.rule == "router-unemitted-metric"
        ]
        unregistered = [
            f for f in fs if f.rule == "router-unregistered-metric"
        ]
        assert len(unemitted) == 1
        assert "llm_router_ghost_total" in unemitted[0].message
        names = {
            n for f in unregistered
            for n in ("llm_router_undeclared_total",
                      "llm_fleet_raw_gauge")
            if n in f.message
        }
        assert names == {
            "llm_router_undeclared_total", "llm_fleet_raw_gauge",
        }
        assert not any(
            "llm_router_emitted_total" in f.message
            or "llm_router_doc_only_total" in f.message
            for f in fs
        )


# ---------------------------------------------------------------------------
# Comms-budget contracts (analysis/comms.py)
# ---------------------------------------------------------------------------

def _mesh4():
    import jax

    from jax_llama_tpu.parallel.serve_mesh import (
        ServeMeshSpec, build_serve_mesh,
    )

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 forced host devices")
    return build_serve_mesh(
        ServeMeshSpec(data=2, tensor=2), devices=jax.devices()[:4]
    )


@pytest.mark.slow
class TestComms:
    """Sharded-lowering comms matrix: compiles tiny mesh programs."""

    def _fixture_contract(self, body_kind, budget):
        """A contract whose program runs ``body_kind`` inside a scan
        body over a pool-shaped sharded operand."""
        import sys as _sys
        import types as _types

        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from jax_llama_tpu.analysis.contracts import ProgramContract

        mesh = _mesh4()
        mod = _types.ModuleType("comms_fixture_mod")

        @jax.jit
        def _fx(pool, x):
            def body(carry, _):
                if body_kind == "pool-gather":
                    full = jax.lax.with_sharding_constraint(
                        pool, NamedSharding(mesh, P())
                    )
                    return carry + full.sum(), None
                row = jax.lax.with_sharding_constraint(
                    pool[0, :, 0, 0], NamedSharding(mesh, P())
                )
                return carry + row.sum(), None

            out, _ = jax.lax.scan(body, x, None, length=2)
            return out

        mod._fx = _fx
        _sys.modules["comms_fixture_mod"] = mod

        def build():
            pool = jax.device_put(
                np.ones((2, 2, 8, 16, 16), np.float32),
                NamedSharding(mesh, P(None, "tensor")),
            )
            return ("pool", "x"), (pool, jnp.zeros(())), {}

        return ProgramContract(
            name="_fx", module="comms_fixture_mod", donated=(),
            max_live_outputs=1, max_fetch_bytes_per_row=1 << 20,
            mesh_build=build, max_cache_keys=4, comms=budget,
            forbidden_shapes=lambda args: [
                tuple(args[0].shape), tuple(args[0].shape[1:]),
            ],
        )

    def test_full_pool_all_gather_in_scan_body_is_hard_finding(self):
        from jax_llama_tpu.analysis.comms import check_comms
        from jax_llama_tpu.analysis.contracts import CommsBudget

        # even a budget that ALLOWS big all-gathers cannot sanction a
        # pool-shaped one
        c = self._fixture_contract("pool-gather", CommsBudget(
            max_count={"all-gather": 99, "all-reduce": 99,
                       "collective-permute": 99},
            max_bytes=1 << 30,
        ))
        fs = check_comms(c)
        assert any(f.rule == "pool-collective" for f in fs), \
            [f.render() for f in fs]

    def test_count_and_byte_budgets_enforced_and_sanctionable(self):
        from jax_llama_tpu.analysis.comms import check_comms
        from jax_llama_tpu.analysis.contracts import CommsBudget

        # a small row gather: not pool-shaped, so the BUDGET decides
        tight = self._fixture_contract("row-gather", CommsBudget(
            max_count={}, max_bytes=1,
        ))
        fs = check_comms(tight)
        assert any(f.rule == "comms-count" for f in fs)
        loose = self._fixture_contract("row-gather", CommsBudget(
            max_count={"all-gather": 8, "all-reduce": 8,
                       "collective-permute": 8},
            max_bytes=65536,
        ))
        assert not [
            f for f in check_comms(loose)
            if f.rule in ("comms-count", "comms-bytes",
                          "pool-collective")
        ]

    def test_mesh_program_without_budget_is_finding(self):
        from jax_llama_tpu.analysis.comms import check_comms

        c = self._fixture_contract("row-gather", None)
        fs = check_comms(c)
        assert [f.rule for f in fs] == ["no-comms-budget"]

    def test_package_comms_clean(self):
        """The regression pin for the full-pool reshard this PR fixed:
        the sharded _paged_decode_chunk / _fused_chunk lowerings hold
        their comms budgets and contain NO pool-shaped collective
        (pre-fix: 4 and 36 full-pool all-gathers per scan body)."""
        from jax_llama_tpu.analysis.comms import check_package

        fs = check_package()
        assert fs == [], "\n".join(f.render() for f in fs)

    def test_every_mesh_contract_declares_budget(self):
        for c in REGISTRY.values():
            if c.mesh_build is not None:
                assert c.comms is not None, c.name


def test_constrain_view_pins_kv_heads():
    """Fast pin for the gathered-view sharding fix: under a serving
    mesh, constrain_view forces the view's KV-head axis onto the
    ``tensor`` axis (the pin that stops GSPMD replicating the pool)."""
    import jax
    import jax.numpy as jnp

    from jax_llama_tpu.models.llama import KVCache
    from jax_llama_tpu.parallel import mesh as pmesh
    from jax_llama_tpu.parallel import serve_mesh as smesh

    mesh = _mesh4()

    @jax.jit
    def f(k, v, pos):
        view = KVCache(
            k=k, v=v, pos=pos, index=jnp.zeros((2,), jnp.int32)
        )
        with pmesh.use_mesh(mesh):
            return smesh.constrain_view(view).k

    k = jnp.zeros((2, 2, 32, 2, 16), jnp.float32)
    out = f(k, k, jnp.zeros((2, 32), jnp.int32))
    spec = out.sharding.spec
    assert tuple(spec)[3] == "tensor", spec


# ---------------------------------------------------------------------------
# Review-hardening pins for the new passes themselves
# ---------------------------------------------------------------------------

class TestPassRobustness:
    def test_unsatisfiable_happens_before_edge_is_not_vacuous(self):
        from jax_llama_tpu.analysis.schedules import (
            Op, ScheduleModel, explore,
        )

        m = ScheduleModel(
            name="vac", module="x", func="f", claim="happens-before",
            make=lambda: object(),
            writers={"main": (Op("w", lambda s, c: None),),
                     "loop": (Op("r", lambda s, c: None),)},
            after={"loop": ("main", "TYPO_no_such_op")},
        )
        fails = explore(m)
        assert fails and "no complete schedule" in fails[0]

    def test_shape_of_parameter_is_not_bounded(self):
        from jax_llama_tpu.analysis.retrace import check_module_source

        src = (
            "import functools, jax\n"
            "import jax.numpy as jnp\n"
            '@functools.partial(jax.jit, static_argnames=("width",))\n'
            "def _prog(x, *, width):\n"
            "    return x[:width]\n"
            "class B:\n"
            "    def f(self, toks):\n"
            "        return _prog(jnp.asarray(toks), "
            "width=toks.shape[0])\n"
        )
        reg = {"_prog": ProgramContract(
            name="_prog", module="fixture_mod", donated=(),
            max_live_outputs=1, max_fetch_bytes_per_row=1 << 20,
            max_cache_keys=4,
        )}
        fs = check_module_source("fixture_mod.py", src, registry=reg)
        assert any("request-shaped" in f.message for f in fs), \
            [f.render() for f in fs]

    def test_tuple_result_collectives_parsed(self):
        from jax_llama_tpu.analysis.comms import collectives_in_text

        text = (
            "%ag = (f32[2,2,8,16,16]{4,3,2,0,1}, s32[4]{0}) "
            "all-gather(f32[2,1,8,16,16] %a, s32[2] %b), dims={1}\n"
            "%ar = f32[1,64]{1,0} all-reduce(f32[1,64] %c)\n"
            "%done = (f32[8]{0}) all-gather-done(%x)\n"
        )
        got = collectives_in_text(text)
        kinds = [k for k, _ in got]
        assert kinds == ["all-gather", "all-reduce"]  # -done skipped
        shapes = [s for _, rs in got for s, _ in rs]
        assert (2, 2, 8, 16, 16) in shapes and (4,) in shapes

    def test_docstring_mention_is_not_emission_evidence(self):
        from jax_llama_tpu.analysis.metricscheck import check_package

        src = (
            '"""Module docs mention ghost_gauge by name."""\n'
            "class P:\n"
            '    """Docs: ghost_gauge again."""\n'
            "    def stats(self):\n"
            "        return {}\n"
        )
        fs = check_package(
            registry={"ghost_gauge": ("gauge", "x")},
            sources=[("provider_mod.py", src)],
            providers=(),
        )
        assert any(
            f.rule == "unemitted-metric" and "ghost_gauge" in f.message
            for f in fs
        ), [f.render() for f in fs]

    def test_cli_comms_no_trace_is_usage_error(self, capsys):
        assert cli_main(["--checker", "comms", "--no-trace"]) == 2
        assert "vacuous" in capsys.readouterr().err

    def test_cli_retrace_with_contracts_is_usage_error(self, capsys):
        assert cli_main(
            ["--checker", "retrace", "--contracts", "anything"]
        ) == 2
        assert "cannot audit an external" in capsys.readouterr().err
