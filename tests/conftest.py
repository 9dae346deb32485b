"""Test environment: CPU with 8 virtual devices, set BEFORE jax import.

Mirrors the survey's test-plan recommendation (SURVEY.md §4): DP/TP/FSDP
paths must be testable without TPU hardware via
``--xla_force_host_platform_device_count``.

The platform comes from ``JAX_PLATFORMS`` and defaults to the CPU here.
The on-chip tier names the chip explicitly, in ONE process:
``JAX_PLATFORMS=tpu python -m pytest tests/test_tpu_compiled.py -m tpu
-p no:xdist``; those tests skip themselves on any other backend.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: compiled-on-chip kernel regression tests (run with "
        "JAX_PLATFORMS=tpu, -m tpu and -p no:xdist on a TPU host; on any "
        "other backend the tests self-skip)",
    )
    config.addinivalue_line(
        "markers",
        "faults: fault-injection / crash-recovery / watchdog tests "
        "(CPU-safe and part of the default tier-1 run; select just them "
        "with pytest -m faults)",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 run "
        "(pytest -m 'not slow')",
    )
    config.addinivalue_line(
        "markers",
        "chaos: full fault-matrix smoke drills (make chaos / "
        "pytest -m 'chaos or faults'); the heavy ones are also marked "
        "slow so tier-1 keeps its time headroom",
    )
    config.addinivalue_line(
        "markers",
        "kvcache: KV-capacity subsystem tests (radix prefix index + "
        "host-DRAM block tier; CPU-safe and part of the default "
        "tier-1 run — select just them with pytest -m kvcache)",
    )
    config.addinivalue_line(
        "markers",
        "obs: observability-layer tests (request timelines, dispatch "
        "spans, latency histograms, SLO accounting, /metrics "
        "exposition, /debug endpoints; CPU-safe and part of the "
        "default tier-1 run — select just them with pytest -m obs "
        "or make obs)",
    )
    config.addinivalue_line(
        "markers",
        "overload: overload-control tests (priority classes, "
        "deadline-aware admission, brownout ladder, open-loop flood "
        "drills — overload.py; CPU-safe, the core set runs in tier-1 "
        "and the heavy acceptance drill is also marked slow — select "
        "with pytest -m overload or make overload)",
    )
    config.addinivalue_line(
        "markers",
        "analysis: invariant-auditor tests (host-boundary lint, "
        "lowering contracts, lock discipline — jax_llama_tpu.analysis; "
        "the static package-cleanliness gates run in tier-1, the "
        "abstract-trace layer is also marked slow — select just them "
        "with pytest -m analysis or make lint-invariants)",
    )
    config.addinivalue_line(
        "markers",
        "mesh_serving: scale-out serving tests (mesh-sharded chunk "
        "programs on the forced 8-device CPU host mesh, sharded KV "
        "pool placement, the ReplicaRouter + disaggregation handoff "
        "— parallel/serve_mesh.py + router.py; the core parity pins "
        "run in tier-1, the broad matrices are also marked slow — "
        "select with pytest -m mesh_serving or make mesh-serve)",
    )


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    """The multi-device CPU fleet for mesh_serving tests: conftest
    already forces ``--xla_force_host_platform_device_count=8`` before
    jax import (top of this file), so this fixture only asserts the
    environment delivered them (a stray XLA_FLAGS override would
    otherwise fail every mesh test with an opaque mesh-size error)."""
    import jax

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(
            f"need 8 forced host devices for serving-mesh tests, "
            f"have {len(devs)} (XLA_FLAGS overridden?)"
        )
    return devs


@pytest.fixture(autouse=True, scope="session")
def _no_persistent_compile_cache():
    """The tests do not enable the persistent compilation cache.  Entry
    points that tests call in-process (``run.main``) would turn it on for
    the rest of the worker: compiles for a described chip
    (tests/test_chip_compile.py) cannot be read back from it and would
    warn, and a test run has no business writing into the checkout."""
    from jax_llama_tpu.utils import compile_cache

    real = compile_cache.enable_compile_cache
    compile_cache.enable_compile_cache = compile_cache.compile_cache_dir
    yield
    compile_cache.enable_compile_cache = real


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_compile_cache():
    """Clear JAX's compiled-executable caches after each test module.

    Running the FULL suite in one process accumulates every module's
    compiled CPU executables; past ~200 tests the XLA:CPU compiler was
    observed to segfault mid-compile (reproduced twice at ~80% of the
    full run, with >100GB RAM free; any module subset passes in
    isolation).  Modules share almost no jit cache entries (each uses its
    own tiny configs), so per-module clearing costs little and keeps the
    process state bounded.

    Set JLT_NO_CACHE_CLEAR=1 to disable the workaround — the repro
    switch for chasing the underlying crash (run the full suite with
    ``-p faulthandler`` and a core-dump ulimit to capture where the
    XLA:CPU compiler dies).
    """
    yield
    if not os.environ.get("JLT_NO_CACHE_CLEAR"):
        jax.clear_caches()
