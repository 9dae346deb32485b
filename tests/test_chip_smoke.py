"""Rehearsal 1 as a test: chip_smoke.py's control flow, tiny, on the CPU.

The smoke's parent must stay off jax (one process per chip), must never
report ``"ok": true`` off a TPU, and its children must put the compile
cache where ``JAX_COMPILATION_CACHE_DIR`` says or at the fixed
in-checkout path.  The seam is ``chip_smoke.run``'s own arguments (a tiny
``Spec``, ``require_tpu=False`` so the phases after the platform check
still run) — nothing in the product knows about this test.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402  (parent side only: imports no jax)


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("XLA_FLAGS", None)   # the children need one CPU device, not 8
    return env


def test_parent_side_import_stays_off_jax():
    """Checked in a fresh interpreter: this process already holds jax."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         "bad = [m for m in sys.modules if m == 'jax' or "
         "m.startswith(('jax.', 'jaxlib', 'jax_llama_tpu'))]; "
         "sys.exit(repr(bad) if bad else 0)"],
        cwd=str(ROOT), env=_cpu_env(), capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    from jax_llama_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # Fixed, derived from the package's own location: the same value on
    # every call, in the checkout, nothing of tempfile / pid / clock in it.
    assert compile_cache.compile_cache_dir() == str(ROOT / ".jax_cache")
    assert compile_cache.compile_cache_dir() == compile_cache.compile_cache_dir()


def test_driver_invocation_fails_on_cpu_before_any_work():
    """``python chip_smoke.py`` exactly as the driver runs it, in a sandbox
    without an accelerator: non-zero exit, no ``"ok": true``, and the
    checkpoint writer stops at the platform check (nothing written)."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=str(ROOT),
        env=_cpu_env(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout          # no result line of any kind
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["smoke"] == "end"
    assert any("no accelerator" in f for f in last["failures"])
    assert not (chip_smoke.WORK_DIR / "ckpt").exists()


TINY = chip_smoke.Spec(
    preset="tiny",
    overrides=(
        ("vocab_size", 512), ("dim", 64), ("n_layers", 2), ("n_heads", 4),
        ("n_kv_heads", 2), ("max_seq_len", 256), ("dtype", "float32"),
        ("param_dtype", "float32"),
    ),
    slots=4, long_prompt_bytes=150, long_new_tokens=12,
    burst=((20, 6), (33, 8), (9, 4), (51, 5)),
    start_timeout_s=240, request_timeout_s=240,
)


def test_tiny_run_on_cpu_exercises_every_phase_and_still_fails(
    monkeypatch, tmp_path, capsys
):
    """The whole one-chip flow at a tiny config on the CPU (children are
    real ``run.py`` servers): checkpoint write -> restore -> cold long
    request -> prefix-hit stream -> concurrent burst -> health -> SIGTERM
    drain all pass, the children's compile cache follows the environment
    variable, and the run STILL fails — the platform is not a TPU."""
    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    monkeypatch.delenv("XLA_FLAGS", raising=False)   # one CPU device
    rc = chip_smoke.run(
        TINY, chips=1, seed=0, work_dir=tmp_path / "work",
        log_dir=tmp_path / "logs", require_tpu=False,
    )
    out = capsys.readouterr().out
    lines = [json.loads(ln) for ln in out.strip().splitlines()]
    assert rc != 0 and '"ok"' not in out      # no result line of any kind
    assert lines[-1]["smoke"] == "end"
    assert lines[-1]["failures"] == ["platform is 'cpu', not 'tpu'"], (
        out[-3000:]
    )
    by_event = {ln.get("smoke"): ln for ln in lines if "smoke" in ln}
    assert by_event["devices"]["compile_cache"] == str(cache)
    assert by_event["kernels"]["paged_kernel_eligible"] is True
    assert by_event["kernels"]["use_pallas_kernel"] is True
    assert by_event["compare"]["name"] == "prefix_hit_vs_cold"
    assert by_event["drained"]["exit_code"] == 0
    assert by_event["metrics"]["llm_fused_admissions_total"] >= 1
    assert not (tmp_path / "work" / "ckpt").exists()
