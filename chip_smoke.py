#!/usr/bin/env python3
"""Chip smoke: the serving main path, end to end, on the attached TPU.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the sharded / replicated paths only

The model is ``get_config("llama3-8b")`` with every published width kept
(dim 4096, 32 query / 8 KV heads, head_dim 128, FFN 14336, vocab 128,256,
rope theta 500,000, untied head, bf16 params and compute).  DEPTH ONLY is
cut, 32 -> 16 layers, because the full model is ~16.06 GB in bf16 and one
v5e chip has 16 GB:

    per layer   4096*(4096+1024+1024) + 4096*4096 + 3*4096*14336
                = 218,103,808 params (+ 2 norms)
    16 layers   3.49 B    embedding + head  2 * 128256*4096 = 1.05 B
    weights     4.54 B params = 9.08 GB bf16
    KV          2 * 16 layers * 8 heads * 128 * 2 B = 64 KiB per token;
                4 slots x 2048 tokens (64 blocks of 128) = 0.54 GB
    prefill     only the last token's [k, 128256] fp32 logits are formed;
                a 2048-token prefill's per-row cache is 0.13 GB and its
                SwiGLU activations ~0.12 GB
    total       ~10 GB of 16 GB: weights + pool + prefill with margin

Weights are random, from ``init_params(PRNGKey(seed))``, written with
``convert.checkpoint.save_checkpoint`` and restored by ``run.py`` exactly
as a user's checkpoint would be.  The server is the normal entry point at
``run.py``'s defaults (``--decode-chunk 8``, ``--prefill-budget 512``,
radix prefix cache, cost models on) plus ``--attn auto`` (Pallas prefill),
``--logprobs`` and ``--log-json``.

One process per chip: this parent never imports jax (nor the package,
which does).  Every child that needs the chip (checkpoint writer, each
server) runs strictly after the previous one has exited, and the device
triple on the last line is what the children themselves reported.
``JAX_PLATFORMS`` is neither set nor defaulted here; anything but a TPU
fails the run.  Timings printed are set-up information, never metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parent
WORK_DIR = ROOT / ".chip_smoke_out"    # the checkpoint; removed at the end
LOG_DIR = ROOT / "chiprun_out" / "chip_smoke_logs"   # children's output

# |delta logprob| allowed between two servings of the same greedy prompt.
# bf16 activations carry 8 mantissa bits (2^-8 ~ 0.4% per rounding); two
# differently shaped programs (flash prefill vs the gathered suffix
# insert; one chip vs four-way tensor-parallel reductions) round in a
# different order, which at logits of a few units is a few 1e-2 in
# log-softmax.  A wrong mask, block table or shard moves logprobs by >= 1.
# Random weights give near-tie top-2 logits, so tokens must agree only up
# to the first divergence, and AT that divergence both picks must sit
# within the same tolerance of each other (a tolerated tie flip).
LOGPROB_TOL = 0.25


@dataclasses.dataclass(frozen=True)
class Spec:
    """What one smoke run serves.  ``FULL`` is what the driver runs; the
    CPU rehearsal (tests/test_chip_smoke.py) passes a tiny one — the only
    seam, and it lives here, not in the product."""

    preset: str = "llama3-8b"
    overrides: Tuple[Tuple[str, Any], ...] = (
        ("n_layers", 16), ("max_seq_len", 2048),
        ("dtype", "bfloat16"), ("param_dtype", "bfloat16"),
    )
    slots: int = 4
    long_prompt_bytes: int = 1100     # >= 1024 byte-tokens: chunked prefill
    long_new_tokens: int = 64         # >= 64: chunked paged decode
    # (prompt bytes, new tokens) of the concurrent burst: different
    # lengths, so admissions land on decoding rows (fused prefill-decode).
    burst: Tuple[Tuple[int, int], ...] = (
        (200, 24), (333, 32), (90, 16), (512, 20),
    )
    # Four-chip mode: one fixed greedy prompt set, served three ways.
    mesh_prompts: Tuple[Tuple[int, int], ...] = (
        (300, 24), (700, 24), (1100, 32),
    )
    start_timeout_s: float = 700.0
    request_timeout_s: float = 500.0


FULL = Spec()


class SmokeFailure(Exception):
    pass


def say(event: str, **fields) -> None:
    print(json.dumps({"smoke": event, **fields}, default=str), flush=True)


def _text(n_bytes: int, salt: int) -> str:
    """Deterministic ASCII text of exactly ``n_bytes`` bytes."""
    words = ("the", "chip", "serves", "tokens", "from", "paged", "blocks",
             "while", "prefill", "and", "decode", "share", "one", "program")
    out, i = [], salt
    while sum(len(w) + 1 for w in out) < n_bytes:
        out.append(words[(i * 7 + i // 3) % len(words)])
        i += 1
    return " ".join(out)[:n_bytes].ljust(n_bytes, ".")


# ---------------------------------------------------------------------------
# Children (the only code here that touches jax)
# ---------------------------------------------------------------------------

def _child_write_checkpoint(argv: Sequence[str]) -> int:
    """``chip_smoke.py _write <ckpt_dir> <seed> <spec-json>``: random
    weights from the seed, saved the way a user's checkpoint is."""
    ckpt_dir, seed, spec_json = argv
    spec = json.loads(spec_json)
    import jax

    from jax_llama_tpu import get_config, init_params
    from jax_llama_tpu.convert.checkpoint import save_checkpoint
    from jax_llama_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    say("devices", platform=dev.platform, device_kind=dev.device_kind,
        count=len(jax.devices()), compile_cache=cache)
    if spec["require_tpu"] and dev.platform != "tpu":
        return 3
    config = get_config(spec["preset"], **dict(spec["overrides"]))
    t0 = time.monotonic()
    # One jitted program: the fp32 normal draws fuse into the bf16 cast,
    # so the device never holds a full-precision copy of the weights.
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(int(seed)), config
    )
    jax.block_until_ready(params)
    t1 = time.monotonic()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    save_checkpoint(ckpt_dir, params, config)
    say("checkpoint_written", params=n_params, bytes=n_bytes,
        init_s=round(t1 - t0, 1), save_s=round(time.monotonic() - t1, 1),
        config={k: getattr(config, k) for k in (
            "dim", "n_layers", "n_heads", "n_kv_heads", "vocab_size",
            "rope_theta", "max_seq_len", "dtype", "param_dtype",
            "tie_word_embeddings")},
        head_dim=config.head_dim, ffn_dim=config.ffn_dim)
    return 0


# ---------------------------------------------------------------------------
# Parent side: processes, HTTP, checks (no jax)
# ---------------------------------------------------------------------------

def _child_env() -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


class Child:
    """One child process: stdout parsed as JSON-per-line events, stderr to
    a log file.  Always terminated by ``close()``."""

    def __init__(self, name: str, cmd: List[str], log_dir: Path):
        self.name = name
        self.events: List[Dict[str, Any]] = []
        self._cv = threading.Condition()
        log_dir.mkdir(parents=True, exist_ok=True)
        self._out_path = log_dir / f"{name}.stdout.log"
        self._err = open(log_dir / f"{name}.stderr.log", "w")
        self.proc = subprocess.Popen(
            cmd, cwd=str(ROOT), env=_child_env(), stdout=subprocess.PIPE,
            stderr=self._err, text=True, bufsize=1,
        )
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        with open(self._out_path, "w") as log:
            for line in self.proc.stdout:
                log.write(line)
                log.flush()
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if isinstance(ev, dict):
                    with self._cv:
                        self.events.append(ev)
                        self._cv.notify_all()

    def find(self, key: str, *values: str) -> List[Dict[str, Any]]:
        with self._cv:
            return [e for e in self.events if e.get(key) in values]

    def wait_event(self, key: str, values: Sequence[str], timeout_s: float):
        """First event whose ``ev[key]`` is one of ``values``; fails if
        the child exits or the timeout passes first."""
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while True:
                hits = [e for e in self.events if e.get(key) in values]
                if hits:
                    return hits[0]
                if self.proc.poll() is not None and not self._reader.is_alive():
                    raise SmokeFailure(
                        f"{self.name} exited {self.proc.returncode} before "
                        f"{key} in {values}: {self.stderr_tail()}"
                    )
                left = deadline - time.monotonic()
                if left <= 0:
                    raise SmokeFailure(
                        f"{self.name}: no {key} in {values} within "
                        f"{timeout_s:.0f}s: {self.stderr_tail()}"
                    )
                self._cv.wait(min(left, 1.0))

    def wait_exit(self, timeout_s: float) -> int:
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{self.name} did not exit in {timeout_s:.0f}s")
        self._reader.join(timeout=10)
        return rc

    def stderr_tail(self, n: int = 1500) -> str:
        self._err.flush()
        try:
            return Path(self._err.name).read_text()[-n:]
        except OSError:
            return ""

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._reader.join(timeout=10)
        self._err.close()


def _url(address: str) -> str:
    address = address.rstrip("/")
    return address if address.startswith("http") else "http://" + address


def http_json(url: str, payload: Optional[dict] = None,
              timeout_s: float = 60.0, headers: Optional[dict] = None):
    """(status, parsed body or NDJSON line list, headers)."""
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(url, data=data, headers={
        "Content-Type": "application/json", **(headers or {}),
    })
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            status, body, hdrs = r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as e:
        status, body, hdrs = e.code, e.read().decode(), dict(e.headers)
    if hdrs.get("Content-Type", "").startswith("application/x-ndjson"):
        return status, [json.loads(ln) for ln in body.splitlines() if ln], hdrs
    try:
        return status, json.loads(body), hdrs
    except ValueError:
        return status, body, hdrs


def generate(base: str, text: str, new_tokens: int, spec: Spec,
             stream: bool = False):
    """One greedy /generate with logprobs; returns ((tokens, logprobs),
    reply headers) after checking status, count and finiteness."""
    status, body, hdrs = http_json(base + "/generate", {
        "text": text, "max_new_tokens": new_tokens, "temperature": 0.0,
        "stop_tokens": [], "logprobs": True, "stream": stream,
    }, timeout_s=spec.request_timeout_s)
    if status != 200:
        raise SmokeFailure(f"/generate -> HTTP {status}: {str(body)[:300]}")
    if stream:
        final = body[-1]
        if not final.get("done") or final.get("error") or final.get("timeout"):
            raise SmokeFailure(f"stream did not finish cleanly: {final}")
        per_line = [ln["token"] for ln in body[:-1]]
        if per_line != final["tokens"]:
            raise SmokeFailure("stream lines disagree with the final line")
        body = final
    toks, lps = body.get("tokens"), body.get("logprobs")
    if not isinstance(toks, list) or len(toks) != new_tokens:
        raise SmokeFailure(
            f"asked {new_tokens} tokens, got {toks and len(toks)}"
        )
    if not isinstance(lps, list) or len(lps) != new_tokens or not all(
        isinstance(x, (int, float)) and math.isfinite(x) and x <= 1e-3
        for x in lps
    ):
        raise SmokeFailure(f"logprobs missing or not finite: {str(lps)[:200]}")
    return (toks, lps), hdrs


def compare_logprobs(name: str, ref, got, tol: float = LOGPROB_TOL) -> dict:
    """Hold ``got`` (tokens, logprobs) to ``ref`` at logprob level: same
    length; tokens identical up to the first divergence; every logprob up
    to and including that index within ``tol``."""
    (rt, rl), (gt, gl) = ref, got
    if len(rt) != len(gt):
        raise SmokeFailure(f"{name}: {len(gt)} tokens vs {len(rt)}")
    same = next((i for i, (a, b) in enumerate(zip(rt, gt)) if a != b), len(rt))
    upto = min(same + 1, len(rt))
    worst = max(abs(a - b) for a, b in zip(rl[:upto], gl[:upto]))
    out = {"tokens": len(rt), "identical_prefix": same,
           "max_abs_dlogprob": round(worst, 5), "tolerance": tol}
    say("compare", name=name, **out)
    if worst > tol:
        raise SmokeFailure(f"{name}: |dlogprob| {worst:.4f} > {tol}")
    return out


def check_health(base: str, name: str) -> dict:
    status, h, _ = http_json(base + "/healthz")
    feats = h.get("features", {}) if isinstance(h, dict) else {}
    summary = {
        "status": status, "healthz_ok": h.get("ok"),
        "recoveries_total": h.get("recoveries_total"),
        "watchdog_stalls_total": h.get("watchdog_stalls_total"),
        "degraded": h.get("degraded"), "quarantined": h.get("quarantined"),
        "feature_failures": {
            k: v.get("failures_total") for k, v in feats.items()
            if v.get("failures_total") or v.get("state") != "healthy"
        },
    }
    say("healthz", server=name, **summary)
    bad = (
        status != 200 or h.get("ok") is not True
        or h.get("recoveries_total") != 0
        or h.get("watchdog_stalls_total") != 0
        or h.get("degraded") is not False or h.get("quarantined")
        or not feats or summary["feature_failures"]
    )
    if bad:
        raise SmokeFailure(f"{name}: /healthz not clean: {summary}")
    return h


def check_kernels(base: str, name: str) -> dict:
    """The attention path the batcher runs (``ContinuousBatcher.describe``
    under /debug/bundle): a run that never could touch a Pallas kernel
    is printed and fails."""
    status, b, _ = http_json(base + "/debug/bundle?trace=0")
    if status != 200:
        raise SmokeFailure(f"{name}: /debug/bundle -> {status}")
    d = b["config"]["batcher"]
    keys = ("attn_impl", "use_pallas_kernel", "paged_kernel_eligible",
            "n_slots", "max_len", "block_size", "n_blocks", "block_bytes",
            "decode_chunk", "prefill_budget", "prefix_index", "serve_mesh")
    got = {k: d.get(k) for k in keys}
    say("kernels", server=name, **got)
    if not (
        got["attn_impl"] == "auto"
        and got["use_pallas_kernel"] is True
        and got["paged_kernel_eligible"] is True
    ):
        raise SmokeFailure(f"{name}: Pallas kernels not on the path: {got}")
    return got


def scrape_metrics(base: str, name: str, want_positive: Sequence[str]) -> dict:
    status, text, _ = http_json(base + "/metrics")
    if status != 200 or not isinstance(text, str):
        raise SmokeFailure(f"{name}: /metrics -> {status}")
    vals: Dict[str, float] = {}
    for ln in text.splitlines():
        if ln.startswith("#") or " " not in ln:
            continue
        k, v = ln.rsplit(" ", 1)
        try:
            vals[k] = float(v)
        except ValueError:
            pass
    keep = {k: vals.get(k) for k in (
        "llm_emitted_tokens_total", "llm_decode_dispatches_total",
        "llm_decode_chunk_size", "llm_host_syncs_per_token",
        "llm_prefill_chunks_total", "llm_fused_admissions_total",
        "llm_prefix_requests_hit_total", "llm_prefix_hit_tokens_ratio",
        "llm_compiles_total", "llm_nonfinite_rows_total",
    )}
    for kind in ("decode", "fused", "insert", "suffix_insert"):
        n = vals.get(f'llm_dispatch_ms_count{{kind="{kind}"}}')
        if n:   # set-up information (includes compiles), not a metric
            keep[f"dispatches_{kind}"] = int(n)
            keep[f"dispatch_ms_sum_{kind}"] = vals.get(
                f'llm_dispatch_ms_sum{{kind="{kind}"}}'
            )
    say("metrics", server=name, **keep)
    for k in want_positive:
        if not vals.get(k, 0) > 0:
            raise SmokeFailure(f"{name}: /metrics {k} = {vals.get(k)}")
    if vals.get("llm_nonfinite_rows_total", 0) != 0:
        raise SmokeFailure(f"{name}: non-finite rows were served")
    return vals


def write_checkpoint(spec: Spec, seed: int, ckpt: Path, logs: Path,
                     require_tpu: bool) -> dict:
    """Child 1: reports the device, then writes the checkpoint."""
    if ckpt.exists():
        shutil.rmtree(ckpt)
    t0 = time.monotonic()
    child = Child("writer", [
        sys.executable, str(ROOT / "chip_smoke.py"), "_write", str(ckpt),
        str(seed), json.dumps({
            "preset": spec.preset, "overrides": list(spec.overrides),
            "require_tpu": require_tpu,
        }),
    ], logs)
    try:
        rc = child.wait_exit(900)
        devs = child.find("smoke", "devices")
        if not devs:
            raise SmokeFailure(
                f"checkpoint writer exited {rc} without naming its device: "
                f"{child.stderr_tail()}"
            )
        dev = devs[0]
        print(json.dumps(dev), flush=True)
        if dev["platform"] != "tpu" and require_tpu:
            raise SmokeFailure(f"no accelerator: jax found {dev['platform']}")
        if rc != 0:
            raise SmokeFailure(f"writer exited {rc}: {child.stderr_tail()}")
        written = child.find("smoke", "checkpoint_written")[0]
        print(json.dumps(written), flush=True)
        say("phase", name="checkpoint_write",
            seconds=round(time.monotonic() - t0, 1))
        return dev
    finally:
        child.close()


class Server:
    """One ``python -m jax_llama_tpu.run --http 0`` child."""

    def __init__(self, name: str, ckpt: Path, spec: Spec, logs: Path,
                 extra: Sequence[str] = ()):
        self.name, self.spec = name, spec
        self.t0 = time.monotonic()
        self.child = Child(name, [
            sys.executable, "-m", "jax_llama_tpu.run",
            "--ckpt-dir", str(ckpt), "--byte-tokenizer", "--http", "0",
            "--slots", str(spec.slots), "--attn", "auto", "--logprobs",
            "--log-json", *extra,
        ], logs)
        self.base = ""
        self.device: Dict[str, Any] = {}

    def wait_ready(self) -> None:
        c, to = self.child, self.spec.start_timeout_s
        self.device = c.wait_event("event", ("devices",), to)
        restored = c.wait_event("event", ("checkpoint_restored",), to)
        ev = c.wait_event("event", ("serving", "serving_replicas"), to)
        self.base = _url(ev["address"])
        deadline = time.monotonic() + 60
        while True:
            try:
                if http_json(self.base + "/healthz", timeout_s=10)[0] == 200:
                    break
            except OSError:
                pass
            if time.monotonic() > deadline:
                raise SmokeFailure(f"{self.name}: /healthz never answered")
            time.sleep(0.5)
        say("phase", name=f"{self.name}_start",
            seconds=round(time.monotonic() - self.t0, 1),
            restore_s=restored.get("seconds"), mesh=restored.get("mesh"),
            device=self.device)

    def memory(self, when: str) -> List[dict]:
        evs = [e for e in self.child.find("event", "device_memory")
               if e.get("when") == when]
        return evs[-1]["devices"] if evs else []

    def drain(self) -> None:
        """SIGTERM; require a clean drain and exit code 0."""
        self.child.proc.send_signal(signal.SIGTERM)
        rc = self.child.wait_exit(120)
        if rc != 0 or not self.child.find("event", "drained"):
            raise SmokeFailure(
                f"{self.name}: exit {rc} after SIGTERM, drained="
                f"{bool(self.child.find('event', 'drained'))}: "
                f"{self.child.stderr_tail()}"
            )
        peak = [d.get("peak_bytes_in_use") for d in self.memory("drained")]
        say("drained", server=self.name, exit_code=rc,
            peak_bytes_in_use=peak)

    def close(self) -> None:
        self.child.close()


def _timed(name: str, t0: float) -> None:
    say("phase", name=name, seconds=round(time.monotonic() - t0, 1))


def one_chip(spec: Spec, ckpt: Path, logs: Path) -> dict:
    """The one-chip main path: cold long request, the same prompt again as
    a stream (prefix-cache hit), a concurrent burst, health, drain."""
    srv = Server("server", ckpt, spec, logs)
    try:
        srv.wait_ready()
        say("device_memory", when="serving", devices=srv.memory("serving"))
        kern = check_kernels(srv.base, "server")
        long_text = _text(spec.long_prompt_bytes, 1)
        t0 = time.monotonic()
        cold, _ = generate(srv.base, long_text, spec.long_new_tokens, spec)
        _timed("first_request_incl_compile", t0)
        t0 = time.monotonic()
        hit, _ = generate(srv.base, long_text, spec.long_new_tokens, spec,
                          stream=True)
        _timed("prefix_hit_stream_request", t0)
        compare_logprobs("prefix_hit_vs_cold", cold, hit)
        t0 = time.monotonic()
        results: List[Any] = [None] * len(spec.burst)

        def one(i: int, n_bytes: int, n_new: int) -> None:
            try:
                results[i], _ = generate(
                    srv.base, _text(n_bytes, 10 + i), n_new, spec
                )
            except Exception as e:  # reported below, per request
                results[i] = e

        threads = []
        for i, (n_bytes, n_new) in enumerate(spec.burst):
            th = threading.Thread(target=one, args=(i, n_bytes, n_new))
            th.start()
            threads.append(th)
            time.sleep(0.3)   # stagger: later ones meet decoding rows
        for th in threads:
            th.join(spec.request_timeout_s + 30)
        for i, r in enumerate(results):
            if not isinstance(r, tuple):
                raise SmokeFailure(f"burst request {i}: {r!r}")
        _timed("concurrent_burst", t0)
        t0 = time.monotonic()
        generate(srv.base, _text(150, 99), 16, spec)
        _timed("steady_request", t0)
        scrape_metrics(srv.base, "server", (
            "llm_emitted_tokens_total", "llm_decode_dispatches_total",
            "llm_prefix_requests_hit_total", "llm_fused_admissions_total",
        ))
        check_health(srv.base, "server")
        srv.drain()
        return {"device": srv.device, "kernels": kern}
    finally:
        srv.close()


def four_chips(spec: Spec, ckpt: Path, logs: Path) -> dict:
    """Only what exists across chips: (a) the one-chip reference, (b) one
    model sharded four ways, (c) two two-chip replicas behind the router,
    all serving the same greedy prompts."""
    prompts = [(_text(n, 40 + i), new)
               for i, (n, new) in enumerate(spec.mesh_prompts)]

    def serve(name: str, extra: Sequence[str], revisit: bool = False):
        srv = Server(name, ckpt, spec, logs, extra)
        try:
            srv.wait_ready()
            mem = srv.memory("serving")
            say("device_memory", server=name, when="serving", devices=mem)
            out, replicas = [], []
            t0 = time.monotonic()
            for text, new in prompts + (prompts[:1] if revisit else []):
                reply, hdrs = generate(srv.base, text, new, spec)
                out.append(reply)
                replicas.append(hdrs.get("X-Replica-Id"))
            _timed(f"{name}_requests_incl_compile", t0)
            placed = srv.child.find("event", "replica_placed")
            if not extra or "--replicas" not in extra:
                check_kernels(srv.base, name)
                check_health(srv.base, name)
            else:
                # Router: its own /healthz, then every replica behind it
                # held to the same health and kernel checks.
                status, h, _ = http_json(srv.base + "/healthz")
                snaps = h.get("replicas", []) if isinstance(h, dict) else []
                say("healthz", server=name, status=status,
                    healthz_ok=h.get("ok"), replicas=[
                        {k: r.get(k) for k in (
                            "index", "healthy", "routed_total",
                            "failures_total", "degraded")}
                        for r in snaps])
                if status != 200 or h.get("ok") is not True or not snaps:
                    raise SmokeFailure(f"{name}: router /healthz: {h}")
                for r in snaps:
                    if not r.get("healthy") or r.get("failures_total"):
                        raise SmokeFailure(f"{name}: replica unhealthy: {r}")
                    check_kernels(_url(r["address"]), f"{name}[{r['index']}]")
                    check_health(_url(r["address"]), f"{name}[{r['index']}]")
            srv.drain()
            return out, mem, srv.device, placed, replicas
        finally:
            srv.close()

    ref, _, dev, _, _ = serve("a_reference_tensor1", ["--tensor", "1"])
    if dev.get("count") != 4:
        raise SmokeFailure(f"--chips 4 needs four devices, found {dev}")

    got, mem, _, _, _ = serve("b_serve_mesh_1x4", ["--serve-mesh", "1,4"])
    for i, (r, g) in enumerate(zip(ref, got)):
        compare_logprobs(f"serve_mesh_1x4_vs_reference[{i}]", r, g)
    # The backend's own bytes_in_use per device (weights + pool); the
    # weight bytes by shard where a backend reports no memory stats (the
    # CPU rehearsal).  A quarter of the weights plus a quarter of the pool
    # on EVERY device (replicated norms and pos planes are noise): none
    # may hold more than 1.5x the mean, and none may be empty.
    key = ("bytes_in_use" if all(d.get("bytes_in_use") for d in mem)
           else "param_bytes")
    used = [d.get(key) or 0 for d in mem]
    say("placement", server="b_serve_mesh_1x4", source=key, bytes=used)
    if len(used) != 4 or min(used) <= 0 or max(used) > 1.5 * sum(used) / 4:
        raise SmokeFailure(f"serve-mesh 1,4 not spread over 4 devices: {used}")

    got, mem, _, placed, replicas = serve(
        "c_replicas_2x2",
        ["--replicas", "2", "--serve-mesh", "1,2", "--route", "cache-aware",
         "--canary-interval-s", "0"],
        revisit=True,
    )
    for i, (r, g) in enumerate(zip(ref + ref[:1], got)):
        compare_logprobs(f"replicas_2x2_vs_reference[{i}]", r, g)
    sets = {e["replica"]: set(e["param_devices"]) for e in placed}
    say("placement", server="c_replicas_2x2", replica_devices={
        k: sorted(v) for k, v in sets.items()}, served_by=replicas)
    if (sorted(sets) != [0, 1] or sets[0] & sets[1]
            or len(sets[0]) != 2 or len(sets[1]) != 2):
        raise SmokeFailure(f"replicas do not own disjoint device pairs: {sets}")
    if replicas[-1] != replicas[0]:
        raise SmokeFailure(
            f"cache-aware revisit went to replica {replicas[-1]}, the "
            f"prefix lives on {replicas[0]}"
        )
    return {"device": dev}


def cache_entries(path: Optional[str]) -> Optional[int]:
    try:
        return sum(1 for _ in Path(path).iterdir()) if path else None
    except OSError:
        return None


def run(spec: Spec = FULL, chips: int = 1, seed: int = 0,
        work_dir: Path = WORK_DIR, log_dir: Path = LOG_DIR,
        require_tpu: bool = True) -> int:
    """The whole smoke.  Returns the exit code; prints the result line
    last.  ``require_tpu=False`` (the CPU rehearsal's seam) still never
    reports ok: it only lets the later phases run so their control flow
    is exercised."""
    t_all = time.monotonic()
    work_dir = Path(work_dir)
    ckpt, logs = work_dir / "ckpt", Path(log_dir)
    work_dir.mkdir(parents=True, exist_ok=True)
    say("start", chips=chips, seed=seed, spec=dataclasses.asdict(spec),
        logprob_tolerance=LOGPROB_TOL)
    failures: List[str] = []
    dev: Dict[str, Any] = {}
    try:
        dev = write_checkpoint(spec, seed, ckpt, logs, require_tpu)
        before = cache_entries(dev.get("compile_cache"))
        if chips == 4:
            four_chips(spec, ckpt, logs)
        else:
            one_chip(spec, ckpt, logs)
        say("compile_cache", dir=dev.get("compile_cache"),
            entries_before_servers=before,
            entries_after=cache_entries(dev.get("compile_cache")))
    except SmokeFailure as e:
        failures.append(str(e))
    except Exception as e:  # a bug in the smoke is a failed smoke
        failures.append(f"{type(e).__name__}: {e}")
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    if dev.get("platform") != "tpu":
        failures.append(f"platform is {dev.get('platform')!r}, not 'tpu'")
    if dev and dev.get("count") != chips:
        failures.append(f"{dev.get('count')} devices, wanted {chips}")
    say("end", seconds=round(time.monotonic() - t_all, 1), failures=failures)
    if failures:   # no result line at all: the failures above, exit code 1
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"],
    }}), flush=True)
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["_write"]:
        return _child_write_checkpoint(argv[1:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded / replicated serving "
                         "paths and their one-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    return run(FULL, chips=args.chips, seed=args.seed)


if __name__ == "__main__":
    sys.exit(main())
