"""Benchmark: greedy decode throughput on the local TPU chip.

Prints ONE JSON line:
    {"metric": "...", "value": N, "unit": "tokens/sec/chip", "vs_baseline": N}

The metric mirrors BASELINE.json ("Llama-3 decode tokens/sec/chip"); the
baseline denominator is its v5p target of 50 tok/s/chip for 70B.  The
reference publishes no numbers of its own (BASELINE.md), so vs_baseline is
measured against that target.

The bench model is a ~1B-param Llama-3-architecture config (GQA 2:1, SwiGLU,
bf16) — the largest that comfortably fits a single v5e-lite chip with its KV
cache.  Decode throughput is measured over full-length generations with no
stop tokens, steady-state (after one compile warmup), batch 8.  The headline
value is the bf16-weight path (parity-honest vs the reference's fp32/bf16
serving); the int8 weight-only serving path is reported in `detail`.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

# Public TPU v5e (v5 lite) single-chip peaks; denominators for the
# utilization figures reported in `detail` (emitted as null when the
# device is not a v5 lite chip).
V5E_HBM_BYTES_PER_S = 819e9     # HBM bandwidth
V5E_BF16_FLOPS = 197e12         # MXU bf16 peak


# ---------------------------------------------------------------------------
# Regression gate: diff headline keys between two trajectory records
# ---------------------------------------------------------------------------

# Key-name direction classes for the --compare gate.  Throughput-ish
# keys regress DOWN, latency-ish keys regress UP; keys matching
# neither are reported but never gate (a mis-guessed direction must
# not fail CI).
_HIGHER_BETTER = (
    "per_s", "tok", "tflops", "gbps", "rate", "util", "goodput",
    "ceiling", "attain", "hit", "value", "vs_baseline",
)
_LOWER_BETTER = ("ms", "latency", "stall", "wait_", "overhead", "_s")


def _headline_keys(record: dict) -> dict:
    """Numeric headline keys of a BENCH_*/MULTICHIP_* record.

    Covers both record styles: proper numeric leaves of the JSON
    (dotted paths), and the older records whose bench stdout lives as
    a TRUNCATED string under "tail" — there, every '"key": number'
    fragment is recovered by regex (last occurrence wins).  Driver
    bookkeeping (rc / n / n_devices) never gates."""
    import re as _re

    skip = {"rc", "n", "n_devices", "devices"}
    out: dict = {}

    def walk(d, prefix=""):
        if isinstance(d, dict):
            for k, v in d.items():
                walk(v, f"{prefix}.{k}" if prefix else str(k))
        elif isinstance(d, str):
            for m in _re.finditer(
                r'"([A-Za-z0-9_]+)":\s*(-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)',
                d,
            ):
                if m.group(1) not in skip:
                    out[m.group(1)] = float(m.group(2))
        elif isinstance(d, (int, float)) and not isinstance(d, bool):
            if prefix.split(".")[-1] not in skip:
                out[prefix] = float(d)

    walk(record)
    return out


def compare_records(
    old: dict, new: dict, tolerance_pct: float = 5.0,
) -> dict:
    """Diff shared headline keys; a REGRESSION is a classified key
    moving in its worse direction by more than ``tolerance_pct``."""
    a, b = _headline_keys(old), _headline_keys(new)
    shared = sorted(set(a) & set(b))
    regressions, improvements, unclassified = [], [], []
    for k in shared:
        if a[k] == 0:
            continue
        rel = (b[k] - a[k]) / abs(a[k]) * 100.0
        low = k.lower()
        higher_better = any(t in low for t in _HIGHER_BETTER)
        lower_better = (
            not higher_better
            and any(t in low for t in _LOWER_BETTER)
        )
        entry = {
            "key": k, "old": a[k], "new": b[k],
            "delta_pct": round(rel, 2),
        }
        if higher_better and rel < -tolerance_pct:
            regressions.append(entry)
        elif lower_better and rel > tolerance_pct:
            regressions.append(entry)
        elif (higher_better or lower_better) and abs(rel) > tolerance_pct:
            improvements.append(entry)
        elif not (higher_better or lower_better) and abs(rel) > tolerance_pct:
            unclassified.append(entry)
    return {
        "shared_keys": len(shared),
        "tolerance_pct": tolerance_pct,
        "regressions": regressions,
        "improvements": improvements,
        "unclassified_changes": unclassified,
        "ok": not regressions,
    }


def compare_main() -> None:
    """``python bench.py --compare OLD.json [NEW.json]
    [--tolerance PCT]``: machine-check the bench trajectory — exits
    non-zero when a shared headline key regressed past tolerance.
    With NEW omitted, the newest record of OLD's family
    (BENCH_*/MULTICHIP_*) in OLD's directory stands in."""
    import glob as _glob
    import os as _os
    import sys as _sys

    argv = argv_rest = _sys.argv[1:]
    tol = 5.0
    if "--tolerance" in argv:
        i = argv.index("--tolerance")
        tol = float(argv[i + 1])
        # Drop the flag AND its value before the positional scan — a
        # bare "10" must not be mistaken for NEW.json.
        argv_rest = argv[:i] + argv[i + 2:]
    files = [
        a for a in argv_rest[argv_rest.index("--compare") + 1:]
        if not a.startswith("--")
    ][:2]
    if not files:
        raise SystemExit("--compare needs OLD.json [NEW.json]")
    old_path = files[0]
    if len(files) == 2:
        new_path = files[1]
    else:
        base = _os.path.basename(old_path)
        fam = base.split("_r")[0]
        cands = sorted(
            p for p in _glob.glob(_os.path.join(
                _os.path.dirname(old_path) or ".", f"{fam}_r*.json"
            )) if _os.path.abspath(p) != _os.path.abspath(old_path)
        )
        if not cands:
            raise SystemExit(f"no other {fam}_r*.json next to {old_path}")
        new_path = cands[-1]
    with open(old_path) as f:
        old = json.load(f)
    with open(new_path) as f:
        new = json.load(f)
    result = compare_records(old, new, tolerance_pct=tol)
    result["old"], result["new"] = old_path, new_path
    print(json.dumps(result, indent=1))
    if result["shared_keys"] == 0:
        # Heterogeneous rounds (a CPU controller round vs a chip
        # round) share nothing — say so loudly but do not fail: the
        # gate is for same-shaped rounds.
        print("bench-compare: WARNING: no shared headline keys",
              file=_sys.stderr)
    if not result["ok"]:
        raise SystemExit(3)


def load_harness(params, config, *, n_slots=8, max_len=1024,
                 block_size=128, duration_s=6.0, max_requests=400,
                 interactive_frac=0.5, seed=0,
                 i_prompt=64, i_new=8, b_prompt=512, b_new=32):
    """Open-loop (Poisson-arrival) load sweep over the HTTP server —
    the closed loop for the overload controller (overload.py): offered
    request rate vs goodput and per-class TTFT/ITL SLO attainment.

    Three phases:
      1. CALIBRATE: a closed-loop drain measures the sustainable
         request rate, and a low-rate flood sets the TTFT SLO at
         8x its median TTFT (attainment ~1.0 when healthy, degrading
         under overload — the sweep's y-axis).
      2. SWEEP (``serving_goodput_vs_rate``): floods at {0.5, 1, 2, 4}x
         the sustainable rate, mixed interactive/batch traffic, ladder
         + priority classes ON.  Each point reports per-class served/
         refused/hung counts, TTFT percentiles, SLO attainment over
         served requests, and goodput tokens/s.
      3. A/B at 4x (``serving_overload_ladder_vs_static``): the same
         flood against priority_classes=off (the pre-PR-9 static
         max_queue 503) vs on — the record that the ladder holds
         interactive attainment where the static config collapses,
         with zero hung clients either way.  (4x, not 2x: the
         sustainable anchor reads conservative — see phase 3.)

    Pure host/HTTP-side measurement: the device work is the same
    serving stack every other bench drives."""
    from jax_llama_tpu.obs import Observability
    from jax_llama_tpu.overload import (
        open_loop_flood, poisson_schedule, summarize_flood,
    )
    from jax_llama_tpu.serving import ContinuousBatcher
    from jax_llama_tpu.server import LLMServer

    rng = np.random.RandomState(9000 + seed)
    V = config.vocab_size
    # Interactive: short chat-turn shape.  Batch: long-prompt bulk
    # shape — the cost asymmetry the static depth count cannot see.
    I_PROMPT, I_NEW = i_prompt, i_new
    B_PROMPT, B_NEW = b_prompt, b_new

    def payload_fn(i):
        # Golden-ratio stride: a deterministic, well-interleaved mix
        # at any fraction (blocks of one class would skew the short
        # floods below).
        interactive = (i * 0.6180339887) % 1.0 < interactive_frac
        if interactive:
            toks = rng.randint(1, V, I_PROMPT).tolist()
            return {"prompt": toks, "max_new_tokens": I_NEW,
                    "priority": "interactive", "stream": True,
                    "timeout_s": 30.0}
        toks = rng.randint(1, V, B_PROMPT).tolist()
        return {"prompt": toks, "max_new_tokens": B_NEW,
                "priority": "batch", "stream": True,
                "timeout_s": 30.0}

    def make_server(priority_on, slo_ttft_ms=None, slo_itl_ms=None):
        obs = Observability(slo_ttft_ms=slo_ttft_ms,
                            slo_itl_ms=slo_itl_ms)
        cb = ContinuousBatcher(
            params, config, n_slots=n_slots, max_len=max_len,
            block_size=block_size, decode_chunk=16, prefill_budget=512,
            obs=obs,
        )
        return LLMServer(
            cb, max_queue=64, priority_classes=priority_on,
            # React within the flood window: these are drill-scale
            # dwell/cooldown, not the production defaults.
            brownout_dwell_s=0.5, brownout_cooldown_s=2.0,
            watchdog_deadline_s=None,
        )

    # -- phases 0/1: warmup + calibrate -------------------------------------
    # The warmup burst compiles every program the floods will hit
    # (multi-row inserts, the K ramp, fused prefill chunks); the SAME
    # burst is then re-run timed for the sustainable rate, and a few
    # SEQUENTIAL interactive requests (no queueing) set the TTFT SLO
    # at 8x their median — attainment ~1.0 when healthy, degrading
    # under overload.  Controller OFF here: the drill-scale dwell
    # would let the ladder escalate (even shed) during the
    # compile-stalled warmup, leaving batch-shape programs uncompiled
    # and inflating the sustainable-rate anchor the whole sweep keys
    # off.
    n_cal = 2 * n_slots
    with make_server(False) as srv:
        open_loop_flood(
            srv.address, [0.0] * n_cal, payload_fn,
            timeout_s=600.0, join_timeout_s=900.0,
        )
        t0 = time.time()
        open_loop_flood(
            srv.address, [0.0] * n_cal, payload_fn,
            timeout_s=120.0, join_timeout_s=300.0,
        )
        cal_wall = time.time() - t0
        base_ttfts = []
        for j in range(4):
            r = open_loop_flood(
                srv.address, [0.0], lambda i: payload_fn(0),
                timeout_s=120.0, join_timeout_s=300.0,
            )[0]
            if r["ttft_ms"] is not None:
                base_ttfts.append(r["ttft_ms"])
    sustainable = n_cal / cal_wall
    base_ttfts.sort()
    base_ttft = (
        base_ttfts[len(base_ttfts) // 2] if base_ttfts else 100.0
    )
    # 8x the UNLOADED median: an SLO that is attainable (~1.0) at the
    # sustainable rate — normal queueing behind a few concurrent
    # requests costs several unloaded-TTFTs — so the sweep measures
    # overload degradation, not a bar nobody could hold (3x was
    # already missed at 1x offered load).
    slo_ttft_ms = max(50.0, round(8.0 * base_ttft, 1))

    # -- phase 2: rate sweep, ladder on -------------------------------------
    def flood(rate, priority_on):
        # Adaptive window: at least ~24 expected arrivals per point
        # (a 6 s window at a slow backend's sustainable rate would
        # sample almost nothing), capped so the sweep stays bounded.
        dur = min(60.0, max(duration_s, 24.0 / max(rate, 1e-6)))
        sched = poisson_schedule(rate, dur, seed=seed + 1)
        if len(sched) > max_requests:
            # Truncation shortens the real flood window: goodput and
            # the point's effective offered rate must be computed over
            # the span actually flooded, not the nominal one.
            sched = sched[:max_requests]
            dur = sched[-1]
        with make_server(priority_on, slo_ttft_ms=slo_ttft_ms) as srv:
            recs = open_loop_flood(
                srv.address, sched, payload_fn,
                timeout_s=60.0, join_timeout_s=240.0,
            )
            summary = summarize_flood(
                recs, slo_ttft_ms=slo_ttft_ms, duration_s=dur
            )
            h = srv.overload.health()
            summary["rung_final"] = h["rung"]
            summary["sheds"] = h["sheds_total"]
            summary["refused"] = dict(h["refused"])
        return summary

    sweep = {}
    for mult in (0.5, 1.0, 2.0, 4.0):
        sweep[f"x{mult:g}"] = flood(sustainable * mult, True)

    # -- phase 3: ladder vs static at 4x ------------------------------------
    # 4x, not 2x: the sustainable estimate comes from a closed-loop
    # burst drain and reads conservative, so 2x of it may not saturate
    # a fast backend at all — 4x reliably lands in the regime the
    # drill is about (the ISSUE criterion is ">= 2x").
    def _ab_view(s):
        return {
            "interactive_attainment": s["interactive"]["slo_attainment"],
            "interactive_ttft_ms_p99": s["interactive"]["ttft_ms_p99"],
            "interactive_served": s["interactive"]["served"],
            "batch_served": s["batch"]["served"],
            "batch_refused_503": s["batch"]["refused_503"],
            "timeouts_504": (
                s["interactive"]["timeout_504"] + s["batch"]["timeout_504"]
            ),
            "hung_total": s["hung_total"],
            "goodput_tokens_per_s": s.get("goodput_tokens_per_s"),
            "rung_final": s.get("rung_final"),
            "sheds": s.get("sheds"),
        }

    static = flood(sustainable * 4.0, False)
    return {
        "sustainable_req_per_s": round(sustainable, 2),
        "slo_ttft_ms": slo_ttft_ms,
        "mix": {
            "interactive": {"prompt": I_PROMPT, "max_new": I_NEW},
            "batch": {"prompt": B_PROMPT, "max_new": B_NEW},
            "interactive_frac": interactive_frac,
        },
        "duration_s": duration_s,
        "serving_goodput_vs_rate": sweep,
        "serving_overload_ladder_vs_static": {
            "offered_x_sustainable": 4.0,
            "ladder": _ab_view(sweep["x4"]),
            "static_max_queue": _ab_view(static),
        },
    }


def load_harness_main() -> None:
    """Standalone entry (``python bench.py --load-harness``): the
    open-loop overload sweep on a small model, printed as one JSON
    line.  CPU-safe — the harness measures controller behavior
    (attainment held, sheds clean, zero hangs), not chip throughput;
    the full-size TPU round embeds the same keys via main()."""
    import jax
    import jax_llama_tpu as jlt
    from jax_llama_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    config = jlt.get_config(
        "llama3-8b",
        dim=256, n_layers=4, n_heads=8, n_kv_heads=4,
        multiple_of=128, vocab_size=4096, max_seq_len=1024,
        param_dtype="float32" if jax.default_backend() == "cpu"
        else "bfloat16",
    )
    params = jlt.init_params(jax.random.PRNGKey(0), config)
    result = {
        "metric": "open-loop overload sweep (goodput + per-class SLO "
                  "attainment vs offered rate), small-model harness",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "params": jlt.param_count(params),
        # Lighter request shapes than the TPU round: the small-model
        # harness proves controller BEHAVIOR, and a CPU backend's
        # sustainable rate would make the full shapes crawl.
        "detail": load_harness(
            params, config, n_slots=4, b_prompt=256, b_new=16
        ),
    }
    print(json.dumps(result))


def multichip_serving_main(record_path=None) -> None:
    """``python bench.py --multichip-serving [--record PATH]``: the
    scale-out serving dryrun round (MULTICHIP_r06) on the forced
    8-host-device CPU mesh — no TPU pod required.  Three certs:

      1. **Sharded-chunk parity**: the mesh-placed batcher
         (``--serve-mesh 2,2`` geometry: KV pool head-sharded over
         tensor, state rows over data) serves a chunked + fused-
         admission mix TOKEN-IDENTICALLY to single-chip.
      2. **Sharded lowering contracts**: the analysis mesh pass
         (donated-leaf donor attributes + sharding stability) is clean
         for every registered mesh variant.
      3. **Routed-replica serving**: 2 LLMServer replicas behind a
         ReplicaRouter serve a concurrent burst token-identically to
         one replica, with the wall tokens/s recorded.

    CPU numbers measure BEHAVIOR, not chips — the throughput keys roll
    forward at the next TPU-attached round, like BENCH_r06 did for the
    overload controller."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass

    from jax_llama_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json as _json
    import threading
    import urllib.request

    import jax_llama_tpu as jlt
    from jax_llama_tpu.parallel.partition import shard_params
    from jax_llama_tpu.parallel.serve_mesh import (
        ServeMeshSpec, build_serve_mesh, mesh_shape,
    )
    from jax_llama_tpu.router import ReplicaRouter
    from jax_llama_tpu.server import LLMServer
    from jax_llama_tpu.serving import ContinuousBatcher

    n_devices = len(jax.devices())
    tail: list = []

    config = jlt.get_config(
        "tiny", vocab_size=512, dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, multiple_of=32, max_seq_len=256,
        dtype="float32", param_dtype="float32",
    )
    params = jlt.init_params(jax.random.PRNGKey(0), config)

    # -- 1. sharded-chunk parity on the 2x2 serving mesh -------------------
    mesh = build_serve_mesh(
        ServeMeshSpec(data=2, tensor=2), devices=jax.devices()[:4]
    )
    sp = shard_params(params, mesh, config)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 512, size=n).tolist()
               for n in (12, 30, 48)]

    def serve(p, m):
        cb = ContinuousBatcher(
            p, config, n_slots=4, max_len=256, mesh=m,
            decode_chunk=8, prefill_budget=32,
        )
        rids = [cb.submit(pr, max_new_tokens=8, seed=7 + i)
                for i, pr in enumerate(prompts)]
        t0 = time.time()
        done = cb.run_to_completion()
        wall = time.time() - t0
        return [done[r] for r in rids], wall, cb

    base, _, _ = serve(params, None)
    sharded, _, cb = serve(sp, mesh)
    parity_ok = sharded == base and cb._mesh_placed
    tail.append(
        f"dryrun_multichip_serving ok: sharded chunk programs on "
        f"data=2 tensor=2 mesh token-identical={parity_ok} "
        f"({sum(map(len, sharded))} tokens)"
    )

    # -- 2. sharded lowering contracts (analysis mesh pass) -----------------
    from jax_llama_tpu.analysis.lowering import check_mesh_traces

    findings = check_mesh_traces()
    lowering_ok = not findings
    mesh_contracts = sorted(
        name for name, c in __import__(
            "jax_llama_tpu.analysis.contracts", fromlist=["REGISTRY"]
        ).REGISTRY.items() if c.mesh_build is not None
    )
    tail.append(
        f"dryrun_multichip_serving ok: mesh lowering contracts clean="
        f"{lowering_ok} ({len(mesh_contracts)} sharded programs: "
        f"{', '.join(mesh_contracts)})"
    )

    # -- 3. routed 2-replica serving vs 1 replica ---------------------------
    def mk_server(i):
        return LLMServer(
            ContinuousBatcher(
                params, config, n_slots=2, max_len=256, decode_chunk=8,
            ),
            replica_id=i,
        ).start()

    def post(url, payload):
        req = urllib.request.Request(
            url + "/generate", data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=600) as r:
            return _json.loads(r.read())

    burst = [
        {"prompt": prompts[i % len(prompts)], "max_new_tokens": 8,
         "seed": 100 + i}
        for i in range(6)
    ]

    def flood(url):
        out = [None] * len(burst)

        def one(i):
            out[i] = post(url, burst[i])["tokens"]

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(burst))]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out, time.time() - t0

    solo = mk_server(0)
    try:
        want, _ = flood(solo.address)
    finally:
        solo.stop()
    servers = [mk_server(i) for i in range(2)]
    router = ReplicaRouter(servers, policy="least-loaded").start()
    try:
        got, wall = flood(router.address)
        routed_ok = got == want
        toks = sum(len(t) for t in got if t)
        routed_tps = round(toks / max(wall, 1e-9), 2)
        h = router.health()
        both_served = all(
            r["routed_total"] > 0 for r in h["replicas"]
        )
        # -- fleet cache baseline (PR 13 telemetry): publish the SAME
        # chain on both replicas (direct posts — deterministic), then
        # scrape the router's fleet cache view.  The duplicate-chain
        # bytes are the number that justifies the cache-aware
        # disaggregation scheduler (ROADMAP item 2); the scrape cost
        # bounds what a scheduler tick would pay.
        shared = {"prompt": prompts[2], "max_new_tokens": 4, "seed": 3}
        for s in servers:
            post(s.address, shared)
        t0 = time.time()
        with urllib.request.urlopen(
            router.address + "/debug/kv/fleet", timeout=60
        ) as r:
            fleet_doc = _json.loads(r.read())
        fleet_scrape_ms = round((time.time() - t0) * 1000.0, 2)
        fl = fleet_doc["fleet"]
        fleet_ok = fl["duplicate_kv_bytes"] > 0 and (
            sorted(fl["replicas_scraped"]) == [0, 1]
        )
        per_replica_hit = {
            str(p["replica"]): p["hit_ratio"]
            for p in fleet_doc["replicas"]
        }
    finally:
        router.stop()
        for s in servers:
            s.stop()
    tail.append(
        f"dryrun_multichip_serving ok: routed 2-replica serving "
        f"token-identical={routed_ok}, both replicas served="
        f"{both_served}, {routed_tps} tok/s wall (CPU behavior round)"
    )
    tail.append(
        f"dryrun_multichip_serving ok: fleet cache view duplicate-"
        f"chain bytes={fl['duplicate_kv_bytes']} "
        f"({fl['duplicate_chains']} chains on both replicas), fleet "
        f"hit ratio={fl['prefix_hit_ratio']}, scrape="
        f"{fleet_scrape_ms} ms"
    )

    # -- 4. fleet-TTFT A/B: cache-aware vs least-loaded (r08) ---------------
    # Deterministic revisit-heavy workload: 4 sessions, each visited
    # 3 times with a growing prompt (the chat shape), posted
    # SEQUENTIALLY so routing policy is the only variable.  Under
    # least-loaded the revisits alternate replicas (half the turns
    # re-prefill cold); cache-aware routes each turn to the replica
    # holding the session's chain — the fleet prefix-hit-tokens ratio
    # is the headline, per-request wall time the TTFT proxy (CPU
    # behavior round; max_new=2 keeps the measurement
    # prefill-dominated).
    rng2 = np.random.RandomState(7)
    bases = [rng2.randint(1, 512, size=48).tolist() for _ in range(4)]
    turns = [
        [b + rng2.randint(1, 512, size=16 * k).tolist()
         for k in range(3)]
        for b in bases
    ]

    def fleet_ab(policy):
        servers = [
            LLMServer(
                ContinuousBatcher(
                    params, config, n_slots=2, max_len=256,
                    decode_chunk=8,
                ),
                replica_id=i,
            ).start()
            for i in range(2)
        ]
        router = ReplicaRouter(
            servers, policy=policy, health_interval_s=0,
            block_size=servers[0].batcher.block_size,
        ).start()
        lat: list = []
        try:
            # Warmup (compile paths) off the clock.
            post(router.address,
                 {"prompt": bases[0][:20], "max_new_tokens": 2})
            router.check_health_now()
            for round_i in range(3):
                for s, session_turns in enumerate(turns):
                    t0 = time.time()
                    post(router.address, {
                        "prompt": session_turns[round_i],
                        "max_new_tokens": 2, "seed": s,
                    })
                    lat.append((time.time() - t0) * 1000.0)
                router.check_health_now()
            router.wait_handoffs(30.0)
            hit = sum(
                s.batcher.prefix_hit_tokens_total for s in servers
            )
            prompt_t = sum(
                s.batcher.prompt_tokens_total for s in servers
            )
            with router._lock:
                handoffs = router.handoffs_completed_total
                stale = router.cache_stale_routes_total
            lat.sort()
            return {
                "fleet_prefix_hit_ratio": round(
                    hit / max(1, prompt_t), 6
                ),
                "prefix_hit_tokens_total": int(hit),
                "prompt_tokens_total": int(prompt_t),
                "ttft_ms_p50": round(lat[len(lat) // 2], 2),
                "ttft_ms_p99": round(lat[-1], 2),
                "handoffs_completed": int(handoffs),
                "stale_routes": int(stale),
            }, router, servers
        except BaseException:
            router.stop()
            for s in servers:
                s.stop()
            raise

    ll, ll_router, ll_servers = fleet_ab("least-loaded")
    ll_router.stop()
    for s in ll_servers:
        s.stop()
    ca, ca_router, ca_servers = fleet_ab("cache-aware")
    try:
        # Dedup-by-migration drill (the demote-after-export
        # acceptance): publish one FRESH chain on BOTH replicas
        # directly (fresh = no deeper session suffix hangs off it, so
        # the leaves-first source drop can actually release it), then
        # migrate it — fleet duplicate bytes must DECREASE (the
        # source demotes/drops its copy; the destination already
        # holding it makes the import a benign no-op).
        dup_tokens = rng2.randint(1, 512, size=48).tolist()
        dup_prompt = {"prompt": dup_tokens, "max_new_tokens": 2,
                      "seed": 99}
        for s in ca_servers:
            post(s.address, dup_prompt)
        ca_router.check_health_now()
        dup_before = ca_router.fleet_kv_json()["fleet"][
            "duplicate_kv_bytes"
        ]
        from jax_llama_tpu.router import chain_keys as _ck

        # "prompt" payloads admit the raw token list verbatim — the
        # chain keys recompute exactly.
        keys_hex = [
            k.hex() for k in _ck(
                dup_tokens, ca_servers[0].batcher.block_size,
            )
        ]
        ca_router.migrate_chain(keys_hex, src=0, dst=1)
        assert ca_router.wait_handoffs(30.0)
        dup_after = ca_router.fleet_kv_json()["fleet"][
            "duplicate_kv_bytes"
        ]
    finally:
        ca_router.stop()
        for s in ca_servers:
            s.stop()
    ab_ok = (
        ca["fleet_prefix_hit_ratio"] >= ll["fleet_prefix_hit_ratio"]
        and dup_after < dup_before
    )
    tail.append(
        f"dryrun_multichip_serving ok: fleet-TTFT A/B cache-aware "
        f"hit ratio={ca['fleet_prefix_hit_ratio']} vs least-loaded "
        f"{ll['fleet_prefix_hit_ratio']} (>= required: {ab_ok}), "
        f"ttft p50 {ca['ttft_ms_p50']} vs {ll['ttft_ms_p50']} ms, "
        f"duplicate bytes {dup_before} -> {dup_after} after "
        f"demote-after-export handoff"
    )

    ok = (
        parity_ok and lowering_ok and routed_ok and fleet_ok and ab_ok
    )
    result = {
        "n_devices": n_devices,
        "rc": 0 if ok else 1,
        "ok": ok,
        "skipped": False,
        "tail": "\n".join(tail) + "\n",
        "serving_mesh": {
            "mesh": mesh_shape(mesh),
            "sharded_chunk_token_identical": parity_ok,
            "mesh_lowering_contracts_clean": lowering_ok,
            "mesh_contract_programs": mesh_contracts,
            "routed_replicas": 2,
            "routed_token_identical": routed_ok,
            "routed_both_replicas_served": both_served,
            "routed_tokens_per_s_wall_cpu": routed_tps,
            "route_policy": "least-loaded",
            # Fleet cache baseline (router /debug/kv/fleet): the next
            # MULTICHIP round diffs these — duplicate-chain bytes are
            # the disaggregation scheduler's headline input.
            "fleet_kv": {
                "duplicate_chains": fl["duplicate_chains"],
                "fleet_duplicate_kv_blocks": fl["duplicate_kv_blocks"],
                "fleet_duplicate_kv_bytes": fl["duplicate_kv_bytes"],
                "fleet_prefix_hit_ratio": fl["prefix_hit_ratio"],
                "per_replica_hit_ratio": per_replica_hit,
                "digest_scrape_ms": fleet_scrape_ms,
                "fleet_view_nonzero_duplicates": fleet_ok,
            },
            # r08: globally cache-aware routing A/B on the
            # deterministic revisit-heavy workload — the hit-ratio
            # delta is the router-side radix index earning its keep;
            # the duplicate-bytes drop is the demote-after-export
            # handoff deduplicating the fleet.  CPU behavior round —
            # TTFT ms roll forward at the next TPU round.
            "fleet_ab_r08": {
                "workload": (
                    "4 sessions x 3 growing turns, sequential, "
                    "max_new=2"
                ),
                "cache_aware": ca,
                "least_loaded": ll,
                "duplicate_kv_bytes_before_handoff": dup_before,
                "duplicate_kv_bytes_after_handoff": dup_after,
                "cache_aware_ge_least_loaded": ab_ok,
            },
        },
    }
    print(_json.dumps(result))
    if record_path:
        with open(record_path, "w") as f:
            _json.dump(result, f, indent=1)
            f.write("\n")
    if not ok:
        # The certs are the point: a red parity/lowering/routing cert
        # must fail `make mesh-serve` (and any CI wiring), not just
        # print "ok": false.
        raise SystemExit(result["rc"])


def main() -> None:
    import jax
    import jax.numpy as jnp
    import jax_llama_tpu as jlt
    from jax_llama_tpu.engine import GenerationConfig, generate
    from jax_llama_tpu.ops.quant import quantize_params
    from jax_llama_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # param_dtype bf16: decode is HBM-bandwidth-bound, so serving keeps
    # weights in bf16 (2 bytes/param of traffic per step, not 4).
    config = jlt.get_config(
        "llama3-8b",
        dim=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        multiple_of=256, vocab_size=32000, max_seq_len=1024,
        param_dtype="bfloat16",
    )
    params = jlt.init_params(jax.random.PRNGKey(0), config)
    n_params = jlt.param_count(params)

    B, P, N = 8, 128, 128
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, config.vocab_size, (B, P)), jnp.int32)
    mask = jnp.ones((B, P), dtype=bool)
    key = jax.random.PRNGKey(0)

    _salt = [0]

    def salted_key():
        """A distinct key per timed call (fold a counter into the PRNG
        key, unused under greedy), kept from the rounds recorded in
        BENCH_r03-r05; whether it matters on a local chip: not
        measured (ROADMAP A0 replaces this benchmark)."""
        _salt[0] += 1
        return jax.random.fold_in(key, _salt[0])

    def run(p, max_new: int) -> float:
        gc = GenerationConfig(
            max_new_tokens=max_new, temperature=0.0, stop_tokens=()
        )
        skey = salted_key()
        t0 = time.time()
        out = generate(p, tokens, mask, skey, config=config, gen_config=gc)
        # Sync via host transfer of the result: the [B, P+N] int32
        # fetch is a few KB — negligible vs the decode itself.
        np.asarray(out)
        return time.time() - t0

    def measure(p):
        """Steady-state decode rate: the (prefill + N) vs (prefill + 1)
        difference cancels both prefill time and the constant per-call
        dispatch overhead out of the metric.

        RANK-PAIRED MEDIAN differencing (r5; was min-of-5 on each
        side): the 5 full and 5 short timings are each sorted, paired
        BY RANK (k-th order statistic of one against the k-th of the
        other — the runs are independent, so there is no meaningful
        run-to-run pairing to preserve), and the median of those
        rank-matched differences is taken.  min-of-min composed two
        independent minima, and the full-run side occasionally
        produces an anomalously FAST outlier (r5 instrumented run:
        full samples [0.456, 0.491, 0.492, 0.493, 0.493] s — one
        35 ms-fast fluke against a 2 ms-tight cluster) which min()
        then selects, overstating the rate by ~9%.  The rank-paired
        median is outlier-robust and agreed with the jitter-immune
        xplane device rate to 0.2% in the same session (2728 vs 2734
        tok/s, vs min-of-min's 2985).  The returned fulls[0] /
        shorts[0] companions are each side's min-of-5 (reported for
        context, not inputs to the rate)."""
        fulls = sorted(run(p, N) for _ in range(5))
        shorts = sorted(run(p, 1) for _ in range(5))
        diffs = sorted(f - s for f, s in zip(fulls, shorts))
        decode_s = max(diffs[len(diffs) // 2], 1e-9)
        return B * (N - 1) / decode_s, decode_s, fulls[0], shorts[0]

    t0 = time.time()
    run(params, N)
    run(params, 1)
    compile_s = time.time() - t0

    toks_per_s, decode_s, full, short = measure(params)

    qparams = quantize_params(params)
    run(qparams, N)
    run(qparams, 1)
    int8_toks_per_s, _, _, _ = measure(qparams)

    # ------------------------------------------------------------------
    # Decode HBM roofline: modeled bytes/step ÷ measured step time.
    # Decode is bandwidth-bound, so bytes = weight traffic (every matmul
    # weight read once per step; the embedding table contributes only B
    # row lookups) + KV-cache read at the mean context length.  Writes
    # and activations are <1% at this scale and are not modeled.
    # ------------------------------------------------------------------
    is_v5e = "v5 lite" in str(jax.devices()[0]).lower()
    embed_entries = config.vocab_size * config.dim

    def modeled_step_bytes(weight_itemsize: float) -> float:
        """The one byte model both figures below read: weights once per
        step + bf16 KV at mean context."""
        weight_bytes = (n_params - embed_entries) * weight_itemsize
        mean_ctx = P + (N + 1) / 2
        kv_bytes = (
            2 * config.n_layers * B * mean_ctx
            * config.kv_heads * config.head_dim * 2  # bf16 cache
        )
        return weight_bytes + kv_bytes

    def hbm_util(weight_itemsize: float, per_step_s: float) -> float:
        return (
            modeled_step_bytes(weight_itemsize)
            / per_step_s / V5E_HBM_BYTES_PER_S
        )

    bf16_hbm = hbm_util(2.0, decode_s / (N - 1))
    int8_step_s = B / int8_toks_per_s
    int8_hbm = hbm_util(1.0, int8_step_s)

    def roofline_tps(weight_itemsize: float) -> float:
        """Decode ceiling if every modeled byte moved at the v5e HBM peak
        with zero other time.  Context for vs_baseline: the param-scaled
        50-tok/s target sits at ~100% of this ceiling for the bf16 B=8
        geometry — crossing ~0.95 vs_baseline means saturating the chip's
        memory system, not trimming overhead."""
        return B / (
            modeled_step_bytes(weight_itemsize) / V5E_HBM_BYTES_PER_S
        )

    # ------------------------------------------------------------------
    # Long-prompt prefill through the compiled Pallas flash kernel
    # (attn_impl="auto" resolves to flash for T>8).  A lax.scan over k
    # independent prefills amortizes this environment's ~100ms per-call
    # dispatch overhead; (k=3) - (k=1) differencing cancels the rest.
    # Small vocab keeps the [1, S, V] fp32 logits that force the
    # computation from dominating memory; FLOPs are counted causally
    # (half the S×S score/weight matmuls — the flash kernel's block
    # skip means executed FLOPs match this closely).
    # ------------------------------------------------------------------
    from jax import lax
    from jax_llama_tpu.models import forward as model_forward

    prefill_sources: list = []  # "xplane_device" | "wall" per measured S

    def prefill_tflops(S: int, impl: str):
        cfg = config.replace(
            vocab_size=512, max_seq_len=S, attn_impl=impl
        )
        pparams = jlt.init_params(jax.random.PRNGKey(1), cfg)
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]

        def one(p, toks):
            logits, _ = model_forward(p, toks, pos, cfg)
            return logits.astype(jnp.float32).sum()

        @jax.jit
        def reps(p, toks_k):
            return lax.scan(
                lambda c, t: (c + one(p, t), None), jnp.float32(0), toks_k
            )[0]

        def timed(k):
            toks = jnp.asarray(
                rng.randint(0, cfg.vocab_size, (k, 1, S)), jnp.int32
            )
            float(reps(pparams, toks))  # compile warmup (per k: shapes differ)
            best = float("inf")
            for i in range(5):  # min-of-5: same jitter policy as decode
                # Salt: vary one token per repetition (anti-caching).
                toks = toks.at[0, 0, 0].set((i * 7 + 1) % cfg.vocab_size)
                t0 = time.time()
                float(reps(pparams, toks))
                best = min(best, time.time() - t0)
            return best

        # Device-time measurement preferred (r5, same rationale as the
        # decode headline): one traced k=1 run's summed device-op time
        # IS the prefill — no differencing, no dispatch to cancel, no
        # min-of-min outlier bias (the wall path read ~2% low vs the
        # device figures).  The wall differencing (two extra compiles +
        # ~20 prefill executions per size) runs ONLY as the fallback.
        per_prefill_s = None
        try:
            from jax_llama_tpu.utils.profiling import device_op_times

            toks1 = jnp.asarray(
                rng.randint(0, cfg.vocab_size, (1, 1, S)), jnp.int32
            )
            float(reps(pparams, toks1))  # compile warmup
            agg = device_op_times(
                lambda: float(reps(pparams, toks1)), by="op"
            )
            dev_s = sum(agg.values()) / 1e12
            if dev_s > 0:
                per_prefill_s = dev_s
        except Exception:
            pass
        if per_prefill_s is None:
            # Provenance must be visible: the wall path reads ~2% low,
            # so cross-environment comparisons need to know which path
            # produced the number (the detail dict records it).
            prefill_sources.append("wall")
            per_prefill_s = max((timed(3) - timed(1)) / 2, 1e-9)
        else:
            prefill_sources.append("xplane_device")

        D, L, F = cfg.dim, cfg.n_layers, cfg.ffn_dim
        kv = cfg.kv_heads * cfg.head_dim
        matmul = 2 * S * L * (2 * D * D + 2 * D * kv + 3 * D * F)
        attn = 2 * S * S * D * L  # causal: QK half + PV half
        head = 2 * S * D * cfg.vocab_size
        flops = matmul + attn + head
        return per_prefill_s, flops / per_prefill_s / 1e12

    flash8k_s, flash8k_tf = prefill_tflops(8192, "auto")
    flash16k_s, flash16k_tf = prefill_tflops(16384, "auto")
    flash32k_s, flash32k_tf = prefill_tflops(32768, "auto")

    # ------------------------------------------------------------------
    # Long-context decode (BASELINE config 4's 8k->32k story): B=1 with a
    # 16k-token context — chunked flash prefill, then append-free decode
    # over the full cache.  KV reads dominate weight reads at this length
    # (~1.07GB cache + 1.94GB weights per step).
    # ------------------------------------------------------------------
    CTX, NEW = 16256, 64
    lc_tokens = jnp.asarray(
        rng.randint(0, config.vocab_size, (1, CTX)), jnp.int32
    )
    lc_mask = jnp.ones((1, CTX), dtype=bool)

    def lc_run(max_new: int) -> float:
        gc = GenerationConfig(
            max_new_tokens=max_new, temperature=0.0, stop_tokens=(),
            prefill_chunk=2048,
        )
        t0 = time.time()
        np.asarray(generate(
            params, lc_tokens, lc_mask, salted_key(), config=config,
            gen_config=gc,
        ))
        return time.time() - t0

    lc_run(NEW); lc_run(1)
    lc_full = min(lc_run(NEW) for _ in range(3))
    lc_short = min(lc_run(1) for _ in range(3))
    lc_toks_per_s = (NEW - 1) / max(lc_full - lc_short, 1e-9)

    # ------------------------------------------------------------------
    # Continuous-batching serving throughput through the Pallas
    # paged-attention decode kernel (block-table pool, 8 slots, ~1k-token
    # contexts).  Wall-clock; min-of-3 full drains.  The 8 submits are
    # admitted as ONE batched prefill dispatch (burst admission), and
    # the HEADLINE runs CHUNKED decode (decode_chunk=16: up to 16 fused
    # decode iterations per dispatch, host state device-resident) —
    # fewer host syncs and dispatches per token; the K=1 loop recorded
    # ~96% host overhead (BENCH_r05: 68 tok/s wall vs 1800 device; not
    # measured on a local chip).  The
    # K sweep below records where that gap goes.
    # ------------------------------------------------------------------
    from jax_llama_tpu.serving import ContinuousBatcher

    def serve_run(decode_chunk=16, p=params, **ctor_kw):
        # prefill_budget mirrors the run.py serving default (fused
        # prefill-decode scheduling); this COLD burst still admits
        # through the classic batched insert — nobody is decoding yet —
        # so the number stays comparable to r05's.  ctor_kw forwards
        # kernel-selection overrides (prefill_kernel / decode_kernel,
        # ops/kernels.py) for the A/B sections below.
        cb = ContinuousBatcher(
            p, config, n_slots=8, max_len=1024, block_size=128,
            decode_chunk=decode_chunk, prefill_budget=512, **ctor_kw,
        )
        _salt[0] += 1
        srng = np.random.RandomState(1000 + _salt[0])  # salted prompts
        for _ in range(8):
            # 850 tokens pad to 7 blocks (896); +48 stays within 1024.
            cb.submit(list(srng.randint(1, config.vocab_size, 850)),
                      max_new_tokens=48)
        t0 = time.time()
        first = cb.step()          # burst admission + first decode step
        admit_s = time.time() - t0
        emitted = len(first)
        while cb.pending():
            emitted += len(cb.step())
        return time.time() - t0, emitted, admit_s

    serve_run()  # compile warmup (insert + chunk programs, K ramp)
    serve_best, serve_toks, admit_s = min(serve_run() for _ in range(3))
    paged_serving_toks_per_s = serve_toks / serve_best

    # Decode-chunk K sweep (wall tok/s at K ∈ {1, 4, 8, 16}): the perf
    # trajectory's record of how much of the host-overhead gap each
    # chunk size closes.  K=16 is the headline above (min-of-3); the
    # smaller Ks run min-of-2 (the K=1 drain alone is ~5 s here).
    chunk_sweep = {"K16": round(paged_serving_toks_per_s, 2)}
    for K in (1, 4, 8):
        t_k, n_k, _ = min(serve_run(decode_chunk=K) for _ in range(2))
        chunk_sweep[f"K{K}"] = round(n_k / t_k, 2)

    # int8 WEIGHT-only serving (reachable via run.py --quantize but
    # never benched through the batcher before r06: the serving benches
    # only ever measured int8 KV): the same burst drain on
    # quantize_params weights — decode is weight-bandwidth-bound, so
    # this is the serving-path realization of the standalone int8
    # decode win.
    serve_run(p=qparams)  # warmup (int8 insert + chunk programs)
    i8_t, i8_n, _ = min(serve_run(p=qparams) for _ in range(2))
    paged_serving_int8w_toks_per_s = i8_n / i8_t

    # ------------------------------------------------------------------
    # Decode-kernel A/B (ops/kernels.py selection layer): the same
    # 8-slot burst drain through each decode attention path —
    #   paged        the custom block-table Pallas kernel (headline),
    #   stock_paged  the stock Pallas paged-attention kernel
    #                (--decode-kernel stock-paged; T=1 steps only, the
    #                fused-chunk prefill rows keep flash),
    #   gathered     the XLA dense-gather view (--decode-kernel
    #                gathered, i.e. use_pallas_kernel=False).
    # Wall tok/s, min-of-2 drains; key names embed tok_per_s so
    # --compare classifies regressions in the right direction.  A
    # kernel that fails to resolve on this backend records null
    # rather than killing the round.
    # ------------------------------------------------------------------
    decode_kernel_ab: dict = {
        "paged_tok_per_s": round(paged_serving_toks_per_s, 2),
    }
    for kname, ab_kw in (
        ("stock_paged", dict(decode_kernel="stock-paged")),
        ("gathered", dict(decode_kernel="gathered")),
    ):
        try:
            serve_run(**ab_kw)  # warmup (kernel-specific chunk programs)
            ab_t, ab_n, _ = min(serve_run(**ab_kw) for _ in range(2))
            decode_kernel_ab[f"{kname}_tok_per_s"] = round(ab_n / ab_t, 2)
        except Exception as e:  # pragma: no cover - backend-dependent
            print(f"decode_kernel_ab[{kname}] skipped: {e}",
                  file=sys.stderr)
            decode_kernel_ab[f"{kname}_tok_per_s"] = None

    # ------------------------------------------------------------------
    # Prefill-kernel sweep (ops/kernels.py): flash vs splash-mha TFLOPs
    # at 8k/16k/32k prompts.  The flash_prefill_* figures above run the
    # CACHELESS model forward, which the splash path never sees (splash
    # lands only on the serving cache-insert dispatch), so BOTH arms
    # here time the whole-prompt insert through a 1-slot batcher —
    # identical FLOP accounting, identical path, only the kernel
    # differs.  Head FLOPs are excluded (the insert samples one row);
    # the figures are therefore comparable to each other, not to
    # flash_prefill_*_tflops.
    # ------------------------------------------------------------------
    def insert_prefill_tflops(S: int, prefill_kernel: str):
        icfg = config.replace(vocab_size=512, max_seq_len=S + 128)
        ip = jlt.init_params(jax.random.PRNGKey(1), icfg)
        cb = ContinuousBatcher(
            ip, icfg, n_slots=1, max_len=S + 128, block_size=128,
            decode_chunk=1, prefill_budget=0,
            prefill_kernel=prefill_kernel,
        )

        def one():
            cb.submit(list(rng.randint(1, icfg.vocab_size, S)),
                      max_new_tokens=2)
            t0 = time.time()
            cb.step()  # whole-prompt insert + first decode step
            dt = time.time() - t0
            while cb.pending():
                cb.step()
            return dt

        one()  # compile warmup
        best = min(one() for _ in range(3))
        D, L, F = icfg.dim, icfg.n_layers, icfg.ffn_dim
        kvw = icfg.kv_heads * icfg.head_dim
        flops = (2 * S * L * (2 * D * D + 2 * D * kvw + 3 * D * F)
                 + 2 * S * S * D * L)  # causal attn: QK half + PV half
        return best, flops / max(best, 1e-9) / 1e12

    prefill_kernel_sweep: dict = {}
    for S_pf, tag in ((8192, "8k"), (16384, "16k"), (32768, "32k")):
        for kname in ("flash", "splash"):
            try:
                _, pf_tf = insert_prefill_tflops(S_pf, kname)
                prefill_kernel_sweep[f"{kname}_{tag}_tflops"] = (
                    round(pf_tf, 1)
                )
            except Exception as e:  # pragma: no cover - backend-dependent
                print(f"prefill_kernel_sweep[{kname}_{tag}] skipped: {e}",
                      file=sys.stderr)
                prefill_kernel_sweep[f"{kname}_{tag}_tflops"] = None

    # ------------------------------------------------------------------
    # Fused prefill-decode scheduling: TTFT / ITL under a MIXED workload
    # — 4 decode-heavy residents, then a burst of 3 long prompts lands
    # mid-decode.  Classic admission (prefill_budget=0) stalls every
    # resident for each whole-prompt prefill dispatch and collapses the
    # decode chunk to K=1 right after; the fused scheduler
    # (run.py --prefill-budget, default 512) advances the prompt inside
    # the decode chunks instead.  serving_ttft_ms is submit -> first
    # token of the burst requests; serving_itl_p99_ms is the residents'
    # inter-token gap while the burst is being admitted (the stall
    # shows up as a fat ITL tail).  The budget sweep records both at
    # B ∈ {0 = classic, 128, 512}.
    # ------------------------------------------------------------------
    def mixed_run(prefill_budget):
        cb = ContinuousBatcher(
            params, config, n_slots=8, max_len=1024, block_size=128,
            decode_chunk=16, prefill_budget=prefill_budget,
        )
        _salt[0] += 1
        srng = np.random.RandomState(3000 + _salt[0])
        residents = [
            cb.submit(list(srng.randint(1, config.vocab_size, 100)),
                      max_new_tokens=160)
            for _ in range(4)
        ]
        for _ in range(4):
            cb.step()  # residents admitted (cold, classic) + K ramp
        burst, t_sub, ttft = [], {}, {}
        for _ in range(3):
            rid = cb.submit(
                list(srng.randint(1, config.vocab_size, 850)),
                max_new_tokens=16,
            )
            t_sub[rid] = time.time()
            burst.append(rid)
        last_seen: dict = {r: None for r in residents}
        itl_gaps = []
        while cb.pending():
            evs = cb.step()
            now = time.time()
            burst_inflight = any(r not in ttft for r in burst)
            for rid, _tok, _done in evs:
                if rid in t_sub and rid not in ttft:
                    ttft[rid] = (now - t_sub[rid]) * 1000.0
                if rid in last_seen:
                    if last_seen[rid] is not None and burst_inflight:
                        itl_gaps.append(
                            (now - last_seen[rid]) * 1000.0
                        )
                    last_seen[rid] = now
        return (
            sorted(ttft.values()),
            itl_gaps,
            cb.stats()["decode_stall_ms_total"],
        )

    mixed_run(512)  # warmup (fused-chunk programs at the 512 budget)
    budget_sweep = {}
    serving_ttft = serving_itl_p99 = None
    for budget in (0, 128, 512):
        ttfts, gaps, stall_ms = mixed_run(budget)
        entry = {
            "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 1),
            "ttft_ms_p99": round(float(np.percentile(ttfts, 99)), 1),
            "itl_ms_p99": (
                round(float(np.percentile(gaps, 99)), 1) if gaps else None
            ),
            "decode_stall_ms": round(stall_ms, 1),
        }
        budget_sweep[f"B{budget}"] = entry
        if budget == 512:  # the headline serving config (run.py default)
            serving_ttft = {
                "p50": entry["ttft_ms_p50"], "p99": entry["ttft_ms_p99"]
            }
            serving_itl_p99 = entry["itl_ms_p99"]

    # ------------------------------------------------------------------
    # Multi-turn chat at KV-capacity scale (kvcache.py, r06): the radix
    # prefix index + host-DRAM block tier on the chat pattern the north
    # star cares about — thousands of sessions sharing system prompts
    # and resuming after idling out of HBM.
    #
    # chat_prefix_hit_ttft_ms: TTFT p50/p99 of a turn whose cached
    # prefix covers {0, 25, 75}% of the prompt (hit depth sweep; depth
    # 0 is the cold-prefill baseline and the deeper hits' win is pure
    # skipped prefill).  Prompt: 512 tokens against a warm pool with a
    # decoding resident, admitted through the fused prefill lane — the
    # run.py serving configuration.
    #
    # sessions_resident_max: how many 512-token sessions' KV one pool
    # can keep addressable with vs without the host tier — the
    # capacity multiplier (without: the HBM pool's idle LRU depth;
    # with: HBM + host tier, revisits restoring through the
    # ``restoring`` admission state).
    # ------------------------------------------------------------------
    def chat_bench():
        P = 512                      # chat prompt (4 blocks of 128)
        depths = {"d0": 0, "d25": 128, "d75": 384}  # block multiples
        ttft = {}
        for label, depth in depths.items():
            cb = ContinuousBatcher(
                params, config, n_slots=8, max_len=1024, block_size=128,
                decode_chunk=16, prefill_budget=512, prefix_cache=True,
            )
            _salt[0] += 1
            srng = np.random.RandomState(5000 + _salt[0])
            shared = list(srng.randint(1, config.vocab_size, depth))
            # Seed the shared prefix chain (one completed turn), then
            # hold a decoding resident so probes admit FUSED.
            if depth:
                cb.submit(shared + [7], max_new_tokens=2)
                while cb.pending():
                    cb.step()
            cb.submit(list(srng.randint(1, config.vocab_size, 64)),
                      max_new_tokens=512)
            cb.step(); cb.step(); cb.step()
            samples = []
            for _ in range(8):
                probe = shared + list(
                    srng.randint(1, config.vocab_size, P - depth)
                )
                t0 = time.time()
                rid = cb.submit(probe, max_new_tokens=4)
                first = None
                while first is None:
                    for ev in cb.step():
                        if ev[0] == rid:
                            first = time.time()
                            break
                samples.append((first - t0) * 1000.0)
                while any(
                    s is not None and s.request_id == rid
                    for s in cb.slots.values()
                ):
                    cb.step()
            ttft[label] = {
                "p50": round(float(np.percentile(samples, 50)), 1),
                "p99": round(float(np.percentile(samples, 99)), 1),
            }

        def resident_sessions(host_blocks):
            # 16-block pool (4 sessions' chains max in HBM); sessions
            # are revisited oldest-first, so WITHOUT the tier the LRU
            # has always just dropped the one being asked for.
            cb = ContinuousBatcher(
                params, config, n_slots=2, max_len=1024, block_size=128,
                n_blocks=16, decode_chunk=16, prefix_cache=True,
                host_kv_blocks=host_blocks,
            )
            _salt[0] += 1
            srng = np.random.RandomState(7000 + _salt[0])
            sessions = [
                list(srng.randint(1, config.vocab_size, P))
                for _ in range(8)
            ]
            for s in sessions:
                cb.submit(list(s), max_new_tokens=4)
                while cb.pending():
                    cb.step()
            h0 = cb.stats()["prefix_requests_hit_total"]
            for s in sessions:   # revisit every session, oldest first
                cb.submit(list(s), max_new_tokens=4)
                while cb.pending():
                    cb.step()
            hits = cb.stats()["prefix_requests_hit_total"] - h0
            # Sessions still addressable = revisits that hit (HBM or
            # restored from the tier) instead of cold re-prefilling.
            return hits, cb.stats()["swap_ins_total"]

        no_tier_hits, _ = resident_sessions(0)
        tier_hits, tier_swap_ins = resident_sessions(64)
        return ttft, {
            "hbm_only": int(no_tier_hits),
            "with_host_tier": int(tier_hits),
            "tier_swap_ins": int(tier_swap_ins),
        }

    chat_bench()  # warmup (suffix-insert + fused-walk + restore programs)
    chat_ttft, sessions_resident = chat_bench()

    # ------------------------------------------------------------------
    # Overload: open-loop (Poisson) load sweep through the HTTP server
    # (overload.py, r06) — goodput + per-class TTFT SLO attainment vs
    # offered rate, and the ladder-vs-static A/B at 4x the sustainable
    # rate (the drill the brownout ladder exists to win: interactive
    # attainment held, batch shed cleanly, zero hung clients).
    # ------------------------------------------------------------------
    try:
        overload_sweep = load_harness(params, config)
    except Exception as e:  # the headline numbers must survive
        overload_sweep = {"error": f"{type(e).__name__}: {str(e)[:160]}"}

    # ------------------------------------------------------------------
    # Speculative serving.  The draft is the target NUDGED by ~2%
    # deterministic relative noise (below): acceptance stays high — the
    # regime speculative decoding targets — but strictly < 1, so the
    # Leviathan reject/replacement path is actually exercised (the old
    # self-draft setup reported spec_serving_kernel_acceptance 1.0 on
    # the gathered path and its "kernel acceptance < 1" was a bf16
    # tiling artifact, not a verified rejection).  The HEADLINE runs
    # FUSED rounds (spec_rounds=8: up to 8 draft+verify rounds per
    # jitted dispatch with batcher state device-resident — BENCH_r05
    # measured the per-round loop at 46.3 tok/s wall vs 927.4 device,
    # ~20x host overhead, the worst gap in the repo);
    # spec_serving_rounds_sweep records where that gap goes.  Kernel vs
    # gathered-view fallback at IDENTICAL block size and pool geometry,
    # as before.
    # ------------------------------------------------------------------
    import zlib

    def _perturbed_draft(p):
        """±2% relative Gaussian nudge on every float leaf, keyed by a
        stable per-leaf path hash (crc32, NOT Python's salted hash()):
        a deterministic draft that closely tracks the target without
        equalling it — the same logits family, slightly wrong."""
        base_key = jax.random.PRNGKey(42)

        def nudge(path, x):
            if not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            key = jax.random.fold_in(
                base_key,
                zlib.crc32(jax.tree_util.keystr(path).encode())
                & 0x7FFFFFFF,
            )
            noise = jax.random.normal(key, x.shape, jnp.float32)
            return (
                x.astype(jnp.float32) * (1.0 + 0.02 * noise)
            ).astype(x.dtype)

        return jax.tree_util.tree_map_with_path(nudge, p)

    draft_params = _perturbed_draft(params)

    def spec_run(use_kernel, spec_rounds=8):
        cb = ContinuousBatcher(
            params, config, n_slots=4, max_len=1024, block_size=128,
            draft_params=draft_params, draft_config=config, n_draft=3,
            use_pallas_kernel=use_kernel, spec_rounds=spec_rounds,
        )
        _salt[0] += 1
        srng = np.random.RandomState(2000 + _salt[0])  # salted prompts
        for _ in range(4):
            cb.submit(list(srng.randint(1, config.vocab_size, 500)),
                      max_new_tokens=48)
        t0 = time.time()
        emitted = 0
        while cb.pending():
            emitted += len(cb.step())
        return time.time() - t0, emitted, cb.stats()["draft_acceptance_rate"]

    spec_run(True)  # warmup (insert + fused-round programs, R ramp)
    sk_t, sk_n, spec_kernel_accept = min(spec_run(True) for _ in range(3))
    spec_kernel_toks_per_s = sk_n / sk_t
    spec_run(False)  # warmup
    sg_t, sg_n, spec_gathered_accept = min(spec_run(False) for _ in range(3))
    spec_gathered_toks_per_s = sg_n / sg_t

    # Spec-rounds sweep (wall tok/s at R ∈ {1, 2, 4, 8}, kernel path):
    # R1 reproduces the pre-fusion one-dispatch-per-round loop — the
    # r05 46.3 tok/s baseline — so R8/R1 is the dispatch-amortization
    # win.  R8 is the headline above (min-of-3); smaller Rs min-of-2.
    spec_rounds_sweep = {"R8": round(spec_kernel_toks_per_s, 2)}
    for R in (1, 2, 4):
        t_r, n_r, _ = min(spec_run(True, spec_rounds=R) for _ in range(2))
        spec_rounds_sweep[f"R{R}"] = round(n_r / t_r, 2)

    # Larger serving batch (B=16): decode is weight-bandwidth-bound, so
    # tokens/sec/chip scales with rows — extra evidence beyond the
    # fixed-B=8 headline (kept at 8 for r1/r2 comparability).
    tokens16 = jnp.asarray(
        rng.randint(0, config.vocab_size, (16, P)), jnp.int32
    )
    mask16 = jnp.ones((16, P), dtype=bool)

    def run16(max_new):
        gc = GenerationConfig(
            max_new_tokens=max_new, temperature=0.0, stop_tokens=()
        )
        t0 = time.time()
        out = generate(
            params, tokens16, mask16, salted_key(), config=config,
            gen_config=gc,
        )
        np.asarray(out)
        return time.time() - t0

    run16(N)
    run16(1)
    full16 = min(run16(N) for _ in range(5))
    short16 = min(run16(1) for _ in range(5))
    b16_toks_per_s = 16 * (N - 1) / max(full16 - short16, 1e-9)

    # ------------------------------------------------------------------
    # Decode step breakdown from an xplane trace (device-op time per
    # decode step, bucketed by HLO source attribution).  Optional: if the
    # profiler/proto stack is unavailable the bench still emits its line.
    # ------------------------------------------------------------------
    step_breakdown = None
    device_toks_per_s = None
    int8_device_toks_per_s = None
    b16_device_toks_per_s = None
    lc_device_toks_per_s = None
    lc_int8kv_device_toks_per_s = None
    serve_device = None
    spec_device = None
    hbm_ceiling_tps = None
    hbm_ceiling_gbps = None
    hbm_ceiling_tps_int8 = None
    lc_serving = None
    train_metrics = None
    try:

        # The framework's own measurement primitive (see its docstring
        # for the wall-vs-device rationale).
        from jax_llama_tpu.utils.profiling import device_op_times

        def _trace_device_ps(
            max_new: int, p=None, toks=None, msk=None, cfg=None,
            prefill_chunk=None,
        ):
            """Sum of device-op time (ps) for one traced generate call,
            bucketed by HLO source file.  Defaults to the headline bf16
            B=8 geometry; the int8 / B=16 / long-context companions pass
            their own operands."""
            gcN = GenerationConfig(
                max_new_tokens=max_new, temperature=0.0, stop_tokens=(),
                **(
                    {"prefill_chunk": prefill_chunk}
                    if prefill_chunk else {}
                ),
            )
            p = params if p is None else p
            toks = tokens if toks is None else toks
            msk = mask if msk is None else msk
            cfg = config if cfg is None else cfg

            def go():
                np.asarray(generate(
                    p, toks, msk, salted_key(), config=cfg,
                    gen_config=gcN,
                ))

            go()  # warmup outside the trace
            return device_op_times(go, by="source")

        def _device_decode_rate(rows: int, **kw):
            """Jitter-immune decode tokens/s: device-op time differenced
            between 32- and 1-token traced runs (31 steady-state steps)."""
            aN = _trace_device_ps(32, **kw)
            a1 = _trace_device_ps(1, **kw)
            step_ps = (sum(aN.values()) - sum(a1.values())) / 31
            return rows / (step_ps / 1e12) if step_ps > 0 else None

        agg32 = _trace_device_ps(32)
        step_breakdown = {
            src: round(ps / 1e6 / 32, 1)  # us per decode step (32-amortized)
            for src, ps in agg32.most_common(8)
        }
        try:
            # Device-time decode throughput: differencing two traced runs
            # (32 vs 1 new tokens) cancels the prefill, leaving 31 steps
            # of pure device-op time.  Unlike the wall-clock headline
            # this is immune to host jitter and any device
            # time-sharing — wall-clock runs of IDENTICAL code have
            # measured 2.6-3.05 ms/step across sessions while this
            # figure stayed put to 0.01%.  A second-trace failure only
            # loses this figure, not the breakdown above.
            agg1 = _trace_device_ps(1)
            step_ps = (sum(agg32.values()) - sum(agg1.values())) / 31
            if step_ps > 0:
                device_toks_per_s = B / (step_ps / 1e12)
            # Differenced per-step breakdown: the 32-amortized figures
            # above still carry prefill ops in each bucket; subtracting
            # the 1-step trace cancels them exactly.  Rank and clamp on
            # the DIFFERENCED values (a prefill-dominated bucket can
            # difference to ~0 or jitter negative and must not displace a
            # real decode bucket).
            diffed = {
                src: max(agg32.get(src, 0) - agg1.get(src, 0), 0) / 1e6 / 31
                for src in set(agg32) | set(agg1)
            }
            step_breakdown = {
                src: round(us, 1)
                for src, us in sorted(
                    diffed.items(), key=lambda kv: -kv[1]
                )[:8]
            }
        except Exception:
            pass

        # --------------------------------------------------------------
        # Device-time companions for every wall decode figure (VERDICT
        # r4 item 1: the wall headline rode a min-of-min artifact; these
        # are the jitter-immune numbers the headline now prefers).  Each
        # is independent — a failure loses only its own field.
        # --------------------------------------------------------------
        try:
            # The breakdown section above usually already produced the
            # bf16 figure from its own agg32/agg1 differencing — don't
            # re-trace (4 extra generates) or risk clobbering a valid
            # value with a jittered None.
            if device_toks_per_s is None:
                device_toks_per_s = _device_decode_rate(B)
        except Exception:
            pass
        try:
            int8_device_toks_per_s = _device_decode_rate(B, p=qparams)
        except Exception:
            pass
        try:
            b16_device_toks_per_s = _device_decode_rate(
                16, toks=tokens16, msk=mask16
            )
        except Exception:
            pass
        # Long-context (16k B=1) decode: bf16 vs int8 KV (VERDICT r4
        # item 4 — the KV stream is the marginal byte at this length;
        # r5 probe measured 5.10 -> 4.66 ms/step, +9.4%).
        try:
            lc_device_toks_per_s = _device_decode_rate(
                1, toks=lc_tokens, msk=lc_mask, prefill_chunk=2048
            )
            lc_int8kv_device_toks_per_s = _device_decode_rate(
                1, toks=lc_tokens, msk=lc_mask, prefill_chunk=2048,
                cfg=config.replace(kv_cache_dtype="int8"),
            )
        except Exception:
            pass

        # --------------------------------------------------------------
        # MEASURED HBM ceiling: stream the exact bytes the roofline model
        # counts (every non-embedding weight leaf once + a bf16 buffer
        # sized to the KV read at mean context) through fp32 sum
        # reductions, and take pure device time from the trace.  This
        # turns the decode denominator into an observed number: on this
        # chip pure streaming reads move at ~90% of the 819 GB/s
        # nameplate (leaf granularity; a single contiguous 2 GB sum
        # reaches ~92%), so "decode / measured ceiling" is the honest
        # utilization — the modeled figure understates it by ~10%.
        # --------------------------------------------------------------
        try:
            leaves = [
                leaf
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                    params
                )
                if "embed" not in jax.tree_util.keystr(path)
            ]
            mean_ctx = P + (N + 1) / 2
            kv_entries = int(
                2 * config.n_layers * B * mean_ctx
                * config.kv_heads * config.head_dim
            )
            kv_buf = jax.random.normal(
                jax.random.PRNGKey(2), (kv_entries,), dtype=jnp.bfloat16
            )

            @jax.jit
            def _stream(ls, kv):
                acc = jnp.float32(0)
                for leaf in ls:
                    acc += jnp.sum(leaf.astype(jnp.float32))
                return acc + jnp.sum(kv.astype(jnp.float32))

            def _stream_ceiling(ls):
                nbytes = sum(
                    l.size * l.dtype.itemsize for l in ls
                ) + kv_buf.size * 2
                float(_stream(ls, kv_buf))  # warmup
                agg = device_op_times(
                    lambda: float(_stream(ls, kv_buf)), by="op"
                )
                t = sum(agg.values()) / 1e12
                return B / t, nbytes / t / 1e9

            hbm_ceiling_tps, hbm_ceiling_gbps = _stream_ceiling(leaves)
            qleaves = [
                leaf
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                    qparams
                )
                if "embed" not in jax.tree_util.keystr(path)
            ]
            hbm_ceiling_tps_int8, _ = _stream_ceiling(qleaves)
        except Exception:
            pass
        finally:
            # Drop the probe buffers AND the int8 param copy (the
            # ceiling probe is its last consumer): together ~1.1 GB of
            # HBM the later sections — the 6 GB training state
            # especially — need.  In a finally so a failure above can't
            # leak them into the training section and masquerade as an
            # unrelated training OOM.
            leaves = qleaves = kv_buf = None  # noqa: F841
            qparams = None  # noqa: F841

        # --------------------------------------------------------------
        # LONG-CONTEXT paged serving (VERDICT r3 item 8): the paged
        # kernel is the declared long-context decode path, so measure it
        # there — 2 slots at an 8k and a 16k context, kernel vs gathered
        # view at IDENTICAL pool geometry.  Wall tok/s was host-bound in
        # the recorded rounds (the paths read identical; not measured
        # on a local chip), so the figure that carries the
        # comparison is device-op ms per decode step from an xplane
        # trace of 8 steps.
        # --------------------------------------------------------------
        try:
            lc_cfg = config.replace(max_seq_len=16384)

            def lc_serve_device_ms(
                ctx: int, max_len: int, use_kernel: bool, cfg=None,
            ) -> float:
                # block_size=None: the batcher's default (512 at both
                # capacities — the on-chip-swept DMA-efficiency sweet
                # spot); identical geometry on both paths.
                cb = ContinuousBatcher(
                    params, cfg or lc_cfg, n_slots=2, max_len=max_len,
                    prefill_chunk=2048, use_pallas_kernel=use_kernel,
                )
                _salt[0] += 1
                srng = np.random.RandomState(4000 + _salt[0])
                for _ in range(2):
                    cb.submit(
                        list(srng.randint(1, config.vocab_size, ctx)),
                        max_new_tokens=33,
                    )
                cb.step()   # admission (chunked prefills) + first decode
                cb.step()   # decode-step compile warmup
                agg = device_op_times(
                    lambda: [cb.step() for _ in range(8)], by="source"
                )
                while cb.pending():
                    cb.step()
                return sum(agg.values()) / 8 / 1e9

            lc_serving = {}
            # Contexts are block-multiples of the default size so the
            # padded prompt + 33 new tokens fits the capacity.
            for ctx, max_len, label in (
                (7680, 8192, "8k"), (15872, 16384, "16k")
            ):
                for use_kernel, path in ((True, "kernel"),
                                         (False, "gathered")):
                    ms = lc_serve_device_ms(ctx, max_len, use_kernel)
                    lc_serving[f"{label}_{path}_device_ms_per_step"] = (
                        round(ms, 2)
                    )
                    lc_serving[f"{label}_{path}_device_tokens_per_s"] = (
                        round(2 / ms * 1e3, 1)
                    )
        except Exception:
            lc_serving = None
        try:
            # int8 KV pool at 16k (kernel path; VERDICT r4 item 4): the
            # dequant scales fold in-kernel, so the pool streams at one
            # byte per element.  Documented A/B, not a silent default —
            # int8 is lossy (~4e-3 rel) and the measured win (~9% at
            # 16k B=1 decode) is half VERDICT's 15-25% trigger.  Own
            # try: a failure here must not discard the bf16 rows above.
            if lc_serving is not None:
                ms = lc_serve_device_ms(
                    15872, 16384, True,
                    cfg=lc_cfg.replace(kv_cache_dtype="int8"),
                )
                lc_serving["16k_kernel_int8kv_device_ms_per_step"] = (
                    round(ms, 2)
                )
                lc_serving["16k_kernel_int8kv_device_tokens_per_s"] = (
                    round(2 / ms * 1e3, 1)
                )
        except Exception:
            pass

        # --------------------------------------------------------------
        # Device-time companions for the SHORT-context serving drain and
        # the speculative rounds (VERDICT r4 item 5): the wall figures
        # were host-bound in the recorded rounds (not measured on a
        # local chip), so regressions could hide inside host noise.  Same
        # xplane pattern as long_context_serving.
        # --------------------------------------------------------------
        try:
            # Chunked batcher (the headline's configuration):
            # device_ms_per_step normalizes by the DECODE ITERATIONS the
            # traced window executed (steps_total delta), so the figure
            # stays per-iteration-comparable with the K=1 rounds'
            # per-dispatch number — the acceptance bar is that fusing K
            # iterations into one program does not regress the
            # per-iteration device time.
            cb = ContinuousBatcher(
                params, config, n_slots=8, max_len=1024, block_size=128,
                decode_chunk=16,
            )
            _salt[0] += 1
            srng = np.random.RandomState(6000 + _salt[0])
            for _ in range(8):
                # max_new 96 (896 + 96 <= 1024) so the traced window
                # below holds full K=16 chunks.
                cb.submit(list(srng.randint(1, config.vocab_size, 850)),
                          max_new_tokens=96)
            cb.step(); cb.step()  # admission + chunk compile warmup
            iters0 = cb.steps_total
            agg = device_op_times(
                lambda: [cb.step() for _ in range(4)], by="source"
            )
            iters = cb.steps_total - iters0
            while cb.pending():
                cb.step()
            ms = sum(agg.values()) / max(iters, 1) / 1e9
            serve_device = {
                "device_ms_per_step": round(ms, 2),
                "device_tokens_per_s": round(8 / ms * 1e3, 1),
                "traced_decode_iterations": iters,
            }
        except Exception:
            serve_device = None
        try:
            # Fused batcher (the headline's configuration):
            # device_ms_per_round normalizes by the ROUNDS the traced
            # window executed (steps_total delta — each fused dispatch
            # carries up to R=8), keeping the figure per-round-
            # comparable with the classic loop's per-dispatch number;
            # the acceptance bar is that fusing R rounds into one
            # program does not regress per-round device time.
            cb = ContinuousBatcher(
                params, config, n_slots=4, max_len=1024, block_size=128,
                draft_params=draft_params, draft_config=config,
                n_draft=3, spec_rounds=8,
            )
            _salt[0] += 1
            srng = np.random.RandomState(7000 + _salt[0])
            for _ in range(4):
                # max_new 96 (512 + 96 <= 1024) so the traced window
                # holds full fused chunks.
                cb.submit(list(srng.randint(1, config.vocab_size, 500)),
                          max_new_tokens=96)
            cb.step(); cb.step()  # admission + fused-round compile warmup
            emitted = [0]
            rounds0 = cb.steps_total

            def _rounds():
                emitted[0] = sum(len(cb.step()) for _ in range(4))

            agg = device_op_times(_rounds, by="source")
            rounds = max(cb.steps_total - rounds0, 1)
            while cb.pending():
                cb.step()
            ms = sum(agg.values()) / rounds / 1e9
            spec_device = {
                "device_ms_per_round": round(ms, 2),
                # Tokens actually emitted over the traced rounds — the
                # honest numerator for a speculative round (acceptance
                # decides it, not the slot count).
                "device_tokens_per_s": round(
                    emitted[0] / rounds / ms * 1e3, 1
                ),
                "traced_rounds": rounds,
            }
        except Exception:
            spec_device = None
        finally:
            # Last consumer of the perturbed draft copy: free its ~2 GB
            # (and the batcher still referencing it + its pools) before
            # the training section allocates its 6 GB state.
            cb = None  # noqa: F841
            draft_params = None  # noqa: F841

        # --------------------------------------------------------------
        # Training step throughput (the subsystem the reference lacks
        # entirely): one AdamW step on the bench model, B=4 x S=2048,
        # bf16 params, per-block remat with the default "dots" policy
        # (save matmul outputs; remat=False OOMs this chip at 1B scale,
        # full recompute costs +13%), flash-attention VJP.  Device time
        # from a trace of ONE donated step; MFU counts fwd 2NT + bwd 4NT
        # matmul flops plus 3x the causal attention flops — remat
        # recompute is NOT counted as useful work (standard MFU
        # convention).
        # --------------------------------------------------------------
        try:
            from jax_llama_tpu.train import (
                init_train_state, make_optimizer, train_step,
            )

            # attn_impl must be explicit: the preset default is "xla",
            # whose dense-bias fwd+bwd measured 674.5 ms/step vs the
            # flash VJP's 487.9 here (1.38x) — and flash is the path
            # that scales past this S anyway.
            tcfg = config.replace(
                max_seq_len=2048, remat=True, attn_impl="flash"
            )
            # Reuse the bench params as the training params: values are
            # random either way, and a second 2 GB init pushed this
            # section over the chip's HBM alongside the 6 GB train
            # state.  train_step DONATES the state, so this must stay
            # the LAST section that touches `params` (it is: every
            # other consumer runs above).
            topt = make_optimizer()
            tstate = init_train_state(params, topt)
            TB, TS = 4, 2048
            ttoks = jnp.asarray(
                rng.randint(0, config.vocab_size, (TB, TS)), jnp.int32
            )
            for _ in range(2):  # compile + warm (state donated through)
                tstate, tloss = train_step(
                    tstate, ttoks, config=tcfg, optimizer=topt
                )

            def _one_step():
                nonlocal tstate
                tstate, tl = train_step(
                    tstate, ttoks, config=tcfg, optimizer=topt
                )
                float(tl)

            tagg = device_op_times(_one_step, by="op")
            t_dev = sum(tagg.values()) / 1e12
            n_mat = n_params - embed_entries
            tflops = (
                6 * n_mat * TB * TS
                + 3 * (2 * TB * TS * TS * config.dim * config.n_layers)
            )
            train_metrics = {
                "train_step_device_ms": round(t_dev * 1e3, 1),
                "train_tokens_per_s": round(TB * TS / t_dev, 1),
                # Peak-relative like its siblings: null off-v5e.
                "train_mfu": (
                    round(tflops / t_dev / V5E_BF16_FLOPS, 3)
                    if is_v5e else None
                ),
            }
        except Exception as e:  # keep the bench's one-line contract,
            # but leave a diagnosable trace instead of a silent null
            # (an OOM here once hid behind "training": null).
            train_metrics = {
                "error": f"{type(e).__name__}: {str(e)[:160]}"
            }
    except Exception:
        step_breakdown = None
        device_toks_per_s = None

    # BASELINE.json's 50 tok/s/chip target is stated for Llama-3-70B on
    # v5p; decode is HBM-bandwidth-bound, so scale the per-chip target by
    # the param ratio to get an honest denominator for this bench model
    # rather than pretending a ~1B model beat a 70B target.
    target = 50.0 * (70e9 / n_params)
    # HBM-utilization numerators prefer the device rates too.
    if device_toks_per_s:
        bf16_hbm = hbm_util(2.0, B / device_toks_per_s)
    if int8_device_toks_per_s:
        int8_hbm = hbm_util(1.0, B / int8_device_toks_per_s)
    # The HEADLINE rides the xplane device-time rate when the profiler
    # stack is available (VERDICT r4 item 1): device-busy time is a
    # lower bound on wall time, so a wall rate above the device rate is
    # a measurement artifact by construction (r4's min-of-min
    # differencing did exactly that — see measure()'s docstring); the
    # wall figure stays as the cross-check companion.
    headline = device_toks_per_s or toks_per_s
    result = {
        "metric": "steady-state greedy decode throughput, ~1B Llama-3-arch "
                  f"bf16, batch {B}, prompt {P}, gen {N}, single chip",
        "value": round(headline, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(headline / target, 3),
        "detail": {
            "headline_source": (
                "xplane_device" if device_toks_per_s else "wall"
            ),
            "params": n_params,
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "compile_s": round(compile_s, 1),
            "prefill+decode_s": round(full, 3),
            "prefill_s": round(short, 3),
            "per_token_ms": round(1e3 * decode_s / (N - 1), 2),
            # Wall companions (paired-median differencing — see
            # measure()): cross-checks for the device figures; device is
            # the headline when available.
            "decode_tokens_per_s_wall": round(toks_per_s, 2),
            "int8_tokens_per_s_wall": round(int8_toks_per_s, 2),
            "int8_tokens_per_s_device_xplane": (
                round(int8_device_toks_per_s, 2)
                if int8_device_toks_per_s else None
            ),
            # Roofline evidence (denominators are v5e public peaks; only
            # meaningful when device above is a v5 lite chip).
            "hbm_utilization_bf16": round(bf16_hbm, 3) if is_v5e else None,
            "hbm_utilization_int8": round(int8_hbm, 3) if is_v5e else None,
            "hbm_model": "weights-once-per-step + bf16 KV at mean context",
            # Bandwidth ceiling for this geometry (see roofline_tps):
            # vs_baseline 0.95 ~= 100% of the bf16 ceiling on this chip.
            "decode_roofline_tokens_per_s_bf16": (
                round(roofline_tps(2.0), 1) if is_v5e else None
            ),
            "decode_roofline_tokens_per_s_int8": (
                round(roofline_tps(1.0), 1) if is_v5e else None
            ),
            # MEASURED ceiling (VERDICT r3 item 2): device time to stream
            # the modeled step bytes through sum reductions, from an
            # xplane trace.  The observed streaming rate on this chip is
            # ~90% of nameplate, so this is the real denominator;
            # decode_vs_measured_ceiling uses the jitter-immune xplane
            # decode rate as numerator.
            "hbm_ceiling_measured_tokens_per_s": (
                round(hbm_ceiling_tps, 1) if hbm_ceiling_tps else None
            ),
            "hbm_ceiling_measured_gbps": (
                round(hbm_ceiling_gbps, 1) if hbm_ceiling_gbps else None
            ),
            # NB: the int8 probe's sum-reduce converts one BYTE per
            # element, so at int8 density the VPU convert — not HBM —
            # can bound the probe; treat this as a LOWER bound on the
            # int8 streaming ceiling (the int8 decode legitimately
            # lands a few % above it).
            "hbm_ceiling_measured_tokens_per_s_int8": (
                round(hbm_ceiling_tps_int8, 1)
                if hbm_ceiling_tps_int8 else None
            ),
            "decode_vs_measured_ceiling": (
                round(device_toks_per_s / hbm_ceiling_tps, 3)
                if device_toks_per_s and hbm_ceiling_tps else None
            ),
            # Compiled Pallas flash kernel, long-prompt prefill (B=1).
            # Device-op time when the profiler stack is up; the wall
            # differencing fallback reads ~2% low (prefill_sources says
            # which path produced each of the 8k/16k/32k figures).
            "prefill_sources": prefill_sources,
            "flash_prefill_8k_s": round(flash8k_s, 3),
            "flash_prefill_8k_tflops": round(flash8k_tf, 1),
            "flash_prefill_16k_s": round(flash16k_s, 3),
            "flash_prefill_16k_tflops": round(flash16k_tf, 1),
            "flash_prefill_32k_s": round(flash32k_s, 3),
            "flash_prefill_32k_tflops": round(flash32k_tf, 1),
            # Prefill-kernel sweep (ops/kernels.py): flash vs splash-mha
            # through the SERVING insert path (both arms; the splash
            # kernel only dispatches on cache-insert, so the cacheless
            # flash_prefill_* figures above can't host the A/B).  The
            # dotted keys embed "tflops" so --compare gates direction.
            "prefill_kernel_sweep": prefill_kernel_sweep,
            # BASELINE config 4 (long context): B=1, 16k-token context,
            # chunked flash prefill + append-free decode over the cache.
            # Wall + device companions, and the int8-KV variant (VERDICT
            # r4 item 4): at 16k the KV stream is the marginal byte.
            "decode_tokens_per_s_ctx16k_b1": round(lc_toks_per_s, 2),
            "decode_tokens_per_s_ctx16k_b1_device_xplane": (
                round(lc_device_toks_per_s, 2)
                if lc_device_toks_per_s else None
            ),
            "decode_tokens_per_s_ctx16k_b1_int8kv": (
                round(lc_int8kv_device_toks_per_s, 2)
                if lc_int8kv_device_toks_per_s else None
            ),
            "mxu_peak_tflops": V5E_BF16_FLOPS / 1e12 if is_v5e else None,
            "mxu_utilization_16k": (
                round(flash16k_tf * 1e12 / V5E_BF16_FLOPS, 3)
                if is_v5e else None
            ),
            # Continuous batching through the Pallas paged-attention
            # kernel (8 slots, 850-token prompts, 48 new tokens each),
            # CHUNKED decode (decode_chunk=16).  Wall-clock: each
            # dispatch still pays its host sync, but a dispatch now
            # carries up to 16 decode
            # iterations with state device-resident, so the figure is
            # ~K x the K=1 loop's (see paged_serving_chunk_sweep and
            # paged_serving_host_overhead_ratio for the remaining gap
            # to the device rate).
            "paged_serving_tokens_per_s": round(
                paged_serving_toks_per_s, 2
            ),
            # Wall tok/s at decode_chunk K ∈ {1, 4, 8, 16}: the record
            # of how much of the dispatch-overhead gap each chunk size
            # closes (K1 reproduces the pre-chunking per-token loop).
            "paged_serving_chunk_sweep": chunk_sweep,
            # Device-time companion for the 8-slot drain (VERDICT r4
            # item 5): regressions become attributable to device vs
            # host.
            "paged_serving_device": serve_device,
            # Host-overhead ratio: xplane device tok/s over wall tok/s
            # (>= 1; 1.0 = the host adds nothing, BENCH_r05's
            # K=1 loop measured ~26x).  Null when the profiler stack is
            # unavailable.
            "paged_serving_host_overhead_ratio": (
                round(
                    serve_device["device_tokens_per_s"]
                    / paged_serving_toks_per_s, 2
                ) if serve_device else None
            ),
            # 8 submits -> ONE batched prefill dispatch + first decode.
            "burst_admission_s": round(admit_s, 3),
            # int8 WEIGHT-only serving (the quantize_params path run.py
            # --quantize reaches; the serving benches previously only
            # ever measured int8 KV): same burst drain, quantized
            # weight stream.
            "paged_serving_int8w_tokens_per_s": round(
                paged_serving_int8w_toks_per_s, 2
            ),
            # Decode-kernel A/B (ops/kernels.py): the burst drain per
            # decode attention path — custom paged (headline) vs stock
            # Pallas paged-attention vs the gathered XLA view.  Keys
            # embed "tok_per_s" for --compare direction classification;
            # a kernel unavailable on this backend records null.
            "decode_kernel_ab": decode_kernel_ab,
            # Fused prefill-decode scheduling (run.py --prefill-budget,
            # the headline serving config): time-to-first-token of a
            # 3 x 850-token burst landing against 4 mid-decode
            # residents, and the residents' p99 inter-token latency
            # while the burst admits.  The budget sweep's B0 entry is
            # the classic whole-prompt-admission baseline — its
            # decode_stall_ms is what fused scheduling drives to ~0.
            "serving_ttft_ms": serving_ttft,
            "serving_itl_p99_ms": serving_itl_p99,
            "serving_prefill_budget_sweep": budget_sweep,
            # KV capacity at chat scale (kvcache.py, r06): TTFT p50/p99
            # of a 512-token turn at prefix hit depth {0, 25, 75}%
            # (radix index, fused admission — the deeper the hit, the
            # less prefill the turn pays), and how many sessions stay
            # cache-addressable when revisited round-robin against a
            # 4-session HBM pool, without vs with the host-DRAM tier
            # (revisits swap back in through the restoring state).
            "chat_prefix_hit_ttft_ms": chat_ttft,
            "sessions_resident_max": sessions_resident,
            # Overload control (overload.py, r06): the open-loop
            # Poisson sweep — per-class served/refused/attainment and
            # goodput tokens/s at {0.5, 1, 2, 4}x the sustainable
            # request rate with the brownout ladder on, plus the
            # ladder-vs-static-max_queue A/B at 4x (interactive
            # attainment held vs collapsed; all refusals 503 +
            # Retry-After; hung_total must read 0 on both sides).
            "serving_overload": overload_sweep,
            # Long-context paged serving (2 slots, 8k/16k contexts):
            # device-op ms per decode step, kernel vs gathered view at
            # identical pool geometry (xplane; wall was host-bound in
            # the recorded rounds and read identical on both paths).
            "long_context_serving": lc_serving,
            # One AdamW train step, B=4 x S=2048, bf16 + remat + flash
            # VJP (device time; MFU excludes remat recompute).
            "training": train_metrics,
            # Speculative serving (perturbed-target draft, n_draft=3,
            # FUSED spec_rounds=8 headline): Pallas path (T=1-shaped
            # draft chain + multi-token verify kernel) vs the
            # gathered-view fallback at IDENTICAL pool geometry.  The
            # draft is the target nudged by ±2% deterministic noise, so
            # acceptance is genuinely < 1 and the reject/replacement
            # path is exercised (self-draft used to pin it at 1.0);
            # the acceptance fields attribute any throughput gap
            # between the two paths.
            "spec_serving_kernel_tokens_per_s": round(
                spec_kernel_toks_per_s, 2
            ),
            "spec_serving_kernel_acceptance": round(spec_kernel_accept, 3),
            "spec_serving_gathered_tokens_per_s": round(
                spec_gathered_toks_per_s, 2
            ),
            "spec_serving_gathered_acceptance": round(
                spec_gathered_accept, 3
            ),
            # Wall tok/s at spec_rounds R ∈ {1, 2, 4, 8} (kernel path):
            # R1 reproduces the pre-fusion per-round loop (the r05
            # 46.3 tok/s baseline), so R8/R1 is the fused-dispatch
            # amortization win.
            "spec_serving_rounds_sweep": spec_rounds_sweep,
            # Device-time per speculative round (kernel path, fused
            # batcher, steps_total-normalized) — the jitter-immune
            # denominator for the host-overhead ratios below.
            "spec_serving_device": spec_device,
            # Wall-vs-device host-overhead ratios for the speculative
            # drain (>= 1; 1.0 = the host adds nothing): the
            # headline fused-R8 figure, and the R1 classic-loop
            # companion (r05 measured ~20x there) the fusion is
            # amortizing away.
            "spec_serving_host_overhead_ratio": (
                round(
                    spec_device["device_tokens_per_s"]
                    / spec_kernel_toks_per_s, 2
                ) if spec_device else None
            ),
            "spec_serving_host_overhead_ratio_r1": (
                round(
                    spec_device["device_tokens_per_s"]
                    / spec_rounds_sweep["R1"], 2
                ) if spec_device and spec_rounds_sweep.get("R1")
                else None
            ),
            # Batch-16 steady-state decode (headline stays B=8 for
            # round-over-round comparability; wall + device).
            "decode_tokens_per_s_b16_wall": round(b16_toks_per_s, 2),
            "decode_tokens_per_s_b16_device_xplane": (
                round(b16_device_toks_per_s, 2)
                if b16_device_toks_per_s else None
            ),
            # Device-op-time decode throughput from xplane differencing
            # (32 vs 1 new tokens): the tenancy/jitter-immune companion
            # of the wall-clock headline — if the two disagree, this one
            # is the chip's actual rate.
            "decode_tokens_per_s_device_xplane": (
                round(device_toks_per_s, 2) if device_toks_per_s else None
            ),
            # Device-op µs per decode step bucketed by HLO source file
            # (llama.py = the projection/MLP matmul fusions + cache
            # update ops — the bf16 weight stream used to misattribute
            # to quant.py through the ops.quant.matmul wrapper frame;
            # quant.py now measures actual int8 dequant work only,
            # attention.py = the decode attention chain, rope.py =
            # rotation).  Includes prefill amortized over 32 steps; None
            # when the profiler stack is unavailable.
            "step_breakdown_us": step_breakdown,
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    import sys

    if "--compare" in sys.argv[1:]:
        compare_main()
    elif "--load-harness" in sys.argv[1:]:
        load_harness_main()
    elif "--multichip-serving" in sys.argv[1:]:
        record = None
        if "--record" in sys.argv[1:]:
            record = sys.argv[sys.argv.index("--record") + 1]
        multichip_serving_main(record_path=record)
    else:
        main()
