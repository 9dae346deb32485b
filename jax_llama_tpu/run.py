"""Serving CLI: load an Orbax checkpoint onto a mesh and complete prompts.

Entry-point parity with the reference example (``/root/reference/
jax_example.py:10-43``: build mesh → tokenizer → convert weights →
device_put → complete 2 prompts), redesigned around this framework's
pipeline: weights restore *sharded* straight from Orbax (no double host-RAM
copy — the defect flagged at SURVEY.md §3.1), and the decode loop is the
native jitted engine.

    python -m jax_llama_tpu.run \
        --ckpt-dir /path/to/llama3-8b-orbax \
        --tokenizer /path/to/tokenizer.model \
        [--llama2] [--tensor 4] [--fsdp 1] \
        [--prompt "..." --prompt "..."] \
        [--max-gen-len 256] [--temperature 0.8] [--top-p 0.95]
"""

from __future__ import annotations

import argparse

DEFAULT_PROMPTS = [
    "I believe the meaning of life is",
    "Simply put, the theory of relativity states that",
]


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ckpt-dir", required=True, help="Orbax checkpoint dir")
    ap.add_argument("--tokenizer", default=None)
    ap.add_argument("--llama2", action="store_true",
                    help="sentencepiece (llama2) tokenizer")
    ap.add_argument("--byte-tokenizer", action="store_true",
                    help="vocab-file-free byte tokenizer (smoke tests)")
    ap.add_argument("--tensor", type=int, default=0,
                    help="tensor-parallel degree (0 = all local devices)")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--prompt", action="append", default=None)
    ap.add_argument("--max-gen-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-p", type=float, default=0.95)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn", default=None,
                    choices=["xla", "flash", "auto"],
                    help="override attn_impl from the checkpoint config "
                         "(auto = flash prefill + append-free xla decode; "
                         "recommended for long prompts)")
    ap.add_argument("--quantize", action="store_true",
                    help="int8-quantize weights after load (weight-only, "
                         "per-channel; ~2x decode throughput)")
    ap.add_argument("--serve", action="store_true",
                    help="continuous-batching mode: read prompts (one per "
                         "line) from stdin, stream completions as they "
                         "finish; requests share a slot pool")
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-pool size for --serve / --http")
    ap.add_argument("--serve-mesh", default=None, metavar="DP,TP",
                    help="serving-mesh geometry for --serve/--http: "
                         "'dp,tp' shards each batcher replica's chunk "
                         "programs over a data(dp) x tensor(tp) mesh — "
                         "the KV block pool shards its KV-head axis "
                         "over tp, per-slot state rows over dp "
                         "(parallel/serve_mesh.py; tp must divide the "
                         "model's KV heads, dp must divide --slots).  "
                         "A bare 'tp' means '1,tp'.  Default: the "
                         "--data/--fsdp/--tensor mesh")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="data-parallel serving replicas behind one "
                         "HTTP door (--http only): N independent "
                         "batcher+server replicas — each owning a "
                         "mesh slice when the host has "
                         "N x (dp*tp) devices, sharing the mesh "
                         "otherwise — fronted by a ReplicaRouter "
                         "(router.py) that exposes the same protocol "
                         "on the --http port")
    ap.add_argument("--route", default="least-loaded",
                    choices=("least-loaded", "affinity", "cache-aware"),
                    help="replica routing policy: 'least-loaded' "
                         "(fewest in-flight requests), 'affinity' "
                         "(sticky sessions by prompt prefix, so "
                         "revisited chats land on the replica holding "
                         "their radix prefix chain), or 'cache-aware' "
                         "(GLOBALLY cache-aware: the router folds "
                         "every replica's chain digest into one radix "
                         "index and routes each request to the "
                         "replica holding the deepest matching "
                         "prefix, spilling to least-loaded past an "
                         "occupancy watermark and migrating chains "
                         "to where traffic lands via the handoff "
                         "scheduler)")
    ap.add_argument("--canary-interval-s", type=float, default=10.0,
                    help="router synthetic-canary period for "
                         "--replicas N: every interval the router "
                         "POSTs a tiny deterministic greedy probe "
                         "(reserved 'canary' priority class — "
                         "excluded from SLO/goodput/brownout inputs) "
                         "directly to every replica, token-checks it "
                         "against the fleet oracle, and feeds "
                         "latency/correctness into the per-replica "
                         "health sentinel (GET /debug/fleet).  "
                         "<= 0 disables the prober")
    ap.add_argument("--autoscale", action="store_true",
                    help="elastic fleet for --replicas N: start the "
                         "FleetController — scale-up under sustained "
                         "interactive-attainment / queue-wait "
                         "pressure, sentinel-gated scale-down with "
                         "live session migration (no dropped "
                         "sessions), every action a recorded "
                         "decision (GET /debug/decisions?kind=scale)."
                         "  New replicas reuse the seed replicas' "
                         "geometry (fresh device slices while the "
                         "host has them, time-sharing replica 0's "
                         "mesh after)")
    ap.add_argument("--autoscale-min", type=int, default=1,
                    help="floor on fleet size under --autoscale")
    ap.add_argument("--autoscale-max", type=int, default=8,
                    help="ceiling on fleet size under --autoscale")
    ap.add_argument("--autoscale-interval-s", type=float, default=5.0,
                    help="control-loop period under --autoscale "
                         "(<= 0: no background loop — operator "
                         "drives ticks)")
    ap.add_argument("--replica-roles", default=None, metavar="R,R,...",
                    help="prefill/decode disaggregation for "
                         "--replicas N: a comma list of one role per "
                         "replica ('prefill' | 'decode').  Cold "
                         "prompts route to the least-loaded prefill "
                         "replica; a request finishing there streams "
                         "its prefix KV to a decode replica "
                         "(export->import handoff) and the session "
                         "re-pins there, so revisits decode warm.  "
                         "Requires --route cache-aware (the "
                         "scheduler routes off the global radix "
                         "index); needs at least one replica of "
                         "each role")
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="fuse up to this many decode iterations per "
                         "jitted dispatch in --serve / --http "
                         "(token-identical to 1; stop detection and "
                         "batcher state live on device, the host syncs "
                         "once per chunk instead of once per token; "
                         "effective K adapts down to 1 around "
                         "admissions; 1 is one dispatch a token.  "
                         "Speculative serving chunks by ROUNDS "
                         "through --spec-rounds instead)")
    ap.add_argument("--prefill-budget", type=int, default=512,
                    help="fused prefill-decode scheduling for --serve / "
                         "--http: admissions that would stall decoding "
                         "rows advance up to this many prompt tokens "
                         "per decode-chunk dispatch instead of running "
                         "a separate whole-prompt prefill (stall-free "
                         "chunked prefill; token-identical, first token "
                         "emitted by the dispatch that finishes the "
                         "prompt).  The default amortizes a 16k prompt "
                         "over ~32 steady decode chunks; 0 restores "
                         "classic whole-prompt admission.  Ignored "
                         "under --draft-ckpt-dir (speculative serving "
                         "keeps classic admission)")
    ap.add_argument("--draft-ckpt-dir", default=None,
                    help="Orbax checkpoint dir of a DRAFT model for "
                         "speculative serving in --serve / --http "
                         "(must share the target's vocabulary; the "
                         "draft only changes speed, never content)")
    ap.add_argument("--n-draft", type=int, default=4,
                    help="draft tokens proposed per speculative round "
                         "(with --draft-ckpt-dir)")
    ap.add_argument("--spec-rounds", type=int, default=8,
                    help="fuse up to this many speculative draft+verify "
                         "rounds per jitted dispatch (the speculative "
                         "twin of --decode-chunk; token-identical to 1 "
                         "including the acceptance pattern; the "
                         "effective R adapts down to 1 around "
                         "admissions; 1 is one dispatch a round)")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve over HTTP on this port (POST /generate "
                         "with blocking or NDJSON-streaming responses, "
                         "POST /chat for llama-3 tokenizers, "
                         "GET /metrics, /healthz) instead of the stdin "
                         "loop; 0 picks a free port")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable prompt prefix caching in the serving "
                         "pool (on by default; hits are token-identical "
                         "in tested configurations — this is a "
                         "memory/debug knob).  The cache shares "
                         "partial prompt prefixes across ALL cached "
                         "chains through a block-granular radix tree "
                         "(leaves-first eviction, host-tier residency)")
    ap.add_argument("--host-kv-blocks", type=int, default=0,
                    help="host-DRAM KV block tier capacity for --serve "
                         "/ --http (not with --no-prefix-cache): "
                         "cold prefix-cache blocks evict into pinned "
                         "host memory instead of being freed, and "
                         "sessions whose cached prefix was demoted "
                         "swap it back into HBM asynchronously, "
                         "overlapped on the decode chunk (a restoring "
                         "request waits; decode rows never stall).  "
                         "0 (default) disables the tier; size it to "
                         "taste — a block's bytes follow from the "
                         "model's cache planes "
                         "(kvcache.pool_block_bytes; the server's "
                         "start-up line and describe() give it as "
                         "block_bytes)")
    ap.add_argument("--logprobs", action="store_true",
                    help="compute per-token model logprobs so HTTP "
                         "requests may ask for them (\"logprobs\": true)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for --http")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="deterministic fault injection for chaos runs "
                         "(--http only): comma-separated "
                         "site[@N|~P]:kind[=v] rules — sites step, "
                         "insert, suffix_insert, prefill_chunk, alloc, "
                         "kv_swap, "
                         "flash_kernel, paged_kernel, spec_decode; "
                         "kinds error, "
                         "oom, delay=SECONDS, nan; e.g. 'step@5:error' "
                         "or 'paged_kernel~0.01:error'.  Also read from "
                         "the JLT_FAULTS env var")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for probabilistic (site~P) fault rules")
    ap.add_argument("--max-recoveries", type=int, default=3,
                    help="crash recoveries (batcher rebuild + request "
                         "replay) allowed per --recovery-window-s "
                         "before the server hard-drains with 503s")
    ap.add_argument("--recovery-window-s", type=float, default=60.0)
    ap.add_argument("--watchdog-s", type=float, default=60.0,
                    help="flip /healthz degraded when the serving loop "
                         "heartbeat stalls past this many seconds "
                         "(0 disables the watchdog thread)")
    ap.add_argument("--quarantine-threshold", type=int, default=3,
                    help="failures attributable to one feature (flash/"
                         "paged kernel, speculative decode, prefix "
                         "cache) inside --quarantine-window-s before it "
                         "is quarantined onto its XLA/plain fallback "
                         "(the server stays up, degraded)")
    ap.add_argument("--quarantine-window-s", type=float, default=60.0)
    ap.add_argument("--quarantine-cooldown-s", type=float, default=30.0,
                    help="how long a quarantined feature stays on its "
                         "fallback before one probe re-trial")
    ap.add_argument("--drain-timeout-s", type=float, default=30.0,
                    help="SIGTERM/SIGINT drain budget: in-flight "
                         "requests run to completion (new POSTs get "
                         "503 + Retry-After); stragglers past this "
                         "many seconds are failed with 503")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="time-to-first-token SLO deadline in ms for "
                         "--http: finished requests are scored against "
                         "it and /metrics exposes attainment gauges "
                         "(llm_slo_ttft_attainment, window 256) plus "
                         "llm_goodput_tokens_total — tokens from "
                         "requests that met EVERY configured deadline.  "
                         "0 (default) leaves the dimension unset "
                         "(always passes)")
    ap.add_argument("--slo-itl-ms", type=float, default=0.0,
                    help="inter-token-latency SLO deadline in ms for "
                         "--http: a request passes when its WORST "
                         "token gap stays under it.  0 (default) "
                         "leaves the dimension unset")
    ap.add_argument("--priority-classes", default="on",
                    choices=["on", "off"],
                    help="overload control for --http (overload.py): "
                         "'on' (default) enables the optional "
                         "per-request \"priority\" field (interactive "
                         "| batch) with strict interactive-first "
                         "admission, cost-based deadline refusals "
                         "(503 + load-derived Retry-After when a "
                         "request's timeout_s provably cannot be "
                         "met), and the SLO-driven brownout ladder; "
                         "'off' keeps plain FIFO admission with only "
                         "the --max-queue depth backstop")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="pre-admission queue depth backstop for "
                         "--http: past it new POSTs are refused 503 + "
                         "Retry-After (each blocked POST holds an OS "
                         "thread, so this bounds handler-thread "
                         "memory under flood)")
    ap.add_argument("--brownout-attainment", type=float, default=0.85,
                    help="brownout ladder escalation bar: escalate "
                         "one rung when windowed interactive-class "
                         "SLO attainment drops below this (needs "
                         "--slo-ttft-ms / --slo-itl-ms to be scored)")
    ap.add_argument("--brownout-recover-attainment", type=float,
                    default=0.95,
                    help="brownout ladder recovery bar: step DOWN one "
                         "rung only once attainment is back at/above "
                         "this (must be >= --brownout-attainment — "
                         "the gap is the hysteresis band)")
    ap.add_argument("--brownout-queue-wait-ms", type=float, default=0.0,
                    help="queue-wait pressure bar for the ladder "
                         "(recent pre-admission wait p90 above it = "
                         "pressure); 0 derives 2x --slo-ttft-ms, or "
                         "2000 ms when no TTFT SLO is set")
    ap.add_argument("--brownout-dwell-s", type=float, default=2.0,
                    help="pressure must persist this long before each "
                         "one-rung escalation")
    ap.add_argument("--brownout-cooldown-s", type=float, default=10.0,
                    help="calm must persist this long before each "
                         "one-rung recovery step")
    ap.add_argument("--brownout-batch-max-new", type=int, default=64,
                    help="batch-class max_new_tokens cap applied at "
                         "brownout-1 (halves again at deeper rungs)")
    ap.add_argument("--brownout-demote-blocks", type=int, default=32,
                    help="idle KV blocks proactively demoted to the "
                         "host tier on entering brownout-1 and deeper "
                         "(no-op without --host-kv-blocks)")
    ap.add_argument("--log-json", action="store_true",
                    help="structured JSON logging: one JSON object per "
                         "operational log line (event / request_id / "
                         "feature fields) instead of 'event k=v' text, "
                         "so a log pipeline joins server lines to the "
                         "/debug request timelines without regexes")
    return ap


def main() -> None:
    args = _parser().parse_args()
    # One formatter for every operational log line this process emits
    # (obs.StructuredLogger; --log-json flips it to JSON objects).
    # Generation OUTPUT (the completions themselves) stays on plain
    # stdout prints — it is the program's product, not its log.
    from .obs import StructuredLogger

    log = StructuredLogger(json_mode=args.log_json)
    if args.host_kv_blocks > 0 and args.no_prefix_cache:
        # The tier hangs off radix-node residency; refusing loudly here
        # beats a silently inert flag (the batcher ctor tolerates the
        # combination only because the degradation layer's prefix-cache
        # quarantine must be able to rebuild with the cache off).
        raise SystemExit(
            "--host-kv-blocks requires the prefix cache enabled (the "
            "host tier hangs off radix-node residency)"
        )
    if args.logprobs and args.http is None:
        raise SystemExit(
            "--logprobs only applies to the HTTP server (--http PORT); "
            "the stdin/--serve and one-shot modes have no logprobs output"
        )
    import os

    # The env var is checked here too: a JLT_FAULTS chaos drill that the
    # chosen mode cannot honor must refuse loudly, not run fault-free
    # while the operator believes injection was armed.
    fault_spec = args.inject_faults or os.environ.get("JLT_FAULTS")
    if fault_spec:
        if args.http is None:
            raise SystemExit(
                "--inject-faults / JLT_FAULTS only apply to the HTTP "
                "server (--http PORT) — the stdin/--serve and one-shot "
                "modes have no crash recovery, so a fault drill there "
                "would just crash the run"
            )
        # Validate the spec BEFORE the (potentially minutes-long) weight
        # load; faults.py imports no jax, so this is free.
        from .faults import FaultSpec

        try:
            FaultSpec.parse(fault_spec)
        except ValueError as e:
            raise SystemExit(f"bad fault spec: {e}")

    import jax

    from .convert.checkpoint import load_checkpoint
    from .generation import LLaMA
    from .parallel.mesh import make_mesh
    from .utils.compile_cache import enable_compile_cache
    from .utils.profiling import DecodeStats, Timer

    if args.replicas > 1 and args.serve_mesh is not None:
        # Replicas on their own device slices compile cold.  On jaxlib
        # 0.9.0 / libtpu 0.0.34 an executable compiled for a slice that
        # does not start at device 0 and then READ BACK from the
        # persistent cache halts the core (PR 21, four-chip host: a
        # two-device matmul on devices [2, 3] — cold ok, warm "Core
        # halted unexpectedly ... enhanced-barrier"; devices [0, 1] are
        # fine either way).  Off here even when the environment placed
        # a cache: a slower start beats a dead replica.
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = None
        log.log(
            "compile_cache_off",
            "replicas own device slices; executables for a slice past "
            "device 0 do not survive the persistent cache on this jaxlib",
        )
    else:
        cache_dir = enable_compile_cache()
    n = len(jax.devices())
    # The device this process actually holds, stated once at start-up:
    # every non-TPU backend interprets the Pallas kernels
    # (ops/flash_attention._resolve_interpret), so an operator (and
    # chip_smoke.py) must be able to tell which one is serving.
    dev0 = jax.devices()[0]
    log.log(
        "devices", platform=dev0.platform, device_kind=dev0.device_kind,
        count=n, compile_cache=cache_dir,
    )
    tensor = args.tensor or n // (args.data * args.fsdp)
    # Use exactly the devices the mesh needs — a smaller-than-host mesh
    # (e.g. --tensor 2 on an 8-device host) is valid for smoke runs.
    mesh = make_mesh(
        data=args.data, fsdp=args.fsdp, tensor=tensor,
        devices=jax.devices()[: args.data * args.fsdp * tensor],
    )
    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    if args.replicas > 1 and args.http is None:
        raise SystemExit(
            "--replicas > 1 needs the HTTP front-end (--http PORT): "
            "the ReplicaRouter speaks HTTP to its replicas"
        )
    if args.autoscale:
        if args.replicas < 2 or args.http is None:
            raise SystemExit(
                "--autoscale needs router mode (--replicas >= 2 with "
                "--http PORT): the FleetController scales the "
                "ReplicaRouter's fleet"
            )
        if args.replica_roles is not None:
            raise SystemExit(
                "--autoscale does not compose with --replica-roles: "
                "role disaggregation pins fleet membership (at least "
                "one replica of each role)"
            )
        if not (1 <= args.autoscale_min <= args.replicas
                <= args.autoscale_max):
            raise SystemExit(
                "--autoscale needs 1 <= --autoscale-min <= --replicas "
                "<= --autoscale-max"
            )
    if args.replica_roles is not None:
        roles = tuple(
            r.strip() for r in args.replica_roles.split(",") if r.strip()
        )
        if args.replicas < 2:
            raise SystemExit(
                "--replica-roles needs --replicas >= 2 (one prefill "
                "and one decode replica at minimum)"
            )
        if len(roles) != args.replicas:
            raise SystemExit(
                f"--replica-roles names {len(roles)} roles for "
                f"--replicas {args.replicas}; give one role per replica"
            )
        bad = sorted(set(roles) - {"prefill", "decode"})
        if bad:
            raise SystemExit(
                f"--replica-roles: unknown role(s) {bad}; valid roles "
                "are 'prefill' and 'decode'"
            )
        if not ("prefill" in roles and "decode" in roles):
            raise SystemExit(
                "--replica-roles needs at least one replica of EACH "
                "role (prefill and decode)"
            )
        if args.route != "cache-aware":
            raise SystemExit(
                "--replica-roles requires --route cache-aware (the "
                "disaggregation scheduler routes off the router's "
                "global radix index)"
            )
        args.replica_roles = roles
    serve_spec = None
    if args.serve_mesh is not None:
        if args.http is None and not args.serve:
            raise SystemExit(
                "--serve-mesh applies to the serving modes "
                "(--serve / --http PORT)"
            )
        from .parallel.serve_mesh import build_serve_mesh, parse_serve_mesh

        try:
            serve_spec = parse_serve_mesh(args.serve_mesh)
        except ValueError as e:
            raise SystemExit(str(e))
        if serve_spec.n_devices > n:
            raise SystemExit(
                f"--serve-mesh {args.serve_mesh} needs "
                f"{serve_spec.n_devices} devices, host has {n}"
            )
        # Replica 0's mesh; _serve_router slices further replicas their
        # own devices when the host has enough.
        mesh = build_serve_mesh(
            serve_spec, devices=jax.devices()[: serve_spec.n_devices]
        )

    if args.byte_tokenizer:
        from .tokenizers import ByteTokenizer

        tokenizer = ByteTokenizer()
    elif args.tokenizer is None:
        raise SystemExit("--tokenizer is required (or pass --byte-tokenizer)")
    elif args.llama2:
        from .tokenizers import LLaMA2Tokenizer

        tokenizer = LLaMA2Tokenizer(args.tokenizer)
    else:
        from .tokenizers import LLaMA3Tokenizer

        tokenizer = LLaMA3Tokenizer(args.tokenizer)

    with Timer() as load_t:
        params, config = load_checkpoint(
            args.ckpt_dir, mesh=mesh, fsdp=args.fsdp > 1
        )
    if args.attn:
        config = config.replace(attn_impl=args.attn)
    if serve_spec is not None:
        # A clear refusal at startup beats a silently unplaced mesh.
        from .parallel.serve_mesh import validate_serve_mesh

        validate_serve_mesh(config, mesh, args.slots)
    if args.quantize:
        from .ops.quant import is_quantized, quantize_params

        if not is_quantized(params):
            params = quantize_params(params, donate=True)
    log.log(
        "checkpoint_restored", ckpt_dir=args.ckpt_dir,
        mesh=str(dict(mesh.shape)), seconds=round(load_t.elapsed_s, 1),
    )

    if args.http is not None:
        _serve_http(params, config, tokenizer, mesh, args, logger=log)
        return
    if args.serve:
        _serve(params, config, tokenizer, mesh, args)
        return

    model = LLaMA(params=params, config=config, tokenizer=tokenizer, mesh=mesh)
    prompts = args.prompt or DEFAULT_PROMPTS

    with Timer() as gen_t:
        outs = model.generate_from_str(
            prompts, args.max_gen_len, args.temperature, args.top_p, args.seed
        )
    stats = DecodeStats(
        batch=len(prompts),
        prompt_len=max(len(tokenizer.encode(p, bos=True, eos=False))
                       for p in prompts),
        new_tokens=args.max_gen_len,
        prefill_s=0.0,
        decode_s=gen_t.elapsed_s,
        n_devices=n,
    )
    for p, o in zip(prompts, outs):
        print(f"\n=== {p!r}\n{o}")
    print(f"\n[{stats.summary()}] (incl. compile)")


def _param_bytes_by_device(params) -> dict:
    """{device id: weight bytes whose shards live there}."""
    import jax

    held: dict = {}
    for leaf in jax.tree_util.tree_leaves(params):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return held


def _log_device_memory(logger, when: str, params=None) -> None:
    """One ``device_memory`` log line: per local device, the bytes the
    backend reports in use / at peak / as its limit, and (given the
    param tree) the weight bytes placed there.  Only the process that
    holds the devices can read these, so the server states them itself
    (once serving, once drained); chip_smoke.py reads placement and the
    peak from here.  Backends that report no memory stats (the CPU) log
    ``null`` for them."""
    import jax

    held = None if params is None else _param_bytes_by_device(params)
    stats = []
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        stats.append({
            "id": d.id,
            "bytes_in_use": ms.get("bytes_in_use"),
            "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
            "bytes_limit": ms.get("bytes_limit"),
            "param_bytes": None if held is None else held.get(d.id, 0),
        })
    logger.log("device_memory", when=when, devices=stats)


def _chat_format_for(tokenizer):
    """The ONE 'is this a llama-3 chat tokenizer' heuristic: both the
    single-server /chat endpoint and the router's cache-aware /chat
    chain-key encoding must resolve the SAME ChatFormat, or the
    router's routing keys drift from what the replicas admit."""
    if hasattr(tokenizer, "special_tokens") and hasattr(
        tokenizer, "eot_id"
    ):
        from .tokenizers.llama3 import ChatFormat

        return ChatFormat(tokenizer)
    return None


def _load_draft(args, mesh):
    """Optional speculative-serving draft model (--draft-ckpt-dir):
    returns (draft_params, draft_config) or (None, None).  Loaded the
    same sharded way as the target; attn_impl follows the --attn
    override so both models resolve the same attention paths."""
    ckpt = getattr(args, "draft_ckpt_dir", None)
    if not ckpt:
        return None, None
    from .convert.checkpoint import load_checkpoint

    draft_params, draft_config = load_checkpoint(
        ckpt, mesh=mesh, fsdp=args.fsdp > 1
    )
    if args.attn:
        draft_config = draft_config.replace(attn_impl=args.attn)
    return draft_params, draft_config


def _stop_tokens(tokenizer) -> tuple:
    return tuple(
        int(s) for s in getattr(tokenizer, "stop_tokens", [tokenizer.eos_id])
    )


def _make_batcher(params, config, tokenizer, mesh, args, *, seed,
                  draft_params, draft_config, fault_injector=None,
                  obs=None):
    """The one place the CLI builds a ``ContinuousBatcher``.  An option a
    bare namespace leaves out (tests, ``benchmark/system.py``) takes the
    parser's own default, so what is served without a flag is written
    once."""
    from .serving import ContinuousBatcher

    parser = _parser()

    def opt(name):
        return getattr(args, name, parser.get_default(name))

    return ContinuousBatcher(
        params, config, n_slots=args.slots,
        max_len=config.max_seq_len, stop_tokens=_stop_tokens(tokenizer),
        temperature=args.temperature, top_p=args.top_p,
        seed=seed, mesh=mesh,
        logprobs=opt("logprobs"),
        prefix_cache=not opt("no_prefix_cache"),
        fault_injector=fault_injector,
        decode_chunk=opt("decode_chunk"),
        draft_params=draft_params, draft_config=draft_config,
        n_draft=opt("n_draft"),
        spec_rounds=opt("spec_rounds"),
        prefill_budget=opt("prefill_budget"),
        host_kv_blocks=opt("host_kv_blocks"),
        obs=obs,
    )


def _serve_http(params, config, tokenizer, mesh, args, _test_hook=None,
                logger=None):
    """HTTP front-end: LLMServer over the batcher until interrupted.

    ``_test_hook(srv)``, when given, runs once the server is up and then
    the function returns instead of blocking (tests drive requests
    against the live server without a second process).
    """
    import os
    import time

    from .obs import Observability, StructuredLogger
    from .server import LLMServer

    if logger is None:
        logger = StructuredLogger(
            json_mode=getattr(args, "log_json", False)
        )
    if getattr(args, "replicas", 1) > 1:
        _serve_router(
            params, config, tokenizer, mesh, args,
            _test_hook=_test_hook, logger=logger,
        )
        return

    # Fault injection (chaos runs / tests): --inject-faults wins over the
    # JLT_FAULTS env var; absent both, no injector is constructed.
    fault_spec = (
        getattr(args, "inject_faults", None) or os.environ.get("JLT_FAULTS")
    )
    injector = None
    if fault_spec:
        from .faults import FaultInjector, install_trace_hook

        injector = FaultInjector(
            fault_spec, seed=getattr(args, "fault_seed", 0)
        )
        # Arm the kernel/spec modules' trace-time hooks too (one
        # registry covers flash_kernel / paged_kernel / spec_decode),
        # so a drill can also exercise the first-compile (Mosaic-style)
        # failure mode — the batcher fires the same sites per dispatch.
        install_trace_hook(injector.fire)
        logger.log("faults_armed", spec=fault_spec)
    draft_params, draft_config = _load_draft(args, mesh)
    if getattr(args, "serve_mesh", None) and draft_config is not None:
        # main() validated the TARGET before the draft existed; an
        # explicit --serve-mesh whose tensor axis cannot divide the
        # draft's KV heads must refuse, not silently unplace.
        from .parallel.serve_mesh import validate_serve_mesh

        validate_serve_mesh(
            config, mesh, args.slots, draft_config=draft_config
        )
    # The observability sink (request timelines, dispatch spans, latency
    # histograms, SLO scoring) is constructed HERE so the CLI's SLO
    # deadlines reach it; the batcher adopts it into its captured ctor
    # kwargs, so crash-recovery/quarantine rebuilds keep one continuous
    # trace.  0/unset deadlines leave that SLO dimension always-passing.
    obs = Observability(
        slo_ttft_ms=getattr(args, "slo_ttft_ms", 0.0) or None,
        slo_itl_ms=getattr(args, "slo_itl_ms", 0.0) or None,
    )
    cb = _make_batcher(
        params, config, tokenizer, mesh, args, seed=args.seed,
        draft_params=draft_params, draft_config=draft_config,
        fault_injector=injector, obs=obs,
    )
    # Llama-3 tokenizers get the dialog endpoint for free (ChatFormat is
    # the reference's own framing; other tokenizers have no chat contract).
    chat_format = _chat_format_for(tokenizer)
    watchdog_s = getattr(args, "watchdog_s", 60.0)
    drain_timeout_s = getattr(args, "drain_timeout_s", 30.0)
    try:
        with LLMServer(
            cb, tokenizer=tokenizer, host=args.host, port=args.http,
            chat_format=chat_format,
            max_recoveries=getattr(args, "max_recoveries", 3),
            recovery_window_s=getattr(args, "recovery_window_s", 60.0),
            watchdog_deadline_s=watchdog_s if watchdog_s > 0 else None,
            quarantine_threshold=getattr(args, "quarantine_threshold", 3),
            quarantine_window_s=getattr(args, "quarantine_window_s", 60.0),
            quarantine_cooldown_s=getattr(
                args, "quarantine_cooldown_s", 30.0
            ),
            drain_timeout_s=drain_timeout_s,
            logger=logger,
            max_queue=getattr(args, "max_queue", 256),
            priority_classes=(
                getattr(args, "priority_classes", "on") == "on"
            ),
            brownout_enter_attainment=getattr(
                args, "brownout_attainment", 0.85
            ),
            brownout_exit_attainment=getattr(
                args, "brownout_recover_attainment", 0.95
            ),
            brownout_queue_wait_ms=(
                getattr(args, "brownout_queue_wait_ms", 0.0) or None
            ),
            brownout_dwell_s=getattr(args, "brownout_dwell_s", 2.0),
            brownout_cooldown_s=getattr(
                args, "brownout_cooldown_s", 10.0
            ),
            brownout_batch_max_new=getattr(
                args, "brownout_batch_max_new", 64
            ),
            brownout_demote_blocks=getattr(
                args, "brownout_demote_blocks", 32
            ),
        ) as srv:
            endpoints = "POST /generate" + (
                ", /chat" if chat_format is not None else ""
            )
            logger.log(
                "serving", address=srv.address,
                endpoints=(
                    f"{endpoints}, GET /metrics, /healthz, /debug/*"
                ),
            )
            if _test_hook is not None:
                _test_hook(srv)
                return
            _log_device_memory(logger, "serving", params)
            # Drain-on-signal: SIGTERM (orchestrator shutdown) and the
            # first Ctrl-C flip the server into drain mode — in-flight
            # requests finish, new POSTs 503 with Retry-After, bounded
            # by --drain-timeout-s.  The handler only flips a plain
            # flag (a dict-slot store is async-signal-safe; calling
            # Event.set()/begin_drain() from the handler could deadlock
            # on the Event's non-reentrant lock if the signal lands
            # inside the main thread's own wait) and restores the
            # default SIGINT disposition so a SECOND Ctrl-C hard-stops;
            # the polling loop below does the actual drain.
            import signal

            state = {"signaled": False}

            def _on_signal(signum, frame):
                state["signaled"] = True
                signal.signal(signal.SIGINT, signal.default_int_handler)

            previous = []
            try:
                for sig in (signal.SIGTERM, signal.SIGINT):
                    previous.append((sig, signal.signal(sig, _on_signal)))
            except ValueError:
                previous = []  # not the main thread; no signal wiring
            try:
                while not state["signaled"]:
                    time.sleep(0.2)
                srv.begin_drain()
                logger.log(
                    "drain_begin",
                    "in-flight requests finish, new requests 503",
                    timeout_s=drain_timeout_s,
                )
                drained = srv.wait_drained(drain_timeout_s + 10)
                _log_device_memory(logger, "drained")
                logger.log(
                    "drained" if drained else "drain_timeout",
                    "shutting down",
                )
            except KeyboardInterrupt:
                srv.begin_drain(timeout_s=0.0)
                logger.log("hard_shutdown", "second interrupt")
            finally:
                for sig, old in previous:
                    try:
                        signal.signal(sig, old)
                    except (ValueError, TypeError):
                        pass
    finally:
        if injector is not None:
            # The trace-time hook is a module global: clear it so an
            # embedding process (or the test suite) does not keep firing
            # a dead drill's injector on later traces.
            install_trace_hook(None)


def _serve_router(params, config, tokenizer, mesh, args,
                  _test_hook=None, logger=None) -> None:
    """``--replicas N`` mode: N independent batcher+server replicas —
    each owning its own device slice when the host has
    ``N x mesh_devices`` devices, sharing replica 0's mesh otherwise —
    behind one :class:`~jax_llama_tpu.router.ReplicaRouter` speaking
    the standard protocol on the ``--http`` port.

    ``_test_hook(router, servers)``, when given, runs once everything
    is up and then the function returns instead of blocking."""
    import os
    import signal
    import time

    import jax

    from .obs import Observability, StructuredLogger
    from .parallel.partition import shard_params
    from .parallel.serve_mesh import build_serve_mesh, parse_serve_mesh
    from .router import ReplicaRouter
    from .server import LLMServer

    if logger is None:
        logger = StructuredLogger(
            json_mode=getattr(args, "log_json", False)
        )
    fault_spec = (
        getattr(args, "inject_faults", None) or os.environ.get("JLT_FAULTS")
    )
    injector = None
    if fault_spec:
        from .faults import FaultInjector, install_trace_hook

        # ONE injector serves the router site and every replica's
        # batcher sites, so site@N counters index process dispatches.
        injector = FaultInjector(
            fault_spec, seed=getattr(args, "fault_seed", 0)
        )
        install_trace_hook(injector.fire)
        logger.log("faults_armed", spec=fault_spec)
    draft_params, draft_config = _load_draft(args, mesh)

    # Per-replica meshes: slice fresh devices per replica when the host
    # has enough, otherwise every replica shares replica 0's mesh (the
    # CPU dev-box case — still N independent pools/queues, just
    # time-sharing the devices).
    spec = (
        parse_serve_mesh(args.serve_mesh)
        if getattr(args, "serve_mesh", None) else None
    )
    if spec is not None:
        # Startup-time refusal with the DRAFT model in hand too — the
        # main() check ran before the draft was loaded, and a draft
        # whose KV heads the tensor axis cannot divide would otherwise
        # silently fall back to unplaced.
        from .parallel.serve_mesh import validate_serve_mesh

        validate_serve_mesh(
            config, mesh, args.slots, draft_config=draft_config
        )
    devs = jax.devices()
    per = spec.n_devices if spec is not None else 0
    _geom_cache = {}

    def _geometry(i):
        """Replica ``i``'s (mesh, params, draft_params): a fresh
        device slice while the host still has one for index i,
        replica 0's mesh (time-shared) after — the same rule for seed
        replicas and autoscale-grown ones."""
        if i in _geom_cache:
            return _geom_cache[i]
        if spec is not None and len(devs) >= (i + 1) * per:
            m = build_serve_mesh(spec, devices=devs[i * per:(i + 1) * per])
            p = params if i == 0 else shard_params(params, m, config)
            # The draft rides the same per-replica device slice — a
            # draft committed to replica 0's devices would either fail
            # jit's device check or pay a cross-device transfer every
            # speculative dispatch on the other replicas.
            d = (
                draft_params if draft_params is None or i == 0
                else shard_params(draft_params, m, draft_config)
            )
        else:
            m, p, d = mesh, params, draft_params
        _geom_cache[i] = (m, p, d)
        return m, p, d

    if spec is None:
        logger.log(
            "serve_mesh_shared",
            "no --serve-mesh: every replica time-shares the "
            f"{mesh.devices.size}-device --data/--fsdp/--tensor mesh "
            "(give --serve-mesh DP,TP for a device slice per replica)",
        )
    elif len(devs) < args.replicas * per:
        logger.log(
            "serve_mesh_shared",
            f"host has {len(devs)} devices < replicas x mesh "
            f"({args.replicas} x {per}); replicas time-share one mesh",
        )

    def make_replica(i):
        """Build + start replica ``i`` (batcher + server).  Doubles as
        the FleetController's ``replica_factory`` under --autoscale:
        a scale-up gets the next index's geometry and a distinct
        sampling seed, everything else identical to the seed fleet."""
        m, p, d = _geometry(i)
        # Where this replica's weights actually live — what a fleet
        # operator (and chip_smoke.py's four-chip run) checks for
        # disjoint device slices.
        logger.log(
            "replica_placed", replica=i, mesh=str(dict(m.shape)),
            param_devices=sorted(_param_bytes_by_device(p)),
        )
        obs = Observability(
            slo_ttft_ms=getattr(args, "slo_ttft_ms", 0.0) or None,
            slo_itl_ms=getattr(args, "slo_itl_ms", 0.0) or None,
        )
        cb = _make_batcher(
            p, config, tokenizer, m, args, seed=args.seed + i,
            draft_params=d, draft_config=draft_config,
            fault_injector=injector, obs=obs,
        )
        srv = LLMServer(
            cb, tokenizer=tokenizer, host=args.host, port=0,
            replica_id=i,
            max_recoveries=getattr(args, "max_recoveries", 3),
            recovery_window_s=getattr(args, "recovery_window_s", 60.0),
            watchdog_deadline_s=(
                getattr(args, "watchdog_s", 60.0) or None
            ),
            drain_timeout_s=getattr(args, "drain_timeout_s", 30.0),
            logger=logger,
            max_queue=getattr(args, "max_queue", 256),
            priority_classes=(
                getattr(args, "priority_classes", "on") == "on"
            ),
        )
        return srv.start()

    servers = []
    controller = None
    try:
        for i in range(args.replicas):
            servers.append(make_replica(i))
        # Cache-aware routing needs the router to speak the replicas'
        # chain-key schema: the tokenizer + chat format mirror each
        # replica's own /generate- and /chat-encoding, block_size is
        # the chain-key granularity (identical across replicas — same
        # config), and --replica-roles turns on the prefill/decode
        # disaggregation scheduler.
        router = ReplicaRouter(
            servers, host=args.host, port=args.http,
            policy=getattr(args, "route", "least-loaded"),
            fault_injector=injector, logger=logger,
            tokenizer=tokenizer,
            block_size=servers[0].batcher.block_size,
            chat_format=_chat_format_for(tokenizer),
            roles=getattr(args, "replica_roles", None),
            canary_interval_s=getattr(args, "canary_interval_s", 10.0),
        ).start()
        if getattr(args, "autoscale", False):
            from .router import FleetController

            controller = FleetController(
                router,
                replica_factory=make_replica,
                min_replicas=getattr(args, "autoscale_min", 1),
                max_replicas=getattr(args, "autoscale_max", 8),
                interval_s=getattr(args, "autoscale_interval_s", 5.0),
                drain_timeout_s=getattr(args, "drain_timeout_s", 30.0),
            )
            logger.log(
                "autoscale_armed",
                min=getattr(args, "autoscale_min", 1),
                max=getattr(args, "autoscale_max", 8),
                interval_s=getattr(args, "autoscale_interval_s", 5.0),
            )
        try:
            logger.log(
                "serving_replicas", address=router.address,
                replicas=args.replicas,
                policy=getattr(args, "route", "least-loaded"),
                meshes=[
                    str(dict(_geometry(i)[0].shape))
                    if _geometry(i)[0] is not None else None
                    for i in range(args.replicas)
                ],
            )
            if _test_hook is not None:
                _test_hook(router, servers)
                return
            _log_device_memory(logger, "serving")
            state = {"signaled": False}

            def _on_signal(signum, frame):
                state["signaled"] = True
                signal.signal(signal.SIGINT, signal.default_int_handler)

            previous = []
            try:
                for sig in (signal.SIGTERM, signal.SIGINT):
                    previous.append((sig, signal.signal(sig, _on_signal)))
            except ValueError:
                previous = []
            try:
                while not state["signaled"]:
                    time.sleep(0.2)
                drain_s = getattr(args, "drain_timeout_s", 30.0)
                logger.log("drain_begin", "all replicas draining",
                           timeout_s=drain_s)
                for srv in servers:
                    srv.begin_drain()
                for srv in servers:
                    srv.wait_drained(drain_s + 10)
                _log_device_memory(logger, "drained")
                logger.log("drained", "shutting down")
            except KeyboardInterrupt:
                for srv in servers:
                    srv.begin_drain(timeout_s=0.0)
                logger.log("hard_shutdown", "second interrupt")
            finally:
                for sig, old in previous:
                    try:
                        signal.signal(sig, old)
                    except (ValueError, TypeError):
                        pass
        finally:
            if controller is not None:
                controller.close(stop_owned=True)
            router.stop()
    finally:
        for srv in servers:
            srv.stop()
        if injector is not None:
            from .faults import install_trace_hook

            install_trace_hook(None)


def _serve(params, config, tokenizer, mesh, args) -> None:
    """Continuous-batching loop over stdin prompts (one per line)."""
    import sys

    stops = _stop_tokens(tokenizer)
    draft_params, draft_config = _load_draft(args, mesh)
    cb = _make_batcher(
        params, config, tokenizer, mesh, args, seed=args.seed,
        draft_params=draft_params, draft_config=draft_config,
    )
    rid_prompt: dict = {}
    emitted: dict = {}
    lines = [ln.rstrip("\n") for ln in sys.stdin if ln.strip()]
    for line in lines:
        try:
            rid = cb.submit(
                tokenizer.encode(line, bos=True, eos=False),
                max_new_tokens=args.max_gen_len,
            )
        except ValueError as e:
            # One over-long prompt must not take down the whole serve loop.
            print(f"\n=== {line!r}\n[rejected: {e}]", flush=True)
            continue
        rid_prompt[rid] = line
    while cb.pending():
        for rid, tok, done in cb.step():
            emitted.setdefault(rid, []).append(tok)
            if done:
                toks = emitted[rid]
                # The batcher finishes a request at its first stop token,
                # so a stop id can only be the terminal element; strip just
                # that one rather than filtering stop ids everywhere.
                if toks and toks[-1] in stops:
                    toks = toks[:-1]
                print(f"\n=== {rid_prompt[rid]!r}\n{tokenizer.decode(toks)}",
                      flush=True)
    print(f"\nserved {len(rid_prompt)} request(s) on {args.slots} slot(s)")


if __name__ == "__main__":
    main()
