"""One command, one cell, one run.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by name:
`BENCHMARK.json` lists them, `workloads/<name>.json` holds the cell's server
settings and traffic parameters, `traffic/<generator>.py` makes the requests,
`configs/<name>.json` holds the sizes and `metrics/<name>.py` reads one metric.
This file names none of them.  See README.md.

Phases: weights from the seed -> server -> warm-up by shapes -> correctness
check on the window's own path -> warm-up by replays of the cell's traffic ->
the window, which holds the cell's traffic and nothing else -> result.

The last line of standard output is the result object; everything before it
(phases, counts, lateness, resolved kernels) is for a reader.  `--rehearse`
runs the whole flow on the CPU at the tiny size the workload file gives and
prints no result line: it is a rehearsal of control flow, never a measurement.
`--sweep` finds an open loop's knee once (README.md) and prints none either.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()  # the nearest this process gets to its own start

import argparse
import importlib
import importlib.util
import itertools
import json
import os
import shutil
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = ROOT / ".bench_out"
SAMPLE = 256  # requests of the cell's traffic looked at for their prompt lengths


def say(event: str, **fields) -> None:
    print(json.dumps({"bench": event, **fields}, default=str), flush=True)


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_reader(name: str):
    """`metrics/<name>.py`, whose `read(ctx)` returns a number, a dict with
    `value` and `note`, or None when there is nothing to read."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: dict, group: str, cell: str) -> List[dict]:
    return [
        m for m in bench[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def merge(base: dict, over: Optional[dict]) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


class Context:
    """What a metric reader may read.  Times are seconds on
    `time.monotonic()` unless a name says otherwise."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def derive_seed(seed: int, salt: int) -> int:
    return (seed * 1000003 + salt * 7919 + 12345) % (2 ** 63)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, no result line")
    ap.add_argument("--sweep", default="",
                    help="comma-separated multiples of an open loop's rate: after "
                         "set-up, one window of --seconds at each, in this one "
                         "process; prints no result line")
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "configuration")
    work = load_json(HERE / "workloads" / f"{args.workload}.json")
    chips = int(cell["chips"])
    if args.rehearse:
        work = merge(work, work.get("rehearse"))
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}"
        )
    sweep = [float(x) for x in args.sweep.split(",") if x]

    try:
        import jax
        import jax_llama_tpu  # noqa: F401  (the system under test)

        from . import system
    except ImportError as e:
        print(f"benchmark: the system under test is not importable here: {e}",
              file=sys.stderr)
        return 4
    from . import loadgen, reference, stats, trace as trace_mod

    peaks = load_json(HERE / "peaks.json")
    devices = jax.devices()
    dev0 = devices[0]
    say("devices", platform=dev0.platform, kind=dev0.device_kind, count=len(devices))
    if not args.rehearse:
        if dev0.platform != "tpu":
            print(f"benchmark: platform is {dev0.platform!r}, not 'tpu'", file=sys.stderr)
            return 3
        if dev0.device_kind not in peaks:
            print(f"benchmark: no peaks for device kind {dev0.device_kind!r}", file=sys.stderr)
            return 3
    if len(devices) != chips:
        print(f"benchmark: the cell asks for {chips} device(s), JAX has {len(devices)}",
              file=sys.stderr)
        return 3

    # The rehearsal leaves no CPU entries where a chip run would look.
    cache_dir = None if args.rehearse else system.enable_compile_cache()
    server = work["server"]
    # `config_overrides` exists only under a workload's `rehearse` object.
    raw_config = merge(load_json(ROOT / cfg_entry["file"]), work.get("config_overrides"))
    reference.load(raw_config)  # a file that names no reference stops here, not after set-up
    config = system.load_config(raw_config, server)
    mesh = system.build_mesh(server, chips)
    t = time.monotonic()
    params = system.make_params(config, mesh, args.seed)
    say("weights", seconds=time.monotonic() - t, compile_cache=cache_dir,
        bytes=sum(x.nbytes for x in jax.tree_util.tree_leaves(params)))

    generator = importlib.import_module(
        f"{__package__}.traffic.{work['traffic']['generator']}"
    )
    traffic_params = dict(work["traffic"])
    vocab = config.vocab_size
    warm = work["warmup"]
    timeout_s = float(work.get("request_timeout_s", 120.0))
    result: Dict[str, Any] = {}

    def body(srv) -> None:
        cb = srv.batcher
        obs = cb.obs
        ring: List[dict] = []
        sink = obs.on_dispatch

        def on_dispatch(rec: dict) -> None:
            ring.append(rec)
            if sink is not None:
                sink(rec)

        obs.on_dispatch = on_dispatch
        address = srv.address
        say("server", address=address, **cb.describe())
        block = int(cb.describe()["block_size"])

        def wait_idle() -> None:
            deadline = time.monotonic() + timeout_s
            while cb.pending():
                if time.monotonic() > deadline:
                    raise SystemExit("the server did not go idle")
                time.sleep(0.005)

        serial = itertools.count(1)

        def variant(req: dict, new_tokens: int) -> dict:
            """`req`'s lengths with another first token each time, so that no
            two set-up requests share a cached prefix."""
            j = next(serial)
            return {"id": f"{req['id']}-v{j}", "max_new_tokens": new_tokens,
                    "prompt": [(req["prompt"][0] + j) % vocab] + req["prompt"][1:]}

        # 1. Warm-up by shapes.  The server buckets a prompt by the power of
        # two of its block count; the longest prompt of each bucket the
        # cell's traffic has is sent (a) alone to the idle server, as a holder
        # that keeps generating, (b) alone beside the holder, (c) three at once
        # beside it, which queue.  Those are the idle server's whole-prompt
        # insert and the fused prefill-decode chunk at both of its lengths
        # (8 iterations, or 4 while requests queue).
        t = time.monotonic()
        c0 = obs.compiles_total
        sample = generator.generate(traffic_params, derive_seed(args.seed, 2), args.seconds, vocab)
        reps: Dict[int, dict] = {}
        for req in itertools.islice(sample["requests"], SAMPLE):
            b = (-(-len(req["prompt"]) // block) - 1).bit_length()
            if b not in reps or len(req["prompt"]) > len(reps[b]["prompt"]):
                reps[b] = req

        def warm_shapes() -> None:
            for b in sorted(reps):
                rep = dict(reps[b], id=f"warm-{b}")
                hold = loadgen.Holder(address, variant(rep, int(warm["hold_tokens"])), timeout_s)
                recs = loadgen.burst(address, [variant(rep, int(warm["new_tokens"]))], timeout_s)
                recs += loadgen.burst(
                    address, [variant(rep, int(warm["new_tokens"])) for _ in range(3)], timeout_s)
                held = hold.holding()
                hold.release()
                wait_idle()
                if not held or not all(r["ok"] for r in recs):
                    raise SystemExit(f"warm-up of bucket {b} failed: held={held} {recs}")

        warm_shapes()
        # One request alone to the end: the decode chunk at each of its
        # lengths (a whole-prompt insert is followed by 1 iteration, then the
        # chunk halves as the request runs out: 16 tokens are 1+1+8+4+2).
        recs = loadgen.burst(address, [variant(dict(reps[min(reps)], id="warm-tail"), 16)], timeout_s)
        wait_idle()
        if not recs[0]["ok"]:
            raise SystemExit(f"warm-up request failed: {recs}")
        say("warmup_shapes", seconds=time.monotonic() - t, buckets=sorted(reps),
            compiles=obs.compiles_total - c0, by_program=dict(obs.compiles_by_program))

        # 2. Correctness, on the path the window takes: the check's prompts
        # are sent at once beside a holder, so they are admitted through the
        # fused chunk; then each is asked again with another ending, which
        # finds its prefix in the cache.  The dispatch records have to say so.
        t = time.monotonic()
        first = reps[min(reps)]
        fresh, reask = reference.check_requests(work["check"], derive_seed(args.seed, 1), vocab)
        hold = loadgen.Holder(address, variant(dict(first, id="check-hold"), int(warm["hold_tokens"])), timeout_s)
        recs = loadgen.burst(address, fresh, timeout_s, keep_tokens=True)
        recs += loadgen.burst(address, reask, timeout_s, keep_tokens=True)
        held = hold.holding()
        hold.release()
        wait_idle()
        check = reference.judge(params, raw_config, fresh + reask, recs)
        kinds_by_seq = {d["seq"]: d["kind"] for d in ring}
        kinds: Dict[str, int] = {}
        hit_tokens = []
        for req in fresh + reask:
            tl = obs.timeline_json(req["id"]) or {"spans": [], "kv": {}}
            for sp in tl["spans"]:
                if sp["state"] == "prefilling":
                    for seq in sp["dispatches"]:
                        k = kinds_by_seq.get(seq, "unknown")
                        kinds[k] = kinds.get(k, 0) + 1
            if req in reask:
                hit_tokens.append(int(tl["kv"].get("prefix_hit_tokens", 0)))
        check["prefill_dispatch_kinds"] = kinds
        check["reask_hit_tokens"] = hit_tokens
        check["beside_a_holder"] = held
        check["ok"] = bool(
            check["ok"] and held and set(kinds) == {"fused"}
            and hit_tokens and min(hit_tokens) > 0
        )
        say("check", seconds=time.monotonic() - t, **check)

        # 3. Warm-up by traffic: replays of the cell's own traffic (other
        # seeds, the same lengths) until one adds no compile.
        for i in range(int(warm["max_replays"])):
            t = time.monotonic()
            c0 = obs.compiles_total
            traffic = generator.generate(
                traffic_params, derive_seed(args.seed, 10 + i),
                float(warm["replay_s"]), vocab,
            )
            recs = loadgen.run_traffic(address, traffic, float(warm["replay_s"]), timeout_s)
            wait_idle()
            added = obs.compiles_total - c0
            say("warmup_replay", i=i, seconds=time.monotonic() - t, compiles=added,
                by_program=dict(obs.compiles_by_program), **stats.phase_counts(recs))
            if added == 0:
                break

        if sweep:
            for scale in sweep:
                params_x = dict(traffic_params, rate_rps=traffic_params["rate_rps"] * scale)
                traffic = generator.generate(params_x, args.seed, args.seconds, vocab)
                c0, n0 = obs.compiles_total, srv.overload.transitions_total
                t0 = time.monotonic()
                recs = loadgen.run_traffic(address, traffic, args.seconds, timeout_s, t0)
                drain_s = time.monotonic() - t0 - args.seconds
                wait_idle()
                thirds = [
                    stats.percentile(stats.ttfts(
                        [r for r in recs if j * args.seconds / 3 <= r["due"] - t0 < (j + 1) * args.seconds / 3],
                        args.seconds), 50)
                    for j in range(3)
                ]
                say("sweep_point", scale=scale, rate_rps=params_x["rate_rps"], seconds=args.seconds,
                    drain_s=drain_s, first_token_ms_p50_by_third=thirds,
                    first_token_ms_p50=stats.percentile(stats.ttfts(recs, args.seconds), 50),
                    first_token_ms_p95=stats.percentile(stats.ttfts(recs, args.seconds), 95),
                    per_token_ms_p50=stats.percentile(stats.tpots(recs, args.seconds), 50),
                    per_token_ms_p95=stats.percentile(stats.tpots(recs, args.seconds), 95),
                    completed_tokens_per_s=stats.completed_tokens(recs, t0, t0 + args.seconds) / args.seconds,
                    compiles=obs.compiles_total - c0, by_program=dict(obs.compiles_by_program),
                    rung_at_end=srv.overload.rung,
                    transitions=srv.overload.transitions_total - n0, **stats.phase_counts(recs))
                counts = stats.phase_counts(recs)
                if counts["succeeded"] < 0.95 * counts["sent"]:
                    break  # past the knee: a higher rate teaches nothing more
            result["check"] = check
            return

        # 4. The window: the cell's traffic and nothing else, from an idle
        # server.
        traffic = generator.generate(traffic_params, args.seed, args.seconds, vocab)
        ring.clear()
        counters0 = read_counters(cb, obs)
        tracer = None
        trace_dir = OUT / f"trace-{args.workload}"
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True, exist_ok=True)
            tracer = trace_mod.Tracer(
                str(trace_dir), start_after_s=float(work["trace"]["start_frac"]) * args.seconds,
                seconds=float(work["trace"]["seconds"]),
            )
        t0 = time.monotonic()
        setup_seconds = t0 - _T_START
        if tracer is not None:
            tracer.start(t0)
        records = loadgen.run_traffic(address, traffic, args.seconds, timeout_s, t0)
        t_drained = time.monotonic()
        if tracer is not None:
            tracer.join()
        counters1 = read_counters(cb, obs)
        timelines = {}
        for r in records:
            tl = obs.timeline_json(r["id"])
            if tl is not None:
                timelines[r["id"]] = tl
        obs.on_dispatch = sink
        to_s = lambda ms: obs.t0 + ms / 1000.0  # noqa: E731 (obs clock -> monotonic)
        dispatches = [
            dict(d, start=to_s(d["start_ms"]), end=to_s(d["start_ms"] + d["wall_ms"]))
            for d in ring
        ]
        result.update(
            records=records, t0=t0, setup_seconds=setup_seconds, drain_s=t_drained - t0 - args.seconds,
            counters0=counters0, counters1=counters1, timelines=timelines,
            dispatches=dispatches, check=check, tracer=tracer,
            describe=cb.describe(), trace_dir=str(trace_dir),
            compiles_by_program=dict(obs.compiles_by_program),
            overload={"rung": srv.overload.rung, "transitions_total": srv.overload.transitions_total},
        )

    system.serve(params, config, mesh, server, args.seed, body)
    if sweep:
        return 0 if result["check"]["ok"] else 1

    records = result["records"]
    counts = stats.phase_counts(records)
    compiles = result["counters1"]["compiles_total"] - result["counters0"]["compiles_total"]
    late = [(r["sent"] - r["due"]) * 1000.0 for r in records if r["sent"] is not None]
    say("window", seconds=args.seconds, drain_s=result["drain_s"], compiles=compiles,
        compiles_by_program=result["compiles_by_program"], overload=result["overload"],
        late_ms_p50=stats.percentile(late, 50), late_ms_max=max(late, default=None),
        dispatches=len(result["dispatches"]), **counts)
    reduced = None
    if result["tracer"] is not None:
        t = time.monotonic()
        tr = result["tracer"]
        reduced = trace_mod.reduce_dir(
            result["trace_dir"], result["dispatches"], tr.sync, tr.sync_mark
        )
        # Beside the trace, what it was joined with: enough to reduce it again.
        with open(Path(result["trace_dir"]) / "joined_with.json", "w") as f:
            json.dump({"sync_host_s": tr.sync, "sync_mark": tr.sync_mark,
                       "dispatches": result["dispatches"],
                       "records": [{k: v for k, v in r.items() if k != "tokens"} for r in records],
                       "rids": {i: tl.get("rids") for i, tl in result["timelines"].items()}},
                      f, default=str)
        say("trace", seconds=time.monotonic() - t, sync_mark=tr.sync_mark,
            sync_slack_ms=None if tr.sync_slack_s is None else 1000.0 * tr.sync_slack_s,
            **{k: v for k, v in reduced.items() if k not in ("modules", "breakdown")})
    ctx = Context(
        records=records, seconds=args.seconds, t0=result["t0"], setup_seconds=result["setup_seconds"],
        counters0=result["counters0"], counters1=result["counters1"],
        timelines=result["timelines"], dispatches=result["dispatches"], trace=reduced,
        config=raw_config, server=server, describe=result["describe"], chips=chips,
        peaks=peaks.get(dev0.device_kind), stats=stats,
    )
    group = "per_layer" if args.trace else "end_to_end"
    metrics: Dict[str, dict] = {}
    for m in metrics_of(bench, group, args.workload):
        got = load_reader(m["name"])(ctx)
        if isinstance(got, dict):
            say("metric_note", name=m["name"], note=got.get("note"))
            got = got.get("value")
        if got is not None:
            metrics[m["name"]] = {"value": float(got), "unit": m["unit"]}
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {
        "correct": bool(result["check"]["ok"] and compiles == 0 and counts["hung"] == 0),
        "attempted": counts["sent"],
        "failed": counts["sent"] - counts["succeeded"],
        "metrics": metrics, "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    # Each number compared beside its limit, as the last lines of standard
    # error: of a run that is not correct, the end of that is what is kept.
    chk = result["check"]
    limits = chk.get("limits") or [None, None]
    compared = [
        ("check max_deficit", chk.get("max_deficit"), f"<= {limits[0]}"),
        ("check mean_deficit", chk.get("mean_deficit"), f"<= {limits[1]}"),
        ("check positions", chk.get("positions"), "> 0"),
        ("check served beside a holder", chk.get("beside_a_holder"), "is True"),
        ("check prefill dispatch kinds", chk.get("prefill_dispatch_kinds"), "fused only"),
        ("check re-ask prefix hit tokens", chk.get("reask_hit_tokens"), "each > 0"),
        ("check error", chk.get("error"), "is None"),
        ("window compiles", compiles, "== 0"),
        ("window hung requests", counts["hung"], "== 0"),
    ]
    for what, got, limit in compared:
        print(f"compared: {what} = {got}  limit {limit}", file=sys.stderr)
    print(f"compared: correct = {out['correct']}", file=sys.stderr, flush=True)
    if args.rehearse:
        say("rehearsal_end", result=out)
        return 0
    print(json.dumps(out), flush=True)
    return 0


def read_counters(cb, obs) -> Dict[str, float]:
    """Program counters read before and after the window."""
    out = dict(cb.stats())
    out.update({k: v for k, v in vars(cb).items() if k.endswith("_total")})
    out["compiles_total"] = obs.compiles_total
    return {k: v for k, v in out.items() if isinstance(v, (int, float))}


if __name__ == "__main__":
    sys.exit(main())
