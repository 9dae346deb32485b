"""Serving CLI end-to-end smoke test (parity: reference jax_example.main,
/root/reference/jax_example.py:33-43 — load weights, complete prompts) —
run against a tiny Orbax checkpoint with the byte tokenizer."""

import sys
from pathlib import Path

import jax
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.convert.checkpoint import save_checkpoint
import jax_llama_tpu.run as run_cli


def test_run_cli_end_to_end(tmp_path, capsys, monkeypatch):
    config = get_config(
        "tiny", vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=64,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), params, config)

    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(ckpt), "--byte-tokenizer",
         "--tensor", "2", "--prompt", "hello world",
         "--max-gen-len", "8", "--temperature", "0.0"],
    )
    run_cli.main()
    out = capsys.readouterr().out
    assert "restored" in out
    assert "'hello world'" in out
    assert "tok/s" in out or "summary" in out or "[" in out


def test_run_cli_requires_tokenizer(tmp_path, monkeypatch):
    monkeypatch.setattr(
        sys, "argv", ["run", "--ckpt-dir", str(tmp_path)],
    )
    with pytest.raises(SystemExit):
        run_cli.main()


def test_run_cli_serve_mode(tmp_path, capsys, monkeypatch):
    """--serve streams completions for stdin prompts via the batcher."""
    import io

    config = get_config(
        "tiny", vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=64,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), params, config)

    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(ckpt), "--byte-tokenizer", "--serve",
         "--slots", "2", "--tensor", "2", "--max-gen-len", "6",
         "--temperature", "0.0"],
    )
    monkeypatch.setattr(sys, "stdin", io.StringIO("hello\nworld\n"))
    run_cli.main()
    out = capsys.readouterr().out
    assert "'hello'" in out and "'world'" in out
    assert "served 2 request(s)" in out


def test_run_cli_http_mode(tmp_path, capsys, monkeypatch):
    """--http starts LLMServer over the batcher; requests served live
    (driven in-process via the test hook instead of the blocking loop)."""
    import json
    import urllib.request

    config = get_config(
        "tiny", vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=64,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), params, config)

    hits = {}

    def hook(srv):
        req = urllib.request.Request(
            srv.address + "/generate",
            data=json.dumps(
                {"text": "hi", "max_new_tokens": 4, "temperature": 0.0}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            hits["gen"] = json.loads(r.read())
        with urllib.request.urlopen(srv.address + "/healthz", timeout=60) as r:
            hits["health"] = json.loads(r.read())

    orig = run_cli._serve_http
    monkeypatch.setattr(
        run_cli, "_serve_http",
        lambda *a, **kw: orig(*a, **kw, _test_hook=hook),
    )
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(ckpt), "--byte-tokenizer",
         "--tensor", "2", "--http", "0", "--max-gen-len", "8",
         "--temperature", "0.0"],
    )
    run_cli.main()
    out = capsys.readouterr().out
    # The operational log line goes through obs.StructuredLogger now:
    # "serving address=http://... endpoints=..." in text mode.
    assert "serving" in out and "http://" in out
    assert len(hits["gen"]["tokens"]) == 4 and "text" in hits["gen"]
    assert hits["health"]["ok"] is True


def test_run_cli_http_log_json(tmp_path, capsys, monkeypatch):
    """--log-json routes every operational line through one JSON
    formatter: each log line parses as a JSON object with an "event"
    field (checkpoint_restored, serving, ...) — no bare prints left on
    the serving path."""
    import json
    import urllib.request

    config = get_config(
        "tiny", vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=64,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), params, config)

    def hook(srv):
        with urllib.request.urlopen(srv.address + "/healthz", timeout=60):
            pass

    orig = run_cli._serve_http
    monkeypatch.setattr(
        run_cli, "_serve_http",
        lambda *a, **kw: orig(*a, **kw, _test_hook=hook),
    )
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(ckpt), "--byte-tokenizer",
         "--tensor", "2", "--http", "0", "--log-json"],
    )
    run_cli.main()
    lines = [
        ln for ln in capsys.readouterr().out.splitlines() if ln.strip()
    ]
    assert lines, "expected structured log output"
    events = []
    for ln in lines:
        rec = json.loads(ln)  # every line is one JSON object
        assert "event" in rec and "ts" in rec
        events.append(rec["event"])
    assert "checkpoint_restored" in events
    assert "serving" in events


@pytest.mark.slow
def test_run_cli_serve_mesh_and_replicas(tmp_path, capsys, monkeypatch):
    """--serve-mesh dp,tp + --replicas N: requests served through the
    ReplicaRouter on mesh-placed replicas, end-to-end from the CLI.
    Slow tier (compiles a mesh'd checkpoint-restored model; the flag
    surface is pinned tier-1 below, the routed/mesh behavior by
    test_router.py + test_serve_mesh.py, and make mesh-serve runs
    this cell)."""
    import json
    import urllib.request

    config = get_config(
        "tiny", vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=64,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), params, config)

    hits = {}

    def hook(router, servers):
        req = urllib.request.Request(
            router.address + "/generate",
            data=json.dumps(
                {"text": "hi", "max_new_tokens": 4, "temperature": 0.0}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            hits["gen"] = json.loads(r.read())
            hits["replica"] = r.headers.get("X-Replica-Id")
        with urllib.request.urlopen(
            router.address + "/healthz", timeout=60
        ) as r:
            hits["health"] = json.loads(r.read())
        hits["meshes"] = [
            dict(s.batcher.mesh.shape) if s.batcher.mesh is not None
            else None
            for s in servers
        ]
        hits["placed"] = [s.batcher._mesh_placed for s in servers]

    orig = run_cli._serve_router
    monkeypatch.setattr(
        run_cli, "_serve_router",
        lambda *a, **kw: orig(
            *a, **{**kw, "_test_hook": hook},
        ),
    )
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(ckpt), "--byte-tokenizer",
         "--http", "0", "--serve-mesh", "1,2", "--replicas", "2",
         "--route", "affinity", "--slots", "2"],
    )
    run_cli.main()
    assert len(hits["gen"]["tokens"]) == 4
    assert hits["replica"] in ("0", "1")
    h = hits["health"]
    assert h["ok"] and h["policy"] == "affinity"
    assert len(h["replicas"]) == 2
    # 8 forced host devices / (1*2 per replica) -> each replica got its
    # own device slice on its own 1x2 serving mesh, placement active.
    assert all(m and m.get("tensor") == 2 for m in hits["meshes"])
    assert hits["placed"] == [True, True]
    # Start-up states where each replica's weights live, and that a
    # sliced fleet runs without the persistent compile cache (run.main).
    out = capsys.readouterr().out
    assert "compile_cache_off" in out
    assert "param_devices=[0, 1]" in out and "param_devices=[2, 3]" in out


def test_run_cli_serve_mesh_flag_validation(tmp_path, monkeypatch):
    """Bad scale-out flag combinations refuse loudly at startup."""
    # --replicas needs --http.
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(tmp_path), "--byte-tokenizer",
         "--replicas", "2"],
    )
    with pytest.raises(SystemExit, match="replicas"):
        run_cli.main()
    # --serve-mesh needs a serving mode.
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(tmp_path), "--byte-tokenizer",
         "--serve-mesh", "1,2"],
    )
    with pytest.raises(SystemExit, match="serve-mesh"):
        run_cli.main()
    # Malformed geometry.
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(tmp_path), "--byte-tokenizer",
         "--http", "0", "--serve-mesh", "1,2,3"],
    )
    with pytest.raises(SystemExit, match="serve-mesh"):
        run_cli.main()
    # More devices than the host has.
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(tmp_path), "--byte-tokenizer",
         "--http", "0", "--serve-mesh", "4,4"],
    )
    with pytest.raises(SystemExit, match="devices"):
        run_cli.main()
    # --replica-roles: wrong count, bad role, missing a role class,
    # and the cache-aware policy requirement — all pre-weight-load.
    base = ["run", "--ckpt-dir", str(tmp_path), "--byte-tokenizer",
            "--http", "0", "--replicas", "2"]
    for extra, msg in (
        (["--replica-roles", "prefill"], "one role per replica"),
        (["--replica-roles", "prefill,cook"], "unknown role"),
        (["--replica-roles", "prefill,prefill",
          "--route", "cache-aware"], "EACH role"),
        (["--replica-roles", "prefill,decode"], "cache-aware"),
    ):
        monkeypatch.setattr(sys, "argv", base + extra)
        with pytest.raises(SystemExit, match=msg):
            run_cli.main()


@pytest.mark.slow
def test_run_cli_cache_aware_disaggregation(
    tmp_path, capsys, monkeypatch,
):
    """--route cache-aware + --replica-roles prefill,decode from the
    CLI: a cold session prefills on replica 0, its chain streams to
    the decode replica, and the revisit lands there warm (slow tier;
    make fleet runs it — the routing/scheduler behavior itself is
    pinned tier-1 by test_cache_routing.py)."""
    import json
    import urllib.request

    config = get_config(
        "tiny", vocab_size=512, dim=64, n_layers=2, n_heads=4,
        n_kv_heads=2, multiple_of=32, max_seq_len=96,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), params, config)

    hits = {}

    def post(url, payload):
        req = urllib.request.Request(
            url + "/generate", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            return json.loads(r.read()), r.headers.get("X-Replica-Id")

    session = "the quick brown fox jumps over the lazy d"

    def hook(router, servers):
        _, rep0 = post(
            router.address,
            {"text": session, "max_new_tokens": 4,
             "temperature": 0.0},
        )
        hits["cold_replica"] = rep0
        hits["handoff_done"] = router.wait_handoffs(20.0)
        _, rep1 = post(
            router.address,
            {"text": session + " and a second turn",
             "max_new_tokens": 4, "temperature": 0.0},
        )
        hits["revisit_replica"] = rep1
        hits["health"] = router.health()

    orig = run_cli._serve_router
    monkeypatch.setattr(
        run_cli, "_serve_router",
        lambda *a, **kw: orig(*a, **{**kw, "_test_hook": hook}),
    )
    monkeypatch.setattr(
        sys, "argv",
        ["run", "--ckpt-dir", str(ckpt), "--byte-tokenizer",
         "--http", "0", "--replicas", "2", "--route", "cache-aware",
         "--replica-roles", "prefill,decode", "--slots", "2",
         "--tensor", "1"],
    )
    run_cli.main()
    assert hits["cold_replica"] == "0"  # prefill role
    assert hits["handoff_done"]
    assert hits["revisit_replica"] == "1"  # decodes warm
    h = hits["health"]
    assert h["policy"] == "cache-aware"
    assert h["roles"] == ["prefill", "decode"]
    assert h["handoff"]["completed_total"] >= 1


WORKLOADS = sorted(
    (Path(__file__).resolve().parent.parent / "benchmark" / "workloads").glob("*.json")
)


@pytest.mark.parametrize("workload", WORKLOADS, ids=lambda p: p.stem)
def test_every_server_key_of_a_cell_is_an_option_of_run(workload):
    """`benchmark/system.serve` hands a cell's `server` block to
    `_serve_http` in a bare namespace that refuses nothing: a key that is
    no `dest` of run.py's parser (an option removed or misspelt) would be
    silently ignored and the cell would run run.py's default instead."""
    import json

    dests = {a.dest for a in run_cli._parser()._actions}
    # What `system.serve` itself consumes: the row count, and the two
    # that go into the configuration, not the namespace.
    known = dests | {"slots", "max_seq_len", "attn"}
    for block in (json.loads(workload.read_text()),
                  json.loads(workload.read_text())["rehearse"]):
        assert set(block["server"]) <= known, set(block["server"]) - known


def test_the_prefix_cache_is_one_switch_and_a_bare_namespace_is_the_parsers_defaults():
    """`--no-prefix-cache` is the one option of the prefix cache (the server
    still reports `prefix_index`: `radix` or `off`), and the one place run.py
    builds a batcher fills what a bare namespace leaves out from the parser,
    not from literals of its own."""
    from types import SimpleNamespace

    from jax_llama_tpu.tokenizers.bytes import ByteTokenizer

    parser = run_cli._parser()
    assert "prefix_index" not in {a.dest for a in parser._actions}
    assert not any("--prefix-index" in a.option_strings for a in parser._actions)
    config = get_config(
        "tiny", vocab_size=ByteTokenizer().n_words, dim=32, n_layers=1,
        n_heads=2, n_kv_heads=1, multiple_of=32, max_seq_len=64)
    params = init_params(jax.random.PRNGKey(0), config)

    def build(**server):
        args = SimpleNamespace(slots=2, temperature=0.0, top_p=0.95, seed=0, **server)
        return run_cli._make_batcher(
            params, config, ByteTokenizer(), None, args, seed=args.seed,
            draft_params=None, draft_config=None).describe()

    bare, off = build(), build(no_prefix_cache=True, decode_chunk=2)
    assert bare["prefix_index"] == "radix" and off["prefix_index"] == "off"
    for key in ("decode_chunk", "prefill_budget", "spec_rounds", "host_kv_blocks"):
        assert bare[key] == parser.get_default(key), key
    assert off["decode_chunk"] == 2


LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@pytest.fixture(scope="module")
def lowerings_of_a_served_window():
    """Serve two requests through `_serve_http` with a bare namespace (as
    the benchmark does: every setting run.py's default), the second
    admitted through the fused lane; returns per program the lowering
    events jax fired and the jit-cache entries the run added."""
    import json
    import threading
    import time
    import urllib.request
    from types import SimpleNamespace

    from jax import monitoring

    from jax_llama_tpu import serving
    from jax_llama_tpu.tokenizers.bytes import ByteTokenizer

    # A vocabulary no other test of this file uses: every variant is new
    # to the process-wide jit cache.
    config = get_config(
        "tiny", vocab_size=384, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=256,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    events, live = {}, [True]

    def listener(event, duration_secs, fun_name=None, **kw):
        if live[0] and event == LOWERING_EVENT:
            events[fun_name] = events.get(fun_name, 0) + 1

    monitoring.register_event_duration_secs_listener(listener)
    before = serving.jit_cache_entries()
    stats = {}

    def post(srv, prompt, n):
        req = urllib.request.Request(
            srv.address + "/generate",
            data=json.dumps({"prompt": prompt, "max_new_tokens": n,
                             "temperature": 0.0}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as r:
            return json.loads(r.read())

    def hook(srv):
        first = threading.Thread(
            target=post, args=(srv, list(range(3, 23)), 100))
        first.start()
        deadline = time.monotonic() + 120
        while (srv.batcher.decode_dispatches_total < 1
               and time.monotonic() < deadline):
            time.sleep(0.005)
        post(srv, list(range(40, 70)), 8)
        first.join()
        stats.update(srv.batcher.stats())

    args = SimpleNamespace(
        slots=4, temperature=0.0, top_p=0.95, seed=0, host="127.0.0.1",
        http=0, replicas=1,
    )
    try:
        run_cli._serve_http(
            params, config, ByteTokenizer(), None, args, _test_hook=hook)
    finally:
        live[0] = False
    after = serving.jit_cache_entries()
    assert stats["fused_admissions_total"] >= 1, stats
    return events, {k: after[k] - before.get(k, 0) for k in after}


@pytest.mark.parametrize("program", ["_paged_decode_chunk", "_fused_chunk"])
def test_setup_lowers_a_program_variant_once(
    lowerings_of_a_served_window, program
):
    """A variant's first dispatch is its only lowering: no second pass
    over the program for a cost model (run.py's default until PR 30)."""
    events, added = lowerings_of_a_served_window
    assert added[program] >= 1
    assert events.get(f"jit({program})", 0) == added[program], (events, added)
