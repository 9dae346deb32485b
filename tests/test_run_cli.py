"""Serving CLI end-to-end smoke test (parity: reference jax_example.main,
/root/reference/jax_example.py:33-43 — load weights, complete prompts) —
run against a tiny Orbax checkpoint with the byte tokenizer."""

import sys

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


def test_peaks_come_from_the_device_table_or_are_off():
    """--peak-tflops / --peak-hbm-gbps default to a lookup by device_kind
    (obs.DEVICE_PEAKS).  A device the table does not list — the CPU these
    tests run on — gets 0 (no utilization gauges) and a log line, never
    the v5e's numbers; explicit flags are kept."""
    import io
    from types import SimpleNamespace

    from jax_llama_tpu.obs import DEVICE_PEAKS, StructuredLogger

    assert DEVICE_PEAKS["TPU v5 lite"] == (197e12, 819e9)
    assert "cpu" not in DEVICE_PEAKS

    def resolve(kind, tflops=None, gbps=None):
        buf = io.StringIO()
        args = SimpleNamespace(peak_tflops=tflops, peak_hbm_gbps=gbps)
        run_cli._resolve_peaks(args, kind, StructuredLogger(stream=buf))
        return args.peak_tflops, args.peak_hbm_gbps, buf.getvalue()

    assert resolve("TPU v5 lite")[:2] == (197.0, 819.0)
    tf, bw, log = resolve("cpu")
    assert (tf, bw) == (0.0, 0.0) and "utilization_gauges_off" in log
    tf, bw, log = resolve("cpu", tflops=10.0, gbps=20.0)
    assert (tf, bw) == (10.0, 20.0) and log == ""
    assert resolve("TPU v5 lite", tflops=100.0)[:2] == (100.0, 819.0)
