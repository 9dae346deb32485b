"""The `falconh1-assist-sessions` cell's own tests: CPU, tiny widths.

    python -m pytest benchmark/tests/test_falconh1.py -q -p no:cacheprovider

Two of them rehearse a whole run of the cell (a few minutes each): its check
must read sound on the window's own path — fused prefill only, every re-ask
resumed from a state snapshot — and unsound when another model's weights are served.
"""

import importlib.util
import json
from pathlib import Path

import pytest

from benchmark import roofline, roofline_falcon_h1 as rf

ROOT = Path(__file__).resolve().parents[2]
CELL = "falconh1-assist-sessions"
NAME = "Falcon-H1-34B-Instruct"
CONFIG = ROOT / "benchmark" / "configs" / f"{NAME}.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ("falconh1_fused_dispatch_roofline", "ssd_scan_roofline", "step.ssm_step_share_pct",
       "step.head_share_pct", "ssm.snapshots_evicted_pct")
LISTED = ("out_tokens_per_s", "sched.occupancy_mean", "kv.prefix_hit_pct",
          "step.prefill_ms_per_ktok", "ssm.match_tokens_cut_pct", "step.ssm_share_pct",
          "step.full_attn_share_pct", "sched.gap_ms_per_fused",
          "sched.admit_work_ms_per_admission", "sched.upload_ms_per_fused",
          "dispatch.submit_ms_per_dispatch", "device.idle_span_named_pct",
          "sched.capacity_blocked_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _lines(out: str):
    return [json.loads(l) for l in out.splitlines() if l.startswith('{"bench"')]


def test_a_rehearsal_of_the_cell_reads_sound(capsys):
    from benchmark import run

    assert run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "4",
                     "--trace", "1", "--rehearse"]) == 0
    lines = _lines(capsys.readouterr().out)
    check = next(l for l in lines if l["bench"] == "check")
    # 128-token prompts in 64-token chunks, 64 of them behind a snapshot
    assert check["ok"] is True and check["max_deficit"] <= 1e-4 and check["positions"] == 32
    assert set(check["prefill_dispatch_kinds"]) == {"fused"} and check["reask_hit_tokens"] == [64, 64]
    window = next(l for l in lines if l["bench"] == "window")
    server = next(l for l in lines if l["bench"] == "server")
    # eight a slot here: the tiny K/V pool's bytes would hold more of the tiny states
    assert server["n_snapshots"] == 32 and server["ssm_state_bytes_per_slot"] == 2 * (3 * 128 * 4 + 4096)
    result = lines[-1]["result"]
    assert lines[-1]["bench"] == "rehearsal_end"
    # a rehearsal's short replays may leave a shape to the window (ROADMAP
    # C12): `correct` is the check's verdict but for that
    assert result["correct"] is (window["compiles"] == 0)
    assert result["failed"] == 0 and result["attempted"] > 0
    # the counters reached the readers
    assert 0 <= result["metrics"]["ssm.match_tokens_cut_pct"]["value"] < 100
    assert 0 <= result["metrics"]["ssm.snapshots_evicted_pct"]["value"] <= 100
    assert result["metrics"]["kv.prefix_hit_pct"]["value"] > 0


def test_a_run_that_serves_other_weights_is_not_correct(monkeypatch, capsys):
    """The server is handed the weights of another seed, so every token it
    produces is another model's: the check says so on the window's own path
    (fused prefill, re-asks resumed from a snapshot) and `correct` is false."""
    from benchmark import run, system

    serve = system.serve

    def serve_other_weights(params, config, mesh, server, seed, body):
        serve(system.make_params(config, mesh, seed + 1), config, mesh, server, seed, body)

    monkeypatch.setattr(system, "serve", serve_other_weights)
    assert run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "2", "--rehearse"]) == 0
    lines = _lines(capsys.readouterr().out)
    check = next(l for l in lines if l["bench"] == "check")
    # logits are ~N(0, 1) at every size (the initialisers see to it): another
    # model's token lies well under the reference's largest
    assert check["ok"] is False and check["mean_deficit"] > check["limits"][1]
    assert set(check["prefill_dispatch_kinds"]) == {"fused"} and min(check["reask_hit_tokens"]) > 0
    assert lines[-1]["result"]["correct"] is False


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_every_catalog_key_and_only_the_depth_is_reduced():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == NAME)
    raw = json.loads(CONFIG.read_text())
    assert raw["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if raw.get(k, "absent") != v) == [
        "num_hidden_layers"]
    assert list(raw["reduced"]) == ["num_hidden_layers"]
    assert (raw["reduced"]["num_hidden_layers"]["published"], raw["num_hidden_layers"]) == (
        row["config"]["num_hidden_layers"], 6) == (72, 6)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == raw["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and raw["reference"] == "falcon_h1"
    for line in ("torch_dtype", "layer", "ssm_multipliers_zone_order", "gate_then_norm",
                 "group_norm_grouping", "key_multiplier_on_k_only", "rope_form", "state_types",
                 "initializers", "max_position_embeddings", "unused_keys"):
        assert line in raw["assumed"], line
    assert "one pipeline stage of 6 layers" in raw["deployment"]


def test_the_counts_of_the_block_are_the_issues():
    cfg = json.loads(CONFIG.read_text())
    n = rf.sizes(cfg)
    assert round(n["attention"] / 1e6, 2) == 31.46 and round(n["mixer"] / 1e6, 2) == 68.35
    assert round(n["ffn"] / 1e6, 2) == 330.30 and round(n["layer"] / 1e6, 2) == 430.12
    assert round(n["head"] / 1e6, 1) == 1336.9 == round(n["embedding"] / 1e6, 1)
    assert rf.parameters(cfg) == 5_254_594_112 and round(2 * rf.parameters(cfg) / 1e9, 3) == 10.509
    assert rf.kv_bytes_per_token(cfg) == 12 * 1024
    assert rf.state_bytes_per_row(cfg) == 25_350_144 == 6 * (4 * 2 ** 20 + 3 * 5120 * 2)
    with pytest.raises(ValueError, match="sambay"):
        rf.sizes({"reference": "sambay"})
    # one decode iteration of 32 rows at 2.5k: layers and head (7.83 GB), the
    # state read and written (1.62 GB), the rows' K/V (0.98 GB)
    weights = 2 * (6 * n["layer"] + n["head"] + 5120)
    assert round(weights / 1e9, 2) == 7.84
    b = rf.decode_iter_bytes(cfg, [2500.0] * 32)
    assert b == weights + 32 * 5120 * 2 + 2 * 32 * 25_350_144 + 12288 * 2500.0 * 32
    assert 10.3e9 < b < 10.6e9 and rf.decode_iter_bytes(cfg, []) == weights
    # a 512-token chunk: 2.64 TFLOP of projections, 16 G of attention on
    # itself, 15 G in the six scans
    assert 2.65e12 < rf.chunk_flops(cfg, 512) < 2.70e12
    assert 2.4e9 < rf.ssd_scan_flops(cfg, 512) < 2.5e9
    assert rf.ssd_scan_bytes(cfg, 512) == 512 * (2 * 4096 + 2 * 512 + 32) * 2 + 2 * 4096 * 256 * 4


def _synthetic(cfg, least, d):
    from benchmark import run as run_mod

    rows = [{"id": f"r{i}", "first": 0.0, "last": 100.0, "n_tokens": 0,
             "prompt_tokens": 2500} for i in range(32)]
    return run_mod.Context(
        trace={"modules": [{"program": d["program"], "start_s": 0.0, "seconds": least,
                            "dispatch": dict(d, end=d["start"] + least + 0.002)}]},
        peaks=PEAKS, records=rows, timelines={f"r{i}": {"rids": [i]} for i in range(32)},
        config=cfg, chips=1, dispatches=[])


def test_roofline_shares_count_low_and_read_under_100(monkeypatch):
    """A synthetic dispatch that takes exactly its least time reads 100 % when
    every row is known; unknown rows only lower it; another block's
    configuration reads nothing.  The scan's share against a scope that took
    exactly its least time reads 100 %, and less for any time more."""
    from benchmark import run as run_mod

    read = run_mod.load_reader("falconh1_fused_dispatch_roofline")
    cfg = json.loads(CONFIG.read_text())
    d = {"start": 10.0, "k": 8, "prefill_tokens": 512, "rids": list(range(32)),
         "kind": "fused", "program": "_fused_chunk"}
    t_iter, _ = roofline.least_seconds(0.0, rf.decode_iter_bytes(cfg, [2500.0] * 32), PEAKS, 1)
    t_chunk, _ = roofline.least_seconds(rf.chunk_flops(cfg, 512), 0.0, PEAKS, 1)
    assert 0.0125 < t_iter < 0.0130 and 0.0134 < t_chunk < 0.0138
    ctx = _synthetic(cfg, 8 * t_iter + t_chunk, d)
    got = read(ctx)
    assert abs(got["value"] - 100.0) < 1e-6 and got["note"]["rows_counted"] == 32
    scan = run_mod.load_reader("ssd_scan_roofline")
    t_scan, bound = roofline.least_seconds(
        6 * rf.ssd_scan_flops(cfg, 512), 6 * rf.ssd_scan_bytes(cfg, 512), PEAKS, 1)
    assert bound == "memory"
    for took, want in ((t_scan, 100.0), (4 * t_scan, 25.0)):
        monkeypatch.setitem(scan.__globals__, "scope_seconds",
                            lambda ctx, took=took: {"ssm.scan": took, "": 1.0})
        assert abs(scan(ctx)["value"] - want) < 1e-6
    ctx.timelines = {}
    assert read(ctx)["value"] < 95.0
    ctx.config = {"reference": "sambay"}
    assert read(ctx) is None and scan(ctx) is None
    ctx.trace = None
    assert read(ctx) is None


def test_the_new_readers_read_nothing_from_a_program_without_their_sources():
    """On the parent commit the configuration is refused, and in another
    cell's traced run the scopes and the block are not this one's: every new
    reader returns None and does not raise (the driver lays these files over
    the parent's checkout for the traced runs)."""
    from benchmark import run as run_mod

    cfg = json.loads(CONFIG.read_text())
    ctx = run_mod.Context(
        trace=None, peaks=PEAKS, records=[], timelines={}, config=cfg, chips=1,
        dispatches=[], counters0={}, counters1={})
    for name in NEW:
        assert run_mod.load_reader(name)(ctx) is None, name
    ctx.trace = {"modules": []}     # a trace, but no `.xplane.pb` to read scopes from
    for other in ("mistral-7b-v0.3", "Trinity-Mini", "Phi-4-mini-flash-reasoning"):
        ctx.config = json.loads((CONFIG.parent / f"{other}.json").read_text())
        for name in NEW:
            assert run_mod.load_reader(name)(ctx) is None, (other, name)
    evicted = run_mod.load_reader("ssm.snapshots_evicted_pct")
    ctx.counters0 = {"ssm_snapshots_evicted_total": 2, "ssm_snapshots_taken_total": 10}
    ctx.counters1 = {"ssm_snapshots_evicted_total": 12, "ssm_snapshots_taken_total": 50}
    assert evicted(ctx) == 25.0
    ctx.counters1 = dict(ctx.counters0)
    assert evicted(ctx) is None


def test_the_new_cell_is_in_the_benchmark_with_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) == 6 and all(w["chips"] == 1 for w in cells.values())
    assert cells[CELL]["config"] == NAME and list(cells)[-1] == CELL
    work = json.loads((ROOT / "benchmark" / "workloads" / f"{CELL}.json").read_text())
    assert work["server"] == {"slots": 32, "max_seq_len": 4096, "decode_chunk": 8,
                              "prefill_budget": 512, "attn": "auto", "priority_classes": "off"}
    assert work["traffic"] == {
        "generator": "doc_sessions", "clients": 40, "ramp_s": 4.0,
        "document_tokens": {"min": 1024, "max": 2560}, "question_tokens": {"min": 32, "max": 160},
        "answer_tokens": {"min": 96, "max": 224}, "asks_per_document": 4, "interleave": 4, "cycle": 8}
    assert work["check"] == {"prompts": 2, "prompt_tokens": 2048, "shared_tokens": 1536, "new_tokens": 64}
    assert work["trace"] == {"start_frac": 0.5, "seconds": 3} and work["request_timeout_s"] == 180
    metrics = {m["name"]: m for g in ("end_to_end", "per_layer") for m in bench[g]}
    for m in metrics.values():
        for cell in m.get("workloads", ()):
            assert cell in cells, (m["name"], cell)
    for name in LISTED + NEW:
        assert metrics[name]["workloads"][-1] == CELL, name
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL] and metrics[name]["moves"] == "out_tokens_per_s"
        assert (ROOT / "benchmark" / "metrics" / f"{name}.py").exists()
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW)
    # the readers of another block's scopes and kernels stay with their cells
    for name in ("ssm_scan_roofline", "sambay_fused_dispatch_roofline", "step.cross_attn_share_pct",
                 "step.window_attn_share_pct", "loop.gap_share_pct"):
        assert CELL not in metrics[name]["workloads"], name
    ref = importlib.util.spec_from_file_location(
        "ref_falcon_h1", ROOT / "benchmark" / "references" / "falcon_h1.py")
    mod = importlib.util.module_from_spec(ref)
    ref.loader.exec_module(mod)
    assert 0 < mod.MEAN_DEFICIT < mod.MAX_DEFICIT
    source = (ROOT / "benchmark" / "references" / "falcon_h1.py").read_text()
    assert "jax_llama_tpu" not in source.split('"""', 2)[2]     # imports nothing of the program
