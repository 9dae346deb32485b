"""The `phi4flash-reason-sessions` cell's own tests: CPU, tiny widths.

    python -m pytest benchmark/tests/test_phi4flash.py -q -p no:cacheprovider

Two of them rehearse a whole run of the cell (several minutes each): its check
must read sound on the window's own path — fused prefill only, every re-ask
resumed from a state snapshot — and unsound when another model's weights are served.
"""

import importlib.util
import json
from pathlib import Path

import pytest

from benchmark import roofline, roofline_sambay as rf

ROOT = Path(__file__).resolve().parents[2]
CELL = "phi4flash-reason-sessions"
CONFIG = ROOT / "benchmark" / "configs" / "Phi-4-mini-flash-reasoning.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ("step.ssm_share_pct", "step.cross_attn_share_pct", "ssm_scan_roofline",
       "sambay_fused_dispatch_roofline", "ssm.match_tokens_cut_pct")


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
    result = lines[-1]["result"]
    assert lines[-1]["bench"] == "rehearsal_end"
    # a rehearsal's short replays may leave a shape to the window (ROADMAP
    # C12): `correct` is the check's verdict but for that
    assert result["correct"] is (window["compiles"] == 0)
    assert result["failed"] == 0 and result["attempted"] > 0
    # the counters reached the readers
    assert 0 <= result["metrics"]["ssm.match_tokens_cut_pct"]["value"] < 100
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
    # at these widths logits are ~N(0, 0.2^2): another model's token lies well
    # under the reference's largest, which the MEAN limit refuses
    assert check["ok"] is False and check["mean_deficit"] > check["limits"][1]
    assert set(check["prefill_dispatch_kinds"]) == {"fused"} and min(check["reask_hit_tokens"]) > 0
    assert lines[-1]["result"]["correct"] is False


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_every_catalog_key_and_nothing_is_reduced():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Phi-4-mini-flash-reasoning")
    raw = json.loads(CONFIG.read_text())
    assert raw["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if raw.get(k, "absent") != v) == []
    assert raw["reduced"] == {}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "Phi-4-mini-flash-reasoning")
    assert entry["reduced"] == [] and entry["source"] == raw["source"]
    for line in ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
                 "mamba_biases", "attention_biases", "differential_attention",
                 "memory_taken_before_the_gate", "window_edge", "layer_kinds",
                 "no_position_encoding", "state_types", "initializers", "torch_dtype"):
        assert line in raw["assumed"], line
    assert "deployment" in raw


def test_the_counts_of_the_block_are_the_issues():
    cfg = json.loads(CONFIG.read_text())
    n = rf.sizes(cfg)
    assert round(n["ffn"] / 1e6, 2) == 78.64 and round(n["mamba"] / 1e6, 2) == 41.23  # 41.24 with conv bias, dt bias and D
    assert round(n["attention"] / 1e6, 2) == 19.66 and round(n["cross"] / 1e6, 2) == 13.11
    assert round(n["gmu"] / 1e6, 2) == 26.21 and round(n["head"] / 1e6, 2) == 512.16
    assert (n["n_mamba"], n["n_window"], n["n_cross"]) == (9, 8, 7)
    # 3,852.6 M with the norms and biases this leaves out: 7.70 GB in bfloat16
    assert 3851.5e6 < rf.parameters(cfg) < 3852.6e6
    assert rf.kv_bytes_per_token(cfg) == 5120 and 9 * rf.kv_bytes_per_token(cfg) == 46080
    assert rf.state_bytes_per_row(cfg) == 358400
    with pytest.raises(ValueError, match="afmoe"):
        rf.sizes({"reference": "afmoe"})
    # one decode iteration of 24 rows at 2.3k: the weights (7.7 GB), layer 17's
    # K/V eight times (2.3 GB), eight windows (0.5 GB), the state twice (0.15 GB)
    b = rf.decode_iter_bytes(cfg, [2300.0] * 24)
    assert 10.4e9 < b < 10.9e9
    assert rf.decode_iter_bytes(cfg, []) == 2 * rf.parameters(cfg)
    # a 512-token chunk: ~3.4 TFLOP of projections beside nine scans
    assert 3.3e12 < rf.chunk_flops(cfg, 512) < 3.6e12
    assert rf.scan_step_bytes(cfg) == 2 * 5120 * 16 * 4 + 5120 * 3 * 2 + 2 * 16 * 2
    assert rf.scan_chunk_bytes(cfg, 512) == 512 * (5120 * 3 * 2 + 64) + 2 * 5120 * 16 * 4


def _synthetic(cfg, least, d):
    from benchmark import run as run_mod

    rows = [{"id": f"r{i}", "first": 0.0, "last": 100.0, "n_tokens": 0,
             "prompt_tokens": 2300} for i in range(24)]
    return run_mod.Context(
        trace={"modules": [{"program": d["program"], "start_s": 0.0, "seconds": least,
                            "dispatch": dict(d, end=d["start"] + least + 0.002)}]},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, records=rows,
        timelines={f"r{i}": {"rids": [i]} for i in range(24)}, config=cfg, chips=1, dispatches=[])


def test_fused_roofline_counts_low_and_reads_under_100():
    """A synthetic dispatch that takes exactly its least time reads 100 % when
    every row is known; unknown rows only lower it; another block's
    configuration reads nothing."""
    from benchmark import run as run_mod

    read = run_mod.load_reader("sambay_fused_dispatch_roofline")
    cfg = json.loads(CONFIG.read_text())
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    d = {"start": 10.0, "k": 8, "prefill_tokens": 512, "rids": list(range(24)),
         "kind": "fused", "program": "_fused_chunk"}
    t_iter, _ = roofline.least_seconds(0.0, rf.decode_iter_bytes(cfg, [2300.0] * 24), peaks, 1)
    t_chunk, _ = roofline.least_seconds(rf.chunk_flops(cfg, 512), 0.0, peaks, 1)
    ctx = _synthetic(cfg, 8 * t_iter + t_chunk, d)
    got = read(ctx)
    assert abs(got["value"] - 100.0) < 1e-6 and got["note"]["rows_counted"] == 24
    ctx.timelines = {}
    assert read(ctx)["value"] < 95.0
    ctx.config = {"reference": "afmoe"}
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None


def test_the_new_readers_read_nothing_from_a_program_without_their_sources():
    """On the parent commit the counters and scopes do not exist: every new
    reader returns None and does not raise (the driver lays these files over
    the parent's checkout for the traced runs)."""
    from benchmark import run as run_mod

    cfg = json.loads(CONFIG.read_text())
    ctx = run_mod.Context(
        trace=None, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, records=[],
        timelines={}, config=cfg, chips=1, dispatches=[], counters0={}, counters1={})
    for name in NEW:
        assert run_mod.load_reader(name)(ctx) is None, name
    for other in ("mistral-7b-v0.3", "Trinity-Mini"):
        ctx.config = json.loads((CONFIG.parent / f"{other}.json").read_text())
        for name in NEW:
            assert run_mod.load_reader(name)(ctx) is None, (other, name)
    cut = run_mod.load_reader("ssm.match_tokens_cut_pct")
    ctx.counters0 = {"ssm_match_tokens_cut_total": 0, "prefix_hit_tokens_total": 100}
    ctx.counters1 = {"ssm_match_tokens_cut_total": 100, "prefix_hit_tokens_total": 400}
    assert cut(ctx) == 25.0


def test_the_new_cell_is_in_the_benchmark_with_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) == 5 and all(w["chips"] == 1 for w in cells.values())
    assert cells[CELL]["config"] == "Phi-4-mini-flash-reasoning"
    work = json.loads((ROOT / "benchmark" / "workloads" / f"{CELL}.json").read_text())
    assert work["server"] == {"slots": 24, "max_seq_len": 4096, "decode_chunk": 8,
                              "prefill_budget": 512, "attn": "auto", "priority_classes": "off"}
    assert work["traffic"] == {
        "generator": "doc_sessions", "clients": 32, "ramp_s": 4.0,
        "document_tokens": {"min": 1024, "max": 2560}, "question_tokens": {"min": 64, "max": 448},
        "answer_tokens": {"min": 128, "max": 384}, "asks_per_document": 4, "interleave": 4, "cycle": 8}
    assert work["check"] == {"prompts": 2, "prompt_tokens": 2048, "shared_tokens": 1536, "new_tokens": 64}
    metrics = {m["name"]: m for g in ("end_to_end", "per_layer") for m in bench[g]}
    for m in metrics.values():
        for cell in m.get("workloads", ()):
            assert cell in cells, (m["name"], cell)
    for name in ("out_tokens_per_s", "sched.occupancy_mean", "kv.prefix_hit_pct",
                 "step.prefill_ms_per_ktok", "step.window_attn_share_pct",
                 "step.full_attn_share_pct") + NEW:
        assert CELL in metrics[name]["workloads"], name
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL] and metrics[name]["moves"] == "out_tokens_per_s"
        assert (ROOT / "benchmark" / "metrics" / f"{name}.py").exists()
    # held to one cell by test_hostspans.py; reads layer_types, which this file lacks
    assert CELL not in metrics["loop.gap_share_pct"]["workloads"]
    assert CELL not in metrics["attn.window_kv_steps_pct"]["workloads"]
    ref = importlib.util.spec_from_file_location("ref_sambay", ROOT / "benchmark" / "references" / "sambay.py")
    mod = importlib.util.module_from_spec(ref)
    ref.loader.exec_module(mod)
    assert 0 < mod.MEAN_DEFICIT < mod.MAX_DEFICIT
