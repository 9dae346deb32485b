"""The `kanana2-docqa-long` cell's own tests: CPU, tiny widths.

    python -m pytest benchmark/tests/test_kanana.py -q -p no:cacheprovider

Two of them rehearse a whole run (about three minutes each): one sound, which
must read `correct` true on the window's own path, and one whose server is
handed another seed's weights, which must read false.
"""

import json
from pathlib import Path

import pytest

from benchmark import roofline, roofline_mla_moe as rf, scopes

ROOT = Path(__file__).resolve().parents[2]
CELL = "kanana2-docqa-long"
CONFIG = ROOT / "benchmark" / "configs" / "kanana-2-30b-a3b-instruct-2601.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _lines(out: str):
    return [json.loads(l) for l in out.splitlines() if l.startswith('{"bench"')]


def test_a_sound_rehearsal_reads_correct(capsys):
    from benchmark import run

    assert run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "4",
                     "--trace", "1", "--rehearse"]) == 0
    said = capsys.readouterr()
    lines = _lines(said.out)
    check = next(l for l in lines if l["bench"] == "check")
    assert check["ok"] is True and check["max_deficit"] <= check["limits"][0]
    assert set(check["prefill_dispatch_kinds"]) == {"fused"} and min(check["reask_hit_tokens"]) > 0
    result = lines[-1]["result"]
    assert lines[-1]["bench"] == "rehearsal_end" and result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    # the router's counters reached the readers through the packed fetch
    assert 1 <= result["metrics"]["moe.experts_touched_mean"]["value"] <= 8
    assert 0 < result["metrics"]["moe.max_load_share_pct"]["value"] <= 100
    assert "kv.prefix_hit_pct" in result["metrics"]


def test_a_run_that_serves_other_weights_is_not_correct(monkeypatch, capsys):
    from benchmark import run, system

    serve = system.serve

    def serve_other_weights(params, config, mesh, server, seed, body):
        serve(system.make_params(config, mesh, seed + 1), config, mesh, server, seed, body)

    monkeypatch.setattr(system, "serve", serve_other_weights)
    assert run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "2", "--rehearse"]) == 0
    lines = _lines(capsys.readouterr().out)
    check = next(l for l in lines if l["bench"] == "check")
    # at these widths logits are ~N(0, 0.16^2): another model's token lies ~0.5
    # under the reference's largest, which the MEAN limit refuses
    assert check["ok"] is False and check["mean_deficit"] > check["limits"][1]
    assert lines[-1]["result"]["correct"] is False


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_every_catalog_key_but_the_reduced_one():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    raw = json.loads(CONFIG.read_text())
    assert raw["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if raw.get(k, "absent") != v)
    assert differs == sorted(raw["reduced"]) == ["num_hidden_layers"]
    assert raw["reduced"]["num_hidden_layers"] == {
        "published": 48, "run": 8, "why": raw["reduced"]["num_hidden_layers"]["why"]}


def test_the_counts_of_the_block_are_the_issues():
    cfg = json.loads(CONFIG.read_text())
    n = rf.sizes(cfg)
    assert round(n["attention"] / 1e6, 2) == 26.35 and round(n["shared"] / 1e6, 2) == 9.44
    assert round(n["expert"] / 1e6, 2) == 4.72 and round(n["dense_ffn"] / 1e6, 1) == 37.7
    assert rf.latent_bytes_per_token(cfg) * cfg["num_hidden_layers"] == 9216
    with pytest.raises(ValueError, match="dense_gqa"):
        rf.sizes({"reference": "dense_gqa"})
    with pytest.raises(ValueError, match="mla_moe"):
        roofline.layer_params(cfg)
    # one decode iteration of 8 rows at 12k, ~40 experts a layer: weights
    # outside the experts, the experts touched, the latent
    b = rf.decode_iter_bytes(cfg, [12000.0] * 8, 7 * 40)
    assert 4.0e9 < b < 4.8e9
    # a 2048-token chunk: 2 x ~64 M active parameters x 2048 x 8 layers + attention on itself
    assert 2.0e12 < rf.chunk_flops(cfg, 2048) < 2.6e12
    assert rf.chunk_experts_touched_max(cfg, 2048) == 7 * 128
    assert rf.chunk_experts_touched_max(cfg, 2) == 7 * 12


def test_fused_roofline_counts_low_and_reads_under_100():
    """Synthetic dispatches that take exactly their least time read 100 %
    when every row and expert is known; unknown rows and the chunk's expert
    allowance only lower it."""
    from benchmark import run as run_mod

    read = run_mod.load_reader("fused_dispatch_roofline")
    cfg = json.loads(CONFIG.read_text())
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx_rows = [{"id": f"r{i}", "first": 0.0, "last": 100.0, "n_tokens": 0,
                 "prompt_tokens": 12000} for i in range(8)]
    timelines = {f"r{i}": {"rids": [i]} for i in range(8)}
    touched_decode = 8 * 7 * 40
    d = {"start": 10.0, "end": 10.2, "k": 8, "prefill_tokens": 2048, "rids": list(range(8)),
         "kind": "fused", "program": "_fused_chunk",
         "moe": {"experts_touched": touched_decode + 7 * 128, "assignments": 0,
                 "layer_calls": 63, "max_load": 0}}
    t_iter, _ = roofline.least_seconds(
        0.0, rf.decode_iter_bytes(cfg, [12000.0] * 8, touched_decode / 8), peaks, 1)
    t_chunk, _ = roofline.least_seconds(rf.chunk_flops(cfg, 2048), 0.0, peaks, 1)
    least = 8 * t_iter + t_chunk
    ctx = run_mod.Context(
        trace={"modules": [{"program": "_fused_chunk", "start_s": 0.0, "seconds": least,
                            "dispatch": dict(d, end=10.0 + least + 0.002)}]},
        peaks=peaks, records=ctx_rows, timelines=timelines, config=cfg, chips=1, dispatches=[])
    got = read(ctx)
    assert abs(got["value"] - 100.0) < 1e-6 and got["note"]["rows_counted"] == 8
    # rows the records do not know count nothing
    ctx.timelines = {}
    assert read(ctx)["value"] < 90.0
    # a program without the counters reads nothing, and does not raise
    ctx.trace["modules"][0]["dispatch"].pop("moe")
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None


def test_scope_of_reads_the_first_scope_of_an_op_name():
    pre = ("mla.", "moe.", "dense.")
    assert scopes.scope_of("jit(_fused_chunk)/while/body/closed_call/moe.experts/dot_general:", pre) == "moe.experts"
    assert scopes.scope_of("jit(f)/mla.attend_decode/pallas_call:", pre) == "mla.attend_decode"
    assert scopes.scope_of("jit(f)/while:", pre) is None and scopes.scope_of("", pre) is None
    ctx = type("C", (), {"trace": None})()
    assert scopes.share_pct(ctx, "moe.") is None
