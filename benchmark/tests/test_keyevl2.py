"""The `keyevl2-hotdocs-asks` cell's own tests: CPU, tiny widths.

    python -m pytest benchmark/tests/test_keyevl2.py -q -p no:cacheprovider

Two of them rehearse a whole run of the cell (several minutes each).  They hold
the benchmark to THIS cell's entries by name, never to how many cells or
entries there are or to which come last.
"""

import importlib.util
import json
from pathlib import Path

import pytest

from benchmark import roofline, roofline_dsa_moe as rf

ROOT = Path(__file__).resolve().parents[2]
CELL = "keyevl2-hotdocs-asks"
NAME = "Keye-VL-2.0-30B-A3B"
CONFIG = ROOT / "benchmark" / "configs" / f"{NAME}.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = ("dsamoe_fused_dispatch_roofline", "sparse_attn_roofline", "index_select_roofline",
               "step.index_share_pct", "step.sparse_attn_share_pct", "attn.selected_kv_pct",
               "step.dsamoe_head_share_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _lines(out: str):
    return [json.loads(l) for l in out.splitlines() if l.startswith('{"bench"')]


def test_a_rehearsal_of_the_cell_reads_sound(capsys):
    from benchmark import run

    assert run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "6",
                     "--trace", "1", "--rehearse"]) == 0
    lines = _lines(capsys.readouterr().out)
    check = next(l for l in lines if l["bench"] == "check")
    # 128-token prompts, four times the rehearsal's topk of 32; 96 found in the cache
    assert check["ok"] is True and check["max_deficit"] <= 1e-4 and check["positions"] == 32
    assert set(check["prefill_dispatch_kinds"]) == {"fused"} and min(check["reask_hit_tokens"]) == 96
    window = next(l for l in lines if l["bench"] == "window")
    result = lines[-1]["result"]
    assert lines[-1]["bench"] == "rehearsal_end"
    # a rehearsal's short replays may leave a shape to the window (ROADMAP
    # C12): `correct` is the check's verdict but for that
    assert result["correct"] is (window["compiles"] == 0)
    assert result["failed"] == 0 and result["attempted"] > 0
    # the counters reached the readers through the packed fetch
    assert 1 <= result["metrics"]["moe.experts_touched_mean"]["value"] <= 8
    assert 0 < result["metrics"]["attn.selected_kv_pct"]["value"] < 100
    assert result["metrics"]["kv.prefix_hit_pct"]["value"] > 0


def test_a_run_that_serves_other_weights_is_not_correct(monkeypatch, capsys):
    """The server is handed the weights of another seed, so every token it
    produces is another model's: the check says so on the window's own path
    (fused prefill, re-asks over a prefix hit) and `correct` is false."""
    from benchmark import run, system

    serve = system.serve

    def serve_other_weights(params, config, mesh, server, seed, body):
        serve(system.make_params(config, mesh, seed + 1), config, mesh, server, seed, body)

    monkeypatch.setattr(system, "serve", serve_other_weights)
    assert run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "2", "--rehearse"]) == 0
    lines = _lines(capsys.readouterr().out)
    check = next(l for l in lines if l["bench"] == "check")
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
    assert (raw["reduced"]["num_hidden_layers"]["published"], raw["num_hidden_layers"]) == (48, 6)
    for line in ("qk_norm", "indexer_inputs", "indexer_key_norm_and_rope", "chunk_sizes",
                 "vision_tower", "torch_dtype"):
        assert line in raw["assumed"], line
    for key in ("source", "architecture", "reference", "reduced", "assumed", "deployment"):
        assert raw[key], key


def test_the_cell_and_its_configuration_are_in_the_benchmark_with_their_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells[CELL] == dict(cells[CELL], config=NAME, traffic="hotdocs-asks", chips=1)
    entry = configs[NAME]
    assert entry["reduced"] == ["num_hidden_layers"] and (ROOT / entry["file"]) == CONFIG
    assert entry["source"] == json.loads(CONFIG.read_text())["source"]
    work = json.loads((ROOT / "benchmark" / "workloads" / f"{CELL}.json").read_text())
    assert work["server"] == {"slots": 8, "max_seq_len": 32768, "decode_chunk": 8,
                              "prefill_budget": 2048, "attn": "auto", "priority_classes": "off"}
    traffic = work["traffic"]
    assert traffic["generator"] == "doc_sessions" and (traffic["clients"], traffic["ramp_s"]) == (16, 4.0)
    assert traffic["document_tokens"] == {"min": 8192, "max": 30720}
    assert traffic["question_tokens"] == {"min": 32, "max": 96}
    assert traffic["answer_tokens"] in ({"min": 48, "max": 128}, {"min": 48, "max": 96})
    assert (traffic["asks_per_document"], traffic["interleave"], traffic["cycle"]) == (64, 8, 8)
    assert work["check"] == {"prompts": 16, "prompt_tokens": 8192, "shared_tokens": 7680, "new_tokens": 64}
    assert work["trace"] == {"start_frac": 0.5, "seconds": 3}
    # every document is at least four times topk deep
    from benchmark.traffic import doc_sessions

    lens = sorted(doc_sessions._spread(traffic["document_tokens"], 8, __import__("random").Random(0)))
    assert lens == [9600, 12416, 15232, 18048, 20864, 23680, 26496, 29312]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["out_tokens_per_s"]["workloads"]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s", name
        assert (ROOT / "benchmark" / "metrics" / f"{name}.py").exists(), name
    for name in ("sparse_attn_roofline", "index_select_roofline", "dsamoe_fused_dispatch_roofline"):
        assert per_layer[name]["unit"] == "%" and per_layer[name]["layer"] == "kernels"


def test_the_reference_loads_and_states_its_limits():
    from benchmark import reference

    mod = reference.load(json.loads(CONFIG.read_text()))
    assert mod.SELECTIONS == ("topk", "dense", "newest")
    assert 0 < mod.MEAN_DEFICIT < mod.MAX_DEFICIT
    src = (ROOT / "benchmark" / "references" / "dsa_moe.py").read_text()
    assert "jax_llama_tpu" not in src.split('"""', 2)[2]      # nothing of the program's code
    for word in ("(assumed", "MAX_DEFICIT", "MEAN_DEFICIT", "float8", "dense", "newest"):
        assert word in mod.__doc__, word


def test_the_counts_of_the_block_are_the_issues():
    cfg = json.loads(CONFIG.read_text())
    n = rf.sizes(cfg)
    assert round(n["attention"] / 1e6, 2) == 18.87 and round(n["indexer"] / 1e6, 2) == 2.26
    assert round(n["router"] / 1e6, 2) == 0.26 and round(128 * n["expert"] / 1e6, 2) == 603.98
    assert round(n["head"] / 1e6, 1) == 311.2
    assert rf.index_bytes_per_token(cfg) == 128 and rf.kv_bytes_per_slot(cfg) == 2048
    with pytest.raises(ValueError, match="afmoe"):
        rf.sizes({"reference": "afmoe"})
    with pytest.raises(ValueError, match="dsa_moe"):
        roofline.layer_params(cfg)


def test_the_roofline_counts_on_a_hand_worked_small_case():
    """hidden 8, 2 query / 1 KV heads of 4, 2 index heads of 4, topk 3, 2
    layers, 4 experts of width 2 top-1, 16 rows of vocabulary, float32."""
    cfg = {"reference": "dsa_moe", "hidden_size": 8, "vocab_size": 16, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 2, "num_experts": 4,
           "num_experts_per_tok": 1, "moe_intermediate_size": 2, "torch_dtype": "float32",
           "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 4, "topk": 3}}
    n = rf.sizes(cfg)
    # q 8x8 + k, v 8x4 each + o 8x8; index q 8x8 + key 8x4 + weights 8x2
    assert n == {"attention": 192, "indexer": 112, "router": 32, "expert": 48, "head": 128}
    # two rows at contexts 2 and 10: index keys for all 12 tokens, chosen 2 + 3 slots
    assert rf.chosen_slots(cfg, [2.0, 10.0]) == 5.0
    assert rf.index_decode_iter_bytes(cfg, [2.0, 10.0]) == 2 * (112 * 4 + 16 * 12)
    # the chosen rows' bytes alone: the projections are the whole step's
    assert rf.sparse_decode_iter_bytes(cfg, [2.0, 10.0]) == 2 * 32 * 5
    assert rf.decode_iter_bytes(cfg, [2.0, 10.0], 3) == (
        2 * (112 * 4 + 16 * 12) + 2 * (192 * 4 + 32 * 5) + (2 * 32 + 128 + 3 * 48) * 4)
    # NOT the dense context: a dense read would be 32 B x 12 tokens a layer
    assert rf.sparse_decode_iter_bytes(cfg, [2.0, 10.0]) < 2 * 32 * 12
    # a 5-token chunk on itself: 15 causal pairs of index scores (2 heads x 4),
    # attention pairs capped at 3 a query: 1 + 2 + 3 + 3 + 3 = 12
    assert rf.chunk_index_flops(cfg, 5) == 2 * 2 * 4 * 15
    assert rf.chunk_attention_flops(cfg, 5) == 4 * 2 * 4 * 12
    assert rf.chunk_attention_flops(cfg, 2) == 4 * 2 * 4 * 3
    assert rf.index_chunk_flops(cfg, 5) == 2 * (2 * 112 * 5 + 240)
    assert rf.sparse_chunk_flops(cfg, 5) == 2 * 384
    assert rf.chunk_flops(cfg, 5) == (
        2 * (2 * 112 * 5 + 240) + 2 * (2 * 192 * 5 + 384) + 2 * 2 * (32 + 48) * 5 + 2 * 128)
    assert rf.chunk_experts_touched_max(cfg, 5) == 2 * 4 and rf.chunk_experts_touched_max(cfg, 1) == 2


def test_a_decode_iteration_of_the_cell_reads_what_the_issue_reckons():
    """Eight rows at the mean context of 19.5k, ~50 experts a layer touched:
    ~3.1 GB of layer weights, 0.62 GB of head, 0.32 GB of index keys and
    chosen K/V — where dense attention would read 1.9 GB of K/V."""
    cfg = json.loads(CONFIG.read_text())
    contexts = [19456.0] * 8
    b = rf.decode_iter_bytes(cfg, contexts, 6 * 50)
    assert 3.9e9 < b < 4.2e9
    cache = (rf.index_decode_iter_bytes(cfg, contexts) + rf.sparse_decode_iter_bytes(cfg, contexts)
             - 6 * 2 * rf.sizes(cfg)["indexer"])
    assert 0.30e9 < cache < 0.34e9
    assert 6 * 2048 * sum(contexts) > 1.9e9
    # a 2048-token chunk: 2 x ~355 M active parameters a token (six times
    # attention, indexer, router and 8 experts) x 2048, + scores and attention
    # of the chunk on itself
    assert 1.6e12 < rf.chunk_flops(cfg, 2048) < 1.8e12


def _synthetic(cfg, least, d):
    from benchmark import run as run_mod

    rows = [{"id": f"r{i}", "first": 0.0, "last": 100.0, "n_tokens": 0,
             "prompt_tokens": 19456} for i in range(8)]
    return run_mod.Context(
        trace={"modules": [{"program": d["program"], "start_s": 0.0, "seconds": least,
                            "dispatch": dict(d, end=d["start"] + least + 0.002)}]},
        peaks=PEAKS, records=rows, timelines={f"r{i}": {"rids": [i]} for i in range(8)},
        config=cfg, chips=1, dispatches=[])


def test_fused_roofline_counts_low_and_reads_under_100():
    """A synthetic dispatch that takes exactly its least time reads 100 % when
    every row and expert is known; unknown rows only lower it; a program
    without the counters, or another block's configuration, reads nothing."""
    from benchmark import run as run_mod

    read = run_mod.load_reader("dsamoe_fused_dispatch_roofline")
    cfg = json.loads(CONFIG.read_text())
    touched = 8 * 6 * 50
    d = {"start": 10.0, "k": 8, "prefill_tokens": 64, "rids": list(range(8)),
         "kind": "fused", "program": "_fused_chunk",
         "moe": {"experts_touched": touched + 6 * 128, "assignments": 0, "layer_calls": 54, "max_load": 0}}
    t_iter, _ = roofline.least_seconds(
        0.0, rf.decode_iter_bytes(cfg, [19456.0] * 8, touched / 8), PEAKS, 1)
    t_chunk, _ = roofline.least_seconds(rf.chunk_flops(cfg, 64), 0.0, PEAKS, 1)
    ctx = _synthetic(cfg, 8 * t_iter + t_chunk, d)
    got = read(ctx)
    assert abs(got["value"] - 100.0) < 1e-6 and got["note"]["rows_counted"] == 8
    ctx.timelines = {}
    assert read(ctx)["value"] < 95.0
    ctx.trace["modules"][0]["dispatch"].pop("moe")
    assert read(ctx) is None
    ctx.config = {"reference": "afmoe"}
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None


def test_the_new_readers_read_nothing_from_a_program_without_their_sources():
    """Without a trace, without the counters, or for another block's
    configuration every new reader returns None and does not raise (the
    driver lays these files over the parent's checkout for the traced runs)."""
    from benchmark import run as run_mod

    cfg = json.loads(CONFIG.read_text())
    other = json.loads((ROOT / "benchmark" / "configs" / "Trinity-Mini.json").read_text())
    for config in (cfg, other):
        ctx = run_mod.Context(
            trace=None, peaks=PEAKS, records=[], timelines={}, config=config, chips=1,
            dispatches=[], counters0={}, counters1={})
        for name in NEW_METRICS:
            assert run_mod.load_reader(name)(ctx) is None, name
    selected = run_mod.load_reader("attn.selected_kv_pct")
    ctx.counters1 = {"attn_selected_slots_total": 2048 * 10, "attn_candidate_slots_total": 19456 * 10,
                     "attn_select_dense_rows_total": 0}
    assert abs(selected(ctx)["value"] - 100 * 2048 / 19456) < 1e-9


def test_the_selection_control_holds_the_served_tokens_to_each_selection(monkeypatch, capsys):
    """`selection_control` after a stubbed run: the reference's `logits` is
    called once a selection with `select=` set, the verdicts print in order,
    and the exit code is 0 only for pass, fail, fail."""
    from benchmark import reference, run, selection_control

    raw = json.loads(CONFIG.read_text())
    calls = []

    def judge(params, cfg, requests, records):
        ref = reference.load(cfg)
        calls.append(ref.logits.keywords["select"] if hasattr(ref.logits, "keywords") else None)
        return {"ok": verdict.get(calls[-1], True), "max_deficit": 0.0, "mean_deficit": 0.0,
                "limits": [ref.MAX_DEFICIT, ref.MEAN_DEFICIT]}

    def fake_run(argv):
        reference.judge({}, raw, [], [])
        return 0

    monkeypatch.setattr(reference, "judge", judge)
    monkeypatch.setattr(run, "main", fake_run)
    verdict = {"dense": False, "newest": False}
    assert selection_control.main([]) == 0
    assert calls == [None, "topk", "dense", "newest"]
    lines = [l for l in _lines(capsys.readouterr().out) if l["bench"] == "selection_control"]
    assert [(l["select"], l["ok"]) for l in lines] == [("topk", True), ("dense", False), ("newest", False)]
    verdict["newest"] = True            # limits the plainest selection passes gate nothing
    assert selection_control.main([]) == 1
    assert not hasattr(reference.load(raw).logits, "keywords")     # restored
