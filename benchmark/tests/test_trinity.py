"""The `trinitymini-docqa-mixed` cell's own tests, and the data of
`kanana2-chat-decode` (measured in PR 32, not a cell): CPU, tiny widths.

    python -m pytest benchmark/tests/test_trinity.py -q -p no:cacheprovider

One of them rehearses a whole run of the new cell (several minutes): its check
must read sound on the window's own path, with prompts deeper than the window.
"""

import importlib.util
import json
from pathlib import Path

import pytest

from benchmark import roofline, roofline_afmoe as rf

ROOT = Path(__file__).resolve().parents[2]
CELL = "trinitymini-docqa-mixed"
CONFIG = ROOT / "benchmark" / "configs" / "Trinity-Mini.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _lines(out: str):
    return [json.loads(l) for l in out.splitlines() if l.startswith('{"bench"')]


def test_a_rehearsal_of_the_cell_reads_sound(capsys):
    from benchmark import run

    assert run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "4",
                     "--trace", "1", "--rehearse"]) == 0
    lines = _lines(capsys.readouterr().out)
    check = next(l for l in lines if l["bench"] == "check")
    # 128-token prompts over a 40-token window, 96 of them found in the cache
    assert check["ok"] is True and check["max_deficit"] <= 1e-4 and check["positions"] == 32
    assert set(check["prefill_dispatch_kinds"]) == {"fused"} and min(check["reask_hit_tokens"]) == 96
    window = next(l for l in lines if l["bench"] == "window")
    result = lines[-1]["result"]
    assert lines[-1]["bench"] == "rehearsal_end"
    # a rehearsal's two short replays may leave a shape to the window (ROADMAP
    # C12): `correct` is the check's verdict but for that
    assert result["correct"] is (window["compiles"] == 0)
    assert result["failed"] == 0 and result["attempted"] > 0
    # the counters reached the readers through the packed fetch
    assert 1 <= result["metrics"]["moe.experts_touched_mean"]["value"] <= 8
    assert 0 < result["metrics"]["attn.window_kv_steps_pct"]["value"] <= 100
    assert "kv.prefix_hit_pct" in result["metrics"]


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_every_catalog_key_but_the_reduced_ones():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Trinity-Mini")
    raw = json.loads(CONFIG.read_text())
    assert raw["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if raw.get(k, "absent") != v)
    assert differs == sorted(raw["reduced"]) == ["layer_types", "num_dense_layers", "num_hidden_layers"]
    assert raw["layer_types"] == row["config"]["layer_types"][:5]
    assert (raw["num_hidden_layers"], raw["num_dense_layers"]) == (5, 1)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "Trinity-Mini")
    assert sorted(entry["reduced"]) == differs and entry["source"] == raw["source"]
    # every line the issue lists as taken from the code, not from a key
    for line in ("embedding_scale", "output_gate", "qk_norm", "four_norms_a_layer",
                 "rope_on_sliding_layers_only", "window_edge", "router_bias_selection_only",
                 "initializer_range", "torch_dtype"):
        assert line in raw["assumed"], line


def test_the_counts_of_the_block_are_the_issues():
    cfg = json.loads(CONFIG.read_text())
    n = rf.sizes(cfg)
    assert round(n["attention"] / 1e6, 2) == 27.26 and round(n["shared"] / 1e6, 2) == 6.29
    assert round(n["expert"] / 1e6, 2) == 6.29 and round(n["dense_ffn"] / 1e6, 2) == 37.75
    assert round(n["router"] / 1e6, 2) == 0.26 and round(n["head"] / 1e6, 1) == 410.0
    assert rf.layers(cfg) == ((1, 4), (4, 1))
    assert rf.kv_bytes_per_token(cfg) == 2048 and 5 * rf.kv_bytes_per_token(cfg) == 10240
    with pytest.raises(ValueError, match="mla_moe"):
        rf.sizes({"reference": "mla_moe"})
    with pytest.raises(ValueError, match="afmoe"):
        roofline.layer_params(cfg)
    # a window layer reads the window, a full layer the context
    assert rf.cache_tokens(cfg, [14016.0, 1000.0]) == (2048.0 + 1000.0, 15016.0)
    # one decode iteration of 8 rows at 14k, ~57 experts a layer: weights outside
    # the experts (~1.2 GB), the experts touched (~2.9 GB), the cache (~0.36 GB)
    b = rf.decode_iter_bytes(cfg, [14016.0] * 8, 4 * 57)
    assert 4.2e9 < b < 4.7e9
    assert rf.window_decode_iter_bytes(cfg, [14016.0] * 8) == 4 * (2 * n["attention"] + 2048 * 8 * 2048)
    # a 2048-token chunk: 2 x ~401 M active parameters x 2048 (five attentions,
    # the dense FFN, four times shared + router + 8 experts) + attention on itself
    assert 1.75e12 < rf.chunk_flops(cfg, 2048) < 1.9e12
    # the chunk on itself is the causal half, each query's keys capped at the window
    assert rf.chunk_attention_flops(cfg, 2048) == 4.0 * 32 * 128 * (2048 * 2049 // 2)
    assert rf.chunk_attention_flops(cfg, 4096) == 4.0 * 32 * 128 * (2048 * 2049 // 2 + 2048 * 2048)
    assert rf.chunk_experts_touched_max(cfg, 2048) == 4 * 128
    assert rf.chunk_experts_touched_max(cfg, 2) == 4 * 16


def _synthetic(cfg, least, d):
    from benchmark import run as run_mod

    rows = [{"id": f"r{i}", "first": 0.0, "last": 100.0, "n_tokens": 0,
             "prompt_tokens": 14016} for i in range(8)]
    return run_mod.Context(
        trace={"modules": [{"program": d["program"], "start_s": 0.0, "seconds": least,
                            "dispatch": dict(d, end=d["start"] + least + 0.002)}]},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, records=rows,
        timelines={f"r{i}": {"rids": [i]} for i in range(8)}, config=cfg, chips=1, dispatches=[])


def test_fused_roofline_counts_low_and_reads_under_100():
    """A synthetic dispatch that takes exactly its least time reads 100 % when
    every row and expert is known; unknown rows only lower it; a program
    without the counters, or another block's configuration, reads nothing."""
    from benchmark import run as run_mod

    read = run_mod.load_reader("afmoe_fused_dispatch_roofline")
    cfg = json.loads(CONFIG.read_text())
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    touched = 8 * 4 * 57
    d = {"start": 10.0, "k": 8, "prefill_tokens": 2048, "rids": list(range(8)),
         "kind": "fused", "program": "_fused_chunk",
         "moe": {"experts_touched": touched + 4 * 128, "assignments": 0, "layer_calls": 36, "max_load": 0}}
    t_iter, _ = roofline.least_seconds(
        0.0, rf.decode_iter_bytes(cfg, [14016.0] * 8, touched / 8), peaks, 1)
    t_chunk, _ = roofline.least_seconds(rf.chunk_flops(cfg, 2048), 0.0, peaks, 1)
    ctx = _synthetic(cfg, 8 * t_iter + t_chunk, d)
    got = read(ctx)
    assert abs(got["value"] - 100.0) < 1e-6 and got["note"]["rows_counted"] == 8
    ctx.timelines = {}
    assert read(ctx)["value"] < 95.0
    ctx.trace["modules"][0]["dispatch"].pop("moe")
    assert read(ctx) is None
    ctx.config = {"reference": "mla_moe"}
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
    for name in ("afmoe_fused_dispatch_roofline", "window_attn_roofline",
                 "step.window_attn_share_pct", "step.full_attn_share_pct",
                 "attn.window_kv_steps_pct"):
        assert run_mod.load_reader(name)(ctx) is None, name
    steps = run_mod.load_reader("attn.window_kv_steps_pct")
    ctx.counters1 = {"attn_window_kv_steps_total": 4 * 50, "attn_full_kv_steps_total": 200}
    assert steps(ctx) == 25.0


def test_the_new_cell_is_in_the_benchmark_with_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "Trinity-Mini" and cells[CELL]["chips"] == 1
    work = json.loads((ROOT / "benchmark" / "workloads" / f"{CELL}.json").read_text())
    assert work["server"] == {"slots": 8, "max_seq_len": 32768, "decode_chunk": 8,
                              "prefill_budget": 2048, "attn": "auto", "priority_classes": "off"}
    assert work["traffic"]["document_tokens"] == {"min": 1024, "max": 30720}
    assert work["check"] == {"prompts": 2, "prompt_tokens": 8192, "shared_tokens": 7680, "new_tokens": 64}
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            for cell in m.get("workloads", ()):
                assert cell in cells, (m["name"], cell)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["out_tokens_per_s"]["workloads"]
    ref = importlib.util.spec_from_file_location("ref_afmoe", ROOT / "benchmark" / "references" / "afmoe.py")
    mod = importlib.util.module_from_spec(ref)
    ref.loader.exec_module(mod)
    # between the sound readings and the float8 ones (the docstring's)
    assert 0.0223 < mod.MEAN_DEFICIT < 0.1435 and 0.858 < mod.MAX_DEFICIT



def test_the_float8_control_rounds_onto_the_float8_grid():
    """`float8_control.round_to_float8` by arithmetic lands where a convert to
    float8_e4m3fn lands (on the CPU the convert pair is honest), so the
    control's second reading is float8's and not a no-op."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.float8_control import round_to_float8

    x = jnp.asarray(np.random.RandomState(5).normal(0, 0.02, size=(64, 96)), jnp.bfloat16)
    got = np.asarray(round_to_float8(x).astype(jnp.float32))
    scale = 2.0 ** np.floor(np.log2(448.0 / np.abs(np.asarray(x, np.float32)).max()))
    want = np.asarray((x.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)) / scale
    want = np.asarray(jnp.asarray(want).astype(jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(got, want)
    assert 0.01 < np.abs(got - np.asarray(x, np.float32)).max() / np.abs(got).max() < 0.07
