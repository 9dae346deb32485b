"""The `xing4-freshdocs-asks` cell's own tests: CPU, tiny widths.

    python -m pytest benchmark/tests/test_xing4.py -q -p no:cacheprovider

Two of them rehearse a whole run of the cell (a few minutes each).  They hold
the benchmark to THIS cell's entries by name, never to how many cells or
entries there are or to which come last.
"""

import json
from pathlib import Path

import pytest

from benchmark import roofline, roofline_mhc_mla_moe as rf

ROOT = Path(__file__).resolve().parents[2]
CELL = "xing4-freshdocs-asks"
NAME = "Xing4.0-29B-A4B"
CONFIG = ROOT / "benchmark" / "configs" / f"{NAME}.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = ("mhcmla_fused_dispatch_roofline", "hc_mix_roofline", "step.hc_share_pct",
               "hc.unconverged_pct")
JOINED_LISTS = ("sched.occupancy_mean", "kv.prefix_hit_pct", "step.prefill_ms_per_ktok",
                "step.mla_attn_share_pct", "step.moe_share_pct", "moe.experts_touched_mean",
                "moe.max_load_share_pct", "sched.gap_ms_per_fused",
                "sched.admit_work_ms_per_admission", "sched.upload_ms_per_fused",
                "dispatch.submit_ms_per_dispatch", "device.idle_span_named_pct",
                "sched.capacity_blocked_pct", "step.admit_sample_share_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _lines(out: str):
    return [json.loads(l) for l in out.splitlines() if l.startswith('{"bench"')]


def test_a_rehearsal_of_the_cell_reads_sound(capsys):
    from benchmark import run

    assert run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "6",
                     "--trace", "1", "--rehearse"]) == 0
    lines = _lines(capsys.readouterr().out)
    check = next(l for l in lines if l["bench"] == "check")
    assert check["ok"] is True and check["max_deficit"] <= 1e-4 and check["positions"] == 32
    assert set(check["prefill_dispatch_kinds"]) == {"fused"} and min(check["reask_hit_tokens"]) == 32
    window = next(l for l in lines if l["bench"] == "window")
    result = lines[-1]["result"]
    assert lines[-1]["bench"] == "rehearsal_end"
    # a rehearsal's short replays may leave a shape to the window (ROADMAP
    # C12): `correct` is the check's verdict but for that
    assert result["correct"] is (window["compiles"] == 0)
    assert result["failed"] == 0 and result["attempted"] > 0
    # the counters reached the readers through the packed fetch
    assert 1 <= result["metrics"]["moe.experts_touched_mean"]["value"] <= 8
    assert 0 <= result["metrics"]["hc.unconverged_pct"]["value"] < 5
    assert result["metrics"]["kv.prefix_hit_pct"]["value"] > 0


def identity_mixing(params):
    """`hc_control`'s fault (b) as WEIGHTS: every unit's H_res logits are +30
    on the diagonal and -30 off it whatever the token (phi's n x n columns
    zero), so the streams never mix and the program needs no switch."""
    import jax.numpy as jnp

    def unit(hp):
        K = hp["b"].shape[-1]
        n = int(round((1 + K) ** 0.5)) - 1
        eye = jnp.where(jnp.eye(n, dtype=bool), 30.0, -30.0).reshape(-1)
        return dict(hp, phi=hp["phi"].at[..., 2 * n:].set(0.0),
                    b=hp["b"].at[..., 2 * n:].set(eye))

    out = dict(params)
    for tree in ("dense_layers", "moe_layers"):
        out[tree] = dict(params[tree], hc_attn=unit(params[tree]["hc_attn"]),
                         hc_ffn=unit(params[tree]["hc_ffn"]))
    return out


def lively(params, gain=4.0):
    """Every layer's projections times `gain` (norms, the units' parameters,
    the embedding and the head as they are).  At the rehearsal's widths
    N(0, 0.02^2) leaves a sub-block's output a tenth of the embedding it is
    added to, so a token's logits follow its own embedding and no wrong unit
    moves an argmax (all five of `hc_control`'s wrong references read a deficit
    of exactly 0 there); at the published widths the sub-blocks outweigh the
    embedding thirty to one.  Times 4 restores that at width 64."""
    def scaled(tree):
        return {k: (v if k.startswith("hc_") else v * gain if v.ndim >= 3 else v)
                for k, v in tree.items()}

    return dict(params, dense_layers=scaled(params["dense_layers"]),
                moe_layers=scaled(params["moe_layers"]))


def test_a_server_whose_streams_never_mix_is_not_correct(monkeypatch, capsys):
    """The server is handed the run's own weights with H_res forced to the
    identity (`hc_control`'s (b)); the check, on the window's own path, holds
    it to the true reference and `correct` is false.  Both sides get `lively`
    weights.  In float32 the sound system reads a deficit of 0 (max <= 1e-4,
    the test above), so the limits here are float32's, not the chip's."""
    from benchmark import reference, run, system

    serve, make_params = system.serve, system.make_params

    def serve_unmixed(params, config, mesh, server, seed, body):
        serve(identity_mixing(params), config, mesh, server, seed, body)

    monkeypatch.setattr(system, "make_params", lambda *a: lively(make_params(*a)))
    monkeypatch.setattr(system, "serve", serve_unmixed)
    ref = reference.load(json.loads(CONFIG.read_text()))
    monkeypatch.setattr(ref, "MAX_DEFICIT", 1e-3)
    monkeypatch.setattr(ref, "MEAN_DEFICIT", 1e-4)
    assert run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "2", "--rehearse"]) == 0
    lines = _lines(capsys.readouterr().out)
    check = next(l for l in lines if l["bench"] == "check")
    assert check["ok"] is False and check["max_deficit"] > check["limits"][0]
    assert set(check["prefill_dispatch_kinds"]) == {"fused"} and min(check["reask_hit_tokens"]) > 0
    assert lines[-1]["result"]["correct"] is False


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_the_file_holds_every_catalog_key_and_only_the_depth_is_reduced():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == NAME)
    raw = json.loads(CONFIG.read_text())
    assert raw["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items() if raw.get(k, "absent") != v) == [
        "first_k_dense_replace", "num_hidden_layers"]
    assert list(raw["reduced"]) == ["num_hidden_layers", "first_k_dense_replace"]
    assert (raw["reduced"]["num_hidden_layers"]["published"], raw["num_hidden_layers"]) == (40, 6)
    assert (raw["reduced"]["first_k_dense_replace"]["published"], raw["first_k_dense_replace"]) == (2, 1)
    for line in ("torch_dtype", "initializer_range", "rope_interleave", "e_score_correction_bias",
                 "streams", "hc_unit", "hc_seeded", "num_nextn_predict_layers", "ep_size",
                 "max_position_embeddings"):
        assert line in raw["assumed"], line
    for key in ("source", "architecture", "reference", "reduced", "assumed", "deployment"):
        assert raw[key], key
    # no width is among the cuts: all 64 experts, the whole vocabulary
    assert (raw["n_routed_experts"], raw["vocab_size"], raw["hidden_size"]) == (64, 131072, 3584)


def test_the_file_maps_and_builds_at_the_rehearsal_size():
    import jax

    from benchmark import run, system

    work = json.loads((ROOT / "benchmark" / "workloads" / f"{CELL}.json").read_text())
    raw = run.merge(json.loads(CONFIG.read_text()), work["rehearse"]["config_overrides"])
    config = system.load_config(raw, run.merge(work["server"], work["rehearse"]["server"]))
    assert (config.hc_mult, config.q_lora_rank, config.dim, config.max_seq_len) == (4, 24, 64, 512)
    assert config.rope_yarn == (64.0, 4096.0, 32.0, 1.0, 1.0)
    mesh = system.build_mesh({}, 1)
    params = system.make_params(config, mesh, 7)
    assert params["moe_layers"]["hc_ffn"]["phi"].shape == (2, 256, 24)
    mixed = identity_mixing(params)
    assert jax.tree.structure(mixed) == jax.tree.structure(params)
    # a key the program does not know is refused, not dropped
    with pytest.raises(SystemExit, match="hc_gate"):
        system.load_config(dict(raw, hc_gate=True), {"max_seq_len": 512})


def test_the_cell_and_its_configuration_are_in_the_benchmark_with_their_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells[CELL] == dict(cells[CELL], config=NAME, traffic="freshdocs-asks", chips=1)
    entry = configs[NAME]
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    assert (ROOT / entry["file"]) == CONFIG
    assert entry["source"] == json.loads(CONFIG.read_text())["source"]
    work = json.loads((ROOT / "benchmark" / "workloads" / f"{CELL}.json").read_text())
    assert work["server"] == {"slots": 16, "max_seq_len": 8192, "decode_chunk": 8,
                              "prefill_budget": 2048, "attn": "auto", "priority_classes": "off"}
    traffic = work["traffic"]
    assert traffic["generator"] == "doc_sessions" and (traffic["clients"], traffic["ramp_s"]) == (16, 4.0)
    assert traffic["document_tokens"] == {"min": 2048, "max": 6144}
    assert traffic["question_tokens"] == {"min": 32, "max": 96}
    assert traffic["answer_tokens"] == {"min": 64, "max": 160}
    assert traffic["asks_per_document"] in (2, 3)
    assert (traffic["interleave"], traffic["cycle"]) == (4, 8)
    assert work["trace"] == {"start_frac": 0.5, "seconds": 3}
    from benchmark.traffic import doc_sessions

    lens = sorted(doc_sessions._spread(traffic["document_tokens"], 8, __import__("random").Random(0)))
    assert lens == [2304, 2816, 3328, 3840, 4352, 4864, 5376, 5888]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["out_tokens_per_s"]["workloads"]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s" and m["unit"] == "%", name
        assert (ROOT / "benchmark" / "metrics" / f"{name}.py").exists(), name
    for name in ("mhcmla_fused_dispatch_roofline", "hc_mix_roofline"):
        assert per_layer[name]["layer"] == "kernels" and per_layer[name]["source"] == "device_trace"
    assert per_layer["hc.unconverged_pct"]["source"] == "program_counter"
    for name in JOINED_LISTS:
        assert CELL in per_layer[name]["workloads"], name


def test_the_reference_loads_and_states_its_limits():
    from benchmark import reference

    mod = reference.load(json.loads(CONFIG.read_text()))
    assert mod.FAULTS == ("sinkhorn_1", "h_res_identity", "h_post_unscaled", "no_yarn_mscale",
                          "no_q_a_norm")
    assert 0 < mod.MEAN_DEFICIT < mod.MAX_DEFICIT
    src = (ROOT / "benchmark" / "references" / "mhc_mla_moe.py").read_text()
    assert "jax_llama_tpu" not in src.split('"""', 2)[2]      # nothing of the program's code
    for word in ("MAX_DEFICIT", "MEAN_DEFICIT", "float8", *mod.FAULTS):
        assert word in mod.__doc__, word


def test_the_counts_of_the_block_are_the_issues():
    """ISSUE 51's arithmetic at the published sizes, worked by hand: attention
    3584x768 + 768x6144 + 3584x576 + 512x8192 + 4096x3584 = 28,409,856; a routed
    expert 3x3584x1024 = 11,010,048; the dense FFN 3x3584x9216 = 99,090,432; a
    unit's phi 14336x24 = 344,064; the head 3584x131072 = 469,762,048."""
    cfg = json.loads(CONFIG.read_text())
    n = rf.sizes(cfg)
    assert n == {"attention": 28409856, "dense_ffn": 99090432, "shared": 11010048,
                 "router": 229376, "expert": 11010048, "head": 469762048, "hc_unit": 344064}
    assert rf.layers(cfg) == (1, 5) and rf.units(cfg) == 12
    assert rf.latent_bytes_per_token(cfg) == 1152
    # (2n + 2) C 2 B = 10 x 3584 x 2 = 71,680 B a token a unit; 12 units x a
    # 2048-token chunk = 1.76 GB (+ phi, float32, once a unit)
    assert rf.hc_mix_bytes_per_token(cfg) == 71680
    assert rf.hc_bytes(cfg, 2048, 1) == 12 * (71680 * 2048 + 344064 * 4) == 1778122752
    # the share's own floor: the carry of 6 layers, 2 x 4 x 3584 x 2 B a token
    assert rf.hc_carry_bytes_per_token(cfg) == 57344
    assert rf.hc_floor_bytes(cfg, 2048, 1) == 6 * 57344 * 2048 + 12 * 344064 * 4 == 721158144
    # sixteen rows at 4,000 tokens, 40 experts a layer touched: 2,997,518,336
    # parameters x 2 B + 6 x 1152 x 64,000 of latent + the units
    assert rf.decode_iter_bytes(cfg, [4000.0] * 16, 200) == (
        2997518336 * 2 + 442368000 + 12 * (71680 * 16 + 344064 * 4)) == 6467682304
    # a 2048-token chunk: 2 x 550,076,416 active parameters a token x 2048 +
    # 6 x 2 x 32 x 320 x 2048 x 2049 / 2 of attention on itself + one head row
    assert rf.chunk_flops(cfg, 2048) == 2.0 * 550076416 * 2048 + 122880 * 2098176 + 2.0 * 469762048
    assert rf.chunk_experts_touched_max(cfg, 2048) == 5 * 64
    with pytest.raises(ValueError, match="mla_moe"):
        rf.sizes({"reference": "mla_moe"})
    with pytest.raises(ValueError, match="mhc_mla_moe"):
        roofline.layer_params(cfg)


def test_the_roofline_counts_on_a_hand_worked_small_case():
    """hidden 8, 2 heads (nope 4, rope 2, v 4), latent 4, query rank 3, 2
    streams, 3 layers (1 dense of width 6), 4 experts of width 2 top-1 + 1
    shared, 16 rows of vocabulary, float32."""
    cfg = {"reference": "mhc_mla_moe", "hidden_size": 8, "vocab_size": 16, "num_attention_heads": 2,
           "kv_lora_rank": 4, "q_lora_rank": 3, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
           "v_head_dim": 4, "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "intermediate_size": 6, "n_routed_experts": 4, "num_experts_per_tok": 1,
           "n_shared_experts": 1, "moe_intermediate_size": 2, "hc_mult": 2,
           "torch_dtype": "float32"}
    # q_a 8x3 + q_b 3x12 + kv_a 8x6 + kv_b 4x16 + o 8x8; phi [2 x 8, 4 + 4]
    assert rf.sizes(cfg) == {"attention": 236, "dense_ffn": 144, "shared": 48, "router": 32,
                             "expert": 48, "head": 128, "hc_unit": 128}
    assert rf.units(cfg) == 6 and rf.latent_bytes_per_token(cfg) == 24
    assert rf.hc_mix_bytes_per_token(cfg) == (2 * 2 + 2) * 8 * 4 == 192
    assert rf.hc_bytes(cfg, 5, 1) == 6 * (192 * 5 + 128 * 4) == 8832
    assert rf.hc_carry_bytes_per_token(cfg) == 2 * 2 * 8 * 4 == 128
    assert rf.hc_floor_bytes(cfg, 5, 1) == 3 * 128 * 5 + 6 * 128 * 4 == 4992
    # two rows at contexts 2 and 10, 3 experts touched
    assert rf.decode_iter_bytes(cfg, [2.0, 10.0], 3) == (
        (3 * 236 + 144 + 2 * (48 + 32) + 128 + 3 * 48) * 4 + 3 * 24 * 12 + 6 * (192 * 2 + 512)) == 11376
    # a 5-token chunk on itself: 15 causal pairs x 2 heads x (4 + 2 + 4) x 2 a layer
    assert rf.chunk_flops(cfg, 5) == 2 * (3 * 236 + 144 + 2 * (48 + 32 + 48) + 6 * 128) * 5 + 1800 + 256 == 20816
    assert rf.chunk_experts_touched_max(cfg, 5) == 8 and rf.chunk_experts_touched_max(cfg, 1) == 2


def _synthetic(cfg, least, d):
    from benchmark import run as run_mod

    rows = [{"id": f"r{i}", "first": 0.0, "last": 100.0, "n_tokens": 0,
             "prompt_tokens": 4000} for i in range(16)]
    return run_mod.Context(
        trace={"modules": [{"program": d["program"], "start_s": 0.0, "seconds": least,
                            "dispatch": dict(d, end=d["start"] + least + 0.002)}]},
        peaks=PEAKS, records=rows, timelines={f"r{i}": {"rids": [i]} for i in range(16)},
        config=cfg, chips=1, dispatches=[], counters0={}, counters1={})


def test_fused_roofline_counts_low_and_reads_under_100():
    """A synthetic dispatch that takes exactly its least time reads 100 % when
    every row and expert is known; unknown rows only lower it; a program
    without the counters, or another block's configuration, reads nothing."""
    from benchmark import run as run_mod

    read = run_mod.load_reader("mhcmla_fused_dispatch_roofline")
    cfg = json.loads(CONFIG.read_text())
    touched = 8 * 5 * 40
    d = {"start": 10.0, "k": 8, "prefill_tokens": 64, "rids": list(range(16)),
         "kind": "fused", "program": "_fused_chunk",
         "moe": {"experts_touched": touched + 5 * 64, "assignments": 0, "layer_calls": 45, "max_load": 0}}
    t_iter, _ = roofline.least_seconds(
        0.0, rf.decode_iter_bytes(cfg, [4000.0] * 16, touched / 8), PEAKS, 1)
    t_chunk, _ = roofline.least_seconds(rf.chunk_flops(cfg, 64), 0.0, PEAKS, 1)
    ctx = _synthetic(cfg, 8 * t_iter + t_chunk, d)
    got = read(ctx)
    assert abs(got["value"] - 100.0) < 1e-6 and got["note"]["rows_counted"] == 16
    ctx.timelines = {}
    assert read(ctx)["value"] < 95.0
    ctx.trace["modules"][0]["dispatch"].pop("moe")
    assert read(ctx) is None
    ctx.config = {"reference": "mla_moe"}
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None


def test_the_new_readers_read_nothing_from_a_program_without_their_sources():
    """Without a trace, without the counters, or for another block's
    configuration every new reader returns None and does not raise (the
    driver lays these files over the parent's checkout for the traced runs)."""
    from benchmark import run as run_mod

    cfg = json.loads(CONFIG.read_text())
    other = json.loads((ROOT / "benchmark" / "configs" / "kanana-2-30b-a3b-instruct-2601.json").read_text())
    for config in (cfg, other):
        ctx = run_mod.Context(
            trace=None, peaks=PEAKS, records=[], timelines={}, config=config, chips=1,
            dispatches=[], counters0={}, counters1={})
        for name in NEW_METRICS:
            assert run_mod.load_reader(name)(ctx) is None, name
    unconverged = run_mod.load_reader("hc.unconverged_pct")
    ctx.counters1 = {"hc_unconverged_total": 3, "hc_units_total": 12000}
    assert abs(unconverged(ctx)["value"] - 0.025) < 1e-12


def test_the_control_holds_the_served_tokens_to_each_wrong_reference(monkeypatch, capsys):
    """`hc_control` after a stubbed run: the reference's `logits` is called
    once a reference with `fault=` set, the verdicts print in order, and the
    exit code is 0 only when the true one passes and every wrong one fails."""
    from benchmark import hc_control, reference, run

    raw = json.loads(CONFIG.read_text())
    faults = reference.load(raw).FAULTS
    calls = []

    def judge(params, cfg, requests, records):
        ref = reference.load(cfg)
        calls.append(ref.logits.keywords["fault"] if hasattr(ref.logits, "keywords") else "run")
        return {"ok": verdict.get(calls[-1], True), "max_deficit": 0.0, "mean_deficit": 0.0,
                "limits": [ref.MAX_DEFICIT, ref.MEAN_DEFICIT]}

    def fake_run(argv):
        reference.judge({}, raw, [], [])
        return 0

    monkeypatch.setattr(reference, "judge", judge)
    monkeypatch.setattr(run, "main", fake_run)
    verdict = dict.fromkeys(faults, False)
    assert hc_control.main([]) == 0
    assert calls == ["run", None, *faults]
    lines = [l for l in _lines(capsys.readouterr().out) if l["bench"] == "hc_control"]
    assert [(l["fault"], l["ok"]) for l in lines] == [(None, True)] + [(f, False) for f in faults]
    verdict["no_q_a_norm"] = True       # limits one wrong reference passes gate nothing of it
    assert hc_control.main([]) == 1
    assert not hasattr(reference.load(raw).logits, "keywords")     # restored
