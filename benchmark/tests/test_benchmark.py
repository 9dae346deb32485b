"""The benchmark's own tests: CPU, seconds each.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import itertools
import json
import re
from pathlib import Path

import pytest

from benchmark import reference, roofline, stats, trace
from benchmark.traffic import doc_sessions, poisson_lognormal

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

CHAT = {"rate_rps": 5.0,
        "prompt_tokens": {"median": 192, "sigma": 0.8, "min": 32, "max": 1024},
        "output_tokens": {"median": 96, "sigma": 0.7, "min": 16, "max": 384}}
DOCS = {"clients": 16, "ramp_s": 4, "document_tokens": {"min": 2048, "max": 3072},
        "question_tokens": {"min": 32, "max": 96}, "answer_tokens": {"min": 16, "max": 48},
        "asks_per_document": 4, "interleave": 4, "cycle": 16}


# -- trace reduction ---------------------------------------------------------

def test_union_is_not_sum():
    assert trace.union_seconds([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4.0)
    assert trace.union_seconds([]) == 0.0
    assert trace.gaps([(0, 2), (1, 3), (5, 6)]) == [(3, 5)]


def test_self_time_takes_children_out():
    evs = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0), ("fusion.2", 5.0, 9.0), ("copy", 11.0, 12.0)]
    own = trace.self_seconds(evs)
    assert own["while"] == pytest.approx(3.0)
    assert own["fusion.1"] == pytest.approx(3.0) and own["copy"] == pytest.approx(1.0)


@pytest.mark.parametrize("name,want", [
    ("jit__paged_decode_chunk(123456789)", "_paged_decode_chunk"),
    ("jit__fused_chunk", "_fused_chunk"),
    ("jit__paged_insert.3", "_paged_insert"),
])
def test_program_name(name, want):
    assert trace.program_name(name) == want


def test_op_name():
    long = ("%fusion.177 = bf16[16,1,2,14336]{3,0,2,1:T(8,128)(2,1)S(1)} fusion(bf16[24,2,4096,14336]"
            "{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.1605), kind=kOutput")
    assert trace.op_name(long) == "%fusion.177 bf16[16,1,2,14336]"
    assert trace.op_name("%copy-start = (bf16[4096,4096]{1,0}, bf16[4096,4096]) copy-start(x)") == "%copy-start bf16[4096,4096]"
    assert trace.op_name("while") == "while"


def _planes():
    ops = [("while", 1.0, 1.4), ("fusion", 1.1, 1.3), ("fusion", 1.6, 1.8), ("fusion", 3.0, 3.5)]
    mods = [("jit__paged_decode_chunk(7)", 1.0, 1.4), ("jit__fused_chunk(8)", 1.6, 1.8),
            ("jit__paged_decode_chunk(7)", 3.0, 3.5)]
    return {
        "/device:TPU:0": {trace.OPS_LINE: ops, trace.MODULES_LINE: mods},
        "/device:TPU:1": {trace.OPS_LINE: [("fusion", 1.0, 2.0)], trace.MODULES_LINE: []},
        "/host:CPU": {"python3": [(trace.SYNC_NAME, 0.5, 0.501)]},
    }


def test_reduce_busy_idle_programs_and_gaps():
    # host clock = trace clock + 100
    disp = [
        {"kind": "decode", "k": 8, "occupancy": 4, "prefill_tokens": 0, "start": 100.9, "end": 101.497},
        {"kind": "fused", "k": 4, "occupancy": 5, "prefill_tokens": 512, "start": 101.55, "end": 101.85},
        {"kind": "decode", "k": 8, "occupancy": 5, "prefill_tokens": 0, "start": 102.9, "end": 103.6},
    ]
    r = trace.reduce(_planes(), disp, sync_host_s=100.5)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(2.5)
    assert r["busy_s_by_device"] == [pytest.approx(1.1), pytest.approx(1.0)]
    assert r["busy_s"] == pytest.approx(1.05)
    assert r["aligned_share"] == 1.0
    assert [m["dispatch"]["kind"] for m in r["modules"]] == ["decode", "fused", "decode"]
    assert r["programs"]["_paged_decode_chunk"] == [2, pytest.approx(0.9)]
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["no dispatch"] == pytest.approx(1.2)      # 1.8 -> 3.0
    assert gaps["after:decode"] == pytest.approx(0.2)     # 1.4 -> 1.6, just behind the first
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion"] == pytest.approx(0.9) and ops["while"] == pytest.approx(0.2)


def test_reduce_without_device_planes_reads_nothing():
    r = trace.reduce({"/host:CPU": {"python3": []}}, [], None)
    assert r["busy_s"] == 0.0 and r["devices"] == 0


def test_per_layer_readers_on_the_reduction():
    from benchmark.run import Context, load_reader

    # a record is its execution plus the host's submit and fetch; the third
    # execution (3.0 -> 3.5) is one the trace's end cut: its record runs on
    disp = [{"kind": "decode", "k": 8, "occupancy": 4, "prefill_tokens": 0, "start": 100.999, "end": 101.402},
            {"kind": "fused", "k": 4, "occupancy": 5, "prefill_tokens": 512, "start": 101.599, "end": 101.802},
            {"kind": "decode", "k": 8, "occupancy": 4, "prefill_tokens": 0, "start": 102.999, "end": 103.802}]
    planes = _planes()
    ctx = Context(trace=trace.reduce(planes, disp, 100.5), dispatches=disp, t0=100.0, seconds=5.0,
                  stats=stats)
    assert load_reader("step.decode_iter_ms")(ctx) == pytest.approx(1000 * 0.4 / 8)
    assert load_reader("step.prefill_ms_per_ktok")(ctx) == pytest.approx(1e6 * 0.2 / 512)
    assert load_reader("sched.occupancy_mean")(ctx) == pytest.approx(13 / 3)
    ctx.trace = None
    assert load_reader("step.decode_iter_ms")(ctx) is None


def test_a_module_joins_only_a_record_of_its_program_that_holds_it():
    # A one-iteration decode chunk (1.85 -> 1.9) right behind a fused chunk.
    # With the clocks joined 0.08 s off, its midpoint falls into the fused
    # record: it must stay unjoined, not take the neighbour's k of 8.
    planes = _planes()
    planes["/device:TPU:0"][trace.MODULES_LINE] = [
        ("jit__fused_chunk(8)", 1.6, 1.8), ("jit__paged_decode_chunk(7)", 1.85, 1.9)]
    disp = [
        {"kind": "fused", "k": 8, "program": "_fused_chunk", "prefill_tokens": 512, "start": 101.55, "end": 101.83},
        {"kind": "decode", "k": 1, "program": "_paged_decode_chunk", "prefill_tokens": 0, "start": 101.849, "end": 101.902},
    ]
    good = trace.reduce(planes, disp, sync_host_s=100.5)
    assert [m["dispatch"] and m["dispatch"]["k"] for m in good["modules"]] == [8, 1]
    off = trace.reduce(planes, disp, sync_host_s=100.42)
    assert [m["dispatch"] for m in off["modules"]][1] is None
    assert off["aligned_share"] < 1.0
    # the same program, a longer neighbour: joined, but it does not fill it
    disp[0]["program"] = "_paged_decode_chunk"
    planes["/device:TPU:0"][trace.MODULES_LINE][0] = ("jit__paged_decode_chunk(9)", 1.6, 1.8)
    off = trace.reduce(planes, disp, sync_host_s=100.42)
    assert off["modules"][1]["dispatch"]["k"] == 8
    assert trace.steps(off, ("_paged_decode_chunk",)) == []   # 0.05 s of a record of 0.28 s
    # joined right, the fused execution still leaves 0.08 s of its record empty
    assert [m["seconds"] for m in trace.steps(good, ("_paged_decode_chunk", "_fused_chunk"))] == [pytest.approx(0.05)]


def test_sync_mark_by_number():
    planes = _planes()
    planes["/host:CPU"]["python3"] = [(trace.SYNC_NAME + "_0", 0.4, 0.401), (trace.SYNC_NAME + "_3", 0.5, 0.501)]
    assert trace.find_sync(planes, 3) == 0.5 and trace.find_sync(planes, 0) == 0.4
    assert trace.find_sync(planes, 5) is None


def test_decode_roofline_counts_low_and_reads_under_100():
    from benchmark.run import Context, load_reader

    cfg = json.load(open(ROOT / "benchmark" / "configs" / "mistral-7b-v0.3.json"))
    peaks = json.load(open(ROOT / "benchmark" / "peaks.json"))["TPU v5 lite"]
    w = roofline.decode_weight_bytes(cfg) / peaks["hbm_bytes_per_s"]
    # one 8-iteration chunk that took 8 x 1.1 x the weights' time, two rows riding
    planes = {"/device:TPU:0": {trace.OPS_LINE: [("fusion", 1.0, 1.0 + 8.8 * w)],
                                trace.MODULES_LINE: [("jit__paged_decode_chunk(7)", 1.0, 1.0 + 8.8 * w)]},
              "/host:CPU": {"python3": [(trace.SYNC_NAME, 0.5, 0.501)]}}
    disp = [{"kind": "decode", "k": 8, "program": "_paged_decode_chunk", "prefill_tokens": 0,
             "rids": [5, 6, 7], "start": 100.999, "end": 101.001 + 8.8 * w}]
    records = [
        {"id": "a", "first": 100.0, "last": 102.0, "n_tokens": 100, "prompt_tokens": 300},
        {"id": "b", "first": 101.0005, "last": 103.0, "n_tokens": 50, "prompt_tokens": 900},  # first token not out yet
    ]
    timelines = {"a": {"rids": [5]}, "b": {"rids": [6]}}  # rid 7 is nobody's: counts nothing
    ctx = Context(trace=trace.reduce(planes, disp, 100.5), records=records, timelines=timelines,
                  config=cfg, peaks=peaks, chips=1)
    got = load_reader("decode_iter_roofline")(ctx)
    kv = roofline.kv_bytes_per_token(cfg) * (300 + 100 * 0.9995 / 2.0 - 16) / peaks["hbm_bytes_per_s"]
    assert got["value"] == pytest.approx(100 * (w + kv) / (1.1 * w), rel=1e-3)
    assert got["value"] < 100 and got["note"]["rows_counted"] == 1
    # the counts are the dense block's: a configuration of another block gets none
    for other in (dict(cfg, reference="latent_moe"), {k: v for k, v in cfg.items() if k != "reference"}):
        with pytest.raises(ValueError, match="dense_gqa"):
            roofline.decode_iter_bytes(other, [100.0])
        with pytest.raises(ValueError, match="dense_gqa"):
            roofline.decode_iter_flops(other, [100.0])


# -- traffic -----------------------------------------------------------------

def test_chat_traffic_is_seeded_and_clipped():
    a = poisson_lognormal.generate(CHAT, 3000000019, 40, 32768)
    b = poisson_lognormal.generate(CHAT, 3000000019, 40, 32768)
    c = poisson_lognormal.generate(CHAT, 5, 40, 32768)
    assert a == b and a != c
    reqs = a["requests"]
    assert a["loop"] == "open" and len(reqs) == 200
    assert all(32 <= len(r["prompt"]) <= 1024 and 16 <= r["max_new_tokens"] <= 384 for r in reqs)
    assert all(0 <= t < 32768 for r in reqs for t in r["prompt"])
    ats = [r["at"] for r in reqs]
    assert ats == sorted(ats) and 0 < ats[0] and ats[-1] < 40
    # every seed offers the same work in another order
    key = lambda t: sorted(len(r["prompt"]) for r in t["requests"])  # noqa: E731
    assert key(a) == key(c)
    assert sorted(r["max_new_tokens"] for r in reqs) == sorted(r["max_new_tokens"] for r in c["requests"])
    med = sorted(len(r["prompt"]) for r in reqs)[100]
    assert 180 <= med <= 205


def test_doc_sessions_interleave_and_clips():
    g = doc_sessions.generate(DOCS, 9, 40, 32768)
    reqs = list(itertools.islice(g["requests"], 64))
    again = list(itertools.islice(doc_sessions.generate(DOCS, 9, 40, 32768)["requests"], 64))
    assert reqs == again and g["loop"] == "closed" and g["clients"] == 16
    assert [(r["document"], r["ask"]) for r in reqs[:16]] == [
        (d, a) for a in range(4) for d in range(4)]
    assert [r["document"] for r in reqs[16:20]] == [4, 5, 6, 7]
    by_doc = {}
    for r in reqs:
        by_doc.setdefault(r["document"], []).append(r)
    for asks in by_doc.values():
        assert len(asks) == 4
        doc_len = min(len(r["prompt"]) for r in asks) - 96
        shared = asks[0]["prompt"][:max(doc_len, 2048)]
        assert all(r["prompt"][:len(shared)] == shared for r in asks)      # one document
        assert len({tuple(r["prompt"][-32:]) for r in asks}) == 4            # four questions
        assert all(2048 + 32 <= len(r["prompt"]) <= 3072 + 96 for r in asks)
        assert all(16 <= r["max_new_tokens"] <= 48 for r in asks)
    other = list(itertools.islice(doc_sessions.generate(DOCS, 10, 40, 32768)["requests"], 64))
    assert sorted(len(r["prompt"]) - 0 for r in other) != [] and other != reqs


# -- percentile and per-token arithmetic ------------------------------------

def test_percentile():
    assert stats.percentile([], 95) is None
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile(range(1, 102), 50) == 51
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)


def test_ttft_counts_from_due_and_a_failure_is_a_miss():
    ok = {"ok": True, "due": 10.0, "sent": 10.4, "first": 10.5, "last": 12.5, "n_tokens": 11}
    failed = {"ok": False, "due": 11.0, "sent": 11.0, "first": None, "last": None, "n_tokens": 0,
              "status": 503}
    assert stats.ttft_ms(ok, 40) == pytest.approx(500.0)     # not 100: the late send counts
    assert stats.ttft_ms(failed, 40) == 40000.0
    assert stats.tpot_ms(ok, 40) == pytest.approx(200.0)
    assert stats.tpot_ms(failed, 40) == 40000.0
    assert stats.tpot_ms(dict(ok, n_tokens=1), 40) is None
    recs = [ok] * 19 + [failed]
    assert stats.percentile(stats.ttfts(recs, 40), 50) == pytest.approx(500.0)
    assert stats.percentile(stats.ttfts(recs, 40), 100) == 40000.0
    assert stats.completed_tokens(recs, 10, 12) == 0 and stats.completed_tokens(recs, 10, 13) == 19 * 11
    assert stats.phase_counts(recs) == {"sent": 20, "succeeded": 19, "refused": 1, "hung": 0, "failed": 0}


# -- the plain reference -----------------------------------------------------

TINY = {"hidden_size": 32, "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "num_hidden_layers": 4, "vocab_size": 256,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "reference": "dense_gqa"}


@pytest.fixture(scope="module")
def tiny():
    import jax
    import numpy as np

    from jax_llama_tpu import forward, get_config, init_params

    config = get_config("tiny")
    params = init_params(jax.random.PRNGKey(3), config)
    toks = np.random.RandomState(0).randint(0, 256, size=(2, 24)).astype(np.int32)
    pos = np.tile(np.arange(24, dtype=np.int32)[None], (2, 1))
    want = np.asarray(forward(params, toks, pos, config)[0])
    # greedy continuations of the first 16 tokens, by the program, one full
    # forward per token
    seq = toks[:, :16]
    for _ in range(8):
        p = np.tile(np.arange(seq.shape[1], dtype=np.int32)[None], (2, 1))
        nxt = np.asarray(forward(params, seq, p, config)[0])[:, -1].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], axis=1)
    return params, toks, want, seq[:, 16:].tolist()


def test_reference_agrees_with_the_program(tiny):
    import numpy as np

    params, toks, want, served = tiny
    assert TINY["intermediate_size"] == params["layers"]["down"].shape[1]
    got = np.asarray(reference.load(TINY).logits(params, toks, TINY, 0))
    assert np.abs(got - want).max() < 2e-4
    d = reference.deficits(params, toks[:, :16].tolist(), served, TINY)
    assert d.shape == (2, 8) and d.max() < 1e-3


@pytest.mark.parametrize("fault", [{"rope_theta": 500000.0}, {"drop_kv_head": True}])
def test_reference_fails_a_wrong_rope_base_or_a_dropped_kv_head(tiny, fault):
    """The served side is the program; the reference is given the fault.  Held
    to the limits scaled to these logits: tiny's largest logit stands less far
    above the mean than the real models' (~4.2 there)."""
    import numpy as np

    params, toks, want, served = tiny
    cfg = dict(TINY)
    if fault.get("drop_kv_head"):
        import jax

        params = jax.tree_util.tree_map(lambda x: x, params)
        qkv = np.array(params["layers"]["qkv"])
        qkv[:, 1] = qkv[:, 0]                            # KV head 1 reads head 0's weights
        params = dict(params, layers=dict(params["layers"], qkv=qkv))
    else:
        cfg.update(fault)
    d = reference.deficits(params, toks[:, :16].tolist(), served, cfg)
    spread = want.max(-1).mean() - want.mean()
    ref = reference.load(TINY)
    assert d.max() > ref.MAX_DEFICIT * spread / 4.2
    assert d.mean() > ref.MEAN_DEFICIT * spread / 4.2


@pytest.mark.parametrize("cast,passes", [
    ("bfloat16", True), ("float8_e4m3fn", False), ("float8_e5m2", False),
])
def test_reference_limits_pass_bf16_and_fail_eight_bits(tiny, cast, passes):
    """Weights rounded to bfloat16 (what is served) stay inside both limits;
    weights rounded to eight bits are a different result and fail both."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax_llama_tpu import forward, get_config

    params, toks, want, _ = tiny
    config = get_config("tiny")
    rounded = jax.tree_util.tree_map(
        lambda x: x.astype(getattr(jnp, cast)).astype(x.dtype) if x.ndim > 1 else x, params)
    seq = np.random.RandomState(1).randint(0, 256, size=(8, 16)).astype(np.int32)
    prompts = seq.tolist()
    for _ in range(16):
        p = np.tile(np.arange(seq.shape[1], dtype=np.int32)[None], (seq.shape[0], 1))
        nxt = np.asarray(forward(rounded, seq, p, config)[0])[:, -1].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], axis=1)
    d = reference.deficits(params, prompts, seq[:, 16:].tolist(), TINY)
    scale = (want.max(-1).mean() - want.mean()) / 4.2
    ref = reference.load(TINY)
    inside = d.max() <= ref.MAX_DEFICIT * scale and d.mean() <= ref.MEAN_DEFICIT * scale
    assert inside == passes, (d.max(), d.mean(), scale)
    if not passes:
        assert d.max() > ref.MAX_DEFICIT * scale and d.mean() > ref.MEAN_DEFICIT * scale


def test_check_requests_reask_shares_a_prefix():
    spec = {"prompts": 3, "prompt_tokens": 40, "shared_tokens": 32, "new_tokens": 4}
    fresh, reask = reference.check_requests(spec, 7, 512)
    assert (fresh, reask) == reference.check_requests(spec, 7, 512)
    assert len(fresh) == len(reask) == 3
    for f, r in zip(fresh, reask):
        assert len(f["prompt"]) == len(r["prompt"]) == 40
        assert f["prompt"][:32] == r["prompt"][:32] and f["prompt"][32:] != r["prompt"][32:]
    assert len({q["id"] for q in fresh + reask}) == 6


# -- the seam: a configuration file -> the program's configuration, its reference

MISTRAL = ROOT / "benchmark" / "configs" / "mistral-7b-v0.3.json"
CELLS = [w["name"] for w in BENCH["workloads"]]


def _server(cell):
    return json.loads((ROOT / "benchmark" / "workloads" / f"{cell}.json").read_text())["server"]


@pytest.mark.parametrize("cell", CELLS)
def test_load_config_is_the_old_map(cell):
    """What `system.load_config` gave before the map became strict, written
    out by hand: nine fields, the two types, the cell's length and kernel."""
    from benchmark import system
    from jax_llama_tpu.config import LLaMAConfig

    raw, server = json.loads(MISTRAL.read_text()), _server(cell)
    want = LLaMAConfig(
        dim=raw["hidden_size"], n_layers=raw["num_hidden_layers"], n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"], intermediate_size=raw["intermediate_size"],
        vocab_size=raw["vocab_size"], rope_theta=raw["rope_theta"], rms_norm_eps=raw["rms_norm_eps"],
        tie_word_embeddings=raw["tie_word_embeddings"], dtype="bfloat16", param_dtype="bfloat16",
        max_seq_len=server["max_seq_len"], attn_impl=server["attn"],
    )
    assert system.load_config(raw, server) == want
    assert (want.dim, want.n_layers, want.kv_heads, want.head_dim, want.ffn_dim) == (4096, 24, 8, 128, 14336)


@pytest.mark.parametrize("key,value,named", [
    ("n_routed_experts", 128, "n_routed_experts"),
    ("kv_lora_rank", 512, "kv_lora_rank"),
    ("first_k_dense_replace", 1, "first_k_dense_replace"),
    ("sliding_window", 4096, "sliding_window"),
    ("head_dim", 64, "head_dim"),
    ("torch_dtype", "float16", "torch_dtype"),
    ("rope_theta", None, "rope_theta"),          # a key of the map taken out
])
def test_a_key_the_map_does_not_understand_is_refused_by_name(key, value, named):
    from benchmark import system

    raw = json.loads(MISTRAL.read_text())
    if value is None:
        del raw[key]
    else:
        raw[key] = value
    with pytest.raises(SystemExit, match=named):
        system.load_config(raw, _server(CELLS[0]))


def test_published_keys_give_the_fields_and_bookkeeping_is_not_a_key():
    from benchmark import published, system

    raw = json.loads(MISTRAL.read_text())
    keys = {k: v for k, v in raw.items() if k not in system._BOOKKEEPING}
    got = published.from_published(keys, max_seq_len=2048, attn_impl="auto")
    assert (got.dim, got.n_heads, got.kv_heads, got.ffn_dim, got.vocab_size) == (4096, 32, 8, 14336, 32768)
    assert (got.rope_theta, got.rms_norm_eps, got.tie_word_embeddings) == (1e6, 1e-5, False)
    assert (got.dtype, got.param_dtype, got.max_seq_len, got.attn_impl) == ("bfloat16", "bfloat16", 2048, "auto")
    with pytest.raises(ValueError, match="'source'"):      # the file's own keys are stripped by the caller
        published.from_published(raw, max_seq_len=2048, attn_impl="auto")
    # head_dim is not a key of every published file; absent, it is hidden / heads
    assert published.from_published({k: v for k, v in keys.items() if k != "head_dim"},
                                    max_seq_len=2048, attn_impl="auto") == got


def test_the_programs_own_map_is_used_once_it_has_one(monkeypatch):
    from benchmark import system
    from jax_llama_tpu import config as program

    seen = {}

    def from_published(raw, *, max_seq_len, attn_impl):
        seen.update(raw=raw, max_seq_len=max_seq_len, attn_impl=attn_impl)
        if "n_routed_experts" not in raw:
            raise ValueError("n_routed_experts is missing")
        return program.tiny()

    monkeypatch.setattr(program, "from_published", from_published, raising=False)
    raw = dict(json.loads(MISTRAL.read_text()), n_routed_experts=128)
    assert system.load_config(raw, {"max_seq_len": 512}) == program.tiny()
    assert seen["max_seq_len"] == 512 and seen["attn_impl"] == "auto"
    assert "n_routed_experts" in seen["raw"] and not set(seen["raw"]) & set(system._BOOKKEEPING)
    with pytest.raises(SystemExit, match="n_routed_experts"):
        system.load_config(json.loads(MISTRAL.read_text()), {"max_seq_len": 512})


def test_a_reference_is_added_as_a_file(tiny, tmp_path, monkeypatch):
    """A second reference, written here into a `references` directory, is
    found through the configuration's `reference` key alone.  It is the dense
    block without its rope, so the program's own tokens fail it, and it
    brings limits of its own."""
    params, toks, want, served = tiny
    src = (reference.REFERENCES / "dense_gqa.py").read_text()
    assert "    q, k = _rope(q, theta), _rope(k, theta)\n" in src
    src = src.replace("    q, k = _rope(q, theta), _rope(k, theta)\n", "")
    src = src.replace("MAX_DEFICIT = 0.15", "MAX_DEFICIT = 0.25").replace("MEAN_DEFICIT = 0.005", "MEAN_DEFICIT = 0.01")
    (tmp_path / "no_rope.py").write_text(src)
    monkeypatch.setattr(reference, "REFERENCES", tmp_path)
    cfg = dict(TINY, reference="no_rope")
    records = [{"ok": True, "tokens": s, "error": None, "status": 200} for s in served]
    requests = [{"prompt": p} for p in toks[:, :16].tolist()]
    got = reference.judge(params, cfg, requests, records)
    assert got["ok"] is False and got["limits"] == [0.25, 0.01] and got["positions"] == 16
    assert got["max_deficit"] > 0.25 and got["argmax_agree"] < 1.0
    # the same tokens under the reference the program's block has
    (tmp_path / "dense_gqa.py").write_text((ROOT / "benchmark" / "references" / "dense_gqa.py").read_text())
    got = reference.judge(params, TINY, requests, records)
    assert got["max_deficit"] < 1e-3 and got["limits"] == [0.15, 0.005]


@pytest.mark.parametrize("cfg,says", [
    ({k: v for k, v in TINY.items() if k != "reference"}, "names no reference"),
    (dict(TINY, reference="latent_moe"), "references/latent_moe.py"),
])
def test_a_missing_reference_is_refused_with_the_path(cfg, says):
    with pytest.raises(SystemExit, match=says):
        reference.load(cfg)


def test_a_reference_without_its_limits_is_refused(tmp_path, monkeypatch):
    (tmp_path / "bare.py").write_text("def logits(params, tokens, cfg, first):\n    return None\n")
    monkeypatch.setattr(reference, "REFERENCES", tmp_path)
    with pytest.raises(SystemExit, match="MAX_DEFICIT"):
        reference.load({"reference": "bare"})


def test_a_run_that_serves_other_weights_is_not_correct(monkeypatch, capsys):
    """The whole run of a cell but the harness's look for a chip (a rehearsal:
    CPU, tiny size, about two minutes), with the timed path broken underneath:
    the server is handed the weights of another seed, so every token it
    produces is another model's.  The check must say so, on the window's own
    path, and `correct` must come out false."""
    from benchmark import run, system

    serve = system.serve

    def serve_other_weights(params, config, mesh, server, seed, body):
        serve(system.make_params(config, mesh, seed + 1), config, mesh, server, seed, body)

    monkeypatch.setattr(system, "serve", serve_other_weights)
    assert run.main(["--workload", CELLS[0], "--seed", "2147483659", "--seconds", "2", "--rehearse"]) == 0
    said = capsys.readouterr()
    lines = [json.loads(l) for l in said.out.splitlines() if l.startswith('{"bench"')]
    check = next(l for l in lines if l["bench"] == "check")
    assert check["ok"] is False and check["max_deficit"] > check["limits"][0]
    assert set(check["prefill_dispatch_kinds"]) == {"fused"} and min(check["reask_hit_tokens"]) > 0
    assert lines[-1]["bench"] == "rehearsal_end" and lines[-1]["result"]["correct"] is False
    # standard error ends with each number compared beside its limit
    tail = [l for l in said.err.splitlines() if l.strip()][-10:]
    assert all(l.startswith("compared: ") for l in tail) and tail[-1] == "compared: correct = False"
    assert tail[0] == f"compared: check max_deficit = {check['max_deficit']}  limit <= {check['limits'][0]}"
    assert "compared: window compiles = 0  limit == 0" in tail


# -- BENCHMARK.json ----------------------------------------------------------

def test_names_units_and_files():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([m["name"] for m in metrics] + [w["name"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]] + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        mover = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(mover.get("workloads", cells))
    for m in metrics:
        assert (ROOT / "benchmark" / "metrics" / f"{m['name']}.py").exists(), m["name"]
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        work = json.loads((ROOT / "benchmark" / "workloads" / f"{w['name']}.json").read_text())
        assert (ROOT / "benchmark" / "traffic" / f"{work['traffic']['generator']}.py").exists()
        assert any(c["name"] == w["config"] for c in BENCH["configs"])
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).exists() and len(c["why"]) <= 200
        raw = json.loads((ROOT / c["file"]).read_text())
        assert raw["source"] == c["source"] and set(raw["reduced"]) == set(c["reduced"])
        ref = reference.load(raw)
        assert ref.__file__ == str(ROOT / "benchmark" / "references" / f"{raw['reference']}.py")
        assert callable(ref.logits) and 0 < ref.MEAN_DEFICIT < ref.MAX_DEFICIT and ref.__doc__
    assert all(p in "benchmark.run" or not (ROOT / p).exists() for p in BENCH["command"][1:])


def test_run_py_names_no_cell_configuration_or_metric():
    src = (ROOT / "benchmark" / "run.py").read_text()
    names = ([m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["name"] for w in BENCH["workloads"]] + [c["name"] for c in BENCH["configs"]])
    word = lambda n: re.search(r"(?<![\w.\-])" + re.escape(n) + r"(?![\w.\-])", src)  # noqa: E731
    assert [n for n in names if word(n)] == []


def test_the_harness_names_no_published_key_of_a_block():
    """`system.py`, `reference.py` and `run.py` know no block: the keys of one
    are read by its reference and mapped by the program (`published.py` until
    the program has the map)."""
    from benchmark import published

    keys = (set(published._FIELDS) | set(published._OTHER) | {
        "n_routed_experts", "kv_lora_rank", "first_k_dense_replace", "num_experts_per_tok"}
    ) - {"vocab_size"}      # `run.py` reads the program's own `config.vocab_size` to draw token ids
    for name in ("system.py", "reference.py", "run.py"):
        src = (ROOT / "benchmark" / name).read_text()
        assert [k for k in sorted(keys) if re.search(r"(?<![\w])" + re.escape(k) + r"(?![\w])", src)] == [], name
        assert "models.llama" not in src and "LLaMAConfig(" not in src, name

