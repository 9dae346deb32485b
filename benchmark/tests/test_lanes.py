"""The four readers of `benchmark/lanes.py`: CPU, a written trace file.

    python -m pytest benchmark/tests/test_lanes.py -q -p no:cacheprovider
"""

import json
from pathlib import Path

import pytest

from benchmark import hostspans, lanes

ROOT = Path(__file__).resolve().parents[2]
NAMES = ("step.unscoped_share_pct", "step.decode_lane_share_pct",
         "step.chunk_ms_per_ktok", "step.fused_decode_iter_ms")
FUSED, DECODE = "jit(_fused_chunk)/jit(main)/", "jit(_paged_decode_chunk)/jit(main)/"

# (tf_op, start us, duration us) on the `XLA Ops` line; a `while` holds its body.
OPS = [
    # a fused execution behind a mixed pass, [0, 1000)
    (FUSED + "lane.chunk/cache.gather/jit(_take)/gather:", 0, 100),
    (FUSED + "lane.chunk/lane.mixed/while:", 100, 500),                      # 20 us its own
    (FUSED + "lane.chunk/lane.mixed/while/body/dense.ffn/dot_general:", 100, 300),
    (FUSED + "lane.chunk/lane.mixed/while/body/dense.attention/dot_general:", 400, 180),
    (FUSED + "lane.chunk/lane.mixed/head/dot_general:", 600, 50),
    (FUSED + "lane.chunk/cache.land/cache.write/dynamic_update_slice:", 650, 50),
    (FUSED + "lane.decode/while:", 700, 300),                                # 10 us its own
    (FUSED + "lane.decode/while/body/dense.ffn/dot_general:", 700, 200),
    (FUSED + "lane.decode/while/body/sample/argmax:", 900, 90),
    # a fused execution in two passes, [2000, 3500)
    (FUSED + "lane.chunk/cache.gather/jit(_take)/gather:", 2000, 200),
    (FUSED + "lane.chunk/while/body/moe.experts/gmm:", 2200, 800),
    (FUSED + "lane.chunk/admit.sample/cond/branch_1_fun/head/dot_general:", 3000, 100),
    (FUSED + "lane.chunk/cache.land/dynamic_slice:", 3100, 100),
    (FUSED + "lane.decode/while/body/moe.experts/gmm:", 3200, 300),
    # a decode dispatch, [4000, 4400)
    (DECODE + "lane.decode/while/body/dense.ffn/dot_general:", 4000, 400),
    # a fused execution the profiler's stop cut, [5000, 5100)
    (FUSED + "lane.chunk/cache.gather/jit(_take)/gather:", 5000, 100),
    # an insert program's draw, and a copy the compiler made
    ("jit(_paged_insert)/jit(main)/sample/argmax:", 6000, 50),
    ("", 6100, 50),
]
MODULES = [("jit__fused_chunk(7)", 0, 1000), ("jit__fused_chunk(7)", 2000, 1500),
           ("jit__paged_decode_chunk(3)", 4000, 400), ("jit__fused_chunk(7)", 5000, 100),
           ("jit__paged_insert(9)", 6000, 50)]


def _record(start_us, dur_us, **fields):
    """A dispatch record around an execution: 1 ms of submit and fetch."""
    return dict({"start": (start_us - 500) * 1e-6, "end": (start_us + dur_us + 500) * 1e-6,
                 "kind": "fused", "occupancy": 2, "prefill_tokens": 0}, **fields)


RECORDS = [
    _record(0, 1000, k=8, merged_rows=3, prefill_tokens=512, occupancy=4),
    _record(2000, 1500, k=2, prefill_tokens=2048),
    _record(4000, 400, kind="decode", k=8),
    # the cut one: its record lasted 50 ms, the traced part of it 0.1 ms
    dict(_record(5000, 100, k=8, merged_rows=0, prefill_tokens=512), end=(5000 + 50000) * 1e-6),
    None,
]


def _write_trace(path, ops, modules=MODULES):
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "tf_op"
    line = plane.lines.add(name="XLA Ops", timestamp_ns=0)
    for i, (tf_op, start_us, dur_us) in enumerate(ops, 1):
        md = plane.event_metadata[i]
        md.id, md.name = i, f"%fusion.{i} = bf16[8,128]{{1,0}} fusion(...)"
        md.stats.add(metadata_id=1, str_value=tf_op)
        line.events.add(metadata_id=i, offset_ps=int(start_us * 1e6), duration_ps=int(dur_us * 1e6))
    mods = plane.lines.add(name="XLA Modules", timestamp_ns=0)
    for j, (name, start_us, dur_us) in enumerate(modules, len(ops) + 1):
        md = plane.event_metadata[j]
        md.id, md.name = j, name
        mods.events.add(metadata_id=j, offset_ps=int(start_us * 1e6), duration_ps=int(dur_us * 1e6))
    path.write_bytes(space.SerializeToString())
    return str(path)


def _reduced(modules=MODULES, records=RECORDS):
    """What `trace.reduce` hands the readers of these executions."""
    from benchmark import trace

    return {"modules": [
        {"program": trace.program_name(name), "start_s": s * 1e-6, "seconds": d * 1e-6, "dispatch": rec}
        for (name, s, d), rec in zip(modules, records)]}


def _read(monkeypatch, name, path, traced=True):
    from benchmark import run as run_mod

    monkeypatch.setattr(hostspans, "newest_xplane", lambda out: path)
    ctx = run_mod.Context(trace=_reduced() if traced else None, config={}, chips=1,
                          server={"prefill_budget": 512})
    return run_mod.load_reader(name)(ctx)


@pytest.fixture
def traced(tmp_path):
    return _write_trace(tmp_path / "lanes.xplane.pb", OPS)


def test_a_path_has_its_last_lane_and_its_innermost_leaf_scope():
    assert lanes.lane_and_leaf(FUSED + "lane.chunk/lane.mixed/head/dot_general:") == ("mixed", "head")
    assert lanes.lane_and_leaf(FUSED + "lane.chunk/admit.sample/cond/head/dot:") == ("chunk", "head")
    assert lanes.lane_and_leaf(FUSED + "lane.decode/while/body/ssm.mix/ssm.step/mul:") == ("decode", "ssm.step")
    assert lanes.lane_and_leaf(FUSED + "lane.decode/while:") == ("decode", "")
    assert lanes.lane_and_leaf("jit(_paged_suffix_insert)/jit(main)/cache.land/cache.write/dus:") == (
        "insert", "cache.write")
    assert lanes.lane_and_leaf("jit(_scatter_rows)/scatter:") == ("none", "")
    assert lanes.lane_and_leaf("") == ("none", "")
    # a scope is a whole path element: `headroom` is no `head`
    assert lanes.lane_and_leaf(FUSED + "lane.decoder/headroom/add:") == ("none", "")


def test_the_unscoped_share_lists_what_has_no_leaf_scope(traced, monkeypatch):
    """The two `while` shells' own 30 us and the compiler's copy, of 3,100 us;
    lanes and scopes each add up to the busy time."""
    got = _read(monkeypatch, NAMES[0], traced)
    assert got["value"] == pytest.approx(100.0 * 80 / 3100)
    note = got["note"]
    assert note["busy_self_s"] == pytest.approx(3100e-6)
    assert sum(note["seconds_by_scope"].values()) == pytest.approx(3100e-6)
    assert note["seconds_by_scope"]["head"] == pytest.approx(150e-6)       # admit.sample's too
    assert note["seconds_by_scope"]["cache.write"] == pytest.approx(50e-6)  # inside cache.land
    assert note["seconds_by_scope"]["cache.land"] == pytest.approx(100e-6)
    assert note["unscoped_ops"] == pytest.approx(
        {"%fusion.18 bf16[8,128]": 50e-6, "%fusion.2 bf16[8,128]": 20e-6, "%fusion.7 bf16[8,128]": 10e-6})


def test_the_decode_lane_is_both_programs_scans_and_the_table_splits_a_scope(traced, monkeypatch):
    got = _read(monkeypatch, NAMES[1], traced)
    assert got["value"] == pytest.approx(100.0 * 1000 / 3100)
    lanes_s = got["note"]["seconds_by_lane"]
    assert lanes_s == pytest.approx({"chunk": 1450e-6, "decode": 1000e-6, "mixed": 550e-6,
                                     "insert": 50e-6, "none": 50e-6})
    assert sum(lanes_s.values()) == pytest.approx(got["note"]["busy_self_s"])
    table = got["note"]["seconds_by_lane_and_scope"]
    assert table["chunk|moe.experts"] == pytest.approx(800e-6)
    assert table["decode|moe.experts"] == pytest.approx(300e-6)
    assert table["mixed|dense.ffn"] == pytest.approx(300e-6)
    assert table["decode|dense.ffn"] == pytest.approx(600e-6)
    assert table["mixed|"] == pytest.approx(20e-6) and table["insert|sample"] == pytest.approx(50e-6)


def test_the_chunk_costs_its_lanes_and_not_the_decode_iterations(traced, monkeypatch):
    """0.7 ms of chunk and mixed pass for 512 tokens, 1.2 ms for 2,048; the
    cut execution is held and not admitted; the old reader of the same
    trace divides the whole executions by the same tokens."""
    got = _read(monkeypatch, NAMES[2], traced)
    assert got["value"] == pytest.approx(1e3 * 1.9 / 2560)
    note = got["note"]
    assert (note["held"], note["admitted"], note["merged"]) == (3, 2, 1)
    big = [1, 2048, pytest.approx(1e3 * 1.2 / 2048), pytest.approx(1.2)]
    small = [1, 512, pytest.approx(1e3 * 0.7 / 512), pytest.approx(0.7)]
    assert note["by_k"] == {"2": big, "8": small}
    assert note["by_chunk"] == {"gt1024": big, "le1024": small}
    assert note["full"] == small     # the chunks of exactly the server's budget
    old = _read(monkeypatch, "step.prefill_ms_per_ktok", traced)
    assert old == pytest.approx(1e3 * 2.5 / 2560)


def test_a_fused_decode_iteration_counts_k_less_the_mixed_one(traced, monkeypatch):
    got = _read(monkeypatch, NAMES[3], traced)
    assert got["value"] == pytest.approx(0.6 / 9)
    assert got["note"]["by_k"] == {"2": [1, 2, pytest.approx(0.15)], "8": [1, 7, pytest.approx(0.3 / 7)]}
    assert got["note"]["occupancy_mean"] == pytest.approx(3.0)
    assert (got["note"]["held"], got["note"]["admitted"]) == (3, 2)


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_program_and_an_untraced_run_read_nothing(tmp_path, monkeypatch, traced, name):
    """The driver lays these files over the parent's checkout for the traced
    runs: a program without `lane.*` reads None, never 0 and never a raise;
    so do a `--trace 0` run and a run that left no trace file."""
    parent = [(tf_op.replace("lane.chunk/", "").replace("lane.mixed/", "").replace("lane.decode/", ""), s, d)
              for tf_op, s, d in OPS]
    path = _write_trace(tmp_path / "parent.xplane.pb", parent)
    assert _read(monkeypatch, name, path) is None
    assert _read(monkeypatch, name, traced, traced=False) is None
    assert _read(monkeypatch, name, None) is None


def test_the_four_entries_list_the_cells_that_report_out_tokens_per_s():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    moved = next(m for m in bench["end_to_end"] if m["name"] == "out_tokens_per_s")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    units = dict(zip(NAMES, (("%", "lower"), ("%", "higher"), ("ms/ktok", "lower"), ("ms", "lower"))))
    for name in NAMES:
        unit, better = units[name]
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": "device_trace",
            "layer": "jitted programs", "moves": "out_tokens_per_s",
            "workloads": moved["workloads"]}
        assert (ROOT / "benchmark" / "metrics" / f"{name}.py").exists()


def test_the_benchmarks_scope_set_is_the_programs():
    obs = pytest.importorskip("jax_llama_tpu.obs")
    assert set(lanes.LANES) == set(obs.DEVICE_LANES)
    assert set(lanes.LANES) | lanes.LEAVES == set(obs.DEVICE_SCOPES)
    assert not set(lanes.LANES) & lanes.LEAVES
