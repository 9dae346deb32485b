"""`step.admit_sample_share_pct`'s own tests: CPU, a written trace file.

    python -m pytest benchmark/tests/test_admit_sample.py -q -p no:cacheprovider
"""

import json
from pathlib import Path

import pytest

from benchmark import hostspans

ROOT = Path(__file__).resolve().parents[2]
NAME = "step.admit_sample_share_pct"
CELLS = ["mistral7b-docqa-batch", "kanana2-docqa-long", "trinitymini-docqa-mixed",
         "phi4flash-reason-sessions", "falconh1-assist-sessions"]


def _write_trace(path, ops):
    """A device plane whose `XLA Ops` line holds `ops`: (tf_op, start us,
    duration us) each, the scope path in the event metadata's `tf_op` stat,
    where the profiler puts it."""
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "tf_op"
    line = plane.lines.add(name="XLA Ops", timestamp_ns=0)
    for i, (tf_op, start_us, dur_us) in enumerate(ops, 1):
        md = plane.event_metadata[i]
        md.id, md.name = i, f"%fusion.{i}"
        md.stats.add(metadata_id=1, str_value=tf_op)
        line.events.add(metadata_id=i, offset_ps=int(start_us * 1e6),
                        duration_ps=int(dur_us * 1e6))
    path.write_bytes(space.SerializeToString())
    return str(path)


def _read(monkeypatch, path, traced=True):
    from benchmark import run as run_mod

    monkeypatch.setattr(hostspans, "newest_xplane", lambda out: path)
    ctx = run_mod.Context(trace={"modules": []} if traced else None, config={}, chips=1)
    return run_mod.load_reader(NAME)(ctx)


def test_a_trace_with_the_scope_reads_its_share_of_the_busy_time(tmp_path, monkeypatch):
    """Operations under `admit.sample` — the cond, the head inside it (under
    its own `head` scope too), the argmax — count by self time over every
    operation's; a `while` around a scope's body counts nothing of it."""
    fused = "jit(_fused_chunk)/jit(main)/"
    path = _write_trace(tmp_path / "t.xplane.pb", [
        (fused + "while/body/dense.ffn/dot_general:", 0, 700),
        (fused + "admit.sample/cond:", 700, 100),                          # 20 us its own
        (fused + "admit.sample/cond/branch_1_fun/head/dot_general:", 710, 50),
        (fused + "admit.sample/cond/branch_1_fun/argmax:", 765, 30),
        (fused + "while/body/head/dot_general:", 800, 200),
    ])
    got = _read(monkeypatch, path)
    assert got["value"] == pytest.approx(10.0)
    assert got["note"]["busy_self_s"] == pytest.approx(1000e-6)
    assert got["note"]["admit_sample_s"] == pytest.approx(100e-6)
    assert _read(monkeypatch, path, traced=False) is None      # a `--trace 0` run


def test_a_program_without_the_scope_reads_nothing(tmp_path, monkeypatch):
    """The parent's program samples outside any scope of this name: None,
    and no raise (the driver lays this file over the parent's checkout for
    the traced runs).  No trace file at all reads None too."""
    fused = "jit(_fused_chunk)/jit(main)/"
    path = _write_trace(tmp_path / "t.xplane.pb", [
        (fused + "while/body/dense.ffn/dot_general:", 0, 700),
        (fused + "sort:", 700, 100),
        (fused + "head/dot_general:", 800, 200),
    ])
    assert _read(monkeypatch, path) is None
    assert _read(monkeypatch, None) is None


def test_the_metric_is_the_last_entry_and_lists_the_closed_loop_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "jitted programs", "moves": "out_tokens_per_s", "workloads": CELLS}
    moved = next(m for m in bench["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert moved["workloads"] == CELLS
    assert (ROOT / "benchmark" / "metrics" / f"{NAME}.py").exists()
