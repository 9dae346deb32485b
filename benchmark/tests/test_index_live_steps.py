"""`attn.index_live_steps_pct`: the share of a full table's grid steps the paged
index-ranking kernel ran, from the program's two counters.

    python -m pytest benchmark/tests/test_index_live_steps.py -q -p no:cacheprovider
"""

import json
from pathlib import Path

from benchmark import run as run_mod

ROOT = Path(__file__).resolve().parents[2]
NAME = "attn.index_live_steps_pct"


def _ctx(before, after):
    return run_mod.Context(
        trace=None, peaks={}, records=[], timelines={}, config={}, chips=1,
        dispatches=[], counters0=before, counters1=after)


def test_the_reader_takes_the_difference_of_the_two_counters():
    """Eight rows of 19.5k tokens under tables of 32,768: 5 of 8 steps a row a
    layer; what the counters read before the window does not count."""
    read = run_mod.load_reader(NAME)
    before = {"attn_index_steps_run_total": 700, "attn_index_steps_table_total": 1000}
    after = {"attn_index_steps_run_total": 700 + 5 * 8 * 6 * 10,
             "attn_index_steps_table_total": 1000 + 8 * 8 * 6 * 10}
    got = read(_ctx(before, after))
    assert abs(got["value"] - 62.5) < 1e-9
    assert got["note"] == {"run": 2400, "table": 3840}


def test_the_reader_reads_nothing_from_a_program_without_the_counters():
    """The parent's program has no such counters (the driver lays this file
    over its checkout for the traced runs), and a window with no decode row
    counts no table step: None, and no exception."""
    read = run_mod.load_reader(NAME)
    assert read(_ctx({}, {})) is None
    assert read(_ctx({"attn_index_steps_table_total": 40}, {"attn_index_steps_table_total": 40})) is None
    assert read(_ctx({}, {"attn_selected_slots_total": 2048, "attn_candidate_slots_total": 19456})) is None


def test_the_entry_is_in_the_benchmark_for_the_one_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "out_tokens_per_s",
                     "workloads": ["keyevl2-hotdocs-asks"]}
    assert NAME in [m["name"] for m in run_mod.metrics_of(bench, "per_layer", "keyevl2-hotdocs-asks")]
    assert NAME not in [m["name"] for m in run_mod.metrics_of(bench, "per_layer", "trinitymini-docqa-mixed")]
