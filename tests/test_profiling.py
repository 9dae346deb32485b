"""Profiling/observability utilities."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from jax_llama_tpu.utils import DecodeStats, Timer, trace


def test_timer_measures_device_work():
    x = jnp.asarray(np.random.randn(256, 256), jnp.float32)
    with Timer() as t:
        y = x
        for _ in range(4):
            y = y @ x
        jax.block_until_ready(y)
    assert t.elapsed_s > 0


def test_decode_stats_math():
    s = DecodeStats(
        batch=8, prompt_len=128, new_tokens=100, prefill_s=0.5,
        decode_s=2.0, n_devices=4,
    )
    assert s.decode_tokens_per_s == 8 * 100 / 2.0
    assert s.decode_tokens_per_s_per_chip == 8 * 100 / 2.0 / 4
    assert s.per_token_latency_ms == 20.0
    assert "tok/s/chip" in s.summary()


def test_trace_writes_profile(tmp_path):
    d = str(tmp_path / "trace")
    with trace(d):
        jax.block_until_ready(jnp.ones((8, 8)) * 2)
    found = []
    for root, _, files in os.walk(d):
        found += [f for f in files if f.endswith(".xplane.pb")]
    assert found, f"no xplane files under {d}"


def test_normalize_program_name():
    """xplane event names map to serving-program names: host-plane
    PjitFunction frames and device-plane jit_ module names (with
    specialization suffixes) both normalize; HLO-op and host noise
    names return None."""
    from jax_llama_tpu.utils.profiling import normalize_program_name

    assert normalize_program_name(
        "PjitFunction(_paged_decode_chunk)"
    ) == "_paged_decode_chunk"
    assert normalize_program_name(
        "jit__fused_chunk"
    ) == "_fused_chunk"
    assert normalize_program_name("jit_myprog.3") == "myprog"
    assert normalize_program_name("%fusion.12") is None
    assert normalize_program_name("Thread dispatch") is None
    assert normalize_program_name("") is None


def test_busy_is_a_union_and_idle_splits_by_overlap():
    """Device busy time is the UNION of op intervals (ops nest), idle
    the gaps inside it; a gap is named by the loop-thread intervals it
    overlaps and the rest is ``unnamed``."""
    from jax_llama_tpu.utils.profiling import busy_and_gaps, split_by_overlap

    busy, gaps = busy_and_gaps([(0, 10), (2, 5), (12, 15), (20, 21)])
    assert busy == 14 and gaps == [(10, 12), (15, 20)]
    assert busy_and_gaps([]) == (0.0, [])
    named = [("emit", 9, 11), ("deliver", 11, 16), ("in dispatch", 18, 30)]
    assert split_by_overlap(gaps, named) == {
        "emit": 1.0, "deliver": 2.0, "in dispatch": 2.0, "unnamed": 2.0,
    }
    assert split_by_overlap(gaps, []) == {"unnamed": 7.0}


def test_an_idle_gap_goes_to_the_innermost_event_over_it():
    """``idle_by_span_ms``: phases, dispatches and their child spans cut
    into pieces that do not overlap, each the innermost event's, so a gap
    under both ``admit`` and ``admit.match`` counts once, for the leaf."""
    from jax_llama_tpu.utils.profiling import innermost, split_by_overlap

    events = [
        ("admit", 0, 10), ("admit.hash", 1, 3), ("admit.alloc", 4, 9),
        ("admit.evict", 5, 7), ("in dispatch", 10, 30),
        ("dispatch.submit", 10, 12), ("emit", 30, 34),
        ("emit.replay", 30, 33), ("emit.free", 32, 35),  # outlives: clipped
    ]
    pieces = innermost(events)
    assert pieces == [
        ("admit", 0, 1), ("admit.hash", 1, 3), ("admit", 3, 4),
        ("admit.alloc", 4, 5), ("admit.evict", 5, 7), ("admit.alloc", 7, 9),
        ("admit", 9, 10), ("dispatch.submit", 10, 12),
        ("in dispatch", 12, 30), ("emit.replay", 30, 32),
        ("emit.free", 32, 33), ("emit", 33, 34),
    ]
    gaps = [(2, 6), (11, 13), (33, 40)]
    assert split_by_overlap(gaps, pieces) == {
        "admit.hash": 1.0, "admit": 1.0, "admit.alloc": 1.0,
        "admit.evict": 1.0, "dispatch.submit": 1.0, "in dispatch": 1.0,
        "emit": 1.0, "unnamed": 6.0,
    }
    assert innermost([]) == []


def test_summarize_xplane_reads_a_capture_with_jax_alone(tmp_path):
    """summarize_xplane parses with jax.profiler.ProfileData (no
    TensorFlow protos): host-side time lands on the jitted program, and
    a CPU capture — no device plane — has no busy, idle or phases."""
    import pytest

    from jax_llama_tpu.utils.profiling import summarize_xplane

    with pytest.raises(FileNotFoundError):
        summarize_xplane(str(tmp_path / "nothing"))

    @jax.jit
    def myprog(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64))
    myprog(x).block_until_ready()
    d = str(tmp_path / "trace")
    with trace(d):
        with jax.profiler.TraceAnnotation("llm.loop.prep"):
            pass
        with jax.profiler.TraceAnnotation("llm.dispatch", seq=0):
            myprog(x).block_until_ready()
    out = summarize_xplane(d)
    assert out["xplane"].endswith(".xplane.pb")
    assert out["programs"]["myprog"]["host_ms"] > 0
    assert out["total_device_ms"] == 0.0
    assert out["busy_ms"] == 0.0 and out["idle_ms"] == 0.0
    assert out["idle_by_phase_ms"] == {}
    assert out["idle_by_span_ms"] == {}
