"""The device side's spans (``obs.DEVICE_SCOPES``): a closed set, lanes
around the halves of a dispatch, a leaf scope on every product and kernel,
and names only — the programs' text does not move.

Traced from abstract operands at ``tests/tools/hashes.py``'s tiny geometry;
nothing is compiled.  A traced equation's ``name_stack`` is relative to the
jaxpr that holds it, and the lowering prefixes a sub-jaxpr's equations with
their caller's stack (in the lowered text a path is split over call sites:
``closed_call``, ``jit(_take)``): ``_paths`` composes them the same way, so
what it yields is the path the profiler's ``tf_op`` carries, less the
compiler's own ``while/body`` elements.
"""

import ast
import contextlib
import functools
import importlib.util
import sys
from pathlib import Path

import jax
import pytest

from jax_llama_tpu import obs
from jax_llama_tpu.utils.profiling import (
    busy_by_lane_and_scope, lane_and_scope, summarize_xplane,
)

ROOT = Path(__file__).resolve().parents[1]
PACKAGE = ROOT / "jax_llama_tpu"
LANES = set(obs.DEVICE_LANES)
LEAVES = set(obs.DEVICE_SCOPES) - LANES
# The two sites that compute their scope's name, and the values it takes.
COMPUTED = {
    "models/afmoe.py": {"attn.window", "attn.full"},
    "models/mla_moe.py": {"mla.project"},
}
# block -> whether its fused dispatch takes the mixed pass at K >= 2
BLOCKS = {
    "dense": True, "latent": False, "streams": False, "windowed": False,
    "recurrent": True, "parallel-mixer": True, "sparse": False,
}


@pytest.fixture(scope="module")
def hashes():
    spec = importlib.util.spec_from_file_location(
        "tools_hashes", ROOT / "tests" / "tools" / "hashes.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _paths(jaxpr, prefix=()):
    """(primitive, scope path) of every equation, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        own = str(eqn.source_info.name_stack)
        path = prefix + tuple(p for p in own.split("/") if p)
        yield eqn.primitive.name, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _paths(sub, path)


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_every_product_and_kernel_has_one_lane_and_a_leaf_scope(hashes, kind):
    """``_fused_chunk`` at K = 2 and 8 and ``_paged_decode_chunk`` at K = 8
    of each block: EVERY equation lies under a lane — ``lane.mixed`` opens
    inside ``lane.chunk`` and nowhere else, ``lane.decode`` under neither,
    so the last lane of a path is its one lane —, every ``dot_general`` and
    every Pallas call under a leaf scope of the closed set.  A block that
    takes the mixed pass shows ``lane.mixed``, a two-pass block does not;
    the decode program is ``lane.decode`` throughout."""
    config = dict(hashes.configs())[kind]
    seen = {}
    for name, program, operands, static in hashes.calls(kind, config):
        if "insert" in name:
            continue
        traced = program.trace(*operands, **static)
        lanes_seen, products = set(), 0
        for prim, path in _paths(traced.jaxpr.jaxpr):
            lanes = [p for p in path if p in LANES]
            assert lanes, (name, prim, path)
            assert lanes in (["lane.chunk"], ["lane.chunk", "lane.mixed"],
                             ["lane.decode"]), (name, prim, path)
            lanes_seen.add(lanes[-1])
            if prim in ("dot_general", "pallas_call"):
                products += 1
                assert any(p in LEAVES for p in path), (name, prim, path)
            assert lane_and_scope("/".join(path))[0] == lanes[-1][5:]
        assert products, name
        seen[name.split(".", 1)[1]] = lanes_seen
    assert seen["decode.k8"] == {"lane.decode"}
    fused = {"lane.chunk", "lane.decode"} | (
        {"lane.mixed"} if BLOCKS[kind] else set())
    assert seen["fused.k2"] == seen["fused.k8"] == fused


def test_the_units_scopes_are_in_the_block_with_several_streams(hashes):
    config = dict(hashes.configs())["streams"]
    assert config.hc_mult == 4
    name, program, operands, static = list(hashes.calls("streams", config))[2]
    assert name == "streams.decode.k8"
    leaves = {p for _, path in _paths(
        program.trace(*operands, **static).jaxpr.jaxpr) for p in path
        if p in LEAVES}
    assert {"hc.coeff", "hc.pre", "hc.post", "mla.project",
            "mla.attend_decode", "moe.experts", "cache.write", "sample",
            "emit", "embed", "head"} <= leaves


def _opened(tree):
    """The names one file's ``named_scope(...)`` calls open, and how many
    of the calls compute theirs."""
    names, computed = set(), 0
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and getattr(
                node.func, "attr", getattr(node.func, "id", "")
        ) == "named_scope"):
            continue
        (arg,) = node.args
        picks = [arg.body, arg.orelse] if isinstance(arg, ast.IfExp) else [arg]
        if all(isinstance(a, ast.Constant) for a in picks):
            names |= {a.value for a in picks}
        else:
            computed += 1
    return names, computed


def test_the_declared_set_is_what_the_package_opens_both_ways():
    """Every ``jax.named_scope`` of the package names a member of
    ``obs.DEVICE_SCOPES`` and every member is opened somewhere: 35 distinct
    names, three of them lanes.  The two sites that compute a name take
    the values ``COMPUTED`` gives them, each a literal of its file."""
    opened, computed = set(), {}
    for path in sorted(PACKAGE.rglob("*.py")):
        tree = ast.parse(path.read_text())
        names, n = _opened(tree)
        opened |= names
        if n:
            rel = path.relative_to(PACKAGE).as_posix()
            computed[rel] = n
            literals = {c.value for c in ast.walk(tree)
                        if isinstance(c, ast.Constant)}
            assert COMPUTED[rel] <= literals, rel
    assert computed == {rel: 1 for rel in COMPUTED}
    opened |= set().union(*COMPUTED.values())
    assert opened == set(obs.DEVICE_SCOPES)
    assert LANES == {"lane.chunk", "lane.mixed", "lane.decode"}
    assert len(obs.DEVICE_SCOPES) == 35 and all(obs.DEVICE_SCOPES.values())


def test_the_scopes_are_names_only(hashes, monkeypatch):
    """The lowered text without debug info is byte-equal with and without
    the scopes (``jax.named_scope`` patched to a null context while the
    program is traced), and only the text WITH debug info carries them."""
    config = dict(hashes.configs())["dense"]
    name, program, operands, static = next(iter(hashes.calls("dense", config)))
    scoped = program.lower(*operands, **static)
    assert "lane.decode" in scoped.as_text(debug_info=True)
    assert "lane." not in scoped.as_text()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    @functools.wraps(program.__wrapped__)  # its name and its signature
    def unscoped(*args, **kwargs):
        # a function object of its own: jit's cache holds the scoped trace
        return program.__wrapped__(*args, **kwargs)

    bare = jax.jit(
        unscoped,
        static_argnames=("config", "n_iter", "pf_chunk", "all_greedy", "mesh",
                         "allow_kernel", "with_logprobs", "placed"),
        donate_argnames=("pool", "fill", "tau", "tau_lp", "pos", "active",
                         "remaining", "keys", "pf_vec"),
    ).lower(*operands, **static)
    assert "lane." not in bare.as_text(debug_info=True)
    assert bare.as_text() == scoped.as_text()


def _write_trace(path, ops):
    """A device plane whose ``XLA Ops`` line holds ``ops``: (scope path,
    start us, duration us), the path in the event metadata's ``tf_op``
    stat, where the profiler puts it."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
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


def test_the_profile_summary_splits_busy_time_by_lane_and_by_scope(tmp_path):
    """The operator's view (``GET /debug/profile/summary``): the first
    device's busy time by the last lane and the innermost leaf scope of its
    operations, by self time — a ``while`` counts nothing of its body —,
    each adding up to ``busy_ms``; an insert program's name is its lane."""
    fused = "jit(_fused_chunk)/jit(main)/"
    trace_dir = tmp_path / "plugins" / "profile" / "run"
    trace_dir.mkdir(parents=True)
    path = _write_trace(trace_dir / "host.xplane.pb", [
        (fused + "lane.chunk/cache.gather/jit(_take)/gather:", 0, 100),
        (fused + "lane.chunk/lane.mixed/while:", 100, 500),   # 20 us its own
        (fused + "lane.chunk/lane.mixed/while/body/dense.ffn/dot_general:", 100, 300),
        (fused + "lane.chunk/lane.mixed/while/body/dense.attention/dot_general:", 400, 180),
        (fused + "lane.chunk/admit.sample/cond/branch_1_fun/head/dot_general:", 600, 100),
        (fused + "lane.decode/while/body/ssm.mix/ssm.step/mul:", 700, 300),
        ("jit(_paged_insert)/jit(main)/cache.land/scatter:", 1200, 50),
        ("", 1300, 50),
    ])
    lanes, scopes = busy_by_lane_and_scope(path)
    assert lanes == pytest.approx({
        "chunk": 0.2, "mixed": 0.5, "decode": 0.3, "_paged_insert": 0.05,
        "none": 0.05})
    assert scopes == pytest.approx({
        "cache.gather": 0.1, "dense.ffn": 0.3, "dense.attention": 0.18,
        "head": 0.1, "ssm.step": 0.3, "cache.land": 0.05, "unscoped": 0.07})
    out = summarize_xplane(str(tmp_path))
    assert out["busy_ms"] == pytest.approx(1.1)
    assert sum(out["busy_by_lane_ms"].values()) == pytest.approx(1.1)
    assert out["busy_by_scope_ms"]["unscoped"] == pytest.approx(0.07)
    assert lane_and_scope(fused + "lane.decoder/headroom/add:") == (
        "_fused_chunk", "unscoped")
