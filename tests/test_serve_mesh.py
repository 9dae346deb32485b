"""Scale-out serving: mesh-sharded chunk programs + sharded KV pool.

The acceptance pins (ISSUE 10): on the forced 8-device CPU host mesh,
the sharded ``_paged_decode_chunk`` / ``_fused_chunk`` programs are
TOKEN-IDENTICAL to single-chip (logprobs allclose — cross-shard
reduction order wobbles fp32 at ~1e-6), the pool/state placement is
the canonical one (KV heads over ``tensor``, state rows over ``data``)
and STABLE across dispatches (the donated-alias precondition the
lowering auditor's mesh pass proves per-program), and the
prefill/decode disaggregation handoff moves prefix KV between batchers
token-identically."""

import jax
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.parallel import serve_mesh as smesh
from jax_llama_tpu.parallel.mesh import make_mesh
from jax_llama_tpu.parallel.partition import shard_params
from jax_llama_tpu.serving import ContinuousBatcher

pytestmark = pytest.mark.mesh_serving

CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=128, dtype="float32",
    param_dtype="float32",
)


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


@pytest.fixture(scope="module")
def mesh22(model, cpu_mesh_devices):
    """data=2 x tensor=2 serving mesh + params sharded onto it."""
    params, config = model
    mesh = make_mesh(data=2, tensor=2, devices=cpu_mesh_devices[:4])
    return mesh, shard_params(params, mesh, config)


def _serve(params, config, mesh, *, prefill_budget=0, logprobs=False,
           fused_admission=False, **cb_kw):
    """The shared request mix (greedy stopping mid-chunk + seeded
    sampled) + optionally a long prompt admitted MID-DECODE so the
    fused prefill lane engages.  Geometry kept to n_slots=2 /
    decode_chunk=2 deliberately: every extra row or K specialization
    compiles another mesh executable, and tier-1's budget cannot
    absorb it (the broader shapes ride the slow tier / make
    mesh-serve).  Returns ([tokens...], [logprobs...], batcher) per
    request in submit order."""
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 128, size=n).tolist() for n in (5, 9)]
    policies = [
        dict(max_new_tokens=4),
        dict(max_new_tokens=6, temperature=0.9, seed=11),
    ]
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, decode_chunk=2,
        mesh=mesh, prefill_budget=prefill_budget, logprobs=logprobs,
        **cb_kw,
    )
    rids = [cb.submit(p, **pol) for p, pol in zip(prompts, policies)]
    toks, lps = {}, {}

    def drain_some(n):
        for _ in range(n):
            for ev in cb.step():
                toks.setdefault(ev[0], []).append(ev[1])
                if logprobs:
                    lps.setdefault(ev[0], []).append(ev[3])

    drain_some(2)
    if fused_admission:
        # Long prompt lands while rows decode -> the fused prefill lane
        # (or, at prefill_budget=0, a classic mid-decode insert).
        long = rng.randint(1, 128, size=40).tolist()
        rids.append(cb.submit(long, max_new_tokens=3, seed=99))
    guard = 0
    while cb.pending():
        guard += 1
        assert guard < 500
        drain_some(1)
    return [toks[r] for r in rids], [lps.get(r) for r in rids], cb


def _assert_parity(base, mesh_out):
    b_toks, b_lps, _ = base
    m_toks, m_lps, _ = mesh_out
    assert m_toks == b_toks
    for bl, ml in zip(b_lps, m_lps):
        if bl is not None:
            np.testing.assert_allclose(ml, bl, rtol=1e-4, atol=1e-5)


def test_sharded_chunk_programs_parity(model, mesh22):
    """ACCEPTANCE PIN: sharded ``_fused_chunk`` AND
    ``_paged_decode_chunk`` ≡ single-chip — tokens exact (greedy AND
    seeded sampling, long admission riding the prefill lane
    mid-decode), logprobs allclose.  One scenario covers both
    programs: dispatches WITH the in-flight admission run
    ``_fused_chunk``, dispatches without run ``_paged_decode_chunk``
    (asserted via the dispatch counters below)."""
    params, config = model
    mesh, sp = mesh22
    base = _serve(params, config, None, prefill_budget=16,
                  logprobs=True, fused_admission=True)
    out = _serve(sp, config, mesh, prefill_budget=16, logprobs=True,
                 fused_admission=True)
    _assert_parity(base, out)
    cb = out[2]
    assert cb._mesh_placed
    # Both programs actually dispatched on the mesh run.
    assert cb.fused_admissions_total >= 1
    assert cb.prefill_chunks_total >= 1
    assert cb.decode_dispatches_total > cb.prefill_chunks_total


@pytest.mark.slow
def test_sharded_decode_chunk_only_parity(model, mesh22):
    """The prefill-free configuration (classic admission,
    ``_paged_decode_chunk`` exclusively) — the tier-1 pin above covers
    the program; this cell pins the prefill_budget=0 config too."""
    params, config = model
    mesh, sp = mesh22
    base = _serve(params, config, None, logprobs=True)
    out = _serve(sp, config, mesh, logprobs=True)
    _assert_parity(base, out)
    assert out[2]._mesh_placed


def test_pool_and_state_placement_stable(model, mesh22):
    """The pool shards KV heads over ``tensor``, per-slot twins shard
    rows over ``data``, and BOTH keep their sharding across dispatches
    — the aliasing precondition (drift = a reshard + silent donation
    copy every chunk)."""
    params, config = model
    mesh, sp = mesh22
    # Same geometry as the parity pin above -> its executables are jit
    # cache hits; this test pays dispatches only.
    cb = ContinuousBatcher(
        sp, config, n_slots=2, max_len=128, decode_chunk=2, mesh=mesh,
    )
    assert cb._mesh_placed
    rid = cb.submit([5, 17, 99, 3, 42], max_new_tokens=6)

    def spec_of(a):
        return a.sharding

    from jax.sharding import NamedSharding

    want_pool = NamedSharding(
        mesh, smesh.pool_pspec("k", cb.pool.k.ndim)
    )
    assert cb.pool.k.sharding.is_equivalent_to(
        want_pool, cb.pool.k.ndim
    )
    cb.step()
    first = {
        "k": spec_of(cb.pool.k), "pos": spec_of(cb.pool.pos),
        "fill": spec_of(cb.d_fill), "table": spec_of(cb.d_table),
        "keys": spec_of(cb.keys),
    }
    while cb.pending():
        cb.step()
    after = {
        "k": spec_of(cb.pool.k), "pos": spec_of(cb.pool.pos),
        "fill": spec_of(cb.d_fill), "table": spec_of(cb.d_table),
        "keys": spec_of(cb.keys),
    }
    for name in first:
        a, b = first[name], after[name]
        arr = {"k": cb.pool.k, "pos": cb.pool.pos, "fill": cb.d_fill,
               "table": cb.d_table, "keys": cb.keys}[name]
        assert a.is_equivalent_to(b, arr.ndim), name
    # KV-head axis genuinely sharded over tensor: each shard holds
    # KVH/tp heads' blocks.
    shard_shape = cb.pool.k.sharding.shard_shape(cb.pool.k.shape)
    assert shard_shape[1] == config.kv_heads // 2
    _ = rid


def test_spec_parse_build_validate(model, cpu_mesh_devices):
    params, config = model
    assert smesh.parse_serve_mesh("2,4") == smesh.ServeMeshSpec(2, 4)
    assert smesh.parse_serve_mesh("4") == smesh.ServeMeshSpec(1, 4)
    with pytest.raises(ValueError):
        smesh.parse_serve_mesh("2,4,8")
    with pytest.raises(ValueError):
        smesh.parse_serve_mesh("zero,none")
    spec = smesh.parse_serve_mesh("2,2")
    mesh = smesh.build_serve_mesh(spec, devices=cpu_mesh_devices[:4])
    smesh.validate_serve_mesh(config, mesh, n_slots=4)
    with pytest.raises(ValueError):  # rows must divide slots
        smesh.validate_serve_mesh(config, mesh, n_slots=3)
    with pytest.raises(ValueError):  # tensor must divide kv_heads
        smesh.validate_serve_mesh(
            config, make_mesh(tensor=8, devices=cpu_mesh_devices),
            n_slots=8,
        )
    with pytest.raises(ValueError):  # no seq/stage axes
        smesh.validate_serve_mesh(
            config,
            make_mesh(seq=2, tensor=2, data=2,
                      devices=cpu_mesh_devices),
            n_slots=8,
        )
    assert smesh.mesh_shape(mesh) == {
        "data": 2, "tensor": 2, "devices": 4,
    }
    assert smesh.mesh_shape(None) == {
        "data": 1, "tensor": 1, "devices": 1,
    }


def test_placement_envelope(model, cpu_mesh_devices):
    """Meshes outside the envelope keep legacy (unplaced) behavior
    rather than erroring: seq/stage axes or a non-dividing tensor."""
    params, config = model
    seq_mesh = make_mesh(seq=2, tensor=2, data=2,
                         devices=cpu_mesh_devices)
    assert not smesh.placement_ok(config, seq_mesh, 8)
    tp8 = make_mesh(tensor=8, devices=cpu_mesh_devices)
    assert not smesh.placement_ok(config, tp8, 8)  # kv_heads=2 % 8
    ok = make_mesh(data=2, tensor=2, devices=cpu_mesh_devices[:4])
    assert smesh.placement_ok(config, ok, 4)
    assert not smesh.placement_ok(config, ok, 3)  # rows don't divide
    assert not smesh.placement_ok(config, None, 4)


@pytest.mark.slow
def test_kv_handoff_token_identity(model):
    """Disaggregation skeleton: prefill on A, export/import the chain,
    serve on B as a prefix hit — token-identical to a cold serve."""
    params, config = model
    prompt = list(np.random.RandomState(3).randint(1, 128, 50))

    def serve(cb):
        r = cb.submit(prompt, max_new_tokens=6, seed=5)
        return cb.run_to_completion()[r]

    def mk():
        return ContinuousBatcher(
            params, config, n_slots=2, max_len=128, block_size=16,
            decode_chunk=4,
        )

    a = mk()
    out_a = serve(a)
    keys, slabs = a.export_prefix(prompt)
    assert len(slabs) == (len(prompt) - 1) // 16
    assert a.kv_export_blocks_total == len(slabs)

    cold = serve(mk())
    b = mk()
    n = b.import_prefix(keys, slabs)
    assert n == len(slabs)
    assert b.kv_import_blocks_total == n
    out_b = serve(b)
    assert out_a == cold
    assert out_b == cold
    assert b.prefix_requests_hit == 1
    assert b.prefix_blocks_reused == n
    # Re-import is a no-op (already resident).
    assert b.import_prefix(keys, slabs) == 0
    # Off-cache batchers export/import nothing.
    off = ContinuousBatcher(
        params, config, n_slots=2, max_len=128, block_size=16,
        prefix_cache=False,
    )
    assert off.export_prefix(prompt) == ([], [])
    assert off.import_prefix(keys, slabs) == 0


@pytest.mark.slow
def test_sharded_host_tier_restore_on_mesh(model, mesh22):
    """The host-DRAM tier under sharded placement: demote a served
    chain, re-admit the session so the swap-in restores through
    mesh-placed staging buffers (``staging_shardings``), and the
    restored serve stays token-identical — with the pool keeping its
    canonical sharding through the adopt scatter."""
    params, config = model
    mesh, sp = mesh22

    def mk(p, m):
        return ContinuousBatcher(
            p, config, n_slots=4, max_len=128, block_size=16,
            decode_chunk=4, mesh=m, host_kv_blocks=16,
        )

    prompt = list(np.random.RandomState(5).randint(1, 128, 50))

    def serve(cb):
        r = cb.submit(prompt, max_new_tokens=5, seed=7)
        out = {}
        guard = 0
        while cb.pending():
            guard += 1
            assert guard < 500
            for ev in cb.step():
                out.setdefault(ev[0], []).append(ev[1])
        return out[r]

    base_cb = mk(params, None)
    want = serve(base_cb)

    cb = mk(sp, mesh)
    got = serve(cb)
    assert got == want
    n = cb.demote_idle(8)
    assert n > 0
    assert cb._store.host_blocks() == n
    got2 = serve(cb)  # re-admission swaps the chain back in
    assert got2 == want
    assert cb.swap_in_blocks_total > 0
    from jax.sharding import NamedSharding

    assert cb.pool.k.sharding.is_equivalent_to(
        NamedSharding(mesh, smesh.pool_pspec("k", cb.pool.k.ndim)),
        cb.pool.k.ndim,
    )


@pytest.mark.slow
def test_tensor_only_mesh_parity(model, cpu_mesh_devices):
    """A 1 x tensor=2 mesh (pure TP replica slice, the router's usual
    per-replica geometry) is also token-identical."""
    params, config = model
    mesh = make_mesh(tensor=2, devices=cpu_mesh_devices[:2])
    sp = shard_params(params, mesh, config)
    base = _serve(params, config, None, prefill_budget=16,
                  logprobs=True, fused_admission=True)
    out = _serve(sp, config, mesh, prefill_budget=16, logprobs=True,
                 fused_admission=True)
    _assert_parity(base, out)


@pytest.mark.slow
def test_sharded_spec_chunk_parity(model, mesh22):
    """Speculative chunked serving (R>1, both pools sharded) on the
    mesh ≡ single-chip: tokens and acceptance-driven emission exact."""
    params, config = model
    mesh, sp = mesh22

    def run(p, m):
        cb = ContinuousBatcher(
            p, config, n_slots=4, max_len=128, decode_chunk=1,
            spec_rounds=2, draft_params=p, draft_config=config,
            n_draft=2, mesh=m,
        )
        rids = [
            cb.submit([5, 17, 99, 3, 42], max_new_tokens=6),
            cb.submit([7, 8, 9], max_new_tokens=5, temperature=0.8,
                      seed=13),
        ]
        out = {}
        guard = 0
        while cb.pending():
            guard += 1
            assert guard < 500
            for ev in cb.step():
                out.setdefault(ev[0], []).append(ev[1])
        return [out[r] for r in rids], cb

    base, _ = run(params, None)
    got, cb = run(sp, mesh)
    assert got == base
    assert cb._mesh_placed
