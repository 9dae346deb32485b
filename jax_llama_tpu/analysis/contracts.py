"""Declarative lowering contracts for the serving stack's jitted programs.

``tests/test_tpu_compiled.py`` pins two programs' lowerings by hand
(no-full-pool-copy, donated carries).  This registry generalizes those
pins: EVERY jitted program the ``ContinuousBatcher`` dispatches declares

  * ``donated``      — the argnames the jit decorator must donate
                       (a dropped ``donate_argnames`` entry silently
                       doubles KV HBM and re-uploads state per dispatch);
  * ``max_live_outputs`` / ``max_fetch_bytes_per_row``
                     — the host-fetch surface: how many outputs are NOT
                       aliased onto donated inputs, and how many bytes
                       per batch row they may total at the example shape
                       (the "1 packed fetch" contract; a [B, V] logits
                       leak blows the per-row budget immediately);
  * ``forbid_pool_shapes``
                     — no copy-class jaxpr equation (broadcast, gather,
                       dynamic-slice, concat, transpose, convert, ...)
                       may produce a full-pool-sized or one-plane-sized
                       array (the regression class the TPU pins catch in
                       optimized HLO; here caught abstractly on any
                       backend);
  * ``build``        — a callable producing concrete example arguments
                       at a tiny geometry, so the auditor can
                       ``.lower()`` the program on CPU in seconds.

New programs MUST join this registry before the batcher dispatches
them — the auditor's coverage check fails on any jit-decorated
module-level function in serving.py / kvcache.py without a contract (allowlist: :data:`NON_DISPATCHED`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

# Example geometry: small enough that tracing all programs on CPU costs
# seconds, real enough that every shape class (pool, plane, state row,
# packed fetch) is present.
_DIM, _LAYERS, _HEADS, _KVH = 64, 2, 4, 2
_VOCAB, _MAXLEN, _BLOCK, _SLOTS = 128, 64, 16, 2


@dataclasses.dataclass(frozen=True)
class CommsBudget:
    """Per-program collective-communication budget, declared at the
    contract's tiny mesh example geometry (data=2 x tensor=2 over 4
    forced host devices) and checked by :mod:`.comms` against the
    COMPILED sharded lowering (GSPMD inserts collectives at partition
    time — they exist nowhere earlier) plus the traced jaxpr (explicit
    ``psum``/``all_gather``-class primitives from shard_map code).

      * ``max_count``: collective kind -> max instruction count in the
        compiled module (a kind absent from the dict allows ZERO).
        Counts are per compiled-module text — an op inside a scan body
        counts once but executes per iteration, which is exactly the
        per-dispatch cost class the budget bounds.
      * ``max_bytes``: result bytes of the largest single collective.
        The legit per-layer tensor-axis reductions the matmul sharding
        implies are activation-sized; a pool-sized reshard is 1-2
        orders larger at any geometry, so the byte bar separates the
        two robustly even as XLA's exact op counts drift.

    Full-pool / one-plane shaped collective RESULTS are a hard finding
    regardless of budget (not declarable here on purpose)."""

    max_count: Dict[str, int]
    max_bytes: int


@dataclasses.dataclass(frozen=True)
class ProgramContract:
    name: str
    module: str                       # import path of the owning module
    donated: Tuple[str, ...]          # argnames (or argnums' param names)
    max_live_outputs: int             # outputs not aliased to donations
    max_fetch_bytes_per_row: int      # live-output bytes / batch rows
    forbid_pool_shapes: bool = True
    build: Optional[Callable[[], Tuple[Tuple[str, ...], tuple, dict]]] = None
    # build() -> (positional argnames, positional args, static kwargs)
    # Forbidden-shape derivation: default scans the example args for
    # BlockPool-shaped leaves (pool_shapes).  A program whose pool
    # state arrives in another form (e.g. _adopt_jit's bare array
    # tuple) declares its own — the rule lives with the contract, so a
    # new pool carrier cannot silently derive an empty shape set and
    # pass the full-pool-copy check vacuously.
    forbidden_shapes: Optional[Callable[[tuple], List[Tuple[int, ...]]]] = None
    # Serving-mesh variant (parallel/serve_mesh.py): ``mesh_build``
    # produces the SAME program's example arguments placed on a small
    # forced-host-device serving mesh (sharded pool + row-sharded
    # state + sharded params, mesh static kwarg set).  The auditor's
    # mesh pass then proves donated-leaf aliasing still RESOLVES under
    # the sharded lowering, and — via ``mesh_aliases`` (donated
    # argname -> output position in the program's return tuple) —
    # executes the program once and asserts each donated input's
    # sharding equals its carried output's (sharding drift between a
    # donated input and its output is exactly how "donated" state
    # silently starts copying/resharding per dispatch on a mesh).
    mesh_build: Optional[
        Callable[[], Tuple[Tuple[str, ...], tuple, dict]]
    ] = None
    mesh_aliases: Optional[Dict[str, int]] = None
    # Jit-cache-key budget (analysis/retrace.py): the maximum number of
    # NEW executable-cache entries ONE serving configuration may create
    # for this program across its whole admission surface — the product
    # of the bounded domains its static args and admission-shaped dims
    # may take (pow2 buckets are O(log), bools are 2, ctor-stable args
    # are 1).  Checked two ways: the static pass proves every cache-key
    # value at every dispatch call site flows through a bounded-domain
    # constructor, and the runtime drill sweeps the admission surface
    # asserting ``serving.jit_cache_entries()`` stays within this
    # budget.  REQUIRED: a registered program without one is a finding.
    max_cache_keys: Optional[int] = None
    # Collective-comms budget (analysis/comms.py) for the SHARDED
    # lowering; required whenever ``mesh_build`` is set.
    comms: Optional[CommsBudget] = None


# -- example-argument factories ---------------------------------------------

_CACHE: Dict[str, Any] = {}


def _tiny_config_params():
    if "cfg" not in _CACHE:
        import jax

        import jax_llama_tpu as jlt

        cfg = jlt.get_config(
            "tiny", dim=_DIM, n_layers=_LAYERS, n_heads=_HEADS,
            n_kv_heads=_KVH, vocab_size=_VOCAB, max_seq_len=_MAXLEN,
            multiple_of=16,
        )
        _CACHE["cfg"] = cfg
        _CACHE["params"] = jlt.init_params(jax.random.PRNGKey(0), cfg)
    return _CACHE["cfg"], _CACHE["params"]


def _plain_batcher():
    if "plain" not in _CACHE:
        import numpy as np

        from ..serving import ContinuousBatcher

        cfg, params = _tiny_config_params()
        cb = ContinuousBatcher(
            params, cfg, n_slots=_SLOTS, max_len=_MAXLEN,
            block_size=_BLOCK, decode_chunk=2,
        )
        rng = np.random.RandomState(0)
        for _ in range(_SLOTS):
            cb.submit(list(rng.randint(1, _VOCAB, 20)), max_new_tokens=4)
        cb.step()
        _CACHE["plain"] = cb
    return _CACHE["plain"]


def _fused_batcher():
    if "fused" not in _CACHE:
        import numpy as np

        from ..serving import ContinuousBatcher

        cfg, params = _tiny_config_params()
        cb = ContinuousBatcher(
            params, cfg, n_slots=_SLOTS, max_len=_MAXLEN,
            block_size=_BLOCK, decode_chunk=2, prefill_budget=_BLOCK,
        )
        rng = np.random.RandomState(1)
        cb.submit(list(rng.randint(1, _VOCAB, 20)), max_new_tokens=8)
        cb.step()  # cold classic admission
        cb.step()
        cb.submit(list(rng.randint(1, _VOCAB, 40)), max_new_tokens=8)
        cb.step()  # fused prefill starts (40-token suffix > one chunk)
        assert cb._pf is not None, "fused example failed to enter prefill"
        _CACHE["fused"] = cb
    return _CACHE["fused"]


def _spec_batcher():
    if "spec" not in _CACHE:
        import numpy as np

        from ..serving import ContinuousBatcher

        cfg, params = _tiny_config_params()
        cb = ContinuousBatcher(
            params, cfg, n_slots=_SLOTS, max_len=_MAXLEN,
            block_size=_BLOCK, spec_rounds=2, draft_params=params,
            draft_config=cfg, n_draft=2,
        )
        rng = np.random.RandomState(2)
        for _ in range(_SLOTS):
            cb.submit(list(rng.randint(1, _VOCAB, 20)),
                      max_new_tokens=8)
        cb.step()
        _CACHE["spec"] = cb
    return _CACHE["spec"]


def _serve_mesh4():
    """A data=2 x tensor=2 serving mesh over 4 of the forced host
    devices (conftest / the analysis CLI force 8): tensor=2 divides
    the tiny config's 2 KV heads, data=2 divides the 2 example slots."""
    if "mesh" not in _CACHE:
        import jax

        from ..parallel.serve_mesh import ServeMeshSpec, build_serve_mesh

        if len(jax.devices()) < 4:
            raise RuntimeError(
                "serving-mesh contract pass needs >= 4 host devices "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)"
            )
        _CACHE["mesh"] = build_serve_mesh(
            ServeMeshSpec(data=2, tensor=2),
            devices=jax.devices()[:4],
        )
    return _CACHE["mesh"]


def _mesh_params():
    if "params_mesh" not in _CACHE:
        from ..parallel.partition import shard_params

        cfg, params = _tiny_config_params()
        _CACHE["params_mesh"] = shard_params(
            params, _serve_mesh4(), cfg
        )
    return _CACHE["params_mesh"]


def _plain_batcher_mesh():
    if "plain_mesh" not in _CACHE:
        import numpy as np

        from ..serving import ContinuousBatcher

        cfg, _ = _tiny_config_params()
        cb = ContinuousBatcher(
            _mesh_params(), cfg, n_slots=_SLOTS, max_len=_MAXLEN,
            block_size=_BLOCK, decode_chunk=2, mesh=_serve_mesh4(),
        )
        assert cb._mesh_placed, "mesh example fell outside placement"
        rng = np.random.RandomState(0)
        for _ in range(_SLOTS):
            cb.submit(list(rng.randint(1, _VOCAB, 20)), max_new_tokens=4)
        cb.step()
        _CACHE["plain_mesh"] = cb
    return _CACHE["plain_mesh"]


def _fused_batcher_mesh():
    if "fused_mesh" not in _CACHE:
        import numpy as np

        from ..serving import ContinuousBatcher

        cfg, _ = _tiny_config_params()
        cb = ContinuousBatcher(
            _mesh_params(), cfg, n_slots=_SLOTS, max_len=_MAXLEN,
            block_size=_BLOCK, decode_chunk=2, prefill_budget=_BLOCK,
            mesh=_serve_mesh4(),
        )
        rng = np.random.RandomState(1)
        cb.submit(list(rng.randint(1, _VOCAB, 20)), max_new_tokens=8)
        cb.step()
        cb.step()
        cb.submit(list(rng.randint(1, _VOCAB, 40)), max_new_tokens=8)
        cb.step()
        assert cb._pf is not None, "fused mesh example missed prefill"
        _CACHE["fused_mesh"] = cb
    return _CACHE["fused_mesh"]


def clear_examples() -> None:
    """Drop the cached example batchers (tests)."""
    _CACHE.clear()


_STATE_NAMES = (
    "table", "n_alloc", "fill", "tau", "tau_lp", "pos", "active",
    "remaining", "stops", "keys", "temperature", "top_p", "top_k",
)


def _chunk_state(cb) -> tuple:
    return (
        cb.d_table, cb.d_n_alloc, cb.d_fill, cb.tau, cb.d_tau_lp,
        cb.d_pos, cb.d_active, cb.d_remaining, cb.d_stops, cb.keys,
        cb.d_temps, cb.d_top_ps, cb.d_top_ks,
    )


def _build_paged_decode_chunk():
    cb = _plain_batcher()
    names = ("params", "pool") + _STATE_NAMES
    args = (cb.params, cb.pool) + _chunk_state(cb)
    kwargs = dict(config=cb.config, n_iter=2, all_greedy=True,
                  mesh=None, allow_kernel=True, with_logprobs=False)
    return names, args, kwargs


def _build_fused_chunk():
    cb = _fused_batcher()
    pf = cb._pf
    names = ("params", "pool") + _STATE_NAMES + ("pf_vec",)
    args = (cb.params, cb.pool) + _chunk_state(cb) + (pf.d_vec,)
    kwargs = dict(config=cb.config, n_iter=2, pf_chunk=pf.chunk,
                  all_greedy=True, mesh=None, allow_kernel=True,
                  with_logprobs=False)
    return names, args, kwargs


def _build_paged_decode_chunk_mesh():
    cb = _plain_batcher_mesh()
    names = ("params", "pool") + _STATE_NAMES
    args = (cb.params, cb.pool) + _chunk_state(cb)
    kwargs = dict(config=cb.config, n_iter=2, all_greedy=True,
                  mesh=cb.mesh, allow_kernel=True, with_logprobs=False,
                  placed=True)
    return names, args, kwargs


def _build_fused_chunk_mesh():
    cb = _fused_batcher_mesh()
    pf = cb._pf
    names = ("params", "pool") + _STATE_NAMES + ("pf_vec",)
    args = (cb.params, cb.pool) + _chunk_state(cb) + (pf.d_vec,)
    kwargs = dict(config=cb.config, n_iter=2, pf_chunk=pf.chunk,
                  all_greedy=True, mesh=cb.mesh, allow_kernel=True,
                  with_logprobs=False, placed=True)
    return names, args, kwargs


# Donated argname -> position in the chunk programs' return tuple
# (packed, tau, tau_lp, fill, pos, active, remaining, keys, pool[,
# pf_vec]) — the mesh pass's sharding-stability map.
_CHUNK_ALIASES = {
    "tau": 1, "tau_lp": 2, "fill": 3, "pos": 4, "active": 5,
    "remaining": 6, "keys": 7, "pool": 8,
}


def _build_spec_rounds_chunk():
    cb = _spec_batcher()
    names = ("t_params", "d_params", "t_pool", "d_pool") + _STATE_NAMES
    args = (cb.params, cb.draft_params, cb.pool,
            cb.draft_pool) + _chunk_state(cb)
    kwargs = dict(t_config=cb.config, d_config=cb.draft_config,
                  n_draft=cb.n_draft, n_rounds=2, all_greedy=True,
                  use_kernel=True, mesh=None, with_logprobs=False)
    return names, args, kwargs


def _build_paged_insert():
    import jax.numpy as jnp
    import numpy as np

    cb = _plain_batcher()
    k, P = 2, 2 * _BLOCK
    rng = np.random.RandomState(3)
    names = ("params", "pool", "block_ids", "prompt_tokens",
             "prompt_mask", "keys", "temperature", "top_p", "top_k")
    args = (
        cb.params, cb.pool,
        jnp.asarray(np.full((k, P // _BLOCK), cb.n_blocks, np.int32)),
        jnp.asarray(rng.randint(1, _VOCAB, (k, P)).astype(np.int32)),
        jnp.asarray(np.ones((k, P), bool)),
        jnp.asarray(np.zeros((k, 2), np.uint32)),
        jnp.asarray(np.zeros((k,), np.float32)),
        jnp.asarray(np.ones((k,), np.float32)),
        jnp.asarray(np.zeros((k,), np.int32)),
    )
    kwargs = dict(config=cb.config, prefill_chunk=None, mesh=None,
                  with_logprobs=False)
    return names, args, kwargs


def _build_paged_suffix_insert():
    import jax.numpy as jnp
    import numpy as np

    cb = _plain_batcher()
    k, T = 2, _BLOCK
    rng = np.random.RandomState(4)
    names = ("params", "pool", "table_rows", "n_alloc", "fill0",
             "suffix_tokens", "suffix_mask", "keys", "temperature",
             "top_p", "top_k")
    args = (
        cb.params, cb.pool,
        jnp.asarray(np.full((k, cb.blocks_per_slot), cb.n_blocks,
                            np.int32)),
        jnp.asarray(np.full((k,), 2, np.int32)),
        jnp.asarray(np.full((k,), _BLOCK, np.int32)),
        jnp.asarray(rng.randint(1, _VOCAB, (k, T)).astype(np.int32)),
        jnp.asarray(np.ones((k, T), bool)),
        jnp.asarray(np.zeros((k, 2), np.uint32)),
        jnp.asarray(np.zeros((k,), np.float32)),
        jnp.asarray(np.ones((k,), np.float32)),
        jnp.asarray(np.zeros((k,), np.int32)),
    )
    kwargs = dict(config=cb.config, prefill_chunk=None, mesh=None,
                  with_logprobs=False)
    return names, args, kwargs


def _build_scatter_rows():
    import jax.numpy as jnp

    from ..serving import pack_rows

    cb = _plain_batcher()
    state = (cb.d_table, cb.d_n_alloc, cb.d_fill, cb.d_pos,
             cb.d_active, cb.d_temps, cb.d_top_ps, cb.d_top_ks,
             cb.d_remaining, cb.d_stops)
    packed = pack_rows(
        [0], 1, cb.n_slots, cb.table, cb.n_alloc, cb.fill, cb.pos,
        cb.active, cb.temp_arr, cb.top_p_arr, cb.top_k_arr, cb.remaining,
        cb.stop_tab,
    )
    return ("state", "packed"), (state, jnp.asarray(packed)), {}


def _build_release_blocks():
    import jax.numpy as jnp
    import numpy as np

    cb = _plain_batcher()
    return (
        ("pos", "block_ids"),
        (cb.pool.pos, jnp.asarray(np.zeros((2,), np.int32))),
        {},
    )


def _build_adopt_jit():
    import numpy as np

    from ..kvcache import _pool_names, stage_restore

    cb = _plain_batcher()
    pool = cb.pool
    names = _pool_names(pool)
    slab = {
        n: (np.zeros((pool.pos.shape[1],), np.int32) if n == "pos"
            else np.zeros(
                (pool.k.shape[0], pool.k.shape[1], pool.k.shape[3],
                 pool.k.shape[4]), np.asarray(pool.k).dtype))
        for n in names
    }
    staged = stage_restore([slab], [0], cb.n_blocks)
    arrays = tuple(getattr(pool, n) for n in names)
    return (
        ("pool_arrays", "ids", "staged"),
        (arrays, staged["ids"], tuple(staged[n] for n in names)),
        {},
    )


# -- the registry ------------------------------------------------------------

_CHUNK_DONATED = (
    "pool", "fill", "tau", "tau_lp", "pos", "active", "remaining",
    "keys",
)

# Comms budgets (see CommsBudget): counts measured on this image's XLA
# at the tiny data=2 x tensor=2 geometry after the gathered-view /
# pool-plane sharding pins landed, with ~50% headroom.  The all-reduce
# populations are the per-layer tensor-axis reductions the Megatron
# matmul sharding implies (attn out + mlp down per layer, per scan
# iteration) plus scalar control reductions; the only all-gathers are
# slab-/row-/[1, V]-logits-sized.  ``max_bytes`` sits an order of
# magnitude below the full-pool byte size at the same geometry (64 KiB)
# so a pool-scale reshard can never hide inside the count budget.
_DECODE_CHUNK_COMMS = CommsBudget(
    max_count={
        "all-gather": 8, "all-reduce": 36, "collective-permute": 12,
        "reduce-scatter": 4,
    },
    max_bytes=4096,
)
_FUSED_CHUNK_COMMS = CommsBudget(
    max_count={
        "all-gather": 24, "all-reduce": 280, "collective-permute": 24,
        "reduce-scatter": 8, "all-to-all": 4,
    },
    max_bytes=16384,
)

REGISTRY: Dict[str, ProgramContract] = {
    c.name: c for c in (
        ProgramContract(
            name="_paged_decode_chunk", module="jax_llama_tpu.serving",
            donated=_CHUNK_DONATED, max_live_outputs=1,
            max_fetch_bytes_per_row=16,
            build=_build_paged_decode_chunk,
            mesh_build=_build_paged_decode_chunk_mesh,
            mesh_aliases=dict(_CHUNK_ALIASES),
            # n_iter pow2 <= decode_chunk (log2 K + 1 <= 6) x all_greedy
            # (2) x stop-table width pow2 regrowth (O(log max stops)).
            max_cache_keys=24,
            comms=_DECODE_CHUNK_COMMS,
        ),
        ProgramContract(
            name="_fused_chunk", module="jax_llama_tpu.serving",
            donated=_CHUNK_DONATED + ("pf_vec",), max_live_outputs=1,
            max_fetch_bytes_per_row=16,
            build=_build_fused_chunk,
            mesh_build=_build_fused_chunk_mesh,
            mesh_aliases=dict(_CHUNK_ALIASES, pf_vec=9),
            # n_iter pow2 (<= 6) x pf_chunk pow2-down from the budget
            # flag (<= 5) x pf_vec's token buffer in pow2 chunk counts
            # (<= 5) x all_greedy (2) — the admission sweep touches a
            # sparse corner of that product, and every axis is O(log).
            max_cache_keys=48,
            comms=_FUSED_CHUNK_COMMS,
        ),
        ProgramContract(
            name="_spec_rounds_chunk", module="jax_llama_tpu.serving",
            donated=("t_pool", "d_pool", "fill", "tau", "tau_lp",
                     "pos", "active", "remaining", "keys"),
            max_live_outputs=1, max_fetch_bytes_per_row=64,
            build=_build_spec_rounds_chunk,
            # n_rounds pow2 <= spec_rounds (<= 5) x all_greedy (2) x
            # use_kernel (2) x stop-width regrowth.
            max_cache_keys=24,
        ),
        ProgramContract(
            name="_paged_insert", module="jax_llama_tpu.serving",
            donated=("pool",), max_live_outputs=4,
            max_fetch_bytes_per_row=32,
            build=_build_paged_insert,
            # row count kb pow2 (log2 n_slots + 1) x group width P in
            # pow2 block counts (log2 blocks_per_slot + 1).
            max_cache_keys=32,
        ),
        ProgramContract(
            name="_paged_suffix_insert", module="jax_llama_tpu.serving",
            donated=("pool",), max_live_outputs=3,
            max_fetch_bytes_per_row=32,
            build=_build_paged_suffix_insert,
            # row count kb pow2 x suffix width T in pow2 block counts
            # (_suffix_pad).
            max_cache_keys=32,
        ),
        ProgramContract(
            name="_scatter_rows", module="jax_llama_tpu.serving",
            donated=("state",), max_live_outputs=0,
            max_fetch_bytes_per_row=0,
            build=_build_scatter_rows,
            # No pool rides this program — it scatters the small
            # per-slot state twins; its whole contract is the
            # donation/zero-live-output check above.
            forbid_pool_shapes=False,
            # dirty-row count Rb pow2 (log2 n_slots + 1) x stop-table
            # width pow2 regrowth.
            max_cache_keys=16,
        ),
        ProgramContract(
            name="_release_blocks", module="jax_llama_tpu.serving",
            donated=("pos",), max_live_outputs=0,
            max_fetch_bytes_per_row=0,
            build=_build_release_blocks,
            # Only the pool's [NB, BLK] pos plane rides along — that
            # is the shape no copy-class equation may produce.
            forbidden_shapes=lambda args: [tuple(args[0].shape)],
            # id batches are padded to the FIXED blocks_per_slot width
            # (_invalidate_evicted): one key per batcher geometry.
            max_cache_keys=2,
        ),
        ProgramContract(
            name="_adopt_jit", module="jax_llama_tpu.kvcache",
            donated=("pool_arrays",), max_live_outputs=0,
            max_fetch_bytes_per_row=0,
            build=_build_adopt_jit,
            # pool arrays arrive as a bare tuple (arg 0), not a
            # BlockPool — derive the forbidden shapes from them
            forbidden_shapes=lambda args: [
                tuple(a.shape) for a in args[0]
            ],
            # staged block count pow2-bucketed (kvcache.stage_restore):
            # log2 n_blocks + 1 buckets.
            max_cache_keys=12,
        ),
    )
}

# jit-decorated module-level functions that the batcher never
# dispatches and which therefore need no contract (currently none —
# every jitted program in serving.py/kvcache.py is on a dispatch path).
NON_DISPATCHED: frozenset = frozenset()

# Modules whose jitted programs must be registered.
CONTRACT_MODULES = ("serving", "kvcache")


def pool_shapes(pool) -> List[Tuple[int, ...]]:
    """Full-pool and one-plane shapes of a BlockPool example — the
    shapes no copy-class equation may produce."""
    shapes: List[Tuple[int, ...]] = []
    for arr in (pool.k, pool.v, pool.k_scale, pool.v_scale):
        if arr is None:
            continue
        shapes.append(tuple(arr.shape))        # [L, KVH, NB, BLK, ...]
        shapes.append(tuple(arr.shape[1:]))    # one-layer plane
    return shapes
