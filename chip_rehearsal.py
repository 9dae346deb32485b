#!/usr/bin/env python3
"""Rehearsal 3: compile chip_smoke.py's programs for a v5e WITHOUT the chip.

    JAX_PLATFORMS=cpu python chip_rehearsal.py            # one chip
    JAX_PLATFORMS=cpu python chip_rehearsal.py --chips 4  # --serve-mesh 1,4

Section 2 of the on-chip-measurement guide: the TPU compiler is installed
here and compiles for a chip that is *described* (``v5e:2x2``), not
attached.  This hands ``eval_shape``d weights and pool, placed on the
described devices, to the jitted serving programs the smoke hits —
``init_params`` (the checkpoint writer), ``_paged_insert``,
``_paged_decode_chunk``, ``_fused_chunk``, ``_paged_suffix_insert`` — at
``chip_smoke.FULL``'s geometry, and prints each program's
``memory_analysis()`` and whether a Pallas kernel (``tpu_custom_call``) is
in it.  Nothing executes: a compile that passes is not a chip run.

The kernels alone, at the same head geometry, are
``tests/test_chip_compile.py`` (tier-1).  The persistent compilation cache
stays OFF here: an executable compiled for a described chip is written to
the cache but cannot be read back without one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke
from jax_llama_tpu import get_config, init_params
from jax_llama_tpu import serving
from jax_llama_tpu.parallel import serve_mesh as smesh
from jax_llama_tpu.parallel.mesh import make_mesh
from jax_llama_tpu.parallel.partition import shard_abstract


def _interpret_off() -> None:
    """``jax.default_backend()`` is the CPU here, and every non-TPU
    backend makes the kernels interpret; the rehearsal compiles the real
    Mosaic kernels, so steer that ONE predicate from this script (the
    guide: steer in the rehearsal, never through a product option)."""
    import importlib

    # By module NAME: ``jax_llama_tpu.ops`` re-exports functions called
    # flash_attention / paged_attention that shadow the submodules.
    for name in ("flash_attention", "paged_attention"):
        mod = importlib.import_module(f"jax_llama_tpu.ops.{name}")
        mod._resolve_interpret = lambda interpret=None: False


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    _interpret_off()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    spec = chip_smoke.FULL
    config = get_config(spec.preset, **dict(spec.overrides)).replace(
        attn_impl="auto"
    )
    if args.chips == 4:
        mesh = smesh.build_serve_mesh(
            smesh.ServeMeshSpec(data=1, tensor=4), devices=list(topo.devices)
        )
    else:
        mesh = make_mesh(tensor=1, devices=list(topo.devices)[:1])
    placed = smesh.placement_ok(config, mesh, spec.slots)
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def rows(shape, dtype):
        return sds(shape, dtype, NamedSharding(mesh, smesh.row_pspec(len(shape))))

    params = shard_abstract(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config)),
        mesh, config,
    )
    B, BLK = spec.slots, 128
    MB = config.max_seq_len // BLK
    NB = B * MB
    pool_shapes = jax.eval_shape(lambda: serving.init_pool(config, NB, BLK))
    pool = serving.BlockPool(**{
        name: sds(a.shape, a.dtype,
                  NamedSharding(mesh, smesh.pool_pspec(name, a.ndim))
                  if placed else rep)
        for name in ("k", "v", "pos")
        for a in [getattr(pool_shapes, name)]
    })
    # Per-device plane dims: a placed pool shards KVH over ``tensor``.
    tp = mesh.shape["tensor"] if placed else 1
    L_, KVH_, *rest = pool_shapes.k.shape
    pool_dims = ",".join(str(n) for n in (L_, KVH_ // tp, *rest))
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    state = dict(
        table=rows((B, MB), i32), n_alloc=rows((B,), i32),
        fill=rows((B,), i32), tau=rows((B,), i32), tau_lp=rows((B,), f32),
        pos=rows((B,), i32), active=rows((B,), jnp.bool_),
        remaining=rows((B,), i32), stops=rows((B, 1), i32),
        keys=rows((B, 2), u32), temperature=rows((B,), f32),
        top_p=rows((B,), f32), top_k=rows((B,), i32),
    )
    common = dict(config=config, mesh=mesh, with_logprobs=True, placed=placed)
    report = {}

    def compile_one(name, fn, *a, want_kernel=True, **kw):
        t0 = time.monotonic()
        compiled = fn.lower(*a, **kw).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        has_kernel = "tpu_custom_call" in text
        # The hot-path invariant tests/test_tpu_compiled.py pins on the
        # chip, asked here at the real pool size: no copy of a whole
        # [L, KVH, NB, BLK, d] plane inside the program.
        pool_copies = sum(
            1 for ln in text.splitlines()
            if " copy(" in ln and f"[{pool_dims}]" in ln
        )
        report[name] = {
            "compile_s": round(time.monotonic() - t0, 1),
            "argument_gb": round(ma.argument_size_in_bytes / 1e9, 3),
            "output_gb": round(ma.output_size_in_bytes / 1e9, 3),
            "alias_gb": round(ma.alias_size_in_bytes / 1e9, 3),
            "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
            "tpu_custom_call": has_kernel,
            "pool_sized_copies": pool_copies,
        }
        print(json.dumps({name: report[name]}), flush=True)
        if want_kernel and not has_kernel:
            raise SystemExit(f"{name}: no Pallas kernel in the program")

    if args.chips == 1:
        compile_one(
            "init_params", jax.jit(init_params, static_argnums=1),
            sds((2,), u32), config, want_kernel=False,
        )
    k, Ppad = 1, config.max_seq_len
    compile_one(
        "_paged_insert", serving._paged_insert, params, pool,
        sds((k, Ppad // BLK), i32), sds((k, Ppad), i32),
        sds((k, Ppad), jnp.bool_), sds((k, 2), u32), sds((k,), f32),
        sds((k,), f32), sds((k,), i32), **common,
    )
    decode_args = [state[n] for n in (
        "table", "n_alloc", "fill", "tau", "tau_lp", "pos", "active",
        "remaining", "stops", "keys", "temperature", "top_p", "top_k")]
    compile_one(
        "_paged_decode_chunk", serving._paged_decode_chunk, params, pool,
        *decode_args, n_iter=8, all_greedy=True, allow_kernel=True, **common,
    )
    compile_one(
        "_fused_chunk", serving._fused_chunk, params, pool, *decode_args,
        sds((serving._PF_HEADER + 512,), i32),
        n_iter=8, pf_chunk=512, all_greedy=True, allow_kernel=True, **common,
    )
    T = BLK
    compile_one(
        "_paged_suffix_insert", serving._paged_suffix_insert, params, pool,
        sds((k, MB), i32), sds((k,), i32), sds((k,), i32), sds((k, T), i32),
        sds((k, T), jnp.bool_), sds((k, 2), u32), sds((k,), f32),
        sds((k,), f32), sds((k,), i32), want_kernel=False, **common,
    )
    print(json.dumps({
        "rehearsal": "compiled, nothing executed", "chips": args.chips,
        "topology": "v5e:2x2", "attn_impl": config.attn_impl,
        "placed": placed,
        "programs": report,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
