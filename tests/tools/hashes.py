"""sha256 of the lowered text of the serving programs the cells dispatch, for
each of the six blocks (the latent one also with a residual of four streams)
at the tests' tiny widths, from abstract operands.

The proof that a change moved no program a cell runs: run it from the root
of each tree on a CPU and compare the two outputs (~1 min a tree):

    python tests/tools/hashes.py            # this tree
    cd <other tree> && python <this file>   # imports come from the cwd

`_fused_chunk` at K = 2 and 8 (the two values `_pick_chunk` gives a fused
dispatch), `_paged_decode_chunk` at K = 8, `_paged_insert`,
`_paged_suffix_insert` (never dispatched for a block with a recurrent state)
and `_scatter_rows` (one program for every block).  Not a test: tier-1 does
not collect it.  On the chip two checkouts never hash equal (a Mosaic
kernel's payload carries the call site's path): compare CPU lowerings."""
import hashlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
for _p in (os.path.join(os.getcwd(), "tests"), os.getcwd()):
    if _p not in sys.path:  # a script's imports; a test run has both
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import test_serving_mixed as tsm  # noqa: E402
from jax_llama_tpu import get_config, init_params, serving  # noqa: E402
from test_serving_fused import fused_chunk_operand_shapes  # noqa: E402

BLK, ROWS, CHUNK, PROMPT = 16, 4, 32, 64
sds = jax.ShapeDtypeStruct
i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32


def digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


# kind -> the test module whose `CONFIG_FILE`, `TINY` and `BOOKKEEPING` give
# the block's tiny configuration (`tsm._tiny_block`'s recipe, kept here so
# that this file runs from the root of a tree older than a kind it names).
BLOCKS = {
    "latent": "test_mla_moe", "windowed": "test_afmoe",
    "recurrent": "test_sambay", "parallel-mixer": "test_falcon_h1",
    "sparse": "test_dsa_moe",        # the sixth block
    "streams": "test_mhc_mla_moe",   # the latent block, four residual streams
}


def configs():
    import importlib

    from jax_llama_tpu import config as config_mod

    yield "dense", get_config("tiny", **tsm.CFG)
    for kind, module in BLOCKS.items():
        mod = importlib.import_module(module)
        raw = dict(json.loads(mod.CONFIG_FILE.read_text()), **mod.TINY)
        yield kind, config_mod.from_published(
            {k: v for k, v in raw.items() if k not in mod.BOOKKEEPING},
            max_seq_len=128, attn_impl="auto")


def calls(kind, config):
    """(name, jitted program, operands, static arguments) of each program,
    from abstract operands: `.trace` or `.lower` them."""
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(1), config))
    mb = config.max_seq_len // BLK
    pool = jax.eval_shape(lambda: serving.init_pool(
        config, ROWS * mb, BLK, n_slots=ROWS, n_snapshots=ROWS))
    ops = fused_chunk_operand_shapes(sds, ROWS, mb, CHUNK)
    snap = (sds((2,), i32),) if config.recurrent_state else ()
    for k in (2, 8):
        yield f"{kind}.fused.k{k}", serving._fused_chunk, (
            params, pool, *ops, *snap), dict(
            config=config, n_iter=k, pf_chunk=CHUNK, all_greedy=True,
            allow_kernel=True)
    yield f"{kind}.decode.k8", serving._paged_decode_chunk, (
        params, pool, *ops[:-1]), dict(
        config=config, n_iter=8, all_greedy=True, allow_kernel=True)
    policy = (sds((1, 2), u32), sds((1,), f32), sds((1,), f32), sds((1,), i32))
    slot = (sds((1,), i32),) if config.recurrent_state else ()
    yield f"{kind}.insert", serving._paged_insert, (
        params, pool, sds((1, PROMPT // BLK), i32), sds((1, PROMPT), i32),
        sds((1, PROMPT), jnp.bool_), *policy, *slot), dict(
        config=config, prefill_chunk=CHUNK)
    if not config.recurrent_state:
        yield f"{kind}.suffix_insert", serving._paged_suffix_insert, (
            params, pool, sds((1, mb), i32), sds((1,), i32), sds((1,), i32),
            sds((1, CHUNK), i32), sds((1, CHUNK), jnp.bool_), *policy), dict(
            config=config, prefill_chunk=CHUNK)


def programs(kind, config):
    for name, program, operands, static in calls(kind, config):
        yield name, program.lower(*operands, **static)


def scatter_rows():
    mb = tsm.CFG["max_seq_len"] // BLK
    row = lambda dt, *tail: sds((ROWS, *tail), dt)  # noqa: E731
    state = (row(i32, mb), row(i32), row(i32), row(i32), row(jnp.bool_),
             row(f32), row(f32), row(i32), row(i32), row(i32, 1))
    packed = sds((2, len(serving._ROW_FIELDS) + mb + 1), i32)
    return serving._scatter_rows.lower(state, packed)


def main():
    out = {}
    for kind, config in configs():
        for name, lowered in programs(kind, config):
            out[name] = digest(lowered)
    out["scatter_rows"] = digest(scatter_rows())
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
