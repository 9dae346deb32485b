"""The tile of the grouped expert product (`ops/moe._tiling`): a function of
the product's shape and dtype alone.  The tiles it gives the four published
configurations' products are pinned here (PERF.md section 6, PR 52 has the
on-chip sweep that chose the rule; `tests/test_chip_compile.py` compiles
them for a described v5e)."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from jax_llama_tpu.ops import moe

ROOT = Path(__file__).resolve().parent.parent
# configuration file -> the tile of its gate/up product [D, 2F] and of its
# down product [F, D], bfloat16
PINNED = {
    "Xing4.0-29B-A4B": {"gate_up": (128, 3584, 512), "down": (128, 1024, 1792)},
    "Trinity-Mini": {"gate_up": (128, 2048, 1024), "down": (128, 1024, 2048)},
    "kanana-2-30b-a3b-instruct-2601": {"gate_up": (128, 2048, 768), "down": (128, 768, 2048)},
    "Keye-VL-2.0-30B-A3B": {"gate_up": (128, 2048, 768), "down": (128, 768, 2048)},
}


def _products(name):
    keys = json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())
    d, f = keys["hidden_size"], keys["moe_intermediate_size"]
    return {"gate_up": (d, 2 * f), "down": (f, d)}


@pytest.mark.parametrize("op", ["gate_up", "down"])
@pytest.mark.parametrize("name", sorted(PINNED))
def test_a_published_product_keeps_its_whole_contraction_resident(name, op):
    k, n = _products(name)[op]
    tm, tk, tn = moe._tiling(k, n, jnp.bfloat16)
    assert (tm, tk, tn) == PINNED[name][op]
    assert tm == moe._TILE_M
    assert tk == k, "one k-tile: an expert's block stays put between its visits"
    assert n % tn == 0 and tn % 128 == 0
    assert moe._block_bytes(tm, tk, tn, 2) <= moe._BLOCK_BUDGET
    wider = [t for t in range(tn + 128, n + 1, 128) if n % t == 0]
    assert all(moe._block_bytes(tm, tk, t, 2) > moe._BLOCK_BUDGET for t in wider)


@pytest.mark.parametrize(
    "k,n,dtype,want",
    [
        # a contraction too long to hold whole at any width: the ladder's
        # largest divisor, as before, and the widest tile that fits beside it
        (14336, 4096, jnp.bfloat16, (128, 1024, 2048)),
        (14336 + 128 * 3, 4096, jnp.bfloat16, (128, 128, 4096)),
        # float32 operands: twice the bytes, half the width
        (3584, 2048, jnp.float32, (128, 3584, 256)),
        # no multiple of 128 divides N, nor any of the ladder K: whole both
        (96, 80, jnp.bfloat16, (128, 96, 80)),
        # too long AND divisible by nothing of the ladder: K whole at the
        # narrowest width (the compiler's to refuse, not a silent wrong tile)
        (2 ** 16 + 1, 256, jnp.bfloat16, (128, 2 ** 16 + 1, 128)),
    ],
    ids=["long-k", "long-k-odd", "float32", "tiny", "indivisible"],
)
def test_the_rule_falls_back_by_shape(k, n, dtype, want):
    assert moe._tiling(k, n, dtype) == want


def test_the_rules_tile_multiplies_uneven_unaligned_groups():
    """Upstream `megablox.gmm`, interpreted, with the rule's tile: groups of
    uneven sizes that start inside row tiles, one of them empty, rows past
    the last group; against `lax.ragged_dot`."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k, n, experts = 384, 96, 256, 6
    sizes = jnp.asarray([70, 0, 129, 5, 100, 37], jnp.int32)   # 341 of 384 rows
    kx, kw = jax.random.split(jax.random.PRNGKey(52))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (experts, k, n), jnp.float32) * k ** -0.5
    tile = moe._tiling(k, n, x.dtype)
    assert tile == (128, 96, 256)
    got = gmm(x, w, sizes, preferred_element_type=x.dtype, tiling=tile, interpret=True)
    want = lax.ragged_dot(x, w, sizes)
    live = int(sizes.sum())
    np.testing.assert_allclose(
        np.asarray(got[:live]), np.asarray(want[:live]), rtol=2e-5, atol=2e-5)
