"""Driver-contract smoke tests for __graft_entry__ (CPU, 8 virtual devs)."""

import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__ as graft


def test_entry_returns_jittable_fn():
    fn, args = graft.entry()
    # Validate traceability/shapes without paying a full CPU execution.
    out = jax.eval_shape(fn, *args)
    params, tokens, positions = args
    assert out.shape == (*tokens.shape, 32000)


# slow (r06 budget rebalance): the 8-device dryrun sweep is ~70 s of
# CPU compiles — the single largest tier-1 item — and its mesh
# configurations are also exercised by test_partition / test_pipeline
# and the MULTICHIP_r* trajectory; `pytest -m slow` / the full suite
# keep it covered.
@pytest.mark.slow
def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


def test_mesh_factors():
    assert graft._mesh_factors(8) == (1, 2, 2, 2)
    assert graft._mesh_factors(16) == (2, 2, 2, 2)
    assert graft._mesh_factors(4) == (1, 1, 2, 2)
    assert graft._mesh_factors(2) == (1, 1, 1, 2)
    assert graft._mesh_factors(1) == (1, 1, 1, 1)
    for n in (1, 2, 4, 6, 8, 16):
        d, f, s, t = graft._mesh_factors(n)
        assert d * f * s * t == n
