"""`_paged_decode_chunk` at one iteration a dispatch against eight, block by
block: the stepping helper the block parity tests share
(tests/paged_steps.py) decodes the same row both ways."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from paged_steps import decode_row
from test_serving_mixed import CFG, _tiny_block

from jax_llama_tpu import get_config, init_params, serving

BLK, NB, P, G = 16, 16, 96, 8      # a prompt of four windows of 24


@pytest.mark.parametrize(
    "kind", ["dense", "latent", "windowed", "recurrent", "parallel-mixer"])
def test_one_iteration_a_dispatch_decodes_what_eight_do(kind):
    """Eight greedy tokens behind a 96-token prompt, over the paged kernel:
    K = 1 (eight dispatches) and K = 8 (one) emit the same tokens and leave
    the same cache positions and the same per-slot state."""
    config = (get_config("tiny", **CFG) if kind == "dense"
              else _tiny_block(kind))
    params = init_params(jax.random.PRNGKey(1), config)
    toks = jnp.asarray(np.random.RandomState(3).randint(
        1, config.vocab_size, size=(1, P)))
    i32, f32 = jnp.int32, jnp.float32
    one = lambda v, dt: jnp.full((1,), v, dt)  # noqa: E731
    state = (one(0, i32),) if config.recurrent_state else ()

    def run(n_iter):
        pool = serving.init_pool(config, NB, BLK, n_slots=1)
        tau, _, _, _, pool = serving._paged_insert(
            params, pool, jnp.arange(P // BLK, dtype=i32)[None], toks,
            jnp.ones((1, P), bool), jnp.zeros((1, 2), jnp.uint32),
            one(0.0, f32), one(1.0, f32), one(0, i32), *state,
            config=config, prefill_chunk=32)
        table = jnp.full((1, 8), NB, i32).at[0, :7].set(jnp.arange(7))
        return decode_row(params, config, pool, table, 7, P, int(tau[0]), G,
                          use_kernel=True, n_iter=n_iter)

    (single, pool1, stats1), (chunk, pool8, stats8) = run(1), run(8)
    assert len(single) == G + 1 and single == chunk
    assert np.array_equal(np.asarray(pool1.pos), np.asarray(pool8.pos))
    for name in serving._STATE:
        if getattr(pool1, name) is not None:
            np.testing.assert_allclose(
                getattr(pool1, name), getattr(pool8, name), rtol=2e-5, atol=2e-6)
    if stats1 is not None:
        assert np.array_equal(stats1, stats8)
