"""One greedy row stepped through the decode program the batcher dispatches.

The block parity tests (tests/test_mla_moe.py, test_afmoe.py, test_sambay.py,
test_falcon_h1.py) prefill a prompt with ``_paged_insert`` and then hold every
decoded token to their reference; this is how they decode."""
import jax.numpy as jnp
import numpy as np

from jax_llama_tpu import serving


def decode_row(params, config, pool, table, n_alloc, fill, tau, n_tokens, *,
               use_kernel, n_iter=1):
    """Advance the one row of a ``[1, MB]`` ``table`` (``n_alloc`` blocks
    held, ``fill`` tokens in the cache, ``tau`` pending) by ``n_tokens``
    greedy decode iterations through ``_paged_decode_chunk``, ``n_iter`` a
    dispatch.  Returns (``tau`` and the ``n_tokens`` draws behind it, the
    pool, the pool's counters summed over the packed fetches or None)."""
    assert n_tokens % n_iter == 0, (n_tokens, n_iter)
    i32, f32 = jnp.int32, jnp.float32
    one = lambda v, dt: jnp.full((1,), v, dt)  # noqa: E731
    fill, pos, tau = one(fill, i32), one(fill, i32), one(tau, i32)
    tau_lp, active = one(0.0, f32), jnp.ones((1,), bool)
    remaining = one(n_tokens + 1, i32)     # never the reason a row ends
    keys = jnp.zeros((1, 2), jnp.uint32)
    served, stats = [], None
    for _ in range(n_tokens // n_iter):
        (packed, tau, tau_lp, fill, pos, active, remaining, keys,
         pool) = serving._paged_decode_chunk(
            params, pool, table, one(n_alloc, i32), fill, tau, tau_lp, pos,
            active, remaining, jnp.full((1, 1), -1, i32), keys,
            one(0.0, f32), one(1.0, f32), one(0, i32), config=config,
            n_iter=n_iter, all_greedy=True, allow_kernel=use_kernel)
        packed = np.asarray(packed)
        served += [int(t) for t in packed[0, 0]]
        if pool.stats is not None:
            # ``serving._pack_stats``: the counters ride behind the tokens
            # and start again from zero in the pool.
            counts = packed[1:].reshape(-1)[:pool.stats.shape[0]]
            stats = counts if stats is None else stats + counts
    return served + [int(tau[0])], pool, stats
