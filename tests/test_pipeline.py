"""Pipeline parallelism: GPipe schedule over the `stage` mesh axis must be
numerically transparent — same forward, loss, and gradients as the plain
scan stack (the reference has no pipeline parallelism at all, SURVEY.md
§2.13b; this is new capability, tested against the framework's own
single-device path as oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params, make_mesh
from jax_llama_tpu.models import forward
from jax_llama_tpu.parallel import shard_params, use_mesh
from jax_llama_tpu.train import init_train_state, lm_loss, make_optimizer, train_step

CFG = dict(
    vocab_size=128, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=32, dtype="float32", param_dtype="float32",
)


def _setup(stage, **mesh_axes):
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    mesh = make_mesh(stage=stage, **mesh_axes, devices=jax.devices()[: stage * int(np.prod(list(mesh_axes.values()) or [1]))])
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, config.vocab_size, (4, 16)),
        jnp.int32,
    )
    return config, params, mesh, tokens


def _reference_logits(config, params, tokens):
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    logits, _ = forward(params, tokens, pos, config)
    return np.asarray(logits)


@pytest.mark.parametrize("stage,extra", [(2, {}), (4, {}), (2, {"tensor": 2})])
def test_pipeline_forward_matches_plain(stage, extra):
    config, params, mesh, tokens = _setup(stage, **extra)
    want = _reference_logits(config, params, tokens)

    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    sharded = shard_params(params, mesh, config)

    @jax.jit
    def run(p, t, q):
        with use_mesh(mesh):
            return forward(p, t, q, config)[0]

    got = np.asarray(run(sharded, tokens, pos))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_pipeline_microbatch_counts():
    config, params, mesh, tokens = _setup(2)
    want = _reference_logits(config, params, tokens)
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    for m in (1, 2, 4):
        cfg_m = config.replace(pp_microbatches=m)

        @jax.jit
        def run(p, t, q):
            with use_mesh(mesh):
                return forward(p, t, q, cfg_m)[0]

        got = np.asarray(run(shard_params(params, mesh, cfg_m), tokens, pos))
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_pipeline_respects_padding():
    """Left-padded rows (-1 positions) must mask identically under pp."""
    config, params, mesh, tokens = _setup(2)
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    pos = pos.at[0, :5].set(-1)  # row 0: 5 pad slots
    logits, _ = forward(params, tokens, pos, config)
    want = np.asarray(logits)

    @jax.jit
    def run(p, t, q):
        with use_mesh(mesh):
            return forward(p, t, q, config)[0]

    got = np.asarray(run(shard_params(params, mesh, config), tokens, pos))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_pipeline_grads_match_plain():
    config, params, mesh, tokens = _setup(2)
    grads_plain = jax.grad(lm_loss)(params, tokens, config)

    sharded = shard_params(params, mesh, config)

    @jax.jit
    def g(p, t):
        with use_mesh(mesh):
            return jax.grad(lm_loss)(p, t, config)

    grads_pp = g(sharded, tokens)
    flat_a, _ = jax.tree.flatten(grads_plain)
    flat_b, _ = jax.tree.flatten(grads_pp)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
        )


def test_pipeline_train_step():
    config, params, mesh, tokens = _setup(2, tensor=2)
    optimizer = make_optimizer(learning_rate=1e-3)
    state = init_train_state(shard_params(params, mesh, config), optimizer)
    state, loss = train_step(state, tokens, config, optimizer, mesh=mesh)
    assert np.isfinite(float(loss))
    state2, loss2 = train_step(state, tokens, config, optimizer, mesh=mesh)
    assert float(loss2) < float(loss)  # tiny model overfits one batch fast


def test_pipeline_rejects_seq_axis():
    config, params, mesh, tokens = _setup(2, seq=2)
    config = config.replace(attn_impl="ring")
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with pytest.raises(NotImplementedError):
        with use_mesh(mesh):
            forward(shard_params(params, mesh, config), tokens, pos, config)


def test_stage_must_divide_layers():
    config = get_config("tiny", **{**CFG, "n_layers": 3})
    params = init_params(jax.random.PRNGKey(0), config)
    mesh = make_mesh(stage=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="stage"):
        shard_params(params, mesh, config)


def test_pipeline_rejects_cache_decode():
    """Decode over a KV cache must refuse on a stage>1 mesh (the scan path
    would silently all-gather stage-sharded weights every step)."""
    from jax_llama_tpu.models.llama import init_cache

    config, params, mesh, tokens = _setup(2)
    cache = init_cache(config, batch=4, max_len=16)
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (4, 16))
    with pytest.raises(NotImplementedError, match="stage"):
        with use_mesh(mesh):
            forward(shard_params(params, mesh, config), tokens, pos, config,
                    cache=cache)


@pytest.mark.slow  # ~17 s; pipeline+dropout composition, tier-1 headroom
def test_pipeline_dropout_training():
    """Dropout composes with stage > 1: per-layer keys ride the staged
    tree and each stage folds in its current microbatch index, so every
    (layer, microbatch) pair draws an independent mask."""
    config, params, mesh, tokens = _setup(2)
    dcfg = config.replace(
        resid_pdrop=0.2, attn_pdrop=0.1, embd_pdrop=0.1, pp_microbatches=2
    )
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    # Rows 0/1 and 2/3 identical: they land in DIFFERENT microbatches, so
    # under dropout their outputs must diverge (per-microbatch folding);
    # without dropout they must match exactly.
    tokens = jnp.concatenate([tokens[:2], tokens[:2]], axis=0)
    sharded = shard_params(params, mesh, dcfg)

    @jax.jit
    def run(p, t, q, rng):
        with use_mesh(mesh):
            return forward(p, t, q, dcfg, dropout_rng=rng)[0]

    @jax.jit
    def run_det(p, t, q):
        with use_mesh(mesh):
            return forward(p, t, q, dcfg)[0]

    det = np.asarray(run_det(sharded, tokens, pos))
    np.testing.assert_array_equal(det[:2], det[2:])  # sanity: same rows

    a = np.asarray(run(sharded, tokens, pos, jax.random.PRNGKey(1)))
    a2 = np.asarray(run(sharded, tokens, pos, jax.random.PRNGKey(1)))
    b = np.asarray(run(sharded, tokens, pos, jax.random.PRNGKey(2)))
    np.testing.assert_array_equal(a, a2)        # same key -> same masks
    assert np.abs(a - det).max() > 0            # dropout actually applied
    assert np.abs(a - b).max() > 0              # key-sensitive
    assert np.abs(a[:2] - a[2:]).max() > 0      # per-microbatch masks

    # Pipeline training with dropout learns.
    opt = make_optimizer(learning_rate=1e-2, warmup_steps=0)
    state = init_train_state(sharded, opt)

    losses = []
    for i in range(20):
        state, loss = train_step(
            state, tokens, dcfg, opt, mesh=mesh,
            dropout_rng=jax.random.fold_in(jax.random.PRNGKey(7), i),
        )
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 0.9, losses[::5]
