"""Training tests: gradient step mechanics, overfit sanity, sharded step."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from jax_llama_tpu import config as cfg_lib
from jax_llama_tpu.models import init_params
from jax_llama_tpu.parallel import make_mesh, shard_params, use_mesh
from jax_llama_tpu.train import (
    init_train_state,
    lm_loss,
    make_optimizer,
    train_step,
)

CFG = cfg_lib.tiny(max_seq_len=32)
OPT = make_optimizer(learning_rate=1e-2, warmup_steps=0)


def test_loss_is_finite_and_near_uniform_at_init():
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, CFG.vocab_size, (2, 16)))
    loss = lm_loss(params, tokens, CFG)
    assert np.isfinite(float(loss))
    # Random init ≈ uniform over vocab.
    assert abs(float(loss) - np.log(CFG.vocab_size)) < 1.0


def test_overfit_single_batch():
    params = init_params(jax.random.PRNGKey(0), CFG)
    state = init_train_state(params, OPT)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, CFG.vocab_size, (2, 16)))
    losses = []
    for _ in range(30):
        state, loss = train_step(state, tokens, CFG, OPT)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses[::10]
    assert int(state.step) == 30


def test_loss_mask_excludes_positions():
    from jax_llama_tpu.models import forward

    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6]])
    mask = jnp.asarray([[True, True, True, False, False, False]])
    got = float(lm_loss(params, tokens, CFG, loss_mask=mask))

    # Query-indexed convention: mask[:, t] gates the loss predicting token
    # t+1 from position t, so mask [T,T,T,F,F,F] keeps the loss terms at
    # query positions 0,1,2 (targets 2,3,4) — lm_loss drops mask[:, -1].
    logits, _ = forward(
        params, tokens[:, :-1],
        jnp.arange(5)[None, :], CFG,
    )
    logp = jax.nn.log_softmax(np.asarray(logits, np.float64), axis=-1)
    targets = np.asarray(tokens)[0, 1:]
    nll = -logp[0, np.arange(5), targets]
    want = nll[:3].mean()  # query positions 0,1,2 are unmasked
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_fused_loss_matches_dense_value_and_grads():
    """The chunked LM-head cross-entropy (ops.loss) must reproduce the
    dense log-softmax path: value to 1e-6 rel and every parameter
    gradient to 1e-5 rel (fp32 CPU) — masked, with a non-chunk-multiple
    row count exercising the weight-0 padding."""
    params = init_params(jax.random.PRNGKey(3), CFG)
    rng = np.random.RandomState(7)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, (2, 13)))
    mask = jnp.asarray(rng.rand(2, 13) > 0.3)

    vf, gf = jax.value_and_grad(
        lambda p: lm_loss(p, tokens, CFG, loss_mask=mask, fused=True)
    )(params)
    vd, gd = jax.value_and_grad(
        lambda p: lm_loss(p, tokens, CFG, loss_mask=mask, fused=False)
    )(params)
    np.testing.assert_allclose(float(vf), float(vd), rtol=1e-6)
    flat_f = jax.tree_util.tree_leaves_with_path(gf)
    flat_d = jax.tree_util.tree_leaves_with_path(gd)
    for (path, lf), (_, ld) in zip(flat_f, flat_d):
        denom = max(np.abs(np.asarray(ld)).max(), 1e-8)
        rel = np.abs(np.asarray(lf) - np.asarray(ld)).max() / denom
        assert rel < 1e-5, (jax.tree_util.keystr(path), rel)


def test_fused_loss_tied_embeddings_and_multichunk():
    """Tied-embedding head (the [V, D] layout is folded into the einsum,
    never transposed) and a multi-chunk row count agree with the dense
    path; chunk-size invariance via a direct chunked_softmax_xent call."""
    from jax_llama_tpu.ops.loss import chunked_softmax_xent

    tied = cfg_lib.tiny(max_seq_len=32, tie_word_embeddings=True)
    params = init_params(jax.random.PRNGKey(4), tied)
    tokens = jnp.asarray(
        np.random.RandomState(8).randint(0, tied.vocab_size, (2, 16))
    )
    vf = float(lm_loss(params, tokens, tied, fused=True))
    vd = float(lm_loss(params, tokens, tied, fused=False))
    np.testing.assert_allclose(vf, vd, rtol=1e-6)

    rng = np.random.RandomState(9)
    N, D, V = 37, 16, 24
    h = jnp.asarray(rng.randn(N, D), jnp.float32)
    head = jnp.asarray(rng.randn(D, V), jnp.float32)
    tgt = jnp.asarray(rng.randint(0, V, N))
    w = jnp.asarray(rng.rand(N) > 0.2, jnp.float32)
    outs = [
        chunked_softmax_xent(h, head, tgt, w, chunk=c) for c in (8, 16, 64)
    ]
    for tot, wsum in outs[1:]:
        np.testing.assert_allclose(float(tot), float(outs[0][0]), rtol=1e-6)
        np.testing.assert_allclose(float(wsum), float(outs[0][1]))


def test_sharded_train_step_matches_single_device():
    # train_step donates its state, so each path gets its own params copy
    # (same seed -> identical values).
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, CFG.vocab_size, (4, 16)))

    state = init_train_state(init_params(jax.random.PRNGKey(0), CFG), OPT)
    _, loss_single = train_step(state, tokens, CFG, OPT)

    mesh = make_mesh(data=2, fsdp=2, tensor=2)
    sharded = shard_params(
        init_params(jax.random.PRNGKey(0), CFG), mesh, CFG, fsdp=True
    )
    sstate = init_train_state(sharded, OPT)
    sstate, loss_sharded = train_step(sstate, tokens, CFG, OPT, mesh=mesh)
    np.testing.assert_allclose(
        float(loss_sharded), float(loss_single), rtol=1e-5
    )
    # Params actually changed and stayed finite.
    qkv = np.asarray(sstate.params["layers"]["qkv"])
    assert np.isfinite(qkv).all()


# ---------------------------------------------------------------------------
# Dropout (reference capability: config.py:85-87, model.py:166-168,296-299)
# ---------------------------------------------------------------------------

DROP_CFG = cfg_lib.tiny(
    max_seq_len=32, resid_pdrop=0.2, embd_pdrop=0.1, attn_pdrop=0.1,
    # Pin the statistical tests to the xla path: since attn_pdrop composes
    # with flash, "auto" would route these T=16 forwards through the
    # interpret-mode Pallas kernel (slow on CPU); flash-dropout semantics
    # are covered by test_flash_attention and test_dropout_refusals.
    attn_impl="xla",
)


def test_dropout_perturbs_loss_deterministically():
    params = init_params(jax.random.PRNGKey(0), DROP_CFG)
    tokens = jnp.asarray(
        np.random.RandomState(3).randint(0, DROP_CFG.vocab_size, (2, 16))
    )
    base = float(lm_loss(params, tokens, DROP_CFG))
    a = float(lm_loss(params, tokens, DROP_CFG, dropout_rng=jax.random.PRNGKey(1)))
    a2 = float(lm_loss(params, tokens, DROP_CFG, dropout_rng=jax.random.PRNGKey(1)))
    b = float(lm_loss(params, tokens, DROP_CFG, dropout_rng=jax.random.PRNGKey(2)))
    assert a == a2                      # same key -> same masks
    assert a != base and b != base and a != b
    # All-zero rates with a key is exactly the deterministic path.
    zero = cfg_lib.tiny(max_seq_len=32)
    z = float(lm_loss(params, tokens, zero, dropout_rng=jax.random.PRNGKey(1)))
    np.testing.assert_allclose(z, float(lm_loss(params, tokens, zero)), rtol=1e-6)


@pytest.mark.slow  # ~18 s of statistical averaging; tier-1 headroom
def test_dropout_mean_approximates_deterministic_loss():
    """Inverted dropout preserves expectations: averaging over many masks
    should land near the no-dropout loss (loose tolerance, tiny model)."""
    params = init_params(jax.random.PRNGKey(0), DROP_CFG)
    tokens = jnp.asarray(
        np.random.RandomState(4).randint(0, DROP_CFG.vocab_size, (2, 16))
    )
    base = float(lm_loss(params, tokens, DROP_CFG))
    ls = [
        float(lm_loss(params, tokens, DROP_CFG, dropout_rng=jax.random.PRNGKey(i)))
        for i in range(24)
    ]
    assert abs(np.mean(ls) - base) < 0.35, (np.mean(ls), base)


def test_train_step_with_dropout_rng_learns():
    params = init_params(jax.random.PRNGKey(0), DROP_CFG)
    state = init_train_state(params, OPT)
    tokens = jnp.asarray(
        np.random.RandomState(5).randint(0, DROP_CFG.vocab_size, (2, 16))
    )
    rng = jax.random.PRNGKey(7)
    losses = []
    for _ in range(30):
        state, loss = train_step(
            state, tokens, DROP_CFG, OPT, dropout_rng=rng
        )
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 0.7, losses[::10]
    # The per-step fold_in gives different masks per step: consecutive
    # losses on the same batch are not byte-identical.
    assert len(set(losses)) > 25


def test_dropout_refusals():
    import pytest

    from jax_llama_tpu.models import forward, init_cache

    params = init_params(jax.random.PRNGKey(0), DROP_CFG)
    tokens = jnp.asarray([[1, 2, 3, 4]])
    pos = jnp.arange(4)[None, :]
    cache = init_cache(DROP_CFG, 1, max_len=8)
    with pytest.raises(ValueError, match="training-only"):
        forward(params, tokens, pos, DROP_CFG, cache=cache,
                dropout_rng=jax.random.PRNGKey(0))
    # attn_pdrop composes with every attention path: flash generates its
    # mask in-kernel, ring hashes absolute positions chunkwise (tested on
    # a seq=2 mesh in test_ring.py); off-mesh "ring" falls back to sdpa
    # and must run, stay finite, and be deterministic per key.
    ring_cfg = DROP_CFG.replace(attn_impl="ring")
    lr1, _ = forward(params, tokens, pos, ring_cfg,
                     dropout_rng=jax.random.PRNGKey(0))
    lr2, _ = forward(params, tokens, pos, ring_cfg,
                     dropout_rng=jax.random.PRNGKey(0))
    assert np.isfinite(np.asarray(lr1, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(lr1), np.asarray(lr2))
    # "auto" resolves to flash at prefill lengths even under attn_pdrop
    # (the kernel generates its own mask); both impls stay finite,
    # deterministic per key, and distinct across keys.
    t16 = jnp.asarray([list(range(1, 17))])
    p16 = jnp.arange(16)[None, :]
    for impl in ("auto", "flash"):
        icfg = DROP_CFG.replace(attn_impl=impl)
        la, _ = forward(params, t16, p16, icfg,
                        dropout_rng=jax.random.PRNGKey(0))
        la2, _ = forward(params, t16, p16, icfg,
                         dropout_rng=jax.random.PRNGKey(0))
        lb, _ = forward(params, t16, p16, icfg,
                        dropout_rng=jax.random.PRNGKey(1))
        assert np.isfinite(np.asarray(la, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(la), np.asarray(la2))
        assert np.abs(np.asarray(la) - np.asarray(lb)).max() > 0
    # Embedding-only dropout needs no layer rng threading; full per-layer
    # dropout on a stage > 1 mesh is covered by
    # test_pipeline.test_pipeline_dropout_training.
    emb_only = cfg_lib.tiny(max_seq_len=32, embd_pdrop=0.5)
    mesh = make_mesh(stage=2, devices=jax.devices()[:2])
    sp = shard_params(init_params(jax.random.PRNGKey(0), emb_only), mesh, emb_only)
    tb = jnp.tile(t16, (2, 1))

    @jax.jit  # the pipeline path runs under jit (like engine/train do)
    def run(p, t, q, rng):
        with use_mesh(mesh):
            return forward(p, t, q, emb_only, dropout_rng=rng)[0]

    logits = run(sp, tb, jnp.tile(p16, (2, 1)), jax.random.PRNGKey(0))
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_remat_policies_identical_gradients():
    """remat_policy changes WHAT is recomputed, never the math: loss and
    gradients must be bit-identical across "dots" / "full" / no remat on
    the fp32 CPU path."""
    results = {}
    for label, kw in (
        ("none", dict(remat=False)),
        ("full", dict(remat=True, remat_policy="full")),
        ("dots", dict(remat=True, remat_policy="dots")),
    ):
        config = cfg_lib.get_config(
            "tiny", dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            vocab_size=128, max_seq_len=32, **kw,
        )
        params = init_params(jax.random.PRNGKey(0), config)
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 128, (2, 32)), jnp.int32
        )
        loss, grads = jax.value_and_grad(lm_loss)(params, toks, config)
        results[label] = (float(loss), jax.tree_util.tree_leaves(grads))
    base_loss, base_grads = results["none"]
    for label in ("full", "dots"):
        loss, grads = results[label]
        assert loss == base_loss, (label, loss, base_loss)
        for a, b in zip(grads, base_grads):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=label
            )
