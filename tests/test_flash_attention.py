"""Flash-attention kernel parity vs the XLA reference path.

The Pallas kernel runs in interpret mode on the CPU test mesh; parity vs
``ops.attention.sdpa`` (itself oracle-checked in test_ops/test_model) at
fp32 tolerances covers the online-softmax math, GQA index mapping,
positional masking, and tile-padding logic.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.models import forward
from jax_llama_tpu.ops import attention_bias, flash_attention, sdpa


def _ref(q, k, v, q_pos, kv_pos):
    bias = attention_bias(
        jnp.asarray(q_pos), jnp.asarray(kv_pos), jnp.asarray(kv_pos) >= 0
    )
    return np.asarray(
        sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), bias)
    )


def _rand(B, T, S, H, KVH, D):
    q = np.random.randn(B, T, H, D).astype(np.float32)
    k = np.random.randn(B, S, KVH, D).astype(np.float32)
    v = np.random.randn(B, S, KVH, D).astype(np.float32)
    return q, k, v


def test_flash_matches_sdpa_causal():
    B, T, H, KVH, D = 2, 24, 4, 2, 16
    q, k, v = _rand(B, T, T, H, KVH, D)
    pos = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    got = np.asarray(
        flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(pos), jnp.asarray(pos), block_q=8, block_k=8,
        )
    )
    want = _ref(q, k, v, pos, pos)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_flash_triangular_diagonal_body():
    """The ragged diagonal body (r5): active when block_q/_KSUB is
    sublane-aligned — (32, 64) tiles here — on every causal crossing
    tile.  Parity vs sdpa with GQA + left-padding, gradient parity, and
    the dynamic triangle-safety fallback under a SHUFFLED kv layout
    (non-ascending positions must route to the uniform masked body and
    still be exact)."""
    import jax

    B, T, H, KVH, D = 2, 160, 4, 2, 64
    rng = np.random.RandomState(11)
    q = rng.randn(B, T, H, D).astype(np.float32) * 0.3
    k = rng.randn(B, T, KVH, D).astype(np.float32) * 0.3
    v = rng.randn(B, T, KVH, D).astype(np.float32) * 0.3
    pos = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    pos[1, :9] = -1
    pos[1, 9:] = np.arange(T - 9)
    qp = np.maximum(pos, 0)

    def fl(q, k, v, kv_pos):
        return flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(qp), jnp.asarray(kv_pos),
            block_q=32, block_k=64,
        )

    got = np.asarray(fl(q, k, v, pos))
    want = _ref(q, k, v, qp, pos)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)

    # Gradients flow through the ragged body (fwd saves lse; backward
    # kernels are tile-uniform — consistency across the pair is what
    # this pins).
    g = rng.randn(B, T, H, D).astype(np.float32)
    f_out, f_vjp = jax.vjp(
        lambda a, b, c: fl(a, b, c, pos),
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
    )

    def dense(a, b, c):
        bias = attention_bias(
            jnp.asarray(qp), jnp.asarray(pos), jnp.asarray(pos) >= 0
        )
        return sdpa(a, b, c, bias)

    d_out, d_vjp = jax.vjp(
        dense, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    )
    for fg, dg, name in zip(
        f_vjp(jnp.asarray(g)), d_vjp(jnp.asarray(g)), ("dq", "dk", "dv")
    ):
        denom = max(np.abs(np.asarray(dg)).max(), 1e-6)
        assert np.abs(np.asarray(fg) - np.asarray(dg)).max() / denom < 2e-3, name

    # Shuffled kv layout: positions non-ascending, triangle safety must
    # reject the ragged body tile-by-tile; result stays exact.
    perm = rng.permutation(T)
    got_sh = np.asarray(fl(q, k[:, perm], v[:, perm], pos[:, perm]))
    want_sh = _ref(q, k[:, perm], v[:, perm], qp, pos[:, perm])
    np.testing.assert_allclose(got_sh, want_sh, atol=1e-5, rtol=1e-4)


def test_flash_non_multiple_block_sizes():
    # T=13, S=21 not multiples of the 8/16 tiles: exercises the padding path.
    B, T, S, H, KVH, D = 1, 13, 21, 4, 4, 8
    q, k, v = _rand(B, T, S, H, KVH, D)
    q_pos = np.tile(np.arange(S - T, S, dtype=np.int32), (B, 1))
    kv_pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    got = np.asarray(
        flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(q_pos), jnp.asarray(kv_pos), block_q=8, block_k=16,
        )
    )
    want = _ref(q, k, v, q_pos, kv_pos)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_flash_padding_and_cache_slots_masked():
    # Left-padded prompt (slots -1) plus unwritten cache tail (slots -1):
    # the decode-over-cache geometry.
    B, T, S, H, KVH, D = 2, 4, 32, 4, 2, 8
    q, k, v = _rand(B, T, S, H, KVH, D)
    kv_pos = np.full((B, S), -1, dtype=np.int32)
    kv_pos[:, 2:10] = np.arange(8)  # 8 valid slots mid-cache
    q_pos = np.tile(np.arange(4, 8, dtype=np.int32), (B, 1))
    got = np.asarray(
        flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(q_pos), jnp.asarray(kv_pos), block_q=8, block_k=8,
        )
    )
    want = _ref(q, k, v, q_pos, kv_pos)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_flash_single_query_decode_shape():
    # T=1 (decode step): the kernel must handle a 1-row q block.
    B, S, H, KVH, D = 2, 40, 8, 2, 16
    q, k, v = _rand(B, 1, S, H, KVH, D)
    kv_pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    kv_pos[:, 30:] = -1
    q_pos = np.full((B, 1), 29, dtype=np.int32)
    got = np.asarray(
        flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(q_pos), jnp.asarray(kv_pos), block_q=8, block_k=8,
        )
    )
    want = _ref(q, k, v, q_pos, kv_pos)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_flash_quantized_matches_dequantized_reference():
    """flash_attention_quantized's in-kernel scale folding must equal
    dense attention over the explicitly dequantized K/V (scales are
    constant along head_dim, so the folding is exact up to fp order)."""
    from jax_llama_tpu.models.llama import quantize_kv
    from jax_llama_tpu.ops import flash_attention_quantized

    B, T, S, H, KVH, D = 2, 12, 24, 4, 2, 16
    q, k, v = _rand(B, T, S, H, KVH, D)
    kq, ks = quantize_kv(jnp.asarray(k))
    vq, vs = quantize_kv(jnp.asarray(v))
    kv_pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    kv_pos[:, 20:] = -1  # unwritten tail
    q_pos = np.tile(np.arange(S - T - 4, S - 4, dtype=np.int32), (B, 1))
    got = np.asarray(
        flash_attention_quantized(
            jnp.asarray(q), kq, vq, ks, vs,
            jnp.asarray(q_pos), jnp.asarray(kv_pos), block_q=8, block_k=8,
        )
    )
    k_deq = np.asarray(kq, np.float32) * np.asarray(ks)[..., None]
    v_deq = np.asarray(vq, np.float32) * np.asarray(vs)[..., None]
    want = _ref(q, k_deq, v_deq, q_pos, kv_pos)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
@pytest.mark.parametrize("data,tensor", [(1, 4), (2, 2), (2, 1)])
def test_flash_sharded_matches_unsharded_on_mesh(data, tensor):
    """``flash_attention_sharded`` under a data x tensor mesh (the kernel
    per shard inside shard_map: heads over tensor, rows over data when
    they divide) equals the plain kernel — bf16/int8-scale paths alike.
    On a TPU a bare Mosaic call under such a mesh does not lower at all;
    interpret mode cannot show that, this pins the wrapper's math."""
    import jax
    from jax_llama_tpu.ops.flash_attention import (
        flash_attention_quantized, flash_attention_sharded,
    )
    from jax_llama_tpu.models.llama import quantize_kv
    from jax_llama_tpu.parallel import make_mesh, use_mesh

    B, T, S, H, KVH, D = 2, 32, 48, 8, 4, 16
    q, k, v = (jnp.asarray(a) for a in _rand(B, T, S, H, KVH, D))
    q_pos = jnp.tile(jnp.arange(S - T, S, dtype=jnp.int32), (B, 1))
    kv_pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    mesh = make_mesh(
        data=data, tensor=tensor, devices=jax.devices()[: data * tensor]
    )

    @jax.jit
    def sharded(*a, **kw):
        with use_mesh(mesh):
            return flash_attention_sharded(*a, **kw)

    np.testing.assert_allclose(
        np.asarray(sharded(q, k, v, q_pos, kv_pos)),
        np.asarray(flash_attention(q, k, v, q_pos, kv_pos)),
        atol=1e-5, rtol=1e-5,
    )
    # One row cannot split over data=2: rows replicate, heads still shard.
    np.testing.assert_allclose(
        np.asarray(sharded(q[:1], k[:1], v[:1], q_pos[:1], kv_pos[:1])),
        np.asarray(flash_attention(q[:1], k[:1], v[:1], q_pos[:1],
                                   kv_pos[:1])),
        atol=1e-5, rtol=1e-5,
    )
    (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
    np.testing.assert_allclose(
        np.asarray(sharded(q, kq, vq, q_pos, kv_pos, k_scale=ks,
                           v_scale=vs)),
        np.asarray(flash_attention_quantized(q, kq, vq, ks, vs, q_pos,
                                             kv_pos)),
        atol=1e-5, rtol=1e-5,
    )


def test_model_forward_flash_matches_xla():
    import jax

    config = get_config("tiny")
    params = init_params(jax.random.PRNGKey(0), config)
    B, T = 2, 18
    tokens = jnp.asarray(
        np.random.randint(0, config.vocab_size, (B, T)), jnp.int32
    )
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    ref_logits, _ = forward(params, tokens, positions, config)
    flash_logits, _ = forward(
        params, tokens, positions, config.replace(attn_impl="flash")
    )
    np.testing.assert_allclose(
        np.asarray(flash_logits), np.asarray(ref_logits), atol=2e-4, rtol=1e-4
    )


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_model_decode_with_cache_flash_matches_xla():
    import jax
    from jax_llama_tpu.engine import GenerationConfig, generate

    config = get_config("tiny")
    params = init_params(jax.random.PRNGKey(0), config)
    B, P = 2, 9
    prompt = np.random.randint(1, config.vocab_size, (B, P)).astype(np.int32)
    mask = np.ones((B, P), dtype=bool)
    mask[0, :3] = False  # left padding on row 0
    prompt[0, :3] = 0
    gc = GenerationConfig(max_new_tokens=8, temperature=0.0, stop_tokens=())
    key = jax.random.PRNGKey(1)
    out_ref = generate(
        params, jnp.asarray(prompt), jnp.asarray(mask), key,
        config=config, gen_config=gc,
    )
    out_flash = generate(
        params, jnp.asarray(prompt), jnp.asarray(mask), key,
        config=config.replace(attn_impl="flash"), gen_config=gc,
    )
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_flash))


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_flash_gradients_match_xla():
    import jax

    config = get_config("tiny")
    params = init_params(jax.random.PRNGKey(0), config)
    from jax_llama_tpu.train import lm_loss

    tokens = jnp.asarray(
        np.random.randint(0, config.vocab_size, (2, 16)), jnp.int32
    )
    l0, g0 = jax.value_and_grad(lm_loss)(params, tokens, config)
    l1, g1 = jax.value_and_grad(lm_loss)(
        params, tokens, config.replace(attn_impl="flash")
    )
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        ),
        g1, g0,
    )


# ---------------------------------------------------------------------------
# Blockwise backward kernels (dQ / dK / dV with recomputed probabilities)
# ---------------------------------------------------------------------------

def _vjps(q, k, v, q_pos, kv_pos, g, bq, bk):
    import jax

    q, k, v, g = map(jnp.asarray, (q, k, v, g))
    q_pos, kv_pos = jnp.asarray(q_pos), jnp.asarray(kv_pos)

    def flash_fn(q, k, v):
        return flash_attention(q, k, v, q_pos, kv_pos, block_q=bq, block_k=bk)

    def dense_fn(q, k, v):
        return sdpa(q, k, v, attention_bias(q_pos, kv_pos, kv_pos >= 0))

    _, fvjp = jax.vjp(flash_fn, q, k, v)
    _, dvjp = jax.vjp(dense_fn, q, k, v)
    return fvjp(g), dvjp(g)


def test_flash_backward_matches_dense_gqa_and_padding():
    B, T, H, KVH, D = 2, 24, 4, 2, 16
    q, k, v = _rand(B, T, T, H, KVH, D)
    # Realistic left-pad geometry (engine.prompt_positions): padded slots
    # carry -1 and real positions restart at 0.  (Fully-masked rows are
    # out of scope: their forward output is unspecified garbage on both
    # paths, so their cotangents are too.)
    pos = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    pos[1, :5] = -1
    pos[1, 5:] = np.arange(T - 5)
    qp = np.maximum(pos, 0)
    g = np.random.randn(B, T, H, D).astype(np.float32)
    g[1, :5] = 0.0  # pad rows are masked downstream; no cotangent flows
    (fdq, fdk, fdv), (ddq, ddk, ddv) = _vjps(q, k, v, qp, pos, g, 8, 8)
    np.testing.assert_allclose(np.asarray(fdq), np.asarray(ddq), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(fdk), np.asarray(ddk), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(fdv), np.asarray(ddv), atol=1e-4, rtol=1e-4)


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_flash_backward_matches_dense_8k():
    """Long-context gradient parity at the production block sizes
    (VERDICT r1 item 4).  Small head count keeps the dense oracle's S^2
    buffers manageable in interpret mode."""
    B, S, H, D = 1, 8192, 1, 64
    q, k, v = _rand(B, S, S, H, H, D)
    pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    g = np.random.randn(B, S, H, D).astype(np.float32)
    (fdq, fdk, fdv), (ddq, ddk, ddv) = _vjps(q, k, v, pos, pos, g, 512, 2048)
    for f, dref, name in ((fdq, ddq, "dq"), (fdk, ddk, "dk"), (fdv, ddv, "dv")):
        f, dref = np.asarray(f), np.asarray(dref)
        denom = np.abs(dref).max()
        assert np.abs(f - dref).max() / denom < 1e-4, name


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_flash_backward_fdiff_16k():
    """At 16k a dense oracle no longer fits; check the analytic gradient
    against a central finite difference along a random direction."""
    import jax

    B, S, H, D = 1, 16384, 1, 32
    rng = np.random.RandomState(0)
    q = rng.randn(B, S, H, D).astype(np.float32) * 0.1
    k = rng.randn(B, S, H, D).astype(np.float32) * 0.1
    v = rng.randn(B, S, H, D).astype(np.float32) * 0.1
    pos = jnp.asarray(np.tile(np.arange(S, dtype=np.int32), (B, 1)))
    w = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))

    def loss(k):
        o = flash_attention(jnp.asarray(q), k, jnp.asarray(v), pos, pos)
        return jnp.vdot(o, w)

    gk = jax.grad(loss)(jnp.asarray(k))
    u = rng.randn(*k.shape).astype(np.float32)
    u /= np.linalg.norm(u)
    eps = 1e-2
    lo = float(loss(jnp.asarray(k - eps * u)))
    hi = float(loss(jnp.asarray(k + eps * u)))
    fdiff = (hi - lo) / (2 * eps)
    analytic = float(jnp.vdot(gk, jnp.asarray(u)))
    np.testing.assert_allclose(analytic, fdiff, rtol=2e-2, atol=1e-3)


def test_flash_backward_no_quadratic_memory_32k():
    """The whole point of the kernel: no S x S intermediate anywhere in the
    VJP jaxpr at 32k (the r1 dense fallback materialized [B, H, T, S])."""
    import jax

    B, S, H, D = 1, 32768, 1, 64

    def loss(q, k, v):
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        return flash_attention(q, k, v, pos, pos).sum()

    sds = jax.ShapeDtypeStruct((B, S, H, D), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(sds, sds, sds)

    limit = S * 1024  # O(S*d) with the lane-replicated lse/delta rows
    def walk(jpr):
        for eqn in jpr.eqns:
            for var in eqn.outvars:
                size = int(np.prod(var.aval.shape)) if var.aval.shape else 1
                assert size <= limit, (eqn.primitive.name, var.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)


# ---------------------------------------------------------------------------
# In-kernel attention-probability dropout (attn_pdrop on the flash path).
#
# There is no PRNG-bit parity to check against the xla path (different
# generators by design), so the tests pin down the *semantics*: the realized
# mask is Bernoulli with the right rate, scaled by 1/(1-rate), identical
# across tilings and calls, and the backward kernels reproduce the exact
# forward draw (gradient parity vs a dense model built from the EXTRACTED
# mask — any fwd/bwd mask drift would show up at O(1), not 1e-4).
# ---------------------------------------------------------------------------


def _extract_dropout_weights(q, k, q_pos, kv_pos, rate, seed, bq, bk):
    """Run the kernel with v = identity basis so row i of the output IS the
    post-dropout weight row u_i = D_i * softmax(s)_i (needs d >= S)."""
    B, T, H, d = q.shape
    S = k.shape[1]
    assert d >= S and H == k.shape[2]
    v = jnp.zeros((B, S, H, d), jnp.float32)
    eye = jnp.arange(S)
    for b in range(B):
        for h in range(H):
            v = v.at[b, eye, h, eye].set(1.0)
    out = flash_attention(
        jnp.asarray(q), jnp.asarray(k), v, jnp.asarray(q_pos),
        jnp.asarray(kv_pos), block_q=bq, block_k=bk,
        dropout_rate=rate, dropout_seed=seed,
    )
    return np.asarray(out[..., :S])  # [B, T, H, S] realized u


def _dense_weights(q, k, q_pos, kv_pos):
    import jax

    s = jnp.einsum("bthd,bshd->bths", jnp.asarray(q), jnp.asarray(k))
    s = s / np.sqrt(q.shape[-1])
    allowed = (
        (jnp.asarray(kv_pos)[:, None, None, :]
         <= jnp.asarray(q_pos)[:, :, None, None])
        & (jnp.asarray(kv_pos) >= 0)[:, None, None, :]
    )
    s = jnp.where(allowed, s, -1e30)
    return np.asarray(jax.nn.softmax(s, axis=-1)), np.asarray(allowed)


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_flash_dropout_mask_is_inverted_bernoulli():
    B, T, S, H, d = 1, 64, 64, 2, 64
    rng = np.random.RandomState(3)
    q = rng.randn(B, T, H, d).astype(np.float32) * 0.2
    k = rng.randn(B, S, H, d).astype(np.float32) * 0.2
    pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    rate = 0.25
    seed = jnp.asarray([77], jnp.uint32)
    u = _extract_dropout_weights(q, k, pos, pos, rate, seed, 16, 16)
    w, allowed = _dense_weights(q, k, pos, pos)
    resolvable = allowed & (w > 1e-3)
    D = u[resolvable] / w[resolvable]
    keep_val = 1.0 / (1.0 - rate)
    is_kept = np.abs(D - keep_val) < 1e-2
    is_dropped = np.abs(D) < 1e-2
    assert np.all(is_kept | is_dropped)  # binary inverted-dropout values
    frac = is_dropped.mean()
    assert abs(frac - rate) < 0.05, frac  # ~Bernoulli(rate)
    # Tile-size invariance: the mask hashes GLOBAL (row, col) indices, so
    # retiling must not change the draw.
    u2 = _extract_dropout_weights(q, k, pos, pos, rate, seed, 32, 64)
    np.testing.assert_allclose(u, u2, atol=1e-5)
    # Seed sensitivity + per-head independence.
    u3 = _extract_dropout_weights(
        q, k, pos, pos, rate, jnp.asarray([78], jnp.uint32), 16, 16
    )
    assert np.abs(u - u3).max() > 0.1
    # The seed is 64-bit: the HIGH word must drive an independent draw
    # (a [1] seed widens to a zero high word, so [77, 1] != [77]).
    u_hi = _extract_dropout_weights(
        q, k, pos, pos, rate, jnp.asarray([77, 1], jnp.uint32), 16, 16
    )
    assert np.abs(u - u_hi).max() > 0.1
    D_full = np.where(w > 1e-3, u / np.maximum(w, 1e-30), 0.0)
    assert np.abs(D_full[0, :, 0] - D_full[0, :, 1]).max() > 0.1


def test_flash_dropout_rate0_and_seed_requirements():
    B, T, H, D = 1, 16, 2, 32
    q, k, v = _rand(B, T, T, H, H, D)
    pos = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    base = flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(pos), jnp.asarray(pos), block_q=8, block_k=8,
    )
    with_seed = flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(pos), jnp.asarray(pos), block_q=8, block_k=8,
        dropout_rate=0.0, dropout_seed=jnp.asarray([5], jnp.uint32),
    )
    np.testing.assert_array_equal(np.asarray(base), np.asarray(with_seed))
    with pytest.raises(ValueError, match="dropout_seed"):
        flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(pos), jnp.asarray(pos), dropout_rate=0.5,
        )


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_flash_dropout_backward_matches_dense_with_extracted_mask():
    """Gradient parity for q/k/v against a dense attention whose dropout
    matrix is the mask EXTRACTED from the kernel forward: proves all three
    kernels (fwd, dQ, dK/dV) regenerate the same draw, including under GQA
    query packing and left-padding."""
    import jax

    B, T, S, H, KVH, d = 2, 40, 40, 4, 2, 64
    rng = np.random.RandomState(5)
    q = rng.randn(B, T, H, d).astype(np.float32) * 0.2
    k = rng.randn(B, S, KVH, d).astype(np.float32) * 0.2
    v = rng.randn(B, S, KVH, d).astype(np.float32) * 0.2
    pos = np.tile(np.arange(T, dtype=np.int32), (B, 1))
    pos[1, :7] = -1
    pos[1, 7:] = np.arange(T - 7)
    qp = np.maximum(pos, 0)
    rate, seed = 0.3, jnp.asarray([123], jnp.uint32)
    g = rng.randn(B, T, H, d).astype(np.float32)
    g[1, :7] = 0.0

    # Extract the realized per-(b, kv-head, packed-row, col) mask by
    # running the PACKED single-group geometry the kernel actually uses.
    group = H // KVH
    q_packed = np.moveaxis(
        q.reshape(B, T, KVH, group, d), 3, 1
    ).reshape(B, group * T, KVH, d)
    qp_packed = np.tile(qp, (1, group))
    u = _extract_dropout_weights(
        q_packed, k, qp_packed, pos, rate, seed, 16, 16
    )  # [B, group*T, KVH, S]
    w, allowed = _dense_weights(q_packed, k, qp_packed, pos)
    keep_val = 1.0 / (1.0 - rate)
    D = np.where(
        allowed & (w > 1e-4),
        np.rint(u / np.maximum(w, 1e-30) / keep_val) * keep_val,
        # Unresolvable (w ~ 0) entries contribute ~nothing to outputs or
        # grads either way; call them kept.
        keep_val,
    ).astype(np.float32)
    D = jnp.asarray(D)  # [B, group*T, KVH, S] packed-row dropout matrix

    def dense_fn(q, k, v):
        qp_j = jnp.moveaxis(
            q.reshape(B, T, KVH, group, d), 3, 1
        ).reshape(B, group * T, KVH, d)
        s = jnp.einsum("bthd,bshd->bths", qp_j, k) / np.sqrt(d)
        s = jnp.where(
            (jnp.asarray(pos)[:, None, None, :]
             <= jnp.asarray(qp_packed)[:, :, None, None])
            & (jnp.asarray(pos) >= 0)[:, None, None, :],
            s, -1e30,
        )
        ww = jax.nn.softmax(s, axis=-1) * D
        o = jnp.einsum("bths,bshd->bthd", ww, v)
        return jnp.moveaxis(
            o.reshape(B, group, T, KVH, d), 1, 3
        ).reshape(B, T, H, d)

    def flash_fn(q, k, v):
        return flash_attention(
            jnp.asarray(q), k, v, jnp.asarray(qp), jnp.asarray(pos),
            block_q=16, block_k=16, dropout_rate=rate, dropout_seed=seed,
        )

    fout, fvjp = jax.vjp(flash_fn, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    dout, dvjp = jax.vjp(dense_fn, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(
        np.asarray(fout)[0], np.asarray(dout)[0], atol=1e-4, rtol=1e-3
    )
    for f, dref, name in zip(fvjp(jnp.asarray(g)), dvjp(jnp.asarray(g)),
                             ("dq", "dk", "dv")):
        f, dref = np.asarray(f), np.asarray(dref)
        denom = max(np.abs(dref).max(), 1e-6)
        assert np.abs(f - dref).max() / denom < 2e-3, name


def test_flash_dropout_no_quadratic_memory_32k():
    """Dropout must not break the O(S*d) guarantee: the mask lives only as
    [block_q, block_k] tiles inside the kernels."""
    import jax

    B, S, H, D = 1, 32768, 1, 64

    def loss(q, k, v):
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        return flash_attention(
            q, k, v, pos, pos, dropout_rate=0.1,
            dropout_seed=jnp.asarray([9], jnp.uint32),
        ).sum()

    sds = jax.ShapeDtypeStruct((B, S, H, D), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(sds, sds, sds)

    limit = S * 1024
    def walk(jpr):
        for eqn in jpr.eqns:
            for var in eqn.outvars:
                size = int(np.prod(var.aval.shape)) if var.aval.shape else 1
                assert size <= limit, (eqn.primitive.name, var.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
