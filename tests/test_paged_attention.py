"""Paged-attention decode kernel: parity vs the gathered-view reference.

The kernel (ops/paged_attention.py) walks the serving block table inside
its BlockSpec index maps; these tests pin its numerics against dense
attention over an explicitly gathered contiguous view — the path it
replaced — including dead table entries, partially-filled blocks,
inactive rows, and the model-level ``paged_forward`` step.
"""

import numpy as np
import jax
import pytest
import jax.numpy as jnp

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.models import forward
from jax_llama_tpu.models.llama import PagedKVCache
from jax_llama_tpu.ops import attention_bias, sdpa
from jax_llama_tpu.ops.paged_attention import paged_decode_attention
from jax_llama_tpu.serving import _gather_cache, init_pool


def _random_pool_state(rng, B, KVH, d, NB, BLK, MB, fills):
    kp = rng.randn(KVH, NB, BLK, d).astype(np.float32)
    vp = rng.randn(KVH, NB, BLK, d).astype(np.float32)
    pool_pos = np.full((NB, BLK), -1, np.int32)
    table = np.full((B, MB), NB, np.int32)
    free = list(range(NB))
    for b, fill in enumerate(fills):
        n = -(-fill // BLK) if fill else 0
        blocks = [free.pop(0) for _ in range(n)]
        table[b, :n] = blocks
        for j, blk in enumerate(blocks):
            m = min(BLK, fill - j * BLK)
            pool_pos[blk, :m] = np.arange(j * BLK, j * BLK + m)
    return kp, vp, pool_pos, table


def _reference(q, kn, vn, kp, vp, pool_pos, table, qpos, b):
    """Dense attention over row b's gathered blocks + the new slot."""
    NB = kp.shape[1]
    ks, vs, ps = [], [], []
    for t in table[b]:
        if t < NB:
            ks.append(kp[:, t])
            vs.append(vp[:, t])
            ps.append(pool_pos[t])
    kcat = np.concatenate(
        ks + [kn[b].transpose(1, 0, 2)], axis=1
    ).transpose(1, 0, 2)[None]
    vcat = np.concatenate(
        vs + [vn[b].transpose(1, 0, 2)], axis=1
    ).transpose(1, 0, 2)[None]
    pcat = np.concatenate(ps + [np.array([qpos[b]])])
    bias = attention_bias(
        jnp.asarray([[qpos[b]]], jnp.int32), jnp.asarray(pcat[None]),
        jnp.asarray((pcat >= 0)[None]),
    )
    return np.asarray(
        sdpa(jnp.asarray(q[b:b + 1]), jnp.asarray(kcat), jnp.asarray(vcat),
             bias)
    )[0]


def test_paged_kernel_matches_gathered_dense():
    rng = np.random.RandomState(0)
    B, H, KVH, d = 4, 8, 2, 32
    NB, BLK, MB = 12, 16, 5
    # row fills: multi-block, empty (inactive), one block, partial block
    fills = [40, 0, 16, 7]
    qpos = np.array([40, -1, 16, 7], np.int32)
    kp, vp, pool_pos, table = _random_pool_state(
        rng, B, KVH, d, NB, BLK, MB, fills
    )
    q = rng.randn(B, 1, H, d).astype(np.float32)
    kn = rng.randn(B, 1, KVH, d).astype(np.float32)
    vn = rng.randn(B, 1, KVH, d).astype(np.float32)

    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pool_pos),
        jnp.asarray(table), jnp.asarray(qpos),
    ))
    assert np.isfinite(got).all()
    for b in range(B):
        if qpos[b] < 0:
            continue  # inactive row: output is ignored by the host
        want = _reference(q, kn, vn, kp, vp, pool_pos, table, qpos, b)
        np.testing.assert_allclose(got[b], want, atol=1e-5, rtol=1e-5)


def test_paged_kernel_gqa_head_order():
    """Query head h must read KV head h // group (the model's layout)."""
    rng = np.random.RandomState(1)
    B, H, KVH, d = 1, 4, 2, 16
    NB, BLK, MB = 4, 8, 2
    fills = [12]
    qpos = np.array([12], np.int32)
    kp, vp, pool_pos, table = _random_pool_state(
        rng, B, KVH, d, NB, BLK, MB, fills
    )
    q = rng.randn(B, 1, H, d).astype(np.float32)
    kn = rng.randn(B, 1, KVH, d).astype(np.float32)
    vn = rng.randn(B, 1, KVH, d).astype(np.float32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pool_pos),
        jnp.asarray(table), jnp.asarray(qpos),
    ))
    want = _reference(q, kn, vn, kp, vp, pool_pos, table, qpos, 0)
    np.testing.assert_allclose(got[0], want, atol=1e-5, rtol=1e-5)


def _reference_multi(q, kn, vn, kp, vp, pool_pos, table, qpos, b, T):
    """Dense attention over row b's gathered blocks + T new slots at
    consecutive positions qpos..qpos+T-1 (within-step causal)."""
    NB = kp.shape[1]
    ks, vs, ps = [], [], []
    for t in table[b]:
        if t < NB:
            ks.append(kp[:, t])
            vs.append(vp[:, t])
            ps.append(pool_pos[t])
    kcat = np.concatenate(
        ks + [kn[b].transpose(1, 0, 2)], axis=1
    ).transpose(1, 0, 2)[None]
    vcat = np.concatenate(
        vs + [vn[b].transpose(1, 0, 2)], axis=1
    ).transpose(1, 0, 2)[None]
    new_pos = qpos[b] + np.arange(T)
    pcat = np.concatenate(ps + [new_pos])
    q_positions = (qpos[b] + np.arange(T))[None]
    bias = attention_bias(
        jnp.asarray(q_positions, jnp.int32), jnp.asarray(pcat[None]),
        jnp.asarray((pcat >= 0)[None]),
    )
    return np.asarray(
        sdpa(jnp.asarray(q[b:b + 1]), jnp.asarray(kcat), jnp.asarray(vcat),
             bias)
    )[0]


def test_paged_kernel_multi_token_matches_dense():
    """T>1 (speculative-verify shape): T consecutive-position queries per
    row share one pool sweep; token t additionally attends the step's own
    slots j <= t.  Must match dense attention over the gathered blocks +
    new slots, including rows whose early tokens see fewer blocks."""
    rng = np.random.RandomState(7)
    B, H, KVH, d, T = 4, 8, 2, 32, 3
    NB, BLK, MB = 12, 16, 5
    fills = [40, 0, 16, 7]
    qpos = np.array([40, -1, 16, 7], np.int32)
    kp, vp, pool_pos, table = _random_pool_state(
        rng, B, KVH, d, NB, BLK, MB, fills
    )
    q = rng.randn(B, T, H, d).astype(np.float32)
    kn = rng.randn(B, T, KVH, d).astype(np.float32)
    vn = rng.randn(B, T, KVH, d).astype(np.float32)

    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pool_pos),
        jnp.asarray(table), jnp.asarray(qpos),
    ))
    assert np.isfinite(got).all()
    for b in range(B):
        if qpos[b] < 0:
            continue
        want = _reference_multi(
            q, kn, vn, kp, vp, pool_pos, table, qpos, b, T
        )
        np.testing.assert_allclose(got[b], want, atol=1e-5, rtol=1e-5)


def test_paged_kernel_multi_token_first_token_empty_pool():
    """A fresh row (empty pool, qpos 0): token 0 attends only itself —
    the all-masked-tile guard must not poison its softmax state."""
    rng = np.random.RandomState(8)
    B, H, KVH, d, T = 2, 4, 2, 16, 4
    NB, BLK, MB = 6, 8, 3
    fills = [0, 11]
    qpos = np.array([0, 11], np.int32)
    kp, vp, pool_pos, table = _random_pool_state(
        rng, B, KVH, d, NB, BLK, MB, fills
    )
    # Row 0: reserve blocks but nothing written yet (pos stays -1).
    table[0, :2] = [4, 5]
    q = rng.randn(B, T, H, d).astype(np.float32)
    kn = rng.randn(B, T, KVH, d).astype(np.float32)
    vn = rng.randn(B, T, KVH, d).astype(np.float32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pool_pos),
        jnp.asarray(table), jnp.asarray(qpos),
    ))
    assert np.isfinite(got).all()
    for b in range(B):
        want = _reference_multi(
            q, kn, vn, kp, vp, pool_pos, table, qpos, b, T
        )
        np.testing.assert_allclose(got[b], want, atol=1e-5, rtol=1e-5)


def _step_edge_state(rng, MB, pool):
    """One batch holding every block-count edge of the multi-block grid
    step: rows of 0, 1, P-1, P, P+1 and MB live blocks (P derived from
    the shapes, as the kernel derives it), an inactive row that still
    owns written blocks between live rows, and a row with a sentinel
    table entry in the middle of a live step.  Returns the operands as
    the kernel takes them and as the dense reference reads them."""
    from jax_llama_tpu.models.llama import quantize_kv
    from jax_llama_tpu.ops.paged_attention import _blocks_per_step

    H, KVH, d, BLK = 16, 8, 32, 16
    itemsize = {"fp32": 4, "bf16": 2, "int8": 1}[pool]
    P = _blocks_per_step(BLK, MB, KVH, d, itemsize)
    assert 1 < P < MB, (P, MB)  # the cases below need several steps a row
    # (live blocks, active) per row; the hole row is appended below.
    rows = [(0, True), (1, True), (P - 1, True), (3, False), (P, True),
            (P + 1, True), (MB, True)]
    fills = [n * BLK - (5 if n else 0) for n, _ in rows] + [0]
    B = len(fills)
    NB = sum(n for n, _ in rows) + P + 2
    kp, vp, pool_pos, table = _random_pool_state(
        rng, B, KVH, d, NB, BLK, MB, fills
    )
    qpos = np.array(
        [f if act else -1 for f, (_, act) in zip(fills, rows)] + [0],
        np.int32,
    )
    # Hole row: P + 1 written blocks, the second table entry a sentinel.
    hole = B - 1
    blocks = [b for b in range(NB) if b not in set(table.ravel())][:P + 1]
    slots = [j for j in range(P + 2) if j != 1]
    for j, blk in zip(slots, blocks):
        table[hole, j] = blk
        pool_pos[blk] = np.arange(j * BLK, (j + 1) * BLK)
    qpos[hole] = (P + 2) * BLK
    scales = {}
    if pool == "int8":
        kq, ks = quantize_kv(jnp.asarray(kp))
        vq, vs = quantize_kv(jnp.asarray(vp))
        scales = dict(k_scale=ks, v_scale=vs)
        kp = np.asarray(kq, np.float32) * np.asarray(ks)[..., None]
        vp = np.asarray(vq, np.float32) * np.asarray(vs)[..., None]
        k_dev, v_dev = kq, vq
    elif pool == "bf16":
        k_dev, v_dev = (jnp.asarray(x, jnp.bfloat16) for x in (kp, vp))
        kp, vp = (np.asarray(x, np.float32) for x in (k_dev, v_dev))
    else:
        k_dev, v_dev = jnp.asarray(kp), jnp.asarray(vp)
    return dict(
        B=B, H=H, KVH=KVH, d=d, P=P, qpos=qpos, table=table,
        pool_pos=pool_pos, kp=kp, vp=vp, k_dev=k_dev, v_dev=v_dev,
        scales=scales,
    )


@pytest.mark.parametrize(
    "pool,T,MB",
    [
        ("fp32", 1, 8),  # P divides MB; every other case pads the table
        ("fp32", 1, 10), ("fp32", 3, 10), ("bf16", 1, 10), ("bf16", 3, 10),
        ("int8", 1, 10), ("int8", 3, 10),
    ],
)
def test_paged_kernel_step_edges_match_dense(pool, T, MB):
    """The grid step covers P table entries: every way a row's live
    blocks can sit against the step boundary must match dense attention
    over the gathered blocks — including MB that P does not divide (the
    table is padded with sentinels), dead entries inside a live step
    (they hold another row's block and must weigh exactly zero) and dead
    steps between live rows."""
    rng = np.random.RandomState(11)
    st = _step_edge_state(rng, MB, pool)
    B, H, KVH, d = st["B"], st["H"], st["KVH"], st["d"]
    qdt = jnp.bfloat16 if pool == "bf16" else jnp.float32
    q = np.asarray(jnp.asarray(rng.randn(B, T, H, d), qdt), np.float32)
    kn = np.asarray(jnp.asarray(rng.randn(B, T, KVH, d), qdt), np.float32)
    vn = np.asarray(jnp.asarray(rng.randn(B, T, KVH, d), qdt), np.float32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q, qdt), jnp.asarray(kn, qdt), jnp.asarray(vn, qdt),
        st["k_dev"], st["v_dev"], jnp.asarray(st["pool_pos"]),
        jnp.asarray(st["table"]), jnp.asarray(st["qpos"]), **st["scales"],
    ), np.float32)
    assert np.isfinite(got).all()
    # bf16: the kernel rounds probabilities to the pool's dtype before
    # the P.V product and returns bf16; the reference is fp32 throughout.
    tol = 3e-2 if pool == "bf16" else 1e-5
    for b in range(B):
        if st["qpos"][b] < 0:
            continue
        want = _reference_multi(
            q, kn, vn, st["kp"], st["vp"], st["pool_pos"], st["table"],
            st["qpos"], b, T,
        )
        np.testing.assert_allclose(got[b], want, atol=tol, rtol=tol)


@pytest.mark.parametrize(
    "blk,mb,kvh,d,itemsize,want",
    [
        (128, 16, 8, 128, 2, 4),    # mistral7b-chat-rate80: 16 slots x 2048
        (128, 32, 8, 128, 2, 4),    # mistral7b-docqa-batch: 8 slots x 4096
        (512, 32, 8, 128, 2, 1),    # a 512-token block is a step already
        (128, 16, 2, 128, 2, 4),    # KVH / tensor = 2 on four chips
        (128, 16, 8, 128, 1, 4),    # int8 pool: tokens, not bytes, set P
        (256, 16, 8, 128, 2, 2),
        (128, 16, 32, 128, 2, 1),   # 32 KV heads: the unroll cap holds P
        (16, 10, 8, 32, 4, 4),      # 10 entries in 3 steps of 4, 2 padded
        (16, 5, 2, 32, 4, 5),       # a short table is one step
        (128, 1, 8, 128, 2, 1),
    ],
)
def test_blocks_per_step_follows_the_shapes(blk, mb, kvh, d, itemsize, want):
    from jax_llama_tpu.ops.paged_attention import _blocks_per_step

    assert _blocks_per_step(blk, mb, kvh, d, itemsize) == want


def test_paged_forward_multi_token_matches_gathered_view():
    """paged_forward at T=3 (the verify shape) vs the gathered-view
    forward: same logits for active rows, same pool afterwards."""
    import dataclasses

    from jax_llama_tpu.serving import _scatter_back

    config = get_config(
        "tiny", vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=64,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    B, NB, BLK, MB, T = 3, 10, 8, 3, 3
    pool = init_pool(config, NB, BLK)
    rng = np.random.RandomState(9)
    pool = dataclasses.replace(
        pool,
        k=jnp.asarray(rng.randn(*pool.k.shape), pool.k.dtype),
        v=jnp.asarray(rng.randn(*pool.v.shape), pool.v.dtype),
    )
    fills = [10, 0, 17]
    qpos = np.array([10, -1, 17], np.int32)
    pool_pos = np.full((NB, BLK), -1, np.int32)
    table = np.full((B, MB), NB, np.int32)
    free = list(range(NB))
    n_alloc = np.zeros((B,), np.int32)
    for b, fill in enumerate(fills):
        n = -(-(fill + T) // BLK) if qpos[b] >= 0 else 0
        blocks = [free.pop(0) for _ in range(n)]
        table[b, :n] = blocks
        n_alloc[b] = n
        for j, blk in enumerate(blocks):
            m = max(0, min(BLK, fill - j * BLK))
            if m:
                pool_pos[blk, :m] = np.arange(j * BLK, j * BLK + m)
    pool = dataclasses.replace(pool, pos=jnp.asarray(pool_pos))

    toks = jnp.asarray(rng.randint(0, 128, (B, T)), jnp.int32)
    active = jnp.asarray(qpos >= 0)
    positions = jnp.asarray(
        np.where((qpos >= 0)[:, None], qpos[:, None] + np.arange(T), -1),
        jnp.int32,
    )
    fill_arr = jnp.asarray(fills, jnp.int32)
    tbl = jnp.asarray(table)
    amask = jnp.broadcast_to(active[:, None], (B, T))

    view = _gather_cache(pool, tbl, jnp.asarray(n_alloc), fill_arr)
    want_logits, view = forward(
        params, toks, positions, config, cache=view, attn_mask=amask,
    )
    want_pool = _scatter_back(pool, view, tbl, fill_arr, active, T=T)

    pcache = PagedKVCache(
        k=pool.k, v=pool.v, pos=pool.pos, table=tbl, fill=fill_arr
    )
    got_logits, pcache = forward(
        params, toks, positions, config, cache=pcache, attn_mask=amask,
    )

    act = np.asarray(active)
    np.testing.assert_allclose(
        np.asarray(got_logits)[act], np.asarray(want_logits)[act],
        atol=1e-4, rtol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(pcache.k), np.asarray(want_pool.k), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(pcache.pos), np.asarray(want_pool.pos)
    )


def test_paged_forward_matches_gathered_view_forward():
    """A full model step via paged_forward (Pallas kernel + scatter) must
    match the gathered-view forward (per-row-offset KVCache) it replaced:
    same logits, and the pool ends in the same state."""
    import dataclasses

    from jax_llama_tpu.serving import _scatter_back

    config = get_config(
        "tiny", vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=64,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    B, NB, BLK, MB = 3, 8, 8, 3
    pool = init_pool(config, NB, BLK)
    rng = np.random.RandomState(2)
    # Fill pools with random content + consistent positions.
    pool = dataclasses.replace(
        pool,
        k=jnp.asarray(rng.randn(*pool.k.shape), pool.k.dtype),
        v=jnp.asarray(rng.randn(*pool.v.shape), pool.v.dtype),
    )
    fills = [10, 0, 17]
    qpos = np.array([10, -1, 17], np.int32)
    pool_pos = np.full((NB, BLK), -1, np.int32)
    table = np.full((B, MB), NB, np.int32)
    free = list(range(NB))
    n_alloc = np.zeros((B,), np.int32)
    for b, fill in enumerate(fills):
        n = -(-fill // BLK) if fill else 0
        blocks = [free.pop(0) for _ in range(n)]
        table[b, :n] = blocks
        n_alloc[b] = n
        for j, blk in enumerate(blocks):
            m = min(BLK, fill - j * BLK)
            pool_pos[blk, :m] = np.arange(j * BLK, j * BLK + m)
    pool = dataclasses.replace(pool, pos=jnp.asarray(pool_pos))

    tau = jnp.asarray(rng.randint(0, 128, (B,)), jnp.int32)
    active = jnp.asarray(qpos >= 0)
    positions = jnp.asarray(qpos, jnp.int32)[:, None]
    fill_arr = jnp.asarray(fills, jnp.int32)
    tbl = jnp.asarray(table)

    # Gathered-view path.
    view = _gather_cache(pool, tbl, jnp.asarray(n_alloc), fill_arr)
    want_logits, view = forward(
        params, tau[:, None], positions, config, cache=view,
        attn_mask=active[:, None],
    )
    want_pool = _scatter_back(pool, view, tbl, fill_arr, active, T=1)

    # Paged kernel path.
    pcache = PagedKVCache(
        k=pool.k, v=pool.v, pos=pool.pos, table=tbl, fill=fill_arr
    )
    got_logits, pcache = forward(
        params, tau[:, None], positions, config, cache=pcache,
        attn_mask=active[:, None],
    )

    act = np.asarray(active)
    np.testing.assert_allclose(
        np.asarray(got_logits)[act], np.asarray(want_logits)[act],
        atol=1e-4, rtol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(pcache.k), np.asarray(want_pool.k), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(pcache.v), np.asarray(want_pool.v), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(pcache.pos), np.asarray(want_pool.pos)
    )


def test_paged_kernel_all_dead_block_contributes_nothing():
    """A table entry whose block holds only pos=-1 slots (e.g. a
    reserved-but-unwritten block, or a hole) must be SKIPPED — processing
    it would add p = exp(MASK - MASK) = 1 garbage into the softmax
    state.  Construct a row whose FIRST block is all-dead so the guard,
    not a lucky earlier live block, is what protects the output."""
    rng = np.random.RandomState(4)
    KVH, d = 2, 16
    NB, BLK, MB = 6, 8, 3
    kp = rng.randn(KVH, NB, BLK, d).astype(np.float32)
    vp = rng.randn(KVH, NB, BLK, d).astype(np.float32)
    pool_pos = np.full((NB, BLK), -1, np.int32)
    # Row 0: table [deadblk, liveblk, sentinel] — block 0 all-dead,
    # block 1 holds positions 8..15 (as if the hole were rolled back).
    pool_pos[1, :] = np.arange(8, 16)
    table = np.array([[0, 1, NB]], np.int32)
    qpos = np.array([16], np.int32)
    q = rng.randn(1, 1, 4, d).astype(np.float32)
    kn = rng.randn(1, 1, KVH, d).astype(np.float32)
    vn = rng.randn(1, 1, KVH, d).astype(np.float32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pool_pos),
        jnp.asarray(table), jnp.asarray(qpos),
    ))
    # Reference: only block 1's slots + the new token.
    ks = np.concatenate([kp[:, 1], kn[0].transpose(1, 0, 2)], axis=1)
    vs = np.concatenate([vp[:, 1], vn[0].transpose(1, 0, 2)], axis=1)
    ps = np.concatenate([pool_pos[1], [16]])
    bias = attention_bias(
        jnp.asarray([[16]], jnp.int32), jnp.asarray(ps[None]),
        jnp.asarray((ps >= 0)[None]),
    )
    want = np.asarray(sdpa(
        jnp.asarray(q), jnp.asarray(ks.transpose(1, 0, 2)[None]),
        jnp.asarray(vs.transpose(1, 0, 2)[None]), bias,
    ))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_paged_forward_int8_matches_gathered_int8():
    """int8 pool through the kernel (in-kernel scale folding) must match
    the gathered-view int8 path: same logits at quantization-noise level,
    bit-equal scattered payload + scales (both quantize the same
    projections with the same math)."""
    import dataclasses

    from jax_llama_tpu.serving import _scatter_back
    from jax_llama_tpu.models.llama import quantize_kv

    config = get_config(
        "tiny", vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=64, kv_cache_dtype="int8",
    )
    params = init_params(jax.random.PRNGKey(0), config)
    B, NB, BLK, MB = 2, 6, 8, 3
    pool = init_pool(config, NB, BLK)
    assert pool.quantized and pool.k.dtype == jnp.int8
    rng = np.random.RandomState(5)
    # Populate with quantized random content + matching scales.
    kf = rng.randn(*pool.k.shape).astype(np.float32)
    vf = rng.randn(*pool.v.shape).astype(np.float32)
    kq, ks = quantize_kv(jnp.asarray(kf))
    vq, vs = quantize_kv(jnp.asarray(vf))
    fills = [12, 20]
    qpos = np.array(fills, np.int32)
    pool_pos = np.full((NB, BLK), -1, np.int32)
    table = np.full((B, MB), NB, np.int32)
    free = list(range(NB))
    n_alloc = np.zeros((B,), np.int32)
    for b, fill in enumerate(fills):
        n = -(-fill // BLK)
        blocks = [free.pop(0) for _ in range(n)]
        table[b, :n] = blocks
        n_alloc[b] = n
        for j, blk in enumerate(blocks):
            m = min(BLK, fill - j * BLK)
            pool_pos[blk, :m] = np.arange(j * BLK, j * BLK + m)
    pool = dataclasses.replace(
        pool, k=kq, v=vq, k_scale=ks, v_scale=vs,
        pos=jnp.asarray(pool_pos),
    )

    tau = jnp.asarray(rng.randint(0, 128, (B,)), jnp.int32)
    active = jnp.ones((B,), bool)
    positions = jnp.asarray(qpos, jnp.int32)[:, None]
    fill_arr = jnp.asarray(fills, jnp.int32)
    tbl = jnp.asarray(table)

    view = _gather_cache(pool, tbl, jnp.asarray(n_alloc), fill_arr)
    want_logits, view = forward(
        params, tau[:, None], positions, config, cache=view,
        attn_mask=active[:, None],
    )
    want_pool = _scatter_back(pool, view, tbl, fill_arr, active, T=1)

    pcache = PagedKVCache(
        k=pool.k, v=pool.v, pos=pool.pos, table=tbl, fill=fill_arr,
        k_scale=pool.k_scale, v_scale=pool.v_scale,
    )
    got_logits, pcache = forward(
        params, tau[:, None], positions, config, cache=pcache,
        attn_mask=active[:, None],
    )
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits),
        atol=2e-4, rtol=2e-4,
    )
    np.testing.assert_array_equal(np.asarray(pcache.k), np.asarray(want_pool.k))
    np.testing.assert_array_equal(np.asarray(pcache.v), np.asarray(want_pool.v))
    np.testing.assert_allclose(
        np.asarray(pcache.k_scale), np.asarray(want_pool.k_scale), rtol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(pcache.pos), np.asarray(want_pool.pos)
    )


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_int8_batcher_kernel_path_runs_end_to_end():
    """End-to-end int8 continuous batching through the paged kernel: full
    deterministic generations on an int8 pool.

    Deliberately NOT a token-prefix comparison against the fp batcher:
    int8-KV rounding shifts logits at the ~1e-2 level, so any near-tie in
    a tiny random model flips a token and the flip point moves with every
    benign change to fp32 reduction order (it did, twice).  Numeric
    closeness of the int8 cache is asserted with real tolerances at the
    logit level in test_quant.test_int8_kv_cache_decode_close_to_fp; this
    test owns the serving plumbing."""
    from jax_llama_tpu.serving import ContinuousBatcher

    kw = dict(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=128,
    )
    params = init_params(jax.random.PRNGKey(0), get_config("tiny", **kw))
    rng = np.random.RandomState(6)
    prompts = [list(rng.randint(1, 128, n)) for n in (5, 19, 40)]

    def run(**cfg_kw):
        cb = ContinuousBatcher(
            params, get_config("tiny", **kw, **cfg_kw),
            n_slots=2, max_len=128, block_size=16,
        )
        # block_size 16 (% 8 == 0) routes the decode dispatch (the
        # fused chunk program; _decode_step_core at K=1) through
        # the Pallas kernel (kernel-vs-gathered equivalence is tested
        # above).
        rids = [cb.submit(p, max_new_tokens=10) for p in prompts]
        res = cb.run_to_completion()
        return [res[r] for r in rids]

    got = run(kv_cache_dtype="int8")
    assert all(len(g) == 10 for g in got)
    assert all(0 <= t < 128 for g in got for t in g)
    # Deterministic: the same int8 pool emits the same tokens.
    assert run(kv_cache_dtype="int8") == got


@pytest.mark.slow  # interpret-mode Pallas / long decode on CPU; out of the tier-1 budget (plain `pytest tests/` still runs it)
def test_batcher_on_tensor_data_mesh_matches_unsharded():
    """Continuous batching on a data x tensor mesh runs the paged kernel
    per-shard via shard_map (KV heads over tensor, rows over data) and
    must reproduce the unsharded batcher's greedy output."""
    from jax_llama_tpu.parallel import make_mesh, shard_params
    from jax_llama_tpu.serving import ContinuousBatcher

    config = get_config(
        "tiny", vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=128,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, 128, n)) for n in (6, 23, 41)]

    def run(mesh, p):
        cb = ContinuousBatcher(
            p, config, n_slots=2, max_len=128, block_size=16, mesh=mesh,
        )
        rids = [cb.submit(x, max_new_tokens=8) for x in prompts]
        res = cb.run_to_completion()
        return [res[r] for r in rids]

    want = run(None, params)
    mesh = make_mesh(data=2, fsdp=2, tensor=2)
    got = run(mesh, shard_params(params, mesh, config))
    assert got == want


def test_use_pallas_kernel_toggle_token_identical():
    """The explicit gathered-view toggle (bench's A/B knob) must not
    change tokens: kernel and gathered paths at IDENTICAL block size and
    pool geometry agree exactly (fp32 CPU), for plain and speculative
    batching."""
    from jax_llama_tpu.serving import ContinuousBatcher

    kw = dict(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=128,
    )
    config = get_config("tiny", **kw)
    params = init_params(jax.random.PRNGKey(0), config)
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(1, 128, n)) for n in (7, 23)]

    def run(use_kernel, spec):
        extra = (
            dict(draft_params=params, draft_config=config, n_draft=2)
            if spec else {}
        )
        cb = ContinuousBatcher(
            params, config, n_slots=2, max_len=128, block_size=16,
            use_pallas_kernel=use_kernel, **extra,
        )
        rids = [cb.submit(p, max_new_tokens=8) for p in prompts]
        res = cb.run_to_completion()
        return [res[r] for r in rids]

    for spec in (False, True):
        assert run(True, spec) == run(False, spec), f"spec={spec}"


def test_paged_pool_write_matches_scatter_drop_semantics():
    """paged_pool_write (the DUS chain that replaced the batched scatter
    to kill XLA:TPU's full-pool layout copies) must match
    ``plane.at[..., blk, off].set(upd, mode="drop")`` exactly — including
    dropped sentinel coordinates — on all three plane ranks."""
    from jax_llama_tpu.models.llama import paged_pool_write

    rng = np.random.RandomState(0)
    L, KVH, NB, BLK, d = 3, 2, 5, 8, 16
    B, T = 4, 2
    # DISTINCT live (blk, off) pairs: with duplicate targets the scatter
    # reference's write order is unspecified while the DUS chain is
    # last-write-wins, so equality would hinge on the seed.  (Callers
    # never produce duplicate live coordinates: paged_write_indices maps
    # each (row, token) to its own slot.)
    flat = rng.choice(NB * BLK, size=B * T, replace=False)
    blk = jnp.asarray(flat // BLK, jnp.int32).reshape(B, T)
    off = jnp.asarray(flat % BLK, jnp.int32).reshape(B, T)
    # Row 2 entirely dead; one more dead (row, token) pair.
    blk = blk.at[2].set(NB).at[0, 1].set(NB)

    plane5 = jnp.asarray(rng.randn(L, KVH, NB, BLK, d), jnp.float32)
    upd5 = jnp.asarray(rng.randn(L, KVH, B, T, d), jnp.float32)
    want5 = plane5.at[:, :, blk, off].set(upd5, mode="drop")
    got5 = paged_pool_write(plane5, upd5, blk, off)
    assert np.array_equal(np.asarray(got5), np.asarray(want5))

    plane4 = jnp.asarray(rng.randn(L, KVH, NB, BLK), jnp.float32)
    upd4 = jnp.asarray(rng.randn(L, KVH, B, T), jnp.float32)
    want4 = plane4.at[:, :, blk, off].set(upd4, mode="drop")
    got4 = paged_pool_write(plane4, upd4, blk, off)
    assert np.array_equal(np.asarray(got4), np.asarray(want4))

    plane2 = jnp.asarray(rng.randint(-5, 99, (NB, BLK)), jnp.int32)
    upd2 = jnp.asarray(rng.randint(100, 200, (B, T)), jnp.int32)
    want2 = plane2.at[blk, off].set(upd2, mode="drop")
    got2 = paged_pool_write(plane2, upd2, blk, off)
    assert np.array_equal(np.asarray(got2), np.asarray(want2))


def test_paged_pool_write_scatter_fallback_above_unroll_bound():
    """Past _POOL_WRITE_UNROLL_MAX (row, token) pairs the write switches
    to the batched scatter (op count of the DUS chain grows linearly);
    both paths must agree bit-for-bit, dead sentinels included."""
    from jax_llama_tpu.models.llama import (
        _POOL_WRITE_UNROLL_MAX, paged_pool_write,
    )

    rng = np.random.RandomState(1)
    NB, BLK = 64, 16
    B, T = _POOL_WRITE_UNROLL_MAX + 8, 1  # just past the bound
    assert B * T <= NB * BLK
    flat = rng.choice(NB * BLK, size=B * T, replace=False)
    blk = jnp.asarray(flat // BLK, jnp.int32).reshape(B, T)
    off = jnp.asarray(flat % BLK, jnp.int32).reshape(B, T)
    blk = blk.at[3].set(NB)  # dead row

    plane2 = jnp.asarray(rng.randint(-5, 99, (NB, BLK)), jnp.int32)
    upd2 = jnp.asarray(rng.randint(100, 200, (B, T)), jnp.int32)
    want2 = plane2.at[blk, off].set(upd2, mode="drop")
    got2 = paged_pool_write(plane2, upd2, blk, off)
    assert np.array_equal(np.asarray(got2), np.asarray(want2))

    L, KVH, d = 2, 2, 8
    plane5 = jnp.asarray(rng.randn(L, KVH, NB, BLK, d), jnp.float32)
    upd5 = jnp.asarray(rng.randn(L, KVH, B, T, d), jnp.float32)
    want5 = plane5.at[:, :, blk, off].set(upd5, mode="drop")
    got5 = paged_pool_write(plane5, upd5, blk, off)
    assert np.array_equal(np.asarray(got5), np.asarray(want5))
