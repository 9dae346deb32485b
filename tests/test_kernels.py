"""Kernel-selection layer (ops/kernels.py): the pluggable splash-mha
prefill and stock Pallas paged-attention decode kernels.

What is pinned here, all CPU-runnable via Pallas ``interpret=True``:

  * registry/resolution: the auto policies, the unknown-name errors,
    the per-chunk splash eligibility predicate, and that every
    selectable kernel's fallback ladder / degrade feature / fault site
    actually exist in degrade.py, faults.py and obs.py — the PR-11/12
    landing-checklist wiring, checked as data;
  * op-level numerics: splash prefill vs a dense causal reference
    (offset mask, GQA head mapping) and the stock decode kernel vs an
    explicit bf16-cast gathered reference (TIGHT — that is the kernel's
    documented arithmetic) and vs the custom paged kernel (LOOSE — the
    stock kernel casts K/V tiles to bf16 in-kernel, a documented ~3e-3
    divergence on fp32 pools, which is why stock-vs-custom greedy
    serving is A/B-comparable but not token-identical);
  * serving-level behavior: a splash batcher is TOKEN-IDENTICAL to the
    flash batcher (same fp32 math, different pipelining), the stock
    decode path is chunking-invariant (K=1 vs K=4 token-identical),
    the speculative path with a stock-paged draft is token-identical
    to the plain custom batcher (the target's verify sweep stays on
    the custom kernel), and each kernel books its own dispatch kind
    ("insert:splash" / "decode:stock-paged") for per-kernel MXU
    attribution;
  * quarantine drills: every splash/stock dispatch faulting quarantines
    the kernel's OWN feature and the batcher rebuilds onto the EXISTING
    custom kernel — mid-stream, with delivered tokens identical to the
    fallback-kernel healthy reference (faults fire before dispatch, so
    no divergent token is ever emitted, and the replay is
    teacher-forced).

TPU companions (compiled Mosaic vs the interpret path) ride the ``tpu``
marker and self-skip off-chip; they are also marked ``slow`` so tier-1
collection never pays for them.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.degrade import FEATURES
from jax_llama_tpu.faults import SITES, FaultInjector
from jax_llama_tpu.obs import DISPATCH_KINDS
from jax_llama_tpu.ops.kernels import (
    DECODE_KERNELS,
    PREFILL_KERNELS,
    resolve_decode_kernel,
    resolve_prefill_kernel,
    splash_eligible,
    splash_prefill,
    stock_paged_decode,
)
from jax_llama_tpu.ops.paged_attention import paged_decode_attention
from jax_llama_tpu.server import LLMServer
from jax_llama_tpu.serving import ContinuousBatcher


@pytest.fixture(scope="module")
def tpu_chip():
    """Skip unless this process holds a TPU — probed when a test that
    asks for it starts, never while the file is imported (every xdist
    worker imports it; an import-time probe makes workers collect
    different tests)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs the real TPU chip (JAX_PLATFORMS=tpu, -m tpu)")


# The stock kernel's tiny serving geometry (d=16 — identical to
# test_degrade's): the stock decode path has no lane-alignment
# requirement in interpret mode.  The SPLASH geometry needs head_dim
# 128 (the kernel's lane tiling), so it gets its own config; with
# block_size=128 every cold insert pads to a 128-multiple P and the
# whole-prompt chunk is splash-eligible.
CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=128, dtype="float32",
    param_dtype="float32",
)
SPLASH_CFG = dict(
    vocab_size=128, dim=256, n_layers=2, n_heads=2, n_kv_heads=1,
    multiple_of=32, max_seq_len=256, dtype="float32",
    param_dtype="float32", attn_impl="auto",
)
PROMPTS = [[5, 17, 99, 3], [7, 8, 9]]
MAX_NEW = 6


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


@pytest.fixture(scope="module")
def reference(model):
    """Healthy greedy tokens through the CUSTOM paged kernel — the
    oracle for the stock-paged fallback/identity assertions."""
    params, config = model
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=64)
    rids = [cb.submit(list(p), max_new_tokens=MAX_NEW) for p in PROMPTS]
    out = cb.run_to_completion()
    return [out[r] for r in rids]


@pytest.fixture(scope="module")
def splash_model():
    config = get_config("tiny", **SPLASH_CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


@pytest.fixture(scope="module")
def flash_reference(splash_model):
    """Healthy greedy tokens through the CUSTOM flash prefill on the
    splash-eligible config — the oracle splash must match exactly."""
    params, config = splash_model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=256, block_size=128,
        prefill_kernel="flash",
    )
    rids = [cb.submit(list(p), max_new_tokens=MAX_NEW) for p in PROMPTS]
    out = cb.run_to_completion()
    return [out[r] for r in rids]


def _post(url, payload, timeout=300):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _health(url):
    try:
        with urllib.request.urlopen(url + "/healthz", timeout=60) as r:
            body = r.read().decode()
    except urllib.error.HTTPError as e:
        body = e.read().decode()
    return json.loads(body)


def _kinds(cb):
    return {d["kind"] for d in cb.obs.dispatches_json()["dispatches"]}


# ---------------------------------------------------------------------------
# Registry / resolution (pure host — no jax arrays)
# ---------------------------------------------------------------------------

def test_resolution_auto_policies(model, splash_model):
    _, small = model          # head_dim 16: splash structurally out
    _, wide = splash_model    # head_dim 128: splash-capable
    assert resolve_prefill_kernel("auto", small) == "flash"
    assert resolve_prefill_kernel(None, small) == "flash"
    assert resolve_prefill_kernel("auto", wide) == "splash"
    # int8 pools stay on the custom kernels under auto.
    assert resolve_prefill_kernel(
        "auto", wide.replace(kv_cache_dtype="int8")
    ) == "flash"
    # Decode auto keeps the custom kernel (int8, multi-token verify,
    # measured grid); stock stays the explicit A/B choice.
    assert resolve_decode_kernel("auto", small) == "paged"
    assert resolve_decode_kernel(None, wide) == "paged"
    assert resolve_decode_kernel("stock-paged", small) == "stock-paged"
    with pytest.raises(ValueError, match="unknown prefill kernel"):
        resolve_prefill_kernel("nosuch", small)
    with pytest.raises(ValueError, match="unknown decode kernel"):
        resolve_decode_kernel("nosuch", small)


def test_describe_names_the_resolved_kernels(model):
    """/debug/bundle's batcher section says which kernels the ctor
    RESOLVED and whether the paged kernel can run at this geometry —
    what chip_smoke.py reads to refuse a run that never touched Pallas."""
    params, config = model
    d = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, prefill_kernel="auto",
        decode_kernel="stock-paged",
    ).describe()
    assert (d["prefill_kernel"], d["decode_kernel"]) == (
        "flash", "stock-paged"
    )
    assert d["attn_impl"] == config.attn_impl
    assert d["use_pallas_kernel"] is True
    assert d["paged_kernel_eligible"] is True
    g = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, decode_kernel="gathered",
    ).describe()
    # "gathered" is the paged path with the Pallas kernel off.
    assert g["use_pallas_kernel"] is False and g["decode_kernel"] == "paged"
    odd = ContinuousBatcher(
        params, config, n_slots=2, max_len=64, block_size=12,
    ).describe()
    assert odd["paged_kernel_eligible"] is False   # 12 % 8 != 0


def test_splash_eligibility_gates(splash_model):
    _, cfg = splash_model
    cfg = cfg.replace(prefill_kernel="splash")
    ok = dict(batch=2, q_len=128, kv_len=256, chunk_offset=0,
              quantized=False, mesh=None)
    assert splash_eligible(cfg, **ok)
    # Each structural requirement gates independently.
    assert not splash_eligible(cfg, **{**ok, "q_len": 120})
    assert not splash_eligible(cfg, **{**ok, "kv_len": 130})
    assert not splash_eligible(cfg, **{**ok, "chunk_offset": None})
    assert not splash_eligible(cfg, **{**ok, "quantized": True})
    assert not splash_eligible(
        cfg.replace(prefill_kernel="flash"), **ok
    )


def test_registry_wiring_is_complete():
    """The landing checklist as data: every selectable kernel's
    fallback names a registered kernel of the same role, and its
    degrade feature / fault site / dispatch kind all exist where
    serving will look them up."""
    assert PREFILL_KERNELS["splash"].fallback == "flash"
    assert DECODE_KERNELS["stock-paged"].fallback == "paged"
    for reg in (PREFILL_KERNELS, DECODE_KERNELS):
        for spec in reg.values():
            if spec.fallback is not None:
                assert spec.fallback in reg
            if spec.feature is not None:
                assert spec.feature in FEATURES
            if spec.fault_site is not None:
                assert spec.fault_site in SITES
    # Per-kernel MXU attribution kinds (obs.py validates these).
    assert "insert:splash" in DISPATCH_KINDS
    assert "decode:stock-paged" in DISPATCH_KINDS


# ---------------------------------------------------------------------------
# Op-level parity (Pallas interpret mode)
# ---------------------------------------------------------------------------

def _pool_state(rng, B, KVH, d, L, NB, BLK, MB, fills):
    """A multi-layer block pool with per-row fills: returns the 5-D
    k/v pools, the slot-position map, and the block table (same layout
    test_paged_attention pins for the custom kernel)."""
    kp = rng.randn(L, KVH, NB, BLK, d).astype(np.float32)
    vp = rng.randn(L, KVH, NB, BLK, d).astype(np.float32)
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


def _stock_case(seed=0, d=32):
    rng = np.random.RandomState(seed)
    B, H, KVH = 4, 8, 2
    L, NB, BLK, MB = 2, 12, 16, 5
    # multi-block, empty (inactive), one block, partial block
    fills = [40, 0, 16, 7]
    qpos = np.array([40, -1, 16, 7], np.int32)
    kp, vp, pool_pos, table = _pool_state(
        rng, B, KVH, d, L, NB, BLK, MB, fills
    )
    q = rng.randn(B, 1, H, d).astype(np.float32)
    kn = rng.randn(B, 1, KVH, d).astype(np.float32)
    vn = rng.randn(B, 1, KVH, d).astype(np.float32)
    return q, kn, vn, kp, vp, pool_pos, table, qpos


def _bf16_reference(q, kn, vn, kp, vp, table, qpos, layer, b):
    """Row b's attention with pool K/V cast to bf16 BEFORE the math —
    exactly the stock kernel's documented in-kernel cast; the step's
    own slot merges at fp32 (outside the kernel)."""
    _, _, H, d = q.shape
    KVH, NB = kp.shape[1], kp.shape[2]
    G = H // KVH
    scale = 1.0 / np.sqrt(d)
    f = int(qpos[b])
    ks = [kp[layer][:, t] for t in table[b] if t < NB]
    vs = [vp[layer][:, t] for t in table[b] if t < NB]
    kcat = np.concatenate(ks, axis=1)[:, :f]    # [KVH, f, d]
    vcat = np.concatenate(vs, axis=1)[:, :f]
    kb = np.asarray(
        jnp.asarray(kcat).astype(jnp.bfloat16).astype(jnp.float32)
    )
    vb = np.asarray(
        jnp.asarray(vcat).astype(jnp.bfloat16).astype(jnp.float32)
    )
    out = np.zeros((H, d), np.float32)
    for h in range(H):
        kh = h // G
        s = np.concatenate([
            (q[b, 0, h] * scale) @ kb[kh].T,
            [(q[b, 0, h] @ kn[b, 0, kh]) * scale],
        ])
        w = np.exp(s - s.max())
        w /= w.sum()
        out[h] = w[:-1] @ vb[kh] + w[-1] * vn[b, 0, kh]
    return out


def test_stock_decode_matches_bf16_reference():
    """TIGHT parity vs the explicit bf16-cast gathered reference: the
    flat-page layer/head offsets, the lse merge of the step's own
    slot, and the GQA head grouping are exact; inactive rows (q_pos
    -1) produce finite discarded output."""
    q, kn, vn, kp, vp, _, table, qpos = _stock_case()
    layer = 1
    got = np.asarray(stock_paged_decode(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(qpos), jnp.asarray(layer, jnp.int32), interpret=True,
    ))
    assert np.isfinite(got).all()
    for b in range(q.shape[0]):
        if qpos[b] < 0:
            continue
        want = _bf16_reference(q, kn, vn, kp, vp, table, qpos, layer, b)
        np.testing.assert_allclose(got[b, 0], want, atol=1e-5, rtol=1e-5)


def test_stock_decode_tracks_custom_kernel_loosely():
    """LOOSE parity vs the custom paged kernel: same contract, but the
    stock kernel's in-kernel bf16 K/V cast rounds fp32 pools once more
    (~3e-3 here) — the reason stock-vs-custom serving is A/B-compared,
    never asserted token-identical."""
    q, kn, vn, kp, vp, pool_pos, table, qpos = _stock_case()
    layer = 1
    got = np.asarray(stock_paged_decode(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(qpos), jnp.asarray(layer, jnp.int32), interpret=True,
    ))
    custom = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp[layer]), jnp.asarray(vp[layer]),
        jnp.asarray(pool_pos), jnp.asarray(table), jnp.asarray(qpos),
    ))
    for b in range(q.shape[0]):
        if qpos[b] < 0:
            continue
        np.testing.assert_allclose(
            got[b], custom[b], atol=2e-2, rtol=2e-2
        )


def test_stock_decode_layer_select_and_guards():
    """The flat-page offset must pick exactly the (layer, head) plane a
    4-D single-layer launch of that plane picks; the T > 1 and
    missing-layer misuses raise before any launch."""
    q, kn, vn, kp, vp, _, table, qpos = _stock_case(seed=3)
    five_d = np.asarray(stock_paged_decode(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(qpos), jnp.asarray(1, jnp.int32), interpret=True,
    ))
    four_d = np.asarray(stock_paged_decode(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp[1]), jnp.asarray(vp[1]), jnp.asarray(table),
        jnp.asarray(qpos), interpret=True,
    ))
    np.testing.assert_array_equal(five_d, four_d)
    with pytest.raises(ValueError, match="multi-layer pool"):
        stock_paged_decode(
            jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
            jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
            jnp.asarray(qpos), interpret=True,
        )
    with pytest.raises(NotImplementedError, match="T == 1 only"):
        stock_paged_decode(
            jnp.asarray(np.repeat(q, 2, axis=1)), jnp.asarray(kn),
            jnp.asarray(vn), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(qpos),
            jnp.asarray(1, jnp.int32), interpret=True,
        )


def test_splash_prefill_matches_dense_reference():
    """Splash vs dense causal attention at a chunk offset: query row t
    at absolute position offset+t attends cache columns j <= offset+t,
    GQA query head h reads KV head h // group, and the caller-side
    d**-0.25 double-scaling reproduces plain 1/sqrt(d) softmax."""
    B, T, S, H, KVH, d = 2, 128, 256, 4, 2, 128
    off = 128
    rng = np.random.RandomState(1)
    q = rng.randn(B, T, H, d).astype(np.float32) * 0.5
    k = rng.randn(B, S, KVH, d).astype(np.float32) * 0.5
    v = rng.randn(B, S, KVH, d).astype(np.float32) * 0.5
    got = np.asarray(splash_prefill(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        chunk_offset=off, interpret=True,
    ))
    G = H // KVH
    scale = d ** -0.5
    mask = np.arange(S)[None, :] <= (np.arange(T)[:, None] + off)
    for b in range(B):
        for h in range(H):
            s = (q[b, :, h] @ k[b, :, h // G].T) * scale
            s = np.where(mask, s, -1e30)
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            np.testing.assert_allclose(
                got[b, :, h], w @ v[b, :, h // G], atol=1e-5, rtol=1e-5
            )


# ---------------------------------------------------------------------------
# Serving-level behavior (CPU, interpret-mode kernels)
# ---------------------------------------------------------------------------

def test_serving_splash_token_identical_to_flash(
    splash_model, flash_reference
):
    """The splash batcher's greedy tokens match the flash batcher's
    EXACTLY (both fp32 — the kernels differ in pipelining, not math),
    and the insert books its per-kernel dispatch kind."""
    params, config = splash_model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=256, block_size=128,
        prefill_kernel="splash",
    )
    rids = [cb.submit(list(p), max_new_tokens=MAX_NEW) for p in PROMPTS]
    out = cb.run_to_completion()
    assert [out[r] for r in rids] == flash_reference
    assert "insert:splash" in _kinds(cb)


# slow (r17 budget rebalance, ~8 s): the stock kernel's numerics stay
# tier-1-pinned op-level (the bf16-reference and loose-custom parity
# cells above) and its serving fallback stays tier-1-pinned by the
# quarantine drill below; the K=1-vs-K=4 serving drain rides the slow
# tier (`make kernels` and the unfiltered suite still run it).
@pytest.mark.slow
def test_serving_stock_decode_chunking_invariant(model):
    """The stock decode path must be chunking-invariant: K=1 and K=4
    drains are token-identical (the kernel sees identical per-step
    geometry either way), and pure-decode chunks book the
    "decode:stock-paged" attribution kind."""
    params, config = model

    def run(K):
        cb = ContinuousBatcher(
            params, config, n_slots=2, max_len=64,
            decode_kernel="stock-paged", decode_chunk=K,
        )
        rids = [
            cb.submit(list(p), max_new_tokens=MAX_NEW) for p in PROMPTS
        ]
        out = cb.run_to_completion()
        return [out[r] for r in rids], _kinds(cb)

    toks1, kinds1 = run(1)
    toks4, kinds4 = run(4)
    assert toks1 == toks4
    assert "decode:stock-paged" in kinds1
    assert "decode:stock-paged" in kinds4


# slow (r17 budget rebalance, ~6 s): the two composing contracts keep
# tier-1 pins — stock decode numerics op-level above, speculative
# serving identity in tests/test_serving_spec.py — so the composed
# stock-draft drill rides slow (`make kernels` still runs it).
@pytest.mark.slow
def test_serving_spec_stock_draft_token_identity(model, reference):
    """Speculative serving with a stock-paged DRAFT stays
    token-identical to the plain custom batcher: the target's verify
    sweep keeps the custom kernel (T = G+1 > 1), so acceptance
    decisions — and therefore emitted tokens — never see the stock
    kernel's bf16 rounding."""
    params, config = model
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64,
        decode_kernel="stock-paged",
        draft_params=params, draft_config=config, n_draft=2,
    )
    rids = [cb.submit(list(p), max_new_tokens=MAX_NEW) for p in PROMPTS]
    out = cb.run_to_completion()
    assert [out[r] for r in rids] == reference


# ---------------------------------------------------------------------------
# Quarantine drills: each opt-in kernel falls back to the EXISTING
# custom kernel, token-identically, mid-stream
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_stock_paged_quarantine_falls_back_to_custom(model, reference):
    """Every stock-paged decode dispatch faults (host-side, BEFORE the
    kernel runs — no divergent token is ever delivered): the
    stock_paged feature quarantines mid-request, the batcher rebuilds
    onto the CUSTOM paged kernel (one rung, not XLA), and the delivered
    tokens are identical to the custom-kernel healthy reference."""
    params, config = model
    inj = FaultInjector("stock_paged_kernel~1.0:error")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=64,
        decode_kernel="stock-paged", fault_injector=inj,
    )
    results = {}
    with LLMServer(
        cb, quarantine_threshold=2, quarantine_cooldown_s=3600.0
    ) as srv:
        def call(i):
            try:
                _, body = _post(
                    srv.address,
                    {"prompt": PROMPTS[i], "max_new_tokens": MAX_NEW},
                )
                results[i] = body["tokens"]
            except Exception as e:  # noqa: BLE001
                results[i] = f"{type(e).__name__}: {e}"

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        for i in range(len(PROMPTS)):
            assert results[i] == reference[i], i
        h = _health(srv.address)
        assert h["ok"] is True and h["degraded"] is True
        assert h["quarantined"] == ["stock_paged"]
        # One rung down the ladder: the rebuilt batcher runs the CUSTOM
        # paged kernel, not the gathered view.
        assert srv.batcher.config.decode_kernel == "paged"
        assert srv.batcher.use_pallas_kernel


@pytest.mark.faults
def test_splash_quarantine_falls_back_to_flash(
    splash_model, flash_reference
):
    """Every splash insert dispatch faults: splash_prefill quarantines,
    the batcher rebuilds with prefill_kernel='flash' (flash_attention
    itself stays healthy — its own site did not fault), and the request
    completes token-identical to the flash reference."""
    params, config = splash_model
    inj = FaultInjector("splash_kernel~1.0:error")
    cb = ContinuousBatcher(
        params, config, n_slots=2, max_len=256, block_size=128,
        prefill_kernel="splash", fault_injector=inj,
    )
    with LLMServer(
        cb, quarantine_threshold=2, quarantine_cooldown_s=3600.0
    ) as srv:
        _, body = _post(
            srv.address, {"prompt": PROMPTS[0], "max_new_tokens": MAX_NEW}
        )
        assert body["tokens"] == flash_reference[0]
        h = _health(srv.address)
        assert h["quarantined"] == ["splash_prefill"]
        assert srv.batcher.config.prefill_kernel == "flash"
        # The flash feature itself is untouched: one rung at a time.
        assert h["features"]["flash_attention"]["state"] == "healthy"
        # A follow-up request serves entirely on the flash path.
        _, body = _post(
            srv.address, {"prompt": PROMPTS[1], "max_new_tokens": MAX_NEW}
        )
        assert body["tokens"] == flash_reference[1]


# ---------------------------------------------------------------------------
# TPU companions (compiled Mosaic; self-skip off-chip, slow-marked so
# tier-1 never collects their cost)
# ---------------------------------------------------------------------------

@pytest.mark.tpu
@pytest.mark.slow
def test_tpu_splash_prefill_compiled_matches_dense(tpu_chip):
    B, T, S, H, KVH, d = 1, 128, 256, 4, 2, 128
    rng = np.random.RandomState(7)
    q = rng.randn(B, T, H, d).astype(np.float32) * 0.5
    k = rng.randn(B, S, KVH, d).astype(np.float32) * 0.5
    v = rng.randn(B, S, KVH, d).astype(np.float32) * 0.5
    got = np.asarray(splash_prefill(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        chunk_offset=128, interpret=False,
    ))
    G, scale = H // KVH, d ** -0.5
    mask = np.arange(S)[None, :] <= (np.arange(T)[:, None] + 128)
    for h in range(H):
        s = (q[0, :, h] @ k[0, :, h // G].T) * scale
        s = np.where(mask, s, -1e30)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            got[0, :, h], w @ v[0, :, h // G], atol=2e-2, rtol=2e-2
        )


@pytest.mark.tpu
@pytest.mark.slow
def test_tpu_stock_decode_compiled_tracks_custom(tpu_chip):
    # head_dim 128: compiled, the stock kernel's (m, l) outputs need a
    # lane-aligned last block dim (first chip run, PR 21: d=32 is refused
    # by the Pallas TPU lowering; interpret mode has no such rule).
    q, kn, vn, kp, vp, pool_pos, table, qpos = _stock_case(seed=11, d=128)
    got = np.asarray(stock_paged_decode(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(qpos), jnp.asarray(1, jnp.int32), interpret=False,
    ))
    custom = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(kp[1]), jnp.asarray(vp[1]), jnp.asarray(pool_pos),
        jnp.asarray(table), jnp.asarray(qpos),
    ))
    for b in range(q.shape[0]):
        if qpos[b] < 0:
            continue
        np.testing.assert_allclose(
            got[b], custom[b], atol=2e-2, rtol=2e-2
        )
