"""Prefix caching in the paged-pool batcher (r5; beyond-reference serving
depth — the reference has no serving at all).

The invariants pinned here:
  * a prefix-cache hit changes WHAT IS COMPUTED, never what is emitted —
    outputs are token-identical to a cold batcher, greedy and sampled;
  * hits actually happen (stats counters) and reuse whole blocks;
  * retained blocks are evicted under allocation pressure without
    corrupting later requests (the stale-position hazard);
  * refcounted sharing frees a block only after its last user finishes.
"""

import jax
import numpy as np
import pytest

from jax_llama_tpu import get_config, init_params
from jax_llama_tpu.serving import ContinuousBatcher

CFG = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    multiple_of=32, max_seq_len=256, dtype="float32", param_dtype="float32",
)


@pytest.fixture(scope="module")
def model():
    config = get_config("tiny", **CFG)
    params = init_params(jax.random.PRNGKey(0), config)
    return params, config


def test_sequential_hit_token_identical_and_counted(model):
    """The /chat pattern: the same long system prompt resubmitted after
    the first request completed must HIT (retained blocks) and emit
    exactly the cold batcher's tokens — greedy and seeded-sampled."""
    params, config = model
    rng = np.random.RandomState(0)
    system = rng.randint(1, 128, size=40).tolist()  # 2.5 blocks of 16
    p1 = system + rng.randint(1, 128, size=5).tolist()
    p2 = system + rng.randint(1, 128, size=7).tolist()

    submits = [
        (p1, dict(max_new_tokens=8)),
        (p2, dict(max_new_tokens=8, temperature=0.8, seed=7)),
    ]
    # Cold: prefix cache disabled entirely.
    cb0 = ContinuousBatcher(params, config, n_slots=1, max_len=128,
                            block_size=16, prefix_cache=False)
    cold_out = []
    for p, kw in submits:
        rid = cb0.submit(list(p), **kw)
        cold_out.append(cb0.run_to_completion()[rid])

    # Warm: sequential submits through one slot; the second shares the
    # system prompt's two full blocks (40 tokens -> blocks 0,1 full;
    # the divergence happens inside block 2).
    cb = ContinuousBatcher(params, config, n_slots=1, max_len=128,
                           block_size=16, prefix_cache=True)
    warm_out = []
    for p, kw in submits:
        rid = cb.submit(list(p), **kw)
        warm_out.append(cb.run_to_completion()[rid])

    assert warm_out == cold_out
    st = cb.stats()
    assert st["prefix_requests_hit_total"] == 1
    assert st["prefix_blocks_reused_total"] == 2
    assert st["prefix_cached_blocks"] > 0  # retained after completion


def test_concurrent_share_refcounts(model):
    """Two live requests sharing a cached prefix: the block is freed only
    after BOTH finish, and outputs match the cold run."""
    params, config = model
    rng = np.random.RandomState(1)
    prefix = rng.randint(1, 128, size=32).tolist()  # 2 full blocks
    a = prefix + [3, 5]
    bq = prefix + [9]

    # Seed the cache with a first request, then submit two sharers that
    # run CONCURRENTLY (2 slots).
    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           block_size=16, prefix_cache=True)
    r0 = cb.submit(list(prefix) + [2], max_new_tokens=4)
    out0 = cb.run_to_completion()[r0]
    assert np.isfinite(len(out0))
    ra = cb.submit(list(a), max_new_tokens=6)
    rb = cb.submit(list(bq), max_new_tokens=6)
    res = cb.run_to_completion()
    st = cb.stats()
    assert st["prefix_requests_hit_total"] == 2
    # Shared blocks survived both completions back into the cache.
    assert st["prefix_cached_blocks"] >= 1

    cold = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                             block_size=16, prefix_cache=False)
    ca = cold.submit(list(a), max_new_tokens=6)
    cbq = cold.submit(list(bq), max_new_tokens=6)
    cres = cold.run_to_completion()
    assert res[ra] == cres[ca]
    assert res[rb] == cres[cbq]


def test_eviction_under_pressure_stays_correct(model):
    """A pool sized so retained prefixes must be evicted to admit new
    work: admission succeeds (capacity counts evictable blocks) and the
    evictee's stale positions never leak into the new request."""
    params, config = model
    rng = np.random.RandomState(2)
    # Pool: exactly two reservations' worth of blocks.
    # Each request: 32-token prompt (2 blocks) + 32 max_new -> 4 blocks.
    n_blocks = 8
    prompts = [rng.randint(1, 128, size=32).tolist() for _ in range(3)]

    cb = ContinuousBatcher(params, config, n_slots=1, max_len=64,
                           block_size=16, n_blocks=n_blocks,
                           prefix_cache=True)
    cold = ContinuousBatcher(params, config, n_slots=1, max_len=64,
                             block_size=16, n_blocks=n_blocks,
                             prefix_cache=False)
    for p in prompts:  # sequential: each retains its prefix on completion
        rid = cb.submit(list(p), max_new_tokens=32)
        want_rid = cold.submit(list(p), max_new_tokens=32)
        got = cb.run_to_completion()[rid]
        want = cold.run_to_completion()[want_rid]
        assert got == want
    # The third admission necessarily evicted earlier retained blocks.
    assert cb.stats()["prefix_cached_blocks"] <= n_blocks


def test_cancel_sharer_keeps_other_alive(model):
    """Cancelling one of two requests sharing cached prefix blocks must
    not free or corrupt the blocks under the survivor (refcount, not
    ownership)."""
    params, config = model
    rng = np.random.RandomState(5)
    prefix = rng.randint(1, 128, size=32).tolist()
    a = prefix + [11]
    b = prefix + [22]

    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           block_size=16, prefix_cache=True)
    cb.submit(list(prefix) + [1], max_new_tokens=2)
    cb.run_to_completion()  # seed the cache
    ra = cb.submit(list(a), max_new_tokens=8)
    rb = cb.submit(list(b), max_new_tokens=8)
    got = {ra: [], rb: []}
    for rid, tok, *_ in cb.step():  # both admitted (as hits), decoding
        got[rid].append(tok)
    assert cb.cancel(ra)
    while cb.pending():
        for rid, tok, *_ in cb.step():
            got[rid].append(tok)

    cold = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                             block_size=16, prefix_cache=False)
    cw = cold.submit(list(b), max_new_tokens=8)
    want = cold.run_to_completion()[cw]
    assert got[rb] == want
    # And a later resubmit (hitting the still-cached chain) matches too.
    rb2 = cb.submit(list(b), max_new_tokens=8)
    assert cb.run_to_completion()[rb2] == want


# slow (r06 budget rebalance, ~19 s): hit + logprobs parity is also
# pinned by test_kvcache.py's parity matrix and the multi-chunk
# suffix shape by test_suffix_admission_buckets below.
@pytest.mark.slow
def test_chunked_suffix_and_logprobs(model):
    """A hit whose remaining suffix spans multiple prefill chunks (the
    chunked gathered-view path), with logprobs on: outputs AND per-token
    logprobs identical to the cold batcher."""
    params, config = model
    rng = np.random.RandomState(4)
    prefix = rng.randint(1, 128, size=32).tolist()  # 2 full blocks
    long_suffix = rng.randint(1, 128, size=70).tolist()  # > 2 chunks of 32
    prompt = prefix + long_suffix

    def run(pc):
        cb = ContinuousBatcher(
            params, config, n_slots=1, max_len=256, block_size=16,
            prefill_chunk=32, logprobs=True, prefix_cache=pc,
        )
        # Seed the cache with a short request sharing only the prefix.
        cb.submit(list(prefix) + [7], max_new_tokens=2)
        cb.run_to_completion()
        rid = cb.submit(list(prompt), max_new_tokens=6)
        out = []
        while cb.pending():
            for tup in cb.step():
                if tup[0] == rid:
                    out.append((tup[1], round(float(tup[3]), 5)))
        return out, cb.stats()

    warm, wst = run(True)
    cold, _ = run(False)
    assert warm == cold
    assert wst["prefix_requests_hit_total"] == 1
    assert wst["prefix_blocks_reused_total"] == 2


def test_grouped_hits_with_differing_prefix_depths(model):
    """One grouped suffix-insert dispatch whose rows have DIFFERENT
    cached-prefix depths (fill0 32 vs 48) but the same padded suffix
    length: per-row offsets must be honored independently — outputs
    identical to the cold batcher for both rows."""
    params, config = model
    rng = np.random.RandomState(6)
    pref_a = rng.randint(1, 128, size=32).tolist()  # 2 full blocks
    pref_b = rng.randint(1, 128, size=48).tolist()  # 3 full blocks
    a = pref_a + rng.randint(1, 128, size=10).tolist()  # suffix pads to 16
    b = pref_b + rng.randint(1, 128, size=12).tolist()  # suffix pads to 16

    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           block_size=16, prefix_cache=True)
    cb.submit(list(pref_a) + [1], max_new_tokens=2)
    cb.submit(list(pref_b) + [2], max_new_tokens=2)
    cb.run_to_completion()  # seed both chains
    ra = cb.submit(list(a), max_new_tokens=6)
    rb = cb.submit(list(b), max_new_tokens=6)
    res = cb.run_to_completion()
    st = cb.stats()
    assert st["prefix_requests_hit_total"] == 2
    assert st["prefix_blocks_reused_total"] == 5  # 2 + 3

    cold = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                             block_size=16, prefix_cache=False)
    ca = cold.submit(list(a), max_new_tokens=6)
    cbr = cold.submit(list(b), max_new_tokens=6)
    cres = cold.run_to_completion()
    assert res[ra] == cres[ca]
    assert res[rb] == cres[cbr]


# slow (r17 budget rebalance, ~7 s): the two composing contracts keep
# tier-1 pins — repeat-hit exactness via
# test_sequential_hit_token_identical_and_counted, speculative serving
# identity via test_serving_spec's tier-1 R cells — so the composed
# prefix-hit x spec drill rides slow (unfiltered suite runs it).
@pytest.mark.slow
def test_repeat_same_prompt_exact_with_spec(model):
    """Prefix hits compose with speculative decoding (draft pool shares
    the same blocks/chain): identical outputs, and the second submit of
    an identical prompt hits."""
    params, config = model
    draft_config = get_config(
        "tiny", **{**CFG, "dim": 32, "n_layers": 1, "n_heads": 2,
                   "n_kv_heads": 1}
    )
    draft_params = init_params(jax.random.PRNGKey(1), draft_config)
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, 128, size=33).tolist()

    outs = []
    for pc in (False, True):
        cb = ContinuousBatcher(
            params, config, n_slots=1, max_len=128, block_size=16,
            draft_params=draft_params, draft_config=draft_config,
            n_draft=2, prefix_cache=pc,
        )
        got = []
        for _ in range(2):
            rid = cb.submit(list(prompt), max_new_tokens=10)
            got.append(cb.run_to_completion()[rid])
        outs.append(got)
        if pc:
            assert cb.stats()["prefix_requests_hit_total"] == 1
    assert outs[0] == outs[1]
    # Determinism across repeats too (greedy).
    assert outs[0][0] == outs[0][1]


def test_duplicate_chain_leaves_no_unreachable_blocks(model):
    """Two identical prompts in ONE cold admission burst both prefill
    fully and both publish the same chain keys.  Radix semantics
    (migrated from the pre-r06 exact-chain supersede pin): the shared
    prefix is ONE set of nodes by construction — the second
    publication leaves the existing nodes' blocks in place and its own
    duplicate copies stay unkeyed, freeing plainly with their slots.
    Nothing retained may be unreachable, refcounts must not dangle,
    and free + retained must account for the whole pool."""
    params, config = model
    rng = np.random.RandomState(11)
    prompt = rng.randint(1, 128, size=40).tolist()  # 2 full keyed blocks

    cb = ContinuousBatcher(params, config, n_slots=2, max_len=128,
                           block_size=16, prefix_cache=True)
    for _ in range(2):  # repeat the burst: cold, then hitting
        r1 = cb.submit(list(prompt), max_new_tokens=4)
        r2 = cb.submit(list(prompt), max_new_tokens=4)
        res = cb.run_to_completion()
        assert set(res) >= {r1, r2}
        assert res[r1] == res[r2]
        # No dangling refcounts, exact capacity accounting, and the
        # tree holds exactly the chain's 2 nodes — the duplicate burst
        # did NOT mint a second copy of the shared prefix.
        assert not cb._block_refs
        assert (len(cb.free_blocks) + cb._store.cached_blocks()
                == cb.n_blocks)
        assert cb.stats()["radix_nodes_total"] == 2

    # Directly exercise the duplicate-publication branch: publishing a
    # fresh block for a chain whose node is already resident keeps the
    # EXISTING node's block; the fresh copy stays unkeyed (it frees
    # with its slot instead of lingering unreachable).
    store = cb._store
    key = next(iter(store._by_key))
    old_blk = store._by_key[key].block
    new_blk = cb.free_blocks[0]
    cb._register_chain([new_blk], [key])
    assert store._by_key[key].block == old_blk
    assert not store.is_keyed(new_blk)
    assert store.is_keyed(old_blk)


# slow (r17 budget rebalance, ~12 s): the bounded-executable contract is
# statically tier-1-pinned by the retrace auditor (tests/test_analysis.py
# gates the bounded jit-cache-key domains, _paged_suffix_insert
# included) and grouped-suffix token identity stays tier-1-pinned by
# test_grouped_hits_with_differing_prefix_depths; the dynamic
# compile-counting drill rides slow (unfiltered suite runs it).
@pytest.mark.slow
def test_suffix_admission_buckets_jit_executables(model):
    """Grouped suffix admission buckets the padded suffix length to a
    power of two of blocks (like admission row counts), so diverse /chat
    suffix lengths compile a BOUNDED set of _paged_suffix_insert
    executables: four hits whose block-rounded suffixes span {32, 48,
    48, 64} tokens share the {32, 64} buckets — 2 compiles, not 3 — and
    outputs stay identical to a cold batcher."""
    from jax_llama_tpu.serving import _paged_suffix_insert

    params, config = model
    rng = np.random.RandomState(12)
    base = rng.randint(1, 128, size=32).tolist()   # the shared 2 blocks
    prime = base + rng.randint(1, 128, size=16).tolist()
    extras = [rng.randint(1, 128, size=n).tolist()
              for n in (17, 33, 45, 60)]

    cb = ContinuousBatcher(params, config, n_slots=1, max_len=128,
                           block_size=16, prefix_cache=True)
    rid = cb.submit(list(prime), max_new_tokens=2)
    cb.run_to_completion()
    before = _paged_suffix_insert._cache_size()
    got = []
    for extra in extras:
        rid = cb.submit(base + extra, max_new_tokens=4)
        got.append(cb.run_to_completion()[rid])
    assert cb.stats()["prefix_requests_hit_total"] == 4
    compiled = _paged_suffix_insert._cache_size() - before
    assert compiled == 2, compiled  # buckets {32, 64}, not {32, 48, 64}

    cold = ContinuousBatcher(params, config, n_slots=1, max_len=128,
                             block_size=16, prefix_cache=False)
    for extra, want in zip(extras, got):
        rid = cold.submit(base + extra, max_new_tokens=4)
        assert cold.run_to_completion()[rid] == want


# NOTE: these run LAST: their admissions compile suffix-insert shapes
# that would otherwise perturb test_suffix_admission_buckets' compile
# count (the jit cache is cleared per MODULE, not per test).

def test_radix_partial_prefix_shared_across_divergent_chains(model):
    """The radix win the flat map could not express as sharing: three
    chains diverging AFTER a common 2-block prefix share those two
    NODES (5 nodes total, not 6+), and a fourth request extending the
    common prefix hits it at full depth — token-identically to cold."""
    params, config = model
    rng = np.random.RandomState(13)
    common = rng.randint(1, 128, size=32).tolist()   # 2 full blocks
    tails = [rng.randint(1, 128, size=18).tolist() for _ in range(3)]

    cb = ContinuousBatcher(params, config, n_slots=1, max_len=128,
                           block_size=16, prefix_cache=True)
    for tail in tails:  # sequential: each publishes its whole chain
        rid = cb.submit(common + tail, max_new_tokens=4)
        cb.run_to_completion()
    st = cb.stats()
    # chains are keyed on blocks strictly before the last token:
    # 50 tokens -> 3 keyed blocks each; 2 shared + 3 x 1 divergent.
    assert st["radix_nodes_total"] == 5
    # Chains 2 and 3 hit the shared 2-block prefix.
    assert st["prefix_requests_hit_total"] == 2
    assert st["prefix_blocks_reused_total"] == 4

    probe = common + [3, 5, 7]
    rid = cb.submit(list(probe), max_new_tokens=6)
    got = cb.run_to_completion()[rid]
    cold = ContinuousBatcher(params, config, n_slots=1, max_len=128,
                             block_size=16, prefix_cache=False)
    cr = cold.submit(list(probe), max_new_tokens=6)
    assert got == cold.run_to_completion()[cr]
    assert cb.stats()["prefix_hit_tokens_ratio"] > 0


