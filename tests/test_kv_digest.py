"""KV chain-digest correctness (kvcache.KvDigest — PR 13 fleet cache
telemetry): determinism for identical published content, version /
loss-version semantics at every mutation class, the bounded /debug/kv
walk, and the per-event ledger.  Pure host-side store manipulation —
no model, no device dispatches — so the whole module is tier-1 cheap."""

import json

import pytest

from jax_llama_tpu.kvcache import (
    KvDigest,
    NullPrefixStore,
    RadixPrefixStore,
)

pytestmark = pytest.mark.kvcache


def _key(i: int) -> bytes:
    return b"chain-%04d" % i


def _chain(prefix: int, n: int):
    """n chain keys sharing a per-prefix namespace (divergent chains
    share nothing here; radix sharing is exercised via shared keys)."""
    return [_key(prefix * 100 + j) for j in range(n)]


def test_digest_deterministic_for_same_published_chains():
    """Same published content (two divergent chains sharing a common
    prefix), different publish/evict interleavings -> identical hash
    and identical sorted node list (the XOR set-hash is order-free)."""
    shared = [_key(1), _key(2)]
    a_tail = [_key(10)]
    b_tail = [_key(20)]

    s1 = RadixPrefixStore()
    s1.publish(shared + a_tail, [0, 1, 2])
    s1.publish(shared + b_tail, [0, 1, 3])

    s2 = RadixPrefixStore()
    # Reverse order, plus a publish/evict detour that cancels out.
    s2.publish(shared + b_tail, [5, 6, 7])
    s2.publish([_key(99)], [4])
    s2.retain([4])
    s2.pop_evictable()  # drops the detour chain again
    s2.publish(shared + a_tail, [5, 6, 8])

    d1, d2 = s1.digest.summary(), s2.digest.summary()
    assert d1["hash"] == d2["hash"]
    assert d1["nodes"] == d2["nodes"] == 4
    n1 = s1.digest.nodes_json()["nodes"]
    n2 = s2.digest.nodes_json()["nodes"]
    strip = lambda ns: [  # noqa: E731 - local shorthand
        {k: n[k] for k in ("key", "depth", "tier")} for n in ns
    ]
    assert strip(n1) == strip(n2)
    # Versions tell the EDIT history apart even when content matches.
    assert d2["version"] > d1["version"]


def test_version_bumps_on_publish_evict_demote_restore():
    store = RadixPrefixStore(host_blocks=4)
    dg = store.digest
    assert dg.summary()["version"] == 0

    store.publish(_chain(0, 2), [0, 1])
    v1 = dg.summary()["version"]
    assert v1 == 2  # one bump per published block
    assert dg.summary()["loss_version"] == 0

    # Demote: version AND loss_version move (HBM residency lost).
    store.retain([0, 1])
    blk, extra = store.pop_evictable(lambda b: {"pos": None})
    assert blk == 1 and not extra  # leaves-first: deepest idle first
    s = dg.summary()
    assert s["version"] > v1
    assert s["loss_version"] == 1
    assert s["demotions_total"] == 1
    assert (s["hbm_blocks"], s["host_blocks"]) == (1, 1)

    # Restore flips it back; version moves, loss_version does not.
    node = store.match(_chain(0, 2)).restore[0]
    store.pin_restoring([node])
    v2, l2 = s["version"], s["loss_version"]
    store.complete_restore([node], [5])
    s = dg.summary()
    assert s["version"] > v2 and s["loss_version"] == l2
    assert s["restores_total"] == 1

    # Unpublish (the non-finite guard): nodes leave, losses count.
    store.unpublish(0)
    s = dg.summary()
    assert s["nodes"] == 0
    assert s["evictions_total"] == 2
    assert s["loss_version"] > l2


def test_idle_flag_tracks_refcount_boundary_without_version_noise():
    store = RadixPrefixStore()
    store.publish(_chain(0, 2), [0, 1])
    v = store.digest.summary()["version"]
    store.retain([0, 1])
    s = store.digest.summary()
    assert s["idle_blocks"] == 2
    assert s["version"] == v  # claims/retains are not content edits
    store.on_claim([0])
    s = store.digest.summary()
    assert s["idle_blocks"] == 1
    by_key = {
        n["key"]: n for n in store.digest.nodes_json()["nodes"]
    }
    assert by_key[_key(0).hex()]["refcount"] is True
    assert by_key[_key(1).hex()]["refcount"] is False


def test_host_lru_eviction_counts_and_removes():
    """A host-tier LRU victim bumps host_evictions_total and its
    (unreachable) node leaves the digest."""
    store = RadixPrefixStore(host_blocks=1)
    store.publish([_key(1)], [0])
    store.publish([_key(2)], [1])
    store.retain([0])
    store.retain([1])
    store.pop_evictable(lambda b: {"pos": None})  # key1 -> host
    store.pop_evictable(lambda b: {"pos": None})  # key2 evicts key1
    s = store.digest.summary()
    assert s["host_evictions_total"] == 1
    assert s["host_blocks"] == 1 and s["hbm_blocks"] == 0
    assert s["nodes"] == 1
    tiers = {
        n["key"]: n["tier"] for n in store.digest.nodes_json()["nodes"]
    }
    assert tiers == {_key(2).hex(): "host"}


def test_nodes_json_bounded_at_max_occupancy():
    """The /debug/kv walk stays under its size bound at max radix
    occupancy: node cap enforced (shallowest-first, deterministic),
    truncation reported, depth cap honored."""
    store = RadixPrefixStore()
    n = 512  # a full pool's worth of keyed blocks
    store.publish([_key(i) for i in range(n)], list(range(n)))
    walk = store.digest.nodes_json(max_nodes=64)
    assert len(walk["nodes"]) == 64
    assert walk["truncated"] == n - 64
    assert [e["depth"] for e in walk["nodes"]] == list(range(1, 65))
    # Bounded payload: the serialized cap stays small even though the
    # tree holds 8x more nodes.
    assert len(json.dumps(walk)) < 64 * 120 + 512
    # Depth cap composes with the node cap.
    shallow = store.digest.nodes_json(depth=8, max_nodes=64)
    assert len(shallow["nodes"]) == 8
    assert shallow["truncated"] == 0
    assert all(e["depth"] <= 8 for e in shallow["nodes"])


def test_null_store_digest_stays_empty():
    store = NullPrefixStore()
    store.publish([_key(1)], [0])
    store.retain([0])
    assert store.digest.summary()["version"] == 0
    assert store.digest.summary()["nodes"] == 0
    assert store.digest.nodes_json()["nodes"] == []


def test_digest_hash_xor_cancellation_is_tier_aware():
    """The set-hash distinguishes residency tier: the same key on HBM
    vs host hashes differently (a fleet diff must not call a demoted
    replica 'identical' to a resident one)."""
    d1, d2 = KvDigest(), KvDigest()
    d1.on_publish(b"k", 1)
    d2.on_publish(b"k", 1)
    assert d1.summary()["hash"] == d2.summary()["hash"]
    d2.on_demote(b"k")
    assert d1.summary()["hash"] != d2.summary()["hash"]
    d2.on_restore(b"k")
    assert d1.summary()["hash"] == d2.summary()["hash"]
