"""Edge-cache accounting: LRU order under a byte budget, pinning,
exact hit/miss/byte counters, EMA-driven prefetch, KV blocks and
experts under one byte budget, and cache-on vs cache-off bit-identity
of the B-MoE system."""
import jax
import numpy as np

from repro.core.attacks import AttackConfig
from repro.core.bmoe import BMoEConfig, BMoESystem
from repro.core.ledger import digest_tree
from repro.storage import ExpertCache, ExpertStore, GateEMA, StorageNetwork
from repro.trust.protocol import TrustConfig


def _populated_store(num_objects=4, leaf=256, chunk_bytes=256, seed=0):
    """Objects "o0".."oN" of identical known size (leaf float32 values ->
    4*leaf payload bytes each)."""
    net = StorageNetwork(num_nodes=4, replication=2, seed=seed)
    store = ExpertStore(net, chunk_bytes=chunk_bytes)
    rng = np.random.default_rng(seed)
    trees = {}
    for i in range(num_objects):
        t = {"w": rng.normal(size=leaf).astype(np.float32)}
        trees[f"o{i}"] = t
        store.put_version(f"o{i}", t, 0)
    return net, store, trees


def test_lru_eviction_order_under_byte_budget():
    net, store, trees = _populated_store(num_objects=4, leaf=256)
    nbytes = 4 * 256
    cache = ExpertCache(store, budget_bytes=2 * nbytes)   # room for two
    like = trees["o0"]
    cache.get("o0", 0, like)
    cache.get("o1", 0, like)
    cache.get("o2", 0, like)          # evicts o0 (least recent)
    assert "o0" not in cache and "o1" in cache and "o2" in cache
    cache.get("o1", 0, like)          # refresh o1's recency
    cache.get("o3", 0, like)          # now o2 is LRU -> evicted
    assert "o2" not in cache and "o1" in cache and "o3" in cache
    assert cache.stats["evictions"] == 2
    assert cache.stats["evicted_bytes"] == 2 * nbytes


def test_pinned_entries_never_evicted():
    net, store, trees = _populated_store(num_objects=4, leaf=256)
    nbytes = 4 * 256
    cache = ExpertCache(store, budget_bytes=2 * nbytes)
    like = trees["o0"]
    cache.get("o0", 0, like)
    cache.pin(["o0"])                  # activated: must survive
    cache.get("o1", 0, like)
    cache.get("o2", 0, like)           # would evict o0 -> evicts o1
    cache.get("o3", 0, like)           # evicts o2
    assert "o0" in cache
    assert cache.stats["evictions"] == 2
    cache.unpin(["o0"])
    cache.get("o1", 0, like)           # now o0 is evictable again
    assert "o0" not in cache


def test_counters_exact_under_seeded_access_trace():
    net, store, trees = _populated_store(num_objects=5, leaf=128)
    nbytes = 4 * 128
    cache = ExpertCache(store, budget_bytes=3 * nbytes)
    like = trees["o0"]
    rng = np.random.default_rng(42)
    trace = [int(i) for i in rng.integers(0, 5, 60)]
    # shadow simulation of the exact LRU discipline
    resident, hits, misses, evicts = [], 0, 0, 0
    for i in trace:
        oid = f"o{i}"
        cache.get(oid, 0, like)
        if oid in resident:
            hits += 1
            resident.remove(oid)
            resident.append(oid)
        else:
            misses += 1
            resident.append(oid)
            if len(resident) > 3:
                resident.pop(0)
                evicts += 1
    assert cache.stats["hits"] == hits
    assert cache.stats["misses"] == misses
    assert cache.stats["evictions"] == evicts
    assert cache.stats["fetched_bytes"] == misses * nbytes
    assert cache.stats["evicted_bytes"] == evicts * nbytes
    assert cache.resident_bytes == len(resident) * nbytes


def test_prefetch_warms_top_ema_within_budget():
    net, store, trees = _populated_store(num_objects=6, leaf=256)
    nbytes = 4 * 256
    like = trees["o0"]
    ema = GateEMA(6, decay=0.5)
    ema.update([0, 10, 1, 7, 0, 2])
    ema.update([0, 8, 2, 9, 0, 1])
    ranking = ema.ranking()
    assert ranking[:2] in ([1, 3], [3, 1])
    cache = ExpertCache(store, budget_bytes=3 * nbytes)
    fetched = cache.prefetch([f"o{e}" for e in ranking], 0, lambda _: like)
    # exactly the top three hottest fit the budget, in ranking order
    assert fetched == [f"o{e}" for e in ranking[:3]]
    assert cache.stats["prefetches"] == 3
    assert cache.resident_bytes == 3 * nbytes
    # prefetch never evicts: a second pass adds nothing (budget full)
    assert cache.prefetch([f"o{e}" for e in ranking], 0,
                          lambda _: like) == []
    assert cache.stats["evictions"] == 0


def test_prefetched_entries_hit_on_access():
    net, store, trees = _populated_store(num_objects=3, leaf=64)
    cache = ExpertCache(store, budget_bytes=None)
    like = trees["o0"]
    cache.prefetch(["o1"], 0, lambda _: like)
    cache.get("o1", 0, like)
    assert cache.stats["hits"] == 1 and cache.stats["misses"] == 0


def test_stale_version_is_a_miss_and_refetches():
    net, store, trees = _populated_store(num_objects=1, leaf=64)
    cache = ExpertCache(store, budget_bytes=None)
    like = trees["o0"]
    cache.get("o0", 0, like)
    t1 = {"w": trees["o0"]["w"] + 1.0}
    store.put_version("o0", t1, 1)
    back = cache.get("o0", 1, like)           # stale -> miss -> refetch
    np.testing.assert_array_equal(back["w"], t1["w"])
    assert cache.stats["misses"] == 2 and cache.stats["hits"] == 0


# ----------------------------------------------------- system identity
def _data(seed=0, n=400):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, 784)).astype(np.float32),
            rng.integers(0, 10, n))


def _run_system(edge_cache, attack=AttackConfig(), rounds=5, seed=0,
                **overrides):
    cfg = BMoEConfig(num_experts=6, num_edges=6, top_k=2,
                     framework="optimistic", pow_difficulty=2, seed=seed,
                     attack=attack, edge_cache=edge_cache,
                     trust=TrustConfig(audit_rate=0.3, challenge_window=2),
                     **overrides)
    s = BMoESystem(cfg)
    x, y = _data()
    rng = np.random.default_rng(1)
    for _ in range(rounds):
        idx = rng.integers(0, len(x), 48)
        s.train_round(x[idx], y[idx])
    s.flush_trust()
    return s


def test_cache_on_off_bit_identical_training_and_inference():
    """The whole point of the resolution path: fetching the bank through
    the chunk store + cache changes nothing — states, audit verdicts and
    inference outputs are bit-identical to the resident-bank oracle."""
    a = _run_system("on")
    b = _run_system("off")
    assert digest_tree(a.experts) == digest_tree(b.experts)
    assert digest_tree(a.gate) == digest_tree(b.gate)
    x, _ = _data(3, 64)
    la, _, _ = a.infer(x, commit=False)
    lb, _, _ = b.infer(x, commit=False)
    np.testing.assert_array_equal(la, lb)
    assert a.edge_cache is not None and b.edge_cache is None


def test_cache_on_off_bit_identical_under_attack_with_rollback():
    atk = AttackConfig(malicious_edges=(3,), attack_prob=1.0, noise_std=5.0)
    a = _run_system("on", attack=atk, rounds=7)
    b = _run_system("off", attack=atk, rounds=7)
    assert digest_tree(a.experts) == digest_tree(b.experts)
    assert [(e.round_id, e.edge) for e in a.protocol.stakes.events] == \
        [(e.round_id, e.edge) for e in b.protocol.stakes.events]
    assert len(a.ledger.rollbacks()) == len(b.ledger.rollbacks()) > 0


def test_tight_budget_thrashes_but_stays_correct():
    """A byte budget below the bank size forces evict/refetch traffic —
    and changes nothing about what is computed."""
    bank_bytes = None
    a = _run_system("on", rounds=4, seed=2)
    bank_bytes = sum(a.expert_store.object_bytes(f"expert/{e}")
                     for e in range(6))
    tight = _run_system("on", rounds=4, seed=2,
                        edge_cache_bytes=bank_bytes // 2)
    b = _run_system("off", rounds=4, seed=2)
    assert digest_tree(tight.experts) == digest_tree(b.experts)
    assert tight.edge_cache.stats["evictions"] > 0
    # the thrash shows on warm accesses: repeated inference against the
    # frozen bank refetches what the budget evicted, while the
    # unbounded cache serves everything from residency
    x, _ = _data(6, 64)
    for s in (a, tight):
        s.infer(x, commit=False)
    base_a = a.edge_cache.stats["fetched_bytes"]
    base_t = tight.edge_cache.stats["fetched_bytes"]
    for s in (a, tight):
        s.infer(x, commit=False)
    assert a.edge_cache.stats["fetched_bytes"] == base_a
    assert tight.edge_cache.stats["fetched_bytes"] > base_t


def test_unrouted_experts_receive_zero_gradient():
    """The dedup-upload premise: an expert the batch never routed to is
    bit-identical after the round, so skipping its re-upload is sound."""
    cfg = BMoEConfig(num_experts=8, num_edges=8, top_k=2,
                     framework="traditional", pow_difficulty=2, seed=0)
    s = BMoESystem(cfg)
    x, y = _data(4, 8)
    before = jax.tree_util.tree_map(np.asarray, s.experts)
    m = s.train_round(x[:1], y[:1])           # one sample: k experts routed
    routed = set(np.nonzero(m["activation"])[0])
    assert len(routed) == 2
    after = jax.tree_util.tree_map(np.asarray, s.experts)
    for e in range(8):
        same = all(np.array_equal(np.asarray(a[e]), np.asarray(b[e]))
                   for a, b in zip(jax.tree_util.tree_leaves(before),
                                   jax.tree_util.tree_leaves(after)))
        assert same == (e not in routed), (e, routed)


def test_warm_cache_inference_fetches_nothing():
    s = _run_system("on", rounds=3)
    x, _ = _data(5, 64)
    s.infer(x, commit=False)                  # first resolve after flush
    fetched = s.edge_cache.stats["fetched_bytes"]
    hits = s.edge_cache.stats["hits"]
    s.infer(x, commit=False)
    s.infer(x, commit=False)
    assert s.edge_cache.stats["fetched_bytes"] == fetched   # all warm
    assert s.edge_cache.stats["hits"] > hits


def test_kv_blocks_and_experts_compete_under_one_budget():
    """KV blocks resolved through the SAME cache as expert weights:
    under a tight budget the cold KV entries are evicted first while the
    pinned (activated) expert survives — with exact counters."""
    import jax.numpy as jnp

    from repro.models.builder import materialize
    from repro.models.transformer import cache_decl, slice_kv_block
    from repro.storage import KVBlockStore, prefix_chain

    net, store, trees = _populated_store(num_objects=1, leaf=256)
    nbytes = 4 * 256                                  # one expert unit
    from repro.configs import get_config
    cfg = get_config("smollm-360m", smoke=True)
    caches = jax.tree_util.tree_map(
        jnp.asarray, materialize(cache_decl(cfg, 1, 40),
                                 jax.random.PRNGKey(0)))
    blocks = [slice_kv_block(caches, 0, b * 8, (b + 1) * 8)
              for b in range(3)]
    kv_bytes = sum(np.asarray(a).nbytes
                   for a in jax.tree_util.tree_leaves(blocks[0]))

    cache = ExpertCache(store, budget_bytes=nbytes + 2 * kv_bytes)
    kv = KVBlockStore(store, cache)
    chain = prefix_chain(np.arange(24), 8)
    for cid, block in zip(chain, blocks):
        kv.seal(cid, block, 8)
    like = slice_kv_block(caches, 0, 0, 1)
    expert = cache.get("o0", 0, trees["o0"])          # the activated expert
    np.testing.assert_array_equal(expert["w"], trees["o0"]["w"])
    cache.pin(["o0"])
    try:
        for cid in chain:                             # 3 blocks, room for 2
            kv.fetch(cid, like)
    finally:
        cache.unpin(["o0"])
    assert "o0" in cache                              # pinned: survived
    oid = KVBlockStore.object_id
    assert oid(chain[0]) not in cache                 # cold KV went first
    assert oid(chain[1]) in cache and oid(chain[2]) in cache
    assert cache.stats["evictions"] == 1
    assert cache.stats["evicted_bytes"] == kv_bytes
    assert cache.resident_bytes == nbytes + 2 * kv_bytes


def test_gate_ema_ranking_deterministic_ties_by_id():
    ema = GateEMA(4, decay=0.9)
    ema.update([1, 1, 1, 1])
    assert ema.ranking() == [0, 1, 2, 3]
    ema.update([0, 0, 8, 0])
    assert ema.ranking()[0] == 2
