"""A byte-budgeted edge: fetch only the activated experts.

The paper's three-layer design keeps the expert bank in the *storage*
layer — "the edge layer employs the activated experts downloaded from
the storage layer" — and the chain records their CIDs.  This example
runs B-MoE rounds on an edge whose cache is smaller than the expert
bank: the executor resolves each round's bank through the cache
(activated experts pinned, LRU eviction under the byte budget), uploads
only the *changed* experts as new chunk-manifest versions, and the
storage report shows the transfer ledger — dedup savings, hit/miss
traffic, and modeled seconds on the deterministic cost model.  The
serving engine keeps its weights resident and resolves no experts
through a cache.

Run: PYTHONPATH=src python examples/edge_cache_serving.py
"""
import json

import numpy as np

from repro.core.bmoe import BMoEConfig, BMoESystem
from repro.trust.protocol import TrustConfig

rng = np.random.default_rng(0)
x = rng.normal(size=(1024, 784)).astype(np.float32)
y = rng.integers(0, 10, 1024)

# ---- training on a memory-constrained edge ----------------------------
full_bank = BMoESystem(BMoEConfig(num_experts=8, num_edges=8, top_k=2,
                                  pow_difficulty=2, framework="optimistic",
                                  seed=0))
bank_bytes = sum(full_bank.expert_store.object_bytes(f"expert/{e}")
                 for e in range(8))

cfg = BMoEConfig(num_experts=8, num_edges=8, top_k=2, pow_difficulty=2,
                 framework="optimistic", seed=0,
                 edge_cache_bytes=bank_bytes // 2,   # half the bank fits
                 prefetch_topk=3,                    # EMA-warm 3 hottest
                 trust=TrustConfig(audit_rate=0.1, challenge_window=2))
system = BMoESystem(cfg)
for r in range(8):
    idx = rng.integers(0, len(x), 128)
    m = system.train_round(x[idx], y[idx])
system.flush_trust()

rep = system.storage_report()
print("edge budget:", cfg.edge_cache_bytes, "of", bank_bytes, "bank bytes")
print("cache:", json.dumps(rep["cache"]))
print("dedup: uploaded", rep["store"]["uploaded_bytes"], "bytes,",
      rep["store"]["chunks_deduped"], "chunks deduped")
print("modeled transfer:",
      round(rep["network"]["modeled_get_s"] + rep["network"]["modeled_put_s"],
            3), "s on the 1 Gbps cost model")
print("bank root on-chain:", system.ledger.head.payload["bank_root"])

# repeated inference against the frozen bank: a budget below the bank
# size pays exactly the evicted half back per resolve — the thrash a
# bigger budget (or prefetch of the right experts) buys away
system.infer(x[:256], commit=False)
before = system.edge_cache.stats["fetched_bytes"]
system.infer(x[:256], commit=False)
print("half-bank budget: warm re-inference refetched",
      system.edge_cache.stats["fetched_bytes"] - before,
      "bytes (the evicted half)")
