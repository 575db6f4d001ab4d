"""RAG-style multi-corpus retrieval (paper §2.2 / Table 4) served by the
multi-tenant `RetrievalService`: a warm-index pool keeps every corpus open
under one DRAM budget (shared PQ centroids charged once), per-corpus queues
serve tenants concurrently, and the exact rerank tier rescores candidates
with the full-precision vectors already sitting in the traversal chunks.

    PYTHONPATH=src python examples/rag_retrieval.py

A simulated LLM chain issues retrievals against three different corpora
(news / docs / code) that share one embedding space, so their AiSAQ indices
share PQ centroids — co-residency costs one centroid table + ~KBs per
corpus.
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.core.index_config import IndexConfig
from repro.core import pq
from repro.core.build import build_index
from repro.data.vectors import make_clustered, make_queries
from repro.serving import RetrievalService, WarmIndexPool


def main():
    d, n_per = 64, 3000
    print("== building 3 corpora sharing one vector space ==")
    everything = make_clustered(3 * n_per, d, seed=0)
    cb = pq.train_codebooks(jax.random.PRNGKey(0), everything, m=16)
    cents = np.asarray(cb.centroids)
    root = tempfile.mkdtemp(prefix="rag_")
    cfg = IndexConfig(name="rag", n_vectors=n_per, dim=d, R=20, pq_m=16,
                      build_L=32)
    corpora = {}
    for i, name in enumerate(("news", "docs", "code")):
        p = os.path.join(root, name)
        build_index(p, everything[i * n_per:(i + 1) * n_per], cfg,
                    mode="aisaq", shared_centroids=cents)
        corpora[name] = p
        print(f"  built {name}")

    # budget generous enough for all three corpora: every index stays warm
    pool = WarmIndexPool(corpora, budget_bytes=64 << 20,
                         cache_bytes=2 << 20)
    svc = RetrievalService(pool, num_workers=2, max_wait_ms=1.0, L=32,
                           rerank=20)        # exact rerank tier on

    print("\n== simulated RAG chain: 12 retrievals across corpora ==")
    chain = ["news", "docs", "docs", "code", "news", "code"] * 2
    queries = make_queries(len(chain), everything, seed=3)
    for step, corpus in enumerate(chain):
        r = svc.submit_wait(queries[step], corpus=corpus, k=5)
        print(f"  step {step:2d} [{corpus:4s}] top-5 ids {r.result.tolist()} "
              f"latency {r.latency_s*1e3:.2f} ms")

    st = svc.stats()
    ps = st["pool"]
    print(f"\nper-corpus serving stats:")
    for name, c in st["corpora"].items():
        print(f"  {name:4s} completed={c['completed']} "
              f"switches={c['switches']} p50={c.get('p50_ms', 0):.2f}ms")
    print(f"pool: open={ps['open']}/{ps['registered']} warm, "
          f"hits={ps['hits']} misses={ps['misses']} "
          f"evictions={ps['evictions']}")
    print(f"shared-centroid dedup: {ps['centroid_shares']} corpora reuse "
          f"one {ps['centroid_bytes']/1e3:.1f} KB table")
    print(f"DRAM for ALL {ps['open']} warm corpora: "
          f"{ps['used_bytes']/1e6:.2f} MB (vs one-at-a-time switching — "
          "that's the point)")
    svc.stop()
    pool.close()


if __name__ == "__main__":
    main()
