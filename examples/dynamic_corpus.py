"""Dynamic RAG corpus: live insertion + deletion + filtered retrieval.

    PYTHONPATH=src python examples/dynamic_corpus.py

The paper's conclusion says AiSAQ's near-zero load time "will enable LLMs
with RAG to employ more simple index addition or filter search algorithms" —
this example exercises exactly that: documents stream into a live index
(in-place chunk appends + reverse-edge patches), stale documents are
tombstoned, and queries filter by a freshness predicate.
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core.index_config import IndexConfig
from repro.core.build import build_index
from repro.core.dynamic import DynamicHostIndex
from repro.data.vectors import make_clustered, make_queries


def main():
    d = 48
    base = make_clustered(2000, d, seed=0)
    cfg = IndexConfig(name="dyn", n_vectors=1500, dim=d, R=16, pq_m=12,
                      build_L=32)
    root = tempfile.mkdtemp(prefix="dyn_")
    path = os.path.join(root, "corpus")
    print("== building initial 1500-doc index ==")
    build_index(path, base[:1500], cfg, mode="aisaq", seed=0)
    idx = DynamicHostIndex.load(path)

    print("== streaming 100 new documents into the live index ==")
    t0 = time.perf_counter()
    for i in range(100):
        idx.insert(base[1500 + i])
    dt = (time.perf_counter() - t0) / 100
    print(f"   mean insert latency: {dt*1e3:.1f} ms/doc "
          f"(search + <=R reverse-edge chunk patches)")

    q = base[1550].astype(np.float32)
    ids, _ = idx.search(q, 5, L=48)
    print(f"   freshly-inserted doc findable: "
          f"{1550 in set(int(i) for i in ids)} (top-5 {ids.tolist()})")

    print("== tombstoning 10 stale docs ==")
    for v in range(1500, 1510):
        idx.delete(v)
    ids, _ = idx.search(base[1505].astype(np.float32), 5, L=48)
    print(f"   deleted docs excluded: "
          f"{not (set(range(1500, 1510)) & set(int(i) for i in ids))}")

    print("== filtered retrieval (only even-id 'fresh' docs) ==")
    ids, _ = idx.search(q, 5, L=48, predicate=lambda i: i % 2 == 0)
    print(f"   filtered top-5: {ids.tolist()} (all even: "
          f"{all(int(i) % 2 == 0 for i in ids)})")

    idx.flush()
    idx.close()
    print("flushed: appended codes + tombstones persist across reloads")


if __name__ == "__main__":
    main()
