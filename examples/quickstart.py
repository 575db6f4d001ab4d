"""Quickstart: build an AiSAQ index, search it, compare with DiskANN mode.

    PYTHONPATH=src python examples/quickstart.py

Demonstrates the paper's three headline claims at laptop scale:
  1. identical recall to DiskANN (same graph topology),
  2. ~N-independent RAM residency (only centroids + entry-point codes),
  3. near-zero index load time.
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core.index_config import IndexConfig
from repro.core import pq
from repro.core.build import build_index
from repro.core.index_io import HostIndex, recall_at
from repro.data.vectors import make_clustered, make_queries


def main():
    n, d = 5000, 64
    print(f"== corpus: {n} x {d} clustered vectors ==")
    base = make_clustered(n, d, seed=0)
    queries = make_queries(32, base)
    gt = pq.groundtruth(queries, base, 10)

    cfg = IndexConfig(name="quickstart", n_vectors=n, dim=d, R=24, pq_m=16,
                      build_L=48)
    root = tempfile.mkdtemp(prefix="aisaq_quickstart_")
    results = {}
    for mode in ("aisaq", "diskann"):
        path = os.path.join(root, mode)
        t0 = time.time()
        # nav=True packs the in-RAM navigation tier (docs/navigation.md)
        # alongside the AiSAQ index; the DiskANN twin stays plain
        meta = build_index(path, base, cfg, mode=mode, seed=0,
                           nav=(mode == "aisaq"))
        print(f"\n[{mode}] built in {time.time()-t0:.1f}s  "
              f"chunk={meta['chunk_bytes']}B  io/hop={meta['io_bytes']}B")
        idx = HostIndex.load(path)
        print(f"[{mode}] load time     : {idx.load_time_s*1e3:.2f} ms")
        print(f"[{mode}] resident bytes: {idx.resident_bytes()/1e3:.1f} KB")
        # entry="medoid" pins the classic fixed-seed traversal so the
        # AiSAQ/DiskANN comparison stays apples-to-apples (the nav demo
        # below opts in explicitly)
        ids, stats = idx.search_batch(queries, 10, L=48, entry="medoid")
        results[mode] = ids
        lat = np.mean([s.latency_s for s in stats]) * 1e3
        print(f"[{mode}] recall@1={recall_at(ids, gt, 1):.3f} "
              f"recall@10={recall_at(ids, gt, 10):.3f} "
              f"mean latency={lat:.2f} ms "
              f"ios/query={np.mean([s.ios for s in stats]):.0f} "
              f"hops/query={np.median([s.hops for s in stats]):.0f} "
              f"(converged by hop "
              f"{np.median([s.convergence_hop for s in stats]):.0f})")
        # the pipelined traversal engine (core.traversal): prefetch>0
        # turns on the two-hop in-flight path — identical ids, reads off
        # the critical path; overlap is visible in the lead query's stats
        idx.cache.clear()
        ids_p, stats_p = idx.search_batch(queries, 10, L=48, prefetch=4,
                                          entry="medoid")
        assert np.array_equal(ids, ids_p)
        print(f"[{mode}] pipelined: blocked wait "
              f"{stats_p[0].blocked_wait_s*1e3:.2f} ms vs compute "
              f"{stats_p[0].compute_s*1e3:.2f} ms (whole batch, "
              f"results identical)")
        if idx.nav is not None:
            # the navigation tier: an in-RAM beam over ~2% pivot nodes
            # replaces the fixed medoid seed with per-query entry
            # vertices — fewer on-disk hops, zero extra storage I/O
            ids_n, st_n = idx.search_batch(queries, 10, L=48, entry="nav")
            print(f"[{mode}] nav entry: hops/query="
                  f"{np.median([s.hops for s in st_n]):.0f} "
                  f"(converged by hop "
                  f"{np.median([s.convergence_hop for s in st_n]):.0f}) "
                  f"recall@10={recall_at(ids_n, gt, 10):.3f}  "
                  f"[nav tier: {idx.nav.resident_nbytes()/1e3:.1f} KB, "
                  f"{idx.nav.params['pivots']} pivots]")
        idx.close()

    same = np.array_equal(results["aisaq"], results["diskann"])
    print(f"\nAiSAQ results identical to DiskANN (same topology): {same}")


if __name__ == "__main__":
    main()
