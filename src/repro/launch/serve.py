"""Serving launcher: host-backend AiSAQ retrieval service with batching,
multi-corpus switching and latency reporting.

    PYTHONPATH=src python -m repro.launch.serve --index-dir <dir> \
        [--corpora a=path1 b=path2] [--queries 200] [--L 48] [--hedge 2]

If no index is given, builds a demo corpus first (same as quickstart).
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--corpora", nargs="*", default=None,
                    help="name=path pairs of index dirs")
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--L", type=int, default=48)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--hedge", type=int, default=1)
    args = ap.parse_args(argv)

    from repro.core.index_switch import IndexManager
    from repro.serving.engine import ServingEngine
    from repro.data.vectors import make_clustered, make_queries

    if args.corpora:
        paths = dict(c.split("=", 1) for c in args.corpora)
        import json
        any_meta = json.load(open(os.path.join(
            next(iter(paths.values())), "meta.json")))
        dim = any_meta["dim"]
        base = None
    else:
        print("no corpora given — building a demo index ...")
        from repro.core.index_config import IndexConfig
        from repro.core.build import build_index
        dim = 64
        base = make_clustered(4000, dim, seed=0)
        cfg = IndexConfig(name="demo", n_vectors=4000, dim=dim, R=24,
                          pq_m=16, build_L=48)
        root = tempfile.mkdtemp(prefix="serve_")
        build_index(os.path.join(root, "demo"), base, cfg, mode="aisaq")
        paths = {"demo": os.path.join(root, "demo")}

    mgr = IndexManager(paths)

    def search(queries, k):
        ids, _ = mgr.search_batch(queries, k, L=max(args.L, k))
        return ids

    eng = ServingEngine({c: search for c in paths}, switch_fn=mgr.switch,
                        max_batch=args.max_batch, hedge=args.hedge,
                        replicas=[search] * max(1, args.hedge))
    if base is not None:
        queries = make_queries(args.queries, base, seed=2)
    else:
        rng = np.random.default_rng(0)
        queries = rng.normal(size=(args.queries, dim)).astype(np.float32)
    corpora = list(paths)
    t0 = time.time()
    reqs = [eng.submit(queries[i], corpus=corpora[i % len(corpora)],
                       k=args.k) for i in range(args.queries)]
    for r in reqs:
        r.event.wait(30)
    wall = time.time() - t0
    print(f"served {args.queries} queries in {wall:.2f}s "
          f"({args.queries / wall:.0f} qps)")
    print("latency:", eng.latency_percentiles())
    if eng.switch_times:
        print(f"index switches: {len(eng.switch_times)}, median "
              f"{np.median(eng.switch_times)*1e3:.2f} ms")
    print(f"resident: {mgr.resident_bytes()/1e3:.1f} KB")
    eng.stop()
    mgr.close()


if __name__ == "__main__":
    main()
