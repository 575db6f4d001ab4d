"""Multi-device sharded AiSAQ search — the paper's Fig. 5 multi-server
system on a local 8-device mesh (2 data x 4 model).

    PYTHONPATH=src python examples/distributed_search.py

Each of the 4 `model`-axis devices owns a dataset shard with its own
sub-index (exactly the paper's per-server layout); queries split over the
`data` axis; results merge via all-gather + global top-k.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pq
from repro.core.chunk_layout import ChunkLayout
from repro.core.index_io import recall_at
from repro.core.sharded_search import (input_sharding, sharded_search_fn,
                                       stack_shards)
from repro.core.vamana import build_sharded
from repro.data.vectors import make_clustered, make_queries
from repro.launch.mesh import make_test_mesh


def main():
    n, d, m, R = 4000, 48, 12, 20
    print(f"== {n} vectors over 4 index shards, 8 virtual devices ==")
    base = make_clustered(n, d, seed=0)
    queries = make_queries(16, base)
    gt = pq.groundtruth(queries, base, 10)
    cb = pq.train_codebooks(jax.random.PRNGKey(0), base, m=m)
    cents, codes = np.asarray(cb.centroids), np.asarray(pq.encode(cb, base))
    lay = ChunkLayout("aisaq", d, "float32", R, m)
    print("building 4 per-shard Vamana sub-indices ...")
    shards = build_sharded(base, 4, R=R, L=32, seed=0)
    mesh = make_test_mesh((2, 4), ("data", "model"))
    arrays = stack_shards(shards, cents, codes, lay, mesh)
    search = jax.jit(sharded_search_fn(
        mesh, k=10, L=48, w=4, max_hops=64, layout=lay, metric="l2",
        backend="ref"))
    _, qsh = input_sharding(mesh)
    qdev = jax.device_put(jnp.asarray(queries), qsh)

    ids, dists = search(arrays, qdev)          # compile
    t0 = time.perf_counter()
    ids, dists = jax.block_until_ready(search(arrays, qdev))
    dt = time.perf_counter() - t0
    ids = np.asarray(ids)
    print(f"recall@1 = {recall_at(ids, gt, 1):.3f}   "
          f"recall@10 = {recall_at(ids, gt, 10):.3f}")
    print(f"batch latency {dt*1e3:.1f} ms for {queries.shape[0]} queries "
          f"across 4 shards x 2 query groups")
    print("per-shard fast-tier residency is (R + n_ep) codes + centroids — "
          "independent of shard size (the paper's scale-out claim)")


if __name__ == "__main__":
    main()
