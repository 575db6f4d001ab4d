import os
import sys

# tests must see exactly ONE device (multi-device tests start their own
# subprocesses with XLA_FLAGS of their own)
os.environ.pop("XLA_FLAGS", None)

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session")
def small_corpus():
    """Clustered vectors + queries + groundtruth shared across tests."""
    from repro.core import pq
    from repro.data.vectors import make_clustered, make_queries
    base = make_clustered(1500, 48, seed=0)
    q = make_queries(12, base, seed=1)
    gt = pq.groundtruth(q, base, 10)
    return base, q, np.asarray(gt)


@pytest.fixture(scope="session")
def built_graph(small_corpus):
    from repro.core.vamana import build_vamana
    base, _, _ = small_corpus
    return build_vamana(base, R=20, L=40, seed=0)


@pytest.fixture(scope="session")
def pq_artifacts(small_corpus):
    from repro.core import pq
    base, _, _ = small_corpus
    cb = pq.train_codebooks(jax.random.PRNGKey(0), base, m=12, iters=8)
    codes = np.asarray(pq.encode(cb, base))
    return np.asarray(cb.centroids), codes


@pytest.fixture(scope="session")
def index_dirs(tmp_path_factory, small_corpus, built_graph, pq_artifacts):
    """One AiSAQ-mode and one DiskANN-mode index over the same build."""
    from repro.core.index_io import write_index
    base, _, _ = small_corpus
    cents, codes = pq_artifacts
    root = tmp_path_factory.mktemp("indices")
    paths = {}
    for mode in ("aisaq", "diskann"):
        p = str(root / mode)
        write_index(p, vectors=base, graph=built_graph, centroids=cents,
                    codes=codes, metric="l2", mode=mode)
        paths[mode] = p
    return paths
