import numpy as np
import pytest

from repro.core.index_io import HostIndex, recall_at
from repro.core.index_switch import IndexManager


def test_recall_and_identity(index_dirs, small_corpus):
    """Paper's central claims at this scale: high recall, and AiSAQ results
    == DiskANN results (same topology, same search params)."""
    base, q, gt = small_corpus
    out = {}
    for mode, path in index_dirs.items():
        idx = HostIndex.load(path)
        ids, stats = idx.search_batch(q, 10, L=40)
        out[mode] = ids
        assert recall_at(ids, gt, 1) >= 0.9, mode
        assert recall_at(ids, gt, 10) >= 0.8, mode
        assert stats[0].ios > 0 and stats[0].hops > 0
        idx.close()
    np.testing.assert_array_equal(out["aisaq"], out["diskann"])


def test_memory_residency_ordering(index_dirs, small_corpus):
    """Table 2: AiSAQ residency excludes the (N, m) code table."""
    base = small_corpus[0]
    a = HostIndex.load(index_dirs["aisaq"])
    d = HostIndex.load(index_dirs["diskann"])
    n, m = base.shape[0], a.meta["pq_m"]
    assert d.resident_bytes() - a.resident_bytes() == n * m
    # AiSAQ residency is independent of N: only centroids + ep codes
    assert a.resident_bytes() == a.centroids.nbytes + a.ep_codes.nbytes
    a.close(), d.close()


def test_load_time_ordering(index_dirs):
    a = HostIndex.load(index_dirs["aisaq"])
    d = HostIndex.load(index_dirs["diskann"])
    # Table 3: aisaq load strictly cheaper (no N-sized file read)
    assert a.load_time_s < d.load_time_s * 1.5 + 0.05
    a.close(), d.close()


def test_recall_improves_with_L(index_dirs, small_corpus):
    """Fig. 3's mechanism: larger candidate list -> higher recall."""
    base, q, gt = small_corpus
    idx = HostIndex.load(index_dirs["aisaq"])
    r = []
    for L in (10, 25, 60):
        ids, _ = idx.search_batch(q, 10, L=L)
        r.append(recall_at(ids, gt, 10))
    assert r[-1] >= r[0]
    idx.close()


def test_index_switch_shared_centroids(tmp_path, small_corpus, pq_artifacts):
    """Table 4: switching with shared PQ centroids skips the centroid load."""
    from repro.core.index_config import IndexConfig
    from repro.core.build import build_index
    base, q, _ = small_corpus
    cents, _ = pq_artifacts
    cfg = IndexConfig(name="sub", n_vectors=400, dim=base.shape[1], R=12,
                      pq_m=12, build_L=24)
    paths = {}
    for i in range(3):
        sub = base[i * 400:(i + 1) * 400]
        p = str(tmp_path / f"sub{i}")
        build_index(p, sub, cfg, mode="aisaq", shared_centroids=cents)
        paths[f"c{i}"] = p
    mgr = IndexManager(paths)
    t_first = mgr.switch("c0")
    cents_c0 = mgr.active.centroids
    t_shared = mgr.switch("c1")
    ids, stats = mgr.search(q[0], 5, L=24)
    assert ids.shape == (5,)
    assert t_shared > 0
    # shared-centroid switch must not reload pq_centroids.npy: verify the
    # newly-active index reuses c0's very array object (pool dedup)
    assert mgr.active.centroids is cents_c0
    mgr2 = IndexManager(paths)
    mgr2.switch("c0")
    c0 = mgr2.active.centroids
    mgr2.switch("c1", share_centroids=True)
    assert mgr2.active.centroids is c0          # no reload happened
    mgr.close(), mgr2.close()


def test_beamwidth_reduces_hops(index_dirs, small_corpus):
    base, q, gt = small_corpus
    idx = HostIndex.load(index_dirs["aisaq"])
    _, s1 = idx.search(q[0], 5, L=40, w=1)
    _, s4 = idx.search(q[0], 5, L=40, w=4)
    assert s4.hops <= s1.hops
    idx.close()
