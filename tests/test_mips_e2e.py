"""End-to-end MIPS (KILT-E5 regime, paper Table 1 column 3) + ablations."""
import numpy as np
import pytest

from repro.core.index_config import IndexConfig
from repro.core import pq
from repro.core.build import build_index
from repro.core.index_io import HostIndex, recall_at
from repro.data.vectors import make_clustered, make_queries


@pytest.fixture(scope="module")
def mips_index(tmp_path_factory):
    base = make_clustered(1200, 64, seed=3)
    # KILT-E5 regime (paper Table 1): e5 embeddings are L2-normalized, so
    # MIPS == cosine on the unit sphere — normalize like the real corpus
    base = base / np.linalg.norm(base, axis=1, keepdims=True)
    q = make_queries(10, base, seed=4)
    gt = pq.groundtruth(q, base, 10, metric="mips")
    cfg = IndexConfig(name="mips", n_vectors=1200, dim=64, metric="mips",
                      R=20, pq_m=16, build_L=40)
    p = str(tmp_path_factory.mktemp("mips") / "idx")
    build_index(p, base, cfg, mode="aisaq", seed=0)
    return p, base, q, np.asarray(gt)


def test_mips_host_search(mips_index):
    p, base, q, gt = mips_index
    idx = HostIndex.load(p)
    # MIPS is non-metric: graph navigability is weaker than L2 (the paper
    # compensates with larger L on KILT-E5) — use L=96 and softer floors
    ids, stats = idx.search_batch(q, 10, L=96)
    assert recall_at(ids, gt, 1) >= 0.7
    assert recall_at(ids, gt, 10) >= 0.6
    idx.close()


def test_mips_device_matches_host(mips_index):
    import jax.numpy as jnp
    from repro.core.device_index import load_device_index, beam_search_device
    p, base, q, gt = mips_index
    didx, lay, metric = load_device_index(p)
    assert metric == "mips"
    ids, d, hops = beam_search_device(didx, jnp.asarray(q), k=10, L=96,
                                      layout=lay, metric="mips")
    assert recall_at(np.asarray(ids), gt, 1) >= 0.7


def test_beamwidth_ablation(mips_index):
    """Paper fixes w=4; hops should drop monotonically-ish with w while
    recall holds (beam search ablation)."""
    p, base, q, gt = mips_index
    idx = HostIndex.load(p)
    hops, recalls = [], []
    for w in (1, 2, 4, 8):
        ids, stats = idx.search_batch(q, 10, L=40, w=w)
        hops.append(np.mean([s.hops for s in stats]))
        recalls.append(recall_at(ids, gt, 10))
    assert hops[-1] < hops[0]
    assert min(recalls) >= max(recalls) - 0.1
    idx.close()
