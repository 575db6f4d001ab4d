"""The benchmark's index generator and plain reference, on the CPU.

At a small size: the index is the same for the same seed, every node is
reachable from the entry point, and the stand-in graph's long edges are
what makes the search work (recall against the pure k-NN graph). The
program's own search (jnp backend) gives the reference's answers, and the
bfloat16 control does not.
"""
import json
import sys
from collections import deque
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import reference  # noqa: E402
from benchmarks.chip.data import index_gen  # noqa: E402

N, NQ, SEED = 20000, 256, 2 ** 31 + 17


def small(name="aisaq-sift1m", n=N, **gen):
    cfg = json.loads((ROOT / "benchmarks" / "chip" / "configs" /
                      f"{name}.json").read_text())
    cfg["n_vectors"] = n
    g = dict(cfg["assumed"]["generator"], pq_train_rows=8192, **gen)
    cfg["assumed"] = dict(cfg["assumed"], generator=g)
    return cfg


@pytest.fixture(scope="module")
def sift():
    cfg = small()
    return cfg, index_gen.make_index(SEED, cfg, NQ)


def search(cfg, ix, graph=None, dtype=np.float64):
    s = cfg["assumed"]["search"]
    return reference.beam_search(
        ix.base, ix.graph if graph is None else graph, ix.codes,
        ix.centroids, ix.queries, k=s["k"], L=s["L"], w=s["w"],
        max_hops=s["max_hops"], metric=cfg["metric"], dtype=dtype)


def test_same_seed_same_index():
    cfg = small(n=3000, n_clusters=8)
    a = index_gen.make_index(7, cfg, 16)
    b = index_gen.make_index(7, cfg, 16)
    c = index_gen.make_index(8, cfg, 16)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.base, c.base)


def test_shapes_and_every_slot_filled(sift):
    cfg, ix = sift
    assert ix.base.shape == (N, cfg["dim"]) and ix.base.dtype == np.float32
    assert ix.graph.shape == (N, cfg["R"]) and ix.graph.dtype == np.int32
    assert ix.graph.min() >= 0 and ix.graph.max() < N
    assert ix.codes.shape == (N, cfg["pq_m"]) and ix.codes.dtype == np.uint8
    assert ix.centroids.shape == (cfg["pq_m"], cfg["pq_ks"], 1)
    assert len(np.unique(ix.queries, axis=0)) == NQ


def test_every_node_reachable_from_entry(sift):
    _, ix = sift
    ep = reference.entry_point(ix.base)
    seen = np.zeros(N, bool)
    seen[ep] = True
    todo = deque([ep])
    while todo:
        nbr = ix.graph[todo.popleft()]
        new = nbr[~seen[nbr]]
        seen[new] = True
        todo.extend(new.tolist())
    assert seen.all(), f"{(~seen).sum()} nodes unreachable"


def test_long_edges_beat_pure_knn(sift):
    cfg, ix = sift
    gt = index_gen.ground_truth(ix.queries, ix.base, 10, cfg["metric"])
    pure = index_gen.make_index(SEED, small(long_edges=0), NQ)
    np.testing.assert_array_equal(pure.base, ix.base)
    r_stand_in = index_gen.recall(search(cfg, ix)[0], gt, 10)
    r_pure = index_gen.recall(search(cfg, ix, pure.graph)[0], gt, 10)
    assert r_stand_in > 0.9 and r_stand_in > r_pure + 0.2, (r_stand_in,
                                                            r_pure)


def test_program_matches_reference_and_control_does_not(sift):
    """The comparison that decides ``correct``, at a size a test can hold:
    the program's search gives no wrong id, the bfloat16 control gives
    more than the configuration's limit."""
    import jax.numpy as jnp
    from repro.core.device_index import beam_search_device, from_arrays
    cfg, ix = sift
    s = cfg["assumed"]["search"]
    idx, layout = from_arrays(ix.base, ix.graph, ix.centroids, ix.codes)
    got, _, _ = beam_search_device(
        idx, jnp.asarray(ix.queries), k=s["k"], L=s["L"], w=s["w"],
        max_hops=s["max_hops"], layout=layout, metric=cfg["metric"],
        backend="ref")
    ref, _, _ = search(cfg, ix)
    ctl, _, _ = search(cfg, ix, dtype=reference.BF16)
    wrong = reference.wrong_ids(ix.base, ix.queries, np.asarray(got), ref,
                                cfg["metric"], s["k"])
    ctl_wrong = reference.wrong_ids(ix.base, ix.queries, ctl, ref,
                                    cfg["metric"], s["k"])
    assert wrong == 0
    assert ctl_wrong > cfg["correct"]["wrong_ids_limit"], ctl_wrong


def test_mips_unit_rows():
    cfg = small("aisaq-kilt-e5-1of44", n=4000, n_clusters=8)
    ix = index_gen.make_index(SEED, cfg, 32)
    np.testing.assert_allclose(np.linalg.norm(ix.base, axis=1), 1, atol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(ix.queries, axis=1), 1,
                               atol=1e-5)
    assert ix.centroids.shape == (128, 256, 8)
    ids, d, _ = search(cfg, ix)
    gt = index_gen.ground_truth(ix.queries, ix.base, 10, "mips")
    assert index_gen.recall(ids, gt, 10) > 0.8
    assert (np.diff(d, axis=1) >= 0).all()     # -inner product, ascending


def test_wrong_ids_counts_far_missing_and_repeated():
    base = np.arange(20, dtype=np.float32)[:, None]
    q = np.zeros((1, 1), np.float32)
    ref = np.arange(10)[None]
    assert reference.wrong_ids(base, q, ref, ref, "l2", 10) == 0
    assert reference.wrong_ids(base, q, ref[:, ::-1], ref, "l2", 10) == 0
    bad = ref.copy()
    bad[0, 9] = 15                     # farther than the 10th
    bad[0, 8] = -1                     # missing
    bad[0, 7] = 0                      # repeated
    assert reference.wrong_ids(base, q, bad, ref, "l2", 10) == 3
