import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device_index import beam_search_device, from_arrays
from repro.core.index_io import HostIndex, recall_at


def _device_search(small_corpus, built_graph, pq_artifacts, mode):
    base, q, gt = small_corpus
    cents, codes = pq_artifacts
    idx, lay = from_arrays(base, built_graph, cents, codes, mode=mode)
    ids, d, hops = beam_search_device(idx, jnp.asarray(q), k=10, L=40,
                                      layout=lay, metric="l2")
    return idx, np.asarray(ids), int(hops)


def test_device_recall_both_modes(small_corpus, built_graph, pq_artifacts):
    base, q, gt = small_corpus
    for mode in ("aisaq", "diskann"):
        _, ids, hops = _device_search(small_corpus, built_graph,
                                      pq_artifacts, mode)
        assert recall_at(ids, gt, 1) >= 0.9, mode
        assert recall_at(ids, gt, 10) >= 0.8, mode
        assert 0 < hops


def test_device_matches_host_results(small_corpus, built_graph, pq_artifacts,
                                     index_dirs):
    """Device while-loop search finds (nearly) the same neighbors as the
    faithful host implementation of Algorithm 1."""
    base, q, gt = small_corpus
    host = HostIndex.load(index_dirs["aisaq"])
    h_ids, _ = host.search_batch(q, 10, L=40)
    host.close()
    _, d_ids, _ = _device_search(small_corpus, built_graph, pq_artifacts,
                                 "aisaq")
    overlap = np.mean([len(set(a) & set(b)) / 10.0
                       for a, b in zip(h_ids, d_ids)])
    assert overlap >= 0.9


def test_fast_tier_residency_invariant(small_corpus, built_graph,
                                       pq_artifacts):
    """The paper's invariant, tier-shifted: AiSAQ fast-tier bytes are
    independent of N; DiskANN's grow with N (the (N, m) code table)."""
    base, q, _ = small_corpus
    cents, codes = pq_artifacts
    idx_a, _ = from_arrays(base, built_graph, cents, codes, mode="aisaq")
    idx_d, _ = from_arrays(base, built_graph, cents, codes, mode="diskann")
    n, m = codes.shape
    fa = idx_a.fast_tier_bytes(1, 40)
    fd = idx_d.fast_tier_bytes(1, 40)
    assert fd - fa == n * m * codes.dtype.itemsize
    # halving N halves only the DiskANN side
    half = n // 2
    g = np.clip(built_graph[:half], -1, half - 1)
    idx_a2, _ = from_arrays(base[:half], g, cents, codes[:half], mode="aisaq")
    assert idx_a2.fast_tier_bytes(1, 40) == fa


def _counts(reg):
    return {name: fam["series"][0]["value"]
            for name, fam in reg.snapshot().items()}


def test_served_fn_counts_trips_slots_and_expansions(small_corpus,
                                                     built_graph,
                                                     pq_artifacts):
    """One call's counters: trips are the loop's own hop count, slots are
    trips x nq x w, and only some slots expand a node."""
    from repro.obs.metrics import MetricsRegistry
    from repro.serving.engine import make_device_search_fn
    base, q, _ = small_corpus
    cents, codes = pq_artifacts
    reg = MetricsRegistry()
    idx, lay = from_arrays(base, built_graph, cents, codes, registry=reg)
    loaded = _counts(reg)
    assert set(loaded) == {"index_pack_seconds", "index_put_seconds",
                           "index_entry_seconds"}
    assert all(v > 0 for v in loaded.values())
    nq, w = 8, 4
    fn = make_device_search_fn(idx, lay, metric="l2", L=40, w=w,
                               backend="ref", registry=reg)
    ids = fn(q[:nq], 10)
    _, _, hops = beam_search_device(idx, jnp.asarray(q[:nq]), k=10, L=40,
                                    w=w, layout=lay, metric="l2",
                                    backend="ref")
    assert isinstance(ids, np.ndarray) and ids.shape == (nq, 10)
    c = _counts(reg)
    assert c["search_calls_total"] == 1
    assert c["search_loop_trips_total"] == int(hops) > 0
    assert c["search_slots_total"] == int(hops) * nq * w
    assert c["search_slots_total"] >= c["search_expansions_total"] > 0
    fn(q[:nq], 10)
    assert _counts(reg)["search_slots_total"] == 2 * int(hops) * nq * w


def test_served_fn_with_rerank_counts_its_beam_search(small_corpus,
                                                     built_graph,
                                                     pq_artifacts):
    """With the exact rerank tier the answer is reranked on the device and
    fetched with the beam search's counts, still as one buffer."""
    from repro.obs.metrics import MetricsRegistry
    from repro.serving.engine import make_device_search_fn
    base, q, _ = small_corpus
    cents, codes = pq_artifacts
    idx, lay = from_arrays(base, built_graph, cents, codes)
    reg = MetricsRegistry()
    fn = make_device_search_fn(idx, lay, metric="l2", L=40, backend="ref",
                               rerank=32, registry=reg)
    ids = fn(q[:4], 10)
    ref, _, hops = beam_search_device(idx, jnp.asarray(q[:4]), k=32, L=40,
                                      layout=lay, metric="l2",
                                      backend="ref")
    assert ids.shape == (4, 10)
    assert all(set(a) <= set(b) for a, b in zip(ids, np.asarray(ref)))
    c = _counts(reg)
    assert c["search_loop_trips_total"] == int(hops)
    assert c["search_slots_total"] >= c["search_expansions_total"] > 0


def test_loop_body_phases_carry_named_scopes(small_corpus, built_graph,
                                             pq_artifacts):
    """Every phase's scope reaches the compiled ops' op_name metadata, and
    the served fn's `lower` is the program it runs."""
    from repro.serving.engine import make_device_search_fn
    base, q, _ = small_corpus
    cents, codes = pq_artifacts
    idx, lay = from_arrays(base, built_graph, cents, codes)
    fn = make_device_search_fn(idx, lay, metric="l2", L=40, backend="ref")
    text = fn.lower(4, 10).compile().as_text()
    for scope in ("lut", "init"):
        assert f'jit(_beam_search)/{scope}/' in text, scope
    for scope in ("frontier", "hop", "pool", "visited", "trim"):
        assert f'jit(_beam_search)/while/body/{scope}/' in text, scope
    ids = fn(q[:4], 10)
    ref, _, _ = beam_search_device(idx, jnp.asarray(q[:4]), k=10, L=40,
                                   layout=lay, metric="l2", backend="ref")
    np.testing.assert_array_equal(ids, np.asarray(ref))
