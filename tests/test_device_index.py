import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.device_index import DeviceIndex, beam_search_device, \
    from_arrays
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


def _small_random_index(seed, n=400, d=16, m=4, ks=16, R=8, nq=24):
    """Random vectors, codes and out-edges (a tenth of them -1): a graph
    with no locality, so a search keeps meeting ids it has dropped."""
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(n, d)).astype(np.float32)
    graph = rng.integers(0, n, size=(n, R)).astype(np.int32)
    graph[rng.random((n, R)) < 0.1] = -1
    cents = rng.normal(size=(m, ks, d // m)).astype(np.float32)
    codes = rng.integers(0, ks, size=(n, m)).astype(np.uint8)
    return base, graph, cents, codes, rng.normal(size=(nq, d)) \
        .astype(np.float32)


def _replay(graph, pq, exact, ep, *, L, w, k, max_hops):
    """Algorithm 1 for one query with an exact `seen` set that starts with
    the entry point, in the device loop's order (ties go to the earlier
    position). `pq(v, first)` is v's PQ distance, `first` set for the entry
    point's seeding; `exact(v)` its exact distance. Returns the top-k ids
    and exact distances, the loop trips, the expansions, and the re-offers
    of all ids and of the entry point: neighbours seen before and no longer
    in the candidate list, the case a list-only dedup must get right."""
    cand, pool, seen = [[pq(ep, True), ep, False]], [], {ep}
    hops = expansions = reoffers = ep_reoffers = 0
    while hops < max_hops and not all(c[2] for c in cand):
        hops += 1
        front = [c for c in cand if not c[2]][:w]
        new = []
        for c in front:
            c[2] = True
            pool.append((exact(c[1]), c[1]))
            for v in map(int, graph[c[1]]):
                if v < 0:
                    continue
                if v in seen:
                    gone = v not in {x[1] for x in cand + new}
                    reoffers += gone
                    ep_reoffers += gone and v == ep
                    continue
                seen.add(v)
                new.append([pq(v, False), v, False])
        expansions += len(front)
        pool = sorted(pool, key=lambda p: p[0])[:L]
        cand = sorted(cand + new, key=lambda c: c[0])[:L]
    return ([p[1] for p in pool[:k]], [p[0] for p in pool[:k]], hops,
            expansions, reoffers, ep_reoffers)


def _hop_distances(idx, lay, q, lut, *, metric, backend, adc):
    """(nq, n) PQ distance of every node as the search's hop computes it,
    read from every chunk that lists the node; asserts they all agree."""
    from repro.kernels import ops
    n = idx.n
    operands = ops.hop_inputs(lut, q, layout=lay, backend=backend,
                              adc_dtype=adc)
    _, nids, nd = ops.hop(idx.chunk_words, jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32), (q.shape[0], n)), operands,
        layout=lay, metric=metric, backend=backend)
    nids, nd = np.asarray(nids).reshape(len(q), -1), \
        np.asarray(nd).reshape(len(q), -1)
    table = np.full((len(q), n), np.nan)
    for i in range(len(q)):
        ok = nids[i] >= 0
        table[i, nids[i][ok]] = nd[i][ok]
        np.testing.assert_array_equal(table[i, nids[i][ok]], nd[i][ok])
    return table


@pytest.mark.parametrize("metric,mode,backend,adc", [
    (metric, "aisaq", backend, adc) for metric in ("l2", "mips")
    for backend in ("ref", "pallas_interpret") for adc in ("f32", "int8")]
    + [("l2", "diskann", "ref", "f32"), ("mips", "diskann", "ref", "f32")])
def test_list_dedup_matches_exact_seen_set(metric, mode, backend, adc):
    """Dedup against the candidate list alone gives the answers of an exact
    visited set, on an index where dropped ids, the entry point among them,
    are offered again: the same ids, trips and expansions, and no id twice
    among the L pooled answers (k = L). The replay is fed the device's own
    PQ distances: the hop's for neighbours (int8 ADC included) and the f32
    LUT's for the entry point's seeding. With f32 ADC the benchmark's
    float64 numpy reference agrees too."""
    from repro.core.device_index import _beam_search
    from repro.kernels import ops
    base, graph, cents, codes, q = _small_random_index(3)
    L, w, max_hops = 6, 2, 64
    idx, lay = from_arrays(base, graph, cents, codes, mode=mode)
    m, ks = codes.shape[1], cents.shape[1]
    ep = int(idx.ep_ids[0])
    lut = ops.build_lut(jnp.asarray(q), idx.centroids, metric=metric,
                        backend=backend)
    flat = np.asarray(lut).reshape(len(q), m * ks)
    seed_d = flat[:, np.asarray(idx.ep_codes[0]) + np.arange(m) * ks] \
        .sum(axis=1, dtype=np.float32)
    if mode == "aisaq":
        nbr_d = _hop_distances(idx, lay, jnp.asarray(q), lut, metric=metric,
                               backend=backend, adc=adc)
    else:
        nbr_d = flat[:, codes.astype(np.int64) + np.arange(m) * ks] \
            .sum(axis=2, dtype=np.float32)
    want = []
    for i, qq in enumerate(q.astype(np.float64)):
        def pq(v, first, i=i):
            return float(seed_d[i] if first else nbr_d[i, v])

        def exact(v, qq=qq):
            x = base[v].astype(np.float64)
            return -(x @ qq) if metric == "mips" else ((x - qq) ** 2).sum()
        want.append(_replay(graph, pq, exact, ep, L=L, w=w, k=L,
                            max_hops=max_hops))
    assert sum(r[4] for r in want) > 0 and sum(r[5] for r in want) > 0
    ids, d, hops, expanded = _beam_search(
        idx, jnp.asarray(q), k=L, L=L, w=w, max_hops=max_hops, layout=lay,
        metric=metric, backend=backend, adc_dtype=adc)
    ids, d = np.asarray(ids), np.asarray(d)
    for row in ids:
        assert len(set(row[row >= 0].tolist())) == len(row[row >= 0])
    np.testing.assert_array_equal(ids, [r[0] for r in want])
    np.testing.assert_allclose(d, [r[1] for r in want], rtol=1e-5,
                               atol=1e-5)
    assert int(hops) == max(r[2] for r in want)
    assert int(expanded) == sum(r[3] for r in want)
    if adc == "f32":
        root = str(Path(__file__).resolve().parents[1])
        if root not in sys.path:
            sys.path.insert(0, root)
        from benchmarks.chip import reference
        r_ids, r_d, r_hops = reference.beam_search(
            base, graph, codes, cents, q, k=4, L=L, w=w, max_hops=max_hops,
            metric=metric, entry=ep)
        np.testing.assert_array_equal(ids[:, :4], r_ids)
        assert int(hops) == int(r_hops.max())
        np.testing.assert_allclose(d[:, :4], r_d, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode,backend,adc", [
    ("aisaq", backend, adc) for backend in ("ref", "pallas_interpret")
    for adc in ("f32", "int8")] + [("diskann", "ref", "f32")])
def test_entry_point_is_never_offered_again(mode, backend, adc):
    """An entry point is seeded from the LUT, not by the hop, so its two PQ
    distances may differ (int8 ADC, another summation order). Seeded here
    with the codes of the row farthest from the data's mean, it is evicted
    early and offered again below its seeding: if only the list were
    checked it would re-enter and be expanded twice. At most L expansions
    a query, so the pool of L holds every expanded id: none may repeat."""
    from repro.core.device_index import _beam_search
    from repro.kernels import ops
    base, graph, cents, codes, q = _small_random_index(3)
    L, w, max_hops = 16, 2, 8
    idx, lay = from_arrays(base, graph, cents, codes, mode=mode)
    far = np.argmax(((base - base.mean(axis=0)) ** 2).sum(axis=1))
    far_codes = codes[far].astype(np.int64)
    idx = idx._replace(ep_codes=jnp.asarray(far_codes[None], jnp.int32))
    ep, m = int(idx.ep_ids[0]), codes.shape[1]
    lut = np.asarray(ops.build_lut(jnp.asarray(q), idx.centroids,
                                   metric="l2", backend="ref"), np.float64)

    def ep_reoffers(lt):
        def pq(v, first):
            return lt[np.arange(m), far_codes if first else codes[v]].sum()
        return _replay(graph, pq, lambda v: 0.0, ep, L=L, w=w, k=L,
                       max_hops=max_hops)[5]
    assert sum(map(ep_reoffers, lut)) > 0
    ids, _, _, expanded = _beam_search(
        idx, jnp.asarray(q), k=L, L=L, w=w, max_hops=max_hops, layout=lay,
        metric="l2", backend=backend, adc_dtype=adc)
    ids = np.asarray(ids)
    assert int((ids >= 0).sum()) == int(expanded) > 0
    for row in ids:
        assert len(set(row[row >= 0].tolist())) == len(row[row >= 0])


def test_search_loop_state_does_not_grow_with_n():
    """At 1 M rows and nq 1,024 (shapes only, nothing allocated) the loop
    body has no scatter, and no array but the chunk table follows N."""
    from repro.configs.aisaq_indices import SIFT1M
    from repro.core.chunk_layout import layout_for
    n, nq = 1_000_000, 1024
    lay = layout_for(SIFT1M, "aisaq")
    m, ks = SIFT1M.pq_m, SIFT1M.pq_ks
    sds = jax.ShapeDtypeStruct
    idx = DeviceIndex(
        chunk_words=sds((n, lay.device_rows, 128), jnp.int32),
        centroids=sds((m, ks, SIFT1M.dim // m), jnp.float32),
        ep_ids=sds((1,), jnp.int32), ep_codes=sds((1, m), jnp.int32))
    hlo = beam_search_device.lower(
        idx, sds((nq, SIFT1M.dim), jnp.float32), k=10, L=48, w=4,
        layout=lay, metric="l2", backend="ref") \
        .compiler_ir("hlo").as_hlo_text()
    comps = dict(re.findall(r"^(\S+) \{\n(.*?)^\}", hlo, re.M | re.S))
    todo = re.findall(r" while\(.*body=([\w.]+)", hlo)
    body = []
    while todo:
        text = comps[todo.pop()]
        body += text.splitlines()
        todo += re.findall(r"(?:to_apply|calls|body|condition)=([\w.]+)",
                           text)
    assert any(" topk(" in ln for ln in body)     # the body was found
    assert not [ln for ln in body if " scatter(" in ln]
    table = f"s32[{n},{lay.device_rows},128]"
    dims = re.findall(r"[a-z]\w*\[([\d,]+)\]", hlo.replace(table, ""))
    sizes = {int(x) for ds in dims for x in ds.split(",")}
    assert nq in sizes and not sizes & {n, -(-n // 32)}, sorted(sizes)[-4:]
