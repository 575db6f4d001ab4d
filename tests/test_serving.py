import time

import numpy as np
import pytest

from repro.serving.engine import ServingEngine


def _search_fn(delay_s=0.0):
    def fn(queries, k):
        if delay_s:
            time.sleep(delay_s)
        # deterministic fake ids
        return np.tile(np.arange(k)[None], (queries.shape[0], 1))
    return fn


def test_engine_batches_and_answers():
    eng = ServingEngine({"default": _search_fn()}, max_batch=8,
                        max_wait_ms=5.0)
    reqs = [eng.submit(np.ones(8, np.float32) * i) for i in range(20)]
    for r in reqs:
        r.event.wait(5.0)
        assert r.result is not None and r.result.shape == (10,)
    pct = eng.latency_percentiles()
    assert pct["n"] == 20
    eng.stop()


def test_engine_histograms_and_spans():
    """One queue wait per request, one batch size per batch, one latency
    per answer; each batch is collected and fanned out under a span."""
    from repro.obs import trace as T
    from repro.obs.metrics import MetricsRegistry
    spans = []

    class _Annotation:
        def __init__(self, name, **annotations):
            spans.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    prev = T.set_profiler_mirror(_Annotation)
    reg = MetricsRegistry()
    try:
        eng = ServingEngine({"default": _search_fn(0.002)}, max_batch=4,
                            max_wait_ms=5.0, registry=reg)
        assert eng.registry is reg
        reqs = [eng.submit(np.ones(8, np.float32) * i) for i in range(10)]
        for r in reqs:
            assert r.event.wait(5.0) and r.result is not None
        eng.stop()
    finally:
        T.set_profiler_mirror(prev)
    snap = reg.snapshot()
    wait = snap["engine_queue_wait_seconds"]["series"][0]
    lat = snap["engine_latency_seconds"]["series"][0]
    size = snap["engine_batch_size"]["series"][0]
    assert wait["count"] == lat["count"] == 10
    assert size["sum"] == 10 and size["count"] == spans.count("engine.fanout")
    assert 3 <= size["count"] <= 10
    assert spans.count("engine.collect") >= size["count"]
    assert 0 <= wait["sum"] <= lat["sum"]
    for p in ("p50", "p95", "p99"):
        assert wait[p] <= lat[p]
    pct = eng.latency_percentiles()
    assert set(pct) == {"p50_ms", "p95_ms", "p99_ms", "n"}
    assert pct["n"] == 10 and 0 < pct["p50_ms"] <= pct["p99_ms"]


@pytest.mark.parametrize("sigma", [0.3, 1.5])
def test_engine_latency_percentiles_within_a_bucket(sigma):
    """`latency_percentiles()` interpolates in `ENGINE_BUCKETS_S`, whose
    bounds are 12.2% apart: no percentile is further from the exact one."""
    from repro.obs.metrics import Histogram
    from repro.serving.engine import ENGINE_BUCKETS_S
    ratio = max(b / a for a, b in zip(ENGINE_BUCKETS_S, ENGINE_BUCKETS_S[1:]))
    assert ratio < 1.123
    lat = np.random.default_rng(3).lognormal(np.log(0.015), sigma, 5000)
    h = Histogram({}, buckets=ENGINE_BUCKETS_S)
    h.observe_many(lat.tolist())
    for q in (0.5, 0.95, 0.99):
        exact = float(np.percentile(lat, 100 * q))
        assert abs(h.quantile(q) / exact - 1) < ratio - 1, q


def test_hedging_beats_straggler():
    fast, slow = _search_fn(0.002), _search_fn(0.25)
    hedged = ServingEngine({"default": slow}, hedge=2,
                           replicas=[slow, fast], max_wait_ms=1.0)
    r = hedged.submit_wait(np.ones(4, np.float32))
    assert r.latency_s < 0.2          # fast replica won the hedge
    hedged.stop()
    unhedged = ServingEngine({"default": slow}, max_wait_ms=1.0)
    r2 = unhedged.submit_wait(np.ones(4, np.float32))
    assert r2.latency_s >= 0.2
    unhedged.stop()


def test_corpus_switch_called():
    calls = []
    eng = ServingEngine({"a": _search_fn(), "b": _search_fn()},
                        switch_fn=lambda c: calls.append(c) or 0.001,
                        max_wait_ms=1.0)
    eng.submit_wait(np.ones(4, np.float32), corpus="a")
    eng.submit_wait(np.ones(4, np.float32), corpus="b")
    eng.submit_wait(np.ones(4, np.float32), corpus="b")  # no switch
    assert calls == ["a", "b"]
    assert len(eng.switch_times) == 2
    eng.stop()


# ---------------------------------------------------------------------------
# hedging fix: first SUCCESSFUL completion wins; wasted work is accounted
# ---------------------------------------------------------------------------


def _failing_fn(delay_s=0.0):
    def fn(queries, k):
        if delay_s:
            time.sleep(delay_s)
        raise ValueError("replica down")
    return fn


def test_hedge_skips_failed_replica():
    """A fast-failing replica must NOT win the hedge race (the old code
    took `list(done)[0].result()`, which could pick the failure)."""
    fail, good = _failing_fn(), _search_fn(0.02)
    for _ in range(5):                    # old bug was racy: hammer it
        eng = ServingEngine({"default": good}, hedge=2,
                            replicas=[fail, good], max_wait_ms=1.0)
        r = eng.submit_wait(np.ones(4, np.float32))
        assert r.error is None
        assert r.result is not None and r.result.shape == (10,)
        assert eng.hedge_stats["failed"] >= 1
        eng.stop()


def test_hedge_all_replicas_fail_sets_error():
    eng = ServingEngine({"default": _failing_fn()}, hedge=2,
                        replicas=[_failing_fn(), _failing_fn(0.01)],
                        max_wait_ms=1.0)
    r = eng.submit_wait(np.ones(4, np.float32))
    assert r.result is None
    assert isinstance(r.error, ValueError)
    assert eng.hedge_stats["failed"] == 2
    eng.stop()


def test_hedge_wasted_work_accounted():
    """Both replicas succeed; the loser's completed work counts as wasted
    (Future.cancel() on a running thread is a no-op — the engine must not
    pretend the work disappeared)."""
    fast, slow = _search_fn(0.005), _search_fn(0.08)
    eng = ServingEngine({"default": fast}, hedge=2,
                        replicas=[slow, fast], max_wait_ms=1.0)
    r = eng.submit_wait(np.ones(4, np.float32))
    assert r.result is not None
    time.sleep(0.15)                      # let the slow loser finish
    assert eng.hedge_stats["batches"] == 1
    assert eng.hedge_stats["wasted"] == 1
    eng.stop()


# ---------------------------------------------------------------------------
# _collect_batch holdover fix (regression for the re-queue starvation bug)
# ---------------------------------------------------------------------------


def test_foreign_corpus_request_not_starved():
    """Old bug: a different-corpus request was pushed to the BACK of the
    FIFO, so sustained load on corpus `a` could starve a `b` request
    indefinitely. With the holdover deque, `b` is served at the next batch
    head — before `a` requests that arrived after it."""
    eng = ServingEngine({"a": _search_fn(0.01), "b": _search_fn(0.01)},
                        max_batch=4, max_wait_ms=20.0)
    head = [eng.submit(np.ones(4, np.float32), corpus="a")
            for _ in range(3)]
    rb = eng.submit(np.ones(4, np.float32), corpus="b")
    tail = [eng.submit(np.ones(4, np.float32), corpus="a")
            for _ in range(8)]
    for r in head + [rb] + tail:
        r.event.wait(10.0)
        assert r.result is not None
    # b (submitted before the tail) must complete before the LAST tail
    # request — under the old re-queue-to-back it would finish dead last
    assert rb.t_done <= tail[-1].t_done
    assert eng.latency_percentiles()["n"] == 12
    eng.stop()


def test_stop_fails_parked_requests():
    """stop() must error out requests still sitting in the queue or the
    holdover deque — a submit_wait caller must not hang to its timeout."""
    eng = ServingEngine({"a": _search_fn(0.2), "b": _search_fn(0.2)},
                        max_batch=2, max_wait_ms=1.0)
    ra = eng.submit(np.ones(4, np.float32), corpus="a")
    parked = [eng.submit(np.ones(4, np.float32), corpus="b")
              for _ in range(3)]
    ra.event.wait(5.0)                    # first a-batch in flight/done
    eng.stop()
    for r in parked:
        assert r.event.wait(1.0)
        assert r.result is not None or r.error is not None
    eng.stop()                            # idempotent
    with pytest.raises(RuntimeError):     # dead loop accepts no work
        eng.submit(np.ones(4, np.float32))


def test_held_requests_preserve_per_corpus_fifo():
    eng = ServingEngine({"a": _search_fn(0.01), "b": _search_fn(0.01)},
                        max_batch=2, max_wait_ms=10.0)
    rs = []
    for corpus in ("a", "b", "a", "b", "b", "a"):
        rs.append((corpus, eng.submit(np.ones(4, np.float32),
                                      corpus=corpus)))
    for _, r in rs:
        r.event.wait(10.0)
        assert r.result is not None
    for corpus in ("a", "b"):
        done = [r.t_done for c, r in rs if c == corpus]
        assert done == sorted(done)       # FIFO within each corpus
    eng.stop()


# ---------------------------------------------------------------------------
# RetrievalService: per-corpus queues, concurrency, admission control
# ---------------------------------------------------------------------------


@pytest.fixture()
def service_pool(tmp_path, small_corpus, pq_artifacts):
    from repro.core.index_io import write_index
    from repro.core.vamana import build_vamana
    from repro.serving.pool import WarmIndexPool
    base, _, _ = small_corpus
    cents, codes = pq_artifacts
    paths = {}
    for i in range(2):
        sl = slice(i * 700, (i + 1) * 700)
        g = build_vamana(base[sl], R=12, L=24, seed=i)
        p = str(tmp_path / f"t{i}")
        write_index(p, vectors=base[sl], graph=g, centroids=cents,
                    codes=codes[sl], metric="l2", mode="aisaq")
        paths[f"t{i}"] = p
    pool = WarmIndexPool(paths, cache_bytes=256 << 10)
    yield pool
    pool.close()


def test_retrieval_service_multicorpus_integration(service_pool,
                                                   small_corpus):
    from repro.core.index_io import HostIndex
    from repro.serving.service import RetrievalService
    base, q, _ = small_corpus
    refs = {}
    for name, path in service_pool.paths.items():
        idx = HostIndex.load(path)
        refs[name], _ = idx.search_batch(q, 5, L=24)
        idx.close()
    svc = RetrievalService(service_pool, num_workers=2, max_batch=4,
                           max_wait_ms=1.0, L=24)
    reqs = [(f"t{i % 2}", i % len(q),
             svc.submit(q[i % len(q)], corpus=f"t{i % 2}", k=5))
            for i in range(16)]
    for name, qi, r in reqs:
        r.event.wait(10.0)
        assert r.error is None and r.result is not None
        np.testing.assert_array_equal(r.result, refs[name][qi])
    st = svc.stats()
    assert st["total_completed"] == 16
    for name in ("t0", "t1"):
        c = st["corpora"][name]
        assert c["completed"] == 8 and c["switches"] == 1
        assert c["p99_ms"] >= c["p50_ms"] > 0
        assert c["qps"] > 0
    assert st["pool"]["misses"] == 2      # one load per corpus, ever
    svc.stop()


def test_service_corpora_serve_concurrently():
    """Two corpora, two workers, a deliberately slow search: total wall
    time must be closer to ONE search than two (the ServingEngine this
    replaces serialized every corpus through one loop thread)."""
    from repro.serving.pool import WarmIndexPool
    from repro.serving.service import RetrievalService
    delay = 0.3

    def slow_fn(idx, queries, k):
        time.sleep(delay)
        return np.tile(np.arange(k)[None], (queries.shape[0], 1))

    pool = WarmIndexPool({"a": "/nonexistent-a", "b": "/nonexistent-b"})
    pool.pin = lambda name, share_centroids=True: (None, 0.0)  # no disk
    pool.unpin = lambda name, index=None: None
    svc = RetrievalService(pool, num_workers=2, max_wait_ms=1.0,
                           search_fn=slow_fn)
    t0 = time.perf_counter()
    ra = svc.submit(np.ones(4, np.float32), corpus="a", k=5)
    rb = svc.submit(np.ones(4, np.float32), corpus="b", k=5)
    ra.event.wait(5.0), rb.event.wait(5.0)
    wall = time.perf_counter() - t0
    assert ra.result is not None and rb.result is not None
    assert wall < 1.8 * delay             # overlapped, not serialized
    svc.stop()


def test_service_admission_control_rejects(service_pool, small_corpus):
    from repro.serving.service import BackpressureError, RetrievalService
    base, q, _ = small_corpus

    def stall(idx, queries, k):
        time.sleep(0.2)
        return np.zeros((queries.shape[0], k), np.int64)

    svc = RetrievalService(service_pool, num_workers=1, max_queue_depth=2,
                           max_wait_ms=0.5, search_fn=stall)
    rejected = 0
    for _ in range(12):
        try:
            svc.submit(q[0], corpus="t0", k=5)
        except BackpressureError as e:
            rejected += 1
            assert e.corpus == "t0" and e.limit == 2
    assert rejected > 0
    assert svc.stats()["total_rejected"] == rejected
    assert svc.stats()["corpora"]["t0"]["rejected"] == rejected
    svc.stop()


def test_service_unknown_corpus_and_stop_drains(service_pool, small_corpus):
    from repro.serving.service import RetrievalService
    base, q, _ = small_corpus
    svc = RetrievalService(service_pool, num_workers=1, max_wait_ms=0.5)
    with pytest.raises(KeyError, match="unknown corpus"):
        svc.submit(q[0], corpus="nope")
    svc.stop()
    with pytest.raises(RuntimeError):
        svc.submit(q[0], corpus="t0")


def test_service_submit_wait_timeout_raises(service_pool, small_corpus):
    from repro.serving.service import RetrievalService
    base, q, _ = small_corpus

    def stall(idx, queries, k):
        time.sleep(0.5)
        return np.zeros((queries.shape[0], k), np.int64)

    svc = RetrievalService(service_pool, num_workers=1, max_wait_ms=0.5,
                           search_fn=stall)
    with pytest.raises(TimeoutError):
        svc.submit_wait(q[0], corpus="t0", timeout=0.05)
    svc.stop()


# ---------------------------------------------------------------------------
# graceful close: drain, then typed rejection
# ---------------------------------------------------------------------------


def test_service_close_drains_then_rejects_typed(service_pool,
                                                 small_corpus):
    from repro.serving.service import RetrievalService, ServiceClosedError
    base, q, _ = small_corpus

    def slowish(idx, queries, k):
        time.sleep(0.05)
        return np.tile(np.arange(k)[None], (queries.shape[0], 1))

    svc = RetrievalService(service_pool, num_workers=1, max_batch=2,
                           max_wait_ms=0.5, search_fn=slowish)
    reqs = [svc.submit(q[0], corpus="t0", k=5) for _ in range(6)]
    svc.close(drain_s=10.0)
    # everything queued before close() COMPLETED (drained, not dropped)
    for r in reqs:
        assert r.event.is_set()
        assert r.error is None and r.result is not None
    # submits after close fail with the typed error, which subclasses
    # RuntimeError so existing except-RuntimeError callers still catch it
    with pytest.raises(ServiceClosedError):
        svc.submit(q[0], corpus="t0", k=5)
    assert issubclass(ServiceClosedError, RuntimeError)


def test_service_stop_fails_queued_with_typed_error(service_pool,
                                                    small_corpus):
    from repro.serving.service import RetrievalService, ServiceClosedError
    base, q, _ = small_corpus

    def stall(idx, queries, k):
        time.sleep(0.3)
        return np.zeros((queries.shape[0], k), np.int64)

    svc = RetrievalService(service_pool, num_workers=1, max_batch=1,
                           max_wait_ms=0.5, search_fn=stall)
    reqs = [svc.submit(q[0], corpus="t0", k=5) for _ in range(4)]
    svc.stop(timeout=1.0)
    failed = [r for r in reqs if r.error is not None]
    assert failed, "stop() left queued requests silently unresolved"
    for r in failed:
        assert isinstance(r.error, ServiceClosedError)


def test_service_stats_one_snapshot_with_pool(service_pool, small_corpus):
    """stats() returns ONE consistent snapshot: totals equal the sum of
    the per-corpus rows taken under the same lock hold, and the pool
    section (taken outside the service lock — the service never holds
    both) carries the journal-recovery map."""
    from repro.serving.service import RetrievalService
    base, q, _ = small_corpus
    svc = RetrievalService(service_pool, num_workers=2, max_wait_ms=0.5,
                           L=24)
    for i in range(8):
        svc.submit_wait(q[i % len(q)], corpus=f"t{i % 2}", k=5,
                        timeout=10.0)
    st = svc.stats()
    assert st["total_completed"] == sum(
        c["completed"] for c in st["corpora"].values()) == 8
    assert "recoveries" in st["pool"]       # clean boot: empty map
    assert st["pool"]["recoveries"] == {}
    svc.stop()
