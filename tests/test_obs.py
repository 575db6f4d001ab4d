"""Observability layer: histogram bucket math, registry exposition and
merging, span tracing, and trace-context propagation through the framed
wire protocol (including corrupted-frame paths)."""
import json
import socket
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.obs import metrics as M
from repro.obs import trace as T
from repro.serving import protocol as proto

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:              # container has no hypothesis: skip the
    HAVE_HYPOTHESIS = False      # property test, keep the deterministic ones


# ---------------------------------------------------------------------------
# histogram bucket math
# ---------------------------------------------------------------------------


def _hist(values, buckets=M.DEFAULT_LATENCY_BUCKETS_S):
    h = M.Histogram({}, buckets)
    for v in values:
        h.observe(v)
    return h


def test_empty_histogram_has_no_quantiles():
    h = _hist([])
    assert h.quantile(0.5) is None
    assert h._series()["p99"] is None
    assert M.bucket_quantile(h.bounds, h.counts, 0.99) is None


def test_observations_land_in_le_buckets():
    # Prometheus `le` semantics: v == bound counts in that bucket
    h = _hist([0.0001, 0.00025, 0.0005], buckets=(0.0001, 0.00025, 0.0005))
    assert h.counts == [1, 1, 1, 0]
    h2 = _hist([100.0], buckets=(0.001, 1.0))
    assert h2.counts == [0, 0, 1]           # overflow bucket


def test_overflow_quantile_clamps_to_last_finite_bound():
    h = _hist([100.0, 200.0], buckets=(0.001, 1.0))
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.99) == 1.0


def test_percentile_monotone_in_q():
    rng = np.random.default_rng(0)
    h = _hist(rng.lognormal(-6, 2, size=500).tolist())
    qs = [0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 0.999]
    vals = [h.quantile(q) for q in qs]
    assert all(a <= b for a, b in zip(vals, vals[1:]))


def test_quantile_interpolates_within_bucket():
    # 10 observations all in (0.001, 0.002]: p50 lands mid-bucket
    h = _hist([0.0015] * 10, buckets=(0.001, 0.002, 0.004))
    v = h.quantile(0.5)
    assert 0.001 < v <= 0.002
    assert h.quantile(1.0) == pytest.approx(0.002)


def test_observe_many_equals_one_observe_each():
    rng = np.random.default_rng(1)
    vals = rng.lognormal(-5, 2, size=300).tolist() + [0.0001, 100.0]
    one, many = _hist(vals), M.Histogram({})
    many.observe_many(vals)
    many.observe_many([])
    assert many.counts == one.counts and many.count == one.count
    assert many.sum == pytest.approx(one.sum)


def _snap_of(values, labels=None):
    reg = M.MetricsRegistry()
    h = reg.histogram("h", labels, buckets=(0.001, 0.01, 0.1))
    for v in values:
        h.observe(v)
    return reg.snapshot()


def test_merge_is_associative_and_commutative():
    a = _snap_of([0.0005, 0.05])
    b = _snap_of([0.005, 5.0])
    c = _snap_of([0.02])
    ab_c = M.merge_snapshots([M.merge_snapshots([a, b]), c])
    a_bc = M.merge_snapshots([a, M.merge_snapshots([b, c])])
    assert ab_c == a_bc
    assert M.merge_snapshots([a, b]) == M.merge_snapshots([b, a])
    s = ab_c["h"]["series"][0]
    assert s["count"] == 5 and sum(s["counts"]) == 5


def test_merge_recomputes_percentiles_from_merged_counts():
    a, b = _snap_of([0.0005] * 3), _snap_of([0.05] * 3)
    m = M.merge_snapshots([a, b])["h"]["series"][0]
    direct = _snap_of([0.0005] * 3 + [0.05] * 3)["h"]["series"][0]
    assert m["counts"] == direct["counts"]
    assert m["p50"] == direct["p50"] and m["p99"] == direct["p99"]


def test_merge_sums_counters_and_gauges_keeps_label_series_apart():
    def snap(n, corpus):
        reg = M.MetricsRegistry()
        reg.counter("c", {"corpus": corpus}).inc(n)
        reg.gauge("g").set(n)
        return reg.snapshot()
    m = M.merge_snapshots([snap(2, "a"), snap(3, "a"), snap(5, "b")])
    by = {tuple(sorted(s["labels"].items())): s["value"]
          for s in m["c"]["series"]}
    assert by[(("corpus", "a"),)] == 5 and by[(("corpus", "b"),)] == 5
    assert m["g"]["series"][0]["value"] == 10   # gauges sum: cluster total


def test_merge_conflicts_raise():
    reg1, reg2 = M.MetricsRegistry(), M.MetricsRegistry()
    reg1.counter("x").inc()
    reg2.gauge("x").set(1)
    with pytest.raises(ValueError, match="kind conflict"):
        M.merge_snapshots([reg1.snapshot(), reg2.snapshot()])
    with pytest.raises(ValueError, match="bounds conflict"):
        M.merge_snapshots([_snap_of([1.0]),
                           {"h": {"type": "histogram", "series": [dict(
                               labels={}, bounds=[1.0, 2.0], counts=[0, 0, 1],
                               sum=3.0, count=1)]}}])


def test_merge_survives_json_roundtrip():
    # worker snapshots arrive through T_STATS as parsed JSON (tuples
    # became lists, label keys are strings) — merging must not care
    a = json.loads(json.dumps(_snap_of([0.0005], labels={"corpus": "x"})))
    b = _snap_of([0.05], labels={"corpus": "x"})
    m = M.merge_snapshots([a, b])["h"]["series"][0]
    assert m["count"] == 2


if HAVE_HYPOTHESIS:
    @settings(max_examples=100, deadline=None)
    @given(st.lists(st.floats(min_value=0, max_value=100,
                              allow_nan=False), max_size=60),
           st.lists(st.floats(min_value=0, max_value=100,
                              allow_nan=False), max_size=60),
           st.lists(st.floats(min_value=0, max_value=100,
                              allow_nan=False), max_size=60))
    def test_property_merge_equals_direct(xs, ys, zs):
        """merge(snap(xs), snap(ys), snap(zs)) has exactly the buckets,
        sums, and percentiles of observing xs+ys+zs directly, however
        the merge is associated."""
        parts = [_snap_of(v) for v in (xs, ys, zs)]
        left = M.merge_snapshots(
            [M.merge_snapshots(parts[:2]), parts[2]])
        right = M.merge_snapshots(
            [parts[0], M.merge_snapshots(parts[1:])])
        direct = _snap_of(list(xs) + list(ys) + list(zs))
        for m in (left, right):
            s, d = m["h"]["series"][0], direct["h"]["series"][0]
            assert s["counts"] == d["counts"]
            assert s["count"] == d["count"]
            assert s["sum"] == pytest.approx(d["sum"])
            for p in ("p50", "p95", "p99"):
                if d[p] is None:
                    assert s[p] is None
                else:
                    assert s[p] == pytest.approx(d[p])
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_merge_equals_direct():
        pass


# ---------------------------------------------------------------------------
# registry + exposition
# ---------------------------------------------------------------------------


def test_registry_handles_are_idempotent_and_kind_checked():
    reg = M.MetricsRegistry()
    c1 = reg.counter("req", {"corpus": "a"})
    c2 = reg.counter("req", {"corpus": "a"})
    assert c1 is c2
    c1.inc(), c2.inc(2)
    assert c1.value == 3
    assert reg.counter("req", {"corpus": "b"}) is not c1
    with pytest.raises(ValueError, match="is a counter"):
        reg.gauge("req")


def test_prometheus_text_exposition():
    reg = M.MetricsRegistry()
    reg.counter("req_total", {"corpus": "a"}, help="requests").inc(4)
    h = reg.histogram("lat", buckets=(0.001, 0.01))
    h.observe(0.0005), h.observe(5.0)
    text = reg.to_prometheus()
    assert '# TYPE req_total counter' in text
    assert 'req_total{corpus="a"} 4.0' in text
    assert 'lat_bucket{le="0.001"} 1' in text
    assert 'lat_bucket{le="+Inf"} 2' in text
    assert 'lat_count 2' in text
    json.loads(reg.to_json())          # JSON exposition stays valid


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_span_tree_and_chrome_export(tmp_path):
    tr = T.Tracer()
    root = tr.start_span("router.search", annotations=dict(k=5))
    with T.activate(root):
        with T.span("child", shard=1):
            with T.span("grandchild"):
                pass
    root.end()
    tree = tr.span_tree(root.trace_id)
    assert [t["name"] for t in tree] == ["router.search"]
    assert tree[0]["children"][0]["name"] == "child"
    assert tree[0]["children"][0]["children"][0]["name"] == "grandchild"
    dest = tmp_path / "trace.json"
    doc = tr.export_chrome(str(dest), trace_id=root.trace_id)
    on_disk = json.loads(dest.read_text())
    assert on_disk == doc
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert evs["router.search"]["args"]["k"] == 5
    assert evs["child"]["args"]["parent_id"] == root.span_id
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in evs.values())


def test_spans_noop_without_active_parent_or_when_disabled():
    tr = T.Tracer()
    assert T.current_span() is None
    with T.span("orphan") as sp:
        assert sp is None
    assert T.begin("orphan") is None
    root = tr.start_span("r")
    try:
        T.set_enabled(False)
        with T.activate(root):
            assert T.current_span() is None    # kill switch wins
    finally:
        T.set_enabled(True)
    root.end()


class _Mirror:
    """A profiler mirror that records what `span()` opens and closes."""

    def __init__(self):
        self.events = []

    def __call__(self, name, **annotations):
        mirror = self

        class _Annotation:
            def __enter__(self):
                mirror.events.append(("open", name, annotations))

            def __exit__(self, *exc):
                mirror.events.append(("close", name, annotations))
        return _Annotation()


@pytest.fixture
def mirror():
    m = _Mirror()
    prev = T.set_profiler_mirror(m)
    yield m
    T.set_profiler_mirror(prev)


def test_span_without_mirror_or_root_opens_nothing():
    prev = T.set_profiler_mirror(None)
    try:
        with T.span("orphan", nq=3) as sp:
            assert sp is None and T.current_span() is None
    finally:
        T.set_profiler_mirror(prev)


def test_mirror_opens_without_a_tracer_root(mirror):
    assert T.current_span() is None
    with T.span("search.call", nq=8) as sp:
        assert sp is None
        with T.span("search.fetch"):
            pass
    assert mirror.events == [
        ("open", "search.call", {"nq": 8}),
        ("open", "search.fetch", {}), ("close", "search.fetch", {}),
        ("close", "search.call", {"nq": 8})]


def test_mirror_and_tracer_span_together(mirror):
    tr = T.Tracer()
    root = tr.start_span("root")
    with T.activate(root):
        with T.span("child", shard=2) as sp:
            assert sp is not None and T.current_span() is sp
    root.end()
    assert [e[:2] for e in mirror.events] == [("open", "child"),
                                              ("close", "child")]
    names = [d["name"] for d in tr.finished()]
    assert names == ["child", "root"]


def test_kill_switch_silences_the_mirror(mirror):
    try:
        T.set_enabled(False)
        with T.span("engine.collect"):
            pass
    finally:
        T.set_enabled(True)
    assert mirror.events == []


def test_obs_imports_without_jax_or_numpy():
    code = ("import sys, repro.obs, repro.obs.trace, repro.obs.metrics; "
            "print(sorted(m for m in ('jax', 'numpy') if m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env={"PYTHONPATH": "src"},
                         cwd=str(Path(__file__).resolve().parents[1]))
    assert out.stdout.strip() == "[]"


def test_deterministic_sampling_rate():
    tr = T.Tracer(sample=0.25)
    assert sum(tr.sampled() for _ in range(100)) == 25
    assert all(T.Tracer(sample=1.0).sampled() for _ in range(5))
    assert not any(T.Tracer(sample=0.0).sampled() for _ in range(5))


def test_take_pops_only_the_requested_trace():
    tr = T.Tracer()
    a, b = tr.start_span("a"), tr.start_span("b")
    a.end(), b.end()
    got = tr.take(a.trace_id)
    assert [d["name"] for d in got] == ["a"]
    assert [d["name"] for d in tr.finished()] == ["b"]


def test_slow_query_log(tmp_path):
    log = tmp_path / "slow.jsonl"
    tr = T.Tracer(slow_threshold_s=0.01, slow_log_path=str(log))
    fast = tr.start_span("fast")
    fast.end()
    slow = tr.start_span("slow")
    with T.activate(slow):
        with T.span("inner"):
            time.sleep(0.02)
    slow.end()
    assert len(tr.slow_queries) == 1
    entry = tr.slow_queries[0]
    assert entry["name"] == "slow" and entry["duration_s"] >= 0.01
    assert entry["tree"][0]["children"][0]["name"] == "inner"
    lines = [json.loads(ln) for ln in log.read_text().splitlines()]
    assert len(lines) == 1 and lines[0]["trace_id"] == slow.trace_id


# ---------------------------------------------------------------------------
# trace context through the wire protocol
# ---------------------------------------------------------------------------


def test_trace_context_roundtrips_through_query_frame():
    tr = T.Tracer()
    sp = tr.start_span("router.shard0")
    q = np.zeros(8, np.float32)
    h, b = proto.encode_query(q, corpus="c", k=3, req_id=1,
                              deadline_s=None, trace=tr.context(sp))
    a, bsock = socket.socketpair()
    try:
        proto.send_frame(a, proto.T_SEARCH, h, b)
        _, h2, _ = proto.recv_frame(bsock)
    finally:
        a.close(), bsock.close()
    ctx = proto.trace_context(h2)
    assert ctx == {"tid": sp.trace_id, "sid": sp.span_id}
    # the worker-side remote span parents onto the router-side span
    wtr = T.Tracer()
    wsp = wtr.start_remote("worker.serve", ctx)
    assert wsp.trace_id == sp.trace_id and wsp.parent_id == sp.span_id
    sp.end()


def test_untraced_query_frame_has_no_context():
    h, _ = proto.encode_query(np.zeros(4, np.float32), corpus="c", k=1,
                              req_id=1, deadline_s=None)
    assert "trace" not in h and proto.trace_context(h) is None


@pytest.mark.parametrize("bad", [
    "not-a-dict", {"tid": "x"}, {"sid": "y"}, {"tid": "", "sid": "y"},
    {"tid": 7, "sid": "y"}, {"tid": None, "sid": None}, [], 3,
])
def test_malformed_trace_context_degrades_to_untraced(bad):
    assert proto.trace_context({"trace": bad, "k": 1}) is None


def test_result_frame_carries_spans_back():
    tr = T.Tracer()
    sp = tr.start_span("worker.serve")
    sp.end()
    spans = tr.take(sp.trace_id)
    ids = np.array([1, 2], np.int64)
    dists = np.array([0.1, 0.2], np.float32)
    h, b = proto.encode_result(ids, dists, req_id=9, spans=spans)
    a, bsock = socket.socketpair()
    try:
        proto.send_frame(a, proto.T_RESULT, h, b)
        _, h2, b2 = proto.recv_frame(bsock)
    finally:
        a.close(), bsock.close()
    assert h2["spans"][0]["span_id"] == sp.span_id
    i2, d2 = proto.decode_result(h2, b2)
    np.testing.assert_array_equal(i2, ids)
    # untraced results stay lean
    h3, _ = proto.encode_result(ids, dists, req_id=9)
    assert "spans" not in h3


def test_corrupted_traced_frame_still_fails_crc():
    tr = T.Tracer()
    sp = tr.start_span("s")
    h, b = proto.encode_query(np.zeros(8, np.float32), corpus="c", k=3,
                              req_id=1, deadline_s=None,
                              trace=tr.context(sp))
    raw = bytearray(proto.pack_frame(proto.T_SEARCH, h, b))
    raw[len(raw) // 2] ^= 0x10
    a, bsock = socket.socketpair()
    try:
        a.sendall(bytes(raw))
        with pytest.raises(proto.ProtocolError):
            proto.recv_frame(bsock)
    finally:
        a.close(), bsock.close()
    sp.end()


# ---------------------------------------------------------------------------
# router telemetry: first-attempt vs hedge split
# ---------------------------------------------------------------------------


def test_router_splits_first_vs_hedge_latency():
    from repro.serving.router import LocalShardClient, ShardRouter

    calls = {"n": 0}

    def flaky(q, k):
        calls["n"] += 1
        if calls["n"] == 1:            # first attempt fails, hedge lands
            raise RuntimeError("boom")
        return (np.arange(k, dtype=np.int64),
                np.arange(k, dtype=np.float32))

    r = ShardRouter([LocalShardClient(flaky)], min_shards=1)
    try:
        out = r.search(np.zeros(4, np.float32), 3)
        assert not out.partial and out.retried_shards == [0]
        s = r.stats()
        assert s["queries"] == 1 and s["full"] == 1
        assert s["shard_attempts"] == 2 and s["shard_failures"] == 1
        assert s["retries"] == 1 and s["retry_successes"] == 1
        al = s["attempt_latency"]
        assert al["first"]["count"] == 1 and al["hedge"]["count"] == 1
        assert al["hedge"]["p50_ms"] >= 0.0
        fam = s["registry"]["router_attempt_latency_seconds"]
        kinds = {s_["labels"]["attempt"] for s_ in fam["series"]}
        assert kinds == {"first", "hedge"}
    finally:
        r.close()


def test_router_traces_local_shards():
    from repro.serving.router import LocalShardClient, ShardRouter

    def ok(q, k):
        return (np.arange(k, dtype=np.int64),
                np.arange(k, dtype=np.float32))

    tr = T.Tracer(sample=1.0)
    r = ShardRouter([LocalShardClient(ok), LocalShardClient(ok)],
                    min_shards=2, tracer=tr)
    try:
        r.search(np.zeros(4, np.float32), 3)
        names = sorted(d["name"] for d in tr.finished())
        assert names == ["router.search", "router.shard0", "router.shard1"]
        roots = [d for d in tr.finished() if d["parent_id"] is None]
        assert len(roots) == 1 and roots[0]["name"] == "router.search"
        assert roots[0]["annotations"]["outcome"] == "full"
    finally:
        r.close()


# ---------------------------------------------------------------------------
# service registry
# ---------------------------------------------------------------------------


def test_service_stats_expose_registry_snapshot(index_dirs):
    from repro.serving.pool import WarmIndexPool
    from repro.serving.service import RetrievalService

    pool = WarmIndexPool({"a": index_dirs["aisaq"]}, cache_bytes=1 << 20)
    svc = RetrievalService(pool, num_workers=1, L=24, w=4)
    try:
        q = np.zeros((48,), np.float32)
        r = svc.submit(q, corpus="a", k=3)
        assert r.event.wait(10.0) and r.error is None
        st = svc.stats()
        ca = st["corpora"]["a"]
        assert ca["completed"] == 1
        assert ca["p99_ms"] >= ca["p50_ms"] > 0
        reg = st["registry"]
        lat = reg["service_latency_seconds"]["series"][0]
        assert lat["count"] == 1 and lat["p50"] is not None
        # the search-path distributions reached the same registry
        assert reg["traversal_hops"]["series"][0]["count"] >= 1
        assert reg["search_batch_latency_seconds"]["series"][0]["count"] == 1
    finally:
        svc.close()
        pool.close()
