"""Storage fault-tolerance layer: deterministic injection, CRC integrity,
retrying reads, traversal degradation, health-aware serving, crash-safe
index writes."""
import errno
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core.block_cache import BlockCache, RetryPolicy
from repro.core.faults import FaultInjector, FaultPlan
from repro.core.index_io import HostIndex, write_index
from repro.core.integrity import (CRC_SIDECAR, FORMAT_VERSION,
                                  CorruptBlockError, CorruptIndexError,
                                  block_checksums, _crc32)
from repro.core.traversal import search_batch, search_batch_ref
from repro.serving.pool import CorpusUnhealthyError, WarmIndexPool
from repro.serving.service import RetrievalService

# fast retries: tests should not sleep through production backoff
FAST_RETRY = RetryPolicy(attempts=6, backoff_s=1e-4, backoff_max_s=1e-3)


@pytest.fixture(scope="module")
def faulty_fixture(tmp_path_factory):
    """One small index + queries + entry-block coordinates, shared by the
    injection tests (each test opens its own handle/injector)."""
    from repro.core import pq
    from repro.core.vamana import build_vamana
    from repro.data.vectors import make_clustered, make_queries
    import jax
    base = make_clustered(900, 48, seed=3)
    q = make_queries(10, base, seed=4)
    g = build_vamana(base, R=16, L=32, seed=0)
    cb = pq.train_codebooks(jax.random.PRNGKey(0), base, m=12, iters=6)
    p = str(tmp_path_factory.mktemp("faulty") / "idx")
    write_index(p, vectors=base, graph=g, centroids=np.asarray(cb.centroids),
                codes=np.asarray(pq.encode(cb, base)), metric="l2",
                mode="aisaq")
    idx = HostIndex.load(p)
    ep = int(idx.meta["entry_points"][0])
    ep_block = idx.layout.file_offset(ep) // idx.layout.io_bytes
    io_bytes = idx.layout.io_bytes
    ref, _ = idx.search_batch(q, 5, L=24)
    idx.close()
    return p, q, ref, ep_block, io_bytes


# ---------------------------------------------------------------------------
# FaultInjector determinism
# ---------------------------------------------------------------------------


def test_injector_schedule_is_deterministic(tmp_path):
    f = tmp_path / "blob.bin"
    f.write_bytes(os.urandom(16 * 512))
    plan = dict(seed=11, eio_rate=0.3, eagain_rate=0.2, short_read_rate=0.2,
                corrupt_blocks={2: 3})

    def run():
        inj = FaultInjector(FaultPlan(**plan))
        fd = os.open(str(f), os.O_RDONLY)
        log = []
        try:
            for off in [0, 512, 1024, 0, 512, 1024, 2048, 1024]:
                buf = bytearray(512)
                try:
                    got = inj.preadv(fd, [buf], off)
                    log.append(("ok", got, bytes(buf)))
                except OSError as e:
                    log.append(("err", e.errno))
        finally:
            os.close(fd)
        return log, inj.stats()

    log1, st1 = run()
    log2, st2 = run()
    assert log1 == log2
    assert st1 == st2
    assert st1["calls"] == 8


def test_injector_retry_is_a_fresh_draw(tmp_path):
    """eio_rate=1.0 with max_faults=1: the first read fails, the retry of
    the SAME offset is a new draw past the budget and succeeds."""
    f = tmp_path / "blob.bin"
    payload = os.urandom(4 * 512)
    f.write_bytes(payload)
    inj = FaultInjector(FaultPlan(seed=0, eio_rate=1.0, max_faults=1))
    fd = os.open(str(f), os.O_RDONLY)
    try:
        buf = bytearray(512)
        with pytest.raises(OSError):
            inj.preadv(fd, [buf], 0)
        assert inj.preadv(fd, [buf], 0) == 512
        assert bytes(buf) == payload[:512]
    finally:
        os.close(fd)
    assert inj.stats()["injected_eio"] == 1


# ---------------------------------------------------------------------------
# Retry + CRC through the real read path
# ---------------------------------------------------------------------------


def test_retry_absorbs_transient_eio(faulty_fixture):
    p, q, ref, _, _ = faulty_fixture
    inj = FaultInjector(FaultPlan(seed=5, eio_rate=1.0, max_faults=1))
    idx = HostIndex.load(p, preadv=inj, retry=FAST_RETRY)
    ids, _ = idx.search_batch(q, 5, L=24)
    assert np.array_equal(ids, ref)
    assert inj.stats()["injected_eio"] == 1
    assert idx.cache.counters.read_retries >= 1
    idx.close()


def test_retry_gives_up_on_persistent_eio(faulty_fixture):
    p, q, _, _, _ = faulty_fixture
    inj = FaultInjector(FaultPlan(seed=5, eio_rate=1.0))   # every attempt
    idx = HostIndex.load(p, preadv=inj,
                         retry=RetryPolicy(attempts=2, backoff_s=1e-4))
    with pytest.raises(OSError) as ei:
        idx.search_batch(q, 5, L=24)
    assert ei.value.errno == errno.EIO
    idx.close()


def test_transient_corruption_healed_by_one_reread(faulty_fixture):
    p, q, ref, ep_block, _ = faulty_fixture
    inj = FaultInjector(FaultPlan(seed=5, corrupt_blocks={ep_block: 1}))
    idx = HostIndex.load(p, preadv=inj, retry=FAST_RETRY)
    ids, _ = idx.search_batch(q, 5, L=24)
    assert np.array_equal(ids, ref)
    c = idx.cache.counters
    assert c.crc_mismatches == 1 and c.crc_rereads == 1
    assert inj.stats()["injected_corrupt"] == 1
    idx.close()


def test_persistent_corruption_raises_corrupt_block(faulty_fixture):
    p, q, _, ep_block, _ = faulty_fixture
    inj = FaultInjector(FaultPlan(seed=5, corrupt_blocks={ep_block: -1}))
    idx = HostIndex.load(p, preadv=inj, retry=FAST_RETRY)
    with pytest.raises(CorruptBlockError) as ei:
        idx.search_batch(q, 5, L=24)
    assert isinstance(ei.value, OSError) and ei.value.errno == errno.EIO
    assert idx.cache.counters.crc_mismatches >= 1
    idx.close()


def test_on_disk_bitrot_detected(faulty_fixture, tmp_path):
    """Actual bytes flipped ON STORAGE (not in flight): the reread reads
    the same bad bytes, so the mismatch is persistent."""
    import shutil
    p, q, _, ep_block, io_bytes = faulty_fixture
    p2 = str(tmp_path / "rot")
    shutil.copytree(p, p2)
    cbin = os.path.join(p2, "chunks.bin")
    with open(cbin, "r+b") as f:
        f.seek(ep_block * io_bytes + 7)
        b = f.read(1)
        f.seek(ep_block * io_bytes + 7)
        f.write(bytes([b[0] ^ 0x40]))
    idx = HostIndex.load(p2)
    with pytest.raises(CorruptBlockError):
        idx.search_batch(q, 5, L=24)
    idx.close()
    # verification off: the rot is served silently (the legacy behavior)
    idx = HostIndex.load(p2, verify_checksums=False)
    idx.search_batch(q, 5, L=24)
    idx.close()


# ---------------------------------------------------------------------------
# Checksummed format + crash-safe writes
# ---------------------------------------------------------------------------


def test_checksummed_format_v3(faulty_fixture):
    p, _, _, _, io_bytes = faulty_fixture
    meta = json.load(open(os.path.join(p, "meta.json")))
    assert meta["format_version"] == FORMAT_VERSION == 3
    assert meta["crc_algo"] in ("crc32", "crc32c")
    crc = np.load(os.path.join(p, CRC_SIDECAR))
    payload = np.fromfile(os.path.join(p, "chunks.bin"), np.uint8)
    assert payload.size % io_bytes == 0
    assert np.array_equal(crc, block_checksums(payload, io_bytes, _crc32))
    assert not os.path.exists(p + ".tmp")
    assert not os.path.exists(p + ".old")


def test_legacy_dir_loads_without_verification(faulty_fixture, tmp_path):
    import shutil
    p, q, ref, _, _ = faulty_fixture
    p2 = str(tmp_path / "legacy")
    shutil.copytree(p, p2)
    os.remove(os.path.join(p2, CRC_SIDECAR))
    mp = os.path.join(p2, "meta.json")
    meta = json.load(open(mp))
    meta.pop("format_version"), meta.pop("crc_algo")
    json.dump(meta, open(mp, "w"))
    idx = HostIndex.load(p2)                     # auto: no sidecar, no CRC
    assert idx.cache.block_crc is None
    ids, _ = idx.search_batch(q, 5, L=24)
    assert np.array_equal(ids, ref)
    idx.close()
    with pytest.raises(CorruptIndexError):       # explicit demand fails
        HostIndex.load(p2, verify_checksums=True)


@pytest.mark.parametrize("damage", ["missing_meta", "truncated_meta",
                                    "future_version", "missing_chunks",
                                    "truncated_chunks"])
def test_loader_rejects_damaged_dirs(faulty_fixture, tmp_path, damage):
    import shutil
    p = faulty_fixture[0]
    p2 = str(tmp_path / damage)
    shutil.copytree(p, p2)
    mp = os.path.join(p2, "meta.json")
    if damage == "missing_meta":
        os.remove(mp)
    elif damage == "truncated_meta":
        raw = open(mp, "rb").read()
        open(mp, "wb").write(raw[:len(raw) // 2])
    elif damage == "future_version":
        meta = json.load(open(mp))
        meta["format_version"] = FORMAT_VERSION + 1
        json.dump(meta, open(mp, "w"))
    elif damage == "missing_chunks":
        os.remove(os.path.join(p2, "chunks.bin"))
    elif damage == "truncated_chunks":
        cbin = os.path.join(p2, "chunks.bin")
        with open(cbin, "r+b") as f:
            f.truncate(os.path.getsize(cbin) // 2)
    with pytest.raises(CorruptIndexError):
        HostIndex.load(p2)


def test_write_index_overwrite_is_atomic(faulty_fixture, tmp_path):
    """Rewriting an existing dir must leave no .tmp/.old residue and the
    new index must be complete and verified."""
    from repro.core import pq
    from repro.core.vamana import build_vamana
    from repro.data.vectors import make_clustered
    import jax
    base = make_clustered(300, 16, seed=9)
    g = build_vamana(base, R=8, L=16, seed=0)
    cb = pq.train_codebooks(jax.random.PRNGKey(1), base, m=4, iters=4)
    cents, codes = np.asarray(cb.centroids), np.asarray(pq.encode(cb, base))
    p = str(tmp_path / "twice")
    for _ in range(2):
        write_index(p, vectors=base, graph=g, centroids=cents, codes=codes,
                    metric="l2", mode="aisaq")
    assert not os.path.exists(p + ".tmp") and not os.path.exists(p + ".old")
    idx = HostIndex.load(p)
    assert idx.cache.block_crc is not None
    idx.search_batch(base[:4], 3, L=16)
    idx.close()


def test_dynamic_mutation_keeps_crc_coherent(tmp_path):
    """In-place writes + appends re-anchor the sidecar: reload after flush
    verifies every block, and searches on the mutated index pass CRC."""
    from repro.core.index_config import IndexConfig
    from repro.core.build import build_index
    from repro.core.dynamic import DynamicHostIndex
    from repro.data.vectors import make_clustered
    base = make_clustered(500, 24, seed=7)
    cfg = IndexConfig(name="dyn", n_vectors=400, dim=24, R=12, pq_m=8,
                      build_L=24)
    p = str(tmp_path / "dyn")
    build_index(p, base[:400], cfg, mode="aisaq", seed=0)
    idx = DynamicHostIndex.load(p)
    assert idx.cache.block_crc is not None
    for i in range(30):
        idx.insert(base[400 + i])
    ids, _ = idx.search(base[410], 3, L=24)      # reads mutated blocks: CRC
    idx.flush()
    idx.close()
    idx2 = DynamicHostIndex.load(p)
    assert idx2.cache.block_crc is not None
    ids2, _ = idx2.search(base[410], 3, L=24)
    assert idx2.cache.counters.crc_mismatches == 0
    assert np.array_equal(ids, ids2)
    idx2.close()


# ---------------------------------------------------------------------------
# Oracle parity under injected faults
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("relabel", [False, True])
@pytest.mark.parametrize("adc_dtype", ["f32", "int8"])
@pytest.mark.parametrize("prefetch,pipeline", [(0, False), (4, False),
                                               (4, True)])
def test_faulty_parity_grid(small_corpus, built_graph, pq_artifacts,
                            tmp_path_factory, relabel, adc_dtype,
                            prefetch, pipeline):
    """Transient EIO + short reads absorbed by retries must leave every
    host configuration bit-identical to the fault-free scalar oracle."""
    base, q, _ = small_corpus
    cents, codes = pq_artifacts
    p = str(tmp_path_factory.mktemp("grid") / f"rl{int(relabel)}")
    write_index(p, vectors=base, graph=built_graph, centroids=cents,
                codes=codes, metric="l2", mode="aisaq", relabel=relabel)
    clean = HostIndex.load(p)
    ref, _ = search_batch_ref(clean, q, 5, L=24, adc_dtype=adc_dtype)
    clean.close()
    inj = FaultInjector(FaultPlan(seed=13, eio_rate=0.05,
                                  short_read_rate=0.05))
    idx = HostIndex.load(p, preadv=inj, retry=FAST_RETRY)
    ids, stats = search_batch(idx, q, 5, L=24, adc_dtype=adc_dtype,
                              prefetch=prefetch, pipeline=pipeline)
    assert np.array_equal(ids, ref)
    idx.close()


# ---------------------------------------------------------------------------
# Prefetch failure: degradation + waiter wakeup
# ---------------------------------------------------------------------------


def test_traversal_degrades_on_persistent_prefetch_failure(faulty_fixture):
    """Every background batch raising must flip the search to the serial
    demand path (SearchStats.degraded) without changing its answer."""
    p, q, ref, _, _ = faulty_fixture
    idx = HostIndex.load(p)

    def boom(batch, gap=0):
        raise RuntimeError("injected background failure")

    idx.cache._pf_read = boom
    ids, stats = search_batch(idx, q[:4], 5, L=24, prefetch=4, pipeline=True)
    assert np.array_equal(ids, ref[:4])
    # the joint batched traversal degrades as a whole; the flag (like
    # `pipelined`) is batch-level and reported on stats[0]
    assert stats[0].degraded == 1 and stats[0].pipelined == 1
    assert idx.cache.counters.prefetch_errors >= 1
    idx.close()


def test_stop_during_failed_prefetch_wakes_demand_waiters(faulty_fixture):
    """stop() racing a failing in-flight background read must not strand a
    demand fetch in its pending-wait: the waiter falls back to a direct
    read well before the bounded wait expires."""
    p, _, _, _, io_bytes = faulty_fixture
    fsize = os.path.getsize(os.path.join(p, "chunks.bin"))
    idx = HostIndex.load(p)
    cache = idx.cache
    real_pf_read = cache._pf_read

    def slow_boom(batch, gap=0):
        time.sleep(0.15)
        raise RuntimeError("injected slow background failure")

    cache._pf_read = slow_boom
    off = (min(4, fsize // io_bytes - 1)) * io_bytes
    assert cache.prefetch_async(np.asarray([off])) == 1
    expect = np.fromfile(os.path.join(p, "chunks.bin"), np.uint8,
                         count=io_bytes, offset=off)
    result = {}

    def demand():
        t0 = time.perf_counter()
        data, _, _ = cache.fetch(np.asarray([off]))
        result["wall"] = time.perf_counter() - t0
        result["data"] = data[0]

    t = threading.Thread(target=demand)
    t.start()
    time.sleep(0.02)                 # let the fetch enter its pending-wait
    cache.stop()                     # joins the worker; clears in-flight
    t.join(timeout=2.0)
    assert not t.is_alive(), "demand fetch stranded after stop()"
    assert np.array_equal(result["data"], expect)
    assert result["wall"] < 0.45     # woke before the bounded wait expired
    assert cache.counters.prefetch_errors == 1
    cache._pf_read = real_pf_read
    idx.close()


# ---------------------------------------------------------------------------
# Health-aware serving
# ---------------------------------------------------------------------------


def test_pool_circuit_breaker_lifecycle(tmp_path):
    pool = WarmIndexPool({"c": str(tmp_path)}, quarantine_after=3,
                         quarantine_cooldown_s=0.05,
                         quarantine_cooldown_max_s=0.5)
    pool.admit("c")                              # healthy passes
    for _ in range(2):
        pool.record_io_failure("c")
    pool.admit("c")                              # still below the threshold
    pool.record_io_failure("c")                  # third consecutive: opens
    assert pool.health("c")["state"] == "quarantined"
    with pytest.raises(CorpusUnhealthyError) as ei:
        pool.admit("c")
    assert ei.value.corpus == "c" and ei.value.retry_in_s >= 0
    time.sleep(0.06)
    pool.admit("c")                              # cooldown over: the probe
    assert pool.health("c")["state"] == "probing"
    with pytest.raises(CorpusUnhealthyError):    # only ONE probe admitted
        pool.admit("c")
    pool.record_io_failure("c")                  # probe failed: back off x2
    h = pool.health("c")
    assert h["state"] == "quarantined" and h["quarantines"] == 2
    assert h["cooldown_s"] == pytest.approx(0.1)
    time.sleep(0.11)
    pool.admit("c")
    pool.record_success("c")                     # probe succeeded: closed
    h = pool.health("c")
    assert h["state"] == "healthy" and h["recoveries"] == 1
    assert h["cooldown_s"] == pytest.approx(0.05)
    pool.admit("c")


def test_probe_timeout_rearms(tmp_path):
    pool = WarmIndexPool({"c": str(tmp_path)}, quarantine_after=1,
                         quarantine_cooldown_s=0.01, probe_timeout_s=0.05)
    pool.record_io_failure("c")
    time.sleep(0.02)
    pool.admit("c")                              # probe #1... then vanishes
    time.sleep(0.06)
    pool.admit("c")                              # stale probe re-armed
    pool.record_success("c")
    assert pool.health("c")["state"] == "healthy"


def test_service_quarantines_on_io_failures(faulty_fixture, tmp_path):
    """End-to-end: persistent corruption -> failed batches -> quarantine ->
    fail-fast submits -> half-open recovery once the region heals."""
    import shutil
    p, q, ref, ep_block, _ = faulty_fixture
    p2 = str(tmp_path / "served")
    shutil.copytree(p, p2)
    inj = FaultInjector(FaultPlan(seed=5, corrupt_blocks={ep_block: 4}))
    pool = WarmIndexPool({"c": p2}, preadv_factory=lambda n: inj,
                         quarantine_after=2, quarantine_cooldown_s=0.2)
    svc = RetrievalService(pool, num_workers=1, max_batch=4, L=24, w=4)
    errs = 0
    for i in range(2):                           # 2 failures x 2 reads each
        with pytest.raises(OSError):
            svc.submit_wait(q[0], corpus="c", k=5, timeout=10.0)
        errs += 1
    assert pool.health("c")["state"] == "quarantined"
    with pytest.raises(CorpusUnhealthyError):
        svc.submit_wait(q[0], corpus="c", k=5, timeout=10.0)
    assert svc.stats()["corpora"]["c"]["unhealthy_rejected"] == 1
    time.sleep(0.25)                             # cooldown; block healed
    r = svc.submit_wait(q[0], corpus="c", k=5, timeout=10.0)
    assert np.array_equal(np.asarray(r.result), ref[0, :5])
    h = pool.health("c")
    assert h["state"] == "healthy" and h["recoveries"] == 1
    st = svc.stats()["corpora"]["c"]
    assert st["errors"] == errs and st["completed"] == 1
    svc.stop()
    pool.close()


def test_request_deadline_expires_unserved(faulty_fixture):
    """A request whose deadline passes while queued is dropped at batch
    assembly (TimeoutError + `expired` telemetry), not served into the
    void and counted completed."""
    p, q, _, _, _ = faulty_fixture
    pool = WarmIndexPool({"c": p})

    def stall(idx, Q, k):
        time.sleep(0.3)
        return np.zeros((Q.shape[0], k), np.int64)

    svc = RetrievalService(pool, num_workers=1, max_batch=1,
                           max_wait_ms=0.1, search_fn=stall)
    a = svc.submit(q[0], corpus="c", k=5)        # occupies the one worker
    time.sleep(0.05)
    b = svc.submit(q[1], corpus="c", k=5, deadline_s=0.05)
    assert b.event.wait(5.0)
    assert isinstance(b.error, TimeoutError)
    a.event.wait(5.0)
    assert a.error is None
    st = svc.stats()
    assert st["corpora"]["c"]["expired"] == 1
    assert st["corpora"]["c"]["completed"] == 1
    assert st["total_expired"] == 1
    svc.stop()
    pool.close()
