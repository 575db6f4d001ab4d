"""Dynamic insertion / deletion / filtered search (beyond-paper: the
capabilities the paper's conclusion says AiSAQ enables), plus the
crash-safety layer: journaled inserts, recovery, crash-safe flush,
compaction, and search-under-mutation."""
import os
import shutil
import threading

import numpy as np
import pytest

from repro.core.index_config import IndexConfig
from repro.core import pq
from repro.core.build import build_index
from repro.core.dynamic import DynamicHostIndex, DynamicIndexError
from repro.core.faults import CrashPoint, KillSwitch
from repro.core.index_io import CorruptIndexError, HostIndex, recall_at
from repro.data.vectors import make_clustered, make_queries


@pytest.fixture()
def dyn_index(tmp_path):
    base = make_clustered(900, 48, seed=7)
    cfg = IndexConfig(name="dyn", n_vectors=700, dim=48, R=16, pq_m=12,
                      build_L=32)
    p = str(tmp_path / "dyn")
    build_index(p, base[:700], cfg, mode="aisaq", seed=0)
    return p, base


def test_insert_makes_new_vectors_findable(dyn_index):
    p, base = dyn_index
    idx = DynamicHostIndex.load(p)
    new_ids = [idx.insert(base[700 + i]) for i in range(60)]
    assert new_ids == list(range(700, 760))
    # query exactly at inserted points: each must find itself at rank 1
    hits = 0
    for i in range(0, 60, 5):
        ids, _ = idx.search(base[700 + i].astype(np.float32), 1, L=48)
        hits += int(ids[0] == 700 + i)
    assert hits >= 10  # ≥ 10/12 self-recall
    # and recall over the GROWN corpus stays high
    q = make_queries(10, base[:760], seed=9)
    gt = np.asarray(pq.groundtruth(q, base[:760], 5))
    got = np.stack([idx.search(q[i], 5, L=48)[0] for i in range(10)])
    assert recall_at(got, gt, 5) >= 0.7
    idx.flush()
    idx.close()


def test_insert_survives_reload(dyn_index):
    p, base = dyn_index
    idx = DynamicHostIndex.load(p)
    nid = idx.insert(base[700])
    idx.flush()
    idx.close()
    idx2 = DynamicHostIndex.load(p)
    assert idx2.meta["n"] == 701
    ids, _ = idx2.search(base[700].astype(np.float32), 1, L=48)
    assert int(ids[0]) == nid
    idx2.close()


def test_delete_tombstones(dyn_index):
    p, base = dyn_index
    idx = DynamicHostIndex.load(p)
    q = base[5].astype(np.float32)
    ids, _ = idx.search(q, 3, L=48)
    victim = int(ids[0])
    idx.delete(victim)
    ids2, _ = idx.search(q, 3, L=48)
    assert victim not in set(int(i) for i in ids2)
    assert len(ids2) == 3              # widened search refills the pool
    idx.flush()
    idx.close()
    idx3 = DynamicHostIndex.load(p)    # tombstones persist
    ids4, _ = idx3.search(q, 3, L=48)
    assert victim not in set(int(i) for i in ids4)
    idx3.close()


def test_filtered_search(dyn_index):
    p, base = dyn_index
    idx = DynamicHostIndex.load(p)
    q = base[10].astype(np.float32)
    even = lambda i: i % 2 == 0
    ids, _ = idx.search(q, 5, L=48, predicate=even)
    assert all(int(i) % 2 == 0 for i in ids)
    assert len(ids) == 5
    idx.close()


# -- crash-safety layer ------------------------------------------------------
# a small pristine build, copied per test (crash drills mutate the dir)

@pytest.fixture(scope="module")
def small_built(tmp_path_factory):
    base = make_clustered(260, 16, seed=3)
    cfg = IndexConfig(name="small", n_vectors=200, dim=16, R=8, pq_m=8,
                      build_L=24)
    p = str(tmp_path_factory.mktemp("small") / "idx")
    build_index(p, base[:200], cfg, mode="aisaq", seed=0)
    return p, base


def _copy(small_built, tmp_path):
    src, base = small_built
    dst = str(tmp_path / "work")
    shutil.copytree(src, dst)
    return dst, base


def test_load_rejects_non_aisaq_mode(tmp_path):
    base = make_clustered(120, 16, seed=5)
    cfg = IndexConfig(name="dk", n_vectors=120, dim=16, R=8, pq_m=8,
                      build_L=24)
    p = str(tmp_path / "dk")
    build_index(p, base, cfg, mode="diskann", seed=0)
    with pytest.raises(DynamicIndexError, match="aisaq"):
        DynamicHostIndex.load(p)


def test_static_load_refuses_pending_journal(small_built, tmp_path):
    p, base = _copy(small_built, tmp_path)
    with open(os.path.join(p, "wal.log"), "wb") as f:
        f.write(b"\x01" * 7)             # garbage = torn unrecovered tail
    with pytest.raises(CorruptIndexError, match="journal"):
        HostIndex.load(p)
    # the dynamic loader recovers (truncates the torn tail) and from then
    # on the dir loads statically again
    idx = DynamicHostIndex.load(p)
    assert idx.recovery["journaled"] == 0 and idx.recovery["torn"]
    idx.close()
    HostIndex.load(p).close()


def test_insert_is_journaled_and_commit_clears_nothing(small_built,
                                                       tmp_path):
    p, base = _copy(small_built, tmp_path)
    idx = DynamicHostIndex.load(p)
    idx.insert(base[200])
    # journal holds BEGIN+COMMIT until the flush checkpoint truncates it
    assert idx.wal.size > 0
    idx.flush()
    assert idx.wal.size == 0
    idx.close()


def test_recovery_after_kill_at_every_point(small_built, tmp_path):
    """Mini crash drill: kill the writer at EVERY injection point of one
    insert; every crash must recover to a consistent index equal to the
    pre- or post-insert oracle (the benchmark scales this to a multi-op
    workload)."""
    src, base = small_built
    vec = base[205]
    # enumeration pass: count the ticks of one full insert
    p0, _ = _copy(small_built, tmp_path / "enum")
    ks = KillSwitch()
    idx = DynamicHostIndex.load(p0, kill=ks)
    idx.insert(vec)
    idx.flush()
    idx.close()
    total = ks.count
    assert total > 10                    # wal + chunks + sync + flush ticks
    for at in range(1, total + 1):
        d = str(tmp_path / f"k{at}")
        shutil.copytree(src, d)
        k = KillSwitch(at=at)
        h = DynamicHostIndex.load(d, kill=k)
        committed = False
        try:
            h.insert(vec)
            committed = True
            h.flush()
            committed = True
        except CrashPoint:
            pass
        h.abandon()                       # nothing in RAM survives
        r = DynamicHostIndex.load(d)      # recovery runs here
        n = r.meta["n"]
        assert n in (200, 201), f"at={at}: n={n}"
        if committed:
            assert n == 201, f"at={at}: committed insert lost"
        # graph consistency: every edge of every node is in-range
        for node in range(n):
            _, nbrs, _ = r._read_node(node)
            live = nbrs[nbrs >= 0]
            assert (live < n).all(), f"at={at}: dangling edge"
        # the index is searchable and CRC-clean
        ids, _ = r.search(vec.astype(np.float32), 3, L=24)
        assert len(ids) == 3
        if n == 201:                      # rolled forward: findable
            ids1, _ = r.search(vec.astype(np.float32), 1, L=24)
            assert int(ids1[0]) == 200, f"at={at}"
        assert r.cache.counters.crc_mismatches == 0
        assert r.wal.size == 0            # checkpointed
        r.close()
        shutil.rmtree(d)


def test_journaled_delete_survives_crash(small_built, tmp_path):
    p, base = _copy(small_built, tmp_path)
    idx = DynamicHostIndex.load(p)
    idx.delete(7)
    idx.abandon()                         # crash before any flush
    r = DynamicHostIndex.load(p)
    assert 7 in r.tombstones
    ids, _ = r.search(base[7].astype(np.float32), 3, L=24)
    assert 7 not in set(int(i) for i in ids)
    r.close()


def test_flush_is_crash_atomic(small_built, tmp_path):
    """Killing flush between any two stages must leave a recoverable dir:
    the journal re-derives whatever the flush had not yet persisted."""
    src, base = small_built
    for stage in range(1, 7):             # flush has 6 tick points
        d = str(tmp_path / f"f{stage}")
        shutil.copytree(src, d)
        h = DynamicHostIndex.load(d)
        h.insert(base[210])
        h.delete(3)
        h.kill = KillSwitch(at=stage)     # arm AFTER the insert
        with pytest.raises(CrashPoint):
            h.flush()
        h.abandon()
        r = DynamicHostIndex.load(d)
        assert r.meta["n"] == 201
        assert 3 in r.tombstones
        ids, _ = r.search(base[210].astype(np.float32), 1, L=24)
        assert int(ids[0]) == 200
        assert r.wal.size == 0
        r.close()
        shutil.rmtree(d)


def test_compaction_reclaims_and_preserves_labels(small_built, tmp_path):
    p, base = _copy(small_built, tmp_path)
    idx = DynamicHostIndex.load(p)
    new_labels = [idx.insert(base[200 + i]) for i in range(8)]
    assert new_labels == list(range(200, 208))
    idx.delete(5)
    idx.delete(new_labels[0])             # delete one old, one new
    dst = str(tmp_path / "v2")
    meta = idx.compact(dst, relabel=True)
    idx.close()
    assert meta["n"] == 200 + 8 - 2
    c = DynamicHostIndex.load(dst)        # compacted dirs stay dynamic
    assert c.meta["n"] == 206
    # tombstoned labels are GONE (not just filtered)
    assert 5 not in set(int(l) for l in c.new_to_old)
    # surviving inserted labels still findable under their OLD labels
    for i in (1, 3, 7):
        ids, _ = c.search(base[200 + i].astype(np.float32), 1, L=24)
        assert int(ids[0]) == 200 + i
    # and ingest continues on the compacted dir: labels keep counting up
    nxt = c.insert(base[220])
    assert nxt == 208                     # next_label survived compaction
    ids, _ = c.search(base[220].astype(np.float32), 1, L=24)
    assert int(ids[0]) == nxt
    c.flush()
    c.close()


def test_concurrent_search_during_insert(small_built, tmp_path):
    """Readers race the writer: no torn chunk, no out-of-range result,
    no CRC mismatch — the RW lock + n-snapshot clamp contract."""
    p, base = _copy(small_built, tmp_path)
    idx = DynamicHostIndex.load(p)
    stop = threading.Event()
    errors = []
    q = make_queries(4, base[:200], seed=1).astype(np.float32)

    def reader():
        rng = np.random.default_rng(0)
        while not stop.is_set():
            try:
                n_snap = int(idx.meta["n"])
                ids, _ = idx.search(q[rng.integers(0, 4)], 5, L=24)
                for i in ids:
                    # labels == ids on this dir; results must never point
                    # past the n the search could have seen
                    assert 0 <= int(i) < idx.n + 1, int(i)
                assert len(ids) == 5
                assert n_snap <= int(idx.meta["n"])
            except Exception as e:        # pragma: no cover - failure path
                errors.append(e)
                return

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for i in range(40):
            idx.insert(base[200 + (i % 50)])
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not errors, errors[0]
    assert idx.cache.counters.crc_mismatches == 0
    assert idx.meta["n"] == 240
    idx.flush()
    idx.close()
    # post-race reload is CRC-clean and consistent
    r = DynamicHostIndex.load(p)
    assert r.recovery["journaled"] == 0
    ids, _ = r.search(base[201].astype(np.float32), 1, L=24)
    assert len(ids) == 1
    r.close()


def test_search_takes_the_read_lock_once(small_built, tmp_path):
    """The lock is writer-priority: a nested read acquire would queue behind
    a waiting insert that waits for this very reader."""
    from contextlib import contextmanager
    p, base = _copy(small_built, tmp_path)
    idx = DynamicHostIndex.load(p)
    real, depth = idx._rw.read, [0, 0]

    @contextmanager
    def counted():
        depth[0] += 1
        depth[1] = max(depth)
        try:
            with real():
                yield
        finally:
            depth[0] -= 1

    idx._rw.read = counted
    idx.delete(3)                     # tombstones force the widened search
    ids, _ = idx.search(base[3].astype(np.float32), 5, L=24)
    assert len(ids) == 5 and 3 not in ids.tolist()
    assert depth[1] == 1
    idx.close()
