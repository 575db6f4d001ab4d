"""Crash-safe streaming-ingest drill: the write-path acceptance benchmark.

Three phases over a dynamic (aisaq-mode) index:

  1. CONCURRENT INGEST — one writer streams inserts into a live index
     while reader threads search it: sustained insert QPS and search QPS,
     zero reader errors, zero CRC mismatches, every post-ingest result
     consistent (no dangling edges, all inserted vectors findable).
  2. COMPACTION SWAP — a `RetrievalService` keeps serving corpus v1 while
     a sibling copy ingests + deletes, compacts into v2 (tombstone
     reclaim + relabel, atomic publish), and `service.swap` switches the
     pool zero-downtime: every concurrent request completes (0 dropped),
     and recall is measured before and after the swap.
  3. KILL-AT-EVERY-OFFSET — a seeded `KillSwitch` crashes a scripted
     insert/delete/flush workload at EVERY durability-relevant write step
     (journal frame halves, chunk-write halves, data sync, each flush
     stage).  After every single crash, recovery must land on a CRC-clean
     index with no dangling edges whose search results are BIT-IDENTICAL
     to the matching pre-/post-op oracle snapshot — 100% recovery.

    PYTHONPATH=src:. python benchmarks/bench_ingest.py          # full
    PYTHONPATH=src:. python benchmarks/bench_ingest.py --quick  # CI smoke
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from repro.core.index_config import IndexConfig
from repro.core import pq
from repro.core.build import build_index
from repro.core.dynamic import DynamicHostIndex
from repro.core.faults import CrashPoint, KillSwitch
from repro.core.index_io import recall_at
from repro.data.vectors import make_clustered, make_queries
from repro.serving.pool import WarmIndexPool
from repro.serving.service import RetrievalService

SCHEMA_VERSION = 1

# full-mode workload sizes (quick shrinks everything)
FULL = dict(n0=2000, dim=32, R=16, pq_m=8, build_L=32, n_insert=150,
            n_readers=3, n_queries=24, drill_n0=400, drill_inserts=4,
            drill_deletes=2, swap_inserts=60, swap_deletes=8,
            swap_clients=4)
QUICK = dict(n0=300, dim=16, R=8, pq_m=8, build_L=24, n_insert=40,
             n_readers=2, n_queries=8, drill_n0=200, drill_inserts=2,
             drill_deletes=1, swap_inserts=16, swap_deletes=3,
             swap_clients=2)
K, L, W = 5, 32, 4


def _build(path: str, base: np.ndarray, p: dict, n: int, seed: int = 0):
    cfg = IndexConfig(name="ingest", n_vectors=n, dim=p["dim"], R=p["R"],
                      pq_m=p["pq_m"], build_L=p["build_L"])
    build_index(path, base[:n], cfg, mode="aisaq", seed=seed)


# ---------------------------------------------------------------------------
# phase 1: concurrent ingest
# ---------------------------------------------------------------------------


def bench_concurrent_ingest(td: str, base: np.ndarray, p: dict) -> dict:
    root = os.path.join(td, "ingest")
    _build(root, base, p, p["n0"])
    idx = DynamicHostIndex.load(root)
    n0, n_ins = p["n0"], p["n_insert"]
    queries = make_queries(p["n_queries"], base[:n0], seed=5
                           ).astype(np.float32)
    stop = threading.Event()
    errors: list = []
    searches = [0] * p["n_readers"]

    def reader(slot: int):
        rng = np.random.default_rng(slot)
        while not stop.is_set():
            try:
                ids, _ = idx.search(queries[rng.integers(0, len(queries))],
                                    K, L=L, w=W)
                if len(ids) != K:
                    raise AssertionError(f"short result: {len(ids)}")
                searches[slot] += 1
            except Exception as e:       # noqa: BLE001 — accounting drill
                errors.append(repr(e))
                return

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(p["n_readers"])]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    try:
        for i in range(n_ins):
            idx.insert(base[n0 + i])
    finally:
        ingest_wall = time.perf_counter() - t0
        stop.set()
        for t in threads:
            t.join(timeout=30)
    # post-ingest consistency: every inserted vector self-findable
    self_hits = 0
    probe = range(0, n_ins, max(1, n_ins // 20))
    for i in probe:
        ids, _ = idx.search(base[n0 + i].astype(np.float32), 1, L=L)
        self_hits += int(len(ids) and int(ids[0]) == n0 + i)
    dangling = 0
    for node in range(idx.n):
        _, nbrs, _ = idx._read_node(node)
        live = nbrs[nbrs >= 0]
        dangling += int((live >= idx.n).any())
    crc_mismatches = int(idx.cache.counters.crc_mismatches)
    idx.flush()
    idx.close()
    return dict(
        n_inserted=n_ins,
        insert_qps=n_ins / ingest_wall,
        search_qps=sum(searches) / ingest_wall,
        concurrent_searches=int(sum(searches)),
        reader_errors=errors,
        self_recall=self_hits / len(list(probe)),
        dangling_edges=dangling,
        crc_mismatches=crc_mismatches)


# ---------------------------------------------------------------------------
# phase 2: zero-downtime compaction swap
# ---------------------------------------------------------------------------


def bench_compaction_swap(td: str, base: np.ndarray, p: dict) -> dict:
    v1 = os.path.join(td, "swap_v1")
    _build(v1, base, p, p["n0"])
    n0, n_ins, n_del = p["n0"], p["swap_inserts"], p["swap_deletes"]
    deleted = list(range(0, n_del * 7, 7))
    # the ingest runs on a sibling COPY so the served v1 bytes never move
    work = os.path.join(td, "swap_work")
    shutil.copytree(v1, work)
    widx = DynamicHostIndex.load(work)
    for i in range(n_ins):
        widx.insert(base[n0 + i])
    for lbl in deleted:
        widx.delete(lbl)
    widx.flush()
    v2 = os.path.join(td, "swap_v2")
    widx.compact(v2, relabel=True)
    widx.close()
    # serve v1 under continuous load, swap to v2 mid-stream
    pool = WarmIndexPool({"live": v1}, cache_bytes=4 << 20)
    svc = RetrievalService(pool, num_workers=2, max_batch=8,
                           max_wait_ms=1.0, L=L, w=W)
    queries = make_queries(p["n_queries"], base[:n0], seed=9
                           ).astype(np.float32)
    stop = threading.Event()
    dropped: list = []
    completed = [0] * p["swap_clients"]

    def client(slot: int):
        rng = np.random.default_rng(100 + slot)
        while not stop.is_set():
            try:
                r = svc.submit_wait(queries[rng.integers(0, len(queries))],
                                    corpus="live", k=K, timeout=30.0)
                if len(r.result) != K:
                    raise AssertionError("short result")
                completed[slot] += 1
            except Exception as e:       # noqa: BLE001 — accounting drill
                dropped.append(repr(e))
                return

    # recall baseline on v1 (pre-swap truth: the original corpus)
    gt1 = np.asarray(pq.groundtruth(queries, base[:n0], K))
    got1 = np.stack([svc.submit_wait(q, corpus="live", k=K).result
                     for q in queries])
    recall_before = float(recall_at(got1, gt1, K))
    clients = [threading.Thread(target=client, args=(i,))
               for i in range(p["swap_clients"])]
    for t in clients:
        t.start()
    time.sleep(0.3)                      # let the stream establish
    swap_load_s = svc.swap("live", v2)
    time.sleep(0.3)                      # serve past the switch point
    stop.set()
    for t in clients:
        t.join(timeout=30)
    # recall on v2 (post-swap truth: grown corpus minus the deleted rows)
    live_rows = np.asarray([i for i in range(n0 + n_ins)
                            if i not in set(deleted)])
    corpus2 = base[live_rows]
    gt2 = live_rows[np.asarray(pq.groundtruth(queries, corpus2, K))]
    got2 = np.stack([svc.submit_wait(q, corpus="live", k=K).result
                     for q in queries])
    recall_after = float(recall_at(got2, gt2, K))
    deleted_served = int(sum(int(x) in set(deleted)
                             for row in got2 for x in row))
    st = pool.stats()
    svc.stop()
    pool.close()
    return dict(
        swap_load_s=swap_load_s,
        completed_during_drill=int(sum(completed)),
        dropped=dropped,
        recall_before_swap=recall_before,
        recall_after_swap=recall_after,
        deleted_rows_served_after_swap=deleted_served,
        pool=dict(swaps=st["swaps"], retired_at_snapshot=st["retired"]))


# ---------------------------------------------------------------------------
# phase 3: kill-at-every-offset crash drill
# ---------------------------------------------------------------------------


def _workload(p: dict, base: np.ndarray):
    """The scripted mutation sequence: each op is (kind, payload)."""
    n0 = p["drill_n0"]
    ops = [("insert", n0 + i) for i in range(p["drill_inserts"])]
    ops += [("delete", 11 * (j + 1)) for j in range(p["drill_deletes"])]
    ops += [("flush", None)]
    return ops


def _apply(idx: DynamicHostIndex, op, base: np.ndarray):
    kind, arg = op
    if kind == "insert":
        idx.insert(base[arg])
    elif kind == "delete":
        idx.delete(arg)
    else:
        idx.flush()


def _state_key(idx: DynamicHostIndex):
    return (int(idx.meta["n"]), frozenset(idx.tombstones))


def _oracle_snapshots(pristine: str, td: str, ops, base, queries):
    """Reference states: after each op PREFIX (flushed), the search
    results a recovered index must reproduce bit-for-bit."""
    oracles = {}
    for j in range(len(ops) + 1):
        d = os.path.join(td, f"oracle{j}")
        shutil.copytree(pristine, d)
        idx = DynamicHostIndex.load(d)
        for op in ops[:j]:
            _apply(idx, op, base)
        idx.flush()
        key = _state_key(idx)
        if key not in oracles:
            ids = np.stack([idx.search(q, K, L=L, w=W)[0]
                            for q in queries])
            oracles[key] = dict(after_ops=j, ids=ids)
        idx.close()
    return oracles


def bench_crash_drill(td: str, base: np.ndarray, p: dict) -> dict:
    pristine = os.path.join(td, "drill_pristine")
    _build(pristine, base, p, p["drill_n0"], seed=1)
    ops = _workload(p, base)
    queries = make_queries(6, base[:p["drill_n0"]], seed=3
                           ).astype(np.float32)
    oracles = _oracle_snapshots(pristine, td, ops, base, queries)
    # enumeration pass: count every crash point in the whole workload
    enum_dir = os.path.join(td, "drill_enum")
    shutil.copytree(pristine, enum_dir)
    ks = KillSwitch()
    idx = DynamicHostIndex.load(enum_dir, kill=ks)
    for op in ops:
        _apply(idx, op, base)
    idx.close()
    total = ks.count
    failures: list = []
    recovered_states: dict = {}
    rolled_back = rolled_forward = 0
    t0 = time.perf_counter()
    for at in range(1, total + 1):
        d = os.path.join(td, "drill_case")
        shutil.rmtree(d, ignore_errors=True)
        shutil.copytree(pristine, d)
        k = KillSwitch(at=at)
        h = DynamicHostIndex.load(d, kill=k)
        crash_label = None
        try:
            for op in ops:
                _apply(h, op, base)
        except CrashPoint as e:
            crash_label = e.label
        h.abandon()
        try:
            r = DynamicHostIndex.load(d)
        except Exception as e:           # noqa: BLE001 — the drill verdict
            failures.append(f"at={at} ({crash_label}): reload failed: {e!r}")
            continue
        rolled_back += r.recovery["rolled_back"]
        rolled_forward += r.recovery["rolled_forward"]
        key = _state_key(r)
        recovered_states[key] = recovered_states.get(key, 0) + 1
        if key not in oracles:
            failures.append(f"at={at} ({crash_label}): recovered to "
                            f"non-oracle state {key}")
            r.close()
            continue
        bad = False
        if r.wal.size != 0:
            failures.append(f"at={at}: journal not checkpointed")
            bad = True
        for node in range(r.n):          # no dangling edges anywhere
            _, nbrs, _ = r._read_node(node)
            live = nbrs[nbrs >= 0]
            if (live >= r.n).any():
                failures.append(f"at={at} ({crash_label}): dangling edge "
                                f"at node {node}")
                bad = True
                break
        if not bad:
            ids = np.stack([r.search(q, K, L=L, w=W)[0] for q in queries])
            if not np.array_equal(ids, oracles[key]["ids"]):
                failures.append(f"at={at} ({crash_label}): search differs "
                                f"from oracle after ops "
                                f"{oracles[key]['after_ops']}")
            if r.cache.counters.crc_mismatches:
                failures.append(f"at={at} ({crash_label}): CRC mismatch "
                                "on recovered index")
        r.close()
    return dict(
        crash_points=total,
        wall_s=time.perf_counter() - t0,
        ops=len(ops),
        recovered_ok=total - len(failures),
        recovery_rate=(total - len(failures)) / max(total, 1),
        rolled_back_total=rolled_back,
        rolled_forward_total=rolled_forward,
        distinct_recovered_states=len(recovered_states),
        failures=failures)


# ---------------------------------------------------------------------------
# verdicts + report
# ---------------------------------------------------------------------------


def drill_failures(rep: dict) -> list:
    fails = []
    ing = rep["concurrent_ingest"]
    if ing["reader_errors"]:
        fails.append(f"ingest readers errored: {ing['reader_errors'][:3]}")
    if ing["crc_mismatches"]:
        fails.append(f"{ing['crc_mismatches']} CRC mismatches under ingest")
    if ing["dangling_edges"]:
        fails.append(f"{ing['dangling_edges']} dangling edges after ingest")
    if ing["self_recall"] < 0.8:
        fails.append(f"post-ingest self recall {ing['self_recall']:.2f}")
    sw = rep["compaction_swap"]
    if sw["dropped"]:
        fails.append(f"swap dropped requests: {sw['dropped'][:3]}")
    if sw["deleted_rows_served_after_swap"]:
        fails.append(f"{sw['deleted_rows_served_after_swap']} tombstoned "
                     "rows served after the swap")
    if sw["recall_after_swap"] < sw["recall_before_swap"] - 0.15:
        fails.append(f"recall collapsed across the swap: "
                     f"{sw['recall_before_swap']:.3f} -> "
                     f"{sw['recall_after_swap']:.3f}")
    if sw["pool"]["swaps"] != 1:
        fails.append("pool recorded no swap")
    cd = rep["crash_drill"]
    if cd["recovery_rate"] < 1.0:
        fails.append(f"crash drill recovered {cd['recovered_ok']}/"
                     f"{cd['crash_points']}: {cd['failures'][:5]}")
    if cd["distinct_recovered_states"] < 2:
        fails.append("crash drill never exercised distinct oracle states")
    return fails


def run_all(p: dict, tag: str) -> dict:
    from benchmarks import common as C
    base = make_clustered(p["n0"] + p["n_insert"] + 64, p["dim"], seed=2)
    rep = {"schema_version": SCHEMA_VERSION, "mode": tag,
           "workload": dict(p, k=K, L=L, w=W),
           "provenance": C.provenance("ingest")}
    with tempfile.TemporaryDirectory() as td:
        rep["concurrent_ingest"] = bench_concurrent_ingest(td, base, p)
        rep["compaction_swap"] = bench_compaction_swap(td, base, p)
        rep["crash_drill"] = bench_crash_drill(td, base, p)
    rep["failures"] = drill_failures(rep)
    rep["headline"] = dict(
        insert_qps=rep["concurrent_ingest"]["insert_qps"],
        concurrent_search_qps=rep["concurrent_ingest"]["search_qps"],
        swap_zero_dropped=not rep["compaction_swap"]["dropped"],
        recall_before_swap=rep["compaction_swap"]["recall_before_swap"],
        recall_after_swap=rep["compaction_swap"]["recall_after_swap"],
        crash_points=rep["crash_drill"]["crash_points"],
        crash_recovery_rate=rep["crash_drill"]["recovery_rate"],
        all_invariants_hold=not rep["failures"])
    return rep


def all_benchmarks():
    rep = run_all(FULL, "full")
    dest = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "BENCH_ingest.json"))
    with open(dest, "w") as f:
        json.dump(rep, f, indent=1)
    print(f"[bench_ingest] wrote {dest}")
    if rep["failures"]:
        for msg in rep["failures"]:
            print(f"[bench_ingest] FAIL: {msg}", file=sys.stderr)
        raise SystemExit(1)
    h = rep["headline"]
    return [
        ("ingest_insert_qps", h["insert_qps"],
         f"search_qps={h['concurrent_search_qps']:.0f}"),
        ("ingest_swap_zero_dropped", float(h["swap_zero_dropped"]),
         f"recall={h['recall_before_swap']:.3f}->"
         f"{h['recall_after_swap']:.3f}"),
        ("ingest_crash_recovery_rate", h["crash_recovery_rate"],
         f"points={h['crash_points']}"),
    ]


def quick_smoke() -> int:
    t0 = time.perf_counter()
    rep = run_all(QUICK, "quick")
    wall = time.perf_counter() - t0
    dest = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "BENCH_ingest.json"))
    with open(dest, "w") as f:
        json.dump(rep, f, indent=1)
    if rep["failures"]:
        for msg in rep["failures"]:
            print(f"[bench_ingest --quick] FAIL: {msg}", file=sys.stderr)
        return 1
    h = rep["headline"]
    print(f"[bench_ingest --quick] all ingest invariants hold ({wall:.1f}s):"
          f" insert_qps={h['insert_qps']:.0f}"
          f" search_qps={h['concurrent_search_qps']:.0f}"
          f" crash_points={h['crash_points']}"
          f" recovery={h['crash_recovery_rate']:.0%}"
          f" swap_recall={h['recall_before_swap']:.2f}->"
          f"{h['recall_after_swap']:.2f}")
    return 0


if __name__ == "__main__":
    if "--quick" in sys.argv[1:]:
        sys.exit(quick_smoke())
    for name, val, extra in all_benchmarks():
        print(f"{name},{val:.3f},{extra}")
