"""Dynamic index maintenance: crash-safe in-place insertion, tombstone
deletion, and background compaction.

The paper's conclusion: "[near-zero load time] will enable LLMs with RAG to
employ more simple index addition or filter search algorithms." This module
implements exactly that enablement on the host backend:

  * insert(vec): FreshDiskANN-style — greedy-search for neighbor candidates,
    RobustPrune, APPEND a new node chunk to chunks.bin, patch the reverse
    edges' chunks in place (pwrite). AiSAQ's inline codes mean patching a
    neighbor's chunk also writes the new node's PQ code into it — the
    placement invariant is preserved under mutation.
  * delete(label): tombstone — removed from results and from future
    traversal expansion targets; space reclaimed by ``compact``.
  * filtered search: per-query predicate over result labels — candidates
    failing the filter still ROUTE (graph stays navigable) but never enter
    the re-rank pool.
  * compact(dst): re-pack the live nodes (tombstone reclaim + optional
    graph-locality relabel) into a sibling version directory published
    with ``write_index``'s atomic recipe — the input to
    ``WarmIndexPool.swap``'s zero-downtime version switch.

Crash-safety (the write-path twin of the PR-6 read-path layer): every
mutation is journaled in ``core.wal`` BEFORE it touches ``chunks.bin`` —
an insert's intent record carries the new id, its code, the chosen
neighbors, and the PRE-IMAGES of every reverse-edge chunk it will patch;
a commit record lands only after the data writes are fdatasynced.
``load`` recovers: the journal is scanned (truncated at the first torn
frame), the uncommitted tail insert is rolled back from its pre-images,
committed-but-unflushed inserts are rolled forward (``meta["n"]``,
pending codes, and labels re-derived), journaled deletes re-applied, the
CRC sidecar re-anchored, and a full durable flush checkpoints the result
and empties the journal.  Every crash point lands on a state equal to a
pre- or post-insert oracle — ``benchmarks/bench_ingest.py`` proves it by
killing the writer at every journal offset.

Concurrency: one writer (``insert``/``delete``/``flush``/``compact`` are
serialized by an internal mutex) and any number of searching readers.  A
writer-priority RW lock makes each chunk write atomic with respect to
in-process readers (no torn chunk is ever observed), and the traversal
engine clamps neighbor ids to its ``meta["n"]`` snapshot, so an edge
patched toward a node a search has not yet admitted is simply invisible
to it — searches always see a consistent pre- or post-insert graph.

Label discipline: a relabeled (graph-locality packed) directory stores
nodes in NEW-id space with an external-label map.  Insertion appends the
new node at the tail (page-locality order: fresh nodes share fresh
blocks) and extends the label map; ``compact`` re-packs with explicit
labels (``write_index(labels=...)``) so external labels survive
tombstone reclaim and re-relabeling.
"""
from __future__ import annotations

import json
import os
import threading
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Set

import numpy as np

from repro.core.adc import np_adc, np_build_lut  # noqa: F401  (public
# surface of this module since the monolith era; kept through the split)
from repro.core.chunk_layout import B_NUM
from repro.core.index_io import (HostIndex, _atomic_json, _atomic_npy,
                                 write_index)
from repro.core.integrity import CRC_SIDECAR, resolve_crc
from repro.core.traversal import SearchStats  # noqa: F401
from repro.core import wal as _wal

__all__ = ["DynamicHostIndex", "DynamicIndexError"]


class DynamicIndexError(RuntimeError):
    """A directory or argument unusable for dynamic (mutating) operation.
    Typed — never ``assert`` — so the refusal survives ``python -O``."""


class _RWLock:
    """Writer-priority readers-writer lock.

    Readers (searches) hold it across a whole traversal; the writer holds
    it per chunk write, so a reader can never observe a torn chunk.
    Writer priority keeps a stream of searches from starving the ingest
    path."""

    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writer = False
        self._writers_waiting = 0

    @contextmanager
    def read(self):
        with self._cond:
            while self._writer or self._writers_waiting:
                self._cond.wait()
            self._readers += 1
        try:
            yield
        finally:
            with self._cond:
                self._readers -= 1
                if not self._readers:
                    self._cond.notify_all()

    @contextmanager
    def write(self):
        with self._cond:
            self._writers_waiting += 1
            while self._writer or self._readers:
                self._cond.wait()
            self._writers_waiting -= 1
            self._writer = True
        try:
            yield
        finally:
            with self._cond:
                self._writer = False
                self._cond.notify_all()


class DynamicHostIndex(HostIndex):
    """HostIndex + journaled insert/delete/compaction (aisaq mode)."""

    #: HostIndex.load refuses dirs with a pending journal; THIS loader is
    #: the one that knows how to recover them.
    _allows_wal = True

    @classmethod
    def load(cls, path: str, *, kill=None, wal_sync: bool = True,
             **kw) -> "DynamicHostIndex":
        """Open for mutation.  Runs journal recovery if a previous writer
        crashed (see module docstring); the outcome lands in
        ``self.recovery`` (a stats dict; ``journaled == 0`` means clean).

        ``kill`` attaches a ``core.faults.KillSwitch`` to every subsequent
        durability-relevant write step (crash drills) — recovery itself
        always runs un-instrumented.  ``wal_sync=False`` skips the
        per-record journal fdatasync (throughput knob: consistency is
        kept, the latest unsynced mutations may be lost on crash)."""
        self = super().load(path, **kw)  # type: ignore[misc]
        if self.meta["mode"] != "aisaq":
            self.close()
            raise DynamicIndexError(
                f"{path!r} is mode={self.meta['mode']!r}: dynamic ops need "
                "inline neighbor codes (aisaq mode) so reverse-edge "
                "patches can carry the new node's code")
        os.close(self.fd)
        self.fd = os.open(os.path.join(path, "chunks.bin"), os.O_RDWR)
        if self.cache is not None:
            self.cache.fd = self.fd      # cache must read via the new fd
        # lazy (mmap) code table for build-time neighbor-code fetches; new
        # codes accumulate in RAM until flush()
        self._codes_mm = np.load(os.path.join(path, "pq_codes.npy"),
                                 mmap_mode="r")
        self._new_codes: List[np.ndarray] = []
        self.n = int(self.meta["n"])
        tomb = os.path.join(path, "tombstones.json")
        self.tombstones: Set[int] = set(
            json.load(open(tomb))) if os.path.exists(tomb) else set()
        if "next_label" in self.meta:
            self._next_label = int(self.meta["next_label"])
        elif self.new_to_old is None:
            self._next_label = self.n            # labels ARE ids
        else:
            self._next_label = int(self.new_to_old.max()) + 1 \
                if len(self.new_to_old) else 0
        self._label_to_int: Optional[Dict[int, int]] = None  # built lazily
        self._rw = _RWLock()
        self._mut = threading.Lock()      # serializes the mutation API
        self.kill = None                  # armed AFTER recovery
        self.wal = _wal.WriteAheadLog(
            os.path.join(path, _wal.WAL_NAME), sync=wal_sync)
        self.recovery = self._recover()
        self.wal.kill = kill
        self.kill = kill
        return self

    def _load_crc_sidecar(self, path, verify):
        """Sidecar load tolerant of a pending journal: recovery may have
        been interrupted after truncating chunks.bin but before rewriting
        the sidecar, so 'sidecar longer than the file' is a RECOVERABLE
        state here (the base loader treats it as a truncated chunks.bin
        and refuses).  Recovery re-anchors every touched block before any
        search runs."""
        spath = os.path.join(path, CRC_SIDECAR)
        wpath = os.path.join(path, _wal.WAL_NAME)
        if verify is not False and os.path.exists(spath) \
                and os.path.exists(wpath) and os.path.getsize(wpath):
            block_crc = np.load(spath).astype(np.uint32)
            nblk = os.fstat(self.fd).st_size // self.layout.io_bytes
            return block_crc[:nblk], \
                resolve_crc(self.meta.get("crc_algo", "crc32"))
        return super()._load_crc_sidecar(path, verify)

    # -- crash injection ----------------------------------------------------
    def _tick(self, label: str):
        if self.kill is not None:
            self.kill.tick(label)

    # -- label mapping -------------------------------------------------------
    def _label_of(self, node: int) -> int:
        return int(node) if self.new_to_old is None \
            else int(self.new_to_old[node])

    def _to_internal(self, label: int) -> int:
        if self.new_to_old is None:
            return int(label)
        if self._label_to_int is None:
            self._label_to_int = {
                int(l): i for i, l in enumerate(self.new_to_old)}
        return self._label_to_int[int(label)]

    # -- helpers -------------------------------------------------------------
    def _code_of(self, node: int) -> np.ndarray:
        base = self._codes_mm.shape[0]
        if node < base:
            return np.asarray(self._codes_mm[node])
        return self._new_codes[node - base]

    def _encode(self, vec: np.ndarray) -> np.ndarray:
        c = self.centroids                      # (m, ks, dsub)
        m, ks, dsub = c.shape
        sub = vec.astype(np.float32).reshape(m, 1, dsub)
        d = ((c - sub) ** 2).sum(-1)            # (m, ks)
        return d.argmin(-1).astype(np.uint8)

    def _read_node(self, node: int):
        from repro.core.chunk_layout import parse_chunk
        lay = self.layout
        raw = os.pread(self.fd, lay.chunk_bytes, lay.file_offset(node))
        return parse_chunk(np.frombuffer(raw, np.uint8), lay)

    def _write_node(self, node: int, vec, nbr_ids: np.ndarray,
                    nbr_codes: np.ndarray):
        lay = self.layout
        chunk = np.zeros(lay.chunk_bytes, np.uint8)
        vb = vec.astype(np.uint8) if lay.data_dtype == "uint8" else \
            vec.astype(np.float32).view(np.uint8)
        chunk[:lay.b_full] = vb
        ids = np.full(lay.R, -1, np.int32)
        ids[:len(nbr_ids)] = nbr_ids
        deg = np.int32(len(nbr_ids))
        chunk[lay.off_deg:lay.off_deg + B_NUM] = \
            deg.reshape(1).view(np.uint8)
        chunk[lay.off_ids:lay.off_ids + lay.R * B_NUM] = ids.view(np.uint8)
        pq_block = np.zeros((lay.R, lay.pq_m), np.uint8)
        pq_block[:len(nbr_ids)] = nbr_codes
        chunk[lay.off_pq:lay.off_pq + lay.R * lay.pq_m] = pq_block.reshape(-1)
        off = lay.file_offset(node)
        payload = chunk.tobytes()
        # the write lock makes the chunk write atomic w.r.t. in-process
        # readers: a search can observe the chunk before or after the
        # patch, never mid-pwrite (and never a half-refreshed sidecar)
        with self._rw.write():
            # extend the file to a whole block if the node opens a new one
            end = off - off % lay.block_bytes + lay.io_bytes
            cur = os.fstat(self.fd).st_size
            if end > cur:
                os.pwrite(self.fd, b"\0" * (end - cur), cur)
            self._tick(f"chunk.pre.{node}")
            if self.kill is not None:
                # two-half write: the drill visits the torn-chunk state
                half = len(payload) // 2
                os.pwrite(self.fd, payload[:half], off)
                self._tick(f"chunk.mid.{node}")
                os.pwrite(self.fd, payload[half:], off + half)
            else:
                os.pwrite(self.fd, payload, off)
            self._tick(f"chunk.post.{node}")
            if self.cache is not None:   # in-place write: drop stale blocks
                self.cache.invalidate(off, lay.chunk_bytes)
                # re-anchor the checksum sidecar to the new on-storage
                # bytes (grows it when the append opened a new block) so
                # verified reads keep passing under mutation
                self.cache.refresh_crc(off, lay.chunk_bytes)

    def _dist(self, a: np.ndarray, b: np.ndarray) -> float:
        a, b = a.astype(np.float32), b.astype(np.float32)
        if self.meta["metric"] == "mips":
            return float(-(a @ b))
        return float(((a - b) ** 2).sum())

    # -- insertion -----------------------------------------------------------
    def insert(self, vec: np.ndarray, *, L: int = 48, alpha: float = 1.2
               ) -> int:
        """Add one vector; returns its LABEL (== node id on an unmapped
        dir).  O(search + R chunk writes), journaled: a crash at any point
        either rolls the insert back completely or (after the commit
        record) preserves it completely."""
        with self._mut:
            return self._insert_locked(np.asarray(vec), L, alpha)

    def _insert_locked(self, vec: np.ndarray, L: int, alpha: float) -> int:
        lay = self.layout
        new_id = self.n
        label = self._next_label
        code = self._encode(vec)
        # candidate pool: the expanded set of a search for `vec` (labels
        # out -> internal ids), widened by one hop of neighbor expansion
        ids, _ = self.search(vec.astype(np.float32), k=min(L, 16), L=L)
        pool = list(dict.fromkeys(
            self._to_internal(int(i)) for i in ids))
        extra = []
        for p in pool:
            _, nbrs, _ = self._read_node(p)
            extra += [int(x) for x in nbrs[(nbrs >= 0) & (nbrs < self.n)]]
        pool = list(dict.fromkeys(pool + extra))[:4 * lay.R]
        pool = [p for p in pool
                if self._label_of(p) not in self.tombstones]
        vecs = {p: self._read_node(p)[0] for p in pool}
        # RobustPrune over the pool
        dists = sorted(pool, key=lambda p: self._dist(vec, vecs[p]))
        chosen: list = []
        alive = dict.fromkeys(dists, True)
        for p in dists:
            if len(chosen) >= lay.R:
                break
            if not alive[p]:
                continue
            chosen.append(p)
            for q in dists:
                if alive[q] and q != p and \
                        alpha * self._dist(vecs[p], vecs[q]) <= \
                        self._dist(vec, vecs[q]):
                    alive[q] = False
        nbr_codes = np.stack([self._code_of(p) for p in chosen]) if chosen \
            else np.zeros((0, lay.pq_m), np.uint8)
        # ---- journal the intent BEFORE any byte of chunks.bin changes ----
        # pre-images cover every chunk the reverse-edge pass MAY patch
        # (the chosen set); rollback restores them and the file size
        file_end = os.fstat(self.fd).st_size
        pre = b"".join(os.pread(self.fd, lay.chunk_bytes,
                                lay.file_offset(p)) for p in chosen)
        self.wal.append(_wal.T_INSERT_BEGIN, dict(
            id=new_id, label=label, n_before=self.n, file_end=file_end,
            chunk_bytes=lay.chunk_bytes,
            chosen=[int(p) for p in chosen]), code.tobytes() + pre)
        # ---- data writes ----
        self._write_node(new_id, vec, np.asarray(chosen, np.int32),
                         nbr_codes)
        self._new_codes.append(code)
        if self.new_to_old is not None:
            if self._label_to_int is not None:
                self._label_to_int[label] = new_id
            self.new_to_old = np.append(self.new_to_old, label)
        self._next_label = label + 1
        self.n += 1
        self.meta["n"] = self.n
        # reverse edges: patch each chosen neighbor's chunk in place
        for p in chosen:
            pvec, pids, pcodes = self._read_node(p)
            valid = pids[(pids >= 0) & (pids < new_id)]
            if len(valid) < lay.R:
                ids2 = np.concatenate([valid, [new_id]]).astype(np.int32)
                codes2 = np.concatenate(
                    [pcodes[:len(valid)], code[None]], axis=0)
            else:
                # over-degree: RobustPrune p's neighborhood ∪ {new}
                npool = [int(x) for x in valid] + [new_id]
                nvecs = {new_id: vec}
                for q in valid:
                    nvecs[int(q)] = self._read_node(int(q))[0]
                order = sorted(npool, key=lambda q: self._dist(pvec, nvecs[q]))
                keep: list = []
                alive2 = dict.fromkeys(order, True)
                for q in order:
                    if len(keep) >= lay.R:
                        break
                    if not alive2[q]:
                        continue
                    keep.append(q)
                    for r in order:
                        if alive2[r] and r != q and \
                                alpha * self._dist(nvecs[q], nvecs[r]) <= \
                                self._dist(pvec, nvecs[r]):
                            alive2[r] = False
                ids2 = np.asarray(keep, np.int32)
                codes2 = np.stack([self._code_of(q) for q in keep])
            self._write_node(p, pvec, ids2, codes2)
        # ---- durability point: data synced, then the commit record ----
        self._tick("data.sync")
        os.fdatasync(self.fd)
        self.wal.append(_wal.T_INSERT_COMMIT, dict(id=new_id, label=label))
        return label

    # -- deletion ------------------------------------------------------------
    def delete(self, node: int):
        """Tombstone one LABEL.  Journaled: the delete survives a crash
        without waiting for a flush."""
        with self._mut:
            self.wal.append(_wal.T_DELETE, dict(label=int(node)))
            self.tombstones.add(int(node))

    # -- flush (the journal checkpoint) --------------------------------------
    def flush(self):
        """Persist appended codes + labels + tombstones + sidecar + meta,
        then truncate the journal.  Every file is rewritten atomically
        (tmp sibling + fsync + rename): a crash mid-flush leaves a
        loadable directory plus a journal that re-derives whatever the
        flush had not yet persisted."""
        with self._mut:
            self._flush_locked()

    def _flush_locked(self):
        self._tick("flush.codes")
        if self._new_codes:
            codes = np.concatenate(
                [np.asarray(self._codes_mm),
                 np.stack(self._new_codes)], axis=0)
            _atomic_npy(os.path.join(self.path, "pq_codes.npy"),
                        codes.astype(np.uint8))
            self._codes_mm = np.load(os.path.join(self.path, "pq_codes.npy"),
                                     mmap_mode="r")
            self._new_codes = []
        self._tick("flush.labels")
        if self.new_to_old is not None:
            # insertion extends the map beyond a permutation of range(n):
            # persist it directly (labels.npy supersedes the id_map branch)
            _atomic_npy(os.path.join(self.path, "labels.npy"),
                        np.asarray(self.new_to_old, np.int64))
            self.meta["label_map"] = "direct"
        self._tick("flush.tombstones")
        _atomic_json(os.path.join(self.path, "tombstones.json"),
                     sorted(self.tombstones))
        self._tick("flush.crc")
        if self.cache is not None and self.cache.block_crc is not None:
            # persist the mutation-refreshed checksums so a reload of the
            # grown chunks.bin verifies cleanly
            _atomic_npy(os.path.join(self.path, CRC_SIDECAR),
                        self.cache.block_crc)
        self._tick("flush.meta")
        self.meta["next_label"] = self._next_label
        _atomic_json(os.path.join(self.path, "meta.json"), self.meta)
        self._tick("flush.wal")
        self.wal.truncate(0)

    # -- journal recovery ----------------------------------------------------
    def _recover(self) -> dict:
        """Reconcile the directory with its journal (load time).  Safe to
        crash at any point DURING recovery too: every step is idempotent
        and the journal is only truncated after the checkpoint flush."""
        records, valid_end, torn = self.wal.scan()
        stats = dict(journaled=len(records), torn=bool(torn),
                     truncated_bytes=0,
                     rolled_back=0, rolled_forward=0, deletes=0)
        if torn:
            # bytes of torn tail dropped from the journal — serving
            # telemetry (WarmIndexPool.stats()["recoveries"]) surfaces
            # this so operators see how much of a crash was unwound
            stats["truncated_bytes"] = max(0, self.wal.size - valid_end)
            self.wal.truncate(valid_end)
        if not records:
            return stats
        lay = self.layout
        committed = {r.header["id"] for r in records
                     if r.rtype == _wal.T_INSERT_COMMIT}
        begins = [r for r in records if r.rtype == _wal.T_INSERT_BEGIN]
        touched: Set[int] = set()        # node ids needing a CRC re-anchor
        # 1. roll the uncommitted tail back from its pre-images (newest
        # first: a later insert's pre-images embed earlier inserts' edges)
        for r in reversed(begins):
            h = r.header
            if h["id"] in committed:
                continue
            cb = int(h["chunk_bytes"])
            pre = r.blob[lay.pq_m:]
            for j, p in enumerate(h["chosen"]):
                img = pre[j * cb:(j + 1) * cb]
                if len(img) == cb:
                    os.pwrite(self.fd, img, lay.file_offset(p))
                    touched.add(int(p))
            os.ftruncate(self.fd, int(h["file_end"]))
            # the aborted node's chunk may live in a block the file
            # ALREADY covered (file_size is whole blocks): truncation
            # leaves its half-written bytes behind, disagreeing with the
            # flushed sidecar — zero the region and re-anchor it
            noff = lay.file_offset(int(h["id"]))
            if noff + cb <= int(h["file_end"]):
                os.pwrite(self.fd, b"\0" * cb, noff)
                touched.add(int(h["id"]))
            stats["rolled_back"] += 1
        # 2. roll committed-but-unflushed inserts forward.  Reconciliation
        # is by-id so a partially completed flush (codes persisted, meta
        # not, or vice versa) replays as a set of no-ops:
        #   code pending  iff id >= rows(pq_codes.npy) + already-pending
        #   label pending iff id >= len(label map)
        #   n             = max(disk n, max committed id + 1)
        base = self._codes_mm.shape[0]
        for r in begins:
            h = r.header
            if h["id"] not in committed:
                continue
            nid = int(h["id"])
            if nid >= base + len(self._new_codes):
                self._new_codes.append(
                    np.frombuffer(r.blob[:lay.pq_m], np.uint8).copy())
            if self.new_to_old is not None \
                    and nid >= len(self.new_to_old):
                self.new_to_old = np.append(self.new_to_old,
                                            int(h["label"]))
            self.n = max(self.n, nid + 1)
            self._next_label = max(self._next_label, int(h["label"]) + 1)
            touched.add(nid)
            touched.update(int(p) for p in h["chosen"])
            stats["rolled_forward"] += 1
        # 3. journaled deletes (set union: idempotent vs tombstones.json)
        for r in records:
            if r.rtype == _wal.T_DELETE:
                self.tombstones.add(int(r.header["label"]))
                stats["deletes"] += 1
        self.meta["n"] = self.n
        # 4. re-anchor the CRC sidecar: the on-disk sidecar describes the
        # pre-crash flush; every chunk recovery restored or rolled forward
        # gets a fresh checksum, and entries past the (possibly truncated)
        # file end are trimmed
        if self.cache is not None:
            fsize = os.fstat(self.fd).st_size
            self.cache.trim_crc(fsize // lay.io_bytes)
            for p in sorted(touched):
                off = lay.file_offset(p)
                if off < fsize:
                    self.cache.invalidate(off, lay.chunk_bytes)
                    self.cache.refresh_crc(off, lay.chunk_bytes)
        # 5. checkpoint: one durable flush, then the journal is history
        self._flush_locked()
        return stats

    # -- compaction ----------------------------------------------------------
    def compact(self, dst: str, *, relabel: bool = True) -> dict:
        """Re-pack the live (un-tombstoned) nodes into a NEW index dir at
        ``dst``: tombstone reclaim, edge remap (edges into dead nodes are
        dropped), optional graph-locality relabel, external labels
        preserved via ``write_index(labels=...)``.  The source directory
        is untouched; ``dst`` is published atomically — hand it to
        ``WarmIndexPool.swap`` for a zero-downtime version switch.
        Returns the new directory's meta dict."""
        with self._mut:
            lay = self.layout
            n = self.n
            labels = np.array([self._label_of(i) for i in range(n)],
                              np.int64)
            live = [i for i in range(n)
                    if int(labels[i]) not in self.tombstones]
            if not live:
                raise DynamicIndexError(
                    "compaction would produce an empty index "
                    "(every node is tombstoned)")
            old_to_new = {p: j for j, p in enumerate(live)}
            dt = np.uint8 if lay.data_dtype == "uint8" else np.float32
            vectors = np.empty((len(live), self.meta["dim"]), dt)
            graph = np.full((len(live), lay.R), -1, np.int32)
            codes = np.empty((len(live), lay.pq_m), np.uint8)
            for j, p in enumerate(live):
                vec, nbrs, _ = self._read_node(p)
                vectors[j] = vec
                codes[j] = self._code_of(p)
                kept = [old_to_new[int(x)] for x in nbrs
                        if 0 <= int(x) < n and int(x) in old_to_new]
                graph[j, :len(kept)] = kept
            return write_index(
                dst, vectors=vectors, graph=graph,
                centroids=self.centroids, codes=codes,
                metric=self.meta["metric"], mode=self.meta["mode"],
                block_bytes=self.meta["block_bytes"],
                n_ep=len(self.meta["entry_points"]),
                relabel=relabel, labels=labels[live],
                extra_meta=dict(next_label=self._next_label))

    # -- lifecycle -----------------------------------------------------------
    def close(self):
        super().close()
        if getattr(self, "wal", None) is not None:
            self.wal.close()

    def abandon(self):
        """Drop the handle WITHOUT flushing — the crash-drill teardown
        (and the honest way to model a dead process: nothing in RAM
        survives, only what the journal and fdatasync made durable)."""
        if self.cache is not None:
            self.cache.stop()
            self.cache.clear()
        if self.fd >= 0:
            os.close(self.fd)
            self.fd = -1
        if getattr(self, "wal", None) is not None:
            self.wal.close()

    # -- filtered + tombstone-aware search -----------------------------------
    def search(self, q, k, L, w=4,
               predicate: Optional[Callable[[int], bool]] = None):
        # the read lock pairs with _write_node's write lock: no torn chunk
        with self._rw.read():
            ids, stats = self._search_locked(q, k, L, w)
            drop = self.tombstones
            ok = [i for i in ids if int(i) >= 0 and int(i) not in drop
                  and (predicate is None or predicate(int(i)))]
            if len(ok) < k and (drop or predicate is not None):
                # widen once: tombstones/filters thin the pool
                ids2, s2 = self._search_locked(q, k * 4, max(L, 2 * k * 4), w)
                stats.ios += s2.ios
                stats.bytes_read += s2.bytes_read
                ok = [i for i in ids2 if int(i) >= 0 and int(i) not in drop
                      and (predicate is None or predicate(int(i)))]
            return np.asarray(ok[:k], np.int64), stats

    def _search_locked(self, q, k, L, w):
        # HostIndex.search goes through self.search_batch, whose second read
        # acquire would queue behind a waiting writer: a deadlock
        ids, stats = super().search_batch(q[None], k, L, w)
        return ids[0], stats[0]

    def search_batch(self, Q, k, L, w=4, **kw):
        with self._rw.read():
            return super().search_batch(Q, k, L, w, **kw)

    def search_ref(self, q, k, L, w=4, **kw):
        with self._rw.read():
            return super().search_ref(q, k, L, w, **kw)
