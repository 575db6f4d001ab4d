"""Batched query-serving engine with hedged requests (straggler mitigation).

The paper's serving story (RAG retriever): requests arrive for possibly
different corpora; the engine batches per-corpus, switches indices (AiSAQ
makes that ms-order), and runs the search backend. `hedge=2` issues each
batch to two replicas and takes the first SUCCESSFUL completion — the
classic tail-latency-at-scale mitigation for the multi-server tier; work
the losing replicas still performed is accounted in `hedge_stats`.

This engine serializes every corpus through one loop thread; the
multi-tenant layer that serves corpora concurrently from a warm-index
pool is `serving.service.RetrievalService` + `serving.pool.WarmIndexPool`.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.obs.metrics import COUNT_BUCKETS, MetricsRegistry
from repro.obs.trace import span

#: bounds of the engine's time histograms: 100 µs .. 10 s, 20 per decade.
#: Each bound is 12.2% above the one before, so a percentile interpolated
#: inside its bucket is within 12.2% of the exact one.
ENGINE_BUCKETS_S = tuple(10.0 ** (e / 20) for e in range(-80, 21))


def make_device_search_fn(index, layout, *, metric: str = "l2", L: int = 48,
                          w: int = 4, max_hops: int = 128,
                          backend: str = "auto", adc_dtype: str = "f32",
                          rerank: int = 0,
                          registry: Optional[MetricsRegistry] = None):
    """Wrap the device beam search into the `(queries, k) -> ids` callable
    `ServingEngine` consumes. `adc_dtype="int8"` serves via the int8
    fused-hop ADC kernel (2x MXU rate) — the public serving entry point for
    the quantized hot path.

    `rerank=r` (r > 0) adds the exact rerank tier: beam search returns its
    top-max(r, k) pool, their full-precision vectors are gathered from the
    HBM chunk table, and `kernels.rerank` (tiled Pallas matmul-with-epilogue
    on TPU, jnp ref elsewhere) rescores them exactly before the final
    top-k.

    Each call is the span `search.call` around `search.stage` (queries to
    the device), `search.dispatch` (the jitted call, until it returns
    asynchronously) and `search.fetch` (until the answers are on the
    host), and counts into `registry` (default: a registry of its own):
    `search_calls_total`, `search_loop_trips_total`, `search_slots_total`
    (trips x nq x w) and `search_expansions_total` (valid frontier slots
    expanded). The program returns the ids and both counts in one int32
    buffer, so a call fetches one array. The callable's `lower(nq, k)`
    lowers the program a call of `nq` queries runs."""
    import jax
    import jax.numpy as jnp
    from repro.core.device_index import _served_search
    from repro.kernels import ops

    reg = registry or MetricsRegistry()
    calls = reg.counter("search_calls_total", help="device search calls",
                        unit="calls")
    trips = reg.counter("search_loop_trips_total",
                        help="beam-search loop trips, summed over calls",
                        unit="trips")
    slots = reg.counter("search_slots_total",
                        help="frontier slots the hop kernel ran: "
                             "trips x nq x w", unit="slots")
    expansions = reg.counter("search_expansions_total",
                             help="valid frontier slots expanded",
                             unit="slots")

    def _depth(k: int) -> int:
        return max(int(rerank), k) if rerank else k

    def _params(k: int) -> dict:
        r = _depth(k)
        return dict(k=r, L=max(L, r), w=w, max_hops=max_hops, layout=layout,
                    metric=metric, backend=backend, adc_dtype=adc_dtype)

    def _gather_vecs(ids: "jax.Array") -> "jax.Array":
        """Candidate full-precision vectors, bitcast out of the packed HBM
        chunk rows ON DEMAND — only (nq*r) rows per call ever materialize,
        never an (N, d) resident copy of the corpus."""
        rows = index.chunk_words[ids.reshape(-1)]     # (nq*r, rows, 128)
        rows = rows.reshape(rows.shape[0], -1)        # (nq*r, stride/4) i32
        by = jax.lax.bitcast_convert_type(
            rows, jnp.uint8).reshape(rows.shape[0], -1)
        vb = by[:, :layout.b_full]
        if layout.data_dtype == "uint8":
            return vb.astype(jnp.float32)
        return jax.lax.bitcast_convert_type(
            vb.reshape(rows.shape[0], layout.dim, 4), jnp.float32)

    def _rerank(qj, ids, k: int):
        r = _depth(k)
        nq = ids.shape[0]
        qf = qj.astype(jnp.float32)
        cand = _gather_vecs(jnp.clip(ids, 0, index.n - 1)) \
            .reshape(nq, r, -1)
        # one kernel call per query (identical shapes -> one compile): the
        # candidate sets are per-query, so a single (nq, nq*r) call would
        # compute nq-times redundant distances
        d = jnp.stack([ops.rerank(qf[i], cand[i], metric=metric,
                                  backend=backend)
                       for i in range(nq)])                     # (nq, r)
        d = jnp.where(ids >= 0, d, jnp.inf)
        top = jnp.argsort(d, axis=1)[:, :k]
        return jnp.take_along_axis(ids, top, axis=1)

    def search(queries: np.ndarray, k: int) -> np.ndarray:
        nq = len(queries)
        with span("search.call", nq=nq, k=k):
            with span("search.stage"):
                qj = jnp.asarray(queries)
            with span("search.dispatch"):
                # ids (nq * r) flat, then hops and expanded slots
                out = _served_search(index, qj, **_params(k))
                if rerank:
                    ids = _rerank(qj, out[:-2].reshape(nq, -1), k)
                    out = jnp.concatenate([ids.reshape(-1), out[-2:]])
            with span("search.fetch"):
                out = np.asarray(out)
        hops, expanded = int(out[-2]), int(out[-1])
        calls.inc()
        trips.inc(hops)
        slots.inc(hops * nq * w)
        expansions.inc(expanded)
        return out[:-2].reshape(nq, k)

    def lower(nq: int, k: int):
        """The beam-search program of a call of `nq` float32 queries,
        lowered (`.compile().as_text()` names each device op and its
        scope)."""
        q = jax.ShapeDtypeStruct((nq, layout.dim), jnp.float32)
        return _served_search.lower(index, q, **_params(k))

    search.lower = lower
    return search


def make_host_search_fn(host_index, *, L: int = 48, w: int = 4,
                        prefetch: int = 0, adc_dtype: str = "f32",
                        rerank: Optional[int] = None,
                        pipeline: Optional[bool] = None,
                        gap=None, entry: str = "auto"):
    """Wrap `HostIndex.search_batch` (the vectorized storage-backed path)
    into the `(queries, k) -> ids` callable `ServingEngine` consumes.
    `prefetch` enables speculative next-hop block reads off the demand
    path; `pipeline` (None = auto: on iff prefetch > 0) keeps two hops in
    flight so traversal ADC overlaps the background reads (the
    `core.traversal` two-hop discipline); `gap` tunes readahead
    coalescing (None = prefetch depth, "auto" = histogram-tuned);
    `adc_dtype="int8"` serves via the quantized host ADC twin;
    `rerank` selects the result tier (None = traversal pool, 0 = PQ-only,
    r > 0 = exact rerank of the top-r candidates — the beam width is
    widened to r so the full depth exists, matching the device tier);
    `entry` selects the seeding ("auto" = per-query nav entry vertices
    iff the index carries a navigation tier, see `core.nav`)."""
    def search(queries: np.ndarray, k: int) -> np.ndarray:
        ids, _ = host_index.search_batch(queries, k,
                                         L=max(L, k, rerank or 0), w=w,
                                         prefetch=prefetch,
                                         adc_dtype=adc_dtype, rerank=rerank,
                                         pipeline=pipeline, gap=gap,
                                         entry=entry)
        return ids

    return search


def exact_distances(host_index, queries: np.ndarray, ids: np.ndarray
                    ) -> np.ndarray:
    """Exact f32 distances (metric from meta.json) for result LABELS.

    The cluster's scatter-gather merge needs scores comparable across
    shards; per-shard PQ-approximate distances are not (each shard has
    its own traversal state), so shard workers rescore their answers
    exactly.  Same formula as the exact rerank tail
    (``core.traversal._rerank_tail_ref``) — cluster answers and
    single-process references score candidates bit-identically.
    Padding ids (< 0) map to +inf.
    """
    from repro.core.chunk_layout import parse_chunk
    from repro.core.traversal import SearchStats

    lut = getattr(host_index, "_label_to_storage", None)
    if lut is None:
        n2o = host_index.new_to_old
        lut = {} if n2o is None else \
            {int(lab): i for i, lab in enumerate(n2o)}
        host_index._label_to_storage = lut  # memoized; index is immutable
    metric = host_index.meta["metric"]
    st = SearchStats()
    ids = np.asarray(ids)
    out = np.full(ids.shape, np.inf, dtype=np.float32)
    for i in range(ids.shape[0]):
        qf = np.asarray(queries[i], dtype=np.float32)
        for j in range(ids.shape[1]):
            lab = int(ids[i, j])
            if lab < 0:
                continue
            node = lut.get(lab, lab) if lut else lab
            raw = host_index._read_chunk(node, st)
            vec, _, _ = parse_chunk(raw, host_index.layout)
            vf = vec.astype(np.float32)
            out[i, j] = -(vf @ qf) if metric == "mips" \
                else ((vf - qf) ** 2).sum()
    return out


def make_host_search_dist_fn(host_index, *, L: int = 48, w: int = 4,
                             prefetch: int = 0, adc_dtype: str = "f32",
                             rerank: Optional[int] = None,
                             pipeline: Optional[bool] = None,
                             gap=None, entry: str = "auto"):
    """`(queries, k) -> (ids, dists)` twin of `make_host_search_fn`: the
    same search plus exact distances for the cross-shard merge.  This is
    the search callable cluster shard workers install on their
    `RetrievalService` (whose `_serve` accepts tuple returns)."""
    base = make_host_search_fn(host_index, L=L, w=w, prefetch=prefetch,
                               adc_dtype=adc_dtype, rerank=rerank,
                               pipeline=pipeline, gap=gap, entry=entry)

    def search(queries: np.ndarray, k: int):
        ids = base(queries, k)
        return ids, exact_distances(host_index, queries, ids)

    return search


@dataclass
class Request:
    query: np.ndarray
    corpus: str = "default"
    k: int = 10
    t_submit: float = field(default_factory=time.perf_counter)
    result: Optional[np.ndarray] = None
    # exact distances for `result`, set when the search_fn returns an
    # (ids, dists) pair (cluster shard workers do: the scatter-gather
    # merge needs comparable scores across shards)
    dists: Optional[np.ndarray] = None
    t_done: float = 0.0
    event: threading.Event = field(default_factory=threading.Event)
    error: Optional[Exception] = None    # set instead of result on failure
    # absolute perf_counter deadline; a worker assembling a batch drops
    # the request (TimeoutError, `expired` telemetry) once it has passed —
    # an abandoned submit_wait must not burn search capacity
    deadline: Optional[float] = None
    # obs.trace.Span this request belongs to (None for untraced traffic);
    # the service activates it around the batch so traversal-hop and
    # block-cache spans parent onto the query's trace
    span: Optional[object] = None

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    def expired(self, now: Optional[float] = None) -> bool:
        if self.deadline is None:
            return False
        return (time.perf_counter() if now is None else now) > self.deadline


class ServingEngine:
    """search_fns: corpus -> fn(queries (B,d), k) -> ids (B,k).

    Multiple entries in `replicas` enable hedging; `switch_fn(corpus)` is
    called when the batch's corpus differs from the active one (the paper's
    index-switch path).

    `registry` (default: one of the engine's own) gets the histograms
    `engine_queue_wait_seconds` (submit until the batch is handed to the
    search fn, per request), `engine_batch_size` and
    `engine_latency_seconds` (submit until done, per answered request).
    Each batch runs under the spans `engine.collect` (collecting it,
    `max_wait_ms` included) and `engine.fanout` (search return until the
    last request is woken)."""

    def __init__(self, search_fns: Dict[str, Callable], *,
                 max_batch: int = 32, max_wait_ms: float = 2.0,
                 hedge: int = 1, replicas: Optional[List[Callable]] = None,
                 switch_fn: Optional[Callable[[str], float]] = None,
                 registry: Optional[MetricsRegistry] = None):
        self.search_fns = search_fns
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1e3
        self.hedge = hedge
        self.replicas = replicas
        self.switch_fn = switch_fn
        self.q: "queue.Queue[Request]" = queue.Queue()
        self._held: "deque[Request]" = deque()   # other-corpus holdover
        self.registry = registry or MetricsRegistry()
        self._queue_wait = self.registry.histogram(
            "engine_queue_wait_seconds", buckets=ENGINE_BUCKETS_S,
            unit="seconds",
            help="submit until the request's batch is handed to search")
        self._batch_size = self.registry.histogram(
            "engine_batch_size", buckets=COUNT_BUCKETS, unit="requests",
            help="requests per batch handed to search")
        self._latency = self.registry.histogram(
            "engine_latency_seconds", buckets=ENGINE_BUCKETS_S,
            unit="seconds",
            help="submit until done, per answered request")
        self.switch_times: List[float] = []
        # hedge accounting: wasted = replicas that ran but lost the race,
        # failed = replicas that raised (the winner is the first SUCCESS)
        self.hedge_stats: Dict[str, int] = dict(batches=0, wasted=0, failed=0)
        self._hedge_lock = threading.Lock()
        # guards the _stop flag vs stop()'s queue drain: a submit racing a
        # concurrent stop() must either raise or have its request drained
        self._submit_lock = threading.Lock()
        self._active_corpus: Optional[str] = None
        self._stop = False
        self._pool = ThreadPoolExecutor(max_workers=max(2, hedge * 2))
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    # -- client API ----------------------------------------------------------
    def submit(self, query: np.ndarray, corpus: str = "default", k: int = 10
               ) -> Request:
        with self._submit_lock:
            if self._stop:
                raise RuntimeError("engine stopped")
            r = Request(query=query, corpus=corpus, k=k)
            self.q.put(r)
            return r

    def submit_wait(self, query, corpus="default", k=10, timeout=30.0):
        r = self.submit(query, corpus, k)
        r.event.wait(timeout)
        return r

    # -- engine loop ----------------------------------------------------------
    def _collect_batch(self) -> List[Request]:
        """Corpus-pure batch with FIFO-preserving holdover: a request for a
        DIFFERENT corpus encountered while collecting is parked in `_held`
        (never re-queued to the back of the FIFO, which would reorder it
        behind later arrivals and starve it under sustained foreign load);
        the next batch starts from the holdover before touching the
        queue."""
        if self._held:
            first = self._held.popleft()
        else:
            try:
                first = self.q.get(timeout=0.1)
            except queue.Empty:
                return []
        batch = [first]
        # same-corpus requests already held keep their relative order
        for r in list(self._held):
            if len(batch) >= self.max_batch:
                break
            if r.corpus == first.corpus:
                try:
                    self._held.remove(r)
                except ValueError:
                    continue             # a concurrent stop() drained it
                batch.append(r)
        deadline = time.perf_counter() + self.max_wait
        while len(batch) < self.max_batch:
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            try:
                r = self.q.get(timeout=left)
            except queue.Empty:
                break
            if r.corpus != first.corpus:      # keep batches corpus-pure
                self._held.append(r)          # served at the NEXT batch head
                continue
            batch.append(r)
        return batch

    def _run_search(self, fn, queries, k):
        return fn(queries, k)

    def _count_hedge_loser(self, fut):
        """done-callback for replicas that lost the race: work that ran to
        completion for nothing is wasted; cancelled-before-running is
        free."""
        with self._hedge_lock:
            if fut.cancelled():
                return
            if fut.exception() is not None:
                self.hedge_stats["failed"] += 1
            else:
                self.hedge_stats["wasted"] += 1

    def _run_hedged(self, queries, k):
        """First SUCCESSFUL replica wins. `Future.cancel()` cannot stop an
        already-running thread, so losing replicas are accounted (wasted /
        failed) via done-callbacks rather than assumed dead."""
        futs = [self._pool.submit(self._run_search, rep, queries, k)
                for rep in self.replicas[:self.hedge]]
        with self._hedge_lock:
            self.hedge_stats["batches"] += 1
        pending = set(futs)
        ids = err = None
        while pending:
            done, pending = wait(pending, return_when=FIRST_COMPLETED)
            for f in done:
                e = f.exception()
                if e is None and ids is None:
                    ids = f.result()
                else:
                    with self._hedge_lock:
                        if e is not None:
                            self.hedge_stats["failed"] += 1
                        else:
                            self.hedge_stats["wasted"] += 1
                    err = e if e is not None else err
            if ids is not None:
                break
        for p in pending:                 # losers still in flight
            p.cancel()
            p.add_done_callback(self._count_hedge_loser)
        if ids is None:                   # every replica failed
            raise err if err is not None else RuntimeError("hedge failed")
        return ids

    def _loop(self):
        try:
            self._loop_inner()
        finally:
            # the loop thread drains its own leftovers on exit: requests
            # it moved into _held after stop()'s drain ran would hang
            self._drain(RuntimeError("engine stopped"))

    def _loop_inner(self):
        while not self._stop:
            with span("engine.collect"):
                batch = self._collect_batch()
            if not batch:
                continue
            if self._stop:               # stopped mid-collect: fail the
                self._held.extend(batch)  # batch via the exit drain
                break
            corpus = batch[0].corpus
            err = t_dispatch = None
            try:
                if self.switch_fn is not None \
                        and corpus != self._active_corpus:
                    self.switch_times.append(self.switch_fn(corpus))
                    self._active_corpus = corpus
                queries = np.stack([r.query for r in batch])
                k = max(r.k for r in batch)
                fn = self.search_fns[corpus]
                t_dispatch = time.perf_counter()
                if self.hedge > 1 and self.replicas:
                    ids = self._run_hedged(queries, k)
                else:
                    ids = fn(queries, k)
                ids = np.asarray(ids)     # malformed returns fail the batch
                if ids.ndim != 2 or ids.shape[0] != len(batch):
                    raise ValueError(
                        f"search fn returned shape {ids.shape}, expected "
                        f"({len(batch)}, k)")
            except Exception as e:        # noqa: BLE001 — fail the batch,
                err = e                   # never kill the engine thread
            with span("engine.fanout", n=len(batch)):
                now = time.perf_counter()
                for i, r in enumerate(batch):
                    r.t_done = now
                    if err is not None:
                        r.error = err
                    else:
                        r.result = ids[i, :r.k]
                    r.event.set()
            # observed once every request is woken, off the path from
            # collecting a batch to dispatching it
            if t_dispatch is not None:
                self._batch_size.observe(len(batch))
                self._queue_wait.observe_many(
                    [t_dispatch - r.t_submit for r in batch])
                if err is None:
                    self._latency.observe_many(
                        [now - r.t_submit for r in batch])

    def _drain(self, err: Exception):
        """Fail every request still parked in the holdover deque or the
        queue.  Safe to run from both the loop thread (on exit) and
        stop(): deque/queue pops are atomic, each request drains once."""
        leftovers = []
        while self._held:
            try:
                leftovers.append(self._held.popleft())
            except IndexError:
                break
        while True:
            try:
                leftovers.append(self.q.get_nowait())
            except queue.Empty:
                break
        for r in leftovers:
            r.error = err
            r.event.set()

    # -- stats ----------------------------------------------------------------
    def latency_percentiles(self):
        """p50/p95/p99 of answered requests' latency in ms and their
        count, read from `engine_latency_seconds`: each is interpolated
        inside its bucket (`ENGINE_BUCKETS_S`), so it lies within 12.2% of
        the exact percentile."""
        h = self._latency
        if not h.count:
            return {}
        return {"p50_ms": h.quantile(0.50) * 1e3,
                "p95_ms": h.quantile(0.95) * 1e3,
                "p99_ms": h.quantile(0.99) * 1e3,
                "n": h.count}

    def stop(self):
        with self._submit_lock:
            self._stop = True
        self._t.join(timeout=2.0)
        self._pool.shutdown(wait=False)
        # fail whatever never made it into a batch (queue + holdover) so
        # submit_wait callers see an error instead of a silent timeout;
        # under _submit_lock no new request can slip in behind the drain.
        # The loop thread ALSO drains on its own exit, covering requests
        # it re-parks after this drain when join() timed out mid-collect.
        with self._submit_lock:
            self._drain(RuntimeError("engine stopped"))
