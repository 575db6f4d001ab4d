"""Open loop: single queries at a fixed rate into the program's
``ServingEngine``.

``round(rate_qps * seconds)`` arrivals fall uniformly in the window (a
Poisson process given its count, so every seed offers the same number of
requests). Each request is timed from the moment it was due, so a late
generator or a backlog shows in the latency; how late the generator itself
ran is reported apart. Requests not answered within ``drain_seconds`` after
the window count as failed. A traced run offers the whole window untraced,
as every run does, and then a segment of ``trace_seconds`` more at the
same load under the profiler, so that the profiler's start and stop, which
hold the host for seconds, delay no request of the window.

Traffic keys: ``rate_qps``, ``k``, ``max_batch``, ``max_wait_ms``,
``query_pool``, ``trace_seconds``, ``drain_seconds``.
"""
from __future__ import annotations

import time

import numpy as np


def pool_size(traffic: dict, seconds: float) -> int:
    return int(traffic["query_pool"])


def batch_sizes(traffic: dict) -> list:
    return list(range(1, int(traffic["max_batch"]) + 1))


def warm(run) -> None:
    """One call at every batch size the engine can form."""
    k = run.cell.traffic["k"]
    for b in batch_sizes(run.cell.traffic):
        run.search(run.queries[:b], k)


def schedule(run, seconds: float, stream: int = 1):
    """(due times in seconds from the start, pool index of each request)."""
    t = run.cell.traffic
    n = int(round(t["rate_qps"] * seconds))
    rng = run.rng(stream)
    due = np.sort(rng.uniform(0.0, seconds, n))
    pool = run.queries.shape[0]
    qidx = np.concatenate([rng.permutation(pool)
                           for _ in range(-(-n // pool))])[:n]
    return due, qidx


def offer(run, due: np.ndarray, qidx: np.ndarray, *, traced=False):
    """Submit every request at its due time, wait for the answers; with
    ``traced`` the profiler records the submissions.

    Returns (requests, submit lateness s, absolute due times)."""
    import jax
    from repro.serving.engine import ServingEngine
    t = run.cell.traffic
    engine = ServingEngine({"default": run.timed_search()},
                           max_batch=int(t["max_batch"]),
                           max_wait_ms=float(t["max_wait_ms"]))
    try:
        if traced:
            jax.profiler.start_trace(run.trace_dir)
        t0 = time.perf_counter() + 0.01
        at = t0 + due
        late = np.empty(len(due))
        reqs = []
        for i, target in enumerate(at):
            left = target - time.perf_counter()
            if left > 0:
                time.sleep(left)
            late[i] = time.perf_counter() - target
            reqs.append(engine.submit(run.queries[qidx[i]], "default",
                                      t["k"]))
        if traced:
            jax.profiler.stop_trace()
        deadline = t0 + (due[-1] if len(due) else 0) + t["drain_seconds"]
        for r in reqs:
            r.event.wait(max(0.0, deadline - time.perf_counter()))
    finally:
        engine.stop()
    return reqs, late, at


def measure(run):
    from benchmarks.chip.harness import Window
    t = run.cell.traffic
    due, qidx = schedule(run, run.seconds)
    reqs, late, at = offer(run, due, qidx)
    if run.trace_dir is not None:
        offer(run, *schedule(run, t["trace_seconds"], stream=2), traced=True)
    ok = np.array([r.event.is_set() and r.error is None
                   and r.result is not None for r in reqs], bool)
    lat = np.array([(r.t_done - a) * 1e3 if good else np.inf
                    for r, a, good in zip(reqs, at, ok)])
    ids = np.stack([reqs[i].result for i in np.flatnonzero(ok)]) if ok.any() \
        else np.zeros((0, t["k"]), np.int64)
    return Window(attempted=len(reqs), failed=int((~ok).sum()),
                  elapsed_s=run.seconds, qidx=qidx[ok], ids=ids,
                  latencies_ms=lat, lateness_ms=late * 1e3)
