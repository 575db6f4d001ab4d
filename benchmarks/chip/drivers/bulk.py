"""Closed loop, one caller: back-to-back calls of ``batch`` distinct queries.

This is what an offline bulk retrieval job does: it hands the program's
device search whole batches and waits for each. Blocks of the query pool
are taken in an order drawn from the seed; every call's queries are
distinct, and a pool that runs out starts again.

Traffic keys: ``batch``, ``k``, ``query_pool``, ``trace_seconds``.
"""
from __future__ import annotations

import time

import numpy as np


def pool_size(traffic: dict, seconds: float) -> int:
    return int(traffic["query_pool"])


def batch_sizes(traffic: dict) -> list:
    return [int(traffic["batch"])]


def warm(run) -> None:
    b, k = run.cell.traffic["batch"], run.cell.traffic["k"]
    for _ in range(2):
        run.search(run.queries[:b], k)


def measure(run):
    import jax
    from benchmarks.chip.harness import Window
    b, k = int(run.cell.traffic["batch"]), int(run.cell.traffic["k"])
    blocks = run.queries.shape[0] // b
    order = run.rng(1).permutation(blocks)
    call = run.timed_search()
    tracing = run.trace_dir is not None
    if tracing:
        jax.profiler.start_trace(run.trace_dir)
    qidx, ids = [], []
    t0 = time.perf_counter()
    end, trace_end = t0 + run.seconds, t0 + run.cell.traffic["trace_seconds"]
    i = 0
    while time.perf_counter() < end:
        rows = np.arange(b) + b * order[i % blocks]
        ids.append(np.asarray(call(run.queries[rows], k)))
        qidx.append(rows)
        i += 1
        if tracing and time.perf_counter() >= trace_end:
            jax.profiler.stop_trace()
            tracing = False
    elapsed = time.perf_counter() - t0
    if tracing:
        jax.profiler.stop_trace()
    n = b * i
    return Window(attempted=n, failed=0, elapsed_s=elapsed,
                  qidx=np.concatenate(qidx), ids=np.concatenate(ids))
