"""Plain reference of the search the benchmark times, and the comparison.

``beam_search`` is Algorithm 1 of the AiSAQ paper (the DiskANN beam search
with PQ-guided frontier and an exact-distance result pool) as the device
path defines it, written straight from that definition in numpy, batched
over queries only for speed:

* the candidate list holds at most L ids, starting from the entry point;
* each hop expands the w unexpanded candidates of least PQ distance;
* an expanded node's exact distance enters the result pool (top L);
* its neighbours not seen before enter the candidate list with their PQ
  distances, and the list is cut back to its L best;
* a query stops when every candidate is expanded, or after max_hops;
* the answer is the pool's top k by exact distance.

Ties go to the lower position, as ``lax.top_k`` breaks them. It takes the
benchmark's own arrays (vectors, graph, codes, centroids), never the
program's chunk table. ``dtype`` is the precision of the operands (LUT,
vectors, queries); sums accumulate in float64, or in float32 for a
low-precision control, as an MXU accumulates bf16 products.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


def entry_point(base: np.ndarray) -> int:
    """The row closest to the mean: the program's documented entry rule."""
    x = base.astype(np.float64)
    return int(np.argmin(((x - x.mean(axis=0)) ** 2).sum(axis=1)))


def _cast(a: np.ndarray, dtype) -> np.ndarray:
    """Round to the operand precision, then widen for the sums."""
    acc = np.float32 if dtype == BF16 else np.float64
    return a.astype(dtype).astype(acc)


def beam_search(base, graph, codes, centroids, queries, *, k, L, w,
                max_hops, metric, dtype=np.float64, entry=None):
    """(nq, d) queries -> (ids (nq, k) int64, exact distances (nq, k),
    hops (nq,) each query made). ``entry``, when given, is
    ``entry_point(base)`` worked out once by a caller that searches often."""
    nq, d = queries.shape
    n = base.shape[0]
    m, ks, dsub = centroids.shape
    q = _cast(queries, dtype)
    c = _cast(centroids, dtype)
    qs = q.reshape(nq, m, 1, dsub)
    if metric == "mips":
        lut = -(qs * c[None]).sum(axis=3)
    else:
        lut = ((c[None] - qs) ** 2).sum(axis=3)               # (nq, m, ks)
    lut = _cast(lut, dtype)
    flat = lut.reshape(nq, m * ks)
    offs = np.arange(m) * ks

    def pq_dist(rows, ids):                                    # ids (r, K)
        idx = codes[ids].astype(np.int64) + offs               # (r, K, m)
        return flat[rows[:, None, None], idx].sum(axis=2)

    def exact(rows, ids):
        v = _cast(base[ids], dtype)                            # (r, K, d)
        if metric == "mips":
            return -(v * q[rows][:, None, :]).sum(axis=2)
        return ((v - q[rows][:, None, :]) ** 2).sum(axis=2)

    ep = entry_point(base) if entry is None else entry
    all_q = np.arange(nq)
    cand_ids = np.full((nq, L), -1, np.int64)
    cand_d = np.full((nq, L), np.inf)
    cand_exp = np.ones((nq, L), bool)
    cand_ids[:, 0] = ep
    cand_d[:, 0] = pq_dist(all_q, np.full((nq, 1), ep))[:, 0]
    cand_exp[:, 0] = False
    seen = [{ep} for _ in range(nq)]
    pool_ids = np.full((nq, L), -1, np.int64)
    pool_d = np.full((nq, L), np.inf)
    hops = np.zeros(nq, np.int64)
    while True:
        live = (~cand_exp & np.isfinite(cand_d)).any(axis=1) \
            & (hops < max_hops)
        rows = np.flatnonzero(live)
        if rows.size == 0:
            break
        hops[rows] += 1
        sel = np.where(cand_exp[rows], np.inf, cand_d[rows])
        pos = np.argsort(sel, axis=1, kind="stable")[:, :w]
        fvalid = np.isfinite(np.take_along_axis(sel, pos, axis=1))
        fids = np.where(fvalid, np.take_along_axis(cand_ids[rows], pos, 1),
                        -1)
        ce = cand_exp[rows]
        np.put_along_axis(ce, pos, np.take_along_axis(ce, pos, 1) | fvalid, 1)
        cand_exp[rows] = ce
        ex = np.where(fvalid, exact(rows, np.maximum(fids, 0)), np.inf)
        p_ids = np.concatenate([pool_ids[rows], fids], axis=1)
        p_d = np.concatenate([pool_d[rows], ex], axis=1)
        keep = np.argsort(p_d, axis=1, kind="stable")[:, :L]
        pool_ids[rows] = np.take_along_axis(p_ids, keep, 1)
        pool_d[rows] = np.take_along_axis(p_d, keep, 1)
        nbr = np.where(fvalid[:, :, None], graph[np.maximum(fids, 0)], -1)
        nbr = nbr.reshape(len(rows), -1).astype(np.int64)
        nd = pq_dist(rows, np.maximum(nbr, 0))
        for j, r in enumerate(rows):
            s = seen[r]
            for t, v in enumerate(nbr[j]):
                if v < 0 or v in s:
                    nbr[j, t] = -1
                else:
                    s.add(int(v))
        nd = np.where(nbr >= 0, nd, np.inf)
        a_ids = np.concatenate([cand_ids[rows], nbr], axis=1)
        a_d = np.concatenate([cand_d[rows], nd], axis=1)
        a_exp = np.concatenate([cand_exp[rows], nbr < 0], axis=1)
        keep = np.argsort(a_d, axis=1, kind="stable")[:, :L]
        cand_ids[rows] = np.take_along_axis(a_ids, keep, 1)
        cand_d[rows] = np.take_along_axis(a_d, keep, 1)
        cand_exp[rows] = np.take_along_axis(a_exp, keep, 1)
    return pool_ids[:, :k], pool_d[:, :k], hops


def exact_distances(base, queries, ids, metric):
    """float64 exact distances of (nq, k) ids; -1 and out of range -> inf."""
    ok = (ids >= 0) & (ids < base.shape[0])
    v = base[np.where(ok, ids, 0)].astype(np.float64)
    q = queries.astype(np.float64)[:, None, :]
    dist = -(v * q).sum(2) if metric == "mips" else ((v - q) ** 2).sum(2)
    return np.where(ok, dist, np.inf)


def wrong_ids(base, queries, got, ref_ids, metric, k) -> int:
    """Answer ids that are not among the reference's k nearest: missing,
    repeated, or farther than the reference's k-th neighbour."""
    kth = exact_distances(base, queries, ref_ids[:, k - 1:k], metric)
    dist = exact_distances(base, queries, got[:, :k], metric)
    tol = 1e-9 * np.maximum(np.abs(kth), 1.0)
    far = ~(dist <= kth + tol)
    srt = np.sort(got[:, :k], axis=1)
    dup = np.zeros_like(far)
    dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
    return int(far.sum() + dup.sum())
