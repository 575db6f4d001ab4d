"""The benchmark's own index generator: data, queries, PQ, graph, ground truth.

Everything is made on the default device by jitted calls from one seed, so
the same seed gives the same index on the same device. Nothing here imports
the program under test; the program only receives the finished arrays.

Recipe (the program cannot build a Vamana graph at this scale in a run):

* data: a Gaussian mixture with power-law cluster weights (the program's
  ``make_clustered`` recipe, copied). Cluster sizes are fixed by ``n`` alone,
  so every seed gets the same sizes; the centres and points move with it.
* graph: each point's ``R - long_edges`` nearest neighbours within its own
  mixture cluster, plus ``long_edges`` uniformly random ids. The random
  edges are what lets a search leave the entry point's cluster.
* PQ: per-subspace Lloyd k-means on a sample, then every row encoded.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Index(NamedTuple):
    base: np.ndarray        # (n, d) float32
    graph: np.ndarray       # (n, R) int32, every slot filled
    centroids: np.ndarray   # (m, ks, dsub) float32
    codes: np.ndarray       # (n, m) uint8
    queries: np.ndarray     # (n_queries, d) float32, distinct


def rng_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, also past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed % 2 ** 32)
    return jax.random.fold_in(key, seed // 2 ** 32)


def cluster_sizes(n: int, n_clusters: int, alpha: float = 0.7) -> tuple:
    """Rows per mixture cluster, weights ~ 1/i**alpha; depends on n only."""
    w = 1.0 / np.arange(1, n_clusters + 1) ** alpha
    raw = n * w / w.sum()
    sizes = np.floor(raw).astype(np.int64)
    extra = n - int(sizes.sum())
    sizes[np.argsort(-(raw - sizes), kind="stable")[:extra]] += 1
    return tuple(int(s) for s in sizes)


@functools.partial(jax.jit, static_argnames=("sizes", "d", "spread",
                                             "normalize", "rank"))
def _mixture(key, *, sizes, d, spread, normalize, rank=None):
    """Rows sorted by cluster: (x (n, d), cluster id (n,)).

    With ``rank`` each cluster's spread lies in a random subspace of that
    dimension (its own basis, per-coordinate variance as at full rank), as
    the intrinsic dimension of learned embeddings is far below their
    width; without it the spread is full-rank, as in ``make_clustered``."""
    n = sum(sizes)
    kc, kx, kb = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (len(sizes), d), jnp.float32)
    cid = jnp.repeat(jnp.arange(len(sizes), dtype=jnp.int32),
                     np.asarray(sizes), total_repeat_length=n)
    if rank is None:
        noise = jax.random.normal(kx, (n, d), jnp.float32)
    else:
        z = jax.random.normal(kx, (n, rank), jnp.float32)
        basis = jax.random.normal(kb, (len(sizes), rank, d), jnp.float32) \
            / np.sqrt(rank)
        cuts = np.cumsum(sizes)[:-1].tolist()
        noise = jnp.concatenate([
            jnp.matmul(zc, basis[c], precision=HIGHEST)
            for c, zc in enumerate(jnp.split(z, cuts))])
    x = centers[cid] + spread * noise
    if normalize:
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return x, cid


@functools.partial(jax.jit, static_argnames=("sizes", "k", "block"))
def _knn_in_clusters(x, cid, *, sizes, k, block):
    """(n, k) int32: each sorted row's k nearest rows (L2) of its own
    cluster, -1 where the cluster has fewer than k other rows."""
    n, d = x.shape
    width = -(-(max(sizes) + block) // 128) * 128
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)
    n_blocks = -(-n // block)
    pad = n_blocks * block + width - n
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    cp = jnp.pad(cid, (0, pad), constant_values=-1)
    norms = jnp.sum(xp * xp, axis=1)

    def one_block(b):
        r0 = b * block
        rows = jax.lax.dynamic_slice_in_dim(xp, r0, block)
        rc = jax.lax.dynamic_slice_in_dim(cp, r0, block)
        w0 = jnp.asarray(starts)[jnp.maximum(rc[0], 0)]
        cand = jax.lax.dynamic_slice_in_dim(xp, w0, width)
        cc = jax.lax.dynamic_slice_in_dim(cp, w0, width)
        cn = jax.lax.dynamic_slice_in_dim(norms, w0, width)
        dist = cn[None, :] - 2.0 * jnp.matmul(rows, cand.T)
        self_ = (w0 + jnp.arange(width))[None, :] == \
            (r0 + jnp.arange(block))[:, None]
        dist = jnp.where((cc[None, :] != rc[:, None]) | self_, jnp.inf, dist)
        val, pos = jax.lax.approx_min_k(dist, k)
        return jnp.where(jnp.isfinite(val), w0 + pos, -1).astype(jnp.int32)

    out = jax.lax.map(one_block, jnp.arange(n_blocks))
    return out.reshape(-1, k)[:n]


@functools.partial(jax.jit, static_argnames=("m", "ks", "iters"))
def train_pq(key, sample, *, m, ks, iters):
    """Per-subspace Lloyd k-means: (n_train, d) -> (m, ks, dsub) f32."""
    nt, d = sample.shape
    subs = sample.reshape(nt, m, d // m).transpose(1, 0, 2)
    init = jax.random.choice(key, nt, (ks,), replace=False)

    def per_sub(sub):
        def step(c, _):
            dist = jnp.sum(c * c, axis=1)[None, :] - 2.0 * jnp.matmul(
                sub, c.T, precision=HIGHEST)
            a = jnp.argmin(dist, axis=1)
            sums = jax.ops.segment_sum(sub, a, num_segments=ks)
            cnt = jax.ops.segment_sum(jnp.ones((nt,), jnp.float32), a,
                                      num_segments=ks)
            new = sums / jnp.maximum(cnt, 1.0)[:, None]
            return jnp.where((cnt > 0)[:, None], new, c), None
        c, _ = jax.lax.scan(step, sub[init], None, length=iters)
        return c

    return jax.lax.map(per_sub, subs)


@functools.partial(jax.jit, static_argnames=("block",))
def encode_pq(x, centroids, *, block=4096):
    """(n, d) -> (n, m) uint8 nearest-centroid codes per subspace."""
    n, d = x.shape
    m, ks, dsub = centroids.shape
    nb = -(-n // block)
    xp = jnp.pad(x, ((0, nb * block - n), (0, 0)))
    cn = jnp.sum(centroids * centroids, axis=2)            # (m, ks)

    def one(b):
        rows = jax.lax.dynamic_slice_in_dim(xp, b * block, block)
        sub = rows.reshape(block, m, dsub)
        dist = cn[None] - 2.0 * jnp.einsum("bmd,mkd->bmk", sub, centroids,
                                           precision=HIGHEST)
        return jnp.argmin(dist, axis=2).astype(jnp.uint8)

    return jax.lax.map(one, jnp.arange(nb)).reshape(-1, m)[:n]


@functools.partial(jax.jit, static_argnames=("n_long", "normalize",
                                             "n_queries", "noise"))
def _finish(key, xs, knn_sorted, *, n_long, normalize, n_queries, noise):
    """Shuffle ids, add long edges, draw queries near base rows."""
    n = xs.shape[0]
    kp, kl, kq, kn = jax.random.split(key, 4)
    perm = jax.random.permutation(kp, n).astype(jnp.int32)  # sorted -> id
    inv = jnp.argsort(perm)                                  # id -> sorted
    base = xs[inv]
    near = knn_sorted[inv]
    near = jnp.where(near >= 0, perm[jnp.maximum(near, 0)], -1)
    long_ = jax.random.randint(kl, (n, n_long), 0, n, jnp.int32)
    # a short cluster leaves -1 slots: fill them with random ids too
    fill = jax.random.randint(kn, near.shape, 0, n, jnp.int32)
    graph = jnp.concatenate([jnp.where(near >= 0, near, fill), long_], 1)
    pick = jax.random.permutation(kq, n)[:n_queries]
    q = base[pick]
    q = q + noise * jax.random.normal(jax.random.fold_in(kq, 1), q.shape) \
        * jnp.mean(jnp.abs(q))
    if normalize:
        q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    return base, graph, q


def make_index(seed: int, cfg: dict, n_queries: int) -> Index:
    """The whole index of one configuration file, from one seed."""
    gen = cfg["assumed"]["generator"]
    n, d, R = cfg["n_vectors"], cfg["dim"], cfg["R"]
    m, ks = cfg["pq_m"], cfg["pq_ks"]
    sizes = cluster_sizes(n, gen["n_clusters"])
    k_mix, k_pq, k_fin = jax.random.split(rng_key(seed), 3)
    xs, cid = _mixture(k_mix, sizes=sizes, d=d, spread=gen["spread"],
                       normalize=gen["normalize"],
                       rank=gen.get("intrinsic_dim"))
    knn = _knn_in_clusters(xs, cid, sizes=sizes, k=R - gen["long_edges"],
                           block=gen["knn_block"])
    base, graph, queries = _finish(
        k_fin, xs, knn, n_long=gen["long_edges"], normalize=gen["normalize"],
        n_queries=n_queries, noise=gen["query_noise"])
    del xs, cid, knn
    k_s, k_i = jax.random.split(k_pq)
    sample = base[jax.random.permutation(k_s, n)[:gen["pq_train_rows"]]]
    cents = train_pq(k_i, sample, m=m, ks=ks, iters=gen["pq_iters"])
    codes = encode_pq(base, cents)
    host = jax.device_get((base, graph, cents, codes, queries))
    return Index(*(np.ascontiguousarray(a) for a in host))


@functools.partial(jax.jit, static_argnames=("k", "metric", "chunk"))
def _topk_exact(queries, base, *, k, metric, chunk):
    n, d = base.shape
    nc = -(-n // chunk)
    bp = jnp.pad(base, ((0, nc * chunk - n), (0, 0)))
    norms = jnp.sum(bp * bp, axis=1)

    def step(carry, c):
        best_d, best_i = carry
        blk = jax.lax.dynamic_slice_in_dim(bp, c * chunk, chunk)
        ip = jnp.matmul(queries, blk.T, precision=HIGHEST)
        bn = jax.lax.dynamic_slice_in_dim(norms, c * chunk, chunk)
        dist = -ip if metric == "mips" else bn[None, :] - 2.0 * ip
        ids = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        dist = jnp.where(ids[None, :] < n, dist, jnp.inf)
        all_d = jnp.concatenate([best_d, dist], axis=1)
        all_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, dist.shape)], axis=1)
        nd, pos = jax.lax.top_k(-all_d, k)
        return (-nd, jnp.take_along_axis(all_i, pos, axis=1)), None

    nq = queries.shape[0]
    init = (jnp.full((nq, k), jnp.inf), jnp.full((nq, k), -1, jnp.int32))
    (_, ids), _ = jax.lax.scan(step, init, jnp.arange(nc))
    return ids


def ground_truth(queries: np.ndarray, base: np.ndarray, k: int, metric: str,
                 *, q_block: int = 1024, chunk: int = 65536) -> np.ndarray:
    """Brute-force top-k ids, on the default device, in f32 at HIGHEST."""
    b = jnp.asarray(base)
    out = []
    for s in range(0, queries.shape[0], q_block):
        q = jnp.asarray(queries[s:s + q_block])
        out.append(np.asarray(_topk_exact(q, b, k=k, metric=metric,
                                          chunk=min(chunk, base.shape[0]))))
    return np.concatenate(out)


def recall(ids: np.ndarray, gt: np.ndarray, k: int) -> float:
    """Mean share of the true top-k that the answers hold."""
    hit = [len(set(a[:k].tolist()) & set(g[:k].tolist()))
           for a, g in zip(ids, gt)]
    return float(np.sum(hit)) / (k * len(hit))
