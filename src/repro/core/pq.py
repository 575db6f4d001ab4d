"""Product Quantization (Jegou et al., TPAMI'11) in pure JAX.

This is the compression layer both DiskANN and AiSAQ build on:
  * ``train_codebooks`` — per-subspace Lloyd k-means (vmapped over subspaces)
  * ``encode`` / ``decode`` — vector <-> (m,) uint8 codes
  * ``build_lut`` — per-query asymmetric distance lookup table (m, ks)
  * ``adc`` — asymmetric distance computation: sum LUT entries over codes

These jnp versions are the *reference semantics*; ``repro.kernels`` holds the
Pallas TPU kernels that mirror them (validated by tests/test_kernels.py).

Distance conventions (smaller is better everywhere):
  l2   -> squared euclidean, decomposed exactly over subspaces
  mips -> negative inner product, decomposed exactly over subspaces
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class PQCodebooks(NamedTuple):
    """(m, ks, dsub) float32 centroids. `m` subquantizers, `ks` centroids."""

    centroids: jax.Array

    @property
    def m(self) -> int:
        return self.centroids.shape[0]

    @property
    def ks(self) -> int:
        return self.centroids.shape[1]

    @property
    def dsub(self) -> int:
        return self.centroids.shape[2]

    @property
    def dim(self) -> int:
        return self.m * self.dsub

    def nbytes(self) -> int:
        return int(np.prod(self.centroids.shape)) * 4


def split_subspaces(x: jax.Array, m: int) -> jax.Array:
    """(n, d) -> (m, n, dsub)."""
    n, d = x.shape
    assert d % m == 0, f"dim {d} not divisible by m={m}"
    return jnp.moveaxis(x.reshape(n, m, d // m), 1, 0)


def _pairwise_sqdist(x: jax.Array, c: jax.Array) -> jax.Array:
    """(n, dsub) x (ks, dsub) -> (n, ks) squared L2 (matmul form for MXU).

    HIGHEST precision: the TPU default rounds f32 matmul operands to bf16."""
    xn = jnp.sum(x * x, axis=-1, keepdims=True)          # (n, 1)
    cn = jnp.sum(c * c, axis=-1)                          # (ks,)
    cross = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    return xn - 2.0 * cross + cn[None, :]


@functools.partial(jax.jit, static_argnames=("m", "ks", "iters", "batch"))
def train_codebooks(rng: jax.Array, data: jax.Array, *, m: int, ks: int = 256,
                    iters: int = 12, batch: int = 65536) -> PQCodebooks:
    """Per-subspace Lloyd k-means. data: (n, d) float. Returns PQCodebooks."""
    data = data.astype(jnp.float32)
    n = data.shape[0]
    subs = split_subspaces(data, m)                       # (m, n, dsub)
    init_idx = jax.random.choice(rng, n, shape=(ks,), replace=n < ks)
    cent = subs[:, init_idx, :]                           # (m, ks, dsub)

    def assign_chunked(sub: jax.Array, cb: jax.Array) -> jax.Array:
        """(n, dsub), (ks, dsub) -> (n,) nearest-centroid ids, chunked."""
        nb = (n + batch - 1) // batch
        pad = nb * batch - n
        subp = jnp.pad(sub, ((0, pad), (0, 0)))
        chunks = subp.reshape(nb, batch, -1)
        ids = jax.lax.map(lambda c: jnp.argmin(_pairwise_sqdist(c, cb), axis=-1),
                          chunks)
        return ids.reshape(-1)[:n]

    def lloyd_step(cent, _):
        def per_sub(sub, cb):
            ids = assign_chunked(sub, cb)
            sums = jax.ops.segment_sum(sub, ids, num_segments=ks)
            cnts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), ids,
                                       num_segments=ks)
            new = sums / jnp.maximum(cnts, 1.0)[:, None]
            # keep old centroid for empty clusters
            new = jnp.where((cnts > 0)[:, None], new, cb)
            return new
        return jax.vmap(per_sub)(subs, cent), None

    cent, _ = jax.lax.scan(lloyd_step, cent, None, length=iters)
    return PQCodebooks(cent)


@functools.partial(jax.jit, static_argnames=("batch",))
def encode(codebooks: PQCodebooks, data: jax.Array, *, batch: int = 65536
           ) -> jax.Array:
    """(n, d) -> (n, m) uint8 codes."""
    data = data.astype(jnp.float32)
    n = data.shape[0]
    m = codebooks.m
    subs = split_subspaces(data, m)                       # (m, n, dsub)
    nb = (n + batch - 1) // batch
    pad = nb * batch - n
    subsp = jnp.pad(subs, ((0, 0), (0, pad), (0, 0)))
    subsp = subsp.reshape(m, nb, batch, -1).transpose(1, 0, 2, 3)

    def chunk_codes(chunk):                                # (m, batch, dsub)
        def per_sub(sub, cb):
            return jnp.argmin(_pairwise_sqdist(sub, cb), axis=-1)
        return jax.vmap(per_sub)(chunk, codebooks.centroids)

    codes = jax.lax.map(chunk_codes, subsp)                # (nb, m, batch)
    codes = codes.transpose(0, 2, 1).reshape(nb * batch, m)[:n]
    return codes.astype(jnp.uint8)


@jax.jit
def decode(codebooks: PQCodebooks, codes: jax.Array) -> jax.Array:
    """(n, m) uint8 -> (n, d) float32 reconstruction."""
    n, m = codes.shape
    # gather per subspace: centroids (m, ks, dsub), codes (n, m)
    rec = jnp.take_along_axis(
        codebooks.centroids[None],                         # (1, m, ks, dsub)
        codes.astype(jnp.int32).T[None, :, :, None]        # (1, m, n, 1)
        .transpose(0, 1, 2, 3),
        axis=2,
    )                                                      # (1, m, n, dsub)
    return rec[0].transpose(1, 0, 2).reshape(n, m * codebooks.dsub)


@functools.partial(jax.jit, static_argnames=("metric",))
def build_lut(codebooks: PQCodebooks, queries: jax.Array, *, metric: str = "l2"
              ) -> jax.Array:
    """(q, d) -> (q, m, ks) float32 LUT.

    l2:   lut[q, j, c] = ||q_j - cent[j, c]||^2
    mips: lut[q, j, c] = -<q_j, cent[j, c]>
    """
    queries = queries.astype(jnp.float32)
    qs = split_subspaces(queries, codebooks.m)             # (m, q, dsub)
    if metric == "l2":
        lut = jax.vmap(_pairwise_sqdist)(qs, codebooks.centroids)  # (m, q, ks)
    elif metric == "mips":
        lut = -jnp.einsum("mqd,mkd->mqk", qs, codebooks.centroids)
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return lut.transpose(1, 0, 2)                          # (q, m, ks)


@jax.jit
def adc(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """Asymmetric distances. lut: (q, m, ks) or (m, ks); codes: (..., m).

    Returns (q, ...) or (...,) float32 distances = sum_j lut[j, codes[..., j]].
    """
    single = lut.ndim == 2
    if single:
        lut = lut[None]
    q, m, ks = lut.shape
    flat = lut.reshape(q, m * ks)                          # (q, m*ks)
    idx = codes.astype(jnp.int32) + (jnp.arange(m) * ks)   # (..., m)
    gathered = flat[:, idx.reshape(-1, m)]                 # (q, n, m)
    out = gathered.sum(-1).reshape((q,) + codes.shape[:-1])
    return out[0] if single else out


@jax.jit
def adc_onehot(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """MXU-friendly ADC: one-hot(codes) @ lut. Same contract as :func:`adc`.

    This is the TPU-native reformulation (DESIGN.md §2): a (n*m, ks) one-hot
    times (m*ks,) LUT contraction instead of scalar gathers.
    """
    single = lut.ndim == 2
    if single:
        lut = lut[None]
    q, m, ks = lut.shape
    oh = jax.nn.one_hot(codes.astype(jnp.int32), ks, dtype=lut.dtype)  # (...,m,ks)
    out = jnp.einsum("...mk,qmk->q...", oh, lut)
    return out[0] if single else out


def exact_distances(queries: jax.Array, base: jax.Array, *, metric: str = "l2"
                    ) -> jax.Array:
    """(q, d) x (n, d) -> (q, n) full-precision distances (smaller=better)."""
    queries = queries.astype(jnp.float32)
    base = base.astype(jnp.float32)
    if metric == "l2":
        return _pairwise_sqdist(queries, base)
    if metric == "mips":
        return -jnp.matmul(queries, base.T,
                           precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown metric {metric!r}")


def groundtruth(queries: jax.Array, base: jax.Array, k: int, *,
                metric: str = "l2", batch: int = 262144) -> np.ndarray:
    """Brute-force top-k ids, chunked over the base set. Returns (q, k) int."""
    queries = jnp.asarray(queries, jnp.float32)
    n = base.shape[0]
    best_d = None
    best_i = None
    for s in range(0, n, batch):
        blk = jnp.asarray(base[s:s + batch], jnp.float32)
        d = exact_distances(queries, blk, metric=metric)
        i = jnp.arange(s, s + blk.shape[0])[None, :].repeat(queries.shape[0], 0)
        if best_d is None:
            best_d, best_i = d, i
        else:
            best_d = jnp.concatenate([best_d, d], axis=1)
            best_i = jnp.concatenate([best_i, i], axis=1)
        # keep running top-k to bound memory
        kk = min(k, best_d.shape[1])
        nd, pos = jax.lax.top_k(-best_d, kk)
        best_d = -nd
        best_i = jnp.take_along_axis(best_i, pos, axis=1)
    return np.asarray(best_i[:, :k])
