"""Pure-jnp oracles for every Pallas kernel in this package.

Device-side chunks are handled as int32 *words* (stride/4 per row): 4-byte
aligned field offsets mean id/float fields are single words and uint8 fields
unpack with shifts — all TPU-lowerable ops (no sub-word memory ops needed).

Every f32 reduction here is elementwise products summed by ``jnp.sum``, never
a dot: XLA's default TPU matmul precision rounds f32 operands through bf16,
which would break agreement with the numpy host oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.chunk_layout import ChunkLayout


# ---------------------------------------------------------------------------
# word-level parsing helpers
# ---------------------------------------------------------------------------


def unpack_u8(words: jax.Array) -> jax.Array:
    """int32 (..., W) -> (..., W*4) values in [0,255] (little-endian)."""
    shifts = jnp.array([0, 8, 16, 24], dtype=jnp.int32)
    b = jnp.right_shift(words[..., None], shifts) & 0xFF
    return b.reshape(words.shape[:-1] + (words.shape[-1] * 4,))


def parse_chunks_words(words: jax.Array, layout: ChunkLayout):
    """words: (w, stride/4) int32 rows gathered from the chunk array.

    Returns (vec_f32 (w, dim), deg (w,), ids (w, R) i32, codes (w, R, m) i32).
    codes is None for diskann-mode layouts.
    """
    w = words.shape[0]
    d, R, m = layout.dim, layout.R, layout.pq_m
    if layout.data_dtype == "uint8":
        nw = (d + 3) // 4
        vec = unpack_u8(words[:, :nw])[:, :d].astype(jnp.float32)
    else:
        vec = jax.lax.bitcast_convert_type(words[:, :d], jnp.float32)
    deg = words[:, layout.dev_off_deg // 4]
    o = layout.dev_off_ids // 4
    ids = words[:, o:o + R]
    codes = None
    if layout.mode == "aisaq":
        o = layout.dev_off_pq // 4
        assert m % 4 == 0, "pq_m must be a multiple of 4 for word layout"
        codes = unpack_u8(words[:, o:o + R * m // 4]).reshape(w, R, m)
    return vec, deg, ids, codes


# ---------------------------------------------------------------------------
# kernel oracles
# ---------------------------------------------------------------------------


def pq_lut_ref(queries: jax.Array, centroids: jax.Array, *, metric: str
               ) -> jax.Array:
    """(q, d), (m, ks, dsub) -> (q, m, ks) f32 (the host twin's formula)."""
    q = queries.shape[0]
    m, ks, dsub = centroids.shape
    qs = queries.astype(jnp.float32).reshape(q, m, 1, dsub)
    c = centroids.astype(jnp.float32)[None]
    if metric == "mips":
        return -jnp.sum(qs * c, axis=-1)
    diff = c - qs
    return jnp.sum(diff * diff, axis=-1)


def pq_adc_ref(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """lut (m, ks) f32, codes (n, m) int -> (n,) f32 (gather semantics)."""
    m, ks = lut.shape
    idx = codes.astype(jnp.int32) + jnp.arange(m, dtype=jnp.int32) * ks
    return jnp.take(lut.reshape(-1), idx).sum(axis=-1)


def fused_hop_ref(chunk_words: jax.Array, frontier_ids: jax.Array,
                  lut: jax.Array, query: jax.Array, layout: ChunkLayout, *,
                  metric: str):
    """One AiSAQ beam-search hop given gathered chunk rows.

    chunk_words: (N, rows, 128) int32 full chunk table (the HBM 'storage').
    frontier_ids: (w,) int32 node ids to expand (may contain -1 padding).
    lut: (m, ks) f32 for this query. query: (d,) f32.

    Returns (exact_d (w,), nbr_ids (w, R) i32, nbr_d (w, R) f32).
    Invalid frontier rows / neighbor slots get +inf distances and id -1.
    """
    w = frontier_ids.shape[0]
    safe = jnp.clip(frontier_ids, 0, chunk_words.shape[0] - 1)
    rows = chunk_words[safe].reshape(w, -1)               # gather (w, S)
    vec, deg, ids, codes = parse_chunks_words(rows, layout)
    fvalid = frontier_ids >= 0
    q = query.astype(jnp.float32)[None, :]
    if metric == "mips":
        exact = -jnp.sum(vec * q, axis=-1)
    else:
        diff = vec - q
        exact = jnp.sum(diff * diff, axis=-1)
    exact = jnp.where(fvalid, exact, jnp.inf)
    nvalid = (ids >= 0) & fvalid[:, None]
    if layout.mode == "aisaq":
        d = pq_adc_ref(lut, codes.reshape(w * layout.R, layout.pq_m))
        d = d.reshape(w, layout.R)
    else:
        d = None  # diskann device mode resolves codes outside (RAM table)
    if d is not None:
        d = jnp.where(nvalid, d, jnp.inf)
    ids = jnp.where(nvalid, ids, -1)
    return exact, ids, d


def rerank_ref(query: jax.Array, cand: jax.Array, *, metric: str) -> jax.Array:
    """(d,), (c, d) -> (c,) exact distances."""
    cand = cand.astype(jnp.float32)
    q = query.astype(jnp.float32)[None, :]
    if metric == "mips":
        return -jnp.sum(cand * q, axis=-1)
    diff = cand - q
    return jnp.sum(diff * diff, axis=-1)
