"""jit'd public wrappers over the Pallas kernels with backend dispatch.

backend:
  "ref"               pure-jnp oracle (fast under XLA:CPU; default off-TPU)
  "pallas_interpret"  Pallas kernel body executed in interpret mode (CPU
                      validation — used by tests/test_kernels.py)
  "pallas"            compiled Pallas (TPU target)
  "auto"              pallas on TPU, ref elsewhere
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.chunk_layout import ChunkLayout
from repro.kernels import ref as _ref
from repro.kernels import chunk_adc as _chunk_adc
from repro.kernels.chunk_adc import quantize_lut
from repro.kernels.pq_adc import pq_adc as _pq_adc_pallas
from repro.kernels.pq_lut import pq_lut as _pq_lut_pallas
from repro.kernels.rerank import rerank as _rerank_pallas


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(backend: str) -> str:
    return default_backend() if backend == "auto" else backend


def build_lut(queries: jax.Array, centroids: jax.Array, *, metric: str = "l2",
              backend: str = "auto") -> jax.Array:
    b = _resolve(backend)
    if b == "ref":
        return _ref.pq_lut_ref(queries, centroids, metric=metric)
    return _pq_lut_pallas(queries, centroids, metric=metric,
                          interpret=(b == "pallas_interpret"))


def adc(lut: jax.Array, codes: jax.Array, *, backend: str = "auto"
        ) -> jax.Array:
    """lut (nq, m, ks) or (m, ks); codes (n, m) -> (nq, n) or (n,)."""
    b = _resolve(backend)
    if b == "ref":
        if lut.ndim == 2:
            return _ref.pq_adc_ref(lut, codes)
        return jax.vmap(lambda l: _ref.pq_adc_ref(l, codes))(lut)
    return _pq_adc_pallas(lut, codes, interpret=(b == "pallas_interpret"))


def hop_inputs(lut: jax.Array, queries: jax.Array, *, layout: ChunkLayout,
               backend: str = "auto", adc_dtype: str = "f32"):
    """Loop-invariant operands of `hop`, to be built once per search.

    The TPU operands hold a lane-tiled LUT, a broadcast XLA sinks into a
    loop that reads it: a caller looping over `hop` passes them through
    `jax.lax.optimization_barrier` first, as `_beam_search` does.

    adc_dtype="int8" runs the §Perf adc-int8 path: per-query symmetric LUT
    quantization. The ref backend emulates the identical numerics (quantize
    + dequantize the LUT) so recall-parity tests run anywhere.
    """
    assert adc_dtype in ("f32", "int8"), adc_dtype
    if _resolve(backend) == "ref":
        if adc_dtype == "int8":
            lut_q8, scale = quantize_lut(lut)
            lut = lut_q8.astype(jnp.float32) * (scale / 127.0)[:, None, None]
        return lut, queries
    return _chunk_adc.hop_inputs(lut, queries, layout=layout,
                                 quantized=(adc_dtype == "int8"))


def hop(chunk_words: jax.Array, frontier_ids: jax.Array, operands, *,
        layout: ChunkLayout, metric: str = "l2", backend: str = "auto"):
    """Batched AiSAQ hop over `hop_inputs` operands. frontier_ids (nq, w)
    -> (exact (nq, w), ids (nq, w, R), nbr_d (nq, w, R))."""
    b = _resolve(backend)
    if b == "ref":
        lut, queries = operands
        fn = functools.partial(_ref.fused_hop_ref, chunk_words,
                               layout=layout, metric=metric)
        return jax.vmap(fn)(frontier_ids, lut, queries)
    return _chunk_adc.hop(chunk_words, frontier_ids, *operands,
                          layout=layout, metric=metric,
                          interpret=(b == "pallas_interpret"))


def fused_hop(chunk_words: jax.Array, frontier_ids: jax.Array, lut: jax.Array,
              queries: jax.Array, *, layout: ChunkLayout, metric: str = "l2",
              backend: str = "auto", adc_dtype: str = "f32"):
    """`hop_inputs` + `hop` in one call (one hop of one batch)."""
    operands = hop_inputs(lut, queries, layout=layout, backend=backend,
                          adc_dtype=adc_dtype)
    return hop(chunk_words, frontier_ids, operands, layout=layout,
               metric=metric, backend=backend)


def rerank(queries: jax.Array, cand: jax.Array, *, metric: str = "l2",
           backend: str = "auto") -> jax.Array:
    b = _resolve(backend)
    if b == "ref":
        if queries.ndim == 1:
            return _ref.rerank_ref(queries, cand, metric=metric)
        return jax.vmap(lambda q: _ref.rerank_ref(q, cand, metric=metric)
                        )(queries)
    return _rerank_pallas(queries, cand, metric=metric,
                          interpret=(b == "pallas_interpret"))
