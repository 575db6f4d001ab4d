"""Pallas TPU kernel: full-precision re-rank distances (query x candidates).

Grid: (nq, ceil(c/bc)). Each program scores a (bc, d) candidate tile
against one query. The per-candidate terms are formed elementwise in f32
(squared differences for L2, products for MIPS) and summed over d by one
matmul with a ones row at HIGHEST precision, which lands the (1, bc)
distances on lanes — no cancellation-prone |c|^2 - 2 q.c + |q|^2 form and
no bf16 rounding of the operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rerank_kernel(q_ref, c_ref, out_ref, *, metric: str):
    q = q_ref[0]                                       # (1, d)
    c = c_ref[...]                                     # (bc, d)
    if metric == "mips":
        terms, sign = c * q, -1.0
    else:
        diff = c - q
        terms, sign = diff * diff, 1.0
    ones = jnp.ones(q.shape, jnp.float32)
    total = jax.lax.dot_general(ones, terms, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    out_ref[0] = sign * total                          # (1, bc)


@functools.partial(jax.jit, static_argnames=("metric", "block_c", "interpret"))
def rerank(queries: jax.Array, cand: jax.Array, *, metric: str = "l2",
           block_c: int = 1024, interpret: bool = False) -> jax.Array:
    """(nq, d) x (c, d) -> (nq, c) f32 exact distances."""
    squeeze = queries.ndim == 1
    if squeeze:
        queries = queries[None]
    nq, d = queries.shape
    c = cand.shape[0]
    bc = min(block_c, c)
    out = pl.pallas_call(
        functools.partial(_rerank_kernel, metric=metric),
        grid=(nq, pl.cdiv(c, bc)),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda q, i: (q, 0, 0)),
            pl.BlockSpec((bc, d), lambda q, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bc), lambda q, i: (q, 0, i)),
        out_shape=jax.ShapeDtypeStruct((nq, 1, c), jnp.float32),
        interpret=interpret,
    )(queries.astype(jnp.float32)[:, None, :], cand.astype(jnp.float32))
    return out[0, 0] if squeeze else out[:, 0]
