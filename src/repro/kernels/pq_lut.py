"""Pallas TPU kernel: per-query PQ distance LUT construction.

Grid: (Q,). Each program builds one query's LUT transposed, (ks, m): code
values on sublanes, subquantizers on lanes. Query and centroids arrive
coordinate-major — q (dsub, m) and centroids (dsub, ks, m) — so every step
of the static loop over the dsub coordinates is a (1, m) row broadcast
against a (ks, m) tile. The L2 entries are summed squared differences in
f32 on the VPU, the host twin's formula (``core.adc.np_build_lut``), with
no matmul to round through bf16. Blocks are tile-legal at any dsub.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lut_kernel(q_ref, c_ref, out_ref, *, metric: str):
    dsub = c_ref.shape[0]
    acc = jnp.zeros(out_ref.shape[1:], jnp.float32)       # (ks, m)
    for t in range(dsub):
        qt = q_ref[0, t:t + 1, :]                          # (1, m)
        ct = c_ref[t]                                      # (ks, m)
        if metric == "mips":
            acc = acc - qt * ct
        else:
            diff = ct - qt
            acc = acc + diff * diff
    out_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def pq_lut(queries: jax.Array, centroids: jax.Array, *, metric: str = "l2",
           interpret: bool = False) -> jax.Array:
    """(q, d) x (m, ks, dsub) -> (q, m, ks) f32 LUT."""
    nq, d = queries.shape
    m, ks, dsub = centroids.shape
    assert m * dsub == d
    qt = queries.astype(jnp.float32).reshape(nq, m, dsub).transpose(0, 2, 1)
    ct = centroids.astype(jnp.float32).transpose(2, 1, 0)   # (dsub, ks, m)
    lut_t = pl.pallas_call(
        functools.partial(_lut_kernel, metric=metric),
        grid=(nq,),
        in_specs=[
            pl.BlockSpec((1, dsub, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((dsub, ks, m), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, ks, m), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, ks, m), jnp.float32),
        interpret=interpret,
    )(qt, ct)
    return lut_t.transpose(0, 2, 1)
