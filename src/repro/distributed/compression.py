"""Gradient compression for the data-parallel all-reduce.

int8 stochastic-free symmetric quantization with a two-phase exchange:
  1. psum the per-tensor max-abs (scalar — negligible wire bytes),
  2. quantize to int8 against the GLOBAL scale, sum as int32, dequantize.

Wire-format note (DESIGN.md §6): XLA exposes no int8 ring all-reduce, so we
express the exchange as int32 psum of int8-valued payloads; on TPU runtimes
with int8 collective support this lowers to a 4x-smaller transfer. The
numerics (what training actually sees) are exactly int8-grade either way,
so convergence claims made with this module transfer to real deployments.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp


def quantize_int8(x: jax.Array, scale: jax.Array) -> jax.Array:
    q = jnp.clip(jnp.round(x / jnp.maximum(scale, 1e-20) * 127.0),
                 -127, 127)
    return q.astype(jnp.int8)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale / 127.0


def compressed_psum(grads: Any, axis_name: str) -> Any:
    """Per-tensor int8-quantized gradient all-reduce over `axis_name`.

    Must run inside shard_map/pmap with `axis_name` bound. Small tensors
    (<1024 elems: norms, biases) skip compression — their bytes don't matter
    and they are precision-critical.
    """
    n = jax.lax.psum(1, axis_name)

    def one(g):
        g = g.astype(jnp.float32)
        if g.size < 1024:
            return jax.lax.psum(g, axis_name) / n
        scale = jax.lax.pmax(jnp.max(jnp.abs(g)), axis_name)
        q = quantize_int8(g, scale)
        tot = jax.lax.psum(q.astype(jnp.int32), axis_name)
        return dequantize_int8(tot, scale) / n

    return jax.tree.map(one, grads)


def make_compressed_dp_grads(loss_fn, mesh, batch_example,
                             dp_axis: str = "data"):
    """Explicit-DP gradient fn: params replicated, batch sharded over
    dp_axis, grads exchanged via compressed_psum (replacing the implicit
    GSPMD fp32 all-reduce). `batch_example` fixes the batch pytree
    structure for the in_specs."""
    from jax.sharding import PartitionSpec as P

    def local(params, batch):
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params,
                                                                   batch)
        g = compressed_psum(g, dp_axis)
        loss = jax.lax.pmean(loss, dp_axis)
        return loss, g

    bspecs = jax.tree.map(lambda _: P(dp_axis), batch_example)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), bspecs),
        out_specs=(P(), P()),
        check_vma=False)
