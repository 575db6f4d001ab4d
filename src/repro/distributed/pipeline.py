"""Pipeline parallelism: GPipe-style microbatch schedule via shard_map +
collective_permute over a `pp` mesh axis.

The production mesh uses (pod, data, model); PP is the alternative layout
for bandwidth-poor inter-pod links — `make_pp_mesh` maps pipeline stages
onto the pod axis. Layers are stacked (L, ...) and split into S stages of
L/S layers; each device scans its own stage slice. The schedule below is
the classic GPipe loop: M microbatches flow through S stages in S+M-1 ticks,
activations hop stages via ppermute.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_pp_mesh(n_stages: int, n_data: int = 1):
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh((n_stages, n_data), ("pp", "data"))


def pipeline_forward(mesh: Mesh, stage_fn: Callable, n_microbatches: int):
    """Build fn(stage_params, x) running the GPipe schedule.

    stage_fn(params_slice, x_mb) -> y_mb, applied by each device to its
    stage's layer slice. stage_params: (S * L_per_stage, ...) stacked layer
    params sharded over 'pp'; x: (M * mb, ...) microbatched inputs,
    replicated (stage 0 reads them; other stages ignore).
    Returns outputs of the LAST stage, replicated.
    """
    S = mesh.shape["pp"]
    M = n_microbatches

    def local(params, x):
        # params arrive as (1, L_per_stage, ...) shards: squeeze stage dim
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index("pp")
        mb_shape = x.shape[1:]
        buf = jnp.zeros(mb_shape, x.dtype)              # current activation
        outs = jnp.zeros((M,) + mb_shape, x.dtype)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            feed = jnp.where(t < M, t, M - 1)
            buf = jnp.where(stage == 0, x[feed], buf)
            y = stage_fn(params, buf)
            # last stage banks its result for microbatch t - (S - 1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            bank = (stage == S - 1) & (t >= S - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(bank, y, outs[out_idx]), out_idx, axis=0)
            # shift activations downstream: stage i -> i+1 (ring permute)
            y_next = jax.lax.ppermute(
                y, "pp", [(i, (i + 1) % S) for i in range(S)])
            return (y_next, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(S + M - 1))
        # broadcast final outputs from the last stage (masked all-reduce)
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), "pp")
        return outs

    fn = jax.shard_map(local, mesh=mesh,
                   in_specs=(P("pp"), P(None)),
                   out_specs=P(None),
                   check_vma=False)
    return fn
