"""Activation sharding constraints (GSPMD guidance).

Without explicit constraints GSPMD is free to pick intermediate layouts from
weight shardings alone — on the production mesh it chose to REPLICATE the
global batch per device and shard d_model instead (observed: 30+ GB of
f32[256,4096,·] temps). `constrain(x, name)` pins the batch/dp sharding at
the few points that anchor propagation.

The policy is process-global and set by the training launcher
(`launch/train.py`) via `set_policy(mesh, ...)`; model code stays
mesh-agnostic. When no policy is active (CPU unit tests), constrain() is
the identity.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_POLICY: Dict[str, NamedSharding] = {}
_MESH: Optional[Mesh] = None
_CP_ATTENTION = False       # context-parallel attention over `model`


def set_policy(mesh: Optional[Mesh], cp_attention: bool = False,
               **overrides) -> None:
    """Install the default LM/GNN/recsys activation policy for `mesh`.

    Pass mesh=None to clear (unit-test mode). `cp_attention` enables
    sequence-sharded flash attention over the `model` axis (§Perf
    iteration "cp-attn")."""
    global _POLICY, _MESH, _CP_ATTENTION
    _POLICY = {}
    _MESH = mesh
    _CP_ATTENTION = cp_attention and mesh is not None \
        and "model" in (mesh.axis_names if mesh else ())
    if mesh is None:
        return
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    specs = {
        "hidden": P(dp, None, None),            # (B, S, D)
        "qkv": P(dp, None, None, None),         # (B, S, H, hd) heads local
        "tokens2d": P(dp, None),                # (B, S)
        "vec": P(dp),                           # (B,)
        "logits_v": P(dp, None, "model"),       # (B, c, V)
        # (E, C, D): E over model (EP). REPRO_MOE_DISP=dp additionally
        # shards capacity slots over dp (§Perf "moe-disp" experiment)
        "moe_expert": (P("model", dp, None)
                       if os.environ.get("REPRO_MOE_DISP") == "dp"
                       else P("model", None, None)),
        "moe_tokens": P(dp, None),              # (T, D) token-major
        "table_rows": P("model", None),         # gathered embedding rows
        "edges": P(dp, None),                   # (E, 2)
        "cache": P(None, dp, "model", None, None),
    }
    specs.update({k: v for k, v in overrides.items()})
    _POLICY = {k: NamedSharding(mesh, v) for k, v in specs.items()}


def constrain(x: jax.Array, name: str) -> jax.Array:
    ns = _POLICY.get(name)
    if ns is None:
        return x
    return jax.lax.with_sharding_constraint(x, ns)


def cp_attention_wrap(flash_fn, seq_len: int):
    """Context-parallel attention: shard the q sequence over `model`.

    flash_fn(q, k, v, q_off) with q (B, S_local, H, hd), k/v full-sequence.
    Returns a shard_map'd fn(q, k, v) -> out, or None if CP is inapplicable
    (policy off, or S not divisible by the axis)."""
    if not _CP_ATTENTION or _MESH is None:
        return None
    ways = _MESH.shape["model"]
    if seq_len % ways or seq_len // ways < 128:
        return None
    dp = tuple(a for a in ("pod", "data") if a in _MESH.axis_names)
    s_local = seq_len // ways

    def local(q, k, v):
        off = jax.lax.axis_index("model") * s_local
        return flash_fn(q, k, v, off)

    return jax.shard_map(
        local, mesh=_MESH,
        in_specs=(P(dp, "model", None, None), P(dp, None, None, None),
                  P(dp, None, None, None)),
        out_specs=P(dp, "model", None, None),
        check_vma=False)
