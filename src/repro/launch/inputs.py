"""Dry-run cell construction: (arch x shape x mesh) -> lowerable jit + specs.

`input_specs(arch, shape)` returns ShapeDtypeStruct stand-ins for every model
input (weak-type-correct, shardable, no device allocation); `build_cell`
bundles them with the step function and in/out NamedShardings.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, IndexConfig, ShapeConfig
from repro.configs.registry import get_arch
from repro.distributed import sharding as SH
from repro.distributed.train_step import (TrainState, default_optimizer,
                                          make_serve_step, make_train_step)

SDS = jax.ShapeDtypeStruct


class Cell(NamedTuple):
    arch_id: str
    shape_name: str
    fn: Callable                  # fn(*args)
    args: tuple                   # tree of ShapeDtypeStruct
    in_shardings: tuple
    out_shardings: Any
    meta: dict                    # model_flops, bytes estimates, notes
    donate: tuple = ()            # donated arg indices (state/cache alias)


def _ns(mesh, tree_of_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# per-family batch ShapeDtypeStructs
# ---------------------------------------------------------------------------


def lm_batch_sds(arch: ArchConfig, shape: ShapeConfig) -> dict:
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "lm_train":
        return {"tokens": SDS((B, S), jnp.int32),
                "labels": SDS((B, S), jnp.int32)}
    if shape.kind == "lm_prefill":
        return {"tokens": SDS((B, S), jnp.int32)}
    if shape.kind == "lm_decode":
        return {"token": SDS((B,), jnp.int32), "pos": SDS((), jnp.int32)}
    raise ValueError(shape.kind)


def gnn_batch_sds(arch: ArchConfig, shape: ShapeConfig, ways: int = 512
                  ) -> dict:
    import os
    f32, i32 = jnp.float32, jnp.int32
    if shape.kind == "gnn_full":
        if os.environ.get("REPRO_GNN") == "sharded":
            # pre-partitioned by destination shard (gnn_sharded.partition_edges)
            e_pad = -(-int(shape.n_edges * 1.25) // ways // 8) * 8
            return {"feats": SDS((shape.n_nodes, shape.d_feat), f32),
                    "edges": SDS((ways, e_pad, 2), i32),
                    "labels": SDS((shape.n_nodes,), i32),
                    "mask": SDS((shape.n_nodes,), f32)}
        # edge list padded to a shardable multiple (gnn.pad_edges no-ops)
        ne = -(-shape.n_edges // 512) * 512
        return {"feats": SDS((shape.n_nodes, shape.d_feat), f32),
                "edges": SDS((ne, 2), i32),
                "labels": SDS((shape.n_nodes,), i32),
                "mask": SDS((shape.n_nodes,), f32)}
    if shape.kind == "gnn_minibatch":
        B, (f1, f2), F = shape.batch_nodes, shape.fanout, shape.d_feat
        return {"seed_feats": SDS((B, F), f32),
                "nbr1_feats": SDS((B, f1, F), f32),
                "nbr2_feats": SDS((B, f1, f2, F), f32),
                "labels": SDS((B,), i32)}
    if shape.kind == "gnn_batched":
        G = shape.batch_graphs
        return {"feats": SDS((G, shape.n_nodes, shape.d_feat), f32),
                "edges": SDS((G, shape.n_edges, 2), i32),
                "labels": SDS((G,), i32)}
    raise ValueError(shape.kind)


def rec_batch_sds(arch: ArchConfig, shape: ShapeConfig) -> dict:
    cfg = arch.model
    f32, i32 = jnp.float32, jnp.int32
    B = shape.batch
    if cfg.kind == "sasrec":
        S = cfg.seq_len
        b = {"seq": SDS((B, S), i32)}
        if shape.kind == "rec_train":
            b.update({"pos_items": SDS((B, S), i32),
                      "neg_items": SDS((B, S), i32),
                      "seq_mask": SDS((B, S), f32)})
        elif shape.kind == "rec_serve":
            b["target"] = SDS((B,), i32)
        elif shape.kind == "rec_retrieval":
            b = {"seq": SDS((1, S), i32),
                 "cand_ids": SDS((shape.n_candidates,), i32)}
        return b
    hot = cfg.multi_hot
    b = {"sparse": SDS((B, cfg.n_sparse, hot), i32)}
    if cfg.n_dense:
        b["dense"] = SDS((B, cfg.n_dense), f32)
    if shape.kind == "rec_train":
        b["label"] = SDS((B,), i32)
    if shape.kind == "rec_retrieval":
        b = {"sparse": SDS((1, cfg.n_sparse, hot), i32),
             "cand_ids": SDS((shape.n_candidates,), i32)}
        if cfg.n_dense:
            b["dense"] = SDS((1, cfg.n_dense), f32)
    return b


def input_specs(arch: ArchConfig, shape: ShapeConfig) -> dict:
    if arch.family == "lm":
        return lm_batch_sds(arch, shape)
    if arch.family == "gnn":
        return gnn_batch_sds(arch, shape)
    if arch.family == "recsys":
        return rec_batch_sds(arch, shape)
    if arch.family == "ann":
        cfg: IndexConfig = arch.model
        return {"queries": SDS((shape.batch, cfg.dim), jnp.float32)}
    raise ValueError(arch.family)


# ---------------------------------------------------------------------------
# model FLOPs (the "useful work" yardstick for §Roofline)
# ---------------------------------------------------------------------------


def model_flops(arch: ArchConfig, shape: ShapeConfig) -> float:
    if arch.family == "lm":
        cfg = arch.model
        n_act = cfg.n_active_params()
        if shape.kind == "lm_train":
            return 6.0 * n_act * shape.global_batch * shape.seq_len
        if shape.kind == "lm_prefill":
            return 2.0 * n_act * shape.global_batch * shape.seq_len
        return 2.0 * n_act * shape.global_batch        # decode: per token
    if arch.family == "gnn":
        cfg = arch.model
        H = cfg.d_hidden
        if shape.kind == "gnn_full":
            per_layer = 2 * shape.n_edges * H + 4 * shape.n_nodes * H * H
            fwd = cfg.n_layers * per_layer + 2 * shape.n_nodes * shape.d_feat * H
            return 3.0 * fwd
        if shape.kind == "gnn_minibatch":
            B, (f1, f2) = shape.batch_nodes, shape.fanout
            nodes = B * (1 + f1 + f1 * f2)
            return 3.0 * (4 * nodes * shape.d_feat * H + 4 * B * H * H)
        nodes = shape.batch_graphs * shape.n_nodes
        return 3.0 * cfg.n_layers * 4 * nodes * cfg.d_hidden * shape.d_feat
    if arch.family == "recsys":
        cfg = arch.model
        B = shape.batch
        if shape.kind == "rec_retrieval":
            return 2.0 * shape.n_candidates * cfg.embed_dim
        dims = []
        if cfg.kind == "dlrm":
            dims = list(zip((cfg.n_dense,) + cfg.bot_mlp[:-1], cfg.bot_mlp))
            f = cfg.n_sparse + 1
            d_int = f * (f - 1) // 2 + cfg.bot_mlp[-1]
            dims += list(zip((d_int,) + cfg.top_mlp[:-1], cfg.top_mlp))
            dims += [(f * cfg.embed_dim, f)]          # interaction
        elif cfg.kind in ("dcnv2", "widedeep"):
            d0 = cfg.n_dense + cfg.n_sparse * cfg.embed_dim
            dims = list(zip((d0,) + cfg.mlp, cfg.mlp + (1,)))
            dims += [(d0, d0)] * cfg.n_cross_layers
        else:  # sasrec
            S, D = cfg.seq_len, cfg.embed_dim
            per_tok = 4 * D * D + 2 * S * D + 2 * D * D
            dims = [(S * per_tok // 2, 1)]
        mults = sum(a * b for a, b in dims)
        fac = 6.0 if shape.kind == "rec_train" else 2.0
        return fac * B * mults
    if arch.family == "ann":
        cfg = arch.model
        # per query: ~hops * w * (R * m ADC adds + exact dist) + LUT
        hops, w = 64, cfg.beamwidth
        per_q = hops * w * (cfg.R * cfg.pq_m * 2 + 2 * cfg.dim) \
            + 2 * cfg.dim * cfg.pq_ks
        return float(shape.batch * per_q)
    raise ValueError(arch.family)


# ---------------------------------------------------------------------------
# cell builder
# ---------------------------------------------------------------------------


def build_cell(arch_id: str, shape_name: str, mesh: Mesh) -> Cell:
    arch = get_arch(arch_id)
    shape = arch.shape(shape_name)
    fam = arch.family
    meta = {"model_flops": model_flops(arch, shape)}

    if fam == "ann":
        return _build_ann_cell(arch, shape, mesh, meta)

    # ---- parameter/optimizer shapes + specs (abstract, no allocation) ----
    train_kind = shape.kind in ("lm_train", "gnn_full", "gnn_minibatch",
                                "gnn_batched", "rec_train")
    if fam == "recsys":
        # table-wise replication is serve-only (§Perf "tablewise")
        rule = SH.rec_param_rule(mesh, tablewise=not train_kind)
    else:
        rule = {"lm": SH.lm_param_rule,
                "gnn": SH.gnn_param_rule}[fam](mesh)
    init_fn = _make_init(arch, shape, mesh)
    p_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    p_specs = SH.spec_tree(p_shapes, rule)
    import os as _os
    gnn_sharded = (_os.environ.get("REPRO_GNN") == "sharded"
                   and shape.kind == "gnn_full")
    n_dev = 1
    for a in mesh.axis_names:
        n_dev *= mesh.shape[a]
    if gnn_sharded:
        batch_sds = gnn_batch_sds(arch, shape, ways=n_dev)
    else:
        batch_sds = input_specs(arch, shape)
    bspec_all = SH.batch_specs(shape.kind, mesh)
    b_specs = {k: bspec_all[k] for k in batch_sds}
    if gnn_sharded:
        b_specs["edges"] = P(tuple(mesh.axis_names), None, None)
    train = shape.kind in ("lm_train", "gnn_full", "gnn_minibatch",
                           "gnn_batched", "rec_train")

    if train:
        opt_init, _ = default_optimizer()
        o_shapes = jax.eval_shape(opt_init, p_shapes)
        o_specs = SH.opt_state_specs(p_specs, p_shapes, o_shapes)
        state_sds = TrainState(p_shapes, o_shapes)
        state_specs = TrainState(p_specs, o_specs)
        # microbatch LM training so layer-scan residuals (L x B_mb x S x D
        # bf16) stay under the budget; fewer microbatches = fewer FSDP
        # weight re-gathers (REPRO_MB_BUDGET_GB tunes the tradeoff, §Perf)
        n_mb = 1
        if shape.kind == "lm_train":
            budget = float(_os.environ.get("REPRO_MB_BUDGET_GB", "4")) * 1e9
            dp = 1
            for a in SH.dp_axes(mesh):
                dp *= mesh.shape[a]
            b_local = shape.global_batch // dp
            cfg = arch.model
            resid_per_seq = 2 * cfg.n_layers * shape.seq_len * cfg.d_model
            b_mb_max = max(1, int(budget // resid_per_seq))
            n_mb = max(1, -(-b_local // b_mb_max))
            while b_local % n_mb:
                n_mb += 1
        fn = make_train_step(arch, shape, microbatches=n_mb)
        meta["microbatches"] = n_mb
        args = (state_sds, batch_sds)
        in_sh = (_ns(mesh, state_specs), _ns(mesh, b_specs))
        out_sh = (_ns(mesh, state_specs), None)
        meta["params"] = _tree_bytes(p_shapes)
        return Cell(arch.arch_id, shape.name, fn, args, in_sh, out_sh, meta,
                    donate=(0,))   # state buffers alias across steps

    # ---- serve cells ------------------------------------------------------
    fn0 = make_serve_step(arch, shape)
    if shape.kind == "lm_decode":
        from repro.models.transformer import init_cache
        cfg = arch.model
        cache_sds = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
        # batch shards over dp only when divisible (long_500k has B=1:
        # replicate batch, shard the KV sequence dim over `model` — SP decode)
        dp = SH.dp_axes(mesh)
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        bax = dp if shape.global_batch % dp_size == 0 else ()
        cspec = P(None, bax if bax else None, "model", None, None)
        cache_spec = jax.tree.map(lambda _: cspec, cache_sds)
        b_specs = {"token": P(bax if bax else None), "pos": P()}
        args = (p_shapes, cache_sds, batch_sds)
        in_sh = (_ns(mesh, p_specs), _ns(mesh, cache_spec), _ns(mesh, b_specs))
        out_sh = (None, _ns(mesh, cache_spec))
        return Cell(arch.arch_id, shape.name, fn0, args, in_sh, out_sh, meta,
                    donate=(1,))   # KV cache aliases in place
    args = (p_shapes, batch_sds)
    in_sh = (_ns(mesh, p_specs), _ns(mesh, b_specs))
    return Cell(arch.arch_id, shape.name, fn0, args, in_sh, None, meta)


def _make_init(arch: ArchConfig, shape: ShapeConfig, mesh: Mesh):
    if arch.family == "lm":
        from repro.models.transformer import init_lm
        ep = mesh.shape.get("model", 1)
        return functools.partial(init_lm, cfg=arch.model, ep=ep)
    if arch.family == "gnn":
        from repro.models.gnn import init_gnn
        return functools.partial(init_gnn, cfg=arch.model, d_feat=shape.d_feat)
    from repro.models.recsys import init_recsys
    return functools.partial(init_recsys, cfg=arch.model)


def _tree_bytes(shapes) -> int:
    return int(jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda s: int(s.size) * s.dtype.itemsize, shapes), 0))


# ---------------------------------------------------------------------------
# ANN cells (the paper's own architecture)
# ---------------------------------------------------------------------------


def _build_ann_cell(arch: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                    meta: dict) -> Cell:
    from repro.core.chunk_layout import layout_for
    from repro.core.sharded_search import ShardedIndexArrays, sharded_search_fn

    cfg: IndexConfig = arch.model
    layout = layout_for(cfg, "aisaq")
    total_chunk_gb = cfg.n_vectors * layout.device_stride / 1e9
    per_dev_budget = 8.0     # GB of HBM we allow the chunk table per device
    # mode A: index shards over `model` only, queries over dp;
    # mode B: index shards over EVERY axis, queries replicated + chunked.
    mode_b = total_chunk_gb / mesh.shape["model"] > per_dev_budget
    if mode_b:
        shard_axes = tuple(mesh.axis_names)
        query_axes: tuple = ()
    else:
        shard_axes = ("model",)
        query_axes = SH.dp_axes(mesh)
    n_shards = 1
    for a in shard_axes:
        n_shards *= mesh.shape[a]
    N_s = -(-cfg.n_vectors // n_shards)
    m, ks = cfg.pq_m, cfg.pq_ks
    dsub = cfg.dim // m
    arrays = ShardedIndexArrays(
        chunk_words=SDS((n_shards, N_s, layout.device_rows, 128), jnp.int32),
        centroids=SDS((m, ks, dsub), jnp.float32),
        ep_ids=SDS((n_shards, cfg.n_ep), jnp.int32),
        ep_codes=SDS((n_shards, cfg.n_ep, m), jnp.int32),
        offsets=SDS((n_shards,), jnp.int32))
    queries = SDS((shape.batch, cfg.dim), jnp.float32)
    # mode B searches the replicated batch in chunks of 128 queries; the
    # search state is O(L) a query, and whether the LUTs and staged hop
    # operands of a whole batch need the chunking is not measured
    qchunk = 128 if (mode_b and shape.batch > 128) else 0
    search = sharded_search_fn(
        mesh, k=10, L=128, w=cfg.beamwidth, max_hops=cfg.max_hops,
        layout=layout, metric=cfg.metric, backend="ref",
        query_axes=query_axes, shard_axes=shard_axes, query_chunk=qchunk)
    sspec = P(shard_axes, None, None, None)
    arr_specs = ShardedIndexArrays(
        chunk_words=sspec, centroids=P(),
        ep_ids=P(shard_axes, None), ep_codes=P(shard_axes, None, None),
        offsets=P(shard_axes))
    qspec = P(query_axes, None) if query_axes else P(None, None)
    in_sh = (_ns(mesh, arr_specs), NamedSharding(mesh, qspec))
    meta.update(mode="B" if mode_b else "A", n_shards=n_shards,
                chunk_gb_per_dev=total_chunk_gb / n_shards)
    return Cell(arch.arch_id, shape.name,
                lambda a, q: search(a, q), (arrays, queries), in_sh, None,
                meta)
