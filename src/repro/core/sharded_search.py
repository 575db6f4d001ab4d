"""Multi-device sharded ANN search — the paper's Fig. 5 multi-server system.

Each device owns one dataset shard with its OWN sub-index (subgraph + entry
point), exactly like the paper's per-server indices. A query fans out to all
shards (replicated over the shard axes), each runs the local AiSAQ beam
search, and local top-k results merge via all-gather + global top-k.

Mesh mapping (DESIGN.md §2):
  query batch  -> ('pod', 'data')   (paper: request load-balancer)
  index shards -> ('model',)        (paper: servers on the ethernet/Lustre tier)

This is the DEVICE-tier fan-out.  The storage-backed host tier it mirrors
lives in the three-layer core (``core.adc`` numerics, ``core.traversal``
pipelined beam engine, ``core.index_io`` format/lifecycle); per-shard
device search has no storage pipeline to overlap, so the host-only
``pipeline=``/``prefetch=`` knobs do not appear here.

The shard MATH — which vector belongs to which shard, and how partial
per-shard top-k lists merge — is shared with the process-level storage
tier (``serving.cluster`` / ``serving.router``) via ``core.shard_math``:
``ShardAssignment`` / ``contiguous_shards`` produce the same
(offset, count) splits ``stack_shards`` consumes here, and
``merge_topk`` is the host twin of this module's all-gather +
``lax.top_k`` merge.  They are re-exported below so either tier can
import them from either module.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.chunk_layout import ChunkLayout
from repro.core.device_index import DeviceIndex, beam_search_device, \
    device_table
from repro.core.shard_math import (          # noqa: F401  (re-exported)
    ShardAssignment, contiguous_shards, merge_topk)


class ShardedIndexArrays(NamedTuple):
    """Stacked per-shard index arrays; leading dim = shard."""

    chunk_words: jax.Array    # (S_h, N_s, rows, 128) int32
    centroids: jax.Array      # (m, ks, dsub) f32 — replicated
    ep_ids: jax.Array         # (S_h, n_ep) int32 (shard-local ids)
    ep_codes: jax.Array       # (S_h, n_ep, m) int32
    offsets: jax.Array        # (S_h,) int32 global-id offset per shard


def stack_shards(shards: Sequence[Tuple[int, "np.ndarray", "np.ndarray"]],
                 centroids: np.ndarray, codes_full: np.ndarray,
                 layout: ChunkLayout, mesh, *,
                 shard_axes: Tuple[str, ...] = ("model",)
                 ) -> ShardedIndexArrays:
    """shards: list of (global_offset, shard_vectors, shard_graph).

    Returns the arrays placed on `mesh` with shards over `shard_axes`. Each
    shard's chunk table is packed straight onto the devices that hold it,
    so no device ever stages another shard's table."""
    n_max = max(v.shape[0] for _, v, _ in shards)
    eps, epc, offs = [], [], []
    for off, vecs, _ in shards:
        codes = codes_full[off:off + vecs.shape[0]]
        mean = vecs.astype(np.float32).mean(axis=0)
        dd = ((vecs.astype(np.float32) - mean) ** 2).sum(axis=1)
        ep = np.argsort(dd)[:1].astype(np.int32)
        eps.append(ep)
        epc.append(codes[ep].astype(np.int32))
        offs.append(off)
    sh, _ = input_sharding(mesh, query_axes=(), shard_axes=shard_axes)
    shape = (len(shards), n_max, layout.device_rows, 128)
    tables = {}
    per_device = []
    for dev, idx in sh.chunk_words.addressable_devices_indices_map(
            shape).items():
        s = idx[0].start or 0
        if s not in tables:
            off, vecs, graph = shards[s]
            codes = codes_full[off:off + vecs.shape[0]]
            pad = n_max - vecs.shape[0]   # ragged shards: unreachable rows
            vecs = np.pad(vecs, ((0, pad), (0, 0)))
            graph = np.pad(graph, ((0, pad), (0, 0)), constant_values=-1)
            tables[s] = (vecs, graph, codes)
        per_device.append(
            device_table(*tables[s], layout, device=dev)[None])
    return ShardedIndexArrays(
        chunk_words=jax.make_array_from_single_device_arrays(
            shape, sh.chunk_words, per_device),
        centroids=jax.device_put(np.asarray(centroids, np.float32),
                                 sh.centroids),
        ep_ids=jax.device_put(np.stack(eps), sh.ep_ids),
        ep_codes=jax.device_put(np.stack(epc), sh.ep_codes),
        offsets=jax.device_put(np.array(offs, np.int32), sh.offsets))


def sharded_search_fn(mesh, *, k: int, L: int, w: int, max_hops: int,
                      layout: ChunkLayout, metric: str, backend: str = "auto",
                      query_axes: Tuple[str, ...] = ("data",),
                      shard_axes: Tuple[str, ...] = ("model",),
                      query_chunk: int = 0, adc_dtype: str = "f32"):
    """Returns a jit-able fn(arrays: ShardedIndexArrays, queries) -> ids, d.

    queries: (B, d) sharded over query_axes (may be empty => replicated —
    "mode B", index sharded over every axis for billion-scale tables);
    index shards over shard_axes. Output: (B, k) ids + dists like queries.

    query_chunk > 0 processes queries in chunks inside lax.map. The search
    state per query is O(L), whatever N_shard; what grows with the batch
    is each query's LUT and staged hop operands, and whether that needs
    the chunking is not measured.
    """
    query_axes = _norm_axes(query_axes)
    qspec = P(query_axes, None) if query_axes else P(None, None)
    sspec = P(shard_axes, None, None, None)

    def local_search(words, cents, ep_ids, ep_codes, offset, queries):
        # shapes inside shard_map: words (1, N_s, rows, 128), queries (B_l, d)
        idx = DeviceIndex(chunk_words=words[0], centroids=cents,
                          ep_ids=ep_ids[0], ep_codes=ep_codes[0])

        def one_chunk(qc):
            ids, d, hops = beam_search_device(
                idx, qc, k=k, L=L, w=w, max_hops=max_hops, layout=layout,
                metric=metric, backend=backend, adc_dtype=adc_dtype)
            return ids, d

        nq = queries.shape[0]
        if query_chunk and nq > query_chunk:
            nc = nq // query_chunk
            ids, d = jax.lax.map(
                one_chunk, queries.reshape(nc, query_chunk, -1))
            ids, d = ids.reshape(nq, k), d.reshape(nq, k)
        else:
            ids, d = one_chunk(queries)
        gids = jnp.where(ids >= 0, ids + offset[0], -1)
        d = jnp.where(ids >= 0, d, jnp.inf)
        # merge across shards: (S, B_l, k) -> top-k per query
        all_ids = jax.lax.all_gather(gids, shard_axes, axis=0, tiled=False)
        all_d = jax.lax.all_gather(d, shard_axes, axis=0, tiled=False)
        S = all_ids.shape[0]
        all_ids = jnp.moveaxis(all_ids, 0, 1).reshape(queries.shape[0], S * k)
        all_d = jnp.moveaxis(all_d, 0, 1).reshape(queries.shape[0], S * k)
        negd, pos = jax.lax.top_k(-all_d, k)
        return jnp.take_along_axis(all_ids, pos, axis=1), -negd

    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(sspec, P(), P(shard_axes, None), P(shard_axes, None, None),
                  P(shard_axes), qspec),
        out_specs=(qspec, qspec),
        check_vma=False)

    def search(arrays: ShardedIndexArrays, queries: jax.Array):
        return fn(arrays.chunk_words, arrays.centroids, arrays.ep_ids,
                  arrays.ep_codes, arrays.offsets, queries)

    return search


def _norm_axes(axes) -> Tuple[str, ...]:
    """Drop None placeholders: (None,) means 'replicated' (an empty spec)."""
    return tuple(a for a in (axes or ()) if a is not None)


def input_sharding(mesh, query_axes=("data",), shard_axes=("model",)):
    """NamedShardings for placing ShardedIndexArrays + queries on the mesh."""
    query_axes = _norm_axes(query_axes)
    qspec = P(query_axes, None) if query_axes else P(None, None)
    return ShardedIndexArrays(
        chunk_words=NamedSharding(mesh, P(shard_axes, None, None, None)),
        centroids=NamedSharding(mesh, P()),
        ep_ids=NamedSharding(mesh, P(shard_axes, None)),
        ep_codes=NamedSharding(mesh, P(shard_axes, None, None)),
        offsets=NamedSharding(mesh, P(shard_axes)),
    ), NamedSharding(mesh, qspec)
