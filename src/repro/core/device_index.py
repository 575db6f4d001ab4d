"""Device (TPU-target) AiSAQ index: HBM chunk table + while_loop beam search.

The HBM-resident `(N, rows, 128)` int32 chunk table is the "storage tier"
(DESIGN.md §2). Per-hop work — chunk gather, parse, inline-PQ ADC — is
`kernels.ops.hop` (Pallas on TPU, jnp ref elsewhere) over the operands
`kernels.ops.hop_inputs` stages once per search, before the loop (an
optimization barrier keeps the compiler from moving them into it). Nothing
N-proportional is ever needed in VMEM: the only per-query fast-tier state is
the (L,) candidate list, the (m, ks) LUT and the re-rank pool — the paper's
`(R + n_ep)·b_pq` residency invariant, tier-shifted.

The search loop is batched: all queries hop together; finished queries pad
their frontier with -1 (the hop kernel emits +inf for those lanes).
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chunk_layout import ChunkLayout, chunk_matrix, \
    pack_chunks_device
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry

# every obs span of this process also lands on the profiler's host
# timeline, beside the device ops, whenever a profile is captured
obs_trace.set_profiler_mirror(jax.profiler.TraceAnnotation)

# rows packed on the host per transfer: bounds a slab at 0.5 GB at sift1m
# widths (1 GB at kilt-e5's) while the device table fills in place
_SLAB_ROWS = 1 << 16


class DeviceIndex(NamedTuple):
    chunk_words: jax.Array        # (N, rows, 128) int32 — HBM storage tier
    centroids: jax.Array          # (m, ks, dsub) f32
    ep_ids: jax.Array             # (n_ep,) int32
    ep_codes: jax.Array           # (n_ep, m) int32

    @property
    def n(self) -> int:
        return self.chunk_words.shape[0]

    def fast_tier_bytes(self, n_queries: int, L: int) -> int:
        """Bytes that must live in the fast tier during search (paper T2)."""
        m, ks = self.centroids.shape[0], self.centroids.shape[1]
        per_q = 4 * (m * ks + 3 * L)          # LUT + candidate list + pool
        resident = self.centroids.size * 4 + self.ep_codes.size * 4
        return int(resident + per_q * n_queries)


@functools.partial(jax.jit, donate_argnums=0)
def _put_slab(table: jax.Array, slab: jax.Array, start) -> jax.Array:
    return jax.lax.dynamic_update_slice_in_dim(table, slab, start, axis=0)


@contextlib.contextmanager
def _timed(counter, name: str):
    """Span `name` over the block; its seconds are added to `counter`."""
    t = time.perf_counter()
    with obs_trace.span(name):
        yield
    counter.inc(time.perf_counter() - t)


def _load_counters(registry: Optional[MetricsRegistry]):
    reg = registry or MetricsRegistry()
    return tuple(reg.counter(f"index_{phase}_seconds", help=doc,
                             unit="seconds")
                 for phase, doc in (
                     ("pack", "host packing of chunk-table slabs"),
                     ("put", "host-to-device transfers and slab writes"),
                     ("entry", "picking the entry point (mean, argsort)")))


def device_table(vectors: np.ndarray, graph: np.ndarray, codes: np.ndarray,
                 layout: ChunkLayout, device=None, *,
                 registry: Optional[MetricsRegistry] = None) -> jax.Array:
    """Pack the (N, rows, 128) chunk table onto `device` slab by slab, so
    neither host nor device ever holds a second full-size copy. Packing
    and placing are timed into `registry`'s `index_pack_seconds` and
    `index_put_seconds` (spans `index.pack`, `index.put`)."""
    pack, put, _ = _load_counters(registry)
    n = vectors.shape[0]
    if n <= _SLAB_ROWS:
        with _timed(pack, "index.pack"):
            slab = pack_chunks_device(vectors, graph, codes, layout)
        with _timed(put, "index.put"):
            return jax.device_put(slab, device)
    with _timed(put, "index.put"):
        table = jnp.zeros((n, layout.device_rows, 128), jnp.int32,
                          device=device)
    for s in range(0, n, _SLAB_ROWS):
        e = min(n, s + _SLAB_ROWS)
        with _timed(pack, "index.pack"):
            slab = pack_chunks_device(vectors[s:e], graph[s:e], codes,
                                      layout)
        with _timed(put, "index.put"):
            table = _put_slab(table, jax.device_put(slab, device), s)
    return table


def from_arrays(vectors: np.ndarray, graph: np.ndarray, centroids: np.ndarray,
                codes: np.ndarray, *, mode: str = "aisaq",
                block_bytes: int = 4096,
                registry: Optional[MetricsRegistry] = None
                ) -> Tuple[DeviceIndex, ChunkLayout]:
    """Place an index on the device. `registry` (default: a registry of
    its own) gets `index_pack_seconds`, `index_put_seconds` and
    `index_entry_seconds`; transfers still in flight when this returns are
    not counted. The device search runs the AiSAQ layout only: a DiskANN
    index (neighbour codes outside the chunk) raises ValueError."""
    if mode != "aisaq":
        raise ValueError(f"the device index holds aisaq chunks only, "
                         f"not {mode!r}")
    n, d = vectors.shape
    layout = ChunkLayout(
        mode=mode, dim=d,
        data_dtype="uint8" if vectors.dtype == np.uint8 else "float32",
        R=graph.shape[1], pq_m=codes.shape[1], block_bytes=block_bytes)
    _, put, entry = _load_counters(registry)
    with _timed(entry, "index.entry"):
        mean = vectors.astype(np.float32).mean(axis=0)
        dd = ((vectors.astype(np.float32) - mean) ** 2).sum(axis=1)
        ep = np.argsort(dd)[:1].astype(np.int32)
    chunk_words = device_table(vectors, graph, codes, layout,
                               registry=registry)
    with _timed(put, "index.put"):
        idx = DeviceIndex(
            chunk_words=chunk_words,
            centroids=jnp.asarray(centroids, jnp.float32),
            ep_ids=jnp.asarray(ep),
            ep_codes=jnp.asarray(codes[ep].astype(np.int32)))
    return idx, layout


def load_device_index(path: str) -> Tuple[DeviceIndex, ChunkLayout, str]:
    """Load a host-format index dir into device arrays (rebuild words)."""
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    codes = np.load(os.path.join(path, "pq_codes.npy"))
    centroids = np.load(os.path.join(path, "pq_centroids.npy"))
    # reconstruct vectors+graph from chunks.bin (vectorized: one strided
    # reshape to an (n, chunk_bytes) view of all chunks, then field slices)
    layout = ChunkLayout(mode=meta["mode"], dim=meta["dim"],
                         data_dtype=meta["data_dtype"], R=meta["R"],
                         pq_m=meta["pq_m"], block_bytes=meta["block_bytes"])
    raw = np.fromfile(os.path.join(path, "chunks.bin"), dtype=np.uint8)
    n = meta["n"]
    chunks = chunk_matrix(raw, layout, n)
    if meta["data_dtype"] == "uint8":
        vecs = chunks[:, :layout.b_full].copy()
    else:
        vecs = np.ascontiguousarray(
            chunks[:, :layout.b_full]).view(np.float32).reshape(n, -1)
    graph = np.ascontiguousarray(
        chunks[:, layout.off_ids:layout.off_ids + layout.R * 4]) \
        .view(np.int32).reshape(n, layout.R)
    if meta.get("relabeled"):
        # locality-relabeled index: undo the pack-time permutation so the
        # device tier works (and returns ids) in ORIGINAL label space —
        # HBM gathers don't care about file-page locality anyway
        from repro.core.relabel import invert_permutation
        old_to_new = np.load(os.path.join(path, "id_map.npy"))
        new_to_old = invert_permutation(old_to_new)
        vecs = vecs[old_to_new]
        codes = codes[old_to_new]
        g = graph[old_to_new]
        graph = np.where(g >= 0, new_to_old[np.where(g >= 0, g, 0)],
                         -1).astype(np.int32)
    idx, layout = from_arrays(vecs, graph, centroids, codes,
                              mode=meta["mode"],
                              block_bytes=meta["block_bytes"])
    return idx, layout, meta["metric"]


# ---------------------------------------------------------------------------
# batched beam search (Algorithm 1 on device)
# ---------------------------------------------------------------------------


def _fresh(ep_ids: jax.Array, cand_ids: jax.Array,
           nids: jax.Array) -> jax.Array:
    """(nq, E) entry ids, (nq, L) candidate ids, (nq, K) neighbour ids ->
    bool mask of the neighbours to offer the trim: a valid id that is not
    an entry point, not in the list and not an earlier neighbour of this
    trip.

    This is the whole visited set, exactly, in O(L) state. The trim keeps
    the top L of `[cand, new]` by PQ distance and `lax.top_k` breaks ties
    toward the lower position, so the list's sorted distances never get
    worse, and an id that was offered and is no longer listed lost to L
    entries at least as close. Those entries, or better ones, fill today's
    list and precede any neighbour in the concatenation. The hop computes
    a neighbour's distance from the search's hop operands and the
    neighbour's own codes, the same way in every slot and trip, so offered
    again at the same distance it cannot re-enter. An entry point's first
    distance comes from the f32 LUT instead, and int8 ADC or another
    summation order can make it differ from its hop distance, so entry
    points are never offered at all, as in a visited set that starts with
    them."""
    all_ids = jnp.concatenate([ep_ids, cand_ids, nids], axis=1)
    P, K = all_ids.shape[1] - nids.shape[1], nids.shape[1]
    # neighbour i (column P + i of all_ids) against every earlier column
    earlier = jnp.arange(P + K)[None, :] < jnp.arange(P, P + K)[:, None]
    dup = jnp.any((nids[:, :, None] == all_ids[:, None, :]) & earlier,
                  axis=2)
    return (nids >= 0) & ~dup


_STATIC = ("k", "L", "w", "max_hops", "layout", "metric", "backend",
           "adc_dtype")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _beam_search(index: DeviceIndex, queries: jax.Array, *, k: int, L: int,
                 w: int = 4, max_hops: int = 128, layout: ChunkLayout,
                 metric: str = "l2", backend: str = "auto",
                 adc_dtype: str = "f32"):
    """The search loop itself: `beam_search_device`'s outputs, then the
    number of valid frontier slots expanded over all loop trips (at most
    hops * nq * w; the hop kernel's grid runs every slot regardless).

    Each phase runs under a `jax.named_scope` (`lut`, `init`, and per trip
    `frontier`, `hop`, `pool`, `visited`, `trim`), so the compiled ops'
    `op_name` metadata says which phase a device op belongs to."""
    nq = queries.shape[0]
    R = layout.R
    with jax.named_scope("lut"):
        lut = ops.build_lut(queries, index.centroids, metric=metric,
                            backend=backend)
        hop_operands = ops.hop_inputs(lut, queries, layout=layout,
                                      backend=backend, adc_dtype=adc_dtype)
        # jnp.tile inflates the staged LUT 4x; unpinned, XLA sinks that
        # broadcast into the loop body and rebuilds it on every trip
        hop_operands = jax.lax.optimization_barrier(hop_operands)
    with jax.named_scope("init"):
        n_ep = index.ep_ids.shape[0]
        ep_ids = jnp.broadcast_to(index.ep_ids[None, :], (nq, n_ep))
        ep_d = jax.vmap(lambda l: jnp.sum(
            jnp.take(l.reshape(-1),
                     index.ep_codes + jnp.arange(lut.shape[1]) * lut.shape[2]),
            axis=-1))(lut)                                    # (nq, n_ep)
        pad = L - n_ep
        cand_ids = jnp.concatenate(
            [ep_ids, jnp.full((nq, pad), -1, jnp.int32)], axis=1)
        cand_d = jnp.concatenate(
            [ep_d, jnp.full((nq, pad), jnp.inf, jnp.float32)], axis=1)
        cand_exp = jnp.concatenate(
            [jnp.zeros((nq, n_ep), bool), jnp.ones((nq, pad), bool)], axis=1)
        pool_ids = jnp.full((nq, L), -1, jnp.int32)
        pool_d = jnp.full((nq, L), jnp.inf, jnp.float32)

    def cond(state):
        cand_ids, cand_d, cand_exp, pool_ids, pool_d, hops, _ = state
        active = jnp.any(~cand_exp & jnp.isfinite(cand_d))
        return active & (hops < max_hops)

    def body(state):
        (cand_ids, cand_d, cand_exp, pool_ids, pool_d, hops,
         expanded) = state
        # 1. frontier: top-w unexpanded by PQ distance
        with jax.named_scope("frontier"):
            sel = jnp.where(cand_exp, jnp.inf, cand_d)
            negd, pos = jax.lax.top_k(-sel, w)                 # (nq, w)
            fvalid = jnp.isfinite(negd)
            fids = jnp.where(fvalid,
                             jnp.take_along_axis(cand_ids, pos, axis=1), -1)
            # marked by comparison, not a scatter: XLA on a TPU runs
            # scatter updates nearly one at a time
            cand_exp = cand_exp | jnp.any(
                (pos[:, :, None] == jnp.arange(L)) & fvalid[:, :, None],
                axis=1)
            # counted per slot from fids, so the TPU compiler adds it to
            # the fusion that makes fids: no extra launch per trip (a
            # scalar sum of fvalid costs two)
            expanded = expanded + (fids >= 0).astype(jnp.int32)
        # 2. expand: chunk gather + parse + exact dist + neighbor ADC
        with jax.named_scope("hop"):
            exact, nids, nd = ops.hop(
                index.chunk_words, fids, hop_operands, layout=layout,
                metric=metric, backend=backend)
        # 3. re-rank pool (exact distances of expanded nodes)
        with jax.named_scope("pool"):
            pool_ids = jnp.concatenate([pool_ids, fids], axis=1)
            pool_d = jnp.concatenate([pool_d, exact], axis=1)
            npd, ppos = jax.lax.top_k(-pool_d, L)
            pool_d = -npd
            pool_ids = jnp.take_along_axis(pool_ids, ppos, axis=1)
        # 4. neighbor dedup against the entry points, the candidate list
        # and the trip's earlier neighbours (see `_fresh`)
        with jax.named_scope("visited"):
            nids_f = nids.reshape(nq, w * R)
            fresh = _fresh(ep_ids, cand_ids, nids_f)
            nd_f = jnp.where(fresh, nd.reshape(nq, w * R), jnp.inf)
            nids_f = jnp.where(fresh, nids_f, -1)
        # 5. trim candidate list to L by PQ distance
        with jax.named_scope("trim"):
            all_ids = jnp.concatenate([cand_ids, nids_f], axis=1)
            all_d = jnp.concatenate([cand_d, nd_f], axis=1)
            all_exp = jnp.concatenate(
                [cand_exp, jnp.ones_like(nids_f, bool) & ~jnp.isfinite(nd_f)],
                axis=1)
            negd2, cpos = jax.lax.top_k(-all_d, L)
            cand_d = -negd2
            cand_ids = jnp.take_along_axis(all_ids, cpos, axis=1)
            cand_exp = jnp.take_along_axis(all_exp, cpos, axis=1)
        return (cand_ids, cand_d, cand_exp, pool_ids, pool_d, hops + 1,
                expanded)

    state = (cand_ids, cand_d, cand_exp, pool_ids, pool_d,
             jnp.array(0, jnp.int32), jnp.zeros((nq, w), jnp.int32))
    state = jax.lax.while_loop(cond, body, state)
    _, _, _, pool_ids, pool_d, hops, expanded = state
    negd, pos = jax.lax.top_k(-pool_d, k)
    return (jnp.take_along_axis(pool_ids, pos, axis=1), -negd, hops,
            jnp.sum(expanded))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _served_search(index: DeviceIndex, queries: jax.Array, *, k: int, L: int,
                   w: int = 4, max_hops: int = 128, layout: ChunkLayout,
                   metric: str = "l2", backend: str = "auto",
                   adc_dtype: str = "f32"):
    """The program a served call runs: `_beam_search`'s top-k ids,
    flattened, then its loop trips and expanded slots, as the one int32
    output. Each output of a TPU program costs the runtime about 0.1 ms a
    call (v5e, 1 M rows, nq 16: 8.93 ms a call with this one output, 9.27
    with `_beam_search`'s four), so the served call's program returns
    only the buffer it fetches."""
    ids, _, hops, expanded = _beam_search(
        index, queries, k=k, L=L, w=w, max_hops=max_hops, layout=layout,
        metric=metric, backend=backend, adc_dtype=adc_dtype)
    return jnp.concatenate([ids.reshape(-1), jnp.stack([hops, expanded])])


def beam_search_device(index: DeviceIndex, queries: jax.Array, *, k: int,
                       L: int, w: int = 4, max_hops: int = 128,
                       layout: ChunkLayout, metric: str = "l2",
                       backend: str = "auto", adc_dtype: str = "f32"):
    """Batched AiSAQ beam search. Returns (topk_ids, topk_d, hops).

    adc_dtype="int8" runs neighbor ADC through the int8 fused-hop kernel
    (2x MXU rate); the exact re-rank distances stay f32, so end recall is
    within quantization noise of the f32 path.

    `beam_search_device.lower(...)` lowers the jitted program this runs
    (`_beam_search`, whose fourth output is the count of expanded
    frontier slots); the served path runs it inside `_served_search`.
    """
    ids, d, hops, _ = _beam_search(
        index, queries, k=k, L=L, w=w, max_hops=max_hops, layout=layout,
        metric=metric, backend=backend, adc_dtype=adc_dtype)
    return ids, d, hops


beam_search_device.lower = _beam_search.lower
