"""Pallas TPU kernel: the fused AiSAQ hop — THE paper's hot loop on TPU.

For each (query q, beam slot i) the kernel:
  1. DMAs node chunk row ``chunks[ids[q, i]]`` HBM->VMEM via scalar-prefetch
     block indexing (the paged-attention-style indirection; this is the TPU
     analogue of the paper's single 4 KiB LBA read per hop),
  2. parses the chunk *in VMEM*: full-precision vector, neighbor ids, and the
     INLINE neighbor PQ codes (AiSAQ's contribution — nothing N-sized is ever
     resident in the fast tier),
  3. emits the exact query<->node distance (re-rank pool) and all R neighbor
     ADC distances.

The table is (N, rows, 128) int32 (``ChunkLayout.device_rows``), so a chunk
row is one tile-legal (1, rows, 128) block and every field is parsed in
whole 128-lane tile rows — no 1-D slice is ever reshaped:

  * vector: the first ``vec_rows`` tile rows against a query laid out the
    same way (f32 words, or four byte planes for uint8 data);
  * neighbor ids: copied out as whole tile rows; the wrapper slices them;
  * ADC: neighbor r's codes are ``g = pq_m/4`` words starting on a multiple
    of g, so lane l always holds subquantizers 4*(l % g) + k (byte k). With
    the LUT staged as ``(4, ks, 128)`` lanes (``hop_inputs``) the lookup is
    a one-hot select per code value on the VPU, and one exact f32 matmul
    with a 0/1 matrix sums each neighbor's g lanes.

Validity masking (frontier -1, neighbor -1) happens in the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.chunk_layout import ChunkLayout

LANES = 128
_V_BLOCK = 16          # LUT rows loaded per step (a whole bf16 tile)


def quantize_lut(lut: jax.Array):
    """Symmetric per-query int8 LUT quantization (§Perf adc-int8).

    lut (nq, m, ks) f32 -> (lut_q8 (nq, m, ks) int8, scale (nq,) f32);
    dequantization is lut_q8 * (scale / 127). The single source of truth
    for the recipe — the Pallas kernel and the ref-backend emulation in
    kernels.ops must stay numerically identical.
    """
    scale = jnp.max(jnp.abs(lut), axis=(1, 2))
    lut_q8 = jnp.clip(jnp.round(lut / jnp.maximum(
        scale[:, None, None], 1e-20) * 127.0), -127, 127).astype(jnp.int8)
    return lut_q8, scale


class _Geom:
    """Static tile geometry of one layout (all in int32 words / tile rows)."""

    def __init__(self, layout: ChunkLayout):
        assert layout.mode == "aisaq", "fused_hop needs inline codes"
        assert layout.pq_m % 4 == 0, "pq_m must be a multiple of 4"
        self.g = layout.pq_m // 4                  # code words per neighbor
        assert LANES % self.g == 0, \
            f"pq_m/4={self.g} must divide {LANES} (pq_m a power of 2 <= 512)"
        self.per_row = LANES // self.g             # neighbors per tile row
        self.u8 = layout.data_dtype == "uint8"
        self.vec_words = -(-layout.b_full // 4)
        self.vec_rows = -(-self.vec_words // LANES)
        o_ids = layout.dev_off_ids // 4
        self.id_r0 = o_ids // LANES
        self.id_rows = (o_ids + layout.R - 1) // LANES + 1 - self.id_r0
        self.id_lane0 = o_ids - self.id_r0 * LANES
        o_pq = layout.dev_off_pq // 4
        self.pq_r0 = o_pq // LANES
        self.pq_rows = (o_pq + layout.R * self.g - 1) // LANES + 1 - self.pq_r0
        self.slot0 = (o_pq - self.pq_r0 * LANES) // self.g


def hop_inputs(lut: jax.Array, queries: jax.Array, *, layout: ChunkLayout,
               quantized: bool = False):
    """Loop-invariant kernel operands, to be built once per search.

    lut (nq, m, ks) f32, queries (nq, d) -> (lut_lanes (nq, 4, ks, 128),
    q_tiles (nq, 4|1, vec_rows, 128) f32, scale (nq,) f32 or None).
    lut_lanes[n, k, v, l] = lut[n, 4*(l % g) + k, v]; quantized stages the
    int8 LUT values exactly in bf16 and returns the dequantization scale.
    The lane tile is a size-inflating broadcast that XLA sinks into any
    loop reading it unless the caller pins the result before the loop
    (`jax.lax.optimization_barrier`).
    """
    geo = _Geom(layout)
    nq, m, ks = lut.shape
    scale = None
    if quantized:
        lut_q8, s = quantize_lut(lut)
        lut, scale = lut_q8.astype(jnp.bfloat16), s / 127.0
    lanes = lut.reshape(nq, geo.g, 4, ks).transpose(0, 2, 3, 1)
    lanes = jnp.tile(lanes, (1, 1, 1, geo.per_row))
    words = geo.vec_rows * LANES
    q = queries.astype(jnp.float32)
    if geo.u8:      # byte plane k holds dims 4p + k of word p
        q = jnp.pad(q, ((0, 0), (0, 4 * words - q.shape[1])))
        q = q.reshape(nq, words, 4).transpose(0, 2, 1)
        q_tiles = q.reshape(nq, 4, geo.vec_rows, LANES)
    else:
        q = jnp.pad(q, ((0, 0), (0, words - q.shape[1])))
        q_tiles = q.reshape(nq, 1, geo.vec_rows, LANES)
    return lanes, q_tiles, scale


def _hop_kernel(ids_ref, row_ref, lut_ref, q_ref, exact_ref, idw_ref, adc_ref,
                *, geo: _Geom, dim: int, metric: str):
    # ---- full-precision vector + exact distance ---------------------------
    vw = row_ref[0, 0:geo.vec_rows, :]                       # (vr, 128) i32
    shape = vw.shape
    word = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    if geo.u8:
        planes = [(jnp.right_shift(vw, 8 * k) & 0xFF).astype(jnp.float32)
                  for k in range(4)]
        valid = [4 * word + k < dim for k in range(4)]
    else:
        planes = [jax.lax.bitcast_convert_type(vw, jnp.float32)]
        valid = [word < dim]
    acc = jnp.zeros(shape, jnp.float32)
    for k, (v, ok) in enumerate(zip(planes, valid)):
        q = q_ref[0, k]
        if metric == "mips":
            acc = acc - jnp.where(ok, v * q, 0.0)
        else:
            diff = jnp.where(ok, v - q, 0.0)
            acc = acc + diff * diff
    exact = jnp.sum(acc, axis=(0, 1), keepdims=True)         # (1, 1)
    exact_ref[0, 0] = jnp.broadcast_to(exact, (1, LANES))
    # ---- neighbor ids: whole tile rows, sliced by the wrapper -------------
    idw_ref[0, 0] = row_ref[0, geo.id_r0:geo.id_r0 + geo.id_rows, :]
    # ---- inline-PQ ADC: one-hot select per code value, per byte plane -----
    cw = row_ref[0, geo.pq_r0:geo.pq_r0 + geo.pq_rows, :]     # (T, 128) i32
    codes = [jnp.right_shift(cw, 8 * k) & 0xFF for k in range(4)]
    ks = lut_ref.shape[2]

    def body(vb, picked):
        v0 = pl.multiple_of(vb * _V_BLOCK, _V_BLOCK)
        picked = list(picked)
        for k in range(4):
            blk = lut_ref[0, k, pl.ds(v0, _V_BLOCK), :].astype(jnp.float32)
            for i in range(_V_BLOCK):
                picked[k] = jnp.where(codes[k] == v0 + i, blk[i:i + 1, :],
                                      picked[k])
        return tuple(picked)

    zero = jnp.zeros(cw.shape, jnp.float32)
    picked = jax.lax.fori_loop(0, ks // _V_BLOCK, body, (zero,) * 4)
    lane_sum = (picked[0] + picked[1]) + (picked[2] + picked[3])
    seg = (jax.lax.broadcasted_iota(jnp.int32, (LANES, geo.per_row), 0)
           // geo.g == jax.lax.broadcasted_iota(
               jnp.int32, (LANES, geo.per_row), 1)).astype(jnp.float32)
    adc_ref[0, 0] = jax.lax.dot_general(
        lane_sum, seg, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                   # (T, per_row)


@functools.partial(jax.jit, static_argnames=("layout", "metric", "interpret"))
def hop(chunk_words: jax.Array, frontier_ids: jax.Array, lut_lanes: jax.Array,
        q_tiles: jax.Array, scale, *, layout: ChunkLayout, metric: str = "l2",
        interpret: bool = False):
    """One batched hop over ``hop_inputs`` operands.

    chunk_words (N, rows, 128) i32; frontier_ids (nq, w) i32. Returns
    (exact (nq, w), ids (nq, w, R) i32, nbr_d (nq, w, R)); invalid frontier
    slots and neighbor slots get +inf and id -1.
    """
    geo = _Geom(layout)
    nq, w = frontier_ids.shape
    _, rows, _ = chunk_words.shape
    R, ks = layout.R, lut_lanes.shape[2]
    assert ks % _V_BLOCK == 0, ks
    nb = q_tiles.shape[1]

    def row_map(q, i, ids):
        return jnp.maximum(ids[q * w + i], 0), 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, w),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), row_map),
            pl.BlockSpec((1, 4, ks, LANES), lambda q, i, ids: (q, 0, 0, 0)),
            pl.BlockSpec((1, nb, geo.vec_rows, LANES),
                         lambda q, i, ids: (q, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, LANES), lambda q, i, ids: (q, i, 0, 0)),
            pl.BlockSpec((1, 1, geo.id_rows, LANES),
                         lambda q, i, ids: (q, i, 0, 0)),
            pl.BlockSpec((1, 1, geo.pq_rows, geo.per_row),
                         lambda q, i, ids: (q, i, 0, 0)),
        ],
    )
    exact, idw, adc = pl.pallas_call(
        functools.partial(_hop_kernel, geo=geo, dim=layout.dim,
                          metric=metric),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, w, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((nq, w, geo.id_rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((nq, w, geo.pq_rows, geo.per_row),
                                 jnp.float32),
        ],
        interpret=interpret,
    )(frontier_ids.reshape(-1), chunk_words, lut_lanes, q_tiles)
    ids = idw.reshape(nq, w, -1)[:, :, geo.id_lane0:geo.id_lane0 + R]
    nbr_d = adc.reshape(nq, w, -1)[:, :, geo.slot0:geo.slot0 + R]
    if scale is not None:
        nbr_d = nbr_d * scale[:, None, None]
    fvalid = frontier_ids >= 0
    nvalid = (ids >= 0) & fvalid[:, :, None]
    return (jnp.where(fvalid, exact[:, :, 0, 0], jnp.inf),
            jnp.where(nvalid, ids, -1),
            jnp.where(nvalid, nbr_d, jnp.inf))

