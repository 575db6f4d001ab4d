"""Node-chunk layout math + packing (paper §2.3/§3.1, Figs 1-2).

A node chunk holds everything beam search needs when it expands node v:

  DiskANN : [ full_vec | n_nbrs | nbr_ids[R] ]
  AiSAQ   : [ full_vec | n_nbrs | nbr_ids[R] | nbr_pq_codes[R] ]

  B_DiskANN = b_full + b_num * (R + 1)
  B_AiSAQ   = B_DiskANN + R * b_pq

Two physical disciplines (DESIGN.md §2):
  * file layout — 4 KiB LBA blocks; a chunk never straddles a block boundary
    unless chunk > block, in which case it starts block-aligned and uses
    ceil(chunk/B) blocks (paper Fig. 1a/1b).
  * device layout — one (N, rows, 128) int32 HBM table: each chunk row is
    `rows` lane-dense 128-word tiles (512 B each), so one row is one
    tile-legal (1, rows, 128) block DMA. `rows` is 1, 2, 4 or a multiple of
    8, which is what the TPU's HBM tiling stores without padding. Every
    field is 4-byte aligned so bitcasts are free, and the neighbor codes
    start on a multiple of their own width (pq_m bytes) so one neighbor's
    codes never straddle a 128-word tile row.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np

B_NUM = 4  # bytes per node id / degree field (paper: "usually 4 bytes")


def _align(x: int, a: int) -> int:
    return (x + a - 1) // a * a


@dataclass(frozen=True)
class ChunkLayout:
    mode: str                 # "aisaq" | "diskann"
    dim: int
    data_dtype: str           # "float32" | "uint8"
    R: int
    pq_m: int                 # b_pq bytes per code
    block_bytes: int = 4096

    # ---- sizes (paper formulas) -----------------------------------------
    @property
    def b_full(self) -> int:
        return self.dim * (1 if self.data_dtype == "uint8" else 4)

    @property
    def chunk_bytes(self) -> int:
        base = self.b_full + B_NUM * (self.R + 1)
        if self.mode == "aisaq":
            base += self.R * self.pq_m
        return base

    # ---- field offsets (raw, unpadded) ----------------------------------
    @property
    def off_vec(self) -> int:
        return 0

    @property
    def off_deg(self) -> int:
        return self.b_full

    @property
    def off_ids(self) -> int:
        return self.b_full + B_NUM

    @property
    def off_pq(self) -> int:
        assert self.mode == "aisaq"
        return self.off_ids + self.R * B_NUM

    # ---- file (LBA) placement -------------------------------------------
    @property
    def nodes_per_block(self) -> int:
        """>0 when chunk <= block (Fig 1a); 0 when multi-block (Fig 1b)."""
        return self.block_bytes // self.chunk_bytes if self.chunk_bytes <= self.block_bytes else 0

    @property
    def blocks_per_chunk(self) -> int:
        return 1 if self.nodes_per_block else -(-self.chunk_bytes // self.block_bytes)

    @property
    def io_bytes(self) -> int:
        """Bytes read from storage per node expansion (paper §2.3)."""
        return self.blocks_per_chunk * self.block_bytes

    def file_offset(self, node: int) -> int:
        if self.nodes_per_block:
            blk, slot = divmod(node, self.nodes_per_block)
            return blk * self.block_bytes + slot * self.chunk_bytes
        return node * self.blocks_per_chunk * self.block_bytes

    def file_size(self, n: int) -> int:
        if self.nodes_per_block:
            return -(-n // self.nodes_per_block) * self.block_bytes
        return n * self.blocks_per_chunk * self.block_bytes

    # ---- device (HBM) placement -----------------------------------------
    @property
    def device_rows(self) -> int:
        """128-word tile rows per chunk row in the (N, rows, 128) table."""
        end = self.dev_off_pq + self.R * self.pq_m if self.mode == "aisaq" \
            else self.dev_off_ids + self.R * B_NUM
        rows = -(-end // 512)
        return 1 << (rows - 1).bit_length() if rows <= 4 else _align(rows, 8)

    @property
    def device_stride(self) -> int:
        """Chunk stride in bytes of the HBM table (a multiple of 512)."""
        return self.device_rows * 512

    @property
    def padded_vec_bytes(self) -> int:
        return _align(self.b_full, 4)

    @property
    def dev_off_deg(self) -> int:
        return self.padded_vec_bytes

    @property
    def dev_off_ids(self) -> int:
        return self.padded_vec_bytes + B_NUM

    @property
    def dev_off_pq(self) -> int:
        end_ids = self.dev_off_ids + self.R * B_NUM
        return _align(end_ids, self.pq_m) if self.pq_m % 4 == 0 else end_ids

    # ---- summary ----------------------------------------------------------
    def describe(self) -> dict:
        return dict(mode=self.mode, chunk_bytes=self.chunk_bytes,
                    block_bytes=self.block_bytes,
                    nodes_per_block=self.nodes_per_block,
                    blocks_per_chunk=self.blocks_per_chunk,
                    io_bytes=self.io_bytes, device_stride=self.device_stride)


def layout_for(index_cfg, mode: str | None = None) -> ChunkLayout:
    """Build a ChunkLayout from an :class:`repro.configs.base.IndexConfig`."""
    return ChunkLayout(
        mode=mode or index_cfg.mode, dim=index_cfg.dim,
        data_dtype=index_cfg.data_dtype, R=index_cfg.R, pq_m=index_cfg.pq_m,
        block_bytes=index_cfg.block_bytes)


# ---------------------------------------------------------------------------
# packing (numpy; build-time only)
# ---------------------------------------------------------------------------


def _vec_bytes(vectors: np.ndarray, layout: ChunkLayout) -> np.ndarray:
    if layout.data_dtype == "uint8":
        return vectors.astype(np.uint8)
    return vectors.astype(np.float32).view(np.uint8).reshape(vectors.shape[0], -1)


def pack_chunks_file(vectors: np.ndarray, adjacency: np.ndarray,
                     codes: np.ndarray, layout: ChunkLayout) -> bytes:
    """Produce the block-aligned chunks.bin payload (file layout).

    adjacency: (N, R) int32, -1 padded. codes: (N, m) uint8 (ignored for
    diskann mode). Neighbor slots for -1 edges store id=-1 and zero codes.
    """
    n = vectors.shape[0]
    buf = np.zeros(layout.file_size(n), dtype=np.uint8)
    vb = _vec_bytes(vectors, layout)
    adj = adjacency.astype(np.int32)
    deg = (adj >= 0).sum(axis=1).astype(np.int32)
    nbr_codes = None
    if layout.mode == "aisaq":
        safe = np.where(adj >= 0, adj, 0)
        nbr_codes = codes[safe]                      # (N, R, m)
        nbr_codes = np.where((adj >= 0)[:, :, None], nbr_codes, 0).astype(np.uint8)
    for i in range(n):
        off = layout.file_offset(i)
        c = buf[off:off + layout.chunk_bytes]
        c[layout.off_vec:layout.off_vec + layout.b_full] = vb[i]
        c[layout.off_deg:layout.off_deg + B_NUM] = deg[i:i + 1].view(np.uint8)
        c[layout.off_ids:layout.off_ids + layout.R * B_NUM] = adj[i].view(np.uint8)
        if layout.mode == "aisaq":
            c[layout.off_pq:layout.off_pq + layout.R * layout.pq_m] = \
                nbr_codes[i].reshape(-1)
    return buf.tobytes()


def pack_chunks_device(vectors: np.ndarray, adjacency: np.ndarray,
                       codes: np.ndarray, layout: ChunkLayout) -> np.ndarray:
    """(N, device_rows, 128) int32 — the HBM-resident 'storage' tier's
    shape and bytes."""
    n = vectors.shape[0]
    out = np.zeros((n, layout.device_stride), dtype=np.uint8)
    vb = _vec_bytes(vectors, layout)
    out[:, :vb.shape[1]] = vb
    adj = adjacency.astype(np.int32)
    deg = (adj >= 0).sum(axis=1).astype(np.int32)
    out[:, layout.dev_off_deg:layout.dev_off_deg + B_NUM] = \
        deg[:, None].view(np.uint8)
    out[:, layout.dev_off_ids:layout.dev_off_ids + layout.R * B_NUM] = \
        adj.view(np.uint8).reshape(n, -1)
    if layout.mode == "aisaq":
        safe = np.where(adj >= 0, adj, 0)
        nc = np.where((adj >= 0)[:, :, None], codes[safe], 0).astype(np.uint8)
        out[:, layout.dev_off_pq:layout.dev_off_pq + layout.R * layout.pq_m] = \
            nc.reshape(n, -1)
    return out.view(np.int32).reshape(n, layout.device_rows, 128)


# ---------------------------------------------------------------------------
# unpacking (numpy host path; the jnp path lives in kernels/ref.py)
# ---------------------------------------------------------------------------


def chunk_matrix(raw: np.ndarray, layout: ChunkLayout, n: int) -> np.ndarray:
    """Whole-file uint8 buffer -> (n, chunk_bytes) matrix of node chunks.

    The strided twin of calling ``parse_chunk`` n times: one reshape peels
    the block padding off, so downstream field slices are plain 2-D views.
    """
    if layout.nodes_per_block:
        npb = layout.nodes_per_block
        nblk = -(-n // npb)
        blocks = raw[:nblk * layout.block_bytes] \
            .reshape(nblk, layout.block_bytes)
        return blocks[:, :npb * layout.chunk_bytes] \
            .reshape(nblk * npb, layout.chunk_bytes)[:n]
    per = layout.blocks_per_chunk * layout.block_bytes
    return raw[:n * per].reshape(n, per)[:, :layout.chunk_bytes]


def parse_chunk(raw: np.ndarray, layout: ChunkLayout):
    """raw: (chunk_bytes,) uint8 -> (vec f32/u8, nbr_ids (R,) i32, nbr_codes)."""
    if layout.data_dtype == "uint8":
        vec = raw[:layout.b_full].copy()
    else:
        vec = raw[:layout.b_full].view(np.float32).copy()
    ids = raw[layout.off_ids:layout.off_ids + layout.R * B_NUM].view(np.int32).copy()
    pq = None
    if layout.mode == "aisaq":
        pq = raw[layout.off_pq:layout.off_pq + layout.R * layout.pq_m] \
            .reshape(layout.R, layout.pq_m).copy()
    return vec, ids, pq
