import numpy as np
import pytest

from repro.core.chunk_layout import (B_NUM, ChunkLayout, pack_chunks_device,
                                     pack_chunks_file, parse_chunk)


def test_paper_formulas():
    """B_DiskANN = b_full + b_num(R+1); B_AiSAQ = B_DiskANN + R*b_pq (§3.1)."""
    for dim, dt, R, m in [(128, "float32", 56, 128), (128, "uint8", 52, 32),
                          (1024, "float32", 69, 128)]:
        d = ChunkLayout("diskann", dim, dt, R, m)
        a = ChunkLayout("aisaq", dim, dt, R, m)
        b_full = dim * (1 if dt == "uint8" else 4)
        assert d.chunk_bytes == b_full + B_NUM * (R + 1)
        assert a.chunk_bytes == d.chunk_bytes + R * m


def test_paper_table1_block_fit():
    """SIFT1B (Table 1): both modes fit one 4 KiB block -> same IO size,
    which is why AiSAQ is latency-neutral-or-better there (§4.3)."""
    d = ChunkLayout("diskann", 128, "uint8", 52, 32)
    a = ChunkLayout("aisaq", 128, "uint8", 52, 32)
    assert d.io_bytes == a.io_bytes == 4096
    # SIFT1M fp32 with b_pq=128: AiSAQ needs more blocks than DiskANN
    d1 = ChunkLayout("diskann", 128, "float32", 56, 128)
    a1 = ChunkLayout("aisaq", 128, "float32", 56, 128)
    assert a1.io_bytes >= d1.io_bytes


def test_block_alignment_no_straddle():
    lay = ChunkLayout("aisaq", 32, "float32", 8, 8)
    assert lay.chunk_bytes <= lay.block_bytes
    npb = lay.nodes_per_block
    for i in range(100):
        off = lay.file_offset(i)
        blk = off // lay.block_bytes
        assert off + lay.chunk_bytes <= (blk + 1) * lay.block_bytes
    # multi-block chunks start block-aligned
    lay2 = ChunkLayout("aisaq", 1024, "float32", 69, 128)
    assert lay2.chunk_bytes > lay2.block_bytes
    for i in range(10):
        assert lay2.file_offset(i) % lay2.block_bytes == 0


def test_device_stride_alignment():
    for dim, dt, R, m, stride in [(48, "float32", 20, 12, 1024),
                                  (128, "uint8", 52, 32, 2048),
                                  (128, "float32", 56, 128, 8192)]:
        lay = ChunkLayout("aisaq", dim, dt, R, m)
        # whole (rows, 128) int32 tiles, a row count HBM tiling stores as is
        assert lay.device_stride == stride == lay.device_rows * 512
        assert lay.device_rows in (1, 2, 4) or lay.device_rows % 8 == 0
        assert lay.dev_off_pq + R * m <= lay.device_stride
        assert lay.dev_off_ids % 4 == 0 and lay.dev_off_pq % 4 == 0


@pytest.mark.parametrize("mode", ["aisaq", "diskann"])
@pytest.mark.parametrize("dt", ["float32", "uint8"])
def test_pack_parse_roundtrip(mode, dt):
    rng = np.random.default_rng(0)
    n, dim, R, m = 50, 24, 10, 8
    if dt == "uint8":
        vecs = rng.integers(0, 255, (n, dim)).astype(np.uint8)
    else:
        vecs = rng.normal(size=(n, dim)).astype(np.float32)
    adj = rng.integers(-1, n, (n, R)).astype(np.int32)
    codes = rng.integers(0, 256, (n, m)).astype(np.uint8)
    lay = ChunkLayout(mode, dim, dt, R, m)
    buf = np.frombuffer(pack_chunks_file(vecs, adj, codes, lay), np.uint8)
    for i in (0, 7, n - 1):
        off = lay.file_offset(i)
        vec, ids, pq = parse_chunk(buf[off:off + lay.chunk_bytes], lay)
        np.testing.assert_array_equal(vec, vecs[i])
        np.testing.assert_array_equal(ids, adj[i])
        if mode == "aisaq":
            valid = adj[i] >= 0
            np.testing.assert_array_equal(pq[valid],
                                          codes[adj[i][valid]])


def test_device_pack_matches_ref_parse():
    from repro.kernels.ref import parse_chunks_words
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    n, dim, R, m = 30, 16, 6, 8
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    adj = rng.integers(-1, n, (n, R)).astype(np.int32)
    codes = rng.integers(0, 256, (n, m)).astype(np.uint8)
    lay = ChunkLayout("aisaq", dim, "float32", R, m)
    dev = pack_chunks_device(vecs, adj, codes, lay)
    words = jnp.asarray(np.ascontiguousarray(dev).view(np.int32)
                        .reshape(n, -1))
    vec, deg, ids, pqc = parse_chunks_words(words[:5], lay)
    np.testing.assert_allclose(np.asarray(vec), vecs[:5], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ids), adj[:5])
    np.testing.assert_array_equal(np.asarray(deg), (adj[:5] >= 0).sum(1))
