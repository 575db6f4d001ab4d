"""Per-kernel interpret-mode validation against the pure-jnp oracles,
sweeping shapes and dtypes (required deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.chunk_layout import ChunkLayout, pack_chunks_device
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("nq,d,m,metric", [
    (1, 32, 4, "l2"), (3, 64, 16, "l2"), (5, 128, 32, "mips"),
    (2, 96, 8, "l2"), (4, 256, 64, "mips"),
])
def test_pq_lut_sweep(nq, d, m, metric):
    q = RNG.normal(size=(nq, d)).astype(np.float32)
    cents = RNG.normal(size=(m, 256, d // m)).astype(np.float32)
    a = np.asarray(ops.build_lut(q, cents, metric=metric,
                                 backend="pallas_interpret"))
    b = np.asarray(ref.pq_lut_ref(jnp.asarray(q), jnp.asarray(cents),
                                  metric=metric))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nq,n,m,code_dt", [
    (1, 100, 8, np.uint8), (2, 700, 16, np.uint8), (3, 64, 32, np.int32),
    (1, 1500, 4, np.uint8),
])
def test_pq_adc_sweep(nq, n, m, code_dt):
    lut = RNG.random(size=(nq, m, 256)).astype(np.float32)
    codes = RNG.integers(0, 256, size=(n, m)).astype(code_dt)
    a = np.asarray(ops.adc(jnp.asarray(lut), jnp.asarray(codes),
                           backend="pallas_interpret"))
    b = np.asarray(ops.adc(jnp.asarray(lut), jnp.asarray(codes),
                           backend="ref"))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dt,metric,R,m,dim", [
    ("float32", "l2", 8, 8, 32), ("float32", "mips", 24, 16, 64),
    ("uint8", "l2", 12, 8, 48), ("uint8", "l2", 52, 32, 128),
])
def test_fused_hop_sweep(dt, metric, R, m, dim):
    N = 100
    lay = ChunkLayout("aisaq", dim, dt, R, m)
    if dt == "uint8":
        vecs = RNG.integers(0, 255, (N, dim)).astype(np.uint8)
    else:
        vecs = RNG.normal(size=(N, dim)).astype(np.float32)
    adj = RNG.integers(-1, N, (N, R)).astype(np.int32)
    codes = RNG.integers(0, 256, (N, m)).astype(np.uint8)
    words = jnp.asarray(pack_chunks_device(vecs, adj, codes, lay))
    fids = jnp.asarray(RNG.integers(-1, N, (2, 4)).astype(np.int32))
    qs = jnp.asarray(RNG.normal(size=(2, dim)).astype(np.float32))
    cents = jnp.asarray(RNG.normal(size=(m, 256, dim // m))
                        .astype(np.float32))
    lut = ref.pq_lut_ref(qs, cents, metric=metric)
    e1, i1, d1 = ops.fused_hop(words, fids, lut, qs, layout=lay,
                               metric=metric, backend="pallas_interpret")
    e2, i2, d2 = ops.fused_hop(words, fids, lut, qs, layout=lay,
                               metric=metric, backend="ref")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    for a, b in ((e1, e2), (d1, d2)):
        a, b = np.asarray(a), np.asarray(b)
        fin = np.isfinite(a)
        assert (fin == np.isfinite(b)).all()
        scale = np.abs(b[fin]).max() + 1e-6
        np.testing.assert_allclose(a[fin] / scale, b[fin] / scale, atol=2e-6)


@pytest.mark.parametrize("nq,c,d,metric", [
    (1, 64, 32, "l2"), (3, 1000, 128, "l2"), (2, 500, 64, "mips"),
])
def test_rerank_sweep(nq, c, d, metric):
    q = RNG.normal(size=(nq, d)).astype(np.float32)
    cand = RNG.normal(size=(c, d)).astype(np.float32)
    a = np.asarray(ops.rerank(q, cand, metric=metric,
                              backend="pallas_interpret"))
    b = np.asarray(ops.rerank(q, cand, metric=metric, backend="ref"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nq,n,m", [(2, 500, 16), (1, 200, 32)])
def test_pq_adc_int8_error_bound(nq, n, m):
    """§Perf adc-int8: |err| <= m*max|lut|/127 and top-k ranking preserved."""
    from repro.kernels.pq_adc import pq_adc_q8
    lut = RNG.random((nq, m, 256)).astype(np.float32) * 3
    codes = RNG.integers(0, 256, (n, m)).astype(np.uint8)
    a = np.asarray(pq_adc_q8(jnp.asarray(lut), jnp.asarray(codes),
                             interpret=True))
    b = np.asarray(ops.adc(jnp.asarray(lut), jnp.asarray(codes),
                           backend="ref"))
    bound = m * np.abs(lut).max() / 127
    assert np.abs(a - b).max() <= bound + 1e-3
    top_a = set(np.argsort(a[0])[:10].tolist())
    top_b = set(np.argsort(b[0])[:10].tolist())
    assert len(top_a & top_b) >= 9


def test_fused_hop_int8_variant():
    """§Perf adc-int8 in the fused hop kernel: error bound + identical ids."""
    N, d, R, m = 150, 64, 24, 16
    lay = ChunkLayout("aisaq", d, "float32", R, m)
    vecs = RNG.normal(size=(N, d)).astype(np.float32)
    adj = RNG.integers(-1, N, (N, R)).astype(np.int32)
    codes = RNG.integers(0, 256, (N, m)).astype(np.uint8)
    words = jnp.asarray(pack_chunks_device(vecs, adj, codes, lay))
    fids = jnp.asarray(RNG.integers(-1, N, (2, 4)).astype(np.int32))
    qs = jnp.asarray(RNG.normal(size=(2, d)).astype(np.float32))
    cents = jnp.asarray(RNG.normal(size=(m, 256, d // m)).astype(np.float32))
    lut = ref.pq_lut_ref(qs, cents, metric="l2")
    _, i1, d1 = ops.fused_hop(words, fids, lut, qs, layout=lay, metric="l2",
                              backend="pallas_interpret", adc_dtype="int8")
    _, i2, d2 = ops.fused_hop(words, fids, lut, qs, layout=lay, metric="l2",
                              backend="pallas_interpret", adc_dtype="f32")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    fin = np.isfinite(np.asarray(d2))
    err = np.abs(np.asarray(d1)[fin] - np.asarray(d2)[fin]).max()
    assert err <= m * float(jnp.abs(lut).max()) / 127 + 1e-3


def test_ref_matches_numpy_twin():
    """jnp refs vs the numpy host implementations (pq.np_* twins)."""
    from repro.core.index_io import np_adc, np_build_lut
    q = RNG.normal(size=(48,)).astype(np.float32)
    cents = RNG.normal(size=(12, 256, 4)).astype(np.float32)
    codes = RNG.integers(0, 256, (20, 12)).astype(np.uint8)
    lut_np = np_build_lut(cents, q, "l2")
    lut_j = np.asarray(ref.pq_lut_ref(jnp.asarray(q[None]),
                                      jnp.asarray(cents), metric="l2"))[0]
    np.testing.assert_allclose(lut_np, lut_j, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np_adc(lut_np, codes),
        np.asarray(ref.pq_adc_ref(jnp.asarray(lut_np), jnp.asarray(codes))),
        rtol=1e-5, atol=1e-4)
