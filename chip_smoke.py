#!/usr/bin/env python3
"""Smoke test of the AiSAQ device search path on a TPU.

    python chip_smoke.py              # phases (a) and (b), one chip
    python chip_smoke.py --chips 4    # phase (c) only, four chips

(a) Served path at aisaq-sift1m widths (d=128 f32, L2, R=56, pq_m=128):
    build_index -> write_index -> load_device_index ->
    make_device_search_fn(backend="pallas") -> ServingEngine, with and
    without exact rerank, f32 and int8 ADC. Checks kernel parity with the
    numpy host twins, recall@10 against brute force next to the host scalar
    oracle on the same index, and top-10 agreement with backend="ref".
(b) The whole SIFT1M deployment resident in HBM: 1,000,000 rows at the same
    widths, one serve_q32 and one serve_q1k batch, Pallas against ref.
(c) The sharded path on a (1, 4) ("data", "model") mesh: 4 shards of
    2**20 rows at aisaq-sift1b widths (uint8 d=128, R=52, pq_m=32), against
    per-shard beam_search_device + merge_topk. Ids must be identical.

Every search runs with backend="pallas" and the compiled programs are
checked for `tpu_custom_call`. Any failed check exits nonzero. On success
the last stdout line is {"ok": true, "device": {...}}. Runs in this one
process only: the process that touches JAX holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

K = 10                    # recall@10
L, W, MAX_HOPS = 48, 4, 128
RERANK = 32
N_SERVED = 10_000         # phase (a) corpus: the Python Vamana build bounds it
N_QUERIES = 64
N_RESIDENT = 1_000_000    # phase (b): the whole SIFT1M deployment
N_SHARD = 1 << 20         # phase (c): rows per shard, one shard per chip


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def agreement(a: np.ndarray, b: np.ndarray, k: int = K) -> float:
    """Mean share of top-k ids two searches agree on."""
    return float(np.mean([len(set(x[:k].tolist()) & set(y[:k].tolist())) / k
                          for x, y in zip(a, b)]))


def n_custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def search_kwargs(**kw):
    return dict(L=L, w=W, max_hops=MAX_HOPS, **kw)


def compiled_search(index, layout, metric, queries, *, backend, adc_dtype,
                    k=K):
    """Compile the device search once for these shapes; returns (compiled,
    seconds)."""
    import jax.numpy as jnp
    from repro.core.device_index import beam_search_device
    t = time.perf_counter()
    c = beam_search_device.lower(
        index, jnp.asarray(queries), k=k, layout=layout, metric=metric,
        backend=backend, adc_dtype=adc_dtype, **search_kwargs()).compile()
    return c, time.perf_counter() - t


def serve(search_fn, queries: np.ndarray, k: int) -> np.ndarray:
    """Submit every query to a ServingEngine and collect the answers."""
    from repro.serving.engine import ServingEngine
    eng = ServingEngine({"sift1m": search_fn}, max_batch=32,
                        max_wait_ms=1000.0)
    try:
        reqs = [eng.submit(q, "sift1m", k) for q in queries]
        for r in reqs:
            r.event.wait(900)
        for r in reqs:
            if r.error is not None:
                raise r.error
            check(r.result is not None, "a served request never completed")
        return np.stack([r.result for r in reqs])
    finally:
        eng.stop()


def precision_probe() -> None:
    """What an f32 matmul computes on this device, at the default precision
    and at the HIGHEST precision the search path pins, against float64."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    a = rng.normal(size=(64, 128)).astype(np.float32)
    b = rng.normal(size=(128, 256)).astype(np.float32)
    want = a.astype(np.float64) @ b.astype(np.float64)
    errs = {}
    for name, prec in (("default", None),
                       ("HIGHEST", jax.lax.Precision.HIGHEST)):
        got = np.asarray(jnp.matmul(jnp.asarray(a), jnp.asarray(b),
                                    precision=prec))
        errs[name] = float(np.abs(got - want).max() / np.abs(want).max())
    log(f"  f32 matmul (64x128 @ 128x256) vs float64: max rel err "
        f"{errs['default']:.3g} at default precision, "
        f"{errs['HIGHEST']:.3g} at HIGHEST")
    check(errs["HIGHEST"] <= 1e-5, "HIGHEST precision matmul is not f32")


def kernel_parity(index, layout, metric, queries, backend):
    """Each kernel against its numpy host twin / jnp oracle on this device."""
    import jax.numpy as jnp
    from repro.core.adc import np_build_lut_batch
    from repro.kernels import ops
    q = queries[:8]
    cents = np.asarray(index.centroids)
    host_lut = np_build_lut_batch(cents, q, metric)
    lut = ops.build_lut(jnp.asarray(q), index.centroids, metric=metric,
                        backend=backend)
    lut_err = float(np.abs(np.asarray(lut) - host_lut).max()
                    / np.abs(host_lut).max())
    log(f"  LUT vs numpy twin: max rel err {lut_err:.3g}")
    check(lut_err <= 1e-5, f"pq_lut disagrees with the host twin: {lut_err}")
    rng = np.random.default_rng(0)
    fids = jnp.asarray(rng.integers(0, index.n, (q.shape[0], W)),
                       jnp.int32)
    for adc_dtype in ("f32", "int8"):
        got = ops.fused_hop(index.chunk_words, fids, lut, jnp.asarray(q),
                            layout=layout, metric=metric, backend=backend,
                            adc_dtype=adc_dtype)
        want = ops.fused_hop(index.chunk_words, fids, lut, jnp.asarray(q),
                             layout=layout, metric=metric, backend="ref",
                             adc_dtype=adc_dtype)
        check(np.array_equal(np.asarray(got[1]), np.asarray(want[1])),
              f"fused_hop {adc_dtype}: neighbor ids differ from ref")
        errs = []
        for a, b in ((got[0], want[0]), (got[2], want[2])):
            a, b = np.asarray(a), np.asarray(b)
            fin = np.isfinite(b)
            check((np.isfinite(a) == fin).all(),
                  f"fused_hop {adc_dtype}: +inf mask differs from ref")
            errs.append(float(np.abs(a[fin] - b[fin]).max()
                              / (np.abs(b[fin]).max() + 1e-30)))
        log(f"  fused_hop {adc_dtype} vs ref: ids identical, max rel err "
            f"exact {errs[0]:.3g}, ADC {errs[1]:.3g}")
        check(max(errs) <= 1e-5, f"fused_hop {adc_dtype} drifts from ref")
    cand = np.asarray(rng.normal(size=(RERANK, layout.dim)), np.float32)
    got = np.asarray(ops.rerank(jnp.asarray(q[0]), jnp.asarray(cand),
                                metric=metric, backend=backend))
    want = ((cand - q[0]) ** 2).sum(1) if metric == "l2" else -(cand @ q[0])
    err = float(np.abs(got - want).max() / np.abs(want).max())
    log(f"  rerank vs numpy: max rel err {err:.3g}")
    check(err <= 1e-5, f"rerank disagrees with numpy: {err}")
    if backend == "pallas":
        from repro.kernels.rerank import rerank
        c = rerank.lower(jnp.asarray(q[0]), jnp.asarray(cand),
                         metric=metric).compile()
        check(n_custom_calls(c) >= 1, "rerank compiled without its kernel")


def phase_served(seed: int, *, n: int = N_SERVED, n_queries: int = N_QUERIES,
                 backend: str = "pallas") -> None:
    import jax.numpy as jnp
    from repro.core.index_config import SIFT1M
    from repro.core import pq
    from repro.core.build import build_index
    from repro.core.device_index import load_device_index
    from repro.core.index_io import HostIndex, recall_at
    from repro.data.vectors import make_clustered, make_queries
    from repro.serving.engine import make_device_search_fn

    log(f"== phase (a): served path at aisaq-sift1m widths, N={n}, "
        f"{n_queries} requests per configuration ==")
    base = make_clustered(n, SIFT1M.dim, seed=seed)
    queries = make_queries(n_queries, base, seed=seed + 1)
    with tempfile.TemporaryDirectory(prefix="aisaq_smoke_") as path:
        t = time.perf_counter()
        build_index(path, base, SIFT1M, seed=seed)
        log(f"  host build (PQ + Vamana + write_index): "
            f"{time.perf_counter() - t:.1f} s")
        index, layout, metric = load_device_index(path)
        log(f"  device table {tuple(index.chunk_words.shape)} int32, "
            f"stride {layout.device_stride} B")
        gt = pq.groundtruth(queries, base, K, metric=metric)
        host = HostIndex.load(path)
        oracle, _ = host.search_batch_ref(queries, K, L, W)
        r_oracle = recall_at(oracle, gt, K)
    precision_probe()
    kernel_parity(index, layout, metric, queries, backend)
    recall = {}
    for adc_dtype in ("f32", "int8"):
        c, secs = compiled_search(index, layout, metric, queries[:32],
                                  backend=backend, adc_dtype=adc_dtype)
        calls = n_custom_calls(c)
        log(f"  compiled search {adc_dtype}: {secs:.1f} s, "
            f"{calls} tpu_custom_call")
        check(backend != "pallas" or calls >= 2,
              "compiled search holds no Pallas kernel")
        for rerank in (0, RERANK):
            ids = {}
            for b in (backend, "ref"):
                fn = make_device_search_fn(
                    index, layout, metric=metric, backend=b,
                    adc_dtype=adc_dtype, rerank=rerank, **search_kwargs())
                ids[b] = serve(fn, queries, K)
            rec = recall[(adc_dtype, rerank)] = recall_at(ids[backend], gt, K)
            agree = agreement(ids[backend], ids["ref"])
            log(f"  adc={adc_dtype} rerank={rerank}: recall@10 {rec:.4f} "
                f"(ref backend {recall_at(ids['ref'], gt, K):.4f}); "
                f"top-10 agreement with ref {agree:.4f}")
            check(agree >= 0.95, f"{backend} and ref disagree: {agree}")
    r_dev = recall[("f32", 0)]
    log(f"  recall@10 device f32 {r_dev:.4f} vs host scalar oracle "
        f"{r_oracle:.4f} (brute-force ground truth)")
    check(abs(r_dev - r_oracle) <= 0.01,
          f"device recall {r_dev} is not within 0.01 of the oracle's")


def phase_resident(seed: int, *, n: int = N_RESIDENT,
                   batches=(("serve_q32", 32), ("serve_q1k", 1024)),
                   backend: str = "pallas") -> None:
    import jax
    from repro.core.index_config import SIFT1M
    from repro.core.device_index import from_arrays
    from repro.data.vectors import make_clustered, make_queries
    from repro.serving.engine import make_device_search_fn

    log(f"== phase (b): {n} rows at aisaq-sift1m widths resident in HBM ==")
    rng = np.random.default_rng(seed)
    t = time.perf_counter()
    base = make_clustered(n, SIFT1M.dim, seed=seed)
    codes = rng.integers(0, SIFT1M.pq_ks, (n, SIFT1M.pq_m), dtype=np.uint8)
    graph = rng.integers(0, n, (n, SIFT1M.R), dtype=np.int32)
    cents = rng.normal(size=(SIFT1M.pq_m, SIFT1M.pq_ks,
                             SIFT1M.dim // SIFT1M.pq_m)).astype(np.float32)
    index, layout = from_arrays(base, graph, cents, codes)
    jax.block_until_ready(index.chunk_words)
    log(f"  generate + pack + place: {time.perf_counter() - t:.1f} s; "
        f"table {tuple(index.chunk_words.shape)} int32 = "
        f"{index.chunk_words.nbytes / 1e9:.3f} GB on "
        f"{index.chunk_words.devices()}")
    for name, nq in batches:
        queries = make_queries(nq, base, seed=seed + 2)
        c, secs = compiled_search(index, layout, "l2", queries,
                                  backend=backend, adc_dtype="f32")
        calls = n_custom_calls(c)
        log(f"  {name}: compiled search {secs:.1f} s, {calls} "
            f"tpu_custom_call")
        check(backend != "pallas" or calls >= 2,
              "compiled search holds no Pallas kernel")
        ids = {}
        for b in (backend, "ref"):
            fn = make_device_search_fn(index, layout, metric="l2", backend=b,
                                       **search_kwargs())
            ids[b] = fn(queries, K)
        check(ids[backend].shape == (nq, K), "wrong result shape")
        check(((ids[backend] >= 0) & (ids[backend] < n)).all(),
              "ids out of range")
        agree = agreement(ids[backend], ids["ref"])
        log(f"  {name}: {nq} queries answered; top-10 agreement with ref "
            f"{agree:.4f}")
        check(agree >= 0.95, f"{backend} and ref disagree: {agree}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"(bytes_limit {stats.get('bytes_limit')})")


def phase_sharded(seed: int, *, n_shard: int = N_SHARD, nq: int = 32,
                  backend: str = "pallas") -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.index_config import SIFT1B
    from repro.core.chunk_layout import layout_for
    from repro.core.device_index import DeviceIndex, beam_search_device
    from repro.core.shard_math import merge_topk
    from repro.core.sharded_search import (input_sharding, sharded_search_fn,
                                           stack_shards)
    from repro.data.vectors import make_clustered, make_queries
    from repro.launch.mesh import make_test_mesh

    n_dev = 4
    log(f"== phase (c): {n_dev} shards x {n_shard} rows at aisaq-sift1b "
        f"widths on a (1, {n_dev}) mesh ==")
    rng = np.random.default_rng(seed)
    t = time.perf_counter()
    base = make_clustered(n_dev * n_shard, SIFT1B.dim, seed=seed,
                          dtype="uint8")
    codes = rng.integers(0, SIFT1B.pq_ks, (base.shape[0], SIFT1B.pq_m),
                         dtype=np.uint8)
    cents = rng.normal(size=(SIFT1B.pq_m, SIFT1B.pq_ks,
                             SIFT1B.dim // SIFT1B.pq_m)).astype(np.float32)
    shards = [(s * n_shard, base[s * n_shard:(s + 1) * n_shard],
               rng.integers(0, n_shard, (n_shard, SIFT1B.R), dtype=np.int32))
              for s in range(n_dev)]
    layout = layout_for(SIFT1B, "aisaq")
    mesh = make_test_mesh((1, n_dev), ("data", "model"))
    arrays = stack_shards(shards, cents, codes, layout, mesh)
    jax.block_until_ready(arrays)
    per_chip = arrays.chunk_words.nbytes / n_dev / 1e9
    log(f"  generate + pack + place: {time.perf_counter() - t:.1f} s; "
        f"{per_chip:.3f} GB of chunk table per chip")
    queries = make_queries(nq, base, seed=seed + 3)
    search = jax.jit(sharded_search_fn(
        mesh, k=K, layout=layout, metric="l2", backend=backend,
        **search_kwargs()))
    _, qsh = input_sharding(mesh)
    qdev = jax.device_put(jnp.asarray(queries), qsh)
    t = time.perf_counter()
    c = search.lower(arrays, qdev).compile()
    calls = n_custom_calls(c)
    log(f"  compiled sharded search: {time.perf_counter() - t:.1f} s, "
        f"{calls} tpu_custom_call")
    check(backend != "pallas" or calls >= 2,
          "compiled sharded search holds no Pallas kernel")
    ids_sh, _ = search(arrays, qdev)
    ids_sh = np.asarray(ids_sh)
    # reference: each shard searched alone on the chip that holds it, the
    # answers merged on the host
    parts_i, parts_d = [], []
    ep_ids, ep_codes = np.asarray(arrays.ep_ids), np.asarray(arrays.ep_codes)
    for piece in sorted(arrays.chunk_words.addressable_shards,
                        key=lambda p: p.index[0].start or 0):
        s, dev = piece.index[0].start or 0, piece.device
        idx = DeviceIndex(
            chunk_words=piece.data[0],
            centroids=jax.device_put(cents, dev),
            ep_ids=jax.device_put(ep_ids[s], dev),
            ep_codes=jax.device_put(ep_codes[s], dev))
        ids, d, _ = beam_search_device(
            idx, jax.device_put(jnp.asarray(queries), dev), k=K,
            layout=layout, metric="l2", backend=backend, **search_kwargs())
        ids = np.asarray(ids)
        parts_i.append(np.where(ids >= 0, ids + shards[s][0], -1))
        parts_d.append(np.asarray(d))
    merged = np.stack([merge_topk([p[i] for p in parts_i],
                                  [p[i] for p in parts_d], K)[0]
                       for i in range(nq)])
    same = float((merged == ids_sh).mean())
    log(f"  sharded ids identical to per-shard search + merge_topk: "
        f"{same:.4f} of {merged.size}")
    check(same == 1.0, "sharded search differs from per-shard + merge_topk")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded phase (c) only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 2
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_sharded(args.seed)
        else:
            phase_served(args.seed)
            phase_resident(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
