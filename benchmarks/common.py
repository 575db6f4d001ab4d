"""Shared benchmark fixtures: one cached corpus + index family.

The Vamana graph is built ONCE and shared across all PQ sizes and both
placement modes (the paper does the same: same graph topology, different
placement/compression), so the full Fig-3/Fig-4/Table-2/3/4 suite needs a
single graph build.

Staleness protection: every cached artifact (corpus, graph, each index
dir) is stamped with a hash of the build parameters that produced it.  A
knob change (N, R, pq_m, relabel, index format, ...) therefore REBUILDS
the artifact instead of silently reusing a stale one — previously a
surviving ``bench_idx/`` would keep serving indices built under old
parameters.  ``benchmarks/run.py --rebuild`` force-clears the whole
artifact cache.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import numpy as np

ART = os.path.join(os.path.dirname(__file__), "artifacts")
IDX = os.path.join(ART, "bench_idx")

N, DIM, NQ = 20000, 96, 64
R, BUILD_L = 24, 40
PQ_MS = (12, 24, 48, 96)          # b_pq sweep for Fig. 4
DEFAULT_M = 48
PQ_ITERS = 8                      # codebook k-means iters (also stamped)

# bump when write_index's on-disk layout changes: stamps embed it, so a
# format change rebuilds every cached index
# v2: checksummed format (block_crc.npy sidecar + format_version in meta)
# v3: optional navigation-tier sidecar (nav_graph.npz + "nav" meta key)
FMT_VERSION = 3

# navigation-tier build knobs for the nav-twin indices (stamped, so a
# change here rebuilds the *_nav directories)
NAV_FRACTION = 0.02
NAV_DEGREE = 8
NAV_SEED = 0


# -- build-params stamping ---------------------------------------------------


def _params_hash(params: dict) -> str:
    return hashlib.sha1(
        json.dumps(params, sort_keys=True).encode()).hexdigest()[:16]


def _stamp_path(dirname: str, name: str) -> str:
    return os.path.join(dirname, name)


def _stamp_ok(dirname: str, name: str, params: dict) -> bool:
    """True iff `dirname` carries a stamp built from exactly `params`."""
    try:
        with open(_stamp_path(dirname, name)) as f:
            return json.load(f).get("hash") == _params_hash(params)
    except (OSError, ValueError):
        return False


def _write_stamp(dirname: str, name: str, params: dict):
    with open(_stamp_path(dirname, name), "w") as f:
        json.dump({"hash": _params_hash(params), "params": params}, f,
                  indent=1)


def force_rebuild():
    """Drop the whole cached corpus/graph/index family (run.py --rebuild)."""
    shutil.rmtree(IDX, ignore_errors=True)


def _corpus_params() -> dict:
    return dict(n=N, dim=DIM, nq=NQ, n_clusters=96, seed=0, qseed=1, gt_k=10)


def _graph_params() -> dict:
    return dict(corpus=_params_hash(_corpus_params()), R=R, build_L=BUILD_L,
                seed=0, two_pass=False)


# -- cached artifacts --------------------------------------------------------


def corpus():
    from repro.data.vectors import make_clustered, make_queries
    os.makedirs(IDX, exist_ok=True)
    fb, fq, fg = (os.path.join(IDX, x) for x in
                  ("base.npy", "queries.npy", "gt.npy"))
    params = _corpus_params()
    if os.path.exists(fb) and _stamp_ok(IDX, "corpus.stamp.json", params):
        return np.load(fb), np.load(fq), np.load(fg)
    base = make_clustered(N, DIM, n_clusters=96, seed=0)
    q = make_queries(NQ, base, seed=1)
    from repro.core import pq
    gt = pq.groundtruth(q, base, 10)
    np.save(fb, base), np.save(fq, q), np.save(fg, gt)
    _write_stamp(IDX, "corpus.stamp.json", params)
    return base, q, gt


def graph(base):
    from repro.core.vamana import build_vamana
    fg = os.path.join(IDX, "graph.npy")
    params = _graph_params()
    if os.path.exists(fg) and _stamp_ok(IDX, "graph.stamp.json", params):
        return np.load(fg)
    t0 = time.time()
    g = build_vamana(base, R=R, L=BUILD_L, seed=0, two_pass=False,
                     log_every=4000)
    print(f"[bench] vamana build {time.time()-t0:.0f}s")
    np.save(fg, g)
    _write_stamp(IDX, "graph.stamp.json", params)
    return g


def index_path(mode: str, m: int, relabel: bool = False,
               nav: bool = False) -> str:
    return os.path.join(IDX, f"{mode}_m{m}" + ("_rl" if relabel else "")
                        + ("_nav" if nav else ""))


def ensure_indices(ms=(DEFAULT_M,), modes=("aisaq", "diskann"),
                   shared_centroids_for=None, relabel=False, nav=False):
    """Build (cached) indices for each (mode, m). Returns paths dict.

    `relabel=True` builds the graph-locality-relabeled twins (same graph,
    same codes, permuted placement) into separate `*_rl` directories so
    the cold-path benchmark can compare the two layouts directly.
    `nav=True` additionally builds the navigation-tier sidecar into
    `*_nav` twins (same graph/codes/placement, plus the pivot graph) so
    nav-vs-medoid entry seeding is an apples-to-apples comparison.

    Each index dir is stamped with its build params (`build_params.json`);
    a stamp mismatch — knob change, format bump, upstream corpus/graph
    rebuild — removes and rebuilds that directory.
    """
    import jax
    from repro.core import pq
    from repro.core.index_io import write_index
    base, q, gt = corpus()
    g = graph(base)
    paths = {}
    for m in ms:
        cache = {}
        for mode in modes:
            p = index_path(mode, m, relabel, nav)
            paths[(mode, m)] = p
            params = dict(fmt=FMT_VERSION, graph=_params_hash(
                _graph_params()), mode=mode, m=m, relabel=bool(relabel),
                metric="l2", pq_iters=PQ_ITERS, pq_seed=m)
            if nav:
                params.update(nav_fraction=NAV_FRACTION,
                              nav_degree=NAV_DEGREE, nav_seed=NAV_SEED)
            if os.path.exists(os.path.join(p, "meta.json")) \
                    and _stamp_ok(p, "build_params.json", params):
                continue
            shutil.rmtree(p, ignore_errors=True)     # stale or absent
            if "cents" not in cache:
                cb = pq.train_codebooks(jax.random.PRNGKey(m), base, m=m,
                                        iters=PQ_ITERS)
                cache["cents"] = np.asarray(cb.centroids)
                cache["codes"] = np.asarray(pq.encode(cb, base))
            write_index(p, vectors=base, graph=g, centroids=cache["cents"],
                        codes=cache["codes"], metric="l2", mode=mode,
                        relabel=relabel, nav=nav, nav_fraction=NAV_FRACTION,
                        nav_degree=NAV_DEGREE, nav_seed=NAV_SEED)
            _write_stamp(p, "build_params.json", params)
    return paths


def ensure_subcorpora(n_sub=5, m=DEFAULT_M):
    """Sub-corpus indices sharing one PQ-centroid set (Table 4)."""
    import jax
    from repro.core import pq
    from repro.core.index_config import IndexConfig
    from repro.core.build import build_index
    base, _, _ = corpus()
    cb = pq.train_codebooks(jax.random.PRNGKey(m), base, m=m, iters=PQ_ITERS)
    cents = np.asarray(cb.centroids)
    sub_n = 2000
    cfg = IndexConfig(name="sub", n_vectors=sub_n, dim=DIM, R=16, pq_m=m,
                      build_L=24)
    paths = {}
    for i in range(n_sub):
        p = os.path.join(IDX, f"sub_{i}")
        paths[f"sub{i}"] = p
        # derived from cfg, not re-typed: a knob edit must change the hash
        params = dict(fmt=FMT_VERSION, corpus=_params_hash(_corpus_params()),
                      m=m, sub_n=sub_n, i=i, R=cfg.R, build_L=cfg.build_L,
                      pq_iters=PQ_ITERS)
        if os.path.exists(os.path.join(p, "meta.json")) \
                and _stamp_ok(p, "build_params.json", params):
            continue
        shutil.rmtree(p, ignore_errors=True)
        build_index(p, base[i * sub_n:(i + 1) * sub_n], cfg,
                    mode="aisaq", shared_centroids=cents)
        _write_stamp(p, "build_params.json", params)
    return paths


def ensure_shard_indices(n_shards: int, m: int = DEFAULT_M,
                         total: int = N):
    """Per-shard AiSAQ indices over a contiguous split of (a prefix of)
    the cached corpus, for the multi-process cluster bench.

    Uses `core.shard_math.contiguous_shards` — the SAME assignment the
    device-mesh tier feeds `stack_shards` — and bakes each vector's
    GLOBAL id into the index via `write_index(labels=...)`, so cluster
    workers answer in global label space and the router merges without
    any offset arithmetic.  One PQ codebook (trained on the whole
    prefix) is shared by every shard, like the Table-4 sub-corpora.

    Returns (shard corpora list — one {"default": path} per shard —
    and the ShardAssignment)."""
    import jax
    from repro.core import pq
    from repro.core.index_io import write_index
    from repro.core.shard_math import contiguous_shards
    from repro.core.vamana import build_vamana
    base, _, _ = corpus()
    base = base[:total]
    asn = contiguous_shards(len(base), n_shards)
    cache = {}
    shards = []
    for s in range(n_shards):
        lo, hi = asn.bounds(s)
        p = os.path.join(IDX, f"shard_{n_shards}x{total}_{s}")
        shards.append({"default": p})
        params = dict(fmt=FMT_VERSION,
                      corpus=_params_hash(_corpus_params()),
                      m=m, total=total, n_shards=n_shards, s=s,
                      R=16, build_L=24, pq_iters=PQ_ITERS)
        if os.path.exists(os.path.join(p, "meta.json")) \
                and _stamp_ok(p, "build_params.json", params):
            continue
        shutil.rmtree(p, ignore_errors=True)
        if "cents" not in cache:
            cb = pq.train_codebooks(jax.random.PRNGKey(m), base, m=m,
                                    iters=PQ_ITERS)
            cache["cents"] = np.asarray(cb.centroids)
            cache["codes"] = np.asarray(pq.encode(cb, base))
        g = build_vamana(base[lo:hi], R=16, L=24, seed=s)
        write_index(p, vectors=base[lo:hi], graph=g,
                    centroids=cache["cents"], codes=cache["codes"][lo:hi],
                    metric="l2", mode="aisaq",
                    labels=np.arange(lo, hi, dtype=np.int64))
        _write_stamp(p, "build_params.json", params)
    return shards, asn


def rss_mb() -> float:
    import psutil
    return psutil.Process().memory_info().rss / 1e6


# -- report provenance -------------------------------------------------------


def provenance(schema: str) -> dict:
    """Uniform provenance header for every BENCH_*.json artifact.

    Regression tooling (`benchmarks/report.py`) and humans reading CI
    artifacts both need to know WHICH code on WHICH box produced a
    number before trusting a delta.  Never raises — a benchmark must
    not fail because git metadata is unavailable (e.g. a bare export).
    """
    import platform
    import subprocess
    from datetime import datetime, timezone
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=5,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        commit = None
    return dict(
        schema=schema,
        git_commit=commit,
        host=dict(hostname=platform.node(),
                  machine=platform.machine(),
                  system=platform.system(),
                  python=platform.python_version(),
                  cpus=os.cpu_count()),
        timestamp=datetime.now(timezone.utc).isoformat(),
    )
