"""End-to-end index construction: PQ training + Vamana + serialization."""
from __future__ import annotations

import os
import time
from typing import Optional

import jax
import numpy as np

from repro.core.index_config import IndexConfig
from repro.core import pq
from repro.core.index_io import write_index
from repro.core.vamana import build_vamana, medoid


def build_index(path: str, vectors: np.ndarray, cfg: IndexConfig, *,
                mode: Optional[str] = None, seed: int = 0,
                shared_centroids: Optional[np.ndarray] = None,
                graph: Optional[np.ndarray] = None, verbose: bool = False,
                relabel: bool = False, nav: bool = False,
                nav_fraction: Optional[float] = None,
                nav_degree: Optional[int] = None,
                nav_seed: int = 0,
                nav_method: Optional[str] = None) -> dict:
    """Build one index directory from raw vectors.

    `shared_centroids` lets multiple corpora in the same vector space share
    PQ centroids (paper §4.4). `relabel=True` applies the graph-locality
    page-packing permutation at pack time (core.relabel) — cold-path reads
    per hop drop because co-expanded neighbors share I/O blocks; search
    results still come back under the original vector labels. `nav=True`
    also builds the in-memory navigation tier (`core.nav` — per-query
    entry vertices via `entry="nav"`; the `nav_*` knobs default to
    `core.nav`'s DEFAULT_* constants). Returns the meta dict (plus timing
    fields).
    """
    from repro.core import nav as _nav
    nav_kw = dict(nav=nav,
                  nav_fraction=_nav.DEFAULT_FRACTION
                  if nav_fraction is None else nav_fraction,
                  nav_degree=_nav.DEFAULT_DEGREE
                  if nav_degree is None else nav_degree,
                  nav_seed=nav_seed,
                  nav_method=_nav.DEFAULT_METHOD
                  if nav_method is None else nav_method)
    mode = mode or cfg.mode
    t0 = time.perf_counter()
    vec_f = vectors.astype(np.float32)
    n = vectors.shape[0]
    rng = jax.random.PRNGKey(seed)
    if shared_centroids is not None:
        centroids = shared_centroids
    else:
        sample = vec_f if n <= 100_000 else vec_f[
            np.random.default_rng(seed).choice(n, 100_000, replace=False)]
        cb = pq.train_codebooks(rng, sample, m=cfg.pq_m, ks=cfg.pq_ks)
        centroids = np.asarray(cb.centroids)
    codes = np.asarray(pq.encode(pq.PQCodebooks(centroids), vec_f))
    t_pq = time.perf_counter() - t0
    if graph is None:
        graph = build_vamana(vec_f, R=cfg.R, L=cfg.build_L, alpha=cfg.alpha,
                             metric=cfg.metric, seed=seed,
                             log_every=2000 if verbose else 0)
    t_graph = time.perf_counter() - t0 - t_pq
    ep = np.array([medoid(vec_f, cfg.metric)])
    meta = write_index(path, vectors=vectors, graph=graph,
                       centroids=centroids, codes=codes, metric=cfg.metric,
                       mode=mode, block_bytes=cfg.block_bytes, n_ep=cfg.n_ep,
                       entry_points=ep, relabel=relabel, **nav_kw,
                       extra_meta=dict(build_pq_s=t_pq, build_graph_s=t_graph))
    if verbose:
        print(f"built {path}: n={n} pq={t_pq:.1f}s graph={t_graph:.1f}s")
    return meta
