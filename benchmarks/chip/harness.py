"""The chip benchmark's harness: one cell of ``BENCHMARK.json`` per run.

Everything a cell needs is found by name:

* ``BENCHMARK.json`` names the cell, its configuration and traffic mix;
* the configuration entry's ``file`` holds the deployment's sizes;
* ``traffic/<traffic>.json`` holds the mix, and its ``kind`` names the
  driver ``drivers/<kind>.py`` that offers it;
* each metric of the cell is read by ``metrics/<name>.py``.

A run makes the configuration's index (``data/index_gen.py``), places it
with the program's ``from_arrays``, warms every shape the mix uses, drives
the program's search for ``--seconds``, and then decides ``correct`` by
comparing a sample of the answers with ``reference.py``. The index and its
query pool come from the configuration's ``index_seed``, so every run does
the same work; ``--seed`` draws the order of the queries, the arrivals and
the sample that is checked. With ``--trace 1`` the profiler records a few
seconds of the cell's traffic (where, the driver says) and the per-layer
metrics are read from that trace (``trace_reduce.py``) and the window.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NoDevice(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell's pieces by name
# ---------------------------------------------------------------------------


def load_module(path: Path):
    """Import one file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Path
    end_to_end: list
    per_layer: list
    metric_files: dict


def resolve(workload: str, root: Path = ROOT, bench: Path = HERE) -> Cell:
    """The cell named ``workload`` with every file it needs, by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    if config["correct"]["wrong_ids_limit"] is None:
        raise ValueError(f"configuration {conf['name']!r} has no measured "
                         "wrong_ids limit: measure the control and sound "
                         "runs, then set it")
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    files = {m["name"]: bench / "metrics" / f"{m['name']}.py"
             for m in e2e + layer}
    for name, path in files.items():
        if not path.is_file():
            raise FileNotFoundError(f"metric {name!r} has no reader {path}")
    driver = bench / "drivers" / f"{traffic['kind']}.py"
    if not driver.is_file():
        raise FileNotFoundError(f"traffic kind {traffic['kind']!r} has no "
                                f"driver {driver}")
    return Cell(workload, int(w["chips"]), config, traffic, driver, e2e,
                layer, files)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def require_tpu(chips: int):
    """The devices to run on; raises NoDevice off a TPU or short of chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices


def use_compile_cache(root: Path) -> None:
    """JAX's persistent cache at a fixed path in the checkout, unless the
    environment names one; every program is kept, however fast it
    compiled, so that a second run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# ---------------------------------------------------------------------------
# what a driver is given, and what it hands back
# ---------------------------------------------------------------------------


@dataclass
class Run:
    """One run's state: the index, the program's search over it, the
    cell's driver, the set-up timings and the call log."""
    cell: Cell
    seed: int
    seconds: float
    trace_dir: Optional[str]
    arrays: object                        # index_gen.Index
    layout: object                        # the program's ChunkLayout
    search: Callable                      # the program's (queries, k) -> ids
    driver: object
    timings: dict
    calls: list = field(default_factory=list)   # (t0, t1, n_queries)

    @property
    def queries(self) -> np.ndarray:
        return self.arrays.queries

    def rng(self, stream: int) -> np.random.Generator:
        """A numpy stream of this seed; each use takes its own number."""
        return np.random.default_rng([self.seed, stream])

    def timed_search(self) -> Callable:
        """The search fn, logging each call and, when tracing, marking it
        in the profiler's host trace as ``search_call``."""
        import jax

        def call(queries, k):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("search_call", nq=len(queries)):
                ids = self.search(queries, k)
            self.calls.append((t0, time.perf_counter(), len(queries)))
            return ids
        return call


@dataclass
class Window:
    """What a driver measured. ``qidx[i]`` is the pool index of the i-th
    answered request and ``ids[i]`` its answer; unanswered requests are
    counted in ``failed`` only."""
    attempted: int
    failed: int
    elapsed_s: float
    qidx: np.ndarray
    ids: np.ndarray
    latencies_ms: Optional[np.ndarray] = None   # inf where failed
    lateness_ms: Optional[np.ndarray] = None
    trace: Optional[dict] = None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def collector_off():
    """Keep the garbage collector out of a measured window. What set-up
    made lives to the end, and the window's own garbage is left to
    reference counting: a collector pass walks every request object a
    driver keeps and stalls all threads for tens of ms."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def search_params(cfg: dict) -> dict:
    s = cfg["assumed"]["search"]
    return dict(k=s["k"], L=s["L"], w=s["w"], max_hops=s["max_hops"],
                backend=s["backend"], adc_dtype=s["adc_dtype"],
                rerank=s["rerank"])


def build(cell: Cell, n_queries: int, timings: dict):
    """The configuration's index arrays, then the program's device index."""
    import jax
    from benchmarks.chip.data import index_gen
    from repro.core.device_index import from_arrays

    t = time.perf_counter()
    arrays = index_gen.make_index(
        cell.config["assumed"]["generator"]["index_seed"], cell.config,
        n_queries)
    timings["generate_s"] = time.perf_counter() - t
    t = time.perf_counter()
    index, layout = from_arrays(arrays.base, arrays.graph, arrays.centroids,
                                arrays.codes)
    jax.block_until_ready(index)
    timings["index_load_s"] = time.perf_counter() - t
    return arrays, index, layout


def check_kernels(index, layout, cell: Cell, queries: np.ndarray,
                  sizes) -> None:
    """Every batch size the window will use lowers with the Pallas kernels
    in it (a program that fell back to XLA is not the system)."""
    import jax.numpy as jnp
    from repro.core.device_index import beam_search_device
    p = search_params(cell.config)
    if p["backend"] != "pallas":
        return
    for nq in sizes:
        lowered = beam_search_device.lower(
            index, jnp.asarray(queries[:nq]), k=p["k"], L=max(p["L"], p["k"]),
            w=p["w"], max_hops=p["max_hops"], layout=layout,
            metric=cell.config["metric"], backend="pallas",
            adc_dtype=p["adc_dtype"])
        if lowered.as_text().count("tpu_custom_call") < 2:
            raise RuntimeError(f"search at batch {nq} lowered without its "
                               "Pallas kernels")


def check_answers(cell: Cell, arrays, win: Window, seed: int) -> dict:
    """Compare a sample of the window's answers with the plain reference.

    The sample is ``correct.sample`` distinct queries drawn from the seed
    among those answered; every answer any of them got is compared."""
    from benchmarks.chip import reference
    cfg, p = cell.config, search_params(cell.config)
    answered = np.unique(win.qidx)
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(answered, min(cfg["correct"]["sample"], answered.size),
                      replace=False)
    ref_ids, _, _ = reference.beam_search(
        arrays.base, arrays.graph, arrays.codes, arrays.centroids,
        arrays.queries[pick], k=p["k"], L=max(p["L"], p["k"]), w=p["w"],
        max_hops=p["max_hops"], metric=cfg["metric"])
    row = {int(q): i for i, q in enumerate(pick)}
    take = np.flatnonzero(np.isin(win.qidx, pick))
    ref_rows = np.array([row[int(q)] for q in win.qidx[take]], np.int64)
    wrong = reference.wrong_ids(
        arrays.base, arrays.queries[win.qidx[take]], win.ids[take],
        ref_ids[ref_rows], cfg["metric"], p["k"])
    return {"wrong_ids": wrong, "compared_ids": int(take.size * p["k"])}


def prepare(cell: Cell, seed: int, seconds: float,
            trace_dir: Optional[str] = None) -> Run:
    """A run's set-up: the configuration's index, placed by the program's
    ``from_arrays``, and the program's search fn over it, its Pallas
    kernels checked and every batch size the mix uses warmed."""
    from repro.serving.engine import make_device_search_fn
    driver = load_module(cell.driver)
    timings: dict = {}
    arrays, index, layout = build(
        cell, driver.pool_size(cell.traffic, seconds), timings)
    p = search_params(cell.config)
    search = make_device_search_fn(index, layout, metric=cell.config["metric"],
                                   L=p["L"], w=p["w"], max_hops=p["max_hops"],
                                   backend=p["backend"],
                                   adc_dtype=p["adc_dtype"],
                                   rerank=p["rerank"])
    t = time.perf_counter()
    check_kernels(index, layout, cell, arrays.queries,
                  driver.batch_sizes(cell.traffic))
    run = Run(cell, seed, seconds, trace_dir, arrays, layout, search, driver,
              timings)
    driver.warm(run)
    timings["warm_s"] = time.perf_counter() - t
    return run


def measure(run: Run, devices, *, t_start: float) -> dict:
    """The window, then the checks: the result line, its compared numbers
    last. The run's search is dropped before the reference runs."""
    from benchmarks.chip.data import index_gen
    from benchmarks.chip.trace_reduce import reduce_trace
    cell, dev = run.cell, devices[0]
    p = search_params(cell.config)
    with collector_off():
        setup_s = time.perf_counter() - t_start
        run.calls.clear()
        win = run.driver.measure(run)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if run.trace_dir is not None and win.trace is None:
        win.trace = reduce_trace(run.trace_dir)
    run.search = None
    arrays = run.arrays
    # brute force over the answered queries, on the device, after the peak
    used = int(win.qidx.max()) + 1 if win.qidx.size else 0
    gt = index_gen.ground_truth(arrays.queries[:used], arrays.base, p["k"],
                                cell.config["metric"])
    rec = dict(window=win, timings=run.timings, setup_s=setup_s,
               recall_at_10=index_gen.recall(win.ids, gt[win.qidx], p["k"])
               if win.qidx.size else None,
               traffic=cell.traffic, config=cell.config,
               device_kind=dev.device_kind, layout=run.layout)
    checks = check_answers(cell, arrays, win, run.seed) if win.qidx.size \
        else {"wrong_ids": None, "compared_ids": 0}
    limits = {"wrong_ids": cell.config["correct"]["wrong_ids_limit"],
              "unanswered": 0}
    shown = {"wrong_ids": [checks["wrong_ids"], limits["wrong_ids"]],
             "compared_ids": [checks["compared_ids"], None],
             "unanswered": [win.failed, limits["unanswered"]]}
    correct = (checks["wrong_ids"] is not None
               and checks["wrong_ids"] <= limits["wrong_ids"]
               and win.failed <= limits["unanswered"]
               and checks["compared_ids"] > 0)
    traced = run.trace_dir is not None
    metrics = {}
    for m in cell.per_layer if traced else cell.end_to_end:
        v = load_module(cell.metric_files[m["name"]]).read(rec)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": int(win.attempted),
            "failed": int(win.failed), "metrics": metrics, "device": device}
    if traced and win.trace is not None:
        device["busy_s"] = win.trace["busy_s"]
        device["window_s"] = win.trace["window_s"]
        line["breakdown"] = win.trace["breakdown"]
    line["setup"] = run.timings
    if win.lateness_ms is not None and len(win.lateness_ms):
        line["generator_late_ms"] = {
            "p50": float(np.percentile(win.lateness_ms, 50)),
            "p99": float(np.percentile(win.lateness_ms, 99))}
    line["checks"] = shown
    return line


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT) -> dict:
    """One run of one cell on the TPU: set-up, window, checks."""
    devices = require_tpu(cell.chips)
    trace_dir = str(root / ".bench_cache" / "trace" / cell.name) \
        if trace else None
    return measure(prepare(cell, seed, seconds, trace_dir), devices,
                   t_start=t_start)


def main(argv=None) -> int:
    import argparse
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        cell = resolve(args.workload)
        require_tpu(cell.chips)
        use_compile_cache(ROOT)
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        t_start=t_start)
    except NoDevice as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 2
    for name, (value, limit) in line["checks"].items():
        bound = "" if limit is None else f" (limit {limit})"
        print(f"check {name}: {value}{bound}", file=sys.stderr)
    print(json.dumps(line, allow_nan=False), flush=True)
    return 0
