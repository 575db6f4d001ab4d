#!/usr/bin/env python3
"""The control of the ``correct`` comparison, at a cell's own size.

    python3 benchmarks/chip/control.py --workload sift1m.bulk-q1k \
        --seeds 11,12,13 --seconds 1

For each seed a run is set up as the benchmark sets one up
(``harness.prepare``), and then the plain reference, computed one precision
step down, is put in the program's place: bfloat16 operands (LUT, vectors,
queries) with float32 sums, as the MXU would do them. The cell's own driver
offers it a short window at the cell's load, and ``harness.measure``
decides ``correct`` as in every run. The control has to come out not
correct on every seed; each seed prints its result line. Benchmark runs
never run this; it is how the limit's upper reading was taken.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def reference_search(cell, arrays, dtype):
    """The plain reference as a ``(queries, k) -> ids`` search fn."""
    import numpy as np
    from benchmarks.chip import harness, reference
    p = harness.search_params(cell.config)
    entry = reference.entry_point(arrays.base)

    def search(queries, k):
        ids, _, _ = reference.beam_search(
            arrays.base, arrays.graph, arrays.codes, arrays.centroids,
            np.asarray(queries), k=k, L=max(p["L"], k), w=p["w"],
            max_hops=p["max_hops"], metric=cell.config["metric"],
            dtype=dtype, entry=entry)
        return ids
    return search


def control_line(cell, seed: int, seconds: float, devices) -> dict:
    """One control run: the result line the harness gives it."""
    from benchmarks.chip import harness, reference
    t_start = time.perf_counter()
    run = harness.prepare(cell, seed, seconds)
    run.search = reference_search(cell, run.arrays, reference.BF16)
    return harness.measure(run, devices, t_start=t_start)


def main(argv=None) -> int:
    from benchmarks.chip import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    try:
        devices = harness.require_tpu(cell.chips)
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache(harness.ROOT)
    for seed in [int(s) for s in args.seeds.split(",")]:
        line = control_line(cell, seed, args.seconds, devices)
        print(json.dumps({"seed": seed, **line}, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
