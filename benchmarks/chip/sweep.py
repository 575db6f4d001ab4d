#!/usr/bin/env python3
"""Sweep the offered rate of an online cell to find its knee.

    python3 benchmarks/chip/sweep.py --workload sift1m.online-q32 \
        --seed 7 --seconds 5 --rates 500,1000,2000,3000

One process builds the cell's index once, warms it, and offers one window
per rate through the cell's own driver. Each rate prints one JSON line:
latency from due time, the median latency of the first and the last
quarter of the requests (a backlog that grows shows as a rising last
quarter), the mean batch the engine formed, answers per second and how
late the generator ran. The cell's rate is set once from this, at about
four fifths of the highest rate without a growing backlog; the benchmark
itself never searches for a rate.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import numpy as np
    from benchmarks.chip import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cell = harness.resolve(args.workload)
    try:
        harness.require_tpu(cell.chips)
    except harness.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache(harness.ROOT)
    run = harness.prepare(cell, args.seed, args.seconds)
    driver, timings = run.driver, run.timings
    timings["setup_s"] = time.perf_counter() - t_start
    print(json.dumps({"setup": timings}), flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate_qps=rate)
        run.calls.clear()
        due, qidx = driver.schedule(run, args.seconds)
        with harness.collector_off():
            reqs, late, at = driver.offer(run, due, qidx)
        ok = np.array([r.error is None and r.result is not None
                       for r in reqs])
        lat = np.array([(r.t_done - a) * 1e3 if g else np.inf
                        for r, a, g in zip(reqs, at, ok)])
        q = len(lat) // 4
        done = [r.t_done for r, g in zip(reqs, ok) if g]
        span = (max(done) - at[0]) if done else float("nan")
        print(json.dumps({
            "rate_qps": rate, "requests": len(reqs),
            "failed": int((~ok).sum()),
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "p50_first_quarter_ms": float(np.median(lat[:q])),
            "p50_last_quarter_ms": float(np.median(lat[-q:])),
            "mean_batch": float(np.mean([c[2] for c in run.calls])),
            "answers_per_s": float(ok.sum() / span),
            "late_p99_ms": float(np.percentile(late, 99) * 1e3),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
