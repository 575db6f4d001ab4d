#!/usr/bin/env python3
"""Run one cell of the chip benchmark named in ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload sift1m.bulk-q1k \
        --seed 1234 --seconds 10 --trace 0

Runs on the machine it is started on and needs a TPU: without one, or with
fewer chips than the cell asks for, it exits nonzero and prints no result.
The last line of standard output is the result as one JSON object.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(ROOT, ".bench_cache", "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
