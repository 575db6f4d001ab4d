"""Device milliseconds outside the Pallas kernels per hop launch (the while-loop body's XLA ops)."""
from benchmarks.chip.metrics_common import xla_ms_per_hop


def read(rec):
    return xla_ms_per_hop(rec)
