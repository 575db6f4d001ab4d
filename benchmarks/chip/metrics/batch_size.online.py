"""Mean queries per search call the engine formed, in the traced window."""
from benchmarks.chip.metrics_common import batch_size


def read(rec):
    return batch_size(rec)
