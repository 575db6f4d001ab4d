"""Mean host milliseconds per search call, in the traced window."""
from benchmarks.chip.metrics_common import search_ms


def read(rec):
    return search_ms(rec)
