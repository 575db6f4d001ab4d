"""Median latency of every request in the window, from its due time; a
request that failed counts as missing every limit."""
from benchmarks.chip.metrics_common import latency_percentile


def read(rec):
    return latency_percentile(rec, 50)
