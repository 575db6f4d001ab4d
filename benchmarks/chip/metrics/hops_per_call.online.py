"""Hop-kernel launches per search call, from the device trace."""
from benchmarks.chip.metrics_common import hops_per_call


def read(rec):
    return hops_per_call(rec)
