"""Device milliseconds per loop trip in ops of the ``visited`` scope (the
visited bitmask's gather and scatter and the in-hop dedup), self time."""
from benchmarks.chip.span_reduce import scope_ms_per_trip


def read(rec):
    return scope_ms_per_trip(rec, __file__, "visited")
