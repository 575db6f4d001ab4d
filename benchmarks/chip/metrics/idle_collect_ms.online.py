"""Device-idle milliseconds per traced search call while the engine was
collecting the next batch (the program's ``engine.collect`` span, its
``max_wait_ms`` included)."""
from benchmarks.chip.span_reduce import COLLECT, idle_ms_per_call


def read(rec):
    return idle_ms_per_call(rec, __file__, [COLLECT])
