"""Device-idle milliseconds per traced search call while the host staged,
dispatched or fetched a call or woke its requests (the program's spans
``search.stage``, ``search.dispatch``, ``search.fetch``, ``engine.fanout``)."""
from benchmarks.chip.span_reduce import HOST_SPANS, idle_ms_per_call


def read(rec):
    return idle_ms_per_call(rec, __file__, HOST_SPANS)
