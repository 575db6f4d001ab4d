"""Share of the traced window in which no op ran on the device, in %."""
from benchmarks.chip.metrics_common import idle_share


def read(rec):
    return idle_share(rec)
