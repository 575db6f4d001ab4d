"""Share of the hop kernel's roofline: least time by the HBM bytes a hop must move over its summed device time, in %."""
from benchmarks.chip.metrics_common import hop_roofline


def read(rec):
    return hop_roofline(rec)
