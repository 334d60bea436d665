"""The decode kernel's least time for its algorithmic work over its device time in the trace, in %."""
from benchlib import readings


def read(rec):
    return readings.kernel_roofline(rec)
