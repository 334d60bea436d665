"""1 - the union of device op intervals over the traced window, in %."""
from benchlib import readings


def read(rec):
    return readings.idle_share(rec)
