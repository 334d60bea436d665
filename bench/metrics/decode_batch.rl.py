"""Tokens decoded per decode step over the window (engine counters)."""
from benchlib import readings


def read(rec):
    return readings.decode_batch(rec)
