"""Mean seconds the controller waited for a batch (StepStats.wait_time) over the window's steps."""
from benchlib import readings


def read(rec):
    return readings.step_mean(rec, "wait_time")
