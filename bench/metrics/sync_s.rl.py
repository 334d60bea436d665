"""Mean seconds of the trainer-side weight-sync window per step (StepStats.sync_time)."""
from benchlib import readings


def read(rec):
    return readings.step_mean(rec, "sync_time")
