"""Mean seconds of train_fn per step (StepStats.train_time; ends in float() of the metrics, so it includes the device work)."""
from benchlib import readings


def read(rec):
    return readings.step_mean(rec, "train_time")
