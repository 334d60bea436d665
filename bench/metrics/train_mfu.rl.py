"""Train FLOPs (6N per real token plus causal attention, no recomputation) over the train program's device time at the bf16 peak, in %."""
from benchlib import readings


def read(rec):
    return readings.train_mfu(rec)
