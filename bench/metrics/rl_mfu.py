"""Model FLOPs of the window (decode and prefill at 2N plus attention, train at 6N plus attention) over window x chips x bf16 peak, in %."""
from benchlib import readings


def read(rec):
    return readings.window_mfu(rec, lambda r: readings.rollout_flops(r) + readings.train_flops(r))
