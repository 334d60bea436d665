"""90th percentile of the window's counted samples' queue wait, receipt to admission summed over legs (task.meta["queue_wait_s"])."""
from benchlib import program_trace


def read(rec):
    return program_trace.queue_wait_p90(rec)
