"""Mean host milliseconds per engine.step call in the window (the benchmark's span around it)."""
from benchlib import readings


def read(rec):
    return readings.engine_step_ms(rec)
