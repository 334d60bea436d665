"""Device idle whose innermost program span is the proxy's (roll.proxy.*), over the traced window, in %."""
from benchlib import program_trace


def read(rec):
    return program_trace.idle_share_under(rec, "proxy")
