"""Host ms in the engine's program spans but the read-back (roll.engine.finish, .prepare, .dispatch, .update) per engine step (roll.engine.dispatch)."""
from benchlib import program_trace


def read(rec):
    return program_trace.engine_host_ms(rec)
