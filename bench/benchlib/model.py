"""The span, counter and clock helpers the drivers share, around the
program's objects.  The program's model configuration comes from the
configuration's architecture module (``bench/arch/<name>.py``,
``program_config``)."""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, List, Optional

import jax

from benchlib import program_trace


class Spans:
    """Host spans around the benchmark's calls into the program: each is a
    ``jax.profiler.TraceAnnotation`` (so a traced run sees it on the
    device's clock) and a ``(start, seconds)`` record on the host clock."""

    def __init__(self):
        self._lock = threading.Lock()
        self.records: Dict[str, List[tuple]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            yield
        dt = time.monotonic() - t0
        with self._lock:
            self.records.setdefault(name, []).append((t0, dt))

    def wrap(self, name: str, fn: Callable) -> Callable:
        def wrapped(*a, **k):
            with self.span(name):
                return fn(*a, **k)
        return wrapped

    def within(self, name: str, t0: float, t1: float) -> List[float]:
        """Durations of the ``name`` spans that started in [t0, t1)."""
        with self._lock:
            return [d for s, d in self.records.get(name, []) if t0 <= s < t1]


class CompileClock:
    """Counts backend compiles (and their seconds) while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


class KernelLengths:
    """Wraps a paged engine's jitted step and records, per call, the
    written lengths of the slots the decode kernel attends for (rows of
    the masked block table that are assigned; length = pos + 1) and the
    prefill chunk's (start, valid tokens).  Reads device arrays, so it is
    armed only in traced runs."""

    def __init__(self, engine):
        self.calls: List[tuple] = []     # (t, decode lengths, (start, n))
        self.armed = False
        self._step = engine._step
        engine._step = self

    def __call__(self, *args):
        if self.armed:
            import numpy as np
            pos, tables = np.asarray(args[3]), np.asarray(args[4])
            lengths = (pos + 1)[tables[:, 0] >= 0] if bool(args[10]) else []
            chunk = ((int(args[7]), int(np.asarray(args[6]).sum()))
                     if bool(args[9]) else None)
            self.calls.append((time.monotonic(), [int(x) for x in lengths],
                               chunk))
        return self._step(*args)


def counters(objs) -> Dict[str, float]:
    """The program's counters, summed over ``objs``: every numeric instance
    attribute whose name starts with ``total_`` (a paged engine's
    ``total_decode_steps``, ``total_tokens_decoded``, ...).  Host integers
    only: nothing runs on the device, and no property is called."""
    out: Dict[str, float] = {}
    for obj in objs:
        for name, v in dict(vars(obj)).items():
            if (name.startswith("total_") and isinstance(v, (int, float))
                    and not isinstance(v, bool)):
                out[name] = out.get(name, 0) + v
    return out


def delta(before: Dict[str, float], after: Dict[str, float]) -> Dict[str, float]:
    """Each counter's growth from ``before`` to ``after``."""
    return {name: v - before.get(name, 0) for name, v in after.items()}


class ProgramSpans:
    """The program's own spans (``repro.core.tracing``, ``roll.*``) over a
    traced window.  ``start`` turns them on and resets their totals, so it
    comes after set-up, just before the profiler starts; ``stop`` reads the
    totals and turns the spans off, before the profiler stops;
    ``record(path)`` gives the run's record ``program_spans`` (``{name:
    (count, seconds)}``) and ``program_idle`` (the device idle of the
    trace at ``path`` by innermost program span)."""

    def __init__(self, tracing):
        self.tracing = tracing
        self.totals: Optional[dict] = None

    def start(self) -> None:
        self.tracing.enable()

    def stop(self) -> None:
        try:
            self.totals = self.tracing.totals()
        finally:
            self.tracing.disable()

    def record(self, path: str) -> dict:
        return {"program_spans": self.totals,
                "program_idle": program_trace.idle_by_span(path)}
