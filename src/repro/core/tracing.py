"""Program spans: named host intervals on the device trace's clock.

Off by default.  Off, ``span`` and ``annotate`` return one shared no-op
context manager: a function call and a flag check, with no allocation and
no clock read, so the spans can sit on the rollout loop's hot path.

On (``enable()``), a span is a ``jax.profiler.TraceAnnotation``: a running
``jax.profiler`` trace records it on the host plane, per thread, nested
under the spans that enclose it on that thread.  Each span also adds its
count and host seconds to a per-name total (``totals()``), which needs no
trace.  Every span the program opens is named ``roll.<layer>.<phase>``::

    from repro.core import tracing
    tracing.enable()
    jax.profiler.start_trace(log_dir)
    ...                                   # run the pipeline
    jax.profiler.stop_trace()
    tracing.disable()
    print(tracing.totals())               # {"roll.engine.readback": (n, s), ...}
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Tuple

from jax.profiler import TraceAnnotation

_enabled = False
_lock = threading.Lock()
_totals: Dict[str, Tuple[int, float]] = {}   # guarded-by: _lock


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _Span:
    __slots__ = ("name", "t0", "ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.monotonic()
        return None

    def __exit__(self, *exc):
        add(self.name, time.monotonic() - self.t0)
        self.ann.__exit__(*exc)
        return False


def enable() -> None:
    """Turn spans on and reset the totals."""
    global _enabled
    with _lock:
        _totals.clear()
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def span(name: str):
    """A context manager timing ``name`` (a no-op while spans are off)."""
    return _Span(name) if _enabled else _NOOP


def annotate(name: str):
    """``span`` without its clock, for a caller that times the interval
    itself and passes the seconds to ``add``."""
    return TraceAnnotation(name) if _enabled else _NOOP


def add(name: str, seconds: float) -> None:
    """Add one interval of ``seconds`` to ``name``'s total (spans on)."""
    if not _enabled:
        return
    with _lock:
        n, s = _totals.get(name, (0, 0.0))
        _totals[name] = (n + 1, s + seconds)


def totals() -> Dict[str, Tuple[int, float]]:
    """A copy of ``{name: (count, seconds)}`` since the last ``enable()``."""
    with _lock:
        return dict(_totals)
