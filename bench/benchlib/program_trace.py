"""Device idle attributed to the program's own spans (``roll.*``, written by
``repro.core.tracing``), and the readings built on them and on the
program's queue-wait stamps and decode-context counter.

The attribution follows ``trace.reduce``'s rule, over the same
``bench.window`` and the same gaps (chip 0's idle intervals): a gap goes to
the innermost ``roll.*`` span, on any thread, that covers its midpoint, or
to ``host:untraced`` where none does.  ``trace.reduce`` and its
``bench.*`` attribution are left as they are.

A reading that has nothing to read returns None: a program without the
spans or counters (older than them) reads nothing here.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional, Tuple

from benchlib import trace, traffic

PREFIX = "roll."
READBACK = "roll.engine.readback"   # the host waits on the device there

Span = Tuple[int, int, str, str]


def program_spans(pd) -> List[Span]:
    """Every program span: (start_ns, end_ns, name, thread line)."""
    return [(int(ev.start_ns), int(ev.end_ns), ev.name, line.name)
            for plane in pd.planes if plane.name == trace.HOST_PLANE
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


def idle_gaps(ops: List[trace.Interval],
              window: trace.Interval) -> List[trace.Interval]:
    """The intervals of ``window`` that no device op covers."""
    lo, hi = window
    busy = trace.union([(max(s, lo), min(e, hi)) for s, e in ops
                        if e > lo and s < hi])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]


def attribute_idle(gaps: List[trace.Interval],
                   spans: List[Span]) -> Dict[str, float]:
    """Seconds of idle per innermost covering span (``trace.attribute``)."""
    idle: Dict[str, int] = collections.Counter()
    for g, name in zip(gaps, trace.attribute(gaps, spans), strict=True):
        idle[name] += g[1] - g[0]
    return {k: v * 1e-9 for k, v in idle.items()}


def idle_by_span(path: str) -> Dict[str, float]:
    """``{span name or host:untraced: idle seconds}`` over the trace's
    ``bench.window`` (the whole trace where there is none), on chip 0."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = [p for p in pd.planes if p.name.startswith(trace.DEVICE_PREFIX)]
    if not devices:
        raise ValueError(f"{path}: no {trace.DEVICE_PREFIX}* plane")
    lines = {ln.name: ln for ln in devices[0].lines}
    ops = ([(int(ev.start_ns), int(ev.end_ns))
            for ev in lines[trace.OPS_LINE].events]
           if trace.OPS_LINE in lines else [])
    win = trace.window_of(trace.host_spans(pd))
    if win is None:
        win = (min(s for s, _ in ops), max(e for _, e in ops)) if ops else (0, 0)
    return attribute_idle(idle_gaps(ops, win), program_spans(pd))


# ---- readings of a run's record (``program_spans``: tracing.totals() over
# the window; ``program_idle``: idle_by_span; ``queue_waits``: the window's
# samples' meta["queue_wait_s"]; ``decode_context``: the window's delta of
# total_decode_context)

def _engine_host(name: str) -> bool:
    return name.startswith("roll.engine.") and name != READBACK


def engine_host_ms(rec: dict) -> Optional[float]:
    """Host milliseconds in the engine's spans other than the read-back,
    per engine step (dispatch) in the window."""
    spans = rec.get("program_spans") or {}
    steps = spans.get("roll.engine.dispatch", (0, 0.0))[0]
    if not steps:
        return None
    return 1e3 * sum(s for n, (_, s) in spans.items() if _engine_host(n)) / steps


def idle_share_under(rec: dict, layer: str) -> Optional[float]:
    """Device idle whose innermost program span belongs to ``layer``
    (``engine``: the engine's spans but the read-back; ``proxy``: the
    proxy's), over the window, in %."""
    idle, tr = rec.get("program_idle"), rec.get("trace")
    if not idle or not tr or tr["window_s"] <= 0:
        return None
    pick = _engine_host if layer == "engine" else (
        lambda n: n.startswith("roll.proxy."))
    return 100.0 * sum(v for n, v in idle.items() if pick(n)) / tr["window_s"]


def queue_wait_p90(rec: dict) -> Optional[float]:
    waits = rec.get("queue_waits") or []
    return traffic.p90(waits) if waits else None


def decode_context(rec: dict) -> Optional[float]:
    ctx, tokens = rec.get("decode_context"), rec.get("tokens_decoded")
    return ctx / tokens if ctx and tokens else None
