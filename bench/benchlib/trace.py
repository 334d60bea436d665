"""From a profiler trace (``.xplane.pb``) to device busy time, per-op and
per-program device time, and idle gaps attributed to the benchmark's host
spans.

What the trace looks like on a TPU v5e (read by hand, jax 0.9):
- one plane per chip, ``/device:TPU:<n>``, with lines ``XLA Modules``
  (one event per program run, named ``jit_<fn>(<hash>)``), ``XLA Ops``
  (every HLO op; control-flow ops such as ``%while.27`` and
  ``%conditional.1`` contain the ops of their bodies, which nest inside
  them on the same line) and ``Async XLA Ops``;
- op events are named by their HLO text, ``%<name>.<n> = <shape> <op>(...)``;
  a Pallas kernel keeps its function's name
  (``%paged_decode_attention.12 = ... custom-call(...)``);
- the host plane ``/host:CPU`` has a line per thread, on the same clock,
  and ``jax.profiler.TraceAnnotation`` spans appear there by name.
"""
from __future__ import annotations

import collections
import heapq
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."

_OP_RE = re.compile(r"^%([^\s=]+)\s*=")
_MODULE_RE = re.compile(r"^(.*?)(?:\(\d+\))?$")


def op_name(event_name: str) -> str:
    """``%paged_decode_attention.12 = bf16[...] custom-call(...)`` ->
    ``paged_decode_attention`` (suffixes such as ``.8.remat`` dropped);
    anything else is returned as it is."""
    m = _OP_RE.match(event_name)
    return m.group(1).split(".")[0] if m else event_name


def module_name(event_name: str) -> str:
    """``jit__step_impl(18431033516731457092)`` -> ``jit__step_impl``."""
    return _MODULE_RE.match(event_name).group(1)


Interval = Tuple[int, int]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Exclusive time per op name on one line whose events nest: a
    control-flow op's own time is its span less the ops inside it."""
    out: Dict[str, int] = collections.Counter()
    stack: List[list] = []          # [end, name, child_time]

    def pop():
        end, name, child, start = stack.pop()
        out[name] += max(0, (end - start) - child)
        if stack:
            stack[-1][2] += end - start

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            pop()
        stack.append([e, name, 0, s])
    while stack:
        pop()
    return out


def _events(line, lo=None, hi=None):
    for ev in line.events:
        s, e = int(ev.start_ns), int(ev.end_ns)
        if lo is not None and (e <= lo or s >= hi):
            continue
        yield s, e, ev.name


def host_spans(pd) -> List[Tuple[int, int, str, str]]:
    """Every benchmark span: (start_ns, end_ns, name, thread line)."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for s, e, name in _events(line):
                if name.startswith(SPAN_PREFIX):
                    out.append((s, e, name, line.name))
    return out


def window_of(spans, name: str = "bench.window") -> Optional[Interval]:
    hits = [(s, e) for s, e, n, _ in spans if n == name]
    return (min(s for s, _ in hits), max(e for _, e in hits)) if hits else None


def attribute(gaps: List[Interval], spans) -> List[str]:
    """For each gap, the innermost benchmark span covering its midpoint
    (the shortest; of equal ones, the first in ``spans``), or
    ``host:untraced`` where none does.  One sweep over the midpoints in
    order, with the spans begun so far in a heap by length: a traced window
    holds some 10^5 gaps and 10^4 spans."""
    cands = sorted((s, e, k, name) for k, (s, e, name, _) in enumerate(spans)
                   if name != "bench.window")
    out = ["host:untraced"] * len(gaps)
    heap: list = []               # (length, index in spans, end, name)
    nxt = 0
    for j in sorted(range(len(gaps)), key=lambda j: sum(gaps[j]) // 2):
        mid = sum(gaps[j]) // 2
        while nxt < len(cands) and cands[nxt][0] <= mid:
            s, e, k, name = cands[nxt]
            heapq.heappush(heap, (e - s, k, e, name))
            nxt += 1
        while heap and heap[0][2] <= mid:   # ended: it covers no later midpoint
            heapq.heappop(heap)
        if heap:
            out[j] = heap[0][3]
    return out


def reduce(path: str, top: int = 10) -> dict:
    """Reduce one trace over the ``bench.window`` span (the whole trace
    where there is none).  Times are seconds; per-device numbers are
    averaged over the chips, per-op and per-module times summed."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = host_spans(pd)
    devices = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane")
    win = window_of(spans)
    ops_by_dev, mods = [], collections.Counter()
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = list(_events(lines[OPS_LINE])) if OPS_LINE in lines else []
        ops_by_dev.append(ops)
        if MODULES_LINE in lines:
            for s, e, name in _events(lines[MODULES_LINE]):
                if win is None or (s >= win[0] and e <= win[1]):
                    mods[module_name(name)] += e - s
    if win is None:
        starts = [s for ops in ops_by_dev for s, _, _ in ops]
        ends = [e for ops in ops_by_dev for _, e, _ in ops]
        win = (min(starts), max(ends)) if starts else (0, 0)
    lo, hi = win
    busy, op_self, op_total, gaps = [], collections.Counter(), collections.Counter(), []
    for i, ops in enumerate(ops_by_dev):
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops if e > lo and s < hi]
        u = union([(s, e) for s, e, _ in inside])
        busy.append(sum(e - s for s, e in u))
        for name, t in self_times([(s, e, op_name(n)) for s, e, n in inside]).items():
            op_self[name] += t
        for s, e, n in inside:
            op_total[op_name(n)] += e - s
        if i == 0:
            edges = [lo] + [x for iv in u for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    idle = collections.Counter()
    for g, name in zip(gaps, attribute(gaps, spans), strict=True):
        idle[name] += g[1] - g[0]
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "chips": len(devices),
        "op_self_s": {k: v * ns for k, v in op_self.items()},
        "op_total_s": {k: v * ns for k, v in op_total.items()},
        "module_s": {k: v * ns for k, v in mods.items()},
        "device_ops": [[k, v * ns] for k, v in op_self.most_common(top)],
        "idle_gaps": [[k, v * ns] for k, v in idle.most_common(top)],
    }

