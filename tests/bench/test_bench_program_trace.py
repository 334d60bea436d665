"""Device idle attributed to the program's ``roll.*`` spans, on hand-built
spans and on a trace recorded on a TPU v5e with the program's spans on:
eight ``LLMProxy.step_once`` calls over a paged engine of qwen3-4b (4
layers, 16 slots; groups finishing, prefilling and decoding), each engine
step inside a ``bench.engine_step`` span, all inside ``bench.window``."""
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "bench"))

from benchlib import program_trace as pt  # noqa: E402
from benchlib import trace  # noqa: E402
from benchlib.model import ProgramSpans  # noqa: E402

RECORDED = HERE / "data" / "proxy_step_v5e.xplane.pb"


def test_idle_gaps_are_the_window_less_the_busy_union():
    assert pt.idle_gaps([(2, 4), (3, 6), (8, 9)], (0, 10)) == [
        (0, 2), (6, 8), (9, 10)]
    assert pt.idle_gaps([(-5, 3), (7, 20)], (0, 10)) == [(3, 7)]
    assert pt.idle_gaps([], (0, 10)) == [(0, 10)]


def test_innermost_span_across_threads_wins_else_untraced():
    spans = [
        (0, 100, "roll.proxy.step", "proxy"),
        (10, 40, "roll.engine.prepare", "proxy"),
        (40, 90, "roll.engine.readback", "proxy"),
        (0, 200, "roll.controller.get_batch", "controller"),
        (120, 130, "roll.controller.sync", "controller"),
    ]
    idle = pt.attribute_idle(
        [(20, 30),      # prepare, inside the proxy step and get_batch
         (92, 98),      # the proxy step between its phases
         (85, 95),      # midpoint 90: the read-back has ended
         (150, 170),    # only get_batch covers it
         (121, 125),    # sync, the shorter of two on another thread
         (300, 310)],   # no span
        spans)
    ns = 1e-9
    assert idle == pytest.approx({
        "roll.engine.prepare": 10 * ns, "roll.proxy.step": 16 * ns,
        "roll.controller.get_batch": 20 * ns,
        "roll.controller.sync": 4 * ns, "host:untraced": 10 * ns})


def test_readings_of_a_record():
    rec = {"program_spans": {"roll.engine.prepare": (4, 0.02),
                             "roll.engine.dispatch": (4, 0.01),
                             "roll.engine.readback": (4, 0.5),
                             "roll.proxy.step": (5, 1.0)},
           "program_idle": {"roll.engine.prepare": 1.0,
                            "roll.engine.readback": 0.5,
                            "roll.proxy.publish": 0.25,
                            "host:untraced": 0.25},
           "trace": {"window_s": 10.0},
           "queue_waits": [float(i) for i in range(1, 11)],
           "decode_context": 350, "tokens_decoded": 2}
    assert pt.engine_host_ms(rec) == pytest.approx(7.5)
    assert pt.idle_share_under(rec, "engine") == pytest.approx(10.0)
    assert pt.idle_share_under(rec, "proxy") == pytest.approx(2.5)
    assert pt.queue_wait_p90(rec) == 9.0
    assert pt.decode_context(rec) == 175.0
    # a program without the spans and counters reads nothing
    empty = {"trace": {"window_s": 10.0}, "tokens_decoded": 2}
    assert all(f(empty) is None for f in (
        pt.engine_host_ms, pt.queue_wait_p90, pt.decode_context,
        lambda r: pt.idle_share_under(r, "engine")))


def test_a_trace_without_program_spans_is_all_untraced():
    """The recorded decode-step trace has only ``bench.*`` spans: every idle
    nanosecond of ``trace.reduce``'s window goes to host:untraced."""
    path = str(HERE / "data" / "engine_step_v5e.xplane.pb")
    reduced = trace.reduce(path)
    assert pt.idle_by_span(path) == {"host:untraced": pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)}


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(str(RECORDED)), pt.idle_by_span(str(RECORDED))


def test_recorded_trace_engine_step_idle_splits_into_engine_phases(recorded):
    reduced, idle = recorded
    gaps = dict(reduced["idle_gaps"])
    engine = sum(v for k, v in idle.items() if k.startswith("roll.engine."))
    assert engine == pytest.approx(gaps["bench.engine_step"], rel=0.05)
    # every idle interval of the window is attributed once
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert {"roll.engine.prepare", "roll.engine.dispatch",
            "roll.engine.readback", "roll.engine.update"} <= set(idle)
    assert set(idle) <= {"roll.proxy.step", "roll.proxy.commands",
                         "roll.proxy.admit", "roll.proxy.publish",
                         "roll.engine.finish", "roll.engine.prepare",
                         "roll.engine.dispatch", "roll.engine.readback",
                         "roll.engine.update", "host:untraced"}


def test_recorded_trace_nests_engine_spans_in_proxy_steps():
    from jax.profiler import ProfileData
    spans = pt.program_spans(ProfileData.from_file(str(RECORDED)))
    steps = [(s, e) for s, e, n, _ in spans if n == "roll.proxy.step"]
    engine = [(s, e) for s, e, n, _ in spans if n.startswith("roll.engine.")]
    assert len(steps) == 8 and len(engine) == 8 * 5
    assert all(any(a <= s and e <= b for a, b in steps) for s, e in engine)


class FakeTracing:
    """``repro.core.tracing``'s calls, recorded, with fixed totals."""
    TOTALS = {"roll.engine.dispatch": (8, 0.004), "roll.engine.prepare": (8, 0.03),
              "roll.engine.readback": (8, 0.2), "roll.proxy.step": (8, 0.3)}

    def __init__(self):
        self.calls = []

    def enable(self):
        self.calls.append("enable")

    def disable(self):
        self.calls.append("disable")

    def totals(self):
        self.calls.append("totals")
        return dict(self.TOTALS)


def test_traced_run_fills_program_spans_and_idle_from_its_trace(recorded):
    reduced, idle = recorded
    fake = FakeTracing()
    program = ProgramSpans(fake)
    program.start()
    assert fake.calls == ["enable"]
    program.stop()
    assert fake.calls == ["enable", "totals", "disable"]
    rec = dict(program.record(str(RECORDED)), trace=reduced)
    assert rec["program_spans"] == FakeTracing.TOTALS
    assert rec["program_idle"] == idle
    # the four readers read it
    assert pt.engine_host_ms(rec) == pytest.approx(1e3 * 0.034 / 8)
    engine = sum(v for k, v in idle.items()
                 if k.startswith("roll.engine.") and k != pt.READBACK)
    proxy = sum(v for k, v in idle.items() if k.startswith("roll.proxy."))
    assert engine > 0 and proxy > 0
    assert pt.idle_share_under(rec, "engine") == pytest.approx(
        100 * engine / reduced["window_s"])
    assert pt.idle_share_under(rec, "proxy") == pytest.approx(
        100 * proxy / reduced["window_s"])


def test_attribute_idle_agrees_with_the_rule_gap_by_gap():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(RECORDED))
    spans = pt.program_spans(pd)
    dev, = [p for p in pd.planes if p.name.startswith(trace.DEVICE_PREFIX)]
    ops = [(int(ev.start_ns), int(ev.end_ns))
           for ln in dev.lines if ln.name == trace.OPS_LINE for ev in ln.events]
    gaps = pt.idle_gaps(ops, (min(s for s, _ in ops), max(e for _, e in ops)))
    by_hand = {}
    for g in gaps:
        mid = (g[0] + g[1]) // 2
        covering = [(e - s, k, n) for k, (s, e, n, _) in enumerate(spans)
                    if s <= mid < e]
        name = min(covering)[2] if covering else "host:untraced"
        by_hand[name] = by_hand.get(name, 0) + (g[1] - g[0])
    got = pt.attribute_idle(gaps, spans)
    assert set(got) == set(by_hand) and len(got) > 2
    for name, ns in by_hand.items():
        assert got[name] == pytest.approx(ns * 1e-9, rel=1e-12), name
