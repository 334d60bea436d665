"""The trace reduction, on a trace recorded on a TPU v5e: ten engine
steps of qwen3-4b (4 layers, 16 slots) with a 2 ms host sleep between
them, each inside a ``bench.engine_step`` / ``bench.host_sleep`` span."""
import pathlib
import random
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "bench"))

from benchlib import trace  # noqa: E402

RECORDED = HERE / "data" / "engine_step_v5e.xplane.pb"


def test_names():
    assert trace.op_name("%paged_decode_attention.12 = bf16[16,8,8,128]{3,2,1,0} "
                         "custom-call(s32[320]{0} %x)") == "paged_decode_attention"
    assert trace.op_name("%copy-done.2 = bf16[4]{0} copy-done(...)") == "copy-done"
    assert trace.op_name("%dynamic-slice_bitcast_fusion.8.remat = bf16[8193,16] "
                         "fusion(...)") == "dynamic-slice_bitcast_fusion"
    assert trace.module_name("jit__step_impl(18431033516731457092)") == "jit__step_impl"


def test_union_and_self_times():
    assert trace.union([(5, 9), (0, 2), (1, 3), (8, 10)]) == [(0, 3), (5, 10)]
    # a while op 0-10 holding a kernel 2-5 and a fusion 6-7
    st = trace.self_times([(0, 10, "while"), (2, 5, "kernel"), (6, 7, "fusion")])
    assert st == {"while": 6, "kernel": 3, "fusion": 1}


def _innermost(gap, spans):
    """The rule ``trace.attribute`` keeps, one gap at a time: the shortest
    span but the window that covers the gap's midpoint, the first of
    equal ones."""
    mid = (gap[0] + gap[1]) // 2
    best = None
    for s, e, name, _ in spans:
        if s <= mid < e and name != "bench.window" and (
                best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host:untraced"


@pytest.mark.parametrize("seed", range(6))
def test_attribute_is_the_innermost_span_at_each_midpoint(seed):
    """Nested and overlapping spans on several threads, spans of equal
    length, midpoints on a span's start and end, gaps no span covers."""
    rng = random.Random(seed)
    spans = [(0, 1000, "bench.window", "main")]
    for k in range(60):
        s = rng.randrange(0, 900)
        e = s + rng.choice([0, 5, 10, 10, 40, rng.randrange(1, 300)])
        spans.append((s, e, f"s{k % 7}", f"t{k % 3}"))
    gaps = [(a, a + rng.choice([0, 1, 2, 10, 20])) for a in
            sorted(rng.randrange(-50, 1050) for _ in range(200))]
    gaps += [(s - 3, s + 3) for s, _, _, _ in spans[1:20]]
    gaps += [(e - 1, e + 1) for _, e, _, _ in spans[20:40]]
    assert trace.attribute(gaps, spans) == [_innermost(g, spans) for g in gaps]


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(RECORDED))


def test_recorded_trace_busy_and_kernel(reduced):
    assert reduced["chips"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # 10 steps x 4 layers of the decode kernel
    assert reduced["op_total_s"]["paged_decode_attention"] == pytest.approx(
        0.035592891, rel=1e-6)
    assert reduced["device_ops"][0][0] == "paged_decode_attention"
    assert reduced["module_s"]["jit__step_impl"] == pytest.approx(0.068679, rel=1e-3)
    # self times add up to the busy union on one chip (nesting is removed)
    assert sum(reduced["op_self_s"].values()) == pytest.approx(
        reduced["busy_s"], rel=0.02)


def test_recorded_trace_attributes_idle_gaps_to_host_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) <= {"bench.engine_step", "bench.host_sleep", "host:untraced"}
    assert gaps["bench.engine_step"] > 0 and gaps["bench.host_sleep"] > 0
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
