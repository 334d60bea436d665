"""Program spans (``repro.core.tracing``), the proxy's queue-wait stamps and
the engine's decode-context counter."""
import itertools
import threading
import time
import tracemalloc

import jax
import numpy as np
import pytest

from conftest import tiny
from repro.core import tracing
from repro.core.llm_proxy import LLMProxy
from repro.core.rollout_client import RolloutClient
from repro.core.types import RolloutTask, next_uid
from repro.models import get_api
from repro.rollout.paged_engine import PagedDecodeEngine

ENGINE_SPANS = ("roll.engine.finish", "roll.engine.prepare",
                "roll.engine.dispatch", "roll.engine.readback",
                "roll.engine.update")


@pytest.fixture(scope="module")
def setup():
    cfg = tiny("qwen3-4b", vocab_size=32)
    api = get_api(cfg)
    return api, api.init(jax.random.PRNGKey(0))


@pytest.fixture
def spans_on():
    tracing.enable()
    yield
    tracing.disable()


def _engine(api, params, **kw):
    base = dict(num_slots=4, max_total_len=64, page_size=8, prefill_chunk=8,
                eos_id=99, temperature=0.0)
    base.update(kw)
    return PagedDecodeEngine(api, params, **base)


def _task(budget, prompt=(3, 1, 4, 1)):
    return RolloutTask(task_id=next_uid(), prompt_id=0, replica_idx=0,
                       prompt_tokens=np.asarray(prompt, np.int32),
                       max_new_tokens=budget)


class _Recorder:
    """Stands in for ``TraceAnnotation``: logs (thread, enter|exit, name)."""
    events = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.events.append((threading.get_ident(), "enter", self.name))

    def __exit__(self, *exc):
        self.events.append((threading.get_ident(), "exit", self.name))


# ------------------------------------------------------------ the facility
def _peak_bytes(make_span):
    for _ in range(10):                 # warm the interpreter's caches
        with make_span("roll.a"):
            pass
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        for _ in itertools.repeat(None, 1000):
            with make_span("roll.a"):
                pass
        return tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()


def test_spans_off_are_one_shared_noop_with_no_clock_and_no_allocation(
        monkeypatch):
    tracing.enable()
    tracing.disable()

    class NoClock:
        @staticmethod
        def monotonic():
            raise AssertionError("a span read the clock while spans were off")

    monkeypatch.setattr(tracing, "time", NoClock)
    assert tracing.span("roll.a") is tracing.span("roll.b")
    assert tracing.annotate("roll.a") is tracing.span("roll.a")
    # the peak of traced memory over 1000 spans is no higher than over
    # 1000 calls that return an object made beforehand
    made = tracing._Noop()
    assert _peak_bytes(tracing.span) <= _peak_bytes(lambda name: made)
    tracing.add("roll.a", 1.0)
    assert tracing.totals() == {}


def test_spans_on_total_counts_and_seconds(spans_on):
    with tracing.span("roll.x"):
        time.sleep(0.01)
    with tracing.span("roll.x"):
        pass
    tracing.add("roll.y", 0.5)
    totals = tracing.totals()
    assert totals["roll.x"][0] == 2 and totals["roll.x"][1] >= 0.01
    assert totals["roll.y"] == (1, 0.5)
    totals["roll.x"] = None             # a copy
    assert tracing.totals()["roll.x"][0] == 2
    tracing.enable()                    # resets
    assert tracing.totals() == {}


# ------------------------------------------------------------ placement
def test_step_once_spans_nest_engine_phases_in_the_proxy_step(
        setup, spans_on, monkeypatch):
    api, params = setup
    monkeypatch.setattr(tracing, "TraceAnnotation", _Recorder)
    _Recorder.events = []
    proxy = LLMProxy(_engine(api, params))
    done = []
    for _ in range(2):
        proxy.generate(_task(3), version=0, callback=done.append)
    # step 1 admits both and prefills one, step 2 prefills the other
    # (reading its first token back) and decodes the first
    assert proxy.step_once() and proxy.step_once()
    totals = tracing.totals()
    for name in ("roll.proxy.step", "roll.proxy.commands", "roll.proxy.admit",
                 "roll.proxy.publish") + ENGINE_SPANS:
        assert totals[name][0] == 2, (name, totals)
    assert "roll.proxy.idle" not in totals      # the loop thread sleeps there
    assert "roll.proxy.apply_weights" not in totals

    stack = []
    for _tid, kind, name in _Recorder.events:
        if kind == "enter":
            # the proxy step encloses every other span, one level deep
            assert stack == ([] if name == "roll.proxy.step"
                             else ["roll.proxy.step"]), (name, stack)
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    order = [n for _, k, n in _Recorder.events
             if k == "enter" and n.startswith("roll.engine.")]
    assert order == list(ENGINE_SPANS) * 2


def test_loop_thread_spans_idle_and_staged_weight_apply(setup, spans_on):
    api, params = setup
    proxy = LLMProxy(_engine(api, params)).start()
    try:
        assert proxy.update_weights_async(params).wait(30)
        deadline = time.monotonic() + 10
        while ("roll.proxy.idle" not in tracing.totals()
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        proxy.stop()
    totals = tracing.totals()
    assert totals["roll.proxy.apply_weights"][0] == 1
    assert totals["roll.proxy.commands"][0] >= 1
    assert totals["roll.proxy.idle"][0] >= 1


def test_controller_spans_reuse_the_step_stats_intervals(spans_on):
    from repro.core.async_controller import AsyncController
    from repro.core.sample_buffer import SampleBuffer
    from repro.core.types import Sample

    buf = SampleBuffer(batch_size=1, alpha=1)
    buf.put(Sample(sample_id=next_uid(), prompt_id=0, replica_idx=0,
                   prompt_tokens=np.zeros(2, np.int32),
                   response_tokens=np.zeros(2, np.int32),
                   logprobs=np.zeros(2, np.float32), reward=1.0))

    def train(batch):
        time.sleep(0.01)
        return {"loss": 0.0}

    ctrl = AsyncController(buf, [], train, lambda: None, alpha=1)
    stats = ctrl.train(1, timeout=10)[0]
    totals = tracing.totals()
    assert totals["roll.controller.get_batch"] == (1, stats.wait_time)
    assert totals["roll.controller.train"] == (1, stats.train_time)
    assert totals["roll.controller.sync"] == (1, stats.sync_time)


# ------------------------------------------------------------ queue wait
class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_queue_wait_per_leg_and_summed_over_abort_resume(setup):
    """One slot, a clock that moves 1 s per proxy step: the first request
    is admitted at once, the second waits for it, and the second's aborted
    (pages retained) and resumed leg waits again behind a third."""
    api, params = setup
    clock = _Clock()
    proxy = LLMProxy(_engine(api, params, num_slots=1))
    proxy._clock = clock
    client = RolloutClient(proxy, version_fn=lambda: 0)
    h0, h1 = client.submit(_task(2)), client.submit(_task(6))

    def step():
        proxy.step_once()
        clock.t += 1.0

    # t=0 admit h0; t=1 decode; t=2 h0 finishes; t=3 admit h1 (waited 3)
    for _ in range(5):
        step()
    assert not h1.done()
    h2 = client.submit(_task(2))                  # received at t=5
    h1.abort(retain=True)
    # t=5: h2 admitted (waited 0); h1's next leg received at t=5 queues
    # behind it; t=7 h2 finishes; t=8 the leg resumes (waited 3)
    while not (h1.done() and h2.done()):
        assert clock.t < 30
        step()
    r0, r1, r2 = (h.result(0) for h in (h0, h1, h2))
    assert not (r0.aborted or r1.aborted or r2.aborted)
    assert client.resumes == 1 and len(r1.legs) == 2
    assert r0.task.meta["queue_wait_s"] == 0.0
    assert r2.task.meta["queue_wait_s"] == 0.0
    assert r1.task.meta["queue_wait_s"] == 3.0 + 3.0


# ------------------------------------------------------------ decode context
def test_total_decode_context_is_the_sum_of_pos_plus_one(setup):
    api, params = setup
    eng = _engine(api, params, num_slots=6)
    rng = np.random.default_rng(0)
    eng.add_request(0, rng.integers(1, 32, 5), 30)
    eng.submit_group([1, 2, 3], rng.integers(1, 32, 11), 30)
    eng.add_request(4, rng.integers(1, 32, 3), 30)
    expect = 0
    for i in range(12):
        if i == 6:          # park one decoding request and bring it back
            eng.abort(0, retain=True)
            eng.resume_request(0, 5, 20)
        pos = np.asarray(eng.pos)
        expect += sum(int(pos[s]) + 1 for s, st in eng.slots.items()
                      if st.phase == "decode")
        eng.step()
    assert eng.total_decode_steps > 0
    assert eng.total_decode_context == expect
