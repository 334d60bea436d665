"""Fault tolerance & elasticity: crash failover, exactly-once handle
resolution, mid-run replica addition, autoscaling hysteresis, and the
seeded chaos sweeps behind the CI ``faults`` tier.

Acceptance-criteria coverage:

* killing a replica loses ZERO completed samples: every in-flight handle
  fails over through the abort→resume path and resolves exactly once;
* a crash during prefill, decode, or a staged weight sync never wedges
  the fleet (the sync ack of a dead replica is waived);
* ``add_replica`` places a warmed replica into rotation mid-run;
* the autoscaler scales up under queue pressure and drains/retires idle
  replicas, with patience + cooldown hysteresis (no flapping);
* after any of the above, ``fleet_audit`` is clean (rid→replica map
  empty at quiescence, engines audit clean).
"""
import threading
import time

import jax
import numpy as np
import pytest

from conftest import tiny
from repro.core.async_controller import AsyncController
from repro.core.faults import (FaultInjector, FaultyProxy, ReplicaDeadError,
                               wrap_fleet)
from repro.core.llm_proxy import LLMProxy
from repro.core.rollout_client import RolloutClient
from repro.core.router import AutoscalePolicy, ProxyRouter
from repro.core.sample_buffer import SampleBuffer
from repro.core.scheduler import RolloutProducer
from repro.core.slo import SLOConfig
from repro.core.types import PRIORITY_HIGH, PRIORITY_LOW
from repro.models import get_api
from repro.rollout.paged_engine import PagedDecodeEngine
from test_router import FakeEngine, _task
from test_slo import _ptask


def _faulty_fleet(n=2, router_kw=None, **kw):
    engines = [FakeEngine(**kw) for _ in range(n)]
    proxies = wrap_fleet([LLMProxy(e, name=f"p{i}")
                          for i, e in enumerate(engines)])
    return engines, proxies, ProxyRouter(proxies, **(router_kw or {}))


def _wait_for(cond, timeout=10.0, tick=0.002):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(tick)
    assert cond(), "condition not reached in time"


# ------------------------------------------------------------ FaultyProxy
def test_faulty_proxy_crash_semantics():
    """A killed replica behaves like a crashed process: unhealthy, raises
    on commands, suppresses in-flight callbacks, snapshots lost decode
    progress, and stop() is a no-op."""
    eng = FakeEngine(slots=2)
    p = FaultyProxy(LLMProxy(eng, name="victim"))
    fired = []
    rid = p.generate(_task(50, prompt=[1, 2]), 0, fired.append)
    assert p.healthy()
    p.start()
    _wait_for(lambda: eng.active.get(rid, {"toks": []})["toks"])
    p.kill()
    assert not p.healthy()
    assert p.kills == 1
    assert p.decoded_counts().get(rid, 0) > 0, "lost progress snapshotted"
    with pytest.raises(ReplicaDeadError):
        p.generate(_task(3), 0, fired.append)
    with pytest.raises(ReplicaDeadError):
        p.abort(rid)
    p.kill()                                  # idempotent
    p.stop()                                  # no-op post-mortem
    assert not fired, "callbacks of a crashed replica never fire"
    assert p.steps_executed >= 0, "metric reads survive the crash"


def test_faulty_proxy_kill_after_steps_watchdog():
    eng = FakeEngine(slots=2)
    p = FaultyProxy(LLMProxy(eng), kill_after_steps=3).start()
    p.generate(_task(1000, prompt=[1]), 0, lambda r: None)
    _wait_for(lambda: not p.healthy())
    assert p.inner.steps_executed >= 3


# --------------------------------------------------------------- failover
def test_failover_mid_decode_resolves_all_handles():
    """Tentpole acceptance: kill a replica mid-decode.  Every in-flight
    handle on it fails over to the survivor and resolves exactly once with
    the full budget; the fleet stays audit-clean; counters account the
    lost decode progress."""
    engines, proxies, router = _faulty_fleet(n=2, slots=4)
    router.start()
    client = RolloutClient(router)
    handles = [client.submit(_task(60, prompt=[1, 2, 3])) for _ in range(4)]
    fired = {id(h): [] for h in handles}
    for h in handles:
        h.add_done_callback(fired[id(h)].append)
    _wait_for(lambda: all(len(e.active) == 2 for e in engines))
    _wait_for(lambda: all(st["toks"]
                          for e in engines for st in e.active.values()))
    victim = 0
    proxies[victim].kill()
    assert router.probe_health() == [victim]
    for h in handles:
        res = h.result(30)
        assert not res.aborted and len(res.tokens) == 60
        assert sum(n for _, n in res.legs) == 60
    time.sleep(0.05)
    router.stop()
    assert all(len(v) == 1 for v in fired.values()), "exactly-once"
    assert router.replica_state(victim) == "dead"
    assert router.replicas_alive == 1
    assert router.failovers == 2, "both in-flight handles failed over"
    assert router.lost_tokens > 0, "mid-decode progress was lost"
    assert client.reprefills == 2, "failover re-admits the full prefix"
    router.fleet_audit()
    # the survivor did all the failed-over work: its own 2 plus the 2
    # re-admitted failover prefixes
    assert len(engines[1 - victim].added) == 4


def test_failover_during_prefill_zero_lost_tokens():
    """A crash before any decode step loses nothing: the failed-over
    requests re-admit from their original prompts, lost_tokens stays 0."""
    engines, proxies, router = _faulty_fleet(n=2, slots=4)
    client = RolloutClient(router)
    # un-started fleet: requests sit admitted pre-decode (prefill phase)
    handles = [client.submit(_task(10, prompt=[1] * 5)) for _ in range(4)]
    victim = router._home[handles[0].task.task_id].idx
    proxies[victim].kill()
    router.probe_health()
    router.start()
    for h in handles:
        res = h.result(30)
        assert not res.aborted and len(res.tokens) == 10
    time.sleep(0.05)
    router.stop()
    assert router.lost_tokens == 0
    assert router.failovers == 2
    router.fleet_audit()


def test_dispatch_detects_unprobed_death():
    """Without any health probe, submitting to a dead replica raises
    ReplicaDeadError at dispatch — the router marks it dead and retries
    placement on a survivor transparently."""
    engines, proxies, router = _faulty_fleet(n=2, slots=4)
    proxies[0].kill()                       # router not told
    client = RolloutClient(router)
    router.start()
    res = client.submit(_task(5, prompt=[1, 2])).result(10)
    router.stop()
    assert not res.aborted and len(res.tokens) == 5
    assert router.replica_state(0) == "dead"
    assert engines[1].added, "retried onto the survivor"


def test_retained_pages_dead_replica_reprefills_elsewhere():
    """An abort-with-retain victim whose home replica dies before the
    resume must NOT resume into vanished pages: the continuation falls
    back to re-prefilling the concatenated prefix on a survivor."""
    engines, proxies, router = _faulty_fleet(n=2, slots=2)
    router.start()
    versions = [0]
    client = RolloutClient(router, version_fn=lambda: versions[0])
    h = client.submit(_task(40, prompt=[1, 2, 3]), version=0)
    _wait_for(lambda: any(e.active for e in engines))
    home = 0 if engines[0].active else 1
    versions[0] = 1
    router.abort_stale(min_version=1, retain=True)
    proxies[home].kill()
    router.probe_health()
    res = h.result(30)
    time.sleep(0.05)
    router.stop()
    assert not res.aborted and sum(n for _, n in res.legs) == 40
    assert engines[1 - home].added, "continuation landed on the survivor"
    router.fleet_audit()


def test_crash_during_staged_weight_sync_waives_dead_ack():
    """A replica dying mid-staged-sync must not deadlock the trainer: the
    fleet sync event is set once every LIVE replica acked (the dead one's
    ack is waived by the in-wait health probe)."""
    engines, proxies, router = _faulty_fleet(n=3, slots=2)
    router.start()
    proxies[2].suspend()                    # wedge replica 2's command loop
    _wait_for(lambda: proxies[2].inner.suspend_count == 1)
    ev = router.update_weights_async("w1")
    assert not ev.wait(0.05), "suspended replica has not acked"
    proxies[2].kill()                       # dies mid-sync
    assert ev.wait(10), "dead replica's ack is waived"
    router.stop()
    assert engines[0].update_count == 1 and engines[1].update_count == 1
    assert router.replica_state(2) == "dead"


# -------------------------------------------------------------- elasticity
def test_add_replica_mid_run_warm_placement():
    """add_replica grows the fleet mid-run: the newcomer is warmed with
    the last-synced weights BEFORE taking traffic, and queue scheduling
    immediately places new work on it."""
    engines, proxies, router = _faulty_fleet(n=1, slots=4)
    router.start()
    assert router.update_weights_async("w7").wait(10)   # remembered for warm-starts
    new_eng = FakeEngine(slots=4)
    idx = router.add_replica(LLMProxy(new_eng, name="p_new"))
    assert idx == 1 and router.num_replicas == 2
    assert router.replicas_added == 1
    assert new_eng.update_count == 1, "warmed with the last weights"
    # load replica 0, then submit: least-loaded routing picks the newcomer
    client = RolloutClient(router)
    ballast = client.submit(_task(500, prompt=[1] * 4))
    h = client.submit(_task(5, prompt=[1, 2]))
    assert h.result(10).tokens is not None
    ballast.abort()
    ballast.result(10)
    router.stop()
    assert h.task.task_id in new_eng.added, "new replica took the work"
    router.fleet_audit()


def test_add_replica_requires_factory_or_proxy():
    _, _, router = _faulty_fleet(n=1)
    with pytest.raises(RuntimeError, match="replica_factory"):
        router.add_replica()


def test_autoscale_up_down_hysteresis():
    """Queue pressure past up_patience ticks grows the fleet; an idle
    fleet drains and RETIRES a replica after down_patience ticks; cooldown
    blocks immediate re-action; min/max bounds are honored."""
    made = []

    def factory():
        e = FakeEngine(slots=1)
        made.append(e)
        return LLMProxy(e, name=f"p_auto_{len(made)}")

    pol = AutoscalePolicy(min_replicas=1, max_replicas=2, queue_high=2.0,
                          active_low=0.5, up_patience=2, down_patience=2,
                          cooldown=1)
    eng = FakeEngine(slots=1, step_sleep=0.002)
    router = ProxyRouter([LLMProxy(eng, name="p0")],
                         replica_factory=factory, autoscale=pol)
    router.start()
    client = RolloutClient(router)
    # slots=1: one admits, the rest stack up as queue depth > 2.0 * 1
    handles = [client.submit(_task(10, prompt=[1])) for _ in range(6)]
    _wait_for(lambda: router.queue_depth >= 3)
    assert router.autoscale_tick() is None, "patience: one tick is noise"
    assert router.autoscale_tick() == "up"
    assert router.num_replicas == 2 and router.scale_ups == 1
    assert router.autoscale_tick() is None, "cooldown blocks re-action"
    for h in handles:
        assert h.result(10).tokens is not None
    _wait_for(lambda: router.load() == 0)
    # idle now: queue 0, utilization 0 < 0.5
    assert router.autoscale_tick() is None, "down patience tick 1"
    assert router.autoscale_tick() == "down"
    victim = next(i for i in range(2)
                  if router.replica_state(i) == "draining")
    assert router.autoscale_tick() is None, "cooldown"
    _wait_for(lambda: router.autoscale_tick() is None
              and router.replica_state(victim) == "retired")
    assert router.scale_downs == 1
    assert router.replicas_alive == 1
    # min_replicas floor: the last replica never drains
    for _ in range(10):
        router.autoscale_tick()
    assert router.replicas_alive == 1
    router.stop()


def test_controller_stats_expose_fleet_health():
    """StepStats carries replicas_alive / failovers / lost_tokens when the
    controller drives a router-fronted fleet — including a crash mid-run."""
    engines, proxies, router = _faulty_fleet(n=2, slots=8)
    router.start()
    buf = SampleBuffer(batch_size=4, alpha=1)

    def prompts():
        i = 0
        while True:
            yield i, np.asarray([1, 2], np.int32)
            i += 1

    prod = RolloutProducer(router, buf, prompts(), group_size=1,
                           max_new_tokens=3, reward_fn=lambda s: 1.0)
    prod.start()
    ctrl = AsyncController(buf, proxies, lambda batch: {"loss": 0.0},
                           lambda: "w", alpha=1, router=router)
    try:
        stats = ctrl.train(2, timeout=60)
        proxies[1].kill()
        router.probe_health()
        stats = ctrl.train(1, timeout=60)
    finally:
        prod.stop()
        buf.close()
        router.stop()
    assert stats[0].replicas_alive == 2
    assert stats[-1].replicas_alive == 1
    assert all(len(s.active_per_replica) == s.replicas_alive for s in stats)


# ------------------------------------------------------- real paged fleet
@pytest.fixture(scope="module")
def paged_setup():
    cfg = tiny("qwen3-4b", vocab_size=32)
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


@pytest.mark.timeout(240)
def test_paged_crash_failover_greedy_parity(paged_setup):
    """Acceptance on the REAL engine: kill one of two paged replicas
    mid-decode.  Every handle resolves with output byte-identical to an
    uninterrupted single-engine run (failover re-prefill preserves greedy
    semantics), and the survivor audits clean."""
    cfg, api, params = paged_setup
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 30, n).astype(np.int32) for n in (5, 7, 4, 9)]
    budget = 24

    ref_eng = PagedDecodeEngine(api, params, num_slots=4, max_total_len=64,
                                page_size=8, prefill_chunk=8, eos_id=99,
                                temperature=0.0)
    ref_proxy = LLMProxy(ref_eng).start()
    ref = [list(RolloutClient(ref_proxy).submit(_task(budget, p))
                .result(120).tokens) for p in prompts]
    ref_proxy.stop()

    engines = [PagedDecodeEngine(api, params, num_slots=2, max_total_len=64,
                                 page_size=8, prefill_chunk=8, eos_id=99,
                                 temperature=0.0) for _ in range(2)]
    proxies = wrap_fleet([LLMProxy(e, name=f"paged_{i}")
                          for i, e in enumerate(engines)])
    router = ProxyRouter(proxies).start()
    client = RolloutClient(router)
    # compile both replicas' steps first (one short request each): a
    # replica's first compile can outlast the other's whole decode, which
    # would leave nothing in flight on the replica that gets killed
    for h in [client.submit(_task(2, p)) for p in prompts[:2]]:
        h.result(120)
    assert all(e.total_tokens_decoded > 0 for e in engines)
    warm = [e.total_tokens_decoded for e in engines]
    handles = [client.submit(_task(budget, p)) for p in prompts]
    fired = []
    for h in handles:
        h.add_done_callback(fired.append)
    deadline = time.monotonic() + 60
    while (min(e.total_tokens_decoded - w
               for e, w in zip(engines, warm, strict=True)) < 3
           and time.monotonic() < deadline):
        time.sleep(0.01)
    proxies[0].kill()
    router.probe_health()
    out = [list(h.result(120).tokens) for h in handles]
    time.sleep(0.1)
    router.stop()
    assert out == ref, "failover must preserve greedy outputs"
    assert len(fired) == len(handles), "every handle resolved exactly once"
    assert router.failovers >= 1 and router.replicas_alive == 1
    router.fleet_audit()


# ------------------------------------------------- SLO x fault interaction
def test_preempted_then_killed_resolves_exactly_once():
    """Preemption composing with crash failover: a low-priority request is
    preempted (pages parked on its home replica), then the replica is
    killed before the resume completes.  Every handle — the preempted one,
    the preemptor, and bystanders — still resolves exactly once with its
    full budget; the fleet audits clean."""
    slo = SLOConfig()
    engines = [FakeEngine(slots=1, step_sleep=0.002) for _ in range(2)]
    proxies = wrap_fleet([LLMProxy(e, name=f"p{i}", slo=slo)
                          for i, e in enumerate(engines)])
    router = ProxyRouter(proxies)
    router.start()
    client = RolloutClient(router)
    # least-loaded placement: low0 -> p0, low1 -> p1, high -> p0
    h_low0 = client.submit(_ptask(20, priority=PRIORITY_LOW))
    h_low1 = client.submit(_ptask(30, priority=PRIORITY_LOW))
    _wait_for(lambda: engines[0].active and engines[1].active)
    h_high = client.submit(_ptask(2, priority=PRIORITY_HIGH))
    _wait_for(lambda: proxies[0].preemptions == 1)
    proxies[0].kill()
    router.probe_health()
    fired = []
    for h in (h_low0, h_low1, h_high):
        h.add_done_callback(fired.append)
    for h in (h_low0, h_low1, h_high):
        res = h.result(60)
        assert not res.aborted, "chaos must never surface an aborted handle"
        assert sum(n for _, n in res.legs) == h.task.max_new_tokens
    time.sleep(0.1)
    router.stop()
    assert len(fired) == 3, "exactly-once, zero duplicates"
    # >= 1: the failed-over high-priority request may legitimately preempt
    # the survivor's low-priority decode too
    assert router.preemptions >= 1, "counters survive the crash"
    router.fleet_audit()


def test_stalled_replica_detected_and_failed_over():
    """A hung replica still answers healthy(); only the router's
    steps-frozen probe (slo.replica_stall_s) catches it.  Its in-flight
    work fails over to the survivor like a crash."""
    slo = SLOConfig(replica_stall_s=0.2)
    engines = [FakeEngine(slots=2, step_sleep=0.002) for _ in range(2)]
    proxies = wrap_fleet([LLMProxy(e, name=f"p{i}")
                          for i, e in enumerate(engines)])
    router = ProxyRouter(proxies, slo=slo)
    router.start()
    router.start_health_monitor(0.02)
    client = RolloutClient(router)
    handles = [client.submit(_task(40, prompt=[1, 2])) for _ in range(2)]
    _wait_for(lambda: engines[0].active and engines[1].active)
    proxies[0].stall()
    assert proxies[0].healthy(), "a hung replica still answers healthy()"
    _wait_for(lambda: router.replica_state(0) == "dead", timeout=15)
    for h in handles:
        res = h.result(60)
        assert not res.aborted and sum(n for _, n in res.legs) == 40
    time.sleep(0.1)
    router.stop()        # unblocks the stalled loop; no late delivery
    assert proxies[0].stalls == 1
    assert router.replicas_alive == 1
    router.fleet_audit()


def test_background_threads_joined_on_shutdown():
    """Regression (thread-leak fix): health monitor, FaultyProxy
    self-destruct watchdogs, and the FaultInjector are all joined by their
    owners' stop() — a full start/stop cycle leaves no new live thread."""
    before = set(threading.enumerate())
    engines = [FakeEngine(slots=2, step_sleep=0.002) for _ in range(2)]
    # arm a never-firing self-destruct so each watchdog thread exists
    proxies = wrap_fleet([LLMProxy(e, name=f"p{i}")
                          for i, e in enumerate(engines)],
                         kill_after_steps=10 ** 9)
    router = ProxyRouter(proxies)
    router.start()
    router.start_health_monitor(0.01)
    injector = FaultInjector(proxies, seed=0, max_kills=1, min_alive=2,
                             on_kill=lambda i: router.probe_health())
    injector.start()
    client = RolloutClient(router)
    assert client.submit(_task(5, prompt=[1, 2])).result(30).tokens is not None
    injector.stop()                      # sets halt AND joins
    assert not injector.is_alive()
    router.stop()                        # joins monitor + proxy watchdogs
    deadline = time.monotonic() + 10
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    while leaked and time.monotonic() < deadline:
        time.sleep(0.01)
        leaked = [t for t in threading.enumerate()
                  if t not in before and t.is_alive()]
    assert not leaked, f"threads leaked past shutdown: {leaked}"


# ------------------------------------------------------------ chaos sweeps
@pytest.mark.faults
def test_chaos_sweep_fake_fleet_seeded():
    """Seeded chaos over a 4-replica fleet: the injector kills up to 2
    random replicas while 32 requests run.  Invariants (never timing):
    every handle resolves exactly once with its full budget, no duplicate
    resolutions, survivors audit clean, counters consistent."""
    engines, proxies, router = _faulty_fleet(n=4, slots=4, step_sleep=0.002)
    router.start()
    client = RolloutClient(router)
    injector = FaultInjector(proxies, seed=1234, min_delay=0.01,
                             max_delay=0.06, max_kills=2, min_alive=2,
                             on_kill=lambda i: router.probe_health())
    injector.start()
    rng = np.random.default_rng(5)
    handles = []
    resolved = []
    for _ in range(32):
        n = int(rng.integers(8, 40))
        h = client.submit(_task(n, prompt=[1] * int(rng.integers(2, 6))))
        h.add_done_callback(resolved.append)
        handles.append(h)
        time.sleep(0.002)
    for h in handles:
        res = h.result(60)
        assert not res.aborted, "chaos must never surface an aborted handle"
        assert len(res.tokens) == h.task.max_new_tokens
        assert sum(n for _, n in res.legs) == len(res.tokens)
    injector.stop()
    injector.join(timeout=5)
    time.sleep(0.1)
    router.stop()
    assert len(resolved) == len(handles), "exactly-once, zero duplicates"
    assert router.replicas_alive == 4 - len(injector.killed)
    assert router.failovers >= 0 and router.replicas_failed == len(injector.killed)
    router.fleet_audit()


@pytest.mark.faults
@pytest.mark.timeout(300)
def test_chaos_sweep_with_weight_syncs_and_aborts():
    """Chaos + the full control plane: staged fleet syncs, stale aborts
    with retain, and a mid-sweep add_replica, while the injector kills a
    replica.  All handles resolve, stitched budgets add up, audit clean."""
    engines, proxies, router = _faulty_fleet(n=3, slots=3, step_sleep=0.002)
    router.start()
    versions = [0]
    client = RolloutClient(router, version_fn=lambda: versions[0])
    injector = FaultInjector(proxies, seed=99, min_delay=0.02,
                             max_delay=0.08, max_kills=1, min_alive=2,
                             on_kill=lambda i: router.probe_health())
    injector.start()
    rng = np.random.default_rng(7)
    handles = []
    for wave in range(4):
        for _ in range(6):
            h = client.submit(_task(int(rng.integers(6, 24)),
                                    prompt=[1] * int(rng.integers(2, 5))),
                              version=versions[0])
            handles.append(h)
        time.sleep(0.03)
        ev = router.update_weights_async(f"w{wave}")
        assert ev.wait(30), "fleet sync completes even with a dead replica"
        versions[0] += 1
        router.abort_stale(min_version=versions[0], retain=True)
        if wave == 2:
            router.add_replica(FaultyProxy(
                LLMProxy(FakeEngine(slots=3, step_sleep=0.002), name="p_new")))
    for h in handles:
        res = h.result(60)
        assert not res.aborted
        assert sum(n for _, n in res.legs) == len(res.tokens)
    injector.stop()
    injector.join(timeout=5)
    time.sleep(0.15)
    router.stop()
    assert router.replicas_added == 1
    router.fleet_audit()


@pytest.mark.faults
@pytest.mark.timeout(300)
def test_chaos_sweep_hang_modes():
    """Chaos beyond crashes: the injector fires kill, stall, AND slow
    faults while 24 requests run.  Stalls are invisible to healthy() — the
    router's steps-frozen probe must rescue their work; slowdowns must
    never break correctness.  Invariants: every handle resolves exactly
    once with its full budget, survivors audit clean."""
    slo = SLOConfig(replica_stall_s=0.3)
    engines = [FakeEngine(slots=4, step_sleep=0.002) for _ in range(4)]
    proxies = wrap_fleet([LLMProxy(e, name=f"p{i}")
                          for i, e in enumerate(engines)])
    router = ProxyRouter(proxies, slo=slo)
    router.start()
    router.start_health_monitor(0.02)
    client = RolloutClient(router)
    injector = FaultInjector(proxies, seed=31337, min_delay=0.01,
                             max_delay=0.05, max_kills=3, min_alive=2,
                             modes=("kill", "stall", "slow"),
                             on_kill=lambda i: router.probe_health())
    injector.start()
    rng = np.random.default_rng(13)
    handles, resolved = [], []
    for _ in range(24):
        h = client.submit(_task(int(rng.integers(8, 32)),
                                prompt=[1] * int(rng.integers(2, 6))))
        h.add_done_callback(resolved.append)
        handles.append(h)
        time.sleep(0.003)
    for h in handles:
        res = h.result(90)
        assert not res.aborted, "chaos must never surface an aborted handle"
        assert len(res.tokens) == h.task.max_new_tokens
        assert sum(n for _, n in res.legs) == len(res.tokens)
    injector.stop()
    assert not injector.is_alive()
    time.sleep(0.15)
    router.stop()
    assert len(resolved) == len(handles), "exactly-once, zero duplicates"
    fired = (len(injector.killed) + len(injector.stalled)
             + len(injector.slowed))
    assert fired <= 3
    router.fleet_audit()
