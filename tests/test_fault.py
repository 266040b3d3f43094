"""Fault tolerance: heartbeat detection, elastic pool membership,
stragglers, and the chaos-hardened live fabric (injected crash /
stall / NaN faults, health-driven failover, retry budgets, publish
gates)."""
import time

import numpy as np
import pytest

from conftest import reference_greedy, sample_prompts
from repro.core.cluster import ClusterConfig, ClusterController
from repro.core.interfaces import BatchResult, Request
from repro.runtime.elastic import ElasticServingPool
from repro.runtime.fault import (
    FailureDetector, FaultEvent, FaultInjector, HealthConfig,
    HealthMonitor, InjectedFault, RetryPolicy, StragglerWatch,
)
from repro.runtime.replica import InterferenceSurface, SimReplica
from repro.runtime.simulator import Simulator

ARCH = "qwen1.5-0.5b"
PROMPT_PAD, MAX_GEN, SLOTS = 10, 6, 2


def _cluster(n=4):
    sim = Simulator()
    cluster = ClusterController(ClusterConfig())
    results = []
    for i in range(n):
        r = SimReplica(f"r{i}", "m", sim,
                       lambda res, sid: results.append(res), seed=i)
        cluster.add_replica(r)
    return sim, cluster, results


# =========================================================================
# Heartbeat detection (load-bearing heartbeats, no liveness back-channel)
# =========================================================================
def test_failure_detector_removes_dead_replica():
    """Detection keys off actual heartbeat() calls: the replica that
    stops beating accrues misses and is removed; peers that keep
    beating stay."""
    sim, cluster, _ = _cluster()
    det = FailureDetector(cluster, timeout=1.0, max_misses=2)
    healthy = [rid for rid in cluster.replicas if rid != "r1"]
    for now in (0.0, 0.5):
        for rid in healthy:
            det.heartbeat(rid, now)
        det.heartbeat("r1", now)
    # r1 goes silent after 0.5; the others keep beating
    for rid in healthy:
        det.heartbeat(rid, 2.0)
    assert det.poll(2.0) == []             # 1.5 s gap -> first miss only
    assert "r1" in cluster.replicas
    for rid in healthy:
        det.heartbeat(rid, 3.5)
    assert det.poll(3.5) == ["r1"]         # second miss -> dead
    assert "r1" not in cluster.replicas
    assert det.removed == ["r1"]
    assert sorted(cluster.replicas) == sorted(healthy)


def test_failure_detector_first_sight_grace():
    """A replica first seen at poll time gets a grace window — joining
    the pool must not count as a missed beat."""
    sim, cluster, _ = _cluster(2)
    det = FailureDetector(cluster, timeout=1.0, max_misses=1)
    assert det.poll(5.0) == []             # registration, not a miss
    assert det.poll(5.5) == []             # still inside the window
    assert sorted(det.poll(7.0)) == ["r0", "r1"]    # now truly silent


def test_elastic_join_leave():
    sim, cluster, results = _cluster(2)
    pool = ElasticServingPool(cluster)
    cluster.dispatcher_for("m")
    newr = SimReplica("r9", "m", sim, lambda res, sid: None, seed=9)
    pool.join(newr, now=1.0)
    assert "r9" in cluster.replicas
    assert "r9" in cluster.dispatchers["m"].replicas
    pool.leave("r9", now=2.0)
    assert "r9" not in cluster.replicas
    assert "r9" not in cluster.dispatchers["m"].replicas


def test_elastic_pool_live_view_routes_to_joiner():
    """Pin the behavior ElasticServingPool depends on: dispatcher
    replica sets are LIVE views over the cluster registry, so a joiner
    becomes routable on the next tick without re-wiring."""
    sim, cluster, _ = _cluster(1)
    pool = ElasticServingPool(cluster)
    d = cluster.dispatcher_for("m")
    assert list(d._active_replicas(0.0)) == ["r0"]
    newr = SimReplica("r9", "m", sim, lambda res, sid: None, seed=9)
    pool.join(newr, now=1.0)
    assert sorted(d._active_replicas(1.0)) == ["r0", "r9"]
    assert pool.joined == 1


# =========================================================================
# Straggler detection
# =========================================================================
def test_straggler_watch_flags_outlier():
    w = StragglerWatch(threshold=2.0, window=16)
    for _ in range(10):
        for rid, lat in [("a", 1.0), ("b", 1.1), ("c", 0.9), ("d", 5.0)]:
            w.observe(rid, lat)
    assert w.stragglers() == ["d"]


def test_straggler_watch_identical_medians_flag_nothing():
    """threshold x identical-median must be vacuous: an all-equal (or
    all-zero) cluster has no stragglers."""
    for lat in (1.0, 0.0):
        w = StragglerWatch(threshold=2.0)
        for _ in range(10):
            for rid in ("a", "b", "c"):
                w.observe(rid, lat)
        assert w.stragglers() == []


def test_straggler_watch_two_replicas_and_window():
    """Peer-relative medians work at pool size 2, and the sample
    window is a bounded deque (old samples age out)."""
    w = StragglerWatch(threshold=2.0, window=8, min_samples=4)
    for _ in range(8):
        w.observe("a", 0.01)
        w.observe("b", 0.08)
    assert w.stragglers() == ["b"]
    assert len(w.samples["a"]) == 8          # window bound held
    # b recovers: fresh fast samples displace the stall window
    for _ in range(8):
        w.observe("b", 0.01)
    assert w.stragglers() == []
    w.reset("a")
    assert "a" not in w.samples


def test_straggler_watch_warmup_drops_compile_spikes():
    """The first ``warmup`` observations per replica are dropped: the
    replica that pays the one-time jit compile must not be quarantined
    as a straggler for it."""
    w = StragglerWatch(threshold=2.0, min_samples=2, warmup=3)
    for _ in range(3):
        w.observe("a", 9.0)          # compile spikes — dropped
    for _ in range(5):
        w.observe("a", 0.01)
        w.observe("b", 0.01)
    assert w.stragglers() == []
    assert max(w.samples["a"]) == pytest.approx(0.01)


# =========================================================================
# Retry policy (budget, backoff, poison verdict, untouched SLO clock)
# =========================================================================
def _req(i=0):
    return Request(request_id=i, stream_id="m", arrival=0.0,
                   deadline=10.0, tokens=4)


def test_retry_policy_backoff_and_budget_exhaustion():
    p = RetryPolicy(max_retries=2, max_failures=5,
                    backoff_base=0.1, backoff_factor=2.0)
    r = _req()
    assert p.on_requeue(r, 1.0, replica_died=False)
    assert r.retries == 1 and r.not_before == pytest.approx(1.1)
    assert r.deadline == 10.0               # SLO clock never extended
    assert p.on_requeue(r, 2.0, replica_died=False)
    assert r.not_before == pytest.approx(2.2)    # exponential backoff
    assert not p.on_requeue(r, 3.0, replica_died=False)
    assert r.terminal and r.status == "failed"
    assert r.failed_reason == "retries_exhausted"
    assert p.retried == 2 and p.rejected == [r]


def test_retry_policy_poison_request():
    """A request whose accepting replica dies max_failures times is
    terminally rejected, not requeued forever."""
    p = RetryPolicy(max_retries=100, max_failures=2)
    r = _req()
    assert p.on_requeue(r, 0.0, replica_died=True)
    assert not p.on_requeue(r, 1.0, replica_died=True)
    assert r.status == "failed" and r.failed_reason == "poison"
    # quarantine drains (replica survived) never count as failures
    p2 = RetryPolicy(max_retries=100, max_failures=2)
    r2 = _req()
    for t in range(5):
        assert p2.on_requeue(r2, float(t), replica_died=False)
    assert r2.failures == 0 and r2.status == "pending"


def test_dispatcher_honors_backoff_gate():
    """A requeued request with a not_before gate is skipped (kept in
    place) until the clock passes the gate."""
    sim, cluster, _ = _cluster(1)
    d = cluster.dispatcher_for("m")
    gated, ready = _req(0), _req(1)
    gated.not_before = 5.0
    d.submit(gated)
    d.submit(ready)
    batch = d._select_batch("r0", 2, now=1.0, pred=0.0)
    assert batch == [ready]
    assert list(d.queue) == [gated]          # kept its place, not shed
    batch = d._select_batch("r0", 2, now=6.0, pred=0.0)
    assert batch == [gated]


# =========================================================================
# Health monitor (pump-driven)
# =========================================================================
def test_health_monitor_missed_beats_and_pump_failure():
    hm = HealthMonitor(HealthConfig(beat_timeout=0.5, max_misses=2,
                                    poll_interval=0.1))
    hm.beat("r0", 0.0)
    hm.beat("r1", 0.0)
    assert hm.poll(0.2) == ([], [])
    hm.beat("r0", 1.0)                       # r1 silent since 0.0
    dead, _ = hm.poll(1.0)
    assert dead == []                        # first miss
    hm.beat("r0", 2.0)
    dead, _ = hm.poll(2.0)
    assert dead == ["r1"]                    # second miss -> dead
    # pump exceptions surface immediately, bypassing the poll cadence
    hm.failure("r0", 2.01, reason="InjectedFault")
    dead, _ = hm.poll(2.02)
    assert dead == ["r0"]


# =========================================================================
# Chaos-hardened live fabric
# =========================================================================
def _drive_fabric(fab, reqs, max_iters=4000):
    """Drive the fabric's OWN tick (containment + health verdicts)
    until every request is terminal."""
    for r in reqs:
        fab.submit(r)
    t0 = time.perf_counter()
    for _ in range(max_iters):
        now = time.perf_counter() - t0
        busy = fab.tick(now)
        if not busy and all(r.terminal for r in reqs):
            return now
        if not busy:
            time.sleep(0.002)
    raise AssertionError(
        f"fabric did not drain: "
        f"{sum(not r.terminal for r in reqs)} non-terminal")


def _fabric_requests(cfg, lens, gens, n_adapters=0):
    prompts = sample_prompts(cfg, len(lens), lens)
    reqs = [Request(request_id=i, stream_id=cfg.name, arrival=0.0,
                    deadline=1e9, tokens=gens[i], prompt=prompts[i],
                    adapter_id=f"tenant{i % n_adapters}"
                    if n_adapters else None)
            for i in range(len(lens))]
    return reqs, prompts


def test_injected_crash_failover_with_tenant_reregistration():
    """An injected mid-wave crash is contained by the fabric tick,
    detected by the health monitor, and failed over: 100% completion,
    greedy tokens bit-identical to the per-tenant reference, and a
    tenant registered ONLY on the dead replica is re-registered on the
    survivor."""
    from repro.runtime.fabric import build_fabric

    # crash early enough that the trace is still live even on a fully
    # warm jit cache (the whole smoke trace drains in ~0.1-0.2s warm)
    inj = FaultInjector([FaultEvent(at=0.05, replica_id="r1",
                                    kind="crash")])
    fab, cfg = build_fabric(ARCH, 2, n_slots=SLOTS,
                            prompt_len=PROMPT_PAD, gen_tokens=MAX_GEN,
                            paged=True, block_size=4, n_adapters=2,
                            injector=inj)
    # a tenant resident ONLY on the doomed replica: failover must carry
    # it to the survivor or its requests become unservable
    r1 = fab.replicas["r1"]
    solo_tree = r1.adapters.host_tree("tenant1")
    r1.adapters.register("tenant9", solo_tree, version=7)
    assert not fab.replicas["r0"].adapters.is_registered("tenant9")

    lens = [6, 8, 5, 7, 6, 9, 4, 8]
    gens = [5, 4, 5, 3, 4, 5, 6, 3]
    reqs, prompts = _fabric_requests(cfg, lens, gens, n_adapters=2)
    _drive_fabric(fab, reqs)

    assert "r1" not in fab.replicas and "r0" in fab.replicas
    assert fab.failovers == 1
    assert any(kind == "crash" for _, rid, kind in inj.injected)
    assert all(r.completed_at is not None for r in reqs)
    assert all(len(r.output_tokens) == gens[i]
               for i, r in enumerate(reqs))
    # greedy streams bit-identical to the per-tenant oracle despite the
    # crash + requeue (survivors regenerate from the prompt)
    rep = fab.replicas["r0"]
    for i, r in enumerate(reqs):
        tree = rep.adapters.host_tree(r.adapter_id)
        ref = reference_greedy(rep.engine.model, rep.params, tree,
                               prompts[i], gens[i])
        assert r.output_tokens == ref, f"req {i} diverged after crash"
    # multi-tenant failover: the solo tenant moved, version intact
    assert rep.adapters.is_registered("tenant9")
    assert rep.adapters.version("tenant9") == 7


def test_straggler_quarantine_requeues_and_recovers():
    """An injected stall flags the replica as a straggler: its pending
    work drains back to the stream queue (front, order preserved), its
    subflows are suspended for the cooldown, and the pool still
    completes every request."""
    from repro.runtime.fabric import FabricConfig, build_fabric

    inj = FaultInjector([FaultEvent(at=0.0, replica_id="r1",
                                    kind="stall", duration=60.0,
                                    stall_s=0.05)])
    cfg_f = FabricConfig(straggler_threshold=2.0, straggler_window=8,
                         straggler_min_samples=4,
                         straggler_warmup=4,   # jit-compile grace
                         quarantine_cooldown=30.0,     # stays benched
                         health_poll_interval=0.05)
    fab, cfg = build_fabric(ARCH, 2, n_slots=SLOTS,
                            prompt_len=PROMPT_PAD, gen_tokens=MAX_GEN,
                            paged=True, block_size=4, cfg=cfg_f,
                            injector=inj)
    lens = [6, 8, 5, 7, 6, 9, 4, 8, 5, 7, 6, 8, 5, 7]
    gens = [5, 4, 5, 3, 4, 5, 6, 3, 4, 4, 5, 6, 4, 5]
    reqs, prompts = _fabric_requests(cfg, lens, gens)
    _drive_fabric(fab, reqs)

    assert fab.quarantines >= 1
    assert any(a == "quarantine" and rid == "r1"
               for _, rid, a in fab.fault_log)
    d = fab.cluster.dispatchers[cfg.name]
    assert d.suspended.get("r1", 0.0) > 0.0
    # the straggler is still a pool MEMBER (quarantine, not kill)
    assert "r1" in fab.replicas
    assert all(r.completed_at is not None for r in reqs)
    # requeued requests kept their original SLO clock
    assert all(r.deadline == 1e9 for r in reqs)
    rep = fab.replicas["r0"]
    for i, r in enumerate(reqs):
        ref = reference_greedy(rep.engine.model, rep.params, rep.lora,
                               prompts[i], gens[i])
        assert r.output_tokens == ref, f"req {i} diverged"


def test_train_ticks_do_not_flag_stragglers():
    """A tick with a fused train leg costs what the session's batch
    costs, not what the replica's health does: a replica whose train
    ticks are slow (here r0, by 0.05 s each) is no straggler."""
    from repro.runtime.fabric import FabricConfig, build_fabric

    cfg_f = FabricConfig(enable_finetuning=True, bootstrap_steps=3,
                         steps_per_round=3, decision_interval=0.05,
                         straggler_threshold=2.0, straggler_window=8,
                         straggler_min_samples=2, straggler_warmup=0,
                         health_poll_interval=0.0)
    fab, cfg = build_fabric(ARCH, 2, n_slots=SLOTS,
                            prompt_len=PROMPT_PAD, gen_tokens=MAX_GEN,
                            paged=True, block_size=4, cfg=cfg_f)
    batcher = fab.replicas["r0"].batcher
    step = batcher.step

    def slow_train_step(train_batch=None, **kw):
        out = step(train_batch=train_batch, **kw)
        if batcher.last_tick_trained:
            time.sleep(0.05)
        return out

    batcher.step = slow_train_step
    lens = [6, 8, 5, 7, 6, 9, 4, 8]
    gens = [6, 6, 5, 6, 4, 5, 6, 5]
    reqs, _ = _fabric_requests(cfg, lens, gens)
    out = fab.run(reqs, min_rounds=1, timeout=120.0)
    assert out["fl_rounds"] >= 1
    assert all(r.completed_at is not None for r in reqs)
    assert fab.quarantines == 0, fab.fault_log


def test_retry_budget_exhaustion_terminal_status():
    """With a zero retry budget, requests drained from a crashed
    replica are terminally rejected — the run loop settles instead of
    spinning, and survivors' requests still complete.  The crash fires
    on r1's FIRST pump, while its share of the initial dispatch wave is
    still queued on it — later crash times race the (warm-jit) trace
    drain and can strand nothing."""
    from repro.runtime.fabric import FabricConfig, build_fabric

    inj = FaultInjector([FaultEvent(at=0.0, replica_id="r1",
                                    kind="crash")])
    fab, cfg = build_fabric(ARCH, 2, n_slots=SLOTS,
                            prompt_len=PROMPT_PAD, gen_tokens=MAX_GEN,
                            paged=True, block_size=4,
                            cfg=FabricConfig(max_retries=0),
                            injector=inj)
    lens = [6, 8, 5, 7, 6, 9, 4, 8]
    gens = [5, 4, 5, 3, 4, 5, 6, 3]
    reqs, _ = _fabric_requests(cfg, lens, gens)
    _drive_fabric(fab, reqs)

    assert all(r.terminal for r in reqs)
    failed = [r for r in reqs if r.status == "failed"]
    done = [r for r in reqs if r.completed_at is not None]
    # the crash stranded SOME requests; with no retry budget they went
    # terminal instead of completing elsewhere
    assert failed and done
    assert len(failed) + len(done) == len(reqs)
    assert all(r.failed_reason == "retries_exhausted" for r in failed)
    assert len(fab.retry_policy.rejected) == len(failed)


def test_nan_shadow_publish_rejected_bit_identical():
    """A NaN-poisoned shadow is rejected at the round boundary: the
    round aborts, the served adapter stays bit-for-bit at its last
    published version, and the rejection is counted."""
    import jax
    import jax.numpy as jnp

    from repro.runtime.fabric import build_fabric

    fab, cfg = build_fabric(ARCH, 1, n_slots=SLOTS,
                            prompt_len=PROMPT_PAD, gen_tokens=MAX_GEN)
    rep = fab.replicas["r0"]
    before = jax.tree.map(np.asarray, rep.lora)
    v0 = rep.adapter_version

    rep.begin_round(train_batch=2, infer_batch=0, steps=2, now=0.0)
    while rep._session is not None and not rep._session.done:
        rep.pump_once(0.0)
    rep._poison_shadow()
    assert rep.batcher.train_lora is not None
    stats = rep.finish_round(1.0)            # gate fires here
    assert rep.batcher.train_lora is None    # round aborted
    assert rep.publish_adapter() == v0       # no version bump
    assert rep.batcher.stats.nan_publishes_blocked == 1
    after = jax.tree.map(np.asarray, rep.lora)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        assert np.array_equal(a, b)          # served tree untouched
    # a non-finite loss never reaches the coordinator's fit inputs
    assert stats.loss_after == stats.loss_after \
        or np.isnan(stats.loss_after)

    # set_adapter guards the FedAvg seam the same way
    poisoned = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan),
                            rep.lora)
    rep.set_adapter(poisoned, version=99)
    assert rep.adapter_version == v0
    assert rep.batcher.stats.nan_publishes_blocked == 2


def test_remove_replica_mid_session():
    """Losing a COMBINED replica must not wedge the FL session."""
    from repro.core.states import ReplicaState
    sim, cluster, _ = _cluster(4)
    for rid in cluster.replicas:
        cluster.states.transition(rid, ReplicaState.IDLE, 0.0)
    cluster.launcher.maybe_launch(0.0)
    assert cluster.launcher.sessions
    some = next(iter(cluster.launcher.sessions.values()))
    victim = some.session.members[0]
    cluster.remove_replica(victim, 1.0)
    assert victim not in cluster.replicas
    for a in cluster.launcher.sessions.values():
        assert victim not in a.session.members
