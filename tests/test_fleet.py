"""Self-healing serving fleet, unit plane (``mxnet_tpu.serving.fleet``
/ ``router`` / ``autoscaler`` / ``replica``): least-depth routing with
consistent-hash fallback, typed at-most-once failover, the latched
brownout state machine, the SLO autoscaler's deterministic ``tick()``
through the elastic membership signal bus, plus the PR's satellites —
``ServeFuture.cancel``, decorrelated-jitter backoff, the federation
``cluster_values`` consumer and the watchdog listener registry.

Everything here is in-process (LocalReplica / fakes) — the
process-level recovery certification lives in test_fleet_recovery.py.
"""

import random
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.observability import federation as fed
from mxnet_tpu.observability import watchdog
from mxnet_tpu.resilience.elastic import MembershipMonitor
from mxnet_tpu.runtime import backoff_delays, retry_with_backoff
from mxnet_tpu.serving import (
    BrownoutShed,
    InferenceEngine,
    LocalReplica,
    ReplicaDead,
    ReplicaLost,
    ReplicaRouter,
    RequestCancelled,
    ServerOverloaded,
    ServingFleet,
    SLOAutoscaler,
)
from mxnet_tpu.serving.replica import build_net, _dense_net
from mxnet_tpu.serving.router import federation_depth_feed


@pytest.fixture(autouse=True)
def _telemetry_state():
    obs.set_enabled(False)
    obs.reset()
    watchdog.reset()
    fed.reset()
    yield
    obs.set_enabled(False)
    obs.reset()
    watchdog.reset()
    fed.reset()


FEAT = 8
SPEC = {"net": {"dense": {"classes": 4, "feat": FEAT, "bias": 0.5}},
        "shapes": [(FEAT,)], "version": "v1",
        "engine": {"max_batch": 4, "max_wait_ms": 2.0}}
X = np.ones((FEAT,), np.float32)


def _fleet(**kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("autostart_heartbeat", False)
    return ServingFleet(SPEC, name="clf", **kw)


# -- fakes for deterministic router tests ----------------------------------

class _FakeFuture:
    def __init__(self, value=None, error=None, ready=True):
        self.value, self.error, self.ready = value, error, ready
        self.version = "v1"

    def done(self):
        return self.ready

    def result(self, timeout=None):
        if not self.ready:
            if timeout in (None, 0):
                raise TimeoutError("fake future never completes")
            time.sleep(min(timeout, 0.05))
            raise TimeoutError("fake future never completes")
        if self.error is not None:
            raise self.error
        return self.value


class _FakeReplica:
    """Scripted replica: a fixed depth (None = no fresh signal) and a
    scripted submit outcome per call."""

    _uid = iter(range(1000, 9999))

    def __init__(self, index, depth=None, outcomes=None):
        self.uid = next(self._uid)
        self.index = index
        self.state = "live"
        self.depth = depth
        self.submits = 0
        self.outcomes = list(outcomes or [])

    def queue_depth(self):
        return self.depth or 0

    def depth_age(self):
        return 0.0 if self.depth is not None else float("inf")

    def submit(self, x, **kw):
        self.submits += 1
        if self.outcomes:
            out = self.outcomes.pop(0)
            if isinstance(out, Exception):
                raise out
            return out
        return _FakeFuture(value=("ok", self.index))


# -- replica spec / net materialization ------------------------------------

def test_build_net_variants():
    direct = build_net({"dense": {"classes": 4, "feat": FEAT,
                                  "bias": 2.0}})
    assert hasattr(direct, "aot_predict_fn") or callable(direct)
    by_path = build_net("mxnet_tpu.serving.replica:_dense_net")
    assert type(by_path).__name__ == type(_dense_net()).__name__
    by_factory = build_net(lambda: _dense_net(bias=1.0))
    assert by_factory is not None
    with pytest.raises(MXNetError):
        build_net(42)


def test_dense_net_is_deterministic():
    net = _dense_net(classes=4, feat=FEAT, bias=0.5, scale=0.1)
    eng = InferenceEngine(net, [(FEAT,)], max_batch=2, max_wait_ms=0.0,
                          name="det")
    try:
        out = np.asarray(eng.predict(X, timeout=30.0))
        np.testing.assert_allclose(out.ravel(),
                                   np.full(4, 0.1 * FEAT + 0.5),
                                   rtol=1e-5)
    finally:
        eng.close()


# -- router: placement -----------------------------------------------------

def test_router_prefers_least_depth():
    shallow = _FakeReplica(0, depth=1)
    deep = _FakeReplica(1, depth=9)
    router = ReplicaRouter(lambda: [deep, shallow], retries=0, hedge_ms=0)
    fut = router.submit(X)
    assert fut.replica is shallow
    assert shallow.submits == 1 and deep.submits == 0


def test_router_hash_fallback_is_deterministic_per_key():
    replicas = [_FakeReplica(i, depth=None) for i in range(4)]
    router = ReplicaRouter(lambda: list(replicas), retries=0, hedge_ms=0)
    first = {k: router._order(k, set())[0].uid for k in range(16)}
    again = {k: router._order(k, set())[0].uid for k in range(16)}
    assert first == again  # same key -> same placement, every time
    assert len(set(first.values())) > 1  # keys actually spread


def test_router_hash_fallback_survives_replica_loss():
    replicas = [_FakeReplica(i, depth=None) for i in range(4)]
    router = ReplicaRouter(lambda: list(replicas), retries=0, hedge_ms=0)
    before = {k: router._order(k, set())[0].uid for k in range(64)}
    gone = replicas.pop(0)
    after = {k: router._order(k, set())[0].uid for k in range(64)}
    moved = sum(1 for k in before
                if before[k] != after[k] and before[k] != gone.uid)
    # consistent hashing: keys NOT owned by the lost replica stay put
    assert moved == 0


def test_router_depth_feed_wins_over_local():
    a = _FakeReplica(0, depth=0)   # local says idle...
    b = _FakeReplica(1, depth=9)
    feed = {a.uid: 50.0, b.uid: 1.0}  # ...but the cluster sees a pile-up
    router = ReplicaRouter(lambda: [a, b], retries=0, hedge_ms=0,
                           depth_feed=lambda r: feed[r.uid])
    assert router.submit(X).replica is b


# -- router: failover ------------------------------------------------------

def test_failover_at_most_once_per_replica():
    dead1 = _FakeReplica(0, depth=0, outcomes=[ReplicaDead("x")] * 9)
    dead2 = _FakeReplica(1, depth=1, outcomes=[ReplicaDead("x")] * 9)
    alive = _FakeReplica(2, depth=2)
    router = ReplicaRouter(lambda: [dead1, dead2, alive], retries=0,
                           hedge_ms=0)
    fut = router.submit(X)
    assert fut.result(5.0) == ("ok", 2)
    assert dead1.submits == 1 and dead2.submits == 1  # at most once each
    assert fut.tried_count() == 3


def test_replica_lost_only_when_all_candidates_fail():
    dead = [_FakeReplica(i, depth=i, outcomes=[ReplicaDead("x")] * 9)
            for i in range(3)]
    router = ReplicaRouter(lambda: list(dead), retries=0, hedge_ms=0)
    with pytest.raises(ReplicaLost):
        router.submit(X)
    assert all(r.submits == 1 for r in dead)


def test_failover_after_dispatch_death():
    # the replica ACCEPTED the request, then died while it waited
    dies_later = _FakeReplica(
        0, depth=0, outcomes=[_FakeFuture(error=ReplicaDead("host kill"))])
    alive = _FakeReplica(1, depth=5)
    router = ReplicaRouter(lambda: [dies_later, alive], retries=0,
                           hedge_ms=0)
    fut = router.submit(X)
    assert fut.replica is dies_later
    assert fut.result(5.0) == ("ok", 1)  # transparently re-dispatched
    assert fut.replica is alive


def test_death_callback_feeds_health_plane():
    seen = []
    dead = _FakeReplica(0, depth=0, outcomes=[ReplicaDead("x")])
    alive = _FakeReplica(1, depth=1)
    router = ReplicaRouter(lambda: [dead, alive], retries=0, hedge_ms=0,
                           on_death=lambda r, e: seen.append(r))
    router.submit(X)
    assert seen == [dead]


def test_retry_budget_caps_candidates():
    dead = [_FakeReplica(i, depth=i, outcomes=[ReplicaDead("x")] * 9)
            for i in range(4)]
    router = ReplicaRouter(lambda: list(dead), retries=1, hedge_ms=0)
    with pytest.raises(ReplicaLost):
        router.submit(X)
    assert sum(r.submits for r in dead) == 2  # first try + 1 retry


def test_hedged_request_promotes_survivor():
    stall = _FakeReplica(0, depth=0,
                         outcomes=[_FakeFuture(ready=False)])
    fast = _FakeReplica(1, depth=5)
    router = ReplicaRouter(lambda: [stall, fast], retries=0, hedge_ms=5.0)
    fut = router.submit(X)
    assert fut.replica is stall
    assert fut.result(10.0) == ("ok", 1)
    assert fut.was_hedged()


# -- brownout state machine ------------------------------------------------

def test_brownout_latches_and_sheds_in_priority_order():
    fleet = _fleet(replicas=1, brownout_enter=0.8, brownout_exit=0.2,
                   brownout_hold_s=10.0)
    try:
        assert fleet._evaluate_brownout(0.85, now=0.0) == 1
        assert not fleet._admit("bulk")
        assert fleet._admit("interactive") and fleet._admit("critical")
        assert fleet._evaluate_brownout(0.95, now=0.1) == 2
        assert not fleet._admit("bulk") and not fleet._admit("interactive")
        assert fleet._admit("critical")  # critical is NEVER policy-shed
        # a dip below exit does not unlatch without the hold window
        assert fleet._evaluate_brownout(0.1, now=0.2) == 2
    finally:
        fleet.close()


def test_brownout_deescalates_one_level_per_hold_window():
    fleet = _fleet(replicas=1, brownout_enter=0.8, brownout_exit=0.2,
                   brownout_hold_s=1.0)
    try:
        assert fleet._evaluate_brownout(0.96, now=0.0) == 2
        assert fleet._evaluate_brownout(0.1, now=0.5) == 2   # draining...
        assert fleet._evaluate_brownout(0.1, now=1.6) == 1   # one step
        assert fleet._evaluate_brownout(0.1, now=2.0) == 1   # not two
        assert fleet._evaluate_brownout(0.1, now=2.8) == 0   # clear
    finally:
        fleet.close()


def test_brownout_relapse_resets_drain_clock():
    fleet = _fleet(replicas=1, brownout_enter=0.8, brownout_exit=0.2,
                   brownout_hold_s=1.0)
    try:
        assert fleet._evaluate_brownout(0.85, now=0.0) == 1
        assert fleet._evaluate_brownout(0.1, now=0.9) == 1
        assert fleet._evaluate_brownout(0.5, now=1.0) == 1  # relapse
        # the earlier 0.9s of drain does not count toward the hold
        assert fleet._evaluate_brownout(0.1, now=1.5) == 1
        assert fleet._evaluate_brownout(0.1, now=2.6) == 0
    finally:
        fleet.close()


def test_brownout_shed_is_typed_and_counted():
    obs.set_enabled(True)
    fleet = _fleet(replicas=1, brownout_enter=0.8, brownout_exit=0.2,
                   brownout_hold_s=60.0)
    try:
        fleet._evaluate_brownout(0.9, now=0.0)
        with pytest.raises(BrownoutShed) as ei:
            fleet.submit(X, priority="bulk")
        assert isinstance(ei.value, ServerOverloaded)  # 503 mapping holds
        shed = obs.FLEET_SHED_TOTAL.value(model="clf", priority="bulk")
        assert shed == 1
    finally:
        fleet.close()


def test_brownout_threshold_validation():
    with pytest.raises(MXNetError):
        _fleet(replicas=1, brownout_enter=0.3, brownout_exit=0.5)


def test_unknown_priority_rejected():
    fleet = _fleet(replicas=1)
    try:
        with pytest.raises(MXNetError):
            fleet.submit(X, priority="shiny")
    finally:
        fleet.close()


# -- local fleet end to end ------------------------------------------------

def test_local_fleet_serves_and_fails_over():
    fleet = _fleet(replicas=2)
    try:
        out = np.asarray(fleet.predict(X, timeout=30.0))
        np.testing.assert_allclose(out.ravel(),
                                   np.full(4, 0.1 * FEAT + 0.5),
                                   rtol=1e-5)
        fut = fleet.submit(X)
        fleet.kill_replica(fut.replica.index)
        np.testing.assert_allclose(np.asarray(fut.result(30.0)).ravel(),
                                   np.full(4, 0.1 * FEAT + 0.5),
                                   rtol=1e-5)
        assert fleet.n_live() == 1
    finally:
        fleet.close()


def test_all_replicas_dead_is_typed_replica_lost():
    fleet = _fleet(replicas=2)
    try:
        fleet.kill_replica(-1)
        fleet.kill_replica(-1)
        with pytest.raises(ReplicaLost):
            fleet.submit(X).result(10.0)
    finally:
        fleet.close()


def test_scale_to_zero_and_restore_on_demand():
    fleet = _fleet(replicas=2)
    try:
        fleet.replica_set.scale_to_zero()
        assert fleet.n_live() == 0
        assert len(fleet.replica_set.warm()) == 2
        # first submit against a parked fleet restores, not fails
        out = fleet.predict(X, timeout=30.0)
        assert out is not None
        assert fleet.n_live() == 2
    finally:
        fleet.close()


def test_rolling_swap_keeps_version_coherent():
    fleet = _fleet(replicas=2)
    try:
        v2 = dict(SPEC, version="v2",
                  net={"dense": {"classes": 4, "feat": FEAT,
                                 "bias": 9.0}})
        assert fleet.swap(v2) == ["v2", "v2"]
        fut = fleet.submit(X)
        np.testing.assert_allclose(np.asarray(fut.result(30.0)).ravel(),
                                   np.full(4, 0.1 * FEAT + 9.0),
                                   rtol=1e-5)
    finally:
        fleet.close()


def test_rolling_swap_under_traffic_serves_no_stale_version():
    """Requests flowing while the fleet swaps replica by replica: every
    answer is its stamped version's, and whatever is submitted after
    ``swap()`` has returned is the new version's."""
    fleet = _fleet(replicas=2)
    want = {"v1": 0.1 * FEAT + 0.5, "v2": 0.1 * FEAT + 9.0}
    done, during = threading.Event(), []

    def pump():
        while not done.is_set():
            try:
                fut = fleet.submit(X)
                during.append((fut.version,
                               float(np.asarray(fut.result(30.0)).ravel()[0])))
            except (ReplicaLost, ServerOverloaded):
                pass

    t = threading.Thread(target=pump, daemon=True)
    try:
        t.start()
        fleet.swap(dict(SPEC, version="v2", net={
            "dense": {"classes": 4, "feat": FEAT, "bias": 9.0}}))
        after = []
        for _ in range(10):
            fut = fleet.submit(X)
            fut.result(30.0)
            after.append(fut.version)
        done.set()
        t.join(timeout=30.0)
        assert not t.is_alive() and during
        assert after == ["v2"] * 10
        for version, value in during:  # None: resolved before the stamp
            answers = [want[version]] if version else want.values()
            assert min(abs(value - w) for w in answers) < 1e-4, \
                (version, value)
    finally:
        done.set()
        fleet.close()


def test_burst_sheds_by_priority_class_from_the_real_queue():
    """A burst of all three classes at a twelve-deep queue whose engine
    is held in its first batch, so the depth is the burst's and not the
    host's scheduling: the brownout latches from the queue's own depth,
    bulk is shed first and most, critical is never shed by policy (a
    full queue's typed reject is backpressure, not policy), and every
    admitted request is served once the engine runs again."""
    spec = dict(SPEC, engine={"max_batch": 4, "max_wait_ms": 40.0,
                              "queue_cap": 12})
    fleet = ServingFleet(spec, name="burst", replicas=1,
                         autostart_heartbeat=False, brownout_enter=0.5,
                         brownout_exit=0.2, brownout_hold_s=30.0)
    shed = {"bulk": 0, "interactive": 0, "critical": 0}
    full, gate, held = 0, threading.Event(), threading.Event()

    def hold(bucket, reqs):
        held.set()
        gate.wait(60.0)
        return run(bucket, reqs)

    try:
        fleet.predict(X, timeout=60.0)
        batcher = fleet.replica_set.replicas()[0]._repo.engine(
            "burst")._batcher
        run, batcher._dispatch = batcher._dispatch, hold
        futs = [fleet.submit(X, priority="critical") for _ in range(4)]
        assert held.wait(30.0)  # the engine is in its batch: the queue grows
        for prio in ["bulk", "interactive", "critical"] * 40:
            try:
                futs.append(fleet.submit(X, priority=prio))
            except BrownoutShed:
                shed[prio] += 1
            except ServerOverloaded:
                full += 1
        assert fleet.queue_fraction() == 1.0 and full > 0
        assert fleet.brownout_level() == 2
        assert len(futs) + full + sum(shed.values()) == 124
        assert shed["critical"] == 0 and shed["bulk"] > 0, shed
        assert shed["bulk"] >= shed["interactive"] > 0, shed
        gate.set()
        for f in futs:
            assert f.result(timeout=60.0) is not None  # served, none hung
    finally:
        gate.set()
        fleet.close()


def test_heartbeat_walks_suspect_then_dead():
    fleet = _fleet(replicas=2, suspect_misses=2)
    rs = fleet.replica_set
    try:
        victim = rs.replicas()[0]
        victim._dead = True  # ping now raises, but state is still live
        rs.heartbeat_once()
        assert victim.state == "suspect"
        rs.heartbeat_once()
        assert victim.state == "dead"
        assert fleet.n_live() == 1
    finally:
        fleet.close()


# -- autoscaler ------------------------------------------------------------

def test_autoscaler_replaces_dead_replica():
    fleet = _fleet(replicas=2)
    scaler = SLOAutoscaler(fleet, min_replicas=2, max_replicas=4,
                           cooldown_s=3600.0, use_watchdog=False)
    try:
        fleet.kill_replica(0)
        assert fleet.n_live() == 1
        scaler.tick()
        assert scaler.replaced == 1
        assert fleet.n_live() == 2
        assert fleet.last_recovery_s is not None
        assert fleet.last_recovery_s >= 0.0
        # the replacement serves
        assert fleet.predict(X, timeout=30.0) is not None
    finally:
        scaler.stop()
        fleet.close()


def test_autoscaler_grows_on_slo_breach():
    fleet = _fleet(replicas=2)
    scaler = SLOAutoscaler(fleet, min_replicas=1, max_replicas=3,
                           slo_p99_ms=50.0, cooldown_s=0.0,
                           use_watchdog=False)
    try:
        for _ in range(20):
            fleet.router.record_latency(1.0)  # 1000ms >> 50ms SLO
        signals = scaler.tick()
        assert any(s["kind"] == "resize" and s["reason"] == "slo"
                   for s in signals)
        assert fleet.n_live() == 3
    finally:
        scaler.stop()
        fleet.close()


def test_autoscaler_growth_respects_cooldown_and_max():
    fleet = _fleet(replicas=2)
    scaler = SLOAutoscaler(fleet, min_replicas=1, max_replicas=3,
                           slo_p99_ms=50.0, cooldown_s=3600.0,
                           use_watchdog=False)
    try:
        for _ in range(20):
            fleet.router.record_latency(1.0)
        # the clock of a host booted 100 s ago: younger than the cooldown
        # (an autoscaler that has changed nothing yet is not cooling down)
        scaler.tick(now=100.0)
        assert fleet.n_live() == 3
        scaler.tick(now=101.0)  # still breaching: cooldown + max cap hold
        assert fleet.n_live() == 3
    finally:
        scaler.stop()
        fleet.close()


def test_autoscaler_shrinks_on_sustained_headroom():
    fleet = _fleet(replicas=3)
    scaler = SLOAutoscaler(fleet, min_replicas=1, max_replicas=4,
                           slo_p99_ms=1000.0, cooldown_s=0.0,
                           use_watchdog=False)
    try:
        for _ in range(20):
            fleet.router.record_latency(0.001)  # way under SLO
        scaler.tick()
        assert fleet.n_live() == 2
    finally:
        scaler.stop()
        fleet.close()


def test_autoscaler_scale_to_zero_on_idle():
    fleet = _fleet(replicas=2)
    scaler = SLOAutoscaler(fleet, min_replicas=0, max_replicas=4,
                           cooldown_s=0.0, idle_to_zero_s=0.01,
                           use_watchdog=False)
    try:
        fleet._last_submit_mono = time.monotonic() - 60.0
        scaler.tick()
        assert fleet.n_live() == 0
        assert len(fleet.replica_set.warm()) == 2
        # traffic returns: restore on demand, then the scaler sees live
        assert fleet.predict(X, timeout=30.0) is not None
        assert fleet.n_live() >= 1
    finally:
        scaler.stop()
        fleet.close()


def test_autoscaler_signals_ride_the_membership_bus():
    fleet = _fleet(replicas=2)
    monitor = MembershipMonitor(straggler_factor=0.0, notice_path="")
    scaler = SLOAutoscaler(fleet, min_replicas=2, max_replicas=4,
                           cooldown_s=3600.0, monitor=monitor,
                           use_watchdog=False)
    try:
        fleet.kill_replica(0)
        scaler._ingest_deaths()
        pend = monitor.pending()
        assert any(s["kind"] == "dead_peer" for s in pend)
        scaler.tick()
        assert fleet.n_live() == 2
    finally:
        scaler.stop()
        fleet.close()


def test_watchdog_saturation_anomaly_requests_growth():
    fleet = _fleet(replicas=2)
    scaler = SLOAutoscaler(fleet, min_replicas=1, max_replicas=4,
                           cooldown_s=0.0, use_watchdog=True)
    try:
        scaler._on_anomaly("queue_saturation", {"depth": 99})
        pend = scaler.monitor.pending()
        assert any(s["kind"] == "resize"
                   and s["reason"] == "queue_saturation" for s in pend)
        scaler.tick()
        assert fleet.n_live() == 3
    finally:
        scaler.stop()
        fleet.close()


def test_watchdog_listener_registry():
    calls = []

    def listener(kind, details):
        calls.append((kind, details))

    watchdog.register_listener(listener)
    watchdog.register_listener(listener)  # idempotent
    watchdog._fire("queue_saturation", depth=7)
    assert calls == [("queue_saturation", {"depth": 7})]
    watchdog.unregister_listener(listener)
    watchdog._fire("queue_saturation", depth=8)
    assert len(calls) == 1


def test_broken_listener_never_breaks_detection():
    def bad(kind, details):
        raise RuntimeError("actuator crashed")

    watchdog.register_listener(bad)
    watchdog._fire("nan_loss", step=3)  # must not raise
    watchdog.unregister_listener(bad)


# -- satellite: ServeFuture.cancel -----------------------------------------

def test_cancel_queued_request_is_typed_and_never_dispatched():
    net = _dense_net(feat=FEAT)
    eng = InferenceEngine(net, [(FEAT,)], max_batch=4, max_wait_ms=500.0,
                          name="cx")
    try:
        batches_before = eng.stats()["batches"]
        fut = eng.submit(X)
        assert fut.cancel() is True
        assert fut.cancelled() is True
        with pytest.raises(RequestCancelled):
            fut.result(5.0)
        # a second cancel / a cancel race is a no-op
        assert fut.cancel() is False
        # the cancelled entry is skipped at drain: submit another and
        # confirm the engine only ever dispatched the live one
        out = eng.predict(X, timeout=30.0)
        assert out is not None
        assert eng.stats()["batches"] == batches_before + 1
    finally:
        eng.close()


def test_cancel_after_completion_returns_false():
    net = _dense_net(feat=FEAT)
    eng = InferenceEngine(net, [(FEAT,)], max_batch=1, max_wait_ms=0.0,
                          name="cy")
    try:
        fut = eng.submit(X)
        fut.result(30.0)
        assert fut.cancel() is False
        assert fut.cancelled() is False
    finally:
        eng.close()


def test_cancel_frees_queue_slot():
    net = _dense_net(feat=FEAT)
    eng = InferenceEngine(net, [(FEAT,)], max_batch=1, max_wait_ms=200.0,
                          queue_cap=64, name="cz")
    try:
        futs = [eng.submit(X) for _ in range(8)]
        for f in futs[2:]:
            assert f.cancel() is True
        # the two uncancelled requests complete normally
        for f in futs[:2]:
            assert f.result(30.0) is not None
    finally:
        eng.close()


# -- satellite: decorrelated-jitter backoff --------------------------------

def test_backoff_delays_decorrelated_jitter_bounds():
    rng = random.Random(42)
    delays = backoff_delays(8, 0.5, max_delay=10.0, rng=rng)
    assert len(delays) == 7
    prev = 0.5
    for d in delays:
        assert 0.5 <= d <= min(10.0, max(0.5, prev * 3.0)) + 1e-9
        prev = d
    # two processes (seeds) must NOT produce the same schedule
    other = backoff_delays(8, 0.5, max_delay=10.0,
                           rng=random.Random(43))
    assert delays != other


def test_backoff_delays_linear_when_jitter_off():
    assert backoff_delays(4, 0.5, jitter=False) == [0.5, 1.0, 1.5]


def test_backoff_delays_respect_max_delay():
    delays = backoff_delays(20, 1.0, max_delay=3.0,
                            rng=random.Random(7))
    assert all(d <= 3.0 for d in delays)


def test_retry_with_backoff_sleeps_jittered_delays():
    sleeps = []
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ValueError("transient")
        return "done"

    out = retry_with_backoff(flaky, attempts=3, base_delay=0.5,
                             rng=random.Random(1),
                             sleep=sleeps.append)
    assert out == "done"
    assert len(sleeps) == 2
    assert all(s >= 0.5 for s in sleeps)


def test_retry_with_backoff_no_retry_is_immediate():
    from mxnet_tpu.kvstore.dist import CollectiveTimeoutError

    calls = []

    def fatal():
        calls.append(1)
        raise CollectiveTimeoutError("partition, not transient")

    with pytest.raises(CollectiveTimeoutError):
        retry_with_backoff(fatal, attempts=5, base_delay=0.01,
                           no_retry=(CollectiveTimeoutError,),
                           sleep=lambda s: None)
    assert len(calls) == 1


# -- satellite: federation cluster_values consumer -------------------------

def _synth_snap(rank, depth, labels=(("model", "clf"),)):
    from mxnet_tpu.observability.federation import _encode_key

    return {"rank": rank, "wall": time.time(), "step_epoch": 1,
            "metrics": {"mxtpu_serving_queue_depth": {
                "kind": "gauge", "help": "",
                "values": {_encode_key(tuple(labels)): float(depth)}}}}


def test_cluster_values_reads_per_rank_depths():
    fed.ingest(_synth_snap(0, 3.0))
    fed.ingest(_synth_snap(1, 11.0))
    vals = fed.cluster_values("mxtpu_serving_queue_depth")
    assert vals == {0: 3.0, 1: 11.0}


def test_cluster_values_match_filter_and_sum():
    from mxnet_tpu.observability.federation import _encode_key

    snap = {"rank": 2, "wall": time.time(), "step_epoch": 1,
            "metrics": {"mxtpu_serving_queue_depth": {
                "kind": "gauge", "help": "",
                "values": {
                    _encode_key((("model", "clf"),)): 4.0,
                    _encode_key((("model", "other"),)): 100.0}}}}
    fed.ingest(snap)
    assert fed.cluster_values("mxtpu_serving_queue_depth",
                              match={"model": "clf"}) == {2: 4.0}
    # no filter: labelsets sum per rank
    assert fed.cluster_values(
        "mxtpu_serving_queue_depth")[2] == pytest.approx(104.0)


def test_cluster_values_excludes_stale_ranks():
    fed.ingest(_synth_snap(0, 3.0), recv_mono=time.monotonic() - 9999.0)
    assert fed.cluster_values("mxtpu_serving_queue_depth") == {}
    assert 0 in fed.cluster_values("mxtpu_serving_queue_depth",
                                   fresh_only=False)


def test_federation_depth_feed_routes_to_cluster_view():
    fed.ingest(_synth_snap(0, 50.0, labels=()))
    fed.ingest(_synth_snap(1, 1.0, labels=()))
    a = _FakeReplica(0, depth=None)
    b = _FakeReplica(1, depth=None)
    feed = federation_depth_feed(lambda r: r.index)
    router = ReplicaRouter(lambda: [a, b], retries=0, hedge_ms=0,
                           depth_feed=feed)
    assert router.submit(X).replica is b


def test_cold_federation_feed_falls_back_to_hash():
    a = _FakeReplica(0, depth=None)
    b = _FakeReplica(1, depth=None)
    feed = federation_depth_feed(lambda r: r.index)  # nothing ingested
    router = ReplicaRouter(lambda: [a, b], retries=0, hedge_ms=0,
                           depth_feed=feed)
    first = router._order("stable-key", set())
    again = router._order("stable-key", set())
    assert [r.uid for r in first] == [r.uid for r in again]


# -- telemetry report: Fleet section ---------------------------------------

def test_report_fleet_section():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        import telemetry_report as tr
    finally:
        sys.path.pop(0)

    events = [
        {"name": "fleet.autoscale", "cat": "serving",
         "args": {"model": "clf", "action": "replace", "n": 2}},
        {"name": "fleet.autoscale", "cat": "serving",
         "args": {"model": "clf", "action": "replace", "n": 2}},
        {"name": "fleet.autoscale", "cat": "serving",
         "args": {"model": "clf", "action": "grow", "n": 3}},
        {"name": "fleet.brownout", "cat": "serving",
         "args": {"model": "clf", "level": 1, "prev": 0}},
    ]
    out = tr.render_fleet(events)
    assert "Fleet:" in out
    assert "autoscale [clf] replace: 2" in out
    assert "autoscale [clf] grow: 1" in out
    assert "brownout [clf] level 0 -> 1" in out
    # crash-proofing contract: malformed args render, never raise
    assert "Fleet:" in tr.render_fleet(
        [{"name": "fleet.brownout", "args": None},
         {"name": "fleet.autoscale", "args": "garbage"}])
    assert tr.render_fleet([{"name": "trainer.step"}]) == ""
