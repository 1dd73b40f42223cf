"""Fleet recovery certification, process plane: real child-process
replicas (one OS process per 'host'), SIGKILL host death mid-traffic,
typed in-flight failover (never a hang), autoscaler replacement, chaos
fault classes (``kill_replica@fleet`` / ``stall@replica<k>``) — plus
the PR-13 swap-race satellite: ``EngineClosed`` from a SWAPPING engine
retries onto the new version while ``EngineClosed`` from a DEAD
replica diverges into router failover.
"""

import time

import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serving import (
    EngineClosed,
    LocalReplica,
    ModelRepository,
    ProcessReplica,
    ReplicaDead,
    ServingFleet,
    SLOAutoscaler,
)


@pytest.fixture(autouse=True)
def _state():
    obs.set_enabled(False)
    obs.reset()
    chaos.reset()
    yield
    obs.set_enabled(False)
    obs.reset()
    chaos.reset()


FEAT = 8
SPEC = {"net": {"dense": {"classes": 4, "feat": FEAT, "bias": 0.5}},
        "shapes": [(FEAT,)], "version": "v1",
        "engine": {"max_batch": 4, "max_wait_ms": 2.0}}
SPEC_V2 = dict(SPEC, version="v2",
               net={"dense": {"classes": 4, "feat": FEAT, "bias": 9.0}})
X = np.ones((FEAT,), np.float32)
EXPECT_V1 = np.full(4, 0.1 * FEAT + 0.5)
EXPECT_V2 = np.full(4, 0.1 * FEAT + 9.0)


# -- satellite: swap-race vs replica-death divergence ----------------------

def test_repo_submit_retries_engine_closed_from_swap():
    """EngineClosed raced by a version flip is absorbed: the retry loop
    re-reads the live pointer and the request lands on the NEW
    version — continuous traffic across a swap never fails spuriously."""
    from mxnet_tpu.serving.replica import build_net

    repo = ModelRepository(keep=1)
    try:
        repo.load("m", lambda: build_net(SPEC["net"]), SPEC["shapes"],
                  version="v1", **SPEC["engine"])
        old = repo.engine("m")
        repo.load("m", lambda: build_net(SPEC_V2["net"]),
                  SPEC_V2["shapes"], version="v2", **SPEC["engine"])
        # the OLD engine is paused (standby): submitting through the
        # repository must NOT surface its EngineClosed — the pointer
        # re-read routes to v2
        with pytest.raises(EngineClosed):
            old.submit(X)  # direct submit: typed refusal, proves the race
        out = np.asarray(repo.predict("m", X, timeout=30.0))
        np.testing.assert_allclose(out.ravel(), EXPECT_V2, rtol=1e-5)
        assert repo.live_version("m") == "v2"
    finally:
        repo.close()


def test_dead_replica_engine_closed_diverges_to_replica_dead():
    """The SAME wire error (EngineClosed) means two different things:
    from a swapping engine it is retried in place; from a DEAD replica
    it must surface as ReplicaDead so the router fails over instead of
    spinning the swap-retry loop against a corpse."""
    replica = LocalReplica(0, SPEC, name="m")
    try:
        replica.kill()
        with pytest.raises(ReplicaDead):
            replica.submit(X)
    finally:
        replica.close()


def test_swap_race_retry_with_concurrent_replica_loss_in_fleet():
    """Both paths at once: replica 0 dies while replica 1 swaps. A
    request must fail over off the corpse AND land on a coherent
    version of the survivor — never a stale answer, never a hang."""
    fleet = ServingFleet(SPEC, name="m", replicas=2,
                         autostart_heartbeat=False)
    try:
        fleet.kill_replica(0)
        survivor = fleet.replica_set.live()[0]
        survivor.swap(SPEC_V2)
        fut = fleet.submit(X)
        out = np.asarray(fut.result(30.0))
        np.testing.assert_allclose(out.ravel(), EXPECT_V2, rtol=1e-5)
    finally:
        fleet.close()


# -- local host-kill: queued work fails typed, never hangs -----------------

def test_killed_replica_fails_queued_requests_typed():
    spec = dict(SPEC, engine={"max_batch": 2, "max_wait_ms": 300.0})
    replica = LocalReplica(0, spec, name="m")
    try:
        futs = [replica.submit(X) for _ in range(6)]
        replica.kill()
        t0 = time.monotonic()
        outcomes = []
        for f in futs:
            try:
                f.result(5.0)
                outcomes.append("ok")
            except ReplicaDead:
                outcomes.append("dead")
            except EngineClosed:
                outcomes.append("dead")
        # every future resolved FAST and TYPED — zero hangs
        assert time.monotonic() - t0 < 5.0
        assert "dead" in outcomes
    finally:
        replica.close()


# -- chaos fault classes ---------------------------------------------------

def test_chaos_kill_replica_spec_fires_once_mid_traffic():
    chaos.configure("kill_replica@fleet:5:0")
    fleet = ServingFleet(SPEC, name="m", replicas=2,
                         autostart_heartbeat=False)
    try:
        for i in range(12):
            out = fleet.predict(X, timeout=30.0)  # traffic never breaks
            assert out is not None
        fired = chaos.fired()
        assert ("kill_replica", "fleet", 5) in fired
        assert len([f for f in fired if f[0] == "kill_replica"]) == 1
        assert fleet.n_live() == 1  # the victim is dead, survivor serves
    finally:
        fleet.close()
        chaos.reset()


def test_chaos_stall_replica_site_injects_latency():
    chaos.configure("stall@replica0:2:0.2")
    replica = LocalReplica(0, SPEC, name="m")
    try:
        replica.submit(X).result(30.0)  # step 1
        t0 = time.monotonic()
        replica.submit(X).result(30.0)  # step 2: stalled 0.2s
        assert time.monotonic() - t0 >= 0.18
        assert ("stall", "replica0", 2) in chaos.fired()
    finally:
        replica.close()
        chaos.reset()


# -- process replicas (real host-kill) -------------------------------------

@pytest.mark.slow
def test_process_replica_roundtrip_and_swap():
    r = ProcessReplica(0, SPEC, name="m").wait_ready(timeout=180.0)
    try:
        out = np.asarray(r.submit(X).result(60.0))
        np.testing.assert_allclose(out.ravel(), EXPECT_V1, rtol=1e-5)
        info = r.ping(timeout=10.0)
        assert info["version"] == "v1"
        assert r.swap(SPEC_V2) == "v2"
        out2 = np.asarray(r.submit(X).result(60.0))
        np.testing.assert_allclose(out2.ravel(), EXPECT_V2, rtol=1e-5)
    finally:
        r.close()


@pytest.mark.slow
def test_process_replica_sigkill_fails_pending_typed():
    r = ProcessReplica(0, SPEC, name="m").wait_ready(timeout=180.0)
    futs = [r.submit(X) for _ in range(4)]
    r.kill()
    t0 = time.monotonic()
    for f in futs:
        try:
            f.result(10.0)
        except (ReplicaDead, Exception):
            pass
    assert time.monotonic() - t0 < 10.0  # resolved, not hung
    assert r.state == "dead"
    with pytest.raises(ReplicaDead):
        r.submit(X)


def test_process_fleet_host_kill_recovery_end_to_end():
    """The tentpole certification in miniature: SIGKILL one of two
    host processes mid-traffic; every in-flight request is retried or
    typed-failed; the autoscaler replaces the host; the fleet serves
    the same answers afterward. Not marked slow: it is tier-1's one
    holder of the real OS-process kill, replace and serve (three child
    processes of one subsystem, about 12 s)."""
    fleet = ServingFleet(SPEC, name="m", replicas=2, process=True,
                         heartbeat_s=0.3, suspect_misses=3)
    scaler = SLOAutoscaler(fleet, min_replicas=2, max_replicas=3,
                           cooldown_s=3600.0, use_watchdog=False)
    try:
        fleet.predict(X, timeout=60.0)
        futs = [fleet.submit(X, key=i) for i in range(8)]
        fleet.kill_replica(0)
        ok = 0
        for f in futs:
            out = np.asarray(f.result(60.0))  # typed or ok — never hung
            np.testing.assert_allclose(out.ravel(), EXPECT_V1, rtol=1e-5)
            ok += 1
        assert ok == 8
        for _ in range(20):
            scaler.tick()
            if scaler.replaced >= 1 and fleet.n_live() >= 2:
                break
            time.sleep(0.2)
        assert scaler.replaced >= 1
        assert fleet.n_live() == 2
        assert fleet.last_recovery_s is not None
        out = np.asarray(fleet.predict(X, timeout=60.0))
        np.testing.assert_allclose(out.ravel(), EXPECT_V1, rtol=1e-5)
    finally:
        scaler.stop()
        fleet.close()


@pytest.mark.slow
def test_process_replica_warm_pause_resume():
    r = ProcessReplica(0, SPEC, name="m").wait_ready(timeout=180.0)
    try:
        r.submit(X).result(60.0)
        r.pause()
        assert r.state == "warm"
        r.resume(timeout=180.0)  # respawn through the compile cache
        assert r.state == "live"
        out = np.asarray(r.submit(X).result(60.0))
        np.testing.assert_allclose(out.ravel(), EXPECT_V1, rtol=1e-5)
    finally:
        r.close()
