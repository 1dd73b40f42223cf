"""Self-healing serving fleet: replica set + router + degraded mode.

:class:`ReplicaSet` owns N :class:`~.replica.LocalReplica` /
:class:`~.replica.ProcessReplica` instances behind health checking —
a heartbeat loop pings every replica; consecutive misses walk a
replica live -> suspect -> dead, and request-level failures
(:class:`~.errors.ReplicaDead` out of a dispatch) short-circuit that
walk, because a broken pipe IS the health check. Death fails the
replica's queued requests typed (never hung), burns its uid for
routing, and hands the corpse to the autoscaler for replacement.

:class:`ServingFleet` is the client object: ``submit(x, priority=...)``
routes through a :class:`~.router.ReplicaRouter` and layers the
degraded-mode overload policy on top — a LATCHED brownout state
machine driven by aggregate queue fraction:

  level 0 (clear)     all classes admitted
  level 1 (brownout)  ``bulk`` shed                     [frac >= enter]
  level 2 (blackout)  ``bulk`` + ``interactive`` shed   [frac >= enter2]
  ``critical`` is NEVER policy-shed (only hard queue-full rejects it)

Escalation is immediate; de-escalation requires the fraction to stay
below the exit threshold for a hold window (one level per window), so
a saturated fleet sheds instantly but a flapping signal cannot
oscillate admission. Transitions emit ``mxtpu_fleet_brownout`` + a
trace instant; every shed increments ``mxtpu_fleet_shed_total`` by
priority class and raises typed :class:`~.errors.BrownoutShed`.

Scale-to-zero parks every replica in the warm pool (weights + compile
cache resident); the first submit against a zero-live fleet restores
synchronously rather than failing — cold start is a latency cost, not
an error.
"""

from __future__ import annotations

import threading
import time

from .. import observability as _obs
from ..base import MXNetError, getenv
from ..resilience import chaos as _chaos
from .engine import serve_queue_cap
from .errors import BrownoutShed, ReplicaDead, ServingError
from .replica import LocalReplica, ProcessReplica, normalize_spec
from .router import ReplicaRouter

#: admission-priority classes, strongest-protection first; shedding
#: strictly walks this list from the RIGHT (bulk first, critical never)
PRIORITIES = ("critical", "interactive", "bulk")


def fleet_replicas() -> int:
    """Initial replica count, ``MXTPU_FLEET_REPLICAS``."""
    return max(1, int(getenv("MXTPU_FLEET_REPLICAS", 2, dtype=int)))


def fleet_min_replicas() -> int:
    """Autoscaler floor, ``MXTPU_FLEET_MIN_REPLICAS`` (0 permits
    scale-to-zero)."""
    return max(0, int(getenv("MXTPU_FLEET_MIN_REPLICAS", 1, dtype=int)))


def fleet_max_replicas() -> int:
    """Autoscaler ceiling, ``MXTPU_FLEET_MAX_REPLICAS``."""
    return max(1, int(getenv("MXTPU_FLEET_MAX_REPLICAS", 8, dtype=int)))


def fleet_heartbeat_s() -> float:
    """Heartbeat period, ``MXTPU_FLEET_HEARTBEAT_S``."""
    return max(0.05, float(getenv("MXTPU_FLEET_HEARTBEAT_S", 0.5,
                                  dtype=float)))


def fleet_suspect_misses() -> int:
    """Consecutive heartbeat misses before a suspect replica is
    declared dead, ``MXTPU_FLEET_SUSPECT_MISSES``."""
    return max(1, int(getenv("MXTPU_FLEET_SUSPECT_MISSES", 3, dtype=int)))


def fleet_brownout_enter() -> float:
    """Aggregate queue fraction that LATCHES brownout level 1,
    ``MXTPU_FLEET_BROWNOUT_ENTER``."""
    return float(getenv("MXTPU_FLEET_BROWNOUT_ENTER", 0.85, dtype=float))


def fleet_brownout_exit() -> float:
    """Queue fraction below which de-escalation becomes ELIGIBLE,
    ``MXTPU_FLEET_BROWNOUT_EXIT`` (hysteresis floor)."""
    return float(getenv("MXTPU_FLEET_BROWNOUT_EXIT", 0.30, dtype=float))


def fleet_brownout_hold_s() -> float:
    """How long the fraction must stay below the exit threshold before
    stepping DOWN one brownout level, ``MXTPU_FLEET_BROWNOUT_HOLD_S``."""
    return max(0.0, float(getenv("MXTPU_FLEET_BROWNOUT_HOLD_S", 1.0,
                                 dtype=float)))


class ReplicaSet:
    """N replicas of one model spec + the health plane over them."""

    #: machine-checked lock protocol (mxtpu-lint thread-guard)
    _GUARDED_BY = {"_replicas": "_lock", "_next_index": "_lock"}

    def __init__(self, spec, *, name="model", replicas=None, process=False,
                 heartbeat_s=None, suspect_misses=None, on_death=None,
                 autostart=True):
        self.name = str(name)
        self.spec = normalize_spec(spec)
        self.process = bool(process)
        self._heartbeat_s = fleet_heartbeat_s() if heartbeat_s is None \
            else float(heartbeat_s)
        self._suspect_misses = fleet_suspect_misses() \
            if suspect_misses is None else int(suspect_misses)
        self._on_death = on_death
        self._lock = threading.RLock()
        self._replicas = []
        self._next_index = 0
        self._closed = False
        self._hb_thread = None
        n = fleet_replicas() if replicas is None else int(replicas)
        self._spawn_initial(n)
        if autostart:
            self.start_heartbeat()

    # -- spawning ----------------------------------------------------------
    def _new_replica(self, spec=None):
        with self._lock:
            index = self._next_index
            self._next_index += 1
        cls = ProcessReplica if self.process else LocalReplica
        return cls(index, spec or self.spec, name=self.name)

    def _spawn_initial(self, n):
        fresh = [self._new_replica() for _ in range(max(1, n))]
        for r in fresh:
            r.wait_ready()  # process replicas compile concurrently
        with self._lock:
            self._replicas.extend(fresh)
        self.census()

    # -- views -------------------------------------------------------------
    def replicas(self):
        with self._lock:
            return list(self._replicas)

    def live(self):
        """Routable replicas (live + suspect: a suspect still serves
        until it is PROVEN dead — requests on it fail over typed)."""
        with self._lock:
            return [r for r in self._replicas
                    if r.state in ("live", "suspect")]

    def warm(self):
        with self._lock:
            return [r for r in self._replicas if r.state == "warm"]

    def n_live(self) -> int:
        return len(self.live())

    def queue_cap(self) -> int:
        return int((self.spec.get("engine") or {}).get("queue_cap")
                   or serve_queue_cap())

    def census(self):
        """Publish per-state replica counts (``mxtpu_fleet_replicas``)."""
        counts = {}
        for r in self.replicas():
            counts[r.state] = counts.get(r.state, 0) + 1
        if _obs.ENABLED:
            _obs.record_fleet_states(self.name, counts)
        return counts

    # -- health plane ------------------------------------------------------
    def start_heartbeat(self):
        with self._lock:
            if self._hb_thread is not None or self._closed:
                return
            self._hb_thread = threading.Thread(
                target=self._hb_loop, daemon=True,
                name=f"mxtpu-fleet-{self.name}-heartbeat")
            self._hb_thread.start()

    def _hb_loop(self):  # mxtpu-lint: hot-path
        while True:
            with self._lock:
                if self._closed:
                    return
            self.heartbeat_once()
            time.sleep(self._heartbeat_s)

    def heartbeat_once(self):
        """One health sweep (the loop body, callable deterministically
        from tests): ping live/suspect replicas, walk the miss ladder."""
        for r in self.replicas():
            if r.state not in ("live", "suspect"):
                continue
            try:
                # generous timeout: a busy-but-alive replica must not be
                # declared dead (EOF/request-level detection catches real
                # deaths much faster than the miss ladder anyway)
                r.ping(timeout=max(1.0, 2.0 * self._heartbeat_s))
            except Exception:
                r.misses += 1
                if r.misses >= self._suspect_misses:
                    self.mark_dead(r, reason="heartbeat")
                elif r.state == "live":
                    r.state = "suspect"
            else:
                r.misses = 0
                if r.state == "suspect":
                    r.state = "live"
        self.census()

    def mark_dead(self, replica, reason="request"):
        """Declare a replica dead: fail its queued work typed, burn it
        for routing, notify the death listener (autoscaler)."""
        with self._lock:
            if replica.state == "dead" or replica not in self._replicas:
                dead_now = False
            else:
                replica.state = "dead"
                dead_now = True
        if not dead_now:
            return
        if replica.death_mono is None:
            replica.death_mono = time.monotonic()
        try:
            replica.kill()  # queued requests fail ReplicaDead, never hang
        except Exception:
            pass
        self.census()
        if self._on_death is not None:
            try:
                self._on_death(replica, reason)
            except Exception:
                pass

    # -- membership actuations --------------------------------------------
    def grow(self, n=1):
        """Add ``n`` fresh replicas (warm pool first, then spawn)."""
        added = []
        for _ in range(int(n)):
            warm = self.warm()
            if warm:
                r = warm[0]
                r.resume()
                added.append(r)
                continue
            r = self._new_replica()
            r.wait_ready()
            with self._lock:
                self._replicas.append(r)
            added.append(r)
        self.census()
        return added

    def shrink(self, n=1):
        """Retire ``n`` live replicas gracefully (drain, then close)."""
        victims = self.live()[-int(n):] if int(n) > 0 else []
        for r in victims:
            with self._lock:
                if r in self._replicas:
                    self._replicas.remove(r)
            r.close()
        self.census()
        return victims

    def replace(self, replica):
        """Swap a dead replica for a fresh one at a NEW uid (the dead
        uid stays burned in every in-flight request's tried set)."""
        fresh = self._new_replica(replica.spec)
        fresh.wait_ready()
        with self._lock:
            try:
                at = self._replicas.index(replica)
                self._replicas[at] = fresh
            except ValueError:
                self._replicas.append(fresh)
        try:
            replica.close()
        except Exception:
            pass
        self.census()
        return fresh

    def reap_dead(self):
        """Drop dead replicas from the set (post-replacement hygiene)."""
        with self._lock:
            dead = [r for r in self._replicas if r.state == "dead"]
            self._replicas = [r for r in self._replicas
                              if r.state != "dead"]
        for r in dead:
            try:
                r.close()
            except Exception:
                pass
        if dead:
            self.census()
        return dead

    def scale_to(self, target):
        target = max(0, int(target))
        n = self.n_live()
        if target > n:
            self.grow(target - n)
        elif target < n:
            if target == 0:
                self.scale_to_zero()
            else:
                self.shrink(n - target)
        return self.n_live()

    def scale_to_zero(self):
        """Park EVERY live replica in the warm pool: drained, weights
        and compile cache resident, zero serving capacity."""
        for r in self.live():
            try:
                r.pause()
            except Exception:
                pass
        self.census()

    def restore(self, n=None):
        """Warm-pool restore: resume parked replicas (no recompile —
        executables were kept / the compile cache is hot)."""
        warm = self.warm()
        n = len(warm) if n is None else min(int(n), len(warm))
        for r in warm[:n]:
            r.resume()
        self.census()
        return n

    # -- staged swap across the fleet --------------------------------------
    def swap(self, spec):
        """Rolling staged swap: each replica stages+verifies+flips the
        new version IN PLACE (repository semantics), one at a time, so
        capacity never drops by more than one replica and every request
        is answered by exactly one coherent version."""
        spec = normalize_spec(spec)
        versions = []
        for r in self.live():
            versions.append(r.swap(spec))
        self.spec = spec
        return versions

    def close(self):
        with self._lock:
            if self._closed:
                return
            self._closed = True
            replicas, self._replicas = list(self._replicas), []
        for r in replicas:
            try:
                r.close()
            except Exception:
                pass


class ServingFleet:
    """The client-facing fleet: routed dispatch + overload policy.

    >>> fleet = ServingFleet({"net": {"dense": {}}, "shapes": [(8,)]},
    ...                      replicas=3)
    >>> fut = fleet.submit(x, priority="interactive")
    >>> y = fut.result(timeout=5.0)
    """

    def __init__(self, spec, *, name="model", replicas=None, process=False,
                 hedge_ms=None, retries=None, depth_feed=None,
                 heartbeat_s=None, suspect_misses=None,
                 brownout_enter=None, brownout_exit=None,
                 brownout_hold_s=None, autostart_heartbeat=True):
        self.name = str(name)
        self._enter = fleet_brownout_enter() if brownout_enter is None \
            else float(brownout_enter)
        self._exit = fleet_brownout_exit() if brownout_exit is None \
            else float(brownout_exit)
        self._enter2 = min(0.98, self._enter + 0.10)
        self._hold_s = fleet_brownout_hold_s() if brownout_hold_s is None \
            else float(brownout_hold_s)
        if not (self._exit < self._enter):
            raise MXNetError(
                f"brownout exit threshold ({self._exit}) must sit below "
                f"enter ({self._enter}) — hysteresis needs a gap")
        self._brownout = 0        # latched level 0|1|2
        self._drain_since = None  # when frac first dipped below exit
        self._bo_lock = threading.Lock()
        self._GUARDED_BY = {"_brownout": "_bo_lock",
                            "_drain_since": "_bo_lock"}
        self._deaths = []         # (replica, reason) pending for autoscaler
        self._death_lock = threading.Lock()
        self._last_death_mono = None
        self._last_recovery_s = None
        self._last_submit_mono = time.monotonic()
        self._set = ReplicaSet(
            spec, name=name, replicas=replicas, process=process,
            heartbeat_s=heartbeat_s, suspect_misses=suspect_misses,
            on_death=self._death_event, autostart=autostart_heartbeat)
        self._router = ReplicaRouter(
            self._set.live, model=name, retries=retries, hedge_ms=hedge_ms,
            depth_feed=depth_feed, on_death=self._router_death)

    # -- death bookkeeping -------------------------------------------------
    def _death_event(self, replica, reason):
        self._last_death_mono = replica.death_mono or time.monotonic()
        with self._death_lock:
            self._deaths.append((replica, reason))

    def _router_death(self, replica, error):
        # request-level failure IS a health signal: skip the miss ladder
        self._set.mark_dead(replica, reason="request")

    def drain_deaths(self):
        """Hand pending death events to the autoscaler (drains)."""
        with self._death_lock:
            deaths, self._deaths = self._deaths, []
        return deaths

    # -- load signals ------------------------------------------------------
    def queue_fraction(self) -> float:
        """Aggregate fleet load: sum of live queue depths over total
        live capacity (0.0 when nothing is live)."""
        live = self._set.live()
        if not live:
            return 0.0
        cap = self._set.queue_cap() * len(live)
        depth = 0
        for r in live:
            try:
                depth += r.queue_depth()
            except Exception:
                pass
        return min(1.0, depth / float(cap)) if cap else 0.0

    def p99_ms(self):
        return self._router.p99_ms()

    def idle_seconds(self) -> float:
        return time.monotonic() - self._last_submit_mono

    @property
    def last_recovery_s(self):
        """Detection->replacement latency of the most recent recovered
        replica death (``mxtpu_fleet_recovery_seconds``)."""
        return self._last_recovery_s

    def note_recovery(self, seconds):
        self._last_recovery_s = float(seconds)
        if _obs.ENABLED:
            _obs.FLEET_RECOVERY_SECONDS.set(float(seconds),
                                            model=self.name)

    # -- degraded mode -----------------------------------------------------
    def brownout_level(self) -> int:
        with self._bo_lock:
            return self._brownout

    def _evaluate_brownout(self, frac, now):
        """The latched state machine (deterministic test seam): step UP
        immediately on threshold crossings, step DOWN one level per
        sustained-drain hold window."""
        with self._bo_lock:
            prev = self._brownout
            if frac >= self._enter2:
                self._brownout = 2
            elif frac >= self._enter:
                self._brownout = max(self._brownout, 1)
            if self._brownout > 0:
                if frac < self._exit:
                    if self._drain_since is None:
                        self._drain_since = now
                    elif now - self._drain_since >= self._hold_s:
                        self._brownout -= 1
                        self._drain_since = now if self._brownout else None
                else:
                    self._drain_since = None
            level = self._brownout
        if level != prev and _obs.ENABLED:
            _obs.record_fleet_brownout(self.name, level, prev)
        return level

    def _admit(self, priority) -> bool:
        level = self.brownout_level()
        if level >= 2:
            return priority == "critical"
        if level >= 1:
            return priority != "bulk"
        return True

    # -- client surface ----------------------------------------------------
    def submit(self, x, priority="interactive", key=None, **kwargs):
        """Dispatch one request at a priority class; raises typed
        :class:`BrownoutShed` under degraded mode, fails over replica
        death internally, and restores from the warm pool when the
        fleet was scaled to zero."""
        if priority not in PRIORITIES:
            raise MXNetError(
                f"unknown priority {priority!r}; want one of {PRIORITIES}")
        self._last_submit_mono = time.monotonic()
        # chaos: kill_replica@fleet fires HERE, mid-traffic
        if _chaos.ENABLED:
            victim = _chaos.kill_replica_due("fleet")
            if victim is not None:
                self.kill_replica(victim)
        if not self._set.live() and self._set.warm():
            self._set.restore()  # scale-from-zero on demand, not an error
            if _obs.ENABLED:
                _obs.record_fleet_autoscale(self.name, "restore",
                                            self._set.n_live())
        level = self._evaluate_brownout(self.queue_fraction(),
                                        time.monotonic())
        if not self._admit(priority):
            if _obs.ENABLED:
                _obs.FLEET_SHED_TOTAL.inc(1, model=self.name, priority=priority)
            raise BrownoutShed(
                f"fleet {self.name!r} is in brownout level {level}: "
                f"priority class {priority!r} is being shed (retry with "
                "backoff, or escalate the request's priority)")
        return self._router.submit(x, key=key, **kwargs)

    def predict(self, x, timeout=None, priority="interactive", key=None,
                **kwargs):
        return self.submit(x, priority=priority, key=key,
                           **kwargs).result(timeout)

    def kill_replica(self, index):
        """Kill the live replica at ``index`` (chaos actuation / manual
        drill). Safe when the index is gone already."""
        for r in self._set.live():
            if r.index == int(index) or int(index) < 0:
                self._set.mark_dead(r, reason="chaos")
                return r
        return None

    # -- delegation --------------------------------------------------------
    @property
    def replica_set(self) -> ReplicaSet:
        return self._set

    @property
    def router(self) -> ReplicaRouter:
        return self._router

    def n_live(self) -> int:
        return self._set.n_live()

    def swap(self, spec):
        return self._set.swap(spec)

    def stats(self) -> dict:
        return {
            "replicas": self._set.census(),
            "brownout": self.brownout_level(),
            "queue_fraction": self.queue_fraction(),
            "p99_ms": self.p99_ms(),
            "last_recovery_s": self._last_recovery_s,
        }

    def close(self):
        self._set.close()
