"""SLO-driven autoscaler: signals in, membership actuations out.

Three signal sources converge on one auditable bus — the PR-11
:class:`~mxnet_tpu.resilience.elastic.MembershipMonitor` signal queue —
and ONE deterministic ``tick()`` drains it into fleet actuations:

- **watchdog anomalies**: a ``queue_saturation`` firing from the PR-15
  anomaly watchdog (registered listener) becomes a grow request;
- **SLO pressure**: router p99 above ``MXTPU_FLEET_SLO_P99_MS`` or
  aggregate queue fraction at the brownout enter threshold becomes a
  grow request; sustained headroom (p99 under half the SLO, fraction
  under the brownout exit) becomes a shrink request; a fully idle
  fleet (``idle_to_zero_s``) requests scale-to-zero;
- **replica deaths**: each death drained off the fleet becomes a
  ``dead_peer`` signal, actuated as an immediate REPLACEMENT — never
  cooldown-gated, because restoring redundancy is what the cooldown
  exists to protect.

Growth/shrink are cooldown-gated (``MXTPU_FLEET_COOLDOWN_S``) and
clamped to [min_replicas, max_replicas]. Replacement measures
detection->ready recovery latency into ``mxtpu_fleet_recovery_seconds``
— the number the chaos certification gates on.

The monitor here is a PRIVATE instance (policy disabled:
``straggler_factor=0.0``, ``notice_path=""``) used purely as the signal
bus; it is never ``attach()``-ed, so global elastic wiring is
untouched.
"""

from __future__ import annotations

import threading
import time

from .. import observability as _obs
from ..base import getenv
from ..observability import watchdog as _watchdog
from ..resilience.elastic import MembershipMonitor


def fleet_slo_p99_ms() -> float:
    """Serving latency SLO (p99, ms), ``MXTPU_FLEET_SLO_P99_MS``."""
    return float(getenv("MXTPU_FLEET_SLO_P99_MS", 100.0, dtype=float))


def fleet_cooldown_s() -> float:
    """Minimum spacing between capacity changes (replacement is
    exempt), ``MXTPU_FLEET_COOLDOWN_S``."""
    return max(0.0, float(getenv("MXTPU_FLEET_COOLDOWN_S", 5.0,
                                 dtype=float)))


class SLOAutoscaler:
    """Drive a :class:`~.fleet.ServingFleet` toward its SLO."""

    def __init__(self, fleet, *, min_replicas=None, max_replicas=None,
                 slo_p99_ms=None, cooldown_s=None, interval_s=0.5,
                 idle_to_zero_s=0.0, monitor=None, use_watchdog=True):
        from .fleet import fleet_min_replicas, fleet_max_replicas
        self.fleet = fleet
        self.min_replicas = fleet_min_replicas() if min_replicas is None \
            else max(0, int(min_replicas))
        self.max_replicas = fleet_max_replicas() if max_replicas is None \
            else max(1, int(max_replicas))
        self.slo_p99_ms = fleet_slo_p99_ms() if slo_p99_ms is None \
            else float(slo_p99_ms)
        self.cooldown_s = fleet_cooldown_s() if cooldown_s is None \
            else float(cooldown_s)
        self.interval_s = float(interval_s)
        self.idle_to_zero_s = float(idle_to_zero_s)
        # signal bus only: straggler policy + notice file-poll disabled,
        # and NEVER .attach()-ed (that would hijack global wiring)
        self.monitor = monitor or MembershipMonitor(
            straggler_factor=0.0, notice_path="")
        # no change yet: a monotonic clock starts at the host's boot, and
        # 0.0 would put a fresh host in cooldown for its first cooldown_s
        self._last_change_mono = float("-inf")
        self._reported_uids = set()
        self._replaced = 0
        self._thread = None
        self._stop = threading.Event()
        self._use_watchdog = bool(use_watchdog)
        if self._use_watchdog:
            _watchdog.register_listener(self._on_anomaly)

    # -- signal ingestion --------------------------------------------------
    def _on_anomaly(self, kind, details):
        """Watchdog actuator hook: saturation anomalies request growth
        through the same auditable bus as everything else."""
        if kind == "queue_saturation":
            self.monitor.request_resize(
                self.fleet.n_live() + 1, reason="queue_saturation")

    def _ingest_deaths(self):
        for replica, reason in self.fleet.drain_deaths():
            if replica.uid in self._reported_uids:
                continue
            self._reported_uids.add(replica.uid)
            self.monitor.report_dead_peer(
                replica.index,
                detail=f"replica uid={replica.uid} ({reason})")

    def _slo_policy(self, now):
        """Translate SLO pressure/headroom into resize requests."""
        n = self.fleet.n_live()
        p99 = self.fleet.p99_ms()
        frac = self.fleet.queue_fraction()
        in_cooldown = now - self._last_change_mono < self.cooldown_s
        if n > 0 and not in_cooldown and n < self.max_replicas and (
                (p99 is not None and p99 > self.slo_p99_ms)
                or frac >= self.fleet._enter):
            self.monitor.request_resize(n + 1, reason="slo")
            return
        if (self.idle_to_zero_s > 0 and n > 0 and self.min_replicas == 0
                and self.fleet.idle_seconds() >= self.idle_to_zero_s):
            self.monitor.request_resize(0, reason="idle")
            return
        if (n > self.min_replicas and n > 1 and not in_cooldown
                and frac <= self.fleet._exit
                and (p99 is None or p99 < 0.5 * self.slo_p99_ms)
                and self.fleet.router.latency_count() >= 5):
            self.monitor.request_resize(n - 1, reason="drain")

    # -- actuation ---------------------------------------------------------
    def _replace_dead(self, now):
        """Replace every dead replica NOW (cooldown-exempt) and record
        detection->ready recovery latency."""
        rs = self.fleet.replica_set
        for replica in [r for r in rs.replicas() if r.state == "dead"]:
            t_death = replica.death_mono or now
            rs.replace(replica)
            recovery = time.monotonic() - t_death
            self.fleet.note_recovery(recovery)
            self._replaced += 1
            if _obs.ENABLED:
                _obs.record_fleet_autoscale(self.fleet.name, "replace",
                                            self.fleet.n_live())

    def _actuate_resize(self, target, reason, now):
        n = self.fleet.n_live()
        target = max(self.min_replicas, min(self.max_replicas, int(target)))
        if target == 0 and n > 0:
            self.fleet.replica_set.scale_to_zero()
            action = "to_zero"
        elif target > n:
            if self.fleet.replica_set.warm():
                action = "restore"
            else:
                action = "grow"
            self.fleet.replica_set.scale_to(target)
        elif target < n:
            self.fleet.replica_set.scale_to(target)
            action = "shrink"
        else:
            return
        self._last_change_mono = now
        if _obs.ENABLED:
            _obs.record_fleet_autoscale(self.fleet.name, action,
                                        self.fleet.n_live())

    def tick(self, now=None):
        """One deterministic control-loop pass: ingest signals, run the
        SLO policy, drain the bus, actuate. Returns the drained signal
        list (auditable)."""
        now = time.monotonic() if now is None else now
        self._ingest_deaths()
        self._slo_policy(now)
        signals = self.monitor.drain(kinds=("dead_peer", "resize"))
        for sig in signals:
            if sig["kind"] == "dead_peer":
                self._replace_dead(now)
            elif sig["kind"] == "resize":
                self._actuate_resize(sig.get("target"),
                                     sig.get("reason"), now)
        # deaths can also be observed directly (chaos kill between
        # ticks): replace even without a routed dead_peer signal
        if any(r.state == "dead"
               for r in self.fleet.replica_set.replicas()):
            self._replace_dead(now)
        return signals

    @property
    def replaced(self) -> int:
        return self._replaced

    # -- background loop ---------------------------------------------------
    def start(self):
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"mxtpu-fleet-{self.fleet.name}-autoscaler")
        self._thread.start()

    def _loop(self):  # mxtpu-lint: hot-path
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:
                pass  # the control loop must outlive any single actuation

    def stop(self):
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)
        if self._use_watchdog:
            _watchdog.unregister_listener(self._on_anomaly)
