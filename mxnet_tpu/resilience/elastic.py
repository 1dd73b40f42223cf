"""Live elasticity: grow/shrink a RUNNING job without a restart.

PR 8/10 made a resize survivable — die, restore from disk, bit-exact —
but "die" is the expensive part: a full process restart, a recompile
storm, and every queued batch lost. This module closes the loop AT
RUNTIME (ROADMAP item 5):

- :class:`MembershipMonitor` — detects membership change: a preemption
  notice (``MXTPU_PREEMPT_NOTICE`` file, or a socket/API integration
  calling :meth:`~MembershipMonitor.notify_preempt`), a dead peer
  diagnosed by the kvstore barrier watchdog
  (``CollectiveTimeoutError`` -> :func:`notify_dead_peer`), a spot-add
  grow request, an explicit/chaos ``resize`` fault — and feeds a
  per-rank **barrier-latency histogram** into a straggler policy
  (``MXTPU_STRAGGLER_FACTOR``): a peer whose recent latency exceeds
  ``factor x`` the median of its peers' is flagged for eviction
  *before* the barrier watchdog timeout would fire, so a slow host is
  resized out instead of hanging (or crashing) the collective.
  Identifying the straggler needs per-rank samples in ONE monitor:
  the per-device heartbeat probe provides them on a single-host mesh;
  on a multi-process pod each rank's kvstore barrier feeds only its
  OWN wait (the tail signal), so a scheduler/sidecar integration
  delivers peers' latencies via :meth:`~MembershipMonitor
  .observe_latency`.
- :class:`ElasticTrainer` — the control loop around
  ``parallel.SPMDTrainStep``: at every STEP BOUNDARY (never
  mid-dispatch) pending signals are applied as a resize: (1) one
  donation-safe in-memory snapshot (``spmd_state_snapshot`` — the PR-8
  one-dispatch copy protocol, skipping the D2H/disk leg's commit), (2)
  mesh teardown + rebuild on the surviving/augmented device set, (3)
  ZeRO-2/3 + fused optimizer state re-sharded through the PR-10
  pad-clipped LOGICAL-span machinery (``spmd_restore_chunks`` re-pads
  for the new dp entirely host/device-side), (4) re-entry into the
  compiled step. Steps objects are cached PER TOPOLOGY, so returning
  to a previously-seen device set re-enters WARM (zero recompiles:
  4->2->4 reuses the original dp=4 executable); a brand-new topology
  in a restarted process still warms from ``MXTPU_COMPILE_CACHE``.

Zero committed steps are lost across a resize: the snapshot is taken
at a step boundary, the restored state is bit-exact with the state the
old mesh produced (pinned by tests/test_elastic.py), and the step counter
continues — no step re-runs, none is skipped. Every resize leaves an
auditable in-memory snapshot descriptor
(:func:`snapshot_descriptor`; ``tools/verify_checkpoint.py
--from-json`` lints it) plus resize counters/spans in the telemetry
registry and a ``elastic.resize`` trace event the crash flight
recorder picks up.

The Gluon (kvstore) training path has no in-process mesh to rebuild;
there the monitor's pause points (``Trainer.step`` /
``Superstep.step`` call :func:`pause_point` behind one module-bool
read) turn a preemption notice into a PROACTIVE async checkpoint at
the next safe step boundary. See docs/robustness.md "Runtime
elasticity".
"""

from __future__ import annotations

import logging
import os
import threading
import time
import zlib
from collections import deque

from .. import fusedstep as _fusedstep
from .. import observability as _obs
from ..base import MXNetError, getenv
from . import chaos as _chaos

_logger = logging.getLogger("mxnet_tpu.elastic")

#: THE pause-point switch (``MXTPU_ELASTIC``, default off — or armed
#: automatically when a MembershipMonitor attaches): when False, the
#: Trainer/Superstep step-boundary hooks cost one module-bool read.
ENABLED = _fusedstep.elastic_enabled()

_ACTIVE = None  # the attached MembershipMonitor (module singleton)

DESCRIPTOR_FORMAT = "mxtpu-snapshot-v1"


def straggler_factor():
    """``MXTPU_STRAGGLER_FACTOR`` (default 0 = straggler detection
    off): a rank whose recent mean barrier/heartbeat latency exceeds
    ``factor x`` the median of the OTHER ranks' (and the absolute
    floor, see :class:`MembershipMonitor`) is flagged for proactive
    eviction."""
    return float(getenv("MXTPU_STRAGGLER_FACTOR", 0.0, dtype=float))


def notice_path():
    """``MXTPU_PREEMPT_NOTICE``: path of the preemption-notice file the
    monitor polls (the TPU metadata-server / cluster-scheduler
    integration point — a sidecar touches the file, optionally writing
    ``shrink:<n>`` / ``grow:<n>`` / ``evict:<rank>``)."""
    return getenv("MXTPU_PREEMPT_NOTICE", None)


def monitor():
    """The attached :class:`MembershipMonitor`, or None."""
    return _ACTIVE


def set_enabled(on):
    """Arm/disarm the step-boundary pause points at runtime; returns
    the previous state."""
    global ENABLED
    prev, ENABLED = ENABLED, bool(on)
    return prev


def observe_barrier(rank, seconds):
    """Feed one barrier-latency sample into the active monitor's
    histogram (the kvstore barrier watchdog calls this after every
    timed sync when elasticity is armed)."""
    if _ACTIVE is not None:
        _ACTIVE.observe_latency(rank, seconds)


def notify_dead_peer(rank=None, detail=""):
    """A collective/barrier watchdog diagnosed a dead peer: queue the
    membership-change signal (the kvstore wiring — called right before
    ``CollectiveTimeoutError`` propagates)."""
    if _ACTIVE is not None:
        _ACTIVE.report_dead_peer(rank=rank, detail=detail)


def pause_point(site, trainer=None):
    """Safe elasticity pause point at a training-step boundary.

    ``Trainer.step`` / ``Superstep.step`` call this behind one
    module-bool read (``ENABLED``), so membership signals are only ever
    processed where pausing is SAFE — never mid-dispatch, never with a
    half-applied carry. On the Gluon/kvstore path there is no
    in-process mesh to rebuild: a pending preemption notice turns into
    a PROACTIVE async checkpoint through the trainer's attached
    :class:`~mxnet_tpu.resilience.checkpoint.CheckpointManager` (one
    copy dispatch now, the write off-thread — the final SIGTERM save
    then has almost nothing left to lose). Resize signals stay queued
    for an elastic controller (:class:`ElasticTrainer` drains them at
    ITS step boundary)."""
    mon = _ACTIVE
    if mon is None:
        return
    mon.poll()
    sigs = mon.drain(kinds=("preempt",))
    if not sigs or trainer is None:
        return
    mgr = getattr(trainer, "_ckpt_manager", None)
    if mgr is not None:
        mgr.save_async(reason="preempt_notice")
        _logger.warning(
            "elastic: preemption notice — proactive checkpoint queued "
            "at the %s step boundary", site)
    else:
        _logger.warning(
            "elastic: preemption notice at the %s step boundary, but "
            "no CheckpointManager is attached — nothing to save "
            "proactively (MXTPU_CHECKPOINT?)", site)


class MembershipMonitor:
    """Membership-change detection + straggler policy.

    Signals are plain dicts ``{"kind", "reason", "target", "rank",
    "detail"}`` with kinds ``preempt`` / ``dead_peer`` / ``straggler``
    / ``resize``; producers enqueue from any thread, a controller
    drains them at a step boundary.

    The straggler policy is fed by :meth:`observe_latency` — barrier
    wait times from the kvstore watchdog wiring, or per-rank heartbeat
    probe latencies on a single-process mesh — into a rolling per-rank
    window. A rank is flagged once when its mean exceeds
    ``straggler_factor x`` the median of the OTHER ranks' means AND the
    absolute floor ``min_latency_s`` (host noise on a sub-millisecond
    barrier must not read as a straggler), with at least
    ``min_samples`` samples per rank.
    """

    def __init__(self, straggler_factor=None, notice_path=None,
                 window=32, min_samples=3, min_latency_s=0.01):
        self.straggler_factor = (
            globals()["straggler_factor"]() if straggler_factor is None
            else float(straggler_factor))
        self._notice = (globals()["notice_path"]()
                        if notice_path is None else notice_path)
        self._notice_seen = None
        self._window = int(window)
        self._min_samples = int(min_samples)
        self._min_latency_s = float(min_latency_s)
        self._lock = threading.Lock()
        self._signals = []
        self._lat = {}       # rank -> deque of recent latencies
        self._flagged = set()

    # -- lifecycle -------------------------------------------------------
    def attach(self):
        """Become THE active monitor: the kvstore watchdog wiring and
        the Trainer/Superstep pause points feed/drain this instance.
        Arms ``ENABLED``. Returns self."""
        global _ACTIVE
        _ACTIVE = self
        set_enabled(True)
        return self

    def detach(self):
        global _ACTIVE
        if _ACTIVE is self:
            _ACTIVE = None
            set_enabled(_fusedstep.elastic_enabled())

    # -- signal producers ------------------------------------------------
    def _enqueue(self, sig):
        with self._lock:
            self._signals.append(sig)
        _logger.warning("elastic: membership signal %s", sig)

    def notify_preempt(self, detail="", target=None):
        """A preemption notice arrived (file poll, SIGTERM chain, or a
        scheduler/socket integration calling this directly)."""
        self._enqueue({"kind": "preempt", "reason": "preempt",
                       "target": target, "rank": None, "detail": detail})

    def report_dead_peer(self, rank=None, detail=""):
        self._enqueue({"kind": "dead_peer", "reason": "dead_peer",
                       "target": None, "rank": rank, "detail": detail})

    def request_resize(self, target, reason="manual"):
        """Ask for a resize to ``target`` devices (spot add = a target
        above the current extent; chaos ``resize`` faults land here)."""
        self._enqueue({"kind": "resize", "reason": reason,
                       "target": int(target), "rank": None, "detail": ""})

    def poll(self):
        """Check the preemption-notice file (``MXTPU_PREEMPT_NOTICE``):
        a new mtime/size enqueues one signal. File contents steer it:
        empty = plain preemption notice (proactive checkpoint),
        ``shrink:<n>``/``grow:<n>`` = resize to n, ``evict:<rank>`` =
        drop one rank."""
        p = self._notice
        if not p:
            return
        try:
            st = os.stat(p)
        except OSError:
            return
        tag = (st.st_mtime_ns, st.st_size)
        if tag == self._notice_seen:
            return
        self._notice_seen = tag
        try:
            with open(p) as f:
                body = f.read().strip()
        except OSError:
            body = ""
        kind, _, arg = body.partition(":")
        if kind in ("shrink", "grow") and arg.strip().isdigit():
            self.request_resize(int(arg), reason="notice")
        elif kind == "evict" and arg.strip().isdigit():
            self._enqueue({"kind": "dead_peer", "reason": "notice",
                           "target": None, "rank": int(arg),
                           "detail": body})
        else:
            self.notify_preempt(detail=body or p)

    # -- straggler policy ------------------------------------------------
    def observe_latency(self, rank, seconds):
        """One barrier/heartbeat latency sample for ``rank``; feeds the
        histogram and (when the policy is armed) may enqueue a one-shot
        ``straggler`` signal for that rank."""
        rank = int(rank)
        with self._lock:
            dq = self._lat.setdefault(rank, deque(maxlen=self._window))
            dq.append(float(seconds))
        if _obs.ENABLED:
            _obs.ELASTIC_PEER_LATENCY_SECONDS.observe(
                float(seconds), rank=str(rank))
        if self.straggler_factor <= 0 or rank in self._flagged:
            return
        if rank in self.straggler_ranks():
            self._flagged.add(rank)
            self._enqueue({"kind": "straggler", "reason": "straggler",
                           "target": None, "rank": rank,
                           "detail": f"mean latency {self._mean(rank):.4f}s"})

    def _mean(self, rank):
        dq = self._lat.get(rank)
        return sum(dq) / len(dq) if dq else 0.0

    def straggler_ranks(self):
        """Ranks currently over the policy line (see class docstring).
        Pure read — enqueuing happens in :meth:`observe_latency`."""
        with self._lock:
            means = {r: sum(d) / len(d) for r, d in self._lat.items()
                     if len(d) >= self._min_samples}
        if self.straggler_factor <= 0 or len(means) < 2:
            return []
        out = []
        for r, m in means.items():
            others = sorted(v for rr, v in means.items() if rr != r)
            med = others[len(others) // 2]
            if m > self.straggler_factor * max(med, 1e-9) \
                    and m > self._min_latency_s:
                out.append(r)
        return out

    def reset_latency(self):
        """Forget all latency windows + straggler flags (rank indices
        remap after every resize, so stale samples would be attributed
        to the wrong device)."""
        with self._lock:
            self._lat.clear()
        self._flagged.clear()

    # -- consumers -------------------------------------------------------
    def pending(self):
        with self._lock:
            return list(self._signals)

    def drain(self, kinds=None):
        """Pop (and return) pending signals — all of them, or only the
        given kinds (the pause points take just ``preempt``, leaving
        resizes for the elastic controller)."""
        with self._lock:
            if kinds is None:
                out, self._signals = self._signals, []
            else:
                out = [s for s in self._signals if s["kind"] in kinds]
                self._signals = [s for s in self._signals
                                 if s["kind"] not in kinds]
        return out


def snapshot_descriptor(chunks, extents=None, step=None, reason="resize",
                        from_devices=None, to_devices=None, cursor=None):
    """Auditable descriptor of an in-memory snapshot: per-chunk
    shape/dtype/nbytes/CRC32 plus opt-state completeness info — what a
    resize hands over, minus the payload. ``tools/verify_checkpoint.py
    --from-json`` (and ``resilience.checkpoint.verify_descriptor``)
    lint it: a driver can certify "the resize carried a complete,
    self-consistent state" without the bytes ever touching disk."""
    import numpy as onp

    tensors = {}
    opt_leaves = {}
    param_names = []
    for key in sorted(chunks):
        for idx, data in chunks[key]:
            host = onp.asarray(data)
            spans = ";".join(f"{sl.start}:{sl.stop}" for sl in idx)
            tensors[f"{key}|{spans}"] = {
                "shape": list(host.shape),
                "dtype": str(host.dtype),
                "nbytes": int(host.nbytes),
                "crc32": zlib.crc32(host.tobytes()) & 0xFFFFFFFF}
        if key.startswith("opt::"):
            name, _, li = key[len("opt::"):].rpartition("::")
            opt_leaves[name] = max(opt_leaves.get(name, 0), int(li) + 1)
        elif key.startswith("param::"):
            param_names.append(key[len("param::"):])
    return {"format": DESCRIPTOR_FORMAT, "kind": "spmd-snapshot",
            "step": None if step is None else int(step),
            "reason": reason,
            "cursor": (None if cursor is None else
                       dict(cursor) if isinstance(cursor, dict) else
                       int(cursor)),
            "topology": {"from_devices": from_devices,
                         "to_devices": to_devices},
            "residual_extents": {k: int(v)
                                 for k, v in (extents or {}).items()},
            "extras": {"opt_leaves": opt_leaves,
                       "param_names": param_names},
            "tensors": tensors}


class ElasticTrainer:
    """The runtime-elasticity control loop around ``SPMDTrainStep``.

    >>> et = ElasticTrainer(net, loss_fn, "adam", {}, zero_stage=2)
    >>> for x, y in stream:
    ...     loss = et.step(x, y, lr=0.01)   # resizes happen HERE,
    ...                                     # at step boundaries

    Feed GLOBAL batches (the batch size must divide every device count
    the job may resize through); ``shard_batch`` re-shards them over
    whatever mesh is current. One :class:`MembershipMonitor` drives
    membership; chaos ``resize`` faults are polled per boundary when
    armed, so the whole loop is chaos-certifiable.
    """

    def __init__(self, block, loss_fn, optimizer="sgd",
                 optimizer_params=None, devices=None, device_pool=None,
                 batch_axis="dp", monitor=None, min_devices=1,
                 ring=None, on_resize=None, heartbeat_every=1,
                 **step_kwargs):
        import jax

        self.block = block
        self.loss_fn = loss_fn
        self._optimizer = optimizer
        self._hyper = dict(optimizer_params or {})
        self._batch_axis = batch_axis
        self._kwargs = dict(step_kwargs)
        self._pool = list(device_pool if device_pool is not None
                          else jax.devices())
        self._devices = list(devices if devices is not None else self._pool)
        if not self._devices:
            raise MXNetError("ElasticTrainer: empty device set")
        self._min_devices = max(1, int(min_devices))
        self._monitor = monitor if monitor is not None \
            else MembershipMonitor()
        self._monitor.attach()
        self._steps = {}  # topology key -> SPMDTrainStep (warm re-entry)
        self._step_obj = self._get_step(self._devices)
        self._committed = 0
        self._ring = ring
        self._on_resize = on_resize
        self._heartbeat_every = max(1, int(heartbeat_every))
        self._hb_x = None
        self.resize_events = []
        self.last_descriptor = None
        self.last_snapshot = None
        if _obs.ENABLED:
            _obs.ELASTIC_WORLD_SIZE.set(len(self._devices))

    # -- topology --------------------------------------------------------
    @property
    def devices(self):
        return list(self._devices)

    @property
    def committed_steps(self):
        """Training steps completed (committed) so far — continues
        MONOTONICALLY across resizes: zero steps are lost or re-run."""
        return self._committed

    @property
    def spmd_step(self):
        """The live ``SPMDTrainStep`` for the current topology."""
        return self._step_obj

    @property
    def monitor(self):
        return self._monitor

    def _topo_key(self, devices):
        return tuple(d.id for d in devices)

    def _mesh(self, devices):
        import numpy as onp

        from jax.sharding import Mesh

        return Mesh(onp.array(devices), (self._batch_axis,))

    def _get_step(self, devices):
        key = self._topo_key(devices)
        st = self._steps.get(key)
        if st is None:
            from ..parallel.spmd import SPMDTrainStep

            st = SPMDTrainStep(self.block, self.loss_fn, self._optimizer,
                               dict(self._hyper), mesh=self._mesh(devices),
                               batch_axis=self._batch_axis, **self._kwargs)
            self._steps[key] = st
        return st

    # -- the control loop ------------------------------------------------
    def step(self, x, y, lr=0.01, sync=True):
        """One training step, with membership processed at the boundary
        FIRST: chaos ``resize`` faults, heartbeat/straggler probing,
        the preemption-notice poll, then any pending resize — and only
        then the compiled step on whatever mesh is now current."""
        if _chaos.ENABLED:
            target = _chaos.resize_due("elastic")
            if target is not None:
                self._monitor.request_resize(target, reason="chaos")
        if self._monitor.straggler_factor > 0 \
                and len(self._devices) > self._min_devices \
                and self._committed % self._heartbeat_every == 0:
            self._heartbeat()
        self._monitor.poll()
        sigs = self._monitor.drain()
        if sigs:
            self._apply_signals(sigs)
        loss = self._step_obj(x, y, lr=lr, sync=sync)
        self._committed += 1
        return loss

    def _heartbeat(self):
        """Per-rank health probe: a tiny host->device transfer timed
        per device feeds the monitor's latency histogram — the
        single-process analog of per-peer barrier wait times (chaos
        ``stall@rank<k>`` faults inflate exactly one rank, simulating a
        straggling host)."""
        import jax
        import numpy as onp

        if self._hb_x is None:
            self._hb_x = onp.zeros((8,), onp.float32)
        for r, dev in enumerate(self._devices):
            t0 = time.perf_counter()
            if _chaos.ENABLED:
                # the stall lands INSIDE the timed window
                _chaos.step_point(f"rank{r}")
            jax.device_put(self._hb_x, dev).block_until_ready()
            self._monitor.observe_latency(r, time.perf_counter() - t0)

    def _apply_signals(self, sigs):
        # rank-bearing signals all refer to the ENQUEUE-time index
        # space (self._devices as it was when flagged), so evictions
        # are collected as a set and applied in one pass — popping a
        # mutating list would evict the wrong device the moment two
        # ranks are flagged in the same drain
        evict = set()
        targets = []
        reason = None
        ckpt_only = False
        for s in sigs:
            k = s["kind"]
            if k == "resize":
                targets.append((int(s["target"]),
                                s.get("reason") or "manual"))
            elif k in ("straggler", "dead_peer"):
                r = s.get("rank")
                if r is not None and 0 <= r < len(self._devices):
                    evict.add(int(r))
                    reason = k
            elif k == "preempt":
                t = s.get("target")
                if t:
                    targets.append((int(t), "preempt"))
                else:
                    ckpt_only = True
        devices = list(self._devices)
        evicted_devs = set()
        if evict:
            allowed = len(devices) - self._min_devices
            kept, removed = [], 0
            for i, d in enumerate(devices):
                if i in evict and removed < allowed:
                    removed += 1
                    evicted_devs.add(d)
                    continue
                kept.append(d)
            devices = kept
        for t, why in targets:  # resize targets apply to the survivors
            n = max(self._min_devices, min(t, len(self._pool)))
            if n <= len(devices):
                devices = devices[:n]
            else:
                for d in self._pool:  # spot add: extend from pool —
                    if len(devices) >= n:  # never re-adding a device
                        break              # evicted in this same drain
                    if d not in devices and d not in evicted_devs:
                        devices.append(d)
            reason = why
        if self._topo_key(devices) != self._topo_key(self._devices):
            self.resize(devices, reason=reason or "signal")
        elif ckpt_only:
            # a targetless preemption notice: proactive in-memory
            # snapshot + descriptor (a disk manager, if any, rides the
            # Trainer pause-point path instead)
            self.snapshot(reason="preempt")

    # -- resize ----------------------------------------------------------
    def snapshot(self, reason="manual"):
        """Proactive checkpoint-in-memory of the CURRENT state (one
        donation-safe copy dispatch); stores ``last_snapshot`` /
        ``last_descriptor``. Returns the descriptor."""
        from ..parallel import spmd as _spmd

        if self._step_obj._state is None:
            self._step_obj.init_state()
        chunks, extents = _spmd.spmd_state_snapshot(self._step_obj)
        self.last_snapshot = (chunks, extents)
        self.last_descriptor = snapshot_descriptor(
            chunks, extents, step=self._committed, reason=reason,
            from_devices=len(self._devices),
            to_devices=len(self._devices), cursor=self._cursor())
        return self.last_descriptor

    def _cursor(self):
        if self._ring is not None:
            c = getattr(self._ring, "cursor", None)
            if c is not None:
                return c if isinstance(c, dict) else int(c)
        return None

    def resize(self, new_devices, reason="manual"):
        """Tear down and rebuild the step on ``new_devices`` — IN
        PROCESS: snapshot-in-memory, per-topology step reuse (warm
        re-entry), pad-clipped logical re-shard of ZeRO/optimizer
        state, residual-carry handoff, kvstore world-cache reset, and
        data-cursor re-partition of an attached prefetcher/ring.
        Returns the resize event record."""
        from ..parallel import spmd as _spmd

        new_devices = list(new_devices)
        if len(new_devices) < self._min_devices:
            raise MXNetError(
                f"resize: {len(new_devices)} devices is below "
                f"min_devices={self._min_devices}")
        if self._topo_key(new_devices) == self._topo_key(self._devices):
            return None
        t0 = time.perf_counter()
        old = self._step_obj
        old_n = len(self._devices)
        if old._state is None:
            old.init_state()
        chunks, extents = _spmd.spmd_state_snapshot(old)
        self.last_snapshot = (chunks, extents)
        self.last_descriptor = snapshot_descriptor(
            chunks, extents, step=self._committed, reason=reason,
            from_devices=old_n, to_devices=len(new_devices),
            cursor=self._cursor())
        new = self._get_step(new_devices)
        warm = new._compiled is not None or new._staged is not None
        if new._state is None:
            new.init_state()
        _spmd.spmd_restore_chunks(new, chunks, extents=extents)
        # drop the OLD topology's state arrays: warm re-entry needs
        # only its compiled executable, and the full param/opt copy
        # would otherwise pin one model's worth of device memory per
        # topology visited. A later re-entry re-inits via init_state()
        # and restores over it. (The 2-bit compression residual carry
        # stays — it is the template an unchanged-dp re-entry restores
        # into, and is only bucket-payload-sized state.)
        old._state = None
        old._last_loss = None
        self._devices = new_devices
        self._step_obj = new
        self._monitor.reset_latency()
        # the kvstore's cached one-device-per-process reduce mesh is
        # stale after a membership change: drop it so the next
        # collective rebuilds against the current world WITHOUT
        # re-registering the store or restarting the process
        from ..kvstore import dist as _kvd

        _kvd.reset_world()
        if self._ring is not None:
            rp = getattr(self._ring, "repartition", None)
            if rp is not None:
                # the deterministic cursor is preserved; already-staged
                # batches re-partition onto the new mesh extent
                rp(mesh=new.mesh)
        dt = time.perf_counter() - t0
        ev = {"reason": str(reason), "from": old_n,
              "to": len(new_devices), "step": self._committed,
              "seconds": dt, "warm": warm}
        self.resize_events.append(ev)
        if _obs.ENABLED:
            _obs.ELASTIC_RESIZES_TOTAL.inc(1, reason=str(reason))
            if reason == "straggler":
                _obs.ELASTIC_STRAGGLER_EVICTIONS_TOTAL.inc()
            _obs.ELASTIC_RESIZE_SECONDS.observe(dt)
            _obs.ELASTIC_WORLD_SIZE.set(len(new_devices))
            _obs.tracer().record("elastic.resize", cat="resilience",
                                 ts=t0, dur=dt, args=dict(ev))
        _logger.warning(
            "elastic: resized %d -> %d devices (%s) in %.3fs at "
            "committed step %d — no restart, state re-sharded in "
            "memory (%s re-entry)", old_n, len(new_devices), reason, dt,
            self._committed, "warm" if warm else "cold")
        if self._on_resize is not None:
            self._on_resize(ev, chunks)
        return ev

    def dump_descriptor(self, path):
        """Write ``last_descriptor`` as JSON (the ``--from-json``
        verification handoff). Returns the path, or None when no
        snapshot was taken yet."""
        import json

        if self.last_descriptor is None:
            return None
        from .checkpoint import atomic_replace

        def write(tmp):
            with open(tmp, "w") as f:
                json.dump(self.last_descriptor, f, indent=1)
                f.write("\n")

        atomic_replace(str(path), write)
        return str(path)

    def sync_to_block(self):
        """Write the live step's params back into the Gluon handles."""
        if self._step_obj._state is not None:
            self._step_obj.sync_to_block()

    def close(self):
        self._monitor.detach()
