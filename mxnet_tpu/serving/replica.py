"""Serving replicas: one engine-behind-a-repository per host/process.

The unit the fleet router dispatches onto. Two implementations with
one surface:

- :class:`LocalReplica` — in-process: a private :class:`ModelRepository`
  wrapping one :class:`InferenceEngine` (same process, own queue). The
  unit-test and single-host form; ``kill()`` simulates abrupt host
  death (queued requests FAIL typed via ``ContinuousBatcher.abort`` —
  they never hang, and the router fails them over).
- :class:`ProcessReplica` — a child process running
  ``mxnet_tpu.serving.replica_worker`` with a length-prefixed pickle
  RPC over stdin/stdout: submit / ping / swap / close. Request
  completions stream back on a reader thread; a broken pipe or child
  death fails every pending future with a typed
  :class:`~.errors.ReplicaDead` IMMEDIATELY — the failure mode chaos
  certification exists to prove (``kill()`` here is a real SIGKILL).

Replica specs are plain dicts so they cross the process boundary::

    {"net": {"dense": {"classes": 4, "feat": 8, "bias": 0.5}},
     "shapes": [(8,)], "version": "v1",
     "engine": {"max_batch": 8, "max_wait_ms": 2.0}}

``net`` is a builtin-net dict, an importable ``"module:callable"``
factory path, a zero-arg factory, or a ready block (the last two for
local replicas). Every replica carries health bookkeeping (state,
heartbeat misses, last-known queue depth) owned by the
:class:`~.fleet.ReplicaSet` health loop.
"""

from __future__ import annotations

import importlib
import itertools
import os
import pickle
import struct
import subprocess
import sys
import threading
import time

import numpy as _np

from ..base import MXNetError
from ..resilience import chaos as _chaos
from .errors import (
    BrownoutShed,
    EngineClosed,
    KVCacheOOM,
    ReplicaDead,
    ReplicaLost,
    RequestCancelled,
    RequestTimeout,
    RequestTooLarge,
    RetraceForbidden,
    ServerOverloaded,
    ServingError,
    StagedLoadError,
)
from .repository import ModelRepository

#: process-unique replica uids: the router's at-most-once set is keyed
#: by uid, so a REPLACEMENT replica at a dead one's index is a fresh
#: candidate while the dead one stays burned
_UIDS = itertools.count(1)

#: typed-error wire registry: the child sends ``(etype, emsg)`` and the
#: parent re-raises the SAME class, so response-code mapping by type
#: survives the RPC hop (unknown types degrade to ServingError)
_ERROR_TYPES = {cls.__name__: cls for cls in (
    ServingError, ServerOverloaded, BrownoutShed, RequestTimeout,
    RequestTooLarge, EngineClosed, RetraceForbidden, StagedLoadError,
    RequestCancelled, ReplicaDead, ReplicaLost, KVCacheOOM, MXNetError)}
_ERROR_TYPES["TimeoutError"] = TimeoutError


def rebuild_error(etype, emsg):
    """Wire form -> typed exception (the parent half of the registry)."""
    return _ERROR_TYPES.get(str(etype), ServingError)(str(emsg))


# ---------------------------------------------------------------------------
# net specs (shared with the child worker)
# ---------------------------------------------------------------------------

def _dense_net(classes=4, feat=8, bias=0.0, scale=0.1):
    """Builtin deterministic worker net — ``y[c] = scale * sum(x) +
    bias`` for every class ``c``. Process replicas build
    it child-side without importing any test code; model VERSIONS are
    distinguishable by their bias (the swap-coherence probes rely on
    it)."""
    from .. import ndarray as nd
    from ..gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(int(classes), in_units=int(feat)))
    net.initialize()
    net[0].weight.set_data(nd.ones((int(classes), int(feat))) * float(scale))
    net[0].bias.set_data(nd.ones((int(classes),)) * float(bias))
    return net


def build_net(net_spec):
    """Materialize a replica spec's ``net`` entry into a servable block:
    a ready block passes through, a zero-arg factory is called, an
    ``"module:attr"`` path is imported (the ONLY callable form that
    crosses the process boundary), and ``{"dense": {...}}`` builds the
    builtin deterministic net."""
    if hasattr(net_spec, "aot_predict_fn") \
            or hasattr(net_spec, "decode_step_fn"):
        return net_spec
    if isinstance(net_spec, str):
        mod, _, attr = net_spec.partition(":")
        if not mod or not attr:
            raise MXNetError(
                f"replica net path {net_spec!r} must be 'module:callable'")
        return build_net(getattr(importlib.import_module(mod), attr))
    if isinstance(net_spec, dict) and "dense" in net_spec:
        return _dense_net(**dict(net_spec["dense"]))
    if isinstance(net_spec, dict) and "decoder" in net_spec:
        # generation workload: every replica rebuilds the decoder from
        # the same seeded spec, so the fleet serves identical weights
        from .decoder import TransformerDecoderLM

        return TransformerDecoderLM(**dict(net_spec["decoder"]))
    if callable(net_spec):
        return build_net(net_spec())
    raise MXNetError(
        f"cannot build a replica net from {type(net_spec).__name__} "
        "(want a block, a factory, 'module:callable', "
        "{'dense': {...}}, or {'decoder': {...}})")


def normalize_spec(spec) -> dict:
    """Validate + copy a replica spec dict."""
    spec = dict(spec)
    if "net" not in spec or "shapes" not in spec:
        raise MXNetError("replica spec needs 'net' and 'shapes' entries")
    spec.setdefault("engine", {})
    return spec


# ---------------------------------------------------------------------------
# wire framing (parent <-> child): 4-byte big-endian length + pickle
# ---------------------------------------------------------------------------

def write_msg(stream, obj):
    blob = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    stream.write(struct.pack(">I", len(blob)) + blob)
    stream.flush()


def read_msg(stream):
    head = stream.read(4)
    if head is None or len(head) < 4:
        raise EOFError("replica pipe closed")
    n = struct.unpack(">I", head)[0]
    chunks = []
    while n > 0:
        chunk = stream.read(n)
        if not chunk:
            raise EOFError("replica pipe truncated mid-frame")
        chunks.append(chunk)
        n -= len(chunk)
    return pickle.loads(b"".join(chunks))


# ---------------------------------------------------------------------------
# replica base: health + depth bookkeeping shared by both kinds
# ---------------------------------------------------------------------------

class _ReplicaBase:
    kind = "?"

    def __init__(self, index, spec, name="model"):
        self.uid = next(_UIDS)
        self.index = int(index)
        self.name = str(name)
        self.spec = normalize_spec(spec)
        self.state = "starting"   # starting|live|suspect|dead|warm|closed
        self.misses = 0           # consecutive heartbeat misses
        self.death_mono = None    # monotonic stamp of death detection
        self._depth = 0
        self._depth_mono = 0.0

    def note_depth(self, depth):
        self._depth = int(depth)
        self._depth_mono = time.monotonic()

    def depth_age(self) -> float:
        """Seconds since the last depth observation (inf before the
        first one) — the router's freshness test for this signal."""
        if not self._depth_mono:
            return float("inf")
        return time.monotonic() - self._depth_mono

    def queue_depth(self) -> int:
        return self._depth

    def _chaos_point(self):
        # stall@replica<k> lands here: every dispatch onto this replica
        # stalls (serving straggler), feeding depth avoidance + hedging
        if _chaos.ENABLED:
            _chaos.step_point(f"replica{self.index}")

    def __repr__(self):
        return (f"<{type(self).__name__} {self.name}#{self.index} "
                f"uid={self.uid} {self.state}>")


class LocalReplica(_ReplicaBase):
    """In-process replica: a private ModelRepository + engine."""

    kind = "local"

    def __init__(self, index, spec, name="model"):
        super().__init__(index, spec, name)
        self._dead = False
        self._repo = ModelRepository(keep=int(self.spec.get("keep", 1)))
        self._load(self.spec)
        self.state = "live"

    def _load(self, spec):
        eng_kwargs = dict(spec.get("engine") or {})
        self._repo.load(self.name, lambda: build_net(spec["net"]),
                        spec["shapes"], version=spec.get("version"),
                        **eng_kwargs)

    def wait_ready(self, timeout=None):
        return self  # construction already compiled + verified

    def _dead_error(self):
        return ReplicaDead(
            f"replica {self.name}#{self.index} is dead (host kill) — "
            "retry on a surviving replica")

    def submit(self, x, **kwargs):
        if self._dead:
            raise self._dead_error()
        self._chaos_point()
        try:
            return self._repo.submit(self.name, x, **kwargs)
        except EngineClosed:
            if self._dead:
                raise self._dead_error() from None
            raise

    def ping(self, timeout=None) -> dict:
        if self._dead:
            raise self._dead_error()
        engine = self._repo.engine(self.name)
        depth = engine.queue_depth()
        self.note_depth(depth)
        return {"depth": depth, "version": engine.version}

    def queue_depth(self) -> int:
        if not self._dead:
            try:
                self.note_depth(self._repo.engine(self.name).queue_depth())
            except ServingError:
                pass
        return self._depth

    def depth_age(self) -> float:
        return 0.0 if not self._dead else super().depth_age()

    def live_version(self):
        return self._repo.live_version(self.name)

    def swap(self, spec, timeout=None):
        """Staged swap on THIS replica (stage -> verify -> atomic flip
        via the repository; a failed stage never becomes visible)."""
        spec = normalize_spec(spec)
        self._load(spec)
        self.spec = spec
        return self._repo.live_version(self.name)

    def stats(self) -> dict:
        return self._repo.stats(self.name)

    def pause(self):
        """Warm-pool parking (scale-to-zero): drain, keep executables
        and weights resident — ``resume()`` is instant, no recompile."""
        self._repo.engine(self.name).pause()
        self.state = "warm"

    def resume(self):
        self._repo.engine(self.name).resume()
        self.state = "live"

    def kill(self):
        """Abrupt host-death simulation: queued requests fail with
        typed ReplicaDead (never drained, never hung)."""
        self._dead = True
        self.state = "dead"
        if self.death_mono is None:
            self.death_mono = time.monotonic()
        try:
            self._repo.engine(self.name).kill()
        except ServingError:
            pass

    def close(self):
        """Graceful retirement (shrink): drain in-flight, release."""
        self._repo.close()
        if self.state != "dead":
            self.state = "closed"


# ---------------------------------------------------------------------------
# process replica (parent side)
# ---------------------------------------------------------------------------

class RemoteFuture:
    """Parent-side handle for one RPC to a child replica; same waiting
    surface as :class:`~.batcher.ServeFuture` (done/result/version)."""

    def __init__(self, replica, msg_id):
        self.replica = replica
        self.msg_id = msg_id
        self.version = None
        self._event = threading.Event()
        self._result = None
        self._error = None

    def done(self) -> bool:
        return self._event.is_set()

    def finish(self, result=None, error=None, version=None):
        self._result = result
        self._error = error
        if version is not None:
            self.version = version
        self._event.set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"replica {self.replica.name}#{self.replica.index} RPC "
                f"{self.msg_id} not ready within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result


class ProcessReplica(_ReplicaBase):
    """A replica in its own OS process (the 'host' of host-kill chaos).

    RPC: pickle frames over stdin/stdout; a reader thread resolves
    pending futures; EOF/broken pipe => every pending future fails with
    typed ReplicaDead immediately (in-flight requests NEVER hang on a
    dead host). ``kill()`` is a real SIGKILL.
    """

    kind = "process"

    #: machine-checked lock protocol (mxtpu-lint thread-guard)
    _GUARDED_BY = {"_pending": "_lock", "_dead": "_lock"}

    def __init__(self, index, spec, name="model", env=None):
        super().__init__(index, spec, name)
        self._env = dict(env or {})
        self._lock = threading.Lock()
        self._wlock = threading.Lock()
        self._closing = False
        self._spawn()

    def _spawn(self):
        with self._lock:
            self._dead = False
            self._ids = itertools.count(1)
            self._pending = {}
        self._closing = False
        child_env = dict(os.environ)
        child_env.update(self._env)
        child_env.setdefault("JAX_PLATFORMS", "cpu")
        # the child must resolve the SAME mxnet_tpu the parent runs,
        # even when the parent found it via sys.path (user script)
        # rather than an installed distribution
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        prior = child_env.get("PYTHONPATH", "")
        child_env["PYTHONPATH"] = \
            pkg_root + (os.pathsep + prior if prior else "")
        # fleet faults fire in the PARENT (by replica index); the child
        # must not independently re-fire the same spec
        child_env.pop("MXTPU_CHAOS", None)
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu.serving.replica_worker"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=child_env)
        self._reader = threading.Thread(
            target=self._read_loop, daemon=True,
            name=f"mxtpu-replica{self.index}-reader")
        self._ready = self._call({"op": "init", "spec": self.spec,
                                  "name": self.name})
        self._reader.start()
        self.state = "starting"

    def wait_ready(self, timeout=180.0):
        """Block until the child compiled + verified its model (the
        persistent compile cache is what makes respawn/restore fast)."""
        self._ready.result(timeout)
        self.state = "live"
        return self

    # -- RPC plumbing ------------------------------------------------------
    def _dead_error(self, why=None):
        return ReplicaDead(
            f"replica {self.name}#{self.index} is dead"
            f"{' (' + why + ')' if why else ''} — retry on a surviving "
            "replica")

    def _call(self, msg) -> RemoteFuture:
        mid = next(self._ids)
        fut = RemoteFuture(self, mid)
        with self._lock:
            if self._dead:
                raise self._dead_error()
            self._pending[mid] = fut
        try:
            with self._wlock:
                write_msg(self._proc.stdin, dict(msg, id=mid))
        except Exception as e:
            self._mark_dead(f"pipe write failed: {type(e).__name__}")
            raise self._dead_error("pipe write failed") from None
        return fut

    def _read_loop(self):  # mxtpu-lint: hot-path
        try:
            while True:
                msg = read_msg(self._proc.stdout)
                mid = msg.get("id")
                if "depth" in msg:
                    self.note_depth(msg["depth"])
                with self._lock:
                    fut = self._pending.pop(mid, None)
                if fut is None:
                    continue
                if msg.get("ok"):
                    fut.finish(result=msg.get("result"),
                               version=msg.get("version"))
                else:
                    fut.finish(error=rebuild_error(msg.get("etype"),
                                                   msg.get("emsg")))
        except Exception:
            pass
        self._mark_dead("child pipe closed")

    def _mark_dead(self, why):
        with self._lock:
            if self._closing:
                # graceful close/pause: EOF is expected, pending is empty
                self._dead = True
                return
            already = self._dead
            self._dead = True
            pending, self._pending = self._pending, {}
        if already:
            return
        self.state = "dead"
        if self.death_mono is None:
            self.death_mono = time.monotonic()
        err = self._dead_error(why)
        for fut in pending.values():
            fut.finish(error=err)

    # -- replica surface ---------------------------------------------------
    def submit(self, x, **kwargs):
        self._chaos_point()
        arr = x.asnumpy() if hasattr(x, "asnumpy") else _np.asarray(x)
        return self._call({"op": "submit", "x": arr,
                           "kwargs": {k: v for k, v in kwargs.items()
                                      if v is not None}})

    def ping(self, timeout=2.0) -> dict:
        info = self._call({"op": "ping"}).result(timeout)
        self.note_depth(int(info.get("depth", 0)))
        return info

    def live_version(self):
        try:
            return self.ping().get("version")
        except (ServingError, TimeoutError):
            return None

    def swap(self, spec, timeout=180.0):
        """Staged swap inside the child (its repository stages,
        verifies, flips); returns the new live version."""
        spec = normalize_spec(spec)
        version = self._call({"op": "swap", "spec": spec}).result(timeout)
        self.spec = spec
        return version

    def stats(self) -> dict:
        return self.ping().get("stats") or {}

    def pause(self):
        """Warm-pool parking for a process replica: the child exits
        (graceful drain) and only the spec is kept — ``resume()``
        respawns through the persistent compile cache."""
        self._shutdown(graceful=True)
        self.state = "warm"

    def resume(self, timeout=180.0):
        self._spawn()
        return self.wait_ready(timeout)

    def kill(self):
        """Real SIGKILL — the chaos ``kill_replica`` actuation."""
        if self.death_mono is None:
            self.death_mono = time.monotonic()
        self.state = "dead"
        try:
            self._proc.kill()
        except Exception:
            pass

    def _shutdown(self, graceful=True):
        with self._lock:
            self._closing = True
        if graceful:
            try:
                self._call({"op": "close"}).result(10.0)
            except Exception:
                pass
        try:
            self._proc.wait(timeout=10.0)
        except Exception:
            try:
                self._proc.kill()
            except Exception:
                pass

    def close(self):
        self._shutdown(graceful=True)
        if self.state != "dead":
            self.state = "closed"

    def __del__(self):
        try:
            if getattr(self, "_proc", None) is not None \
                    and self._proc.poll() is None:
                self._proc.kill()
        except Exception:
            pass
