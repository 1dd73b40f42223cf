"""Async training checkpoints (``MXTPU_CHECKPOINT=<dir>[:every_n]``).

What ``Block.save_parameters`` misses is exactly what a preemption
loses: the donated ``_fused_states`` optimizer pytree, AMP master
weights and loss-scaler counters, per-param update counts, the RNG key,
and the input-pipeline position. A :class:`CheckpointManager` snapshots
the COMPLETE training state at a step boundary and writes it from a
background thread, so the training loop pays only for the on-device
copy dispatch (donation-safe fresh buffers) — the host transfer,
checksumming and disk I/O all overlap the following steps.

Commit protocol (crash-safe by construction):

- everything is written into ``<dir>/.tmp-step_<n>-<pid>/`` first:
  ``data.bin`` (concatenated raw tensors) then ``MANIFEST.json``
  (shape/dtype/offset/crc32 per tensor + the scalar extras), fsynced;
- the tmp dir is ``os.replace``-renamed to ``<dir>/step_<n>/`` — a
  checkpoint either exists completely or not at all;
- ``<dir>/LATEST`` is updated by atomic rename afterwards (advisory:
  discovery falls back to the highest committed ``step_*``);
- a retention policy (``keep``, default 3) trims the oldest committed
  steps after each commit.

``tools/verify_checkpoint.py`` (and :func:`verify` here) re-checksums
any checkpoint dir. On SIGTERM one FINAL checkpoint is written
synchronously before the process dies — chained deterministically with
the crash flight recorder: checkpoint first, flight bundle second,
original disposition re-raised last (observability/flight.py pre-dump
hooks). Resume (including onto a CHANGED device count) lives in
:mod:`mxnet_tpu.resilience.resume`.
"""

from __future__ import annotations

import json
import logging
import os
import queue
import shutil
import signal
import sys
import threading
import time
import zlib

import numpy as _np

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..base import MXNetError, getenv

_logger = logging.getLogger("mxnet_tpu.checkpoint")

FORMAT = "mxtpu-checkpoint-v1"
MANIFEST = "MANIFEST.json"
PAYLOAD = "data.bin"
LATEST = "LATEST"

_KEEP_DEFAULT = 3


# ---------------------------------------------------------------------------
# state flattening: ANY optimizer-state shape (fused flat tuples, eager
# (master, (m, v)) nests, None) round-trips through (structure, tensors)
# ---------------------------------------------------------------------------

def _flatten_state(obj, key_prefix, sink, _counter=None):
    """Recursively flatten tuples/lists/NDArrays/raw arrays/None into a
    JSON structure descriptor; array leaves land in ``sink`` under
    ``<key_prefix>::<n>`` and are referenced by key. The leaf counter
    is explicit (deriving it by scanning ``sink`` made a snapshot
    O(total_keys) per leaf on the training thread)."""
    if _counter is None:
        import itertools

        _counter = itertools.count()
    if obj is None:
        return None
    if isinstance(obj, (tuple, list)):
        return [_flatten_state(o, key_prefix, sink, _counter)
                for o in obj]
    if isinstance(obj, (int, float)):
        return {"__v": obj}
    raw = obj.data if hasattr(obj, "data") and not callable(obj.data) \
        else obj
    key = f"{key_prefix}::{next(_counter)}"
    sink[key] = raw
    return {"__t": key}


def _unflatten_state(desc, tensors, wrap=None):
    """Inverse of :func:`_flatten_state`. ``wrap`` converts each array
    leaf (e.g. to NDArray for eager states); default leaves jnp arrays."""
    if desc is None:
        return None
    if isinstance(desc, list):
        return tuple(_unflatten_state(d, tensors, wrap) for d in desc)
    if "__v" in desc:
        return desc["__v"]
    raw = tensors[desc["__t"]]
    return wrap(raw) if wrap is not None else raw


# one dispatch snapshots the whole tensor set into FRESH buffers — the
# fused/superstep executables donate their inputs, so holding bare
# references across the next step would read deleted arrays
@jax.jit
def _copy_leaves(leaves):
    return [jnp.copy(l) for l in leaves]


def _dtype_name(dt):
    return str(jnp.dtype(dt))


def _np_dtype(name):
    if name == "bfloat16":
        import ml_dtypes

        return _np.dtype(ml_dtypes.bfloat16)
    return _np.dtype(name)


# ---------------------------------------------------------------------------
# snapshot assembly
# ---------------------------------------------------------------------------

def snapshot_trainer(trainer, net=None, step=None, cursor=None):
    """Capture the complete state of a Gluon training loop as
    ``(tensors, extras)``: params, per-param optimizer state (fused
    pytrees AND eager states, whichever path owns each param), AMP
    loss-scaler counters, update counts, and the global RNG key. The
    tensor values are device-copied in ONE dispatch (donation-safe) —
    call this at a step boundary; it never syncs to host itself."""
    from .. import random as _random
    from ..gluon.trainer import Trainer

    if not isinstance(trainer, Trainer):
        raise MXNetError("snapshot_trainer needs a gluon.Trainer")
    tensors = {}
    extras = {"kind": "trainer", "opt_kind": {}, "eager_structs": {},
              "fused_leaves": {}}
    # STRUCTURAL keys when the net is known (the save_parameters naming
    # scheme): global prefixed names (dense0_weight) differ between two
    # models built in one process, but "0.weight" survives any rebuild.
    struct = {}
    if net is not None:
        for sname, p in net._collect_params_with_prefix().items():
            struct.setdefault(id(p), sname)

    def keyof(p):
        return struct.get(id(p), p.name)

    params = list(trainer._params)
    if net is not None:
        # prefer the net's full param set (covers grad_req="null"
        # aux params a partial trainer might not hold)
        seen = {id(p) for p in params}
        for _, p in sorted(net.collect_params().items()):
            if id(p) not in seen:
                params.append(p)
    for p in params:
        if p._data is None:
            continue
        tensors[f"param::{keyof(p)}"] = p.data().data
    for p in trainer._params:
        key = keyof(p)
        st = trainer._fused_states.get(p.name)
        if st is not None:
            extras["opt_kind"][key] = "fused"
            extras["fused_leaves"][key] = len(st)  # 0 is valid (plain sgd)
            for i, leaf in enumerate(st):
                tensors[f"fused::{key}::{i}"] = leaf
            continue
        est = getattr(p, "_opt_state", None)
        if est is not None:
            extras["opt_kind"][key] = "eager"
            extras["eager_structs"][key] = _flatten_state(
                est, f"eager::{key}", tensors)
    o = trainer._optimizer
    extras["update_counts"] = {str(k): int(v)
                               for k, v in o._index_update_count.items()}
    extras["num_update"] = int(o.num_update)
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is not None:
        extras["scaler"] = {"factor": scaler._factor,
                            "window": scaler._window}
        tensors["scaler::scale"] = scaler._scale_arr
        tensors["scaler::unskipped"] = scaler._unskipped_arr
        tensors["scaler::overflow_total"] = scaler._overflow_total_arr
    else:
        extras["scaler"] = None
    key = _random._S.key
    if key is not None:
        tensors["rng::key"] = key
    if step is not None:
        extras["step"] = int(step)
    if cursor is not None:
        # int = delivered-batch count (resume.skip_batches); dict = a
        # structured streaming cursor (stream.StreamReader.state()) —
        # JSON-serializable, rides the extras sidecar verbatim
        extras["cursor"] = dict(cursor) if isinstance(cursor, dict) \
            else int(cursor)
    # ONE dispatch: donation-safe copies of every leaf
    keys = sorted(tensors)
    copies = _copy_leaves([jnp.asarray(tensors[k]) for k in keys])
    out = {}
    for k, c in zip(keys, copies):
        try:  # start the device->host transfer now, materialize later
            c.copy_to_host_async()
        except Exception:
            pass
        out[k] = c
    return out, extras


# ---------------------------------------------------------------------------
# directory protocol
# ---------------------------------------------------------------------------

def _step_dirname(step):
    return f"step_{int(step):010d}"


def _committed_steps(directory):
    """Sorted committed step numbers (a step counts only with a
    manifest — half-written tmp dirs never match)."""
    steps = []
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    for n in names:
        if n.startswith("step_") and os.path.exists(
                os.path.join(directory, n, MANIFEST)):
            try:
                steps.append(int(n[5:]))
            except ValueError:
                continue
    return sorted(steps)


def latest_checkpoint(directory):
    """Path of the newest committed checkpoint under ``directory`` (the
    LATEST pointer when valid, else the highest committed step dir), or
    None."""
    try:
        with open(os.path.join(directory, LATEST)) as f:
            name = f.read().strip()
        if name and os.path.exists(os.path.join(directory, name, MANIFEST)):
            return os.path.join(directory, name)
    except OSError:
        pass
    steps = _committed_steps(directory)
    if not steps:
        return None
    return os.path.join(directory, _step_dirname(steps[-1]))


def _atomic_write(path, data, binary=False):
    def write(tmp):
        with open(tmp, "wb" if binary else "w") as f:
            f.write(data)

    atomic_replace(path, write)


_TMP_SEQ = [0]  # per-process uniquifier: the SIGTERM final save and a
# still-in-flight writer may build the SAME step concurrently — (step,
# pid) alone would collide their tmp dirs (one rmtree'ing the other's
# half-written files). RLock, same reason as the manager's _cv: the
# SIGTERM handler runs ON the main thread and may interrupt a frame
# already inside this lock
_TMP_SEQ_LOCK = threading.RLock()


def _next_seq():
    with _TMP_SEQ_LOCK:
        _TMP_SEQ[0] += 1
        return _TMP_SEQ[0]


# ---------------------------------------------------------------------------
# step-boundary critical sections: SIGTERM arriving MID-STEP (e.g. while
# a K-iteration superstep scan executes, or between the dispatch return
# and the param write-back loop) must not snapshot a half-applied carry.
# Trainer.step / Superstep.step bracket their state-mutating window with
# step_critical_section(); the SIGTERM handler defers the final save to
# the section's exit — the last COMPLETED K-boundary — where params,
# fused states, update counts and the manager's step counter are
# mutually consistent. Signal handlers and the bracketing code both run
# on the main thread, so a plain counter suffices.
# ---------------------------------------------------------------------------

_CRITICAL = [0]
_DEFERRED = []


def in_step_critical():
    return _CRITICAL[0] > 0


class _StepCritical:
    def __enter__(self):
        _CRITICAL[0] += 1
        return self

    def __exit__(self, *exc):
        _CRITICAL[0] -= 1
        if _CRITICAL[0] == 0 and _DEFERRED:
            # deferred handlers run on EXCEPTION exits too: dropping
            # the signal would leave the process alive after a SIGTERM
            # it never saw. Consistency holds because the step's error
            # paths roll their bookkeeping back before re-raising (the
            # superstep rewinds its K-step count advance), so the
            # deferred final save still snapshots the last completed
            # boundary.
            pending = list(_DEFERRED)
            del _DEFERRED[:]
            for fn, args in pending:
                fn(*args)
        return False


def step_critical_section():
    """Mark the code between a train step's first state mutation and its
    last bookkeeping write as uninterruptible for the SIGTERM final
    checkpoint: a handler firing inside (a preemption landing mid-scan)
    is deferred to the section's exit, so the final save always commits
    at a completed step/K-boundary — never a half-applied carry.
    Reentrant (a superstep's single-step fallback nests Trainer.step)."""
    return _StepCritical()


_COMMIT_BARRIER_SEQ = [0]


def default_commit_barrier():
    """The automated multi-host commit-coordination barrier: a callable
    every rank invokes around the rank-0 manifest/commit of a sharded
    checkpoint (``resume.save_spmd_checkpoint`` uses it whenever the
    caller passes no explicit barrier on a multi-process mesh).

    Single-process: a no-op. Multi-process: one
    ``multihost_utils.sync_global_devices`` per call, under the same
    loud watchdog timeout + no-retry-on-timeout discipline as
    ``kvstore.barrier`` (``MXTPU_BARRIER_TIMEOUT_S``) — a preempted
    peer turns into a diagnosable crash at the commit point, never an
    indefinite hang with a half-staged checkpoint. Tags are
    process-globally unique so nested/successive saves never alias."""
    if jax.process_count() == 1:
        return lambda: None

    from ..kvstore.dist import _barrier_timeout_s, _call_with_timeout

    def barrier():
        from jax.experimental import multihost_utils

        _COMMIT_BARRIER_SEQ[0] += 1
        tag = f"mxtpu_ckpt_commit_{_COMMIT_BARRIER_SEQ[0]}"
        _call_with_timeout(
            lambda: multihost_utils.sync_global_devices(tag),
            _barrier_timeout_s(), f"checkpoint commit barrier {tag!r}")

    return barrier


def atomic_replace(path, write_fn):
    """Crash-safe file replacement: ``write_fn(tmp_path)`` produces the
    content, which is fsynced and renamed over ``path`` — unique tmp
    name per CALL (concurrent savers of one path never clobber each
    other's half-written file). THE commit primitive shared by the
    checkpoint manifests/LATEST pointer and ``Block.save_parameters``."""
    tmp = f"{path}.tmp{os.getpid()}-{_next_seq()}"
    try:
        write_fn(tmp)
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def write_checkpoint(directory, tensors, extras, step, reason="manual",
                     extra_files=None):
    """Serialize one snapshot into ``<directory>/step_<step>/`` with the
    atomic tmp-dir + rename-commit protocol. ``tensors`` maps keys to
    (device or host) arrays; ``extra_files`` maps relative names to
    already-written absolute paths to move in (SPMD shard files).
    Returns the committed directory path."""
    t0 = time.perf_counter()
    os.makedirs(directory, exist_ok=True)
    seq = _next_seq()
    tmp = os.path.join(
        directory, f".tmp-{_step_dirname(step)}-{os.getpid()}-{seq}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    manifest = {"format": FORMAT, "step": int(step),
                "time_unix": time.time(), "reason": reason,
                "payload": PAYLOAD, "tensors": {}, "extras": extras,
                "files": {}}
    try:
        manifest["world"] = {"backend": jax.default_backend(),
                             "process_count": jax.process_count(),
                             "process_index": jax.process_index(),
                             "device_count": jax.device_count()}
    except Exception:
        manifest["world"] = None
    nbytes_total = 0
    with open(os.path.join(tmp, PAYLOAD), "wb") as f:
        offset = 0
        for key in sorted(tensors):
            # NB: no ascontiguousarray — it promotes 0-d scalars (the
            # adam/lamb t leaf) to shape (1,), which would fail the
            # restore-side shape match; tobytes() is C-order regardless
            host = _np.asarray(tensors[key])
            buf = host.tobytes()
            manifest["tensors"][key] = {
                "shape": list(host.shape),
                "dtype": _dtype_name(host.dtype),
                "offset": offset, "nbytes": len(buf),
                "crc32": zlib.crc32(buf) & 0xFFFFFFFF}
            f.write(buf)
            offset += len(buf)
        nbytes_total = offset
        f.flush()
        os.fsync(f.fileno())
    manifest["payload_bytes"] = nbytes_total
    for rel, src in (extra_files or {}).items():
        dst = os.path.join(tmp, rel)
        shutil.move(src, dst)
        # streamed CRC: shard files can be multi-GB and the commit
        # moment is exactly when host memory is scarcest
        crc, n = 0, 0
        with open(dst, "rb") as f:
            while True:
                chunk = f.read(1 << 24)
                if not chunk:
                    break
                crc = zlib.crc32(chunk, crc)
                n += len(chunk)
        manifest["files"][rel] = {"nbytes": n,
                                  "crc32": crc & 0xFFFFFFFF}
        nbytes_total += n
    _atomic_write(os.path.join(tmp, MANIFEST),
                  json.dumps(manifest, indent=1) + "\n")
    final = os.path.join(directory, _step_dirname(step))
    old = None
    if os.path.exists(final):
        # re-checkpoint of the same step: move the existing commit
        # ASIDE (atomic rename) rather than rmtree'ing it first — a
        # kill between a slow delete and the replace would leave the
        # step with no checkpoint at all; discovery ignores dot-dirs,
        # so the window without a valid step_<n> is one rename wide
        old = os.path.join(directory,
                           f".old-{_step_dirname(step)}-{os.getpid()}-{seq}")
        try:
            os.replace(final, old)
        except OSError:
            old = None
    os.replace(tmp, final)
    if old is not None:
        shutil.rmtree(old, ignore_errors=True)
    # LATEST advances MONOTONICALLY: an out-of-order commit (a slow
    # background write landing after the SIGTERM final save of a LATER
    # step) must not point resume at the older recovery point
    cur = -1
    try:
        with open(os.path.join(directory, LATEST)) as f:
            cur = int(f.read().strip()[5:])
    except (OSError, ValueError):
        pass
    if int(step) >= cur:
        _atomic_write(os.path.join(directory, LATEST), _step_dirname(step))
    dt = time.perf_counter() - t0
    if _obs.ENABLED:
        _obs.CHECKPOINT_TOTAL.inc(1, reason=reason)
        _obs.CHECKPOINT_BYTES_TOTAL.inc(nbytes_total)
        _obs.CHECKPOINT_SECONDS.observe(dt)
        _obs.CHECKPOINT_LAST_STEP.set(float(step))
        _obs.tracer().record("checkpoint.commit", cat="resilience",
                             ts=t0, dur=dt,
                             args={"step": int(step), "reason": reason,
                                   "bytes": nbytes_total})
    _logger.info("checkpoint: committed %s (%d bytes, %.3fs, %s)",
                 final, nbytes_total, dt, reason)
    return final


def read_checkpoint(path, verify_checksums=True):
    """Load a committed checkpoint dir -> ``(manifest, tensors)`` with
    tensors as host numpy arrays (bf16 via ml_dtypes). ``path`` may be
    the checkpoint root (the latest committed step is used) or one
    ``step_*`` dir."""
    if not os.path.exists(os.path.join(path, MANIFEST)):
        latest = latest_checkpoint(path)
        if latest is None:
            raise MXNetError(f"no committed checkpoint under {path!r}")
        path = latest
    with open(os.path.join(path, MANIFEST)) as f:
        manifest = json.load(f)
    if manifest.get("format") != FORMAT:
        raise MXNetError(
            f"{path}: unknown checkpoint format {manifest.get('format')!r}")
    tensors = {}
    with open(os.path.join(path, manifest["payload"]), "rb") as f:
        blob = f.read()
    view = memoryview(blob)
    for key, meta in manifest["tensors"].items():
        # zero-copy views into the one payload buffer — slicing bytes
        # per tensor would transiently double the checkpoint's host
        # footprint at exactly the resume moment (jnp.asarray copies
        # to device later anyway)
        end = meta["offset"] + meta["nbytes"]
        if verify_checksums and \
                (zlib.crc32(view[meta["offset"]:end]) & 0xFFFFFFFF) \
                != meta["crc32"]:
            raise MXNetError(
                f"{path}: checksum mismatch for tensor {key!r} — "
                "checkpoint is corrupt")
        dt = _np_dtype(meta["dtype"])
        tensors[key] = _np.frombuffer(
            blob, dtype=dt, count=meta["nbytes"] // dt.itemsize,
            offset=meta["offset"]).reshape(meta["shape"])
    manifest["_path"] = path
    return manifest, tensors


def verify(path):
    """Integrity/completeness lint of a checkpoint dir. Returns a list
    of problem strings (empty = verified). Never raises on corrupt
    input — the linter reports, the loader enforces."""
    problems = []
    if not os.path.exists(os.path.join(path, MANIFEST)):
        latest = latest_checkpoint(path)
        if latest is None:
            return [f"{path}: no committed checkpoint "
                    f"(no step_*/{MANIFEST})"]
        path = latest
    try:
        with open(os.path.join(path, MANIFEST)) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable manifest: {e}"]
    if manifest.get("format") != FORMAT:
        problems.append(f"unknown format {manifest.get('format')!r}")
    payload = os.path.join(path, manifest.get("payload", PAYLOAD))
    try:
        with open(payload, "rb") as f:
            blob = f.read()
    except OSError as e:
        return problems + [f"payload unreadable: {e}"]
    expect = manifest.get("payload_bytes")
    if expect is not None and expect != len(blob):
        problems.append(
            f"payload is {len(blob)} bytes, manifest says {expect}")
    view = memoryview(blob)
    for key, meta in manifest.get("tensors", {}).items():
        end = meta["offset"] + meta["nbytes"]
        if end > len(blob):
            problems.append(f"tensor {key!r} extends past payload end")
            continue
        if (zlib.crc32(view[meta["offset"]:end]) & 0xFFFFFFFF) \
                != meta["crc32"]:
            problems.append(f"tensor {key!r} checksum mismatch")
        size = 1
        for d in meta["shape"]:
            size *= d
        try:
            if size * _np_dtype(meta["dtype"]).itemsize != meta["nbytes"]:
                problems.append(
                    f"tensor {key!r} shape/dtype disagree with nbytes")
        except TypeError:
            problems.append(f"tensor {key!r} has unknown dtype "
                            f"{meta['dtype']!r}")
    for rel, meta in manifest.get("files", {}).items():
        fp = os.path.join(path, rel)
        try:
            with open(fp, "rb") as f:
                fblob = f.read()
        except OSError as e:
            problems.append(f"file {rel!r} unreadable: {e}")
            continue
        if len(fblob) != meta["nbytes"]:
            problems.append(f"file {rel!r} is {len(fblob)} bytes, "
                            f"manifest says {meta['nbytes']}")
        elif (zlib.crc32(fblob) & 0xFFFFFFFF) != meta["crc32"]:
            problems.append(f"file {rel!r} checksum mismatch")
    # completeness: a trainer checkpoint must carry every opt-state
    # leaf the manifest declares (a zero-leaf state — plain sgd — is
    # complete by definition)
    extras = manifest.get("extras", {})
    leaves = extras.get("fused_leaves", {})
    have = manifest.get("tensors", {})
    for name, kind in extras.get("opt_kind", {}).items():
        if kind == "fused":
            n = leaves.get(name)
            want = [f"fused::{name}::{i}" for i in range(n)] \
                if n is not None else [f"fused::{name}::0"]
        elif kind == "eager":
            # every array leaf the structure descriptor references must
            # exist — a linter that certifies what the loader then
            # KeyErrors on is worse than none
            want = []

            def _refs(desc, out):
                if isinstance(desc, list):
                    for d in desc:
                        _refs(d, out)
                elif isinstance(desc, dict) and "__t" in desc:
                    out.append(desc["__t"])

            _refs(extras.get("eager_structs", {}).get(name), want)
        else:
            continue
        for key in want:
            if key not in have:
                problems.append(
                    f"opt state for {name!r} declared {kind} but "
                    f"tensor {key!r} is missing")
    return [f"{path}: {p}" for p in problems]


DESCRIPTOR_FORMAT = "mxtpu-snapshot-v1"


def verify_descriptor(desc):
    """Integrity/completeness lint of an IN-MEMORY snapshot descriptor
    (``resilience.elastic.snapshot_descriptor`` — the record a runtime
    resize hands over). Same contract as :func:`verify`: a list of
    problem strings, empty = verified. The payload lives in memory, so
    the checks are manifest self-consistency (shape x dtype vs nbytes,
    CRC presence) and completeness (every declared param and optimizer
    leaf has at least one chunk) — not byte re-checksums."""
    if not isinstance(desc, dict):
        return [f"descriptor is {type(desc).__name__}, not a dict"]
    if desc.get("format") != DESCRIPTOR_FORMAT:
        return [f"unknown snapshot format {desc.get('format')!r}"]
    problems = []
    tensors = desc.get("tensors", {})
    if not tensors:
        problems.append("descriptor lists no tensors")
    keys = set()
    for k, meta in tensors.items():
        name = k.rpartition("|")[0] or k
        keys.add(name)
        size = 1
        for d in meta.get("shape", []):
            size *= int(d)
        try:
            itemsize = _np_dtype(meta.get("dtype")).itemsize
        except (TypeError, ValueError, ImportError):
            problems.append(
                f"tensor {k!r} has unknown dtype {meta.get('dtype')!r}")
            continue
        if size * itemsize != meta.get("nbytes"):
            problems.append(
                f"tensor {k!r} shape/dtype disagree with nbytes")
        if not isinstance(meta.get("crc32"), int):
            problems.append(f"tensor {k!r} missing crc32")
    extras = desc.get("extras", {})
    for name in extras.get("param_names", []):
        if f"param::{name}" not in keys:
            problems.append(f"param::{name} declared but has no chunk")
    for name, n in extras.get("opt_leaves", {}).items():
        for i in range(int(n)):
            if f"opt::{name}::{i}" not in keys:
                problems.append(
                    f"opt state leaf opt::{name}::{i} declared but "
                    "has no chunk")
    return problems


# ---------------------------------------------------------------------------
# the manager
# ---------------------------------------------------------------------------

class CheckpointManager:
    """Interval-driven async checkpointing for a Gluon training loop.

    >>> mgr = CheckpointManager("/ckpt", every_n_steps=100, net=net,
    ...                         trainer=trainer)
    >>> mgr.attach(trainer)        # Trainer.step / Superstep.step tick it
    ... train ...
    >>> mgr.close()                # flush + join the writer

    Or let the env drive it: ``MXTPU_CHECKPOINT=<dir>[:every_n]`` +
    ``resilience.maybe_checkpointing(net, trainer)``.

    The step hook snapshots on the TRAINING thread (one copy dispatch)
    and hands the host transfer + write to a daemon writer thread; if a
    write is still in flight when the next interval arrives, the new
    snapshot replaces the queued one (latest-wins — a slow disk degrades
    cadence, never correctness). A SIGTERM writes one final checkpoint
    synchronously, ordered BEFORE the flight-recorder bundle.
    """

    #: lock protocol, machine-checked by mxtpu-lint's thread-guard rule
    #: (the PR-8 flush() race was exactly an off-lock mutation of this
    #: accounting): pending-snapshot count only moves under the condvar.
    _GUARDED_BY = {"_pending": "_cv"}

    def __init__(self, directory, every_n_steps=100, keep=_KEEP_DEFAULT,
                 net=None, trainer=None, ring=None, install_sigterm=True):
        self.directory = str(directory)
        self.every_n_steps = max(1, int(every_n_steps))
        self.keep = max(1, int(keep))
        self._net = net
        self._trainer = trainer
        self._ring = ring
        self._step = 0
        self._last_saved = None
        self.commits = 0  # lifetime successful commits (retention may
        self.last_error = None  # keep fewer dirs than this on disk)
        self._queue = queue.Queue(maxsize=1)
        # pending-snapshot accounting under one condition variable: an
        # Event-based idle flag raced (writer could observe an empty
        # queue and signal idle BETWEEN a producer's clear() and its
        # put(), letting flush() return with a snapshot still queued).
        # RLock-backed: the SIGTERM final save runs ON the main thread
        # and may interrupt a frame already inside this lock — a plain
        # Lock would deadlock the handler instead of checkpointing
        # (flush()'s bounded wait_for covers the interrupted-increment
        # edge: worst case one timeout, never a hang)
        self._cv = threading.Condition(threading.RLock())
        self._pending = 0
        self._closed = False
        self._sig_state = {"installed": False, "prev": None, "done": False}
        self._writer = threading.Thread(target=self._write_loop,
                                        name="mxtpu-checkpoint-writer",
                                        daemon=True)
        self._writer.start()
        if install_sigterm:
            self._install_sigterm()
        # drain + join at interpreter exit: a daemon writer caught
        # mid-np.asarray by runtime teardown aborts the whole process
        # (std::terminate in the backend) — close() is idempotent
        import atexit

        atexit.register(self.close)

    # -- step hook -------------------------------------------------------
    def attach(self, trainer=None):
        """Register on the trainer so ``Trainer.step`` / ``Superstep``
        tick this manager automatically. Returns self."""
        tr = trainer or self._trainer
        if tr is None:
            raise MXNetError("CheckpointManager.attach: no trainer")
        self._trainer = tr
        tr._ckpt_manager = self
        # hand the anomaly watchdog a save path: with
        # MXTPU_WATCHDOG_CHECKPOINT=1 a detector firing requests one
        # proactive async save (the recovery point moves BEFORE the
        # divergence kills the job)
        from ..observability import watchdog as _watchdog

        if _watchdog.ENABLED:
            _watchdog.attach_checkpoint_manager(self)
        return self

    def on_step(self, n=1, cursor=None):
        """Advance the step counter by ``n`` (a superstep passes its K);
        snapshot + enqueue when an interval boundary is crossed."""
        before = self._step
        self._step += int(n)
        if cursor is not None:
            self._cursor = cursor
        if self._step // self.every_n_steps > before // self.every_n_steps:
            if _obs.ENABLED:
                # the in-LOOP slice only (snapshot dispatch + writer
                # handoff) — the background write is never loop time;
                # the attribution plane charges this to ckpt_overhead
                t0 = time.perf_counter()
                self.save_async(reason="interval")
                _obs.record_ckpt_tick(time.perf_counter() - t0)
            else:
                self.save_async(reason="interval")
        return self._step

    @property
    def step(self):
        return self._step

    def restore_step(self, step):
        """Align the interval counter with a resumed run (call with
        ``ResumeReport.step`` after ``load_checkpoint``) so the next
        checkpoints land at the same global-step boundaries the dead
        process would have used."""
        self._step = int(step)
        return self

    @property
    def last_saved(self):
        """Directory of the most recently COMMITTED checkpoint."""
        return self._last_saved

    def _cursor_value(self, cursor=None):
        if cursor is not None:
            return cursor if isinstance(cursor, dict) else int(cursor)
        if self._ring is not None:
            c = getattr(self._ring, "cursor", None)
            if c is not None:
                return c if isinstance(c, dict) else int(c)
        return getattr(self, "_cursor", None)

    # -- save paths ------------------------------------------------------
    def _snapshot(self, cursor=None):
        if self._trainer is None:
            raise MXNetError("CheckpointManager: no trainer to snapshot")
        return snapshot_trainer(self._trainer, net=self._net,
                                step=self._step,
                                cursor=self._cursor_value(cursor))

    def save_async(self, reason="manual", cursor=None):
        """Snapshot now (one dispatch), write in the background."""
        if self._closed:
            return
        try:
            snap = (self._snapshot(cursor), self._step, reason)
        except Exception as e:
            self.last_error = e
            _logger.error("checkpoint snapshot failed: %s: %s",
                          type(e).__name__, e)
            if _obs.ENABLED:
                _obs.CHECKPOINT_ERRORS_TOTAL.inc()
            return
        with self._cv:
            self._pending += 1
        while True:  # latest-wins: drop a stale queued snapshot
            try:
                self._queue.put_nowait(snap)
                return
            except queue.Full:
                try:
                    dropped = self._queue.get_nowait()
                    if dropped is None:
                        # close()'s stop sentinel, not a snapshot: we
                        # are shutting down — hand it back so the
                        # writer still exits, and drop OUR snapshot
                        self._queue.put(dropped)
                        with self._cv:
                            self._pending -= 1
                            self._cv.notify_all()
                        return
                    with self._cv:  # the dropped one will never write
                        self._pending -= 1
                        self._cv.notify_all()
                    if _obs.ENABLED:
                        _obs.CHECKPOINT_DROPPED_TOTAL.inc()
                except queue.Empty:
                    continue

    def save_sync(self, reason="manual", cursor=None):
        """Snapshot and write NOW on the calling thread (after draining
        any in-flight async write). Returns the committed path."""
        self.flush()
        (tensors, extras), step, _ = (self._snapshot(cursor), self._step,
                                      reason)
        path = write_checkpoint(self.directory, tensors, extras, step,
                                reason=reason)
        self._last_saved = path
        self.commits += 1
        self._trim()
        return path

    def flush(self, timeout=60.0):
        """Block until the writer finishes everything queued. Returns
        True when drained, False on timeout (callers that VERIFY after
        flushing — the tests do — must check it; the SIGTERM final
        save proceeds regardless, protected by per-write unique tmp
        dirs and the monotonic LATEST pointer)."""
        with self._cv:
            return self._cv.wait_for(lambda: self._pending == 0, timeout)

    # -- writer thread ---------------------------------------------------
    def _write_loop(self):
        while True:
            item = self._queue.get()
            if item is None:
                with self._cv:
                    self._cv.notify_all()
                return
            (tensors, extras), step, reason = item
            try:
                self._last_saved = write_checkpoint(
                    self.directory, tensors, extras, step, reason=reason)
                self.commits += 1
                self._trim()
                self.last_error = None
            except Exception as e:  # a full disk must not kill training
                self.last_error = e
                _logger.error("checkpoint write failed: %s: %s",
                              type(e).__name__, e)
                if _obs.ENABLED:
                    _obs.CHECKPOINT_ERRORS_TOTAL.inc()
            finally:
                with self._cv:
                    self._pending -= 1
                    self._cv.notify_all()

    def _trim(self):
        steps = _committed_steps(self.directory)
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.directory, _step_dirname(s)),
                          ignore_errors=True)
        # sweep leftovers from CRASHED commits of other processes (this
        # process's own tmp dirs are transient by construction):
        # .tmp-*/.old-* dirs never count as checkpoints but would
        # accumulate across preemption cycles. Age-gated: a fresh tmp
        # dir may be another LIVE process's in-flight final save (the
        # dying predecessor sharing this dir during an overlap window)
        try:
            now = time.time()
            for n in os.listdir(self.directory):
                if not (n.startswith(".tmp-") or n.startswith(".old-")) \
                        or f"-{os.getpid()}-" in n:
                    continue
                p = os.path.join(self.directory, n)
                try:
                    if now - os.path.getmtime(p) > 3600:
                        shutil.rmtree(p, ignore_errors=True)
                except OSError:
                    pass
        except OSError:
            pass

    # -- SIGTERM final checkpoint ---------------------------------------
    def _final_save(self, reason="sigterm"):
        """One synchronous final checkpoint on the way down; idempotent
        per process death and never raises (a failed save must not mask
        the signal)."""
        if self._sig_state["done"] or self._closed:
            return
        self._sig_state["done"] = True
        try:
            self.save_sync(reason=reason)
        except Exception as e:  # pragma: no cover - last-breath path
            try:
                _logger.error("final checkpoint failed: %s: %s",
                              type(e).__name__, e)
            except Exception:
                pass

    def _install_sigterm(self):
        """Deterministic chaining with the crash flight recorder: the
        final checkpoint runs as a flight PRE-DUMP hook (checkpoint
        first, bundle second) whenever the recorder is installed —
        before or after us, either order — and an own SIGTERM handler
        covers the recorder-less case, chaining to whatever handler was
        there (the ``done`` flag keeps the save single-shot when both
        paths fire)."""
        from ..observability import flight

        flight.register_pre_dump(self._final_save, signals_only=True)
        if threading.current_thread() is not threading.main_thread():
            return  # signal hooks only land on the main thread
        try:
            if signal.getsignal(signal.SIGTERM) is signal.SIG_IGN:
                return
            prev = signal.signal(signal.SIGTERM, self._sigterm_handler)
            self._sig_state["installed"] = True
            if prev not in (signal.SIG_DFL, self._sigterm_handler):
                self._sig_state["prev"] = prev
        except (ValueError, OSError) as e:  # pragma: no cover
            _logger.warning("checkpoint: cannot hook SIGTERM: %s", e)

    def _sigterm_handler(self, signum, frame):
        if _CRITICAL[0] > 0:
            # mid-step (e.g. the signal landed while a superstep scan
            # executed and the handler ran between the dispatch return
            # and the write-back loop): committing NOW would snapshot a
            # half-applied carry — defer the whole handler (final save
            # + re-raise) to the step boundary
            _DEFERRED.append((self._sigterm_handler, (signum, None)))
            return
        self._final_save()
        prev = self._sig_state["prev"]
        if callable(prev):
            prev(signum, frame)
            return
        signal.signal(signum, signal.SIG_DFL)
        os.kill(os.getpid(), signum)

    def _uninstall_sigterm(self):
        from ..observability import flight

        flight.unregister_pre_dump(self._final_save)
        if self._sig_state["installed"]:
            try:
                if signal.getsignal(signal.SIGTERM) is self._sigterm_handler:
                    signal.signal(signal.SIGTERM,
                                  self._sig_state["prev"] or signal.SIG_DFL)
            except (ValueError, OSError):  # pragma: no cover
                pass
            self._sig_state["installed"] = False

    # -- lifecycle -------------------------------------------------------
    def close(self):
        """Flush queued writes, stop the writer, restore signal hooks."""
        if self._closed:
            return
        self._closed = True
        self._queue.put(None)
        self._writer.join(timeout=60.0)
        import atexit

        atexit.unregister(self.close)  # else atexit pins the manager
        # (and its net/trainer/params) for the life of the process
        self._uninstall_sigterm()
        if self._trainer is not None and \
                getattr(self._trainer, "_ckpt_manager", None) is self:
            self._trainer._ckpt_manager = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def parse_env(value=None):
    """``MXTPU_CHECKPOINT=<dir>[:every_n]`` -> ``(dir, every_n)`` or
    None. A trailing ``:N`` is the cadence; the dir itself may contain
    colons only on platforms where that is a terrible idea anyway."""
    v = value if value is not None else getenv("MXTPU_CHECKPOINT", None)
    if not v:
        return None
    v = str(v)
    every = 100
    if ":" in v:
        head, _, tail = v.rpartition(":")
        if tail.isdigit():
            v, every = head, int(tail)
    return v, max(1, every)


def maybe_checkpointing(net=None, trainer=None, ring=None):
    """Build + attach a :class:`CheckpointManager` from
    ``MXTPU_CHECKPOINT`` (returns None when unset). The idiomatic
    train-script call right after creating the Trainer::

        mgr = mx.resilience.maybe_checkpointing(net, trainer)
    """
    cfg = parse_env()
    if cfg is None:
        return None
    d, every = cfg
    keep = int(getenv("MXTPU_CHECKPOINT_KEEP", _KEEP_DEFAULT, dtype=int))
    mgr = CheckpointManager(d, every_n_steps=every, keep=keep, net=net,
                            trainer=trainer, ring=ring)
    if trainer is not None:
        mgr.attach(trainer)
    return mgr
