"""Cluster-scale streaming data plane (ROADMAP item 4, the unfinished
half of the elasticity work).

The compute fast paths (fused step, superstep, overlapped ZeRO, 4D
parallelism) all assume input arrives at line rate — but until now
input was whatever the user's Python iterator yielded, with its cursor
hidden inside iterator state. This module is the half MXNet solved in
C++ (SURVEY Data IO: ``ImageRecordIter2``/``PrefetcherIter`` threaded
decode/augment/prefetch off the Python thread), rebuilt TPU-native:

- :class:`ShardIndex` — one RecordIO pack or webdataset-style tar
  shard with an O(1) per-record byte index (native
  ``MXTPURecordIOScanIndex`` fast scan when ``libmxtpu.so`` is
  available, pure-Python scan / ``.idx`` sidecar / tar-member walk
  otherwise).
- :class:`GlobalOrder` — the deterministic epoch-scale sample order:
  shard-level shuffle composed with block **window shuffle**, both
  derived purely from ``(seed, epoch)`` so ANY position in the
  permuted sequence is computable in O(1) without materializing an
  epoch-sized permutation (datasets that don't fit in memory shuffle
  at window granularity; ``window=0`` keeps shard order).
- :class:`StreamReader` — the sharded, resumable, line-rate reader:
  a read-ahead thread streams raw records from (possibly slow,
  latency-emulated) storage under bounded backpressure, a
  multi-threaded decode pool turns them into samples off the train
  thread, and a sequence-numbered reorder stage re-emits batches in
  the exact deterministic global order. Feed it to
  :class:`~.prefetcher.DevicePrefetcher` / ``SuperstepRing`` for the
  device-staging leg; host work is decode only — augmentation belongs
  on device via :func:`device_augment` (crop/flip/normalize inside
  the compiled step).
- **Deterministic global cursor** — ``state()`` is a plain dict
  ``(seed, base_batch, steps, world, rank, batch_size, ...)`` from
  which every future sample is derivable; it checkpoints through the
  PR-8 manager (``CheckpointManager`` accepts structured cursors) and
  re-partitions across ranks on a PR-11 elastic resize
  (:meth:`StreamReader.repartition`) without skipping or replaying a
  single sample.

Partitioning contract: the global sample sequence is chunked into
batches of ``batch_size``; at partition step ``t`` rank ``r`` of
``world`` consumes global batch ``base + t*world + r``. A resize at a
step boundary (all ranks at equal ``t``) rebases
``base += t * world`` and continues under the new ``(world', rank')``
— the union of all ranks' batches remains exactly the uninterrupted
global sequence. See docs/performance.md "Streaming input".
"""

from __future__ import annotations

import ctypes
import io
import os
import random
import struct
import tarfile
import threading
import time

import numpy as _np

from ... import observability as _obs
from ..._native import get_lib
from ...base import MXNetError, getenv
from ...recordio import _LEN_MASK, _MAGIC, IRHeader, unpack

__all__ = [
    "ShardIndex", "ShardSet", "GlobalOrder", "StreamReader",
    "device_augment", "write_recordio_shards", "decode_threads",
    "readahead_records", "emulated_latency_ms", "shuffle_window",
]

CURSOR_VERSION = 1


# -- knobs (docs/env_vars.md, machine-enforced) ---------------------------

def decode_threads() -> int:
    """``MXTPU_STREAM_DECODE_THREADS`` (default 4): decode/augment pool
    width. Decode never runs on the train thread regardless; this is
    how many records decode concurrently."""
    return max(1, int(getenv("MXTPU_STREAM_DECODE_THREADS", 4,
                             dtype=int)))


def readahead_records() -> int:
    """``MXTPU_STREAM_READAHEAD`` (default 128): bounded read-ahead in
    RECORDS — the raw-bytes staging queue and the decoded reorder
    buffer are each capped at this depth (backpressure against slow
    consumers; read-ahead against slow storage)."""
    return max(2, int(getenv("MXTPU_STREAM_READAHEAD", 128, dtype=int)))


def emulated_latency_ms() -> float:
    """``MXTPU_STREAM_LATENCY_MS`` (default 0): emulated slow-storage
    latency added to every shard read op — the test/chaos knob that
    turns local files into 'remote object storage' so prefetch-ahead
    and backpressure are measurable without a network."""
    return max(0.0, float(getenv("MXTPU_STREAM_LATENCY_MS", 0.0,
                                 dtype=float)))


def shuffle_window() -> int:
    """``MXTPU_STREAM_WINDOW`` (default 0 = shard order): default
    window-shuffle size in records when ``StreamReader(window=None)``.
    Epoch-scale datasets shuffle at this granularity without an
    epoch-sized permutation in memory."""
    return max(0, int(getenv("MXTPU_STREAM_WINDOW", 0, dtype=int)))


# -- shard index ----------------------------------------------------------

def _python_scan_recordio(path):
    """Pure-Python offset scan (the no-native fallback): hop over
    payloads header-by-header."""
    offsets = []
    with open(path, "rb") as f:
        while True:
            pos = f.tell()
            hdr = f.read(8)
            if not hdr:
                break
            if len(hdr) < 8:
                raise MXNetError(f"{path}: truncated RecordIO header")
            magic, lrec = struct.unpack("<II", hdr)
            if magic != _MAGIC:
                raise MXNetError(
                    f"{path}: invalid RecordIO magic {magic:#x}")
            length = lrec & _LEN_MASK
            f.seek(length + ((4 - (length % 4)) % 4), io.SEEK_CUR)
            offsets.append(pos)
    return _np.asarray(offsets, dtype=_np.uint64)


def _native_scan_recordio(path):
    """Native index scan: one call to size, one to fill (both are pure
    fseeko hops in C — ~100x the Python scan on large packs)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "MXTPURecordIOScanIndex"):
        return None
    n = lib.MXTPURecordIOScanIndex(path.encode(), None, 0)
    if n < 0:
        raise MXNetError(
            f"{path}: {lib.MXTPUGetLastError().decode()}")
    offsets = _np.zeros(int(n), dtype=_np.uint64)
    if n:
        buf = offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
        n2 = lib.MXTPURecordIOScanIndex(path.encode(), buf, int(n))
        if n2 != n:
            raise MXNetError(f"{path}: index scan changed size "
                             f"({n} -> {n2}) — file being written?")
    return offsets


class ShardIndex:
    """One shard with an O(1) per-record byte index.

    Two layouts:

    - ``kind="recordio"``: a RecordIO pack (magic ``0xced7230a``);
      the index is the byte offset of every record header, built by
      the native scan, loaded from a ``.idx`` sidecar, or scanned in
      Python. ``read(i)`` returns the raw record payload bytes.
    - ``kind="webdataset"``: a webdataset-style tar shard; members are
      grouped by basename stem into samples, the index stores each
      member's ``(data_offset, size)`` from one tar walk. ``read(i)``
      returns ``{extension: bytes}`` for sample ``i``.

    Reads are thread-safe (per-thread file handles) and charge the
    emulated-storage latency + byte/rate telemetry per read op.
    """

    def __init__(self, path, kind, index, name=None):
        self.path = str(path)
        self.kind = kind
        self._index = index
        self.name = name or os.path.basename(self.path)
        self._tls = threading.local()

    def __len__(self):
        return len(self._index)

    def __repr__(self):
        return (f"ShardIndex({self.name!r}, kind={self.kind!r}, "
                f"records={len(self)})")

    # -- constructors ---------------------------------------------------
    @classmethod
    def recordio(cls, path, idx_path=None):
        """Index a RecordIO pack. ``idx_path`` (or ``<path>.idx`` /
        the im2rec ``<base>.idx`` sidecar, when present) is preferred;
        otherwise the native scan, then the Python scan."""
        for cand in ([idx_path] if idx_path else
                     [str(path) + ".idx",
                      os.path.splitext(str(path))[0] + ".idx"]):
            if cand and os.path.exists(cand):
                offsets = []
                with open(cand) as f:
                    for line in f:
                        parts = line.split()
                        if len(parts) >= 2:
                            offsets.append(int(parts[1]))
                return cls(path, "recordio",
                           _np.asarray(sorted(offsets), dtype=_np.uint64))
        offsets = _native_scan_recordio(str(path))
        if offsets is None:
            offsets = _python_scan_recordio(str(path))
        return cls(path, "recordio", offsets)

    @classmethod
    def webdataset(cls, path):
        """Index a webdataset-style tar shard: one tar walk records
        every member's data offset/size; members sharing a basename
        stem (up to the first dot) form one sample."""
        samples = {}  # stem -> [(ext, offset, size)]
        order = []
        with tarfile.open(path, "r:") as tf:
            for m in tf:
                if not m.isfile():
                    continue
                base = os.path.basename(m.name)
                stem, _, ext = base.partition(".")
                if stem not in samples:
                    samples[stem] = []
                    order.append(stem)
                samples[stem].append((ext, m.offset_data, m.size))
        index = [tuple(samples[s]) for s in order]
        return cls(path, "webdataset", index)

    # -- reads ----------------------------------------------------------
    def _fp(self):
        fp = getattr(self._tls, "fp", None)
        if fp is None or getattr(self._tls, "pid", None) != os.getpid():
            fp = open(self.path, "rb")
            self._tls.fp = fp
            self._tls.pid = os.getpid()
        return fp

    def _native_handle(self):
        """Per-thread native RecordIO handle (the read-at data pointer
        is only valid until the handle's next read, so handles cannot
        be shared across threads)."""
        if self.kind != "recordio":
            return None
        h = getattr(self._tls, "nh", None)
        if h is not None and getattr(self._tls, "nh_pid", None) == os.getpid():
            return h
        lib = get_lib()
        if lib is None or not hasattr(lib, "MXTPURecordIOReadAt"):
            self._tls.nh = None
            return None
        handle = ctypes.c_void_p()
        if lib.MXTPURecordIOOpen(self.path.encode(), 0,
                                 ctypes.byref(handle)) != 0:
            self._tls.nh = None
            return None
        self._tls.nh = handle
        self._tls.nh_pid = os.getpid()
        return handle

    def _charge(self, nbytes, dt):
        if _obs.ENABLED:
            _obs.record_stream_read(self.name, nbytes, dt)

    def read(self, i):
        """Record ``i``: payload ``bytes`` (recordio) or
        ``{ext: bytes}`` (webdataset). O(1): one seek+read per
        member; native ``MXTPURecordIOReadAt`` when libmxtpu is
        loaded, Python seek+read otherwise."""
        lat = emulated_latency_ms()
        t0 = time.perf_counter()
        if self.kind == "recordio":
            if lat:
                time.sleep(lat / 1e3)
            nh = self._native_handle()
            if nh is not None:
                lib = get_lib()
                data = ctypes.POINTER(ctypes.c_uint8)()
                # the index is a host numpy array — the cast is a
                # scalar read, not a device sync
                off = int(self._index[i])  # mxtpu-lint: host-sync-ok
                n = lib.MXTPURecordIOReadAt(nh, off, ctypes.byref(data))
                if n < 0:
                    raise MXNetError(
                        f"{self.path}[{i}]: "
                        f"{lib.MXTPUGetLastError().decode()}")
                out = ctypes.string_at(data, n)
            else:
                fp = self._fp()
                fp.seek(int(self._index[i]))  # mxtpu-lint: host-sync-ok
                hdr = fp.read(8)
                magic, lrec = struct.unpack("<II", hdr)
                if magic != _MAGIC:
                    raise MXNetError(
                        f"{self.path}[{i}]: invalid magic {magic:#x} "
                        f"(stale index?)")
                out = fp.read(lrec & _LEN_MASK)
            self._charge(len(out) + 8, time.perf_counter() - t0)
            return out
        sample = {}
        fp = self._fp()
        for ext, off, size in self._index[i]:
            if lat:
                time.sleep(lat / 1e3)  # one op per member, like object
                # storage range requests
            fp.seek(off)
            sample[ext] = fp.read(size)
        self._charge(sum(len(v) for v in sample.values()),
                     time.perf_counter() - t0)
        return sample

    def close(self):
        fp = getattr(self._tls, "fp", None)
        if fp is not None:
            try:
                fp.close()
            except OSError:
                pass
            self._tls.fp = None
        nh = getattr(self._tls, "nh", None)
        if nh is not None:
            lib = get_lib()
            if lib is not None:
                lib.MXTPURecordIOClose(nh)
            self._tls.nh = None


def _open_shard(spec):
    """Coerce one shard spec (ShardIndex | path) to a ShardIndex; tar
    suffixes open as webdataset, everything else as RecordIO."""
    if isinstance(spec, ShardIndex):
        return spec
    p = str(spec)
    if p.endswith((".tar", ".tgz", ".tar.gz")):
        if p.endswith(("gz",)):
            raise MXNetError(
                f"{p}: compressed tar shards have no O(1) member "
                f"access — repack uncompressed (webdataset convention)")
        return ShardIndex.webdataset(p)
    return ShardIndex.recordio(p)


class ShardSet:
    """An ordered shard collection with global-record prefix sums: maps
    a linear record id (under a given shard permutation) to
    ``(shard, record)`` in O(log S)."""

    def __init__(self, shards):
        self.shards = [_open_shard(s) for s in shards]
        if not self.shards:
            raise MXNetError("ShardSet: no shards")
        self.sizes = _np.asarray([len(s) for s in self.shards],
                                 dtype=_np.int64)
        self.total = int(self.sizes.sum())
        if self.total == 0:
            raise MXNetError("ShardSet: shards contain no records")

    def __len__(self):
        return self.total

    def close(self):
        for s in self.shards:
            s.close()


# -- deterministic epoch order -------------------------------------------

def _rng(*key):
    """A process-independent deterministic RNG: string seeding goes
    through sha512, not PYTHONHASHSEED."""
    return random.Random(":".join(str(k) for k in key))


class GlobalOrder:
    """The deterministic order of one epoch: shard permutation composed
    with block window shuffle, all derived from ``(seed, epoch)``.

    ``locate(epoch, i)`` -> ``(shard_id, record_id)`` for within-epoch
    position ``i`` in O(1) amortized: the shard permutation + prefix
    sums are cached per epoch, window permutations (``window``-sized)
    are generated on demand and memoized for the handful of windows a
    sequential consumer straddles — never an epoch-sized array."""

    def __init__(self, shardset, seed=0, window=0, shuffle_shards=True):
        self.shardset = shardset
        self.seed = int(seed)
        self.window = int(window)
        self.shuffle_shards = bool(shuffle_shards)
        self._epoch = None
        self._perm = None     # shard permutation for _epoch
        self._cum = None      # prefix sums under that permutation
        self._windows = {}    # (epoch, w) -> list perm (tiny LRU)

    def _epoch_tables(self, epoch):
        if self._epoch != epoch:
            perm = list(range(len(self.shardset.shards)))
            if self.shuffle_shards:
                _rng(self.seed, epoch, "shards").shuffle(perm)
            sizes = self.shardset.sizes[perm]
            self._perm = perm
            self._cum = _np.concatenate(
                ([0], _np.cumsum(sizes))).astype(_np.int64)
            self._epoch = epoch
            self._windows.clear()
        return self._perm, self._cum

    def _window_perm(self, epoch, w):
        key = (epoch, w)
        cached = self._windows.get(key)
        if cached is None:
            n = self.shardset.total
            lo = w * self.window
            size = min(self.window, n - lo)
            cached = list(range(size))
            _rng(self.seed, epoch, "win", w).shuffle(cached)
            self._windows[key] = cached
            while len(self._windows) > 8:  # sequential consumers
                self._windows.pop(next(iter(self._windows)))
        return cached

    def locate(self, epoch, i):
        """Within-epoch position ``i`` -> ``(shard_id, record_id)``."""
        perm, cum = self._epoch_tables(epoch)
        if self.window:
            w = i // self.window
            i = w * self.window + self._window_perm(epoch, w)[i % self.window]
        s = int(_np.searchsorted(cum, i, side="right")) - 1
        return perm[s], int(i - cum[s])


# -- default decode/collate ----------------------------------------------

def decode_recordio_f32(payload):
    """Default RecordIO decode: ``recordio.unpack`` the IRHeader, view
    the body as float32 — the synthetic-tensor shard format
    ``write_recordio_shards`` emits. Returns ``(data, label)``."""
    header, body = unpack(payload)
    return (_np.frombuffer(body, dtype=_np.float32).copy(),
            _np.asarray(header.label, dtype=_np.float32))


def _collate(samples):
    """Stack structurally identical samples leaf-wise into batch
    arrays (tuple/dict structure preserved)."""
    first = samples[0]
    if isinstance(first, (tuple, list)):
        return type(first)(_collate([s[i] for s in samples])
                           for i in range(len(first)))
    if isinstance(first, dict):
        return {k: _collate([s[k] for s in samples]) for k in first}
    if isinstance(first, _np.ndarray):
        return _np.stack(samples)
    return list(samples)


# -- the reader -----------------------------------------------------------

_SENTINEL = object()


class StreamReader:
    """Sharded, resumable, line-rate streaming reader.

    >>> rd = StreamReader(["train-000.rec", "train-001.rec"],
    ...                   batch_size=64, seed=0, window=4096)
    >>> pf = DevicePrefetcher(rd, mesh=mesh)   # device staging leg
    >>> state = rd.state()                     # checkpointable cursor
    >>> rd.repartition(world=2, rank=0)        # elastic resize, no
    ...                                        # skip, no replay

    Threads: one read-ahead thread streams raw records (bounded by
    ``readahead``), a ``pool``-wide decode pool turns them into
    samples, a reorder stage re-emits them in exact global order.
    ``epochs=None`` streams forever (epoch = reshuffle boundary);
    ``epochs=k`` stops after k full passes (drop-tail to whole
    batches). An exception in any stage propagates from ``next()``.
    """

    #: machine-checked lock protocol (mxtpu-lint thread-guard): the
    #: reorder buffer and error slot are shared between the decode
    #: pool, the reader thread, and the consumer — mutating them
    #: off-lock re-creates the PR-8 flush() race shape (a batch
    #: observed missing between a worker's pop and its put)
    _GUARDED_BY = {"_reorder": "_cv", "_error": "_cv",
                   "_eof_seq": "_cv", "_live_workers": "_cv"}

    def __init__(self, shards, batch_size, seed=0, world=1, rank=0,
                 window=None, shuffle_shards=True, decode=None,
                 collate=None, pool=None, readahead=None, epochs=None):
        self.shardset = shards if isinstance(shards, ShardSet) \
            else ShardSet(shards)
        self.batch_size = int(batch_size)
        if self.batch_size <= 0:
            raise MXNetError("StreamReader: batch_size must be >= 1")
        self.seed = int(seed)
        self._window = shuffle_window() if window is None else int(window)
        self.order = GlobalOrder(self.shardset, seed=self.seed,
                                 window=self._window,
                                 shuffle_shards=shuffle_shards)
        self._decode = decode if decode is not None \
            else decode_recordio_f32
        self._collate = collate if collate is not None else _collate
        self._pool_size = pool if pool is not None else decode_threads()
        self._readahead = readahead if readahead is not None \
            else readahead_records()
        self.epochs = epochs
        # -- cursor (the deterministic global position) ----------------
        self._world = int(world)
        self._rank = int(rank)
        if not (0 <= self._rank < self._world):
            raise MXNetError(
                f"StreamReader: rank {self._rank} outside world "
                f"{self._world}")
        self._base = 0    # global batch index all ranks rebased from
        self._steps = 0   # batches THIS partition delivered since base
        self._stats_due = False  # batches recorded since a stream.stats
        # -- pipeline state --------------------------------------------
        self._cv = threading.Condition()
        self._reorder = {}      # seq -> decoded sample
        self._error = None
        self._eof_seq = None    # first seq the reader did NOT produce
        self._live_workers = 0
        self._raw_q = None
        self._threads = []
        self._stop = threading.Event()
        self._next_seq = 0      # consumer's next expected sample seq

    # -- cursor arithmetic ----------------------------------------------
    def _global_batch(self, step):
        return self._base + step * self._world + self._rank

    def _sample_limit(self):
        """First global sample index past the end (None = infinite)."""
        if self.epochs is None:
            return None
        return int(self.epochs) * self.shardset.total

    def locate_sample(self, g):
        """Global sample index -> (epoch, shard_id, record_id)."""
        n = self.shardset.total
        e = g // n
        shard, rec = self.order.locate(e, g % n)
        return e, shard, rec

    def state(self, steps=None):
        """The deterministic global cursor: a plain JSON-serializable
        dict from which every future sample is derivable. ``steps``
        overrides the delivered-batch count (the DevicePrefetcher
        passes its DELIVERED count so staged-ahead batches are not
        marked consumed)."""
        return {
            "version": CURSOR_VERSION,
            "kind": "stream",
            "seed": self.seed,
            "batch_size": self.batch_size,
            "world": self._world,
            "rank": self._rank,
            "base_batch": self._base,
            "steps": int(self._steps if steps is None else steps),
            "window": self._window,
            "records": self.shardset.total,
        }

    def restore(self, state):
        """Resume from a :meth:`state` cursor — bit-exact continuation:
        the next batch yielded is exactly the one that would have
        followed the checkpoint."""
        if not isinstance(state, dict) or state.get("kind") != "stream":
            raise MXNetError(f"StreamReader.restore: not a stream "
                             f"cursor: {state!r}")
        if int(state.get("version", -1)) > CURSOR_VERSION:
            raise MXNetError(
                f"StreamReader.restore: cursor version "
                f"{state['version']} is newer than this reader "
                f"({CURSOR_VERSION})")
        if int(state["records"]) != self.shardset.total:
            raise MXNetError(
                f"StreamReader.restore: cursor was cut for "
                f"{state['records']} records, shards now hold "
                f"{self.shardset.total} — the global order would "
                f"silently diverge")
        if int(state["batch_size"]) != self.batch_size or \
                int(state["seed"]) != self.seed or \
                int(state["window"]) != self._window:
            raise MXNetError(
                "StreamReader.restore: batch_size/seed/window differ "
                "from the cursor's — the global order would diverge")
        self._drain()
        self._world = int(state["world"])
        self._rank = int(state["rank"])
        self._base = int(state["base_batch"])
        self._steps = int(state["steps"])
        return self

    def repartition(self, world, rank, steps=None):
        """Re-partition the stream across a NEW rank extent at a step
        boundary (the PR-11 elastic-resize hook). The collective
        contract: every surviving rank calls this with the same
        ``steps`` (defaults to its own delivered count — equal across
        ranks at a boundary), so the global position rebases to
        ``base + steps*old_world`` and the union of the new ranks'
        batches continues the global sequence with zero skipped and
        zero replayed samples."""
        world, rank = int(world), int(rank)
        if not (0 <= rank < world):
            raise MXNetError(
                f"StreamReader.repartition: rank {rank} outside "
                f"world {world}")
        self._drain()
        t = self._steps if steps is None else int(steps)
        self._base = self._base + t * self._world
        self._steps = 0
        self._world = world
        self._rank = rank
        if _obs.ENABLED:
            _obs.STREAM_REPARTITIONS_TOTAL.inc()
        return self

    @property
    def cursor(self):
        """Structured cursor property (DevicePrefetcher/checkpoint
        integration point)."""
        return self.state()

    # -- producer side ---------------------------------------------------
    def _positions(self):
        """Yield ``(seq, global_sample_index)`` for every sample this
        partition will consume, starting at the current cursor."""
        limit = self._sample_limit()
        seq = self._next_seq
        step = self._steps
        while True:
            g = self._global_batch(step)
            lo = g * self.batch_size
            if limit is not None and lo + self.batch_size > limit:
                return  # drop-tail: only whole batches
            for j in range(self.batch_size):
                yield seq, lo + j
                seq += 1
            step += 1

    def _read_loop(self, raw_q, stop):
        """Read-ahead thread: stream raw records for the upcoming
        sample positions, in order, under queue backpressure."""
        last_seq = None
        try:
            for seq, g in self._positions():
                if stop.is_set():
                    return
                _e, shard_id, rec = self.locate_sample(g)
                shard = self.shardset.shards[shard_id]
                raw = shard.read(rec)
                while not stop.is_set():
                    try:
                        raw_q.put((seq, g, raw), timeout=0.05)
                        last_seq = seq
                        break
                    except Exception:  # queue.Full
                        continue
                else:
                    return
                if _obs.ENABLED:
                    _obs.STREAM_QUEUE_DEPTH.set(raw_q.qsize(),
                                                queue="raw")
        except BaseException as e:
            with self._cv:
                if self._error is None:
                    self._error = e
                self._cv.notify_all()
        finally:
            for _ in range(self._pool_size):  # one sentinel per worker
                while not stop.is_set():
                    try:
                        raw_q.put(_SENTINEL, timeout=0.05)
                        break
                    except Exception:
                        continue
            with self._cv:
                if self._error is None:
                    self._eof_seq = (last_seq + 1) if last_seq is not None \
                        else self._next_seq
                self._cv.notify_all()

    def _decode_loop(self, raw_q, stop):
        """Decode-pool worker: raw record -> sample, emitted into the
        reorder buffer under bounded decoded-ahead backpressure."""
        import queue as _queue

        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                item = raw_q.get(timeout=0.05)
            except _queue.Empty:
                if _obs.ENABLED:
                    _obs.STREAM_DECODE_WAIT_SECONDS.inc(
                        time.perf_counter() - t0)
                continue
            if item is _SENTINEL:
                break
            if _obs.ENABLED:
                _obs.STREAM_DECODE_WAIT_SECONDS.inc(
                    time.perf_counter() - t0)
            seq, g, raw = item
            try:
                t1 = time.perf_counter()
                sample = self._decode(raw)
                dt = time.perf_counter() - t1
                with self._cv:
                    while (not stop.is_set()
                           and self._error is None
                           and len(self._reorder) >= self._readahead
                           and seq >= self._next_seq + self._readahead):
                        self._cv.wait(0.05)
                    if stop.is_set():
                        return
                    self._reorder[seq] = sample
                    self._cv.notify_all()
                if _obs.ENABLED:
                    _obs.record_stream_decode(dt)
            except BaseException as e:
                with self._cv:
                    if self._error is None:
                        self._error = e
                    self._cv.notify_all()
                return

    # -- lifecycle --------------------------------------------------------
    def _start(self):
        import queue as _queue

        self._stop = threading.Event()
        self._raw_q = _queue.Queue(maxsize=self._readahead)
        with self._cv:
            self._reorder = {}
            self._error = None
            self._eof_seq = None
            self._live_workers = self._pool_size
        self._next_seq = 0
        self._threads = [threading.Thread(
            target=self._read_loop, args=(self._raw_q, self._stop),
            name="mxtpu-stream-read", daemon=True)]
        for i in range(self._pool_size):
            self._threads.append(threading.Thread(
                target=self._decode_loop,
                args=(self._raw_q, self._stop),
                name=f"mxtpu-stream-decode-{i}", daemon=True))
        for t in self._threads:
            t.start()

    def _drain(self):
        """Stop the pipeline, discarding staged-but-undelivered work
        (the cursor marks only DELIVERED batches, so nothing staged is
        lost — it is re-read on restart)."""
        self._stop.set()
        q = self._raw_q
        if q is not None:
            while True:
                try:
                    q.get_nowait()
                except Exception:
                    break
        with self._cv:
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []
        self._raw_q = None
        with self._cv:
            self._reorder = {}
            self._error = None
            self._eof_seq = None

    def close(self):
        """Idempotent shutdown: join the reader + pool threads and
        close per-thread shard handles."""
        self._closing_stats()
        self._drain()
        self.shardset.close()

    def _closing_stats(self):
        """One last ``stream.stats`` after the batches delivered since
        the last one, when the reader is exhausted or closed: the
        periodic instant of a short run (batch 1's) can precede every
        finished read."""
        if _obs.ENABLED and self._stats_due:
            self._stats_due = False
            _obs.record_stream_stats(len(self._reorder))

    def reset(self):
        """DataIter-protocol reset: restart this partition from the
        beginning of the stream."""
        self._drain()
        self._base = 0
        self._steps = 0

    def __del__(self):
        try:
            self._drain()
        except Exception:
            pass

    # -- consumer ---------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if not self._threads:
            self._start()
        t0 = time.perf_counter()
        samples = []
        with self._cv:
            stop = self._stop
            for _ in range(self.batch_size):
                want = self._next_seq
                while (want not in self._reorder
                       and self._error is None
                       and not stop.is_set()
                       and (self._eof_seq is None
                            or want < self._eof_seq)):
                    self._cv.wait(0.1)
                if self._error is not None:
                    err = self._error  # kept set: later next() re-raises
                    self._drain_locked_exit()
                    raise err
                if stop.is_set():
                    # drained under us (repartition/close from another
                    # thread): surface end-of-epoch, never a hang
                    raise StopIteration
                if want in self._reorder:
                    samples.append(self._reorder.pop(want))
                    self._next_seq = want + 1
                    self._cv.notify_all()
                    continue
                # EOF before a full batch: drop-tail contract
                break
        wait = time.perf_counter() - t0
        if len(samples) < self.batch_size:
            self._closing_stats()
            raise StopIteration
        self._steps += 1
        batch = self._collate(samples)
        if _obs.ENABLED:
            _obs.record_stream_batch(wait, len(self._reorder))
            self._stats_due = True
            if _obs.attribution.ENABLED:
                _obs.attribution.note_input_wait(wait)
        return batch

    def next(self):
        return self.__next__()

    def _drain_locked_exit(self):
        # called with self._cv held, on the error path only: stop
        # producers so the failed epoch does not keep decoding behind
        # a consumer that already raised
        self._stop.set()
        self._cv.notify_all()


# -- shard authoring (tests) ---------------------------------------------

def write_recordio_shards(directory, samples, shard_size,
                          prefix="shard"):
    """Write ``(data: np.float32 array, label: float)`` samples into
    RecordIO shards of ``shard_size`` records each + ``.idx`` sidecars.
    Returns the shard paths (the ``im2rec``-compatible pack layout the
    streaming reader consumes)."""
    from ...recordio import MXIndexedRecordIO, pack

    os.makedirs(directory, exist_ok=True)
    paths = []
    writer = None
    for i, (data, label) in enumerate(samples):
        if i % shard_size == 0:
            if writer is not None:
                writer.close()
            p = os.path.join(directory,
                             f"{prefix}-{len(paths):05d}.rec")
            writer = MXIndexedRecordIO(p + ".idx", p, "w")
            paths.append(p)
        payload = pack(IRHeader(0, float(label), i, 0),
                       _np.ascontiguousarray(data, _np.float32).tobytes())
        writer.write_idx(i % shard_size, payload)
    if writer is not None:
        writer.close()
    return paths


# -- on-device augmentation ----------------------------------------------

def device_augment(crop=None, flip=False, mean=None, std=None):
    """Build a jit-composable on-device augmentation: random crop /
    horizontal flip / normalize, executed INSIDE the compiled step (the
    host does image decode only — SURVEY Data IO's C++ augment stage
    moves onto the accelerator where it is free under XLA fusion).

    Returns ``fn(images, key) -> images`` for NHWC batches: ``crop``
    is the target ``(h, w)`` (random offsets per image, derived from
    ``jax.random.fold_in(key, i)`` so augmentation is deterministic in
    the global RNG key), ``flip`` mirrors each image with p=0.5,
    ``mean``/``std`` normalize per channel. All shapes are static —
    safe under ``jit``/``scan``/donation.
    """
    import jax
    import jax.numpy as jnp

    mean_a = None if mean is None else jnp.asarray(mean, jnp.float32)
    std_a = None if std is None else jnp.asarray(std, jnp.float32)

    def one(img, key):
        if crop is not None:
            ch, cw = crop
            kh, kw, key = jax.random.split(key, 3)
            oy = jax.random.randint(kh, (), 0, img.shape[0] - ch + 1)
            ox = jax.random.randint(kw, (), 0, img.shape[1] - cw + 1)
            img = jax.lax.dynamic_slice(
                img, (oy, ox, 0), (ch, cw, img.shape[2]))
        if flip:
            kf, key = jax.random.split(key)
            img = jnp.where(jax.random.bernoulli(kf),
                            img[:, ::-1, :], img)
        img = img.astype(jnp.float32)
        if mean_a is not None:
            img = img - mean_a
        if std_a is not None:
            img = img / std_a
        return img

    def augment(images, key):
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(images.shape[0]))
        return jax.vmap(one)(images, keys)

    return augment
