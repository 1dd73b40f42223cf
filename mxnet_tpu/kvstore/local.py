"""Single-process KVStore: multi-device gradient aggregation.

Reference: ``src/kvstore/kvstore_local.h`` + ``comm.h`` (``CommCPU``/
``CommDevice``/``CommDeviceTree``). The reference needed explicit reduce
trees over PCIe; on TPU, XLA's ``psum``/addition graphs pick the reduction
topology, so aggregation is a jitted tree-sum followed by broadcast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import fusedstep as _fusedstep
from .. import observability as _obs
from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .base import KVStoreBase, register_kvstore


def _nd_nbytes(v) -> int:
    """Payload bytes of one NDArray-like (0 when unknowable)."""
    try:
        return int(v.size) * v.dtype.itemsize
    except Exception:
        return 0


def _group_nbytes(value) -> int:
    vs = value if isinstance(value, (list, tuple)) else [value]
    return sum(_nd_nbytes(v) for v in vs)


@jax.jit
def _tree_sum(arrays):
    acc = arrays[0]
    for a in arrays[1:]:
        acc = acc + a
    return acc


@jax.jit
def _tree_sum_groups(groups):
    """Sum each key's device list — every key in ONE executable."""
    return [_tree_sum.__wrapped__(list(g)) for g in groups]


@register_kvstore("local", "device")
class KVStoreLocal(KVStoreBase):
    """In-process store. ``device`` and ``local`` collapse to the same
    implementation: XLA owns placement and reduction topology."""

    def __init__(self):
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._opt_states = {}
        self._bucket_plans = {}  # signature -> compiled bucket round-trip
        self._bucket_residuals = {}  # signature -> 2-bit residual carry

    def _key(self, key):
        return str(key)

    def init(self, key, value):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.init(k, v)
            return
        self._store[self._key(key)] = value.copy()

    def _merge(self, values):
        if isinstance(values, NDArray):
            return values
        if len(values) == 1:
            return values[0]
        # cross-device sum: gather to first device, tree-add (jitted)
        dev = values[0].data.device if hasattr(values[0].data, "device") else None
        raws = [v.data if v.data.device == dev else jax.device_put(v.data, dev)
                for v in values]
        return NDArray(_tree_sum(raws), ctx=values[0].ctx)

    def push(self, key, value, priority=0):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.push(k, v, priority)
            return
        k = self._key(key)
        if k not in self._store:
            raise MXNetError(f"key {key} has not been initialized")
        if _obs.ENABLED:
            _obs.record_kv("push", _group_nbytes(value))
        merged = self._reduce(k, self._compress(k, self._merge(value)))
        if self._updater is not None:
            self._updater(int(key) if k.isdigit() else k, merged, self._store[k])
        elif self._optimizer is not None:
            idx = int(key) if k.isdigit() else k
            if idx not in self._opt_states:
                self._opt_states[idx] = self._optimizer.create_state_multi_precision(
                    idx, self._store[k]
                )
            self._optimizer.update_multi_precision(
                idx, self._store[k], merged, self._opt_states[idx]
            )
        else:
            self._store[k]._set_data(self._place(merged.data, self._store[k]))

    @staticmethod
    def _place(raw, o):
        """Move/cast ``raw`` for writing into ``o`` — both are almost
        always no-ops on the fused single-chip path; skipping the eager
        device_put/astype dispatches saves one eager dispatch per key
        for identity work."""
        dev = getattr(o.ctx, "jax_device", None)
        if dev is not None and getattr(raw, "device", dev) != dev:
            raw = jax.device_put(raw, dev)
        if str(raw.dtype) != str(o.dtype):
            raw = raw.astype(o.dtype)
        return raw

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if isinstance(key, (list, tuple)):
            # per-key pull handles nested and flat ``out`` entries alike
            for k, o in zip(key, out):
                self.pull(k, out=o, priority=priority)
            return
        k = self._key(key)
        stored = self._store[k]
        outs = out if isinstance(out, (list, tuple)) else [out]
        if _obs.ENABLED:
            _obs.record_kv("pull", _nd_nbytes(stored) * len(outs))
        for o in outs:
            o._set_data(self._place(stored.data, o))

    def pushpull(self, key, value, out=None, priority=0):
        """Aggregate ``value`` across devices and broadcast into ``out``
        WITHOUT touching the stored weight (Trainer's allreduce path)."""
        if isinstance(key, (list, tuple)):
            eligible = (out is not None and self._updater is None
                        and self._optimizer is None)
            # 2-bit compression rides the BUCKETED path (per-bucket
            # quantize + residual carry compiled into the pack, before
            # the wire reduction) — only the grouped/per-key fallbacks
            # still do it per key
            if eligible and _fusedstep.ENABLED \
                    and self._bucketed_pushpull(key, value, out):
                return
            if eligible and getattr(self, "_compression", None) is None \
                    and self._grouped_pushpull(key, value, out):
                return
            for i, k in enumerate(key):
                self.pushpull(k, value[i], out=None if out is None else out[i],
                              priority=priority)
            return
        if self._updater is not None or self._optimizer is not None:
            # update-on-kvstore semantics: push grads, pull weights
            self.push(key, value, priority)
            if out is not None:
                self.pull(key, out=out, priority=priority)
            return
        if out is None:
            self.push(key, value, priority)
        else:
            k = self._key(key)
            if _obs.ENABLED:
                _obs.record_kv("push", _group_nbytes(value))
                _obs.record_kv("pushpull", 0)
            merged = self._reduce(k, self._compress(k, self._merge(value)))
            outs = out if isinstance(out, (list, tuple)) else [out]
            if _obs.ENABLED:
                _obs.record_kv("pull", _nd_nbytes(merged) * len(outs))
            for o in outs:
                o._set_data(self._place(merged.data, o))

    @staticmethod
    def _gather_groups(values):
        """Normalize multi-key ``values`` into per-key raw-array tuples,
        gathered to the first value's device (one jit call needs all its
        operands on one device, like ``_merge`` does per key). Returns
        None when a sparse value needs the general per-key path. Shared
        by the grouped and bucketed fast paths so their eligibility and
        device handling can never diverge."""
        from ..ndarray.sparse import BaseSparseNDArray

        nd_groups = []
        for v in values:
            vs = v if isinstance(v, (list, tuple)) else [v]
            if any(isinstance(x, BaseSparseNDArray) for x in vs):
                return None
            nd_groups.append(vs)
        # zero keys / empty per-key lists: the callers' loops all
        # degenerate to no-ops, matching the old per-key behavior
        dev = next((getattr(vs[0].data, "device", None)
                    for vs in nd_groups if vs), None)
        return tuple(
            tuple(x.data if getattr(x.data, "device", None) == dev
                  else jax.device_put(x.data, dev) for x in vs)
            for vs in nd_groups)

    def _grouped_pushpull(self, keys, values, outs):
        """Batched multi-key aggregate: ONE jitted computation sums every
        key's device list (VERDICT r3 item 7 — per-key eager dispatch was
        the 15x cliff; grouping amortizes it across the whole grad set).
        Returns False when shapes need the general per-key path."""
        if type(self)._reduce is not KVStoreLocal._reduce:
            return False  # dist subclasses psum inside _reduce per key
        groups = self._gather_groups(values)
        if groups is None:
            return False
        if all(len(g) == 1 for g in groups):
            merged = [g[0] for g in groups]  # nothing to sum
        else:
            merged = _tree_sum_groups(groups)
            if _obs.ENABLED:
                _obs.record_xla_dispatch("kv_grouped")
        if _obs.ENABLED:
            _obs.record_kv(
                "push", sum(_nd_nbytes(x) for g in groups for x in g),
                count=len(groups))
            _obs.record_kv("pushpull", 0, count=len(groups))
            _obs.record_kv(
                "pull",
                sum(_nd_nbytes(m)
                    * (len(o) if isinstance(o, (list, tuple)) else 1)
                    for m, o in zip(merged, outs)),
                count=len(groups))
        for m, out in zip(merged, outs):
            os_ = out if isinstance(out, (list, tuple)) else [out]
            for o in os_:
                o._set_data(self._place(m, o))
        return True

    # -- bucketed multi-key pushpull (the fused-step allreduce path) -----
    #
    # Gradients are concatenated into a small number of fixed-size
    # dtype-homogeneous flat buckets (target MXTPU_BUCKET_BYTES, default
    # 4 MiB; built once per signature), reduced with ONE operation per
    # bucket, and scattered back in-graph. In-process, pack+reduce+unpack
    # fuse into a single executable; the dist store reduces each bucket
    # with one global-mesh allreduce between a compiled pack and unpack —
    # either way O(1) dispatches per step instead of O(num_keys).

    def _bucketed_pushpull(self, keys, values, outs):
        raw_groups = self._gather_groups(values)
        if raw_groups is None:
            _fusedstep.log_fallback(
                "kvstore", "sparse gradients use the per-key path")
            return False
        compress = getattr(self, "_compression", None)
        thr = compress["threshold"] if compress else None
        if self._reduce_raw_is_identity() \
                and all(len(vs) == 1 for vs in raw_groups) \
                and thr is None:
            # single device, nothing to reduce (in-process store, or a
            # dist store running one process): pure identity — the
            # grouped path short-circuits to a no-op, so a bucket
            # pack/unpack round-trip would only ADD a dispatch and a
            # full-gradient-set copy per step. (With compression there
            # IS in-graph work — quantize + residual — so that case
            # stays on the bucketed path.)
            return False
        groups = raw_groups  # raw jax arrays: shape/dtype/nbytes below
        # reduced-precision wire format only matters when a real
        # cross-process reduction runs (in-process there is no wire)
        comm = "" if self._reduce_raw_is_identity() \
            else _fusedstep.amp_allreduce_dtype()
        key_sig = tuple((tuple(vs[0].shape), str(vs[0].dtype), len(vs))
                        for vs in groups)
        sig = (comm, thr) + key_sig
        plan = self._bucket_plans.get(sig)
        if plan is None:
            plan = self._build_bucket_plan(key_sig, comm, compress=thr)
            self._bucket_plans[sig] = plan
            if _obs.ENABLED:
                _obs.KV_BUCKET_BUILD_TOTAL.inc()
                _obs.OVERLAP_BUCKETS.set(len(plan["buckets"]),
                                         site="kvstore")
        # per-bucket error-feedback carry, keyed by the SAME signature
        # the plan is (a shape/dtype change restarts the carry — the
        # residual layout is a function of the plan)
        res = self._bucket_residuals.get(sig, ()) if thr is not None \
            else ()
        if thr is not None and not res:
            res = tuple(jnp.zeros((n,), jnp.dtype(dt))
                        for n, dt in plan["res_shapes"])

        intro = _obs.introspect
        if plan["fused"] is not None:
            if intro.ENABLED and not intro.registered("kv_bucket"):
                intro.register_jit("kv_bucket", plan["fused"],
                                   (intro.avals_of(raw_groups),
                                    intro.avals_of(res)))
            with _obs.span("kv.grad_bucket", cat="comms"):
                merged, new_res = plan["fused"](raw_groups, res)
            n_dispatch = 1
        else:
            if intro.ENABLED and not intro.registered("kv_bucket_pack"):
                intro.register_jit("kv_bucket_pack", plan["pack"],
                                   (intro.avals_of(raw_groups),
                                    intro.avals_of(res)))
            with _obs.span("kv.grad_pack", cat="comms"):
                bucket_arrs, new_res = plan["pack"](raw_groups, res)
            reduce_live = not self._reduce_raw_is_identity()
            with _obs.span("kv.grad_allreduce", cat="comms"):
                bucket_arrs = tuple(self._reduce_raw(b)
                                    for b in bucket_arrs)
            with _obs.span("kv.grad_unpack", cat="comms"):
                merged = plan["unpack"](bucket_arrs)
            n_dispatch = 2 + (len(bucket_arrs) if reduce_live else 0)
        if thr is not None:
            self._bucket_residuals[sig] = tuple(new_res)
        if _obs.ENABLED:
            _obs.record_xla_dispatch("kv_bucket", n_dispatch)
            _obs.KV_BUCKET_PUSHPULL_TOTAL.inc()
            _obs.record_kv(
                "push", sum(_nd_nbytes(x) for g in groups for x in g),
                count=len(groups))
            _obs.record_kv("pushpull", 0, count=len(groups))
            _obs.record_kv(
                "pull",
                sum(_nd_nbytes(m)
                    * (len(o) if isinstance(o, (list, tuple)) else 1)
                    for m, o in zip(merged, outs)),
                count=len(groups))
        for m, out in zip(merged, outs):
            os_ = out if isinstance(out, (list, tuple)) else [out]
            for o in os_:
                o._set_data(self._place(m, o))
        return True

    def _build_bucket_plan(self, sig, comm="", compress=None):
        """Readiness-ordered dtype-homogeneous packing of keys into
        ~bucket_bytes flat buckets, plus the compiled pack/unpack for
        this signature. The packing itself delegates to
        :func:`parallel.overlap.build_bucket_plan` — ONE greedy
        algorithm serves the in-graph overlapped step and this staged
        store, composed in reverse key order (the trainer pushes keys
        in parameter order and backward produces the LAST parameter's
        gradient first, so bucket 0's reduction dispatch goes on the
        wire while later buckets still pack — the kvstore-level shadow
        of the in-graph bucket-ready schedule).

        ``comm`` (MXTPU_AMP_ALLREDUCE_DTYPE): non-empty casts float32
        buckets down to that dtype inside the compiled pack — half the
        wire bytes through ``_reduce_raw`` — and back to float32 inside
        the compiled unpack (the reduction itself accumulates in fp32,
        see ``dist._accum_sum``). ``compress``: 2-bit threshold —
        per-bucket quantize with error-feedback residual INSIDE the
        compiled pack, before the wire (the reference's worker-side
        compress-then-push order). In-graph throughout: no extra
        dispatches, and ``_place`` still sees the storage dtype."""
        from ..parallel import overlap as _overlap

        shapes = [s for s, _, _ in sig]
        dtypes = [dt for _, dt, _ in sig]
        oplan = _overlap.build_bucket_plan(
            shapes, dtypes, bucket_bytes=max(_fusedstep.bucket_bytes(), 1))
        buckets = [list(b) for b in oplan.buckets]
        sizes = list(oplan.sizes)
        bucket_dtypes = [dtypes[idxs[0]] for idxs in buckets]
        # only fp32 buckets are downcast: half/low dtypes gain nothing
        cast_down = [bool(comm) and dt == "float32" for dt in bucket_dtypes]
        res_shapes = [(sum(sizes[ki] for ki in idxs), bucket_dtypes[bi])
                      for bi, idxs in enumerate(buckets)]

        def pack(raw_groups, residuals):
            out, new_res = [], []
            for bi, idxs in enumerate(buckets):
                parts = []
                for ki in idxs:
                    g = raw_groups[ki]
                    s = g[0]
                    for extra in g[1:]:
                        s = s + extra  # cross-device tree-sum per key
                    parts.append(s.reshape(-1))
                b = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
                if compress is not None:
                    b, r = _overlap.compress_bucket(b, compress,
                                                    residuals[bi])
                    new_res.append(r)
                if cast_down[bi]:
                    b = b.astype(jnp.dtype(comm))
                out.append(b)
            return tuple(out), tuple(new_res)

        def unpack(bucket_arrs):
            raws = [None] * len(sig)
            for bi, idxs in enumerate(buckets):
                arr = bucket_arrs[bi]
                if cast_down[bi]:
                    arr = arr.astype(jnp.dtype(bucket_dtypes[bi]))
                off = 0
                for ki in idxs:
                    n = sizes[ki]
                    raws[ki] = jax.lax.slice(
                        arr, (off,), (off + n,)
                    ).reshape(shapes[ki])
                    off += n
            return tuple(raws)

        if type(self)._reduce_raw is KVStoreLocal._reduce_raw:
            # in-process reduction is identity: the whole round-trip is
            # ONE executable (pack, quantize, sum, scatter all fused)
            def fused(raw_groups, residuals):
                bs, nr = pack(raw_groups, residuals)
                return unpack(bs), nr

            return {"fused": jax.jit(fused), "pack": None,
                    "unpack": None, "buckets": buckets,
                    "res_shapes": res_shapes}
        return {"fused": None, "pack": jax.jit(pack),
                "unpack": jax.jit(unpack), "buckets": buckets,
                "res_shapes": res_shapes}

    def _reduce_raw(self, raw):
        """Cross-process reduction of one flat gradient bucket: identity
        in-process; the dist store overrides with the global-mesh
        allreduce (the bucketed analog of per-key ``_reduce``)."""
        return raw

    def _reduce_raw_is_identity(self) -> bool:
        """True when ``_reduce_raw`` does no work RIGHT NOW (the dist
        override refines this per process count), so bucketing can skip
        pure-identity aggregations."""
        return type(self)._reduce_raw is KVStoreLocal._reduce_raw

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        from ..ndarray.sparse import RowSparseNDArray, retain_rows

        k = self._key(key)
        stored = self._store[k]
        outs = out if isinstance(out, (list, tuple)) else [out]
        ids = row_ids if isinstance(row_ids, (list, tuple)) else [row_ids]
        for o, rid in zip(outs, ids):
            retain_rows(stored, rid, out=o)

    def _reduce(self, key, merged):
        """Cross-process reduction hook: identity in-process; the dist
        store overrides this with the global-mesh psum. Runs after
        ``_compress`` so compression happens before the wire, matching the
        reference's worker-side compress-then-push order."""
        return merged

    def set_updater(self, updater):
        self._updater = updater

    def _set_updater(self, updater):
        self._updater = updater

    def set_optimizer(self, optimizer):
        self._optimizer = optimizer

    def set_gradient_compression(self, compression_params):
        """2-bit gradient compression (reference:
        ``kv.set_gradient_compression`` -> ``gradient_compression.cc``).
        Applied on the push path with per-key residuals."""
        ctype = compression_params.get("type", "2bit")
        if ctype != "2bit":
            from ..base import MXNetError

            raise MXNetError(f"unsupported compression type {ctype}")
        self._compression = {
            "threshold": float(compression_params.get("threshold", 0.5))
        }
        self._residuals = {}
        self._bucket_residuals = {}  # threshold rides the plan signature

    def _compress(self, key, merged):
        if getattr(self, "_compression", None) is None:
            return merged
        import jax.numpy as jnp

        thr = self._compression["threshold"]
        res = self._residuals.get(key)
        if res is None:
            res = jnp.zeros(merged.shape, merged.data.dtype)
        acc = merged.data + res
        q = jnp.where(acc >= thr, thr, jnp.where(acc <= -thr, -thr, 0.0))
        self._residuals[key] = acc - q
        return NDArray(q, ctx=merged.ctx)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        import pickle

        with open(fname, "wb") as f:
            pickle.dump(self._opt_states, f)

    def load_optimizer_states(self, fname):
        import pickle

        with open(fname, "rb") as f:
            self._opt_states = pickle.load(f)
