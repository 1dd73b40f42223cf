"""Fused SPMD train step over a device mesh.

This is the TPU-native training fast path (SURVEY.md §7.4): the whole
forward + backward + optimizer update compiles into ONE XLA executable with
sharding annotations; gradients are psum'd by XLA over the mesh's ``dp``
axis (replacing KVStore push/pull entirely). Tensor-parallel and
ZeRO-style state sharding are expressed as alternative param shardings on
the same step.

Uses the same "functionalize the imperative frontend" trick as CachedOp:
the Gluon block's Python forward runs once under tracing with parameter
handles bound to tracers.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import autograd
from .. import fusedstep as _fusedstep
from .. import observability as _obs
from .. import random as _random
from ..base import MXNetError
from ..gluon.block import _TRACE_STATE
from ..ndarray.ndarray import NDArray
from . import overlap as _overlap


def _put_global(raw, sharding):
    """Build a global array under ``sharding`` with each PROCESS serving
    its own addressable shards from ``raw`` (device_put would need
    cross-host transfers on a multi-process mesh, which CPU/DCN-less
    backends refuse). On a single process this degenerates to a plain
    sharded placement."""
    import numpy as onp

    host = onp.asarray(raw)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


def shard_batch(arr, mesh, axis_name="dp"):
    """Place a host batch sharded along its leading axis. On a
    multi-process mesh every process passes an array of the GLOBAL batch
    shape and contributes the rows its devices own (identical arrays
    everywhere -> the natural single-program semantics; per-rank data ->
    the global batch is the concatenation of each rank's owned rows)."""
    raw = arr.data if isinstance(arr, NDArray) else jnp.asarray(arr)
    sharding = NamedSharding(mesh, P(axis_name, *([None] * (raw.ndim - 1))))
    if isinstance(raw, jax.Array):
        try:
            if raw.sharding.is_equivalent_to(sharding, raw.ndim):
                # already placed (e.g. staged ahead by DevicePrefetcher):
                # re-sharding would gather the global batch to host
                return raw
        except Exception:
            pass
    return _put_global(raw, sharding)


def replicate(arr, mesh):
    raw = arr.data if isinstance(arr, NDArray) else jnp.asarray(arr)
    return _put_global(raw, NamedSharding(mesh, P()))


def _state_dtype(w):
    """Multi-precision rule (reference: ``mp_sgd_update``/``mp_adam_update``
    in optimizer_op): low-precision weights carry f32 optimizer state and
    update in f32 master math, casting back on write. This is also what
    keeps the step's avals STABLE: without it, ``lr(f32) * m(bf16)``
    promotes the new params to f32, every aval flips after step 1, and
    jit recompiles the whole train step (observed: 2 extra 60s compiles
    on BERT-base)."""
    return jnp.float32 if w.dtype in (jnp.bfloat16, jnp.float16) else w.dtype


def _sgd_rule(hyper):
    mom = hyper.get("momentum", 0.0)
    wd_const = hyper.get("wd", 0.0)

    def init(w):
        return (jnp.zeros(w.shape, _state_dtype(w)),) if mom else ()

    # ``wd`` defaults to the hyper constant but also accepts a traced
    # scalar operand (gluon.Trainer's fused update passes per-param
    # wd*wd_mult that way, so changing wd never retraces)
    def update(w, g, state, lr, wd=wd_const):
        dt = _state_dtype(w)
        w32, g32, lr32 = w.astype(dt), g.astype(dt), lr.astype(dt)
        g32 = g32 + wd * w32
        if mom:
            m = mom * state[0] - lr32 * g32
            return (w32 + m).astype(w.dtype), (m,)
        return (w32 - lr32 * g32).astype(w.dtype), ()

    return init, update


def _adam_rule(hyper):
    beta1 = hyper.get("beta1", 0.9)
    beta2 = hyper.get("beta2", 0.999)
    eps = hyper.get("epsilon", 1e-8)
    wd_const = hyper.get("wd", 0.0)

    def init(w):
        dt = _state_dtype(w)
        return (jnp.zeros(w.shape, dt), jnp.zeros(w.shape, dt),
                jnp.zeros((), jnp.int32))

    def update(w, g, state, lr, wd=wd_const):
        dt = _state_dtype(w)
        m, v, t = state
        t = t + 1
        w32, g32, lr32 = w.astype(dt), g.astype(dt), lr.astype(dt)
        g32 = g32 + wd * w32
        m = beta1 * m + (1 - beta1) * g32
        v = beta2 * v + (1 - beta2) * jnp.square(g32)
        tf = t.astype(dt)
        lr_t = lr32 * jnp.sqrt(1 - beta2 ** tf) / (1 - beta1 ** tf)
        return (w32 - lr_t * m / (jnp.sqrt(v) + eps)).astype(w.dtype), \
            (m, v, t)

    return init, update


def _lamb_rule(hyper):
    beta1 = hyper.get("beta1", 0.9)
    beta2 = hyper.get("beta2", 0.999)
    eps = hyper.get("epsilon", 1e-6)
    wd_const = hyper.get("wd", 0.0)

    def init(w):
        dt = _state_dtype(w)
        return (jnp.zeros(w.shape, dt), jnp.zeros(w.shape, dt),
                jnp.zeros((), jnp.int32))

    def update(w, g, state, lr, wd=wd_const):
        dt = _state_dtype(w)
        m, v, t = state
        t = t + 1
        w32, g32, lr32 = w.astype(dt), g.astype(dt), lr.astype(dt)
        m = beta1 * m + (1 - beta1) * g32
        v = beta2 * v + (1 - beta2) * jnp.square(g32)
        tf = t.astype(dt)
        m_hat = m / (1 - beta1 ** tf)
        v_hat = v / (1 - beta2 ** tf)
        r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * w32
        w_norm = jnp.linalg.norm(w32)
        r_norm = jnp.linalg.norm(r)
        ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return (w32 - lr32 * ratio * r).astype(w.dtype), (m, v, t)

    return init, update


def _lamb_rule_sharded(hyper, axis_name):
    """lamb over a flat dp-shard (ZeRO-2/3): identical math to
    :func:`_lamb_rule` except the trust-ratio norms are computed as
    local-shard sums of squares reduced with ONE extra psum pair over
    the data axis — each flat array is one parameter, and its pad
    region is zeros in both w and r, so the reduced norms are the
    whole-parameter norms. This is what lets lamb keep stage 2/3
    instead of declining to stage 1."""
    beta1 = hyper.get("beta1", 0.9)
    beta2 = hyper.get("beta2", 0.999)
    eps = hyper.get("epsilon", 1e-6)
    wd_const = hyper.get("wd", 0.0)

    def init(w):
        dt = _state_dtype(w)
        return (jnp.zeros(w.shape, dt), jnp.zeros(w.shape, dt),
                jnp.zeros((), jnp.int32))

    def update(w, g, state, lr, wd=wd_const):
        dt = _state_dtype(w)
        m, v, t = state
        t = t + 1
        w32, g32, lr32 = w.astype(dt), g.astype(dt), lr.astype(dt)
        m = beta1 * m + (1 - beta1) * g32
        v = beta2 * v + (1 - beta2) * jnp.square(g32)
        tf = t.astype(dt)
        m_hat = m / (1 - beta1 ** tf)
        v_hat = v / (1 - beta2 ** tf)
        r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * w32
        w_norm = jnp.sqrt(jax.lax.psum(jnp.sum(w32 * w32), axis_name))
        r_norm = jnp.sqrt(jax.lax.psum(jnp.sum(r * r), axis_name))
        ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return (w32 - lr32 * ratio * r).astype(w.dtype), (m, v, t)

    return init, update


def _nag_rule(hyper):
    """Nesterov momentum, matching ``optimizer.NAG.update``."""
    mom = hyper.get("momentum", 0.0)
    wd_const = hyper.get("wd", 0.0)

    def init(w):
        return (jnp.zeros(w.shape, _state_dtype(w)),) if mom else ()

    def update(w, g, state, lr, wd=wd_const):
        dt = _state_dtype(w)
        w32, g32, lr32 = w.astype(dt), g.astype(dt), lr.astype(dt)
        g32 = g32 + wd * w32
        if mom:
            m = mom * state[0] + g32
            return (w32 - lr32 * (g32 + mom * m)).astype(w.dtype), (m,)
        return (w32 - lr32 * g32).astype(w.dtype), ()

    return init, update


_RULES = {"sgd": _sgd_rule, "nag": _nag_rule, "adam": _adam_rule,
          "adamw": _adam_rule, "lamb": _lamb_rule}

_MP_SENTINEL = object()


def mp_rule(rule_init, rule_update):
    """fp32 master-weight wrapper around a ``_RULES`` pair (reference:
    ``mp_sgd_update``/``mp_adam_update``): for bf16/fp16 params the
    fp32 master copy becomes STATE LEAF 0, so it lives (and is donated)
    in the same optimizer-state pytree as the moments — updates
    accumulate in the master across steps and the stored weight is a
    rounded VIEW of it, instead of being re-derived from the rounded
    weight every step (which loses updates smaller than one bf16 ulp).
    fp32 params pass through untouched, so one wrapped rule serves a
    mixed-precision param set."""

    from ..amp.policy import is_low_precision_dtype

    def init(w):
        if not is_low_precision_dtype(w.dtype):
            return rule_init(w)
        master = w.astype(jnp.float32)
        return (master,) + tuple(rule_init(master))

    def update(w, g, state, lr, wd=_MP_SENTINEL):
        kw = {} if wd is _MP_SENTINEL else {"wd": wd}
        if not is_low_precision_dtype(w.dtype):
            return rule_update(w, g, state, lr, **kw)
        master, inner = state[0], tuple(state[1:])
        new_master, new_inner = rule_update(
            master, g.astype(jnp.float32), inner, lr, **kw)
        return new_master.astype(w.dtype), \
            (new_master,) + tuple(new_inner)

    return init, update


def bucketed_psum(grads, axis_name, bucket_bytes=None):
    """Scan-compatible bucketed gradient allreduce: one ``lax.psum`` per
    ~``bucket_bytes`` dtype-homogeneous flat bucket instead of one per
    gradient tensor — the in-graph analog of the kvstore's bucketed
    pushpull (PR 3), usable inside ``shard_map``/``lax.scan`` bodies
    (pure, no host round trip, stable avals across iterations). Returns
    the reduced gradients in the original order/shapes/dtypes.

    This is what a K-step superstep body calls per iteration on a
    multi-device mesh: K iterations x one-psum-per-bucket, all inside a
    single dispatched executable."""
    from .. import fusedstep as _fusedstep

    target = int(bucket_bytes if bucket_bytes is not None
                 else _fusedstep.bucket_bytes())
    flat = [g.reshape(-1) for g in grads]
    # greedy dtype-homogeneous fill, preserving order within a dtype
    buckets = []  # [idx list, payload bytes], one per bucket
    open_by_dtype = {}
    for i, f in enumerate(flat):
        dt = f.dtype
        nbytes = f.size * f.dtype.itemsize
        cur = open_by_dtype.get(dt)
        if cur is None or (cur[1] + nbytes > target and cur[0]):
            cur = [[], 0]
            open_by_dtype[dt] = cur
            buckets.append(cur)
        cur[0].append(i)
        cur[1] += nbytes
    out = [None] * len(grads)
    for idxs, _ in buckets:
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = jax.lax.psum(grads[i], axis_name)
            continue
        packed = jnp.concatenate([flat[i] for i in idxs])
        red = jax.lax.psum(packed, axis_name)
        off = 0
        for i in idxs:
            n = flat[i].size
            out[i] = red[off:off + n].reshape(grads[i].shape)
            off += n
    return out


class SPMDTrainStep:
    """One-executable train step for a Gluon block over a mesh.

    >>> step = SPMDTrainStep(net, loss_fn, "sgd", {"momentum": 0.9}, mesh)
    >>> loss = step(batch_x, batch_y, lr=0.1)
    """

    def __init__(self, block, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, batch_axis="dp", param_sharding=None,
                 shard_opt_states=False, grad_dtype=None, donate=True,
                 multi_precision=False, zero_stage=None, overlap=None,
                 compression_params=None):
        self.block = block
        self.loss_fn = loss_fn
        if mesh is not None:
            from .mesh import validate_mesh_axes, axis_size
            validate_mesh_axes(mesh, "SPMDTrainStep")
            if axis_size(mesh, "pp") > 1:
                raise MXNetError(
                    "SPMDTrainStep shards data/tensor axes only; a "
                    f"pp={axis_size(mesh, 'pp')} mesh needs the "
                    "pipeline executor — use Composed4DStep (or "
                    "PipelineTrainStep for pp alone)")
        self.mesh = mesh
        self.batch_axis = batch_axis
        hyper = dict(optimizer_params or {})
        if optimizer not in _RULES:
            raise MXNetError(
                f"SPMD step supports {sorted(_RULES)}; got {optimizer}. "
                "Use gluon.Trainer for other optimizers.")
        self._optimizer_name = optimizer
        self._rule_init, self._rule_update = _RULES[optimizer](hyper)
        if multi_precision:
            # bf16/fp16 params carry fp32 masters as state leaf 0 —
            # sharded/donated with the rest of the opt-state pytree
            self._rule_init, self._rule_update = mp_rule(
                self._rule_init, self._rule_update)
        self._param_sharding = param_sharding or {}
        # ZeRO stage (SURVEY P13 / docs/performance.md "scale-out"):
        # 0 replicated, 1 sharded opt state (legacy shard_opt_states),
        # 2 reduce-scattered grads + flat-sharded opt state, 3 params
        # sharded at rest too (gathered just-in-time inside the step)
        if zero_stage is None:
            zero_stage = 1 if shard_opt_states else _fusedstep.zero_stage()
        if int(zero_stage) not in (0, 1, 2, 3):
            raise MXNetError(f"zero_stage must be 0-3, got {zero_stage}")
        self.zero_stage = int(zero_stage)
        # lamb + ZeRO-2/3: the overlap build swaps in _lamb_rule_sharded
        # (shard-local trust-ratio norms + one psum pair), so the stage
        # is kept — the factory inputs are stashed for that rebuild
        self._hyper = hyper
        self._multi_precision = bool(multi_precision)
        self._shard_opt_states = shard_opt_states or self.zero_stage == 1
        self._overlap_explicit = overlap is not None
        if overlap is None:
            self._overlap_mode = _fusedstep.overlap_mode()
        elif overlap is True:
            self._overlap_mode = "ready"
        elif overlap is False:
            self._overlap_mode = "barrier"
        else:
            self._overlap_mode = str(overlap)
        if self._overlap_mode not in ("ready", "barrier", "staged",
                                      "nocomm"):
            raise MXNetError(f"overlap mode {overlap!r} not one of "
                             "ready/barrier/staged (True/False ok)")
        # reduced-precision gradient communication: buckets are cast to
        # this dtype for the collective (summed in it) and back after
        self._grad_dtype = None if grad_dtype is None \
            else jnp.dtype(grad_dtype)
        self._compress_thr = None
        if compression_params:
            ctype = compression_params.get("type", "2bit")
            if ctype != "2bit":
                raise MXNetError(f"unsupported compression type {ctype}")
            self._compress_thr = float(
                compression_params.get("threshold", 0.5))
        self._donate = donate
        self._compiled = None
        self._state = None  # (params, aux, opt_states) raw pytrees
        self._names = None
        self._diff = None
        self._io_avals = None
        self._run_many = None
        self._last_loss = None
        self._mode = None  # resolved at init_state: jit|overlap|staged
        self._shapes = None  # logical per-param shapes (handle order)
        self._logical = {}  # checkpoint key -> logical flat length
        self._bucket_plan = None
        self._residuals = None  # per-bucket 2-bit compression carry
        self._staged = None  # staged-mode executables (bwd/comm/upd)

    # -- mode resolution ---------------------------------------------------
    def _dp_size(self):
        if self.mesh is None:
            return 1
        return dict(zip(self.mesh.axis_names,
                        self.mesh.devices.shape)).get(self.batch_axis, 1)

    def _nontrivial_sharding(self):
        return any(len(tuple(spec)) and any(s is not None for s in spec)
                   for spec in self._param_sharding.values())

    def _mesh_mode(self):
        """``jit`` (the GSPMD single-executable path: single device,
        tensor-parallel shardings, or ZeRO-1 constraints), ``overlap``
        (explicit ``shard_map`` step with bucket-ready collectives —
        ZeRO 0/2/3), or ``staged`` (host-driven backward/comm/update
        dispatches — the legacy architecture, kept for the exposed-comm
        ablation)."""
        def _jit(reason):
            # an explicitly requested non-default schedule has no
            # meaning on the GSPMD single-executable path — say so
            # instead of silently measuring the wrong thing
            if self._overlap_explicit and self._overlap_mode != "ready":
                _fusedstep.log_fallback(
                    "spmd", f"overlap={self._overlap_mode!r} has no "
                    f"effect on the {reason} GSPMD path; running the "
                    "single-executable step")
            return "jit"

        if self.mesh is None or self._dp_size() <= 1:
            return _jit("single-device")
        if self.zero_stage == 1:
            return _jit("ZeRO-1")
        if self._nontrivial_sharding():
            if self.zero_stage >= 2:
                # dp-axis opt-state sharding composes with the tensor
                # partition on the GSPMD path: each moment rides the
                # param's tp spec extended along its first free
                # dp-divisible dim (see _opt_state_spec) — GSPMD emits
                # the equivalent reduce-scatter/allgather itself, so
                # the stage-2 memory layout survives tp
                self._shard_opt_states = True
            return _jit("tensor-parallel")
        if self._overlap_mode == "staged":
            if self.zero_stage >= 2:
                _fusedstep.log_fallback(
                    "spmd", "staged mode has no ZeRO-2/3 layout; "
                    "running the in-graph barrier mode instead")
                # make the log true: collectives pinned behind the
                # whole backward, not the bucket-ready schedule
                self._overlap_mode = "barrier"
                return "overlap"
            if self._compress_thr is not None:
                _fusedstep.log_fallback(
                    "spmd", "staged mode has no compressed-comm path "
                    "(it is the uncompressed measurement baseline); "
                    "running the in-graph barrier mode instead")
                self._overlap_mode = "barrier"
                return "overlap"
            return "staged"
        return "overlap"

    # -- state management -------------------------------------------------
    def _collect(self):
        items = sorted(self.block.collect_params().items())
        names = [n for n, _ in items]
        handles = [p.data() for _, p in items]
        diff = [p.grad_req != "null" for _, p in items]
        return names, handles, diff

    def _sharding_for(self, name, raw):
        if self.mesh is None:
            return None
        spec = self._param_sharding.get(name, P())
        return NamedSharding(self.mesh, spec)

    def _opt_state_spec(self, name, raw):
        """ZeRO-1 (SURVEY P13): moment tensors shard along dim 0 over the
        data axis, unless the param itself is already sharded on dim 0
        (tensor parallel) or dim 0 doesn't divide, in which case they
        follow the param's sharding."""
        pspec = self._param_sharding.get(name, P())
        if not self._shard_opt_states or self.mesh is None:
            return pspec
        dp = dict(zip(self.mesh.axis_names, self.mesh.devices.shape)).get(
            self.batch_axis)
        if dp and len(pspec) > 0 and any(s is not None for s in pspec):
            # tensor-parallel param: compose the dp shard ORTHOGONALLY —
            # extend the tp spec along the first free dp-divisible dim
            # (the tp split already divides sharded dims, so the check
            # uses the tp-local extent)
            dims = list(pspec) + [None] * (raw.ndim - len(pspec))
            sizes = dict(zip(self.mesh.axis_names,
                             self.mesh.devices.shape))
            for d in range(raw.ndim):
                local = raw.shape[d] // sizes.get(dims[d], 1) \
                    if dims[d] is not None else raw.shape[d]
                if dims[d] is None and local % dp == 0:
                    dims[d] = self.batch_axis
                    return P(*dims)
            import logging

            logging.getLogger(__name__).warning(
                "ZeRO-%d: opt state for %r (shape %s, tp spec %s) has "
                "no free dp-divisible dim; this moment stays on the "
                "param sharding (replicated over dp)", self.zero_stage,
                name, tuple(raw.shape), pspec)
            return pspec
        if (dp and raw.ndim >= 1 and raw.shape[0] % dp == 0
                and not (len(pspec) > 0 and pspec[0] is not None)):
            return P(self.batch_axis, *([None] * (raw.ndim - 1)))
        if (dp and raw.ndim >= 1 and raw.shape[0] % dp != 0
                and not (len(pspec) > 0 and pspec[0] is not None)):
            # visible fallback: on a real pod a silently replicated moment
            # is an invisible memory-budget surprise
            import logging

            logging.getLogger(__name__).warning(
                "ZeRO-1: opt state for %r (shape %s) not divisible by "
                "dp=%d; falling back to the param sharding %s",
                name, tuple(raw.shape), dp, pspec)
        return pspec

    def init_state(self):
        names, handles, diff = self._collect()
        self._names, self._handles, self._diff = names, handles, diff
        self._shapes = [tuple(h.data.shape) for h in handles]
        self._mode = self._mesh_mode()
        if self._mode in ("overlap", "staged"):
            return self._init_state_overlap()
        params = []
        opt_states = []
        opt_specs = []
        commit_dev = None
        if self.mesh is None:
            # commit to the default device: eager-built arrays are
            # UNCOMMITTED while jit outputs are committed, and that
            # sharding flip alone recompiles the step after call 1
            commit_dev = jax.devices()[0]
        for n, h, d in zip(names, handles, diff):
            raw = h.data
            if self.mesh is not None:
                # per-process shard feeding (works across hosts) + a fresh
                # buffer: the compiled step DONATES its param buffers, and
                # a donated alias of the Gluon handle's array kills it (a
                # second step on the same block then dies with "Array has
                # been deleted")
                raw = _put_global(raw, self._sharding_for(n, raw))
            else:
                raw = jnp.copy(jax.device_put(raw, commit_dev))
            params.append(raw)
            if not d:
                opt_states.append(())
                opt_specs.append(())
                continue
            state = self._rule_init(raw)
            spec = self._opt_state_spec(n, raw)
            # only moment-shaped leaves get the ZeRO spec; scalars (step
            # counters) stay replicated
            leaf_specs = tuple(
                spec if getattr(leaf, "shape", ()) == raw.shape else P()
                for leaf in state)
            if self.mesh is not None:
                state = tuple(
                    _put_global(leaf, NamedSharding(self.mesh, sp))
                    for leaf, sp in zip(state, leaf_specs))
            else:
                state = tuple(jax.device_put(leaf, commit_dev)
                              for leaf in state)
            opt_states.append(state)
            opt_specs.append(leaf_specs)
        self._opt_specs = opt_specs
        self._state = (params, opt_states)

    def _init_state_overlap(self):
        """State layout for the shard_map (overlap/staged) modes:

        - ZeRO-0 / staged: params + opt states replicated on the mesh;
        - ZeRO-2: params replicated; every diff param's optimizer-state
          moment (and fp32 master) lives as a flat ``[pad]`` array
          zero-padded to a multiple of dp and SHARDED over the batch
          axis — each rank owns 1/dp of every optimizer tensor;
        - ZeRO-3: the diff params themselves take the same flat-sharded
          layout at rest and are allgathered just-in-time in the step.

        ``self._logical`` records the unpadded flat length per
        checkpoint key so sharded saves clip the pad and elastic
        restores re-pad for the NEW dp (the pad is layout, not state).
        """
        names, handles, diff = self._names, self._handles, self._diff
        dp = self._dp_size()
        axis = self.batch_axis
        stage = self.zero_stage
        repl = NamedSharding(self.mesh, P())
        shard1d = NamedSharding(self.mesh, P(axis))
        params, opt_states, opt_specs = [], [], []
        self._logical = {}
        for n, h, d in zip(names, handles, diff):
            raw = jnp.asarray(h.data)
            flat_pad = None
            if d and stage >= 2:
                pad = _overlap._ceil_to(raw.size, dp)
                flat_pad = _overlap.pad_flat(raw, pad)
            if d and stage == 3:
                params.append(_put_global(flat_pad, shard1d))
                self._logical[f"param::{n}"] = int(raw.size)
            else:
                params.append(_put_global(raw, repl))
            if not d:
                opt_states.append(())
                opt_specs.append(())
                continue
            basis = flat_pad if stage >= 2 else raw
            state = self._rule_init(basis)
            leaf_specs = tuple(
                P(axis) if (stage >= 2
                            and getattr(leaf, "shape", ()) == basis.shape)
                else P() for leaf in state)
            placed = []
            for li, (leaf, sp) in enumerate(zip(state, leaf_specs)):
                if len(sp) and sp[0] is not None:
                    placed.append(_put_global(leaf, shard1d))
                    self._logical[f"opt::{n}::{li}"] = int(raw.size)
                else:
                    placed.append(_put_global(leaf, repl))
            opt_states.append(tuple(placed))
            opt_specs.append(leaf_specs)
        self._opt_specs = opt_specs
        self._state = (params, opt_states)
        if _obs.ENABLED:
            rep = self.zero_memory_report()
            _obs.ZERO_STATE_BYTES.set(rep["opt_bytes_per_device"],
                                      kind="opt")
            _obs.ZERO_STATE_BYTES.set(rep["param_bytes_per_device"],
                                      kind="param")

    def zero_memory_report(self):
        """Per-device at-rest memory accounting for the current state
        layout vs a fully replicated baseline: what ZeRO actually buys.
        ``grad_bytes_per_device`` is the gradient footprint the step's
        communication output materializes (full grads under allreduce,
        1/dp shards under the ZeRO-2/3 reduce-scatter)."""
        params, opt_states = self._state
        diff = self._diff

        def dev_bytes(a):
            """Bytes ONE device holds: a replicated tensor costs its
            full size per device, a sharded one just its shard."""
            try:
                sh = a.addressable_shards
                if sh:
                    return int(sh[0].data.size) * a.dtype.itemsize
            except Exception:
                pass
            return int(a.size) * a.dtype.itemsize

        opt_dev = sum(dev_bytes(leaf) for st in opt_states for leaf in st)
        opt_full = sum(int(leaf.size) * leaf.dtype.itemsize
                       for st in opt_states for leaf in st)
        par_dev = sum(dev_bytes(p) for p in params)
        par_full = sum(int(p.size) * p.dtype.itemsize for p in params)
        dp = self._dp_size()
        grad_full = sum(int(p.size) * p.dtype.itemsize
                        for p, d in zip(params, diff) if d)
        grad_dev = grad_full // dp if self.zero_stage >= 2 and dp > 1 \
            else grad_full
        return {"zero_stage": self.zero_stage, "dp": dp,
                "opt_bytes_per_device": opt_dev,
                "opt_bytes_replicated": opt_full,
                "param_bytes_per_device": par_dev,
                "param_bytes_replicated": par_full,
                "grad_bytes_per_device": grad_dev,
                "grad_bytes_replicated": grad_full}

    def _diff_idx(self):
        return [i for i, d in enumerate(self._diff) if d]

    def _plan_buckets(self, x_aval, y_aval, run_forward):
        """Readiness order from the VJP structure + the bucket plan.
        The order probe traces ONE extra forward (host-side, build
        time); a failed trace falls back to reversed parameter order
        (the DDP heuristic) — never a build failure."""
        diff_idx = self._diff_idx()
        shapes = [self._shapes[i] for i in diff_idx]
        handles = self._handles
        dtypes = [jnp.asarray(handles[i].data).dtype for i in diff_idx]
        dp = self._dp_size()
        params = [jnp.asarray(h.data) for h in handles]

        def probe(diff_params, x, y, key):
            full = list(params)
            for i, p in zip(diff_idx, diff_params):
                full[i] = p
            lmean, _ = run_forward(full, x, y, key)
            return lmean

        diff_avals = [jax.ShapeDtypeStruct(s, dt)
                      for s, dt in zip(shapes, dtypes)]
        order = _overlap.first_use_order(
            probe, (diff_avals, x_aval, y_aval, jax.random.PRNGKey(0)),
            len(diff_idx))
        plan = _overlap.build_bucket_plan(
            shapes, dtypes, order=order,
            dp=dp if self.zero_stage >= 2 else 1)
        if _obs.ENABLED:
            _obs.OVERLAP_BUCKETS.set(len(plan), site="spmd_step")
        return plan

    def _init_residuals(self, plan):
        """Per-bucket 2-bit compression carry: one flat zeros array per
        bucket, ``[dp * payload]`` sharded over the batch axis so each
        rank owns exactly its own error-feedback state."""
        dp = self._dp_size()
        lens = _overlap.residual_shapes(plan, self.zero_stage >= 2)
        shard1d = NamedSharding(self.mesh, P(self.batch_axis))
        res = []
        for bi, (L, idxs) in enumerate(zip(lens, plan.buckets)):
            dt = jnp.dtype(plan.dtypes[idxs[0]])
            res.append(_put_global(jnp.zeros(dp * L, dt), shard1d))
        self._residuals = tuple(res)
        pending = getattr(self, "_pending_residual_chunks", None)
        if pending is not None:
            # checkpoint loaded before the first step compiled: the
            # saved carry was stashed by spmd_load_states
            self._pending_residual_chunks = None
            _restore_residuals(self, *pending)

    # -- compiled step ----------------------------------------------------
    def _make_run_forward(self):
        """The functionalized Gluon forward shared by every mode: binds
        raw arrays into the parameter handles, runs block + loss under
        tracing, returns (mean loss, mutated handle list). Under the
        shard_map modes ``x`` is this rank's batch shard, so the mean
        is the LOCAL mean — callers psum/dp it back to the global one."""
        block, loss_fn, handles = self.block, self.loss_fn, self._handles

        def run_forward(param_raws, x, y, key):
            _TRACE_STATE.active = True
            _random.push_trace_key(key)
            saved = [h._data_ for h in handles]
            try:
                for h, raw in zip(handles, param_raws):
                    h._data_ = raw
                xin = NDArray(x)
                yin = NDArray(y)
                with autograd._RecordingStateScope(False, True):
                    out = block(xin)
                    loss = loss_fn(out, yin)
                loss_raw = jnp.mean(loss.data)
                mutated = [h._data_ for h in handles]
                return loss_raw, mutated
            finally:
                for h, s in zip(handles, saved):
                    h._data_ = s
                _random.pop_trace_key()
                _TRACE_STATE.active = False

        return run_forward

    def _build(self, raw_x, raw_y):
        if self._mode in ("overlap", "staged") and self._bucket_plan \
                is None:
            dp = self._dp_size()
            xs = (raw_x.shape[0] // dp,) + tuple(raw_x.shape[1:])
            ys = (raw_y.shape[0] // dp,) + tuple(raw_y.shape[1:])
            self._bucket_plan = self._plan_buckets(
                jax.ShapeDtypeStruct(xs, raw_x.dtype),
                jax.ShapeDtypeStruct(ys, raw_y.dtype),
                self._make_run_forward())
            if self._compress_thr is not None \
                    and self._residuals is None:
                self._init_residuals(self._bucket_plan)
        if self._mode == "overlap":
            return self._build_overlap(raw_x.ndim, raw_y.ndim)
        if self._mode == "staged":
            return self._build_staged(raw_x.ndim, raw_y.ndim)
        return self._build_jit()

    def _in_out_specs(self):
        """shard_map in/out specs mirroring the state pytrees: flat
        ZeRO shards ride P(batch_axis), everything else replicated."""
        axis = self.batch_axis
        stage = self.zero_stage
        pspec = [P(axis) if (d and stage == 3) else P()
                 for d in self._diff]
        sspec = [tuple(sp for sp in specs) for specs in self._opt_specs]
        rspec = tuple([P(axis)] * (len(self._residuals)
                                   if self._residuals is not None else 0))
        return pspec, sspec, rspec

    def _build_overlap(self, ndim_x, ndim_y):
        """ONE executable: forward + backward + bucket-ready gradient
        collectives + (ZeRO-sharded) update, as an explicit shard_map
        over the batch axis. Each bucket's psum / psum_scatter depends
        only on its own gradients, so XLA's scheduler can start it the
        moment the bucket's last contributor exists — while the rest of
        backward still computes (``barrier`` mode pins an
        optimization_barrier in front of the collectives instead: same
        numerics, no early start; ``nocomm`` drops the collectives for
        the exposed-comm measurement and is numerically WRONG on
        purpose)."""
        mesh, axis = self.mesh, self.batch_axis
        dp = self._dp_size()
        stage = self.zero_stage
        barrier = self._overlap_mode == "barrier"
        nocomm = self._overlap_mode == "nocomm"
        diff_idx = self._diff_idx()
        diff_set = set(diff_idx)
        rule_update = self._rule_update
        if self._optimizer_name == "lamb" and stage >= 2:
            # flat-sharded update: swap in the trust-ratio rule that
            # reduces its norms over the data axis (the decline to
            # stage 1 this used to force is gone)
            ri, ru = _lamb_rule_sharded(self._hyper, axis)
            if self._multi_precision:
                ri, ru = mp_rule(ri, ru)
            rule_update = ru
        run_forward = self._make_run_forward()
        plan = self._bucket_plan
        comp = self._compress_thr
        wdt = self._grad_dtype
        inv_dp = 1.0 / dp

        def body(params, opt_states, residuals, x, y, lr, key):
            full = list(params)
            if stage == 3:
                # just-in-time param gather: each all_gather depends
                # only on its own shard, so XLA schedules it right
                # before the layer's first use (and the buffer dies
                # after backward) — params are 1/dp at rest
                for k, i in enumerate(diff_idx):
                    fl = _overlap.gather_shard(params[i], axis)
                    full[i] = _overlap.unpad_reshape(
                        fl, plan.sizes[k], plan.shapes[k])

            def loss_of(diff_params):
                f2 = list(full)
                for i, p in zip(diff_idx, diff_params):
                    f2[i] = p
                lmean, mutated = run_forward(f2, x, y, key)
                return lmean, mutated

            (lmean, mutated), grads = jax.value_and_grad(
                loss_of, has_aux=True)([full[i] for i in diff_idx])
            loss = jax.lax.psum(lmean, axis) * inv_dp
            res_in = list(residuals) if comp is not None else None
            if nocomm:
                if stage >= 2:
                    gparts = [_overlap.shard_of(g, plan, axis, k) * inv_dp
                              for k, g in enumerate(grads)]
                else:
                    gparts = [g * jnp.asarray(inv_dp, g.dtype)
                              for g in grads]
                new_res = res_in
            elif stage >= 2:
                gparts, new_res = _overlap.bucket_reduce_scatter(
                    grads, axis, plan, postscale=inv_dp, barrier=barrier,
                    compress=comp, residuals=res_in, wire_dtype=wdt)
            else:
                gparts, new_res = _overlap.bucket_allreduce(
                    grads, axis, plan, postscale=inv_dp, barrier=barrier,
                    compress=comp, residuals=res_in, wire_dtype=wdt)
            new_params = list(mutated)
            for i in range(len(new_params)):
                if i not in diff_set and new_params[i] is not full[i]:
                    # aux state the forward mutated (BN batch stats):
                    # average the per-shard updates so every rank keeps
                    # identical replicas
                    new_params[i] = jax.lax.psum(
                        new_params[i], axis) * jnp.asarray(
                            inv_dp, new_params[i].dtype)
            new_states = list(opt_states)
            for k, i in enumerate(diff_idx):
                if stage >= 2:
                    wsh = params[i] if stage == 3 \
                        else _overlap.shard_of(full[i], plan, axis, k)
                    w2, s2 = rule_update(wsh, gparts[k],
                                         opt_states[i], lr)
                    if stage == 2:
                        fl = _overlap.gather_shard(w2, axis)
                        new_params[i] = _overlap.unpad_reshape(
                            fl, plan.sizes[k], plan.shapes[k])
                    else:
                        new_params[i] = w2
                else:
                    w2, s2 = rule_update(full[i], gparts[k],
                                         opt_states[i], lr)
                    new_params[i] = w2
                new_states[i] = s2
            new_res_out = tuple(new_res) if comp is not None else ()
            return new_params, new_states, new_res_out, loss

        pspec, sspec, rspec = self._in_out_specs()
        in_specs = (pspec, sspec, rspec,
                    P(axis, *([None] * (ndim_x - 1))),
                    P(axis, *([None] * (ndim_y - 1))), P(), P())
        out_specs = (pspec, sspec, rspec, P())
        return jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False),
            donate_argnums=(0, 1, 2) if self._donate else ())

    def _build_staged(self, ndim_x, ndim_y):
        """The legacy three-dispatch architecture, kept as the
        exposed-comm baseline: (A) backward executable producing
        per-rank gradients, (B) one bucketed-allreduce executable
        (same per-bucket psum as the overlap mode — numerics
        identical), (C) replicated fused update. The host sits between
        every stage, so communication time is fully exposed — exactly
        what the overlap mode hides."""
        mesh, axis = self.mesh, self.batch_axis
        dp = self._dp_size()
        diff_idx = self._diff_idx()
        diff_set = set(diff_idx)
        rule_update = self._rule_update
        run_forward = self._make_run_forward()
        plan = self._bucket_plan
        inv_dp = 1.0 / dp
        nondiff_idx = [i for i in range(len(self._diff))
                       if i not in diff_set]

        def bwd_body(params, x, y, key):
            def loss_of(diff_params):
                f2 = list(params)
                for i, p in zip(diff_idx, diff_params):
                    f2[i] = p
                lmean, mutated = run_forward(f2, x, y, key)
                return lmean, mutated

            (lmean, mutated), grads = jax.value_and_grad(
                loss_of, has_aux=True)([params[i] for i in diff_idx])
            aux = [mutated[i][None] for i in nondiff_idx]
            return [g[None] for g in grads], aux, lmean[None]

        wdt = self._grad_dtype

        def comm_body(gstack, austack, lstack):
            gs = [g.reshape(g.shape[1:]) for g in gstack]
            reds, _ = _overlap.bucket_allreduce(gs, axis, plan,
                                                postscale=inv_dp,
                                                wire_dtype=wdt)
            auxs = [jax.lax.psum(a.reshape(a.shape[1:]), axis)
                    * jnp.asarray(inv_dp, a.dtype) for a in austack]
            loss = jax.lax.psum(lstack.reshape(()), axis) * inv_dp
            return reds, auxs, loss

        def upd(params, opt_states, grads, auxs, lr):
            new_params = list(params)
            for i, a in zip(nondiff_idx, auxs):
                new_params[i] = a
            new_states = list(opt_states)
            for k, i in enumerate(diff_idx):
                w2, s2 = rule_update(params[i], grads[k],
                                     opt_states[i], lr)
                new_params[i] = w2
                new_states[i] = s2
            return new_params, new_states

        pspec = [P()] * len(self._diff)
        bwd = jax.jit(jax.shard_map(
            bwd_body, mesh=mesh,
            in_specs=(pspec, P(axis, *([None] * (ndim_x - 1))),
                      P(axis, *([None] * (ndim_y - 1))), P()),
            out_specs=([P(axis)] * len(diff_idx),
                       [P(axis)] * len(nondiff_idx), P(axis)),
            check_vma=False))
        comm = jax.jit(jax.shard_map(
            comm_body, mesh=mesh,
            in_specs=([P(axis)] * len(diff_idx),
                      [P(axis)] * len(nondiff_idx), P(axis)),
            out_specs=([P()] * len(diff_idx),
                       [P()] * len(nondiff_idx), P()),
            check_vma=False))
        updj = jax.jit(upd, donate_argnums=(0, 1)
                       if self._donate else ())
        return {"bwd": bwd, "comm": comm, "upd": updj}

    def _build_jit(self):
        handles, diff = self._handles, self._diff
        rule_update = self._rule_update
        run_forward = self._make_run_forward()
        mesh = self.mesh
        opt_specs = getattr(self, "_opt_specs", None)

        def step(params, opt_states, x, y, lr, key):
            diff_idx = [i for i, d in enumerate(diff) if d]

            def loss_of(diff_params):
                full = list(params)
                for i, p in zip(diff_idx, diff_params):
                    full[i] = p
                loss, mutated = run_forward(full, x, y, key)
                return loss, mutated

            (loss, mutated), grads = jax.value_and_grad(loss_of, has_aux=True)(
                [params[i] for i in diff_idx]
            )
            new_params = list(mutated)  # aux (BN stats) updates carried here
            new_states = list(opt_states)
            for k, i in enumerate(diff_idx):
                w, s = rule_update(params[i], grads[k], opt_states[i], lr)
                if mesh is not None and opt_specs is not None and opt_specs[i]:
                    # pin ZeRO-1 shardings so XLA keeps moments sharded
                    # across steps instead of replicating them
                    s = tuple(
                        jax.lax.with_sharding_constraint(
                            leaf, NamedSharding(mesh, sp))
                        for leaf, sp in zip(s, opt_specs[i]))
                new_params[i] = w
                new_states[i] = s
            return new_params, new_states, loss

        donate = (0, 1) if self._donate else ()
        return jax.jit(step, donate_argnums=donate)

    def __call__(self, x, y, lr=0.01, sync=True):
        if self._state is None:
            # resolve deferred init with one tiny eager pass. The probe
            # runs on a HOST copy of one row: the incoming batch may
            # already be mesh-sharded (DevicePrefetcher stages ahead),
            # and an eager forward mixing an 8-device input with
            # single-device params dies in dispatch.
            import numpy as onp

            raw = x.data if isinstance(x, NDArray) else jnp.asarray(x)
            if isinstance(raw, jax.Array) and raw.addressable_shards:
                host = onp.asarray(raw.addressable_shards[0].data)
            else:
                host = onp.asarray(raw)
            xin = NDArray(jnp.asarray(host[0:1] if host.shape[0] > 1
                                      else host))
            with autograd.predict_mode():
                self.block(xin)
            self.init_state()
        with _obs.span("spmd.step", cat="train"):
            return self._step(x, y, lr, sync)

    def _step(self, x, y, lr, sync):
        raw_x = x.data if isinstance(x, NDArray) else jnp.asarray(x)
        raw_y = y.data if isinstance(y, NDArray) else jnp.asarray(y)
        if self.mesh is not None:
            raw_x = shard_batch(NDArray(raw_x), self.mesh, self.batch_axis)
            raw_y = shard_batch(NDArray(raw_y), self.mesh, self.batch_axis)
        if self._compiled is None and self._staged is None:
            built = self._build(raw_x, raw_y)
            if self._mode == "staged":
                self._staged = built
            else:
                self._compiled = built
        key = _random._next_key()
        lr_arr = jnp.asarray(lr, raw_x.dtype
                             if raw_x.dtype in (jnp.float32, jnp.bfloat16)
                             else jnp.float32)
        if self._mode == "staged":
            loss = self._call_staged(raw_x, raw_y, lr_arr, key)
            return float(loss) if sync else loss
        params, opt_states = self._state
        # only the small call-arg avals are kept; param/state avals are
        # rebuilt lazily from _state in cost_analysis() (keeps this hot
        # path free of an O(n_params) tree_map per step)
        self._io_avals = (raw_x.shape, raw_x.dtype, raw_y.shape, raw_y.dtype,
                          lr_arr.dtype, key)
        if self._mode == "overlap":
            res = self._residuals if self._residuals is not None else ()
            args = (params, opt_states, res, raw_x, raw_y, lr_arr, key)
        else:
            args = (params, opt_states, raw_x, raw_y, lr_arr, key)
        if _obs.introspect.ENABLED \
                and not _obs.introspect.registered("spmd_step"):
            _obs.introspect.register_jit(
                "spmd_step", self._compiled,
                _obs.introspect.avals_of(args), donated=self._donate)
        att = _obs.ENABLED and _obs.attribution.ENABLED
        t0 = time.perf_counter() if att else 0.0
        with _obs.span("spmd.dispatch", cat="train"):
            if _obs.flight.INSTALLED:
                with _obs.flight.dispatch("spmd_step"):
                    out = self._compiled(*args)
            else:
                out = self._compiled(*args)
        if _obs.ENABLED:
            _obs.record_xla_dispatch("spmd_step")
            if att:
                # comm is in-graph here — the overlap probe's hint (by
                # mode) stands in for the unobservable wire time
                _obs.attribution.record_step(
                    t0, time.perf_counter(), site="spmd",
                    comm_mode=self._mode)
        if self._mode == "overlap":
            new_params, new_states, new_res, loss = out
            if self._compress_thr is not None:
                self._residuals = new_res
        else:
            new_params, new_states, loss = out
        self._state = (new_params, new_states)
        return float(loss) if sync else loss

    def _call_staged(self, raw_x, raw_y, lr_arr, key):
        """Three host-driven dispatches (backward / bucketed allreduce /
        update): communication is fully serialized behind the backward —
        the exposed-comm baseline the overlap mode is measured against."""
        st = self._staged
        params, opt_states = self._state
        att = _obs.ENABLED and _obs.attribution.ENABLED
        t0 = time.perf_counter() if att else 0.0
        with _obs.span("spmd.dispatch", cat="train", leg="bwd"):
            gstack, austack, lstack = st["bwd"](params, raw_x, raw_y, key)
        tc = time.perf_counter() if att else 0.0
        with _obs.span("spmd.dispatch", cat="train", leg="comm"):
            reds, auxs, loss = st["comm"](gstack, austack, lstack)
        if att:
            # the comm leg is a separate host-driven dispatch here —
            # its host-side span IS observable, so attribution gets a
            # measured figure instead of the overlap-probe hint
            _obs.attribution.note_comm(time.perf_counter() - tc)
        with _obs.span("spmd.dispatch", cat="train", leg="upd"):
            new_params, new_states = st["upd"](params, opt_states, reds,
                                               auxs, lr_arr)
        if _obs.ENABLED:
            _obs.record_xla_dispatch("spmd_step", 3)
            if att:
                _obs.attribution.record_step(
                    t0, time.perf_counter(), site="spmd_staged")
        self._state = (new_params, new_states)
        return loss

    def run_steps(self, x, y, n, lr=0.01):
        """Run ``n`` steps on one batch inside a single executable
        (``lax.fori_loop`` over the compiled step) — the analog of the
        reference's bulked execution (``MXNET_EXEC_BULK_EXEC_TRAIN``):
        one dispatch instead of n, which matters where dispatch latency
        bounds the Python loop. Per-step RNG keys are folded from one
        base key. Returns the final loss (device scalar)."""
        if self._state is None \
                or (self._compiled is None and self._staged is None) \
                or self._last_loss is None:
            # one plain step: resolves deferred init, compiles the inner
            # step, and seeds the loss carry with the right dtype
            self._last_loss = self(x, y, lr=lr, sync=False)
            n -= 1
            if n <= 0:
                return self._last_loss
        if self._mode == "staged":
            # the staged baseline is host-driven by definition: n
            # single steps, 3 dispatches each
            for _ in range(int(n)):
                self._last_loss = self(x, y, lr=lr, sync=False)
            return self._last_loss
        raw_x = x.data if isinstance(x, NDArray) else jnp.asarray(x)
        raw_y = y.data if isinstance(y, NDArray) else jnp.asarray(y)
        if self.mesh is not None:
            raw_x = shard_batch(NDArray(raw_x), self.mesh, self.batch_axis)
            raw_y = shard_batch(NDArray(raw_y), self.mesh, self.batch_axis)
        lr_arr = jnp.asarray(lr, raw_x.dtype
                             if raw_x.dtype in (jnp.float32, jnp.bfloat16)
                             else jnp.float32)
        base_key = _random._next_key()
        inner = self._compiled
        has_res = self._mode == "overlap"

        if self._run_many is None:
            if has_res:
                def many(params, opt_states, residuals, xx, yy, lr_a,
                         key, loss0, n_steps):
                    def body(i, c):
                        p, s, r, _ = c
                        return inner(p, s, r, xx, yy, lr_a,
                                     jax.random.fold_in(key, i))

                    return jax.lax.fori_loop(
                        0, n_steps, body,
                        (params, opt_states, residuals, loss0))

                donate = (0, 1, 2) if self._donate else ()
            else:
                def many(params, opt_states, xx, yy, lr_a, key, loss0,
                         n_steps):
                    def body(i, c):
                        p, s, _ = c
                        return inner(p, s, xx, yy, lr_a,
                                     jax.random.fold_in(key, i))

                    # n_steps is a TRACED bound (lowers to while_loop):
                    # one compile covers every n
                    return jax.lax.fori_loop(0, n_steps, body,
                                             (params, opt_states, loss0))

                donate = (0, 1) if self._donate else ()
            self._run_many = jax.jit(many, donate_argnums=donate)
        params, opt_states = self._state
        if has_res:
            res = self._residuals if self._residuals is not None else ()
            new_params, new_states, new_res, loss = self._run_many(
                params, opt_states, res, raw_x, raw_y, lr_arr, base_key,
                self._last_loss, jnp.asarray(n, jnp.int32))
            if self._compress_thr is not None:
                self._residuals = new_res
        else:
            new_params, new_states, loss = self._run_many(
                params, opt_states, raw_x, raw_y, lr_arr, base_key,
                self._last_loss, jnp.asarray(n, jnp.int32))
        if _obs.ENABLED:
            _obs.record_xla_dispatch("spmd_step")
        self._state = (new_params, new_states)
        self._last_loss = loss
        return loss

    def run_superstep(self, xs, ys, lr=0.01):
        """K DISTINCT batches in one dispatch: ``lax.scan`` of the
        compiled step over stacked ``[K, ...]`` operands. ``run_steps``
        re-consumes ONE batch (a bulked micro-benchmark); this is the
        training superstep — each scan iteration consumes its own batch
        slot, so a real input pipeline (``gluon.data.SuperstepRing``)
        feeds it with the host touching the loop once per K steps.
        Per-iteration RNG keys fold from one base key. ``lr`` may be a
        scalar or a length-K vector (a per-iteration in-graph schedule:
        iteration i applies ``lr[i]``). Returns the per-iteration
        losses as a length-K device array (lazy)."""
        raw_x = xs.data if isinstance(xs, NDArray) else jnp.asarray(xs)
        raw_y = ys.data if isinstance(ys, NDArray) else jnp.asarray(ys)
        if self._state is None:
            # resolve deferred init + build state WITHOUT consuming an
            # update (a priming step would apply slot 0 twice): same
            # host-row predict probe as __call__
            import numpy as onp

            # one-time deferred-init probe (self._state is None exactly
            # once), never on the per-superstep path
            if isinstance(raw_x, jax.Array) and raw_x.addressable_shards:
                host = onp.asarray(  # mxtpu-lint: host-sync-ok
                    raw_x.addressable_shards[0].data)
            else:
                host = onp.asarray(raw_x)  # mxtpu-lint: host-sync-ok
            xin = NDArray(jnp.asarray(host[0][0:1] if host[0].ndim and
                                      host[0].shape[0] > 1 else host[0]))
            with autograd.predict_mode():
                self.block(xin)
            self.init_state()
        if self._compiled is None and self._staged is None:
            built = self._build(raw_x[0], raw_y[0])
            if self._mode == "staged":
                self._staged = built
            else:
                self._compiled = built
        k = int(raw_x.shape[0])
        lr_arr = jnp.asarray(lr, raw_x.dtype
                             if raw_x.dtype in (jnp.float32, jnp.bfloat16)
                             else jnp.float32)
        # per-iteration lr: a scalar broadcasts to all K slots; a
        # length-K vector applies lr[i] at scan iteration i (how the
        # Superstep's in-graph scheduler samples per step)
        lrs = jnp.full((k,), lr_arr) if lr_arr.ndim == 0 else lr_arr
        if lrs.shape != (k,):
            raise MXNetError(
                f"run_superstep: lr must be scalar or shape ({k},); "
                f"got {tuple(lr_arr.shape)}")
        if self._mode == "staged":
            # host-driven baseline: K staged steps
            losses = [self._call_staged(
                shard_batch(NDArray(raw_x[i]), self.mesh, self.batch_axis),
                shard_batch(NDArray(raw_y[i]), self.mesh, self.batch_axis),
                lrs[i], _random._next_key()) for i in range(k)]
            losses = jnp.stack(losses)
            self._last_loss = losses[-1]
            return losses
        if self.mesh is not None:
            # slot axis 0 stays unsharded; the per-iteration batch axis
            # (dim 1) shards over the mesh exactly like a single step's
            raw_x = _put_global(raw_x, NamedSharding(
                self.mesh, P(None, self.batch_axis,
                             *([None] * (raw_x.ndim - 2)))))
            raw_y = _put_global(raw_y, NamedSharding(
                self.mesh, P(None, self.batch_axis,
                             *([None] * (raw_y.ndim - 2)))))
        base_key = _random._next_key()
        inner = self._compiled
        has_res = self._mode == "overlap"

        if getattr(self, "_run_super", None) is None:
            if has_res:
                def many(params, opt_states, residuals, xxs, yys, lr_s,
                         keys):
                    def body(carry, slot):
                        p, s, r = carry
                        xx, yy, key, lr_i = slot
                        p2, s2, r2, loss = inner(p, s, r, xx, yy, lr_i,
                                                 key)
                        return (p2, s2, r2), loss

                    (p, s, r), losses = jax.lax.scan(
                        body, (params, opt_states, residuals),
                        (xxs, yys, keys, lr_s))
                    return p, s, r, losses

                donate = (0, 1, 2) if self._donate else ()
            else:
                def many(params, opt_states, xxs, yys, lr_s, keys):
                    def body(carry, slot):
                        p, s = carry
                        xx, yy, key, lr_i = slot
                        p2, s2, loss = inner(p, s, xx, yy, lr_i, key)
                        return (p2, s2), loss

                    (p, s), losses = jax.lax.scan(
                        body, (params, opt_states), (xxs, yys, keys, lr_s))
                    return p, s, losses

                donate = (0, 1) if self._donate else ()
            self._run_super = jax.jit(many, donate_argnums=donate)
        keys = jax.random.split(base_key, k)
        params, opt_states = self._state
        if has_res:
            res = self._residuals if self._residuals is not None else ()
            args = (params, opt_states, res, raw_x, raw_y, lrs, keys)
        else:
            args = (params, opt_states, raw_x, raw_y, lrs, keys)
        if _obs.introspect.ENABLED \
                and not _obs.introspect.registered("spmd_superstep"):
            _obs.introspect.register_jit(
                "spmd_superstep", self._run_super,
                _obs.introspect.avals_of(args), donated=self._donate)
        att = _obs.ENABLED and _obs.attribution.ENABLED
        t0 = time.perf_counter() if att else 0.0
        if _obs.flight.INSTALLED:
            with _obs.flight.dispatch("spmd_superstep"):
                out = self._run_super(*args)
        else:
            out = self._run_super(*args)
        if has_res:
            new_params, new_states, new_res, losses = out
            if self._compress_thr is not None:
                self._residuals = new_res
        else:
            new_params, new_states, losses = out
        if _obs.ENABLED:
            _obs.record_xla_dispatch("spmd_superstep")
            # per-iteration in-scan loss series, stored whole and lazy
            _obs.record_superstep_series(losses)
            if att:
                _obs.attribution.record_step(
                    t0, time.perf_counter(), k=k, site="spmd_superstep",
                    comm_mode=self._mode)
        self._state = (new_params, new_states)
        self._last_loss = losses[-1]
        return losses

    def _compile_step(self):
        """The step executable at the last call's shapes, as a
        ``jax.stages.Compiled`` (``as_text()``, ``cost_analysis()``,
        ``memory_analysis()``), or None before the first call or in
        staged mode. NB: lowers again, and compiles again unless the
        persistent compile cache holds the step."""
        if self._compiled is None or self._io_avals is None:
            return None
        xs, xd, ys, yd, lrd, key = self._io_avals

        def aval(a):  # the state keeps its placement: same executable
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)

        avals = (jax.tree_util.tree_map(aval, self._state[0]),
                 jax.tree_util.tree_map(aval, self._state[1]))
        if self._mode == "overlap":
            res = self._residuals if self._residuals is not None else ()
            avals += (jax.tree_util.tree_map(aval, res),)
        avals += (jax.ShapeDtypeStruct(xs, xd),
                  jax.ShapeDtypeStruct(ys, yd),
                  jax.ShapeDtypeStruct((), lrd),
                  jax.ShapeDtypeStruct(key.shape, key.dtype))
        return self._compiled.lower(*avals).compile()

    def cost_analysis(self):
        """XLA's cost analysis for the compiled step (``{"flops": ...}``),
        or None when the backend doesn't expose it (some PJRT plugins).
        Costs what :meth:`_compile_step` costs."""
        try:
            compiled = self._compile_step()
            return None if compiled is None else compiled.cost_analysis()
        except Exception:
            return None

    def _logical_view(self, i, raw):
        """A ZeRO-3 flat-padded param back in its logical shape (no-op
        for naturally shaped entries)."""
        shape = self._shapes[i] if self._shapes is not None else None
        if shape is not None and tuple(raw.shape) != tuple(shape):
            size = 1
            for d in shape:
                size *= int(d)
            return raw.reshape(-1)[:size].reshape(shape)
        return raw

    def sync_to_block(self):
        """Write the step's param state back into the Gluon parameters
        (copies — the compiled step donates its param buffers, and a
        handle aliasing a donated buffer dies on the next step). ZeRO-3
        flat-sharded params are gathered back to their logical shapes."""
        params, _ = self._state
        for i, (h, raw) in enumerate(zip(self._handles, params)):
            h._set_data(jnp.copy(self._logical_view(i, raw)))


# ---------------------------------------------------------------------------
# sharded checkpointing (reference: Module.save_checkpoint /
# Trainer.save_states, re-designed for SPMD: each process writes only its
# ADDRESSABLE shards — on a pod no host ever materializes a full tensor)
# ---------------------------------------------------------------------------


def _shard_key(name, arr, index):
    spans = []
    for sl, dim in zip(index, arr.shape):
        start = 0 if sl.start is None else sl.start
        stop = dim if sl.stop is None else sl.stop
        spans.append(f"{start}:{stop}")
    return name + "|" + ";".join(spans) if spans else name + "|"


def _iter_state_tensors(step):
    """Stable (key, raw_array) walk over params + optimizer states +
    any 2-bit compression residual carry."""
    params, opt_states = step._state
    for n, p in zip(step._names, params):
        yield f"param::{n}", p
    for n, state in zip(step._names, opt_states):
        for li, leaf in enumerate(state):
            yield f"opt::{n}::{li}", leaf
    res = getattr(step, "_residuals", None)
    if res:
        for bi, r in enumerate(res):
            yield f"residual::{bi}", r


def _clipped_shard_chunks(raw, logical):
    """Pad-clipped ``(index, host_array)`` chunks of one state tensor:
    one chunk per replica-0 addressable shard, with flat ZeRO spans
    clipped to the tensor's LOGICAL length (the pad is LAYOUT — a
    function of this mesh's dp — not state, so an elastic restore with
    a different dp/pad reads pure-logical coordinates). Slice bounds
    are normalized to concrete ints."""
    import numpy as onp

    out = []
    for shard in raw.addressable_shards:
        if shard.replica_id != 0:
            continue
        idx = tuple(
            slice(0 if sl.start is None else int(sl.start),
                  int(dim) if sl.stop is None else int(sl.stop))
            for sl, dim in zip(shard.index, raw.shape))
        data = onp.asarray(shard.data)
        if logical is not None and idx:
            start, stop = idx[0].start, idx[0].stop
            if start >= logical:
                continue  # shard is entirely pad
            if stop > logical:
                data = data[:logical - start]
                idx = (slice(start, logical),) + tuple(idx[1:])
        out.append((idx, data))
    return out


def spmd_state_snapshot(step, copy=True):
    """Checkpoint-in-memory: the step's complete state as pad-clipped
    LOGICAL-span host chunks ``{key: [(index, np.ndarray), ...]}`` plus
    the residual-extent map — exactly what :func:`spmd_save_states`
    writes to disk, minus the disk leg. With ``copy`` (the default)
    every leaf is first snapshotted in ONE donation-safe jit copy
    dispatch (the PR-8 snapshot protocol) with the device->host
    transfers kicked off asynchronously, so the live state can keep
    being stepped (and donated) while the handoff drains. This is the
    elastic-resize handoff format: :func:`spmd_restore_chunks` re-pads
    and re-shards it onto ANY new mesh/stage layout. On a multi-host
    mesh each process snapshots only its addressable shards."""
    if step._state is None:
        raise MXNetError("state_snapshot: call init_state()/step first")
    items = list(_iter_state_tensors(step))
    if copy:
        from ..resilience.checkpoint import _copy_leaves

        copies = _copy_leaves([jnp.asarray(raw) for _, raw in items])
        for c in copies:
            try:  # start the device->host transfer now
                c.copy_to_host_async()
            except Exception:
                pass
        items = [(k, c) for (k, _), c in zip(items, copies)]
    logical = getattr(step, "_logical", None) or {}
    chunks = {}
    extents = {}
    for key, raw in items:
        chunks[key] = _clipped_shard_chunks(raw, logical.get(key))
        if key.startswith("residual::"):
            extents[key] = int(raw.shape[0])
    return chunks, extents


def spmd_save_states(step, prefix):
    """Write this process's shards of the step's params + opt states to
    ``{prefix}.shard{process_index}.npz``. On a multi-host mesh every
    process writes its own file into a shared filesystem; together the
    files tile every global tensor exactly once (replicated tensors are
    written by their first replica only)."""
    import numpy as onp

    if step._state is None:
        raise MXNetError("save_states: call init_state()/step first")
    store = {}
    logical = getattr(step, "_logical", None) or {}
    for key, raw in _iter_state_tensors(step):
        for idx, data in _clipped_shard_chunks(raw, logical.get(key)):
            store[_shard_key(key, raw, idx)] = data
    fname = f"{prefix}.shard{jax.process_index()}.npz"
    onp.savez(fname, **store)
    return fname


def spmd_load_states(step, prefix):
    """Restore a checkpoint written by ``spmd_save_states`` into the
    step's (already initialized) state, re-sharding each tensor with its
    CURRENT sharding — the mesh/spec layout may differ from save time
    (elastic restart, changed dp/tp split)."""
    import glob as _glob

    import numpy as onp

    if step._state is None:
        step.init_state()
    files = sorted(_glob.glob(f"{prefix}.shard*.npz"))
    if not files:
        raise MXNetError(f"no checkpoint shards match {prefix}.shard*.npz")
    # local-shard index map per tensor: only chunks overlapping THIS
    # process's shards are decompressed (the whole point of the sharded
    # format — no host materializes the full state)
    def _local_spans(like):
        spans = []
        for idx in like.sharding.addressable_devices_indices_map(
                like.shape).values():
            spans.append(tuple(
                (0 if sl.start is None else sl.start,
                 dim if sl.stop is None else sl.stop)
                for sl, dim in zip(idx, like.shape)))
        return spans

    logical = getattr(step, "_logical", None) or {}
    wanted = {}
    all_pad = set()
    for key, raw in _iter_state_tensors(step):
        spans = _local_spans(raw)
        lg = logical.get(key)
        if lg is not None:
            # padded flat shards only want their LOGICAL sub-span (the
            # pad region reassembles to zeros, its init value)
            spans = [((s0, min(s1, lg)),) + tuple(rest)
                     for (s0, s1), *rest in spans if s0 < lg]
            if not spans:
                # every shard THIS process holds is pure pad (a tensor
                # smaller than the new dp on a multi-host mesh): there
                # is legitimately nothing to read — reassemble zeros
                all_pad.add(key)
        wanted[key] = spans

    chunks = {}
    res_extent = {}
    for f in files:
        with onp.load(f) as z:
            for k in z.files:
                name, _, spans = k.rpartition("|")
                idx = tuple(slice(int(a), int(b)) for a, b in
                            (s.split(":") for s in spans.split(";") if s))
                if name.startswith("residual::") and idx:
                    # saved GLOBAL length, recorded before the local-span
                    # filter below can discard out-of-range chunks — the
                    # dp-layout guard in _restore_residuals needs it
                    res_extent[name] = max(res_extent.get(name, 0),
                                           idx[0].stop)
                local = wanted.get(name)
                if local is not None and idx:
                    src = [(sl.start, sl.stop) for sl in idx]
                    # only span-filter chunks saved in the SAME layout
                    # as the target (zip would silently truncate a
                    # flat-vs-natural rank mismatch); layout-crossing
                    # chunks all flow to _reassemble_cross
                    if all(len(t) == len(src) for t in local) and \
                            not any(all(sb > ta and sa < tb
                                        for (sa, sb), (ta, tb)
                                        in zip(src, tgt))
                                    for tgt in local):
                        continue  # chunk entirely on other hosts
                chunks.setdefault(name, []).append((idx, z[k]))
    spmd_restore_chunks(step, chunks, extents=res_extent,
                        allow_empty=all_pad)


def spmd_restore_chunks(step, chunks, extents=None, allow_empty=()):
    """Restore a logical-coordinate chunk set — an in-memory
    :func:`spmd_state_snapshot` (the elastic-resize handoff) or the
    span-filtered contents of a shard-file set — into the step's
    CURRENT state layout: every tensor is reassembled, re-padded and
    re-sharded for the mesh/stage the step has NOW, entirely
    host/device-side. ``extents`` maps ``residual::N`` keys to their
    saved global lengths (the dp-layout guard for the compression
    carry); ``allow_empty`` names keys whose local shards are entirely
    pad (multi-host flat tensors smaller than dp)."""
    if step._state is None:
        step.init_state()
    extents = extents or {}
    params, opt_states = step._state
    new_params = []
    for n, p in zip(step._names, params):
        new_params.append(_reassemble(f"param::{n}", p, chunks,
                                      allow_empty=f"param::{n}"
                                      in allow_empty))
    new_opt = []
    for n, state in zip(step._names, opt_states):
        new_opt.append(tuple(
            _reassemble(f"opt::{n}::{li}", leaf, chunks,
                        allow_empty=f"opt::{n}::{li}" in allow_empty)
            for li, leaf in enumerate(state)))
    step._state = (new_params, new_opt)
    res = getattr(step, "_residuals", None)
    res_chunks = {k: v for k, v in chunks.items()
                  if k.startswith("residual::")}
    if res:
        _restore_residuals(step, res_chunks, extents)
    elif res_chunks and getattr(step, "_compress_thr", None) is not None:
        # the carry tensors are created lazily by _init_residuals at
        # the first compiled step (the bucket plan needs a batch):
        # stash the saved chunks so they restore there instead of
        # being silently zeroed
        step._pending_residual_chunks = (res_chunks, extents)
    # push restored params back into the Gluon parameter handles so
    # eval/export paths see the checkpoint too. COPIES, not the state
    # arrays themselves: the compiled step donates its param buffers, and
    # a handle aliasing a donated buffer dies with it (observed as
    # "Array has been deleted" on the next init_state()). ZeRO-3 flat
    # entries go back in their logical shapes.
    for i, (h, raw) in enumerate(zip(step._handles, new_params)):
        h._set_data(jnp.copy(step._logical_view(i, raw)))


def _reassemble_cross(key, like, saved):
    """Layout-crossing restore: flat padded ZeRO shards into a
    natural-layout target (elastic shrink to a single device, or
    loading into a lower zero_stage) or natural shards into a flat
    target (raising the stage). Rebuilds the full LOGICAL tensor on
    the host first — the elastic fallback path, not the steady-state
    sharded format."""
    import numpy as onp

    src_nd = {len(idx) for idx, _ in saved if idx}
    if len(src_nd) != 1:
        raise MXNetError(
            f"checkpoint tensor {key!r}: mixed chunk layouts {src_nd}")
    if src_nd == {1}:
        # flat-saved -> natural target: everything past the logical
        # length (= the natural element count) is dp pad
        logical = int(onp.prod(like.shape, dtype=onp.int64)) \
            if like.shape else 1
        flat = onp.zeros((logical,), like.dtype)
        for idx, data in saved:
            a = idx[0].start or 0
            b = min(idx[0].stop, logical)
            if a < b:
                flat[a:b] = data[: b - a]
        full = flat.reshape(like.shape)
    else:
        # natural-saved -> flat target: the shard files tile the
        # natural tensor exactly, so its shape is the span union
        nd = src_nd.pop()
        shape = tuple(max(idx[d].stop for idx, _ in saved)
                      for d in range(nd))
        nat = onp.zeros(shape, like.dtype)
        for idx, data in saved:
            nat[idx] = data
        if nat.size > like.shape[0]:
            raise MXNetError(
                f"checkpoint tensor {key!r}: natural size {nat.size} "
                f"exceeds the flat layout length {like.shape[0]}")
        full = onp.zeros(like.shape, like.dtype)
        full[:nat.size] = nat.reshape(-1)
    sharding = like.sharding
    idx_map = sharding.addressable_devices_indices_map(like.shape)
    arrays = [jax.device_put(onp.ascontiguousarray(full[tgt_idx]), dev)
              for dev, tgt_idx in idx_map.items()]
    return jax.make_array_from_single_device_arrays(
        like.shape, sharding, arrays)


def _restore_residuals(step, chunks, extents):
    """Restore the 2-bit error-feedback carry (``residual::N``).
    PER-RANK state with a dp-interleaved ``[dp, payload/dp]`` element
    layout: it only restores exactly onto the same dp layout; an
    elastic restart restarts the carry from zeros (one warning,
    bounded error — one quantization step's worth). ``extents`` maps
    each key to its saved GLOBAL length — compared against the current
    length because ``chunks`` was pre-filtered to this process's
    current spans, which would otherwise hide a dp-shrink mismatch."""
    import logging

    new_res = []
    for bi, r in enumerate(step._residuals):
        key = f"residual::{bi}"
        saved = chunks.get(key, [])
        fits = saved and extents.get(key) == r.shape[0] and all(
            (idx[0].stop or r.shape[0]) <= r.shape[0]
            for idx, _ in saved if idx)
        if fits:
            new_res.append(_reassemble(key, r, chunks))
        else:
            logging.getLogger(__name__).warning(
                "load_states: compression residual %s does not "
                "match the current dp layout; restarting the "
                "error-feedback carry from zeros", key)
            new_res.append(r)
    step._residuals = tuple(new_res)


def _reassemble(key, like, chunks, allow_empty=False):
    """Rebuild one global tensor under ``like``'s CURRENT sharding,
    materializing only this process's addressable shards (never the full
    tensor — that is the point of the sharded format on a pod).
    ``allow_empty``: this process's shards are entirely pad (a flat
    ZeRO tensor smaller than dp), so a missing chunk set means zeros,
    not a corrupt checkpoint."""
    import numpy as onp

    if key not in chunks and not allow_empty:
        raise MXNetError(f"checkpoint missing tensor {key!r}")

    saved = chunks.get(key, [])
    src_nd = {len(idx) for idx, _ in saved if idx}
    if src_nd and src_nd != {len(like.shape)}:
        # saved layout differs from the target layout (flat ZeRO
        # shards vs the natural GSPMD/jit shapes)
        return _reassemble_cross(key, like, saved)

    def _span(sl, dim):
        return (0 if sl.start is None else sl.start,
                dim if sl.stop is None else sl.stop)

    sharding = like.sharding
    idx_map = sharding.addressable_devices_indices_map(like.shape)
    arrays = []
    for dev, tgt_idx in idx_map.items():
        tgt = [_span(sl, dim) for sl, dim in zip(tgt_idx, like.shape)]             if tgt_idx else []
        shard_shape = tuple(b - a for a, b in tgt)
        buf = onp.zeros(shard_shape, like.dtype)
        for src_idx, data in chunks.get(key, []):
            src = [_span(sl, dim) for sl, dim in zip(src_idx, like.shape)]
            # overlap of the saved chunk and this target shard
            inter = [(max(sa, ta), min(sb, tb))
                     for (sa, sb), (ta, tb) in zip(src, tgt)]
            if any(b <= a for a, b in inter):
                continue
            dst_sl = tuple(slice(a - ta, b - ta)
                           for (a, b), (ta, _) in zip(inter, tgt))
            src_sl = tuple(slice(a - sa, b - sa)
                           for (a, b), (sa, _) in zip(inter, src))
            buf[dst_sl] = data[src_sl]
        arrays.append(jax.device_put(buf, dev))
    return jax.make_array_from_single_device_arrays(
        like.shape, sharding, arrays)


# method-style access, matching Trainer.save_states naming
SPMDTrainStep.save_states = spmd_save_states
SPMDTrainStep.load_states = spmd_load_states
