"""Gluon Trainer: Parameters <-> KVStore <-> Optimizer bridge.

Reference: ``python/mxnet/gluon/trainer.py`` (symbols ``Trainer.step``,
``_allreduce_grads``, ``_update``). Multi-device aggregation goes through
the KVStore exactly as in the reference; on a TPU mesh the ``dist_tpu_sync``
store lowers push/pull to an ICI allreduce (SURVEY.md §2.5 P2/P4).

Fused update fast path (MXTPU_FUSED_STEP, default on): ONE jitted
executable updates every parameter per step — the analog of the
reference's multi-tensor ``multi_sgd``/``multi_mp_sgd`` kernels — with
scheduled lr, ``clip_gradient`` and per-param ``lr_mult``/``wd_mult``
passed as jit OPERANDS (not trace constants, so hyperparameter changes
never retrace), weight and optimizer-state buffers donated to XLA, and
the telemetry grad-norm gauge folded into the same executable (no
per-step device sync). See docs/performance.md for eligibility.

K-step superstep (``Superstep``, ``MXTPU_SUPERSTEP_K``): the whole-
program generalization — forward + backward + update for K DISTINCT
batches compiled into one ``lax.scan`` executable whose carry is the
donated weights + optimizer state + AMP loss-scaler state, consuming
stacked ``[K, ...]`` batch slots staged ahead by
``gluon.data.SuperstepRing``. The host touches the training loop once
per K steps. See docs/performance.md "superstep".
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from .. import autograd
from .. import fusedstep as _fusedstep
from .. import observability as _obs
from .. import optimizer as opt
from .. import random as _random
from ..resilience import chaos as _chaos
from ..resilience import checkpoint as _ckptmod
from ..resilience import elastic as _elastic
from ..base import MXNetError
from ..kvstore import create as _create_kvstore
from ..kvstore.base import KVStoreBase
from ..ndarray.ndarray import NDArray
from .parameter import Parameter, ParameterDict


# -- shared fused-update numerics ------------------------------------------
# Traced inside BOTH the one-step fused executable and the superstep scan
# body: the two paths are parity-pinned, so the per-iteration arithmetic
# must live in exactly one place (like _fused_rules/_fused_sig for
# eligibility/staleness).

def _dispatch_call(site, span, fn, args):
    """Slow-path executable invocation under the program's span
    ``span``, marked in flight as ``site`` for the crash flight recorder
    when that is installed. Call sites come here only when the recorder
    is installed or someone is looking (``_obs.watching()``): the
    normal path stays a bare call."""
    with _obs.span(span, cat="train"):
        if _obs.flight.INSTALLED:
            with _obs.flight.dispatch(site):
                return fn(*args)
        return fn(*args)


def _all_finite(gs):
    """ONE fused all-finite reduction over a gradient list (the fp16
    skip-update predicate)."""
    finite = jnp.bool_(True)
    for g in gs:
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
    return finite


def _apply_fused_update(ws, gs, sts, rule_update, lr, wd, rescale, clip,
                        lr_mults, wd_mults, has_clip, has_amp, with_gnorm,
                        finite, unscale_div):
    """Multi-tensor optimizer update for one iteration: in-graph grad
    norm (pre-rescale, for gauge parity with the eager probe), the fp16
    f32-upcast BEFORE the combined (1/batch)/loss_scale factor touches
    the grad (at batch 512 x scale 2^16 that factor is 3e-8, below
    fp16's 6e-8 subnormal floor — applied in g.dtype it rounds to
    literal 0 and every update silently vanishes), clip, the pytree
    rule, and the ``where``-based fp16 skip (a non-finite gradient set
    leaves the weights AND the whole state pytree untouched — no NaN
    can reach the (master) weights). ``rescale`` arrives with any
    unscale factor already folded in; ``unscale_div`` only corrects the
    reported grad norm (the buffers hold SCALED grads under deferred
    scale_loss)."""
    new_ws, new_sts, sq = [], [], []
    for i, (w, g, s) in enumerate(zip(ws, gs, sts)):
        if with_gnorm:
            g32 = g.astype(jnp.float32)
            sq.append(jnp.vdot(g32, g32))
        if has_amp:
            g = g.astype(jnp.float32)
        g = g * rescale.astype(g.dtype)
        if has_clip:
            c = clip.astype(g.dtype)
            g = jnp.clip(g, -c, c)
        w2, s2 = rule_update(w, g, s, lr * lr_mults[i],
                             wd=wd * wd_mults[i])
        if has_amp:
            w2 = jnp.where(finite, w2, w)
            s2 = tuple(jnp.where(finite, a, b) for a, b in zip(s2, s))
        new_ws.append(w2)
        new_sts.append(s2)
    gnorm = jnp.sqrt(sum(sq)) if sq else jnp.float32(0.0)
    if has_amp:
        gnorm = gnorm / unscale_div
    return new_ws, new_sts, gnorm


def _amp_scale_step(finite, scale, unskipped, ovf_total, factor, window):
    """In-graph dynamic loss-scale adjustment (the device twin of
    ``LossScaler.update_scale``): backoff on overflow (floor 1.0), grow
    after ``window`` clean updates, count overflows."""
    ovf = jnp.logical_not(finite)
    unsk1 = unskipped + 1
    grow = unsk1 >= window
    scale = jnp.where(ovf, jnp.maximum(scale / factor, 1.0),
                      jnp.where(grow, scale * factor, scale))
    unskipped = jnp.where(jnp.logical_or(ovf, grow),
                          jnp.zeros_like(unskipped), unsk1)
    ovf_total = ovf_total + ovf.astype(ovf_total.dtype)
    return scale, unskipped, ovf_total


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = [params[k] for k in sorted(params.keys())]
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be a list/dict/ParameterDict of Parameter")
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError(f"invalid parameter {p}")
            self._params.append(p)
            self._param2idx[p.name] = i
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_type = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._params_to_init = list(self._params)
        self._fused = None  # fused-update plan cache (None = undecided)
        self._fused_states = {}  # param name -> raw optimizer-state pytree

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx() if param._data is not None or param._deferred_init else None
            if ctx is None:
                continue
            if contexts is not None and set(map(str, ctx)) != set(map(str, contexts)):
                raise MXNetError("All Parameters must be initialized on the same contexts")
            contexts = ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params:
                raise MXNetError(
                    "optimizer_params must be empty if optimizer is an instance"
                )
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)

    def _init_kvstore(self):
        if isinstance(self._kvstore_type, KVStoreBase):
            self._kvstore = self._kvstore_type
        elif self._kvstore_type is None:
            self._kvstore = None
        else:
            n_dev = max(len(self._contexts), 1)
            if n_dev > 1 or (isinstance(self._kvstore_type, str)
                             and self._kvstore_type.startswith("dist")):
                self._kvstore = _create_kvstore(self._kvstore_type)
            else:
                self._kvstore = None  # single device: in-process update
        if self._kvstore is not None and self._compression_params:
            self._kvstore.set_gradient_compression(self._compression_params)
        self._kv_initialized = True

    def _init_params(self):
        if not self._kv_initialized:
            self._init_kvstore()
        remaining = []
        initialized_any = False
        for param in self._params_to_init:
            if param._deferred_init is not None:
                remaining.append(param)
                continue
            initialized_any = True
            if self._kvstore is not None and param._data is not None:
                idx = self._param2idx[param.name]
                self._kvstore.init(idx, param.list_data()[0])
        self._params_to_init = remaining
        if not self._contexts:
            self._contexts = self._check_contexts()
        if initialized_any:
            # new handles exist: any cached fused plan refers to the old
            # ones (or to a "not eligible" verdict reached before init)
            self._invalidate_fused()

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)
        # lr rides into the fused executable as an OPERAND, so a valid
        # plan needs no rebuild (per-step manual scheduling must not
        # retrace); only a cached "not eligible" verdict is re-examined
        if self._fused is False:
            self._invalidate_fused()

    def step(self, batch_size, ignore_stale_grad=False):
        """Scale grads by 1/batch_size, aggregate across devices, update."""
        if _chaos.ENABLED:
            # fault point: kill/term/raise/stall at the Nth step entry
            _chaos.step_point("trainer")
        if _elastic.ENABLED:
            # elasticity pause point: membership signals (preemption
            # notice -> proactive checkpoint) process at the boundary,
            # never mid-step
            _elastic.pause_point("trainer", trainer=self)
        # step-boundary commit protocol: a SIGTERM final checkpoint
        # landing INSIDE this window defers to its exit, so it always
        # snapshots a consistent post-step state
        with _ckptmod.step_critical_section():
            if _obs.introspect.PROFILING:
                # MXTPU_PROFILE window: step-bounded jax.profiler
                # capture, each covered step in a StepTraceAnnotation
                with _obs.introspect.profile_step():
                    out = self._step_instrumented(batch_size,
                                                  ignore_stale_grad)
            else:
                out = self._step_instrumented(batch_size,
                                              ignore_stale_grad)
            mgr = getattr(self, "_ckpt_manager", None)
            if mgr is not None:
                # async checkpoint tick: at an interval boundary this
                # costs one copy dispatch; the write happens off-thread
                mgr.on_step(1)
        return out

    def _step_instrumented(self, batch_size, ignore_stale_grad):
        sp = _obs.span("trainer.step", cat="train")
        if sp is _obs.NO_SPAN:
            self._step_impl(batch_size, ignore_stale_grad)
            return
        t0 = time.perf_counter()
        with sp:
            gnorm = self._step_impl(batch_size, ignore_stale_grad)
            # the tracer's step advances inside the span: its own
            # event, and every one after it, carries the new id
            _obs.tracer().mark_step()
        t1 = time.perf_counter()  # span excludes any probe device sync
        if not _obs.ENABLED:  # a profiler session alone: the span is all
            return
        if gnorm is None:
            # eager update path: grad norm AFTER allreduce — forces one
            # device sync per step (docs/observability.md overhead notes);
            # the fused path computes it in-graph and hands back a LAZY
            # device scalar instead, so there is no extra sync at all
            gnorm = self._grad_norm()
        if isinstance(gnorm, float):
            # only plain floats go into the ring buffer: a lazy device
            # scalar per event would pin one live device buffer per step
            # for the lifetime of the ring (the gauge keeps the latest)
            sp.set(grad_norm=gnorm)
        _obs.record_trainer_step(t0, t1, gnorm)
        if _obs.watchdog.ENABLED:
            # detector sweep at trainer cadence: a monotonic-clock
            # compare per step (MXTPU_WATCHDOG_INTERVAL_S gates the
            # actual sweep) — reads series already recorded above,
            # never adds a dispatch
            _obs.watchdog.poll()
        # multi-process federation exchange at the step boundary: the
        # side-channel collectives must interleave with the training
        # allreduces in the same order on every rank, so they run HERE
        # (same thread as pushpull, step-count beat) and never on the
        # publisher timer thread; no-op unless armed + multi-process
        _obs.federation.poll()

    def _step_impl(self, batch_size, ignore_stale_grad):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._allreduce_grads()
        return self._update(ignore_stale_grad)

    def _grad_norm(self):
        """Global L2 norm of the aggregated gradients (telemetry gauge)."""
        sq = []
        for param in self._params:
            if param.grad_req == "null" or param._data is None:
                continue
            try:
                g = param.list_grad()[0].data
            except Exception:
                continue  # grad never attached: skip, don't break the step
            sq.append(jnp.vdot(g, g).astype(jnp.float32))
        if not sq:
            return 0.0
        total = sq[0]
        for s in sq[1:]:
            total = total + s
        # deliberate eager-path sync, documented in docs/observability.md
        # overhead notes (the fused path returns a LAZY device scalar)
        return float(jnp.sqrt(total))  # mxtpu-lint: host-sync-ok

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        keys, grads = [], []
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            keys.append(i)
            grads.append(param.list_grad())
        if not keys:
            return
        # one multi-key pushpull: the store takes its bucketed (or
        # grouped) fast path — O(1) dispatches instead of one per key
        self._kvstore.pushpull(keys, grads, out=grads)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    # -- fused update fast path ------------------------------------------
    # One jitted executable updates every parameter per step (the analog
    # of the reference's multi-tensor `multi_sgd` kernels) when the
    # optimizer maps onto a pure pytree rule and every param lives on one
    # device. Scheduled lr, clip_gradient, rescale_grad and per-param
    # lr_mult/wd_mult ride in as OPERANDS; momentum/betas stay trace
    # constants. (AdamW excluded: its decoupled wd differs from the
    # shared adam rule.)
    _FUSABLE = {"sgd": ("momentum", "wd"),
                "nag": ("momentum", "wd"),
                "adam": ("beta1", "beta2", "epsilon", "wd"),
                "lamb": ("beta1", "beta2", "epsilon", "wd")}

    def _invalidate_fused(self):
        """Drop the cached fused plan (kept optimizer states survive in
        ``_fused_states``); the next step re-runs eligibility."""
        self._fused = None

    def _fused_setup(self):
        if self._fused is not None:
            return self._fused
        active = [p for p in self._params if p.grad_req != "null"]
        if not active or any(p._data is None or p._deferred_init is not None
                             for p in active):
            # some params not initialized yet (deferred init): decide
            # LATER. Caching False here permanently disabled the fast
            # path for models whose first forward had not run yet — and
            # planning over the initialized SUBSET would silently skip
            # the deferred params once they materialize.
            return False
        self._fused = self._build_fused_plan(active)
        return self._fused

    def _fused_rules(self):
        """Shared optimizer-eligibility gate + pytree rule assembly for
        the one-step fused plan AND the K-step superstep (the two must
        stay in lockstep: a new rule or restriction added here applies
        to both). Returns ``(name, hyper, rule_init, rule_update)``, or
        a decline-reason string when the optimizer has no fused rule."""
        o = self._optimizer
        name = type(o).__name__.lower()
        if name not in self._FUSABLE:
            return f"optimizer '{name}' has no fused pytree rule"
        if name == "lamb" and (
                getattr(o, "lower_bound", None) is not None
                or getattr(o, "upper_bound", None) is not None
                or not getattr(o, "bias_correction", True)):
            return "lamb with bounds/bias_correction=False"

        from ..parallel.spmd import _RULES, mp_rule

        hyper = {k: getattr(o, k) for k in self._FUSABLE[name]
                 if hasattr(o, k)}
        hyper["wd"] = o.wd
        rule_init, rule_update = _RULES[name](hyper)
        if o.multi_precision:
            # fp32 master weights for bf16/fp16 params live as state
            # leaf 0 in the donated pytree (the multi-tensor analog of
            # the reference's mp_sgd/mp_adam kernels)
            rule_init, rule_update = mp_rule(rule_init, rule_update)
        return name, hyper, rule_init, rule_update

    def _fused_sig(self):
        """Hyperparameter signature shared by BOTH compiled-plan
        staleness guards (one-step fused update and superstep): any
        change here means the executable's trace constants are stale
        and the plan must rebuild."""
        o = self._optimizer
        scaler = getattr(self, "_amp_loss_scaler", None)
        return (o.clip_gradient is not None,
                type(o).__name__.lower(),
                _obs.ENABLED,
                o.multi_precision,
                scaler is not None,
                (scaler._factor, scaler._window)
                if scaler is not None else None)

    def _build_fused_plan(self, active):
        o = self._optimizer

        def no(reason):
            _fusedstep.log_fallback("trainer", reason)
            return False

        # (the MXTPU_FUSED_STEP switch is checked once, in
        # _maybe_fused_update — a disabled flag never reaches here)
        rules = self._fused_rules()
        if isinstance(rules, str):
            return no(rules)
        name, hyper, rule_init, rule_update = rules
        if any(p._stype != "default" or p._grad_stype != "default"
               for p in active):
            return no("sparse parameters/gradients")
        # real per-context count: a param replicated on >1 device updates
        # via the update-once-broadcast path, not the fused executable
        if any(len(p._data) != 1 for p in active):
            return no("multi-device parameters")
        handles = [p.data() for p in active]
        grads = [h.grad for h in handles]
        if any(g is None for g in grads):
            return no("gradient buffers not attached")
        idx = [self._param2idx[p.name] for p in active]
        # seeded states are committed to their weight's placement: a
        # fresh zeros_like is uncommitted while the state the executable
        # hands back is committed, and that difference alone is a second
        # compile of the fused update at step 2 (same HLO, new signature)
        states = [tuple(jax.device_put(leaf, h.data.sharding) for leaf in
                        self._restore_fused_state(name, p, i, h.data,
                                                  rule_init))
                  for p, i, h in zip(active, idx, handles)]
        has_clip = o.clip_gradient is not None
        # the in-graph grad-norm gauge reads the whole gradient set once
        # more — only pay that when telemetry is on (toggling telemetry
        # rebuilds the plan via the staleness guard)
        with_gnorm = _obs.ENABLED
        # fp16 AMP: loss scaling runs INSIDE this executable — unscale
        # (folded into rescale), the all-finite check, skip-update via
        # where, and the dynamic scale adjustment; factor/window are
        # trace constants, the scale/counters ride as device operands
        scaler = getattr(self, "_amp_loss_scaler", None)
        has_amp = scaler is not None
        amp_factor = scaler._factor if has_amp else 2.0
        amp_window = scaler._window if has_amp else 0

        # ``unscale_div`` is the factor still LEFT to divide out of the
        # grad buffers (the live scale normally; 1.0 after the user
        # already called amp.unscale, or with no scale_loss pending);
        # ``scale`` always carries the real scale for the backoff/growth
        # arithmetic — the two diverge exactly when amp.unscale ran
        def fused(ws, gs, sts, lr, wd, rescale, clip, lr_mults, wd_mults,
                  scale, unscale_div, unskipped, ovf_total):
            finite = _all_finite(gs) if has_amp else None
            if has_amp:
                rescale = rescale / unscale_div  # unscale rides the rescale
            new_ws, new_sts, gnorm = _apply_fused_update(
                ws, gs, sts, rule_update, lr, wd, rescale, clip,
                lr_mults, wd_mults, has_clip, has_amp, with_gnorm,
                finite, unscale_div)
            if has_amp:
                scale, unskipped, ovf_total = _amp_scale_step(
                    finite, scale, unskipped, ovf_total,
                    amp_factor, amp_window)
            return new_ws, new_sts, gnorm, scale, unskipped, ovf_total

        fused_jit = jax.jit(
            fused,
            donate_argnums=(0, 2) if _fusedstep.DONATE else ())
        # publish the seeded states: ownership lives in _fused_states
        # from build time on, so the superstep (and a rebuilt plan)
        # migrate from here by IDENTITY instead of resetting momentum
        for p, st in zip(active, states):
            self._fused_states[p.name] = st
        return {"fn": fused_jit, "active": active, "handles": handles,
                "grads": grads, "states": states, "idx": idx, "name": name,
                "rule_init": rule_init, "sig": self._fused_sig(),
                "has_clip": has_clip, "mults": None,
                "lr_mults": None, "wd_mults": None,
                # freezing/unfreezing params (grad_req mutation) and a
                # multi_precision toggle change WHICH params the plan
                # covers — the staleness guard compares this signature
                "req_sig": tuple(p.grad_req for p in self._params),
                "amp": has_amp,
                # scaler-shaped neutral operands for the non-amp (and
                # not-pending) case, built ONCE (a fresh jnp scalar per
                # step would be an extra device_put dispatch)
                "amp_neutral": (jnp.asarray(1.0, jnp.float32),
                                jnp.asarray(0, jnp.int32),
                                jnp.asarray(0, jnp.int32)),
                # trace CONSTANTS (momentum/betas/epsilon — wd is an
                # operand): the per-step staleness guard compares these
                # so direct attribute mutation rebuilds instead of
                # silently using baked-in values
                "static_hyper": {k: v for k, v in hyper.items()
                                 if k != "wd"}}

    @staticmethod
    def _mp_low(raw) -> bool:
        from ..amp.policy import is_low_precision_dtype

        return is_low_precision_dtype(raw.dtype)

    def _restore_fused_state(self, name, p, idx, raw, rule_init):
        """Optimizer state for one param: prefer the state a previous
        fused plan left in ``_fused_states``; else migrate a per-param
        eager state (``param._opt_state``); else a fresh init — so
        flipping between paths or rebuilding the plan never resets
        momentum. Under ``multi_precision`` the low-precision params'
        pytrees carry the fp32 master as leaf 0 (see ``spmd.mp_rule``)
        and migration preserves it in both directions."""
        expected = rule_init(raw)
        cached = self._fused_states.get(p.name)
        if cached is not None and len(cached) == len(expected) and all(
                getattr(c, "shape", None) == e.shape
                and c.dtype == e.dtype for c, e in zip(cached, expected)):
            return cached
        st = getattr(p, "_opt_state", None)
        o = self._optimizer
        mp = o.multi_precision and self._mp_low(raw)
        if st is not None:
            # COPIES: the fused executable donates its state buffers, and
            # aliasing the eager NDArray state would kill it. Ownership
            # TRANSFERS to the fused path (the eager copy is deleted) so
            # a later flip back never resurrects a stale state.
            t = o._index_update_count.get(idx, o.begin_num_update)
            prefix = ()
            inner_expected = expected
            inner_st = st
            ok = True
            if mp:
                # eager mp state: (fp32 master NDArray, inner state)
                if isinstance(st, tuple) and len(st) == 2 and \
                        getattr(st[0], "shape", None) == expected[0].shape:
                    prefix = (jnp.copy(st[0].data)
                              .astype(expected[0].dtype),)
                    inner_expected = expected[1:]
                    inner_st = st[1]
                else:
                    ok = False
            migrated = None
            if ok:
                if name in ("sgd", "nag") and len(inner_expected) == 0 \
                        and inner_st is None:
                    migrated = prefix  # momentum=0: master only
                elif name in ("sgd", "nag") and len(inner_expected) == 1 \
                        and getattr(inner_st, "shape", None) \
                        == inner_expected[0].shape:
                    migrated = prefix + (jnp.copy(inner_st.data)
                                         .astype(inner_expected[0].dtype),)
                elif name in ("adam", "lamb") \
                        and isinstance(inner_st, tuple) \
                        and len(inner_st) == 2:
                    m, v = inner_st
                    if getattr(m, "shape", None) == inner_expected[0].shape:
                        migrated = prefix + (
                            jnp.copy(m.data).astype(inner_expected[0].dtype),
                            jnp.copy(v.data).astype(inner_expected[1].dtype),
                            jnp.asarray(t, jnp.int32))
            if migrated is not None:
                del p._opt_state
                return migrated
        if name in ("adam", "lamb") and len(expected) >= 3:
            # fresh state: the bias-correction step count continues from
            # the optimizer's counts (begin_num_update / prior eager
            # steps), matching the eager path's t=_index_update_count
            # (the t leaf is LAST; with a master prefix it sits at 3)
            t0 = o._index_update_count.get(idx, o.begin_num_update)
            if t0:
                expected = expected[:-1] + (jnp.asarray(t0, jnp.int32),)
        return expected

    def _remigrate_states(self, name, rule_init, params, idxs, handles,
                          states):
        """Cross-path state refresh shared by the one-step fused plan
        AND the superstep: when the other compiled path advanced the
        per-param states in ``_fused_states`` since ``states`` were
        seeded (detected by IDENTITY — cheap pointer compares), re-seed
        through ``_restore_fused_state`` and republish, WITHOUT
        rebuilding or retracing the caller's executable. Returns the
        (possibly unchanged) state list."""
        if all(self._fused_states.get(p.name) is st
               for p, st in zip(params, states)):
            return states
        states = [self._restore_fused_state(name, p, i, h.data, rule_init)
                  for p, i, h in zip(params, idxs, handles)]
        for p, st in zip(params, states):
            self._fused_states[p.name] = st
        return states

    def _migrate_fused_to_eager(self, param, idx, weight):
        """Reverse migration: when the eager per-param path takes over
        from the fused one (flag flipped, model turned ineligible), its
        optimizer state seeds from the fused pytree state so momentum is
        never silently reset. Ownership transfers (the fused copy is
        dropped). ``multi_precision`` states rebuild the eager
        ``(fp32 master NDArray, inner)`` pair from the pytree's master
        leaf."""
        from ..ndarray.ndarray import NDArray

        st = self._fused_states.pop(param.name, None)
        if st is None:
            return None
        o = self._optimizer
        name = type(o).__name__.lower()
        mp = o.multi_precision and self._mp_low(weight.data)
        if mp:
            if not st:
                return None
            master = NDArray(jnp.copy(st[0]), ctx=weight.ctx)  # stays f32
            inner = tuple(st[1:])
            mk32 = lambda raw: NDArray(jnp.copy(raw), ctx=weight.ctx)  # noqa: E731
            if name in ("sgd", "nag"):
                if len(inner) == 0:
                    return (master, None)
                if len(inner) == 1:
                    return (master, mk32(inner[0]))
            if name in ("adam", "lamb") and len(inner) == 3:
                m, v, t = inner
                o._index_update_count[idx] = max(
                    o._index_update_count.get(idx, o.begin_num_update),
                    int(t))
                return (master, (mk32(m), mk32(v)))
            return None
        wdt = weight.data.dtype
        mk = lambda raw: NDArray(jnp.copy(raw).astype(wdt),  # noqa: E731
                                 ctx=weight.ctx)
        if name in ("sgd", "nag") and len(st) == 1:
            return mk(st[0])
        if name in ("adam", "lamb") and len(st) == 3:
            m, v, t = st
            o._index_update_count[idx] = max(
                o._index_update_count.get(idx, o.begin_num_update), int(t))
            return (mk(m), mk(v))
        return None

    def _maybe_fused_update(self):
        """Run the fused multi-tensor update; returns the in-graph grad
        norm (lazy device scalar) on success, None on fallback."""
        if not _fusedstep.ENABLED:
            return None
        plan = self._fused_setup()
        if not plan:
            return None
        o = self._optimizer
        scaler = getattr(self, "_amp_loss_scaler", None)
        # staleness guards (pure Python, no device work): hyperparameter
        # shape changes or re-initialized params rebuild the plan
        if (self._fused_sig() != plan["sig"]
                or tuple(p.grad_req for p in self._params) != plan["req_sig"]
                or any(getattr(o, k, None) != v
                       for k, v in plan["static_hyper"].items())
                or any(p._data is None or p.data() is not h or h.grad is not g
                       for p, h, g in zip(plan["active"], plan["handles"],
                                          plan["grads"]))):
            self._invalidate_fused()
            plan = self._fused_setup()
            if not plan:
                return None
        # another path (the K-step superstep) may have advanced the
        # shared per-param states since this plan last ran: re-migrate
        # by IDENTITY — no rebuild, no retrace of the executable
        plan["states"] = self._remigrate_states(
            plan["name"], plan["rule_init"], plan["active"],
            plan["idx"], plan["handles"], plan["states"])
        # advance update counts exactly like the eager per-param path
        for i in plan["idx"]:
            o._index_update_count[i] = o._index_update_count.get(
                i, o.begin_num_update) + 1
            o.num_update = max(o.num_update, o._index_update_count[i])
        mults = tuple((p.lr_mult, p.wd_mult) for p in plan["active"])
        if mults != plan["mults"]:
            plan["mults"] = mults
            plan["lr_mults"] = jnp.asarray([m[0] for m in mults], jnp.float32)
            plan["wd_mults"] = jnp.asarray([m[1] for m in mults], jnp.float32)
        lr = jnp.asarray(o.learning_rate, jnp.float32)  # scheduler-aware
        wd = jnp.asarray(o.wd, jnp.float32)
        rescale = jnp.asarray(o.rescale_grad, jnp.float32)
        clip = jnp.asarray(o.clip_gradient if plan["has_clip"] else 0.0,
                           jnp.float32)
        # fp16 AMP operands: a pending scale_loss block hands its scale
        # in as a device scalar; without one the neutral constants ride
        # along (the executable still skip-protects against non-finite
        # grads, it just leaves the scaler untouched). A pending of
        # "unscaled" (amp.unscale already divided the buffers) keeps
        # the overflow check + scale update armed but must not divide
        # again — unscale_div rides as its own operand.
        pending = plan["amp"] and getattr(self, "_amp_pending", False)
        if pending:
            self._amp_pending = False
            scale_in = scaler._scale_arr
            unsk_in = scaler._unskipped_arr
            div_in = scaler._scale_arr if pending == "scaled" \
                else plan["amp_neutral"][0]
        else:
            scale_in, unsk_in, _ = plan["amp_neutral"]
            div_in = plan["amp_neutral"][0]
        ovf_in = scaler._overflow_total_arr if plan["amp"] \
            else plan["amp_neutral"][2]
        handles = plan["handles"]
        args = ([h.data for h in handles],
                [g.data for g in plan["grads"]],
                plan["states"], lr, wd, rescale, clip,
                plan["lr_mults"], plan["wd_mults"], scale_in, div_in,
                unsk_in, ovf_in)
        if _obs.introspect.ENABLED and not plan.get("introspected"):
            # cost/memory analysis once per plan, from the aval
            # skeleton (the call below donates the live buffers)
            plan["introspected"] = True
            _obs.introspect.register_jit(
                "trainer_fused", plan["fn"],
                _obs.introspect.avals_of(args),
                donated=_fusedstep.DONATE)
        if _obs.flight.INSTALLED or _obs.watching():
            new_ws, new_sts, gnorm, new_scale, new_unsk, new_ovf = \
                _dispatch_call("trainer_fused", "trainer.fused_update",
                               plan["fn"], args)
        else:
            new_ws, new_sts, gnorm, new_scale, new_unsk, new_ovf = \
                plan["fn"](*args)
        if _obs.ENABLED:
            _obs.record_xla_dispatch("trainer_fused")
        for h, w in zip(handles, new_ws):
            h._set_data(w)
        plan["states"] = new_sts
        for p, s in zip(plan["active"], new_sts):
            self._fused_states[p.name] = s
        if plan["amp"]:
            # everything stays a lazy device scalar — zero per-step syncs
            scaler._overflow_total_arr = new_ovf
            if pending:
                scaler._scale_arr = new_scale
                scaler._unskipped_arr = new_unsk
            if _obs.ENABLED:
                _obs.record_amp_lazy(scaler._scale_arr, new_ovf)
        return gnorm

    def _amp_eager_pending(self):
        """Per-param fallback for a deferred ``scale_loss`` block: one
        fused ``isfinite`` reduction decides skip-vs-update, then the
        gradient BUFFERS are divided by the scale in one fused
        executable (``amp.unscale``) — so user-visible grads and the
        eager grad-norm probe see TRUE gradients, exactly like the
        pre-deferral ``scale_loss.__exit__`` semantics. Returns True to
        skip the update (overflow)."""
        scaler = getattr(self, "_amp_loss_scaler", None)
        pending = getattr(self, "_amp_pending", False)
        if scaler is None or not pending:
            return False
        active = [p for p in self._params
                  if p.grad_req != "null" and p._data is not None]
        overflow = scaler.has_overflow(active)  # fallback path: one sync
        if not overflow and pending == "scaled":
            from ..amp import unscale as _amp_unscale

            _amp_unscale(self)  # buffers -> TRUE grads (one executable)
        self._amp_pending = False
        scaler.update_scale(overflow)
        return overflow

    def _update(self, ignore_stale_grad=False):
        gnorm = self._maybe_fused_update()
        if gnorm is not None:
            return gnorm
        if isinstance(self._fused, dict):
            # the eager loop below advances optimizer state the cached
            # plan's `states` copies don't see — a later re-enable of the
            # fast path must rebuild (and re-migrate states) or it would
            # silently rewind momentum to the flip-off point
            self._invalidate_fused()
        if self._amp_eager_pending():
            return None  # hard skip: same semantics as the fused path
        return self._update_eager(ignore_stale_grad)

    def _update_eager(self, ignore_stale_grad=False):
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            datas = param.list_data()
            grads = param.list_grad()
            # after allreduce every device holds the aggregated grad:
            # run the update once, broadcast the new weight
            if not hasattr(param, "_opt_state"):
                param._opt_state = (
                    self._migrate_fused_to_eager(param, i, datas[0])
                    if param.name in self._fused_states else None)
                if param._opt_state is None:
                    param._opt_state = \
                        self._optimizer.create_state_multi_precision(
                            i, datas[0])
            self._optimizer.update_multi_precision(i, datas[0], grads[0],
                                                   param._opt_state)
            for d in datas[1:]:
                d._set_data(datas[0].data)
        return None

    @staticmethod
    def _natural_key(name):
        """Digit-aware sort key: construction order, not lexicographic
        (``dense9_`` was created before ``dense10_`` but sorts after
        it — and Trainer param order is the LEXICOGRAPHIC sort, so two
        models of identical structure can order the same layers
        differently depending on where the global name counter stood)."""
        import re as _re

        return [int(t) if t.isdigit() else t
                for t in _re.split(r"(\d+)", name)]

    def _state_index_map(self, saved_names):
        """saved-state index -> current-param index, aligned by
        construction order (natural sort of names on each side). With
        no saved names (format < 3) the map is identity."""
        n = len(self._params)
        if not saved_names or len(saved_names) != n:
            return {i: i for i in range(n)}
        s_order = sorted(range(n),
                         key=lambda i: self._natural_key(saved_names[i]))
        c_order = sorted(range(n),
                         key=lambda i: self._natural_key(
                             self._params[i].name))
        return dict(zip(s_order, c_order))

    @staticmethod
    def _eager_state_to_np(st, key):
        """Eager per-param optimizer state -> a numpy-only
        ``{"desc", "tensors"}`` pair via the SAME structure flattener
        the resilience checkpoints use (one walk to maintain, two
        on-disk consumers)."""
        import numpy as _np

        from ..resilience.checkpoint import _flatten_state

        if st is None:
            return None
        sink = {}
        desc = _flatten_state(st, key, sink)
        return {"desc": desc,
                "tensors": {k: _np.asarray(v) for k, v in sink.items()}}

    @staticmethod
    def _eager_state_from_np(st):
        from ..resilience.checkpoint import _unflatten_state

        if st is None:
            return None
        if isinstance(st, dict) and "desc" in st:
            return _unflatten_state(
                st["desc"], st["tensors"],
                wrap=lambda raw: NDArray(jnp.asarray(raw)))
        return st  # format-1 file: a pickled state rides through

    def save_states(self, fname):
        """Save optimizer state covering BOTH update paths: the fused /
        superstep per-param pytrees (``_fused_states`` — momentum and
        the adam/lamb bias-correction ``t`` included) AND any eager
        ``_opt_state`` (converted to numpy), plus update counts. A
        model trained fused, saved, loaded, and continued on EITHER
        path keeps its momentum (tests/test_fused_step.py)."""
        import pickle

        import numpy as _np

        states = {
            i: self._eager_state_to_np(getattr(p, "_opt_state", None),
                                       f"s{i}")
            for i, p in enumerate(self._params)
        }
        # fused states keyed by PARAM INDEX, not global name: a fresh
        # model built by the loading process gets new prefixed names
        # (dense7_weight...), but position in the trainer is stable —
        # name-keyed files silently orphaned every entry on reload
        fused_states = {
            i: tuple(_np.asarray(leaf) for leaf in
                     self._fused_states[p.name])
            for i, p in enumerate(self._params)
            if p.name in self._fused_states
        }
        with open(fname, "wb") as f:
            pickle.dump(
                {
                    "format": 2,
                    "states": states,
                    # the saving trainer's param names, in ITS order:
                    # the loader aligns indices by construction order
                    # (lexicographic trainer order flips at the
                    # dense9_/dense10_ digit boundary)
                    "param_names": [p.name for p in self._params],
                    "update_counts": self._optimizer._index_update_count,
                    "num_update": self._optimizer.num_update,
                    "fused_states": fused_states,
                },
                f,
            )

    def load_states(self, fname):
        """Inverse of :meth:`save_states`. Params whose state lives in
        the restored fused store get any stale eager ``_opt_state``
        CLEARED — the eager update path prefers an existing attribute,
        so leaving one would silently shadow the restored momentum
        (the pre-PR-8 bug). The next step on either path re-migrates
        from the restored store without resetting anything."""
        import pickle

        with open(fname, "rb") as f:
            blob = pickle.load(f)
        fmt = blob.get("format", 1)
        n = len(self._params)
        saved_n = len(blob.get("param_names", [])) or \
            len(blob.get("states", {}))
        if fmt >= 2 and saved_n and saved_n != n:
            # the old name-keyed files silently skipped mismatches;
            # silently skipping INDEX-keyed state would pair the wrong
            # layers — refuse with a diagnosis instead
            raise MXNetError(
                f"load_states: file holds state for {saved_n} params, "
                f"this trainer has {n} — the model structure differs")
        idx_map = self._state_index_map(blob.get("param_names")) \
            if fmt >= 2 else {i: i for i in range(n)}
        inv_map = {ci: si for si, ci in idx_map.items()}
        for i, p in enumerate(self._params):
            st = blob["states"].get(inv_map.get(i, i))
            if st is not None:
                p._opt_state = st if fmt < 2 \
                    else self._eager_state_from_np(st)
            elif hasattr(p, "_opt_state"):
                del p._opt_state
        fused = {}
        for key, st in blob.get("fused_states", {}).items():
            if fmt >= 2:
                name = self._params[idx_map.get(int(key), int(key))].name
            else:  # format-1 files were name-keyed
                name = key
            fused[name] = tuple(jnp.asarray(leaf) for leaf in st)
        self._fused_states = fused
        # update counts are keyed by the SAVING trainer's indices: remap
        # through the same alignment as the states, or reordered params
        # would resume with each other's counts (skewed bias-correction)
        self._optimizer._index_update_count = \
            {idx_map.get(int(k), int(k)): int(v)
             for k, v in blob["update_counts"].items()}
        self._optimizer.num_update = int(blob["num_update"])
        self._invalidate_fused()


def _is_execution_error(e) -> bool:
    """True when ``e`` came from EXECUTING a compiled function rather
    than tracing it — after execution starts, donated input buffers may
    already be consumed, so the caller must surface the error instead
    of falling back onto possibly-dead handles. Trace-time failures
    (TracerError/TypeError/ValueError from a capture-unsafe forward)
    are safe to fall back from: nothing ran, nothing was donated."""
    name = type(e).__name__
    return name in ("XlaRuntimeError", "JaxRuntimeError") \
        or isinstance(e, MemoryError)


class Superstep:
    """K-step on-device training superstep: whole-program capture.

    Compiles K full forward + backward + optimizer-update iterations of
    the idiomatic Gluon loop into ONE ``lax.scan`` executable. The scan
    carry is the donated weights + optimizer-state pytree (+ the AMP
    loss-scaler state under fp16); the scanned operands are ``[K, ...]``
    stacked batch slots staged ahead on device by
    :class:`~mxnet_tpu.gluon.data.prefetcher.SuperstepRing`. The host
    touches the loop once per K steps: it reads lazy telemetry gauges,
    applies the in-graph loss-scale backoff/growth results back to the
    scaler, and samples the lr scheduler once per covered update count
    (a [K] lr vector rides the scan operands, so per-iteration
    schedules apply at exactly the single-step loop's cadence).

    >>> sstep = gluon.Superstep(net, loss_fn, trainer, k=8)
    >>> for group, n in gluon.data.SuperstepRing(loader, 8, device=ctx):
    ...     if n == 8:
    ...         losses = sstep.step(group[0], group[1], batch_size)
    ...     else:                       # short tail: single-step it
    ...         sstep.run_single(group, batch_size)

    or just ``sstep.run(loader, batch_size)`` for a whole pass.

    State migrates BOTH ways with the single-step paths: the scan carry
    seeds from (and writes back to) the same per-param state store the
    fused one-step plan and the eager per-param loop use, so mixing
    ``trainer.step`` and supersteps never resets momentum. Ineligible
    models (non-fusable optimizer, kvstore aggregation, sparse params,
    capture-unsafe forward) fall back to the single-step loop with a
    loudly logged reason — never a wrong answer.
    """

    def __init__(self, block, loss_fn, trainer, k=None):
        self._block = block
        self._loss_fn = loss_fn
        self._trainer = trainer
        self._k = max(1, int(k)) if k is not None \
            else _fusedstep.superstep_k()
        self._plan = None  # None = undecided, False = declined (sticky)

    @property
    def k(self):
        return self._k

    def invalidate(self):
        """Drop the cached capture (a declined verdict too); the next
        step re-runs eligibility and re-captures. NB: a re-capture
        recompiles the whole K-step executable — expensive by design,
        so mutate hyperparameters between supersteps sparingly."""
        self._plan = None

    # -- plan build ------------------------------------------------------
    def _setup(self):
        if self._plan is not None:
            return self._plan
        tr = self._trainer
        if not tr._kv_initialized:
            tr._init_kvstore()
        if tr._params_to_init:
            tr._init_params()

        def no(reason):
            _fusedstep.log_fallback("superstep", reason)
            self._plan = False
            return False

        if tr._kvstore is not None:
            return no("kvstore-backed gradient aggregation (use "
                      "SPMDTrainStep.run_superstep on a mesh)")
        o = tr._optimizer
        rules = tr._fused_rules()  # the SAME gate the one-step plan uses
        if isinstance(rules, str):
            return no(rules)
        name, hyper, rule_init, rule_update = rules
        items = sorted(self._block.collect_params().items())
        if not items:
            return no("block has no parameters")
        if any(p._data is None or p._deferred_init is not None
               for _, p in items):
            return False  # deferred init: decide later (not sticky)
        if any(p._stype != "default" or p._grad_stype != "default"
               for _, p in items):
            return no("sparse parameters/gradients")
        if any(len(p._data) != 1 for _, p in items):
            return no("multi-device parameters")
        block_names = {p.name for _, p in items}
        if any(p.grad_req != "null" and p.name not in block_names
               for p in tr._params):
            return no("trainer updates params outside the captured block")
        handles = [p.data() for _, p in items]
        # a block param outside the trainer is carried but never updated
        # (exactly what the plain loop does with it)
        tr_names = {p.name for p in tr._params}
        diff = [p.grad_req != "null" and p.name in tr_names
                for _, p in items]
        if not any(diff):
            return no("no trainable parameters in the captured block")
        diff_pos = [i for i, d in enumerate(diff) if d]
        idx = [tr._param2idx[items[i][1].name] for i in diff_pos]
        # optimizer states seed from wherever they currently live (a
        # previous fused plan, eager per-param state, or fresh) — the
        # same migration the one-step plan uses, so paths interleave
        states = [tr._restore_fused_state(name, items[i][1], ix,
                                          handles[i].data, rule_init)
                  for i, ix in zip(diff_pos, idx)]
        has_clip = o.clip_gradient is not None
        with_gnorm = _obs.ENABLED
        scaler = getattr(tr, "_amp_loss_scaler", None)
        has_amp = scaler is not None
        amp_factor = scaler._factor if has_amp else 2.0
        amp_window = scaler._window if has_amp else 0

        block, loss_fn = self._block, self._loss_fn
        from .block import _TRACE_STATE

        def run_forward(param_raws, x, y, key):
            _TRACE_STATE.active = True
            _random.push_trace_key(key)
            saved = [h._data_ for h in handles]
            saved_ver = [h._version for h in handles]
            try:
                for h, raw in zip(handles, param_raws):
                    h._data_ = raw
                    h._version += 1
                xin, yin = NDArray(x), NDArray(y)
                with autograd._RecordingStateScope(False, True):
                    out = block(xin)
                    loss = loss_fn(out, yin)
                mutated = [h._data_ for h in handles]
                return loss.data, mutated
            finally:
                for h, s, v in zip(handles, saved, saved_ver):
                    h._data_ = s
                    h._version = v
                _random.pop_trace_key()
                _TRACE_STATE.active = False

        def superstep_fn(params, sts, scale, unsk, ovf, xs, ys, keys,
                         lrs, wd, rescale, clip, lr_mults, wd_mults):
            # ``lrs`` is a [K] vector: iteration i applies lrs[i] — the
            # scheduler is sampled PER SCAN ITERATION on the host (K
            # cheap pure-function calls), so lr cadence inside a
            # superstep matches the single-step loop exactly instead of
            # freezing at K-step granularity
            def body(carry, slot):
                params, sts, scale, unsk, ovf = carry
                x, y, key, lr = slot

                def loss_of(dp):
                    full = list(params)
                    for pos, w in zip(diff_pos, dp):
                        full[pos] = w
                    loss_raw, mutated = run_forward(full, x, y, key)
                    # grads of the SUM (what loss.backward()'s ones
                    # cotangent yields); rescale_grad divides by batch
                    lsum = jnp.sum(loss_raw)
                    lmean = jnp.mean(loss_raw).astype(jnp.float32)
                    if has_amp:
                        # in-graph scale_loss: the fp16 loss meets the
                        # f32 scale, promoting exactly like the eager
                        # ``loss * NDArray(scale)``
                        lsum = lsum.astype(jnp.float32) * scale
                    return lsum, (lmean, mutated)

                (_, (lmean, mutated)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)([params[i] for i in diff_pos])
                # per-iteration fp16 skip: one overflowing microbatch
                # leaves only ITS OWN iteration's weights+state
                # untouched — iteration i+1 of the same superstep
                # applies — and the scale backs off/grows in-graph
                finite = _all_finite(grads) if has_amp else None
                it_rescale = rescale / scale if has_amp else rescale
                new_ws, new_sts, gnorm = _apply_fused_update(
                    [params[i] for i in diff_pos], grads, sts,
                    rule_update, lr, wd, it_rescale, clip,
                    lr_mults, wd_mults, has_clip, has_amp, with_gnorm,
                    finite, scale)
                new_params = list(mutated)  # aux (BN stats) carried here
                for pos, w2 in zip(diff_pos, new_ws):
                    new_params[pos] = w2
                # per-iteration overflow flag rides the scan ys so the
                # host sees WHICH iteration skipped, not just a per-K
                # total (in-scan device metrics; zero extra dispatches)
                it_ovf = jnp.logical_not(finite).astype(jnp.float32) \
                    if has_amp else jnp.float32(0.0)
                if has_amp:
                    scale, unsk, ovf = _amp_scale_step(
                        finite, scale, unsk, ovf, amp_factor, amp_window)
                return (new_params, new_sts, scale, unsk, ovf), \
                    (lmean, gnorm, it_ovf)

            (params, sts, scale, unsk, ovf), (losses, gnorms, it_ovfs) = \
                jax.lax.scan(body, (params, sts, scale, unsk, ovf),
                             (xs, ys, keys, lrs))
            return params, sts, scale, unsk, ovf, losses, gnorms, it_ovfs

        fn = jax.jit(superstep_fn,
                     donate_argnums=(0, 1) if _fusedstep.DONATE else ())
        self._plan = {
            "fn": fn, "handles": handles, "items": items, "diff": diff,
            "diff_pos": diff_pos, "idx": idx, "states": states,
            "name": name, "rule_init": rule_init,
            "has_clip": has_clip,
            "mults": None, "lr_mults": None, "wd_mults": None,
            "amp": has_amp, "sig": tr._fused_sig(),
            "req_sig": tuple(p.grad_req for _, p in items),
            "static_hyper": {h: v for h, v in hyper.items() if h != "wd"},
            "neutral": (jnp.asarray(1.0, jnp.float32),
                        jnp.asarray(0, jnp.int32),
                        jnp.asarray(0, jnp.int32)),
            "warm": False,
        }
        # ownership: the scan carry is now the live optimizer state;
        # publish it so the one-step paths (and a later superstep
        # rebuild) migrate from here instead of resetting momentum
        for i, st in zip(diff_pos, states):
            tr._fused_states[items[i][1].name] = st
        return self._plan

    def _refresh_states(self, plan):
        """Re-seed the carry's optimizer states from the shared store
        when another path (trainer.step fused or eager) advanced them
        between supersteps — migration WITHOUT recompiling the scan
        (the shared ``Trainer._remigrate_states`` identity check)."""
        tr = self._trainer
        items, diff_pos = plan["items"], plan["diff_pos"]
        plan["states"] = tr._remigrate_states(
            plan["name"], plan["rule_init"],
            [items[i][1] for i in diff_pos], plan["idx"],
            [plan["handles"][i] for i in diff_pos], plan["states"])

    def _plan_ok(self):
        """Build-or-validate; returns the plan dict or False."""
        plan = self._setup()
        if not plan:
            return False
        tr = self._trainer
        o = tr._optimizer
        if (tr._fused_sig() != plan["sig"]
                or tuple(p.grad_req for _, p in plan["items"])
                != plan["req_sig"]
                or any(getattr(o, h, None) != v
                       for h, v in plan["static_hyper"].items())
                or any(p._data is None or p.data() is not h
                       for (_, p), h in zip(plan["items"],
                                            plan["handles"]))):
            self.invalidate()
            plan = self._setup()
            if not plan:
                return False
        self._refresh_states(plan)
        return plan

    # -- dispatch --------------------------------------------------------
    def step(self, xs, ys, batch_size):
        """Run one superstep over stacked batches: ``xs``/``ys`` carry a
        leading ``[K]`` slot axis (``gluon.data.stack_batches``). One XLA
        dispatch executes all K iterations; returns the K per-iteration
        mean losses as one lazy device NDArray. Falls back to K single
        steps (same numerics, logged reason) when the capture declines.
        """
        raw_x = xs.data if isinstance(xs, NDArray) else jnp.asarray(xs)
        raw_y = ys.data if isinstance(ys, NDArray) else jnp.asarray(ys)
        k = int(raw_x.shape[0])
        tr = self._trainer
        if _chaos.ENABLED:
            # fault points (per-superstep-dispatch counter): process
            # faults at entry; a due ``nan`` fault poisons SLOT 0 only,
            # so "one bad microbatch skips one iteration" is testable
            _chaos.step_point("superstep")
            # dtype check FIRST: nan_due consumes (and counts) a
            # one-shot fault — firing it for an unpoisonable int batch
            # would log an injection that never happened
            if jnp.issubdtype(raw_x.dtype, jnp.floating) and \
                    _chaos.nan_due("superstep"):
                raw_x = raw_x.at[0].set(jnp.nan)
        if _elastic.ENABLED:
            # elasticity pause point: the superstep boundary is the
            # safe place to process membership signals (K iterations
            # commit or none do)
            _elastic.pause_point("superstep", trainer=tr)
        if self._plan is None and any(
                p._data is None
                for _, p in self._block.collect_params().items()):
            # resolve deferred init with one tiny predict pass on a
            # slot-0 slice (never consumes an update). Only while no
            # plan exists: the walk is per-dispatch host work, and a
            # built plan's staleness guard already covers re-init.
            with autograd.predict_mode():
                self._block(NDArray(raw_x[0][:1]))
        plan = self._plan_ok() if _fusedstep.ENABLED else False
        if not plan:
            # declined (sticky) or still deferred (re-decided next
            # group): same numerics through the single-step loop
            losses = self.run_single(
                [(NDArray(raw_x[i]), NDArray(raw_y[i])) for i in range(k)],
                batch_size)
            return NDArray(jnp.stack([l.data for l in losses]))
        # step-boundary commit protocol: the whole fused window (count
        # advance -> dispatch -> write-back -> manager tick) is ONE
        # critical section — a SIGTERM final checkpoint landing inside
        # it (a preemption mid-scan) defers to the section exit, i.e.
        # the last COMPLETED K-boundary, never a half-applied carry
        with _ckptmod.step_critical_section():
            return self._step_fused(plan, raw_x, raw_y, k, batch_size)

    def _step_fused(self, plan, raw_x, raw_y, k, batch_size):
        tr = self._trainer
        o = tr._optimizer
        scaler = getattr(tr, "_amp_loss_scaler", None)
        # host bookkeeping, once per K steps: update counts advance by
        # K; the scheduler is sampled PER ITERATION — scan slot i rides
        # lr(first_update + i), exactly the count the single-step loop
        # would have used (K pure host calls; the [K] lr vector is an
        # operand, so a schedule change never retraces)
        first_update = None
        prev_num_update = o.num_update
        for ix in plan["idx"]:
            c = o._index_update_count.get(ix, o.begin_num_update) + k
            o._index_update_count[ix] = c
            o.num_update = max(o.num_update, c)
            first_update = c - k + 1 if first_update is None \
                else max(first_update, c - k + 1)
        o.rescale_grad = tr._scale / batch_size
        if o.lr_scheduler is not None:
            lr_vals = [o.lr_scheduler(first_update + i) for i in range(k)]
        else:
            lr_vals = [o.learning_rate] * k
        mults = tuple((p.lr_mult, p.wd_mult)
                      for i, (_, p) in enumerate(plan["items"])
                      if plan["diff"][i])
        if mults != plan["mults"]:
            plan["mults"] = mults
            plan["lr_mults"] = jnp.asarray([m[0] for m in mults],
                                           jnp.float32)
            plan["wd_mults"] = jnp.asarray([m[1] for m in mults],
                                           jnp.float32)
        lr = jnp.asarray(lr_vals, jnp.float32)
        wd = jnp.asarray(o.wd, jnp.float32)
        rescale = jnp.asarray(o.rescale_grad, jnp.float32)
        clip = jnp.asarray(o.clip_gradient if plan["has_clip"] else 0.0,
                           jnp.float32)
        if plan["amp"]:
            if getattr(tr, "_amp_pending", False):
                # an orphaned scale_loss backward never met its
                # trainer.step; the superstep scales in-graph and never
                # reads the grad buffers, so consume the stale flag —
                # left armed, the NEXT direct trainer.step would divide
                # fresh UNSCALED grads by the scale
                tr._amp_pending = False
            scale_in = scaler._scale_arr
            unsk_in = scaler._unskipped_arr
            ovf_in = scaler._overflow_total_arr
        else:
            scale_in, unsk_in, ovf_in = plan["neutral"]
        keys = jax.random.split(_random._next_key(), k)
        handles = plan["handles"]
        args = ([h.data for h in handles], plan["states"],
                scale_in, unsk_in, ovf_in, raw_x, raw_y, keys,
                lr, wd, rescale, clip,
                plan["lr_mults"], plan["wd_mults"])
        t0 = time.perf_counter()
        try:
            out = self._dispatch(plan, args, k)
        except Exception as e:
            # no update was applied: roll back the count advance so the
            # scheduler/update bookkeeping stays true to what actually
            # ran (num_update included — the recovery path's real steps
            # must not sample the schedule K steps ahead)
            for ix in plan["idx"]:
                o._index_update_count[ix] -= k
            o.num_update = prev_num_update
            if plan["warm"] or _is_execution_error(e):
                # an EXECUTION failure (OOM, preemption, lost device —
                # warm or first run alike): donation may have consumed
                # the live buffers, so surface it rather than silently
                # single-stepping on possibly-dead handles
                raise
            # cold TRACE failure = capture-unsafe forward: fall back
            # loudly (nothing was donated/mutated if tracing raised)
            reason = f"capture failed: {type(e).__name__}: {e}"
            self._plan = False
            _fusedstep.log_fallback("superstep", reason[:200])
            losses = self.run_single(
                [(NDArray(raw_x[i]), NDArray(raw_y[i]))
                 for i in range(k)], batch_size)
            return NDArray(jnp.stack([l.data for l in losses]))
        plan["warm"] = True
        new_params, new_sts, new_scale, new_unsk, new_ovf, losses, \
            gnorms, it_ovfs = out
        t1 = time.perf_counter()
        for h, w in zip(handles, new_params):
            h._set_data(w)
        plan["states"] = new_sts
        for i, st in zip(plan["diff_pos"], new_sts):
            tr._fused_states[plan["items"][i][1].name] = st
        # no plan invalidation needed: the one-step fused path detects
        # the _fused_states identity change and re-migrates its state
        # copies without rebuilding/retracing its executable
        if plan["amp"]:
            scaler._scale_arr = new_scale
            scaler._unskipped_arr = new_unsk
            scaler._overflow_total_arr = new_ovf
        if _obs.ENABLED:
            _obs.record_xla_dispatch("superstep")
            _obs.record_superstep(k, t0, t1, gnorms[-1])
            # per-iteration in-scan series (loss / grad-norm / overflow
            # flag), stored WHOLE and LAZY — per-step metric cadence at
            # K-step dispatch cadence, zero added dispatches
            _obs.record_superstep_series(losses, gnorms, it_ovfs)
            if plan["amp"]:
                _obs.record_amp_lazy(scaler._scale_arr, new_ovf)
            if _obs.watchdog.ENABLED:
                # superstep-cadence detector sweep (interval-gated);
                # the lazy loss/grad series above sync inside the
                # watchdog, not here — zero added dispatches
                _obs.watchdog.poll()
            # step-beat federation exchange on the superstep thread —
            # identically ordered vs the training collectives on every
            # rank (no-op unless armed + multi-process)
            _obs.federation.poll()
        mgr = getattr(tr, "_ckpt_manager", None)
        if mgr is not None:
            # one superstep = K training steps for checkpoint cadence
            # (the fallback path ticks per-step through tr.step instead)
            mgr.on_step(k)
        return NDArray(losses)

    def _dispatch(self, plan, args, k):
        """One compiled superstep invocation, with the optional slow-
        path instrumentation (cost registration, the ``MXTPU_PROFILE``
        window's state machine, the program's span, flight-recorder
        in-flight marking) kept off the default path."""
        intro = _obs.introspect
        armed = _obs.introspect.PROFILING  # MXTPU_PROFILE's step window
        if not (intro.ENABLED or armed or _obs.flight.INSTALLED
                or _obs.watching()):
            return plan["fn"](*args)
        if intro.ENABLED and not plan.get("introspected"):
            plan["introspected"] = True
            intro.register_jit("superstep", plan["fn"],
                               intro.avals_of(args),
                               donated=_fusedstep.DONATE)
        with intro.profile_step(k, name="superstep") if armed \
                else _obs.NO_SPAN:
            return _dispatch_call("superstep", "trainer.superstep_dispatch",
                                  plan["fn"], args)

    # -- fallback / tail -------------------------------------------------
    def run_single(self, batches, batch_size):
        """Run ``batches`` (``(x, y)`` pairs) through the normal
        single-step loop — the tail of an epoch whose last group came up
        short, or the fallback for declined captures. Same numerics as
        user-written record/backward/step. Returns per-batch mean-loss
        NDArrays."""
        tr = self._trainer
        scaler = getattr(tr, "_amp_loss_scaler", None)
        losses = []
        for x, y in batches:
            with autograd.record():
                loss = self._loss_fn(self._block(x), y)
                if scaler is not None:
                    from .. import amp as _amp

                    with _amp.scale_loss(loss, tr) as scaled:
                        scaled.backward()
            if scaler is None:
                loss.backward()
            tr.step(batch_size)
            losses.append(NDArray(jnp.mean(loss.data)))
        return losses

    @staticmethod
    def _split_xy(batch):
        if isinstance(batch, (list, tuple)) and len(batch) >= 2:
            return batch[0], batch[1]
        if batch.__class__.__name__ == "DataBatch" \
                and hasattr(batch, "data"):
            return batch.data[0], batch.label[0]
        raise MXNetError(
            "Superstep.run expects (x, y) batches or DataBatch; use "
            "step(xs, ys, batch_size) for custom structures")

    def run(self, source, batch_size, device=None, mesh=None):
        """One pass over ``source`` (DataLoader / DataIter / iterable /
        an existing ``SuperstepRing``): full K-groups run as one
        dispatch each, a short tail single-steps. Returns the per-step
        mean losses as floats (one device sync, at the end)."""
        from .data.prefetcher import SuperstepRing

        ring = source if isinstance(source, SuperstepRing) \
            else SuperstepRing(source, self._k, device=device, mesh=mesh)
        out = []
        try:
            for group, n in ring:
                # n == RING.k <=> a stacked full group (the ring only
                # yields raw batch LISTS for short tails, which always
                # have n < ring.k) — the ring's own k is the authority:
                # comparing against self._k would mistake a tail of
                # exactly self._k batches for a stacked block when the
                # caller passed a ring with a different k. The stacked
                # batch itself may well BE a list (the DataLoader
                # default batchify yields [x, y]).
                if n == ring.k:
                    x, y = self._split_xy(group)
                    out.append(self.step(x, y, batch_size))
                else:
                    out.extend(self.run_single(
                        [self._split_xy(b) for b in group], batch_size))
        finally:
            ring.close()
        if not out:
            return []
        import numpy as _np

        # ONE device->host transfer: concatenate the lazy per-group
        # loss arrays on device first (syncing each of the ~steps/K
        # results serially would re-add the per-dispatch RTT the
        # superstep amortizes away)
        joined = jnp.concatenate(
            [jnp.atleast_1d(l.data).astype(jnp.float32) for l in out])
        return _np.asarray(joined).tolist()
