"""Imperative op dispatch + tape recording.

Reference call stack being replaced (SURVEY.md §3.1):
``_imperative_invoke -> MXImperativeInvokeEx -> Imperative::Invoke ->
Engine::PushAsync -> FCompute kernel``.

TPU-native: one Python hop. Arrays are unwrapped, the cached XLA executable
for (op, attrs) runs asynchronously (JAX dispatch ≈ the dependency engine:
results are futures; the Python thread does not block), and outputs are
wrapped back into NDArrays. When ``autograd.record()`` is active and any
input is tracked, the op is computed through ``jax.vjp`` and a TapeNode is
linked (reference: ``Imperative::RecordOp``).
"""

from __future__ import annotations

import jax

from .. import autograd, engine
from .. import observability as _obs
from .registry import OpDef, jitted


def _maybe_sync(res):
    """NaiveEngine analog (SURVEY §5.2): with MXTPU_SYNC_EXEC=1, block
    until the dispatched computation finishes so errors surface at the
    faulting op instead of at the next sync point."""
    if engine.sync_exec_enabled():
        engine.wait(res)
    return res


def _run_timed(opdef, fn, raw):
    """Execute ``fn(*raw)``; with profiler aggregate stats on, block and
    attribute wall time to the op (reference: ``AggregateStats`` hooks in
    the engine's operator execution path). The same seam feeds the
    observability registry per-op count/time when telemetry is on —
    WITHOUT blocking (dispatch wall time only), so it is cheap enough to
    leave on during training.

    Each eager op here is ONE compiled-executable invocation, so this
    seam also feeds ``mxtpu_xla_dispatch_total{site="op"}`` (via
    ``record_op_dispatch``) — the counter the fused-train-step
    regression tests assert stays O(1) per step: a hybridized step
    routes around this per-op path entirely (CachedOp fwd/bwd, bucketed
    kvstore, fused update each count their own site)."""
    from .. import profiler

    aggregate = profiler.aggregate_enabled()
    if not (aggregate or _obs.ENABLED or _obs.introspect.ENABLED):
        return fn(*raw)
    if _obs.introspect.ENABLED and hasattr(fn, "lower") \
            and not _obs.introspect.registered(f"op[{opdef.name}]"):
        # per-(op) executable cost/memory accounting — one registration
        # covers every later call of the op (first attrs-variant wins);
        # non-jittable ops (data-dependent shapes) have no executable
        _obs.introspect.register_jit(
            f"op[{opdef.name}]", fn, _obs.introspect.avals_of(tuple(raw)))
    if not (aggregate or _obs.ENABLED):
        return fn(*raw)
    import time

    t0 = time.perf_counter()
    res = fn(*raw)
    dispatch_dt = time.perf_counter() - t0  # before any blocking wait:
    if aggregate:                           # the telemetry metric stays
        engine.wait(res)                    # dispatch-only either way
        profiler.record_op(opdef.name, time.perf_counter() - t0)
    if _obs.ENABLED:
        _obs.record_op_dispatch(opdef.name, dispatch_dt)
    return res


_MONITOR = None


def _tap_monitor(opdef, result):
    """Per-op output tap (reference: the engine monitor callback behind
    ``MXExecutorSetMonitorCallback``); no-op unless a Monitor called
    ``install_ops()``."""
    global _MONITOR
    if _MONITOR is None:
        from .. import monitor as _MONITOR_mod

        _MONITOR = _MONITOR_mod
    if _MONITOR.OP_TAP_ON:
        _MONITOR.tap_op(opdef.name, result)
    return result


def _unwrap(x):
    from ..ndarray.ndarray import NDArray

    return x.data if isinstance(x, NDArray) else x


def apply_op(opdef: OpDef, args, kwargs, out=None):
    """Execute a registered op on NDArray/scalar args. Returns NDArray(s)."""
    from ..ndarray.ndarray import NDArray, _wrap_result

    raw = [_unwrap(a) for a in args]
    ctx = None
    for a in args:
        if isinstance(a, NDArray):
            ctx = a.ctx
            break

    if autograd.is_recording():
        tracked_idx = [
            i
            for i, a in enumerate(args)
            if isinstance(a, NDArray) and autograd.is_tracked(a)
        ]
        if tracked_idx:
            return _apply_recorded(opdef, args, raw, kwargs, tracked_idx, ctx, out)

    res = _maybe_sync(_run_timed(opdef, jitted(opdef, kwargs), raw))
    return _tap_monitor(opdef, _wrap_result(res, ctx, out))


def _apply_recorded(opdef, args, raw, kwargs, tracked_idx, ctx, out):
    from ..ndarray.ndarray import NDArray, _wrap_result

    fn = jitted(opdef, kwargs)
    tracked_raw = [raw[i] for i in tracked_idx]

    def f(*t):
        full = list(raw)
        for i, v in zip(tracked_idx, t):
            full[i] = v
        return fn(*full)

    res, vjp_fn = _run_timed(opdef, lambda *t: jax.vjp(f, *t), tracked_raw)
    _maybe_sync(res)
    result = _wrap_result(res, ctx, out)
    outs = result if isinstance(result, (list, tuple)) else [result]

    node = autograd.TapeNode(
        vjp_fn, [args[i] for i in tracked_idx], len(outs), name=opdef.name
    )
    node._replay = (f, tracked_raw)  # for grad(create_graph=True)
    node._sym_info = (list(args), dict(kwargs))  # for get_symbol export
    node.out_arrays = list(outs)
    for k, o in enumerate(outs):
        o._ag = (node, k)
    return _tap_monitor(opdef, result)


def invoke(name, *args, **kwargs):
    """Invoke an op by registry name (testing/debug helper)."""
    from .registry import get

    out = kwargs.pop("out", None)
    return apply_op(get(name), args, kwargs, out=out)
