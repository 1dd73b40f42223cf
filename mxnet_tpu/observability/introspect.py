"""Performance introspection: XLA cost/memory accounting per executable
site, an MFU/roofline estimator, and step-bounded profiler windows.

PRs 3-6 collapsed training into one dispatch per step (or per K steps),
which made the host-side telemetry blind exactly where the time now
goes: inside compiled executables. This module opens that box:

- **Executable cost/memory accounting** (``MXTPU_INTROSPECT=1`` or
  ``set_enabled(True)``): every cached executable site — CachedOp
  fwd/bwd, the fused ``Trainer`` update, ``gluon.Superstep``,
  ``SPMDTrainStep``, kvstore gradient buckets — registers its
  ``lowered.compile().cost_analysis()`` / ``memory_analysis()`` once at
  build time: FLOPs, HBM bytes accessed, arithmetic intensity,
  temp/argument/output bytes, and donation verification (a donated
  buffer the compiled program did NOT alias is warned loudly — on a
  real accelerator that silently doubles peak memory). Backends lacking
  the analyses degrade to ``None`` fields, never an error.
- **MFU / roofline estimator**: per-site achieved-vs-peak from the
  device peak tables below (``mfu_estimate``), and a formatted
  ``cost_table()``; ``tools/telemetry_report.py`` renders the same
  table from a dumped trace (each registration also records one
  ``introspect.cost`` trace event carrying the full record).
- **Profiler windows**: ``MXTPU_PROFILE=<dir>[:start:stop]`` arms
  ``jax.profiler`` step-bounded trace capture — the window opens when
  the global step counter reaches ``start`` (default 1) and closes
  after ``stop`` (default ``start+9``); every covered ``Trainer.step``
  / ``Superstep.step`` is wrapped in a
  ``jax.profiler.StepTraceAnnotation``. ``profile_window(logdir)`` is
  the programmatic context-manager form.

Cost note: registration runs one extra ``lower().compile()`` per site
(JAX's AOT path does not share the jit call cache; with
``MXTPU_COMPILE_CACHE`` wired the XLA compile itself is a cache hit).
That is why introspection is opt-in and registration happens once per
site, at build time — the steady-state hot path pays one module-bool
read.
"""

from __future__ import annotations

import contextlib
import logging
import threading

from ..base import getenv

_logger = logging.getLogger("mxnet_tpu.introspect")

#: THE switch: cost/memory registration is skipped entirely when False.
#: Seeded from MXTPU_INTROSPECT (default off).
ENABLED = bool(getenv("MXTPU_INTROSPECT", False, dtype=bool))

_LOCK = threading.Lock()
_COSTS: dict = {}  # site -> cost record dict
_WARNED_DONATION: set = set()

#: mxtpu-graphcheck capture callback (tools/mxtpu_lint/graphcheck/).
#: When installed, every registration ALSO traces the site's jaxpr and
#: hands ``(site, jaxpr, compiled, rec, donated, meta)`` to the hook so
#: the compiled-artifact contract checker sees exactly what each hot
#: site lowered — no second tracing pipeline, no drift from what runs.
_GRAPH_HOOK = None


def set_graph_hook(cb):
    """Install (or clear, with ``None``) the graphcheck capture
    callback; returns the previous hook. The hook must never raise into
    training — exceptions are swallowed with a warning."""
    global _GRAPH_HOOK
    prev, _GRAPH_HOOK = _GRAPH_HOOK, cb
    return prev


def _graph_notify(site, jaxpr, compiled, rec, donated, meta):
    hook = _GRAPH_HOOK
    if hook is None:
        return
    try:
        hook(site, jaxpr, compiled, dict(rec) if rec else {},
             bool(donated), dict(meta) if meta else {})
    except Exception as e:  # the checker must never take training down
        _logger.warning("graphcheck hook failed for site %r: %s: %s",
                        site, type(e).__name__, e)


def enabled() -> bool:
    return ENABLED


def set_enabled(on: bool) -> bool:
    """Flip executable introspection at runtime; returns the previous
    state. Already-built executables register on their next dispatch."""
    global ENABLED
    prev, ENABLED = ENABLED, bool(on)
    return prev


def reset():
    """Drop every registered site record (tests)."""
    with _LOCK:
        _COSTS.clear()
        _WARNED_DONATION.clear()


# ---------------------------------------------------------------------------
# device peak tables (per chip). FLOPs: bf16 dense peak. HBM: GB/s.
# Sources: public TPU system specs; the CPU backend has no meaningful
# peak, so MFU degrades to None with a reason there.
# ---------------------------------------------------------------------------

_PEAK_TFLOPS = {
    "TPU v6 lite": 918.0,   # v6e
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v4": 275.0,
    "TPU v3": 123.0,
    "TPU v2": 46.0,
}

_PEAK_HBM_GBS = {
    "TPU v6 lite": 1640.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5p": 2765.0,
    "TPU v5": 2765.0,
    "TPU v4": 1228.0,
    "TPU v3": 900.0,
    "TPU v2": 700.0,
}


def device_peaks():
    """``(peak_tflops, peak_hbm_gbs, reason)`` for device 0 of the
    current backend; the peaks are None (with the reason filled) when
    the device kind has no table entry (CPU, unknown PJRT plugins)."""
    try:
        import jax

        kind = jax.devices()[0].device_kind
    except Exception as e:  # backend not initializable
        return None, None, f"backend unavailable: {type(e).__name__}"
    for k, v in _PEAK_TFLOPS.items():
        if kind.startswith(k):
            return v, _PEAK_HBM_GBS.get(k), None
    return None, None, f"no peak-FLOPs table for device kind {kind!r}"


# ---------------------------------------------------------------------------
# cost/memory registration
# ---------------------------------------------------------------------------

def _cost_dict(compiled):
    """``compiled.cost_analysis()`` → dict or None (some PJRT plugins
    return None/raise)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    return ca if isinstance(ca, dict) else None


def _mem_stats(compiled):
    try:
        return compiled.memory_analysis()
    except Exception:
        return None


def _num(d, key):
    """A float field from a (possibly partial) cost dict, else None."""
    if not isinstance(d, dict):
        return None
    v = d.get(key)
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def analyze_compiled(site, compiled, donated=False):
    """Build one site cost record from a ``Compiled`` object. Every
    field degrades independently to ``None`` — a backend returning
    ``None`` or a partial dict from either analysis must never break
    registration (tested in tests/test_introspect.py)."""
    ca = _cost_dict(compiled)
    ma = _mem_stats(compiled)
    flops = _num(ca, "flops")
    nbytes = _num(ca, "bytes accessed")
    rec = {
        "site": site,
        "flops": flops,
        "bytes_accessed": nbytes,
        "transcendentals": _num(ca, "transcendentals"),
        "arith_intensity": (flops / nbytes)
        if flops is not None and nbytes else None,
        "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
        "output_bytes": getattr(ma, "output_size_in_bytes", None),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
        "generated_code_bytes": getattr(
            ma, "generated_code_size_in_bytes", None),
        "donated": bool(donated),
    }
    peak_tf, peak_bw, peak_reason = device_peaks()
    rec["peak_tflops"] = peak_tf
    rec["peak_hbm_gbs"] = peak_bw
    if peak_reason:
        rec["peak_reason"] = peak_reason
    return rec


def _verify_donation(rec):
    """Warn LOUDLY (once per site) when buffers were donated but the
    compiled program aliased none of them: the donation silently failed
    and peak memory holds both copies. ``alias_bytes`` None (no memory
    analysis on this backend) is indeterminate — stay quiet."""
    if not rec["donated"]:
        return
    alias = rec.get("alias_bytes")
    if alias is None or alias > 0:
        return
    site = rec["site"]
    if site in _WARNED_DONATION:
        return
    _WARNED_DONATION.add(site)
    from . import DONATION_UNALIASED_TOTAL, ENABLED as _TEL

    if _TEL:
        DONATION_UNALIASED_TOTAL.inc(1, site=site)
    _logger.warning(
        "introspect: executable %r donated its input buffers but the "
        "compiled program aliased 0 bytes — donation FAILED (expected on "
        "the CPU backend, which never aliases; on an accelerator this "
        "doubles the site's peak memory)", site)


def registered(site) -> bool:
    """Lock-free already-registered probe (a plain dict containment
    read under the GIL): hot paths call this BEFORE building the
    ``avals_of`` skeleton, so a registered site costs one dict lookup
    per dispatch instead of an O(n_params) tree_map + lock."""
    return site in _COSTS


def avals_of(args):
    """Shape/dtype skeleton of an argument pytree, captured BEFORE a
    donating call (the live buffers may be consumed by it)."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") and hasattr(a, "dtype") else a, args)


def register_jit(site, jit_fn, args, donated=False, force=False,
                 graph_meta=None):
    """Register cost/memory analysis for ``jit_fn`` called with
    ``args`` (concrete arrays or the ``avals_of`` skeleton) under site
    name ``site``. One-shot per site unless ``force``; a no-op when
    introspection is disabled. Never raises: an un-lowerable function
    or an analysis-less backend records a stub with ``error`` set.
    ``graph_meta`` annotates the site for mxtpu-graphcheck (e.g. a
    sanctioned baked-constant exemption) and is only consulted when a
    graph hook is installed."""
    if not ENABLED:
        return None
    with _LOCK:
        if site in _COSTS and not force:
            return _COSTS[site]
    jaxpr = None
    compiled = None
    try:
        if _GRAPH_HOOK is not None and hasattr(jit_fn, "trace"):
            try:
                jaxpr = jit_fn.trace(*args).jaxpr
            except Exception:
                jaxpr = None  # un-traceable: the hook still sees memory
        compiled = jit_fn.lower(*args).compile()
        rec = analyze_compiled(site, compiled, donated=donated)
    except Exception as e:  # introspection must never take training down
        rec = {"site": site, "flops": None, "bytes_accessed": None,
               "donated": bool(donated),
               "error": f"{type(e).__name__}: {e}"[:200]}
    _publish(rec)
    _graph_notify(site, jaxpr, compiled, rec, donated, graph_meta)
    return rec


def register_compiled(site, compiled, donated=False, force=False,
                      jaxpr=None, graph_meta=None):
    """Register an already-compiled executable (AOT / SPMD paths).
    Callers that kept the traced ``jaxpr`` may pass it through for
    mxtpu-graphcheck; without it only the memory-level checks see the
    site."""
    if not ENABLED:
        return None
    with _LOCK:
        if site in _COSTS and not force:
            return _COSTS[site]
    rec = analyze_compiled(site, compiled, donated=donated)
    _publish(rec)
    _graph_notify(site, jaxpr, compiled, rec, donated, graph_meta)
    return rec


def _publish(rec):
    site = rec["site"]
    with _LOCK:
        _COSTS[site] = rec
    _verify_donation(rec)
    # gauges + one trace event carrying the whole record — this is what
    # tools/telemetry_report.py's roofline table reads from a dump
    from . import (
        ENABLED as _TEL,
        EXEC_ALIAS_BYTES,
        EXEC_ARG_BYTES,
        EXEC_ARITH_INTENSITY,
        EXEC_BYTES_ACCESSED,
        EXEC_FLOPS,
        EXEC_OUT_BYTES,
        EXEC_TEMP_BYTES,
        tracer,
    )

    if _TEL:
        for gauge, key in ((EXEC_FLOPS, "flops"),
                           (EXEC_BYTES_ACCESSED, "bytes_accessed"),
                           (EXEC_ARITH_INTENSITY, "arith_intensity"),
                           (EXEC_TEMP_BYTES, "temp_bytes"),
                           (EXEC_ARG_BYTES, "argument_bytes"),
                           (EXEC_OUT_BYTES, "output_bytes"),
                           (EXEC_ALIAS_BYTES, "alias_bytes")):
            if rec.get(key) is not None:
                gauge.set(rec[key], site=site)
    tracer().record("introspect.cost", cat="introspect", dur=0.0,
                    args=dict(rec), ph="i")


def costs() -> dict:
    """``{site: record}`` snapshot of every registered executable."""
    with _LOCK:
        return {k: dict(v) for k, v in _COSTS.items()}


def site_cost(site):
    with _LOCK:
        rec = _COSTS.get(site)
        return dict(rec) if rec else None


def flops_per_step(sites=None):
    """Sum of registered per-invocation FLOPs over ``sites`` (default:
    the one-dispatch train-step trio). Returns ``(flops, reason)`` —
    flops None with the reason filled when nothing usable registered.
    A superstep site's FLOPs cover K iterations; divide by K yourself.
    """
    if sites is None:
        snap = costs()
        sites = [s for s in snap
                 if s.startswith(("cachedop_fwd", "cachedop_bwd"))
                 or s in ("trainer_fused", "spmd_step")]
    total, seen = 0.0, 0
    for s in sites:
        rec = site_cost(s)
        if rec is None:
            continue
        if rec.get("flops") is None:
            return None, rec.get(
                "error", f"backend reports no cost analysis for {s!r}")
        total += rec["flops"]
        seen += 1
    if not seen:
        return None, "no executable sites registered " \
                     "(MXTPU_INTROSPECT off, or nothing dispatched yet)"
    return total, None


def mfu_estimate(site, step_seconds):
    """Achieved-vs-peak for one site: ``{"achieved_tflops", "mfu",
    "bound", "reason"}``. ``mfu`` is None with a reason on backends
    without a peak table or cost analysis. Gated on the runtime feature
    set — ``Features()["INTROSPECTION"]`` — so environments that stub
    it out degrade to the reason string instead of wrong numbers."""
    from ..runtime import Features

    out = {"site": site, "achieved_tflops": None, "mfu": None,
           "bound": None, "reason": None}
    try:
        if not Features().is_enabled("INTROSPECTION"):
            out["reason"] = "INTROSPECTION feature disabled"
            return out
    except Exception:
        pass
    rec = site_cost(site)
    if rec is None:
        out["reason"] = f"site {site!r} not registered"
        return out
    flops = rec.get("flops")
    if flops is None:
        out["reason"] = rec.get("error",
                                "backend reports no cost analysis")
        return out
    if not step_seconds or step_seconds <= 0:
        out["reason"] = "no step timing"
        return out
    out["achieved_tflops"] = flops / step_seconds / 1e12
    ai = rec.get("arith_intensity")
    peak_tf, peak_bw = rec.get("peak_tflops"), rec.get("peak_hbm_gbs")
    if peak_tf is None:
        out["reason"] = rec.get("peak_reason", "no peak-FLOPs table")
        return out
    out["mfu"] = out["achieved_tflops"] / peak_tf
    if ai is not None and peak_bw:
        ridge = peak_tf * 1e12 / (peak_bw * 1e9)  # flops/byte
        out["bound"] = "compute" if ai >= ridge else "memory"
    return out


def cost_table() -> str:
    """Human-readable per-site roofline table of every registered
    executable (the in-process twin of telemetry_report's section)."""
    snap = costs()
    if not snap:
        return "introspect: no executables registered " \
               "(set MXTPU_INTROSPECT=1 before building)"
    lines = ["Executable cost/memory (per invocation):",
             f"{'Site':<34}{'GFLOPs':>10}{'MiB acc':>10}{'AI':>8}"
             f"{'Temp MiB':>10}{'Alias MiB':>10}{'Donated':>9}"]
    for site in sorted(snap):
        rec = snap[site]

        def fmt(key, scale, nd=2):
            v = rec.get(key)
            return f"{v / scale:.{nd}f}" if v is not None else "-"

        lines.append(
            f"{site:<34}{fmt('flops', 1e9):>10}"
            f"{fmt('bytes_accessed', 2**20):>10}"
            f"{fmt('arith_intensity', 1.0, 1):>8}"
            f"{fmt('temp_bytes', 2**20):>10}"
            f"{fmt('alias_bytes', 2**20):>10}"
            f"{'yes' if rec.get('donated') else 'no':>9}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# profiler windows (jax.profiler)
# ---------------------------------------------------------------------------

def _parse_profile_env(value):
    """``<dir>[:start:stop]`` → (dir, start, stop). Bare dir defaults
    to steps [1, 10]; the trailing two fields must both be ints (a
    path containing ':' is otherwise kept whole)."""
    parts = value.split(":")
    if len(parts) >= 3 and parts[-1].isdigit() and parts[-2].isdigit():
        start = max(int(parts[-2]), 1)
        return ":".join(parts[:-2]), start, max(int(parts[-1]), start)
    start = 1
    return value, start, start + 9


_PROFILE = {
    "dir": None, "start": 0, "stop": 0,
    "active": False, "done": False, "step": 0, "captures": 0,
}

#: True while a MXTPU_PROFILE window is armed: the switch of
#: ``profile_step``'s window state machine and of nothing else (the
#: program's spans ask the profiler itself whether a session is open).
PROFILING = False


def configure_profile(logdir, start=1, stop=None):
    """Arm a step-bounded profiler window: capture starts when the
    step counter reaches ``start`` and stops after ``stop``."""
    global PROFILING
    _PROFILE.update(dir=logdir, start=max(int(start), 1),
                    stop=int(stop) if stop is not None else int(start) + 9,
                    active=False, done=False, step=0)
    PROFILING = logdir is not None
    return dict(_PROFILE)


def _maybe_arm_from_env():
    v = getenv("MXTPU_PROFILE", None)
    if v:
        d, start, stop = _parse_profile_env(str(v))
        configure_profile(d, start, stop)


def profile_state() -> dict:
    return dict(_PROFILE)


def _start_trace():
    import jax

    try:
        jax.profiler.start_trace(_PROFILE["dir"])
        _PROFILE["active"] = True
        _PROFILE["captures"] += 1
        _logger.info("profiler window OPEN at step %d -> %s",
                     _PROFILE["step"], _PROFILE["dir"])
    except Exception as e:  # profiler plugin missing/busy: disarm loudly
        _PROFILE["done"] = True
        global PROFILING
        PROFILING = False  # steps go back to the zero-cost path
        _logger.warning("profiler window failed to open: %s: %s",
                        type(e).__name__, e)


def _stop_trace():
    import jax

    try:
        jax.profiler.stop_trace()
    except Exception as e:
        _logger.warning("profiler stop_trace failed: %s: %s",
                        type(e).__name__, e)
    _PROFILE["active"] = False
    _PROFILE["done"] = True
    global PROFILING
    PROFILING = False
    _logger.info("profiler window CLOSED after step %d", _PROFILE["step"])


@contextlib.contextmanager
def profile_step(k=1, name="train"):
    """Wrap one ``Trainer.step`` / K-step superstep dispatch: advances
    the window state machine (open at ``start``, close after ``stop``)
    and annotates the covered region with
    ``jax.profiler.StepTraceAnnotation`` so the device trace aligns
    with host step numbers. Call only when ``PROFILING`` is True."""
    import jax

    first = _PROFILE["step"] + 1
    _PROFILE["step"] += int(k)
    if (not _PROFILE["active"] and not _PROFILE["done"]
            and _PROFILE["dir"] and _PROFILE["step"] >= _PROFILE["start"]):
        _start_trace()
    if _PROFILE["active"]:
        try:
            with jax.profiler.StepTraceAnnotation(name, step_num=first):
                yield
        finally:
            if _PROFILE["step"] >= _PROFILE["stop"]:
                _stop_trace()
    else:
        yield


@contextlib.contextmanager
def profile_window(logdir):
    """Programmatic capture: everything inside the block lands in one
    ``jax.profiler`` trace under ``logdir`` (open in TensorBoard or
    Perfetto), the program's own spans (``observability.span``) beside
    the device's operations as ``mx:<name>``."""
    import jax

    jax.profiler.start_trace(logdir)
    _PROFILE["captures"] += 1
    try:
        yield logdir
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            _logger.warning("profile_window stop failed: %s: %s",
                            type(e).__name__, e)


def annotate(name):
    """Alias of ``observability.span`` from before the program had one
    span; live whenever a profiler session is, whoever opened it."""
    from . import span

    return span(name, cat="profile")


_maybe_arm_from_env()
