"""Runtime feature detection (reference: ``python/mxnet/runtime.py`` +
``src/libinfo.cc``) and persistent-compilation-cache wiring."""

from __future__ import annotations

import logging
import os
import random
import time

import jax

_logger = logging.getLogger("mxnet_tpu.runtime")

#: process-local RNG for retry jitter, seeded from OS entropy — every
#: process in a fleet draws a DIFFERENT backoff sequence, which is the
#: whole point (never seed this from a shared config value)
_RETRY_RNG = random.Random()


# ---------------------------------------------------------------------------
# transient-failure retry (a shared primitive: backend bring-up,
# collective setup and the kvstore barrier all retry through here
# instead of each growing its own loop)
# ---------------------------------------------------------------------------

def backoff_delays(attempts, base_delay, max_delay=30.0, jitter=True,
                   rng=None):
    """The sleep schedule ``retry_with_backoff`` walks, as a list of
    ``attempts - 1`` floats. With ``jitter`` (the default) it is
    DEcorrelated jitter (AWS-style): ``d_i = min(max_delay,
    uniform(base_delay, 3 * d_{i-1}))``, seeded per process — a fleet
    of replicas reconnecting after a coordinator blip spreads out
    instead of thundering-herding it in lockstep. ``jitter=False``
    keeps the old deterministic linear ramp (``base_delay * i``) for
    callers that need reproducible timing."""
    attempts = max(1, int(attempts))
    base_delay = float(base_delay)
    if not jitter:
        return [base_delay * i for i in range(1, attempts)]
    r = rng if rng is not None else _RETRY_RNG
    delays, prev = [], base_delay
    for _ in range(attempts - 1):
        prev = min(float(max_delay), r.uniform(base_delay, max(base_delay,
                                                               prev * 3.0)))
        delays.append(prev)
    return delays


def retry_with_backoff(fn, attempts=3, base_delay=2.0, desc="operation",
                       retry_on=(Exception,), no_retry=(), logger=None,
                       jitter=True, max_delay=30.0, rng=None,
                       sleep=time.sleep):
    """Call ``fn()`` up to ``attempts`` times with backoff between
    tries (decorrelated jitter by default — see :func:`backoff_delays`;
    ``jitter=False`` restores the deterministic linear ramp), logging
    each failure LOUDLY. Re-raises the last exception when every
    attempt fails — a transient infra hiccup retries, a real failure
    still surfaces (never silently swallowed). Exception types in
    ``no_retry`` surface IMMEDIATELY (e.g. a barrier watchdog timeout:
    the peers are gone, and re-entering the same barrier tag after
    abandoning a still-blocked watchdog thread could double-join)."""
    log = logger or _logger
    attempts = max(1, int(attempts))
    delays = backoff_delays(attempts, base_delay, max_delay=max_delay,
                            jitter=jitter, rng=rng)
    last = None
    for i in range(1, attempts + 1):
        try:
            return fn()
        except retry_on as e:  # noqa: PERF203 - retry loop by design
            if no_retry and isinstance(e, no_retry):
                raise
            last = e
            log.warning("%s attempt %d/%d failed: %s: %s", desc, i,
                        attempts, type(e).__name__, str(e)[:300])
            if i < attempts:
                sleep(delays[i - 1])
    raise last


def init_backend(attempts=3):
    """Resolve the JAX backend with retry + backoff. Returns
    ``(backend_name, None)`` or ``(None, error_string)`` — one
    transient 'Unable to initialize backend' at startup must not erase
    a run (VERDICT r5)."""
    try:
        return retry_with_backoff(jax.default_backend, attempts=attempts,
                                  desc="backend init"), None
    except Exception as e:
        return None, f"{type(e).__name__}: {e}"[:300]

# ---------------------------------------------------------------------------
# persistent compilation cache (MXTPU_COMPILE_CACHE)
# ---------------------------------------------------------------------------
# The reference never recompiled across restarts (kernels were AOT .so
# code); XLA recompiles every executable per process, which on a pod is
# minutes of startup per restart. JAX's persistent cache keys compiled
# executables by (HLO, compile options, backend version) in a shared
# directory; wiring it behind one env var makes restart N cost tracing
# only. Hit/miss counts land in the telemetry registry
# (mxtpu_compile_cache_{hit,miss}_total) via jax.monitoring.

_CACHE_STATE = {"dir": None, "listener": False}


def setup_compile_cache(path=None):
    """Enable JAX's persistent compilation cache at ``path`` (or
    ``$MXTPU_COMPILE_CACHE``). Idempotent; called automatically the
    first time a ``Context`` is created. Returns the active cache dir,
    or None when unconfigured.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside: JAX reads that variable itself, so this sets NO directory,
    whatever ``path`` and ``MXTPU_COMPILE_CACHE`` say (a second
    directory set in code would never be found again by whoever placed
    the first). The thresholds and the hit/miss listener still apply."""
    from .base import getenv

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or path or getenv("MXTPU_COMPILE_CACHE")
    if not path:
        return _CACHE_STATE["dir"]
    path = os.path.abspath(os.path.expanduser(str(path)))
    if _CACHE_STATE["dir"] == path:
        return path
    if not placed:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache EVERY executable: the defaults skip sub-second compiles,
    # which is exactly the many-small-executables regime the fused step
    # produces (and the whole of the CPU test tier)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _CACHE_STATE["dir"] = path
    if not _CACHE_STATE["listener"]:
        _CACHE_STATE["listener"] = True
        import jax.monitoring as _mon

        from . import observability as _obs

        def _on_event(name, **kwargs):
            if name == "/jax/compilation_cache/cache_hits":
                _obs.COMPILE_CACHE_HITS.inc()
            elif name == "/jax/compilation_cache/cache_misses":
                _obs.COMPILE_CACHE_MISSES.inc()

        _mon.register_event_listener(_on_event)
    return path


def compile_cache_dir():
    """The active persistent-compile-cache directory (or None)."""
    return _CACHE_STATE["dir"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


class Features(dict):
    """Queryable feature set (reference: ``mx.runtime.Features``)."""

    def __init__(self):
        backend = "cpu"
        try:
            backend = jax.default_backend()
        except Exception:
            pass
        feats = {
            "TPU": backend not in ("cpu", "gpu"),
            "CUDA": False,
            "CUDNN": False,
            "XLA": True,
            "PJIT": True,
            "PALLAS": True,
            "MKLDNN": False,
            "OPENCV": _has_pillow(),
            "DIST_KVSTORE": True,
            # >2^31-element arrays: value ops (create/elementwise/
            # reduce/matmul rows) work on host at any size, but
            # INDEX-producing ops (argmax/argsort/take, big slice
            # offsets) need int64 index types, which JAX only enables
            # globally via jax_enable_x64 — report accordingly
            # (reference: MXNET_INT64_TENSOR_SIZE build flag;
            # tests/test_large_tensor.py; docs/design_decisions.md)
            "INT64_TENSOR_SIZE": bool(jax.config.jax_enable_x64),
            "COMPILE_CACHE": _CACHE_STATE["dir"] is not None,
            # XLA cost/memory analysis + MFU/roofline estimation
            # (observability.introspect); the estimator checks this
            # feature and degrades to null-with-reason when disabled
            "INTROSPECTION": True,
            "SIGNAL_HANDLER": True,
            "F16C": True,
            "BF16": True,
        }
        super().__init__({k: Feature(k, v) for k, v in feats.items()})

    def is_enabled(self, name):
        return self[name.upper()].enabled


def _has_pillow():
    try:
        import PIL  # noqa: F401

        return True
    except ImportError:
        return False


def feature_list():
    return list(Features().values())
