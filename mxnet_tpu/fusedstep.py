"""Fused-train-step policy: one switch, one fallback funnel.

The O(1)-dispatch training fast path (shared-residual CachedOp backward,
bucketed gradient allreduce, generalized fused optimizer update — see
docs/performance.md) is coordinated from here so the three layers agree:

- ``ENABLED`` — THE switch, seeded from ``MXTPU_FUSED_STEP`` (default on).
- ``DONATE`` — buffer donation inside the fast path's executables, seeded
  from ``MXTPU_FUSED_DONATE`` (default on; a no-op on the CPU backend).
- ``bucket_bytes()`` — target flat-bucket size for the kvstore gradient
  allreduce, from ``MXTPU_BUCKET_BYTES`` (default 4 MiB).
- ``log_fallback(site, reason)`` — every place the fast path declines a
  model funnels through here: the reason is logged LOUDLY once per
  (site, reason) and counted in the telemetry registry, so "why is my
  step slow" is one grep (the fallback is never silent, and never wrong
  answers — the general per-param path takes over).
"""

from __future__ import annotations

import logging

from .base import getenv

#: Master switch for the fused train step (block/kvstore/trainer fast
#: paths). Flip at runtime with set_enabled(); hybridized blocks pick the
#: change up on their next call (the flag is part of the CachedOp key).
ENABLED = bool(getenv("MXTPU_FUSED_STEP", True, dtype=bool))

#: Donate weight/optimizer-state/residual buffers to the fused
#: executables (XLA reuses the memory in place). Off-switch for the
#: retain_graph / aliased-output caveats in docs/performance.md.
DONATE = bool(getenv("MXTPU_FUSED_DONATE", True, dtype=bool))

_BUCKET_BYTES_DEFAULT = 4 << 20

# NB: XLA:CPU does not implement donation, so on the CPU backend jax
# warns "Some donated buffers were not usable" once per donated
# executable — harmless there (the fast path is correct either way).
# We deliberately do NOT install a process-global warnings filter: on a
# real accelerator that warning flags a genuinely failed donation, and
# user code must be able to see it. The test suite filters it locally
# (tests/conftest.py).

_logger = logging.getLogger("mxnet_tpu.fusedstep")
_LOGGED: set = set()


def enabled() -> bool:
    return ENABLED


def set_enabled(on: bool) -> bool:
    """Flip the fused step at runtime; returns the previous state."""
    global ENABLED
    prev, ENABLED = ENABLED, bool(on)
    return prev


def donate_enabled() -> bool:
    return DONATE


def bucket_bytes() -> int:
    """Target gradient-bucket payload size (bytes)."""
    return int(getenv("MXTPU_BUCKET_BYTES", _BUCKET_BYTES_DEFAULT,
                      dtype=int))


_AMP_AR_DTYPES = ("bfloat16", "float16")
_AMP_AR_WARNED = [False]


def amp_allreduce_dtype() -> str:
    """Reduced-precision gradient allreduce dtype from
    ``MXTPU_AMP_ALLREDUCE_DTYPE`` ("" = off, the default). When set to
    ``bfloat16``/``float16``, fp32 gradient buckets are cast down
    before crossing the wire (halving ICI/DCN bytes) and summed with
    fp32 accumulation on the other side — see docs/performance.md
    "mixed precision". Unknown values are ignored with one loud
    warning (a typo must not silently change training numerics)."""
    v = getenv("MXTPU_AMP_ALLREDUCE_DTYPE", "", dtype=str) or ""
    if v and v not in _AMP_AR_DTYPES:
        if not _AMP_AR_WARNED[0]:
            _AMP_AR_WARNED[0] = True
            _logger.warning(
                "MXTPU_AMP_ALLREDUCE_DTYPE=%r is not one of %s; "
                "gradient allreduce stays full precision", v, _AMP_AR_DTYPES)
        return ""
    return v


#: K-step superstep: how many full fwd+bwd+update iterations one
#: gluon.Superstep dispatch runs on device (MXTPU_SUPERSTEP_K, default
#: 1 = today's one-step behavior). Mutable at runtime for tests.
SUPERSTEP_K = max(1, int(getenv("MXTPU_SUPERSTEP_K", 1, dtype=int)))


def superstep_k() -> int:
    """Default iteration count per on-device training superstep
    (``MXTPU_SUPERSTEP_K``). 1 means every ``gluon.Superstep`` dispatch
    covers a single step — exactly the PR-3/5 fused behavior, just
    captured whole-program. Raising K amortizes the per-step host round
    trip (batch feed, loss-scale bookkeeping, telemetry) over K steps;
    see docs/performance.md "superstep" for choosing K."""
    return SUPERSTEP_K


def set_superstep_k(k: int) -> int:
    """Set the default superstep K at runtime; returns the previous
    value. Existing Superstep objects keep the K they were built with."""
    global SUPERSTEP_K
    prev, SUPERSTEP_K = SUPERSTEP_K, max(1, int(k))
    return prev


_OVERLAP_MODES = ("ready", "barrier", "staged")


def overlap_mode() -> str:
    """Gradient-communication scheduling for the mesh train step
    (``MXTPU_OVERLAP``): ``ready`` (default, and what ``1`` means) —
    per-bucket allreduce issued inside the compiled step as soon as the
    bucket's last contributing gradient exists (readiness order from
    the VJP structure; XLA's latency-hiding scheduler overlaps the
    collectives with the remaining backward compute); ``barrier`` (or
    ``0``) — same single executable, but an optimization barrier holds
    every collective until the whole backward finished (the parity/
    ablation baseline); ``staged`` — the legacy host-driven
    architecture: backward dispatch, then bucket-allreduce dispatch,
    then update dispatch (comm fully exposed; kept for measurement).
    Unknown values fall back to ``ready`` with one loud warning."""
    v = str(getenv("MXTPU_OVERLAP", "ready", dtype=str) or "ready").lower()
    v = {"1": "ready", "true": "ready", "on": "ready",
         "0": "barrier", "false": "barrier", "off": "barrier"}.get(v, v)
    if v not in _OVERLAP_MODES:
        key = ("fusedstep", f"MXTPU_OVERLAP={v!r}")
        if key not in _LOGGED:
            _LOGGED.add(key)
            _logger.warning("MXTPU_OVERLAP=%r is not one of %s; using "
                            "'ready'", v, _OVERLAP_MODES)
        return "ready"
    return v


def overlap_bucket_bytes() -> int:
    """Target bucket payload for the in-graph overlapped allreduce
    (``MXTPU_OVERLAP_BUCKET_BYTES``; defaults to ``MXTPU_BUCKET_BYTES``
    so the in-graph and kvstore bucket plans agree unless tuned apart —
    smaller buckets start communicating earlier, larger ones amortize
    per-collective latency better)."""
    v = getenv("MXTPU_OVERLAP_BUCKET_BYTES", None, dtype=int)
    return int(v) if v else bucket_bytes()


_PIPELINE_SCHEDULES = ("gpipe", "1f1b", "interleaved")


def pipeline_schedule() -> str:
    """Default pipeline schedule for ``PipelineTrainStep`` /
    ``Composed4DStep`` (``MXTPU_PIPELINE_SCHEDULE``): ``gpipe``
    (default — fill-drain, bubble (S-1)/(M+S-1), activation stash grows
    with M), ``1f1b`` (same bubble, stash capped at the stage depth —
    the memory schedule), ``interleaved`` (1F1B over v virtual stage
    chunks per rank — divides the bubble by v; requires the stacked
    stage count to be a multiple of the ``pp`` axis). Unknown values
    warn once and fall back to ``gpipe``. See docs/performance.md
    "choosing a 4D layout"."""
    v = str(getenv("MXTPU_PIPELINE_SCHEDULE", "gpipe", dtype=str)
            or "gpipe").lower()
    if v not in _PIPELINE_SCHEDULES:
        key = ("fusedstep", f"MXTPU_PIPELINE_SCHEDULE={v!r}")
        if key not in _LOGGED:
            _LOGGED.add(key)
            _logger.warning("MXTPU_PIPELINE_SCHEDULE=%r is not one of %s; "
                            "using 'gpipe'", v, _PIPELINE_SCHEDULES)
        return "gpipe"
    return v


def pipeline_microbatches() -> int:
    """Default microbatch count for the pipeline schedules
    (``MXTPU_PIPELINE_MICROBATCHES``, default 0 = one per pipeline
    stage). More microbatches shrink the fill/drain bubble
    (bubble ~ (S-1)/(M+S-1)) at the cost of smaller per-microbatch
    matmuls; see docs/performance.md "choosing a 4D layout"."""
    return max(0, int(getenv("MXTPU_PIPELINE_MICROBATCHES", 0, dtype=int)))


_MOE_ROUTERS = ("top1", "top2")


def moe_router() -> str:
    """Default MoE router (``MXTPU_MOE_ROUTER``): ``top1`` (default —
    Switch-style, one expert per token) or ``top2`` (GShard-style, two
    experts with normalized combine weights + the load-balancing aux
    loss). Unknown values warn once and fall back to ``top1``."""
    v = str(getenv("MXTPU_MOE_ROUTER", "top1", dtype=str) or "top1").lower()
    if v not in _MOE_ROUTERS:
        key = ("fusedstep", f"MXTPU_MOE_ROUTER={v!r}")
        if key not in _LOGGED:
            _LOGGED.add(key)
            _logger.warning("MXTPU_MOE_ROUTER=%r is not one of %s; using "
                            "'top1'", v, _MOE_ROUTERS)
        return "top1"
    return v


def moe_capacity_factor() -> float:
    """Default expert capacity factor (``MXTPU_MOE_CAPACITY_FACTOR``,
    default 1.5): per-expert slot count = ceil(tokens/experts * factor).
    Tokens past capacity drop to the residual path (output 0 for that
    token's expert contribution) — raise for exactness, lower for
    speed/memory. See docs/performance.md "choosing a 4D layout"."""
    v = getenv("MXTPU_MOE_CAPACITY_FACTOR", None, dtype=float)
    return float(v) if v else 1.5


def moe_a2a_chunks() -> int:
    """Expert-dispatch chunking for the in-graph MoE all-to-all
    (``MXTPU_MOE_A2A_CHUNKS``, default 2): the capacity buffer splits
    into this many chunks, each dispatched as its own ``all_to_all`` so
    XLA's latency-hiding scheduler overlaps chunk k+1's wire time with
    chunk k's expert FFN — the bucket-allreduce trick applied to expert
    parallelism. 1 = single all-to-all (no overlap; the measurement
    baseline)."""
    return max(1, int(getenv("MXTPU_MOE_A2A_CHUNKS", 2, dtype=int)))


def zero_stage() -> int:
    """Default ZeRO sharding stage for ``SPMDTrainStep``
    (``MXTPU_ZERO_STAGE``, default 0): 0 = replicated optimizer state,
    1 = sharded optimizer state (GSPMD sharding constraints, the
    legacy ``shard_opt_states=True``), 2 = reduce-scattered gradients +
    flat-sharded optimizer state + allgathered updated params, 3 =
    params sharded at rest too, allgathered just-in-time inside the
    step. See docs/performance.md "scale-out"."""
    s = int(getenv("MXTPU_ZERO_STAGE", 0, dtype=int))
    if s not in (0, 1, 2, 3):
        key = ("fusedstep", f"MXTPU_ZERO_STAGE={s}")
        if key not in _LOGGED:
            _LOGGED.add(key)
            _logger.warning("MXTPU_ZERO_STAGE=%s is not 0-3; using 0", s)
        return 0
    return s


def elastic_enabled() -> bool:
    """Live-elasticity master switch (``MXTPU_ELASTIC``, default off):
    arms the membership-monitor pause points in ``Trainer.step`` /
    ``Superstep.step`` (``resilience/elastic.py``) so preemption
    notices and resize signals are processed at safe step boundaries.
    Attaching a ``MembershipMonitor`` programmatically arms them too;
    when off, each pause point costs one module-bool read. See
    docs/robustness.md "Runtime elasticity"."""
    return bool(getenv("MXTPU_ELASTIC", False, dtype=bool))


_RETRACE_BUDGET_DEFAULT = 8


def retrace_budget() -> int:
    """Per-block budget of DISTINCT input-shape signatures a CachedGraph
    may compile before the telemetry flags ``shape_wobble`` loudly
    (``MXTPU_RETRACE_BUDGET``, default 8). Shape churn — partial last
    batches, unbucketed variable-length text — silently multiplies
    compile time and cache footprint; the budget turns that into one
    grep-able warning + counter instead (docs/performance.md, "input
    pipeline"). 0 disables the check."""
    return int(getenv("MXTPU_RETRACE_BUDGET", _RETRACE_BUDGET_DEFAULT,
                      dtype=int))


def log_fallback(site: str, reason: str):
    """Record that ``site`` declined the fast path because of ``reason``.

    Logged at WARNING once per (site, reason) per process — loud enough
    to see, quiet enough to train through — and counted per-label in the
    telemetry registry when telemetry is on.
    """
    from . import observability as _obs

    if _obs.ENABLED:
        _obs.FUSED_FALLBACK_TOTAL.inc(1, site=site, reason=reason)
    key = (site, reason)
    if key not in _LOGGED:
        _LOGGED.add(key)
        _logger.warning(
            "fused step: %s falling back to the general path (%s); "
            "set MXTPU_FUSED_STEP=0 to silence the fast path entirely",
            site, reason)


def reset_fallback_log():
    """Forget which (site, reason) pairs were already logged (tests)."""
    _LOGGED.clear()
