"""Bucket-ready overlapped gradient communication + ZeRO shard math.

The reference hid data-parallel communication behind backward compute by
scheduling per-key push/pull through the dependency engine (SURVEY §2.5
P1/P2): a gradient's allreduce could start the moment that gradient was
produced, while the engine kept executing the rest of backward. The
TPU-native analog lives here: gradient **readiness order** is computed
from the VJP structure (reverse-mode AD produces grads roughly in
reverse order of each parameter's first forward use), buckets are
composed in that order so a bucket's *last* contributor arrives early,
and each bucket's collective is issued inside the SAME compiled step the
backward runs in — XLA's latency-hiding scheduler (async collectives /
start-done pairs on TPU) then overlaps the wire time with the remaining
backward compute. No host round trip ever sits between "gradient ready"
and "collective issued"; mxtpu-lint's ``overlap-window-sync`` rule
machine-checks that invariant.

Three comm flavors over one :class:`BucketPlan`:

- :func:`bucket_allreduce` — ``lax.psum`` per bucket (ZeRO-0/1),
- :func:`bucket_reduce_scatter` — ``lax.psum_scatter`` per bucket,
  handing each rank only its 1/N gradient shard (ZeRO-2/3),
- both optionally behind :func:`jax.lax.optimization_barrier` (the
  ``barrier`` ablation mode: comm can't start before backward ends),
  and both optionally through in-graph 2-bit compression
  (:func:`compress_bucket`) with per-rank residual carry.

Everything here is pure and trace-safe: usable inside ``jax.jit``,
``shard_map`` and ``lax.scan`` bodies (the K-step superstep scans a step
whose body calls these helpers).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend.core import Var


# ---------------------------------------------------------------------------
# readiness order from the VJP structure
# ---------------------------------------------------------------------------

def first_use_order(fn, example_args, n_diff):
    """Gradient readiness order for ``fn(diff_params, *rest)``.

    Traces ``fn`` (``jax.make_jaxpr``) and records, for each of the
    first ``n_diff`` flattened inputs, the index of the first equation
    consuming it. Reverse-mode AD emits each parameter's gradient near
    the (reversed) position of its first forward use, so sorting by
    DESCENDING first-use index approximates the order grads become
    available during backward. Returns a permutation of
    ``range(n_diff)`` (grad index of the earliest-ready gradient
    first), or None when the trace yields no signal (e.g. the whole
    forward collapsed into one fused call) — callers fall back to
    reversed parameter order, the classic DDP heuristic. A trace that
    raises is the caller's error too (the step traces the same
    forward next), so it propagates.
    """
    jaxpr = jax.make_jaxpr(fn)(*example_args).jaxpr
    # diff params are the FIRST pytree argument: its leaves are the
    # first n_diff flat invars (callers pass them as a list of raw
    # arrays, each one leaf)
    targets = jaxpr.invars[:n_diff]
    first = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, Var) and v not in first:
                first[v] = i
    idxs = [first.get(v, -1) for v in targets]
    if len(set(idxs)) <= 1:
        return None  # no signal: one mega-equation consumed all
    return sorted(range(n_diff), key=lambda k: (-idxs[k], k))


# ---------------------------------------------------------------------------
# bucket plan
# ---------------------------------------------------------------------------

class BucketPlan:
    """Readiness-ordered, dtype-homogeneous gradient bucketing.

    ``buckets``: tuple of tuples of gradient indices, in ISSUE order
    (bucket 0's collective can go on the wire first). ``shapes`` /
    ``dtypes`` / ``sizes`` are per-gradient (original order);
    ``pad_sizes`` is the per-gradient flat length padded up to a
    multiple of ``dp`` (equal to ``sizes`` when ``dp`` is 1 — padding
    only matters for the reduce-scatter layout).
    """

    __slots__ = ("buckets", "shapes", "dtypes", "sizes", "pad_sizes",
                 "order", "dp")

    def __init__(self, buckets, shapes, dtypes, sizes, pad_sizes, order,
                 dp):
        self.buckets = tuple(tuple(b) for b in buckets)
        self.shapes = tuple(tuple(s) for s in shapes)
        self.dtypes = tuple(dtypes)
        self.sizes = tuple(sizes)
        self.pad_sizes = tuple(pad_sizes)
        self.order = tuple(order)
        self.dp = int(dp)

    def __len__(self):
        return len(self.buckets)


def _ceil_to(n, m):
    return ((int(n) + m - 1) // m) * m if m > 1 else int(n)


def build_bucket_plan(shapes, dtypes, order=None, bucket_bytes=None,
                      dp=1):
    """Greedy ~``bucket_bytes`` dtype-homogeneous packing in readiness
    order. ``order`` is the issue order from :func:`first_use_order`
    (default: reversed index order — last parameter's grad is produced
    first). ``dp`` > 1 additionally pads every gradient's flat length
    to a multiple of ``dp`` so reduce-scatter shards stay aligned
    per-gradient (a gradient never straddles two ranks' chunks)."""
    from .. import fusedstep as _fusedstep

    n = len(shapes)
    if order is None:
        order = list(range(n - 1, -1, -1))
    target = max(int(bucket_bytes if bucket_bytes is not None
                     else _fusedstep.overlap_bucket_bytes()), 1)
    sizes = []
    for shape in shapes:
        c = 1
        for d in shape:
            c *= int(d)
        sizes.append(c)
    pad_sizes = [_ceil_to(s, dp) for s in sizes]
    buckets = []
    open_by_dtype = {}
    for gi in order:
        dt = str(dtypes[gi])
        nbytes = pad_sizes[gi] * jnp.dtype(dtypes[gi]).itemsize
        cur = open_by_dtype.get(dt)
        if cur is None or (cur[1] and cur[1] + nbytes > target):
            cur = [[], 0]
            open_by_dtype[dt] = cur
            buckets.append(cur)
        cur[0].append(gi)
        cur[1] += nbytes
    return BucketPlan([b for b, _ in buckets], shapes, dtypes, sizes,
                      pad_sizes, order, dp)


# ---------------------------------------------------------------------------
# flat-shard math (ZeRO-2/3 layout)
# ---------------------------------------------------------------------------

def pad_flat(arr, pad_size):
    """Flatten + zero-pad one array to ``pad_size`` elements."""
    flat = arr.reshape(-1)
    if pad_size > flat.shape[0]:
        flat = jnp.pad(flat, (0, pad_size - flat.shape[0]))
    return flat

def unpad_reshape(flat, size, shape):
    """Inverse of :func:`pad_flat` (drops the pad tail)."""
    return flat[:size].reshape(shape)


def shard_of(full, plan_or_dp, axis_name, gi=None):
    """This rank's ``[pad/dp]`` flat shard of one full array — inside a
    ``shard_map`` body (``lax.axis_index`` picks the row)."""
    if isinstance(plan_or_dp, BucketPlan):
        dp = plan_or_dp.dp
        pad = plan_or_dp.pad_sizes[gi]
    else:
        dp = int(plan_or_dp)
        pad = _ceil_to(full.size, dp)
    rows = pad_flat(full, pad).reshape(dp, pad // dp)
    return jax.lax.dynamic_index_in_dim(
        rows, jax.lax.axis_index(axis_name), axis=0, keepdims=False)


def _chaos_point(site):
    """Trace-time chaos fault point for the in-graph collectives: these
    helpers run under tracing (inside jit/shard_map/scan bodies), so a
    due one-shot ``collective`` fault (``MXTPU_CHAOS=collective@<site>``)
    surfaces as a LOUD build/step failure at the issue point — never
    wrong numerics, and zero extra dispatches when chaos is off (one
    module-bool read behind a lazy import)."""
    from ..resilience import chaos as _chaos

    if _chaos.ENABLED:
        _chaos.collective_point(site)


def gather_shard(shard, axis_name):
    """All ranks' ``[pad/dp]`` shards -> the full ``[pad]`` flat array
    (``lax.all_gather`` tiled on the existing axis)."""
    _chaos_point("bucket_allgather")
    return jax.lax.all_gather(shard, axis_name, tiled=True)


# ---------------------------------------------------------------------------
# in-graph 2-bit compression (the kvstore 2bit scheme, bucket-shaped)
# ---------------------------------------------------------------------------

def compress_bucket(bucket, threshold, residual):
    """Quantize one flat bucket to ``{-t, 0, +t}`` with error feedback:
    the pre-reduction payload drops to 2 effective bits per element (the
    reference's ``gradient_compression.cc`` scheme, applied to the
    packed bucket instead of per key — elementwise, so bucketing does
    not change the numerics), and the quantization error carries to the
    next step through ``residual``. Returns ``(q, new_residual)``."""
    t = jnp.asarray(threshold, bucket.dtype)
    acc = bucket + residual
    q = jnp.where(acc >= t, t, jnp.where(acc <= -t, -t,
                                         jnp.zeros((), bucket.dtype)))
    return q, acc - q


# ---------------------------------------------------------------------------
# bucketed collectives
# ---------------------------------------------------------------------------

def _maybe_barrier(flats, barrier):
    """``barrier=True`` pins every gradient behind one optimization
    barrier, so no collective can be scheduled before the whole
    backward finished — the ablation/parity baseline for the
    bucket-ready mode (numerics are identical either way; only the
    schedule differs)."""
    if not barrier:
        return flats
    # the ONE sanctioned graph-level barrier: the ablation mode exists
    # to measure what the bucket-ready schedule buys
    return list(jax.lax.optimization_barrier(  # mxtpu-lint: overlap-barrier-ok
        tuple(flats)))


def bucket_allreduce(grads, axis_name, plan, postscale=None,
                     barrier=False, compress=None, residuals=None,
                     wire_dtype=None):
    """One ``lax.psum`` per plan bucket, issued in readiness order;
    returns (reduced grads in original order, new residuals or None).

    ``postscale`` multiplies each bucket AFTER the reduction (the
    1/dp of a mean-loss data-parallel step rides here — one fused
    multiply per bucket instead of one per gradient). ``compress`` is
    a 2-bit threshold applied per bucket pre-reduction with
    ``residuals`` carry (list aligned with ``plan.buckets``).
    ``wire_dtype`` casts each bucket to a reduced precision for the
    collective (summation happens in that dtype) and back afterwards —
    1/2 the wire bytes for bf16 gradients at bf16-sum accuracy."""
    _chaos_point("bucket_psum")
    flat = _maybe_barrier([g.reshape(-1) for g in grads], barrier)
    out = [None] * len(grads)
    new_res = [None] * len(plan.buckets) if compress is not None else None
    for bi, idxs in enumerate(plan.buckets):
        parts = [flat[i] for i in idxs]
        b = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if compress is not None:
            b, new_res[bi] = compress_bucket(b, compress, residuals[bi])
        odt = b.dtype
        if wire_dtype is not None and b.dtype != jnp.dtype(wire_dtype):
            b = b.astype(wire_dtype)
        red = jax.lax.psum(b, axis_name)
        if red.dtype != odt:
            red = red.astype(odt)
        if postscale is not None:
            red = red * jnp.asarray(postscale, red.dtype)
        off = 0
        for i in idxs:
            n = plan.sizes[i]
            out[i] = jax.lax.slice(red, (off,), (off + n,)).reshape(
                plan.shapes[i])
            off += n
    return out, new_res


def bucket_reduce_scatter(grads, axis_name, plan, postscale=None,
                          barrier=False, compress=None, residuals=None,
                          wire_dtype=None):
    """One ``lax.psum_scatter`` per plan bucket (ZeRO-2/3): each rank
    receives only its 1/dp shard of every summed gradient — 1/dp the
    wire bytes AND 1/dp the gradient memory of an allreduce. Layout:
    each gradient pads to a multiple of ``dp`` and reshapes to
    ``[dp, pad/dp]``; buckets concatenate along axis 1, so scattering
    axis 0 hands rank r row r — the r-th shard of every gradient in
    the bucket, sliceable per gradient without cross-rank straddling.
    Returns (per-gradient ``[pad/dp]`` shards in original order, new
    residuals or None)."""
    _chaos_point("bucket_psum_scatter")
    dp = plan.dp
    flat = _maybe_barrier([g.reshape(-1) for g in grads], barrier)
    out = [None] * len(grads)
    new_res = [None] * len(plan.buckets) if compress is not None else None
    for bi, idxs in enumerate(plan.buckets):
        parts = [pad_flat(flat[i], plan.pad_sizes[i]).reshape(dp, -1)
                 for i in idxs]
        b = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        if compress is not None:
            q, new_res[bi] = compress_bucket(
                b.reshape(-1), compress, residuals[bi])
            b = q.reshape(b.shape)
        odt = b.dtype
        if wire_dtype is not None and b.dtype != jnp.dtype(wire_dtype):
            b = b.astype(wire_dtype)
        red = jax.lax.psum_scatter(b, axis_name, scatter_dimension=0,
                                   tiled=False)
        if red.dtype != odt:
            red = red.astype(odt)
        if postscale is not None:
            red = red * jnp.asarray(postscale, red.dtype)
        off = 0
        for i in idxs:
            n = plan.pad_sizes[i] // dp
            out[i] = jax.lax.slice(red, (off,), (off + n,))
            off += n
    return out, new_res


def residual_shapes(plan, reduce_scatter):
    """Per-bucket residual payload lengths for the compression carry
    (the packed bucket's element count: padded when the bucket feeds a
    reduce-scatter, exact otherwise)."""
    sizes = plan.pad_sizes if reduce_scatter else plan.sizes
    return [sum(sizes[i] for i in idxs) for idxs in plan.buckets]


# ---------------------------------------------------------------------------
# overlap measurement probe
# ---------------------------------------------------------------------------

def measure_overlap(block_factory, loss_fn, optimizer, optimizer_params,
                    mesh, x, y, lr=0.01, steps=20, warmup=3,
                    modes=("nocomm", "ready", "barrier", "staged")):
    """Measure how much gradient-communication time each scheduling
    mode exposes, on the SAME model/batch/mesh.

    ``nocomm`` (collectives dropped — numerically wrong on purpose) is
    the compute-only floor; each mode's exposed comm is its mean step
    wall time minus the floor's. ``hidden_fraction`` is
    ``1 - exposed[ready] / exposed[staged]`` — the share of the
    host-driven baseline's exposed comm the bucket-ready in-graph
    schedule hides. Publishes the result through
    ``observability.record_overlap_probe``; returns a dict with
    ``step_seconds``, ``exposed_comm_seconds`` and ``hidden_fraction``.

    ``block_factory`` must build an identically-initialized fresh block
    per call (each mode compiles its own executable and donates its own
    state)."""
    import time

    from .. import observability as _obs
    from .spmd import SPMDTrainStep

    step_seconds = {}
    for mode in modes:
        block = block_factory()
        # zero_stage pinned to 0: an ambient MXTPU_ZERO_STAGE>=2 would
        # downgrade the staged leg to barrier mode (staged has no ZeRO
        # layout) and change the comm layout under the other legs —
        # the modes would no longer measure the same collectives
        step = SPMDTrainStep(block, loss_fn, optimizer,
                             optimizer_params, mesh, overlap=mode,
                             zero_stage=0)
        out = None
        for _ in range(warmup):
            out = step(x, y, lr=lr, sync=False)
        if out is not None:
            jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step(x, y, lr=lr, sync=False)
        jax.block_until_ready(out)
        step_seconds[mode] = (time.perf_counter() - t0) / steps
    floor = step_seconds.get("nocomm")
    exposed = {}
    if floor is not None:
        for mode, t in step_seconds.items():
            if mode != "nocomm":
                exposed[mode] = max(t - floor, 0.0)
    hidden = None
    # baseline = the staged leg when it RAN (even if it measured 0.0
    # exposed comm on a noisy host — that means nothing to hide, not
    # "fall back to barrier"); barrier only when staged wasn't probed
    base = exposed.get("staged") if "staged" in exposed \
        else exposed.get("barrier")
    if base is not None and "ready" in exposed:
        hidden = (max(0.0, min(1.0, 1.0 - exposed["ready"] / base))
                  if base > 0.0 else 0.0)
    _obs.record_overlap_probe(exposed, hidden)
    return {"step_seconds": step_seconds,
            "exposed_comm_seconds": exposed,
            "hidden_fraction": hidden}
