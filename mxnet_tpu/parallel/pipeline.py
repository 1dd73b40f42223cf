"""Pipeline parallelism (P10): GPipe / 1F1B / interleaved-1F1B schedules
over a ``pp`` mesh axis.

No reference counterpart (SURVEY.md §2.5 P10 — "does not exist in the
reference"; previously a documented drop). TPU-native design per the
public scaling-book recipe: stages live on devices along the ``pp`` axis
(stage parameters stacked on a leading axis, sharded over ``pp``);
activations hop stage-to-stage with ``lax.ppermute`` riding ICI.

Three schedules, all realized from ONE dependency-simulated tick table
(:func:`build_pipeline_schedule`), so the reported ``bubble_fraction``
is measured from the realized table, not a formula:

- ``gpipe`` — fill-drain: all M forwards, then all M backwards.
  Bubble (S-1)/(M+S-1); the activation stash grows with M (every
  in-flight microbatch's input is held until its backward).
- ``1f1b`` — same bubble as gpipe at the same microbatch count (the
  warmup/drain ramps are identical — that is arithmetic, not an
  implementation artifact), but the steady state interleaves one
  backward after each forward so at most ~S activations are ever
  stashed: the MEMORY schedule. ``stash_slots`` exposes the win.
- ``interleaved`` — 1F1B over v virtual stage chunks per rank
  (stage g lives on rank g mod S), which divides the fill/drain ramp
  by v: bubble ~ ((S-1)/v)/(M + (S-1)/v). The LATENCY schedule, and
  the one that clears the >= 90% pipeline-overlap gate.

The backward is schedule-driven (not autodiff-transposed): each
backward tick recomputes its stage from the stashed input via
``jax.vjp`` (remat semantics) and hands the cotangent to the previous
stage with the reverse ``ppermute`` ring. The legacy fill-drain
``pipeline_apply`` (autodiff through the forward loop) is kept as the
``gpipe`` train-step path and for inference.
"""

from __future__ import annotations

import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError

#: schedule tick tables are built once per (name, S, M, v) — the build
#: is host-side simulation, cached because train steps, probes and
#: gauges all ask for the same table
_SCHEDULE_CACHE = {}
_CACHE_LOCK = threading.Lock()

_GUARDED_BY = {"_SCHEDULE_CACHE": "_CACHE_LOCK"}


def pipeline_apply(stage_fn, stage_params, x, mesh, axis_name="pp",
                   num_microbatches=None):
    """Apply ``S`` pipelined stages to ``x`` (fill-drain forward).

    stage_fn(params_one_stage, activation) -> activation (same shape);
    stage_params: pytree whose leaves carry a leading stage axis of size
    S (sharded over ``axis_name``); x: (B, ...) global batch, B divisible
    by num_microbatches. Returns the (B, ...) output of the last stage.
    """
    S = mesh.shape[axis_name]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != S:
            raise MXNetError(
                f"stage axis {leaf.shape[0]} != mesh {axis_name}={S}: "
                "each device must hold exactly one stage")
    M = num_microbatches or S
    B = x.shape[0]
    if B % M:
        raise MXNetError(
            f"num_microbatches {M} must divide the batch size {B}")
    mb = B // M
    xs = x.reshape(M, mb, *x.shape[1:])

    def per_stage(params_local, xs_local):
        # params_local: (1, ...) this device's stage slice
        params_one = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = lax.axis_index(axis_name)

        def _vary(v):  # mark as varying over pp (shard_map vma check)
            return lax.pcast(v, (axis_name,), to="varying")

        state = _vary(jnp.zeros_like(xs_local[0]))   # in-flight activation
        outputs = _vary(jnp.zeros_like(xs_local))    # filled by last stage
        fwd = [(i, (i + 1) % S) for i in range(S)]

        for t in range(M + S - 1):
            # stage 0 ingests microbatch t; everyone else uses the state
            # handed over from the previous stage
            feed = xs_local[jnp.minimum(t, M - 1)]
            inp = jnp.where(stage == 0, feed, state)
            out = stage_fn(params_one, inp)
            # last stage banks microbatch t-(S-1)
            oidx = t - (S - 1)
            live = (oidx >= 0) & (stage == S - 1)
            banked = outputs.at[jnp.clip(oidx, 0, M - 1)].set(out)
            outputs = jnp.where(live, banked, outputs)
            # hand the activation to the next stage
            state = lax.ppermute(out, axis_name, fwd)
        # activations circulate back to stage 0 from the last hop; only
        # the last stage's banked outputs matter — broadcast them so the
        # (replicated) output spec is consistent
        outputs = lax.psum(
            jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)),
            axis_name)
        return outputs


    spec_params = jax.tree_util.tree_map(
        lambda _: P(axis_name), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(spec_params, P()),
                       out_specs=P())
    ys = fn(stage_params, xs)
    return ys.reshape(B, *x.shape[1:])


def stack_stage_params(per_stage_params):
    """[pytree_per_stage, ...] -> one pytree with a leading stage axis."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def shard_stages(stacked, mesh, axis_name="pp"):
    """Place stacked stage params with the stage axis over ``pp``."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.device_put(
            leaf, NamedSharding(mesh, P(axis_name))), stacked)


# ---------------------------------------------------------------------------
# schedule tables: dependency-simulated tick programs
# ---------------------------------------------------------------------------


def stage_permutation(num_ranks, virtual):
    """Stacked position -> global stage, rank-major chunk layout.

    Position ``p = r*v + c`` (rank r's c-th local chunk) holds global
    stage ``g = c*S + r`` — so sharding the permuted stack over ``pp``
    gives rank r exactly its interleaved chunks, and every forward hop
    g -> g+1 is the uniform +1 ring (rank S-1 wraps to rank 0 at chunk
    boundaries)."""
    S, v = num_ranks, virtual
    return [ (p % v) * S + (p // v) for p in range(S * v) ]


class PipelineSchedule:
    """A realized pipeline schedule: per-tick work tables + measured
    bubble. Built by :func:`build_pipeline_schedule`; consumed by the
    schedule executor and the bubble probe/gauges."""

    def __init__(self, name, num_ranks, num_microbatches, virtual,
                 ticks, tables, stash_slots, bstash_slots):
        self.name = name
        self.num_ranks = num_ranks
        self.num_microbatches = num_microbatches
        self.virtual = virtual
        self.num_stages = num_ranks * virtual
        self.ticks = ticks
        self.tables = tables
        #: peak live forward-activation stash entries on any rank — the
        #: 1F1B memory win vs gpipe is this number (S vs M)
        self.stash_slots = stash_slots
        self.bstash_slots = bstash_slots
        busy = 2 * num_microbatches * virtual  # F+B units per rank
        #: measured from the realized table: fraction of (rank, tick)
        #: slots with no scheduled work
        self.bubble_fraction = 1.0 - busy / float(ticks)

    def report(self):
        return {"schedule": self.name, "ranks": self.num_ranks,
                "virtual": self.virtual,
                "microbatches": self.num_microbatches,
                "ticks": self.ticks,
                "bubble_fraction": round(self.bubble_fraction, 6),
                "stash_slots": self.stash_slots}


def _rank_order(name, S, v, M, r):
    """This rank's work order: the classic per-rank sequences."""
    L = S * v
    if name == "gpipe":
        return ([("F", r, m) for m in range(M)] +
                [("B", r, m) for m in reversed(range(M))])
    if name == "1f1b":
        W = min(M, S - 1 - r)
        order = [("F", r, m) for m in range(W)]
        for i in range(M - W):
            order.append(("F", r, W + i))
            order.append(("B", r, i))
        order += [("B", r, i) for i in range(M - W, M)]
        return order
    if name == "interleaved":
        if M % S:
            raise MXNetError(
                f"interleaved schedule needs microbatches ({M}) to be a "
                f"multiple of the pp axis ({S})")
        total = M * v

        def fwd_unit(k):
            rnd, within = divmod(k, S * v)
            return ("F", (within // S) * S + r, rnd * S + within % S)

        def bwd_unit(j):
            rnd, within = divmod(j, S * v)
            c = v - 1 - within // S
            return ("B", c * S + r, rnd * S + within % S)

        W = min(total, (v - 1) * S + 2 * (S - r - 1) + 1)
        order = [fwd_unit(k) for k in range(W)]
        for i in range(total - W):
            order.append(fwd_unit(W + i))
            order.append(bwd_unit(i))
        order += [bwd_unit(j) for j in range(total - W, total)]
        return order
    raise MXNetError(f"unknown pipeline schedule {name!r} "
                     "(gpipe | 1f1b | interleaved)")


class _Slots:
    """Greedy interval slot allocator (per rank): reuse a slot whose
    previous tenant was last read strictly before the new deposit."""

    def __init__(self):
        self.ends = []  # slot -> last read tick of current tenant

    def alloc(self, start, end):
        for i, e in enumerate(self.ends):
            if e <= start:  # last read happens before the new deposit
                self.ends[i] = end
                return i
        self.ends.append(end)
        return len(self.ends) - 1

    @property
    def n(self):
        return len(self.ends)


def build_pipeline_schedule(num_ranks, num_microbatches, name="gpipe",
                            virtual=1):
    """Simulate ``name`` over S ranks / M microbatches / v virtual
    chunks and return the realized :class:`PipelineSchedule`.

    The simulator walks the classic per-rank work orders tick by tick,
    releasing each unit only when its producer finished on an earlier
    tick (cross-rank messages ride the end-of-tick ppermute) — so the
    table, its bubble fraction, and the stash liveness are measured
    properties of the realized schedule.
    """
    key = (name, int(num_ranks), int(num_microbatches), int(virtual))
    with _CACHE_LOCK:
        hit = _SCHEDULE_CACHE.get(key)
    if hit is not None:
        return hit

    S, M, v = int(num_ranks), int(num_microbatches), int(virtual)
    L = S * v
    if name != "interleaved" and v != 1:
        raise MXNetError(f"schedule {name!r} runs one stage per rank; "
                         f"got {L} stages on {S} ranks — use "
                         "schedule='interleaved' for virtual chunks")
    orders = [_rank_order(name, S, v, M, r) for r in range(S)]
    done = {}
    ptr = [0] * S
    exec_at = {}  # (kind, g, m) -> (tick, rank)
    t, limit = 0, 4 * (2 * M * L + L + S) + 16
    while any(ptr[r] < len(orders[r]) for r in range(S)):
        for r in range(S):
            if ptr[r] >= len(orders[r]):
                continue
            kind, g, m = orders[r][ptr[r]]
            if kind == "F":
                dep = None if g == 0 else ("F", g - 1, m)
            else:
                dep = ("F", L - 1, m) if g == L - 1 else ("B", g + 1, m)
            if dep is None or done.get(dep, limit) < t:
                done[(kind, g, m)] = t
                exec_at[(kind, g, m)] = (t, r)
                ptr[r] += 1
        t += 1
        if t > limit:  # pragma: no cover - schedule bug guard
            raise MXNetError(f"pipeline schedule {name!r} deadlocked "
                             f"(S={S}, M={M}, v={v})")
    T = t

    cols = ("f_on f_mb f_chunk f_src f_slot bank_on bank_mb "
            "b_on b_mb b_chunk b_src b_slot bx_src bx_slot "
            "rf_on rf_slot rb_on rb_slot").split()
    tbl = {c: np.zeros((T, S), np.int32) for c in cols}
    fslots = [_Slots() for _ in range(S)]
    bslots = [_Slots() for _ in range(S)]

    for (kind, g, m), (tick, r) in sorted(exec_at.items(),
                                          key=lambda kv: kv[1]):
        c = g // S
        if kind == "F":
            tbl["f_on"][tick, r] = 1
            tbl["f_mb"][tick, r] = m
            tbl["f_chunk"][tick, r] = c
            if g == L - 1:
                tbl["bank_on"][tick, r] = 1
                tbl["bank_mb"][tick, r] = m
            if g > 0:
                arrive = done[("F", g - 1, m)]
                last_read = exec_at[("B", g, m)][0]
                slot = fslots[r].alloc(arrive, last_read)
                tbl["rf_on"][arrive, r] = 1
                tbl["rf_slot"][arrive, r] = slot
                tbl["f_src"][tick, r] = 1
                tbl["f_slot"][tick, r] = slot
                tbl["bx_src"][exec_at[("B", g, m)][0], r] = 1
                tbl["bx_slot"][exec_at[("B", g, m)][0], r] = slot
        else:
            tbl["b_on"][tick, r] = 1
            tbl["b_mb"][tick, r] = m
            tbl["b_chunk"][tick, r] = c
            if g < L - 1:
                arrive = done[("B", g + 1, m)]
                slot = bslots[r].alloc(arrive, tick)
                tbl["rb_on"][arrive, r] = 1
                tbl["rb_slot"][arrive, r] = slot
                tbl["b_src"][tick, r] = 1
                tbl["b_slot"][tick, r] = slot

    n_f = max((s.n for s in fslots), default=0)
    n_b = max((s.n for s in bslots), default=0)
    # idle rows point their slot reads/deposits at the scratch slot
    for slot_col, on_col in (("f_slot", "f_on"), ("b_slot", "b_on"),
                             ("bx_slot", "b_on"), ("rf_slot", "rf_on"),
                             ("rb_slot", "rb_on")):
        scratch = n_f if slot_col in ("f_slot", "bx_slot", "rf_slot") \
            else n_b
        tbl[slot_col][tbl[on_col] == 0] = scratch
    sched = PipelineSchedule(name, S, M, v, T, tbl, n_f, n_b)
    with _CACHE_LOCK:
        _SCHEDULE_CACHE[key] = sched
    return sched


def measure_pipeline_bubble(num_ranks, num_microbatches, virtual=2,
                            schedules=("gpipe", "1f1b", "interleaved")):
    """Realize each schedule's tick table at this config and publish
    the measured bubble fractions + stash depths (the pipeline analog
    of ``measure_overlap``). Returns {schedule: report dict}."""
    out = {}
    for name in schedules:
        v = virtual if name == "interleaved" else 1
        sched = build_pipeline_schedule(num_ranks, num_microbatches,
                                        name, virtual=v)
        out[name] = sched.report()
        from .. import observability as _obs
        _obs.record_pipeline_schedule(name, sched.bubble_fraction,
                                      sched.stash_slots,
                                      ticks=sched.ticks)
    return out


# ---------------------------------------------------------------------------
# schedule executor: one uniform SPMD tick program
# ---------------------------------------------------------------------------


def _run_schedule(stage_fn, loss_fn, sched, axis_name, params_local,
                  xs, ys, head_fn=None, head_params=None,
                  embed_fn=None, embed_params=None):
    """Run one fwd+bwd pass of ``sched`` (inside shard_map over
    ``axis_name``). ``params_local``: leaves [v, ...] (this rank's
    chunks); ``xs``/``ys``: [M, mb, ...] microbatched batch (replicated
    over pp). Optional ``embed_fn(embed_params, x_mb)`` feeds stage 0
    (token embedding — re-applied at stage-0 backward ticks for its
    vjp) and ``head_fn(head_params, h)`` sits between the last stage
    and the loss (folded into the loss seed's vjp). Returns
    (loss, grads_local, {"head": g or None, "embed": g or None}).

    Per tick: at most one forward (reading its input from the feed or
    the activation stash) and one backward (recomputing its stage from
    the stashed input via ``jax.vjp``, seeding from the loss at the
    last stage), then one +1-ring ppermute of activations and one
    -1-ring ppermute of cotangents. Slot/chunk/microbatch indices come
    from the schedule's host-built tables (indexed by this rank's axis
    position), so the traced program is identical on every rank — ticks
    where no rank forwards (or none backwards) skip that half entirely.
    """
    S, v, M, T = (sched.num_ranks, sched.virtual,
                  sched.num_microbatches, sched.ticks)
    tbl = sched.tables
    rank = lax.axis_index(axis_name)
    if embed_fn is None:
        act_shape = xs.shape[1:]
        act_dtype = xs.dtype
    else:
        a0 = jax.eval_shape(embed_fn, embed_params,
                            jax.eval_shape(lambda a: a[0], xs))
        act_shape, act_dtype = a0.shape, a0.dtype

    def _vary(val):
        return lax.pcast(val, (axis_name,), to="varying")

    def row(col, t):  # this rank's entry of a [T, S] host table
        return _vary(jnp.asarray(tbl[col][t]))[rank]

    def pick(arr, idx):
        return lax.dynamic_index_in_dim(arr, idx, 0, keepdims=False)

    def put_if(arr, val, idx, on):
        cur = pick(arr, idx)
        return lax.dynamic_update_index_in_dim(
            arr, jnp.where(on, val, cur), idx, 0)

    def chunk_of(idx):
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
            params_local)

    def feed(m):
        xm = pick(xs, m)
        if embed_fn is None:
            return xm.astype(act_dtype)
        return embed_fn(embed_params, xm).astype(act_dtype)

    stash = _vary(jnp.zeros((sched.stash_slots + 1,) + act_shape,
                            act_dtype))
    bstash = _vary(jnp.zeros((sched.bstash_slots + 1,) + act_shape,
                             act_dtype))
    out_bank = _vary(jnp.zeros((M,) + act_shape, act_dtype))
    grads = jax.tree_util.tree_map(jnp.zeros_like, params_local)
    if head_params is not None:
        head_grads = jax.tree_util.tree_map(jnp.zeros_like, head_params)
    if embed_params is not None:
        embed_grads = jax.tree_util.tree_map(jnp.zeros_like,
                                             embed_params)
    loss_acc = jnp.zeros((), jnp.float32)
    fwd_ring = [(i, (i + 1) % S) for i in range(S)]
    bwd_ring = [(i, (i - 1) % S) for i in range(S)]
    inv_m = jnp.asarray(1.0 / M, jnp.float32)

    def seed_of(out_m, y_m):
        """Loss value + cotangent at the last stage (head folded in)."""
        if head_fn is not None:
            def lf(o, hp):
                return loss_fn(head_fn(hp, o), y_m)
            val, vjp = jax.vjp(lf, out_m, head_params)
            g_o, g_h = vjp(lax.full_like(val, 1.0 / M))
            return val, g_o.astype(act_dtype), g_h
        val, vjp = jax.vjp(lambda o: loss_fn(o, y_m), out_m)
        (g_o,) = vjp(lax.full_like(val, 1.0 / M))
        return val, g_o.astype(act_dtype), None

    for t in range(T):
        any_f = bool(tbl["f_on"][t].any())
        any_b = bool(tbl["b_on"][t].any())
        any_rf = bool(tbl["rf_on"][t].any())
        any_rb = bool(tbl["rb_on"][t].any())

        f_out = None
        if any_f:
            f_mb = row("f_mb", t)
            f_in = jnp.where(row("f_src", t) == 0, feed(f_mb),
                             pick(stash, row("f_slot", t)))
            f_out = stage_fn(chunk_of(row("f_chunk", t)), f_in)
            if tbl["bank_on"][t].any():
                out_bank = put_if(out_bank, f_out, row("bank_mb", t),
                                  row("bank_on", t) == 1)

        b_msg = None
        if any_b:
            b_mb = row("b_mb", t)
            b_live = row("b_on", t) == 1
            y_m = pick(ys, b_mb)
            if bool((tbl["b_on"][t] & (tbl["b_src"][t] == 0)).any()):
                loss_m, g_seed, g_head = seed_of(pick(out_bank, b_mb),
                                                 y_m)
                seed_live = b_live & (row("b_src", t) == 0)
                loss_acc = loss_acc + jnp.where(
                    seed_live, loss_m.astype(jnp.float32), 0.0) * inv_m
                if head_params is not None and g_head is not None:
                    w = jnp.where(seed_live, 1.0, 0.0)
                    head_grads = jax.tree_util.tree_map(
                        lambda acc, g: acc + w.astype(g.dtype) * g,
                        head_grads, g_head)
                g_out = jnp.where(seed_live, g_seed,
                                  pick(bstash, row("b_slot", t)))
            else:
                g_out = pick(bstash, row("b_slot", t))
            feeds_here = bool(
                (tbl["b_on"][t] & (tbl["bx_src"][t] == 0)).any())
            evjp = None
            if embed_fn is not None and feeds_here:
                bx0, evjp = jax.vjp(
                    lambda ep: embed_fn(ep, pick(xs, b_mb)).astype(
                        act_dtype), embed_params)
            else:
                bx0 = feed(b_mb) if feeds_here else None
            bx = pick(stash, row("bx_slot", t))
            if bx0 is not None:
                bx = jnp.where(row("bx_src", t) == 0, bx0, bx)
            _, stage_vjp = jax.vjp(stage_fn, chunk_of(row("b_chunk", t)),
                                   bx)
            g_p, g_in = stage_vjp(g_out.astype(act_dtype))
            if evjp is not None:
                feed_live = b_live & (row("bx_src", t) == 0)
                g_feed = jnp.where(feed_live, g_in,
                                   jnp.zeros_like(g_in))
                (g_emb,) = evjp(g_feed)
                embed_grads = jax.tree_util.tree_map(
                    lambda acc, g: acc + g, embed_grads, g_emb)
            oh = (jnp.arange(v) == row("b_chunk", t))
            oh = jnp.where(b_live, oh, jnp.zeros_like(oh))
            grads = jax.tree_util.tree_map(
                lambda acc, g: acc + oh.astype(g.dtype).reshape(
                    (v,) + (1,) * g.ndim) * g[None],
                grads, g_p)
            b_msg = g_in

        if any_rf:
            recv_f = lax.ppermute(
                f_out if f_out is not None
                else jnp.zeros(act_shape, act_dtype), axis_name, fwd_ring)
            stash = put_if(stash, recv_f, row("rf_slot", t),
                           row("rf_on", t) == 1)
        if any_rb:
            recv_b = lax.ppermute(
                b_msg if b_msg is not None
                else jnp.zeros(act_shape, act_dtype), axis_name, bwd_ring)
            bstash = put_if(bstash, recv_b, row("rb_slot", t),
                            row("rb_on", t) == 1)

    loss = lax.psum(loss_acc, axis_name)
    aux = {"head": None, "embed": None}
    if head_params is not None:
        aux["head"] = jax.tree_util.tree_map(
            lambda g: lax.psum(g, axis_name), head_grads)
    if embed_params is not None:
        aux["embed"] = jax.tree_util.tree_map(
            lambda g: lax.psum(g, axis_name), embed_grads)
    return loss, grads, aux


def _microbatch(x, y, M):
    B = x.shape[0]
    if B % M:
        raise MXNetError(
            f"num_microbatches {M} must divide the batch size {B}")
    mb = B // M
    return (x.reshape(M, mb, *x.shape[1:]),
            y.reshape(M, mb, *y.shape[1:]))


def _amp_wrap(stage_fn, amp_dtype):
    """bf16 compute wrapper: params + activation cast down for the
    stage matmuls, output restored to the fp32 hop/stash dtype."""
    if not amp_dtype:
        return stage_fn
    dt = jnp.dtype(amp_dtype)

    def wrapped(params_one, h):
        lo = jax.tree_util.tree_map(lambda p: p.astype(dt), params_one)
        return stage_fn(lo, h.astype(dt)).astype(jnp.float32)

    return wrapped


class PipelineTrainStep:
    """Pipelined training over the ``pp`` axis.

    ``schedule``: ``gpipe`` (default; fill-drain via autodiff — the
    legacy path), ``1f1b``, or ``interleaved`` (both run the manual
    tick-table executor; ``interleaved`` wants ``len(stages)`` to be a
    multiple of the pp axis, running v = L/S chunks per rank).
    ``optimizer``: any of the SPMD rule names (sgd, adam, ...).

    >>> step = PipelineTrainStep(stage_fn, stage_params, mesh, loss_fn)
    >>> loss = step(x, y, lr=0.1)
    """

    def __init__(self, stage_fn, stage_params, mesh, loss_fn,
                 axis_name="pp", num_microbatches=None, schedule=None,
                 optimizer="sgd", optimizer_params=None, amp_dtype=None):
        from .. import fusedstep, observability as _obs
        from .spmd import _RULES

        self._mesh = mesh
        self._axis = axis_name
        S = mesh.shape[axis_name]
        L = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        schedule = schedule or fusedstep.pipeline_schedule()
        M = num_microbatches or fusedstep.pipeline_microbatches() or S
        if optimizer not in _RULES:
            raise MXNetError(f"pipeline step supports {sorted(_RULES)}; "
                             f"got {optimizer}")
        rule_init, rule_update = _RULES[optimizer](optimizer_params or {})
        fn = _amp_wrap(stage_fn, amp_dtype)

        if schedule == "gpipe":
            if L != S:
                raise MXNetError(
                    f"gpipe runs one stage per rank: {L} stages != "
                    f"{axis_name}={S} (use schedule='interleaved')")
            self.schedule = build_pipeline_schedule(S, M, "gpipe")
            self._params = shard_stages(stage_params, mesh, axis_name)
            self._opt = jax.tree_util.tree_map(rule_init, self._params)

            def train(params, opt, x, y, lr):
                def loss_of(p):
                    out = pipeline_apply(fn, p, x, mesh, axis_name, M)
                    return loss_fn(out, y)

                loss, grads = jax.value_and_grad(loss_of)(params)
                flat_p, tdef = jax.tree_util.tree_flatten(params)
                flat_g = tdef.flatten_up_to(grads)
                flat_o = tdef.flatten_up_to(opt)
                new_p, new_o = [], []
                for p, g, st in zip(flat_p, flat_g, flat_o):
                    p2, st2 = rule_update(p, g, st, lr)
                    new_p.append(p2)
                    new_o.append(st2)
                return (tdef.unflatten(new_p), tdef.unflatten(new_o),
                        loss)

            self._train = jax.jit(train, donate_argnums=(0, 1))
        else:
            if L % S:
                raise MXNetError(
                    f"{L} stages do not tile the {axis_name}={S} axis")
            v = L // S
            if schedule == "1f1b" and v != 1:
                raise MXNetError(
                    f"1f1b runs one stage per rank: {L} stages != "
                    f"{axis_name}={S} (use schedule='interleaved')")
            sched = build_pipeline_schedule(S, M, schedule, virtual=v)
            self.schedule = sched
            perm = stage_permutation(S, v)
            permuted = jax.tree_util.tree_map(
                lambda a: a[np.asarray(perm)], stage_params)
            self._params = shard_stages(permuted, mesh, axis_name)
            self._opt = jax.tree_util.tree_map(rule_init, self._params)

            spec_p = jax.tree_util.tree_map(lambda _: P(axis_name),
                                            self._params)

            def body(params_block, opt_block, xs, ys, lr):
                # params_block leaves: [v, ...] local chunks
                loss, grads, _ = _run_schedule(
                    fn, loss_fn, sched, axis_name, params_block, xs, ys)
                flat_p, tdef = jax.tree_util.tree_flatten(params_block)
                flat_g = tdef.flatten_up_to(grads)
                flat_o = tdef.flatten_up_to(opt_block)
                new_p, new_o = [], []
                for p, g, st in zip(flat_p, flat_g, flat_o):
                    p2, st2 = rule_update(p, g, st, lr)
                    new_p.append(p2)
                    new_o.append(st2)
                return (tdef.unflatten(new_p), tdef.unflatten(new_o),
                        loss)

            # adam/lamb carry a scalar step counter: replicated, not
            # sharded over pp like the per-stage moment tensors
            spec_o = jax.tree_util.tree_map(
                lambda leaf: P(axis_name)
                if getattr(leaf, "ndim", 0) >= 1 else P(),
                self._opt)
            mapped = jax.shard_map(
                body, mesh=mesh,
                in_specs=(spec_p, spec_o, P(), P(), P()),
                out_specs=(spec_p, spec_o, P()))

            def train(params, opt, x, y, lr):
                xs, ys = _microbatch(x, y, M)
                return mapped(params, opt, xs, ys, lr)

            self._train = jax.jit(train, donate_argnums=(0, 1))

        _obs.record_pipeline_schedule(
            self.schedule.name, self.schedule.bubble_fraction,
            self.schedule.stash_slots, ticks=self.schedule.ticks)

    def schedule_report(self):
        return self.schedule.report()

    def __call__(self, x, y, lr=0.01):
        def _raw(a):
            # mx ndarrays carry the device buffer as .data; a numpy
            # array's .data is a memoryview, not an array
            d = getattr(a, "data", None)
            return d if isinstance(d, jax.Array) else jnp.asarray(a)

        raw_x = _raw(x)
        raw_y = _raw(y)
        self._params, self._opt, loss = self._train(
            self._params, self._opt, raw_x, raw_y,
            jnp.asarray(lr, jnp.float32))
        return loss
