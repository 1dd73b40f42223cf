"""4D-parallel composed train step: dp x pp x tp with ZeRO on the dp axis.

``Composed4DStep`` is the one-mesh trainer the parallelism contract
(``mesh.MESH_AXES``) exists for. A single ``shard_map`` over the full
``Mesh(dp, pp, tp, sp, ep)`` runs:

* **pp** — the tick-table pipeline executor (``pipeline._run_schedule``)
  with any of the three schedules (``1f1b`` default at one chunk per
  rank, ``interleaved`` default when stages tile the axis more than
  once, ``gpipe`` for comparison runs);
* **tp** — per-stage parameters carry a ``PartitionSpec`` over their
  stage dims (``tp_specs``); the stage function owns its tensor
  collectives (Megatron-style psum/all_gather over ``"tp"``), exactly
  as in the jit path of ``SPMDTrainStep``;
* **dp** — the batch is sharded over ``dp`` and gradients are either
  ``pmean``'d (ZeRO-0/1) or flattened, padded, ``psum_scatter``'d and
  updated shard-wise (ZeRO-2/3) — the same flat-shard layout
  ``SPMDTrainStep``'s overlap path uses, made orthogonal to pp/tp by
  applying it per (pp-rank, tp-index) cell. lamb keeps stage 2/3 via
  the shard-norm rule (one extra psum pair, over ``dp`` alone for
  tp-replicated leaves and ``(dp, tp)`` for tp-sharded ones).

``sp`` and ``ep`` must be 1 inside the step: sequence sharding rides
:func:`ring_attention.ring_attention` and expert parallelism rides
:func:`moe.moe_apply_a2a`, both of which a stage function can call
(they only need their axis to exist in the mesh).

Checkpoints are topology-independent by construction:
``state_snapshot`` emits every tensor in **natural per-stage form**
(key ``param::p<i>::s<g>`` = global stage ``g`` of leaf ``i``), so a
snapshot taken at (dp=4, pp=1) restores bit-exact into (dp=2, pp=2)
and back — the flat ZeRO shards and the stage permutation are a
storage detail undone on the way out and redone on the way in.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .mesh import axis_size, validate_mesh_axes
from .pipeline import (build_pipeline_schedule, stage_permutation,
                       _run_schedule, _microbatch, _amp_wrap)


def _raw(a):
    """Unwrap an mx ndarray handle; pass numpy/jax arrays through
    (numpy's ``.data`` is a memoryview, not the payload)."""
    d = getattr(a, "data", None)
    return d if isinstance(d, jax.Array) else jnp.asarray(a)


def _prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out


def tp_copy(x, axis_name="tp"):
    """Megatron's *f* function: identity forward, ``psum`` backward.

    Put this on a stage input consumed by a column-parallel matmul —
    each tp rank back-propagates only its shard's partial input
    gradient, and the psum on the way back restores the full one."""
    @jax.custom_vjp
    def f(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, g):
        return (lax.psum(g, axis_name),)

    f.defvjp(fwd, bwd)
    return f(x)


def tp_all_gather(x, axis_name="tp", axis=-1):
    """Megatron's *g* function: ``all_gather`` forward, **slice**
    backward. The default transpose of all_gather (psum_scatter) is
    wrong when every tp rank consumes the gathered tensor redundantly
    — each rank would contribute its full cotangent copy, scaling the
    gradient by tp. Slicing back out this rank's block is the correct
    adjoint of gather-then-replicate."""
    ax = axis % x.ndim
    k = x.shape[ax]

    @jax.custom_vjp
    def f(v):
        return lax.all_gather(v, axis_name, axis=ax, tiled=True)

    def fwd(v):
        return lax.all_gather(v, axis_name, axis=ax, tiled=True), None

    def bwd(_, g):
        i = lax.axis_index(axis_name)
        return (lax.dynamic_slice_in_dim(g, i * k, k, axis=ax),)

    f.defvjp(fwd, bwd)
    return f(x)


class Composed4DStep:
    """Train over the composed ``(dp, pp, tp)`` mesh in one step.

    ``stage_params``: pytree whose leaves have a leading stage axis
    ``[L, ...]`` (``L`` a multiple of the ``pp`` size; ``L/pp`` virtual
    chunks per rank). ``tp_specs``: optional matching pytree of
    ``PartitionSpec`` over the *stage* dims (``P(None, "tp")`` etc.);
    unspecified leaves are tp-replicated. ``embed_fn(p, x_mb)`` /
    ``head_fn(p, h)`` bracket the pipeline with replicated params.

    >>> mesh = composed_mesh(dp=2, pp=2, tp=2)
    >>> step = Composed4DStep(stage_fn, params, mesh, loss_fn,
    ...                       optimizer="adam", zero_stage=2)
    >>> loss = step(x, y, lr=1e-3)
    """

    def __init__(self, stage_fn, stage_params, mesh, loss_fn, *,
                 optimizer="sgd", optimizer_params=None,
                 num_microbatches=None, schedule=None, zero_stage=0,
                 amp_dtype=None, tp_specs=None,
                 embed_fn=None, embed_params=None,
                 head_fn=None, head_params=None):
        from .. import fusedstep, observability as _obs
        from .spmd import _RULES, _lamb_rule_sharded

        validate_mesh_axes(mesh, "Composed4DStep")
        if "pp" not in mesh.shape or "dp" not in mesh.shape:
            raise MXNetError(
                "Composed4DStep wants the composed mesh contract "
                "(dp, pp, ...); build it with composed_mesh()")
        for ax in ("sp", "ep"):
            if axis_size(mesh, ax) != 1:
                raise MXNetError(
                    f"Composed4DStep: {ax}={axis_size(mesh, ax)} — "
                    "sequence sharding rides ring_attention and expert "
                    "parallelism rides moe.moe_apply_a2a (call them "
                    f"from the stage function); keep {ax}=1 here")
        self._mesh = mesh
        S = axis_size(mesh, "pp")
        dp = axis_size(mesh, "dp")
        tp = axis_size(mesh, "tp")
        self._S, self._dp, self._tp = S, dp, tp

        leaves, treedef = jax.tree_util.tree_flatten(stage_params)
        if not leaves:
            raise MXNetError("Composed4DStep: empty stage_params")
        L = int(leaves[0].shape[0])
        for a in leaves:
            if int(a.shape[0]) != L:
                raise MXNetError(
                    "Composed4DStep: every stage_params leaf needs the "
                    f"same leading stage axis (got {a.shape[0]} vs {L})")
        if L % S:
            raise MXNetError(
                f"{L} stages do not tile the pp={S} axis")
        v = L // S
        self._L, self._v = L, v
        self._treedef = treedef

        if schedule is None:
            schedule = "interleaved" if v > 1 else "1f1b"
        if schedule in ("gpipe", "1f1b") and v != 1:
            raise MXNetError(
                f"{schedule} runs one stage per rank: {L} stages != "
                f"pp={S} (use schedule='interleaved')")
        M = num_microbatches or fusedstep.pipeline_microbatches() or S
        sched = build_pipeline_schedule(S, M, schedule, virtual=v)
        self.schedule = sched
        self._M = M

        if optimizer not in _RULES:
            raise MXNetError(
                f"Composed4DStep supports {sorted(_RULES)}; got "
                f"{optimizer}")
        zero_stage = int(zero_stage)
        if zero_stage not in (0, 1, 2, 3):
            raise MXNetError(f"zero_stage must be 0..3; got {zero_stage}")
        self.zero_stage = zero_stage
        hyper = dict(optimizer_params or {})
        rule_init, rule_update = _RULES[optimizer](hyper)
        self._rule_init = rule_init
        fn = _amp_wrap(stage_fn, amp_dtype)

        # --- per-leaf tp layout -------------------------------------
        if tp_specs is None:
            tentries = [()] * len(leaves)
        else:
            tentries = [tuple(s) if s is not None else ()
                        for s in treedef.flatten_up_to(tp_specs)]
        self._tp_dim = []
        self._pspec = []
        self._stage_shapes = []
        self._local_shapes = []
        for i, a in enumerate(leaves):
            ent = tentries[i]
            bad = [e for e in ent if e not in (None, "tp")]
            if bad:
                raise MXNetError(
                    f"tp_specs leaf {i}: only the 'tp' axis may appear "
                    f"in stage specs (got {bad})")
            d = ent.index("tp") if "tp" in ent else None
            stage_shape = tuple(int(s) for s in a.shape[1:])
            local = list(stage_shape)
            if d is not None:
                if "tp" not in mesh.shape:
                    raise MXNetError("tp_specs name 'tp' but the mesh "
                                     "has no tp axis")
                if local[d] % tp:
                    raise MXNetError(
                        f"stage dim {d} ({local[d]}) of leaf {i} does "
                        f"not tile tp={tp}")
                local[d] //= tp
            self._tp_dim.append(d)
            self._stage_shapes.append(stage_shape)
            self._local_shapes.append(tuple(local))
            self._pspec.append(P("pp", *ent))
        self._n_local = [v * _prod(sh) for sh in self._local_shapes]
        self._npad = [-(-n // dp) * dp for n in self._n_local]
        self._shard = [npad // dp for npad in self._npad]

        perm = stage_permutation(S, v)
        self._perm = np.asarray(perm)
        self._inv = np.argsort(self._perm)
        self._flat_spec = P("pp", "tp", "dp")

        # --- initial storage ----------------------------------------
        nat0 = [np.asarray(a) for a in leaves]  # global stage order
        if zero_stage >= 3:
            self._params = [self._put_flat(self._nat_to_flat(i, nat0[i]))
                            for i in range(len(leaves))]
        else:
            self._params = [self._put_nat(i, nat0[i])
                            for i in range(len(leaves))]
        if zero_stage >= 2:
            self._opt = [self._init_flat_opt(i, nat0[i])
                         for i in range(len(leaves))]
        else:
            self._opt = [self._init_nat_opt(i, nat0[i])
                         for i in range(len(leaves))]

        self._extra = {}
        for part, p0 in (("embed", embed_params), ("head", head_params)):
            if p0 is not None:
                pdev = jax.tree_util.tree_map(
                    lambda x: jax.device_put(
                        jnp.asarray(x), NamedSharding(mesh, P())), p0)
                self._extra[part + "_p"] = pdev
                self._extra[part + "_o"] = jax.tree_util.tree_map(
                    rule_init, pdev)
        self._embed_fn, self._head_fn = embed_fn, head_fn

        # --- per-leaf update rules ----------------------------------
        if optimizer == "lamb":
            # trust-ratio norms span the whole stacked leaf: psum over
            # every axis that shards it (pp always; dp once the leaf
            # is flat-scattered; tp when tp_specs shard it) — the same
            # update whatever the topology, and exact under ZeRO-2/3
            leaf_update = []
            for i in range(len(leaves)):
                axes = ["pp"]
                if zero_stage >= 2:
                    axes.append("dp")
                if self._tp_dim[i] is not None and tp > 1:
                    axes.append("tp")
                leaf_update.append(
                    _lamb_rule_sharded(hyper, tuple(axes))[1])
        else:
            leaf_update = [rule_update] * len(leaves)

        n_leaves = len(leaves)
        n_local, npad, shard_len = self._n_local, self._npad, self._shard
        local_shapes = self._local_shapes
        zstage = zero_stage
        run_embed, run_head = embed_fn, head_fn

        def _opt_dev_spec(i, st):
            return tuple(
                (self._flat_spec if zstage >= 2 else self._pspec[i])
                if getattr(x, "ndim", 0) >= 1 else P() for x in st)

        def body(params_dev, opt_dev, extra_dev, xs, ys, lr):
            if zstage >= 3:
                nat = []
                for i in range(n_leaves):
                    flat = lax.all_gather(params_dev[i][0, 0], "dp",
                                          tiled=True)
                    nat.append(flat[: n_local[i]].reshape(
                        (v,) + local_shapes[i]))
            else:
                nat = list(params_dev)
            ep_p = extra_dev.get("embed_p")
            hp_p = extra_dev.get("head_p")
            loss, grads, aux = _run_schedule(
                fn, loss_fn, sched, "pp", nat, xs, ys,
                head_fn=run_head if hp_p is not None else None,
                head_params=hp_p,
                embed_fn=run_embed if ep_p is not None else None,
                embed_params=ep_p)
            loss = lax.pmean(loss, "dp")
            new_p, new_o = [], []
            for i in range(n_leaves):
                g, w, st = grads[i], nat[i], opt_dev[i]
                if zstage < 2:
                    g = lax.pmean(g, "dp")
                    w2, st2 = leaf_update[i](w, g, st, lr)
                    new_p.append(w2)
                    new_o.append(st2)
                    continue
                gflat = jnp.pad(g.reshape(-1),
                                (0, npad[i] - n_local[i]))
                gsh = lax.psum_scatter(gflat, "dp",
                                       scatter_dimension=0,
                                       tiled=True) / dp
                if zstage >= 3:
                    wsh = params_dev[i][0, 0]
                else:
                    wflat = jnp.pad(w.reshape(-1),
                                    (0, npad[i] - n_local[i]))
                    wsh = lax.dynamic_slice(
                        wflat, (lax.axis_index("dp") * shard_len[i],),
                        (shard_len[i],))
                st_loc = tuple(x[0, 0] if getattr(x, "ndim", 0) == 3
                               else x for x in st)
                w2, st2 = leaf_update[i](wsh, gsh, st_loc, lr)
                if zstage >= 3:
                    new_p.append(w2[None, None])
                else:
                    full = lax.all_gather(w2, "dp", tiled=True)
                    new_p.append(full[: n_local[i]].reshape(w.shape))
                new_o.append(tuple(
                    x[None, None] if getattr(x, "ndim", 0) == 1 else x
                    for x in st2))
            new_extra = dict(extra_dev)
            for part, gaux in (("embed", aux["embed"]),
                               ("head", aux["head"])):
                if gaux is None:
                    continue
                pk, ok = part + "_p", part + "_o"
                g = jax.tree_util.tree_map(
                    lambda a: lax.pmean(a, "dp"), gaux)
                fp, tdef = jax.tree_util.tree_flatten(extra_dev[pk])
                fg = tdef.flatten_up_to(g)
                fo = tdef.flatten_up_to(extra_dev[ok])
                np_, no_ = [], []
                for pw, pg, po in zip(fp, fg, fo):
                    w2, st2 = rule_update(pw, pg, po, lr)
                    np_.append(w2)
                    no_.append(st2)
                new_extra[pk] = tdef.unflatten(np_)
                new_extra[ok] = tdef.unflatten(no_)
            return new_p, new_o, new_extra, loss

        if zero_stage >= 3:
            pspec_dev = [self._flat_spec] * n_leaves
        else:
            pspec_dev = list(self._pspec)
        ospec_dev = [_opt_dev_spec(i, st)
                     for i, st in enumerate(self._opt)]
        espec = jax.tree_util.tree_map(lambda _: P(), self._extra)
        self._mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec_dev, ospec_dev, espec,
                      P(None, "dp"), P(None, "dp"), P()),
            out_specs=(pspec_dev, ospec_dev, espec, P()),
            check_vma=False)

        def train(params, opt, extra, x, y, lr):
            xs, ys = _microbatch(x, y, M)
            return self._mapped(params, opt, extra, xs, ys, lr)

        def superstep(params, opt, extra, xss, yss, lr):
            def scan_body(carry, xy):
                p, o, e = carry
                p, o, e, loss = self._mapped(p, o, e, xy[0], xy[1], lr)
                return (p, o, e), loss

            (p, o, e), losses = lax.scan(
                scan_body, (params, opt, extra), (xss, yss))
            return p, o, e, losses

        self._train = jax.jit(train, donate_argnums=(0, 1, 2))
        self._superstep = jax.jit(superstep, donate_argnums=(0, 1, 2))
        self._registered = set()
        _obs.record_pipeline_schedule(
            sched.name, sched.bubble_fraction, sched.stash_slots,
            ticks=sched.ticks)

    # --- storage layout helpers (host-side numpy) -------------------

    def _put_nat(self, i, nat):
        """Natural global-stage-order [L, ...] -> permuted stacked
        device array sharded (pp, *tp)."""
        return jax.device_put(
            jnp.asarray(nat[self._perm]),
            NamedSharding(self._mesh, self._pspec[i]))

    def _put_flat(self, flat):
        return jax.device_put(
            jnp.asarray(flat), NamedSharding(self._mesh, self._flat_spec))

    def _nat_to_flat(self, i, nat):
        """[L, *stage_shape] -> [S, tp, npad] flat ZeRO cells."""
        S, v, tp = self._S, self._v, self._tp
        d = self._tp_dim[i]
        out = np.zeros((S, tp, self._npad[i]), nat.dtype)
        for r in range(S):
            for j in range(tp):
                parts = []
                for c in range(v):
                    t = nat[c * S + r]
                    if d is not None:
                        k = t.shape[d] // tp
                        t = np.take(t, range(j * k, (j + 1) * k), axis=d)
                    parts.append(np.asarray(t).reshape(-1))
                vec = np.concatenate(parts)
                out[r, j, : vec.size] = vec
        return out

    def _flat_to_nat(self, i, flat):
        """[S, tp, npad] -> [L, *stage_shape] natural stage order."""
        S, v, tp = self._S, self._v, self._tp
        d = self._tp_dim[i]
        nat = np.zeros((self._L,) + self._stage_shapes[i], flat.dtype)
        for r in range(S):
            cells = [flat[r, j, : self._n_local[i]].reshape(
                (v,) + self._local_shapes[i]) for j in range(tp)]
            merged = (np.concatenate(cells, axis=d + 1)
                      if d is not None else cells[0])
            for c in range(v):
                nat[c * S + r] = merged[c]
        return nat

    def _init_nat_opt(self, i, nat):
        st = jax.jit(self._rule_init)(jnp.asarray(nat[self._perm]))
        return tuple(
            jax.device_put(x, NamedSharding(
                self._mesh,
                self._pspec[i] if getattr(x, "ndim", 0) >= 1 else P()))
            for x in st)

    def _init_flat_opt(self, i, nat):
        flat = self._nat_to_flat(i, nat)
        init = jax.jit(self._rule_init)
        cells = [[init(jnp.asarray(flat[r, j]))
                  for j in range(self._tp)] for r in range(self._S)]
        out = []
        for li in range(len(cells[0][0])):
            leaf = cells[0][0][li]
            if getattr(leaf, "ndim", 0) == 0:
                out.append(jax.device_put(
                    leaf, NamedSharding(self._mesh, P())))
            else:
                stacked = np.stack(
                    [np.stack([np.asarray(cells[r][j][li])
                               for j in range(self._tp)])
                     for r in range(self._S)])
                out.append(self._put_flat(stacked))
        return tuple(out)

    # --- stepping ---------------------------------------------------

    def _register(self, site, jit_fn, args):
        if site in self._registered:
            return
        self._registered.add(site)
        try:
            from .. import observability as _obs
            _obs.introspect.register_jit(
                site, jit_fn, _obs.introspect.avals_of(args),
                donated=True)
        except Exception:  # pragma: no cover - introspection is best-effort
            pass

    def __call__(self, x, y, lr=0.01):
        raw_x, raw_y = _raw(x), _raw(y)
        if (raw_x.shape[0] // self._M) % self._dp:
            raise MXNetError(
                f"microbatch size {raw_x.shape[0] // self._M} does not "
                f"tile the dp={self._dp} axis")
        lr = jnp.asarray(lr, jnp.float32)
        args = (self._params, self._opt, self._extra, raw_x, raw_y, lr)
        self._register("composed4d_step", self._train, args)
        self._params, self._opt, self._extra, loss = self._train(*args)
        return loss

    def run_superstep(self, x, y, lr=0.01):
        """Scan ``k`` fused steps on device: ``x``/``y`` lead with the
        step axis ``[k, B, ...]``. Returns the per-step losses."""
        raw_x, raw_y = _raw(x), _raw(y)
        k, B = raw_x.shape[0], raw_x.shape[1]
        M = self._M
        if B % M or (B // M) % self._dp:
            raise MXNetError(
                f"superstep batch {B} must tile microbatches {M} x "
                f"dp={self._dp}")
        xss = raw_x.reshape(k, M, B // M, *raw_x.shape[2:])
        yss = raw_y.reshape(k, M, B // M, *raw_y.shape[2:])
        lr = jnp.asarray(lr, jnp.float32)
        args = (self._params, self._opt, self._extra, xss, yss, lr)
        self._register("composed4d_superstep", self._superstep, args)
        self._params, self._opt, self._extra, losses = \
            self._superstep(*args)
        return losses

    def schedule_report(self):
        return self.schedule.report()

    def memory_report(self):
        """Per-device bytes by storage plane plus the schedule's stash
        cost — the numbers a 4D layout choice trades against."""
        def dev_bytes(arrs):
            total = 0
            for a in jax.tree_util.tree_leaves(arrs):
                try:
                    total += a.addressable_shards[0].data.nbytes
                except Exception:
                    total += a.nbytes // self._mesh.size
            return int(total)

        return {"zero_stage": self.zero_stage,
                "schedule": self.schedule.name,
                "bubble_fraction": round(
                    self.schedule.bubble_fraction, 6),
                "stash_slots": self.schedule.stash_slots,
                "param_bytes_per_device": dev_bytes(self._params),
                "opt_bytes_per_device": dev_bytes(self._opt),
                "extra_bytes_per_device": dev_bytes(self._extra)}

    # --- topology-independent snapshot/restore ----------------------

    def state_snapshot(self):
        """Emit (chunks, extents): every tensor in natural per-stage
        form, keyed topology-independently — ``param::p<i>::s<g>``,
        ``opt::p<i>::s<g>::<li>`` (scalar state leaves live at ``s0``),
        ``embed::p<j>`` / ``head::p<j>`` and their ``_opt`` rows. A
        snapshot from any (dp, pp, tp) restores into any other."""
        chunks, extents = {}, {}

        def put(key, arr):
            arr = np.asarray(arr)
            idx = tuple(slice(0, s) for s in arr.shape)
            # np.ascontiguousarray would promote 0-d scalars to (1,)
            chunks[key] = [(idx, np.array(arr, copy=True))]
            extents[key] = arr.shape

        for i in range(len(self._params)):
            if self.zero_stage >= 3:
                nat = self._flat_to_nat(i, np.asarray(self._params[i]))
            else:
                nat = np.asarray(self._params[i])[self._inv]
            for g in range(self._L):
                put(f"param::p{i}::s{g}", nat[g])
            for li, leaf in enumerate(self._opt[i]):
                a = np.asarray(leaf)
                if a.ndim == 0:
                    put(f"opt::p{i}::s0::{li}", a)
                    continue
                nat_o = (self._flat_to_nat(i, a)
                         if self.zero_stage >= 2 else a[self._inv])
                for g in range(self._L):
                    put(f"opt::p{i}::s{g}::{li}", nat_o[g])
        for part in ("embed", "head"):
            if part + "_p" not in self._extra:
                continue
            fp = jax.tree_util.tree_leaves(self._extra[part + "_p"])
            fo = jax.tree_util.tree_leaves(self._extra[part + "_o"])
            for j, leaf in enumerate(fp):
                put(f"{part}::p{j}", leaf)
            for j, leaf in enumerate(fo):
                put(f"{part}_opt::p{j}", leaf)
        return chunks, extents

    def restore_chunks(self, chunks, extents=None):
        """Load a :meth:`state_snapshot` (possibly taken on a different
        (dp, pp, tp) topology) into this step's storage layout."""
        del extents  # extents are implied by this step's own shapes

        def paste(key, shape, dtype):
            if key not in chunks:
                raise MXNetError(f"restore: missing snapshot key {key}")
            if shape == ():
                return np.asarray(chunks[key][0][1])
            out = np.zeros(shape, dtype)
            for idx, data in chunks[key]:
                out[idx] = data
            return out

        for i in range(len(self._params)):
            dt = np.asarray(
                jax.tree_util.tree_leaves(self._params[i])[0]).dtype
            nat = np.stack([
                paste(f"param::p{i}::s{g}", self._stage_shapes[i], dt)
                for g in range(self._L)])
            if self.zero_stage >= 3:
                self._params[i] = self._put_flat(
                    self._nat_to_flat(i, nat))
            else:
                self._params[i] = self._put_nat(i, nat)
            new_st = []
            for li, leaf in enumerate(self._opt[i]):
                a = np.asarray(leaf)
                if a.ndim == 0:
                    val = paste(f"opt::p{i}::s0::{li}", (), a.dtype)
                    new_st.append(jax.device_put(
                        jnp.asarray(val, a.dtype),
                        NamedSharding(self._mesh, P())))
                    continue
                nat_o = np.stack([
                    paste(f"opt::p{i}::s{g}::{li}",
                          self._stage_shapes[i], a.dtype)
                    for g in range(self._L)])
                if self.zero_stage >= 2:
                    new_st.append(self._put_flat(
                        self._nat_to_flat(i, nat_o)))
                else:
                    new_st.append(jax.device_put(
                        jnp.asarray(nat_o[self._perm]),
                        NamedSharding(self._mesh, self._pspec[i])))
            self._opt[i] = tuple(new_st)
        for part in ("embed", "head"):
            if part + "_p" not in self._extra:
                continue
            for token, store in ((part, part + "_p"),
                                 (part + "_opt", part + "_o")):
                fl, tdef = jax.tree_util.tree_flatten(self._extra[store])
                out = []
                for j, leaf in enumerate(fl):
                    a = np.asarray(leaf)
                    out.append(jax.device_put(
                        jnp.asarray(paste(f"{token}::p{j}", a.shape,
                                          a.dtype)),
                        NamedSharding(self._mesh, P())))
                self._extra[store] = tdef.unflatten(out)
