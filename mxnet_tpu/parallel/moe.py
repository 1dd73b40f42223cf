"""Mixture-of-Experts with expert parallelism (P12).

No reference counterpart (SURVEY.md §2.5 P12 — "does not exist in the
reference"; previously a documented drop). TPU-native design: the
classic mesh-tensorflow/GShard algorithm — top-1/top-2 gating with
capacity, einsum dispatch/combine, experts sharded over an ``ep`` mesh
axis inside ``shard_map`` so each device runs only its local experts.

This is the TRAINING router: a capacity that drops the tokens over it,
two-matrix ReLU experts, a softmax gate. Nothing in ``serving/`` calls
it. A generation server's expert layer (a sigmoid router with a
balancing bias, top-k with renormalisation, gated experts as grouped
products over tokens sorted by expert, nothing dropped) is
:mod:`mxnet_tpu.ops.experts`.

Two dispatch paths:

- :func:`moe_apply` — tokens replicated, the dispatch einsum reshards
  onto locally-sharded expert tensors (XLA lowers the movement to an
  all-to-all over ICI). Simple, but the whole exchange is one opaque
  collective.
- :func:`moe_apply_a2a` — tokens sharded over ``ep``; each shard routes
  its own tokens, then an EXPLICIT ``lax.all_to_all`` carries the
  per-expert queues to their owners, the experts run, and a second
  all-to-all brings results home. The capacity axis is split into
  ``MXTPU_MOE_A2A_CHUNKS`` segments so the compiler can hide segment
  k+1's exchange behind segment k's expert matmuls — the same
  bucket-style overlap the PR-10 gradient path uses. The win is
  measured, not assumed: :func:`measure_moe_overlap` times
  nocomm/chunked/serial variants and publishes
  ``mxtpu_moe_a2a_hidden_fraction``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError


def top1_routing(gate_logits, num_experts, capacity):
    """Top-1 router with capacity (GShard): returns (dispatch (T,E,C),
    combine (T,E,C), aux_loss). Tokens beyond an expert's capacity drop
    (standard semantics)."""
    T = gate_logits.shape[0]
    probs = jax.nn.softmax(gate_logits, axis=-1)           # (T, E)
    expert = jnp.argmax(probs, axis=-1)                    # (T,)
    onehot = jax.nn.one_hot(expert, num_experts)           # (T, E)
    # position of each token within its expert's queue (0-based; the
    # onehot factor keeps non-selected experts from contributing)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot      # (T, E)
    pos_in_expert = jnp.sum(pos, axis=-1)                  # (T,)
    keep = pos_in_expert < capacity
    pos_oh = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity)
    dispatch = onehot[:, :, None] * pos_oh[:, None, :] \
        * keep[:, None, None]                              # (T, E, C)
    gate_val = jnp.sum(probs * onehot, axis=-1)            # (T,)
    combine = dispatch * gate_val[:, None, None]
    # load-balance auxiliary loss (Shazeer et al.): E * <fraction, prob>
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def top2_routing(gate_logits, num_experts, capacity):
    """Top-2 router with capacity (GShard §3.2): each token goes to its
    two highest-probability experts with renormalized combine weights;
    first choices take queue priority (second choices fill in behind
    ALL first choices, so congestion drops them first). Returns
    (dispatch (T,E,C), combine (T,E,C), aux_loss) — aux is the same
    load-balance form as top-1, over first-choice assignments."""
    probs = jax.nn.softmax(gate_logits, axis=-1)           # (T, E)
    e1 = jnp.argmax(probs, axis=-1)
    oh1 = jax.nn.one_hot(e1, num_experts)                  # (T, E)
    e2 = jnp.argmax(probs * (1.0 - oh1), axis=-1)
    oh2 = jax.nn.one_hot(e2, num_experts)

    # first-choice queue positions; second choices queue behind every
    # first choice of the same expert (GShard's priority rule)
    pos1 = jnp.sum((jnp.cumsum(oh1, axis=0) - 1.0) * oh1, axis=-1)
    cnt1 = jnp.sum(oh1, axis=0)                            # (E,)
    pos2 = jnp.sum(((jnp.cumsum(oh2, axis=0) - 1.0)
                    + cnt1[None, :]) * oh2, axis=-1)
    keep1 = pos1 < capacity
    keep2 = pos2 < capacity
    # out-of-range positions one_hot to a zero row, but mask anyway
    d1 = oh1[:, :, None] * jax.nn.one_hot(
        pos1.astype(jnp.int32), capacity)[:, None, :] * keep1[:, None, None]
    d2 = oh2[:, :, None] * jax.nn.one_hot(
        pos2.astype(jnp.int32), capacity)[:, None, :] * keep2[:, None, None]
    dispatch = d1 + d2
    g1 = jnp.sum(probs * oh1, axis=-1)
    g2 = jnp.sum(probs * oh2, axis=-1)
    denom = g1 + g2 + 1e-9
    combine = d1 * (g1 / denom)[:, None, None] \
        + d2 * (g2 / denom)[:, None, None]
    frac = jnp.mean(oh1, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


_ROUTERS = {"top1": top1_routing, "top2": top2_routing}


def _router_fn(router):
    from .. import fusedstep
    name = router or fusedstep.moe_router()
    if name not in _ROUTERS:
        raise MXNetError(f"unknown MoE router {name!r} "
                         f"(one of {sorted(_ROUTERS)})")
    return name, _ROUTERS[name]


def init_moe_params(key, d_model, d_hidden, num_experts):
    k1, k2, k3 = jax.random.split(key, 3)
    scale = 1.0 / jnp.sqrt(d_model)
    return {
        "gate": jax.random.normal(k1, (d_model, num_experts)) * scale,
        "w1": jax.random.normal(k2, (num_experts, d_model, d_hidden))
        * scale,
        "w2": jax.random.normal(k3, (num_experts, d_hidden, d_model))
        * (1.0 / jnp.sqrt(d_hidden)),
    }


def moe_apply(params, x, mesh=None, axis_name="ep", capacity_factor=1.5,
              router="top1"):
    """MoE FFN over tokens x (T, d). Experts shard over ``axis_name``
    when a mesh is given (expert parallelism); single-device otherwise.
    ``router``: ``top1`` (default) or ``top2``; ``None`` reads
    ``MXTPU_MOE_ROUTER``. Returns (out (T, d), aux_loss)."""
    E = params["w1"].shape[0]
    T, D = x.shape
    capacity = int(max(1, (T / E) * capacity_factor))
    gate_logits = x @ params["gate"]
    _, route = _router_fn(router)
    dispatch, combine, aux = route(gate_logits, E, capacity)
    expert_in = jnp.einsum("td,tec->ecd", x, dispatch)      # (E, C, d)

    def run_experts(w1, w2, ein):
        h = jax.nn.relu(jnp.einsum("ecd,edh->ech", ein, w1))
        return jnp.einsum("ech,ehd->ecd", h, w2)

    if mesh is None:
        expert_out = run_experts(params["w1"], params["w2"], expert_in)
    else:
        if E % mesh.shape[axis_name]:
            raise MXNetError(
                f"experts {E} must divide mesh axis {axis_name} "
                f"({mesh.shape[axis_name]})")

        expert_out = jax.shard_map(
            run_experts, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name), P(axis_name)),
            out_specs=P(axis_name),
        )(params["w1"], params["w2"], expert_in)
    out = jnp.einsum("ecd,tec->td", expert_out, combine)
    return out, aux


def shard_moe_params(params, mesh, axis_name="ep"):
    """Place expert tensors with the expert axis over ``ep``; the gate is
    replicated."""
    out = dict(params)
    out["w1"] = jax.device_put(params["w1"],
                               NamedSharding(mesh, P(axis_name)))
    out["w2"] = jax.device_put(params["w2"],
                               NamedSharding(mesh, P(axis_name)))
    out["gate"] = jax.device_put(params["gate"], NamedSharding(mesh, P()))
    return out


def _run_experts(w1, w2, ein):
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", ein, w1))
    return jnp.einsum("ech,ehd->ecd", h, w2)


def moe_apply_a2a(params, x, mesh, axis_name="ep", capacity_factor=None,
                  router=None, chunks=None, comm="chunked"):
    """MoE FFN with tokens sharded over ``axis_name`` and the expert
    exchange as explicit chunked ``lax.all_to_all`` inside the compiled
    step.

    Each token shard routes locally (capacity is per shard per expert),
    builds its (E, C, d) per-expert queues, and the all-to-all regroups
    them so each rank holds the full inbound queue of its own E/ep
    experts. The capacity axis is cut into ``chunks`` segments — one
    all-to-all + expert matmul + return all-to-all per segment — so the
    scheduler can run segment k+1's exchange under segment k's compute.

    ``comm``: ``chunked`` (default) | ``serial`` (one exchange) |
    ``nocomm`` (probe baseline: the exchange is replaced by a local
    relayout of identical shape, measuring pure compute).
    Returns (out (T, d), aux_loss); out rides the same token sharding.
    """
    from .. import fusedstep

    E = params["w1"].shape[0]
    T, D = x.shape
    ep = mesh.shape[axis_name]
    cf = capacity_factor if capacity_factor is not None \
        else fusedstep.moe_capacity_factor()
    k = chunks if chunks is not None else fusedstep.moe_a2a_chunks()
    if comm != "chunked":
        k = 1
    if E % ep:
        raise MXNetError(f"experts {E} must divide mesh axis "
                         f"{axis_name} ({ep})")
    if T % ep:
        raise MXNetError(f"tokens {T} must divide mesh axis "
                         f"{axis_name} ({ep}) for a2a dispatch")
    E_l, T_l = E // ep, T // ep
    cap = int(max(1, (T_l / E) * cf))
    cap = -(-cap // k) * k  # pad to the chunk count
    _, route = _router_fn(router)

    def local_fn(gate, w1, w2, xl):
        logits = xl @ gate
        dispatch, combine, aux = route(logits, E, cap)
        ein = jnp.einsum("td,tec->ecd", xl, dispatch)      # (E, cap, d)
        segs = jnp.reshape(ein, (E, k, cap // k, D))
        outs = []
        for i in range(k):
            seg = segs[:, i]                               # (E, cap/k, d)
            if comm == "nocomm":
                # shape-identical local relayout: pure-compute baseline
                inb = jnp.transpose(
                    jnp.reshape(seg, (ep, E_l, cap // k, D)),
                    (1, 0, 2, 3)).reshape(E_l, ep * cap // k, D)
            else:
                inb = lax.all_to_all(seg, axis_name, 0, 1, tiled=True)
            o = _run_experts(w1, w2, inb)       # (E_l, ep*cap/k, d)
            if comm == "nocomm":
                o = jnp.transpose(
                    jnp.reshape(o, (E_l, ep, cap // k, D)),
                    (1, 0, 2, 3)).reshape(E, cap // k, D)
            else:
                o = lax.all_to_all(o, axis_name, 1, 0, tiled=True)
            outs.append(o)
        expert_out = jnp.stack(outs, axis=1).reshape(E, cap, D)
        out = jnp.einsum("ecd,tec->td", expert_out, combine)
        return out, lax.pmean(aux, axis_name)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(), P(axis_name), P(axis_name),
                                 P(axis_name)),
                       out_specs=(P(axis_name), P()))
    return fn(params["gate"], params["w1"], params["w2"], x)


def measure_moe_overlap(mesh, axis_name="ep", d_model=64, d_hidden=128,
                        num_experts=None, tokens=None, steps=10,
                        warmup=3, chunks=None, seed=0):
    """Time the a2a MoE step under nocomm / chunked / serial dispatch
    and publish the hidden fraction (the MoE analog of
    ``measure_overlap``): exposed(mode) = step(mode) - step(nocomm),
    hidden = 1 - exposed(chunked)/exposed(serial).

    Returns {"exposed": {mode: seconds}, "hidden_fraction": float,
    "step_seconds": {mode: seconds}}.
    """
    ep = mesh.shape[axis_name]
    E = num_experts or 2 * ep
    T = tokens or 128 * ep
    key = jax.random.PRNGKey(seed)
    params = init_moe_params(key, d_model, d_hidden, E)
    params = shard_moe_params(params, mesh, axis_name)
    x = jax.device_put(
        jax.random.normal(key, (T, d_model)),
        NamedSharding(mesh, P(axis_name)))

    step_s = {}
    for mode in ("nocomm", "chunked", "serial"):
        fn = jax.jit(lambda p, xx, m=mode: moe_apply_a2a(
            p, xx, mesh, axis_name, chunks=chunks, comm=m)[0])
        for _ in range(warmup):
            fn(params, x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(params, x)
        out.block_until_ready()
        step_s[mode] = (time.perf_counter() - t0) / steps

    exposed = {m: max(0.0, step_s[m] - step_s["nocomm"])
               for m in ("chunked", "serial")}
    if exposed["serial"] > 1e-9:
        hidden = 1.0 - exposed["chunked"] / exposed["serial"]
    else:
        hidden = 0.0
    hidden = max(-1.0, min(1.0, hidden))
    from .. import observability as _obs
    _obs.record_moe_probe(exposed, hidden)
    return {"exposed": exposed, "hidden_fraction": hidden,
            "step_seconds": step_s}
