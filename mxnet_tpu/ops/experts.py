"""A served expert layer: a sigmoid router with a balancing bias, and
the chosen experts' gated MLPs as grouped matrix products.

This is the layer of the DeepSeek-V3 line of models (``scoring_func``
``sigmoid``, ``topk_method`` ``noaux_tc``) as a generation server runs
it: every token's ``k`` experts are computed, whatever the imbalance,
with static shapes. :mod:`mxnet_tpu.parallel.moe` is the TRAINING
router of the GShard line (softmax, top-1/top-2, a capacity that drops
tokens, two-matrix ReLU experts over an ``ep`` mesh axis); nothing here
drops a token and nothing there serves one.

- :func:`route`: ``s = sigmoid(h W_r)`` in float32; the ``k`` experts
  with the largest ``s + b`` (``b`` the balancing bias: it enters the
  choice and not the weight); ``w = s[chosen] / (sum s[chosen] + 1e-20)
  * scale``.
- :func:`experts_apply`: the ``(tokens x k)`` pairs sorted by expert,
  group sizes from a count, three grouped products
  (:func:`grouped_matmul`) over the stacked ``(E, d, ff)`` / ``(E, ff,
  d)`` weights, each pair's row weighted and summed back to its token.
  One expert may take every pair. A dead row (an empty decode slot, a
  prompt's padding) routes nowhere: its pairs sort past the last group
  and are counted by no expert.
- :func:`grouped_matmul`: rows sorted by group against a stack of
  matrices, a group its own matrix. On the chip the Pallas kernel
  ``mxtpu_experts_gmm``: one grid step a (row tile, group) pair that
  meet, the group's whole matrix copied in while the step before
  computes, the tile's rows of that group stored and the others left;
  row tiles of 64 for a decode batch and 256 for a prompt, so that a
  step's product is no larger than the rows it serves (the compiler's
  own kernel for ``jax.lax.ragged_dot`` tiles the rows by 512 whatever
  the groups are, and at 512 pairs over 230 experts spends its time
  multiplying masked rows: PERF.md, PR 32). On the CPU
  ``jax.lax.ragged_dot`` (the oracle).
- :func:`load_counters`: what the generation engine sums into
  ``stats()["experts"]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["route", "experts_apply", "grouped_matmul", "load_counters",
           "gated_mlp"]


def _use_pallas():
    """Kernel path on any backend but the CPU."""
    return jax.default_backend() != "cpu"


def _row_tile(m):
    """Rows a tile of the grouped product: a decode batch's pairs meet
    a few rows an expert, a prompt's some tens to hundreds."""
    return 64 if m <= 1024 else 256


def _gmm_steps(load, m, tm):
    """The grid of the grouped product: ``(group, tile)`` of every step,
    int32 ``(m / tm + E - 1,)`` each (the most steps any load needs),
    and the groups' first and end rows. Group ``g`` with rows meets the
    tiles from ``start // tm`` to ``(end - 1) // tm``, one step each, in
    order of group; the steps past the last one name its blocks again
    (nothing is copied for them) and, the fifth result being the count
    of steps that do work, compute nothing."""
    E = load.shape[0]
    ends = jnp.cumsum(load)
    starts = ends - load
    tiles = jnp.where(load > 0, (ends - 1) // tm - starts // tm + 1, 0)
    step_end = jnp.cumsum(tiles)
    steps = jnp.arange(m // tm + E - 1, dtype=jnp.int32)
    steps = jnp.minimum(steps, jnp.maximum(step_end[-1] - 1, 0))
    group = jnp.minimum(jnp.searchsorted(step_end, steps, side="right"),
                        E - 1).astype(jnp.int32)
    tile = starts[group] // tm + steps - (step_end - tiles)[group]
    return (group, jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32),
            starts.astype(jnp.int32), ends.astype(jnp.int32),
            step_end[-1:].astype(jnp.int32))


def _gmm_kernel(group_ref, tile_ref, start_ref, end_ref, active_ref, x_ref,
                w_ref, o_ref, *, tm):
    """One step: this tile's rows against this group's matrix; the rows
    that are the group's are stored, the tile's other rows keep what
    their own groups' steps store (steps of one tile are consecutive,
    and its block stays in VMEM between them)."""
    s = pl.program_id(0)

    @pl.when(s < active_ref[0])
    def _():
        g = group_ref[s]
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = tile_ref[s] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (rows >= start_ref[g]) & (rows < end_ref[g])
        o_ref[...] = jnp.where(mine, acc, o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_gmm(x, w, load, interpret=False):
    m, k = x.shape
    _, _, n = w.shape
    tm = _row_tile(m)
    steps = _gmm_steps(load, m, tm)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps[0].shape[0],),
            in_specs=[
                pl.BlockSpec((tm, k), lambda s, g, t, *_: (t[s], 0)),
                pl.BlockSpec((None, k, n), lambda s, g, t, *_: (g[s], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, n), lambda s, g, t, *_: (t[s], 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two of a group's matrix in flight, the tile and its result
            vmem_limit_bytes=int(4 * k * n * w.dtype.itemsize
                                 + 4 * tm * (k + 2 * n) + (8 << 20))),
        interpret=interpret,
        name="mxtpu_experts_gmm",
    )(*steps, x, w)


def grouped_matmul(x, w, load):
    """``x`` ``(m, k)`` with its rows sorted by group, ``w`` ``(E, k,
    n)``, ``load`` ``(E,)`` int32 the rows of each group: float32
    ``(m, n)`` whose row ``i`` is ``x[i] @ w[group of i]``. Rows past
    ``sum(load)`` belong to no group and hold anything. ``m`` is whole
    row tiles (64 up to 1,024 rows, else 256)."""
    if _use_pallas() and x.shape[0] % _row_tile(x.shape[0]) == 0:
        return _pallas_gmm(x, w, load)
    return jax.lax.ragged_dot(x, w, load,
                              preferred_element_type=jnp.float32)


def gated_mlp(x, w_gate, w_up, w_down):
    """``(silu(x W_g) * (x W_u)) W_d``: the dense form every expert has
    (the shared expert and a dense layer run it as it stands)."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(h, w_router, bias, k, scale=1.0):
    """``(weights (T, k) float32, chosen (T, k) int32)`` for hidden
    states ``h`` ``(T, d)``. Computed in float32 at full precision: a
    bf16 score flips near-ties of the top-k against a float32
    reference, and a flipped expert is a different function."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), int(k))
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return w * float(scale), chosen.astype(jnp.int32)


def experts_apply(h, w, chosen, w_gate, w_up, w_down, live=None):
    """``(y (T, d), load (E,) int32)``: ``y[t] = sum_j w[t, j] *
    E_chosen[t, j](h[t])`` over stacked experts ``w_gate``/``w_up``
    ``(E, d, ff)`` and ``w_down`` ``(E, ff, d)``; ``load`` is the pairs
    each expert took. Rows where ``live`` ``(T,)`` is false give zeros
    and load nothing. Accumulates in float32; ``y`` has ``h``'s
    dtype."""
    T, k = chosen.shape
    E = w_gate.shape[0]
    flat = chosen.reshape(-1)
    if live is not None:
        # past every group: the products leave those rows alone
        flat = jnp.where(jnp.repeat(live, k), flat, E)
    order = jnp.argsort(flat, stable=True)
    load = jnp.sum(flat[:, None] == jnp.arange(E, dtype=flat.dtype),
                   axis=0, dtype=jnp.int32)
    x = h[order // k]                                     # (T k, d)
    act = (jax.nn.silu(grouped_matmul(x, w_gate, load))
           * grouped_matmul(x, w_up, load)).astype(h.dtype)
    y = grouped_matmul(act, w_down, load)
    # a row past the last group holds whatever the product left there
    y = jnp.where((flat[order] < E)[:, None],
                  y * w.reshape(-1)[order][:, None].astype(jnp.float32), 0.0)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype), unique_indices=True)
    return y[back].reshape(T, k, -1).sum(axis=1).astype(h.dtype), load


def load_counters(load):
    """int32 ``[routed_pairs, experts_hit, load_max]`` of one layer's
    ``load``: the pairs routed, the distinct experts that took one, the
    most on one expert."""
    return jnp.stack([jnp.sum(load), jnp.sum(load > 0),
                      jnp.max(load)]).astype(jnp.int32)
