"""The served expert layer (``ops/experts.py``): the router, the grouped
products over tokens sorted by expert, and the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import experts as ex


def _layer(seed=0, T=13, d=16, E=8, ff=12):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(T, d), jnp.float32)
    w_r = jnp.asarray(rng.randn(d, E), jnp.float32)
    w_g, w_u = (jnp.asarray(rng.randn(E, d, ff) * 0.3, jnp.float32)
                for _ in range(2))
    w_d = jnp.asarray(rng.randn(E, ff, d) * 0.3, jnp.float32)
    return h, w_r, (w_g, w_u, w_d)


def _every_expert(h, w, chosen, mats, live=None):
    """Every expert applied to every token, weighted, zero where not
    chosen: what the plain reference computes."""
    w_g, w_u, w_d = mats
    T, E = h.shape[0], w_g.shape[0]
    dense = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], chosen].add(w)
    every = jnp.stack([ex.gated_mlp(h, w_g[e], w_u[e], w_d[e])
                       for e in range(E)], 1)
    y = jnp.einsum("te,ted->td", dense, every)
    return y if live is None else jnp.where(live[:, None], y, 0.0)


@pytest.mark.parametrize("k", [1, 2, 8])
def test_experts_apply_equals_the_every_expert_weighted_sum(k):
    h, w_r, mats = _layer()
    w, chosen = ex.route(h, w_r, jnp.zeros(8), k, 2.5)
    y, load = jax.jit(ex.experts_apply)(h, w, chosen, *mats)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_every_expert(h, w, chosen, mats)),
                               atol=1e-5)
    assert int(load.sum()) == 13 * k


def test_nothing_is_dropped_when_every_pair_lands_on_one_expert():
    h, _, mats = _layer(T=40)
    chosen = jnp.full((40, 1), 5, jnp.int32)
    w = jnp.full((40, 1), 2.5)
    y, load = ex.experts_apply(h, w, chosen, *mats)
    want = 2.5 * ex.gated_mlp(h, *(m[5] for m in mats))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    assert load.tolist() == [0, 0, 0, 0, 0, 40, 0, 0]
    assert ex.load_counters(load).tolist() == [40, 1, 40]


def test_the_bias_moves_the_choice_and_not_the_weights():
    h, w_r, _ = _layer()
    w0, c0 = ex.route(h, w_r, jnp.zeros(8), 2, 1.0)
    # a bias that lifts expert 3 over all: it is chosen everywhere...
    bias = jnp.zeros(8).at[3].set(10.0)
    w1, c1 = ex.route(h, w_r, bias, 2, 1.0)
    assert (np.asarray(c1)[:, 0] == 3).all()
    assert not (np.asarray(c0)[:, 0] == 3).all()
    # ...and weighs what its score says, not score + bias
    s = np.asarray(jax.nn.sigmoid(h @ w_r))
    picked = np.take_along_axis(s, np.asarray(c1), -1)
    np.testing.assert_allclose(np.asarray(w1),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_the_weights_sum_to_the_scale(scale):
    h, w_r, _ = _layer()
    w, chosen = ex.route(h, w_r, jnp.asarray(np.linspace(-.1, .1, 8)), 3,
                         scale)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), scale, rtol=1e-5)
    assert chosen.dtype == jnp.int32 and w.dtype == jnp.float32
    # three distinct experts a token
    assert all(len(set(row)) == 3 for row in np.asarray(chosen))


def test_the_router_computes_in_float32_whatever_the_weights_are():
    h, w_r, _ = _layer()
    lo = ex.route(h.astype(jnp.bfloat16), w_r.astype(jnp.bfloat16),
                  jnp.zeros(8), 2, 1.0)
    hi = ex.route(h.astype(jnp.bfloat16).astype(jnp.float32),
                  w_r.astype(jnp.bfloat16).astype(jnp.float32),
                  jnp.zeros(8), 2, 1.0)
    assert (np.asarray(lo[1]) == np.asarray(hi[1])).all()
    np.testing.assert_allclose(np.asarray(lo[0]), np.asarray(hi[0]),
                               rtol=1e-6)


def test_dead_rows_add_nothing_and_are_not_counted():
    h, w_r, mats = _layer()
    live = jnp.asarray([True, False, True, True, False, False, True,
                        True, True, False, True, True, True])
    w, chosen = ex.route(h, w_r, jnp.zeros(8), 2, 2.5)
    y, load = jax.jit(ex.experts_apply)(h, w, chosen, *mats, live)
    assert not np.asarray(y)[~np.asarray(live)].any()
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_every_expert(h, w, chosen, mats, live)),
        atol=1e-5)
    assert int(load.sum()) == 2 * int(live.sum())
    # no live row at all: zeros, no load
    y, load = ex.experts_apply(h, w, chosen, *mats, jnp.zeros(13, bool))
    assert not np.asarray(y).any() and not np.asarray(load).any()


def test_the_three_counters_against_hand_counts():
    # four live tokens' pairs: experts 1, 1, 1, 2, 5, 5, 7, 0
    chosen = jnp.asarray([[1, 2], [1, 5], [1, 5], [7, 0], [3, 3]], jnp.int32)
    live = jnp.asarray([True, True, True, True, False])
    h, _, mats = _layer(T=5)
    _, load = ex.experts_apply(h, jnp.ones((5, 2)), chosen, *mats, live)
    assert load.tolist() == [1, 3, 1, 0, 0, 2, 0, 1]
    # 8 pairs routed, 5 distinct experts hit, 3 on the fullest
    assert ex.load_counters(load).tolist() == [8, 5, 3]
    assert ex.load_counters(jnp.zeros(8, jnp.int32)).tolist() == [0, 0, 0]


@pytest.mark.parametrize("m,loads", [
    (128, [10, 0, 70, 30]),        # a group across a tile's edge
    (128, [0, 0, 128, 0]),         # one group takes every row
    (128, [0, 0, 0, 0]),           # no row at all
    (128, [64, 64, 0, 0]),         # groups that end with their tiles
    (192, [1, 63, 1, 64, 0, 3]),   # rows past the last group
    (2048, [300, 0, 700, 1000, 40, 0]),  # a prompt's tiles of 256
], ids=["straddle", "one_group", "empty", "aligned", "tail", "prompt"])
def test_grouped_matmul_kernel_matches_ragged_dot(m, loads):
    """The Pallas kernel in the interpreter against the oracle, on the
    rows that belong to a group (the others hold anything)."""
    rng = np.random.RandomState(m + len(loads))
    load = jnp.asarray(loads, jnp.int32)
    x = jnp.asarray(rng.randn(m, 32), jnp.float32)
    w = jnp.asarray(rng.randn(len(loads), 32, 24) * 0.1, jnp.float32)
    got = np.asarray(ex._pallas_gmm(x, w, load, interpret=True))
    want = np.asarray(jax.lax.ragged_dot(x, w, load))
    rows = int(load.sum())
    np.testing.assert_allclose(got[:rows], want[:rows], atol=1e-5)


def test_the_grid_of_the_grouped_product_by_hand():
    # rows 0-9 group 0, 10-79 group 2, 80-109 group 3, tiles of 64:
    # group 0 meets tile 0; group 2 tiles 0 and 1; group 3 tile 1
    group, tile, starts, ends, active = ex._gmm_steps(
        jnp.asarray([10, 0, 70, 30], jnp.int32), 128, 64)
    assert active.tolist() == [4]  # the fifth step names the fourth's
    assert group.tolist() == [0, 2, 2, 3, 3]
    assert tile.tolist() == [0, 0, 1, 1, 1]
    assert starts.tolist() == [0, 10, 10, 80]
    assert ends.tolist() == [10, 10, 80, 110]
    assert ex._row_tile(512) == 64 and ex._row_tile(32768) == 256
