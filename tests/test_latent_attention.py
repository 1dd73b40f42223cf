"""Latent attention: the absorbed form (decode, over the cached latent
rows) against the expanded form (prefill and the oracle), the decode
kernel in the Pallas interpreter against its ``jnp`` oracle, and the
rotary pairing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.serving import SequenceCache, TransformerDecoderLM
from mxnet_tpu.serving import decoder


def _pool_case(lens, dtype, seed=0, layers=2, nb=40, bs=16, width=640,
               heads=32, mb=12):
    """A latent pool with each sequence's blocks in scattered order."""
    rng = np.random.RandomState(seed)
    pool = jnp.asarray(rng.randn(layers, nb, bs, width), dtype)
    q = jnp.asarray(rng.randn(len(lens), heads, width) * 0.2, dtype)
    tables = np.zeros((len(lens), mb), np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    for b, n in enumerate(lens):
        for j in range(-(-n // bs)):
            tables[b, j] = free.pop()
    return (pool, q, jnp.asarray(tables), jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("case", ["ragged", "empty_slot", "all_empty",
                                  "whole_table", "bf16"])
def test_latent_decode_kernel_matches_its_oracle(case):
    lens = {"ragged": [1, 37, 130, 17], "empty_slot": [50, 0, 16, 0, 129],
            "all_empty": [0, 0, 0], "whole_table": [192, 191],
            "bf16": [1, 0, 37, 192, 130]}[case]
    dtype, tol = (jnp.bfloat16, 2e-2) if case == "bf16" \
        else (jnp.float32, 2e-5)
    pool, q, tables, lens = _pool_case(lens, dtype)
    for layer in (0, 1):
        at = jnp.asarray([layer], jnp.int32)
        want = fa._jnp_latent_decode(at, tables, lens, q, pool, scale=0.07)
        got = fa._pallas_latent_decode(at, tables, lens, q, pool, scale=0.07,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
        # an empty slot reads zeros
        assert not np.asarray(got, np.float32)[np.asarray(lens) == 0].any()


def test_latent_decode_attention_pads_the_queries_to_the_pools_lanes():
    """The public call: 512 + 64 = 576 numbers a row in a pool of 640
    lanes; the lanes past the row add nothing, and the result is the
    latent part alone."""
    pool, q, tables, lens = _pool_case([33, 5], jnp.float32, heads=4)
    pool = pool.at[..., 576:].set(0.0)
    out = fa.latent_decode_attention(q[..., :512], q[..., 512:576], pool,
                                     tables, lens, 0.05, layer=1)
    assert out.shape == (2, 4, 512)
    rows = np.asarray(pool[1][np.asarray(tables)]).reshape(2, -1, 640)
    for b, n in enumerate([33, 5]):
        s = np.einsum("hw,sw->hs", np.asarray(q[b, :, :576]),
                      rows[b, :n, :576]) * 0.05
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(out[b]), p @ rows[b, :n, :512],
                                   atol=2e-5)
    with pytest.raises(ValueError, match="latent pool"):
        fa.latent_decode_attention(q[..., :512], q[..., 512:576],
                                   pool[..., :500], tables, lens, 0.05)


def _net(**over):
    return TransformerDecoderLM.from_preset("joyai_tiny", seed=5, **over)


@pytest.mark.parametrize("layers", [2, 3])
def test_the_absorbed_form_equals_the_expanded_form(layers):
    """Prefill (expanded, writing the rows) then decode steps (absorbed,
    over the rows) give the oracle's logits, elementwise."""
    net = _net(num_layers=layers,
               mlp=["swiglu"] + ["experts"] * (layers - 1))
    params = net.params()
    rng = np.random.RandomState(1)
    plen, steps, bucket = 11, 6, 16
    seq = rng.randint(0, 128, (1, plen + steps))
    want = np.asarray(net.forward_fn()(params, seq))[0]
    cache = SequenceCache(net.cache_spec(), slots=2, max_seq=64,
                          num_blocks=12, block_size=4)
    s = cache.allocate(plen + steps)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :plen] = seq[0, :plen]
    logits, *arrays = net.prefill_fn()(
        params, jnp.asarray(padded), *cache.arrays(), *cache.rows([s]),
        jnp.asarray([plen], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits)[0], want[plen - 1],
                               atol=2e-5)
    step = net.decode_step_fn()
    tables = cache.rows([s, None])[0]
    for i in range(steps):
        pos = plen + i
        logits, *arrays = step(
            params, jnp.asarray([seq[0, pos], 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), *arrays, tables,
            jnp.asarray([True, False]))
        np.testing.assert_allclose(np.asarray(logits)[0], want[pos],
                                   atol=2e-5)


def test_the_cache_row_is_the_normed_latent_and_the_rotated_key():
    """A token keeps ``kv_rank + rope_dim`` numbers a layer and nothing
    else: one pool, in whole lane tiles."""
    net = _net()
    assert net.cache_spec() == {"latent": {"layers": 2, "width": 40}}
    cache = SequenceCache(net.cache_spec(), slots=2, max_seq=32,
                          num_blocks=6, block_size=4)
    (pool,) = cache.arrays()
    assert pool.shape == (2, 6, 4, 128)  # 40 numbers in one lane tile


def test_rotary_pairs_are_interleaved_not_half_split():
    x = jnp.asarray(np.random.RandomState(0).randn(3, 1, 8), jnp.float32)
    pos = jnp.asarray([0, 1, 5])
    got = np.asarray(decoder._rope_pairs(x, pos, 100.0))
    freq = 100.0 ** (-np.arange(4) / 4)
    for t, p in enumerate([0, 1, 5]):
        for i in range(4):
            a, b = np.asarray(x)[t, 0, 2 * i], np.asarray(x)[t, 0, 2 * i + 1]
            c, s = np.cos(p * freq[i]), np.sin(p * freq[i])
            np.testing.assert_allclose(
                got[t, 0, 2 * i:2 * i + 2], [a * c - b * s, b * c + a * s],
                atol=1e-5)
    assert not np.allclose(got, np.asarray(decoder._rope(x, pos, 100.0)),
                           atol=1e-3)


def test_a_net_built_with_the_other_pairing_is_another_function():
    seq = np.random.RandomState(2).randint(0, 128, (1, 12))
    a, b = _net(), _net(rope_interleave=False)
    la = np.asarray(a.forward_fn()(a.params(), seq))
    lb = np.asarray(b.forward_fn()(b.params(), seq))
    assert np.abs(la - lb).max() > 1e-3 * np.abs(la).max()


def test_the_spec_rebuilds_the_same_latent_net():
    net = _net()
    spec = net.spec()["decoder"]
    assert spec["mlp"] == ["swiglu", "experts"] and spec["q_rank"] == 48
    again = TransformerDecoderLM(**spec)
    a, b = (jax.tree_util.tree_leaves(n.params()) for n in (net, again))
    assert len(a) == len(b) and all(
        (np.asarray(x) == np.asarray(y)).all() for x, y in zip(a, b))
    # the config's own names say the same
    named = TransformerDecoderLM(**{
        **{k: v for k, v in spec.items() if k not in (
            "q_rank", "experts", "route_scale")},
        "q_lora_rank": 48, "n_routed_experts": 8,
        "routed_scaling_factor": 2.5})
    assert named.spec() == net.spec()
    with pytest.raises(TypeError, match="unexpected"):
        TransformerDecoderLM(q_lora=4)
    with pytest.raises(ValueError, match="latent layer needs"):
        TransformerDecoderLM(layer_kinds="latent", norm="rmsnorm",
                             positions="rope")
    with pytest.raises(ValueError, match="mlp is one of"):
        TransformerDecoderLM(mlp=["gelu"])


def test_a_cache_has_one_block_table():
    with pytest.raises(ValueError, match="not both"):
        SequenceCache({"attention": {"layers": 1, "kv_heads": 1,
                                     "head_dim": 4},
                       "latent": {"layers": 1, "width": 8}}, slots=1)


def test_the_paged_cache_is_a_tuple_of_pools_of_a_row_width():
    """One allocator, tables and copy-on-write over however many pools
    a kind keeps: K and V of ``kv_heads * head_dim``, or one pool of a
    stated width."""
    from mxnet_tpu.serving import PagedKVCache

    kv = PagedKVCache(2, 3, 8, max_seq=32, num_blocks=6, block_size=4)
    assert len(kv.pools()) == 2 and kv.width == 24
    assert kv.k_pool.shape == kv.v_pool.shape == (2, 6, 4, 24)
    one = PagedKVCache(2, width=128, arrays=1, max_seq=32, num_blocks=6,
                       block_size=4)
    (pool,) = one.pools()
    assert pool.shape == (2, 6, 4, 128) and one.k_pool is pool
    with pytest.raises(ValueError, match="1 pool"):
        one.update_pools(pool, pool)
    # a fork's first write copies the shared partial block of the pool
    t = one.allocate(6)
    t.length = 6
    one.update_pools(pool.at[:, t.blocks[1]].set(7.0))
    child = one.fork(t)
    one.ensure(child, 7)
    assert child.blocks[1] != t.blocks[1] and one.cow_copies == 1
    assert (np.asarray(one.k_pool[:, child.blocks[1]]) == 7.0).all()
