"""The paged-decode kernel on the chip, alone, at the GPT-2 serve cells'
shapes (128 slots, 12 heads of 64, 64 blocks of 16 tokens a sequence,
the whole pool of 12 layers) and at their two loads: about 280 live
blocks (``gpt2_small.serve_chat``) and about 1,144
(``gpt2_small.serve_chat_pool8193``), ragged contexts, most slots
empty. Against the gather oracle on the device, then timed: ms a call,
GB/s of live K/V, and from the two loads the kernel's fixed cost and
its cost a live block, which one traced cell cannot tell apart.

    chiprun -- python -m pytest tests_tpu/test_paged_decode.py -q -s -p no:xdist

``-s`` shows the JSON lines (what PERF.md quotes).
"""

import json
import time

import numpy as np
import pytest

B, H, D, BS, MB, LAYERS, NB = 128, 12, 64, 16, 64, 12, 3073
# live slots and live blocks: the two cells' window means, then a full
# table (what the memory's bandwidth allows)
LOADS = {"serve_chat": (22, 280), "pool8193": (86, 1144),
         "full": (B, B * MB)}


def _operands(slots_live, blocks_live, seed=0):
    """Ragged contexts over ``slots_live`` slots picked at random among
    the 128, ``blocks_live`` blocks in all, each sequence's last block
    partly filled; distinct shuffled pool blocks, the null block in the
    unused entries of every table (a full table asks for more blocks
    than the pool has, and sequences then share them)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    share = rng.dirichlet(np.full(slots_live, 2.0))
    blocks = np.clip(np.round(share * blocks_live).astype(int), 1, MB)
    while blocks.sum() != blocks_live:  # rounding's remainder
        i = rng.randint(slots_live)
        step = np.sign(blocks_live - blocks.sum())
        if 1 <= blocks[i] + step <= MB:
            blocks[i] += step
    live = rng.permutation(B)[:slots_live]
    lens = np.zeros(B, np.int32)
    lens[live] = (blocks - 1) * BS + rng.randint(1, BS + 1, slots_live)
    if blocks_live == B * MB:
        lens[:] = MB * BS
    tables = np.zeros((B, MB), np.int32)
    free = np.resize(1 + rng.permutation(NB - 1), blocks.sum())
    at = 0
    for row, n in zip(live, blocks):
        tables[row, :n] = free[at:at + n]
        at += n
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = (LAYERS, NB, BS, H * D)
    q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    k_pool = jax.random.normal(kk, pool, jnp.bfloat16)
    v_pool = jax.random.normal(kv, pool, jnp.bfloat16)
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens)


def _call_ms(fn, *args, n=40):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


@pytest.fixture(scope="module")
def readings():
    return {}


@pytest.mark.parametrize("load", list(LOADS))
def test_kernel_agrees_with_the_gather_oracle_and_its_time(load, readings):
    import jax

    from mxnet_tpu.ops import flash_attention as fa

    slots_live, blocks_live = LOADS[load]
    q, k_pool, v_pool, tables, lens = _operands(slots_live, blocks_live)
    scale = D ** -0.5
    layer = LAYERS - 1
    want = jax.jit(lambda *a: fa._jnp_paged_decode(*a, scale, layer=layer))(
        q, k_pool, v_pool, tables, lens)
    got = jax.jit(lambda *a: fa._pallas_paged_decode(*a, scale, layer=layer))(
        q, k_pool, v_pool, tables, lens)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    assert not got[np.asarray(lens) == 0].any()

    # a decode step's twelve calls in one executable, as the engine
    # makes them: the host's launch is paid once for the twelve
    def step(q, k_pool, v_pool, tables, lens):
        out = q
        for li in range(LAYERS):
            out = fa._pallas_paged_decode(out, k_pool, v_pool, tables, lens,
                                          scale, layer=li)
        return out

    ms = _call_ms(jax.jit(step), q, k_pool, v_pool, tables, lens) / LAYERS
    live_bytes = 2 * int(np.asarray(lens).sum()) * H * D * 2
    readings[load] = (blocks_live, ms)
    print(json.dumps({
        "load": load, "slots_live": slots_live, "blocks_live": blocks_live,
        "paged_decode_call_ms": ms, "live_kv_bytes": live_bytes,
        "GB_per_s": live_bytes / ms / 1e6,
        "share_of_819_GB_per_s": live_bytes / ms / 1e6 / 819}))
    assert live_bytes / ms / 1e6 < 1.05 * 819


def test_fixed_cost_and_cost_a_live_block(readings):
    """Two loads, two unknowns: ``ms = fixed + blocks * per_block``."""
    if not {"serve_chat", "pool8193"} <= set(readings):
        pytest.skip("the two loads were not both timed")
    (b0, t0), (b1, t1) = readings["serve_chat"], readings["pool8193"]
    per_block_us = (t1 - t0) / (b1 - b0) * 1e3
    fixed_ms = t0 - b0 * per_block_us / 1e3
    print(json.dumps({"fixed_ms_a_call": fixed_ms,
                      "us_a_live_block": per_block_us,
                      "bound_us_a_live_block": 2 * BS * H * D * 2 / 819e3}))
    # the parent's grid: 0.68 ms fixed, 1.07 us a live block
    assert fixed_ms < 0.34 and per_block_us < 0.54
