"""The retention decode kernel on the chip, at the Brumby layer's own
sizes (40 query heads over 8 KV heads of 128; 16 slots): against the
``jax.numpy`` form of the same step, with some slots not live, and how
near it comes to the memory's bandwidth.

    chiprun -- python -m pytest tests_tpu/test_retention_decode.py -q -s -p no:xdist

``-s`` shows the JSON line the timing prints (what PERF.md quotes).
"""

import json
import time

import numpy as np


def _operands(B, H, KVH, d, layers, seed=0):
    import jax.numpy as jnp

    from mxnet_tpu.ops import retention as R

    rng = np.random.RandomState(seed)
    s_shape, z_shape = R.state_shapes(layers, B, KVH, d)
    q = jnp.asarray(rng.randn(B, H, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, KVH, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, KVH, d), jnp.bfloat16)
    log_g = jnp.asarray(-np.abs(rng.randn(B, KVH)) * 0.01, jnp.float32)
    S = jnp.asarray(rng.randn(*s_shape) * 0.1, jnp.float32)
    z = jnp.asarray(np.abs(rng.randn(*z_shape)) + 1.0, jnp.float32)
    slots = jnp.asarray(rng.permutation(B), jnp.int32)
    return q, k, v, log_g, S, z, slots


def test_kernel_agrees_with_the_jnp_step_and_leaves_dead_slots_alone():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import retention as R

    B, H, KVH, d, layers = 6, 40, 8, 128, 2
    q, k, v, log_g, S, z, slots = _operands(B, H, KVH, d, layers)
    active = jnp.asarray([True, False, True, True, False, True])
    want_o, want_S, want_z = jax.jit(
        lambda *a: R._jnp_step(*a, 1))(q, k, v, log_g, S, z, slots, active)
    o, S1, z1 = jax.jit(lambda *a: R._pallas_step(*a, 1))(
        q, k, v, log_g, S, z, slots, active)
    live = np.asarray(active)
    at = np.asarray(slots)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live],
                               rtol=2e-4, atol=2e-4)
    assert not np.asarray(o)[~live].any()
    for got, want, before in ((S1, want_S, S), (z1, want_z, z)):
        got, want, before = map(np.asarray, (got, want, before))
        np.testing.assert_allclose(got[1, at[live]], want[1, at[live]],
                                   rtol=1e-5, atol=1e-5)
        # layer 0, and the dead rows' states of layer 1, bit for bit
        assert (got[0] == before[0]).all()
        assert (got[1, at[~live]] == before[1, at[~live]]).all()


def test_kernel_time_against_the_memory_bound():
    import jax

    from mxnet_tpu.ops import retention as R

    B, H, KVH, d = 16, 40, 8, 128
    q, k, v, log_g, S, z, slots = _operands(B, H, KVH, d, 1)
    active = np.ones(B, bool)
    step = jax.jit(lambda q, k, v, g, S, z: R._pallas_step(
        q, k, v, g, S, z, slots, active, 0), donate_argnums=(4, 5))
    o, S, z = step(q, k, v, log_g, S, z)
    jax.block_until_ready(o)
    n = 50
    t0 = time.perf_counter()
    for _ in range(n):
        o, S, z = step(q, k, v, log_g, S, z)
    jax.block_until_ready(o)
    dt = (time.perf_counter() - t0) / n
    moved = 2 * B * (S.nbytes + z.nbytes) / (B + 1)
    print(json.dumps({"retention_decode_call_ms": dt * 1e3,
                      "state_bytes_moved": moved,
                      "GB_per_s": moved / dt / 1e9,
                      "share_of_819_GB_per_s": moved / dt / 819e9}))
    assert moved / dt > 0.2 * 819e9
