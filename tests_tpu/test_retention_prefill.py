"""The retention prefill kernel on the chip, at the Brumby layer's own
sizes (40 query heads over 8 KV heads of 128, chunks of 256): one
sequence through one layer in a 4,096-token bucket whose prompt is
2,900 tokens long, against the ``jax.numpy`` form on the same bfloat16
operands, and both timed. On a host with a TPU:

    python -m pytest tests_tpu/test_retention_prefill.py -q -s -p no:xdist

``-s`` shows the JSON line the timing prints (what PERF.md quotes).
"""

import json
import math
import time

import numpy as np

KVH, G, D, C = 8, 5, 128, 256
BUCKET, LENGTH = 4096, 2900


def chunk_flops(kv_heads, groups, chunk, d):
    """The operations of one chunk through one layer: ``phi(q)`` against
    the state and its normaliser, ``phi(k)`` into both, and the chunk's
    attention form (scores and their product with ``v``)."""
    P = (d // 2 + 1) * d
    rows = kv_heads * groups * chunk
    return (2 * rows * P * (d + 1)                  # num and den
            + 2 * kv_heads * chunk * P * (d + 1)    # S and z updated
            + 2 * 2 * rows * chunk * d)             # q k^T, then w v


def _operands(seed=0):
    import jax.numpy as jnp

    from mxnet_tpu.ops import retention as R

    rng = np.random.RandomState(seed)
    H = KVH * G
    q = jnp.asarray(rng.randn(BUCKET, H, D) / D ** 0.25, jnp.bfloat16)
    k = jnp.asarray(rng.randn(BUCKET, KVH, D) / D ** 0.25, jnp.bfloat16)
    v = jnp.asarray(rng.randn(BUCKET, KVH, D), jnp.bfloat16)
    log_g = jnp.asarray(-np.abs(rng.randn(BUCKET, KVH)) * 0.01, jnp.float32)
    S = jnp.asarray(rng.randn(KVH, D, R.feature_dim(D)) * 0.1, jnp.float32)
    z = jnp.asarray(np.abs(rng.randn(KVH, R.feature_rows(D), D)) + 1.0,
                    jnp.float32)
    return q, k, v, log_g, (S, z)


def _calls():
    import jax

    from mxnet_tpu.ops import retention as R

    kernel = jax.jit(lambda q, k, v, g, st, n: R._pallas_chunked(
        q, k, v, g, st, n, C))
    plain = jax.jit(lambda q, k, v, g, st, n: R._jnp_chunked(
        q, k, v, g, st, n, C))
    return kernel, plain


def test_kernel_agrees_with_the_jnp_form():
    """Outputs against the largest output, the leaving state against
    its largest entry: both forms round ``phi`` and the state to
    bfloat16 on their way into the matrix unit, in other orders. The
    tolerance, a hundredth, is bfloat16's rounding (2**-8) with room:
    the interpreter reads 1.2e-3..4.2e-3 against the jnp form on the CPU
    (``tests/test_retention.py``); the line printed here is the chip's
    reading."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import retention as R

    args = _operands()
    kernel, plain = _calls()
    o, (S, z) = kernel(*args, jnp.int32(LENGTH))
    want_o, (want_S, want_z) = plain(*args, jnp.int32(LENGTH))
    assert not np.asarray(o)[LENGTH:].any()
    errs = {}
    for name, got, want in (("o", o, want_o), ("S", S, want_S),
                            ("z", z, want_z)):
        got, want = np.asarray(got), np.asarray(want)
        errs[name] = float(np.abs(got - want).max() / np.abs(want).max())
    print(json.dumps({"retention_prefill_rel_err": errs}))
    assert max(errs.values()) < 1e-2, errs
    # the public entry takes the kernel for these operands
    assert R._use_pallas(D)


def _time(fn, args, n=10):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def test_kernel_time_against_the_jnp_form():
    import jax.numpy as jnp

    args = (*_operands(1), jnp.int32(LENGTH))
    kernel, plain = _calls()
    t_kernel = _time(kernel, args)
    t_plain = _time(plain, args)
    live = math.ceil(LENGTH / C)
    flops = live * chunk_flops(KVH, G, C, D)
    print(json.dumps({
        "retention_prefill_call_ms": t_kernel * 1e3,
        "jnp_chunked_call_ms": t_plain * 1e3,
        "live_chunks": live, "chunks": BUCKET // C,
        "gflop_a_chunk": chunk_flops(KVH, G, C, D) / 1e9,
        "kernel_ms_a_live_chunk": t_kernel * 1e3 / live,
        "kernel_tflop_per_s": flops / t_kernel / 1e12,
        "share_of_197_tflop_per_s": flops / t_kernel / 197e12}))
    assert t_kernel <= t_plain / 3
