#!/usr/bin/env python
"""Layout probe for the ResNet-50 conv path (round-4 perf work).

Times a hand-rolled ResNet-50 v1 train step (fwd+bwd+SGD-momentum, BN train
stats) in raw JAX under different data layouts/dtypes, independent of the
framework, to locate the MFU gap seen in a chip record from before this
round, deleted in PR 22; the figure is a claim.

Usage: python tools/probe_resnet_layout.py [nchw|nhwc|both] [batch]
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mxnet_tpu import engine

BOTTLENECK = [3, 4, 6, 3]
WIDTHS = [64, 128, 256, 512]


def _conv_init(key, cin, cout, k, layout):
    w = jax.random.normal(key, (cout, cin, k, k), jnp.float32) * 0.05
    if layout == "NHWC":
        w = w.transpose(2, 3, 1, 0)  # HWIO
    return w.astype(jnp.bfloat16)


def _bn_init(c):
    return {"gamma": jnp.ones((c,), jnp.bfloat16),
            "beta": jnp.zeros((c,), jnp.bfloat16),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def init_params(key, layout):
    keys = iter(jax.random.split(key, 256))
    params = {"conv0": _conv_init(next(keys), 3, 64, 7, layout),
              "bn0": _bn_init(64)}
    cin = 64
    for si, (n, w) in enumerate(zip(BOTTLENECK, WIDTHS)):
        cout = w * 4
        for bi in range(n):
            pre = f"s{si}b{bi}"
            params[pre + "c1"] = _conv_init(next(keys), cin, w, 1, layout)
            params[pre + "n1"] = _bn_init(w)
            params[pre + "c2"] = _conv_init(next(keys), w, w, 3, layout)
            params[pre + "n2"] = _bn_init(w)
            params[pre + "c3"] = _conv_init(next(keys), w, cout, 1, layout)
            params[pre + "n3"] = _bn_init(cout)
            if bi == 0:
                params[pre + "cd"] = _conv_init(next(keys), cin, cout, 1, layout)
                params[pre + "nd"] = _bn_init(cout)
            cin = cout
    params["fc_w"] = (jax.random.normal(next(keys), (2048, 1000), jnp.float32)
                      * 0.01).astype(jnp.bfloat16)
    params["fc_b"] = jnp.zeros((1000,), jnp.bfloat16)
    return params


def conv(x, w, stride, pad, layout):
    dn = ("NCHW", "OIHW", "NCHW") if layout == "NCHW" else \
        ("NHWC", "HWIO", "NHWC")
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=[(pad, pad)] * 2,
        dimension_numbers=lax.conv_dimension_numbers(x.shape, w.shape, dn))


BN_MODE = "fp32"  # fp32 | bf16 | 1pass | none


def bn_relu(x, p, layout, relu=True):
    ax = 1 if layout == "NCHW" else -1
    shape = [1] * 4
    shape[ax] = x.shape[ax]
    if BN_MODE == "none":
        out = x + p["beta"].reshape(shape)
        return jnp.maximum(out, 0) if relu else out
    red = tuple(i for i in range(4) if i != (ax % 4))
    xf = x.astype(jnp.float32) if BN_MODE in ("fp32", "1pass") else x
    if BN_MODE == "1pass":
        # one fused read: both reductions share the same pass over x
        mean = jnp.mean(xf, axis=red)
        ex2 = jnp.mean(xf * xf, axis=red)
        var = jnp.maximum(ex2 - mean * mean, 0.0)
    else:
        mean = jnp.mean(xf, axis=red)
        var = jnp.var(xf, axis=red)
    inv = lax.rsqrt(var + 1e-5).astype(x.dtype)
    out = (x - mean.astype(x.dtype).reshape(shape)) * inv.reshape(shape) \
        * p["gamma"].reshape(shape) + p["beta"].reshape(shape)
    if relu:
        out = jnp.maximum(out, 0)
    return out


def forward(params, x, layout):
    x = conv(x, params["conv0"], 2, 3, layout)
    x = bn_relu(x, params["bn0"], layout)
    pool_dims = (1, 1, 3, 3) if layout == "NCHW" else (1, 3, 3, 1)
    pool_str = (1, 1, 2, 2) if layout == "NCHW" else (1, 2, 2, 1)
    pool_pad = ((0, 0), (0, 0), (1, 1), (1, 1)) if layout == "NCHW" else \
        ((0, 0), (1, 1), (1, 1), (0, 0))
    x = lax.reduce_window(x, -jnp.inf, lax.max, pool_dims, pool_str, pool_pad)
    for si, (n, w) in enumerate(zip(BOTTLENECK, WIDTHS)):
        for bi in range(n):
            pre = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            sc = x
            if pre + "cd" in params:
                sc = conv(x, params[pre + "cd"], stride, 0, layout)
                sc = bn_relu(sc, params[pre + "nd"], layout, relu=False)
            y = conv(x, params[pre + "c1"], stride, 0, layout)
            y = bn_relu(y, params[pre + "n1"], layout)
            y = conv(y, params[pre + "c2"], 1, 1, layout)
            y = bn_relu(y, params[pre + "n2"], layout)
            y = conv(y, params[pre + "c3"], 1, 0, layout)
            y = bn_relu(y, params[pre + "n3"], layout, relu=False)
            x = jnp.maximum(y + sc, 0)
    red = (2, 3) if layout == "NCHW" else (1, 2)
    x = jnp.mean(x.astype(jnp.float32), axis=red).astype(jnp.bfloat16)
    return jnp.matmul(x, params["fc_w"]) + params["fc_b"]


def make_step(layout):
    def loss_fn(params, x, y):
        logits = forward(params, x, layout).astype(jnp.float32)
        lse = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lse, y[:, None], 1))

    def step(carry, _):
        params, mom, x, y = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        new_mom = jax.tree.map(lambda m, g: 0.9 * m + g.astype(m.dtype),
                               mom, grads)
        new_p = jax.tree.map(
            lambda p, m: p - (0.05 * m).astype(p.dtype), params, new_mom)
        return (new_p, new_mom, x, y), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4,))
    def run(params, mom, x, y, n):
        (params, mom, _, _), losses = lax.scan(
            step, (params, mom, x, y), None, length=n)
        return params, mom, losses[-1]

    return run


def probe(layout, batch=128, steps=50):
    key = jax.random.PRNGKey(0)
    params = init_params(key, layout)
    mom = jax.tree.map(lambda p: jnp.zeros_like(p), params)
    shape = (batch, 3, 224, 224) if layout == "NCHW" else (batch, 224, 224, 3)
    x = jnp.asarray(np.random.rand(*shape), jnp.bfloat16)
    y = jnp.asarray(np.random.randint(0, 1000, (batch,)), jnp.int32)
    run = make_step(layout)
    n = steps
    t0 = time.perf_counter()
    params, mom, loss = run(params, mom, x, y, n)
    engine.wait(loss)
    print(f"{layout} compile+first: {time.perf_counter()-t0:.1f}s "
          f"loss={float(loss):.3f}", flush=True)
    t0 = time.perf_counter()
    params, mom, loss = run(params, mom, x, y, n)
    engine.wait(loss)
    dt = time.perf_counter() - t0
    step_ms = dt / steps * 1e3
    img_s = batch * steps / dt
    flops = 3 * 4.09e9 * batch
    tflops = flops / (dt / steps) / 1e12
    print(f"{layout} bs{batch}: {step_ms:.2f} ms/step, {img_s:.0f} img/s, "
          f"{tflops:.1f} TFLOP/s, mfu={tflops/197.0:.3f}", flush=True)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    BN_MODE = sys.argv[3] if len(sys.argv) > 3 else "fp32"
    print(f"bn_mode={BN_MODE}")
    if which in ("nchw", "both"):
        probe("NCHW", batch)
    if which in ("nhwc", "both"):
        probe("NHWC", batch)
