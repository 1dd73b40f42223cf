#!/usr/bin/env python
"""End-to-end ResNet-50 train-step timing: plain vs optimize_for-fused.

Usage: python tools/probe_fused_resnet.py [plain|fused|both] [batch] [steps]
Methodology: SPMDTrainStep.run_steps bulked chains + engine.wait (a
single-shot timing is mostly dispatch+sync, not the device).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_fwd(mode, batch=128):
    """Forward-only (training-mode BN stats, no grad) chain timing."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel.spmd import _TRACE_STATE
    from mxnet_tpu.test_utils import chain_time_per_iter

    net = vision.resnet50_v1(prefix=f"f{mode}_")
    net.initialize(init=mx.initializer.Xavier())
    net.cast("bfloat16")
    model = net
    if mode == "fused":
        model = net.optimize_for(backend="tpu_fused_conv_bn")
    x0 = mx.nd.array(np.random.rand(batch, 3, 224, 224).astype(np.float32)
                     ).astype("bfloat16")
    model(x0)  # init
    handles = [p.data() for _, p in sorted(net.collect_params().items())]

    def fwd(xr):
        _TRACE_STATE.active = True
        saved = [h._data_ for h in handles]
        try:
            with autograd._RecordingStateScope(False, True):
                out = model(NDArray(xr))
            return xr + (jnp.sum(out.data.astype(jnp.float32))
                         * jnp.float32(1e-30)).astype(xr.dtype)
        finally:
            for h, s in zip(handles, saved):
                h._data_ = s
            _TRACE_STATE.active = False

    ms = chain_time_per_iter(fwd, x0.data, n1=5, n2=35, reps=3) * 1e3
    print(f"{mode} fwd-only: {ms:.2f} ms", flush=True)


def run(mode, batch=128, steps=100):
    import mxnet_tpu as mx
    from mxnet_tpu import engine, gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.resnet50_v1(prefix=f"{mode}_")
    net.initialize(init=mx.initializer.Xavier())
    net.cast("bfloat16")
    model = net
    if mode == "fused":
        model = net.optimize_for(backend="tpu_fused_conv_bn")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = parallel.SPMDTrainStep(model, loss_fn, "sgd",
                                  {"momentum": 0.9, "wd": 1e-4}, mesh=None)
    x = mx.nd.array(np.random.rand(batch, 3, 224, 224).astype(np.float32)
                    ).astype("bfloat16")
    y = mx.nd.array(np.random.randint(0, 10, (batch,)).astype(np.float32))

    t0 = time.perf_counter()
    step(x, y, lr=0.05, sync=False)
    engine.wait(step.run_steps(x, y, 3, lr=0.05))
    print(f"{mode}: compile+warm {time.perf_counter()-t0:.0f}s", flush=True)

    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        loss = step.run_steps(x, y, steps, lr=0.05)
        engine.wait(loss)
        dt = time.perf_counter() - t0
        best = dt if best is None or dt < best else best
    step_ms = best / steps * 1e3
    img_s = batch * steps / best
    tflops = 3 * 4.09e9 * batch / (best / steps) / 1e12
    print(f"{mode}: {step_ms:.2f} ms/step  {img_s:.0f} img/s  "
          f"{tflops:.1f} TFLOP/s  mfu={tflops/197.0:.3f}  "
          f"loss={float(loss.asnumpy() if hasattr(loss, 'asnumpy') else loss):.3f}",
          flush=True)
    return step_ms


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 100
    if which == "fwd":
        run_fwd("plain", batch)
        run_fwd("fused", batch)
    else:
        if which in ("plain", "both"):
            run("plain", batch, steps)
        if which in ("fused", "both"):
            run("fused", batch, steps)
