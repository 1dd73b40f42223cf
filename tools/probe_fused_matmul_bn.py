#!/usr/bin/env python
"""Probe: Pallas fused matmul+BN-stats vs XLA at ResNet-50 1x1-conv shapes.

Round-5 de-risk for the fused conv+BN plan (VERDICT r4 "do this" #1).
Per stride-1 1x1-conv shape (bs128 NHWC flattened), times:

  dot        XLA matmul only (floor — what a BN-free layer pays)
  xla_bn     XLA matmul + one-pass f32 stats + materialised apply+relu
             (what the framework does today)
  fused      Pallas matmul with stats epilogue + XLA apply+relu
  fused_pro  Pallas matmul with normalize+relu PROLOGUE on a raw input
             and stats epilogue (no materialised apply anywhere)

Methodology: dependent fori_loop chains, two-point slope
(test_utils.chain_time_per_iter); a single-shot timing is mostly
dispatch+sync.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import fused_conv_bn as F
from mxnet_tpu.test_utils import chain_time_per_iter

# (M, K, N, count) — count = how many times this shape appears per
# ResNet-50 train step fwd (stride-1 1x1 convs only), bs128 @224
SHAPES = [
    (401408, 64, 64, 1),      # s0 b0 c1
    (401408, 256, 64, 2),     # s0 b1-2 c1
    (401408, 64, 256, 3),     # s0 c3
    (100352, 512, 128, 3),    # s1 b1-3 c1
    (100352, 128, 512, 4),    # s1 c3
    (25088, 1024, 256, 5),    # s2 b1-5 c1
    (25088, 256, 1024, 6),    # s2 c3
    (6272, 2048, 512, 2),     # s3 b1-2 c1
    (6272, 512, 2048, 3),     # s3 c3
]


def one_pass_stats_apply(y, materialize=True):
    yf = y.astype(jnp.float32)
    mean = jnp.mean(yf, axis=0)
    ex2 = jnp.mean(yf * yf, axis=0)
    var = jnp.maximum(ex2 - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + 1e-5)
    if not materialize:
        return inv[0]
    out = jnp.maximum((y - mean.astype(y.dtype)) * inv.astype(y.dtype), 0.0)
    return jnp.sum(out.astype(jnp.float32))


def probe_shape(M, K, N, bm=None, bn=None, bk=None):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
    w = jnp.asarray(rng.randn(K, N) * 0.05, jnp.bfloat16)
    s = jnp.asarray(rng.rand(K) + 0.5, jnp.float32)
    t = jnp.asarray(rng.randn(K) * 0.1, jnp.float32)
    eps = jnp.float32(1e-30)

    def chain(fn):
        # kernels here are 0.05-1 ms: chains must be LONG or the two-point
        # slope drowns in the ±run variance (r4 lesson, memory notes).
        # Every variant consumes a FULL reduction of its outputs — a
        # scalar tap (y[0,0]) lets XLA dead-code the rest of the matmul
        # (observed: 0.018 ms for a 256 MB matmul), while Pallas calls
        # are opaque and can't be DCE'd, poisoning the comparison.
        return chain_time_per_iter(fn, x, n1=100, n2=900, reps=4) * 1e3

    def dot_only(xc):
        # abs() blocks XLA's sum(AB) -> colsum(A)@rowsum(B) algebraic
        # rewrite, which otherwise deletes the matmul entirely
        y = jnp.dot(xc, w, preferred_element_type=jnp.float32)
        return xc + (jnp.sum(jnp.abs(y)) * eps).astype(xc.dtype)

    def xla_bn(xc):
        y = jnp.dot(xc, w, preferred_element_type=jnp.float32
                    ).astype(xc.dtype)
        r = one_pass_stats_apply(y, materialize=True)
        return xc + (r * eps).astype(xc.dtype)

    def fused(xc):
        y, ysum, yssq = F._fused_fwd_pallas(xc, w, None, None,
                                            bm=bm, bn=bn, bk=bk)
        mean = ysum / M
        var = jnp.maximum(yssq / M - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + 1e-5)
        out = jnp.maximum((y - mean.astype(y.dtype))
                          * inv.astype(y.dtype), 0.0)
        return xc + (jnp.sum(out.astype(jnp.float32)) * eps).astype(xc.dtype)

    def fused_pro(xc):
        # xc plays the RAW previous output; prologue applies s,t+relu
        # in-kernel, so no applied tensor is ever materialised
        y, ysum, yssq = F._fused_fwd_pallas(xc, w, s, t, relu=True,
                                            bm=bm, bn=bn, bk=bk)
        return xc + ((jnp.sum(ysum) + jnp.sum(yssq)) * eps).astype(xc.dtype)

    res = {}
    for name, fn in [("dot", dot_only), ("xla_bn", xla_bn),
                     ("fused", fused), ("fused_pro", fused_pro)]:
        try:
            res[name] = chain(fn)
        except Exception as e:  # noqa: BLE001
            res[name] = float("nan")
            print(f"  {name} FAILED: {type(e).__name__}: {e}", flush=True)
    return res


def main():
    print(f"devices: {jax.devices()}", flush=True)
    total = {"dot": 0.0, "xla_bn": 0.0, "fused": 0.0, "fused_pro": 0.0}
    for (M, K, N, count) in SHAPES:
        r = probe_shape(M, K, N)
        for k in total:
            total[k] += r[k] * count
        print(f"M={M:7d} K={K:5d} N={N:5d} x{count}:  "
              + "  ".join(f"{k}={v:7.3f}ms" for k, v in r.items()),
              flush=True)
    print("--- fwd totals over stride-1 1x1 convs (ms/step) ---", flush=True)
    print("  ".join(f"{k}={v:7.2f}" for k, v in total.items()), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "sweep":
        # block-size sweep on two representative shapes
        for (M, K, N) in [(401408, 64, 256), (25088, 1024, 256)]:
            for bm in (512, 1024):
                for bn in (128, 256):
                    for bk in (256, 512):
                        if bk > K or bn > N:
                            continue
                        r = probe_shape(M, K, N, bm=bm, bn=bn, bk=bk)
                        print(f"M={M} K={K} N={N} bm={bm} bn={bn} bk={bk}: "
                              f"fused={r['fused']:.3f} "
                              f"fused_pro={r['fused_pro']:.3f}", flush=True)
    else:
        main()
