#!/usr/bin/env python
"""Allreduce bandwidth benchmark (reference: ``tools/bandwidth/measure.py`` —
the harness behind the BASELINE KVStore-bandwidth metric).

Measures both the KVStore pushpull path and the fused in-step psum path
over the device mesh (the latter is what training actually uses).

  python tools/bandwidth/measure.py --kv-store device --size 64MB
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def parse_size(s):
    s = s.upper()
    for suffix, mult in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10)):
        if s.endswith(suffix):
            return int(float(s[:-2]) * mult)
    return int(s)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--kv-store", type=str, default="device",
                        help="device|local|dist_tpu_sync|psum (psum = fused "
                             "in-graph allreduce, the training fast path)")
    parser.add_argument("--size", type=str, default="64MB")
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--num-keys", type=int, default=1)
    args = parser.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx

    nbytes = parse_size(args.size)
    n_elem = nbytes // 4
    ndev = len(jax.devices())

    if args.kv_store == "psum":
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("dp",))
        x = jax.device_put(
            jnp.ones((ndev, n_elem // max(ndev, 1)), jnp.float32),
            NamedSharding(mesh, P("dp", None)))

        @jax.jit
        def allreduce(v):
            return jax.shard_map(lambda a: jax.lax.psum(a, "dp"), mesh=mesh,
                                 in_specs=P("dp", None),
                                 out_specs=P("dp", None))(v)

        r = allreduce(x)
        _ = np.asarray(r).ravel()[0]  # sync: dependent host read
        t0 = time.perf_counter()
        for _ in range(args.num_iters):
            r = allreduce(r)
        _ = np.asarray(r).ravel()[0]
        dt = time.perf_counter() - t0
        total = nbytes * args.num_iters
        # ring allreduce moves 2*(n-1)/n of the data per device
        algo_bytes = total * 2 * (ndev - 1) / max(ndev, 1)
        print(f"devices={ndev} size={args.size} iters={args.num_iters} "
              f"time={dt:.4f}s algo_bw={algo_bytes / dt / (1 << 30):.2f} GB/s")
        return

    kv = mx.kv.create(args.kv_store)
    shape = (args.num_keys, n_elem // args.num_keys)
    kv.init("x", mx.nd.zeros(shape))
    vals = [mx.nd.ones(shape) for _ in range(max(1, min(ndev, 8)))]
    outs = [mx.nd.zeros(shape) for _ in vals]
    kv.pushpull("x", vals, out=outs)
    outs[0].wait_to_read()
    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        kv.pushpull("x", vals, out=outs)
    _ = outs[0].asnumpy().ravel()[0]
    dt = time.perf_counter() - t0
    total = nbytes * args.num_iters * len(vals)
    print(f"kvstore={args.kv_store} ndev={len(vals)} size={args.size} "
          f"iters={args.num_iters} time={dt:.4f}s "
          f"bw={total / dt / (1 << 30):.2f} GB/s")


if __name__ == "__main__":
    main()
