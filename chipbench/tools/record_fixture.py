#!/usr/bin/env python3
"""Records the small trace that ``chipbench/tests`` reads: a few runs of
a small jitted program with a flash-attention call inside, under the
benchmark's spans, on whatever TPU chips the machine has (with four, an
all-reduce across them as well).

    python chipbench/tools/record_fixture.py chiprun_out/fixture_1chip
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    out = argv[1]
    devices = jax.devices()
    n = len(devices)
    mesh = jax.sharding.Mesh(devices, ("dp",))
    P = jax.sharding.PartitionSpec

    def body(q, w):
        o = fa.flash_attention(q, q, q)
        y = jnp.tanh(o.reshape(o.shape[0], -1, 64) @ w)
        return jax.lax.psum(y.sum(), "dp") if n > 1 else y.sum()

    if n > 1:
        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                                  out_specs=P(), check_vma=False))
    else:
        f = jax.jit(body)
    q = jnp.ones((2 * n, 4, 256, 64), jnp.bfloat16)
    w = jnp.ones((64, 64), jnp.bfloat16)
    f(q, w).block_until_ready()
    tmp = out + "_tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("cb:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("cb:step"):
                r = f(q, w)
            with jax.profiler.TraceAnnotation("cb:wait_last"):
                r.block_until_ready()
            with jax.profiler.TraceAnnotation("cb:pause"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    shutil.copy(src, out + ".xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    print(out + ".xplane.pb", os.path.getsize(out + ".xplane.pb"), "bytes")


if __name__ == "__main__":
    main(sys.argv)
