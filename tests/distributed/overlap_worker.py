"""Multi-process overlap/ZeRO worker: barrier-mode vs bucket-ready
overlapped-mode training must be bit-identical, and ZeRO-2 must match
ZeRO-0, on a REAL multi-process mesh (2 procs x 2 devices under
``tools/launch.py -n 2`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=2``). Also runs
standalone (1 proc x 4 devices) as the single-process reference."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel

if "MXTPU_COORDINATOR" in os.environ:
    from mxnet_tpu.kvstore.dist import init_distributed

    init_distributed()
    nprocs = int(os.environ["MXTPU_NUM_PROCESSES"])
    rank = int(os.environ["MXTPU_PROCESS_ID"])
else:
    nprocs, rank = 1, 0

assert jax.device_count() == 4, jax.device_count()
mesh = parallel.make_mesh({"dp": 4})
loss_fn = gluon.loss.L2Loss()

rng = np.random.RandomState(0)
X = rng.uniform(-1, 1, (16, 16)).astype(np.float32)
Y = rng.uniform(-1, 1, (16, 8)).astype(np.float32)


def build():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu", in_units=16),
            gluon.nn.Dense(8, in_units=32))
    net.initialize(init=mx.initializer.Constant(0.0))
    r = np.random.RandomState(1)
    for _, p in sorted(net.collect_params().items()):
        p.set_data(mx.nd.array(r.uniform(-0.2, 0.2, p.shape)
                               .astype(np.float32)))
    return net


def run(mode, stage=0, steps=10):
    mx.random.seed(5)
    net = build()
    step = parallel.SPMDTrainStep(net, loss_fn, "adam", {}, mesh,
                                  overlap=mode, zero_stage=stage)
    loss = None
    for _ in range(steps):
        loss = float(step(mx.nd.array(X), mx.nd.array(Y), lr=0.05))
    step.sync_to_block()
    csum = float(sum(np.abs(np.asarray(p.data().data)).sum()
                     for _, p in net.collect_params().items()))
    return loss, csum


loss_b, sum_b = run("barrier")
loss_r, sum_r = run("ready")
assert loss_b == loss_r, (loss_b, loss_r)
assert sum_b == sum_r, (sum_b, sum_r)
loss_z2, sum_z2 = run("ready", stage=2)
if nprocs == 1:
    assert loss_z2 == loss_r, (loss_z2, loss_r)
    assert sum_z2 == sum_r, (sum_z2, sum_r)
else:
    # XLA:CPU's cross-process reduce-scatter adds (d0+d1)+(d2+d3) where
    # its all-reduce adds in another order (read in PR 22: 1415 of 4096
    # random float32 sums differ); after ten steps loss and checksum each
    # sit one float32 ulp apart, run after run. Hold them to two.
    np.testing.assert_array_max_ulp(np.float32(loss_z2), np.float32(loss_r),
                                    maxulp=2)
    np.testing.assert_array_max_ulp(np.float32(sum_z2), np.float32(sum_r),
                                    maxulp=2)
print(f"OVERLAP_WORKER_OK rank={rank}/{nprocs} loss={loss_r:.10f} "
      f"checksum={sum_r:.8f}", flush=True)
