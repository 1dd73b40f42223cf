"""Input-side fast path (PR4 tentpole): async device prefetch, shape
stabilization (pad/bucket + retrace budget), persistent compile cache +
warmup, and the PrefetchingIter/DataLoader lifecycle fixes."""

import gc
import json
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, observability as obs
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data import (
    ArrayDataset,
    DataLoader,
    DevicePrefetcher,
    SequenceBucketer,
    pad_batch,
)
from mxnet_tpu.gluon.data.prefetcher import wrap_for_fit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# DevicePrefetcher
# ---------------------------------------------------------------------------

def test_prefetcher_matches_direct_iteration():
    X = np.random.rand(10, 3).astype(np.float32)
    Y = np.arange(10).astype(np.float32)
    loader = DataLoader(ArrayDataset(X, Y), batch_size=4, last_batch="keep")
    direct = [(x.asnumpy(), y.asnumpy()) for x, y in loader]
    pf = DevicePrefetcher(loader, device=mx.cpu())
    for _ in range(2):  # two epochs through the same wrapper
        got = [(x.asnumpy(), y.asnumpy()) for x, y in pf]
        assert len(got) == len(direct)
        for (dx, dy), (gx, gy) in zip(direct, got):
            np.testing.assert_array_equal(dx, gx)
            np.testing.assert_array_equal(dy, gy)


def test_prefetcher_preserves_structure_and_commits_to_device():
    import jax

    loader = DataLoader(ArrayDataset(np.random.rand(8, 2).astype(np.float32),
                                     np.arange(8).astype(np.float32)),
                        batch_size=4)
    (x, y) = next(iter(DevicePrefetcher(loader, device=mx.cpu())))
    assert isinstance(x, mx.NDArray) and isinstance(y, mx.NDArray)
    assert x.data.devices() == {jax.local_devices()[0]}


def test_prefetcher_propagates_source_error_and_closes():
    def bad():
        yield mx.nd.ones((2, 2))
        raise RuntimeError("boom in source")

    pf = DevicePrefetcher(bad(), device=mx.cpu())
    it = iter(pf)
    next(it)
    with pytest.raises(RuntimeError, match="boom in source"):
        next(it)
    assert pf._thread is None  # closed (thread joined), not leaked
    pf.close()
    pf.close()  # idempotent


def test_prefetcher_close_unblocks_full_queue():
    def endless():
        i = 0
        while True:
            yield np.full((4,), i, np.float32)
            i += 1

    pf = DevicePrefetcher(endless(), device=mx.cpu(), depth=2)
    it = iter(pf)
    next(it)
    time.sleep(0.1)  # let the producer fill + block on the bounded queue
    pf.close()
    assert pf._thread is None


def test_prefetcher_dataiter_protocol():
    data = np.arange(24, dtype=np.float32).reshape(12, 2)
    it = mx.io.NDArrayIter(data, np.arange(12, dtype=np.float32),
                           batch_size=4, shuffle=False)
    pf = DevicePrefetcher(it, device=mx.cpu())
    assert pf.batch_size == 4  # attribute passthrough
    assert len(pf.provide_data) == 1
    for _ in range(2):  # epochs: wrapper resets the exhausted source
        batches = list(pf)
        assert len(batches) == 3
        np.testing.assert_array_equal(batches[0].data[0].asnumpy(),
                                      data[:4])


def test_prefetcher_shards_over_mesh():
    import jax

    from mxnet_tpu.parallel import make_mesh, shard_batch

    mesh = make_mesh({"dp": len(jax.devices())})
    src = [[mx.nd.array(np.random.rand(8, 3).astype(np.float32))]
           for _ in range(2)]
    pf = DevicePrefetcher(src, mesh=mesh)
    (batch,), = [b for b in pf][:1]
    assert batch.shape == (8, 3)
    # already-sharded: shard_batch recognizes the placement and returns
    # the SAME array instead of a host round-trip
    again = shard_batch(batch, mesh)
    assert again is batch.data


def test_spmd_step_accepts_presharded_batches():
    """An SPMDTrainStep fed mesh-sharded batches (the DevicePrefetcher
    staging path) must still resolve deferred init — the eager probe
    runs on a host copy, never on the 8-device global array."""
    import jax

    from mxnet_tpu import parallel

    mesh = parallel.make_mesh({"dp": len(jax.devices())})
    net = nn.Dense(2, in_units=8)
    net.initialize(init=mx.initializer.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = parallel.SPMDTrainStep(net, loss_fn, "sgd", {}, mesh=mesh)
    rng = np.random.RandomState(0)
    src = [(rng.randn(16, 8).astype(np.float32),
            rng.randint(0, 2, (16,)).astype(np.float32))
           for _ in range(3)]
    losses = [step(x, y, lr=0.1)
              for x, y in DevicePrefetcher(src, mesh=mesh)]
    assert all(np.isfinite(l) for l in losses)


def test_wrap_for_fit_respects_env(monkeypatch):
    src = [1, 2, 3]
    monkeypatch.setenv("MXTPU_DEVICE_PREFETCH", "0")
    assert wrap_for_fit(src) is src
    monkeypatch.setenv("MXTPU_DEVICE_PREFETCH", "3")
    wrapped = wrap_for_fit(src)
    assert isinstance(wrapped, DevicePrefetcher)
    assert wrap_for_fit(wrapped) is wrapped  # never double-wraps
    # a device-enabled DataLoader already prefetches: no second wrapper
    loader = DataLoader(ArrayDataset(np.zeros((4, 2), np.float32),
                                     np.zeros((4,), np.float32)),
                        batch_size=2, device=mx.cpu())
    assert wrap_for_fit(loader) is loader


def test_prefetcher_iter_on_inflight_iterator_loses_nothing():
    """list(it) / enumerate(it) call iter() on the returned iterator
    again — that must NOT restart the epoch (a restart drops whatever
    the producer already staged)."""
    loader = DataLoader(ArrayDataset(np.arange(10, dtype=np.float32),
                                     np.arange(10, dtype=np.float32)),
                        batch_size=4, last_batch="keep", device=mx.cpu())
    it = iter(loader)
    time.sleep(0.1)  # let the producer stage batches ahead
    assert len(list(it)) == 3  # list() re-invokes iter() internally


def test_prefetcher_stays_exhausted_until_reiterated():
    """Iterator protocol: next() after exhaustion keeps raising
    StopIteration (no silent epoch restart / duplicated batches); a new
    iter() or reset() starts the next epoch."""
    pf = DevicePrefetcher(DataLoader(
        ArrayDataset(np.arange(8, dtype=np.float32),
                     np.arange(8, dtype=np.float32)), batch_size=4),
        device=mx.cpu())
    it = iter(pf)
    assert len(list(it)) == 2
    for _ in range(3):
        with pytest.raises(StopIteration):
            next(it)
    assert len(list(iter(pf))) == 2  # explicit re-iteration restarts


def test_prefetcher_telemetry_series():
    prev = obs.set_enabled(True)
    try:
        obs.reset()
        loader = DataLoader(
            ArrayDataset(np.random.rand(8, 4).astype(np.float32),
                         np.arange(8, dtype=np.float32)), batch_size=4)
        list(DevicePrefetcher(loader, device=mx.cpu()))
        assert obs.DATA_PREFETCH_BATCHES.total() == 2
        # X: 8 rows x 4 cols x 4 B; Y: 8 x 4 B — across the 2 batches
        assert obs.DATA_H2D_BYTES.total() == 8 * 4 * 4 + 8 * 4
        assert obs.DATA_H2D_SECONDS.total() == 2
        prom = obs.dump_prometheus()
        assert "mxtpu_data_h2d_bytes_total" in prom
        assert "mxtpu_data_prefetch_wait_seconds_total" in prom
    finally:
        obs.set_enabled(prev)
        obs.reset()


# ---------------------------------------------------------------------------
# DataLoader: device=, last_batch="pad", pin_memory, __del__
# ---------------------------------------------------------------------------

def test_dataloader_device_and_pad_last_batch():
    X = np.random.rand(10, 3).astype(np.float32)
    Y = np.arange(10).astype(np.float32)
    loader = DataLoader(ArrayDataset(X, Y), batch_size=4, last_batch="pad",
                        device=mx.cpu())
    assert len(loader) == 3
    for _ in range(2):
        shapes = [tuple(x.shape) for x, _ in loader]
        assert shapes == [(4, 3)] * 3  # shape-stable epoch
    # the pad rows wrap from the epoch start
    last_y = list(loader)[-1][1].asnumpy()
    np.testing.assert_array_equal(last_y, [8, 9, 0, 1])


def test_dataloader_pad_shorter_than_one_batch():
    loader = DataLoader(ArrayDataset(np.arange(3, dtype=np.float32),
                                     np.arange(3, dtype=np.float32)),
                        batch_size=8, last_batch="pad")
    (x, _), = list(loader)
    assert x.shape == (8,)
    np.testing.assert_array_equal(x.asnumpy(), [0, 1, 2, 0, 1, 2, 0, 1])


def test_dataloader_pin_memory_warns_exactly_once(caplog):
    import mxnet_tpu.gluon.data.dataloader as dl

    prev = dl._PIN_MEMORY_WARNED
    dl._PIN_MEMORY_WARNED = False
    try:
        ds = ArrayDataset(np.zeros((4, 2), np.float32),
                          np.zeros((4,), np.float32))
        with caplog.at_level(logging.WARNING,
                             logger="mxnet_tpu.gluon.data.dataloader"):
            DataLoader(ds, batch_size=2, pin_memory=True)
            DataLoader(ds, batch_size=2, pin_memory=True)
        warns = [r for r in caplog.records if "pin_memory" in r.message]
        assert len(warns) == 1
    finally:
        dl._PIN_MEMORY_WARNED = prev


def test_dataloader_del_robust_when_init_raised():
    with pytest.raises(ValueError):
        DataLoader(ArrayDataset(np.zeros((4, 2), np.float32),
                                np.zeros((4,), np.float32)))  # no batch_size
    obj = DataLoader.__new__(DataLoader)  # __init__ never ran at all
    obj.__del__()  # must not raise
    gc.collect()


# ---------------------------------------------------------------------------
# shape stabilization
# ---------------------------------------------------------------------------

def test_pad_batch_mask_parity_with_discard():
    """A padded final batch + validity mask produces the same loss and
    gradients as discarding the tail (mask correctness)."""
    mx.random.seed(0)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    Xv = mx.nd.array(np.random.RandomState(0).randn(5, 6)
                     .astype(np.float32))
    Yv = mx.nd.array(np.random.RandomState(1).randint(0, 3, (5,))
                     .astype(np.float32))

    def run(padded):
        mx.random.seed(0)
        np.random.seed(0)
        net = nn.Dense(3, in_units=6)
        net.initialize(init=mx.initializer.Xavier())
        if padded:
            (x, y), mask = pad_batch([Xv, Yv], 8)
            with autograd.record():
                l = loss_fn(net(x), y)
                total = (l * mask).sum() / mask.sum()
        else:
            with autograd.record():
                total = loss_fn(net(Xv), Yv).sum() / 5.0
        total.backward()
        return (float(total.asnumpy()),
                net.weight.grad(None).asnumpy().copy(),
                net.bias.grad(None).asnumpy().copy())

    lp, wp, bp = run(True)
    ld, wd, bd = run(False)
    assert lp == pytest.approx(ld, rel=1e-6)
    np.testing.assert_allclose(wp, wd, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(bp, bd, rtol=1e-5, atol=1e-7)


def test_pad_batch_structure_and_errors():
    from mxnet_tpu.base import MXNetError

    d = mx.nd.ones((3, 2))
    (out, mask) = pad_batch(d, 4)
    assert out.shape == (4, 2) and list(mask.asnumpy()) == [1, 1, 1, 0]
    nested, mask = pad_batch([d, [d, d]], 4)
    assert nested[1][0].shape == (4, 2)
    with pytest.raises(MXNetError):
        pad_batch(mx.nd.ones((5, 2)), 4)  # batch larger than target


def test_sequence_bucketer():
    from mxnet_tpu.base import MXNetError

    b = SequenceBucketer([8, 16])
    x, L = b(mx.nd.ones((2, 5)))
    assert x.shape == (2, 8) and L == 5
    assert x.asnumpy()[:, 5:].sum() == 0  # padded with pad_value
    x, L = b(mx.nd.ones((2, 16)))
    assert x.shape == (2, 16) and L == 16
    host, L = b(np.ones((2, 9), np.float32))
    assert host.shape == (2, 16)
    with pytest.raises(MXNetError):
        b(mx.nd.ones((2, 17)))  # longer than the largest bucket
    with pytest.raises(MXNetError):
        SequenceBucketer([])


def test_shape_wobble_budget_flags_loudly(monkeypatch, caplog):
    monkeypatch.setenv("MXTPU_RETRACE_BUDGET", "2")
    prev = obs.set_enabled(True)
    try:
        obs.reset()
        net = nn.Dense(4, in_units=8)
        net.initialize()
        net.hybridize()
        name = net.name
        with caplog.at_level(logging.WARNING, logger="mxnet_tpu.gluon.block"):
            for bsz in (1, 2, 3, 4):
                net(mx.nd.ones((bsz, 8)))
        assert obs.SHAPE_WOBBLE_TOTAL.value(block=name) == 2  # 3rd + 4th
        warns = [r for r in caplog.records if "shape_wobble" in r.message]
        assert len(warns) == 1  # loud but once per block
    finally:
        obs.set_enabled(prev)
        obs.reset()


def test_shape_wobble_budget_disabled(monkeypatch):
    monkeypatch.setenv("MXTPU_RETRACE_BUDGET", "0")
    prev = obs.set_enabled(True)
    try:
        obs.reset()
        net = nn.Dense(4, in_units=8)
        net.initialize()
        net.hybridize()
        for bsz in (1, 2, 3, 4):
            net(mx.nd.ones((bsz, 8)))
        assert obs.SHAPE_WOBBLE_TOTAL.total() == 0
    finally:
        obs.set_enabled(prev)
        obs.reset()


# ---------------------------------------------------------------------------
# warmup
# ---------------------------------------------------------------------------

def _mlp():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=6),
            nn.Dense(3, in_units=8))
    net.initialize(init=mx.initializer.Xavier())
    net.hybridize()
    return net


def _params_in_order(net):
    """Parameters in structural (layer) order — name sorting is unstable
    once the global dense counter reaches double digits."""
    out = []
    for child in net._children.values():
        out.extend(p for _, p in sorted(child._reg_params.items()))
    return out


def test_warmup_precompiles_inference_shapes():
    prev = obs.set_enabled(True)
    try:
        obs.reset()
        net = _mlp()
        assert net.warmup([(4, 6), (8, 6)]) == 2
        compiled = obs.CACHEDOP_COMPILE_TOTAL.total()
        assert compiled >= 2
        with autograd.predict_mode():
            net(mx.nd.ones((4, 6)))
            net(mx.nd.ones((8, 6)))
        assert obs.CACHEDOP_COMPILE_TOTAL.total() == compiled, \
            "warmed shapes must not compile again"
    finally:
        obs.set_enabled(prev)
        obs.reset()


def test_warmup_full_step_restores_training_state():
    net = _mlp()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore=None)
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    assert net.warmup([(4, 6), (8, 6)], loss_fn=loss_fn, trainer=tr) == 2
    for k, p in net.collect_params().items():
        np.testing.assert_array_equal(p.data().asnumpy(), before[k])
    assert not tr._optimizer._index_update_count  # update counts restored
    assert not tr._fused_states                   # momentum restored
    # training after warmup matches training without warmup
    X = mx.nd.array(np.random.RandomState(1).randn(4, 6).astype(np.float32))
    Y = mx.nd.array(np.random.RandomState(2).randint(0, 3, (4,))
                    .astype(np.float32))
    for _ in range(3):
        with autograd.record():
            l = loss_fn(net(X), Y)
        l.backward()
        tr.step(4)
    assert tr._fused not in (False, None)

    # a fresh net given the SAME initial weights, trained WITHOUT warmup
    net2 = _mlp()
    tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9},
                        kvstore=None)
    for p1, p2 in zip(_params_in_order(net), _params_in_order(net2)):
        p2.set_data(mx.nd.array(before[p1.name]))
    for _ in range(3):
        with autograd.record():
            l = loss_fn(net2(X), Y)
        l.backward()
        tr2.step(4)
    for p1, p2 in zip(_params_in_order(net), _params_in_order(net2)):
        np.testing.assert_allclose(p1.data().asnumpy(),
                                   p2.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6)


def test_warmup_accepts_single_shape_forms():
    net = _mlp()
    assert net.warmup((4, 6)) == 1   # bare tuple
    assert net.warmup([4, 6]) == 1   # bare list
    assert net.warmup([[4, 6], (8, 6)]) == 2


def test_warmup_resolves_deferred_init():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(2))  # deferred shapes
    net.initialize()
    net.hybridize()
    assert net.warmup([(4, 6)]) == 1
    assert net(mx.nd.ones((4, 6))).shape == (4, 2)


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

_CACHE_SNIPPET = """
import jax
jax.config.update("jax_platforms", "cpu")
import sys
sys.path.insert(0, {root!r})
import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.gluon import nn
net = nn.Dense(4, in_units=8)
net.initialize()
net.hybridize()
net(mx.nd.ones((2, 8)))
import json
print(json.dumps({{"hits": int(obs.COMPILE_CACHE_HITS.total()),
                   "misses": int(obs.COMPILE_CACHE_MISSES.total()),
                   "dir": __import__("mxnet_tpu.runtime", fromlist=["x"])
                          .compile_cache_dir(),
                   "jax_dir": jax.config.jax_compilation_cache_dir}}))
"""


def test_compile_cache_cold_then_warm(tmp_path):
    """MXTPU_COMPILE_CACHE: run 1 populates the cache (misses), run 2
    compiles NOTHING (zero misses, all hits) — restart cost is tracing
    only."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["MXTPU_COMPILE_CACHE"] = str(tmp_path / "cc")

    def run():
        res = subprocess.run(
            [sys.executable, "-c", _CACHE_SNIPPET.format(root=ROOT)],
            env=env, capture_output=True, text=True, timeout=240)
        assert res.returncode == 0, res.stderr[-2000:]
        return json.loads(res.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["misses"] > 0
    assert cold["dir"] == str(tmp_path / "cc")
    assert os.listdir(str(tmp_path / "cc"))
    warm = run()
    assert warm["misses"] == 0, warm
    assert warm["hits"] > 0


def test_compile_cache_placed_from_outside_wins(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and the
    library sets no directory in code, whatever MXTPU_COMPILE_CACHE
    says — the cache lands where it was placed, nowhere else."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    env["MXTPU_COMPILE_CACHE"] = str(tmp_path / "ours")
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_SNIPPET.format(root=ROOT)],
        env=env, capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["dir"] == out["jax_dir"] == str(tmp_path / "placed")
    assert out["misses"] > 0
    assert os.listdir(str(tmp_path / "placed"))
    assert not os.path.exists(str(tmp_path / "ours"))


# ---------------------------------------------------------------------------
# PrefetchingIter lifecycle (io/io.py)
# ---------------------------------------------------------------------------

class _BoomIter(mx.io.DataIter):
    def __init__(self, good_batches=1):
        super().__init__(2)
        self._n = 0
        self._good = good_batches
        self.provide_data = [mx.io.DataDesc("data", (2, 2))]
        self.provide_label = [mx.io.DataDesc("softmax_label", (2,))]

    def reset(self):
        self._n = 0

    def next(self):
        self._n += 1
        if self._n > self._good:
            raise ValueError("decode failed")
        return mx.io.DataBatch(data=[mx.nd.ones((2, 2))],
                               label=[mx.nd.ones((2,))], pad=0)


def test_prefetching_iter_propagates_worker_exception():
    it = mx.io.PrefetchingIter(_BoomIter(good_batches=1))
    it.next()
    with pytest.raises(ValueError, match="decode failed"):
        it.next()
    # threads are shut down and JOINED, not leaked
    for t in it.prefetch_threads:
        t.join(timeout=5.0)
        assert not t.is_alive()


def test_prefetching_iter_close_idempotent():
    inner = mx.io.NDArrayIter(np.zeros((6, 2), np.float32),
                              np.zeros((6,), np.float32), batch_size=2)
    it = mx.io.PrefetchingIter(inner)
    assert it.next() is not None
    it.close()
    it.close()
    for t in it.prefetch_threads:
        assert not t.is_alive()


def test_prefetching_iter_normal_epoch_still_works():
    inner = mx.io.NDArrayIter(np.arange(12, dtype=np.float32).reshape(6, 2),
                              np.arange(6, dtype=np.float32), batch_size=2)
    it = mx.io.PrefetchingIter(inner)
    n = sum(1 for _ in it)
    assert n == 3
    it.reset()
    assert sum(1 for _ in it) == 3
    it.close()
