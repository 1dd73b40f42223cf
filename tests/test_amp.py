"""End-to-end mixed precision (PR5 tentpole): the dispatch-time fp32
cast policy, convert_model's norm pinning, fp32 master weights (fused
and eager), in-graph fp16 loss scaling (overflow -> skip -> backoff),
and the reduced-precision bucketed allreduce."""

import importlib.util
import os

import numpy as np
import pytest

from conftest import natsorted_items

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, fusedstep, gluon, observability as obs
from mxnet_tpu.gluon import nn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _amp_off_after():
    yield
    amp.disable()


def _build_mlp(width=16, in_units=8, classes=3, n_hidden=2, seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    for _ in range(n_hidden):
        net.add(nn.Dense(width, activation="relu", in_units=in_units))
        in_units = width
    net.add(nn.Dense(classes, in_units=in_units))
    net.initialize(init=mx.initializer.Xavier())
    return net


# ---------------------------------------------------------------------------
# cast policy at op dispatch / trace time
# ---------------------------------------------------------------------------

def test_cast_policy_swaps_executables_and_keeps_dtype():
    from mxnet_tpu.ops import registry

    op = registry.get("softmax")
    off = registry.jitted(op, {"axis": -1})
    amp.init("bfloat16")
    on = registry.jitted(op, {"axis": -1})
    assert on is not off, "FP32-list op must use the cast-policy executable"
    x = mx.nd.array(np.random.rand(2, 5).astype(np.float32)).astype(
        "bfloat16")
    out = mx.nd.softmax(x)
    assert str(out.dtype) == "bfloat16"  # downcast back: activations stay low
    amp.disable()
    assert registry.jitted(op, {"axis": -1}) is off, \
        "disabling AMP must restore the original executable"


def test_cast_policy_upcasts_reduction_math():
    """mean over many bf16 values accumulates in fp32 under the policy:
    the result matches the fp64 reference to fp32-level error even
    though in- and outputs are bf16."""
    rng = np.random.RandomState(0)
    vals = rng.rand(4096).astype(np.float32)
    amp.init("bfloat16")
    x = mx.nd.array(vals).astype("bfloat16")
    got = float(mx.nd.mean(x).asnumpy().astype(np.float64))
    ref = float(np.asarray(vals, np.float64).mean())
    # the inputs are bf16-rounded (~0.4% per-element), but the fp32
    # accumulation keeps the MEAN error at rounding level, not O(n) drift
    assert got == pytest.approx(ref, rel=5e-3)
    assert str(mx.nd.mean(x).dtype) == "bfloat16"


def test_direct_state_reset_disables_policy():
    """Legacy tests flip ``amp._STATE['target_dtype']`` directly; the
    policy checks must read the shared dict, not a separate flag."""
    from mxnet_tpu.amp import policy

    amp.init("bfloat16")
    assert policy.cast_active()
    mx.amp._STATE["target_dtype"] = None
    assert not policy.cast_active()
    assert not amp.is_enabled()


def test_amp_toggle_retraces_cached_graph():
    """The CachedGraph key carries the AMP dtype: toggling amp.init()
    must not replay a pre-policy executable (and names the cause)."""
    prev = obs.set_enabled(True)
    try:
        obs.reset()
        net = nn.Dense(4, in_units=6)
        net.initialize(init=mx.initializer.Xavier())
        net.hybridize()
        x = mx.nd.ones((2, 6))
        net(x)
        net(x)
        compiled0 = obs.CACHEDOP_COMPILE_TOTAL.value(block=net.name)
        amp.init("bfloat16")
        net(x)
        assert obs.CACHEDOP_COMPILE_TOTAL.value(block=net.name) \
            == compiled0 + 1
        causes = [dict(k).get("cause", "")
                  for k in obs.CACHEDOP_RETRACE_TOTAL._values]
        assert any("amp" in c for c in causes), causes
    finally:
        obs.set_enabled(prev)
        obs.reset()


# ---------------------------------------------------------------------------
# convert_model: norm layers pinned fp32
# ---------------------------------------------------------------------------

def test_convert_model_pins_norm_stats_fp32_model_zoo():
    from mxnet_tpu.gluon.model_zoo import vision

    amp.init("bfloat16")
    net = vision.resnet18_v1(classes=4)
    net.initialize(init=mx.initializer.Xavier())
    amp.convert_model(net)
    # resolve deferred-init shapes (conv in_channels) with one forward
    with autograd.predict_mode():
        net(mx.nd.zeros((1, 3, 32, 32)).astype("bfloat16"))
    saw_bn = saw_conv = False
    for name, p in net.collect_params().items():
        if "batchnorm" in name or "running_" in name or "gamma" in name \
                or "beta" in name:
            assert str(p.data().dtype) == "float32", \
                f"norm param {name} must stay fp32"
            saw_bn = True
        elif "conv" in name or "dense" in name:
            assert str(p.data().dtype) == "bfloat16", \
                f"compute param {name} must be bf16"
            saw_conv = True
    assert saw_bn and saw_conv
    x = mx.nd.random.uniform(shape=(2, 3, 32, 32)).astype("bfloat16")
    with autograd.predict_mode():
        out = net(x)
    assert str(out.dtype) == "bfloat16"
    assert np.isfinite(out.asnumpy().astype(np.float32)).all()


def test_convert_model_layernorm_pinned():
    amp.init("bfloat16")
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=8), nn.LayerNorm(in_channels=8))
    net.initialize()
    amp.convert_model(net)
    dense = net._children["0"]
    ln = net._children["1"]
    assert str(dense.weight.data().dtype) == "bfloat16"
    assert str(ln.gamma.data().dtype) == "float32"
    out = net(mx.nd.ones((2, 8)).astype("bfloat16"))
    assert str(out.dtype) == "bfloat16"  # policy downcasts LayerNorm's fp32


# ---------------------------------------------------------------------------
# bf16 training parity + master weights
# ---------------------------------------------------------------------------

def _train_losses(dtype, steps=6, multi_precision=True):
    if dtype != "float32":
        amp.init(dtype)
    try:
        np.random.seed(0)
        net = _build_mlp()
        if dtype != "float32":
            amp.convert_model(net)
        net.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9,
                            "multi_precision": multi_precision
                            and dtype != "float32"},
                           kvstore=None)
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        X = mx.nd.array(np.random.RandomState(1).rand(16, 8)
                        .astype(np.float32))
        Y = mx.nd.array(np.random.RandomState(2).randint(0, 3, (16,))
                        .astype(np.float32))
        if dtype != "float32":
            X = X.astype(dtype)
        losses = []
        for _ in range(steps):
            with autograd.record():
                l = loss_fn(net(X), Y)
            l.backward()
            tr.step(16)
            losses.append(float(l.mean().asnumpy().astype(np.float64)))
        assert tr._fused not in (False, None), "fused path must engage"
        return losses
    finally:
        amp.disable()


def test_bf16_fp32_loss_trajectory_parity():
    """The acceptance contract: bf16 training (cast policy + fp32
    masters) tracks the fp32 loss trajectory within bf16 tolerance on
    a small MLP."""
    l32 = _train_losses("float32")
    l16 = _train_losses("bfloat16")
    for a, b in zip(l32, l16):
        assert b == pytest.approx(a, rel=0.08, abs=0.05), (l32, l16)
    # and it actually trains (loss decreases)
    assert l16[-1] < l16[0]


def test_fused_bf16_master_weights_in_state():
    amp.init("bfloat16")
    net = _build_mlp(n_hidden=1)
    amp.convert_model(net)
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9,
                        "multi_precision": True}, kvstore=None)
    X = mx.nd.ones((4, 8)).astype("bfloat16")
    for _ in range(2):
        with autograd.record():
            l = (net(X) ** 2).sum()
        l.backward()
        tr.step(4)
    assert tr._fused not in (False, None)
    name, st = natsorted_items(tr._fused_states.items())[0]
    # (fp32 master, fp32 momentum) for a bf16 param
    assert len(st) == 2 and all(str(s.dtype) == "float32" for s in st)
    p = dict(net.collect_params().items())[name]
    assert str(p.data().dtype) == "bfloat16"
    # stored weight is the rounded view of the master
    np.testing.assert_allclose(
        p.data().asnumpy().astype(np.float32),
        np.asarray(st[0].astype(np.float32)), rtol=1e-2, atol=1e-2)


def test_eager_bf16_master_weights(monkeypatch):
    """Satellite: create_state_multi_precision/update_multi_precision
    treat bfloat16 like float16 — the eager path gets masters too."""
    from mxnet_tpu.optimizer import SGD

    opt = SGD(learning_rate=0.1, momentum=0.9, multi_precision=True)
    w = mx.nd.array(np.ones((4,), np.float32)).astype("bfloat16")
    state = opt.create_state_multi_precision(0, w)
    master, mom = state
    assert str(master.dtype) == "float32"
    assert str(mom.dtype) == "float32"
    g = mx.nd.array(np.full((4,), 0.5, np.float32)).astype("bfloat16")
    opt.update_multi_precision(0, w, g, state)
    assert str(w.dtype) == "bfloat16"
    np.testing.assert_allclose(np.asarray(master.data), np.full((4,), 0.95),
                               rtol=1e-6)


def test_mp_bf16_fused_to_eager_migration_keeps_master():
    """Flipping the fused path off mid-run must hand the fp32 master
    (and momentum) to the eager per-param path — trajectory matches an
    all-eager multi_precision run."""
    def run(flip_at):
        amp.init("bfloat16")
        try:
            np.random.seed(0)
            net = _build_mlp(n_hidden=1, seed=0)
            amp.convert_model(net)
            net.hybridize()
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9,
                                "multi_precision": True}, kvstore=None)
            X = mx.nd.array(np.random.RandomState(1).rand(8, 8)
                            .astype(np.float32)).astype("bfloat16")
            for i in range(6):
                if i == flip_at:
                    fusedstep.set_enabled(False)
                with autograd.record():
                    l = (net(X) ** 2).sum()
                l.backward()
                tr.step(8)
            fusedstep.set_enabled(True)
            p = natsorted_items(net.collect_params().items())[0][1]
            return p.data().asnumpy().astype(np.float32)
        finally:
            fusedstep.set_enabled(True)
            amp.disable()

    mixed = run(flip_at=3)
    eager = run(flip_at=0)
    np.testing.assert_allclose(mixed, eager, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# fp16 in-graph loss scaling
# ---------------------------------------------------------------------------

def _fp16_net_and_trainer(window=1000):
    amp.init("float16")
    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize(init=mx.initializer.Xavier())
    amp.convert_model(net)
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.01, "multi_precision": True},
                       kvstore=None)
    amp.init_trainer(tr)
    tr._amp_loss_scaler = amp.LossScaler(init_scale=1024.0,
                                         scale_factor=2.0,
                                         scale_window=window)
    return net, tr


def test_fp16_overflow_skip_backoff_fused():
    import jax.numpy as jnp

    net, tr = _fp16_net_and_trainer()
    X = mx.nd.ones((4, 8)).astype("float16")
    w_snap = None
    for i in range(4):
        with autograd.record():
            l = (net(X) ** 2).sum()
            with amp.scale_loss(l, tr) as sl:
                sl.backward()
        if i == 1:  # inject an overflow after backward
            w_snap = net.weight.data().asnumpy().copy()
            g = net.weight.grad(None)
            g._set_data(jnp.full(g.shape, jnp.inf, g.data.dtype))
        tr.step(4)
        if i == 1:
            # skip-update: the poisoned step left the weights untouched
            np.testing.assert_array_equal(net.weight.data().asnumpy(),
                                          w_snap)
    assert tr._fused not in (False, None), "fp16 amp must ride the fused path"
    scaler = tr._amp_loss_scaler
    assert scaler.loss_scale == 512.0  # one backoff
    assert scaler.overflow_total == 1
    w = net.weight.data().asnumpy().astype(np.float32)
    assert np.isfinite(w).all(), "no NaN may reach the (master) weights"
    # master state stayed finite too
    for st in tr._fused_states.values():
        for leaf in st:
            assert np.isfinite(np.asarray(leaf, np.float32)).all()


def test_fp16_tiny_combined_rescale_does_not_underflow():
    """Code-review regression: (1/batch)/loss_scale at batch 4096 x
    scale 2^15 is 7.5e-9 — below fp16's 6e-8 subnormal floor. The fused
    update must apply it AFTER upcasting the grad to fp32, or every
    update silently rounds to zero while training 'runs' happily.
    (2^15, not 2^16: a 2^16 cotangent itself exceeds fp16 max 65504 and
    would trigger the overflow-skip path instead of exercising the
    rescale.)"""
    amp.init("float16")
    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize(init=mx.initializer.Xavier())
    amp.convert_model(net)
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 1.0, "multi_precision": True},
                       kvstore=None)
    amp.init_trainer(tr)
    tr._amp_loss_scaler = amp.LossScaler(init_scale=2 ** 15,
                                         scale_window=10 ** 6)
    X = (mx.nd.ones((4, 8)) * 0.01).astype("float16")
    w0 = net.weight.data().asnumpy().astype(np.float64)
    for _ in range(3):
        with autograd.record():
            l = (net(X) ** 2).sum()
            with amp.scale_loss(l, tr) as sl:
                sl.backward()
        tr.step(4096)
    assert tr._fused not in (False, None)
    assert tr._amp_loss_scaler.overflow_total == 0, \
        "probe invalidated: grads overflowed, rescale never exercised"
    master = np.asarray(
        tr._fused_states[net.weight.name][0]).astype(np.float64)
    delta = np.abs(master - w0).max()
    assert delta > 0.0, \
        "combined rescale underflowed fp16: updates silently zeroed"


def test_amp_reinit_with_fp32_ops_retraces():
    """Code-review regression: re-initializing AMP with an extended
    fp32_ops list must retrace cached executables (the cast_ops set is
    part of the CachedGraph key, not just the target dtype)."""
    prev = obs.set_enabled(True)
    try:
        obs.reset()
        amp.init("bfloat16")
        net = nn.Dense(4, in_units=6)
        net.initialize(init=mx.initializer.Xavier())
        net.hybridize()
        x = mx.nd.ones((2, 6)).astype("bfloat16")
        net(x)
        net(x)
        compiled0 = obs.CACHEDOP_COMPILE_TOTAL.value(block=net.name)
        amp.init("bfloat16", fp32_ops=["FullyConnected"])
        net(x)
        assert obs.CACHEDOP_COMPILE_TOTAL.value(block=net.name) \
            == compiled0 + 1, "extended fp32_ops silently ignored"
    finally:
        obs.set_enabled(prev)
        obs.reset()


def test_fp16_eager_fallback_unscales_buffers():
    """The per-param fallback divides the gradient BUFFERS by the scale
    (not a hidden rescale fold): user-visible grads are TRUE grads
    after step, like the pre-deferral scale_loss semantics."""
    prev = fusedstep.set_enabled(False)
    try:
        net, tr = _fp16_net_and_trainer()
        X = mx.nd.ones((4, 8)).astype("float16")
        with autograd.record():
            l = (net(X) ** 2).sum()
            with amp.scale_loss(l, tr) as sl:
                sl.backward()
        scaled = net.weight.grad(None).asnumpy().astype(np.float32).copy()
        tr.step(4)
        unscaled = net.weight.grad(None).asnumpy().astype(np.float32)
        np.testing.assert_allclose(unscaled * 1024.0, scaled, rtol=2e-3,
                                   atol=1e-4)
    finally:
        fusedstep.set_enabled(prev)


def test_fp16_scale_growth_after_window():
    net, tr = _fp16_net_and_trainer(window=2)
    X = mx.nd.ones((4, 8)).astype("float16")
    for _ in range(4):  # 4 clean scaled steps, window 2 -> two growths
        with autograd.record():
            l = (net(X) ** 2).sum()
            with amp.scale_loss(l, tr) as sl:
                sl.backward()
        tr.step(4)
    assert tr._amp_loss_scaler.loss_scale == 4096.0


def test_fp16_eager_fallback_skips_and_backs_off():
    """MXTPU_FUSED_STEP off: the deferred scale_loss resolves on the
    per-param path — one fused isfinite reduction, hard skip, host-side
    scale update."""
    import jax.numpy as jnp

    prev = fusedstep.set_enabled(False)
    try:
        net, tr = _fp16_net_and_trainer()
        X = mx.nd.ones((4, 8)).astype("float16")
        for i in range(3):
            with autograd.record():
                l = (net(X) ** 2).sum()
                with amp.scale_loss(l, tr) as sl:
                    sl.backward()
            if i == 1:
                snap = net.weight.data().asnumpy().copy()
                g = net.weight.grad(None)
                g._set_data(jnp.full(g.shape, jnp.inf, g.data.dtype))
            tr.step(4)
            if i == 1:
                np.testing.assert_array_equal(net.weight.data().asnumpy(),
                                              snap)
        assert tr._amp_loss_scaler.loss_scale == 512.0
        assert np.isfinite(net.weight.data().asnumpy()
                           .astype(np.float32)).all()
    finally:
        fusedstep.set_enabled(prev)


def test_unscale_divides_pending_grads():
    net, tr = _fp16_net_and_trainer()
    X = mx.nd.ones((4, 8)).astype("float16")
    with autograd.record():
        l = (net(X) ** 2).sum()
        with amp.scale_loss(l, tr) as sl:
            sl.backward()
    scaled = net.weight.grad(None).asnumpy().astype(np.float32).copy()
    amp.unscale(tr)
    unscaled = net.weight.grad(None).asnumpy().astype(np.float32)
    np.testing.assert_allclose(unscaled * 1024.0, scaled, rtol=1e-3)
    # pending moves to "unscaled" (NOT off): step keeps the overflow
    # check + scale update armed, it just won't divide again
    assert tr._amp_pending == "unscaled"


def test_unscale_then_step_no_double_division():
    """Code-review regression: amp.unscale moves pending to 'unscaled'
    — the following step must NOT divide by the scale again. The
    unscale+step run lands on the same weights as the plain
    scale_loss+step run (fused path)."""
    def run(with_unscale):
        np.random.seed(0)
        net, tr = _fp16_net_and_trainer()
        X = mx.nd.ones((4, 8)).astype("float16")
        for _ in range(3):
            with autograd.record():
                l = (net(X) ** 2).sum()
                with amp.scale_loss(l, tr) as sl:
                    sl.backward()
            if with_unscale:
                amp.unscale(tr)
            tr.step(4)
        assert tr._fused not in (False, None)
        return net.weight.data().asnumpy().astype(np.float32)

    np.testing.assert_allclose(run(True), run(False), rtol=2e-3,
                               atol=1e-4)


@pytest.mark.parametrize("fused", [True, False])
def test_unscale_keeps_overflow_protection_armed(fused):
    """Code-review regression (CONFIRMED repro): the documented
    unscale-then-clip recipe must not disarm the deferred overflow
    check — an inf gradient after amp.unscale still skips the update
    and backs the scale off, on both paths."""
    import jax.numpy as jnp

    prev = fusedstep.set_enabled(fused)
    try:
        net, tr = _fp16_net_and_trainer()
        X = mx.nd.ones((4, 8)).astype("float16")
        with autograd.record():
            l = (net(X) ** 2).sum()
            with amp.scale_loss(l, tr) as sl:
                sl.backward()
        g = net.weight.grad(None)
        g._set_data(jnp.full(g.shape, jnp.inf, g.data.dtype))
        amp.unscale(tr)  # inf/scale is still inf: check must stay armed
        w0 = net.weight.data().asnumpy().copy()
        tr.step(4)
        np.testing.assert_array_equal(net.weight.data().asnumpy(), w0)
        assert np.isfinite(net.weight.data().asnumpy()
                           .astype(np.float32)).all()
        assert tr._amp_loss_scaler.loss_scale == 512.0, \
            "scale must back off even after a user unscale"
        assert tr._amp_loss_scaler.overflow_total == 1
    finally:
        fusedstep.set_enabled(prev)


def test_has_overflow_single_fused_reduction():
    """Satellite: no per-param numpy loop — one fused reduction handles
    Parameters, NDArrays-with-grads, and plain arrays alike."""
    ls = amp.LossScaler()
    assert not ls.has_overflow([])
    assert not ls.has_overflow([mx.nd.ones((3,)), mx.nd.ones((2, 2))])
    assert ls.has_overflow([mx.nd.ones((3,)),
                            mx.nd.array([np.nan, 1.0])])
    p = gluon.Parameter("w", shape=(4,))
    p.initialize(ctx=mx.cpu())
    p.data().attach_grad()
    with autograd.record():
        (p.data() * 2).sum().backward()
    assert not ls.has_overflow([p])


# ---------------------------------------------------------------------------
# reduced-precision bucketed allreduce
# ---------------------------------------------------------------------------

def _fake_dist_store():
    """A KVStoreLocal subclass whose bucket reduction is live (simulates
    the dist store's per-bucket allreduce on one process): doubles each
    bucket and records the dtype it saw on the 'wire'."""
    from mxnet_tpu.kvstore.local import KVStoreLocal

    seen = []

    class FakeDist(KVStoreLocal):
        def _reduce_raw(self, raw):
            seen.append(str(raw.dtype))
            return raw + raw

        def _reduce(self, key, merged):  # per-key path parity
            from mxnet_tpu.ndarray.ndarray import NDArray

            return NDArray(merged.data * 2, ctx=merged.ctx)

    return FakeDist(), seen


def test_amp_allreduce_dtype_casts_buckets(monkeypatch):
    monkeypatch.setenv("MXTPU_AMP_ALLREDUCE_DTYPE", "bfloat16")
    kv, seen = _fake_dist_store()
    rng = np.random.RandomState(0)
    keys, vals, outs, ref = [], [], [], []
    for i, sh in enumerate([(64,), (7, 3), (129,)]):
        a = rng.rand(*sh).astype(np.float32)
        kv.init(i, mx.nd.zeros(sh))
        keys.append(i)
        vals.append([mx.nd.array(a)])
        outs.append(mx.nd.zeros(sh))
        ref.append(2 * a)
    kv.pushpull(keys, vals, out=outs)
    assert seen and all(d == "bfloat16" for d in seen), seen
    for o, e in zip(outs, ref):
        assert str(o.dtype) == "float32"
        np.testing.assert_allclose(o.asnumpy(), e, rtol=1e-2, atol=1e-2)


def test_amp_allreduce_dtype_off_by_default():
    kv, seen = _fake_dist_store()
    kv.init(0, mx.nd.zeros((16,)))
    outs = [mx.nd.zeros((16,))]
    kv.pushpull([0], [[mx.nd.ones((16,))]], out=outs)
    assert seen == ["float32"], seen
    np.testing.assert_allclose(outs[0].asnumpy(), np.full((16,), 2.0))


def test_amp_allreduce_dtype_leaves_fp16_buckets_alone(monkeypatch):
    monkeypatch.setenv("MXTPU_AMP_ALLREDUCE_DTYPE", "bfloat16")
    kv, seen = _fake_dist_store()
    kv.init(0, mx.nd.zeros((8,), dtype="float16"))
    outs = [mx.nd.zeros((8,), dtype="float16")]
    kv.pushpull([0], [[mx.nd.ones((8,), dtype="float16")]], out=outs)
    assert seen == ["float16"], seen  # already half: no extra cast


def test_amp_allreduce_dtype_invalid_ignored(monkeypatch):
    monkeypatch.setenv("MXTPU_AMP_ALLREDUCE_DTYPE", "float8")
    assert fusedstep.amp_allreduce_dtype() == ""


def test_dist_accum_sum_fp32_accumulation():
    import jax.numpy as jnp

    from mxnet_tpu.kvstore.dist import _accum_sum

    # 256 bf16 ones: a bf16 accumulator saturates (1 ulp at 256 is 2),
    # fp32 accumulation returns the exact count
    a = jnp.ones((256, 4), jnp.bfloat16) * 1.0078125  # needs low bits
    out = _accum_sum(a)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.full((4,), 258.0), rtol=1e-2)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_amp_gauges_lazy_under_fused_step():
    import jax.numpy as jnp

    prev = obs.set_enabled(True)
    try:
        obs.reset()
        net, tr = _fp16_net_and_trainer()
        X = mx.nd.ones((4, 8)).astype("float16")
        for i in range(2):
            with autograd.record():
                l = (net(X) ** 2).sum()
                with amp.scale_loss(l, tr) as sl:
                    sl.backward()
            if i == 0:
                g = net.weight.grad(None)
                g._set_data(jnp.full(g.shape, jnp.inf, g.data.dtype))
            tr.step(4)
        stored = obs.AMP_OVERFLOW_TOTAL._values.get(())
        assert stored is not None and not isinstance(stored, float), \
            "fused amp must store a lazy device scalar, not a synced float"
        assert obs.AMP_OVERFLOW_TOTAL.value() == 1.0
        assert obs.AMP_LOSS_SCALE.value() == 512.0
        dump = obs.dump_prometheus()
        assert "mxtpu_amp_overflow_total" in dump
        assert "mxtpu_amp_loss_scale" in dump
    finally:
        obs.set_enabled(prev)
        obs.reset()


def _load_report_tool():
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(ROOT, "tools",
                                         "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_telemetry_report_amp_section_crash_proof():
    tool = _load_report_tool()
    assert tool.render_amp([]) == ""
    assert tool.render_amp([{"name": "trainer.step", "dur": 1.0}]) == ""
    evs = [
        {"name": "amp.scale_update", "cat": "amp", "dur": 0.0,
         "args": {"scale": 512.0, "overflow_total": 1, "overflow": True}},
        {"name": "amp.scale_update", "cat": "amp", "dur": 0.0,
         "args": {"scale": 512.0, "overflow_total": 1, "overflow": False}},
        {"name": "amp.scale_update", "cat": "amp", "dur": 0.0,
         "args": None},  # malformed args must not crash
    ]
    out = tool.render_amp(evs)
    assert "AMP loss scaling" in out and "overflows (skipped steps): 1" in out
    # and the generic table aggregates the unknown series without crashing
    assert "amp.scale_update" in tool.render_table(evs)


def test_eager_update_scale_emits_trace_event():
    prev = obs.set_enabled(True)
    try:
        obs.reset()
        ls = amp.LossScaler(init_scale=64.0, scale_factor=2.0)
        ls.update_scale(True)
        evs = [e for e in obs.tracer().events()
               if e["name"] == "amp.scale_update"]
        assert evs and evs[-1]["args"]["overflow"] is True
        assert obs.AMP_LOSS_SCALE.value() == 32.0
    finally:
        obs.set_enabled(prev)
        obs.reset()


# ---------------------------------------------------------------------------
# load_parameters after convert_model (PR 8 satellite): the saved mixed
# dtype set (fp32-pinned norm layers + low-precision compute weights)
# must restore to exactly the same dtypes, and the fused plan must keep
# working across the reload
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [
    pytest.param("bfloat16", marks=pytest.mark.slow),  # fp16 cell is
    "float16",  # the superset: masters + scaler ride the load
])
def test_load_parameters_after_convert_model(tmp_path, dtype):
    amp.init(dtype)

    def build():
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, in_units=8))
        net.add(nn.BatchNorm(in_channels=8))
        net.add(nn.Dense(3, in_units=8))
        net.initialize(init=mx.initializer.Xavier())
        amp.convert_model(net)
        net.hybridize()
        return net

    net = build()
    X = mx.nd.ones((4, 8)).astype(dtype)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9,
                        "multi_precision": True}, kvstore=None)
    if dtype == "float16":
        amp.init_trainer(tr)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(2):  # real training: running stats move, plan builds
        with autograd.record():
            l = loss_fn(net(X), mx.nd.zeros((4,)))
            if dtype == "float16":
                with amp.scale_loss(l, tr) as sl:
                    sl.backward()
        if dtype != "float16":
            l.backward()
        tr.step(4)
    assert isinstance(tr._fused, dict)  # fast path active pre-save
    fname = str(tmp_path / "mixed.params")
    net.save_parameters(fname)

    # restore into a FRESH converted net: every dtype must come back
    # exactly (low-precision compute weights, fp32 norm params + stats)
    net2 = build()
    net2.load_parameters(fname)
    p1 = net._collect_params_with_prefix()
    p2 = net2._collect_params_with_prefix()
    saw_low = saw_f32 = False
    for name in p1:
        d1, d2 = p1[name].data(), p2[name].data()
        assert str(d2.dtype) == str(d1.dtype), \
            f"{name}: saved {d1.dtype} restored as {d2.dtype}"
        np.testing.assert_array_equal(
            np.asarray(d1.data.astype("float32")),
            np.asarray(d2.data.astype("float32")))
        if str(d1.dtype) == dtype:
            saw_low = True
        if str(d1.dtype) == "float32":
            saw_f32 = True
    assert saw_low and saw_f32  # the mix survived, not a blanket cast

    # and reloading into the LIVE net must not break the fused plan:
    # _load_init mutates the existing handles in place, so the cached
    # plan stays valid and the next step still takes the fast path
    plan_before = tr._fused
    net.load_parameters(fname)
    with autograd.record():
        l = loss_fn(net(X), mx.nd.zeros((4,)))
        if dtype == "float16":
            with amp.scale_loss(l, tr) as sl:
                sl.backward()
    if dtype != "float16":
        l.backward()
    tr.step(4)
    assert isinstance(tr._fused, dict)
    assert tr._fused is plan_before  # not invalidated by the reload
