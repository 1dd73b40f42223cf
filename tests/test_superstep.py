"""K-step on-device superstep (PR6 tentpole): whole-program capture of
fwd+bwd+update into one lax.scan dispatch — parity vs the single-step
fused path (params, optimizer state, loss trajectory) for sgd/adam x
AMP off/bf16/fp16 at K in {1, 2, 4}, the dispatch-count amortization
regression, per-iteration in-scan fp16 overflow skip, state migration
between paths, the staging ring contract, and the scan-compatible
bucketed psum."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import (amp, autograd, fusedstep, gluon,
                       observability as obs, parallel)
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data.prefetcher import (DevicePrefetcher,
                                             SuperstepRing, stack_batches)


@pytest.fixture(autouse=True)
def _fused_on():
    prev = fusedstep.set_enabled(True)
    yield
    fusedstep.set_enabled(prev)


loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()


def _batch(i, n=16, width=8, classes=3, dtype=None, poison=False):
    rs = np.random.RandomState(100 + i)
    x = rs.randn(n, width).astype(np.float32)
    if poison:
        x[0, 0] = np.inf
    y = rs.randint(0, classes, (n,)).astype(np.float32)
    if dtype:
        x = x.astype(dtype)
    return mx.nd.array(x, dtype=str(x.dtype)), mx.nd.array(y)


def _build(opt="sgd", amp_dtype=None, bn=False, deferred=False,
           scale_window=2000):
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu",
                     **({} if deferred else {"in_units": 8})))
    if bn:
        net.add(nn.BatchNorm())
    net.add(nn.Dense(3, **({} if deferred else {"in_units": 16})))
    net.initialize(init=mx.initializer.Xavier())
    if amp_dtype:
        amp.convert_model(net)
    net.hybridize()
    params = {"learning_rate": 0.05, "multi_precision": bool(amp_dtype)}
    if opt == "sgd":
        params["momentum"] = 0.9
    tr = gluon.Trainer(net.collect_params(), opt, params, kvstore=None)
    if amp_dtype == "float16":
        tr._amp_loss_scaler = amp.LossScaler(
            init_scale=1024.0, scale_factor=2.0, scale_window=scale_window)
    return net, tr


def _weights(net):
    return [p.data().asnumpy().astype(np.float32) for _, p in
            sorted(net.collect_params().items(),
                   key=lambda kv: kv[0].split("_", 1)[-1])]


def _opt_states(net, tr):
    # ordered by the block's layer-REGISTRATION order: param names carry
    # run-dependent global counters, so any name-based ordering flips at
    # digit boundaries (dense10 < dense9) between the two compared runs
    out = []
    for _, p in net.collect_params().items():
        st = tr._fused_states.get(p.name)
        if st is not None:
            out.append(tuple(np.asarray(leaf, dtype=np.float32)
                             for leaf in st))
    return out


def _run_single(steps, opt="sgd", amp_dtype=None, poison=None, bn=False,
                scale_window=2000):
    net, tr = _build(opt, amp_dtype, bn=bn, scale_window=scale_window)
    losses = []
    for i in range(steps):
        x, y = _batch(i, dtype=amp_dtype, poison=(i == poison))
        with autograd.record():
            l = loss_fn(net(x), y)
            if amp_dtype == "float16":
                with amp.scale_loss(l, tr) as sl:
                    sl.backward()
        if amp_dtype != "float16":
            l.backward()
        tr.step(16)
        losses.append(float(jnp.mean(l.data.astype(jnp.float32))))
    return net, tr, losses


def _run_super(steps, k, opt="sgd", amp_dtype=None, poison=None, bn=False,
               scale_window=2000):
    net, tr = _build(opt, amp_dtype, bn=bn, scale_window=scale_window)
    ss = gluon.Superstep(net, loss_fn, tr, k=k)
    losses = []
    for g in range(steps // k):
        xs = stack_batches([_batch(g * k + i, dtype=amp_dtype,
                                   poison=(g * k + i == poison))[0]
                            for i in range(k)])
        ys = stack_batches([_batch(g * k + i)[1] for i in range(k)])
        l = ss.step(xs, ys, 16)
        losses.extend(np.asarray(l.data, dtype=np.float32).tolist())
    assert isinstance(ss._plan, dict), \
        f"superstep declined for {opt}/{amp_dtype}: {ss._plan}"
    return net, tr, losses


# ---------------------------------------------------------------------------
# parity: K-step superstep == single-step fused path
# (params, optimizer state, loss trajectory)
# ---------------------------------------------------------------------------

# Full matrix is opt x dtype x k (18 cells). Tier-1 keeps every
# opt/dtype combo at the real superstep depth (k=4) plus the k-axis
# itself on one combo; the remaining cells only re-cross axes that are
# each already covered and run under -m slow.
@pytest.mark.parametrize("opt,amp_dtype,tol,k", [
    pytest.param("sgd", None, 1e-5, 1),
    pytest.param("sgd", None, 1e-5, 2, marks=pytest.mark.slow),
    pytest.param("sgd", None, 1e-5, 4),
    pytest.param("adam", None, 1e-5, 1, marks=pytest.mark.slow),
    pytest.param("adam", None, 1e-5, 2, marks=pytest.mark.slow),
    pytest.param("adam", None, 1e-5, 4, marks=pytest.mark.slow),
    pytest.param("sgd", "bfloat16", 2e-2, 1, marks=pytest.mark.slow),
    pytest.param("sgd", "bfloat16", 2e-2, 2, marks=pytest.mark.slow),
    pytest.param("sgd", "bfloat16", 2e-2, 4),
    pytest.param("adam", "bfloat16", 2e-2, 1, marks=pytest.mark.slow),
    pytest.param("adam", "bfloat16", 2e-2, 2, marks=pytest.mark.slow),
    pytest.param("adam", "bfloat16", 2e-2, 4, marks=pytest.mark.slow),
    pytest.param("sgd", "float16", 2e-3, 1, marks=pytest.mark.slow),
    pytest.param("sgd", "float16", 2e-3, 2, marks=pytest.mark.slow),
    pytest.param("sgd", "float16", 2e-3, 4, marks=pytest.mark.slow),
    pytest.param("adam", "float16", 2e-3, 1, marks=pytest.mark.slow),
    pytest.param("adam", "float16", 2e-3, 2, marks=pytest.mark.slow),
    pytest.param("adam", "float16", 2e-3, 4),
])
def test_superstep_parity(k, opt, amp_dtype, tol):
    if amp_dtype:
        amp.init(amp_dtype)
    try:
        steps = 2 * k if k > 1 else 4
        n1, t1, l1 = _run_single(steps, opt, amp_dtype)
        n2, t2, l2 = _run_super(steps, k, opt, amp_dtype)
    finally:
        if amp_dtype:
            amp.disable()
    np.testing.assert_allclose(l1, l2, rtol=tol, atol=tol)
    for a, b in zip(_weights(n1), _weights(n2)):
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol)
    # optimizer state parity: the single-step run's fused states vs the
    # superstep carry (both live in trainer._fused_states)
    s1, s2 = _opt_states(n1, t1), _opt_states(n2, t2)
    assert len(s1) == len(s2) and s1
    for st1, st2 in zip(s1, s2):
        assert len(st1) == len(st2)
        for a, b in zip(st1, st2):
            np.testing.assert_allclose(a, b, rtol=tol, atol=tol)
    if amp_dtype == "float16":
        assert t1._amp_loss_scaler.loss_scale == \
            t2._amp_loss_scaler.loss_scale


def test_superstep_parity_batchnorm_aux_carry():
    """BN running stats (non-diff aux params) ride the scan carry and
    match the single-step trajectory."""
    n1, _, _ = _run_single(8, bn=True)
    n2, _, _ = _run_super(8, 4, bn=True)
    for a, b in zip(_weights(n1), _weights(n2)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_superstep_deferred_init_probe():
    """Uninitialized (deferred) params resolve via the slot-0 predict
    probe without consuming an update."""
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))
    net.add(nn.Dense(3))
    net.initialize(init=mx.initializer.Xavier())
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9},
                       kvstore=None)
    ss = gluon.Superstep(net, loss_fn, tr, k=4)
    xs = stack_batches([_batch(i)[0] for i in range(4)])
    ys = stack_batches([_batch(i)[1] for i in range(4)])
    ss.step(xs, ys, 16)
    ss.step(stack_batches([_batch(4 + i)[0] for i in range(4)]),
            stack_batches([_batch(4 + i)[1] for i in range(4)]), 16)
    assert isinstance(ss._plan, dict)
    n1, _, _ = _run_single(8)
    for a, b in zip(_weights(n1), _weights(net)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# in-scan fp16 overflow skip: iteration i overflows, i+1 still applies
# ---------------------------------------------------------------------------

def test_superstep_fp16_overflow_skip_mid_scan():
    amp.init("float16")
    try:
        # poison iteration 1 of 8 (inside the first K=4 superstep)
        n1, t1, _ = _run_single(8, amp_dtype="float16", poison=1)
        n2, t2, _ = _run_super(8, 4, amp_dtype="float16", poison=1)
    finally:
        amp.disable()
    # exactly one overflow: scale backed off 1024 -> 512 once, and the
    # weights kept training (iterations 2..7 applied) with parity
    assert t2._amp_loss_scaler.loss_scale == 512.0
    assert t2._amp_loss_scaler.overflow_total == 1
    assert t1._amp_loss_scaler.loss_scale == 512.0
    for a, b in zip(_weights(n1), _weights(n2)):
        assert np.isfinite(a).all() and np.isfinite(b).all()
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_superstep_fp16_scale_growth_in_scan():
    """The growth branch also runs per-iteration in-graph: a small
    scale_window grows the scale inside one superstep."""
    amp.init("float16")
    try:
        _, tr, _ = _run_super(4, 4, amp_dtype="float16", scale_window=2)
    finally:
        amp.disable()
    # 4 clean iterations, window 2 -> two growth events: 1024 -> 4096
    assert tr._amp_loss_scaler.loss_scale == 4096.0


# ---------------------------------------------------------------------------
# dispatch-count amortization regression
# ---------------------------------------------------------------------------

def _dispatch_total():
    return obs.XLA_DISPATCH_TOTAL.total()


def test_superstep_dispatch_amortization():
    prev = obs.set_enabled(True)
    obs.reset()
    try:
        k = 4
        # single-step fused loop (today's behavior), warmed
        net, tr = _build()
        for i in range(2):
            x, y = _batch(i)
            with autograd.record():
                l = loss_fn(net(x), y)
            l.backward()
            tr.step(16)
        c0 = _dispatch_total()
        for i in range(k):
            x, y = _batch(i)
            with autograd.record():
                l = loss_fn(net(x), y)
            l.backward()
            tr.step(16)
        per_step_k1 = (_dispatch_total() - c0) / k

        net2, tr2 = _build()
        ss = gluon.Superstep(net2, loss_fn, tr2, k=k)
        xs = stack_batches([_batch(i)[0] for i in range(k)])
        ys = stack_batches([_batch(i)[1] for i in range(k)])
        ss.step(xs, ys, 16)  # warm: capture + compile
        c0 = _dispatch_total()
        ss.step(xs, ys, 16)
        per_step_kk = (_dispatch_total() - c0) / k
        # ONE dispatch per K steps: amortization >= K (vs >= 3 executables
        # per step on the one-step fused path)
        assert per_step_kk <= 1.0 / k + 1e-9, per_step_kk
        assert per_step_k1 / per_step_kk >= k, (per_step_k1, per_step_kk)
        # telemetry: superstep counters advanced, gauges have K-cadence
        assert obs.SUPERSTEP_ITERATIONS_TOTAL.total() == 2 * k
        assert obs.SUPERSTEP_TOTAL.total() == 2
    finally:
        obs.set_enabled(prev)
        obs.reset()


def test_superstep_amortization_report_line():
    """tools/telemetry_report.py prints the dispatches-per-step line
    from trainer.superstep trace events."""
    import sys
    sys.path.insert(0, mx.__path__[0].rsplit("/", 1)[0])
    from tools.telemetry_report import render_superstep

    events = [{"name": "trainer.superstep", "cat": "trainer",
               "dur": 4000.0, "args": {"k": 8, "step": 8}},
              {"name": "trainer.superstep", "cat": "trainer",
               "dur": 3900.0, "args": {"k": 8, "step": 16}}]
    out = render_superstep(events)
    assert "2 dispatches covering 16 training steps" in out
    assert "0.125 dispatches/step" in out
    assert render_superstep([]) == ""
    # malformed args must not crash (crash-proof contract)
    assert "1 dispatches" in render_superstep(
        [{"name": "trainer.superstep", "args": None}])


# ---------------------------------------------------------------------------
# migration to/from the single-step plan
# ---------------------------------------------------------------------------

def test_superstep_migration_keeps_momentum():
    """step -> superstep -> step interleaving matches an all-single-step
    run exactly (optimizer state migrates both ways, never resets)."""
    n1, _, _ = _run_single(8)
    net, tr = _build()
    ss = gluon.Superstep(net, loss_fn, tr, k=4)
    for i in range(2):
        x, y = _batch(i)
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        tr.step(16)
    ss.step(stack_batches([_batch(2 + i)[0] for i in range(4)]),
            stack_batches([_batch(2 + i)[1] for i in range(4)]), 16)
    for i in range(6, 8):
        x, y = _batch(i)
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        tr.step(16)
    for a, b in zip(_weights(n1), _weights(net)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_superstep_does_not_rebuild_one_step_plan():
    """Interleaving superstep and trainer.step must NOT drop the
    one-step fused plan (a rebuild retraces its executable): the plan
    object survives and only its state copies re-migrate by identity."""
    net, tr = _build()
    ss = gluon.Superstep(net, loss_fn, tr, k=2)
    x, y = _batch(0)
    with autograd.record():
        l = loss_fn(net(x), y)
    l.backward()
    tr.step(16)
    plan_before = tr._fused
    assert isinstance(plan_before, dict)
    ss.step(stack_batches([_batch(1 + i)[0] for i in range(2)]),
            stack_batches([_batch(1 + i)[1] for i in range(2)]), 16)
    assert tr._fused is plan_before  # not invalidated by the superstep
    x, y = _batch(3)
    with autograd.record():
        l = loss_fn(net(x), y)
    l.backward()
    tr.step(16)
    assert tr._fused is plan_before  # same compiled plan, states refreshed
    n1, _, _ = _run_single(4)
    for a, b in zip(_weights(n1), _weights(net)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_superstep_adam_update_counts_advance_by_k():
    """Bias-correction t advances per scan iteration and the host-side
    update counts advance by K per dispatch (scheduler cadence)."""
    net, tr = _build(opt="adam")
    ss = gluon.Superstep(net, loss_fn, tr, k=4)
    ss.step(stack_batches([_batch(i)[0] for i in range(4)]),
            stack_batches([_batch(i)[1] for i in range(4)]), 16)
    assert tr._optimizer.num_update == 4
    ts = [int(st[-1]) for st in tr._fused_states.values()]
    assert all(t == 4 for t in ts), ts


class _StepDownSched(mx.lr_scheduler.LRScheduler):
    """Probe schedule: records every sampled count, steps 0.1 -> 0.01
    after update 2 — INSIDE the first K=4 superstep, so per-iteration
    sampling is observable in the weights, not just the counts."""

    def __init__(self, seen):
        super().__init__()
        self.seen = seen

    def __call__(self, num_update):
        self.seen.append(num_update)
        return 0.1 if num_update <= 2 else 0.01


def _build_sched(seen):
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.Dense(3, in_units=8)
    net.initialize(init=mx.initializer.Xavier())
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1,
                        "lr_scheduler": _StepDownSched(seen)},
                       kvstore=None)
    return net, tr


def test_superstep_lr_scheduler_per_iteration():
    """ROADMAP item 5 remainder: the scheduler is sampled PER SCAN
    ITERATION (counts first_update .. first_update+K-1 ride the scan as
    a [K] lr vector), so a schedule boundary inside a superstep applies
    at the right iteration — no more K-step lr granularity."""
    seen = []
    net, tr = _build_sched(seen)
    ss = gluon.Superstep(net, loss_fn, tr, k=2)
    for g in range(2):
        ss.step(stack_batches([_batch(g * 2 + i)[0] for i in range(2)]),
                stack_batches([_batch(g * 2 + i)[1] for i in range(2)]),
                16)
    # sampled once per iteration, at exactly the single-step counts
    assert seen == [1, 2, 3, 4], seen


def test_superstep_lr_schedule_parity_vs_single_step():
    """A schedule stepping down mid-superstep produces bit-comparable
    weights to the single-step loop over the same batches (the parity
    pin for the per-iteration lr vector)."""
    net_s, tr_s = _build_sched([])
    for i in range(4):
        x, y = _batch(i)
        with autograd.record():
            l = loss_fn(net_s(x), y)
        l.backward()
        tr_s.step(16)
    net_k, tr_k = _build_sched([])
    ss = gluon.Superstep(net_k, loss_fn, tr_k, k=4)
    ss.step(stack_batches([_batch(i)[0] for i in range(4)]),
            stack_batches([_batch(i)[1] for i in range(4)]), 16)
    for a, b in zip(_weights(net_s), _weights(net_k)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# fallback contract
# ---------------------------------------------------------------------------

def test_superstep_unfusable_optimizer_falls_back_and_logs(caplog):
    fusedstep.reset_fallback_log()
    mx.random.seed(0)
    net = nn.Dense(3, in_units=8)
    net.initialize(init=mx.initializer.Xavier())
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adagrad",
                       {"learning_rate": 0.05}, kvstore=None)
    ss = gluon.Superstep(net, loss_fn, tr, k=4)
    xs = stack_batches([_batch(i)[0] for i in range(4)])
    ys = stack_batches([_batch(i)[1] for i in range(4)])
    import logging
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.fusedstep"):
        l = ss.step(xs, ys, 16)
    assert ss._plan is False
    assert l.shape == (4,)
    assert np.isfinite(np.asarray(l.data)).all()
    assert any("superstep" in r.message for r in caplog.records)
    # the fallback actually trained (4 single steps)
    assert tr._optimizer.num_update == 4


def test_superstep_disabled_flag_uses_single_steps():
    prev = fusedstep.set_enabled(False)
    try:
        net, tr = _build()
        ss = gluon.Superstep(net, loss_fn, tr, k=4)
        l = ss.step(stack_batches([_batch(i)[0] for i in range(4)]),
                    stack_batches([_batch(i)[1] for i in range(4)]), 16)
        assert l.shape == (4,)
        assert ss._plan is None  # never decided, flag short-circuits
    finally:
        fusedstep.set_enabled(prev)
    n1, _, _ = _run_single(4)
    for a, b in zip(_weights(n1), _weights(net)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_superstep_k_env_default():
    prev = fusedstep.set_superstep_k(6)
    try:
        net, tr = _build()
        ss = gluon.Superstep(net, loss_fn, tr)
        assert ss.k == 6
    finally:
        fusedstep.set_superstep_k(prev)


# ---------------------------------------------------------------------------
# staging ring + run() epoch driver
# ---------------------------------------------------------------------------

def test_superstep_ring_groups_and_tail():
    ring = SuperstepRing(((_batch(i)) for i in range(10)), 4,
                         device=mx.cpu())
    groups = list(ring)
    assert [n for _, n in groups] == [4, 4, 2]
    stacked, n = groups[0]
    assert n == 4 and stacked[0].shape == (4, 16, 8)
    tail, n = groups[2]
    assert isinstance(tail, list) and len(tail) == 2
    ring.close()


def test_superstep_ring_error_contract():
    def bad():
        yield _batch(0)
        yield _batch(1)
        yield _batch(2)
        raise RuntimeError("producer exploded")

    ring = SuperstepRing(bad(), 2, device=mx.cpu())
    _, n = next(ring)
    assert n == 2
    tail, n = next(ring)  # staged batch delivered before the error
    assert n == 1
    with pytest.raises(RuntimeError, match="producer exploded"):
        next(ring)
    ring.close()  # idempotent
    ring.close()


def test_superstep_ring_wraps_existing_prefetcher():
    pf = DevicePrefetcher((_batch(i) for i in range(6)), device=mx.cpu())
    ring = SuperstepRing(pf, 2)
    assert ring._pf is pf and ring._own is False
    _, n = next(iter(ring))
    assert n == 2
    ring.close()  # must NOT close a prefetcher it doesn't own
    x, _ = next(pf)  # still serving staged batches after ring.close()
    assert x.shape == (16, 8)
    pf.close()


def test_stack_batches_structure_and_mismatch():
    b0 = {"x": mx.nd.ones((2, 3)), "y": [mx.nd.zeros((2,)), 7]}
    b1 = {"x": mx.nd.ones((2, 3)), "y": [mx.nd.zeros((2,)), 7]}
    out = stack_batches([b0, b1])
    assert out["x"].shape == (2, 2, 3)
    assert out["y"][0].shape == (2, 2) and out["y"][1] == 7
    with pytest.raises(ValueError, match="shape/structure"):
        stack_batches([b0, {"x": mx.nd.ones((3, 3)),
                            "y": [mx.nd.zeros((2,)), 7]}])


def test_superstep_run_with_dataloader_list_batches():
    """run() over a real DataLoader: the default batchify yields LIST
    batches, whose stacked full groups must still route to the one-
    dispatch path (regression: a list-typed stacked group was once
    mistaken for a short tail)."""
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    rs = np.random.RandomState(7)
    ds = ArrayDataset(rs.randn(64, 8).astype(np.float32),
                      rs.randint(0, 3, (64,)).astype(np.float32))
    net, tr = _build()
    ss = gluon.Superstep(net, loss_fn, tr, k=2)
    losses = ss.run(DataLoader(ds, batch_size=16), 16, device=mx.cpu())
    assert len(losses) == 4
    assert np.isfinite(losses).all()
    assert isinstance(ss._plan, dict), ss._plan  # superstep path engaged
    assert tr._optimizer.num_update == 4


def test_superstep_run_with_mismatched_ring_k():
    """A caller-supplied ring whose k differs from the Superstep's:
    full groups of RING.k run stacked, and a short tail of exactly
    superstep-k batches must still single-step (regression: it was once
    mistaken for a stacked block, training with batch-1 as labels)."""
    net, tr = _build()
    ss = gluon.Superstep(net, loss_fn, tr, k=2)
    # 6 batches through a k=4 ring: one full group of 4, tail of 2 == ss.k
    ring = SuperstepRing((_batch(i) for i in range(6)), 4, device=mx.cpu())
    losses = ss.run(ring, 16)
    assert len(losses) == 6
    n1, _, ref = _run_single(6)
    np.testing.assert_allclose(losses, ref, rtol=1e-5, atol=1e-6)
    for a, b in zip(_weights(n1), _weights(net)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_superstep_ring_does_not_defer_keyboard_interrupt():
    """Ctrl-C must surface immediately, not after a tail group trains."""
    def src():
        yield _batch(0)
        raise KeyboardInterrupt

    ring = SuperstepRing(src(), 4, device=mx.cpu())
    with pytest.raises(KeyboardInterrupt):
        next(ring)
    ring.close()


def test_superstep_run_epoch_with_tail_parity():
    net, tr = _build()
    ss = gluon.Superstep(net, loss_fn, tr, k=4)
    losses = ss.run((_batch(i) for i in range(10)), 16, device=mx.cpu())
    assert len(losses) == 10
    n1, _, ref = _run_single(10)
    np.testing.assert_allclose(losses, ref, rtol=1e-5, atol=1e-6)
    for a, b in zip(_weights(n1), _weights(net)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# scan-compatible bucketed allreduce + SPMD superstep
# ---------------------------------------------------------------------------

def test_bucketed_psum_in_scan_parity():
    from jax.sharding import PartitionSpec as P

    mesh = parallel.make_mesh({"dp": 8})
    rs = np.random.RandomState(0)
    grads = [jnp.asarray(rs.randn(*s).astype(dt)) for s, dt in
             [((33, 7), np.float32), ((5,), np.float32),
              ((4, 4), np.float16), ((129,), np.float32),
              ((2, 3, 5), np.float16)]]

    def inner(gs):
        def body(c, _):
            return c, parallel.bucketed_psum(gs, "dp", bucket_bytes=256)

        _, outs = jax.lax.scan(body, 0, jnp.arange(2))
        return [o[1] for o in outs]  # second scan iteration's results

    outs = jax.shard_map(inner, mesh=mesh, in_specs=(P(),),
                         out_specs=P())(grads)
    for g, o in zip(grads, outs):
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   8 * np.asarray(g, np.float32),
                                   rtol=6e-3)


def test_bucketed_psum_single_tensor_and_split():
    """Odd sizes, one-tensor buckets, and the bucket-bytes split all
    reduce correctly (dtype-homogeneous buckets only)."""
    from jax.sharding import PartitionSpec as P

    mesh = parallel.make_mesh({"dp": 8})
    grads = [jnp.ones((1000,), jnp.float32),  # 4000 B: splits at 1024
             jnp.ones((3,), jnp.float32),
             jnp.ones((7,), jnp.float16)]

    f = jax.shard_map(lambda gs: parallel.bucketed_psum(gs, "dp",
                                                        bucket_bytes=1024),
                      mesh=mesh, in_specs=(P(),), out_specs=P())
    outs = f(grads)
    for g, o in zip(grads, outs):
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   8 * np.asarray(g, np.float32),
                                   rtol=1e-3)


def test_spmd_run_superstep_parity():
    def build():
        mx.random.seed(0)
        np.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8))
        net.add(nn.Dense(3, in_units=16))
        net.initialize(init=mx.initializer.Xavier())
        return net

    mesh = parallel.make_mesh({"dp": 8})
    for use_mesh in (None, mesh):
        net1 = build()
        s1 = parallel.SPMDTrainStep(net1, loss_fn, "sgd",
                                    {"momentum": 0.9}, mesh=use_mesh)
        seq = [s1(*_batch(i), lr=0.1) for i in range(4)]
        net2 = build()
        s2 = parallel.SPMDTrainStep(net2, loss_fn, "sgd",
                                    {"momentum": 0.9}, mesh=use_mesh)
        xs = stack_batches([_batch(i)[0] for i in range(4)])
        ys = stack_batches([_batch(i)[1] for i in range(4)])
        losses = s2.run_superstep(xs, ys, lr=0.1)
        np.testing.assert_allclose(np.asarray(losses, np.float32), seq,
                                   rtol=1e-4, atol=1e-5)
        s1.sync_to_block()
        s2.sync_to_block()
        for a, b in zip(_weights(net1), _weights(net2)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# in-scan device metrics (PR7): per-iteration loss/grad-norm/overflow
# series at K=8 with zero added dispatches — K-step capture no longer
# reduces metric cadence to K
# ---------------------------------------------------------------------------

def test_superstep_per_iteration_series_zero_added_dispatches():
    prev = obs.set_enabled(True)
    obs.reset()
    try:
        net, tr = _build("sgd")
        ss = gluon.Superstep(net, loss_fn, tr, k=8)
        xs = stack_batches([_batch(i)[0] for i in range(8)])
        ys = stack_batches([_batch(i)[1] for i in range(8)])
        ss.step(xs, ys, 16)  # warm: capture + compile
        assert isinstance(ss._plan, dict)
        before = obs.XLA_DISPATCH_TOTAL.total()
        ss.step(xs, ys, 16)
        # the K=8 superstep is STILL one dispatch — publishing the
        # per-iteration series stores the scan's stacked outputs whole
        # (lazy), never slicing or syncing on the hot path
        assert obs.XLA_DISPATCH_TOTAL.total() - before == 1
        series = obs.superstep_series()
        assert len(series["loss"]) == 8
        assert len(series["grad_norm"]) == 8
        assert len(series["overflow"]) == 8
        assert all(np.isfinite(series["loss"]))
        assert all(g > 0 for g in series["grad_norm"])
        assert series["overflow"] == [0.0] * 8
        # per-slot exposition for scrapers
        expo = obs.dump_prometheus()
        assert 'mxtpu_superstep_iter_loss{slot="7"}' in expo
        assert 'mxtpu_superstep_iter_grad_norm{slot="0"}' in expo
    finally:
        obs.set_enabled(prev)
        obs.reset()


def test_superstep_overflow_series_marks_poisoned_iteration():
    """fp16 in-scan AMP: the overflow series points at the exact
    iteration that skipped its update (slot 1 of 4), not just a per-K
    total."""
    amp.init("float16")
    prev = obs.set_enabled(True)
    obs.reset()
    try:
        net, tr = _build("sgd", amp_dtype="float16")
        ss = gluon.Superstep(net, loss_fn, tr, k=4)
        xs = stack_batches([_batch(i, dtype="float16",
                                   poison=(i == 1))[0] for i in range(4)])
        ys = stack_batches([_batch(i)[1] for i in range(4)])
        ss.step(xs, ys, 16)
        assert isinstance(ss._plan, dict)
        series = obs.superstep_series()
        assert series["overflow"] == [0.0, 1.0, 0.0, 0.0]
        assert tr._amp_loss_scaler.overflow_total == 1
    finally:
        amp.disable()
        obs.set_enabled(prev)
        obs.reset()
