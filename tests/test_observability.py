"""Unified runtime telemetry (mxnet_tpu.observability): registry
semantics, hot-path instrumentation (dispatch / CachedOp / kvstore /
trainer / engine.wait), exporters, and the disabled-path guarantee.

Reference analog: ``tests/python/unittest/test_profiler.py`` — extended
to the Prometheus/chrome-trace model this repro uses instead of the
engine-integrated profiler."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, observability as obs
from mxnet_tpu.gluon import nn

TOOLS = os.path.join(os.path.dirname(__file__), os.pardir, "tools")


@pytest.fixture(autouse=True)
def _telemetry_state():
    """Each test starts from a clean, DISABLED registry and leaves the
    process-default state behind (tier-1 runs with MXTPU_TELEMETRY unset)."""
    obs.set_enabled(False)
    obs.reset()
    yield
    obs.set_enabled(False)
    obs.reset()


def _tiny_net(in_units=8, hidden=16, classes=4, prefix=None):
    net = nn.HybridSequential(prefix=prefix)
    net.add(nn.Dense(hidden, activation="relu", in_units=in_units),
            nn.Dense(classes, in_units=hidden))
    net.initialize()
    return net


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = obs.MetricsRegistry()
    c = reg.counter("t_requests_total", "help text")
    c.inc()
    c.inc(2, route="a")
    c.inc(3, route="b")
    assert c.value() == 1
    assert c.value(route="a") == 2
    assert c.total() == 6
    with pytest.raises(mx.MXNetError):
        c.inc(-1)

    g = reg.gauge("t_depth")
    g.set(5)
    g.dec(2)
    assert g.value() == 3

    h = reg.histogram("t_latency_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.value() == 3
    assert h.sum() == pytest.approx(5.55)

    # same name -> same object; kind mismatch -> loud error
    assert reg.counter("t_requests_total") is c
    with pytest.raises(mx.MXNetError):
        reg.gauge("t_requests_total")


def test_registry_prometheus_exposition():
    reg = obs.MetricsRegistry()
    c = reg.counter("t_ops_total", "ops processed")
    c.inc(4, op="dot")
    h = reg.histogram("t_lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.dump_prometheus()
    assert "# HELP t_ops_total ops processed" in text
    assert "# TYPE t_ops_total counter" in text
    assert 't_ops_total{op="dot"} 4' in text
    assert "# TYPE t_lat_seconds histogram" in text
    assert 't_lat_seconds_bucket{le="0.1"} 1' in text
    assert 't_lat_seconds_bucket{le="+Inf"} 2' in text
    assert "t_lat_seconds_count 2" in text
    # label values escape per the exposition format
    c.inc(1, op='say "hi"\nback\\slash')
    line = [l for l in reg.dump_prometheus().splitlines() if "say" in l][0]
    assert line == 't_ops_total{op="say \\"hi\\"\\nback\\\\slash"} 1'


# ---------------------------------------------------------------------------
# hot-path instrumentation
# ---------------------------------------------------------------------------

def test_op_dispatch_counters():
    obs.set_enabled(True)
    a = mx.nd.ones((8, 8))
    for _ in range(3):
        b = mx.nd.dot(a, a)
    b.asnumpy()
    assert obs.OP_DISPATCH_TOTAL.value(op="dot") >= 3
    assert obs.OP_DISPATCH_SECONDS.value(op="dot") > 0


def test_cachedop_exactly_one_compile_then_hits():
    net = _tiny_net()
    net.hybridize()
    obs.set_enabled(True)
    x = mx.nd.ones((2, 8))
    for _ in range(5):
        net(x).asnumpy()
    assert obs.CACHEDOP_COMPILE_TOTAL.total() == 1
    assert obs.CACHEDOP_CACHE_HITS.total() == 4
    assert obs.CACHEDOP_TRACE_SECONDS.total() > 0
    # compile event landed in the tracer with cause=first
    compiles = [ev for ev in obs.tracer().events() if ev["cat"] == "compile"]
    assert len(compiles) == 1
    assert compiles[0]["args"]["cause"] == "first"


def test_cachedop_retrace_cause_diagnosis():
    net = _tiny_net()
    net.hybridize()
    obs.set_enabled(True)
    net(mx.nd.ones((2, 8))).asnumpy()
    net(mx.nd.ones((3, 8))).asnumpy()  # batch change -> shape retrace
    causes = obs.CACHEDOP_RETRACE_TOTAL.labelsets()
    assert any(ls.get("cause") == "shape" for ls in causes), causes
    with autograd.record():  # recording flips -> another retrace
        net(mx.nd.ones((3, 8)))
    causes = [ls.get("cause") for ls in obs.CACHEDOP_RETRACE_TOTAL.labelsets()]
    assert any("recording" in c for c in causes), causes
    assert obs.CACHEDOP_COMPILE_TOTAL.total() == 3


def test_kvstore_push_pull_byte_accounting():
    kv = mx.kv.create("local")
    shape = (4, 5)  # f32: 80 bytes
    kv.init(3, mx.nd.ones(shape))
    obs.set_enabled(True)
    kv.push(3, mx.nd.ones(shape))
    out = mx.nd.zeros(shape)
    kv.pull(3, out=out)
    assert obs.KV_PUSH_TOTAL.total() == 1
    assert obs.KV_PUSH_BYTES.total() == 80
    assert obs.KV_PULL_TOTAL.total() == 1
    assert obs.KV_PULL_BYTES.total() == 80
    # multi-device-style push: bytes sum over the value list
    kv.push(3, [mx.nd.ones(shape), mx.nd.ones(shape)])
    assert obs.KV_PUSH_BYTES.total() == 80 + 160


def test_kvstore_pushpull_accounting():
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.ones((2, 8)))  # 64 bytes
    obs.set_enabled(True)
    g = mx.nd.ones((2, 8))
    kv.pushpull("w", g, out=g)
    assert obs.KV_PUSHPULL_TOTAL.total() == 1
    assert obs.KV_PUSH_BYTES.total() == 64
    assert obs.KV_PULL_BYTES.total() == 64


def test_engine_wait_instrumented():
    obs.set_enabled(True)
    from mxnet_tpu import engine

    a = mx.nd.ones((4, 4)) + 1
    engine.wait(a.data)
    assert obs.ENGINE_WAIT_TOTAL.value(path="native") >= 1
    assert obs.ENGINE_WAIT_SECONDS.value(path="native") >= 0


# ---------------------------------------------------------------------------
# the acceptance loop: hybridized Trainer training on CPU
# ---------------------------------------------------------------------------

def test_trainer_loop_end_to_end_telemetry():
    rng = np.random.RandomState(0)
    net = _tiny_net()
    net.hybridize()
    kv = mx.kv.create("local")
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=kv)
    loss_fn = gluon.loss.L2Loss()
    x = mx.nd.array(rng.rand(2, 8).astype(np.float32))
    y = mx.nd.array(rng.rand(2, 4).astype(np.float32))

    obs.set_enabled(True)
    for _ in range(3):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(2)
    loss.asnumpy()

    # per-op dispatch counters ticked (loss math runs eagerly)
    assert obs.OP_DISPATCH_TOTAL.total() > 0
    # exactly-one-compile cache stats for the whole loop
    assert obs.CACHEDOP_COMPILE_TOTAL.total() == 1
    assert obs.CACHEDOP_CACHE_HITS.total() == 2
    # kvstore byte totals: 4 params aggregated per step
    assert obs.KV_PUSHPULL_TOTAL.total() == 12
    assert obs.KV_PUSH_BYTES.total() > 0
    assert obs.KV_PUSH_BYTES.total() == obs.KV_PULL_BYTES.total()
    # step metrics + grad-norm gauge
    assert obs.TRAINER_STEP_TOTAL.total() == 3
    assert obs.TRAINER_GRAD_NORM.value() > 0
    # step spans exportable both ways
    spans = [ev for ev in obs.tracer().events()
             if ev["name"] == "trainer.step"]
    assert [ev["args"]["step"] for ev in spans] == [1, 2, 3]
    prom = obs.dump_prometheus()
    for name in ("mxtpu_op_dispatch_total", "mxtpu_cachedop_compile_total",
                 "mxtpu_kvstore_push_bytes_total", "mxtpu_trainer_step_total",
                 "mxtpu_trainer_grad_norm"):
        assert name in prom, name
    chrome = json.loads(obs.dump_chrome_trace())
    assert any(ev["name"] == "trainer.step" and ev["ph"] == "X"
               for ev in chrome["traceEvents"])
    # summary is renderable and mentions the step count
    assert "3 steps" in obs.summary()


def test_disabled_path_records_nothing():
    """MXTPU_TELEMETRY=0 semantics: instrumented paths record zero."""
    assert not obs.enabled()
    net = _tiny_net()
    net.hybridize()
    kv = mx.kv.create("local")
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=kv)
    loss_fn = gluon.loss.L2Loss()
    x = mx.nd.ones((2, 8))
    y = mx.nd.ones((2, 4))
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(2)
    loss.asnumpy()
    for m in obs.registry().metrics():
        if m is obs.PROFILE_COUNTER:
            continue  # user-driven, not hot-path
        assert m.total() == 0, m.name
    assert len(obs.tracer()) == 0


def test_env_switch_parsing():
    """MXTPU_TELEMETRY=1 flips the import-time default (the unset->off
    default is exercised by every other test via the autouse fixture)."""
    code = ("import mxnet_tpu as mx; "
            "print(mx.observability.enabled())")
    env = dict(os.environ, MXTPU_TELEMETRY="1", JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert res.stdout.strip() == "True", res.stdout


# ---------------------------------------------------------------------------
# exporters round-trip + report tool
# ---------------------------------------------------------------------------

def test_jsonl_round_trip(tmp_path):
    obs.set_enabled(True)
    with obs.span("work", cat="test", tag="a"):
        pass
    obs.tracer().instant("marker", cat="test")
    path = str(tmp_path / "trace.jsonl")
    obs.dump_jsonl(path)
    events = obs.load_jsonl(path)
    assert [ev["name"] for ev in events] == ["work", "marker"]
    assert events[0]["ph"] == "X" and events[1]["ph"] == "i"
    assert events[0]["args"]["tag"] == "a"
    # chrome trace holds the same events under traceEvents
    chrome = json.loads(obs.dump_chrome_trace(str(tmp_path / "trace.json")))
    assert len(chrome["traceEvents"]) == 2


def test_trace_ring_buffer_bounded():
    tr = obs.Tracer(capacity=16)
    for i in range(100):
        tr.record(f"ev{i}", cat="test")
    assert len(tr) == 16
    assert tr.events()[-1]["name"] == "ev99"


def test_telemetry_report_cli(tmp_path):
    """tools/telemetry_report.py renders the dumps-style table (tier-1
    smoke: pure-stdlib subprocess, no jax import)."""
    obs.set_enabled(True)
    for _ in range(3):
        with obs.span("trainer.step", cat="trainer"):
            pass
    with obs.span("cachedop.compile[net]", cat="compile"):
        pass
    path = str(tmp_path / "t.jsonl")
    obs.dump_jsonl(path)

    res = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "telemetry_report.py"), path,
         "--steps"],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    out = res.stdout
    assert "Telemetry Trace Statistics:" in out
    assert "Total Count" in out and "Avg (ms)" in out
    line = [l for l in out.splitlines() if l.startswith("trainer.step")][0]
    assert int(line.split()[1]) == 3
    # the static graph-contracts section rides along on every report
    assert "Graph contracts" in out and "spmd_step" in out
    # --cat filter drops other categories
    res2 = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "telemetry_report.py"), path,
         "--cat", "compile"],
        capture_output=True, text=True, timeout=60)
    assert "trainer.step" not in res2.stdout
    assert "cachedop.compile[net]" in res2.stdout


def test_profile_counter_absorbed_into_registry():
    from mxnet_tpu import profiler

    c = profiler.ProfileCounter("requests")
    c.increment(5)
    c.decrement(2)
    c.value = 7  # legacy attribute-style write still works
    assert c.value == 7
    assert 'mxtpu_profile_counter{name="requests"} 7' \
        in obs.dump_prometheus()


# ---------------------------------------------------------------------------
# training-loop integrations
# ---------------------------------------------------------------------------

def test_estimator_telemetry_handler(caplog):
    import logging

    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        TelemetryHandler,
    )

    net = _tiny_net()
    net.hybridize()
    est = Estimator(net, gluon.loss.L2Loss(),
                    metrics=mx.metric.Loss("l2"))
    data = [(mx.nd.ones((2, 8)), mx.nd.ones((2, 4)))] * 2
    handler = TelemetryHandler()
    with caplog.at_level(logging.INFO, logger="telemetry"):
        est.fit(data, epochs=1, event_handlers=[handler])
    assert obs.enabled()  # attaching the handler is the opt-in
    text = caplog.text
    assert "op dispatches" in text
    assert "telemetry summary" in text
    epochs = [ev for ev in obs.tracer().events() if ev["cat"] == "epoch"]
    assert len(epochs) == 1 and epochs[0]["args"]["batches"] == 2


def test_callback_telemetry_logger(caplog):
    import logging

    obs.set_enabled(True)
    a = mx.nd.ones((2, 2))
    (a + a).asnumpy()
    cb = mx.callback.TelemetryLogger()
    with caplog.at_level(logging.INFO, logger="telemetry"):
        cb(0, None, None, None)  # epoch_end_callback signature
    assert "telemetry summary" in caplog.text
    assert "[Epoch 0]" in caplog.text


# ---------------------------------------------------------------------------
# Prometheus histogram exposition spec (PR7 satellite): cumulative
# bucket counts, an explicit +Inf bucket equal to _count, and the
# _sum/_count series — the format prometheus scrapers actually require
# ---------------------------------------------------------------------------

def test_histogram_prometheus_spec_compliance():
    reg = obs.MetricsRegistry()
    h = reg.histogram("t_lat_seconds", "spec probe",
                      buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    h.observe(0.5, route="a")
    lines = h.expose()
    assert lines.count("# TYPE t_lat_seconds histogram") == 1

    def val(line):
        return float(line.rsplit(" ", 1)[1])

    # unlabeled series: cumulative, monotonically non-decreasing counts
    unl = [ln for ln in lines
           if ln.startswith('t_lat_seconds_bucket{le=')]
    assert [val(ln) for ln in unl] == [1, 3, 4, 5]
    assert unl[-1].startswith('t_lat_seconds_bucket{le="+Inf"}')
    # +Inf bucket == _count, and _sum is the exact observation sum
    assert val([ln for ln in lines
                if ln.startswith("t_lat_seconds_count ")][0]) == 5
    assert val([ln for ln in lines
                if ln.startswith("t_lat_seconds_sum ")][0]) \
        == pytest.approx(56.05)
    # labeled series carry their labels plus le, same cumulative rule
    lab = [ln for ln in lines
           if ln.startswith('t_lat_seconds_bucket{route="a"')]
    assert [val(ln) for ln in lab] == [0, 1, 1, 1]
    assert 'le="+Inf"' in lab[-1]
    assert val([ln for ln in lines if ln.startswith(
        't_lat_seconds_count{route="a"}')][0]) == 1


def test_series_gauge_lazy_array_semantics():
    import jax.numpy as jnp

    reg = obs.MetricsRegistry()
    s = reg.series_gauge("t_iter_series", "per-slot probe")
    s.set_series(jnp.asarray([1.0, 2.0, 3.0]))  # stored lazy, whole
    assert s.series() == [1.0, 2.0, 3.0]
    assert s.value() == 3.0  # last slot
    assert s.total() == 6.0
    lines = s.expose()
    assert 't_iter_series{slot="0"} 1' in lines
    assert 't_iter_series{slot="2"} 3' in lines
    s.set_series([5.0])  # plain lists work too; old slots drop
    assert s.series() == [5.0]
    assert len([ln for ln in s.expose() if "slot=" in ln]) == 1


# ---------------------------------------------------------------------------
# scrape endpoint (PR7 satellite): /metrics + /healthz on a
# background thread, idempotent shutdown
# ---------------------------------------------------------------------------

def test_serve_metrics_endpoint_and_idempotent_shutdown():
    import urllib.error
    import urllib.request

    port = obs.serve_metrics(0)  # ephemeral
    try:
        assert obs.metrics_port() == port
        # idempotent start: same port back, no second server
        assert obs.serve_metrics(0) == port
        obs.registry().counter("t_http_probe_total").inc(7)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "t_http_probe_total 7" in body
        assert "mxtpu_trainer_step_total" in body  # whole catalog served
        hz = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert hz.read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
    finally:
        obs.stop_metrics_server()
        obs.stop_metrics_server()  # idempotent
    assert obs.metrics_port() is None
    # restartable after shutdown
    p2 = obs.serve_metrics(0)
    try:
        assert p2
    finally:
        obs.stop_metrics_server()


# ---------------------------------------------------------------------------
# telemetry-overhead regression (PR7 satellite): MXTPU_TELEMETRY=1 must
# add ZERO XLA dispatches to the fused loop (the in-graph grad norm is
# a lazy device scalar, not an extra executable) and bounded wall cost
# ---------------------------------------------------------------------------

def test_telemetry_adds_zero_dispatches_and_bounded_wall():
    import time as _time

    from mxnet_tpu import autograd as ag, engine, gluon as gl

    loss_fn = gl.loss.SoftmaxCrossEntropyLoss()
    net = _tiny_net()
    net.hybridize()
    tr = gl.Trainer(net.collect_params(), "sgd",
                    {"learning_rate": 0.05, "momentum": 0.9},
                    kvstore=None)
    X, Y = mx.nd.ones((8, 8)), mx.nd.zeros((8,))

    def one():
        with ag.record():
            l = loss_fn(net(X), Y)
        l.backward()
        tr.step(8)
        return l

    def timed(n):
        t0 = _time.perf_counter()
        l = None
        for _ in range(n):
            l = one()
        engine.wait(l.data)
        return _time.perf_counter() - t0

    N = 30
    one(); engine.wait(one().data)      # warm (telemetry off)
    t_off = timed(N)
    obs.set_enabled(True)
    # telemetry flips the CachedOp key + fused-plan signature: one
    # warm step absorbs the rebuild before counting
    one(); engine.wait(one().data)
    c0 = obs.XLA_DISPATCH_TOTAL.total()
    engine.wait(one().data)
    per_step = obs.XLA_DISPATCH_TOTAL.total() - c0  # steady-state cost
    c0 = obs.XLA_DISPATCH_TOTAL.total()
    fused0 = obs.XLA_DISPATCH_TOTAL.value(site="trainer_fused")
    op0 = obs.XLA_DISPATCH_TOTAL.value(site="op")
    t_on = timed(N)
    delta = obs.XLA_DISPATCH_TOTAL.total() - c0
    # telemetry dispatches NOTHING of its own: every step costs exactly
    # the steady-state constant (the grad-norm gauge rides the fused
    # executable as a lazy scalar — no probe executable, no sync), and
    # the fused trio stays one dispatch per site per step. The only
    # `op` dispatches are the un-hybridized loss block's own eager ops
    # (a property of the loop, identical with telemetry off).
    assert delta == per_step * N, (delta, per_step, N)
    assert obs.XLA_DISPATCH_TOTAL.value(site="trainer_fused") \
        - fused0 == N
    assert (obs.XLA_DISPATCH_TOTAL.value(site="op") - op0) \
        == (per_step - 3) * N  # fwd + bwd + fused update = the 3
    # bounded wall overhead; re-measure BOTH legs once before failing —
    # CI host pressure must not masquerade as a telemetry regression
    # (and the retry baseline must really run telemetry-OFF, or the
    # retry would compare on-vs-on and the gate would be vacuous)
    if t_on > 4.0 * t_off:
        obs.set_enabled(False)
        engine.wait(one().data)  # re-warm the off-keyed executables
        t_off = timed(N)
        obs.set_enabled(True)
        engine.wait(one().data)
        t_on = timed(N)
    assert t_on < 4.0 * t_off, (t_on, t_off)
