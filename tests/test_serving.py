"""Production inference serving (``mxnet_tpu.serving``): AOT
shape-bucket executables + the sealed no-retrace contract, continuous
batching (deadlines, load shed, drain-on-close), multi-model hosting
with live swap/rollback, and the serving SLO surface.

Reference analog: the C predict API / model-server heritage tests —
here the contracts under test are the TPU-native ones (one executable
per bucket, zero recompiles after warmup, atomic version flips)."""

import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, observability as obs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data.shape_guard import pad_to_shape
from mxnet_tpu.observability.metrics import Histogram
from mxnet_tpu.serving import (
    ContinuousBatcher,
    EngineClosed,
    InferenceEngine,
    ModelRepository,
    RequestTimeout,
    RequestTooLarge,
    RetraceForbidden,
    ServerOverloaded,
    ServingError,
    StagedLoadError,
)
from mxnet_tpu.serving.batcher import _Request


@pytest.fixture(autouse=True)
def _telemetry_state():
    obs.set_enabled(False)
    obs.reset()
    yield
    obs.set_enabled(False)
    obs.reset()


FEAT = 6
CLASSES = 4
BUCKETS = [(4, FEAT), (8, FEAT), (16, FEAT)]


class _RaggedNet(gluon.HybridBlock):
    """Rows are (T, FEAT) sequences, ragged on T; output (CLASSES,)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.proj = nn.Dense(CLASSES, flatten=False, in_units=FEAT)

    def hybrid_forward(self, F, x):
        return F.mean(self.proj(x), axis=1)


def _ragged_net():
    net = _RaggedNet()
    net.initialize()
    return net


def _vec_net(bias=0.0, feat=8, classes=CLASSES):
    """Fixed-shape net with deterministic params: y = 0.1 * sum(x) + bias
    per class — versions are distinguishable by their bias."""
    net = nn.HybridSequential()
    net.add(nn.Dense(classes, in_units=feat))
    net.initialize()
    net[0].weight.set_data(mx.nd.ones((classes, feat)) * 0.1)
    net[0].bias.set_data(mx.nd.ones((classes,)) * bias)
    return net


def _engine(net=None, shapes=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 20.0)
    return InferenceEngine(net or _ragged_net(),
                           shapes or BUCKETS, **kw)


def _expect(net, row):
    """Ground truth for a request row: the net applied to the
    bucket-padded input (padding participates in non-row-wise math like
    the mean above, by design — the bucket IS the contract shape)."""
    return net(mx.nd.array(row[None])).asnumpy()[0]


# -- satellite units: pad_to_shape / Histogram.quantile --------------------

def test_pad_to_shape():
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    p = pad_to_shape(a, (2, 5))
    assert p.shape == (2, 5)
    assert np.array_equal(p[:, :3], a) and np.all(p[:, 3:] == 0)
    p = pad_to_shape(a, (4, 3), pad_value=7)
    assert p.shape == (4, 3) and np.all(p[2:] == 7)
    nd = pad_to_shape(mx.nd.array(a), (3, 4))
    assert nd.shape == (3, 4)
    with pytest.raises(MXNetError):
        pad_to_shape(a, (2, 3, 1))  # rank mismatch
    with pytest.raises(MXNetError):
        pad_to_shape(a, (2, 2))  # truncation is never implicit


def test_histogram_quantile():
    h = Histogram("t_q", buckets=(1.0, 2.0, 4.0, 8.0))
    assert h.quantile(0.5) is None  # no observations
    for v in (0.5, 1.5, 3.0, 3.5, 6.0):
        h.observe(v)
    p50 = h.quantile(0.5)
    assert 1.0 <= p50 <= 4.0
    assert h.quantile(0.0) <= h.quantile(0.99) <= 8.0
    h.observe(100.0)  # beyond the last finite bucket: clamps, no inf
    assert h.quantile(1.0) == 8.0
    with pytest.raises(MXNetError):
        h.quantile(1.5)


# -- AOT extraction hook ---------------------------------------------------

def test_aot_predict_fn_parity():
    import jax

    net = _ragged_net()
    fn, params = net.aot_predict_fn(sample_shape=(1, 8, FEAT))
    x = np.random.RandomState(0).rand(3, 8, FEAT).astype(np.float32)
    got = np.asarray(jax.jit(fn)(params, x))
    want = net(mx.nd.array(x)).asnumpy()
    assert np.allclose(got, want, atol=1e-5)


def test_aot_predict_fn_required():
    with pytest.raises(MXNetError, match="aot_predict_fn"):
        InferenceEngine(object(), BUCKETS)


# -- engine: AOT buckets, parity, the sealed no-retrace contract -----------

def test_engine_parity_and_zero_recompiles():
    net = _ragged_net()
    eng = _engine(net)
    try:
        assert eng.sealed and eng.stats()["compiles"] == len(BUCKETS)
        rng = np.random.RandomState(1)
        for t in [1, 3, 4, 5, 8, 9, 16, 2, 13]:  # ragged traffic
            row = rng.rand(t, FEAT).astype(np.float32)
            bucket = eng._bucket_for(row.shape)
            padded_row = pad_to_shape(row[None], (1,) + bucket)[0]
            out = eng.predict(row, timeout=10.0)
            assert out.shape == (1, CLASSES)
            assert np.allclose(out[0], _expect(net, padded_row), atol=1e-5)
        st = eng.stats()
        assert st["compiles"] == len(BUCKETS)  # FLAT after warmup
        assert st["retraces_after_warmup"] == 0
        assert st["requests_ok"] == 9
        assert st["latency_p50_ms"] is not None
    finally:
        eng.close()


def test_engine_micro_batch_rows():
    net = _ragged_net()
    eng = _engine(net)
    try:
        x = np.random.RandomState(2).rand(3, 4, FEAT).astype(np.float32)
        out = eng.predict(x, timeout=10.0)
        assert out.shape == (3, CLASSES)  # exactly the request's rows
        for i in range(3):
            assert np.allclose(out[i], _expect(net, x[i]), atol=1e-5)
    finally:
        eng.close()


def test_engine_refuses_unbucketable_shape():
    eng = _engine()
    try:
        with pytest.raises(RetraceForbidden, match="shape"):
            eng.submit(np.zeros((40, FEAT), np.float32))
        with pytest.raises(RetraceForbidden):
            eng.submit(np.zeros((2, 3, 4, 5), np.float32))  # bad rank
        assert eng.stats()["refused"] == 2
        assert eng.stats()["compiles"] == len(BUCKETS)  # refused != traced
    finally:
        eng.close()


def test_engine_refuses_dtype_with_cast_off():
    eng = _engine()
    try:
        x = np.zeros((4, FEAT), np.int32)
        with pytest.raises(RetraceForbidden, match="dtype"):
            eng.submit(x, cast=False)
        out = eng.predict(x, timeout=10.0)  # default casts instead
        assert out.shape == (1, CLASSES)
    finally:
        eng.close()


def test_engine_oversized_request_typed():
    eng = _engine(max_batch=4)
    try:
        with pytest.raises(RequestTooLarge, match="split it client-side"):
            eng.submit(np.zeros((5, 4, FEAT), np.float32))
    finally:
        eng.close()


# -- continuous batching ---------------------------------------------------

def test_batching_coalesces_requests():
    eng = _engine(max_batch=4, max_wait_ms=100.0)
    try:
        x = np.zeros((4, FEAT), np.float32)
        futs = [eng.submit(x) for _ in range(4)]
        for f in futs:
            assert f.result(timeout=10.0).shape == (1, CLASSES)
        st = eng.stats()
        assert st["requests_ok"] == 4
        assert st["batches"] <= 2  # coalesced, not one dispatch each
        assert st["mean_batch_fill"] >= 0.5
    finally:
        eng.close()


def test_deadline_expires_as_typed_timeout():
    # autostart=False holds the scheduler so the expiry is deterministic
    eng = _engine(autostart=False)
    try:
        fut = eng.submit(np.zeros((4, FEAT), np.float32), deadline_ms=1.0)
        time.sleep(0.03)
        eng._batcher.start()
        with pytest.raises(RequestTimeout, match="deadline expired"):
            fut.result(timeout=10.0)
        assert eng.stats()["timeouts"] == 1
    finally:
        eng.close()


def test_full_queue_sheds_typed():
    eng = _engine(autostart=False, queue_cap=2)
    x = np.zeros((4, FEAT), np.float32)
    accepted = [eng.submit(x), eng.submit(x)]
    with pytest.raises(ServerOverloaded, match="load shed"):
        eng.submit(x)
    assert eng.stats()["shed"] == 1
    eng.close()  # scheduler never ran: accepted work fails typed
    for f in accepted:
        with pytest.raises(EngineClosed):
            f.result(timeout=10.0)


def test_close_drains_inflight():
    net = _ragged_net()
    eng = _engine(net, max_wait_ms=200.0)  # long window: work sits queued
    x = np.random.RandomState(3).rand(4, FEAT).astype(np.float32)
    futs = [eng.submit(x) for _ in range(5)]
    eng.close()  # DevicePrefetcher contract: accepted work completes
    for f in futs:
        out = f.result(timeout=10.0)
        assert np.allclose(out[0], _expect(net, x), atol=1e-5)
    with pytest.raises(EngineClosed):
        eng.submit(x)
    eng.close()  # idempotent


def test_pause_resume_cycle():
    eng = _engine()
    try:
        x = np.zeros((4, FEAT), np.float32)
        eng.predict(x, timeout=10.0)
        compiles = eng.stats()["compiles"]
        eng.pause()
        with pytest.raises(EngineClosed, match="paused"):
            eng.submit(x)
        eng.resume()
        eng.predict(x, timeout=10.0)  # serving again, no recompile
        assert eng.stats()["compiles"] == compiles
    finally:
        eng.close()
    with pytest.raises(EngineClosed, match="released"):
        eng.resume()


def test_batcher_dispatch_error_propagates():
    def bad_dispatch(bucket, reqs):
        raise ValueError("device exploded")

    b = ContinuousBatcher(bad_dispatch, max_batch=2, max_wait=0.001,
                          queue_cap=8)
    try:
        req = _Request(np.zeros((1, 2), np.float32), 1, (2,))
        b.submit(req)
        assert req.event.wait(10.0)
        with pytest.raises(ValueError, match="device exploded"):
            from mxnet_tpu.serving.batcher import ServeFuture
            ServeFuture(req).result(0)
    finally:
        b.close()
        b.close()  # idempotent


def test_future_client_timeout_does_not_cancel():
    eng = _engine(autostart=False)  # result will never arrive
    try:
        fut = eng.submit(np.zeros((4, FEAT), np.float32))
        with pytest.raises(TimeoutError, match="still in flight"):
            fut.result(timeout=0.01)
        assert not fut.done()  # client patience != request deadline
    finally:
        eng.close()


# -- multi-model repository: swap, rollback, corrupt loads -----------------

def test_repository_swap_and_rollback():
    repo = ModelRepository(keep=1)
    try:
        x = np.ones((8,), np.float32)
        repo.load("clf", _vec_net(bias=0.0), shapes=[(8,)], version="v1",
                  max_batch=2, max_wait_ms=1.0)
        v1_out = repo.predict("clf", x, timeout=10.0)
        assert np.allclose(v1_out, 0.8, atol=1e-5)  # 0.1 * 8

        e2 = repo.load("clf", _vec_net(bias=100.0), shapes=[(8,)],
                       version="v2", max_batch=2, max_wait_ms=1.0)
        assert repo.models()["clf"] == {"live": "v2", "standby": ["v1"]}
        assert np.allclose(repo.predict("clf", x, timeout=10.0),
                           100.8, atol=1e-4)

        compiles_v1 = repo._models["clf"]["standby"][0].stats()["compiles"]
        restored = repo.rollback("clf")
        assert restored.version == "v1"
        assert np.allclose(repo.predict("clf", x, timeout=10.0),
                           0.8, atol=1e-5)
        # rollback is a pointer flip + resume, never a recompile
        assert restored.stats()["compiles"] == compiles_v1
        assert repo.models()["clf"] == {"live": "v1", "standby": ["v2"]}
        assert e2.version == "v2"
    finally:
        repo.close()


def test_repository_corrupt_load_never_serves():
    repo = ModelRepository()
    try:
        x = np.ones((8,), np.float32)
        repo.load("clf", _vec_net(bias=0.0), shapes=[(8,)], version="v1",
                  max_batch=2, max_wait_ms=1.0)
        with pytest.raises(StagedLoadError, match="keeps serving"):
            repo.load("clf", _vec_net(bias=float("nan")), shapes=[(8,)],
                      version="v2", max_batch=2, max_wait_ms=1.0)
        # the canary veto means v2 never became visible
        assert repo.models()["clf"] == {"live": "v1", "standby": []}
        assert np.allclose(repo.predict("clf", x, timeout=10.0),
                           0.8, atol=1e-5)
        # a crashing factory is equally invisible
        with pytest.raises(StagedLoadError):
            repo.load("clf", lambda: 1 / 0, shapes=[(8,)])
        assert repo.models()["clf"]["live"] == "v1"
    finally:
        repo.close()


def test_repository_swap_version_coherence_under_traffic():
    """Continuous requests across a live swap: every request succeeds
    and is answered by exactly one coherent version (its result matches
    the version stamped on its future)."""
    repo = ModelRepository(keep=1)
    expected = {"v1": 0.8, "v2": 100.8}
    stop = threading.Event()
    outcomes, errors = [], []

    def client():
        x = np.ones((8,), np.float32)
        while not stop.is_set():
            try:
                fut = repo.submit("clf", x)
                out = fut.result(timeout=10.0)
                outcomes.append((fut.version, float(out[0, 0])))
            except BaseException as e:  # no error is acceptable mid-swap
                errors.append(e)
                return

    try:
        repo.load("clf", _vec_net(bias=0.0), shapes=[(8,)], version="v1",
                  max_batch=2, max_wait_ms=1.0)
        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.05)  # traffic flowing on v1
        repo.load("clf", _vec_net(bias=100.0), shapes=[(8,)],
                  version="v2", max_batch=2, max_wait_ms=1.0)
        time.sleep(0.05)  # traffic flowing on v2
        stop.set()
        t.join(timeout=30.0)
        assert not t.is_alive()
        assert not errors, f"requests failed across the swap: {errors!r}"
        assert len(outcomes) > 0
        versions = {v for v, _ in outcomes}
        assert versions <= {"v1", "v2"}
        assert "v2" in versions  # the swap actually happened under load
        for version, value in outcomes:
            assert abs(value - expected[version]) < 1e-3, \
                f"request answered by an incoherent version: " \
                f"{version} -> {value}"
    finally:
        stop.set()
        repo.close()


def test_repository_unknown_model_and_unload():
    repo = ModelRepository()
    with pytest.raises(ServingError, match="no live version"):
        repo.engine("ghost")
    repo.load("m", _vec_net(), shapes=[(8,)], max_batch=2,
              max_wait_ms=1.0)
    assert repo.stats("m")["model"] == "m"
    repo.unload("m")
    with pytest.raises(ServingError):
        repo.predict("m", np.ones((8,), np.float32))
    repo.unload("m")  # idempotent
    repo.close()


def test_repository_rollback_without_standby():
    repo = ModelRepository()
    try:
        repo.load("m", _vec_net(), shapes=[(8,)], max_batch=2,
                  max_wait_ms=1.0)
        with pytest.raises(ServingError, match="no standby"):
            repo.rollback("m")
    finally:
        repo.close()


# -- int8 path -------------------------------------------------------------

def test_engine_serves_quantized_net():
    from mxnet_tpu.contrib.quantization import quantize_net

    net = _vec_net(bias=1.0)
    rng = np.random.RandomState(4)
    calib = [rng.rand(4, 8).astype(np.float32) for _ in range(3)]
    qnet = quantize_net(net, calib_data=calib)
    eng = InferenceEngine(qnet, shapes=[(8,)], max_batch=2,
                          max_wait_ms=1.0, name="int8")
    try:
        x = calib[0][0]
        got = eng.predict(x, timeout=10.0)[0]
        want = net(mx.nd.array(x[None])).asnumpy()[0]
        assert np.allclose(got, want, atol=0.1)  # int8 tolerance
        assert eng.stats()["retraces_after_warmup"] == 0
    finally:
        eng.close()


# -- SLO observability -----------------------------------------------------

def _tool(name):
    import importlib
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


def test_serving_metrics_and_slo_snapshot():
    obs.set_enabled(True)
    obs.reset()
    eng = _engine(name="slo")
    try:
        x = np.zeros((4, FEAT), np.float32)
        for _ in range(3):
            eng.predict(x, timeout=10.0)
        with pytest.raises(RetraceForbidden):
            eng.submit(np.zeros((99, FEAT), np.float32))
        assert obs.SERVE_REQUESTS_TOTAL.value(model="slo", code="ok") == 3
        assert obs.SERVE_REQUESTS_TOTAL.value(model="slo",
                                              code="error") == 1
        assert obs.SERVE_COMPILE_TOTAL.value(model="slo") == len(BUCKETS)
        assert obs.SERVE_BATCHES_TOTAL.value(model="slo",
                                             bucket=str((4, FEAT))) >= 1
        assert obs.XLA_DISPATCH_TOTAL.value(site="serving") >= 1
        snap = obs.serve_slo_snapshot("slo")
        assert snap["requests_ok"] == 3
        assert snap["latency_p50_s"] is not None
        assert snap["compiles"] == len(BUCKETS)
        names = [ev["name"] for ev in obs.tracer().events()]
        assert "serving.batch" in names and "serving.compile" in names
        # the doctor reads its serving verdict from the same ring
        doctor = _tool("mxtpu_doctor")
        verdicts = doctor.diagnose(obs.tracer().events())["serving"]
        assert verdicts and all(
            v["verdict"] in doctor.RECIPES and v["requests"] > 0
            for v in verdicts)
        text = obs.registry().dump_prometheus()
        assert "mxtpu_serving_latency_seconds" in text
    finally:
        eng.close()


def test_report_serving_section():
    tr = _tool("telemetry_report")

    events = [
        {"name": "serving.batch", "cat": "serving", "dur": 2000.0,
         "args": {"model": "clf", "bucket": "(8, 6)", "n_valid": 3,
                  "capacity": 4, "fill": 0.75, "queue_depth": 2}},
        {"name": "serving.batch", "cat": "serving", "dur": 1000.0,
         "args": {"model": "clf", "bucket": "(8, 6)", "n_valid": 4,
                  "capacity": 4, "fill": 1.0, "queue_depth": 0}},
        {"name": "serving.shed", "cat": "serving", "args": {"model": "clf"}},
        {"name": "serving.timeout", "cat": "serving",
         "args": {"model": "clf"}},
        {"name": "serving.compile", "cat": "serving",
         "args": {"model": "clf", "bucket": "(8, 6)"}},
        {"name": "serving.swap", "cat": "serving",
         "args": {"model": "clf", "outcome": "committed",
                  "version": "v2", "prev_version": "v1"}},
    ]
    out = tr.render_serving(events)
    assert "Serving:" in out
    assert "clf: 2 batches, 7 requests" in out
    assert "shed: 1, deadline timeouts: 1" in out
    assert "AOT bucket compiles: 1" in out
    assert "committed: v1 -> v2" in out
    # crash-proofing contract: malformed args render, never raise
    assert "Serving:" in tr.render_serving(
        [{"name": "serving.batch", "args": None},
         {"name": "serving.swap", "args": "garbage"}])
    assert tr.render_serving([{"name": "trainer.step"}]) == ""


def test_env_knob_defaults(monkeypatch):
    from mxnet_tpu.serving import (serve_max_batch, serve_max_wait_ms,
                                   serve_queue_cap)

    monkeypatch.delenv("MXTPU_SERVE_MAX_BATCH", raising=False)
    monkeypatch.delenv("MXTPU_SERVE_MAX_WAIT_MS", raising=False)
    monkeypatch.delenv("MXTPU_SERVE_QUEUE", raising=False)
    assert serve_max_batch() == 8
    assert serve_max_wait_ms() == 5.0
    assert serve_queue_cap() == 256
    monkeypatch.setenv("MXTPU_SERVE_MAX_BATCH", "2")
    monkeypatch.setenv("MXTPU_SERVE_MAX_WAIT_MS", "0.5")
    monkeypatch.setenv("MXTPU_SERVE_QUEUE", "3")
    assert serve_max_batch() == 2
    assert serve_max_wait_ms() == 0.5
    assert serve_queue_cap() == 3
