"""Performance introspection (observability/introspect.py): per-site
XLA cost/memory registration, donation verification, the MFU/roofline
estimator's null-with-reason contract, graceful degradation on
backends whose analyses return None/partial, and profiler windows."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, observability as obs
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import introspect

TOOLS = os.path.join(os.path.dirname(__file__), os.pardir, "tools")
loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()


@pytest.fixture(autouse=True)
def _clean_introspect():
    """Every test starts with introspection off and an empty site
    table, and restores the process defaults."""
    introspect.set_enabled(False)
    introspect.reset()
    obs.set_enabled(False)
    obs.reset()
    yield
    introspect.set_enabled(False)
    introspect.reset()
    obs.set_enabled(False)
    obs.reset()


def _train_steps(n=3, hybridize=True):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize()
    if hybridize:
        net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9},
                       kvstore=None)
    X, Y = mx.nd.ones((8, 8)), mx.nd.zeros((8,))
    for _ in range(n):
        with autograd.record():
            l = loss_fn(net(X), Y)
        l.backward()
        tr.step(8)
    return net, tr


# ---------------------------------------------------------------------------
# cost/memory registration
# ---------------------------------------------------------------------------

def test_fused_loop_registers_all_sites():
    introspect.set_enabled(True)
    _train_steps()
    sites = set(introspect.costs())
    assert "trainer_fused" in sites
    assert any(s.startswith("cachedop_fwd[") for s in sites)
    assert any(s.startswith("cachedop_bwd[") for s in sites)
    rec = introspect.site_cost("trainer_fused")
    # the XLA CPU backend reports both analyses: every numeric field set
    assert rec["flops"] > 0
    assert rec["bytes_accessed"] > 0
    assert rec["arith_intensity"] == pytest.approx(
        rec["flops"] / rec["bytes_accessed"])
    assert rec["argument_bytes"] > 0 and rec["output_bytes"] > 0
    assert rec["donated"] is True
    # registration happens ONCE per site: the gauge sees the same value
    # and the table stays one row per site over repeated steps
    assert len([s for s in sites if s == "trainer_fused"]) == 1


def test_superstep_site_registers_the_scan_cost():
    """The K-step scan executable is a cost site of its own: its FLOPs
    cover K iterations of what the one-step trio costs."""
    from mxnet_tpu.gluon.data.prefetcher import stack_batches

    introspect.set_enabled(True)
    net, tr = _train_steps()
    k = 2
    sstep = gluon.Superstep(net, loss_fn, tr, k=k)
    xs = stack_batches([mx.nd.ones((8, 8))] * k)
    ys = stack_batches([mx.nd.zeros((8,))] * k)
    sstep.step(xs, ys, 8)
    rec = introspect.site_cost("superstep")
    assert rec and rec["flops"] > 0
    one_step, reason = introspect.flops_per_step()
    assert reason is None and rec["flops"] / k > 0.5 * one_step


def test_eager_op_sites_register():
    introspect.set_enabled(True)
    (mx.nd.ones((4, 4)) + mx.nd.ones((4, 4))).asnumpy()
    sites = introspect.costs()
    assert any(s.startswith("op[") for s in sites), sites


def test_disabled_registers_nothing():
    _train_steps()
    assert introspect.costs() == {}


def test_cost_gauges_published_under_telemetry():
    obs.set_enabled(True)
    introspect.set_enabled(True)
    _train_steps()
    assert obs.EXEC_FLOPS.value(site="trainer_fused") > 0
    expo = obs.dump_prometheus()
    assert 'mxtpu_executable_flops{site="trainer_fused"}' in expo
    # each registration also records one introspect.cost trace event
    names = [ev["name"] for ev in obs.tracer().events()]
    assert "introspect.cost" in names


def test_cost_table_renders():
    introspect.set_enabled(True)
    _train_steps()
    table = introspect.cost_table()
    assert "trainer_fused" in table and "GFLOPs" in table
    # and the empty-state message is not an exception either
    introspect.reset()
    assert "no executables registered" in introspect.cost_table()


# ---------------------------------------------------------------------------
# donation verification
# ---------------------------------------------------------------------------

def test_donation_unaliased_warns_once_and_counts(caplog):
    obs.set_enabled(True)
    rec = {"site": "t_fake_site", "donated": True, "alias_bytes": 0}
    import logging

    with caplog.at_level(logging.WARNING, "mxnet_tpu.introspect"):
        introspect._verify_donation(rec)
        introspect._verify_donation(rec)  # second call: silent
    msgs = [r for r in caplog.records if "donation FAILED" in r.message]
    assert len(msgs) == 1
    assert obs.DONATION_UNALIASED_TOTAL.value(site="t_fake_site") == 1


def test_donation_ok_or_unknown_stays_quiet(caplog):
    import logging

    with caplog.at_level(logging.WARNING, "mxnet_tpu.introspect"):
        introspect._verify_donation(
            {"site": "t_ok", "donated": True, "alias_bytes": 128})
        introspect._verify_donation(
            {"site": "t_na", "donated": True, "alias_bytes": None})
        introspect._verify_donation(
            {"site": "t_nodon", "donated": False, "alias_bytes": 0})
    assert not [r for r in caplog.records if "donation" in r.message]


# ---------------------------------------------------------------------------
# graceful degradation: None / partial analyses (satellite)
# ---------------------------------------------------------------------------

class _FakeCompiled:
    def __init__(self, cost, mem=None, raise_cost=False):
        self._cost, self._mem, self._raise = cost, mem, raise_cost

    def cost_analysis(self):
        if self._raise:
            raise NotImplementedError("no cost analysis on this backend")
        return self._cost

    def memory_analysis(self):
        return self._mem


@pytest.mark.parametrize("cost", [
    None, {}, [{}], [], {"flops": 12.0},            # partial dicts
    {"bytes accessed": 8.0}, "not-a-dict",
])
def test_analyze_compiled_survives_partial_cost(cost):
    rec = introspect.analyze_compiled("t_site", _FakeCompiled(cost))
    assert rec["site"] == "t_site"
    assert rec["temp_bytes"] is None  # no memory analysis
    # flops/bytes filled only when the dict had them
    if isinstance(cost, dict) and "flops" in cost:
        assert rec["flops"] == 12.0
    else:
        assert rec["arith_intensity"] is None


def test_analyze_compiled_survives_raising_backend():
    rec = introspect.analyze_compiled(
        "t_site", _FakeCompiled(None, raise_cost=True))
    assert rec["flops"] is None and rec["bytes_accessed"] is None


def test_register_jit_unlowerable_records_error_stub():
    introspect.set_enabled(True)
    rec = introspect.register_jit("t_bad", object(), ())
    assert rec["flops"] is None and "error" in rec
    # the stub registers: the report/table paths see it, nothing raised
    assert "t_bad" in introspect.costs()
    assert "t_bad" in introspect.cost_table()


def test_flops_per_step_null_reasons():
    flops, reason = introspect.flops_per_step()
    assert flops is None and "no executable sites" in reason
    introspect.set_enabled(True)
    introspect.register_jit("t_bad2", object(), ())
    flops, reason = introspect.flops_per_step(sites=["t_bad2"])
    assert flops is None and reason


def test_flops_per_step_sums_fused_sites():
    introspect.set_enabled(True)
    _train_steps()
    flops, reason = introspect.flops_per_step()
    assert reason is None
    rec = introspect.site_cost("trainer_fused")
    assert flops >= rec["flops"]


# ---------------------------------------------------------------------------
# MFU / roofline estimator
# ---------------------------------------------------------------------------

def test_mfu_estimate_null_with_reason_paths():
    est = obs.mfu_estimate("nowhere", 0.01)
    assert est["mfu"] is None and "not registered" in est["reason"]
    introspect._publish({"site": "t_noflops", "flops": None,
                         "donated": False})
    est = obs.mfu_estimate("t_noflops", 0.01)
    assert est["mfu"] is None and est["reason"]
    # CPU backend: achieved computes, mfu null with the peak reason
    introspect._publish({"site": "t_cpu", "flops": 2e9,
                         "bytes_accessed": 1e9, "arith_intensity": 2.0,
                         "peak_tflops": None, "peak_hbm_gbs": None,
                         "peak_reason": "no peak-FLOPs table for device "
                                        "kind 'cpu'", "donated": False})
    est = obs.mfu_estimate("t_cpu", 0.001)
    assert est["achieved_tflops"] == pytest.approx(2.0)
    assert est["mfu"] is None and "peak" in est["reason"]


def test_mfu_estimate_with_peak_tables():
    # a synthetic accelerator record: 100 TFLOP/s peak, 1000 GB/s HBM
    introspect._publish({"site": "t_tpu", "flops": 1e12,
                         "bytes_accessed": 1e10, "arith_intensity": 100.0,
                         "peak_tflops": 100.0, "peak_hbm_gbs": 1000.0,
                         "donated": False})
    est = obs.mfu_estimate("t_tpu", 0.1)  # 10 TFLOP/s achieved
    assert est["achieved_tflops"] == pytest.approx(10.0)
    assert est["mfu"] == pytest.approx(0.1)
    assert est["bound"] == "compute"  # AI 100 >= ridge 100e12/1000e9=100
    introspect._publish({"site": "t_mem", "flops": 1e12,
                         "bytes_accessed": 1e12, "arith_intensity": 1.0,
                         "peak_tflops": 100.0, "peak_hbm_gbs": 1000.0,
                         "donated": False})
    assert obs.mfu_estimate("t_mem", 0.1)["bound"] == "memory"


def test_device_peaks_reason_on_cpu():
    peak, bw, reason = introspect.device_peaks()
    if jax.default_backend() == "cpu":
        assert peak is None and bw is None and "cpu" in reason


# ---------------------------------------------------------------------------
# profiler windows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("value,expect", [
    ("/tmp/prof", ("/tmp/prof", 1, 10)),
    ("/tmp/prof:5:20", ("/tmp/prof", 5, 20)),
    ("/tmp/pro:f", ("/tmp/pro:f", 1, 10)),       # colon in path, no ints
    ("/tmp/prof:0:0", ("/tmp/prof", 1, 1)),      # clamped to >= 1
])
def test_profile_env_parsing(value, expect):
    assert introspect._parse_profile_env(value) == expect


def test_profile_step_window_state_machine(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    introspect.configure_profile(str(tmp_path), start=3, stop=4)
    assert introspect.PROFILING
    for _ in range(6):
        if introspect.PROFILING:
            with introspect.profile_step():
                pass
    assert calls == [("start", str(tmp_path)), ("stop",)]
    st = introspect.profile_state()
    assert st["done"] and not st["active"]
    assert not introspect.PROFILING  # disarmed after the window closed


def test_profile_step_counts_superstep_k(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    introspect.configure_profile(str(tmp_path), start=5, stop=8)
    with introspect.profile_step(4):   # steps 1-4: before the window
        pass
    assert calls == []
    with introspect.profile_step(4, name="superstep"):  # steps 5-8
        pass
    assert calls == ["start", "stop"]


def test_trainer_step_under_profile_window(monkeypatch, tmp_path):
    """The Trainer.step hook drives the window: armed via
    configure_profile, steps open/close the (stubbed) trace."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    introspect.configure_profile(str(tmp_path), start=1, stop=2)
    try:
        _train_steps(n=4)
        assert calls == ["start", "stop"]
    finally:
        introspect.configure_profile(None)


def test_profile_window_writes_real_trace(tmp_path):
    """End-to-end jax.profiler capture through the public context
    manager (one real trace per test run — start_trace costs seconds)."""
    d = str(tmp_path / "prof")
    try:
        with obs.profile_window(d):
            with introspect.annotate("mxtpu.test_region"):
                jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))
                        ).block_until_ready()
    except Exception as e:  # pragma: no cover - env-specific plugin
        pytest.skip(f"jax profiler unavailable here: {e}")
    files = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert files, "profiler window produced no trace files"


# ---------------------------------------------------------------------------
# report tool roofline
# ---------------------------------------------------------------------------

def _cost_event(site, **args):
    return {"name": "introspect.cost", "cat": "introspect", "ph": "i",
            "ts": 0.0, "dur": 0.0, "pid": 1, "tid": 1,
            "args": dict(site=site, **args)}


def test_report_tool_renders_roofline(tmp_path, capsys):
    sys.path.insert(0, TOOLS)
    try:
        import telemetry_report as tr
    finally:
        sys.path.pop(0)
    events = [
        _cost_event("superstep", flops=8.5e7, bytes_accessed=5e5,
                    arith_intensity=170.0, peak_tflops=100.0,
                    peak_hbm_gbs=1000.0),
        # timing span so achieved TFLOP/s + MFU fill in
        {"name": "trainer.superstep", "cat": "trainer", "ph": "X",
         "ts": 0.0, "dur": 850.0, "pid": 1, "tid": 1,
         "args": {"k": 8}},
        # malformed records must render as '-' rows, never crash
        _cost_event("t_partial"),
        _cost_event("t_strings", flops="oops", peak_tflops="x"),
        {"name": "introspect.cost", "args": None},
    ]
    out = tr.render_roofline(events)
    assert "Executable roofline" in out
    assert "superstep" in out and "compute" in out
    assert "t_partial" in out and "t_strings" in out
    # achieved = 8.5e7 flops / 0.85ms span / 1e12 = 0.1 TFLOP/s
    assert "0.100" in out
    # absent series -> empty string
    assert tr.render_roofline([{"name": "trainer.step"}]) == ""
    # and the CLI path end-to-end
    p = tmp_path / "t.jsonl"
    p.write_text("\n".join(json.dumps(ev) for ev in events) + "\n")
    assert tr.main([str(p)]) == 0
    assert "Executable roofline" in capsys.readouterr().out
