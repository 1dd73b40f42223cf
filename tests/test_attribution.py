"""Step-time attribution plane (observability.attribution) and its
consumers: the budget-decomposition invariants, the hot-path hooks
(trainer / prefetch-wait / watchdog), the zero-added-dispatch contract,
the multi-track timeline export (tools/timeline.py), and mxtpu-doctor
verdicts / --env (tools/mxtpu_doctor.py).

The plane is arithmetic over host floats the hot paths already record:
every test here drives either REAL training steps or the exact record
shapes those paths emit — no synthetic phase math that the production
code doesn't produce."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, gluon, observability as obs
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import attribution as attr
from mxnet_tpu.observability import watchdog as wd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools import mxtpu_doctor as doctor  # noqa: E402
from tools import telemetry_report  # noqa: E402
from tools import timeline  # noqa: E402


@pytest.fixture(autouse=True)
def _plane_state():
    """Armed telemetry + a pristine attribution plane per test."""
    obs.set_enabled(True)
    obs.reset()
    attr.set_enabled(True)
    attr.reset()
    yield
    wd.set_enabled(False)
    wd.reset()
    attr.set_enabled(True)
    attr.reset()
    obs.set_enabled(False)
    obs.reset()


def _tiny_loop(steps=6, hybridize=True, width=8):
    """A real fused Gluon train loop; returns (wall_seconds, loss)."""
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(width, activation="relu", in_units=width))
    net.add(nn.Dense(4, in_units=width))
    net.initialize(init=mx.initializer.Xavier())
    if hybridize:
        net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore=None)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    X = mx.nd.array(np.random.RandomState(0).rand(4, width)
                    .astype(np.float32))
    Y = mx.nd.array(np.array([0, 1, 2, 3], dtype=np.float32))

    def one():
        with autograd.record():
            l = loss_fn(net(X), Y)
        l.backward()
        tr.step(4)
        return l

    engine.wait(one().data)  # warmup: compile fwd/bwd/update
    t0 = time.perf_counter()
    l = None
    for _ in range(steps):
        l = one()
    engine.wait(l.data)
    return time.perf_counter() - t0, l


# ---------------------------------------------------------------------------
# the budget decomposition: invariants by construction
# ---------------------------------------------------------------------------

def test_budget_sum_equals_period_and_nonnegative():
    """Every phase >= 0 and sum(phases) == period, exactly — with all
    three feeder kinds active at once (counter delta, host-timed comm,
    single-wait max)."""
    t = time.perf_counter()
    obs.DATA_PREFETCH_WAIT_SECONDS.inc(0.004)
    attr.note_input_wait(0.003)
    attr.note_input_wait(0.001)  # not the max: must not overwrite
    attr.note_comm(0.002)
    rec = attr.record_step(t, t + 0.010)
    assert rec is not None
    for ph in attr.PHASES:
        assert rec[ph] >= 0.0, rec
    assert sum(rec[ph] for ph in attr.PHASES) == \
        pytest.approx(rec["period_s"], rel=1e-9)
    # first record after reset: period is the dispatch span alone
    assert rec["period_s"] == pytest.approx(0.010, rel=1e-6)
    assert rec["input_wait"] == pytest.approx(0.004, rel=1e-6)
    assert rec["comm_exposed"] == pytest.approx(0.002, rel=1e-6)
    assert rec["compute"] == pytest.approx(0.004, rel=1e-6)
    assert rec["input_wait_max_s"] == pytest.approx(0.003, rel=1e-6)


def test_budget_caps_oversized_feeders():
    """A feeder backlog larger than the period cannot push any phase
    negative or the sum past the period (the cap order is the budget
    contract)."""
    t = time.perf_counter()
    obs.DATA_PREFETCH_WAIT_SECONDS.inc(10.0)  # absurd backlog
    attr.note_comm(5.0)
    rec = attr.record_step(t, t + 0.002)
    assert rec["input_wait"] == pytest.approx(0.002, rel=1e-6)
    for ph in ("h2d", "ckpt_overhead", "comm_exposed", "compute",
               "host_gap"):
        assert rec[ph] == 0.0, rec
    assert sum(rec[ph] for ph in attr.PHASES) == \
        pytest.approx(rec["period_s"], rel=1e-9)


def test_superstep_amortizes_per_k():
    """A K-step dispatch publishes per-step amortized phases: the
    per-step sum times K recovers the whole period."""
    t = time.perf_counter()
    rec = attr.record_step(t, t + 0.008, k=4, site="superstep")
    assert rec["k"] == 4
    assert sum(rec[ph] for ph in attr.PHASES) * 4 == \
        pytest.approx(rec["period_s"], rel=1e-9)
    assert rec["compute"] == pytest.approx(0.002, rel=1e-6)


def test_real_loop_phases_sum_bounded_by_wall():
    """Real fused loop: every record's phases sum to its period, and
    the periods together never exceed the measured outer wall (the
    acceptance-criteria inequality, on real records)."""
    attr.reset()
    t_begin = time.perf_counter()  # outer wall covers EVERY record's
    _tiny_loop(steps=6)            # period (warmup included)
    wall = time.perf_counter() - t_begin
    recs = [r for r in attr.records() if r["site"] == "trainer"]
    assert len(recs) >= 6, recs
    for r in recs:
        assert all(r[ph] >= 0.0 for ph in attr.PHASES), r
        assert sum(r[ph] for ph in attr.PHASES) * r["k"] == \
            pytest.approx(r["period_s"], rel=1e-9)
    assert sum(r["period_s"] for r in recs) <= wall * 1.001, \
        (sum(r["period_s"] for r in recs), wall)
    mean = attr.mean_phases(site="trainer", last_n=6)
    assert mean["count"] == 6
    assert mean["step_wall"] > 0


def test_series_gauge_and_trace_span_publish():
    """Each record lands in the lazy last-N series gauge and as a
    ``step.phases`` trace span with per-phase ms args."""
    t = time.perf_counter()
    attr.record_step(t, t + 0.004)
    attr.record_step(t + 0.004, t + 0.009)
    series = obs.STEP_PHASE_LAST.series(phase="compute")
    assert isinstance(series, list) and len(series) == 2, series
    assert series[-1] == pytest.approx(0.005, rel=1e-6)
    spans = [e for e in obs.tracer().events()
             if e.get("name") == "step.phases"]
    assert len(spans) >= 2
    args = spans[-1]["args"]
    assert args["site"] == "trainer"
    assert set(f"{ph}_ms" for ph in attr.PHASES) <= set(args), args
    assert args["period_ms"] == pytest.approx(5.0, rel=1e-4)
    # and the report tool's section renders from those spans
    section = telemetry_report.render_attribution(obs.tracer().events())
    assert "Attribution" in section and "trainer" in section


def test_disarmed_plane_records_nothing():
    """MXTPU_ATTRIBUTION=0 semantics: hot sites skip the plane
    entirely (records stay empty through a real loop)."""
    attr.set_enabled(False)
    attr.reset()
    _tiny_loop(steps=3)
    assert attr.records() == []


# ---------------------------------------------------------------------------
# hot-path hooks: prefetch wait delta series + watchdog detector
# ---------------------------------------------------------------------------

def test_prefetch_wait_delta_series():
    """The per-step DELTA gauge (satellite of the PR-4 running total)
    tracks each boundary's increment, not the cumulative value."""
    t = time.perf_counter()
    obs.DATA_PREFETCH_WAIT_SECONDS.inc(0.004)
    attr.record_step(t, t + 0.010)
    assert obs.DATA_PREFETCH_WAIT_DELTA.value() == \
        pytest.approx(0.004, rel=1e-6)
    obs.DATA_PREFETCH_WAIT_SECONDS.inc(0.001)
    attr.record_step(t + 0.010, t + 0.020)
    assert obs.DATA_PREFETCH_WAIT_DELTA.value() == \
        pytest.approx(0.001, rel=1e-6)


def test_watchdog_input_wait_detector_fires_once():
    """input_wait >= half the step period -> one anomaly per NEW
    record; re-sweeping the same record must not re-fire."""
    wd.reset()
    wd.set_enabled(True)
    t = time.perf_counter()
    obs.DATA_PREFETCH_WAIT_SECONDS.inc(0.008)
    obs.tracer().mark_step()
    attr.record_step(t, t + 0.010)
    wd.check_now()
    assert obs.ANOMALY_TOTAL.value(kind="input_wait") == 1
    wd.check_now()  # same record: stale, no re-fire
    assert obs.ANOMALY_TOTAL.value(kind="input_wait") == 1
    obs.DATA_PREFETCH_WAIT_SECONDS.inc(0.009)
    obs.tracer().mark_step()
    attr.record_step(t + 0.010, t + 0.020)
    wd.check_now()
    assert obs.ANOMALY_TOTAL.value(kind="input_wait") == 2


def test_watchdog_input_wait_ignores_healthy_steps():
    """A small wait fraction (below half the period) never fires."""
    wd.reset()
    wd.set_enabled(True)
    t = time.perf_counter()
    obs.DATA_PREFETCH_WAIT_SECONDS.inc(0.0005)
    obs.tracer().mark_step()
    attr.record_step(t, t + 0.010)
    wd.check_now()
    assert obs.ANOMALY_TOTAL.value(kind="input_wait") == 0


def test_flight_bundle_carries_phase_records():
    """The crash bundle ships the last-N phase records (post-mortem
    'where did the step time go' without a live process)."""
    from mxnet_tpu.observability import flight

    t = time.perf_counter()
    attr.record_step(t, t + 0.004)
    bundle = flight.build_bundle("test")
    assert bundle["phase_records"], bundle.keys()
    rec = bundle["phase_records"][-1]
    assert set(attr.PHASES) <= set(rec), rec


# ---------------------------------------------------------------------------
# the zero-added-dispatch contract (armed plane == free, in dispatches)
# ---------------------------------------------------------------------------

def test_zero_added_device_dispatches_when_armed():
    """The armed attribution plane adds ZERO XLA dispatches per step:
    the same fused loop costs the same dispatch count with the plane
    on and off (host arithmetic only — the tentpole's hard contract)."""
    _tiny_loop(steps=2)  # settle compilation before counting

    d0 = obs.XLA_DISPATCH_TOTAL.total()
    _tiny_loop(steps=5)
    armed = obs.XLA_DISPATCH_TOTAL.total() - d0

    attr.set_enabled(False)
    d0 = obs.XLA_DISPATCH_TOTAL.total()
    _tiny_loop(steps=5)
    disarmed = obs.XLA_DISPATCH_TOTAL.total() - d0
    assert armed == disarmed, (armed, disarmed)


# ---------------------------------------------------------------------------
# mxtpu-doctor: verdict fixtures per bottleneck class
# ---------------------------------------------------------------------------

def _phase_event(site="trainer", k=1, step=1, **phase_ms):
    """One ``step.phases`` span exactly as attribution emits it (args
    are per-step amortized; period covers the whole K-step dispatch)."""
    ms = {f"{ph}_ms": 0.0 for ph in attr.PHASES}
    ms.update({f"{key}_ms": val for key, val in phase_ms.items()})
    period = sum(ms.values()) * k
    return {"name": "step.phases", "cat": "attribution", "ph": "X",
            "ts": 0.0, "dur": period * 1e3, "pid": 1, "tid": 1,
            "args": {"site": site, "k": k, "step": step,
                     "period_ms": period, "dispatch_ms": period, **ms}}


def _cost_event(site="trainer_fused", ai=2.0):
    return {"name": "introspect.cost", "cat": "introspect", "ph": "i",
            "ts": 0.0, "pid": 1, "tid": 1,
            "args": {"site": site, "arith_intensity": ai,
                     "peak_tflops": 197.0, "peak_hbm_gbs": 819.0}}


def _serve_event(model="m", **phase_ms):
    args = {"model": model, "req": 1}
    args.update({f"{key}_ms": val for key, val in phase_ms.items()})
    return {"name": "serving.request", "cat": "serving", "ph": "X",
            "ts": 0.0, "dur": 1.0, "pid": 1, "tid": 2, "args": args}


def _verdict(events, site):
    report = doctor.diagnose(events)
    for v in report["training"]:
        if v["site"] == site:
            return v
    raise AssertionError((site, report))


def test_doctor_input_bound_verdict():
    events = [_phase_event(input_wait=4.0, compute=4.0, host_gap=0.5,
                           step=i) for i in range(5)]
    v = _verdict(events, "trainer")
    assert v["verdict"] == "input_bound"
    assert any("input_wait" in e for e in v["evidence"]), v
    assert "MXTPU_DEVICE_PREFETCH" in v["recipe"]


def test_doctor_comm_bound_verdict():
    events = [_phase_event(site="spmd_staged", comm_exposed=3.0,
                           compute=6.0, host_gap=1.0, step=i)
              for i in range(5)]
    v = _verdict(events, "spmd_staged")
    assert v["verdict"] == "comm_bound"
    assert "MXTPU_OVERLAP" in v["recipe"]


def test_doctor_host_bound_verdict():
    events = [_phase_event(host_gap=5.0, compute=3.0, step=i)
              for i in range(5)]
    v = _verdict(events, "trainer")
    assert v["verdict"] == "host_bound"
    assert "MXTPU_SUPERSTEP_K" in v["recipe"]


def test_doctor_roofline_split_memory_vs_flops():
    """Compute-dominated sites split at the roofline ridge when a cost
    record is present, and default to flops-bound (with an explicit
    evidence line) when it is not."""
    compute = [_phase_event(compute=9.0, host_gap=1.0, step=i)
               for i in range(4)]
    v = _verdict(compute + [_cost_event(ai=2.0)], "trainer")
    assert v["verdict"] == "compute_memory_bound", v
    v = _verdict(compute + [_cost_event(ai=500.0)], "trainer")
    assert v["verdict"] == "compute_flops_bound", v
    v = _verdict(compute, "trainer")  # no cost analysis in the dump
    assert v["verdict"] == "compute_flops_bound"
    assert any("no cost-analysis" in e for e in v["evidence"]), v


def test_doctor_serving_verdicts():
    queuey = [_serve_event(queue=6.0, batch=2.0, dispatch=1.0,
                           slice=0.2) for _ in range(4)]
    report = doctor.diagnose(queuey)
    assert report["serving"][0]["verdict"] == "serving_queue_bound"
    dispatchy = [_serve_event(queue=0.5, batch=0.2, dispatch=7.0,
                              slice=0.2) for _ in range(4)]
    report = doctor.diagnose(dispatchy)
    assert report["serving"][0]["verdict"] == "compute_flops_bound"


def test_doctor_ranks_unhealthy_first():
    """The top verdict is the dominant bottleneck, not whichever site
    sorts first alphabetically."""
    events = [_phase_event(site="a_healthy", compute=9.7, host_gap=0.1,
                           input_wait=0.1, step=i) for i in range(4)]
    events += [_cost_event(site="a_healthy", ai=500.0)]
    events += [_phase_event(site="z_starved", input_wait=8.0,
                            compute=2.0, step=i) for i in range(4)]
    report = doctor.diagnose(events)
    assert report["top"]["site"] == "z_starved"
    assert report["top"]["verdict"] == "input_bound"


def _pipeline_event(schedule="gpipe", bubble=0.3, ticks=22, stash=8):
    """One ``pipeline.schedule`` instant as record_pipeline_schedule
    emits it at step-build time."""
    return {"name": "pipeline.schedule", "cat": "parallel", "ph": "i",
            "ts": 0.0, "pid": 1, "tid": 1,
            "args": {"schedule": schedule, "bubble_fraction": bubble,
                     "ticks": ticks, "stash_slots": stash}}


def test_doctor_pipeline_bubble_bound_verdict():
    """A fat measured bubble joined with compute-dominated phase spans
    yields pipeline_bubble_bound: the host books schedule idle as
    device compute, so the roofline verdict alone would mislead."""
    events = [_phase_event(compute=9.0, host_gap=1.0, step=i)
              for i in range(4)]
    events += [_pipeline_event(schedule="gpipe", bubble=0.273)]
    report = doctor.diagnose(events)
    assert report["pipeline"], report
    v = report["pipeline"][0]
    assert v["verdict"] == "pipeline_bubble_bound"
    assert v["schedule"] == "gpipe"
    assert abs(v["bubble_fraction"] - 0.273) < 1e-9
    # the join: evidence names the compute-dominated site's share
    assert any("compute-bound" in e for e in v["evidence"]), v["evidence"]
    assert "MXTPU_PIPELINE" in v["recipe"]
    # phase-bound verdicts outrank it; with only compute-flops sites
    # in the trace, the bubble is the actionable top verdict
    assert report["top"]["verdict"] == "pipeline_bubble_bound"
    rendered = doctor.render(report)
    assert "pipeline_bubble_bound" in rendered


def test_doctor_pipeline_bubble_below_threshold_silent():
    """A tuned interleaved schedule (bubble under the bound) emits no
    pipeline verdict, and an input-bound site still wins top."""
    events = [_phase_event(input_wait=8.0, compute=2.0, step=i)
              for i in range(4)]
    events += [_pipeline_event(schedule="interleaved", bubble=0.059,
                               ticks=34, stash=4)]
    report = doctor.diagnose(events)
    assert report["pipeline"] == []
    assert report["top"]["verdict"] == "input_bound"
    # over threshold but a starved input pipeline still outranks it
    report2 = doctor.diagnose(events + [_pipeline_event(bubble=0.4)])
    assert report2["pipeline"]
    assert report2["top"]["verdict"] == "input_bound"


def test_doctor_cli_seeded_scenarios(tmp_path):
    """The acceptance pair, end-to-end through the REAL plumbing: an
    input-starved loop and a staged-comm loop, recorded by attribution
    itself, dumped to JSONL, diagnosed by the CLI."""
    base = time.perf_counter()
    for i in range(8):  # starved: waits dominate each 10 ms period
        obs.DATA_PREFETCH_WAIT_SECONDS.inc(0.006)
        attr.record_step(base + i * 0.010, base + i * 0.010 + 0.004)
    attr.reset()  # scenario boundary: the idle
    # gap between the two loops must not attribute as a giant host_gap
    for i in range(8):  # staged comm: the host-timed comm leg dominates
        attr.note_comm(0.005)
        attr.record_step(base + 1 + i * 0.010,
                         base + 1 + i * 0.010 + 0.008,
                         site="spmd_staged")
    trace = tmp_path / "trace.jsonl"
    obs.dump_jsonl(str(trace))
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxtpu_doctor.py"),
         "--json", str(trace)],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    report = json.loads(res.stdout)
    assert report["format"] == "mxtpu-doctor-v1" and "top" in report
    assert all(v["verdict"] in doctor.RECIPES and v["recipe"]
               for v in report["training"])
    verdicts = {v["site"]: v["verdict"] for v in report["training"]}
    assert verdicts["trainer"] == "input_bound", report
    assert verdicts["spmd_staged"] == "comm_bound", report
    # human rendering also resolves (no --json)
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxtpu_doctor.py"),
         str(trace)],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    assert "input_bound" in res.stdout and "comm_bound" in res.stdout


# ---------------------------------------------------------------------------
# mxtpu-doctor --env (the ported legacy diagnose tool)
# ---------------------------------------------------------------------------

def test_doctor_env_report():
    report = doctor.env_report()
    assert report["format"] == "mxtpu-doctor-env-v1"
    assert report["jax"]["backend"]
    assert report["mxnet_tpu"]["ops"] > 400
    assert isinstance(report["warnings"], list)
    text = doctor.render_env(report)
    assert "mxtpu-doctor --env:" in text and "jax" in text


# ---------------------------------------------------------------------------
# tools/timeline.py: valid multi-track chrome://tracing export
# ---------------------------------------------------------------------------

def _timeline_fixture():
    return [
        _phase_event(input_wait=2.0, compute=3.0, host_gap=1.0, k=2),
        {"name": "serving.batch", "cat": "serving", "ph": "X", "ts": 50.0,
         "dur": 30.0, "pid": 9, "tid": 9, "id": 7, "args": {}},
        {"name": "serving.request", "cat": "serving", "ph": "X",
         "ts": 60.0, "dur": 10.0, "pid": 9, "tid": 10,
         "args": {"model": "m", "parent": 7}},
        {"name": "anomaly", "cat": "watchdog", "ph": "i", "ts": 70.0,
         "args": {"kind": "input_wait"}},
    ]


def test_timeline_is_valid_chrome_trace():
    doc = timeline.build_timeline(_timeline_fixture())
    text = json.dumps(doc)  # must serialize round-trip
    doc2 = json.loads(text)
    evs = doc2["traceEvents"]
    assert isinstance(evs, list) and evs
    for ev in evs:
        assert "ph" in ev and "pid" in ev, ev
        if ev["ph"] in ("X", "i", "s", "f"):
            assert isinstance(ev["ts"], (int, float)), ev
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"train loop", "attribution", "prefetcher", "collectives",
            "checkpoint writer", "serving batcher"} <= names, names


def test_timeline_expands_phase_slices_and_flows():
    doc = timeline.build_timeline(_timeline_fixture())
    evs = doc["traceEvents"]
    slices = [e for e in evs if e.get("cat") == "attribution.phase"]
    got = {e["name"]: e["dur"] for e in slices}
    # per-step amortized args * k=2 lay the slices across the period
    assert got["input_wait"] == pytest.approx(2.0 * 1e3 * 2)
    assert got["compute"] == pytest.approx(3.0 * 1e3 * 2)
    assert "host_gap" in got and "h2d" not in got  # zero phases skipped
    span_dur = [e for e in evs if e.get("name") == "step.phases"][0]["dur"]
    assert sum(got.values()) == pytest.approx(span_dur, rel=1e-6)
    flows = [e for e in evs if e.get("cat") == "correlation"]
    assert {e["ph"] for e in flows} == {"s", "f"}, flows
    # instants carry a scope, not a duration
    inst = [e for e in evs if e.get("name") == "anomaly"][0]
    assert inst["s"] == "t" and "dur" not in inst


def test_timeline_cli_roundtrip(tmp_path):
    trace = tmp_path / "trace.jsonl"
    with open(trace, "w") as f:
        for ev in _timeline_fixture():
            f.write(json.dumps(ev) + "\n")
    out = tmp_path / "out.json"
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "timeline.py"),
         str(trace), "-o", str(out)],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    doc = json.load(open(out))
    assert doc["traceEvents"], doc
    # the tool also reads its own output (chrome-trace shaped input)
    assert timeline.load_events(str(out))
