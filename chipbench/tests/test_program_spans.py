"""The readers over the program's own spans, through ``run.drive`` at a
tiny size on the CPU, and ``kernel_roofline_kind`` against
``kernel_roofline`` over the recorded v5e trace."""

import json
import os
import re
import types

import pytest
from helpers import PEAKS, drive_tiny

from chipbench.harness import flops as F
from chipbench.harness import trace as TR
from chipbench.readers import kernel_roofline, kernel_roofline_kind
from chipbench.readers import program_spans

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "fixture_1chip.xplane.pb")
SPAN_METRICS = {"engine_host_work_share.serve",
                "kv_pool_high_water_share.serve",
                "host_call_ms_per_step.train"}


def _ring():
    from mxnet_tpu import observability as obs

    return obs.tracer()


def _traced_part(result):
    """What a reader sees of the run that gave ``result``."""
    return types.SimpleNamespace(trace=types.SimpleNamespace(
        window_s=result["device"]["window_s"]))


def test_a_traced_server_reports_its_scheduler_and_its_pool():
    r = drive_tiny("gpt_tiny", "gpt_tiny.serve", seconds=1.0, trace=1)
    assert r["correct"], r["compared"]
    m = r["metrics"]
    assert 0 < m["engine_host_work_share.serve"]["value"] <= 100
    assert 0 < m["kv_pool_high_water_share.serve"]["value"] <= 100
    assert "host_call_ms_per_step.train" not in m
    # the pool's mark in the spans is no lower than the loop's samples
    c = r["counters"]
    assert m["kv_pool_high_water_share.serve"]["value"] \
        >= 100.0 * c["kv_blocks_in_use_max"] / c["kv_blocks"] - 1e-9
    gen = program_spans.window_events(_traced_part(r), "generation")
    assert {"gen.admit", "gen.prefill", "gen.prefill.device", "gen.chunk",
            "gen.chunk.prep", "gen.chunk.device", "gen.chunk.deliver"} \
        <= {e["name"] for e in gen}
    assert {e["name"] for e in program_spans.window_events(
        _traced_part(r), "request")} \
        == {"req", "req.queue", "req.prefill", "req.decode"}


def test_a_traced_training_run_reports_the_host_call():
    r = drive_tiny("bert_tiny", "bert_tiny.spmd", trace=1)
    assert r["correct"], r["compared"]
    assert r["metrics"]["host_call_ms_per_step.train"]["value"] > 0
    assert not SPAN_METRICS - {"host_call_ms_per_step.train"} \
        & set(r["metrics"])
    # no kernel of that name in a CPU trace: left out, never 0
    assert not {"flash_fwd_roofline", "flash_bwd_dq_roofline",
                "flash_bwd_dkv_roofline"} & set(r["metrics"])
    train = program_spans.window_events(_traced_part(r), "train")
    steps = [e for e in train if e["name"] == "spmd.step"]
    own = program_spans.self_times(train)
    assert steps and all(0 <= own[e["id"]] <= e["dur"] for e in steps)


@pytest.mark.parametrize("cfg,cell", [("gpt_tiny", "gpt_tiny.serve"),
                                      ("bert_tiny", "bert_tiny.spmd")])
def test_an_untraced_run_reports_none_and_leaves_the_ring_empty(cfg, cell):
    _ring().clear()
    r = drive_tiny(cfg, cell, seconds=0.5, trace=0)
    assert r["correct"], r["compared"]
    assert not SPAN_METRICS & set(r["metrics"])
    assert len(_ring()) == 0


def test_two_traced_runs_in_one_process_do_not_mix():
    drive_tiny("gpt_tiny", "gpt_tiny.serve", seconds=0.5, trace=1)
    r = drive_tiny("bert_tiny", "bert_tiny.spmd", trace=1)
    # the ring still holds the server's spans; the reader clips to the
    # newest run's traced part
    assert any(e["cat"] == "generation" for e in _ring().events())
    assert program_spans.window_events(_traced_part(r), "generation") == []
    assert program_spans.window_events(_traced_part(r), "train")
    # without a trace to clip by, the whole ring
    assert program_spans.window_events(None, "generation")


def test_self_time_is_the_duration_less_what_the_children_cover():
    def ev(i, ts, dur, parent=None):
        return {"id": i, "ts": ts, "dur": dur, "ph": "X", "tid": 1,
                "args": {} if parent is None else {"parent": parent}}

    own = program_spans.self_times(
        [ev(1, 0.0, 100.0), ev(2, 10.0, 30.0, 1), ev(3, 30.0, 30.0, 1),
         ev(4, 35.0, 5.0, 3), ev(5, 90.0, 40.0, 1)])
    # children cover 10-60 (they overlap) and 90-100 (clipped to the parent)
    assert own == {1: 40.0, 2: 30.0, 3: 25.0, 4: 5.0, 5: 40.0}
    assert program_spans.covered([(5, 8), (0, 2), (7, 20)], 1, 10) == 6


# -- kernel_roofline_kind -------------------------------------------------------

CALLS = [("fwd",) + F.attention_call_cost(
             batch=2, heads=4, q_len=256, k_len=256, head_dim=64, itemsize=2,
             passes=2),
         ("bwd_dq", 3e9, 1e6), ("bwd_dkv", 1e6, 4e8)]


def _stand_in(trace):
    glue = types.SimpleNamespace(calls_per_run=lambda *a: list(CALLS))
    return types.SimpleNamespace(
        trace=trace, model=glue, counters={}, cfg={}, peaks=PEAKS, chips=1,
        workload={"dtype": "bfloat16", "shapes": {}})


def _by_hand_trace():
    t = TR.Trace.__new__(TR.Trace)
    d = TR.DeviceTrace(0)
    line = ('%{0}.{1} = bf16[8,256,64] custom-call(%x), '
            'custom_call_target="tpu_custom_call"')
    d.ops = [(line.format("mxtpu_flash_fwd", 1), 0, 400),
             (line.format("mxtpu_flash_bwd_dq", 1), 400, 1400),
             (line.format("mxtpu_flash_bwd_dkv", 2), 1400, 3400),
             ("%fusion.7 = f32[8] fusion(%y)", 3400, 3500),
             # consumers that take a kernel's result as an operand
             ("%fusion.8 = bf16[8,256,64] fusion(%mxtpu_flash_fwd.1, "
              "%mxtpu_flash_bwd_dq.1)", 3500, 3700),
             ("%get-tuple-element.9 = bf16[8,256,64] get-tuple-element("
              "%mxtpu_flash_bwd_dkv.2), index=0", 3700, 3750)]
    d.modules = [("jit_step(1)", 0, 3750)]
    t.devices, t.window, t.host_spans = [d], (0, 4000), []
    return t


@pytest.mark.parametrize("trace", ["recorded", "by_hand"])
def test_every_kind_reads_what_kernel_roofline_reads(trace):
    run = _stand_in(TR.Trace(FIXTURE) if trace == "recorded"
                    else _by_hand_trace())
    spec = {"match": "tpu_custom_call", "calls": "calls_per_run"}
    whole = kernel_roofline.read(spec, run)
    assert whole is not None and whole > 0
    assert kernel_roofline_kind.read(
        dict(spec, kinds=["fwd", "bwd_dq", "bwd_dkv"]), run) == whole
    assert run.model.calls_per_run() == CALLS  # the run is left as it was


@pytest.mark.parametrize("kind,bound", [("fwd", "memory"),
                                        ("bwd_dq", "compute"),
                                        ("bwd_dkv", "memory")])
def test_one_kind_is_its_least_time_over_its_kernels_time(kind, bound):
    run = _stand_in(_by_hand_trace())
    # the metric's own pattern: anchored to the instruction's name, so
    # the consumers of the kernel's result are not counted as the kernel
    with open(os.path.join(os.path.dirname(FIXTURE), "..", "..", "metrics",
                           f"flash_{kind}_roofline.json")) as f:
        match = json.load(f)["match"]
    assert sum(run.trace.op_seconds(
        re.compile("mxtpu_flash_" + kind)).values()) > sum(
            run.trace.op_seconds(re.compile(match)).values())
    _, flops, nbytes = next(c for c in CALLS if c[0] == kind)
    least, which = F.roofline_seconds(flops, nbytes, PEAKS)
    assert which == bound
    spent = sum(run.trace.op_seconds(re.compile(match)).values())
    assert spent in (400e-9, 1000e-9, 2000e-9)  # that kernel's alone
    got = kernel_roofline_kind.read(
        {"match": match, "calls": "calls_per_run", "kinds": [kind]}, run)
    assert got == pytest.approx(100.0 * least / spent)


def test_one_kind_over_the_recorded_trace():
    # one forward kernel, two runs of its program: the forward kind
    # alone against every operation of that target
    rec = _stand_in(TR.Trace(FIXTURE))
    spec = {"match": "tpu_custom_call", "calls": "calls_per_run"}
    fwd = kernel_roofline_kind.read(dict(spec, kinds=["fwd"]), rec)
    fl, by = CALLS[0][1:]
    assert fwd == pytest.approx(
        100.0 * 2 * F.roofline_seconds(fl, by, PEAKS)[0] / 2.0245e-05,
        rel=1e-3)


def test_a_kind_with_nothing_to_read_reads_nothing():
    run = _stand_in(_by_hand_trace())
    assert kernel_roofline_kind.read(
        {"match": "mxtpu_flash_fwd", "calls": "calls_per_run",
         "kinds": ["no_such_kind"]}, run) is None
    assert kernel_roofline_kind.read(
        {"match": "no_such_kernel", "calls": "calls_per_run",
         "kinds": ["fwd"]}, run) is None
    assert kernel_roofline_kind.read(
        {"match": "mxtpu_flash_fwd", "calls": "no_such_function",
         "kinds": ["fwd"]}, run) is None


# -- tools/host_gaps.py ------------------------------------------------------------

def test_idle_gaps_go_to_the_innermost_span_of_any_thread():
    from chipbench.tools import host_gaps as HG

    sched = [("gen.admit", 0, 100), ("gen.prefill", 10, 60),
             ("gen.prefill.device", 20, 50), ("gen.chunk", 100, 300),
             ("gen.chunk.device", 150, 250), ("gen.idle", 400, 500)]
    assert HG.innermost_segments(sched) == [
        (0, 10, "gen.admit"), (10, 20, "gen.prefill"),
        (20, 50, "gen.prefill.device"), (50, 60, "gen.prefill"),
        (60, 100, "gen.admit"), (100, 150, "gen.chunk"),
        (150, 250, "gen.chunk.device"), (250, 300, "gen.chunk"),
        (400, 500, "gen.idle")]
    other = [("spmd.step", 0, 1000)]
    gaps = [(12, 16), (30, 40), (45, 110), (320, 380), (440, 460),
            (990, 3000)]
    idle = dict(HG.idle_by_span(gaps, {"a": sched, "b": other}))
    # a gap is split over the spans it lasts through; the latest to
    # start wins between threads; none open: said so
    assert idle == pytest.approx({
        "gen.prefill": 4e-9 + 10e-9, "gen.prefill.device": 10e-9 + 5e-9,
        "gen.admit": 40e-9, "gen.chunk": 10e-9,
        "spmd.step": 60e-9 + 10e-9, "gen.idle": 20e-9,
        HG.NO_SPAN: 2000e-9})
    # what a call-and-wait span holds around the device's own work
    t = _by_hand_trace()
    t.devices[0].ops = [("%a = f32[8] fusion()", 23_000_000, 30_000_000),
                        ("%b = f32[8] fusion()", 31_000_000, 48_500_000)]
    lat = HG.device_span_latency(
        t, {"a": [(n, s * 1_000_000, e * 1_000_000) for n, s, e in sched]})
    assert lat == {"gen.prefill.device": (1, pytest.approx(3.0),
                                          pytest.approx(1.5))}
    counts, offsets = HG.parity(
        [{"name": "gen.idle", "ts": 5.0}, {"name": "gen.idle", "ts": 9.0},
         {"name": "gen.chunk", "ts": 1.0}],
        {"a": [("gen.idle", 7000, 7500), ("gen.idle", 11000, 11500)]})
    assert counts == {"gen.chunk": [1, 0], "gen.idle": [2, 2]}
    assert offsets == [2.0, 2.0]
