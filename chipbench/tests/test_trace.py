"""The trace reduction against a small trace recorded on a v5e
(``tools/record_fixture.py``: a jitted program with one flash-attention
call, run under the benchmark's spans) and against hand-made events."""

import os
import re

import pytest

from chipbench.harness import trace as TR

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "fixture_1chip.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return TR.Trace(FIXTURE)


def test_window_is_the_benchmarks_own_span(recorded):
    assert len(recorded.devices) == 1
    assert recorded.window_s == pytest.approx(0.010063, rel=1e-3)


def test_busy_is_the_union_of_the_operations(recorded):
    ops = recorded.devices[0].ops
    by_hand = sum(e - s for _, s, e in ops) / 1e9  # none overlap here
    assert recorded.busy_s() == pytest.approx(by_hand, rel=1e-9)
    assert 0 < recorded.busy_s() < recorded.window_s
    assert recorded.busy_s() == pytest.approx(2.3736e-05, rel=1e-3)


def test_kernel_time_by_name(recorded):
    spent = recorded.op_seconds(re.compile("tpu_custom_call"))
    assert len(spent) == 1  # the one Pallas call of the program
    assert sum(spent.values()) == pytest.approx(2.0245e-05, rel=1e-3)
    assert recorded.op_calls(re.compile("tpu_custom_call")) == 2
    label, seconds = recorded.top_ops(1)[0]
    assert label == "custom-call(tpu_custom_call) bf16[8,256,64]"
    assert seconds == pytest.approx(2.0245e-05, rel=1e-3)


def test_programs_launched_and_runs_of_the_main_one(recorded):
    assert recorded.module_launches() == 2
    assert recorded.module_runs() == 2
    assert recorded.module_runs(re.compile("jit_body")) == 2
    assert recorded.module_runs(re.compile("no_such_program")) == 0


def test_idle_gaps_are_named_by_the_open_span(recorded):
    gaps = dict(recorded.idle_gaps())
    assert set(gaps) <= {"pause", "step", "wait_last",
                         "outside the benchmark's spans"}
    assert sum(gaps.values()) == pytest.approx(
        recorded.window_s - recorded.busy_s(), rel=1e-6)
    assert max(gaps, key=gaps.get) == "pause"  # the loop sleeps there


def _by_hand(ops_by_device, window, spans=()):
    t = TR.Trace.__new__(TR.Trace)
    t.devices = []
    for i, ops in enumerate(ops_by_device):
        d = TR.DeviceTrace(i)
        d.ops = list(ops)
        d.modules = []
        t.devices.append(d)
    t.window = window
    t.host_spans = list(spans)
    return t


def test_union_by_hand():
    length, merged = TR.union_length([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert length == 35 and merged == [[0, 20], [30, 45]]


def test_busy_time_is_the_chips_average_by_hand():
    t = _by_hand(
        [[("%fusion.1 = f32[8] fusion(...)", 0, 14),
          ("%fusion.2 = f32[8] fusion(...)", 10, 20)],
         [("%fusion.1 = f32[8] fusion(...)", 0, 30)]],
        (0, 100))
    assert t.busy_s() == pytest.approx((20 + 30) / 2 / 1e9)


def test_labels():
    hlo = ('%transpose_jvp.23 = (bf16[768,128,64]{2,1,0:T(8,128)(2,1)}, '
           'bf16[768,128,64]{2,1,0}) custom-call(bf16[768,128,64]{2,1,0} '
           '%bitcast.2645), custom_call_target="tpu_custom_call"')
    assert TR.op_label(hlo) == "custom-call(tpu_custom_call) bf16[768,128,64]"
    assert TR.op_label("%fusion.4 = f32[64,128]{1,0:T(8,128)S(1)} "
                       "fusion(bf16[64] %x), kind=kLoop") == "fusion f32[64,128]"
    assert TR.base_name("jit_step(11937236725742203718)") == "jit_step"
    assert TR.base_name("%fusion.689 = f32[2] fusion()") == "fusion"
