"""The ``jamba_lm`` family through the loop kind ``generation_server_rows``
at a tiny size on the CPU, through ``run.drive``: the program reads
``correct`` true; the control and three planted faults (a slot's state
not zeroed on re-use, the convolution's window dropped between prefill
and decode, the selective state dropped between prefill and decode)
each read ``correct`` false; a program without Mamba layers ends in
set-up; the glue's counts against counts made by hand."""

import argparse
import json
import math
import os
import time

import pytest

from chipbench.models import jamba_lm
from helpers import PEAKS, ROOT, tiny

CELL = "jamba2_3b.serve_reasoning"


@pytest.fixture(autouse=True)
def chunks_of_four(monkeypatch):
    """A tiny prompt crosses chunks of the prefill scan."""
    from mxnet_tpu.serving import decoder

    monkeypatch.setattr(decoder, "MAMBA_CHUNK", 4)


def drive(*, seed=3, seconds=1.0, trace=0):
    """One tiny cell of this family; the result object."""
    import jax

    from chipbench import run as R

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if CELL in m.get("workloads", []):
                m["workloads"] = m["workloads"] + ["tiny.cell"]
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    return R.drive(args, {"name": "tiny.cell", "chips": 1}, bench,
                   tiny("jamba_tiny.serve"), tiny("jamba_tiny"),
                   jax.devices()[:1], PEAKS, time.perf_counter())


def _fails(result, *names):
    assert not result["correct"], result["compared"]
    over = {n for n, c in result["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over & set(names), (over, result["compared"])


def test_the_jamba_server_runs_and_is_correct():
    r = drive()
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] == 20  # 20/s for 1 s
    assert r["metrics"]["tpot_ms_mean"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    for c in r["compared"].values():
        assert math.isfinite(c["value"]) and c["value"] <= c["limit"]
    c = r["counters"]
    # the paged pool is the cache's main part beside the state store
    assert c["kv_blocks"] == 160
    assert 0 < c["kv_blocks_in_use_mean"] <= c["kv_blocks_in_use_max"]
    assert int(r["compared"]["logit_gap"]["at"].split()[0]) > 0  # tokens


def test_a_traced_run_reports_the_counter_metrics_and_no_roofline():
    r = drive(trace=1)
    assert r["correct"], r["compared"]
    assert {"tpot_ms_p95", "decode_slots_active_share", "step_mfu.serve",
            "compiles_in_window.serve", "kv_pool_high_water_share.serve",
            "engine_host_work_share.serve"} <= set(r["metrics"])
    # no TPU planes in a CPU trace: nothing to read, nothing reported
    assert "mamba_decode_roofline" not in r["metrics"]


def test_a_whole_run_on_the_control_is_not_correct(monkeypatch):
    from chipbench.loops import generation_server_rows as gsr
    from chipbench.tools import readings

    monkeypatch.setattr(gsr.Loop, "gaps", gsr.Loop.gaps)
    readings.put_control_in_place("generation_server_rows", "fp8")
    _fails(drive(), "logit_gap")


def test_a_state_not_zeroed_on_reuse_is_not_correct(monkeypatch):
    from mxnet_tpu.serving import kvcache

    real = kvcache.StateStore._clear
    built = []

    def once(self, slot):  # the constructor's own call, then never
        if self not in built:
            built.append(self)
            real(self, slot)

    monkeypatch.setattr(kvcache.StateStore, "_clear", once)
    _fails(drive(), "logit_gap")


def test_the_window_dropped_after_prefill_is_not_correct(monkeypatch):
    from mxnet_tpu.ops import mamba

    real = mamba.causal_conv

    def dropped(x, window, w, b, length):
        y, _ = real(x, window, w, b, length)
        return y, window  # the slot keeps what it held before the prompt

    monkeypatch.setattr(mamba, "causal_conv", dropped)
    _fails(drive(), "logit_gap")


def test_the_state_dropped_after_prefill_is_not_correct(monkeypatch):
    from mxnet_tpu.ops import mamba

    real = mamba.selective_scan

    def dropped(x, dt, A, B, C, D, state, length, chunk):
        y, _ = real(x, dt, A, B, C, D, state, length, chunk)
        return y, state

    monkeypatch.setattr(mamba, "selective_scan", dropped)
    _fails(drive(), "logit_gap")


def test_a_program_without_mamba_layers_ends_in_setup(monkeypatch):
    from mxnet_tpu.serving import decoder

    monkeypatch.setattr(decoder, "KINDS", ("attention", "retention",
                                           "latent"))
    with pytest.raises(RuntimeError, match="no Mamba"):
        jamba_lm.check_program()


def _jamba():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "jamba2_3b.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one():
    cfg = _jamba()
    from chipbench.references import jamba_lm as ref

    kinds = ref.layer_kinds(cfg)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert [(k, n) for k, _, n in ref.runs(cfg)] == [
        ("mamba", 7), ("attention", 1), ("mamba", 13), ("attention", 1),
        ("mamba", 6)]
    assert cfg["reduced"] == [] and cfg["num_experts"] == 1


def test_request_flops_by_hand():
    cfg = _jamba()
    d, ff, v, di = 2560, 8192, 65536, 5120
    mlp = 3 * d * ff
    # in 2560 -> 10240, x 5120 -> 192, dt 160 -> 5120, out 5120 -> 2560
    mamba = d * 2 * di + di * 192 + 160 * di + di * d + mlp
    assert mamba - mlp == 41_123_840
    # q and o 2560 x 2560, k and v 2560 x 128
    attention = 2 * d * d + 2 * d * 128 + mlp
    scan = 2 * 4 * di + 6 * 16 * di
    per_token = 26 * (2 * mamba + scan) + 2 * 2 * attention
    # a prompt of 3 and an answer of 2: four tokens go through the
    # layers, each attends to itself and what precedes it (1 + 2 + 3 +
    # 4 keys), two tokens are produced
    want = 4 * per_token + 2 * 4 * 20 * 128 * 10 + 2 * 2 * d * v
    assert jamba_lm.request_forward_flops(cfg, 3, 2) == want


def test_mamba_decode_calls_by_hand():
    cfg = _jamba()
    # 100 chunks of 8 steps made 200,040 tokens beside 40 prefills'
    # own: 250 live slots a step
    counters = {"tokens_generated": 200_040, "prefills": 40,
                "decode_chunks": 100, "chunk": 8}
    calls = jamba_lm.mamba_decode_calls_per_step(cfg, {}, 2, counters)
    assert len(calls) == 2 * 26
    assert [k for k, _, _ in calls[:2]] == ["conv", "scan"]
    # a slot's window (3 x 5120 float32) each way and its two rows
    assert calls[0][2] == 250 * (2 * 3 * 5120 + 2 * 5120) * 4
    # its state (16 x 5120 float32) each way, three rows in and out,
    # B and C
    assert calls[1][2] == 250 * (2 * 16 * 5120 + 3 * 5120 + 32) * 4
    # both together: 0.80 MB a slot a layer
    assert round((calls[0][2] + calls[1][2]) / 250 / 1e6, 2) == 0.88
    assert jamba_lm.mamba_decode_calls_per_step(cfg, {}, 2, {}) == []
