"""The ``retention_lm`` family and the loop kind ``generation_server_rows``
at a tiny size on the CPU, through ``run.drive``: the program reads
``correct`` true; the control and three planted faults (a slot's state
not zeroed on re-use, the state entering a chunk dropped, the gate fixed
at 1) each read ``correct`` false; the glue's counts against counts made
by hand; the reference's rows against its whole logits."""

import argparse
import json
import math
import os
import time

import numpy as np
import pytest

from chipbench.models import retention_lm
from helpers import PEAKS, ROOT, tiny


@pytest.fixture(autouse=True)
def chunks_of_eight(monkeypatch):
    """Prefill's chunk is the program's constant, 256; at 8 a tiny
    prompt still crosses chunks."""
    from mxnet_tpu.serving import decoder

    monkeypatch.setattr(decoder, "RETENTION_CHUNK", 8)


def drive(*, seed=3, seconds=1.0, trace=0):
    """One tiny cell of the new kind; the result object. (``helpers.
    drive_tiny`` knows the two kinds the first benchmark had.)"""
    import jax

    from chipbench import run as R

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "brumby_14b.serve_long_doc" in m.get("workloads", []):
                m["workloads"] = m["workloads"] + ["tiny.cell"]
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    return R.drive(args, {"name": "tiny.cell", "chips": 1}, bench,
                   tiny("brumby_tiny.serve"), tiny("brumby_tiny"),
                   jax.devices()[:1], PEAKS, time.perf_counter())


def _fails(result, *names):
    assert not result["correct"], result["compared"]
    over = {n for n, c in result["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over & set(names), (over, result["compared"])


def test_the_retention_server_runs_and_is_correct():
    r = drive()
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] == 20  # 20/s for 1 s
    assert r["metrics"]["tpot_ms_mean"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    for c in r["compared"].values():
        assert math.isfinite(c["value"]) and c["value"] <= c["limit"]
    c = r["counters"]
    # a block is one sequence's state: three slots and the null slot
    assert c["kv_blocks"] == 4
    assert 0 < c["kv_blocks_in_use_mean"] <= c["kv_blocks_in_use_max"] <= 3
    assert int(r["compared"]["logit_gap"]["at"].split()[0]) > 0  # tokens


def test_a_traced_run_reports_the_counter_metrics_and_no_roofline():
    r = drive(trace=1)
    assert r["correct"], r["compared"]
    assert {"tpot_ms_p95", "decode_slots_active_share", "step_mfu.serve",
            "compiles_in_window.serve", "kv_pool_high_water_share.serve",
            "engine_host_work_share.serve"} <= set(r["metrics"])
    # no TPU planes in a CPU trace: nothing to read, nothing reported
    assert "retention_decode_roofline" not in r["metrics"]
    assert r["metrics"]["kv_pool_high_water_share.serve"]["value"] <= 75.0


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


def test_the_state_entering_a_chunk_dropped_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from mxnet_tpu.ops import retention

    real = retention.power_retention_chunked

    def dropped(q, k, v, log_g, state, length, chunk=256):
        zero = tuple(jnp.zeros_like(a) for a in state)
        outs = []
        for start in range(0, q.shape[0], chunk):
            piece = slice(start, start + chunk)
            o, state = real(q[piece], k[piece], v[piece], log_g[piece],
                            zero, jnp.clip(length - start, 0, chunk), chunk)
            outs.append(o)
        return jnp.concatenate(outs), state

    monkeypatch.setattr(retention, "power_retention_chunked", dropped)
    _fails(drive(), "logit_gap")


def test_the_gate_fixed_at_one_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from mxnet_tpu.serving import TransformerDecoderLM

    monkeypatch.setattr(
        TransformerDecoderLM, "_log_gate", staticmethod(
            lambda lyr, h: jnp.zeros(h.shape[:-1] + (lyr["bg"].shape[-1],),
                                     jnp.float32)))
    _fails(drive(), "logit_gap")


def test_the_reference_rows_are_rows_of_its_whole_logits():
    from chipbench.references import retention_lm as ref

    cfg = tiny("brumby_tiny")
    params = ref.init_params(cfg, 11, "float32")
    tokens = np.random.RandomState(0).randint(0, 128, (1, 40))
    whole = np.asarray(ref.logits(params, tokens, cfg))
    assert whole.shape == (1, 40, 128)
    part = np.asarray(ref.logits(params, tokens, cfg, rows=(7, 16)))
    np.testing.assert_allclose(part, whole[:, 7:23], rtol=1e-6, atol=1e-6)
    # the gates remember: sigmoid(b_g) within 1/100 of 1
    forget = 1.0 / (1.0 + np.exp(np.asarray(params["bg"], np.float64)))
    assert forget.max() <= 1e-2 * 1.001 and forget.min() >= 1e-4 * 0.999


def _brumby():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "brumby_14b.json")) as f:
        return json.load(f)


def test_request_flops_by_hand():
    cfg = _brumby()
    d, ff, v = 5120, 17408, 151936
    # q and o are d x 40 x 128, k and v d x 8 x 128, the gate d x 8, the
    # MLP three d x ff: 330.35 M weights a layer
    weights = 2 * d * 5120 + 2 * d * 1024 + d * 8 + 3 * d * ff
    assert weights == 330_342_400
    # the symmetric second power of 128 numbers has 8,256 entries, each
    # beside 128 values and one of the normaliser: updated by 8 KV
    # heads, read by 40 query heads
    state = 8256 * 129 * (8 + 40)
    per_token = 8 * (2 * weights + 2 * state)
    # a prompt of 3 and an answer of 2: four tokens go through the
    # layers; two tokens are produced
    want = 4 * per_token + 2 * 2 * d * v
    assert retention_lm.request_forward_flops(cfg, 3, 2) == want


def test_retention_decode_calls_by_hand():
    cfg = _brumby()
    # 100 chunks of 8 steps made 9,000 tokens beside 40 prefills' own:
    # 11.2 live slots a step
    counters = {"tokens_generated": 9000, "prefills": 40,
                "decode_chunks": 100, "chunk": 8}
    calls = retention_lm.retention_decode_calls_per_step(cfg, {}, 2, counters)
    assert len(calls) == 8
    live = (9000 - 40) / 800
    # a slot's eight heads of 8,256 x 129 float32 numbers, read and
    # written: 68.2 MB
    assert calls[0][2] == live * 2 * 8 * 8256 * 129 * 4
    assert round(calls[0][2] / live / 1e6, 1) == 68.2
    assert calls[0][1] == live * 2 * 8256 * 129 * 48
    assert retention_lm.retention_decode_calls_per_step(cfg, {}, 2, {}) == []
