"""Each loop kind at a tiny size on the CPU, through ``run.drive``."""

import math

from helpers import drive_tiny


def _sound(result, metric):
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"][metric]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    for c in result["compared"].values():
        assert math.isfinite(c["value"]) and c["value"] <= c["limit"]


def test_spmd_step_runs_and_is_correct():
    _sound(drive_tiny("bert_tiny", "bert_tiny.spmd"), "train_samples_per_s")


def test_generation_server_runs_and_is_correct():
    r = drive_tiny("gpt_tiny", "gpt_tiny.serve", seconds=1.0)
    _sound(r, "tpot_ms_mean")
    c = r["counters"]
    assert c["tokens_per_s"] > 0
    assert c["ttft_ms_p95"] >= c["ttft_ms_p50"] > 0
    assert c["tpot_ms_p95"] >= c["tpot_ms_mean"] > 0
    assert 0 < c["kv_blocks_in_use_mean"] <= c["kv_blocks_in_use_max"] \
        < c["kv_blocks"]
    assert r["attempted"] == c["requests_due"] == 20  # 20/s for 1 s
    assert c["mean_live_context_tokens"] > 0


def test_spmd_step_with_float32_masters_under_bfloat16_weights():
    def edit(w):
        w["dtype"] = "bfloat16"
        w["step_options"] = {"multi_precision": True}
        w["limits"] = {"loss_gap": 5e-4, "grad_gap": 0.1, "change_gap": 0.1}

    _sound(drive_tiny("bert_tiny", "bert_tiny.spmd", workload_edit=edit),
           "train_samples_per_s")



def test_a_traced_run_of_the_server_reports_the_per_layer_metrics():
    # no TPU planes in a CPU trace: the metrics read from counters are
    # there, those read from the trace are left out, none reads 0
    r = drive_tiny("gpt_tiny", "gpt_tiny.serve", seconds=1.0, trace=1)
    assert r["correct"], r["compared"]
    assert {"tpot_ms_p95", "decode_slots_active_share", "step_mfu.serve",
            "compiles_in_window.serve"} <= set(r["metrics"])
    assert "tpot_ms_mean" not in r["metrics"]  # the untraced run's
    assert "paged_decode_roofline" not in r["metrics"]
    assert r["device"]["window_s"] > 0 and "breakdown" in r
