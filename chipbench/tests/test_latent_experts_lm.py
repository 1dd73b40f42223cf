"""The ``latent_experts_lm`` family and the loop kind
``generation_server_experts`` at a tiny size on the CPU, through
``run.drive``: the program reads ``correct`` true; the control and three
planted faults (no shared expert, the balancing bias added to the
weights, a prompt's first block of latent rows never written) each read
``correct`` false; the glue's counts against counts made by hand; the
cut in depth; the reference's rows against its whole logits. (A router
computed in bf16 is no planted fault: it flips a near-tie now and then
and may or may not pass; the program computes it in float32.)"""

import argparse
import json
import math
import os
import time

import numpy as np

from chipbench.models import latent_experts_lm as glue
from chipbench.references import latent_experts_lm as ref
from helpers import PEAKS, ROOT, tiny

CELL = "joyai_llm_flash.serve_assist"


def drive(*, seed=3, seconds=1.0, trace=0, **cfg_edit):
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
                   tiny("joyai_tiny.serve"), {**tiny("joyai_tiny"), **cfg_edit},
                   jax.devices()[:1], PEAKS, time.perf_counter())


def _fails(result, *names):
    assert not result["correct"], result["compared"]
    over = {n for n, c in result["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over & set(names), (over, result["compared"])


def test_the_expert_server_runs_and_is_correct():
    r = drive()
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] == 20  # 20/s for 1 s
    assert r["metrics"]["tpot_ms_mean"]["value"] > 0
    for c in r["compared"].values():
        assert math.isfinite(c["value"]) and c["value"] <= c["limit"]
    c = r["counters"]
    assert c["kv_blocks"] == 40 and c["expert_layers"] == 2
    assert c["routed_experts"] == 8
    # every decoded token routes two pairs in each of two expert layers
    assert c["routed_pairs"] == 4 * (c["tokens_generated"] - c["prefills"])
    assert c["load_mean"] == c["routed_pairs"] / 8
    assert 0 < c["experts_hit"] <= c["routed_pairs"]
    assert int(r["compared"]["logit_gap"]["at"].split()[0]) > 0  # tokens
    # the largest gap and the mean one, each beside its limit
    assert r["compared"]["logit_gap_mean"]["value"] \
        <= r["compared"]["logit_gap"]["value"]


def test_a_traced_run_reports_the_counter_metrics_and_no_roofline():
    r = drive(trace=1)
    assert r["correct"], r["compared"]
    m = r["metrics"]
    assert {"tpot_ms_p95", "decode_slots_active_share", "step_mfu.serve",
            "compiles_in_window.serve", "kv_pool_high_water_share.serve",
            "engine_host_work_share.serve", "experts_hit_share",
            "expert_load_imbalance"} <= set(m)
    assert 0 < m["experts_hit_share"]["value"] <= 100.0
    assert m["expert_load_imbalance"]["value"] >= 1.0
    # no TPU planes in a CPU trace: nothing to read, nothing reported
    assert "latent_decode_roofline" not in m
    assert "experts_product_roofline" not in m


def test_a_whole_run_on_the_control_is_not_correct(monkeypatch):
    from chipbench.loops import generation_server_experts as gse
    from chipbench.tools import readings

    monkeypatch.setattr(gse.Loop, "gaps", gse.Loop.gaps, raising=False)
    readings.put_control_in_place("generation_server_experts", "fp8")
    _fails(drive(), "logit_gap_mean")


def test_a_missing_shared_expert_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    real = glue.build_net

    def no_shared(cfg, params, dtype):
        return real(cfg, {k: jnp.zeros_like(v) if k.endswith(".ws_down")
                          else v for k, v in params.items()}, dtype)

    monkeypatch.setattr(glue, "build_net", no_shared)
    _fails(drive(), "logit_gap")


def test_the_bias_added_to_the_weights_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import experts as ex

    def biased(h, w_router, bias, k, scale=1.0):
        s = jax.nn.sigmoid(h.astype(jnp.float32)
                           @ w_router.astype(jnp.float32)) + bias
        picked, chosen = jax.lax.top_k(s, int(k))
        w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
        return w * scale, chosen.astype(jnp.int32)

    monkeypatch.setattr(ex, "route", biased)
    # (logit_gap sees a fault only where a first token changes, and at
    # this size the logits hardly feel an expert's weight: wider weights
    # and a wider bias than the configuration's show it in 33 tokens)
    _fails(drive(router_bias_std=1.0, initializer_range=0.3), "logit_gap")


def test_latent_rows_left_unwritten_are_not_correct(monkeypatch):
    """A prompt's first block goes to the null block: its tokens read
    what the block held before (a re-used block's stale rows)."""
    import jax.numpy as jnp

    from mxnet_tpu.serving import kvcache

    real = kvcache._prefill_coords

    def skip_first_block(table_row, length, num_tokens, block_size):
        blk, off = real(table_row, length, num_tokens, block_size)
        return jnp.where(jnp.arange(num_tokens) < block_size, 0, blk), off

    monkeypatch.setattr(kvcache, "_prefill_coords", skip_first_block)
    _fails(drive(), "logit_gap")


def test_the_reference_rows_are_rows_of_its_whole_logits():
    cfg = tiny("joyai_tiny")
    params = ref.init_params(cfg, 11, "float32")
    tokens = np.random.RandomState(0).randint(0, 128, (1, 40))
    whole = np.asarray(ref.logits(params, tokens, cfg))
    assert whole.shape == (1, 40, 128)
    part = np.asarray(ref.logits(params, tokens, cfg, rows=(7, 16)))
    np.testing.assert_allclose(part, whole[:, 7:23], rtol=1e-6, atol=1e-6)
    # the balancing bias is wide enough to move a choice: float32, std 0.1
    bias = np.asarray(params["layer1.router_bias"])
    assert bias.dtype == np.float32 and 0.03 < bias.std() < 0.3


def test_the_cut_net_is_the_first_layers_of_the_deeper_reference():
    """The benchmark serves 5 of 40 layers: at a tiny size, the program
    built at depth 3 gives the logits of the first three layers of a
    reference drawn at depth 4 (a layer's weights do not depend on the
    depth, and nothing of a later layer enters an earlier one)."""
    cut, deep = tiny("joyai_tiny"), {**tiny("joyai_tiny"),
                                     "num_hidden_layers": 4}
    p_cut = ref.init_params(cut, 5, "float32")
    p_deep = ref.init_params(deep, 5, "float32")
    assert set(p_cut) < set(p_deep)
    assert all((np.asarray(p_cut[k]) == np.asarray(p_deep[k])).all()
               for k in p_cut)
    tokens = np.random.RandomState(1).randint(0, 128, (1, 24))
    want = np.asarray(ref.logits(p_deep, tokens, deep, layers=3))
    net = glue.build_net(cut, p_cut, "float32")
    got = np.asarray(net.forward_fn()(net.params(), tokens))
    np.testing.assert_allclose(got, want, atol=2e-5)
    full = np.asarray(ref.logits(p_deep, tokens, deep))
    assert np.abs(full - want).max() > 1e-3  # the fourth layer does work


def _joyai():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "joyai_llm_flash.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalogs_cut_in_depth_alone():
    cfg = _joyai()
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 5
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["vocab_size"]) == (
        2048, 1536, 512, 256, 8, 768, 129280)
    # 5.558 B parameters: the issue's count
    import jax

    shapes = jax.eval_shape(lambda: ref.init_params(cfg, 1, "bfloat16"))
    assert round(sum(v.size for v in shapes.values()) / 1e9, 3) == 5.558


def test_every_line_of_text_in_the_benchmark_file_fits_its_200_characters():
    # the contract test holds the cells' `why` to 200; the driver holds the
    # configurations' to it too (PR 32's first check was refused for 208)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"] + bench["workloads"]:
        for key in ("why", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), entry["name"]


def test_request_flops_by_hand():
    cfg = _joyai()
    d, H, V = 2048, 32, 129280
    # q_a, q_b, kv_a, kv_b, o: 26.35 M weights a layer's attention
    attention = (d * 1536 + 1536 * H * 192 + d * 576 + 512 * H * 256
                 + H * 128 * d)
    assert attention == 26_345_472
    expert = 3 * d * 768
    assert expert == 4_718_592
    # a token: five attentions, the dense MLP, and in each of four
    # expert layers the router, eight experts and the shared one
    per_token = 2 * (5 * attention + 3 * d * 7168
                     + 4 * (d * 256 + 9 * expert))
    # a prompt of 3 and an answer of 2: four tokens go through the
    # layers; the prompt attends expanded over 1 + 2 + 3 pairs (192 + 128
    # a head), the one decoded token absorbed over its 4 rows (576 + 512)
    attend = 5 * (2 * H * 320 * 6 + 2 * H * 1088 * 4)
    want = 4 * per_token + attend + 2 * 2 * d * V
    assert glue.request_forward_flops(cfg, 3, 2) == want
    # 0.70 GFLOP a token through the layers, the issue's figure
    assert round(per_token / 1e9, 2) == 0.70


def test_the_kernels_calls_by_hand():
    cfg = _joyai()
    # 100 chunks of 8 steps; the live contexts a step sum to 50,000 tokens
    counters = {"mean_live_context_tokens": 50_000.0, "decode_chunks": 100,
                "chunk": 8, "expert_layers": 4, "experts_hit": 576_000,
                "routed_pairs": 960_000}
    calls = glue.latent_decode_calls_per_step(cfg, {}, 2, counters)
    assert len(calls) == 5
    # a token's row: 576 bf16 numbers, 1,152 bytes; 32 heads score over
    # 576 and sum over 512
    assert calls[0][2] == 50_000 * 1152
    assert calls[0][1] == 50_000 * 2 * 32 * 1088
    calls = glue.experts_calls_per_step(cfg, {}, 2, counters)
    assert len(calls) == 4
    # 180 experts hit a step a layer, 9.44 MB each; 300 pairs through
    # three 2048 x 768 matrices
    assert calls[0][2] == 180 * 3 * 2048 * 768 * 2
    assert round(calls[0][2] / 180 / 1e6, 2) == 9.44
    assert calls[0][1] == 300 * 2 * 3 * 2048 * 768
    assert glue.latent_decode_calls_per_step(cfg, {}, 2, {}) == []
    assert glue.experts_calls_per_step(cfg, {}, 2, {"chunk": 8}) == []
