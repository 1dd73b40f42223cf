"""The FLOP and byte functions against counts made by hand."""

import json
import os

import pytest

from chipbench.harness import flops as F
from chipbench.harness import peaks as P
from chipbench.models import bert_mlm, decoder_lm

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_bert_base_training_sample_by_hand():
    cfg = _cfg("bert_base")
    # per token forward: 12 layers of 4 d x d projections, two d x 4d
    # feed-forward products and attention over 128 keys; the MLM head's
    # d x d transform and d x V projection; 2 operations a multiply-add
    d, ff, v, T = 768, 3072, 30522, 128
    layer = 2 * (4 * d * d + 2 * d * ff) + 4 * T * d
    fwd = 12 * layer + 2 * d * d + 2 * d * v
    assert fwd == 222_649_344
    got = bert_mlm.train_flops_per_sample(cfg, {"seq": T})
    assert got == 3 * fwd * T == 85_497_348_096
    # bench.py's constant for the same thing: 6 * (N - N_embed) * tokens
    # + 12 * L * T^2 * d a sample
    n_matmul = 12 * (4 * d * d + 2 * d * ff) + d * d + d * v
    assert got == 6 * n_matmul * T + 12 * 12 * T * T * d


def test_flash_attention_calls_by_hand():
    cfg = _cfg("bert_base")
    calls = bert_mlm.attention_calls_per_step(
        cfg, {"seq": 128, "batch": 64}, 2)
    assert len(calls) == 36 and [c[0] for c in calls[:3]] == [
        "fwd", "bwd_dq", "bwd_dkv"]
    one = 2 * 64 * 12 * 128 * 128 * 64  # one (T x T x D) product, all heads
    tensor = 64 * 12 * 128 * 64 * 2     # one (B, H, T, D) bf16 array
    assert calls[0][1:] == (2 * one, 4 * tensor)
    assert calls[1][1:] == (3 * one, 5 * tensor)
    assert calls[2][1:] == (4 * one, 6 * tensor)


def test_decode_request_and_kernel_by_hand():
    cfg = _cfg("gpt2_small")
    d, ff, v = 768, 3072, 50257
    per_token = 12 * 2 * (4 * d * d + 2 * d * ff)
    # a prompt of 3 and an answer of 2: four tokens go through the
    # layers, attending to 1, 2, 3 and 4 keys; two tokens are produced
    want = 4 * per_token + 12 * 4 * d * (1 + 2 + 3 + 4) + 2 * 2 * d * v
    assert decoder_lm.request_forward_flops(cfg, 3, 2) == want
    calls = decoder_lm.paged_decode_calls_per_step(
        cfg, {}, 2, {"mean_live_context_tokens": 1000.0})
    assert len(calls) == 12
    assert calls[0][2] == 1000 * 2 * 12 * 64 * 2  # K and V rows, bf16
    assert calls[0][1] == 4 * 1000 * 12 * 64
    assert decoder_lm.paged_decode_calls_per_step(cfg, {}, 2, {}) == []


def test_roofline_says_which_bound():
    peaks = P.peaks_for("TPU v5 lite")
    assert F.roofline_seconds(197e12, 1, peaks) == (1.0, "compute")
    assert F.roofline_seconds(1, 819e9, peaks) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(P.UnknownDevice):
        P.peaks_for("TPU v9000")
    with pytest.raises(P.UnknownDevice):
        P.peaks_for("_source")
