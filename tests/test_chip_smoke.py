"""chip_smoke.py rehearsed without the chip (``on-chip-measurement``
guide, section 2, rehearsals 1 and 2): every phase function at a tiny
size on the CPU mesh, the ``--chips 4`` phase on four of the eight
virtual devices, and the command line refusing to run without a TPU.
The phases' own checks (finite losses, no compile after warm-up, fused
plan held, reference parity) are what is asserted: a phase that fails
raises ``SmokeFailure``."""

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture
def compiles():
    return chip_smoke.CompileCounter()


def _tiny_resnet():
    from mxnet_tpu.gluon.model_zoo import vision

    return vision.resnet18_v1(classes=10)


def _tiny_bert():
    from mxnet_tpu.models import bert as bert_mod

    return bert_mod.get_bert_model(
        "bert_12_768_12", vocab_size=100, dropout=0.0, num_layers=2,
        units=32, hidden_size=64, num_heads=4, max_length=32,
        use_pooler=False, use_classifier=False)


def test_train_resnet_phase_holds_the_fused_plan(compiles):
    out = chip_smoke.train_resnet50(
        compiles, make_net=_tiny_resnet, batch=4, image=32, classes=10,
        steps=3, dtype="bfloat16", platform="cpu")
    assert out["compiles_by_step"][0] > 0
    assert out["compiles_by_step"][1:] == [0, 0]
    assert out["fused_fallbacks"] == []
    json.dumps(out)


def test_train_resnet_phase_fails_when_params_are_elsewhere(compiles):
    with pytest.raises(chip_smoke.SmokeFailure, match="parameters live"):
        chip_smoke.train_resnet50(
            compiles, make_net=_tiny_resnet, batch=4, image=32, classes=10,
            steps=2, dtype="float32", platform="tpu")


def test_train_bert_phase(compiles):
    out = chip_smoke.train_bert_base(
        compiles, make_net=_tiny_bert, batch=4, seq=16, vocab=100, steps=3,
        dtype="float32", platform="cpu")
    assert out["compiles_by_step"][1:] == [0, 0]
    assert out["tpu_custom_call_in_step_hlo"] is False  # the CPU's jnp path
    json.dumps(out)


def test_train_bert_phase_demands_the_kernel(compiles):
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.train_bert_base(
            compiles, make_net=_tiny_bert, batch=4, seq=16, vocab=100,
            steps=2, dtype="float32", platform="tpu")


def test_kernels_phase():
    out = chip_smoke.kernels(
        flash=(((2, 2, 32, 16), False), ((1, 2, 64, 16), True)),
        window=((1, 2, 64, 16), 16), decode=(5, 4, 2, 16, 8, 4),
        dtype="float32", tol=1e-4)
    assert len(out["max_rel_err"]) == 4
    json.dumps(out)


def test_serve_decode_phase_is_token_exact(compiles):
    model = dict(vocab_size=64, num_layers=2, d_model=32, num_heads=4,
                 d_ff=64, max_seq=64, dtype="float32")
    out = chip_smoke.serve_decode(
        compiles, model=model, prompt_lens=(3, 5, 9, 14), buckets=(4, 16),
        new_tokens=10, slots=4, chunk=4, platform="cpu")
    assert out["tokens_equal_reference"] == out["tokens_total"] == 40
    assert out["tokens_at_reference_rounding_tie"] == 0
    assert out["logits_max_rel_err"] < 1e-5
    assert out["compiles_after_warmup"] == 0
    json.dumps(out)


def test_serve_decode_phase_refuses_a_stale_context(compiles, monkeypatch):
    """A decode that reads one row too few nudges the logits: the tap
    must refuse it whether or not a token flips."""
    from mxnet_tpu.ops import flash_attention as fa

    real = fa.paged_decode_attention

    def one_row_short(q, k_pool, v_pool, tables, lens, **kw):
        return real(q, k_pool, v_pool, tables, lens - (lens > 1), **kw)

    monkeypatch.setattr(fa, "paged_decode_attention", one_row_short)
    model = dict(vocab_size=64, num_layers=2, d_model=32, num_heads=4,
                 d_ff=64, max_seq=64, dtype="float32")
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="logits are off"):
        chip_smoke.serve_decode(
            compiles, model=model, prompt_lens=(3, 5, 9, 14),
            buckets=(4, 16), new_tokens=10, slots=4, chunk=4, platform="cpu")


@pytest.mark.parametrize("peak,warns", [(8.0e9, False), (15.95e9, True)],
                         ids=["half", "pr22_resnet_peak"])
def test_memory_says_when_the_peak_nears_the_limit(peak, warns, capsys):
    class Device:
        def memory_stats(self):
            return {"peak_bytes_in_use": int(peak), "bytes_in_use": 1,
                    "bytes_limit": 16909336064}

    out = chip_smoke._memory(Device(), "some_phase")
    assert ("warning" in out) is warns
    assert ("some_phase" in capsys.readouterr().err) is warns


def _convnet_without_batchnorm():
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
            nn.MaxPool2D(2), nn.Flatten(), nn.Dense(10))
    return net


def test_dp4_phase_on_four_virtual_devices():
    """With no BatchNorm the two legs are the same arithmetic (the
    full-size phase normalises per shard, hence its looser bound), so
    data parallelism must reproduce the one-device losses tightly."""
    out = chip_smoke.train_resnet50_dp4(
        make_net=_convnet_without_batchnorm, batch=8, image=16, classes=10,
        steps=3, dtype="float32", devices=jax.devices()[:4], tol=1e-4)
    assert out["mode"] == "overlap"  # the shard_map step, not GSPMD
    assert out["batch_shard_devices"] == out["param_shard_devices"] \
        == [0, 1, 2, 3]
    assert out["grad_all_reduce_in_step_hlo"]
    json.dumps(out)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["default", "chips4"])
def test_command_line_refuses_to_run_without_a_tpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                          *argv], env=env, capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert res.stdout.strip() == ""  # no result of any kind
    assert "needs a TPU" in res.stderr
