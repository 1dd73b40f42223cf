"""``correct`` has to come out false where it should: the control (the
reference at the nearest lower precision, put in the program's place)
and each fault the cells can have, planted under the timed path of a
whole run at a tiny size. The tiny configurations state float32, so
their control is bfloat16; the cells on the chip state bfloat16 and
their control is fp8 (``tools/readings.py`` reads it there)."""

import numpy as np
import pytest

from chipbench.harness import train_check
from helpers import drive_tiny, tiny


def _fails(result, *names):
    assert not result["correct"], result["compared"]
    over = {n for n, c in result["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over & set(names), (over, result["compared"])


# -- training ---------------------------------------------------------------

def _train_loop(seed=5):
    import argparse
    import jax

    from chipbench import run as R
    from helpers import PEAKS

    cfg, workload = tiny("bert_tiny"), tiny("bert_tiny.spmd")
    ns = argparse.Namespace(seed=seed, seconds=0.1, trace=0)
    _, loop = R.make_loop(ns, {"name": "tiny.cell", "chips": 1}, workload,
                          cfg, jax.devices()[:1], PEAKS)
    loop.setup()
    loop.window_losses = []
    loop.release()
    return loop, workload["limits"]


def test_training_control_and_planted_faults_fail_a_number():
    loop, limits = _train_loop()
    ref = loop.reference_reading()
    sound = train_check.compare(loop.program_reading, ref)
    assert all(v <= limits[k] for k, (v, _) in sound.items()), sound
    control = train_check.compare(loop.reference_reading(quant="bf16"), ref)
    assert any(v > limits[k] for k, (v, _) in control.items()), control
    half = train_check.compare(loop.reference_reading(drop_half=True), ref)
    assert any(v > limits[k] for k, (v, _) in half.items()), half
    frozen = train_check.compare(loop.reference_reading(freeze=True), ref)
    assert frozen["change_gap"][0] == pytest.approx(1.0)
    assert frozen["change_gap"][0] > limits["change_gap"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    import jax.numpy as jnp

    from chipbench.loops import spmd_step

    real = spmd_step.Loop.one_step

    def frozen(self, i):
        before = self.step._state
        if before is not None:  # the step donates its state: keep copies
            before = jax_copy(before)
        out = real(self, i)
        if before is not None:
            self.step._state = before
        return out

    def jax_copy(tree):
        import jax

        return jax.tree.map(jnp.copy, tree)

    try:
        spmd_step.Loop.one_step = frozen
        result = drive_tiny("bert_tiny", "bert_tiny.spmd")
    finally:
        spmd_step.Loop.one_step = real
    _fails(result, "change_gap")


def test_half_of_the_batch_left_out_is_not_correct():
    from chipbench.loops import spmd_step

    real = spmd_step.Loop.one_step

    def half(self, i):
        x, y = self.ring[i % len(self.ring)]
        n = x.shape[0] // 2
        return self.step(x[:n], y[:n], lr=self.lr, sync=False)

    try:
        spmd_step.Loop.one_step = half
        result = drive_tiny("bert_tiny", "bert_tiny.spmd")
    finally:
        spmd_step.Loop.one_step = real
    _fails(result, "grad_gap", "loss_gap", "change_gap")


# -- serving ----------------------------------------------------------------

def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from mxnet_tpu.serving import generation

    real = generation.sample_tokens

    def altered(logits, *a, **kw):
        return (real(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(generation, "sample_tokens", altered)
    result = drive_tiny("gpt_tiny", "gpt_tiny.serve", seconds=1.0)
    _fails(result, "logit_gap")


def test_serving_control_reads_above_the_limit():
    import argparse
    import jax

    from chipbench import run as R
    from helpers import PEAKS

    cfg, workload = tiny("gpt_tiny"), tiny("gpt_tiny.serve")
    ns = argparse.Namespace(seed=9, seconds=1.0, trace=0)
    _, loop = R.make_loop(ns, {"name": "tiny.cell", "chips": 1}, workload,
                          cfg, jax.devices()[:1], PEAKS)
    loop.setup()
    loop.window(1.0)
    loop.release()
    sound, tokens = loop.gaps()
    # bfloat16 flips no first token among 96 well-separated logits: the
    # tiny model takes the next step down
    control, _ = loop.gaps(quant="fp8")
    assert tokens > 0 and sound <= workload["limits"]["logit_gap"]
    assert control > workload["limits"]["logit_gap"] and control > 3 * sound


def test_an_answer_that_comes_back_short_is_not_correct(monkeypatch):
    from chipbench.loops import generation_server as gs

    real = gs.Loop._reduce

    def short(self, *window):
        ok = [r for r in self.results if r["error"] is None]
        ok[0]["tokens"] = np.asarray(ok[0]["tokens"])[:-1]
        return real(self, *window)

    monkeypatch.setattr(gs.Loop, "_reduce", short)
    result = drive_tiny("gpt_tiny", "gpt_tiny.serve", seconds=1.0)
    _fails(result, "short_answers")


# -- the control put in the program's place, through a whole run ------------

def test_a_whole_run_on_the_training_control_is_not_correct(monkeypatch):
    from chipbench.loops import spmd_step
    from chipbench.tools import readings

    monkeypatch.setattr(spmd_step.Loop, "release", spmd_step.Loop.release)
    readings.put_control_in_place("spmd_step", "bf16")
    _fails(drive_tiny("bert_tiny", "bert_tiny.spmd"),
           "loss_gap", "grad_gap", "change_gap")


def test_a_whole_run_on_the_serving_control_is_not_correct(monkeypatch):
    from chipbench.loops import generation_server as gs
    from chipbench.tools import readings

    monkeypatch.setattr(gs.Loop, "gaps", gs.Loop.gaps)
    readings.put_control_in_place("generation_server", "fp8")
    _fails(drive_tiny("gpt_tiny", "gpt_tiny.serve", seconds=1.0),
           "logit_gap")
