"""The on-device sampler on the chip, alone, at the serve cells' logits:
``(128, 50257)`` (GPT-2's decode batch) and ``(16, 151936)`` (Brumby's),
float32. Three batches each: every row greedy; one live row that asks
for a draw; one DEAD row that asks for one (a slot keeps the policy of
the last request it held). Against the sampler as it was before it
branched, kept below as the reference for ids and for time.

    chiprun -- python -m pytest tests_tpu/test_sampler.py -q -s -p no:xdist

``-s`` shows the JSON lines (what PERF.md quotes).
"""

import json
import time

import numpy as np
import pytest

SHAPES = {"gpt2_small": (128, 50257), "brumby_14b": (16, 151936)}


def _unconditional(logits, key, temperature, top_k, top_p, greedy):
    """``sample_tokens`` before the branch: every row filtered and drawn
    (``tests/test_generation.py`` keeps the same copy for the CPU)."""
    import jax
    import jax.numpy as jnp

    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kk = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v)
    kth = jnp.take_along_axis(sorted_desc, (kk - 1)[:, None], axis=-1)
    limited = jnp.where(scaled < kth, -jnp.inf, scaled)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    keep = mass_before < top_p[:, None]
    thresh = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    limited = jnp.where(scaled < thresh, -jnp.inf, limited)
    drawn = jax.random.categorical(key, limited, axis=-1)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1),
                     drawn).astype(jnp.int32)


def _call_ms(fn, *args, n=50):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


@pytest.mark.parametrize("family", sorted(SHAPES))
def test_sampler_sorts_only_for_a_live_row_that_draws(family):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import sample_tokens

    rows, vocab = SHAPES[family]
    logits = jax.random.normal(jax.random.PRNGKey(3), (rows, vocab),
                               jnp.float32) * 4.0
    key = jax.random.PRNGKey(5)
    temp = jnp.full(rows, 0.8, jnp.float32)
    top_k = jnp.full(rows, 40, jnp.int32)
    top_p = jnp.full(rows, 0.95, jnp.float32)
    all_greedy = all_live = jnp.ones(rows, bool)
    one_draws = all_greedy.at[rows // 2].set(False)
    that_one_dead = one_draws  # live everywhere but where it draws
    new, old = jax.jit(sample_tokens), jax.jit(_unconditional)
    policy = (temp, top_k, top_p)
    amax = np.asarray(jnp.argmax(logits, -1))

    got = np.asarray(new(logits, key, *policy, all_greedy, all_live))
    assert np.array_equal(got, amax)
    got = np.asarray(new(logits, key, *policy, one_draws, all_live))
    want = np.asarray(old(logits, key, *policy, one_draws))
    assert np.array_equal(got, want)
    got = np.asarray(new(logits, key, *policy, one_draws, that_one_dead))
    assert np.array_equal(got, amax)

    ms = {
        "all_greedy": _call_ms(new, logits, key, *policy, all_greedy,
                               all_live),
        "one_live_row_draws": _call_ms(new, logits, key, *policy, one_draws,
                                       all_live),
        "one_dead_row_draws": _call_ms(new, logits, key, *policy, one_draws,
                                       that_one_dead),
        "before_all_greedy": _call_ms(old, logits, key, *policy, all_greedy),
        "before_one_row_draws": _call_ms(old, logits, key, *policy,
                                         one_draws),
    }
    print(json.dumps({"family": family, "logits": [rows, vocab],
                      "sample_tokens_call_ms": ms}))
    # a pass over the logits at the memory's peak is 0.03 and 0.012 ms
    assert ms["all_greedy"] < 0.3
    assert ms["one_dead_row_draws"] < 0.3
    assert ms["one_dead_row_draws"] < 1.5 * ms["all_greedy"] + 0.05
    assert abs(ms["one_live_row_draws"] / ms["before_one_row_draws"] - 1) \
        < 0.05
