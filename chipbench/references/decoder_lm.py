"""Plain reference: a GPT-2-shaped decoder language model's forward pass
in straightforward ``jax.numpy``, float32, ``highest`` precision, one
dense causal pass over the whole sequence: no cache, no paging, no
buckets, no batching of requests.

Follows Radford et al. 2019 and openai-community/gpt2's ``config.json``:
token + learned position embeddings, ``n_layer`` pre-LayerNorm blocks
(causal self-attention, residual, GELU(tanh) feed-forward, residual), a
final LayerNorm and a projection to the vocabulary. Departures, as the
configuration file states them (they are the served class's): no biases
on the four attention projections, an output head of its own (not tied
to the embedding).

Nothing here imports the program; ``init_params`` draws the benchmark's
own weights on the device from the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .bert_mlm import _quantizer

LN_EPS = 1e-5  # gpt2's layer_norm_epsilon


def leaf_shapes(cfg):
    d, v = cfg["n_embd"], cfg["vocab_size"]
    ff = cfg.get("n_inner") or 4 * d
    out = {"embed": ((v, d), "matrix"), "pos": ((cfg["n_positions"], d),
                                               "matrix"),
           "lnf_g": ((d,), "gain"), "lnf_b": ((d,), "bias"),
           "head": ((d, v), "matrix")}
    for i in range(cfg["n_layer"]):
        p = f"layer{i}_"
        out.update({
            p + "ln1_g": ((d,), "gain"), p + "ln1_b": ((d,), "bias"),
            p + "wq": ((d, d), "matrix"), p + "wk": ((d, d), "matrix"),
            p + "wv": ((d, d), "matrix"), p + "wo": ((d, d), "matrix"),
            p + "ln2_g": ((d,), "gain"), p + "ln2_b": ((d,), "bias"),
            p + "w1": ((d, ff), "matrix"), p + "b1": ((ff,), "bias"),
            p + "w2": ((ff, d), "matrix"), p + "b2": ((d,), "bias"),
        })
    return out


def init_params(cfg, seed, dtype):
    shapes = leaf_shapes(cfg)
    std = cfg.get("initializer_range", 0.02)

    @jax.jit
    def draw(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            if kind == "gain":
                z = 1.0 + z
            out[name] = z.astype(dtype)
        return out

    return draw(jax.random.PRNGKey(seed % (2 ** 31)))


def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_layer", "n_head", "quant"))
def _logits(params, tokens, n_layer, n_head, quant):
    q = _quantizer(quant)
    hi = jax.lax.Precision.HIGHEST

    def mm(x, w):
        return jnp.einsum("...i,io->...o", q(x), q(w), precision=hi)

    p = {k: v.astype(jnp.float32) for k, v in params.items()}
    B, T = tokens.shape
    d = p["embed"].shape[1]
    hd = d // n_head
    x = p["embed"][tokens] + p["pos"][:T][None]
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
    for i in range(n_layer):
        n = f"layer{i}_"
        h = _ln(x, p[n + "ln1_g"], p[n + "ln1_b"])

        def heads(a):
            return a.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)

        qh, kh, vh = (heads(mm(h, p[n + w])) for w in ("wq", "wk", "wv"))
        s = jnp.einsum("bhtd,bhsd->bhts", q(qh), q(kh),
                       precision=hi) * (hd ** -0.5)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhts,bhsd->bhtd", q(a), q(vh), precision=hi)
        x = x + mm(o.transpose(0, 2, 1, 3).reshape(B, T, d), p[n + "wo"])
        h = _ln(x, p[n + "ln2_g"], p[n + "ln2_b"])
        x = x + mm(_gelu_tanh(mm(h, p[n + "w1"]) + p[n + "b1"]),
                   p[n + "w2"]) + p[n + "b2"]
    return mm(_ln(x, p["lnf_g"], p["lnf_b"]), p["head"])


def logits(params, tokens, cfg, quant=None):
    """(B, T) token ids -> (B, T, V) float32 logits; position ``t``
    predicts token ``t + 1``."""
    return _logits(params, jnp.asarray(tokens, jnp.int32), cfg["n_layer"],
                   cfg["n_head"], quant)
