"""Plain reference: BERT encoder with a masked-LM head, its loss, its
gradients and its Adam update, in straightforward ``jax.numpy``.

Nothing here imports the program. The weights are the benchmark's own
(``init_params`` draws them on the device from the seed, in the type
the configuration serves them in); the reference reads them as float32
and multiplies at ``highest`` precision.

Follows Devlin et al. 2018 and google-bert/bert-base-uncased's
``config.json``: token + position embeddings, LayerNorm, ``L`` post-norm
layers (self-attention, residual, LayerNorm, GELU feed-forward, residual,
LayerNorm), MLM head (dense, GELU, LayerNorm, dense to the vocabulary).
Departures, as the configuration file states them: no segment input (the
token-type table is a leaf that only weight decay moves), no dropout,
the loss is taken over every position, the MLM head's output matrix is
not tied to the embedding.

``quant`` puts each matrix product's two operands through a lower
precision first, in the forward pass: ``None`` (float32), ``"bf16"`` or
``"fp8"`` (e4m3 with one scale a tensor). That is the control, never
the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5  # the program's LayerNorm default; the paper's 1e-12


def leaf_shapes(cfg):
    """name -> (shape, kind). Kind decides how a leaf is drawn."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "word_embed": ((v, d), "matrix"),
        "token_type_embed": ((cfg["type_vocab_size"], d), "matrix"),
        "position": ((cfg["max_position_embeddings"], d), "matrix"),
        "embed_ln_g": ((d,), "gain"), "embed_ln_b": ((d,), "bias"),
        "head_dense_w": ((d, d), "matrix"), "head_dense_b": ((d,), "bias"),
        "head_ln_g": ((d,), "gain"), "head_ln_b": ((d,), "bias"),
        "head_out_w": ((v, d), "matrix"), "head_out_b": ((v,), "bias"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}_"
        for n in ("q", "k", "v", "o"):
            out[p + n + "_w"] = ((d, d), "matrix")
            out[p + n + "_b"] = ((d,), "bias")
        out[p + "ln1_g"] = ((d,), "gain")
        out[p + "ln1_b"] = ((d,), "bias")
        out[p + "ffn1_w"] = ((ff, d), "matrix")
        out[p + "ffn1_b"] = ((ff,), "bias")
        out[p + "ffn2_w"] = ((d, ff), "matrix")
        out[p + "ffn2_b"] = ((d,), "bias")
        out[p + "ln2_g"] = ((d,), "gain")
        out[p + "ln2_b"] = ((d,), "bias")
    return out


def init_params(cfg, seed, dtype):
    """Every leaf in one jitted call from the seed, in ``dtype``."""
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


def _quantizer(quant):
    """Rounds a matrix product's operand to the lower precision in the
    forward pass and lets the gradient through as it is (a cast's own
    transpose would round the gradient to fp8 unscaled, and lose it)."""
    if quant is None:
        return lambda a: a
    if quant == "bf16":
        low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    elif quant == "fp8":
        def low(a):
            s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
            return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    else:
        raise ValueError(f"unknown precision {quant!r}")
    return lambda a: a + jax.lax.stop_gradient(low(a) - a)


def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))


def forward_loss_sum(params, tokens, labels, cfg, quant=None):
    """Sum over every position of the cross entropy: float32."""
    q = _quantizer(quant)
    hi = jax.lax.Precision.HIGHEST

    def dense(x, w, b):  # w is (out, in), as the paper's y = xW^T + b
        return jnp.einsum("...i,oi->...o", q(x), q(w), precision=hi) + b

    p = {k: v.astype(jnp.float32) for k, v in params.items()}
    B, T = tokens.shape
    H = cfg["num_attention_heads"]
    D = cfg["hidden_size"] // H
    x = p["word_embed"][tokens] + p["position"][:T][None]
    x = _ln(x, p["embed_ln_g"], p["embed_ln_b"])
    for i in range(cfg["num_hidden_layers"]):
        n = f"layer{i}_"

        def heads(a):
            return a.reshape(B, T, H, D).transpose(0, 2, 1, 3)

        qh = heads(dense(x, p[n + "q_w"], p[n + "q_b"]))
        kh = heads(dense(x, p[n + "k_w"], p[n + "k_b"]))
        vh = heads(dense(x, p[n + "v_w"], p[n + "v_b"]))
        s = jnp.einsum("bhtd,bhsd->bhts", q(qh), q(kh),
                       precision=hi) * (D ** -0.5)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhts,bhsd->bhtd", q(a), q(vh), precision=hi)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        x = _ln(x + dense(o, p[n + "o_w"], p[n + "o_b"]),
                p[n + "ln1_g"], p[n + "ln1_b"])
        f = dense(_gelu(dense(x, p[n + "ffn1_w"], p[n + "ffn1_b"])),
                  p[n + "ffn2_w"], p[n + "ffn2_b"])
        x = _ln(x + f, p[n + "ln2_g"], p[n + "ln2_b"])
    h = _gelu(dense(x, p["head_dense_w"], p["head_dense_b"]))
    h = _ln(h, p["head_ln_g"], p["head_ln_b"])
    logits = dense(h, p["head_out_w"], p["head_out_b"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant", "block",
                                             "drop_half"))
def _loss_and_grads(params, tokens, labels, cfg_key, quant, block, drop_half):
    cfg = dict(cfg_key)
    B = tokens.shape[0]
    if drop_half:  # the planted fault: the mean over the first half
        tokens, labels, B = tokens[:B // 2], labels[:B // 2], B // 2
    n = B // block
    tb = tokens[:n * block].reshape(n, block, -1)
    lb = labels[:n * block].reshape(n, block, -1)
    zero = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)

    def body(carry, xs):
        loss, grads = carry
        l, g = jax.value_and_grad(forward_loss_sum)(params, xs[0], xs[1],
                                                    cfg, quant)
        return (loss + l, jax.tree.map(
            lambda a, b: a + b.astype(jnp.float32), grads, g)), None

    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zero),
                                    (tb, lb))
    count = n * block * tokens.shape[1]
    return loss / count, jax.tree.map(lambda g: g / count, grads)


def loss_and_grads(params, tokens, labels, cfg, quant=None, block=8,
                   drop_half=False):
    """Mean loss over every position and its gradient by leaf, summed
    over blocks of ``block`` rows so that it fits beside nothing."""
    B = tokens.shape[0] // (2 if drop_half else 1)
    block = max(1, min(block, B))
    while B % block:
        block -= 1
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str))))
    return _loss_and_grads(params, jnp.asarray(tokens, jnp.int32),
                           jnp.asarray(labels, jnp.int32), cfg_key, quant,
                           block, drop_half)
