"""Plain reference: AI21's Jamba decoder (``JambaForCausalLM``, here
ai21labs/AI21-Jamba2-3B), forward pass in straightforward ``jax.numpy``,
float32, under ``jax.default_matmul_precision("highest")``: the
selective scan a position at a time, the convolution written out, dense
causal attention; no cache, no chunks, no kernels, no buckets, no
batching of requests.

The shapes are the published ``config.json``'s. Layer ``i`` is an
attention layer where ``i % attn_layer_period == attn_layer_offset``
and a Mamba layer otherwise; ``num_experts`` 1 makes every layer's MLP
the dense gated SiLU of ``intermediate_size``. Every layer is pre-norm
(RMSNorm, eps ``rms_norm_eps``, with a gain): ``x += mixer(RMSNorm(x))``,
then ``x += W_down(silu(h W_gate) * (h W_up))`` with ``h =
RMSNorm(x)``. A final RMSNorm, and the head is the embedding's
transpose (``tie_word_embeddings``). For a Mamba layer, ``d_inner =
mamba_expand x hidden_size``, ``N = mamba_d_state``, ``R =
mamba_dt_rank``, ``K = mamba_d_conv``, over the positions ``t`` of a
sequence:

1. ``[x | z] = h W_in`` (no bias, ``mamba_proj_bias`` false);
2. ``xc_t = silu(b_conv + sum_k w_k x_{t - K + 1 + k})``, zeros before
   the sequence (``mamba_conv_bias`` true);
3. ``[dt_r | B | C] = xc W_x``, each through an RMSNorm with a gain;
4. ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``;
5. ``s_t = exp(dt_t A) * s_{t-1} + (dt_t xc_t) B_t^T`` from ``s_0 = 0``;
   ``y_t = C_t s_t + D xc_t``;
6. the mixer's output ``(y * silu(z)) W_out`` (``wo``, no bias).

An attention layer: ``num_attention_heads`` query heads over
``num_key_value_heads`` K/V heads of ``hidden_size /
num_attention_heads``, no biases, **no positional encoding** (Jamba's
attention has neither rotary positions nor a table), causal softmax
with scale ``1 / sqrt(head size)``.

Assumed, as the configuration file lists with reasons: seeded weights
at std ``initializer_range`` 0.02 (conv taps and bias too), gains ``1 +
N(0, 0.02)``, and Mamba's own initialisation of its memory: ``A_log =
log(1..N)`` for every channel, ``D = 1``, ``b_dt`` the inverse softplus
of a step drawn log-uniformly in [0.001, 0.1].

Layout of the weights, which the program serves as they are (the device
holds one copy): ``embed`` ``(V, d)``, ``lnf_g``, and ``layers``, a list
with one entry a run: a run of consecutive Mamba layers is ONE dict
whose leaves carry the run's layers on a leading axis, an attention
layer a dict of its own. Matrices are ``(in, out)``; ``A_log`` is
``(N, d_inner)`` and the conv taps ``(K, d_inner)``, the transposes of
the published tensors. Computed a layer at a time (each layer's
weights cast to float32 when it runs), attention a block of query rows
at a time, the head on the rows asked for only (``rows=``), the
sequence padded to a whole number of :data:`PAD` positions (the padding
follows the sequence and cannot reach it). Nothing here imports the
program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 512  # query rows of an attention layer computed at a time
PAD = 1024       # a sequence is padded to a multiple of this: few shapes

MAMBA_LEAVES = ("ln1_g", "ln2_g", "w_in", "conv_w", "conv_b", "w_x",
                "dt_norm", "b_norm", "c_norm", "w_dt", "b_dt", "a_log",
                "d_skip", "wo", "w_gate", "w_up", "w_down")
ATTENTION_LEAVES = ("ln1_g", "ln2_g", "wq", "wk", "wv", "wo", "w_gate",
                    "w_up", "w_down")


def sizes(cfg):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, H=H, KVH=cfg["num_key_value_heads"], hd=d // H,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"], Di=cfg["mamba_expand"] * d,
                N=cfg["mamba_d_state"], R=cfg["mamba_dt_rank"],
                K=cfg["mamba_d_conv"])


def layer_kinds(cfg):
    """``"attention"`` or ``"mamba"``, a layer."""
    return ["attention" if i % cfg["attn_layer_period"]
            == cfg["attn_layer_offset"] else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def runs(cfg):
    """``[(kind, first layer, layers)]``: consecutive Mamba layers are
    one run, an attention layer a run of its own."""
    out = []
    for i, kind in enumerate(layer_kinds(cfg)):
        if out and kind == "mamba" and out[-1][0] == "mamba":
            out[-1][2] += 1
        else:
            out.append([kind, i, 1])
    return [tuple(r) for r in out]


def leaf_shapes(cfg, kind, n):
    """{leaf: (shape, kind of draw)} of a run of ``n`` layers (a Mamba
    run's leaves with a leading axis, an attention layer's without)."""
    z = sizes(cfg)
    d, ff = z["d"], z["ff"]
    common = {"ln1_g": ((d,), "gain"), "ln2_g": ((d,), "gain"),
              "w_gate": ((d, ff), "matrix"), "w_up": ((d, ff), "matrix"),
              "w_down": ((ff, d), "matrix")}
    if kind == "attention":
        H, KVH, hd = z["H"], z["KVH"], z["hd"]
        return {**common, "wq": ((d, H * hd), "matrix"),
                "wk": ((d, KVH * hd), "matrix"),
                "wv": ((d, KVH * hd), "matrix"),
                "wo": ((H * hd, d), "matrix")}
    Di, N, R, K = z["Di"], z["N"], z["R"], z["K"]
    one = {**common, "w_in": ((d, 2 * Di), "matrix"),
           "conv_w": ((K, Di), "matrix"), "conv_b": ((Di,), "matrix"),
           "w_x": ((Di, R + 2 * N), "matrix"), "dt_norm": ((R,), "gain"),
           "b_norm": ((N,), "gain"), "c_norm": ((N,), "gain"),
           "w_dt": ((R, Di), "matrix"), "b_dt": ((Di,), "dt_bias"),
           "a_log": ((N, Di), "a_log"), "d_skip": ((Di,), "ones"),
           "wo": ((Di, d), "matrix")}
    return {k: ((n,) + shape, draw) for k, (shape, draw) in one.items()}


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std", "dtype"))
def _draw(key, shape, kind, std, dtype):
    """One leaf. A stacked leaf is drawn a layer at a time, so that the
    float32 draw of a run's matrices never stands whole beside the
    weights."""
    def one(k, shape):
        if kind == "a_log":   # log(1..N) down the rows, every channel
            n = jnp.arange(1, shape[-2] + 1, dtype=jnp.float32)
            return jnp.broadcast_to(jnp.log(n)[:, None], shape)
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "dt_bias":  # softplus^-1 of a log-uniform step
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        z = jax.random.normal(k, shape, jnp.float32) * std
        return 1.0 + z if kind == "gain" else z

    if len(shape) < 3 or kind not in ("matrix",):
        return one(key, shape).astype(dtype)
    return jax.lax.map(lambda i: one(jax.random.fold_in(key, i), shape[1:])
                       .astype(dtype), jnp.arange(shape[0]))


def init_params(cfg, seed, dtype):
    std = float(cfg.get("initializer_range", 0.02))
    key = jax.random.PRNGKey(seed % (2 ** 31))
    z = sizes(cfg)

    def leaf(i, shape, kind):
        return _draw(jax.random.fold_in(key, i), tuple(shape), kind, std,
                     str(dtype))

    layers, at = [], 2
    for kind, _, n in runs(cfg):
        shapes = leaf_shapes(cfg, kind, n)
        layers.append({name: leaf(at + j, *shapes[name])
                       for j, name in enumerate(sorted(shapes))})
        at += len(shapes)
    return {"embed": leaf(0, (z["V"], z["d"]), "matrix"),
            "lnf_g": leaf(1, (z["d"],), "gain"), "layers": layers}


def _quantizer(quant):
    """``low(a)``: a matrix product's operand rounded to the lower
    precision (fp8 scaled a tensor)."""
    if quant is None:
        return lambda a: a
    if quant == "bf16":
        return lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "fp8":
        def low(a):
            s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
            return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return low
    raise ValueError(f"unknown precision {quant!r}")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _mm(low, x, w):
    return jnp.einsum("...i,io->...o", low(x), low(w), precision=_HI)


def _mlp(x, p, eps, low):
    h = _rms(x, p["ln2_g"], eps)
    act = jax.nn.silu(_mm(low, h, p["w_gate"])) * _mm(low, h, p["w_up"])
    return x + _mm(low, act, p["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _mamba_layer(x, p, eps, quant):
    """One Mamba layer over one sequence ``x`` ``(T, d)`` float32."""
    low = _quantizer(quant)
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    T = x.shape[0]
    K, Di = p["conv_w"].shape
    N = p["a_log"].shape[0]
    R = p["w_dt"].shape[0]
    h = _rms(x, p["ln1_g"], eps)
    xz = _mm(low, h, p["w_in"])
    xs, z = xz[:, :Di], xz[:, Di:]
    xx = jnp.concatenate([jnp.zeros((K - 1, Di), jnp.float32), xs])
    pre = p["conv_b"]
    for k in range(K):  # tap k meets the input K - 1 - k positions back
        pre = pre + p["conv_w"][k] * xx[k:k + T]
    xc = jax.nn.silu(pre)
    proj = _mm(low, xc, p["w_x"])
    dt_r = _rms(proj[:, :R], p["dt_norm"], eps)
    B = _rms(proj[:, R:R + N], p["b_norm"], eps)
    C = _rms(proj[:, R + N:], p["c_norm"], eps)
    dt = jax.nn.softplus(_mm(low, dt_r, p["w_dt"]) + p["b_dt"])
    A = -jnp.exp(p["a_log"])                                # (N, Di)

    def position(s, xs_t):
        x_t, dt_t, b_t, c_t = xs_t
        s = jnp.exp(dt_t[None] * A) * s + (dt_t * x_t)[None] * b_t[:, None]
        return s, jnp.einsum("n,nd->d", c_t, s, precision=_HI) \
            + p["d_skip"] * x_t

    _, y = jax.lax.scan(position, jnp.zeros((N, Di), jnp.float32),
                        (xc, dt, B, C))
    x = x + _mm(low, y * jax.nn.silu(z), p["wo"])
    return _mlp(x, p, eps, low)


@functools.partial(jax.jit, static_argnames=("H", "KVH", "eps", "quant"))
def _attention_layer(x, p, H, KVH, eps, quant):
    """One attention layer over one sequence ``x`` ``(T, d)``, a block of
    :data:`ROW_BLOCK` query rows at a time (``T`` a multiple of it)."""
    low = _quantizer(quant)
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    T, d = x.shape
    hd = d // H
    G = H // KVH
    h = _rms(x, p["ln1_g"], eps)
    q = low(_mm(low, h, p["wq"]).reshape(T, KVH, G, hd))
    k = low(_mm(low, h, p["wk"]).reshape(T, KVH, hd))
    v = low(_mm(low, h, p["wv"]).reshape(T, KVH, hd))
    B = min(ROW_BLOCK, T)
    cols = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, B)       # (B, KVH, G, hd)
        s = jnp.einsum("bngd,tnd->ngbt", qb, k, precision=_HI) / hd ** 0.5
        seen = cols[None, :] <= (start + jnp.arange(B))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ngbt,tnd->bngd", low(w), v, precision=_HI)

    o = jax.lax.map(block, jnp.arange(T // B) * B).reshape(T, H * hd)
    x = x + _mm(low, o, p["wo"])
    return _mlp(x, p, eps, low)


@functools.partial(jax.jit, static_argnames=("count", "quant"))
def _head(embed, hidden, start, count, quant):
    """Logits of ``count`` rows of ``hidden`` from ``start`` against the
    embedding's transpose, the vocabulary a slab at a time."""
    low = _quantizer(quant)
    rows = low(jax.lax.dynamic_slice_in_dim(hidden, start, count))
    V = embed.shape[0]
    parts = 8 if V % 8 == 0 else 1
    whole = low(embed.astype(jnp.float32))

    def slab(i):
        w = jax.lax.dynamic_slice_in_dim(whole, i * (V // parts), V // parts)
        return jnp.einsum("bi,vi->bv", rows, w, precision=_HI)

    out = jax.lax.map(slab, jnp.arange(parts))          # (parts, count, V/p)
    return out.transpose(1, 0, 2).reshape(count, V)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, g, eps):
    return _rms(x, g.astype(jnp.float32), eps)


def logits(params, tokens, cfg, quant=None, rows=None):
    """(B, T) token ids -> float32 logits; position ``t`` predicts
    token ``t + 1``. Whole ``(B, T, V)``, or with ``rows=(start,
    count)`` those rows only, ``(B, count, V)``."""
    z = sizes(cfg)
    eps = float(cfg["rms_norm_eps"])
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[1]
    start, count = rows if rows is not None else (0, T)
    Tp = -(-T // PAD) * PAD
    out = []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = params["embed"][jnp.pad(seq, (0, Tp - T))].astype(jnp.float32)
            for (kind, _, n), run in zip(runs(cfg), params["layers"]):
                if kind == "attention":
                    x = _attention_layer(x, run, z["H"], z["KVH"], eps,
                                         quant)
                    continue
                for i in range(n):
                    x = _mamba_layer(x, {k: v[i] for k, v in run.items()},
                                     eps, quant)
            hidden = _final(x, params["lnf_g"], eps)
            out.append(_head(params["embed"], hidden, start, int(count),
                             quant))
    return jnp.stack(out)
