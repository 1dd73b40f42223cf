"""Plain reference: a decoder language model whose every layer is a
power-retention layer (manifestai/Brumby-14B-Base), forward pass in
straightforward ``jax.numpy``, float32, ``highest`` precision, in the
ATTENTION form over the whole sequence: no state, no chunks, no cache,
no buckets, no batching of requests, no feature map.

The shapes are the published ``config.json``'s, which repeats
Qwen3-14B's letter for letter: RMSNorm (eps ``rms_norm_eps``) before
the mixer and before the MLP, ``q``/``k``/``v``/``o`` projections
without biases (``attention_bias`` false) with ``num_attention_heads``
query heads and ``num_key_value_heads`` KV heads of ``head_dim``, a
gated SiLU MLP of ``intermediate_size``, a final RMSNorm and a head of
its own (``tie_word_embeddings`` false). For token ``t`` of a sequence,
layer input ``x_t``:

1. ``h = RMSNorm(x_t; ln1_g)``; ``q = h W_q``, ``k = h W_k``,
   ``v = h W_v``, ``gamma = h W_g + b_g`` (one a KV head).
2. ``q <- RoPE(RMSNorm(q; q_norm), t)``, ``k <- RoPE(RMSNorm(k;
   k_norm), t)``: per-head norms over ``head_dim``, then rotary
   positions in the half-split form with ``rope_theta``.
3. ``log g_t = log sigmoid(gamma)``; ``G_i = sum_{l <= i} log g_l``.
4. Power retention of degree 2 (Buckman, Gelada and Zhang, "Scaling
   Context Requires Rethinking Attention", arXiv:2507.04239), for KV
   head ``n`` and each query head ``m`` of its group:
   ``o_{i,m} = sum_{j<=i} w_{ij} v_j / sum_{j<=i} w_{ij}`` with
   ``w_{ij} = exp(G_i - G_j) (q_{i,m} . k_j)^2``.
5. ``x <- x + concat_m(o_m) W_o``; ``x <- x + (silu(h' W_gate) *
   (h' W_up)) W_down`` with ``h' = RMSNorm(x; ln2_g)``.

Assumed, as the configuration file lists with reasons (the published
config gives the shapes, not these): the degree 2; the gate one scalar
a KV head from a linear map of ``h`` with a bias; RoPE and the per-head
norms kept from Qwen3-14B; no epsilon in the denominator (the ``j = i``
term keeps it positive); ``initializer_range`` 0.02; and ``b_g`` drawn
so that ``sigmoid(b_g)`` lies log-uniformly between ``1 - 1/100`` and
``1 - 1/10,000`` (with a zero bias a random model forgets in two
tokens, and neither a state dropped between chunks nor a stale state
in a re-used slot would reach the logits).

Computed in blocks so that 8,700 tokens at the published widths fit
beside the weights: a layer's weights are cast to float32 one layer at
a time (a scan over the stacked leaves), attention runs a KV head and a
block of query rows at a time, the MLP a block of rows at a time, and
the head is applied to the rows asked for only (``rows=``).

Nothing here imports the program; ``init_params`` draws the benchmark's
own weights on the device from the seed, the layers' leaves stacked on
a leading axis (the program serves them as they are, so the device
holds one copy).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 512  # query rows, and MLP rows, computed at a time


def sizes(cfg):
    return dict(d=cfg["hidden_size"], H=cfg["num_attention_heads"],
                KVH=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def leaf_shapes(cfg):
    """{leaf: (shape, kind)}; a layer leaf's first axis is the layer."""
    z = sizes(cfg)
    d, H, KVH, hd, ff, V, L = (z[k] for k in ("d", "H", "KVH", "hd", "ff",
                                              "V", "L"))
    return {
        "embed": ((V, d), "matrix"), "head": ((d, V), "matrix"),
        "lnf_g": ((d,), "gain"),
        "ln1_g": ((L, d), "gain"), "ln2_g": ((L, d), "gain"),
        "wq": ((L, d, H * hd), "matrix"), "wk": ((L, d, KVH * hd), "matrix"),
        "wv": ((L, d, KVH * hd), "matrix"), "wo": ((L, H * hd, d), "matrix"),
        "q_norm": ((L, hd), "gain"), "k_norm": ((L, hd), "gain"),
        "wg": ((L, d, KVH), "matrix"), "bg": ((L, KVH), "gate_bias"),
        "w_gate": ((L, d, ff), "matrix"), "w_up": ((L, d, ff), "matrix"),
        "w_down": ((L, ff, d), "matrix"),
    }


LAYER_LEAVES = ("ln1_g", "ln2_g", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                "wg", "bg", "w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std", "dtype"))
def _draw(key, shape, kind, std, dtype):
    """One leaf, drawn a slab of its first axis at a time so that the
    float32 draw of a matrix of billions never stands whole beside the
    weights."""
    parts = next(p for p in (8, 4, 2, 1) if shape[0] % p == 0)
    slab = (shape[0] // parts,) + tuple(shape[1:])

    def one(i):
        k = jax.random.fold_in(key, i)
        if kind == "gate_bias":
            # 1 - sigmoid(b) log-uniform between 1/100 and 1/10,000
            forget = 10.0 ** -jax.random.uniform(k, slab, jnp.float32,
                                                 2.0, 4.0)
            z = jnp.log((1.0 - forget) / forget)
        else:
            z = jax.random.normal(k, slab, jnp.float32) * std
            if kind == "gain":
                z = 1.0 + z
        return z.astype(dtype)

    return jax.lax.map(one, jnp.arange(parts)).reshape(shape)


def init_params(cfg, seed, dtype):
    std = float(cfg.get("initializer_range", 0.02))
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return {name: _draw(jax.random.fold_in(key, i), tuple(shape), kind, std,
                        str(dtype))
            for i, (name, (shape, kind)) in enumerate(
                sorted(leaf_shapes(cfg).items()))}


def _quantizer(quant):
    """``low(a, amax)``: a matrix product's operand rounded to the lower
    precision; ``amax`` is the whole operand's largest magnitude where
    only a block of it is at hand (fp8 is scaled a tensor)."""
    if quant is None:
        return lambda a, amax=None: a
    if quant == "bf16":
        return lambda a, amax=None: a.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "fp8":
        def low(a, amax=None):
            if amax is None:
                amax = jnp.max(jnp.abs(a))
            s = jnp.maximum(amax, 1e-30) / 448.0
            return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return low
    raise ValueError(f"unknown precision {quant!r}")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Half-split rotary positions; ``x`` is ``(T, heads, hd)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _blocks(n, block):
    """``n`` rows padded up to whole blocks: (padded, block)."""
    block = min(block, n)
    return -(-n // block) * block, block


def _retain(q, k, v, log_g, low):
    """The attention form for one sequence. ``q`` ``(T, KVH, G, hd)``,
    ``k``/``v`` ``(T, KVH, hd)``, ``log_g`` ``(T, KVH)`` -> ``(T, KVH,
    G, hd)``; a KV head and a block of query rows at a time."""
    T = q.shape[0]
    Tp, B = _blocks(T, ROW_BLOCK)
    run = jnp.cumsum(log_g, axis=0)                       # G_i
    qp = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0), (0, 0)))
    runp = jnp.pad(run, ((0, Tp - T), (0, 0)))
    cols = jnp.arange(T)

    def head(xs):
        qn, kn, vn, rn, rpn = xs       # (Tp, G, hd) (T, hd) (T, hd) (T,) (Tp,)
        kq, vq = low(kn), low(vn)

        def block(start):
            qb = jax.lax.dynamic_slice_in_dim(qn, start, B)    # (B, G, hd)
            rb = jax.lax.dynamic_slice_in_dim(rpn, start, B)   # (B,)
            s = jnp.einsum("bgd,td->gbt", low(qb), kq, precision=_HI)
            seen = cols[None, :] <= (start + jnp.arange(B))[:, None]
            decay = jnp.exp(jnp.where(seen, rb[:, None] - rn[None, :], 0.0))
            w = jnp.where(seen, decay, 0.0)[None] * s * s
            num = jnp.einsum("gbt,td->bgd", low(w), vq, precision=_HI)
            return num / jnp.sum(w, axis=-1).T[..., None]

        out = jax.lax.map(block, jnp.arange(Tp // B) * B)
        return out.reshape(Tp, *qn.shape[1:])[:T]

    o = jax.lax.map(head, (qp.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                           v.transpose(1, 0, 2), run.T, runp.T))
    return o.transpose(1, 0, 2, 3)


@functools.partial(jax.jit, static_argnames=("H", "KVH", "hd", "eps", "theta",
                                             "quant"))
def _hidden(params, tokens, H, KVH, hd, eps, theta, quant):
    """(T,) token ids -> (T, d) float32 after the final norm."""
    low = _quantizer(quant)

    def mm(x, w):
        return jnp.einsum("...i,io->...o", low(x), low(w), precision=_HI)

    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = params["embed"][tokens].astype(jnp.float32)
    Tp, B = _blocks(T, ROW_BLOCK)

    def layer(x, p):
        p = {k: v.astype(jnp.float32) for k, v in p.items()}
        h = _rms(x, p["ln1_g"], eps)
        q = mm(h, p["wq"]).reshape(T, H, hd)
        k = mm(h, p["wk"]).reshape(T, KVH, hd)
        v = mm(h, p["wv"]).reshape(T, KVH, hd)
        log_g = jax.nn.log_sigmoid(mm(h, p["wg"]) + p["bg"])
        q = _rope(_rms(q, p["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, p["k_norm"], eps), pos, theta)
        o = _retain(q.reshape(T, KVH, H // KVH, hd), k, v, log_g, low)
        x = x + mm(o.reshape(T, H * hd), p["wo"])
        h = jnp.pad(_rms(x, p["ln2_g"], eps), ((0, Tp - T), (0, 0)))
        # fp8 is scaled a tensor: a block of rows keeps the whole's scale
        amax = jnp.max(jnp.abs(h))
        wg, wu, wd = low(p["w_gate"]), low(p["w_up"]), low(p["w_down"])

        def rows(hb):
            hb = low(hb, amax)
            return jax.nn.silu(
                jnp.einsum("bi,io->bo", hb, wg, precision=_HI)) \
                * jnp.einsum("bi,io->bo", hb, wu, precision=_HI)

        act = jax.lax.map(rows, h.reshape(Tp // B, B, -1)).reshape(Tp, -1)
        amax2 = jnp.max(jnp.abs(act))
        down = jax.lax.map(
            lambda ab: jnp.einsum("bi,io->bo", low(ab, amax2), wd,
                                  precision=_HI),
            act.reshape(Tp // B, B, -1)).reshape(Tp, -1)
        return x + down[:T], None

    layers = {k: params[k] for k in LAYER_LEAVES}
    x, _ = jax.lax.scan(layer, x, layers)
    return _rms(x, params["lnf_g"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("count", "quant"))
def _head(head, hidden, start, count, quant):
    """Logits of ``count`` rows of ``hidden`` from ``start``, the
    vocabulary a slab at a time (the head in float32 is 3 GB)."""
    low = _quantizer(quant)
    rows = jax.lax.dynamic_slice_in_dim(hidden, start, count)
    rows = low(rows)
    d, V = head.shape
    parts = 8 if V % 8 == 0 else 1
    amax = jnp.max(jnp.abs(head)).astype(jnp.float32)

    def slab(i):
        w = jax.lax.dynamic_slice_in_dim(head, i * (V // parts), V // parts,
                                         axis=1).astype(jnp.float32)
        return jnp.einsum("bi,io->bo", rows, low(w, amax), precision=_HI)

    out = jax.lax.map(slab, jnp.arange(parts))          # (parts, count, V/p)
    return out.transpose(1, 0, 2).reshape(count, V)


def logits(params, tokens, cfg, quant=None, rows=None):
    """(B, T) token ids -> float32 logits; position ``t`` predicts
    token ``t + 1``. Whole ``(B, T, V)``, or with ``rows=(start,
    count)`` those rows only, ``(B, count, V)``: at the published
    vocabulary a whole long sequence's logits are gigabytes."""
    z = sizes(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    start, count = rows if rows is not None else (0, tokens.shape[1])
    out = []
    for seq in tokens:
        hidden = _hidden(params, seq, z["H"], z["KVH"], z["hd"],
                         float(cfg["rms_norm_eps"]),
                         float(cfg["rope_theta"]), quant)
        out.append(_head(params["head"], hidden, start, int(count), quant))
    return jnp.stack(out)
