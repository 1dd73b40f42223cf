"""Plain reference: a decoder language model of latent-attention layers
whose MLPs are sparse experts after the leading dense layers
(jdopensource/JoyAI-LLM-Flash; the DeepSeek-V3 line's layer), forward
pass in straightforward ``jax.numpy``, float32, ``highest`` precision,
in the EXPANDED form over the whole sequence: no cache, no absorbed
products, no sorting of tokens by expert, no grouped product, no
buckets, no batching of requests.

From the published ``config.json``'s keys, for token ``t`` of a
sequence and layer input ``x`` (RMSNorm eps ``rms_norm_eps``, no biases
anywhere):

1. ``h = RMSNorm(x; ln1_g)``; ``c_q = RMSNorm(h W_qa; q_a_norm)``
   (``q_lora_rank``); ``[q_nope | q_rope] = c_q W_qb``,
   ``num_attention_heads`` heads of ``qk_nope_head_dim +
   qk_rope_head_dim``.
2. ``[c_kv | k_r] = h W_kva`` (``kv_lora_rank + qk_rope_head_dim``);
   ``c_kv <- RMSNorm(c_kv; kv_a_norm)``; ``[k_nope | v] = c_kv W_kvb``,
   a head ``qk_nope_head_dim + v_head_dim``.
3. ``q_rope`` and ``k_r`` rotated at position ``t`` with ``rope_theta``
   over ``qk_rope_head_dim`` dimensions in interleaved pairs ``(2i, 2i
   + 1)`` (``rope_interleave`` true); ``k_r`` is ONE head that every
   query head shares. ``rope_scaling`` null: no factor on the scale.
4. Scores ``(q_nope . k_nope + q_rope . k_r) / sqrt(qk_head_dim)``,
   causal softmax, ``o = sum p v``; ``x <- x + concat_heads(o) W_o``.
5. ``h' = RMSNorm(x; ln2_g)``. The first ``first_k_dense_replace``
   layers: ``x <- x + (silu(h' W_gate) * (h' W_up)) W_down`` at
   ``intermediate_size``. Every later layer: ``s = sigmoid(h' W_r)``
   over ``n_routed_experts`` (float32 always: the published code
   computes the router in float32); chosen = the ``num_experts_per_tok``
   largest of ``s + b`` (``b`` the balancing bias; ``n_group`` =
   ``topk_group`` = 1, no group limit); ``w = s[chosen] / (sum
   s[chosen] + 1e-20) * routed_scaling_factor`` (``norm_topk_prob``;
   ``b`` does not enter ``w``); ``x <- x + sum_e w_e E_e(h') +
   E_shared(h')``, every ``E`` the gated form at
   ``moe_intermediate_size`` (the shared one ``n_shared_experts`` times
   as wide). HERE every expert is applied to every token and weighted
   by ``w``, zero where not chosen.
6. After the last layer RMSNorm (``lnf_g``), then an untied head.

Not built, here or in the program: the multi-token prediction module
(``num_nextn_predict_layers``), which the model's own logits never run.

Computed in blocks so that a request at the published widths fits
beside the weights: a layer's small leaves are cast to float32 a layer
at a time, attention runs a block of query rows at a time, ONE expert's
three matrices are cast to float32 at a time (a scan over the layer's
stacked experts: all of them in float32 would be 4.8 GB), and the head is applied to the rows asked for
only (``rows=``).

Nothing here imports the program. ``init_params`` draws the benchmark's
own weights on the device from the seed, every layer's leaves on their
own under ``layer<i>.<leaf>`` (an expert layer's experts stacked on the
leaf's first axis; the program serves the leaves as they are, so the
device holds one copy), each leaf from a key of its own name, so that a
net cut in depth holds the first layers of the deeper one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256  # query rows computed at a time
EXPERT_GROUP = 8  # experts applied in one step of the scan over them
PAD_TO = 512  # a sequence is padded to whole such: few shapes to compile

ATTENTION_LEAVES = ("ln1_g", "w_qa", "q_a_norm", "w_qb", "w_kva", "kv_a_norm",
                    "w_kvb", "wo", "ln2_g")
DENSE_LEAVES = ATTENTION_LEAVES + ("w_gate", "w_up", "w_down")
MOE_LEAVES = ATTENTION_LEAVES + ("router", "router_bias", "we_gate", "we_up",
                                 "we_down", "ws_gate", "ws_up", "ws_down")


def sizes(cfg):
    return dict(
        d=cfg["hidden_size"], H=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], ff=cfg["intermediate_size"],
        E=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        eff=cfg["moe_intermediate_size"], shared=cfg["n_shared_experts"],
        V=cfg["vocab_size"], L=cfg["num_hidden_layers"],
        dense=cfg["first_k_dense_replace"])


def layer_shapes(cfg):
    """{leaf: (shape, kind)} of ONE layer: every leaf a dense layer or
    an expert layer has."""
    z = sizes(cfg)
    d, H, E, eff = z["d"], z["H"], z["E"], z["eff"]
    sff = z["shared"] * eff
    return {
        "ln1_g": ((d,), "gain"), "ln2_g": ((d,), "gain"),
        "w_qa": ((d, z["q_rank"]), "matrix"),
        "q_a_norm": ((z["q_rank"],), "gain"),
        "w_qb": ((z["q_rank"], H * (z["nope"] + z["rope"])), "matrix"),
        "w_kva": ((d, z["kv_rank"] + z["rope"]), "matrix"),
        "kv_a_norm": ((z["kv_rank"],), "gain"),
        "w_kvb": ((z["kv_rank"], H * (z["nope"] + z["vd"])), "matrix"),
        "wo": ((H * z["vd"], d), "matrix"),
        "w_gate": ((d, z["ff"]), "matrix"), "w_up": ((d, z["ff"]), "matrix"),
        "w_down": ((z["ff"], d), "matrix"),
        "router": ((d, E), "matrix"), "router_bias": ((E,), "bias"),
        "we_gate": ((E, d, eff), "matrix"), "we_up": ((E, d, eff), "matrix"),
        "we_down": ((E, eff, d), "matrix"),
        "ws_gate": ((d, sff), "matrix"), "ws_up": ((d, sff), "matrix"),
        "ws_down": ((sff, d), "matrix"),
    }


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std",
                                             "bias_std", "dtype"))
def _draw(key, shape, kind, std, bias_std, dtype):
    """One leaf, drawn a slab of its first axis at a time so that the
    float32 draw of a stack of experts never stands whole beside the
    weights."""
    parts = next(p for p in (8, 4, 2, 1) if shape[0] % p == 0)
    slab = (shape[0] // parts,) + tuple(shape[1:])

    def one(j):
        k = jax.random.fold_in(key, j)
        if kind == "bias":  # the router's, float32 whatever the rest
            return jax.random.normal(k, slab, jnp.float32) * bias_std
        z = jax.random.normal(k, slab, jnp.float32) * std
        return ((1.0 + z) if kind == "gain" else z).astype(dtype)

    return jax.lax.map(one, jnp.arange(parts)).reshape(shape)


def layer_leaves(cfg, i):
    """The leaves layer ``i`` has: a dense layer's or an expert
    layer's."""
    return DENSE_LEAVES if i < cfg["first_k_dense_replace"] else MOE_LEAVES


def init_params(cfg, seed, dtype):
    z = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    bias_std = float(cfg.get("router_bias_std", 0.1))
    key = jax.random.PRNGKey(seed % (2 ** 31))
    shapes = layer_shapes(cfg)
    every = sorted(shapes)
    plan = {"embed": ((z["V"], z["d"]), "matrix"),
            "head": ((z["d"], z["V"]), "matrix"),
            "lnf_g": ((z["d"],), "gain")}
    keys = {name: jax.random.fold_in(key, i) for i, name in enumerate(plan)}
    for i in range(z["L"]):
        for leaf in layer_leaves(cfg, i):
            name = f"layer{i}.{leaf}"
            plan[name] = shapes[leaf]
            # a key a (layer, leaf), whatever the depth
            keys[name] = jax.random.fold_in(
                key, 1000 + i * len(every) + every.index(leaf))
    return {name: _draw(keys[name], tuple(shape), kind, std, bias_std,
                        str(dtype))
            for name, (shape, kind) in plan.items()}


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


def _rope_pairs(x, pos, theta):
    """Rotary positions over the last axis in interleaved pairs ``(2i,
    2i + 1)``; ``x`` is ``(T, ..., width)``, ``pos`` ``(T,)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * freq
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def _blocks(n, block):
    """``n`` rows padded up to whole blocks: (padded, block)."""
    block = min(block, n)
    return -(-n // block) * block, block


def _attend(q_nope, q_rope, k_nope, k_rope, v, scale, low):
    """Causal attention for one sequence, a block of query rows at a
    time. ``q_nope``/``k_nope`` ``(T, H, nope)``, ``q_rope`` ``(T, H,
    rope)``, ``k_rope`` ``(T, rope)`` (one head for all), ``v`` ``(T,
    H, vd)`` -> ``(T, H, vd)``."""
    T = q_nope.shape[0]
    Tp, B = _blocks(T, ROW_BLOCK)
    pad = ((0, Tp - T), (0, 0), (0, 0))
    qn, qr = jnp.pad(low(q_nope), pad), jnp.pad(low(q_rope), pad)
    kn, kr, vq = low(k_nope), low(k_rope), low(v)
    cols = jnp.arange(T)

    def block(start):
        a = jax.lax.dynamic_slice_in_dim(qn, start, B)
        b = jax.lax.dynamic_slice_in_dim(qr, start, B)
        s = (jnp.einsum("bhd,thd->hbt", a, kn, precision=_HI)
             + jnp.einsum("bhd,td->hbt", b, kr, precision=_HI)) * scale
        seen = cols[None, :] <= (start + jnp.arange(B))[:, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hbt,thd->bhd", low(p), vq, precision=_HI)

    out = jax.lax.map(block, jnp.arange(Tp // B) * B)
    return out.reshape(Tp, *v.shape[1:])[:T]


def _gated(h, wg, wu, wd, low):
    """``(silu(h W_g) * (h W_u)) W_d`` with every operand through
    ``low`` (``h`` already is)."""
    act = jax.nn.silu(jnp.einsum("ti,io->to", h, low(wg), precision=_HI)) \
        * jnp.einsum("ti,io->to", h, low(wu), precision=_HI)
    return jnp.einsum("ti,io->to", low(act), low(wd), precision=_HI)


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "scale",
                                             "quant", "layers"))
def _hidden(params, tokens, z, eps, theta, scale, quant, layers):
    """(T,) token ids -> (T, d) float32 after the final norm, through
    the first ``layers`` layers."""
    z = dict(z)
    low = _quantizer(quant)

    def mm(x, w):
        return jnp.einsum("...i,io->...o", low(x), low(w), precision=_HI)

    T = tokens.shape[0]
    pos = jnp.arange(T)
    H, nope, rank, vd = z["H"], z["nope"], z["kv_rank"], z["vd"]

    def attention(x, p):
        h = _rms(x, p["ln1_g"], eps)
        c_q = _rms(mm(h, p["w_qa"]), p["q_a_norm"], eps)
        q = mm(c_q, p["w_qb"]).reshape(T, H, nope + z["rope"])
        kv = mm(h, p["w_kva"])
        c_kv = _rms(kv[:, :rank], p["kv_a_norm"], eps)
        k_rope = _rope_pairs(kv[:, rank:], pos, theta)
        q_rope = _rope_pairs(q[..., nope:], pos, theta)
        kvb = mm(c_kv, p["w_kvb"]).reshape(T, H, nope + vd)
        o = _attend(q[..., :nope], q_rope, kvb[..., :nope], k_rope,
                    kvb[..., nope:], scale, low)
        return x + mm(o.reshape(T, H * vd), p["wo"])

    def f32(p):
        return {k: v.astype(jnp.float32) for k, v in p.items()}

    def experts(x, p):
        h = _rms(x, p["ln2_g"].astype(jnp.float32), eps)
        # the router in float32 whatever the rest is computed in
        s = jax.nn.sigmoid(jnp.einsum(
            "ti,ie->te", h, p["router"].astype(jnp.float32), precision=_HI))
        _, chosen = jax.lax.top_k(s + p["router_bias"], z["k"])
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
            * z["route_scale"]
        dense_w = jnp.zeros_like(s).at[jnp.arange(T)[:, None], chosen].set(w)
        hq = low(h)

        def expert(y, xs):  # one expert's three matrices in float32
            e, wg, wu, wd = xs
            return y + dense_w[:, e][:, None] * _gated(
                hq, *(m.astype(jnp.float32) for m in (wg, wu, wd)),
                low), None

        def some(y, xs):  # a few experts an iteration: fewer, longer steps
            return jax.lax.scan(expert, y, xs, unroll=True)

        n = next(g for g in (EXPERT_GROUP, 1) if z["E"] % g == 0)
        y, _ = jax.lax.scan(some, jnp.zeros_like(x), jax.tree_util.tree_map(
            lambda a: a.reshape(z["E"] // n, n, *a.shape[1:]),
            (jnp.arange(z["E"]), p["we_gate"], p["we_up"], p["we_down"])))
        if z["shared"]:
            y = y + _gated(hq, *(p[k].astype(jnp.float32) for k in (
                "ws_gate", "ws_up", "ws_down")), low)
        return x + y

    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(layers):
        p = {k[len(f"layer{i}."):]: v for k, v in params.items()
             if k.startswith(f"layer{i}.")}
        x = attention(x, f32({k: p[k] for k in ATTENTION_LEAVES}))
        if i < z["dense"]:
            h = low(_rms(x, p["ln2_g"].astype(jnp.float32), eps))
            x = x + _gated(h, *(p[k].astype(jnp.float32) for k in (
                "w_gate", "w_up", "w_down")), low)
        else:
            x = experts(x, p)
    return _rms(x, params["lnf_g"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("count", "quant"))
def _head(head, hidden, start, count, quant):
    """Logits of ``count`` rows of ``hidden`` from ``start``, the
    vocabulary a slab at a time (the head in float32 is 1 GB)."""
    low = _quantizer(quant)
    rows = low(jax.lax.dynamic_slice_in_dim(hidden, start, count))
    d, V = head.shape
    parts = 8 if V % 8 == 0 else 1
    amax = jnp.max(jnp.abs(head)).astype(jnp.float32)

    def slab(i):
        w = jax.lax.dynamic_slice_in_dim(head, i * (V // parts), V // parts,
                                         axis=1).astype(jnp.float32)
        return jnp.einsum("bi,io->bo", rows, low(w, amax), precision=_HI)

    out = jax.lax.map(slab, jnp.arange(parts))          # (parts, count, V/p)
    return out.transpose(1, 0, 2).reshape(count, V)


def logits(params, tokens, cfg, quant=None, rows=None, layers=None):
    """(B, T) token ids -> float32 logits; position ``t`` predicts
    token ``t + 1``. Whole ``(B, T, V)``, or with ``rows=(start,
    count)`` those rows only, ``(B, count, V)``. ``layers``: through
    the first so many layers only (the test of the cut in depth)."""
    z = sizes(cfg)
    z["route_scale"] = float(cfg["routed_scaling_factor"])
    depth = z.pop("L")
    n = depth if layers is None else int(layers)
    tokens = jnp.asarray(tokens, jnp.int32)
    start, count = rows if rows is not None else (0, tokens.shape[1])
    scale = (z["nope"] + z["rope"]) ** -0.5
    # causal: what is appended changes no row before it
    tokens = jnp.pad(tokens, ((0, 0), (0, -tokens.shape[1] % PAD_TO)))
    out = []
    for seq in tokens:
        hidden = _hidden(params, seq, tuple(sorted(z.items())),
                         float(cfg["rms_norm_eps"]),
                         float(cfg["rope_theta"]), scale, quant, n)
        out.append(_head(params["head"], hidden, start, int(count), quant))
    return jnp.stack(out)
