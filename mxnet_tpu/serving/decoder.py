"""The functional decoder LM of the generation stack: one class that
its configuration drives.

This is deliberately NOT a Gluon block: the decode fast path needs
pure ``(params, state) -> (logits, state)`` functions it can close
into AOT-compiled prefill/decode executables, with the cache's arrays
threaded through as donated operands. The class carries the
hyperparameters and the (deterministically seeded) weights; everything
the device runs comes out of :meth:`prefill_fn` / :meth:`decode_step_fn`
/ :meth:`forward_fn` as pure closures over nothing but shapes.

What the configuration chooses, each on its own:

- ``norm``: ``"layernorm"`` (gain and bias) or ``"rmsnorm"`` (gain);
- ``positions``: ``"learned"`` (a table added to the embedding) or
  ``"rope"`` (rotary, half-split form, applied to queries and keys);
- ``mlp``: ``"gelu"`` (two matrices with biases), ``"swiglu"``
  (``silu(x W_gate) * (x W_up)`` through ``W_down``, no biases) or
  ``"experts"`` (a sigmoid router's top-k of ``experts`` gated MLPs of
  ``expert_ff`` and ``shared_experts`` that every token runs,
  :mod:`mxnet_tpu.ops.experts`); one name for every layer, or a list
  with a name a layer (a leading dense layer before expert layers);
- ``qk_norm``: an RMSNorm over ``head_dim`` of every query and key head
  before the positions are applied;
- ``layer_kinds``: a kind a layer (:data:`KINDS`). ``"attention"``:
  softmax attention over the paged K/V pools. ``"retention"``: power
  retention of degree 2 over a fixed state a sequence
  (:mod:`mxnet_tpu.ops.retention`: a gate ``sigmoid(h W_g + b_g)`` a KV
  head decays the state). ``"latent"``: latent attention (MLA): queries
  through a rank-``q_rank`` bottleneck with an RMSNorm, keys and values
  through one rank-``kv_rank`` latent and one ``rope_dim``-wide rotary
  key a token that all heads share, heads of ``nope_dim + rope_dim``
  query-key and ``v_dim`` value dimensions, rotary positions on the
  ``rope_dim`` part only (``rope_interleave``: pairs ``(2i, 2i + 1)``,
  else the half-split form). Its cache is ONE row a token a layer,
  ``[latent after its norm | rotary key after its rotation]``; prefill
  and the oracle compute the expanded form (keys and values a head from
  the latent), decode the absorbed form over the cached rows
  (:func:`~mxnet_tpu.ops.flash_attention.latent_decode_attention`).

A net whose layers are all retention layers keeps their weights stacked
on a leading axis and runs them under one ``lax.scan`` (``scan_layers``,
which follows from ``layer_kinds``): one layer's program whatever the
depth, which is what keeps the compile of a wide model's prefill
buckets short. Every other net runs its layers one by one: the
paged-decode kernel takes its layer as a constant, and an expert
layer's grouped products take their stacked experts as a whole operand
(under a scan each step would first copy the layer's experts out of the
stack: 2.4 GB a layer at the published widths).

The defaults are the GPT-2 shape the stack started with (learned
positions, pre-LayerNorm, GELU MLP, grouped-query attention allowed,
``kv_heads | num_heads``, a head of its own). :data:`PRESETS` names
two more at a size for the CPU tests: ``brumby_tiny``, the switches of
manifestai/Brumby-14B-Base (Qwen3-14B's shapes with every layer a
retention layer), and ``joyai_tiny``, those of
jdopensource/JoyAI-LLM-Flash (latent layers, the first with a dense
gated MLP, then expert layers with a shared expert). The published
widths are the benchmark's ``chipbench/configs/*.json``.

The SAME math is exposed three ways, which is what the correctness
tests pin against each other:

- :meth:`forward_fn`: dense full-context causal forward (the oracle;
  a retention layer in its attention form, a latent layer expanded);
- :meth:`prefill_fn`: dense over the prompt; every attention layer's
  K/V and every latent layer's rows scattered into the paged pool
  through the request's block table, every retention layer's state
  carried chunk to chunk from the one its slot holds and left there at
  the prompt's real length;
- :meth:`decode_step_fn`: one token per sequence, attention through
  :func:`~mxnet_tpu.ops.flash_attention.paged_decode_attention`
  (which appends the token's K/V to the pool itself),
  retention through :func:`~mxnet_tpu.ops.retention.power_retention_step`,
  latent attention absorbed through
  :func:`~mxnet_tpu.ops.flash_attention.latent_decode_attention`.

Process replicas rebuild it from the ``{"decoder": {...}}`` spec with
the same seed, so every replica serves identical weights.
"""

from __future__ import annotations

import numpy as np

from .kvcache import CACHE_ARRAYS

_EPS = 1e-5
KINDS = tuple(CACHE_ARRAYS)  # "attention", "retention", "latent"
MLPS = ("gelu", "swiglu", "experts")

# tokens a chunk of a retention layer's prefill: inside a chunk the
# attention form, between chunks the state (the program's, not a model's)
RETENTION_CHUNK = 256
# query rows a latent layer's prefill scores at a time, each block
# against the keys up to its own end: no T x T scores at a long bucket
LATENT_QUERY_BLOCK = 256

PRESETS = {
    "brumby_tiny": dict(
        vocab_size=128, num_layers=2, d_model=64, num_heads=4, kv_heads=2,
        head_dim=16, d_ff=128, max_seq=256, norm="rmsnorm", norm_eps=1e-6,
        positions="rope", rope_theta=1e6, mlp="swiglu", qk_norm=True,
        layer_kinds="retention"),
    "joyai_tiny": dict(
        vocab_size=128, num_layers=2, d_model=64, num_heads=4, d_ff=96,
        max_seq=256, norm="rmsnorm", norm_eps=1e-6, positions="rope",
        rope_theta=32e6, mlp=["swiglu", "experts"], layer_kinds="latent",
        q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
        rope_interleave=True, experts=8, experts_per_token=2, expert_ff=24,
        shared_experts=1, route_scale=2.5),
}

# a published config.json's names for the latent and expert sizes
_CONFIG_NAMES = {
    "q_lora_rank": "q_rank", "kv_lora_rank": "kv_rank",
    "qk_nope_head_dim": "nope_dim", "qk_rope_head_dim": "rope_dim",
    "v_head_dim": "v_dim", "n_routed_experts": "experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "expert_ff", "n_shared_experts":
    "shared_experts", "routed_scaling_factor": "route_scale",
}


def _ln(x, g, b):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + _EPS) * g + b


def _rms(x, g, eps):
    """RMSNorm over the last axis, computed in float32."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return y.astype(x.dtype) * g


def _rope(x, pos, theta):
    """Rotary positions in the half-split form. ``x`` is ``(..., heads,
    head_dim)`` and ``pos`` has its leading axes."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _rope_pairs(x, pos, theta):
    """Rotary positions in the interleaved form: dimensions ``(2i, 2i +
    1)`` are a pair, rotated where they stand. Shapes as :func:`_rope`."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    a, b = x32[..., 0], x32[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


class TransformerDecoderLM:
    """Decoder-only LM with cache-aware prefill/decode.

    >>> net = TransformerDecoderLM(vocab_size=64, num_layers=2,
    ...                            d_model=32, num_heads=4, kv_heads=2)
    >>> dims = net.decode_dims()   # sizes the engine asks for
    >>> net.cache_spec()           # what its layers keep, a layer kind
    {'attention': {'layers': 2, 'kv_heads': 2, 'head_dim': 8}}
    >>> tiny = TransformerDecoderLM.from_preset("brumby_tiny")
    >>> TransformerDecoderLM.from_preset("joyai_tiny").cache_spec()
    {'latent': {'layers': 2, 'width': 40}}

    The latent sizes (``q_rank``, ``kv_rank``, ``nope_dim``,
    ``rope_dim``, ``v_dim``, ``rope_interleave``) and the expert sizes
    (``experts``, ``experts_per_token``, ``expert_ff``,
    ``shared_experts``, ``route_scale``) may also be given under a
    published config's names (``q_lora_rank``, ``n_routed_experts``,
    ...: :data:`_CONFIG_NAMES`).
    """

    def __init__(self, vocab_size=64, num_layers=2, d_model=32,
                 num_heads=4, kv_heads=None, d_ff=None, max_seq=128,
                 seed=0, dtype="float32", *, head_dim=None,
                 norm="layernorm", norm_eps=None, positions="learned",
                 rope_theta=10000.0, mlp="gelu", qk_norm=False,
                 layer_kinds="attention", q_rank=None, kv_rank=None,
                 nope_dim=None, rope_dim=None, v_dim=None,
                 rope_interleave=True, experts=0, experts_per_token=0,
                 expert_ff=None, shared_experts=0, route_scale=1.0,
                 **config_names):
        sizes = dict(q_rank=q_rank, kv_rank=kv_rank, nope_dim=nope_dim,
                     rope_dim=rope_dim, v_dim=v_dim, experts=experts,
                     experts_per_token=experts_per_token,
                     expert_ff=expert_ff, shared_experts=shared_experts,
                     route_scale=route_scale)
        for name, value in config_names.items():
            if name not in _CONFIG_NAMES:
                raise TypeError(f"unexpected argument {name!r}")
            sizes[_CONFIG_NAMES[name]] = value
        self.vocab_size = int(vocab_size)
        self.num_layers = int(num_layers)
        self.d_model = int(d_model)
        self.num_heads = int(num_heads)
        self.kv_heads = int(kv_heads or num_heads)
        self.d_ff = int(d_ff or 2 * d_model)
        self.max_seq = int(max_seq)
        self.seed = int(seed)
        self.dtype = str(dtype)
        if self.num_heads % self.kv_heads != 0:
            raise ValueError("num_heads must be a multiple of kv_heads; "
                             f"got {self.num_heads} vs {self.kv_heads}")
        if head_dim is None and self.d_model % self.num_heads != 0:
            raise ValueError("d_model must divide into num_heads")
        self.head_dim = int(head_dim or self.d_model // self.num_heads)
        for name, value, known in (
                ("norm", norm, ("layernorm", "rmsnorm")),
                ("positions", positions, ("learned", "rope"))):
            if value not in known:
                raise ValueError(f"{name} is one of {known}; got {value!r}")
        self.norm, self.positions = norm, positions
        # one name for every layer, or a name a layer
        self.mlp = mlp if isinstance(mlp, str) else [str(m) for m in mlp]
        self.layer_mlps = [mlp] * self.num_layers if isinstance(mlp, str) \
            else self.mlp
        if len(self.layer_mlps) != self.num_layers \
                or any(m not in MLPS for m in self.layer_mlps):
            raise ValueError(
                f"mlp is one of {MLPS}, or a list with one for each of the "
                f"{self.num_layers} layers; got {mlp!r}")
        self.norm_eps = float(norm_eps if norm_eps is not None else _EPS)
        self.rope_theta = float(rope_theta)
        self.qk_norm = bool(qk_norm)
        if isinstance(layer_kinds, str):
            layer_kinds = [layer_kinds] * self.num_layers
        self.layer_kinds = [str(k) for k in layer_kinds]
        if len(self.layer_kinds) != self.num_layers \
                or any(k not in KINDS for k in self.layer_kinds):
            raise ValueError(
                f"layer_kinds names one of {KINDS} for each of the "
                f"{self.num_layers} layers; got {layer_kinds!r}")
        # a layer's place among the layers of its kind: its index in
        # that kind's cache
        seen = dict.fromkeys(KINDS, 0)
        self._kind_index = []
        for k in self.layer_kinds:
            self._kind_index.append(seen[k])
            seen[k] += 1
        self._kind_count = {k: n for k, n in seen.items() if n}
        if "latent" in self._kind_count:
            for name in ("q_rank", "kv_rank", "nope_dim", "rope_dim",
                         "v_dim"):
                if not sizes[name]:
                    raise ValueError(f"a latent layer needs {name}")
            if self.positions != "rope" or self.norm != "rmsnorm":
                raise ValueError("latent layers take rotary positions and "
                                 "RMSNorm")
        self.q_rank, self.kv_rank, self.nope_dim, self.rope_dim, \
            self.v_dim = (int(sizes[k] or 0) for k in (
                "q_rank", "kv_rank", "nope_dim", "rope_dim", "v_dim"))
        self.rope_interleave = bool(rope_interleave)
        self.expert_layers = self.layer_mlps.count("experts")
        self.experts = int(sizes["experts"] or 0)
        self.experts_per_token = int(sizes["experts_per_token"] or 0)
        self.expert_ff = int(sizes["expert_ff"] or self.d_ff)
        self.shared_experts = int(sizes["shared_experts"] or 0)
        self.route_scale = float(sizes["route_scale"])
        if self.expert_layers and not (
                0 < self.experts_per_token <= self.experts):
            raise ValueError(
                "an expert layer needs experts >= experts_per_token > 0; "
                f"got {self.experts} and {self.experts_per_token}")
        # layers of one kind under one scan; the paged-decode kernel
        # takes its layer as a constant and the grouped products their
        # layer's experts whole, so retention layers only
        self.scan_layers = set(self.layer_kinds) == {"retention"}
        self._params = self._init_params()

    @classmethod
    def from_preset(cls, name, **overrides):
        """A net of :data:`PRESETS`, with some of its sizes replaced."""
        return cls(**{**PRESETS[name], **overrides})

    # -- weights -----------------------------------------------------------
    def _init_params(self):
        import jax.numpy as jnp

        rng = np.random.RandomState(self.seed)
        s = 0.02

        def w(*shape):
            return jnp.asarray(rng.normal(0.0, s, shape), dtype=self.dtype)

        def zeros(*shape):
            return jnp.zeros(shape, dtype=self.dtype)

        def ones(*shape):
            return jnp.ones(shape, dtype=self.dtype)

        d, h, kvh, hd, ff = (self.d_model, self.num_heads, self.kv_heads,
                             self.head_dim, self.d_ff)
        biased = self.norm == "layernorm"

        def norm_leaves(prefix):
            out = {prefix + "_g": ones(d)}
            if biased:
                out[prefix + "_b"] = zeros(d)
            return out

        layers = []
        for kind, mlp in zip(self.layer_kinds, self.layer_mlps):
            lyr = norm_leaves("ln1")
            if kind == "latent":
                qk = self.nope_dim + self.rope_dim
                lyr.update(
                    w_qa=w(d, self.q_rank), q_a_norm=ones(self.q_rank),
                    w_qb=w(self.q_rank, h * qk),
                    w_kva=w(d, self.kv_rank + self.rope_dim),
                    kv_a_norm=ones(self.kv_rank),
                    w_kvb=w(self.kv_rank, h * (self.nope_dim + self.v_dim)),
                    wo=w(h * self.v_dim, d))
            else:
                lyr.update(wq=w(d, h * hd), wk=w(d, kvh * hd),
                           wv=w(d, kvh * hd), wo=w(h * hd, d))
            if self.qk_norm:
                lyr.update(q_norm=ones(hd), k_norm=ones(hd))
            if kind == "retention":
                # gates that remember: 1 - sigmoid(b_g) log-uniform
                # between 1/100 and 1/10,000, so that a state carried
                # over hundreds of tokens still reaches the logits
                forget = 10.0 ** -rng.uniform(2.0, 4.0, kvh)
                lyr.update(wg=w(d, kvh), bg=jnp.asarray(
                    np.log((1.0 - forget) / forget), dtype=self.dtype))
            lyr.update(norm_leaves("ln2"))
            if mlp == "gelu":
                lyr.update(w1=w(d, ff), b1=zeros(ff), w2=w(ff, d),
                           b2=zeros(d))
            elif mlp == "swiglu":
                lyr.update(w_gate=w(d, ff), w_up=w(d, ff), w_down=w(ff, d))
            else:
                E, eff = self.experts, self.expert_ff
                # a bias wide enough to move the choice: one that were
                # added to the weights, or left out, would show
                lyr.update(router=w(d, E), router_bias=jnp.asarray(
                    rng.normal(0.0, 0.1, E), jnp.float32),
                    we_gate=w(E, d, eff), we_up=w(E, d, eff),
                    we_down=w(E, eff, d))
                if self.shared_experts:
                    sff = self.shared_experts * eff
                    lyr.update(ws_gate=w(d, sff), ws_up=w(d, sff),
                               ws_down=w(sff, d))
            layers.append(lyr)
        if self.scan_layers:
            layers = {k: jnp.stack([lyr[k] for lyr in layers])
                      for k in layers[0]}
        out = {"embed": w(self.vocab_size, d), "layers": layers}
        if self.positions == "learned":
            out["pos"] = w(self.max_seq, d)
        out.update(norm_leaves("lnf"))
        out["head"] = w(d, self.vocab_size)
        return out

    def params(self):
        """The weight pytree (a plain dict — device-resident arrays)."""
        return self._params

    def decode_dims(self) -> dict:
        """The sizes the engine reads (``max_seq``, ``vocab_size``) and
        the attention cache's geometry."""
        return {
            "layers": self.num_layers,
            "kv_heads": self.kv_heads,
            "head_dim": self.head_dim,
            "max_seq": self.max_seq,
            "vocab_size": self.vocab_size,
            "d_model": self.d_model,
        }

    def cache_spec(self) -> dict:
        """What a live sequence keeps, a layer kind the net has:
        ``attention`` layers keep K/V rows that grow with the sequence
        (the paged pools), ``retention`` layers a fixed state a KV
        head, ``latent`` layers one row of ``kv_rank + rope_dim`` a
        token (one paged pool). :class:`~.kvcache.SequenceCache` is
        built from this."""
        return {kind: {"layers": n, "width": self.kv_rank + self.rope_dim}
                if kind == "latent" else
                {"layers": n, "kv_heads": self.kv_heads,
                 "head_dim": self.head_dim}
                for kind, n in self._kind_count.items()}

    def spec(self) -> dict:
        """The ``{"decoder": ...}`` replica spec that rebuilds this net
        (same seed -> identical weights in every process replica)."""
        return {"decoder": {
            "vocab_size": self.vocab_size, "num_layers": self.num_layers,
            "d_model": self.d_model, "num_heads": self.num_heads,
            "kv_heads": self.kv_heads, "d_ff": self.d_ff,
            "max_seq": self.max_seq, "seed": self.seed,
            "dtype": self.dtype, "head_dim": self.head_dim,
            "norm": self.norm, "norm_eps": self.norm_eps,
            "positions": self.positions, "rope_theta": self.rope_theta,
            "mlp": self.mlp, "qk_norm": self.qk_norm,
            "layer_kinds": list(self.layer_kinds),
            "q_rank": self.q_rank, "kv_rank": self.kv_rank,
            "nope_dim": self.nope_dim, "rope_dim": self.rope_dim,
            "v_dim": self.v_dim, "rope_interleave": self.rope_interleave,
            "experts": self.experts,
            "experts_per_token": self.experts_per_token,
            "expert_ff": self.expert_ff,
            "shared_experts": self.shared_experts,
            "route_scale": self.route_scale,
        }}

    # -- shared layer math -------------------------------------------------
    def _norm(self, x, p, name):
        if self.norm == "rmsnorm":
            return _rms(x, p[name + "_g"], self.norm_eps)
        return _ln(x, p[name + "_g"], p[name + "_b"])

    def _qkv(self, lyr, h, pos=None):
        """Project one layer's hidden states ``(..., d)`` to q/k/v with
        head axes split out; queries and keys normed a head and rotated
        to ``pos`` (the leading axes' positions) where the
        configuration says so."""
        lead = h.shape[:-1]
        q = (h @ lyr["wq"]).reshape(*lead, self.num_heads, self.head_dim)
        k = (h @ lyr["wk"]).reshape(*lead, self.kv_heads, self.head_dim)
        v = (h @ lyr["wv"]).reshape(*lead, self.kv_heads, self.head_dim)
        if self.qk_norm:
            q = _rms(q, lyr["q_norm"], self.norm_eps)
            k = _rms(k, lyr["k_norm"], self.norm_eps)
        if self.positions == "rope":
            q = _rope(q, pos, self.rope_theta)
            k = _rope(k, pos, self.rope_theta)
        return q, k, v

    @staticmethod
    def _log_gate(lyr, h):
        """``log sigmoid(h W_g + b_g)``, one a KV head, float32."""
        import jax
        import jax.numpy as jnp

        return jax.nn.log_sigmoid((h @ lyr["wg"] + lyr["bg"])
                                  .astype(jnp.float32))

    def _latent_parts(self, lyr, h, pos):
        """One latent layer's hidden states ``(..., d)`` to ``(q_nope
        (..., H, nope), q_rope (..., H, rope), c_kv (..., rank), k_rope
        (..., rope))``: the queries through their bottleneck and its
        norm, the latent after its norm, both rotary parts rotated to
        ``pos`` (the key's is one head that all query heads share)."""
        rope = _rope_pairs if self.rope_interleave else _rope
        lead = h.shape[:-1]
        c_q = _rms(h @ lyr["w_qa"], lyr["q_a_norm"], self.norm_eps)
        q = (c_q @ lyr["w_qb"]).reshape(*lead, self.num_heads,
                                        self.nope_dim + self.rope_dim)
        kv = h @ lyr["w_kva"]
        c_kv = _rms(kv[..., :self.kv_rank], lyr["kv_a_norm"], self.norm_eps)
        k_rope = rope(kv[..., None, self.kv_rank:], pos,
                      self.rope_theta)[..., 0, :]
        return (q[..., :self.nope_dim],
                rope(q[..., self.nope_dim:], pos, self.rope_theta),
                c_kv, k_rope)

    def _latent_expand(self, lyr, q_nope, q_rope, c_kv, k_rope):
        """The expanded form's ``(q, k, v)``, heads of ``nope + rope``,
        ``nope + rope`` and ``v_dim``: keys and values a head from the
        latent through ``W_kvb``, the shared rotary key beside each
        head's own part."""
        import jax.numpy as jnp

        kv = (c_kv @ lyr["w_kvb"]).reshape(
            *c_kv.shape[:-1], self.num_heads, self.nope_dim + self.v_dim)
        k_rope = jnp.broadcast_to(k_rope[..., None, :],
                                  q_rope.shape[:-1] + k_rope.shape[-1:])
        return (jnp.concatenate([q_nope, q_rope], axis=-1),
                jnp.concatenate([kv[..., :self.nope_dim], k_rope], axis=-1),
                kv[..., self.nope_dim:])

    @staticmethod
    def _latent_row(c_kv, k_rope, pool):
        """A token's row as the latent pool keeps it: ``[c_kv |
        k_rope]`` and zeros up to the pool's whole lane tiles."""
        import jax.numpy as jnp

        pad = pool.shape[-1] - c_kv.shape[-1] - k_rope.shape[-1]
        return jnp.concatenate(
            [c_kv, k_rope, jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)],
            axis=-1).astype(pool.dtype)

    @property
    def _latent_scale(self):
        return 1.0 / ((self.nope_dim + self.rope_dim) ** 0.5)

    def _mlp(self, lyr, x, kind, live=None):
        """Layer ``lyr``'s MLP of ``kind`` over ``x`` ``(..., d)``, and
        an expert layer's int32 ``[routed_pairs, experts_hit,
        load_max]`` (``None`` from a dense one). Rows where ``live`` is
        false route to no expert."""
        import jax

        from ..ops import experts as ex

        if kind == "swiglu":
            return ex.gated_mlp(x, lyr["w_gate"], lyr["w_up"],
                                lyr["w_down"]), None
        if kind == "gelu":
            return jax.nn.gelu(x @ lyr["w1"] + lyr["b1"]) @ lyr["w2"] \
                + lyr["b2"], None
        h = x.reshape(-1, x.shape[-1])
        w, chosen = ex.route(h, lyr["router"], lyr["router_bias"],
                             self.experts_per_token, self.route_scale)
        y, load = ex.experts_apply(
            h, w, chosen, lyr["we_gate"], lyr["we_up"], lyr["we_down"],
            None if live is None else live.reshape(-1))
        if self.shared_experts:
            y = y + ex.gated_mlp(h, lyr["ws_gate"], lyr["ws_up"],
                                 lyr["ws_down"])
        return y.reshape(x.shape), ex.load_counters(load)

    def _dense_attend(self, q, k, v, causal_mask, scale=None):
        """Dense causal attention over full context (oracle + prefill).
        q: (B, T, H, hd); k/v: (B, S, KVH, hd) (a value head may have a
        width of its own)."""
        import jax.numpy as jnp

        group = q.shape[2] // k.shape[2]
        if group > 1:
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        if scale is None:
            scale = 1.0 / (self.head_dim ** 0.5)
        s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        s = jnp.where(causal_mask, s, -1e30)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
        return o.astype(q.dtype)

    @staticmethod
    def _blocked_attend(q, k, v, scale):
        """Causal attention over one prompt, :data:`LATENT_QUERY_BLOCK`
        query rows at a time, each block against the keys up to its own
        end: the work of the triangle, and no ``T x T`` scores (32 heads
        of 4,096 squared in float32 are 2.1 GB). q/k: (T, H, dq); v:
        (T, H, dv). The products take the operands' width and
        accumulate in float32, the softmax is float32."""
        import jax.numpy as jnp

        t = q.shape[0]
        out = []
        for start in range(0, t, LATENT_QUERY_BLOCK):
            end = min(t, start + LATENT_QUERY_BLOCK)
            s = jnp.einsum("thd,shd->hts", q[start:end], k[:end],
                           preferred_element_type=jnp.float32) * scale
            seen = jnp.arange(end)[None, :] \
                <= jnp.arange(start, end)[:, None]
            s = jnp.where(seen[None], s, -1e30)
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = p / jnp.sum(p, axis=-1, keepdims=True)
            out.append(jnp.einsum(
                "hts,shd->thd", p.astype(v.dtype), v[:end],
                preferred_element_type=jnp.float32).astype(q.dtype))
        return jnp.concatenate(out)

    def _dense_retain(self, q, k, v, log_g, causal_mask):
        """Power retention in its attention form over full context (the
        oracle): weights ``exp(G_t - G_s) (q_t . k_s)^2`` over their
        own sum. q: (B, T, H, hd); k/v: (B, T, KVH, hd); log_g: (B, T,
        KVH)."""
        import jax.numpy as jnp

        group = self.num_heads // self.kv_heads
        run = jnp.cumsum(log_g, axis=1)                  # (B, T, KVH)
        if group > 1:
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
            run = jnp.repeat(run, group, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                       k.astype(jnp.float32))
        run = run.transpose(0, 2, 1)                     # (B, H, T)
        gap = jnp.where(causal_mask, run[..., :, None] - run[..., None, :],
                        0.0)
        w = jnp.where(causal_mask, jnp.exp(gap) * s * s, 0.0)
        o = jnp.einsum("bhts,bshd->bthd", w, v.astype(jnp.float32))
        den = jnp.sum(w, axis=-1).transpose(0, 2, 1)     # (B, T, H)
        return (o / den[..., None]).astype(q.dtype)

    def _embed(self, params, tokens, pos):
        x = params["embed"][tokens]
        if self.positions == "learned":
            x = x + params["pos"][pos]
        return x

    def _through_layers(self, params, x, carry, mixers, live=None):
        """Every layer over ``x``. ``mixers[kind](lyr, h, carry, j)``
        mixes the normed hidden states ``h`` across positions for layer
        ``j`` of its kind and returns ``(o, carry)``, ``o`` with the
        heads side by side; ``carry`` is whatever the face threads
        through (the cache's arrays, or nothing). Stacked layers run
        under one scan, with ``j`` traced. Returns ``(h, carry, load)``:
        ``load`` is the expert layers' summed int32 ``[routed_pairs,
        experts_hit, load_max]`` over the rows that are ``live`` (every
        row without it), ``None`` for a net with no expert layer."""
        import jax
        import jax.numpy as jnp

        def layer(lyr, x, carry, load, kind, mlp, j):
            o, carry = mixers[kind](lyr, self._norm(x, lyr, "ln1"), carry, j)
            x = x + o.reshape(*x.shape[:-1], -1).astype(x.dtype) @ lyr["wo"]
            y, more = self._mlp(lyr, self._norm(x, lyr, "ln2"), mlp, live)
            return x + y, carry, load if more is None else load + more

        layers = params["layers"]
        load = jnp.zeros(3, jnp.int32) if self.expert_layers else None
        if isinstance(layers, dict):
            kind, mlp = self.layer_kinds[0], self.layer_mlps[0]

            def body(c, xs):
                lyr, j = xs
                return layer(lyr, *c, kind, mlp, j), None

            (x, carry, load), _ = jax.lax.scan(
                body, (x, carry, load),
                (layers, jnp.arange(self.num_layers, dtype=jnp.int32)))
        else:
            for li, lyr in enumerate(layers):
                x, carry, load = layer(
                    lyr, x, carry, load, self.layer_kinds[li],
                    self.layer_mlps[li], self._kind_index[li])
        return self._norm(x, params, "lnf"), carry, load

    def _trunk_dense(self, params, tokens):
        """Dense causal trunk over ``tokens`` (B, T), every layer in
        its attention form."""
        import jax.numpy as jnp

        b, t = tokens.shape
        pos = jnp.arange(t, dtype=jnp.int32)
        mask = jnp.tril(jnp.ones((t, t), bool))[None, None]

        def attend(lyr, h, carry, j):
            q, k, v = self._qkv(lyr, h, pos)
            return self._dense_attend(q, k, v, mask), carry

        def retain(lyr, h, carry, j):
            q, k, v = self._qkv(lyr, h, pos)
            return self._dense_retain(q, k, v, self._log_gate(lyr, h),
                                      mask), carry

        def latent(lyr, h, carry, j):
            q, k, v = self._latent_expand(
                lyr, *self._latent_parts(lyr, h, pos))
            return self._dense_attend(q, k, v, mask,
                                      self._latent_scale), carry

        x = self._embed(params, tokens, pos[None])
        return self._through_layers(
            params, x, None, {"attention": attend, "retention": retain,
                              "latent": latent})[0]

    def _split_cache(self, operands):
        """The faces' cache operands, in their fixed order: each kind's
        arrays in :data:`KINDS`' order, as many as
        :data:`~.kvcache.CACHE_ARRAYS` says (the attention layers'
        ``k_pool, v_pool``, the retention layers' ``state, norm``, the
        latent layers' one pool); after them an index a kind (the block
        tables, the slots' states). Returns ``({kind: arrays}, {kind:
        index})``."""
        kinds = [k for k in KINDS if k in self._kind_count]
        counts = [CACHE_ARRAYS[k] for k in kinds]
        if len(operands) != sum(counts) + len(kinds):
            raise TypeError(
                f"a net with {kinds} layers takes {sum(counts)} cache "
                f"array(s) and {len(kinds)} indices; got {len(operands)}")
        arrays, at = {}, 0
        for k, n in zip(kinds, counts):
            arrays[k] = tuple(operands[at:at + n])
            at += n
        index = dict(zip(kinds, operands[at:]))
        return arrays, index

    def _join_cache(self, arrays):
        return tuple(a for k in KINDS if k in arrays for a in arrays[k])

    # -- the three pure faces ---------------------------------------------
    def forward_fn(self):
        """Dense full-context oracle: ``(params, tokens[B, T]) ->
        logits[B, T, V]`` — what every decode step must reproduce."""

        def forward(params, tokens):
            h = self._trunk_dense(params, tokens)
            return h @ params["head"]

        return forward

    def prefill_fn(self):
        """Prompt ingestion over ONE padded prompt. ``(params,
        tokens[1, Tb], *cache, *index, length[1]) -> (logits[1, V],
        *cache)``: ``cache`` is ``k_pool, v_pool`` for a net of
        attention layers (``index`` the request's block table ``[1,
        mb]``), ``state, norm`` for one of retention layers (``index``
        the request's state ``[1]``), both pairs and both indices for a
        mixed one, one ``pool`` for a net of latent layers (``index``
        the block table). Logits are at the LAST REAL position
        (``length - 1``).

        Attention layers: dense causal attention, then every layer's
        K/V scattered into the pool in place — one scatter for K and
        one for V at ``(layer, block, offset)``, no layer's slice taken
        out and written back; pad positions write to the null block.
        Retention layers: the chunked form from the state the slot
        holds (zeros for a fresh sequence) to the state after
        ``length`` tokens, written back to the slot. Latent layers: the
        expanded form a block of query rows at a time, then every
        layer's rows ``[latent | rotary key]`` scattered into the one
        pool as K and V are; padding routes to no expert."""
        from ..ops.retention import power_retention_chunked
        from .kvcache import paged_prefill_write_all

        def prefill(params, tokens, *operands):
            import jax.numpy as jnp

            *operands, length = operands
            arrays, index = self._split_cache(operands)
            t = tokens.shape[1]
            pos = jnp.arange(t, dtype=jnp.int32)
            mask = jnp.tril(jnp.ones((t, t), bool))[None, None]
            ks, vs = [], []

            def attend(lyr, h, carry, j):
                q, k, v = self._qkv(lyr, h, pos)
                # (Tb, KVH * hd): a token's row as the pool keeps it
                ks.append(k[0].reshape(t, -1))
                vs.append(v[0].reshape(t, -1))
                return self._dense_attend(q, k, v, mask), carry

            def retain(lyr, h, carry, j):
                q, k, v = self._qkv(lyr, h, pos)
                S, z = carry["retention"]
                at = index["retention"][0]
                o, (s1, z1) = power_retention_chunked(
                    q[0], k[0], v[0], self._log_gate(lyr, h)[0],
                    (S[j, at], z[j, at]), length[0], RETENTION_CHUNK)
                return o[None], {**carry, "retention": (
                    S.at[j, at].set(s1), z.at[j, at].set(z1))}

            def latent(lyr, h, carry, j):
                parts = self._latent_parts(lyr, h[0], pos)
                ks.append(self._latent_row(parts[2], parts[3],
                                           carry["latent"][0]))
                o = self._blocked_attend(
                    *self._latent_expand(lyr, *parts), self._latent_scale)
                return o[None], carry

            x = self._embed(params, tokens, pos[None])
            h, arrays, _ = self._through_layers(
                params, x, arrays,
                {"attention": attend, "retention": retain,
                 "latent": latent}, live=(pos < length[0])[None])
            if ks:  # the paged kind's rows: K and V, or the latent's
                paged = "attention" if vs else "latent"
                table = index[paged][0]
                arrays[paged] = tuple(
                    paged_prefill_write_all(pool, table, length[0],
                                            jnp.stack(rows))
                    for pool, rows in zip(arrays[paged], (ks, vs)))
            last = jnp.clip(length - 1, 0, t - 1)
            h_last = jnp.take_along_axis(
                h, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            return (h_last @ params["head"],) + self._join_cache(arrays)

        return prefill

    def decode_step_fn(self, with_load=False):
        """One decode step for the whole slot batch. ``(params,
        token[B], pos[B], *cache, *index, active[B]) -> (logits[B, V],
        *cache)`` with ``cache`` and ``index`` as :meth:`prefill_fn`
        takes them (``index`` a row a slot: ``tables[B, mb]``,
        ``states[B]``). The step is branch-free in slot liveness.
        ``with_load`` (a net with expert layers) appends the step's
        int32 ``[routed_pairs, experts_hit, load_max]``, summed over its
        expert layers, of the active slots' tokens.

        Attention layers hand the decode kernel each slot's new K/V
        rows with the WHOLE pool and the layer index (never
        ``k_pool[li]``): the kernel appends an active slot's rows at
        its position ``pos`` through its block table, attends over the
        context that ends there, and returns the pools updated in
        place; an inactive slot has an empty context, writes nothing
        and reads nothing. Retention layers step each active slot's
        state in place and read it in the same pass; the states of
        inactive slots are neither read nor written. Latent layers hand
        their kernel each slot's row for the one pool the same way and
        attend in the absorbed form, over the rows as they lie
        there."""
        from ..ops.flash_attention import (latent_decode_attention,
                                           paged_decode_attention)
        from ..ops.retention import power_retention_step

        def step(params, token, pos, *operands):
            import jax.numpy as jnp

            *operands, active = operands
            arrays, index = self._split_cache(operands)
            pos_c = jnp.clip(pos, 0, self.max_seq - 1)
            x = self._embed(params, token, pos_c)
            paged = next((k for k in ("attention", "latent")
                          if k in arrays), None)
            if paged:
                tables = index[paged]
                # context includes the token being written THIS step
                ctx = jnp.where(active, pos_c + 1, 0).astype(jnp.int32)
                scale = 1.0 / (self.head_dim ** 0.5)

            def attend(lyr, h, carry, j):
                q, k, v = self._qkv(lyr, h, pos_c)   # (B, H/KVH, hd)
                o, *pools = paged_decode_attention(
                    q, *carry["attention"], tables, ctx, scale=scale,
                    layer=j, new=(k, v))
                return o, {**carry, "attention": tuple(pools)}

            def retain(lyr, h, carry, j):
                q, k, v = self._qkv(lyr, h, pos_c)
                o, state = power_retention_step(
                    q, k, v, self._log_gate(lyr, h), carry["retention"],
                    index["retention"], active, layer=j)
                return o, {**carry, "retention": state}

            def latent(lyr, h, carry, j):
                q_nope, q_rope, c_kv, k_rope = self._latent_parts(
                    lyr, h, pos_c)
                (pool,) = carry["latent"]
                # absorbed: W_kvb's key half carries the queries into
                # the latent's space, its value half expands the result
                w_kvb = lyr["w_kvb"].reshape(
                    self.kv_rank, self.num_heads, self.nope_dim + self.v_dim)
                q_lat = jnp.einsum("bhn,chn->bhc", q_nope,
                                   w_kvb[..., :self.nope_dim])
                o_lat, pool = latent_decode_attention(
                    q_lat, q_rope, pool, tables, ctx, self._latent_scale,
                    layer=j, new=self._latent_row(c_kv, k_rope, pool))
                o = jnp.einsum("bhc,chv->bhv", o_lat,
                               w_kvb[..., self.nope_dim:])
                return o, {**carry, "latent": (pool,)}

            h, arrays, load = self._through_layers(
                params, x, arrays, {"attention": attend, "retention": retain,
                                    "latent": latent}, live=active)
            out = (h @ params["head"],) + self._join_cache(arrays)
            return out + (load,) if with_load else out

        return step
