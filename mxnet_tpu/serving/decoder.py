"""A small functional transformer decoder LM — the generation stack's
reference model (and the fleet demo/test workload).

This is deliberately NOT a Gluon block: the decode fast path needs
pure ``(params, state) -> (logits, state)`` functions it can close
into AOT-compiled prefill/decode executables, with the KV pools
threaded through as donated operands. The class carries the
hyperparameters and the (deterministically seeded) weights; everything
the device runs comes out of :meth:`prefill_fn` / :meth:`decode_step_fn`
/ :meth:`forward_fn` as pure closures over nothing but shapes.

The SAME math is exposed three ways, which is what the correctness
tests pin against each other:

- :meth:`forward_fn` — dense full-context causal forward (the oracle);
- :meth:`prefill_fn` — dense over the prompt, then one scatter of
  every layer's K/V into the paged pool through the request's block
  table;
- :meth:`decode_step_fn` — one token per sequence, K/V appended to the
  pool and attention read back through
  :func:`~mxnet_tpu.ops.flash_attention.paged_decode_attention`.

Architecture: learned positional embeddings, pre-LN, grouped-query
attention (``kv_heads | num_heads``), GELU MLP, weight-tied-free head.
Process replicas rebuild it from the ``{"decoder": {...}}`` spec with
the same seed, so every replica serves identical weights.
"""

from __future__ import annotations

import numpy as np

_EPS = 1e-5


def _ln(x, g, b):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + _EPS) * g + b


class TransformerDecoderLM:
    """Tiny decoder-only LM with paged-cache-aware prefill/decode.

    >>> net = TransformerDecoderLM(vocab_size=64, num_layers=2,
    ...                            d_model=32, num_heads=4, kv_heads=2)
    >>> dims = net.decode_dims()   # cache geometry for PagedKVCache
    """

    def __init__(self, vocab_size=64, num_layers=2, d_model=32,
                 num_heads=4, kv_heads=None, d_ff=None, max_seq=128,
                 seed=0, dtype="float32"):
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
        if self.d_model % self.num_heads != 0:
            raise ValueError("d_model must divide into num_heads")
        self.head_dim = self.d_model // self.num_heads
        self._params = self._init_params()

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
        layers = []
        for _ in range(self.num_layers):
            layers.append({
                "ln1_g": ones(d), "ln1_b": zeros(d),
                "wq": w(d, h * hd), "wk": w(d, kvh * hd),
                "wv": w(d, kvh * hd), "wo": w(h * hd, d),
                "ln2_g": ones(d), "ln2_b": zeros(d),
                "w1": w(d, ff), "b1": zeros(ff),
                "w2": w(ff, d), "b2": zeros(d),
            })
        return {
            "embed": w(self.vocab_size, d),
            "pos": w(self.max_seq, d),
            "layers": layers,
            "lnf_g": ones(d), "lnf_b": zeros(d),
            "head": w(d, self.vocab_size),
        }

    def params(self):
        """The weight pytree (a plain dict — device-resident arrays)."""
        return self._params

    def decode_dims(self) -> dict:
        """Cache geometry the engine hands to :class:`PagedKVCache`."""
        return {
            "layers": self.num_layers,
            "kv_heads": self.kv_heads,
            "head_dim": self.head_dim,
            "max_seq": self.max_seq,
            "vocab_size": self.vocab_size,
            "d_model": self.d_model,
        }

    def spec(self) -> dict:
        """The ``{"decoder": ...}`` replica spec that rebuilds this net
        (same seed -> identical weights in every process replica)."""
        return {"decoder": {
            "vocab_size": self.vocab_size, "num_layers": self.num_layers,
            "d_model": self.d_model, "num_heads": self.num_heads,
            "kv_heads": self.kv_heads, "d_ff": self.d_ff,
            "max_seq": self.max_seq, "seed": self.seed,
            "dtype": self.dtype,
        }}

    # -- shared layer math -------------------------------------------------
    def _qkv(self, lyr, h):
        """Project one layer's hidden states ``(..., d)`` to q/k/v with
        head axes split out."""
        lead = h.shape[:-1]
        q = (h @ lyr["wq"]).reshape(*lead, self.num_heads, self.head_dim)
        k = (h @ lyr["wk"]).reshape(*lead, self.kv_heads, self.head_dim)
        v = (h @ lyr["wv"]).reshape(*lead, self.kv_heads, self.head_dim)
        return q, k, v

    def _mlp(self, lyr, x):
        import jax

        return jax.nn.gelu(x @ lyr["w1"] + lyr["b1"]) @ lyr["w2"] + lyr["b2"]

    def _dense_attend(self, q, k, v, causal_mask):
        """Dense causal attention over full context (oracle + prefill).
        q: (B, T, H, hd); k/v: (B, S, KVH, hd)."""
        import jax.numpy as jnp

        group = self.num_heads // self.kv_heads
        if group > 1:
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        scale = 1.0 / (self.head_dim ** 0.5)
        s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        s = jnp.where(causal_mask, s, -1e30)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
        return o.astype(q.dtype)

    def _trunk_dense(self, params, tokens, write_kv=None):
        """Dense causal trunk over ``tokens`` (B, T). ``write_kv`` is an
        optional callback ``(layer_idx, k, v)`` the prefill path uses to
        scatter each layer's K/V into the paged pool."""
        import jax.numpy as jnp

        b, t = tokens.shape
        x = params["embed"][tokens] + params["pos"][:t][None]
        mask = jnp.tril(jnp.ones((t, t), bool))[None, None]
        for li, lyr in enumerate(params["layers"]):
            h = _ln(x, lyr["ln1_g"], lyr["ln1_b"])
            q, k, v = self._qkv(lyr, h)
            if write_kv is not None:
                write_kv(li, k, v)
            o = self._dense_attend(q, k, v, mask)
            x = x + o.reshape(b, t, -1) @ lyr["wo"]
            x = x + self._mlp(lyr, _ln(x, lyr["ln2_g"], lyr["ln2_b"]))
        return _ln(x, params["lnf_g"], params["lnf_b"])

    # -- the three pure faces ---------------------------------------------
    def forward_fn(self):
        """Dense full-context oracle: ``(params, tokens[B, T]) ->
        logits[B, T, V]`` — what every decode step must reproduce."""

        def forward(params, tokens):
            h = self._trunk_dense(params, tokens)
            return h @ params["head"]

        return forward

    def prefill_fn(self):
        """Prompt ingestion: dense causal forward over ONE padded
        prompt, then every layer's K/V scattered into the pool in place
        — one scatter for K and one for V at ``(layer, block, offset)``
        through the request's block table, no layer's slice taken out
        and written back. ``(params, tokens[1, Tb], k_pool, v_pool,
        table[1, mb], length[1]) -> (logits[1, V], k_pool, v_pool)`` —
        logits are at the LAST REAL position (``length - 1``); pad
        positions write to the null block."""
        from .kvcache import paged_prefill_write_all

        def prefill(params, tokens, k_pool, v_pool, table, length):
            import jax.numpy as jnp

            ks, vs = [], []

            def write_kv(li, k, v):
                # (Tb, KVH * hd): a token's row as the pool keeps it
                ks.append(k[0].reshape(k.shape[1], -1))
                vs.append(v[0].reshape(v.shape[1], -1))

            h = self._trunk_dense(params, tokens, write_kv=write_kv)
            k_pool = paged_prefill_write_all(k_pool, table[0], length[0],
                                             jnp.stack(ks))
            v_pool = paged_prefill_write_all(v_pool, table[0], length[0],
                                             jnp.stack(vs))
            last = jnp.clip(length - 1, 0, tokens.shape[1] - 1)
            h_last = jnp.take_along_axis(
                h, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            return h_last @ params["head"], k_pool, v_pool

        return prefill

    def decode_step_fn(self):
        """One decode step for the whole slot batch: append each active
        slot's token K/V to the pool, attend through the block table,
        return next-token logits. ``(params, token[B], pos[B], k_pool,
        v_pool, tables[B, mb], active[B]) -> (logits[B, V], k_pool,
        v_pool)``. Inactive slots write to the null block and read an
        empty context — the step is branch-free in slot liveness. The
        pool is updated and read in place: each layer scatters its rows
        at ``[li, blk, off]`` and hands the kernel the WHOLE pool with
        the layer index, never ``k_pool[li]``."""
        from ..ops.flash_attention import paged_decode_attention
        from .kvcache import slot_coords

        def step(params, token, pos, k_pool, v_pool, tables, active):
            import jax.numpy as jnp

            block_size = k_pool.shape[2]
            pos_c = jnp.clip(pos, 0, self.max_seq - 1)
            x = params["embed"][token] + params["pos"][pos_c]
            blk, off = slot_coords(tables, pos_c, block_size, active)
            # context includes the token being written THIS step
            ctx = jnp.where(active, pos_c + 1, 0).astype(jnp.int32)
            scale = 1.0 / (self.head_dim ** 0.5)
            for li, lyr in enumerate(params["layers"]):
                h = _ln(x, lyr["ln1_g"], lyr["ln1_b"])
                q, k, v = self._qkv(lyr, h)       # (B, H/KVH, hd)
                rows = (k.shape[0], -1)  # (B, KVH * hd), the pool's row
                k_pool = k_pool.at[li, blk, off].set(k.reshape(rows))
                v_pool = v_pool.at[li, blk, off].set(v.reshape(rows))
                o = paged_decode_attention(q, k_pool, v_pool, tables, ctx,
                                           scale=scale, layer=li)
                x = x + o.reshape(x.shape[0], -1) @ lyr["wo"]
                x = x + self._mlp(lyr, _ln(x, lyr["ln2_g"], lyr["ln2_b"]))
            h = _ln(x, params["lnf_g"], params["lnf_b"])
            return h @ params["head"], k_pool, v_pool

        return step
