"""Ring attention: sequence/context parallelism over a mesh axis.

No reference counterpart (SURVEY.md §2.5 P11 — "does not exist in the
reference"; §5.7 marks it as the required new capability). Design follows
the public ring-attention recipe: shard Q/K/V along the sequence axis over
the mesh's ``sp`` axis; each device computes blockwise attention against
its local KV shard, then rotates the KV shard around the ring with
``lax.ppermute`` (riding ICI), accumulating with the online-softmax
combine. Peak memory per device is O(T/n) regardless of total context.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.flash_attention import _jnp_flash_fwd, flash_attention_core


def _local_attn_with_lse(q, k, v, scale, mask_fn=None):
    """Blockwise local attention returning (out_unnormalized, m, l)."""
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask_fn is not None:
        s = mask_fn(s)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.maximum(m, -1e30)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32))
    return o, m_safe, l


def ring_attention(query, key, value, mesh, axis_name="sp", scale=None,
                   causal=False):
    """Sequence-parallel attention over ``mesh[axis_name]``.

    query/key/value: (B, H, T, D) GLOBAL arrays (host view); T is sharded
    across the axis. Returns the global (B, H, T, D) result with the same
    sharding. Jit-able; collectives lower to ICI ppermute.
    """

    if scale is None:
        scale = 1.0 / (query.shape[-1] ** 0.5)
    n = mesh.shape[axis_name]
    T = query.shape[2]
    assert T % n == 0, f"seq len {T} must divide ring size {n}"
    chunk = T // n

    def per_device(q, k, v):
        # q,k,v: (B, H, T/n, D) local shards
        my = lax.axis_index(axis_name)

        def mask_for(kv_owner_idx):
            if not causal:
                return None

            def mask_fn(s):
                rows = my * chunk + jnp.arange(chunk)[:, None]
                cols = kv_owner_idx * chunk + jnp.arange(chunk)[None, :]
                return jnp.where(rows >= cols, s, -1e30)

            return mask_fn

        def step(carry, r):
            o_acc, m_acc, l_acc, k_cur, v_cur = carry
            owner = (my - r) % n
            o, m, l = _local_attn_with_lse(q, k_cur, v_cur, scale,
                                           mask_for(owner))
            m_new = jnp.maximum(m_acc, m)
            alpha_acc = jnp.exp(m_acc - m_new)
            alpha = jnp.exp(m - m_new)
            o_acc = o_acc * alpha_acc + o * alpha
            l_acc = l_acc * alpha_acc + l * alpha
            # rotate KV around the ring (skip after last step is harmless)
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
            return (o_acc, m_new, l_acc, k_nxt, v_nxt), None

        B, H, Tl, D = q.shape

        def _vary(x):
            # mark constants as varying over the ring axis so the scan
            # carry types match shard_map's varying-axes check
            return lax.pcast(x, (axis_name,), to="varying")

        init = (
            _vary(jnp.zeros((B, H, Tl, D), jnp.float32)),
            _vary(jnp.full((B, H, Tl, 1), -1e30, jnp.float32)),
            _vary(jnp.zeros((B, H, Tl, 1), jnp.float32)),
            k, v,
        )
        (o_acc, m_acc, l_acc, _, _), _ = lax.scan(step, init, jnp.arange(n))
        return (o_acc / jnp.maximum(l_acc, 1e-30)).astype(q.dtype)

    spec = P(None, None, axis_name, None)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(query, key, value)


def shard_sequence(arr, mesh, axis_name="sp", seq_axis=2):
    """Place a (B, H, T, D) array with T sharded over the ring axis."""
    ndim = arr.ndim
    spec = [None] * ndim
    spec[seq_axis] = axis_name
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))
