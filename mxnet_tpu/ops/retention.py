"""Power retention of degree 2: linear attention whose feature map is the
symmetric second power, so that ``phi(q) . phi(k) == (q . k) ** 2``
exactly (Buckman, Gelada and Zhang, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239).

For one KV head with gate ``g_t`` in ``(0, 1)`` and its group of query
heads::

    S_t = g_t S_{t-1} + v_t phi(k_t)^T        (d x P)
    z_t = g_t z_{t-1} + phi(k_t)              (P)
    o_t = S_t phi(q_t) / (z_t . phi(q_t))

which is the attention ``sum_j exp(G_t - G_j) (q_t . k_j)^2 v_j`` over
its own normaliser, with ``G`` the running sum of ``log g``. No softmax
and no cache that grows: the state is what a sequence keeps.

**The feature map's layout.** ``phi`` is laid out by diagonals, not by
the upper triangle: row ``r`` of ``R = d/2 + 1`` holds ``w_r a_i
a_{(i + r) mod d}`` for ``i < d``, with ``w_0 = 1``, ``w_r = sqrt(2)``
for ``0 < r < d/2`` (each unordered pair once) and ``w_{d/2} = 1``
(each pair ``{i, i + d/2}`` twice at weight 1, which is once at
``sqrt(2)``). ``P = R d`` is 8,320 at ``d`` = 128 where the triangle
has 8,256: 0.78 % more bytes, and in return every row is a lane
rotation of ``a`` times ``a`` (no gather), ``P`` is whole (8, 128)
tiles, and the normaliser ``(R, d)`` has ``phi``'s own shape.

**The state's layout.** ``S`` is kept as ``(d, P)``, the value's
channel on the rows and ``phi``'s index on the lanes: the update adds
``v`` down the rows times ``phi(k)`` along the lanes, both of which the
decode kernel has without a transpose of ``phi``. A store of states is
``(layers, slots, kv_heads, d, P)`` and ``(layers, slots, kv_heads, R,
d)``, float32; its last slot is the null slot, which steps of slots
that are not live are routed to.

Three forms of the same mathematics, pinned against each other by
``tests/test_retention.py``:

- :func:`power_retention_chunked`: prefill. Inside a chunk the
  attention form, between chunks the state; positions at or beyond
  ``length`` leave the state untouched, so a padded bucket gives the
  state after ``length`` tokens.
- :func:`power_retention_step`: one token a slot against the store, in
  place. On a TPU the Pallas kernel ``mxtpu_retention_decode``; on the
  CPU the same mathematics in ``jax.numpy`` (chosen by what the backend
  is and the head size, as ``paged_decode_attention`` chooses).
- the plain attention form, which only the benchmark's reference and
  the tests compute.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def feature_rows(d):
    """``R``: the rows of ``phi`` for a head of ``d`` channels."""
    if d % 2:
        raise ValueError(f"power retention needs an even head size; got {d}")
    return d // 2 + 1


def feature_dim(d):
    """``P = R d``: the length of ``phi`` for a head of ``d`` channels."""
    return feature_rows(d) * d


def _row_weight(r, d):
    return 1.0 if r == 0 or 2 * r == d else math.sqrt(2.0)


def phi(a):
    """``(..., d) -> (..., R, d)`` float32 with ``sum(phi(a) * phi(b))
    == (a . b) ** 2``: row ``r`` is ``w_r a_i a_{(i + r) mod d}``."""
    a = a.astype(jnp.float32)
    d = a.shape[-1]
    twice = jnp.concatenate([a, a], axis=-1)
    rows = [_row_weight(r, d) * a * twice[..., r:r + d]
            for r in range(feature_rows(d))]
    return jnp.stack(rows, axis=-2)


def _flat(p):
    """``phi``'s two last axes as one: ``(..., R, d) -> (..., P)``."""
    return p.reshape(*p.shape[:-2], p.shape[-2] * p.shape[-1])


def state_shapes(layers, slots, kv_heads, head_dim):
    """Shapes of a store of ``slots`` states and one null slot."""
    r = feature_rows(head_dim)
    return ((layers, slots + 1, kv_heads, head_dim, r * head_dim),
            (layers, slots + 1, kv_heads, r, head_dim))


# ---------------------------------------------------------------------------
# prefill: the chunked form
# ---------------------------------------------------------------------------

def power_retention_chunked(q, k, v, log_g, state, length, chunk=256):
    """One sequence through one layer. ``q`` ``(T, H, d)``, ``k`` and
    ``v`` ``(T, KVH, d)``, ``log_g`` ``(T, KVH)`` (the log of the gate,
    at or under 0), ``state = (S, z)`` entering, ``(KVH, d, P)`` and
    ``(KVH, R, d)`` float32. Returns ``(o (T, H, d) float32, state)``
    where the state is the one after ``length`` tokens: positions at or
    beyond ``length`` neither decay it nor add to it, and their outputs
    are zero.

    The matrix products take their operands in the activations' own
    width, as every other product of the net does: under a bfloat16 net
    ``phi(q)`` and the state it meets are rounded to bfloat16 on their
    way into the matrix unit (accumulated in float32; the stored state
    stays float32), under a float32 net they are float32 at ``highest``
    precision."""
    T, H, d = q.shape
    half = q.dtype in (jnp.bfloat16, jnp.float16)
    low = q.dtype if half else jnp.float32
    prec = None if half else _HI
    KVH = k.shape[1]
    G = H // KVH
    C = int(min(chunk, T))
    pad = -T % C
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        log_g = jnp.pad(log_g, ((0, pad), (0, 0)))
    n = (T + pad) // C
    qc = q.reshape(n, C, KVH, G, d)
    kc = k.reshape(n, C, KVH, d)
    vc = v.reshape(n, C, KVH, d)
    gc = log_g.astype(jnp.float32).reshape(n, C, KVH)
    starts = jnp.arange(n, dtype=jnp.int32) * C
    lower = jnp.tril(jnp.ones((C, C), bool))

    def one(carry, xs):
        S, z = carry
        qi, ki, vi, lg, start = xs
        valid = (start + jnp.arange(C, dtype=jnp.int32)) < length
        lg = jnp.where(valid[:, None], lg, 0.0)
        run = jnp.cumsum(lg, axis=0)                     # G_i - G_0
        # what entered the chunk, seen through each position's decay: one
        # product against the state with its normaliser as a last row
        # gives numerator and denominator from the same phi(q). phi(q)
        # is the one large array here (C x H x P): it is made once, in
        # the width the matrix unit reads
        pq = _flat(phi(qi)).astype(low)                  # (C, KVH, G, P)
        sz = jnp.concatenate([S, _flat(z)[:, None, :]], axis=1)
        both = jnp.einsum("cngp,ndp->cngd", pq, sz.astype(low),
                          preferred_element_type=jnp.float32, precision=prec)
        into = jnp.exp(run)[:, :, None]
        num = both[..., :d] * into[..., None]
        den = both[..., d] * into
        # the chunk's own positions, in the attention form
        s = jnp.einsum("cngd,jnd->ngcj", qi, ki,
                       preferred_element_type=jnp.float32, precision=prec)
        gap = run.T[:, :, None] - run.T[:, None, :]      # (KVH, C, C)
        keep = lower & valid[None, :]
        w = jnp.where(keep[None], jnp.exp(jnp.where(keep[None], gap, 0.0)),
                      0.0)[:, None] * (s * s)            # (KVH, G, C, C)
        num = num + jnp.einsum("ngcj,jnd->cngd", w, vi.astype(jnp.float32),
                               precision=prec)
        den = den + jnp.sum(w, axis=-1).transpose(2, 0, 1)
        ok = valid[:, None, None]
        o = jnp.where(ok[..., None], num, 0.0) \
            / jnp.where(ok, den, 1.0)[..., None]
        # the state leaving the chunk
        out = jnp.where(valid[:, None], jnp.exp(run[-1][None] - run), 0.0)
        pk = phi(ki)                                     # (C, KVH, R, d)
        through = jnp.exp(run[-1])
        S = through[:, None, None] * S + jnp.einsum(
            "cnd,cnp->ndp", vi.astype(jnp.float32) * out[..., None],
            _flat(pk), precision=prec)
        z = through[:, None, None] * z + jnp.einsum("cn,cnrd->nrd", out, pk,
                                                    precision=prec)
        return (S, z), o

    state, o = jax.lax.scan(one, state, (qc, kc, vc, gc, starts))
    return o.reshape(n * C, H, d)[:T], state


# ---------------------------------------------------------------------------
# decode: one token a slot against the store
# ---------------------------------------------------------------------------

def _use_pallas(d):
    """Kernel path: heads of whole 128-lane rows, on any backend but the
    CPU. A backend that fails to start raises here."""
    return d % 128 == 0 and jax.default_backend() != "cpu"


def _jnp_step(q, k, v, log_g, S, z, slots, active, layer):
    """CPU path and oracle: gather each slot's state, step it, scatter
    it back. Slots that are not live read and write the null slot."""
    B, H, d = q.shape
    KVH = k.shape[1]
    G = H // KVH
    null = S.shape[1] - 1
    at = jnp.where(active, slots, null)
    g = jnp.exp(log_g.astype(jnp.float32))[..., None, None]
    pk = phi(k)                                          # (B, KVH, R, d)
    S1 = g * S[layer, at] + (v.astype(jnp.float32)[..., :, None]
                             * _flat(pk)[..., None, :])
    z1 = g * z[layer, at] + pk
    pq = _flat(phi(q)).reshape(B, KVH, G, -1)
    num = jnp.einsum("bngp,bndp->bngd", pq, S1, precision=_HI)
    den = jnp.einsum("bngp,bnp->bng", pq, _flat(z1), precision=_HI)
    live = active[:, None, None]
    o = jnp.where(live[..., None], num, 0.0) \
        / jnp.where(live, den, 1.0)[..., None]
    return (o.reshape(B, H, d), S.at[layer, at].set(S1),
            z.at[layer, at].set(z1))


def _decode_kernel(meta_ref, at_ref, row_ref, x_ref, s_ref, z_ref,
                   o_ref, s_out, z_out, *, groups, d):
    """One grid step a (live slot, kv head): grid ``(B, KVH)``. The
    live slots come first (``at_ref`` names each step's state,
    ``row_ref`` its row of the batch); a step past the last live slot
    keeps the block indices of the last live step, so nothing of a
    slot that is not live is fetched or written back. ``x_ref`` holds
    the step's rows: ``groups`` queries, then k, v and the gate on
    every lane. The state's ``(d, P)`` block is walked by ``phi``'s
    rows: ``d`` lanes at a time it is decayed, gets ``v`` down its rows
    times ``phi(k)``'s row along its lanes, is stored, and is read by
    the group's queries before it leaves the registers."""
    del at_ref, row_ref  # the index maps' own
    i = pl.program_id(0)
    live = i < meta_ref[1]
    R = feature_rows(d)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _step():
        x = x_ref[0, 0]                                  # (rows, d)
        q = x[0:groups]
        k = x[groups:groups + 1]
        v = x[groups + 1:groups + 2]
        g = x[groups + 2:groups + 3]
        v_col = jnp.broadcast_to(v, (d, d)).T            # [c, j] = v[c]
        g_all = jnp.broadcast_to(g, (d, d))
        acc = [jnp.zeros((d, d), jnp.float32) for _ in range(groups)]
        den = jnp.zeros((groups, d), jnp.float32)
        for r in range(R):
            w = _row_weight(r, d)
            shift = (d - r) % d                          # a[(i + r) mod d]
            pk = k * (pltpu.roll(k, shift, 1) if shift else k) * w
            pq = q * (pltpu.roll(q, shift, 1) if shift else q) * w
            lanes = slice(r * d, (r + 1) * d)
            s_new = g_all * s_ref[0, 0, 0, :, lanes] + v_col * pk
            s_out[0, 0, 0, :, lanes] = s_new
            z_new = g * z_ref[0, 0, 0, r:r + 1, :] + pk
            z_out[0, 0, 0, r:r + 1, :] = z_new
            for m in range(groups):
                acc[m] = acc[m] + s_new * pq[m:m + 1]
            den = den + pq * z_new
        # row sums of each query's (d, d) tile, as rows: column m of a
        # tile, transposed
        col = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
        tile = jnp.zeros((d, d), jnp.float32)
        for m in range(groups):
            tile = jnp.where(col == m,
                             jnp.sum(acc[m], axis=1, keepdims=True), tile)
        num = tile.T[0:groups]                           # (groups, d)
        o_ref[0, 0] = jnp.zeros(o_ref.shape[2:], jnp.float32)
        o_ref[0, 0, 0:groups, :] = num / jnp.sum(den, axis=1, keepdims=True)


def _pallas_step(q, k, v, log_g, S, z, slots, active, layer,
                 interpret=False):
    """``S`` and ``z`` are the WHOLE store; the layer and each step's
    state ride the scalar-prefetch lane into the index maps, and both
    arrays are aliased to the outputs: the kernel rewrites the live
    slots' blocks of this layer where they lie."""
    B, H, d = q.shape
    KVH = k.shape[1]
    G = H // KVH
    R = feature_rows(d)
    P = R * d
    null = S.shape[1] - 1
    rows = -(-(G + 3) // 8) * 8
    f32 = jnp.float32
    x = jnp.concatenate([
        q.astype(f32).reshape(B, KVH, G, d),
        k.astype(f32)[:, :, None], v.astype(f32)[:, :, None],
        jnp.broadcast_to(jnp.exp(log_g.astype(f32))[:, :, None, None],
                         (B, KVH, 1, d)),
        jnp.zeros((B, KVH, rows - G - 3, d), f32)], axis=2)
    # live slots first, in slot order; the rest keep the batch's rows
    # (their outputs are zeroed) and the last live step's state
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    n_live = jnp.sum(active).astype(jnp.int32)
    last = jnp.where(n_live > 0, order[jnp.maximum(n_live - 1, 0)], 0)
    step = jnp.arange(B, dtype=jnp.int32)
    at = jnp.where(step < n_live, slots.astype(jnp.int32)[order],
                   jnp.where(n_live > 0, slots.astype(jnp.int32)[last], null))
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), n_live])

    def head(i, n, meta):
        return jnp.where(i < meta[1], n, KVH - 1)

    x_spec = pl.BlockSpec(
        (1, 1, rows, d), lambda i, n, meta, at, row: (row[i], n, 0, 0))
    s_spec = pl.BlockSpec(
        (1, 1, 1, d, P),
        lambda i, n, meta, at, row: (meta[0], at[i], head(i, n, meta), 0, 0))
    z_spec = pl.BlockSpec(
        (1, 1, 1, R, d),
        lambda i, n, meta, at, row: (meta[0], at[i], head(i, n, meta), 0, 0))
    block = d * P * 4
    o, S, z = pl.pallas_call(
        functools.partial(_decode_kernel, groups=G, d=d),
        out_shape=(jax.ShapeDtypeStruct((B, KVH, rows, d), f32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, KVH),
            in_specs=[x_spec, s_spec, z_spec],
            out_specs=[x_spec, s_spec, z_spec]),
        # operands count the three prefetched scalars first
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the state's block in and out, each double-buffered, and
            # the group's accumulators beside them
            vmem_limit_bytes=int(4 * block + (16 << 20))),
        interpret=interpret,
        name="mxtpu_retention_decode",
    )(meta, at, order, x, S, z)
    return o[:, :, :G].reshape(B, H, d), S, z


def power_retention_step(q, k, v, log_g, state, slots, active, layer=0):
    """One token a slot. ``q`` ``(B, H, d)``, ``k`` and ``v`` ``(B, KVH,
    d)``, ``log_g`` ``(B, KVH)``; ``state = (S, z)`` is the whole store
    (:func:`state_shapes`), ``slots`` ``(B,)`` int32 names each row's
    state in it, ``active`` ``(B,)`` says which rows are live and
    ``layer`` (an int, or a traced scalar under a scan over layers)
    which layer's states. Returns ``(o (B, H, d) float32, state)``; the
    states of live rows are stepped in place, the others are neither
    read nor written, and their outputs are zero."""
    S, z = state
    step = _pallas_step if _use_pallas(q.shape[-1]) else _jnp_step
    o, S, z = step(q, k, v, log_g, S, z, slots, active, layer)
    return o, (S, z)
