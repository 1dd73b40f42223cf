"""Flash (blockwise) attention — Pallas TPU kernel + blockwise VJP.

No reference counterpart: MXNet 1.x predates flash attention (SURVEY.md
§5.7 — "a genuinely new capability, not a port"); the closest reference
surface is ``contrib/transformer.cc`` interleaved attention, which this
subsumes.

Design:
- Forward: Pallas kernel, grid (batch*heads, q_blocks, kv_blocks), online
  softmax in fp32 VMEM scratch (m, l, acc); causal blocks short-circuit.
  O(T) memory — no T×S score matrix ever materializes in HBM.
- Backward: blockwise ``lax.scan`` recomputation from the saved LSE —
  also O(T) memory. (Pallas bwd kernel is a later optimization.)
- CPU/debug fallback: same math in plain jnp (the test oracle).

Layout: (B, H, T, D) with D <= 128 on the kernel path (MXU lane width);
larger D falls back to the jnp path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register

_NEG_INF = -1e30


def _use_pallas(d):
    """Kernel path: head_dim within the MXU lane width, on any backend
    but the CPU. A backend that fails to start raises here."""
    return d <= 128 and jax.default_backend() != "cpu"


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _mask_scores(s, q_pos0, col0, bq, bk, causal, window):
    """Apply the causal / sliding-window mask to a (bq, bk) score block
    at rows q_pos0.. and cols col0.. (shared by all four kernels)."""
    if not (causal or window > 0):
        return s
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_pos0
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + col0
    ok = rows >= cols
    if window > 0:  # sliding window: see only the last W positions
        ok = ok & (rows - cols < window)
    return jnp.where(ok, s, _NEG_INF)


def _block_active(q_pos0, col0, bq, bk, window):
    """True when a (q block, kv block) cell intersects the causal
    triangle (and, for window > 0, the band)."""
    cond = col0 <= q_pos0 + bq - 1
    if window > 0:
        cond = cond & (col0 + bk - 1 >= q_pos0 - window + 1)
    return cond


def _block_needs_mask(q_pos0, col0, bq, bk, window):
    """False for INTERIOR blocks (every (row, col) pair legal): skipping
    the iota/where there recovers most of the causal-vs-dense gap
    (a pre-round reading: dense ran at 139 TFLOP/s, causal at 81, and
    the mask was a large share of the difference)."""
    need = col0 + bk - 1 > q_pos0
    if window > 0:
        need = need | (q_pos0 + bq - 1 - col0 >= window)
    return need


def _masked_dispatch(compute, cond, need):
    """Run ``compute(apply_mask)`` under ``pl.when``: masked for
    diagonal/boundary blocks, mask-free for interior ones (shared by all
    four kernels so the branch structure cannot drift)."""
    @pl.when(cond & need)
    def _():
        compute(True)

    @pl.when(cond & ~need)
    def _():
        compute(False)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, scale, causal, bq, bk,
                      kv_blocks, window=0, true_t=0, n_active=0):
    """``true_t > 0`` = grouped-query mode: the q rows are G stacked
    heads of a TRUE sequence length ``true_t`` (the wrapper guarantees
    bq | true_t, so a block never straddles heads); masks use the row's
    position WITHIN its head, ``global_row % true_t``.

    ``n_active > 0`` = banded sliding-window mode: the kv grid dimension
    covers only the ``n_active`` blocks that can intersect the band, and
    the TRUE kv block index is derived from the q position — grid steps
    (and their k/v DMA) scale as O(T*W) instead of O(T^2)."""
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    q_pos0 = (qi * bq) % true_t if true_t else qi * bq
    if n_active:
        kv_blk = q_pos0 // bk - (n_active - 1) + ki
        col0 = kv_blk * bk
        last_ki = n_active - 1
    else:
        kv_blk = ki
        col0 = ki * bk
        last_ki = kv_blocks - 1

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute(apply_mask=True):
        # matmul operands stay in the INPUT dtype (bf16 on the training
        # path) with f32 MXU accumulation: fp32xfp32 runs at ~1/4 the
        # bf16 MXU rate on v5e — casting up first capped the whole kernel
        # at ~51 TFLOP/s (measured; the fp32 matmul ceiling)
        q = q_ref[0]                                     # (bq, d)
        k = k_ref[0]                                     # (bk, d)
        v = v_ref[0]                                     # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if apply_mask:
            s = _mask_scores(s, q_pos0, col0, bq, bk, causal, window)
        m_prev = m_scr[:]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    if causal or window > 0:
        # skip blocks entirely above the diagonal, and (windowed) blocks
        # entirely below the band; banded mode additionally guards the
        # clamped negative block indices at the sequence start
        cond = _block_active(q_pos0, col0, bq, bk, window)
        if n_active:
            cond = cond & (kv_blk >= 0)
        _masked_dispatch(compute, cond,
                         _block_needs_mask(q_pos0, col0, bq, bk, window))
    else:
        compute(False)

    @pl.when(ki == last_ki)
    def _finish():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # lse carried as (.., bq, 1): TPU tiling wants the last two block
        # dims to be (8k, 128k) or span the array; (1, bq) violates that
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _pallas_flash_fwd(q, k, v, scale, causal, bq=512, bk=512, window=0):
    B, H, T, D = q.shape
    KVH = k.shape[1]
    S = k.shape[2]
    group = H // KVH
    if group > 1:
        # native grouped-query: fold each kv head's G query heads into
        # the sequence axis (one kernel row per KV head — k/v are fetched
        # ONCE per group instead of being repeated in HBM). bq | T keeps
        # every block inside one head; masks use row % T.
        qr = q.reshape(B * KVH, group * T, D)
        true_t, t_eff = T, group * T
    else:
        qr = q.reshape(B * H, T, D)
        true_t, t_eff = 0, T
    bq = min(bq, T)
    bk = min(bk, S)
    assert T % bq == 0 and S % bk == 0, "seq lens must divide block sizes"
    kr = k.reshape(B * KVH, S, D)
    vr = v.reshape(B * KVH, S, D)
    kv_blocks = S // bk
    # banded grid for sliding-window: only the blocks that can intersect
    # the band get grid steps (O(T*W) instead of O(T^2) DMA + overhead)
    n_active = 0
    # banded indexing assumes self-attention (t_eff == S): with T != S
    # the clamped DMA index and the kernel's unclamped mask positions
    # would disagree (the public op already enforces T == S for windows;
    # this guard keeps internal callers safe too)
    if window > 0 and bq == bk and true_t == 0 and t_eff == S:
        n_active = min((window - 1) // bk + 2, kv_blocks)
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, kv_blocks=kv_blocks,
                               window=window, true_t=true_t,
                               n_active=n_active)
    if n_active:
        grid = (B * KVH, t_eff // bq, n_active)

        def kv_map(b, i, j, _n=n_active, _max=kv_blocks - 1):
            return (b, jnp.clip(i - (_n - 1) + j, 0, _max), 0)

        kv_spec = pl.BlockSpec((1, bk, D), kv_map)
    else:
        grid = (B * KVH, t_eff // bq, kv_blocks)
        kv_spec = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qr.shape[0], t_eff, D), q.dtype),
            jax.ShapeDtypeStruct((qr.shape[0], t_eff, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        name="mxtpu_flash_fwd",
    )(qr, kr, vr)
    return out.reshape(B, H, T, D), lse.reshape(B, H, T)


def _pallas_ready(q, k, causal, block_size):
    """True when the Pallas kernel handles these shapes (else jnp path).
    Grouped-query (fewer kv heads) is native as long as the head counts
    divide; the q block is clamped to the TRUE sequence length so the
    flattened-group layout never straddles heads."""
    bq = min(block_size, q.shape[2])
    return (_use_pallas(q.shape[-1])
            and (not causal or q.shape[2] == k.shape[2])
            and q.shape[1] % k.shape[1] == 0
            and q.shape[2] % bq == 0
            and k.shape[2] % min(block_size, k.shape[2]) == 0)


# ---------------------------------------------------------------------------
# Pallas backward kernels (FlashAttention-2 style two-pass)
# ---------------------------------------------------------------------------


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                      scale, causal, bq, bk, q_blocks, kv_blocks, window=0,
                      true_t=0):
    """Fused FA2-style backward: one pass over (kv_block, q_block) computes
    s/p once and emits all three grads. ALL accumulation happens in VMEM
    scratch — dk/dv over the consecutive q (fast) axis, dq in a full
    (T, d) scratch addressed by dynamic slice — because Pallas TPU only
    defines output-window contents across CONSECUTIVE grid revisits; dq's
    per-q-block output windows would be revisited once per kv block, which
    is exactly the undefined pattern. dq is written out once per
    batch-head row (its (1, T, d) window is current for that whole row)."""
    qi = pl.program_id(2)
    ki = pl.program_id(1)
    q_pos0 = (qi * bq) % true_t if true_t else qi * bq

    @pl.when(qi == 0)
    def _init_kv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute(apply_mask=True):
        # bf16 matmul operands + f32 accumulation (see _flash_fwd_kernel)
        q = q_ref[0]                                     # (bq, d)
        k = k_ref[0]                                     # (bk, d)
        v = v_ref[0]                                     # (bk, d)
        do = do_ref[0]                                   # (bq, d)
        lse = lse_ref[0]                                 # (bq, 1)
        delta = delta_ref[0]                             # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if apply_mask:
            s = _mask_scores(s, q_pos0, ki * bk, bq, bk, causal, window)
        p = jnp.exp(s - lse)                             # (bq, bk) f32
        pc = p.astype(v.dtype)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                    # (bq, bk) f32
        dsc = ds.astype(q.dtype)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            dsc, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.dslice(qi * bq, bq)
        dq_scr[rows, :] = dq_scr[rows, :] + jax.lax.dot_general(
            dsc, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal or window > 0:
        _masked_dispatch(
            compute, _block_active(q_pos0, ki * bk, bq, bk, window),
            _block_needs_mask(q_pos0, ki * bk, bq, bk, window))
    else:
        compute(False)

    @pl.when(qi == q_blocks - 1)
    def _finish_kv():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when((ki == kv_blocks - 1) & (qi == q_blocks - 1))
    def _finish_dq():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_preamble(q, k, v, out, lse, g, block_size):
    """Shared backward setup: GQA head folding, reshapes, and the delta
    term (sum of do*o per row)."""
    B, H, T, D = q.shape
    KVH = k.shape[1]
    S = k.shape[2]
    group = H // KVH
    bq = min(block_size, T)
    bk = min(block_size, S)
    true_t, t_eff = (T, group * T) if group > 1 else (0, T)
    qr = q.reshape(B * KVH, t_eff, D)
    kr = k.reshape(B * KVH, S, D)
    vr = v.reshape(B * KVH, S, D)
    gr = g.reshape(B * KVH, t_eff, D)
    lse_r = lse.reshape(B * KVH, t_eff, 1)
    delta = jnp.sum(gr.astype(jnp.float32)
                    * out.reshape(B * KVH, t_eff, D).astype(jnp.float32),
                    axis=-1, keepdims=True)
    return (qr, kr, vr, gr, lse_r, delta, bq, bk, t_eff // bq, S // bk,
            true_t, t_eff, B * KVH, S, D)


def _pallas_flash_bwd(q, k, v, out, lse, g, scale, causal, bq=512, bk=512,
                      window=0):
    # grouped-query (see _pallas_flash_fwd): q-side tensors fold the
    # group into the sequence axis; dk/dv then accumulate over ALL of a
    # kv head's query heads through the ordinary qi sweep
    (qr, kr, vr, gr, lse_r, delta, bq, bk, q_blocks, kv_blocks, true_t,
     t_eff, BK, S, D) = _bwd_preamble(q, k, v, out, lse, g, max(bq, bk))

    # grid: (batch, kv_block, q_block) — q is the fast (reduction) axis
    q_spec = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
    row_spec = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, q_blocks=q_blocks,
                          kv_blocks=kv_blocks, window=window,
                          true_t=true_t),
        grid=(BK, kv_blocks, q_blocks),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((1, t_eff, D), lambda b, j, i: (b, 0, 0)),
                   pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
                   pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((BK, t_eff, D), q.dtype),
                   jax.ShapeDtypeStruct((BK, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BK, S, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((t_eff, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        name="mxtpu_flash_bwd",
    )(qr, kr, vr, gr, lse_r, delta)

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


# ---------------------------------------------------------------------------
# jnp blockwise reference (CPU path + oracle)
# ---------------------------------------------------------------------------


def _jnp_flash_fwd(q, k, v, scale, causal, window=0):
    B, H, T, D = q.shape
    S = k.shape[2]
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal or window > 0:
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        if window > 0:
            rows = jnp.arange(T)[:, None]
            cols = jnp.arange(S)[None, :]
            mask = mask & (rows - (cols + (T - S)) < window)
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhts,bhsd->bhtd", p / l, v.astype(jnp.float32))
    lse = (m + jnp.log(l))[..., 0]
    return o.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# custom VJP: blockwise backward via scan over kv blocks
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_core(q, k, v, scale, causal, block_size, window=0,
                         native_gqa=False):
    out, _ = _fwd_impl(q, k, v, scale, causal, block_size, window,
                       native_gqa)
    return out


def _repeat_kv(q, k, v):
    """Expand grouped kv heads to the full head count (non-Pallas paths)."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


def _fwd_impl(q, k, v, scale, causal, block_size, window=0, native_gqa=False):
    if _pallas_ready(q, k, causal, block_size):
        # default: repeat kv for the kernel — measured 3x FASTER than the
        # flattened native-GQA layout at H32/KVH8/T4k (0.61 vs 1.91 ms
        # fwd; Mosaic pipelines the static-offset kernel much better than
        # the dynamic row%T variant). native_gqa=True trades that for
        # O(KVH) kv HBM at very long contexts.
        kf, vf = (k, v) if native_gqa else _repeat_kv(q, k, v)
        return _pallas_flash_fwd(q, kf, vf, scale, causal,
                                 bq=block_size, bk=block_size, window=window)
    kf, vf = _repeat_kv(q, k, v)
    return _jnp_flash_fwd(q, kf, vf, scale, causal, window)


def _flash_fwd_rule(q, k, v, scale, causal, block_size, window=0,
                    native_gqa=False):
    out, lse = _fwd_impl(q, k, v, scale, causal, block_size, window,
                         native_gqa)
    return out, (q, k, v, out, lse)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, scale, causal, bq, bk,
                         kv_blocks, window=0, true_t=0):
    """Split-backward dq kernel: grid (batch, q_block, kv_block) with kv
    innermost, so each q block's output window is revisited CONSECUTIVELY
    and dq accumulates in a (bq, d) scratch — no full-(T, d) scratch and
    no dynamic-slice writes (those serialize Mosaic's pipeline in the
    fused kernel). s/p are recomputed per cell; the extra matmul is
    cheaper than the lost overlap."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_pos0 = (qi * bq) % true_t if true_t else qi * bq

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute(apply_mask=True):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if apply_mask:
            s = _mask_scores(s, q_pos0, ki * bk, bq, bk, causal, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal or window > 0:
        _masked_dispatch(
            compute, _block_active(q_pos0, ki * bk, bq, bk, window),
            _block_needs_mask(q_pos0, ki * bk, bq, bk, window))
    else:
        compute(False)

    @pl.when(ki == kv_blocks - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                          bq, bk, q_blocks, window=0, true_t=0):
    """Split-backward dk/dv kernel: grid (batch, kv_block, q_block) with
    q innermost; dk/dv accumulate in (bk, d) scratches over the q sweep."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    q_pos0 = (qi * bq) % true_t if true_t else qi * bq

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute(apply_mask=True):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if apply_mask:
            s = _mask_scores(s, q_pos0, ki * bk, bq, bk, causal, window)
        p = jnp.exp(s - lse)
        pc = p.astype(v.dtype)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal or window > 0:
        _masked_dispatch(
            compute, _block_active(q_pos0, ki * bk, bq, bk, window),
            _block_needs_mask(q_pos0, ki * bk, bq, bk, window))
    else:
        compute(False)

    @pl.when(qi == q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pallas_flash_bwd_split(q, k, v, out, lse, g, scale, causal, bq=512,
                            bk=512, window=0):
    """Two-kernel FA2 backward (dq pass + dkv pass). No full-T scratch,
    so it scales to any T the forward handles."""
    (qr, kr, vr, gr, lse_r, delta, bq, bk, q_blocks, kv_blocks, true_t,
     t_eff, BK, S, D) = _bwd_preamble(q, k, v, out, lse, g, max(bq, bk))

    q_spec_q = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    kv_spec_q = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))
    row_spec_q = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, kv_blocks=kv_blocks, window=window,
                          true_t=true_t),
        grid=(BK, q_blocks, kv_blocks),
        in_specs=[q_spec_q, kv_spec_q, kv_spec_q, q_spec_q, row_spec_q,
                  row_spec_q],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BK, t_eff, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        name="mxtpu_flash_bwd_dq",
    )(qr, kr, vr, gr, lse_r, delta)

    q_spec_kv = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0))
    kv_spec_kv = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
    row_spec_kv = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, q_blocks=q_blocks, window=window,
                          true_t=true_t),
        grid=(BK, kv_blocks, q_blocks),
        in_specs=[q_spec_kv, kv_spec_kv, kv_spec_kv, q_spec_kv, row_spec_kv,
                  row_spec_kv],
        out_specs=[pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
                   pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((BK, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BK, S, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        name="mxtpu_flash_bwd_dkv",
    )(qr, kr, vr, gr, lse_r, delta)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


# the FUSED Pallas backward accumulates dq in a full (T, d) VMEM scratch
# (see _flash_bwd_kernel docstring) — past this T the scratch blows the
# VMEM budget. Only the FUSED backward (opt-in via MXTPU_FLASH_BWD=fused,
# see _flash_bwd_rule) is subject to this cap; the default split backward
# has no full-T scratch and runs at any T the forward handles.
_PALLAS_BWD_MAX_T = 8192


def _flash_bwd_rule(scale, causal, block_size, window, native_gqa, res, g):
    q, k, v, out, lse = res
    group = q.shape[1] // k.shape[1]
    from ..base import getenv

    _fused = getenv("MXTPU_FLASH_BWD", "split") == "fused"
    use_native = (native_gqa and group > 1
                  and _pallas_ready(q, k, causal, block_size)
                  # only the FUSED backward's full-T dq scratch caps the
                  # flattened length; the split default has no cap
                  and (not _fused
                       or group * q.shape[2] <= _PALLAS_BWD_MAX_T))
    if group > 1 and not use_native:
        # default GQA path (also the fallback when the native backward's
        # flattened q exceeds the VMEM cap): run the grad on repeated kv,
        # fold dk/dv back down over the group
        kf, vf = _repeat_kv(q, k, v)
        dq, dkf, dvf = _flash_bwd_rule(scale, causal, block_size, window,
                                       False, (q, kf, vf, out, lse), g)
        B, KVH, S, D = k.shape
        dk = dkf.reshape(B, KVH, group, S, D).sum(axis=2).astype(k.dtype)
        dv = dvf.reshape(B, KVH, group, S, D).sum(axis=2).astype(v.dtype)
        return dq, dk, dv
    if _pallas_ready(q, k, causal, block_size):
        fits_fused = group * q.shape[2] <= _PALLAS_BWD_MAX_T
        if _fused and fits_fused:
            # kept selectable for A/B: measured 2.44 ms vs split's 1.88
            # at T=4k D=64 (the full-T dq scratch + dynamic-slice writes
            # serialize the pipeline), and capped at _PALLAS_BWD_MAX_T
            return _pallas_flash_bwd(q, k, v, out, lse, g, scale, causal,
                                     bq=block_size, bk=block_size,
                                     window=window)
        # default: split two-kernel backward — no full-T scratch, so it
        # also extends the Pallas path past _PALLAS_BWD_MAX_T
        return _pallas_flash_bwd_split(q, k, v, out, lse, g, scale,
                                       causal, bq=block_size,
                                       bk=block_size, window=window)
    B, H, T, D = q.shape
    S = k.shape[2]
    bk = min(block_size, S)
    g32 = g.astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)  # (B,H,T)

    nblocks = S // bk if S % bk == 0 else 1
    if S % bk != 0:
        bk = S

    def kv_block(j):
        ks = lax.dynamic_slice_in_dim(k, j * bk, bk, axis=2).astype(jnp.float32)
        vs = lax.dynamic_slice_in_dim(v, j * bk, bk, axis=2).astype(jnp.float32)
        s = jnp.einsum("bhtd,bhsd->bhts", q32, ks) * scale
        if causal or window > 0:
            rows = jnp.arange(T)[:, None]
            cols = j * bk + jnp.arange(bk)[None, :]
            ok = rows >= cols + (T - S)
            if window > 0:
                ok = ok & (rows - (cols + (T - S)) < window)
            s = jnp.where(ok, s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])  # (B,H,T,bk)
        dv = jnp.einsum("bhts,bhtd->bhsd", p, g32)
        dp = jnp.einsum("bhtd,bhsd->bhts", g32, vs)
        ds = p * (dp - delta[..., None]) * scale
        dq = jnp.einsum("bhts,bhsd->bhtd", ds, ks)
        dk = jnp.einsum("bhts,bhtd->bhsd", ds, q32)
        return dq, dk, dv

    def scan_body(dq_acc, j):
        dq_j, dk_j, dv_j = kv_block(j)
        return dq_acc + dq_j, (dk_j, dv_j)

    dq, (dks, dvs) = lax.scan(scan_body,
                              jnp.zeros(q.shape, jnp.float32),
                              jnp.arange(nblocks))
    dk = jnp.moveaxis(dks, 0, 2).reshape(k.shape)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(v.shape)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


flash_attention_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# paged decode attention: batch=many, q_len=1, K/V via block-table
# indirection (the serving fast path — vLLM/PagedAttention shape)
# ---------------------------------------------------------------------------


# VMEM the kernel spends on K/V in flight: two buffers of K and two of
# V, a group of blocks each
_PAGED_BUFFER_BYTES = 1 << 20


def _paged_group_blocks(block_size, width, itemsize, max_blocks):
    """Blocks of a sequence that land in one VMEM buffer and are
    attended to in one compute step: as many as the buffer budget
    holds, a power of two (at a block of 16 tokens a group's rows then
    fill whole lane tiles of the scores) and no more than a table
    holds."""
    fit = _PAGED_BUFFER_BYTES // (4 * block_size * width * itemsize)
    g = 1
    while 2 * g <= min(fit, max_blocks):
        g *= 2
    return g


def _paged_decode_kernel(layer_ref, tables_ref, lens_ref, *refs, scale, bs,
                         gb, latent=False):
    """One invocation walks the LIVE blocks of every sequence, ``gb`` of
    them a compute step. The pools stay in HBM; block ``j`` of sequence
    ``b`` is copied from ``(layer, tables[b, j])`` into rows ``(j % gb)
    * bs ..`` of a ``(gb * bs, KVH*D)`` buffer by an explicit DMA,
    double buffered: while one group is attended to the next one is in
    flight, and that is the next group of the same sequence or, at a
    sequence's last group, the first group of the next sequence that
    has a context. A sequence with ``ctx == 0`` costs a scalar test.

    All heads at once: the sequence's queries are spread to a block
    diagonal ``a`` ``(H, KVH*D)`` (row ``h`` holds query head ``h`` in
    the lanes of its kv head and zeros elsewhere; ``seg_ref`` is that
    pattern), so ``a . K^T`` is every head's scores ``(H, rows)`` in
    one product with K's whole rows, and ``p . V`` ``(H, KVH*D)`` holds
    each head's weighted sum in the lanes of its kv head; what the
    other lanes hold is never read. Online softmax in float32 over the
    groups, exactly the prefill kernel's recurrence with the heads as
    its rows.

    ``latent``: ONE pool whose row is a token's latent, shared by every
    query head (:func:`latent_decode_attention`). The queries are their
    own diagonal (``q_ref`` is ``(B, H, width)``, no pattern), one copy
    a block brings the tile that serves both products (the scores over
    all of a row's lanes, the weighted sum over the same rows: the
    caller keeps the lanes that are values), and ``o_ref`` takes all
    heads' rows whole."""
    if latent:
        q_ref, k_hbm, o_ref, k_buf, sems, a_scr, m_scr, l_scr, acc_scr = refs
        seg_ref, v_buf, pools = None, k_buf, ((k_hbm, k_buf),)
        B, group = q_ref.shape[0], 1
    else:
        (q_ref, seg_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, a_scr,
         m_scr, l_scr, acc_scr) = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
        B, group, _ = q_ref.shape
    mb = tables_ref.shape[1]
    rows = gb * bs
    layer = layer_ref[0]

    def ctx_of(b):
        return jnp.minimum(lens_ref[b], mb * bs)

    def fetch(b, j, slot, wait):
        """Start, or wait for, the copies of sequence ``b``'s group
        ``j`` into buffer ``slot``: its live blocks only."""
        n = pl.cdiv(ctx_of(b), bs)
        for i in range(gb):
            blk = j * gb + i

            @pl.when(blk < n)
            def _(i=i, blk=blk):
                src = tables_ref[b, blk]
                dst = pl.ds(i * bs, bs)
                for s, (pool, buf) in enumerate(pools):
                    copy = pltpu.make_async_copy(
                        pool.at[layer, src], buf.at[slot, dst],
                        sems.at[s, slot])
                    if wait:
                        copy.wait()
                    else:
                        copy.start()

    def next_live(b):
        """The first sequence after ``b`` with a context, or ``B``."""
        return lax.while_loop(
            lambda x: jnp.logical_and(
                x < B, lens_ref[jnp.minimum(x, B - 1)] <= 0),
            lambda x: x + 1, b + 1)

    # rows of a buffer past a context keep what an earlier group left
    # there and weigh 0: they only have to be finite
    for _, buf in pools:
        buf[...] = jnp.zeros_like(buf)
    o_ref[...] = jnp.zeros_like(o_ref)

    first = next_live(-1)

    @pl.when(first < B)
    def _prologue():
        fetch(first, 0, 0, wait=False)

    def sequence(b, slot):
        ctx = ctx_of(b)
        groups = pl.cdiv(ctx, rows)

        @pl.when(groups > 0)
        def _init():
            if latent:
                a_scr[...] = q_ref[b]
            else:
                a = jnp.zeros(a_scr.shape, jnp.float32)
                for g in range(group):
                    a = a + (q_ref[b, g:g + 1, :].astype(jnp.float32)
                             * seg_ref[g].astype(jnp.float32))
                a_scr[...] = a.astype(a_scr.dtype)
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        def attend(j, slot):
            last = j + 1 == groups
            nb = lax.cond(last, lambda: next_live(b), lambda: b)
            nj = jnp.where(last, 0, j + 1)

            @pl.when(nb < B)
            def _prefetch():
                fetch(nb, nj, 1 - slot, wait=False)

            fetch(b, j, slot, wait=True)
            s = jax.lax.dot_general(
                a_scr[...], k_buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (H, rows)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * rows
            s = jnp.where(cols < ctx, s, _NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
                p.astype(v_buf.dtype), v_buf[slot], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new
            return 1 - slot

        slot = lax.fori_loop(0, groups, attend, slot)

        @pl.when(groups > 0)
        def _finish():
            o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
            if latent:
                o_ref[b] = o.astype(o_ref.dtype)
                return
            for g in range(group):
                o_ref[b, g:g + 1, :] = jnp.sum(
                    jnp.where(seg_ref[g] != 0, o, 0.0), axis=0,
                    keepdims=True).astype(o_ref.dtype)

        return slot

    lax.fori_loop(0, B, sequence, 0)


def _pallas_paged_decode(q, k_pool, v_pool, tables, lens, scale,
                         layer=0, interpret=False):
    """``k_pool``/``v_pool`` are the WHOLE pool ``(layers, num_blocks,
    block_size, KVH*D)``, handed to the kernel where they lie in HBM;
    ``layer`` rides the scalar-prefetch lane into the source of the
    kernel's copies, so they address the layer's blocks inside the pool
    and no slice of it is ever made."""
    _, _, bs, width = k_pool.shape
    gb = _paged_group_blocks(bs, width, k_pool.dtype.itemsize,
                             tables.shape[1])
    return _paged_decode_call(jnp.asarray(layer, jnp.int32).reshape(1),
                              tables, lens, q, k_pool, v_pool, scale=scale,
                              gb=gb, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "gb", "interpret"))
def _paged_decode_call(layer, tables, lens, q, k_pool, v_pool, *, scale, gb,
                       interpret):
    """The kernel's call, a function of its own under ``jit`` with the
    layer as an operand: a decoder's layers then share one trace and
    one lowering of the kernel (traced layer by layer, twelve of them
    cost a serve cell 9 s of every start). Queries go in and results
    come out as ``(B, group, KVH*D)``: row ``g`` holds query head ``g``
    of every kv head, each in its kv head's lanes (for one query head a
    kv head that is ``q`` itself, reshaped)."""
    B, H, D = q.shape
    _, _, bs, width = k_pool.shape
    KVH = width // D
    group = H // KVH
    itemsize = k_pool.dtype.itemsize
    # heads as whole sublane tiles of either operand dtype; the rows
    # past H are zero queries that nothing reads
    hp = -(-H // 16) * 16
    head = np.arange(hp)[None, :, None]
    lane_head = (np.arange(width) // D)[None, None, :]
    seg = jnp.asarray(
        head == lane_head * group + np.arange(group)[:, None, None],
        q.dtype)                                         # (group, hp, width)
    qr = q.reshape(B, KVH, group, D).swapaxes(1, 2).reshape(B, group, width)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    # what grows with the shapes: K/V in flight, and q, o and the
    # pattern whole (each twice, as the pipeline holds them); the
    # scratch and the values of a step ride in the margin
    vmem = (4 * gb * bs * width * itemsize
            + (4 * qr.size + 2 * seg.size) * q.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, bs=bs, gb=gb),
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole, whole, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, gb * bs, width), k_pool.dtype),
                pltpu.VMEM((2, gb * bs, width), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hp, width), q.dtype),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, width), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem + (8 << 20))),
        interpret=interpret,
        name="mxtpu_paged_decode",
    )(layer, tables, lens, qr, seg, k_pool, v_pool)
    return out.reshape(B, group, KVH, D).swapaxes(1, 2).reshape(B, H, D)


def _jnp_paged_decode(q, k_pool, v_pool, tables, lens, scale, layer=0):
    """CPU path + oracle: materialize each slot's context via the same
    ``(layer, table)`` gather the kernel's copies perform (one
    indexing step into the whole pool, no layer's slice in between),
    then masked softmax."""
    B, H, D = q.shape
    _, _, bs, width = k_pool.shape
    KVH = width // D
    mb = tables.shape[1]
    S = mb * bs
    k = k_pool[layer, tables].reshape(B, S, KVH, D)
    v = v_pool[layer, tables].reshape(B, S, KVH, D)
    group = H // KVH
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] < lens[:, None]
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhs,bshd->bhd", p / l, v.astype(jnp.float32))
    # fully-masked rows (empty / inactive slots) produce zeros, not the
    # uniform-weights garbage a raw softmax would
    out = jnp.where((lens > 0)[:, None, None], out, 0.0)
    return out.astype(q.dtype)


@register("paged_decode_attention")
def paged_decode_attention(query, k_pool, v_pool, block_tables,
                           context_lens, scale=None, layer=None):
    """Decode-specialized attention: ``query`` is one new token per
    sequence, ``(B, H, D)``. With ``layer`` (a static int) K/V are the
    WHOLE paged pool as :class:`~mxnet_tpu.serving.PagedKVCache` keeps
    it, ``(layers, num_blocks, block_size, KVH*D)``, read in place: the
    index goes into the source of the kernel's copies, so no layer's
    slice of the pool is ever materialised (a ``k_pool[li]`` operand is
    a copy of that layer per call). Without ``layer`` they are one layer
    with the heads apart, ``(num_blocks, block_size, KVH, D)`` — the
    same path over a pool of one layer. ``block_tables`` ``(B,
    max_blocks)`` int32 names each sequence's pool blocks in logical
    order and
    ``context_lens`` ``(B,)`` int32 is how many positions are valid
    (rows past it — padding and the null block — are masked).

    TPU path: one kernel invocation a call. The tables and lengths are
    scalar-prefetched, the pool stays in HBM, and the kernel walks each
    sequence's ``ceil(ctx / block_size)`` live blocks only: a group of
    blocks (as many as a fixed VMEM budget holds, 8 at 16 tokens of
    768 lanes) is copied from ``(layer, tables[seq, j])`` by explicit
    double-buffered DMAs, the next group (of this sequence, or the
    first of the next one with a context) in flight while this one is
    attended to; an empty slot costs a scalar test. All heads of a
    group of blocks are two products: the queries spread to a block
    diagonal ``(H, KVH*D)`` against K's whole rows, and the weights
    against V's; GQA is native (a kv head's ``H/KVH`` query heads are
    rows of the same diagonal block, so each K/V block is fetched
    once). CPU/debug path: the same math via a plain gather (the test
    oracle).

    Sequences with ``context_lens == 0`` (empty batch slots) return
    zeros. Grows O(1) per generated token — no T×S score matrix, no
    cache reshuffling as sequences grow (allocation is the host-side
    free list in :mod:`mxnet_tpu.serving.kvcache`)."""
    if scale is None:
        scale = 1.0 / (query.shape[-1] ** 0.5)
    D = query.shape[-1]
    if layer is None:
        nb, bs = k_pool.shape[:2]
        k_pool = k_pool.reshape(1, nb, bs, -1)
        v_pool = v_pool.reshape(1, nb, bs, -1)
        layer = 0
    if k_pool.ndim != 4 or not 0 <= layer < k_pool.shape[0]:
        raise ValueError(
            "the pool is (layers, num_blocks, block_size, KVH*D) with "
            f"layer=, or one layer (num_blocks, block_size, KVH, D); got "
            f"{k_pool.shape} and layer={layer}")
    kvh, rest = divmod(k_pool.shape[-1], D)
    if rest or kvh == 0 or query.shape[1] % kvh != 0:
        raise ValueError("query heads must be a multiple of kv heads; got "
                         f"{query.shape[1]} vs {k_pool.shape[-1]}/{D}")
    tables = block_tables.astype(jnp.int32)
    lens = context_lens.astype(jnp.int32)
    decode = _pallas_paged_decode if _use_pallas(D) else _jnp_paged_decode
    return decode(query, k_pool, v_pool, tables, lens, float(scale),
                  layer=int(layer))


# ---------------------------------------------------------------------------
# latent decode attention: the same walk over ONE pool whose row is a
# token's compressed key-value latent, shared by every query head
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pallas_latent_decode(layer, tables, lens, q, pool, *, scale,
                          interpret=False):
    """:func:`_paged_decode_kernel` over one pool ``(layers, num_blocks,
    block_size, width)`` with one "KV head" of ``width`` lanes: ``q``
    is ``(B, H, width)``, each head against a token's whole row, and
    the result ``(B, H, width)`` is the weights' sum over the same
    rows. A group of blocks is one ``(gb * bs, width)`` tile in VMEM,
    fetched once for both products."""
    B, H, width = q.shape
    _, _, bs, _ = pool.shape
    gb = _paged_group_blocks(bs, width, pool.dtype.itemsize, tables.shape[1])
    hp = -(-H // 16) * 16
    qp = jnp.pad(q, ((0, 0), (0, hp - H), (0, 0)))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    vmem = 2 * gb * bs * width * pool.dtype.itemsize \
        + 4 * qp.size * q.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, bs=bs, gb=gb,
                          latent=True),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, gb * bs, width), pool.dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.VMEM((hp, width), q.dtype),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, 1), jnp.float32),
                pltpu.VMEM((hp, width), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem + (8 << 20))),
        interpret=interpret,
        name="mxtpu_latent_decode",
    )(layer, tables, lens, qp, pool)
    return out[:, :H]


def _jnp_latent_decode(layer, tables, lens, q, pool, *, scale):
    """CPU path + oracle: each slot's rows through the ``(layer,
    table)`` gather, a masked softmax in float32, the weights' sum over
    the rows themselves."""
    B, mb = tables.shape
    rows = pool[layer, tables].reshape(B, mb * pool.shape[2], -1)
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * scale
    mask = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] < lens[:, None]
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhs,bsw->bhw", p, rows)
    # an empty slot gives zeros, not a uniform mean of the null block
    return jnp.where((lens > 0)[:, None, None], out, 0.0).astype(q.dtype)


def latent_decode_attention(q_lat, q_rope, pool, block_tables, context_lens,
                            scale, layer=0):
    """Decode attention in the absorbed form of latent attention (MLA):
    a token keeps ONE row for all heads, ``[c_kv | k_rope]`` (``rank +
    rope`` numbers: the compressed key-value latent after its norm and
    the shared rotary key after its rotation), in the paged pool
    ``(layers, num_blocks, block_size, lanes)``: ``lanes`` is ``rank +
    rope`` in whole lane tiles of 128 (576 -> 640, which is what the
    device's tiling makes of a 576-wide row whatever the array says; the
    kernel's copies take whole tiles), zeros past the row. ``q_lat``
    ``(B, H, rank)`` is each head's no-position query carried into the
    latent's space (``q_nope W_uk^T``), ``q_rope`` ``(B, H, rope)`` its
    rotated part. Head ``h``'s score against a token is ``([q_lat |
    q_rope]_h . row) * scale``; the result ``(B, H, rank)`` is the
    softmax-weighted sum of the rows' latent parts, which the caller
    expands a head through ``W_uv``. ``layer`` may be traced (a scanned
    stack of layers): it rides the scalar-prefetch lane into the source
    of the kernel's copies, so the pool is read where it lies.

    TPU path: :func:`paged_decode_attention`'s kernel body with one
    pool (``mxtpu_latent_decode``): live blocks only, 8 blocks a
    double-buffered copy, one ``(128, lanes)`` tile a group that
    serves the scores (all its lanes against the 32 heads' rows) and the
    weighted sum (the same tile; the rotary lanes of the result are
    dropped here). CPU/debug path: a plain gather (the oracle).
    Sequences with ``context_lens == 0`` return zeros."""
    rank = q_lat.shape[-1]
    pad = pool.shape[-1] - rank - q_rope.shape[-1]
    if pool.ndim != 4 or pad < 0:
        raise ValueError(
            "the latent pool is (layers, num_blocks, block_size, lanes) "
            f"with lanes >= rank + rope = {rank} + {q_rope.shape[-1]}; got "
            f"{pool.shape}")
    # lanes past rank + rope (the pool's row in whole lane tiles) hold
    # zeros on both sides and add nothing to a score
    q = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros(q_lat.shape[:-1] + (pad,), q_lat.dtype)],
        axis=-1).astype(pool.dtype)
    # (any row width: the kernel's choice is the backend's alone)
    decode = _pallas_latent_decode if _use_pallas(0) else _jnp_latent_decode
    out = decode(jnp.asarray(layer, jnp.int32).reshape(1),
                 block_tables.astype(jnp.int32),
                 context_lens.astype(jnp.int32), q, pool,
                 scale=float(scale))
    return out[..., :rank].astype(q_lat.dtype)


@register("flash_attention", aliases=("_contrib_flash_attention",))
def flash_attention(query, key, value, scale=None, causal=False,
                    block_size=1024, window=0, native_gqa=False):
    """Memory-efficient attention. query/key/value: (B, H, T, D).

    Kernel matmuls keep the INPUT dtype (bf16 on the training path)
    with f32 MXU accumulation — the round-3 kernels upcast to fp32
    first, which capped them at the ~51 TFLOP/s fp32 MXU ceiling. With
    bf16 operands, the split two-kernel backward (default, see
    MXTPU_FLASH_BWD), and mask-free interior blocks, causal fwd+bwd
    measures 85 TFLOP/s / 43% MFU and dense non-causal 139 TFLOP/s /
    71% MFU (T=4k, D=64, v5e).
    block_size sweep with the bf16 kernels: 512 -> 45, 1024 -> 49-61
    (run variance) — 1024 stays the default; (bq, bk) clamp to (T, S)
    for short sequences. 1024x1024 bf16 q/k/v/o blocks + f32
    accumulators fit v5e VMEM (~16 MB) at D<=128.

    Grouped-query attention (fewer kv heads, ``KVH | H``) is accepted
    directly; the default path repeats kv inside the op (measured 3x
    faster on v5e than the flattened native-GQA kernel layout, whose
    dynamic row%T offsets pipeline poorly in Mosaic). ``native_gqa=True``
    opts into the no-repeat kernels — O(KVH) kv HBM instead of O(H),
    the right trade at very long contexts; both paths are oracle-tested
    on-chip (tests_tpu).

    ``window > 0`` selects sliding-window (Mistral/Longformer-style
    local causal) attention: position i sees the last ``window``
    positions only. The forward kernel uses a BANDED grid: the kv grid
    dimension covers only the blocks that can intersect the band, so
    grid steps and k/v DMA scale as O(T*window) like the FLOPs
    (measured: 8.7 -> 21.3 TFLOP/s at T=32k/W=1k on v5e). The backward
    kernel skips out-of-band COMPUTE but still walks the full grid.
    The sldwin_atten_* ops are the dense op-surface analog."""
    if scale is None:
        scale = 1.0 / (query.shape[-1] ** 0.5)
    if window and window < 0:
        raise ValueError(f"window must be >= 0 (0 disables); got {window}")
    if query.shape[1] % key.shape[1] != 0:
        raise ValueError("query heads must be a multiple of kv heads; got "
                         f"{query.shape[1]} vs {key.shape[1]}")
    if window and window > 0:
        causal = True
        if query.shape[2] != key.shape[2]:
            raise ValueError("window attention expects self-attention "
                             "(T == S)")
    return flash_attention_core(query, key, value, float(scale), bool(causal),
                                int(block_size), int(window),
                                bool(native_gqa))
