"""Fused 1x1-convolution (matmul) + BatchNorm-statistics Pallas kernels.

The reference fuses Conv+BN in its graph passes (reference:
``src/operator/subgraph/mkldnn/mkldnn_conv.cc`` MKLDNN conv+BN subgraph
fusion; ``src/operator/nn/batch_norm.cc`` for the op semantics). On TPU
the equivalent leverage point is different: XLA already fuses elementwise
chains, but it cannot (a) compute the BN batch statistics in the epilogue
of the conv that produces the tensor — the reduction forces a second full
HBM read — or (b) feed a conv from an *unmaterialised* normalize+relu of
the previous conv's raw output. A 1x1 convolution is exactly a matmul
over the flattened (N*H*W, C) activations, and ResNet-50's 1x1 convs
produce ~79% of all conv-output elements, so this module implements:

    y_raw, ysum, ysumsq = fused_matmul_bn_stats(x, w, scale, bias, relu)

a Pallas matmul with
  * an optional **prologue**: x is interpreted as a RAW conv output and
    normalize+scale+shift (+relu) is applied per-channel on the fly while
    tiles stream from HBM (scale/bias fold mean/var/gamma/beta), and
  * a **stats epilogue**: per-output-channel sum and sum-of-squares are
    accumulated in f32 across the grid, so the following BatchNorm's
    batch moments come for free with the matmul's own output write.

The backward (``fused_matmul_bn_stats_vjp``-registered custom_vjp) hands
the stat-output cotangents back as per-channel scalars: because
``mean``/``var`` are derived from ysum/ysumsq *outside* the kernel by
ordinary jnp arithmetic, the BN backward's batch-coupling terms arrive
here as ``dY = dy_raw + d_ysum[c] + 2*Y*d_ysumsq[c]``, and the heavy
matmuls (dW, dX) run as Pallas kernels with that correction applied in
their prologues — no standalone BN-backward reduction kernels remain.
"""

import functools

import jax
import jax.numpy as jnp


def _pick_block(dim, candidates):
    for c in candidates:
        if dim % c == 0:
            return c
    return None


def _blocks_ok(m, n, k):
    return (_pick_block(m, _BM_CANDIDATES) is not None
            and _pick_block(n, _BN_CANDIDATES) is not None
            and _pick_block(k, _BK_CANDIDATES) is not None)


_BM_CANDIDATES = (8192, 6272, 4096, 3136, 2048, 1792, 1024, 896, 784, 512,
                  448, 392, 256, 128, 64, 32, 16, 8)
_BN_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)
_BK_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)

#: VMEM working-set budget (bytes) for joint block-size selection —
#: x/w/o tiles are double-buffered by Mosaic, acc is f32
_VMEM_BUDGET = 10 * 1024 * 1024


def _pick_fwd_blocks(M, K, N, bm=None, bn=None, bk=None, itemsize=2):
    """Largest bm then bn/bk that divide the shape and fit the budget:
    big tiles amortise the per-grid-step DMA/sequencing overhead that
    dominates the small-K/N ResNet shapes (M=401408, K=N=64 measured
    4x slower with 1024-row tiles than XLA's matmul)."""
    bn = bn or _pick_block(N, _BN_CANDIDATES)
    bk = bk or _pick_block(K, _BK_CANDIDATES)
    if bm is None:
        for cand in _BM_CANDIDATES:
            if M % cand:
                continue
            vmem = (2 * cand * bk * itemsize + 2 * bk * bn * itemsize
                    + 2 * cand * bn * itemsize + cand * bn * 4)
            if vmem <= _VMEM_BUDGET:
                bm = cand
                break
        bm = bm or _pick_block(M, _BM_CANDIDATES)
    return bm, bn, bk


def _pick_bwd_blocks(M, K, N, itemsize=2):
    """Block sizes for the two backward kernels under the VMEM budget.
    The dX kernel is the fattest: dy/y (bm, bn) + w/x (bko-sided) tiles
    double-buffered plus an (bm, bko) f32 accumulator."""
    bko = _pick_block(K, (512, 256, 128, 64, 32, 16, 8))
    bn = _pick_block(N, (512, 256, 128, 64, 32, 16, 8))
    bm = None
    for cand in _BM_CANDIDATES:
        if M % cand:
            continue
        vmem = (2 * 2 * cand * bn * itemsize      # dy, y tiles
                + 2 * bko * bn * itemsize         # w tile
                + 2 * cand * bko * itemsize       # x tile
                + 2 * cand * bko * itemsize       # dx out tile
                + cand * bko * 4                  # accumulator
                + cand * bn * 4)                  # dY f32 intermediate
        if vmem <= _VMEM_BUDGET:
            bm = cand
            break
    bm = bm or _pick_block(M, _BM_CANDIDATES)
    return bm, bko, bn


def _fwd_kernel(x_ref, w_ref, s_ref, t_ref, o_ref, sum_ref, ssq_ref,
                acc_ref, stat_ref, *, nk, nm, bn, apply_input, relu,
                out_dtype):
    from jax.experimental import pallas as pl

    k = pl.program_id(2)
    m = pl.program_id(1)
    n = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if apply_input:
        xf = x.astype(jnp.float32) * s_ref[...] + t_ref[...]
        if relu:
            xf = jnp.maximum(xf, 0.0)
        x = xf.astype(x_ref.dtype)
    acc_ref[...] += jnp.dot(x, w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        y = acc_ref[...]
        o_ref[...] = y.astype(out_dtype)
        # stats accumulate in VMEM scratch — writing them through
        # revisited (1, bn) output windows forces a flush/refetch every
        # m-step that breaks the DMA pipeline (measured 3.4x off the
        # HBM roofline). n is outermost, so one (1, bn) scratch pair
        # serves each n-block's whole m-sweep; emitted once at the end.

        @pl.when(m == 0)
        def _zero():
            stat_ref[...] = jnp.zeros_like(stat_ref)

        stat_ref[0:1, :] += jnp.sum(y, axis=0, keepdims=True)
        stat_ref[1:2, :] += jnp.sum(y * y, axis=0, keepdims=True)

        @pl.when(m == nm - 1)
        def _emit():
            sum_ref[...] = stat_ref[0:1, :]
            ssq_ref[...] = stat_ref[1:2, :]


@functools.partial(jax.jit, static_argnames=("relu", "bm", "bn", "bk",
                                             "interpret"))
def _fused_fwd_pallas(x, w, scale, bias, relu=False, bm=None, bn=None,
                      bk=None, interpret=False):
    """x: (M, K) conv-output-major activations; w: (K, N).

    scale/bias: (K,) f32 per-channel prologue (None disables); returns
    (y_raw (M, N) x.dtype, ysum (N,) f32, ysumsq (N,) f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bn, bk = _pick_fwd_blocks(M, K, N, bm, bn, bk,
                                  itemsize=x.dtype.itemsize)
    nk = K // bk
    apply_input = scale is not None
    if apply_input:
        s2 = scale.astype(jnp.float32).reshape(1, K)
        t2 = bias.astype(jnp.float32).reshape(1, K)
    else:  # dummy operands keep the call signature static
        s2 = jnp.zeros((1, K), jnp.float32)
        t2 = jnp.zeros((1, K), jnp.float32)

    kernel = functools.partial(_fwd_kernel, nk=nk, nm=M // bm, bn=bn,
                               apply_input=apply_input,
                               relu=relu, out_dtype=x.dtype)
    # grid order (n, m, k): for one n-block all m-tiles run consecutively,
    # so the scratch stat slices accumulate then emit once per n
    grid = (N // bn, M // bm, nk)
    y, ysum, yssq = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda n, m, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda n, m, k: (k, n)),
            pl.BlockSpec((1, bk), lambda n, m, k: (0, k)),
            pl.BlockSpec((1, bk), lambda n, m, k: (0, k)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda n, m, k: (m, n)),
            pl.BlockSpec((1, bn), lambda n, m, k: (0, n)),
            pl.BlockSpec((1, bn), lambda n, m, k: (0, n)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((2, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, w, s2, t2)
    return y, ysum.reshape(N), yssq.reshape(N)


def _dw_kernel(x_ref, dy_ref, y_ref, ds_ref, dq_ref, s_ref, t_ref, dw_ref,
               acc_ref, *, nm, apply_input, relu, mm_dtype):
    """dW[k, n] = sum_m xa[m, k] * dY[m, n] with the stat-cotangent
    correction dY = dy + dsum + 2*y*dssq formed in the prologue; xa is
    recomputed from the raw input when the forward had a prologue."""
    from jax.experimental import pallas as pl

    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dy = dy_ref[...].astype(jnp.float32) + ds_ref[...] \
        + 2.0 * y_ref[...].astype(jnp.float32) * dq_ref[...]
    x = x_ref[...]
    if apply_input:
        xf = x.astype(jnp.float32) * s_ref[...] + t_ref[...]
        if relu:
            xf = jnp.maximum(xf, 0.0)
        x = xf.astype(mm_dtype)
    acc_ref[...] += jax.lax.dot_general(
        x, dy.astype(mm_dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(m == nm - 1)
    def _finish():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _dx_kernel(dy_ref, y_ref, w_ref, ds_ref, dq_ref, x_ref, s_ref, t_ref,
               *refs, nn_, nm, bko, apply_input, relu, mm_dtype):
    """dx[m, k] = sum_n dY[m, n] * w[k, n]; when the forward had a
    prologue, the relu-mask * scale chain factor is applied on the way
    out and the per-channel dscale/dbias reductions accumulate in a
    scratch epilogue (so no standalone BN-backward kernels remain)."""
    from jax.experimental import pallas as pl

    if apply_input:
        dx_ref, dsc_ref, dbi_ref, acc_ref, stat_ref = refs
    else:
        dx_ref, acc_ref = refs
    n = pl.program_id(2)
    m = pl.program_id(1)
    ko = pl.program_id(0)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dy = dy_ref[...].astype(jnp.float32) + ds_ref[...] \
        + 2.0 * y_ref[...].astype(jnp.float32) * dq_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        dy.astype(mm_dtype), w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n == nn_ - 1)
    def _finish():
        dxa = acc_ref[...]
        if apply_input:
            xf = x_ref[...].astype(jnp.float32) * s_ref[...] + t_ref[...]
            if relu:
                dxa = jnp.where(xf > 0.0, dxa, 0.0)
            dx_ref[...] = (dxa * s_ref[...]).astype(dx_ref.dtype)
            # ko is outermost: one (2, bko) scratch serves each
            # ko-block's m-sweep (same flush-avoidance as forward)

            @pl.when(m == 0)
            def _zero():
                stat_ref[...] = jnp.zeros_like(stat_ref)

            stat_ref[0:1, :] += jnp.sum(
                dxa * x_ref[...].astype(jnp.float32), axis=0, keepdims=True)
            stat_ref[1:2, :] += jnp.sum(dxa, axis=0, keepdims=True)

            @pl.when(m == nm - 1)
            def _emit():
                dsc_ref[...] = stat_ref[0:1, :]
                dbi_ref[...] = stat_ref[1:2, :]
        else:
            dx_ref[...] = dxa.astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("relu", "interpret"))
def _fused_bwd_pallas(x, w, y, scale, bias, dy, dsum, dssq, relu=False,
                      interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    _, N = w.shape
    apply_input = scale is not None
    mm_dtype = x.dtype
    if apply_input:
        s2 = scale.astype(jnp.float32).reshape(1, K)
        t2 = bias.astype(jnp.float32).reshape(1, K)
    else:
        s2 = jnp.zeros((1, K), jnp.float32)
        t2 = jnp.zeros((1, K), jnp.float32)
    ds2 = dsum.astype(jnp.float32).reshape(1, N)
    dq2 = dssq.astype(jnp.float32).reshape(1, N)

    # --- dW: grid (ko, n, m), contraction over m innermost -------------
    bm, bko, bn = _pick_bwd_blocks(M, K, N, itemsize=x.dtype.itemsize)
    nm = M // bm
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, nm=nm, apply_input=apply_input,
                          relu=relu, mm_dtype=mm_dtype),
        grid=(K // bko, N // bn, nm),
        in_specs=[
            pl.BlockSpec((bm, bko), lambda ko, n, m: (m, ko)),   # x
            pl.BlockSpec((bm, bn), lambda ko, n, m: (m, n)),     # dy
            pl.BlockSpec((bm, bn), lambda ko, n, m: (m, n)),     # y
            pl.BlockSpec((1, bn), lambda ko, n, m: (0, n)),      # dsum
            pl.BlockSpec((1, bn), lambda ko, n, m: (0, n)),      # dssq
            pl.BlockSpec((1, bko), lambda ko, n, m: (0, ko)),    # scale
            pl.BlockSpec((1, bko), lambda ko, n, m: (0, ko)),    # bias
        ],
        out_specs=pl.BlockSpec((bko, bn), lambda ko, n, m: (ko, n)),
        out_shape=jax.ShapeDtypeStruct((K, N), w.dtype),
        scratch_shapes=[pltpu.VMEM((bko, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, dy, y, ds2, dq2, s2, t2)

    # --- dX (+ dscale/dbias epilogue): grid (ko, m, n) -----------------
    nn_ = N // bn
    nm_dx = M // bm
    out_specs = [pl.BlockSpec((bm, bko), lambda ko, m, n: (m, ko))]
    out_shape = [jax.ShapeDtypeStruct((M, K), x.dtype)]
    scratch = [pltpu.VMEM((bm, bko), jnp.float32)]
    if apply_input:
        out_specs += [pl.BlockSpec((1, bko), lambda ko, m, n: (0, ko)),
                      pl.BlockSpec((1, bko), lambda ko, m, n: (0, ko))]
        out_shape += [jax.ShapeDtypeStruct((1, K), jnp.float32),
                      jax.ShapeDtypeStruct((1, K), jnp.float32)]
        scratch.append(pltpu.VMEM((2, bko), jnp.float32))
    res = pl.pallas_call(
        functools.partial(_dx_kernel, nn_=nn_, nm=nm_dx, bko=bko,
                          apply_input=apply_input,
                          relu=relu, mm_dtype=mm_dtype),
        grid=(K // bko, nm_dx, nn_),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda ko, m, n: (m, n)),     # dy
            pl.BlockSpec((bm, bn), lambda ko, m, n: (m, n)),     # y
            pl.BlockSpec((bko, bn), lambda ko, m, n: (ko, n)),   # w
            pl.BlockSpec((1, bn), lambda ko, m, n: (0, n)),      # dsum
            pl.BlockSpec((1, bn), lambda ko, m, n: (0, n)),      # dssq
            pl.BlockSpec((bm, bko), lambda ko, m, n: (m, ko)),   # x
            pl.BlockSpec((1, bko), lambda ko, m, n: (0, ko)),    # scale
            pl.BlockSpec((1, bko), lambda ko, m, n: (0, ko)),    # bias
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(dy, y, w, ds2, dq2, x, s2, t2)
    if apply_input:
        dx, dsc, dbi = res
        return dx, dw, dsc.reshape(K), dbi.reshape(K)
    return res[0], dw, None, None


def _fused_fwd_reference(x, w, scale, bias, relu=False):
    """Pure-jnp reference (CPU tests + non-TPU fallback)."""
    acc = jnp.promote_types(x.dtype, jnp.float32)
    if scale is not None:
        xf = x.astype(acc) * scale.astype(acc).reshape(1, -1) \
            + bias.astype(acc).reshape(1, -1)
        if relu:
            xf = jnp.maximum(xf, 0.0)
        x = xf.astype(x.dtype)
    y = jnp.dot(x, w, preferred_element_type=acc)
    yf = y
    ysum = jnp.sum(yf, axis=0)
    yssq = jnp.sum(yf * yf, axis=0)
    return y.astype(x.dtype), ysum, yssq


def _fused_bwd_reference(x, w, y, scale, bias, dy, dsum, dssq, relu=False):
    """jnp mirror of the backward kernels (same casts, for parity tests
    and the non-TPU path)."""
    mm = x.dtype
    acc = jnp.promote_types(x.dtype, jnp.float32)
    dY = (dy.astype(acc) + dsum.astype(acc).reshape(1, -1)
          + 2.0 * y.astype(acc) * dssq.astype(acc).reshape(1, -1)).astype(mm)
    apply_input = scale is not None
    xa = x
    if apply_input:
        xf = x.astype(acc) * scale.astype(acc).reshape(1, -1) \
            + bias.astype(acc).reshape(1, -1)
        if relu:
            xf = jnp.maximum(xf, 0.0)
        xa = xf.astype(mm)
    dw = jax.lax.dot_general(xa, dY, (((0,), (0,)), ((), ())),
                             preferred_element_type=acc).astype(w.dtype)
    dxa = jax.lax.dot_general(dY, w, (((1,), (1,)), ((), ())),
                              preferred_element_type=acc)
    if not apply_input:
        return dxa.astype(x.dtype), dw, None, None
    if relu:
        dxa = jnp.where(xf > 0.0, dxa, 0.0)
    dx = (dxa * scale.astype(acc).reshape(1, -1)).astype(x.dtype)
    dsc = jnp.sum(dxa * x.astype(acc), axis=0)
    dbi = jnp.sum(dxa, axis=0)
    return dx, dw, dsc, dbi


def on_tpu():
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:  # noqa: BLE001
        return False


# ---------------------------------------------------------------------------
# public custom_vjp ops
# ---------------------------------------------------------------------------

@jax.custom_vjp
def matmul_stats(x, w):
    """(M, K) @ (K, N) with per-output-channel sum / sum-of-squares
    accumulated in the kernel epilogue. Returns (y, ysum, yssq)."""
    if on_tpu() and _blocks_ok(x.shape[0], w.shape[1], x.shape[1]):
        return _fused_fwd_pallas(x, w, None, None)
    return _fused_fwd_reference(x, w, None, None)


def _matmul_stats_fwd(x, w):
    out = matmul_stats(x, w)
    return out, (x, w, out[0])


def _matmul_stats_bwd(res, cts):
    x, w, y = res
    dy, dsum, dssq = cts
    if on_tpu() and _blocks_ok(x.shape[0], w.shape[1], x.shape[1]):
        dx, dw, _, _ = _fused_bwd_pallas(x, w, y, None, None, dy, dsum, dssq)
    else:
        dx, dw, _, _ = _fused_bwd_reference(x, w, y, None, None,
                                            dy, dsum, dssq)
    return dx, dw


matmul_stats.defvjp(_matmul_stats_fwd, _matmul_stats_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def scaled_matmul_stats(x, scale, bias, w, relu=True):
    """Normalize+shift (+relu) a RAW conv output on the fly, matmul it,
    and emit output stats — the prologue-chained form: the producer's
    BatchNorm never materialises its applied tensor."""
    if on_tpu() and _blocks_ok(x.shape[0], w.shape[1], x.shape[1]):
        return _fused_fwd_pallas(x, w, scale, bias, relu=relu)
    return _fused_fwd_reference(x, w, scale, bias, relu=relu)


def _scaled_matmul_stats_fwd(x, scale, bias, w, relu):
    out = scaled_matmul_stats(x, scale, bias, w, relu)
    return out, (x, scale, bias, w, out[0])


def _scaled_matmul_stats_bwd(relu, res, cts):
    x, scale, bias, w, y = res
    dy, dsum, dssq = cts
    if on_tpu() and _blocks_ok(x.shape[0], w.shape[1], x.shape[1]):
        dx, dw, dsc, dbi = _fused_bwd_pallas(x, w, y, scale, bias,
                                             dy, dsum, dssq, relu=relu)
    else:
        dx, dw, dsc, dbi = _fused_bwd_reference(x, w, y, scale, bias,
                                                dy, dsum, dssq, relu=relu)
    return dx, dsc.astype(scale.dtype), dbi.astype(bias.dtype), dw


scaled_matmul_stats.defvjp(_scaled_matmul_stats_fwd,
                           _scaled_matmul_stats_bwd)


# ---------------------------------------------------------------------------
# registry surface (tape-recordable; consumed by the gluon fusion pass)
# ---------------------------------------------------------------------------

from .registry import register  # noqa: E402


@register("_contrib_fused_matmul_stats")
def _op_matmul_stats(x, w):
    return matmul_stats(x, w)


@register("_contrib_fused_scaled_matmul_stats")
def _op_scaled_matmul_stats(x, scale, bias, w, relu=True):
    return scaled_matmul_stats(x, scale, bias, w, bool(relu))
