"""The selective state-space layer of Mamba-1 (Gu and Dao, "Mamba:
Linear-Time Sequence Modeling with Selective State Spaces",
arXiv:2312.00752), as Jamba interleaves it with attention: a causal
depthwise convolution, then a diagonal recurrence whose input and
output maps ``B_t``, ``C_t`` and step ``dt_t`` are read from the token.

For a sequence, with ``d_inner`` channels, ``N`` state rows and a
convolution of ``K`` taps (``x`` is the in-projection's first half)::

    xc_t = silu(sum_k w_k x_{t - K + 1 + k} + b)          (d_inner)
    s_t  = exp(dt_t A) s_{t-1} + (dt_t xc_t) B_t^T        (N x d_inner)
    y_t  = C_t s_t + D xc_t                               (d_inner)

with ``A = -exp(A_log)`` ``(N, d_inner)`` and ``dt``, ``B``, ``C`` made
from ``xc`` by the layer (:mod:`mxnet_tpu.serving.decoder`). What a
sequence keeps is fixed: ``s`` and the last ``K - 1`` inputs of the
convolution.

**Layout.** Both are kept with ``d_inner`` minor, float32: a store of
states is ``(layers, slots + 1, N, d_inner)`` and a store of windows
``(layers, slots + 1, K - 1, d_inner / 128, 128)``; the last slot is
the null slot. With ``N`` = 16 on the lanes a state would fill 16 of
128 lanes (eight times its bytes); on the rows it fills two whole
``(8, 128)`` tiles. A window's ``K - 1`` = 3 rows of ``d_inner`` would
take a tile's 8 rows, and the device keeps such an array with its slots
on the rows instead (which the kernel cannot take a slot of, so every
call would copy the store twice); each of its inputs is therefore laid
out as ``(d_inner / 128, 128)``, whole tiles where ``d_inner`` is a
multiple of 1,024: at Jamba2-3B's widths nothing is padded.

Three forms of the same mathematics, pinned against each other and
against the benchmark's sequential reference by ``tests/test_mamba.py``:

- :func:`causal_conv` and :func:`selective_scan`: prefill, one padded
  prompt. The convolution starts from the slot's window; the scan is a
  ``lax.scan`` over chunks, inside a chunk the closed form with the
  cumulative sums of ``dt A``. Positions at or past ``length`` leave
  the state alone, and the window is left at ``length``.
- :func:`conv_step` and :func:`scan_step`: one token a slot against the
  stores, in place. The projection to ``dt``, ``B`` and ``C`` runs
  between them over the whole batch (on the matrix unit), so the step
  is two calls. On a TPU each is the Pallas kernel
  ``mxtpu_mamba_decode``; on the CPU the same mathematics in
  ``jax.numpy``, which is also the kernel's oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32


def _lanes(d_inner):
    return 128 if d_inner % 128 == 0 else d_inner


def state_shapes(layers, slots, d_inner, d_state, d_conv):
    """Shapes of a store of ``slots`` states and one null slot: the
    selective state, then the convolution's window."""
    lanes = _lanes(d_inner)
    return ((layers, slots + 1, d_state, d_inner),
            (layers, slots + 1, d_conv - 1, d_inner // lanes, lanes))


def _use_pallas(d_inner):
    """Kernel path: channels in whole 128-lane rows, on any backend but
    the CPU. A backend that fails to start raises here."""
    return d_inner % 128 == 0 and jax.default_backend() != "cpu"


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def causal_conv(x, window, w, b, length):
    """The depthwise causal convolution over one padded prompt. ``x``
    ``(T, d_inner)``, ``window`` ``(K - 1, ...)`` float32 as a slot of
    the store holds it (the inputs before the prompt, oldest first:
    zeros for a new sequence), ``w`` ``(K, d_inner)``, ``b``
    ``(d_inner,)``. Returns the convolution before its activation, ``(T,
    d_inner)`` float32, and the window after ``length`` tokens, as the
    store holds it: padding does not move it."""
    K = w.shape[0]
    T, Di = x.shape
    xx = jnp.concatenate([window.reshape(K - 1, Di).astype(_F32),
                          x.astype(_F32)], axis=0)
    w = w.astype(_F32)
    y = b.astype(_F32) + sum(w[k] * xx[k:k + T] for k in range(K))
    left = jax.lax.dynamic_slice_in_dim(xx, length, K - 1, axis=0)
    return y, left.reshape(window.shape)


def selective_scan(x, dt, A, B, C, D, state, length, chunk):
    """The recurrence over one padded prompt. ``x`` and ``dt``
    ``(T, d_inner)``, ``A`` ``(N, d_inner)``, ``B`` and ``C`` ``(T,
    N)``, ``D`` ``(d_inner,)``, ``state`` ``(N, d_inner)`` entering;
    all taken in float32. Returns ``(y (T, d_inner), state after
    length tokens)``: a padded position has ``dt = 0``, which neither
    decays the state nor adds to it.

    A ``lax.scan`` over chunks of ``chunk`` positions carries the
    state; inside a chunk, with ``L_t`` the cumulative sum of ``dt A``
    from the chunk's start, ``s_t = exp(L_t) (s_0 + sum_{j <= t}
    exp(-L_j) dt_j x_j B_j^T)``: one pass of cumulative sums, no loop
    over positions. ``exp(-L_j)`` grows across a chunk, so its summed
    decay has to stay inside float32's range (``|sum dt A| < 88``: a
    mean factor ``exp(dt A)`` above ``exp(-88 / chunk)`` a position)."""
    T, Di = x.shape
    Q = int(min(chunk, T))
    pad = -T % Q
    valid = jnp.arange(T + pad, dtype=jnp.int32) < length
    x, dt, B, C = (jnp.pad(a.astype(_F32), ((0, pad), (0, 0)))
                   for a in (x, dt, B, C))
    dt = jnp.where(valid[:, None], dt, 0.0)
    n = (T + pad) // Q
    A = A.astype(_F32)
    D = D.astype(_F32)

    def one(s, xs):
        xq, dq, bq, cq = xs                       # (Q, Di) (Q, Di) (Q, N)
        # L_t = A * (the steps' running sum): A is a constant a channel
        run = A[None] * jnp.cumsum(dq, axis=0)[:, None, :]   # (Q, N, Di)
        u = (dq * xq)[:, None, :] * bq[:, :, None]
        into = jnp.cumsum(jnp.exp(-run) * u, axis=0)
        # the states after each position are only read by C: summed over
        # the state's rows where they are made, the last one kept
        y = jnp.sum(cq[:, :, None] * jnp.exp(run) * (s[None] + into),
                    axis=1) + D * xq
        return jnp.exp(run[-1]) * (s + into[-1]), y

    state, y = jax.lax.scan(
        one, state.astype(_F32),
        tuple(a.reshape(n, Q, -1) for a in (x, dt, B, C)))
    return y.reshape(n * Q, Di)[:T], state


# ---------------------------------------------------------------------------
# decode: one token a slot against the stores
# ---------------------------------------------------------------------------

def _order(slots, active, null):
    """The live rows first (in slot order) and each grid step's state:
    a step past the last live one keeps the last live step's state, so
    nothing of a row that is not live is fetched or written back."""
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    n_live = jnp.sum(active).astype(jnp.int32)
    slots = slots.astype(jnp.int32)
    last = jnp.where(n_live > 0, slots[order[jnp.maximum(n_live - 1, 0)]],
                     null)
    step = jnp.arange(slots.shape[0], dtype=jnp.int32)
    at = jnp.where(step < n_live, slots[order], last)
    return order, at, n_live


def _jnp_conv(x, window, w, b, slots, active, layer):
    """CPU path and oracle: gather each slot's window, convolve, scatter
    the window back. Slots that are not live read and write the null
    slot."""
    Bt, Di = x.shape
    K = w.shape[0]
    null = window.shape[1] - 1
    at = jnp.where(active, slots, null)
    xx = jnp.concatenate([window[layer, at].reshape(Bt, K - 1, Di),
                          x.astype(_F32)[:, None]], axis=1)  # (B, K, Di)
    w = w.astype(_F32)
    y = b.astype(_F32) + jnp.einsum("bkd,kd->bd", xx, w,
                                    precision=jax.lax.Precision.HIGHEST)
    xc = jnp.where(active[:, None], jax.nn.silu(y), 0.0)
    return xc, window.at[layer, at].set(
        xx[:, 1:].reshape((Bt,) + window.shape[2:]))


def _jnp_scan(xc, dt, A, B, C, D, state, slots, active, layer):
    """CPU path and oracle: gather each slot's state, step it, scatter
    it back."""
    null = state.shape[1] - 1
    at = jnp.where(active, slots, null)
    dt = dt.astype(_F32)
    s = jnp.exp(dt[:, None] * A.astype(_F32)) * state[layer, at] \
        + (dt * xc)[:, None] * B.astype(_F32)[:, :, None]
    y = jnp.einsum("bn,bnd->bd", C.astype(_F32), s,
                   precision=jax.lax.Precision.HIGHEST) \
        + D.astype(_F32) * xc
    return jnp.where(active[:, None], y, 0.0), state.at[layer, at].set(s)


def _conv_kernel(meta_ref, at_ref, row_ref, x_ref, w_ref, b_ref, win_ref,
                 y_ref, win_out):
    """One grid step a live slot (``at_ref`` its window, ``row_ref`` its
    row of the batch): the step's input joins the window, the
    convolution and its SiLU are the output, and the window moves on by
    one. Every ``d_inner`` vector here is a ``(d_inner / 128, 128)``
    tile, as the store lays out a window's inputs. A step past the last
    live slot writes a zero row and nothing else."""
    del at_ref, row_ref  # the index maps' own
    live = pl.program_id(0) < meta_ref[1]
    K = w_ref.shape[0]

    @pl.when(jnp.logical_not(live))
    def _dead():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _step():
        x = x_ref[0]                                      # (Di/128, 128)
        acc = b_ref[...] + w_ref[K - 1] * x
        for k in range(K - 1):
            acc = acc + w_ref[k] * win_ref[0, 0, k]
        y_ref[0] = acc * jax.nn.sigmoid(acc)
        for k in range(K - 2):
            win_out[0, 0, k] = win_ref[0, 0, k + 1]
        win_out[0, 0, K - 2] = x


def _scan_kernel(meta_ref, at_ref, row_ref, xd_ref, bc_ref, a_ref, d_ref,
                 s_ref, y_ref, s_out):
    """One grid step a live slot: its ``(N, d_inner)`` state decays by
    ``exp(dt A)``, takes ``dt x`` along its lanes times ``B`` down its
    rows, is stored, and is read by ``C`` before it leaves the
    registers. ``xd_ref`` holds the slot's ``xc`` and ``dt`` rows,
    ``bc_ref`` its ``B`` and ``C`` as columns."""
    del at_ref, row_ref
    live = pl.program_id(0) < meta_ref[1]

    @pl.when(jnp.logical_not(live))
    def _dead():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _step():
        x = xd_ref[0, 0:1, :]                             # (1, Di)
        dt = xd_ref[0, 1:2, :]
        b = bc_ref[0, :, 0:1]                             # (N, 1)
        c = bc_ref[0, :, 1:2]
        s = jnp.exp(dt * a_ref[...]) * s_ref[0, 0] + (dt * x) * b
        s_out[0, 0] = s
        y_ref[0] = jnp.sum(c * s, axis=0, keepdims=True) + d_ref[...] * x


def _compiler(vmem):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=int(vmem))


def _pallas_conv(x, window, w, b, slots, active, layer, interpret=False):
    """``window`` is the WHOLE store; the layer and each step's window
    ride the scalar-prefetch lane into the index maps, and the store is
    aliased to the output: the kernel rewrites the live slots' windows
    of this layer where they lie."""
    Bt, Di = x.shape
    K = w.shape[0]
    tile = window.shape[3:]                               # (Di/128, 128)
    order, at, n_live = _order(slots, active, window.shape[1] - 1)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), n_live])
    row = pl.BlockSpec((1,) + tile, lambda i, meta, at, order: (order[i], 0, 0))
    whole = pl.BlockSpec((K,) + tile, lambda i, meta, at, order: (0, 0, 0))
    bias = pl.BlockSpec(tile, lambda i, meta, at, order: (0, 0))
    win = pl.BlockSpec((1, 1, K - 1) + tile,
                       lambda i, meta, at, order: (meta[0], at[i], 0, 0, 0))
    xc, window = pl.pallas_call(
        _conv_kernel,
        out_shape=(jax.ShapeDtypeStruct((Bt,) + tile, _F32),
                   jax.ShapeDtypeStruct(window.shape, window.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(Bt,),
            in_specs=[row, whole, bias, win], out_specs=[row, win]),
        # operands count the three prefetched scalars first
        input_output_aliases={6: 1},
        interpret=interpret,
        name="mxtpu_mamba_decode",
        compiler_params=_compiler(16 << 20),
    )(meta, at, order, x.astype(_F32).reshape((Bt,) + tile),
      w.astype(_F32).reshape((K,) + tile), b.astype(_F32).reshape(tile),
      window)
    return xc.reshape(Bt, Di), window


def _pallas_scan(xc, dt, A, B, C, D, state, slots, active, layer,
                 interpret=False):
    """``state`` is the WHOLE store, rewritten in place as
    :func:`_pallas_conv` rewrites the windows."""
    Bt, Di = xc.shape
    N = A.shape[0]
    order, at, n_live = _order(slots, active, state.shape[1] - 1)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), n_live])
    xd = jnp.stack([xc.astype(_F32), dt.astype(_F32)], axis=1)  # (B, 2, Di)
    bc = jnp.stack([B.astype(_F32), C.astype(_F32)], axis=2)    # (B, N, 2)
    s_spec = pl.BlockSpec((1, 1, N, Di),
                          lambda i, meta, at, order: (meta[0], at[i], 0, 0))
    y, state = pl.pallas_call(
        _scan_kernel,
        out_shape=(jax.ShapeDtypeStruct((Bt, 1, Di), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(Bt,),
            in_specs=[
                pl.BlockSpec((1, 2, Di),
                             lambda i, meta, at, order: (order[i], 0, 0)),
                pl.BlockSpec((1, N, 2),
                             lambda i, meta, at, order: (order[i], 0, 0)),
                pl.BlockSpec((N, Di), lambda i, meta, at, order: (0, 0)),
                pl.BlockSpec((1, Di), lambda i, meta, at, order: (0, 0)),
                s_spec],
            out_specs=[
                pl.BlockSpec((1, 1, Di),
                             lambda i, meta, at, order: (order[i], 0, 0)),
                s_spec]),
        input_output_aliases={7: 1},
        interpret=interpret,
        name="mxtpu_mamba_decode",
        # the state's block in and out, each double-buffered, A beside
        # them, and the step's (N, d_inner) temporaries
        compiler_params=_compiler(10 * N * Di * 4 + (8 << 20)),
    )(meta, at, order, xd, bc, A.astype(_F32), D.astype(_F32).reshape(1, Di),
      state)
    return y.reshape(Bt, Di), state


def conv_step(x, window, w, b, slots, active, layer=0):
    """The convolution of one token a slot. ``x`` ``(B, d_inner)``,
    ``window`` the whole store of windows (:func:`state_shapes`),
    ``slots`` ``(B,)`` int32 each row's state, ``active`` ``(B,)`` which
    rows are live, ``layer`` an int or a traced scalar. Returns ``(xc
    (B, d_inner) float32 after the SiLU, window)``: the windows of live
    rows move on in place, the others are neither read nor written and
    their outputs are zero."""
    step = _pallas_conv if _use_pallas(x.shape[-1]) else _jnp_conv
    return step(x, window, w, b, slots, active, layer)


def scan_step(xc, dt, A, B, C, D, state, slots, active, layer=0):
    """The recurrence of one token a slot: ``xc`` and ``dt`` ``(B,
    d_inner)``, ``A`` ``(N, d_inner)``, ``B`` and ``C`` ``(B, N)``, ``D``
    ``(d_inner,)``, ``state`` the whole store. Returns ``(y (B, d_inner)
    float32, state)``, the states of live rows stepped in place."""
    step = _pallas_scan if _use_pallas(xc.shape[-1]) else _jnp_scan
    return step(xc, dt, A, B, C, D, state, slots, active, layer)

