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

**The decode kernel.** Each call is one invocation that walks the live
rows only, their count and order (live rows first) taken by scalar
prefetch. The stores and the rows stay in HBM: a group of live slots
(as many as :data:`_GROUP_BYTES` holds of what a slot brings in) is
copied in by explicit DMA, its windows or states from where they lie
in the store and its rows from the batch, while the group before it is
computed and the one before that goes back, the store entries to where
they came from (the store is aliased to the output). A dead row costs
one zero output row, copied from VMEM, and nothing is read of it or of
its slot.
"""

from __future__ import annotations

import functools
import math

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

def _order(slots, active):
    """The rows in the order the kernel walks them, the live ones first
    (in row order), each one's state slot, and how many are live."""
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    n_live = jnp.sum(active).astype(jnp.int32)
    return order, slots.astype(jnp.int32)[order], n_live


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


# VMEM one DMA group's store entries and rows take coming in (about as
# much again going out, and both twice over, double-buffered). More
# slots a group keep more copies in flight: on a v5e at the Jamba
# cell's widths (d_inner 5,120) and 108 live rows of 256, the two calls
# took 0.1443 ms at 256 KiB, 0.1339 at 1 MiB and 0.1270 at 4 MiB
_GROUP_BYTES = 4 << 20


def _group_slots(entry_bytes, rows):
    """Live slots in one DMA group: as many as the group's budget holds
    of what one slot brings in, at least one and at most the batch."""
    return int(max(1, min(rows, _GROUP_BYTES // entry_bytes)))


def _vmem_bytes(shape):
    """Bytes of a float32 array in VMEM: its lanes whole."""
    *lead, lanes = shape
    return 4 * math.prod(lead) * (-(-lanes // 128) * 128)


def _move(copy, wait):
    if wait:
        copy.wait()
    else:
        copy.start()


def _walk_kernel(meta_ref, order_ref, at_ref, *refs, step, n_const, n_in,
                 group):
    """One invocation walks the live rows (the first ``meta[1]`` of
    ``order``), ``group`` of them a DMA group, and nothing else. The
    store (``(layers, slots + 1, ...)``) and the rows stay in HBM. Row
    ``order[i]``'s inputs and its slot's entry ``(layer, at[i])`` are
    copied into position ``i % group`` of an input buffer, ``step``
    computes the slot's new entry and output row into the output
    buffers, and those go back by DMA, the entry where it came from.
    Double-buffered both ways: while one group is computed the next one
    is coming in and the one before it going out. Each dead row gets a
    zero output row, copied from VMEM while the live ones are walked;
    nothing of a dead row or its slot is read."""
    consts, refs = refs[:n_const], refs[n_const:]
    rows_in, (store, y_hbm, store_out), refs = \
        refs[:n_in], refs[n_in:n_in + 3], refs[n_in + 3:]
    bufs_in, (entry_buf, y_buf, zero, sems) = refs[:n_in + 1], refs[n_in + 1:]
    out_sem, zero_sem = n_in + 1, n_in + 3     # semaphore rows, one a stream
    layer, n_live = meta_ref[0], meta_ref[1]
    groups = pl.cdiv(n_live, group)

    def coming(i, g, slot):
        row = order_ref[i]
        return [(store.at[layer, at_ref[i]], bufs_in[0].at[slot, g])] + [
            (src.at[row], buf.at[slot, g])
            for src, buf in zip(rows_in, bufs_in[1:])]

    def going(i, g, slot):
        return [(entry_buf.at[slot, g], store_out.at[layer, at_ref[i]]),
                (y_buf.at[slot, g], y_hbm.at[order_ref[i]])]

    def each_slot(j, fn):
        """``fn(i, g)`` for the live rows of group ``j``."""
        def one(g, carry):
            fn(j * group + g, g)
            return carry
        jax.lax.fori_loop(0, jnp.minimum(group, n_live - j * group), one, 0)

    def copies(pairs, sem, j, slot, wait):
        def fn(i, g):
            for s, (src, dst) in enumerate(pairs(i, g, slot)):
                _move(pltpu.make_async_copy(src, dst, sems.at[sem + s, slot]),
                      wait)
        each_slot(j, fn)

    def dead(wait):
        def one(i, carry):
            _move(pltpu.make_async_copy(zero, y_hbm.at[order_ref[i]],
                                        sems.at[zero_sem, 0]), wait)
            return carry
        jax.lax.fori_loop(n_live, order_ref.shape[0], one, 0)

    @pl.when(groups > 0)
    def _prologue():
        copies(coming, 0, 0, 0, wait=False)

    zero[...] = jnp.zeros_like(zero)
    dead(wait=False)

    def walk(j, carry):
        slot = j % 2

        @pl.when(j + 1 < groups)
        def _prefetch():
            copies(coming, 0, j + 1, 1 - slot, wait=False)

        copies(coming, 0, j, slot, wait=True)

        @pl.when(j >= 2)  # group j - 2 has left the output buffers
        def _drained():
            copies(going, out_sem, j - 2, slot, wait=True)

        each_slot(j, lambda i, g: step(
            consts, [buf.at[slot, g] for buf in bufs_in],
            entry_buf.at[slot, g], y_buf.at[slot, g]))
        copies(going, out_sem, j, slot, wait=False)
        return carry

    jax.lax.fori_loop(0, groups, walk, 0)
    for back in (2, 1):

        @pl.when(groups >= back)
        def _epilogue(back=back):
            j = groups - back
            copies(going, out_sem, j, j % 2, wait=True)

    dead(wait=True)


def _walk(step, consts, rows_in, store, row, slots, active, layer,
          interpret):
    """The ``pallas_call`` of both calls: ``consts`` whole in VMEM,
    ``rows_in`` ``(B, ...)`` and the WHOLE ``store`` where they lie in
    HBM, the store aliased to the output; ``row`` the shape of an
    output row. The layer, the live count and the walk's order ride the
    scalar-prefetch lane. Returns the output rows and the store."""
    Bt = slots.shape[0]
    order, at, n_live = _order(slots, active)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), n_live])
    entry = store.shape[2:]
    entries_in = [entry] + [r.shape[1:] for r in rows_in]
    group = _group_slots(sum(_vmem_bytes(e) for e in entries_in), Bt)
    buffers = [(2, group) + e for e in entries_in + [entry, row]]
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    n_in = len(rows_in)
    # the buffers and the zero row, the constants whole, and the
    # margin of the other kernels for a slot's values
    vmem = sum(_vmem_bytes(b) for b in buffers + [row]) \
        + sum(_vmem_bytes(c.shape) for c in consts) + (8 << 20)
    y, store = pl.pallas_call(
        functools.partial(_walk_kernel, step=step, n_const=len(consts),
                          n_in=n_in, group=group),
        out_shape=(jax.ShapeDtypeStruct((Bt,) + row, _F32),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[whole] * len(consts) + [in_hbm] * (n_in + 1),
            out_specs=[in_hbm, in_hbm],
            scratch_shapes=[pltpu.VMEM(b, _F32) for b in buffers + [row]]
            + [pltpu.SemaphoreType.DMA((n_in + 4, 2))]),
        # operands count the three prefetched scalars first
        input_output_aliases={3 + len(consts) + n_in: 1},
        interpret=interpret,
        name="mxtpu_mamba_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=int(vmem)),
    )(meta, order, at, *consts, *rows_in, store)
    return y, store


def _conv_slot(consts, ins, win_out, y_ref):
    """A live slot's window call: the step's input joins the window,
    the convolution and its SiLU are the output, and the window moves on
    by one. Every ``d_inner`` vector here is a ``(d_inner / 128, 128)``
    tile, as the store lays out a window's inputs."""
    w_ref, b_ref = consts
    win, x_ref = ins
    K = w_ref.shape[0]
    x = x_ref[...]                                        # (Di/128, 128)
    acc = b_ref[...] + w_ref[K - 1] * x
    for k in range(K - 1):
        acc = acc + w_ref[k] * win[k]
    y_ref[...] = acc * jax.nn.sigmoid(acc)
    for k in range(K - 2):
        win_out[k] = win[k + 1]
    win_out[K - 2] = x


def _scan_slot(consts, ins, s_out, y_ref):
    """A live slot's state call: its ``(N, d_inner)`` state decays by
    ``exp(dt A)``, takes ``dt x`` along its lanes times ``B`` down its
    rows, is stored, and is read by ``C`` before it leaves the
    registers. ``xd`` holds the slot's ``xc`` and ``dt`` rows, ``bc``
    its ``B`` and ``C`` as columns."""
    a_ref, d_ref = consts
    s_ref, xd, bc = ins
    x = xd[0:1, :]                                        # (1, Di)
    dt = xd[1:2, :]
    b = bc[:, 0:1]                                        # (N, 1)
    c = bc[:, 1:2]
    s = jnp.exp(dt * a_ref[...]) * s_ref[...] + (dt * x) * b
    s_out[...] = s
    y_ref[...] = jnp.sum(c * s, axis=0, keepdims=True) + d_ref[...] * x


def _pallas_conv(x, window, w, b, slots, active, layer, interpret=False):
    """``window`` is the WHOLE store, rewritten in place: the kernel
    copies the live slots' windows of this layer in and out where they
    lie."""
    Bt, Di = x.shape
    K = w.shape[0]
    tile = window.shape[3:]                               # (Di/128, 128)
    xc, window = _walk(
        _conv_slot,
        [w.astype(_F32).reshape((K,) + tile), b.astype(_F32).reshape(tile)],
        [x.astype(_F32).reshape((Bt,) + tile)], window, tile, slots,
        active, layer, interpret)
    return xc.reshape(Bt, Di), window


def _pallas_scan(xc, dt, A, B, C, D, state, slots, active, layer,
                 interpret=False):
    """``state`` is the WHOLE store, rewritten in place as
    :func:`_pallas_conv` rewrites the windows."""
    Bt, Di = xc.shape
    xd = jnp.stack([xc.astype(_F32), dt.astype(_F32)], axis=1)  # (B, 2, Di)
    # B and C as a slot's first two lanes: a copy takes whole lane tiles
    bc = jnp.pad(jnp.stack([B.astype(_F32), C.astype(_F32)], axis=2),
                 ((0, 0), (0, 0), (0, 126)))                    # (B, N, 128)
    y, state = _walk(
        _scan_slot,
        [A.astype(_F32), D.astype(_F32).reshape(1, Di)], [xd, bc], state,
        (1, Di), slots, active, layer, interpret)
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

