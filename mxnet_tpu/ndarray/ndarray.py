"""NDArray: an imperative, mutable tensor handle over immutable ``jax.Array``.

Reference: ``src/ndarray/ndarray.cc`` + ``python/mxnet/ndarray/ndarray.py``
(symbols ``NDArray``, ``CopyFromTo``, ``WaitToRead``).

TPU-native design (SURVEY.md §7.1):

- An NDArray *handle* owns a current ``jax.Array`` plus a version counter;
  in-place ops rebind the buffer (XLA buffers are immutable — mutation is
  rebinding, donation happens inside fused jitted steps).
- Basic-slice views alias their base: a view holds ``(_base, _index)`` and
  resolves its data lazily from the base, so ``b = a[1:3]; b[:] = 0``
  mutates ``a`` and later mutations of ``a`` are visible through ``b`` —
  the reference's shared-memory view semantics without shared memory.
- Async semantics: JAX dispatch returns futures; ``wait_to_read`` /
  ``waitall`` are the sync points where deferred device errors surface
  (reference: exceptions stored on engine vars, rethrown at wait).
"""

from __future__ import annotations

import weakref

import numpy as _np

import jax
import jax.numpy as jnp

from .. import autograd
from ..base import MXNetError, is_int
from ..context import Context, current_context

_LIVE: "weakref.WeakSet[NDArray]" = weakref.WeakSet()

_BASIC_TYPES = (int, slice, type(Ellipsis), type(None))


def _is_basic_index(idx) -> bool:
    if isinstance(idx, tuple):
        return all(isinstance(i, _BASIC_TYPES) or is_int(i) for i in idx)
    return isinstance(idx, _BASIC_TYPES) or is_int(idx)


class NDArray:
    __slots__ = (
        "_data_",
        "_base",
        "_index",
        "_cached",
        "_cached_ver",
        "_version",
        "_ctx",
        "_ag",
        "_grad",
        "_grad_req",
        "__weakref__",
    )

    # higher than numpy's so ndarray.__add__(np, mx) defers to us
    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None, _base=None, _index=None):
        self._base = _base
        self._index = _index
        self._cached = None
        self._cached_ver = -1
        self._version = 0
        self._ag = None
        self._grad = None
        self._grad_req = "write"
        if _base is not None:
            self._data_ = None
            self._ctx = _base._ctx
        else:
            if not isinstance(data, jax.Array):
                data = jnp.asarray(data)
            self._data_ = data
            self._ctx = ctx if ctx is not None else current_context()
        _LIVE.add(self)

    # ------------------------------------------------------------------
    # buffer access / mutation
    # ------------------------------------------------------------------
    @property
    def data(self) -> jax.Array:
        if self._base is None:
            return self._data_
        base = self._base
        if self._cached is None or self._cached_ver != base._root_version():
            self._cached = base.data[self._index]
            self._cached_ver = base._root_version()
        return self._cached

    def _root_version(self) -> int:
        return self._version if self._base is None else self._base._root_version()

    def _set_data(self, new):
        """Rebind the buffer (in-place mutation). Views write through."""
        if self._base is not None:
            base = self._base
            base._set_data(base.data.at[self._index].set(new))
            self._cached = None
            return
        if not isinstance(new, jax.Array):
            new = jnp.asarray(new)
        self._data_ = new
        self._version += 1

    # ------------------------------------------------------------------
    # metadata
    # ------------------------------------------------------------------
    @property
    def shape(self) -> tuple:
        if self._base is None:
            return tuple(self._data_.shape)
        return tuple(jax.eval_shape(lambda b: b[self._index], self._base.data).shape)

    @property
    def dtype(self):
        return _np.dtype(self.data.dtype)

    @property
    def size(self) -> int:
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def ctx(self) -> Context:
        return self._ctx

    @property
    def context(self) -> Context:
        return self._ctx

    @property
    def stype(self) -> str:
        return "default"

    @property
    def T(self) -> "NDArray":
        from . import op as _op

        return _op.transpose(self)

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "The truth value of an NDArray with multiple elements is ambiguous."
            )
        return bool(self.asnumpy().reshape(())[()])

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------
    # host transfer / sync points
    # ------------------------------------------------------------------
    def asnumpy(self) -> _np.ndarray:
        return _np.asarray(self.data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __index__(self):
        return int(self.asscalar())

    def wait_to_read(self):
        from .. import engine

        engine.wait(self.data)

    def wait_to_write(self):
        from .. import engine

        engine.wait(self.data)

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # ------------------------------------------------------------------
    # copies / placement
    # ------------------------------------------------------------------
    def copy(self) -> "NDArray":
        return NDArray(self.data, ctx=self._ctx)

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._set_data(
                jax.device_put(self.data, other.ctx.jax_device).astype(other.dtype)
            )
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self.data, other.jax_device), ctx=other)
        raise TypeError(f"copyto does not support type {type(other)}")

    def as_in_context(self, context) -> "NDArray":
        if context == self._ctx:
            return self
        return self.copyto(Context(context))

    def as_in_ctx(self, context) -> "NDArray":
        return self.as_in_context(context)

    def astype(self, dtype, copy=True) -> "NDArray":
        if not copy and _np.dtype(dtype) == self.dtype:
            return self
        from . import op as _op

        return _op.cast(self, dtype=_np.dtype(dtype).name)

    def tostype(self, stype):
        if stype == "default":
            return self
        from .sparse import cast_storage

        return cast_storage(self, stype)

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        self._grad = (
            NDArray(jnp.zeros(self.shape, self.data.dtype), ctx=self._ctx)
            if grad_req != "null"
            else None
        )
        self._grad_req = grad_req

    @property
    def grad(self):
        return self._grad

    def detach(self) -> "NDArray":
        out = NDArray(self.data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward(
            [self],
            [out_grad] if out_grad is not None else None,
            retain_graph=retain_graph,
            train_mode=train_mode,
        )

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def __getitem__(self, idx):
        if isinstance(idx, NDArray):
            # int32 gather indices wrap silently past 2^31; keep int64
            # when x64 is live (the documented large-tensor posture)
            idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
            return NDArray(jnp.take(self.data, idx.data.astype(idt), axis=0),
                           ctx=self._ctx)
        if _is_basic_index(idx):
            if autograd.is_recording() and autograd.is_tracked(self):
                from ..ops.dispatch import invoke

                return invoke("_slice_basic", self, index=_freeze_index(idx))
            return NDArray(None, _base=self, _index=idx)
        # advanced indexing -> functional copy (numpy semantics)
        if isinstance(idx, (list, _np.ndarray)):
            idx = jnp.asarray(idx)
        return NDArray(self.data[idx], ctx=self._ctx)

    def __setitem__(self, idx, value):
        if isinstance(idx, NDArray):
            idx = idx.data
        if isinstance(idx, tuple):
            idx = tuple(i.data if isinstance(i, NDArray) else i for i in idx)
        # without x64, scatter into a >2^31-element array picks int64
        # indices that JAX then truncates to int32 and SILENTLY DROPS
        # the update — turn the footgun into an error (see
        # docs/design_decisions.md "Large-tensor support")
        if self.size > 2**31 - 1:
            import jax as _jax

            if not _jax.config.jax_enable_x64:
                raise MXNetError(
                    f"in-place update on a {self.size}-element array "
                    "requires int64 scatter indices: enable "
                    "jax_enable_x64 (INT64_TENSOR_SIZE feature)")
        val_nd = value if isinstance(value, NDArray) else None
        v = val_nd if val_nd is not None else value
        if isinstance(v, (list, tuple, _np.ndarray)):
            v = jnp.asarray(v, self.data.dtype)

        def assign(base, vv):
            vv2 = vv.astype(base.dtype) if hasattr(vv, "astype") else vv
            return base.at[idx].set(vv2)

        # recorded slice-assign (reference: the `_slice_assign` op has
        # FGradient): gradients flow into the assigned value and are
        # zeroed through the overwritten base positions
        autograd.record_inplace(
            self, assign, (v,), "_slice_assign",
            tracked_extra=(val_nd,) if val_nd is not None else ())

    # ------------------------------------------------------------------
    # operators (delegate to the op registry; methods attached in register.py)
    # ------------------------------------------------------------------
    def _binop(self, name, other, reverse=False):
        from ..ops.dispatch import invoke

        if isinstance(other, _np.ndarray):
            other = NDArray(jnp.asarray(other), ctx=self._ctx)
        a, b = (other, self) if reverse else (self, other)
        return invoke(name, a, b)

    def __add__(self, o):
        return self._binop("broadcast_add", o)

    def __radd__(self, o):
        return self._binop("broadcast_add", o, True)

    def __sub__(self, o):
        return self._binop("broadcast_sub", o)

    def __rsub__(self, o):
        return self._binop("broadcast_sub", o, True)

    def __mul__(self, o):
        return self._binop("broadcast_mul", o)

    def __rmul__(self, o):
        return self._binop("broadcast_mul", o, True)

    def __truediv__(self, o):
        return self._binop("broadcast_div", o)

    def __rtruediv__(self, o):
        return self._binop("broadcast_div", o, True)

    def __mod__(self, o):
        return self._binop("broadcast_mod", o)

    def __rmod__(self, o):
        return self._binop("broadcast_mod", o, True)

    def __pow__(self, o):
        return self._binop("broadcast_power", o)

    def __rpow__(self, o):
        return self._binop("broadcast_power", o, True)

    def __matmul__(self, o):
        return self._binop("matmul", o)

    def __neg__(self):
        return self._binop("broadcast_mul", -1.0)

    def __abs__(self):
        from ..ops.dispatch import invoke

        return invoke("abs", self)

    def __eq__(self, o):
        return self._binop("broadcast_equal", o)

    def __ne__(self, o):
        return self._binop("broadcast_not_equal", o)

    def __lt__(self, o):
        return self._binop("broadcast_lesser", o)

    def __le__(self, o):
        return self._binop("broadcast_lesser_equal", o)

    def __gt__(self, o):
        return self._binop("broadcast_greater", o)

    def __ge__(self, o):
        return self._binop("broadcast_greater_equal", o)

    def __hash__(self):
        return id(self)

    # in-place: rebind
    def _iop(self, name, other):
        res = self._binop(name, other)
        self._set_data(res.data)
        return self

    def __iadd__(self, o):
        return self._iop("broadcast_add", o)

    def __isub__(self, o):
        return self._iop("broadcast_sub", o)

    def __imul__(self, o):
        return self._iop("broadcast_mul", o)

    def __itruediv__(self, o):
        return self._iop("broadcast_div", o)

    def __repr__(self):
        return f"\n{self.asnumpy()}\n<NDArray {'x'.join(str(d) for d in self.shape)} @{self._ctx}>"

    def __str__(self):
        return self.__repr__()

    # pickling / save support
    def __reduce__(self):
        return (_rebuild, (self.asnumpy(), self._ctx.device_type, self._ctx.device_id))


def _rebuild(arr, devtype, devid):
    return NDArray(jnp.asarray(arr), ctx=Context(devtype, devid))


def _freeze_index(idx):
    """Make a basic index hashable for use as a static jit attr."""

    def f(i):
        if isinstance(i, slice):
            return ("slice", i.start, i.stop, i.step)
        if i is Ellipsis:
            return ("ellipsis",)
        if i is None:
            return ("newaxis",)
        return ("int", int(i))

    if isinstance(idx, tuple):
        return ("tuple",) + tuple(f(i) for i in idx)
    return f(idx)


def _thaw_index(fi):
    def t(e):
        if e[0] == "slice":
            return slice(e[1], e[2], e[3])
        if e[0] == "ellipsis":
            return Ellipsis
        if e[0] == "newaxis":
            return None
        return e[1]

    if fi[0] == "tuple":
        return tuple(t(e) for e in fi[1:])
    return t(fi)


def _wrap_result(res, ctx, out=None):
    """Wrap raw jax output(s) into NDArray(s), honoring ``out=``."""
    if ctx is None:
        ctx = current_context()
    if isinstance(res, (tuple, list)):
        if out is not None:
            outs = out if isinstance(out, (tuple, list)) else [out]
            for o, r in zip(outs, res):
                o._set_data(r)
            return list(outs)
        return [NDArray(r, ctx=ctx) for r in res]
    if out is not None:
        if isinstance(out, (tuple, list)):
            out = out[0]
        out._set_data(res)
        return out
    return NDArray(res, ctx=ctx)


# --------------------------------------------------------------------------
# creation
# --------------------------------------------------------------------------


def _place(raw, ctx):
    ctx = Context(ctx) if ctx is not None else current_context()
    return NDArray(jax.device_put(raw, ctx.jax_device), ctx=ctx)


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        raw = source_array.data
    else:
        raw = jnp.asarray(
            source_array,
            dtype=dtype
            if dtype is not None
            else (None if hasattr(source_array, "dtype") else jnp.float32),
        )
    if dtype is not None:
        raw = raw.astype(dtype)
    elif raw.dtype == jnp.float64:
        raw = raw.astype(jnp.float32)
    return _place(raw, ctx)


def empty(shape, ctx=None, dtype="float32"):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype="float32", **kw):
    return _place(jnp.zeros(shape, dtype or "float32"), ctx)


def ones(shape, ctx=None, dtype="float32", **kw):
    return _place(jnp.ones(shape, dtype or "float32"), ctx)


def full(shape, val, ctx=None, dtype="float32", **kw):
    return _place(jnp.full(shape, val, dtype or "float32"), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    r = jnp.arange(start, stop, step, dtype=dtype or "float32")
    if repeat != 1:
        r = jnp.repeat(r, repeat)
    return _place(r, ctx)


def eye(N, M=0, k=0, ctx=None, dtype="float32"):
    return _place(jnp.eye(N, M if M > 0 else None, k, dtype=dtype or "float32"), ctx)


def linspace(start, stop, num, endpoint=True, ctx=None, dtype="float32"):
    return _place(jnp.linspace(start, stop, num, endpoint=endpoint, dtype=dtype), ctx)


def zeros_like(a, **kw):
    return NDArray(jnp.zeros_like(a.data), ctx=a.ctx)


def ones_like(a, **kw):
    return NDArray(jnp.ones_like(a.data), ctx=a.ctx)


def waitall():
    """Block until all live arrays are computed; re-raise deferred errors.

    Reference: ``MXNDArrayWaitAll`` — the global sync point where async
    engine exceptions surface (SURVEY.md §5.3).
    """
    from .. import engine

    live = [arr._data_ for arr in list(_LIVE)
            if arr._base is None and arr._data_ is not None]
    try:
        # one batched sync for ALL live arrays
        engine.wait(live)
        return
    except Exception:
        pass
    errs = []
    for data in live:  # re-sync per array to attribute the failure
        try:
            engine.wait(data)
        except Exception as e:
            errs.append(e)
    if errs:
        raise MXNetError(str(errs[0])) from errs[0]


def save(fname, data):
    """Save NDArrays in the reference binary format (``NDArray::Save``,
    magic ``NDARRAY_V2`` inside the 0x112 list container) — the declared
    compatibility boundary: files interchange with reference MXNet's
    ``mx.nd.save``. Sparse arrays fall back to the ``.npz`` container
    (binary sparse blobs are a documented drop; ``load`` sniffs both)."""
    import numpy as np

    from . import serialization

    if isinstance(data, NDArray):
        arrays, names = [data], []
    elif isinstance(data, (list, tuple)):
        arrays, names = list(data), []
    elif isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    else:
        raise TypeError(f"cannot save type {type(data)}")
    if all(type(a) is NDArray for a in arrays):
        raws = [a.asnumpy() for a in arrays]
        try:  # every dtype must be expressible as an NDARRAY_V2 flag
            for r in raws:
                serialization._flag_from_np(r.dtype)
            serializable = True
        except MXNetError:
            serializable = False  # e.g. bool masks -> npz fallback below
        if serializable:
            serialization.save_params(fname, raws, names)
            return
    payload = ({f"__mxtpu_list_{i}": d.asnumpy() for i, d in enumerate(arrays)}
               if not names else
               {k: v.asnumpy() for k, v in zip(names, arrays)})
    with open(fname, "wb") as f:  # exact fname (np.savez would append .npz)
        np.savez(f, **payload)


def load(fname):
    import numpy as np

    from . import serialization

    if serialization.sniff_format(fname) == "ndarray_v2":
        arrays, names = serialization.load_params(fname)
        if names:
            return {n: array(a) for n, a in zip(names, arrays)}
        return [array(a) for a in arrays]
    with np.load(fname, allow_pickle=False) as z:
        keys = list(z.keys())
        if keys and all(k.startswith("__mxtpu_list_") for k in keys):
            keys.sort(key=lambda k: int(k.rsplit("_", 1)[1]))
            return [array(z[k]) for k in keys]
        return {k: array(z[k]) for k in keys}


def concatenate(arrays, axis=0, always_copy=True):
    return NDArray(jnp.concatenate([a.data for a in arrays], axis=axis),
                   ctx=arrays[0].ctx)


def imdecode(buf, **kw):  # implemented in mxnet_tpu.image
    from ..image import imdecode as _imdecode

    return _imdecode(buf, **kw)
