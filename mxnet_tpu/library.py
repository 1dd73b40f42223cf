"""Runtime-loaded native operator libraries (reference: ``src/lib_api.cc``
``MXLoadLib`` + ``python/mxnet/library.py`` ``mx.library.load``, 1.6+).

The reference dlopens a user ``.so`` whose ops were written against
``include/mxnet/lib_api.h`` and registers them like built-ins. The
TPU-native equivalent keeps the same developer story — compile a small C
library, ``mx.library.load("libmyop.so")``, call ``mx.nd.my_op(...)`` —
with a JAX-idiomatic execution path: the C compute function runs on the
host via ``jax.pure_callback``, so loaded ops compose with ``jit``/
``hybridize`` (XLA treats them as host custom-calls) while the
hot path stays on the TPU. Native-performance *device* kernels belong in
Pallas; this surface is for the reference's actual MXLoadLib use cases —
custom CPU ops, pre/post-processing, licensing-isolated vendor code.

C ABI the library must export (all arrays float32 row-major)::

    int  mxtpu_lib_num_ops(void);
    const char* mxtpu_lib_op_name(int op);
    int  mxtpu_lib_op_num_inputs(int op);
    //   out_shape has room for 8 dims; return ndim (or -1 on error)
    int  mxtpu_lib_op_infer_shape(int op, const long long** in_shapes,
                                  const int* in_ndims, int nin,
                                  long long* out_shape);
    //   write the result into out; return 0 on success
    int  mxtpu_lib_op_compute(int op, const float** inputs,
                              const long long** in_shapes,
                              const int* in_ndims, int nin,
                              float* out, const long long* out_shape,
                              int out_ndim);
"""

from __future__ import annotations

import ctypes
import os

import numpy as onp

from .base import MXNetError

_MAX_DIM = 8
_LOADED = {}


class _NativeOp:
    """One op slot of a loaded library: shape inference + host compute."""

    def __init__(self, lib, index, name, nin):
        self._lib = lib
        self._index = index
        self.name = name
        self.nin = nin

    def infer_shape(self, in_shapes):
        arrs = [onp.asarray(s, dtype=onp.longlong) for s in in_shapes]
        ptrs = (ctypes.POINTER(ctypes.c_longlong) * len(arrs))(
            *[a.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong))
              for a in arrs])
        ndims = (ctypes.c_int * len(arrs))(*[len(s) for s in in_shapes])
        out = (ctypes.c_longlong * _MAX_DIM)()
        ndim = self._lib.mxtpu_lib_op_infer_shape(
            self._index, ptrs, ndims, len(arrs), out)
        if ndim < 0 or ndim > _MAX_DIM:
            raise MXNetError(
                f"native op {self.name!r}: infer_shape failed ({ndim})")
        return tuple(int(out[i]) for i in range(ndim))

    def compute(self, *inputs, out_shape=None):
        arrs = [onp.ascontiguousarray(onp.asarray(a), dtype=onp.float32)
                for a in inputs]
        shapes = [onp.asarray(a.shape, dtype=onp.longlong) for a in arrs]
        if out_shape is None:
            out_shape = self.infer_shape([a.shape for a in arrs])
        out = onp.empty(out_shape, dtype=onp.float32)
        in_ptrs = (ctypes.POINTER(ctypes.c_float) * len(arrs))(
            *[a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
              for a in arrs])
        shape_ptrs = (ctypes.POINTER(ctypes.c_longlong) * len(arrs))(
            *[s.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong))
              for s in shapes])
        ndims = (ctypes.c_int * len(arrs))(*[a.ndim for a in arrs])
        out_shape_c = (ctypes.c_longlong * len(out_shape))(*out_shape)
        rc = self._lib.mxtpu_lib_op_compute(
            self._index, in_ptrs, shape_ptrs, ndims, len(arrs),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out_shape_c, len(out_shape))
        if rc != 0:
            raise MXNetError(f"native op {self.name!r}: compute rc={rc}")
        return out


def _make_registered_fn(native):
    import jax

    def fn(*arrays, **ignored_attrs):
        import jax.numpy as jnp

        out_shape = native.infer_shape([a.shape for a in arrays])
        if not any(isinstance(a, jax.core.Tracer) for a in arrays):
            # eager: call straight into the C library
            host = [onp.asarray(a, dtype=onp.float32) for a in arrays]
            return jnp.asarray(native.compute(*host, out_shape=out_shape))
        result = jax.ShapeDtypeStruct(out_shape, onp.float32)
        return jax.pure_callback(
            lambda *xs: native.compute(*xs, out_shape=out_shape), result,
            *[a.astype("float32") for a in arrays], vmap_method="sequential")

    fn.__name__ = native.name
    fn.__doc__ = (f"Native op {native.name!r} loaded via mx.library.load "
                  "(reference: MXLoadLib); host compute through "
                  "jax.pure_callback.")
    return fn


def load(path, verbose=True):
    """Load a native op library and register its ops (reference:
    ``library.py`` ``load`` → ``MXLoadLib``). Returns the op names
    registered; they appear under ``mx.nd.*`` / ``mx.sym.*`` immediately."""
    path = os.path.abspath(path)
    if not os.path.exists(path):
        raise MXNetError(f"library not found: {path}")
    lib = ctypes.CDLL(path)
    for sym in ("mxtpu_lib_num_ops", "mxtpu_lib_op_name",
                "mxtpu_lib_op_num_inputs", "mxtpu_lib_op_infer_shape",
                "mxtpu_lib_op_compute"):
        if not hasattr(lib, sym):
            raise MXNetError(f"{path}: missing required symbol {sym!r}")
    lib.mxtpu_lib_op_name.restype = ctypes.c_char_p

    import logging

    from . import ndarray as nd_pkg
    from . import symbol as sym_pkg
    from .ndarray import op as nd_op
    from .ops.registry import all_ops, get as get_opdef, register
    from .symbol import op as sym_op

    prior_owner = {n: p for p, ns in _LOADED.items() for n in ns}
    names = []
    for i in range(lib.mxtpu_lib_num_ops()):
        name = lib.mxtpu_lib_op_name(i).decode()
        nin = lib.mxtpu_lib_op_num_inputs(i)
        if name in all_ops() and prior_owner.get(name) != path:
            # the reference MXLoadLib logs when re-registering; overriding
            # a BUILT-IN with host compute is almost always a user error
            # (re-loading the SAME library is routine and stays silent)
            logging.getLogger(__name__).warning(
                "mx.library.load: op %r from %s overrides an existing "
                "registration (now host pure_callback compute)", name,
                os.path.basename(path))
        native = _NativeOp(lib, i, name, nin)
        # jit=False: pure_callback handles jit composition itself; the
        # registry-level jit cache would only add a trace layer
        register(name, jit=False)(_make_registered_fn(native))
        opdef = get_opdef(name)
        wrapped = nd_op._make_op(opdef)
        # the nd/sym namespaces re-exported op.* at import time; publish
        # post-load names on both (reference: stubs are regenerated after
        # MXLoadLib by re-running _init_op_module)
        setattr(nd_op, name, wrapped)
        setattr(nd_pkg, name, wrapped)
        sym_fn = sym_op._make_sym_op(opdef)
        setattr(sym_op, name, sym_fn)
        setattr(sym_pkg, name, sym_fn)
        names.append(name)
    _LOADED[path] = names
    if verbose:
        print(f"mx.library.load: registered {len(names)} ops from "
              f"{os.path.basename(path)}: {names}")
    return names
