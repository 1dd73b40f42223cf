"""Testing utilities (reference: ``python/mxnet/test_utils.py``)."""

from __future__ import annotations

import functools
import random as _pyrandom

import numpy as _np

from . import autograd
from .context import Context, cpu, current_context
from .ndarray.ndarray import NDArray, array as _array

_DEFAULT_CTX = [None]


def default_context():
    return _DEFAULT_CTX[0] or current_context()


def set_default_context(ctx):
    _DEFAULT_CTX[0] = ctx


_DTYPE_TOL = {
    _np.dtype(_np.float16): (1e-2, 1e-2),
    _np.dtype("bfloat16") if hasattr(_np, "dtype") else None: None,
    _np.dtype(_np.float32): (1e-4, 1e-5),
    _np.dtype(_np.float64): (1e-6, 1e-8),
}


def _as_np(x):
    if isinstance(x, NDArray):
        return x.asnumpy()
    return _np.asarray(x)


def get_tolerance(arr, rtol=None, atol=None):
    d = _np.dtype(getattr(arr, "dtype", _np.float32))
    base = _DTYPE_TOL.get(d, (1e-4, 1e-5))
    return (rtol if rtol is not None else base[0],
            atol if atol is not None else base[1])


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Per-dtype tolerance comparison (reference: ``assert_almost_equal``)."""
    a_np, b_np = _as_np(a), _as_np(b)
    rtol, atol = get_tolerance(a_np, rtol, atol)
    _np.testing.assert_allclose(
        a_np.astype(_np.float64), b_np.astype(_np.float64),
        rtol=rtol, atol=atol, equal_nan=equal_nan,
        err_msg=f"{names[0]} vs {names[1]} mismatch")


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    try:
        assert_almost_equal(a, b, rtol, atol, equal_nan=equal_nan)
        return True
    except AssertionError:
        return False


def same(a, b):
    return _np.array_equal(_as_np(a), _as_np(b))


def rand_shape_nd(ndim, dim=10):
    return tuple(_np.random.randint(1, dim + 1, size=ndim))


def rand_shape_2d(dim0=10, dim1=10):
    return (_np.random.randint(1, dim0 + 1), _np.random.randint(1, dim1 + 1))

def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (_np.random.randint(1, dim0 + 1), _np.random.randint(1, dim1 + 1),
            _np.random.randint(1, dim2 + 1))


def rand_ndarray(shape, stype="default", density=None, dtype="float32",
                 ctx=None, scale=1.0):
    if stype == "default":
        return _array(_np.random.uniform(-scale, scale, size=shape).astype(dtype),
                      ctx=ctx or default_context())
    from .ndarray import sparse

    density = 0.1 if density is None else density
    arr = _np.random.uniform(-scale, scale, size=shape).astype(dtype)
    mask = _np.random.rand(shape[0]) < density
    arr[~mask] = 0
    dense = _array(arr, ctx=ctx or default_context())
    return dense.tostype(stype)


def random_seed(seed=None):
    seed = seed or _np.random.randint(0, 2 ** 31)
    from . import random as mxrandom

    _np.random.seed(seed)
    _pyrandom.seed(seed)
    mxrandom.seed(seed)
    return seed


def with_seed(seed=None):
    """Reproducible-per-test decorator (reference:
    ``tests/python/unittest/common.py:with_seed``)."""

    def decorator(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            used = random_seed(seed)
            try:
                return fn(*args, **kwargs)
            except Exception:
                print(f"Test {fn.__name__} failed with seed {used}; "
                      f"reproduce with with_seed({used})")
                raise

        return wrapper

    return decorator


def check_numeric_gradient(fn, inputs, grads=None, eps=1e-4, rtol=1e-2,
                           atol=1e-4):
    """Central-difference gradient check against the tape autograd
    (reference: ``check_numeric_gradient`` — the workhorse of
    test_operator.py)."""
    arrays = [a if isinstance(a, NDArray) else _array(a) for a in inputs]
    # non-float inputs (indices, boolean masks) are constants: no gradient
    # is defined and central differences would corrupt them
    is_float = [_np.issubdtype(_np.dtype(str(a.dtype)), _np.floating)
                for a in arrays]
    for a, fl in zip(arrays, is_float):
        if fl:
            a.attach_grad()
    with autograd.record():
        out = fn(*arrays)
    out.backward()
    analytic = [a.grad.asnumpy() if fl else None
                for a, fl in zip(arrays, is_float)]

    for idx, a in enumerate(arrays):
        if not is_float[idx]:
            continue
        base = a.asnumpy().astype(_np.float64)
        num = _np.zeros_like(base)
        flat = base.reshape(-1)
        num_flat = num.reshape(-1)
        # the reduction runs on HOST in float64: a device fp32 .sum()
        # adds ~ulp(sum) of rounding noise, and divided by 2*eps that is
        # ~ulp(sum)/2e-4 — observed 2.4e-3 absolute gradient error on
        # gelu, enough to fail a 1e-3 atol. With the f64 host sum the
        # unperturbed elements' fp32 errors cancel exactly in fp - fm.
        def f64_sum():
            with autograd.pause():
                return float(fn(*arrays).asnumpy()
                             .astype(_np.float64).sum())

        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + eps
            a._set_data(base.reshape(base.shape).astype(a.dtype))
            fp = f64_sum()
            flat[i] = orig - eps
            a._set_data(base.reshape(base.shape).astype(a.dtype))
            fm = f64_sum()
            flat[i] = orig
            a._set_data(base.reshape(base.shape).astype(a.dtype))
            num_flat[i] = (fp - fm) / (2 * eps)
        _np.testing.assert_allclose(analytic[idx], num, rtol=rtol, atol=atol,
                                    err_msg=f"gradient mismatch for input {idx}")


def check_consistency(fn, ctx_list, inputs, rtol=None, atol=None):
    """Run the same function on several contexts/dtypes and cross-compare
    (reference: ``check_consistency`` — for us CPU-vs-TPU)."""
    results = []
    for ctx in ctx_list:
        ctx_inputs = [
            i.as_in_context(ctx) if isinstance(i, NDArray) else _array(i, ctx=ctx)
            for i in inputs
        ]
        out = fn(*ctx_inputs)
        results.append(_as_np(out))
    for r in results[1:]:
        rt, at = get_tolerance(results[0], rtol, atol)
        _np.testing.assert_allclose(results[0].astype(_np.float64),
                                    r.astype(_np.float64), rtol=rt, atol=at)
    return results


def simple_forward(block, *inputs):
    out = block(*[_array(i) if not isinstance(i, NDArray) else i for i in inputs])
    return out.asnumpy() if isinstance(out, NDArray) else [o.asnumpy() for o in out]


class DummyIter:
    """Repeats one batch forever (reference: ``test_utils.DummyIter``)."""

    def __init__(self, real_iter):
        self.real_iter = real_iter
        self.provide_data = real_iter.provide_data
        self.provide_label = real_iter.provide_label
        self.batch_size = real_iter.batch_size
        self.the_batch = next(iter(real_iter))

    def __iter__(self):
        return self

    def next(self):
        return self.the_batch

    __next__ = next

    def reset(self):
        pass


def chain_time_per_iter(step_fn, init, n1=5, n2=40, reps=3):
    """Per-iteration wall time of ``step_fn`` (an ``x -> x``-shaped device
    computation) via a two-point slope over dependent ``fori_loop`` chains.

    A single dispatch+sync carries a fixed host cost that swamps a
    sub-millisecond kernel, so single-shot timings measure the host,
    not the device. Chaining n iterations inside ONE jit and
    differencing two chain lengths cancels that fixed cost exactly.
    Used by tests_tpu/.
    """
    import time

    import jax
    from jax import lax

    from . import engine

    def chain(n):
        f = jax.jit(lambda s: lax.fori_loop(0, n, lambda i, s: step_fn(s), s))
        engine.wait(f(init))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            engine.wait(f(init))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    return (chain(n2) - chain(n1)) / (n2 - n1)


def check_symbolic_forward(sym, location, expected, rtol=1e-4, atol=1e-6,
                           ctx=None, **bind_kwargs):
    """Bind a symbol, run forward, compare each output against
    ``expected`` (reference: ``test_utils.check_symbolic_forward``).

    location: list of arrays (positional, matched to list_arguments) or
    a name->array dict. expected: list of numpy arrays."""
    import numpy as onp

    from .ndarray.ndarray import NDArray, array

    args = sym.list_arguments()
    if isinstance(location, dict):
        feed = {k: (v if isinstance(v, NDArray) else array(v))
                for k, v in location.items()}
    else:
        feed = {n: (v if isinstance(v, NDArray) else array(v))
                for n, v in zip(args, location)}
    ex = sym.simple_bind(ctx=ctx, **{n: tuple(v.shape)
                                     for n, v in feed.items()},
                         **bind_kwargs)
    outs = ex.forward(**feed)
    assert len(outs) == len(expected), (len(outs), len(expected))
    for o, e in zip(outs, expected):
        assert_almost_equal(o.asnumpy(), onp.asarray(e), rtol=rtol,
                            atol=atol)
    return outs


def check_symbolic_backward(sym, location, out_grads, expected,
                            rtol=1e-4, atol=1e-6, grad_req="write",
                            ctx=None):
    """Bind, forward+backward with ``out_grads``, compare each argument
    gradient (reference: ``test_utils.check_symbolic_backward``)."""
    import numpy as onp

    from .ndarray.ndarray import NDArray, array

    args = sym.list_arguments()
    if isinstance(location, dict):
        feed = {k: (v if isinstance(v, NDArray) else array(v))
                for k, v in location.items()}
    else:
        feed = {n: (v if isinstance(v, NDArray) else array(v))
                for n, v in zip(args, location)}
    ex = sym.simple_bind(ctx=ctx, grad_req=grad_req,
                         **{n: tuple(v.shape) for n, v in feed.items()})
    ex.forward(is_train=True, **feed)
    ogs = [g if isinstance(g, NDArray) else array(g) for g in
           (out_grads if isinstance(out_grads, (list, tuple))
            else [out_grads])]
    ex.backward(ogs)
    if isinstance(expected, dict):
        items = expected.items()
    else:
        items = zip(args, expected)
    for name, e in items:
        if e is None:
            continue
        got = ex.grad_dict[name].asnumpy()
        assert_almost_equal(got, onp.asarray(e), rtol=rtol, atol=atol)
    return ex.grad_dict


def same_symbol_structure(sym1, sym2):
    """True when two symbols have identical graph structure — op types,
    topology, and attrs — ignoring node names (reference:
    ``test_utils.same_symbol_structure``)."""
    import json

    def canon(s):
        g = json.loads(s.tojson())
        nodes = []
        for n in g.get("nodes", []):
            inputs = [[e[0], e[1]] for e in n.get("inputs", [])]
            nodes.append((n.get("op"), tuple(sorted(
                (k, str(v)) for k, v in (n.get("attrs") or {}).items())),
                tuple(map(tuple, inputs))))
        return nodes

    return canon(sym1) == canon(sym2)
