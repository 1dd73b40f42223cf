"""Ctx-flip suite reuse (reference pattern: tests/python/gpu/
``test_operator_gpu.py`` does ``from test_operator import *`` and flips
the default context — SURVEY.md §4 names this as the pattern to copy).

Here the flip is implicit: without the CPU-forcing conftest of
``tests/``, the default context on this backend resolves to ``tpu(0)``,
so every imported CPU test runs its ops on the real chip. A curated set
keeps wall-clock sane (each distinct op shape triggers a compile); the
full CPU suite remains the source of truth.
"""

import importlib.util
import os
import sys

_TESTS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"cpu_suite_{name}", os.path.join(_TESTS_DIR, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


_autograd = _load("test_autograd")
_ndarray = _load("test_ndarray")

# re-export: pytest collects these and runs them on the TPU default ctx
test_simple_grad = _autograd.test_simple_grad
test_chain_and_branches = _autograd.test_chain_and_branches
test_grad_req_add = _autograd.test_grad_req_add
test_head_gradient = _autograd.test_head_gradient
test_detach = _autograd.test_detach
test_train_predict_mode = _autograd.test_train_predict_mode
test_intermediate_attach_grad = _autograd.test_intermediate_attach_grad

test_creation = _ndarray.test_creation
test_arithmetic = _ndarray.test_arithmetic
test_inplace = _ndarray.test_inplace
test_indexing_basic = _ndarray.test_indexing_basic
test_view_aliasing = _ndarray.test_view_aliasing
test_setitem = _ndarray.test_setitem
test_scalar_conversion = _ndarray.test_scalar_conversion
test_waitall_and_sync = _ndarray.test_waitall_and_sync


def test_default_context_is_tpu():
    """The whole point: these tests must actually run on the chip."""
    import jax

    import mxnet_tpu as mx

    if jax.default_backend() == "cpu":  # skipped via conftest anyway
        return
    assert mx.context.current_context().device_type == "tpu"
    a = mx.nd.ones((2, 2))
    assert "Tpu" in type(a.data.device).__name__ or \
        a.data.device.platform == "tpu"
