"""Device contexts.

Reference: ``python/mxnet/context.py`` (symbol ``Context``). The TPU-native
design maps a Context onto a concrete ``jax.Device``:

- ``mx.cpu(i)``   -> i-th host CPU device
- ``mx.tpu(i)``   -> i-th accelerator device of the default JAX backend
- ``mx.gpu(i)``   -> alias for ``mx.tpu(i)`` so reference model scripts run
  with a one-line (or zero-line) change.

A thread-local default-context stack backs ``with mx.Context(...)`` exactly
like the reference. Unlike the reference there is no stream or dev_mask —
XLA owns scheduling; a Context is only a placement annotation consumed by
``jax.device_put`` / jit sharding.
"""

from __future__ import annotations

import threading

import jax

from .base import MXNetError

_DEVTYPE_TO_ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
_ID_TO_DEVTYPE = {v: k for k, v in _DEVTYPE_TO_ID.items()}


_CACHE_WIRED = False


def _wire_compile_cache():
    """One-shot env hookups deferred to the first Context so plain
    imports never touch jax config (and the flag keeps
    Context.__init__ to one boolean check afterwards):
    MXTPU_COMPILE_CACHE, the MXTPU_METRICS_PORT scrape endpoint, the
    MXTPU_FEDERATION publisher and the MXTPU_WATCHDOG loop."""
    global _CACHE_WIRED
    _CACHE_WIRED = True
    from . import runtime

    runtime.setup_compile_cache()
    from .observability import serve as _serve

    _serve.maybe_serve()
    from .observability import federation as _federation
    from .observability import watchdog as _watchdog

    _federation.maybe_start()
    _watchdog.maybe_start()


class Context:
    """A device context. ``Context('tpu', 0)`` or ``Context(other_ctx)``."""

    _default_stack = threading.local()
    devtype2str = _ID_TO_DEVTYPE
    devstr2type = _DEVTYPE_TO_ID

    def __init__(self, device_type, device_id: int = 0):
        if not _CACHE_WIRED:
            _wire_compile_cache()
        if isinstance(device_type, Context):
            self.device_type, self.device_id = (
                device_type.device_type,
                device_type.device_id,
            )
        elif isinstance(device_type, int):
            self.device_type = _ID_TO_DEVTYPE[device_type]
            self.device_id = device_id
        else:
            if device_type not in _DEVTYPE_TO_ID:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_type = device_type
            self.device_id = device_id
        self._old_ctx = None

    # -- identity ---------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return _DEVTYPE_TO_ID[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self._canonical() == other._canonical()
        )

    def _canonical(self):
        # gpu is an alias for tpu when the backend is a TPU; both resolve to
        # the same jax device, so they must compare equal.
        dt = self.device_type
        if dt in ("gpu", "tpu") and jax.default_backend() != "cpu":
            dt = "accel"
        elif dt in ("cpu_pinned", "cpu_shared"):
            dt = "cpu"
        return (dt, self.device_id)

    def __hash__(self):
        return hash(self._canonical())

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()

    # -- jax mapping ------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        plat = jax.default_backend()
        # device ids index PROCESS-LOCAL devices: under multi-process SPMD
        # (jax.distributed), jax.devices() spans all hosts and remote
        # entries are non-addressable from this process.
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = (jax.local_devices(backend="cpu") if plat != "cpu"
                    else jax.local_devices())
        else:  # gpu / tpu -> default accelerator backend
            devs = jax.local_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self} out of range: backend '{plat}' has {len(devs)} device(s)"
            )
        return devs[self.device_id]

    # -- default-context stack -------------------------------------------
    @classmethod
    def _current(cls) -> "Context":
        stack = getattr(cls._default_stack, "stack", None)
        if stack:
            return stack[-1]
        return _DEFAULT

    def __enter__(self):
        stack = getattr(Context._default_stack, "stack", None)
        if stack is None:
            stack = Context._default_stack.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_stack.stack.pop()
        return False

    # reference parity helpers
    def empty_cache(self):  # XLA owns the allocator; nothing to do
        return None


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias of :func:`tpu` on TPU backends (reference scripts use mx.gpu())."""
    return Context("gpu", device_id)


def num_gpus() -> int:
    """Number of accelerator devices (reference: ``context.py:num_gpus``)."""
    plat = jax.default_backend()
    return 0 if plat == "cpu" else len(jax.local_devices())


def num_tpus() -> int:
    return num_gpus()


def current_context() -> Context:
    return Context._current()


def _default_ctx() -> Context:
    return Context("tpu", 0) if jax.default_backend() != "cpu" else Context("cpu", 0)


class _LazyDefault(Context):
    """Default ctx resolved lazily so importing never initializes a backend."""

    def __init__(self):  # noqa: super-init-not-called - lazy by design
        self._resolved = None

    def _r(self) -> Context:
        if self._resolved is None:
            self._resolved = _default_ctx()
        return self._resolved

    @property
    def device_type(self):
        return self._r().device_type

    @property
    def device_id(self):
        return self._r().device_id


_DEFAULT = _LazyDefault()
