"""Engine control surface (reference: ``python/mxnet/engine.py`` over
``src/engine/``).

TPU-native: JAX async dispatch replaces the dependency engine; these
entry points keep the API (bulking is XLA fusion — free; NaiveEngine's
synchronous-debug role maps to ``MXTPU_SYNC_EXEC=1``, which blocks after
every op dispatch — SURVEY.md §5.2)."""

from __future__ import annotations

import contextlib

from . import observability as _obs
from .base import getenv

_BULK = {"size": 15}


def set_bulk_size(size):
    prev, _BULK["size"] = _BULK["size"], size
    return prev


@contextlib.contextmanager
def bulk(size):
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)


def sync_exec_enabled() -> bool:
    """NaiveEngine analog: MXTPU_SYNC_EXEC=1 -> block after every op."""
    return bool(getenv("MXTPU_SYNC_EXEC", False, dtype=bool))


def wait(tree):
    """THE sync primitive (reference: ``Engine::WaitForVar`` /
    ``MXNDArrayWaitToRead``): block until every jax.Array leaf in
    ``tree`` has finished computing, and surface any deferred device
    error here. ``jax.block_until_ready`` plus the telemetry timer."""
    import jax

    if not _obs.ENABLED:
        return jax.block_until_ready(tree)
    import time

    t0 = time.perf_counter()
    try:
        return jax.block_until_ready(tree)
    finally:
        _obs.record_engine_wait("native", time.perf_counter() - t0)
