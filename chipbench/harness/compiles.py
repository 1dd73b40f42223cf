"""Counts XLA backend compiles (copied from ``chip_smoke.py``'s
``CompileCounter``; the original is listed in PERF.md for deletion)."""

from __future__ import annotations


class CompileCounter:
    """A persistent-cache hit counts too: it is still a new executable
    that a measured window must not need."""

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
