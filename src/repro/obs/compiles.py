"""XLA compilations of this process, counted from JAX's own monitoring event.

JAX reports the duration of every backend compilation (a fresh program
compiled, or loaded from the persistent compilation cache) as the event
``/jax/core/compile/backend_compile_duration``; a call that finds its program
in the in-memory jit cache reports nothing.  ``COMPILES.install()`` registers one
listener per process (jax is imported there, so obs stays import-light);
``CacheService.metrics()`` mirrors the count and the seconds as
``xla_compiles_total`` and ``xla_compile_seconds_total``.

Locking: ``CompileCounter._lock`` is a leaf — the listener runs inside JAX's
compile path, under whatever locks the caller holds.
"""
from __future__ import annotations

from ..analysis.sanitizer import make_lock

__all__ = ["COMPILE_EVENT", "COMPILES", "CompileCounter"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self._lock = make_lock("CompileCounter._lock")
        self.compiles = 0  # guarded-by: self._lock
        self.seconds = 0.0  # guarded-by: self._lock
        self._installed = False  # guarded-by: self._lock

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self.seconds += duration

    def install(self) -> None:
        """Register the listener (once; later calls do nothing)."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def snapshot(self) -> tuple[int, float]:
        """(compilations, seconds compiling) since the listener was
        installed."""
        with self._lock:
            return self.compiles, self.seconds


COMPILES = CompileCounter()
