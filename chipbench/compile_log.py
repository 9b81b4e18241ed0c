"""Counts the programs JAX lowers and times its backend compiles.

Listens to ``jax.monitoring``.  A lowering is counted for every program
JAX traces to MLIR, eager operations included, whether the persistent
cache then has it or not; so lowerings inside a window are programs the
window had to build.  Only backend compiles are timed: trace events nest
(tracing an outer jit traces the inner ones), so summing them would count
time twice."""

from __future__ import annotations

import collections

import jax

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self) -> None:
        self.count = collections.Counter()
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        self.count[event] += 1
        if event == COMPILE:
            self.secs += duration

    def _event(self, event: str, **_kw) -> None:
        self.count[event] += 1

    def snapshot(self):
        """(programs lowered, persistent-cache hits, backend compiles,
        backend-compile seconds) so far."""
        return (self.count[LOWER], self.count[HIT], self.count[COMPILE],
                self.secs)
