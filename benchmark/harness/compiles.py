"""Counts the programs JAX builds: every trip through its compile path,
whether XLA compiled or the persistent cache answered. Either one is a
stall, so a run shows that none happened inside its measured window.
Cache hits are counted apart, so that set-up can say how many of its
programs were compiled and how many loaded."""

from __future__ import annotations

import jax.monitoring

_BUILD = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self) -> None:
        self.builds = 0
        self.build_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_kw) -> None:
        if event == _BUILD:
            self.builds += 1
            self.build_s += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == _HIT:
            self.cache_hits += 1
