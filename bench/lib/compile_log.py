"""Counts the programs JAX compiled or loaded from the persistent cache
(its backend-compile event covers both), their seconds, and how many of
them the cache served (JAX monitoring events); copied from the
repository's ``chip_smoke.py``."""
from __future__ import annotations

import jax

COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        self.n = 0
        self.hits = 0
        self.seconds = 0.0
        self.names: list[str] = []      # each program's name, in order
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, fun_name: str = "?",
                     **_) -> None:
        if event == COMPILE:
            self.n += 1
            self.seconds += secs
            self.names.append(fun_name)

    def _on_event(self, event: str, **_) -> None:
        if event == HIT:
            self.hits += 1

    def mark(self) -> tuple[int, int, float]:
        return self.n, self.hits, self.seconds
