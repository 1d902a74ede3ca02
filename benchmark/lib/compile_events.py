"""Counts compilation requests through jax's monitoring events.

With the persistent cache on (the benchmark always places it), every
program that is not already compiled in this process raises
``compile_requests_use_cache`` before it is looked up or compiled; that
is the number of compilations asked for, and the benchmark reads it at
both ends of the window: the difference has to be 0.

``chip_smoke.py::CacheCounter`` (PR 21) counts cache hits and misses,
and so did this counter at first.  Their sum is not the number of
compilations: jax raises ``cache_misses`` only when it writes an entry,
and it writes none for a program that compiled in under
``jax_persistent_cache_min_compile_time_secs`` (0.5 s here), so a small
program compiled inside the window raised neither (PR 22: the serving
engine's key schedules).  Hits and misses are kept for the run's
cache line.
"""

from __future__ import annotations

_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    def __init__(self):
        import jax.monitoring

        self.requests = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == _REQUEST:
            self.requests += 1
        elif event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def total(self) -> int:
        """Compilations asked for so far in this process."""
        return self.requests
