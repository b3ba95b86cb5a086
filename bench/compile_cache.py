"""JAX's persistent compile cache and a count of compilations.

``place`` keeps the cache where ``JAX_COMPILATION_CACHE_DIR`` says, or else
at ``<checkout>/.jax_cache``: a fixed path, because the path is part of the
cache's key. In both cases every program is cached, whatever its compile
time (JAX's default threshold of one second left most of this workload's
programs uncached).
"""
from __future__ import annotations

import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def place() -> str:
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Counts compile requests (each served by the backend compiler or the
    persistent cache), the seconds they took, and the cache's hits and
    misses, via ``jax.monitoring``."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def install(self) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
