"""JAX's persistent compilation cache, and a count of compiles.

Every entry point that compiles for the chip (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks/run.py``) calls ``enable()`` before its
first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and no other directory is set.  Otherwise the cache lives at a fixed path,
``<repo>/.jax_cache``: the path is part of each entry's key, so a directory
that moved between runs would never hit.
"""

from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)

# JAX's default (1 s) skips most kernels and small jitted steps, which
# compile in well under a second each but number in the dozens per run.
MIN_COMPILE_SECS = 0.0

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_SECS)
    return path


class CompileCounter:
    """Counts backend compiles (cache hits included) and persistent-cache hits
    while it is open.  Use as a context manager."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
