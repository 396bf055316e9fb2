"""Host spans of the benchmark's own files (``bench.*``), written into the
profiler's trace by ``jax.profiler.TraceAnnotation`` when a trace is on."""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def span(name: str):
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield
