"""Host spans of the program's layers, for the JAX profiler's trace.

``span(name, **attrs)`` is ``jax.profiler.TraceAnnotation`` itself: a context
manager that records one event on the profiler's host plane, on the same
clock as the device planes, when a trace is on (``jax.profiler.trace`` /
``start_trace``), and costs one C++ check when it is off.  Keyword attributes
become the event's stats (``qid``, ``rows``, ...).  There is no flag, buffer
or exporter of our own.

Every span is named ``velo.<layer>.<what>`` and sits at a layer boundary
(docs/tracing.md lists them).  The rules they keep:

- no span is held open across a ``yield``: a coroutine that suspends inside
  a span would charge other coroutines' work to it, so the engine puts the
  step span around ``gen.send`` and coroutines wrap only plain calls;
- no span per row, record slot or event-heap entry;
- spans read no clock and feed nothing back: simulated time and results are
  the same with the profiler on or off.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation as span

__all__ = ["span"]
