"""Host spans on the profiler's clock.

``span(name, **counts)`` marks a stretch of host code at a layer boundary
of the fleet build or live serving.  It is a
``jax.profiler.TraceAnnotation`` named ``repro:<name>``, so a profiler
trace holds it on the same clock as the device's operations, nested
inside the spans around it; the keyword arguments are the span's
counters (integers such as the frames featurized or the steps scanned),
stored as the event's stats.  The ``repro:`` prefix tells the program's
spans from JAX's own host events.

The span also keeps its own host-clock length, ``.seconds``, on the
object it yields, so code that reports a wall time needs no second
timer.  With the profiler off a span costs one ``TraceAnnotation``,
about a microsecond: open spans around whole stages, never inside a
per-device or per-frame loop.

Usage::

    with span("serve.scan", steps=n_steps) as sp:
        ...
    wall_s = sp.seconds
"""
from __future__ import annotations

import time

import jax

#: prefix of every span's name in a profiler trace
PREFIX = "repro:"


class span:
    """A context manager that marks ``name`` in the profiler's trace, with
    ``counts`` as its counters, and keeps its host-clock length in
    ``.seconds`` once it closes."""

    __slots__ = ("name", "seconds", "_ann", "_t0")

    def __init__(self, name: str, **counts: int):
        self.name = name
        self.seconds: float | None = None
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **counts)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
