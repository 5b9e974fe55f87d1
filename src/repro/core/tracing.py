"""Spans: the wall time of one stretch of a search's work.

``span(name, stats, field)`` adds the stretch's ``time.perf_counter()``
seconds to ``stats.<field>`` (always on: a plain float on
``SearchStats``), and marks it as ``jax.profiler.TraceAnnotation(name)``,
which lands in the profiler's trace, on the device's clock, whenever a
trace is recording (``jax.profiler.trace(dir)``); with none recording
the span opens no annotation.

A process that has not imported ``jax`` cannot be recording a trace, so
the span then skips the annotation and never imports ``jax`` itself: the
plain (non-quantized) search path stays free of it.

Span names: ``serve.search`` (a worker's whole request) and
``serve.more`` on the serving layer; ``ecp.fetch``, ``ecp.pack``,
``ecp.kernel``, ``ecp.expand`` and ``ecp.rerank`` inside a search, which
never overlap one another.
"""
from __future__ import annotations

import sys
import time

__all__ = ["span"]


class span:
    """``with span(name, stats=None, field=None) as s:`` — after the block
    ``s.seconds`` holds its duration, already added to ``stats.<field>``
    when ``stats`` is given."""

    __slots__ = ("name", "stats", "field", "seconds", "_t0", "_ann")

    def __init__(self, name: str, stats=None, field: str | None = None):
        self.name = name
        self.stats = stats
        self.field = field
        self.seconds = 0.0

    def __enter__(self) -> "span":
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        self._ann = None
        if profiler is not None and profiler.TraceAnnotation.is_enabled():
            self._ann = profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.stats is not None:
            setattr(self.stats, self.field, getattr(self.stats, self.field) + self.seconds)
