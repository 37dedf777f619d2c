"""Host spans and stage counters of the serving path.

Every timed stage of a batch is one ``jax.profiler.TraceAnnotation``
named ``serve.<stage>``: while a profiler trace is active it lies on
the trace's clock beside the device's operations, nested in the span
that encloses it on the same thread; otherwise it records nothing. The
same stage adds its ``time.perf_counter()`` seconds to a per-batch
``Stages`` accumulator, which the service folds into its cumulative
``stats``. So the counters are always kept, and the spans cost a
constant fraction of a microsecond each when no trace runs.

    stages = Stages()
    with stages("serve.merge", "merge_s"):
        ...
    stages.totals        # {"merge_s": seconds}

Spans without a counter (the predictor's ``serve.upload``,
``serve.launch`` and ``serve.fetch``) are plain ``TraceAnnotation`` spans.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class Stages:
    """One batch's host seconds and counts, keyed as in
    ``ServingService.stats``."""

    __slots__ = ("totals",)

    def __init__(self):
        self.totals: dict = {}

    def add(self, key: str, value) -> None:
        self.totals[key] = self.totals.get(key, 0) + value

    def __call__(self, name: str, key: str, **meta) -> "_Stage":
        """A span ``name`` whose elapsed seconds are added to ``key``."""
        return _Stage(self, key, TraceAnnotation(name, **meta))


class _Stage:
    __slots__ = ("_stages", "_key", "_span", "_t0")

    def __init__(self, stages: Stages, key: str, span: TraceAnnotation):
        self._stages, self._key, self._span = stages, key, span

    def set_metadata(self, **meta) -> None:
        """Metadata known only once the span is open (rows, requests)."""
        self._span.set_metadata(**meta)

    def __enter__(self) -> "_Stage":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        # the span closes right after the clock is read, so the span
        # and the counter cover the same interval but for the crossings
        t = time.perf_counter()
        self._span.__exit__(*exc)
        self._stages.add(self._key, t - self._t0)
