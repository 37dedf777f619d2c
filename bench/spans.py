"""The harness's own host spans.

Each span is a ``jax.profiler.TraceAnnotation``, so that in a
traced run it lies on the profiler's clock beside the device's
operations and an idle gap can be charged to the span that was open.
Names in use: ``fit`` (one ``SVC.fit``), ``certify`` (the reference
check), ``submit`` (one ``ServingService.submit``), ``generate`` (a generator
that paces arrivals inside the serving process, between submissions;
the open-loop generator paces from a process of its own).
"""
from __future__ import annotations

NAMES = ("fit", "certify", "submit", "generate")


def span(name: str):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)
