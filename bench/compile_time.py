"""Host time that JAX spends compiling, from its own monitoring events.

JAX 0.9 reports a time span (on `time.time()`'s clock) for each trace of a
function to a jaxpr, each lowering of a jaxpr to an MLIR module, and each
backend compile, which includes reading the persistent compilation cache
(`/jax/compilation_cache/cache_retrieval_time_sec` is reported inside it).
A jitted function traced inside another reports its span inside the
outer's, so the spans are merged, not summed: `CompileClock.seconds` is
the length of their union, the wall time in which some compilation ran.
"""
from __future__ import annotations

import time

import xtrace

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Collects the compile spans JAX reports once `register` is called."""

    def __init__(self):
        self.spans = []

    def register(self):
        import jax
        jax.monitoring.register_event_time_span_listener(self.on_span)
        return self

    def on_span(self, event: str, start: float, end: float, **_):
        if event in EVENTS:
            self.spans.append((start, end))

    def seconds(self, until: float | None = None) -> float:
        """Length of the union of the spans, cut at `until` (a `time.time()`
        reading; default now)."""
        until = time.time() if until is None else until
        return xtrace.length(xtrace.union(
            (s, min(e, until)) for s, e in self.spans))
