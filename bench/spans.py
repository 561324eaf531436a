"""The benchmark's own host spans: name, start and end on the host clock.

Spans are kept in memory.  Each also opens a ``jax.profiler``
``TraceAnnotation`` when the run is traced, so the profiler's trace carries
them on the device's clock and an idle gap can be labelled by the span
open during it.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, *, traced: bool = False):
        self.traced = traced
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> list[float]:
        """Seconds of every closed span called ``name``, in order."""
        return [b - a for n, a, b in self.records if n == name]
