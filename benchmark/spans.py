"""Spans of the benchmark's own, recorded in memory around its calls
into each layer of the program.

A span is (name, start, end) on ``time.perf_counter``. While a device
trace is being taken each span is also a ``jax.profiler
.TraceAnnotation`` named ``bench:<name>``, which puts it on the
profiler's clock beside the device's operations: that is how an idle
gap of the device is labelled with what the host was doing.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, List, Tuple

PREFIX = "bench:"


class Recorder:
    def __init__(self) -> None:
        self.spans: List[Tuple[str, float, float]] = []
        self.annotate = False          # True while the profiler runs

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(PREFIX + name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def durations(self, name: str, t_from: float = float("-inf"),
                  t_to: float = float("inf")) -> List[float]:
        """Seconds of every span ``name`` that started in [t_from,
        t_to)."""
        return [t1 - t0 for n, t0, t1 in list(self.spans)
                if n == name and t_from <= t0 < t_to]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list: the
    smallest value with at least q % of the sample at or below it."""
    import math

    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
