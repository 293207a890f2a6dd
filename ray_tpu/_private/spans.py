"""Spans of the program's own hot loops, kept in memory.

A span is ``(name, t0, t1, ident, parent, fields)`` on
``time.perf_counter``: ``ident`` is shared by the spans of one request,
``parent`` is the ``trace_plane`` context of the call that caused it
(None where that plane is off), ``fields`` are the counts that belong
to the span (a burst's steps, a launch's rows), so that a ratio is
taken where the work happens. They go into ONE ring for the whole
process, bounded, oldest dropped first, because whoever reads them (an
operator's debugger, the benchmark's readers) comes after the object
that wrote them is gone. Always on: a writer pays one tuple append and
one ``TraceAnnotation`` a span, so it records a span per burst, launch,
request or replica call, never per token or step. Who writes what: the
inference engine its loop's rounds, prefill launches and requests
(``engine.*``), a serve replica one ``replica.call`` a call it ran
(``serve/core.py``), under the ``ident`` of the engine request the call
served where the deployment named one.

``span`` is also a ``jax.profiler.TraceAnnotation``: while a profile is
being taken the same span stands on the profiler's clock beside the
device's operations. ``record`` is not (its ends are known only once it
is over), and jax is imported by the first ``span`` alone, so a replica
whose deployment never touches jax does not load it for its calls'
records.
"""

from __future__ import annotations

import collections
import time
from typing import Any, List, Optional, Tuple

Span = Tuple[str, float, float, Any, Any, dict]

_RING: "collections.deque[Span]" = collections.deque(maxlen=65536)


class span:
    """``with span("engine.fetch"): ...`` records the block. ``fields``
    may be added to until the block ends (``sp.fields["steps"] = n``);
    the annotation carries those known at its start."""

    __slots__ = ("name", "ident", "fields", "t0", "_note")

    def __init__(self, name: str, ident: Any = None, **fields: Any) -> None:
        self.name, self.ident, self.fields = name, ident, fields

    def __enter__(self) -> "span":
        from jax.profiler import TraceAnnotation

        self._note = TraceAnnotation(self.name, **self.fields)
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._note.__exit__(*exc)
        _RING.append((self.name, self.t0, t1, self.ident, None, self.fields))


def record(name: str, t0: float, t1: float, /, ident: Any = None,
           parent: Optional[tuple] = None, **fields: Any) -> None:
    """A span whose ends the caller took itself: they lie in different
    threads, or what the span says is known only once it is over."""
    _RING.append((name, t0, t1, ident, parent, fields))


def since(t: float) -> List[Span]:
    """Every span still in the ring that ended at or after ``t``,
    oldest first."""
    return [s for s in list(_RING) if s[2] >= t]
