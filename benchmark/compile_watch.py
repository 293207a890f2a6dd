"""What jax spent compiling, from its own monitoring events (the idea
of chip_smoke.CompileClock, copied so that the yardstick does not move
with it): splits set-up into its parts, and finds the compilations
that fall inside the measured window, each with the name jax gives it.
None may be of a jitted FUNCTION: set-up has to have built every one
that the window uses. What a program dispatches eagerly, one primitive
at a time (``jit(concatenate)``), compiles anew in every process for
each new shape, and is part of what the program costs its users: those
are counted and reported, not refused (``eager_primitive``).

The two are told apart by name and never by how long a compilation
took or by whether the persistent cache answered: on a host that
stands still for a second a 40 ms compilation reads as one of over a
second, jax then writes it into the persistent cache, and every later
run of that checkout meets it there (PERF.md, PR 24, third session)."""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Tuple

_DURATIONS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}
_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileWatch:
    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {
            k: 0.0 for k in (*_DURATIONS.values(), *_COUNTS.values(),
                             "backend_compiles")}
        # (perf_counter at its end, seconds, jax's name for it) of
        # every backend compile, read from the cache or not
        self.compiles: List[Tuple[float, float, str]] = []
        self.hits: List[float] = []     # perf_counter of each cache hit
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        key = _DURATIONS.get(event)
        if key is not None:
            with self._lock:
                self._totals[key] += secs
                if key == "backend_compile_s":
                    self._totals["backend_compiles"] += 1
                    self.compiles.append((time.perf_counter(), secs,
                                          str(kw.get("fun_name", "?"))))

    def _on_event(self, event: str, **_kw) -> None:
        key = _COUNTS.get(event)
        if key is not None:
            with self._lock:
                self._totals[key] += 1
                if key == "cache_hits":
                    self.hits.append(time.perf_counter())

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        return {k: round(v - before[k], 3)
                for k, v in self.snapshot().items()}


    def between(self, t0: float, t1: float) -> List[Tuple[str, float]]:
        """(name, seconds) of each backend compile that ended in
        [t0, t1]."""
        with self._lock:
            return [(name, secs) for t, secs, name in self.compiles
                    if t0 <= t <= t1]

    def hits_between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(t0 <= t <= t1 for t in self.hits)


def eager_primitive(fun_name: str) -> bool:
    """Whether jax's name of a compiled program, ``jit(<name>)``, is
    that of one primitive dispatched on its own (``jnp.concatenate``
    called outside any jit) and not of a jitted function."""
    from jax.extend.core import Primitive, primitives

    names = {p.name for p in vars(primitives).values()
             if isinstance(p, Primitive)}
    return (fun_name.startswith("jit(") and fun_name.endswith(")")
            and fun_name[4:-1] in names)


class CompiledInWindow(RuntimeError):
    """Something compiled inside the measured window."""
