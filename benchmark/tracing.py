"""Takes the device trace of a part of the measured window and reduces
it. Only the process that holds the chip can trace it, and a trace of
the whole window would be too large to read back inside a run's time
limit, so the traced part is ``trace.seconds`` long and starts
``trace.start_frac`` of the way into the window (both in the traffic
file; the system has reached its steady state by then)."""

from __future__ import annotations

import shutil
import threading
import time
from typing import Any, Dict, Optional

from benchmark import trace_reduce
from benchmark.spans import Recorder


class WindowTracer:
    def __init__(self, cell, recorder: Recorder) -> None:
        spec = cell.traffic["trace"]
        self.seconds = min(float(spec["seconds"]), cell.seconds)
        self.offset = min(float(spec["start_frac"]) * cell.seconds,
                          cell.seconds - self.seconds)
        self.dir = cell.scratch("trace")
        self.n_devices = cell.chips
        self.rehearsal = cell.rehearsal
        self.recorder = recorder
        self.t0 = self.t1 = 0.0       # perf_counter, of the traced part
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[Exception] = None

    def start(self, t_open: float) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread = threading.Thread(
            target=self._run, args=(t_open,), daemon=True,
            name="bench_tracer")
        self._thread.start()

    def _run(self, t_open: float) -> None:
        import jax

        try:
            time.sleep(max(0.0, t_open + self.offset - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                self.recorder.annotate = True
                with jax.profiler.TraceAnnotation(
                        trace_reduce.WINDOW_SPAN):
                    self.t0 = time.perf_counter()
                    time.sleep(self.seconds)
                    self.t1 = time.perf_counter()
            finally:
                self.recorder.annotate = False
                jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - re-raised by finish
            self._error = e

    def finish(self) -> Optional[Dict[str, Any]]:
        """Wait for the trace, read it, reduce it and delete it. A CPU
        rehearsal's trace has no device plane and reduces to None; on
        the chip that raises."""
        self._thread.join()
        if self._error is not None:
            raise self._error
        t0 = time.perf_counter()
        trace = trace_reduce.load_xplane(trace_reduce.find_xplane(self.dir))
        if self.rehearsal and not trace_reduce.device_planes(trace):
            trace_reduce.traced_window(trace)     # the span is there
            shutil.rmtree(self.dir, ignore_errors=True)
            return None
        summary = trace_reduce.summarize(trace, self.n_devices)
        summary["read_s"] = time.perf_counter() - t0
        summary["t0"], summary["t1"] = self.t0, self.t1
        shutil.rmtree(self.dir, ignore_errors=True)
        return summary
