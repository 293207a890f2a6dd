"""From a ``jax.profiler`` trace to the numbers the benchmark reports.

Every PR computes the same number in the same way because this file is
under the benchmark's paths. It works on a plain structure,

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

which ``load_xplane`` reads from an ``.xplane.pb`` with nothing but JAX
and which a test can keep as JSON. What a v5e trace looks like (seen by
hand, PR 24): one plane ``/device:TPU:<n>`` a chip, whose line ``XLA
Ops`` holds one event for each executed HLO instruction, named by its
whole HLO text (cut here to the instruction's name: ``fusion.12``,
``flash_attention.4``, ``all-reduce.7``; they stay HLO names until the
program names scopes) and whose line ``XLA Modules`` holds one event
for each executed program (``jit_<function>(<id>)``); lines ``Steps``,
``Async XLA Ops``, ``Scalar Unit`` and ``TC Overlay`` are not read;
the host's threads are lines of the plane ``/host:CPU`` and hold the
benchmark's ``bench:<span>`` annotations on the same clock.

All times returned are seconds. A share that comes out above 1 raises:
the numerator then counts work twice or the denominator leaves time
out, and clamping it would hide the fault.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:traced_window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)(-start|-done)?(\.\d+)?$")


class ShareOverOne(ValueError):
    """A share read above 100 %."""


def share(part: float, whole: float, what: str) -> float:
    """part / whole as a fraction, refusing anything above 1 (a hair of
    slack for float rounding)."""
    if whole <= 0:
        raise ValueError(f"{what}: the whole is {whole}")
    value = part / whole
    if value > 1.0 + 1e-9:
        raise ShareOverOne(f"{what}: {part} of {whole} is "
                           f"{100 * value:.3f} %, over 100 %")
    return min(value, 1.0)


# ----------------------------------------------------------------------
# reading
# ----------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO text
    (``%fusion.44 = (f32[...]) fusion(...)``); keep the instruction's
    name alone."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str, keep_host_prefix: str = SPAN_PREFIX
                ) -> Dict[str, Any]:
    """Device planes whole; of the host plane only the benchmark's own
    annotations (the rest is the runtime's threads)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = DEVICE_PLANE.match(plane.name) is not None
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[short_name(e.name), float(e.start_ns),
                       float(e.duration_ns)]
                      for e in line.events
                      if is_device or e.name.startswith(keep_host_prefix)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ----------------------------------------------------------------------
# interval arithmetic
# ----------------------------------------------------------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of (merged) ``a`` that no interval of (merged) ``b``
    covers."""
    out: List[Interval] = []
    b = list(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ----------------------------------------------------------------------
# the trace's parts
# ----------------------------------------------------------------------

def device_planes(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(planes,
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def line_events(plane: Dict[str, Any], line_name: str) -> List[List[Any]]:
    return [e for line in plane["lines"] if line["name"] == line_name
            for e in line["events"]]


def host_spans(trace: Dict[str, Any]) -> List[Tuple[str, float, float]]:
    """(name without the prefix, start_ns, end_ns) of every benchmark
    annotation on any host thread."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name[len(SPAN_PREFIX):], start,
                                start + dur))
    return out


def traced_window(trace: Dict[str, Any]) -> Interval:
    """The span the harness holds open for exactly the traced part of
    the measured window."""
    for name, s, e in host_spans(trace):
        if SPAN_PREFIX + name == WINDOW_SPAN:
            return (s, e)
    raise ValueError(f"the trace holds no {WINDOW_SPAN} annotation")


def op_intervals(plane: Dict[str, Any], window: Interval,
                 keep=lambda name: True) -> List[Interval]:
    return clip(((s, s + d) for n, s, d in line_events(plane, OPS_LINE)
                 if keep(n)), window)


def busy_seconds(plane: Dict[str, Any], window: Interval) -> float:
    """Seconds inside the window in which at least one operation ran on
    this device."""
    return total(merge(op_intervals(plane, window))) / 1e9


def idle_gaps(plane: Dict[str, Any], window: Interval) -> List[Interval]:
    return subtract([window], merge(op_intervals(plane, window)))


def op_seconds(plane: Dict[str, Any], window: Interval) -> Dict[str, float]:
    """Device seconds by operation name (an operation running while
    another does counts in full under its own name)."""
    out: Dict[str, float] = {}
    for name, s, d in line_events(plane, OPS_LINE):
        got = clip([(s, s + d)], window)
        if got:
            out[name] = out.get(name, 0.0) + total(got) / 1e9
    return out


def module_runs(plane: Dict[str, Any], window: Interval
                ) -> List[Tuple[str, float, float]]:
    """(program name without its run id, start_ns, end_ns) of every
    program execution that lies WHOLLY inside the window."""
    out = []
    for name, s, d in line_events(plane, MODULES_LINE):
        if s >= window[0] and s + d <= window[1]:
            out.append((re.sub(r"\(\d+\)$", "", name), s, s + d))
    return out


def collective_exposed_seconds(plane: Dict[str, Any], window: Interval
                               ) -> float:
    """Seconds in which a collective ran on this device and nothing
    else did."""
    is_coll = lambda n: COLLECTIVE.match(n) is not None   # noqa: E731
    coll = merge(op_intervals(plane, window, is_coll))
    rest = merge(op_intervals(plane, window, lambda n: not is_coll(n)))
    return total(subtract(coll, rest)) / 1e9


def label_gaps(gaps: Sequence[Interval],
               spans: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """Each gap with the benchmark span open on the host at its middle
    (the innermost: the one that started last), longest gap first, as
    (label, seconds)."""
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        open_now = [sp for sp in spans if sp[1] <= mid < sp[2]
                    and SPAN_PREFIX + sp[0] != WINDOW_SPAN]
        label = (max(open_now, key=lambda sp: sp[1])[0] if open_now
                 else "(no benchmark span open)")
        out.append((label, (e - s) / 1e9))
    return sorted(out, key=lambda x: -x[1])


def summarize(trace: Dict[str, Any], n_devices: Optional[int] = None
              ) -> Dict[str, Any]:
    """What every traced run reports whatever its cell: the traced
    window, busy seconds (mean over the devices used, and of the
    idlest), the ten operations that took most device time and the idle
    seconds by the benchmark span open on the host during each gap
    (ten largest), both on the idlest device."""
    window = traced_window(trace)
    planes = device_planes(trace)
    if n_devices is not None:
        planes = planes[:n_devices]
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane")
    window_s = (window[1] - window[0]) / 1e9
    busy = [busy_seconds(p, window) for p in planes]
    for b in busy:
        share(b, window_s, "device busy share")
    worst = planes[busy.index(min(busy))]
    if not any(busy):
        raise ValueError("no operation ran on the device in the traced "
                         "window")
    ops = sorted(op_seconds(worst, window).items(), key=lambda x: -x[1])
    by_label: Dict[str, float] = {}
    for label, seconds in label_gaps(idle_gaps(worst, window),
                                     host_spans(trace)):
        by_label[label] = by_label.get(label, 0.0) + seconds
    gaps = sorted(by_label.items(), key=lambda x: -x[1])
    return {
        "window": window, "window_s": window_s,
        "busy_s": sum(busy) / len(busy), "busy_s_min": min(busy),
        "device_ops": [[n, s] for n, s in ops[:10]],
        "idle_gaps": [[n, s] for n, s in gaps[:10]],
        "planes": planes,
    }
