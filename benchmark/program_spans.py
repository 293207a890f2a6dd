"""The program's own spans, for the readers under ``layer_metrics/``.

The serving engine records its bursts, prefill launches and requests in
a ring of its process (``ray_tpu/_private/spans.py``: ``(name, t0, t1,
ident, parent, fields)`` on ``time.perf_counter``), which outlives the
engine. This file reads that ring, cuts it to the measured window, puts
the engine's bursts together, and maps ``perf_counter`` onto the device
trace's nanoseconds. A program without the ring (a commit from before
it) gives every function here nothing to read, and they return None.

**The clock map** goes through the two points the tracer gives: the
``bench:traced_window`` annotation's ends in the trace
(``trace_summary["window"]``) and ``perf_counter`` read just inside
them (``trace_summary["t0"], ["t1"]``). It is checked in every traced
run against what the trace itself holds of the engine, the executions
of its decode programs (the trace summary keeps the device's planes
and none of the host's annotations): a burst's chunks cannot start on
the device before the host began ``engine.dispatch`` and cannot end
after ``engine.fetch`` returned their tokens, so after mapping every
``jit_engine_decode_n*`` run lies inside its burst's span from
dispatch to fetch, and the bursts' chunk and step counts are those of
the runs found there. How far a run sticks out is the map's error; the
least room a run leaves at either end bounds the error from the other
side. ``[clock_map]`` prints all three, and more than 1 ms raises.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmark import trace_reduce
from benchmark.common import say

BURST = ("engine.admit", "engine.dispatch", "engine.fetch",
         "engine.deliver")
HOST_SIDE = ("engine.admit", "engine.dispatch", "engine.deliver")
DECODE_RUN = re.compile(r"^jit_engine_decode_n(\d+)$")
PREFILL_RUN = re.compile(r"^jit_engine_prefill_b(\d+)$")
CLOCK_MAP_LIMIT_NS = 1e6
NO_SPAN = "(no engine span open)"

Span = Tuple[str, float, float, Any, Any, Dict[str, Any]]


def since(t: float) -> Optional[List[Span]]:
    """The ring's spans that ended at or after ``t``; None where the
    program has no ring."""
    try:
        from ray_tpu._private import spans
    except ImportError:
        return None
    return spans.since(t)


def started_in(records: Sequence[Span], name: str,
               window: Tuple[float, float]) -> List[Span]:
    return [r for r in records
            if r[0] == name and window[0] <= r[1] < window[1]]


def bursts(records: Sequence[Span]) -> List[Dict[str, Span]]:
    """The engine loop's rounds, oldest first: each a dict by span name
    of the ``BURST`` spans that one round left. A round opens with its
    ``engine.admit``; one that dispatched no decode chunk and fetched
    nothing has that span alone."""
    out: List[Dict[str, Span]] = []
    for r in sorted((r for r in records if r[0] in BURST),
                    key=lambda r: r[1]):
        if r[0] == "engine.admit" or not out:
            out.append({})
        out[-1][r[0]] = r
    return out


def whole(burst: Dict[str, Span]) -> bool:
    return all(name in burst for name in BURST)


def clock_map(summary: Dict[str, Any]) -> Callable[[float], float]:
    """perf_counter seconds -> the trace's nanoseconds."""
    (w0, w1), t0, t1 = summary["window"], summary["t0"], summary["t1"]
    rate = (w1 - w0) / (t1 - t0)
    return lambda t: w0 + (t - t0) * rate


def idlest_plane(summary: Dict[str, Any]) -> Dict[str, Any]:
    planes = summary["planes"]
    if len(planes) == 1:
        return planes[0]
    return min(planes, key=lambda p: trace_reduce.busy_seconds(
        p, summary["window"]))


def say_counters(ctx: Dict[str, Any], ring_sums: Dict[str, int]) -> None:
    """``[engine_counters]``: whole-life sums over the ring beside the
    engine's own cumulative counters of the same names
    (``engine_stats``), as ``ring/counter``. They agree while the ring
    has dropped nothing and one engine wrote it."""
    stats = ctx.get("engine_stats") or {}
    say("engine_counters",
        **{k: f"{v}/{stats.get(k)}" for k, v in ring_sums.items()},
        agree=all(stats.get(k) == v for k, v in ring_sums.items()))


def traced_bursts(ctx: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The bursts that lie wholly inside the traced window, each with
    its spans mapped onto the trace's clock (``spans``: name -> (start
    ns, end ns)), the fields of its dispatch, and the decode runs that
    began after its dispatch and before the next burst's (``runs``:
    [(steps, start ns, end ns)]); a burst whose chunk and step counts
    are not those of its runs is left out. Checks the clock map on
    every run (module docstring), once a traced run. None without a
    trace or without the ring."""
    if "_traced_bursts" in ctx:
        return ctx["_traced_bursts"]
    summary = ctx.get("trace_summary")
    records = since(summary["t0"]) if summary else None
    if not records:
        return None
    to_ns = clock_map(summary)
    window = summary["window"]
    out = [{"spans": {n: (to_ns(b[n][1]), to_ns(b[n][2])) for n in BURST},
            "fields": b["engine.dispatch"][5], "runs": []}
           for b in bursts(records) if whole(b)]
    starts = [b["spans"]["engine.dispatch"][0] for b in out]
    worst = 0.0
    room_before = room_after = float("inf")
    for name, s, e in trace_reduce.module_runs(idlest_plane(summary), window):
        m = DECODE_RUN.match(name)
        i = bisect.bisect_right(starts, (s + e) / 2) - 1
        if m is None or i < 0:
            continue      # not a decode run, or of a burst from before
        lo, hi = starts[i], out[i]["spans"]["engine.fetch"][1]
        out[i]["runs"].append((int(m.group(1)), s, e))
        worst = max(worst, lo - s, e - hi)
        room_before = min(room_before, s - lo)
        room_after = min(room_after, hi - e)
    out = [b for b in out if b["spans"]["engine.admit"][0] >= window[0]
           and b["spans"]["engine.deliver"][1] <= window[1]]
    agree = [b for b in out
             if len(b["runs"]) == b["fields"]["chunks"]
             and sum(r[0] for r in b["runs"]) == b["fields"]["steps"]]
    say("clock_map", worst_ms=worst / 1e6,
        least_room_before_ms=room_before / 1e6,
        least_room_after_ms=room_after / 1e6, bursts=len(out),
        bursts_whose_runs_agree=len(agree),
        decode_runs=sum(len(b["runs"]) for b in out))
    if worst > CLOCK_MAP_LIMIT_NS:
        raise ValueError(
            f"clock map: a decode run lies {worst / 1e6:.3f} ms outside "
            f"its burst's engine.dispatch..engine.fetch after mapping "
            f"perf_counter onto the trace's clock (limit 1 ms)")
    ctx["_traced_bursts"] = agree
    return agree


def idle_by_span(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Idle seconds of the idlest device in the traced window by the
    engine-loop span under which they passed, once those spans are
    mapped onto the trace's clock. A gap is cut where a span ends (the
    loop's spans are short beside a gap, and one thread's do not
    overlap), so the seconds add up to the device's idle time; what lay
    under no span of the loop is ``NO_SPAN``."""
    summary = ctx.get("trace_summary")
    records = since(summary["t0"]) if summary else None
    if not records or traced_bursts(ctx) is None:
        return None
    to_ns = clock_map(summary)
    gaps = trace_reduce.idle_gaps(idlest_plane(summary), summary["window"])
    out = {NO_SPAN: trace_reduce.total(gaps) / 1e9}
    for name, t0, t1, *_ in records:
        under = trace_reduce.total(trace_reduce.clip(
            gaps, (to_ns(t0), to_ns(t1)))) / 1e9 if name in BURST else 0.0
        if under:
            out[name] = out.get(name, 0.0) + under
            out[NO_SPAN] -= under
    return out
