"""A request's way in and a token's way out, for the readers under
``layer_metrics/``: what the serve replica and the engine say of one
request, joined to what its client saw.

The program's ring (``ray_tpu/_private/spans.py``, read through
``program_spans.since``) holds one ``replica.call`` a call that a
replica ran (fields ``method``, ``t_routed`` and ``waited_ms`` where
router and replica share a clock, and what the deployment named:
``ident``, ``prompt_tokens``, ``max_new`` for a ``start_stream``;
``ident``, ``tokens``, ``done``, ``blocked_ms``, ``held_ms`` for a
``next_tokens``) beside the engine's three spans a request
(``engine.queue``, ``.first_token``, ``.decode``, the last two with
``tokens``) under the same ``ident``. All of it is on
``time.perf_counter`` in the process that holds the chip, the clock of
the clients (``drivers/serve.py::Served``) and of the benchmark's own
recorder, so spans are compared as they stand. A program from before
these spans leaves every function here nothing to read: they return
None or an empty list and print nothing.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans
from benchmark.common import say
from benchmark.program_spans import Span
from benchmark.spans import percentile

CALL = "replica.call"
PIECES = ("due_to_sent", "sent_to_routed", "replica_wait",
          "call_to_submit", "engine_queue", "engine_first_token",
          "held", "return_path")


def ms(seconds: float) -> float:
    return 1e3 * seconds


def window_calls(ctx: Dict[str, Any], method: str = "") -> List[Span]:
    """The ``replica.call`` spans whose hand-over by the router fell in
    the measured window (so only those that carry the router's stamp),
    of one method or of all, oldest hand-over first."""
    w0, w1 = ctx["window"]
    calls = [r for r in program_spans.since(w0) or ()
             if r[0] == CALL and "t_routed" in r[5]
             and w0 <= r[5]["t_routed"] < w1
             and method in ("", r[5].get("method"))]
    return sorted(calls, key=lambda r: r[5]["t_routed"])


def quantiles(values: Sequence[float]) -> Dict[str, float]:
    return {"p50": statistics.median(values),
            "p90": percentile(values, 90), "max": max(values)}


def most_waiting_at_once(calls: Sequence[Span]) -> int:
    """The largest number of calls that had been handed over and had no
    replica thread yet, at any one time."""
    edges = sorted([(r[5]["t_routed"], 1) for r in calls]
                   + [(r[1], -1) for r in calls])
    most = now = 0
    for _t, step in edges:
        now += step
        most = max(most, now)
    return most


def say_replica_calls(calls: Sequence[Span]) -> None:
    """``[replica_calls]``: a line a method with the count and the p50,
    p90 and max of the wait for a replica thread and of the method's
    own run, then one line for all of them with the largest number
    that waited at once."""
    by_method: Dict[str, List[Span]] = {}
    for r in calls:
        by_method.setdefault(r[5]["method"], []).append(r)
    for method, rs in sorted(by_method.items()):
        say("replica_calls", method=method, n=len(rs),
            **{f"waited_{k}_ms": v for k, v in
               quantiles([r[5]["waited_ms"] for r in rs]).items()},
            **{f"ran_{k}_ms": v for k, v in
               quantiles([ms(r[2] - r[1]) for r in rs]).items()})
    say("replica_calls", method="(all)", n=len(calls),
        most_waiting_at_once=most_waiting_at_once(calls))


def by_ident(records: Sequence[Span], name: str) -> Dict[Any, Span]:
    return {r[3]: r for r in records if r[0] == name}


def joined(ctx: Dict[str, Any]) -> Tuple[List[Dict[str, Any]], int]:
    """The window's requests as their clients saw them, each joined to
    the program's spans of it, and how many requests the window had.
    A client's request is the engine request whose ``start_stream``
    call lies inside the client's own ``serve.start_stream`` span and
    has its prompt's length and its ``max_new``; a request with no
    such call or with more than one, one that failed, and one of whose
    spans the ring no longer holds are left out. Each row has the
    client's ``served``, the ``ident``, the ``start`` call, the
    engine's ``queue``, ``first`` and ``decode`` spans and ``poll``,
    the first ``next_tokens`` call that returned a token."""
    w0 = ctx["window"][0]
    records = program_spans.since(w0) or ()
    starts = [r for r in records if r[0] == CALL
              and r[5].get("method") == "start_stream"
              and r[3] is not None and "t_routed" in r[5]]
    queue, first, decode = (by_ident(records, "engine." + n)
                            for n in ("queue", "first_token", "decode"))
    polls: Dict[Any, Span] = {}
    for r in records:
        if (r[0] == CALL and r[5].get("method") == "next_tokens"
                and r[5].get("tokens") and (
                    r[3] not in polls or r[1] < polls[r[3]][1])):
            polls[r[3]] = r
    client = sorted((t0, t1) for n, t0, t1 in list(ctx["recorder"].spans)
                    if n == "serve.start_stream")
    begins = [t0 for t0, _ in client]
    wanted = [s for s in ctx["served"] if s.req["id"] >= 0]
    rows = []
    for s in wanted:
        i = bisect.bisect_left(begins, s.t_sent)
        if not s.ok or i == len(client):
            continue
        c0, c1 = client[i]
        mine = [r for r in starts if c0 <= r[1] and r[2] <= c1
                and r[5].get("prompt_tokens") == len(s.req["prompt"])
                and r[5].get("max_new") == s.req["max_new"]]
        if len(mine) != 1:
            continue
        ident = mine[0][3]
        if all(ident in d for d in (queue, first, decode, polls)):
            rows.append({"served": s, "ident": ident, "start": mine[0],
                         "queue": queue[ident], "first": first[ident],
                         "decode": decode[ident], "poll": polls[ident]})
    return rows, len(wanted)


def pieces_ms(row: Dict[str, Any]) -> Dict[str, float]:
    """A client's TTFT cut at the program's own marks, each piece from
    where the one before ends, so that they add up to first token
    received minus DUE: due, sent, the router's hand-over, the method's
    start on a replica thread, ``submit_stream`` (where ``engine.queue``
    begins), admission to a slot, the first hand-out (where
    ``engine.first_token`` ends: the stamp ``held_ms`` counts from),
    the return of the poll that took it, and the client's own clock
    after the actor call gave it the frame."""
    s, start = row["served"], row["start"]
    marks = (s.t_due, s.t_sent, start[5]["t_routed"], start[1],
             row["queue"][1], row["queue"][2], row["first"][2],
             row["poll"][2], s.t_first)
    return {name: ms(b - a)
            for name, a, b in zip(PIECES, marks, marks[1:])}


def say_request_path(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``[request_path]``: how many of the window's requests were
    joined, then a line a piece of their TTFT with its p50 and p90,
    and the smallest remainder (``return_path``: what is left of a
    client's TTFT after the program's pieces; never negative on one
    clock). Returns what it printed, None where nothing joins."""
    rows, wanted = joined(ctx)
    if not rows:
        return None
    cut = [pieces_ms(r) for r in rows]
    out = {"joined": len(rows), "of": wanted, "pieces": {
        name: quantiles([c[name] for c in cut]) for name in PIECES}}
    out["return_path_min_ms"] = min(c["return_path"] for c in cut)
    out["ttft_p50_ms"] = statistics.median(sum(c.values()) for c in cut)
    say("request_path", joined=f"{len(rows)}/{wanted}",
        ttft_p50_ms=out["ttft_p50_ms"],
        return_path_min_ms=out["return_path_min_ms"])
    for name in PIECES:
        say("request_path", piece=name,
            p50_ms=out["pieces"][name]["p50"],
            p90_ms=out["pieces"][name]["p90"],
            max_ms=out["pieces"][name]["max"])
    return out


def loop_gaps(ctx: Dict[str, Any]) -> List[Tuple[float, int]]:
    """(milliseconds, how many ``replica.call`` spans ended inside it)
    for every stretch of the window between one round's
    ``engine.deliver`` and the next round's ``engine.admit`` in which
    the engine had live slots: the round's ``live_slots`` less the
    requests whose ``engine.decode`` ended in its delivery."""
    w0, w1 = ctx["window"]
    records = program_spans.since(w0) or ()
    rounds = [b for b in program_spans.bursts(records)
              if "engine.admit" in b]
    finished = sorted(r[2] for r in records if r[0] == "engine.decode")
    returned = sorted(r[2] for r in records if r[0] == CALL)

    def between(ts: List[float], a: float, b: float) -> int:
        return bisect.bisect_right(ts, b) - bisect.bisect_left(ts, a)

    out = []
    for prev, nxt in zip(rounds, rounds[1:]):
        if not program_spans.whole(prev) or not w0 <= prev[
                "engine.admit"][1] < w1:
            continue
        deliver = prev["engine.deliver"]
        live = prev["engine.dispatch"][5]["live_slots"] - between(
            finished, deliver[1], deliver[2])
        if live > 0:
            a, b = deliver[2], nxt["engine.admit"][1]
            out.append((ms(b - a), between(returned, a, b)))
    return out


def say_loop_gap(ctx: Dict[str, Any]) -> None:
    """``[loop_gap]``: a line for the gaps in which no replica call
    returned, one for those with up to the median number of returns
    and one for those with more, each with its share of all the gaps'
    time."""
    gaps = loop_gaps(ctx)
    if not gaps:
        return
    some = [n for _, n in gaps if n]
    middle = int(statistics.median(some)) if some else 0
    for label, lo, hi in (
            ("no_replica_call_returned", 0, 0),
            (f"1_to_{middle}_calls_returned", 1, middle),
            (f"over_{middle}_calls_returned", middle + 1, 1 << 30)):
        mine = [g for g, n in gaps if lo <= n <= hi]
        if mine:
            say("loop_gap", during=label, n=len(mine),
                share_of_gap_time=sum(mine) / sum(g for g, _ in gaps),
                **{f"{k}_ms": v for k, v in quantiles(mine).items()})


def tpot_pair(seconds: float, tokens: int, first: int
              ) -> Tuple[float, float]:
    """(TPOT, TPOT with the first hand-out left out) in ms of a request
    whose ``tokens`` (more than one) took ``seconds`` from the first
    hand-out to the last, ``first`` of them in the first hand-out: the
    second leaves those tokens out of the count as the time leaves
    their making out (not a number where all came at once)."""
    later = tokens - first
    return (ms(seconds) / (tokens - 1),
            ms(seconds) / later if later > 0 else float("nan"))


def engine_tpots(ctx: Dict[str, Any]) -> Dict[Any, Tuple[float, float]]:
    """ident -> ``tpot_pair`` as the ENGINE gave the tokens, for the
    requests whose ``engine.queue`` began in the window and whose
    ``engine.decode`` says how many tokens it covers: the span runs
    from the first hand-out to the last, so it is the client's (last -
    first) taken where the tokens are made."""
    records = program_spans.since(ctx["window"][0]) or ()
    began = {r[3] for r in program_spans.started_in(
        records, "engine.queue", ctx["window"])}
    first = by_ident(records, "engine.first_token")
    return {r[3]: tpot_pair(r[2] - r[1], r[5]["tokens"],
                            first[r[3]][5]["tokens"])
            for r in records if r[0] == "engine.decode"
            and r[3] in began and r[3] in first
            and r[5].get("tokens", 0) > 1}


def client_tpots(ctx: Dict[str, Any]) -> List[Tuple[float, float]]:
    """The same on the clients' clocks, for the window's requests that
    ended well with more than one token."""
    return [tpot_pair(s.t_last - s.t_first, len(s.tokens), s.frames[0][1])
            for s in ctx["served"]
            if s.req["id"] >= 0 and s.ok and len(s.tokens) > 1]


def say_tpot_sides(ctx: Dict[str, Any]) -> None:
    """``[tpot_sides]``: p50, p90 and max of TPOT on the clients' side
    and on the engine's, then of both with each request's first
    hand-out left out of the time and of the count."""
    sides = (("client", client_tpots(ctx)),
             ("engine", list(engine_tpots(ctx).values())))
    for i, reading in enumerate(("tpot", "tpot_after_first_handout")):
        fields: Dict[str, Any] = {}
        for side, pairs in sides:
            values = [p[i] for p in pairs if p[i] == p[i]]
            if values:
                fields[f"{side}_n"] = len(values)
                fields.update({f"{side}_{k}_ms": v
                               for k, v in quantiles(values).items()})
        if fields:
            say("tpot_sides", reading=reading, **fields)
