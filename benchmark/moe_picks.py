"""The engine's counted picks of routed experts, for the two readers
under ``layer_metrics/`` that share them: the ``engine.prefill_launch``
records (a prompt's tokens) and ``engine.deliver`` records (the decode
steps of live slots) that carry ``moe_picks_total``, ``moe_picks_local``
and ``moe_load_by_expert``."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark import program_spans

RECORDS = ("engine.prefill_launch", "engine.deliver")


def in_window(ctx: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """[fields] of the measured window's records that carry picks; None
    where the program counts none. ``[engine_counters]`` prints the
    ring's whole-life sums beside the engine's cumulative counters of
    the same names."""
    records = program_spans.since(float("-inf"))
    counted = [r for r in records or ()
               if r[0] in RECORDS and "moe_picks_total" in r[5]]
    if not counted:
        return None
    program_spans.say_counters(ctx, {
        k: sum(r[5][k] for r in counted)
        for k in ("moe_picks_total", "moe_picks_local")})
    t0, t1 = ctx["window"]
    return [r[5] for r in counted if t0 <= r[1] < t1]
