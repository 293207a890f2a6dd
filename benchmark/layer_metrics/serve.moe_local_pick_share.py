"""Share of the routed experts' picks that fell on an expert held on
this chip and were computed here, over the measured window:
``moe_picks_local`` over ``moe_picks_total`` of the engine's records
that began in the window (``benchmark/moe_picks.py``). A check on the
cut more than a goal: with 40 of 320 experts held and routing near
uniform it reads 12.5 %; what it reads times the picks is the expert
layer's work here."""
from benchmark import moe_picks


def read(ctx):
    fields = moe_picks.in_window(ctx)
    total = sum(f["moe_picks_total"] for f in fields or ())
    if not total:
        return None
    return 100.0 * sum(f["moe_picks_local"] for f in fields) / total
