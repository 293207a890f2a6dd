"""How unevenly the held experts were loaded over the measured window:
the busiest held expert's picks over the mean of the held experts'
(1 is even), from ``moe_load_by_expert`` of the same records as
``serve.moe_local_pick_share``. The grouped products take as long as
their rows are many, so an uneven load costs nothing on one chip; in
the deployment the busiest chip's experts set the exchange's pace."""
import numpy as np

from benchmark import moe_picks


def read(ctx):
    fields = moe_picks.in_window(ctx)
    if not fields:
        return None
    load = np.sum([f["moe_load_by_expert"] for f in fields], axis=0)
    if not load.sum():
        return None
    return float(load.max() / load.mean())
