"""Share of the traced window in which no operation ran on the device,
on the idlest device of the cell."""


def read(ctx):
    s = ctx.get("trace_summary")
    if not s:
        return None
    return 100.0 * (1.0 - s["busy_s_min"] / s["window_s"])
