"""What the window layers hold of what they would hold uncut, over the
bursts that began in the measured window: the pages of a window layer's
rings that hold the live slots' tokens (``window_pages_live`` of
``engine.dispatch``: ``min(pages of the context, ring)`` a slot) over
the pages the same slots own under the engine's page table
(``live_pages``: what every layer cached whole holds for them, the
answer's reservation included), each weighted by the burst's steps. A
check on the mechanism, not a goal: under 100 % the cache did stop
growing, and the lower it reads the longer the contexts are against the
window. ``[engine_counters]`` prints the ring's whole-life sums of the
live context, whole and clipped to the window, beside the engine's
cumulative counters of the same names. Reads nothing where the
program's bursts carry no such field."""
from benchmark import program_spans


def read(ctx):
    records = program_spans.since(float("-inf"))
    bursts = [r[5] for r in records or ()
              if r[0] == "engine.dispatch" and "window_pages_live" in r[5]
              and "steps" in r[5]]
    if not bursts:
        return None
    program_spans.say_counters(ctx, {
        "decode_ctx_tokens_live": sum(
            f["live_ctx_tokens"] * f["steps"] for f in bursts),
        "decode_window_tokens_live": sum(
            f["live_window_tokens"] * f["steps"] for f in bursts)})
    window = [r[5] for r in program_spans.started_in(
        records, "engine.dispatch", ctx["window"])
        if "window_pages_live" in r[5] and "steps" in r[5]]
    uncut = sum(f["live_pages"] * f["steps"] for f in window)
    if not uncut:
        return None
    return 100.0 * sum(f["window_pages_live"] * f["steps"]
                       for f in window) / uncut
