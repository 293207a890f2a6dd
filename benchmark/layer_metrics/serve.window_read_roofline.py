"""The windowed paged read's share of its roofline in the traced window:
the least time the chip could take for the window layers' attention of
the steps the traced decode programs ran (the configuration's FLOP
module, ``window_read``: K and V of each live slot's ``min(context,
window)`` tokens read once in every window layer, every head's two
products over them, the queries in and the result out;
``live_window_tokens`` and ``live_slots`` of the burst's
``engine.dispatch``, the tokens a burst appends while it runs left
out), over the device time of the ``paged_window_read*`` operations
inside those programs' executions (the Pallas call of
``ops/paged_attention.py`` keeps that name in the HLO). The required
work is the WINDOW's: a program that fetched pages outside it would
read low. Reads nothing where the program has no such operation, its
bursts no such field, or the FLOP module no such count."""
import jax.numpy as jnp

from benchmark import program_spans, trace_reduce
from benchmark.common import say


def read(ctx):
    cell, flops = ctx["cell"], ctx["flops"]
    summary = ctx.get("trace_summary")
    bursts = program_spans.traced_bursts(ctx)
    if (not bursts or cell.peaks is None
            or not hasattr(flops, "window_read")
            or any("live_window_tokens" not in b["fields"] for b in bursts)):
        return None
    kv_bytes = jnp.dtype(cell.config["run"]["dtype"]).itemsize
    runs = sorted((s, e) for b in bursts for _, s, e in b["runs"])
    kernel_s, calls = 0.0, 0
    for name, start, dur in trace_reduce.line_events(
            program_spans.idlest_plane(summary), trace_reduce.OPS_LINE):
        if name.startswith("paged_window_read") and any(
                s <= start and start + dur <= e for s, e in runs):
            kernel_s += dur / 1e9
            calls += 1
    if kernel_s <= 0.0:
        return None
    least = 0.0
    bound = set()
    for b in bursts:
        f = b["fields"]
        need = flops.window_read(cell.config, f["live_window_tokens"],
                                 f["live_slots"], kv_bytes)
        step = flops.roofline_seconds(need["flops"], need["bytes"],
                                      cell.peaks)
        bound.add(step["bound"])
        least += step["seconds"] * f["steps"]
    say("window_read_roofline", least_s=least, kernel_s=kernel_s,
        calls=calls, steps=sum(b["fields"]["steps"] for b in bursts),
        live_window_tokens=sum(b["fields"]["live_window_tokens"]
                               * b["fields"]["steps"] for b in bursts),
        live_ctx_tokens=sum(b["fields"]["live_ctx_tokens"]
                            * b["fields"]["steps"] for b in bursts),
        bound=sorted(bound))
    return 100.0 * trace_reduce.share(least, kernel_s,
                                      "window read roofline share")
