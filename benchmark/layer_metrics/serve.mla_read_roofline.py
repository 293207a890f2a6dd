"""The paged latent read's share of its roofline in the traced window:
the least time the chip could take for the absorbed attention of the
steps the traced decode programs ran (the configuration's FLOP module,
``latent_read``: every head against each live token's cached row, the
row read once, all layers; ``live_ctx_tokens`` and ``live_slots`` of
the burst's ``engine.dispatch``, the tokens a burst appends while it
runs left out), over the device time of the ``mla_paged_read*``
operations inside those programs' executions (the Pallas call of
``ops/paged_attention.py`` keeps that name in the HLO). Reads nothing
where the program has no such operation or the FLOP module no such
count."""
import jax.numpy as jnp

from benchmark import program_spans, trace_reduce
from benchmark.common import say


def read(ctx):
    cell, flops = ctx["cell"], ctx["flops"]
    summary = ctx.get("trace_summary")
    bursts = program_spans.traced_bursts(ctx)
    if (not bursts or cell.peaks is None
            or not hasattr(flops, "latent_read")):
        return None
    kv_bytes = jnp.dtype(cell.config["run"]["dtype"]).itemsize
    runs = sorted((s, e) for b in bursts for _, s, e in b["runs"])
    kernel_s, calls = 0.0, 0
    for name, start, dur in trace_reduce.line_events(
            program_spans.idlest_plane(summary), trace_reduce.OPS_LINE):
        if name.startswith("mla_paged_read") and any(
                s <= start and start + dur <= e for s, e in runs):
            kernel_s += dur / 1e9
            calls += 1
    if kernel_s <= 0.0:
        return None
    least = 0.0
    bound = set()
    for b in bursts:
        f = b["fields"]
        need = flops.latent_read(cell.config, f["live_ctx_tokens"],
                                 f["live_slots"], kv_bytes)
        step = flops.roofline_seconds(need["flops"], need["bytes"],
                                      cell.peaks)
        bound.add(step["bound"])
        least += step["seconds"] * f["steps"]
    say("mla_read_roofline", least_s=least, kernel_s=kernel_s, calls=calls,
        steps=sum(b["fields"]["steps"] for b in bursts),
        bound=sorted(bound))
    return 100.0 * trace_reduce.share(least, kernel_s,
                                      "latent read roofline share")
