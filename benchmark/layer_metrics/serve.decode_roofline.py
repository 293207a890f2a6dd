"""The decode programs' share of their roofline in the traced window:
the least time the chip could take for the steps they ran, over the
device time of the ``jit_engine_decode_n<steps>`` executions (the
trace's ``XLA Modules`` line; steps from the name).

Least time of ONE step (benchmark/flops.py): every weight read once at
the served type's bytes, plus K and V of every token that the burst's
live slots held when it began (``live_ctx_tokens`` of the
``engine.dispatch`` span the run lies under, found through
``program_spans.traced_bursts``), over the chip's memory bandwidth;
against 2 x N FLOPs for each live slot over the peak. Memory-bound at
these shapes: the weights alone are 8.85 ms a step. Left out, because
no answer needs them: the tokens a burst appends while it runs (under
1 % of the bytes), idle slots' rows, the rewrite of the page pool by
``append_token_kv`` and the gather of pages."""
import jax.numpy as jnp

from benchmark import program_spans, trace_reduce
from benchmark.common import say


def read(ctx):
    cell = ctx["cell"]
    bursts = program_spans.traced_bursts(ctx)
    if not bursts or cell.peaks is None:
        return None
    flops = ctx["flops"]
    run = cell.config["run"]
    sizes = {"weight_bytes": jnp.dtype(run["param_dtype"]).itemsize,
             "kv_bytes": jnp.dtype(run["dtype"]).itemsize}
    least = device = 0.0
    bound = set()
    for b in bursts:
        f = b["fields"]
        step = flops.roofline_seconds(
            flops.forward_flops(cell.config, f["live_slots"]),
            flops.decode_step_bytes(cell.config, [f["live_ctx_tokens"]],
                                    **sizes),
            cell.peaks)
        bound.add(step["bound"])
        least += step["seconds"] * f["steps"]
        device += sum(e - s for _, s, e in b["runs"]) / 1e9
    if device <= 0.0:
        return None
    say("decode_roofline", least_s=least, device_s=device,
        steps=sum(b["fields"]["steps"] for b in bursts),
        bound=sorted(bound))
    return 100.0 * trace_reduce.share(least, device,
                                      "decode roofline share")
