"""``serve.decode_roofline`` for a model whose decode step is not a
dense decoder's: the least time the chip could take for the steps the
``jit_engine_decode_n<steps>`` programs ran in the traced window, over
their device time.

Least time of ONE step (the configuration's FLOP module,
``decode_step_bytes``): the weights outside the routed experts once,
the held experts that the live slots' counted local picks touch once,
each live slot's recurrent state read and written, K and V of the live
context in the layers that keep them (``live_slots``,
``live_ctx_tokens`` of the burst's ``engine.dispatch``), over the
chip's memory bandwidth; against ``decode_step_flops`` over the peak.
Left out, because no answer needs them: idle slots' rows, the rewrite
of the page pool by ``append_token_kv``, the sorted copies of the
picks."""
import jax.numpy as jnp

from benchmark import program_spans, trace_reduce
from benchmark.common import say


def read(ctx):
    cell, flops = ctx["cell"], ctx["flops"]
    bursts = program_spans.traced_bursts(ctx)
    if (not bursts or cell.peaks is None
            or not hasattr(flops, "decode_step_flops")):
        return None
    run = cell.config["run"]
    sizes = {"weight_bytes": jnp.dtype(run["param_dtype"]).itemsize,
             "kv_bytes": jnp.dtype(run["dtype"]).itemsize,
             "state_bytes": jnp.dtype(run["state_dtype"]).itemsize}
    least = device = 0.0
    bound = set()
    for b in bursts:
        f = b["fields"]
        step = flops.roofline_seconds(
            flops.decode_step_flops(cell.config, f["live_slots"],
                                    f["live_ctx_tokens"]),
            flops.decode_step_bytes(cell.config, f["live_slots"],
                                    f["live_ctx_tokens"], **sizes),
            cell.peaks)
        bound.add(step["bound"])
        least += step["seconds"] * f["steps"]
        device += sum(e - s for _, s, e in b["runs"]) / 1e9
    if device <= 0.0:
        return None
    say("hybrid_decode_roofline", least_s=least, device_s=device,
        steps=sum(b["fields"]["steps"] for b in bursts),
        bound=sorted(bound))
    return 100.0 * trace_reduce.share(least, device,
                                      "hybrid decode roofline share")
