"""The delta rule's decode kernel's share of its roofline in the traced
window: the least time the chip could take for the recurrence of the
steps the traced decode programs ran in the delta-rule layers (the
configuration's FLOP module: ``recurrence_flops_per_token`` a live slot
and step, against each live slot's ``[dr_d, dr_d]`` state of ``dr_h``
heads read and written once a step and layer at the configuration's
``state_dtype``; ``live_slots`` and ``steps`` of the burst's
``engine.dispatch``; memory-bound), over the device time of the
``kda_decode_step*`` operations (the Pallas call of ``ops/kda.py``
under that name) inside those programs' executions. q, k, v, g, beta
and the output (a fiftieth of the state's bytes at the cell's widths)
are left out, and so are the idle slots, whose state no answer needs.
Reads nothing where the program has no such operation (a step that
takes every slot's state through XLA, as before PR 36) or the FLOP
module has no such count."""
import importlib

import jax.numpy as jnp

from benchmark import program_spans, trace_reduce
from benchmark.common import say

KERNEL = "kda_decode_step"


def required(module, config, live_slots, state_bytes):
    """FLOPs and HBM bytes of one decode step's recurrence over the live
    slots in every delta-rule layer."""
    s = module.dims(config)
    layers = s["layers"] - len(s["gqa"])
    state = layers * s["dr_h"] * s["dr_d"] * s["dr_d"] * state_bytes
    return {"flops": module.recurrence_flops_per_token(config) * live_slots,
            "bytes": 2.0 * state * live_slots}


def read(ctx):
    cell = ctx["cell"]
    summary = ctx.get("trace_summary")
    bursts = program_spans.traced_bursts(ctx)
    if not bursts or cell.peaks is None or "flops" not in cell.config:
        return None
    module = importlib.import_module("benchmark." + cell.config["flops"])
    if not hasattr(module, "recurrence_flops_per_token"):
        return None
    state_bytes = jnp.dtype(cell.config["run"]["state_dtype"]).itemsize
    runs = sorted((s, e) for b in bursts for _, s, e in b["runs"])
    kernel_s, calls = 0.0, 0
    for name, start, dur in trace_reduce.line_events(
            program_spans.idlest_plane(summary), trace_reduce.OPS_LINE):
        if name.startswith(KERNEL) and any(
                s <= start and start + dur <= e for s, e in runs):
            kernel_s += dur / 1e9
            calls += 1
    if kernel_s <= 0.0:
        return None
    least = 0.0
    bound = set()
    for b in bursts:
        f = b["fields"]
        need = required(module, cell.config, f["live_slots"], state_bytes)
        step = module.roofline_seconds(need["flops"], need["bytes"],
                                       cell.peaks)
        bound.add(step["bound"])
        least += step["seconds"] * f["steps"]
    say("kda_decode_roofline", least_s=least, kernel_s=kernel_s,
        calls=calls, steps=sum(b["fields"]["steps"] for b in bursts),
        live_slot_steps=sum(b["fields"]["live_slots"] * b["fields"]["steps"]
                            for b in bursts),
        bound=sorted(bound))
    return 100.0 * trace_reduce.share(least, kernel_s,
                                      "delta-rule decode roofline share")
