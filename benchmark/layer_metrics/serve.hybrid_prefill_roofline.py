"""The prefill programs' share of their roofline in the traced window:
the least time the chip could take for what the launches' PROMPTS
require (the configuration's FLOP module, ``prefill_flops``: active
parameters a prompt token, the head once a prompt, causal attention
over each prompt, the recurrence; against every held weight read once
a launch), over the device time of the ``jit_engine_prefill_b<bucket>``
executions. Padding to the bucket, dummy rows and the sorted copies of
the picks are the program's own cost and do not count.

A launch is matched to its execution through its burst: the launches
recorded inside a burst's ``engine.admit`` run on the device between
that span's start and the end of the burst's ``engine.fetch``
(``program_spans.traced_bursts`` maps both onto the trace's clock). A
burst whose launches and executions differ in number or bucket is left
out."""
import jax.numpy as jnp

from benchmark import program_spans, trace_reduce
from benchmark.common import say


def read(ctx):
    cell, flops = ctx["cell"], ctx["flops"]
    summary = ctx.get("trace_summary")
    bursts = program_spans.traced_bursts(ctx)
    if (not bursts or cell.peaks is None
            or not hasattr(flops, "prefill_flops")):
        return None
    to_ns = program_spans.clock_map(summary)
    launches = sorted(
        (to_ns(r[1]), r[5]) for r in program_spans.since(summary["t0"])
        if r[0] == "engine.prefill_launch")
    runs = [(int(m.group(1)), s, e) for name, s, e in
            trace_reduce.module_runs(program_spans.idlest_plane(summary),
                                     summary["window"])
            for m in [program_spans.PREFILL_RUN.match(name)] if m]
    weight = jnp.dtype(cell.config["run"]["param_dtype"]).itemsize
    least = device = 0.0
    matched = 0
    for b in bursts:
        a0, a1 = b["spans"]["engine.admit"]
        mine = [f for t, f in launches if a0 <= t <= a1]
        ran = [r for r in runs
               if a0 <= r[1] and r[2] <= b["spans"]["engine.fetch"][1]]
        if (not mine or [f["bucket"] for f in mine] != [r[0] for r in ran]
                or any("prompt_lens" not in f for f in mine)):
            continue
        for f, (_, s, e) in zip(mine, ran):
            least += flops.roofline_seconds(
                flops.prefill_flops(cell.config, f["prompt_lens"]),
                weight * flops.param_count(cell.config),
                cell.peaks)["seconds"]
            device += (e - s) / 1e9
        matched += len(mine)
    if device <= 0.0:
        return None
    say("hybrid_prefill_roofline", least_s=least, device_s=device,
        launches=matched)
    return 100.0 * trace_reduce.share(least, device,
                                      "hybrid prefill roofline share")
