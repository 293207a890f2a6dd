"""The prefill attention kernel's share of its roofline in the traced
window: the least time the chip could take for what expanded causal
attention over the launches' PROMPTS requires (the configuration's FLOP
module, ``latent_prefill_attention``: two products a head over each
prompt's causal half at the true widths, all layers), over the device
time of the attention kernel's operations inside the
``jit_engine_prefill_b<bucket>`` executions: ``mla_prefill_attention*``
(ops/mla_prefill.py's Pallas call) or, should the program run the
library's kernel on padded operands, ``flash_attention*``. Padding to
the bucket and dummy rows are the program's own cost. Launches and
executions are matched through their bursts as
``serve.hybrid_prefill_roofline`` matches them. Reads nothing where the
program runs no such operation or the FLOP module has no such count."""
from benchmark import program_spans, trace_reduce
from benchmark.common import say

KERNELS = ("mla_prefill_attention", "flash_attention")


def read(ctx):
    cell, flops = ctx["cell"], ctx["flops"]
    summary = ctx.get("trace_summary")
    bursts = program_spans.traced_bursts(ctx)
    if (not bursts or cell.peaks is None
            or not hasattr(flops, "latent_prefill_attention")):
        return None
    to_ns = program_spans.clock_map(summary)
    plane = program_spans.idlest_plane(summary)
    launches = sorted(
        (to_ns(r[1]), r[5]) for r in program_spans.since(summary["t0"])
        if r[0] == "engine.prefill_launch")
    runs = [(int(m.group(1)), s, e) for name, s, e in
            trace_reduce.module_runs(plane, summary["window"])
            for m in [program_spans.PREFILL_RUN.match(name)] if m]
    kernels = sorted((s, s + d) for name, s, d in trace_reduce.line_events(
        plane, trace_reduce.OPS_LINE) if name.startswith(KERNELS))
    least = kernel_s = 0.0
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
            need = flops.latent_prefill_attention(cell.config,
                                                  f["prompt_lens"])
            least += flops.roofline_seconds(need["flops"], need["bytes"],
                                            cell.peaks)["seconds"]
            kernel_s += sum(k1 - k0 for k0, k1 in kernels
                            if s <= k0 and k1 <= e) / 1e9
        matched += len(mine)
    if kernel_s <= 0.0:
        return None
    say("mla_prefill_attn_roofline", least_s=least, kernel_s=kernel_s,
        launches=matched)
    return 100.0 * trace_reduce.share(least, kernel_s,
                                      "latent prefill attention roofline "
                                      "share")
