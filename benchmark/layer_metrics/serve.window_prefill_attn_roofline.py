"""The window layers' prefill attention kernel's share of its roofline
in the traced window: the least time the chip could take for what
attention over the launches' PROMPTS requires in the window layers (the
configuration's FLOP module, ``window_prefill_attention``: two products
a head over each prompt's BAND, row ``i`` against its last ``min(i + 1,
window)`` keys), over the device time of the
``window_prefill_attention*`` operations (the Pallas call of
``ops/mla_prefill.py`` under that name) inside the
``jit_engine_prefill_b<bucket>`` executions. Padding to the bucket is
the program's own cost.

A launch is matched to its execution through its burst's FETCH: the
launches recorded inside a burst's ``engine.admit`` are the prefill
runs whose middle lies after the previous burst's ``engine.fetch``
ended (until then the host was blocked and had dispatched nothing of
this burst) and before this burst's ends. A run lasts tens of
milliseconds and the clock map is good to one, so no run falls to the
wrong side (matching by the admit span's START loses the runs that
begin within that millisecond of it: PERF.md section 7, PR 32 (b)). A
burst whose launches and runs differ in number or bucket is left out.
Reads nothing where the program runs no such operation or the FLOP
module has no such count."""
from benchmark import program_spans, trace_reduce
from benchmark.common import say

KERNEL = "window_prefill_attention"


def read(ctx):
    cell, flops = ctx["cell"], ctx["flops"]
    summary = ctx.get("trace_summary")
    bursts = program_spans.traced_bursts(ctx)
    if (not bursts or cell.peaks is None
            or not hasattr(flops, "window_prefill_attention")):
        return None
    to_ns = program_spans.clock_map(summary)
    plane = program_spans.idlest_plane(summary)
    records = program_spans.since(summary["t0"])
    launches = sorted((to_ns(r[1]), r[5]) for r in records
                      if r[0] == "engine.prefill_launch")
    fetch_ends = sorted(to_ns(r[2]) for r in records
                        if r[0] == "engine.fetch")
    runs = [(int(m.group(1)), s, e) for name, s, e in
            trace_reduce.module_runs(plane, summary["window"])
            for m in [program_spans.PREFILL_RUN.match(name)] if m]
    kernels = sorted((s, s + d) for name, s, d in trace_reduce.line_events(
        plane, trace_reduce.OPS_LINE) if name.startswith(KERNEL))
    least = kernel_s = 0.0
    matched = seen = 0
    for b in bursts:
        a0, a1 = b["spans"]["engine.admit"]
        f1 = b["spans"]["engine.fetch"][1]
        before = max((t for t in fetch_ends if t <= a0),
                     default=float("-inf"))
        mine = [f for t, f in launches if a0 <= t <= a1]
        ran = [r for r in runs if before < (r[1] + r[2]) / 2 <= f1]
        seen += len(mine)
        if (not mine or [f["bucket"] for f in mine] != [r[0] for r in ran]
                or any("prompt_lens" not in f for f in mine)):
            continue
        for f, (_, s, e) in zip(mine, ran):
            need = flops.window_prefill_attention(cell.config,
                                                  f["prompt_lens"])
            least += flops.roofline_seconds(need["flops"], need["bytes"],
                                            cell.peaks)["seconds"]
            kernel_s += sum(k1 - k0 for k0, k1 in kernels
                            if s <= k0 and k1 <= e) / 1e9
        matched += len(mine)
    if kernel_s <= 0.0:
        return None
    say("window_prefill_attn_roofline", least_s=least, kernel_s=kernel_s,
        launches=matched, launches_in_traced_bursts=seen)
    return 100.0 * trace_reduce.share(least, kernel_s,
                                      "window prefill attention roofline "
                                      "share")
