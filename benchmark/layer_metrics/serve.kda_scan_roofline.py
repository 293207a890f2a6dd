"""The delta rule's chunk-scan kernel's share of its roofline in the
traced window: the least time the chip could take for what the
recurrence over the launches' PROMPTS requires in the delta-rule layers
(the configuration's FLOP module, ``recurrence_flops_per_token``: decay,
k^T S, the rank-1 update and S^T q a head and position; against q, k,
g, v, beta read and the output written once a position in float32, and
a head's state read and written once a segment of the program's
``_DELTA_RULE_SEGMENT`` positions), over the device time of the
``kda_chunk_scan*`` operations (the Pallas call of ``ops/kda.py`` under
that name) inside the ``jit_engine_prefill_b<bucket>`` executions.
Padding to the bucket is the program's own cost.

A launch is matched to its execution through its burst's FETCH, as
``serve.window_prefill_attn_roofline`` matches it: the launches
recorded inside a burst's ``engine.admit`` are the prefill runs whose
middle lies after the previous burst's ``engine.fetch`` ended and
before this burst's ends. A burst whose launches and runs differ in
number or bucket is left out. Reads nothing where the program runs no
such operation (a program whose scan is not that kernel) or the FLOP
module has no such count."""
import importlib

from benchmark import program_spans, trace_reduce
from benchmark.common import say

KERNEL = "kda_chunk_scan"


def required(module, config, prompt_lens):
    """FLOPs and float32 HBM bytes of the recurrence over these prompts
    in every delta-rule layer."""
    from ray_tpu.models.decoder_forward import _DELTA_RULE_SEGMENT

    s = module.dims(config)
    layers = s["layers"] - len(s["gqa"])
    h, d = s["dr_h"], s["dr_d"]
    tokens = sum(prompt_lens)
    segments = sum(-(-n // _DELTA_RULE_SEGMENT) for n in prompt_lens)
    return {"flops": module.recurrence_flops_per_token(config) * tokens,
            "bytes": 4.0 * layers * h * ((5 * d + 1) * tokens
                                         + 2 * d * d * segments)}


def read(ctx):
    cell = ctx["cell"]
    summary = ctx.get("trace_summary")
    bursts = program_spans.traced_bursts(ctx)
    if not bursts or cell.peaks is None or "flops" not in cell.config:
        return None
    module = importlib.import_module("benchmark." + cell.config["flops"])
    if not hasattr(module, "recurrence_flops_per_token"):
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
            need = required(module, cell.config, f["prompt_lens"])
            least += module.roofline_seconds(need["flops"], need["bytes"],
                                             cell.peaks)["seconds"]
            kernel_s += sum(k1 - k0 for k0, k1 in kernels
                            if s <= k0 and k1 <= e) / 1e9
        matched += len(mine)
    if kernel_s <= 0.0:
        return None
    say("kda_scan_roofline", least_s=least, kernel_s=kernel_s,
        launches=matched, launches_in_traced_bursts=seen)
    return 100.0 * trace_reduce.share(least, kernel_s,
                                      "delta-rule chunk scan roofline share")
