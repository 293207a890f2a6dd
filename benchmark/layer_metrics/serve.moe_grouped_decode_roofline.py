"""The held experts' grouped products' share of their roofline in the
traced window's DECODE steps: the least time the chip could take for
what the steps' expert work requires, over the device time of the
``moe_grouped*`` operations (the Pallas call of ``ops/moe.py::
grouped_matmul`` under that name, three an expert layer and step)
inside the ``jit_engine_decode_n<steps>`` executions.

What a burst requires, from its records: each held expert that got a
pick in a step and layer has its three matrices read once,
``moe_experts_touched`` of the burst's ``engine.dispatch`` (summed over
its steps and expert layers: the count that
``serve.moe_grouped_prefill_roofline`` could not have, since a burst's
``moe_load_by_expert`` is summed over its steps); each local pick's
rows go in and out once and take its 6 x d x f FLOPs,
``moe_picks_local`` of the burst's ``engine.deliver``. The
configuration's FLOP module counts them (``grouped_decode``); the least
time is memory-bound at the peaks' HBM rate. Idle slots' rows, the
sorted copies of the picks and tiles shared by two experts are the
program's own cost.

Reads nothing where the program counts no touched experts (a commit
from before the count), runs no such operation, or the cell's FLOP
module has no ``grouped_decode``."""
import bisect
import importlib

from benchmark import program_spans, trace_reduce
from benchmark.common import say
from benchmark.flops import roofline_seconds

KERNEL = "moe_grouped"


def read(ctx):
    cell = ctx["cell"]
    summary = ctx.get("trace_summary")
    bursts = program_spans.traced_bursts(ctx)
    if not bursts or cell.peaks is None or "flops" not in cell.config:
        return None
    module = importlib.import_module("benchmark." + cell.config["flops"])
    if not hasattr(module, "grouped_decode"):
        return None
    to_ns = program_spans.clock_map(summary)
    delivers = {round(to_ns(r[1])): r[5]
                for r in program_spans.since(summary["t0"])
                if r[0] == "engine.deliver"}
    kernels = sorted(
        (s, s + d) for name, s, d in trace_reduce.line_events(
            program_spans.idlest_plane(summary), trace_reduce.OPS_LINE)
        if name.startswith(KERNEL))
    starts = [k0 for k0, _ in kernels]

    def inside(s, e):
        """Device seconds of the kernels that lie within [s, e]."""
        first = bisect.bisect_left(starts, s)
        return sum(k1 - k0 for k0, k1 in kernels[
            first:bisect.bisect_right(starts, e)] if k1 <= e) / 1e9

    least = kernel_s = 0.0
    touched = picks = steps = matched = 0
    for b in bursts:
        dispatch = b["fields"]
        deliver = delivers.get(round(b["spans"]["engine.deliver"][0]), {})
        if ("moe_experts_touched" not in dispatch
                or "moe_picks_local" not in deliver):
            continue
        need = module.grouped_decode(cell.config,
                                     dispatch["moe_experts_touched"],
                                     deliver["moe_picks_local"])
        least += roofline_seconds(need["flops"], need["bytes"],
                                  cell.peaks)["seconds"]
        kernel_s += sum(inside(s, e) for _, s, e in b["runs"])
        touched += dispatch["moe_experts_touched"]
        picks += deliver["moe_picks_local"]
        steps += dispatch["steps"]
        matched += 1
    if kernel_s <= 0.0:
        return None
    dims = module.dims(cell.config)
    say("moe_grouped_decode_roofline", least_s=least, kernel_s=kernel_s,
        bursts=matched, steps=steps, experts_touched=touched,
        local_picks=picks, touched_per_step_and_layer=touched / steps / (
            dims["layers"] - dims.get("dense", 0)))
    return 100.0 * trace_reduce.share(
        least, kernel_s, "held experts' grouped decode products roofline "
        "share")
