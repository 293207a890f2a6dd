"""The held experts' grouped products' share of their roofline in the
traced window's prefill launches: the least time the chip could take
for what the launches' LOCAL picks require, over the device time of the
``moe_grouped*`` operations (the Pallas call of ``ops/moe.py::
grouped_matmul`` under that name, three an expert layer and block of a
prompt's tokens) inside the ``jit_engine_prefill_b<bucket>``
executions.

What a launch requires, from its ``engine.prefill_launch`` record
(``moe_picks_local`` and ``moe_load_by_expert`` are summed over the
expert layers): 6 x d x f FLOPs a local pick (the gate, up and down
products of width f at d), against the matrices of the held experts the
launch touched, 3 x d x f at 2 B an expert and expert layer, read once,
and a local pick's rows in and out, 3 x (d + f) at 2 B. An expert with a
pick in the launch counts as touched in every expert layer: the
shortest prompt of each of the three cells puts 2 to 8 local picks on a
held expert of each layer on average, and short prompts share a launch,
so nearly every expert of the union is touched in every layer (where
one is not, the least time reads a little high). The rows of
non-local picks that the program gathers, blocks of a prompt that
re-read the touched matrices and padding to the bucket are the
program's own cost.

A launch is matched to its execution through its burst's FETCH, as
``serve.kda_scan_roofline`` matches it. A burst whose launches and runs
differ in number or bucket is left out, and so is one whose launches
carry no counts. Decode steps are left out: a burst's
``moe_load_by_expert`` is summed over its steps, so which experts one
step touched is not known. Reads nothing where the program runs no such
operation (grouped products through XLA's ragged dot) or the cell has
no FLOP module."""
import importlib

from benchmark import program_spans, trace_reduce
from benchmark.common import say
from benchmark.flops import roofline_seconds

KERNEL = "moe_grouped"


def required(dims, fields):
    """FLOPs and bytes of one launch's grouped products."""
    d, f = dims["d"], dims["fe"]
    layers = dims["layers"] - dims.get("dense", 0)
    picks = fields["moe_picks_local"]
    touched = sum(1 for n in fields["moe_load_by_expert"] if n)
    return {"flops": 6.0 * picks * d * f,
            "bytes": 2.0 * (3 * layers * touched * d * f
                            + 3 * picks * (d + f))}


def read(ctx):
    cell = ctx["cell"]
    summary = ctx.get("trace_summary")
    bursts = program_spans.traced_bursts(ctx)
    if not bursts or cell.peaks is None or "flops" not in cell.config:
        return None
    module = importlib.import_module("benchmark." + cell.config["flops"])
    if not hasattr(module, "dims"):
        return None
    dims = module.dims(cell.config)
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
                or any("moe_load_by_expert" not in f for f in mine)):
            continue
        for f, (_, s, e) in zip(mine, ran):
            need = required(dims, f)
            least += roofline_seconds(need["flops"], need["bytes"],
                                      cell.peaks)["seconds"]
            kernel_s += sum(k1 - k0 for k0, k1 in kernels
                            if s <= k0 and k1 <= e) / 1e9
        matched += len(mine)
    if kernel_s <= 0.0:
        return None
    say("moe_grouped_prefill_roofline", least_s=least, kernel_s=kernel_s,
        launches=matched, launches_in_traced_bursts=seen)
    return 100.0 * trace_reduce.share(
        least, kernel_s, "held experts' grouped products roofline share")
