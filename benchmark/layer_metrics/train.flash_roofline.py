"""The attention kernel's share of its roofline in the train step: the
least time the chip could take for the FLOPs and bytes that causal
attention REQUIRES in the traced steps (benchmark/flops.flash_fwd_bwd:
forward once, backward with one recomputation of the scores; the
forward pass that remat runs again does not count), over the device
time of every ``flash_attention*`` / ``flash_mha_bwd*`` operation in
the trace (ops/flash.py's Pallas calls keep those names in the HLO).
Compute-bound at these shapes. Reads nothing where the step holds no
such operation (under a mesh the kernel is off)."""
from benchmark import trace_reduce


def read(ctx):
    s, cell = ctx.get("trace_summary"), ctx["cell"]
    if not s or cell.peaks is None:
        return None
    plane, window = s["planes"][0], s["window"]
    steps = [m for m in trace_reduce.module_runs(plane, window)
             if m[0].startswith("jit_train_step")]
    if not steps:
        return None
    kernel_s = 0.0
    for name, start, dur in trace_reduce.line_events(
            plane, trace_reduce.OPS_LINE):
        if name.startswith(("flash_attention", "flash_mha_bwd")) and any(
                a <= start and start + dur <= b for _, a, b in steps):
            kernel_s += dur / 1e9
    if kernel_s <= 0.0:
        return None
    job = cell.traffic
    need = ctx["flops"].flash_fwd_bwd(cell.config, int(job["batch"]),
                                      int(job["seq_len"]))
    least = ctx["flops"].roofline_seconds(
        need["flops"] * len(steps), need["bytes"] * len(steps), cell.peaks)
    return 100.0 * trace_reduce.share(least["seconds"], kernel_s,
                                      "flash kernel roofline share")
