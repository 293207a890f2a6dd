"""Model FLOP/s utilisation of the whole train step: the FLOPs the
job's steps REQUIRE (6*N*tokens plus causal attention, no
recomputation; benchmark/flops.py) for the steps that ran in the
measured window, over window x peak x chips. Not a kernel's roofline:
it bounds what any kernel's gain can be worth."""


def read(ctx):
    cell, run = ctx["cell"], ctx["run"]
    if cell.peaks is None:
        return None
    job = cell.traffic
    need = ctx["flops"].train_step_flops(
        cell.config, int(job["batch"]), int(job["seq_len"])) * run["steps"]
    window_s = run["t_close"] - run["t_open"]
    return 100.0 * need / (window_s * cell.peaks["bf16_flops_per_s"]
                           * cell.chips)
