"""Entry kind "train": a job file driven through
``train.Trainer(loop, ScalingConfig(num_workers=1)).fit()`` in the
process that holds the chip(s). The loop is what a user of
``ray_tpu.train`` writes: it builds the model from the configuration,
jits ``models.train_step.make_train_step`` (on more than one chip under
``MeshConfig.for_devices(n)`` with sharded state), feeds a fresh batch
every step and calls ``train.report`` every step.

Set-up builds ONE compiled step with its state, drives it through the
job's first ``check_steps`` steps (which warm it up, and whose losses,
first gradient and parameter change the reference follows afterwards)
and hands the same object to the measured window.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import statistics
import time
from typing import Any, Dict, List, Tuple

from benchmark import compile_watch, flops, traffic_gen, weights
from benchmark.common import load_json, say
from benchmark.spans import Recorder
from benchmark.tracing import WindowTracer

def transformer_kwargs(config: Dict[str, Any], max_seq_len: int
                       ) -> Dict[str, Any]:
    """The configuration file's Hugging Face keys as
    ``TransformerConfig`` arguments."""
    import jax.numpy as jnp

    s = weights.dims(config)
    if s["d"] // s["h"] != s["hd"]:
        raise ValueError("TransformerConfig derives head_dim as "
                         "d_model // n_heads; this configuration's "
                         f"head_dim {s['hd']} differs")
    run = config.get("run", {})
    return dict(vocab_size=s["v"], d_model=s["d"], n_layers=s["layers"],
                n_heads=s["h"], n_kv_heads=s["kv"], d_ff=s["ff"],
                max_seq_len=max_seq_len,
                rope_theta=float(config["rope_theta"]),
                norm_eps=float(config["rms_norm_eps"]),
                dtype=jnp.dtype(run.get("dtype", "bfloat16")),
                param_dtype=jnp.dtype(run.get("param_dtype", "float32")))


def _loop(loop_config: Dict[str, Any]) -> None:
    """train_loop_per_worker. It runs on a Trainer worker thread of this
    same process (thread-mode head), so ``loop_config["bench"]`` is the
    caller's own dict: the loop reads the cell from it and leaves what
    it measured in it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu import train
    from ray_tpu.models import train_step as ts
    from ray_tpu.models.transformer import Transformer, TransformerConfig
    from ray_tpu.parallel import mesh as mesh_lib

    out = loop_config["bench"]
    cell, rec, watch = out["cell"], out["recorder"], out["watch"]
    config, job = cell.config, cell.traffic
    batch, seq = int(job["batch"]), int(job["seq_len"])
    opt = job["optimizer"]
    cfg = TransformerConfig(**transformer_kwargs(config, seq), remat=True,
                            remat_policy=job["remat"])
    model = Transformer(cfg)
    optimizer = ts.make_optimizer(opt["learning_rate"],
                                  opt["weight_decay"])
    key = weights.seed_key(cell.seed)
    init = lambda k: weights.init_params(config, k, cfg.param_dtype)  # noqa: E731

    c0 = watch.snapshot()
    t0 = time.perf_counter()
    if cell.chips == 1:
        mesh_ctx = contextlib.nullcontext
        params = jax.jit(init)(key)
        step_fn = ts.make_train_step(model, optimizer)
        put = jnp.asarray
    else:
        mesh = mesh_lib.make_mesh(
            mesh_lib.MeshConfig.for_devices(cell.chips), cell.devices)
        mesh_ctx = lambda: mesh_lib.use_mesh(mesh)  # noqa: E731
        _, _, logical_specs = ts.abstract_state(cfg, batch, seq)
        shardings = ts.mesh_shardings(mesh, logical_specs)
        params = jax.jit(init, out_shardings=shardings)(key)
        step_fn = ts.make_train_step(model, optimizer,
                                     param_shardings=shardings)
        rows = NamedSharding(mesh, PartitionSpec(("data", "fsdp"), None))
        put = lambda b: jax.device_put(b, rows)  # noqa: E731
        out["mesh_shape"] = {k: v for k, v in mesh.shape.items() if v > 1}
    feed = lambda n: traffic_gen.train_batch(  # noqa: E731
        job, weights.dims(config)["v"], cell.seed, n)
    with mesh_ctx():
        opt_state = jax.jit(optimizer.init)(params)
        jax.block_until_ready((params, opt_state))
        out["init_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
            params, opt_state, {"tokens": put(feed(1))}).compile()
        out["compile_s"] = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    out["step_memory"] = {
        k: int(getattr(mem, k + "_size_in_bytes", 0))
        for k in ("argument", "output", "temp", "alias")} if mem else {}
    out["flash_kernel_in_step"] = "tpu_custom_call" in compiled.as_text()

    def one_step(n: int, params, opt_state) -> Tuple[Any, Any, float]:
        """The window's own call and feed, also used for the first
        steps."""
        with rec.span("train.feed"):
            tokens = put(feed(n))
        with rec.span("train.step"):
            params, opt_state, metrics = compiled(params, opt_state,
                                                  {"tokens": tokens})
            loss = float(jax.block_until_ready(metrics["loss"]))
        with rec.span("train.report"):
            train.report({"step": n, "loss": loss,
                          "grad_norm": float(metrics["grad_norm"])})
        return params, opt_state, loss

    # -- the first steps: warm-up, and what the reference follows ------
    t0 = time.perf_counter()
    losses: List[float] = []
    n_check = int(job["check_steps"])
    for n in range(1, n_check + 1):
        params, opt_state, loss = one_step(n, params, opt_state)
        losses.append(loss)
        if n == 1:
            mu = next(s.mu for s in opt_state if hasattr(s, "mu"))
            out["grad_norms"] = {
                k: v / (1.0 - opt["b1"])
                for k, v in weights.leaf_norms(mu).items()}
    out["losses"] = losses
    out["delta_norms"] = weights.param_change_norms(config, cell.seed,
                                                     params)
    out["warm_s"] = time.perf_counter() - t0
    out["setup_compile"] = watch.since(c0)

    # -- the measured window -------------------------------------------
    c_open = watch.snapshot()
    tracer = WindowTracer(cell, rec) if cell.trace else None
    t_open = time.perf_counter()
    if tracer is not None:
        tracer.start(t_open)
    n, steps = n_check, 0
    while True:
        n += 1
        params, opt_state, loss = one_step(n, params, opt_state)
        steps += 1
        t_close = time.perf_counter()
        if t_close - t_open >= cell.seconds:
            break
    compiled_in_window = watch.since(c_open)["backend_compiles"]
    out.update(t_open=t_open, t_close=t_close, steps=steps,
                last_loss=loss, compiled_in_window=compiled_in_window)
    stats = [d.memory_stats() or {} for d in cell.devices]
    out["peak_bytes_in_use"] = max(
        int(s.get("peak_bytes_in_use", 0)) for s in stats)
    out["bytes_in_use"] = max(int(s.get("bytes_in_use", 0)) for s in stats)
    if tracer is not None:
        out["trace_summary"] = tracer.finish()
    del params, opt_state, compiled


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep=lambda name: True) -> Tuple[float, str]:
    """The largest gap between the program's norm and the reference's,
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    median = statistics.median(ref.values())
    worst, at = 0.0, ""
    for name, r in ref.items():
        if not keep(name):
            continue
        gap = abs(prog[name] - r) / max(r, median)
        if gap >= worst:
            worst, at = gap, name
    return worst, at


def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]) -> List[Tuple[str, float, float]]:
    """The numbers that decide ``correct`` for a training cell, each
    with its limit."""
    checks = []
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        checks.append((f"loss_rel_gap_step{i}", abs(a - b) / abs(b),
                       limits["loss_rel_gap"]))
    g, _ = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    checks.append(("grad_norm_gap_worst_leaf", g,
                   limits["grad_norm_gap_worst_leaf"]))
    # leaves whose gradient is nought to rounding in the reference move
    # under Adam by round-off alone: left out of the change
    floor = 1e-3 * statistics.median(ref["grad_norms"].values())
    d, _ = worst_leaf_gap(prog["delta_norms"], ref["delta_norms"],
                          lambda name: ref["grad_norms"][name] >= floor)
    checks.append(("param_change_gap_worst_leaf", d,
                   limits["param_change_gap_worst_leaf"]))
    return checks


def run(cell, t_process_start: float) -> Dict[str, Any]:
    import jax

    import ray_tpu
    from ray_tpu import train

    job = cell.traffic
    rec = Recorder()
    r: Dict[str, Any] = {"cell": cell, "recorder": rec,
                         "watch": compile_watch.CompileWatch()}
    t0 = time.perf_counter()
    ray_tpu.init(num_workers=4, scheduler="tensor",
                 _system_config={"log_dir": cell.scratch("logs")})
    start_s = time.perf_counter() - t0
    try:
        result = train.Trainer(
            _loop, train_loop_config={"bench": r},
            scaling_config=train.ScalingConfig(num_workers=1),
            run_config=train.RunConfig(
                name=cell.name, storage_path=cell.scratch("train"))).fit()
    finally:
        ray_tpu.shutdown()
    if r["compiled_in_window"]:
        raise compile_watch.CompiledInWindow(
            f"{r['compiled_in_window']} program(s) compiled inside the "
            "measured window")
    reported = len(result.metrics_history)
    steps_all = r["steps"] + int(job["check_steps"])
    window_s = r["t_close"] - r["t_open"]
    tokens = r["steps"] * int(job["batch"]) * int(job["seq_len"])
    setup_s = r["t_open"] - t_process_start
    say("setup", setup_s=setup_s, import_and_init_s=start_s,
        weights_s=r["init_s"], lower_compile_s=r["compile_s"],
        first_steps_s=r["warm_s"], **r["setup_compile"])
    say("step_memory", **r["step_memory"],
        peak_bytes_in_use=r["peak_bytes_in_use"],
        bytes_in_use=r["bytes_in_use"],
        flash_kernel_in_step=r["flash_kernel_in_step"],
        mesh=r.get("mesh_shape", "none"))
    in_window = rec.durations("train.step", r["t_open"], r["t_close"])
    say("window", steps=r["steps"], window_s=window_s, tokens=tokens,
        step_ms=1e3 * window_s / r["steps"],
        first_10_steps_ms=1e3 * statistics.mean(in_window[:10]),
        last_10_steps_ms=1e3 * statistics.mean(in_window[-10:]),
        last_loss=r["last_loss"], reports=reported)

    # -- the reference, once the program's state is gone ---------------
    gc.collect()
    t0 = time.perf_counter()
    ref_mod = importlib.import_module(
        "benchmark.reference." + cell.config["reference"])
    batches = [traffic_gen.train_batch(job, weights.dims(cell.config)["v"],
                                       cell.seed, n)
               for n in range(1, int(job["check_steps"]) + 1)]
    ref = ref_mod.train_three_steps(cell.config, cell.seed, batches,
                                    job["optimizer"],
                                    **job.get("reference_args", {}))
    limits = load_json("benchmark", "limits", cell.name + ".json")["limits"]
    checks = compare(r, ref, limits)
    checks.append(("reports_missing", float(steps_all - reported), 0.0))
    say("reference", seconds=time.perf_counter() - t0,
        ref_losses=[round(x, 6) for x in ref["losses"]],
        losses=[round(x, 6) for x in r["losses"]])
    for name in sorted(ref["grad_norms"]):
        say("leaf", name=name, grad=r["grad_norms"][name],
            ref_grad=ref["grad_norms"][name], change=r["delta_norms"][name],
            ref_change=ref["delta_norms"][name])

    ctx = {"cell": cell, "recorder": rec, "run": r, "flops": flops,
           "reference": ref,
           "window": (r["t_open"], r["t_close"]),
           "trace_summary": r.get("trace_summary")}
    return {
        "setup_s": setup_s,
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "attempted": r["steps"], "failed": 0,
        "checks": checks,
        "memory_peak_bytes": r["peak_bytes_in_use"],
        "ctx": ctx,
    }
