#!/usr/bin/env python
"""chip_smoke.py — does ray_tpu still start on the chip?

Drives the three things this repo puts on the accelerator through the
entry points a user calls, in ONE process (thread-mode head: the
process that holds the chip), at the full width of the configurations
the repo benchmarks, and checks every result against something
independent:

  A  scheduling  ray_tpu.init(scheduler="tensor", sched_backend="jax"),
                 a 64-row virtual cluster, BASELINE config 2 (100 k-task
                 two-level map-reduce) through f.map_remote -> get, then
                 the 1 M-task north-star fan-out driven on the device;
                 both compared with plain Python / the numpy backend.
  B  training    the 445 M flagship through train.Trainer(...).fit():
                 three optimizer steps, loss finite and falling, first
                 loss against a float32 einsum-attention forward.
  C  serving     the 127 M decode model behind serve.llm.build_llm_app
                 (twice: 16 and 128 pages a sequence, both read by
                 the Pallas kernel) and run_disagg_llm; streamed tokens
                 against a naive full-context float32 forward.

    python chip_smoke.py               # one chip: A, B, C
    python chip_smoke.py --four-chips  # only the sharded train step on
                                       # four chips, against one chip

Chip-or-fail: without a TPU it exits non-zero before any phase and
never prints a result. Any phase that fails raises, and the script
exits non-zero. The LAST line of stdout of a passing run is
``{"ok": true, "device": {...}}``; everything worth seeing (wall time
per phase, compile seconds apart from run seconds, which attention
path each program took, which backend served the scheduler ticks, how
the native libraries were obtained, peak device memory) is on earlier
lines.

The phases are plain functions that take their sizes as arguments, so
tests/test_chip_smoke.py runs the same code at tiny sizes on the CPU
(kernels in interpret mode, four virtual devices).
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(tag: str, **fields: Any) -> None:
    parts = []
    for k, v in fields.items():
        if isinstance(v, float):
            v = f"{v:.6g}"
        parts.append(f"{k}={v}")
    print(f"[{tag}] " + " ".join(parts), flush=True)


# ----------------------------------------------------------------------
# what jax spent compiling, from its own monitoring events
# ----------------------------------------------------------------------

class CompileClock:
    """Sums jax's compile-path events so that every phase can print its
    compile seconds apart from its run seconds, and so that a run on a
    warm persistent cache can be told from a cold one."""

    # jax event -> the name it is printed under
    _DURATIONS = {
        "/jax/core/compile/backend_compile_duration":
            "compile_backend_compile_s",
        "/jax/core/compile/jaxpr_trace_duration": "compile_trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration":
            "compile_lower_s",
        "/jax/compilation_cache/cache_retrieval_time_sec":
            "compile_cache_retrieval_s",
    }
    _COUNTS = {
        "/jax/compilation_cache/cache_hits": "compile_cache_hits",
        "/jax/compilation_cache/cache_misses": "compile_cache_misses",
    }

    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {
            k: 0.0 for k in (*self._DURATIONS.values(),
                             *self._COUNTS.values())}
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(
            self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        key = self._DURATIONS.get(event)
        if key is not None:
            with self._lock:
                self._totals[key] += secs

    def _on_event(self, event: str, **_kw) -> None:
        key = self._COUNTS.get(event)
        if key is not None:
            with self._lock:
                self._totals[key] += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)

    def since(self, before: Dict[str, float]) -> Dict[str, Any]:
        counts = set(self._COUNTS.values())
        return {k: int(v - before[k]) if k in counts
                else round(v - before[k], 3)
                for k, v in self.snapshot().items()}


def device_memory(tag: str) -> None:
    import jax

    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        say(tag, device=d.id,
            bytes_in_use=stats.get("bytes_in_use", "n/a"),
            peak_bytes_in_use=stats.get("peak_bytes_in_use", "n/a"))


def _cache_file(*parts: str) -> str:
    """A path under the checkout's cache directory (logs, checkpoints
    of the smoke itself): nothing is written around the checkout."""
    from ray_tpu._private.cache_dir import checkout_cache_dir

    return checkout_cache_dir("smoke", *parts)


# ----------------------------------------------------------------------
# phase A: scheduling
# ----------------------------------------------------------------------

def drive_numpy(g, threshold: float) -> Tuple[Any, Any, int]:
    """The numpy backend's instant-completion drive of a BenchGraph:
    (final state [C], assignments per node [N], ticks). The reference
    the device drive is compared with."""
    import numpy as np

    from ray_tpu._private.scheduler import kernels
    from ray_tpu._private.scheduler.kernels import DONE, WAITING

    order = np.argsort(g.dst, kind="stable")
    src, dst = g.src[order], g.dst[order]
    state = np.full(len(g.indeg), WAITING, dtype=np.int8)
    indeg = g.indeg.copy()
    consumed = np.zeros(len(src), dtype=bool)
    counts = np.zeros(len(g.cap), dtype=np.int64)
    ticks = 0
    while (state == WAITING).any() and ticks < g.max_ticks:
        ready = np.flatnonzero((state == WAITING) & (indeg <= 0))
        # avail starts every tick at capacity: completion is instant
        node_of, _avail = kernels.assign_np(
            ready, g.cls, g.demands, g.cap.copy(), g.cap, threshold)
        took = node_of >= 0
        state[ready[took]] = DONE
        counts += np.bincount(node_of[took], minlength=len(g.cap))
        indeg, consumed = kernels.fire_edges_np(
            state == DONE, src, dst, consumed, indeg)
        ticks += 1
    return state, counts, ticks


def drive_device(g, threshold: float, clock: CompileClock
                 ) -> Dict[str, Any]:
    """Drive a BenchGraph on the default device twice: fused
    (kernels.jax_drive: final state + tick count, ONE program) and tick
    by tick (kernels.jax_tick: the same tick body, which also returns
    each task's node, for the per-node counts)."""
    import jax
    import numpy as np

    from ray_tpu._private import benchmarks
    from ray_tpu._private.scheduler import kernels
    from ray_tpu._private.scheduler.kernels import WAITING

    num_classes = int(g.demands.shape[0])
    st = benchmarks._device_state(g)
    jax.block_until_ready(st)
    c0 = clock.snapshot()
    t0 = time.perf_counter()
    out = kernels.jax_drive(*st, num_classes=num_classes,
                            threshold=threshold, max_ticks=g.max_ticks,
                            donate=False)
    jax.block_until_ready(out)
    first_s = time.perf_counter() - t0
    compile_s = clock.since(c0)
    t0 = time.perf_counter()
    out = kernels.jax_drive(*st, num_classes=num_classes,
                            threshold=threshold, max_ticks=g.max_ticks,
                            donate=False)
    jax.block_until_ready(out)
    warm_s = time.perf_counter() - t0
    state, _indeg, _avail, _consumed, ticks = out

    # tick by tick, for node_of (jax_tick donates state/indeg/consumed)
    st = list(benchmarks._device_state(g))
    counts = np.zeros(len(g.cap), dtype=np.int64)
    tick_ticks = 0
    while tick_ticks < g.max_ticks:
        if not bool((np.asarray(st[0]) == WAITING).any()):
            break
        s, indeg, avail, node_of, consumed = kernels.jax_tick(
            *st, num_classes=num_classes, threshold=threshold,
            instant_completion=True)
        node_of = np.asarray(node_of)
        counts += np.bincount(node_of[node_of >= 0],
                              minlength=len(g.cap))
        st[0], st[1], st[5], st[9] = s, indeg, avail, consumed
        tick_ticks += 1
    return {"state": np.asarray(state), "ticks": int(ticks),
            "counts": counts, "tick_state": np.asarray(st[0]),
            "tick_ticks": tick_ticks, "first_s": first_s,
            "warm_s": warm_s, "compile": compile_s}


def phase_scheduling(clock: CompileClock, *, nodes: int = 64,
                     cpus_per_node: float = 4.0,
                     map_reduce_tasks: int = 100_000, fan_in: int = 100,
                     north_star_tasks: int = 1_000_000,
                     get_timeout_s: float = 900.0) -> None:
    import numpy as np

    import ray_tpu
    from ray_tpu._private import benchmarks
    from ray_tpu._private import worker as worker_mod
    from ray_tpu._private.scheduler.kernels import DONE
    from ray_tpu.cluster_utils import Cluster

    # -- BASELINE config 2 through the public API ----------------------
    num_reduce = map_reduce_tasks // (fan_in + 1)
    num_map = map_reduce_tasks - num_reduce
    def plain_map(i: int) -> int:
        return (i * i + 7) % 1_000_003

    c0 = clock.snapshot()
    t_phase = time.perf_counter()
    cluster = Cluster(initialize_head=True, head_node_args=dict(
        num_cpus=cpus_per_node, num_workers=int(cpus_per_node),
        scheduler="tensor",
        _system_config={"sched_backend": "jax",
                        "log_dir": _cache_file("logs")}))
    try:
        for _ in range(nodes - 1):
            cluster.add_node(num_cpus=cpus_per_node, num_workers=1)
        cluster.wait_for_nodes(timeout=300.0)
        sched = worker_mod.get_worker().scheduler
        rows = sched.node_count()
        require(rows >= nodes, f"cluster has {rows} rows, wanted {nodes}")
        say("A.cluster", rows=rows, cpus_per_node=cpus_per_node,
            worker_mode="thread head + 1 process worker per added node",
            up_s=time.perf_counter() - t_phase)

        mapper = ray_tpu.remote(plain_map)

        @ray_tpu.remote
        def reducer(*parts):
            return sum(parts)

        t0 = time.perf_counter()
        maps = mapper.map_remote([(i,) for i in range(num_map)])
        reds = reducer.map_remote(
            [tuple(maps[j * fan_in:(j + 1) * fan_in])
             for j in range(num_reduce)])
        t_submit = time.perf_counter() - t0
        got = ray_tpu.get(reds, timeout=get_timeout_s)
        tail = ray_tpu.get(maps[num_reduce * fan_in:],
                           timeout=get_timeout_s)
        wall = time.perf_counter() - t0
        want = [sum(plain_map(i) for i in range(j * fan_in,
                                                (j + 1) * fan_in))
                for j in range(num_reduce)]
        want_tail = [plain_map(i)
                     for i in range(num_reduce * fan_in, num_map)]
        require(got == want, "map-reduce: reducer results differ from "
                "the plain-Python reduction")
        require(tail == want_tail, "map-reduce: unreduced map results "
                "differ from plain Python")
        stats = sched.stats()
        by = stats["ticks_by_backend"]
        say("A.map_reduce", tasks=num_map + num_reduce, maps=num_map,
            reducers=num_reduce, fan_in=fan_in, results="equal",
            submit_s=t_submit, wall_s=wall,
            tasks_per_s=(num_map + num_reduce) / wall,
            ticks=stats["ticks"], device_ticks=by["jax"],
            numpy_ticks=by["numpy"],
            assign_failures=stats["assign_failures"],
            **clock.since(c0))
        require(by["jax"] > 0, "no scheduler tick ran on the device")
        require(by["numpy"] == 0,
                f"{by['numpy']} ticks were served by numpy under "
                "sched_backend=jax")
        require(stats["assign_failures"] == 0,
                f"{stats['assign_failures']} device ticks raised")
        require(stats["finished"] >= num_map + num_reduce,
                f"scheduler saw {stats['finished']} completions")
    finally:
        cluster.shutdown()

    # -- the north star, driven on the device --------------------------
    g = benchmarks.build_north_star(north_star_tasks, nodes)
    threshold = 0.99   # what benchmarks.run_graph drives it with
    dev = drive_device(g, threshold, clock)
    t0 = time.perf_counter()
    np_state, np_counts, np_ticks = drive_numpy(g, threshold)
    np_s = time.perf_counter() - t0
    say("A.north_star", graph=g.name, tasks=len(g.indeg), nodes=nodes,
        ticks=dev["ticks"], numpy_ticks=np_ticks,
        done=int((dev["state"] == DONE).sum()),
        first_call_s=dev["first_s"], warm_call_s=dev["warm_s"],
        numpy_s=np_s,
        **dev["compile"])
    require(bool((dev["state"] == DONE).all()),
            "device drive left tasks unfinished")
    require(bool((dev["state"] == np_state).all()),
            "device drive and numpy drive end in different task states")
    require(bool((dev["tick_state"] == np_state).all()),
            "tick-by-tick device drive ends in different task states")
    require(dev["ticks"] == np_ticks == dev["tick_ticks"],
            f"tick counts differ: fused {dev['ticks']}, per-tick "
            f"{dev['tick_ticks']}, numpy {np_ticks}")
    require(bool((dev["counts"] == np_counts).all()),
            "per-node assignment counts differ between device and "
            f"numpy: {dev['counts'].tolist()} vs {np_counts.tolist()}")
    say("A.north_star", node_counts="equal",
        min_per_node=int(np_counts.min()),
        max_per_node=int(np_counts.max()))


# ----------------------------------------------------------------------
# phase B: training
# ----------------------------------------------------------------------

FLAGSHIP = dict(vocab_size=32_768, d_model=2048, n_layers=8, n_heads=16,
                n_kv_heads=8, d_ff=5632, max_seq_len=2048)


def _reference_loss(model_kw: Dict[str, Any], params, tokens,
                    rows: int) -> float:
    """Mean next-token loss of a plain float32 einsum-attention forward
    of the same parameters, ``rows`` sequences at a time."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import (Transformer,
                                            TransformerConfig,
                                            cross_entropy_loss)

    ref = Transformer(TransformerConfig(
        **model_kw, dtype=jnp.float32, flash_attention="off"))

    @jax.jit
    def slice_loss(p, toks):
        logits = ref.apply({"params": p}, toks[:, :-1])
        return cross_entropy_loss(logits, toks[:, 1:])

    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, tokens.shape[0], rows):
            losses.append(float(slice_loss(params, tokens[i:i + rows])))
    # equal-sized slices of equal-length rows: the mean of means
    return sum(losses) / len(losses)


def _train_loop(config: Dict[str, Any]) -> None:
    """train_loop_per_worker: what a user of ray_tpu.train writes."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import train_step as ts
    from ray_tpu.models.transformer import Transformer, TransformerConfig

    model_kw, batch, seq = config["model"], config["batch"], config["seq"]
    cfg = TransformerConfig(**model_kw, remat=True, remat_policy="dots")
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(config["seed"] + 1),
                                (batch, seq), 0, cfg.vocab_size,
                                dtype=jnp.int32)
    params = jax.jit(lambda rng: model.init(rng, tokens)["params"])(
        jax.random.PRNGKey(config["seed"]))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    t0 = time.perf_counter()
    ref_loss = _reference_loss(model_kw, params, tokens,
                               config["ref_rows"])
    ref_s = time.perf_counter() - t0

    optimizer = ts.make_optimizer()
    opt_state = jax.jit(optimizer.init)(params)
    step = jax.jit(ts.make_train_step(model, optimizer),
                   donate_argnums=(0, 1))
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, {"tokens": tokens}).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    for i in range(config["steps"]):
        t0 = time.perf_counter()
        params, opt_state, metrics = compiled(params, opt_state,
                                              {"tokens": tokens})
        loss = float(jax.block_until_ready(metrics["loss"]))
        train.report({
            "step": i, "loss": loss, "step_s": time.perf_counter() - t0,
            "grad_norm": float(metrics["grad_norm"]),
            "n_params": int(n_params), "ref_loss": ref_loss,
            "ref_s": ref_s, "compile_s": compile_s,
            "flash_kernel_in_step": has_kernel})


def phase_training(clock: CompileClock, *,
                   model_kw: Dict[str, Any] = FLAGSHIP, batch: int = 8,
                   seq: int = 2048, steps: int = 3, ref_rows: int = 2,
                   seed: int = 0, expect_flash: bool = True) -> None:
    import math

    import ray_tpu
    from ray_tpu import train

    c0 = clock.snapshot()
    t_phase = time.perf_counter()
    ray_tpu.init(num_workers=4, scheduler="tensor",
                 _system_config={"log_dir": _cache_file("logs")})
    try:
        result = train.Trainer(
            _train_loop,
            train_loop_config={"model": dict(model_kw), "batch": batch,
                               "seq": seq, "steps": steps,
                               "ref_rows": ref_rows, "seed": seed},
            scaling_config=train.ScalingConfig(num_workers=1),
            run_config=train.RunConfig(
                name="chip_smoke",
                storage_path=_cache_file("train"))).fit()
    finally:
        ray_tpu.shutdown()
    hist = result.metrics_history
    require(len(hist) == steps, f"trainer reported {len(hist)} steps, "
            f"wanted {steps}")
    losses = [h["loss"] for h in hist]
    first = hist[0]
    # one bf16 ulp of the loss itself
    tol = 2.0 ** -8 * abs(first["ref_loss"])
    say("B.train", n_params=first["n_params"], batch=batch, seq=seq,
        dtype="bfloat16", remat="dots",
        flash_kernel_in_step=first["flash_kernel_in_step"],
        compile_s=first["compile_s"],
        step_s=[round(h["step_s"], 4) for h in hist],
        losses=[round(x, 5) for x in losses],
        grad_norm=[round(h["grad_norm"], 4) for h in hist],
        wall_s=time.perf_counter() - t_phase,
        **clock.since(c0))
    say("B.reference", kind="float32 einsum attention, precision=highest",
        ref_loss=first["ref_loss"], first_loss=losses[0],
        abs_diff=abs(losses[0] - first["ref_loss"]), tol=tol,
        ref_s=first["ref_s"])
    require(all(math.isfinite(x) for x in losses),
            f"loss not finite: {losses}")
    require(all(b < a for a, b in zip(losses, losses[1:])),
            f"loss not falling: {losses}")
    require(abs(losses[0] - first["ref_loss"]) <= tol,
            f"first-step loss {losses[0]} is not within {tol} of the "
            f"float32 reference {first['ref_loss']}")
    if expect_flash:
        require(first["flash_kernel_in_step"],
                "the compiled train step holds no tpu_custom_call: the "
                "flash kernel was not taken")


# ----------------------------------------------------------------------
# phase C: serving
# ----------------------------------------------------------------------

DECODE_MODEL = dict(vocab_size=32_000, d_model=1024, n_layers=8,
                    n_heads=8, n_kv_heads=4, d_ff=2816, max_seq_len=2048)


def _decode_program_has_kernel(params, mcfg, icfg, shape=None) -> bool:
    """Compile the engine's decode step for this geometry and look for
    the paged attention kernel: a Pallas custom call under the ``attn``
    scope. (ANY custom call will not do: every decode program holds
    ``append_token_kv``'s kernel, under ``kv_append``.) The engine jits
    this function (inference.decode_chunk) with its pools donated, and
    so does this: not donated, the compiler copies each pool first, and
    where that copy fits VMEM its memory assignment aborts on the
    kernel's HBM pin (append_token_kv's docstring). Every geometry
    runs the kernel (paged_attention_auto chooses nothing); a decode
    program without it would be running something else.
    ``shape(dims, dtype)`` describes an argument;
    tests/test_chip_compile.py places them on a described chip."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import inference

    shape = shape or jax.ShapeDtypeStruct
    kv = tuple(
        shape((icfg.num_pages, mcfg.n_kv_heads, icfg.page_size,
               mcfg.head_dim), mcfg.dtype)
        for _ in range(mcfg.n_layers))
    ints = lambda *s: shape(s, jnp.int32)  # noqa: E731
    fn = jax.jit(lambda p, t, kp, vp, table, lens: inference.decode_chunk(
        p, mcfg, t, kp, vp, table, lens, n_steps=1), donate_argnums=(2, 3))
    compiled = fn.lower(params, ints(icfg.batch_size), kv, kv,
                        ints(icfg.batch_size, icfg.max_pages_per_seq),
                        ints(icfg.batch_size)).compile()
    return any('custom_call_target="tpu_custom_call"' in line
               and re.search(r'op_name="[^"]*/attn/', line)
               for line in compiled.as_text().splitlines())


def _check_against_naive(ref_model, params, prompt: Sequence[int],
                         tokens: Sequence[int]) -> Tuple[int, float]:
    """Hold streamed tokens to a naive full-context float32 forward of
    the same parameters. Token i must be the argmax of the naive logits
    after prompt + tokens[:i] — which, when every token is, makes the
    stream the naive greedy decode — or, the engine computing in bf16,
    within 8 bf16 ulps of it at the top logit's magnitude. Returns
    (tokens that were the exact argmax, largest gap seen)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + list(tokens)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref_model.apply(
            {"params": params}, jnp.asarray([seq[:-1]], jnp.int32))[0])
    exact, worst = 0, 0.0
    for i, tok in enumerate(tokens):
        row = logits[len(prompt) - 1 + i]
        gap = float(row.max() - row[tok])
        tol = 8 * 2.0 ** -8 * float(np.abs(row).max())
        require(gap <= tol,
                f"token {i} = {tok} is not the naive greedy token "
                f"{int(row.argmax())}: its logit is {gap:.4f} under the "
                f"max, tolerance {tol:.4f}")
        exact += gap == 0.0
        worst = max(worst, gap)
    return exact, worst


def _stream_all(open_stream, requests: List[Tuple[str, List[int]]],
                ) -> Dict[str, List[int]]:
    """Stream every request concurrently (they share the engine's
    continuous batch) to completion; ``open_stream(session, prompt)``
    yields token-burst frames."""
    out: Dict[str, List[int]] = {}
    errors: List[BaseException] = []

    def run(session: str, prompt: List[int]) -> None:
        try:
            toks: List[int] = []
            for frame in open_stream(session, prompt):
                toks.extend(frame.get("tokens") or ())
            out[session] = toks
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=run, args=r, daemon=True)
               for r in requests]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=1200.0)
        require(not t.is_alive(), "a stream did not finish in 1200 s")
    if errors:
        raise errors[0]
    return out


def phase_serving(clock: CompileClock, *,
                  model_kw: Dict[str, Any] = DECODE_MODEL,
                  slots: int = 64, page_size: int = 16,
                  geometries: Sequence[Tuple[int, int]] = (
                      (16, 1024), (128, 64 * 128 + 1)),
                  expect_kernel: Optional[Sequence[bool]] = (True, True),
                  prompt_lens: Sequence[int] = (5, 16, 23, 40, 64),
                  buckets: Tuple[int, ...] = (16, 64),
                  max_new: int = 24, seed: int = 0) -> None:
    """``geometries`` are (max_pages_per_seq, num_pages) per engine
    build; the FIRST is also what the disaggregated pools run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.inference import InferenceConfig
    from ray_tpu.models.transformer import Transformer, TransformerConfig
    from ray_tpu.serve import core
    from ray_tpu.serve.llm import build_llm_app, run_disagg_llm

    mcfg = TransformerConfig(**model_kw)
    params = jax.jit(lambda rng: Transformer(mcfg).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(seed))
    ref_model = Transformer(dataclasses.replace(
        mcfg, dtype=jnp.float32, flash_attention="off"))
    rng = np.random.default_rng(seed)
    requests = [(f"s{i}", rng.integers(1, mcfg.vocab_size, n).tolist())
                for i, n in enumerate(prompt_lens)]
    n_tokens = len(requests) * max_new

    def engine_cfg(mp: int, pages: int) -> InferenceConfig:
        return InferenceConfig(batch_size=slots, page_size=page_size,
                               max_pages_per_seq=mp, num_pages=pages,
                               prefill_buckets=buckets,
                               max_new_tokens=max_new)

    def check(tag: str, streams: Dict[str, List[int]], **extra) -> None:
        exact, worst = 0, 0.0
        for session, prompt in requests:
            toks = streams[session]
            require(len(toks) == max_new, f"{tag}: {session} streamed "
                    f"{len(toks)} tokens, wanted {max_new}")
            e, w = _check_against_naive(ref_model, params, prompt, toks)
            exact += e
            worst = max(worst, w)
        say(tag, requests=len(requests), prompt_lens=list(prompt_lens),
            tokens=n_tokens, naive_argmax_exact=exact,
            bf16_near_ties=n_tokens - exact, max_logit_gap=worst,
            **extra)

    ray_tpu.init(num_workers=8, scheduler="tensor",
                 _system_config={"log_dir": _cache_file("logs")})
    try:
        mono: List[Dict[str, List[int]]] = []
        for gi, (mp, pages) in enumerate(geometries):
            icfg = engine_cfg(mp, pages)
            c0 = clock.snapshot()
            has_kernel = _decode_program_has_kernel(params, mcfg, icfg)
            handle = serve.run(build_llm_app(params, mcfg, icfg))

            def open_mono(_session, prompt, handle=handle):
                sid = ray_tpu.get(
                    handle.start_stream.remote(prompt, max_new),
                    timeout=600.0)
                while True:
                    frame = ray_tpu.get(
                        handle.next_tokens.remote(sid, 600.0),
                        timeout=900.0)
                    yield frame
                    if frame.get("done"):
                        return

            t0 = time.perf_counter()
            streams = _stream_all(open_mono, requests)
            first_s = time.perf_counter() - t0
            comp = clock.since(c0)
            t0 = time.perf_counter()
            again = _stream_all(open_mono, requests)
            warm_s = time.perf_counter() - t0
            serve.shutdown()
            check(f"C.mono[max_pages_per_seq={mp}]", streams,
                  context=mp * page_size, num_pages=pages, slots=slots,
                  decode_path=("pallas kernel" if has_kernel
                               else "no kernel under attn"
                               if jax.default_backend() == "tpu"
                               else "pallas kernel, interpret mode"),
                  first_pass_s=first_s, warm_pass_s=warm_s,
                  **comp)
            require(again == streams, f"engine max_pages_per_seq={mp}: "
                    "a second pass of the same requests streamed "
                    "different tokens")
            if expect_kernel is not None:
                require(has_kernel == expect_kernel[gi],
                        f"engine max_pages_per_seq={mp}: decode program "
                        f"{'holds' if has_kernel else 'lacks'} the "
                        "Pallas kernel, expected the opposite")
            mono.append(streams)
            gc.collect()
        same = sum(mono[0][s] == m[s] for m in mono[1:]
                   for s, _ in requests)
        say("C.mono", engines=len(mono),
            streams_identical_across_engines=f"{same}/"
            f"{(len(mono) - 1) * len(requests)}")

        # -- disaggregated: one prefill + one decode replica -----------
        mp, pages = geometries[0]
        c0 = clock.snapshot()
        core.metrics.reset()
        handle = run_disagg_llm(params, mcfg, engine_cfg(mp, pages),
                                prefill_replicas=1, decode_replicas=1)

        def open_disagg(session, prompt):
            return handle.stream_frames(prompt, max_new,
                                        session_id=session,
                                        start_timeout=600.0,
                                        poll_timeout=900.0)

        t0 = time.perf_counter()
        streams = _stream_all(open_disagg, requests)
        first_s = time.perf_counter() - t0
        comp = clock.since(c0)
        snap = core.metrics.snapshot()
        check("C.disagg", streams, prefill_replicas=1, decode_replicas=1,
              kv_bytes=snap["kv_bytes"], first_pass_s=first_s,
              **comp)
        require(snap["kv_bytes"] > 0, "no KV bytes crossed the handoff")
        require(snap["resumed"] == 0,
                f"{snap['resumed']} stream(s) lost their decode replica "
                "and were resumed: a replica failed under the smoke")
        # follow-up turn: same session, same prompt -> the KV directory
        # routes it to the replica that holds the session's KV
        t0 = time.perf_counter()
        follow = _stream_all(open_disagg, requests[:2])
        snap2 = core.metrics.snapshot()
        say("C.disagg.follow_up", sessions=2,
            affinity_hit=snap2["affinity_hit"],
            affinity_miss=snap2["affinity_miss"],
            kv_bytes_added=snap2["kv_bytes"] - snap["kv_bytes"],
            wall_s=time.perf_counter() - t0)
        require(snap2["affinity_hit"] >= 2,
                f"follow-up turns did not hit the KV directory: {snap2}")
        for session, _ in requests[:2]:
            require(follow[session] == streams[session],
                    f"follow-up turn of {session} streamed different "
                    "tokens from its first turn")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# ----------------------------------------------------------------------
# the path across chips (behind --four-chips, and nothing else with it)
# ----------------------------------------------------------------------

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def phase_four_chips(clock: CompileClock, *,
                     model_kw: Dict[str, Any] = FLAGSHIP, batch: int = 8,
                     seq: int = 2049, steps: int = 2, seed: int = 0,
                     expect_kernels: bool = True) -> None:
    """The sharded train step on four devices, on (i) the mesh
    MeshConfig.for_devices(4) gives and (ii) fsdp=2 x seq=2 with ring
    attention, against the same step on ONE device: same seed, same
    tokens, first-step loss and post-update parameters at the
    tolerance __graft_entry__.dryrun_multichip uses. ``seq`` counts the
    tokens of a row; the model sees seq - 1 of them, which the seq axis
    has to divide."""
    import flax.core
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.linen import partitioning as nn_partitioning
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.models import train_step as ts
    from ray_tpu.models.transformer import Transformer, TransformerConfig
    from ray_tpu.parallel import mesh as mesh_lib

    devices = jax.devices()[:4]
    require(len(devices) == 4, f"need 4 devices, have {len(devices)}")
    tokens_host = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, seq), 0,
        model_kw["vocab_size"], dtype=jnp.int32))
    rules = mesh_lib.default_logical_rules()
    rtol = atol = 1e-3   # dryrun_multichip's

    def per_device_bytes(tag: str) -> List[int]:
        used = []
        for d in devices:
            stats = d.memory_stats() or {}
            used.append(int(stats.get("bytes_in_use", 0)))
        say(tag, bytes_in_use_per_device=used)
        return used

    # -- the one-chip step --------------------------------------------
    cfg1 = TransformerConfig(**model_kw, remat=True, remat_policy="dots")
    model1 = Transformer(cfg1)
    opt = ts.make_optimizer()
    with jax.default_device(devices[0]):
        tok1 = jax.device_put(tokens_host, devices[0])

        def init_fn(rng, toks):
            with nn_partitioning.axis_rules(rules):
                return flax.core.unfreeze(
                    model1.init(rng, toks)["params"])

        params1 = jax.jit(init_fn)(jax.random.PRNGKey(seed), tok1)
        init_host = jax.tree_util.tree_map(np.asarray, params1)
        c0 = clock.snapshot()
        step1 = jax.jit(ts.make_train_step(model1, opt),
                        donate_argnums=(0, 1))
        opt1 = jax.jit(opt.init)(params1)
        compiled1 = step1.lower(params1, opt1, {"tokens": tok1}).compile()
        t0 = time.perf_counter()
        params1, opt1, m1 = compiled1(params1, opt1, {"tokens": tok1})
        ref_loss = float(jax.block_until_ready(m1["loss"]))
        step_s = time.perf_counter() - t0
        ref_params = jax.tree_util.tree_map(np.asarray, params1)
        say("4.one_chip", device=devices[0].id, loss=ref_loss,
            step_s=step_s,
            flash_kernel="tpu_custom_call" in compiled1.as_text(),
            **clock.since(c0))
        per_device_bytes("4.one_chip")
        del params1, opt1, m1, compiled1, tok1
    gc.collect()

    # -- the two meshes ------------------------------------------------
    meshes = (
        ("for_devices(4)", mesh_lib.MeshConfig.for_devices(4), False),
        ("fsdp2_seq2_ring", mesh_lib.MeshConfig(fsdp=2, seq=2), True),
    )
    for name, mesh_cfg, ring in meshes:
        mesh = mesh_lib.make_mesh(mesh_cfg, devices)
        cfg = TransformerConfig(**model_kw, remat=True,
                                remat_policy="dots", ring_attention=ring)
        c0 = clock.snapshot()
        model, params, shardings = ts.init_sharded(cfg, mesh, batch, seq,
                                                   seed=seed)
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(init_host),
                jax.tree_util.tree_leaves(params)):
            require(np.array_equal(a, np.asarray(b)),
                    f"{name}: sharded init differs from the one-chip "
                    f"init at {jax.tree_util.keystr(path)}")
        shard_frac = []
        for leaf in jax.tree_util.tree_leaves(params):
            shard = leaf.addressable_shards[0].data
            shard_frac.append((shard.size, leaf.size))
        held = sum(s for s, _ in shard_frac) / sum(n for _, n in
                                                   shard_frac)
        with mesh_lib.use_mesh(mesh):
            opt_state = jax.jit(opt.init)(params)
            step = jax.jit(ts.make_train_step(model, opt,
                                              param_shardings=shardings),
                           donate_argnums=(0, 1))
            tokens = jax.device_put(tokens_host, NamedSharding(
                mesh, PartitionSpec(("data", "fsdp"), None)))
            compiled = step.lower(params, opt_state,
                                  {"tokens": tokens}).compile()
            text = compiled.as_text()
            losses, times = [], []
            for i in range(steps):
                t0 = time.perf_counter()
                params, opt_state, m = compiled(params, opt_state,
                                                {"tokens": tokens})
                losses.append(float(jax.block_until_ready(m["loss"])))
                times.append(time.perf_counter() - t0)
                if i == 0:
                    max_pd = 0.0
                    for a, b in zip(
                            jax.tree_util.tree_leaves(ref_params),
                            jax.tree_util.tree_leaves(params)):
                        b = np.asarray(b)
                        max_pd = max(max_pd, float(np.abs(a - b).max()))
                        require(np.allclose(a, b, rtol=rtol, atol=atol),
                                f"{name}: post-update parameters "
                                "diverge from the one-chip step (max "
                                f"delta {max_pd:.2e})")
        found = {c: n for c in _COLLECTIVES
                 if (n := len(re.findall(
                     rf" {c}(?:-start)?\(", text)))}
        kernel = "tpu_custom_call" in text
        say(f"4.{name}", mesh={k: v for k, v in mesh.shape.items()
                               if v > 1},
            ring_attention=ring, losses=[round(x, 5) for x in losses],
            one_chip_loss=round(ref_loss, 5),
            loss_diff=abs(losses[0] - ref_loss),
            post_update_max_param_delta=max_pd, tol=rtol,
            step_s=[round(t, 4) for t in times],
            param_share_per_device=round(held, 4),
            collectives=found, pallas_kernel_in_step=kernel,
            **clock.since(c0))
        used = per_device_bytes(f"4.{name}")
        require(np.isclose(losses[0], ref_loss, rtol=rtol, atol=atol),
                f"{name}: first-step loss {losses[0]} vs one-chip "
                f"{ref_loss}")
        require(all(np.isfinite(losses)), f"{name}: losses {losses}")
        require(held < 1.0, f"{name}: every device holds all parameters")
        require(bool(found), f"{name}: no collective in the compiled "
                "step")
        if used[0] > 0:
            require(min(used) > 0.5 * max(used),
                    f"{name}: device memory is lopsided: {used}")
        if ring:
            require("collective-permute" in found,
                    f"{name}: the ring (collective-permute) is missing")
            if expect_kernels:
                require(kernel, f"{name}: the ring block's Pallas "
                        "kernel is not in the compiled step")
        del params, opt_state, compiled, tokens
        gc.collect()


# ----------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the sharded train step on four chips "
                         "and the one-chip step it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, tokens and prompts")
    args = ap.parse_args(argv)

    import logging

    import jax

    from ray_tpu import _native
    from ray_tpu._private.cache_dir import enable_compile_cache

    # ray_tpu.init() hangs a file handler on the "ray_tpu" logger, so
    # what the program logs (a scheduler tick that raised, an engine
    # step that failed, a resumed stream) would otherwise only reach
    # the session's gcs.out: show warnings and errors here too
    to_stderr = logging.StreamHandler(sys.stderr)
    to_stderr.setLevel(logging.WARNING)
    to_stderr.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)s %(name)s: %(message)s"))
    logging.getLogger("ray_tpu").addHandler(to_stderr)

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: jax reports platform "
              f"{dev.platform!r} ({dev.device_kind}); this script only "
              f"runs on the chip", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) != want:
        print(f"chip_smoke: {'--four-chips needs' if args.four_chips else 'the default run needs'} "
              f"{want} chip(s), jax reports {len(devices)}",
              file=sys.stderr)
        return 2
    say("start", platform=dev.platform, kind=dev.device_kind,
        count=len(devices), jax=jax.__version__, seed=args.seed,
        compile_cache=cache_dir,
        cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        cache_entries_at_start=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0)
    clock = CompileClock()
    t_all = time.perf_counter()

    if args.four_chips:
        phases = [("4", lambda: phase_four_chips(clock, seed=args.seed))]
    else:
        _native.load_allocator_lib()
        _native.load_exchange_lib()
        for name, status in _native.build_status().items():
            say("native", lib=name, status=status)
            if status.startswith("unavailable"):
                say("native", FINDING=f"{name} did not build on this "
                    "machine; the pure-Python fallback serves (for the "
                    "allocator: shm_store's Python free list)")
        phases = [
            ("A", lambda: phase_scheduling(clock)),
            ("B", lambda: phase_training(clock, seed=args.seed)),
            ("C", lambda: phase_serving(clock, seed=args.seed)),
        ]
    for tag, run in phases:
        c0 = clock.snapshot()
        t0 = time.perf_counter()
        run()
        comp = clock.since(c0)
        gc.collect()
        say(f"{tag}.done", wall_s=time.perf_counter() - t0,
            **comp)
        device_memory(f"{tag}.memory")
    say("done", wall_s=time.perf_counter() - t_all,
        **clock.snapshot())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
