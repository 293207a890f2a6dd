"""End-to-end performance measurements for the headline bench.

Two honest numbers the scheduling-kernel bench (benchmarks.py) does not
capture:

1. ``e2e_task_throughput`` — real task throughput through the PUBLIC API
   (``f.remote()`` -> ``get``), including submit(), the arena, locks,
   dispatch, and result plumbing. This is the analog of the reference's
   ``ray microbenchmark`` single-node numbers
   (ray: python/ray/_private/ray_perf.py, SURVEY.md §6).

2. ``model_mfu`` — flagship-transformer training step time / tokens/s /
   MFU on the real chip, sized to use HBM. FLOPs come from the compiled
   program's own cost analysis (XLA's count), falling back to the
   analytic 6*N*D estimate. MFU = flops_per_step / step_time / peak,
   with peak looked up from the device kind.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

# Published per-chip peaks, keyed by the EXACT ``device_kind`` string
# jax reports. A kind that is not here is an error, not a default: add
# it with its source.
DEVICE_PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s
        "hbm_bytes_per_sec": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def device_peaks(device_kind: str) -> Dict[str, Any]:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks recorded for device kind "
            f"{device_kind!r}: add it to perf.DEVICE_PEAKS with its "
            f"source (known: {sorted(DEVICE_PEAKS)})") from None


# PINNED --smoke configs: the small fixed shapes bench.py runs under
# --smoke (CI, CPU) instead of the flagship ones. Frozen so smoke runs
# stay comparable with each other — do NOT resize to "use the host
# better". bench.py records them in the output JSON so a reader can
# tell which shape produced a number.
SMOKE_MODEL: Dict[str, int] = {
    "d_model": 256, "n_layers": 2, "n_heads": 8, "n_kv_heads": 4,
    "d_ff": 704, "vocab_size": 2048, "seq_len": 256, "batch_size": 4,
    "steps": 3,
}
SMOKE_DECODE: Dict[str, int] = {
    "vocab_size": 256, "d_model": 64, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 128, "max_seq_len": 256, "batch": 2,
    "new_tokens": 16, "pages": 64,
}


def e2e_task_throughput(n_tasks: int = 10_000, mode: str = "thread",
                        scheduler: str = "tensor",
                        num_workers: int = 8,
                        batched: bool = False,
                        best_of: int = 1) -> Dict[str, Any]:
    """Submit n_tasks no-op tasks through the public API and get() them.

    Measures the full path: RemoteFunction._remote -> Worker.submit ->
    scheduler tick -> dispatch -> execution -> result store -> get.
    batched=True submits through map_remote (the vectorized path the
    libraries use); best_of>1 keeps the fastest trial (this host is a
    shared 1-CPU VM with ±30% noise between trials).
    """
    import resource

    import ray_tpu
    from ray_tpu._private import worker as worker_mod

    ray_tpu.shutdown()
    sys_cfg = {"worker_mode": mode}
    ray_tpu.init(num_workers=num_workers, scheduler=scheduler,
                 _system_config=sys_cfg)
    try:
        @ray_tpu.remote
        def _noop():
            return None

        # Warm the pool / caches (process mode: function-blob push, worker
        # spin-up) so the measurement is steady-state.
        ray_tpu.get([_noop.remote() for _ in range(min(200, n_tasks))])
        if mode == "process":
            time.sleep(2.0)  # let late worker imports finish competing

        sched = worker_mod.global_worker.scheduler
        best = None
        for _ in range(max(1, best_of)):
            ticks0 = getattr(sched, "_num_ticks", 0)
            ru0 = resource.getrusage(resource.RUSAGE_SELF)
            t0 = time.perf_counter()
            if batched:
                refs = _noop.map_remote([()] * n_tasks)
            else:
                refs = [_noop.remote() for _ in range(n_tasks)]
            t_submit = time.perf_counter() - t0
            ray_tpu.get(refs)
            trial_dt = time.perf_counter() - t0
            ru1 = resource.getrusage(resource.RUSAGE_SELF)
            trial_ticks = getattr(sched, "_num_ticks", 0) - ticks0
            trial = (trial_dt, t_submit, ru0, ru1, trial_ticks)
            if best is None or trial_dt < best[0]:
                best = trial
            del refs
        dt, t_submit, ru0, ru1, ticks = best
    finally:
        ray_tpu.shutdown()
    driver_cpu = (ru1.ru_utime - ru0.ru_utime) + (ru1.ru_stime - ru0.ru_stime)
    return {
        "n_tasks": n_tasks,
        "mode": mode,
        "scheduler": scheduler,
        "seconds": dt,
        "tasks_per_sec": n_tasks / dt,
        # per-task host-overhead budget (microseconds)
        "budget_us": {
            "submit": round(t_submit / n_tasks * 1e6, 1),
            "driver_cpu_total": round(driver_cpu / n_tasks * 1e6, 1),
            "wall_total": round(dt / n_tasks * 1e6, 1),
        },
        "sched_ticks": ticks,
        "tasks_per_tick": round(n_tasks / max(ticks, 1), 1),
    }


def locality_ab(locality: bool, n_consumers: int = 8,
                arg_mb: float = 1.0,
                spill_depth: int = 32) -> Dict[str, Any]:
    """One arm of the locality-scheduling A/B: a 2-remote-node cluster,
    large objects produced on the SOURCE node, a consumer fanout free to
    run on either remote node.

    With ``locality=True`` the scheduler scores candidates by
    resident-arg-bytes and the consumers land (or wait, bounded by
    ``spill_depth``) on the source node — cross-node arg bytes stay
    near zero. With ``locality=False`` (the pre-PR placement) the
    least-loaded fill sends a batch of consumers to the sink node,
    each pulling its argument across. The SINK node is added first so
    the load-tiebreak favors it: the off arm genuinely moves bytes.

    Returns {sum, bytes_pulled, bytes_saved, seconds, hits, misses}.
    ``sum`` must match between arms (equal task results)."""
    import numpy as np

    import ray_tpu
    from ray_tpu._private import worker as worker_mod
    from ray_tpu.cluster_utils import Cluster

    n = max(1, int(arg_mb * 1024 * 1024) // 8)
    ray_tpu.shutdown()
    c = Cluster(initialize_head=True,
                head_node_args=dict(
                    num_cpus=2, num_workers=2, scheduler="tensor",
                    _system_config={
                        "scheduler_locality": bool(locality),
                        "locality_spillback_queue_depth": spill_depth}))
    try:
        c.add_node(num_cpus=4, remote=True, resources={"r": 100.0})
        c.add_node(num_cpus=4, remote=True,
                   resources={"r": 100.0, "src": 100.0})
        c.wait_for_nodes()
        w = worker_mod.get_worker()

        @ray_tpu.remote(resources={"src": 1.0})
        def produce(i):
            import numpy as np  # task-side: don't close over the
            return np.full(n, float(i))  # driver's local module binding

        @ray_tpu.remote(resources={"r": 1.0})
        def consume(x):
            return float(x[0]) * len(x)

        refs = [produce.remote(i) for i in range(n_consumers)]
        for r in refs:
            ray_tpu.wait([r], timeout=120.0)
        ts = w.transfer_stats
        p0 = ts["bytes_pulled"]
        t0 = time.perf_counter()
        out = ray_tpu.get([consume.remote(r) for r in refs],
                          timeout=300.0)
        dt = time.perf_counter() - t0
        return {
            "locality": bool(locality),
            "n_consumers": n_consumers,
            "arg_mb": arg_mb,
            "sum": float(sum(out)),
            "bytes_pulled": int(ts["bytes_pulled"] - p0),
            "bytes_saved": int(ts["bytes_saved"]),
            "hits": int(ts["locality_hits"]),
            "misses": int(ts["locality_misses"]),
            "seconds": round(dt, 3),
        }
    finally:
        c.shutdown()


def head_bypass_ab(p2p: Optional[bool], n_calls: int = 40,
                   n_submit: int = 24,
                   head_tick_delay_s: float = 0.02) -> Dict[str, Any]:
    """One arm of the two-level/head-bypass A/B: a 2-remote-node
    cluster, an actor resident on node B, a caller task on node A
    issuing ``n_calls`` sequential actor calls.

    With ``p2p=True`` (``actor_p2p`` + ``local_dispatch`` on) the calls
    ship worker -> caller daemon -> peer daemon once the route
    resolves; only sequenced completion receipts reach the head. With
    ``p2p=False`` every call round-trips the head (the escape hatch,
    byte-for-byte the pre-two-level wire). With ``p2p=None`` the arm
    runs the DEFAULT config — no knob overrides at all — and widens
    the submit lane to the shapes that used to spill before the
    defaults flipped: retry-carrying tasks and ref-carrying args
    resident on the submitting node.

    The sustained-submit lane then arms a chaos ``sched_tick slow``
    plan (every head scheduler tick delayed by ``head_tick_delay_s``)
    and has a node-A task submit+get ``n_submit`` nested tasks: with
    local dispatch on, the node's LocalScheduler admits them without
    waiting out the slowed head tick.

    Returns {mode, p2p, n_calls, total, actor_seconds, calls_p2p,
    head_fallback, submit_seconds, local_dispatch, spillback,
    head_skip}. ``total`` must match between arms (equal results)."""
    import ray_tpu
    from ray_tpu import chaos
    from ray_tpu._private import worker as worker_mod
    from ray_tpu.cluster_utils import Cluster

    overrides = ({} if p2p is None else
                 {"local_dispatch": bool(p2p), "actor_p2p": bool(p2p)})
    two_level_on = p2p is None or bool(p2p)
    ray_tpu.shutdown()
    c = Cluster(initialize_head=True,
                head_node_args=dict(
                    num_cpus=2, num_workers=2, scheduler="tensor",
                    _system_config=overrides))
    try:
        c.add_node(num_cpus=2, remote=True, resources={"a": 100.0})
        c.add_node(num_cpus=2, remote=True, resources={"b": 100.0})
        c.wait_for_nodes()
        w = worker_mod.get_worker()

        @ray_tpu.remote(resources={"b": 1.0})
        class _Acc:
            def __init__(self):
                self.total = 0

            def bump(self, x):
                self.total += x
                return self.total

        actor = _Acc.remote()
        ray_tpu.get(actor.bump.remote(0), timeout=60.0)  # placed + live

        @ray_tpu.remote(resources={"a": 1.0})
        def caller(h, n):
            import ray_tpu
            out = 0
            for _ in range(n):
                out = ray_tpu.get(h.bump.remote(1), timeout=60.0)
            return out

        t0 = time.perf_counter()
        total = ray_tpu.get(caller.remote(actor, n_calls),
                            timeout=300.0)
        actor_dt = time.perf_counter() - t0
        # sequenced p2p_done receipts ride the outbox; give the last
        # few a beat to land before reading the counters
        deadline = time.monotonic() + 10.0
        while (two_level_on and time.monotonic() < deadline
               and (w.two_level_stats["p2p"]
                    + w.two_level_stats["head_fallback"]) < n_calls - 1):
            time.sleep(0.05)
        stats = dict(w.two_level_stats)

        # the on/off A/B keeps the historical admissible shape (default
        # resources, no retries) so arms stay comparable release to
        # release; the default-config arm mixes in the shapes the
        # LocalScheduler used to spill and now admits — retry-carrying
        # tasks and ref-carrying args resident on the node
        @ray_tpu.remote(max_retries=0)
        def _nested_noop():
            return 1

        @ray_tpu.remote  # default task_max_retries: retry-carrying
        def _nested_retry():
            return 1

        @ray_tpu.remote(max_retries=0)
        def _nested_ref(blob):
            return 1 if blob else 0

        @ray_tpu.remote(resources={"a": 1.0})
        def submitter(n, mixed):
            import ray_tpu
            if not mixed:
                return sum(ray_tpu.get(
                    [_nested_noop.remote() for _ in range(n)],
                    timeout=120.0))
            # over inline_object_max_bytes -> sealed in node A's arena,
            # the shape the residency check admits locally
            data = ray_tpu.put(b"x" * (256 * 1024))
            refs = []
            for i in range(n):
                kind = i % 3
                if kind == 0:
                    refs.append(_nested_noop.remote())
                elif kind == 1:
                    refs.append(_nested_retry.remote())
                else:
                    refs.append(_nested_ref.remote(data))
            return sum(ray_tpu.get(refs, timeout=120.0))

        chaos.arm(chaos.FaultPlan(7))
        chaos.set_probability("sched_tick", 1.0,
                              delay_s=head_tick_delay_s)
        try:
            t0 = time.perf_counter()
            n_done = ray_tpu.get(
                submitter.remote(n_submit, p2p is None), timeout=300.0)
            submit_dt = time.perf_counter() - t0
        finally:
            chaos.disarm()
        stats_after = dict(w.two_level_stats)
        ld = int(stats_after["local_dispatch"])
        sb = int(stats_after["spillback"])
        return {
            "mode": "default" if p2p is None else
                    ("on" if p2p else "off"),
            "p2p": two_level_on,
            "n_calls": n_calls,
            "total": int(total),
            "actor_seconds": round(actor_dt, 3),
            "calls_p2p": int(stats["p2p"]),
            "head_fallback": int(stats["head_fallback"]),
            "n_submit": int(n_done),
            "submit_seconds": round(submit_dt, 3),
            "local_dispatch": ld,
            "spillback": sb,
            "head_skip": (round(ld / (ld + sb), 3) if ld + sb else None),
        }
    finally:
        c.shutdown()


def qos_ab(qos: bool, n_per_tenant: int = 30,
           n_submit: int = 16) -> Dict[str, Any]:
    """One arm of the QoS-plane A/B: a head + 1-remote-node cluster
    under a mixed two-tenant load (tenant "prod" at priority tier 1,
    weight 3; tenant "batch" at tier 0, weight 1), every task stamping
    its completion wall-clock so the driver gets honest per-task
    latency (submit -> finish) without serializing the gets.

    With ``qos=True`` the head drains by strict tier + weighted
    fair-share and resview frames carry the watermark (a queued tier-1
    backlog makes node daemons spill tier-0 nested submissions). With
    ``qos=False`` — the escape hatch, byte-for-byte the pre-QoS wire —
    the same submission mix runs FIFO. Preemption grace is set long so
    neither arm's latencies include kill/respawn time (the preemption
    path has its own tests).

    The head-skip lane runs DURING the load: a node-resident task
    submits ``n_submit`` nested no-ops, so the on-arm number shows
    what the watermark costs local admission under tier pressure.

    Returns {mode, n_tasks, seconds, tasks_per_sec, per-tier p50/p99
    ms, head_skip, local_dispatch, spillback, spillback_tier,
    preemptions, total}. ``total`` must match between arms."""
    import ray_tpu
    from ray_tpu._private import worker as worker_mod
    from ray_tpu.cluster_utils import Cluster

    overrides: Dict[str, Any] = {"qos": bool(qos)}
    if qos:
        overrides["tenant_quotas"] = '{"prod": 3, "batch": 1}'
        overrides["preempt_grace_s"] = 300.0
    ray_tpu.shutdown()
    c = Cluster(initialize_head=True,
                head_node_args=dict(
                    num_cpus=2, num_workers=2, scheduler="tensor",
                    _system_config=overrides))
    try:
        c.add_node(num_cpus=2, remote=True, resources={"a": 100.0})
        c.wait_for_nodes()
        w = worker_mod.get_worker()

        @ray_tpu.remote(priority=1, tenant="prod")
        def prod_task(x):
            import time
            time.sleep(0.01)
            return (x, time.time())

        @ray_tpu.remote(tenant="batch")
        def batch_task(x):
            import time
            time.sleep(0.01)
            return (x, time.time())

        @ray_tpu.remote(max_retries=0)
        def _nested_noop():
            return 1

        @ray_tpu.remote(resources={"a": 1.0})
        def submitter(n):
            import ray_tpu
            return sum(ray_tpu.get(
                [_nested_noop.remote() for _ in range(n)],
                timeout=120.0))

        # one saturating burst, tiers interleaved adversarially
        # (every batch submitted before its prod peer), with the
        # head-skip submitter racing the same window
        refs, submits, tiers = [], [], []
        t0 = time.perf_counter()
        sub_ref = submitter.remote(n_submit)
        for i in range(n_per_tenant):
            submits.append(time.time())
            refs.append(batch_task.remote(i))
            tiers.append(0)
            submits.append(time.time())
            refs.append(prod_task.remote(i))
            tiers.append(1)
        out = ray_tpu.get(refs, timeout=300.0)
        wall = time.perf_counter() - t0
        n_done = ray_tpu.get(sub_ref, timeout=120.0)

        lat_ms: Dict[int, list] = {0: [], 1: []}
        total = 0
        for (x, end), t_sub, tier in zip(out, submits, tiers):
            total += x
            lat_ms[tier].append((end - t_sub) * 1000.0)

        def _pct(vals, q):
            vals = sorted(vals)
            return round(vals[min(len(vals) - 1,
                                  int(q * (len(vals) - 1)))], 2)

        stats = dict(w.two_level_stats)
        ld = int(stats.get("local_dispatch", 0))
        sb = int(stats.get("spillback", 0))
        plane = w.qos_plane
        return {
            "mode": "on" if qos else "off",
            "n_tasks": 2 * n_per_tenant,
            "seconds": round(wall, 3),
            "tasks_per_sec": round(2 * n_per_tenant / wall, 1),
            "tier0_p50_ms": _pct(lat_ms[0], 0.50),
            "tier0_p99_ms": _pct(lat_ms[0], 0.99),
            "tier1_p50_ms": _pct(lat_ms[1], 0.50),
            "tier1_p99_ms": _pct(lat_ms[1], 0.99),
            "n_submit": int(n_done),
            "local_dispatch": ld,
            "spillback": sb,
            "spillback_tier": int(stats.get("spillback:tier", 0)),
            "head_skip": (round(ld / (ld + sb), 3) if ld + sb else None),
            "preemptions": (plane.stats()["preemptions_total"]
                            if plane is not None else 0),
            "total": int(total),
        }
    finally:
        c.shutdown()


def rl_rollout_throughput(iters: int = 4) -> Dict[str, Any]:
    """IMPALA's async pipeline under load: env-steps/s streamed from
    runner actors through the object store into the V-trace learner
    (the rollout-throughput line). Run with
    JAX_PLATFORMS=cpu — the policy is a toy MLP and stepping is host
    work: this measures the host pipeline, not an accelerator."""
    import ray_tpu
    from ray_tpu.rllib import IMPALAConfig

    ray_tpu.shutdown()
    ray_tpu.init(num_workers=8, scheduler="tensor")
    try:
        algo = IMPALAConfig(num_env_runners=4, num_envs_per_runner=8,
                            rollout_len=64, updates_per_iter=8,
                            seed=0).build()
        algo.train()  # warm the jits + pipeline
        steps = 0
        secs = 0.0
        returns = []
        for _ in range(iters):
            m = algo.train()
            steps += m["num_env_steps"]
            secs += m["num_env_steps"] / m["env_steps_per_sec"]
            if m["num_episodes"]:
                returns.append(m["episode_return_mean"])
        algo.stop()
    finally:
        ray_tpu.shutdown()
    return {
        "env_steps_per_sec": round(steps / max(secs, 1e-9), 1),
        "env_steps": steps,
        "episode_return_mean": (round(sum(returns) / len(returns), 1)
                                if returns else None),
    }


def data_pipeline_throughput(num_blocks: int = 100_000,
                             rows_per_block: int = 10,
                             num_workers: int = 8) -> Dict[str, Any]:
    """BASELINE config 3 through the REAL library: a map_batches pipeline
    over num_blocks blocks via the public ray_tpu.data API (streaming
    executor, backpressure, fused read+map), not a synthetic DAG."""
    import ray_tpu
    from ray_tpu import data

    ray_tpu.shutdown()
    ray_tpu.init(num_workers=num_workers, scheduler="tensor")
    try:
        n_rows = num_blocks * rows_per_block
        ds = data.range(n_rows, parallelism=num_blocks).map_batches(
            lambda b: [x * 2 for x in b])
        t0 = time.perf_counter()
        total = ds.count()
        dt = time.perf_counter() - t0
        assert total == n_rows, (total, n_rows)
        stats = ds.stats()
    finally:
        ray_tpu.shutdown()
    return {
        "num_blocks": num_blocks,
        "rows": n_rows,
        "seconds": dt,
        "blocks_per_sec": num_blocks / dt,
        "rows_per_sec": n_rows / dt,
        "stages": stats["stages"] if stats else None,
    }


def data_ingest_overlap(num_blocks: int = 96, rows_per_block: int = 50,
                        sleep_s: float = 0.025, consumers: int = 2,
                        num_workers: int = 8) -> Dict[str, Any]:
    """Streaming-split ingest vs. materialize-then-split, same pipeline
    in the same run. The map stage sleeps per block (a stand-in for
    real decode/transform work that releases the GIL, so thread
    workers overlap): the materialized baseline pays the WHOLE
    pipeline before its first batch; streaming_split hands consumers
    block 0 as soon as it finishes. Reports both time-to-first-batch
    values and the measured producer/consumer overlap fraction."""
    import threading

    import ray_tpu
    from ray_tpu import data
    from ray_tpu.data import block as blk

    ray_tpu.shutdown()
    ray_tpu.init(num_workers=num_workers, scheduler="tensor")
    try:
        def make_ds():
            def slow(b, _s=sleep_s):
                time.sleep(_s)
                return [x * 2 for x in b]

            return data.range(num_blocks * rows_per_block,
                              parallelism=num_blocks).map_batches(slow)

        # warm the pool + jit-free paths so neither side pays spin-up
        data.range(num_workers * 4, parallelism=num_workers * 4).count()

        # baseline: materialize, split by rank, first batch of shard 0
        t0 = time.perf_counter()
        refs = make_ds().materialize().block_refs
        ray_tpu.get(refs[0])
        ttfb_mat = time.perf_counter() - t0
        t_mat = time.perf_counter() - t0

        # streaming: identical pipeline through streaming_split
        shards = make_ds().streaming_split(consumers, equal=True)
        ttfb = [None] * consumers
        rows = [0] * consumers

        def drain(i: int, t_start: float):
            for b in shards[i].iter_batches():
                if ttfb[i] is None:
                    ttfb[i] = time.perf_counter() - t_start
                rows[i] += blk.block_rows(b)

        t1 = time.perf_counter()
        threads = [threading.Thread(target=drain, args=(i, t1))
                   for i in range(consumers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_stream = time.perf_counter() - t1
        split_stats = shards[0].stats()
        coord = shards[0].coordinator
        coord.shutdown()
        total_rows = num_blocks * rows_per_block
        assert sum(rows) == total_rows, (rows, total_rows)
        ttfb_stream = min(t for t in ttfb if t is not None)
    finally:
        ray_tpu.shutdown()
    return {
        "num_blocks": num_blocks,
        "rows": total_rows,
        "consumers": consumers,
        "ttfb_materialize_s": round(ttfb_mat, 4),
        "ttfb_streaming_s": round(ttfb_stream, 4),
        "ttfb_speedup": round(ttfb_mat / max(ttfb_stream, 1e-9), 1),
        "overlap_fraction": split_stats["overlap_fraction"],
        "materialize_total_s": round(t_mat, 4),
        "streaming_total_s": round(t_stream, 4),
        "streaming_blocks_per_sec": round(num_blocks / t_stream, 1),
        "backpressure_wait_s": split_stats["backpressure_wait_s"],
    }


def _arrow_data_bench(make_ds, warm_op, total_mb: int, num_blocks: int,
                      num_workers: int, arena_mult: int,
                      payload_mult: int,
                      worker_mode: str = "process",
                      best_of: int = 1) -> Dict[str, Any]:
    """Shared harness for the Arrow data-plane benchmarks: sized shm
    arena (the default 256 MB would thrash the spill tier and measure
    disk), a warm-up dataset to absorb worker spin-up and per-worker
    pyarrow imports (hundreds of ms each, serialized on small hosts),
    then a timed iter_batches pass with honest block-nbytes accounting.
    payload_mult: 2 counts in+out payload (map), 1 counts output only
    (exchange). best_of reruns the timed pass and keeps the fastest
    (page-cache warming on loaded single-CPU hosts dominates trial 0)."""
    import numpy as np
    import pyarrow as pa

    import ray_tpu
    from ray_tpu import data
    from ray_tpu.data import block as blk

    ray_tpu.shutdown()
    cfg = {"worker_mode": worker_mode}
    if worker_mode == "process":
        cfg["object_store_memory"] = (max(arena_mult * total_mb, 512)
                                      * 1024 * 1024)
    ray_tpu.init(num_workers=num_workers, scheduler="tensor",
                 _system_config=cfg)
    try:
        n_rows = total_mb * 1024 * 1024 // 8
        table = pa.table({"x": np.arange(n_rows, dtype=np.int64)})
        warm = pa.table({"x": np.arange(num_workers * 4, dtype=np.int64)})
        warm_op(data.from_arrow(warm, parallelism=num_workers * 4)).count()
        time.sleep(2.0)
        dt = None
        for _ in range(max(1, best_of)):
            ds = make_ds(data.from_arrow(table, parallelism=num_blocks))
            t0 = time.perf_counter()
            out_bytes = 0
            rows = 0
            for b in ds.iter_batches():
                out_bytes += blk.block_nbytes(b)
                rows += blk.block_rows(b)
            trial = time.perf_counter() - t0
            assert rows == n_rows, (rows, n_rows)
            dt = trial if dt is None else min(dt, trial)
    finally:
        ray_tpu.shutdown()
    return {
        "total_mb": round(payload_mult * out_bytes / 1e6, 1),
        "seconds": dt,
        "mb_per_sec": round(payload_mult * out_bytes / 1e6 / dt, 1),
        "num_blocks": num_blocks,
    }


def data_arrow_throughput(total_mb: int = 256, num_blocks: int = 64,
                          num_workers: int = 8) -> Dict[str, Any]:
    """Columnar path MB/s: Arrow blocks flow through a numpy-format
    map_batches in PROCESS workers (shm arena data plane; the sizes are
    real block nbytes, so MB/s is honest in+out payload throughput)."""
    def mapped(ds):
        return ds.map_batches(lambda cols: {"x": cols["x"] * 2},
                              batch_format="numpy")

    def warm(ds):
        return ds.map_batches(lambda cols: cols, batch_format="numpy")

    return _arrow_data_bench(mapped, warm, total_mb, num_blocks,
                             num_workers, arena_mult=4, payload_mult=2)


def data_shuffle_throughput(total_mb: int = 128, num_blocks: int = 16,
                            num_workers: int = 0) -> Dict[str, Any]:
    """Columnar all-to-all MB/s: random_shuffle over Arrow blocks.

    The exchange is two derived-permutation (Feistel PRP) gather
    stages running in the native C++ kernel (_native/exchange.cc) —
    rows never materialize, permutations are never stored. Runs in the
    framework's default thread mode (single-host shuffles have no
    reason to pay IPC) with workers sized to the host's cores; a
    best-of-3 absorbs page-cache warmup on loaded hosts."""
    import os

    def shuffled(ds, _seed=[0]):
        _seed[0] += 1
        return ds.random_shuffle(seed=_seed[0])

    nw = num_workers or max(2, min(8, os.cpu_count() or 2))
    return _arrow_data_bench(shuffled, shuffled, total_mb, num_blocks,
                             nw, arena_mult=6, payload_mult=1,
                             worker_mode="thread", best_of=3)


def data_join_throughput(total_mb: int = 64, num_blocks: int = 8,
                         num_workers: int = 0) -> Dict[str, Any]:
    """Columnar hash-join MB/s: key-partitioned exchange + Arrow hash
    join per reducer (data/_streaming.py join_exchange). Payload is
    the JOINED output's nbytes; thread mode + best-of-3 like the
    shuffle bench."""
    import os
    import time as _time

    import numpy as np
    import pyarrow as pa

    import ray_tpu
    from ray_tpu import data
    from ray_tpu.data import block as blk

    ray_tpu.shutdown()
    nw = num_workers or max(2, min(8, os.cpu_count() or 2))
    ray_tpu.init(num_workers=nw, scheduler="tensor",
                 _system_config={"worker_mode": "thread"})
    try:
        n_rows = total_mb * 1024 * 1024 // 16  # two int64 cols
        keys = np.arange(n_rows, dtype=np.int64)
        left_t = pa.table({"k": keys, "v": keys * 2})
        right_t = pa.table({"k": keys, "w": keys * 3})
        dt = None
        out_bytes = rows = 0
        for _ in range(3):
            left = data.from_arrow(left_t, parallelism=num_blocks)
            right = data.from_arrow(right_t, parallelism=num_blocks)
            t0 = _time.perf_counter()
            out_bytes = 0
            rows = 0
            for b in left.join(right, on="k")._execute():
                out_bytes += blk.block_nbytes(b)
                rows += blk.block_rows(b)
            trial = _time.perf_counter() - t0
            assert rows == n_rows, (rows, n_rows)
            dt = trial if dt is None else min(dt, trial)
    finally:
        ray_tpu.shutdown()
    return {
        "total_mb": round(out_bytes / 1e6, 1),
        "seconds": dt,
        "mb_per_sec": round(out_bytes / 1e6 / dt, 1),
        "num_blocks": num_blocks,
    }


def _flops_per_step(compiled, params, batch: int, seq: int) -> float:
    """XLA's own FLOP count for the compiled step; analytic fallback."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        if flops > 0:
            return flops
    except Exception:
        pass
    # Analytic fallback: fwd+bwd ~ 6 * n_params * n_tokens.
    import jax

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    return 6.0 * n_params * batch * seq


def model_mfu(d_model: int = 2048, n_layers: int = 8, n_heads: int = 16,
              n_kv_heads: int = 8, d_ff: int = 5632,
              vocab_size: int = 32_768, seq_len: int = 2048,
              batch_size: int = 8, steps: int = 10,
              smoke: bool = False,
              remat_policy: str = "dots") -> Dict[str, Any]:
    """Flagship transformer train-step perf on the default device.

    Runs at the batch size it is given: a batch that does not fit the
    device raises. Returns step_ms, tokens_per_sec, flops_per_step,
    mfu, device info.
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import train_step as ts
    from ray_tpu.models.transformer import Transformer, TransformerConfig

    if smoke:
        sm = SMOKE_MODEL
        d_model, n_layers = sm["d_model"], sm["n_layers"]
        n_heads, n_kv_heads = sm["n_heads"], sm["n_kv_heads"]
        d_ff, vocab_size = sm["d_ff"], sm["vocab_size"]
        seq_len, batch_size, steps = (sm["seq_len"], sm["batch_size"],
                                      sm["steps"])

    dev = jax.devices()[0]
    cfg = TransformerConfig(vocab_size=vocab_size, d_model=d_model,
                            n_layers=n_layers, n_heads=n_heads,
                            n_kv_heads=n_kv_heads, d_ff=d_ff,
                            max_seq_len=seq_len,
                            remat=not smoke,
                            remat_policy=remat_policy)
    model = Transformer(cfg)
    optimizer = ts.make_optimizer()
    step_fn = ts.make_train_step(model, optimizer)

    # random tokens: constant data (e.g. all-ones) is memorized within
    # the warmup+timing steps and collapses the loss to 0
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (batch_size, seq_len), 0, vocab_size,
                                dtype=jnp.int32)
    params = jax.jit(
        lambda rng: model.init(rng, tokens)["params"])(
            jax.random.PRNGKey(0))
    opt_state = jax.jit(optimizer.init)(params)
    step = jax.jit(step_fn, donate_argnums=(0, 1))
    compiled = step.lower(params, opt_state, {"tokens": tokens}).compile()
    flops = _flops_per_step(compiled, params, batch_size, seq_len)
    # Warmup (first run may still include transfer/layout work), then
    # time K chained steps; fetching the last loss is the barrier that
    # ends the chain.
    params, opt_state, metrics = compiled(params, opt_state,
                                          {"tokens": tokens})
    loss_host = float(jax.device_get(metrics["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, metrics = compiled(
            params, opt_state, {"tokens": tokens})
    loss_host = float(jax.device_get(metrics["loss"]))
    dt = (time.perf_counter() - t0) / steps

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    # utilization is a device metric: off the TPU it is not measured
    peak = (device_peaks(dev.device_kind)["bf16_flops"]
            if dev.platform == "tpu" else None)
    # MFU convention: USEFUL model flops (6·N·D) over peak — remat
    # recompute does not count. The compiled program's own count (which
    # does include recompute) is the hardware utilization, reported as
    # hfu alongside.
    model_flops = 6.0 * n_params * batch_size * seq_len
    mfu = (model_flops / dt / peak) if peak else None
    hfu = (flops / dt / peak) if peak else None
    return {
        "device": dev.device_kind,
        "platform": dev.platform,
        "n_params": int(n_params),
        "batch_size": batch_size,
        "seq_len": seq_len,
        "step_ms": dt * 1e3,
        "tokens_per_sec": batch_size * seq_len / dt,
        "flops_per_step": flops,
        "model_flops_per_step": model_flops,
        "model_flops_per_sec": model_flops / dt,
        "hardware_flops_per_sec": flops / dt,
        "peak_flops": peak,
        "mfu": mfu,
        "hfu": hfu,
        "loss": loss_host,
    }


def llm_decode_throughput(smoke: bool = False,
                          batch_slots: Optional[int] = None) -> dict:
    """Paged-attention decode tokens/s on the attached device
    (models/inference.py engine, full continuous batch). The analog of
    the reference serving stack's decode-throughput benchmark.

    batch_slots overrides the continuous-batch slot count (the bench
    sweeps 32/64/128 when budget allows: decode matmuls scale
    near-linearly with slots on the v5e — 32→10.2k, 64→14.9k,
    128→19.2k tok/s measured at 127M params in round 4)."""
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.inference import InferenceConfig, InferenceEngine
    from ray_tpu.models.transformer import Transformer, TransformerConfig

    if smoke:
        sd = SMOKE_DECODE
        mcfg = TransformerConfig(
            vocab_size=sd["vocab_size"], d_model=sd["d_model"],
            n_layers=sd["n_layers"], n_heads=sd["n_heads"],
            n_kv_heads=sd["n_kv_heads"], d_ff=sd["d_ff"],
            max_seq_len=sd["max_seq_len"])
        batch, new_tokens, pages = (sd["batch"], sd["new_tokens"],
                                    sd["pages"])
    else:
        # serving-shaped model: head_dim 128 keeps the Pallas kernel on
        # full-width lanes. 64 continuous-batch slots x 128 new tokens:
        # the r3 config (32x64) left the MXU under-fed — the decode
        # matmuls scale near-linearly to 64 slots on this chip
        # (10.2k -> 17.9k tok/s measured) and longer decodes amortize
        # the per-burst host work
        mcfg = TransformerConfig(vocab_size=32000, d_model=1024,
                                 n_layers=8, n_heads=8, n_kv_heads=4,
                                 d_ff=2816, max_seq_len=2048)
        batch, new_tokens, pages = 64, 128, 1024
    if batch_slots is not None:
        batch = batch_slots
        pages = max(pages, batch * 16)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    icfg = InferenceConfig(batch_size=batch, page_size=16,
                           max_pages_per_seq=16, num_pages=pages,
                           prefill_buckets=(16,), max_new_tokens=new_tokens)
    engine = InferenceEngine(params, mcfg, icfg)
    try:
        # warm compiles with the SAME admission/chunk pattern as the
        # timed run (the batched prefill specializes on group size, the
        # decode programs on chunk size)
        warm = [engine.submit([i + 1] * 4, new_tokens)
                for i in range(batch)]
        for f in warm:
            f.result(timeout=900)
        t0 = time.perf_counter()
        futs = [engine.submit([i + 1] * 4, new_tokens)
                for i in range(batch)]
        total = sum(len(f.result(timeout=600)) for f in futs)
        dt = time.perf_counter() - t0
    finally:
        engine.shutdown()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    return {
        "tokens_per_sec": total / dt,
        "batch_slots": batch,
        "new_tokens": new_tokens,
        "n_params": int(n_params),
        "seconds": dt,
    }


def serving_ab(disagg: bool, sessions: int = 8, turns: int = 2,
               max_new: int = 48) -> Dict[str, Any]:
    """One arm of the serving-plane A/B: mono (N LLMDeployment
    replicas, prefill and decode share each replica's continuous
    batch) vs disaggregated (1 prefill + 1 decode replica — the same
    TWO replicas of hardware) under a mixed interactive load:
    ``sessions`` concurrent sessions, each streaming ``turns`` turns
    of ``max_new`` tokens, follow-up turns reusing the session id so
    the disaggregated arm exercises cache-affinity routing.

    Engine batches are deliberately SMALLER than the offered load
    (batch_size=2 per engine, sessions > total slots): in the mono
    arm a new prompt's first token waits for a continuous-batch slot
    behind whole ongoing decodes, while the disaggregated arm streams
    the first token straight off the prefill handoff — the TTFT
    contrast under saturation is exactly what the split buys.

    TTFT is measured CLIENT-side (first non-empty frame) so both arms
    are scored by the same clock. CPU-host caveat: both arms share
    one host's cores, so tokens/s differences are scheduling effects,
    not accelerator effects; the TTFT ordering is the honest signal.

    Returns {mode, sessions, turns, max_new, replicas, ttft_p50_ms/
    p95/p99, tokens_per_sec, tokens_per_sec_per_replica, total_tokens,
    seconds, affinity_hit_rate, kv_bytes, sheds}."""
    import threading

    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.inference import InferenceConfig
    from ray_tpu.models.transformer import Transformer, TransformerConfig
    from ray_tpu.serve import core
    from ray_tpu.serve.llm import build_llm_app, run_disagg_llm

    mcfg = TransformerConfig(vocab_size=128, d_model=32, n_layers=2,
                             n_heads=2, n_kv_heads=2, d_ff=64,
                             max_seq_len=128)
    icfg = InferenceConfig(batch_size=2, page_size=4,
                           max_pages_per_seq=16, num_pages=64,
                           prefill_buckets=(16,),
                           max_new_tokens=max_new, decode_chunk=1)
    model = Transformer(mcfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    replicas = 2  # both arms: two engine-hosting replicas
    ray_tpu.init(num_workers=2)
    try:
        if disagg:
            handle = run_disagg_llm(params, mcfg, icfg,
                                    prefill_replicas=1,
                                    decode_replicas=1)

            def frames(prompt, session):
                return handle.stream_frames(prompt, max_new,
                                            session_id=session)
        else:
            h = serve.run(build_llm_app(params, mcfg, icfg,
                                        num_replicas=replicas))
            st = h._state()

            def frames(prompt, session):
                return core._sticky_stream_frames(st, prompt, max_new,
                                                  start_timeout=300.0)

        # warm the compile caches with the run's own shapes (prefill
        # bucket, decode chunk, KV import) before the timed window;
        # one concurrent stream per replica reaches both mono engines
        warm = [threading.Thread(
            target=lambda i=i: [None for _ in frames([i + 1] * 4, None)])
            for i in range(replicas)]
        for t in warm:
            t.start()
        for t in warm:
            t.join()
        core.metrics.reset()

        results: list = []
        lock = threading.Lock()

        def run_session(i: int) -> None:
            session = f"bench-s{i}"
            prompt = [(i * 7 + j) % 100 + 1 for j in range(6)]
            for _turn in range(turns):
                t0 = time.perf_counter()
                ttft = None
                n = 0
                for fr in frames(prompt, session):
                    toks = fr.get("tokens") or ()
                    if toks and ttft is None:
                        ttft = time.perf_counter() - t0
                    n += len(toks)
                with lock:
                    results.append((ttft, n))

        threads = [threading.Thread(target=run_session, args=(i,),
                                    daemon=True)
                   for i in range(sessions)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start

        ttfts = sorted(t for t, _ in results if t is not None)

        def _pct(q: float) -> Optional[float]:
            if not ttfts:
                return None
            return ttfts[min(len(ttfts) - 1, int(q * len(ttfts)))]

        total_tokens = sum(n for _, n in results)
        snap = core.metrics.snapshot()
        aff = snap["affinity_hit"] + snap["affinity_miss"]
        return {
            "mode": "disagg" if disagg else "mono",
            "sessions": sessions,
            "turns": turns,
            "max_new": max_new,
            "replicas": replicas,
            "n_streams": len(results),
            "ttft_p50_ms": round(_pct(0.50) * 1e3, 2) if ttfts else None,
            "ttft_p95_ms": round(_pct(0.95) * 1e3, 2) if ttfts else None,
            "ttft_p99_ms": round(_pct(0.99) * 1e3, 2) if ttfts else None,
            "tokens_per_sec": round(total_tokens / wall, 1),
            "tokens_per_sec_per_replica":
                round(total_tokens / wall / replicas, 1),
            "total_tokens": total_tokens,
            "seconds": round(wall, 3),
            "affinity_hit_rate": (round(snap["affinity_hit"] / aff, 3)
                                  if aff else None),
            "kv_bytes": snap["kv_bytes"],
            "sheds": snap["admission_shed"],
        }
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
