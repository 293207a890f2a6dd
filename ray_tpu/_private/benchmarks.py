"""Scheduling benchmark harness — the 5 BASELINE configs + the north star.

Each config builds a task DAG in the array form the scheduler kernels
consume (see scheduler/kernels.py) and measures the AGGREGATE SCHEDULING
OVERHEAD: the time the jitted instant-completion tick kernel needs to
drive the whole DAG from submitted to done — every ready-set computation,
every node-assignment decision, every dependency-wave propagation — with
task execution simulated as instantaneous. This isolates exactly what the
reference measures as scheduler throughput (its per-task
ClusterTaskManager/LocalTaskManager C++ event-loop path, amortized by
lease reuse; see SURVEY.md §3.2) and what BASELINE.md's north star bounds:
1M-task fan-out DAG < 10 ms aggregate on one TPU chip.

Configs (BASELINE.md):
  1. fanout:      10 k no-op tasks, zero deps
  2. map_reduce:  100 k tasks, 2-level ObjectRef deps (north-star shape at
                  1 M tasks = ``north_star``)
  3. pipeline:    map_batches-style wide DAG (stages of uniform demand)
  4. actor_heavy: 1 k actors × 1 k calls (per-actor ordered chains — the
                  lease-reuse path; deep narrow DAG, many ticks)
  5. ppo:         rollout/learn DAG with heterogeneous demands (CPU
                  rollouts feeding TPU learner tasks, placement-grouped)
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Tuple

import numpy as np

from ray_tpu._private.scheduler import kernels
from ray_tpu._private.scheduler.kernels import DONE, WAITING


@dataclasses.dataclass
class BenchGraph:
    name: str
    indeg: np.ndarray      # [C] int32
    cls: np.ndarray        # [C] int32
    demands: np.ndarray    # [K, R] float32
    src: np.ndarray        # [E] int32
    dst: np.ndarray        # [E] int32 (must be sorted ascending)
    cap: np.ndarray        # [N, R] float32
    max_ticks: int
    pin: Optional[np.ndarray] = None  # [C] int32, -1 = schedule normally


def _nodes(n: int, cpu: float, tpu: float = 0.0) -> np.ndarray:
    cap = np.zeros((n, 4), dtype=np.float32)
    cap[:, 0] = cpu
    cap[:, 1] = tpu
    return cap


def build_fanout(num_tasks: int = 10_000, num_nodes: int = 64) -> BenchGraph:
    """Config 1: embarrassingly parallel no-op tasks, zero deps."""
    per_node = -(-num_tasks // num_nodes)
    return BenchGraph(
        name=f"fanout_{num_tasks}",
        indeg=np.zeros(num_tasks, dtype=np.int32),
        cls=np.zeros(num_tasks, dtype=np.int32),
        demands=np.asarray([[1, 0, 0, 0]], dtype=np.float32),
        src=np.zeros(0, dtype=np.int32),
        dst=np.zeros(0, dtype=np.int32),
        cap=_nodes(num_nodes, float(per_node)),
        max_ticks=4,
    )


def build_map_reduce(num_tasks: int = 100_000, fan_in: int = 100,
                     num_nodes: int = 64) -> BenchGraph:
    """Config 2 / north star: 2-level DAG. num_tasks total; the last
    num_tasks/(fan_in+1) tasks are reducers, each depending on fan_in maps."""
    num_reduce = num_tasks // (fan_in + 1)
    num_map = num_tasks - num_reduce
    c = num_tasks
    indeg = np.zeros(c, dtype=np.int32)
    # reducer j occupies slot num_map + j and reads maps [j*fan_in, ...)
    rj = np.arange(num_reduce, dtype=np.int64)
    starts = rj * fan_in
    src = (starts[:, None] + np.arange(fan_in)[None, :]).reshape(-1)
    src = np.minimum(src, num_map - 1).astype(np.int32)
    dst = np.repeat(num_map + rj, fan_in).astype(np.int32)
    np.add.at(indeg, dst, 1)
    per_node = -(-num_map // num_nodes)
    return BenchGraph(
        name=f"map_reduce_{num_tasks}",
        indeg=indeg,
        cls=np.zeros(c, dtype=np.int32),
        demands=np.asarray([[1, 0, 0, 0]], dtype=np.float32),
        src=src, dst=dst,
        cap=_nodes(num_nodes, float(per_node)),
        max_ticks=8,
    )


def build_pipeline(num_stages: int = 4, width: int = 25_000,
                   num_nodes: int = 64) -> BenchGraph:
    """Config 3: map_batches-style pipeline — ``width`` parallel block
    chains through ``num_stages`` uniform-demand operators."""
    c = num_stages * width
    idx = np.arange(c, dtype=np.int64)
    stage = idx // width
    indeg = (stage > 0).astype(np.int32)
    has_edge = stage < num_stages - 1
    src = idx[has_edge].astype(np.int32)
    dst = (idx[has_edge] + width).astype(np.int32)
    per_node = -(-width // num_nodes)
    return BenchGraph(
        name=f"pipeline_{num_stages}x{width}",
        indeg=indeg,
        cls=np.zeros(c, dtype=np.int32),
        demands=np.asarray([[1, 0, 0, 0]], dtype=np.float32),
        src=src, dst=dst,
        cap=_nodes(num_nodes, float(per_node)),
        max_ticks=num_stages + 2,
    )


def build_actor_heavy(num_actors: int = 1000, calls: int = 1000,
                      num_nodes: int = 64) -> BenchGraph:
    """Config 4: 1k actors × 1k calls. Models the reference's actor path
    faithfully: actor CREATION is a scheduled task (resource-bearing);
    method CALLS are pinned to the actor's node and consume no scheduler
    resources — in the reference, calls go directly to the actor's leased
    worker over its ordered queue and never re-enter the scheduler (the
    lease-reuse mechanism that makes actor calls cheap). Each call still
    depends on its actor's creation completing, so the kernel processes
    creation wave -> 1M-call pinned assignment wave."""
    c = num_actors * (calls + 1)
    # slots [0, num_actors) = creations; rest = calls grouped by actor
    creation = np.arange(num_actors, dtype=np.int64)
    call_idx = np.arange(num_actors * calls, dtype=np.int64)
    call_actor = call_idx // calls
    call_slot = num_actors + call_idx
    indeg = np.zeros(c, dtype=np.int32)
    indeg[call_slot] = 1
    src = call_actor.astype(np.int32)          # creation -> each call
    dst = call_slot.astype(np.int32)           # sorted ascending
    cls = np.zeros(c, dtype=np.int32)
    cls[call_slot] = 1                         # calls: zero-demand class
    pin = np.full(c, -1, dtype=np.int32)
    pin[call_slot] = (call_actor % num_nodes).astype(np.int32)
    per_node = -(-num_actors // num_nodes)
    return BenchGraph(
        name=f"actor_{num_actors}x{calls}",
        indeg=indeg,
        cls=cls,
        demands=np.asarray([[1, 0, 0, 0], [0, 0, 0, 0]], dtype=np.float32),
        src=src, dst=dst,
        cap=_nodes(num_nodes, float(per_node)),
        max_ticks=4,
        pin=pin,
    )


def build_ppo(num_rollout: int = 8000, num_learn: int = 80,
              rounds: int = 10, num_nodes: int = 16) -> BenchGraph:
    """Config 5: PPO-style rounds — a wave of CPU rollout tasks feeding a
    wave of TPU learner tasks, repeated; heterogeneous demand classes.

    The learner group is placement-grouped like the reference's RLlib
    LearnerGroup (ray: rllib/core/learner/ — PG of one TPU bundle per
    learner, PACK): the bundle bin-pack solve (pack_bundles_np — the
    GcsPlacementGroupScheduler analog) reserves learner slots at build
    time, and every learner task is PINNED to its bundle's node — the
    per-call fast path for placement-grouped work, with resources held
    by the reservation rather than re-acquired per task."""
    per_round = num_rollout + num_learn
    c = per_round * rounds
    cls = np.zeros(c, dtype=np.int32)
    indeg = np.zeros(c, dtype=np.int32)
    srcs, dsts = [], []
    fan = num_rollout // num_learn
    for r in range(rounds):
        base = r * per_round
        learn0 = base + num_rollout
        cls[learn0:learn0 + num_learn] = 1
        rollouts = base + np.arange(num_rollout, dtype=np.int64)
        learners = learn0 + (np.arange(num_rollout, dtype=np.int64) // fan)
        srcs.append(rollouts)
        dsts.append(learners)
        np.add.at(indeg, learners, 1)
        if r + 1 < rounds:
            next_rollouts = base + per_round + np.arange(
                num_rollout, dtype=np.int64)
            feeders = learn0 + (np.arange(num_rollout, dtype=np.int64)
                                % num_learn)
            srcs.append(feeders)
            dsts.append(next_rollouts)
            np.add.at(indeg, next_rollouts, 1)
    src = np.concatenate(srcs).astype(np.int32)
    dst = np.concatenate(dsts).astype(np.int32)
    cap = _nodes(num_nodes, float(-(-num_rollout // num_nodes)),
                 tpu=float(-(-num_learn // num_nodes)))

    # placement-group the learners: PACK one 1-TPU bundle per learner,
    # pin learner task j (every round) to its bundle's node. Bundle
    # resources are held by the reservation, so the learner class demand
    # is zero per-call (kernel pin-path convention, kernels.py).
    from ray_tpu._private.scheduler.kernels import pack_bundles_np

    bundle_demands = np.tile(np.asarray([[0, 1, 0, 0]], np.float32),
                             (num_learn, 1))
    sol = pack_bundles_np(bundle_demands, cap.copy(), cap, "PACK")
    if sol is None:
        raise RuntimeError("ppo bench: learner placement group cannot fit")
    pin = np.full(c, -1, dtype=np.int32)
    for r in range(rounds):
        learn0 = r * per_round + num_rollout
        pin[learn0:learn0 + num_learn] = sol
    return BenchGraph(
        name=f"ppo_{rounds}r",
        indeg=indeg,
        cls=cls,
        demands=np.asarray([[1, 0, 0, 0], [0, 0, 0, 0]], dtype=np.float32),
        src=src, dst=dst,
        cap=cap,
        max_ticks=2 * rounds + 4,
        pin=pin,
    )


def build_north_star(num_tasks: int = 1_000_000,
                     num_nodes: int = 64) -> BenchGraph:
    """BASELINE.json north star: 1M-task fan-out DAG."""
    g = build_fanout(num_tasks=num_tasks, num_nodes=num_nodes)
    g.name = f"north_star_fanout_{num_tasks}"
    return g


def build_north_star_waves(num_tasks: int = 1_000_000,
                           num_waves: int = 64,
                           num_nodes: int = 64) -> BenchGraph:
    """North-star honesty companion: the same 1M tasks admitted over
    ``num_waves`` dependency waves instead of one flat fan-out. Wave w
    gates on wave w-1's first task, so the kernel must run a full
    ready-set/admission tick PER WAVE — the multi-tick admission cost a
    single-wave fan-out never shows. Capacity is sized to one wave, not
    the whole DAG."""
    per_wave = num_tasks // num_waves
    num_tasks = per_wave * num_waves
    c = num_tasks
    idx = np.arange(c, dtype=np.int64)
    wave = idx // per_wave
    indeg = (wave > 0).astype(np.int32)
    # every task of wave w>0 depends on wave w-1's FIRST task; dst is
    # naturally ascending in this wave-major layout
    has_edge = wave > 0
    src = ((wave[has_edge] - 1) * per_wave).astype(np.int32)
    dst = idx[has_edge].astype(np.int32)
    per_node = -(-per_wave // num_nodes)
    return BenchGraph(
        name=f"north_star_waves_{num_tasks}x{num_waves}",
        indeg=indeg,
        cls=np.zeros(c, dtype=np.int32),
        demands=np.asarray([[1, 0, 0, 0]], dtype=np.float32),
        src=src, dst=dst,
        cap=_nodes(num_nodes, float(per_node)),
        max_ticks=num_waves + 2,
    )


CONFIGS = {
    "fanout": build_fanout,
    "map_reduce": build_map_reduce,
    "pipeline": build_pipeline,
    "actor_heavy": build_actor_heavy,
    "ppo": build_ppo,
    "north_star": build_north_star,
    "north_star_waves": build_north_star_waves,
}


# ----------------------------------------------------------------------
# Runner
# ----------------------------------------------------------------------

def _device_state(g: BenchGraph):
    import jax.numpy as jnp

    pin = (g.pin if g.pin is not None
           else np.full(len(g.indeg), -1, dtype=np.int32))
    # the edge-fire segment_sum assumes dst sorted ascending; sort into
    # locals (never mutate the caller's BenchGraph — callers may hold
    # edge-index views built before this call)
    order = np.argsort(g.dst, kind="stable")
    src, dst = g.src[order], g.dst[order]
    return (
        jnp.full(len(g.indeg), WAITING, dtype=jnp.int8),
        jnp.asarray(g.indeg),
        jnp.asarray(g.cls),
        jnp.asarray(pin),
        jnp.asarray(g.demands),
        jnp.asarray(g.cap),       # avail starts at capacity
        jnp.asarray(g.cap),
        jnp.asarray(src),
        jnp.asarray(dst),
        jnp.zeros(len(src), dtype=bool),
    )


def run_graph(g: BenchGraph, threshold: float = 0.99, repeats: int = 5,
              retries: int = 3, warm_only: bool = False,
              k_lo: int = 1, k_hi: int = 9) -> Dict[str, float]:
    """Per-DAG scheduling time by K-differencing, so that the fixed
    cost of one dispatch and one fetch is not counted as scheduling.
    Protocol (see kernels._jit_bench; ROADMAP S1 replaces it with a
    host clock around block_until_ready):
      - one program runs K whole-DAG drives chained by true data
        dependence (no CSE/hoisting possible);
      - the timed region ends when the tick-count scalar has been
        FETCHED;
      - T(K) = dispatch_and_fetch + K * drive; measure at K=k_lo and
        K=k_hi and difference to cancel the fixed part.
    """
    import jax

    num_classes = int(g.demands.shape[0])
    st = _device_state(g)
    jax.block_until_ready(st)

    def timed(k: int):
        t0 = time.perf_counter()
        total, state = kernels.jax_bench(
            *st, num_classes=num_classes, threshold=threshold,
            max_ticks=g.max_ticks, k_reps=k)
        total = int(total)  # D2H fetch: forces genuine completion
        dt = time.perf_counter() - t0
        return dt, total, state

    def retrying(fn, *a):
        last = None
        for _ in range(retries):
            try:
                return fn(*a)
            except Exception as e:  # retried, then re-raised
                last = e
                time.sleep(0.5)
        raise last

    # warmup / compile both K variants
    _, total_lo, state = retrying(timed, k_lo)
    if not bool((np.asarray(state) == DONE).all()):
        raise RuntimeError(
            f"bench graph {g.name} did not complete in {g.max_ticks} ticks")
    ticks = total_lo // k_lo
    if warm_only:
        retrying(timed, k_hi)
        return {"name": g.name, "tasks": len(g.indeg), "ticks": ticks,
                "scheduling_ms": float("nan"), "tasks_per_sec": float("nan")}
    retrying(timed, k_hi)

    # Sample (lo, hi) back-to-back so both see the same host load, and
    # take the MEDIAN of the positive per-pair differences: a min would
    # keep pairs where the load changed between the two samples
    # (arbitrarily small diffs), a mean would keep the slow outliers.
    diffs = []
    for _ in range(max(repeats, 5)):
        t_lo = retrying(timed, k_lo)[0]
        t_hi = retrying(timed, k_hi)[0]
        diffs.append((t_hi - t_lo) / (k_hi - k_lo))
    positive = sorted(d for d in diffs if d > 0)
    if not positive:
        # a failed measurement must never be reported as a (record-
        # setting) success: every (hi, lo) pair was inverted by noise,
        # so there is no honest number to report
        raise RuntimeError(
            f"bench {g.name}: no positive (K_hi - K_lo) timing pair over "
            f"{len(diffs)} samples; too noisy to measure")
    per_drive = positive[len(positive) // 2]
    n = len(g.indeg)
    return {
        "name": g.name,
        "tasks": n,
        "ticks": ticks,
        "scheduling_ms": per_drive * 1e3,
        "tasks_per_sec": n / per_drive,
    }


def settle_device(threshold_ms: float = 2.0, timeout_s: float = 30.0) -> None:
    """Wait until device dispatch latency returns to its floor.

    Compilation keeps the host busy for a while after it returns;
    measuring during that window would report host noise, not kernel
    time. Spin a trivial jitted dispatch until it is consistently fast
    (or give up after timeout and measure anyway).
    """
    import jax
    import jax.numpy as jnp

    probe = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8)
    jax.block_until_ready(probe(x))
    deadline = time.perf_counter() + timeout_s
    fast = 0
    while time.perf_counter() < deadline and fast < 3:
        t0 = time.perf_counter()
        jax.block_until_ready(probe(x))
        if (time.perf_counter() - t0) * 1e3 < threshold_ms:
            fast += 1
        else:
            fast = 0
            time.sleep(0.2)


def run_all(sizes: str = "full") -> Dict[str, Dict[str, float]]:
    """sizes: 'full' = BASELINE sizes, 'smoke' = tiny CI sizes."""
    if sizes == "smoke":
        graphs = [
            build_fanout(1000, 8),
            build_map_reduce(2020, 100, 8),
            build_pipeline(3, 500, 8),
            build_actor_heavy(50, 20, 8),
            build_ppo(200, 10, 3, 4),
            build_north_star(10_000, 8),
        ]
    else:
        graphs = [
            build_fanout(),
            build_map_reduce(),
            build_pipeline(),
            build_actor_heavy(),
            build_ppo(),
            build_north_star(),
        ]
    # Phase 1: compile-warm every config, THEN time. Interleaving compiles
    # with timed runs leaves the host busy (see settle_device).
    for g in graphs:
        run_graph(g, warm_only=True)
    return {g.name: run_graph(g) for g in graphs}
