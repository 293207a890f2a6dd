#!/usr/bin/env python
"""Headline benchmark — chip-or-fail.

Prints ONE JSON line on stdout with the north-star metric plus
end-to-end numbers:
  {"metric": ..., "value": N, "unit": "ms", "vs_baseline": N,
   "device": {...}, "north_star": {...}, "e2e_tasks_per_sec": {...},
   "mfu": N, ...}

- north star (BASELINE.json): aggregate scheduling overhead for a 1M-task
  fan-out DAG on one TPU chip (target < 10 ms; the reference's per-task
  C++ scheduler path runs ~1M tasks/s cluster-wide, i.e. ~1000 ms for the
  same DAG). vs_baseline = target_ms / measured_ms, so > 1.0 beats it.
- e2e_tasks_per_sec: REAL task throughput through the public API
  (f.remote() -> get), thread and process worker modes (the analog of
  `ray microbenchmark`, ray: python/ray/_private/ray_perf.py).
- mfu / llm_decode: flagship-transformer train-step MFU and
  paged-attention decode throughput on the attached chip.

Contract:
- the device sections run in THIS process, which takes the chip when it
  first asks jax for its devices; every child it starts is CPU jax
  (spawn_env.child_env) — a chip belongs to one process at a time;
- without --smoke a missing TPU is an error (exit 2) before any
  section: a device metric is never measured on the CPU. --smoke runs
  tiny pinned shapes on whatever jax finds (CI), and the record names
  the device it ran on;
- a section that raises is recorded under "sections_failed" and makes
  the exit code 1; the other sections still run;
- the whole run has a wall budget (RAY_TPU_BENCH_BUDGET_S, default
  600 s); every section declares a minimum time estimate and is skipped
  with an explicit reason when the remaining budget cannot cover it;
- the record is INCREMENTAL: after every section the full JSON line so
  far is atomically rewritten to BENCH_PARTIAL.json; SIGTERM/SIGINT
  print the current line to stdout before exiting, so a timeout cannot
  zero the record.

Usage:
  python bench.py            # the one JSON line (all sections; needs a TPU)
  python bench.py --all      # also run the 5 BASELINE configs (stderr)
  python bench.py --smoke    # tiny sizes (CI / CPU)
"""

import json
import os
import signal
import subprocess
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ray_tpu._private import spawn_env  # light import: no jax

_START = time.monotonic()
BUDGET_S = float(os.environ.get("RAY_TPU_BENCH_BUDGET_S", "600"))
PARTIAL_PATH = os.path.join(REPO, "BENCH_PARTIAL.json")

# the one record; sections fill it in, _emit() persists it after each
OUT = {
    "metric": "north_star_1M_fanout_scheduling_overhead",
    "value": None,
    "unit": "ms",
    "vs_baseline": None,
}
SKIPPED = {}
FAILED = {}


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _START)


def _emit(to_stdout: bool = False) -> None:
    """Atomically persist the record so far; optionally print it.

    The partial file plus the SIGTERM handler guarantee that a kill at
    ANY point leaves a complete-as-of-the-last-section record."""
    line = dict(OUT)
    if SKIPPED:
        line["sections_skipped"] = dict(SKIPPED)
    if FAILED:
        line["sections_failed"] = dict(FAILED)
    line["elapsed_s"] = round(time.monotonic() - _START, 1)
    txt = json.dumps(line)
    try:
        tmp = PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            f.write(txt + "\n")
        os.replace(tmp, PARTIAL_PATH)
    except OSError:
        pass
    if to_stdout:
        print(txt)
        sys.stdout.flush()


def _on_term(signum, frame):
    SKIPPED["_terminated"] = f"signal {signum} with {_remaining():.0f}s budget left"
    OUT["terminated_early"] = True
    _emit(to_stdout=True)
    os._exit(0)


def _failed(name: str) -> None:
    """Call from an ``except`` block: the section's traceback goes to
    stderr, its last line into the record, and main() will exit 1."""
    traceback.print_exc()
    exc = sys.exc_info()[1]
    FAILED[name] = f"{type(exc).__name__}: {exc}"


def section(name: str, min_needed: float):
    """Budget gate: returns True when the section should run; records an
    explicit skip reason otherwise (silent truncation reads as 'covered
    everything' when it didn't)."""
    rem = _remaining()
    if rem < min_needed:
        SKIPPED[name] = (f"budget: {rem:.0f}s left < {min_needed:.0f}s "
                         "estimated")
        print(f"  SKIP {name}: {SKIPPED[name]}", file=sys.stderr)
        return False
    return True


_E2E_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from ray_tpu._private import perf
r = perf.e2e_task_throughput(n_tasks={n}, mode={mode!r}, scheduler="tensor",
                             batched={batched}, best_of=3)
print("E2E_JSON:" + json.dumps(r))
"""


def _e2e_subprocess(n: int, mode: str, batched: bool = False,
                    extra_env: dict = None) -> dict:
    """Run one e2e measurement in a fresh interpreter (no jax/XLA heap
    from the device sections; CPU platform — the task path touches no
    accelerator). extra_env lets a section flip config knobs via their
    RAY_TPU_* env overrides (the log_overhead A/B uses it)."""
    env = spawn_env.child_env()
    env.update(extra_env or {})
    code = _E2E_CHILD.format(repo=REPO, n=n, mode=mode, batched=batched)
    timeout = max(30.0, min(300.0, _remaining() - 10.0))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith("E2E_JSON:"):
            return json.loads(line[len("E2E_JSON:"):])
    raise RuntimeError(
        f"e2e child produced no result: {out.stderr[-2000:]}")


_LOCALITY_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from ray_tpu._private import perf
r = perf.locality_ab(locality={locality}, n_consumers={n}, arg_mb={arg_mb})
print("LOC_JSON:" + json.dumps(r))
"""


def _locality_subprocess(locality: bool, n: int, arg_mb: float) -> dict:
    """One locality A/B arm in a fresh interpreter (the cluster spawns
    node daemons; a clean process keeps the arms independent)."""
    env = spawn_env.child_env()
    code = _LOCALITY_CHILD.format(repo=REPO, locality=locality, n=n,
                                  arg_mb=arg_mb)
    timeout = max(60.0, min(300.0, _remaining() - 10.0))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith("LOC_JSON:"):
            return json.loads(line[len("LOC_JSON:"):])
    raise RuntimeError(
        f"locality child produced no result: {out.stderr[-2000:]}")


_HEAD_BYPASS_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from ray_tpu._private import perf
r = perf.head_bypass_ab({p2p}, n_calls={n_calls}, n_submit={n_submit})
print("HB_JSON:" + json.dumps(r))
"""


def _head_bypass_subprocess(p2p, n_calls: int,
                            n_submit: int) -> dict:
    """One head-bypass A/B arm in a fresh interpreter (the cluster
    spawns node daemons; a clean process keeps the arms independent)."""
    env = spawn_env.child_env()
    code = _HEAD_BYPASS_CHILD.format(repo=REPO, p2p=p2p, n_calls=n_calls,
                                     n_submit=n_submit)
    timeout = max(60.0, min(300.0, _remaining() - 10.0))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith("HB_JSON:"):
            return json.loads(line[len("HB_JSON:"):])
    raise RuntimeError(
        f"head_bypass child produced no result: {out.stderr[-2000:]}")


_QOS_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from ray_tpu._private import perf
r = perf.qos_ab({qos}, n_per_tenant={n_per_tenant}, n_submit={n_submit})
print("QOS_JSON:" + json.dumps(r))
"""


def _qos_subprocess(qos: bool, n_per_tenant: int,
                    n_submit: int) -> dict:
    """One QoS A/B arm in a fresh interpreter (the cluster spawns node
    daemons; a clean process keeps the arms independent)."""
    env = spawn_env.child_env()
    code = _QOS_CHILD.format(repo=REPO, qos=qos,
                             n_per_tenant=n_per_tenant,
                             n_submit=n_submit)
    timeout = max(60.0, min(300.0, _remaining() - 10.0))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith("QOS_JSON:"):
            return json.loads(line[len("QOS_JSON:"):])
    raise RuntimeError(
        f"qos child produced no result: {out.stderr[-2000:]}")


_SERVING_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from ray_tpu._private import perf
r = perf.serving_ab({disagg}, sessions={sessions}, turns={turns})
print("SERVING_JSON:" + json.dumps(r))
"""


def _serving_subprocess(disagg: bool, sessions: int, turns: int) -> dict:
    """One serving A/B arm in a fresh interpreter (each arm deploys
    its own serve controller + engines; a clean process keeps the
    arms' compile caches and actor planes independent)."""
    env = spawn_env.child_env()
    env["JAX_PLATFORMS"] = "cpu"  # the serving A/B is a routing
    #                               benchmark, not a kernel benchmark
    code = _SERVING_CHILD.format(repo=REPO, disagg=disagg,
                                 sessions=sessions, turns=turns)
    timeout = max(60.0, min(300.0, _remaining() - 10.0))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith("SERVING_JSON:"):
            return json.loads(line[len("SERVING_JSON:"):])
    raise RuntimeError(
        f"serving child produced no result: {out.stderr[-2000:]}")


_FAILOVER_CHILD = """
import json, os, re, signal, subprocess, sys, time
sys.path.insert(0, {repo!r})
import ray_tpu
from ray_tpu._private import spawn_env
from ray_tpu.util import state as util_state

TMP = {tmp!r}
journal = os.path.join(TMP, "gcs.journal")
log_path = os.path.join(TMP, "head.log")


def start_head():
    env = spawn_env.child_env(repo_path={repo!r})
    offset = os.path.getsize(log_path) if os.path.exists(log_path) else 0
    log = open(log_path, "a")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu", "start", "--head",
         "--num-cpus", "2", "--num-workers", "2",
         "--gcs-journal", journal],
        env=env, stdout=log, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        with open(log_path) as f:
            f.seek(offset)
            tail = f.read()
        m = re.search(r"address='(ray://[^']+)'", tail)
        if m:
            return proc, m.group(1)
        if proc.poll() is not None:
            raise RuntimeError("head died during startup: " + tail[-1500:])
        time.sleep(0.1)
    raise RuntimeError("head printed no connect string")


head1, address = start_head()
node_env = spawn_env.child_env(
    repo_path={repo!r},
    extra={{"RAY_TPU_DAEMON_REJOIN_TIMEOUT_S": "60"}})
node_log = open(os.path.join(TMP, "node.log"), "a")
node = subprocess.Popen(
    [sys.executable, "-m", "ray_tpu", "start", "--address", address,
     "--num-cpus", "2", "--resources", '{{"bench": 2}}'],
    env=node_env, stdout=node_log, stderr=subprocess.STDOUT)
ray_tpu.init(address=address)

# exec-loaded so cloudpickle ships the functions by value
ns = {{}}
exec("def tick(i):\\n    return i * i\\n"
     "def nap(i):\\n    import time\\n    time.sleep(6.0)\\n    return i\\n",
     ns)
tick = ray_tpu.remote(ns["tick"]).options(resources={{"bench": 1}})
nap = ray_tpu.remote(ns["nap"]).options(resources={{"bench": 1}})

deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    try:
        assert ray_tpu.get(tick.remote(3), timeout=5) == 9
        break
    except Exception:
        time.sleep(0.3)
else:
    raise RuntimeError("warmup task never completed")

# in-flight work across the blackout: finishes while the head is dead,
# lands in the daemon outbox, replays into the restarted head
pending = [nap.remote(i) for i in range(2)]
time.sleep(0.5)

t0 = time.monotonic()
head1.send_signal(signal.SIGKILL)
head1.wait(timeout=30)
head2, _ = start_head()
first = None
deadline = time.monotonic() + 90
while time.monotonic() < deadline:
    try:
        if ray_tpu.get(tick.remote(5), timeout=5) == 25:
            first = time.monotonic()
            break
    except Exception:
        time.sleep(0.2)
if first is None:
    raise RuntimeError("no post-failover dispatch within 90s")
vals = ray_tpu.get(pending, timeout=60)

# phase 2 — replay volume: this time keep the head DOWN until the
# in-flight tasks have finished into the daemon outbox, so the rejoin
# actually replays buffered completions (phase 1 restarts too fast for
# a 6s task to beat it)
pending2 = [nap.remote(10 + i) for i in range(2)]
time.sleep(0.5)
head2.send_signal(signal.SIGKILL)
head2.wait(timeout=30)
time.sleep(6.5)
head3, _ = start_head()
vals2 = ray_tpu.get(pending2, timeout=90)
replayed = depth = 0
for row in util_state.list_nodes():
    replayed += row.get("outbox_replayed", 0)
    depth += row.get("outbox_depth", 0)
r = {{"blackout_s": round(first - t0, 3),
     "outbox_replayed": replayed,
     "outbox_depth_after": depth,
     "inflight_results_correct": vals == [0, 1] and vals2 == [10, 11]}}
ray_tpu.shutdown()
for p in (head3, node):
    if p.poll() is None:
        p.terminate()
print("FAILOVER_JSON:" + json.dumps(r))
"""


_NODE_LOSS_CHILD = """
import json
import sys
import time

sys.path.insert(0, {repo!r})

import ray_tpu
from ray_tpu._private import worker as worker_mod

ray_tpu.init(num_workers=2,
             _system_config={{"worker_mode": "process",
                              "node_heartbeat_timeout_s": 20.0,
                              "health_check_timeout_s": 5.0}})
w = worker_mod.get_worker()
ea = w.add_remote_cluster_node(num_cpus=4.0, num_workers=3,
                               resources={{"a": 4}})

# exec-loaded so cloudpickle ships the functions by value
ns = {{}}
exec("def nap(i):\\n    import time\\n    time.sleep(5.0)\\n    return i\\n"
     "def produce():\\n    return bytes(range(256)) * 4096\\n", ns)
ns["nap_r"] = ray_tpu.remote(ns["nap"]).options(max_retries=3)
ns["prod_r"] = ray_tpu.remote(ns["produce"]).options(max_retries=2)
exec("def spawn(m):\\n"
     "    return [nap_r.remote(i) for i in range(m)]\\n"
     "def make():\\n"
     "    import ray_tpu\\n"
     "    ref = prod_r.remote()\\n"
     "    assert len(ray_tpu.get(ref, timeout=60.0)) == 1024 * 1024\\n"
     "    return ref\\n", ns)
spawn = ray_tpu.remote(ns["spawn"]).options(resources={{"a": 1.0}})
make = ray_tpu.remote(ns["make"]).options(resources={{"a": 1.0}})

# sole copy: a locally-dispatched nested producer fills 1 MiB into the
# node's arena; only the ref escapes to the head
inner = ray_tpu.get(make.remote(), timeout=120.0)

# in-flight: locally-dispatched retry-carrying naps, refs held head-side
refs = ray_tpu.get(spawn.remote(2), timeout=60.0)
deadline = time.monotonic() + 30
while w.two_level_stats["local_dispatch"] < 3 \\
        and time.monotonic() < deadline:
    time.sleep(0.05)

t0 = time.monotonic()
ea.pool.simulate_machine_death()
ready, _ = ray_tpu.wait(refs, num_returns=1, timeout=120.0)
if not ready:
    raise RuntimeError("no recovered result within 120s of node kill")
blackout = time.monotonic() - t0
vals = ray_tpu.get(refs, timeout=120.0)

t1 = time.monotonic()
blob = ray_tpu.get(inner, timeout=120.0)
recon_s = time.monotonic() - t1

s = w.two_level_stats
r = {{"blackout_s": round(blackout, 3),
     "recovered_ok": vals == [0, 1],
     "reconstruct_s": round(recon_s, 3),
     "reconstruct_mb": round(len(blob) / (1024.0 * 1024.0), 3),
     "orphan_leases_retried": s.get("orphan_retried", 0),
     "node_deaths": s.get("node_deaths", 0)}}
ray_tpu.shutdown()
print("NODE_LOSS_JSON:" + json.dumps(r))
"""


def _node_loss_subprocess() -> dict:
    """Whole-node SIGKILL drill in a fresh interpreter: one remote
    node with locally-dispatched retry-carrying leases mid-flight and
    a sole-copy object in its arena; killpg the daemon tree and
    measure kill -> first reconciler-recovered result (the blackout)
    plus how many bytes lineage reconstruction re-derived."""
    env = spawn_env.child_env()
    code = _NODE_LOSS_CHILD.format(repo=REPO)
    timeout = max(120.0, min(300.0, _remaining() - 10.0))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith("NODE_LOSS_JSON:"):
            return json.loads(line[len("NODE_LOSS_JSON:"):])
    raise RuntimeError(
        f"node_loss child produced no result: {out.stderr[-2000:]}")


def _failover_subprocess() -> dict:
    """Head-kill blackout drill in a fresh interpreter: subprocess head
    on a journal + one remote node, SIGKILL the head mid-run, restart
    it on the same journal, measure kill -> first post-rejoin dispatch
    and how much the daemon outbox replayed."""
    import tempfile

    tmp = tempfile.mkdtemp(prefix="ray_tpu_bench_failover_")
    env = spawn_env.child_env()
    code = _FAILOVER_CHILD.format(repo=REPO, tmp=tmp)
    timeout = max(120.0, min(300.0, _remaining() - 10.0))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    for line in out.stdout.splitlines():
        if line.startswith("FAILOVER_JSON:"):
            return json.loads(line[len("FAILOVER_JSON:"):])
    raise RuntimeError(
        f"failover child produced no result: {out.stderr[-2000:]}")


def main() -> int:
    smoke = "--smoke" in sys.argv
    run_all = "--all" in sys.argv

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    # this process owns the chip from here on (children are CPU jax)
    import jax

    from ray_tpu._private.cache_dir import enable_compile_cache

    OUT["compile_cache"] = enable_compile_cache()
    dev = jax.devices()[0]
    OUT["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    if dev.platform != "tpu" and not smoke:
        print(f"bench.py: no TPU (jax reports {dev.platform!r}, "
              f"{dev.device_kind}). Device metrics are only measured on "
              "the chip; pass --smoke for the tiny CPU run.",
              file=sys.stderr)
        return 2
    OUT["host_cpus"] = os.cpu_count()
    _emit()

    if smoke:
        # record the PINNED smoke shapes (perf.py freezes them) so smoke
        # runs are comparable with each other and a reader can tell
        # which shape produced a number
        from ray_tpu._private import perf as _perf
        OUT["smoke_config"] = {"model": dict(_perf.SMOKE_MODEL),
                               "decode": dict(_perf.SMOKE_DECODE)}

    from ray_tpu._private import benchmarks, perf

    # --- static analysis gate (raylint) --------------------------------
    # cheap and host-independent, so it always runs: the five AST passes
    # must stay interactive (<10s wall) and find nothing new
    if section("lint", 15):
        from ray_tpu._private import analysis
        t0 = time.perf_counter()
        report = analysis.run_all()
        lint_s = time.perf_counter() - t0
        OUT["lint"] = {"seconds": round(lint_s, 3),
                       "new": len(report.new),
                       "baselined": len(report.baselined),
                       "stale_suppressions": len(report.stale_suppressions),
                       "durations_s": {k: round(v, 3)
                                       for k, v in report.durations.items()}}
        print(f"  lint: {len(report.new)} new, {len(report.baselined)} "
              f"baselined in {lint_s:.2f}s", file=sys.stderr)
        assert lint_s < 10.0, f"raylint took {lint_s:.1f}s (budget 10s)"
        assert report.ok, "raylint found NEW findings:\n" + report.render_text()
        _emit()

    if run_all and section("baseline_configs", 60):
        results = benchmarks.run_all("smoke" if smoke else "full")
        for name, r in results.items():
            print(f"  {name}: {r['scheduling_ms']:.3f} ms, "
                  f"{r['tasks_per_sec']:.3g} tasks/s, {r['ticks']} ticks",
                  file=sys.stderr)
        _emit()

    # --- north star ----------------------------------------------------
    # Protocol (with or without --all): MIN of per-group MEDIANS of
    # run_graph's K-differenced timings; the per-group spread is
    # reported alongside, and one noisy group is skipped rather than
    # aborting the whole benchmark. (ROADMAP S1 replaces this protocol
    # with a host clock around block_until_ready.)
    target_ms = 10.0
    if section("north_star", 20):
        try:
            g = (benchmarks.build_north_star(10_000, 8) if smoke
                 else benchmarks.build_north_star())
            if not smoke:
                try:
                    # discarded warm-up group
                    benchmarks.run_graph(g, repeats=3)
                except RuntimeError:
                    pass
            groups = []
            n_groups = 1 if smoke else 5
            for _ in range(n_groups):
                if _remaining() < 15 and groups:
                    SKIPPED["north_star_groups"] = (
                        f"budget: stopped after {len(groups)} groups")
                    break
                try:
                    groups.append(benchmarks.run_graph(g, repeats=5))
                except RuntimeError:
                    traceback.print_exc()
            if not groups:
                raise RuntimeError("north star: no group could be timed")
            ns = min(groups, key=lambda r: r["scheduling_ms"])
            value = round(ns["scheduling_ms"], 4)
            OUT["value"] = value
            OUT["vs_baseline"] = round(target_ms / max(value, 1e-9), 2)
            OUT["north_star"] = {
                "scheduling_ms": value,
                "tasks_per_sec": round(ns["tasks_per_sec"], 1),
                "ticks": ns["ticks"],
                "runs_ms": [round(r["scheduling_ms"], 3)
                            for r in groups]}
            print(f"  north star: {value} ms "
                  f"(groups {OUT['north_star']['runs_ms']})",
                  file=sys.stderr)
        except Exception:
            _failed("north_star")
        _emit()

    # --- north star, multi-tick admission ------------------------------
    # honesty companion: the SAME 1M tasks admitted over 64 dependency
    # waves — a full ready-set/admission tick per wave, the cost the
    # single-wave fan-out headline never shows
    if section("north_star_multi_tick", 20):
        try:
            gw = (benchmarks.build_north_star_waves(10_000, 16, 8)
                  if smoke else benchmarks.build_north_star_waves())
            groups = []
            for _ in range(1 if smoke else 3):
                if _remaining() < 15 and groups:
                    break
                try:
                    groups.append(benchmarks.run_graph(gw, repeats=3))
                except RuntimeError:
                    traceback.print_exc()
            if not groups:
                raise RuntimeError(
                    "north star multi-tick: no group could be timed")
            ns = min(groups, key=lambda r: r["scheduling_ms"])
            OUT["north_star_multi_tick"] = {
                "scheduling_ms": round(ns["scheduling_ms"], 4),
                "tasks_per_sec": round(ns["tasks_per_sec"], 1),
                "ticks": ns["ticks"],
                "waves": 16 if smoke else 64,
                "runs_ms": [round(r["scheduling_ms"], 3)
                            for r in groups]}
            print(f"  north star multi-tick: "
                  f"{OUT['north_star_multi_tick']['scheduling_ms']}"
                  f" ms over {ns['ticks']} ticks", file=sys.stderr)
        except Exception:
            _failed("north_star_multi_tick")
        _emit()

    # --- e2e task throughput through the public API --------------------
    e2e = {}
    budgets = {}
    n_thread = 2_000 if smoke else 50_000
    n_proc = 500 if smoke else 20_000
    for label, mode, n, batched in (
            ("thread", "thread", n_thread, False),
            ("thread_batched", "thread", n_thread, True),
            ("process", "process", n_proc, False),
            ("process_batched", "process", n_proc, True)):
        if not section(f"e2e_{label}", 15):
            e2e[label] = None
            continue
        try:
            # FRESH subprocess per mode: the north-star sections leave a
            # jax/XLA heap and device state behind, which costs the
            # in-process e2e measurement ~25% on small hosts
            r = _e2e_subprocess(n, mode, batched)
            e2e[label] = round(r["tasks_per_sec"], 1)
            budgets[label] = dict(r["budget_us"],
                                  tasks_per_tick=r["tasks_per_tick"])
            print(f"  e2e[{label}]: {r['tasks_per_sec']:.0f} tasks/s "
                  f"({n} tasks in {r['seconds']:.2f}s; "
                  f"budget {r['budget_us']} us/task, "
                  f"{r['tasks_per_tick']} tasks/tick)", file=sys.stderr)
        except Exception:
            _failed(f"e2e_{label}")
            e2e[label] = None
        OUT["e2e_tasks_per_sec"] = dict(e2e)
        OUT["e2e_budget_us"] = dict(budgets)
        _emit()

    # --- control ring: shm control-plane A/B ---------------------------
    # A/B of the process-batched e2e lane with the shm control ring
    # disabled (RAY_TPU_CONTROL_RING=0 — per-task framed pipe messages,
    # the pre-ring transport). The e2e numbers above ran with the ring
    # ON (the default); the claim under test is that batched lease
    # envelopes over the ring are never slower than the pipe path
    # (tests/test_benchmarks.py guards the recorded artifact).
    if section("e2e_ring", 25):
        er = {}
        try:
            on = e2e.get("process_batched")
            if on is None:
                on = round(_e2e_subprocess(n_proc, "process", True)
                           ["tasks_per_sec"], 1)
            off = round(_e2e_subprocess(
                n_proc, "process", True,
                extra_env={"RAY_TPU_CONTROL_RING": "0"})
                ["tasks_per_sec"], 1)
            er = {
                "ring_on_tasks_per_sec": on,
                "ring_off_tasks_per_sec": off,
                "speedup_pct": round(100.0 * (on - off) / off, 1),
            }
            print(f"  e2e_ring: {on:.0f} tasks/s with ring vs "
                  f"{off:.0f} over the pipe "
                  f"({er['speedup_pct']:+.1f}%)", file=sys.stderr)
        except Exception:
            _failed("e2e_ring")
        OUT["e2e_ring"] = er or None
        _emit()

    # --- log plane: stdout/stderr capture overhead ---------------------
    # A/B of the e2e harness with capture disabled (RAY_TPU_LOG_CAPTURE=0
    # — no session dir, no per-worker files, no monitor thread). The e2e
    # numbers above ran with capture ON (the default), so only the OFF
    # side needs measuring; the claim under test is that the capture
    # machinery stays within ~10% of the uninstrumented path.
    if section("log_overhead", 25):
        lo = {}
        for label, mode, n in (("thread", "thread", n_thread),
                               ("process", "process", n_proc)):
            try:
                on = e2e.get(label)
                if on is None:
                    on = round(_e2e_subprocess(n, mode)["tasks_per_sec"],
                               1)
                off = round(_e2e_subprocess(
                    n, mode,
                    extra_env={"RAY_TPU_LOG_CAPTURE": "0"})
                    ["tasks_per_sec"], 1)
                lo[label] = {
                    "capture_on_tasks_per_sec": on,
                    "capture_off_tasks_per_sec": off,
                    "overhead_pct": round(100.0 * (off - on) / off, 1),
                }
                print(f"  log overhead[{label}]: {on:.0f} tasks/s with "
                      f"capture vs {off:.0f} without "
                      f"({lo[label]['overhead_pct']}%)", file=sys.stderr)
            except Exception:
                _failed("log_overhead")
        OUT["log_overhead"] = lo or None
        _emit()

    # --- task event plane: lifecycle telemetry overhead ----------------
    # A/B of the e2e harness with the task event aggregator disabled
    # (RAY_TPU_TASK_EVENTS_MAX=0 — no submit/ready/dispatch/finish
    # recording, no worker-side exec timestamps). The e2e numbers above
    # ran with events ON (the default); the claim under test is that the
    # telemetry stays within ~10% of the unrecorded path — on the
    # BATCHED lanes, where per-task bookkeeping is most exposed.
    if section("task_event_overhead", 25):
        teo = {}
        for label, mode, n, batched in (
                ("thread_batched", "thread", n_thread, True),
                ("process_batched", "process", n_proc, True)):
            try:
                on = e2e.get(label)
                if on is None:
                    on = round(_e2e_subprocess(n, mode, batched)
                               ["tasks_per_sec"], 1)
                off = round(_e2e_subprocess(
                    n, mode, batched,
                    extra_env={"RAY_TPU_TASK_EVENTS_MAX": "0"})
                    ["tasks_per_sec"], 1)
                teo[label] = {
                    "events_on_tasks_per_sec": on,
                    "events_off_tasks_per_sec": off,
                    "overhead_pct": round(100.0 * (off - on) / off, 1),
                }
                print(f"  task event overhead[{label}]: {on:.0f} "
                      f"tasks/s with events vs {off:.0f} without "
                      f"({teo[label]['overhead_pct']}%)",
                      file=sys.stderr)
            except Exception:
                _failed("task_event_overhead")
        OUT["task_event_overhead"] = teo or None
        _emit()

    # --- trace plane: distributed tracing overhead ---------------------
    # A/B of the e2e harness with the trace plane disabled
    # (RAY_TPU_TRACE_SAMPLE_RATE=0 — no context stamping at submit, no
    # span records, no payload "trace" key). The e2e numbers above ran
    # with tracing ON (sample rate 1.0 is the default); the claim under
    # test is that full-rate span recording stays within ~10% of the
    # untraced path on the BATCHED lanes, where per-task bookkeeping is
    # most exposed.
    if section("trace_overhead", 25):
        tro = {}
        for label, mode, n, batched in (
                ("thread_batched", "thread", n_thread, True),
                ("process_batched", "process", n_proc, True)):
            try:
                on = e2e.get(label)
                if on is None:
                    on = round(_e2e_subprocess(n, mode, batched)
                               ["tasks_per_sec"], 1)
                off = round(_e2e_subprocess(
                    n, mode, batched,
                    extra_env={"RAY_TPU_TRACE_SAMPLE_RATE": "0"})
                    ["tasks_per_sec"], 1)
                tro[label] = {
                    "trace_on_tasks_per_sec": on,
                    "trace_off_tasks_per_sec": off,
                    "overhead_pct": round(100.0 * (off - on) / off, 1),
                }
                print(f"  trace overhead[{label}]: {on:.0f} tasks/s "
                      f"with tracing vs {off:.0f} without "
                      f"({tro[label]['overhead_pct']}%)",
                      file=sys.stderr)
            except Exception:
                _failed("trace_overhead")
        OUT["trace_overhead"] = tro or None
        _emit()

    # --- profile plane: continuous sampling profiler overhead ----------
    # A/B of the e2e harness with the profile/utilization plane ENABLED
    # (RAY_TPU_PROFILE_HZ=100 — sampler thread per worker + head, folded
    # stack aggregation, resource samplers). Unlike the other planes the
    # profiler is OFF by default, so here the instrumented lane is the
    # env-override one and the baseline is the plain e2e number. The
    # claim under test: 100 Hz sampling stays within ~10% of the
    # unprofiled path on the BATCHED lanes.
    if section("profile_overhead", 25):
        pro = {}
        for label, mode, n, batched in (
                ("thread_batched", "thread", n_thread, True),
                ("process_batched", "process", n_proc, True)):
            try:
                off = e2e.get(label)
                if off is None:
                    off = round(_e2e_subprocess(n, mode, batched)
                                ["tasks_per_sec"], 1)
                on = round(_e2e_subprocess(
                    n, mode, batched,
                    extra_env={"RAY_TPU_PROFILE_HZ": "100"})
                    ["tasks_per_sec"], 1)
                pro[label] = {
                    "profile_on_tasks_per_sec": on,
                    "profile_off_tasks_per_sec": off,
                    "overhead_pct": round(100.0 * (off - on) / off, 1),
                }
                print(f"  profile overhead[{label}]: {on:.0f} tasks/s "
                      f"at 100 Hz vs {off:.0f} unprofiled "
                      f"({pro[label]['overhead_pct']}%)",
                      file=sys.stderr)
            except Exception:
                _failed("profile_overhead")
        OUT["profile_overhead"] = pro or None
        _emit()

    # --- locality-aware scheduling: cross-node byte A/B ----------------
    # 2-remote-node cluster, large objects produced on one node, a
    # consumer fanout free to run on either. ON: the scheduler's
    # resident-arg-bytes column keeps consumers (bounded by the
    # spillback depth) on the data; OFF restores the pre-locality
    # least-loaded placement, which ships a batch of args across. The
    # claim under test: ON moves >= 50% fewer cross-node bytes with
    # equal task results. A small-arg lane (the plain e2e no-op fanout
    # with the knob off) checks the common path pays nothing.
    if section("locality", 40):
        loc = {}
        n_cons, arg_mb = (4, 0.5) if smoke else (8, 1.0)
        try:
            on = _locality_subprocess(True, n_cons, arg_mb)
            off = _locality_subprocess(False, n_cons, arg_mb)
            loc["on"] = on
            loc["off"] = off
            loc["equal_results"] = on["sum"] == off["sum"]
            moved_off = max(off["bytes_pulled"], 1)
            loc["bytes_reduction_pct"] = round(
                100.0 * (off["bytes_pulled"] - on["bytes_pulled"])
                / moved_off, 1)
            print(f"  locality: {on['bytes_pulled']} B pulled with "
                  f"locality vs {off['bytes_pulled']} B without "
                  f"({loc['bytes_reduction_pct']}% fewer; "
                  f"{on['bytes_saved']} B saved, "
                  f"{on['hits']} hits / {on['misses']} misses)",
                  file=sys.stderr)
        except Exception:
            _failed("locality")
        try:
            small_on = e2e.get("process")
            if small_on is None:
                small_on = round(_e2e_subprocess(
                    n_proc, "process")["tasks_per_sec"], 1)
            small_off = round(_e2e_subprocess(
                n_proc, "process",
                extra_env={"RAY_TPU_SCHEDULER_LOCALITY": "0"})
                ["tasks_per_sec"], 1)
            loc["small_arg"] = {
                "locality_on_tasks_per_sec": small_on,
                "locality_off_tasks_per_sec": small_off,
                "overhead_pct": round(
                    100.0 * (small_off - small_on) / small_off, 1),
            }
            print(f"  locality small-arg lane: {small_on:.0f} tasks/s "
                  f"on vs {small_off:.0f} off "
                  f"({loc['small_arg']['overhead_pct']}%)",
                  file=sys.stderr)
        except Exception:
            _failed("locality")
        OUT["locality"] = loc or None
        _emit()

    # --- two-level scheduling: head off the data path ------------------
    # 2-remote-node cluster, actor on node B, caller task on node A.
    # ON (actor_p2p + local_dispatch): calls ship worker -> peer daemon
    # over the peer lane with only completion receipts to the head, and
    # nested submissions admit on the node's LocalScheduler; the
    # sustained-submit lane runs against a chaos-slowed head tick, so
    # local dispatch shows up as immunity to head latency. OFF is the
    # pre-PR everything-through-the-head path. Claims under test: ON is
    # never slower, >=90% of steady-state actor calls skip the head,
    # and both arms produce equal results.
    if section("head_bypass", 65):
        hb = {}
        n_calls, n_submit = (12, 8) if smoke else (40, 24)
        try:
            on = _head_bypass_subprocess(True, n_calls, n_submit)
            off = _head_bypass_subprocess(False, n_calls, n_submit)
            # the default-config arm: NO knob overrides (the flipped
            # defaults) and a submit mix including retry-carrying and
            # resident-ref-carrying tasks — the acceptance bar is
            # head_skip >= 0.9 on exactly this arm
            dflt = _head_bypass_subprocess(None, n_calls, n_submit)
            hb["on"] = on
            hb["off"] = off
            hb["default"] = dflt
            hb["default_head_skip"] = dflt.get("head_skip")
            hb["equal_results"] = (on["total"] == off["total"]
                                   and on["n_submit"] == off["n_submit"])
            hb["p2p_fraction"] = round(
                on["calls_p2p"] / max(n_calls, 1), 3)
            hb["actor_speedup"] = round(
                off["actor_seconds"] / max(on["actor_seconds"], 1e-9), 2)
            hb["slowed_head_submit_speedup"] = round(
                off["submit_seconds"] / max(on["submit_seconds"], 1e-9),
                2)
            print(f"  head_bypass: {on['calls_p2p']}/{n_calls} actor "
                  f"calls p2p ({hb['p2p_fraction']:.0%}), "
                  f"{on['head_fallback']} fallbacks; actor lane "
                  f"{on['actor_seconds']}s vs {off['actor_seconds']}s "
                  f"({hb['actor_speedup']}x); slowed-head submit "
                  f"{on['submit_seconds']}s vs {off['submit_seconds']}s "
                  f"({hb['slowed_head_submit_speedup']}x, "
                  f"{on['local_dispatch']} local / {on['spillback']} "
                  f"spilled); default-config arm head_skip "
                  f"{dflt['head_skip']} ({dflt['local_dispatch']} "
                  f"local / {dflt['spillback']} spilled, mixed "
                  "retry+ref lane)", file=sys.stderr)
        except Exception:
            _failed("head_bypass")
        OUT["head_bypass"] = hb or None
        _emit()

    # --- QoS plane: tiers + fair-share vs the escape hatch --------------
    # Mixed two-tenant load (tier-1 "prod" at weight 3, tier-0 "batch"
    # at weight 1) with a concurrent node-side nested-submit lane. ON
    # drains by strict tier + weighted fair-share and ships the resview
    # watermark; OFF (qos=False) is the byte-for-byte escape hatch.
    # Claims under test: tier-1 p50 drops under the plane (the A/B is
    # the point: OFF has no tiers so the batch class drains first),
    # head-skip stays high (tier spills are the only new decline
    # reason), both arms produce equal results, and the escape hatch
    # costs nothing — the OFF arm's total wall-clock is never slower
    # than the ON arm's (15% noise margin).
    if section("qos", 65):
        qs = {}
        n_per_tenant, n_submit = (10, 6) if smoke else (30, 16)
        try:
            on = _qos_subprocess(True, n_per_tenant, n_submit)
            off = _qos_subprocess(False, n_per_tenant, n_submit)
            qs["on"] = on
            qs["off"] = off
            qs["equal_results"] = (on["total"] == off["total"]
                                   and on["n_submit"] == off["n_submit"])
            qs["tier1_p50_speedup"] = round(
                off["tier1_p50_ms"] / max(on["tier1_p50_ms"], 1e-9), 2)
            qs["tier1_p99_speedup"] = round(
                off["tier1_p99_ms"] / max(on["tier1_p99_ms"], 1e-9), 2)
            # the escape-hatch guard: qos=False pays no overall tax
            qs["off_never_slower"] = bool(
                off["seconds"] <= on["seconds"] * 1.15)
            print(f"  qos: tier-1 p50 {on['tier1_p50_ms']}ms / p99 "
                  f"{on['tier1_p99_ms']}ms with the plane vs "
                  f"{off['tier1_p50_ms']}ms / {off['tier1_p99_ms']}ms "
                  f"off ({qs['tier1_p50_speedup']}x p50); tier-0 p50 "
                  f"{on['tier0_p50_ms']}ms vs {off['tier0_p50_ms']}ms; "
                  f"head_skip {on['head_skip']} on ({on['spillback_tier']}"
                  f" tier-spills) vs {off['head_skip']} off; off arm "
                  f"never slower overall: {qs['off_never_slower']}",
                  file=sys.stderr)
        except Exception:
            _failed("qos")
        OUT["qos"] = qs or None
        _emit()

    # --- model perf: step time / tokens/s / MFU ------------------------
    if section("mfu", 25 if smoke else 90):
        try:
            m = perf.model_mfu(smoke=smoke)
            OUT["mfu"] = (round(m["mfu"], 4)
                          if m["mfu"] is not None else None)
            OUT["hfu"] = (round(m["hfu"], 4)
                          if m.get("hfu") is not None else None)
            OUT["model"] = {
                "device": m["device"],
                "n_params": m["n_params"],
                "batch": m["batch_size"], "seq": m["seq_len"],
                "step_ms": round(m["step_ms"], 2),
                "tokens_per_sec": round(m["tokens_per_sec"], 1),
                "tflops_per_sec": round(
                    m["model_flops_per_sec"] / 1e12, 2),
            }
            print(f"  mfu: {OUT['mfu']} on {m['device']} "
                  f"({m['n_params']/1e6:.0f}M params, "
                  f"{m['step_ms']:.1f} ms/step, "
                  f"{m['tokens_per_sec']:.0f} tok/s)", file=sys.stderr)
        except Exception:
            _failed("mfu")
            OUT["mfu"] = None
        _emit()

    # --- LLM serving: paged-attention decode throughput ----------------
    if section("llm_decode", 25 if smoke else 90):
        try:
            d = perf.llm_decode_throughput(smoke=smoke)
            OUT["llm_decode"] = {
                "tokens_per_sec": round(d["tokens_per_sec"], 1),
                "batch_slots": d["batch_slots"],
                "n_params": d["n_params"],
                "new_tokens": d["new_tokens"],
            }
            print(f"  llm decode: {d['tokens_per_sec']:.0f} tok/s "
                  f"({d['batch_slots']} slots, {d['n_params']/1e6:.0f}M "
                  f"params)", file=sys.stderr)
        except Exception:
            _failed("llm_decode")
            OUT["llm_decode"] = None
        _emit()

    # --- serving at traffic scale: disaggregation A/B ------------------
    # mono (2 LLM replicas, prefill shares each replica's continuous
    # batch) vs split (1 prefill + 1 decode replica) under a sustained
    # concurrent-streams load with follow-up turns. Claims under test:
    # the split arm's p95 TTFT beats mono under saturation (a new
    # prompt's first token streams off the prefill handoff instead of
    # queueing behind whole decodes), and follow-up turns route back
    # to the KV-holding decode replica (affinity hit rate). CPU-host
    # caveat rides in the record: both arms share one host's cores,
    # so TTFT ordering is the honest signal, not tokens/s.
    if section("serving", 60):
        sv = {}
        sessions, turns = (4, 2) if smoke else (8, 2)
        try:
            mono = _serving_subprocess(False, sessions, turns)
            split = _serving_subprocess(True, sessions, turns)
            sv["mono"] = mono
            sv["split"] = split
            sv["equal_tokens"] = (mono["total_tokens"]
                                  == split["total_tokens"])
            sv["ttft_p95_speedup"] = round(
                mono["ttft_p95_ms"] / max(split["ttft_p95_ms"], 1e-9), 2)
            sv["affinity_hit_rate"] = split["affinity_hit_rate"]
            print(f"  serving: split p95 TTFT {split['ttft_p95_ms']}ms "
                  f"vs {mono['ttft_p95_ms']}ms mono "
                  f"({sv['ttft_p95_speedup']}x); "
                  f"{split['tokens_per_sec_per_replica']} tok/s/replica "
                  f"split vs {mono['tokens_per_sec_per_replica']} mono; "
                  f"affinity hit rate {split['affinity_hit_rate']}",
                  file=sys.stderr)
        except Exception:
            _failed("serving")
        OUT["serving"] = sv or None
        _emit()

    # decode slot sweep (32/128 beyond the 64 above) — opportunistic:
    # only on a real chip with budget to spare
    if not smoke and section("llm_decode_sweep", 180):
        sweep = {}
        for slots in (32, 128):
            if _remaining() < 90:
                SKIPPED["llm_decode_sweep"] = (
                    f"budget: stopped before {slots} slots")
                break
            try:
                d = perf.llm_decode_throughput(batch_slots=slots)
                sweep[str(slots)] = round(d["tokens_per_sec"], 1)
                print(f"  llm decode[{slots} slots]: "
                      f"{d['tokens_per_sec']:.0f} tok/s", file=sys.stderr)
            except Exception:
                _failed("llm_decode_sweep")
        if sweep and OUT.get("llm_decode"):
            sweep["64"] = OUT["llm_decode"]["tokens_per_sec"]
            OUT["llm_decode"]["slots_sweep_tok_s"] = sweep
        _emit()

    # --- Data library: 100k-block map_batches pipeline -----------------
    if section("data_pipeline", 25):
        try:
            r = perf.data_pipeline_throughput(
                num_blocks=1_000 if smoke else 100_000)
            OUT["data_pipeline"] = {
                "blocks_per_sec": round(r["blocks_per_sec"], 1),
                "rows_per_sec": round(r["rows_per_sec"], 1),
                "num_blocks": r["num_blocks"],
                "seconds": round(r["seconds"], 2),
            }
            print(f"  data: {r['blocks_per_sec']:.0f} blocks/s "
                  f"({r['num_blocks']} blocks in {r['seconds']:.1f}s)",
                  file=sys.stderr)
        except Exception:
            _failed("data_pipeline")
            OUT["data_pipeline"] = None
        _emit()

    # --- Data library: Arrow columnar MB/s -----------------------------
    if section("data_arrow", 10):
        try:
            r = perf.data_arrow_throughput(total_mb=32 if smoke else 256)
            OUT["data_arrow_mb_per_sec"] = r["mb_per_sec"]
            print(f"  data arrow: {r['mb_per_sec']:.0f} MB/s "
                  f"({r['total_mb']:.0f} MB in {r['seconds']:.1f}s)",
                  file=sys.stderr)
        except Exception:
            _failed("data_arrow")
            OUT["data_arrow_mb_per_sec"] = None
        _emit()

    # --- Data library: columnar shuffle MB/s ---------------------------
    if section("data_shuffle", 8):
        try:
            r = perf.data_shuffle_throughput(total_mb=16 if smoke else 128)
            OUT["data_shuffle_mb_per_sec"] = r["mb_per_sec"]
            print(f"  data shuffle: {r['mb_per_sec']:.0f} MB/s "
                  f"({r['total_mb']:.0f} MB in {r['seconds']:.1f}s)",
                  file=sys.stderr)
        except Exception:
            _failed("data_shuffle")
            OUT["data_shuffle_mb_per_sec"] = None
        _emit()

    # --- Data library: columnar hash-join MB/s -------------------------
    if section("data_join", 10):
        try:
            r = perf.data_join_throughput(total_mb=8 if smoke else 64)
            OUT["data_join_mb_per_sec"] = r["mb_per_sec"]
            print(f"  data join: {r['mb_per_sec']:.0f} MB/s "
                  f"({r['total_mb']:.0f} MB in {r['seconds']:.1f}s)",
                  file=sys.stderr)
        except Exception:
            _failed("data_join")
            OUT["data_join_mb_per_sec"] = None
        _emit()

    # --- Data library: streaming-split ingest overlap ------------------
    if section("data_ingest_overlap", 15):
        try:
            r = perf.data_ingest_overlap(
                num_blocks=32 if smoke else 96,
                sleep_s=0.01 if smoke else 0.025)
            OUT["data_ingest_overlap"] = {
                "ttfb_materialize_s": r["ttfb_materialize_s"],
                "ttfb_streaming_s": r["ttfb_streaming_s"],
                "ttfb_speedup": r["ttfb_speedup"],
                "overlap_fraction": r["overlap_fraction"],
                "streaming_blocks_per_sec":
                    r["streaming_blocks_per_sec"],
                "consumers": r["consumers"],
                "num_blocks": r["num_blocks"],
            }
            print(f"  data ingest overlap: ttfb {r['ttfb_streaming_s']}s"
                  f" streaming vs {r['ttfb_materialize_s']}s materialized"
                  f" ({r['ttfb_speedup']}x; overlap "
                  f"{r['overlap_fraction']})", file=sys.stderr)
        except Exception:
            _failed("data_ingest_overlap")
            OUT["data_ingest_overlap"] = None
        _emit()

    # --- RLlib: IMPALA async rollout throughput ------------------------
    # --- failover: head-kill blackout + outbox replay volume -----------
    if section("failover", 45):
        try:
            r = _failover_subprocess()
            OUT["failover"] = r
            print(f"  failover: {r['blackout_s']:.2f}s blackout "
                  f"(SIGKILL head -> first post-rejoin dispatch); "
                  f"{r['outbox_replayed']} outbox envelopes replayed, "
                  f"in-flight results "
                  f"{'intact' if r['inflight_results_correct'] else 'LOST'}",
                  file=sys.stderr)
        except Exception:
            _failed("failover")
            OUT["failover"] = None
        _emit()

    # --- node loss: whole-node SIGKILL blackout + reconstruction -------
    if section("node_loss", 45):
        try:
            r = _node_loss_subprocess()
            OUT["node_loss"] = r
            print(f"  node_loss: {r['blackout_s']:.2f}s blackout "
                  f"(SIGKILL node -> first reconciler-recovered "
                  f"result); {r['reconstruct_mb']:.1f} MiB "
                  f"reconstructed in {r['reconstruct_s']:.2f}s, "
                  f"{r['orphan_leases_retried']} orphan leases retried, "
                  f"in-flight results "
                  f"{'intact' if r['recovered_ok'] else 'LOST'}",
                  file=sys.stderr)
        except Exception:
            _failed("node_loss")
            OUT["node_loss"] = None
        _emit()

    if section("rl_rollout", 45):
        try:
            code = (
                "import json, sys\n"
                f"sys.path.insert(0, {REPO!r})\n"
                # this child RUNS jax compute, on the CPU: child_env
                # pins it there (this process holds the chip)
                "from ray_tpu._private import perf\n"
                f"r = perf.rl_rollout_throughput(iters={1 if smoke else 4})\n"
                "print('RL_JSON:' + json.dumps(r))\n")
            env = spawn_env.child_env()
            timeout = max(30.0, min(300.0, _remaining() - 10.0))
            p = subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True,
                               timeout=timeout)
            r = None
            for line in p.stdout.splitlines():
                if line.startswith("RL_JSON:"):
                    r = json.loads(line[len("RL_JSON:"):])
            if r is None:
                raise RuntimeError(f"rl child failed: {p.stderr[-1500:]}")
            OUT["rl_rollout"] = r
            print(f"  rl rollout: {r['env_steps_per_sec']:.0f} "
                  f"env-steps/s (IMPALA, return "
                  f"{r['episode_return_mean']})", file=sys.stderr)
        except Exception:
            _failed("rl_rollout")
            OUT["rl_rollout"] = None
        _emit()

    _emit(to_stdout=True)
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
