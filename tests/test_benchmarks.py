"""Benchmark harness smoke tests (tiny sizes, CPU backend via conftest).

Checks the 5 BASELINE graph builders produce well-formed DAGs and that
run_graph drives each to completion with correct tick counts, plus the
control-ring A/B guard: the shm ring transport must never be slower
than the pipe-only path it replaced."""

import os

import numpy as np
import pytest

from ray_tpu._private import benchmarks as B


class TestGraphBuilders:
    @pytest.mark.parametrize("build,expected_depth", [
        (lambda: B.build_fanout(100, 4), 1),
        (lambda: B.build_map_reduce(202, 100, 4), 2),
        (lambda: B.build_pipeline(3, 50, 4), 3),
        (lambda: B.build_actor_heavy(10, 5, 4), 2),
        (lambda: B.build_ppo(40, 4, 2, 2), 4),
    ])
    def test_builds_and_completes(self, build, expected_depth):
        g = build()
        assert (np.sort(g.dst) == g.dst).all() or len(g.dst) <= 1
        # repeats=1 gives ONE timing pair; run_graph rightly refuses to
        # report when transport noise inverts it, so retry a few times
        # on a loaded machine instead of flaking
        for attempt in range(3):
            try:
                r = B.run_graph(g, repeats=2)
                break
            except RuntimeError:
                if attempt == 2:
                    raise
        assert r["ticks"] == expected_depth
        assert r["scheduling_ms"] >= 0

    def test_indegree_consistency(self):
        g = B.build_map_reduce(202, 100, 4)
        indeg = np.zeros(len(g.indeg), dtype=np.int32)
        np.add.at(indeg, g.dst, 1)
        assert (indeg == g.indeg).all()

    def test_actor_pin_layout(self):
        g = B.build_actor_heavy(10, 5, 4)
        # creations unpinned + resource-bearing; calls pinned + zero-demand
        assert (g.pin[:10] == -1).all()
        assert (g.pin[10:] >= 0).all()
        assert (g.demands[1] == 0).all()

    def test_north_star_is_fanout(self):
        g = B.build_north_star(1000, 4)
        assert g.name.startswith("north_star")
        assert (g.indeg == 0).all()


# ---------------------------------------------------------------------------
# chip-or-fail: no device metric from the CPU, no failure carried past
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_bench_without_smoke_refuses_to_run_off_the_chip():
    """A missing TPU is an error before any section (exit 2), not a
    quiet CPU run under device metric names."""
    import subprocess
    import sys

    from ray_tpu._private import spawn_env

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], cwd=REPO,
        env=spawn_env.child_env(extra={"JAX_PLATFORMS": "cpu"}),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""   # no record at all


def test_bench_section_that_raises_is_recorded_and_fails_the_run(
        capsys, tmp_path, monkeypatch):
    bench = _load_bench()
    monkeypatch.setattr(bench, "PARTIAL_PATH",
                        str(tmp_path / "partial.json"))
    try:
        raise ValueError("section blew up")
    except Exception:
        bench._failed("mfu")
    assert bench.FAILED == {"mfu": "ValueError: section blew up"}
    bench._emit(to_stdout=True)
    captured = capsys.readouterr()
    import json
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert line["sections_failed"] == {"mfu": "ValueError: section blew up"}
    assert "ValueError" in captured.err   # the traceback is shown


def test_device_peaks_are_keyed_by_exact_kind_and_sourced():
    from ray_tpu._private import perf

    v5e = perf.device_peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_sec"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in v5e["source"]
    # no substring matching, no default: an unknown kind is an error
    for kind in ("TPU v5", "tpu v5 lite", "TPU v5 lite pod", "cpu"):
        with pytest.raises(KeyError, match="DEVICE_PEAKS"):
            perf.device_peaks(kind)


# ---------------------------------------------------------------------------
# control ring: ring-on must never be slower than ring-off
# ---------------------------------------------------------------------------

def test_ring_on_never_slower_than_ring_off():
    """The tentpole's enforceable perf bound: batched lease envelopes
    over the shm ring must not lose to the per-task pipe transport
    (bench.py's e2e_ring section records the full-size A/B; this is
    the tier-1 guard at smoke size)."""
    import ray_tpu
    from ray_tpu._private import perf

    def run(ring_on: bool) -> float:
        if not ring_on:
            os.environ["RAY_TPU_CONTROL_RING"] = "0"
        try:
            # e2e_task_throughput's own shutdown() resets the config
            # from the env, so the override takes effect inside
            return perf.e2e_task_throughput(
                n_tasks=800, mode="process", num_workers=2,
                batched=True, best_of=3)["tasks_per_sec"]
        finally:
            os.environ.pop("RAY_TPU_CONTROL_RING", None)

    # shared-VM noise between trials can exceed the margin under test,
    # and load drifts over a long suite run — so each retry re-measures
    # a fresh off/on PAIR under the same machine conditions; a real
    # systematic transport regression fails every pair
    for attempt in range(3):
        off = run(ring_on=False)
        on = run(ring_on=True)
        if on >= 0.85 * off:
            break
    assert on >= 0.85 * off, (
        f"ring-on {on:.0f} tasks/s vs ring-off {off:.0f} tasks/s: the "
        f"shm control ring is slower than the pipe path it replaces")
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# two-level scheduling: head-bypass must never be slower, and must
# actually bypass
# ---------------------------------------------------------------------------

def test_head_bypass_on_never_slower_and_mostly_skips_head():
    """The two-level tentpole's enforceable bound (bench.py's
    head_bypass section records the full-size A/B; this is the tier-1
    guard at smoke size): with actor_p2p + local_dispatch on, the
    worker->actor call lane must not lose to the head round-trip it
    replaces, >=90% of steady-state actor calls must skip the head
    (only the pre-route-resolution call may head-route), and both arms
    must produce identical results."""
    import ray_tpu
    from ray_tpu._private import perf

    n_calls, n_submit = 12, 8
    # fresh on/off PAIR per retry, same reasoning as the ring guard
    for attempt in range(3):
        on = perf.head_bypass_ab(True, n_calls=n_calls,
                                 n_submit=n_submit)
        off = perf.head_bypass_ab(False, n_calls=n_calls,
                                  n_submit=n_submit)
        if on["actor_seconds"] <= off["actor_seconds"] / 0.85:
            break
    # correctness is unconditional — no retry excuses a wrong result
    assert on["total"] == off["total"] == n_calls
    assert on["n_submit"] == off["n_submit"] == n_submit
    # >=90% of steady-state calls skip the head, with no fallbacks
    assert on["calls_p2p"] >= 0.9 * n_calls - 1, on
    assert on["head_fallback"] == 0, on
    # the off arm never bypasses (knobs-off is the pre-PR path)
    assert off["calls_p2p"] == 0 and off["local_dispatch"] == 0, off
    # the sustained-submit lane actually dispatched locally
    assert on["local_dispatch"] >= n_submit, on
    assert on["actor_seconds"] <= off["actor_seconds"] / 0.85, (
        f"p2p-on {on['actor_seconds']}s vs head-routed "
        f"{off['actor_seconds']}s: the peer actor lane is slower than "
        f"the head round-trip it replaces")
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# serving disaggregation: split pools must never lose on TTFT
# ---------------------------------------------------------------------------

def test_serving_split_ttft_never_slower_than_mono():
    """The disaggregation tentpole's enforceable bound (bench.py's
    serving section records the full-size A/B; this is the tier-1
    guard at smoke size): under a concurrent-streams load that
    oversubscribes the mono arm's continuous-batch slots, the split
    arm's p95 TTFT must not lose to mono — a new prompt's first token
    streams straight off the prefill handoff instead of queueing
    behind whole ongoing decodes. Follow-up turns must route back to
    the KV-holding decode replica (affinity), and both arms must
    deliver the same token volume."""
    from ray_tpu._private import perf

    # 6 sessions > the mono arm's 4 total batch slots: mono queues,
    # split streams first tokens off handoffs. Fresh mono/split PAIR
    # per retry (shared-VM noise), same reasoning as the ring guard.
    for attempt in range(3):
        mono = perf.serving_ab(False, sessions=6, turns=2, max_new=24)
        split = perf.serving_ab(True, sessions=6, turns=2, max_new=24)
        if split["ttft_p95_ms"] <= mono["ttft_p95_ms"] / 0.85:
            break
    # correctness is unconditional — no retry excuses a wrong result
    assert split["total_tokens"] == mono["total_tokens"], (split, mono)
    assert split["n_streams"] == mono["n_streams"] == 12
    # follow-up turns hit the KV-holding replica (first-ever turns
    # count neither hit nor miss, so this is the honest follow-up rate)
    assert split["affinity_hit_rate"] is not None
    assert split["affinity_hit_rate"] >= 0.8, split
    # KV pages actually moved through the object plane, and nothing
    # was shed (no SLO target is set in the A/B)
    assert split["kv_bytes"] > 0, split
    assert split["sheds"] == mono["sheds"] == 0
    assert split["ttft_p95_ms"] <= mono["ttft_p95_ms"] / 0.85, (
        f"split p95 TTFT {split['ttft_p95_ms']}ms vs mono "
        f"{mono['ttft_p95_ms']}ms: the disaggregated path is slower "
        f"at first-token than the monolith it replaces")
