"""run.py end to end at a tiny size on the CPU for each driver, behind
the test-only --rehearsal argument; the four-device path on four
virtual devices; the last line against the contract's keys; and the
normal path, which without a TPU exits 2 and prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAIN, SERVE = "train.mistral7b-L2.seq4k", "serve.mistral7b-L16.chat-steady"


def run_py(args, root=ROOT, devices=4):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def check_contract(line, bench, workload, trace):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert isinstance(line["correct"], bool)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        # a CPU number never goes under a device metric's name
        assert name.startswith("cpu_rehearsal.")
    names = {n[len("cpu_rehearsal."):] for n in line["metrics"]}
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in bench[kind]}
    assert names <= declared
    if not trace:
        assert "setup_s" in names and len(names) >= 2
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    return names


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload, tiny, trace, devices", [
    (TRAIN, "tiny_train.json", 0, 1),
    (TRAIN, "tiny_train.json", 1, 1),
    (TRAIN, "tiny_train_4chips.json", 0, 4),
    (SERVE, "tiny_serve.json", 0, 1),
    (SERVE, "tiny_serve.json", 1, 1),
])
def test_rehearsal_end_to_end(bench, workload, tiny, trace, devices):
    proc = run_py(["--workload", workload, "--seed", str(2**31 + 11),
                   "--seconds", "3", "--trace", str(trace), "--rehearsal",
                   os.path.join("benchmark", "tests", tiny)],
                  devices=devices)
    line = last_line(proc)
    names = check_contract(line, bench, workload, trace)
    assert line["correct"] is True, proc.stderr[-2000:]
    assert line["device"]["count"] == devices
    # the numbers compared are the last lines of stderr
    tail = proc.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)
    if trace:
        # the readers that need no device trace found something to read
        spans = {TRAIN: "train.report_ms_p50",
                 SERVE: "serve.start_stream_ms_p50"}
        assert spans[workload] in names
    if devices == 4:
        assert "mesh=" in proc.stdout


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
def test_without_a_tpu_the_normal_path_exits_2(workload):
    proc = run_py(["--workload", workload, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_unknown_workload_is_refused():
    proc = run_py(["--workload", "nope", "--seed", "1", "--seconds", "1"])
    assert proc.returncode != 0 and "no workload" in proc.stderr


def test_bare_checkout_exits_nonzero(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's
    paths has no program to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_py(["--workload", TRAIN, "--seed", "1", "--seconds", "1",
                   "--trace", "0"], root=str(tmp_path))
    assert proc.returncode not in (0, None)
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())
