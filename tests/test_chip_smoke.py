"""chip_smoke.py: chip-or-fail, and its phases rehearsed off the chip.

The script's phases are plain functions that take their sizes, so the
same code runs here at tiny sizes on the CPU — kernels in interpret
mode, four of conftest's virtual devices for the path across chips —
which finds wrong paths, arguments, meshes and sharding rules before
any chip time is spent. What only the chip can say (that the kernels
run there, at width) is chip_smoke.py's own job."""

import os
import shutil
import subprocess
import sys

import pytest

from ray_tpu._private import spawn_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

TINY = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=128, max_seq_len=64)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_without_a_tpu_it_fails_before_any_phase(argv):
    out = subprocess.run(
        [sys.executable, SCRIPT, *argv], cwd=REPO,
        env=spawn_env.child_env(extra={"JAX_PLATFORMS": "cpu"}),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
    assert "[A." not in out.stdout and "[4." not in out.stdout


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    """The script is nothing without the program."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = spawn_env.child_env()
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.fixture
def smoke():
    import ray_tpu
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    ray_tpu.shutdown()
    clock = chip_smoke.CompileClock()
    yield chip_smoke, clock
    clock.close()
    ray_tpu.shutdown()


def test_phase_scheduling_rehearsal(smoke, capfd):
    """4-row cluster (three process-worker nodes), a 2 k-task
    map-reduce through the public API under sched_backend="jax", a
    10 k-task fan-out driven by the jitted kernels against numpy."""
    chip_smoke, clock = smoke
    chip_smoke.phase_scheduling(
        clock, nodes=4, cpus_per_node=2.0, map_reduce_tasks=2020,
        fan_in=20, north_star_tasks=10_000, get_timeout_s=300.0)
    out = capfd.readouterr().out
    assert "numpy_ticks=0" in out and "results=equal" in out
    assert "node_counts=equal" in out


def test_phase_scheduling_holds_a_dag_to_numpy(smoke):
    """The device drive and the numpy drive agree on a graph WITH
    edges too (the chip run drives the edgeless 1 M fan-out)."""
    from ray_tpu._private import benchmarks

    chip_smoke, clock = smoke
    g = benchmarks.build_map_reduce(2020, 100, 8)
    dev = chip_smoke.drive_device(g, 0.99, clock)
    state, counts, ticks = chip_smoke.drive_numpy(g, 0.99)
    assert ticks == dev["ticks"] == dev["tick_ticks"] == 2
    assert (dev["state"] == state).all()
    assert (dev["counts"] == counts).all() and counts.sum() == 2020


def test_phase_training_rehearsal(smoke, capfd):
    chip_smoke, clock = smoke
    chip_smoke.phase_training(clock, model_kw=TINY, batch=4, seq=33,
                              steps=3, ref_rows=2, expect_flash=False)
    assert "[B.reference]" in capfd.readouterr().out


def test_phase_training_refuses_a_loss_off_the_reference(smoke,
                                                         monkeypatch):
    chip_smoke, clock = smoke
    monkeypatch.setattr(chip_smoke, "_reference_loss",
                        lambda *a, **kw: 1.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="reference"):
        chip_smoke.phase_training(clock, model_kw=TINY, batch=4, seq=33,
                                  steps=2, expect_flash=False)


def test_phase_serving_rehearsal(smoke, capfd):
    """Two engine geometries behind build_llm_app and the split pools
    behind run_disagg_llm; the Pallas paged-attention kernel runs in
    interpret mode here."""
    chip_smoke, clock = smoke
    model = dict(TINY, vocab_size=128, max_seq_len=128)
    chip_smoke.phase_serving(
        clock, model_kw=model, slots=4, page_size=4,
        geometries=((8, 64), (16, 96)), expect_kernel=None,
        prompt_lens=(3, 8, 11), buckets=(8, 16), max_new=6)
    out = capfd.readouterr().out
    assert "[C.disagg.follow_up]" in out and "affinity_hit=2" in out


def test_streamed_tokens_are_held_to_the_naive_forward(smoke):
    """A token that is not the naive greedy token (beyond a bf16
    near-tie) fails the phase."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import Transformer, TransformerConfig

    chip_smoke, _clock = smoke
    cfg = TransformerConfig(**TINY, dtype=jnp.float32)
    model = Transformer(dataclasses.replace(cfg, flash_attention="off"))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompt, toks = [3, 1, 4, 1, 5], []
    for _ in range(4):
        logits = model.apply({"params": params},
                             jnp.asarray([prompt + toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    assert chip_smoke._check_against_naive(
        model, params, prompt, toks) == (4, 0.0)
    wrong = list(toks)
    wrong[2] = int(jnp.argmin(logits[0, -1]))
    with pytest.raises(chip_smoke.SmokeFailure, match="token 2"):
        chip_smoke._check_against_naive(model, params, prompt, wrong)


def test_phase_four_chips_rehearsal(smoke, capfd):
    """Both meshes on four virtual devices; the ring step takes a
    gradient (through the XLA block here, the kernel on the chip)."""
    chip_smoke, clock = smoke
    chip_smoke.phase_four_chips(clock, model_kw=TINY, batch=4, seq=65,
                                steps=2, expect_kernels=False)
    out = capfd.readouterr().out
    assert "[4.for_devices(4)]" in out and "[4.fsdp2_seq2_ring]" in out
    assert "'collective-permute'" in out
