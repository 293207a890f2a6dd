"""The Solar-Open2 configuration's files: a CPU rehearsal of its cell
at a tiny size through run.py, the fp8 control failing the limit where
the float32 reference passes, the FLOP module's counts by hand, the
weights' layout, and the two traffic files."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_solar_open2 as flops
from benchmark import traffic_gen
from benchmark import weights_solar_open2 as weights
from benchmark.common import load_json, passes
from benchmark.drivers import serve as serve_driver

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve.solar-open2-L4.longdoc-steady"
CONFIG = load_json("benchmark", "configs",
                   "solar-open2-250b-serve-L4-ep8.json")


def run_py(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_end_to_end(trace):
    proc = run_py(["--workload", CELL, "--seed", str(2**31 + 11),
                   "--seconds", "3", "--trace", str(trace), "--rehearsal",
                   os.path.join("benchmark", "tests",
                                "tiny_serve_decoder.json")])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert all(n.startswith("cpu_rehearsal.") for n in line["metrics"])
    names = {n[len("cpu_rehearsal."):] for n in line["metrics"]}
    if trace:
        # the readers that need no device trace found something to read
        assert {"serve.moe_local_pick_share", "serve.moe_load_imbalance",
                "serve.prefill_pad_share",
                "serve.decode_useful_share"} <= names
        share = line["metrics"]["cpu_rehearsal.serve.moe_local_pick_share"]
        assert 20.0 < share["value"] < 30.0          # 8 of 32 held
        assert proc.stdout.count("agree=True") >= 3
        assert "agree=False" not in proc.stdout
    else:
        assert names == {"setup_s", "serve_tpot_p90_ms"}


def test_without_a_tpu_the_normal_path_exits_2():
    proc = run_py(["--workload", CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert proc.returncode == 2 and "no TPU" in proc.stderr


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_serving_control_fails(seed):
    """``output_checks`` with the float8 reference in the program's
    place, at every published width: layers 0 and 1 (one of each
    mixer), 8 held experts of a router of 320 and 4,096 rows of the
    vocabulary, what a test run can hold. The limit is the cell's."""
    config = {**CONFIG, "num_hidden_layers": 2, "vocab_size": 4096,
              "experts_held": [0, 8]}
    cell = types.SimpleNamespace(name=CELL, config=config, seed=seed)
    rng = np.random.default_rng(seed)
    sample = []
    for plen, n_out in ((40, 56), (17, 79)):
        s = serve_driver.Served(
            {"prompt": rng.integers(1, 4096, plen).tolist(),
             "max_new": n_out}, 0.0)
        s.tokens = rng.integers(1, 4096, n_out).tolist()
        sample.append(s)
    control = serve_driver.output_checks(cell, sample, 0, control="fp8")
    assert not passes(control), control
    assert [n for n, v, lim in control if not v <= lim] == [
        "widest_logit_gap"]
    exact = serve_driver.output_checks(cell, sample, 0, control="f32")
    assert passes(exact) and exact[0][1] == 0.0


def test_flop_module_counts_by_hand():
    d, hd, f = 4096, 128, 1280
    gqa = d * 64 * hd * 3 + d * 8 * hd * 2                  # q, o, gate; k, v
    dr = 4 * d * 64 * hd + 2 * (d * 128 + 128 * 64 * hd) + d * 64
    assert flops.mixer_params(CONFIG, 0) == gqa == 109_051_904
    assert flops.mixer_params(CONFIG, 1) == dr == 137_625_600
    expert = 3 * d * f
    assert flops.expert_params(CONFIG) == expert == 15_728_640
    outside = gqa + 3 * dr + 4 * (d * 320 + expert) + 24_576 * d
    assert flops.outside_experts_params(CONFIG) == outside
    assert flops.param_count(CONFIG) == (outside + 24_576 * d
                                         + 4 * 40 * expert) == 3_307_995_136
    # 8 picks a token, 40 of 320 held: 1 local pick a layer
    assert flops.expected_local_picks(CONFIG) == 1.0
    assert flops.active_params(CONFIG) == outside + 4 * expert
    assert flops.forward_flops(CONFIG, 10) == 20.0 * (outside + 4 * expert)
    # a prompt of 1,000: the head once, causal attention in one layer
    per_token = 2.0 * (outside - 24_576 * d + 4 * expert) + 3 * 64 * 7 * hd * hd
    want = (1000 * per_token + 2.0 * 24_576 * d
            + 2 * 2 * 64 * hd * 1000 * 1001 / 2)
    assert flops.prefill_flops(CONFIG, [1000]) == pytest.approx(want)
    # a decode step of 30 live slots holding 200,000 tokens: 30 local
    # picks a layer touch 40 (1 - (39/40)^30) = 21.3 experts
    touched = 4 * 40 * (1 - (39 / 40) ** 30)
    want = (2 * (outside + touched * expert)
            + 30 * 3 * 64 * hd * hd * 4 * 2 + 200_000 * 2 * 8 * hd * 2)
    assert flops.decode_step_bytes(CONFIG, 30, 200_000) == pytest.approx(want)
    counted = flops.Counted(CONFIG, {"moe_picks_total": 3200,
                                     "moe_picks_local": 800})
    assert counted.local_picks == 2.0
    assert counted.forward_flops(CONFIG, 1) == 2.0 * (outside + 8 * expert)


def test_weights_are_the_programs_layout_and_the_chips_share():
    import jax
    import jax.numpy as jnp

    tiny = {**CONFIG, **load_json("benchmark", "tests",
                                  "tiny_serve_decoder.json")["config"]}
    shapes = jax.eval_shape(lambda k: weights.init_params(
        tiny, k, jnp.float32), jax.random.PRNGKey(0))
    assert set(shapes) == {"embedding", "lm_head", "final_norm", "layer_0",
                           "layer_1", "layer_2", "layer_3"}
    assert "Attention_0" in shapes["layer_0"]
    assert all("DeltaRule_0" in shapes[f"layer_{i}"] for i in (1, 2, 3))
    moe = shapes["layer_2"]["MoE_0"]
    assert moe["router"].shape == (64, 32)          # every output
    assert moe["w_gate"].shape == (8, 64, 32)       # the held experts
    # an expert remade alone is the one in the stack
    key = weights.seed_key(2**31 + 5)
    whole = weights.init_layer(tiny, key, 2)["MoE_0"]
    one = weights.init_expert(tiny, key, 2, 5)
    np.testing.assert_array_equal(np.asarray(whole["w_down"][5]),
                                  np.asarray(one["w_down"]))
    full = jax.eval_shape(lambda k: weights.init_params(
        CONFIG, k, jnp.bfloat16), jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(full))
    assert abs(held - flops.param_count(CONFIG)) < 1e-3 * held


@pytest.mark.parametrize("name, prompt_mean, out_mean", [
    ("longdoc-steady", (6000, 7600), (150, 185)),
    ("decode-heavy", (30, 40), (700, 800)),
])
def test_traffic_files_give_the_lengths_they_state(name, prompt_mean,
                                                   out_mean):
    mix = load_json("benchmark", "traffic", name + ".json")
    reqs = traffic_gen.serve_requests(mix, 24_576, 1, 300.0)
    p = [len(r["prompt"]) for r in reqs]
    o = [r["max_new"] for r in reqs]
    assert prompt_mean[0] < np.mean(p) < prompt_mean[1]
    assert out_mean[0] < np.mean(o) < out_mean[1]
    eng = mix["engine"]
    ctx = eng["page_size"] * eng["max_pages_per_seq"]
    assert max(p) <= max(eng["prefill_buckets"])
    assert max(a + b for a, b in zip(p, o)) <= ctx
    # every slot's whole context fits the pool
    assert eng["num_pages"] - 1 >= min(
        eng["batch_size"] * eng["max_pages_per_seq"],
        524_288 // eng["page_size"])
