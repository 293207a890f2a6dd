"""The Trinity-Large configuration's files: a CPU rehearsal of its cell
at a tiny size through run.py, the reference's control modes through
the cell's own ``output_checks``, the FLOP module's counts by hand
(ISSUE 33's arithmetic), the weights' layout and count, what the
configuration file states of its cut, and the traffic file."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_trinity_large as flops
from benchmark import traffic_gen
from benchmark import weights_trinity_large as weights
from benchmark.common import load_json, passes
from benchmark.drivers import serve as serve_driver
from benchmark.drivers import serve_described

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve.trinity-large-L5.mixed-steady"
CONFIG = load_json("benchmark", "configs",
                   "trinity-large-preview-serve-L5-ep8.json")
TINY = os.path.join("benchmark", "tests", "tiny_serve_trinity.json")


def run_py(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_end_to_end(trace):
    proc = run_py(["--workload", CELL, "--seed", str(2**31 + 13),
                   "--seconds", "3", "--trace", str(trace), "--rehearsal",
                   TINY])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert all(n.startswith("cpu_rehearsal.") for n in line["metrics"])
    names = {n[len("cpu_rehearsal."):] for n in line["metrics"]}
    if trace:
        # the readers that need no device trace found something to
        # read; the two that read the kernels' device time found none
        assert {"serve.window_kv_held_share", "serve.moe_local_pick_share",
                "serve.moe_load_imbalance", "serve.prefill_pad_share",
                "serve.decode_useful_share"} <= names
        assert not {"serve.window_read_roofline",
                    "serve.window_prefill_attn_roofline"} & names
        held = line["metrics"]["cpu_rehearsal.serve.window_kv_held_share"]
        # contexts of 7 to 144 tokens against a window of 16: a ring of
        # 3 pages of 8 where the page table holds up to 18
        assert 10.0 < held["value"] < 90.0
        share = line["metrics"]["cpu_rehearsal.serve.moe_local_pick_share"]
        # 8 of 32 held; the bias tilts a tiny router's picks a little
        assert 15.0 < share["value"] < 35.0
        assert proc.stdout.count("agree=True") >= 4
        assert "agree=False" not in proc.stdout
    else:
        assert names == {"setup_s", "serve_tpot_p90_ms"}


def test_a_program_without_window_layers_fails_at_once_in_description(
        monkeypatch):
    """What the parent commit does under these files: its
    ``LayerSpec`` knows no ``window``, and ``weights.description`` is
    the first thing the driver asks for."""
    from ray_tpu.models import decoder

    monkeypatch.setattr(decoder, "MIXERS", ("attention", "delta_rule",
                                            "latent"))
    with pytest.raises(ValueError, match="unknown layer kinds"):
        weights.description(CONFIG)


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_serving_controls(seed):
    """The cell's own ``output_checks`` (``drivers/serve_described.py``)
    with the reference in a lower precision in the program's place, at
    every published width: layers 0 and 1 of the kept five (the dense
    layer and one expert layer, both window layers) plus the period's
    full layer, 8 held experts of a router of 256 and 2,048 rows of the
    vocabulary, 140 compared tokens in contexts up to 100, what a test
    run can hold. The limits are the cell's. The fp8 control fails by
    the share of tokens that are not the reference's first; the
    reference judged against itself reads zero."""
    config = {**CONFIG, "num_hidden_layers": 3, "vocab_size": 2048,
              "experts_held": [0, 8], "sliding_window": 64,
              "layer_types": ["sliding_attention", "sliding_attention",
                              "full_attention"]}
    cell = types.SimpleNamespace(name=CELL, config=config, seed=seed)
    rng = np.random.default_rng(seed)
    sample = []
    for plen, n_out in ((40, 60), (17, 80)):
        s = serve_driver.Served(
            {"prompt": rng.integers(1, 2048, plen).tolist(),
             "max_new": n_out}, 0.0)
        s.tokens = rng.integers(1, 2048, n_out).tolist()
        sample.append(s)
    control = serve_described.output_checks(cell, sample, 0, control="fp8")
    assert not passes(control), control
    assert "argmax_miss_share" in [n for n, v, lim in control
                                   if not v <= lim]
    assert dict((n, v) for n, v, _ in control)["argmax_miss_share"] > 0.15
    exact = serve_described.output_checks(cell, sample, 0, control="f32")
    assert passes(exact) and [v for _, v, _ in exact] == [0.0, 0.0, 0.0]
    bf16 = serve_described.output_checks(cell, sample, 0, control="bf16")
    assert dict((n, v) for n, v, _ in bf16)["argmax_miss_share"] < 0.1


def test_the_limits_against_the_chips_readings():
    """PR 33's chip runs at the cell's own size (the limits file's
    ``_readings``): a sound run misses the reference's first token at
    most 4.8 % of the time and the fp8 control at least 28.8 %; the
    widest gap reached 1.021 sound against 1.029 fp8 and is a guard
    only."""
    limits = load_json("benchmark", "limits", CELL + ".json")["limits"]
    assert 2 * 0.0483 < limits["argmax_miss_share"] < 0.5 * 0.288
    assert limits["widest_logit_gap"] > 1.5 * 1.021


def test_flop_module_counts_by_hand():
    """ISSUE 33's arithmetic under Motivation, recounted with the norms'
    scales and the bias."""
    d, h, kv, hd = 3072, 48, 8, 128
    attn = d * h * hd * 3 + 2 * d * kv * hd
    assert flops.mixer_params(CONFIG) == attn == 62_914_560
    expert = 3 * d * 3072
    assert flops.expert_params(CONFIG) == expert == 28_311_552
    outside = (5 * attn + 3 * d * 12288 + 4 * (d * 256 + expert)
               + 25_024 * d)
    assert flops.outside_experts_params(CONFIG) == outside
    scales = 5 * (4 * d + 2 * hd) + d
    assert flops.param_count(CONFIG) == (
        outside + 25_024 * d + 4 * (32 * expert + 256) + scales
    ) == 4_321_903_872
    assert flops.n_window_layers(CONFIG) == 4
    # 4 picks a token, 32 of 256 held: half a local pick a layer
    assert flops.expected_local_picks(CONFIG) == 0.5
    assert flops.active_params(CONFIG) == outside + 2 * expert
    assert flops.forward_flops(CONFIG, 10) == 20.0 * (outside + 2 * expert)
    pair = 2 * 2 * h * hd
    # a prompt of 1,000 lies inside the window: band and triangle agree
    tri = 1000 * 1001 / 2
    assert flops.band_pairs(1000, 4096) == tri
    assert flops.window_prefill_attention(CONFIG, [1000]) == {
        "flops": 4 * pair * tri,
        "bytes": 4.0 * (2 * h + 2 * kv) * hd * 2 * 1000}
    # a prompt of 16,384: row i sees min(i + 1, 4096) keys: 58.7 M
    # score cells of the triangle's 134 M
    band = 4096 * 4097 / 2 + (16384 - 4096) * 4096
    assert flops.band_pairs(16384, 4096) == band
    assert round(band / 1e6, 1) == 58.7
    assert round(16384 * 16385 / 2 / 1e6) == 134
    assert flops.window_prefill_attention(CONFIG, [16384, 1000])[
        "flops"] == 4 * pair * (band + tri)
    per_token = 2.0 * (outside - 25_024 * d + 2 * expert)
    assert flops.prefill_flops(CONFIG, [16384]) == pytest.approx(
        16384 * per_token + 2.0 * 25_024 * d
        + pair * 16384 * 16385 / 2 + 4 * pair * band)
    # a decode step of 32 live slots holding 160,000 tokens of which
    # 83,200 lie inside their slot's window: 4,096 B of keys and
    # values a token and layer
    read = flops.window_read(CONFIG, 83_200, 32)
    assert read["flops"] == 4 * pair * 83_200
    assert read["bytes"] == 4 * (4096 * 83_200 + 32 * h * hd * (2 + 4))
    touched = 4 * 32 * (1 - (31 / 32) ** 16)
    rows = 4096 * (160_000 + 4 * 83_200)
    want = 2 * (outside + touched * expert) + rows
    assert flops.decode_step_bytes(
        CONFIG, 32, 160_000, window_share=0.52) == pytest.approx(want)
    assert flops.decode_step_flops(
        CONFIG, 32, 160_000, window_share=0.52) == pytest.approx(
            32 * 2.0 * (outside + 2 * expert)
            + pair * (160_000 + 4 * 83_200))
    # uncut, every window layer would read the whole context
    assert flops.decode_step_bytes(CONFIG, 32, 160_000) == pytest.approx(
        2 * (outside + touched * expert) + 4096 * 5 * 160_000)
    counted = flops.Counted(CONFIG, {
        "moe_picks_total": 3200, "moe_picks_local": 800,
        "decode_ctx_tokens_live": 1000, "decode_window_tokens_live": 520})
    assert counted.local_picks == 1.0 and counted.window_share == 0.52
    assert counted.forward_flops(CONFIG, 1) == 2.0 * (outside + 4 * expert)
    assert counted.decode_step_bytes(
        CONFIG, 32, 160_000, weight_bytes=2, kv_bytes=2,
        state_bytes=4) == pytest.approx(
            2 * (outside + 4 * 32 * (1 - (31 / 32) ** 32) * expert) + rows)


def test_the_configuration_file_states_its_cut():
    """Every key of the catalog row's ``config`` under its own name,
    the five reduced keys with their published values beside them, no
    width among them."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 3072,
        "intermediate_size": 12288, "load_balance_coeff": 5e-05,
        "max_position_embeddings": 262144, "model_type": "afmoe",
        "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 48, "num_dense_layers": 6,
        "num_expert_groups": 1, "num_experts": 256,
        "num_experts_per_tok": 4, "num_hidden_layers": 60,
        "num_key_value_heads": 8, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.448, "score_func": "sigmoid",
        "sliding_window": 4096, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
        "layer_types": (["sliding_attention"] * 3
                        + ["full_attention"]) * 15}
    differs = {k for k, v in published.items() if CONFIG[k] != v}
    assert differs == set(CONFIG["reduced"]) == set(CONFIG["published"])
    assert all(CONFIG["published"][k] == published[k]
               for k in differs - {"layer_types"})
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["num_experts"], CONFIG["vocab_size"]) == (
                5, 1, 32, 25024)
    # layers 0 and 8-11: the dense layer's kind, then one whole period
    assert CONFIG["layer_types"] == [published["layer_types"][i]
                                     for i in (0, 8, 9, 10, 11)]
    assert CONFIG["experts_held"] == [0, 32] and CONFIG[
        "router_width"] == 256 and CONFIG["vocab_size"] * 8 == 200192
    entry = next(c for c in load_json("BENCHMARK.json")["configs"]
                 if c["file"].endswith(
                     "trinity-large-preview-serve-L5-ep8.json"))
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert len(CONFIG["assumed"]) >= 7 and "8 chips" in CONFIG["deployment"]


def test_weights_are_the_programs_layout_and_the_chips_share():
    import jax
    import jax.numpy as jnp

    tiny = {**CONFIG, **load_json(TINY)["config"]}
    shapes = jax.eval_shape(lambda k: weights.init_params(
        tiny, k, jnp.float32), jax.random.PRNGKey(0))
    assert set(shapes) == {"embedding", "lm_head", "final_norm", "layer_0",
                           "layer_1", "layer_2"}
    assert all(sorted(shapes[f"layer_{i}"]["Attention_0"]) == [
        "k_norm", "q_norm", "w_gate", "wk", "wo", "wq", "wv"]
        for i in range(3))
    assert "MLP_0" in shapes["layer_0"] and "MoE_0" not in shapes["layer_0"]
    moe = shapes["layer_2"]["MoE_0"]
    assert moe["router"].shape == (64, 32)          # every output
    assert moe["bias"].shape == (32,)
    assert moe["w_gate"].shape == (8, 64, 32)       # the held experts
    mcfg = weights.description(tiny)
    assert [(l.mixer, l.ffn) for l in mcfg.layers] == [
        ("window", "dense"), ("window", "experts"),
        ("attention", "experts")]
    assert mcfg.routed_scale == 2.448 and mcfg.sandwich_norm
    assert mcfg.window == 16 and mcfg.qk_norm and mcfg.attn_gate
    assert mcfg.router_bias and mcfg.embed_scale == 8.0
    assert not mcfg.rope_attention and mcfg.rope_theta == 10000.0
    # an expert remade alone is the one in the stack; norm scales and
    # the bias are drawn, not ones and zeros
    key = weights.seed_key(2**31 + 5)
    whole = weights.init_layer(tiny, key, 2)
    one = weights.init_expert(tiny, key, 2, 5)
    np.testing.assert_array_equal(np.asarray(whole["MoE_0"]["w_down"][5]),
                                  np.asarray(one["w_down"]))
    assert 0.01 < float(np.std(whole["MoE_0"]["bias"])) < 0.04
    assert 0.05 < float(np.std(whole["PostNorm_1"]["scale"])) < 0.2
    full = jax.eval_shape(lambda k: weights.init_params(
        CONFIG, k, jnp.bfloat16), jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(full))
    assert held == flops.param_count(CONFIG) == 4_321_903_872
    # 8.64 GB in bfloat16
    assert round(2 * held / 1e9, 2) == 8.64


def test_traffic_file_gives_the_lengths_it_states():
    mix = load_json("benchmark", "traffic", "mixed-steady.json")
    reqs = traffic_gen.serve_requests(mix, 25_024, 1, 300.0)
    p = np.asarray([len(r["prompt"]) for r in reqs])
    o = np.asarray([r["max_new"] for r in reqs])
    # as clipped: 3,743 and 570 (unclipped 4,474 and 613)
    assert 3600 < p.mean() < 3900 and 555 < o.mean() < 585
    assert 350 < np.quantile(p, 0.1) < 470
    assert 9500 < np.quantile(p, 0.9) < 11000
    # 29 % of prompts are longer than the window when they arrive
    assert 0.26 < (p > 4096).mean() < 0.32
    assert 0.03 < (p == 16384).mean() < 0.07
    eng = mix["engine"]
    ctx = eng["page_size"] * eng["max_pages_per_seq"]
    assert max(p) <= max(eng["prefill_buckets"]) == 16384
    assert max(a + b for a, b in zip(p, o)) <= ctx == 17_408
    assert o.min() >= 256 and o.max() <= 1024
    # every slot's whole context fits the full layer's pool
    assert eng["num_pages"] - 1 >= (eng["batch_size"]
                                    * eng["max_pages_per_seq"])
    assert mix["driver"] == "serve_described" and mix["check_requests"] == 3
