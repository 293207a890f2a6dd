"""The LFM2 configuration (``lfm2_moe``): the plain reference against a
loop written position by position, the configuration file against the
catalog row it was cut from, ``flops_lfm2`` against hand counts at the
cell's widths, and ``serve.moe_grouped_decode_roofline`` on a hand-made
ring and trace (the arithmetic worked out here) and on a window
recorded on the chip from a program that counted no touched experts,
where it reads nothing."""

import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_lfm2 as F
from benchmark import program_spans as ps
from benchmark import weights_lfm2 as W
from benchmark.common import load_json
from benchmark.reference import lfm2 as ref

HERE = os.path.dirname(__file__)
CONFIG = "lfm2-24b-a2b-serve-L9"
CELL = "serve.lfm2-24b-L9.chat-hirate"
TINY = {
    "hidden_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 40, "num_hidden_layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "num_dense_layers": 1, "conv_L_cache": 3, "intermediate_size": 24,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 8,
    "routed_scaling_factor": 1.0, "norm_topk_prob": True,
    "use_expert_bias": True, "router_bias_std": 0.3, "router_eps": 1e-6,
    "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "run": {"dtype": "float32", "param_dtype": "float32"},
}


def full_config():
    return load_json("benchmark", "configs", CONFIG + ".json")


# ----------------------------------------------------------------------
# the reference against a loop over positions
# ----------------------------------------------------------------------

def _rms(x, scale, eps=1e-5):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(p, x):
    g = x @ p["w_gate"]
    return (g / (1 + np.exp(-g)) * (x @ p["w_up"])) @ p["w_down"]


def _rotate(x, t, theta):
    """x [heads, hd] at position t; lanes (0,1), (2,3), ... as pairs."""
    hd = x.shape[-1]
    ang = t / theta ** (np.arange(0, hd, 2) / hd)
    x1, x2 = x[:, 0::2], x[:, 1::2]
    out = np.empty_like(x)
    out[:, 0::2] = x1 * np.cos(ang) - x2 * np.sin(ang)
    out[:, 1::2] = x2 * np.cos(ang) + x1 * np.sin(ang)
    return out


def loop_logits(config, seed, row):
    """The model one position at a time, in float64 numpy, with what
    each layer keeps between positions: a conv layer the rows of B * x
    seen so far, a full layer its keys and values. [S, V]."""
    params = jax.tree_util.tree_map(np.asarray, jax.jit(
        lambda k: W.init_params(config, k, jnp.float32))(W.seed_key(seed)))
    params = jax.tree_util.tree_map(lambda w: w.astype(np.float64), params)
    s = W.dims(config)
    kept = [[] for _ in range(s["layers"])]
    out = []
    for t, tok in enumerate(row):
        x = params["embedding"][tok]
        for i in range(s["layers"]):
            p = params[f"layer_{i}"]
            u = _rms(x, p["RMSNorm_0"]["scale"])
            if s["kinds"][i] == "conv":
                c = p["ShortConv_0"]
                b, cg, xt = np.einsum("d,dkc->kc", u, c["w_in"])
                kept[i].append(b * xt)
                y = sum(c["conv"][k] * kept[i][t - 2 + k]
                        for k in range(3) if t - 2 + k >= 0)
                o = (cg * y) @ c["w_out"]
            else:
                a = p["Attention_0"]
                q = _rms(np.einsum("d,dhk->hk", u, a["wq"]), a["q_norm"])
                k = _rms(np.einsum("d,dhk->hk", u, a["wk"]), a["k_norm"])
                v = np.einsum("d,dhk->hk", u, a["wv"])
                kept[i].append((_rotate(k, t, s["theta"]), v))
                q = _rotate(q, t, s["theta"])
                heads = []
                for h in range(s["h"]):
                    g = h // (s["h"] // s["kv"])
                    sc = np.array([q[h] @ kk[g] for kk, _ in kept[i]])
                    w = np.exp(sc / np.sqrt(s["hd"]) - (sc / np.sqrt(
                        s["hd"])).max())
                    w /= w.sum()
                    heads.append(sum(wj * vv[g]
                                     for wj, (_, vv) in zip(w, kept[i])))
                o = np.einsum("hk,hkd->d", np.stack(heads), a["wo"])
            x = x + o
            g = _rms(x, p["RMSNorm_1"]["scale"])
            if "MLP_0" in p:
                x = x + _swiglu(p["MLP_0"], g)
                continue
            m = p["MoE_0"]
            score = 1 / (1 + np.exp(-(g @ m["router"])))
            picked = np.argsort(-(score + m["bias"]))[:s["top_k"]]
            wsum = score[picked].sum() + config["router_eps"]
            x = x + sum(score[e] / wsum * _swiglu(
                {n: m[n][e] for n in ("w_gate", "w_up", "w_down")}, g)
                for e in picked)
        out.append(_rms(x, params["final_norm"]["scale"])
                   @ params["embedding"].T)
    return np.stack(out)


def test_the_reference_is_the_loop_over_positions():
    """Float32 blocks against float64 positions: 1e-4 on logits of up
    to ~0.3 (the reference's order of sums is not the loop's); a tap
    off by one position moves them by 1e-2."""
    row = np.random.default_rng(0).integers(1, 40, 11)
    got = np.asarray(ref.teacher_forced_logits(
        TINY, 3, row[None], "f32", jnp.float32))[0]
    want = loop_logits(TINY, 3, row)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_controls_move_the_reference():
    row = np.random.default_rng(1).integers(1, 40, 9)
    f32, bf16, fp8 = (np.asarray(ref.teacher_forced_logits(
        TINY, 3, row[None], m, jnp.float32)) for m in ("f32", "bf16", "fp8"))
    assert 1e-4 < np.abs(bf16 - f32).max() < np.abs(fp8 - f32).max()


# ----------------------------------------------------------------------
# the configuration file
# ----------------------------------------------------------------------

def test_the_file_is_the_catalog_row_cut_as_it_says():
    c = full_config()
    bench = load_json("BENCHMARK.json")
    entry = next(e for e in bench["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    assert c["source"] == entry["source"]
    assert c["published"]["num_hidden_layers"] == 40
    assert c["published"]["num_dense_layers"] == 2
    kinds = c["published"]["layer_types"]
    assert len(kinds) == 40 and kinds.count("full_attention") == 10
    # layer 0 and layers 2-9 of the published pattern
    assert c["layer_types"] == kinds[:1] + kinds[2:10]
    assert c["num_hidden_layers"] == 9 and c["num_dense_layers"] == 1
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "vocab_size", "conv_L_cache"):
        assert key not in c["reduced"]
    assert c["rope_parameters"] == {"rope_theta": 1000000,
                                    "rope_type": "default"}
    assert c["experts_held"] == [0, 64] and c["router_eps"] == 1e-6
    for what in ("deployment", "equations"):
        assert len(c[what]) > 200
    assert any("tied head" in a for a in c["assumed"])
    assert any("head_dim" in a for a in c["assumed"])
    # the equations the code and the reference run
    for part in ("z_t = B_t * x~_t", "w_2 multiplies the current position",
                 "+ 1e-6", "before the rotation", "theta 1,000,000",
                 "no shared expert", "tied"):
        assert part in c["equations"], part


def test_the_experts_down_projection_is_drawn_at_its_stated_scale():
    """``expert_down_scale`` scales a routed expert's down projection
    and nothing else; the configuration states it among ``assumed``."""
    c = full_config()
    assert c["expert_down_scale"] == 0.25
    assert any("expert_down_scale 0.25" in a for a in c["assumed"])
    key = W.seed_key(2147483647)
    plain = W.init_expert(TINY, key, 1, 3)
    cut = W.init_expert({**TINY, "expert_down_scale": 0.25}, key, 1, 3)
    np.testing.assert_array_equal(cut["w_down"], 0.25 * plain["w_down"])
    for name in ("w_gate", "w_up"):
        np.testing.assert_array_equal(cut[name], plain[name])


def test_the_description_at_the_cells_widths():
    mcfg = W.description(full_config())
    assert (mcfg.d_model, mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim) == (
        2048, 32, 8, 64)
    assert mcfg.conv_layers == (0, 2, 3, 4, 6, 7, 8)
    assert mcfg.kv_layers == (1, 5) and mcfg.moe_layers == tuple(range(1, 9))
    assert mcfg.experts_held == (0, 64) and mcfg.experts_per_token == 4
    assert mcfg.router_bias and mcfg.router_eps == 1e-6
    assert mcfg.qk_norm and mcfg.tie_embeddings and not mcfg.attn_gate
    assert mcfg.rope_theta == 1e6 and mcfg.d_ff == 11776
    assert mcfg.d_expert == 1536 and mcfg.d_shared == 0


# ----------------------------------------------------------------------
# flops_lfm2 against hand counts
# ----------------------------------------------------------------------

def test_parameters_by_hand():
    c = full_config()
    assert F.conv_mixer_params(c) + 0 == 4 * 2048 ** 2 + 3 * 2048 \
        == 16_783_360
    assert F.attention_mixer_params(c) + 2 * 64 == 10_485_888
    assert 64 * F.expert_params(c) == 603_979_776
    assert F.param_count(c) == 5_177_950_976          # 10.36 GB in bf16
    # a token's share: every expert layer's 4 picks
    assert F.expected_local_picks(c) == 4.0
    assert F.active_params(c) == (
        7 * 16_783_360 + 2 * 10_485_760 + 72_351_744 + 8 * 131_072
        + 134_217_728 + 8 * 4 * 9_437_184)


def test_a_decode_step_by_hand():
    """64 live slots at 400 tokens of context each: 256 picks a layer
    touch 62.9 of the 64 experts: 9.49 GB of them over 8 layers, 0.69
    GB outside, 0.10 GB of keys and values, 7.3 MB of tails read and
    written; the experts are 92 % of the step's 10.30 GB."""
    c = full_config()
    touched = 64 * (1 - (63 / 64) ** 256)
    assert touched == pytest.approx(62.86, abs=0.01)
    got = F.decode_step_bytes(c, 64, 64 * 400)
    experts = 2 * 8 * touched * 9_437_184
    outside = 2 * F.outside_experts_params(c)
    kv = 2 * 2 * 8 * 64 * 2 * 64 * 400
    tails = 2 * 64 * 7 * 2 * 2048 * 4
    assert got == pytest.approx(experts + outside + kv + tails)
    assert experts / got == pytest.approx(0.9212, abs=1e-3)
    assert got == pytest.approx(10.30e9, rel=1e-3)
    assert F.decode_step_flops(c, 64, 64 * 400) == pytest.approx(
        64 * 2 * F.active_params(c) + 2 * 4 * 32 * 64 * 64 * 400)


def test_the_grouped_decode_work_by_hand():
    c = full_config()
    need = F.grouped_decode(c, experts_touched_count=504, local_picks=2048)
    assert need["bytes"] == 2 * (3 * 2048 * 1536 * 504
                                 + 3 * (2048 + 1536) * 2048)
    assert need["flops"] == 6 * 2048 * 1536 * 2048


def test_counted_takes_the_engines_picks():
    c = full_config()
    counted = F.Counted(c, {"moe_picks_total": 800, "moe_picks_local": 800})
    assert counted.local_picks == 4.0
    assert counted.forward_flops(c, 10) == F.forward_flops(c, 10)


# ----------------------------------------------------------------------
# serve.moe_grouped_decode_roofline
# ----------------------------------------------------------------------

NAME = "serve.moe_grouped_decode_roofline"
T0, W0 = 100.0, 5e9


def reader():
    path = os.path.join(HERE, "..", "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location(NAME.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell(config):
    return types.SimpleNamespace(
        config=load_json("benchmark", "configs", config + ".json"),
        peaks=load_json("benchmark", "peaks.json")["TPU v5 lite"], chips=1,
        name=CELL)


def ns(t):
    return W0 + (t - T0) * 1e9


def burst(t, touched, picks):
    """One round at perf_counter t: admit 1 ms, dispatch 2 ms of two
    chunks of 32 steps, fetch 0.9 s, deliver 1 ms."""
    a, d = t + .001, t + .003
    f = d + .9
    dispatch = {"live_slots": 64, "live_ctx_tokens": 25600, "steps": 64,
                "chunks": 2}
    if touched is not None:
        dispatch["moe_experts_touched"] = touched
    return [("engine.admit", t, a, None, None, {}),
            ("engine.dispatch", a, d, None, None, dispatch),
            ("engine.fetch", d, f, None, None, {}),
            ("engine.deliver", f, f + .001, None, None,
             {"kept_tokens": 9, "moe_picks_total": picks,
              "moe_picks_local": picks})]


def hand_made(monkeypatch, touched=(30_000, 32_000)):
    """Two bursts of 64 steps (32,768 local picks each, 30,000 and
    32,000 experts touched of the 64 x 64 x 8 = 32,768 there could
    be); each burst's two chunks of 32 steps run 0.42 s with 768 kernel
    calls of 0.5 ms inside (32 steps x 8 layers x 3 matrices); a call
    outside the decode runs does not count."""
    records = (burst(100.100, touched[0], 32768)
               + burst(101.100, touched[1], 32768))
    monkeypatch.setattr(ps, "since",
                        lambda t: [r for r in records if r[2] >= t])
    modules, ops = [], []
    for start in (100.105, 101.105):
        for c in range(2):
            s = ns(start) + c * 0.42e9
            modules.append(["jit_engine_decode_n32(1)", s, 0.42e9])
            ops += [["moe_grouped.%d" % i, s + 1e5 + i * 5.4e5, 5e5]
                    for i in range(768)]
            ops.append(["fusion.1", s, 1e5])
    ops.append(["moe_grouped.9", ns(101.050), 5e5])     # outside a run
    plane = {"name": "/device:TPU:0",
             "lines": [{"name": "XLA Modules", "events": modules},
                       {"name": "XLA Ops", "events": ops}]}
    summary = {"window": (ns(100.0), ns(103.0)), "window_s": 3.0,
               "t0": 100.0, "t1": 103.0, "planes": [plane]}
    return {"cell": cell(CONFIG), "trace_summary": summary,
            "engine": {"batch_size": 64}, "window": (100.0, 151.0)}


def test_hand_made_bursts_against_their_least_time(monkeypatch, capsys):
    ctx = hand_made(monkeypatch)
    peaks = ctx["cell"].peaks
    least = sum(F.roofline_seconds(**{
        "flops": F.grouped_decode(ctx["cell"].config, t, 32768)["flops"],
        "nbytes": F.grouped_decode(ctx["cell"].config, t, 32768)["bytes"],
        "peak": peaks})["seconds"] for t in (30_000, 32_000))
    # memory-bound: a touched expert's three matrices are 18.9 MB,
    # 23 us of HBM
    assert least == pytest.approx(
        2 * (3 * 2048 * 1536 * 62_000 + 3 * 3584 * 65536) / 819e9)
    got = reader()(ctx)
    assert got == pytest.approx(100 * least / (4 * 768 * 5e-4))
    assert 0 < got <= 100
    line = next(l for l in capsys.readouterr().out.splitlines()
                if l.startswith("[moe_grouped_decode_roofline]"))
    fields = dict(f.split("=") for f in line.split()[1:])
    assert fields["bursts"] == "2" and fields["steps"] == "128"
    assert float(fields["touched_per_step_and_layer"]) == pytest.approx(
        62_000 / 128 / 8)


def test_a_burst_without_the_count_is_left_out(monkeypatch):
    ctx = hand_made(monkeypatch, touched=(None, 32_000))
    c, peaks = ctx["cell"].config, ctx["cell"].peaks
    need = F.grouped_decode(c, 32_000, 32768)
    least = F.roofline_seconds(need["flops"], need["bytes"],
                               peaks)["seconds"]
    assert reader()(ctx) == pytest.approx(100 * least / (2 * 768 * 5e-4))


def test_nothing_to_read_is_none(monkeypatch):
    ctx = hand_made(monkeypatch, touched=(None, None))
    assert reader()(ctx) is None
    assert reader()(dict(hand_made(monkeypatch), trace_summary=None)) is None


def test_a_window_recorded_without_the_count_reads_nothing(monkeypatch):
    """The long-document cell's traced window as the chip recorded it
    (a program from before the count): its decode runs hold
    ``moe_grouped`` calls, its bursts no ``moe_experts_touched``."""
    with open(os.path.join(HERE, "data",
                           "v5e_longdoc_moe_grouped.json")) as f:
        rec = json.load(f)
    ring = [tuple(r) for r in rec["ring"]]
    monkeypatch.setattr(ps, "since",
                        lambda t: [r for r in ring if r[2] >= t])
    window = tuple(rec["run"]["window"])
    ctx = {"cell": cell(CONFIG), "engine": {"batch_size": 64},
           "trace_summary": {"window": window,
                             "window_s": (window[1] - window[0]) / 1e9,
                             "t0": rec["run"]["t0"], "t1": rec["run"]["t1"],
                             "planes": rec["planes"]}}
    assert ps.traced_bursts(ctx)
    assert reader()(ctx) is None
