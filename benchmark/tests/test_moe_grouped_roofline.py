"""serve.moe_grouped_prefill_roofline: the held experts' grouped
products (``moe_grouped*`` operations) against the least time of the
prefill launches' local picks, on a hand-made ring and trace (the
arithmetic worked out here), on a traced window of the long-document
cell recorded on the chip (benchmark/tests/data/
v5e_longdoc_moe_grouped.json) and on the slice recorded from a dense
model's cell (benchmark/tests/data/v5e_serve_slice.json), where no such
operation runs and the reader reads nothing."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import program_spans as ps
from benchmark import trace_reduce
from benchmark.common import load_json

HERE = os.path.dirname(__file__)
NAME = "serve.moe_grouped_prefill_roofline"
HYBRID = ["serve.solar-open2-L4.longdoc-steady",
          "serve.openpangu-ultra-L5.reason-steady",
          "serve.trinity-large-L5.mixed-steady"]


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
        peaks=load_json("benchmark", "peaks.json")["TPU v5 lite"], chips=1)


# perf_counter 100.0 is trace time 5e9 ns; the traced window is 2 s long
T0, W0 = 100.0, 5e9


def ns(t):
    return W0 + (t - T0) * 1e9


def burst(t, fetch, launches):
    """One round at perf_counter t: admit 4 ms holding the launch
    records, dispatch 2 ms of one 32-step chunk, ``fetch`` s, deliver
    1 ms."""
    a, d = t + .004, t + .006
    f = d + fetch
    return ([("engine.admit", t, a, None, None, {}),
             ("engine.dispatch", a, d, None, None,
              {"live_slots": 4, "live_ctx_tokens": 9000, "steps": 32,
               "chunks": 1}),
             ("engine.fetch", d, f, None, None, {}),
             ("engine.deliver", f, f + .001, None, None, {"kept_tokens": 9})]
            + [("engine.prefill_launch", t + .001 + i * 1e-4, t + .002,
                None, None, fields) for i, fields in enumerate(launches)])


def launch(bucket, picks, touched):
    load = [picks // touched] * touched + [0] * (40 - touched)
    return {"bucket": bucket, "rows": 1, "useful_rows": 1,
            "prompt_tokens": bucket, "prompt_lens": [bucket],
            "moe_picks_total": 8 * bucket * 4, "moe_picks_local": picks,
            "moe_expert_load_max": max(load), "moe_load_by_expert": load}


def hand_made(monkeypatch):
    """Solar's cell. Burst 1 at 100.100 holds a launch of bucket 16,384
    (65,536 local picks over the four expert layers, all 40 held
    experts touched): its program runs 100.105-100.505 with 24 calls of
    the kernel of 1 ms, then the burst's 32-step chunk 100.505-100.695,
    in which 12 calls of 0.04 ms do not count. Burst 2 at 100.800 holds
    a launch of bucket 2,048 (8,192 local picks, 30 experts touched):
    its program 100.805-100.905, 24 calls of 0.25 ms; its chunk
    100.905-101.095."""
    records = (burst(100.100, .6, [launch(16384, 65536, 40)])
               + burst(100.800, .3, [launch(2048, 8192, 30)]))
    records.sort(key=lambda r: r[2])
    monkeypatch.setattr(ps, "since",
                        lambda t: [r for r in records if r[2] >= t])
    modules = [["jit_engine_prefill_b16384(1)", ns(100.105), .400e9],
               ["jit_engine_decode_n32(2)", ns(100.505), .190e9],
               ["jit_engine_prefill_b2048(3)", ns(100.805), .100e9],
               ["jit_engine_decode_n32(2)", ns(100.905), .190e9]]
    ops = ([["moe_grouped.%d" % i, ns(100.106) + i * 2e6, 1e6]
            for i in range(24)]
           + [["moe_grouped.%d" % i, ns(100.506) + i * 1e6, 4e4]
              for i in range(12)]
           + [["moe_grouped.%d" % i, ns(100.806) + i * 1e6, 2.5e5]
              for i in range(24)]
           + [["fusion.1", s, d] for _, s, d in modules])
    plane = {"name": "/device:TPU:0",
             "lines": [{"name": "XLA Modules", "events": modules},
                       {"name": "XLA Ops", "events": ops}]}
    summary = {"window": (ns(100.0), ns(102.0)), "window_s": 2.0,
               "t0": 100.0, "t1": 102.0, "planes": [plane]}
    return {"cell": cell("solar-open2-250b-serve-L4-ep8"),
            "trace_summary": summary, "engine": {"batch_size": 64},
            "window": (100.0, 151.0)}


def least_s(picks, touched, peaks):
    d, f = 4096, 1280
    flops = 6.0 * picks * d * f
    nbytes = 2.0 * (3 * 4 * touched * d * f + 3 * picks * (d + f))
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def test_hand_made_launches_against_their_least_time(monkeypatch, capsys):
    ctx = hand_made(monkeypatch)
    peaks = ctx["cell"].peaks
    least = least_s(65536, 40, peaks) + least_s(8192, 30, peaks)
    # the 16,384 launch is compute-bound: 2.06 TFLOP, 10.5 ms
    assert least_s(65536, 40, peaks) == pytest.approx(
        6.0 * 65536 * 4096 * 1280 / 197e12)
    got = reader()(ctx)
    assert got == pytest.approx(100 * least / (0.024 + 0.006))
    assert 0 < got <= 100
    out = capsys.readouterr().out
    line = next(l for l in out.splitlines()
                if l.startswith("[moe_grouped_prefill_roofline]"))
    fields = dict(f.split("=") for f in line.split()[1:])
    assert fields["launches"] == fields["launches_in_traced_bursts"] == "2"
    assert float(fields["kernel_s"]) == pytest.approx(0.030)


def test_a_launch_without_counts_is_left_out(monkeypatch):
    ctx = hand_made(monkeypatch)
    records = ps.since(float("-inf"))
    late = next(r for r in records if r[0] == "engine.prefill_launch"
                and r[5]["bucket"] == 2048)
    del late[5]["moe_load_by_expert"]
    least = least_s(65536, 40, ctx["cell"].peaks)
    assert reader()(ctx) == pytest.approx(100 * least / 0.024)


def test_a_share_over_100_raises(monkeypatch):
    ctx = hand_made(monkeypatch)
    for e in ctx["trace_summary"]["planes"][0]["lines"][1]["events"]:
        if e[0].startswith("moe_grouped"):
            e[2] /= 100.0             # calls a hundred times too short
    with pytest.raises(trace_reduce.ShareOverOne):
        reader()(ctx)


def test_nothing_to_read_is_none(monkeypatch):
    """No trace, no ring, and grouped products that are not the kernel
    (the parent's ``ragged-dot`` operations)."""
    ctx = hand_made(monkeypatch)
    assert reader()(dict(ctx, trace_summary=None)) is None
    for e in ctx["trace_summary"]["planes"][0]["lines"][1]["events"]:
        e[0] = e[0].replace("moe_grouped", "ragged-dot-none")
    assert reader()(ctx) is None
    monkeypatch.setattr(ps, "since", lambda t: [])
    ctx.pop("_traced_bursts", None)
    assert reader()(ctx) is None


def recorded_context(monkeypatch):
    with open(os.path.join(HERE, "data",
                           "v5e_longdoc_moe_grouped.json")) as f:
        rec = json.load(f)
    ring = [tuple(r) for r in rec["ring"]]
    monkeypatch.setattr(ps, "since",
                        lambda t: [r for r in ring if r[2] >= t])
    window = tuple(rec["run"]["window"])
    return rec, {"cell": cell("solar-open2-250b-serve-L4-ep8"),
                 "engine": {"batch_size": 64},
                 "trace_summary": {
                     "window": window,
                     "window_s": (window[1] - window[0]) / 1e9,
                     "t0": rec["run"]["t0"], "t1": rec["run"]["t1"],
                     "planes": rec["planes"]}}


def test_the_recorded_long_document_window(monkeypatch, capsys):
    """What the chip's run read, from what it recorded: every launch of
    the traced bursts matched, under 100 %, and the kernel's time
    inside the prefill executions only."""
    rec, ctx = recorded_context(monkeypatch)
    got = reader()(ctx)
    assert got == pytest.approx(51.875, abs=1e-3)
    assert 0 < got <= 100
    line = next(l for l in capsys.readouterr().out.splitlines()
                if l.startswith("[moe_grouped_prefill_roofline]"))
    fields = dict(f.split("=") for f in line.split()[1:])
    assert fields["launches"] == fields["launches_in_traced_bursts"]
    assert int(fields["launches"]) >= 15
    calls = sum(d for n, _, d in trace_reduce.line_events(
        rec["planes"][0], trace_reduce.OPS_LINE)) / 1e9
    assert float(fields["kernel_s"]) <= calls
    # the parent's program: the same window with XLA's ragged dot
    for e in rec["planes"][0]["lines"][1]["events"]:
        e[0] = e[0].replace("moe_grouped", "ragged-dot-none")
    ctx.pop("_traced_bursts", None)
    assert reader()(ctx) is None


@pytest.mark.parametrize("config", ["mistral-7b-v0.3-serve-L16",
                                    "solar-open2-250b-serve-L4-ep8"])
def test_the_recorded_dense_slice_reads_nothing(monkeypatch, config):
    """The recorded slice of a dense model's cell has prefill launches
    and no ``moe_grouped`` operation: nothing to read, whichever
    configuration the cell names."""
    with open(os.path.join(HERE, "data", "v5e_serve_slice.json")) as f:
        rec = json.load(f)
    ring = [tuple(r) for r in rec["ring"]]
    monkeypatch.setattr(ps, "since",
                        lambda t: [r for r in ring if r[2] >= t])
    window = tuple(rec["slice"]["window"])
    ctx = {"cell": cell(config), "engine": {"batch_size": 32},
           "trace_summary": {"window": window,
                             "window_s": (window[1] - window[0]) / 1e9,
                             "t0": rec["slice"]["t0"],
                             "t1": rec["slice"]["t1"],
                             "planes": rec["planes"]}}
    assert not any(n.startswith("moe_grouped") for n, _, _ in
                   trace_reduce.line_events(rec["planes"][0],
                                            trace_reduce.OPS_LINE))
    assert reader()(ctx) is None


@pytest.mark.parametrize("config, d, f", [
    ("solar-open2-250b-serve-L4-ep8", 4096, 1280),
    ("openpangu-ultra-moe-718b-serve-L5-ep16", 7680, 2048),
    ("trinity-large-preview-serve-L5-ep8", 3072, 3072)])
def test_what_a_launch_requires_in_each_hybrid_configuration(config, d, f):
    """Four expert layers in each of the three cuts (the dense leading
    layer of two of them has no experts): 6 d f FLOPs a local pick, the
    touched experts' three matrices once an expert layer."""
    import importlib

    path = os.path.join(HERE, "..", "layer_metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("reader_module", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    c = cell(config).config
    dims = importlib.import_module("benchmark." + c["flops"]).dims(c)
    need = mod.required(dims, launch(2048, 1000, 7))
    assert need["flops"] == 6.0 * 1000 * d * f
    assert need["bytes"] == 2.0 * (3 * 4 * 7 * d * f + 3 * 1000 * (d + f))


def test_declared_for_the_three_hybrid_cells():
    bench = load_json("BENCHMARK.json")
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert m["workloads"] == HYBRID
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == ("%", "higher", "device_trace", "expert kernel",
            "serve_tpot_p90_ms")
    assert bench["per_layer"][-1] is m
