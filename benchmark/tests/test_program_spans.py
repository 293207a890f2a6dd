"""program_spans.py and the eight readers that ISSUE 25 added, on a
hand-made context (arithmetic worked out here, None where there is
nothing to read) and on a slice recorded on the chip
(benchmark/tests/data/v5e_serve_slice.json: the engine's named programs
and its ring, side by side)."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import flops, program_spans as ps
from benchmark.common import load_json

HERE = os.path.dirname(__file__)
SERVE = "serve.mistral7b-L16.chat-steady"
NEW = ["serve.prefill_pad_share", "serve.decode_useful_share",
       "serve.queue_wait_ms_p90", "serve.first_token_hold_ms_p50",
       "serve.engine_host_ms_per_burst_p50", "serve.prefill_device_share",
       "serve.decode_roofline", "serve.idle_under_engine_host_share"]


def reader(name):
    path = os.path.join(HERE, "..", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell():
    return types.SimpleNamespace(
        config=load_json("benchmark", "configs",
                         "mistral-7b-v0.3-serve-L16.json"),
        peaks=load_json("benchmark", "peaks.json")["TPU v5 lite"], chips=1)


# perf_counter 100.0 is trace time 5e9 ns; the traced window is 2 s long
T0, W0 = 100.0, 5e9


def ns(t):
    return W0 + (t - T0) * 1e9


def burst(t, admit, dispatch, fetch, deliver, **fields):
    """The four spans of one round that begins at perf_counter t."""
    a, d, f = t + admit, t + admit + dispatch, t + admit + dispatch + fetch
    return [("engine.admit", t, a, None, None, {}),
            ("engine.dispatch", a, d, None, None,
             {k: fields[k] for k in ("live_slots", "live_ctx_tokens",
                                     "steps", "chunks")}),
            ("engine.fetch", d, f, None, None, {}),
            ("engine.deliver", f, f + deliver, None, None,
             {"kept_tokens": fields["kept_tokens"]})]


def request(ident, submit, admit, first, done):
    return [("engine.queue", submit, admit, ident, None, {}),
            ("engine.first_token", admit, first, ident, None, {}),
            ("engine.decode", first, done, ident, None, {})]


def hand_made(monkeypatch):
    """Two bursts inside the traced window [100, 102) s.

    Burst 1 at 100.100: admit 4 ms (one launch of bucket 128, one
    prompt of 100 tokens in 32 rows), dispatch 2 ms, fetch 685 ms,
    deliver 1 ms; 32 + 8 steps with 10 slots live holding 4,000 tokens;
    360 tokens kept. Burst 2 follows at 100.792: admit 1 ms, dispatch
    2 ms, fetch 200 ms, deliver 3 ms (until 100.998); 16 steps, 12
    slots, 5,000 tokens; 150 kept. On the device: the prefill runs
    100.105-100.285, burst 1's chunks 100.285-100.685 and
    100.685-100.785 (12.5 ms a step), burst 2's chunk
    100.7955-100.992."""
    records = (burst(100.100, .004, .002, .685, .001, live_slots=10,
                     live_ctx_tokens=4000, steps=40, chunks=2,
                     kept_tokens=360)
               + [("engine.prefill_launch", 100.101, 100.103, None, None,
                   {"bucket": 128, "rows": 32, "useful_rows": 1,
                    "prompt_tokens": 100})]
               + burst(100.792, .001, .002, .200, .003, live_slots=12,
                       live_ctx_tokens=5000, steps=16, chunks=1,
                       kept_tokens=150)
               + request(7, 100.050, 100.102, 100.7915, 100.900)
               + request(8, 100.300, 100.7925, 100.9955, 101.500)
               + request(3, 99.000, 99.400, 99.900, 100.7915))
    records.sort(key=lambda r: r[2])
    monkeypatch.setattr(ps, "since",
                        lambda t: [r for r in records if r[2] >= t])
    modules = [["jit_engine_prefill_b128(11)", ns(100.105), .180e9],
               ["jit_engine_decode_n32(12)", ns(100.285), .400e9],
               ["jit_engine_decode_n8(13)", ns(100.685), .100e9],
               ["jit_engine_decode_n16(15)", ns(100.7955), .1965e9]]
    ops = [["fusion.1", s, d] for _, s, d in modules]
    plane = {"name": "/device:TPU:0",
             "lines": [{"name": "XLA Modules", "events": modules},
                       {"name": "XLA Ops", "events": ops}]}
    summary = {"window": (ns(100.0), ns(102.0)), "window_s": 2.0,
               "t0": 100.0, "t1": 102.0, "planes": [plane]}
    return {"cell": cell(), "trace_summary": summary, "flops": flops,
            "engine": {"batch_size": 32}, "window": (100.0, 151.0),
            "engine_stats": {"bursts": 2, "decode_steps": 56,
                             "decode_tokens_kept": 510,
                             "prefill_launches": 1,
                             "prefill_useful_rows": 1,
                             "prefill_positions": 4096,
                             "prefill_prompt_tokens": 100}}


def test_counter_and_span_readers_on_a_hand_made_ring(monkeypatch, capsys):
    ctx = hand_made(monkeypatch)
    assert reader("serve.prefill_pad_share")(ctx) == pytest.approx(
        100 * (1 - 100 / 4096))
    assert reader("serve.decode_useful_share")(ctx) == pytest.approx(
        100 * 510 / (32 * 56))
    out = capsys.readouterr().out
    assert out.count("agree=True") == 2 and "agree=False" not in out
    # requests 7 and 8 were submitted in the window, request 3 before it
    assert reader("serve.queue_wait_ms_p90")(ctx) == pytest.approx(492.5)
    assert reader("serve.first_token_hold_ms_p50")(ctx) == pytest.approx(
        (689.5 + 203.0) / 2)
    assert reader("serve.engine_host_ms_per_burst_p50")(ctx) == \
        pytest.approx((7 + 6) / 2)


def test_counters_that_disagree_with_the_ring_are_said(monkeypatch, capsys):
    ctx = hand_made(monkeypatch)
    ctx["engine_stats"]["decode_steps"] = 57
    reader("serve.decode_useful_share")(ctx)
    out = capsys.readouterr().out
    assert "decode_steps=56/57" in out and "agree=False" in out


def test_device_readers_on_a_hand_made_trace(monkeypatch, capsys):
    ctx = hand_made(monkeypatch)
    assert reader("serve.prefill_device_share")(ctx) == pytest.approx(
        100 * 0.180 / 2.0)
    got = reader("serve.decode_roofline")(ctx)
    peaks = ctx["cell"].peaks
    weights = 2.0 * flops.param_count(ctx["cell"].config)
    kv = 2 * 16 * 8 * 128 * 2
    least = (40 * (weights + kv * 4000) + 16 * (weights + kv * 5000)) \
        / peaks["hbm_bytes_per_s"]
    assert got == pytest.approx(100 * least / (0.400 + 0.100 + 0.1965))
    assert 0 < got <= 100
    out = capsys.readouterr().out
    assert "bound=['memory']" in out
    # every decode run lies inside its burst's dispatch..fetch; the
    # least room: burst 2 began dispatching at 100.793 and its chunk
    # ran at 100.7955, its fetch returned at 100.995 and the chunk had
    # ended at 100.992
    line = next(l for l in out.splitlines() if l.startswith("[clock_map]"))
    fields = dict(f.split("=") for f in line.split()[1:])
    assert float(fields["worst_ms"]) == 0
    assert float(fields["least_room_before_ms"]) == pytest.approx(2.5)
    assert float(fields["least_room_after_ms"]) == pytest.approx(3.0)
    assert fields["bursts_whose_runs_agree"] == fields["bursts"] == "2"
    share = reader("serve.idle_under_engine_host_share")(ctx)
    out = capsys.readouterr().out
    assert "[clock_map]" not in out            # checked once a run
    # idle: 100.000-100.105 (admit 4 ms and 1 ms of dispatch at its
    # end), 100.785-100.7955 (6 ms of fetch, deliver 1, admit 1,
    # dispatch 2, fetch 0.5) and 100.992-102.000 (3 ms of fetch,
    # deliver 3, then no span)
    table = next(l for l in out.splitlines()
                 if l.startswith("[idle_by_program_span]"))
    fields = dict(f.split("=") for f in table.split()[1:])
    assert float(fields["engine.fetch"]) == pytest.approx(0.0095)
    assert float(fields["engine.admit"]) == pytest.approx(0.005)
    assert float(fields["engine.dispatch"]) == pytest.approx(0.003)
    assert float(fields["engine.deliver"]) == pytest.approx(0.004)
    assert float(fields["(no_engine_span_open)"]) == pytest.approx(1.102)
    assert share == pytest.approx(100 * 0.012 / 1.1235)


def test_a_clock_map_that_is_off_raises(monkeypatch):
    ctx = hand_made(monkeypatch)
    ctx["trace_summary"]["t0"] += 0.005       # the map now runs 5 ms early
    ctx["trace_summary"]["t1"] += 0.005
    with pytest.raises(ValueError, match="clock map"):
        ps.traced_bursts(ctx)


def test_a_roofline_share_over_100_raises(monkeypatch):
    from benchmark import trace_reduce

    ctx = hand_made(monkeypatch)
    for line in ctx["trace_summary"]["planes"][0]["lines"]:
        for e in line["events"]:
            if "decode" in e[0]:
                e[2] /= 100.0            # runs a hundred times too short
    with pytest.raises(trace_reduce.ShareOverOne):
        reader("serve.decode_roofline")(ctx)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none(monkeypatch, name):
    """No ring (a program from before it), an empty ring, no trace."""
    ctx = hand_made(monkeypatch)
    bare = dict(ctx, trace_summary=None)
    device = name in NEW[5:]
    if device:
        assert reader(name)(bare) is None
    for empty in (None, []):
        ctx.pop("_traced_bursts", None)
        monkeypatch.setattr(ps, "since", lambda t, e=empty: e)
        if name == "serve.prefill_device_share":
            continue                    # reads the trace alone
        assert reader(name)(ctx) is None
        assert reader(name)(bare) is None
    # old program names in the trace: no prefill or decode run is found
    for e in ctx["trace_summary"]["planes"][0]["lines"][0]["events"]:
        e[0] = e[0].replace("jit_engine_prefill_b128", "jit__unknown") \
            .replace("jit_engine_decode_n", "jit__lambda_")
    if device:
        assert reader(name)(ctx) is None


def test_every_new_metric_is_declared_for_the_serving_cell():
    bench = load_json("BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        m = declared[name]
        assert m["workloads"] == [SERVE]
        assert m["moves"] == "serve_tpot_p90_ms"
        assert os.path.exists(os.path.join(HERE, "..", "layer_metrics",
                                           name + ".py"))


def test_traced_rehearsal_reports_the_counter_and_span_metrics():
    """run.py end to end on the CPU: the five metrics that need no
    device trace are read from the tiny engine's own ring and counters
    (and agree with each other); the three that need one are left
    out."""
    from test_rehearsal import last_line, run_py

    proc = run_py(["--workload", SERVE, "--seed", str(2**31 + 25),
                   "--seconds", "3", "--trace", "1", "--rehearsal",
                   os.path.join("benchmark", "tests", "tiny_serve.json")],
                  devices=1)
    got = last_line(proc)["metrics"]
    for name in NEW[:5]:
        assert got["cpu_rehearsal." + name]["value"] >= 0, name
    for name in NEW[:2]:
        assert got["cpu_rehearsal." + name]["value"] <= 100
    for name in NEW[5:]:
        assert "cpu_rehearsal." + name not in got
    counters = [l for l in proc.stdout.splitlines()
                if l.startswith("[engine_counters]")]
    assert len(counters) == 2 and all("agree=True" in l for l in counters)


# ----------------------------------------------------------------------
# the recorded slice
# ----------------------------------------------------------------------

@pytest.fixture()
def recorded(monkeypatch):
    with open(os.path.join(HERE, "data", "v5e_serve_slice.json")) as f:
        rec = json.load(f)
    ring = [tuple(r) for r in rec["ring"]]
    monkeypatch.setattr(ps, "since",
                        lambda t: [r for r in ring if r[2] >= t])

    def ctx(points):
        window = tuple(rec[points]["window"])
        return {"cell": cell(), "flops": flops,
                "engine": {"batch_size": 32},
                "trace_summary": {
                    "window": window,
                    "window_s": (window[1] - window[0]) / 1e9,
                    "t0": rec[points]["t0"], "t1": rec[points]["t1"],
                    "planes": rec["planes"]}}
    return rec, ctx


def test_recorded_programs_carry_their_names(recorded):
    from benchmark import trace_reduce

    rec, ctx = recorded
    runs = trace_reduce.module_runs(rec["planes"][0],
                                    tuple(rec["slice"]["window"]))
    names = [n for n, _, _ in runs]
    assert [n for n in names if ps.PREFILL_RUN.match(n)] == [
        "jit_engine_prefill_b128", "jit_engine_prefill_b256",
        "jit_engine_prefill_b64"]
    assert [n for n in names if ps.DECODE_RUN.match(n)] == [
        "jit_engine_decode_n8", "jit_engine_decode_n16"]
    assert not any("unknown" in n or "lambda" in n for n in names)
    assert "jit_engine_split_packed" in names


def test_two_point_clock_map_on_the_recorded_slice(recorded, capsys):
    """Mapped through the whole run's two points, the two bursts of the
    slice enclose the decode runs that the device recorded, with the
    chunk and step counts the ring holds."""
    rec, ctx = recorded
    bursts = ps.traced_bursts(ctx("run"))
    assert [(b["fields"]["steps"], b["fields"]["chunks"],
             [r[0] for r in b["runs"]]) for b in bursts] == [
        (8, 1, [8]), (16, 1, [16])]
    line = next(l for l in capsys.readouterr().out.splitlines()
                if l.startswith("[clock_map]"))
    fields = dict(f.split("=") for f in line.split()[1:])
    assert float(fields["worst_ms"]) == 0
    # the device began the second burst's chunk 85 ms after the host
    # began dispatching it (the round's 64-token prefill launch ran
    # first) and ended the chunks 2.9-3.0 ms before the fetch returned
    assert 80 < float(fields["least_room_before_ms"]) < 90
    assert 2.5 < float(fields["least_room_after_ms"]) < 3.5
    for b in bursts:
        (run,) = b["runs"]
        assert b["spans"]["engine.dispatch"][0] < run[1]
        assert run[2] < b["spans"]["engine.fetch"][1]
    # a map that is 5 ms off puts a run outside its burst
    off = ctx("run")
    off["trace_summary"]["t0"] += 0.005
    off["trace_summary"]["t1"] += 0.005
    with pytest.raises(ValueError, match="clock map"):
        ps.traced_bursts(off)


def test_device_readers_on_the_recorded_slice(recorded, capsys):
    from benchmark import trace_reduce

    rec, ctx = recorded
    c = ctx("slice")
    plane, window = rec["planes"][0], tuple(rec["slice"]["window"])
    prefill = sum(e - s for n, s, e in trace_reduce.module_runs(
        plane, window) if ps.PREFILL_RUN.match(n))
    assert reader("serve.prefill_device_share")(c) == pytest.approx(
        100 * prefill / (window[1] - window[0]))
    assert 52 < reader("serve.prefill_device_share")(c) < 54
    # 8 steps over 3,296 and 16 steps over 3,194 live tokens
    share = reader("serve.decode_roofline")(c)
    peaks = c["cell"].peaks
    weights = 2.0 * flops.param_count(c["cell"].config)
    least = (8 * (weights + 65536 * 3296) + 16 * (weights + 65536 * 3194)) \
        / peaks["hbm_bytes_per_s"]
    device = sum(r[2] - r[1] for b in ps.traced_bursts(c)
                 for r in b["runs"]) / 1e9
    assert share == pytest.approx(100 * least / device)
    assert 35 < share < 45                  # 22-23 ms a step against 9.1
    # idle seconds by span add up to the device's idle time in the slice
    by_span = ps.idle_by_span(c)
    idle = trace_reduce.total(trace_reduce.idle_gaps(plane, window)) / 1e9
    assert sum(by_span.values()) == pytest.approx(idle)
    assert set(by_span) <= set(ps.BURST) | {ps.NO_SPAN}
    # the device waits while the loop delivers, admits and dispatches
    # (the gap between a burst's last run and the next round's first)
    # and while the fetched tokens travel (under engine.fetch)
    assert by_span["engine.admit"] > by_span["engine.deliver"] > 0
    assert by_span["engine.fetch"] > 0.004
    got = reader("serve.idle_under_engine_host_share")(c)
    assert got == pytest.approx(100 * sum(
        by_span.get(n, 0.0) for n in ps.HOST_SIDE) / idle)
    assert "[idle_by_program_span]" in capsys.readouterr().out
