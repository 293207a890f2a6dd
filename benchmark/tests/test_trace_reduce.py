"""trace_reduce.py on a small recorded trace (benchmark/tests/data/):
busy union, idle share, collective overlap and gap labelling give the
values worked out by hand; shares over 100 % raise."""

import json
import os

import numpy as np
import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_train_step_slice.json")


@pytest.fixture(scope="module")
def trace():
    with open(DATA) as f:
        return json.load(f)


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (1, 3), (2, 4), (7, 7)]) == [(1, 4), (5, 7)]
    assert tr.clip([(0, 10), (20, 30)], (5, 25)) == [(5, 10), (20, 25)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.total([(0, 2), (3, 5)]) == 4


def test_names_and_planes(trace):
    assert [p["name"] for p in tr.device_planes(trace)] == [
        "/device:TPU:0", "/device:TPU:1"]
    assert tr.short_name("%fusion.44 = (f32[8]{0}) fusion(...)") == \
        "fusion.44"
    assert tr.traced_window(trace) == (0, 20000)
    spans = tr.host_spans(trace)
    assert ("train.report", 7500, 8500) in spans
    assert all(not n.startswith("not_ours") for n, _, _ in spans)


def test_hand_made_device(trace):
    """Device 1, by hand: operations cover [1000,7000] and
    [9000,15000] of a 20000 ns window."""
    plane = tr.device_planes(trace)[1]
    window = tr.traced_window(trace)
    assert tr.busy_seconds(plane, window) == pytest.approx(12000e-9)
    assert tr.idle_gaps(plane, window) == [(0, 1000), (7000, 9000),
                                           (15000, 20000)]
    # collectives [3000,7000] + [9000,11000]; other work leaves
    # [5000,6000] and [9000,10000] of them uncovered
    assert tr.collective_exposed_seconds(plane, window) == \
        pytest.approx(2000e-9)
    labels = tr.label_gaps(tr.idle_gaps(plane, window),
                           tr.host_spans(trace))
    assert labels == [("(no benchmark span open)", pytest.approx(5e-6)),
                      ("train.report", pytest.approx(2e-6)),
                      ("train.step", pytest.approx(1e-6))]


def test_recorded_device_against_brute_force(trace):
    """Device 0 is a recording: mark every nanosecond an operation
    covers and count."""
    plane = tr.device_planes(trace)[0]
    end = trace["recorded_end_ns"]
    window = (0, end)
    covered = np.zeros(end, bool)
    by_name = {}
    for name, start, dur in tr.line_events(plane, tr.OPS_LINE):
        covered[start:start + dur] = True
        by_name[name] = by_name.get(name, 0) + dur
    assert tr.busy_seconds(plane, window) == pytest.approx(
        covered.sum() / 1e9, rel=1e-12)
    got = tr.op_seconds(plane, window)
    assert got == pytest.approx({k: v / 1e9 for k, v in by_name.items()})
    assert max(got, key=got.get).startswith("fusion")
    runs = tr.module_runs(plane, window)
    assert [r[0] for r in runs] == ["jit_train_step"]
    # clipped to a window that ends mid-operation
    half = (0, end // 2)
    assert tr.busy_seconds(plane, half) == pytest.approx(
        covered[:end // 2].sum() / 1e9, rel=1e-12)
    assert tr.module_runs(plane, half) == []     # not wholly inside


def test_summarize(trace):
    s = tr.summarize(trace, n_devices=2)
    assert s["window_s"] == pytest.approx(20e-6)
    assert s["busy_s_min"] == pytest.approx(12e-6)      # device 1
    # device 0's first recorded operation outlasts the 20 us window
    assert s["busy_s"] == pytest.approx((20e-6 + 12e-6) / 2)
    assert s["idle_gaps"][0] == ["(no benchmark span open)",
                                 pytest.approx(5e-6)]
    assert [n for n, _ in s["device_ops"]][:2] == ["fusion.3", "fusion.1"]
    one = tr.summarize(trace, n_devices=1)
    assert one["busy_s_min"] == one["busy_s"]


def test_share_over_one_raises():
    assert tr.share(1.0, 2.0, "x") == 0.5
    with pytest.raises(tr.ShareOverOne):
        tr.share(2.1, 2.0, "busy share")
    with pytest.raises(ValueError):
        tr.share(1.0, 0.0, "nothing")


def test_no_window_or_device_is_an_error(trace):
    no_span = {"planes": [p for p in trace["planes"]
                          if p["name"] != "/host:CPU"]}
    with pytest.raises(ValueError):
        tr.traced_window(no_span)
    no_dev = {"planes": [p for p in trace["planes"]
                         if p["name"] == "/host:CPU"]}
    with pytest.raises(ValueError):
        tr.summarize(no_dev)
