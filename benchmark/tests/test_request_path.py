"""The three readers that ISSUE 35 added and ``request_path.py``, on the
tiny serving cell (CPU, one whole run of run.py in this process with
its context kept): each reads a number where the program records
``replica.call`` and says what ``engine.decode`` covers, and None on a
ring as a program from before left it; ``[request_path]`` joins every
request; ``BENCHMARK.json`` declares each metric once for the five
serving cells."""

import contextlib
import io
import json
import os

import pytest

from benchmark import program_spans, request_path
from benchmark.common import load_json

HERE = os.path.dirname(__file__)
SERVE = "serve.mistral7b-L16.chat-steady"
NEW = {"serve.replica_wait_ms_p90": "serve router",
       "serve.token_delivery_ms_p90": "serve router",
       "serve.engine_tpot_p90_ms": "engine"}


def reader(name):
    import importlib.util

    path = os.path.join(HERE, "..", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def tiny_run():
    """(the readers' context, stdout's lines, the result line) of one
    traced rehearsal of the dense serving cell."""
    from benchmark import run as run_py
    from benchmark.drivers import serve as driver

    kept, real, out = {}, driver.run, io.StringIO()

    def keep(cell, t_start):
        result = real(cell, t_start)
        kept.update(result["ctx"])
        return result

    driver.run = keep
    try:
        with contextlib.redirect_stdout(out):
            code = run_py.main([
                "--workload", SERVE, "--seed", str(2 ** 31 + 35),
                "--seconds", "3", "--trace", "1", "--rehearsal",
                os.path.join(HERE, "tiny_serve.json")])
    finally:
        driver.run = real
    assert code == 0
    lines = out.getvalue().strip().splitlines()
    return kept, lines, json.loads(lines[-1])


def test_each_reader_reads_a_number_on_the_change(tiny_run):
    ctx, lines, result = tiny_run
    for name in NEW:
        value = result["metrics"]["cpu_rehearsal." + name]
        assert value["unit"] == "ms" and value["value"] >= 0.0
    for tag in ("[replica_calls]", "[request_path]", "[tpot_sides]"):
        assert any(line.startswith(tag) for line in lines), tag
    assert "[engine_counters]" in "\n".join(lines)
    assert not any("agree=False" in line for line in lines)
    calls = request_path.window_calls(ctx)
    assert {r[5]["method"] for r in calls} == {"start_stream",
                                               "next_tokens"}
    assert request_path.most_waiting_at_once(calls) >= 1
    engine = request_path.engine_tpots(ctx)
    assert engine and all(t >= 0.0 for t, _ in engine.values())


def test_request_path_joins_every_request_and_adds_up(tiny_run):
    ctx, lines, _ = tiny_run
    rows, wanted = request_path.joined(ctx)
    assert wanted == len(rows) == sum(
        s.req["id"] >= 0 for s in ctx["served"]) > 0
    joined = next(line for line in lines
                  if line.startswith("[request_path] joined="))
    assert f"joined={wanted}/{wanted} " in joined
    for row in rows:
        s, cut = row["served"], request_path.pieces_ms(row)
        assert min(cut.values()) >= 0.0, cut
        assert sum(cut.values()) == pytest.approx(
            1e3 * (s.t_first - s.t_due), abs=1e-6)
        assert row["decode"][5]["tokens"] == len(s.tokens)
    # the engine's TPOT is its decode span over the client's own count
    engine = request_path.engine_tpots(ctx)
    for row in rows:
        s = row["served"]
        if len(s.tokens) > 1:
            assert engine[row["ident"]][0] == pytest.approx(
                1e3 * (row["decode"][2] - row["decode"][1])
                / (len(s.tokens) - 1))
            # a client's first frame holds the first hand-out at least
            assert s.frames[0][1] >= row["first"][5]["tokens"] >= 1


def as_before(records):
    """The ring as the parent's program leaves it: no ``replica.call``,
    and the request spans without fields."""
    return [r[:5] + ({},) if r[0] in (
        "engine.queue", "engine.first_token", "engine.decode") else r
        for r in records if r[0] != request_path.CALL]


def test_nothing_to_read_on_a_ring_without_the_new_spans(
        tiny_run, monkeypatch, capsys):
    ctx = tiny_run[0]
    real = program_spans.since
    monkeypatch.setattr(program_spans, "since",
                        lambda t: as_before(real(t)))
    for name in NEW:
        assert reader(name)(ctx) is None
    assert request_path.say_request_path(ctx) is None
    assert request_path.joined(ctx)[0] == []
    request_path.say_tpot_sides(ctx)     # the clients' side alone
    request_path.say_loop_gap(ctx)
    out = capsys.readouterr().out
    assert "[replica_calls]" not in out and "[request_path]" not in out
    assert "engine_" not in out
    # and with no ring at all
    monkeypatch.setattr(program_spans, "since", lambda t: None)
    for name in NEW:
        assert reader(name)(ctx) is None


def test_the_benchmark_declares_each_new_metric_once():
    bench = load_json("BENCHMARK.json")
    serving = [w["name"] for w in bench["workloads"]
               if w["name"].startswith("serve.")]
    assert len(serving) == 5
    for name, layer in NEW.items():
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry == {"name": name, "unit": "ms", "better": "lower",
                         "source": "program_span", "layer": layer,
                         "moves": "serve_tpot_p90_ms",
                         "workloads": serving}
        assert os.path.exists(os.path.join(HERE, "..", "layer_metrics",
                                           name + ".py"))
    assert [m["name"] for m in bench["per_layer"]][-3:] == list(NEW)
