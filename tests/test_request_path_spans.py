"""A request's way in and a token's way out, on the ring: what a serve
replica records of every call it runs (``replica.call``) beside the
engine's three spans of the request the call served, through
``serve.run(build_llm_app(...))`` and a handle.

No wall-clock assertion: spans are compared with each other and with
the caller's own clock reads around them (all ``time.perf_counter`` in
one process), by order and sign only.
"""

import os
import sys
import threading
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu import serve  # noqa: E402
from ray_tpu._private import spans  # noqa: E402
from ray_tpu.models.inference import (InferenceConfig,  # noqa: E402
                                      InferenceEngine, TokenStream,
                                      _Request)
from ray_tpu.models.transformer import (Transformer,  # noqa: E402
                                        TransformerConfig)
from ray_tpu.serve import core  # noqa: E402
from ray_tpu.serve.llm import LLMDeployment, build_llm_app  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:           # the benchmark's readers, for (c)
    sys.path.insert(0, ROOT)

REPLICA_THREADS = 8      # _DeploymentState._spawn: max_concurrency=8
# one slot: a stream opened behind a long one stays queued in the
# engine, so its poll blocks for as long as the test needs it to
ICFG = InferenceConfig(batch_size=1, page_size=4, max_pages_per_seq=40,
                       num_pages=48, prefill_buckets=(8,), decode_chunk=4)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=64,
                            max_seq_len=256, dtype=jnp.float32)
    variables = Transformer(cfg).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32))
    return cfg, variables["params"]


@pytest.fixture(scope="module")
def handle(tiny_model):
    cfg, params = tiny_model
    ray_tpu.shutdown()
    ray_tpu.init(num_workers=4)
    try:
        yield serve.run(build_llm_app(params, cfg, ICFG))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def get(ref):
    return ray_tpu.get(ref, timeout=120.0)


def drain(handle, sid):
    """Poll the stream to its end: [(tokens, clock before the poll was
    sent, clock after its frame was back)] a poll."""
    polls = []
    while True:
        t_sent = time.perf_counter()
        frame = get(handle.next_tokens.remote(sid, 60.0))
        polls.append((frame["tokens"], t_sent, time.perf_counter()))
        if frame["done"]:
            return polls


def calls(records, method=None, **fields):
    return [r for r in records if r[0] == "replica.call"
            and method in (None, r[5]["method"])
            and all(r[5].get(k) == v for k, v in fields.items())]


def test_one_request_leaves_one_chain_under_one_ident(handle):
    t0 = time.perf_counter()
    sid = get(handle.start_stream.remote([1, 2, 3], 40))
    polls = drain(handle, sid)
    # engine.decode is recorded on the loop's thread before the last
    # poll can see the stream's end, the polls' own spans as they return
    records = spans.since(t0)
    (start,) = calls(records, "start_stream", stream=sid)
    ident = start[3]
    assert ident is not None
    assert start[5]["prompt_tokens"] == 3 and start[5]["max_new"] == 40
    mine = [r for r in records if r[3] == ident]
    by_name = {}
    for r in mine:
        by_name.setdefault(r[0], []).append(r)
    assert sorted(by_name) == ["engine.decode", "engine.first_token",
                               "engine.queue", "replica.call"]
    (queue,), (first,), (decode,) = (by_name["engine." + n] for n in (
        "queue", "first_token", "decode"))
    polled = calls(mine, "next_tokens")
    assert len(polled) == len(polls)
    assert len(by_name["replica.call"]) == 1 + len(polls)
    # the request was submitted inside the start_stream call, and the
    # engine's spans follow each other without a hole
    assert start[1] <= queue[1] <= start[2]
    assert queue[2] == first[1] and first[2] == decode[1] <= decode[2]
    assert queue[5] == {"prompt_tokens": 3, "max_new": 40}
    # every token once: the polls' counts are the engine's
    assert [r[5]["tokens"] for r in polled] == [len(p[0]) for p in polls]
    assert sum(r[5]["tokens"] for r in polled) == decode[5]["tokens"] == 40
    gave = [r for r in polled if r[5]["tokens"]]
    assert 1 <= len(gave) <= decode[5]["handouts"]
    assert gave[0][5]["tokens"] >= first[5]["tokens"] >= 1
    assert [r[5]["done"] for r in polled] == [False] * (len(polls) - 1) + [
        True]
    # a token is handed out before a poll returns it, the last one too
    assert first[2] <= gave[0][2] and decode[2] <= polled[-1][2]
    for r in polled:
        assert r[5]["blocked_ms"] >= 0.0
        if r[5]["tokens"]:
            assert 0.0 <= r[5]["held_ms"] <= 1e3 * (r[2] - first[2])
        else:
            assert r[5]["held_ms"] is None
    # one trace-plane context a call; the engine's spans carry the
    # start_stream call's
    assert queue[4] == first[4] == decode[4] == start[4]


def test_a_start_behind_blocked_polls_waits_for_a_replica_thread(handle):
    """One slot, so streams opened behind a long one stay queued and
    their polls block: eight of them hold the replica's eight threads,
    and a ninth call waits in the actor's inbox until one returns."""
    t0 = time.perf_counter()
    lone_sid = get(handle.start_stream.remote([1, 2], 120))
    queued = [get(handle.start_stream.remote([3 + i], 2))
              for i in range(REPLICA_THREADS)]
    poll_refs = [handle.next_tokens.remote(sid, 120.0) for sid in queued]
    late_sid = get(handle.start_stream.remote([5, 6], 2))
    frames = [get(ref) for ref in poll_refs]
    for sid, frame in zip(queued, frames):
        if not frame["done"]:
            drain(handle, sid)
    drain(handle, lone_sid)
    drain(handle, late_sid)
    records = spans.since(t0)
    (lone,) = calls(records, "start_stream", stream=lone_sid)
    (late,) = calls(records, "start_stream", stream=late_sid)
    routed = late[5]["t_routed"]
    # handed over before it, so ahead of it in the replica's inbox, and
    # not back yet
    holding = [r for r in calls(records, "next_tokens")
               if r[5]["t_routed"] <= routed <= r[2]]
    assert len(holding) == REPLICA_THREADS
    # it got its thread only once a poll had given one up ...
    freed = min(r[2] for r in holding)
    assert late[1] >= freed > routed
    assert late[5]["waited_ms"] >= 1e3 * (freed - routed)
    # ... and that poll had been blocked on the engine all the while
    first_back = min(holding, key=lambda r: r[2])
    assert first_back[5]["blocked_ms"] > 0.0
    assert first_back[1] + 1e-3 * first_back[5]["blocked_ms"] >= routed
    # the lone request met free threads: it waited less than the late
    # one, and less than the late one's wait on the poll alone
    assert 0.0 <= lone[5]["waited_ms"] < late[5]["waited_ms"]
    assert lone[5]["waited_ms"] < 1e3 * (freed - routed)


def test_the_pieces_of_a_request_path_add_up(handle):
    """The benchmark's own clients and its cut of their TTFT
    (``benchmark/request_path.py``): the pieces add up to first token
    minus due, and what is left to the caller's side after the
    program's last mark is not negative and less than the caller's own
    round trip of the poll that brought the first token."""
    from benchmark import request_path
    from benchmark.drivers.serve import offer
    from benchmark.spans import Recorder

    rec = Recorder()
    requests = [{"id": i, "due_s": 0.02 * i, "prompt": [1 + i] * (1 + i),
                 "max_new": n} for i, n in enumerate((9, 3, 1, 17, 6))]
    t_open = time.perf_counter()
    served = offer(handle, rec, requests, t_open, t_open + 120.0, 60.0)
    assert all(s.ok for s in served)
    ctx = {"window": (t_open, time.perf_counter()), "recorder": rec,
           "served": served}
    rows, wanted = request_path.joined(ctx)
    assert wanted == len(rows) == len(requests)
    assert len({row["ident"] for row in rows}) == len(requests)
    client_polls = sorted((t0, t1) for n, t0, t1 in rec.spans
                          if n == "serve.next_tokens")
    for row in rows:
        s, cut = row["served"], request_path.pieces_ms(row)
        assert list(cut) == list(request_path.PIECES)
        assert all(v >= 0.0 for v in cut.values()), cut
        assert sum(cut.values()) == pytest.approx(
            1e3 * (s.t_first - s.t_due), abs=1e-6)
        program = sum(cut[p] for p in request_path.PIECES[1:-1])
        left = 1e3 * (s.t_first - s.t_sent) - program
        assert left == pytest.approx(cut["return_path"], abs=1e-6)
        # the caller's poll that brought the first token: the last one
        # it had sent by then
        sent = max(t0 for t0, _ in client_polls if t0 <= s.t_first)
        assert 0.0 <= left < 1e3 * (s.t_first - sent)
        assert row["decode"][5]["tokens"] == s.req["max_new"]
    out = request_path.say_request_path(ctx)
    assert out["joined"] == out["of"] == len(requests)
    assert out["return_path_min_ms"] >= 0.0


def test_an_empty_poll_holds_nothing(handle):
    t0 = time.perf_counter()
    long_sid = get(handle.start_stream.remote([1], 60))
    sid = get(handle.start_stream.remote([2], 2))     # queued behind it
    frame = get(handle.next_tokens.remote(sid, 0.001))
    drain(handle, long_sid)
    drain(handle, sid)
    records = spans.since(t0)
    (start,) = calls(records, "start_stream", stream=sid)
    empty, *rest = [r for r in calls(records, "next_tokens")
                    if r[3] == start[3]]
    if not frame["tokens"]:        # the long stream was still running
        assert empty[5]["tokens"] == 0 and empty[5]["held_ms"] is None
        assert empty[5]["blocked_ms"] > 0.0 and not empty[5]["done"]
    assert all(r[5]["held_ms"] >= 0.0 for r in rest if r[5]["tokens"])


def test_a_handout_keeps_its_own_stamp_under_a_concurrent_reader(
        tiny_model):
    """``_hand_out`` on one thread, ``next_tokens`` on another, the
    interpreter switching as often as it can: the age a poll reports is
    that of the OLDEST token it returns, i.e. its stamp lies between the
    producer's clock reads around the hand-out that gave that token,
    never a later hand-out's."""
    cfg, params = tiny_model
    dep = LLMDeployment._cls(params, cfg, ICFG)
    engine: InferenceEngine = dep._engine
    req = _Request([1], 10 ** 6, ident=7)
    req.stream = TokenStream(req.future, req.ident)
    sid = dep._register_stream(req.stream, 1, 10 ** 6)
    n = 2000
    around = []                    # token i was handed out inside these

    def produce():
        for i in range(n):
            req.out.append(i)
            if i == n - 1:
                req.future.set_result(req.out)
            lo = time.perf_counter()
            engine._hand_out(req)
            around.append((lo, time.perf_counter()))

    seen, ages = [], []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    producer = threading.Thread(target=produce, daemon=True)
    try:
        producer.start()
        done = False
        while not done:
            fields = {}
            token = core._current_call_fields.set(fields)
            try:
                before = time.perf_counter()
                frame = dep.next_tokens(sid, 30.0)
                after = time.perf_counter()
            finally:
                core._current_call_fields.reset(token)
            done = frame["done"]
            assert fields["tokens"] == len(frame["tokens"])
            if frame["tokens"]:
                ages.append((frame["tokens"][0], fields["held_ms"],
                             before, after))
            else:
                assert fields["held_ms"] is None
            seen.extend(frame["tokens"])
        producer.join(30.0)
    finally:
        sys.setswitchinterval(interval)
        dep.shutdown()
    assert not producer.is_alive()
    assert seen == list(range(n)) and req.handouts == n
    assert len(ages) > 1
    for oldest, held_ms, before, after in ages:
        lo, hi = around[oldest]
        assert held_ms >= 0.0
        # stamp = (the poll's own clock read, between before and after)
        # - held: it can be the oldest token's hand-out, in [lo, hi]
        assert before - 1e-3 * held_ms <= hi
        assert after - 1e-3 * held_ms >= lo
    assert req.got_first


@serve.deployment(name="plain_adder")
class PlainAdder:
    """Names nothing of its calls."""

    def __call__(self, x):
        return x + 1

    def named(self, x):
        serve.get_call_span_fields().update(ident=("job", x), rows=x)
        return x


def test_the_span_is_the_replicas_not_the_llms(handle):
    h = serve.run(PlainAdder.bind())      # beside the module's llm app
    t0 = time.perf_counter()
    assert get(h.remote(1)) == 2
    assert get(h.named.remote(5)) == 5
    with pytest.raises(Exception):
        get(h.missing.remote())
    actor = h._state()._replicas[0].actor
    elsewhere = (os.getpid() + 1, time.perf_counter())
    assert get(actor.handle_request.remote(
        "__call__", (2,), {}, None, elsewhere)) == 3
    assert get(actor.handle_request.remote("__call__", (3,), {})) == 4
    records = calls(spans.since(t0))
    plain, named, missing, foreign, unstamped = records
    assert plain[3] is None and set(plain[5]) == {
        "method", "t_routed", "waited_ms"}
    assert plain[5]["method"] == "__call__"
    assert plain[5]["waited_ms"] == pytest.approx(
        1e3 * (plain[1] - plain[5]["t_routed"]))
    assert t0 <= plain[5]["t_routed"] <= plain[1] <= plain[2]
    assert named[3] == ("job", 5) and named[5]["rows"] == 5
    assert named[5]["method"] == "named" and "waited_ms" in named[5]
    # a call that raised leaves its span too
    assert missing[5]["method"] == "missing" and "waited_ms" in missing[5]
    # another process's clock is not read: nothing is guessed
    for r in (foreign, unstamped):
        assert r[5] == {"method": "__call__"}
    # outside a replica call the accessor hands out a dict nobody reads
    scratch = serve.get_call_span_fields()
    scratch["x"] = 1
    assert serve.get_call_span_fields() == {}


def test_the_ring_has_no_span_per_token_of_a_stream(handle):
    """A stream of 8 and one of 100 tokens: a ``replica.call`` a poll,
    and a poll takes a whole hand-out (a burst's tokens) at the least,
    so their number follows the bursts, not the tokens."""
    counts = {}
    for max_new in (8, 100):
        t0 = time.perf_counter()
        sid = get(handle.start_stream.remote([1, 2], max_new))
        polls = drain(handle, sid)
        records = spans.since(t0)
        (decode,) = [r for r in records if r[0] == "engine.decode"]
        assert decode[5]["tokens"] == max_new
        assert len(calls(records)) == 1 + len(polls)
        # each poll but a last, empty one returned a hand-out or more
        assert len(polls) <= decode[5]["handouts"] + 1
        counts[max_new] = decode[5]["handouts"]
    per_burst = 4 * ICFG.decode_chunk
    assert counts[100] <= -(-100 // per_burst) + 1 < 100 // 4


def test_fields_stay_out_of_the_way_of_the_spans_own():
    """``record`` takes any field name: a span's own five are not
    keywords that a deployment's fields could collide with."""
    t0 = time.perf_counter()
    spans.record("replica.call", t0, t0, ident=3, name="x", t0=1.0, t1=2.0)
    (got,) = [r for r in spans.since(t0) if r[0] == "replica.call"
              and r[3] == 3]
    assert got[5] == {"name": "x", "t0": 1.0, "t1": 2.0}
