"""What the inference engine records of itself: cumulative counters in
``stats()``, burst and request spans in ``_private/spans.py``'s ring,
a name for every jitted program and scopes inside the forward.

No wall-clock assertion: spans are compared with each other only. A
test reads the ring after ``engine.shutdown()``, which joins the loop
thread, so every span of the run is in it.
"""

import collections
import contextlib
import threading
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu._private import spans, trace_plane  # noqa: E402
from ray_tpu.models import inference  # noqa: E402
from ray_tpu.models import train_step as ts  # noqa: E402
from ray_tpu.models.inference import (InferenceConfig,  # noqa: E402
                                      InferenceEngine, decode_step,
                                      prefill_batch)
from ray_tpu.models.transformer import (Transformer,  # noqa: E402
                                        TransformerConfig)

BURST = ("engine.admit", "engine.dispatch", "engine.fetch",
         "engine.deliver")
REQUEST = ("engine.queue", "engine.first_token", "engine.decode")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=64,
                            max_seq_len=256, dtype=jnp.float32)
    model = Transformer(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    return cfg, model, variables["params"]


ICFG = InferenceConfig(batch_size=3, page_size=4, max_pages_per_seq=16,
                       num_pages=64, prefill_buckets=(8, 16),
                       decode_chunk=4)
PROMPTS = [[7], [1, 2, 3, 4, 5, 6, 7, 8, 9], [9, 9, 9], [5] * 16, [3, 4],
           [2] * 11, [8, 1]]
MAX_NEW = [5, 9, 1, 17, 6, 3, 12]


def serve(tiny_model, prompts=PROMPTS, max_new=MAX_NEW, icfg=ICFG,
          parent=None):
    """Run the requests through a fresh engine; (stats, outputs, the
    ring's spans of this run)."""
    cfg, _model, params = tiny_model
    t0 = time.perf_counter()
    engine = InferenceEngine(params, cfg, icfg)
    try:
        with trace_plane.parent_scope(parent):
            futs = [engine.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, max_new)]
        outs = [f.result(timeout=300) for f in futs]
    finally:
        engine.shutdown()
    return engine.stats(), outs, spans.since(t0)


@pytest.fixture(scope="module")
def run(tiny_model):
    return serve(tiny_model, parent=("trace-1", "span-1", None, True))


def named(records, name):
    return [r for r in records if r[0] == name]


def test_stats_keeps_its_six_keys(run):
    stats, outs, records = run
    assert list(stats)[:6] == ["mode", "num_steps", "max_concurrent",
                               "free_pages", "active", "queued"]
    assert stats["mode"] == "both" and stats["active"] == 0
    assert stats["queued"] == 0
    assert stats["free_pages"] == ICFG.num_pages - 1
    assert 1 <= stats["max_concurrent"] <= ICFG.batch_size
    # num_steps stays the count of chunk DISPATCHES
    assert stats["num_steps"] == sum(
        r[5]["chunks"] for r in named(records, "engine.dispatch"))
    assert [len(o) for o in outs] == MAX_NEW


def rows_of(icfg, bucket):
    """Rows of a prefill launch: a budget of positions (one prompt in
    the largest bucket, at most ``_LAUNCH_POSITIONS``) over the bucket,
    at most ``batch_size`` rows and at least one."""
    budget = min(max(icfg.prefill_buckets), inference._LAUNCH_POSITIONS)
    return max(1, min(icfg.batch_size, budget // bucket))


def test_counters_conserve(run):
    stats, outs, _ = run
    rows = ICFG.batch_size
    assert stats["prefill_useful_rows"] == len(PROMPTS)
    assert stats["prefill_prompt_tokens"] == sum(map(len, PROMPTS))
    by_bucket = stats["prefill_by_bucket"]
    assert set(by_bucket) <= set(ICFG.prefill_buckets)
    for bucket, c in by_bucket.items():
        assert c["rows"] == c["launches"] * rows_of(ICFG, bucket)
        assert c["positions"] == c["rows"] * bucket
        assert 1 <= c["useful_rows"] <= c["rows"]
    for key in ("launches", "rows", "useful_rows", "positions",
                "prompt_tokens"):
        assert stats["prefill_" + key] == sum(
            c[key] for c in by_bucket.values())
    assert by_bucket[16]["useful_rows"] == sum(len(p) > 8 for p in PROMPTS)
    # a request's first token comes from its prefill, the rest from
    # decode steps; every step runs every slot
    assert stats["decode_tokens_kept"] == sum(len(o) - 1 for o in outs)
    assert stats["decode_slot_steps"] == rows * stats["decode_steps"]
    assert stats["decode_tokens_kept"] <= stats["decode_slot_steps"]


def test_ring_and_counters_agree_to_the_unit(run):
    """The engine was fresh, so its counters are the differences over
    the run, and the ring's records of the run sum to them."""
    stats, _, records = run
    launches = [r[5] for r in named(records, "engine.prefill_launch")]
    assert len(launches) == stats["prefill_launches"]
    assert all(f["rows"] == rows_of(ICFG, f["bucket"]) for f in launches)
    for key in ("rows", "useful_rows", "prompt_tokens"):
        assert sum(f[key] for f in launches) == stats["prefill_" + key]
    assert sum(f["rows"] * f["bucket"] for f in launches) == \
        stats["prefill_positions"]
    for bucket, c in stats["prefill_by_bucket"].items():
        assert c["launches"] == sum(f["bucket"] == bucket for f in launches)
    dispatches = [r[5] for r in named(records, "engine.dispatch")]
    assert sum(f["steps"] for f in dispatches) == stats["decode_steps"]
    delivers = named(records, "engine.deliver")
    assert len(delivers) == stats["bursts"]
    assert sum(r[5]["kept_tokens"] for r in delivers) == \
        stats["decode_tokens_kept"]
    # the share of the page table a step has to read
    assert sum(f["live_pages"] * f["steps"] for f in dispatches) == \
        stats["decode_pages_live"]
    assert stats["decode_pages_tabled"] == (
        stats["decode_steps"] * ICFG.batch_size * ICFG.max_pages_per_seq)
    assert 0 < stats["decode_pages_live"] <= stats["decode_pages_tabled"]
    for f in dispatches:
        assert 1 <= f["live_slots"] <= ICFG.batch_size
        assert f["live_ctx_tokens"] >= f["live_slots"]
        # a live slot owns a page at least and its table's at most
        assert f["live_slots"] <= f["live_pages"] <= (
            f["live_slots"] * ICFG.max_pages_per_seq)
        assert f["chunks"] <= 4 and f["steps"] <= 4 * ICFG.decode_chunk


def _benchmark_config(name: str, tiny: str = ""):
    """A configuration file of the benchmark: as its cell runs it, or
    with the sizes of the benchmark's tiny rehearsal ``tiny`` laid over
    it. Puts the checkout on the path, for the weights modules."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)

    def load(*parts):
        with open(os.path.join(root, "benchmark", *parts)) as f:
            return json.load(f)

    config = load("configs", name + ".json")
    if tiny:
        config.update(load("tests", tiny + ".json")["config"])
    return config


def _openpangu(tiny: bool):
    """(description, configuration) of the benchmark's latent-attention
    model."""
    config = _benchmark_config("openpangu-ultra-moe-718b-serve-L5-ep16",
                               "tiny_serve_described" if tiny else "")
    from benchmark import weights_openpangu_ultra as W

    return W.description(config), config


@pytest.fixture(scope="module")
def hybrid_model():
    """The benchmark's delta-rule + experts model at its rehearsal's
    sizes (one attention layer, three ``delta_rule`` layers, every
    feed-forward 4 picks of 8 held experts), in ``tiny_model``'s form."""
    from ray_tpu.models.decoder import DecoderConfig, LayerSpec

    config = _benchmark_config("solar-open2-250b-serve-L4-ep8",
                               "tiny_serve_decoder")
    from benchmark import weights_solar_open2 as W

    s = W.dims(config)
    layers = tuple(LayerSpec("attention" if i in s["gqa"] else "delta_rule",
                             "experts") for i in range(s["layers"]))
    mcfg = DecoderConfig(layers=layers, **W.decoder_kwargs(config))
    assert mcfg.state_layers == (1, 2, 3) and len(mcfg.moe_layers) == 4
    params = jax.jit(lambda k: W.init_params(config, k, jnp.float32))(
        W.seed_key(3))
    return mcfg, None, params


@pytest.fixture
def served(request):
    """``tiny_model`` or ``hybrid_model``, by the parameter's name"""
    return request.getfixturevalue(request.param)


@pytest.fixture
def launch_positions(request, monkeypatch):
    """The engine's ``_LAUNCH_POSITIONS`` at the parameter for the test;
    None leaves the program's own."""
    if request.param:
        monkeypatch.setattr(inference, "_LAUNCH_POSITIONS", request.param)
    return request.param


def test_a_latent_models_ring_and_counters_agree():
    """The latent-attention model (a dense layer, then expert layers)
    through the same engine: the ring's launches and deliveries carry
    the picks and sum to ``stats()``'s; the dispatches carry the live
    pages and tokens its read's roofline is counted from; the two
    latent keys say what the pools hold."""
    mcfg, tiny = _openpangu(tiny=True)
    from benchmark import weights_openpangu_ultra as W

    params = jax.jit(lambda k: W.init_params(tiny, k, jnp.float32))(
        W.seed_key(3))
    stats, outs, records = serve((mcfg, None, params))
    assert [len(o) for o in outs] == MAX_NEW
    counted = [r[5] for r in records if r[0] in (
        "engine.prefill_launch", "engine.deliver")
        and "moe_picks_total" in r[5]]
    for key in ("moe_picks_total", "moe_picks_local"):
        assert sum(f[key] for f in counted) == stats[key] > 0
    # 4 picks a token in each of the two expert layers
    tokens = sum(map(len, PROMPTS)) + sum(len(o) - 1 for o in outs)
    assert stats["moe_picks_total"] == tokens * 4 * 2
    dispatches = named(records, "engine.dispatch")
    assert all({"live_slots", "live_ctx_tokens", "live_pages", "steps",
                "chunks"} <= set(r[5]) for r in dispatches)
    assert not any("state_slots_live" in r[5] for r in dispatches)
    launches = named(records, "engine.prefill_launch")
    assert all(len(r[5]["prompt_lens"]) == r[5]["useful_rows"]
               for r in launches)
    assert stats["latent_bytes_per_token"] == 3 * 128 * 4
    assert stats["latent_pool_bytes"] == (
        3 * ICFG.num_pages * ICFG.page_size * 128 * 4)
    assert stats["pool_tokens"] == (ICFG.num_pages - 1) * ICFG.page_size


def test_latent_bytes_a_token_at_the_published_widths():
    """512 + 64 numbers a token and layer are 1,152 B in bfloat16; the
    row is held as 640 columns (whole lanes of 128: the chip's memory
    tiles it so anyway, and the kernel reads whole lanes), 1,280 B, so
    the cell's five layers hold 6,400 B a token where the arithmetic of
    the widths alone says 5,760."""
    mcfg, _ = _openpangu(tiny=False)
    assert mcfg.kv_rank + mcfg.rope_dim == 576 and mcfg.latent_width == 640
    assert len(mcfg.latent_layers) == 5
    per_token = len(mcfg.latent_layers) * mcfg.latent_width * 2
    assert per_token == 6400 and 5 * 576 * 2 == 5760
    # at most 1,280 B a token and layer (ISSUE 31's bound)
    assert mcfg.latent_width * 2 <= 1280


def test_request_spans_share_an_ident_and_are_ordered(run):
    _, _, records = run
    by_ident = collections.defaultdict(dict)
    for r in records:
        if r[0] in REQUEST:
            assert r[0] not in by_ident[r[3]]
            by_ident[r[3]][r[0]] = r
    assert sorted(by_ident) == list(range(len(PROMPTS)))
    for got in by_ident.values():
        queue, first, decode = (got[n] for n in REQUEST)
        # submit <= admit <= first token <= finish, each span beginning
        # where the one before it ended
        assert queue[1] <= queue[2] == first[1] <= first[2] == decode[1] \
            <= decode[2]
        # the trace plane's context of the submitting call
        assert {r[4] for r in got.values()} == {("trace-1", "span-1",
                                                 None, True)}
    assert all(r[3] is None and r[4] is None for r in records
               if r[0] in BURST)


def test_no_parent_outside_a_traced_call(tiny_model):
    _, _, records = serve(tiny_model, PROMPTS[:2], [2, 2])
    assert [r[4] for r in records if r[0] in REQUEST] == [None] * 6


def test_burst_spans_of_the_loop_do_not_overlap(run):
    _, _, records = run
    loop = sorted((r for r in records if r[0] in BURST), key=lambda r: r[1])
    for a, b in zip(loop, loop[1:]):
        assert a[2] <= b[1], (a, b)
    # one set a round, in the loop's order
    assert [r[0] for r in loop] == list(BURST) * (len(loop) // 4)
    admits = named(records, "engine.admit")
    for launch in named(records, "engine.prefill_launch"):
        assert any(a[1] <= launch[1] and launch[2] <= a[2] for a in admits)


def test_ring_grows_with_bursts_launches_and_requests_not_with_tokens(
        tiny_model):
    """Three requests of 8 and of 100 tokens: four spans a burst, one a
    launch, three a request and nothing else, and a request takes at
    most two bursts however long its answer (up to 4 x decode_chunk
    steps go into one)."""
    icfg = InferenceConfig(batch_size=3, page_size=4, max_pages_per_seq=32,
                           num_pages=128, prefill_buckets=(8,),
                           decode_chunk=32)
    prompts = [[1, 2], [3], [4, 5, 6]]
    for max_new in (8, 100):
        stats, outs, records = serve(tiny_model, prompts, [max_new] * 3,
                                     icfg)
        assert [len(o) for o in outs] == [max_new] * 3
        assert stats["decode_steps"] >= max_new - 1
        assert stats["bursts"] <= 2 * len(prompts)
        assert len(records) == (4 * stats["bursts"]
                                + stats["prefill_launches"]
                                + 3 * len(prompts))
        # what the request spans say they cover grows with the answer,
        # their number and the hand-outs they count do not (a replica's
        # polls follow the hand-outs: test_request_path_spans.py)
        decodes = named(records, "engine.decode")
        assert [r[5]["tokens"] for r in decodes] == [max_new] * 3
        assert all(1 <= r[5]["handouts"] <= 2 for r in decodes)
        assert all(1 <= r[5]["tokens"] <= max_new
                   for r in named(records, "engine.first_token"))


@contextlib.contextmanager
def one_admission_round(engine):
    """Hold the loop's admission while the block submits, so that what
    it submits (to an idle engine, with slots and pages for all of it)
    is admitted in ONE round, a group to a bucket."""
    gate, admit = threading.Event(), engine._try_admit

    def held():
        gate.wait()
        admit()

    engine._try_admit = held
    try:
        yield
    finally:
        del engine._try_admit
        gate.set()


def watch_prefill_shapes(engine):
    """{bucket: [shape of ``packed`` at each call of its program]}"""
    seen = collections.defaultdict(list)
    for bucket, fn in list(engine._prefill_many.items()):
        def call(p, packed, *rest, _fn=fn, _bucket=bucket):
            seen[_bucket].append(packed.shape)
            return _fn(p, packed, *rest)
        engine._prefill_many[bucket] = call
    return seen


def test_live_pages_agree_with_a_hand_count(tiny_model):
    """Two requests admitted in one round: 3 tokens + 6 new own
    ceil(9 / 4) = 3 pages, 8 + 13 new own ceil(21 / 4) = 6, from
    admission to the end (the engine reserves ``max_new`` up front).
    Bursts run both until the shorter finishes, then the longer
    alone."""
    cfg, _model, params = tiny_model
    t0 = time.perf_counter()
    engine = InferenceEngine(params, cfg, ICFG)
    try:
        with one_admission_round(engine):
            futs = [engine.submit([1, 2, 3], max_new_tokens=6),
                    engine.submit([5] * 8, max_new_tokens=13)]
        assert [len(f.result(timeout=300)) for f in futs] == [6, 13]
    finally:
        engine.shutdown()
    stats = engine.stats()
    dispatches = [r[5] for r in named(spans.since(t0), "engine.dispatch")]
    pages = [f["live_pages"] for f in dispatches]
    assert pages[0] == 3 + 6 and pages[-1] == 6
    assert pages == sorted(pages, reverse=True) and set(pages) == {9, 6}
    both = sum(f["steps"] for f in dispatches if f["live_pages"] == 9)
    alone = sum(f["steps"] for f in dispatches if f["live_pages"] == 6)
    assert both >= 5 and both + alone >= 12      # 5 and 12 decode tokens
    assert stats["decode_pages_live"] == 9 * both + 6 * alone
    assert stats["decode_pages_tabled"] == (both + alone) * 3 * 16


LONGDOC = (1024, 2048, 4096, 8192, 16384)


@pytest.mark.parametrize("batch_size, buckets, want", [
    # the two hybrid cells': a bucket of _LAUNCH_POSITIONS and more
    # runs its one row alone, whatever the largest bucket
    (64, LONGDOC, {1024: 2, 2048: 1, 4096: 1, 8192: 1, 16384: 1}),
    (32, LONGDOC[:4], {1024: 2, 2048: 1, 4096: 1, 8192: 1}),
    # the dense cells': under the constant, PR 26's rule to the letter
    (32, (64, 128, 256, 512), {64: 8, 128: 4, 256: 2, 512: 1}),
    # the largest bucket IS the constant
    (32, (256, 512, 1024, 2048), {256: 8, 512: 4, 1024: 2, 2048: 1}),
    # rows still clamp to the slots
    (2, (512, 4096), {512: 2, 4096: 1}),
])
def test_the_budget_of_a_launch_stops_at_launch_positions(
        tiny_model, batch_size, buckets, want):
    """budget = min(largest bucket, _LAUNCH_POSITIONS), rows(b) =
    clamp(budget // b, 1, batch_size): read off ``_prefill_rows`` of an
    engine with the cell's buckets and slots around the tiny model (no
    program is built until a request comes)."""
    assert inference._LAUNCH_POSITIONS == 2048
    cfg, _model, params = tiny_model
    icfg = InferenceConfig(batch_size=batch_size, page_size=128,
                           max_pages_per_seq=-(-max(buckets) // 128) + 1,
                           num_pages=8, prefill_buckets=buckets)
    engine = InferenceEngine(params, cfg, icfg)
    try:
        assert engine._prefill_rows == want
        assert {b: rows_of(icfg, b) for b in buckets} == want
        assert sorted(engine._prefill_many) == sorted(buckets)
    finally:
        engine.shutdown()


@pytest.mark.parametrize("launch_positions, batch_size, buckets, want", [
    (None, 3, (8, 16), {8: 2, 16: 1}),                    # ICFG's
    # 8 rows clamp to 4
    (None, 4, (8, 16, 32, 64), {8: 4, 16: 4, 32: 2, 64: 1}),
    (None, 2, (16,), {16: 1}),
    # the cell's
    (None, 32, (64, 128, 256, 512), {64: 8, 128: 4, 256: 2, 512: 1}),
    # the constant under the largest bucket: one row from there up
    (16, 4, (8, 16, 32, 64), {8: 2, 16: 1, 32: 1, 64: 1}),
    (32, 8, (8, 16, 32, 64), {8: 4, 16: 2, 32: 1, 64: 1}),
], indirect=["launch_positions"])
def test_a_launch_has_the_rows_its_bucket_gives_it(
        tiny_model, launch_positions, batch_size, buckets, want):
    """rows(b) = clamp(min(largest bucket, _LAUNCH_POSITIONS) // b, 1,
    batch_size): the program is called with that many rows, and the
    launch's span and stats() report that many."""
    cfg, _model, params = tiny_model
    largest = max(buckets)
    icfg = InferenceConfig(batch_size=batch_size, page_size=4,
                           max_pages_per_seq=-(-largest // 4) + 1,
                           num_pages=2 * (largest // 4 + 2),
                           prefill_buckets=buckets, decode_chunk=2)
    assert {b: rows_of(icfg, b) for b in buckets} == want
    t0 = time.perf_counter()
    engine = InferenceEngine(params, cfg, icfg)
    try:
        assert engine._prefill_rows == want
        shapes = watch_prefill_shapes(engine)
        for bucket in buckets:      # one at a time: a launch a request
            assert len(engine.generate([5] * bucket, 2)) == 2
    finally:
        engine.shutdown()
    for bucket, rows in want.items():
        assert shapes[bucket] == [(rows, 2 + bucket + -(-bucket // 4))]
    launches = [r[5] for r in named(spans.since(t0), "engine.prefill_launch")]
    assert [(f["bucket"], f["rows"], f["useful_rows"]) for f in launches] \
        == [(b, want[b], 1) for b in buckets]
    by_bucket = engine.stats()["prefill_by_bucket"]
    assert {b: (c["launches"], c["rows"], c["positions"])
            for b, c in by_bucket.items()} \
        == {b: (1, want[b], want[b] * b) for b in buckets}


GROUPS = InferenceConfig(batch_size=10, page_size=4, max_pages_per_seq=12,
                         num_pages=10 * 12 + 1, prefill_buckets=(8, 16, 32),
                         decode_chunk=4)      # rows: 4, 2, 1


def group_prompts(sizes):
    """``sizes[bucket]`` distinct prompts that fall into each bucket"""
    prompts = []
    for bucket, n in sizes.items():
        for i in range(n):
            plen = bucket - (i % (bucket // 2))     # bucket/2 < plen <= bucket
            prompts.append([(7 * len(prompts) + 3 * j + 1) % 64
                            for j in range(plen)])
    return prompts


@pytest.mark.parametrize("served, launch_positions, sizes", [
    ("tiny_model", None, {8: 5, 16: 3, 32: 2}),
    ("tiny_model", None, {8: 9, 16: 1}),
    ("tiny_model", None, {16: 4, 32: 3, 8: 1}),
    # rows 2, 1, 1: a group of a long bucket is a launch a request
    ("tiny_model", 16, {8: 5, 16: 3, 32: 2}),
    ("hybrid_model", 16, {8: 3, 16: 3, 32: 2}),
    ("hybrid_model", None, {8: 5, 16: 3, 32: 1}),
], indirect=["served", "launch_positions"])
def test_a_group_larger_than_a_launch_is_split_and_answers_as_alone(
        served, launch_positions, sizes):
    """One admission round with more requests of a bucket than a launch
    has rows: ceil(group / rows) launches, the last one padded, and every
    request answers with the tokens it gets when it is served alone;
    for an attention-only model and for one with ``delta_rule`` layers
    and experts, with the launch's budget at the largest bucket and
    under it."""
    cfg, _model, params = served
    prompts = group_prompts(sizes)
    max_new = [3 + i % 5 for i in range(len(prompts))]
    engine = InferenceEngine(params, cfg, GROUPS)
    try:
        alone = [engine.generate(p, n) for p, n in zip(prompts, max_new)]
        t0, before = time.perf_counter(), engine.stats()
        with one_admission_round(engine):
            futs = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
        together = [f.result(timeout=300) for f in futs]
        after = engine.stats()
    finally:
        engine.shutdown()
    assert together == alone
    assert [len(o) for o in together] == max_new
    launches = [r[5] for r in named(spans.since(t0), "engine.prefill_launch")]
    for bucket, n in sizes.items():
        rows = rows_of(GROUPS, bucket)
        mine = [f for f in launches if f["bucket"] == bucket]
        assert [f["useful_rows"] for f in mine] == \
            [rows] * (n // rows) + [n % rows] * (n % rows > 0)
        assert all(f["rows"] == rows for f in mine)
    # positions are the launches' rows x bucket, in the ring and in stats()
    ring = {"launches": len(launches),
            "rows": sum(f["rows"] for f in launches),
            "useful_rows": len(prompts),
            "positions": sum(f["rows"] * f["bucket"] for f in launches),
            "prompt_tokens": sum(map(len, prompts))}
    assert {key: after["prefill_" + key] - before["prefill_" + key]
            for key in ring} == ring
    assert sum(f["prompt_tokens"] for f in launches) == ring["prompt_tokens"]


@pytest.mark.parametrize("launch_positions", [None, 16], indirect=True)
def test_each_prefill_program_compiles_once_whatever_the_group(
        tiny_model, launch_positions):
    """Groups of every size from 1 to batch_size in every bucket: a
    bucket's program is always called with one shape, so jit has
    specialised it once."""
    cfg, _model, params = tiny_model
    icfg = InferenceConfig(batch_size=4, page_size=4, max_pages_per_seq=10,
                           num_pages=4 * 10 + 1, prefill_buckets=(8, 16, 32),
                           decode_chunk=2)      # rows: 4, 2, 1 (or 2, 1, 1)
    engine = InferenceEngine(params, cfg, icfg)
    try:
        programs = dict(engine._prefill_many)
        for bucket in icfg.prefill_buckets:
            for size in range(1, icfg.batch_size + 1):
                with one_admission_round(engine):
                    futs = [engine.submit(p, 2) for p in
                            group_prompts({bucket: size})]
                assert [len(f.result(timeout=300)) for f in futs] == \
                    [2] * size
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert {b: fn._cache_size() for b, fn in programs.items()} == \
        {8: 1, 16: 1, 32: 1}
    sizes = range(1, icfg.batch_size + 1)
    assert {b: c["launches"] for b, c in stats["prefill_by_bucket"].items()} \
        == {b: sum(-(-n // rows_of(icfg, b)) for n in sizes)
            for b in icfg.prefill_buckets}
    assert stats["prefill_useful_rows"] == 3 * sum(sizes)


def test_a_full_ring_drops_the_oldest(monkeypatch):
    monkeypatch.setattr(spans, "_RING", collections.deque(maxlen=3))
    for i in range(5):
        spans.record("r", float(i), float(i) + 0.5, ident=i, n=i)
    with spans.span("s", ident=9, a=1) as sp:
        sp.fields["b"] = 2
    got = spans.since(float("-inf"))
    assert [(r[0], r[3]) for r in got] == [("r", 3), ("r", 4), ("s", 9)]
    assert got[-1][5] == {"a": 1, "b": 2} and got[-1][1] <= got[-1][2]
    assert spans.since(4.2) == got[1:]
    assert spans._RING.maxlen == 3


def test_the_process_ring_is_bounded():
    assert spans._RING.maxlen == 65536


def engine_programs(engine):
    """(expected module name, lowered program) of every jitted program
    of a "both"-mode engine."""
    cfg, params, rows = engine.cfg, engine.params, engine.cfg.batch_size
    table = jnp.zeros((rows, cfg.max_pages_per_seq), jnp.int32)
    lens = jnp.zeros((rows,), jnp.int32)
    kv_shape = (engine.mcfg.n_layers, 8, engine.mcfg.n_kv_heads,
                engine.mcfg.head_dim)
    yield "jit_engine_split_packed", engine._split_packed.lower(
        jnp.zeros((rows, 1 + cfg.max_pages_per_seq), jnp.int32))
    yield "jit_engine_kv_import", engine._kv_import.lower(
        engine._cache, engine._dev_toks,
        jnp.zeros(kv_shape), jnp.zeros(kv_shape),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32))
    for steps, fn in engine._decode_chunks.items():
        yield f"jit_engine_decode_n{steps}", fn.lower(
            params, engine._dev_toks, engine._cache, table, lens,
            jnp.zeros((rows,), bool))
    for bucket, fn in engine._prefill_many.items():
        packed = jnp.zeros(
            (rows_of(cfg, bucket), 2 + bucket + -(-bucket // cfg.page_size)),
            jnp.int32)
        yield f"jit_engine_prefill_b{bucket}", fn.lower(
            params, packed, engine._cache, engine._dev_toks)
    for bucket, fn in engine._export_jits.items():
        yield f"jit_engine_prefill_export_b{bucket}", fn.lower(
            params, jnp.zeros((1, bucket), jnp.int32))


def test_every_engine_program_has_a_name_of_its_own(tiny_model):
    from jax.extend.core import Primitive, primitives

    cfg, _model, params = tiny_model
    engine = InferenceEngine(params, cfg, ICFG)
    try:
        got = [(want, lowered.as_text().split("\n", 1)[0])
               for want, lowered in engine_programs(engine)]
    finally:
        engine.shutdown()
    assert len(got) == 2 + 3 + 2 + 2      # chunks of 1, 2 and 4 steps
    primitive_names = {p.name for p in vars(primitives).values()
                       if isinstance(p, Primitive)}
    for want, first_line in got:
        assert first_line.startswith(f"module @{want} "), (want, first_line)
        # what jax calls the compiled program is jit(<name>): a name that
        # is a primitive's would read as an eager dispatch
        assert want[len("jit_"):] not in primitive_names
    assert len({want for want, _ in got}) == len(got)


def scopes_in(lowered):
    """Under jax.grad a scope reads jvp(<scope>) and
    transpose(jvp(<scope>))."""
    text = lowered.as_text(debug_info=True)
    return {s for s in ("embed", "attn", "kv_append", "mlp", "head",
                        "loss", "optimizer")
            if f"/{s}/" in text or f"/jvp({s})/" in text}


def test_the_forward_carries_its_scopes(tiny_model):
    cfg, _model, params = tiny_model
    engine = InferenceEngine(params, cfg, ICFG)
    try:
        rows = ICFG.batch_size
        k_pages, v_pages = zip(*engine._cache)
        step = jax.jit(lambda p, t, k, v, table, lens: decode_step(
            p, cfg, t, k, v, table, lens)).lower(
                params, engine._dev_toks, k_pages, v_pages,
                jnp.zeros((rows, ICFG.max_pages_per_seq), jnp.int32),
                jnp.zeros((rows,), jnp.int32))
        assert scopes_in(step) == {"embed", "attn", "kv_append", "mlp",
                                   "head"}
        batch = jax.jit(lambda p, t: prefill_batch(p, cfg, t)).lower(
            params, jnp.zeros((rows, 8), jnp.int32))
        assert scopes_in(batch) == {"embed", "attn", "mlp", "head"}
        # the prefill program writes the prompt's K/V into the pages
        lowered = dict(engine_programs(engine))
        assert "kv_append" in scopes_in(lowered["jit_engine_prefill_b8"])
        assert "kv_append" in scopes_in(lowered["jit_engine_kv_import"])
    finally:
        engine.shutdown()


def test_the_train_step_carries_loss_and_optimizer(tiny_model):
    _cfg, model, params = tiny_model
    optimizer = ts.make_optimizer()
    step = jax.jit(ts.make_train_step(model, optimizer)).lower(
        params, optimizer.init(params),
        {"tokens": jnp.zeros((2, 9), jnp.int32)})
    assert {"loss", "optimizer"} <= scopes_in(step)


def test_a_profile_holds_the_engines_annotations(tiny_model, tmp_path):
    """Under jax.profiler the loop's spans are annotations on the
    profiler's clock, in the host's plane."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        stats, _, _ = serve(tiny_model, PROMPTS[:3], MAX_NEW[:3])
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert found
    data = jax.profiler.ProfileData.from_file(str(found[-1]))
    names = collections.Counter(
        e.name for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith("engine."))
    assert names["engine.fetch"] == stats["bursts"]
    assert names["engine.dispatch"] == stats["bursts"]
    assert names["engine.prefill_launch"] == stats["prefill_launches"]
