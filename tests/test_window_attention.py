"""Window attention through the serving path: a layer that sees the last
``window`` positions keeps them in a ring of pages that belongs to its
slot, beside full layers under the engine's page table. Prefill then
decode through the cache are held, on LOGITS, to the benchmark's plain
reference (benchmark/reference/trinity_large.py: float32, no cache, no
ring, the window a mask built from positions) at a window of 8 with
pages of 4, so that rings wrap several times; the read kernel with a
first position, interpreted, against the gather oracle under the same
mask; the wrapped append; the band of a prompt against dense masked
attention; what the engine counts and refuses."""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights_trinity_large as W  # noqa: E402
from benchmark.reference import trinity_large as ref  # noqa: E402
from ray_tpu._private import spans  # noqa: E402
from ray_tpu.models import decoder_forward as forward  # noqa: E402
from ray_tpu.models import inference  # noqa: E402
from ray_tpu.models.decoder import DecoderConfig, LayerSpec  # noqa: E402
from ray_tpu.models.inference import (InferenceConfig,  # noqa: E402
                                      InferenceEngine)
from ray_tpu.ops import paged_attention as pa  # noqa: E402
from ray_tpu.ops.mla_prefill import window_prefill_attention  # noqa: E402

WINDOW, PAGE, RING = 8, 4, 3
TINY = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "vocab_size": 64, "num_hidden_layers": 3,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "num_dense_layers": 1, "sliding_window": WINDOW,
    "intermediate_size": 48, "router_width": 8, "experts_held": [2, 6],
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "num_shared_experts": 1, "route_scale": 2.448, "router_bias_std": 0.2,
    "mup_enabled": True, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False,
    "run": {"dtype": "float32", "param_dtype": "float32"},
}
SEED = 7


@pytest.fixture(scope="module")
def model():
    mcfg = W.description(TINY)
    params = jax.jit(lambda k: W.init_params(TINY, k, jnp.float32))(
        W.seed_key(SEED))
    return mcfg, params


def reference_logits(rows):
    return np.asarray(ref.teacher_forced_logits(
        TINY, SEED, np.asarray(rows, np.int32), "f32", jnp.float32))


def test_the_description(model):
    mcfg, params = model
    assert mcfg.layers == (LayerSpec("window", "dense"),
                           LayerSpec("window", "experts"),
                           LayerSpec("attention", "experts"))
    assert mcfg.window_layers == (0, 1) and mcfg.kv_layers == (2,)
    assert mcfg.window == WINDOW and forward.window_ring(mcfg, PAGE) == RING
    # window layers rotate, this model's full layers do not
    assert mcfg.rotates("window") and not mcfg.rotates("attention")
    assert mcfg.embed_scale == 32 ** 0.5 and mcfg.router_bias
    assert sorted(params["layer_1"]["Attention_0"]) == [
        "k_norm", "q_norm", "w_gate", "wk", "wo", "wq", "wv"]
    assert sorted(params["layer_1"]["MoE_0"]) == [
        "bias", "router", "shared", "w_down", "w_gate", "w_up"]
    with pytest.raises(ValueError, match="window's length"):
        DecoderConfig(vocab_size=8, d_model=8, n_heads=1, n_kv_heads=1,
                      head_dim=8, layers=(LayerSpec("window"),))
    # every description from before there were windows rotates as it did
    old = DecoderConfig(vocab_size=8, d_model=8, n_heads=1, n_kv_heads=1,
                        head_dim=8, layers=(LayerSpec(),), rope_theta=1e4)
    assert old.rotates("attention") and not old.window_layers


_STEP = jax.jit(forward.decode_step_cached, static_argnums=(1,))


def _launch_and_decode(mcfg, params, rows, plens, bucket, slots,
                       batch_size=3):
    """Prefill ``rows[r][:plens[r]]`` in ONE launch of ``bucket`` (with
    a dummy row behind them), then decode the rest of each row
    teacher-forced through the cache. Returns for each row the logits of
    every position from its plen - 1 on."""
    total = len(rows[0])
    n_pages = -(-total // PAGE)
    icfg = InferenceConfig(batch_size=batch_size, page_size=PAGE,
                           max_pages_per_seq=n_pages,
                           num_pages=batch_size * n_pages + 1,
                           prefill_buckets=(bucket,))
    parking = icfg.num_pages - 1
    cache = forward.init_cache(mcfg, icfg)
    # a window layer's pool: a ring a slot and one page more, whatever
    # the context; the full layer's: the engine's pages
    assert [e[0].shape[0] for e in cache] == [
        batch_size * RING + 1] * 2 + [icfg.num_pages]
    n, n_prog = len(rows), -(-bucket // PAGE)
    toks = np.zeros((n + 1, bucket), np.int32)
    launch = np.full((n + 1, n_prog), parking, np.int32)
    table = np.full((batch_size, n_pages), parking, np.int32)
    for r, (row, plen, slot) in enumerate(zip(rows, plens, slots)):
        toks[r, :plen] = row[:plen]
        table[slot] = slot * n_pages + np.arange(n_pages)[::-1]
        launch[r, :min(n_prog, n_pages)] = table[slot, :n_prog]
    slot_ids = np.asarray(list(slots) + [batch_size], np.int32)
    logits, cache, _ = forward.prefill_cached(
        params, mcfg, cache, jnp.asarray(toks),
        jnp.asarray(list(plens) + [1]), jnp.asarray(slot_ids),
        jnp.asarray(launch), jnp.asarray(slot_ids < batch_size))
    got = [[np.asarray(logits)[r]] for r in range(n)]
    lens = np.zeros(batch_size, np.int32)
    lens[list(slots)] = plens
    while (lens[list(slots)] < total).any():
        live = (lens > 0) & (lens < total)
        tokens = np.zeros(batch_size, np.int32)
        for row, slot in zip(rows, slots):
            if live[slot]:
                tokens[slot] = row[lens[slot]]
        step, cache, _ = _STEP(
            params, mcfg, jnp.asarray(tokens), cache, jnp.asarray(table),
            jnp.asarray(np.where(live, lens, 0)), jnp.asarray(live))
        for r, slot in enumerate(slots):
            if live[slot]:
                got[r].append(np.asarray(step)[slot])
        lens = np.where(live, lens + 1, lens)
    return [np.stack(g) for g in got]


# buckets of 16 and 32 positions have 4 and 8 pages against a ring of 3:
# the pages past the prompt are padding, and written by ``page % ring``
# they would land on ring pages that hold live tokens (a prompt of 9 in
# 32: padding pages 3..7 over ring pages 0, 1, 2)
@pytest.mark.parametrize("plen, bucket", [
    pytest.param(5, 8, id="shorter-than-the-window"),
    pytest.param(8, 8, id="the-window"),
    pytest.param(9, 32, id="padding-pages-past-the-ring"),
    pytest.param(19, 32, id="longer-than-the-window"),
    pytest.param(30, 32, id="ring-wrapped-twice-by-the-prompt"),
])
def test_prefill_then_decode_is_the_reference(model, plen, bucket):
    """Every logit of the prompt (a band at a short row is a mask),
    then every logit up to a context of 40 from the rings and the page
    table, equal the reference's full forward. Tolerance 2e-4: float32
    on both sides, the program's softmax and norms in another order of
    operations than the reference's; a dropped or overwritten token
    moves a logit by 1e-2 and more."""
    mcfg, params = model
    row = np.random.default_rng(plen).integers(1, 64, 40)
    want = reference_logits(row[None])[0]
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :plen] = row[:plen]
    x, _, _ = forward._prefill_hidden(params, mcfg, jnp.asarray(toks),
                                      jnp.asarray([plen]))
    whole = np.asarray(forward._head(params, mcfg, x, "bsd,vd->bsv"))[0]
    np.testing.assert_allclose(whole[:plen], want[:plen], atol=2e-4)
    (got,) = _launch_and_decode(mcfg, params, [row], [plen], bucket, [1])
    np.testing.assert_allclose(got, want[plen - 1:], atol=2e-4)


def test_rows_of_one_launch_keep_to_their_own_rings(model):
    """Three prompts of 6, 13 and 29 tokens in one launch of 32
    positions, into slots 2, 0 and 1: each decodes to 40 as alone."""
    mcfg, params = model
    rng = np.random.default_rng(1)
    rows = [rng.integers(1, 64, 40) for _ in range(3)]
    want = reference_logits(np.stack(rows))
    got = _launch_and_decode(mcfg, params, rows, [6, 13, 29], 32, [2, 0, 1])
    for g, w, plen in zip(got, want, [6, 13, 29]):
        np.testing.assert_allclose(g, w[plen - 1:], atol=2e-4)


def test_idle_slots_leave_their_rings_alone(model):
    """A decode step with slots 0 and 2 idle: their rings (NaN here)
    are neither written nor read, their dummy tokens land in the pool's
    last page, and the live slot's logits are what they are with zeros
    there."""
    mcfg, params = model
    icfg = InferenceConfig(batch_size=3, page_size=PAGE, max_pages_per_seq=4,
                           num_pages=13, prefill_buckets=(8,))
    rng = np.random.default_rng(2)
    filled = [tuple(jnp.asarray(rng.normal(size=a.shape), jnp.float32)
                    for a in e) for e in forward.init_cache(mcfg, icfg)]

    def run(idle):
        cache = tuple(
            tuple(a.at[:RING].set(idle).at[2 * RING:3 * RING].set(idle)
                  for a in e) if i < 2 else e
            for i, e in enumerate(filled))
        table = jnp.asarray([[12] * 4, [0, 1, 12, 12], [12] * 4], jnp.int32)
        logits, cache, _ = forward.decode_step_cached(
            params, mcfg, jnp.asarray([0, 5, 0], jnp.int32), cache, table,
            jnp.asarray([0, 6, 0], jnp.int32),
            jnp.asarray([False, True, False]))
        return np.asarray(logits)[1], cache

    clean, _ = run(0.0)
    got, cache = run(jnp.nan)
    np.testing.assert_array_equal(got, clean)
    assert np.isfinite(clean).all()
    for k_pool, v_pool in cache[:2]:
        assert np.isnan(np.asarray(k_pool[:RING])).all()
        assert np.isnan(np.asarray(v_pool[2 * RING:3 * RING])).all()
        assert np.isfinite(np.asarray(k_pool[RING:2 * RING])).all()
        assert np.isfinite(np.asarray(k_pool[3 * RING])).all()


def test_padding_pages_written_by_their_remainder_would_fail(model,
                                                             monkeypatch):
    """The trap the launch's write avoids: with every page of the
    bucket written to ``page % ring`` the first decoded tokens of a
    prompt of 9 in a bucket of 32 read padding for keys."""
    mcfg, params = model

    def by_remainder(cfg, pool_pages, page, n_pages, slots, plens):
        ring = forward.window_ring(cfg, page)
        return jnp.where((slots < (pool_pages - 1) // ring)[:, None],
                         slots[:, None] * ring + jnp.arange(n_pages) % ring,
                         pool_pages - 1)

    monkeypatch.setattr(forward, "window_prefill_pages", by_remainder)
    row = np.random.default_rng(9).integers(1, 64, 40)
    want = reference_logits(row[None])[0]
    (got,) = _launch_and_decode(mcfg, params, [row], [9], 32, [1])
    assert np.abs(got[1:4] - want[9:12]).max() > 1e-2


def test_where_a_launch_writes_its_pages(model):
    mcfg, _ = model
    where = np.asarray(forward.window_prefill_pages(
        mcfg, 3 * RING + 1, PAGE, 8, jnp.asarray([1, 0, 2, 3]),
        jnp.asarray([19, 4, 32, 7])))
    park = 3 * RING
    # 19 tokens: the window starts at 11, in page 2; the last is page 4
    assert where[0].tolist() == [park, park, 3 + 2, 3 + 0, 3 + 1] + [park] * 3
    assert where[1].tolist() == [0] + [park] * 7
    # 32 tokens: positions 24.. in pages 6, 7
    assert where[2].tolist() == [park] * 6 + [6 + 0, 6 + 1]
    # a dummy row (slot out of bounds) writes nothing that is read
    assert where[3].tolist() == [park] * 8


# ----------------------------------------------------------------------
# the kernels against their oracles
# ----------------------------------------------------------------------

def _ring_case(page, kv, g, d, ring, lens, window, dtype, seed=0):
    """A pool of rings with every token of every sequence appended in
    order (so the rings have wrapped), beside the uncut keys and
    values."""
    rng = np.random.default_rng(seed)
    b, longest = len(lens), max(max(lens), 1)
    k_all = rng.normal(size=(b, longest, kv, d)).astype(np.float32)
    v_all = rng.normal(size=(b, longest, kv, d)).astype(np.float32)
    k_pool = np.full((b * ring + 1, kv, page, d), np.nan, np.float32)
    v_pool = k_pool.copy()
    for s, n in enumerate(lens):
        for t in range(n):
            at = s * ring + (t // page) % ring
            k_pool[at, :, t % page] = k_all[s, t]
            v_pool[at, :, t % page] = v_all[s, t]
    q = jnp.asarray(rng.normal(size=(b, kv * g, d)), dtype)
    table = jnp.arange(b * ring, dtype=jnp.int32).reshape(b, ring)
    return (q, jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype),
            table, jnp.asarray(lens, jnp.int32), k_all, v_all)


@pytest.mark.parametrize("page, kv, g, d, window, block, lens, dtype", [
    pytest.param(4, 2, 2, 16, 8, 8, [0, 1, 5, 8, 9, 23, 40], jnp.float32,
                 id="window-8-pages-of-4"),
    pytest.param(4, 2, 2, 16, 8, pa.BLOCK_TOKENS, [13, 0, 40],
                 jnp.float32, id="ring-shorter-than-a-block"),
    pytest.param(16, 2, 4, 128, 48, 32, [0, 47, 48, 49, 200],
                 jnp.bfloat16, id="window-48-pages-of-16-bf16"),
    pytest.param(128, 8, 6, 128, 512, 256, [700, 0, 512, 1300],
                 jnp.bfloat16, id="pages-of-128-six-heads-a-key-head"),
    pytest.param(4, 1, 4, 16, 6, 8, [3, 30], jnp.float32,
                 id="window-no-multiple-of-the-page"),
])
def test_the_windowed_read_matches_the_gather(page, kv, g, d, window, block,
                                              lens, dtype):
    """Against ``paged_attention_reference`` over the UNCUT keys and
    values under the same mask (positions ``len - window .. len - 1``):
    the ring holds what the window needs, the walk starts at the first
    position's block and wraps, unwritten cells (NaN here) reach no
    row."""
    ring = -(-(window - 1) // page) + 1
    q, k_pool, v_pool, table, lens_, k_all, v_all = _ring_case(
        page, kv, g, d, ring, lens, window, dtype)
    first = jnp.maximum(lens_ - window, 0)
    got = np.asarray(pa.paged_attention(
        q, k_pool, v_pool, table, lens_, block_tokens=block,
        interpret=True, first=first, ring=ring))
    # the oracle: each sequence's tokens in pages of their own, in order
    mp = -(-k_all.shape[1] // page)
    pad = mp * page - k_all.shape[1]

    def paged(x):
        x = np.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return jnp.asarray(x.reshape(len(lens) * mp, page, kv, d)
                           .transpose(0, 2, 1, 3), dtype)

    want = np.asarray(pa.paged_attention_reference(
        q, paged(k_all), paged(v_all),
        jnp.arange(len(lens) * mp).reshape(len(lens), mp), lens_,
        first=first))
    assert got.shape == (len(lens), kv * g, d) and np.isfinite(got).all()
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(
        want[live], got[live], atol=1e-5 if dtype == jnp.float32 else 2e-2)


def _pallas_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def test_the_read_without_a_ring_is_the_program_it_was():
    """``first`` and ``ring`` are absent from the call of every caller
    that does not ask: three prefetched scalars and no name of its own;
    a window layer's has a fourth and is ``paged_window_read``."""
    q = jnp.zeros((2, 4, 16))
    pool = jnp.zeros((9, 2, 4, 16))
    table = jnp.zeros((2, 4), jnp.int32)
    lens = jnp.asarray([3, 9], jnp.int32)
    (plain,) = _pallas_calls(jax.make_jaxpr(lambda: pa.paged_attention(
        q, pool, pool, table, lens))().jaxpr)
    (ringed,) = _pallas_calls(jax.make_jaxpr(lambda: pa.paged_attention(
        q, pool, pool, table[:, :3], lens, first=lens - 2,
        ring=3))().jaxpr)
    # table, lens, next_live, q, K, V (and, ringed, the first positions)
    assert len(plain.invars) == 6 and len(ringed.invars) == 7
    assert plain.params["grid_mapping"].num_index_operands == 3
    assert ringed.params["grid_mapping"].num_index_operands == 4
    assert "paged_window_read" not in str(plain.params)
    assert "paged_window_read" in str(ringed.params)


def test_the_append_wraps():
    pools = (jnp.ones((7, 1, 4, 128), jnp.float32),) * 2
    new = jnp.full((2, 1, 128), 7.0, jnp.float32)
    rings = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
    # position 13 is logical page 3 -> ring page 0; position 22 is
    # logical page 5 -> ring page 2
    k_out, v_out = pa.append_token_kv(*pools, new, new, rings,
                                      jnp.asarray([13, 22]), 3)
    want = np.ones((7, 1, 4, 128), np.float32)
    want[0, :, 1] = 7.0
    want[3 + 2, :, 2] = 7.0
    np.testing.assert_array_equal(np.asarray(k_out), want)
    np.testing.assert_array_equal(np.asarray(v_out), want)


def _dense_band(q, k, v, window):
    """q [N,S,H,D], k, v [N,S,KV,D] -> [N,S,H,D] by the whole masked
    scores."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = q.shape[1]
    at = jnp.arange(s)
    seen = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - window)
    scores = jnp.einsum("nshd,nthd->nhst", q, k) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    return jnp.einsum("nhst,nthd->nshd", probs, v)


@pytest.mark.parametrize("s, window", [
    pytest.param(2100, 700, id="band-crosses-three-blocks-of-five"),
    pytest.param(1500, 512, id="window-of-one-block"),
    pytest.param(600, 1000, id="window-longer-than-the-row"),
    pytest.param(300, 100, id="one-block"),
])
def test_the_band_kernel_interpreted(s, window):
    """ops/mla_prefill.py's forward with a band and two query heads a
    key head, against dense masked attention, at the kernel's own tile
    of 512; a length that is no multiple of the block."""
    rng = np.random.default_rng(0)
    n, h, kv, d = 1, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(n, s, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(n, s, kv, d)), jnp.float32)
            for _ in range(2))
    got = window_prefill_attention(
        *(jnp.moveaxis(t, 1, 2) for t in (q, k, v)), window=window,
        interpret=True)
    np.testing.assert_allclose(jnp.moveaxis(got, 2, 1),
                               _dense_band(q, k, v, window), atol=1e-5)


@pytest.mark.parametrize("s", [40, forward._SCORES_MAX_SEQ,
                               forward._SCORES_MAX_SEQ + 88])
def test_a_window_layers_prompt_is_a_band(model, s):
    """``_prefill_attention`` of a window layer at rows shorter and
    longer than ``_SCORES_MAX_SEQ`` (the masked scores, and off the
    chip the scores of a block of rows at a time) against dense masked
    attention built from the same projections."""
    mcfg, params = model
    a = params["layer_1"]["Attention_0"]
    h = jnp.asarray(np.random.default_rng(s).normal(size=(1, s, 32)),
                    jnp.float32)
    pos = jnp.arange(s)[None]
    out, k, v = forward._prefill_attention(a, mcfg, h, pos, "window")
    q, k2, v2 = forward._attention_qkv(a, mcfg, "window", h, pos)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(k2))
    want = forward._attention_out(a, mcfg, h,
                                  _dense_band(q, k2, v2, WINDOW))
    np.testing.assert_allclose(out, want, atol=1e-5)
    # and the same layer as a full one sees more
    full, _, _ = forward._prefill_attention(a, mcfg, h, pos)
    assert np.abs(np.asarray(full) - np.asarray(out))[:, WINDOW:].max() > 1e-3


# ----------------------------------------------------------------------
# the engine
# ----------------------------------------------------------------------

ICFG = InferenceConfig(batch_size=2, page_size=PAGE, max_pages_per_seq=12,
                       num_pages=25, prefill_buckets=(8, 16, 32),
                       max_new_tokens=16, decode_chunk=4)


def test_the_engine_serves_it_counts_it_and_a_successor_sees_nothing(model):
    """Through ``InferenceEngine``: a context of 46 wraps a ring of 3
    pages almost four times and every served token is the reference's
    first choice; the rings never grow; the next tenant of the slot
    answers as on a fresh engine; the three request spans and the new
    fields are there and agree with ``stats()``."""
    mcfg, params = model
    rng = np.random.default_rng(4)
    long_prompt = rng.integers(1, 64, 30).tolist()
    short = rng.integers(1, 64, 5).tolist()
    mark = len(spans.since(float("-inf")))
    eng = InferenceEngine(params, mcfg, ICFG)
    try:
        pools = [e[0].shape for e in eng._cache]
        # heads of 8, padded with zeros to whole lanes of 128
        assert pools[:2] == [(2 * RING + 1, 2, PAGE, 128)] * 2
        assert pools[2] == (25, 2, PAGE, 128)
        out = eng.generate(long_prompt, max_new_tokens=16)
        rows = np.asarray([long_prompt + out], np.int32)
        gaps, _ = ref.served_token_gaps(
            jnp.asarray(reference_logits(rows)), rows, [30], [46])
        assert gaps.max() < 1e-4
        # the slot's next tenant: a short request, then the same on a
        # fresh engine
        after = eng.generate(short, max_new_tokens=12)
        stats = eng.stats()
        assert [e[0].shape for e in eng._cache] == pools
    finally:
        eng.shutdown()
    records = spans.since(float("-inf"))[mark:]
    fresh = InferenceEngine(params, mcfg, ICFG)
    try:
        assert fresh.generate(short, max_new_tokens=12) == after
    finally:
        fresh.shutdown()
    dispatches = [r[5] for r in records if r[0] == "engine.dispatch"]
    launches = [r[5] for r in records if r[0] == "engine.prefill_launch"]
    assert all({"live_window_tokens", "window_pages_live"} <= set(f)
               for f in dispatches)
    # one slot live at a time: clipped to the window, never past a ring
    assert all(f["live_window_tokens"] == min(f["live_ctx_tokens"], WINDOW)
               and f["window_pages_live"]
               == min(-(-f["live_ctx_tokens"] // PAGE), RING)
               for f in dispatches)
    assert max(f["window_pages_live"] for f in dispatches) == RING
    # 30 tokens: positions 22.. seen, in pages 5, 6, 7 = 20..29; 5 whole
    assert [f["window_tokens_kept"] for f in launches] == [10, 5]
    assert [f["prompt_tokens"] for f in launches] == [30, 5]
    assert stats["decode_ctx_tokens_live"] == sum(
        f["live_ctx_tokens"] * f["steps"] for f in dispatches)
    assert stats["decode_window_tokens_live"] == sum(
        f["live_window_tokens"] * f["steps"] for f in dispatches)
    assert (0 < stats["decode_window_tokens_live"]
            < stats["decode_ctx_tokens_live"])
    assert stats["window_pool_tokens"] == 2 * RING * PAGE
    assert stats["window_pages_live"] == 0
    for ident in {r[3] for r in records if r[0] == "engine.queue"}:
        assert [r[0] for r in records if r[3] == ident
                and r[0].startswith("engine.") and r[0] in (
                    "engine.queue", "engine.first_token", "engine.decode")
                ] == ["engine.queue", "engine.first_token", "engine.decode"]


def test_a_model_without_window_layers_reports_no_window(model):
    from ray_tpu.models.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_layers=1,
                            n_heads=2, n_kv_heads=1, d_ff=32,
                            max_seq_len=64, dtype=jnp.float32)
    params = Transformer(cfg).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 8), jnp.int32))
    mark = len(spans.since(float("-inf")))
    eng = InferenceEngine(params, cfg, ICFG)
    try:
        eng.generate([1, 2, 3], max_new_tokens=4)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert stats["window_pool_tokens"] == stats["window_pages_live"] == 0
    assert stats["decode_window_tokens_live"] == 0
    assert stats["decode_ctx_tokens_live"] > 0
    for r in spans.since(float("-inf"))[mark:]:
        assert not {"live_window_tokens", "window_pages_live",
                    "window_tokens_kept"} & set(r[5] or {})


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_split_modes_refuse_a_window(model, mode):
    mcfg, params = model
    with pytest.raises(ValueError, match="ring of the last positions"):
        InferenceEngine(params, mcfg, InferenceConfig(), mode=mode)


def test_handoff_entry_points_refuse_a_window(model):
    mcfg, params = model
    eng = InferenceEngine(params, mcfg, ICFG)
    try:
        with pytest.raises(RuntimeError, match="ring of the last positions"):
            eng.prefill_export([1, 2, 3])
        with pytest.raises(RuntimeError, match="whole sequences"):
            eng.submit_stream_from_kv({"prompt": [1], "k": None, "v": None,
                                       "first_token": 0})
        with pytest.raises(ValueError, match="ring of the last positions"):
            inference.prefill_batch(params, mcfg,
                                    jnp.zeros((1, 8), jnp.int32))
    finally:
        eng.shutdown()
