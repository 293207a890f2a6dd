"""Latent attention (MLA) through the serving path: a prompt runs the
published, expanded form, every later token the absorbed form against
the paged latent cache (one row a token: the normed latent and the
rotated key all heads share), and both are held, on LOGITS, to the
benchmark's plain reference (benchmark/reference/openpangu_ultra.py:
float32, expanded attention only, no cache, a loop over experts). The
read kernel, interpreted, against the gather oracle at the geometries
the K/V read is tested at; the append's one cell; a parking page full
of NaN."""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights_openpangu_ultra as W  # noqa: E402
from benchmark.reference import openpangu_ultra as ref  # noqa: E402
from ray_tpu.models import decoder_forward as forward  # noqa: E402
from ray_tpu.models import inference  # noqa: E402
from ray_tpu.models.decoder import LayerSpec  # noqa: E402
from ray_tpu.models.inference import (InferenceConfig,  # noqa: E402
                                      InferenceEngine)
from ray_tpu.ops import paged_attention as pa  # noqa: E402

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "vocab_size": 96,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "intermediate_size": 80,
    "router_width": 16, "experts_held": [4, 12], "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "rms_norm_eps": 1e-5, "rope_theta": 25600000,
    "tie_word_embeddings": False,
    "run": {"dtype": "float32", "param_dtype": "float32"},
}
SEED = 11


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
    assert mcfg.layers == (LayerSpec("latent", "dense"),
                           LayerSpec("latent", "experts"),
                           LayerSpec("latent", "experts"))
    assert mcfg.latent_layers == (0, 1, 2) and mcfg.moe_layers == (1, 2)
    assert not mcfg.kv_layers and not mcfg.state_layers
    # a row of 32 + 8 numbers, held as one whole lane of 128
    assert mcfg.latent_width == 128 and mcfg.routed_scale == 2.5
    assert sorted(params["layer_0"]) == [
        "LatentAttention_0", "MLP_0", "PostNorm_0", "PostNorm_1",
        "RMSNorm_0", "RMSNorm_1"]
    assert sorted(params["layer_1"]["LatentAttention_0"]) == [
        "kv_norm", "q_norm", "w_kva", "w_kvb", "w_qa", "w_qb", "wo"]


def _decode_through_the_cache(mcfg, params, row, plen, bucket, page=4):
    """Prefill ``row[:plen]`` in a bucket, then decode the rest of the
    row teacher-forced, through the paged latent cache. Returns the
    logits of every position from plen - 1 on."""
    n_pages = -(-len(row) // page)
    icfg = InferenceConfig(batch_size=2, page_size=page,
                           max_pages_per_seq=n_pages,
                           num_pages=2 * n_pages + 1,
                           prefill_buckets=(bucket,))
    cache = forward.init_cache(mcfg, icfg)
    assert [e[0].shape for e in cache] == [
        (2 * n_pages + 1, 1, page, mcfg.latent_width)] * 3
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :plen] = row[:plen]
    # slot 1 of 2; pages in an order of their own
    pages = np.arange(1, 1 + n_pages)[::-1].copy()
    n_prog = -(-bucket // page)
    launch = np.full((1, n_prog), 2 * n_pages, np.int32)   # parking
    launch[0, :min(n_prog, n_pages)] = pages[:n_prog]
    logits, cache, _ = forward.prefill_cached(
        params, mcfg, cache, jnp.asarray(toks), jnp.asarray([plen]),
        jnp.asarray([1]), jnp.asarray(launch), jnp.asarray([True]))
    got = [np.asarray(logits)[0]]
    table = np.full((2, n_pages), 2 * n_pages, np.int32)
    table[1] = pages
    for t in range(plen, len(row)):
        step, cache, _ = forward.decode_step_cached(
            params, mcfg, jnp.asarray([0, row[t]], jnp.int32), cache,
            jnp.asarray(table), jnp.asarray([0, t], jnp.int32),
            jnp.asarray([False, True]))
        got.append(np.asarray(step)[1])
    return np.stack(got)


@pytest.mark.parametrize("plen, bucket", [(1, 16), (11, 16), (16, 16)])
def test_absorbed_decode_is_expanded_prefill_is_the_reference(model, plen,
                                                              bucket):
    """Token by token: every logit of the prompt from the expanded
    form, then every logit of 8 more positions from the absorbed form
    against the cache, equal the reference's full forward."""
    mcfg, params = model
    row = np.random.default_rng(plen).integers(1, 96, plen + 8)
    want = reference_logits(row[None])[0]
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :plen] = row[:plen]
    x, _, _ = forward._prefill_hidden(params, mcfg, jnp.asarray(toks),
                                      jnp.asarray([plen]))
    whole = np.asarray(forward._head(params, mcfg, x, "bsd,vd->bsv"))[0]
    np.testing.assert_allclose(whole[:plen], want[:plen], atol=2e-4)
    got = _decode_through_the_cache(mcfg, params, row, plen, bucket)
    np.testing.assert_allclose(got, want[plen - 1:], atol=2e-4)


def test_a_long_prompt_never_holds_its_scores(model, monkeypatch):
    """Past 512 positions the expanded form goes a block of query rows
    at a time (on the chip: the kernel of ops/mla_prefill.py), and a
    launch of many positions a group of heads at a time (here 2 of the
    4: at the published widths 32 of 128); what the layer keeps and
    what decode then reads are the same rows."""
    mcfg, params = model
    monkeypatch.setattr(forward, "_LATENT_GROUP_NUMBERS", 2 * 640)
    row = np.random.default_rng(3).integers(1, 96, 600 + 3)
    want = reference_logits(row[None])[0]
    got = _decode_through_the_cache(mcfg, params, row, 600, 640, page=64)
    np.testing.assert_allclose(got, want[599:], atol=5e-4)


def test_the_prefill_kernel_interpreted():
    """ops/mla_prefill.py against plain causal attention, keys and
    values of two widths, a length that is no multiple of the block."""
    from ray_tpu.ops.mla_prefill import mla_prefill_attention

    rng = np.random.default_rng(0)
    n, h, s, dk, dv = 2, 3, 700, 24, 16
    q, k = (jnp.asarray(rng.normal(size=(n, h, s, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(n, h, s, dv)), jnp.float32)
    got = mla_prefill_attention(q, k, v, scale=0.2, interpret=True)
    scores = jnp.einsum("nhsd,nhtd->nhst", q, k) * 0.2
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    want = jnp.einsum("nhst,nhtd->nhsd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(got, want, atol=1e-5)


def _latent_case(page, g, w, mp, lens, dtype, seed=0):
    """q, pool, table, lens with physical page 0 as the parking page,
    full of NaN, as tests/test_inference.py makes its K/V cases."""
    rng = np.random.default_rng(seed)
    b, pool = len(lens), 1 + len(lens) * mp
    q = jnp.asarray(rng.normal(size=(b, g, w)), dtype)
    rows = rng.normal(size=(pool, 1, page, w)).astype(np.float32)
    rows[0] = np.nan
    table = 1 + rng.permutation(pool - 1).reshape(b, mp).astype(np.int32)
    for i, n in enumerate(lens):
        table[i, -(-n // page):] = 0
    return (q, jnp.asarray(rows, dtype), jnp.asarray(table),
            jnp.asarray(lens, jnp.int32))


# PR 30's eight geometries of the K/V read, one pool in place of two:
# (page, query heads, row width, value width, pages a sequence, tokens
# a block, lengths, dtype)
@pytest.mark.parametrize("page, g, w, vw, mp, block, lens, dtype", [
    pytest.param(8, 8, 32, 24, 4, 16, [5, 17, 32], jnp.float32,
                 id="pages-of-8"),
    pytest.param(4, 4, 16, 8, 7, 8, [0, 1, 4, 8, 9, 28], jnp.float32,
                 id="pages-of-4-table-not-a-multiple-of-the-block"),
    pytest.param(16, 8, 128, 96, 5, 32, [0, 16, 32, 33, 80],
                 jnp.bfloat16, id="pages-of-16-bf16"),
    pytest.param(128, 8, 128, 64, 3, 256, [0, 128, 256, 300, 384],
                 jnp.bfloat16, id="pages-of-128-bf16"),
    pytest.param(16, 8, 128, 128, 20, pa.BLOCK_TOKENS,
                 [320, 257, 256, 0, 1], jnp.bfloat16,
                 id="the-default-block"),
    pytest.param(4, 4, 16, 8, 3, pa.BLOCK_TOKENS, [12, 0, 5],
                 jnp.float32, id="table-shorter-than-a-block"),
    pytest.param(4, 4, 16, 8, 3, 8, [13, 40, 12], jnp.float32,
                 id="length-past-the-table"),
    pytest.param(4, 4, 16, 8, 3, 8, [0, 0], jnp.float32, id="all-idle"),
])
def test_read_kernel_matches_the_gather(page, g, w, vw, mp, block, lens,
                                        dtype):
    """Live rows equal the gather's over the same pool read as keys
    (whole rows, scores over a width that is not the row's) and as
    values (the first ``vw`` columns); the parking page's NaN reaches
    no row; an idle slot's row is finite."""
    q, rows, table, lens = _latent_case(page, g, w, mp, lens, dtype)
    want = pa.paged_attention_reference(
        q, rows, rows[..., :vw], table, lens, score_width=w - 3)
    got = np.asarray(pa.paged_latent_attention(
        q, rows, table, lens, score_width=w - 3, value_width=vw,
        block_tokens=block, interpret=True))
    assert got.shape == (len(lens), g, vw) and np.isfinite(got).all()
    live = np.asarray(lens) > 0
    # bfloat16: the kernel rounds the probabilities to the pool's type
    # for the product with the values
    np.testing.assert_allclose(
        np.asarray(want)[live], got[live],
        atol=1e-5 if dtype == jnp.float32 else 2e-2)


def test_the_append_touches_one_cell():
    pool = jnp.ones((9, 1, 4, 128), jnp.float32)
    new = jnp.full((2, 1, 128), 7.0, jnp.float32)
    table = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    (out,) = pa.append_token((pool,), (new,), table, jnp.asarray([5, 2]))
    want = np.ones((9, 1, 4, 128), np.float32)
    want[1, :, 1] = 7.0
    want[2, :, 2] = 7.0
    np.testing.assert_array_equal(np.asarray(out), want)


def test_a_nan_parking_page_harms_nothing(model):
    """Idle slots append to the parking page and every table entry past
    a length names it: with NaN there from the start, a live slot's
    logits are what they are with zeros there."""
    mcfg, params = model
    icfg = InferenceConfig(batch_size=3, page_size=4, max_pages_per_seq=4,
                           num_pages=9, prefill_buckets=(8,))
    rng = np.random.default_rng(2)

    def run(parking):
        cache = tuple(
            (e[0].at[:8].set(jnp.asarray(
                rng.normal(size=e[0][:8].shape), jnp.float32))
             .at[8].set(parking),) for e in forward.init_cache(mcfg, icfg))
        table = jnp.asarray([[8] * 4, [0, 1, 8, 8], [8] * 4], jnp.int32)
        logits, _, _ = forward.decode_step_cached(
            params, mcfg, jnp.asarray([0, 5, 0], jnp.int32), cache, table,
            jnp.asarray([0, 6, 0], jnp.int32),
            jnp.asarray([False, True, False]))
        return np.asarray(logits)[1]

    rng = np.random.default_rng(2)
    clean = run(0.0)
    rng = np.random.default_rng(2)
    np.testing.assert_array_equal(run(jnp.nan), clean)
    assert np.isfinite(clean).all()


def test_the_engine_serves_it_and_refuses_the_handoff(model):
    mcfg, params = model
    icfg = InferenceConfig(batch_size=2, page_size=4, max_pages_per_seq=8,
                           num_pages=20, prefill_buckets=(8, 16),
                           max_new_tokens=6, decode_chunk=2)
    for mode in ("prefill", "decode"):
        with pytest.raises(ValueError, match="latent rows"):
            InferenceEngine(params, mcfg, icfg, mode=mode)
    with pytest.raises(ValueError, match="latent rows"):
        inference.prefill_batch(params, mcfg, jnp.zeros((1, 8), jnp.int32))
    eng = InferenceEngine(params, mcfg, icfg)
    try:
        with pytest.raises(RuntimeError, match="latent rows"):
            eng.prefill_export([1, 2, 3])
        prompt = [7, 3, 90, 41, 5]
        out = eng.generate(prompt, max_new_tokens=6)
        stats = eng.stats()
    finally:
        eng.shutdown()
    want = reference_logits(np.asarray([prompt + out], np.int32))[0]
    assert out == [int(np.argmax(want[t])) for t in range(4, 10)]
    # three layers, a row of 128 float32 each
    assert stats["latent_bytes_per_token"] == 3 * 128 * 4
    assert stats["latent_pool_bytes"] == 3 * 20 * 4 * 128 * 4
    assert stats["state_bytes"] == 0 and stats["moe_picks_total"] > 0
