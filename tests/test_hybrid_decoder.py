"""A decoder described layer by layer (models/decoder.py) through the
serving engine: gated attention without positions, delta-rule layers
with their state beside the paged pool, experts with a held share, an
untied head. Held to the benchmark's plain reference
(benchmark/reference/solar_open2.py: float32, token by token, a loop
over experts) on LOGITS, prefill and then decode through both caches;
and the dense decoder's programs are what they were."""

import dataclasses
import hashlib
import os
import re
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights_solar_open2 as W  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402
from ray_tpu._private import spans  # noqa: E402
from ray_tpu.models import inference  # noqa: E402
from ray_tpu.models.decoder import (DecoderConfig, LayerSpec,  # noqa: E402
                                    describe)
from ray_tpu.models.inference import (InferenceConfig,  # noqa: E402
                                      InferenceEngine)
from ray_tpu.models.transformer import (Transformer,  # noqa: E402
                                        TransformerConfig)
from ray_tpu.ops.paged_attention import write_prefill_kv  # noqa: E402

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 96, "num_hidden_layers": 4,
    "gqa_layers": [0, 4],
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "kda_gate_rank": 16, "router_width": 16, "experts_held": [0, 8],
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "use_rope": False, "use_gqa_gate": True, "tie_word_embeddings": False,
    "run": {"dtype": "float32", "param_dtype": "float32"},
}
SEED = 5


def description(config):
    s = W.dims(config)
    layers = tuple(LayerSpec("attention" if i in s["gqa"] else "delta_rule",
                             "experts") for i in range(s["layers"]))
    return DecoderConfig(layers=layers, **W.decoder_kwargs(config))


@pytest.fixture(scope="module")
def model():
    mcfg = description(TINY)
    params = jax.jit(lambda k: W.init_params(TINY, k, jnp.float32))(
        W.seed_key(SEED))
    return mcfg, params


def reference_logits(rows):
    return np.asarray(ref.teacher_forced_logits(
        TINY, SEED, np.asarray(rows, np.int32), "f32", jnp.float32))


def test_the_description_of_the_dense_decoder():
    cfg = TransformerConfig.tiny()
    d = describe(cfg)
    assert d.layers == (LayerSpec("attention", "dense"),) * cfg.n_layers
    assert d.kv_layers == (0, 1) and not d.state_layers and not d.moe_layers
    assert d.tie_embeddings and d.rope_theta == cfg.rope_theta
    assert describe(d) is d
    with pytest.raises(ValueError, match="DecoderConfig"):
        describe(TransformerConfig(moe=True))
    with pytest.raises(ValueError, match="unknown layer kinds"):
        LayerSpec("conv", "dense")
    with pytest.raises(ValueError, match="experts_held"):
        DecoderConfig(vocab_size=8, d_model=8, n_heads=1, n_kv_heads=1,
                      head_dim=8, layers=(LayerSpec("attention", "experts"),),
                      n_routed_experts=4, experts_held=(2, 6),
                      experts_per_token=2)


def test_kinds_of_layers(model):
    mcfg, _ = model
    assert mcfg.kv_layers == (0,) and mcfg.state_layers == (1, 2, 3)
    assert mcfg.moe_layers == (0, 1, 2, 3) and mcfg.n_experts_held == 8


@pytest.mark.parametrize("plen", [1, 11, 16])
def test_prefill_then_decode_logits_match_the_reference(model, plen):
    """One row: a prompt of ``plen`` in a bucket of 16, then decode
    steps teacher-forced on the row, through a page pool for layer 0
    and a state for layers 1-3. Every logit of every position."""
    mcfg, params = model
    rng = np.random.default_rng(plen)
    row = rng.integers(1, 96, 24)
    want = reference_logits(row[None])[0]
    toks = np.zeros((1, 16), np.int32)
    toks[0, :plen] = row[:plen]
    plens = jnp.asarray([plen])
    x, kept, counts = inference._prefill_hidden(
        params, mcfg, jnp.asarray(toks), plens)
    got = np.asarray(inference._head(params, mcfg, x, "bsd,vd->bsv"))[0]
    np.testing.assert_allclose(got[:plen], want[:plen], atol=2e-4)
    assert int(counts.sum()) <= plen * 4 * 4       # picks of valid tokens
    page, n_pages = 4, 8
    pool = jnp.zeros((n_pages + 1, 2, page, 16), jnp.float32)
    pages = jnp.arange(4)
    kp, vp = write_prefill_kv(pool, pool, kept[0][0][0], kept[0][1][0], pages)
    table = jnp.arange(n_pages)[None]
    state = tuple(kept[i] for i in mcfg.state_layers)
    kp, vp = (kp,), (vp,)
    live = jnp.asarray([True])
    for pos in range(plen, 24):
        logits, kp, vp, state, _ = inference._decode_step(
            params, mcfg, jnp.asarray(row[pos:pos + 1], jnp.int32), kp, vp,
            table, jnp.asarray([pos]), state, live)
        np.testing.assert_allclose(np.asarray(logits)[0], want[pos],
                                   atol=2e-4, err_msg=f"position {pos}")


def test_rows_of_a_padded_launch_do_not_disturb_each_other(model):
    mcfg, params = model
    rng = np.random.default_rng(1)
    rows = rng.integers(1, 96, (3, 16)).astype(np.int32)
    plens = jnp.asarray([16, 5, 9])
    x, kept, _ = inference._prefill_hidden(params, mcfg, jnp.asarray(rows),
                                           plens)
    for r, ln in enumerate((16, 5, 9)):
        alone = rows[r:r + 1].copy()
        alone[0, ln:] = 0
        x1, kept1, _ = inference._prefill_hidden(
            params, mcfg, jnp.asarray(alone), jnp.asarray([ln]))
        np.testing.assert_allclose(np.asarray(x)[r, :ln],
                                   np.asarray(x1)[0, :ln], atol=1e-5)
        for i in mcfg.state_layers:      # state and tail at the length
            for a, b in zip(kept[i], kept1[i]):
                np.testing.assert_allclose(np.asarray(a)[r],
                                           np.asarray(b)[0], atol=1e-5)


def test_gated_attention_without_positions(model):
    """Layer 0's mixer alone against the reference's."""
    mcfg, params = model
    a = params["layer_0"]["Attention_0"]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 12, 64)),
                    jnp.float32)
    out, k, v = inference._prefill_attention(a, mcfg, h, jnp.arange(12)[None])
    s = W.dims(TINY)
    for r in range(2):
        want = ref.gated_attention(a, h[r], s, "f32")
        np.testing.assert_allclose(np.asarray(out)[r], np.asarray(want),
                                   atol=1e-5)
    assert k.shape == v.shape == (2, 12, 2, 16)
    # no rotation: a key does not depend on its position
    _, k2, _ = inference._prefill_attention(a, mcfg, h[:, ::-1],
                                            jnp.arange(12)[None])
    np.testing.assert_allclose(np.asarray(k2)[:, ::-1], np.asarray(k),
                               atol=1e-6)


def test_long_rows_never_hold_their_scores(model):
    """Past 512 positions attention goes a block of query rows at a
    time (off the chip; the flash kernel on it): same numbers."""
    mcfg, params = model
    a = params["layer_0"]["Attention_0"]
    h = jnp.asarray(np.random.default_rng(3).normal(size=(1, 640, 64)),
                    jnp.float32)
    pos = jnp.arange(640)[None]
    out, _, _ = inference._prefill_attention(a, mcfg, h, pos)
    want = ref.gated_attention(a, h[0], W.dims(TINY), "f32")
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(want),
                               atol=2e-5)
    text = jax.jit(lambda h: inference._prefill_attention(
        a, mcfg, h, pos)[0]).lower(h).as_text()
    assert "640x640" not in text


@pytest.mark.parametrize("n", [1, 2])
def test_delta_rule_segments_carry_state_and_tail(model, monkeypatch, n):
    mcfg, params = model
    a = params["layer_1"]["DeltaRule_0"]
    h = jnp.asarray(np.random.default_rng(4).normal(size=(n, 256, 64)),
                    jnp.float32)
    plens = jnp.asarray([200, 256][:n])
    whole = inference._prefill_delta_rule(a, mcfg, h, plens)
    monkeypatch.setattr(inference, "_DELTA_RULE_SEGMENT", 64 * n)
    parts = inference._prefill_delta_rule(a, mcfg, h, plens)
    for got, want in zip(parts, whole):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
    want = ref.delta_rule(a, h[0, :200], W.dims(TINY), "f32", 1e-5)
    np.testing.assert_allclose(np.asarray(parts[0])[0, :200],
                               np.asarray(want), atol=5e-5)


@pytest.fixture(scope="module")
def served(model):
    """Five requests through an engine of three slots (two buckets,
    chunks of up to four steps): slots are reused, and idle slots run
    beside live ones. -> (requests, outputs, stats, spans of the run)."""
    mcfg, params = model
    icfg = InferenceConfig(batch_size=3, page_size=4, max_pages_per_seq=16,
                           num_pages=40, prefill_buckets=(8, 32),
                           max_new_tokens=8, decode_chunk=4)
    t0 = spans.time.perf_counter()
    eng = InferenceEngine(params, mcfg, icfg)
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(1, 96, n).tolist(), m)
            for n, m in [(5, 6), (20, 9), (8, 3), (31, 7), (3, 12)]]
    try:
        first = eng.submit(*reqs[0]).result(300)    # alone: two idle slots
        outs = [first] + [f.result(300) for f in
                          [eng.submit(p, m) for p, m in reqs[1:]]]
        stats = eng.stats()
    finally:
        eng.shutdown()
    return reqs, outs, stats, spans.since(t0), eng


def test_engine_tokens_are_the_references_argmax(served):
    """Served tokens against the reference's full forward: the gap of a
    served token's logit under the reference's best is nil, for a slot's
    first tenant and for its second (no state survives)."""
    reqs, outs, stats, _, _ = served
    assert stats["max_concurrent"] == 3
    rows = np.zeros((len(reqs), 48), np.int32)
    plens, totals = [], []
    for i, ((p, m), o) in enumerate(zip(reqs, outs)):
        assert len(o) == m
        rows[i, :len(p) + m] = p + o
        plens.append(len(p))
        totals.append(len(p) + m)
    logits = ref.teacher_forced_logits(TINY, SEED, rows, "f32", jnp.float32)
    gaps, _ = ref.served_token_gaps(logits, rows, plens, totals)
    assert gaps.max() < 1e-4, gaps.max()


def test_engine_counters_agree_with_the_ring(served):
    reqs, _, stats, ring, _ = served
    counted = [s[5] for s in ring if s[0] in ("engine.prefill_launch",
                                              "engine.deliver")
               and "moe_picks_total" in s[5]]
    launches = [s[5] for s in ring if s[0] == "engine.prefill_launch"]
    assert all("moe_picks_local" in f and "moe_expert_load_max" in f
               and "prompt_lens" in f for f in launches)
    for key in ("moe_picks_total", "moe_picks_local"):
        assert sum(f[key] for f in counted) == stats[key] > 0
    np.testing.assert_array_equal(
        np.sum([f["moe_load_by_expert"] for f in counted], axis=0),
        stats["moe_load_by_expert"])
    assert sum(stats["moe_load_by_expert"]) == stats["moe_picks_local"]
    # every prompt token picks experts_per_token experts in each layer
    prompt = sum(len(p) for p, _ in reqs)
    assert sum(f["moe_picks_total"] for f in launches) == prompt * 4 * 4
    # 8 of 16 experts held, routing near uniform
    assert 0.35 < stats["moe_picks_local"] / stats["moe_picks_total"] < 0.65
    dispatches = [s[5] for s in ring if s[0] == "engine.dispatch"]
    assert all(1 <= f["state_slots_live"] <= 3 for f in dispatches)
    assert dispatches[0]["state_slots_live"] == 1
    # three layers x three slots x (4 heads x 16 x 16 float32 + 3 x 192)
    assert stats["state_bytes"] == 3 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert stats["pool_tokens"] == 39 * 4


def test_pools_only_for_layers_with_keys_and_values(served):
    eng = served[4]
    assert len(eng._k_pages) == len(eng._v_pages) == 1
    assert len(eng._state) == 3
    assert eng._state[0][0].shape == (3, 4, 16, 16)
    assert eng._state[0][0].dtype == jnp.float32


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_split_modes_refuse_recurrent_state(model, mode):
    mcfg, params = model
    with pytest.raises(ValueError, match="recurrent state"):
        InferenceEngine(params, mcfg, InferenceConfig(), mode=mode)


def test_handoff_entry_points_refuse_recurrent_state(served, model):
    mcfg, params = model
    eng = InferenceEngine(params, mcfg, InferenceConfig(
        batch_size=1, page_size=4, max_pages_per_seq=4, num_pages=8,
        prefill_buckets=(8,), max_new_tokens=2, decode_chunk=1))
    try:
        with pytest.raises(RuntimeError, match="recurrent state"):
            eng.prefill_export([1, 2, 3])
        with pytest.raises(ValueError, match="recurrent state"):
            inference.prefill_batch(params, mcfg, jnp.zeros((1, 8),
                                                            jnp.int32))
    finally:
        eng.shutdown()


def test_served_through_serve_run(model):
    """The normal path: serve.run(build_llm_app(params, description,
    engine config)), a request in and its tokens out."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app

    mcfg, params = model
    icfg = InferenceConfig(batch_size=2, page_size=4, max_pages_per_seq=8,
                           num_pages=20, prefill_buckets=(8, 16),
                           max_new_tokens=6, decode_chunk=2)
    ray_tpu.shutdown()
    ray_tpu.init(num_workers=2)
    try:
        handle = serve.run(build_llm_app(params, mcfg, icfg))
        prompt = [7, 3, 90, 41, 5]
        out = ray_tpu.get(handle.generate.remote(prompt, 6), timeout=300)
        stats = ray_tpu.get(handle.engine_stats.remote(), timeout=60)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    assert len(out) == 6 and stats["moe_picks_total"] > 0
    row = np.asarray([prompt + out], np.int32)
    want = reference_logits(row)[0]
    assert out == [int(np.argmax(want[t])) for t in range(4, 10)]


# ----------------------------------------------------------------------
# the dense decoder's programs are what they were
# ----------------------------------------------------------------------

# sha256 (16 hex digits) of the programs' lowered text at the commit
# before the layer-by-layer description (84b28e3), locations and the
# module's name stripped: prefill, split and export are still the
# parent's to the letter. The two decode programs are not: their
# ``append_token_kv`` is a Pallas kernel that writes one cell a slot
# in place (PR 28; before it a one-hot product over the whole pool,
# PR 27's with the parking cell overwritten), lowered here in interpret
# mode; nothing of the description shows in them either. Their hashes
# are this commit's.
PARENT_PROGRAMS = {
    "prefill_b8": "ab1c7c908e0a4268",
    "prefill_b16": "d5cefb7ddf632494",
    "split_packed": "d1b0bdb325ef069d",
    "export_b8": "b3bca5d09d7192d8",
    "export_b16": "9f3f5b3beffab62a",
}
WITH_THE_IN_PLACE_APPEND = {
    "decode_n1": "5204b95cc5dd9c5c",
    "decode_n2": "02b49d64d90d4998",
}


def lowered_programs(params, cfg):
    icfg = InferenceConfig(batch_size=3, page_size=4, max_pages_per_seq=8,
                           num_pages=32, prefill_buckets=(8, 16),
                           max_new_tokens=8, decode_chunk=2)
    eng = InferenceEngine(params, cfg, icfg)
    out = {}

    def keep(name, lowered):
        text = re.sub(r"loc\([^)]*\)", "", lowered.as_text())
        text = "\n".join(l for l in text.splitlines()
                         if not l.startswith("#loc"))
        text = re.sub(r"module @\S+", "module", text)
        out[name] = hashlib.sha256(text.encode()).hexdigest()[:16]

    try:
        for b, fn in eng._prefill_many.items():
            packed = jnp.zeros((eng._prefill_rows[b], 2 + b + -(-b // 4)),
                               jnp.int32)
            keep(f"prefill_b{b}", fn.lower(eng.params, packed, eng._k_pages,
                                           eng._v_pages, eng._dev_toks))
        table = jnp.zeros((3, 8), jnp.int32)
        lens = jnp.zeros((3,), jnp.int32)
        for n, fn in eng._decode_chunks.items():
            keep(f"decode_n{n}", fn.lower(eng.params, eng._dev_toks,
                                          eng._k_pages, eng._v_pages, table,
                                          lens))
        keep("split_packed", eng._split_packed.lower(
            jnp.zeros((3, 9), jnp.int32)))
        for b, fn in eng._export_jits.items():
            keep(f"export_b{b}", fn.lower(eng.params,
                                          jnp.zeros((1, b), jnp.int32)))
    finally:
        eng.shutdown()
    return out


@pytest.fixture(scope="module")
def dense():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=64, max_seq_len=128,
                            dtype=jnp.float32)
    params = Transformer(cfg).init(jax.random.PRNGKey(0),
                                   jnp.ones((1, 8), jnp.int32))
    return cfg, params["params"]


def test_dense_decoder_programs_lower_to_the_parents_text(dense):
    cfg, params = dense
    assert lowered_programs(params, cfg) == {**PARENT_PROGRAMS,
                                             **WITH_THE_IN_PLACE_APPEND}


def test_untied_head_is_the_only_difference_it_makes(dense):
    """The same decoder described with an untied head: given the
    embedding as ``lm_head`` it computes what the tied one computes;
    its programs take one more argument and are otherwise the same."""
    cfg, params = dense
    untied = dataclasses.replace(describe(cfg), tie_embeddings=False)
    with_head = {**params, "lm_head": params["embedding"]}
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 64, (2, 8)),
                       jnp.int32)
    tied = inference.prefill_batch(params, cfg, toks)
    free = inference.prefill_batch(with_head, untied, toks)
    for a, b in zip(tied, free):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    other = {**with_head, "lm_head": params["embedding"] * 2.0}
    twice = inference.prefill_batch(other, untied, toks)
    np.testing.assert_allclose(np.asarray(twice[0]),
                               2.0 * np.asarray(tied[0]), atol=1e-5)
