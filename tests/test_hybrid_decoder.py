"""A decoder described layer by layer (models/decoder.py) through the
serving engine: gated attention without positions, delta-rule layers
with their state beside the paged pool, experts with a held share, an
untied head. Held to the benchmark's plain reference
(benchmark/reference/solar_open2.py: float32, token by token, a loop
over experts) on LOGITS, prefill and then decode through both caches;
and the dense decoder's programs are what they were."""

import dataclasses
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights_openpangu_ultra as WL  # noqa: E402
from benchmark import weights_solar_open2 as W  # noqa: E402
from benchmark.reference import openpangu_ultra as ref_latent  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402
from ray_tpu._private import spans  # noqa: E402
from ray_tpu.models import decoder_forward as forward  # noqa: E402
from ray_tpu.models import inference  # noqa: E402
from ray_tpu.models.decoder import (DecoderConfig, LayerSpec,  # noqa: E402
                                    describe)
from ray_tpu.models.inference import (InferenceConfig,  # noqa: E402
                                      InferenceEngine)
from ray_tpu.models.transformer import (Transformer,  # noqa: E402
                                        TransformerConfig)

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
        LayerSpec("selective_scan", "dense")
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
    steps teacher-forced on the row, through the cache (a page pool
    for layer 0, a state for layers 1-3). Every logit of every
    position."""
    mcfg, params = model
    rng = np.random.default_rng(plen)
    row = rng.integers(1, 96, 24)
    want = reference_logits(row[None])[0]
    toks = np.zeros((1, 16), np.int32)
    toks[0, :plen] = row[:plen]
    plens = jnp.asarray([plen])
    x, _, counts = forward._prefill_hidden(params, mcfg, jnp.asarray(toks),
                                           plens)
    got = np.asarray(forward._head(params, mcfg, x, "bsd,vd->bsv"))[0]
    np.testing.assert_allclose(got[:plen], want[:plen], atol=2e-4)
    assert int(counts.sum()) <= plen * 4 * 4       # picks of valid tokens
    n_pages = 8
    cache = forward.init_cache(mcfg, InferenceConfig(
        batch_size=1, page_size=4, num_pages=n_pages + 1))
    last, cache, _ = forward.prefill_cached(
        params, mcfg, cache, jnp.asarray(toks), plens, jnp.asarray([0]),
        jnp.arange(4)[None], jnp.asarray([True]))
    np.testing.assert_allclose(np.asarray(last)[0], want[plen - 1],
                               atol=2e-4)
    table = jnp.arange(n_pages)[None]
    live = jnp.asarray([True])
    for pos in range(plen, 24):
        logits, cache, _ = forward.decode_step_cached(
            params, mcfg, jnp.asarray(row[pos:pos + 1], jnp.int32), cache,
            table, jnp.asarray([pos]), live)
        np.testing.assert_allclose(np.asarray(logits)[0], want[pos],
                                   atol=2e-4, err_msg=f"position {pos}")


def test_rows_of_a_padded_launch_do_not_disturb_each_other(model):
    mcfg, params = model
    rng = np.random.default_rng(1)
    rows = rng.integers(1, 96, (3, 16)).astype(np.int32)
    plens = jnp.asarray([16, 5, 9])
    x, kept, _ = forward._prefill_hidden(params, mcfg, jnp.asarray(rows),
                                           plens)
    for r, ln in enumerate((16, 5, 9)):
        alone = rows[r:r + 1].copy()
        alone[0, ln:] = 0
        x1, kept1, _ = forward._prefill_hidden(
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
    out, k, v = forward._prefill_attention(a, mcfg, h, jnp.arange(12)[None])
    s = W.dims(TINY)
    for r in range(2):
        want = ref.gated_attention(a, h[r], s, "f32")
        np.testing.assert_allclose(np.asarray(out)[r], np.asarray(want),
                                   atol=1e-5)
    assert k.shape == v.shape == (2, 12, 2, 16)
    # no rotation: a key does not depend on its position
    _, k2, _ = forward._prefill_attention(a, mcfg, h[:, ::-1],
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
    out, _, _ = forward._prefill_attention(a, mcfg, h, pos)
    want = ref.gated_attention(a, h[0], W.dims(TINY), "f32")
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(want),
                               atol=2e-5)
    text = jax.jit(lambda h: forward._prefill_attention(
        a, mcfg, h, pos)[0]).lower(h).as_text()
    assert "640x640" not in text


@pytest.mark.parametrize("n", [1, 2])
def test_delta_rule_segments_carry_state_and_tail(model, monkeypatch, n):
    mcfg, params = model
    a = params["layer_1"]["DeltaRule_0"]
    h = jnp.asarray(np.random.default_rng(4).normal(size=(n, 256, 64)),
                    jnp.float32)
    plens = jnp.asarray([200, 256][:n])
    whole = forward._prefill_delta_rule(a, mcfg, h, plens)
    monkeypatch.setattr(forward, "_DELTA_RULE_SEGMENT", 64 * n)
    parts = forward._prefill_delta_rule(a, mcfg, h, plens)
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


def test_the_cache_has_one_entry_a_layer(served, model):
    """``init_cache``: pages for the layer with keys and values, a
    float32 state and a convolution tail a slot for the other three;
    the engine holds exactly that, and ``state_bytes`` is its leaves'."""
    mcfg, _ = model
    _, _, stats, _, eng = served
    cache = forward.init_cache(mcfg, eng.cfg)
    assert len(cache) == len(mcfg.layers) == 4
    assert all(len(entry) == 2 for entry in cache)
    for pool in cache[0]:
        # heads of 16, padded with zeros to whole lanes of 128
        assert pool.shape == (40, 2, 4, 128) and pool.dtype == jnp.float32
    for i in mcfg.state_layers:
        state, tail = cache[i]
        assert state.shape == (3, 4, 16, 16) and state.dtype == jnp.float32
        assert tail.shape == (3, 3, 4 * 48) and tail.dtype == mcfg.dtype
    assert cache[0][0] is not cache[0][1]       # donated: two buffers
    shapes = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (a.shape, a.dtype), tree)
    assert shapes(eng._cache) == shapes(cache)
    assert stats["state_bytes"] == sum(
        a.nbytes for i in mcfg.state_layers for a in cache[i])
    dense = forward.init_cache(describe(TransformerConfig.tiny()), eng.cfg)
    assert [tuple(a.shape for a in entry) for entry in dense] == \
        [((40, 2, 4, 128),) * 2] * 2


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
# one family of programs, whatever the description
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def dense():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=64, max_seq_len=128,
                            dtype=jnp.float32)
    params = Transformer(cfg).init(jax.random.PRNGKey(0),
                                   jnp.ones((1, 8), jnp.int32))
    return cfg, params["params"]


# the third family: latent attention in every layer, sandwich norms, a
# dense feed-forward then expert layers with a scaled router
TINY_LATENT = {
    "hidden_size": 64, "num_attention_heads": 4, "vocab_size": 96,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "intermediate_size": 80,
    "router_width": 16, "experts_held": [0, 8], "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "rms_norm_eps": 1e-5, "rope_theta": 25600000,
    "tie_word_embeddings": False,
    "run": {"dtype": "float32", "param_dtype": "float32"},
}


@pytest.fixture(scope="module")
def latent():
    params = jax.jit(lambda k: WL.init_params(TINY_LATENT, k, jnp.float32))(
        WL.seed_key(SEED))
    return WL.description(TINY_LATENT), params


def latent_logits(rows):
    return np.asarray(ref_latent.teacher_forced_logits(
        TINY_LATENT, SEED, np.asarray(rows, np.int32), "f32", jnp.float32))


RAGGED = [([7], 5), ([1, 2, 3, 4, 5, 6, 7, 8, 9], 9), ([9, 9, 9], 1),
          ([5] * 16, 7), ([3, 4], 6), ([2] * 11, 3)]


def dense_logits(dense, rows):
    """The flax module's full forward over the rows, float32."""
    cfg, params = dense
    return np.asarray(Transformer(cfg).apply({"params": params},
                                             jnp.asarray(rows)))


@pytest.mark.parametrize("kind", ["dense", "hybrid", "latent"])
def test_one_program_family_for_every_description(kind, dense, model,
                                                  latent):
    """An engine built from the dense decoder's description, one built
    from the hybrid's and one from the latent-attention model's (a dense
    layer then expert layers, one pool a layer) expose the SAME
    programs: each lowers with
    one argument list (parameters, the cache as one donated pytree, the
    packed rows or the burst's table, lengths and live mask), and hands
    the cache back in the structure it took it. And what they serve for
    ragged prompts (dummy rows in the launches, idle slots beside live
    ones, slots reused) is, token by token, the argmax of the model's
    full forward: the flax ``Transformer``'s for the dense decoder, the
    plain references' for the other two."""
    if kind == "dense":
        mcfg, params = dense
        full_forward, vocab = lambda rows: dense_logits(dense, rows), 64
    elif kind == "hybrid":
        mcfg, params = model
        full_forward, vocab = reference_logits, 96
    else:
        mcfg, params = latent
        full_forward, vocab = latent_logits, 96
    icfg = InferenceConfig(batch_size=3, page_size=4, max_pages_per_seq=8,
                           num_pages=32, prefill_buckets=(8, 16),
                           max_new_tokens=8, decode_chunk=2)
    eng = InferenceEngine(params, mcfg, icfg)
    try:
        cache = jax.tree_util.tree_structure(eng._cache)
        assert cache.num_leaves == (1 if kind == "latent" else 2) * len(
            eng.mcfg.layers)
        rows = icfg.batch_size
        ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        split = eng._split_packed.lower(ints(rows, 9))
        assert [o.dtype for o in split.out_info] == [jnp.int32, jnp.int32,
                                                     jnp.bool_]
        lowered = {"jit_engine_split_packed": split}
        for n, fn in eng._decode_chunks.items():
            low = lowered[f"jit_engine_decode_n{n}"] = fn.lower(
                eng.params, eng._dev_toks, eng._cache, ints(rows, 8),
                ints(rows), jnp.zeros((rows,), bool))
            outs, toks, lens, kept, picks = low.out_info
            assert outs.shape == (n, rows) and toks.shape == lens.shape
            assert jax.tree_util.tree_structure(kept) == cache
            assert (picks is None) == (not eng.mcfg.moe_layers)
        for b, fn in eng._prefill_many.items():
            low = lowered[f"jit_engine_prefill_b{b}"] = fn.lower(
                eng.params, ints(eng._prefill_rows[b], 2 + b + -(-b // 4)),
                eng._cache, eng._dev_toks)
            first, toks, kept = low.out_info
            assert first.shape == (eng._prefill_rows[b]
                                   + eng.mcfg.n_experts_held,)
            assert jax.tree_util.tree_structure(kept) == cache
        assert sorted(lowered) == sorted(
            ["jit_engine_split_packed", "jit_engine_decode_n1",
             "jit_engine_decode_n2", "jit_engine_prefill_b8",
             "jit_engine_prefill_b16"])
        for name, low in lowered.items():
            assert low.as_text().startswith(f"module @{name} ")
        prompts = [([t % (vocab - 1) + 1 for t in p], m) for p, m in RAGGED]
        outs = [f.result(300) for f in
                [eng.submit(p, m) for p, m in prompts]]
    finally:
        eng.shutdown()
    for (prompt, max_new), out in zip(prompts, outs):
        assert len(out) == max_new
        logits = full_forward(np.asarray([prompt + out], np.int32))[0]
        start = len(prompt) - 1
        assert out == [int(np.argmax(logits[start + t]))
                       for t in range(max_new)]


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
