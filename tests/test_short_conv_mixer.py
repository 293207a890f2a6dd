"""The gated short convolution (mixer ``conv``) through the serving
path: ``(C * conv(B * x)) W_out`` with a causal convolution of three
taps that keeps, a slot, the last two rows of ``B * x`` and nothing
else, beside full attention layers under the engine's page table and
expert layers that hold their whole router. Prefill then decode through
the cache are held, on LOGITS, to the benchmark's plain reference
(benchmark/reference/lfm2.py: float32, no cache, the convolution an
explicit sum over its taps): rows of different lengths in one launch
(prompts of 1, 2 and 3 tokens among them), a slot's next tenant, idle
slots, a router whose bias flips picks and whose denominator carries
1e-6; what the engine counts and refuses; and that the tolerance would
catch the mixer's band run in bfloat16 where the model says float32.

Every tolerance here is 2e-5 on logits of magnitude up to ~1: float32 on
both sides, the program's norms and softmax in another order of
operations than the reference's (the largest gap seen is 1e-6). The
band in bfloat16 moves a logit by 1e-3 and more, a dropped tail by
1e-2 and more."""

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

from benchmark import weights_lfm2 as W  # noqa: E402
from benchmark.reference import lfm2 as ref  # noqa: E402
from ray_tpu._private import spans  # noqa: E402
from ray_tpu.models import decoder_forward as forward  # noqa: E402
from ray_tpu.models import inference  # noqa: E402
from ray_tpu.models.decoder import DecoderConfig, LayerSpec  # noqa: E402
from ray_tpu.models.inference import (InferenceConfig,  # noqa: E402
                                      InferenceEngine)
from ray_tpu.ops.moe import route_topk  # noqa: E402

PAGE, VOCAB, ATOL = 4, 64, 2e-5
TINY = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": VOCAB, "num_hidden_layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "num_dense_layers": 1, "conv_L_cache": 3, "intermediate_size": 48,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "routed_scaling_factor": 1.0, "norm_topk_prob": True,
    "use_expert_bias": True, "router_bias_std": 0.3, "router_eps": 1e-6,
    "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "run": {"dtype": "float32", "param_dtype": "float32"},
}
SEED = 5


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
    assert mcfg.layers == (LayerSpec("conv", "dense"),
                           LayerSpec("attention", "experts"),
                           LayerSpec("conv", "experts"),
                           LayerSpec("conv", "experts"),
                           LayerSpec("attention", "experts"))
    assert mcfg.kv_layers == (1, 4) and mcfg.conv_layers == (0, 2, 3)
    # a tail is a state of its own a slot: the engine counts it
    assert mcfg.state_layers == (0, 2, 3)
    assert mcfg.conv_taps == 3 and mcfg.router_eps == 1e-6
    assert mcfg.experts_held == (0, 8) and mcfg.tie_embeddings
    assert sorted(params["layer_0"]["ShortConv_0"]) == ["conv", "w_in",
                                                        "w_out"]
    assert params["layer_0"]["ShortConv_0"]["w_in"].shape == (32, 3, 32)
    assert sorted(params["layer_1"]["MoE_0"]) == [
        "bias", "router", "w_down", "w_gate", "w_up"]
    assert forward.kept_beside_kv(mcfg) == "a convolution tail"
    # every description from before keeps what it kept and routes with
    # the denominator it had
    old = DecoderConfig(vocab_size=8, d_model=8, n_heads=1, n_kv_heads=1,
                        head_dim=8, layers=(LayerSpec("delta_rule"),
                                            LayerSpec()))
    assert old.state_layers == (0,) and not old.conv_layers
    assert old.router_eps == 1e-20
    with pytest.raises(ValueError, match="unknown layer kinds"):
        LayerSpec("short_conv")


_STEP = jax.jit(forward.decode_step_cached, static_argnums=(1,))


def _engine_geometry(total, batch_size, bucket):
    n_pages = -(-total // PAGE)
    return n_pages, InferenceConfig(
        batch_size=batch_size, page_size=PAGE, max_pages_per_seq=n_pages,
        num_pages=batch_size * n_pages + 1, prefill_buckets=(bucket,))


def _launch(mcfg, params, cache, rows, plens, bucket, slots, table,
            batch_size, parking, prefill=forward.prefill_cached):
    """One launch of ``bucket`` with a dummy row behind the rows:
    (logits at each row's last position, cache)."""
    n, n_prog = len(rows), -(-bucket // PAGE)
    toks = np.zeros((n + 1, bucket), np.int32)
    launch = np.full((n + 1, n_prog), parking, np.int32)
    for r, (row, plen, slot) in enumerate(zip(rows, plens, slots)):
        toks[r, :plen] = row[:plen]
        launch[r] = table[slot, :n_prog]
    slot_ids = np.asarray(list(slots) + [batch_size], np.int32)
    logits, cache, _ = prefill(
        params, mcfg, cache, jnp.asarray(toks),
        jnp.asarray(list(plens) + [1]), jnp.asarray(slot_ids),
        jnp.asarray(launch), jnp.asarray(slot_ids < batch_size))
    return np.asarray(logits)[:n], cache


def _decode_to(mcfg, params, cache, rows, slots, lens, table, total,
               step=_STEP):
    """Teacher-forced decode steps until every slot of ``slots`` has
    ``total`` positions; other slots stay idle. Returns (the logits of
    each row from its next position on, cache)."""
    got = [[] for _ in rows]
    lens = lens.copy()
    while (lens[list(slots)] < total).any():
        live = np.zeros(len(lens), bool)
        live[list(slots)] = lens[list(slots)] < total
        tokens = np.zeros(len(lens), np.int32)
        for row, slot in zip(rows, slots):
            if live[slot]:
                tokens[slot] = row[lens[slot]]
        logits, cache, _ = step(
            params, mcfg, jnp.asarray(tokens), cache, jnp.asarray(table),
            jnp.asarray(np.where(live, lens, 0)), jnp.asarray(live))
        for r, slot in enumerate(slots):
            if live[slot]:
                got[r].append(np.asarray(logits)[slot])
        lens = np.where(live, lens + 1, lens)
    return [np.stack(g) for g in got], cache


def _launch_and_decode(mcfg, params, rows, plens, bucket, slots, total=24,
                       batch_size=4, **over):
    """Prefill ``rows[r][:plens[r]]`` in ONE launch, then decode every
    row teacher-forced to ``total``: each row's logits from position
    plen - 1 on."""
    n_pages, icfg = _engine_geometry(total, batch_size, bucket)
    parking = icfg.num_pages - 1
    cache = forward.init_cache(mcfg, icfg)
    table = np.full((batch_size, n_pages), parking, np.int32)
    for slot in slots:
        table[slot] = slot * n_pages + np.arange(n_pages)[::-1]
    first, cache = _launch(mcfg, params, cache, rows, plens, bucket, slots,
                           table, batch_size, parking, **over.get(
                               "launch", {}))
    lens = np.zeros(batch_size, np.int32)
    lens[list(slots)] = plens
    rest, cache = _decode_to(mcfg, params, cache, rows, slots, lens, table,
                             total, **over.get("decode", {}))
    return [np.concatenate([f[None], r]) for f, r in zip(first, rest)]


@pytest.mark.parametrize("plen", [1, 2, 3, 9, 16])
def test_prefill_then_decode_is_the_reference(model, plen):
    """Every logit of the prompt, then every logit up to a context of 24
    through the tails and the pages, equal the reference's full forward.
    A prompt of one or two tokens hands on a tail with zeros before
    position 0."""
    mcfg, params = model
    row = np.random.default_rng(plen).integers(1, VOCAB, 24)
    want = reference_logits(row[None])[0]
    toks = np.zeros((1, 16), np.int32)
    toks[0, :plen] = row[:plen]
    x, _, _ = forward._prefill_hidden(params, mcfg, jnp.asarray(toks),
                                      jnp.asarray([plen]))
    whole = np.asarray(forward._head(params, mcfg, x, "bsd,vd->bsv"))[0]
    np.testing.assert_allclose(whole[:plen], want[:plen], atol=ATOL)
    (got,) = _launch_and_decode(mcfg, params, [row], [plen], 16, [1])
    np.testing.assert_allclose(got, want[plen - 1:], atol=ATOL)


def test_pools_of_whole_lanes_are_the_reference(model):
    """The full layers' pools hold each head padded with zeros to whole
    lanes of 128 (``DecoderConfig.kv_width``: the chip's paged kernels
    take no row of 64): the pools are 128 wide, and prefill then decode
    equal the reference."""
    mcfg, params = model
    assert mcfg.kv_width == 128
    icfg = InferenceConfig(batch_size=2, page_size=PAGE, max_pages_per_seq=6,
                           num_pages=13)
    assert [e[0].shape for i, e in enumerate(forward.init_cache(mcfg, icfg))
            if i in mcfg.kv_layers] == [(13, 2, PAGE, 128)] * 2
    row = np.random.default_rng(6).integers(1, VOCAB, 24)
    want = reference_logits(row[None])[0]
    step = jax.jit(lambda *a: forward.decode_step_cached(*a),
                   static_argnums=(1,))
    (got,) = _launch_and_decode(mcfg, params, [row], [9], 16, [1],
                                decode={"step": step})
    np.testing.assert_allclose(got, want[8:], atol=ATOL)


def test_rows_of_one_launch_keep_to_their_own_tails(model):
    """Prompts of 1, 2, 3 and 7 tokens in one launch of 8 positions,
    into slots 2, 0, 3 and 1: each decodes to 24 as alone."""
    mcfg, params = model
    rng = np.random.default_rng(1)
    rows = [rng.integers(1, VOCAB, 24) for _ in range(4)]
    plens = [1, 2, 3, 7]
    want = reference_logits(np.stack(rows))
    got = _launch_and_decode(mcfg, params, rows, plens, 8, [2, 0, 3, 1])
    for g, w, plen in zip(got, want, plens):
        np.testing.assert_allclose(g, w[plen - 1:], atol=ATOL)


def test_a_successor_starts_from_a_zero_tail(model):
    """A slot that decoded a long row takes a prompt of ONE token next:
    the launch replaces the whole tail, so the successor sees zeros
    before its position 0 and answers as the reference does. A tail
    that kept the predecessor's rows would not (checked here too)."""
    mcfg, params = model
    rng = np.random.default_rng(3)
    first, second = rng.integers(1, VOCAB, 20), rng.integers(1, VOCAB, 12)
    n_pages, icfg = _engine_geometry(20, 2, 8)
    parking = icfg.num_pages - 1
    table = np.full((2, n_pages), parking, np.int32)
    table[1] = np.arange(n_pages)
    cache = forward.init_cache(mcfg, icfg)
    _, cache = _launch(mcfg, params, cache, [first], [6], 8, [1], table, 2,
                       parking)
    lens = np.asarray([0, 6], np.int32)
    _, cache = _decode_to(mcfg, params, cache, [first], [1], lens, table, 20)
    tails = [np.asarray(cache[i][0][1]) for i in mcfg.conv_layers]
    assert all(np.abs(t).max() > 0.1 for t in tails)
    # the successor, in the same pages
    got, cache = _launch(mcfg, params, cache, [second], [1], 8, [1], table,
                         2, parking)
    for i in mcfg.conv_layers:
        np.testing.assert_array_equal(np.asarray(cache[i][0][1][0]), 0.0)
    rest, _ = _decode_to(mcfg, params, cache, [second], [1],
                         np.asarray([0, 1], np.int32), table, 12)
    want = reference_logits(second[None])[0]
    np.testing.assert_allclose(np.concatenate([got, rest[0]]), want,
                               atol=ATOL)
    # had the tail kept the predecessor's rows, the answer would differ
    _, leaked = _launch(mcfg, params, forward.init_cache(mcfg, icfg),
                        [second], [1], 8, [1], table, 2, parking)
    leaked = list(leaked)
    for j, i in enumerate(mcfg.conv_layers):
        leaked[i] = (leaked[i][0].at[1].set(tails[j]),)
    wrong, _ = _decode_to(mcfg, params, tuple(leaked), [second], [1],
                          np.asarray([0, 1], np.int32), table, 12)
    assert np.abs(wrong[0] - want[1:]).max() > 1e-2


def test_idle_slots_keep_their_tails(model):
    """A decode step with slots 0 and 2 idle: their tails (NaN here)
    are neither read nor written, and the live slot's logits are what
    they are with zeros there."""
    mcfg, params = model
    icfg = InferenceConfig(batch_size=3, page_size=PAGE, max_pages_per_seq=4,
                           num_pages=13, prefill_buckets=(8,))
    rng = np.random.default_rng(2)
    filled = [tuple(jnp.asarray(rng.normal(size=a.shape), jnp.float32)
                    for a in e) for e in forward.init_cache(mcfg, icfg)]

    def run(idle):
        cache = tuple(
            (e[0].at[0].set(idle).at[2].set(idle),)
            if i in mcfg.conv_layers else e for i, e in enumerate(filled))
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
    for i in mcfg.conv_layers:
        (tail,) = cache[i]
        assert np.isnan(np.asarray(tail[0])).all()
        assert np.isnan(np.asarray(tail[2])).all()
        # the live slot's tail moved on by one row
        np.testing.assert_array_equal(np.asarray(tail[1][0]),
                                      np.asarray(filled[i][0][1][1]))


def test_the_routers_bias_and_its_denominator(model):
    """The bias of the configuration flips picks (against the picks of
    the scores alone), and ``router_eps`` is what the expert layer puts
    under the sum: with scores of ~1e-9 the weights of 1e-6 and of
    Trinity's 1e-20 differ by a factor, and the program's layer is the
    reference's at the description's value."""
    mcfg, params = model
    m = params["layer_2"]["MoE_0"]
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    biased, _ = route_topk(x, m["router"], 2, bias=m["bias"])
    plain, _ = route_topk(x, m["router"], 2)
    flipped = np.mean(np.sort(np.asarray(biased), -1)
                      != np.sort(np.asarray(plain), -1))
    assert 0.1 < flipped < 0.9
    # positive inputs against a router shifted down: logits near -20,
    # scores near 2e-9, so 1e-6 under their sum dominates it
    x = jnp.abs(x)
    faint = {**m, "router": m["router"] - 0.8}
    valid = jnp.ones((64,), bool)
    ys = {}
    for eps in (1e-6, 1e-20):
        cfg = DecoderConfig(**{**mcfg.__dict__, "router_eps": eps})
        ys[eps] = np.asarray(forward._experts(faint, cfg, x, valid)[0])
    assert np.abs(ys[1e-20]).mean() > 3 * np.abs(ys[1e-6]).mean()
    s = W.dims(TINY)
    want = ref.expert_layer(
        faint, lambda e: jax.tree_util.tree_map(lambda w: w[e],
                                                {k: m[k] for k in (
                                                    "w_gate", "w_up",
                                                    "w_down")}),
        x, s, 1e-6, "f32")
    np.testing.assert_allclose(ys[1e-6], np.asarray(want), atol=1e-6)


def test_the_band_in_bfloat16_would_fail(model, monkeypatch):
    """The tolerance's own check: the same prompt and steps with ``B *
    x``, the convolution and ``C * y`` rounded to bfloat16 (the model
    says float32) miss the reference by more than ATOL."""
    mcfg, params = model
    bf = jnp.bfloat16
    gates, out = forward._conv_gates, forward._conv_out

    def gates_bf16(a, cfg, h):
        z, c = gates(a, cfg, h)
        return z.astype(bf).astype(jnp.float32), c

    def out_bf16(a, cfg, c, y):
        return out(a, cfg, c.astype(bf).astype(jnp.float32),
                   y.astype(bf).astype(jnp.float32))

    monkeypatch.setattr(forward, "_conv_gates", gates_bf16)
    monkeypatch.setattr(forward, "_conv_out", out_bf16)
    row = np.random.default_rng(9).integers(1, VOCAB, 16)
    want = reference_logits(row[None])[0]
    step = jax.jit(lambda *a: forward.decode_step_cached(*a),
                   static_argnums=(1,))
    (got,) = _launch_and_decode(mcfg, params, [row], [5], 8, [0], total=16,
                                decode={"step": step})
    assert np.abs(got - want[4:]).max() > 10 * ATOL


ICFG = InferenceConfig(batch_size=3, page_size=PAGE, max_pages_per_seq=10,
                       num_pages=31, prefill_buckets=(8, 16),
                       max_new_tokens=24, decode_chunk=4)


def test_the_engine_serves_it_and_counts_it(model):
    """Through ``InferenceEngine``: three requests (prompts of 2, 9 and 16
    tokens) decode over two bursts of chunks of 4 and every served
    token is the reference's first choice; the launch says how many
    slots' tails it wrote, every burst how many held experts its steps
    touched (one to all eight a step and expert layer), and ``stats()``
    sums the same and counts the tails as the slots' state."""
    mcfg, params = model
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (2, 9, 16)]
    mark = len(spans.since(float("-inf")))
    eng = InferenceEngine(params, mcfg, ICFG)
    try:
        futures = [eng.submit(p, max_new_tokens=24) for p in prompts]
        outs = [f.result(300) for f in futures]
    finally:
        eng.shutdown()
    # read once the loop has stopped: it hands a burst's tokens out
    # before it adds the burst's counts
    stats = eng.stats()
    for prompt, out in zip(prompts, outs):
        rows = np.asarray([prompt + out], np.int32)
        gaps, _ = ref.served_token_gaps(
            jnp.asarray(reference_logits(rows)), rows, [len(prompt)],
            [rows.shape[1]])
        assert gaps.max() < 1e-4
    records = spans.since(float("-inf"))[mark:]
    dispatches = [r[5] for r in records if r[0] == "engine.dispatch"]
    launches = [r[5] for r in records if r[0] == "engine.prefill_launch"]
    assert len(dispatches) >= 2
    assert sum(f["conv_tails_written"] for f in launches) == 3
    assert all(f["conv_tails_written"] == f["useful_rows"] for f in launches)
    assert all(f["state_slots_live"] == f["live_slots"] for f in dispatches)
    moe = len(mcfg.moe_layers)
    for f in dispatches:
        assert moe * f["steps"] <= f["moe_experts_touched"]
        assert f["moe_experts_touched"] <= moe * f["steps"] * min(
            8, 2 * f["live_slots"])
    assert stats["moe_experts_touched"] == sum(
        f["moe_experts_touched"] for f in dispatches)
    assert stats["state_bytes"] == 3 * 3 * 2 * 32 * 4


def test_the_programs_scope_the_mixer(model):
    """The decode program and a launch run the mixer's products under
    ``conv`` and its tails' update under ``conv_state``; the decode
    program's picks come with the touched count behind them."""
    mcfg, params = model
    eng = InferenceEngine(params, mcfg, ICFG)
    try:
        ints = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
        low = eng._decode_chunks[2].lower(
            eng.params, eng._dev_toks, eng._cache, ints(3, 10), ints(3),
            jnp.zeros((3,), bool))
        picks = low.out_info[-1]
        decode = low.as_text(debug_info=True)
        launch = eng._prefill_many[8].lower(
            eng.params, ints(eng._prefill_rows[8], 2 + 8 + 2), eng._cache,
            eng._dev_toks).as_text(debug_info=True)
    finally:
        eng.shutdown()
    assert picks.shape == (mcfg.n_experts_held + 1,)
    for text in (decode, launch):
        for scope in ("conv", "conv_state"):
            assert re.search(rf'loc\("(jit\([^)]*\)/)?{scope}/', text)


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_split_modes_refuse_a_convolution_tail(model, mode):
    mcfg, params = model
    with pytest.raises(ValueError, match="a convolution tail"):
        InferenceEngine(params, mcfg, InferenceConfig(), mode=mode)


def test_handoff_entry_points_refuse_a_convolution_tail(model):
    mcfg, params = model
    eng = InferenceEngine(params, mcfg, ICFG)
    try:
        with pytest.raises(RuntimeError, match="a convolution tail"):
            eng.prefill_export([1, 2, 3])
        with pytest.raises(RuntimeError, match="whole sequences"):
            eng.submit_stream_from_kv({"prompt": [1], "k": None, "v": None,
                                       "first_token": 0})
        with pytest.raises(ValueError, match="a convolution tail"):
            inference.prefill_batch(params, mcfg,
                                    jnp.zeros((1, 8), jnp.int32))
    finally:
        eng.shutdown()
