"""The plain reference against the program's own module at a tiny size
on the CPU, on the benchmark's seeded weights: the two compute the same
function (tied head and rotary lane pairing included), and the lower-
precision modes of the control really do differ."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.common import load_json
from benchmark.drivers.train import transformer_kwargs
from benchmark.reference import dense_decoder as dd

TINY = {**load_json("benchmark", "configs",
                    "mistral-7b-v0.3-train-L2.json"),
        **load_json("benchmark", "tests", "tiny_train.json")["config"],
        "run": {"dtype": "float32", "param_dtype": "float32"}}


@pytest.fixture(scope="module")
def params():
    return weights.init_params(TINY, weights.seed_key(2**31 + 7))


def ref_logits(params, row, mode="f32"):
    x = params["embedding"][row]
    for i in range(TINY["num_hidden_layers"]):
        x = dd.block(params[f"layer_{i}"], x, TINY, mode, q_chunk=8)
    return dd.head_logits(x, params["embedding"],
                          params["final_norm"]["scale"], TINY, mode)


def test_forward_matches_the_programs_module(params):
    from ray_tpu.models.transformer import Transformer, TransformerConfig

    row = np.random.default_rng(0).integers(0, 256, 24)
    model = Transformer(TransformerConfig(
        **transformer_kwargs(TINY, 32), flash_attention="off"))
    with jax.default_matmul_precision("highest"):
        prog = model.apply({"params": params}, jnp.asarray(row[None]))[0]
    ref = ref_logits(params, jnp.asarray(row))
    assert float(jnp.max(jnp.abs(prog - ref))) < 2e-5


def test_seed_beyond_32_bits_gives_its_own_weights():
    a = weights.init_layer(TINY, weights.seed_key(5), 0)
    b = weights.init_layer(TINY, weights.seed_key(5 + 2**31), 0)
    again = weights.init_layer(TINY, weights.seed_key(5), 0)
    wq = lambda t: np.asarray(t["Attention_0"]["wq"])  # noqa: E731
    assert np.array_equal(wq(a), wq(again))
    assert not np.array_equal(wq(a), wq(b))


@pytest.mark.parametrize("mode, at_least, at_most", [
    ("bf16", 1e-4, 0.2), ("fp8", 2e-2, 2.0)])
def test_lower_precision_modes_move_the_logits(params, mode, at_least,
                                               at_most):
    row = jnp.asarray(np.random.default_rng(1).integers(0, 256, 24))
    gap = float(jnp.max(jnp.abs(ref_logits(params, row, mode)
                                - ref_logits(params, row))))
    assert at_least < gap < at_most


def test_teacher_forced_logits_layer_by_layer(params):
    """The serving reference remakes each layer's weights from the seed
    and gives the same logits as the whole tree at once."""
    seed = 2**31 + 7
    rows = np.random.default_rng(2).integers(0, 256, (3, 16))
    got = dd.teacher_forced_logits(TINY, seed, rows, "f32", jnp.float32)
    want = jnp.stack([ref_logits(params, jnp.asarray(r)) for r in rows])
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    gaps, best = dd.served_token_gaps(got, rows, [4, 4, 4], [16, 12, 8])
    assert gaps.shape == (12 + 8 + 4,) and (gaps >= 0).all()
    # the gap of the reference's own best token is nought
    forced = rows.copy()
    forced[0, 4:16] = np.asarray(jnp.argmax(got[0, 3:15], axis=-1))
    # (teacher forcing: recompute with those tokens in place)
    again = dd.teacher_forced_logits(TINY, seed, forced, "f32",
                                     jnp.float32)
    g2, _ = dd.served_token_gaps(again, forced, [4], [5])
    assert g2[0] == 0.0
