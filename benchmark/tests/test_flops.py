"""flops.py against the numbers ISSUE 24 reckoned by hand."""

import pytest

from benchmark import flops
from benchmark.common import load_json


def config(name, **over):
    return {**load_json("benchmark", "configs", name + ".json"), **over}


TRAIN = "mistral-7b-v0.3-train-L2"
SERVE = "mistral-7b-v0.3-serve-L16"


def test_one_layer_and_embedding():
    c = config(TRAIN)
    assert flops.layer_params(c) == 218_112_000
    assert flops.embedding_params(c) == 134_217_728


@pytest.mark.parametrize("name, layers, params", [
    (TRAIN, 2, 570_445_824),
    (SERVE, 16, 3_624_013_824),
    (TRAIN, 8, 1_879_117_824),          # the open four-chip row
])
def test_parameter_counts(name, layers, params):
    c = config(name, num_hidden_layers=layers)
    assert flops.param_count(c) == params


@pytest.mark.parametrize("layers, tflop", [(2, 59.4), (8, 198.0)])
def test_train_step_flops(layers, tflop):
    c = config(TRAIN, num_hidden_layers=layers)
    got = flops.train_step_flops(c, batch=4, seq=4096) / 1e12
    assert got == pytest.approx(tflop, abs=0.1)
    # 6*N*D alone, and attention's part
    six_nd = 6 * flops.matmul_params(c) * 4 * 4096 / 1e12
    assert got - six_nd == pytest.approx(3.3 * layers / 2, abs=0.05)


def test_decode_step_bytes():
    c = config(SERVE)
    # weights once (7.25 GB in bf16), no live token
    assert flops.decode_step_bytes(c, []) == pytest.approx(7.248e9, rel=1e-3)
    # K and V of one live token: 2 * 16 layers * 8 heads * 128 * 2 B
    one = flops.decode_step_bytes(c, [1]) - flops.decode_step_bytes(c, [])
    assert one == 2 * 16 * 8 * 128 * 2
    full = flops.decode_step_bytes(c, [768] * 32)
    assert full - 7.248e9 == pytest.approx(1.61e9, rel=1e-2)


def test_roofline_says_which_bound():
    peak = load_json("benchmark", "peaks.json")["TPU v5 lite"]
    assert flops.roofline_seconds(197e12, 1.0, peak) == {
        "seconds": 1.0, "bound": "compute"}
    assert flops.roofline_seconds(1.0, 819e9, peak) == {
        "seconds": 1.0, "bound": "memory"}


def test_every_config_file_states_its_cut():
    bench = load_json("BENCHMARK.json")
    for entry in bench["configs"]:
        c = load_json(entry["file"])
        for key in ("source", "reduced", "assumed", "deployment",
                    "reference"):
            assert c.get(key), (entry["name"], key)
        assert c["source"] == entry["source"]
        assert sorted(c["reduced"]) == sorted(entry["reduced"])
        # every published width of Mistral-7B-v0.3
        assert (c["hidden_size"], c["intermediate_size"],
                c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"], c["vocab_size"]) == (
                    4096, 14336, 32, 8, 128, 32768)
