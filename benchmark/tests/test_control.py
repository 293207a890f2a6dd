"""The control of the output check at a size a test can hold: the
reference put in the program's place and computed in float8 (the step
below the bfloat16 the configurations state) comes out NOT correct
under the cells' own limits. On the chip at the cells' size it was run
on three seeds each (PERF.md section 2); the benchmark's runs do not
run it."""

import types

import numpy as np
import pytest

from benchmark import traffic_gen
from benchmark.common import load_json, passes
from benchmark.drivers import serve as serve_driver
from benchmark.drivers import train as train_driver
from benchmark.reference import dense_decoder as dd

TRAIN, SERVE = "train.mistral7b-L2.seq4k", "serve.mistral7b-L16.chat-steady"


def tiny(config_name, tiny_name):
    over = load_json("benchmark", "tests", tiny_name)
    config = {**load_json("benchmark", "configs", config_name + ".json"),
              **over["config"]}
    return config, over["traffic"]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_training_control_fails_a_number(seed):
    config, over = tiny("mistral-7b-v0.3-train-L2", "tiny_train.json")
    job = {**load_json("benchmark", "traffic", "pretrain-seq4k.json"),
           **over}
    limits = load_json("benchmark", "limits", TRAIN + ".json")["limits"]
    batches = [traffic_gen.train_batch(job, config["vocab_size"], seed, n)
               for n in (1, 2, 3)]
    run = lambda mode: dd.train_three_steps(  # noqa: E731
        config, seed, batches, job["optimizer"], mode=mode,
        **job["reference_args"])
    ref = run("f32")
    sound = train_driver.compare(ref, ref, limits)
    assert passes(sound)
    control = train_driver.compare(run("fp8"), ref, limits)
    assert not passes(control), control


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_serving_control_fails(seed):
    """The driver's own check (``output_checks``: the reference's gap
    function and the cell's limit), given the float8 reference in the
    program's place: at every position of the same prompts and tokens
    it judges the token that float8 puts first. The limit is in logit
    units, so the test keeps every published width (logits spread as
    in the cell, std 1.28) and cuts depth to one layer and the
    vocabulary to 4,096 rows: what a test run can hold."""
    config = {**load_json("benchmark", "configs",
                          "mistral-7b-v0.3-serve-L16.json"),
              "num_hidden_layers": 1, "vocab_size": 4096}
    cell = types.SimpleNamespace(name=SERVE, config=config, seed=seed)
    rng = np.random.default_rng(seed)
    sample = []
    for plen, n_out in ((40, 56), (17, 79)):
        s = serve_driver.Served(
            {"prompt": rng.integers(1, 4096, plen).tolist(),
             "max_new": n_out}, 0.0)
        s.tokens = rng.integers(1, 4096, n_out).tolist()
        sample.append(s)
    control = serve_driver.output_checks(cell, sample, 0, control="fp8")
    assert not passes(control), control
    assert [n for n, v, lim in control if not v <= lim] == [
        "widest_logit_gap"]
    # the float32 reference in the program's place is exact
    exact = serve_driver.output_checks(cell, sample, 0, control="f32")
    assert passes(exact) and exact[0][1] == 0.0
    # and nothing finished to sample is not correct
    assert not passes(serve_driver.output_checks(cell, [], 0))
