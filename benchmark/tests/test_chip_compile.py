"""Each cell's programs compiled at the real size for a described
v5e:2x2 (no chip needed), printing the memory analysis that sized the
cell. Run by hand with -s; about two minutes. ONE file, and the
topology is described inside a fixture, because only one process at a
time may load the TPU's library."""

import pytest

from benchmark import weights
from benchmark.common import load_json
from benchmark.drivers.train import transformer_kwargs

GB = 1e9
HBM = 15.75 * GB        # what a v5e chip's compiler allows a program


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def on_chip(topo):
    """shape tree -> the same shapes placed on the first described
    chip."""
    import jax
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    return lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The program asks jax.default_backend() which path to take and
    would take its CPU branch here: steer it in the test."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def report(tag, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"\n[{tag}] arguments {m.argument_size_in_bytes / GB:.2f} GB, "
          f"outputs {m.output_size_in_bytes / GB:.2f}, temporaries "
          f"{m.temp_size_in_bytes / GB:.2f}, aliased "
          f"{m.alias_size_in_bytes / GB:.2f}: {total / GB:.2f} GB in all")
    return total


def test_train_cell_step(on_chip, as_on_tpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import train_step as ts
    from ray_tpu.models.transformer import Transformer, TransformerConfig

    config = load_json("benchmark", "configs",
                       "mistral-7b-v0.3-train-L2.json")
    job = load_json("benchmark", "traffic", "pretrain-seq4k.json")
    cfg = TransformerConfig(**transformer_kwargs(config, job["seq_len"]),
                            remat=True, remat_policy=job["remat"])
    optimizer = ts.make_optimizer()
    params = jax.eval_shape(lambda k: weights.init_params(
        config, k, cfg.param_dtype), jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    tokens = jax.ShapeDtypeStruct((job["batch"], job["seq_len"] + 1),
                                  jnp.int32)
    compiled = jax.jit(ts.make_train_step(Transformer(cfg), optimizer),
                       donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt_state),
        {"tokens": on_chip(tokens)}).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the flash kernel
    total = report("train.mistral7b-L2.seq4k step", compiled)
    assert 0.6 * HBM < total < 0.9 * HBM


def serve_shapes(on_chip):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    config = load_json("benchmark", "configs",
                       "mistral-7b-v0.3-serve-L16.json")
    eng = load_json("benchmark", "traffic", "chat-steady.json")["engine"]
    mcfg = TransformerConfig(**transformer_kwargs(
        config, eng["page_size"] * eng["max_pages_per_seq"]))
    params = on_chip(jax.eval_shape(lambda k: weights.init_params(
        config, k, mcfg.param_dtype), jax.random.PRNGKey(0)))
    pool = tuple(on_chip(jax.ShapeDtypeStruct(
        (eng["num_pages"], mcfg.n_kv_heads, eng["page_size"],
         mcfg.head_dim), mcfg.dtype)) for _ in range(mcfg.n_layers))
    ints = lambda *s: on_chip(jax.ShapeDtypeStruct(s, jnp.int32))  # noqa: E731
    return mcfg, eng, params, pool, ints


def test_serve_cell_decode_chunk(on_chip, as_on_tpu):
    import jax

    from ray_tpu.models import inference

    mcfg, eng, params, pool, ints = serve_shapes(on_chip)
    b = eng["batch_size"]
    compiled = jax.jit(
        lambda p, t, kp, vp, table, lens: inference.decode_chunk(
            p, mcfg, t, kp, vp, table, lens, n_steps=32),
        donate_argnums=(2, 3)).lower(
        params, ints(b), pool, pool, ints(b, eng["max_pages_per_seq"]),
        ints(b)).compile()
    # 768 tokens of context: the XLA gather, not the Pallas kernel
    assert "tpu_custom_call" not in compiled.as_text()
    total = report("serve.mistral7b-L16.chat-steady decode chunk, 32 steps",
                   compiled)
    assert total < HBM


def test_serve_cell_prefill_largest_bucket(on_chip, as_on_tpu):
    import jax

    from ray_tpu.models import inference

    mcfg, eng, params, pool, ints = serve_shapes(on_chip)
    bucket = max(eng["prefill_buckets"])
    compiled = jax.jit(lambda p, t: inference.prefill_batch(
        p, mcfg, t)).lower(params, ints(eng["batch_size"], bucket)).compile()
    total = report(f"serve.mistral7b-L16.chat-steady prefill_batch, 32 x "
                   f"{bucket}, pool not counted", compiled)
    pool_bytes = 2 * sum(x.size * 2 for x in pool)
    print(f"[pool] {pool_bytes / GB:.2f} GB beside it")
    assert total + pool_bytes < HBM
