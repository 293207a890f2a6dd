"""The kernels of the chip's main paths compile for a TPU v5e.

Nothing runs here: the TPU compiler is installed beside jax and
compiles for a chip that is DESCRIBED (``v5e:2x2``), not attached. What
it refuses — a slice off the tiling, too much fast memory, a kernel
with no gradient — it refuses at no chip time. Shapes are those of
chip_smoke.py's phases. A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, in this
worker's own process, after collection: only one process at a time may
load the TPU's library, every xdist worker imports every test file, and
a module that asked at import would leave the workers with different
tests to collect. Keep every such test in THIS file (another file can
land on another worker, whose fixture would then skip in silence).
"""

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # an AOT compile is written to the persistent cache but cannot be
    # read back without a chip: the next one would warn and recompile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops it, skip
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """shape factory: a ShapeDtypeStruct placed on one described chip"""
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    return shape


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("max_pages", [16, 128])
def test_paged_attention_kernel(chip, max_pages):
    """Decode width of chip_smoke phase C: 64 slots, 8 heads / 4 KV
    heads of 128, pages of 16; 16 pages per sequence is what the bench
    runs, 128 is where the engine takes the kernel."""
    from ray_tpu.ops.paged_attention import paged_attention

    pool = 64 * max_pages + 1
    pages = chip((pool, 4, 16, 128), jnp.bfloat16)
    _c, text = _compile(
        paged_attention, chip((64, 8, 128), jnp.bfloat16), pages, pages,
        chip((64, max_pages), jnp.int32), chip((64,), jnp.int32))
    assert "tpu_custom_call" in text


def _ring_args(chip):
    q = chip((2, 16, 1024, 128), jnp.bfloat16)
    kv = chip((2, 8, 1024, 128), jnp.bfloat16)
    off = chip((), jnp.int32)
    return q, kv, kv, off, off


def test_ring_block_forward(chip):
    ra = importlib.import_module("ray_tpu.ops.ring_attention")
    _c, text = _compile(
        lambda q, k, v, a, b: ra._block_attention_pallas(
            q, k, v, a, b, True, False), *_ring_args(chip))
    assert "tpu_custom_call" in text


def test_ring_block_gradient(chip):
    """The train step under a seq mesh differentiates the block: the
    bare pallas_call could not be (no JVP for a kernel that reads
    program_id); the custom_vjp takes the XLA block's VJP."""
    ra = importlib.import_module("ray_tpu.ops.ring_attention")

    def loss(q, k, v, a, b):
        o, m, l = ra._block_attention_pallas(q, k, v, a, b, True, False)
        return (jnp.sum(o / jnp.maximum(l, 1e-30)[..., None])
                + 1e-3 * jnp.sum(m))

    _c, text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        *_ring_args(chip))
    assert "tpu_custom_call" in text   # the forward stays the kernel


def test_flash_attention_forward(chip):
    """Per-chip attention of the 445 M train step: seq 2047 (the LM's
    S - 1) pads to the kernel's 512 block inside the wrapper."""
    from ray_tpu.ops.flash import flash_attention_bhsd

    q = chip((4, 16, 2047, 128), jnp.bfloat16)
    _c, text = _compile(flash_attention_bhsd, q, q, q)
    assert "tpu_custom_call" in text


def test_flash_attention_gradient(chip):
    from ray_tpu.ops.flash import flash_attention_bhsd

    q = chip((4, 16, 2047, 128), jnp.bfloat16)
    _c, text = _compile(
        jax.grad(lambda q, k, v: jnp.sum(
            flash_attention_bhsd(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2)), q, q, q)
    assert text.count("tpu_custom_call") >= 2   # dq and dkv kernels


def test_scheduler_assign_kernel(chip):
    """One device tick of chip_smoke phase A: a 64-node cluster and a
    100 k-task ready batch (padded to 2**17), one scheduling class."""
    from ray_tpu._private.scheduler import kernels

    kpad, nodes, res = 1 << 17, 64, 4
    compiled = kernels._jit_assign(0.5).lower(
        chip((kpad,), jnp.int32), chip((kpad,), jnp.bool_),
        chip((1, res), jnp.float32), chip((nodes, res), jnp.float32),
        chip((nodes, res), jnp.float32), chip((1, nodes), jnp.bool_),
        chip((1,), jnp.bool_)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("bucket, rows", [(512, 1), (64, 8)])
def test_engine_prefill_program(chip, bucket, rows, capsys):
    """The serving cell's prefill program (Mistral-7B widths, 2 of its
    layers, 32 slots, 1,537 pages of 16, buckets 64 to 512): a launch
    computes the positions of one prompt in the largest bucket, so
    ``engine_prefill_b512`` has 1 row and ``b64`` has 8. The engine is
    built on shapes alone; its own jitted program is what compiles."""
    from ray_tpu.models.inference import InferenceConfig, InferenceEngine
    from ray_tpu.models.transformer import Transformer, TransformerConfig

    mcfg = TransformerConfig(vocab_size=32768, d_model=4096, n_layers=2,
                             n_heads=32, n_kv_heads=8, d_ff=14336,
                             max_seq_len=768, rope_theta=1e6,
                             dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    icfg = InferenceConfig(batch_size=32, page_size=16, max_pages_per_seq=48,
                           num_pages=1537,
                           prefill_buckets=(64, 128, 256, 512))
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: chip(x.shape, x.dtype), tree)
    params = jax.eval_shape(Transformer(mcfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(params, mcfg, icfg)
    try:
        assert engine._prefill_rows[bucket] == rows
        packed = chip((rows, 2 + bucket + bucket // icfg.page_size),
                      jnp.int32)
        compiled = engine._prefill_many[bucket].lower(
            on_chip(params), packed, on_chip(engine._k_pages),
            on_chip(engine._v_pages), on_chip(engine._dev_toks)).compile()
    finally:
        engine.shutdown()
    assert compiled.as_text().startswith(
        f"HloModule jit_engine_prefill_b{bucket}")
    m = compiled.memory_analysis()
    pool = 2 * sum(x.size * 2 for x in engine._k_pages)
    with capsys.disabled():
        print(f"\n[engine_prefill_b{bucket}, {rows} rows, 2 layers] "
              f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB (pool "
              f"{pool / 1e9:.3f}), temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f} GB")
    # the pool is donated and updated in place
    assert m.alias_size_in_bytes >= pool
    # prefill_batch of 32 x 512 took 4.7 GB beside the 16 layers'
    # weights (PERF.md section 4); a launch's budget of 512 positions
    # has to stay a small part of the chip
    assert m.temp_size_in_bytes < 1 << 30
