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
import inspect
import re

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
    heads of 128, pages of 16, at both its geometries: 16 pages a
    sequence (one block of the walk) and 128 (eight)."""
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


def _mistral_two_layers(max_seq_len):
    """Mistral-7B's widths, 2 of its layers, bfloat16: the description
    and the parameter tree as shapes (no device yet)."""
    from ray_tpu.models.transformer import Transformer, TransformerConfig

    mcfg = TransformerConfig(vocab_size=32768, d_model=4096, n_layers=2,
                             n_heads=32, n_kv_heads=8, d_ff=14336,
                             max_seq_len=max_seq_len, rope_theta=1e6,
                             dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(Transformer(mcfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return mcfg, params


@pytest.mark.parametrize("bucket, rows", [(512, 1), (64, 8)])
def test_engine_prefill_program(chip, bucket, rows, capsys):
    """The serving cell's prefill program (Mistral-7B widths, 2 of its
    layers, 32 slots, 1,537 pages of 16, buckets 64 to 512): a launch
    computes the positions of one prompt in the largest bucket, so
    ``engine_prefill_b512`` has 1 row and ``b64`` has 8. The engine is
    built on shapes alone; its own jitted program is what compiles."""
    from ray_tpu.models.inference import InferenceConfig, InferenceEngine

    mcfg, params = _mistral_two_layers(768)
    icfg = InferenceConfig(batch_size=32, page_size=16, max_pages_per_seq=48,
                           num_pages=1537,
                           prefill_buckets=(64, 128, 256, 512))
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: chip(x.shape, x.dtype), tree)
    engine = InferenceEngine(params, mcfg, icfg)
    try:
        assert engine._prefill_rows[bucket] == rows
        packed = chip((rows, 2 + bucket + bucket // icfg.page_size),
                      jnp.int32)
        compiled = engine._prefill_many[bucket].lower(
            on_chip(params), packed, on_chip(engine._cache),
            on_chip(engine._dev_toks)).compile()
    finally:
        engine.shutdown()
    assert compiled.as_text().startswith(
        f"HloModule jit_engine_prefill_b{bucket}")
    m = compiled.memory_analysis()
    pool = sum(x.size * 2 for x in jax.tree_util.tree_leaves(engine._cache))
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


def _pool_shaped(text, pool_dims, dtype="bf16"):
    """The instructions of a compiled program's text whose result, or
    one element of whose result tuple, has the pool's shape (bfloat16
    unless ``dtype`` says otherwise): (name, opcode, the line)."""
    shape = f"{dtype}[{','.join(map(str, pool_dims))}]"
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if m and shape in m.group(2):
            found.append((m.group(1), m.group(3), line))
    return found


def _no_pool_moves(text, pool_dims):
    """No copy yields a whole pool (a ``copy`` re-lays it out, a
    ``copy-start`` moves it between HBM and VMEM: with the kernel's
    pools not pinned to HBM the compiler fetched a 50 MB pool into
    VMEM for its reader and wrote it back, every step), no fusion
    under ``kv_append`` does, and no ``copy-start`` or ``slice-start``
    has a pool among its operands either (a pool fetched by slices)."""
    whole = _pool_shaped(text, pool_dims)
    assert [n for n, op, _l in whole if op in ("copy", "copy-start")] == []
    assert [n for n, op, line in whole
            if op == "fusion" and "kv_append" in line] == []
    shape = f"bf16[{','.join(map(str, pool_dims))}]"
    assert [line for line in text.splitlines() if shape in line
            and re.search(r" (copy|slice)-start\(", line)] == []


def _no_gathered_copy(text, slots, pages_a_seq, kv, page, d):
    """No instruction has the shape of a sequence-major copy of the
    pages: ``[slots, pages a sequence, KV, page, D]`` in any order of
    those dimensions (the gather's result and its transpose), or with
    the pages of a sequence run together."""
    import itertools

    shapes = {f"bf16[{','.join(map(str, dims))}]"
              for base in ((slots, pages_a_seq, kv, page, d),
                           (slots, kv, pages_a_seq * page, d))
              for dims in itertools.permutations(base)}
    assert [line for line in text.splitlines()
            if any(sh in line for sh in shapes)] == []


def _attention_kernels(text, scope):
    """The Pallas custom calls under a scope: under a mixer's the paged
    attention kernel's (the append's lie under ``kv_append``)."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and re.search(rf'op_name="[^"]*/{scope}/', line)]


def test_chip_smoke_tells_the_attention_kernel_from_the_append(
        chip, monkeypatch):
    """chip_smoke.py phase C holds every decode program to the paged
    attention kernel: at 16 pages a sequence, where the gather ran
    until PR 30, as at 128. Every decode program holds a Pallas custom
    call since the append is one, so "a custom call" says nothing: the
    check reads the one under the ``attn`` scope, at the phase's own
    two geometries (2 of the model's 8 layers), and finds none once
    the read is the gather again."""
    import os
    import sys

    from ray_tpu.models.inference import InferenceConfig
    from ray_tpu.models.transformer import Transformer, TransformerConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo)
    import chip_smoke

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mcfg = TransformerConfig(**dict(chip_smoke.DECODE_MODEL, n_layers=2))
    params = jax.tree_util.tree_map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(Transformer(mcfg).init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))["params"])
    defaults = inspect.signature(chip_smoke.phase_serving).parameters
    geometries = defaults["geometries"].default

    def has_kernel(mp, pages):
        return chip_smoke._decode_program_has_kernel(
            params, mcfg, InferenceConfig(
                batch_size=defaults["slots"].default,
                page_size=defaults["page_size"].default,
                max_pages_per_seq=mp, num_pages=pages), shape=chip)

    found = [has_kernel(mp, pages) for mp, pages in geometries]
    assert found == list(defaults["expect_kernel"].default) == [True, True]
    from ray_tpu.models import decoder_forward
    from ray_tpu.ops.paged_attention import paged_attention_reference
    monkeypatch.setattr(decoder_forward, "paged_attention_auto",
                        paged_attention_reference)
    assert not has_kernel(*geometries[0])


@pytest.mark.parametrize("num_pages, pages_a_seq", [(1537, 48), (2177, 68)])
def test_dense_decode_chunk(chip, monkeypatch, capsys, num_pages,
                            pages_a_seq):
    """The decode program of the two dense serving cells (Mistral-7B
    widths, 2 of its layers, 32 slots, pages of 16: 1,537 pages and 48
    a sequence in ``chat-steady``, 2,177 and 68 in ``decode-heavy``),
    4 steps, pools donated. A step appends one cell a slot IN PLACE and
    reads the pages its live sequences own where they lie: the compiled
    text may hold no ``copy`` that yields a whole pool, in the scan's
    body or in ``main``, nothing pool-shaped out of a fusion under
    ``kv_append`` (the one-hot form left six such copies and four such
    fusions a layer), no pool fetched into VMEM whole or by slices, no
    sequence-major copy of the pages (the gather left one a pool a
    layer, and its transpose), and one Pallas call a layer under
    ``attn``. The compiler's analysis, not a chip reading."""
    from ray_tpu.models import decoder_forward
    from ray_tpu.models.decoder import describe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, layers = 32, 2
    mcfg, params = _mistral_two_layers(16 * pages_a_seq)
    params = jax.tree_util.tree_map(lambda x: chip(x.shape, x.dtype), params)
    pool_dims = (num_pages, 8, 16, 128)
    pool = chip(pool_dims, jnp.bfloat16)
    compiled = jax.jit(
        lambda p, t, cache, table, lens, live:
        decoder_forward.decode_chunk_cached(
            p, describe(mcfg), t, cache, table, lens, live, n_steps=4),
        donate_argnums=(2,)).lower(
            params, chip((slots,), jnp.int32), ((pool, pool),) * layers,
            chip((slots, pages_a_seq), jnp.int32),
            chip((slots,), jnp.int32), chip((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    pool_bytes = 2 * layers * 2 * num_pages * 8 * 16 * 128
    with capsys.disabled():
        print(f"\n[dense decode chunk, {num_pages} pages, 4 steps, 2 layers] "
              f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB (pool "
              f"{pool_bytes / 1e9:.3f}), temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f} GB")
    assert len(_attention_kernels(text, "attn")) == layers
    _no_pool_moves(text, pool_dims)
    _no_gathered_copy(text, slots, pages_a_seq, 8, 16, 128)
    # the pools go through the scan in their own buffers
    assert m.alias_size_in_bytes >= pool_bytes
    # the gather held 156 and 247 MB here. What is left, 105 MB at both
    # geometries, is no attention's: ``main`` re-lays ``wq``, ``wk``
    # and ``wv`` out for their products once a chunk (PERF.md section
    # 5), 100.7 MB for two layers
    relaid = 2 * layers * 4096 * (32 + 8 + 8) * 128
    assert m.temp_size_in_bytes < relaid + (8 << 20)


def test_kda_chunk_scan(chip, monkeypatch, capsys):
    """One segment of a delta-rule layer at the published widths of the
    second served family (64 heads, key and value width 128): 2,048
    positions through the chunk scan. It is ONE Pallas call under its
    own name whose grid walks the chunks with the state in VMEM: no
    ``while`` over chunks is left for XLA, the operands are read in the
    [N,S,H,*] layout they arrive in (no chunk-major float32 copy of a
    segment beside it), and what a chunk needs (the pairwise decay of
    its diagonal blocks, the triangular inverse) lives inside the
    kernel."""
    from ray_tpu.ops.kda import kda_chunked

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    qk = chip((1, 2048, 64, 128), jnp.float32)
    compiled, text = _compile(
        kda_chunked, qk, qk, qk, qk, chip((1, 2048, 64), jnp.float32),
        chip((1, 64, 128, 128), jnp.float32))
    m = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[kda chunk scan, 2,048 positions, 64 heads of 128] "
              f"temporaries {m.temp_size_in_bytes / 1e6:.1f} MB")
    assert text.count("tpu_custom_call") == 1 and "kda_chunk_scan" in text
    assert "while" not in text
    # the scan it replaced re-laid q, k, v, g and beta out chunk-major
    assert "[128,1,64,16,128]" not in text and "[32,1,64,64,128]" not in text
    # the scan held 2 GiB's bound; the kernel's operands are the
    # caller's own and its output is written in place: no segment-sized
    # copy (67 MB each) is left
    assert m.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("tokens", [64, 8192])
def test_experts_grouped_products(chip, tokens, monkeypatch):
    """The held experts' part of an expert layer (40 of 320 held, top-8,
    width 1,280 at d 4,096) for a decode step's 64 slots and for a
    block of a long prompt: the three grouped products are the Pallas
    kernel ``moe_grouped``, whose grid visits the held experts' rows
    alone, not XLA's ragged dot over every pick nor a dense product a
    group."""
    from ray_tpu.ops.moe import experts_held, route_topk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def layer(x, router, w_gate, w_up, w_down):
        ids, weights = route_topk(x, router, 8)
        return experts_held(x, ids, weights, w_gate, w_up, w_down, 0)

    w = chip((40, 4096, 1280), jnp.bfloat16)
    compiled, text = _compile(
        layer, chip((tokens, 4096), jnp.bfloat16),
        chip((4096, 320), jnp.bfloat16), w, w,
        chip((40, 1280, 4096), jnp.bfloat16))
    assert "ragged-dot" not in text
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " = " in line]
    assert len(kernels) == 3
    assert all(line.split("=")[0].strip(" %").startswith("moe_grouped")
               for line in kernels)
    # the sorted copies of a block's picks stay a small part of the chip
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


def test_hybrid_decode_chunk(chip, monkeypatch, capsys):
    """The decode program of the benchmark's second configuration at its
    own size, on shapes alone: one gated-GQA layer on a pool of 4,097
    pages of 128 read by the Pallas paged kernel, three delta-rule
    layers on a float32 state of 64 slots, 40 held experts a layer, an
    eighth of the vocabulary. It has to fit one chip beside nothing
    else: arguments + temporaries under 15 GB."""
    from ray_tpu.models import decoder_forward
    from ray_tpu.models.decoder import DecoderConfig, LayerSpec

    # the trace-time choice of kernel follows the backend; this test
    # compiles for the chip, so it answers as the chip would
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d, h, hd, f, e, v = 4096, 64, 128, 1280, 40, 24576
    bf, f32 = jnp.bfloat16, jnp.float32
    mcfg = DecoderConfig(
        vocab_size=v, d_model=d, n_heads=h, n_kv_heads=8, head_dim=hd,
        layers=(LayerSpec("attention", "experts"),)
        + (LayerSpec("delta_rule", "experts"),) * 3,
        rope_theta=None, attn_gate=True, tie_embeddings=False,
        dr_heads=h, dr_key_dim=hd, dr_value_dim=hd, dr_conv=4, dr_rank=128,
        n_routed_experts=320, experts_held=(0, e), experts_per_token=8,
        d_expert=f, d_shared=f)
    mlp = lambda *lead: {"w_gate": chip(lead + (d, f), bf),  # noqa: E731
                         "w_up": chip(lead + (d, f), bf),
                         "w_down": chip(lead + (f, d), bf)}
    norm = {"scale": chip((d,), bf)}
    moe = {"router": chip((d, 320), bf), "shared": mlp(), **mlp(e)}
    proj = lambda n=h: chip((d, n, hd), bf)  # noqa: E731
    attention = {"wq": proj(), "wk": proj(8), "wv": proj(8),
                 "w_gate": proj(), "wo": chip((h, hd, d), bf)}
    low = lambda: chip((d, 128), bf)  # noqa: E731
    delta = {"wq": proj(), "wk": proj(), "wv": proj(),
             "wo": chip((h, hd, d), bf),
             **{c: chip((4, h, hd), bf)
                for c in ("conv_q", "conv_k", "conv_v")},
             "w_f_down": low(), "w_f_up": chip((128, h, hd), bf),
             "w_g_down": low(), "w_g_up": chip((128, h, hd), bf),
             "A_log": chip((h,), bf), "dt_bias": chip((h, hd), bf),
             "w_beta": chip((d, h), bf), "o_norm": chip((hd,), bf)}
    params = {"embedding": chip((v, d), bf), "lm_head": chip((v, d), bf),
              "final_norm": norm}
    for i in range(4):
        mixer = ({"Attention_0": attention} if i == 0
                 else {"DeltaRule_0": delta})
        params[f"layer_{i}"] = {**mixer, "MoE_0": moe, "RMSNorm_0": norm,
                                "RMSNorm_1": norm}
    slots, page, pages_a_seq = 64, 128, 132
    pool = chip((4097, 8, page, hd), bf)
    state = (chip((slots, h, hd, hd), f32), chip((slots, 3, h * 3 * hd), bf))
    compiled = jax.jit(
        lambda p, t, cache, table, lens, live:
        decoder_forward.decode_chunk_cached(
            p, mcfg, t, cache, table, lens, live, n_steps=32),
        donate_argnums=(2,)).lower(
            params, chip((slots,), jnp.int32), ((pool, pool),) + (state,) * 3,
            chip((slots, pages_a_seq), jnp.int32),
            chip((slots,), jnp.int32), chip((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "moe_grouped" in text and "ragged-dot" not in text
    # the one attention layer reads its pages through the one kernel,
    # appends through the other, and neither is a window layer's
    assert len(_attention_kernels(text, "gqa")) == 1
    assert len(_attention_kernels(text, "kv_append")) == 1
    assert "paged_window_read" not in text
    _no_gathered_copy(text, slots, pages_a_seq, 8, page, hd)
    m = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[hybrid decode chunk, 32 steps] arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f} GB")
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15e9
    # pool and state are donated and updated in place (2.15 GB of
    # pages, 0.81 GB of the delta-rule layers' state)
    assert m.alias_size_in_bytes > 2.9e9
    # the append writes its cells into the pool where it lies: no copy
    # of a pool in the text, nothing pool-shaped out of a fusion under
    # ``kv_append``, and the temporaries hold no K and V pair of 2.15 GB
    # (4.01 GB with the one-hot append, 0.83 GB since; the compiler's
    # analysis, not a chip reading)
    _no_pool_moves(text, (4097, 8, page, hd))
    assert m.temp_size_in_bytes < 1.9e9
    # each delta-rule layer's step is the one kernel that reads and
    # writes the live slots' state in place: nothing else yields a
    # state, no fusion (the parent's `where` over all 64 slots, 0.85 ms
    # a step and layer on the chip) and no copy
    kernels = _attention_kernels(text, "kda")
    assert len(kernels) == 3
    assert all("kda_decode_step" in line for line in kernels)
    moved = [(n, op) for n, op, _l in _pool_shaped(
        text, (slots, h, hd, hd), "f32")
        if op not in ("parameter", "get-tuple-element", "tuple", "while")]
    assert moved and all(op == "custom-call" and n.startswith(
        "kda_decode_step") for n, op in moved), moved


def _benchmark_config(name, **over):
    """A configuration file of the benchmark with ``over`` laid over
    it; puts the checkout on the path, for the weights modules."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as f:
        return dict(json.load(f), **over)


def test_hybrid_prefill_launch_of_a_long_bucket(chip, monkeypatch, capsys):
    """The long-document cell's ``engine_prefill_b4096`` at every width
    of its configuration, cut to the gated-GQA layer and one delta-rule
    layer (40 held experts each), 8 slots and 265 pages of 128 (slots
    and pages are donated arguments, not temporaries): the engine gives
    a bucket of 2,048 positions and more ONE row, and its own jitted
    program compiles for the described chip. Beside it the same program
    at the four rows PR 26's rule gave it (16,384 positions whatever
    arrived). The compiler's analysis, not a chip reading."""
    from ray_tpu.models.decoder import DecoderConfig, LayerSpec
    from ray_tpu.models.inference import InferenceConfig, InferenceEngine

    config = _benchmark_config("solar-open2-250b-serve-L4-ep8",
                               num_hidden_layers=2)
    from benchmark import weights_solar_open2 as W

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mcfg = DecoderConfig(
        layers=(LayerSpec("attention", "experts"),
                LayerSpec("delta_rule", "experts")),
        **W.decoder_kwargs(config))
    params = jax.eval_shape(
        lambda k: W.init_params(config, k, jnp.bfloat16), W.seed_key(1))
    icfg = InferenceConfig(batch_size=8, page_size=128, max_pages_per_seq=132,
                           num_pages=265,
                           prefill_buckets=(1024, 2048, 4096, 8192, 16384))
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: chip(x.shape, x.dtype), tree)
    bucket, temporaries = 4096, {}
    engine = InferenceEngine(params, mcfg, icfg)
    try:
        assert engine._prefill_rows == {1024: 2, 2048: 1, 4096: 1, 8192: 1,
                                        16384: 1}
        for rows in (engine._prefill_rows[bucket], 4):
            compiled = engine._prefill_many[bucket].lower(
                on_chip(params),
                chip((rows, 2 + bucket + bucket // 128), jnp.int32),
                on_chip(engine._cache), on_chip(engine._dev_toks)).compile()
            text = compiled.as_text()
            assert text.startswith(f"HloModule jit_engine_prefill_b{bucket}")
            assert "moe_grouped" in text and "ragged-dot" not in text
            temporaries[rows] = compiled.memory_analysis().temp_size_in_bytes
    finally:
        engine.shutdown()
    with capsys.disabled():
        print(f"\n[hybrid engine_prefill_b{bucket}, 2 layers] temporaries "
              f"{temporaries[1] / 1e9:.3f} GB at 1 row, "
              f"{temporaries[4] / 1e9:.3f} GB at the 4 rows of PR 26's rule")
    # a lone prompt's launch holds less than the four-row launch did
    # (0.90 against 1.77 GB: the delta rule's segment is 2,048
    # positions either way, the rest follows the positions)
    assert temporaries[1] < 0.75 * temporaries[4]
    assert temporaries[1] < 1 << 30


def _openpangu_two_layers(chip):
    """The benchmark's latent-attention configuration at every
    published width, its dense layer and ONE of its four expert layers
    (16 held experts of a router of 256), an eighth of the vocabulary:
    (description, parameter tree as shapes on the described chip)."""
    config = _benchmark_config("openpangu-ultra-moe-718b-serve-L5-ep16",
                               num_hidden_layers=2)
    from benchmark import weights_openpangu_ultra as W

    params = jax.eval_shape(
        lambda k: W.init_params(config, k, jnp.bfloat16), W.seed_key(1))
    return W.description(config), jax.tree_util.tree_map(
        lambda x: chip(x.shape, x.dtype), params)


def test_latent_decode_chunk(chip, monkeypatch, capsys):
    """The decode program of the latent-attention cell at its own
    geometry (32 slots, 2,561 pages of 128, 80 a sequence), 4 steps,
    the dense layer and one expert layer, pools donated. A step appends
    one row a slot IN PLACE and reads the rows its live sequences own
    where they lie, each page ONCE: one ``mla_paged_read`` call a layer
    (no second pool, no second read for the values), no copy that
    yields a whole pool, no pool fetched into VMEM, no sequence-major
    copy of the pages. The compiler's analysis, not a chip reading."""
    from ray_tpu.models import decoder_forward

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mcfg, params = _openpangu_two_layers(chip)
    slots, pages_a_seq = 32, 80
    pool_dims = (2561, 1, 128, mcfg.latent_width)
    assert mcfg.latent_width == 640
    cache = tuple((chip(pool_dims, jnp.bfloat16),) for _ in mcfg.layers)
    compiled = jax.jit(
        lambda p, t, cache, table, lens, live:
        decoder_forward.decode_chunk_cached(
            p, mcfg, t, cache, table, lens, live, n_steps=4),
        donate_argnums=(2,)).lower(
            params, chip((slots,), jnp.int32), cache,
            chip((slots, pages_a_seq), jnp.int32),
            chip((slots,), jnp.int32), chip((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    pool_bytes = len(mcfg.layers) * 2 * 2561 * 128 * 640
    with capsys.disabled():
        print(f"\n[latent decode chunk, 4 steps, 2 layers] arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f} GB (pools "
              f"{pool_bytes / 1e9:.3f}), temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    reads = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.match(r"\s*%?mla_paged_read", line)]
    assert len(reads) == len(mcfg.layers)
    assert all(re.search(r'op_name="[^"]*/mla_absorb/', r) for r in reads)
    appends = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and re.search(r'op_name="[^"]*/latent_append/', line)]
    assert len(appends) == len(mcfg.layers)
    _no_pool_moves(text, pool_dims)
    assert [n for n, op, line in _pool_shaped(text, pool_dims)
            if op == "fusion" and "latent_append" in line] == []
    _no_gathered_copy(text, slots, pages_a_seq, 1, 128, 640)
    assert m.alias_size_in_bytes >= pool_bytes
    # w_kvb's two halves are views taken while tracing: what a chunk
    # re-lays out of the weights stays far under a second copy of them
    assert m.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("bucket, rows", [(8192, 1), (1024, 2)])
def test_latent_prefill_program(chip, monkeypatch, capsys, bucket, rows):
    """A prefill launch of the latent-attention cell (the dense layer
    and one expert layer): the one row of its largest bucket and the
    two of its smallest (2,048 positions a launch since PR 32; eight
    until then), the expanded form through the kernel of
    ops/mla_prefill.py a group of heads at a time. No [S,S] scores, no
    copy of a pool, and temporaries that leave the chip room for the
    other three expert layers' weights (the five-layer program:
    1.6 GB)."""
    from ray_tpu.models import decoder_forward

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mcfg, params = _openpangu_two_layers(chip)
    pool_dims = (2561, 1, 128, mcfg.latent_width)
    cache = tuple((chip(pool_dims, jnp.bfloat16),) for _ in mcfg.layers)
    compiled = jax.jit(
        lambda p, cache, toks, plens, slots, pages, req:
        decoder_forward.prefill_cached(p, mcfg, cache, toks, plens, slots,
                                       pages, req),
        donate_argnums=(1,)).lower(
            params, cache, chip((rows, bucket), jnp.int32),
            chip((rows,), jnp.int32), chip((rows,), jnp.int32),
            chip((rows, bucket // 128), jnp.int32),
            chip((rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[latent prefill b{bucket}, {rows} rows, 2 layers] "
              f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB")
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and re.match(r"\s*%?mla_prefill_attention", line)]
    assert len(kernels) == len(mcfg.layers)       # one a layer, in a loop
    assert all(re.search(r'op_name="[^"]*/mla/', k) for k in kernels)
    # no scores of a whole row: [.., S, S] in any type
    assert not re.search(rf"\[(?:\d+,)*{bucket},{bucket}\]", text)
    _no_pool_moves(text, pool_dims)
    assert m.alias_size_in_bytes >= len(mcfg.layers) * 2 * 2561 * 128 * 640
    assert m.temp_size_in_bytes < 2 << 30


# ----------------------------------------------------------------------
# window layers (PR 33): the cell's programs at its own geometry, and
# the shared kernels as they were for those who do not ask
# ----------------------------------------------------------------------

def _trinity_two_layers(chip):
    """The benchmark's window configuration at every published width,
    one window layer (the leading dense layer) and the period's full
    layer with its experts (32 held of a router of 256), an eighth of
    the vocabulary: (description, parameter tree as shapes on the
    described chip, cache as shapes)."""
    config = _benchmark_config(
        "trinity-large-preview-serve-L5-ep8", num_hidden_layers=2,
        layer_types=["sliding_attention", "full_attention"])
    from benchmark import weights_trinity_large as W
    from ray_tpu.models import decoder_forward
    from ray_tpu.models.inference import InferenceConfig

    mcfg = W.description(config)
    params = jax.eval_shape(
        lambda k: W.init_params(config, k, jnp.bfloat16), W.seed_key(1))
    icfg = InferenceConfig(batch_size=32, page_size=128,
                           max_pages_per_seq=136, num_pages=4353)
    cache = jax.eval_shape(lambda: decoder_forward.init_cache(mcfg, icfg))
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: chip(x.shape, x.dtype), tree)
    return mcfg, on_chip(params), on_chip(cache)


def test_window_decode_chunk(chip, monkeypatch, capsys):
    """The decode program of the window cell at its own geometry (32
    slots, pages of 128: 4,353 in the full layer's pool with 136 a
    sequence, 32 rings of 33 + 1 in the window layer's), 4 steps, pools
    donated: one ``paged_window_read`` under ``win`` and one append
    under ``win_append`` for the window layer, the plain read under
    ``gqa`` and ``kv_append`` for the full one, no copy of either
    pool. The compiler's analysis, not a chip reading."""
    from ray_tpu.models import decoder_forward

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mcfg, params, cache = _trinity_two_layers(chip)
    assert [e[0].shape for e in cache] == [(32 * 33 + 1, 8, 128, 128),
                                           (4353, 8, 128, 128)]
    compiled = jax.jit(
        lambda p, t, cache, table, lens, live:
        decoder_forward.decode_chunk_cached(
            p, mcfg, t, cache, table, lens, live, n_steps=4),
        donate_argnums=(2,)).lower(
            params, chip((32,), jnp.int32), cache,
            chip((32, 136), jnp.int32), chip((32,), jnp.int32),
            chip((32,), jnp.bool_)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[window decode chunk, 4 steps, 2 layers] arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    reads = _attention_kernels(text, "win")
    assert len(reads) == 1 and re.match(r"\s*%?paged_window_read", reads[0])
    assert len(_attention_kernels(text, "win_append")) == 1
    assert len(_attention_kernels(text, "gqa")) == 1
    assert not re.match(r"\s*%?paged_window_read",
                        _attention_kernels(text, "gqa")[0])
    assert len(_attention_kernels(text, "kv_append")) == 1
    for pool_dims in ((32 * 33 + 1, 8, 128, 128), (4353, 8, 128, 128)):
        _no_pool_moves(text, pool_dims)
    assert m.alias_size_in_bytes >= 2 * 2 * (1057 + 4353) * 8 * 128 * 128
    assert m.temp_size_in_bytes < 1 << 30


def test_window_prefill_launch_of_the_longest_bucket(chip, monkeypatch,
                                                     capsys):
    """A launch of 16,384 positions of the window cell (one window and
    one full layer): the band through ``window_prefill_attention``
    under ``win``, the full layer through the library's flash kernel
    under ``gqa``, no [S,S] scores, no copy of a pool, and temporaries
    that leave the chip room for the other three layers' weights (the
    five-layer program: 2.3 GB beside 13.1 GB of arguments, my AOT
    compile, PR 33)."""
    from ray_tpu.models import decoder_forward

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mcfg, params, cache = _trinity_two_layers(chip)
    bucket = 16384
    compiled = jax.jit(
        lambda p, cache, toks, plens, slots, pages, req:
        decoder_forward.prefill_cached(p, mcfg, cache, toks, plens, slots,
                                       pages, req),
        donate_argnums=(1,)).lower(
            params, cache, chip((1, bucket), jnp.int32),
            chip((1,), jnp.int32), chip((1,), jnp.int32),
            chip((1, bucket // 128), jnp.int32),
            chip((1,), jnp.bool_)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[window prefill b{bucket}, 2 layers] temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    band = _attention_kernels(text, "win")
    assert len(band) == 1
    assert re.match(r"\s*%?window_prefill_attention", band[0])
    assert len(_attention_kernels(text, "gqa")) == 1
    assert not re.search(rf"\[(?:\d+,)*{bucket},{bucket}\]", text)
    # a launch's write is a scatter into the donated pool: a fusion that
    # yields it, never a copy of it
    for pool_dims in ((32 * 33 + 1, 8, 128, 128), (4353, 8, 128, 128)):
        assert [n for n, op, _ in _pool_shaped(text, pool_dims)
                if op in ("copy", "copy-start")] == []
    assert m.temp_size_in_bytes < 3 << 30


def test_route_topk_without_a_bias_lowers_as_it_did():
    """``route_topk`` gained an optional selection bias; without one it
    is the function it was, to the letter of its lowered text (the
    body of PR 32's function stands here as the oracle)."""
    from ray_tpu.ops.moe import route_topk

    def as_it_was(x, w_router, top_k, scale=1.0):
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,de->te", x.astype(jnp.float32),
            w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        picked, ids = jax.lax.top_k(scores, top_k)
        weights = picked / picked.sum(-1, keepdims=True)
        return ids.astype(jnp.int32), (weights if scale == 1.0
                                       else weights * scale)

    x = jax.ShapeDtypeStruct((64, 128), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((128, 256), jnp.bfloat16)

    def lowered(fn, *extra, **kw):
        text = jax.jit(lambda x, w, *e: fn(x, w, 8, 2.5, *e, **kw)).lower(
            x, w, *extra).as_text()
        return re.sub(r"\bas_it_was\b|\broute_topk\b", "f", text)

    assert lowered(route_topk) == lowered(as_it_was)
    bias = jax.ShapeDtypeStruct((256,), jnp.float32)
    assert lowered(route_topk, bias) != lowered(as_it_was)


@pytest.mark.parametrize("model", ["dense", "latent"])
def test_shared_kernels_are_as_they_were_for_those_who_do_not_ask(
        chip, monkeypatch, model):
    """The read and append kernels gained a ring and a first position
    for window layers. A model without such a layer holds the Pallas
    calls it held on the parent (PR 32), one read and one append a
    layer under the scopes they had, none of them a window's, and the
    read takes its three prefetched vectors and no fourth
    (``test_hybrid_decode_chunk`` holds the same for the gated layer's
    ``gqa``)."""
    from ray_tpu.models import decoder_forward
    from ray_tpu.models.decoder import describe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if model == "dense":
        mcfg, params = _mistral_two_layers(16 * 48)
        params = jax.tree_util.tree_map(lambda x: chip(x.shape, x.dtype),
                                        params)
        mcfg = describe(mcfg)
        pool = chip((1537, 8, 16, 128), jnp.bfloat16)
        cache, pages_a_seq = ((pool, pool),) * 2, 48
        read, append = "attn", "kv_append"
    else:
        mcfg, params = _openpangu_two_layers(chip)
        cache = tuple((chip((2561, 1, 128, 640), jnp.bfloat16),)
                      for _ in mcfg.layers)
        pages_a_seq, read, append = 80, "mla_absorb", "latent_append"
    lowered = jax.jit(
        lambda p, t, cache, table, lens, live:
        decoder_forward.decode_chunk_cached(
            p, mcfg, t, cache, table, lens, live, n_steps=2),
        donate_argnums=(2,)).lower(
            params, chip((32,), jnp.int32), cache,
            chip((32, pages_a_seq), jnp.int32), chip((32,), jnp.int32),
            chip((32,), jnp.bool_))
    text = lowered.compile().as_text()
    assert len(_attention_kernels(text, read)) == len(mcfg.layers)
    assert len(_attention_kernels(text, append)) == len(mcfg.layers)
    assert "paged_window_read" not in text
    assert not _attention_kernels(text, "win") + _attention_kernels(text, "win_append")


def _lfm2_stage(chip):
    """The benchmark's LFM2 stage at every published width and at its
    cell's geometry (9 layers: 7 conv, 2 full with 64 of 64 experts,
    the whole vocabulary; 64 slots, 1,537 pages of 128): (description,
    parameter tree as shapes on the described chip, cache as
    shapes)."""
    config = _benchmark_config("lfm2-24b-a2b-serve-L9")
    from benchmark import weights_lfm2 as W
    from ray_tpu.models import decoder_forward
    from ray_tpu.models.inference import InferenceConfig

    mcfg = W.description(config)
    params = jax.eval_shape(
        lambda k: W.init_params(config, k, jnp.bfloat16), W.seed_key(1))
    icfg = InferenceConfig(batch_size=64, page_size=128,
                           max_pages_per_seq=24, num_pages=1537)
    cache = jax.eval_shape(lambda: decoder_forward.init_cache(mcfg, icfg))
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: chip(x.shape, x.dtype), tree)
    return mcfg, on_chip(params), on_chip(cache)


def test_short_conv_decode_chunk(chip, monkeypatch, capsys):
    """The decode program of the LFM2 cell, 32 steps, whole: its two
    full layers read and append through the paged kernels with heads of
    64 padded to a lane of 128 in the pools (the kernels refuse a row
    of 64 for the chip), the seven conv layers' products under
    ``conv`` and their tails under ``conv_state``, the experts through
    ``moe_grouped``; arguments and temporaries fit the chip, pools and
    tails donated, no copy of a pool."""
    from ray_tpu.models import decoder_forward

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mcfg, params, cache = _lfm2_stage(chip)
    pool_dims = (1537, 8, 128, 128)
    assert [e[0].shape for i, e in enumerate(cache)
            if i in mcfg.kv_layers] == [pool_dims] * 2
    assert [e[0].shape for i, e in enumerate(cache)
            if i in mcfg.conv_layers] == [(64, 2, 2048)] * 7
    compiled = jax.jit(
        lambda p, t, cache, table, lens, live:
        decoder_forward.decode_chunk_cached(
            p, mcfg, t, cache, table, lens, live, n_steps=32),
        donate_argnums=(2,)).lower(
            params, chip((64,), jnp.int32), cache, chip((64, 24), jnp.int32),
            chip((64,), jnp.int32), chip((64,), jnp.bool_)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[short-conv decode chunk, 32 steps, 9 layers] arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    assert "moe_grouped" in text and "ragged-dot" not in text
    assert len(_attention_kernels(text, "attn")) == 2
    assert len(_attention_kernels(text, "kv_append")) == 2
    assert re.search(r'op_name="[^"]*/conv/', text)
    assert re.search(r'op_name="[^"]*/conv_state/', text)
    _no_pool_moves(text, pool_dims)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15e9
    assert m.alias_size_in_bytes >= 4 * 1537 * 8 * 128 * 128 * 2


def test_short_conv_prefill_launch_of_the_longest_bucket(chip, monkeypatch,
                                                         capsys):
    """A launch of 2,048 positions of the LFM2 cell (one row, the
    engine's rule for its longest bucket), whole: the conv layers'
    products under ``conv``, the tails written under ``conv_state``,
    the experts through ``moe_grouped``, no [S,S] scores of the whole
    row, no copy of a pool, and arguments and temporaries that fit the
    chip."""
    from ray_tpu.models import decoder_forward

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mcfg, params, cache = _lfm2_stage(chip)
    bucket = 2048
    compiled = jax.jit(
        lambda p, cache, toks, plens, slots, pages, req:
        decoder_forward.prefill_cached(p, mcfg, cache, toks, plens, slots,
                                       pages, req),
        donate_argnums=(1,)).lower(
            params, cache, chip((1, bucket), jnp.int32),
            chip((1,), jnp.int32), chip((1,), jnp.int32),
            chip((1, bucket // 128), jnp.int32),
            chip((1,), jnp.bool_)).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[short-conv prefill b{bucket}, 9 layers] temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB")
    assert "moe_grouped" in text and "ragged-dot" not in text
    assert re.search(r'op_name="[^"]*/conv/', text)
    assert re.search(r'op_name="[^"]*/conv_state/', text)
    # the whole row's scores of the 32 heads ([2048,2048] alone is a
    # row's hidden state: the width is 2,048 too)
    assert not re.search(rf"\[(?:\d+,)*32,{bucket},{bucket}\]", text)
    assert [n for n, op, _ in _pool_shaped(text, (1537, 8, 128, 128))
            if op in ("copy", "copy-start")] == []
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15e9


def test_route_topk_at_the_default_eps_lowers_as_it_did():
    """``route_topk`` gained the denominator's ``eps`` of a biased
    router (1e-20 before; LFM2's 1e-6). At the default it is the
    function it was, with a bias and without, to the letter of its
    lowered text (the body of the function with its constant 1e-20
    stands here as the oracle); 1e-6 lowers to another."""
    from ray_tpu.ops.moe import route_topk

    def as_it_was(x, w_router, top_k, scale=1.0, bias=None):
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,de->te", x.astype(jnp.float32),
            w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        if bias is None:
            picked, ids = jax.lax.top_k(scores, top_k)
            weights = picked / picked.sum(-1, keepdims=True)
        else:
            _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
            picked = jnp.take_along_axis(scores, ids, axis=-1)
            weights = picked / (picked.sum(-1, keepdims=True) + 1e-20)
        return ids.astype(jnp.int32), (weights if scale == 1.0
                                       else weights * scale)

    x = jax.ShapeDtypeStruct((64, 128), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((128, 64), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((64,), jnp.float32)

    def lowered(fn, *extra, **kw):
        text = jax.jit(lambda x, w, *e: fn(x, w, 4, 1.0, *e, **kw)).lower(
            x, w, *extra).as_text()
        return re.sub(r"\bas_it_was\b|\broute_topk\b", "f", text)

    assert lowered(route_topk) == lowered(as_it_was)
    assert lowered(route_topk, bias) == lowered(as_it_was, bias)
    assert lowered(route_topk, bias, eps=1e-6) != lowered(as_it_was, bias)
