"""Ring attention: block kernel parity, ring-vs-reference numerics on a
virtual seq-sharded mesh, causality, and the model integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.ring_attention import (_block_attention_pallas,
                                        _block_attention_xla,
                                        attention_reference, block_attention,
                                        ring_attention_sharded)
from ray_tpu.parallel import mesh as mesh_lib


def _qkv(b=2, s=64, h=4, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) * 0.3 for k in ks)


class TestBlockAttention:
    def test_single_block_equals_full_attention(self):
        q, k, v = _qkv()
        # one block covering the whole sequence == plain attention
        qt = jnp.moveaxis(q, 1, 2)
        kt = jnp.moveaxis(k, 1, 2)
        vt = jnp.moveaxis(v, 1, 2)
        o, m, l = _block_attention_xla(qt, kt, vt, 0, 0, causal=True)
        out = (o / l[..., None]).astype(q.dtype)
        out = jnp.moveaxis(out, 2, 1)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_pallas_kernel_matches_xla(self):
        """interpret=True runs the kernel on CPU — logic parity; the real
        MXU path runs on hardware via impl='auto'."""
        q, k, v = _qkv(b=1, s=128, h=2, d=64)
        qt = jnp.moveaxis(q, 1, 2)
        kt = jnp.moveaxis(k, 1, 2)
        vt = jnp.moveaxis(v, 1, 2)
        o_x, m_x, l_x = _block_attention_xla(qt, kt, vt, 128, 0, True)
        o_p, m_p, l_p = _block_attention_pallas(qt, kt, vt, 128, 0, True,
                                                interpret=True)
        np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_x),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_x),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("q_offset,k_offset", [(128, 0), (0, 0)])
    def test_gradient_through_pallas_block_matches_xla(self, q_offset,
                                                       k_offset):
        """On a TPU impl="auto" is the kernel, and the train step under
        a seq mesh takes its gradient. The bare pallas_call has none
        (AssertionError in its JVP rule); the custom_vjp's backward is
        the XLA block's VJP, so the two impls must agree in (o, m, l)
        cotangents — GQA (h=4 over kv=2), diagonal and off-diagonal
        blocks."""
        q, _k, _v = _qkv(b=1, s=128, h=4, d=64)
        _q, k, v = _qkv(b=1, s=128, h=2, d=64, seed=1)
        qt, kt, vt = (jnp.moveaxis(x, 1, 2) for x in (q, k, v))

        def loss(impl):
            def f(q, k, v):
                o, m, l = block_attention(
                    q, k, v, q_offset=q_offset, k_offset=k_offset,
                    causal=True, impl=impl, interpret=True)
                return (jnp.sum(jnp.sin(o)) + jnp.sum(m * m)
                        + jnp.sum(jnp.log1p(l)))
            return f

        g_p = jax.grad(loss("pallas"), argnums=(0, 1, 2))(qt, kt, vt)
        g_x = jax.grad(loss("xla"), argnums=(0, 1, 2))(qt, kt, vt)
        for a, b in zip(g_p, g_x):
            assert float(jnp.abs(b).max()) > 0.0
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    def test_fully_masked_block_contributes_zero(self):
        q, k, v = _qkv(s=16)
        qt = jnp.moveaxis(q, 1, 2)
        kt = jnp.moveaxis(k, 1, 2)
        vt = jnp.moveaxis(v, 1, 2)
        # keys strictly in the future of every query
        o, m, l = _block_attention_xla(qt, kt, vt, 0, 1000, causal=True)
        assert float(jnp.abs(o).max()) == 0.0
        assert float(l.max()) == 0.0


@pytest.fixture(scope="module")
def seq_mesh():
    cfg = mesh_lib.MeshConfig(data=1, fsdp=2, seq=2, tensor=2)
    return mesh_lib.make_mesh(cfg, jax.devices()[:8])


class TestRing:
    def test_ring_matches_reference(self, seq_mesh):
        q, k, v = _qkv(b=2, s=64, h=4, d=32)
        with seq_mesh:
            out = jax.jit(lambda a, b_, c: ring_attention_sharded(
                a, b_, c, seq_mesh, causal=True))(q, k, v)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_ring_non_causal(self, seq_mesh):
        q, k, v = _qkv(b=2, s=32, h=4, d=32, seed=3)
        with seq_mesh:
            out = jax.jit(lambda a, b_, c: ring_attention_sharded(
                a, b_, c, seq_mesh, causal=False))(q, k, v)
        ref = attention_reference(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_ring_gqa_unrepeated_kv(self, seq_mesh):
        """KV rotate UNREPEATED (n_kv < n_heads); result matches the
        reference computed on repeated heads."""
        q, _, _ = _qkv(b=2, s=64, h=4, d=32, seed=7)
        kk = jax.random.normal(jax.random.PRNGKey(8), (2, 64, 2, 32)) * 0.3
        vv = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 2, 32)) * 0.3
        with seq_mesh:
            out = jax.jit(lambda a, b_, c: ring_attention_sharded(
                a, b_, c, seq_mesh, causal=True))(q, kk, vv)
        k_rep = jnp.repeat(kk, 2, axis=2)
        v_rep = jnp.repeat(vv, 2, axis=2)
        ref = attention_reference(q, k_rep, v_rep, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_causality_holds_across_ring(self, seq_mesh):
        """Perturbing future tokens must not change earlier outputs —
        the cross-device masking is the part a broken offset would wreck."""
        q, k, v = _qkv(b=2, s=64, h=4, d=32, seed=5)
        k2 = k.at[:, 48:].set(jax.random.normal(
            jax.random.PRNGKey(9), k[:, 48:].shape, k.dtype))
        v2 = v.at[:, 48:].set(0.0)
        with seq_mesh:
            f = jax.jit(lambda a, b_, c: ring_attention_sharded(
                a, b_, c, seq_mesh, causal=True))
            o1 = f(q, k, v)
            o2 = f(q, k2, v2)
        np.testing.assert_allclose(np.asarray(o1[:, :48]),
                                   np.asarray(o2[:, :48]),
                                   atol=1e-5, rtol=1e-5)


class TestModelIntegration:
    def test_model_logits_parity_with_ring(self, seq_mesh):
        """Flagship forward with ring attention on a seq=2 mesh matches
        the plain single-device forward."""
        from ray_tpu.models.transformer import Transformer, TransformerConfig

        base = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                                 n_heads=4, n_kv_heads=2, d_ff=176,
                                 max_seq_len=64, dtype=jnp.float32)
        ring_cfg = TransformerConfig(**{**base.__dict__,
                                        "ring_attention": True})
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 128)
        model = Transformer(base)
        params = model.init(jax.random.PRNGKey(1), tokens)["params"]
        ref = model.apply({"params": params}, tokens)

        ring_model = Transformer(ring_cfg)
        with mesh_lib.use_mesh(seq_mesh):
            out = jax.jit(lambda p, t: ring_model.apply({"params": p}, t)
                          )(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-4, rtol=3e-4)
