"""LLM inference engine: paged attention + continuous batching.

Parity strategy (SURVEY.md §4 style): the paged-cache decode path must
produce EXACTLY the greedy tokens of the naive full-context forward
(the flax Transformer re-run on the whole sequence each step) — same
params, tiny config. The Pallas kernel itself is parity-tested against
the XLA gather reference in test_paged_attention below.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models.inference import (InferenceConfig,  # noqa: E402
                                      InferenceEngine, decode_step,
                                      prefill)
from ray_tpu.models.transformer import (Transformer,  # noqa: E402
                                        TransformerConfig)
from ray_tpu.ops import paged_attention as pa  # noqa: E402


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=64,
                            max_seq_len=128, dtype=jnp.float32)
    model = Transformer(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    return cfg, model, variables["params"]


def naive_greedy(model, params, prompt, n_new):
    toks = list(prompt)
    for _ in range(n_new):
        logits = model.apply({"params": params},
                             jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def _paged_case(page, kv, g, d, mp, lens, dtype, seed=0):
    """q, pools, table, lens with physical page 0 as the parking page,
    full of NaN: every table entry past a sequence's length names it,
    an idle slot's whole row does."""
    rng = np.random.default_rng(seed)
    b, pool = len(lens), 1 + len(lens) * mp
    q = jnp.asarray(rng.normal(size=(b, kv * g, d)), dtype)
    kp = rng.normal(size=(pool, kv, page, d)).astype(np.float32)
    vp = rng.normal(size=(pool, kv, page, d)).astype(np.float32)
    kp[0] = vp[0] = np.nan
    table = 1 + rng.permutation(pool - 1).reshape(b, mp).astype(np.int32)
    for i, n in enumerate(lens):
        table[i, -(-n // page):] = 0
    return (q, jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
            jnp.asarray(table), jnp.asarray(lens, jnp.int32))


class TestPagedAttention:
    # (page, KV, G, D, pages a sequence, tokens a block, lengths, dtype)
    @pytest.mark.parametrize("page, kv, g, d, mp, block, lens, dtype", [
        # the kernel's first test: pages of 8, two a block
        pytest.param(8, 4, 2, 32, 4, 16, [5, 17, 32], jnp.float32,
                     id="pages-of-8"),
        # 7 pages in blocks of 2: an idle slot, one token, exactly a
        # page, exactly a block, one past a block, the whole table
        pytest.param(4, 2, 2, 16, 7, 8, [0, 1, 4, 8, 9, 28], jnp.float32,
                     id="pages-of-4-table-not-a-multiple-of-the-block"),
        # the dense cells' page and group, bfloat16 pools
        pytest.param(16, 2, 4, 128, 5, 32, [0, 16, 32, 33, 80],
                     jnp.bfloat16, id="pages-of-16-G4-bf16"),
        # the hybrid cell's page, two a block
        pytest.param(128, 2, 4, 128, 3, 256, [0, 128, 256, 300, 384],
                     jnp.bfloat16, id="pages-of-128-G4-bf16"),
        # the block the programs run (256 tokens = 16 pages of 16)
        pytest.param(16, 2, 4, 128, 20, pa.BLOCK_TOKENS,
                     [320, 257, 256, 0, 1], jnp.bfloat16,
                     id="the-default-block"),
        # a table shorter than a block: the block shrinks to the table
        pytest.param(4, 2, 2, 16, 3, pa.BLOCK_TOKENS, [12, 0, 5],
                     jnp.float32, id="table-shorter-than-a-block"),
        # a length past the table reads the table and nothing else
        pytest.param(4, 2, 2, 16, 3, 8, [13, 40, 12], jnp.float32,
                     id="length-past-the-table"),
        # every slot idle
        pytest.param(4, 2, 2, 16, 3, 8, [0, 0], jnp.float32,
                     id="all-idle"),
    ])
    def test_kernel_matches_reference(self, page, kv, g, d, mp, block,
                                      lens, dtype):
        """Live rows equal the gather's on the same pools; the parking
        page's NaN, which every table entry past a length names, reaches
        no row (in the gather it would: its oracle reads a parking page
        of zeros); an idle slot's row is finite."""
        q, kp, vp, table, lens = _paged_case(page, kv, g, d, mp, lens,
                                             dtype)
        ref = pa.paged_attention_reference(
            q, kp.at[0].set(0), vp.at[0].set(0), table, lens)
        ker = np.asarray(pa.paged_attention(q, kp, vp, table, lens,
                                            block_tokens=block,
                                            interpret=True))
        assert np.isfinite(ker).all()
        live = np.asarray(lens) > 0
        np.testing.assert_allclose(np.asarray(ref)[live], ker[live],
                                   atol=1e-5)

    def test_auto_raises_when_the_kernel_raises(self, monkeypatch):
        """paged_attention_auto runs the kernel; it never swaps a
        failing kernel for the XLA gather (on the chip that recorded a
        kernel that did not lower as a slow pass)."""
        def boom(*a, **kw):
            raise RuntimeError("kernel failed to lower")

        monkeypatch.setattr(pa, "paged_attention", boom)
        q = jnp.ones((2, 4, 16))
        pages = jnp.ones((8, 2, 4, 16))
        with pytest.raises(RuntimeError, match="failed to lower"):
            pa.paged_attention_auto(q, pages, pages,
                                    jnp.zeros((2, 2), jnp.int32),
                                    jnp.asarray([1, 3], jnp.int32))

    def test_zero_length_sequence(self):
        B, H, KV, D, page, P, MP = 2, 4, 2, 16, 4, 8, 2
        q = jnp.ones((B, H, D))
        kp = jnp.ones((P, KV, page, D))
        vp = jnp.ones((P, KV, page, D))
        table = jnp.zeros((B, MP), jnp.int32)
        lens = jnp.asarray([0, 3], jnp.int32)
        out = pa.paged_attention(q, kp, vp, table, lens, interpret=True)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(out[1], 1.0, atol=1e-5)

    def test_append_token(self):
        rng = np.random.default_rng(1)
        B, KV, D, page, P, MP = 2, 2, 8, 4, 6, 3
        kp = jnp.zeros((P, KV, page, D))
        vp = jnp.zeros((P, KV, page, D))
        table = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
        lens = jnp.asarray([5, 0], jnp.int32)
        kn = jnp.asarray(rng.normal(size=(B, KV, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(B, KV, D)), jnp.float32)
        k2, _v2 = pa.append_token_kv(kp, vp, kn, vn, table, lens)
        # seq 0: logical page 5//4=1 -> phys 2, slot 1
        np.testing.assert_allclose(k2[2, :, 1, :], kn[0])
        # seq 1: logical page 0 -> phys 3, slot 0
        np.testing.assert_allclose(k2[3, :, 0, :], kn[1])


class TestFunctionalForwardParity:
    def test_prefill_matches_flax(self, tiny_model):
        cfg, model, params = tiny_model
        toks = jnp.asarray([[5, 9, 2, 40, 7, 1, 33, 12]], jnp.int32)
        flax_logits = model.apply({"params": params}, toks)[0]
        fn_logits, k_seq, v_seq = prefill(params, cfg, toks)
        np.testing.assert_allclose(fn_logits, flax_logits, atol=2e-4)
        assert k_seq.shape == (cfg.n_layers, 8, cfg.n_kv_heads,
                               cfg.head_dim)

    @pytest.mark.slow
    def test_paged_decode_matches_full_forward(self, tiny_model):
        cfg, model, params = tiny_model
        icfg = InferenceConfig(batch_size=2, page_size=4,
                               max_pages_per_seq=8, num_pages=32,
                               prefill_buckets=(8, 16))
        engine = InferenceEngine(params, cfg, icfg)
        try:
            for prompt in ([3, 14, 15, 9, 2], [1, 2]):
                got = engine.generate(prompt, max_new_tokens=8)
                want = naive_greedy(model, params, prompt, 8)
                assert got == want, (prompt, got, want)
        finally:
            engine.shutdown()


class TestContinuousBatching:
    def test_more_requests_than_slots(self, tiny_model):
        cfg, _model, params = tiny_model
        icfg = InferenceConfig(batch_size=2, page_size=4,
                               max_pages_per_seq=8, num_pages=16,
                               prefill_buckets=(8,))
        engine = InferenceEngine(params, cfg, icfg)
        try:
            futs = [engine.submit([i + 1, i + 2], max_new_tokens=6)
                    for i in range(5)]
            outs = [f.result(timeout=120) for f in futs]
            assert all(len(o) == 6 for o in outs)
            st = engine.stats()
            assert st["active"] == 0 and st["queued"] == 0
            assert engine.max_concurrent <= 2
            # all pages returned to the pool
            assert st["free_pages"] == icfg.num_pages - 1
        finally:
            engine.shutdown()

    def test_ragged_prompts_decode_together(self, tiny_model):
        cfg, model, params = tiny_model
        icfg = InferenceConfig(batch_size=3, page_size=4,
                               max_pages_per_seq=8, num_pages=32,
                               prefill_buckets=(8, 16))
        engine = InferenceEngine(params, cfg, icfg)
        try:
            prompts = [[7], [1, 2, 3, 4, 5, 6, 7, 8], [9, 9, 9]]
            futs = [engine.submit(p, max_new_tokens=5) for p in prompts]
            outs = [f.result(timeout=120) for f in futs]
            for p, got in zip(prompts, outs):
                assert got == naive_greedy(model, params, p, 5)
        finally:
            engine.shutdown()

    def test_serve_llm_deployment(self, tiny_model):
        cfg, model, params = tiny_model
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.serve.llm import build_llm_app

        ray_tpu.shutdown()
        ray_tpu.init(num_workers=4)
        try:
            icfg = InferenceConfig(batch_size=2, page_size=4,
                                   max_pages_per_seq=8, num_pages=32,
                                   prefill_buckets=(8,))
            handle = serve.run(build_llm_app(params, cfg, icfg))
            prompt = [4, 8, 15]
            got = ray_tpu.get(handle.generate.remote(prompt, 5),
                              timeout=120.0)
            assert got == naive_greedy(model, params, prompt, 5)
            st = ray_tpu.get(handle.engine_stats.remote())
            assert st["active"] == 0
        finally:
            serve.shutdown()
            ray_tpu.shutdown()

    def test_token_stream_matches_generate(self, tiny_model):
        """submit_stream yields the same tokens generate() returns, in
        multiple increments (small decode_chunk forces several sync
        bursts)."""
        cfg, model, params = tiny_model
        icfg = InferenceConfig(batch_size=2, page_size=4,
                               max_pages_per_seq=8, num_pages=32,
                               prefill_buckets=(8,), decode_chunk=2)
        engine = InferenceEngine(params, cfg, icfg)
        try:
            prompt = [3, 14, 15]
            want = engine.generate(prompt, max_new_tokens=8)
            stream = engine.submit_stream(prompt, max_new_tokens=8)
            got = list(stream)
            assert got == want
            assert stream.result(timeout=10) == want
        finally:
            engine.shutdown()

    @pytest.mark.slow
    def test_serve_llm_stream_polls(self, tiny_model):
        """The Serve replica's poll protocol (start_stream/next_tokens)
        delivers the full generation incrementally across >= 2 polls."""
        cfg, model, params = tiny_model
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.serve.llm import build_llm_app

        ray_tpu.shutdown()
        ray_tpu.init(num_workers=4)
        try:
            icfg = InferenceConfig(batch_size=2, page_size=4,
                                   max_pages_per_seq=8, num_pages=32,
                                   prefill_buckets=(8,), decode_chunk=2)
            handle = serve.run(build_llm_app(params, cfg, icfg))
            prompt = [4, 8, 15]
            # budget > pending-cap x chunk so the engine needs >= 2
            # sync bursts -> the stream observably arrives in pieces
            want = naive_greedy(model, params, prompt, 16)
            sid = ray_tpu.get(handle.start_stream.remote(prompt, 16),
                              timeout=120.0)
            got = []
            polls = 0
            for _ in range(100):
                r = ray_tpu.get(handle.next_tokens.remote(sid),
                                timeout=120.0)
                polls += 1
                got.extend(r["tokens"])
                if r["done"]:
                    break
            assert got == want
            assert polls >= 2  # incremental, not one lump
        finally:
            serve.shutdown()
            ray_tpu.shutdown()

    def test_rejects_oversized(self, tiny_model):
        cfg, _model, params = tiny_model
        icfg = InferenceConfig(batch_size=1, page_size=4,
                               max_pages_per_seq=2, num_pages=8,
                               prefill_buckets=(8,))
        engine = InferenceEngine(params, cfg, icfg)
        try:
            with pytest.raises(ValueError, match="max context"):
                engine.submit([1, 2, 3, 4], max_new_tokens=32)
            with pytest.raises(ValueError, match="empty"):
                engine.submit([])
        finally:
            engine.shutdown()


class TestHTTPStreaming:
    def test_sse_stream_over_http(self, tiny_model):
        """POST /{app}/stream emits incremental Server-Sent Events with
        the generated tokens, ending with done=true."""
        import http.client
        import json as _json

        cfg, model, params = tiny_model
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.serve.llm import build_llm_app

        ray_tpu.shutdown()
        ray_tpu.init(num_workers=4)
        try:
            icfg = InferenceConfig(batch_size=2, page_size=4,
                                   max_pages_per_seq=8, num_pages=32,
                                   prefill_buckets=(8,), decode_chunk=2)
            serve.run(build_llm_app(params, cfg, icfg))
            port = serve.start_http(0)
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=120)
            conn.request("POST", "/llm/stream",
                         body=_json.dumps({"prompt": [4, 8, 15],
                                           "max_new_tokens": 16}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Content-Type") == "text/event-stream"
            tokens = []
            events = 0
            buf = b""
            while True:
                chunk = resp.read(1)
                if not chunk:
                    break
                buf += chunk
                while b"\n\n" in buf:
                    raw, buf = buf.split(b"\n\n", 1)
                    assert raw.startswith(b"data: ")
                    ev = _json.loads(raw[len(b"data: "):])
                    events += 1
                    tokens.extend(ev["tokens"])
                    if ev["done"]:
                        break
            conn.close()
            assert len(tokens) == 16
            # at least one data event; incrementality is pinned by the
            # poll-protocol test (a loaded host can buffer every burst
            # before the first drain, legally yielding one event here)
            assert events >= 1
            # parity with the non-streaming path
            assert tokens == naive_greedy(model, params, [4, 8, 15], 16)
        finally:
            serve.shutdown()
            ray_tpu.shutdown()

    @pytest.mark.slow
    def test_sse_stream_sticky_across_replicas(self, tiny_model):
        """With num_replicas=2 every poll must hit the replica holding
        the stream (sticky sessions) — load-balanced polls would land
        on strangers and drop the stream."""
        import http.client
        import json as _json

        cfg, model, params = tiny_model
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.serve.llm import build_llm_app

        ray_tpu.shutdown()
        ray_tpu.init(num_workers=4)
        try:
            icfg = InferenceConfig(batch_size=2, page_size=4,
                                   max_pages_per_seq=8, num_pages=32,
                                   prefill_buckets=(8,), decode_chunk=2)
            serve.run(build_llm_app(params, cfg, icfg, num_replicas=2))
            port = serve.start_http(0)
            want = naive_greedy(model, params, [4, 8, 15], 12)
            for _ in range(4):  # several streams: routing would flake
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=120)
                conn.request("POST", "/llm/stream",
                             body=_json.dumps({"prompt": [4, 8, 15],
                                               "max_new_tokens": 12}))
                resp = conn.getresponse()
                assert resp.status == 200
                tokens, buf = [], b""
                while True:
                    chunk = resp.read(1)
                    if not chunk:
                        break
                    buf += chunk
                    while b"\n\n" in buf:
                        raw, buf = buf.split(b"\n\n", 1)
                        ev = _json.loads(raw[len(b"data: "):])
                        assert "error" not in ev, ev
                        tokens.extend(ev["tokens"])
                        if ev["done"]:
                            break
                conn.close()
                assert tokens == want
        finally:
            serve.shutdown()
            ray_tpu.shutdown()
