"""ops/kda.py: the chunkwise gated delta rule against the recurrence
taken one position at a time in float64, the one-step form, padding,
and the short convolution with its tail."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import kda  # noqa: E402

H, DK, DV = 3, 8, 6


def inputs(seed, n, s, g_scale=3.0, beta_max=2.0, h=H):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q = kda.l2norm(f(n, s, h, DK)) / np.sqrt(DK)
    k = kda.l2norm(f(n, s, h, DK))
    v = f(n, s, h, DV)
    g = -jnp.asarray(rng.uniform(0, g_scale, (n, s, h, DK)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, beta_max, (n, s, h)), jnp.float32)
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta, state=None):
    """Token by token, float64: the definition."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    n, s, h = q.shape[:3]
    st = (np.zeros((n, h, DK, DV)) if state is None
          else np.asarray(state, np.float64))
    out = np.zeros((n, s, h, DV))
    for t in range(s):
        st = st * np.exp(g[:, t])[..., None]
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("nhk,nhkv->nhv", k[:, t], st))
        st = st + k[:, t][..., None] * u[..., None, :]
        out[:, t] = np.einsum("nhk,nhkv->nhv", q[:, t], st)
    return out, st


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("s", [37, 64, 100])
def test_chunkwise_matches_the_recurrence(chunk, s):
    x = inputs(chunk * 1000 + s, 2, s)
    o, st = kda.kda_chunked(*x, chunk=chunk)
    ro, rst = recurrence(*x)
    np.testing.assert_allclose(np.asarray(o), ro, atol=5e-6)
    np.testing.assert_allclose(np.asarray(st), rst, atol=5e-6)


def test_beta_of_two_reaches_the_negative_eigenvalue():
    q, k, v, g, _ = inputs(7, 1, 48, g_scale=0.05)
    beta = jnp.full((1, 48, H), 2.0, jnp.float32)
    o, st = kda.kda_chunked(q, k, v, g, beta, chunk=16)
    ro, rst = recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(o), ro, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st), rst, atol=2e-5)


def test_a_channel_may_decay_by_any_factor_inside_a_chunk():
    """exp(-40) a step, 64 steps a chunk: a K / exp(G) formulation
    overflows float32; differences of G never do."""
    q, k, v, g, beta = inputs(11, 1, 128, g_scale=1.0)
    g = g.at[:, :, 0, :4].set(-40.0)
    o, st = kda.kda_chunked(q, k, v, g, beta, chunk=64)
    ro, rst = recurrence(q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), ro, atol=5e-6)
    np.testing.assert_allclose(np.asarray(st), rst, atol=5e-6)


@pytest.mark.parametrize("lens", [(5, 29), (16, 1), (32, 31)])
def test_padding_does_not_touch_the_state(lens):
    """Rows of a padded bucket: the state after the bucket is the state
    after each row's own length."""
    s = 32
    q, k, v, g, beta = inputs(sum(lens), 2, s)
    gm, bm = kda.pad_mask(g, beta, jnp.asarray(lens))
    o, st = kda.kda_chunked(q, k, v, gm, bm, chunk=8)
    for n, ln in enumerate(lens):
        ro, rst = recurrence(*(a[n:n + 1, :ln] for a in (q, k, v, g, beta)))
        np.testing.assert_allclose(np.asarray(o)[n, :ln], ro[0], atol=5e-6)
        np.testing.assert_allclose(np.asarray(st)[n], rst[0], atol=5e-6)


@pytest.mark.parametrize("split", [1, 20, 39])
def test_prefill_then_decode_continues_the_state(split):
    q, k, v, g, beta = inputs(split, 2, 40)
    full, last = kda.kda_chunked(q, k, v, g, beta, chunk=16)
    o, st = kda.kda_chunked(*(a[:, :split] for a in (q, k, v, g, beta)),
                            chunk=16)
    outs = [o]
    for t in range(split, 40):
        ot, st = kda.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                              beta[:, t], st)
        outs.append(ot[:, None])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(full), atol=5e-6)
    np.testing.assert_allclose(np.asarray(st), np.asarray(last), atol=5e-6)


def test_a_given_state_is_carried_on():
    q, k, v, g, beta = inputs(3, 1, 24)
    s0 = jnp.asarray(np.random.default_rng(0).normal(size=(1, H, DK, DV)),
                     jnp.float32)
    o, st = kda.kda_chunked(q, k, v, g, beta, s0, chunk=8)
    ro, rst = recurrence(q, k, v, g, beta, s0)
    np.testing.assert_allclose(np.asarray(o), ro, atol=5e-6)
    np.testing.assert_allclose(np.asarray(st), rst, atol=5e-6)


@pytest.mark.parametrize("s", [1, 5, 13])
def test_a_row_shorter_than_one_chunk(s):
    """The kernel's own chunk (64): the row is padded with no-ops."""
    x = inputs(40 + s, 2, s)
    o, st = kda.kda_chunked(*x)
    ro, rst = recurrence(*x)
    np.testing.assert_allclose(np.asarray(o), ro, atol=5e-6)
    np.testing.assert_allclose(np.asarray(st), rst, atol=5e-6)


@pytest.mark.parametrize("lens", [(96, 33, 1), (64, 65, 128), (7, 100, 63)])
def test_rows_of_different_lengths_in_one_call(lens):
    """N > 1 rows at the kernel's own chunk, each with its own length
    and its own given state: a row's chunks past its length (whole
    no-op chunks among them) leave its state alone."""
    s = 128
    q, k, v, g, beta = inputs(sum(lens), 3, s)
    s0 = jnp.asarray(np.random.default_rng(1).normal(size=(3, H, DK, DV)),
                     jnp.float32)
    gm, bm = kda.pad_mask(g, beta, jnp.asarray(lens))
    o, st = kda.kda_chunked(q, k, v, gm, bm, s0)
    for n, ln in enumerate(lens):
        ro, rst = recurrence(*(a[n:n + 1, :ln] for a in (q, k, v, g, beta)),
                             s0[n:n + 1])
        np.testing.assert_allclose(np.asarray(o)[n, :ln], ro[0], atol=5e-6)
        np.testing.assert_allclose(np.asarray(st)[n], rst[0], atol=5e-6)


@pytest.mark.parametrize("g_scale", [0.02, 1.0])
def test_a_segment_of_the_engine_at_four_heads(g_scale):
    """2,048 positions, the engine's segment, through 32 chunks of the
    kernel with the state carried in its scratch from the first to the
    last; the slow decay keeps the early chunks' state alive to the
    end."""
    x = inputs(2048, 1, 2048, g_scale=g_scale, h=4)
    o, st = kda.kda_chunked(*x)
    ro, rst = recurrence(*x)
    np.testing.assert_allclose(np.asarray(o), ro, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st), rst, atol=2e-5)


@pytest.mark.parametrize("h", [3, 5])
def test_heads_are_read_where_they_lie(h):
    """[N,S,H,*] goes to the kernel as it is, a head's rows H apart: a
    head count that is no whole tile gives each head what it gets
    alone."""
    x = inputs(70 + h, 2, 70, h=h)
    o, st = kda.kda_chunked(*x)
    for i in range(h):
        oi, sti = kda.kda_chunked(*(a[:, :, i:i + 1] for a in x))
        np.testing.assert_allclose(np.asarray(o)[:, :, i:i + 1],
                                   np.asarray(oi), atol=1e-6)
        np.testing.assert_allclose(np.asarray(st)[:, i:i + 1],
                                   np.asarray(sti), atol=1e-6)


@pytest.mark.parametrize("live,h,heads", [
    ((), 3, None), ((4,), 3, None), ((0, 2, 5, 6), 3, None),
    (tuple(range(7)), 3, None), ((1, 3, 6), 16, 8)])
def test_the_decode_kernel_touches_the_live_slots_alone(live, h, heads,
                                                        monkeypatch):
    """``kda_decode_step`` over 7 slots, four steps in a row, against
    ``kda_step`` followed by the ``where`` it replaced: a live slot's
    state and output to float32's rounding, a dead slot's state bitwise
    as it was and its output zeros. The last case takes 16 heads in two
    blocks of 8, so a slot is two grid steps."""
    if heads:
        monkeypatch.setattr(kda, "_DECODE_HEADS", heads)
    b = 7
    mask = jnp.asarray([i in live for i in range(b)])
    rng = np.random.default_rng(len(live) * 10 + h)
    state = jnp.asarray(rng.normal(size=(b, h, DK, DV)), jnp.float32)
    want = state
    step = jax.jit(kda.kda_decode_step)
    for t in range(4):
        q, k, v, g, beta = (a[:, 0] for a in inputs(100 * h + t, b, 1, h=h))
        o, new = step(q, k, v, g, beta, state, mask)
        ro, rs = kda.kda_step(q, k, v, g, beta, want)
        want = jnp.where(mask[:, None, None, None], rs, want)
        dead = ~np.asarray(mask)
        np.testing.assert_array_equal(np.asarray(new)[dead],
                                      np.asarray(state)[dead])
        np.testing.assert_array_equal(np.asarray(o)[dead], 0.0)
        np.testing.assert_allclose(np.asarray(new), np.asarray(want),
                                   atol=2e-6)
        np.testing.assert_allclose(
            np.asarray(o)[~dead], np.asarray(ro)[~dead], atol=2e-6)
        state = new


def test_short_convolution_and_its_tail():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 12, 5)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
    y = np.asarray(kda.short_conv(x, w))
    xn, wn = np.asarray(x), np.asarray(w)
    for t in range(12):
        want = sum(wn[3 - j] * xn[:, t - j] for j in range(4) if t - j >= 0)
        np.testing.assert_allclose(y[:, t], want, atol=1e-6)
    # the tail of a row of 7 (and of 2: zeros before position 0) lets
    # the one-step form continue where the prompt's convolution ended
    lens = jnp.asarray([7, 2])
    tail = kda.conv_tail(x, lens, 4)
    for n, ln in enumerate((7, 2)):
        t = tail[n:n + 1]
        for pos in range(ln, 12):
            yt, t = kda.short_conv_step(x[n:n + 1, pos], w, t)
            if ln == 7:      # row 0's later inputs are the same x
                np.testing.assert_allclose(np.asarray(yt)[0], y[n, pos],
                                           atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail)[1, 0], 0.0)
    np.testing.assert_allclose(np.asarray(tail)[1, 1:], xn[1, :2])
