"""ops/moe.py's deployed expert layer (``route_topk``,
``experts_held``): against a loop over experts, the share test of the
model-configs guide (the shares of the experts, with the shared expert
counted once, add up to the uncut layer), blocks, padding and the
picks it counts."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.moe import experts_held, route_topk  # noqa: E402

D, F, E, K = 16, 8, 40, 8


@pytest.fixture(scope="module")
def layer():
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape) / 4, jnp.float32)  # noqa: E731
    return {"router": f(D, E), "w_gate": f(E, D, F), "w_up": f(E, D, F),
            "w_down": f(E, F, D),
            "shared": {"w_gate": f(D, F), "w_up": f(D, F), "w_down": f(F, D)},
            "x": f(50, D) * 4}


def swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def loop_over_experts(p, lo, hi):
    """The definition: for every token, the weighted sum over its picks
    that fall in [lo, hi)."""
    x = p["x"]
    scores = jax.nn.sigmoid(x @ p["router"])
    top = np.argsort(-np.asarray(scores), axis=1)[:, :K]
    y = np.zeros((x.shape[0], D))
    for t in range(x.shape[0]):
        norm = float(scores[t, top[t]].sum())
        for e in top[t]:
            if lo <= e < hi:
                y[t] += float(scores[t, e]) / norm * np.asarray(swiglu(
                    x[t:t + 1], p["w_gate"][e], p["w_up"][e],
                    p["w_down"][e]))[0]
    return y, top


def held(p, lo, hi, **kw):
    ids, w = route_topk(p["x"], p["router"], K)
    return experts_held(p["x"], ids, w, p["w_gate"][lo:hi],
                        p["w_up"][lo:hi], p["w_down"][lo:hi], lo, **kw)


def test_router_picks_k_and_normalises_over_the_picked(layer):
    ids, w = route_topk(layer["x"], layer["router"], K)
    assert ids.shape == w.shape == (50, K) and ids.dtype == jnp.int32
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    _, top = loop_over_experts(layer, 0, E)
    assert (np.sort(np.asarray(ids), 1) == np.sort(top, 1)).all()
    assert w.dtype == jnp.float32          # whatever the model's type
    ids16, _ = route_topk(layer["x"].astype(jnp.bfloat16),
                          layer["router"].astype(jnp.bfloat16), K)
    assert ids16.shape == (50, K)


@pytest.mark.parametrize("lo, hi", [(0, E), (0, 5), (5, 10), (35, 40)])
def test_held_experts_against_a_loop_over_experts(layer, lo, hi):
    y, counts = held(layer, lo, hi)
    want, top = loop_over_experts(layer, lo, hi)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-6)
    np.testing.assert_array_equal(
        np.asarray(counts), [(top == e).sum() for e in range(lo, hi)])


def test_the_shares_add_up_to_the_uncut_layer(layer):
    """Eight chips, five experts each, every one routing over all 40:
    their parts, plus the shared expert ONCE, are the whole layer."""
    parts = [held(layer, lo, lo + 5) for lo in range(0, E, 5)]
    sh = layer["shared"]
    shared = swiglu(layer["x"], sh["w_gate"], sh["w_up"], sh["w_down"])
    whole, _ = loop_over_experts(layer, 0, E)
    np.testing.assert_allclose(
        np.asarray(sum(y for y, _ in parts) + shared),
        whole + np.asarray(shared), atol=5e-6)
    # no pick dropped, none counted twice
    assert sum(int(c.sum()) for _, c in parts) == 50 * K
    # a share alone is not the layer (the test can fail)
    assert np.abs(np.asarray(parts[0][0]) - whole).max() > 1e-3


def test_sixteen_shares_with_a_routed_scale_add_up(layer):
    """The latent-attention model's deployment at a small size: a
    router of 32, 16 chips of 2 experts each, the picked experts'
    normalised weights scaled by 2.5, the shared expert unscaled and
    counted ONCE: the parts add up to the uncut layer."""
    p = dict(layer, router=layer["router"][:, :32],
             **{w: layer[w][:32] for w in ("w_gate", "w_up", "w_down")})
    x = p["x"]
    ids, w = route_topk(x, p["router"], K, 2.5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, atol=1e-6)
    ids1, w1 = route_topk(x, p["router"], K)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids1))
    np.testing.assert_allclose(np.asarray(w), 2.5 * np.asarray(w1),
                               rtol=1e-6)
    parts = [experts_held(x, ids, w, p["w_gate"][lo:lo + 2],
                          p["w_up"][lo:lo + 2], p["w_down"][lo:lo + 2], lo)
             for lo in range(0, 32, 2)]
    assert len(parts) == 16
    sh = p["shared"]
    shared = np.asarray(swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"]))
    whole, _ = loop_over_experts(p, 0, 32)
    np.testing.assert_allclose(
        np.asarray(sum(y for y, _ in parts)) + shared,
        2.5 * whole + shared, atol=1e-5)
    assert sum(int(c.sum()) for _, c in parts) == 50 * K
    # the shared expert scaled too would be another layer
    assert np.abs(2.5 * shared - shared).max() > 1e-3


def test_no_capacity_every_pick_on_one_expert_is_computed(layer):
    """All tokens alike: all 50 pick the same experts; nothing drops."""
    p = dict(layer, x=jnp.tile(layer["x"][:1], (50, 1)))
    y, counts = held(p, 0, E)
    want, _ = loop_over_experts(p, 0, E)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-6)
    assert sorted(np.asarray(counts))[-K:] == [50] * K


def test_blocks_of_tokens_give_the_same(layer):
    y, counts = held(layer, 5, 25)
    yb, cb = held(layer, 5, 25, block_tokens=16)     # 50 tokens: 4 blocks
    np.testing.assert_allclose(np.asarray(yb), np.asarray(y), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(cb), np.asarray(counts))


def test_padding_is_neither_computed_nor_counted(layer):
    valid = jnp.arange(50) < 30
    y, counts = held(layer, 0, 20, valid=valid)
    want, top = loop_over_experts(layer, 0, 20)
    np.testing.assert_allclose(np.asarray(y)[:30], want[:30], atol=2e-6)
    assert not np.asarray(y)[30:].any()
    np.testing.assert_array_equal(
        np.asarray(counts), [(top[:30] == e).sum() for e in range(20)])


def test_jits_and_runs_in_the_models_type(layer):
    bf = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), layer)
    y, _ = jax.jit(lambda p: held(p, 0, 20))(bf)
    want, _ = loop_over_experts(layer, 0, 20)
    assert y.dtype == jnp.bfloat16
    assert np.abs(np.asarray(y, np.float32) - want).max() < 0.1


def test_eight_shares_with_a_selection_bias_add_up(layer):
    """The window model's deployment at a small size: a router of 40
    whose 4 picks are the largest of ``score + bias`` and are weighed
    by their scores alone (normalised over the picked, scaled by
    2.448), 8 chips of 5 experts each, the shared expert unscaled and
    counted ONCE: the parts add up to the uncut layer, by a loop over
    experts that knows no program; the bias changes the picks, and a
    bias that also weighed would be another layer."""
    p, x, k, scale = layer, layer["x"], 4, 2.448
    bias = jnp.asarray(np.random.default_rng(1).normal(size=E) * 0.1,
                       jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    top = np.argsort(-(scores + np.asarray(bias)), axis=1)[:, :k]
    whole = np.zeros((x.shape[0], D))
    for t in range(x.shape[0]):
        norm = scores[t, top[t]].sum() + 1e-20
        for e in top[t]:
            whole[t] += scale * scores[t, e] / norm * np.asarray(swiglu(
                x[t:t + 1], p["w_gate"][e], p["w_up"][e],
                p["w_down"][e]))[0]
    ids, w = route_topk(x, p["router"], k, scale, bias)
    assert (np.sort(np.asarray(ids), 1) == np.sort(top, 1)).all()
    np.testing.assert_allclose(np.asarray(w.sum(-1)), scale, atol=1e-5)
    plain_ids, _ = route_topk(x, p["router"], k, scale)
    changed = (np.sort(np.asarray(ids), 1)
               != np.sort(np.asarray(plain_ids), 1)).any(axis=1)
    assert changed.mean() > 0.2             # the bias changes the picks
    parts = [experts_held(x, ids, w, p["w_gate"][lo:lo + 5],
                          p["w_up"][lo:lo + 5], p["w_down"][lo:lo + 5], lo)
             for lo in range(0, E, 5)]
    assert len(parts) == 8
    sh = p["shared"]
    shared = np.asarray(swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"]))
    np.testing.assert_allclose(
        np.asarray(sum(y for y, _ in parts)) + shared, whole + shared,
        atol=1e-5)
    assert sum(int(c.sum()) for _, c in parts) == 50 * k
    # weights taken from score + bias would be another layer
    biased = np.take_along_axis(scores + np.asarray(bias), np.asarray(ids), 1)
    assert np.abs(scale * biased / biased.sum(-1, keepdims=True)
                  - np.asarray(w)).max() > 1e-3


def test_a_zero_bias_selects_what_no_bias_selects(layer):
    ids, w = route_topk(layer["x"], layer["router"], K, 2.5)
    ids0, w0 = route_topk(layer["x"], layer["router"], K, 2.5,
                          jnp.zeros((E,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids0))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w0), rtol=1e-6)


# ----------------------------------------------------------------------
# the grouped products: one Pallas call over the held groups' rows
# ----------------------------------------------------------------------

def _picks(case, rng):
    """ids [T, 8] of one of four routings onto 40 held experts (ids
    0-39; the others are held elsewhere)."""
    if case == "decode":        # 64 slots, 3 local picks in 2 experts
        ids = np.full((64, K), 100)
        ids[5, 2], ids[40, 7], ids[63, 0] = 7, 7, 28
        return ids
    if case == "no_local":      # a prompt's block, every pick elsewhere
        return rng.integers(40, 320, size=(1024, K))
    if case == "all_local":
        return np.stack([rng.permutation(E)[:K] for _ in range(1024)])
    return np.full((1024, K), 17)        # every pick on one expert


CASES = ["decode", "no_local", "all_local", "one_expert"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_products_against_ragged_dot_over_the_held_groups(
        case, dtype):
    """The kernel against ``jax.lax.ragged_dot`` on the rows of the held
    groups, the picks sorted by expert as the layer sorts them; rows
    past the last group hold NaN, which no held row may see."""
    from ray_tpu.ops.moe import grouped_matmul

    rng = np.random.default_rng(3)
    ids = _picks(case, rng).reshape(-1)
    m = ids.size
    sizes = jnp.asarray(np.bincount(ids[ids < E], minlength=E), jnp.int32)
    held = int(sizes.sum())
    lhs = rng.normal(size=(m, D))
    lhs[held:] = np.nan
    lhs = jnp.asarray(lhs, dtype)
    rhs = jnp.asarray(rng.normal(size=(E, D, F)) / 4, dtype)
    got = np.asarray(grouped_matmul(lhs, rhs, sizes), np.float32)[:held]
    want = np.asarray(jax.lax.ragged_dot(lhs, rhs, sizes),
                      np.float32)[:held]
    assert grouped_matmul(lhs, rhs, sizes).dtype == dtype
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6,
                               atol=1e-5)


def _poisoned(monkeypatch):
    """The kernel, with NaN written into every row past the last held
    group of what it returns: rows it leaves undefined."""
    from ray_tpu.ops import moe

    real = moe.grouped_matmul

    def poisoned(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes)
        past = jnp.arange(out.shape[0]) >= sizes.sum()
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(moe, "grouped_matmul", poisoned)


@pytest.mark.parametrize("case", CASES)
def test_held_experts_on_the_kernel_path_with_a_poisoned_tail(
        layer, case, monkeypatch):
    """``experts_held`` through the kernel, against the sum over each
    token's held picks computed directly; the kernel's rows past the
    last held group are NaN and must not reach ``y``. The long cases
    go in two blocks."""
    _poisoned(monkeypatch)
    rng = np.random.default_rng(4)
    ids = _picks(case, rng)
    t = ids.shape[0]
    x = jnp.asarray(rng.normal(size=(t, D)), jnp.float32)
    w = jnp.asarray(rng.random(size=(t, K)), jnp.float32)
    y, counts = experts_held(x, jnp.asarray(ids, jnp.int32), w,
                             layer["w_gate"], layer["w_up"],
                             layer["w_down"], 0, block_tokens=512)
    on = ids < E
    e = np.where(on, ids, 0)
    g = np.einsum("td,tkdf->tkf", x, np.asarray(layer["w_gate"])[e])
    u = np.einsum("td,tkdf->tkf", x, np.asarray(layer["w_up"])[e])
    h = np.asarray(jax.nn.silu(g)) * u
    out = np.einsum("tkf,tkfd->tkd", h, np.asarray(layer["w_down"])[e])
    want = (out * (np.asarray(w) * on)[:, :, None]).sum(1)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.bincount(ids[on], minlength=E))


def test_the_layer_runs_the_kernel_and_no_ragged_dot(layer):
    jaxpr = str(jax.make_jaxpr(lambda p: held(p, 0, 20))(layer))
    assert "moe_grouped" in jaxpr and "ragged_dot" not in jaxpr


@pytest.mark.parametrize("name, m, d, f", [
    ("solar decode", 512, 4096, 1280), ("solar prefill", 65536, 4096, 1280),
    ("openpangu decode", 256, 7680, 2048),
    ("openpangu prefill", 32768, 7680, 2048),
    ("trinity decode", 128, 3072, 3072),
    ("trinity prefill", 65536, 3072, 3072)])
def test_tiling_for_each_configuration(name, m, d, f):
    """Both matrices' tilings at the three served configurations'
    widths, a decode step's rows and a prompt block's: whole row tiles,
    whole lane-aligned column blocks, the contraction in one block of
    at most 16 MiB, the row tile by the rows alone (so the three
    products of a block visit the same tiles), and a decode step's
    rows in tiles of 128."""
    from ray_tpu.ops.moe import grouped_tiling

    tilings = [grouped_tiling(m, k, n, 2) for k, n in ((d, f), (f, d))]
    for (tm, tn), (k, n) in zip(tilings, ((d, f), (f, d))):
        assert m % tm == 0 and n % tn == 0 and tn % 128 == 0
        assert k * tn * 2 <= 16 << 20
        assert tn >= 1024                 # few column blocks a matrix
    assert tilings[0][0] == tilings[1][0] == (128 if m <= 512 else 256)
