"""Idle decode slots all park their dummy token in ONE cell of the
parking page. ``append_token_kv`` has to overwrite that cell, not
scale it by ``1 - (idle slots)``: scaled, the cell overflows after a
few hundred steps without a prefill launch to rewrite the page (the
drain of a run with long answers), and ``0 x inf`` at the masked
positions of every page table that names the parking page turns all
live slots' attention into NaN (PERF.md, PR 27: how the decode-heavy
cell read a logit gap of 5-8)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.paged_attention import (append_token_kv,  # noqa: E402
                                         paged_attention_reference)

P, KV, PAGE, D, B = 9, 2, 4, 8, 6
PARKING = P - 1


def run_steps(dtype, steps):
    """Slot 0 live on pages 0-2, five idle slots on the parking page,
    lengths restarting at 0 every 8 steps as a burst's upload does."""
    kp = jnp.zeros((P, KV, PAGE, D), dtype)
    vp = kp
    table = jnp.full((B, 3), PARKING, jnp.int32).at[0].set(
        jnp.asarray([0, 1, PARKING]))
    rng = np.random.default_rng(0)
    step = jax.jit(append_token_kv)
    live_len = 0
    for t in range(steps):
        new = jnp.asarray(rng.normal(size=(B, KV, D)), dtype)
        lens = jnp.asarray([live_len % 8] + [t % 8] * (B - 1))
        kp, vp = step(kp, vp, new, new, table, lens)
        live_len += 1
    return kp, vp, table


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_parking_cell_is_overwritten_not_scaled(dtype):
    kp, vp, table = run_steps(dtype, 800)
    parking = np.asarray(kp[PARKING], np.float32)
    assert np.isfinite(parking).all()
    # the sum of five dummy tokens of unit scale, never a power of 4
    assert np.abs(parking).max() < 5 * 6.0


def test_live_slots_stay_finite_beside_idle_ones():
    kp, vp, table = run_steps(jnp.float32, 800)
    q = jnp.ones((B, 4, D), jnp.float32)
    out = paged_attention_reference(q, kp, vp, table,
                                    jnp.asarray([6, 1, 1, 1, 1, 1]))
    assert np.isfinite(np.asarray(out)).all()


def test_a_unique_cell_gets_exactly_its_token():
    kp = jnp.ones((P, KV, PAGE, D), jnp.float32)
    new = jnp.full((2, KV, D), 7.0, jnp.float32)
    table = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    out, _ = append_token_kv(kp, kp, new, new, table, jnp.asarray([5, 2]))
    want = np.ones((P, KV, PAGE, D), np.float32)
    want[1, :, 1] = 7.0
    want[2, :, 2] = 7.0
    np.testing.assert_array_equal(np.asarray(out), want)


# ----------------------------------------------------------------------
# the in-place append against a loop that writes cell by cell
# ----------------------------------------------------------------------

def plain_append(pool, new, table, lens, page):
    """What ``append_token_kv`` means, cell by cell in slot order (the
    last writer of a shared cell stays)."""
    pool = pool.copy()
    for b in range(len(lens)):
        pool[table[b, lens[b] // page], :, lens[b] % page] = new[b]
    return pool


def geometry(page, idle):
    """4 live slots on pages of their own and ``idle`` slots whose
    whole table is the parking page; 3 pages a sequence."""
    live, mp = 4, 3
    n_pages = live * mp + 1
    table = np.full((live + idle, mp), n_pages - 1, np.int32)
    table[:live] = np.arange(live * mp).reshape(live, mp)
    return n_pages, table


@pytest.mark.parametrize("idle", [0, 3], ids=["all-live", "idle-slots"])
@pytest.mark.parametrize("page", [4, 16, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_append_writes_what_a_cell_by_cell_loop_writes(dtype, page, idle):
    kv, d = 2, 8
    n_pages, table = geometry(page, idle)
    parking = n_pages - 1
    b = len(table)
    rng = np.random.default_rng(page + idle)
    start = jnp.asarray(rng.normal(size=(n_pages, kv, page, d)), dtype)
    # live slots at lengths of their own (a page's first and last row
    # among them), idle slots all at one length
    lens = np.asarray([0, page - 1, page, 2 * page + 1] + [2] * idle,
                      np.int32)
    news = [jnp.asarray(rng.normal(size=(b, kv, d)), dtype)
            for _ in range(4)]
    bits = lambda x: np.asarray(x).view(  # noqa: E731
        np.uint32 if dtype == jnp.float32 else np.uint16)

    # one step: bit for bit on every cell a live slot owns and on every
    # cell no slot named; the parking cell holds ONE idle slot's token
    k1, v1 = append_token_kv(start, start, news[0], news[1],
                             jnp.asarray(table), jnp.asarray(lens))
    same = np.ones((n_pages, page), bool)
    same[parking, 2] = not idle         # the one cell idle slots share
    for got, new in ((k1, news[0]), (v1, news[1])):
        want = plain_append(np.asarray(start), np.asarray(new), table, lens,
                            page)
        np.testing.assert_array_equal(
            bits(got).transpose(0, 2, 1, 3)[same],
            bits(want).transpose(0, 2, 1, 3)[same])
        if idle:
            assert any(np.array_equal(bits(got)[parking, :, 2], bits(new)[i])
                       for i in range(4, b))

    # 4 steps under jit with donated pools inside a scan = 4 eager calls
    def chunk(kp, vp, new_k, new_v, table, lens):
        def body(carry, new):
            kp, vp, lens = carry
            kp, vp = append_token_kv(kp, vp, new[0], new[1], table, lens)
            return (kp, vp, lens + 1), None
        (kp, vp, _), _ = jax.lax.scan(body, (kp, vp, lens),
                                      (new_k, new_v))
        return kp, vp

    lens4 = np.minimum(lens, 3 * page - 4)      # room for 4 more tokens
    ek, ev = start, start
    for t in range(4):
        ek, ev = append_token_kv(ek, ev, news[t], news[3 - t],
                                 jnp.asarray(table), jnp.asarray(lens4 + t))
    new_k, new_v = jnp.stack(news), jnp.stack(news[::-1])
    jk, jv = jax.jit(chunk, donate_argnums=(0, 1))(
        start + 0, start + 0, new_k, new_v, jnp.asarray(table),
        jnp.asarray(lens4))
    np.testing.assert_array_equal(bits(jk), bits(ek))
    np.testing.assert_array_equal(bits(jv), bits(ev))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_parking_cell_holds_one_idle_slots_token(dtype):
    """After 800 steps the cell idle slots share is finite and is the
    LAST step's token of one of them, not a sum and not a product."""
    steps = 800
    kp, vp, table = run_steps(dtype, steps)
    rng = np.random.default_rng(0)
    for _ in range(steps):
        last = jnp.asarray(rng.normal(size=(B, KV, D)), dtype)
    cell = np.asarray(kp[PARKING, :, (steps - 1) % 8 % PAGE], np.float32)
    assert np.isfinite(np.asarray(kp, np.float32)).all()
    assert any(np.array_equal(cell, np.asarray(last[i], np.float32))
               for i in range(1, B))


def test_a_length_past_the_page_table_writes_nothing():
    """A finished slot decoding out a burst may run past its table: the
    one-hot form wrote nothing then (no page matched), and the kernel,
    which moves the slot's last page whatever its length, hands it back
    as it came."""
    kp = jnp.ones((P, KV, PAGE, D), jnp.float32)
    new = jnp.full((3, KV, D), 7.0, jnp.float32)
    table = jnp.asarray([[0, 1], [2, 3], [4, 5]], jnp.int32)
    out, _ = append_token_kv(kp, kp, new, new, table,
                             jnp.asarray([2 * PAGE, 1, PAGE + 1]))
    want = np.ones((P, KV, PAGE, D), np.float32)
    want[2, :, 1] = 7.0
    want[5, :, 1] = 7.0
    np.testing.assert_array_equal(np.asarray(out), want)
