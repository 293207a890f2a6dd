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
