"""Causal attention of a prompt for latent-attention (MLA) layers in
their published, expanded form: every head has its own keys of width
``nope + rope`` (192 at the published sizes) and values of another
width (128). ``ops/flash.py``'s library kernel wants one width for all
three and a multiple of 128, and fixes the softmax scale to the width it
is given; padded to 256 it would compute 512 columns a score-and-value
pair where 320 are asked for, and hold three padded copies of a
launch's heads. This is a flash forward of its own for the two widths,
at the true scale: grid (rows, heads, query blocks, key blocks), online
softmax in float32, blocks above the diagonal neither fetched nor
computed. No backward: the serving path's.

The same forward serves a WINDOW layer's prompt
(``window_prefill_attention``): both widths 128, a causal band in place
of the triangle (row ``i`` sees ``i - window < j <= i``), so the grid's
last axis walks the blocks the band crosses and no others, and each of
a key head's G query heads reads that head's keys and values where
they lie (the index map divides the head by G: no repeated copy).

The trace keeps each call's name, ``mla_prefill_attention`` and
``window_prefill_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30
BLOCK = 512


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block: int, window: int = 0):
    import jax.experimental.pallas as pl

    qb, kb = pl.program_id(2), pl.program_id(3)
    first = kb == 0
    if window:
        # the last axis counts from the band's lowest block of this
        # row of blocks (block 0 for the rows nearer the start)
        kb += jnp.maximum(qb - (pl.num_programs(3) - 1), 0)

    @pl.when(first)
    def _start():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kb <= qb)
    def _block():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, bk]
        # blocks are square: a key lies past a row on the diagonal only,
        # and masking that block alone measured no faster (PERF.md
        # section 6, PR 31)
        col = kb * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = qb * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        seen = col <= row
        if window:
            seen &= col > row - window
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == qb)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _flash_forward(q, k, v, *, scale: float, window: int, interpret: bool,
                   name: str) -> jnp.ndarray:
    """q [N,H,S,Dk]; k [N,KV,S,Dk], v [N,KV,S,Dv] with H a multiple of
    KV -> [N,H,S,Dv]: causal, and with a ``window`` banded."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, s, dk = q.shape
    dv = v.shape[-1]
    group = h // k.shape[1]
    block = min(BLOCK, -(-s // 128) * 128)
    pad = (-s) % block
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for a in (q, k, v))
    nb = (s + pad) // block
    # blocks of keys a row of blocks can see: all up to its own, or
    # those a band of ``window`` crosses (its lowest row sees window -
    # 1 keys back)
    nk = min(nb, -(-(window - 1) // block) + 1) if window else nb

    def rows(width):
        return pl.BlockSpec((1, 1, block, width),
                            lambda b, hd, qb, kb: (b, hd, qb, 0))

    def cols(width):
        # the last axis counts from the lowest block this row of blocks
        # sees (block 0 without a window); past the diagonal the block
        # index stands still: nothing new is fetched for the steps that
        # compute nothing. A key head serves its ``group`` query heads
        return pl.BlockSpec(
            (1, 1, block, width),
            lambda b, hd, qb, kb: (
                b, hd // group,
                jnp.minimum(kb + jnp.maximum(qb - (nk - 1), 0), qb), 0))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block, window=window),
        grid=(n, h, nb, nk),
        in_specs=[rows(dk), cols(dk), cols(dv)],
        out_specs=rows(dv),
        out_shape=jax.ShapeDtypeStruct((n, h, s + pad, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name,
    )(q, k, v)
    return out[:, :, :s] if pad else out


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          *, scale: float, interpret: bool = False
                          ) -> jnp.ndarray:
    """q, k [N,H,S,Dk], v [N,H,S,Dv] -> causal softmax(q k^T * scale) v
    [N,H,S,Dv] in q's type. S pads to the block inside (a padded key
    lies above every real row's diagonal, padded rows are cut off)."""
    return _flash_forward(q, k, v, scale=scale, window=0,
                          interpret=interpret, name="mla_prefill_attention")


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def window_prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             *, window: int, interpret: bool = False
                             ) -> jnp.ndarray:
    """q [N,H,S,D], k, v [N,KV,S,D] (H a multiple of KV) -> softmax(q
    k^T / sqrt(D) + band) v [N,H,S,D] in q's type, row ``i`` seeing ``i
    - window < j <= i``. Blocks wholly above the diagonal or wholly
    below the band are neither fetched nor computed."""
    return _flash_forward(q, k, v, scale=q.shape[-1] ** -0.5, window=window,
                          interpret=interpret,
                          name="window_prefill_attention")
