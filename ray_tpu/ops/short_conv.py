"""Short causal depthwise convolution over positions, and the tail of
inputs that a prompt hands to the decode steps.

Two mixers run it: the delta rule (``ops/kda.py``) over its q, k, v
projections before the recurrence, and the gated short convolution of
``models/decoder_forward.py`` (mixer ``conv``) over ``B * x``. A
convolution of ``K`` taps at position ``t`` needs the inputs of ``t -
K + 1 .. t``: a prompt's pass runs over the whole row (``short_conv``),
keeps its last ``K - 1`` inputs (``conv_tail``), and each decode step
takes one position against that tail and hands on the next
(``short_conv_step``). Before position 0 every input is zero, so every
sequence starts from zeros.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp


def short_conv(x: jnp.ndarray, w: jnp.ndarray,
               tail: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Causal depthwise convolution over positions. x [N,S,C], w [K,C]
    (``w[K-1]`` multiplies the current position), ``tail`` [N,K-1,C] the
    inputs before position 0 (zeros when None). Returns [N,S,C]."""
    n, s, c = x.shape
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((n, k - 1, c), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    return sum(xp[:, i:i + s] * w[i].astype(x.dtype) for i in range(k))


def conv_tail(x: jnp.ndarray, lens: jnp.ndarray, k: int) -> jnp.ndarray:
    """The last ``k - 1`` inputs before position ``lens[n]`` of each row
    of x [N,S,C] (zeros before position 0): what the convolution of
    position ``lens[n]`` needs of the prompt. Returns [N,k-1,C]."""
    n, _s, c = x.shape
    xp = jnp.concatenate([jnp.zeros((n, k - 1, c), x.dtype), x], axis=1)
    idx = lens[:, None] + jnp.arange(k - 1)[None, :]      # in xp's frame
    return jnp.take_along_axis(xp, idx[:, :, None], axis=1)


def short_conv_step(x: jnp.ndarray, w: jnp.ndarray, tail: jnp.ndarray,
                    precision=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One position: x [B,C], tail [B,K-1,C] -> (y [B,C], new tail).
    ``precision`` is that of the sum over the taps, a contraction the
    chip may run on its matrix unit (``Precision.HIGHEST`` keeps a
    float32 input float32 there)."""
    win = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    y = jnp.einsum("bkc,kc->bc", win, w.astype(x.dtype),
                   precision=precision)
    return y, win[:, 1:]
