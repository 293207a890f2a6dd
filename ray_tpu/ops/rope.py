"""Rotary position embedding, shared by the training module
(models/transformer.py) and the served forward
(models/decoder_forward.py)."""

from __future__ import annotations

import jax.numpy as jnp


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float) -> jnp.ndarray:
    """Rotary embedding over the last dim of [..., seq, heads, head_dim]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [.., S, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    out = jnp.stack([rx1, rx2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)
