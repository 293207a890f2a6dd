"""Gated delta rule with a per-channel decay: the recurrence of Kimi
Delta Attention (arXiv:2510.26692), the linear-attention mixer of
hybrid decoders whose other layers are softmax attention.

A head keeps a state ``S`` of ``[dk, dv]`` in float32. One position
with key ``k`` (unit length), value ``v``, query ``q``, log decay
``g <= 0`` a key channel and step size ``beta`` in (0, 2):

    S' = diag(exp(g)) S
    S  = S' + beta * k (v - k^T S')^T      # (I - beta k k^T) S' + beta k v^T
    o  = S^T q

Two forms of the same function:

- ``kda_step``: one position for every slot of a decode batch.
- ``kda_chunked``: a whole prompt, ``chunk`` positions at a time. Inside
  a chunk the updates are a unit lower-triangular system (the WY form):
  with ``G_t`` the decay accumulated from the chunk's start,
  ``A[i,j] = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` for j < i, and
  ``(I + A) U = beta (V - (K exp(G)) S0)``; then
  ``O = (Q exp(G)) S0 + tril(Q K^T decayed) U`` and
  ``S1 = exp(G_C) S0 + (K exp(G_C - G))^T U``. Every exponent that is
  taken is a difference ``G_i - G_j`` with j <= i, so none is positive:
  a channel may decay by any factor inside a chunk without an overflow
  (the usual ``K / exp(G)`` is never formed).

A position with ``g = 0`` and ``beta = 0`` leaves the state as it is:
that is how the rows of a padded bucket past their prompt's length are
kept from touching it (``pad_mask``). The short causal convolution that
precedes the recurrence, and the tail of inputs it hands from a prompt
to the decode steps, are here too.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


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


def short_conv_step(x: jnp.ndarray, w: jnp.ndarray, tail: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One position: x [B,C], tail [B,K-1,C] -> (y [B,C], new tail)."""
    win = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    y = jnp.einsum("bkc,kc->bc", win, w.astype(x.dtype))
    return y, win[:, 1:]


def pad_mask(g: jnp.ndarray, beta: jnp.ndarray, lens: jnp.ndarray
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """g [N,S,H,dk], beta [N,S,H] with every position at or past
    ``lens[n]`` made a no-op of the recurrence."""
    valid = jnp.arange(g.shape[1])[None, :] < lens[:, None]
    return (jnp.where(valid[:, :, None, None], g, 0.0),
            jnp.where(valid[:, :, None], beta, 0.0))


def kda_step(q, k, v, g, beta, state):
    """One position a slot. q, k, g [B,H,dk], v [B,H,dv], beta [B,H],
    state [B,H,dk,dv] float32. Returns (o [B,H,dv] float32, state)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    s = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, s,
                                          precision=_HI))
    s = s + k[..., None] * u[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, s, precision=_HI), s


def kda_chunked(q, k, v, g, beta, state=None, *, chunk: int = 16):
    """A row of positions through the recurrence, ``chunk`` at a time.
    q, k, g [N,S,H,dk], v [N,S,H,dv], beta [N,S,H]; ``state``
    [N,H,dk,dv] float32 (zeros when None). S need not be a multiple of
    ``chunk``. Returns (o [N,S,H,dv] float32, final state). The chunk
    of 16 is the fastest of 16, 32, 64 and of three variants with
    sub-chunks on a v5e at 64 heads of 128 (6.97, 7.45, 11.25 ms for
    2,048 positions; PERF.md, PR 27): the pairwise decay of a chunk is
    float32 work for the vector unit that grows with the chunk."""
    f32 = jnp.float32
    n, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-s) % chunk
    if pad:       # no-op positions: g = 0, beta = 0
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk

    def chunks(a):      # [N,S,H,...] -> [nc,N,H,C,...]
        a = a.reshape((n, nc, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    if state is None:
        state = jnp.zeros((n, h, dk, dv), f32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def body(s0, xs):
        qc, kc, vc, gc, bc = (a.astype(f32) for a in xs)
        bc = bc[..., 0]                                # [N,H,C]
        big = jnp.cumsum(gc, axis=2)                   # G, [N,H,C,dk]
        # decay from position j to position i >= j, a key channel;
        # masked BEFORE the exponential so that none is positive
        diff = big[:, :, :, None, :] - big[:, :, None, :, :]
        decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        kj = kc[:, :, None, :, :] * decay
        kk = jnp.sum(kc[:, :, :, None, :] * kj, axis=-1)
        qk = jnp.sum(qc[:, :, :, None, :] * kj, axis=-1)
        a = jnp.where(strict, bc[..., None] * kk, 0.0)
        e = jnp.exp(big)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "nhck,nhkv->nhcv", kc * e, s0, precision=_HI))
        u = jax.lax.linalg.triangular_solve(
            a + jnp.eye(chunk, dtype=f32), rhs, left_side=True,
            lower=True, unit_diagonal=True)
        o = (jnp.einsum("nhck,nhkv->nhcv", qc * e, s0, precision=_HI)
             + jnp.einsum("nhij,nhjv->nhiv", qk, u, precision=_HI))
        last = big[:, :, -1:, :]                       # G_C
        s1 = (jnp.exp(last[:, :, 0, :, None]) * s0
              + jnp.einsum("nhck,nhcv->nhkv", kc * jnp.exp(last - big),
                           u, precision=_HI))
        return s1, o

    state, o = jax.lax.scan(
        body, state.astype(f32),
        (chunks(q), chunks(k), chunks(v), chunks(g),
         chunks(beta[..., None])))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)      # [N,nc,C,H,dv]
    return o.reshape(n, nc * chunk, h, dv)[:, :s], state
