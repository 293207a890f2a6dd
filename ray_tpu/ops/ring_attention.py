"""Ring attention — sequence/context parallelism over the ICI ring.

ABSENT from the reference (SURVEY.md §2.3: Ray reaches long context only
through engines run inside actors), so this subsystem is greenfield and
first-class per the survey's mandate: blockwise attention for memory,
KV blocks rotated around the `seq` mesh axis with jax.lax.ppermute, the
per-step block computation as a Pallas TPU kernel (flash-style online
softmax), and an XLA reference path for CPU meshes / parity tests.

Layout convention: q, k, v are [B, S_local, H, D] INSIDE shard_map (the
sequence axis already split over `seq`). The public entry point
`ring_attention_sharded` takes global [B, S, H, D] and wraps shard_map.

Algorithm (Liu et al., Ring Attention with Blockwise Transformers,
arXiv:2310.01889 — PAPERS.md pattern source):
  each of the n seq-devices holds Q_i and rotates (K_j, V_j) around the
  ring; per step it computes blockwise attention of Q_i against the
  current block with a numerically stable online-softmax merge
      m' = max(m, m_b); acc = acc*e^{m-m'} + o_b*e^{m_b-m'};
      l = l*e^{m-m'} + l_b*e^{m_b-m'}
  and finally normalizes acc / l. Causality uses GLOBAL offsets, so
  fully-masked blocks contribute zeros (no special-casing).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


# ======================================================================
# single-block attention: (o_unnorm f32, m, l) given global offsets
# ======================================================================

def _block_attention_xla(q, k, v, q_offset, k_offset, causal: bool):
    """Reference block computation. q [B,H,Tq,D], k/v [B,Hkv,Tk,D] with
    Hkv dividing H (GQA repeat happens HERE, locally — never on the
    ring) -> (o [B,H,Tq,D] f32 unnormalized, m [B,H,Tq], l [B,H,Tq])."""
    rep = q.shape[1] // k.shape[1]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        rows = q_offset + jnp.arange(q.shape[2])[:, None]
        cols = k_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where((rows >= cols)[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: m == NEG_INF -> p would be exp(0)=1 per col; zero
    p = jnp.where((m > _NEG_INF / 2)[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, m, l


def _block_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, *, causal: bool, tq: int):
    """Pallas kernel: one (batch, head, q-tile) block against the whole
    local KV block (bounded by ring partitioning, so it fits VMEM)."""
    import jax.experimental.pallas as pl

    q = q_ref[0, 0].astype(jnp.float32)                 # [Tq, D]
    k = k_ref[0, 0].astype(jnp.float32)                 # [Sk, D]
    v = v_ref[0, 0].astype(jnp.float32)                 # [Sk, D]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        tile = pl.program_id(2)
        rows = (qoff_ref[0] + tile * tq
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        cols = (koff_ref[0]
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        s = jnp.where(rows >= cols, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                             # [Tq]
    p = jnp.exp(s - m[:, None])
    p = jnp.where((m > _NEG_INF / 2)[:, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] = o
    m_ref[0, 0] = m[:, None]
    l_ref[0, 0] = l[:, None]


def _block_pallas_forward(q, k, v, q_offset, k_offset, causal: bool,
                          interpret: bool):
    """The bare kernel launch; same contract as _block_attention_xla.
    Not differentiable (pallas_call has no JVP for a kernel that reads
    program_id) — callers go through _block_attention_pallas."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    rep = h // k.shape[1]  # GQA: kv head for query head j is j // rep
    tq = min(256, sq)
    while sq % tq:
        tq //= 2
    nq = sq // tq
    qoff = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (1,))
    koff = jnp.reshape(jnp.asarray(k_offset, jnp.int32), (1,))

    kernel = functools.partial(_block_kernel, causal=causal, tq=tq)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(b, h, nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, tq, d), lambda i, j, t: (i, j, t, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda i, j, t: (i, j // rep, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda i, j, t: (i, j // rep, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tq, d), lambda i, j, t: (i, j, t, 0)),
            # trailing singleton keeps the (sublane, lane) tiling legal:
            # block (tq, 1) matches the array's last dim exactly
            pl.BlockSpec((1, 1, tq, 1), lambda i, j, t: (i, j, t, 0)),
            pl.BlockSpec((1, 1, tq, 1), lambda i, j, t: (i, j, t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qoff, koff, q, k, v)
    return o, m[..., 0], l[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _block_attention_pallas(q, k, v, q_offset, k_offset, causal: bool,
                            interpret: bool = False):
    """Pallas path; same contract as _block_attention_xla.

    Forward is the kernel. BACKWARD is the VJP of _block_attention_xla
    at the same inputs: the two compute the same (o, m, l), so the XLA
    block's cotangents are the kernel's, at the price of materializing
    one [B, H, Tq, Tk] f32 score block per ring step in the backward
    pass. A train step under a seq mesh on TPU therefore runs the
    kernel forward and the XLA block backward."""
    return _block_pallas_forward(q, k, v, q_offset, k_offset, causal,
                                 interpret)


def _block_pallas_fwd(q, k, v, q_offset, k_offset, causal, interpret):
    out = _block_pallas_forward(q, k, v, q_offset, k_offset, causal,
                                interpret)
    return out, (q, k, v, q_offset, k_offset)


def _block_pallas_bwd(causal, interpret, res, cts):
    q, k, v, q_offset, k_offset = res
    _, vjp = jax.vjp(
        lambda q, k, v: _block_attention_xla(q, k, v, q_offset, k_offset,
                                             causal), q, k, v)
    # the integer offsets carry no cotangent
    return (*vjp(cts), None, None)


_block_attention_pallas.defvjp(_block_pallas_fwd, _block_pallas_bwd)


def block_attention(q, k, v, q_offset=0, k_offset=0, causal: bool = True,
                    impl: str = "auto", interpret: bool = False):
    """One blockwise attention step. q [B,H,T,D], k/v [B,Hkv,Tk,D] (Hkv
    divides H: GQA); offsets are the GLOBAL sequence positions of the
    first row/col (causality across ring steps). Returns
    (o_unnormalized f32, m, l). impl="auto" is the Pallas kernel on a
    TPU backend and the XLA block elsewhere; both are differentiable
    (the kernel through the XLA block's VJP, see
    _block_attention_pallas)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        return _block_attention_pallas(q, k, v, q_offset, k_offset, causal,
                                       interpret=interpret)
    return _block_attention_xla(q, k, v, q_offset, k_offset, causal)


# ======================================================================
# the ring
# ======================================================================

def _merge(acc, m, l, o_b, m_b, l_b):
    m_new = jnp.maximum(m, m_b)
    # guard exp(-inf - -inf): fully-masked contributions scale to zero
    a1 = jnp.where(m > _NEG_INF / 2, jnp.exp(m - m_new), 0.0)
    a2 = jnp.where(m_b > _NEG_INF / 2, jnp.exp(m_b - m_new), 0.0)
    acc = acc * a1[..., None] + o_b * a2[..., None]
    l = l * a1 + l_b * a2
    return acc, m_new, l


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = True,
                   impl: str = "auto", interpret: bool = False):
    """Ring attention for use INSIDE shard_map: q/k/v [B, S_local, H, D]
    with the sequence axis sharded over ``axis_name``. KV blocks rotate
    around the ring via ppermute; each step runs the blockwise kernel and
    merges with the online-softmax rule. Returns [B, S_local, H, D] in
    q.dtype."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape

    # [B,H,S,D] layout for the kernel
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)

    acc = jnp.zeros((b, h, s_local, d), jnp.float32)
    m = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_local), jnp.float32)
    q_off = idx * s_local

    def step(t, carry):
        acc, m, l, kt, vt = carry
        # at step t we hold the KV block of device (idx - t) mod n
        src = (idx - t) % n
        o_b, m_b, l_b = block_attention(
            qt, kt, vt, q_offset=q_off, k_offset=src * s_local,
            causal=causal, impl=impl, interpret=interpret)
        acc, m, l = _merge(acc, m, l, o_b, m_b, l_b)
        # rotate: receive the next block from the left neighbor
        perm = [(i, (i + 1) % n) for i in range(n)]
        kt = jax.lax.ppermute(kt, axis_name, perm)
        vt = jax.lax.ppermute(vt, axis_name, perm)
        return acc, m, l, kt, vt

    # python loop: n is static (mesh axis size); permutes pipeline with
    # compute under XLA latency hiding
    carry = (acc, m, l, kt, vt)
    for t in range(n):
        carry = step(t, carry)
    acc, m, l, _, _ = carry

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 2, 1).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "seq",
                           causal: bool = True, impl: str = "auto",
                           interpret: bool = False, rules=None):
    """Global entry: q [B,S,H,D], k/v [B,S,Hkv,D]; shard_map over the
    mesh's seq axis. Partition specs derive from the SAME logical rules
    the surrounding pjit program uses (parallel/mesh.py
    default_logical_rules), so no resharding appears at the boundary."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import mesh as mesh_lib

    table = dict(rules if rules is not None
                 else mesh_lib.default_logical_rules())
    q_spec = P(*(table.get(ax) for ax in
                 ("batch", "act_seq", "heads", "head_dim")))
    kv_spec = P(*(table.get(ax) for ax in
                  ("batch", "act_seq", "kv_heads", "head_dim")))
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal, impl=impl, interpret=interpret)
    from ray_tpu.parallel.collectives import shard_map_norep

    return shard_map_norep(fn, mesh=mesh,
                           in_specs=(q_spec, kv_spec, kv_spec),
                           out_specs=q_spec)(q, k, v)


def attention_reference(q, k, v, causal: bool = True):
    """Plain single-device attention (the parity oracle). [B,S,H,D]."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq = q.shape[1]
        mask = jnp.tril(jnp.ones((sq, sq), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)
