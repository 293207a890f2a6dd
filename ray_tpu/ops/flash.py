"""Fused flash attention for the single-chip train path.

Wraps jax's Pallas TPU flash-attention kernels (forward + custom-VJP
backward, jax.experimental.pallas.ops.tpu.flash_attention) with block
sizes tuned for this project's flagship shapes on v5e: the library
defaults (block 128) leave ~40% of the kernel's throughput on the table
at seq 2048 / head_dim 128; 512-wide blocks measured 12.8 ms vs 20.5 ms
forward and 19.3 ms vs 47.9 ms forward+backward for [8,16,2048,128].

Reference role: the reference has no attention kernel of its own (models
run inside torch actors; SURVEY.md §2.3) — this is part of the
greenfield compute path, alongside ops/ring_attention.py which handles
the sequence-parallel case with its own blockwise kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def flash_supported(head_dim: int) -> bool:
    """The fused kernel covers the SINGLE-CHIP causal path: TPU
    backend, lane-aligned head_dim, and no multi-device mesh active —
    pallas_call carries no GSPMD partitioning rule, so sharded
    activations must take the einsum path (XLA partitions it) or the
    ring path (which owns seq parallelism explicitly). Ragged sequence
    lengths pad inside the wrapper (flash_attention_bhsd)."""
    if jax.default_backend() != "tpu" or head_dim % 128 != 0:
        return False
    from ray_tpu.parallel import mesh as mesh_lib

    m = mesh_lib.current_mesh()
    return m is None or all(v <= 1 for v in m.shape.values())


def _block(seq: int) -> int:
    """One source of truth for the kernel tile width: padding rounds
    seq up to a multiple of this, and BlockSizes uses exactly this."""
    return 512 if seq >= 512 else 128


@functools.lru_cache(maxsize=None)
def _tuned_block_sizes(blk: int):
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    return BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk,
        block_k_dkv=blk, block_q_dkv=blk,
        block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk,
    )


def flash_attention_bhsd(q, k, v, causal: bool = True):
    """[B, H, S, D] fused attention, differentiable (library VJP).

    Ragged sequence lengths (e.g. the LM convention S = max_seq - 1)
    pad up to the kernel's block multiple: under the causal mask no
    real row can attend a padded key column (col > row), and padded
    query rows are sliced off, so padding is exact, not approximate.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention)

    s = q.shape[2]
    blk = _block(s)
    pad = (-s) % blk
    if pad and not causal:
        # zero-padded keys are only excluded by the causal mask; a
        # non-causal caller would silently attend them
        raise ValueError(
            f"flash_attention_bhsd: seq {s} needs padding to {blk}, "
            "which is only exact under causal=True")
    if pad:
        cfgpad = [(0, 0), (0, 0), (0, pad), (0, 0)]
        q = jnp.pad(q, cfgpad)
        k = jnp.pad(k, cfgpad)
        v = jnp.pad(v, cfgpad)
    out = flash_attention(
        q, k, v, causal=causal,
        sm_scale=1.0 / float(q.shape[-1]) ** 0.5,
        block_sizes=_tuned_block_sizes(blk))
    return out[:, :, :s] if pad else out


def flash_attention_bshk(q, k, v, causal: bool = True):
    """[B, S, H, D] layout (the model's native layout); same kernel."""
    out = flash_attention_bhsd(jnp.moveaxis(q, 1, 2),
                               jnp.moveaxis(k, 1, 2),
                               jnp.moveaxis(v, 1, 2), causal=causal)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)
