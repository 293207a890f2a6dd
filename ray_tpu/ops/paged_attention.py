"""Paged attention — the decode-time kernel for LLM serving.

Pattern source: "Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU" (arXiv:2604.15464, PAPERS.md) —
KV cache lives in fixed-size PAGES scattered through HBM; each sequence
owns a page list (page table), so ragged batches of wildly different
lengths share one static-shape kernel and memory fragments at page
granularity instead of max-seq granularity. Reference-framework analog:
the serving stack's attention kernels (the reference runs vLLM-style
paged attention on GPU); here it is a Pallas TPU kernel.

Two implementations, parity-tested:

  - ``paged_attention_reference``: pure-XLA gather over the page table
    (the short-context path and the numerics oracle);
  - ``paged_attention``: Pallas flash-decoding kernel. Grid =
    (batch, kv_heads, pages); the page table rides scalar prefetch and
    the K/V BlockSpec index_maps select each sequence's physical page,
    so the kernel only ever DMAs pages the sequence actually owns.
    Online softmax state (m, l, acc) persists in VMEM scratch across
    the page axis of the grid (the flash-attention recurrence).

Layout: K/V pages are [n_pages, n_kv_heads, page_size, head_dim];
queries are single decode tokens [B, n_heads, head_dim] (GQA: n_heads =
G * n_kv_heads, grouped so each (batch, kv_head) grid cell computes its
G query heads against one shared KV stream).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ----------------------------------------------------------------------
# reference implementation (XLA gather; numerics oracle + short contexts)
# ----------------------------------------------------------------------

def paged_attention_reference(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray,
                              page_table: jnp.ndarray,
                              seq_lens: jnp.ndarray) -> jnp.ndarray:
    """q [B,H,D]; k_pages/v_pages [P,KV,page,D]; page_table [B,MP]
    (physical page per logical page, 0-padded); seq_lens [B] = valid
    cache tokens per sequence. Returns [B,H,D] (f32)."""
    B, H, D = q.shape
    _P, KV, page, _D = k_pages.shape
    MP = page_table.shape[1]
    G = H // KV

    # gather each sequence's pages: [B, KV, MP*page, D]
    k = k_pages[page_table]  # [B, MP, KV, page, D]
    v = v_pages[page_table]
    k = k.transpose(0, 2, 1, 3, 4).reshape(B, KV, MP * page, D)
    v = v.transpose(0, 2, 1, 3, 4).reshape(B, KV, MP * page, D)

    qg = q.reshape(B, KV, G, D).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg,
                        k.astype(jnp.float32)) / jnp.sqrt(D)
    valid = jnp.arange(MP * page)[None, :] < seq_lens[:, None]  # [B,T]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, D)


# ----------------------------------------------------------------------
# Pallas flash-decoding kernel
# ----------------------------------------------------------------------

def _decode_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, page_size: int,
                   max_pages: int):
    """One grid cell = (sequence, page): ALL kv-heads of one page (the
    KV axis stays inside the cell — a (B, KV, MP) grid would multiply
    the per-cell fixed cost by KV for no reuse win)."""
    import jax.experimental.pallas as pl

    bi = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[bi]
    # tokens this page contributes: positions [p*page, p*page + valid)
    start = p * page_size
    valid = jnp.clip(seq_len - start, 0, page_size)

    @pl.when(valid > 0)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # [KV, G, D]
        k = k_ref[0].astype(jnp.float32)          # [KV, page, D]
        v = v_ref[0].astype(jnp.float32)          # [KV, page, D]
        d = q.shape[-1]
        s = jnp.einsum("kgd,kpd->kgp", q, k,
                       preferred_element_type=jnp.float32) / jnp.sqrt(
                           d * 1.0)               # [KV, G, page]
        mask = jnp.arange(page_size)[None, None, :] < valid
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                       # [KV, G, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(s - m_new)                # [KV, G, page]
        l_ref[...] = l_ref[...] * alpha + probs.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "kgp,kpd->kgd", probs, v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(p == max_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, page_table: jnp.ndarray,
                    seq_lens: jnp.ndarray, *,
                    interpret: bool = False) -> jnp.ndarray:
    """Pallas flash-decoding over paged KV (see module docstring);
    interpret=True runs the kernel body off-TPU for testing."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    P, KV, page, _D = k_pages.shape
    MP = page_table.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, D)

    kernel = functools.partial(_decode_kernel, page_size=page,
                               max_pages=MP)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # page_table, seq_lens
        grid=(B, MP),
        in_specs=[
            # q: one sequence's query heads, all kv groups
            pl.BlockSpec((1, KV, G, D),
                         lambda b, p, table, lens: (b, 0, 0, 0)),
            # K/V: the physical page the table names for (b, p)
            pl.BlockSpec((1, KV, page, D),
                         lambda b, p, table, lens: (table[b, p], 0,
                                                    0, 0)),
            pl.BlockSpec((1, KV, page, D),
                         lambda b, p, table, lens: (table[b, p], 0,
                                                    0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, D),
                               lambda b, p, table, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((KV, G, 1), jnp.float32),    # l (running denom)
            pltpu.VMEM((KV, G, D), jnp.float32),    # acc
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), jnp.float32),
        interpret=interpret,
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, H, D)


def paged_attention_auto(q, k_pages, v_pages, page_table, seq_lens):
    """Path choice at trace time: the Pallas kernel amortizes at LONG
    max contexts (it reads only the pages each sequence owns); at short
    contexts the XLA gather reference is faster (the kernel's per-cell
    fixed cost dominates tiny reads). Off-TPU the kernel runs in
    interpret mode so tests exercise the real kernel logic. A kernel
    that fails to lower raises: the gather is a choice, never a
    fallback."""
    MP, page = page_table.shape[1], k_pages.shape[2]
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and MP * page < 2048:
        return paged_attention_reference(q, k_pages, v_pages, page_table,
                                         seq_lens)
    return paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                           interpret=not on_tpu)


# ----------------------------------------------------------------------
# page-cache update helpers (functional; jit-friendly)
# ----------------------------------------------------------------------

def append_token_kv(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                    k_new: jnp.ndarray, v_new: jnp.ndarray,
                    page_table: jnp.ndarray,
                    seq_lens: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write one decode token's K/V [B,KV,D] into each sequence's tail
    slot (page_table[b, seq_len // page], seq_len % page).

    Formulated as a ONE-HOT masked update, not an XLA scatter: batched
    vector-index scatters lower to serial per-index loops on TPU, which
    dominated the whole decode step; the dense mask-multiply is a pure
    VPU/MXU streaming op over the cache (slots are unique per batch —
    the page allocator never shares a page between live sequences;
    idle slots do share the parking page's cell, which then holds the
    sum of their dummy tokens)."""
    P, KV, page, D = k_pages.shape
    logical = seq_lens // page
    slot = seq_lens % page
    phys = jnp.take_along_axis(page_table, logical[:, None],
                               axis=1)[:, 0]                   # [B]
    oh_p = jax.nn.one_hot(phys, P, dtype=k_pages.dtype)        # [B,P]
    oh_s = jax.nn.one_hot(slot, page, dtype=k_pages.dtype)     # [B,page]
    mask = jnp.einsum("bp,bs->ps", oh_p, oh_s)                 # [P,page]
    # idle slots all park their dummy token in ONE cell (same parking
    # page, same length), so the mask counts them there: 1 - count
    # would multiply that cell by -(idle - 1) every time it is hit,
    # and with no prefill launch to rewrite the parking page (a drain,
    # long answers) it overflows in a few hundred steps; 0 x inf at the
    # masked positions of every table that names the parking page is
    # then NaN in all live slots. The cell is overwritten, not scaled.
    keep = (1 - jnp.minimum(mask, 1))[:, None, :, None]
    k_contrib = jnp.einsum("bp,bs,bkd->pksd", oh_p, oh_s,
                           k_new.astype(k_pages.dtype))
    v_contrib = jnp.einsum("bp,bs,bkd->pksd", oh_p, oh_s,
                           v_new.astype(v_pages.dtype))
    return (k_pages * keep + k_contrib, v_pages * keep + v_contrib)


def write_prefill_kv(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                     k_seq: jnp.ndarray, v_seq: jnp.ndarray,
                     pages: jnp.ndarray,
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write a prefilled sequence's K/V [S,KV,D] into its pages
    ([n] physical ids; S must be <= n*page_size — the tail page may be
    partially filled, trailing slots are don't-care)."""
    page = k_pages.shape[2]
    n = pages.shape[0]
    S = k_seq.shape[0]
    pad = n * page - S
    k_fill = jnp.concatenate(
        [k_seq, jnp.zeros((pad,) + k_seq.shape[1:], k_seq.dtype)])
    v_fill = jnp.concatenate(
        [v_seq, jnp.zeros((pad,) + v_seq.shape[1:], v_seq.dtype)])
    k_fill = k_fill.reshape(n, page, -1, k_seq.shape[-1]).transpose(
        0, 2, 1, 3)  # [n, KV, page, D]
    v_fill = v_fill.reshape(n, page, -1, v_seq.shape[-1]).transpose(
        0, 2, 1, 3)
    k_pages = k_pages.at[pages].set(k_fill.astype(k_pages.dtype))
    v_pages = v_pages.at[pages].set(v_fill.astype(v_pages.dtype))
    return k_pages, v_pages
