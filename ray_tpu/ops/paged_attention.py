"""Paged attention — the decode-time kernel for LLM serving.

Pattern source: "Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU" (arXiv:2604.15464, PAPERS.md) —
KV cache lives in fixed-size PAGES scattered through HBM; each sequence
owns a page list (page table), so ragged batches of wildly different
lengths share one static-shape kernel and memory fragments at page
granularity instead of max-seq granularity. Reference-framework analog:
the serving stack's attention kernels (the reference runs vLLM-style
paged attention on GPU); here it is a Pallas TPU kernel.

Two implementations of the read, parity-tested, and the two writes:

  - ``paged_attention_reference``: pure-XLA gather over the page table
    (the short-context path and the numerics oracle);
  - ``paged_attention``: Pallas flash-decoding kernel. Grid =
    (batch, pages); the page table rides scalar prefetch and
    the K/V BlockSpec index_maps select each sequence's physical page,
    so the kernel only ever DMAs pages the sequence actually owns.
    Online softmax state (m, l, acc) persists in VMEM scratch across
    the page axis of the grid (the flash-attention recurrence);
  - ``append_token_kv``: a decode step's write, one cell a sequence.
    A Pallas kernel too: each sequence's tail page goes through VMEM
    and back to where it lay, the rest of the pool is not touched;
  - ``write_prefill_kv``: a prompt's write, whole pages by an XLA
    scatter.

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

def _append_kernel(phys_ref, slot_ref, k_new_ref, v_new_ref, k_in_ref,
                   v_in_ref, k_out_ref, v_out_ref):
    """One grid cell = one sequence: its tail page comes in, the row
    ``slot_ref[b]`` is replaced by the token, the page goes back to
    where it came from. The row is picked by a ``where`` over an iota
    (a dynamic sublane store into packed bfloat16 need not lower); a
    slot of -1 picks none and the page goes back as it came."""
    import jax.experimental.pallas as pl

    del phys_ref  # read by the index maps
    slot = slot_ref[pl.program_id(0)]
    hit = jax.lax.broadcasted_iota(jnp.int32, k_in_ref.shape[1:],
                                   1) == slot
    k_out_ref[0] = jnp.where(hit, k_new_ref[0][:, None, :], k_in_ref[0])
    v_out_ref[0] = jnp.where(hit, v_new_ref[0][:, None, :], v_in_ref[0])


def append_token_kv(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                    k_new: jnp.ndarray, v_new: jnp.ndarray,
                    page_table: jnp.ndarray,
                    seq_lens: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write one decode token's K/V [B,KV,D] into each sequence's tail
    cell (page_table[b, seq_len // page], seq_len % page), in place.

    A Pallas kernel over grid (B,), K and V in one call: the pools are
    input AND output of the same buffers (``input_output_aliases``) in
    blocks of one page, chosen by the physical page ids that ride
    scalar prefetch, so a step reads and writes back B pages and
    touches nothing else of the pool. A Pallas operand also pins the
    pool's default layout, the one the gather and the paged kernel
    read: the one-hot product this replaces, an XLA scatter and a loop
    of ``dynamic_update_slice`` each make the compiler choose a layout
    of their own for the pool and re-lay it out around the update,
    whole, every step (PERF.md, PR 28). The outputs, and through the
    aliases the inputs, are pinned to HBM: left free, the compiler
    fetched a pool that fits VMEM (50 MB) there for the kernel and the
    gather after it and wrote it back, whole, every step. Off the TPU
    the kernel runs in interpret mode, as ``paged_attention_auto``'s
    does.

    A live sequence's cell gets exactly its token (the page allocator
    never shares a page between live sequences). Idle slots all name
    ONE cell of the parking page: it holds the token of whichever of
    them was written back last, always a finite value, and nothing
    live reads it unmasked. A sequence whose length lies past its page
    table writes nothing (a finished slot decoding out a burst: its
    own last page goes through unchanged).

    Two things the caller owes. Every id in ``page_table`` names a page
    of the pool, as the engine's do (what it has not allocated is the
    parking page): an id outside is clipped, so that no DMA leaves the
    pool, and the token lands in the clipped page. And under jit on a
    TPU the pools are donated, or carried from donated ones, as in
    every engine program: not donated, the compiler copies the pool
    first, and where that copy fits VMEM its memory assignment aborts
    on the HBM pin (seen compiling for the described v5e, PR 28: a
    16 MB pool, one step)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, KV, page, D = k_pages.shape
    B, MP = page_table.shape
    logical = seq_lens // page
    phys = jnp.take_along_axis(page_table,
                               jnp.minimum(logical, MP - 1)[:, None],
                               axis=1)[:, 0]                   # [B]
    phys = jnp.clip(phys, 0, P - 1).astype(jnp.int32)
    # a cell that writes nothing still moves a page: its own last one
    slot = jnp.where(logical < MP, seq_lens % page, -1).astype(jnp.int32)

    token = pl.BlockSpec((1, KV, D), lambda b, phys, slot: (b, 0, 0))
    tail_page = pl.BlockSpec((1, KV, page, D),
                             lambda b, phys, slot: (phys[b], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # phys, slot
        grid=(B,),
        in_specs=[token, token, tail_page, tail_page],
        out_specs=[tail_page, tail_page],
    )
    return tuple(pl.pallas_call(
        _append_kernel, grid_spec=grid_spec,
        out_shape=[pltpu.HBM(k_pages.shape, k_pages.dtype),
                   pltpu.HBM(v_pages.shape, v_pages.dtype)],
        # operands count the two prefetched scalars: 4, 5 are the pools
        input_output_aliases={4: 0, 5: 1},
        interpret=jax.default_backend() != "tpu",
    )(phys, slot, k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype),
      k_pages, v_pages))


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
