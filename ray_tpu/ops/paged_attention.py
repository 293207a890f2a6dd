"""Paged attention — the decode-time kernel for LLM serving.

Pattern source: "Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU" (arXiv:2604.15464, PAPERS.md) —
KV cache lives in fixed-size PAGES scattered through HBM; each sequence
owns a page list (page table), so ragged batches of wildly different
lengths share one static-shape kernel and memory fragments at page
granularity instead of max-seq granularity. Reference-framework analog:
the serving stack's attention kernels (the reference runs vLLM-style
paged attention on GPU); here it is a Pallas TPU kernel.

The read, its oracle, and the two writes:

  - ``paged_attention``: the read of every decode program, a Pallas
    flash-decoding kernel after the paper's. Grid = (sequences,); the
    pools stay in HBM in their own layout and a cell walks its
    sequence a BLOCK of pages at a time (``BLOCK_TOKENS`` tokens: 16
    pages of 16, 2 of 128), up to the sequence's length and no
    further: one DMA a page it owns, K and V, into a double-buffered
    VMEM scratch laid out [KV, tokens, D], the next block (the
    sequence's own, or the next live sequence's first) in flight while
    this one is computed. An idle slot moves nothing. The page table,
    the lengths and the next live sequence ride scalar prefetch. Online
    softmax state (m, l, acc) in float32, as are scores and
    probabilities; K and V reach the products as the pool holds them.
    A WINDOW layer's read (``ring`` > 0) is the same kernel told each
    sequence's first visible position: the walk starts at that
    position's block and finds logical page ``p`` at entry ``p % ring``
    of the sequence's ring. The trace keeps its name,
    ``paged_window_read``;
  - ``paged_latent_attention``: the same walk over ONE pool whose row
    a token serves as key and as value (latent attention in its
    absorbed form: one shared "KV head", every query head against it,
    the value a row's first ``value_width`` columns): one DMA a page,
    scores over the whole row, values from the same VMEM buffer. The
    trace keeps its name, ``mla_paged_read``;
  - ``paged_attention_reference``: pure-XLA gather of every page of
    every slot over the page table. The numerics oracle of the tests;
    no program calls it (until PR 30 contexts under 2,048 tokens ran
    it: PERF.md section 6);
  - ``append_token`` (``append_token_kv`` for K and V): a decode
    step's write, one cell a sequence in each pool it is given. A
    Pallas kernel too: each sequence's tail page goes through VMEM
    and back to where it lay, the rest of the pool is not touched; in
    a ring the logical page wraps;
  - ``write_prefill_pages`` (``write_prefill_kv``): a prompt's write,
    whole pages by an XLA scatter.

Layout: K/V pages are [n_pages, n_kv_heads, page_size, head_dim];
queries are single decode tokens [B, n_heads, head_dim] (GQA: n_heads =
G * n_kv_heads, grouped so each kv head's G query heads run against
one shared KV stream).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ----------------------------------------------------------------------
# reference implementation (XLA gather; the tests' numerics oracle)
# ----------------------------------------------------------------------

def paged_attention_reference(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray,
                              page_table: jnp.ndarray,
                              seq_lens: jnp.ndarray,
                              score_width: int = 0,
                              first: Optional[jnp.ndarray] = None
                              ) -> jnp.ndarray:
    """q [B,H,D]; k_pages [P,KV,page,D], v_pages [P,KV,page,Dv];
    page_table [B,MP] (physical page per logical page, 0-padded);
    seq_lens [B] = valid cache tokens per sequence; ``first`` [B] the
    first position a sequence still sees (a window's lower edge; 0 when
    None). Scores are divided by the root of ``score_width`` (D when
    0). Returns [B,H,Dv] (f32)."""
    B, H, D = q.shape
    _P, KV, page, _D = k_pages.shape
    MP = page_table.shape[1]
    G = H // KV

    # gather each sequence's pages: [B, KV, MP*page, D]
    k = k_pages[page_table]  # [B, MP, KV, page, D]
    v = v_pages[page_table]
    k = k.transpose(0, 2, 1, 3, 4).reshape(B, KV, MP * page, D)
    v = v.transpose(0, 2, 1, 3, 4).reshape(B, KV, MP * page, -1)

    qg = q.reshape(B, KV, G, D).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg,
                        k.astype(jnp.float32)) / jnp.sqrt(score_width or D)
    valid = jnp.arange(MP * page)[None, :] < seq_lens[:, None]  # [B,T]
    if first is not None:
        valid &= jnp.arange(MP * page)[None, :] >= first[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # a masked row may hold anything (a parking page's NaN): 0 x NaN
    v = jnp.where(valid[:, None, :, None], v.astype(jnp.float32), 0.0)
    out = jnp.einsum("bkgt,bktd->bkgd", probs, v)
    return out.reshape(B, H, -1)


# ----------------------------------------------------------------------
# Pallas flash-decoding kernel
# ----------------------------------------------------------------------

# tokens a block of the walk holds: the pages a DMA wave brings and the
# width of the score matrix a head. PERF.md section 6, PR 30 has the
# measurement behind the number.
BLOCK_TOKENS = 256
# the same for the latent read, whose block is one pool's pages and 128
# query heads' scores: 1,024 tokens a block took 0.49 ms where 256 took
# 0.73 and 512 took 0.57 (32 slots, 170 k live tokens; PERF.md section
# 6, PR 31 has the table)
LATENT_BLOCK_TOKENS = 1024


def _read_kernel(table_ref, lens_ref, next_ref, *refs,
                 page: int, pages_a_block: int, max_pages: int,
                 n_pools: int, score_width: int, value_width: int,
                 ring: int):
    """One grid cell = one sequence, walked a block of ``pages_a_block``
    pages at a time up to its length. The pools stay in HBM: a block's
    pages come by one DMA each and pool, into one of two VMEM buffers
    laid out [KV, tokens, D], so that a head's product runs over the
    whole block. While a block is computed the next one is in flight:
    the sequence's own next block, or the first block of the next live
    sequence (``next_ref``), which that cell then finds arriving. An
    idle slot starts and waits for nothing.

    With a ``ring`` (a window layer's pool) a fourth prefetched vector
    gives each sequence's first visible position: the walk starts at
    the block that holds it, fetches no page that lies wholly before
    it, masks what lies before it inside its page, and finds logical
    page ``p`` at the table's entry ``p % ring``; a length is then not
    held to the table's width.

    ``refs``: (the first positions,) the queries, the ``n_pools``
    pools, the output, a buffer a pool, the semaphores and the softmax
    state. Two pools are keys and values. One pool is both: scores over
    a row's whole width, values its first ``value_width`` columns out of
    the same buffer, and the probabilities go to that product in the
    pool's type (128 query heads against one row make it a matrix
    product worth the MXU's rate; in float32 it would take several
    passes)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if ring:
        first_ref, *refs = refs
    q_ref, *refs = refs
    pools, o_ref = refs[:n_pools], refs[n_pools]
    bufs = refs[n_pools + 1:2 * n_pools + 1]
    sems, m_ref, l_ref, acc_ref, blocks_ref = refs[2 * n_pools + 1:]
    k_buf = bufs[0]
    b = pl.program_id(0)
    n_seqs = pl.num_programs(0)
    T = pages_a_block * page

    def first_block(seq):
        """The block a sequence's walk starts at."""
        return first_ref[seq] // T if ring else 0

    def wave(what, seq, block, slot):
        """``what`` = "start" or "wait": the DMAs of the pages that
        ``seq`` owns (and still sees) in its ``block``, into buffer
        ``slot``"""
        first = block * pages_a_block
        if ring:
            owned = pl.cdiv(lens_ref[seq], page)
            seen = jnp.clip(first_ref[seq] // page - first, 0,
                            pages_a_block)
        else:
            owned = jnp.minimum(pl.cdiv(lens_ref[seq], page), max_pages)
            seen = 0

        def one(i, carry):
            pid = table_ref[seq, (first + i) % ring if ring else first + i]
            rows = pl.ds(pl.multiple_of(i * page, page), page)
            for which, (pool, buf) in enumerate(zip(pools, bufs)):
                getattr(pltpu.make_async_copy(
                    pool.at[pid], buf.at[slot, :, rows],
                    sems.at[which, slot]), what)()
            return carry

        jax.lax.fori_loop(
            seen, jnp.clip(owned - first, 0, pages_a_block), one, None)

    @pl.when(b == 0)
    def _first():
        blocks_ref[0] = 0

        @pl.when(next_ref[0] < n_seqs)
        def _():
            wave("start", next_ref[0], first_block(next_ref[0]), 0)

    if ring:
        seq_len = lens_ref[b]
        n_blocks = pl.cdiv(seq_len, T) - first_block(b)
    else:
        seq_len = jnp.minimum(lens_ref[b], max_pages * page)
        n_blocks = pl.cdiv(seq_len, T)
    done = blocks_ref[0]      # blocks walked so far: its parity is the buffer
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(j, carry):
        slot = (done + j) % 2
        last = j + 1 == n_blocks
        seq_next = jnp.where(last, next_ref[b + 1], b)
        at = first_block(b) + j if ring else j

        @pl.when(seq_next < n_seqs)
        def _():
            wave("start", seq_next,
                 jnp.where(last, first_block(seq_next), at + 1), 1 - slot)

        wave("wait", b, at, slot)
        q = q_ref[0]                                # [KV, G, D]
        k = k_buf[slot]                             # [KV, T, D]
        dtype = jnp.promote_types(q.dtype, k.dtype)
        s = jnp.einsum("kgd,ktd->kgt", q.astype(dtype), k.astype(dtype),
                       preferred_element_type=jnp.float32) / jnp.sqrt(
                           score_width * 1.0)       # [KV, G, T]
        # past the length a buffer holds what an earlier block left
        # there, or nothing yet, and so it does before a window's first
        # position: a score there counts for nothing, and a value row
        # there must not reach the product (0 x NaN)
        def live(shape, axis):
            pos = at * T + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            if ring:
                return (pos < seq_len) & (pos >= first_ref[b])
            return pos < seq_len

        s = jnp.where(live((1, 1, T), 2), s, NEG_INF)
        if n_pools == 2:
            v = jnp.where(live((1, T, 1), 1),
                          bufs[1][slot].astype(jnp.float32), 0.0)
        else:
            v = k_buf[slot, :, :, :value_width]
            v = jnp.where(live((1, T, 1), 1), v, jnp.zeros_like(v))

        m_prev = m_ref[...]                         # [KV, G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(s - m_new)                  # [KV, G, T]
        l_ref[...] = l_ref[...] * alpha + probs.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "kgt,ktd->kgd", probs.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, None)
    blocks_ref[0] = done + n_blocks
    # an idle slot's row is zeros: finite, and discarded by the caller
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
        o_ref.dtype)


def _paged_read(q, pools, page_table, seq_lens, *, block_tokens: int,
                interpret: bool, score_width: int, value_width: int,
                name=None, first=None, ring: int = 0) -> jnp.ndarray:
    """The walk's ``pallas_call``: q [B,KV,G,D] against ``pools`` ([P,
    KV,page,D] each; two are keys and values, one is both). With a
    ``ring``, ``first`` [B] are the sequences' first visible positions
    and ``page_table`` [B,ring] their rings. Returns
    [B,KV,G,value_width] float32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, KV, G, D = q.shape
    P, _KV, page, _D = pools[0].shape
    MP = page_table.shape[1]
    pages_a_block = max(1, min(block_tokens // page, MP))
    T = pages_a_block * page
    lens = seq_lens.astype(jnp.int32)
    # as in append_token: an id outside the pool is clipped, so that
    # no DMA leaves it
    table = jnp.clip(page_table, 0, P - 1).astype(jnp.int32)
    # next_live[0] the first live sequence, next_live[b + 1] the first
    # after b; B where there is none
    ids = jnp.where(lens > 0, jnp.arange(B, dtype=jnp.int32), B)
    next_live = jnp.concatenate([
        jax.lax.cummin(ids, reverse=True), jnp.full((1,), B, jnp.int32)])

    kernel = functools.partial(
        _read_kernel, page=page, pages_a_block=pages_a_block, max_pages=MP,
        n_pools=len(pools), score_width=score_width,
        value_width=value_width, ring=ring)
    # page_table, seq_lens, next_live (and, of a ring, the first
    # visible positions)
    scalars = (table, lens, next_live)
    if ring:
        scalars += (jnp.clip(first, 0, lens).astype(jnp.int32),)

    def head_rows(width):
        return pl.BlockSpec((1, KV, G, width),
                            lambda b, *scalars: (b, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B,),
        # the pools are read where they lie: left to the compiler, one
        # that fits VMEM may be fetched there whole, every step
        in_specs=[head_rows(D)] + [pl.BlockSpec(memory_space=pltpu.HBM)
                                   for _ in pools],
        out_specs=head_rows(value_width),
        scratch_shapes=[
            *[pltpu.VMEM((2, KV, T, D), pool.dtype) for pool in pools],
            pltpu.SemaphoreType.DMA((len(pools), 2)),   # (pool, buffer)
            pltpu.VMEM((KV, G, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((KV, G, 1), jnp.float32),    # l (running denom)
            pltpu.VMEM((KV, G, value_width), jnp.float32),    # acc
            pltpu.SMEM((1,), jnp.int32),            # blocks walked
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, value_width),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(*scalars, q, *pools)


@functools.partial(jax.jit, static_argnames=(
    "block_tokens", "interpret", "ring", "score_width"))
def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, page_table: jnp.ndarray,
                    seq_lens: jnp.ndarray, *,
                    block_tokens: int = BLOCK_TOKENS,
                    interpret: bool = False,
                    first: Optional[jnp.ndarray] = None,
                    ring: int = 0, score_width: int = 0) -> jnp.ndarray:
    """Pallas flash-decoding over paged KV (see module docstring);
    interpret=True runs the kernel body off-TPU for testing. Jitted on
    its own, so that a decode program traces the kernel once and not
    once a layer, and the engine's six programs once between them
    (tracing it costs what a whole layer's einsums do).

    ``ring`` > 0 is a window layer's read: ``page_table`` [B,ring] is
    each sequence's ring (logical page ``p`` lies at entry ``p %
    ring``), ``first`` [B] its first visible position, and only
    positions ``first <= t < seq_lens`` are fetched and scored. The
    trace keeps that call's name, ``paged_window_read``. Without a
    ring the program is the one it was before there were rings.

    ``score_width`` divides the scores by its root in place of D's:
    that of heads whose rows the pools hold padded with zeros to whole
    lanes (``DecoderConfig.kv_width``)."""
    B, H, D = q.shape
    KV = k_pages.shape[1]
    out = _paged_read(q.reshape(B, KV, H // KV, D), (k_pages, v_pages),
                      page_table, seq_lens, block_tokens=block_tokens,
                      interpret=interpret, score_width=score_width or D,
                      value_width=D, first=first, ring=ring,
                      name="paged_window_read" if ring else None)
    return out.reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=(
    "score_width", "value_width", "block_tokens", "interpret"))
def paged_latent_attention(q: jnp.ndarray, pages: jnp.ndarray,
                           page_table: jnp.ndarray, seq_lens: jnp.ndarray,
                           *, score_width: int, value_width: int,
                           block_tokens: int = LATENT_BLOCK_TOKENS,
                           interpret: bool = False) -> jnp.ndarray:
    """The walk of ``paged_attention`` over ONE pool [P,1,page,W] whose
    row a token is its key and, in the first ``value_width`` columns,
    its value: q [B,H,W] (every head against the one row) -> [B,H,
    value_width] float32. Scores are divided by the root of
    ``score_width`` (the width of the keys the row stands for, which is
    not W). A page is fetched once."""
    B, H, W = q.shape
    out = _paged_read(q.reshape(B, 1, H, W), (pages,), page_table,
                      seq_lens, block_tokens=block_tokens,
                      interpret=interpret, score_width=score_width,
                      value_width=value_width, name="mla_paged_read")
    return out.reshape(B, H, value_width)


def paged_attention_auto(q, k_pages, v_pages, page_table, seq_lens,
                         **window):
    """The read of every decode program: the Pallas kernel, at every
    geometry (PERF.md section 6, PR 30: it beats the gather at short
    contexts too). Off-TPU it runs in interpret mode so tests exercise
    the real kernel logic. A kernel that fails to lower raises: nothing
    falls back to the gather. ``window``: a window layer's ``first``
    and ``ring``, and a padded head's ``score_width``
    (``paged_attention``)."""
    return paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                           interpret=jax.default_backend() != "tpu",
                           **window)


# ----------------------------------------------------------------------
# page-cache update helpers (functional; jit-friendly)
# ----------------------------------------------------------------------

def _append_kernel(phys_ref, slot_ref, *refs):
    """One grid cell = one sequence: its tail page of each pool comes
    in, the row ``slot_ref[b]`` is replaced by the token, the page goes
    back to where it came from. ``refs``: a token, then a page in, then
    a page out for each pool. The row is picked by a ``where`` over an
    iota (a dynamic sublane store into packed bfloat16 need not lower);
    a slot of -1 picks none and the page goes back as it came."""
    import jax.experimental.pallas as pl

    del phys_ref  # read by the index maps
    n = len(refs) // 3
    slot = slot_ref[pl.program_id(0)]
    hit = jax.lax.broadcasted_iota(jnp.int32, refs[n].shape[1:],
                                   1) == slot
    for new_ref, in_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                        refs[2 * n:]):
        out_ref[0] = jnp.where(hit, new_ref[0][:, None, :], in_ref[0])


def append_token_kv(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                    k_new: jnp.ndarray, v_new: jnp.ndarray,
                    page_table: jnp.ndarray,
                    seq_lens: jnp.ndarray, ring: int = 0
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``append_token`` for K and V in one call."""
    return append_token((k_pages, v_pages), (k_new, v_new), page_table,
                        seq_lens, ring)


def append_token(pools: Tuple[jnp.ndarray, ...],
                 news: Tuple[jnp.ndarray, ...], page_table: jnp.ndarray,
                 seq_lens: jnp.ndarray, ring: int = 0
                 ) -> Tuple[jnp.ndarray, ...]:
    """Write one decode token's rows ``news`` ([B,KV,D] each) into each
    sequence's tail cell (page_table[b, seq_len // page], seq_len %
    page) of ``pools`` ([P,KV,page,D] each, of one shape), in place.
    With a ``ring`` (a window layer: ``page_table`` [B,ring]) the
    logical page wraps, ``(seq_len // page) % ring``, over the oldest
    page, whose tokens no later position sees.

    A Pallas kernel over grid (B,), every pool in one call: the pools
    are input AND output of the same buffers (``input_output_aliases``)
    in blocks of one page, chosen by the physical page ids that ride
    scalar prefetch, so a step reads and writes back B pages and
    touches nothing else of the pool. A Pallas operand also pins the
    pool's default layout, the one the paged kernel reads: the one-hot
    product this replaces, an XLA scatter and a loop of
    ``dynamic_update_slice`` each make the compiler choose a layout of
    their own for the pool and re-lay it out around the update, whole,
    every step (PERF.md, PR 28). The outputs, and through the aliases
    the inputs, are pinned to HBM: left free, the compiler fetched a
    pool that fits VMEM (50 MB) there for the kernel and its reader
    and wrote it back, whole, every step. Off the TPU the kernel runs
    in interpret mode, as ``paged_attention_auto``'s does.

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

    P, KV, page, D = pools[0].shape
    B, MP = page_table.shape
    n = len(pools)
    logical = (seq_lens // page) % ring if ring else seq_lens // page
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
        in_specs=[token] * n + [tail_page] * n,
        out_specs=[tail_page] * n,
    )
    return tuple(pl.pallas_call(
        _append_kernel, grid_spec=grid_spec,
        out_shape=[pltpu.HBM(pool.shape, pool.dtype) for pool in pools],
        # operands count the two prefetched scalars and the tokens
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=jax.default_backend() != "tpu",
    )(phys, slot, *[new.astype(pool.dtype)
                    for new, pool in zip(news, pools)], *pools))


def write_prefill_pages(pool: jnp.ndarray, seq: jnp.ndarray,
                        pages: jnp.ndarray) -> jnp.ndarray:
    """Write a prefilled sequence's rows [S,KV,D] into its pages ([n]
    physical ids; S must be <= n*page_size — the tail page may be
    partially filled, trailing slots are don't-care)."""
    page = pool.shape[2]
    n = pages.shape[0]
    pad = n * page - seq.shape[0]
    fill = jnp.concatenate(
        [seq, jnp.zeros((pad,) + seq.shape[1:], seq.dtype)])
    fill = fill.reshape(n, page, -1, seq.shape[-1]).transpose(
        0, 2, 1, 3)  # [n, KV, page, D]
    return pool.at[pages].set(fill.astype(pool.dtype))


def write_prefill_kv(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                     k_seq: jnp.ndarray, v_seq: jnp.ndarray,
                     pages: jnp.ndarray,
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``write_prefill_pages`` for K and V."""
    return (write_prefill_pages(k_pages, k_seq, pages),
            write_prefill_pages(v_pages, v_seq, pages))
