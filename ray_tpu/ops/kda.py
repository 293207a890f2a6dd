"""Gated delta rule with a per-channel decay: the recurrence of Kimi
Delta Attention (arXiv:2510.26692), the linear-attention mixer of
hybrid decoders whose other layers are softmax attention.

A head keeps a state ``S`` of ``[dk, dv]`` in float32. One position
with key ``k`` (unit length), value ``v``, query ``q``, log decay
``g <= 0`` a key channel and step size ``beta`` in (0, 2):

    S' = diag(exp(g)) S
    S  = S' + beta * k (v - k^T S')^T      # (I - beta k k^T) S' + beta k v^T
    o  = S^T q

Three forms of the same function:

- ``kda_step``: one position for every slot of a decode batch, in XLA:
  the plain recurrence the tests hold the kernels to.
- ``kda_decode_step``: one position for the LIVE slots of a decode
  batch, one Pallas call that reads and writes those slots' state in
  place and touches no other slot's. What it asks of its caller: under
  jit on a TPU the state is donated (or carried from a donated one);
  ``live`` has one entry a slot of the state, so every slot id the
  kernel derives from it lies inside the state.
- ``kda_chunked``: a whole prompt, a chunk of positions at a time. Inside
  a chunk the updates are a unit lower-triangular system (the WY form):
  with ``G_t`` the decay accumulated from the chunk's start,
  ``A[i,j] = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` for j < i, and
  ``(I + A) U = beta (V - (K exp(G)) S0)``; then
  ``O = (Q exp(G)) S0 + tril(Q K^T decayed) U`` and
  ``S1 = exp(G_C) S0 + (K exp(G_C - G))^T U``. Every exponent that is
  taken is a difference ``G_i - G_j`` with j <= i, so none is positive:
  a channel may decay by any factor inside a chunk without an overflow
  (the usual ``K / exp(G)`` is never formed).

``kda_chunked`` is ONE Pallas call (``kda_chunk_scan`` in a trace) whose
grid walks the chunks of a row with every head's ``[dk, dv]`` state
resident in VMEM from the first chunk to the last; q, k, v, g, beta are
read in the ``[N,S,H,*]`` layout they arrive in and the output is
written in it. How a chunk of 64 is made cheap (PERF.md section 6, PR
34, has the table that chose the sizes):

- the pairwise decay is taken element by element on the 16 x 16
  DIAGONAL blocks only. A block under the diagonal is a matrix product
  of ``K_i exp(G_i - G_b)`` against ``K_j exp(G_b - G_j)`` with ``b``
  the boundary between the two spans (``j < b <= i``: both exponents
  <= 0), neighbouring spans of 16, then of 32;
- ``(I + A)^-1`` is built by merging neighbouring spans from single
  rows up (``[[T1, 0], [-T2 A21 T1, T2]]``), two heads side by side in
  the matrix unit's 128 columns; it is as stable as substitution (the
  product ``(I - A)(I + A^2)(I + A^4)...`` is not at beta = 2);
- everything up to there hangs on q, k, g, beta and not on the state,
  so grid step t PREPARES chunk t while chunk t - 1, prepared a step
  earlier and kept in VMEM scratch, goes through the three state
  products: two independent streams of work in one basic block, the
  vector unit's (the diagonal blocks) under the matrix unit's;
- every product is float32 at ``Precision.HIGHEST``, state and operands
  float32. (The cumulative sum is a product with ones and zeros: three
  passes over ``g = hi + mid + lo``, an exact split, give what six
  would.)

A position with ``g = 0`` and ``beta = 0`` leaves the state as it is:
that is how the rows of a padded bucket past their prompt's length are
kept from touching it (``pad_mask``). The short causal convolution that
precedes the recurrence, and the tail of inputs it hands from a prompt
to the decode steps, are ``ops/short_conv.py``'s (a second mixer runs
them too) and are importable from here.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.short_conv import (conv_tail, short_conv,  # noqa: F401
                                    short_conv_step)

_HI = jax.lax.Precision.HIGHEST


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


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


# heads of one slot a grid step of ``kda_decode_step`` holds: a block
# of 32 heads' state is 2 MB at 128 x 128, read and written in 5.1 us
# at the v5e's 819 GB/s, against ~4,600 bundles of the body (bound
# by the cross-lane unit, which broadcasts q, k and exp(g) along the
# value axis), which the pipeline lays under the DMAs; 16 and 64 heads
# measured no better (PERF.md section 6, PR 36)
_DECODE_HEADS = 32


def _decode_kernel(ids_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref,
                   o_ref, s_out_ref):
    """Grid step (i, j): heads block j of the i-th live slot through one
    position, a head at a time on the vector unit in float32. q, k and
    g come as rows [h, dk] and are wanted as columns along the state's
    key axis, so they are transposed once a step."""
    del ids_ref   # read by the index maps
    qt, kt = q_ref[0].T, k_ref[0].T                   # [dk, h]
    dec = jnp.exp(g_ref[0].T)
    v, beta = v_ref[0], beta_ref[0, 0]                # [h, dv], [1, h]
    rows = []
    for h in range(s_ref.shape[1]):
        s = s_ref[0, h] * dec[:, h:h + 1]
        kc = kt[:, h:h + 1]
        u = beta[:, h:h + 1] * (
            v[h:h + 1] - jnp.sum(kc * s, 0, keepdims=True))
        s = s + kc * u
        s_out_ref[0, h] = s
        rows.append(jnp.sum(qt[:, h:h + 1] * s, 0, keepdims=True))
    o_ref[0] = jnp.concatenate(rows, 0)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _decode_step(q, k, v, g, beta, state, live, *, heads: int,
                 interpret: bool):
    """``kda_decode_step``. Jitted, so that the programs that call it
    with the same shapes (a decode program's layers, the engine's chunk
    sizes) trace the kernel once between them."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, h, dk, dv = state.shape
    # a block of heads is whole sublane tiles of q, k, v, g, or all
    hb = next(m for m in range(min(heads, h), 0, -1)
              if h % m == 0 and (m == h or m % 8 == 0))
    nh = h // hb
    ids = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n = jnp.sum(live, dtype=jnp.int32)

    def spec(*block):
        return pl.BlockSpec((1,) + block, lambda i, j, ids: (
            ids[i], j) + (0,) * (len(block) - 1))

    o, state = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n, nh),
            in_specs=[spec(hb, dk), spec(hb, dk), spec(hb, dv), spec(hb, dk),
                      spec(1, 1, hb), spec(hb, dk, dv)],
            out_specs=[spec(hb, dv), spec(hb, dk, dv)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), f32),
                   pltpu.HBM(state.shape, f32)],
        # operands count the prefetched ids
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret, name="kda_decode_step",
    )(ids, *(a.astype(f32) for a in (q, k, v, g)),
      beta.astype(f32).reshape(b, nh, 1, hb), state.astype(f32))
    return jnp.where(live[:, None, None], o, 0.0), state


def kda_decode_step(q, k, v, g, beta, state, live):
    """``kda_step`` for the ``live`` [B] bool slots alone, the state
    updated where it lies: one Pallas call (``kda_decode_step`` in a
    trace) whose grid runs over (live slot, block of heads). The grid's
    first bound is the live count, a traced scalar, and the live slots'
    ids, compacted to the front, ride scalar prefetch: the state block
    a step reads and writes is chosen through the id, and the state is
    input and output of one buffer (``input_output_aliases``). A dead
    slot has no grid step, so its state is neither read nor written.
    Returns (o [B,H,dv] float32, zeros in the dead slots' rows; state).

    What the caller owes, as for ``paged_attention.append_token``:
    under jit on a TPU the state is donated, or carried from a donated
    one, as in the engine's decode programs (the output, and through
    the alias the input, are pinned to HBM; not donated, the compiler
    copies the state first, and where the copy fits VMEM its memory
    assignment aborts). Off the TPU the kernel runs in interpret
    mode."""
    return _decode_step(q, k, v, g, beta, state, live, heads=_DECODE_HEADS,
                        interpret=jax.default_backend() != "tpu")


# positions a grid step takes through the three state products, the
# diagonal blocks whose pairwise decay is taken element by element, and
# the heads a grid step holds (the table that chose them: PERF.md
# section 6, PR 34)
_CHUNK = 64
_BLOCK = 16
_HEADS = 8


def _mm(a, b):
    """a [h,m,k] @ b [h,k,n] a head, float32 at full precision."""
    return jnp.einsum("hmk,hkn->hmn", a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _prepare(q, k, g, beta, block: int, out: dict):
    """What a chunk needs that does not hang on the state, for a few
    heads at once: q, k, g [h,C,dk] and beta [h,C,1]; ``out`` receives
    ``ke`` = K exp(G), ``qe`` = Q exp(G), ``kd`` = K exp(G_C - G)
    [h,C,dk], ``dec`` = exp(G_C) [h,1,dk], ``inv`` = (I + A)^-1 and
    ``qk`` = tril(Q K^T decayed) [h,C,C] of the docstring's WY form.
    The heads go through each stage together, a batch of every array (a
    head's stages hang on one another through the matrix unit's
    latency, and the compiler keeps the order it is given). A generator
    that stops after each stage, so that the stages of the chunk that
    is going through the state can be laid between them."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    h, c, dk = q.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    pos = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    # G, inclusive: a product with ones and zeros, so three passes of
    # the matrix unit over g = hi + mid + lo (exact: 3 x 8 bits) do
    # what six would
    tril = jnp.broadcast_to((col <= row).astype(bf16), (h, c, c))
    hi = g.astype(bf16)
    rest = g - hi.astype(f32)
    mid = rest.astype(bf16)
    low = (rest - mid.astype(f32)).astype(bf16)
    big = sum(jnp.einsum("hmk,hkn->hmn", tril, x, preferred_element_type=f32)
              for x in (low, mid, hi))
    yield
    # the diagonal blocks: decay from j to i >= j a key channel, the
    # exponent held at or under 0 BEFORE it is taken
    nb = c // block
    kk, qk = [], []
    for m in range(nb):
        km, qm, gm = (x[:, m * block:(m + 1) * block] for x in (k, q, big))
        kj = km[:, None] * jnp.exp(
            jnp.minimum(gm[:, :, None] - gm[:, None], 0.0))
        kk.append(jnp.sum(km[:, :, None] * kj, -1))
        qk.append(jnp.sum(qm[:, :, None] * kj, -1))
        yield
    same = row // block == col // block
    kk, qk = (jnp.where(same, jnp.tile(jnp.concatenate(x, 1), (1, 1, nb)),
                        0.0) for x in (kk, qk))
    # the blocks under the diagonal, a pair of neighbouring spans at a
    # time: rows of the later span decay from the boundary b between
    # the two, columns of the earlier span decay up to it, so that
    # exp(G_i - G_j) = exp(G_i - G_b) exp(G_b - G_j) with both <= 0
    span = block
    while span < c:
        pairs = c // (2 * span)
        later = (pos // span) % 2 == 1
        bound = jnp.broadcast_to(
            big.reshape(h, pairs, 2 * span, dk)[:, :, span - 1:span],
            (h, pairs, 2 * span, dk)).reshape(h, c, dk)
        e = jnp.exp(jnp.where(later, big - bound, bound - big))
        ke = k * e
        # the later rows alone go through the matrix unit
        rows = jnp.concatenate(
            [x.reshape(h, pairs, 2, span, dk)[:, :, 1].reshape(h, c // 2, dk)
             for x in (ke, q * e)], 1)
        p = jnp.einsum("hmk,hnk->hmn", rows, jnp.where(later, 0.0, ke),
                       precision=_HI, preferred_element_type=f32)
        p = p.reshape(h, 2, pairs, span, c)
        p = jnp.concatenate([jnp.zeros_like(p), p], 3).reshape(h, 2, c, c)
        pair = row // (2 * span) == col // (2 * span)
        kk += jnp.where(pair, p[:, 0], 0.0)
        qk += jnp.where(pair, p[:, 1], 0.0)
        span *= 2
        yield
    a = jnp.where(col < row, beta * kk, 0.0)
    # (I + A)^-1, pairs of neighbouring spans merged from single rows
    # up: [[T1, 0], [-T2 A21 T1, T2]] (a pair of rows: [[1, 0], [-a, 1]]).
    # The matrix unit's time goes by the rows it is fed, so as many
    # heads as fill its 128 columns go side by side, [C, w C], against
    # the block-diagonal [w C, w C] of the other factor
    w = max(d for d in range(1, h + 1) if h % d == 0 and d * c <= max(128, c))
    rw = jax.lax.broadcasted_iota(jnp.int32, (c, w * c), 0)
    cw = jax.lax.broadcasted_iota(jnp.int32, (c, w * c), 1) % c
    own = (jax.lax.broadcasted_iota(jnp.int32, (w * c, w * c), 0) // c
           == jax.lax.broadcasted_iota(jnp.int32, (w * c, w * c), 1) // c)
    diag = lambda x: jnp.where(own, jnp.tile(x, (1, w, 1)), 0.0)  # noqa: E731
    a = jnp.concatenate([a[i * (h // w):(i + 1) * (h // w)]
                         for i in range(w)], 2)
    inv = (rw == cw).astype(f32) - jnp.where(
        (rw % 2 == 1) & (cw == rw - 1), a, 0.0)
    span = 2
    while span < c:
        under = ((rw // span) % 2 == 1) & ((cw // span) % 2 == 0) & (
            rw // (2 * span) == cw // (2 * span))
        part = _mm(jnp.where(under, a, 0.0), diag(inv))
        yield
        inv = inv - _mm(inv, diag(part))
        yield
        span *= 2
    e = jnp.exp(big)
    # G_C by a masked sum, not the slice big[:, c - 1:c]: a row taken
    # from the last sublane of a tile and stored as a row of its own
    # aborts the TPU compiler (jax 0.9.0)
    last = jnp.sum(jnp.where(pos == c - 1, big, 0.0), 1, keepdims=True)
    out.update(
        ke=k * e, qe=q * e, kd=k * jnp.exp(last - big), dec=jnp.exp(last),
        inv=jnp.concatenate([inv[:, :, i * c:(i + 1) * c]
                             for i in range(w)], 0),
        qk=jnp.where(col <= row, qk, 0.0))


def _advance(v, beta, s0, kept, out: dict):
    """A prepared chunk through the state, for a few heads at once: v
    [h,C,dv], beta [h,C,1], s0 [h,dk,dv] and ``kept``, a function of
    the name that reads what ``_prepare`` left of the chunk; ``out``
    receives o [h,C,dv] and s1. A generator, as ``_prepare`` is."""
    c = v.shape[1]
    # (K e; Q e) share one load of the state
    into = _mm(jnp.concatenate([kept("ke"), kept("qe")], 1), s0)
    yield
    u = _mm(kept("inv"), beta * (v - into[:, :c]))
    yield
    out["o"] = into[:, c:] + _mm(kept("qk"), u)
    yield
    out["s"] = (jnp.swapaxes(kept("dec"), 1, 2) * s0 + jnp.einsum(
        "hck,hcv->hkv", kept("kd"), u, precision=_HI,
        preferred_element_type=jnp.float32))


_KEPT = ("ke", "qe", "kd", "dec", "inv", "qk")


def _scan_kernel(q_ref, k_ref, g_ref, beta_next_ref, v_ref, beta_ref, s0_ref,
                 o_ref, s_ref, *kept_refs, heads: int, block: int):
    """Grid step t prepares chunk t and takes chunk t - 1, which step
    t - 1 prepared, through the state: the two hang on nothing of one
    another, so the compiler can fill the matrix unit's waits of the
    one with the vector unit's work of the other. The first step's
    second half works on what the scratch happens to hold and is thrown
    away (its state by the select, its output by the next step, which
    writes the same block); the last step's first half prepares the
    last chunk again for nobody. A step holds every head and walks them
    ``heads`` at a time; a head's rows of a block [C * H, d] lie H
    apart."""
    import jax.experimental.pallas as pl

    t = pl.program_id(1)
    kept = dict(zip(_KEPT, kept_refs))
    n_heads = s_ref.shape[1]
    chunk = beta_ref.shape[1]

    @pl.when(t == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    def some_heads(i, carry):
        first = i * heads
        mine = pl.ds(first, heads)

        def rows(h):
            return (0, pl.ds(first + h, chunk, stride=n_heads), slice(None))

        def of(ref):           # [C*H,d] -> the heads' [h,C,d]
            return jnp.stack([ref[rows(h)] for h in range(heads)])

        def column(ref):       # [C,H] -> the heads' [h,C,1]
            x = ref[0]
            lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            return jnp.stack([
                jnp.sum(jnp.where(lane == first + h, x, 0.0), 1,
                        keepdims=True) for h in range(heads)])

        prepared, done = {}, {}
        s0 = s_ref[0, mine]
        ahead = _prepare(of(q_ref), of(k_ref), of(g_ref),
                         column(beta_next_ref), block, prepared)
        behind = _advance(of(v_ref), column(beta_ref), s0,
                          lambda name: kept[name][mine], done)
        # a stage of the one after each stage of the other
        for _ in ahead:
            next(behind, None)
        for _ in behind:
            pass
        for h in range(heads):
            o_ref[rows(h)] = done["o"][h]
        s_ref[0, mine] = jnp.where(t > 0, done["s"], s0)
        for name in _KEPT:
            kept[name][mine] = prepared[name]
        return carry

    jax.lax.fori_loop(0, n_heads // heads, some_heads, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _chunk_scan(q, k, v, g, beta, state, *, chunk: int, interpret: bool):
    """``kda_chunked`` with a state. Jitted, so that the programs that
    call it with the same shapes (a layer, a bucket) trace the kernel
    once between them."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    n, s, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    pad = (-s) % chunk
    if pad:       # no-op positions: g = 0, beta = 0
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk
    heads = next(m for m in range(min(_HEADS, h), 0, -1) if h % m == 0)

    # the operands are read where they lie: [N,S,H,d] is [N,S*H,d] as it
    # stands in memory (H whole sublane tiles), a chunk of every head
    # one block of C*H rows. A step reads the chunk it prepares and the
    # chunk before it, which it takes through the state
    def ahead(*block):
        return pl.BlockSpec((1,) + block, lambda b, t: (
            b, jnp.minimum(t, nc - 1), 0))

    def behind(*block):
        return pl.BlockSpec((1,) + block, lambda b, t: (
            b, jnp.maximum(t - 1, 0), 0))

    rows = lambda a: a.reshape(n, (s + pad) * h, -1)  # noqa: E731
    whole = pl.BlockSpec((1, h, dk, dv), lambda b, t: (b, 0, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_scan_kernel, heads=heads,
                          block=min(_BLOCK, chunk)),
        grid=(n, nc + 1),
        in_specs=[ahead(chunk * h, dk), ahead(chunk * h, dk),
                  ahead(chunk * h, dk), ahead(chunk, h),
                  behind(chunk * h, dv), behind(chunk, h), whole],
        out_specs=[behind(chunk * h, dv), whole],
        out_shape=[jax.ShapeDtypeStruct((n, (s + pad) * h, dv), f32),
                   jax.ShapeDtypeStruct((n, h, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((h,) + shape, f32) for shape in (
            (chunk, dk), (chunk, dk), (chunk, dk), (1, dk),
            (chunk, chunk), (chunk, chunk))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=100 << 20),
        interpret=interpret, name="kda_chunk_scan",
    )(rows(q), rows(k), rows(g), beta, rows(v), beta, state.astype(f32))
    return o.reshape(n, s + pad, h, dv)[:, :s], state


def kda_chunked(q, k, v, g, beta, state=None, *, chunk: int = _CHUNK):
    """A row of positions through the recurrence, one Pallas call whose
    grid walks the chunks with the state of every head held in VMEM
    (the module's docstring). q, k, g [N,S,H,dk], v [N,S,H,dv], beta
    [N,S,H]; ``state`` [N,H,dk,dv] float32 (zeros when None). S need
    not be a multiple of ``chunk``. Returns (o [N,S,H,dv] float32,
    final state). On a v5e at 64 heads of 128, 2,048 positions with a
    given state: 3.8 ms by the host's clock (2.7 ms of the device's
    inside the engine's launches) where the ``lax.scan`` it replaced
    took 6.9 (6.0 of the device's); chunk, block and heads by the table
    of PERF.md section 6, PR 34."""
    if state is None:
        n, _s, h, dk = q.shape
        state = jnp.zeros((n, h, dk, v.shape[-1]), jnp.float32)
    return _chunk_scan(q, k, v, g, beta, state, chunk=chunk,
                       interpret=jax.default_backend() != "tpu")
