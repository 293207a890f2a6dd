"""Mixture-of-Experts — expert parallelism over the `expert` mesh axis.

Absent from the reference core (SURVEY.md §2.3: integration-only), so
built TPU-first: top-1 capacity-factor routing (the Switch-Transformer
formulation) producing a dense dispatch tensor, tokens exchanged to
their experts with jax.lax.all_to_all over the ICI inside shard_map,
per-device expert FFMs as one batched einsum on the MXU, and the
reverse all_to_all + weighted combine.

Layout inside shard_map over ("expert",):
  tokens   [T_local, D]      (token axis sharded over `expert`)
  experts  [E_local, ...]    (expert weights sharded over `expert`)
  dispatch [E_total, C, D]   per device -> all_to_all -> each device
           holds its E_local experts' slices from every peer.

Beside that training layer stands the expert layer as deployed models
have it (``route_topk``, ``experts_held``): sigmoid scores, the top-k
of ALL experts with weights normalised over the picked, gated (SwiGLU)
experts, no capacity and no dropped token. A chip is told which experts
it holds (a range of ids) and computes the part of the result that its
own experts give, by grouped products over the picks sorted by expert
(``grouped_matmul``: one Pallas call, ``moe_grouped`` in a trace, that
multiplies the held experts' rows and no others); picks that fall on
experts held elsewhere add nothing here. On one chip the layer runs
without its exchange.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def top1_dispatch(logits: jnp.ndarray, capacity: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The routing kernel: token -> (expert, slot) under capacity.

    logits [T, E]. Returns (dispatch [T, E, C] one-hot f32,
    combine [T, E, C] prob-weighted, aux_loss scalar — the
    load-balancing loss of Shazeer et al.). Tokens beyond an expert's
    capacity are DROPPED (standard switch routing; the residual path
    carries them)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                      # [T]
    prob = jnp.take_along_axis(probs, expert[:, None], 1)[:, 0]
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)    # [T, E]
    # position of each token within its expert's queue
    position = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot   # [T, E]
    keep = (position < capacity) & (onehot > 0)
    slot = jnp.where(keep, position, 0).astype(jnp.int32)
    dispatch = (keep[..., None]
                * jax.nn.one_hot(slot, capacity, dtype=jnp.float32))
    combine = dispatch * prob[:, None, None]
    # load balancing: fraction routed * mean prob, per expert
    frac = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_ffn_local(tokens, w_router, w_in, w_out, capacity_factor: float,
                  axis_name: str = "expert"):
    """The shard_map body: tokens [T,D] (this device's shard), w_router
    [D,E_total], w_in [E_local,D,F], w_out [E_local,F,D]. Returns
    ([T,D] expert outputs combined per token, aux loss)."""
    n = jax.lax.psum(1, axis_name)
    T, D = tokens.shape
    e_local = w_in.shape[0]
    E = e_local * n
    capacity = max(1, int(T * capacity_factor / E))

    logits = tokens @ w_router                       # [T, E]
    dispatch, combine, aux = top1_dispatch(logits, capacity)

    # gather tokens into expert slots: [E, C, D]
    slots = jnp.einsum("tec,td->ecd", dispatch, tokens)
    # exchange over the ring: split the expert axis across devices and
    # concat the peer shards -> [E_local, n*C, D] on each device
    slots = slots.reshape(n, e_local, capacity, D)
    slots = jax.lax.all_to_all(slots, axis_name, split_axis=0,
                               concat_axis=0, tiled=False)
    slots = jnp.moveaxis(slots, 0, 1).reshape(e_local, n * capacity, D)

    # expert FFN (batched over local experts -> one MXU einsum chain)
    h = jnp.einsum("ecd,edf->ecf", slots, w_in)
    h = jax.nn.gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h, w_out)

    # reverse exchange: send each peer its tokens' results back
    out = out.reshape(e_local, n, capacity, D)
    out = jnp.moveaxis(out, 1, 0)
    out = jax.lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
    out = out.reshape(E, capacity, D)

    # combine back per token, weighted by the router prob
    y = jnp.einsum("tec,ecd->td", combine, out)
    aux = jax.lax.pmean(aux, axis_name)
    return y, aux


def moe_ffn_reference(tokens, w_router, w_in_full, w_out_full,
                      capacity_factor: float):
    """Single-device oracle with identical routing/capacity semantics.
    tokens [T,D], w_in_full [E,D,F], w_out_full [E,F,D]."""
    T, D = tokens.shape
    E = w_in_full.shape[0]
    capacity = max(1, int(T * capacity_factor / E))
    logits = tokens @ w_router
    dispatch, combine, aux = top1_dispatch(logits, capacity)
    slots = jnp.einsum("tec,td->ecd", dispatch, tokens)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", slots, w_in_full))
    out = jnp.einsum("ecf,efd->ecd", h, w_out_full)
    return jnp.einsum("tec,ecd->td", combine, out), aux


def moe_ffn_sharded(tokens, w_router, w_in, w_out, mesh,
                    capacity_factor: float = 1.25,
                    axis_name: str = "expert"):
    """Global entry: tokens [T, D] sharded over the expert axis (token
    rows), w_in/w_out [E, ...] sharded over experts, router replicated.
    NOTE: per-device routing — each device routes ITS tokens against all
    experts with per-shard capacity (the standard data-local
    formulation)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.collectives import shard_map_norep

    fn = functools.partial(moe_ffn_local,
                           capacity_factor=capacity_factor,
                           axis_name=axis_name)
    return shard_map_norep(
        fn, mesh=mesh,
        in_specs=(P(axis_name, None), P(None, None),
                  P(axis_name, None, None), P(axis_name, None, None)),
        out_specs=(P(axis_name, None), P()))(
            tokens, w_router, w_in, w_out)


# ----------------------------------------------------------------------
# the deployed expert layer: top-k of all, dropless, a held share
# ----------------------------------------------------------------------

def route_topk(x: jnp.ndarray, w_router: jnp.ndarray, top_k: int,
               scale: float = 1.0, bias: Optional[jnp.ndarray] = None,
               eps: float = 1e-20) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [T,d], w_router [d,E] -> (ids [T,k] int32, weights [T,k] f32).
    The router runs in float32 whatever the model's type: a near-tie
    between the k-th and the next expert decides which experts a token
    sees. Scores are sigmoids; the weights of the k picked sum to
    ``scale`` (a model's routed scaling factor; 1 multiplies
    nothing). ``bias`` [E] SELECTS and never weighs: the k picked are
    the largest of ``score + bias``, their weights come from the scores
    alone (a load-balancing bias that training moves; such a router
    guards its denominator with ``eps``: 1e-20 in Trinity-Large's
    router, 1e-6 in LFM2's)."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if bias is None:
        picked, ids = jax.lax.top_k(scores, top_k)
        weights = picked / picked.sum(-1, keepdims=True)
    else:
        _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        weights = picked / (picked.sum(-1, keepdims=True) + eps)
    return ids.astype(jnp.int32), (weights if scale == 1.0
                                   else weights * scale)


# ----------------------------------------------------------------------
# the held experts' grouped products: one Pallas call a matrix
# ----------------------------------------------------------------------

# the most bytes of one expert's matrix a grid step of ``grouped_matmul``
# holds (double-buffered, so twice this in VMEM): a whole column block
# of the contraction, so that the consecutive row tiles of one expert
# find it in place and each touched expert is read once a call
_RHS_BLOCK_BYTES = 16 << 20


def grouped_tiling(m: int, k: int, n: int, itemsize: int
                   ) -> Tuple[int, int]:
    """(tm, tn) of ``grouped_matmul`` for rows [m, k] against experts
    [E, k, n] of ``itemsize`` bytes: rows a tile by the number of rows
    (a decode step's few hundred picks, of which a handful are local,
    take one tile a touched expert; a prompt's block of tens of
    thousands takes tiles of 256), columns the widest divisor of ``n``
    in lanes of 128 (or ``n`` itself) whose ``[k, tn]`` block stays
    within ``_RHS_BLOCK_BYTES``. The contraction is never split."""
    tm = 128 if m <= 4096 else 256
    fits = [c for c in range(n, 0, -1) if n % c == 0
            and (c == n or c % 128 == 0)]
    small = [c for c in fits if k * c * itemsize <= _RHS_BLOCK_BYTES]
    return tm, (small or fits[-1:])[0]


def _visits(sizes, m: int, tm: int):
    """The grid's row visits for groups of ``sizes`` [E] laid one after
    another from row 0: each non-empty group visits every tile of
    ``tm`` rows that holds one of its rows, in order of group, so the
    tiles come in order too and a tile shared by two groups is visited
    twice in a row. Rows past the last group and empty groups have no
    visit. Returns (the number of visits, a traced scalar; group and
    tile of each visit, [m // tm + E - 1], the most there can be; first
    and end row of each group, [E])."""
    e = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    done = jnp.cumsum(count)
    v = jnp.arange(m // tm + e - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(done[None, :] <= v[:, None], axis=1,
                                dtype=jnp.int32), e - 1)
    tile = jnp.clip(first[group] + v - (done - count)[group], 0,
                    m // tm - 1)
    return done[-1], group, tile, starts, ends


def _grouped_kernel(group_ref, tile_ref, start_ref, end_ref, lhs_ref,
                    rhs_ref, out_ref):
    """Grid step (j, i): the i-th visit's rows against column block j of
    its group's matrix. Rows of the tile that are not the group's keep
    what the tile holds: another group's, written on the visit before,
    or nothing anyone reads."""
    import jax.experimental.pallas as pl

    i = pl.program_id(1)
    g = group_ref[i]
    row = tile_ref[i] * out_ref.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, out_ref.shape, 0)
    mine = (row >= start_ref[g]) & (row < end_ref[g])
    y = jnp.dot(lhs_ref[...], rhs_ref[...],
                preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, y, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _moe_grouped(lhs, rhs, sizes, *, tm: int, tn: int, interpret: bool):
    """``grouped_matmul`` at a static tiling. Jitted, so that the three
    matrices of every expert layer of every program that calls it with
    the same shapes trace the kernel once between them."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), n = lhs.shape, rhs.shape[2]
    visits, group, tile, starts, ends = _visits(sizes, m, tm)
    return pl.pallas_call(
        _grouped_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n // tn, visits),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, g, t, s, e: (t[i], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, i, g, t, s, e: (g[i], 0, j))],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, g, t, s, e: (t[i], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 << 20),
        interpret=interpret, name="moe_grouped",
    )(group, tile, starts, ends, lhs, rhs)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray, sizes: jnp.ndarray
                   ) -> jnp.ndarray:
    """``jax.lax.ragged_dot(lhs, rhs, sizes)`` on the rows that belong to
    a group: lhs [m, k] holds group 0's ``sizes[0]`` rows, then group
    1's, ...; rhs [E, k, n]; the result [m, n] is in lhs's type, the
    products accumulated in float32. Rows past ``sum(sizes)`` belong to
    no group and are not computed: what the result holds there is
    undefined, and the caller masks them. One Pallas call (``moe_grouped``
    in a trace) whose grid's bound is the number of row tiles that hold
    a group's rows, a traced scalar; a touched group's ``[k, tn]`` block
    is fetched once for all of its tiles, so a call reads the touched
    experts' matrices once and no other. Off the TPU the kernel runs in
    interpret mode."""
    m, k = lhs.shape
    tm, tn = grouped_tiling(m, k, rhs.shape[2], lhs.dtype.itemsize)
    pad = -m % tm
    out = _moe_grouped(jnp.pad(lhs, ((0, pad), (0, 0))) if pad else lhs,
                       rhs, sizes, tm=tm, tn=tn,
                       interpret=jax.default_backend() != "tpu")
    return out[:m] if pad else out


def _experts_held_block(x, ids, weights, valid, w_gate, w_up, w_down,
                        lo: int):
    t, k = ids.shape
    e = w_gate.shape[0]
    local = (ids >= lo) & (ids < lo + e) & valid[:, None]
    # a pick on an expert held elsewhere goes to group e, which sorts
    # last and which the grouped products never reach
    group = jnp.where(local, ids - lo, e).reshape(-1)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((e + 1,), jnp.int32).at[group].add(1)[:e]
    rows = x[order // k]                               # [T*k, d]
    h = (jax.nn.silu(grouped_matmul(rows, w_gate, sizes))
         * grouped_matmul(rows, w_up, sizes))
    out = grouped_matmul(h, w_down, sizes)
    # back to the picks' own order; rows past the last group are
    # whatever the kernel left there, so they are masked, not scaled
    back = jnp.argsort(order)
    picked = jnp.where(local[:, :, None], out[back].reshape(t, k, -1), 0)
    y = (picked.astype(jnp.float32) * weights[:, :, None]).sum(axis=1)
    return y.astype(x.dtype), sizes


def experts_held(x: jnp.ndarray, ids: jnp.ndarray, weights: jnp.ndarray,
                 w_gate: jnp.ndarray, w_up: jnp.ndarray,
                 w_down: jnp.ndarray, first_held: int,
                 valid: Optional[jnp.ndarray] = None,
                 block_tokens: int = 8192
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of the layer: ``sum_i w_i E_i(x)`` over a
    token's picks ``i`` in ``[first_held, first_held + E_held)``, with
    ``E(x) = w_down(silu(w_gate x) * (w_up x))``. x [T,d]; ids, weights
    [T,k] from ``route_topk``; w_gate, w_up [E_held,d,f]; w_down
    [E_held,f,d]. No capacity: every local pick is computed. ``valid``
    [T] marks the tokens that count (a padded bucket's other positions
    are neither computed nor counted). Returns (y [T,d], picks a held
    expert [E_held] int32). More than ``block_tokens`` tokens go a block
    at a time, so that the sorted copies of a long prompt's picks stay
    bounded."""
    t = x.shape[0]
    if valid is None:
        valid = jnp.ones((t,), bool)
    if t <= block_tokens:
        return _experts_held_block(x, ids, weights, valid, w_gate, w_up,
                                   w_down, first_held)
    pad = (-t) % block_tokens
    parts = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
             for a in (x, ids, weights, valid)]
    parts = [a.reshape((-1, block_tokens) + a.shape[1:]) for a in parts]
    y, sizes = jax.lax.map(
        lambda b: _experts_held_block(*b, w_gate, w_up, w_down,
                                      first_held), tuple(parts))
    return y.reshape(-1, y.shape[-1])[:t], sizes.sum(axis=0)
