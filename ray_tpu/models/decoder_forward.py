"""What a described decoder computes, and what it keeps between steps.

``models/decoder.py`` says, layer by layer, what a model is; this module
runs it: the functional forward over the parameter tree a description
stands for (the dense decoder's is the flax module's, so what a Train
run produces serves directly; a parity test pins this forward to the
module's output). Every choice between kinds of layer is made here,
while tracing.

It owns the cache, one entry a layer: ``(k_pages, v_pages)`` for an
attention layer (its own pool, [P,KV,page,D]), the same pair for a
window layer but in a pool of ``batch_size`` rings (``window_ring``
pages a slot, which are the slot's own: a token at position ``t`` lies
in ring page ``(t // page_size) % ring``, so the layer's cache stops
growing at the window), ``(state, tail)`` a slot for a delta-rule
layer, ``(latent_pages,)`` for a latent layer (one pool [P,1,page,W]
whose row a token is the normed latent and the rotated shared key),
``(tail,)`` a slot for a conv layer (the last K-1 rows of ``B * x``
before the slot's next position, float32).
What a kind of mixer keeps and does is one entry
of ``MIXERS_BY_KIND`` (``init``, ``write``, ``prefill``, ``decode``,
and whether what it keeps is keys and values a position that a caller
may hold itself);
the layer around it (norms, residual, feed-forward) is the same for
all. A pytree, never stacked: each layer's append
kernel takes its own pool as input and output of one buffer under
jit/scan, and one [L,...] array would be copied whole every step. The
serving engine (models/inference.py) holds it as one donated value and
never looks inside; page tables, slots and the parking page are the
engine's.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.models.decoder import DecoderConfig, LayerSpec, describe
from ray_tpu.ops import kda
from ray_tpu.ops.flash import flash_attention_bshk, flash_supported
from ray_tpu.ops.mla_prefill import (mla_prefill_attention,
                                     window_prefill_attention)
from ray_tpu.ops.moe import experts_held, route_topk
from ray_tpu.ops.paged_attention import (append_token, append_token_kv,
                                         paged_attention_auto,
                                         paged_latent_attention,
                                         write_prefill_kv,
                                         write_prefill_pages)
from ray_tpu.ops.rope import rope
from ray_tpu.ops.short_conv import conv_tail, short_conv, short_conv_step

# a row longer than this never materialises its [S,S] scores
_SCORES_MAX_SEQ = 512
# numbers in the sorted copy of one block of tokens' picks (tokens x
# picks a token x d_model): the expert layer goes a block at a time,
# the largest power of two of tokens that stays within this (8,192
# tokens at 8 picks and a width of 4,096; 4,096 at a width of 7,680)
_EXPERT_BLOCK_NUMBERS = 1 << 28


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            * scale).astype(x.dtype)


def _mlp(p, x, dtype):
    h = (jax.nn.silu(x @ p["w_gate"].astype(dtype))
         * (x @ p["w_up"].astype(dtype)))
    return h @ p["w_down"].astype(dtype)


def _experts(m, cfg: DecoderConfig, x, valid):
    """The expert feed-forward over x [..., d] (normed): routed experts
    held here plus the shared one. Returns (y, picks a held expert
    [E_held] int32, of the ``valid`` tokens)."""
    t = x.reshape(-1, x.shape[-1])
    dt = cfg.dtype
    with jax.named_scope("moe_route"):
        ids, weights = route_topk(t, m["router"], cfg.experts_per_token,
                                  cfg.routed_scale,
                                  m["bias"] if cfg.router_bias else None,
                                  cfg.router_eps)
    with jax.named_scope("moe_experts"):
        block = _EXPERT_BLOCK_NUMBERS // (cfg.experts_per_token
                                          * cfg.d_model)
        y, counts = experts_held(
            t, ids, weights, m["w_gate"].astype(dt), m["w_up"].astype(dt),
            m["w_down"].astype(dt), cfg.experts_held[0],
            valid.reshape(-1), 1 << (block.bit_length() - 1))
    if cfg.d_shared:
        with jax.named_scope("moe_shared"):
            y = y + _mlp(m["shared"], t, dt)
    return y.reshape(x.shape), counts


def _feed_forward(p, cfg: DecoderConfig, spec: LayerSpec, x, valid):
    """x + FFN(norm(x)) of one layer (the branch normed once more before
    the add, of a model with sandwich norms); the picks a held expert,
    or None."""
    h = _rms(x, p["RMSNorm_1"]["scale"], cfg.norm_eps)
    if spec.ffn == "dense":
        with jax.named_scope("mlp"):
            y, counts = _mlp(p["MLP_0"], h, cfg.dtype), None
    else:
        y, counts = _experts(p["MoE_0"], cfg, h, valid)
    if cfg.sandwich_norm:
        y = _rms(y, p["PostNorm_1"]["scale"], cfg.norm_eps)
    return x + y, counts


def _seen(rows, cols, window: int = 0):
    """Which of the positions ``cols`` [T] each of ``rows`` [S] sees:
    those up to its own and, of a ``window``, the last that many."""
    seen = cols[None, :] <= rows[:, None]
    if window:
        seen &= cols[None, :] > rows[:, None] - window
    return seen


def _blockwise_scores_attention(q, kr, vr, scale, block=_SCORES_MAX_SEQ,
                                window: int = 0):
    """Causal attention over [N,S,H,D] (heads repeated; values of any
    width) with the scores of one block of query rows at a time; of a
    ``window``, over a row's last that many positions."""
    n, s, h, _ = q.shape
    pad = (-s) % block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * block, block, axis=1)
        scores = (jnp.einsum("bshk,bthk->bhst", qb, kr)
                  * scale).astype(jnp.float32)
        seen = _seen(i * block + jnp.arange(block), jnp.arange(s), window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e30),
                               axis=-1).astype(q.dtype)
        return jnp.einsum("bhst,bthk->bshk", probs, vr)

    out = jax.lax.map(rows, jnp.arange((s + pad) // block))
    return jnp.moveaxis(out, 0, 1).reshape(n, s + pad, h, -1)[:, :s]


def _blockwise_attention(q, kr, vr):
    """Causal attention over [N,S,H,D] (heads repeated) without the
    [S,S] scores: the flash kernel where it runs (ops/flash.py), else
    the scores of one block of query rows at a time."""
    if flash_supported(q.shape[-1]):
        return flash_attention_bshk(q, kr, vr)
    return _blockwise_scores_attention(q, kr, vr, q.shape[-1] ** -0.5)


def _attention_qkv(a, cfg: DecoderConfig, mixer: str, h, positions):
    """What softmax attention of kind ``mixer`` takes from the normed
    input h [...,d] at ``positions`` [...]: (q [...,H,D], k, v
    [...,KV,D]), q and k normed a head and rotated where the model
    says so."""
    q, k, v = (jnp.einsum("...d,dhk->...hk", h, a[w].astype(cfg.dtype))
               for w in ("wq", "wk", "wv"))
    if cfg.qk_norm:
        q = _rms(q, a["q_norm"], cfg.norm_eps)
        k = _rms(k, a["k_norm"], cfg.norm_eps)
    if cfg.rotates(mixer):
        if h.ndim == 2:
            # rope over a length-1 "sequence" per slot
            q = rope(q[:, None], positions[:, None], cfg.rope_theta)[:, 0]
            k = rope(k[:, None], positions[:, None], cfg.rope_theta)[:, 0]
        else:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attention_out(a, cfg: DecoderConfig, h, attn):
    """attn [...,H,D] -> the mixer's output [...,d]: the gate a element
    of a gated model, the output projection."""
    if cfg.attn_gate:
        attn = attn * jax.nn.sigmoid(jnp.einsum(
            "...d,dhk->...hk", h, a["w_gate"].astype(cfg.dtype)))
    return jnp.einsum("...hk,hkd->...d", attn.astype(cfg.dtype),
                      a["wo"].astype(cfg.dtype))


def _window_attention(q, k, v, window: int):
    """Attention over a long row's band: q [N,S,H,D], k, v [N,S,KV,D].
    The kernel of ops/mla_prefill.py where the flash kernel would run
    (each key head read where it lies by its G query heads), else the
    scores of a block of query rows at a time."""
    if flash_supported(q.shape[-1]):
        out = window_prefill_attention(
            *(jnp.moveaxis(t, 1, 2) for t in (q, k, v)), window=window)
        return jnp.moveaxis(out, 2, 1)
    rep = q.shape[2] // k.shape[2]
    return _blockwise_scores_attention(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
        q.shape[-1] ** -0.5, window=window)


def _prefill_attention(a, cfg: DecoderConfig, h, positions,
                       mixer: str = "attention"):
    """Softmax attention over a bucket, causal and of a ``window``
    layer banded: h [N,S,Dm] (normed) -> (out [N,S,Dm] before the
    residual, k, v [N,S,KV,D])."""
    q, k, v = _attention_qkv(a, cfg, mixer, h, positions)
    window = cfg.window if mixer == "window" else 0
    s = h.shape[1]
    if s > _SCORES_MAX_SEQ and window:
        attn = _window_attention(q, k, v, window)
    else:
        rep = cfg.n_heads // cfg.n_kv_heads
        kr = jnp.repeat(k, rep, axis=2)
        vr = jnp.repeat(v, rep, axis=2)
        if s > _SCORES_MAX_SEQ:
            attn = _blockwise_attention(q, kr, vr)
        else:
            at = jnp.arange(s)
            scores = (jnp.einsum("bshk,bthk->bhst", q, kr)
                      / jnp.sqrt(cfg.head_dim))
            scores = jnp.where(_seen(at, at, window)[None, None],
                               scores.astype(jnp.float32), -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            attn = jnp.einsum("bhst,bthk->bshk", probs, vr)
    return _attention_out(a, cfg, h, attn), k, v


def _delta_rule_inputs(a, cfg: DecoderConfig, h, mixed):
    """What the recurrence takes, from the normed input h [..., d] and
    the convolved, activated projections ``mixed`` [..., H, 2dk+dv]:
    (q, k, v, g, beta), float32."""
    dk = cfg.dr_key_dim
    f32 = jnp.float32
    q = kda.l2norm(mixed[..., :dk]) * dk ** -0.5
    k = kda.l2norm(mixed[..., dk:2 * dk])
    v = mixed[..., 2 * dk:].astype(f32)
    low = jnp.einsum("...d,dr->...r", h, a["w_f_down"].astype(cfg.dtype))
    step = jnp.einsum("...r,rhk->...hk", low,
                      a["w_f_up"].astype(cfg.dtype)).astype(f32)
    g = -jnp.exp(a["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        step + a["dt_bias"].astype(f32))
    beta = 2.0 * jax.nn.sigmoid(jnp.einsum(
        "...d,dh->...h", h, a["w_beta"].astype(cfg.dtype)).astype(f32))
    return q, k, v, g, beta


def _delta_rule_output(a, cfg: DecoderConfig, h, o):
    """o [..., H, dv] float32 -> the layer's output [..., d]: a norm a
    head, the low-rank sigmoid gate, the output projection."""
    f32 = jnp.float32
    o = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                           + cfg.norm_eps) * a["o_norm"].astype(f32))
    low = jnp.einsum("...d,dr->...r", h, a["w_g_down"].astype(cfg.dtype))
    gate = jax.nn.sigmoid(jnp.einsum(
        "...r,rhv->...hv", low, a["w_g_up"].astype(cfg.dtype)).astype(f32))
    return jnp.einsum("...hv,hvd->...d", (o * gate).astype(cfg.dtype),
                      a["wo"].astype(cfg.dtype))


def _delta_rule_projections(a, cfg: DecoderConfig, h):
    """(q|k|v projections side by side a head, flattened to channels
    [..., H*(2dk+dv)]; the convolution's weights [K, channels])."""
    qkv = jnp.concatenate(
        [jnp.einsum("...d,dhk->...hk", h, a[w].astype(cfg.dtype))
         for w in ("wq", "wk", "wv")], axis=-1)
    w = jnp.concatenate([a["conv_q"], a["conv_k"], a["conv_v"]], axis=-1)
    return (qkv.reshape(qkv.shape[:-2] + (-1,)),
            w.reshape(w.shape[0], -1).astype(jnp.float32))


# positions of a launch that a delta_rule layer works on at a time: its
# float32 intermediates (24,576 channels a position at the published
# widths) are held for one segment, not for the bucket
_DELTA_RULE_SEGMENT = 2048


def _prefill_delta_rule(a, cfg: DecoderConfig, h, plens):
    """The recurrent mixer over a bucket: h [N,S,Dm] (normed), plens
    [N] valid positions a row -> (out [N,S,Dm], state [N,H,dk,dv]
    float32 after position plens-1, tail [N,K-1,channels] of
    projections before position plens). Positions past a row's length
    leave its state alone. The bucket goes a segment of positions at a
    time, state and convolution tail carried from one to the next."""
    n, s, d = h.shape
    taps = cfg.dr_conv
    seg = min(s, max(64, _DELTA_RULE_SEGMENT // n))
    pad = (-s) % seg
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    segments = jnp.moveaxis(hp.reshape(n, -1, seg, d), 1, 0)

    def segment(carry, xs):
        state, tail = carry
        hs, start = xs
        flat, w = _delta_rule_projections(a, cfg, hs)
        mixed = jax.nn.silu(short_conv(flat.astype(jnp.float32), w, tail))
        q, k, v, g, beta = _delta_rule_inputs(
            a, cfg, hs, mixed.reshape(n, seg, cfg.dr_heads,
                                      cfg.dr_channels))
        g, beta = kda.pad_mask(g, beta, plens - start)
        o, state = kda.kda_chunked(q, k, v, g, beta, state)
        return ((state, flat[:, seg - (taps - 1):]),
                _delta_rule_output(a, cfg, hs, o))

    with jax.named_scope("kda"):
        zero = (jnp.zeros((n, cfg.dr_heads, cfg.dr_key_dim,
                           cfg.dr_value_dim), jnp.float32),
                jnp.zeros((n, taps - 1, cfg.dr_heads * cfg.dr_channels),
                          cfg.dtype))
        (state, _), out = jax.lax.scan(
            segment, zero,
            (segments, jnp.arange(segments.shape[0]) * seg))
        out = jnp.moveaxis(out, 0, 1).reshape(n, s + pad, d)[:, :s]
        # what the convolution of position plens needs: the
        # projections of the row's last K-1 inputs
        tail, _ = _delta_rule_projections(a, cfg, conv_tail(h, plens, taps))
        return out, state, tail


def _decode_delta_rule(p, cfg: DecoderConfig, h, kept, page_table,
                       seq_lens, live):
    """One position a slot: h [B,Dm] (normed), ``kept`` the layer's
    (state, tail). Slots that are not ``live`` keep their state and
    tail as they are: the kernel reads and writes the live slots'
    state alone, in place."""
    a = p["DeltaRule_0"]
    state, tail = kept
    with jax.named_scope("kda"):
        flat, w = _delta_rule_projections(a, cfg, h)
        mixed, new_tail = short_conv_step(flat.astype(jnp.float32), w, tail)
        q, k, v, g, beta = _delta_rule_inputs(
            a, cfg, h, jax.nn.silu(mixed).reshape(
                h.shape[0], cfg.dr_heads, cfg.dr_channels))
        o, state = kda.kda_decode_step(q, k, v, g, beta, state, live)
        out = _delta_rule_output(a, cfg, h, o)
    with jax.named_scope("kda_state"):
        tail = jnp.where(live[:, None, None], new_tail.astype(tail.dtype),
                         tail)
    return out, (state, tail)


# ----------------------------------------------------------------------
# latent attention: a prompt in the published, expanded form; a decode
# step in the absorbed form against what the layer keeps
# ----------------------------------------------------------------------

def _latent_row(a, cfg: DecoderConfig, h, positions):
    """The row a latent layer keeps a token, h [...,S,d] (normed) at
    ``positions`` [...,S] -> [...,S,W]: the normed latent, the rotated
    key all heads share, zeros up to whole lanes."""
    kva = h @ a["w_kva"].astype(cfg.dtype)
    c = _rms(kva[..., :cfg.kv_rank], a["kv_norm"], cfg.norm_eps)
    k_rope = rope(kva[..., None, cfg.kv_rank:], positions,
                  cfg.rope_theta)[..., 0, :]
    pad = cfg.latent_width - cfg.kv_rank - cfg.rope_dim
    return jnp.concatenate(
        [c, k_rope, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)


def _latent_queries(cfg: DecoderConfig, cq, w_qb, positions):
    """The heads of ``w_qb`` [q_rank,h,n+p] over the normed compressed
    query cq [...,S,q_rank] -> (q_nope [...,S,h,n], q_rope [...,S,h,p]
    rotated)."""
    q = jnp.einsum("...r,rhk->...hk", cq, w_qb)
    return (q[..., :cfg.nope_dim],
            rope(q[..., cfg.nope_dim:], positions, cfg.rope_theta))


def _latent_prefill_attention(q, k, v, scale):
    """Causal attention over [N,H,S,*] with keys and values of two
    widths at the true ``scale``, never the [S,S] scores of a long row:
    the kernel of ops/mla_prefill.py where the flash kernel would run,
    else the scores of a block of query rows at a time."""
    s = q.shape[2]
    if s > _SCORES_MAX_SEQ and flash_supported(128):
        return mla_prefill_attention(q, k, v, scale=scale)
    if s > _SCORES_MAX_SEQ:
        out = _blockwise_scores_attention(
            *(jnp.moveaxis(t, 1, 2) for t in (q, k, v)), scale)
        return jnp.moveaxis(out, 2, 1)
    scores = jnp.einsum("bhsk,bhtk->bhst", q, k) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None],
                       scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bhtv->bhsv", probs, v)


# positions x heads of a launch that the expanded form holds at a time:
# its per-head queries, keys and values (704 numbers a position and
# head at the published widths, lanes padded) exist for one group of
# heads, not for all 128 (8,192 positions x 32 heads)
_LATENT_GROUP_NUMBERS = 1 << 18


def _prefill_latent(p, cfg: DecoderConfig, h, positions, plens):
    """Latent attention over a bucket in the expanded form: h [N,S,Dm]
    (normed) -> (out [N,S,Dm], (rows [N,S,W],) that the layer keeps).
    Every head gets its own keys [w_kvb's first n columns over the
    latent | the shared rotated key] and values, as published; the
    heads go a group at a time, each group's part of the output
    projection summed in float32."""
    a = p["LatentAttention_0"]
    dt = cfg.dtype
    n, s, _ = h.shape
    heads = cfg.n_heads
    hg = max(1, min(heads, _LATENT_GROUP_NUMBERS // (n * s)))
    while heads % hg:
        hg -= 1
    with jax.named_scope("mla"):
        row = _latent_row(a, cfg, h, positions)
        cq = _rms(h @ a["w_qa"].astype(dt), a["q_norm"], cfg.norm_eps)
        c = row[..., :cfg.kv_rank]
        k_rope = row[:, None, :, cfg.kv_rank:cfg.kv_rank + cfg.rope_dim]

        def group(out, g):
            def mine(w, axis):
                return jax.lax.dynamic_slice_in_dim(
                    w.astype(dt), g * hg, hg, axis=axis)

            q = jnp.concatenate(_latent_queries(
                cfg, cq, mine(a["w_qb"], 1), positions), axis=-1)
            kv = jnp.einsum("bsr,rhk->bhsk", c, mine(a["w_kvb"], 1))
            k = jnp.concatenate(
                [kv[..., :cfg.nope_dim],
                 jnp.broadcast_to(k_rope, kv.shape[:3] + (cfg.rope_dim,))],
                axis=-1)
            attn = _latent_prefill_attention(
                jnp.moveaxis(q, 1, 2), k, kv[..., cfg.nope_dim:],
                (cfg.nope_dim + cfg.rope_dim) ** -0.5)
            return out + jnp.einsum(
                "bhsv,hvd->bsd", attn, mine(a["wo"], 0),
                preferred_element_type=jnp.float32), None

        out, _ = jax.lax.scan(group, jnp.zeros(h.shape, jnp.float32),
                              jnp.arange(heads // hg))
    return out.astype(dt), (row,)


def _decode_latent(p, cfg: DecoderConfig, h, kept, page_table, seq_lens,
                   live):
    """One position a slot in the absorbed form: the token's row is
    appended to the layer's pool, the query goes through ``w_kvb``'s key
    half to the latent's width and is scored against the rows as they
    lie, and the weighted rows come back through its value half. The
    two halves are views of ``w_kvb`` taken while tracing."""
    a = p["LatentAttention_0"]
    dt = cfg.dtype
    (pages,) = kept
    w_kvb = a["w_kvb"].astype(dt)
    # a length-1 "sequence" per slot, as the other kinds rotate a step
    h, positions = h[:, None], seq_lens[:, None]
    with jax.named_scope("mla_absorb"):
        cq = _rms(h @ a["w_qa"].astype(dt), a["q_norm"], cfg.norm_eps)
        q_nope, q_rope = _latent_queries(cfg, cq, a["w_qb"].astype(dt),
                                         positions)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0],
                           w_kvb[..., :cfg.nope_dim])
        pad = cfg.latent_width - cfg.kv_rank - cfg.rope_dim
        q = jnp.concatenate(
            [q_lat, q_rope[:, 0],
             jnp.zeros(q_lat.shape[:2] + (pad,), q_lat.dtype)], axis=-1)
        # [B,1,W]: the one position stands where the pool has its one
        # "KV head"
        row = _latent_row(a, cfg, h, positions)
    with jax.named_scope("latent_append"):
        (pages,) = append_token((pages,), (row,), page_table, seq_lens)
    with jax.named_scope("mla_absorb"):
        mixed = paged_latent_attention(
            q, pages, page_table, seq_lens + 1,
            score_width=cfg.nope_dim + cfg.rope_dim,
            value_width=cfg.kv_rank,
            interpret=jax.default_backend() != "tpu")
        o = jnp.einsum("bhr,rhv->bhv", mixed.astype(dt),
                       w_kvb[..., cfg.nope_dim:])
        out = jnp.einsum("bhv,hvd->bd", o, a["wo"].astype(dt))
    return out, (pages,)


def _init_latent(cfg: DecoderConfig, icfg):
    return (jnp.zeros((icfg.num_pages, 1, icfg.page_size,
                       cfg.latent_width), cfg.dtype),)


def _write_latent(cfg, entry, kept, slots, pages, plens):
    (pool,), (rows,) = entry, kept
    with jax.named_scope("latent_append"):
        for r in range(pages.shape[0]):
            pool = write_prefill_pages(pool, rows[r][:, None], pages[r])
    return (pool,)


# ----------------------------------------------------------------------
# the other kinds, in the table's form: softmax attention over keys and
# values a position, whole (under the engine's page table) or of a
# window (in a ring a slot); the delta rule
# ----------------------------------------------------------------------

def _lanes(x, width: int):
    """x [..., D] padded with zeros to ``width`` on its last axis."""
    pad = width - x.shape[-1]
    return x if not pad else jnp.pad(
        x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def window_ring(cfg: DecoderConfig, page_size: int) -> int:
    """Pages a sequence's ring has in a window layer's pool: as many as
    ``cfg.window`` consecutive positions can straddle."""
    return -(-(cfg.window - 1) // page_size) + 1


def _scope(cfg: DecoderConfig, mixer: str) -> str:
    """The named scope of a softmax mixer's products and read."""
    return "win" if mixer == "window" else (
        "gqa" if cfg.attn_gate else "attn")


def _prefill_attention_kind(p, cfg: DecoderConfig, h, positions, plens,
                            mixer: str = "attention"):
    with jax.named_scope(_scope(cfg, mixer)):
        out, k, v = _prefill_attention(p["Attention_0"], cfg, h, positions,
                                       mixer)
    return out, (k, v)


def _decode_attention(p, cfg: DecoderConfig, h, kept, page_table, seq_lens,
                      live, mixer: str = "attention"):
    """``kept`` is the layer's (k_pages, v_pages), to which this token's
    K/V are appended (seq_lens = cache length BEFORE the token = the
    token's position). A ``window`` layer appends into its slot's own
    ring of pages, wrapping, and reads the last ``cfg.window`` positions
    (the token among them) from it: the engine's page table is not
    its."""
    a = p["Attention_0"]
    k_pages, v_pages = kept
    scope = _scope(cfg, mixer)
    ring, window = 0, {}
    if mixer == "window":
        ring = window_ring(cfg, k_pages.shape[2])
        slots = h.shape[0]
        page_table = jnp.arange(slots * ring, dtype=jnp.int32).reshape(
            slots, ring)
        if live is not None:
            # an idle slot's dummy token goes to the pool's last page,
            # as a full layer's goes to the engine's parking page: the
            # append moves ONE page for all of them, not a page each
            # (52 us a call against the full layer's 18 with 20 of 32
            # slots idle: PERF.md section 6, PR 33)
            page_table = jnp.where(live[:, None], page_table, slots * ring)
        window = {"first": seq_lens + 1 - cfg.window, "ring": ring}
    with jax.named_scope(scope):
        q, k, v = _attention_qkv(a, cfg, mixer, h, seq_lens)
    width = k_pages.shape[-1]
    if width != cfg.head_dim:
        # the pools hold the heads padded to whole lanes: zeros in q and
        # k add nothing to a score, zeros in v come out and are cut
        q, k, v = (_lanes(t, width) for t in (q, k, v))
        window["score_width"] = cfg.head_dim
    with jax.named_scope("win_append" if ring else "kv_append"):
        k_pages, v_pages = append_token_kv(k_pages, v_pages, k, v,
                                           page_table, seq_lens, ring)
    with jax.named_scope(scope):
        out = paged_attention_auto(q, k_pages, v_pages, page_table,
                                   seq_lens + 1, **window)
        out = _attention_out(a, cfg, h, out[..., :cfg.head_dim])
    return out, (k_pages, v_pages)


def _init_attention(cfg: DecoderConfig, icfg):
    pool = (icfg.num_pages, cfg.n_kv_heads, icfg.page_size, cfg.kv_width)
    return jnp.zeros(pool, cfg.dtype), jnp.zeros(pool, cfg.dtype)


def _write_attention(cfg, entry, kept, slots, pages, plens):
    k_pages, v_pages = entry
    k, v = (_lanes(t, k_pages.shape[-1]) for t in kept)
    with jax.named_scope("kv_append"):
        for r in range(pages.shape[0]):
            k_pages, v_pages = write_prefill_kv(
                k_pages, v_pages, k[r], v[r], pages[r])
    return k_pages, v_pages


def _init_window(cfg: DecoderConfig, icfg):
    """A ring a slot (slot ``s`` owns pages ``s * ring ... s * ring +
    ring - 1``) and, last, a page for what a launch writes and nothing
    reads."""
    pool = (icfg.batch_size * window_ring(cfg, icfg.page_size) + 1,
            cfg.n_kv_heads, icfg.page_size, cfg.kv_width)
    return jnp.zeros(pool, cfg.dtype), jnp.zeros(pool, cfg.dtype)


def window_prefill_pages(cfg: DecoderConfig, pool_pages: int, page: int,
                         n_pages: int, slots, plens):
    """Where a launch's rows write each of their bucket's ``n_pages``
    logical pages in a window layer's pool of ``pool_pages``: [N,
    n_pages] physical ids. A page that holds a position decoding can
    still see (``>= plen - window``, up to the prompt's last) goes to
    its place in the slot's ring; the pages before the window, the
    padding pages past the prompt (a bucket has more pages than the
    ring: written by ``page % ring`` they would land on live tokens)
    and a dummy row's all go to the pool's last page."""
    ring = window_ring(cfg, page)
    logical = jnp.arange(n_pages)[None, :]
    keep = ((logical >= (jnp.maximum(plens - cfg.window, 0) // page)[:, None])
            & (logical <= ((plens - 1) // page)[:, None])
            & (slots < (pool_pages - 1) // ring)[:, None])
    return jnp.where(keep, slots[:, None] * ring + logical % ring,
                     pool_pages - 1)


def _write_window(cfg, entry, kept, slots, pages, plens):
    k_pages, v_pages = entry
    n_pages, page = pages.shape[1], k_pages.shape[2]
    where = window_prefill_pages(cfg, k_pages.shape[0], page, n_pages,
                                 slots, plens)
    k, v = (_lanes(t, k_pages.shape[-1]) for t in kept)
    with jax.named_scope("win_append"):
        for r in range(where.shape[0]):
            k_pages, v_pages = write_prefill_kv(
                k_pages, v_pages, k[r], v[r], where[r])
    return k_pages, v_pages


def _prefill_delta_rule_kind(p, cfg: DecoderConfig, h, positions, plens):
    out, state, tail = _prefill_delta_rule(p["DeltaRule_0"], cfg, h, plens)
    return out, (state, tail)


def _init_delta_rule(cfg: DecoderConfig, icfg):
    return (jnp.zeros((icfg.batch_size, cfg.dr_heads, cfg.dr_key_dim,
                       cfg.dr_value_dim), jnp.float32),
            jnp.zeros((icfg.batch_size, cfg.dr_conv - 1,
                       cfg.dr_heads * cfg.dr_channels), cfg.dtype))


# ----------------------------------------------------------------------
# the gated short convolution: (C * conv(B * x)) w_out, a tail a slot
# ----------------------------------------------------------------------

def _conv_gates(a, cfg: DecoderConfig, h):
    """h [..., d] (normed) -> (z = B * x, C), float32 [..., d]: the
    input projection's three blocks B, C, x, the first and last
    multiplied."""
    bcx = jnp.einsum("...d,dkc->...kc", h, a["w_in"].astype(cfg.dtype))
    bcx = bcx.astype(jnp.float32)
    return bcx[..., 0, :] * bcx[..., 2, :], bcx[..., 1, :]


def _conv_out(a, cfg: DecoderConfig, c, y):
    """C * y (float32) through the output projection."""
    return (c * y).astype(cfg.dtype) @ a["w_out"].astype(cfg.dtype)


def _prefill_conv(p, cfg: DecoderConfig, h, positions, plens):
    """The mixer over a bucket: h [N,S,Dm] (normed) -> (out [N,S,Dm],
    (tail [N,K-1,Dm],)). Each row starts from zeros; its tail is the
    last K-1 rows of ``B * x`` before position ``plens``, zeros before
    position 0 for a prompt shorter than that."""
    a = p["ShortConv_0"]
    with jax.named_scope("conv"):
        z, c = _conv_gates(a, cfg, h)
        y = short_conv(z, a["conv"].astype(jnp.float32))
        return _conv_out(a, cfg, c, y), (conv_tail(z, plens, cfg.conv_taps),)


def _decode_conv(p, cfg: DecoderConfig, h, kept, page_table, seq_lens,
                 live):
    """One position a slot: h [B,Dm] (normed) against the slot's tail.
    Slots that are not ``live`` keep their tails as they are."""
    a = p["ShortConv_0"]
    (tail,) = kept
    with jax.named_scope("conv"):
        z, c = _conv_gates(a, cfg, h)
        y, new_tail = short_conv_step(
            z, a["conv"].astype(jnp.float32), tail,
            precision=jax.lax.Precision.HIGHEST)
        out = _conv_out(a, cfg, c, y)
    with jax.named_scope("conv_state"):
        if live is not None:
            new_tail = jnp.where(live[:, None, None], new_tail, tail)
    return out, (new_tail,)


def _init_conv(cfg: DecoderConfig, icfg):
    return (jnp.zeros((icfg.batch_size, cfg.conv_taps - 1, cfg.d_model),
                      jnp.float32),)


def _write_slots(cfg, entry, kept, slots, pages, plens, *, scope: str):
    """Each row's state to slot ``slots[r]`` whole (a dummy row's slot
    is out of bounds and its scatter is dropped)."""
    with jax.named_scope(scope):
        return tuple(held.at[slots].set(new)
                     for held, new in zip(entry, kept))


class _Mixer(NamedTuple):
    """What a kind of mixer brings. ``init(cfg, icfg)`` -> the layer's
    empty cache entry; ``write(cfg, entry, kept, slots, pages, plens)``
    -> the entry with what a launch's rows kept: row r's keys and values
    [S,KV,D] or latent rows [S,W] go to the pages ``pages[r]`` (of a
    window layer: those of its last ``window`` positions before
    ``plens[r]``, to the ring of slot ``slots[r]``), its final state
    and tail to slot ``slots[r]`` whole (nothing of the slot's previous
    tenant survives; a dummy row's slot is out of bounds and its
    scatter is dropped); ``prefill(p, cfg, h, positions, plens)`` -> (out
    [N,S,Dm] before the residual, kept); ``decode(p, cfg, h, entry,
    page_table, seq_lens, live)`` -> (out [B,Dm], entry).
    ``keeps_beside_kv`` names what the layer keeps where that is NOT
    keys and values a position under the caller's page table, which a
    caller may hold itself (``decode_step``) and the disaggregated
    handoff carries (``import_kv``)."""
    init: Callable
    write: Callable
    prefill: Callable
    decode: Callable
    keeps_beside_kv: str = ""


MIXERS_BY_KIND = {
    "attention": _Mixer(_init_attention, _write_attention,
                        _prefill_attention_kind, _decode_attention),
    "window": _Mixer(
        _init_window, _write_window,
        functools.partial(_prefill_attention_kind, mixer="window"),
        functools.partial(_decode_attention, mixer="window"),
        "a ring of the last positions' keys and values a slot"),
    "delta_rule": _Mixer(_init_delta_rule,
                         functools.partial(_write_slots, scope="kda_state"),
                         _prefill_delta_rule_kind, _decode_delta_rule,
                         "recurrent state"),
    "latent": _Mixer(_init_latent, _write_latent, _prefill_latent,
                     _decode_latent, "latent rows"),
    "conv": _Mixer(_init_conv,
                   functools.partial(_write_slots, scope="conv_state"),
                   _prefill_conv, _decode_conv, "a convolution tail"),
}


def kept_beside_kv(cfg) -> str:
    """What the layers of ``cfg`` keep that is not keys and values
    under the caller's page table ("" when nothing): the disaggregated
    handoff and the callers that hold K and V themselves carry none of
    it."""
    return ", ".join(sorted(
        {MIXERS_BY_KIND[l.mixer].keeps_beside_kv
         for l in describe(cfg).layers} - {""}))


def _mix(p, cfg: DecoderConfig, x, run):
    """x + mixer(norm(x)), the branch normed once more before the add
    of a model with sandwich norms; ``run(h)`` -> (out, kept)."""
    out, kept = run(_rms(x, p["RMSNorm_0"]["scale"], cfg.norm_eps))
    if cfg.sandwich_norm:
        out = _rms(out, p["PostNorm_0"]["scale"], cfg.norm_eps)
    return x + out, kept


def _prefill_layer(p, cfg: DecoderConfig, spec: LayerSpec, x, positions,
                   plens, valid):
    """One layer over a bucket [N,S,Dm]. Returns (x_out, what the layer
    keeps for decoding, in its kind's form; picks a held expert or
    None)."""
    x, kept = _mix(p, cfg, x, lambda h: MIXERS_BY_KIND[spec.mixer].prefill(
        p, cfg, h, positions, plens))
    x, counts = _feed_forward(p, cfg, spec, x, valid)
    return x, kept, counts


def _decode_layer(p, cfg: DecoderConfig, spec: LayerSpec, x, kept,
                  page_table, seq_lens, live):
    """Single-token decode for one layer over [B,Dm]; ``kept`` is the
    layer's cache entry. Returns (x_out, kept, picks a held expert or
    None)."""
    x, kept = _mix(p, cfg, x, lambda h: MIXERS_BY_KIND[spec.mixer].decode(
        p, cfg, h, kept, page_table, seq_lens, live))
    valid = live if live is not None else jnp.ones(x.shape[:1], bool)
    x, counts = _feed_forward(p, cfg, spec, x, valid)
    return x, kept, counts


def _head(params, cfg: DecoderConfig, x, spec: str):
    """Final norm and output head over x [..., d]; ``spec`` is the
    einsum of hidden and head matrix [V, d]."""
    with jax.named_scope("head"):
        table = params["embedding" if cfg.tie_embeddings else "lm_head"]
        x = _rms(x, params["final_norm"]["scale"], cfg.norm_eps)
        logits = jnp.einsum(spec, x, table.astype(cfg.dtype))
        return logits.astype(jnp.float32)


def _embed(params, cfg: DecoderConfig, tokens):
    with jax.named_scope("embed"):
        x = params["embedding"].astype(cfg.dtype)[tokens]
        return x if cfg.embed_scale == 1.0 else x * cfg.embed_scale


def _sum_counts(counts):
    counts = [c for c in counts if c is not None]
    return sum(counts[1:], counts[0]) if counts else None


def _step_counts(counts):
    """What a decode step counts of the expert layers' picks a held
    expert (a list by layer, None for a layer without experts): the
    picks summed over the layers, then how many held experts got at
    least one pick, summed over the layers; [E_held + 1] int32, or
    None."""
    counts = [c for c in counts if c is not None]
    if not counts:
        return None
    touched = sum(jnp.sum(c > 0, dtype=jnp.int32) for c in counts)
    return jnp.concatenate([_sum_counts(counts), touched[None]])


def _prefill_hidden(params, cfg: DecoderConfig, tokens, plens=None,
                    rows=None):
    """tokens [N,S] (padded to a bucket) -> (hidden [N,S,Dm] before the
    final norm; what each layer keeps, a list by layer; picks a held
    expert summed over the layers, or None). ``plens`` [N] are the
    rows' valid lengths (the whole bucket when None) and ``rows`` [N]
    marks the rows that are requests: what lies past a length or in a
    dummy row neither touches a state nor counts as a pick."""
    n, s = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.arange(s)[None, :]
    if plens is None:
        plens = jnp.full((n,), s, jnp.int32)
    valid = positions < plens[:, None]
    if rows is not None:
        valid = valid & rows[:, None]
    kept, counts = [], []
    for i, spec in enumerate(cfg.layers):
        x, keep, c = _prefill_layer(params[f"layer_{i}"], cfg, spec, x,
                                    positions, plens, valid)
        kept.append(keep)
        counts.append(c)
    return x, kept, _sum_counts(counts)


# ----------------------------------------------------------------------
# the cache: what the layers keep between steps, one entry a layer
# ----------------------------------------------------------------------

def init_cache(cfg: DecoderConfig, icfg) -> tuple:
    """The empty cache of ``cfg`` for an engine of ``icfg`` (its
    ``num_pages``, ``page_size`` and ``batch_size``): a tuple with one
    entry a layer. An attention layer keeps ``(k_pages, v_pages)``, each
    [num_pages, KV, page_size, ``cfg.kv_width``] in the model's dtype; a
    conv layer ``(tail,)`` [B, K-1, d] in float32; a delta-rule
    layer keeps, for each slot, ``(state, tail)``: its state [B, H, dk,
    dv] in float32 and the last K-1 inputs of its short convolution
    [B, K-1, channels]; a latent layer keeps ``(latent_pages,)``
    [num_pages, 1, page_size, ``cfg.latent_width``]."""
    return tuple(MIXERS_BY_KIND[spec.mixer].init(cfg, icfg)
                 for spec in cfg.layers)


def prefill_cached(params, cfg: DecoderConfig, cache, tokens, plens, slots,
                   pages, requests):
    """One prefill launch into the cache: tokens [N,S] (padded to a
    bucket), plens [N] the rows' lengths, slots [N] and pages [N,
    ceil(S/page_size)] where each row's state and its keys and values
    go, requests [N] bool the rows that are requests and not padding.
    Only a row's last position goes through the head. Returns (logits
    [N,V] f32 at each row's last position, cache, picks a held expert
    summed over the layers or None)."""
    x, kept, counts = _prefill_hidden(params, cfg, tokens, plens, requests)
    cache = tuple(
        MIXERS_BY_KIND[spec.mixer].write(cfg, entry, keep, slots, pages,
                                         plens)
        for spec, entry, keep in zip(cfg.layers, cache, kept))
    last = x[jnp.arange(tokens.shape[0]), plens - 1]
    return _head(params, cfg, last, "bd,vd->bv"), cache, counts


def import_kv(cfg: DecoderConfig, cache, k_seq, v_seq, pages):
    """Write one sequence's keys and values ([L,S,KV,D] over the layers
    of ``cfg.kv_layers``, as ``prefill`` hands them on) into ``pages`` of
    those layers' pools."""
    cache = list(cache)
    with jax.named_scope("kv_append"):
        for j, i in enumerate(cfg.kv_layers):
            width = cache[i][0].shape[-1]
            cache[i] = write_prefill_kv(*cache[i], _lanes(k_seq[j], width),
                                        _lanes(v_seq[j], width), pages)
    return tuple(cache)


def decode_step_cached(params, cfg: DecoderConfig, tokens, cache, page_table,
                       seq_lens, live):
    """One continuous-batching step: tokens [B] int32 (last emitted or
    last prompt token per slot), ``seq_lens`` [B] the cache length
    before the token, ``live`` [B] bool the slots that hold a request
    (None: every slot). Returns (next_logits [B,V] f32, cache, picks a
    held expert and experts touched, ``_step_counts``, or None)."""
    x = _embed(params, cfg, tokens)                            # [B, Dm]
    new_cache, counts = [], []
    for i, (spec, kept) in enumerate(zip(cfg.layers, cache)):
        x, kept, c = _decode_layer(params[f"layer_{i}"], cfg, spec, x, kept,
                                   page_table, seq_lens, live)
        new_cache.append(kept)
        counts.append(c)
    logits = _head(params, cfg, x, "bd,vd->bv")
    return logits, tuple(new_cache), _step_counts(counts)


def decode_chunk_cached(params, cfg: DecoderConfig, tokens, cache, page_table,
                        seq_lens, live, *, n_steps: int):
    """n_steps greedy decode steps in ONE jitted program (lax.scan with
    argmax feedback). Returns (tokens [n_steps, B] int32, next_tokens
    [B], next_lens [B], cache, ``_step_counts`` summed over the chunk's
    steps or None): the feedback state comes back as DEVICE arrays so
    the engine can chain chunks without a host round trip: chunks
    pipeline asynchronously and the host syncs only when a burst
    ends."""
    def body(carry, _):
        toks, kept, lens, total = carry
        logits, kept, counts = decode_step_cached(
            params, cfg, toks, kept, page_table, lens, live)
        with jax.named_scope("head"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if counts is not None:
            total = total + counts
        return (nxt, kept, lens + 1, total), nxt

    total = (jnp.zeros((cfg.n_experts_held + 1,), jnp.int32)
             if cfg.moe_layers else None)
    (toks, cache, lens, total), outs = jax.lax.scan(
        body, (tokens, cache, seq_lens, total), None, length=n_steps)
    return outs, toks, lens, cache, total


# ----------------------------------------------------------------------
# the same forward for callers that hold the keys and values themselves
# (chip_smoke.py, the benchmark's compile checks): models whose every
# mixer is attention, since one with recurrent state, a latent cache or
# a window's ring has other things to hand on than keys and values
# under one page table, and goes through the engine
# ----------------------------------------------------------------------

def _attention_only(cfg, who: str) -> DecoderConfig:
    cfg = describe(cfg)
    beside = kept_beside_kv(cfg)
    if beside:
        raise ValueError(f"{who} carries keys and values under one page "
                         f"table only; this model keeps {beside} too")
    return cfg


def prefill_batch(params: Dict[str, Any], cfg, tokens: jnp.ndarray):
    """tokens [N,S] (padded to a bucket) -> (logits [N,S,V] f32,
    k_seq/v_seq [L,N,S,KV,D]) — N prompts prefill in one program."""
    cfg = _attention_only(cfg, "prefill_batch")
    x, kept, _ = _prefill_hidden(params, cfg, tokens)
    k_seq, v_seq = zip(*kept)
    return (_head(params, cfg, x, "bsd,vd->bsv"), jnp.stack(k_seq),
            jnp.stack(v_seq))


def prefill(params: Dict[str, Any], cfg, tokens: jnp.ndarray):
    """tokens [1,S] (padded to a bucket) -> (logits [S,V] f32,
    k_seq/v_seq [L,S,KV,D])."""
    logits, ks, vs = prefill_batch(params, cfg, tokens)
    return logits[0], ks[:, 0], vs[:, 0]


def decode_step(params: Dict[str, Any], cfg, tokens: jnp.ndarray,
                k_pages, v_pages, page_table: jnp.ndarray,
                seq_lens: jnp.ndarray):
    """``decode_step_cached`` over per-layer TUPLES of [P,KV,page,D]
    pools. Returns (next_logits [B,V] f32, k_pages, v_pages)."""
    cfg = _attention_only(cfg, "decode_step")
    logits, cache, _ = decode_step_cached(
        params, cfg, tokens, tuple(zip(k_pages, v_pages)), page_table,
        seq_lens, None)
    return (logits, *zip(*cache))


def decode_chunk(params: Dict[str, Any], cfg, tokens: jnp.ndarray,
                 k_pages, v_pages, page_table: jnp.ndarray,
                 seq_lens: jnp.ndarray, *, n_steps: int):
    """``decode_chunk_cached`` over per-layer tuples of pools. Returns
    (tokens [n_steps, B] int32, next_tokens [B], next_lens [B], k_pages,
    v_pages)."""
    cfg = _attention_only(cfg, "decode_chunk")
    outs, toks, lens, cache, _ = decode_chunk_cached(
        params, cfg, tokens, tuple(zip(k_pages, v_pages)), page_table,
        seq_lens, None, n_steps=n_steps)
    return (outs, toks, lens, *zip(*cache))
