"""Plain reference for the openPangu-Ultra-MoE block: a decoder with
sandwich norms (four a layer: each branch is normed again before its
residual add), multi-head latent attention in every layer, a dense
SwiGLU feed-forward in the leading layers and experts with a shared
one and a scaled sigmoid router in the others, untied head.

Straightforward ``jax.numpy`` in float32 with every product at
``precision=HIGHEST``; nothing of ray_tpu, no kernel, no cache, no
absorption: the attention is the published, EXPANDED form (every head
its own keys ``[W_kvb's key columns over the normed latent | the one
rotated key all heads share]`` and values), scores taken a block of
query rows at a time so that a 10,240-token row fits; the experts are a
loop over the held ids with each expert's weights remade from the seed
as it is reached. Weights come from ``benchmark.weights_openpangu_ultra``
and the seed, one layer at a time.

The layer equations are ISSUE 31's reading of the published config
(``benchmark/configs/openpangu-ultra-moe-718b-serve-L5-ep16.json``
repeats them and lists what is ``assumed``). Departures from the
published model, all stated in that file: this chip's share only
(experts ``experts_held`` of the router's ``router_width``,
``vocab_size`` rows of embedding and head; a token's picks on absent
experts add nothing), one leading dense layer and four expert layers,
no multi-token-prediction module.

A convention shared with the program because it is part of the
function, not of its implementation: the rotation pairs even and odd
lanes (``dense_decoder._rope``, ``ops/rope.py``).

``mode`` lowers the precision of every matrix product with a weight
(and of attention's two) for the control of the output check: ``"f32"``
is the reference, ``"bf16"`` and ``"fp8"`` round both operands
(``dense_decoder._round_to``).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_openpangu_ultra as weights
from benchmark.reference.dense_decoder import (_mm, _rms, _rope,  # noqa: F401
                                               served_token_gaps)
from benchmark.reference.solar_open2 import _swiglu

Q_BLOCK = 256


def latent_attention(a, u, s: Dict[str, Any], theta: float, eps: float,
                     mode: str):
    """Multi-head latent attention, expanded: u [S,d] (normed) ->
    [S,d]."""
    n = u.shape[0]
    pos = jnp.arange(n)
    c_q = _rms(_mm("sd,dr->sr", u, a["w_qa"], mode), a["q_norm"], eps)
    q = _mm("sr,rhk->shk", c_q, a["w_qb"], mode)
    kva = _mm("sd,dr->sr", u, a["w_kva"], mode)
    c = _rms(kva[:, :s["kv_rank"]], a["kv_norm"], eps)
    k_rope = _rope(kva[:, None, s["kv_rank"]:], pos, theta)     # [S,1,p]
    kv = _mm("sr,rhk->shk", c, a["w_kvb"], mode)
    k = jnp.concatenate(
        [kv[..., :s["nope"]],
         jnp.broadcast_to(k_rope, (n, s["h"], s["rope"]))], axis=-1)
    v = kv[..., s["nope"]:]
    q = jnp.concatenate([q[..., :s["nope"]],
                         _rope(q[..., s["nope"]:], pos, theta)], axis=-1)
    pad = (-n) % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
        scores = _mm("qhd,khd->hqk", qb, k, mode) / np.sqrt(
            s["nope"] + s["rope"])
        seen = (jnp.arange(n)[None, :]
                <= i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None])
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
        return _mm("hqk,khd->qhd", probs, v, mode)

    out = jax.lax.map(rows, jnp.arange((n + pad) // Q_BLOCK))
    out = out.reshape((n + pad,) + out.shape[2:])[:n]
    return _mm("shv,hvd->sd", out, a["wo"], mode)


def expert_layer(outside, expert_of, h, s: Dict[str, Any], mode: str):
    """Sigmoid router over every expert, the ``top_k`` largest picked,
    weights normalised over the picked and scaled by the routed scaling
    factor; the held experts one after another (``expert_of(id)`` gives
    one's weights), the shared expert once and unscaled. h [S,d] ->
    [S,d]."""
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, outside["router"], mode))
    picked, ids = jax.lax.top_k(scores, s["top_k"])
    share = s["scale"] * picked / picked.sum(-1, keepdims=True)
    lo, hi = s["held"]

    def add(e, y):
        w = jnp.sum(jnp.where(ids == e, share, 0.0), axis=-1)   # [S]
        return y + w[:, None] * _swiglu(expert_of(e), h, mode)

    y = jax.lax.fori_loop(lo, hi, add, jnp.zeros_like(h))
    if "shared" in outside:
        y = y + _swiglu(outside["shared"], h, mode)
    return y


def block(mixer, ffn, expert_of, x, config: Dict[str, Any],
          mode: str = "f32"):
    """One decoder block over one row x [S,d]: ``ffn`` is
    ``{"MLP_0": ...}`` of a dense layer, else the router and the shared
    expert. Norm scales are ones, as the weights module makes them."""
    s = weights.dims(config)
    eps = float(config["rms_norm_eps"])
    ones = jnp.ones((s["d"],), jnp.float32)
    a = latent_attention(mixer["LatentAttention_0"], _rms(x, ones, eps), s,
                         float(config["rope_theta"]), eps, mode)
    x = x + _rms(a, ones, eps)
    h = _rms(x, ones, eps)
    if "MLP_0" in ffn:
        y = _swiglu(ffn["MLP_0"], h, mode)
    else:
        y = expert_layer(ffn, expert_of, h, s, mode)
    return x + _rms(y, ones, eps)


def teacher_forced_logits(config: Dict[str, Any], seed: int,
                          rows: np.ndarray, mode: str = "f32",
                          weight_dtype=jnp.bfloat16) -> jnp.ndarray:
    """rows [N,S] int (padded on the right; causal mixers keep padding
    from reaching earlier positions) -> logits [N,S,V] float32 on the
    device. Weights are drawn from the seed, rounded once to
    ``weight_dtype`` (the type they are served in) and used in float32;
    one layer's are alive at a time, and of its experts one."""
    key = weights.seed_key(seed)
    s = weights.dims(config)

    def as_served(tree):
        return jax.tree_util.tree_map(
            lambda w: w.astype(weight_dtype).astype(jnp.float32), tree)

    def layer(index: int):
        @jax.jit
        def run(x, k):
            return block(
                as_served(weights.init_mixer(config, k, index)),
                as_served(weights.init_ffn_outside_experts(config, k,
                                                           index)),
                lambda e: as_served(weights.init_expert(config, k, index,
                                                        e)),
                x, config, mode)
        return run

    embed = jax.jit(lambda k: as_served(weights.init_embedding(config, k)))(
        key)
    xs = [embed[jnp.asarray(r, jnp.int32)] for r in rows]
    del embed
    for i in range(s["layers"]):
        run = layer(i)
        xs = [run(x, key) for x in xs]      # a row at a time
    head = jax.jit(lambda k: as_served(weights.init_head(config, k)))(key)
    ones = jnp.ones((s["d"],), jnp.float32)
    # one product for all rows: stacking rows of logits afterwards
    # would hold them twice
    return jax.jit(lambda x, w: _mm(
        "nsd,vd->nsv", _rms(x, ones, float(config["rms_norm_eps"])), w,
        mode))(jnp.stack(xs), head)
