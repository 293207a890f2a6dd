"""Plain reference for the Solar-Open2 block pattern: pre-norm decoder
whose mixers alternate 1:3 between gated softmax attention without
positions and a gated delta rule with a per-channel decay (Kimi Delta
Attention, arXiv:2510.26692), experts with a shared one in every
feed-forward, untied head.

Straightforward ``jax.numpy`` in float32 with every product at
``precision=HIGHEST``; nothing of ray_tpu, no kernel, no cache, no
chunked scan: the recurrence goes token by token (``lax.scan``), the
experts are a loop over the held ids with each expert's weights remade
from the seed as it is reached, attention's scores are taken a block of
query rows at a time so that a 16,896-token row fits. Weights come from
``benchmark.weights_solar_open2`` and the seed, one layer at a time.

The layer equations are ISSUE 27's reading of the published config
(``benchmark/configs/solar-open2-250b-serve-L4-ep8.json`` repeats them
and lists what is ``assumed``). Departures from the published model,
all stated in that file: this chip's share only (experts
``experts_held`` of the router's ``router_width``, ``vocab_size`` rows
of embedding and head; a token's picks on absent experts add nothing),
the layers of one period.

Conventions shared with the program because they are part of the
function, not of its implementation: ``l2norm(x) = x / sqrt(sum x^2 +
1e-6)``; the short convolution's last tap multiplies the current
position; the decay's ``A_log`` is one scalar a head.

``mode`` lowers the precision of every matrix product with a weight
(and of attention's two) for the control of the output check: ``"f32"``
is the reference, ``"bf16"`` and ``"fp8"`` round both operands
(``dense_decoder._round_to``). The recurrence's state stays float32 in
every mode, as the configuration states.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_solar_open2 as weights
from benchmark.reference.dense_decoder import (HIGHEST, _mm, _rms,  # noqa: F401
                                               served_token_gaps)

Q_BLOCK = 256


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _swiglu(p, h, mode):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", h, p["w_gate"],
                                            mode))
               * _mm("sd,df->sf", h, p["w_up"], mode), p["w_down"], mode)


def gated_attention(a, h, s: Dict[str, Any], mode: str):
    """Causal softmax attention without positions, gated per element:
    h [S,d] -> [S,d]."""
    q = _mm("sd,dhk->shk", h, a["wq"], mode)
    k = _mm("sd,dhk->shk", h, a["wk"], mode)
    v = _mm("sd,dhk->shk", h, a["wv"], mode)
    rep = s["h"] // s["kv"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    n = h.shape[0]
    pad = (-n) % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
        scores = _mm("qhd,khd->hqk", qb, k, mode) / np.sqrt(s["hd"])
        seen = (jnp.arange(n)[None, :]
                <= i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None])
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
        return _mm("hqk,khd->qhd", probs, v, mode)

    out = jax.lax.map(rows, jnp.arange((n + pad) // Q_BLOCK))
    out = out.reshape((n + pad,) + out.shape[2:])[:n]
    gate = jax.nn.sigmoid(_mm("sd,dhk->shk", h, a["w_gate"], mode))
    return _mm("shk,hkd->sd", out * gate, a["wo"], mode)


def delta_rule(a, h, s: Dict[str, Any], mode: str, eps: float):
    """The gated delta rule with a per-channel decay, one position
    after another: h [S,d] -> [S,d]."""
    n = h.shape[0]
    k_taps = s["conv"]

    def conv(x, w):          # x [S,H,D], w [K,H,D]; causal, depthwise
        xp = jnp.pad(x, ((k_taps - 1, 0), (0, 0), (0, 0)))
        return sum(xp[i:i + n] * w[i] for i in range(k_taps))

    def mixed(wname, cname):
        return jax.nn.silu(conv(_mm("sd,dhk->shk", h, a[wname], mode),
                                a[cname]))

    q = _l2norm(mixed("wq", "conv_q")) / np.sqrt(s["dr_d"])
    k = _l2norm(mixed("wk", "conv_k"))
    v = mixed("wv", "conv_v")
    step = _mm("sr,rhk->shk", _mm("sd,dr->sr", h, a["w_f_down"], mode),
               a["w_f_up"], mode)
    alpha = jnp.exp(-jnp.exp(a["A_log"])[:, None]
                    * jax.nn.softplus(step + a["dt_bias"]))
    beta = 2.0 * jax.nn.sigmoid(_mm("sd,dh->sh", h, a["w_beta"], mode))

    def one(state, x):       # state [H,dk,dv]
        q_t, k_t, v_t, a_t, b_t = x
        state = a_t[:, :, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, state,
                                             precision=HIGHEST))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state,
                                 precision=HIGHEST)

    zero = jnp.zeros((s["dr_h"], s["dr_d"], s["dr_d"]), jnp.float32)
    _, o = jax.lax.scan(one, zero, (q, k, v, alpha, beta))
    o = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
         * a["o_norm"])
    gate = jax.nn.sigmoid(_mm(
        "sr,rhv->shv", _mm("sd,dr->sr", h, a["w_g_down"], mode),
        a["w_g_up"], mode))
    return _mm("shv,hvd->sd", o * gate, a["wo"], mode)


def expert_layer(outside, expert_of, h, s: Dict[str, Any], mode: str):
    """Sigmoid router over every expert, the ``top_k`` largest picked,
    weights normalised over the picked; the held experts one after
    another (``expert_of(id)`` gives one's weights), the shared expert
    once. h [S,d] -> [S,d]."""
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, outside["router"], mode))
    picked, ids = jax.lax.top_k(scores, s["top_k"])
    share = picked / picked.sum(-1, keepdims=True)
    lo, hi = s["held"]

    def add(e, y):
        w = jnp.sum(jnp.where(ids == e, share, 0.0), axis=-1)   # [S]
        return y + w[:, None] * _swiglu(expert_of(e), h, mode)

    y = jax.lax.fori_loop(lo, hi, add, jnp.zeros_like(h))
    if "shared" in outside:
        y = y + _swiglu(outside["shared"], h, mode)
    return y


def block(mixer, outside, expert_of, x, config: Dict[str, Any],
          mode: str = "f32"):
    """One decoder block over one row x [S,d]."""
    s = weights.dims(config)
    eps = float(config["rms_norm_eps"])
    h = _rms(x, jnp.ones((s["d"],), jnp.float32), eps)
    if "Attention_0" in mixer:
        x = x + gated_attention(mixer["Attention_0"], h, s, mode)
    else:
        x = x + delta_rule(mixer["DeltaRule_0"], h, s, mode, eps)
    h = _rms(x, jnp.ones((s["d"],), jnp.float32), eps)
    return x + expert_layer(outside, expert_of, h, s, mode)


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
                as_served(weights.init_moe_outside_experts(config, k,
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
    # would hold them twice (5 GB at three rows of 16,896 positions)
    return jax.jit(lambda x, w: _mm(
        "nsd,vd->nsv", _rms(x, ones, float(config["rms_norm_eps"])), w,
        mode))(jnp.stack(xs), head)
