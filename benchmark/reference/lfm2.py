"""Plain reference for the LFM2 block with experts (``lfm2_moe``): a
decoder with two norms a layer whose mixer is either a gated short
convolution (``conv``) or grouped-query softmax attention with an
RMSNorm over each head's queries and keys before the rotation
(``full_attention``), a dense SwiGLU feed-forward in the leading layers
and routed experts without a shared one in the others, picked by
sigmoid scores plus a bias that SELECTS and never weighs, a tied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, every product with a weight
at ``precision=HIGHEST`` besides; nothing of ray_tpu, no kernel, no
cache, no batching: every layer over each whole row, the rows side by
side only so that a layer's weights are drawn once for all of them.
The convolution is the explicit sum over its taps, ``y_t = sum_k w_k
z_{t-K+1+k}`` with ``z_j = 0`` for ``j < 0``. Attention's scores are
taken a block of query rows at a time, so that a row of a few thousand
positions fits; the experts are a loop over the held ids with each
expert's weights remade from the seed as it is reached. Weights come
from ``benchmark.weights_lfm2`` and the seed, one layer at a time.

The layer equations are those of
``benchmark/configs/lfm2-24b-a2b-serve-L9.json`` (``equations``, which
lists what is ``assumed`` beside them). Departures from the published
model, all stated there: one leading dense layer and two periods of
expert layers of the 40 layers.

A convention shared with the program because it is part of the
function, not of its implementation: the rotation pairs even and odd
lanes (``dense_decoder._rope``, ``ops/rope.py``). The published code
rotates the first half of a head against the second
(``rotate_half``); the two are the same function up to one fixed
permutation of the columns of ``Wq`` and ``Wk`` (and of the two head
norms' scales), which random weights do not see.

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

from benchmark import weights_lfm2 as weights
from benchmark.reference.dense_decoder import (_mm, _rms, _rope,  # noqa: F401
                                               served_token_gaps)
from benchmark.reference.solar_open2 import _swiglu

Q_BLOCK = 256


def short_conv(c, u, s: Dict[str, Any], mode: str):
    """The gated short convolution: u [S,d] (normed) -> [S,d].
    ``[B | C | x] = u W_in``, ``z = B * x``, ``y_t = sum_k w_k
    z_{t-K+1+k}`` (zeros before position 0), out ``(C * y) W_out``."""
    n, taps = u.shape[0], s["taps"]
    bcx = _mm("sd,dkc->skc", u, c["w_in"], mode)
    z = bcx[:, 0] * bcx[:, 2]
    zp = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    y = sum(c["conv"][k] * zp[k:k + n] for k in range(taps))
    return _mm("sd,de->se", bcx[:, 1] * y, c["w_out"], mode)


def attention(a, u, s: Dict[str, Any], mode: str):
    """Grouped-query causal attention with normed, rotated queries and
    keys: u [S,d] (normed) -> [S,d]."""
    n = u.shape[0]
    pos = jnp.arange(n)
    q = _rms(_mm("sd,dhk->shk", u, a["wq"], mode), a["q_norm"], s["eps"])
    k = _rms(_mm("sd,dhk->shk", u, a["wk"], mode), a["k_norm"], s["eps"])
    v = _mm("sd,dhk->shk", u, a["wv"], mode)
    q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
    rep = s["h"] // s["kv"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    pad = (-n) % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
        scores = _mm("qhd,khd->hqk", qb, k, mode) / np.sqrt(s["hd"])
        seen = pos[None, :] <= i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
        return _mm("hqk,khd->qhd", probs, v, mode)

    out = jax.lax.map(rows, jnp.arange((n + pad) // Q_BLOCK))
    out = out.reshape((n + pad,) + out.shape[2:])[:n]
    return _mm("shk,hkd->sd", out, a["wo"], mode)


def expert_layer(outside, expert_of, h, s: Dict[str, Any], eps: float,
                 mode: str, held=None):
    """Sigmoid scores over every expert; the ``top_k`` picked are the
    largest of ``score + bias``, weighed by their scores alone,
    normalised over the picked (``eps`` under the sum) and scaled by the
    routed scale; the experts ``held`` (the configuration's, or a range
    given) one after another (``expert_of(id)`` gives one's weights).
    h [S,d] -> [S,d]."""
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, outside["router"], mode))
    _, ids = jax.lax.top_k(scores + outside["bias"], s["top_k"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    share = s["scale"] * picked / (picked.sum(-1, keepdims=True) + eps)
    lo, hi = held or s["held"]

    def add(e, y):
        w = jnp.sum(jnp.where(ids == e, share, 0.0), axis=-1)   # [S]
        return y + w[:, None] * _swiglu(expert_of(e), h, mode)

    return jax.lax.fori_loop(lo, hi, add, jnp.zeros_like(h))


def block(mixer, ffn, norms, expert_of, x, config: Dict[str, Any],
          index: int, mode: str = "f32"):
    """Decoder block ``index`` over one row x [S,d]: ``ffn`` is
    ``{"MLP_0": ...}`` of a dense layer, else the router and its bias;
    ``norms`` the two scales."""
    s = weights.dims(config)
    u = _rms(x, norms["RMSNorm_0"]["scale"], s["eps"])
    if s["kinds"][index] == "conv":
        o = short_conv(mixer["ShortConv_0"], u, s, mode)
    else:
        o = attention(mixer["Attention_0"], u, s, mode)
    x = x + o
    g = _rms(x, norms["RMSNorm_1"]["scale"], s["eps"])
    if "MLP_0" in ffn:
        return x + _swiglu(ffn["MLP_0"], g, mode)
    return x + expert_layer(ffn, expert_of, g, s,
                            float(config["router_eps"]), mode)


def teacher_forced_logits(config: Dict[str, Any], seed: int,
                          rows: np.ndarray, mode: str = "f32",
                          weight_dtype=jnp.bfloat16) -> jnp.ndarray:
    """rows [N,S] int (padded on the right; causal mixers keep padding
    from reaching earlier positions) -> logits [N,S,V] float32 on the
    device. Weights are drawn from the seed, rounded once to
    ``weight_dtype`` (the type they are served in) and used in float32;
    one layer's are alive at a time, and of its experts one."""
    with jax.default_matmul_precision("highest"):
        return _logits(config, seed, rows, mode, weight_dtype)


def _logits(config, seed, rows, mode, weight_dtype):
    key = weights.seed_key(seed)
    s = weights.dims(config)

    def as_served(tree):
        return jax.tree_util.tree_map(
            lambda w: w.astype(weight_dtype).astype(jnp.float32), tree)

    def layer(index: int):
        @jax.jit
        def run(xs, k):
            mixer = as_served(weights.init_mixer(config, k, index))
            ffn = as_served(weights.init_ffn_outside_experts(config, k,
                                                             index))
            norms = as_served(weights.init_norms(config, k, index))
            # rows [N,S,d] one by one through the block; what does not
            # hang on a row (an expert's weights, drawn as the loop
            # reaches it) is made once for all of them
            return jax.vmap(lambda x: block(
                mixer, ffn, norms,
                lambda e: as_served(weights.init_expert(config, k, index,
                                                        e)),
                x, config, index, mode))(xs)
        return run

    embed = jax.jit(lambda k: as_served(weights.init_embedding(config, k)))(
        key)
    xs = embed[jnp.asarray(rows, jnp.int32)]
    for i in range(s["layers"]):
        run = layer(i)
        xs = run(xs, key)
    final = jax.jit(lambda k: as_served(weights.init_final_norm(config,
                                                                k)))(key)
    # one product for all rows, the embedding an ARGUMENT (tied head):
    # closed over, it would be baked into the program as a constant
    return jax.jit(lambda x, w, g: _mm(
        "nsd,vd->nsv", _rms(x, g, s["eps"]), w, mode))(xs, embed, final)
