"""Plain reference for the Trinity-Large (``afmoe``) block: a decoder
with four norms a layer (each branch normed again before its residual
add), gated grouped-query softmax attention with an RMSNorm over each
head's queries and keys, ``sliding_attention`` layers that rotate and
see the last ``sliding_window`` positions beside ``full_attention``
layers that rotate nothing and see everything before them, a dense
SwiGLU feed-forward in the leading layers and experts with a shared one
in the others, routed by sigmoid scores that a bias SELECTS and never
weighs, the embedding's rows times the root of the width, untied head.

Straightforward ``jax.numpy`` in float32 with every product at
``precision=HIGHEST``; nothing of ray_tpu, no kernel, no cache, no ring:
every layer sees the whole row, the window is a mask built from
positions (``i - window < j <= i``), scores are taken a block of query
rows at a time so that a 17,408-token row fits; the experts are a loop
over the held ids with each expert's weights remade from the seed as it
is reached. Weights come from ``benchmark.weights_trinity_large`` and
the seed, one layer at a time.

The layer equations are ISSUE 33's reading of the published config
(``benchmark/configs/trinity-large-preview-serve-L5-ep8.json`` repeats
them and lists what is ``assumed``). Departures from the published
model, all stated in that file: this chip's share only (experts
``experts_held`` of the router's ``router_width``, ``vocab_size`` rows
of embedding and head; a token's picks on absent experts add nothing),
one leading dense layer and one period of four expert layers.

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

from benchmark import weights_trinity_large as weights
from benchmark.reference.dense_decoder import (_mm, _rms, _rope,  # noqa: F401
                                               served_token_gaps)
from benchmark.reference.solar_open2 import _swiglu

Q_BLOCK = 256


def attention(a, u, s: Dict[str, Any], window: int, theta: float,
              eps: float, mode: str):
    """Gated grouped-query attention with normed queries and keys:
    u [S,d] (normed) -> [S,d]. ``window`` > 0 is a sliding layer: it
    rotates queries and keys and position ``i`` sees ``i - window < j
    <= i``; 0 is a full layer: no rotation, every ``j <= i``."""
    n = u.shape[0]
    pos = jnp.arange(n)
    q = _rms(_mm("sd,dhk->shk", u, a["wq"], mode), a["q_norm"], eps)
    k = _rms(_mm("sd,dhk->shk", u, a["wk"], mode), a["k_norm"], eps)
    v = _mm("sd,dhk->shk", u, a["wv"], mode)
    if window:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = s["h"] // s["kv"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    pad = (-n) % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
        scores = _mm("qhd,khd->hqk", qb, k, mode) / np.sqrt(s["hd"])
        at = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        seen = pos[None, :] <= at
        if window:
            seen &= pos[None, :] > at - window
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
        return _mm("hqk,khd->qhd", probs, v, mode)

    out = jax.lax.map(rows, jnp.arange((n + pad) // Q_BLOCK))
    out = out.reshape((n + pad,) + out.shape[2:])[:n]
    gate = jax.nn.sigmoid(_mm("sd,dhk->shk", u, a["w_gate"], mode))
    return _mm("shk,hkd->sd", out * gate, a["wo"], mode)


def expert_layer(outside, expert_of, h, s: Dict[str, Any], mode: str,
                 held=None):
    """Sigmoid scores over every expert; the ``top_k`` picked are the
    largest of ``score + bias``, weighed by their scores alone,
    normalised over the picked (1e-20 under the sum) and scaled by the
    routed scale; the experts ``held`` (the configuration's, or a range
    given) one after another (``expert_of(id)`` gives one's weights),
    the shared expert once and unscaled. h [S,d] -> [S,d]."""
    scores = jax.nn.sigmoid(_mm("sd,de->se", h, outside["router"], mode))
    _, ids = jax.lax.top_k(scores + outside["bias"], s["top_k"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    share = s["scale"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    lo, hi = held or s["held"]

    def add(e, y):
        w = jnp.sum(jnp.where(ids == e, share, 0.0), axis=-1)   # [S]
        return y + w[:, None] * _swiglu(expert_of(e), h, mode)

    y = jax.lax.fori_loop(lo, hi, add, jnp.zeros_like(h))
    if "shared" in outside:
        y = y + _swiglu(outside["shared"], h, mode)
    return y


def block(mixer, ffn, norms, expert_of, x, config: Dict[str, Any],
          index: int, mode: str = "f32"):
    """Decoder block ``index`` over one row x [S,d]: ``ffn`` is
    ``{"MLP_0": ...}`` of a dense layer, else the router, its bias and
    the shared expert; ``norms`` the four scales."""
    s = weights.dims(config)
    eps = float(config["rms_norm_eps"])
    window = (s["window"] if s["kinds"][index] == "sliding_attention"
              else 0)
    a = attention(mixer["Attention_0"],
                  _rms(x, norms["RMSNorm_0"]["scale"], eps), s, window,
                  float(config["rope_theta"]), eps, mode)
    x = x + _rms(a, norms["PostNorm_0"]["scale"], eps)
    h = _rms(x, norms["RMSNorm_1"]["scale"], eps)
    if "MLP_0" in ffn:
        y = _swiglu(ffn["MLP_0"], h, mode)
    else:
        y = expert_layer(ffn, expert_of, h, s, mode)
    return x + _rms(y, norms["PostNorm_1"]["scale"], eps)


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
                as_served(weights.init_norms(config, k, index)),
                lambda e: as_served(weights.init_expert(config, k, index,
                                                        e)),
                x, config, index, mode)
        return run

    embed = jax.jit(lambda k: as_served(weights.init_embedding(config, k)))(
        key)
    xs = [embed[jnp.asarray(r, jnp.int32)] * s["embed_scale"] for r in rows]
    del embed
    for i in range(s["layers"]):
        run = layer(i)
        xs = [run(x, key) for x in xs]      # a row at a time
    head = jax.jit(lambda k: as_served(weights.init_head(config, k)))(key)
    final = jax.jit(lambda k: as_served(weights.init_final_norm(config,
                                                                k)))(key)
    # one product for all rows: stacking rows of logits afterwards
    # would hold them twice
    return jax.jit(lambda x, w, g: _mm(
        "nsd,vd->nsv", _rms(x, g, float(config["rms_norm_eps"])), w,
        mode))(jnp.stack(xs), head, final)
