"""Plain reference for a dense pre-norm decoder (RMSNorm, rotary
grouped-query attention, SwiGLU, tied head): Mistral-7B's block.

Straightforward ``jax.numpy`` in float32 with every product at
``precision=HIGHEST``; no kernel, no cache, no batching tricks. It
imports nothing of ray_tpu and takes nothing the program made: weights
come from ``benchmark.weights`` and the seed.

Departures from the published model, both inherited from the program
under test and stated in the configuration files: the output head is
the embedding matrix (published: untied), and the rotary embedding
pairs even/odd lanes (mistral-inference's convention; the Hugging Face
port rotates halves).

``mode`` lowers the precision of every matrix product for the CONTROL
of the output check ("How correct is decided", step 2): ``"f32"`` is
the reference, ``"bf16"`` rounds both operands to bfloat16 (what the
configurations state), ``"fp8"`` rounds them to float8_e4m3 with a
per-tensor scale (the step below bfloat16 that would tempt a later PR).

So that it fits beside nothing but itself on one chip, training goes
row by row with each block recomputed in the backward pass, attention
and the loss go in chunks of positions, and serving goes layer by
layer with each layer's weights remade from the seed.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

HIGHEST = jax.lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _round_to(x: jnp.ndarray, mode: str) -> jnp.ndarray:
    """``x`` rounded to the mode's type and back, gradient passed
    straight through."""
    if mode == "f32":
        return x
    if mode == "bf16":
        # reduce_precision, not a cast there and back: XLA may elide a
        # round trip through bfloat16 (xla_allow_excess_precision)
        q = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    elif mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
        q = (x / scale).astype(_F8).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown precision mode {mode!r}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec: str, a: jnp.ndarray, b: jnp.ndarray, mode: str):
    return jnp.einsum(spec, _round_to(a, mode), _round_to(b, mode),
                      precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x [S, heads, hd]; lanes (0,1), (2,3), ... are rotated as pairs."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * freqs      # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attend(q, k, v, q0: int, mode: str):
    """Causal attention of query rows q0.. over all keys. q [Sq,H,hd],
    k/v [S,H,hd] (heads already repeated)."""
    sq, s = q.shape[0], k.shape[0]
    scores = _mm("qhd,khd->hqk", q, k, mode) / np.sqrt(q.shape[-1])
    allowed = (jnp.arange(s)[None, :] <= (q0 + jnp.arange(sq))[:, None])
    scores = jnp.where(allowed[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return _mm("hqk,khd->qhd", probs, v, mode)


def block(p: Dict[str, Any], x: jnp.ndarray, config: Dict[str, Any],
          mode: str = "f32", q_chunk: int = 1024,
          remat_chunks: bool = False) -> jnp.ndarray:
    """One decoder block over one row x [S, d]."""
    s = weights.dims(config)
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    a = p["Attention_0"]
    h = _rms(x, p["RMSNorm_0"]["scale"], eps)
    pos = jnp.arange(x.shape[0])
    q = _rope(_mm("sd,dhk->shk", h, a["wq"], mode), pos, theta)
    k = _rope(_mm("sd,dhk->shk", h, a["wk"], mode), pos, theta)
    v = _mm("sd,dhk->shk", h, a["wv"], mode)
    rep = s["h"] // s["kv"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    attend = functools.partial(_attend, mode=mode)
    if remat_chunks:
        attend = jax.checkpoint(attend, static_argnums=(3,))
    outs = [attend(q[i:i + q_chunk], k, v, i)
            for i in range(0, x.shape[0], q_chunk)]
    x = x + _mm("shk,hkd->sd", jnp.concatenate(outs), a["wo"], mode)
    m = p["MLP_0"]
    h = _rms(x, p["RMSNorm_1"]["scale"], eps)
    h = (jax.nn.silu(_mm("sd,df->sf", h, m["w_gate"], mode))
         * _mm("sd,df->sf", h, m["w_up"], mode))
    return x + _mm("sf,fd->sd", h, m["w_down"], mode)


def head_logits(x, embedding, final_scale, config, mode="f32"):
    """x [S, d] -> logits [S, V] through the final norm and the tied
    head."""
    h = _rms(x, final_scale, float(config["rms_norm_eps"]))
    return _mm("sd,vd->sv", h, embedding, mode)


# ----------------------------------------------------------------------
# training: loss, gradients and AdamW, three steps from the seed
# ----------------------------------------------------------------------

def row_loss(params, row, config, mode, q_chunk, loss_chunk):
    """Mean next-token cross entropy of one row of S+1 tokens."""
    inp, tgt = row[:-1], row[1:]
    x = params["embedding"][inp]
    blk = jax.checkpoint(
        lambda p, x: block(p, x, config, mode, q_chunk, True))
    for i in range(weights.dims(config)["layers"]):
        x = blk(params[f"layer_{i}"], x)

    @jax.checkpoint
    def chunk_nll(xc, tc):
        lg = head_logits(xc, params["embedding"],
                         params["final_norm"]["scale"], config, mode)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, tc[:, None], 1)[:, 0])

    total = 0.0
    for i in range(0, x.shape[0], loss_chunk):
        total = total + chunk_nll(x[i:i + loss_chunk],
                                  tgt[i:i + loss_chunk])
    return total / x.shape[0]


def train_three_steps(config: Dict[str, Any], seed: int,
                      batches: Sequence[np.ndarray], opt: Dict[str, float],
                      mode: str = "f32", rows_used: Optional[int] = None,
                      q_chunk: int = 1024, loss_chunk: int = 1024
                      ) -> Dict[str, Any]:
    """Follow ``len(batches)`` optimizer steps from the seed's weights.
    ``batches`` are [B, S+1] int arrays, one a step. ``rows_used`` is
    for the planted fault "half of the batch left out, the mean taken
    over the rest". Returns the loss of each step, the per-leaf norm of
    the first step's gradient and the per-leaf norm of the parameters'
    change over all the steps."""
    key = weights.seed_key(seed)
    make = jax.jit(lambda k: weights.init_params(config, k, jnp.float32))
    params = make(key)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)
    lr, b1, b2 = opt["learning_rate"], opt["b1"], opt["b2"]
    eps, wd = opt["eps"], opt["weight_decay"]

    grad = jax.value_and_grad(
        lambda p, row: row_loss(p, row, config, mode, q_chunk, loss_chunk))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def accumulate(p, acc, row, weight):
        loss, g = grad(p, row)
        return loss, jax.tree_util.tree_map(
            lambda a, b: a + weight * b, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adamw(p, m, v, g, t):
        def one(p, m, v, g):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
            return p, m, v
        out = jax.tree_util.tree_map(one, p, m, v, g)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    losses: List[float] = []
    first_grad: Dict[str, float] = {}
    for step, batch in enumerate(batches, start=1):
        rows = np.asarray(batch)[:rows_used] if rows_used else \
            np.asarray(batch)
        acc = zeros(params)
        row_losses = []
        for r in rows:
            loss, acc = accumulate(params, acc, jnp.asarray(r, jnp.int32),
                                   jnp.float32(1.0 / len(rows)))
            row_losses.append(loss)
        losses.append(float(np.mean(jax.device_get(row_losses))))
        if step == 1:
            first_grad = weights.leaf_norms(acc)
        params, m, v = adamw(params, m, v, acc, jnp.float32(step))
        del acc
    delta = weights.param_change_norms(config, seed, params)
    del params, m, v
    return {"losses": losses, "grad_norms": first_grad,
            "delta_norms": delta}


# ----------------------------------------------------------------------
# serving: teacher-forced logits over prompt + served tokens
# ----------------------------------------------------------------------

def teacher_forced_logits(config: Dict[str, Any], seed: int,
                          rows: np.ndarray, mode: str = "f32",
                          weight_dtype=jnp.bfloat16) -> jnp.ndarray:
    """rows [N, S] int (padded on the right; causal attention keeps
    padding from reaching earlier positions) -> logits [N, S, V] in
    float32, on the device. Weights are drawn from the seed, rounded
    once to ``weight_dtype`` (the type they are served in) and used in
    float32; one layer's are alive at a time."""
    key = weights.seed_key(seed)
    s = rows.shape[1]

    def as_served(tree):
        return jax.tree_util.tree_map(
            lambda w: w.astype(weight_dtype).astype(jnp.float32), tree)

    embed = jax.jit(lambda k: as_served(
        weights.init_embedding(config, k)))(key)

    @jax.jit
    def layer(x, k, index):
        p = as_served(weights.init_layer(config, k, index))
        return jax.vmap(lambda r: block(p, r, config, mode, s))(x)

    x = embed[jnp.asarray(rows, jnp.int32)]
    for i in range(weights.dims(config)["layers"]):
        # the index is traced: ONE program serves every layer
        x = layer(x, key, jnp.int32(i))
    ones = jnp.ones((weights.dims(config)["d"],), jnp.float32)
    # the embedding is an ARGUMENT: closed over, its half gigabyte
    # would be baked into the program as a constant
    return jax.jit(lambda x, e: jax.vmap(
        lambda r: head_logits(r, e, ones, config, mode))(x))(x, embed)


def served_token_gaps(logits: jnp.ndarray, rows: np.ndarray,
                      prompt_lens: Sequence[int], total_lens: Sequence[int]
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """For every served token (positions prompt_len .. total_len-1 of
    each row), how far its logit lies below the row's best at the
    position that produced it. Returns (gaps, argmax) as flat arrays
    over the served tokens, row after row."""
    lg = np.asarray(jax.device_get(_gap_parts(logits, jnp.asarray(
        rows, jnp.int32))))
    gaps, best = [], []
    for n, (pl, tl) in enumerate(zip(prompt_lens, total_lens)):
        # the token at position t was produced by the logits at t-1
        gaps.append(lg[0, n, pl - 1:tl - 1])
        best.append(lg[1, n, pl - 1:tl - 1])
    return np.concatenate(gaps), np.concatenate(best).astype(np.int64)


@jax.jit
def _gap_parts(logits, rows):
    nxt = jnp.concatenate([rows[:, 1:], rows[:, :1]], axis=1)
    at = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
    return jnp.stack([jnp.max(logits, axis=-1) - at,
                      jnp.argmax(logits, axis=-1).astype(jnp.float32)])
