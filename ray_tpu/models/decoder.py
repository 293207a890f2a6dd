"""A decoder described layer by layer.

``TransformerConfig`` says what kind of model it is with booleans, and
every layer is the same. The models people serve mix kinds: a softmax
attention layer, then three with a recurrent state, experts in every
feed-forward, an untied head. ``DecoderConfig`` names, for each layer,
its mixer (``attention``, ``window``, ``delta_rule``, ``latent`` or
``conv``) and
its feed-forward (``dense`` or ``experts``), and beside them what the
kinds need; a model may mix feed-forward kinds as well as mixers. The
dense decoder is the one-kind case: ``describe`` lowers a
``TransformerConfig`` to it. ``models/decoder_forward.py`` runs a
description (the functional forward, and the cache of what each kind of
layer keeps between steps); the serving engine (``models/inference.py``)
hands that module the description and knows no kind of layer itself.

The parameter tree a description stands for (``layer_<i>`` under the
root, beside ``embedding``, ``final_norm/scale`` and, untied,
``lm_head`` [V, d]):

- ``RMSNorm_0/scale``, ``RMSNorm_1/scale`` (before the mixer, before
  the feed-forward) and, with ``sandwich_norm``, ``PostNorm_0/scale``,
  ``PostNorm_1/scale`` (on each branch before its residual add);
- mixer ``attention``: ``Attention_0/{wq [d,H,hd], wk, wv [d,KV,hd], wo
  [H,hd,d]}`` and, gated, ``w_gate [d,H,hd]``, and, with ``qk_norm``,
  ``q_norm``, ``k_norm`` [hd] (an RMSNorm over each head's width, one
  scale for all query heads and one for all key heads, before any
  rotation);
- mixer ``window`` (softmax attention over the last ``window``
  positions, itself among them): the same leaves under the same name,
  ``Attention_0``. It keeps at most a ring of ``window / page_size +
  1`` pages a sequence (``models/decoder_forward.py``);
- mixer ``delta_rule`` (ops/kda.py): ``DeltaRule_0/{wq, wk [d,H,dk], wv
  [d,H,dv], conv_q, conv_k [K,H,dk], conv_v [K,H,dv], w_f_down [d,r],
  w_f_up [r,H,dk], dt_bias [H,dk], A_log [H], w_beta [d,H], w_g_down
  [d,r], w_g_up [r,H,dv], o_norm [dv], wo [H,dv,d]}``;
- mixer ``latent`` (multi-head latent attention; r = ``kv_rank``, n =
  ``nope_dim``, p = ``rope_dim``): ``LatentAttention_0/{w_qa [d,
  q_rank], q_norm [q_rank], w_qb [q_rank,H,n+p], w_kva [d,r+p], kv_norm
  [r], w_kvb [r,H,n+v_dim], wo [H,v_dim,d]}``; a head's keys are ``[the
  first n columns of w_kvb over the normed latent | the ONE rotated
  p-wide key all heads share]``, its values the other ``v_dim``;
- mixer ``conv`` (a gated short convolution; K = ``conv_taps``):
  ``ShortConv_0/{w_in [d,3,d], conv [K,d], w_out [d,d]}``; ``w_in``'s
  three blocks are B, C and x in that order, and the mixer is ``(C *
  conv(B * x)) w_out`` with a causal depthwise convolution whose
  ``conv[K-1]`` multiplies the current position (ops/short_conv.py).
  It keeps the last K-1 rows of ``B * x`` a slot and nothing else;
- feed-forward ``dense``: ``MLP_0/{w_gate, w_up [d,f], w_down [f,d]}``;
- feed-forward ``experts`` (ops/moe.py): ``MoE_0/{router [d,E], w_gate,
  w_up [E_held,d,fe], w_down [E_held,fe,d]}``, with a shared expert
  ``MoE_0/shared/{w_gate, w_up [d,fs], w_down [fs,d]}`` and, with
  ``router_bias``, ``MoE_0/bias [E]`` (added to the scores to SELECT
  the picked, never to weigh them).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp

MIXERS = ("attention", "window", "delta_rule", "latent", "conv")
FFNS = ("dense", "experts")


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str = "attention"
    ffn: str = "dense"

    def __post_init__(self):
        if self.mixer not in MIXERS or self.ffn not in FFNS:
            raise ValueError(f"unknown layer kinds {self}")


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    d_model: int
    layers: Tuple[LayerSpec, ...]
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int = 0                       # dense feed-forward width
    # what a layer rotates with (None: no rotation at all): window and
    # latent layers always, attention layers unless rope_attention says
    # otherwise (a model whose full layers carry no positions beside
    # window layers that do)
    rope_theta: Optional[float] = None
    rope_attention: bool = True
    attn_gate: bool = False             # out = wo(attn * sigmoid(w_gate h))
    qk_norm: bool = False               # RMSNorm a head on q and on k
    window: int = 0                     # positions a window layer sees
    embed_scale: float = 1.0            # factor on the embedding's rows
    tie_embeddings: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # delta_rule layers: heads, key and value width, convolution
    # length, rank of the two low-rank gate projections
    dr_heads: int = 0
    dr_key_dim: int = 0
    dr_value_dim: int = 0
    dr_conv: int = 4
    dr_rank: int = 0
    # latent layers: rank of the query's and of the keys-and-values'
    # compression, a head's key width without and with positions, a
    # head's value width
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    # conv layers: taps of the gated short convolution
    conv_taps: int = 3
    # four norms a layer: each branch is normed again before its
    # residual add
    sandwich_norm: bool = False
    # experts layers: the router's width, the range of ids held here
    # [first, last), picks a token, an expert's width, the shared
    # expert's (0: none), the factor on the picked experts' normalised
    # weights
    n_routed_experts: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    experts_per_token: int = 0
    d_expert: int = 0
    d_shared: int = 0
    routed_scale: float = 1.0
    router_bias: bool = False           # picks by score + MoE_0/bias
    # what a biased router adds under the sum of the picked scores
    router_eps: float = 1e-20

    def __post_init__(self):
        if self.window_layers and self.window <= 0:
            raise ValueError("window layers need the window's length "
                             "(DecoderConfig.window)")
        lo, hi = self.experts_held
        if self.moe_layers and not (
                0 <= lo < hi <= self.n_routed_experts
                and 0 < self.experts_per_token <= self.n_routed_experts):
            raise ValueError(
                f"experts_held {self.experts_held} / experts_per_token "
                f"{self.experts_per_token} do not fit a router of "
                f"{self.n_routed_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def kv_layers(self) -> Tuple[int, ...]:
        """Layers that keep keys and values in the paged pool."""
        return tuple(i for i, l in enumerate(self.layers)
                     if l.mixer == "attention")

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """Layers that keep keys and values of the last ``window``
        positions, in a ring of pages a slot."""
        return tuple(i for i, l in enumerate(self.layers)
                     if l.mixer == "window")

    def rotates(self, mixer: str) -> bool:
        """Whether layers of kind ``mixer`` rotate queries and keys."""
        return self.rope_theta is not None and (
            mixer != "attention" or self.rope_attention)

    @property
    def latent_layers(self) -> Tuple[int, ...]:
        """Layers that keep one latent row a token in a paged pool of
        their own."""
        return tuple(i for i, l in enumerate(self.layers)
                     if l.mixer == "latent")

    @property
    def latent_width(self) -> int:
        """What a latent layer keeps a token, in numbers: the normed
        latent and the rotated shared key side by side, padded with
        zeros to whole lanes of 128 (the chip's memory holds a row of
        576 as 640 anyway, and a whole-lane row is one the kernel's
        products take as it lies)."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def kv_width(self) -> int:
        """What an attention or window layer's pools keep of a head, in
        numbers: the head's keys or values padded with zeros to whole
        lanes of 128, as ``latent_width`` pads a latent row (the chip's
        paged read and append take rows of whole lanes; its compiler
        refuses a row of 64). A head of 128 is kept as it is."""
        return -(-self.head_dim // 128) * 128

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """Layers that keep a state of their own a slot: a recurrent
        state and a convolution tail (``delta_rule``), a convolution
        tail alone (``conv``)."""
        return tuple(i for i, l in enumerate(self.layers)
                     if l.mixer in ("delta_rule", "conv"))

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        """Layers whose mixer is the gated short convolution."""
        return tuple(i for i, l in enumerate(self.layers)
                     if l.mixer == "conv")

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, l in enumerate(self.layers)
                     if l.ffn == "experts")

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def dr_channels(self) -> int:
        """Channels of a delta_rule layer's convolution: q, k and v side
        by side, a head."""
        return 2 * self.dr_key_dim + self.dr_value_dim


def describe(cfg: Any) -> DecoderConfig:
    """The description the engine is given: a ``DecoderConfig`` as it is,
    a ``TransformerConfig`` as the dense decoder it is (rotary GQA,
    SwiGLU, tied head in every layer)."""
    if isinstance(cfg, DecoderConfig):
        return cfg
    if getattr(cfg, "moe", False):
        raise ValueError(
            "TransformerConfig(moe=True) is the training module's top-1 "
            "switch layer; the serving engine runs experts through a "
            "DecoderConfig (models/decoder.py)")
    return DecoderConfig(
        vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        layers=(LayerSpec(),) * cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, d_ff=cfg.d_ff,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype)
