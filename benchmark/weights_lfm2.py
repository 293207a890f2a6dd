"""Random weights from --seed for the LFM2 family with experts
(``lfm2_moe``: gated short-convolution layers beside grouped-query
softmax attention with a norm on each head's queries and keys, leading
dense layers then experts without a shared one, a sigmoid router whose
picks a bias selects, a tied head), in the layout
``ray_tpu.models.decoder`` documents for a ``DecoderConfig``: that
layout is the program's interface. The values are the benchmark's own;
the reference remakes them from the seed a layer, and inside an expert
layer an expert, at a time.

What is made is this chip's share: the experts ``experts_held`` (ids
first..last-1 of the router's ``num_experts``; here all of them) and
``vocab_size`` rows of the embedding, which is the head too.

Drawn as ``weights_trinity_large`` draws them: normal, std 0.02 for the
embedding, 1/sqrt(fan_in) for matrices and the router (the depthwise
convolution's fan-in is its taps), every norm's scale 1 + 0.1 x normal
(a scale of one would leave a norm's place in the forward pass
untested), the router's bias normal with std ``router_bias_std`` (the
published buffer starts at zero and training moves it; zeros would
leave the selection untested). One departure: a routed expert's down
projection is drawn at ``expert_down_scale`` times 1/sqrt(fan_in).
With every expert held, a near-tie at the 4th/5th pick swaps a whole
expert's output; at the full scale that swap moves the residual stream
by about a tenth, which flips picks in the layers after it, and the
model's argmax follows bfloat16's rounding as much as its weights
(PERF.md section 2).

``description(config)`` is the whole ``DecoderConfig``, layers
included: ``drivers/serve_described.py`` asks the weights module for it
and knows no model's layer rule itself.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.weights import _normal, seed_key  # noqa: F401 (seed_key: the drivers')
from benchmark.weights_solar_open2 import _mlp

MIXER_OF = {"conv": "conv", "full_attention": "attention"}


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the tree needs, under short names."""
    kinds = tuple(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types names another number of layers than "
                         "num_hidden_layers")
    if not config["norm_topk_prob"]:
        raise ValueError("the program normalises the picked experts' "
                         "weights (norm_topk_prob true)")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    lo, hi = config.get("experts_held") or (0, config["num_experts"])
    return {
        "d": d,
        "h": h,
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim") or d // h),
        "v": int(config["vocab_size"]),
        "layers": len(kinds),
        "kinds": kinds,
        "dense": int(config["num_dense_layers"]),
        "taps": int(config["conv_L_cache"]),
        "f": int(config["intermediate_size"]),
        "router": int(config["num_experts"]),
        "held": (int(lo), int(hi)),
        "top_k": int(config["num_experts_per_tok"]),
        "fe": int(config["moe_intermediate_size"]),
        "scale": float(config["routed_scaling_factor"]),
        "bias_std": float(config["router_bias_std"]),
        "down_scale": float(config.get("expert_down_scale", 1.0)),
        "eps": float(config["norm_eps"]),
        "theta": float(config["rope_parameters"]["rope_theta"]),
    }


def is_dense(config: Dict[str, Any], index: int) -> bool:
    return index < dims(config)["dense"]


def init_embedding(config: Dict[str, Any], key) -> jnp.ndarray:
    s = dims(config)
    return _normal(jax.random.fold_in(key, 1_000_003), (s["v"], s["d"]),
                   0.02)


def _scale(key, width: int) -> jnp.ndarray:
    """A norm's learned scale."""
    return 1.0 + _normal(key, (width,), 0.1)


def init_final_norm(config: Dict[str, Any], key) -> jnp.ndarray:
    return _scale(jax.random.fold_in(key, 1_000_037), dims(config)["d"])


def _layer_key(key, index: int, what: int):
    return jax.random.fold_in(jax.random.fold_in(key, index), what)


def init_expert(config: Dict[str, Any], key, index: int, expert
                ) -> Dict[str, jnp.ndarray]:
    """One routed expert of layer ``index`` by its id (which may be
    traced), float32: ``w_gate``, ``w_up`` [d,fe], ``w_down`` [fe,d]."""
    s = dims(config)
    p = _mlp(jax.random.fold_in(_layer_key(key, index, 7_000_003), expert),
             s["d"], s["fe"])
    return {**p, "w_down": p["w_down"] * s["down_scale"]}


def init_mixer(config: Dict[str, Any], key, index: int) -> Dict[str, Any]:
    """``{"ShortConv_0": ...}`` of a conv layer, ``{"Attention_0": ...}``
    of a full_attention layer, float32."""
    s = dims(config)
    d, h, kv, hd = s["d"], s["h"], s["kv"], s["hd"]
    ks = jax.random.split(_layer_key(key, index, 7_000_001), 6)
    if s["kinds"][index] == "conv":
        return {"ShortConv_0": {
            "w_in": _normal(ks[0], (d, 3, d), d ** -0.5),
            "conv": _normal(ks[1], (s["taps"], d), s["taps"] ** -0.5),
            "w_out": _normal(ks[2], (d, d), d ** -0.5)}}
    return {"Attention_0": {
        "wq": _normal(ks[0], (d, h, hd), d ** -0.5),
        "wk": _normal(ks[1], (d, kv, hd), d ** -0.5),
        "wv": _normal(ks[2], (d, kv, hd), d ** -0.5),
        "wo": _normal(ks[3], (h, hd, d), (h * hd) ** -0.5),
        "q_norm": _scale(ks[4], hd),
        "k_norm": _scale(ks[5], hd)}}


def init_norms(config: Dict[str, Any], key, index: int) -> Dict[str, Any]:
    """The two norms of layer ``index``: before the mixer, before the
    feed-forward."""
    d = dims(config)["d"]
    ks = jax.random.split(_layer_key(key, index, 7_000_004), 2)
    return {name: {"scale": _scale(k, d)}
            for name, k in zip(("RMSNorm_0", "RMSNorm_1"), ks)}


def init_ffn_outside_experts(config: Dict[str, Any], key, index: int
                             ) -> Dict[str, Any]:
    """What of layer ``index``'s feed-forward every token goes through:
    ``{"MLP_0": ...}`` of a leading dense layer, else ``{"router",
    "bias"}`` with every output."""
    s = dims(config)
    ks = jax.random.split(_layer_key(key, index, 7_000_002), 2)
    if is_dense(config, index):
        return {"MLP_0": _mlp(ks[0], s["d"], s["f"])}
    return {"router": _normal(ks[0], (s["d"], s["router"]), s["d"] ** -0.5),
            "bias": _normal(ks[1], (s["router"],), s["bias_std"])}


def init_layer(config: Dict[str, Any], key, index: int) -> Dict[str, Any]:
    """One block in float32, the held experts stacked [E_held, ...]."""
    s = dims(config)
    ffn = init_ffn_outside_experts(config, key, index)
    if not is_dense(config, index):
        lo, hi = s["held"]
        experts = jax.vmap(lambda e: init_expert(config, key, index, e))(
            jnp.arange(lo, hi))
        ffn = {"MoE_0": {**ffn, **experts}}
    return {**init_mixer(config, key, index), **ffn,
            **init_norms(config, key, index)}


def init_params(config: Dict[str, Any], key, dtype=jnp.float32
                ) -> Dict[str, Any]:
    """The whole tree, rounded once to ``dtype``. Call under
    ``jax.jit``."""
    s = dims(config)
    tree: Dict[str, Any] = {
        "embedding": init_embedding(config, key),
        "final_norm": {"scale": init_final_norm(config, key)},
    }
    for i in range(s["layers"]):
        tree[f"layer_{i}"] = init_layer(config, key, i)
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def description(config: Dict[str, Any]):
    """The configuration file's keys as the ``DecoderConfig`` the
    program is given: a ``conv`` layer is the mixer ``conv``, a
    ``full_attention`` layer the mixer ``attention`` (rotary, a norm on
    each head's queries and keys); the first ``num_dense_layers``
    feed-forwards dense, the others expert layers."""
    from ray_tpu.models.decoder import DecoderConfig, LayerSpec

    s = dims(config)
    run = config["run"]
    return DecoderConfig(
        vocab_size=s["v"], d_model=s["d"],
        layers=tuple(
            LayerSpec(MIXER_OF[kind], "dense" if is_dense(config, i)
                      else "experts") for i, kind in enumerate(s["kinds"])),
        n_heads=s["h"], n_kv_heads=s["kv"], head_dim=s["hd"], d_ff=s["f"],
        rope_theta=s["theta"], qk_norm=True, tie_embeddings=True,
        norm_eps=s["eps"], dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]), conv_taps=s["taps"],
        n_routed_experts=s["router"], experts_held=s["held"],
        experts_per_token=s["top_k"], d_expert=s["fe"],
        routed_scale=s["scale"], router_bias=bool(config["use_expert_bias"]),
        router_eps=float(config["router_eps"]))
