"""Random weights from --seed for the Trinity-Large family (``afmoe``:
gated softmax attention with a norm on each head's queries and keys,
window layers that rotate and full layers that do not, four norms a
layer, leading dense layers then experts with a shared one and a
sigmoid router whose picks a bias selects, an embedding scaled by the
root of the width, untied head), in the layout
``ray_tpu.models.decoder`` documents for a ``DecoderConfig``: that
layout is the program's interface. The values are the benchmark's own;
the reference remakes them from the seed a layer, and inside an expert
layer an expert, at a time.

What is made is this chip's share: the experts ``experts_held`` (ids
first..last-1 of the router's ``router_width``) and ``vocab_size`` rows
of embedding and head. The router keeps every output and its bias.

Every norm's scale is drawn (1 + 0.1 x normal), not ones: the
configuration's norms on queries and keys and its post norms have
learned scales, and a scale of one would leave their place in the
forward pass untested. The router's bias is drawn too (normal,
``router_bias_std``): the published buffer starts at zero and training
moves it; zeros would leave the selection untested.

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

MIXER_OF = {"sliding_attention": "window", "full_attention": "attention"}


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the tree needs, under short names."""
    lo, hi = config["experts_held"]
    kinds = tuple(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types names another number of layers than "
                         "num_hidden_layers")
    return {
        "d": int(config["hidden_size"]),
        "h": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "v": int(config["vocab_size"]),
        "layers": len(kinds),
        "kinds": kinds,
        "dense": int(config["num_dense_layers"]),
        "window": int(config["sliding_window"]),
        "f": int(config["intermediate_size"]),
        "router": int(config["router_width"]),
        "held": (int(lo), int(hi)),
        "top_k": int(config["num_experts_per_tok"]),
        "fe": int(config["moe_intermediate_size"]),
        "fs": int(config["moe_intermediate_size"])
        * int(config["num_shared_experts"]),
        "scale": float(config["route_scale"]),
        "bias_std": float(config["router_bias_std"]),
        "embed_scale": (float(config["hidden_size"]) ** 0.5
                        if config["mup_enabled"] else 1.0),
    }


def is_dense(config: Dict[str, Any], index: int) -> bool:
    return index < dims(config)["dense"]


def init_embedding(config: Dict[str, Any], key) -> jnp.ndarray:
    s = dims(config)
    return _normal(jax.random.fold_in(key, 1_000_003), (s["v"], s["d"]),
                   0.02)


def init_head(config: Dict[str, Any], key) -> jnp.ndarray:
    s = dims(config)
    return _normal(jax.random.fold_in(key, 1_000_033), (s["v"], s["d"]),
                   s["d"] ** -0.5)


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
    return _mlp(jax.random.fold_in(_layer_key(key, index, 7_000_003),
                                   expert), s["d"], s["fe"])


def init_mixer(config: Dict[str, Any], key, index: int) -> Dict[str, Any]:
    """{"Attention_0": ...} of layer ``index`` (a window layer and a
    full one have the same leaves), float32."""
    s = dims(config)
    d, h, kv, hd = s["d"], s["h"], s["kv"], s["hd"]
    ks = jax.random.split(_layer_key(key, index, 7_000_001), 7)
    return {"Attention_0": {
        "wq": _normal(ks[0], (d, h, hd), d ** -0.5),
        "wk": _normal(ks[1], (d, kv, hd), d ** -0.5),
        "wv": _normal(ks[2], (d, kv, hd), d ** -0.5),
        "w_gate": _normal(ks[3], (d, h, hd), d ** -0.5),
        "wo": _normal(ks[4], (h, hd, d), (h * hd) ** -0.5),
        "q_norm": _scale(ks[5], hd),
        "k_norm": _scale(ks[6], hd)}}


def init_norms(config: Dict[str, Any], key, index: int) -> Dict[str, Any]:
    """The four norms of layer ``index``: before the mixer, on its
    branch before the add, before the feed-forward, on its branch."""
    d = dims(config)["d"]
    ks = jax.random.split(_layer_key(key, index, 7_000_004), 4)
    return {name: {"scale": _scale(k, d)} for name, k in zip(
        ("RMSNorm_0", "PostNorm_0", "RMSNorm_1", "PostNorm_1"), ks)}


def init_ffn_outside_experts(config: Dict[str, Any], key, index: int
                             ) -> Dict[str, Any]:
    """What of layer ``index``'s feed-forward every token goes through:
    ``{"MLP_0": ...}`` of a leading dense layer, else ``{"router",
    "bias", "shared"}`` (router and bias with every output)."""
    s = dims(config)
    ks = jax.random.split(_layer_key(key, index, 7_000_002), 3)
    if is_dense(config, index):
        return {"MLP_0": _mlp(ks[0], s["d"], s["f"])}
    out = {"router": _normal(ks[0], (s["d"], s["router"]), s["d"] ** -0.5),
           "bias": _normal(ks[2], (s["router"],), s["bias_std"])}
    if s["fs"]:
        out["shared"] = _mlp(ks[1], s["d"], s["fs"])
    return out


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
        "lm_head": init_head(config, key),
        "final_norm": {"scale": init_final_norm(config, key)},
    }
    for i in range(s["layers"]):
        tree[f"layer_{i}"] = init_layer(config, key, i)
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def description(config: Dict[str, Any]):
    """The configuration file's keys as the ``DecoderConfig`` the
    program is given: a ``sliding_attention`` layer is the mixer
    ``window`` (which rotates), a ``full_attention`` layer the mixer
    ``attention`` (which in this model does not); the first
    ``num_dense_layers`` feed-forwards dense, the others expert
    layers."""
    from ray_tpu.models.decoder import DecoderConfig, LayerSpec

    s = dims(config)
    run = config["run"]
    return DecoderConfig(
        vocab_size=s["v"], d_model=s["d"],
        layers=tuple(
            LayerSpec(MIXER_OF[kind], "dense" if is_dense(config, i)
                      else "experts") for i, kind in enumerate(s["kinds"])),
        n_heads=s["h"], n_kv_heads=s["kv"], head_dim=s["hd"], d_ff=s["f"],
        rope_theta=float(config["rope_theta"]), rope_attention=False,
        attn_gate=True, qk_norm=True, window=s["window"],
        embed_scale=s["embed_scale"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
        sandwich_norm=True,
        n_routed_experts=s["router"], experts_held=s["held"],
        experts_per_token=s["top_k"], d_expert=s["fe"], d_shared=s["fs"],
        routed_scale=s["scale"], router_bias=True)
