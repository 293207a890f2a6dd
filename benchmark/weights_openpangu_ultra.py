"""Random weights from --seed for the openPangu-Ultra-MoE family
(latent attention in every layer, sandwich norms, leading dense layers
then experts with a shared one and a scaled sigmoid router, untied
head), in the layout ``ray_tpu.models.decoder`` documents for a
``DecoderConfig``: that layout is the program's interface. The values
are the benchmark's own; the reference remakes them from the seed a
layer, and inside an expert layer an expert, at a time.

What is made is this chip's share: the experts ``experts_held`` (ids
first..last-1 of the router's ``router_width``) and ``vocab_size`` rows
of embedding and head. The router keeps every output.

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


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the tree needs, under short names."""
    lo, hi = config["experts_held"]
    return {
        "d": int(config["hidden_size"]),
        "h": int(config["num_attention_heads"]),
        "v": int(config["vocab_size"]),
        "layers": int(config["num_hidden_layers"]),
        "dense": int(config["first_k_dense_replace"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "vd": int(config["v_head_dim"]),
        "f": int(config["intermediate_size"]),
        "router": int(config["router_width"]),
        "held": (int(lo), int(hi)),
        "top_k": int(config["num_experts_per_tok"]),
        "fe": int(config["moe_intermediate_size"]),
        "fs": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        "scale": float(config["routed_scaling_factor"]),
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
    """{"LatentAttention_0": ...} of layer ``index``, float32."""
    s = dims(config)
    d, h = s["d"], s["h"]
    ks = jax.random.split(_layer_key(key, index, 7_000_001), 5)
    return {"LatentAttention_0": {
        "w_qa": _normal(ks[0], (d, s["q_rank"]), d ** -0.5),
        "q_norm": jnp.ones((s["q_rank"],), jnp.float32),
        "w_qb": _normal(ks[1], (s["q_rank"], h, s["nope"] + s["rope"]),
                        s["q_rank"] ** -0.5),
        "w_kva": _normal(ks[2], (d, s["kv_rank"] + s["rope"]), d ** -0.5),
        "kv_norm": jnp.ones((s["kv_rank"],), jnp.float32),
        "w_kvb": _normal(ks[3], (s["kv_rank"], h, s["nope"] + s["vd"]),
                         s["kv_rank"] ** -0.5),
        "wo": _normal(ks[4], (h, s["vd"], d), (h * s["vd"]) ** -0.5)}}


def init_ffn_outside_experts(config: Dict[str, Any], key, index: int
                             ) -> Dict[str, Any]:
    """What of layer ``index``'s feed-forward every token goes through:
    ``{"MLP_0": ...}`` of a leading dense layer, else ``{"router",
    "shared"}`` (the router with every output)."""
    s = dims(config)
    ks = jax.random.split(_layer_key(key, index, 7_000_002), 2)
    if is_dense(config, index):
        return {"MLP_0": _mlp(ks[0], s["d"], s["f"])}
    out = {"router": _normal(ks[0], (s["d"], s["router"]), s["d"] ** -0.5)}
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
    ones = {"scale": jnp.ones((s["d"],), jnp.float32)}
    return {**init_mixer(config, key, index), **ffn,
            "RMSNorm_0": ones, "RMSNorm_1": ones,
            "PostNorm_0": ones, "PostNorm_1": ones}


def init_params(config: Dict[str, Any], key, dtype=jnp.float32
                ) -> Dict[str, Any]:
    """The whole tree, rounded once to ``dtype``. Call under
    ``jax.jit``."""
    s = dims(config)
    tree: Dict[str, Any] = {
        "embedding": init_embedding(config, key),
        "lm_head": init_head(config, key),
        "final_norm": {"scale": jnp.ones((s["d"],), jnp.float32)},
    }
    for i in range(s["layers"]):
        tree[f"layer_{i}"] = init_layer(config, key, i)
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def description(config: Dict[str, Any]):
    """The configuration file's keys as the ``DecoderConfig`` the
    program is given: every mixer ``latent``, the first
    ``first_k_dense_replace`` feed-forwards dense, the others expert
    layers."""
    from ray_tpu.models.decoder import DecoderConfig, LayerSpec

    s = dims(config)
    run = config["run"]
    return DecoderConfig(
        vocab_size=s["v"], d_model=s["d"],
        layers=tuple(
            LayerSpec("latent", "dense" if is_dense(config, i)
                      else "experts") for i in range(s["layers"])),
        n_heads=s["h"], n_kv_heads=1, head_dim=s["nope"] + s["rope"],
        d_ff=s["f"], rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
        q_rank=s["q_rank"], kv_rank=s["kv_rank"], nope_dim=s["nope"],
        rope_dim=s["rope"], v_dim=s["vd"],
        sandwich_norm=bool(config["sandwich_norm"]),
        n_routed_experts=s["router"], experts_held=s["held"],
        experts_per_token=s["top_k"], d_expert=s["fe"], d_shared=s["fs"],
        routed_scale=s["scale"])
