"""Random weights from --seed for the Solar-Open2 family (3:1 delta-rule
/ gated-GQA mixers, experts with a shared one in every layer, untied
head), in the layout ``ray_tpu.models.decoder`` documents for a
``DecoderConfig``: that layout is the program's interface. The values
are the benchmark's own; the reference remakes them from the seed a
layer, and inside an expert layer an expert, at a time.

What is made is this chip's share: the experts ``experts_held`` (ids
first..last-1 of the router's ``router_width``) and ``vocab_size`` rows
of embedding and head. The router keeps every output.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.weights import _normal, seed_key  # noqa: F401 (seed_key: the drivers')


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the tree needs, under short names."""
    lin = config["linear_attn_config"]
    lo, hi = config["experts_held"]
    layers = int(config["num_hidden_layers"])
    return {
        "d": int(config["hidden_size"]),
        "h": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "v": int(config["vocab_size"]),
        "layers": layers,
        "gqa": tuple(int(i) for i in config["gqa_layers"] if i < layers),
        "dr_h": int(lin["num_heads"]), "dr_d": int(lin["head_dim"]),
        "conv": int(lin["short_conv_kernel_size"]),
        "rank": int(config["kda_gate_rank"]),
        "router": int(config["router_width"]),
        "held": (int(lo), int(hi)),
        "top_k": int(config["num_experts_per_tok"]),
        "fe": int(config["moe_intermediate_size"]),
        "fs": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
    }


def init_embedding(config: Dict[str, Any], key) -> jnp.ndarray:
    s = dims(config)
    return _normal(jax.random.fold_in(key, 1_000_003), (s["v"], s["d"]),
                   0.02)


def init_head(config: Dict[str, Any], key) -> jnp.ndarray:
    s = dims(config)
    return _normal(jax.random.fold_in(key, 1_000_033), (s["v"], s["d"]),
                   s["d"] ** -0.5)


def _mlp(key, d: int, f: int) -> Dict[str, jnp.ndarray]:
    ks = jax.random.split(key, 3)
    return {"w_gate": _normal(ks[0], (d, f), d ** -0.5),
            "w_up": _normal(ks[1], (d, f), d ** -0.5),
            "w_down": _normal(ks[2], (f, d), f ** -0.5)}


def _experts_key(key, index: int):
    return jax.random.fold_in(jax.random.fold_in(key, index), 7_000_003)


def init_expert(config: Dict[str, Any], key, index: int, expert
                ) -> Dict[str, jnp.ndarray]:
    """One routed expert of layer ``index`` by its id (which may be
    traced), float32: ``w_gate``, ``w_up`` [d,fe], ``w_down`` [fe,d]."""
    s = dims(config)
    return _mlp(jax.random.fold_in(_experts_key(key, index), expert),
                s["d"], s["fe"])


def init_mixer(config: Dict[str, Any], key, index: int) -> Dict[str, Any]:
    """{"Attention_0": ...} for a layer in ``gqa_layers``, else
    {"DeltaRule_0": ...}."""
    s = dims(config)
    d = s["d"]
    ks = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, index), 7_000_001), 16)
    if index in s["gqa"]:
        h, kv, hd = s["h"], s["kv"], s["hd"]
        return {"Attention_0": {
            "wq": _normal(ks[0], (d, h, hd), d ** -0.5),
            "wk": _normal(ks[1], (d, kv, hd), d ** -0.5),
            "wv": _normal(ks[2], (d, kv, hd), d ** -0.5),
            "wo": _normal(ks[3], (h, hd, d), (h * hd) ** -0.5),
            "w_gate": _normal(ks[4], (d, h, hd), d ** -0.5)}}
    h, dd, k, r = s["dr_h"], s["dr_d"], s["conv"], s["rank"]
    # decay a head A = exp(A_log) in (1, 16) and a step size dt in
    # (0.001, 0.1) a channel before the low-rank projection moves it,
    # as the family's released code draws them
    dt = jnp.exp(jax.random.uniform(
        ks[10], (h, dd), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {"DeltaRule_0": {
        "wq": _normal(ks[0], (d, h, dd), d ** -0.5),
        "wk": _normal(ks[1], (d, h, dd), d ** -0.5),
        "wv": _normal(ks[2], (d, h, dd), d ** -0.5),
        "wo": _normal(ks[3], (h, dd, d), (h * dd) ** -0.5),
        "conv_q": _normal(ks[4], (k, h, dd), k ** -0.5),
        "conv_k": _normal(ks[5], (k, h, dd), k ** -0.5),
        "conv_v": _normal(ks[6], (k, h, dd), k ** -0.5),
        "w_f_down": _normal(ks[7], (d, r), d ** -0.5),
        "w_f_up": _normal(ks[8], (r, h, dd), r ** -0.5),
        "A_log": jnp.log(jax.random.uniform(ks[9], (h,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),    # softplus^-1(dt)
        "w_beta": _normal(ks[11], (d, h), d ** -0.5),
        "w_g_down": _normal(ks[12], (d, r), d ** -0.5),
        "w_g_up": _normal(ks[13], (r, h, dd), r ** -0.5),
        "o_norm": jnp.ones((dd,), jnp.float32)}}


def init_moe_outside_experts(config: Dict[str, Any], key, index: int
                             ) -> Dict[str, Any]:
    """The router (every output) and the shared expert of a layer."""
    s = dims(config)
    ks = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, index), 7_000_002), 2)
    out = {"router": _normal(ks[0], (s["d"], s["router"]), s["d"] ** -0.5)}
    if s["fs"]:
        out["shared"] = _mlp(ks[1], s["d"], s["fs"])
    return out


def init_layer(config: Dict[str, Any], key, index: int) -> Dict[str, Any]:
    """One block in float32, the held experts stacked [E_held, ...]."""
    s = dims(config)
    lo, hi = s["held"]
    experts = jax.vmap(lambda e: init_expert(config, key, index, e))(
        jnp.arange(lo, hi))
    return {
        **init_mixer(config, key, index),
        "MoE_0": {**init_moe_outside_experts(config, key, index), **experts},
        "RMSNorm_0": {"scale": jnp.ones((s["d"],), jnp.float32)},
        "RMSNorm_1": {"scale": jnp.ones((s["d"],), jnp.float32)},
    }


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


def decoder_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's keys as ``DecoderConfig`` arguments
    (without the layers, which the caller builds from ``gqa``)."""
    s = dims(config)
    run = config["run"]
    return dict(
        vocab_size=s["v"], d_model=s["d"], n_heads=s["h"],
        n_kv_heads=s["kv"], head_dim=s["hd"],
        rope_theta=(float(config["rope_theta"]) if config["use_rope"]
                    else None),
        attn_gate=bool(config["use_gqa_gate"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
        dr_heads=s["dr_h"], dr_key_dim=s["dr_d"], dr_value_dim=s["dr_d"],
        dr_conv=s["conv"], dr_rank=s["rank"],
        n_routed_experts=s["router"], experts_held=s["held"],
        experts_per_token=s["top_k"], d_expert=s["fe"], d_shared=s["fs"])
