"""Random weights from --seed, made by the benchmark and handed to the
program and to the reference alike.

The tree has the layout ``ray_tpu.models.transformer.Transformer``
keeps its parameters in (that layout is the program's interface: the
train step and the serving engine both take it as an argument). The
values are the benchmark's own: nothing here calls the program's
initialisers, so the reference can remake the very same numbers from
the seed after the program's state is gone, whole or one layer at a
time.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the tree needs, under short names, from a
    configuration file's Hugging Face keys."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    hd = int(config.get("head_dim") or d // h)
    return {"d": d, "h": h, "kv": int(config["num_key_value_heads"]),
            "hd": hd, "ff": int(config["intermediate_size"]),
            "v": int(config["vocab_size"]),
            "layers": int(config["num_hidden_layers"])}


def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def init_embedding(config: Dict[str, Any], key) -> jnp.ndarray:
    s = dims(config)
    return _normal(jax.random.fold_in(key, 1_000_003), (s["v"], s["d"]),
                   float(config.get("initializer_range", 0.02)))


def init_layer(config: Dict[str, Any], key, index: int) -> Dict[str, Any]:
    """One block's parameters in float32, from ``key`` and the block's
    index alone: the reference calls this layer by layer."""
    s = dims(config)
    d, h, kv, hd, ff = s["d"], s["h"], s["kv"], s["hd"], s["ff"]
    ks = jax.random.split(jax.random.fold_in(key, index), 7)
    return {
        "Attention_0": {
            "wq": _normal(ks[0], (d, h, hd), d ** -0.5),
            "wk": _normal(ks[1], (d, kv, hd), d ** -0.5),
            "wv": _normal(ks[2], (d, kv, hd), d ** -0.5),
            "wo": _normal(ks[3], (h, hd, d), (h * hd) ** -0.5),
        },
        "MLP_0": {
            "w_gate": _normal(ks[4], (d, ff), d ** -0.5),
            "w_up": _normal(ks[5], (d, ff), d ** -0.5),
            "w_down": _normal(ks[6], (ff, d), ff ** -0.5),
        },
        "RMSNorm_0": {"scale": jnp.ones((d,), jnp.float32)},
        "RMSNorm_1": {"scale": jnp.ones((d,), jnp.float32)},
    }


def init_params(config: Dict[str, Any], key, dtype=jnp.float32
                ) -> Dict[str, Any]:
    """The whole tree. Call under ``jax.jit`` so that it is made on the
    device in one program; ``dtype`` is the type the weights are held
    in (values are drawn in float32 and rounded once)."""
    s = dims(config)
    tree: Dict[str, Any] = {
        "embedding": init_embedding(config, key),
        "final_norm": {"scale": jnp.ones((s["d"],), jnp.float32)},
    }
    for i in range(s["layers"]):
        tree[f"layer_{i}"] = init_layer(config, key, i)
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def seed_key(seed: int):
    """--seed may exceed 32 signed bits: fold it into a key in two
    halves instead of handing it to PRNGKey."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _named(tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(x)
            for path, x in flat}


def _l2(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _norms_jit(tree):
    return jax.tree_util.tree_map(_l2, tree)


def leaf_norms(tree) -> Dict[str, float]:
    """{'layer_0/MLP_0/w_up': l2 norm, ...} of a tree of arrays."""
    return _named(_norms_jit(tree))


def param_change_norms(config: Dict[str, Any], seed: int, params
                       ) -> Dict[str, float]:
    """Per-leaf norm of ``params`` minus the seed's weights, the
    latter remade inside the program that takes the norms (no second
    copy of the weights is kept)."""
    @jax.jit
    def norms(p, key):
        p0 = init_params(config, key, jnp.float32)
        return jax.tree_util.tree_map(
            lambda a, b: _l2(a.astype(jnp.float32) - b), p, p0)

    return _named(norms(params, seed_key(seed)))
