"""Sharded training step for the flagship transformer.

One jitted program: forward, loss, backward, optimizer update — with
input/param/optimizer shardings derived from the model's logical axis
metadata and the mesh rules (parallel/mesh.py). XLA's SPMD partitioner
inserts every collective (gradient all-reduce over data/fsdp, activation
all-gathers for tensor parallelism) — the TPU-native replacement for the
reference's torch.distributed DDP/FSDP wiring inside Train workers
(ray: python/ray/train/torch/, SURVEY.md §3.5).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax.linen import partitioning as nn_partitioning

import flax.linen as nn
from ray_tpu.models.transformer import (Transformer, TransformerConfig,
                                        cross_entropy_loss)
from ray_tpu.parallel import mesh as mesh_lib


def make_optimizer(learning_rate: float = 3e-4,
                   weight_decay: float = 0.01) -> optax.GradientTransformation:
    return optax.adamw(learning_rate, b1=0.9, b2=0.95,
                       weight_decay=weight_decay)


def abstract_state(config: TransformerConfig, batch_size: int, seq_len: int):
    """Shapes + logical specs without allocating anything."""
    import flax.core

    model = Transformer(config)
    tokens = jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32)
    rng = jax.random.PRNGKey(0)
    abs_vars = jax.eval_shape(model.init, rng, tokens)
    logical_specs = flax.core.unfreeze(
        nn_partitioning.get_axis_names(abs_vars["params_axes"]))
    return model, abs_vars, logical_specs


def mesh_shardings(mesh, logical_specs, rules=None):
    """flax logical PartitionSpecs -> NamedShardings on the mesh."""
    rules = rules if rules is not None else mesh_lib.default_logical_rules()
    return nn.logical_to_mesh_sharding(logical_specs, mesh, rules)


def init_sharded(config: TransformerConfig, mesh, batch_size: int,
                 seq_len: int, seed: int = 0, rules=None):
    """Initialize params DIRECTLY in their sharded layout (no host-side
    full copy): jit with out_shardings from the logical metadata."""
    rules = rules if rules is not None else mesh_lib.default_logical_rules()
    model, abs_vars, logical_specs = abstract_state(config, batch_size,
                                                   seq_len)
    shardings = mesh_shardings(mesh, logical_specs, rules)
    tokens = jnp.zeros((batch_size, seq_len), jnp.int32)

    def init_fn(rng, tokens):
        import flax.core

        with nn_partitioning.axis_rules(rules):
            return flax.core.unfreeze(model.init(rng, tokens)["params"])

    init_jit = jax.jit(init_fn, out_shardings=shardings)
    with mesh:
        params = init_jit(jax.random.PRNGKey(seed), tokens)
    return model, params, shardings


def make_train_step(model: Transformer,
                    optimizer: optax.GradientTransformation,
                    rules=None, param_shardings=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). batch = {"tokens": [B,S] int32} (next-token LM).

    param_shardings (from init_sharded) pins the updated params to their
    original layout — without the constraint the GSPMD partitioner is
    free to re-shard jit outputs, silently changing layouts step over
    step."""
    rules = rules if rules is not None else mesh_lib.default_logical_rules()

    def loss_fn(params, tokens):
        with nn_partitioning.axis_rules(rules):
            logits, mods = model.apply({"params": params},
                                       tokens[:, :-1],
                                       mutable=["intermediates"])
        with jax.named_scope("loss"):
            loss = cross_entropy_loss(logits, tokens[:, 1:])
        # MoE load balancing: consume every sown moe_aux term (a sown-
        # but-unconsumed aux would let the router collapse all tokens
        # onto one expert). Zero-cost for dense models (no leaves).
        aux_leaves = [
            a for a in jax.tree_util.tree_leaves(
                mods.get("intermediates", {}))
        ]
        if aux_leaves:
            loss = loss + 0.01 * sum(jnp.mean(a) for a in aux_leaves)
        return loss

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch["tokens"])
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if param_shardings is not None:
            params = jax.lax.with_sharding_constraint(params,
                                                      param_shardings)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_forward(model: Transformer, rules=None):
    rules = rules if rules is not None else mesh_lib.default_logical_rules()

    def forward(params, tokens):
        with nn_partitioning.axis_rules(rules):
            return model.apply({"params": params}, tokens)

    return forward
