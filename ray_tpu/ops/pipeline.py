"""Pipeline parallelism — GPipe-style microbatching over the `pipe`
mesh axis.

The reference expresses pipelines as compiled actor DAGs with NCCL
channels (ray: python/ray/dag/, experimental/channel/); TPU-first the
whole pipeline is ONE jitted program: each device holds one stage's
params, activations circulate stage-to-stage with jax.lax.ppermute, and
the schedule is the classic M + n - 1 step loop (fill, steady state,
drain). XLA overlaps the ppermute with the next microbatch's compute.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp


def pipeline_local(stage_params, microbatches, *, stage_fn,
                   axis_name: str = "pipe"):
    """shard_map body. stage_params: THIS stage's params pytree.
    microbatches [M, mb, ...]: the full input on stage 0 (other stages
    ignore their copy). Returns [M, mb, ...] outputs, valid on every
    device (broadcast from the last stage)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    steps = M + n - 1
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]

    state = jnp.zeros_like(microbatches[0])
    out_buf = jnp.zeros((M,) + microbatches.shape[1:],
                        microbatches.dtype)

    def step(t, carry):
        state, out_buf = carry
        # stage 0 injects microbatch t (while any remain); others take
        # the activation handed over by the previous stage
        inject = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, M - 1), 0, keepdims=False)
        x_in = jnp.where(idx == 0, inject, state)
        y = stage_fn(stage_params, x_in)
        # last stage banks its result for microbatch (t - (n-1))
        done_idx = t - (n - 1)
        valid = jnp.logical_and(idx == n - 1, done_idx >= 0)
        updated = jax.lax.dynamic_update_index_in_dim(
            out_buf, y, jnp.maximum(done_idx, 0), 0)
        out_buf = jnp.where(valid, updated, out_buf)
        # hand activations to the next stage (ring; last->0 ignored)
        state = jax.lax.ppermute(y, axis_name, perm_fwd)
        return state, out_buf

    # fori_loop keeps ONE traced copy of stage_fn: a Python unroll would
    # inline it M+n-1 times and scale XLA compile time with the
    # microbatch count
    state, out_buf = jax.lax.fori_loop(0, steps, step, (state, out_buf))

    # broadcast the last stage's buffer to every device: out_buf is
    # zeros elsewhere, so a psum over the axis is a select+broadcast
    out_buf = jax.lax.psum(
        jnp.where(idx == n - 1, out_buf, jnp.zeros_like(out_buf)),
        axis_name)
    return out_buf


def pipeline_forward(stage_fn: Callable, stage_params, microbatches,
                     mesh, axis_name: str = "pipe"):
    """Global entry. stage_params: pytree whose leaves have a leading
    STAGE axis of size n (stage i's slice lives on pipe-device i);
    microbatches [M, mb, ...] replicated in. Output [M, mb, ...]
    replicated (every stage ends with the final result).

    Differentiable: grads flow back through the ppermute chain, so one
    jitted train step covers fwd+bwd across stages."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.collectives import shard_map_norep

    fn = functools.partial(pipeline_local, stage_fn=stage_fn,
                           axis_name=axis_name)
    param_specs = jax.tree_util.tree_map(
        lambda _: P(axis_name), stage_params)

    # shard_map hands each device its stage's slice with a leading axis
    # of size 1; the body drops it before running the stage
    def body(params, mb):
        params = jax.tree_util.tree_map(lambda x: x[0], params)
        return fn(params, mb)

    return shard_map_norep(body, mesh=mesh,
                           in_specs=(param_specs, P()),
                           out_specs=P())(stage_params, microbatches)
