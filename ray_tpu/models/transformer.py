"""Flagship model: decoder-only transformer (LLaMA-family shape).

TPU-first design notes:
  - bfloat16 activations/weights compute (params kept f32 for the
    optimizer), so matmuls land on the MXU at full rate;
  - GQA attention with RoPE, RMSNorm, SwiGLU — the modern decoder block;
  - every parameter/activation carries LOGICAL axis names via flax
    partitioning metadata; parallel/mesh.py maps them onto the device
    mesh (dp/fsdp/tp/sp), and the XLA SPMD partitioner inserts the ICI
    collectives — no hand-written communication in model code;
  - static shapes and lax-friendly control flow only: the whole train
    step jits into a single program.

The reference has no model zoo of its own — models run inside Train/
RLlib workers (ray: python/ray/train/ torch integration). Here the model
family is first-class because the framework's compute path is jitted TPU
programs rather than opaque torch actors.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

from ray_tpu.ops.flash import flash_attention_bshk, flash_supported
from ray_tpu.ops.rope import rope

param_with_axes = nn_partitioning.param_with_axes
with_sharding_constraint = nn_partitioning.with_sharding_constraint


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1408
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False          # jax.checkpoint each block (HBM vs FLOPs)
    # selective checkpointing: save matmul outputs, recompute only the
    # cheap elementwise ops — most of remat's memory win at a fraction
    # of its recompute cost ("dots" = jax.checkpoint_policies
    # .dots_with_no_batch_dims_saveable; "full" recomputes everything)
    remat_policy: str = "full"   # "full" | "dots"
    # sequence/context parallelism: ring attention over the mesh's `seq`
    # axis (ray_tpu/ops/ring_attention.py). Takes effect when the model
    # runs under parallel.mesh.use_mesh(mesh) with seq > 1.
    ring_attention: bool = False
    # mixture-of-experts: replace the dense MLP with a switch-routed
    # expert layer (ray_tpu/ops/moe.py); all_to_all dispatch engages
    # under a mesh whose `expert` axis > 1
    moe: bool = False
    moe_num_experts: int = 8
    moe_capacity_factor: float = 1.25
    # fused flash attention (Pallas, jax.experimental.pallas.ops.tpu):
    # never materializes the [S,S] score matrix — the HBM-traffic fix
    # for the single-chip train path. "auto" = on TPU backends for the
    # causal/unmasked/no-ring case; "off" forces the einsum path.
    flash_attention: str = "auto"   # "auto" | "off"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def tiny() -> "TransformerConfig":
        return TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                 n_heads=4, n_kv_heads=2, d_ff=128,
                                 max_seq_len=128)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = param_with_axes("scale", nn.initializers.ones,
                                (x.shape[-1],), self.param_dtype,
                                axes=("act_embed",))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        y = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (y * scale).astype(x.dtype)


class Attention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        cfg = self.config
        hd = cfg.head_dim
        wq = param_with_axes("wq", nn.initializers.lecun_normal(),
                             (cfg.d_model, cfg.n_heads, hd),
                             cfg.param_dtype, axes=("embed", "heads", "head_dim"))
        wk = param_with_axes("wk", nn.initializers.lecun_normal(),
                             (cfg.d_model, cfg.n_kv_heads, hd),
                             cfg.param_dtype,
                             axes=("embed", "kv_heads", "head_dim"))
        wv = param_with_axes("wv", nn.initializers.lecun_normal(),
                             (cfg.d_model, cfg.n_kv_heads, hd),
                             cfg.param_dtype,
                             axes=("embed", "kv_heads", "head_dim"))
        wo = param_with_axes("wo", nn.initializers.lecun_normal(),
                             (cfg.n_heads, hd, cfg.d_model),
                             cfg.param_dtype, axes=("heads", "head_dim", "embed"))

        q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(cfg.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, wk.astype(cfg.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, wv.astype(cfg.dtype))
        q = with_sharding_constraint(q, ("batch", "act_seq", "heads",
                                         "head_dim"))
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

        ring_mesh = None
        if cfg.ring_attention and mask is None:
            # ring path implements CAUSAL attention only: an explicit
            # mask (padding etc.) falls back to the standard path rather
            # than being silently ignored
            from ray_tpu.parallel import mesh as mesh_lib

            m = mesh_lib.current_mesh()
            if m is not None and m.shape.get(mesh_lib.AXIS_SEQ, 1) > 1:
                ring_mesh = m
        if ring_mesh is not None:
            # sequence parallelism: blockwise ring attention, UNREPEATED
            # GQA KV rotated over the seq axis (repeat happens inside the
            # per-step block so ICI traffic stays at n_kv_heads size)
            from ray_tpu.ops.ring_attention import ring_attention_sharded

            out = ring_attention_sharded(q, k, v, ring_mesh, causal=True)
        elif (mask is None and cfg.flash_attention != "off"
              and flash_supported(hd)):
            rep = cfg.n_heads // cfg.n_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            out = flash_attention_bshk(q, k, v, causal=True)
        else:
            # GQA: repeat kv heads up to query heads
            rep = cfg.n_heads // cfg.n_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            if mask is None:
                s = x.shape[1]
                mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
            scores = jnp.einsum("bshk,bthk->bhst", q, k) / jnp.sqrt(hd)
            scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bhst,bthk->bshk", probs, v)
        out = jnp.einsum("bshk,hkd->bsd", out, wo.astype(cfg.dtype))
        return with_sharding_constraint(out, ("batch", "act_seq",
                                              "act_embed"))


class MLP(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w_gate = param_with_axes("w_gate", nn.initializers.lecun_normal(),
                                 (cfg.d_model, cfg.d_ff), cfg.param_dtype,
                                 axes=("embed", "mlp"))
        w_up = param_with_axes("w_up", nn.initializers.lecun_normal(),
                               (cfg.d_model, cfg.d_ff), cfg.param_dtype,
                               axes=("embed", "mlp"))
        w_down = param_with_axes("w_down", nn.initializers.lecun_normal(),
                                 (cfg.d_ff, cfg.d_model), cfg.param_dtype,
                                 axes=("mlp", "embed"))
        h = (jax.nn.silu(x @ w_gate.astype(cfg.dtype))
             * (x @ w_up.astype(cfg.dtype)))
        return h @ w_down.astype(cfg.dtype)


class MoEMLP(nn.Module):
    """Switch-routed expert MLP (ops/moe.py): top-1 capacity routing,
    all_to_all token dispatch when the active mesh has expert > 1, the
    single-device reference path otherwise. The load-balancing aux loss
    is sown under ("intermediates", "moe_aux")."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        E = cfg.moe_num_experts
        d, f = cfg.d_model, cfg.d_ff
        w_router = param_with_axes(
            "router", nn.initializers.lecun_normal(), (d, E),
            cfg.param_dtype, axes=("embed", "experts"))
        w_in = param_with_axes(
            "w_in", nn.initializers.lecun_normal(), (E, d, f),
            cfg.param_dtype, axes=("experts", "embed", "mlp"))
        w_out = param_with_axes(
            "w_out", nn.initializers.lecun_normal(), (E, f, d),
            cfg.param_dtype, axes=("experts", "mlp", "embed"))

        from ray_tpu.ops.moe import moe_ffn_reference, moe_ffn_sharded
        from ray_tpu.parallel import mesh as mesh_lib

        b, s, _ = x.shape
        tokens = x.reshape(b * s, d).astype(cfg.dtype)
        wr = w_router.astype(cfg.dtype)
        wi = w_in.astype(cfg.dtype)
        wo = w_out.astype(cfg.dtype)
        m = mesh_lib.current_mesh()
        if m is not None and m.shape.get(mesh_lib.AXIS_EXPERT, 1) > 1:
            n_exp = m.shape[mesh_lib.AXIS_EXPERT]
            t = tokens.shape[0]
            pad = (-t) % n_exp
            if pad:
                # token rows shard over the expert axis: pad to a
                # multiple (padding rows route and get sliced off)
                tokens = jnp.concatenate(
                    [tokens, jnp.zeros((pad, d), tokens.dtype)])
            y, aux = moe_ffn_sharded(tokens, wr, wi, wo, m,
                                     cfg.moe_capacity_factor)
            if pad:
                y = y[:t]
        else:
            y, aux = moe_ffn_reference(tokens, wr, wi, wo,
                                       cfg.moe_capacity_factor)
        self.sow("intermediates", "moe_aux", aux)
        return y.reshape(b, s, d).astype(cfg.dtype)


class Block(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        cfg = self.config
        x = x + Attention(cfg)(RMSNorm(cfg.norm_eps, cfg.param_dtype)(x), positions, mask)
        mlp = MoEMLP(cfg) if cfg.moe else MLP(cfg)
        x = x + mlp(RMSNorm(cfg.norm_eps, cfg.param_dtype)(x))
        return with_sharding_constraint(x, ("batch", "act_seq", "act_embed"))


class Transformer(nn.Module):
    """Causal LM: tokens [B, S] int32 -> logits [B, S, V]."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        embed = param_with_axes("embedding", nn.initializers.normal(0.02),
                                (cfg.vocab_size, cfg.d_model),
                                cfg.param_dtype, axes=("vocab", "embed"))
        x = embed.astype(cfg.dtype)[tokens]
        x = with_sharding_constraint(x, ("batch", "act_seq", "act_embed"))

        s = tokens.shape[1]
        positions = jnp.arange(s)[None, :]
        # mask=None means CAUSAL — built on demand by the standard path;
        # the ring-attention path handles causality via global offsets
        mask = None

        block = Block
        if cfg.remat:
            policy = None
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.\
                    dots_with_no_batch_dims_saveable
            block = nn.remat(Block, static_argnums=(), policy=policy)
        for i in range(cfg.n_layers):
            x = block(cfg, name=f"layer_{i}")(x, positions, mask)

        x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
        # logits stay in compute dtype: an f32 [B,S,V] copy costs ~2x
        # the HBM traffic of the lm-head matmul itself; the loss casts
        # inside its reductions (XLA fuses the cast into them)
        return jnp.einsum("bsd,vd->bsv", x, embed.astype(cfg.dtype))


def cross_entropy_loss(logits: jnp.ndarray, targets: jnp.ndarray,
                       mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean next-token cross entropy. logits [B,S,V], targets [B,S].

    logsumexp formulation: nll = lse(logits) - logits[target]. Unlike
    log_softmax, this never materializes a full [B,S,V] f32 result —
    the cast fuses into the reduction, and backward recomputes softmax
    from the (bf16) logits.
    """
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None],
                                 axis=-1)[..., 0].astype(jnp.float32)
    nll = lse - picked
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()
