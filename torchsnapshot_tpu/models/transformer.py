"""Flagship benchmark model: a llama-style decoder-only transformer in flax.

The reference validates its checkpointer against real workloads — a 1.9B
FSDP transformer (upstream torchsnapshot's benchmarks/fsdp/main.py:36-43)
and DDP ResNet (its benchmarks/ddp) — so this repo bundles an equivalent TPU-native workload:
float32 params (flax's default ``param_dtype``) computed in bf16, RMSNorm
+ rotary + SwiGLU blocks, `jax.checkpoint` remat on each block, and a
pjit-able train step whose params/optimizer state carry real dp/tp
NamedShardings for the checkpointer to exercise.  With adamw the train
state is 12 bytes a parameter.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    d_ff: int = 11008
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @staticmethod
    def tiny() -> "TransformerConfig":
        return TransformerConfig(
            vocab=256, d_model=64, n_layers=2, n_heads=4, d_ff=128, max_seq=64
        )


def _rope(x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    # x: [b, s, h, hd]
    hd = x.shape[-1]
    half = hd // 2
    freq = 1.0 / (10000 ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freq  # [b, s, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * scale


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        hd = cfg.d_model // cfg.n_heads
        dense = lambda name: nn.Dense(  # noqa: E731
            cfg.d_model, use_bias=False, dtype=cfg.dtype, name=name
        )
        q = dense("wq")(x).reshape(*x.shape[:2], cfg.n_heads, hd)
        k = dense("wk")(x).reshape(*x.shape[:2], cfg.n_heads, hd)
        v = dense("wv")(x).reshape(*x.shape[:2], cfg.n_heads, hd)
        q, k = _rope(q, positions), _rope(k, positions)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(hd).astype(
            cfg.dtype
        )
        seq = x.shape[1]
        mask = jnp.tril(jnp.ones((seq, seq), dtype=bool))
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
            cfg.dtype
        )
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        out = out.reshape(*x.shape[:2], cfg.d_model)
        return nn.Dense(
            cfg.d_model, use_bias=False, dtype=cfg.dtype, name="wo"
        )(out)


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="gate")(x)
        up = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="w1")(x)
        return nn.Dense(
            cfg.d_model, use_bias=False, dtype=cfg.dtype, name="w2"
        )(nn.silu(gate) * up)


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        x = x + Attention(self.cfg, name="attn")(
            RMSNorm(name="norm1")(x), positions
        )
        x = x + MLP(self.cfg, name="mlp")(RMSNorm(name="norm2")(x))
        return x


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        x = nn.Embed(
            cfg.vocab, cfg.d_model, dtype=cfg.dtype, name="embed"
        )(tokens)
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1]), tokens.shape
        )
        block = Block
        if cfg.remat:
            block = nn.remat(Block)  # trade FLOPs for HBM
        for i in range(cfg.n_layers):
            x = block(cfg, name=f"layer{i}")(x, positions)
        x = RMSNorm(name="norm_f")(x)
        return nn.Dense(
            cfg.vocab, use_bias=False, dtype=jnp.float32, name="lm_head"
        )(x)


def make_train_state(
    cfg: TransformerConfig, seed: int = 0, mesh=None
):
    """Init params (+ optax adamw state); optionally place on a mesh per
    the tp/dp rules so the checkpointer sees real shardings."""
    import optax
    from flax.training import train_state

    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, min(cfg.max_seq, 8)), dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), tokens)
    tx = optax.adamw(3e-4, weight_decay=0.01)
    ts = train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=tx
    )
    if mesh is not None:
        from ..parallel.mesh import shard_pytree

        ts = shard_pytree(ts, mesh)
    return ts


def loss_fn(params, apply_fn, tokens):
    logits = apply_fn(params, tokens[:, :-1])
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)


def train_step(ts, tokens):
    """One LM training step — jit/pjit this over a mesh for the multi-chip
    path (data batch sharded over 'dp', params per the tp rules)."""
    loss, grads = jax.value_and_grad(loss_fn)(
        ts.params, ts.apply_fn, tokens
    )
    return ts.apply_gradients(grads=grads), loss
