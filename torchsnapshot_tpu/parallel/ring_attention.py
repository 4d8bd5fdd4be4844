"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context training shards the sequence dimension across devices; exact
attention then needs every (query, key) pair, which ring attention provides
by rotating K/V shards around the mesh axis with ``lax.ppermute`` while
accumulating the softmax **online** (flash-attention style running max /
denominator), so no device ever materializes the full attention matrix or
the full K/V.

On TPU the ppermute rides the ICI ring and overlaps with the per-block
matmuls; memory per device is O(seq_local) instead of O(seq_global).

The reference has no sequence-parallel code (SURVEY §5: absent — subsumed
by sharding metadata for *checkpointing* purposes); this module exists
because a TPU training framework needs the op itself, and its Q/K/V and
activation shardings are exactly what the checkpointer's ShardedArray path
persists and reshards.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _block_attend(q, k, v, q_offset, k_offset, causal, scale):
    """One (q_block, kv_block) interaction: returns (p @ v, row_max,
    row_sumexp) with positions offset into the global sequence."""
    # q: [b, sq, h, d]; k/v: [b, sk, h, d]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = scores.astype(jnp.float32)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(sq)[:, None]
        k_pos = k_offset + jnp.arange(sk)[None, :]
        mask = q_pos >= k_pos
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)  # [b, h, q]
    # guard fully-masked rows (m = -inf): exp(-inf - -inf) -> use 0
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    l = jnp.sum(p, axis=-1)  # [b, h, q]
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return pv, m_safe, l, jnp.isfinite(m)


def ring_attention_shard(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
):
    """Exact attention over sequence shards — call INSIDE shard_map.

    q/k/v: local shards ``[batch, seq_local, heads, head_dim]``, sequence
    sharded over ``axis_name``. Returns the local output shard.
    """
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    s_local = q.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    from .. import knobs

    if knobs.use_pallas_attention():
        from ..ops.flash_attention import flash_attention_partials

        attend = functools.partial(flash_attention_partials, vma=(axis_name,))
    else:
        attend = _block_attend

    # Derive the fresh carries FROM q so they inherit q's device-varying
    # axes (jax>=0.8 manual-axes typing requires scan carry in/out types,
    # including varying axes, to match exactly).
    zeros = (q * 0).astype(jnp.float32)  # [b, s, h, d]
    acc = zeros
    zrow = zeros.sum(-1).transpose(0, 2, 1)  # [b, h, s]
    m_run = zrow - jnp.inf
    l_run = zrow

    def step(carry, step_idx):
        acc, m_run, l_run, k_cur, v_cur = carry
        src = (my_idx - step_idx) % n  # whose block we currently hold
        pv, m_blk, l_blk, valid = attend(
            q, k_cur, v_cur,
            q_offset=my_idx * s_local,
            k_offset=src * s_local,
            causal=causal,
            scale=scale,
        )
        m_blk = jnp.where(valid, m_blk, -jnp.inf)
        m_new = jnp.maximum(m_run, m_blk)
        m_new_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        corr_run = jnp.where(
            jnp.isfinite(m_run), jnp.exp(m_run - m_new_safe), 0.0
        )
        corr_blk = jnp.where(
            jnp.isfinite(m_blk), jnp.exp(m_blk - m_new_safe), 0.0
        )
        l_new = l_run * corr_run + l_blk * corr_blk
        acc = (
            acc * corr_run.transpose(0, 2, 1)[..., None]
            + pv.astype(jnp.float32) * corr_blk.transpose(0, 2, 1)[..., None]
        )
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (acc, m_new, l_new, k_nxt, v_nxt), None

    (acc, m_run, l_run, _, _), _ = lax.scan(
        step, (acc, m_run, l_run, k, v), jnp.arange(n)
    )
    denom = jnp.where(l_run == 0.0, 1.0, l_run)
    out = acc / denom.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    batch_axis: Optional[str] = None,
):
    """Convenience wrapper: shard_map ``ring_attention_shard`` over
    ``mesh``, sequence dim sharded on ``axis_name`` (optionally batch on
    ``batch_axis``)."""
    from jax.sharding import PartitionSpec as P

    from .. import knobs

    spec = P(batch_axis, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(
            ring_attention_shard, axis_name=axis_name, causal=causal
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # pallas_call's interpret-mode discharge mixes varying and
        # unvarying operands in its internal dynamic_slices, which trips
        # shard_map's vma checker (jax suggests check_vma=False as the
        # workaround); the numerics are covered by the dense-oracle tests.
        # Gated exactly like the shard-level kernel selection so the
        # plain XLA path keeps vma checking.
        check_vma=not knobs.use_pallas_attention(),
    )
    return fn(q, k, v)


def dense_attention(q, k, v, causal: bool = True):
    """Single-device reference implementation (for tests)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = scores.astype(jnp.float32)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
