"""Pallas flash-attention kernel for the ring-attention hot path.

The ring step's compute is one (q_shard, kv_shard) block-attention
producing online-softmax partials (reference has no sequence-parallel
code — SURVEY §5; this belongs to the framework's own long-context
support, parallel/ring_attention.py).  The XLA fallback materializes the
full [b, h, sq, sk] score matrix in HBM; this kernel tiles it through
VMEM flash-attention style, so per-step memory is O(BQ x BK) instead of
O(sq x sk) and the matmuls stay on the MXU back-to-back with the
online-softmax VPU work.

Layout: grid over (batch*heads, q_blocks, kv_blocks) with kv innermost —
Mosaic walks it sequentially, so exactly one (BK, d) k/v block is
VMEM-resident at a time (VMEM cost is O(BQ·d + BK·d) regardless of local
sequence length) and the running (max, denominator, accumulator) triple
lives in f32 VMEM scratch across kv steps.  Sequence offsets (where this
shard's rows/cols sit in the global sequence, needed for causal masking
inside a ring step) arrive via scalar prefetch so the same compiled
kernel serves every ring position.

Outputs are the *partials* (pv, row_max, row_sumexp) rather than the
normalized attention, exactly the contract the ring accumulator needs;
``flash_attention`` also offers the standalone normalized form.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BQ = 128  # query rows per program
_BK = 128  # kv rows per inner step
_LANE = 128  # TPU lane width; head_dim padded up to a multiple

_NEG_INF = float("-inf")


def _use_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _block_scores(
    q_scaled, k_blk, jq, kb, q_offset, k_offset, sk_real, sq_real, causal
):
    """Masked scores for one (q-block, kv-block) pair — the ONE place
    the masking semantics live; forward and both backward kernels share
    it so the backward can never drift from the forward's convention.
    Returns (scores [BQ,BK] with -inf outside, mask, global k_idx)."""
    scores = jax.lax.dot_general(
        q_scaled, k_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    row = jax.lax.broadcasted_iota(jnp.int32, (_BQ, _BK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_BQ, _BK), 1)
    q_pos = q_offset + jq * _BQ + row
    k_idx = kb * _BK + col
    mask = jnp.logical_and(
        k_idx < sk_real, (jq * _BQ + row) < sq_real
    )
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_offset + k_idx)
    return jnp.where(mask, scores, _NEG_INF), mask, k_idx


def _attend_kernel(
    offs_ref,  # SMEM scalar prefetch: [q_offset, k_offset, sk_real, sq_real]
    q_ref,  # [1, BQ, D]      (revisited across the kv grid dim)
    k_ref,  # [1, BK, D]      (one kv block resident at a time)
    v_ref,  # [1, BK, D]
    out_ref,  # [1, BQ, D]     (index_map ignores kv dim → stays in VMEM)
    m_ref,  # [1, 1, BQ]  (row stats ride a [bh, 1, s] layout: a 2-D
    #  [bh, s] output would need a (1, BQ) block whose second-minor dim
    #  (1) is neither 8-divisible nor equal to bh — Mosaic rejects it;
    #  with the singleton axis the block's trailing dims (1, BQ) match
    #  (array dim, 128-multiple) and lowering is legal)
    l_ref,  # [1, 1, BQ]
    acc_sc,  # VMEM scratch [BQ, D]: running accumulator
    m_sc,  # VMEM scratch [BQ]: running row max
    l_sc,  # VMEM scratch [BQ]: running row sumexp
    *,
    causal: bool,
    scale: float,
):
    """One (q-block, kv-block) step of online-softmax attention.

    The kv sequence is the LAST grid dimension, so Mosaic iterates it
    innermost and sequentially; only one (BK, D) k/v block is resident in
    VMEM at a time (VMEM stays O(BQ·D + BK·D) however long the local
    sequence is), and the online (max, sumexp, acc) state lives in VMEM
    scratch, persisting across kv steps of the same q block."""
    q_offset = offs_ref[0]
    k_offset = offs_ref[1]
    sk_real = offs_ref[2]
    sq_real = offs_ref[3]
    jq = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, D]
    k_blk = k_ref[0].astype(jnp.float32)  # [BK, D]
    v_blk = v_ref[0].astype(jnp.float32)

    scores, mask, _ = _block_scores(
        q, k_blk, jq, kb, q_offset, k_offset, sk_real, sq_real, causal
    )

    m_run, l_run = m_sc[:], l_sc[:]
    m_blk = jnp.max(scores, axis=-1)  # [BQ]
    m_new = jnp.maximum(m_run, m_blk)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(scores - m_safe[:, None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - m_safe), 0.0)
    l_new = l_run * corr + jnp.sum(p, axis=-1)
    acc_new = acc_sc[:] * corr[:, None] + jax.lax.dot_general(
        p,
        v_blk,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_sc[:] = acc_new
    m_sc[:] = m_new
    l_sc[:] = l_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _emit():
        out_ref[0] = acc_sc[:]
        m_ref[0, 0] = m_sc[:]
        l_ref[0, 0] = l_sc[:]


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "vma"))
def _flash_partials_jit(
    q, k, v, offs, *, causal: bool, scale: float, vma: tuple = ()
):
    """q/k/v: [bh, s, d] (already merged batch*heads).  Returns f32
    partials (pv [bh, sq, d], m [bh, sq], l [bh, sq]).  ``vma`` names the
    shard_map axes the operands vary over (required by pallas_call under
    shard_map's varying-mesh-axes checking)."""
    bh, sq, d0 = q.shape
    sk = k.shape[1]
    qp = _pad_to(_pad_to(q, 1, _BQ), 2, _LANE)
    kp = _pad_to(_pad_to(k, 1, _BK), 2, _LANE)
    vp = _pad_to(_pad_to(v, 1, _BK), 2, _LANE)
    sq_pad, d = qp.shape[1], qp.shape[2]
    sk_pad = kp.shape[1]
    offs = jnp.concatenate(
        [offs.astype(jnp.int32), jnp.array([sk, sq], jnp.int32)]
    )

    grid = (bh, sq_pad // _BQ, sk_pad // _BK)
    kernel = functools.partial(_attend_kernel, causal=causal, scale=scale)
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, _BQ, d), lambda i, j, kb, offs: (i, j, 0)),
                pl.BlockSpec((1, _BK, d), lambda i, j, kb, offs: (i, kb, 0)),
                pl.BlockSpec((1, _BK, d), lambda i, j, kb, offs: (i, kb, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, _BQ, d), lambda i, j, kb, offs: (i, j, 0)),
                pl.BlockSpec((1, 1, _BQ), lambda i, j, kb, offs: (i, 0, j)),
                pl.BlockSpec((1, 1, _BQ), lambda i, j, kb, offs: (i, 0, j)),
            ],
            scratch_shapes=[
                pltpu.VMEM((_BQ, d), jnp.float32),
                pltpu.VMEM((_BQ,), jnp.float32),
                pltpu.VMEM((_BQ,), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(
                (bh, sq_pad, d), jnp.float32, vma=frozenset(vma)
            ),
            jax.ShapeDtypeStruct(
                (bh, 1, sq_pad), jnp.float32, vma=frozenset(vma)
            ),
            jax.ShapeDtypeStruct(
                (bh, 1, sq_pad), jnp.float32, vma=frozenset(vma)
            ),
        ],
        interpret=_use_interpret(),
    )(offs, qp, kp, vp)
    return out[:, :sq, :d0], m[:, 0, :sq], l[:, 0, :sq]


def _partials_impl(q, k, v, qo, ko, causal: bool, scale: float, vma: tuple):
    b, sq, h, d = q.shape
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
    offs = jnp.stack([qo, ko]).astype(jnp.int32)
    pv, m, l = _flash_partials_jit(
        to_bh(q), to_bh(k), to_bh(v), offs,
        causal=causal, scale=scale, vma=tuple(vma),
    )
    pv = pv.reshape(b, h, sq, d).transpose(0, 2, 1, 3).astype(v.dtype)
    m = m.reshape(b, h, sq)
    l = l.reshape(b, h, sq)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    return pv, m_safe, l


# --------------------------------------------------- pallas backward

def _bwd_dq_kernel(
    offs_ref,  # SMEM: [q_offset, k_offset, sk_real, sq_real]
    q_ref,  # [1, BQ, D]
    k_ref,  # [1, BK, D]
    v_ref,  # [1, BK, D]
    m_ref,  # [1, 1, BQ]  final row max (m_safe) from the forward
    gpv_ref,  # [1, BQ, D]  cotangent of pv (f32)
    gl_ref,  # [1, 1, BQ]  cotangent of l
    dq_ref,  # [1, BQ, D]  out (f32)
    amax_ref,  # [1, 1, BQ]  out (i32): global col of the row max
    dq_sc,  # VMEM [BQ, D] f32
    amax_sc,  # VMEM [BQ] i32 (-1 = none valid yet)
    runm_sc,  # VMEM [BQ] f32: running max of recomputed scores
    *,
    causal: bool,
    scale: float,
):
    """dq for one (q-block, kv-block) step, kv innermost.

    With the forward's final (m, l, pv) saved, the backward needs no
    online softmax: p_ij = exp(s_ij - m_i) directly, and the row term
    T_i collapses to gpv_i·pv_i + l_i·g_l_i (computed outside).  The
    g_m cotangent lands on the FIRST column attaining the row max — a
    valid subgradient of max; located here (the kv walk is sequential)
    and exported for the dk/dv kernel."""
    q_offset, k_offset = offs_ref[0], offs_ref[1]
    sk_real, sq_real = offs_ref[2], offs_ref[3]
    jq = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)
        amax_sc[:] = jnp.full_like(amax_sc, -1)
        runm_sc[:] = jnp.full_like(runm_sc, _NEG_INF)

    q = q_ref[0].astype(jnp.float32) * scale
    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    m = m_ref[0, 0]
    gpv = gpv_ref[0].astype(jnp.float32)
    gl = gl_ref[0, 0]

    scores, mask, k_idx = _block_scores(
        q, k_blk, jq, kb, q_offset, k_offset, sk_real, sq_real, causal
    )
    p = jnp.where(mask, jnp.exp(scores - m[:, None]), 0.0)
    gv = jax.lax.dot_general(  # gpv_i · v_j  -> [BQ, BK]
        gpv, v_blk, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (gv + gl[:, None])

    # Row-argmax of the RECOMPUTED scores, tracked as a running
    # (max, first-col) pair across kv blocks.  Never compared against
    # the saved m from the separately compiled forward — cross-kernel
    # float drift therefore cannot drop or misplace the g_m cotangent;
    # the δ contribution itself is applied OUTSIDE the kernels as an
    # XLA gather/scatter on this argmax (a valid subgradient of max).
    blk_max = jnp.max(scores, axis=-1)  # -inf when nothing valid
    big = jnp.int32(2**30)
    blk_first = jnp.min(
        jnp.where(
            jnp.logical_and(mask, scores == blk_max[:, None]), k_idx, big
        ),
        axis=-1,
    )
    better = jnp.logical_and(blk_first < big, blk_max > runm_sc[:])
    amax_sc[:] = jnp.where(better, blk_first, amax_sc[:])
    runm_sc[:] = jnp.maximum(runm_sc[:], blk_max)

    dq_sc[:] = dq_sc[:] + scale * jax.lax.dot_general(
        ds, k_blk, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kb == pl.num_programs(2) - 1)
    def _emit():
        dq_ref[0] = dq_sc[:]
        amax_ref[0, 0] = amax_sc[:]


def _bwd_dkv_kernel(
    offs_ref,
    q_ref,  # [1, BQ, D]
    k_ref,  # [1, BK, D]
    v_ref,  # [1, BK, D]
    m_ref,  # [1, 1, BQ]
    gpv_ref,  # [1, BQ, D]
    gl_ref,  # [1, 1, BQ]
    dk_ref,  # [1, BK, D] out (f32)
    dv_ref,  # [1, BK, D] out (f32)
    dk_sc,  # VMEM [BK, D] f32
    dv_sc,  # VMEM [BK, D] f32
    *,
    causal: bool,
    scale: float,
):
    """dk/dv for one (kv-block, q-block) step, q innermost (the
    accumulation axis for dk/dv is q, so the grid transposes)."""
    q_offset, k_offset = offs_ref[0], offs_ref[1]
    sk_real, sq_real = offs_ref[2], offs_ref[3]
    kb = pl.program_id(1)
    jq = pl.program_id(2)

    @pl.when(jq == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    q = q_ref[0].astype(jnp.float32) * scale
    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    m = m_ref[0, 0]
    gpv = gpv_ref[0].astype(jnp.float32)
    gl = gl_ref[0, 0]

    scores, mask, _ = _block_scores(
        q, k_blk, jq, kb, q_offset, k_offset, sk_real, sq_real, causal
    )
    p = jnp.where(mask, jnp.exp(scores - m[:, None]), 0.0)

    dv_sc[:] = dv_sc[:] + jax.lax.dot_general(  # p^T · gpv
        p, gpv, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    gv = jax.lax.dot_general(
        gpv, v_blk, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # the g_m δ term is applied outside the kernels (gather/scatter on
    # the dq kernel's exported argmax)
    ds = p * (gv + gl[:, None])
    # q is already pre-scaled above, so dk_j = Σ_i ds_ij (scale·q_i)
    # needs no extra factor (dq does: k is unscaled there)
    dk_sc[:] = dk_sc[:] + jax.lax.dot_general(  # ds^T · (scale·q)
        ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(jq == pl.num_programs(2) - 1)
    def _emit():
        dk_ref[0] = dk_sc[:]
        dv_ref[0] = dv_sc[:]


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "vma")
)
def _flash_bwd_jit(
    q, k, v, m, gpv, gl, offs, *, causal: bool, scale: float,
    vma: tuple = (),
):
    """q/k/v/gpv: [bh, s, d]; m/gl: [bh, sq].  Returns f32
    (dq [bh,sq,d], dk [bh,sk,d], dv [bh,sk,d], amax [bh,sq] i32) —
    flash-tiled backward (without the g_m δ term, which the caller
    applies from amax), per-step memory O(BQ·BK) like the forward."""
    bh, sq, d0 = q.shape
    sk = k.shape[1]
    qp = _pad_to(_pad_to(q, 1, _BQ), 2, _LANE)
    kp = _pad_to(_pad_to(k, 1, _BK), 2, _LANE)
    vp = _pad_to(_pad_to(v, 1, _BK), 2, _LANE)
    gpvp = _pad_to(_pad_to(gpv.astype(jnp.float32), 1, _BQ), 2, _LANE)
    mp = _pad_to(m, 1, _BQ)[:, None, :]    # [bh, 1, sq_pad]
    glp = _pad_to(gl, 1, _BQ)[:, None, :]  # [bh, 1, sq_pad]
    sq_pad, d = qp.shape[1], qp.shape[2]
    sk_pad = kp.shape[1]
    offs = jnp.concatenate(
        [offs.astype(jnp.int32), jnp.array([sk, sq], jnp.int32)]
    )
    vma = frozenset(vma)

    grid_a = (bh, sq_pad // _BQ, sk_pad // _BK)
    kern_a = functools.partial(_bwd_dq_kernel, causal=causal, scale=scale)
    dq, amax = pl.pallas_call(
        kern_a,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid_a,
            in_specs=[
                pl.BlockSpec((1, _BQ, d), lambda i, j, kb, o: (i, j, 0)),
                pl.BlockSpec((1, _BK, d), lambda i, j, kb, o: (i, kb, 0)),
                pl.BlockSpec((1, _BK, d), lambda i, j, kb, o: (i, kb, 0)),
                pl.BlockSpec((1, 1, _BQ), lambda i, j, kb, o: (i, 0, j)),
                pl.BlockSpec((1, _BQ, d), lambda i, j, kb, o: (i, j, 0)),
                pl.BlockSpec((1, 1, _BQ), lambda i, j, kb, o: (i, 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, _BQ, d), lambda i, j, kb, o: (i, j, 0)),
                pl.BlockSpec((1, 1, _BQ), lambda i, j, kb, o: (i, 0, j)),
            ],
            scratch_shapes=[
                pltpu.VMEM((_BQ, d), jnp.float32),
                pltpu.VMEM((_BQ,), jnp.int32),
                pltpu.VMEM((_BQ,), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_pad, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, 1, sq_pad), jnp.int32, vma=vma),
        ],
        interpret=_use_interpret(),
    )(offs, qp, kp, vp, mp, gpvp, glp)

    grid_b = (bh, sk_pad // _BK, sq_pad // _BQ)
    kern_b = functools.partial(
        _bwd_dkv_kernel, causal=causal, scale=scale
    )
    dk, dv = pl.pallas_call(
        kern_b,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid_b,
            in_specs=[
                pl.BlockSpec((1, _BQ, d), lambda i, kb, j, o: (i, j, 0)),
                pl.BlockSpec((1, _BK, d), lambda i, kb, j, o: (i, kb, 0)),
                pl.BlockSpec((1, _BK, d), lambda i, kb, j, o: (i, kb, 0)),
                pl.BlockSpec((1, 1, _BQ), lambda i, kb, j, o: (i, 0, j)),
                pl.BlockSpec((1, _BQ, d), lambda i, kb, j, o: (i, j, 0)),
                pl.BlockSpec((1, 1, _BQ), lambda i, kb, j, o: (i, 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, _BK, d), lambda i, kb, j, o: (i, kb, 0)),
                pl.BlockSpec((1, _BK, d), lambda i, kb, j, o: (i, kb, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((_BK, d), jnp.float32),
                pltpu.VMEM((_BK, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_pad, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, sk_pad, d), jnp.float32, vma=vma),
        ],
        interpret=_use_interpret(),
    )(offs, qp, kp, vp, mp, gpvp, glp)
    return (
        dq[:, :sq, :d0],
        dk[:, :sk, :d0],
        dv[:, :sk, :d0],
        amax[:, 0, :sq],
    )


def _flash_bwd(q, k, v, qo, ko, outs, cts, causal, scale, vma):
    """Pallas flash backward for the partials contract (pv, m, l)."""
    pv, m_safe, l = outs
    g_pv, g_m, g_l = cts
    b, sq, h, d = q.shape
    # T_i = gpv_i·pv_i + l_i·g_l_i collapses the row sum the standard
    # flash backward would recompute
    T = (
        jnp.einsum(
            "bshd,bshd->bhs",
            g_pv.astype(jnp.float32),
            pv.astype(jnp.float32),
        )
        + l * g_l
    )
    gmt = g_m.astype(jnp.float32) - T

    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * h, x.shape[1], x.shape[3]
    )
    flat = lambda x: x.reshape(b * h, x.shape[2])  # [b,h,s] -> [bh,s]
    offs = jnp.stack([qo, ko]).astype(jnp.int32)
    q_bh, k_bh, v_bh = to_bh(q), to_bh(k), to_bh(v)
    dq, dk, dv, amax = _flash_bwd_jit(
        q_bh, k_bh, v_bh,
        flat(m_safe), to_bh(g_pv), flat(g_l.astype(jnp.float32)),
        offs,
        causal=causal, scale=scale, vma=tuple(vma),
    )
    # g_m δ term, applied OUTSIDE the kernels on the dq kernel's
    # exported argmax (gather for dq, scatter-add for dk): a valid
    # subgradient of max with no cross-kernel float comparison to
    # drift on hardware.  Rows with no valid position keep zero.
    sk = k.shape[1]
    gmt_flat = flat(gmt)
    valid = amax >= 0
    gmt_eff = jnp.where(valid, gmt_flat, 0.0)  # [bh, sq]
    idx = jnp.clip(amax, 0, sk - 1)  # [bh, sq]
    k_at = jnp.take_along_axis(
        k_bh.astype(jnp.float32), idx[:, :, None], axis=1
    )  # [bh, sq, d]
    dq = dq + scale * gmt_eff[:, :, None] * k_at
    contrib = scale * gmt_eff[:, :, None] * q_bh.astype(jnp.float32)
    bh_idx = jnp.arange(b * h)[:, None]
    dk = dk.at[bh_idx, idx, :].add(contrib)

    back = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return (
        back(dq, sq).astype(q.dtype),
        back(dk, k.shape[1]).astype(k.dtype),
        back(dv, k.shape[1]).astype(v.dtype),
    )


@functools.lru_cache(maxsize=64)
def _make_diff_partials(causal: bool, scale: float, vma: tuple):
    """pallas_call has no autodiff rule; wrap the kernel in a custom_vjp.

    The backward is flash-tiled pallas too (_flash_bwd: O(BQ·BK)
    per-step memory, saved (m, l, pv) instead of an online pass) when
    the pallas knob resolves on; otherwise it recomputes the block pair
    with XLA ops (correct everywhere, O(sq·sk) score materialization)."""

    @jax.custom_vjp
    def f(q, k, v, qo, ko):
        return _partials_impl(q, k, v, qo, ko, causal, scale, vma)

    def fwd(q, k, v, qo, ko):
        out = _partials_impl(q, k, v, qo, ko, causal, scale, vma)
        return out, (q, k, v, qo, ko, out)

    def bwd(res, cts):
        q, k, v, qo, ko, outs = res
        from .. import knobs

        if knobs.use_pallas_attention():
            dq, dk, dv = _flash_bwd(
                q, k, v, qo, ko, outs, cts, causal, scale, vma
            )
        else:
            from ..parallel.ring_attention import _block_attend

            def xla_fn(q, k, v):
                pv, m_safe, l, _ = _block_attend(
                    q, k, v,
                    q_offset=qo, k_offset=ko, causal=causal, scale=scale,
                )
                return pv, m_safe, l

            _, vjp = jax.vjp(xla_fn, q, k, v)
            dq, dk, dv = vjp(cts)
        # integer offsets: cotangent type is float0
        zero0 = lambda x: np.zeros(x.shape, dtype=jax.dtypes.float0)
        return dq, dk, dv, zero0(qo), zero0(ko)

    f.defvjp(fwd, bwd)
    return f


def flash_attention_partials(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset,
    k_offset,
    causal: bool,
    scale: float,
    vma: tuple = (),
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Drop-in for ring_attention's ``_block_attend`` contract.

    q: [b, sq, h, d]; k/v: [b, sk, h, d].  Returns (pv [b, sq, h, d],
    m_safe [b, h, sq], l [b, h, sq], valid [b, h, sq]).  Pass the
    enclosing shard_map axis name(s) via ``vma`` when calling inside one.
    """
    # offsets stay integer end-to-end: float32 would round past 2^24,
    # silently shifting the causal boundary at very long contexts
    qo = jnp.asarray(q_offset, jnp.int32)
    ko = jnp.asarray(k_offset, jnp.int32)
    pv, m_safe, l = _make_diff_partials(causal, scale, tuple(vma))(
        q, k, v, qo, ko
    )
    # a fully-masked row has every softmax term zeroed → l == 0; any
    # unmasked row contributes exp(max - max) == 1 ≤ l
    valid = l > 0.0
    return pv, m_safe, l, valid


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True
) -> jax.Array:
    """Standalone normalized flash attention (single shard, no ring).

    q/k/v: [b, s, h, d] → [b, s, h, d]."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    pv, _, l, valid = flash_attention_partials(
        q, k, v, 0, 0, causal, scale
    )
    denom = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → 0 output
    out = pv.astype(jnp.float32) / denom.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)
