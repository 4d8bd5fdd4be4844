"""Pallas flash-attention kernel vs the dense XLA oracle.

Runs in interpret mode on CPU (flash_attention is called directly here,
bypassing the knob — which resolves "auto" to OFF on CPU so production
CPU runs never pay interpret-mode cost); the same kernel compiles for
TPU via Mosaic, where "auto" is on (chip_smoke.py compiles and checks it
on the chip).
Oracle: dense_attention / _block_attend in parallel/ring_attention.py.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from torchsnapshot_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_partials,
)
from torchsnapshot_tpu.parallel.ring_attention import (
    _block_attend,
    dense_attention,
)

def _qkv(b, s, h, d, seed=0, dtype=jnp.float32, sk=None):
    rng = np.random.default_rng(seed)
    mk = lambda sl: jnp.asarray(
        rng.standard_normal((b, sl, h, d)), dtype
    )
    return mk(s), mk(sk or s), mk(sk or s)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "shape",
    [(1, 128, 2, 64), (2, 192, 4, 48), (1, 300, 1, 128)],
    ids=["aligned", "unaligned", "odd-seq"],
)
def test_matches_dense(causal, shape):
    q, k, v = _qkv(*shape)
    out = flash_attention(q, k, v, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_partials_match_block_attend_with_offsets():
    # ring-step semantics: q rows sit at global offset 256, k at 128
    q, k, v = _qkv(1, 128, 2, 64, seed=3, sk=256)
    scale = 1.0 / 8.0
    got = flash_attention_partials(q, k, v, 256, 128, True, scale)
    want = _block_attend(
        q, k, v, q_offset=256, k_offset=128, causal=True, scale=scale
    )
    for g, w, name in zip(got, want, ("pv", "m", "l", "valid")):
        np.testing.assert_allclose(
            np.asarray(g, dtype=np.float32),
            np.asarray(w, dtype=np.float32),
            rtol=2e-5,
            atol=2e-5,
            err_msg=name,
        )


def test_fully_masked_rows_are_invalid():
    # q block entirely BEFORE the k block in the global sequence: with
    # causal masking nothing attends; valid must be all-False and the
    # normalized output zero (matches _block_attend's convention)
    q, k, v = _qkv(1, 128, 1, 64, seed=5)
    got = flash_attention_partials(q, k, v, 0, 4096, True, 0.125)
    assert not bool(np.asarray(got[3]).any())
    np.testing.assert_array_equal(np.asarray(got[2]), 0.0)


def test_bf16_io_f32_accumulation():
    q, k, v = _qkv(1, 256, 2, 128, seed=7, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        rtol=5e-2,
        atol=5e-2,
    )


def test_grads_flow_through_custom_vjp():
    q, k, v = _qkv(1, 128, 1, 32, seed=9)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
            err_msg=f"d{name}",
        )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("offsets", [(0, 0), (256, 128)])
def test_pallas_backward_matches_xla_backward(causal, offsets):
    """The flash-tiled pallas backward (saved m/l/pv, first-argmax g_m
    subgradient) must match the XLA-recompute backward on the full
    partials vjp — including cotangents for m and l, which the ring
    accumulator produces."""
    from torchsnapshot_tpu import knobs

    qo, ko = offsets
    q, k, v = _qkv(2, 256, 2, 64, seed=3, sk=384)
    rng = np.random.default_rng(7)

    def partials(q, k, v):
        pv, m, l, _ = flash_attention_partials(
            q, k, v, qo, ko, causal, scale=0.125
        )
        return pv, m, l

    pv, m, l = partials(q, k, v)
    cts = (
        jnp.asarray(rng.standard_normal(pv.shape), pv.dtype),
        jnp.asarray(rng.standard_normal(m.shape), m.dtype),
        jnp.asarray(rng.standard_normal(l.shape), l.dtype),
    )

    grads = {}
    for mode in ("1", "0"):  # pallas bwd vs XLA-recompute bwd
        with knobs.override_pallas_attention(mode):
            _, vjp = jax.vjp(partials, q, k, v)
            grads[mode] = vjp(cts)
    for a, b, name in zip(grads["1"], grads["0"], "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} (causal={causal}, offsets={offsets})",
        )


def test_pallas_backward_bf16_and_ragged():
    """bf16 operands + sequence lengths that don't divide the block
    size (padding rows/cols must contribute zero gradient).

    No m-cotangent here: the g_m subgradient lands on the argmax
    COLUMN, and with bf16 inputs the two backends' score arithmetic can
    legitimately disagree about which column that is — both answers are
    valid subgradients but not elementwise-comparable.  The f32 parity
    test above covers g_m (identical f32 arithmetic on both paths)."""
    from torchsnapshot_tpu import knobs

    q, k, v = _qkv(1, 200, 2, 48, seed=11, dtype=jnp.bfloat16, sk=136)

    def loss(q, k, v):
        pv, m, l, _ = flash_attention_partials(
            q, k, v, 0, 0, True, scale=0.2
        )
        return (
            jnp.sum(pv.astype(jnp.float32) ** 2)
            + jnp.sum(l * 0.25)
        )

    grads = {}
    for mode in ("1", "0"):
        with knobs.override_pallas_attention(mode):
            grads[mode] = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # bf16 rounding enters the two backwards at different points (the
    # XLA recompute scores in bf16, the kernel in f32), so elementwise
    # parity between them is not meaningful — instead require the
    # pallas backward to be at least as CLOSE to the f32 ground truth
    # as the XLA backward is (plus slack), per input
    f32 = lambda x: x.astype(jnp.float32)
    with knobs.override_pallas_attention("0"):
        truth = jax.grad(loss, argnums=(0, 1, 2))(f32(q), f32(k), f32(v))
    for a, b, t, name in zip(grads["1"], grads["0"], truth, "qkv"):
        assert a.dtype == b.dtype == jnp.bfloat16
        t = np.asarray(t, np.float32)
        err_pallas = np.linalg.norm(np.asarray(a, np.float32) - t)
        err_xla = np.linalg.norm(np.asarray(b, np.float32) - t)
        assert err_pallas <= 2.0 * err_xla + 1e-3, (
            name, err_pallas, err_xla,
        )
