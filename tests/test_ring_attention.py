"""Ring attention vs dense reference on the 8-device virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu.parallel.ring_attention import (
    dense_attention,
    ring_attention,
)


def _qkv(b, s, h, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype=dtype) for k in ks)


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_matches_dense(causal, sp, pallas):
    # "auto" resolves to off on CPU (interpret mode is for tests only),
    # so the pallas path is opted into explicitly here
    from torchsnapshot_tpu import knobs

    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    q, k, v = _qkv(2, 32, 4, 16)
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    with knobs.override_pallas_attention(int(pallas)):
        out = ring_attention(qs, ks, vs, mesh, axis_name="sp", causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    assert out.sharding.spec == P(None, "sp", None, None)


def test_ring_with_batch_axis():
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "sp"))
    q, k, v = _qkv(4, 16, 2, 8)
    sharding = NamedSharding(mesh, P("dp", "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    out = ring_attention(
        qs, ks, vs, mesh, axis_name="sp", causal=True, batch_axis="dp"
    )
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_ring_bf16():
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    q, k, v = _qkv(1, 32, 2, 16, dtype=jnp.bfloat16, seed=1)
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out).astype(np.float32),
        np.asarray(ref).astype(np.float32),
        rtol=3e-2,
        atol=3e-2,
    )


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
def test_ring_grad_flows(pallas):
    # differentiable end-to-end (scan + ppermute have transpose rules;
    # the pallas kernel differentiates through its custom_vjp)
    from torchsnapshot_tpu import knobs

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    q, k, v = _qkv(1, 16, 2, 8)
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    with knobs.override_pallas_attention(int(pallas)):
        g = jax.grad(loss)(qs, ks, vs)
    ref_g = jax.grad(lambda q, k, v: jnp.sum(dense_attention(q, k, v) ** 2))(
        q, k, v
    )
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g), rtol=1e-4, atol=1e-4)
