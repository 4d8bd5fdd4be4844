"""Static Mosaic-lowering checks for the pallas flash kernels, on CPU.

Interpret mode (how CI exercises kernel NUMERICS) never runs the Mosaic
lowering pipeline, so a kernel could be numerically perfect yet
unlowerable on real TPU hardware — exactly what happened: the row-stat
outputs used (1, BQ) blocks whose second-minor dim (1) is neither
8-divisible nor equal to the array dim, and Mosaic rejects that at
lowering time (VERDICT r4 #6 asked for precisely this check; the probe
found a real bug on its first run).

``jax.export`` cross-platform lowering runs the FULL jax-side Mosaic
pipeline on a CPU-only box — `lower_jaxpr_to_module` builds and
verifies the Mosaic MLIR and serializes it into `tpu_custom_call`.
What remains hardware-only is the XLA TPU compiler consuming that
module (chip_smoke.py's flash-attention leg compiles and runs it on the
chip).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax import export  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from torchsnapshot_tpu import knobs  # noqa: E402
from torchsnapshot_tpu.ops import flash_attention as fa  # noqa: E402


def _clear_kernel_caches():
    # ``interpret=_use_interpret()`` is evaluated at TRACE time, so a
    # trace made while this fixture forces compiled lowering would be
    # replayed (with interpret=False baked in) by later interpret-mode
    # tests sharing shapes — clear both the jit trace cache and the
    # custom_vjp lru on entry AND exit
    fa._flash_partials_jit.clear_cache()
    fa._flash_bwd_jit.clear_cache()
    fa._make_diff_partials.cache_clear()


@pytest.fixture
def _force_compiled_lowering(monkeypatch):
    """Lowering for platform 'tpu' must take the compiled (Mosaic)
    path, not interpret — that's the entire point of the check."""
    _clear_kernel_caches()
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    yield
    _clear_kernel_caches()


def _export_tpu(fn, *args, **jit_kwargs):
    return export.export(jax.jit(fn, **jit_kwargs), platforms=["tpu"])(*args)


@pytest.mark.parametrize(
    "b,s,h,d,causal",
    [(1, 512, 2, 128, True), (2, 1024, 4, 128, False), (1, 384, 1, 64, True)],
)
def test_forward_kernel_lowers_under_mosaic(_force_compiled_lowering, b, s, h, d, causal):
    q = jnp.zeros((b, s, h, d), jnp.bfloat16)
    with knobs.override_pallas_attention("1"):
        exp = _export_tpu(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=causal),
            q, q, q,
        )
    txt = exp.mlir_module()
    assert txt.count("tpu_custom_call") == 1, "kernel did not lower to Mosaic"


def test_backward_kernels_lower_under_mosaic(_force_compiled_lowering):
    b, s, h, d = 1, 512, 2, 128
    q = jnp.zeros((b, s, h, d), jnp.bfloat16)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    with knobs.override_pallas_attention("1"):
        exp = _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    # forward (for residuals) + dq kernel + dkv kernel
    assert exp.mlir_module().count("tpu_custom_call") == 3


def test_partials_contract_lowers_with_offsets(_force_compiled_lowering):
    # the ring-attention entry point: offsets ride scalar prefetch
    b, s, h, d = 1, 256, 2, 128
    q = jnp.zeros((b, s, h, d), jnp.bfloat16)

    def f(q, k, v):
        pv, m, l, valid = fa.flash_attention_partials(
            q, k, v, q_offset=256, k_offset=0, causal=True,
            scale=1.0 / d ** 0.5,
        )
        return pv, m, l, valid

    with knobs.override_pallas_attention("1"):
        exp = _export_tpu(f, q, q, q)
    assert "tpu_custom_call" in exp.mlir_module()


def test_ring_attention_lowers_for_tpu_mesh(_force_compiled_lowering):
    """The MULTI-CHIP long-context path: ring attention (shard_map over
    an 8-device sp mesh, flash kernel inside each shard) must lower for
    TPU — Mosaic custom call for the kernel plus collective-permutes
    for the ring.  Exported cross-platform from the CPU box, so the
    whole sp-parallel program is lowering-validated without hardware."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu.parallel import ring_attention as ra

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = Mesh(np.array(devs[:8]).reshape(8), ("sp",))
    b, s, h, d = 1, 8 * 256, 2, 128
    q = jnp.zeros((b, s, h, d), jnp.bfloat16)
    sh = NamedSharding(mesh, P(None, "sp", None, None))

    def f(q, k, v):
        return ra.ring_attention(
            q, k, v, mesh=mesh, axis_name="sp", causal=True
        )

    with knobs.override_pallas_attention("1"):
        exp = _export_tpu(
            f, q, q, q, in_shardings=(sh, sh, sh), out_shardings=sh
        )
    txt = exp.mlir_module()
    assert txt.count("tpu_custom_call") >= 1, "flash kernel not lowered"
    assert txt.count("collective_permute") >= 1, "ring permutes missing"

    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

    with knobs.override_pallas_attention("1"):
        expg = _export_tpu(
            jax.grad(loss, argnums=(0, 1, 2)),
            q, q, q, in_shardings=(sh, sh, sh),
        )
    gtxt = expg.mlir_module()
    assert gtxt.count("tpu_custom_call") >= 3, "backward kernels missing"
    # the backward must keep the RING too: a VJP regression that
    # degrades to all-gather (losing the O(s/N) memory property) would
    # still carry >=3 kernels
    assert gtxt.count("collective_permute") >= 1, "backward ring missing"


def test_flagship_train_step_exports_for_tpu():
    """The flagship model's FULL sharded training step (the program
    `dryrun_multichip` executes on the virtual mesh) must also lower
    for TPU: GSPMD programs carry sharding annotations through
    StableHLO, so a TPU-illegal op or layout in the train step would
    fail here on the CPU box instead of at first contact with a chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu.models.transformer import (
        TransformerConfig,
        make_train_state,
        train_step,
    )
    from torchsnapshot_tpu.parallel.mesh import build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    mesh = build_mesh(8)
    cfg = TransformerConfig.tiny()
    ts = make_train_state(cfg, seed=0, mesh=mesh)
    dp = mesh.shape["dp"]
    tokens = jax.device_put(
        np.zeros((max(2, dp) * 2, 32), np.int32),
        NamedSharding(mesh, P("dp", None)),
    )
    with mesh:
        exp = _export_tpu(train_step, ts, tokens)
    txt = exp.mlir_module()
    # the mesh shardings must survive into the exported module as
    # CONCRETE Shardy annotations naming both mesh axes (the XLA TPU
    # compiler partitions from these) — a bare substring check would
    # pass on any single default annotation
    assert txt.count("sdy.sharding") >= 4, "sharding annotations lost"
    assert '{"dp"}' in txt, "dp axis sharding missing from export"
    assert '{"tp"}' in txt, "tp axis sharding missing from export"
    assert exp.platforms == ("tpu",)


def test_interpret_numerics_match_lowerable_layout():
    # the layout that lowers is the layout CI validates numerically:
    # interpret-mode flash vs dense XLA attention, same [bh,1,s] stats
    from torchsnapshot_tpu.parallel.ring_attention import dense_attention

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    b, s, h, d = 1, 256, 2, 64
    q, k, v = (
        jax.random.normal(kk, (b, s, h, d), jnp.float32) for kk in ks
    )
    with knobs.override_pallas_attention("1"):
        got = fa.flash_attention(q, k, v, causal=True)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
