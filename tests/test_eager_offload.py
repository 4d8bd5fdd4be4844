"""Unblock-early async snapshots: eager host offload semantics.

The TPU-native async_take returns after one batched device→pinned_host
transfer plus eager defensive copies — before *staging* (client-RAM
materialization) rather than after it (reference scheduler.py:299 blocks
until staged because CUDA tensors are mutable).  These tests pin down the
semantics on hosts without TPU memory kinds, where the offload degrades to
the defensive-copy-only pass and jax arrays stay safe by immutability.
"""

import time

import numpy as np
import pytest

from torchsnapshot_tpu import PyTreeState, Snapshot, StateDict, knobs
from torchsnapshot_tpu.host_offload import eager_offload_write_reqs
from torchsnapshot_tpu.preparers import prepare_write


def _prepare(obj, lpath="app/w", is_async=True):
    return prepare_write(
        obj=obj,
        logical_path=lpath,
        rank=0,
        replicated=False,
        is_async_snapshot=is_async,
        process_index=0,
        process_count=1,
    )


def test_eager_offload_takes_defensive_copy_now():
    src = np.arange(256, dtype=np.float32)
    _, reqs = _prepare(src)
    moved = eager_offload_write_reqs(reqs)
    assert moved >= src.nbytes
    src[:] = -1.0  # mutate after offload, before staging

    import asyncio

    buf = asyncio.new_event_loop().run_until_complete(
        reqs[0].buffer_stager.stage_buffer()
    )
    staged = np.frombuffer(bytes(buf), dtype=np.float32)
    np.testing.assert_array_equal(staged, np.arange(256, dtype=np.float32))


def test_eager_offload_idempotent_and_sync_snapshots_uncopied():
    # sync snapshots don't request defensive copies; offload must not
    # copy them either (cost discipline of reference tensor.py:283-307)
    src = np.arange(64, dtype=np.int32)
    _, reqs = _prepare(src, is_async=False)
    assert eager_offload_write_reqs(reqs) == 0
    assert reqs[0].buffer_stager.arr is src


def test_async_take_jax_state_round_trips(tmp_path):
    import jax.numpy as jnp

    params = {"w": jnp.arange(1024, dtype=jnp.float32), "b": jnp.ones((8,))}
    pending = Snapshot.async_take(
        str(tmp_path / "s"), {"model": PyTreeState(dict(params))}
    )
    # simulate a training step replacing the arrays immediately
    params = {k: v * 0.0 for k, v in params.items()}
    snap = pending.wait()
    dest = PyTreeState({"w": jnp.zeros(1024), "b": jnp.zeros((8,))})
    snap.restore({"model": dest})
    np.testing.assert_array_equal(
        np.asarray(dest.tree["w"]), np.arange(1024, dtype=np.float32)
    )
    np.testing.assert_array_equal(np.asarray(dest.tree["b"]), np.ones(8))
    # the REAL batched pinned-host offload must have engaged (this
    # backend supports host memory kinds), not the degraded fallback —
    # the headline unblock mechanism, asserted, not assumed
    from torchsnapshot_tpu.host_offload import (
        LAST_OFFLOAD_STATS,
        host_memory_supported,
    )

    if host_memory_supported():
        assert LAST_OFFLOAD_STATS.get("device_offload_bytes", 0) >= 1024 * 4


def test_release_fallbacks_on_completion():
    # successful transfer → device refs dropped; failed → retained
    import time as _time

    from torchsnapshot_tpu.host_offload import _release_fallbacks_on_completion
    from torchsnapshot_tpu.preparers.array import JaxArrayBufferStager

    ok = JaxArrayBufferStager(np.zeros(4), nbytes=32)
    ok.fallback_arr = np.zeros(4)
    _release_fallbacks_on_completion([np.zeros(4)], [[ok]])
    deadline = _time.monotonic() + 5
    while ok.fallback_arr is not None and _time.monotonic() < deadline:
        _time.sleep(0.01)
    assert ok.fallback_arr is None

    class _Poisoned:
        def block_until_ready(self):
            raise RuntimeError("transfer failed")

    bad = JaxArrayBufferStager(np.zeros(4), nbytes=32)
    bad.fallback_arr = np.zeros(4)
    _release_fallbacks_on_completion([_Poisoned()], [[bad]])
    _time.sleep(0.2)
    assert bad.fallback_arr is not None


def test_offload_failure_falls_back_to_device_array():
    # A dispatched pinned-host transfer can fail asynchronously; staging
    # must degrade to the (immutable) original array, not fail the snapshot.
    import asyncio

    import jax.numpy as jnp

    from torchsnapshot_tpu.preparers.array import JaxArrayBufferStager

    class _DoomedHostCopy:
        nbytes = 32

        def copy_to_host_async(self):
            pass

        def __array__(self, *a, **k):
            raise RuntimeError("pinned-host allocation failed")

    src = jnp.arange(8, dtype=jnp.float32)
    st = JaxArrayBufferStager(src)
    st.fallback_arr = st.arr
    st.arr = _DoomedHostCopy()
    buf = asyncio.new_event_loop().run_until_complete(st.stage_buffer())
    np.testing.assert_array_equal(
        np.frombuffer(bytes(buf), dtype=np.float32),
        np.arange(8, dtype=np.float32),
    )
    assert st.arr is None and st.fallback_arr is None


def test_small_leaves_offloaded_for_donation_safety(tmp_path):
    """Sub-MB leaves ride the batched offload too: under
    jit(donate_argnums=...) the next step DELETES the device buffers, so
    any leaf left to stage lazily would fail.  After offload, deleting
    every source array (what donation does) must not hurt the snapshot."""
    import asyncio

    import jax.numpy as jnp

    from torchsnapshot_tpu.host_offload import host_memory_supported

    if not host_memory_supported():
        pytest.skip("runtime lacks host memory kinds")

    src = jnp.arange(256, dtype=jnp.float32)  # 1KB — tiny
    _, reqs = _prepare(src)
    moved = eager_offload_write_reqs(reqs)
    assert moved >= src.nbytes
    st = reqs[0].buffer_stager
    # wait for the release watcher to confirm the transfer landed
    deadline = time.monotonic() + 5
    while st.fallback_arr is not None and time.monotonic() < deadline:
        time.sleep(0.01)
    src.delete()  # what the next donated training step does
    buf = asyncio.new_event_loop().run_until_complete(st.stage_buffer())
    np.testing.assert_array_equal(
        np.frombuffer(bytes(buf), dtype=np.float32),
        np.arange(256, dtype=np.float32),
    )


def test_deleted_source_array_fails_with_donation_diagnosis():
    """A lazily-staged leaf whose buffer was donated away must fail with
    a clear diagnosis, not XLA's bare 'Array has been deleted'."""
    import asyncio

    import jax.numpy as jnp

    from torchsnapshot_tpu.preparers.array import JaxArrayBufferStager

    src = jnp.arange(8, dtype=jnp.float32)
    st = JaxArrayBufferStager(src)
    src.delete()
    with pytest.raises(RuntimeError, match="donate"):
        asyncio.new_event_loop().run_until_complete(st.stage_buffer())


def test_deleted_chunk_fails_with_chunk_diagnosis():
    """Chunked (indexed) stagers never offload; their donation failure
    must say so instead of blaming the offload budget."""
    import asyncio

    import jax.numpy as jnp

    from torchsnapshot_tpu.preparers.array import JaxArrayBufferStager

    src = jnp.arange(64, dtype=jnp.float32)
    st = JaxArrayBufferStager(src, index=(slice(0, 8),), nbytes=32)
    src.delete()
    with pytest.raises(RuntimeError, match="chunk"):
        asyncio.new_event_loop().run_until_complete(st.stage_buffer())


def test_eager_offload_host_copy_uses_fast_path_for_extension_dtypes(
    monkeypatch,
):
    import ml_dtypes

    from torchsnapshot_tpu import serialization

    calls = []
    real_fast_copy = serialization.fast_copy
    monkeypatch.setattr(
        serialization,
        "fast_copy",
        lambda a: (calls.append(a.dtype), real_fast_copy(a))[1],
    )

    src = np.arange(512, dtype=np.float32).astype(ml_dtypes.bfloat16)
    _, reqs = _prepare(src)
    moved = eager_offload_write_reqs(reqs)
    assert moved >= src.nbytes
    # the eager defensive copy must go through the memory-bandwidth path,
    # not numpy's per-element extension-dtype cast machinery
    assert calls == [src.dtype]
    orig = src.copy()
    src[:] = ml_dtypes.bfloat16(-1.0)

    import asyncio

    buf = asyncio.new_event_loop().run_until_complete(
        reqs[0].buffer_stager.stage_buffer()
    )
    np.testing.assert_array_equal(
        np.frombuffer(bytes(buf), dtype=ml_dtypes.bfloat16), orig
    )


@pytest.mark.parametrize("disable", [False, True])
def test_async_take_round_trip_with_and_without_eager_staging(
    tmp_path, disable
):
    src = np.arange(4096, dtype=np.float64)
    with knobs.override_disable_eager_host_staging(disable):
        pending = Snapshot.async_take(
            str(tmp_path / "s"), {"app": StateDict(w=src.copy(), step=7)}
        )
        snap = pending.wait()
    out = snap.read_object("0/app/w")
    np.testing.assert_array_equal(out, src)
    assert snap.read_object("0/app/step") == 7


def test_pinned_offload_copies_released_after_commit(tmp_path):
    """The eager-offload pinned-host copies (2x payload across fallback
    + host copy) must be FREED once the take commits — the release
    thread's frame locals used to pin the last take's copies for as
    long as the loop blocked between takes, so a training loop leaked
    one payload of pinned host memory per checkpoint (found round 5 via
    a 10x post-async restore slowdown on the 1-core box)."""
    import gc

    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu.host_offload import host_memory_supported

    if not host_memory_supported():
        pytest.skip("no pinned_host memory kinds on this backend")

    params = {
        f"l{i}": jnp.ones((500_000,), jnp.float32) * i for i in range(4)
    }
    jax.block_until_ready(params)

    def live_pinned_bytes() -> int:
        gc.collect()
        return sum(
            o.nbytes
            for o in gc.get_objects()
            if isinstance(o, jax.Array)
            and getattr(getattr(o, "sharding", None), "memory_kind", "")
            == "pinned_host"
        )

    # baseline-relative: unrelated pinned arrays elsewhere in the
    # process (other tests, runtime internals) must not flake this;
    # the invariant is NO GROWTH attributable to the takes
    baseline = live_pinned_bytes()
    for it in range(3):
        Snapshot.async_take(
            str(tmp_path / f"s{it}"), {"m": PyTreeState(dict(params))}
        ).wait()
        # the release thread processes its queue asynchronously; give it
        # a beat, then nothing from this take may remain pinned (and
        # certainly nothing may ACCUMULATE across takes)
        deadline = time.time() + 5
        while time.time() < deadline and live_pinned_bytes() > baseline:
            time.sleep(0.1)
        assert live_pinned_bytes() <= baseline, (
            f"pinned copies leaked at take {it}"
        )


def test_offload_dispatch_failure_is_counted_not_silent(monkeypatch):
    """When the batched device->pinned_host dispatch fails the leaves
    stage lazily (safe by immutability) — logged and counted through
    exceptions.swallowed, so a chip run cannot quietly lose the
    unblock-early design."""
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import host_offload, obs

    src = jnp.arange(256, dtype=jnp.float32)
    _, reqs = _prepare(src)

    def refuse(*a, **k):
        raise RuntimeError("pinned_host allocation refused (injected)")

    monkeypatch.setattr(jax, "device_put", refuse)
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    assert eager_offload_write_reqs(reqs) == 0
    assert counter.value == before + 1
    assert host_offload.LAST_OFFLOAD_STATS["device_offload_bytes"] == 0
    assert reqs[0].buffer_stager.arr is src  # still stages from the device


def test_offload_budget_skip_is_counted_not_silent():
    import jax.numpy as jnp

    from torchsnapshot_tpu import host_offload, obs

    src = jnp.arange(256, dtype=jnp.float32)
    _, reqs = _prepare(src)
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    assert eager_offload_write_reqs(reqs, budget_bytes=16) == 0
    assert counter.value == before + 1
    assert host_offload.LAST_OFFLOAD_STATS["device_offload_bytes"] == 0
