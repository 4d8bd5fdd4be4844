"""Template donation on restore: the 1x-device-memory property.

The reference restores IN PLACE into pre-allocated tensors
(snapshot.py:743-753, io_preparers/tensor.py:91-126), so device peak is
~1x payload.  jax.Arrays are immutable, so the TPU-native equivalent is
put-then-delete: each template's device buffers are freed as soon as its
replacement is reachable through the leaf's Future (preparers/array.py
donate_template) — peak is ~1x payload + one leaf.  Mid-failure
semantics match the reference's in-place load: state ends mixed
old/new but entirely valid (Snapshot._repair_after_failed_restore).
On CPU the knob's "auto" resolves off; these tests force it on to
exercise the mechanism.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import PyTreeState, Snapshot, knobs
from torchsnapshot_tpu.preparers.array import (
    donate_template,
    materialize_into_template,
)


def _params(n=4, m=64):
    return {
        f"w{i}": jnp.arange(m, dtype=jnp.float32) * (i + 1) for i in range(n)
    }


def test_donation_deletes_templates_and_restores(tmp_path):
    params = _params()
    snap = Snapshot.take(str(tmp_path / "snap"), {"m": PyTreeState(params)})
    templates = {k: jnp.zeros_like(v) for k, v in params.items()}
    refs = dict(templates)  # outside refs: donation must still free them
    dest = PyTreeState(templates)
    with knobs.override_restore_donate("1"):
        snap.restore({"m": dest})
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(dest.tree[k]), np.asarray(v))
    for k, t in refs.items():
        assert t.is_deleted(), f"template {k} not donated"


def test_donation_auto_is_off_on_cpu(tmp_path):
    params = _params(n=2)
    snap = Snapshot.take(str(tmp_path / "snap"), {"m": PyTreeState(params)})
    templates = {k: jnp.zeros_like(v) for k, v in params.items()}
    refs = dict(templates)
    snap.restore({"m": PyTreeState(templates)})  # default: auto
    for t in refs.values():
        assert not t.is_deleted()


def test_materialize_never_donates_itself():
    # the load-bearing ordering: donation happens strictly AFTER the
    # replacement is reachable through the leaf's Future — so
    # materialize_into_template itself must NOT donate (its caller
    # donates after fut.set; see ArrayBufferConsumer.consume_buffer).
    # A donated template therefore always implies a retrievable
    # replacement, which _repair_after_failed_restore relies on.
    template = jnp.zeros((32,), jnp.float32)
    data = np.arange(32, dtype=np.float32)
    real_put = jax.device_put
    deleted_at_put = []

    def spy_put(x, sharding=None, **kw):
        deleted_at_put.append(template.is_deleted())
        return real_put(x, sharding, **kw)

    with knobs.override_restore_donate("1"):
        jax.device_put = spy_put
        try:
            out = materialize_into_template(data, template)
        finally:
            jax.device_put = real_put
    assert deleted_at_put == [False]
    assert not template.is_deleted()  # caller's job, after fut.set
    np.testing.assert_array_equal(np.asarray(out), data)


def test_failed_restore_leaves_template_intact():
    # mid-restore failure (H2D error, transfer wedge) must not destroy
    # the caller's live state: donation never precedes the put
    template = jnp.ones((32,), jnp.float32)
    data = np.arange(32, dtype=np.float32)
    real_put = jax.device_put

    def failing_put(x, sharding=None, **kw):
        raise RuntimeError("injected transfer failure")

    with knobs.override_restore_donate("1"):
        jax.device_put = failing_put
        try:
            with pytest.raises(RuntimeError, match="injected"):
                materialize_into_template(data, template)
        finally:
            jax.device_put = real_put
    assert not template.is_deleted()
    np.testing.assert_array_equal(np.asarray(template), np.ones(32))


def test_aliased_template_restores_both_leaves(tmp_path):
    # one array object serving as the template for two paths: the second
    # donation no-ops on the already-deleted array, and both leaves are
    # rebuilt from storage bytes
    params = {"a": jnp.arange(16, dtype=jnp.float32), "b": jnp.ones((16,))}
    snap = Snapshot.take(str(tmp_path / "snap"), {"m": PyTreeState(params)})
    shared = jnp.zeros((16,), jnp.float32)
    dest = PyTreeState({"a": shared, "b": shared})
    with knobs.override_restore_donate("1"):
        snap.restore({"m": dest})
    np.testing.assert_array_equal(np.asarray(dest.tree["a"]), np.arange(16))
    np.testing.assert_array_equal(np.asarray(dest.tree["b"]), np.ones(16))
    assert shared.is_deleted()


def test_sharded_template_donated(tmp_path):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    arr = jax.device_put(jnp.arange(64, dtype=jnp.float32), sharding)
    snap = Snapshot.take(str(tmp_path / "snap"), {"m": PyTreeState({"w": arr})})
    template = jax.device_put(jnp.zeros((64,), jnp.float32), sharding)
    dest = PyTreeState({"w": template})
    with knobs.override_restore_donate("1"):
        snap.restore({"m": dest})
    np.testing.assert_array_equal(np.asarray(dest.tree["w"]), np.arange(64))
    assert template.is_deleted()
    assert dest.tree["w"].sharding.is_equivalent_to(sharding, 1)


def test_offloaded_template_round_trips_with_donation(tmp_path):
    # restoring INTO a pinned-host template: the replacement must land
    # back in the template's memory kind, and donation frees the
    # template's host buffer like any other
    from torchsnapshot_tpu.host_offload import (
        host_memory_supported,
        is_host_offloaded,
        offload_to_host,
    )

    if not host_memory_supported():
        pytest.skip("backend lacks host memory kinds")
    snap = Snapshot.take(
        str(tmp_path / "s"),
        {"m": PyTreeState({"w": jnp.arange(64, dtype=jnp.float32)})},
    )
    tmpl = offload_to_host(jnp.zeros(64, jnp.float32))
    assert is_host_offloaded(tmpl)
    dest = PyTreeState({"w": tmpl})
    with knobs.override_restore_donate("1"):
        snap.restore({"m": dest})
    out = dest.tree["w"]
    assert out.sharding.memory_kind == "pinned_host"
    assert tmpl.is_deleted()
    np.testing.assert_array_equal(np.asarray(out), np.arange(64))


def test_later_leaf_failure_repairs_live_state(tmp_path):
    # A failure on a LATER leaf after earlier templates were donated
    # must not strand deleted arrays in the caller's state: the repair
    # path loads already-restored leaves (mixed old/new, all VALID) —
    # the reference's in-place-load mid-failure semantics.
    import threading

    params = {
        "a": jnp.arange(64, dtype=jnp.float32),
        "b": jnp.full((64,), 7.0, jnp.float32),
    }
    snap = Snapshot.take(str(tmp_path / "snap"), {"m": PyTreeState(params)})
    templates = {k: jnp.zeros_like(v) for k, v in params.items()}
    refs = dict(templates)
    dest = PyTreeState(dict(templates))

    real_put = jax.device_put
    lock = threading.Lock()
    calls = [0]

    def second_put_fails(x, sharding=None, **kw):
        with lock:
            calls[0] += 1
            n = calls[0]
        if n == 2:
            raise RuntimeError("injected H2D failure")
        return real_put(x, sharding, **kw)

    with knobs.override_restore_donate("1"):
        jax.device_put = second_put_fails
        try:
            with pytest.raises(Exception, match="injected"):
                snap.restore({"m": dest})
        finally:
            jax.device_put = real_put

    donated = [k for k, t in refs.items() if t.is_deleted()]
    assert len(donated) <= 1  # only the first put could have succeeded
    for k in params:
        leaf = dest.tree[k]
        # the repaired state must never reference deleted buffers
        assert not (hasattr(leaf, "is_deleted") and leaf.is_deleted()), k
        if k in donated:
            # donated ⟹ replacement was reachable ⟹ repair loaded it
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(params[k]))
        else:
            # never donated ⟹ template (or its equal value) survives
            np.testing.assert_array_equal(
                np.asarray(leaf), np.zeros_like(np.asarray(params[k]))
            )


def test_later_leaf_failure_with_aliased_template(tmp_path):
    # tied weights: ONE array object is the template for both paths.
    # The sibling path's donation deletes the shared template; repair
    # must substitute the sibling's replacement for the path whose own
    # read failed — never hand back the deleted array.
    import threading

    params = {
        "a": jnp.arange(64, dtype=jnp.float32),
        "b": jnp.arange(64, dtype=jnp.float32) * 2,
    }
    snap = Snapshot.take(str(tmp_path / "snap"), {"m": PyTreeState(params)})
    shared = jnp.zeros((64,), jnp.float32)
    dest = PyTreeState({"a": shared, "b": shared})

    real_put = jax.device_put
    lock = threading.Lock()
    calls = [0]

    def second_put_fails(x, sharding=None, **kw):
        with lock:
            calls[0] += 1
            n = calls[0]
        if n == 2:
            raise RuntimeError("injected H2D failure")
        return real_put(x, sharding, **kw)

    with knobs.override_restore_donate("1"):
        jax.device_put = second_put_fails
        try:
            with pytest.raises(Exception, match="injected"):
                snap.restore({"m": dest})
        finally:
            jax.device_put = real_put

    expected = {k: np.asarray(v) for k, v in params.items()}
    for k in params:
        leaf = dest.tree[k]
        assert not (hasattr(leaf, "is_deleted") and leaf.is_deleted()), k
        got = np.asarray(leaf)
        if shared.is_deleted():
            # whichever leaf restored first donated the shared template;
            # both paths must now hold SOME restored value (mixed is ok,
            # deleted is not)
            assert any(
                np.array_equal(got, v) for v in expected.values()
            ), k
        else:
            np.testing.assert_array_equal(got, np.zeros(64, np.float32))


def test_failure_with_donation_off_leaves_state_untouched(tmp_path):
    params = {"a": jnp.arange(16, dtype=jnp.float32), "b": jnp.ones((16,))}
    snap = Snapshot.take(str(tmp_path / "snap"), {"m": PyTreeState(params)})
    templates = {k: jnp.zeros_like(v) for k, v in params.items()}
    refs = dict(templates)
    dest = PyTreeState(dict(templates))
    real_put = jax.device_put

    def always_fails(x, sharding=None, **kw):
        raise RuntimeError("injected H2D failure")

    with knobs.override_restore_donate("0"):
        jax.device_put = always_fails
        try:
            with pytest.raises(Exception, match="injected"):
                snap.restore({"m": dest})
        finally:
            jax.device_put = real_put
    for k, t in refs.items():
        assert not t.is_deleted()
        assert dest.tree[k] is t  # repair no-ops; state untouched


def test_donate_helper_modes():
    arr = jnp.ones((4,))
    with knobs.override_restore_donate("0"):
        donate_template(arr)
        assert not arr.is_deleted()
    with knobs.override_restore_donate("auto"):  # cpu -> off
        donate_template(arr)
        assert not arr.is_deleted()
    with knobs.override_restore_donate("1"):
        donate_template(arr)
        assert arr.is_deleted()
        donate_template(arr)  # idempotent on a deleted array
    # the default mode on an aliased template's SECOND path: the array is
    # already deleted (its .devices() raises) and donation must no-op
    with knobs.override_restore_donate("auto"):
        donate_template(arr)
    # unrecognized values degrade to auto (a typo'd env var must not
    # abort a half-applied restore), with a warning
    with knobs.override_restore_donate("bogus"):
        assert knobs.restore_donation() == "auto"


def test_donation_ignores_host_templates_and_counts_failures(monkeypatch):
    """donate_template only ever frees jax arrays; a delete() that raises
    is an optimization lost — counted, never fatal, never silent."""
    import jax.numpy as jnp

    from torchsnapshot_tpu import knobs, obs
    from torchsnapshot_tpu.preparers import array as array_mod

    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    with knobs.override_restore_donate("1"):
        array_mod.donate_template(None)
        array_mod.donate_template(np.zeros(4))
        assert counter.value == before  # nothing to free, nothing failed

        class _Stuck:
            def is_deleted(self):
                return False

            def delete(self):
                raise RuntimeError("buffer has an external reference")

        monkeypatch.setattr(array_mod, "_is_jax_array", lambda a: True)
        array_mod.donate_template(_Stuck())
        assert counter.value == before + 1
        monkeypatch.undo()
        t = jnp.zeros(4)
        array_mod.donate_template(t)
        assert t.is_deleted()


def test_auto_donation_on_an_accelerator_handles_aliased_templates(monkeypatch):
    """RESTORE_DONATE=auto on a non-cpu device: the first path of an
    aliased template donates it, the second finds it deleted — where
    ``jax.Array.devices()`` raises — and no-ops without a counted
    failure."""
    from types import SimpleNamespace

    from torchsnapshot_tpu import knobs, obs
    from torchsnapshot_tpu.preparers import array as array_mod

    class _OnTpu:
        sharding = SimpleNamespace(
            device_set=[SimpleNamespace(platform="tpu")]
        )
        deleted = False

        def devices(self):
            if self.deleted:
                raise RuntimeError("Array has been deleted")
            return self.sharding.device_set

        def is_deleted(self):
            return self.deleted

        def delete(self):
            self.deleted = True

    monkeypatch.setattr(array_mod, "_is_jax_array", lambda a: True)
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    donated = array_mod.DONATION_STATS["donated_templates"]
    template = _OnTpu()
    with knobs.override_restore_donate("auto"):
        array_mod.donate_template(template)
        assert template.deleted
        array_mod.donate_template(template)  # the aliased second path
    assert array_mod.DONATION_STATS["donated_templates"] == donated + 1
    assert counter.value == before
