"""Batcher tests: slab packing byte-range math, entry re-pointing, ranged
read merging (reference tests/test_batcher.py)."""

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, knobs
from torchsnapshot_tpu.batcher import batch_read_requests, batch_write_requests
from torchsnapshot_tpu.io_types import ReadIO, ReadReq, WriteIO, WriteReq
from torchsnapshot_tpu.manifest import ArrayEntry
from torchsnapshot_tpu.preparers.array import ArrayIOPreparer
from torchsnapshot_tpu.scheduler import (
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)
from torchsnapshot_tpu.storage.memory import MemoryStoragePlugin, reset_namespace


def _prep(name, arr):
    return ArrayIOPreparer.prepare_write(
        arr, f"0/{name}", replicated=False, is_async_snapshot=False
    )


def test_slab_packing_and_roundtrip():
    reset_namespace("batch")
    storage = MemoryStoragePlugin("batch")
    arrays = {
        f"a{i}": np.random.default_rng(i).standard_normal(16).astype(np.float32)
        for i in range(10)
    }
    entries = {}
    write_reqs = []
    for name, arr in arrays.items():
        e, reqs = _prep(name, arr)
        entries[f"0/{name}"] = e
        write_reqs += reqs
    with knobs.override_slab_size_threshold_bytes(200):
        entries, write_reqs = batch_write_requests(entries, write_reqs, rank=0)
    # all 64B arrays became slab members
    slab_paths = {wr.path for wr in write_reqs}
    assert all(p.startswith("0/batched.") for p in slab_paths)
    assert len(slab_paths) < 10
    pending = sync_execute_write_reqs(write_reqs, storage, 1 << 30, 0)
    pending.sync_complete()
    # read back through the re-pointed entries (ranged reads + merging)
    read_reqs = []
    futs = {}
    for name in arrays:
        e = entries[f"0/{name}"]
        assert e.byte_range is not None
        reqs, fut = ArrayIOPreparer.prepare_read(e)
        read_reqs += reqs
        futs[name] = fut
    merged = batch_read_requests(read_reqs)
    assert len(merged) < len(read_reqs)  # adjacent ranges merged
    sync_execute_read_reqs(merged, storage, 1 << 30, 0)
    for name, arr in arrays.items():
        np.testing.assert_array_equal(futs[name].obj, arr)


def test_gap_limit_prevents_giant_spans():
    class NullConsumer:
        def get_consuming_cost_bytes(self):
            return 8

        async def consume_buffer(self, buf, executor=None):
            pass

    reqs = [
        ReadReq(path="x", byte_range=[0, 8], buffer_consumer=NullConsumer()),
        ReadReq(
            path="x",
            byte_range=[100 * 1024 * 1024, 100 * 1024 * 1024 + 8],
            buffer_consumer=NullConsumer(),
        ),
    ]
    merged = batch_read_requests(reqs)
    assert len(merged) == 2  # 100MB gap is not spanned


def test_batching_skips_large_and_objects():
    entries = {}
    write_reqs = []
    big = np.zeros(1024, dtype=np.float64)  # 8KB > threshold below
    e, reqs = _prep("big", big)
    entries["0/big"] = e
    write_reqs += reqs
    with knobs.override_slab_size_threshold_bytes(100):
        e2, reqs2 = batch_write_requests(entries, write_reqs, rank=0)
    assert reqs2[0].path == "0/big"  # untouched
    assert entries["0/big"].byte_range is None


def test_end_to_end_batching_matches_unbatched(tmp_path):
    state = {
        "app": StateDict(
            **{f"w{i}": np.full(8, i, dtype=np.float32) for i in range(20)}
        )
    }
    with knobs.override_disable_batching(False), knobs.override_slab_size_threshold_bytes(128):
        snap = Snapshot.take(str(tmp_path / "b"), state)
    dest = {
        "app": StateDict(
            **{f"w{i}": np.zeros(8, dtype=np.float32) for i in range(20)}
        )
    }
    snap.restore(dest)
    for i in range(20):
        np.testing.assert_array_equal(
            dest["app"][f"w{i}"], np.full(8, i, dtype=np.float32)
        )
    # storage contains fewer objects than arrays (slabs worked)
    import os

    files = []
    for root, _, fnames in os.walk(tmp_path / "b"):
        files += [f for f in fnames if not f.startswith(".")]
    assert len(files) < 20


def test_device_packed_slab_roundtrip(tmp_path):
    """All-jax slabs pack on device (bitcast+concat); bytes must equal the
    per-array serialization exactly."""
    import jax.numpy as jnp

    state = {
        "app": StateDict(
            a=jnp.arange(16, dtype=jnp.float32),
            b=jnp.ones((4, 4), dtype=jnp.bfloat16),
            c=jnp.arange(8, dtype=jnp.int32),
        )
    }
    with knobs.override_disable_batching(False), knobs.override_slab_size_threshold_bytes(4096):
        snap = Snapshot.take(str(tmp_path / "s"), state)
    manifest = snap.get_manifest()
    assert any("batched" in getattr(e, "location", "") for e in manifest.values())
    dest = {
        "app": StateDict(
            a=jnp.zeros(16, dtype=jnp.float32),
            b=jnp.zeros((4, 4), dtype=jnp.bfloat16),
            c=jnp.zeros(8, dtype=jnp.int32),
        )
    }
    snap.restore(dest)
    import numpy as np

    np.testing.assert_array_equal(np.asarray(dest["app"]["a"]), np.arange(16, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(dest["app"]["b"]), np.ones((4, 4)))
    np.testing.assert_array_equal(np.asarray(dest["app"]["c"]), np.arange(8, dtype=np.int32))


def test_device_unpack_restore_roundtrip(tmp_path):
    """DEVICE_UNPACK: batched slab restores via one H2D + one compiled
    slice/bitcast program; values bitwise-match the host path."""
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import PyTreeState, Snapshot, knobs

    from torchsnapshot_tpu.ops.device_pack import _jitted_unpack

    tree = {
        "w_f32": jnp.arange(512, dtype=jnp.float32),
        "w_bf16": (jnp.arange(256, dtype=jnp.float32) * 0.5).astype(
            jnp.bfloat16
        ),
        "w_i32": jnp.arange(128, dtype=jnp.int32).reshape(8, 16),
    }
    Snapshot.take(str(tmp_path / "s"), {"m": PyTreeState(dict(tree))})

    def fresh():
        return PyTreeState(
            {
                "w_f32": jnp.zeros(512, jnp.float32),
                "w_bf16": jnp.zeros(256, jnp.bfloat16),
                "w_i32": jnp.zeros((8, 16), jnp.int32),
            }
        )

    # all-jax template: the device path must actually run (observable
    # as a new compiled layout in the unpack cache)
    dest = fresh()
    misses_before = _jitted_unpack.cache_info().misses
    with knobs.override_device_unpack("1"):
        Snapshot(str(tmp_path / "s")).restore({"m": dest})
    assert (
        _jitted_unpack.cache_info().misses > misses_before
    ), "device unpack did not run"
    for k in tree:
        got = np.asarray(dest.tree[k])
        want = np.asarray(tree[k])
        assert got.dtype == want.dtype and np.array_equal(got, want), k
        assert hasattr(dest.tree[k], "sharding")  # landed on device

    # knob off: host path produces identical values
    dest2 = fresh()
    with knobs.override_device_unpack("0"):
        Snapshot(str(tmp_path / "s")).restore({"m": dest2})
    for k in tree:
        assert np.array_equal(
            np.asarray(dest2.tree[k]), np.asarray(dest.tree[k])
        ), k


def test_device_unpack_mixed_members_falls_back(tmp_path):
    """A slab with a numpy-template member is ineligible: the host path
    restores every member correctly (all-or-nothing per slab)."""
    from torchsnapshot_tpu import PyTreeState, Snapshot, knobs
    import jax.numpy as jnp

    tree = {
        "dev": jnp.arange(256, dtype=jnp.float32),
        "host": np.linspace(0, 1, 64),
    }
    Snapshot.take(str(tmp_path / "s"), {"m": PyTreeState(dict(tree))})
    dest = PyTreeState(
        {"dev": jnp.zeros(256, jnp.float32), "host": np.zeros(64)}
    )
    with knobs.override_device_unpack("1"):
        Snapshot(str(tmp_path / "s")).restore({"m": dest})
    assert np.array_equal(np.asarray(dest.tree["dev"]), np.asarray(tree["dev"]))
    assert np.array_equal(dest.tree["host"], tree["host"])


def test_device_unpack_dtype_cast(tmp_path):
    """Template dtype differs from saved dtype: the cast happens on
    device inside the unpack program."""
    import jax.numpy as jnp

    from torchsnapshot_tpu import PyTreeState, Snapshot, StateDict, knobs

    Snapshot.take(
        str(tmp_path / "s"),
        {
            "m": PyTreeState(
                {
                    "a": jnp.arange(256, dtype=jnp.float32),
                    "b": jnp.ones(128, jnp.float32),
                }
            )
        },
    )
    dest = PyTreeState(
        {
            "a": jnp.zeros(256, jnp.bfloat16),  # cast f32 -> bf16
            "b": jnp.zeros(128, jnp.float32),
        }
    )
    with knobs.override_device_unpack("1"):
        Snapshot(str(tmp_path / "s")).restore({"m": dest})
    assert dest.tree["a"].dtype == jnp.bfloat16
    assert np.array_equal(
        np.asarray(dest.tree["a"]),
        np.arange(256, dtype=np.float32).astype(
            np.asarray(dest.tree["a"]).dtype
        ),
    )


def test_unpack_slab_primitives():
    """unpack_slab_to_device inverts pack_arrays_to_host for every
    supported dtype class (float, int, bool, complex, bf16), one slab per
    element width — the only kind either program takes."""
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu.ops.device_pack import (
        pack_arrays_to_host,
        unpack_slab_to_device,
    )

    slabs = [
        [
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            jnp.arange(8, dtype=jnp.float32).astype(jnp.complex64) * (1 + 2j),
        ],
        [jnp.arange(32, dtype=jnp.int8), jnp.array([True, False, True, True])],
        [(jnp.arange(16, dtype=jnp.float32) * 0.25).astype(jnp.bfloat16)],
    ]
    for arrays in slabs:
        slab = pack_arrays_to_host(arrays)
        members = []
        off = 0
        for a in arrays:
            dt = np.asarray(a).dtype
            members.append((off, str(dt), tuple(a.shape)))
            off += np.asarray(a).nbytes
        out = unpack_slab_to_device(
            memoryview(slab),
            tuple(members),
            tuple(np.asarray(a).dtype for a in arrays),
            jax.devices()[0],
        )
        for a, b in zip(arrays, out):
            assert np.asarray(a).dtype == np.asarray(b).dtype
            assert np.array_equal(np.asarray(a), np.asarray(b)), a


def test_big_host_members_bypass_slab():
    # a big HOST member's slab pack is a pure extra memcpy: members at
    # or above SLAB_HOST_MEMBER_MAX_BYTES write directly; small ones
    # still coalesce
    import numpy as np

    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.batcher import batch_write_requests
    from torchsnapshot_tpu.io_types import WriteReq
    from torchsnapshot_tpu.manifest import ArrayEntry
    from torchsnapshot_tpu.preparers.array import HostArrayBufferStager

    def req(name, nbytes):
        entry = ArrayEntry(name, "buffer_protocol", "uint8", [nbytes], False)
        return entry, WriteReq(
            path=name,
            buffer_stager=HostArrayBufferStager(
                np.zeros(nbytes, np.uint8), defensive_copy=False
            ),
        )

    with knobs.override_slab_host_member_max_bytes(1024):
        entries, reqs = {}, []
        for name, nb in [("big0", 4096), ("big1", 2048),
                         ("s0", 100), ("s1", 200), ("s2", 300)]:
            e, wr = req(name, nb)
            entries[name] = e
            reqs.append(wr)
        out_entries, out_reqs = batch_write_requests(entries, reqs, rank=0)
    paths = sorted(wr.path for wr in out_reqs)
    # big members keep their own objects; the three smalls became 1 slab
    assert "big0" in paths and "big1" in paths
    assert any(p.startswith("0/batched.") for p in paths)
    assert len(out_reqs) == 3
    for name in ("s0", "s1", "s2"):
        assert out_entries[name].location.startswith("0/batched.")
    for name in ("big0", "big1"):
        assert out_entries[name].location == name


def test_tiny_object_leaves_coalesce_into_slabs(tmp_path):
    # thousands of tiny OBJECT leaves (numpy scalars in optimizer state)
    # used to write one storage object each — 5000 PUTs on cloud
    # backends; they now slab like array payloads, and their restore
    # reads merge into spanning reads
    import os

    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    arrs = {f"s{i}": np.float32(i * 0.5) for i in range(300)}
    snap = Snapshot.take(str(tmp_path / "b"), {"app": StateDict(**arrs)})
    files = [
        os.path.join(r, f)
        for r, _, fs in os.walk(tmp_path / "b")
        for f in fs
    ]
    # one slab + .snapshot_metadata (not 301 objects)
    assert len(files) <= 3, files[:5]

    entry = snap.get_manifest()["0/app/s7"]
    assert type(entry).__name__ == "ObjectEntry"
    assert entry.byte_range is not None and ("batched" in entry.location)

    dest = {"app": StateDict(**{k: np.float32(0) for k in arrs})}
    snap.restore(dest)
    for k, v in arrs.items():
        got = dest["app"][k]
        assert float(got) == float(v), k
        assert np.asarray(got).dtype == np.float32, k
    # integrity audit still passes with ranged object crcs
    assert snap.verify(deep=True).ok

    # incremental take against the base dedups the (unchanged) slab
    snap2 = Snapshot.take(
        str(tmp_path / "b2"),
        {"app": StateDict(**arrs)},
        base=str(tmp_path / "b"),
    )
    slabs2 = [
        os.path.join(r, f)
        for r, _, fs in os.walk(tmp_path / "b2")
        for f in fs
        if "batched" in f
    ]
    assert slabs2 and all(os.stat(f).st_nlink > 1 for f in slabs2), slabs2
    assert snap2.verify(deep=True).ok


def test_device_and_host_members_slab_separately():
    # one host member in a device slab would forfeit the device pack
    # (one-DMA-per-slab); groups must not interleave
    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu.batcher import (
        BatchedBufferStager,
        batch_write_requests,
    )
    from torchsnapshot_tpu.io_types import WriteReq
    from torchsnapshot_tpu.manifest import ArrayEntry
    from torchsnapshot_tpu.preparers.array import (
        HostArrayBufferStager,
        JaxArrayBufferStager,
    )

    entries, reqs = {}, []
    for i in range(3):
        name = f"dev{i}"
        entries[name] = ArrayEntry(name, "buffer_protocol", "float32", [64], False)
        reqs.append(WriteReq(
            path=name,
            buffer_stager=JaxArrayBufferStager(jnp.arange(64, dtype=jnp.float32)),
        ))
    for i in range(3):
        name = f"host{i}"
        entries[name] = ArrayEntry(name, "buffer_protocol", "uint8", [64], False)
        reqs.append(WriteReq(
            path=name,
            buffer_stager=HostArrayBufferStager(
                np.zeros(64, np.uint8), defensive_copy=False
            ),
        ))
    _, out = batch_write_requests(entries, reqs, rank=0)
    slab_stagers = [
        r.buffer_stager for r in out
        if isinstance(r.buffer_stager, BatchedBufferStager)
    ]
    assert len(slab_stagers) == 2
    kinds = sorted(s._all_jax for s in slab_stagers)
    assert kinds == [False, True], "device and host members interleaved"


def test_failed_device_pack_is_counted_not_silent(tmp_path, monkeypatch):
    """A device slab pack that fails still lands on the host pack — but
    through exceptions.swallowed and a WARNING, never quietly."""
    import jax.numpy as jnp

    from torchsnapshot_tpu import obs
    from torchsnapshot_tpu.ops import device_pack

    def oom(arrays):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    monkeypatch.setattr(device_pack, "pack_arrays_to_host", oom)
    state = {
        "app": StateDict(
            a=jnp.arange(16, dtype=jnp.float32),
            c=jnp.arange(8, dtype=jnp.int32),
        )
    }
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    with knobs.override_disable_batching(False), knobs.override_slab_size_threshold_bytes(4096):
        snap = Snapshot.take(str(tmp_path / "s"), state)
    assert counter.value == before + 1
    assert snap.verify(deep=True).ok  # the host pack wrote the same bytes
    dest = {"app": StateDict(a=jnp.zeros(16), c=jnp.zeros(8, jnp.int32))}
    snap.restore(dest)
    np.testing.assert_array_equal(np.asarray(dest["app"]["a"]), np.arange(16))


def test_device_slabs_group_by_element_width():
    """Every member of a device slab joins it by a same-width bitcast, so
    slabs never mix element widths (ops/device_pack.py)."""
    import jax.numpy as jnp

    from torchsnapshot_tpu.batcher import (
        BatchedBufferStager,
        batch_write_requests,
    )
    from torchsnapshot_tpu.io_types import WriteReq
    from torchsnapshot_tpu.manifest import ArrayEntry
    from torchsnapshot_tpu.preparers.array import JaxArrayBufferStager

    entries, reqs = {}, []
    for i, dt in enumerate(
        [jnp.float32, jnp.bfloat16, jnp.int32, jnp.bfloat16, jnp.float32]
    ):
        name = f"w{i}"
        entries[name] = ArrayEntry(
            name, "buffer_protocol", str(jnp.dtype(dt)), [64], False
        )
        reqs.append(WriteReq(
            path=name,
            buffer_stager=JaxArrayBufferStager(jnp.ones(64, dt)),
        ))
    _, out = batch_write_requests(entries, reqs, rank=0)
    slabs = [
        r.buffer_stager for r in out
        if isinstance(r.buffer_stager, BatchedBufferStager)
    ]
    widths = sorted(
        tuple(sorted({s.arr.dtype.itemsize for s, _ in slab.stagers}))
        for slab in slabs
    )
    assert widths == [(2,), (4,)]
    assert all(slab._device_packable for slab in slabs)


def test_failed_device_unpack_is_counted_not_silent(tmp_path, monkeypatch):
    import jax.numpy as jnp

    from torchsnapshot_tpu import PyTreeState, obs
    from torchsnapshot_tpu.ops import device_pack

    tree = {
        "a": jnp.arange(512, dtype=jnp.float32),
        "b": jnp.arange(128, dtype=jnp.int32),
    }
    Snapshot.take(str(tmp_path / "s"), {"m": PyTreeState(dict(tree))})

    def oom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    monkeypatch.setattr(device_pack, "unpack_slab_to_device", oom)
    dest = PyTreeState(
        {"a": jnp.zeros(512, jnp.float32), "b": jnp.zeros(128, jnp.int32)}
    )
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    with knobs.override_device_unpack("1"):
        Snapshot(str(tmp_path / "s")).restore({"m": dest})
    assert counter.value == before + 1
    for k in tree:  # the host path restored the same values
        assert np.array_equal(np.asarray(dest.tree[k]), np.asarray(tree[k]))


def test_mixed_width_slab_takes_host_unpack_by_choice(tmp_path, monkeypatch):
    """A slab laid out with mixed element widths (an older plan) is
    INELIGIBLE for the device unpack — no attempt, no counted failure."""
    import jax.numpy as jnp

    from torchsnapshot_tpu import PyTreeState, batcher, obs
    from torchsnapshot_tpu.ops import device_pack

    # lay the slab out the old way: all device members in one group
    monkeypatch.setattr(device_pack, "packed_width", lambda dt: 0)
    tree = {
        "a": jnp.arange(512, dtype=jnp.float32),
        "b": jnp.ones(256, jnp.bfloat16),
    }
    snap = Snapshot.take(str(tmp_path / "s"), {"m": PyTreeState(dict(tree))})
    monkeypatch.undo()
    locations = {e.location for e in snap.get_manifest().values()
                 if hasattr(e, "location")}
    assert any("batched" in loc for loc in locations)

    calls = []
    real = device_pack.unpack_slab_to_device
    monkeypatch.setattr(
        device_pack, "unpack_slab_to_device",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1],
    )
    dest = PyTreeState(
        {"a": jnp.zeros(512, jnp.float32), "b": jnp.zeros(256, jnp.bfloat16)}
    )
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    with knobs.override_device_unpack("1"):
        Snapshot(str(tmp_path / "s")).restore({"m": dest})
    assert calls == [] and counter.value == before
    for k in tree:
        assert np.array_equal(np.asarray(dest.tree[k]), np.asarray(tree[k]))


def test_slab_unpack_donates_member_templates(tmp_path):
    """Members restored through the device unpack free their templates
    like every other restore path (1x-restore; donation strictly after
    the replacement is reachable)."""
    import jax.numpy as jnp

    from torchsnapshot_tpu import PyTreeState
    from torchsnapshot_tpu.preparers.array import DONATION_STATS

    tree = {
        "a": jnp.arange(512, dtype=jnp.float32),
        "b": jnp.arange(128, dtype=jnp.int32),
    }
    Snapshot.take(str(tmp_path / "s"), {"m": PyTreeState(dict(tree))})
    templates = {
        "a": jnp.zeros(512, jnp.float32), "b": jnp.zeros(128, jnp.int32),
    }
    dest = PyTreeState(dict(templates))
    before = DONATION_STATS["donated_templates"]
    with knobs.override_device_unpack("1"), knobs.override_restore_donate("1"):
        Snapshot(str(tmp_path / "s")).restore({"m": dest})
    assert DONATION_STATS["donated_templates"] == before + 2
    assert all(t.is_deleted() for t in templates.values())
    for k in tree:
        assert np.array_equal(np.asarray(dest.tree[k]), np.asarray(tree[k]))


def test_slab_of_shards_from_several_devices_packs_on_host_by_choice(tmp_path):
    """One jit cannot take operands committed to different devices: such
    a slab is never offered to the device pack, so a sharded blocking
    take counts no swallowed exception."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import PyTreeState, obs
    from torchsnapshot_tpu.ops import device_pack

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    w = jax.device_put(
        jnp.arange(4 * 64, dtype=jnp.float32).reshape(4, 64),
        NamedSharding(mesh, P("x", None)),
    )
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    packs = device_pack.CALL_COUNTS["pack"]
    snap = Snapshot.take(str(tmp_path / "s"), {"m": PyTreeState({"w": w})})
    assert counter.value == before
    assert device_pack.CALL_COUNTS["pack"] == packs
    assert snap.verify(deep=True).ok
