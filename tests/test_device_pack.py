"""ops/device_pack.py: slabs travel as words as wide as their members'
elements, so members join (and leave) a slab by a same-width bitcast that
moves nothing — the byte-granular ``uint8[n, itemsize]`` form it replaced
could not be loaded on a TPU beside a real train state (PERF.md, PR 21).
There is no second form: anything else is left to the host path.  (A slab
of 2-byte members is handed to the host as pairs in 4-byte words.)"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import ml_dtypes  # noqa: E402

from torchsnapshot_tpu.ops import device_pack  # noqa: E402
from torchsnapshot_tpu.ops.device_pack import (  # noqa: E402
    pack_arrays_to_host,
    packed_width,
    slab_word_bytes,
    unpack_slab_to_device,
)


def _members(arrays):
    members, off = [], 0
    for a in arrays:
        host = np.asarray(a)
        members.append((off, str(host.dtype), tuple(host.shape)))
        off += host.nbytes
    return tuple(members)


def _arrays(dtypes):
    rng = np.random.default_rng(len(dtypes))
    out = []
    for i, dt in enumerate(dtypes):
        shape = (8 + i, 16)
        if np.dtype(dt) == np.bool_:
            host = rng.integers(0, 2, shape).astype(bool)
        else:
            host = rng.integers(-100, 100, shape).astype(dt)
        out.append(jnp.asarray(host))
    return out


_CASES = {
    "f32-only": (["float32", "float32"], 4),
    "f32+i32": (["float32", "int32", "uint32"], 4),
    "bf16-only": ([ml_dtypes.bfloat16, ml_dtypes.bfloat16], 2),
    "bf16+f16": ([ml_dtypes.bfloat16, "float16", "int16"], 2),
    "bytes": (["int8", "uint8", "bool"], 1),
    "complex": (["complex64", "float32"], 4),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_pack_is_the_serialized_bytes_in_words_of_member_width(case):
    dtypes, word_bytes = _CASES[case]
    arrays = _arrays(dtypes)
    want = b"".join(np.asarray(a).tobytes() for a in arrays)
    # on the device the slab is words of the members' width (2-byte
    # members join as such and leave for the host as pairs in 4-byte words:
    # a device→host copy of narrower words is slow on a TPU)...
    leaves_as = 4 if word_bytes == 2 else word_bytes
    assert device_pack._pack(arrays).dtype == np.dtype(f"uint{8 * leaves_as}")
    # ...and the host reads exactly the per-array serialization
    before = _counters()
    slab = pack_arrays_to_host(arrays)
    assert slab.dtype == np.uint8 and slab.tobytes() == want
    # counted under the members' width, by their bytes
    assert _counters().get(f"device_pack.bytes_w{word_bytes}", 0) - before.get(
        f"device_pack.bytes_w{word_bytes}", 0) == len(want)


def _counters():
    from torchsnapshot_tpu import obs

    return dict(obs.metrics_snapshot()["counters"])


@pytest.mark.parametrize("elements", [1, 2, 255, 256, 257, 511, 40_000, 131_073])
def test_two_byte_members_travel_as_pairs_and_arrive_as_their_bytes(elements):
    """Whatever the element count (odd, under one row of pairs, a row and
    one): the words the device makes are the members' bytes in memory
    order, zeros after them, and the host keeps the members' bytes alone."""
    rng = np.random.default_rng(elements)
    # every 16-bit pattern but the NaNs (a backend may quieten those)
    bits = rng.integers(0, 0x7F80, size=elements, dtype=np.uint16)
    arrays = [jnp.asarray(bits.view(ml_dtypes.bfloat16)), jnp.asarray(bits[::-1].view(np.int16))]
    want = bits.tobytes() + bits[::-1].tobytes()
    words = np.asarray(device_pack._pack(arrays))
    assert words.dtype == np.uint32 and words.size % 128 == 0
    raw = words.tobytes()
    assert raw[: len(want)] == want and not any(raw[len(want):])
    assert pack_arrays_to_host(arrays).tobytes() == want


@pytest.mark.parametrize("case", sorted(_CASES))
def test_unpack_inverts_pack(case):
    dtypes, _ = _CASES[case]
    arrays = _arrays(dtypes)
    slab = pack_arrays_to_host(arrays)
    out = unpack_slab_to_device(
        memoryview(slab),
        _members(arrays),
        tuple(np.asarray(a).dtype for a in arrays),
        jax.devices()[0],
    )
    for a, b in zip(arrays, out):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_same_width_pack_has_no_per_byte_intermediate():
    """The repaired program: nothing in it has a trailing axis of
    itemsize bytes (the shape a TPU pads to a full lane row)."""
    arrays = _arrays(["float32", "int32"])
    jaxpr = jax.make_jaxpr(device_pack._pack)(arrays)
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            assert var.aval.dtype != np.uint8, eqn
            assert var.aval.shape[-1:] != (4,), eqn


def test_only_one_width_at_aligned_offsets_is_a_device_slab():
    """Mixed widths and unaligned members have no device form: the
    eligibility test says so and both programs refuse them."""
    f32, bf16 = ("float32", (32,)), ("bfloat16", (8,))
    assert slab_word_bytes(((0, *f32), (128, "int32", (4,)))) == 4
    assert slab_word_bytes(((0, *bf16), (16, "float16", (3,)))) == 2
    assert slab_word_bytes(((0, "bool", (3,)), (3, "int8", (5,)))) == 1
    assert slab_word_bytes(((0, *f32), (128, *bf16))) is None  # widths
    assert slab_word_bytes(((0, "uint8", (3,)), (3, *f32))) is None
    assert slab_word_bytes(((2, *f32),)) is None  # offset % width

    with pytest.raises(ValueError, match="one element width"):
        device_pack._pack(_arrays(["float32", ml_dtypes.bfloat16]))
    slab = np.zeros(2 + 32 * 4, np.uint8)
    with pytest.raises(ValueError, match="no device unpack"):
        unpack_slab_to_device(
            memoryview(slab), ((2, *f32),), (np.dtype("float32"),),
            jax.devices()[0],
        )


def test_eight_byte_members_need_x64():
    """With jax_enable_x64 off, device_put narrows uint64 words to uint32
    and the bitcast target to 32 bits — the same-width bitcast would then
    succeed on garbage.  Such a slab is not a device slab."""
    assert not jax.config.jax_enable_x64
    for dt in ("float64", "int64", "uint64", "complex128"):
        assert slab_word_bytes(((0, dt, (4,)), (64, dt, (4,)))) is None
    body = np.arange(8, dtype=np.float64)
    with pytest.raises(ValueError, match="no device unpack"):
        unpack_slab_to_device(
            memoryview(body.view(np.uint8)),
            ((0, "float64", (8,)),),
            (np.dtype("float32"),),
            jax.devices()[0],
        )
    with jax.enable_x64(True):
        assert slab_word_bytes(((0, "float64", (8,)),)) == 8
        out = unpack_slab_to_device(
            memoryview(body.view(np.uint8)),
            ((0, "float64", (8,)),),
            (np.dtype("float64"),),
            jax.devices()[0],
        )
        assert np.array_equal(np.asarray(out[0]), body)


@pytest.mark.parametrize(
    "saved,template",
    [
        ("float64", "float32"),
        ("int64", "int32"),
        ("complex128", "complex64"),
    ],
)
def test_eight_byte_leaves_restore_through_the_host_path(
    tmp_path, saved, template
):
    """The restore that found it: numpy 8-byte leaves saved with
    ``Snapshot.take`` and restored into 32-bit jax templates with the
    device unpack ON come back right, with no device unpack attempted and
    no counted failure."""
    from torchsnapshot_tpu import PyTreeState, Snapshot, knobs, obs

    tree = {
        "a": (np.arange(64) - 7).astype(saved),
        "b": (np.arange(32) * 3).astype(saved),
    }
    Snapshot.take(str(tmp_path / "s"), {"m": PyTreeState(dict(tree))})
    dest = PyTreeState(
        {k: jnp.zeros(v.shape, template) for k, v in tree.items()}
    )
    unpacks = device_pack.CALL_COUNTS["unpack"]
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    with knobs.override_device_unpack("1"):
        Snapshot(str(tmp_path / "s")).restore({"m": dest})
    assert device_pack.CALL_COUNTS["unpack"] == unpacks
    assert counter.value == before
    for k, v in tree.items():
        got = np.asarray(dest.tree[k])
        assert got.dtype == np.dtype(template)
        assert np.array_equal(got, v.astype(template)), k


def test_packed_width():
    assert packed_width("bool") == 1
    assert packed_width(ml_dtypes.bfloat16) == 2
    assert packed_width("float32") == 4
    assert packed_width("complex64") == 4  # (real, imag) float32 pair
    assert packed_width("complex128") == 8
