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


# ------------------------------------------------ how offsets reach the device
#
# A slab's member offsets go up as ONE int32 vector and every member program
# is called with device-resident arguments (PR 37).  The CPU backend enforces
# ``transfer_guard_host_to_device("disallow")``: it refuses a program handed
# an ``np.int32`` and takes one handed a device scalar; an explicit
# ``jax.device_put`` (the slab, the vector) passes it.

def _host_slab(dtypes, count, rng):
    """``count`` host members cycling through ``dtypes``, back to back."""
    hosts = []
    for i in range(count):
        dt = np.dtype(dtypes[i % len(dtypes)])
        shape = (3 + i % 5, 8)
        if dt == np.bool_:
            hosts.append(rng.integers(0, 2, shape).astype(bool))
        elif np.issubdtype(dt, np.complexfloating):
            hosts.append((rng.integers(-99, 99, shape) + 1j * rng.integers(-99, 99, shape)).astype(dt))
        else:
            hosts.append(rng.integers(-99, 99, shape).astype(dt))
    return hosts, np.frombuffer(b"".join(h.tobytes() for h in hosts), np.uint8)


_OFFSET_CASES = {
    # name: (saved dtypes, template dtype or None, members)
    "w1-int8+uint8": (["int8", "uint8"], None, 5),
    "w1-bool": (["bool"], None, 4),
    "w2-bf16+f16": ([ml_dtypes.bfloat16, "float16", "int16"], None, 6),
    "w4-f32+i32": (["float32", "int32", "uint32"], None, 7),
    "w4-complex64": (["complex64", "float32"], None, 4),
    "cast-f32-to-bf16": (["float32"], ml_dtypes.bfloat16, 3),
    "cast-bf16-to-f32": ([ml_dtypes.bfloat16], "float32", 3),
    "one-member": (["float32"], None, 1),
    "sixty-four-members": (["float32", "int32"], None, 64),
    "sixty-five-members": (["int16"], None, 65),
}


@pytest.mark.parametrize("case", sorted(_OFFSET_CASES))
def test_member_calls_take_device_resident_offsets_and_give_the_same_bytes(case):
    dtypes, cast, count = _OFFSET_CASES[case]
    hosts, slab = _host_slab(dtypes, count, np.random.default_rng(count))
    outs = tuple(np.dtype(cast) if cast is not None else h.dtype for h in hosts)
    before = _counters().get("device_unpack.arg_puts", 0)
    with jax.transfer_guard_host_to_device("disallow"):
        out = unpack_slab_to_device(memoryview(slab), _members(hosts), outs, jax.devices()[0])
    # one transfer of arguments a slab (a vector of at most 64 offsets)
    assert _counters()["device_unpack.arg_puts"] - before == -(-count // 64)
    for host, want_dt, got in zip(hosts, outs, out):
        got = np.asarray(got)
        assert got.dtype == want_dt and got.shape == host.shape
        assert got.tobytes() == host.astype(want_dt).tobytes()


def test_a_host_scalar_offset_is_what_the_guard_refuses():
    """The form the member calls had: the guard that the test above runs
    under is not vacuous."""
    fn = device_pack._jitted_unpack("int16", (4,), None)
    slab = jax.device_put(np.arange(16, dtype=np.uint16))
    (off,), puts = device_pack._scalars_on_device([4], jax.devices()[0])
    with jax.transfer_guard_host_to_device("disallow"):
        (member,) = fn(slab, off)
        assert np.asarray(member).tolist() == [4, 5, 6, 7]
        with pytest.raises(Exception, match="(?i)disallowed host-to-device"):
            fn(slab, np.int32(4))
    assert puts == 1 and off.shape == () and off.dtype == np.int32


@pytest.mark.parametrize("off", [-4, 4 * 30, 2**31], ids=["negative", "clamped", "over-int32"])
def test_an_offset_outside_the_slab_raises_with_nothing_put(monkeypatch, off):
    """``dynamic_slice`` would clamp it and deliver a shifted region: the
    host says no before the slab, or any argument, is on its way."""
    slab = np.zeros(4 * 32, np.uint8)
    puts = []
    monkeypatch.setattr(jax, "device_put", lambda *a, **k: puts.append(a) or 1 / 0)
    before = _counters().get("device_unpack.arg_puts", 0), device_pack.CALL_COUNTS["unpack"]
    with pytest.raises(ValueError, match="outside slab"):
        unpack_slab_to_device(
            memoryview(slab),
            ((0, "float32", (4,)), (off, "float32", (4,))),
            (None, None),
            jax.devices()[0],
        )
    assert not puts
    assert (_counters().get("device_unpack.arg_puts", 0), device_pack.CALL_COUNTS["unpack"]) == before


def test_a_signatures_members_share_a_cache_entry_and_a_layout_an_executable():
    """Slabs of one length that hold 1, 2, 3, 5, 20 and 64 members of one
    signature, each twice: one entry in the member programs' cache whatever
    the member count, and behind it an executable a layout met (a call of
    k members for k up to 32; 64 go as 32 + 32), none a slab restored again;
    a split program a padded length, not a member count."""
    device_pack._jitted_unpack.cache_clear()
    device_pack._jitted_split.cache_clear()
    member = np.arange(24, dtype=np.float32).reshape(4, 6)
    for count in (1, 2, 3, 5, 20, 64) * 2:
        hosts = [member + i for i in range(count)]
        slab = np.zeros(64 * member.nbytes, np.uint8)
        slab[: count * member.nbytes] = np.frombuffer(b"".join(h.tobytes() for h in hosts), np.uint8)
        out = unpack_slab_to_device(
            memoryview(slab), _members(hosts), (None,) * count, jax.devices()[0]
        )
        assert all(np.array_equal(np.asarray(o), h) for o, h in zip(out, hosts))
    assert device_pack._jitted_unpack.cache_info().currsize == 1
    # calls of 1, 2, 3, 5, 20 and 32 members
    assert device_pack._jitted_unpack("float32", (4, 6), None)._cache_size() == 6
    # lengths 1, 2, 4, 8, 32, 64
    assert device_pack._jitted_split.cache_info().currsize == 6


@pytest.mark.parametrize(
    "count, calls", [(1, 1), (2, 1), (7, 1), (20, 1), (32, 1), (33, 2), (64, 2), (65, 3)]
)
def test_a_signatures_members_go_in_one_call_up_to_thirty_two(count, calls):
    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.obs import tracer

    hosts, _ = _host_slab(["float32"], count, np.random.default_rng(count))
    hosts = [h[:3] for h in hosts]  # one shape: one signature
    slab = np.frombuffer(b"".join(h.tobytes() for h in hosts), np.uint8)
    with knobs.override_trace(True):
        tracer.get_tracer().reset()
        out = unpack_slab_to_device(
            memoryview(slab), _members(hosts), (None,) * count, jax.devices()[0]
        )
        (span,) = [s for s in tracer.get_tracer().spans() if s.name == "unpack/dispatch"]
    assert span.attrs["calls"] == calls and span.attrs["members"] == count
    assert all(np.array_equal(np.asarray(o), h) for o, h in zip(out, hosts))


def test_members_come_back_in_their_order_whatever_call_made_them():
    """Three signatures interleaved in one slab: every member lands at its
    own index, with its own bytes, from a call that held its signature's
    others."""
    rng = np.random.default_rng(5)
    hosts = []
    for i in range(23):
        shape = [(4, 8), (8, 4), (32,)][i % 3]
        hosts.append(rng.integers(-99, 99, shape).astype(np.int32 if i % 3 == 2 else np.float32))
    slab = np.frombuffer(b"".join(h.tobytes() for h in hosts), np.uint8)
    out = unpack_slab_to_device(memoryview(slab), _members(hosts), (None,) * 23, jax.devices()[0])
    for host, got in zip(hosts, out):
        got = np.asarray(got)
        assert got.dtype == host.dtype and got.shape == host.shape
        assert got.tobytes() == host.tobytes()


def test_the_dispatch_span_says_its_first_call_and_its_argument_transfers():
    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.obs import tracer

    hosts, _ = _host_slab(["float32"], 6, np.random.default_rng(0))
    hosts = [h[:3] for h in hosts]  # one shape: one signature
    slab = np.frombuffer(b"".join(h.tobytes() for h in hosts), np.uint8)
    with knobs.override_trace(True):
        tracer.get_tracer().reset()
        unpack_slab_to_device(memoryview(slab), _members(hosts), (None,) * 6, jax.devices()[0])
        (span,) = [s for s in tracer.get_tracer().spans() if s.name == "unpack/dispatch"]
    assert span.attrs["members"] == 6 and span.attrs["width"] == 4
    assert span.attrs["arg_puts"] == 1 and span.attrs["calls"] == 1
    assert 0 < span.attrs["first_call_ns"] <= span.attrs["longest_call_ns"]
    assert span.attrs["longest_call_ns"] <= span.end_ns - span.start_ns
