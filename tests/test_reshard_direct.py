"""The resharding restore's direct path (``preparers/sharded.py``): a leaf
whose every local box lies whole inside one read piece takes no host
assembly buffer; its bytes go from the read piece to ``jax.device_put`` as
they lie ONCE, to one of the devices that hold a box of it; a column box
is cut out there, and a box that another device holds is moved device to
device.  On the CPU mesh under
``knobs.override_device_unpack(True)`` (at auto a CPU "device" is host
memory and the host path runs, as it always did)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict, knobs, obs
from torchsnapshot_tpu.ops import device_pack

COUNTERS = (
    obs.RESHARD_HOST_ALLOC_BYTES,
    obs.RESHARD_DIRECT_BYTES,
    obs.EXCEPTIONS_SWALLOWED,
    obs.RESHARD_LINK_BYTES,
    obs.RESHARD_HANDOFF_BYTES,
)


@pytest.fixture(autouse=True)
def link_tally(monkeypatch):
    """The process-wide tally of host-link bytes a device, fresh a test:
    which device receives a shared piece is then the same in every order
    of the tests."""
    from torchsnapshot_tpu.preparers import sharded

    tally = sharded._LinkTally()
    monkeypatch.setattr(sharded, "_LINK_TALLY", tally)
    return tally

# a leaf of each kind of parallel/mesh.py::_RULES, and a 0-d count
LEAVES = {
    "rows": ((16, 8), ("tp", None)),
    "cols": ((8, 16), (None, "tp")),
    "norm": ((32,), (None,)),
    "count": ((), ()),
}


def _mesh(dp, tp):
    return Mesh(np.array(jax.devices()[: dp * tp]).reshape(dp, tp), ("dp", "tp"))


def _value(kind, seed=0, dtype=np.float32):
    shape, _ = LEAVES[kind]
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


def _put(kind, mesh, value):
    return jax.device_put(value, NamedSharding(mesh, P(*LEAVES[kind][1])))


def _unique_boxes(array):
    return {str(s.index): s.data.nbytes for s in array.addressable_shards}


def _box_bytes(template):
    """Bytes of the template's unique local boxes: what a restore of it
    either allocates on the host or puts direct."""
    return sum(_unique_boxes(template).values())


class _Gained:
    """What the counters and the unpack count gained over a block."""

    def __enter__(self):
        self.before = self._read()
        return self

    def __exit__(self, *exc):
        after = self._read()
        self.host, self.direct, self.swallowed, self.link, self.handoff, self.cuts = (
            a - b for a, b in zip(after, self.before)
        )

    @staticmethod
    def _read():
        snap = obs.metrics_snapshot()["counters"]
        return [snap.get(name, 0) for name in COUNTERS] + [device_pack.CALL_COUNTS["unpack"]]


def _counter(name):
    return obs.metrics_snapshot()["counters"].get(name, 0)


def _assert_restored(leaf, value, template):
    assert leaf.dtype == template.dtype and leaf.shape == template.shape
    assert leaf.sharding.is_equivalent_to(template.sharding, leaf.ndim)
    np.testing.assert_array_equal(np.asarray(leaf), value)
    assert len(leaf.addressable_shards) == len(template.addressable_shards)
    for shard in leaf.addressable_shards:  # every device holds global[index]
        np.testing.assert_array_equal(np.asarray(shard.data), value[shard.index])


# (saved mesh, restore mesh) -> kinds whose local boxes each lie in one
# saved shard, and the cuts a column leaf takes (one a unique box)
LAYOUTS = {
    ((2, 2), (1, 4)): ({"rows", "cols", "norm", "count"}, 4),  # a piece feeds 2 devices
    ((1, 2), (1, 8)): ({"rows", "cols", "norm", "count"}, 8),  # a piece feeds 4
    ((2, 2), (2, 4)): ({"rows", "cols", "norm", "count"}, 4),  # ... 2 boxes, each on 2
    ((1, 2), (2, 2)): ({"rows", "cols", "norm", "count"}, 0),  # a row box on 2 devices
    ((1, 4), (1, 4)): ({"rows", "cols", "norm", "count"}, 0),  # whole shards
    ((2, 2), (4, 1)): ({"norm", "count"}, 0),  # gathered boxes: host path
    ((1, 4), (2, 2)): ({"norm", "count"}, 0),
}


@pytest.mark.parametrize("kind", sorted(LEAVES))
@pytest.mark.parametrize(
    "layout", sorted(LAYOUTS), ids=lambda l: "{}x{}to{}x{}".format(*l[0], *l[1])
)
def test_a_leaf_comes_back_bitwise_on_every_device(tmp_path, layout, kind):
    (save, restore), (direct_kinds, cuts) = layout, LAYOUTS[layout]
    value = _value(kind, seed=3)
    saved = _put(kind, _mesh(*save), value)
    Snapshot.take(str(tmp_path / "s"), {"app": StateDict(w=saved)})
    template = _put(kind, _mesh(*restore), np.zeros_like(value))
    dest = StateDict(w=template)
    with knobs.override_device_unpack(True), _Gained() as g:
        Snapshot(str(tmp_path / "s")).restore({"app": dest})
    _assert_restored(dest["w"], value, template)
    want_direct = kind in direct_kinds
    assert g.direct == (_box_bytes(template) if want_direct else 0)
    assert g.host + g.direct == _box_bytes(template)
    assert g.cuts == (cuts if kind == "cols" and want_direct else 0)
    assert g.swallowed == 0
    # every byte crosses the host link once; of a piece's boxes one lands
    # where the piece was put, and the others get theirs device to device.
    # A column piece is a saved shard put whole; any other put is one box
    assert g.link == g.direct
    boxes = _unique_boxes(template)
    puts = len(_unique_boxes(saved)) if kind == "cols" else len(boxes)
    delivered = sum(s.data.nbytes for s in template.addressable_shards)
    assert g.handoff == (delivered - puts * max(boxes.values()) if want_direct else 0)


def _host_path_cases():
    def other_dtype(snap, value):
        template = _put("cols", _mesh(1, 4), np.zeros(value.shape, np.float16))
        dest = StateDict(w=template)
        snap.restore({"app": dest})
        assert dest["w"].dtype == np.float16
        np.testing.assert_array_equal(np.asarray(dest["w"]), value.astype(np.float16))
        return value.nbytes

    def numpy_template(snap, value):
        dest = StateDict(w=np.zeros_like(value))
        snap.restore({"app": dest})
        np.testing.assert_array_equal(dest["w"], value)
        return value.nbytes

    def no_template(snap, value):
        np.testing.assert_array_equal(snap.read_object("0/app/w"), value)
        return value.nbytes

    def single_device_template(snap, value):
        out = snap.read_object("0/app/w", obj_out=jnp.zeros_like(value))
        np.testing.assert_array_equal(np.asarray(out), value)
        return value.nbytes

    def tiled_read(snap, value):
        # a budget under a saved shard (256 B): the fetch is cut into row
        # ranges, and a column box never lies in one
        template = _put("cols", _mesh(1, 4), np.zeros_like(value))
        out = snap.read_object("0/app/w", obj_out=template, memory_budget_bytes=64)
        _assert_restored(out, value, template)
        return value.nbytes

    def pinned_host_template(snap, value):
        # the plan alone: a template in host memory stays on the host path,
        # which keeps the template's memory kind
        from torchsnapshot_tpu.preparers import prepare_read

        try:
            sharding = NamedSharding(_mesh(1, 4), P(None, "tp"), memory_kind="pinned_host")
            template = jax.device_put(np.zeros_like(value), sharding)
        except Exception as e:  # this backend has no such memory
            pytest.skip(f"no pinned_host memory here: {e}")
        prepare_read(snap.get_manifest()["0/app/w"], obj_out=template)
        return value.nbytes

    return [
        other_dtype, numpy_template, no_template, single_device_template, tiled_read,
        pinned_host_template,
    ]


@pytest.mark.parametrize("case", _host_path_cases(), ids=lambda f: f.__name__)
def test_what_the_plan_or_the_template_rules_out_runs_the_host_path(tmp_path, case):
    value = _value("cols", seed=5)
    Snapshot.take(str(tmp_path / "s"), {"app": StateDict(w=_put("cols", _mesh(2, 2), value))})
    with knobs.override_device_unpack(True), _Gained() as g:
        box_bytes = case(Snapshot(str(tmp_path / "s")), value)
    assert (g.host, g.direct, g.cuts, g.swallowed) == (box_bytes, 0, 0, 0)


@pytest.mark.parametrize("unpack", ["auto", False])
def test_with_the_knob_at_auto_on_cpu_every_count_is_what_it_was(tmp_path, unpack):
    value = _value("cols", seed=6)
    Snapshot.take(str(tmp_path / "s"), {"app": StateDict(w=_put("cols", _mesh(2, 2), value))})
    template = _put("cols", _mesh(1, 4), np.zeros_like(value))
    dest = StateDict(w=template)
    with knobs.override_device_unpack(unpack), _Gained() as g:
        Snapshot(str(tmp_path / "s")).restore({"app": dest})
    _assert_restored(dest["w"], value, template)
    assert (g.host, g.direct, g.cuts, g.swallowed) == (value.nbytes, 0, 0, 0)


@pytest.mark.parametrize("kind", ["rows", "cols"])
def test_verify_on_restore_still_catches_a_corrupted_shard(tmp_path, kind):
    value = _value(kind, seed=7)
    root = str(tmp_path / "s")
    Snapshot.take(root, {"app": StateDict(w=_put(kind, _mesh(2, 2), value))})
    payloads = [
        os.path.join(base, f)
        for base, _dirs, files in os.walk(root)
        for f in files
        if not f.startswith(".snapshot") and os.path.getsize(os.path.join(base, f))
    ]
    with open(payloads[0], "r+b") as f:
        f.seek(9)
        byte = f.read(1)
        f.seek(9)
        f.write(bytes([byte[0] ^ 0x10]))
    template = _put(kind, _mesh(1, 4), np.zeros_like(value))
    with knobs.override_device_unpack(True), knobs.override_verify_on_restore(True):
        with pytest.raises(Exception, match="(?i)crc|checksum|corrupt|mismatch"):
            Snapshot(root).restore({"app": StateDict(w=template)})
    np.testing.assert_array_equal(np.asarray(template), np.zeros_like(value))


@pytest.mark.parametrize(
    "raises, fails_from, cuts",
    [("cut", 0, {0}), ("cut", 2, {2}), ("handoff", 0, {1, 2}), ("handoff", 1, {3, 4})],
    ids=["first_cut", "cut_after_a_piece_landed", "first_handoff", "handoff_after_a_piece_landed"],
)
def test_a_cut_that_raises_falls_back_to_the_host_path_once(
    tmp_path, monkeypatch, raises, fails_from, cuts
):
    """Both read pieces of the leaf fail (or the second, with the first
    piece's boxes already on their devices and read back), in a cut or in
    the move of a cut box to the sibling's device: the leaf comes out
    bitwise through assembly buffers made then, counted once, and its
    template is whole whenever the direct path is asked."""
    value = _value("cols", seed=9)
    Snapshot.take(str(tmp_path / "s"), {"app": StateDict(w=_put("cols", _mesh(2, 2), value))})
    template = _put("cols", _mesh(1, 4), np.zeros_like(value))
    real_cut, real_put = device_pack.cut_box_on_device, jax.device_put
    calls, handoffs, template_deleted = [], [], []

    def cut(wide, starts, sizes):
        template_deleted.append(template.is_deleted())
        calls.append(starts)
        if raises == "cut" and len(calls) > fails_from:
            raise RuntimeError("planted: no room on the device")
        return real_cut(wide, starts, sizes)

    def put(x, *args, **kwargs):
        if isinstance(x, jax.Array):  # a box on its way to a sibling
            handoffs.append(x.shape)
            if raises == "handoff" and len(handoffs) > fails_from:
                raise RuntimeError("planted: the sibling has no room")
        return real_put(x, *args, **kwargs)

    monkeypatch.setattr(device_pack, "cut_box_on_device", cut)
    monkeypatch.setattr(jax, "device_put", put)
    dest = StateDict(w=template)
    # one worker: the pieces land one after the other, in either order
    with knobs.override_device_unpack(True), knobs.override_staging_threads(1):
        with knobs.override_restore_donate("on"), _Gained() as g:
            Snapshot(str(tmp_path / "s")).restore({"app": dest})
    assert calls and not any(template_deleted)
    assert template.is_deleted()  # donated, after its replacement was set
    np.testing.assert_array_equal(np.asarray(dest["w"]), value)
    for shard in dest["w"].addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data), value[shard.index])
    assert g.swallowed == 1
    assert (g.host, g.direct) == (value.nbytes, 0)
    # a hand-off follows the cut of the sibling's box, which is the piece's
    # first or its second
    assert g.cuts in cuts and (raises == "cut" or g.cuts == len(calls))
    assert len(handoffs) == (fails_from + 1 if raises == "handoff" else fails_from // 2)


def test_a_read_piece_crosses_the_host_link_once_and_the_links_stay_even(tmp_path, link_tally):
    """Four column leaves saved under 2x2, restored under 1x4: eight read
    pieces, each wanted by two devices.  One host put a piece; the host
    link carries the state once and the siblings get their halves device
    to device; no device's link has taken over a piece more than another's."""
    from torchsnapshot_tpu.obs import tracer

    values = {f"w{i}": _value("cols", seed=20 + i) for i in range(4)}
    saved = {k: _put("cols", _mesh(2, 2), v) for k, v in values.items()}
    Snapshot.take(str(tmp_path / "s"), {"app": StateDict(**saved)})
    templates = {k: _put("cols", _mesh(1, 4), np.zeros_like(v)) for k, v in values.items()}
    dest = StateDict(**templates)
    with knobs.override_device_unpack(True), knobs.override_trace(True), _Gained() as g:
        tracer.get_tracer().reset()
        Snapshot(str(tmp_path / "s")).restore({"app": dest})
        spans = tracer.get_tracer().spans()
    for k, v in values.items():
        _assert_restored(dest[k], v, templates[k])
    state_bytes = sum(v.nbytes for v in values.values())
    piece = values["w0"].nbytes // 2
    puts = [s for s in spans if s.name == "h2d/put"]
    moves = [s for s in spans if s.name == "d2d/put"]
    assert len(puts) == 8 and {s.attrs["bytes"] for s in puts} == {piece}
    assert len(moves) == 8 and {s.attrs["bytes"] for s in moves} == {piece // 2}
    assert all(s.attrs["src"] != s.attrs["dst"] for s in moves)
    assert (g.link, g.handoff, g.direct, g.host) == (state_bytes, state_bytes // 2, state_bytes, 0)
    assert (g.cuts, g.swallowed) == (16, 0)
    # the tally is what the puts' spans say, a device
    taken = link_tally.snapshot()
    assert sum(taken.values()) == state_bytes
    for dev in jax.devices()[:4]:
        assert taken.get(dev.id, 0) == sum(
            s.attrs["bytes"] for s in puts if s.attrs["device"] == dev.id
        )
    assert max(taken.values()) - min(taken.get(d.id, 0) for d in jax.devices()[:4]) <= piece


def test_the_link_tally_loses_no_charge_under_many_workers(link_tally):
    """More workers than cores charge equal pieces to a pair of devices
    each: every charge is counted, and no link is ever a piece ahead of
    its pair's other by more than one."""
    import sys
    import threading

    devs = jax.devices()[:4]
    pairs, rounds, workers = [devs[:2], devs[2:]], 400, 16
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(rounds):
                assert link_tally.charge_least(pairs[(k + i) % 2], 8) in pairs[(k + i) % 2]

        threads = [threading.Thread(target=work, args=(k,)) for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    taken = link_tally.snapshot()
    assert sum(taken.values()) == workers * rounds * 8
    for pair in pairs:
        assert abs(taken[pair[0].id] - taken[pair[1].id]) <= 8


def _mapped(tmp_path, nbytes):
    from torchsnapshot_tpu.storage.fs import mmap_read

    path = tmp_path / "piece"
    path.write_bytes(bytes(range(256)) * (nbytes // 256))
    return mmap_read(str(path), None)


@pytest.mark.parametrize("refused", [False, True], ids=["populated", "mlock_refused"])
def test_a_mapped_piece_is_populated_in_one_call_and_a_refusal_changes_nothing(
    tmp_path, monkeypatch, refused
):
    from torchsnapshot_tpu.obs import tracer
    from torchsnapshot_tpu.preparers import sharded

    calls = []

    class Libc:
        def mlock(self, addr, n):
            calls.append(("mlock", addr.value, n.value))
            return -1 if refused else 0

        def munlock(self, addr, n):
            calls.append(("munlock", addr.value, n.value))
            return 0

    monkeypatch.setattr(sharded, "_libc", Libc)
    import mmap

    page = mmap.PAGESIZE
    mapped = _mapped(tmp_path, 3 * page)
    piece = mapped[100 : 100 + 2 * page].view(np.float32)  # starts and ends inside pages
    refused0 = _counter("reshard.populate_refused")
    with knobs.override_trace(True):
        tracer.get_tracer().reset()
        sharded._populate(piece)
        sharded._populate(np.array(piece))  # on the heap: touched by its read
        spans = [s for s in tracer.get_tracer().spans() if s.name == "reshard/populate"]
    # the pages that hold the piece, whole: from the page start below it
    start = piece.ctypes.data - piece.ctypes.data % page
    assert start == piece.ctypes.data - 100
    whole = (start, 100 + 2 * page)
    # a refusal locked nothing, so there is nothing to unlock: it counts,
    # and the span says so
    assert calls == [("mlock", *whole)] + ([] if refused else [("munlock", *whole)])
    assert _counter("reshard.populate_refused") - refused0 == int(refused)
    assert [s.attrs["bytes"] for s in spans] == [2 * page]
    assert [s.attrs.get("refused", False) for s in spans] == [refused]
    np.testing.assert_array_equal(piece.view(np.uint8), mapped[100 : 100 + 2 * page])


def test_populate_asks_the_real_libc_and_leaves_the_bytes_alone(tmp_path):
    from torchsnapshot_tpu.preparers import sharded

    mapped = _mapped(tmp_path, 1 << 20)
    want = bytes(mapped)
    sharded._populate(mapped.view(np.float32).reshape(512, 512))
    assert bytes(mapped) == want


def test_a_restore_compiles_one_cut_a_column_shape(tmp_path):
    """The box's start is a runtime argument: the two halves of a saved
    shard, and every leaf of the same shape, share one program."""
    device_pack._jitted_cut.cache_clear()
    values = {f"w{i}": _value("cols", seed=10 + i) for i in range(3)}
    saved = {k: _put("cols", _mesh(2, 2), v) for k, v in values.items()}
    Snapshot.take(str(tmp_path / "s"), {"app": StateDict(**saved)})
    dest = StateDict(**{k: _put("cols", _mesh(1, 4), np.zeros_like(v)) for k, v in values.items()})
    with knobs.override_device_unpack(True), _Gained() as g:
        Snapshot(str(tmp_path / "s")).restore({"app": dest})
    for k, v in values.items():
        np.testing.assert_array_equal(np.asarray(dest[k]), v)
    assert g.cuts == 12 and device_pack._jitted_cut.cache_info().currsize == 1


@pytest.mark.parametrize(
    "starts, sizes", [((0, 9), (8, 8)), ((-1, 0), (8, 8)), ((0,), (8,)), ((1, 0), (8, 4))]
)
def test_a_box_outside_the_wide_array_raises_and_does_not_count(starts, sizes):
    wide = jnp.arange(128, dtype=jnp.float32).reshape(8, 16)
    before = device_pack.CALL_COUNTS["unpack"]
    with pytest.raises(ValueError, match="outside"):
        device_pack.cut_box_on_device(wide, starts, sizes)
    assert device_pack.CALL_COUNTS["unpack"] == before


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8", "bool", "complex64"])
def test_a_cut_moves_words_of_every_dtype(dtype):
    import ml_dtypes  # noqa: F401 — registers bfloat16

    raw = np.random.default_rng(11).integers(0, 2, size=(4, 12)).astype(np.dtype(dtype))
    out = device_pack.cut_box_on_device(jnp.asarray(raw), (1, 4), (2, 8))
    assert out.dtype == raw.dtype
    np.testing.assert_array_equal(np.asarray(out), raw[1:3, 4:12])


# A cut is handed its starts as numpy scalars, which jit transfers inside the
# call: ``device_unpack.arg_puts`` counts them where they are made (PR 37: the
# one-vector-a-piece form the slabs take was measured on the four-chip cell,
# read slower, and was taken out again).

@pytest.mark.parametrize(
    "dtype", ["float32", "int32", "bfloat16", "float16", "int8", "bool", "complex64"]
)
def test_a_pieces_cuts_share_a_program_and_count_the_scalars_they_are_handed(dtype):
    import ml_dtypes  # noqa: F401 — registers bfloat16

    raw = np.random.default_rng(12).integers(0, 2, size=(6, 16)).astype(np.dtype(dtype))
    wide = jnp.asarray(raw)
    boxes = [((0, 4 * i), (6, 4)) for i in range(4)] + [((2, 3), (3, 9))]
    device_pack._jitted_cut.cache_clear()
    before = _counter("device_unpack.arg_puts"), device_pack.CALL_COUNTS["unpack"]
    outs = [device_pack.cut_box_on_device(wide, st, sz) for st, sz in boxes]
    # two starts a cut of a matrix, one cut a box
    assert _counter("device_unpack.arg_puts") - before[0] == 10
    assert device_pack.CALL_COUNTS["unpack"] - before[1] == 5
    for out, ((r, c), (nr, nc)) in zip(outs, boxes):
        assert out.dtype == raw.dtype
        assert np.asarray(out).tobytes() == raw[r : r + nr, c : c + nc].tobytes()
    # the four quarters share a program: no executable an offset value
    assert device_pack._jitted_cut.cache_info().currsize == 2
    quarter = device_pack._jitted_cut((6, 16), str(raw.dtype), (6, 4))
    assert quarter._cache_size() == 1


@pytest.mark.parametrize(
    "starts, sizes", [((0, 9), (8, 8)), ((-1, 0), (8, 8)), ((0,), (8,)), ((1, 0), (8, 4))]
)
def test_a_box_outside_the_piece_raises_with_nothing_handed_over(starts, sizes):
    wide = jnp.arange(128, dtype=jnp.float32).reshape(8, 16)
    before = _counter("device_unpack.arg_puts")
    with pytest.raises(ValueError, match="outside"):
        device_pack.cut_box_on_device(wide, starts, sizes)
    assert _counter("device_unpack.arg_puts") == before


def test_a_restore_counts_two_scalars_a_cut_and_none_for_a_row_piece(tmp_path):
    """Column leaves and a row leaf saved under 2x2, restored under 1x4:
    the counter gains the cuts' starts, a row piece (a box as it lies, no
    program) nothing."""
    values = {f"w{i}": _value("cols", seed=30 + i) for i in range(3)}
    values["r"] = _value("rows", seed=33)
    kinds = {k: ("rows" if k == "r" else "cols") for k in values}
    saved = {k: _put(kinds[k], _mesh(2, 2), v) for k, v in values.items()}
    Snapshot.take(str(tmp_path / "s"), {"app": StateDict(**saved)})
    templates = {k: _put(kinds[k], _mesh(1, 4), np.zeros_like(v)) for k, v in values.items()}
    dest = StateDict(**templates)
    before = _counter("device_unpack.arg_puts")
    with knobs.override_device_unpack(True), _Gained() as g:
        Snapshot(str(tmp_path / "s")).restore({"app": dest})
    for k, v in values.items():
        _assert_restored(dest[k], v, templates[k])
    # 3 column leaves x 2 pieces x 2 boxes; the row leaf's pieces are boxes as they lie
    assert (g.cuts, g.swallowed, g.host) == (12, 0, 0)
    assert _counter("device_unpack.arg_puts") - before == 24


def test_the_ab_script_restores_one_snapshot_by_each_mechanism(tmp_path, capsys):
    """``benchmarks/reshard_ab.py`` at tiny widths: every restore of a
    variant takes that variant's path and none other, and comes out
    against the reference."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "reshard_ab", os.path.join(os.path.dirname(__file__), "..", "benchmarks", "reshard_ab.py")
    )
    reshard_ab = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reshard_ab)
    out = tmp_path / "ab.jsonl"
    order = "direct,twice,populate,twice,direct"
    argv = ["--tiny", "--rounds", "1", "--parent", "1", "--order", order, "--out", str(out)]
    assert reshard_ab.main(argv) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["answers_checked"] == 3 and not any(summary["wrong"].values())
    state_bytes = summary["state_bytes"]
    runs = [json.loads(line) for line in out.read_text().splitlines()]
    assert [r["variant"] for r in runs] == (
        ["direct", "twice", "populate"] + order.split(",") + ["parent"]
        + ["direct", "twice", "populate"]
    )
    for r in runs:
        took_direct = r["variant"] in ("direct", "twice")
        assert r["reshard.direct_bytes"] == (state_bytes if took_direct else 0)
        assert r["reshard.host_alloc_bytes"] == (0 if took_direct else state_bytes)
        assert r["exceptions.swallowed"] == 0
    # the package puts a piece once and hands the siblings' boxes on; the
    # script's ``twice`` sends the piece to every device that shares it
    link = {r["variant"]: r["reshard.link_bytes"] for r in runs}
    handoff = {r["variant"]: r["reshard.handoff_bytes"] for r in runs}
    assert link["direct"] == state_bytes < link["twice"] and link["populate"] == 0
    assert 0 < handoff["direct"] <= link["twice"] - state_bytes  # a half for each piece sent twice
    assert handoff["twice"] == handoff["populate"] == handoff["parent"] == 0
    traced = {r["variant"]: r["spans"] for r in runs if "spans" in r}
    for name in ("direct", "twice"):
        assert "reshard/direct" in traced[name] and "reshard/scatter" not in traced[name]
    assert "d2d/put" in traced["direct"] and "d2d/put" not in traced["twice"]
    assert traced["direct"]["h2d/put"][0] < traced["twice"]["h2d/put"][0]
    assert "reshard/scatter" in traced["populate"] and "reshard/direct" not in traced["populate"]
