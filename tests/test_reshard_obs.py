"""What the sharded restore path records: ``reshard/plan``, ``reshard/scatter``
and ``reshard/assemble`` spans, one ``h2d/put`` span a device of a sharded
leaf, and the counter ``reshard.host_alloc_bytes`` (the local boxes' bytes)
beside ``bytes_read`` (what the sink gave); and, for a leaf on the direct
path, ``reshard/direct`` in place of ``reshard/scatter`` (an ``h2d/put`` a
host put and a ``d2d/put`` a box handed to a sibling under it) and the
counters ``reshard.direct_bytes`` in place of ``reshard.host_alloc_bytes``,
``reshard.link_bytes`` and ``reshard.handoff_bytes``."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import PyTreeState, Snapshot, knobs, obs
from torchsnapshot_tpu.obs import tracer

COUNTERS = (
    obs.RESHARD_HOST_ALLOC_BYTES, obs.RESHARD_DIRECT_BYTES, obs.BYTES_READ,
    obs.RESHARD_LINK_BYTES, obs.RESHARD_HANDOFF_BYTES,
)


def _mesh(dp, tp):
    return Mesh(np.array(jax.devices()[: dp * tp]).reshape(dp, tp), ("dp", "tp"))


def _state(mesh, seed):
    rng = np.random.default_rng(seed)

    def put(shape, spec):
        return jax.device_put(
            rng.standard_normal(shape).astype(np.float32), NamedSharding(mesh, P(*spec))
        )

    return {
        "cols": put((8, 16), (None, "tp")),  # a saved shard's halves go to two devices
        "rows": put((16, 8), ("tp", None)),  # dim-0 slabs
        "norm": put((32,), (None,)),  # replicated on every device
    }


def _counters():
    snap = obs.metrics_snapshot()["counters"]
    return {name: snap.get(name, 0) for name in COUNTERS}


def _restore_traced(tmp_path, device_unpack):
    """A state saved under 2x2 and restored under 1x4 with tracing on: the
    templates, the restored leaves, the spans and what the counters gained."""
    saved = _state(_mesh(2, 2), 1)
    Snapshot.take(str(tmp_path / "snap"), {"ts": PyTreeState(saved)})
    templates = _state(_mesh(1, 4), 2)
    app = {"ts": PyTreeState(dict(templates))}
    before = _counters()
    with knobs.override_trace(True), knobs.override_device_unpack(device_unpack):
        tracer.get_tracer().reset()
        Snapshot(str(tmp_path / "snap")).restore(app)
        spans = tracer.get_tracer().spans()
    gained = {name: after - before[name] for name, after in _counters().items()}
    return saved, templates, app["ts"].tree, spans, gained


@pytest.fixture
def restored(tmp_path):
    return _restore_traced(tmp_path, "auto")  # on CPU: the host path


@pytest.fixture
def restored_direct(tmp_path):
    return _restore_traced(tmp_path, True)


def test_a_sharded_leafs_device_puts_record_h2d_put_spans(restored):
    saved, templates, tree, spans, _ = restored
    for name, leaf in tree.items():
        assert np.array_equal(np.asarray(leaf), np.asarray(saved[name])), name
        assert leaf.sharding.is_equivalent_to(templates[name].sharding, leaf.ndim)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    assembles = by_name["reshard/assemble"]
    assert len(assembles) == len(by_name["reshard/plan"]) == 3
    assert sorted(s.attrs["devices"] for s in assembles) == [4, 4, 4]
    assert sum(s.attrs["bytes"] for s in assembles) == sum(x.nbytes for x in tree.values())
    puts = by_name["h2d/put"]
    inside = {a.span_id for a in assembles}
    assert all(p.parent_id in inside for p in puts)
    # a put a device for each leaf the tp axis cuts, one broadcasting put
    # for the replicated one
    per_device = [p for p in puts if "device" in p.attrs]
    assert sorted(p.attrs["device"] for p in per_device) == sorted(
        2 * [d.id for d in jax.devices()[:4]]
    )
    assert len(puts) == len(per_device) + 1
    assert sum(p.attrs["bytes"] for p in per_device) == saved["cols"].nbytes + saved["rows"].nbytes
    plans = by_name["reshard/plan"]
    # the plan's counts: saved shards read, unique local boxes, read requests
    assert sorted(s.attrs["saved_shards"] for s in plans) == [1, 2, 2]
    assert sorted(s.attrs["local_boxes"] for s in plans) == [1, 4, 4]
    assert all(s.attrs["read_reqs"] >= 1 for s in plans)
    scatters = by_name["reshard/scatter"]
    assert sum(s.attrs["bytes"] for s in scatters) == sum(x.nbytes for x in tree.values())
    hops = {s.span_id: s for s in by_name["consume/materialize"]}
    assert all(s.parent_id in hops and "queue_ns" in hops[s.parent_id].attrs for s in scatters)


def test_a_direct_leafs_pieces_record_reshard_direct_spans(restored_direct):
    saved, templates, tree, spans, gained = restored_direct
    for name, leaf in tree.items():
        assert np.array_equal(np.asarray(leaf), np.asarray(saved[name])), name
        assert leaf.sharding.is_equivalent_to(templates[name].sharding, leaf.ndim)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # no copy happens, so none is recorded; a leaf is still planned and
    # assembled once
    assert "reshard/scatter" not in by_name
    assembles = by_name["reshard/assemble"]
    assert len(assembles) == len(by_name["reshard/plan"]) == 3
    assert sum(s.attrs["bytes"] for s in assembles) == sum(x.nbytes for x in tree.values())
    # one reshard/direct a read piece (two saved shards for each leaf the tp
    # axis cuts, one for the replicated one), on a consume worker, inside
    # the hop that carries the wait for the worker
    directs = by_name["reshard/direct"]
    assert len(directs) == 5
    hops = {s.span_id: s for s in by_name["consume/materialize"]}
    assert all(d.parent_id in hops and "queue_ns" in hops[d.parent_id].attrs for d in directs)
    assert all(d.thread_name.startswith("tsnp-consume") for d in directs)
    # each mapped piece is populated in one call before its bytes are used,
    # in the same hop
    populates = by_name["reshard/populate"]
    assert sorted(p.parent_id for p in populates) == sorted(d.parent_id for d in directs)
    assert sum(p.attrs["bytes"] for p in populates) == sum(x.nbytes for x in tree.values())
    # every put and every hand-off is a child of its piece's span and names
    # its devices; the puts of a piece sum to the bytes it sent over the host
    # link, its hand-offs to the bytes it moved device to device, and
    # together they deliver one box a device
    puts, moves = by_name["h2d/put"], by_name["d2d/put"]
    inside = {d.span_id: d for d in directs}
    assert all(p.parent_id in inside and "device" in p.attrs for p in puts)
    assert all(m.parent_id in inside and m.attrs["src"] != m.attrs["dst"] for m in moves)
    for d in directs:
        mine = [p for p in puts if p.parent_id == d.span_id]
        handed = [m for m in moves if m.parent_id == d.span_id]
        assert len(mine) + len(handed) == d.attrs["devices"]
        assert sum(p.attrs["bytes"] for p in mine) == d.attrs["bytes"]
        assert sum(m.attrs["bytes"] for m in handed) == d.attrs["handoff_bytes"]
        assert {m.attrs["src"] for m in handed} <= {p.attrs["device"] for p in mine}
    # a column leaf's saved shard goes whole to ONE of the two devices that
    # share it (the leaf once over the link), both halves are cut there and
    # one is handed on; a row leaf's boxes are sent as they lie, each to its
    # device; the replicated leaf is put once and copied to the other three
    cut = [d for d in directs if d.attrs["cut"]]
    assert len(cut) == 2 and sum(d.attrs["bytes"] for d in cut) == saved["cols"].nbytes
    assert sum(d.attrs["handoff_bytes"] for d in cut) == saved["cols"].nbytes // 2
    plain = [d for d in directs if not d.attrs["cut"]]
    assert sum(d.attrs["bytes"] for d in plain) == saved["rows"].nbytes + saved["norm"].nbytes
    assert sum(d.attrs["handoff_bytes"] for d in plain) == 3 * saved["norm"].nbytes
    assert len(puts) == 7 and len(moves) == 5
    assert sorted(
        [p.attrs["device"] for p in puts] + [m.attrs["dst"] for m in moves]
    ) == sorted(3 * [d.id for d in jax.devices()[:4]])
    state_bytes = sum(x.nbytes for x in tree.values())
    assert gained[obs.RESHARD_DIRECT_BYTES] == state_bytes
    assert gained[obs.RESHARD_HOST_ALLOC_BYTES] == 0
    assert gained[obs.BYTES_READ] == state_bytes
    assert gained[obs.RESHARD_LINK_BYTES] == state_bytes
    assert gained[obs.RESHARD_HANDOFF_BYTES] == sum(m.attrs["bytes"] for m in moves)


def test_host_alloc_bytes_is_the_sum_of_the_local_boxes(restored):
    _, templates, tree, _, gained = restored
    local_boxes = 0
    for leaf in templates.values():
        shards = {str(s.index): s.data.nbytes for s in leaf.addressable_shards}
        local_boxes += sum(shards.values())  # one buffer a unique box
    state_bytes = sum(x.nbytes for x in tree.values())
    assert gained[obs.RESHARD_HOST_ALLOC_BYTES] == local_boxes == state_bytes
    assert gained[obs.RESHARD_DIRECT_BYTES] == 0
    assert gained[obs.RESHARD_LINK_BYTES] == gained[obs.RESHARD_HANDOFF_BYTES] == 0
    # every saved byte comes from the sink once: a saved shard whose halves
    # go to two devices is still one read
    assert gained[obs.BYTES_READ] == state_bytes


def test_the_spans_cost_nothing_recorded_with_tracing_off(tmp_path):
    saved = _state(_mesh(2, 2), 3)
    Snapshot.take(str(tmp_path / "snap"), {"ts": PyTreeState(saved)})
    app = {"ts": PyTreeState(_state(_mesh(1, 4), 4))}
    tracer.get_tracer().reset()
    before = _counters()
    Snapshot(str(tmp_path / "snap")).restore(app)
    assert not [s for s in tracer.get_tracer().spans() if s.name.startswith(("reshard/", "h2d/"))]
    # the counters are always on
    assert _counters()[obs.RESHARD_HOST_ALLOC_BYTES] - before[obs.RESHARD_HOST_ALLOC_BYTES] == sum(
        x.nbytes for x in saved.values()
    )
