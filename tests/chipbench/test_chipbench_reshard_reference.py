"""``Snapshot.restore`` under another layout against the plain reference
(``chipbench/reference/reshard.py``): for every leaf of a seeded train state
at tiny widths, every device holds, bit for bit, ``whole[index]`` of its
index in the target sharding, whatever layout the state was saved under.
And the guarantees the four-chip configuration states: a shard that several
devices hold is written once, and the marker is written last."""

import os

import jax
import numpy as np
import pytest

from chipbench import state
from chipbench.reference import reshard

LAYOUTS = [((2, 2), (1, 4)), ((2, 2), (4, 1)), ((2, 2), (2, 2)), ((1, 4), (2, 2))]


@pytest.fixture(scope="module")
def conf(repo, dense_lm):
    conf = state.load_json(os.path.join(repo, "chipbench/configs/ouro-2.6b-4chip.json"))
    conf.update(dense_lm.TINY)
    return conf


@pytest.fixture(scope="module")
def factories(conf, dense_lm):
    made = {}

    def factory(mesh):
        if mesh not in made:
            made[mesh] = dense_lm.factory(conf, state.build_mesh(jax.devices(), *mesh))
        return made[mesh]

    return factory


def _app(tree):
    from torchsnapshot_tpu import PyTreeState

    return {"ts": PyTreeState(tree)}


@pytest.mark.parametrize("saved,target", LAYOUTS, ids=lambda m: "x".join(map(str, m)))
def test_every_device_holds_what_the_reference_gives_it(factories, tmp_path, saved, target):
    from torchsnapshot_tpu import Snapshot

    handed = factories(saved).make(2**31 + 41)
    whole = reshard.gather(state.array_leaves(handed))
    Snapshot.take(str(tmp_path / "snap"), _app(handed))
    del handed
    template = factories(target).make(5)  # other values, the target's layout
    want_layout = state.layout_of(template)
    app = _app(template)
    del template
    Snapshot(str(tmp_path / "snap")).restore(app)
    restored = state.array_leaves(app["ts"].tree)
    assert len(restored) == len(whole) > 20
    sharded = 0
    for ref, got, (shape, dtype, sharding) in zip(whole, restored, want_layout):
        assert (tuple(got.shape), str(got.dtype)) == (shape, dtype)
        assert got.sharding.is_equivalent_to(sharding, got.ndim)
        assert reshard.differing_shards(ref, got) == []
        sharded += len({str(s.index) for s in got.addressable_shards}) > 1
    # the tp axis really cuts leaves under the target; under 4x1 every
    # device holds every leaf whole
    assert sharded > 10 if target[1] > 1 else sharded == 0


def test_the_reference_names_a_shard_that_holds_other_bytes(factories):
    """The comparison shown to fail: one device's shard altered, one leaf
    laid out otherwise than the reference is asked about."""
    tree = factories((1, 4)).make(7)
    leaf = max(state.array_leaves(tree), key=lambda x: x.nbytes)
    whole = np.asarray(leaf)
    assert reshard.differing_shards(whole, leaf) == []
    shards = [np.array(s.data) for s in leaf.addressable_shards]
    shards[2].flat[0] += 1
    altered = jax.make_array_from_single_device_arrays(
        leaf.shape, leaf.sharding,
        [jax.device_put(a, s.device) for a, s in zip(shards, leaf.addressable_shards)],
    )
    wrong = reshard.differing_shards(whole, altered)
    assert len(wrong) == 1 and str(leaf.addressable_shards[2].device) in wrong[0]
    rolled = np.roll(whole, 1, axis=-1)
    assert len(reshard.differing_shards(rolled, leaf)) == len(leaf.addressable_shards)


def test_a_replicated_shard_is_written_once_and_the_marker_last(factories, tmp_path, monkeypatch):
    from torchsnapshot_tpu import Snapshot, knobs
    from torchsnapshot_tpu.preparers.overlap import index_to_box
    from torchsnapshot_tpu.storage.fs import FSStoragePlugin

    written = []
    write = FSStoragePlugin.write

    async def recording(self, write_io):
        await write(self, write_io)
        written.append(write_io.path)

    monkeypatch.setattr(FSStoragePlugin, "write", recording)
    handed = factories((2, 2)).make(2**31 + 43)
    leaves = [x for x in state.array_leaves(handed) if len(x.sharding.device_set) > 1]
    unique_boxes = sum(
        len({index_to_box(i, x.shape) for i in x.sharding.devices_indices_map(x.shape).values()})
        for x in leaves
    )
    held = sum(len(x.addressable_shards) for x in leaves)
    with knobs.override_disable_batching(True):  # one payload file a box
        Snapshot.take(str(tmp_path / "snap"), _app(handed))
    payloads = [p for p in written if p.startswith("sharded/")]
    assert len(payloads) == len(set(payloads)) == unique_boxes < held
    assert written[-1] == ".snapshot_metadata" and written.count(".snapshot_metadata") == 1
    on_disk = sum(
        os.path.getsize(os.path.join(base, f))
        for base, _dirs, files in os.walk(tmp_path / "snap" / "sharded") for f in files
    )
    assert on_disk == sum(x.nbytes for x in leaves)  # each byte once, not once a replica
