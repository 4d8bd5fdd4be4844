"""A leaf over ``MAX_CHUNK_SIZE_BYTES`` restored straight to the device
(``preparers/array.py``: ``_chunks_to_device``, ``_DeviceChunkJoin``): each
chunk put as it is read, on a consume worker, the row ranges joined there by
one program, no whole-array host buffer.  On the CPU mesh, where the
predicate refuses (a CPU "device" is host memory), the device path is
applied by monkeypatching ``_chunks_to_device``, and the predicate's other
rules by monkeypatching ``_on_cpu``; the chunk size is cut to 512 B as the
DLRM state's tests cut it."""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict, knobs, obs
from torchsnapshot_tpu.io_types import Future
from torchsnapshot_tpu.manifest import ChunkedArrayEntry, Shard
from torchsnapshot_tpu.preparers import array
from torchsnapshot_tpu.obs import tracer

CHUNK = 512
COUNTERS = (
    obs.CHUNKED_HOST_ASSEMBLY_BYTES,
    obs.CHUNKED_DEVICE_PUT_BYTES,
    obs.CHUNKED_READ_BYTES,
    obs.EXCEPTIONS_SWALLOWED,
)


@pytest.fixture
def small_chunks():
    """Leaves of a few hundred bytes are chunked, and every chunk is an
    object of its own (none joins a slab)."""
    with knobs.override_max_chunk_size_bytes(CHUNK), \
            knobs.override_slab_size_threshold_bytes(64):
        yield


@pytest.fixture
def device_path(monkeypatch):
    """The device path for every jax template, as on an accelerator."""
    monkeypatch.setattr(
        array, "_chunks_to_device", lambda entry, obj_out: array._is_jax_array(obj_out)
    )


@pytest.fixture
def accelerator(monkeypatch):
    """The predicate as it decides off the CPU backend."""
    monkeypatch.setattr(array, "_on_cpu", lambda sharding: False)


@pytest.fixture
def traced():
    tracer.set_tracing(True)
    tracer.get_tracer().reset()
    yield lambda: tracer.get_tracer().spans()
    tracer.set_tracing(False)
    tracer.get_tracer().reset()


def _counters():
    return obs.metrics_snapshot()["counters"]


class _Gained:
    def __enter__(self):
        self.before = _counters()
        return self

    def __exit__(self, *exc):
        now = _counters()
        self.present = set(now)
        self.found = {n: now.get(n, 0) - self.before.get(n, 0) for n in COUNTERS}


def _value(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype) == np.int32:
        return rng.integers(-(2**31), 2**31 - 1, shape, dtype=np.int32)
    return rng.standard_normal(shape).astype(dtype)


def _take(tmp_path, leaves):
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"s": StateDict(**{k: jnp.asarray(v) for k, v in leaves.items()})})
    return path


def _restore(path, templates):
    app = {"s": StateDict(**templates)}
    Snapshot(path).restore(app)
    return app["s"]


def _zeros_like(leaves, sharding=None):
    return {
        k: jax.device_put(np.zeros(v.shape, v.dtype), sharding) for k, v in leaves.items()
    }


def _chunk_rows(path, name):
    entry = Snapshot(path).metadata.manifest[f"0/s/{name}"]
    assert isinstance(entry, ChunkedArrayEntry)
    return [c.sizes[0] for c in entry.chunks]


# ------------------------------------------------------ the round trip


@pytest.mark.parametrize(
    "dtype,rows",
    [
        # 32-byte rows: 16 a chunk, the last of 8
        (np.float32, [16, 16, 8]),
        (np.int32, [16, 16, 8]),
        # 16-byte rows: 32 a chunk, the last of 8
        (ml_dtypes.bfloat16, [32, 8]),
    ],
    ids=["float32", "int32", "bfloat16"],
)
def test_a_chunked_leaf_round_trips_bit_for_bit(tmp_path, small_chunks, device_path, dtype, rows):
    leaves = {"table": _value((40, 8), dtype)}
    path = _take(tmp_path, leaves)
    assert _chunk_rows(path, "table") == rows
    with _Gained() as g:
        got = _restore(path, _zeros_like(leaves))["table"]
    assert isinstance(got, jax.Array) and got.dtype == np.dtype(dtype)
    assert np.asarray(got).tobytes() == leaves["table"].tobytes()
    assert g.found[obs.CHUNKED_DEVICE_PUT_BYTES] == leaves["table"].nbytes


def test_a_single_chunk_leaf_round_trips(tmp_path, small_chunks, device_path, traced):
    # one row of 800 B is over the limit: one chunk of one row, no join
    # program, but the Future is still set and the template donated
    leaves = {"row": _value((1, 200), np.float32)}
    path = _take(tmp_path, leaves)
    assert _chunk_rows(path, "row") == [1]
    got = _restore(path, _zeros_like(leaves))["row"]
    assert np.asarray(got).tobytes() == leaves["row"].tobytes()
    joins = [s for s in traced() if s.name == "chunk/join"]
    assert [(s.attrs["bytes"], s.attrs["chunks"]) for s in joins] == [(800, 1)]


def test_a_tail_that_joins_a_slab_still_reaches_the_device(tmp_path, device_path):
    # under the default slab threshold the 8-row tail (256 B) is a slab
    # member: the slab's host unpack hands it to the chunk's consumer
    leaves = {"table": _value((40, 8), np.float32), "bias": _value((3,), np.float32)}
    with knobs.override_max_chunk_size_bytes(CHUNK):
        path = _take(tmp_path, leaves)
        with _Gained() as g:
            got = _restore(path, _zeros_like(leaves))
    for k, v in leaves.items():
        assert np.asarray(got[k]).tobytes() == v.tobytes()
    assert g.found[obs.CHUNKED_DEVICE_PUT_BYTES] == leaves["table"].nbytes
    assert g.found[obs.CHUNKED_HOST_ASSEMBLY_BYTES] == 0


# ------------------------------------------- counters, spans and threads


def test_the_counters_and_spans_of_the_device_path(tmp_path, small_chunks, device_path, traced):
    leaves = {
        "a": _value((40, 8), np.float32, 1),
        "b": _value((40, 8), np.float32, 2),
        "c": _value((40, 8), ml_dtypes.bfloat16, 3),
    }
    path = _take(tmp_path, leaves)
    chunks = {k: _chunk_rows(path, k) for k in leaves}
    tracer.get_tracer().reset()
    with _Gained() as g:
        got = _restore(path, _zeros_like(leaves))
    for k, v in leaves.items():
        assert np.asarray(got[k]).tobytes() == v.tobytes()
    total = sum(v.nbytes for v in leaves.values())
    # taken, never raised: present and 0
    assert obs.CHUNKED_HOST_ASSEMBLY_BYTES in g.present
    assert g.found == {
        obs.CHUNKED_HOST_ASSEMBLY_BYTES: 0,
        obs.CHUNKED_DEVICE_PUT_BYTES: total,
        obs.CHUNKED_READ_BYTES: total,
        obs.EXCEPTIONS_SWALLOWED: 0,
    }
    spans = traced()
    by_id = {s.span_id: s for s in spans}
    puts = [s for s in spans if s.name == "chunk/put"]
    joins = [s for s in spans if s.name == "chunk/join"]
    assert sorted(s.attrs["rows"] for s in puts) == sorted(r for rows in chunks.values() for r in rows)
    assert sum(s.attrs["bytes"] for s in puts) == total
    assert sorted((s.attrs["bytes"], s.attrs["chunks"]) for s in joins) == sorted(
        (v.nbytes, len(chunks[k])) for k, v in leaves.items()
    )
    assert not [s for s in spans if s.name in ("chunk/assemble", "h2d/put")]
    lands = [s for s in spans if s.name == "chunk/land"]
    for s in puts + joins + lands:
        assert s.thread_name.startswith("tsnp-consume"), s.thread_name
    # a put and the join that follows it both lie inside the worker's
    # consume/materialize: the loop runs neither
    assert {by_id[s.parent_id].name for s in puts} == {"consume/materialize"}
    assert {by_id[s.parent_id].name for s in joins} == {"consume/materialize"}
    # one chunk/land a group of chunks a worker places: after the group's
    # puts, before any join, holding the group's bytes
    assert {by_id[s.parent_id].name for s in lands} == {"consume/materialize"}
    assert {s.attrs["link"] for s in puts + lands} == {"h2d"}
    placed = []
    for land in lands:
        group = [
            s for s in puts
            if s.parent_id == land.parent_id and s.end_ns <= land.start_ns
            and s.span_id not in placed
        ]
        placed += [s.span_id for s in group]
        assert land.attrs["chunks"] == len(group) >= 1
        assert land.attrs["bytes"] == sum(s.attrs["bytes"] for s in group)
    assert sorted(placed) == sorted(s.span_id for s in puts)
    assert sum(s.attrs["bytes"] for s in lands) == total


def test_with_tracing_off_the_device_path_records_no_land(tmp_path, small_chunks, device_path):
    leaves = {"a": _value((40, 8), np.float32, 1)}
    path = _take(tmp_path, leaves)
    tracer.get_tracer().reset()
    got = _restore(path, _zeros_like(leaves))
    assert np.asarray(got["a"]).tobytes() == leaves["a"].tobytes()
    assert len(tracer.get_tracer()) == 0


def test_the_join_compiles_once_for_leaves_cut_alike(tmp_path, small_chunks, device_path):
    leaves = {k: _value((40, 8), np.float32, i) for i, k in enumerate("abcde")}
    path = _take(tmp_path, leaves)
    array._compiled_join.cache_clear()
    _restore(path, _zeros_like(leaves))
    assert array._compiled_join.cache_info().currsize == 1
    assert array._compiled_join.cache_info().hits == 4


# ------------------------------------------------------- the donation


def test_the_template_is_donated_only_after_the_future_holds_the_result(monkeypatch):
    table = _value((40, 8), np.float32)
    ranges = [(0, 16), (16, 32), (32, 40)]
    entry = ChunkedArrayEntry(
        dtype="float32", shape=[40, 8], replicated=False,
        chunks=[Shard(offsets=[lo, 0], sizes=[hi - lo, 8], location=f"t_{lo}_{hi}") for lo, hi in ranges],
    )
    template = jnp.zeros((40, 8), jnp.float32)
    fut = Future()
    leaf = array._DeviceChunkJoin(template, fut, entry)
    seen = []
    real = array.donate_template

    def donate(arr):
        seen.append((arr is template, fut.done, fut.obj is not None))
        real(arr)

    monkeypatch.setattr(array, "donate_template", donate)
    gate = array._ChunkGate()
    with knobs.override_restore_donate("1"):
        for i, (lo, hi) in reversed(list(enumerate(ranges))):
            assert not fut.done and not seen
            gate.put(leaf, i, table[lo:hi])
    assert seen == [(True, True, True)]
    assert template.is_deleted()
    assert np.asarray(fut.obj).tobytes() == table.tobytes()


def test_workers_that_put_at_once_join_a_leaf_exactly_once(monkeypatch):
    # the count of chunks still to come is shared by the workers: a lost
    # update would join twice, or never
    import sys
    import threading

    rows = 64
    table = _value((rows, 4), np.float32)
    entry = ChunkedArrayEntry(
        dtype="float32", shape=[rows, 4], replicated=False,
        chunks=[Shard(offsets=[r, 0], sizes=[1, 4], location=f"t_{r}") for r in range(rows)],
    )
    fut = Future()
    leaf = array._DeviceChunkJoin(jnp.zeros((rows, 4), jnp.float32), fut, entry)
    finished = []
    real = leaf.finish
    monkeypatch.setattr(leaf, "finish", lambda: (finished.append(1), real()))
    gate = array._ChunkGate()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(target=lambda k=k: [gate.put(leaf, r, table[r : r + 1]) for r in range(k, rows, 16)])
            for k in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert finished == [1]
    assert np.asarray(fut.obj).tobytes() == table.tobytes()


def _leaves_of(n, rows, seed=0):
    """``n`` (rows, 4) float32 leaves cut one row a chunk, with their
    joins and values."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        table = rng.standard_normal((rows, 4)).astype(np.float32)
        entry = ChunkedArrayEntry(
            dtype="float32", shape=[rows, 4], replicated=False,
            chunks=[Shard(offsets=[r, 0], sizes=[1, 4], location=f"t_{r}") for r in range(rows)],
        )
        leaf = array._DeviceChunkJoin(jnp.zeros((rows, 4), jnp.float32), Future(), entry)
        out.append((leaf, table))
    return out


class _Watched(array._ChunkGate):
    """A gate that records the most bytes it ever held admitted."""

    most = 0

    def _admit(self, leaf):
        parked = super()._admit(leaf)
        self.most = max(self.most, self.admitted_bytes)
        return parked


@pytest.mark.parametrize("bound_leaves", [0.5, 1, 2])
def test_the_gate_holds_the_unjoined_bytes_to_its_bound_whatever_the_order(monkeypatch, bound_leaves):
    # chunks of eight leaves arrive from 16 threads in a shuffled order; a
    # leaf over the bound is admitted alone, and every leaf is joined once
    import random
    import sys
    import threading

    rows = 8
    leaves = _leaves_of(8, rows)
    nbytes = rows * 4 * 4
    monkeypatch.setattr(array, "_UNJOINED_MAX_BYTES", int(bound_leaves * nbytes))
    gate = _Watched()
    joins = {"now": 0, "most": 0}
    real_finish = array._DeviceChunkJoin.finish

    def finish(leaf):
        joins["now"] += 1
        joins["most"] = max(joins["most"], joins["now"])
        try:
            real_finish(leaf)
        finally:
            joins["now"] -= 1

    monkeypatch.setattr(array._DeviceChunkJoin, "finish", finish)
    work = [(leaf, r, table[r : r + 1]) for leaf, table in leaves for r in range(rows)]
    random.Random(7).shuffle(work)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(target=lambda k=k: [gate.put(*w) for w in work[k::16]])
            for k in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert gate.most == max(1, int(bound_leaves)) * nbytes
    assert joins["most"] == 1  # a join's output and its template: one pair at a time
    assert gate.admitted_bytes == 0 and gate.queue == []
    for leaf, table in leaves:
        assert leaf.fut.done and leaf.parked == []
        assert np.asarray(leaf.fut.obj).tobytes() == table.tobytes()


def test_a_restore_under_a_small_bound_parks_and_still_restores_every_leaf(
    tmp_path, small_chunks, device_path, monkeypatch
):
    leaves = {k: _value((40, 8), np.float32, i) for i, k in enumerate("abcde")}
    path = _take(tmp_path, leaves)
    monkeypatch.setattr(array, "_UNJOINED_MAX_BYTES", 2 * 1280)
    made = []

    def gate_for(executor):
        with array._GATES_LOCK:
            if executor not in array._GATES:
                array._GATES[executor] = _Watched()
                made.append(array._GATES[executor])
            return array._GATES[executor]

    monkeypatch.setattr(array, "_gate_for", gate_for)
    with _Gained() as g:
        got = _restore(path, _zeros_like(leaves))
    for k, v in leaves.items():
        assert np.asarray(got[k]).tobytes() == v.tobytes()
    assert g.found[obs.CHUNKED_DEVICE_PUT_BYTES] == 5 * 1280
    assert made and all(gt.most <= 2 * 1280 for gt in made)
    assert all(gt.admitted_bytes == 0 and gt.queue == [] for gt in made)


def test_a_restore_donates_the_templates(tmp_path, small_chunks, device_path):
    leaves = {"table": _value((40, 8), np.float32)}
    path = _take(tmp_path, leaves)
    templates = _zeros_like(leaves)
    with knobs.override_restore_donate("1"):
        got = _restore(path, templates)["table"]
    assert templates["table"].is_deleted()
    assert np.asarray(got).tobytes() == leaves["table"].tobytes()


def test_a_join_that_fails_leaves_the_template_whole(tmp_path, small_chunks, device_path, monkeypatch):
    leaves = {"table": _value((40, 8), np.float32)}
    path = _take(tmp_path, leaves)

    def failing(sharding, shapes, dtype):
        def join(*parts):
            raise RuntimeError("injected join failure")

        return join

    monkeypatch.setattr(array, "_compiled_join", failing)
    templates = _zeros_like(leaves)
    template = templates["table"]
    with knobs.override_restore_donate("1"):
        with pytest.raises(RuntimeError, match="injected join failure"):
            _restore(path, templates)
    assert not template.is_deleted()
    assert not np.asarray(template).any()


# ------------------------------------- what keeps today's host path


def _host_path(tmp_path, template, read=None):
    """Restore a (40, 8) float32 chunked leaf into ``template``; the host
    path's counters are what the run has to show."""
    table = _value((40, 8), np.float32)
    path = _take(tmp_path, {"table": table})
    with _Gained() as g:
        if read is None:
            got = _restore(path, {"table": template})["table"]
        else:
            got = read(Snapshot(path), template)
    got_np = np.asarray(got)
    assert got_np.tobytes() == table.astype(got_np.dtype).tobytes()
    assert g.found[obs.CHUNKED_DEVICE_PUT_BYTES] == 0
    assert g.found[obs.CHUNKED_READ_BYTES] == table.nbytes
    return got, g.found


def test_a_numpy_template_is_still_assembled_in_place(tmp_path, small_chunks, accelerator):
    template = np.zeros((40, 8), np.float32)
    got, found = _host_path(tmp_path, template)
    assert got is template
    assert found[obs.CHUNKED_HOST_ASSEMBLY_BYTES] == 0


def test_no_template_assembles_on_the_host(tmp_path, small_chunks, accelerator):
    got, found = _host_path(
        tmp_path, None, read=lambda snap, _: snap.read_object("0/s/table")
    )
    assert isinstance(got, np.ndarray)
    assert found[obs.CHUNKED_HOST_ASSEMBLY_BYTES] == 40 * 8 * 4


def test_a_sharded_template_assembles_on_the_host(tmp_path, small_chunks, accelerator):
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    sharding = NamedSharding(mesh, P("x"))
    got, found = _host_path(tmp_path, jax.device_put(np.zeros((40, 8), np.float32), sharding))
    assert got.sharding == sharding
    assert found[obs.CHUNKED_HOST_ASSEMBLY_BYTES] == 40 * 8 * 4


def test_a_template_of_another_dtype_is_cast_on_the_host(tmp_path, small_chunks, accelerator):
    got, found = _host_path(tmp_path, jnp.zeros((40, 8), jnp.float16))
    assert got.dtype == np.float16
    assert found[obs.CHUNKED_HOST_ASSEMBLY_BYTES] == 40 * 8 * 4


def test_a_budgeted_read_object_is_tiled_on_the_host(tmp_path, small_chunks, accelerator):
    got, found = _host_path(
        tmp_path,
        jnp.zeros((40, 8), jnp.float32),
        read=lambda snap, t: snap.read_object("0/s/table", obj_out=t, memory_budget_bytes=256),
    )
    assert isinstance(got, jax.Array)
    assert found[obs.CHUNKED_HOST_ASSEMBLY_BYTES] == 40 * 8 * 4


# ------------------------------------------------------ the predicate


ENTRY = ChunkedArrayEntry(dtype="float32", shape=[40, 8], chunks=[], replicated=False)


def _on(devices, spec, dtype=np.float32, shape=(40, 8)):
    mesh = Mesh(np.array(jax.devices()[:devices]), ("x",))
    return jax.device_put(np.zeros(shape, dtype), NamedSharding(mesh, spec))


def test_the_predicate_refuses_every_template_on_the_cpu():
    assert not array._chunks_to_device(ENTRY, jnp.zeros((40, 8), jnp.float32))
    assert not array._chunks_to_device(ENTRY, _on(2, P()))


@pytest.mark.parametrize(
    "make,applies",
    [
        (lambda: jnp.zeros((40, 8), jnp.float32), True),
        (lambda: _on(1, P("x")), True),
        (lambda: _on(2, P()), True),  # replicated: a chunk takes it as it is
        (lambda: _on(2, P("x")), False),
        (lambda: _on(1, P(), dtype=np.int32), False),
        (lambda: _on(1, P(), dtype=ml_dtypes.bfloat16), False),
        (lambda: _on(1, P(), shape=(320,)), False),
        (lambda: np.zeros((40, 8), np.float32), False),
        (lambda: None, False),
    ],
    ids=[
        "one-device", "mesh-of-one", "replicated", "sharded", "other-dtype",
        "bfloat16", "other-shape", "numpy", "none",
    ],
)
def test_the_predicate_off_the_cpu_is_decided_by_sharding_dtype_and_shape(accelerator, make, applies):
    assert array._chunks_to_device(ENTRY, make()) is applies
