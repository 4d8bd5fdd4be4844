"""Budgeted scheduler tests: budget admission, progress guarantee, pending
I/O semantics, error propagation (reference scheduler behavior,
scheduler.py:222-463)."""

import asyncio
import threading
import time

import pytest

from torchsnapshot_tpu import knobs
from torchsnapshot_tpu.io_types import (
    BufferConsumer,
    BufferStager,
    ReadIO,
    ReadReq,
    StoragePlugin,
    WriteIO,
    WriteReq,
)
from torchsnapshot_tpu.scheduler import (
    get_process_memory_budget_bytes,
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)


class TrackingStorage(StoragePlugin):
    def __init__(
        self,
        delay=0.0,
        fail_on=None,
        track_budget=False,
        budget_stats=None,
        budget_lock=None,
    ):
        self.writes = {}
        self.delay = delay
        self.fail_on = fail_on
        self.track_budget = track_budget
        # injectable live-byte accounting (test_scheduler_fuzz): the
        # SAME decrement-on-write-completion mechanism as track_budget,
        # but against a per-test stats dict instead of ChunkStager's
        # class counters
        self.budget_stats = budget_stats
        self.budget_lock = budget_lock or threading.Lock()
        self.concurrent = 0
        self.max_concurrent = 0
        self._lock = threading.Lock()

    async def write(self, write_io: WriteIO) -> None:
        with self._lock:
            self.concurrent += 1
            self.max_concurrent = max(self.max_concurrent, self.concurrent)
        if self.delay:
            await asyncio.sleep(self.delay)
        if self.fail_on == write_io.path:
            with self._lock:
                self.concurrent -= 1
            raise RuntimeError(f"injected failure on {write_io.path}")
        self.writes[write_io.path] = bytes(write_io.buf)
        if self.track_budget:
            with ChunkStager.lock:
                ChunkStager.live -= len(write_io.buf)
        if self.budget_stats is not None:
            with self.budget_lock:
                self.budget_stats["live"] -= len(write_io.buf)
        with self._lock:
            self.concurrent -= 1

    async def read(self, read_io: ReadIO) -> None:
        data = self.writes[read_io.path]
        if read_io.byte_range:
            s, e = read_io.byte_range
            data = data[s:e]
        read_io.buf = data

    async def delete(self, path: str) -> None:
        del self.writes[path]


class ChunkStager(BufferStager):
    live = 0
    peak = 0
    lock = threading.Lock()

    def __init__(self, payload: bytes):
        self.payload = payload

    async def stage_buffer(self, executor=None):
        with ChunkStager.lock:
            ChunkStager.live += len(self.payload)
            ChunkStager.peak = max(ChunkStager.peak, ChunkStager.live)
        return self.payload

    def get_staging_cost_bytes(self):
        return len(self.payload)


class CollectConsumer(BufferConsumer):
    def __init__(self, sink, key, cost=1):
        self.sink = sink
        self.key = key
        self.cost = cost

    async def consume_buffer(self, buf, executor=None):
        self.sink[self.key] = bytes(buf)

    def get_consuming_cost_bytes(self):
        return self.cost


def test_write_read_roundtrip():
    storage = TrackingStorage()
    reqs = [
        WriteReq(path=f"p{i}", buffer_stager=ChunkStager(bytes([i]) * (i + 1)))
        for i in range(20)
    ]
    pending = sync_execute_write_reqs(reqs, storage, 1 << 30, rank=0)
    pending.sync_complete()
    assert len(storage.writes) == 20
    assert pending.bytes_written == sum(i + 1 for i in range(20))

    sink = {}
    read_reqs = [
        ReadReq(path=f"p{i}", buffer_consumer=CollectConsumer(sink, f"p{i}"))
        for i in range(20)
    ]
    sync_execute_read_reqs(read_reqs, storage, 1 << 30, rank=0)
    assert sink == storage.writes


def test_oversized_item_progresses():
    # an item bigger than the whole budget must still be written
    storage = TrackingStorage()
    reqs = [WriteReq(path="big", buffer_stager=ChunkStager(b"x" * 1000))]
    pending = sync_execute_write_reqs(reqs, storage, memory_budget_bytes=10, rank=0)
    pending.sync_complete()
    assert storage.writes["big"] == b"x" * 1000


def test_no_head_of_line_blocking():
    # A head item bigger than the whole budget must not idle smaller
    # items that DO fit: admission scans the whole ready set (reference
    # scheduler.py:266-277).  Staging is largest-first, so "big" heads
    # the deque; it should stage LAST (only once the pipeline drains to
    # empty and the oversized-progress rule admits it).
    order = []
    lock = threading.Lock()

    class OrderStager(ChunkStager):
        def __init__(self, name, payload):
            super().__init__(payload)
            self.name = name

        async def stage_buffer(self, executor=None):
            with lock:
                order.append(self.name)
            return await super().stage_buffer(executor)

    storage = TrackingStorage(delay=0.005)
    reqs = [WriteReq(path="big", buffer_stager=OrderStager("big", b"B" * 1000))]
    reqs += [
        WriteReq(path=f"s{i}", buffer_stager=OrderStager(f"s{i}", b"s" * 50))
        for i in range(4)
    ]
    pending = sync_execute_write_reqs(reqs, storage, memory_budget_bytes=120, rank=0)
    pending.sync_complete()
    assert len(storage.writes) == 5
    assert storage.writes["big"] == b"B" * 1000
    assert order[0] != "big", f"oversized head staged first: {order}"
    assert order[-1] == "big", f"small items idled behind the head: {order}"


def test_read_no_head_of_line_blocking():
    # Same property on the read pipeline: a consuming cost larger than
    # the budget must not idle smaller reads behind it.  "big" heads the
    # request list; the fixed admission scans past it (it reaches the
    # storage layer LAST, via the pipeline-empty oversized rule), while
    # the old head-first admission read it FIRST and serialized the
    # smalls behind its budget debit.
    order = []
    lock = threading.Lock()

    class OrderStorage(TrackingStorage):
        async def read(self, read_io):
            with lock:
                order.append(read_io.path)
            await super().read(read_io)

    storage = OrderStorage()
    payloads = {"big": b"B" * 1000, **{f"s{i}": b"s" * 50 for i in range(4)}}
    for path, data in payloads.items():
        storage.writes[path] = data
    sink = {}
    read_reqs = [
        ReadReq(
            path=p,
            buffer_consumer=CollectConsumer(sink, p, cost=len(d)),
        )
        for p, d in payloads.items()
    ]
    sync_execute_read_reqs(read_reqs, storage, memory_budget_bytes=120, rank=0)
    assert sink == payloads
    assert order[0] != "big", f"oversized head read first: {order}"
    assert order[-1] == "big", f"small reads idled behind the head: {order}"


def test_io_concurrency_cap():
    storage = TrackingStorage(delay=0.02)
    with knobs.override_max_per_rank_io_concurrency(3):
        reqs = [
            WriteReq(path=f"p{i}", buffer_stager=ChunkStager(b"x"))
            for i in range(12)
        ]
        pending = sync_execute_write_reqs(reqs, storage, 1 << 30, rank=0)
        pending.sync_complete()
    assert storage.max_concurrent <= 3
    assert len(storage.writes) == 12


def test_write_error_propagates():
    storage = TrackingStorage(fail_on="p3")
    reqs = [
        WriteReq(path=f"p{i}", buffer_stager=ChunkStager(b"y" * 10))
        for i in range(6)
    ]
    with pytest.raises(RuntimeError, match="injected failure"):
        pending = sync_execute_write_reqs(reqs, storage, 1 << 30, rank=0)
        pending.sync_complete()


def test_read_error_propagates():
    storage = TrackingStorage()
    read_reqs = [ReadReq(path="missing", buffer_consumer=CollectConsumer({}, "k"))]
    with pytest.raises(KeyError):
        sync_execute_read_reqs(read_reqs, storage, 1 << 30, rank=0)


def test_budget_env_override():
    with knobs.override_per_rank_memory_budget_bytes(12345):
        assert get_process_memory_budget_bytes() == 12345
    assert get_process_memory_budget_bytes() > 0


def test_budget_bounds_staging_memory():
    # With a slow storage backend and a tight budget, peak staged bytes stay
    # near the budget (single oversized-admission slack allowed).
    ChunkStager.live = 0
    ChunkStager.peak = 0
    storage = TrackingStorage(delay=0.005, track_budget=True)
    # consume credits happen on write completion; 40 x 100B items, budget 250B
    reqs = [
        WriteReq(path=f"p{i}", buffer_stager=ChunkStager(b"z" * 100))
        for i in range(40)
    ]
    pending = sync_execute_write_reqs(reqs, storage, memory_budget_bytes=250, rank=0)
    pending.sync_complete()
    assert len(storage.writes) == 40
    # budget 250 allows 2 items staged + 1 oversized-slack; peak must stay
    # well under the unbudgeted 4000
    assert ChunkStager.peak <= 400


def test_loop_thread_survives_and_counts_a_loader_failure(monkeypatch):
    """A native-loader failure on the io-loop thread must not kill the
    thread (every submit would hang) — and must not be silent."""
    from torchsnapshot_tpu import _csrc, obs
    from torchsnapshot_tpu.scheduler import _LoopThread

    def broken():
        raise OSError("fastio.cpp unreadable (injected)")

    monkeypatch.setattr(_csrc, "load", broken)
    counter = obs.counter(obs.EXCEPTIONS_SWALLOWED)
    before = counter.value
    lt = _LoopThread(name="tsnp-test-loop")
    try:

        async def ping():
            return 41 + 1

        assert lt.submit(ping()).result(timeout=10) == 42
        assert counter.value == before + 1
    finally:
        lt.shutdown()


# ------------------------------------------------ the order of staging work
#
# The staging pool is a FIFO.  A budget that admits every request at once
# must not queue every materialization ahead of the first staged object's
# checksum: no more materializations are in the pool than it has workers.


class _Record:
    """What the stagers and checksum sinks of one save did, in order."""

    def __init__(self):
        self.lock = threading.Lock()
        self.events = []  # ("materialize" | "checksum", k) as each starts
        self.in_flight = 0  # stage_buffer calls entered and not left
        self.most_in_flight = 0

    def mark(self, what, k):
        with self.lock:
            self.events.append((what, k))

    def at(self, what, k):
        return self.events.index((what, k))


class RecordingStager(BufferStager):
    """Materializes on the pool, as a device array's stager does."""

    def __init__(self, record, k, payload, work_s=0.0, fail=False):
        self.record, self.k, self.payload = record, k, payload
        self.work_s, self.fail = work_s, fail

    def _materialize(self):
        self.record.mark("materialize", self.k)
        time.sleep(self.work_s)
        if self.fail:
            raise RuntimeError(f"injected staging failure on {self.k}")
        return self.payload

    async def stage_buffer(self, executor=None):
        rec = self.record
        with rec.lock:
            rec.in_flight += 1
            rec.most_in_flight = max(rec.most_in_flight, rec.in_flight)
        try:
            return await asyncio.get_running_loop().run_in_executor(
                executor, self._materialize
            )
        finally:
            with rec.lock:
                rec.in_flight -= 1

    def get_staging_cost_bytes(self):
        return len(self.payload)


def _recorded_reqs(record, n, work_s, fail_at=None):
    # staging is largest-first: object k is the k-th to stage.  The two
    # workers' completions are kept half a materialization apart (object 1
    # alone takes half as long again), so at each completion exactly one
    # worker is free and what it runs next is in the pool's queue order
    reqs = []
    for k in range(n):
        stager = RecordingStager(
            record, k, bytes([k]) * (200 - k),
            work_s=work_s * (1.5 if k == 1 else 1.0), fail=(k == fail_at),
        )
        sink = lambda crc, k=k: record.mark("checksum", k)  # noqa: E731
        reqs.append(
            WriteReq(path=f"o{k}", buffer_stager=stager, checksum_sinks=[(sink, None)])
        )
    return reqs


def _ends_within(seconds, fn):
    """``fn``'s result or exception; a pipeline that waits forever fails
    the test instead of hanging the suite."""
    box = {}

    def run():
        try:
            box["result"] = fn()
        except BaseException as e:  # noqa: BLE001
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"the pipeline did not end within {seconds} s"
    if "error" in box:
        raise box["error"]
    return box["result"]


def _save(reqs, storage, budget=1 << 30):
    def run():
        pending = sync_execute_write_reqs(reqs, storage, budget, rank=0)
        pending.sync_complete()
        return pending

    return _ends_within(30, run)


def test_no_more_materializations_in_the_pool_than_it_has_workers():
    record = _Record()
    storage = TrackingStorage()
    with knobs.override_staging_threads(2):
        _save(_recorded_reqs(record, 8, work_s=0.02), storage)
    assert len(storage.writes) == 8
    assert record.most_in_flight == 2, record.events


def test_a_staged_objects_checksum_runs_before_later_materializations():
    record = _Record()
    storage = TrackingStorage()
    with knobs.override_staging_threads(2):
        _save(_recorded_reqs(record, 8, work_s=0.04), storage)
    assert sorted(storage.writes) == sorted(f"o{k}" for k in range(8))
    for k in range(6):
        assert record.at("checksum", k) < record.at("materialize", k + 2), (
            k, record.events
        )


def test_a_stager_that_raises_gives_its_place_back():
    record = _Record()
    storage = TrackingStorage()
    with knobs.override_staging_threads(2):
        with pytest.raises(RuntimeError, match="injected staging failure on 1"):
            _save(_recorded_reqs(record, 8, work_s=0.01, fail_at=1), storage)
    assert record.in_flight == 0


def test_a_cancelled_save_leaves_no_request_waiting():
    from concurrent.futures import ThreadPoolExecutor

    from torchsnapshot_tpu.scheduler import (
        _Budget,
        _WritePipeline,
        _execute_write_pipelines,
    )

    record = _Record()
    staging_done = threading.Event()
    executor = ThreadPoolExecutor(max_workers=1)

    async def cancelled_mid_save():
        pipelines = [
            _WritePipeline(wr) for wr in _recorded_reqs(record, 6, work_s=0.05)
        ]
        save = asyncio.ensure_future(_execute_write_pipelines(
            pipelines, TrackingStorage(), _Budget(1 << 30), executor, 1,
            staging_done, {"bytes_written": 0},
        ))
        await asyncio.sleep(0.02)  # object 0 materializes, 1..5 wait their turn
        save.cancel()
        with pytest.raises(asyncio.CancelledError):
            await save
        for _ in range(200):  # the cancelled stagers run their ``finally``
            if len(asyncio.all_tasks()) == 1:
                break
            await asyncio.sleep(0.01)
        return len(asyncio.all_tasks())

    try:
        assert _ends_within(30, lambda: asyncio.run(cancelled_mid_save())) == 1
    finally:
        executor.shutdown(wait=True)
    assert staging_done.is_set()
    assert record.in_flight == 0
    assert ("materialize", 5) not in record.events


def test_a_host_packed_slab_stages_its_members_in_one_place():
    # the host fallback stages a slab's members in turn inside the slab's
    # own stage_buffer: it holds one place throughout, so a pool (and a
    # bound) of 1 must not leave it waiting for itself
    import numpy as np

    from torchsnapshot_tpu.batcher import BatchedBufferStager
    from torchsnapshot_tpu.preparers.array import HostArrayBufferStager

    members = [
        np.full(300_000 + 1000 * i, i, dtype=np.uint8) for i in range(4)
    ]  # over the slab's executor-hop floor: each member's copy runs on the pool
    crcs = {}

    def slab(tag):
        stagers = [(HostArrayBufferStager(m, defensive_copy=False), m.nbytes) for m in members]
        sinks, offset = [], 0
        for i, m in enumerate(members):
            sinks.append((
                lambda crc, key=(tag, i): crcs.__setitem__(key, crc),
                (offset, offset + m.nbytes),
            ))
            offset += m.nbytes
        return WriteReq(
            path=f"slab.{tag}", buffer_stager=BatchedBufferStager(stagers, offset),
            checksum_sinks=sinks,
        )

    storage = TrackingStorage()
    reqs = [slab("a"), slab("b"), WriteReq(path="leaf", buffer_stager=ChunkStager(b"x" * 10))]
    with knobs.override_staging_threads(1):
        _save(reqs, storage)
    want = b"".join(m.tobytes() for m in members)
    assert storage.writes["slab.a"] == want and storage.writes["slab.b"] == want
    assert storage.writes["leaf"] == b"x" * 10
    import zlib

    assert crcs == {
        (tag, i): zlib.crc32(m.tobytes()) for tag in "ab" for i, m in enumerate(members)
    }
