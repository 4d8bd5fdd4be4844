"""How many of one object's parts the stripe engines write at once.

A striped-write handle says it (``StripedWriteHandle.max_concurrent_parts``).
An fs file on the buffered leg says 1: a buffered write holds the file's
inode lock for its whole copy, so its parts go one at a time, in offset
order, and the I/O pool's threads go to other files.  Memory, S3, GCS and
the fs O_DIRECT leg keep the engine's per-object part concurrency.  The
file's bytes and the folded digest are the same either way.
"""

import asyncio
import contextlib
import os
import threading
import time
import zlib

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, knobs, obs
from torchsnapshot_tpu.obs import tracer
from torchsnapshot_tpu.storage import fastio, stripe
from torchsnapshot_tpu.storage.fs import FSStoragePlugin, _FSStripedWriteHandle
from torchsnapshot_tpu.storage.memory import (
    MemoryStoragePlugin,
    _MemoryStripedWriteHandle,
    reset_namespace,
)
from torchsnapshot_tpu.utils.checksums import combine_piece_digests

PART = 4096


def _engine_available() -> bool:
    from torchsnapshot_tpu import _csrc

    lib = _csrc.load()
    return lib is not None and hasattr(lib, "tsnp_part_pwrite")


needs_engine = pytest.mark.skipif(
    not _engine_available(), reason="no C++ toolchain / engine symbols"
)


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def _knobs(part=PART, min_bytes=PART + 1):
    ctx = contextlib.ExitStack()
    ctx.enter_context(knobs.override_stripe_part_size_bytes(part))
    ctx.enter_context(knobs.override_stripe_min_object_size_bytes(min_bytes))
    return ctx


class _Log:
    """Entry and exit of every part write, from whatever thread: the
    most writes in flight at once, over all and per file."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.events = []  # (t_enter, t_exit, key, offset)
        self._open = {}
        self.most = 0
        self.most_keys = 0
        self.most_per_key = 0

    def enter(self, key) -> None:
        with self._lock:
            self._open[key] = self._open.get(key, 0) + 1
            self.most = max(self.most, sum(self._open.values()))
            self.most_keys = max(
                self.most_keys, sum(1 for n in self._open.values() if n)
            )
            self.most_per_key = max(self.most_per_key, self._open[key])

    def exit(self, key, t0, offset) -> None:
        with self._lock:
            self._open[key] -= 1
            self.events.append((t0, time.perf_counter(), key, offset))


def _logged_engine(plugin, log, delay_s=0.01):
    """Wrap the plugin's engine so every part write is logged by its fd
    and slowed, so that writes that may overlap do."""
    engine = plugin._fastio
    inner = engine.pwrite_part

    def pwrite_part(fd, fd_direct, offset, buf, want_digest):
        t0 = time.perf_counter()
        log.enter(fd)
        try:
            time.sleep(delay_s)
            return inner(fd, fd_direct, offset, buf, want_digest)
        finally:
            log.exit(fd, t0, offset)

    engine.pwrite_part = pwrite_part


def _assert_one_at_a_time_in_offset_order(log, nparts):
    events = sorted(log.events)
    assert len(events) == nparts
    assert all(b[0] >= a[1] for a, b in zip(events, events[1:])), events
    assert [e[3] for e in events] == sorted(e[3] for e in events)
    assert log.most == 1


def _data(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


@needs_engine
def test_fs_buffered_parts_go_one_at_a_time_in_offset_order(tmp_path):
    plugin = FSStoragePlugin(str(tmp_path / "fs"))
    log = _Log()
    _logged_engine(plugin, log)
    data = _data(6 * PART + 123)
    with _knobs():
        d = run(stripe.striped_write(plugin, "obj", data, want_digests=True))
    _assert_one_at_a_time_in_offset_order(log, 7)
    with open(tmp_path / "fs" / "obj", "rb") as f:
        assert f.read() == data.tobytes()
    assert d == (zlib.crc32(data), zlib.adler32(data), data.nbytes)


class _ScrambledStager:
    """Stages a buffer's parts with the LAST part ready first, so writes
    that went as parts were staged would go back to front."""

    def __init__(self, data, nparts):
        self._data = data
        self._nparts = nparts

    async def stage_part(self, span, executor):
        lo, hi = span
        await asyncio.sleep(0.005 * (self._nparts - lo // PART))
        return self._data[lo:hi]

    def release_source(self) -> None:
        pass


@needs_engine
def test_fs_buffered_streamed_parts_go_one_at_a_time_in_offset_order(tmp_path):
    plugin = FSStoragePlugin(str(tmp_path / "fs"))
    log = _Log()
    _logged_engine(plugin, log)
    data = _data(5 * PART + 77, seed=1)
    spans = stripe.plan_parts(data.nbytes, PART)
    digests = run(
        stripe.streamed_part_write(
            plugin, "obj", _ScrambledStager(data, len(spans)), spans, None,
            window_parts=len(spans), want_digests=True,
        )
    )
    _assert_one_at_a_time_in_offset_order(log, len(spans))
    with open(tmp_path / "fs" / "obj", "rb") as f:
        assert f.read() == data.tobytes()
    assert combine_piece_digests(digests) == (
        zlib.crc32(data), zlib.adler32(data), data.nbytes
    )


@needs_engine
def test_through_the_scheduler_files_write_side_by_side_parts_one_at_a_time(
    tmp_path, monkeypatch
):
    """Four objects of three parts each: all four files have a part in
    flight at some moment, and no file ever has two."""
    log = _Log()
    inner = fastio.FastIOEngine.pwrite_part

    def pwrite_part(self, fd, fd_direct, offset, buf, want_digest):
        t0 = time.perf_counter()
        log.enter(fd)
        try:
            time.sleep(0.05)
            return inner(self, fd, fd_direct, offset, buf, want_digest)
        finally:
            log.exit(fd, t0, offset)

    monkeypatch.setattr(fastio.FastIOEngine, "pwrite_part", pwrite_part)
    state = {
        f"w{i}": np.arange(3 * PART // 4, dtype=np.float32) + i
        for i in range(4)
    }
    path = str(tmp_path / "snap")
    with _knobs(), knobs.override_disable_batching(True):
        Snapshot.take(path, {"app": StateDict(**state)})
        dest = {"app": StateDict(**{k: np.zeros_like(v) for k, v in state.items()})}
        Snapshot(path).restore(dest)
    for k, v in state.items():
        np.testing.assert_array_equal(dest["app"][k], v)
    assert len(log.events) == 12
    assert log.most_keys == 4
    assert log.most_per_key == 1


def test_memory_parts_still_go_several_at_once(monkeypatch):
    ns = f"turns-{os.getpid()}"
    reset_namespace(ns)
    plugin = MemoryStoragePlugin(ns)
    log = _Log()
    inner = _MemoryStripedWriteHandle.write_part

    async def write_part(self, index, offset, buf, want_digest=False):
        t0 = time.perf_counter()
        log.enter(id(self))
        try:
            await asyncio.sleep(0.01)
            return await inner(self, index, offset, buf, want_digest)
        finally:
            log.exit(id(self), t0, offset)

    monkeypatch.setattr(_MemoryStripedWriteHandle, "write_part", write_part)
    data = _data(6 * PART, seed=2)
    serial = obs.counter(obs.STRIPE_SERIAL_PARTS).value
    written = obs.counter(obs.STRIPE_PARTS_WRITTEN).value
    with _knobs():
        run(stripe.striped_write(plugin, "obj", data))
    assert log.most > 1
    assert obs.counter(obs.STRIPE_PARTS_WRITTEN).value - written == 6
    assert obs.counter(obs.STRIPE_SERIAL_PARTS).value == serial
    assert bytes(plugin._store["obj"]) == data.tobytes()


@needs_engine
def test_fs_direct_leg_parts_still_go_several_at_once(tmp_path, monkeypatch):
    """A handle with an O_DIRECT fd keeps the engine's part concurrency
    (the stub fd is a plain one: on a buffered-only engine the bytes
    still take the buffered copy, so only the admission is under test)."""
    plugin = FSStoragePlugin(str(tmp_path / "fs"))
    monkeypatch.setattr(
        plugin._fastio, "open_direct", lambda path, flags=None: os.open(path, os.O_RDWR)
    )
    log = _Log()
    _logged_engine(plugin, log)
    data = _data(6 * PART, seed=3)
    serial = obs.counter(obs.STRIPE_SERIAL_PARTS).value
    with _knobs():
        d = run(stripe.striped_write(plugin, "obj", data, want_digests=True))
    assert log.most > 1
    assert obs.counter(obs.STRIPE_SERIAL_PARTS).value == serial
    with open(tmp_path / "fs" / "obj", "rb") as f:
        assert f.read() == data.tobytes()
    assert d == (zlib.crc32(data), zlib.adler32(data), data.nbytes)


@pytest.mark.parametrize("engine", [True, False], ids=["engine", "no-engine"])
def test_what_an_fs_handle_says(tmp_path, engine):
    """1 on the buffered leg, the engine's part concurrency where the
    handle holds an O_DIRECT fd."""
    if engine and not _engine_available():
        pytest.skip("no C++ toolchain / engine symbols")
    with knobs.override_fastio(engine):
        plugin = FSStoragePlugin(str(tmp_path / "fs"))

    async def opened():
        handle = await plugin.begin_striped_write("obj", 16)
        try:
            return handle.max_concurrent_parts
        finally:
            await handle.abort()

    assert run(opened()) == 1
    direct = _FSStripedWriteHandle(plugin, "p", "f", "t", fd=-1, fd_direct=7)
    direct._closed = True  # the fds are not real: nothing to close
    assert direct.max_concurrent_parts == (
        stripe.part_concurrency() if engine else 1
    )


def test_serial_parts_counts_every_fs_buffered_part(tmp_path):
    plugin = FSStoragePlugin(str(tmp_path / "fs"))
    data = _data(5 * PART + 1, seed=4)
    spans = stripe.plan_parts(data.nbytes, PART)
    serial = obs.counter(obs.STRIPE_SERIAL_PARTS).value
    written = obs.counter(obs.STRIPE_PARTS_WRITTEN).value
    with _knobs():
        run(stripe.striped_write(plugin, "a", data))
        run(
            stripe.streamed_part_write(
                plugin, "b", _ScrambledStager(data, len(spans)), spans, None,
                window_parts=2,
            )
        )
    assert obs.counter(obs.STRIPE_PARTS_WRITTEN).value - written == 12
    assert obs.counter(obs.STRIPE_SERIAL_PARTS).value - serial == 12


def test_the_stripe_spans_say_their_part_concurrency(tmp_path):
    ns = f"turns-span-{os.getpid()}"
    reset_namespace(ns)
    fs, mem = FSStoragePlugin(str(tmp_path / "fs")), MemoryStoragePlugin(ns)
    data = _data(3 * PART, seed=5)
    spans = stripe.plan_parts(data.nbytes, PART)
    with _knobs(), knobs.override_trace(True):
        tracer.get_tracer().reset()
        run(stripe.striped_write(fs, "a", data))
        run(stripe.striped_write(mem, "a", data))
        run(
            stripe.streamed_part_write(
                fs, "b", _ScrambledStager(data, len(spans)), spans, None,
                window_parts=2,
            )
        )
        got = [
            (s.name, s.attrs["backend"], s.attrs["part_concurrency"])
            for s in tracer.get_tracer().spans()
            if s.name in ("stripe/write", "stripe/stream_write")
        ]
    assert sorted(got) == sorted([
        ("stripe/write", "fs", 1),
        ("stripe/write", "memory", stripe.part_concurrency()),
        ("stripe/stream_write", "fs", 1),
    ])


def test_a_part_that_fails_hands_its_turn_on(tmp_path):
    """A streamed part that fails while staging must not leave the parts
    after it waiting for their turn: the write fails, and leaves no
    temp file."""
    plugin = FSStoragePlugin(str(tmp_path / "fs"))
    data = _data(4 * PART, seed=6)
    spans = stripe.plan_parts(data.nbytes, PART)

    class Failing(_ScrambledStager):
        async def stage_part(self, span, executor):
            if span[0] == PART:
                raise OSError(5, "staging failed")
            return await super().stage_part(span, executor)

    with pytest.raises(OSError, match="staging failed"):
        run(
            asyncio.wait_for(
                stripe.streamed_part_write(
                    plugin, "obj", Failing(data, len(spans)), spans, None,
                    window_parts=4,
                ),
                timeout=30,
            )
        )
    assert not [
        f for _, _, files in os.walk(tmp_path / "fs") for f in files
    ]
