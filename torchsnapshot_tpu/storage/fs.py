"""Local/posix filesystem storage plugin.

Reference: torchsnapshot/storage_plugins/fs.py:21-62 (aiofiles-based).

Two backends, selected at construction:

- **native** (default when the C++ ext builds): single-syscall-chain
  write/read in ``_csrc/fastio.cpp`` called via ctypes from executor
  threads with the GIL released — one C call per object instead of
  aiofiles' per-chunk thread hops.  With the fast-I/O engine
  (``storage/fastio.py``, probed once here at init) the per-object and
  per-part legs additionally fuse the (crc32, adler32) digest into the
  write pass, batch syscalls via pwritev, and optionally take the
  O_DIRECT page-cache-bypass path (``FASTIO_DIRECT``; see
  docs/fastio.md for the fallback ladder).
- **aiofiles** fallback, behaviorally identical (imported once at
  init, never per op).

Ranged reads seek + read only the requested bytes either way, so
``read_object`` under a memory budget touches O(range) data.
"""

from __future__ import annotations

import asyncio
import functools
import os
import threading
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from .. import knobs, obs
from ..io_types import (
    ReadIO,
    StoragePlugin,
    StripedWriteHandle,
    WriteIO,
    resolve_read_destination,
)
from ..resilience import classify_fs, get_breaker, retry_call
from ..resilience.retry import lazy_shared_progress
from ..resilience.failpoints import failpoint


def _tmp_name(full: str) -> str:
    """Unique sibling temp name: data lands here first and is
    ``os.replace``d onto the final name, so a mid-write failure (ENOSPC,
    crash) can never leave a partial file where a reader — or a later
    recovery sweep — would trust it."""
    return f"{full}.tsnp-tmp-{os.getpid()}-{uuid.uuid4().hex[:8]}"


def _unlink_quiet(path: str) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass


def mmap_read(full: str, byte_range, path: str = ""):
    """Zero-copy read: a READ-ONLY numpy view over a private file-backed
    mapping of ``full`` (whole file mapped; ``byte_range`` selects a
    sub-view — mmap offsets must be page-aligned, numpy offsets need
    not be).  The pages never enter the Python heap: they fault in from
    the page cache on first touch and the kernel can reclaim them under
    pressure, which is why the read scheduler admits mmap reads
    budget-exempt.

    SIGBUS discipline (the madvise/copy-on-verify decision): touching a
    mapped page past the inode's EOF raises SIGBUS, so a file truncated
    IN PLACE while mapped would crash the reader.  We deliberately do
    NOT defensively copy (that would forfeit the whole zero-copy win);
    instead every writer in this codebase publishes via temp+rename
    (never truncates a live name) and every eviction path — tier fast
    GC, cache eviction — UNLINKS (POSIX keeps an unlinked-but-mapped
    inode's pages valid until the last mapping drops).  So our own
    lifecycle can never SIGBUS a live mapping; digest verification
    (tier fast reads, VERIFY_ON_RESTORE) additionally reads through the
    map immediately after it is created, so an EXTERNALLY truncated or
    corrupted file fails the checksum inside normal exception handling
    (→ peer/durable fallback + repair) instead of surfacing later as a
    mid-consume fault.  The extent check below catches truncation that
    happened before the map existed.  MADV_WILLNEED kicks off readahead
    for the mapped span — the common consumer walks it sequentially
    right away."""
    import mmap as _mmap

    import numpy as np

    with obs.span("storage/mmap_read", path=path or full):
        fd = os.open(full, os.O_RDONLY)
        try:
            size = os.fstat(fd).st_size
            if byte_range is None:
                offset, length = 0, size
            else:
                offset, length = byte_range[0], byte_range[1] - byte_range[0]
            if offset + length > size:
                # shorter than the manifest says: surface the I/O error
                # here (errno EIO) rather than SIGBUS at first touch
                raise OSError(
                    5,
                    f"mmap read of [{offset}, {offset + length}) exceeds "
                    f"file size {size}",
                    full,
                )
            if length == 0:
                return np.empty(0, dtype=np.uint8)
            mm = _mmap.mmap(fd, size, access=_mmap.ACCESS_READ)
        finally:
            os.close(fd)
        try:
            # madvise offsets must be page-aligned; round the span out
            lo = offset - (offset % _mmap.PAGESIZE)
            mm.madvise(_mmap.MADV_WILLNEED, lo, length + (offset - lo))
        except (AttributeError, OSError, ValueError) as e:
            obs.swallowed_exception("storage.fs.mmap_madvise", e)
        obs.counter(obs.MMAP_READS).inc()
        obs.counter(obs.MMAP_BYTES_MAPPED).inc(length)
        # the array holds the only reference to ``mm`` — the mapping
        # lives exactly as long as some view of the buffer does
        return np.frombuffer(mm, dtype=np.uint8, count=length, offset=offset)


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir_chain(leaf_dir: str, stop_below: str) -> None:
    """fsync ``leaf_dir`` and every ancestor down to (and including) the
    parent of ``stop_below``: POSIX durability of a NEW file requires
    syncing each newly-created directory's dirent in ITS parent, and the
    snapshot root itself is usually freshly created by take()."""
    leaf_dir = os.path.abspath(leaf_dir)
    stop = os.path.dirname(os.path.abspath(stop_below))
    cur = leaf_dir
    while True:
        _fsync_dir(cur)
        if cur == stop or os.path.dirname(cur) == cur:
            break
        cur = os.path.dirname(cur)


@obs.instrument_storage("fs")
class FSStoragePlugin(StoragePlugin):
    def __init__(self, root: str) -> None:
        self.root = root
        # mkdir dedup across the loop's writes and executor legs; the
        # makedirs itself runs OUTSIDE the lock (exist_ok makes a
        # concurrent double-create benign, a held lock would not)
        self._dirs_lock = threading.Lock()
        self._dirs_created: set = set()
        self._lib = None
        if knobs.is_native_ext_enabled():
            from .. import _csrc

            self._lib = _csrc.load()
        # fast-I/O engine (storage/fastio.py): probed ONCE here — the
        # knob and the root's O_DIRECT support resolve at plugin init,
        # never per op
        self._fastio = None
        if self._lib is not None:
            from . import fastio as _fastio_mod

            self._fastio = _fastio_mod.create_engine(self._lib, root)
        # fused digest-while-writing is only real on the native path
        self.supports_fused_digest = self._lib is not None
        # part-level twin: the engine's pwrite_part fuses each striped
        # part's digest into the write, so the scheduler may defer
        # digest work for stripe-eligible writes too
        self.supports_fused_part_digest = self._fastio is not None
        self._executor: Optional[ThreadPoolExecutor] = (
            ThreadPoolExecutor(
                max_workers=knobs.get_max_per_rank_io_concurrency(),
                thread_name_prefix="tsnp-fsio",
            )
            if self._lib is not None
            else None
        )
        # aiofiles fallback: import ONCE at init (repeated per-op
        # imports cost import-lock acquisitions on the hot path).  Only
        # the pure-Python backend needs it; absence degrades those legs
        # to synchronous work on the loop's default pool.
        self._aiofiles = None
        self._aiofiles_os = None
        if self._lib is None:
            try:
                import aiofiles
                import aiofiles.os

                self._aiofiles = aiofiles
                self._aiofiles_os = aiofiles.os
            except ImportError as e:
                obs.swallowed_exception("storage.fs.aiofiles_import", e)

    def _full(self, path: str) -> str:
        return os.path.join(self.root, path)

    def _ensure_dir(self, full: str) -> None:
        d = os.path.dirname(full)
        with self._dirs_lock:
            if d in self._dirs_created:
                return
        os.makedirs(d, exist_ok=True)
        with self._dirs_lock:
            self._dirs_created.add(d)

    async def _retry(self, fn, op_name: str, executor=None, breaker=None):
        return await retry_call(
            fn,
            op_name=op_name,
            backend="fs",
            classify=classify_fs,
            progress=lazy_shared_progress(self, "fs"),
            executor=executor,
            breaker=breaker,
        )

    async def write(self, write_io: WriteIO) -> None:
        # All paths write a sibling temp file and os.replace it onto the
        # final name: a mid-write OSError (ENOSPC, EIO) leaves NO
        # partial file behind, and replacing the dirent (instead of
        # truncating in place) means incremental-dedup hardlinks shared
        # with other snapshots are never rewritten through.  Transient
        # EINTR/EAGAIN retries via the shared policy.
        full = self._full(write_io.path)
        self._ensure_dir(full)
        breaker = get_breaker("fs")
        if self._lib is not None:

            def native_attempt():
                failpoint("storage.fs.write", path=write_io.path)
                return self._native_write(
                    full, write_io.buf, write_io.durable, write_io.want_digest
                )

            write_io.digests = await self._retry(
                native_attempt,
                f"write {write_io.path}",
                executor=self._executor,
                breaker=breaker,
            )
            return
        if write_io.durable or knobs.is_fs_sync_data():
            # aiofiles can't fsync; a synced write is one synchronous
            # write+fdatasync in a thread.  Only the commit-point write
            # syncs the directory chain (data files' dirents become
            # durable with the metadata's chain sync that follows them).
            def sync_work():
                failpoint("storage.fs.write", path=write_io.path)
                self._durable_fallback_write(
                    full, write_io.buf, write_io.durable
                )

            async def sync_attempt():
                await asyncio.get_running_loop().run_in_executor(
                    None, sync_work
                )

            await self._retry(
                sync_attempt, f"write {write_io.path}", breaker=breaker
            )
            return
        if self._aiofiles is None:
            # aiofiles missing from the environment: same temp+rename
            # bytes via one synchronous write on the default pool
            def plain_work():
                failpoint("storage.fs.write", path=write_io.path)
                tmp = _tmp_name(full)
                try:
                    with open(tmp, "wb") as f:
                        f.write(write_io.buf)
                    failpoint("storage.fs.write.sync", path=write_io.path)
                    os.replace(tmp, full)
                except BaseException:
                    _unlink_quiet(tmp)
                    raise

            async def plain_attempt():
                await asyncio.get_running_loop().run_in_executor(
                    None, plain_work
                )

            await self._retry(
                plain_attempt, f"write {write_io.path}", breaker=breaker
            )
            return
        aiofiles = self._aiofiles

        async def aio_attempt():
            failpoint("storage.fs.write", path=write_io.path)
            tmp = _tmp_name(full)
            try:
                async with aiofiles.open(tmp, "wb") as f:
                    await f.write(write_io.buf)
                failpoint("storage.fs.write.sync", path=write_io.path)
                os.replace(tmp, full)
            except BaseException:
                _unlink_quiet(tmp)
                raise

        await self._retry(
            aio_attempt, f"write {write_io.path}", breaker=breaker
        )

    def _durable_fallback_write(self, full: str, buf, chain: bool = True) -> None:
        tmp = _tmp_name(full)
        try:
            with open(tmp, "wb") as f:
                f.write(buf)
                f.flush()
                os.fdatasync(f.fileno())
            failpoint("storage.fs.write.sync", path=full)
            os.replace(tmp, full)
        except BaseException:
            _unlink_quiet(tmp)
            raise
        if chain:
            _fsync_dir_chain(os.path.dirname(full), self.root)

    def _native_write(
        self, full: str, buf, durable: bool = False, want_digest: bool = False
    ):
        import ctypes

        from .._csrc import _buffer_address

        sync_file = durable or knobs.is_fs_sync_data()
        view = memoryview(buf).cast("B")
        addr = _buffer_address(view) if view.nbytes else None
        digests = None
        tmp = _tmp_name(full)
        try:
            if self._fastio is not None:
                # fast-I/O engine: pwritev-batched (optionally
                # O_DIRECT) write with the digest fused into the same
                # native pass; temp+rename commit stays here
                digests = self._fastio.write_file(
                    tmp, view, sync_file, want_digest
                )
            elif want_digest:
                out = (ctypes.c_uint32 * 2)()
                rc = self._lib.tsnp_write_file_digest(
                    tmp.encode(), addr, view.nbytes, 1 if sync_file else 0, out
                )
                if rc != 0:
                    raise OSError(-rc, os.strerror(-rc), full)
                digests = (int(out[0]), int(out[1]))
            else:
                rc = self._lib.tsnp_write_file(
                    tmp.encode(), addr, view.nbytes, 1 if sync_file else 0
                )
                if rc != 0:
                    raise OSError(-rc, os.strerror(-rc), full)
            failpoint("storage.fs.write.sync", path=full)
            os.replace(tmp, full)
        except BaseException:
            _unlink_quiet(tmp)
            raise
        if durable:
            # fdatasync covers the file CONTENT; the file's existence
            # needs every (possibly just-created) directory up the chain
            # synced too
            _fsync_dir_chain(os.path.dirname(full), self.root)
        if knobs.is_fs_verify_writes() and view.nbytes:
            # re-read + crc32c compare: catches torn/corrupted local writes
            # at save time (GCS gets this from server-side crc32c;
            # local fs otherwise gets nothing)
            expected = self._lib.tsnp_crc32c(addr, view.nbytes, 0)
            back = self._native_read(full, None)
            got = self._lib.tsnp_crc32c(
                _buffer_address(memoryview(back)), len(back), 0
            )
            if got != expected:
                raise OSError(
                    5, f"crc32c mismatch after write ({got:#x} != {expected:#x})", full
                )
        return digests

    supports_mmap_read = True
    mmap_budget_exempt = True  # every read is a local file: maps never decline

    async def read(self, read_io: ReadIO) -> None:
        full = self._full(read_io.path)
        if read_io.want_mmap and knobs.mmap_enabled():
            # zero-copy serving path (works on both backends — the map
            # is pure Python); the mmap_read docstring carries the
            # SIGBUS/verify contract
            def mmap_attempt():
                failpoint("storage.fs.read", path=read_io.path)
                return mmap_read(full, read_io.byte_range, read_io.path)

            read_io.buf = await self._retry(
                mmap_attempt,
                f"read {read_io.path}",
                executor=self._executor,
            )
            return
        if self._lib is not None:

            def native_attempt():
                failpoint("storage.fs.read", path=read_io.path)
                return self._native_read(
                    full, read_io.byte_range, read_io.into
                )

            read_io.buf = await self._retry(
                native_attempt,
                f"read {read_io.path}",
                executor=self._executor,
            )
            return
        if self._aiofiles is None:
            # aiofiles missing from the environment: one synchronous
            # read on the default pool, same into-honor contract
            def plain_read():
                failpoint("storage.fs.read", path=read_io.path)
                with open(full, "rb") as f:
                    if read_io.byte_range is None:
                        start, length = 0, os.fstat(f.fileno()).st_size
                    else:
                        start, end = read_io.byte_range
                        length = end - start
                        f.seek(start)
                    dst = resolve_read_destination(read_io.into, length)
                    got = f.readinto(memoryview(dst).cast("B"))
                    if got != length:
                        raise OSError(
                            5, f"short read: {got} of {length} bytes", full
                        )
                    return read_io.into if dst is read_io.into else dst

            async def plain_attempt():
                return await asyncio.get_running_loop().run_in_executor(
                    None, plain_read
                )

            read_io.buf = await self._retry(
                plain_attempt, f"read {read_io.path}"
            )
            return
        aiofiles = self._aiofiles

        async def aio_attempt():
            failpoint("storage.fs.read", path=read_io.path)
            async with aiofiles.open(full, "rb") as f:
                if read_io.byte_range is None:
                    start = 0
                    length = (await f.seek(0, os.SEEK_END)) or 0
                    await f.seek(0)
                else:
                    start, end = read_io.byte_range
                    length = end - start
                    await f.seek(start)
                # honor the destination hint like _native_read does:
                # one-touch restore (read straight into the template)
                # must not be a native-ext-only property.  The shared
                # resolve_read_destination carries the honor contract;
                # identity tells us whether the hint was usable.
                if read_io.into is None or not hasattr(f, "readinto"):
                    return await f.read(length)
                dst = resolve_read_destination(read_io.into, length)
                if dst is not read_io.into:
                    return await f.read(length)  # unusable hint
                view = memoryview(dst).cast("B")
                pos = 0
                while pos < length:
                    n = await f.readinto(view[pos:])
                    if not n:
                        # short read can't satisfy the in-place
                        # contract; surface it as the I/O error it is
                        raise OSError(
                            5, f"short read: {pos} of {length} bytes", full
                        )
                    pos += n
                return read_io.into

        read_io.buf = await self._retry(aio_attempt, f"read {read_io.path}")

    def _native_read(self, full: str, byte_range, into=None):
        import numpy as np

        from .._csrc import _buffer_address

        if byte_range is None:
            size = self._lib.tsnp_file_size(full.encode())
            if size < 0:
                raise OSError(-size, os.strerror(-size), full)
            offset, length = 0, size
        else:
            offset, length = byte_range[0], byte_range[1] - byte_range[0]
        # read straight into the caller's destination (a restore
        # template's memory) when the hint matches exactly — host
        # restore then touches the bytes ONCE; otherwise a fresh
        # UNINITIALIZED buffer (np.empty, not bytearray: zeroing memory
        # the read is about to overwrite costs a full extra pass)
        dst = None
        if into is not None:
            try:
                view = memoryview(into).cast("B")
                if not view.readonly and view.nbytes == length:
                    dst = into
            except (TypeError, ValueError):
                pass  # non-contiguous/exotic hint: ignore, normal path
        out = dst if dst is not None else np.empty(length, dtype=np.uint8)
        if length:
            if self._fastio is not None:
                # fast-I/O engine: optionally O_DIRECT (page-cache-
                # bypassing) read straight into the destination
                n = self._fastio.read_into(full, offset, length, out)
            else:
                n = self._lib.tsnp_read_file(
                    full.encode(),
                    _buffer_address(memoryview(out).cast("B")),
                    offset,
                    length,
                )
                if n < 0:
                    raise OSError(-n, os.strerror(-n), full)
            if n != length:
                if dst is not None:
                    # short read can't satisfy the in-place contract;
                    # surface it as the I/O error it is
                    raise OSError(
                        5, f"short read: {n} of {length} bytes", full
                    )
                out = out[:n]
        return out

    # ------------------------------------------------- striped writes

    supports_striped_write = True

    async def begin_striped_write(
        self, path: str, total_size: int
    ) -> "_FSStripedWriteHandle":
        full = self._full(path)
        self._ensure_dir(full)
        tmp = _tmp_name(full)

        def _open():
            fd = os.open(tmp, os.O_RDWR | os.O_CREAT | os.O_EXCL, 0o644)
            fd_direct = -1
            try:
                os.ftruncate(fd, total_size)
                if self._fastio is not None:
                    # one O_DIRECT fd shared by every part's aligned
                    # body (engine declines per part below the direct
                    # size floor); -1 when the direct leg is off
                    fd_direct = self._fastio.open_direct(tmp)
            except BaseException:
                if fd_direct >= 0:
                    os.close(fd_direct)
                os.close(fd)
                _unlink_quiet(tmp)
                raise
            return fd, fd_direct

        fd, fd_direct = await self._off_loop(_open)
        return _FSStripedWriteHandle(self, path, full, tmp, fd, fd_direct)

    async def _off_loop(self, fn):
        """Run a sync syscall off the event loop (the plugin's executor
        when the native path owns one, the default pool otherwise)."""
        return await asyncio.get_running_loop().run_in_executor(
            self._executor, fn
        )

    async def delete(self, path: str) -> None:
        # keep the shared event loop responsive: remove() off-loop
        full = self._full(path)
        if self._executor is not None:
            await asyncio.get_running_loop().run_in_executor(
                self._executor, os.remove, full
            )
        elif self._aiofiles_os is not None:
            await self._aiofiles_os.remove(full)
        else:
            await asyncio.get_running_loop().run_in_executor(
                None, os.remove, full
            )

    async def link_from(self, base_url: str, path: str) -> None:
        """Hardlink the base snapshot's object (content-addressed dedup
        for incremental takes).  Hardlinks give each snapshot its own
        directory entry to the shared inode: deleting either snapshot
        leaves the other intact.  Cross-device links fall back to a
        copy (still no read through Python: shutil.copyfile)."""
        base_root = base_url.split("://", 1)[-1]
        src = os.path.join(base_root, path)
        dst = self._full(path)

        def _link() -> None:
            self._ensure_dir(dst)
            try:
                if os.path.exists(dst):
                    os.remove(dst)
                os.link(src, dst)
            except OSError:
                import shutil

                shutil.copyfile(src, dst)

        if self._executor is not None:
            await asyncio.get_running_loop().run_in_executor(
                self._executor, _link
            )
        else:
            _link()

    async def stat(self, path: str) -> int:
        full = self._full(path)
        if self._executor is not None:
            st = await asyncio.get_running_loop().run_in_executor(
                self._executor, os.stat, full
            )
        elif self._aiofiles_os is not None:
            st = await self._aiofiles_os.stat(full)
        else:
            st = await asyncio.get_running_loop().run_in_executor(
                None, os.stat, full
            )
        return st.st_size

    async def close(self) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=False)


class _FSStripedWriteHandle(StripedWriteHandle):
    """Offset-parallel part writes into a preallocated sibling temp file.

    With the fast-I/O engine each part is ONE GIL-free native call
    (pwritev-batched, optionally O_DIRECT for the aligned body, the
    part's (crc32, adler32) fused into the same pass — the handle then
    honors ``want_digest`` and the stripe engine skips its separate
    per-part digest read); without it, the pre-engine ``os.pwrite``
    loop.  Either way the plugin's temp+rename commit discipline holds:
    parts land in the ``.tsnp-tmp-*`` file (preallocated with ftruncate
    so concurrent pwrites never race an append), ``complete``
    optionally fdatasyncs and ``os.replace``s onto the final name — a
    mid-stripe failure or abort leaves NO partial file where a reader
    (or a recovery sweep) would trust it.  Each part retries
    independently under the shared fs policy (EINTR/EAGAIN transient,
    ENOSPC/EIO fatal) and feeds the fs breaker."""

    def __init__(
        self, plugin: FSStoragePlugin, path, full, tmp, fd, fd_direct=-1
    ) -> None:
        self._plugin = plugin
        self._path = path
        self._final = full
        self._tmp = tmp
        self._fd = fd
        self._fd_direct = fd_direct
        self._closed = False
        # the handle fuses part digests exactly when the engine writes
        # the parts (io_types.StripedWriteHandle contract)
        self.supports_fused_digest = plugin._fastio is not None
        # extent actually written: the preallocated size is an UPPER
        # bound when parts carry data-dependent sizes (codec frames) —
        # complete() truncates to this high-water mark, so raw-sized
        # preallocation never publishes trailing zeros
        self._hwm = 0

    async def write_part(
        self, index: int, offset: int, buf, want_digest: bool = False
    ):
        view = memoryview(buf).cast("B")
        self._hwm = max(self._hwm, offset + view.nbytes)
        engine = self._plugin._fastio

        def attempt():
            failpoint(
                "storage.fs.part.write", path=self._path, part=index
            )
            if engine is not None:
                return engine.pwrite_part(
                    self._fd, self._fd_direct, offset, view, want_digest
                )
            pos = 0
            while pos < view.nbytes:
                pos += os.pwrite(self._fd, view[pos:], offset + pos)
            return None

        async def aio_attempt():
            # off-loop even on the aiofiles fallback (plugin executor
            # None -> the loop's default pool): a part-sized pwrite on
            # the loop thread would stall every concurrent pipeline
            return await self._plugin._off_loop(attempt)

        return await self._plugin._retry(
            aio_attempt,
            f"write {self._path} [part {index}]",
            breaker=get_breaker("fs"),
        )

    async def complete(self) -> None:
        durable = knobs.is_fs_sync_data()

        def commit() -> None:
            failpoint("storage.fs.write.sync", path=self._path)
            try:
                if os.fstat(self._fd).st_size != self._hwm:
                    os.ftruncate(self._fd, self._hwm)
                if durable:
                    os.fdatasync(self._fd)
            finally:
                self._close_fd()
            os.replace(self._tmp, self._final)

        try:
            await self._plugin._off_loop(commit)
        except BaseException:
            await self.abort()
            raise

    def _close_fd(self) -> None:
        if not self._closed:
            self._closed = True
            if self._fd_direct >= 0:
                os.close(self._fd_direct)
            os.close(self._fd)

    async def abort(self) -> None:
        def cleanup() -> None:
            self._close_fd()
            _unlink_quiet(self._tmp)

        await self._plugin._off_loop(cleanup)
