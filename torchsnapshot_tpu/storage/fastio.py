"""Native fast-I/O engine: GIL-free direct I/O for the fs hot path.

Every stripe part, CAS chunk, host-cache fill, and tier promotion on a
local filesystem funnels through the fs plugin's read/write legs; this
module turns each of those legs into ONE native call
(``_csrc/fastio.cpp``: ``tsnp_part_pwrite`` / ``tsnp_part_pread``) that
runs entirely outside the GIL:

- **writes** digest each 256KB block while cache-hot and batch the
  syscalls via ``pwritev`` (64 blocks per syscall), so a checksummed
  part write touches the staged bytes ONCE — the separate digest pass
  the pre-engine striped path paid is gone;
- **reads** land straight in the caller's destination buffer;
- **O_DIRECT** (``TORCHSNAPSHOT_TPU_FASTIO_DIRECT=1``) moves payload
  bytes around the page cache in both directions — takes stop churning
  the cache, and a serving cold start stops evicting the very model it
  is loading.  Alignment is owned by the native engine: sub-sector
  heads/tails go buffered while the aligned body is copied through a
  preallocated aligned bounce buffer (fused with the digest) and
  written direct — bytes and digests are bitwise-identical to the
  buffered path in all cases.

Fallback ladder, probed ONCE at engine construction (never per-op):

1. native ext present with the engine symbols and ``FASTIO`` on →
   engine active (buffered legs);
2. ``FASTIO_DIRECT`` on and the root's filesystem accepts O_DIRECT →
   direct legs for spans ≥ :data:`DIRECT_MIN_BYTES`;
3. ``FASTIO_DIRECT`` on but O_DIRECT unsupported (tmpfs on older
   kernels, some network filesystems) → buffered legs plus best-effort
   ``posix_fadvise(DONTNEED)`` on reads (page-cache hygiene without
   the bypass);
4. engine unavailable (``FASTIO=0``, stale cached ``.so``, no
   toolchain) → the fs plugin keeps its pre-engine paths unchanged.

The aligned bounce-buffer pool is preallocated at engine construction
whenever the direct leg is active (:data:`POOL_BYTES` total, fixed 4MB
buffers; buffered-only engines allocate none — they move
bytes straight between caller memory and the kernel); an exhausted
pool backpressures the requesting part (``storage.fastio.pool_waits``)
instead of allocating — the engine can never amplify the scheduler's
memory budget.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
import uuid
from typing import Any, Optional, Tuple

from .. import knobs, obs

logger = logging.getLogger(__name__)

# Alignment for O_DIRECT offsets/lengths/memory.  4096 covers every
# deployed logical-block size (512e drives accept 4096-aligned I/O; a
# 4Kn drive rejects 512).  Also the bounce-buffer memory alignment.
ALIGN = 4096

# Each pool buffer's size.  4MB amortizes the direct write syscalls
# (one pwrite per bounce fill) without making a single part hold a
# large slice of the pool.
BOUNCE_BYTES = 4 * 1024 * 1024

# Spans below this stay buffered even when the direct leg is available:
# a sub-MB object is all head/tail anyway, and O_DIRECT's synchronous
# media round-trip would dominate its latency.
DIRECT_MIN_BYTES = 1 * 1024 * 1024

# Total preallocated aligned bounce-buffer pool (split into fixed 4MB
# buffers).  Direct-path parts each hold one buffer for the duration of
# their copy+write; an exhausted pool backpressures (the part waits for
# a buffer, and storage.fastio.pool_waits counts the waits).
POOL_BYTES = 64 * 1024 * 1024


class _AlignedPool:
    """Preallocated pool of ALIGN-aligned bounce buffers.

    ``acquire`` blocks when every buffer is out (backpressure — counted
    in ``storage.fastio.pool_waits``); ``release`` returns a buffer.
    Buffers are handed out as ``(address, nbytes)`` plus the backing
    array, so native calls use the address directly.  Thread-safe: the
    engine is called from every scheduler executor thread at once.
    """

    def __init__(self, total_bytes: int, buf_bytes: int = BOUNCE_BYTES) -> None:
        import numpy as np

        count = max(1, int(total_bytes) // buf_bytes)
        self._cond = threading.Condition()
        self._free: list = []
        self._bufs: list = []  # keep the arrays alive for the pool's life
        for _ in range(count):
            raw = np.empty(buf_bytes + ALIGN, dtype=np.uint8)
            off = (-raw.ctypes.data) % ALIGN
            view = raw[off : off + buf_bytes]
            self._bufs.append(raw)
            self._free.append((int(view.ctypes.data), buf_bytes))
        self.buf_bytes = buf_bytes
        self.count = count

    def acquire(self) -> Tuple[int, int]:
        with self._cond:
            if not self._free:
                obs.counter(obs.FASTIO_POOL_WAITS).inc()
                while not self._free:
                    self._cond.wait()
            return self._free.pop()

    def release(self, buf: Tuple[int, int]) -> None:
        with self._cond:
            self._free.append(buf)
            self._cond.notify()

    def free_count(self) -> int:
        with self._cond:
            return len(self._free)


def _buffer_address(view: memoryview) -> Optional[int]:
    from .._csrc import _buffer_address as addr

    return addr(view) if view.nbytes else None


def probe_direct(root: str) -> bool:
    """One-time O_DIRECT capability probe for ``root``'s filesystem:
    create-and-unlink a probe file opened with O_DIRECT.  When the
    create fails for PERMISSION reasons (read-only serving mounts —
    the restore side's primary use case), fall back to opening an
    existing file under ``root`` with O_RDONLY|O_DIRECT, which is all
    the read path needs.  Filesystem-level failures (EINVAL from
    tmpfs, missing flag off-Linux) mean "unsupported" — the engine
    then takes the fadvise fallback rung."""
    flag = getattr(os, "O_DIRECT", None)
    if flag is None:
        return False
    probe = os.path.join(
        root, f".tsnp-fastio-probe-{os.getpid()}-{uuid.uuid4().hex[:8]}"
    )
    try:
        os.makedirs(root, exist_ok=True)
        fd = os.open(probe, os.O_WRONLY | os.O_CREAT | os.O_EXCL | flag, 0o644)
    except OSError as e:
        logger.debug("fastio O_DIRECT create-probe failed for %s: %r", root, e)
        return _probe_direct_readonly(root, flag)
    try:
        os.close(fd)
    finally:
        try:
            os.unlink(probe)
        except OSError:
            pass
    return True


def _probe_direct_readonly(root: str, flag: int) -> bool:
    """Read-only rung of the O_DIRECT probe: try O_RDONLY|O_DIRECT on
    an existing regular file under ``root`` (bounded walk)."""
    examined = 0
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            examined += 1
            if examined > 16:
                return False
            try:
                fd = os.open(os.path.join(dirpath, fn), os.O_RDONLY | flag)
            except OSError:
                continue
            os.close(fd)
            return True
    return False


def create_engine(lib: Any, root: str) -> Optional["FastIOEngine"]:
    """The fs plugin's one probe point: a :class:`FastIOEngine` when the
    knob is on and the native ``lib`` loaded, else None (the
    plugin keeps its pre-engine paths).  O_DIRECT support is probed
    here, once per plugin — never per op."""
    if lib is None or not knobs.fastio_enabled():
        return None
    want_direct = knobs.fastio_direct_enabled()
    direct_ok = probe_direct(root) if want_direct else False
    return FastIOEngine(
        lib,
        direct=direct_ok,
        dontneed=want_direct and not direct_ok,
        pool_bytes=POOL_BYTES,
    )


class FastIOEngine:
    """GIL-free part reader/writer over a preallocated aligned pool.

    All methods are SYNCHRONOUS and thread-safe — the fs plugin calls
    them from its executor threads (the native call releases the GIL
    for the whole syscall chain).  Temp-file naming, rename commits,
    retries, failpoints and breaker accounting stay with the caller;
    the engine owns byte movement, digest fusion, and alignment only.
    """

    def __init__(
        self,
        lib: Any,
        *,
        direct: bool,
        dontneed: bool,
        pool_bytes: int,
    ) -> None:
        self._lib = lib
        self.direct = direct
        self.dontneed = dontneed
        # the bounce pool exists only for the direct leg (buffered legs
        # write/read straight from/to caller memory) — don't hold 64MB
        # of aligned buffers in every plugin that will never go direct
        self._pool = _AlignedPool(pool_bytes) if direct else None

    # ------------------------------------------------------- helpers

    def _use_direct(self, nbytes: int) -> bool:
        return self.direct and nbytes >= DIRECT_MIN_BYTES

    def open_direct(self, path: str, flags: Optional[int] = None) -> int:
        """O_DIRECT fd on ``path`` (``flags`` defaults to O_RDWR for
        the striped-write handle; the read leg passes O_RDONLY), or -1
        when the direct leg is off or the open fails (per-file
        filesystems can still decline after a successful probe).  Not
        span-bracketed: one open(2) whose latency is inside the
        enclosing stripe/engine span."""
        if not self.direct:
            return -1
        try:
            return os.open(
                path, (os.O_RDWR if flags is None else flags) | os.O_DIRECT
            )
        except OSError as e:
            obs.swallowed_exception("fastio.open_direct", e)
            return -1

    def _part_pwrite(
        self,
        fd: int,
        fd_direct: int,
        offset: int,
        view: memoryview,
        want_digest: bool,
    ) -> Optional[Tuple[int, int]]:
        """One native part write; returns (crc32, adler32) when
        ``want_digest``.  Acquires a pool bounce buffer only for the
        direct leg, and ALWAYS returns it (the chaos suite asserts the
        pool is whole after injected faults)."""
        use_direct = (
            fd_direct >= 0
            and self._pool is not None
            and self._use_direct(view.nbytes)
        )
        out = (ctypes.c_uint32 * 2)()
        bounce = None
        try:
            if use_direct:
                bounce = self._pool.acquire()
            rc = self._lib.tsnp_part_pwrite(
                fd,
                fd_direct if use_direct else -1,
                _buffer_address(view),
                view.nbytes,
                offset,
                ALIGN if use_direct else 0,
                bounce[0] if use_direct else None,
                bounce[1] if use_direct else 0,
                1 if want_digest else 0,
                out,
            )
        finally:
            if bounce is not None:
                self._pool.release(bounce)
        if rc != 0:
            raise OSError(-rc, os.strerror(-rc))
        obs.counter(
            obs.FASTIO_DIRECT_PARTS if use_direct else obs.FASTIO_BUFFERED_PARTS
        ).inc()
        obs.counter(obs.FASTIO_BYTES_WRITTEN).inc(view.nbytes)
        if want_digest:
            obs.counter(obs.FASTIO_FUSED_DIGESTS).inc()
            return (int(out[0]), int(out[1]))
        return None

    # ------------------------------------------------- whole objects

    def write_file(
        self,
        path: str,
        buf: Any,
        sync_file: bool,
        want_digest: bool,
    ) -> Optional[Tuple[int, int]]:
        """Create/truncate ``path`` and write ``buf`` through the
        engine, returning the fused (crc32, adler32) when requested.
        ``path`` is the caller's sibling TEMP file — the temp+rename
        commit discipline stays with the fs plugin."""
        view = memoryview(buf).cast("B")
        with obs.span("fastio/write_file", path=path, bytes=view.nbytes):
            fd = os.open(
                path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC | os.O_CLOEXEC, 0o644
            )
            fd_direct = -1
            try:
                if self._use_direct(view.nbytes):
                    fd_direct = self.open_direct(path)
                digests = self._part_pwrite(
                    fd, fd_direct, 0, view, want_digest
                )
                if sync_file:
                    os.fdatasync(fd)
                if self.dontneed:
                    # best-effort cache hygiene without the bypass —
                    # AFTER the fdatasync: DONTNEED only drops CLEAN
                    # pages, so advising before the sync would be a
                    # no-op for durable writes.  Non-durable writes
                    # still carry dirty pages here; those trim rather
                    # than drop (writeback cleans them later).
                    self._fadvise_dontneed(fd, 0, view.nbytes)
            finally:
                if fd_direct >= 0:
                    os.close(fd_direct)
                os.close(fd)
            return digests

    def read_into(
        self, path: str, offset: int, length: int, out: Any
    ) -> int:
        """Read ``[offset, offset+length)`` of ``path`` into ``out`` (a
        writable buffer of exactly ``length`` bytes); returns bytes
        read (short only at EOF — the caller surfaces that as the I/O
        error it is)."""
        view = memoryview(out).cast("B")
        with obs.span("fastio/read_into", path=path, bytes=length):
            fd = os.open(path, os.O_RDONLY | os.O_CLOEXEC)
            fd_direct = -1
            bounce = None
            try:
                use_direct = self._pool is not None and self._use_direct(
                    length
                )
                if use_direct:
                    fd_direct = self.open_direct(path, os.O_RDONLY)
                    use_direct = fd_direct >= 0
                if use_direct:
                    bounce = self._pool.acquire()
                n = self._lib.tsnp_part_pread(
                    fd,
                    fd_direct if use_direct else -1,
                    _buffer_address(view),
                    length,
                    offset,
                    ALIGN if use_direct else 0,
                    bounce[0] if use_direct else None,
                    bounce[1] if use_direct else 0,
                )
                if n < 0:
                    raise OSError(-n, os.strerror(-n), path)
                if self.dontneed:
                    self._fadvise_dontneed(fd, offset, length)
                    obs.counter(obs.FASTIO_DONTNEED_READS).inc()
                obs.counter(
                    obs.FASTIO_DIRECT_PARTS
                    if use_direct
                    else obs.FASTIO_BUFFERED_PARTS
                ).inc()
                obs.counter(obs.FASTIO_BYTES_READ).inc(int(n))
                return int(n)
            finally:
                if bounce is not None:
                    self._pool.release(bounce)
                if fd_direct >= 0:
                    os.close(fd_direct)
                os.close(fd)

    # ------------------------------------------------- striped parts

    def pwrite_part(
        self,
        fd: int,
        fd_direct: int,
        offset: int,
        buf: Any,
        want_digest: bool,
    ) -> Optional[Tuple[int, int]]:
        """One striped part write at ``offset`` through already-open
        fds (the striped-write handle owns them); returns the part's
        fused (crc32, adler32) when requested — the handle's
        ``supports_fused_digest`` contract."""
        view = memoryview(buf).cast("B")
        with obs.span("fastio/pwrite_part", bytes=view.nbytes, offset=offset):
            return self._part_pwrite(fd, fd_direct, offset, view, want_digest)

    def _fadvise_dontneed(self, fd: int, offset: int, length: int) -> None:
        try:
            os.posix_fadvise(fd, offset, length, os.POSIX_FADV_DONTNEED)
        except (AttributeError, OSError) as e:
            obs.swallowed_exception("fastio.fadvise", e)

    def pool_free_count(self) -> int:
        """Free bounce buffers right now (chaos tests assert the pool
        is whole after injected failures); 0 when the direct leg — and
        with it the pool — is off.  Pure accessor."""
        return self._pool.free_count() if self._pool is not None else 0
