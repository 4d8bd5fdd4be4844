"""Core I/O contracts: cost-annotated deferred work items + storage plugin ABC.

This is the load-bearing abstraction of the whole design, mirrored from the
reference (torchsnapshot/io_types.py:24-120):

- ``BufferStager``: deferred "produce the bytes" (device→host transfer +
  serialize), annotated with its peak host-memory cost so the scheduler can
  admit work under a budget.
- ``BufferConsumer``: the read-side dual — "consume these bytes" (deserialize
  + place into the target array/object).
- ``WriteReq``/``ReadReq`` bind a storage path to a stager/consumer;
  ``ReadReq`` carries an optional byte range for ranged reads.
- ``StoragePlugin``: async write/read/delete/close against a storage backend.

On TPU the stager's device→host copy is ``jax.Array.copy_to_host_async()``
per addressable shard followed by ``np.asarray`` in a worker thread — XLA
transfers complete on their own stream, so cost accounting hooks transfer
completion, not task creation (see scheduler.py).
"""

from __future__ import annotations

import abc
import asyncio
from concurrent.futures import Executor
from dataclasses import dataclass, field
from typing import Any, Callable, Generic, List, Optional, Tuple, TypeVar

T = TypeVar("T")


class Future(Generic[T]):
    """A placeholder for a value produced after read execution completes
    (reference io_types.py Future)."""

    __slots__ = ("obj", "_done")

    def __init__(self, obj: Optional[T] = None) -> None:
        self.obj = obj
        self._done = False

    def set(self, obj: T) -> None:
        self.obj = obj
        self._done = True

    @property
    def done(self) -> bool:
        return self._done


class BufferStager(abc.ABC):
    """Deferred producer of a write buffer (reference io_types.py:24-38)."""

    # Codec preconditioning hint (codec.py): the element stride the
    # byte-shuffle filter should use for this stager's bytes (0 = no
    # filter).  Preparers set it from the manifest dtype (float formats
    # shuffle; ints/bytes/objects don't) — a pure hint, never
    # correctness-bearing: the chosen stride is recorded in each frame's
    # header, so restore needs nothing from the stager.
    codec_filter_stride: int = 0

    @abc.abstractmethod
    async def stage_buffer(self, executor: Optional[Executor] = None) -> Any:
        """Produce the bytes to write (bytes / memoryview). May launch
        device→host transfers; heavy host work should run on ``executor``."""

    @abc.abstractmethod
    def get_staging_cost_bytes(self) -> int:
        """Peak host memory consumed while the staged buffer is alive."""

    # ------------------------------------------------- part streaming
    # Optional capability consumed by the scheduler's stripe path: a
    # stager that can produce its bytes one part at a time lets a large
    # object's staging and storage I/O overlap WITHIN the object — a
    # part stages, its write dispatches immediately, later parts are
    # still staging — and the memory-budget reservation shrinks from
    # the whole object to a window of parts.  Stagers that can only
    # materialize whole (device packs, slabs with interior checksum
    # ranges) keep the defaults and stage as before.

    def part_plan(self, part_size_bytes: int) -> Optional[List[Tuple[int, int]]]:
        """``[start, end)`` byte spans that exactly tile the staged
        object (last span may be short), or None when this stager can
        only stage whole.  Spans must be returnable BEFORE staging (the
        exact-size property the buffer-protocol stagers already have)."""
        return None

    async def stage_part(
        self, span: Tuple[int, int], executor: Optional[Executor] = None
    ) -> Any:
        """Produce exactly the bytes of ``span`` (a span from
        ``part_plan``).  Each part buffer must be independent of the
        others so it can be released as soon as its write completes."""
        raise NotImplementedError

    def release_source(self) -> None:
        """Drop references to the staging source after the last
        ``stage_part`` call (success or failure) — the part-streaming
        twin of ``stage_buffer``'s drop-refs-early discipline."""


class BufferConsumer(abc.ABC):
    """Read-side dual of BufferStager (reference io_types.py:41-56)."""

    @abc.abstractmethod
    async def consume_buffer(
        self, buf: Any, executor: Optional[Executor] = None
    ) -> None:
        """Deserialize ``buf`` and place the result into its target."""

    @abc.abstractmethod
    def get_consuming_cost_bytes(self) -> int:
        """Peak host memory consumed while the read buffer is alive."""


@dataclass
class WriteReq:
    path: str
    buffer_stager: BufferStager
    # (sink, byte_range | None): after staging, each sink receives the
    # crc32 of its slice of the staged buffer (None = whole buffer) —
    # preparers point these at manifest entry/shard ``crc32`` fields so
    # committed metadata carries end-to-end content checksums.  The
    # batcher re-ranges sinks when it folds requests into a slab.
    checksum_sinks: Optional[
        List[Tuple[Callable[[int], None], Optional[Tuple[int, int]]]]
    ] = None
    # incremental takes: (base snapshot url, that base's object digest
    # for this same location).  When the staged object's digest matches,
    # the write is replaced by StoragePlugin.link_from (hardlink /
    # server-side copy) — content-addressed dedup against the previous
    # checkpoint.  The digest is (crc32, adler32, size): two independent
    # checksums + exact length, so one 32-bit collision can't silently
    # link stale content.
    dedup: Optional[Tuple[str, Tuple[int, int, int]]] = None
    # receives the staged object's (crc32, adler32, size) at staging
    # time when WRITE_CHECKSUMS is on
    digest_sink: Optional[Callable[[List[int]], None]] = None
    # filled via digest_sink; consumed by the dedup check
    object_digest: Optional[Tuple[int, int, int]] = None
    # codec layer (codec.py): receives the object's frame table when the
    # write was stored compressed — the snapshot take points this at its
    # per-rank codec map, which rides the crc gather into
    # SnapshotMetadata.codecs.  Writes WITHOUT a sink are never encoded
    # (nothing could record how to decode them).
    codec_sink: Optional[Callable[[dict], None]] = None
    # incremental takes: the BASE snapshot's codec-table entry for this
    # location (None = base stored it raw).  A successful dedup link
    # copies the base's stored bytes, so its frame table must carry over
    # verbatim.
    dedup_codec: Optional[dict] = None
    # content-addressed chunk store (cas/): a CasWriteContext routing
    # this write through the shared chunk pool instead of a per-step
    # object — the scheduler digests the staged bytes in chunk-size
    # spans, skips the write for every chunk an earlier committed step
    # already stored, and the context's sink records the chunk table
    # into the manifest.  Mutually exclusive with ``dedup`` (chunk-level
    # addressing subsumes whole-object base links) and with the codec
    # layer (chunks store raw bytes — their keys ARE raw digests).
    cas: Optional[Any] = None


def check_read_crc(read_req: "ReadReq", buf: Any) -> None:
    """VERIFY_ON_RESTORE: fail loudly when a whole-payload read doesn't
    match its manifest-recorded checksum (shared by the scheduler's
    request-level check and the batcher's per-member slice check)."""
    from .utils.checksums import crc32_fast

    expected = read_req.expected_crc32
    actual = crc32_fast(memoryview(buf).cast("B"))
    if actual != expected:
        raise RuntimeError(
            f"checksum mismatch reading {read_req.path!r} "
            f"(range {read_req.byte_range}): recorded crc32={expected}, "
            f"read crc32={actual} — the payload changed after commit"
        )


@dataclass
class ReadReq:
    path: str
    buffer_consumer: BufferConsumer
    byte_range: Optional[List[int]] = None  # [start, end)
    # manifest-recorded crc32 when this read covers a payload exactly
    # (whole entry/shard/chunk — never a tile); checked before consume
    # when knobs VERIFY_ON_RESTORE is on
    expected_crc32: Optional[int] = None
    # OPTIONAL destination hint: a writable buffer of exactly this
    # read's byte length (e.g. a numpy restore template's memory).  A
    # plugin MAY read straight into it and set ``buf = into`` (the fs
    # plugin's native path does), making host restore a single read
    # pass with no intermediate buffer — the reference's read-into-
    # preallocated-tensor property.  Plugins are free to ignore it;
    # consumers detect honor by identity (``buf is into``) and fall
    # back to the normal copy otherwise, so ignoring is always safe.
    into: Any = None
    # Restore prioritization (serving): lower values execute first.
    # The read scheduler orders its admission queue by this key (stable
    # within a priority class), so a server restoring a snapshot can
    # ask for its first-requested layers first and begin serving before
    # the full snapshot lands.  Purely an ordering hint — correctness
    # never depends on it.
    priority: int = 0


def resolve_read_destination(into: Any, length: int) -> Any:
    """The assembly buffer for a ``length``-byte read honoring the
    ``into`` hint (see ReadReq.into): ``into`` itself when it is a
    writable buffer of exactly ``length`` bytes (callers detect honor
    by identity), else a fresh uint8 array.  Shared by every ranged
    parallel assembler (striped_read, codec.framed_read) so the
    into-honoring contract can't diverge between them."""
    if into is not None:
        try:
            v = memoryview(into).cast("B")
            if not v.readonly and v.nbytes == length:
                return into
        except (TypeError, ValueError):
            pass  # exotic/non-contiguous hint: assemble normally
    import numpy as np

    return np.empty(length, dtype=np.uint8)


@dataclass
class WriteIO:
    path: str
    buf: Any  # bytes | memoryview
    # Durable writes are fdatasync'd, with every directory up the chain
    # fsync'd too.  Set for the COMMIT-point write (.snapshot_metadata)
    # only; bulk data defaults to page-cache mode, so by default the
    # guarantee is "a crash never leaves a HALF-written metadata file" —
    # NOT "a committed local-fs snapshot survives any crash" (data files
    # behind the marker may still be in page cache; a crash window of
    # seconds remains).  For full local-fs crash durability set
    # TORCHSNAPSHOT_TPU_FS_SYNC_DATA=1, which fdatasyncs every data
    # write (costs write throughput).  Object stores (the production
    # target) are durable-on-success by nature and ignore all of this.
    durable: bool = False
    # Digest request: the caller wants the zlib (crc32, adler32) of
    # ``buf``.  A plugin MAY compute it fused with its write (the fs
    # native path digests each block cache-hot in the same pass that
    # hands it to write(2)) and set ``digests``; plugins that don't are
    # fine — the scheduler computes post-write when ``digests`` is
    # still None.  Saves one full read pass over every checksummed
    # direct write on honoring plugins.
    want_digest: bool = False
    digests: Optional[Tuple[int, int]] = None  # set by honoring plugins


@dataclass
class ReadIO:
    path: str
    byte_range: Optional[List[int]] = None
    buf: Any = field(default=None)  # filled by the plugin
    # destination hint (see ReadReq.into); honoring plugins read into
    # it and set ``buf = into``
    into: Any = None
    # Zero-copy request: a plugin that declares ``supports_mmap_read``
    # MAY serve this read as a READ-ONLY mmap-backed buffer (a numpy
    # view over file-backed pages) instead of copying into the heap.
    # Callers detect honor with ``is_mmap_backed(buf)``; plugins are
    # free to ignore the flag (e.g. when the knob is off), so setting
    # it is always safe.  Mutually exclusive with ``into`` in practice
    # — a caller that wants bytes placed into its own buffer has no
    # use for a foreign mapping.
    want_mmap: bool = False


def is_mmap_backed(buf: Any) -> bool:
    """True when ``buf`` is (a view over) an ``mmap.mmap`` — the
    detection contract for ReadIO.want_mmap honor.  Walks the
    numpy ``.base`` / memoryview ``.obj`` ownership chain, so sliced
    and dtype-viewed arrays over a mapping still report True."""
    import mmap as _mmap

    o = buf
    for _ in range(8):  # ownership chains are shallow; bound the walk
        if o is None:
            return False
        if isinstance(o, _mmap.mmap):
            return True
        o = o.obj if isinstance(o, memoryview) else getattr(o, "base", None)
    return False


class StripedWriteHandle(abc.ABC):
    """One in-flight striped (multipart) write of a single object.

    Obtained from ``StoragePlugin.begin_striped_write``; parts may be
    written concurrently (up to ``max_concurrent_parts``) and in any
    order, then EXACTLY ONE of
    ``complete``/``abort`` finishes the handle.  The object must never
    be observable half-written: ``complete`` is the atomic publish (S3
    CompleteMultipartUpload, GCS compose, fs temp→rename) and ``abort``
    must leave zero orphaned parts/temp files behind — a poisoned or
    failed take cleans up after itself (the chaos suite asserts this).

    Retry/failpoint/breaker discipline lives INSIDE ``write_part`` (the
    per-backend classifiers know what a transient looks like), so a
    transient mid-object re-sends one part, not the object."""

    # the part-level twin of StoragePlugin.supports_fused_digest: True
    # when write_part honors ``want_digest`` by computing the part's
    # (crc32, adler32) fused with its copy/upload — the stripe engine
    # then skips its separate per-part digest pass
    supports_fused_digest: bool = False

    # smallest part the backend accepts in any position but the last
    # (S3 rejects CompleteMultipartUpload with EntityTooSmall when a
    # non-final part is under 5MiB).  0 = no floor.  The codec stream
    # consults this: an encoded frame that lands under the floor stores
    # that part raw instead (raw parts are sized by the stripe knob,
    # which backends size above their floor)
    min_part_bytes: int = 0

    @property
    def max_concurrent_parts(self) -> int:
        """How many of this object's parts the stripe engine may have in
        flight at once.  Defaults to the engine's per-object part
        concurrency; a handle whose backend runs one object's parts one
        after another anyway (a buffered file write holds the inode
        lock for its whole copy) says 1, so the caller's threads go to
        other objects instead of queueing on that lock."""
        from .storage.stripe import part_concurrency

        return part_concurrency()

    @abc.abstractmethod
    async def write_part(
        self, index: int, offset: int, buf: Any, want_digest: bool = False
    ) -> Optional[Tuple[int, int]]:
        """Write ``buf`` at byte ``offset`` as part ``index`` (0-based,
        contiguous, exactly tiling the object).  Returns the part's
        (crc32, adler32) when ``want_digest`` and the handle fuses
        digests, else None."""

    @abc.abstractmethod
    async def complete(self) -> None:
        """Atomically publish the assembled object."""

    @abc.abstractmethod
    async def abort(self) -> None:
        """Tear down without publishing; idempotent and best-effort
        (never raises over the original failure)."""


class StoragePlugin(abc.ABC):
    """Async storage backend (reference io_types.py:80-120)."""

    # True when this plugin honors WriteIO.want_digest by computing the
    # (crc32, adler32) fused with its write (one pass over the staged
    # bytes).  The scheduler only DEFERS checksum work to the write for
    # such plugins — on anything else the pre-write digest path keeps
    # its staging-phase overlap.
    supports_fused_digest: bool = False

    # True when begin_striped_write is implemented; the stripe engine
    # (storage/stripe.py) checks this before splitting a write.  Ranged
    # READS need no capability flag — every plugin already honors
    # ReadIO.byte_range, so striped restore works against any backend.
    supports_striped_write: bool = False

    # True when this plugin's striped-write HANDLES honor write_part's
    # ``want_digest`` (StripedWriteHandle.supports_fused_digest) — the
    # scheduler then defers checksum work for stripe-eligible writes
    # too: the folded per-part digests replace the separate staging-
    # phase pass over the whole object.  Plugin-level so the defer
    # decision can be made BEFORE a handle exists.
    supports_fused_part_digest: bool = False

    # True when this plugin can honor ReadIO.want_mmap by serving raw
    # object bytes as a read-only mmap-backed buffer (fs, the shared-
    # host cache, tiered fast reads).
    supports_mmap_read: bool = False

    # STRICTER than supports_mmap_read: True only when every read this
    # plugin serves stays off the Python heap (a local file map, or a
    # cache whose fills stream in bounded spans) — it can never decline
    # into buffering a whole object.  This is the flag the read
    # scheduler keys budget-exempt admission (and the striped-read
    # bypass) on: a composite that can fall back to a raw cloud GET
    # (tier over uncached s3) must keep budgeted, striped reads on that
    # degraded path, even though its fast leg serves mappings.
    mmap_budget_exempt: bool = False

    async def begin_striped_write(
        self, path: str, total_size: int
    ) -> StripedWriteHandle:
        """Open a striped write of ``total_size`` bytes to ``path``.
        Only called when ``supports_striped_write`` is True."""
        raise NotImplementedError

    @abc.abstractmethod
    async def write(self, write_io: WriteIO) -> None: ...

    @abc.abstractmethod
    async def read(self, read_io: ReadIO) -> None: ...

    @abc.abstractmethod
    async def delete(self, path: str) -> None: ...

    async def stat(self, path: str) -> int:
        """Object size in bytes; FileNotFoundError if absent.  The
        default reads the whole object (correct on any plugin);
        subclasses override with a cheap metadata call."""
        read_io = ReadIO(path=path)
        await self.read(read_io)
        return len(read_io.buf)

    async def link_from(self, base_url: str, path: str) -> None:
        """Make ``path`` under this plugin's root hold the same content
        as ``path`` under ``base_url``, WITHOUT moving the bytes through
        this host when the backend can avoid it (fs: hardlink; object
        stores: server-side copy).  Each snapshot must own the resulting
        object — deleting the base must not affect it.  Raising
        NotImplementedError makes the caller fall back to a normal
        write."""
        raise NotImplementedError

    async def close(self) -> None:
        pass

    # Sync convenience wrappers (reference io_types.py:107-120)
    def sync_write(self, write_io: WriteIO) -> None:
        from .utils.asyncio_utils import run_in_fresh_loop

        run_in_fresh_loop(self.write(write_io))

    def sync_read(self, read_io: ReadIO) -> None:
        from .utils.asyncio_utils import run_in_fresh_loop

        run_in_fresh_loop(self.read(read_io))

    def sync_delete(self, path: str) -> None:
        from .utils.asyncio_utils import run_in_fresh_loop

        run_in_fresh_loop(self.delete(path))

    def sync_stat(self, path: str) -> int:
        from .utils.asyncio_utils import run_in_fresh_loop

        return run_in_fresh_loop(self.stat(path))

    def sync_close(self) -> None:
        from .utils.asyncio_utils import run_in_fresh_loop

        run_in_fresh_loop(self.close())
