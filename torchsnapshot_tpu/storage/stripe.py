"""Striped storage I/O engine: part-parallel writes/reads of large objects.

The staging pipeline already overlaps D2H with storage I/O *across*
objects, but a single large tensor used to move as ONE stream — one
``put_object``, one file write, one ranged GET — so intra-object
parallelism was zero and a transient mid-object re-sent everything.
This engine splits any object at or above
``TORCHSNAPSHOT_TPU_STRIPE_MIN_OBJECT_SIZE_BYTES`` into
``TORCHSNAPSHOT_TPU_STRIPE_PART_SIZE_BYTES`` parts and drives the parts
concurrently:

- **writes** go through ``StoragePlugin.begin_striped_write`` — S3 true
  multipart uploads, GCS parallel compose-part uploads, fs ``pwrite``
  into the preallocated temp file, memory ranged writes — with
  retry/failpoint/breaker discipline INSIDE each part
  (``storage.<backend>.part.write`` failpoints), so one flaky connection
  re-sends one part.  A handle says how many of its parts may be in
  flight at once (``StripedWriteHandle.max_concurrent_parts``): an fs
  file on the buffered leg takes one at a time, in offset order, since
  the inode lock serializes its writes anyway — parallelism there comes
  from the files the scheduler writes at once;
- **reads** fan out as parallel ranged ``StoragePlugin.read`` calls
  assembled into one buffer (honoring the ``into`` destination hint),
  which needs no new plugin capability — every backend already honors
  ``ReadIO.byte_range``;
- **streamed writes** (scheduler stream path) overlap staging and I/O
  *within* the object: a part's D2H/defensive copy completes → its
  write dispatches immediately while later parts are still staging, and
  the memory-budget reservation shrinks from the whole object to a
  window of parts.

Failure semantics: any part failure (after its own retries) aborts the
handle — ``abort_multipart_upload`` on S3, part-blob sweep on GCS, temp
unlink on fs — so no orphaned parts survive a failed or poisoned take.

Everything here is span-bracketed and feeds the ``storage.stripe.*``
counters plus part-latency histograms (obs/metrics.py); per-backend
byte/latency instruments keep recording per part via
``record_storage_io``, so backend dashboards see striped traffic too.
"""

from __future__ import annotations

import asyncio
import contextlib
import time
from collections import deque
from concurrent.futures import Executor
from typing import Any, AsyncIterator, Callable, List, Optional, Tuple

from .. import knobs, obs
from ..io_types import ReadIO, StoragePlugin, resolve_read_destination
from ..resilience.failpoints import failpoint


def plan_parts(total: int, part_size: Optional[int] = None) -> List[Tuple[int, int]]:
    """``[start, end)`` byte spans of ``part_size`` exactly tiling
    ``total`` bytes (last span short when the part size doesn't divide
    the object — the boundary case the edge-case suite fuzzes)."""
    if part_size is None:
        part_size = knobs.get_stripe_part_size_bytes()
    if total <= 0:
        return []
    return [
        (lo, min(lo + part_size, total)) for lo in range(0, total, part_size)
    ]


def write_eligible(nbytes: int, storage: StoragePlugin) -> bool:
    """True when a write of ``nbytes`` to ``storage`` should stripe:
    striping enabled, the object clears the threshold (which the knob
    layer floors above one part, so eligibility implies ≥ 2 parts), and
    the plugin implements the striped-write handle."""
    min_bytes = knobs.get_stripe_min_object_size_bytes()
    return (
        min_bytes is not None
        and nbytes >= min_bytes
        and getattr(storage, "supports_striped_write", False)
    )


def read_eligible(nbytes: int) -> bool:
    """Reads stripe on size alone — ranged reads are universal."""
    min_bytes = knobs.get_stripe_min_object_size_bytes()
    return min_bytes is not None and nbytes >= min_bytes


def _backend_name(storage: StoragePlugin) -> str:
    return getattr(storage, "obs_backend", type(storage).__name__)


async def _abort_quiet(handle: Any) -> None:
    """Abort is cleanup: it must never raise OVER the failure that
    triggered it (handles already swallow their own secondary errors;
    this is the engine-level backstop)."""
    try:
        await handle.abort()
    except Exception as e:  # noqa: BLE001
        obs.swallowed_exception("stripe.abort", e)


def part_concurrency() -> int:
    """Concurrent parts per striped object.  Deliberately below the
    per-process I/O cap: one giant object must not monopolize every
    storage slot while smaller objects queue behind it."""
    return max(2, min(knobs.get_max_per_rank_io_concurrency(), 8))


class _PartTurns:
    """Admission of one object's part writes to its handle, at most
    ``handle.max_concurrent_parts`` in flight.  Where that is 1 the parts
    also go strictly in index (offset) order: part i writes once part
    i-1 is over, written or failed, so a file whose writes the backend
    serializes is written front to back by one thread at a time."""

    def __init__(self, limit: int, nparts: int) -> None:
        self.limit = limit
        self._sem = asyncio.Semaphore(limit)
        loop = asyncio.get_running_loop()
        self._over: List[asyncio.Future] = (
            [loop.create_future() for _ in range(nparts)]
            if limit == 1
            else []
        )

    @contextlib.asynccontextmanager
    async def turn(self, idx: int) -> AsyncIterator[None]:
        """Part ``idx``'s write: waits for its admission, and hands the
        turn on when the write is over, however it ended."""
        if self._over and idx:
            await self._over[idx - 1]
        async with self._sem:
            try:
                yield
            finally:
                self.pass_on(idx)

    def pass_on(self, idx: int) -> None:
        """Part ``idx`` is over without (or after) its write: a part
        that failed before writing calls this, or its successors would
        wait for a turn that never comes.  Idempotent."""
        if self._over and not self._over[idx].done():
            self._over[idx].set_result(None)


async def striped_write(
    storage: StoragePlugin,
    path: str,
    buf: Any,
    *,
    on_part_done: Optional[Callable[[int], None]] = None,
    want_digests: bool = False,
) -> Optional[Tuple[int, int, int]]:
    """Write an already-staged buffer as parts, as many at once as the
    handle's ``max_concurrent_parts`` allows.

    ``on_part_done(nbytes)`` fires on the event loop as each part
    completes — the scheduler points it at budget/stat accounting so
    progress is visible (and, for plugins that copy per part, the
    transient part copy is released) at part granularity instead of at
    object end.

    ``want_digests``: ask each part write to fuse its (crc32, adler32)
    into the part's copy/upload (StripedWriteHandle.supports_fused_
    digest) and return the whole object's folded (crc32, adler32,
    size).  Returns None when any part declined — the caller then pays
    the one separate digest pass the pre-fusion path always paid."""
    view = memoryview(buf).cast("B") if not isinstance(buf, memoryview) else buf.cast("B")
    total = view.nbytes
    spans = plan_parts(total)
    backend = _backend_name(storage)
    m_part_lat = obs.histogram(obs.STRIPE_PART_WRITE_LATENCY_S)
    digests: List[Optional[Tuple[int, int, int]]] = [None] * len(spans)

    with obs.span(
        "stripe/write", backend=backend, path=path, bytes=total,
        parts=len(spans),
    ) as sp:
        handle = await storage.begin_striped_write(path, total)
        # direct attribute access (the ABC defaults them), NOT getattr:
        # passing the handle to a call here would read as an ownership
        # handoff to the resource-pairing lint pass and silence its
        # complete/abort check on this function
        fuse = want_digests and handle.supports_fused_digest
        turns = _PartTurns(handle.max_concurrent_parts, len(spans))
        if sp is not None:
            sp.attrs["part_concurrency"] = turns.limit

        async def one(idx: int, lo: int, hi: int) -> None:
            async with turns.turn(idx):
                t0 = time.perf_counter()
                with obs.span(
                    "stripe/write_part", path=path, part=idx, bytes=hi - lo
                ):
                    d = await handle.write_part(
                        idx, lo, view[lo:hi], want_digest=fuse
                    )
                    if fuse and d is not None:
                        digests[idx] = (d[0], d[1], hi - lo)
                dt = time.perf_counter() - t0
            m_part_lat.observe(dt)
            obs.record_storage_io(backend, "write", hi - lo, dt)
            obs.counter(obs.STRIPE_PARTS_WRITTEN).inc()
            if turns.limit == 1:
                obs.counter(obs.STRIPE_SERIAL_PARTS).inc()
            obs.counter(obs.STRIPE_BYTES_WRITTEN).inc(hi - lo)
            if on_part_done is not None:
                on_part_done(hi - lo)

        try:
            # settle every part before deciding the handle's fate: plain
            # gather would cancel awaiting coroutines while their
            # executor threads keep writing, racing the abort's cleanup
            # sweep
            results = await asyncio.gather(
                *(one(i, lo, hi) for i, (lo, hi) in enumerate(spans)),
                return_exceptions=True,
            )
            errs = [r for r in results if isinstance(r, BaseException)]
            if errs:
                raise errs[0]
        except BaseException:
            # BaseException: OUTER cancellation (the scheduler tearing
            # down sibling tasks after another pipeline failed) escapes
            # the gather without an errs entry, and MUST still abort —
            # an unaborted S3 multipart upload bills storage forever.
            # shield: the abort must survive the cancellation that
            # triggered it.  The counter increments BEFORE the shielded
            # await on purpose: a second cancellation landing during
            # the shield re-raises past anything after it, and an abort
            # that actually ran must not vanish from the metric.
            obs.counter(obs.STRIPE_ABORTS).inc()
            await asyncio.shield(_abort_quiet(handle))
            raise
        await handle.complete()
        obs.counter(obs.STRIPE_WRITES).inc()
    if want_digests and all(d is not None for d in digests):
        from ..utils.checksums import combine_piece_digests

        return combine_piece_digests(digests)
    return None


class _ByteGate:
    """Strict-FIFO byte-credit admission for the stream window.  A part
    acquires its raw span size before staging and gives credit back in
    up to two steps: the bytes its encoded frame doesn't need the
    moment the frame exists, the rest when its write completes.  The
    FIFO discipline (a waiter never overtakes an earlier one, even when
    its claim would fit) keeps part admission in index order, so the
    codec offset cascade fills front-to-back and a large head part
    can't be starved by smaller successors."""

    __slots__ = ("_free", "_waiters")

    def __init__(self, capacity: int) -> None:
        self._free = capacity
        self._waiters: deque = deque()

    async def acquire(self, n: int) -> None:
        if self._free >= n and not self._waiters:
            self._free -= n
            return
        fut = asyncio.get_running_loop().create_future()
        entry = (fut, n)
        self._waiters.append(entry)
        try:
            await fut
        except asyncio.CancelledError:
            if fut.done() and not fut.cancelled():
                # the grant raced the cancellation: give it back
                self.release(n)
            else:
                try:
                    self._waiters.remove(entry)
                except ValueError:
                    pass
            raise

    def release(self, n: int) -> None:
        self._free += n
        while self._waiters and self._waiters[0][1] <= self._free:
            fut, need = self._waiters.popleft()
            if fut.done():  # cancelled while queued
                continue
            self._free -= need
            fut.set_result(None)


async def streamed_part_write(
    storage: StoragePlugin,
    path: str,
    stager: Any,
    spans: List[Tuple[int, int]],
    executor: Optional[Executor],
    *,
    window_parts: int,
    on_part_staged: Optional[Callable[[int], None]] = None,
    on_part_done: Optional[Callable[[int], None]] = None,
    want_digests: bool = False,
    codec_spec: Any = None,
    filter_stride: int = 0,
    codec_sink: Optional[Callable[[dict], None]] = None,
) -> Optional[List[Tuple[int, int, int]]]:
    """Per-part stage→write streaming: stage span N, dispatch its write
    the moment its bytes exist, while spans N+1… are still staging.  In-
    flight bytes (staged-but-unwritten or writing) are capped at
    ``window_parts`` full-size parts, which is exactly the scheduler's
    budget reservation for the whole object — the admission win that
    lets an object larger than the budget move under it.  The cap is
    byte-granular (_ByteGate): with a codec, a part's claim shrinks to
    its frame size the moment its encode finishes, so later parts are
    admitted while earlier frames drain to storage.

    Returns ordered per-part ``(crc32, adler32, size)`` digests when
    ``want_digests`` (computed on the executor while the NEXT part
    stages; the caller folds them into the object digest via
    ``utils.checksums.combine_piece_digests``), else None.

    With ``codec_spec`` (codec.WriteSpec), each part additionally passes
    through the compress stage between its RAW digest and its write:
    encode runs on the staging executor, so part N's compression
    overlaps parts N-1…'s storage I/O under the same window.  Encoded
    frames have data-dependent sizes, so each part's storage offset
    resolves from a forward cascade (part N's start = part N-1's end,
    known the moment N-1's encode finishes — encodes run concurrently,
    so the cascade settles far ahead of the uploads it gates).  The
    handle is opened at the raw-size upper bound (+1 header per part)
    and truncates to the high-water mark on complete.  Digests returned
    stay RAW; the stored-byte digest and per-frame lengths flow to
    ``codec_sink`` as the object's manifest codec-table entry."""
    backend = _backend_name(storage)
    total = spans[-1][1]
    m_part_lat = obs.histogram(obs.STRIPE_PART_WRITE_LATENCY_S)
    # per-part phase clocks: streamed parts never pass through the
    # scheduler's stage_one/write_one (where the whole-object phase
    # observations live), so the part IS the phase unit here — these
    # feed the flight record's straggler attribution (obs/aggregate)
    m_phase_stage = obs.histogram(obs.PHASE_STAGE_S)
    m_phase_encode = obs.histogram(obs.PHASE_ENCODE_S)
    m_phase_write = obs.histogram(obs.PHASE_WRITE_S)
    # byte-granular window: capacity equals the scheduler's reservation
    # (window_parts full-size parts).  Without a codec every part holds
    # its raw size from stage to write-complete — identical admission
    # to a window_parts semaphore.  With one, a part returns the bytes
    # compression saved the moment its frame exists, so part N+window
    # starts staging and encoding while earlier (smaller) frames are
    # still on the wire — that early credit is what lets the pipeline
    # hide encode cost instead of running encode waves and wire waves
    # in lockstep.
    gate = _ByteGate(window_parts * max(hi - lo for lo, hi in spans))
    digests: List[Optional[Tuple[int, int, int]]] = [None] * len(spans)
    loop = asyncio.get_running_loop()
    if codec_spec is not None:
        from .. import codec as codec_mod

        # raw upper bound: a frame is never larger than raw + header
        # (store-raw fallback caps expansion at FRAME_HEADER_BYTES)
        ub_total = total + len(spans) * codec_mod.FRAME_HEADER_BYTES
        enc_digests: List[Optional[Tuple[int, int, int]]] = (
            [None] * len(spans)
        )
        frame_lens: List[int] = [0] * len(spans)
        # starts[i] resolves to frame i's storage offset once every
        # earlier frame's encoded size is known
        starts: List[asyncio.Future] = [
            loop.create_future() for _ in spans
        ]
        starts[0].set_result(0)
    else:
        ub_total = total

    def _digest(piece: Any) -> Tuple[int, int, int]:
        from ..utils.checksums import adler32_fast, crc32_fast

        v = memoryview(piece).cast("B")
        return (crc32_fast(v), adler32_fast(v), v.nbytes)

    with obs.span(
        "stripe/stream_write", backend=backend, path=path, bytes=total,
        parts=len(spans), codec=getattr(codec_spec, "codec", None),
    ) as sp:
        handle = await storage.begin_striped_write(path, ub_total)

        # fused copy+digest would hash the STORED bytes; under a codec
        # the manifest digests must be RAW, so fusing is disabled and
        # the raw digest runs before the encode stage
        fuse = (
            want_digests
            and codec_spec is None
            and getattr(handle, "supports_fused_digest", False)
        )
        # parts still stage inside the window as they are admitted;
        # only their writes take turns on the handle
        turns = _PartTurns(handle.max_concurrent_parts, len(spans))
        if sp is not None:
            sp.attrs["part_concurrency"] = turns.limit

        async def one(idx: int, span: Tuple[int, int]) -> None:
            lo, hi = span
            await gate.acquire(hi - lo)
            held = hi - lo
            try:
                flow_id = None
                # clock before the failpoint: injected delay<ms>
                # slowness must land in the stage phase it simulates
                t_stage = time.perf_counter()
                failpoint("scheduler.stage.part", path=path, part=idx)
                with obs.span(
                    "stripe/stage_part", path=path, part=idx, bytes=hi - lo
                ) as stage_sp:
                    piece = await stager.stage_part(span, executor)
                    if stage_sp is not None:
                        # Perfetto flow arrow anchor: this part's stage
                        # slice links to its write slice below, so the
                        # stage→write pipelining of a striped object is
                        # visible per PART in the trace, not just as
                        # one object-level arrow
                        flow_id = stage_sp.flow_out = obs.next_flow_id()
                m_phase_stage.observe(time.perf_counter() - t_stage)
                if on_part_staged is not None:
                    on_part_staged(hi - lo)
                if want_digests and not fuse:
                    if executor is not None:
                        digests[idx] = await loop.run_in_executor(
                            executor, _digest, piece
                        )
                    else:
                        digests[idx] = _digest(piece)
                offset = lo
                if codec_spec is not None:
                    # compress stage: encode on the staging executor
                    # (raw digest above ran on the raw bytes), resolve
                    # this frame's offset from the cascade, and release
                    # the raw part the moment the frame exists
                    t_enc = time.perf_counter()
                    frame = await codec_mod.encode_frame_async(
                        memoryview(piece).cast("B"),
                        codec_spec,
                        filter_stride,
                        executor,
                        path=path,
                        part=idx,
                        # backend part-size floor (S3 EntityTooSmall)
                        # binds every part but the last
                        min_frame_bytes=(
                            getattr(handle, "min_part_bytes", 0)
                            if idx + 1 < len(spans)
                            else 0
                        ),
                    )
                    m_phase_encode.observe(time.perf_counter() - t_enc)
                    del piece
                    frame_lens[idx] = len(frame)
                    # the raw part is gone; return the bytes the frame
                    # doesn't need (an expanded frame — store-raw header
                    # overhead — keeps the full raw claim: ≤24B/part
                    # inside the handle's preallocation headroom)
                    early = held - min(held, len(frame))
                    if early:
                        gate.release(early)
                        held -= early
                    if want_digests:
                        if executor is not None:
                            enc_digests[idx] = await loop.run_in_executor(
                                executor, _digest, frame
                            )
                        else:
                            enc_digests[idx] = _digest(frame)
                    offset = await starts[idx]
                    if idx + 1 < len(spans):
                        starts[idx + 1].set_result(offset + len(frame))
                    piece = frame
                nbytes = memoryview(piece).cast("B").nbytes
                async with turns.turn(idx):
                    t0 = time.perf_counter()
                    with obs.span(
                        "stripe/write_part", path=path, part=idx,
                        bytes=nbytes,
                    ) as write_sp:
                        if write_sp is not None and flow_id is not None:
                            write_sp.flow_in = flow_id
                        d = await handle.write_part(
                            idx, offset, piece, want_digest=fuse
                        )
                    dt = time.perf_counter() - t0
                m_phase_write.observe(dt)
                if fuse:
                    if d is not None:
                        digests[idx] = (d[0], d[1], hi - lo)
                    elif executor is not None:
                        # handle declined this part after all: one
                        # separate pass, same values
                        digests[idx] = await loop.run_in_executor(
                            executor, _digest, piece
                        )
                    else:
                        digests[idx] = _digest(piece)
                m_part_lat.observe(dt)
                obs.record_storage_io(backend, "write", nbytes, dt)
                obs.counter(obs.STRIPE_PARTS_WRITTEN).inc()
                if turns.limit == 1:
                    obs.counter(obs.STRIPE_SERIAL_PARTS).inc()
                obs.counter(obs.STRIPE_BYTES_WRITTEN).inc(nbytes)
                del piece  # the part's bytes die with its write
                if on_part_done is not None:
                    on_part_done(nbytes)
            except BaseException as e:
                # ANY failure in this part — stage failpoint, stager,
                # raw digest, encode, or a poisoned upstream start —
                # must keep the offset cascade flowing, or part idx+1
                # awaits a start that never resolves and the stream
                # wedges instead of failing
                if (
                    codec_spec is not None
                    and idx + 1 < len(spans)
                    and not starts[idx + 1].done()
                ):
                    starts[idx + 1].set_exception(
                        RuntimeError(
                            f"part {idx} of {path!r} failed "
                            f"upstream: {e!r}"
                        )
                    )
                raise
            finally:
                gate.release(held)
                turns.pass_on(idx)

        try:
            try:
                results = await asyncio.gather(
                    *(one(i, s) for i, s in enumerate(spans)),
                    return_exceptions=True,
                )
            finally:
                stager.release_source()
                if codec_spec is not None:
                    # settle the offset cascade: cancel never-resolved
                    # futures and mark propagated errors retrieved, so a
                    # failed stream can't log "exception never
                    # retrieved" at GC
                    for f in starts:
                        if not f.done():
                            f.cancel()
                        elif not f.cancelled():
                            f.exception()
            errs = [r for r in results if isinstance(r, BaseException)]
            if errs:
                raise errs[0]
        except BaseException:
            # outer cancellation must abort too (see striped_write,
            # including why the counter precedes the shielded await)
            obs.counter(obs.STRIPE_ABORTS).inc()
            await asyncio.shield(_abort_quiet(handle))
            raise
        await handle.complete()
        obs.counter(obs.STRIPE_WRITES).inc()
        obs.counter(obs.STRIPE_STREAMED_WRITES).inc()
    if codec_spec is not None and codec_sink is not None:
        stored_digest = None
        if want_digests and all(d is not None for d in enc_digests):
            from ..utils.checksums import combine_piece_digests

            stored_digest = list(combine_piece_digests(enc_digests))
        part_size = spans[0][1] - spans[0][0]
        codec_sink(
            codec_mod.make_table(
                codec_spec.codec, part_size, total, frame_lens,
                stored_digest,
            )
        )
    return [d for d in digests if d is not None] if want_digests else None


async def striped_read(
    storage: StoragePlugin,
    path: str,
    *,
    offset: int,
    length: int,
    into: Any = None,
) -> Any:
    """Ranged parallel read: fetch ``[offset, offset+length)`` as
    concurrent part GETs assembled into one buffer.

    Honors the ``into`` destination hint (io_types.ReadReq.into) by
    reading each part straight into its slice of the destination — the
    caller detects honor by identity, same contract as the plugins'
    own read-into paths.  Per-part retries/failpoints come for free:
    each part is a normal ``storage.read`` against the instrumented,
    retry-wrapped plugin."""
    import numpy as np

    spans = plan_parts(length)
    backend = _backend_name(storage)
    m_part_lat = obs.histogram(obs.STRIPE_PART_READ_LATENCY_S)
    sem = asyncio.Semaphore(part_concurrency())

    out = resolve_read_destination(into, length)
    out_view = memoryview(out).cast("B")

    with obs.span(
        "stripe/read", backend=backend, path=path, bytes=length,
        parts=len(spans),
    ):

        async def one(idx: int, lo: int, hi: int) -> None:
            async with sem:
                dst = out_view[lo:hi]
                t0 = time.perf_counter()
                with obs.span(
                    "stripe/read_part", path=path, part=idx, bytes=hi - lo
                ):
                    rio = ReadIO(
                        path=path,
                        byte_range=[offset + lo, offset + hi],
                        into=dst,
                    )
                    await storage.read(rio)
                    if rio.buf is not dst:
                        got = memoryview(rio.buf).cast("B")
                        if got.nbytes != hi - lo:
                            raise IOError(
                                f"striped read {path} part {idx} "
                                f"[{offset + lo}:{offset + hi}] returned "
                                f"{got.nbytes} bytes"
                            )
                        dst[:] = got
                m_part_lat.observe(time.perf_counter() - t0)
                obs.counter(obs.STRIPE_PARTS_READ).inc()
                obs.counter(obs.STRIPE_BYTES_READ).inc(hi - lo)

        await asyncio.gather(
            *(one(i, lo, hi) for i, (lo, hi) in enumerate(spans))
        )
        obs.counter(obs.STRIPE_READS).inc()
    return out
