"""Budgeted async execution engine for write/read pipelines.

TPU-native analogue of the reference scheduler (torchsnapshot/
scheduler.py:222-463).  Same discipline:

- Write path: ``ready_for_staging → staging → ready_for_io → io → done``.
  A request is admitted to staging iff its cost fits the remaining host
  memory budget, or the pipeline is empty (guaranteed progress for oversized
  items) (reference scheduler.py:266-277).  The budget is debited by the
  declared staging cost and corrected to the actual buffer size once staging
  completes (reference scheduler.py:308-312).
- Concurrent storage ops are capped per process (default 16,
  knobs.get_max_per_rank_io_concurrency; reference scheduler.py:279-290).
- Once all staging completes, control returns to the caller with a
  ``PendingIOWork`` while storage I/O keeps draining — this is what makes
  ``async_take`` "unblock after staging" fall out of the same code path
  (reference scheduler.py:299,334-339).
- Read path is the mirror image: admit reads under the consuming-cost
  budget, chain each completed read into a consume task (reference
  scheduler.py:386-446).

Design difference vs the reference: instead of nesting event loops in the
caller's thread, the pipeline runs on a dedicated event-loop *thread* owned
by the scheduler.  The training thread regains control the moment staging
finishes; residual I/O keeps running on the loop thread with no involvement
from the caller — which is exactly the execution model async snapshots need
on TPU (the background work never issues collectives, so it can never race
with XLA's ICI traffic).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Awaitable, Callable, List, Optional, Tuple

import psutil

from . import _csrc
from . import codec as codec_mod
from . import knobs
from . import staging_arena
from .cas import store as cas_store_mod
from .io_types import (
    ReadIO,
    ReadReq,
    StoragePlugin,
    WriteIO,
    WriteReq,
    check_read_crc,
    is_mmap_backed,
)
from .obs import buf_nbytes as _buf_nbytes
from .obs import metrics as obs_metrics
from .obs import swallowed_exception
from .obs import tracer as obs_tracer
from .resilience.failpoints import failpoint
from .storage import stripe

# Parts of one streamed object in flight (staged-but-unwritten or
# writing) at a time.  This bound IS the budget reservation for the
# whole object: a streamed 8GB tensor reserves 4 parts' worth of host
# memory instead of 8GB, which is what lets objects larger than the
# budget move under it (the progress rule used to admit them alone).
_STREAM_WINDOW_PARTS = 4

logger = logging.getLogger(__name__)

_MAX_PER_RANK_MEMORY_BUDGET_BYTES = 32 * 1024 * 1024 * 1024
_AVAILABLE_MEMORY_MULTIPLIER = 0.6


def _apply_checksum_sinks(buf, sinks, digest_sink=None, precomputed=None) -> None:
    """Feed each sink the crc32 of its byte range of the staged buffer
    (WriteReq.checksum_sinks contract, io_types.py); ``digest_sink``
    additionally receives the whole object's (crc32, adler32, size).

    ``precomputed``: {(start, end): (crc32, adler32, size)} recorded by
    the stager while it packed the bytes (the native fused copy+digest
    pass, batcher.BatchedBufferStager) — matching spans skip hashing
    entirely.  When the sink ranges exactly tile the buffer (a slab:
    members packed back-to-back; or one whole-buffer sink), the object
    digest FOLDS from the per-piece values (utils/checksums.py) instead
    of re-reading every byte; with a full precomputed set the staged
    data is not touched at all here."""
    from . import _csrc
    from .utils.checksums import (
        adler32_fast,
        combine_piece_digests,
        crc32_fast,
    )

    view = memoryview(buf).cast("B")
    pre = precomputed or {}
    sinks = list(sinks or ())  # a generator would be empty on re-iteration
    spans = [
        (0, view.nbytes) if rng is None else (rng[0], rng[1])
        for _, rng in sinks
    ]
    ordered = sorted(set(spans))
    can_fold = (
        digest_sink is not None
        and spans
        and len(ordered) == len(spans)
        and ordered[0][0] == 0
        and ordered[-1][1] == view.nbytes
        and all(a[1] == b[0] for a, b in zip(ordered, ordered[1:]))
    )
    piece_digests = {}
    for (sink, rng), span in zip(sinks, spans):
        hit = pre.get(span)
        if hit is not None and hit[2] == span[1] - span[0]:
            crc = hit[0]
            adler = hit[1]
        else:
            piece = view[span[0] : span[1]]
            crc = crc32_fast(piece)
            adler = adler32_fast(piece) if can_fold else None
        sink(crc)
        if can_fold:
            piece_digests[span] = (crc, adler, span[1] - span[0])
    if digest_sink is None:
        return
    if can_fold:
        crc, adler, total = combine_piece_digests(
            [piece_digests[s] for s in ordered]
        )
        digest_sink([crc, adler, total])
    else:
        # one interleaved native pass when available; else two fast ones
        d = _csrc.digest(view)
        if d is None:
            d = (crc32_fast(view), adler32_fast(view))
        digest_sink([d[0], d[1], view.nbytes])


async def _encode_staged_buffer(
    p: "_WritePipeline",
    wr: WriteReq,
    spec: "codec_mod.WriteSpec",
    executor: Optional[ThreadPoolExecutor],
):
    """Whole-staged writes' compress stage: encode the staged buffer as
    stripe-part-sized frames CONCURRENTLY on the staging executor (a
    multi-part object's frames encode in parallel; a small object is one
    frame), assemble the stored byte stream, and hand the frame table to
    the write's codec_sink.  The raw buffer is released on return — the
    caller replaces ``p.buf`` with the encoded stream, so storage I/O
    and budget accounting both see stored bytes."""
    import numpy as np

    view = memoryview(p.buf).cast("B")
    raw_size = view.nbytes
    if raw_size == 0:
        return p.buf  # nothing to encode; stays a raw (table-less) object
    part_size = knobs.get_stripe_part_size_bytes()
    spans = stripe.plan_parts(raw_size, part_size)
    stride = getattr(wr.buffer_stager, "codec_filter_stride", 0)
    frames = await asyncio.gather(
        *(
            codec_mod.encode_frame_async(
                view[lo:hi], spec, stride, executor,
                path=wr.path, part=i,
            )
            for i, (lo, hi) in enumerate(spans)
        )
    )
    frame_lens = [len(f) for f in frames]
    stored_size = sum(frame_lens)
    out = np.empty(stored_size, dtype=np.uint8)
    pos = 0
    for i, n in enumerate(frame_lens):
        out[pos : pos + n] = np.frombuffer(frames[i], dtype=np.uint8)
        # drop each frame as it lands: peak memory stays raw + stored
        # instead of raw + 2x stored while the stream assembles
        frames[i] = None
        pos += n
    stored_digest = None
    if knobs.write_checksums_enabled():
        def _digest_stored():
            from ._csrc import digest as native_digest
            from .utils.checksums import adler32_fast, crc32_fast

            d = native_digest(out)
            if d is None:
                d = (crc32_fast(out), adler32_fast(out))
            return [d[0], d[1], stored_size]

        if executor is not None:
            stored_digest = await obs_tracer.run_in_executor(
                executor, _digest_stored,
                name="stage/digest", nbytes=stored_size,
            )
        else:
            stored_digest = _digest_stored()
    wr.codec_sink(
        codec_mod.make_table(
            spec.codec, part_size, raw_size, frame_lens, stored_digest,
        )
    )
    return out


def get_process_memory_budget_bytes(local_process_count: int = 1) -> int:
    """Host-memory budget for staging (reference scheduler.py:47-67)."""
    override = knobs.get_per_rank_memory_budget_bytes()
    if override is not None:
        return override
    available = psutil.virtual_memory().available
    budget = int(available * _AVAILABLE_MEMORY_MULTIPLIER / max(1, local_process_count))
    return min(budget, _MAX_PER_RANK_MEMORY_BUDGET_BYTES)


class _LoopThread:
    """A dedicated event-loop thread that outlives the submitting call."""

    def __init__(self, name: str = "tsnp-io-loop") -> None:
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)
        if obs_tracer.ENABLED:
            obs_tracer.name_os_thread()  # the line's name in an xplane
        # Warm the lazy native-library loader BEFORE the loop runs:
        # load() may open /proc/cpuinfo and even compile the .so on
        # its first call in a process, and the first digest/codec user
        # is otherwise an async pipeline task — a multi-second compile
        # on the event loop stalls every in-flight pipeline at once
        # (surfaced by snaplint effect-escape; load() is memoized, so
        # this costs one no-op lock acquire ever after).  A loader
        # failure here must not kill the thread before run_forever, or
        # every submit() would hang on a dead loop — it is logged and
        # counted, and load() (memoized) answers None from then on.
        try:
            _csrc.load()
        except Exception as e:  # noqa: BLE001
            logger.warning(
                "native fastio warm-up failed; continuing without it",
                exc_info=True,
            )
            swallowed_exception("scheduler.fastio_warmup", e)
        self.loop.run_forever()

    def submit(self, coro: Awaitable) -> concurrent.futures.Future:
        return asyncio.run_coroutine_threadsafe(coro, self.loop)

    def shutdown(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join()
        self.loop.close()


class _Budget:
    def __init__(self, total: int) -> None:
        self.total = total
        self.used = 0

    def fits(self, cost: int) -> bool:
        return self.used + cost <= self.total

    def debit(self, cost: int) -> None:
        self.used += cost

    def credit(self, cost: int) -> None:
        self.used -= cost


class _WritePipeline:
    """One write request's journey through the pipeline (reference
    scheduler.py:70-97)."""

    __slots__ = (
        "write_req",
        "staging_cost",
        "admission_cost",
        "stream_spans",
        "buf",
        "buf_size",
        "deduped",
        "defer_digest",
        # chunk-store accounting (cas/): bytes actually written vs
        # skipped because the content was already pooled
        "cas_written",
        "cas_shared",
    )

    def __init__(self, write_req: WriteReq) -> None:
        self.write_req = write_req
        self.staging_cost = write_req.buffer_stager.get_staging_cost_bytes()
        # what budget admission actually debits: the full staging cost,
        # except for part-streamed striped writes, which reserve only a
        # window of parts (set in _execute_write_pipelines)
        self.admission_cost = self.staging_cost
        # part spans when this pipeline stage→writes per part through
        # the stripe engine instead of staging whole
        self.stream_spans = None
        self.buf = None
        self.buf_size = 0
        self.deduped = False
        # checksums deferred to the write itself (fused digest-while-
        # writing on honoring plugins; post-write fallback otherwise)
        self.defer_digest = False
        self.cas_written = 0
        self.cas_shared = 0


class PendingIOWork:
    """Handle for storage I/O still draining after staging completed
    (reference PendingIOWork, scheduler.py:196-216)."""

    def __init__(
        self,
        fut: Optional[concurrent.futures.Future],
        loop_thread: _LoopThread,
        executor: ThreadPoolExecutor,
        stats: dict,
        starter: Optional[Callable[[], concurrent.futures.Future]] = None,
    ) -> None:
        self._fut = fut
        self._starter = starter
        self._loop_thread = loop_thread
        self._executor = executor
        self._stats = stats
        self._completed = False
        # the caller's sync_complete and the commit thread can both
        # reach ensure_started: without the lock a deferred pipeline
        # could be spun up TWICE (two budget admissions, double writes)
        self._start_lock = threading.Lock()

    def ensure_started(self) -> concurrent.futures.Future:
        """Kick off the pipeline if construction deferred it (the
        async_take path defers so the commit thread — not the caller's
        blocked window — pays for pipeline spin-up and the GIL contention
        of the first staging memcpys)."""
        with self._start_lock:
            if self._fut is None:
                self._fut = self._starter()
            return self._fut

    def sync_complete(self) -> None:
        if self._completed:
            return
        try:
            self.ensure_started().result()
        finally:
            self._completed = True
            self._executor.shutdown(wait=False)
            self._loop_thread.shutdown()
            staging_arena.end_save()
        elapsed = self._stats.get("end_ts", time.monotonic()) - self._stats["begin_ts"]
        gb = self._stats["bytes_written"] / 1e9
        if elapsed > 0 and gb > 0:
            logger.info(
                "Wrote %.3f GB in %.2fs (%.2f GB/s)", gb, elapsed, gb / elapsed
            )

    @property
    def bytes_written(self) -> int:
        return self._stats["bytes_written"]


_PROGRESS_INTERVAL_S = 10.0


class _WriteReporter:
    """Periodic pipeline progress log (reference _WriteReporter,
    scheduler.py:98-177: stageable/staging/writable/writing counts, budget
    usage, GB written)."""

    def __init__(self, budget: "_Budget", stats: dict) -> None:
        self.budget = budget
        self.stats = stats
        self.last_ts = time.monotonic()

    def maybe_report(
        self, stageable: int, staging: int, writable: int, writing: int
    ) -> None:
        now = time.monotonic()
        if now - self.last_ts < _PROGRESS_INTERVAL_S:
            return
        self.last_ts = now
        logger.info(
            "write pipeline: %d stage-able | %d staging | %d writable | "
            "%d writing | budget %.1f/%.1f MB | %.2f GB written",
            stageable,
            staging,
            writable,
            writing,
            self.budget.used / 1e6,
            self.budget.total / 1e6,
            self.stats["bytes_written"] / 1e9,
        )


async def _execute_write_pipelines(
    pipelines: List[_WritePipeline],
    storage: StoragePlugin,
    budget: _Budget,
    executor: ThreadPoolExecutor,
    workers: int,
    staging_done: threading.Event,
    stats: dict,
) -> None:
    # Part-streaming eligibility: a stager that can produce parts, a
    # plugin that can absorb them, an object over the stripe threshold,
    # and no interior checksum ranges (slab member sinks need the whole
    # buffer) or pending dedup decision (link-vs-write needs the object
    # digest before any byte moves).  Eligible pipelines reserve only a
    # window of parts from the budget and stage→write each part through
    # the stripe engine.
    #
    # Codec (codec.py): resolved ONCE per pipeline run — CODEC=raw
    # resolves to None here and the whole layer vanishes (zero per-part
    # cost).  Only writes carrying a codec_sink participate: the sink is
    # how the per-object frame table reaches the manifest, and a write
    # without one (external callers, metadata) could never be decoded.
    codec_spec = codec_mod.resolve_write_spec()
    part_size = knobs.get_stripe_part_size_bytes()
    stream_floor = knobs.get_stripe_min_object_size_bytes()
    for p in pipelines:
        wr = p.write_req
        if wr.cas is not None:
            # CAS part pipeline (cas/store.cas_streamed_write): large
            # objects stage→digest→store per CHUNK, so an unchanged
            # part skips its write and releases its admission window
            # the moment its digest resolves.  Needs whole-buffer-only
            # checksum sinks (interior slab ranges want the assembled
            # buffer) and the same size floor as striping — chunk puts
            # need no striped-write plugin capability (each chunk is an
            # ordinary whole-object write).
            if (
                stream_floor is not None
                and p.staging_cost >= stream_floor
                and all(
                    rng is None for _, rng in (wr.checksum_sinks or ())
                )
            ):
                spans = wr.buffer_stager.part_plan(wr.cas.chunk_size)
                if (
                    spans
                    and len(spans) > 1
                    and spans[-1][1] == p.staging_cost
                ):
                    p.stream_spans = spans
                    p.admission_cost = min(
                        p.staging_cost,
                        _STREAM_WINDOW_PARTS * wr.cas.chunk_size,
                    )
            continue
        if (
            wr.dedup is None
            and stripe.write_eligible(p.staging_cost, storage)
            and all(rng is None for _, rng in (wr.checksum_sinks or ()))
        ):
            spans = wr.buffer_stager.part_plan(part_size)
            if spans and len(spans) > 1 and spans[-1][1] == p.staging_cost:
                p.stream_spans = spans
                p.admission_cost = min(
                    p.staging_cost, _STREAM_WINDOW_PARTS * part_size
                )

    ready_for_staging = deque(pipelines)
    ready_for_io: deque = deque()
    staging_tasks: set = set()
    io_tasks: set = set()
    stream_tasks: set = set()
    io_concurrency = knobs.get_max_per_rank_io_concurrency()
    reporter = _WriteReporter(budget, stats)
    # observability: counters/gauges are always on (one locked arithmetic
    # op per pipeline transition); spans exist only under the TRACE knob.
    # Budget-admission spans open per request at pipeline start and close
    # at admission, so queue-wait time is first-class in the trace; a
    # flow id recorded at staging completion links each staging span to
    # its storage-I/O span (the Perfetto async arrow).
    m_staged = obs_metrics.counter(obs_metrics.BYTES_STAGED)
    m_written = obs_metrics.counter(obs_metrics.BYTES_WRITTEN)
    m_deduped = obs_metrics.counter(obs_metrics.BYTES_DEDUPED)
    m_budget = obs_metrics.gauge(obs_metrics.BUDGET_BYTES_IN_USE)
    m_ioq = obs_metrics.gauge(obs_metrics.IO_QUEUE_DEPTH)
    # always-on phase clocks: per-operation deltas of these feed the
    # cross-rank flight record's straggler attribution (obs/aggregate)
    m_phase_stage = obs_metrics.histogram(obs_metrics.PHASE_STAGE_S)
    m_phase_encode = obs_metrics.histogram(obs_metrics.PHASE_ENCODE_S)
    m_phase_write = obs_metrics.histogram(obs_metrics.PHASE_WRITE_S)
    tracer = obs_tracer.get_tracer()
    adm_spans: dict = {}
    flow_ids: dict = {}
    if obs_tracer.ENABLED:
        for p in pipelines:
            adm_spans[id(p)] = tracer.begin(
                "pipeline/budget_admission",
                path=p.write_req.path,
                bytes=p.staging_cost,
            )

    def _admitted(p: _WritePipeline) -> None:
        m_budget.set(budget.used)
        sp = adm_spans.pop(id(p), None)
        if sp is not None:
            tracer.end(sp, fire_event=True)

    # smallest pending admission cost: lets a wake where nothing can fit
    # skip the admission scan in O(1) instead of rotating the whole
    # deque on every task completion (O(n^2) across a large take)
    min_pending_cost = min((p.admission_cost for p in pipelines), default=0)

    # Order, not bytes (the budget bounds those): the staging pool is a
    # FIFO, and a budget that admits every request at once would queue all
    # their materializations ahead of the first staged object's checksum.
    # No more materializations than the pool has workers are in it at any
    # time, so a staged object's later stages are next in line and copy,
    # checksum and write of different objects overlap for the whole save.
    # (The wait for a place is the loop's, not the pool's: no ``stage/*``
    # span's ``queue_ns`` counts it.)
    materializing = asyncio.Semaphore(workers)

    async def stage_one(p: _WritePipeline) -> _WritePipeline:
        with obs_tracer.span(
            "pipeline/staging", path=p.write_req.path, cost=p.staging_cost
        ) as sp:
            await _stage_one_inner(p)
            if sp is not None:
                sp.attrs["bytes"] = p.buf_size
                # flow arrow anchor: this staging span's end links to
                # the matching pipeline/io span's start in the export
                sp.flow_out = flow_ids[id(p)] = obs_tracer.next_flow_id()
        return p

    async def _stage_one_inner(p: _WritePipeline) -> _WritePipeline:
        # clock starts BEFORE the failpoint so injected delay<ms>
        # slowness lands in the phase the flight record attributes
        t_stage = time.perf_counter()
        failpoint("scheduler.stage", path=p.write_req.path)
        async with materializing:
            p.buf = await p.write_req.buffer_stager.stage_buffer(executor)
        p.buf_size = _buf_nbytes(p.buf)
        wr = p.write_req
        # chunk-store writes never encode (chunk keys ARE raw digests;
        # compressing would re-key identical content per take) and never
        # defer digests (the skip-write decision needs them pre-write)
        will_encode = (
            codec_spec is not None
            and wr.codec_sink is not None
            and wr.cas is None
        )
        if (wr.checksum_sinks or wr.digest_sink) and (
            knobs.write_checksums_enabled()
        ):
            precomputed = getattr(wr.buffer_stager, "piece_digests", None)
            if (
                (
                    # stripe-eligible writes defer when the plugin's
                    # part handles fuse digests (the folded per-part
                    # digests replace this pass); whole-object writes
                    # defer on the plugin-level fused write
                    getattr(storage, "supports_fused_part_digest", False)
                    if stripe.write_eligible(p.buf_size, storage)
                    else getattr(storage, "supports_fused_digest", False)
                )
                and wr.dedup is None
                and wr.cas is None
                and not will_encode  # fused digest would hash STORED bytes
                and precomputed is None
                and all(
                    rng is None or (rng[0] == 0 and rng[1] == p.buf_size)
                    for _, rng in (wr.checksum_sinks or ())
                )
            ):
                # whole-buffer sinks, no dedup decision pending: defer
                # to write_one, where an honoring plugin digests each
                # block cache-hot in the SAME pass that writes it —
                # one read of the staged bytes instead of two.  Dedup
                # writes can't defer (the link-vs-write decision needs
                # the digest first), and slab writes already fold from
                # the pack's per-member digests.
                p.defer_digest = True
                m_phase_stage.observe(time.perf_counter() - t_stage)
                return p
            # content checksums into the manifest (entries are serialized
            # at commit, strictly after staging completes) — off-loop,
            # the staged buffer is immutable from here on
            await obs_tracer.run_in_executor(
                executor,
                _apply_checksum_sinks,
                p.buf,
                wr.checksum_sinks,
                wr.digest_sink,
                precomputed,
                name="stage/digest",
                nbytes=p.buf_size,
            )
        m_phase_stage.observe(time.perf_counter() - t_stage)
        if will_encode and not (
            wr.dedup is not None and wr.object_digest == wr.dedup[1]
        ):
            # compress stage (codec.py): digests above ran on the RAW
            # bytes; the staged buffer is replaced by its encoded frames
            # here, so everything downstream (striping decision, budget
            # correction, bytes_written stats) sees STORED bytes.  A
            # write whose dedup digest matched the base skips encoding
            # entirely — it will link, not move bytes.
            t_enc = time.perf_counter()
            p.buf = await _encode_staged_buffer(p, wr, codec_spec, executor)
            p.buf_size = _buf_nbytes(p.buf)
            m_phase_encode.observe(time.perf_counter() - t_enc)
        return p

    async def write_one(p: _WritePipeline) -> _WritePipeline:
        with obs_tracer.span(
            "pipeline/io", path=p.write_req.path, bytes=p.buf_size
        ) as sp:
            if sp is not None:
                fid = flow_ids.pop(id(p), None)
                if fid is not None:
                    sp.flow_in = fid
            t_write = time.perf_counter()
            try:
                return await _write_one_inner(p)
            finally:
                m_phase_write.observe(time.perf_counter() - t_write)

    async def _write_one_inner(p: _WritePipeline) -> _WritePipeline:
        failpoint("scheduler.write", path=p.write_req.path)
        wr = p.write_req
        if wr.cas is not None:
            # content-addressed skip-write short-circuit: digest the
            # staged buffer in chunk-size spans and move only the
            # chunks no committed step already pooled; the chunk table
            # (not a per-step object) is what reaches the manifest
            _table, p.cas_written, p.cas_shared = (
                await cas_store_mod.chunked_write(
                    wr.cas, wr.path, p.buf, executor
                )
            )
            return p
        if wr.dedup is not None and wr.object_digest == wr.dedup[1]:
            # content unchanged vs the base snapshot: link/server-side
            # copy instead of moving the bytes again.  Any failure
            # (plugin without link_from, base object gone, S3's 5GiB
            # CopyObject cap) degrades to the normal write — dedup is an
            # optimization, never a correctness dependency.
            try:
                await storage.link_from(wr.dedup[0], wr.path)
                stats["deduped_bytes"] = (
                    stats.get("deduped_bytes", 0) + p.buf_size
                )
                # the linked object is a byte-copy of the BASE's stored
                # object; if the base was codec-encoded, this snapshot's
                # manifest must carry the base's frame table verbatim
                if wr.codec_sink is not None and wr.dedup_codec is not None:
                    wr.codec_sink(dict(wr.dedup_codec))
                p.deduped = True
                return p
            except Exception as e:  # noqa: BLE001
                logger.info(
                    "dedup link for %r failed (%r); writing normally",
                    wr.path, e,
                )
        if stripe.write_eligible(p.buf_size, storage):
            # whole-staged striped write: the buffer exists, so split it
            # into concurrent parts (true multipart on s3, compose parts
            # on gcs, engine/offset-parallel pwrite on fs).  When the
            # digest was deferred (_stage_one_inner: the plugin's part
            # handles fuse), each part's (crc32, adler32) rides its
            # write and the folded result replaces the staging-phase
            # pass; a declining handle degrades to that one extra pass.
            d = await stripe.striped_write(
                storage, wr.path, p.buf, want_digests=p.defer_digest
            )
            if p.defer_digest:
                if d is None:
                    await obs_tracer.run_in_executor(
                        executor,
                        _apply_checksum_sinks,
                        p.buf,
                        wr.checksum_sinks,
                        wr.digest_sink,
                        None,
                        name="stage/digest",
                        nbytes=p.buf_size,
                    )
                else:
                    for sink, _rng in wr.checksum_sinks or ():
                        sink(d[0])
                    if wr.digest_sink is not None:
                        wr.digest_sink([d[0], d[1], d[2]])
            return p
        wio = WriteIO(path=wr.path, buf=p.buf, want_digest=p.defer_digest)
        await storage.write(wio)
        if p.defer_digest:
            d = wio.digests
            if d is None:
                # plugin didn't fuse: compute now (same values, one
                # extra pass — exactly what the old order always paid)
                await obs_tracer.run_in_executor(
                    executor,
                    _apply_checksum_sinks,
                    p.buf,
                    wr.checksum_sinks,
                    wr.digest_sink,
                    None,
                    name="stage/digest",
                    nbytes=p.buf_size,
                )
            else:
                for sink, _rng in wr.checksum_sinks or ():
                    sink(d[0])
                if wr.digest_sink is not None:
                    wr.digest_sink([d[0], d[1], p.buf_size])
        return p

    async def stream_one(p: _WritePipeline) -> _WritePipeline:
        """Per-part stage→write streaming through the stripe engine: a
        part's copy completes → its write dispatches immediately while
        later parts are still staging.  Budget debit/credit, retries,
        failpoints, breaker accounting and spans/metrics all sit at
        part granularity inside the engine."""
        wr = p.write_req
        want = bool(wr.checksum_sinks or wr.digest_sink) and (
            knobs.write_checksums_enabled()
        )

        def on_part_staged(n: int) -> None:
            m_staged.inc(n)

        def on_part_done(n: int) -> None:
            stats["bytes_written"] += n
            m_written.inc(n)

        stream_codec = (
            codec_spec if wr.codec_sink is not None else None
        )
        with obs_tracer.span(
            "pipeline/stream", path=wr.path, bytes=p.staging_cost,
            parts=len(p.stream_spans),
        ):
            # both scheduler failpoints fire so existing stage/write
            # chaos schedules keep covering streamed objects
            failpoint("scheduler.stage", path=wr.path)
            failpoint("scheduler.write", path=wr.path)
            if wr.cas is not None:
                # CAS part pipeline: stage→digest→store per chunk;
                # unchanged chunks skip their write and on_part_done
                # reports 0 bytes for them, so accounting below sees
                # only content that moved; skipped bytes feed
                # bytes_deduped like the whole-staged CAS path does
                digests = await cas_store_mod.cas_streamed_write(
                    wr.cas,
                    wr.path,
                    wr.buffer_stager,
                    p.stream_spans,
                    executor,
                    window_parts=_STREAM_WINDOW_PARTS,
                    on_part_staged=on_part_staged,
                    on_part_done=on_part_done,
                    on_part_shared=m_deduped.inc,
                )
            else:
                digests = await stripe.streamed_part_write(
                    storage,
                    wr.path,
                    wr.buffer_stager,
                    p.stream_spans,
                    executor,
                    window_parts=_STREAM_WINDOW_PARTS,
                    on_part_staged=on_part_staged,
                    on_part_done=on_part_done,
                    want_digests=want,
                    codec_spec=stream_codec,
                    filter_stride=getattr(
                        wr.buffer_stager, "codec_filter_stride", 0
                    ),
                    codec_sink=wr.codec_sink,
                )
        p.buf_size = p.staging_cost
        if want and digests:
            from .utils.checksums import combine_piece_digests

            crc, adler, total = combine_piece_digests(digests)
            for sink, _rng in wr.checksum_sinks or ():
                sink(crc)
            if wr.digest_sink is not None:
                wr.digest_sink([crc, adler, total])
        return p

    def _launch(p: _WritePipeline) -> None:
        if p.stream_spans is not None:
            stream_tasks.add(asyncio.ensure_future(stream_one(p)))
        else:
            staging_tasks.add(asyncio.ensure_future(stage_one(p)))

    def dispatch_staging() -> None:
        # Scan ALL pending requests, admitting every one that fits the
        # remaining budget — the deque is largest-first, so breaking at
        # a non-fitting head would idle smaller items that DO fit
        # (head-of-line blocking; reference scheduler.py:266-277 iterates
        # the whole ready set).  If nothing fits and nothing is in
        # flight, admit one oversized item to guarantee progress.
        nonlocal min_pending_cost
        if not ready_for_staging:
            return
        if budget.fits(min_pending_cost):
            new_min = None
            for _ in range(len(ready_for_staging)):
                p = ready_for_staging.popleft()
                if budget.fits(p.admission_cost):
                    budget.debit(p.admission_cost)
                    _admitted(p)
                    _launch(p)
                else:
                    ready_for_staging.append(p)
                    if new_min is None or p.admission_cost < new_min:
                        new_min = p.admission_cost
            min_pending_cost = new_min or 0
            if not ready_for_staging:
                return
        if (
            not staging_tasks
            and not stream_tasks
            and not io_tasks
            and not ready_for_io
        ):
            # rotation preserves the largest-first order, so the head is
            # the largest pending item; admitting it leaves min unchanged
            p = ready_for_staging.popleft()
            budget.debit(p.admission_cost)
            _admitted(p)
            _launch(p)
            if not ready_for_staging:
                min_pending_cost = 0

    def dispatch_io() -> None:
        while ready_for_io and len(io_tasks) < io_concurrency:
            p = ready_for_io.popleft()
            io_tasks.add(asyncio.ensure_future(write_one(p)))
        m_ioq.set(len(ready_for_io))

    try:
        while (
            ready_for_staging
            or staging_tasks
            or ready_for_io
            or io_tasks
            or stream_tasks
        ):
            dispatch_staging()
            dispatch_io()
            reporter.maybe_report(
                len(ready_for_staging),
                len(staging_tasks) + len(stream_tasks),
                len(ready_for_io),
                len(io_tasks),
            )
            if not staging_tasks and not io_tasks and not stream_tasks:
                continue
            # timeout keeps the reporter ticking through long stalls (e.g.
            # one giant storage write in flight)
            done, _ = await asyncio.wait(
                staging_tasks | io_tasks | stream_tasks,
                return_when=asyncio.FIRST_COMPLETED,
                timeout=_PROGRESS_INTERVAL_S,
            )
            for task in done:
                if task in staging_tasks:
                    staging_tasks.discard(task)
                    p = task.result()
                    # correct declared cost to actual buffer size
                    # (reference scheduler.py:308-312)
                    budget.credit(p.staging_cost - p.buf_size)
                    m_budget.set(budget.used)
                    m_staged.inc(p.buf_size)
                    ready_for_io.append(p)
                    m_ioq.set(len(ready_for_io))
                elif task in stream_tasks:
                    # streamed pipelines account bytes per part inside
                    # the engine; only the window reservation returns
                    stream_tasks.discard(task)
                    p = task.result()
                    budget.credit(p.admission_cost)
                    m_budget.set(budget.used)
                else:
                    io_tasks.discard(task)
                    p = task.result()
                    if p.write_req.cas is not None:
                        # chunked objects account what actually moved;
                        # skipped chunk bytes are the dedup win
                        stats["bytes_written"] += p.cas_written
                        m_written.inc(p.cas_written)
                        if p.cas_shared:
                            m_deduped.inc(p.cas_shared)
                    elif not p.deduped:  # linked objects moved no bytes
                        stats["bytes_written"] += p.buf_size
                        m_written.inc(p.buf_size)
                    else:
                        m_deduped.inc(p.buf_size)
                    budget.credit(p.buf_size)
                    m_budget.set(budget.used)
                    p.buf = None
            if (
                not ready_for_staging
                and not staging_tasks
                and not stream_tasks
            ):
                # a streamed pipeline's source stays referenced until
                # its LAST part stages, so "staging done" (the point the
                # caller may mutate training state again) must wait for
                # in-flight streams too
                staging_done.set()
        stats["end_ts"] = time.monotonic()
        staging_done.set()
    except BaseException:
        staging_done.set()  # unblock the waiting caller; error surfaces via fut
        for t in staging_tasks | io_tasks | stream_tasks:
            t.cancel()
        raise
    finally:
        # requests never admitted (error/cancel path) close their
        # admission spans here so the trace has no dangling opens
        for sp in adm_spans.values():
            sp.attrs["error"] = True
            tracer.end(sp, fire_event=True)
        adm_spans.clear()


def sync_execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    wait_for_staging: bool = True,
) -> PendingIOWork:
    """Stage all write requests under the memory budget; return once staging
    completes, with residual storage I/O draining in the background
    (reference sync_execute_write_reqs, scheduler.py:342-357).

    With ``wait_for_staging=False`` the call returns immediately and the
    whole pipeline (staging + storage I/O) drains on the loop thread — used
    by ``async_take`` after ``eager_offload_write_reqs`` has already made
    every buffer independent of training state, which moves the unblock
    point from staged-in-client-RAM to DMA-dispatched (the pipeline
    itself kicks off lazily from the commit thread's sync_complete so the
    caller's blocked window pays for nothing but planning + dispatch)."""
    workers = knobs.get_staging_threads()
    # take/pipeline: pool and loop creation and the pipelines up to
    # staging-done, on the caller's thread; the parent of every loop-thread
    # and worker span of a blocking take (storage I/O still draining is
    # waited for under take/commit).  The deferred path opens none: its
    # pipelines start later, on the commit thread.
    with (
        obs_tracer.span("take/pipeline", workers=workers, writes=len(write_reqs))
        if wait_for_staging
        else obs_tracer.NULL_CM
    ):
        executor = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="tsnp-staging"
        )
        # Largest-first staging keeps the budget well-packed and starts the
        # biggest D2H transfers earliest.
        pipelines = sorted(
            (_WritePipeline(wr) for wr in write_reqs),
            key=lambda p: p.staging_cost,
            reverse=True,
        )
        budget = _Budget(memory_budget_bytes)
        # the budget bounds the arena's bytes too, kept ones included;
        # PendingIOWork.sync_complete ends what begins here
        staging_arena.begin_save(memory_budget_bytes)
        staging_done = threading.Event()
        stats = {"bytes_written": 0, "begin_ts": time.monotonic()}
        loop_thread = _LoopThread()

        def _start() -> concurrent.futures.Future:
            return loop_thread.submit(
                _execute_write_pipelines(
                    pipelines, storage, budget, executor, workers,
                    staging_done, stats,
                )
            )

        if not wait_for_staging:
            # Unblock-early path: every buffer is already independent of
            # training state (eager_offload_write_reqs), so nothing here
            # needs to run before control returns.  Defer the pipeline
            # kick-off to the background thread that calls sync_complete()
            # (traced, that thread runs in the async_take's context, so
            # the pipelines' spans still reach the call that planned them).
            return PendingIOWork(
                None, loop_thread, executor, stats, starter=_start
            )

        fut = _start()
        while not staging_done.wait(timeout=0.05):
            if fut.done():
                break
    pending = PendingIOWork(fut, loop_thread, executor, stats)
    if fut.done() and fut.exception() is not None:
        pending.sync_complete()  # raises
    return pending


async def _execute_copy_pipelines(
    paths: List[str],
    src_storage: StoragePlugin,
    dst_storage: StoragePlugin,
    budget: _Budget,
    io_concurrency: int,
    counter_name: str,
) -> int:
    """Copy whole objects src→dst, admitted under the host-memory budget
    (each in-flight copy buffers its full payload; an oversized object is
    admitted alone — the same progress rule as the write pipeline)."""
    m_promoted = obs_metrics.counter(counter_name)
    sem = asyncio.Semaphore(io_concurrency)
    cond = asyncio.Condition()
    in_use = 0

    async def one(path: str) -> int:
        nonlocal in_use
        nbytes = await src_storage.stat(path)
        async with cond:
            await cond.wait_for(
                lambda: in_use == 0 or in_use + nbytes <= budget.total
            )
            in_use += nbytes
        try:
            async with sem:
                with obs_tracer.span(
                    "tier/promote_object", path=path, bytes=nbytes
                ):
                    read_io = ReadIO(path=path)
                    await src_storage.read(read_io)
                    await dst_storage.write(
                        WriteIO(path=path, buf=read_io.buf)
                    )
            m_promoted.inc(nbytes)
            return nbytes
        finally:
            async with cond:
                in_use -= nbytes
                cond.notify_all()

    copied = await asyncio.gather(*(one(p) for p in paths))
    return sum(copied)


async def _execute_buffer_writes(
    items: List[Tuple[str, Any]],
    dst_storage: StoragePlugin,
    budget: _Budget,
    io_concurrency: int,
    counter_name: str,
    failpoint_site: Optional[str] = None,
    span_label: str = "scheduler/buffer_write",
    transport: Any = None,
) -> int:
    """Write already-staged ``(path, buf)`` pairs to ``dst_storage``,
    admitted under the host-memory budget: the buffers exist either
    way, but admission bounds how many a retrying/backpressured target
    can hold IN FLIGHT at once (each queued write can buffer its
    payload again inside the plugin — temp copies, retry bodies), with
    the same oversized-item progress rule as the copy pipeline.

    ``transport`` routes each payload through the engine's fabric leg
    (``Transport.device_move`` — a digest-verified device round-trip on
    the collective engine, identity on KV) before the write.  Any
    transport failure degrades THAT payload to the original staged
    bytes with ``transport.fallbacks`` advancing; correctness never
    depends on the fabric."""
    m_written = obs_metrics.counter(counter_name)
    sem = asyncio.Semaphore(io_concurrency)
    cond = asyncio.Condition()
    in_use = 0

    async def one(path: str, buf: Any) -> int:
        nonlocal in_use
        nbytes = memoryview(buf).cast("B").nbytes
        async with cond:
            await cond.wait_for(
                lambda: in_use == 0 or in_use + nbytes <= budget.total
            )
            in_use += nbytes
        try:
            if failpoint_site is not None:
                failpoint(failpoint_site, path=path)
            out = buf
            if transport is not None:
                from .transport import count_fallback

                loop = asyncio.get_running_loop()
                try:
                    out = await loop.run_in_executor(
                        None, transport.device_move, buf
                    )
                except Exception as e:  # noqa: BLE001 — fabric-leg
                    # failure must cost speed, never the replica
                    count_fallback("buffer-write", e)
                    out = buf
            async with sem:
                with obs_tracer.span(span_label, path=path, bytes=nbytes):
                    await dst_storage.write(WriteIO(path=path, buf=out))
            m_written.inc(nbytes)
            return nbytes
        finally:
            async with cond:
                in_use -= nbytes
                cond.notify_all()

    written = await asyncio.gather(*(one(p, b) for p, b in items))
    return sum(written)


def sync_execute_buffer_writes(
    items: List[Tuple[str, Any]],
    dst_storage: StoragePlugin,
    memory_budget_bytes: int,
    counter_name: str,
    failpoint_site: Optional[str] = None,
    span_label: str = "scheduler/buffer_write",
    loop_thread: Optional[_LoopThread] = None,
    transport: Any = None,
) -> int:
    """Write staged ``(path, buf)`` pairs concurrently under the staging
    memory budget; returns bytes written.  This is the continuous
    checkpoint loop's replication engine (continuous/loop.py): per-step
    delta chunks ride this to the local and peer fast roots as budgeted
    background work, so replication can never out-buffer the budget a
    host sized for takes (the same admission discipline as staging and
    tier promotion).  ``loop_thread`` lets a per-step caller reuse ONE
    long-lived event-loop thread (it stays alive after the call)
    instead of paying thread+loop churn on every training step; omitted,
    a private one is created and torn down like the copy engine's."""
    if not items:
        return 0
    budget = _Budget(memory_budget_bytes)
    own_loop = loop_thread is None
    lt = loop_thread or _LoopThread(name="tsnp-continuous-loop")
    try:
        return lt.submit(
            _execute_buffer_writes(
                items,
                dst_storage,
                budget,
                knobs.get_max_per_rank_io_concurrency(),
                counter_name,
                failpoint_site,
                span_label,
                transport,
            )
        ).result()
    finally:
        if own_loop:
            lt.shutdown()


async def _execute_chunk_reads(
    items: List[Tuple[str, Optional[Tuple[int, int]], Optional[str], int]],
    storage: StoragePlugin,
    budget: _Budget,
    io_concurrency: int,
    span_label: str,
) -> List[bytes]:
    """Read ``(path, byte_range, content_key, nbytes)`` items under the
    host-memory budget, verifying keyed payloads against their embedded
    (crc32, adler32, size) digest — a torn or stale copy fails closed.
    Results come back in submission order."""
    from .utils.checksums import adler32_fast, crc32_fast

    sem = asyncio.Semaphore(io_concurrency)
    cond = asyncio.Condition()
    in_use = 0
    out: List[Optional[bytes]] = [None] * len(items)

    async def one(i: int) -> None:
        nonlocal in_use
        path, byte_range, key, nbytes = items[i]
        async with cond:
            await cond.wait_for(
                lambda: in_use == 0 or in_use + nbytes <= budget.total
            )
            in_use += nbytes
        try:
            async with sem:
                with obs_tracer.span(
                    span_label, path=path, bytes=nbytes
                ):
                    io = ReadIO(path=path, byte_range=byte_range)
                    await storage.read(io)
            view = memoryview(io.buf).cast("B")
            if key is not None and (
                view.nbytes != cas_store_mod.key_size(key)
                or cas_store_mod.chunk_key(
                    (crc32_fast(view), adler32_fast(view), view.nbytes)
                )
                != key
            ):
                raise IOError(
                    f"chunk {key} at {path!r} failed its content "
                    f"check ({view.nbytes} bytes)"
                )
            if view.nbytes != nbytes:
                raise IOError(
                    f"ranged read of {path!r} returned {view.nbytes} "
                    f"bytes, expected {nbytes}"
                )
            out[i] = bytes(view)
        finally:
            async with cond:
                in_use -= nbytes
                cond.notify_all()

    results = await asyncio.gather(
        *(one(i) for i in range(len(items))), return_exceptions=True
    )
    errs = [r for r in results if isinstance(r, BaseException)]
    if errs:
        raise errs[0]
    # every slot filled: a None would have surfaced as an error above
    return [b for b in out if b is not None]


def sync_execute_chunk_reads(
    items: List[Tuple[str, Optional[Tuple[int, int]], Optional[str], int]],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    priorities: Optional[List[int]] = None,
    span_label: str = "scheduler/chunk_read",
    loop_thread: Optional[_LoopThread] = None,
) -> List[bytes]:
    """Verified ranged/content-addressed reads for delta subscribers
    (publish/subscriber.py): fetch ``(path, byte_range, content_key,
    nbytes)`` items concurrently under the staging memory budget and
    return payloads in the caller's order.  ``priorities`` reuses the
    restore priority classes (ReadReq.priority discipline from
    sync_execute_read_reqs): a stable sort dispatches lower classes
    first, so a serving fleet can front-load the leaves its next
    request needs while bulk deltas trail — within a class, submission
    order is preserved.  ``loop_thread`` lets a long-lived watcher
    reuse one event-loop thread across polls instead of paying
    thread+loop churn per update."""
    if not items:
        return []
    order = list(range(len(items)))
    if priorities is not None and any(priorities):
        order.sort(key=lambda i: priorities[i])
    budget = _Budget(memory_budget_bytes)
    own_loop = loop_thread is None
    lt = loop_thread or _LoopThread(name="tsnp-publish-loop")
    try:
        fetched = lt.submit(
            _execute_chunk_reads(
                [items[i] for i in order],
                storage,
                budget,
                knobs.get_max_per_rank_io_concurrency(),
                span_label,
            )
        ).result()
    finally:
        if own_loop:
            lt.shutdown()
    out: List[bytes] = [b""] * len(items)
    for pos, i in enumerate(order):
        out[i] = fetched[pos]
    return out


def sync_execute_copy_reqs(
    paths: List[str],
    src_storage: StoragePlugin,
    dst_storage: StoragePlugin,
    memory_budget_bytes: int,
    counter_name: Optional[str] = None,
) -> int:
    """Copy the named objects from ``src_storage`` to ``dst_storage``
    under the staging memory budget; returns bytes copied.  This is the
    tier promoter's engine (tier/promoter.py): write-back fast-tier
    payloads ride this to the durable tier in the background, with the
    same budget discipline as staging so a promotion burst can never
    OOM a host that sized its budget for takes.  Peer replication
    (tier/plugin.py) reuses it with ``counter_name`` pointed at the
    replication counter."""
    if not paths:
        return 0
    budget = _Budget(memory_budget_bytes)
    loop_thread = _LoopThread(name="tsnp-promote-loop")
    try:
        return loop_thread.submit(
            _execute_copy_pipelines(
                paths,
                src_storage,
                dst_storage,
                budget,
                knobs.get_max_per_rank_io_concurrency(),
                counter_name or obs_metrics.BYTES_PROMOTED,
            )
        ).result()
    finally:
        loop_thread.shutdown()


class _ReadPipeline:
    __slots__ = ("read_req", "consuming_cost", "admission_cost", "use_mmap", "buf")

    def __init__(self, read_req: ReadReq) -> None:
        self.read_req = read_req
        self.consuming_cost = read_req.buffer_consumer.get_consuming_cost_bytes()
        # what budget admission debits: the consuming cost, except for
        # mmap-served reads, which admit at 0 (set in
        # _execute_read_pipelines) — their pages are file-backed and
        # reclaimable, so they occupy no heap the budget protects
        self.admission_cost = self.consuming_cost
        self.use_mmap = False
        self.buf = None


async def _execute_read_pipelines(
    pipelines: List[_ReadPipeline],
    storage: StoragePlugin,
    budget: _Budget,
    executor: ThreadPoolExecutor,
    codec_tables: Optional[dict] = None,
    cas_reads: Optional[tuple] = None,
) -> None:
    # Zero-copy serving (io_types.ReadIO.want_mmap): raw reads against
    # a plugin whose reads NEVER transit the heap (mmap_budget_exempt —
    # fs, the host cache, tiers whose both legs qualify) are served as
    # read-only file-backed mappings and admitted BUDGET-EXEMPT —
    # serializing reclaimable page mappings behind the host staging
    # budget would throttle a many-reader cold start for no
    # memory-safety gain.  Deliberately keyed on the STRICT capability,
    # not supports_mmap_read: a tier over a raw cloud durable keeps its
    # budgeted, striped reads on the degraded fallback path.  Codec
    # frames and CAS chunk refs need a byte transform, so they keep the
    # copying (budgeted) path; a read with an ``into`` destination is
    # already one-touch and wants the bytes in ITS buffer, not a
    # foreign mapping.
    mmap_capable = knobs.mmap_enabled() and getattr(
        storage, "mmap_budget_exempt", False
    )
    for p in pipelines:
        rr = p.read_req
        if (
            mmap_capable
            and rr.into is None
            and not (codec_tables and rr.path in codec_tables)
            and not (cas_reads is not None and rr.path in cas_reads[1])
        ):
            p.use_mmap = True
            p.admission_cost = 0
    ready_for_io = deque(pipelines)
    io_tasks: set = set()
    consume_tasks: set = set()
    io_concurrency = knobs.get_max_per_rank_io_concurrency()
    # observability twins of the write loop's instruments, direction-
    # suffixed: an async_take's background drain can overlap a restore
    # in this process, so the pipelines get separate gauges
    m_read = obs_metrics.counter(obs_metrics.BYTES_READ)
    m_budget = obs_metrics.gauge(obs_metrics.BUDGET_BYTES_IN_USE_READ)
    m_ioq = obs_metrics.gauge(obs_metrics.IO_QUEUE_DEPTH_READ)
    # restore-side phase clocks (flight-record straggler attribution)
    m_phase_read = obs_metrics.histogram(obs_metrics.PHASE_READ_S)
    m_phase_consume = obs_metrics.histogram(obs_metrics.PHASE_CONSUME_S)
    tracer = obs_tracer.get_tracer()
    adm_spans: dict = {}
    if obs_tracer.ENABLED:
        for p in pipelines:
            adm_spans[id(p)] = tracer.begin(
                "pipeline/budget_admission",
                path=p.read_req.path,
                bytes=p.consuming_cost,
            )

    def _admitted(p: _ReadPipeline) -> None:
        m_budget.set(budget.used)
        sp = adm_spans.pop(id(p), None)
        if sp is not None:
            tracer.end(sp, fire_event=True)

    # smallest pending admission cost — O(1) skip of the admission scan
    # on wakes where nothing can fit (see the write loop's twin)
    min_pending_cost = min((p.admission_cost for p in pipelines), default=0)

    # striped reads need the object's byte length up front; a whole-
    # object read only knows its consuming-cost ESTIMATE, so resolve it
    # with a stat — but never through the base-class default, which
    # "stats" by reading the whole object (all shipped plugins override
    # it with a cheap metadata call)
    cheap_stat = type(storage).stat is not StoragePlugin.stat

    async def _striped_read(p: _ReadPipeline, sp) -> bool:
        """Fan a large read out as parallel ranged part GETs through the
        stripe engine (storage/stripe.py).  Returns False when the read
        turns out ineligible (size below threshold once known) so the
        caller falls through to the single-stream path."""
        rr = p.read_req
        if rr.byte_range is not None:
            offset, length = rr.byte_range[0], rr.byte_range[1] - rr.byte_range[0]
        else:
            if not cheap_stat:
                return False
            offset, length = 0, await storage.stat(rr.path)
        if not stripe.read_eligible(length):
            return False
        if sp is not None:
            sp.attrs["striped"] = True
        p.buf = await stripe.striped_read(
            storage, rr.path, offset=offset, length=length, into=rr.into
        )
        return True

    async def read_one(p: _ReadPipeline) -> _ReadPipeline:
        with obs_tracer.span(
            "pipeline/io",
            path=p.read_req.path,
            cost=p.consuming_cost,
            op="read",
        ) as sp:
            # clock before failpoint: injected delay must be attributed
            t_read = time.perf_counter()
            failpoint("scheduler.read", path=p.read_req.path)
            try:
                return await _read_one_inner(p, sp)
            finally:
                m_phase_read.observe(time.perf_counter() - t_read)

    async def _read_one_inner(p: _ReadPipeline, sp) -> _ReadPipeline:
        rr = p.read_req
        if cas_reads is not None:
            ctable = cas_reads[1].get(rr.path)
            if ctable is not None:
                # chunk-ref'd object (cas/): no per-step storage object
                # exists at this location — assemble the RAW byte range
                # from the shared chunk pool (parallel ranged chunk
                # reads, into-honoring).  Chunked objects are never
                # codec-encoded or striped, so this subsumes both.
                p.buf = await cas_store_mod.chunked_read(
                    cas_reads[0],
                    rr.path,
                    ctable,
                    byte_range=rr.byte_range,
                    into=rr.into,
                )
                if sp is not None:
                    sp.attrs["cas"] = True
                    sp.attrs["bytes"] = _buf_nbytes(p.buf)
                return p
        table = codec_tables.get(rr.path) if codec_tables else None
        if table is not None:
            # codec-encoded object (codec.py): the byte range is a
            # RAW range — map it to the overlapping frames, read
            # them as parallel ranged GETs and decode concurrently
            # on the consume executor.  Subsumes the striped-read
            # fan-out (frames ARE the parts).
            p.buf = await codec_mod.framed_read(
                storage,
                rr.path,
                table,
                byte_range=rr.byte_range,
                into=rr.into,
                executor=executor,
            )
            if sp is not None:
                sp.attrs["codec"] = table.get("codec")
                sp.attrs["bytes"] = _buf_nbytes(p.buf)
            return p
        if p.use_mmap:
            # one map call serves any size — fanning out parallel ranged
            # GETs (striping) would only buy page-cache copies, so the
            # striped path is deliberately skipped here
            read_io = ReadIO(
                path=rr.path, byte_range=rr.byte_range, want_mmap=True
            )
            await storage.read(read_io)
            p.buf = read_io.buf
            if _buf_nbytes(p.buf) and not is_mmap_backed(p.buf):
                # the plugin declined the mapping (e.g. a tiered read
                # whose fast copy is gone falling back to a cloud
                # durable): these bytes ARE heap — debit them so a
                # burst of declined reads can't blow past the budget
                # unaccounted.  May transiently overshoot the total;
                # further admission stalls until the consume credits
                # it back, which is exactly the wanted backpressure.
                p.admission_cost = p.consuming_cost
                budget.debit(p.admission_cost)
                m_budget.set(budget.used)
            if sp is not None:
                sp.attrs["mmap"] = is_mmap_backed(p.buf)
                sp.attrs["bytes"] = _buf_nbytes(p.buf)
            return p
        if stripe.read_eligible(
            rr.byte_range[1] - rr.byte_range[0]
            if rr.byte_range is not None
            else p.consuming_cost
        ) and await _striped_read(p, sp):
            if sp is not None:
                sp.attrs["bytes"] = _buf_nbytes(p.buf)
            return p
        read_io = ReadIO(
            path=rr.path,
            byte_range=rr.byte_range,
            into=rr.into,
        )
        await storage.read(read_io)
        p.buf = read_io.buf
        if sp is not None:
            sp.attrs["bytes"] = _buf_nbytes(p.buf)
        return p

    async def consume_one(p: _ReadPipeline) -> _ReadPipeline:
        with obs_tracer.span(
            "pipeline/consume",
            path=p.read_req.path,
            cost=p.consuming_cost,
        ) as sp:
            nbytes = None
            if sp is not None:
                # actual size, not the pre-read estimate (object entries
                # declare cost 1) — p.buf is released below, measure now
                nbytes = sp.attrs["bytes"] = _buf_nbytes(p.buf)
            t_consume = time.perf_counter()
            if (
                p.read_req.expected_crc32 is not None
                and knobs.verify_on_restore()
            ):
                await obs_tracer.run_in_executor(
                    executor, check_read_crc, p.read_req, p.buf,
                    name="consume/crc", nbytes=nbytes,
                )
            await p.read_req.buffer_consumer.consume_buffer(p.buf, executor)
            p.buf = None
            m_phase_consume.observe(time.perf_counter() - t_consume)
            return p

    try:
        while ready_for_io or io_tasks or consume_tasks:
            # admit reads under the consuming-cost budget, scanning past
            # non-fitting items so one big read can't idle small ones
            # (reference scheduler.py:386-446)
            if (
                ready_for_io
                and len(io_tasks) < io_concurrency
                and budget.fits(min_pending_cost)
            ):
                # Rotation discipline: once something was RE-APPENDED
                # (budget-skipped), the rotation must complete so the
                # deque's relative order is preserved; but when the io
                # CAP stops a pure-prefix admission, the remaining deque
                # is untouched and already in order — stop immediately.
                # A 20k-tiny-leaf restore otherwise pays a full O(n)
                # deque rotation on every wake (measured: most of the
                # admission loop's time).  On the early stop the min
                # watermark keeps its previous value, which remains a
                # valid conservative lower bound of the pending set.
                new_min = None
                reappended = False
                early_stop = False
                for _ in range(len(ready_for_io)):
                    if len(io_tasks) >= io_concurrency and not reappended:
                        early_stop = True
                        break
                    p = ready_for_io.popleft()
                    if len(io_tasks) < io_concurrency and budget.fits(
                        p.admission_cost
                    ):
                        budget.debit(p.admission_cost)
                        _admitted(p)
                        io_tasks.add(asyncio.ensure_future(read_one(p)))
                    else:
                        ready_for_io.append(p)
                        reappended = True
                        if new_min is None or p.admission_cost < new_min:
                            new_min = p.admission_cost
                if not early_stop:
                    min_pending_cost = new_min if new_min is not None else 0
            if ready_for_io and not io_tasks and not consume_tasks:
                p = ready_for_io.popleft()
                budget.debit(p.admission_cost)
                _admitted(p)
                io_tasks.add(asyncio.ensure_future(read_one(p)))
                min_pending_cost = min(
                    (q.admission_cost for q in ready_for_io), default=0
                )
            m_ioq.set(len(ready_for_io))
            if not io_tasks and not consume_tasks:
                continue
            done, _ = await asyncio.wait(
                io_tasks | consume_tasks, return_when=asyncio.FIRST_COMPLETED
            )
            for task in done:
                if task in io_tasks:
                    io_tasks.discard(task)
                    p = task.result()
                    # count ACTUAL bytes, not the consuming-cost estimate
                    # (object entries declare cost 1 before the read —
                    # the estimate would undercount them by orders of
                    # magnitude); p.buf is released by consume_one, so
                    # this is the last cheap place to measure it
                    m_read.inc(_buf_nbytes(p.buf))
                    consume_tasks.add(asyncio.ensure_future(consume_one(p)))
                else:
                    consume_tasks.discard(task)
                    p = task.result()
                    budget.credit(p.admission_cost)
                    m_budget.set(budget.used)
    except BaseException:
        for t in io_tasks | consume_tasks:
            t.cancel()
        raise
    finally:
        for sp in adm_spans.values():
            sp.attrs["error"] = True
            tracer.end(sp, fire_event=True)
        adm_spans.clear()


def sync_execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    codec_tables: Optional[dict] = None,
    cas_reads: Optional[tuple] = None,
    publish_first: Optional[set] = None,
) -> None:
    """Execute read requests under the memory budget (reference
    sync_execute_read_reqs, scheduler.py:449-463).

    ``codec_tables``: location → manifest codec-table entry for objects
    stored as compressed frames (SnapshotMetadata.codecs); reads of
    those locations decode transparently — byte ranges stay RAW
    everywhere above this call.

    ``cas_reads``: ``(ChunkStore, {location → chunk table})`` for
    chunk-ref'd objects (SnapshotMetadata.cas); reads of those
    locations assemble from the shared chunk pool instead of the
    snapshot's own storage — equally transparent.

    ``publish_first``: locations this rank redistributes to fan-out
    siblings (topology/fanout.py) — within each priority class those
    reads execute FIRST, so every sibling's wait for this rank's
    publications is bounded by the designated reads' latency, not by
    wherever they happened to land in the queue."""
    workers = knobs.get_staging_threads()
    # restore/pipeline: this call whole on the caller's thread (pool and
    # loop creation, the pipelines, shutdown); the parent of every
    # loop-thread and worker span of the read
    with obs_tracer.span(
        "restore/pipeline", workers=workers, reads=len(read_reqs)
    ):
        executor = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="tsnp-consume"
        )
        # Restore prioritization (ReadReq.priority): stable sort, so a
        # server's first-requested layers head the admission queue and can
        # start serving before the full snapshot lands.  The common case
        # (all priorities 0, no fan-out) keeps its original order untouched.
        if publish_first:
            read_reqs = sorted(
                read_reqs,
                key=lambda rr: (
                    rr.priority, 0 if rr.path in publish_first else 1
                ),
            )
        elif any(rr.priority for rr in read_reqs):
            read_reqs = sorted(read_reqs, key=lambda rr: rr.priority)
        pipelines = [_ReadPipeline(rr) for rr in read_reqs]
        budget = _Budget(memory_budget_bytes)
        loop_thread = _LoopThread(name="tsnp-read-loop")
        t0 = time.monotonic()
        fut = loop_thread.submit(
            _execute_read_pipelines(
                pipelines, storage, budget, executor, codec_tables, cas_reads
            )
        )
        try:
            fut.result()
            # read throughput breadcrumb (reference logs the symmetric
            # number on its read path, scheduler.py:443-444)
            total = sum(p.consuming_cost for p in pipelines)
            dt = max(time.monotonic() - t0, 1e-9)
            if total:
                logger.info(
                    "rank %d: read %.2fGB in %.2fs (%.2f GB/s)",
                    rank, total / 1e9, dt, total / 1e9 / dt,
                )
        finally:
            executor.shutdown(wait=False)
            loop_thread.shutdown()
