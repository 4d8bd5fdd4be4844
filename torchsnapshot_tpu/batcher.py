"""Small-write coalescing (slabs) and ranged-read merging.

Reference: torchsnapshot/batcher.py:51-486.  Write requests smaller than the
slab threshold (128MB knob) whose manifest entries carry a byte-range field
are packed into slab objects written as one storage op; the entries are
re-pointed at ``(slab_location, byte_range)``.  On read, multiple ranged
reads of the same location are merged into one spanning read whose consumer
slices and feeds the original consumers (reference batcher.py:387-478).

All byte sizes are exactly known at plan time (buffer-protocol staging cost
== serialized size), so entries can be re-pointed before staging happens —
same property the reference relies on.

The reference's GPU-slab variant (pack on device + single DtoH,
batcher.py:104-162) has a TPU analogue here: when every slab member is a
device jax.Array of one element width on one device, the slab is packed
on device (same-width bitcast + concatenate as one XLA op,
ops/device_pack.py) and fetched in a single transfer; every other slab
packs on the host.
"""

from __future__ import annotations

import logging
from concurrent.futures import Executor
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import knobs, obs
from .io_types import BufferConsumer, BufferStager, ReadReq, WriteReq
from .utils import domain_private
from .manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    Entry,
    ObjectEntry,
    ShardedArrayEntry,
)

logger = logging.getLogger(__name__)


@domain_private(
    "a batch is built by the planner, staged exactly once by one "
    "pipeline task, and its stagers list is cleared by that same "
    "task — instances are never shared between concurrent stage calls"
)
class BatchedBufferStager(BufferStager):
    """Stage sub-buffers into one slab (reference BatchedBufferStager,
    batcher.py:51-103).

    When every member is a device jax.Array with the same element width
    (what ``batch_write_requests`` groups by) on the same device(s), the
    slab is packed ON DEVICE (same-width bitcast+concat, one XLA op) and
    fetched with a single transfer — the TPU analogue of the reference's
    GPU slab (batcher.py:104-162).  Any other slab packs on the host by
    choice; a device pack that FAILS (ditto the reference's OOM fallback,
    batcher.py:144-152) also lands there, logged and counted."""

    def __init__(self, stagers: List[Tuple[BufferStager, int]], total: int):
        self.stagers = stagers
        self.total = total
        from .preparers.array import JaxArrayBufferStager

        self._all_jax = all(
            isinstance(s, JaxArrayBufferStager) for s, _ in stagers
        )
        groups = {_pack_group(s) for s, _ in stagers} if self._all_jax else ()
        self._device_packable = len(groups) == 1
        # element width of a slab the device can pack; 0: host members,
        # several widths or several devices
        self._width = next(iter(groups))[1] if self._device_packable else 0

    async def stage_buffer(self, executor: Optional[Executor] = None) -> memoryview:
        with obs.span(
            "pipeline/slab_pack",
            members=len(self.stagers),
            bytes=self.total,
            width=self._width,
        ):
            buf = await self._stage_buffer_impl(executor)
        obs.counter(obs.SLABS_PACKED).inc()
        return buf

    async def _stage_buffer_impl(
        self, executor: Optional[Executor] = None
    ) -> memoryview:
        # Members already offloaded to host memory kind must NOT go through
        # the device pack: computing (concat) on host-kind arrays is not a
        # supported XLA path — copy them out individually instead.
        if self._device_packable and not self._any_member_on_host():
            try:
                return await self._stage_device_packed(executor)
            except Exception as e:  # e.g. HBM OOM: host-side packing
                logger.warning(
                    "device slab pack failed; host fallback", exc_info=True
                )
                obs.swallowed_exception("batcher.device_pack", e)
        # Host fallback stages members SEQUENTIALLY so peak memory stays at
        # slab + one member — matching get_staging_cost_bytes regardless of
        # which path ran.  When the native engine is present, each member
        # is packed with the fused copy+digest pass (one read + one write
        # of memory traffic, GIL released); the recorded per-member
        # (crc32, adler32, size) lets the scheduler feed manifest checksum
        # sinks and fold the slab digest with NO further passes over the
        # staged bytes (scheduler._apply_checksum_sinks).
        import zlib

        from ._csrc import copy_digest

        # with checksums disabled (max-throughput mode) the pack is a
        # plain memcpy — computing crc+adler only to throw them away
        # would cost ~2x on the pack pass
        want_digests = knobs.write_checksums_enabled()

        def _pack_one(dst, view):
            # heavy pass (memcpy + crc32 + adler32, GIL released inside
            # the ctypes call) — big members run in the executor so the
            # loop thread stays free for other pipelines' staging and
            # I/O completions
            if not want_digests:
                dst[:] = view
                return None
            d = copy_digest(dst, view)
            if d is None:  # no native lib: plain copy, no digests
                dst[:] = view
            return d

        # tiny members: the ctypes/executor round-trips cost more than
        # the copy itself (a 20k-leaf optimizer state is 20k ~16-byte
        # members) — python slice copy + zlib digests inline; mid-size
        # members pack natively inline (sub-ms loop occupancy); only
        # genuinely big copies pay the executor hop
        _INLINE_PY_MAX = 4096
        _EXEC_OFFLOAD_MIN = 256 * 1024

        slab = bytearray(self.total)
        slab_view = memoryview(slab)
        piece_digests: dict = {}
        offset = 0
        for s, cost in self.stagers:
            buf = await s.stage_buffer(executor)
            view = memoryview(buf).cast("B")
            assert view.nbytes == cost, (view.nbytes, cost)
            dst = slab_view[offset : offset + cost]
            if cost == 0:
                digest = (0, 1)
            elif cost <= _INLINE_PY_MAX:
                dst[:] = view
                digest = (
                    (
                        zlib.crc32(view) & 0xFFFFFFFF,
                        zlib.adler32(view) & 0xFFFFFFFF,
                    )
                    if want_digests
                    else None
                )
            elif executor is not None and cost >= _EXEC_OFFLOAD_MIN:
                digest = await obs.run_in_executor(
                    executor, _pack_one, dst, view,
                    name="stage/copy", nbytes=cost,
                )
            else:
                digest = _pack_one(dst, view)
            if digest is None:
                piece_digests = None
            elif piece_digests is not None:
                piece_digests[(offset, offset + cost)] = (
                    digest[0],
                    digest[1],
                    cost,
                )
            offset += cost
            del buf, view, dst
        if piece_digests:
            self.piece_digests = piece_digests
        self.stagers = []
        obs.counter(obs.SLAB_HOST_PACK_BYTES).inc(self.total)
        return memoryview(slab)

    def _any_member_on_host(self) -> bool:
        from .host_offload import is_host_offloaded

        return any(
            getattr(s, "arr", None) is not None and is_host_offloaded(s.arr)
            for s, _ in self.stagers
        )

    async def _stage_device_packed(
        self, executor: Optional[Executor]
    ) -> memoryview:
        from .ops.device_pack import pack_arrays_to_host

        arrays = [
            s.arr if s.index is None else s.arr[s.index] for s, _ in self.stagers
        ]
        if executor is not None:
            slab = await obs.run_in_executor(
                executor, pack_arrays_to_host, arrays,
                name="stage/materialize", nbytes=self.total,
            )
        else:
            slab = pack_arrays_to_host(arrays)
        if slab.nbytes != self.total:
            raise ValueError(f"packed {slab.nbytes} != expected {self.total}")
        self.stagers = []
        return memoryview(slab).cast("B")

    def part_plan(self, part_size_bytes: int):
        # Deliberately not part-streamable: members carry re-ranged
        # checksum sinks over interior slab spans, the device pack is a
        # single XLA op with no per-part completion signal, and the host
        # fallback's fused copy+digest already records per-member piece
        # digests.  A slab that clears the stripe threshold still gets
        # intra-object write parallelism from the whole-staged striped
        # path in scheduler._write_one_inner.
        return None

    def get_staging_cost_bytes(self) -> int:
        # covers both paths: device pack holds just the slab (1x); the
        # sequential host fallback holds slab + one member at a time
        max_member = max((c for _, c in self.stagers), default=0)
        return self.total + max_member


def _pack_group(stager: Any) -> Tuple:
    """What the operands of one device pack must share: the device(s)
    they are committed to (one jit takes no others) and the element
    width (each joins the slab by a same-width bitcast)."""
    from .ops.device_pack import packed_width

    arr = stager.arr
    return tuple(sorted(d.id for d in arr.devices())), packed_width(arr.dtype)


def _byte_range_targets(entries: Dict[str, Entry]) -> Dict[str, Any]:
    """location → the manifest record whose (location, byte_range) must be
    re-pointed when its blob moves into a slab."""
    targets: Dict[str, Any] = {}
    for entry in entries.values():
        if isinstance(entry, (ArrayEntry, ObjectEntry)):
            targets[entry.location] = entry
        elif isinstance(entry, ChunkedArrayEntry):
            for chunk in entry.chunks:
                targets[chunk.location] = chunk
        elif isinstance(entry, ShardedArrayEntry):
            for shard in entry.shards:
                targets[shard.location] = shard
    return targets


def batch_write_requests(
    entries: Dict[str, Entry], write_reqs: List[WriteReq], rank: int
) -> Tuple[Dict[str, Entry], List[WriteReq]]:
    """Coalesce small array writes into ≥slab-threshold objects (reference
    batch_write_requests, batcher.py:204-355)."""
    from .preparers.array import JaxArrayBufferStager

    threshold = knobs.get_slab_size_threshold_bytes()
    host_member_max = knobs.get_slab_host_member_max_bytes()
    targets = _byte_range_targets(entries)
    small: List[Tuple[WriteReq, int]] = []
    rest: List[WriteReq] = []
    for wr in write_reqs:
        cost = wr.buffer_stager.get_staging_cost_bytes()
        # big HOST members skip the slab: their pack is a pure extra
        # memcpy with nothing left to amortize.  Device members stay
        # eligible at any size — the device pack collapses N transfers
        # into one.
        fits = 0 < cost < threshold and (
            cost < host_member_max
            or isinstance(wr.buffer_stager, JaxArrayBufferStager)
        )
        if wr.path in targets and fits:
            small.append((wr, cost))
        else:
            rest.append(wr)
    if len(small) < 2:
        return entries, write_reqs

    # Device members and host/object members slab SEPARATELY: a single
    # host member in a slab would make _all_jax false and forfeit the
    # device pack (one D2H transfer per slab), and symmetrically poison
    # the read-side device unpack for every array in the merged run.
    # Device members further split by element width, so every member
    # joins its slab by a same-width bitcast (ops/device_pack.py).
    from .ops.device_pack import packed_width

    small.sort(key=lambda x: x[0].path)  # deterministic slab layout
    by_width: Dict[int, List[Tuple[WriteReq, int]]] = {}  # 0 = host
    for wr, c in small:
        st = wr.buffer_stager
        width = (
            packed_width(st.arr.dtype)
            if isinstance(st, JaxArrayBufferStager)
            else 0
        )
        by_width.setdefault(width, []).append((wr, c))
    # device groups widest first, host members last
    groups = [by_width[w] for w in sorted(by_width, reverse=True)]
    slabs: List[List[Tuple[WriteReq, int]]] = []
    new_reqs = list(rest)
    for group in groups:
        if len(group) < 2:
            # a lone member gains nothing from a one-member slab; keep
            # its original object
            new_reqs.extend(wr for wr, _ in group)
            continue
        cur: List[Tuple[WriteReq, int]] = []
        cur_bytes = 0
        for wr, cost in group:
            cur.append((wr, cost))
            cur_bytes += cost
            if cur_bytes >= threshold:
                slabs.append(cur)
                cur, cur_bytes = [], 0
        if cur:
            slabs.append(cur)

    for i, slab in enumerate(slabs):
        slab_location = f"{rank}/batched.{i}"
        offset = 0
        stagers: List[Tuple[BufferStager, int]] = []
        sinks = []
        for wr, cost in slab:
            record = targets[wr.path]
            record.location = slab_location
            record.byte_range = [offset, offset + cost]
            stagers.append((wr.buffer_stager, cost))
            # re-range the member's checksum sinks into slab coordinates
            # so each entry's crc still covers exactly its own payload
            for sink, rng in wr.checksum_sinks or ():
                lo = offset + (rng[0] if rng else 0)
                hi = offset + (rng[1] if rng else cost)
                sinks.append((sink, (lo, hi)))
            offset += cost
        new_reqs.append(
            WriteReq(
                path=slab_location,
                buffer_stager=BatchedBufferStager(stagers, offset),
                checksum_sinks=sinks or None,
            )
        )
    if len(new_reqs) == len(write_reqs):
        # nothing actually coalesced (e.g. one device + one host small
        # member): keep the originals untouched
        return entries, write_reqs
    return entries, new_reqs


class _MergedRangeConsumer(BufferConsumer):
    """Feed one spanning read into the original ranged consumers
    (reference BatchedBufferConsumer, batcher.py:358-386)."""

    def __init__(self, base: int, subs: List[Tuple[ReadReq, int, int]]):
        self.base = base
        self.subs = subs

    async def consume_buffer(
        self, buf: Any, executor: Optional[Executor] = None
    ) -> None:
        from .io_types import check_read_crc

        view = memoryview(buf).cast("B")
        verify = knobs.verify_on_restore()
        if verify:
            for req, start, end in self.subs:
                piece = view[start - self.base : end - self.base]
                if req.expected_crc32 is None:
                    continue
                # the merged spanning read bypassed the scheduler's
                # whole-request check; each member still verifies its
                # own slice (off-loop: tens of MB per member would
                # stall every concurrent read pipeline)
                if executor is not None:
                    await obs.run_in_executor(
                        executor, check_read_crc, req, piece,
                        name="consume/crc", nbytes=end - start,
                    )
                else:
                    check_read_crc(req, piece)
        # eligibility first (pure isinstance checks, no jax import), THEN
        # the knob (whose "auto" may import jax); the unpack itself runs
        # on the executor — first-restore XLA compilation would stall
        # every concurrent read pipeline if it ran on the loop thread
        if self._device_unpack_eligible() and knobs.device_unpack_enabled():
            if executor is not None:
                done = await obs.run_in_executor(
                    executor, self._try_device_unpack, view,
                    name="consume/unpack", nbytes=view.nbytes,
                )
            else:
                done = self._try_device_unpack(view)
            if done:
                return
        obs.counter(obs.SLAB_HOST_UNPACK_BYTES).inc(view.nbytes)
        for req, start, end in self.subs:
            piece = view[start - self.base : end - self.base]
            await req.buffer_consumer.consume_buffer(piece, executor)

    def _device_unpack_eligible(self) -> bool:
        from .preparers.array import ArrayBufferConsumer, _is_jax_array

        return bool(self.subs) and all(
            isinstance(req.buffer_consumer, ArrayBufferConsumer)
            and req.buffer_consumer.obj_out is not None
            # module-name check, no jax import: numpy/torch templates
            # skip the executor dispatch entirely
            and _is_jax_array(req.buffer_consumer.obj_out)
            for req, _, _ in self.subs
        )

    def _try_device_unpack(self, view: memoryview) -> bool:
        """Restore every member with ONE H2D transfer + one compiled
        slice/bitcast program when all members are plain array reads
        into single-device jax templates on the same device (the
        read-side mirror of the device slab pack).  Any ineligibility
        or failure returns False and the host path runs instead."""
        from .preparers.array import ArrayBufferConsumer, _is_jax_array
        from .serialization import BUFFER_PROTOCOL, string_to_dtype

        from .ops.device_pack import slab_word_bytes, unpack_slab_to_device

        members = []
        out_dtypes = []
        consumers = []
        device = None
        try:
            for req, start, end in self.subs:
                c = req.buffer_consumer
                if not isinstance(c, ArrayBufferConsumer):
                    return False
                if c.entry.serializer != BUFFER_PROTOCOL:
                    return False
                out = c.obj_out
                if out is None or not _is_jax_array(out):
                    return False
                devs = list(out.sharding.device_set)
                if len(devs) != 1:
                    return False
                # pinned_host templates must stay in host memory: the
                # unpack commits to default device memory, which would
                # silently defeat an offload (the host path preserves
                # the template's full sharding incl. memory kind)
                if getattr(out.sharding, "memory_kind", None) not in (
                    None, "device",
                ):
                    return False
                if device is None:
                    device = devs[0]
                elif devs[0] != device:
                    return False
                if tuple(out.shape) != tuple(c.entry.shape):
                    return False
                members.append(
                    (
                        start - self.base,
                        str(np.dtype(string_to_dtype(c.entry.dtype))),
                        tuple(c.entry.shape),
                    )
                )
                out_dtypes.append(np.dtype(out.dtype))
                consumers.append(c)
            # mixed element widths or unaligned members (older layouts),
            # or 8-byte elements the device would narrow: the host path
            # BY CHOICE, not a counted failure
            if not consumers or slab_word_bytes(members) is None:
                return False
            arrays = unpack_slab_to_device(
                view, tuple(members), tuple(out_dtypes), device
            )
        except Exception as e:  # noqa: BLE001 — host path is always correct
            logger.warning(
                "device slab unpack failed; host fallback", exc_info=True
            )
            obs.swallowed_exception("batcher.device_unpack", e)
            return False
        from .preparers.array import donate_template

        for c, arr in zip(consumers, arrays):
            c.fut.set(arr)
            # strictly after fut.set: donated ⟹ replacement reachable
            donate_template(c.obj_out)
        return True

    def get_consuming_cost_bytes(self) -> int:
        # the spanning buffer is what actually occupies host memory
        span = max(e for _, _, e in self.subs) - self.base
        return max(
            span,
            sum(
                req.buffer_consumer.get_consuming_cost_bytes()
                for req, _, _ in self.subs
            ),
        )


def batch_read_requests(read_reqs: List[ReadReq]) -> List[ReadReq]:
    """Merge ranged reads of the same location into one spanning read
    (reference batch_read_requests, batcher.py:387-478)."""
    by_path: Dict[str, List[ReadReq]] = {}
    out: List[ReadReq] = []
    for rr in read_reqs:
        if rr.byte_range is not None:
            by_path.setdefault(rr.path, []).append(rr)
        else:
            out.append(rr)
    max_gap = 1 << 20  # don't span holes larger than 1MB between ranges
    for path, reqs in by_path.items():
        if len(reqs) == 1:
            out.append(reqs[0])
            continue
        reqs.sort(key=lambda r: r.byte_range[0])
        run: List[ReadReq] = []
        run_hi = 0  # rolling max end of the current run: the gap test
        # must be O(1) per request, not a scan of the run (20k ranged
        # reads to one slab would otherwise cost O(n^2) — measured 50s
        # of a 54s restore for 20k tiny leaves)

        def flush() -> None:
            if not run:
                return
            if len(run) == 1:
                out.append(run[0])
            else:
                lo = run[0].byte_range[0]
                hi = max(r.byte_range[1] for r in run)
                subs = [(r, r.byte_range[0], r.byte_range[1]) for r in run]
                out.append(
                    ReadReq(
                        path=path,
                        byte_range=[lo, hi],
                        buffer_consumer=_MergedRangeConsumer(lo, subs),
                        # a merged read executes as early as its most
                        # urgent member asks (restore prioritization)
                        priority=min(r.priority for r in run),
                    )
                )
            run.clear()

        for r in reqs:
            if run and r.byte_range[0] - run_hi > max_gap:
                flush()
            run_hi = (
                r.byte_range[1] if not run else max(run_hi, r.byte_range[1])
            )
            run.append(r)
        flush()
    return out
