"""Sharded-array preparer: multi-device ``jax.Array`` save/restore with
collective-free write partitioning and overlap-based resharding reads.

This single path subsumes three reference components — ShardedTensor
(io_preparers/sharded_tensor.py:129-333), DTensor (io_preparers/
dtensor.py:123-278), and the replicated-write partitioner's common case
(partitioner.py:67-213) — because on TPU the sharding layout is *global
knowledge*: every process holds the same ``Sharding.devices_indices_map``,
so dedup of replicated shards and write load-balancing are pure functions
computed identically everywhere, with zero collectives.  (The reference
must all_gather entry metadata and have rank 0 broadcast a partition,
partitioner.py:170-192 — that entire control-plane round trip disappears.)

Write: unique shard boxes are balanced greedily (largest-first) across the
processes that can address them; boxes larger than the max-shard-size knob
are subdivided along their largest dim (reference sharded_tensor.py:48-78).

Read: the restore template's shard boxes are intersected with the saved
boxes (overlap algebra in overlap.py); each overlapping saved shard is read
once and scattered into every overlapping local region (reference
sharded_tensor.py:197-298).  When the overlap is a dim-0 slab of the saved
blob, only that byte range is fetched.  The assembled per-device buffers
become the restored array via ``jax.make_array_from_single_device_arrays``
— resharding across world sizes/meshes (elasticity) is this same code path
with a different template sharding.

A leaf whose every local box lies whole inside ONE read piece takes no
host assembly buffer at all (``_DirectLeaf``): the consume worker hands
the piece's bytes, as they lie in the mapped file, to ``jax.device_put``
ONCE, for one of the devices that hold a box of it; where a box is not a
contiguous range of the piece (a column range) it is cut out on that
device (``ops.device_pack.cut_box_on_device``), and a box that another
device holds goes there device to device.  Same plan, same reads, same
countdown, same assemble step: only where a piece's bytes go differs.
"""

from __future__ import annotations

import functools
import logging
import threading
from concurrent.futures import Executor
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from .. import knobs, obs
from ..io_types import BufferConsumer, BufferStager, Future, ReadReq, WriteReq
from ..manifest import Shard, ShardedArrayEntry
from ..serialization import (
    array_from_buffer,
    fast_copyto,
    serialized_size_bytes,
    string_to_dtype,
)
from .array import (
    JaxArrayBufferStager,
    array_dtype_str,
    donate_template,
    materialize_into_template,
    _Countdown,
    _TileCrcFold,
    _is_jax_array,
    _plan_flat_tiles,
)
from .overlap import (
    Box,
    box_intersect,
    box_nelems,
    index_to_box,
    is_dim0_slab,
    make_box,
    relative_slices,
)

logger = logging.getLogger(__name__)


def is_multi_device_jax_array(obj: Any) -> bool:
    if not _is_jax_array(obj):
        return False
    return len(obj.sharding.device_set) > 1


def _location_for_box(logical_path: str, box: Box) -> str:
    off = "_".join(str(o) for o in box[0])
    sz = "_".join(str(s) for s in box[1])
    return f"sharded/{logical_path}.{off}.{sz}" if off else f"sharded/{logical_path}.scalar"


def _sharding_metadata(sharding: Any) -> Tuple[Optional[List[str]], Optional[List[int]], Optional[List[Any]]]:
    """Extract (mesh_axis_names, mesh_shape, spec) from a NamedSharding for
    the manifest (advisory; analogue of DTensorEntry's mesh+dim_map,
    reference manifest.py:211-261)."""
    from jax.sharding import NamedSharding

    if not isinstance(sharding, NamedSharding):
        return None, None, None
    mesh = sharding.mesh
    axis_names = [str(a) for a in mesh.axis_names]
    mesh_shape = [int(s) for s in mesh.devices.shape]
    spec: List[Any] = []
    for elem in sharding.spec:
        if elem is None:
            spec.append(None)
        elif isinstance(elem, (tuple, list)):
            spec.append([str(e) for e in elem])
        else:
            spec.append(str(elem))
    return axis_names, mesh_shape, spec


def _unique_boxes(sharding: Any, shape: Tuple[int, ...]) -> Dict[Box, List[Any]]:
    """Map each unique shard box to the devices holding it (replicas)."""
    boxes: Dict[Box, List[Any]] = {}
    for dev, idx in sharding.devices_indices_map(tuple(shape)).items():
        box = index_to_box(idx, shape)
        boxes.setdefault(box, []).append(dev)
    return boxes


def _subdivide(box: Box, itemsize: int, max_bytes: int) -> List[Box]:
    """Split a box along its largest dim until every piece ≤ max_bytes
    (reference sharded_tensor.py:48-78; dtensor.py:63-98 picks the largest
    sharded dim — largest dim is the natural generalization)."""
    nbytes = box_nelems(box) * itemsize
    if nbytes <= max_bytes or not box[1]:
        return [box]
    dim = max(range(len(box[1])), key=lambda d: box[1][d])
    if box[1][dim] <= 1:
        return [box]
    rows = box[1][dim]
    row_bytes = nbytes // rows
    rows_per = max(1, max_bytes // max(1, row_bytes))
    out: List[Box] = []
    for r in range(0, rows, rows_per):
        n = min(rows_per, rows - r)
        offsets = list(box[0])
        sizes = list(box[1])
        offsets[dim] += r
        sizes[dim] = n
        out.extend(_subdivide(make_box(offsets, sizes), itemsize, max_bytes))
    return out


def assign_box_writers(
    boxes: Dict[Box, List[Any]],
    itemsize: int,
    process_count: int,
    preloads: Optional[List[int]] = None,
    topology: Optional[Any] = None,
) -> Dict[Box, int]:
    """Deterministic greedy balance: every process computes the identical
    assignment from the (global) sharding metadata. Largest box first, to
    the least-loaded candidate process (reference partitioner.py:140-213,
    minus the gather+broadcast).

    ``preloads``: per-process byte loads already committed elsewhere —
    per-rank host-state bytes and earlier sharded leaves' assignments
    (reference partitioner.py:266-270 counts non-replicated bytes as
    pre-load).  MUTATED IN PLACE so one vector composes across every
    sharded leaf of a take; callers must pass an identical vector on
    every controller (it feeds a collective-free assignment).

    ``topology``: optional ``topology.Topology`` (identical on every
    controller) — a box whose replica group spans several slices elects
    its writer by least-loaded slice → host → rank, so sharded-replica
    writes spread across slices like replicated host state does
    (partitioner.partition_replicated_writes).  The flat behavior is
    unchanged when omitted or non-explicit."""
    loads = preloads if preloads is not None else [0] * max(1, process_count)
    assignment: Dict[Box, int] = {}
    if topology is not None and getattr(topology, "explicit", False):
        from ..partitioner import _topology_chooser

        choose_key, charge = _topology_chooser(topology, loads)
    else:
        def choose_key(p: int):
            return (loads[p], p)

        def charge(p: int, nbytes: int) -> None:
            loads[p] += nbytes

    ordered = sorted(
        boxes.keys(), key=lambda b: (-box_nelems(b), b[0])
    )
    for box in ordered:
        candidates = sorted({d.process_index for d in boxes[box]})
        writer = min(candidates, key=choose_key)
        assignment[box] = writer
        charge(writer, box_nelems(box) * itemsize)
    return assignment


class ShardedArrayIOPreparer:
    @staticmethod
    def prepare_write(
        obj: Any,
        logical_path: str,
        process_index: int,
        process_count: int,
        writer_loads: Optional[List[int]] = None,
        topology: Optional[Any] = None,
    ) -> Tuple[ShardedArrayEntry, List[WriteReq]]:
        shape = tuple(int(s) for s in obj.shape)
        itemsize = np.dtype(obj.dtype).itemsize
        boxes = _unique_boxes(obj.sharding, shape)
        assignment = assign_box_writers(
            boxes, itemsize, process_count, preloads=writer_loads,
            topology=topology,
        )

        # device -> local shard data for this process
        local_data: Dict[Any, Any] = {
            s.device: s.data for s in obj.addressable_shards
        }

        axis_names, mesh_shape, spec = _sharding_metadata(obj.sharding)
        shards: List[Shard] = []
        write_reqs: List[WriteReq] = []
        max_shard_bytes = knobs.get_max_shard_size_bytes()
        for box, devices in boxes.items():
            if assignment[box] != process_index:
                continue
            device = next(d for d in devices if d.process_index == process_index)
            data = local_data[device]
            for sub in _subdivide(box, itemsize, max_shard_bytes):
                location = _location_for_box(logical_path, sub)
                shards.append(
                    Shard(
                        offsets=list(sub[0]),
                        sizes=list(sub[1]),
                        location=location,
                    )
                )
                index = relative_slices(sub, box)
                shard_stager = JaxArrayBufferStager(
                    data,
                    index=index if sub != box else None,
                    nbytes=box_nelems(sub) * itemsize,
                )
                # codec preconditioning hint (see preparers/array.py)
                from ..codec import filter_for_dtype

                shard_stager.codec_filter_stride = filter_for_dtype(
                    array_dtype_str(obj)
                )
                write_reqs.append(
                    WriteReq(
                        path=location,
                        buffer_stager=shard_stager,
                        checksum_sinks=[
                            (
                                lambda c, s=shards[-1]: setattr(
                                    s, "crc32", c
                                ),
                                None,
                            )
                        ],
                    )
                )
        entry = ShardedArrayEntry(
            dtype=array_dtype_str(obj),
            shape=list(shape),
            shards=shards,
            mesh_axis_names=axis_names,
            mesh_shape=mesh_shape,
            spec=spec,
        )
        return entry, write_reqs

    @staticmethod
    def prepare_read(
        entry: ShardedArrayEntry,
        obj_out: Any = None,
        buffer_size_limit_bytes: Optional[int] = None,
    ) -> Tuple[List[ReadReq], Future]:
        # reshard/plan: the overlap algebra of one leaf and the allocation
        # of its assembly buffers, on the caller's thread
        with obs.span("reshard/plan") as sp:
            fut: Future = Future()
            shape = tuple(entry.shape)
            dtype = string_to_dtype(entry.dtype)
            itemsize = dtype.itemsize

            # Dedup saved shards by box (replicas may appear in merged manifests).
            saved: Dict[Box, Shard] = {}
            for s in entry.shards:
                saved.setdefault(make_box(s.offsets, s.sizes), s)

            sharded_template = obj_out is not None and is_multi_device_jax_array(
                obj_out
            )
            if sharded_template:
                sharding = obj_out.sharding
                local_boxes: Dict[Box, List[Any]] = {}
                idx_map = sharding.devices_indices_map(tuple(obj_out.shape))
                for dev in sharding.addressable_devices:
                    box = index_to_box(idx_map[dev], obj_out.shape)
                    local_boxes.setdefault(box, []).append(dev)
                target_dtype = np.dtype(obj_out.dtype)
            else:
                # No sharded template: materialize the full array, then hand it
                # to the template logic (numpy in-place / device_put / fresh).
                local_boxes = {make_box((0,) * len(shape), shape): [None]}
                target_dtype = dtype

            box_bytes = sum(box_nelems(b) for b in local_boxes) * itemsize
            target_shards = sum(len(devs) for devs in local_boxes.values())

            # one fetch a saved box that overlaps a local one
            fetches: List[_Fetch] = []
            for sbox, shard in saved.items():
                overlaps = []
                for lbox in local_boxes:
                    inter = box_intersect(sbox, lbox)
                    if inter is not None:
                        overlaps.append((inter, lbox))
                if overlaps:
                    fetches.append(_plan_fetch(shard, sbox, overlaps, itemsize))

            # filled by the host path only (at once, or when the direct
            # path of this leaf falls back)
            buffers: Dict[Box, np.ndarray] = {}
            direct: Optional[_DirectLeaf] = None
            if sharded_template and _direct_applies(
                obj_out, shape, dtype, local_boxes, fetches,
                buffer_size_limit_bytes,
            ):
                direct = _DirectLeaf(local_boxes, dtype, buffers)
            else:
                _make_buffers(buffers, local_boxes, dtype)
            if sp is not None:
                sp.attrs.update(
                    saved_shards=len(fetches), local_boxes=len(local_boxes)
                )

            def assemble() -> None:
                # reshard/assemble: the filled assembly buffers, or the
                # boxes already on their devices, become the restored leaf,
                # on the thread that counted the last shard in (the read
                # loop's)
                with obs.span(
                    "reshard/assemble", devices=target_shards, bytes=box_bytes
                ):
                    if direct is not None and not direct.fell_back:
                        _assemble_direct()
                    else:
                        _assemble()

            def _assemble_direct() -> None:
                import jax

                out = jax.make_array_from_single_device_arrays(
                    tuple(obj_out.shape),
                    obj_out.sharding,
                    [
                        direct.placed[dev]
                        for devs in local_boxes.values()
                        for dev in devs
                    ],
                )
                obs.counter(obs.RESHARD_DIRECT_BYTES).inc(box_bytes)
                # fut.set BEFORE donation, as below
                fut.set(out)
                donate_template(obj_out)

            def _assemble() -> None:
                if sharded_template:
                    import jax

                    if target_dtype != dtype:
                        for box in list(buffers):
                            buffers[box] = buffers[box].astype(target_dtype)
                    full_box = make_box(
                        (0,) * len(obj_out.shape), tuple(obj_out.shape)
                    )
                    if set(local_boxes) == {full_box}:
                        # fully-replicated template: one broadcasting device_put
                        with obs.span(
                            "h2d/put", bytes=buffers[full_box].nbytes,
                            link="h2d",
                        ):
                            out = jax.device_put(buffers[full_box], sharding)
                        # fut.set BEFORE donation: a donated template must
                        # always imply a replacement reachable through the
                        # Future (1x-restore; see donate_template)
                        fut.set(out)
                        donate_template(obj_out)
                        return
                    arrays = []
                    for box, devs in local_boxes.items():
                        for dev in devs:
                            with obs.span(
                                "h2d/put",
                                bytes=buffers[box].nbytes,
                                device=dev.id,
                                link="h2d",
                            ):
                                arrays.append(
                                    jax.device_put(buffers[box], dev)
                                )
                    out = jax.make_array_from_single_device_arrays(
                        tuple(obj_out.shape), sharding, arrays
                    )
                    fut.set(out)
                    donate_template(obj_out)
                else:
                    (buf,) = buffers.values()
                    result = materialize_into_template(buf, obj_out)
                    fut.set(result)
                    if result is not obj_out:
                        donate_template(obj_out)

            if not fetches:  # degenerate: nothing to read (e.g. zero-size array)
                assemble()
                return [], fut

            countdown = _Countdown(n=len(fetches), on_zero=assemble)
            read_reqs: List[ReadReq] = []
            for fetch in fetches:
                read_reqs.extend(
                    _emit_shard_reads(
                        fetch,
                        entry.dtype,
                        itemsize,
                        buffers,
                        countdown,
                        buffer_size_limit_bytes,
                        direct,
                    )
                )
            if sp is not None:
                sp.attrs["read_reqs"] = len(read_reqs)
            return read_reqs, fut


class _Fetch(NamedTuple):
    """One saved shard's read: the box fetched (the shard's, or the row
    range of it that covers every overlap), where it lies in the stored
    object, the checksum that applies to exactly those bytes, and the
    (overlap, local box) pairs it feeds."""

    location: str
    read_box: Box
    byte_range: Optional[List[int]]
    expected_crc: Optional[int]
    overlaps: List[Tuple[Box, Box]]


def _plan_fetch(
    shard: Shard, sbox: Box, overlaps: List[Tuple[Box, Box]], itemsize: int
) -> _Fetch:
    # Minimal fetch: if every overlap is a dim-0 slab of the saved
    # blob, fetch just the covering row range.
    if all(is_dim0_slab(ov, sbox) for ov, _ in overlaps) and sbox[1]:
        r0 = min(ov[0][0] for ov, _ in overlaps) - sbox[0][0]
        r1 = max(ov[0][0] + ov[1][0] for ov, _ in overlaps) - sbox[0][0]
        row_bytes = (box_nelems(sbox) // max(1, sbox[1][0])) * itemsize
        base = shard.byte_range[0] if shard.byte_range else 0
        byte_range: Optional[List[int]] = [
            base + r0 * row_bytes,
            base + r1 * row_bytes,
        ]
        read_offsets = list(sbox[0])
        read_offsets[0] += r0
        read_sizes = list(sbox[1])
        read_sizes[0] = r1 - r0
        read_box = make_box(read_offsets, read_sizes)
        # only where the covering row range IS the whole shard payload
        # does its recorded checksum apply
        whole = r0 == 0 and r1 == sbox[1][0]
        expected_crc = shard.crc32 if whole else None
    else:
        byte_range = list(shard.byte_range) if shard.byte_range else None
        read_box = sbox
        # this branch reads the WHOLE shard payload: its recorded
        # checksum applies (partial row-range reads above don't)
        expected_crc = shard.crc32
    return _Fetch(shard.location, read_box, byte_range, expected_crc, overlaps)


def _make_buffers(
    buffers: Dict[Box, np.ndarray], local_boxes: Dict[Box, List[Any]], dtype
) -> None:
    """One host assembly buffer a unique local box, counted as made."""
    for box in local_boxes:
        buffers[box] = np.empty(box[1], dtype=dtype)
    obs.counter(obs.RESHARD_HOST_ALLOC_BYTES).inc(
        sum(b.nbytes for b in buffers.values())
    )


def _is_tiled(read_box: Box, itemsize: int, budget: Optional[int]) -> bool:
    """Whether a fetch is split into dim-0 row-range tiles: over budget,
    and more than one row to split."""
    rows = read_box[1][0] if read_box[1] else 0
    return (
        budget is not None
        and box_nelems(read_box) * itemsize > budget
        and rows > 1
    )


def _direct_applies(
    obj_out: Any,
    shape: Tuple[int, ...],
    dtype: np.dtype,
    local_boxes: Dict[Box, List[Any]],
    fetches: List["_Fetch"],
    budget: Optional[int],
) -> bool:
    """Whether a leaf with a multi-device ``jax.Array`` template is
    restored without host assembly buffers, by what the plan and the
    template show (all or nothing a leaf):

    - the template has the saved shape and dtype and lies in device
      memory (a cast, ``pinned_host``: the host path; so is a numpy, a
      single-device or an absent template, which never comes here);
    - every local box lies whole inside one fetch, and no fetch is tiled
      (a tile is a row range; a column box never lies in one).  A local
      box gathered from several saved shards runs the host path;
    - ``knobs.device_unpack_enabled()``: the switch that already means
      "carve restored bytes on the device, not on the host" (auto: off
      on cpu, where a device is host memory).  Read last: auto imports
      jax."""
    if tuple(obj_out.shape) != shape or np.dtype(obj_out.dtype) != dtype:
        return False
    if getattr(obj_out.sharding, "memory_kind", None) not in (None, "device"):
        return False
    pairs = [pair for fetch in fetches for pair in fetch.overlaps]
    if any(inter != lbox for inter, lbox in pairs) or sorted(
        lbox for _, lbox in pairs
    ) != sorted(local_boxes):
        return False
    if any(
        _is_tiled(fetch.read_box, dtype.itemsize, budget) for fetch in fetches
    ):
        return False
    return knobs.device_unpack_enabled()


@functools.lru_cache(maxsize=1)
def _libc():
    import ctypes

    return ctypes.CDLL(None, use_errno=True)


def _populate(src: np.ndarray) -> None:
    """Ask the kernel for the page-table entries of a MAPPED read piece in
    one call, before a transfer or a copy touches its pages one by one.

    ``mlock`` then ``munlock``: the populate every POSIX system has (the
    pages stay mapped, nothing stays locked; ``MADV_POPULATE_READ`` is
    Linux 5.14's name for it and gVisor has none).  On a sandboxed host a
    first touch of a tmpfs mapping is one trap a 4 KiB page whoever's
    thread takes it (0.5-1.4 GB/s over all threads, PERF.md section 5),
    and it was the whole of a resharding restore's time there; populated in
    one call the same pages are there at 5 GB/s a thread.  A refusal
    (``RLIMIT_MEMLOCK``, no libc) leaves the pages to their first touches,
    as before, and counts in ``reshard.populate_refused``: a host where the
    populate never engages says so.  A piece on the heap was touched by the
    read that made it."""
    from ..io_types import is_mmap_backed

    if not src.nbytes or not is_mmap_backed(src):
        return
    import ctypes
    import mmap

    with obs.span("reshard/populate", bytes=src.nbytes) as sp:
        lo = src.ctypes.data - src.ctypes.data % mmap.PAGESIZE
        span = ctypes.c_void_p(lo), ctypes.c_size_t(
            src.ctypes.data + src.nbytes - lo
        )
        try:
            libc = _libc()
            refused = libc.mlock(*span) != 0
            if not refused:
                libc.munlock(*span)
        except (AttributeError, OSError):  # no libc, or none with mlock
            refused = True
        if refused:
            obs.counter(obs.RESHARD_POPULATE_REFUSED).inc()
            if sp is not None:
                sp.attrs["refused"] = True


class _LinkTally:
    """Bytes each device has taken over its host link on the direct path,
    in this process: a read piece that several devices share goes to the
    one with the fewest so far (ties to the lowest id), so the links stay
    as even as they are when every device is sent its own copy.  Charged
    when the receiver is chosen, so pieces in flight on other workers
    count.  (Process-wide, not a restore's own: ``prepare_read`` plans a
    leaf at a time and knows no restore; a restore starts from where the
    last one left the links, which is even.)"""

    def __init__(self) -> None:
        self._bytes: Dict[int, int] = {}  # device id -> bytes
        self._lock = threading.Lock()

    def charge_least(self, devs: List[Any], nbytes: int) -> Any:
        with self._lock:
            dev = min(devs, key=lambda d: (self._bytes.get(d.id, 0), d.id))
            self._bytes[dev.id] = self._bytes.get(dev.id, 0) + nbytes
        return dev

    def snapshot(self) -> Dict[int, int]:
        with self._lock:
            return dict(self._bytes)


_LINK_TALLY = _LinkTally()


class _DirectLeaf:
    """A leaf on the direct path: its boxes as they land on their devices,
    and the way back to the host path.

    A read piece crosses the host link ONCE.  The bytes of it that are
    sent (a row range that is a box, as it lies; the piece whole for its
    column boxes) go to one of the devices that want them: the one
    ``_LINK_TALLY`` has charged the fewest host-link bytes, ties to the
    lowest id.  Every box is cut out of that buffer there, and a box that
    belongs to another device (the sibling that shares a column piece, a
    replica of the box under the template's sharding) is moved device to
    device (``jax.device_put`` of a device array: a ``d2d/put`` span).
    At its peak a receiver holds, a worker that serves it: the wide
    buffer, its own box, and its siblings' boxes until their copies have
    landed; a sibling holds only its box.

    Counted: ``reshard.link_bytes`` (bytes put from host memory) and
    ``reshard.handoff_bytes`` (bytes moved device to device); the
    ``reshard/direct`` span carries ``bytes`` (over the host link),
    ``handoff_bytes``, ``devices`` (boxes delivered) and ``cut``.

    The first exception on any piece of the leaf (a put, a cut, a
    hand-off) sends the WHOLE leaf down the host path, once: the assembly
    buffers are made then, boxes already on a device are read back into
    them, and every later piece scatters on the host.  Counted once in
    ``exceptions.swallowed``."""

    def __init__(
        self,
        local_boxes: Dict[Box, List[Any]],
        dtype: np.dtype,
        buffers: Dict[Box, np.ndarray],
    ) -> None:
        self.local_boxes = local_boxes
        self.dtype = dtype
        self.buffers = buffers  # the leaf's own dict: empty while direct
        self.placed: Dict[Any, Any] = {}  # device -> its box, on it
        self.fell_back = False
        self._lock = threading.Lock()  # pieces land on several workers

    def place(
        self, src: np.ndarray, read_box: Box, overlaps: List[Tuple[Box, Box]]
    ) -> bool:
        """Put one read piece's boxes on their devices.  False: the leaf
        is on the host path, and the caller scatters the piece."""
        if self.fell_back:
            return False
        try:
            placed = self._put_and_cut(src, read_box, overlaps)
        except Exception as e:  # noqa: BLE001 — host path is always correct
            with self._lock:
                first = not self.fell_back
                if first:
                    self._fall_back()
            if first:
                logger.warning(
                    "direct resharding restore failed; host fallback",
                    exc_info=True,
                )
                obs.swallowed_exception("sharded.direct_restore", e)
            return False
        with self._lock:
            if self.fell_back:  # another piece failed meanwhile
                return False
            self.placed.update(placed)
        return True

    def _put_and_cut(
        self, src: np.ndarray, read_box: Box, overlaps: List[Tuple[Box, Box]]
    ) -> Dict[Any, Any]:
        import jax

        from ..ops.device_pack import cut_box_on_device

        # (bytes to send, [(where a box starts in them or None, its sizes,
        # the devices that hold it)]): a row range of the piece is
        # contiguous bytes of the mapping, exactly one box; the column
        # boxes all come out of the piece whole
        sends = []
        columns = []
        for inter, lbox in overlaps:
            devs = self.local_boxes[lbox]
            if is_dim0_slab(inter, read_box):
                rows = relative_slices(inter, read_box)[:1]
                sends.append((src[rows] if rows else src, [(None, None, devs)]))
            else:
                start = tuple(i - r for i, r in zip(inter[0], read_box[0]))
                columns.append((start, inter[1], devs))
        if columns:
            sends.append((src, columns))
        with obs.span(
            "reshard/direct",
            bytes=sum(view.nbytes for view, _ in sends),
            devices=sum(len(devs) for _, boxes in sends for _, _, devs in boxes),
            cut=bool(columns),
            link="h2d",
        ) as sp:
            placed: Dict[Any, Any] = {}
            spent = []  # the wide buffers, and boxes cut for a sibling
            handoff_bytes = 0
            for view, boxes in sends:
                receiver = _LINK_TALLY.charge_least(
                    [dev for _, _, devs in boxes for dev in devs], view.nbytes
                )
                with obs.span(
                    "h2d/put", bytes=view.nbytes, device=receiver.id,
                    link="h2d",
                ):
                    arr = jax.device_put(view, receiver)
                obs.counter(obs.RESHARD_LINK_BYTES).inc(view.nbytes)
                if boxes is columns:  # the piece whole: a wide buffer
                    spent.append(arr)
                for start, sizes, devs in boxes:
                    box = (
                        arr
                        if start is None
                        else cut_box_on_device(arr, start, sizes)
                    )
                    if receiver not in devs:
                        spent.append(box)
                    for dev in devs:
                        if dev == receiver:
                            placed[dev] = box
                            continue
                        with obs.span(
                            "d2d/put",
                            bytes=box.nbytes,
                            src=receiver.id,
                            dst=dev.id,
                            link="d2d",
                        ):
                            placed[dev] = jax.device_put(box, dev)
                        handoff_bytes += box.nbytes
            obs.counter(obs.RESHARD_HANDOFF_BYTES).inc(handoff_bytes)
            if sp is not None:
                sp.attrs["handoff_bytes"] = handoff_bytes
            # the worker waits for its piece before it takes the next: at
            # most one piece's wide buffer and hand-offs a worker are on
            # the devices
            jax.block_until_ready(list(placed.values()))
            for arr in spent:
                arr.delete()
        return placed

    def _fall_back(self) -> None:
        _make_buffers(self.buffers, self.local_boxes, self.dtype)
        for box, devs in self.local_boxes.items():
            landed = next(
                (self.placed[d] for d in devs if d in self.placed), None
            )
            if landed is not None:
                fast_copyto(self.buffers[box], np.asarray(landed))
        for arr in self.placed.values():
            arr.delete()
        self.placed.clear()
        # last: a piece that reads it true finds the buffers made
        self.fell_back = True


def _emit_shard_reads(
    fetch: _Fetch,
    dtype: str,
    itemsize: int,
    buffers: Dict[Box, np.ndarray],
    outer: _Countdown,
    budget: Optional[int],
    direct: Optional["_DirectLeaf"] = None,
) -> List[ReadReq]:
    """Emit the read(s) for one saved-shard fetch, splitting an
    over-budget fetch into dim-0 row-range tiles.

    ``read_box`` is always a dim-0 row range of the saved shard (the
    whole box, or the covering row range of the dim-0-slab fast path),
    and shards are stored C-order — so consecutive rows are consecutive
    payload bytes, and a row range is an exact byte range.  That makes
    budgeted tiling a pure re-slicing of the fetch: each tile scatters
    into the same local buffers through the overlap algebra, and peak
    transient host memory per request is O(budget) instead of O(shard)
    (the reference's budget stops at per-shard granularity,
    io_preparers/tensor.py:128-181 applies only to dense tensors; this
    extends the same contract to sharded entries).

    Tiling must not weaken integrity: when the fetch covers the whole
    shard payload (``expected_crc`` set), per-tile crc32s fold in offset
    order back to the recorded whole-payload value (``_TileCrcFold``,
    same VERIFY_ON_RESTORE gate as unbudgeted reads).  A single row
    larger than the budget reads row-at-a-time (the floor; element-level
    splits would tear rows across scatter boxes)."""
    location, read_box, byte_range, expected_crc, overlaps = fetch
    if not _is_tiled(read_box, itemsize, budget):
        return [
            ReadReq(
                path=location,
                byte_range=byte_range,
                buffer_consumer=_ShardConsumer(
                    read_box=read_box,
                    dtype=dtype,
                    overlaps=overlaps,
                    buffers=buffers,
                    countdown=outer,
                    direct=direct,
                ),
                expected_crc32=expected_crc,
            )
        ]

    # one "element" per dim-0 row: the shared tile math splits the row
    # range exactly as it splits flat element ranges elsewhere
    rows = read_box[1][0]
    row_bytes = box_nelems(read_box) * itemsize // rows
    base = byte_range[0] if byte_range else 0
    tiles = _plan_flat_tiles(0, rows, row_bytes, budget, base_byte=base)
    fold = _TileCrcFold(
        expected_crc, what=f"sharded payload {location}", then=outer.step
    )
    inner = _Countdown(n=len(tiles), on_zero=fold.finish)
    reqs: List[ReadReq] = []
    for t0, t1, tile_byte_range in tiles:
        offsets = list(read_box[0])
        offsets[0] += t0
        sizes = list(read_box[1])
        sizes[0] = t1 - t0
        tile_box = make_box(offsets, sizes)
        tile_overlaps = []
        for inter, lbox in overlaps:
            sub = box_intersect(inter, tile_box)
            if sub is not None:
                tile_overlaps.append((sub, lbox))
        # gap tiles (covering range between disjoint overlaps) still
        # read so the crc fold sees every payload byte; their scatter
        # list is empty
        reqs.append(
            ReadReq(
                path=location,
                byte_range=list(tile_byte_range),
                buffer_consumer=_ShardConsumer(
                    read_box=tile_box,
                    dtype=dtype,
                    overlaps=tile_overlaps,
                    buffers=buffers,
                    countdown=inner,
                    crc_fold=fold,
                    crc_key=t0,
                ),
            )
        )
    return reqs


class _ShardConsumer(BufferConsumer):
    """Scatter one saved shard's bytes into every overlapping local region
    (reference ShardedTensorBufferConsumer, sharded_tensor.py:301-333), or,
    for a leaf on the direct path, put them on the devices as they lie."""

    def __init__(
        self,
        read_box: Box,
        dtype: str,
        overlaps: List[Tuple[Box, Box]],
        buffers: Dict[Box, np.ndarray],
        countdown: _Countdown,
        crc_fold: Optional[Any] = None,
        crc_key: int = 0,
        direct: Optional[_DirectLeaf] = None,
    ) -> None:
        self.direct = direct
        self.read_box = read_box
        self.dtype = dtype
        self.overlaps = overlaps
        self.buffers = buffers
        self.countdown = countdown
        self.crc_fold = crc_fold
        self.crc_key = crc_key

    async def consume_buffer(
        self, buf: Any, executor: Optional[Executor] = None
    ) -> None:
        if self.crc_fold is not None:
            self.crc_fold.record(self.crc_key, buf)
        src = array_from_buffer(buf, self.dtype, self.read_box[1])

        def scatter() -> None:
            # reshard/scatter: the copies alone, inside the worker's
            # consume/materialize (whose queue_ns is the wait for the worker)
            with obs.span("reshard/scatter", bytes=src.nbytes):
                for inter, lbox in self.overlaps:
                    s_sl = relative_slices(inter, self.read_box)
                    d_sl = relative_slices(inter, lbox)
                    # 0-d boxes: arr[()] yields a scalar, not a view — use [...]
                    s = src[s_sl] if s_sl else src[...]
                    d = (
                        self.buffers[lbox][d_sl]
                        if d_sl
                        else self.buffers[lbox][...]
                    )
                    fast_copyto(d, s)

        def place() -> None:
            _populate(src)
            if self.direct is None or not self.direct.place(
                src, self.read_box, self.overlaps
            ):
                scatter()

        if executor is not None:
            await obs.run_in_executor(
                executor, place,
                name="consume/materialize", nbytes=src.nbytes,
            )
        else:
            place()
        self.countdown.step()

    def get_consuming_cost_bytes(self) -> int:
        return box_nelems(self.read_box) * string_to_dtype(self.dtype).itemsize
