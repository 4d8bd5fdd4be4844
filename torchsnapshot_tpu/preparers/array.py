"""Array preparer: write/read planning for host arrays and single-device
``jax.Array``s, plus the chunked variant for big arrays.

Reference: torchsnapshot/io_preparers/tensor.py:50-409 and
io_preparers/chunked_tensor.py:36-128.  TPU-native differences:

- The device→host copy is ``jax.Array.copy_to_host_async()`` and then
  ``np.asarray``, both on the staging worker that materializes the object,
  one line apart (``_materialize`` below, ``ops/device_pack.py``
  ``pack_arrays_to_host``): a transfer is asked for when a worker is free
  to wait for it, never at admission or ahead of the pool — the analogue
  of the reference's CUDA DtoH in a thread pool with the GIL released
  (io_preparers/tensor.py:249-255).  It should stay so: on a TPU v5 lite,
  into newly made host arrays, four workers that each ask and wait move
  2.5–2.6 GB/s, one alone 2.4, and 16 x 403 MiB asked for at once 1.1–1.4
  (PERF.md §5, the D2H probe of PR 28: many outstanding transfers
  collapse, as they do host→device).  The host array itself comes from
  ``staging_arena``: a block kept from the save before, not a new mapping.
- Chunked staging slices the array **on device** (bounded HBM copy) so host
  memory stays bounded by the chunk size while D2H overlaps storage I/O.
- Defensive copies for async snapshots apply only to *host* arrays
  (numpy/torch): a jax.Array is immutable, so its staged bytes can never be
  mutated by training — the reference's hardest async-safety problem
  (io_preparers/tensor.py:283-307) disappears by construction.
"""

from __future__ import annotations

import functools
import threading
from concurrent.futures import Executor
from typing import Any, List, Optional, Tuple

import numpy as np

from .. import knobs, obs, staging_arena
from ..io_types import BufferConsumer, BufferStager, Future, ReadReq, WriteReq
from ..manifest import ArrayEntry, ChunkedArrayEntry, Shard
import logging

from ..serialization import (
    BUFFER_PROTOCOL,
    array_as_memoryview,
    array_from_buffer,
    dtype_to_string,
    fast_copy,
    fast_copyto,
    serialized_size_bytes,
    string_to_dtype,
)

logger = logging.getLogger(__name__)

@functools.lru_cache(maxsize=256)
def _root_module(tp: type) -> str:
    # called several times per leaf on the planning path (the
    # async_take blocked window); cached on the type object
    return tp.__module__.split(".")[0]


def _is_torch_tensor(obj: Any) -> bool:
    return _root_module(type(obj)) == "torch"


def _is_jax_array(obj: Any) -> bool:
    if _root_module(type(obj)) not in ("jax", "jaxlib"):
        return False
    import jax

    return isinstance(obj, jax.Array)


def donate_template(arr: Any) -> None:
    """Free a jax restore-template's device buffers as soon as its
    replacement has materialized, so restore's device peak stays at ~1x
    payload + one leaf instead of 2x (all templates + all restored) —
    the jax analogue of the reference's in-place load into pre-allocated
    tensors (snapshot.py:743-753, io_preparers/tensor.py:91-126).

    Called strictly AFTER the replacement is visible through the leaf's
    Future (``fut.set`` precedes donation at every call site), never
    before: a restore that fails mid-leaf (transfer wedge, H2D OOM)
    leaves THAT leaf's template intact, and every already-donated
    template has a retrievable replacement.  A failure on a LATER leaf
    of the same stateful therefore cannot strand deleted arrays in the
    caller's live state: the repair path in
    ``Snapshot._restore_stateful`` loads the already-restored leaves
    (non-strict, mixed old/new — the reference's in-place load has the
    same mid-failure semantics, snapshot.py:743-753) before re-raising.

    ``delete()`` frees the buffers while keeping shape/dtype/sharding
    metadata valid, which is all any later step needs.  Aliased leaves
    (one array as the template for several paths) are safe: the second
    donation sees ``is_deleted()`` and no-ops, and each path's restored
    array is built from storage bytes, never from the template."""
    if not _is_jax_array(arr):
        return  # host templates restore in place; None has no buffers
    mode = knobs.restore_donation()
    if mode == "off":
        return
    # .sharding outlives delete(); .devices() raises on a deleted array,
    # which is what an aliased template's second path hands in
    if mode == "auto" and any(
        d.platform == "cpu" for d in arr.sharding.device_set
    ):
        return
    try:
        if not arr.is_deleted():
            arr.delete()
            DONATION_STATS["donated_templates"] += 1
    except Exception as e:  # donation is an optimization, never fatal
        obs.swallowed_exception("restore.donate_template", e)


# how many restore templates were actually freed (the benchmark's
# donation.templates_share reads it)
DONATION_STATS = {"donated_templates": 0}


def is_array_like(obj: Any) -> bool:
    if isinstance(obj, np.ndarray):
        return True
    if _is_jax_array(obj):
        return True
    if _is_torch_tensor(obj):
        import torch

        return isinstance(obj, torch.Tensor)
    return False


def _to_host_view(obj: Any) -> np.ndarray:
    """Zero-copy host view when possible (torch CPU → numpy shares memory)."""
    if isinstance(obj, np.ndarray):
        return obj
    if _is_torch_tensor(obj):
        return obj.detach().cpu().numpy()
    raise TypeError(type(obj))


def array_nbytes(obj: Any) -> int:
    if _is_torch_tensor(obj):
        obj = _to_host_view(obj)
    return serialized_size_bytes(obj.shape, obj.dtype)


def array_dtype_str(obj: Any) -> str:
    if _is_torch_tensor(obj):
        obj = _to_host_view(obj)
    return dtype_to_string(obj.dtype)


def _count_chunk_staged(nbytes: int) -> None:
    """One chunk of a leaf over MAX_CHUNK_SIZE_BYTES has been staged whole
    (a host chunk that a striped store streams by parts has no such moment
    and is not counted)."""
    obs.counter(obs.CHUNKED_WRITE_BYTES).inc(nbytes)
    obs.counter(obs.CHUNKED_WRITE_CHUNKS).inc()


class JaxArrayBufferStager(BufferStager):
    """Stage a (slice of a) single-device/replicated jax.Array: launch the
    async D2H transfer, then materialize to numpy in a worker thread."""

    def __init__(
        self,
        arr: Any,
        index: Optional[Tuple] = None,
        nbytes: int = 0,
        chunk_rows: Optional[int] = None,
    ):
        self.arr = arr
        self.index = index
        self.nbytes = nbytes or array_nbytes(arr)
        # rows of the dim-0 range this stager holds of a leaf over
        # MAX_CHUNK_SIZE_BYTES (ChunkedArrayIOPreparer); None for any
        # other write.  Read by the chunked path's span and counters only.
        self.chunk_rows = chunk_rows
        # Set by eager_offload_write_reqs when it re-points ``arr`` at an
        # in-flight pinned-host copy: the original (immutable) device array,
        # kept so an asynchronous offload failure (e.g. pinned-host
        # allocation) degrades to staging straight from the device instead
        # of failing the snapshot.  Cleared the moment the host copy
        # materializes successfully.
        self.fallback_arr: Any = None

    async def stage_buffer(self, executor: Optional[Executor] = None) -> memoryview:
        def _materialize(src: Any) -> np.ndarray:
            is_deleted = getattr(src, "is_deleted", None)
            if callable(is_deleted) and is_deleted():
                # A training step deleted the buffer this write was going
                # to stage from — the donate_argnums hazard.  Fail with a
                # diagnosis instead of XLA's bare "Array has been deleted".
                if self.index is not None:
                    why = (
                        "this leaf is a chunk of an array over "
                        "MAX_CHUNK_SIZE_BYTES; chunks slice on device "
                        "and always stage lazily. With donation, call "
                        "pending.wait() before the next step (or raise "
                        "the chunk-size knob so the array is offloaded "
                        "whole)."
                    )
                else:
                    why = (
                        "this leaf staged lazily (eager-offload budget "
                        "exceeded, or host memory kinds unavailable). "
                        "Raise TORCHSNAPSHOT_TPU_PER_RANK_MEMORY_BUDGET_"
                        "BYTES, or call pending.wait() before the next "
                        "step."
                    )
                raise RuntimeError(
                    "device array was deleted before async-snapshot "
                    "staging — usually jit(donate_argnums=...) donated "
                    "the train state on the step after async_take. "
                    "Offloaded leaves are immune; " + why
                )
            if self.chunk_rows is None:
                a = src if self.index is None else src[self.index]
            else:
                with obs.span(
                    "chunk/slice", bytes=self.nbytes, rows=self.chunk_rows
                ) as sliced:
                    a = src[self.index]
                    if sliced is not None:
                        # a traced run waits here, so that the span holds
                        # the slice and d2h/copy the copy alone; the copy
                        # waits for the slice either way
                        a.block_until_ready()
            # the host array is made by whichever of the two calls comes
            # to it first: both inside the arena
            with staging_arena.allocating():
                try:
                    a.copy_to_host_async()
                except Exception as e:
                    # some array types (fully replicated committed) decline
                    # the async prefetch; np.asarray below does the copy
                    # synchronously either way
                    obs.swallowed_exception(
                        "array_stager.copy_to_host_async", e
                    )
                with obs.span("d2h/copy", bytes=self.nbytes):
                    return np.asarray(a)

        async def _run(src: Any) -> np.ndarray:
            if executor is not None:
                return await obs.run_in_executor(
                    executor, _materialize, src,
                    name="stage/materialize", nbytes=self.nbytes,
                )
            return _materialize(src)

        try:
            np_arr = await _run(self.arr)
        except Exception as e:
            fallback = self.fallback_arr
            if fallback is None:
                raise
            logger.warning(
                "eager pinned-host offload failed asynchronously; staging "
                "from the device array instead (safe: jax.Array is immutable)",
                exc_info=True,
            )
            obs.swallowed_exception("array_stager.offload_fallback", e)
            np_arr = await _run(fallback)
        self.arr = None  # drop refs as early as possible
        self.fallback_arr = None
        if self.chunk_rows is not None:
            _count_chunk_staged(self.nbytes)
        return array_as_memoryview(np_arr)

    def get_staging_cost_bytes(self) -> int:
        return self.nbytes


class HostArrayBufferStager(BufferStager):
    """Stage a host (numpy / torch CPU) array. For async snapshots, take a
    defensive copy at staging time: the caller may mutate the source before
    storage I/O completes (reference io_preparers/tensor.py:283-307)."""

    def __init__(
        self, arr: np.ndarray, defensive_copy: bool, chunk: bool = False
    ):
        self.arr = arr
        self.defensive_copy = defensive_copy
        # a dim-0 range of a leaf over MAX_CHUNK_SIZE_BYTES: counted
        self.chunk = chunk
        # Set when the stager holds a private copy (eager offload took the
        # defensive copy early); staging then drops the ref so the copy is
        # freed as soon as its storage write completes, matching the
        # scheduler's budget credits.
        self.owns_arr = False

    async def stage_buffer(self, executor: Optional[Executor] = None) -> memoryview:
        arr = self.arr
        if self.defensive_copy:
            if executor is not None:
                arr = await obs.run_in_executor(
                    executor, fast_copy, arr,
                    name="stage/copy", nbytes=arr.nbytes,
                )
            else:
                arr = fast_copy(arr)
            self.arr = None
        elif self.owns_arr:
            self.arr = None
        if self.chunk:
            _count_chunk_staged(arr.nbytes)
        return array_as_memoryview(arr)

    # ------------------------------------------------- part streaming
    # A host array is the one source whose bytes exist BEFORE staging,
    # so it can stage per part for the scheduler's stripe stream path:
    # each part is a view (sync take: zero copy) or a part-sized
    # defensive copy (async take: the copy that used to be whole-object
    # now peaks at the stream window), and the part's write dispatches
    # while later parts are still copying.

    def part_plan(self, part_size_bytes: int):
        arr = self.arr
        if self.defensive_copy:
            # an async take that still needs its defensive copy must
            # take it WHOLE at staging time: per-part copies would move
            # the unblock point (staging_done, which streams delay to
            # ~write completion) from one memcpy to the whole upload.
            # Eager offload clears this flag once it owns a private
            # copy, so offloaded async leaves still stream.
            return None
        if (
            arr is None
            or not arr.flags["C_CONTIGUOUS"]
            or arr.dtype.byteorder == ">"
        ):
            # staging whole would copy/normalize anyway — per-part
            # staging on top of that would re-copy the object per part
            return None
        from ..storage.stripe import plan_parts

        return plan_parts(arr.nbytes, part_size_bytes)

    async def stage_part(
        self, span, executor: Optional[Executor] = None
    ):
        lo, hi = span
        view = array_as_memoryview(self.arr)[lo:hi]
        if not self.defensive_copy:
            return view

        def copy() -> np.ndarray:
            dst = np.empty(hi - lo, dtype=np.uint8)
            np.copyto(dst, np.frombuffer(view, dtype=np.uint8))
            return dst

        if executor is not None:
            return await obs.run_in_executor(
                executor, copy, name="stage/copy", nbytes=hi - lo
            )
        return copy()

    def release_source(self) -> None:
        self.arr = None

    def get_staging_cost_bytes(self) -> int:
        return self.arr.nbytes if self.arr is not None else 0


def materialize_into_template(np_arr: np.ndarray, obj_out: Any) -> Any:
    """Place host data into/onto the restore template.

    - numpy template: in-place copy (casts if needed) — keeps the 1× memory
      property of the reference's in-place load (snapshot.py:743-753).
    - torch CPU template: in-place copy through the shared-memory view.
    - jax template: ``device_put`` honoring the template's sharding (the
      result is a new immutable array).
    - no template: a fresh numpy array.
    """
    if obj_out is None:
        return np_arr.copy()
    if isinstance(obj_out, np.ndarray):
        fast_copyto(obj_out, np_arr.reshape(obj_out.shape))
        return obj_out
    if _is_torch_tensor(obj_out):
        import torch

        view = obj_out.detach().cpu().numpy()
        fast_copyto(view, np_arr.reshape(view.shape))
        return obj_out
    if _is_jax_array(obj_out):
        import jax

        if np.dtype(np_arr.dtype) != np.dtype(obj_out.dtype):
            np_arr = np_arr.astype(obj_out.dtype)
        shaped = np_arr.reshape(obj_out.shape)
        sharding = obj_out.sharding
        with obs.span("h2d/put", bytes=shaped.nbytes):
            out = jax.device_put(shaped, sharding)
        # NOTE: the template is NOT donated here.  Callers donate only
        # after the replacement is visible through the leaf's Future
        # (fut.set then donate_template), so a donated template always
        # implies a retrievable replacement — the invariant the
        # failed-restore repair path in snapshot.py relies on.
        return out
    # Template is some other leaf (e.g. a Python scalar where the saved
    # state had a traced jax scalar, like TrainState.step before/after the
    # first jitted step). Behave like "no template": return fresh host data.
    return np_arr.copy()


class ArrayBufferConsumer(BufferConsumer):
    def __init__(
        self, entry: ArrayEntry, obj_out: Any, fut: Future, into: Any = None
    ):
        self.entry = entry
        self.obj_out = obj_out
        self.fut = fut
        self.into = into

    # below this, the executor thread-hop costs more than the copy —
    # a 20k-tiny-leaf restore spends most of its wall time in loop
    # wakeups and submits without this short-circuit.  HOST templates
    # only: a jax template's materialize calls device_put (and may
    # compile an unpack program), which must not run on the event loop
    # thread, where it would hold up all restore I/O.
    _INLINE_CONSUME_MAX = 256 * 1024

    async def consume_buffer(
        self, buf: Any, executor: Optional[Executor] = None
    ) -> None:
        if self.into is not None and buf is self.into:
            # the plugin honored the in-place hint: the template already
            # holds the payload bytes — nothing to copy or cast
            self.fut.set(self.obj_out)
            return
        np_arr = array_from_buffer(
            buf, self.entry.dtype, tuple(self.entry.shape)
        )
        if self.obj_out is None:
            from ..io_types import is_mmap_backed

            if is_mmap_backed(buf):
                # zero-copy materialization: the result IS the mapping
                # (a read-only view over file-backed pages) — no heap
                # copy before the caller's device put.  Pages fault in
                # on first touch and stay kernel-reclaimable, which is
                # what keeps a many-reader cold start's RSS flat.
                self.fut.set(np_arr)
                return
        inline = (
            np_arr.nbytes < self._INLINE_CONSUME_MAX
            and not _is_jax_array(self.obj_out)
        )
        if executor is not None and not inline:
            result = await obs.run_in_executor(
                executor, materialize_into_template, np_arr, self.obj_out,
                name="consume/materialize", nbytes=np_arr.nbytes,
            )
        else:
            result = materialize_into_template(np_arr, self.obj_out)
        self.fut.set(result)
        if result is not self.obj_out:
            # strictly after fut.set: donated ⟹ replacement reachable
            donate_template(self.obj_out)

    def get_consuming_cost_bytes(self) -> int:
        return serialized_size_bytes(self.entry.shape, string_to_dtype(self.entry.dtype))


class _TiledConsumer(BufferConsumer):
    """Consume one byte-range tile into a region of the target host buffer
    (reference prepare_read_tiled, io_preparers/tensor.py:128-181)."""

    def __init__(
        self,
        target_flat: np.ndarray,
        elem_range: Tuple[int, int],
        countdown: "_Countdown",
        tile_bytes: int,
        dtype: str,
        crc_fold: Optional["_TileCrcFold"] = None,
    ):
        self.target_flat = target_flat
        self.elem_range = elem_range
        self.countdown = countdown
        self.tile_bytes = tile_bytes
        self.dtype = dtype
        self.crc_fold = crc_fold

    async def consume_buffer(
        self, buf: Any, executor: Optional[Executor] = None
    ) -> None:
        start, end = self.elem_range
        if self.crc_fold is not None:
            self.crc_fold.record(start, buf)
        np_arr = array_from_buffer(buf, self.dtype, (end - start,))
        fast_copyto(self.target_flat[start:end], np_arr)
        self.countdown.step()

    def get_consuming_cost_bytes(self) -> int:
        return self.tile_bytes


class _DeviceTileAcc:
    """Shared flat device accumulator for a budgeted read into a jax
    template: each tile chains a donated ``dynamic_update_slice``
    (``ops.device_pack.tile_update_device``), so device peak stays at
    ~1x the target plus one tile and host peak at O(budget) — the
    reference's bounded-RSS random-access property (upstream
    torchsnapshot's benchmarks/load_tensor) extended to DEVICE
    targets, which is the TPU-native case.  The user's template seeds the chain and is
    consumed by the first update; on a mid-read failure the template is
    therefore already donated — accessing it raises jax's
    deleted-buffer error, a LOUDER outcome than the host tiled path's
    documented garbage-contents one (_TileCrcFold CONTRACT note).

    Updates are dispatched onto the scheduler's executor (a put and a
    program dispatch must not hold up the loop thread — see
    ArrayBufferConsumer), so concurrent tiles of the same read race on
    the chain: a per-accumulator lock serializes them.  Tiles cover
    disjoint ranges, so completion order is irrelevant.  Construction
    happens at PLAN time on the caller thread and pre-compiles every
    executable the chain will dispatch — flatten, tile updates, final
    reshape (``warm_tile_updates``) — so worker threads never compile:
    a compile on the scheduler's executor would stall every tile queued
    behind it, and a compile error surfaces at plan time, before any
    template is consumed."""

    def __init__(self, template, tile_sigs, payload_dtype) -> None:
        import jax
        from jax.sharding import SingleDeviceSharding

        from ..ops.device_pack import warm_tile_updates

        self.out_shape = tuple(template.shape)
        self.lock = threading.Lock()
        device = list(template.sharding.device_set)[0]
        n = int(np.prod(self.out_shape)) if self.out_shape else 1
        acc_dt = np.dtype(template.dtype)
        sharding = SingleDeviceSharding(device)

        def _aot(fn, *avals):
            return jax.jit(fn, donate_argnums=0).lower(*avals).compile()

        flat_aval = jax.ShapeDtypeStruct((n,), acc_dt, sharding=sharding)
        if self.out_shape != (n,):
            # seed the chain with a DONATED flatten: a plain .reshape(-1)
            # of a multi-d template leaves the caller's array alive for
            # the whole read (2x device peak, no deleted-buffer signal)
            shaped_aval = jax.ShapeDtypeStruct(
                self.out_shape, acc_dt, sharding=sharding
            )
            self.acc = _aot(lambda a: a.reshape((n,)), shaped_aval)(template)
            out_shape = self.out_shape
            self._reshape = _aot(lambda a: a.reshape(out_shape), flat_aval)
        else:
            self.acc = template
            self._reshape = None
        warm_tile_updates(
            n,
            acc_dt,
            tuple(
                (t1 - t0, np.dtype(string_to_dtype(payload_dtype)))
                for t0, t1 in tile_sigs
            ),
            device,
        )

    def update(self, tile_np: np.ndarray, off: int) -> None:
        from ..ops.device_pack import tile_update_device

        with self.lock:
            self.acc = tile_update_device(self.acc, tile_np, off)

    def finish(self):
        if self._reshape is None:
            return self.acc
        return self._reshape(self.acc)


class _DeviceTiledConsumer(BufferConsumer):
    """Consume one byte-range tile into a shared device accumulator
    (the jax-template twin of _TiledConsumer)."""

    def __init__(
        self,
        acc: "_DeviceTileAcc",
        elem_range: Tuple[int, int],
        countdown: "_Countdown",
        tile_bytes: int,
        dtype: str,
        crc_fold: Optional["_TileCrcFold"] = None,
    ):
        self.acc = acc
        self.elem_range = elem_range
        self.countdown = countdown
        self.tile_bytes = tile_bytes
        self.dtype = dtype
        self.crc_fold = crc_fold

    async def consume_buffer(
        self, buf: Any, executor: Optional[Executor] = None
    ) -> None:
        start, end = self.elem_range
        if self.crc_fold is not None:
            self.crc_fold.record(start, buf)
        np_arr = array_from_buffer(buf, self.dtype, (end - start,))
        if executor is not None:
            # the update puts the tile on the device and dispatches
            # its program, which must not hold up the scheduler loop
            # thread — same rule as ArrayBufferConsumer's materialize
            await obs.run_in_executor(
                executor, self.acc.update, np_arr, start,
                name="consume/materialize", nbytes=np_arr.nbytes,
            )
        else:
            self.acc.update(np_arr, start)
        self.countdown.step()

    def get_consuming_cost_bytes(self) -> int:
        return self.tile_bytes


class _Countdown:
    """Run ``on_zero`` after N consume steps complete (consumers all run on
    the scheduler's single loop thread, so a plain counter suffices)."""

    def __init__(self, n: int, on_zero) -> None:
        self.n = n
        self.on_zero = on_zero

    def step(self) -> None:
        self.n -= 1
        if self.n == 0:
            self.on_zero()


def _plan_flat_tiles(
    c0: int, c1: int, itemsize: int, budget_bytes: int, base_byte: int = 0
) -> List[Tuple[int, int, List[int]]]:
    """Split flat element range [c0, c1) into budget-sized tiles.

    Returns (t0, t1, byte_range) per tile; byte_range is relative to the
    stored object (``base_byte`` = the region's offset inside it, for
    slab-batched payloads).  Shared by the plain, chunked, and sharded
    (one "element" per dim-0 row) tiled-read paths so the tile math
    cannot drift between them."""
    elems_per_tile = max(1, budget_bytes // itemsize)
    tiles = []
    for t0 in range(c0, c1, elems_per_tile):
        t1 = min(t0 + elems_per_tile, c1)
        tiles.append(
            (
                t0,
                t1,
                [
                    base_byte + (t0 - c0) * itemsize,
                    base_byte + (t1 - c0) * itemsize,
                ],
            )
        )
    return tiles


class _TileCrcFold:
    """Integrity checking for a tiled region: byte-range reads cannot be
    checked individually against the recorded whole-object crc32, so each
    tile contributes the crc32 of its RAW payload bytes (hashed before
    any dtype cast into the target — a float32 payload restored into a
    float64 template must still verify against the stored bytes), and on
    completion the per-tile values fold via crc32_combine in offset order
    (tiles complete out of order).  Work on the scheduler's loop thread
    stays O(tile), never O(region); the final fold is O(tiles·log n)
    integer math.  Same VERIFY_ON_RESTORE gate as io_types.check_read_crc;
    tiling must not silently weaken integrity checking.

    CONTRACT under budgets: tiles are written into the target BEFORE the
    fold can detect corruption (pre-verifying would need an O(region)
    scratch buffer, which the memory budget exists to forbid), so on a
    detected mismatch the read raises but the output buffer's contents
    are unspecified.  The unbudgeted path verifies before any copy and
    leaves templates pristine on failure."""

    def __init__(self, expected_crc32, what: str, then) -> None:
        self.expected = expected_crc32
        self.what = what
        self.then = then
        self.want = expected_crc32 is not None and knobs.verify_on_restore()
        self.pieces: dict = {}  # tile start offset -> (crc32, nbytes)

    def record(self, start: int, buf) -> None:
        if not self.want:
            return
        from ..utils.checksums import crc32_fast

        view = memoryview(buf).cast("B")
        self.pieces[start] = (crc32_fast(view), view.nbytes)

    def finish(self) -> None:
        if self.want:
            from ..utils.checksums import crc32_combine

            actual, _total = 0, 0
            for start in sorted(self.pieces):
                crc, nbytes = self.pieces[start]
                actual = crc32_combine(actual, crc, nbytes)
            if actual != self.expected:
                raise RuntimeError(
                    f"crc32 mismatch for {self.what}: recorded "
                    f"crc32={self.expected}, assembled-from-tiles "
                    f"crc32={actual} — the payload changed after commit "
                    f"(output buffer contents are unspecified)"
                )
        self.then()


class ArrayIOPreparer:
    """Reference TensorIOPreparer (io_preparers/tensor.py:50-126)."""

    @staticmethod
    def prepare_write(
        obj: Any, location: str, replicated: bool, is_async_snapshot: bool
    ) -> Tuple[ArrayEntry, List[WriteReq]]:
        entry = ArrayEntry(
            location=location,
            serializer=BUFFER_PROTOCOL,
            dtype=array_dtype_str(obj),
            shape=list(obj.shape),
            replicated=replicated,
        )
        if _is_jax_array(obj):
            stager: BufferStager = JaxArrayBufferStager(obj)
        else:
            stager = HostArrayBufferStager(
                _to_host_view(obj), defensive_copy=is_async_snapshot
            )
        # codec preconditioning hint: float payloads byte-shuffle before
        # compression (codec.filter_for_dtype; 0 disables the filter)
        from ..codec import filter_for_dtype

        stager.codec_filter_stride = filter_for_dtype(entry.dtype)
        return entry, [
            WriteReq(
                path=location,
                buffer_stager=stager,
                checksum_sinks=[
                    (lambda c, e=entry: setattr(e, "crc32", c), None)
                ],
            )
        ]

    @staticmethod
    def prepare_read(
        entry: ArrayEntry,
        obj_out: Any = None,
        buffer_size_limit_bytes: Optional[int] = None,
    ) -> Tuple[List[ReadReq], Future]:
        fut: Future = Future()
        total = serialized_size_bytes(entry.shape, string_to_dtype(entry.dtype))
        itemsize = string_to_dtype(entry.dtype).itemsize
        can_tile = (
            buffer_size_limit_bytes is not None
            and total > buffer_size_limit_bytes
            and entry.byte_range is None
            and (obj_out is None or isinstance(obj_out, np.ndarray)
                 or _is_torch_tensor(obj_out))
        )
        # jax-template twin: tiles stream through a donated device
        # accumulator chain, keeping host at O(budget) and device at
        # ~1x target + one tile (_DeviceTileAcc).  Single-device,
        # default-memory templates of the exact stored shape only.
        # ALL executables the chain dispatches are AOT-compiled at plan
        # time on the caller thread (_DeviceTileAcc.__init__), never
        # lazily on a worker thread.  Element offsets ride int32
        # dynamic-slice indices, so ≥2^31-element arrays (8GB+ float32
        # — only reachable with the chunking knob raised) fall back to
        # the whole-buffer path rather than overflow.
        can_device_tile = (
            not can_tile
            and buffer_size_limit_bytes is not None
            and total > buffer_size_limit_bytes
            and entry.byte_range is None
            and _is_jax_array(obj_out)
            and len(obj_out.sharding.device_set) == 1
            and getattr(obj_out.sharding, "memory_kind", None)
            in (None, "device")
            and tuple(obj_out.shape) == tuple(entry.shape)
            and total // itemsize < np.iinfo(np.int32).max
        )
        if can_tile or can_device_tile:
            # Tile the flat element range so host memory stays O(limit).
            if can_device_tile:
                n_elems = int(np.prod(entry.shape)) if entry.shape else 1
            else:
                if obj_out is None:
                    target = np.empty(
                        tuple(entry.shape), dtype=string_to_dtype(entry.dtype)
                    )
                elif isinstance(obj_out, np.ndarray):
                    target = obj_out
                else:
                    target = obj_out.detach().cpu().numpy()
                target_flat = target.reshape(-1)
                n_elems = target_flat.shape[0]
            tiles = _plan_flat_tiles(
                0, n_elems, itemsize, buffer_size_limit_bytes
            )
            if can_device_tile:
                acc = _DeviceTileAcc(
                    obj_out,
                    {(t0, t1) for t0, t1, _ in tiles},
                    entry.dtype,
                )
                on_all_tiles = lambda: fut.set(acc.finish())  # noqa: E731
            else:
                on_all_tiles = lambda: fut.set(  # noqa: E731
                    target
                    if obj_out is None or isinstance(obj_out, np.ndarray)
                    else obj_out
                )
            fold = _TileCrcFold(
                getattr(entry, "crc32", None),
                f"{entry.location} (tiled)",
                on_all_tiles,
            )
            countdown = _Countdown(n=len(tiles), on_zero=fold.finish)
            read_reqs: List[ReadReq] = []
            for start, end, byte_range in tiles:
                if can_device_tile:
                    consumer: BufferConsumer = _DeviceTiledConsumer(
                        acc=acc,
                        elem_range=(start, end),
                        countdown=countdown,
                        tile_bytes=(end - start) * itemsize,
                        dtype=entry.dtype,
                        crc_fold=fold,
                    )
                else:
                    consumer = _TiledConsumer(
                        target_flat=target_flat,
                        elem_range=(start, end),
                        countdown=countdown,
                        tile_bytes=(end - start) * itemsize,
                        dtype=entry.dtype,
                        crc_fold=fold,
                    )
                read_reqs.append(
                    ReadReq(
                        path=entry.location,
                        byte_range=byte_range,
                        buffer_consumer=consumer,
                    )
                )
            return read_reqs, fut
        # In-place hint: a numpy template with the stored dtype and
        # exactly the payload's bytes lets an honoring plugin read
        # straight into the template (one pass, no intermediate buffer
        # and no copy — the reference's read-into-preallocated-tensor
        # property, io_preparers/tensor.py:91-126).  Consumers detect
        # honor by identity, so plugins without the fast path are
        # unaffected.
        into = None
        if (
            isinstance(obj_out, np.ndarray)
            and obj_out.dtype == string_to_dtype(entry.dtype)
            and obj_out.flags["C_CONTIGUOUS"]
            and not obj_out.flags["WRITEBACKIFCOPY"]
            and obj_out.nbytes == total
            # VERIFY_ON_RESTORE's unbudgeted contract is verify-before-
            # copy (templates stay pristine on a crc mismatch); reading
            # in place would dirty the template before the check runs
            and not knobs.verify_on_restore()
        ):
            into = obj_out
        return (
            [
                ReadReq(
                    path=entry.location,
                    byte_range=list(entry.byte_range) if entry.byte_range else None,
                    buffer_consumer=ArrayBufferConsumer(
                        entry, obj_out, fut, into=into
                    ),
                    expected_crc32=getattr(entry, "crc32", None),
                    into=into,
                )
            ],
            fut,
        )


def _chunk_dim0(shape: List[int], dtype: Any, max_chunk_bytes: int) -> List[Tuple[int, int]]:
    """Row ranges [(start, end), ...] such that each chunk ≤ max_chunk_bytes
    (reference chunk_tensor, io_preparers/chunked_tensor.py:36-65)."""
    if not shape or shape[0] == 0:
        return [(0, shape[0] if shape else 0)]
    row_bytes = serialized_size_bytes(shape[1:], dtype) if len(shape) > 1 else np.dtype(dtype).itemsize
    rows_per_chunk = max(1, max_chunk_bytes // max(1, row_bytes))
    return [
        (r, min(r + rows_per_chunk, shape[0]))
        for r in range(0, shape[0], rows_per_chunk)
    ]


class ChunkedArrayIOPreparer:
    """Reference ChunkedTensorIOPreparer (io_preparers/chunked_tensor.py)."""

    @staticmethod
    def prepare_write(
        obj: Any, location: str, replicated: bool, is_async_snapshot: bool
    ) -> Tuple[ChunkedArrayEntry, List[WriteReq]]:
        dtype = obj.dtype
        shape = list(obj.shape)
        ndim = len(shape)
        chunks: List[Shard] = []
        write_reqs: List[WriteReq] = []
        for (r0, r1) in _chunk_dim0(shape, dtype, knobs.get_max_chunk_size_bytes()):
            chunk_location = f"{location}_{r0}_{r1}"
            sizes = [r1 - r0] + shape[1:]
            chunks.append(
                Shard(
                    offsets=[r0] + [0] * (ndim - 1),
                    sizes=sizes,
                    location=chunk_location,
                )
            )
            nbytes = serialized_size_bytes(sizes, dtype)
            if _is_jax_array(obj):
                stager: BufferStager = JaxArrayBufferStager(
                    obj, index=(slice(r0, r1),), nbytes=nbytes,
                    chunk_rows=r1 - r0,
                )
            else:
                stager = HostArrayBufferStager(
                    _to_host_view(obj)[r0:r1],
                    defensive_copy=is_async_snapshot,
                    chunk=True,
                )
            from ..codec import filter_for_dtype

            stager.codec_filter_stride = filter_for_dtype(
                array_dtype_str(obj)
            )
            write_reqs.append(
                WriteReq(
                    path=chunk_location,
                    buffer_stager=stager,
                    checksum_sinks=[
                        (
                            lambda c, s=chunks[-1]: setattr(s, "crc32", c),
                            None,
                        )
                    ],
                )
            )
        entry = ChunkedArrayEntry(
            dtype=array_dtype_str(obj),
            shape=shape,
            chunks=chunks,
            replicated=replicated,
        )
        return entry, write_reqs

    @staticmethod
    def prepare_read(
        entry: ChunkedArrayEntry,
        obj_out: Any = None,
        buffer_size_limit_bytes: Optional[int] = None,
    ) -> Tuple[List[ReadReq], Future]:
        fut: Future = Future()
        dtype = string_to_dtype(entry.dtype)
        # Host-side assembly buffer; written into by each chunk's consumer.
        if isinstance(obj_out, np.ndarray) and obj_out.dtype == dtype:
            host_buf = obj_out
        else:
            host_buf = np.empty(tuple(entry.shape), dtype=dtype)
            obs.counter(obs.CHUNKED_HOST_ASSEMBLY_BYTES).inc(host_buf.nbytes)

        def on_done() -> None:
            if host_buf is obj_out:
                fut.set(obj_out)
                return
            # the whole array goes up in one put, on the thread that
            # stepped the countdown to zero
            with obs.span(
                "chunk/put", bytes=host_buf.nbytes, chunks=len(entry.chunks)
            ):
                result = materialize_into_template(host_buf, obj_out)
                fut.set(result)
                if result is not obj_out:
                    donate_template(obj_out)

        # Budget-aware tiling (reference prepare_read_tiled semantics
        # extended to chunks): a chunk is a dim-0 row range, so in flat
        # element space it is CONTIGUOUS — each over-budget chunk splits
        # into byte-range tiles written straight into the target, keeping
        # host memory O(limit) instead of O(chunk) (the contract of
        # upstream torchsnapshot's benchmarks/load_tensor/main.py:26-27).
        # One outer step per chunk; a tiled chunk steps the outer
        # countdown only after its tiles land AND the assembled region
        # passes the recorded crc32 (VERIFY_ON_RESTORE).
        itemsize = dtype.itemsize
        row_elems = 1
        for s in entry.shape[1:]:
            row_elems *= s
        can_tile_into = (
            buffer_size_limit_bytes is not None
            and host_buf.flags["C_CONTIGUOUS"]
        )
        outer = _Countdown(n=len(entry.chunks), on_zero=on_done)
        host_flat = host_buf.reshape(-1) if can_tile_into else None
        read_reqs: List[ReadReq] = []
        for chunk in entry.chunks:
            r0 = chunk.offsets[0]
            r1 = r0 + chunk.sizes[0]
            chunk_bytes = serialized_size_bytes(chunk.sizes, dtype)
            if can_tile_into and chunk_bytes > buffer_size_limit_bytes:
                c0 = r0 * row_elems
                c1 = r1 * row_elems
                tiles = _plan_flat_tiles(
                    c0,
                    c1,
                    itemsize,
                    buffer_size_limit_bytes,
                    base_byte=chunk.byte_range[0] if chunk.byte_range else 0,
                )
                def tiled_chunk_placed(n: int = chunk_bytes) -> None:
                    obs.counter(obs.CHUNKED_READ_BYTES).inc(n)
                    outer.step()

                fold = _TileCrcFold(
                    chunk.crc32, f"{chunk.location} (tiled)", tiled_chunk_placed
                )
                inner = _Countdown(n=len(tiles), on_zero=fold.finish)
                for t0, t1, byte_range in tiles:
                    read_reqs.append(
                        ReadReq(
                            path=chunk.location,
                            byte_range=byte_range,
                            buffer_consumer=_TiledConsumer(
                                target_flat=host_flat,
                                elem_range=(t0, t1),
                                countdown=inner,
                                tile_bytes=(t1 - t0) * itemsize,
                                dtype=entry.dtype,
                                crc_fold=fold,
                            ),
                        )
                    )
            else:
                read_reqs.append(
                    ReadReq(
                        path=chunk.location,
                        byte_range=list(chunk.byte_range)
                        if chunk.byte_range
                        else None,
                        buffer_consumer=_ChunkConsumer(
                            host_buf=host_buf,
                            row_range=(r0, r1),
                            sizes=list(chunk.sizes),
                            dtype=entry.dtype,
                            countdown=outer,
                        ),
                        expected_crc32=chunk.crc32,
                    )
                )
        return read_reqs, fut


class _ChunkConsumer(BufferConsumer):
    def __init__(self, host_buf, row_range, sizes, dtype, countdown):
        self.host_buf = host_buf
        self.row_range = row_range
        self.sizes = sizes
        self.dtype = dtype
        self.countdown = countdown

    async def consume_buffer(
        self, buf: Any, executor: Optional[Executor] = None
    ) -> None:
        r0, r1 = self.row_range
        np_arr = array_from_buffer(buf, self.dtype, tuple(self.sizes))

        def copy() -> None:
            with obs.span("chunk/assemble", bytes=np_arr.nbytes):
                fast_copyto(self.host_buf[r0:r1], np_arr)

        if executor is not None:
            await obs.run_in_executor(
                executor, copy,
                name="consume/materialize", nbytes=np_arr.nbytes,
            )
        else:
            copy()
        obs.counter(obs.CHUNKED_READ_BYTES).inc(np_arr.nbytes)
        self.countdown.step()

    def get_consuming_cost_bytes(self) -> int:
        return serialized_size_bytes(self.sizes, string_to_dtype(self.dtype))
