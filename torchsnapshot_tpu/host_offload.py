"""Host-offloaded array support (the TPU answer to UVM embeddings).

Reference: torchsnapshot/uvm_tensor.py:13-45 wraps fbgemm's CUDA
unified-virtual-memory ops so giant torchrec embedding tables living in
host memory can be checkpointed without device round-trips.  On TPU the
equivalent is explicit host offload via ``jax`` memory kinds
(``pinned_host``): arrays placed there are addressable from the host, so
staging them is a zero-copy ``np.asarray`` instead of a D2H transfer — the
preparers handle them transparently; this module provides the placement
helpers and feature detection.  Every backend of the supported jax
(cpu included) exposes ``pinned_host``; a runtime that does not fails
loudly in ``with_memory_kind`` instead of passing arrays through.
"""

from __future__ import annotations

import logging
from typing import Any, Iterator, List

from . import obs

_HOST_KINDS = ("pinned_host", "unpinned_host")

# last eager_offload_write_reqs breakdown (see its tail) — benchmark
# evidence of which unblock mechanism engaged
LAST_OFFLOAD_STATS: dict = {}

logger = logging.getLogger(__name__)


def host_memory_supported() -> bool:
    import jax

    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    return any(k in kinds for k in _HOST_KINDS)


def is_host_offloaded(arr: Any) -> bool:
    try:
        return arr.sharding.memory_kind in _HOST_KINDS
    except Exception:
        return False


def offload_to_host(arr: Any):
    """Move an array to pinned host memory."""
    import jax

    return jax.device_put(arr, arr.sharding.with_memory_kind("pinned_host"))


def to_device(arr: Any):
    """Bring a host-offloaded array back to device HBM."""
    import jax

    if not is_host_offloaded(arr):
        return arr
    sharding = arr.sharding.with_memory_kind("device")
    return jax.device_put(arr, sharding)


def _iter_stagers(write_reqs) -> Iterator[Any]:
    """Yield every leaf buffer stager, looking through batched slabs."""
    from .batcher import BatchedBufferStager

    for wr in write_reqs:
        st = wr.buffer_stager
        if isinstance(st, BatchedBufferStager):
            for member, _ in st.stagers:
                yield member
        else:
            yield st


_release_queue = None


def _watch_releases(q) -> None:
    """Single daemon loop multiplexing every pending release job by
    polling ``is_ready()``: one hung transfer delays only its own
    release (its device refs stay as staging fallbacks — the degrade
    path), never blocks jobs queued after it, and being a daemon thread
    never blocks interpreter exit.  Per-call threads would accumulate
    without bound; a joined executor would hang shutdown."""
    import queue as _queue

    import jax

    pending: List[Any] = []
    while True:
        try:
            # the loop can block on q.get for MINUTES between takes;
            # a lingering `job` local from the previous iteration would
            # keep that take's pinned-host copies (2x payload) alive
            # the whole time — clear every strong local before blocking
            job = None
            job = q.get(timeout=0.05 if pending else None)
            pending.append(job)
            job = None
        except _queue.Empty:
            pass
        still: List[Any] = []
        for host_arrays, stager_lists in pending:
            try:
                ready = all(
                    a.is_ready() if hasattr(a, "is_ready") else True
                    for a in host_arrays
                )
            except Exception:
                ready = True  # error state resolves in block_until_ready
            if not ready:
                still.append((host_arrays, stager_lists))
                continue
            try:
                jax.block_until_ready(host_arrays)
            except Exception as e:
                logger.warning(
                    "eager pinned-host offload failed after dispatch; "
                    "device refs retained for fallback staging",
                    exc_info=True,
                )
                obs.swallowed_exception("host_offload.async_transfer", e)
                continue
            for sts in stager_lists:
                for st in sts:
                    st.fallback_arr = None
        # the for-loop targets outlive the loop; while this thread then
        # blocks on q.get they would pin the last job's host copies
        host_arrays = stager_lists = None
        pending = still


def _release_fallbacks_on_completion(host_arrays, stager_lists) -> None:
    """Drop the stagers' device refs the moment the batched DMA completes,
    so HBM is released as soon as training drops its own references — not
    held for the whole background storage drain.  On transfer failure the
    refs stay, and staging degrades to the device arrays."""
    global _release_queue
    if _release_queue is None:
        import queue
        import threading

        _release_queue = queue.Queue()
        threading.Thread(
            target=_watch_releases,
            args=(_release_queue,),
            name="tsnp-offload-release",
            daemon=True,
        ).start()
    _release_queue.put((host_arrays, stager_lists))


def eager_offload_write_reqs(
    write_reqs, budget_bytes: int | None = None
) -> int:
    """Make the pending write requests independent of device state NOW, in
    one batched transfer — the TPU-native unblock point for ``async_take``.

    The reference blocks ``async_take`` until every tensor is staged in
    host RAM, because CUDA tensors are mutable and the next optimizer step
    would corrupt unstaged data (io_preparers/tensor.py:283-307,
    scheduler.py:299).  On TPU the equivalent safety point is much earlier
    and much cheaper:

    - device ``jax.Array``s are immutable, so *correctness* never requires
      staging — but holding them pins HBM.  One batched ``device_put`` of
      every pending device array to ``pinned_host`` moves them at DMA
      bandwidth (the analogue of the reference's GPU slab + single DtoH,
      batcher.py:104-162) and releases HBM as soon as training drops its
      own references.
    - mutable *host* arrays (numpy / torch CPU) get their defensive copies
      taken here instead of lazily at staging-admission time.

    After this returns, training may mutate anything; staging + storage
    I/O proceed in the background from the offloaded copies.  Only whole
    arrays are offloaded (``index is None``): computing on host-kind
    arrays (e.g. slicing a >512MB chunked array) is not a supported XLA
    path, so indexed stagers keep their device refs and stage lazily —
    still safe by immutability.

    ``budget_bytes`` caps the pinned-host memory claimed by the device
    offload (callers pass a fraction of the scheduler's staging budget so
    offloaded-but-unstaged pinned buffers plus in-flight staged copies
    stay within host RAM).  Device arrays past the cap are skipped — they
    stage lazily in the background, still safe by immutability, so the
    unblock point is unaffected.  Mutable *host* arrays are always copied
    regardless of the cap: their safety depends on the copy happening
    before control returns to training.

    **Donated train states**: under ``jit(..., donate_argnums=...)`` the
    next training step DELETES the device buffers async_take left behind.
    Offloaded arrays are safe (the pinned-host copy is independent), but
    any leaf that stages lazily from the device array — one skipped by
    ``budget_bytes``, any leaf when the runtime lacks host memory kinds,
    and every CHUNK of an over-``max_chunk_size`` array (indexed stagers
    slice on device and are never offloaded) — will find its buffer
    deleted and the snapshot fails with a clear error (see
    JaxArrayBufferStager).  With donation, call ``.wait()`` before the
    next step; for non-chunked leaves a large enough offload budget also
    suffices.

    Returns the number of bytes made training-independent.  Every leaf
    that is left to stage lazily — skipped by the budget, or because the
    offload dispatch failed — is logged at WARNING and counted through
    ``obs.swallowed_exception``: the mechanism stays, its silence does
    not.
    """
    with obs.span("offload/eager", reqs=len(write_reqs)) as sp:
        moved = _eager_offload_impl(write_reqs, budget_bytes)
        if sp is not None:
            sp.attrs["bytes"] = moved
    obs.counter(obs.BYTES_OFFLOADED).inc(moved)
    return moved


def _eager_offload_impl(write_reqs, budget_bytes: int | None = None) -> int:
    from .serialization import fast_copy
    from .preparers.array import (
        HostArrayBufferStager,
        JaxArrayBufferStager,
        _is_jax_array,
    )

    by_array: dict = {}
    host_stagers: List[Any] = []
    for st in _iter_stagers(write_reqs):
        if (
            isinstance(st, JaxArrayBufferStager)
            and st.index is None
            and st.arr is not None
            and _is_jax_array(st.arr)
        ):
            by_array.setdefault(id(st.arr), []).append(st)
        elif (
            isinstance(st, HostArrayBufferStager)
            and st.defensive_copy
            and st.arr is not None
        ):
            host_stagers.append(st)

    moved = 0
    if by_array:
        import jax

        arrays, shardings, keys = [], [], []
        claimed = 0
        skipped = 0
        for key, sts in by_array.items():
            a = sts[0].arr
            if is_host_offloaded(a):
                continue
            # Small arrays are offloaded too — they cost next to nothing
            # inside the single batched device_put, and leaving them on
            # device would break donated train states (the next step
            # deletes the buffers they'd stage from).
            if budget_bytes is not None and claimed + a.nbytes > budget_bytes:
                skipped += a.nbytes  # stages lazily; safe by immutability
                continue  # (NOT under donation — see docstring)
            arrays.append(a)
            shardings.append(a.sharding.with_memory_kind("pinned_host"))
            keys.append(key)
            claimed += a.nbytes
        if skipped:
            logger.warning(
                "eager host offload: %d bytes exceed the %d-byte budget "
                "and will stage lazily from the device arrays",
                skipped, budget_bytes,
            )
            obs.swallowed_exception(
                "host_offload.budget_skip",
                MemoryError(f"{skipped} bytes past budget {budget_bytes}"),
            )
        if arrays:
            try:
                # Dispatch ONE batched DMA and return without waiting for
                # completion: jax.Arrays are immutable, so training can
                # never corrupt the snapshot content, and the background
                # staging's np.asarray blocks on the in-flight transfer
                # naturally.  The unblock point is transfer *dispatch*,
                # not transfer completion — HBM is released as the DMA
                # drains, a fraction of a second later.
                host_arrays = jax.device_put(arrays, shardings)
            except Exception as e:
                logger.warning(
                    "eager host offload dispatch failed; arrays will "
                    "stage lazily (safe: jax.Array is immutable)",
                    exc_info=True,
                )
                obs.swallowed_exception("host_offload.device_put", e)
                host_arrays = None
            if host_arrays is not None:
                stager_lists = []
                for key, h in zip(keys, host_arrays):
                    for st in by_array[key]:
                        # Keep the original device ref as a staging
                        # fallback: the dispatched transfer can still fail
                        # asynchronously (pinned-host allocation), and the
                        # immutable device array remains a valid source.
                        st.fallback_arr = st.arr
                        st.arr = h
                    stager_lists.append(by_array[key])
                    moved += h.nbytes
                _release_fallbacks_on_completion(host_arrays, stager_lists)

    host_copied = 0
    for st in host_stagers:
        st.arr = fast_copy(st.arr)
        st.defensive_copy = False
        st.owns_arr = True  # staging must drop the copy once consumed
        moved += st.arr.nbytes
        host_copied += st.arr.nbytes
    # breadcrumbs for benchmarks/diagnostics: which unblock mechanism
    # actually engaged on this take (the pinned-host path only exists on
    # runtimes with host memory kinds — evidence matters on hardware)
    LAST_OFFLOAD_STATS.clear()
    LAST_OFFLOAD_STATS.update(
        {
            "device_offload_bytes": moved - host_copied,
            "host_defensive_copy_bytes": host_copied,
            "host_memory_kinds": host_memory_supported(),
        }
    )
    return moved
