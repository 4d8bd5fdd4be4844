"""Host memory for staged device arrays, kept from one object to the next.

The host side of a device→host copy is a numpy array that jaxlib makes:
one ``malloc`` of the object's size, and one ``free`` when its write is
done.  At a slab's or a leaf's size glibc maps and unmaps every such
request (over its 32 MiB ceiling nothing is recycled), so each save
faults all its staged bytes in anew and hands them all back.  Measured on
a TPU v5 lite host (PERF_LEDGER.jsonl, PR 29, six pairs): a blocking
7.97 GB save commits in 1.47 s with this arena and the scheduler's order
rule, against 4.82 s at the parent, whose device→host copies waited for
fresh pages (``d2h.copy_s`` 11.07 → 1.82 thread-s); the pages given back,
7.97 GB a save, are what the sandbox of that host cannot take back as
fast as back-to-back saves return them (PERF.md §5).

numpy lets a caller choose the allocator of the arrays made in the
current context (NEP 49, ``PyDataMem_SetHandler``).  ``allocating()``
puts the arena of ``_csrc/fastio.cpp`` in that place for the span of one
materialization, on the staging worker that runs it, and nowhere else:
an array of 32 MiB or more made inside takes a block the arena kept, of
exactly its size, or a new mapping; when numpy frees it the block is
kept again.  The host-memory bound of a save stays the budget's
(``begin_save``: the scheduler passes its memory budget): bytes kept and
bytes handed out together stay under it, a request that no kept block
fits unmaps kept blocks, oldest first, before it maps its own.  A block
no request took during a whole save goes at that save's end
(``end_save``), and everything kept goes when no save has followed for
``_IDLE_RELEASE_S``: the arena holds memory between saves only while
saves follow each other (a preempted job's last saves, a burst), and a
process that has made its last save holds none.

The C entry point is reached through numpy's own function table, as a
compiled extension reaches it: ``PyDataMem_SetHandler`` has no Python
name.  Its slot is part of numpy's ABI (the table only grows); the ABI
and feature versions the table itself reports are checked before the
slot is called.  Where anything on that path is missing (no native
library, another numpy ABI, a numpy without handlers) the arena is off,
arrays are made as before, and the process says so once
(``exceptions.swallowed``, a warning).
"""

from __future__ import annotations

import contextlib
import ctypes
import logging
import threading
from typing import Any, Callable, Dict, NamedTuple, Optional

from . import _csrc, obs

logger = logging.getLogger(__name__)

_NAME = "tsnp_staging_arena"

# numpy's C function table (``_ARRAY_API``; numpy/__multiarray_api.h)
_SLOT_ABI_VERSION = 0  # PyArray_GetNDArrayCVersion
_SLOT_FEATURE_VERSION = 211  # PyArray_GetNDArrayCFeatureVersion
_SLOT_SET_HANDLER = 304  # PyDataMem_SetHandler, since NPY_1_22_API_VERSION
_ABI_VERSIONS = (0x01000009, 0x02000000)  # numpy 1.x, 2.x
_FEATURE_WITH_HANDLERS = 0x0000000F

# what is kept goes when no save has begun for this long after one ended
_IDLE_RELEASE_S = 10.0


class _Hook(NamedTuple):
    set_handler: Callable[[Any], Any]  # numpy's PyDataMem_SetHandler
    capsule: Any  # the arena's handler, as that call takes it
    lib: ctypes.CDLL


_lock = threading.Lock()
_tried = False
_hook: Optional[_Hook] = None
_saves = 0  # begun and not ended
_idle: Optional[threading.Timer] = None


def _install() -> _Hook:
    lib = _csrc.load()
    if lib is None:
        raise RuntimeError("no native library")
    try:
        from numpy._core import _multiarray_umath as umath
        from numpy._core.multiarray import get_handler_name
    except ImportError:  # a numpy before 2.0 keeps them elsewhere
        from numpy.core import _multiarray_umath as umath
        from numpy.core.multiarray import get_handler_name
    import numpy as np

    api = ctypes.pythonapi
    api.PyCapsule_New.restype = ctypes.py_object
    api.PyCapsule_New.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p]
    api.PyCapsule_GetPointer.restype = ctypes.c_void_p
    api.PyCapsule_GetPointer.argtypes = [ctypes.py_object, ctypes.c_char_p]
    table = ctypes.cast(
        api.PyCapsule_GetPointer(umath._ARRAY_API, None),
        ctypes.POINTER(ctypes.c_void_p),
    )
    version = ctypes.CFUNCTYPE(ctypes.c_uint)
    abi = version(table[_SLOT_ABI_VERSION])()
    if abi not in _ABI_VERSIONS:
        raise RuntimeError(f"numpy ABI {abi:#x}")
    feature = version(table[_SLOT_FEATURE_VERSION])()
    if feature < _FEATURE_WITH_HANDLERS:
        raise RuntimeError(f"numpy C API {feature:#x} has no allocator handlers")
    set_handler = ctypes.PYFUNCTYPE(ctypes.py_object, ctypes.py_object)(
        table[_SLOT_SET_HANDLER]
    )
    # the struct is a static of the library: it outlives every array
    capsule = api.PyCapsule_New(lib.tsnp_arena_handler(), b"mem_handler", None)
    # one array through it before anything relies on it
    old = set_handler(capsule)
    try:
        made_by = get_handler_name(np.empty(8, dtype=np.uint8))
    finally:
        set_handler(old)
    if made_by != _NAME:
        raise RuntimeError(f"numpy made an array with {made_by!r} under the arena")
    return _Hook(set_handler, capsule, lib)


def _installed() -> Optional[_Hook]:
    with _lock:
        return _hook


@contextlib.contextmanager
def allocating():
    """numpy arrays made inside, in this thread's context, take their
    bytes from the arena (the host side of a device→host copy is one).
    Nothing changes before the first ``begin_save`` has installed it."""
    hook = _installed()
    if hook is None:
        yield
        return
    old = hook.set_handler(hook.capsule)
    try:
        yield
    finally:
        hook.set_handler(old)


def begin_save(budget_bytes: int) -> None:
    """A save begins: the most bytes the arena holds from here on, kept
    and handed out together.  The first call installs the arena;
    installing may build the native library, so it happens here, off the
    event loop."""
    global _tried, _hook, _saves, _idle
    with _lock:
        if not _tried:
            _tried = True
            try:
                _hook = _install()
            except Exception as e:  # noqa: BLE001 — the arena is an optimization
                obs.swallowed_exception("staging_arena.unavailable", e)
                logger.warning(
                    "staging arena unavailable (%s); staged arrays are "
                    "allocated as numpy allocates them", e,
                )
        if _hook is None:
            return
        _saves += 1
        if _idle is not None:
            _idle.cancel()
            _idle = None
        _hook.lib.tsnp_arena_set_cap(max(0, int(budget_bytes)))


def end_save() -> None:
    """A save's last write is done: blocks that were kept all through it
    and that no request took go back to the system (sizes of an earlier
    state go after one save), and the rest if no save begins within
    ``_IDLE_RELEASE_S`` of the last one's end."""
    global _saves, _idle
    with _lock:
        if _hook is None:
            return
        _hook.lib.tsnp_arena_end_save()
        _saves = max(0, _saves - 1)
        if _saves == 0:
            _idle = timer = threading.Timer(_IDLE_RELEASE_S, lambda: _release(timer))
            timer.daemon = True
            timer.name = "tsnp-arena-idle"
            timer.start()


def _release(timer: threading.Timer) -> None:
    global _idle
    with _lock:
        if _idle is timer:  # no save has begun since
            _idle = None
            _hook.lib.tsnp_arena_set_cap(0)


def stats() -> Dict[str, int]:
    """Bytes kept and handed out, and how many requests a kept block
    served against a new mapping; all 0 where the arena is off."""
    out = (ctypes.c_uint64 * 4)()
    hook = _installed()
    if hook is not None:
        hook.lib.tsnp_arena_stats(out)
    return dict(zip(("kept_bytes", "live_bytes", "reused", "mapped"), map(int, out)))
