"""Native extension loader: builds fastio.so on first use (g++, cached),
falls back to pure Python — logged once at WARNING — when no toolchain
is available.

Bindings are ctypes (no pybind11 in the image); all entry points release
the GIL for the duration of the syscall chain, so the scheduler's worker
threads overlap I/O properly.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "fastio.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False

# (compile flags, link flags) per attempt, most-preferred first.  zlib
# linkage first (its SIMD crc32 beats our slice-by-8 ~2x); then without,
# for hosts missing zlib.h/libz.  -march=native is a ~25% win for the
# fused digest loops (the adler closed-form reductions vectorize), but an
# ISA-specific binary must never outlive its host CPU: it is cached under
# the CPU fingerprint and only ever loaded by a host with the same one (a
# copied venv / NFS tree / docker image moved to an older CPU resolves to
# a different name and rebuilds).
_BASE_FLAGS = ("-O3", "-shared", "-fPIC")
_NATIVE_VARIANTS = (
    (("-march=native", "-DTSNP_USE_ZLIB"), ("-lz",)),
    (("-march=native",), ()),
)
_PORTABLE_VARIANTS = ((("-DTSNP_USE_ZLIB",), ("-lz",)), ((), ()))


def _build_key() -> str:
    """Hash of fastio.cpp's CONTENT and of every flag set a build may
    use.  It is part of every cached file's name, so a library built
    from other source or other flags is simply never a candidate — file
    times mean nothing after a copy or an artifact restore."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(
        repr((_BASE_FLAGS, _NATIVE_VARIANTS, _PORTABLE_VARIANTS)).encode()
    )
    return h.hexdigest()[:12]


def _native_so(fp: str, key: str) -> str:
    # The CPU fingerprint and the build key are embedded in the FILENAME,
    # so a native .so and its provenance are published by ONE atomic
    # rename — there is no companion record that a crash or concurrent
    # builder could leave missing/stale (which would let a -march=native
    # binary masquerade as portable and SIGILL on an older CPU).
    return os.path.join(_HERE, f"fastio.{fp}.{key}.so")


def _portable_so(key: str) -> str:
    return os.path.join(_HERE, f"fastio.portable.{key}.so")


def _no_native_marker(fp: str, key: str) -> str:
    return os.path.join(_HERE, f"fastio.{fp}.{key}.nonative")


def _build(fp: str, key: str) -> Optional[str]:
    # Compile to a process-unique temp file and os.replace into the
    # destination: atomic on posix, so concurrent first-use across
    # processes (the multi-process tests spawn several) can never observe
    # a half-written .so or a native .so under the portable name — worst
    # case they each build once, last rename wins.
    tmp = os.path.join(_HERE, f"fastio.so.tmp.{os.getpid()}")
    # ISA-specific variants exist ONLY when a CPU fingerprint can be
    # recorded; order prefers zlib linkage, then no-zlib
    native = [(v, _native_so(fp, key)) for v in _NATIVE_VARIANTS] if fp else []
    portable = [(v, _portable_so(key)) for v in _PORTABLE_VARIANTS]
    attempts = native[:1] + portable[:1] + native[1:] + portable[1:]
    for (cflags, libs), dest in attempts:
        try:
            subprocess.run(
                ["g++", *_BASE_FLAGS, *cflags, "-o", tmp, _SRC, *libs],
                check=True,
                capture_output=True,
                timeout=120,
            )
            os.replace(tmp, dest)
        except (OSError, subprocess.SubprocessError) as e:
            logger.debug(
                "fastio build failed with %s (%r)", cflags or "base flags", e
            )
            try:
                os.remove(tmp)
            except OSError:
                pass
            continue
        if fp and dest == _portable_so(key):
            # every native variant failed on a fingerprintable host
            # (e.g. a g++ that rejects -march=native): record that,
            # so later processes accept the cached portable build
            # instead of re-paying the failed native compiles on
            # every startup
            _publish_marker(_no_native_marker(fp, key))
        # libraries and markers built from other source or flags can
        # never be loaded again; same-key files for other CPUs stay
        for stale in glob.glob(os.path.join(_HERE, "fastio.*")):
            if stale.endswith((".so", ".nonative")) and key not in stale:
                try:
                    os.remove(stale)
                except OSError:
                    pass
        return dest
    return None


def _publish_marker(path: str) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w"):
            pass
        os.replace(tmp, path)
    except OSError:
        pass


def _cpu_fingerprint() -> str:
    """Hash of this host's CPU feature flags ('' when undeterminable —
    callers then avoid ISA-specific codegen entirely)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return hashlib.sha256(
                        " ".join(sorted(line.split(":", 1)[1].split())).encode()
                    ).hexdigest()[:16]
    except OSError:
        pass
    return ""


def _try_load(path: str) -> Optional[ctypes.CDLL]:
    if not os.path.exists(path):
        return None
    try:
        return ctypes.CDLL(path)
    except OSError as e:
        logger.debug("fastio load failed for %s: %r", path, e)
        return None


def load() -> Optional[ctypes.CDLL]:
    """The fastio library, or None when unavailable."""
    global _lib, _load_attempted
    with _lock:
        if _load_attempted:
            return _lib
        _load_attempted = True

        fp, key = _cpu_fingerprint(), _build_key()
        # Only the PREFERRED (native, when fingerprintable) candidate is
        # accepted from cache: settling for a portable .so while the
        # native one is absent would silently forfeit the -march=native
        # win forever (a successful load skips _build) — UNLESS a
        # .nonative marker records that native compilation already
        # failed for this CPU and this source, in which case the cached
        # portable build is the best achievable and rebuilding every
        # process would just re-pay the failed native compiles.
        preferred = _native_so(fp, key) if fp else _portable_so(key)
        lib = _try_load(preferred)
        if lib is None and fp and os.path.exists(_no_native_marker(fp, key)):
            lib = _try_load(_portable_so(key))
        if lib is None:
            dest = _build(fp, key)
            lib = _try_load(dest) if dest else None
        if lib is None:
            # no toolchain: a same-source portable library beats the
            # pure-python fallback
            lib = _try_load(_portable_so(key))
        if lib is None:
            logger.warning(
                "native fastio library unavailable (no cached build for "
                "this source and g++ failed or is missing); storage, "
                "digest and codec paths run in pure Python"
            )
            return None
        lib.tsnp_write_file.restype = ctypes.c_int
        lib.tsnp_write_file.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int,
        ]
        lib.tsnp_write_file_digest.restype = ctypes.c_int
        lib.tsnp_write_file_digest.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.tsnp_read_file.restype = ctypes.c_int64
        lib.tsnp_read_file.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
        ]
        lib.tsnp_file_size.restype = ctypes.c_int64
        lib.tsnp_file_size.argtypes = [ctypes.c_char_p]
        lib.tsnp_crc32c.restype = ctypes.c_uint32
        lib.tsnp_crc32c.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_uint32,
        ]
        lib.tsnp_copy_digest.restype = None
        lib.tsnp_copy_digest.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.tsnp_crc32z.restype = ctypes.c_uint32
        lib.tsnp_crc32z.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_uint32,
        ]
        lib.tsnp_adler32.restype = ctypes.c_uint32
        lib.tsnp_adler32.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_uint32,
        ]
        lib.tsnp_digest.restype = None
        lib.tsnp_digest.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        # the fast-I/O engine (storage/fastio.py)
        lib.tsnp_part_pwrite.restype = ctypes.c_int
        lib.tsnp_part_pwrite.argtypes = [
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.tsnp_part_pread.restype = ctypes.c_int64
        lib.tsnp_part_pread.argtypes = [
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_int64,
        ]
        # the "huff" block codec
        for fn in (lib.tsnp_huff_compress, lib.tsnp_huff_decompress):
            fn.restype = ctypes.c_int64
            fn.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_int64,
            ]
        for fn in (lib.tsnp_byte_shuffle, lib.tsnp_byte_unshuffle):
            fn.restype = None
            fn.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_void_p,
            ]
        # the staging arena (staging_arena.py): numpy calls the allocator
        # through the handler struct, Python only these
        lib.tsnp_arena_handler.restype = ctypes.c_void_p
        lib.tsnp_arena_handler.argtypes = []
        lib.tsnp_arena_set_cap.restype = None
        lib.tsnp_arena_set_cap.argtypes = [ctypes.c_uint64]
        lib.tsnp_arena_end_save.restype = None
        lib.tsnp_arena_end_save.argtypes = []
        lib.tsnp_arena_stats.restype = None
        lib.tsnp_arena_stats.argtypes = [ctypes.POINTER(ctypes.c_uint64)]
        _lib = lib
        return _lib


def _buffer_address(view: memoryview) -> int:
    # zero-copy pointer even for read-only buffers
    import numpy as np

    return np.frombuffer(view, dtype=np.uint8).ctypes.data


def crc32c(data, seed: int = 0) -> Optional[int]:
    """crc32c via the native lib; None when unavailable."""
    lib = load()
    if lib is None:
        return None
    view = memoryview(data).cast("B")
    if view.nbytes == 0:
        return int(lib.tsnp_crc32c(None, 0, seed))
    return int(lib.tsnp_crc32c(_buffer_address(view), view.nbytes, seed))


def crc32z(data, seed: int = 0) -> Optional[int]:
    """zlib-polynomial crc32 (bit-compatible with zlib.crc32) via the
    native PCLMUL path; None when the lib is unavailable."""
    lib = load()
    if lib is None:
        return None
    view = memoryview(data).cast("B")
    if view.nbytes == 0:
        return seed
    return int(lib.tsnp_crc32z(_buffer_address(view), view.nbytes, seed))


def adler32(data, seed: int = 1) -> Optional[int]:
    """adler32 (bit-compatible with zlib.adler32) via the native AVX2
    path; None when the lib is unavailable."""
    lib = load()
    if lib is None:
        return None
    view = memoryview(data).cast("B")
    if view.nbytes == 0:
        return seed
    return int(lib.tsnp_adler32(_buffer_address(view), view.nbytes, seed))


def digest(data) -> Optional[tuple]:
    """(crc32, adler32) of ``data`` in one native call (no copy); None
    when the lib is unavailable."""
    lib = load()
    if lib is None:
        return None
    view = memoryview(data).cast("B")
    if view.nbytes == 0:
        return (0, 1)
    out = (ctypes.c_uint32 * 2)()
    lib.tsnp_digest(_buffer_address(view), view.nbytes, out)
    return (int(out[0]), int(out[1]))


def byte_shuffle(data, stride: int, inverse: bool = False):
    """Byte-shuffle (or unshuffle) ``data`` with the native cache-blocked
    transpose — GIL-free, one pass, no intermediate copy; None when the
    native lib is unavailable."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    view = memoryview(data).cast("B")
    out = np.empty(view.nbytes, dtype=np.uint8)
    fn = lib.tsnp_byte_unshuffle if inverse else lib.tsnp_byte_shuffle
    fn(_buffer_address(view), view.nbytes, stride, out.ctypes.data)
    return out


def huff_available() -> bool:
    """True when the native lib (which carries the huff codec) loaded."""
    return load() is not None


def huff_compress(data, headroom: int = 0):
    """Compress ``data`` with the native block-Huffman coder; None when
    the native lib is unavailable.  The returned
    stream may exceed the input by ~5 bytes per 128KB block on
    incompressible data (raw-mode blocks) — codec.py's min-ratio check
    handles store-raw fallback above this layer.

    ``headroom``: reserve that many writable bytes BEFORE the stream
    and return a uint8 array of headroom+stream (codec.py packs the
    frame header into the reservation) — the stream is produced exactly
    once, in place; with headroom=0 plain bytes are returned."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    view = memoryview(data).cast("B")
    if view.nbytes == 0:
        return np.empty(headroom, dtype=np.uint8) if headroom else b""
    cap = view.nbytes + view.nbytes // 64 + 4096
    out = np.empty(headroom + cap, dtype=np.uint8)
    rc = lib.tsnp_huff_compress(
        _buffer_address(view), view.nbytes,
        out.ctypes.data + headroom, cap,
    )
    if rc < 0:  # cap is sized so this cannot happen; guard anyway
        return None
    if headroom:
        ret = out[: headroom + rc]
        # a slice view pins the whole raw-sized capacity allocation for
        # as long as the frame lives (through the write queue) — the
        # stripe engine's byte-gate credits the saved bytes as freed, so
        # they must actually free: shrink-copy when compression saved
        # enough to matter
        if out.nbytes - ret.nbytes > (1 << 20):
            ret = ret.copy()
        return ret
    return out[:rc].tobytes()


def huff_decompress(data, raw_len: int):
    """Decompress a huff stream to exactly ``raw_len`` bytes (bytes-like
    uint8 array — no trailing tobytes copy on the restore hot path);
    None when the native lib is unavailable; ValueError on malformed
    input."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    view = memoryview(data).cast("B")
    if raw_len == 0 and view.nbytes == 0:
        return b""
    out = np.empty(raw_len, dtype=np.uint8)
    rc = lib.tsnp_huff_decompress(
        _buffer_address(view), view.nbytes, out.ctypes.data, raw_len
    )
    if rc != raw_len:
        raise ValueError(
            f"corrupt huff stream: decoded {rc} of {raw_len} expected bytes"
        )
    return out


def copy_digest(dst, src) -> Optional[tuple]:
    """memcpy ``src`` into ``dst`` (equal-size buffers) while computing
    the zlib (crc32, adler32) of the bytes in the same cache-blocked
    native pass; None when the lib is unavailable (caller falls back to
    a python copy + separate hashing)."""
    lib = load()
    if lib is None:
        return None
    sview = memoryview(src).cast("B")
    dview = memoryview(dst).cast("B")
    if dview.nbytes != sview.nbytes or dview.readonly:
        return None
    if sview.nbytes == 0:
        return (0, 1)
    out = (ctypes.c_uint32 * 2)()
    lib.tsnp_copy_digest(
        _buffer_address(dview), _buffer_address(sview), sview.nbytes, out
    )
    return (int(out[0]), int(out[1]))
