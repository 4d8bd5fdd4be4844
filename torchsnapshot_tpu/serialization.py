"""Zero-copy array (de)serialization + a safe object codec.

TPU-native analogue of the reference's serialization layer
(torchsnapshot/serialization.py:34-477), redesigned for JAX host buffers:

- Arrays are stored as raw little-endian C-contiguous bytes; dtype/shape live
  in the manifest.  ``memoryview`` over the numpy buffer gives zero-copy
  writes (reference ``tensor_as_memoryview``, serialization.py:177-251).
- bfloat16 (and fp8 variants) are first-class via ``ml_dtypes`` — no
  UntypedStorage tricks needed: numpy handles the buffer protocol for these
  extension dtypes directly.
- The object fallback is NOT pickle-by-default: we use a self-describing
  msgpack codec covering containers/primitives/numpy scalars+arrays
  (reference uses torch.save/pickle, serialization.py:268-275).  Arbitrary
  objects fall back to pickle only when the ``ALLOW_PICKLE_OBJECTS`` knob is
  on; payloads are tagged so readers can refuse pickles.
"""

from __future__ import annotations

import io
import pickle
from typing import Any, Tuple

import numpy as np

import ml_dtypes

_ML_DTYPES = {
    "bfloat16": np.dtype(ml_dtypes.bfloat16),
    "float8_e4m3fn": np.dtype(ml_dtypes.float8_e4m3fn),
    "float8_e5m2": np.dtype(ml_dtypes.float8_e5m2),
    "float8_e4m3fnuz": np.dtype(ml_dtypes.float8_e4m3fnuz),
    "int4": np.dtype(ml_dtypes.int4),
    "uint4": np.dtype(ml_dtypes.uint4),
}

from . import knobs

# Serializer tags recorded in the manifest (reference Serializer enum,
# serialization.py:155-159).
BUFFER_PROTOCOL = "buffer_protocol"
SAFE_OBJECT = "safe_object"  # msgpack codec
PICKLE_OBJECT = "pickle"

# dtype-string table (reference serialization.py:34-110). We use numpy dtype
# names directly; ml_dtypes extension dtypes keep their canonical names.
_STD_DTYPES = [
    "float16", "float32", "float64",
    "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64",
    "bool", "complex64", "complex128",
]


_DTYPE_NAME_CACHE: dict = {}


def dtype_to_string(dtype: Any) -> str:
    # memoized on the np.dtype object: the linear _ML_DTYPES scan per
    # array leaf is measurable planning cost at tens of thousands of
    # leaves (the async_take blocked window is exactly this planning)
    dt = np.dtype(dtype)
    cached = _DTYPE_NAME_CACHE.get(dt)
    if cached is not None:
        return cached
    name = None
    for mname, mdt in _ML_DTYPES.items():
        if dt == mdt:
            name = mname
            break
    if name is None:
        if dt.name not in _STD_DTYPES:
            raise ValueError(f"unsupported dtype for serialization: {dtype!r}")
        name = dt.name
    _DTYPE_NAME_CACHE[dt] = name
    return name


def string_to_dtype(s: str) -> np.dtype:
    if s in _ML_DTYPES:
        return _ML_DTYPES[s]
    if s in _STD_DTYPES:
        return np.dtype(s)
    raise ValueError(f"unknown serialized dtype: {s!r}")


def array_as_memoryview(arr: np.ndarray) -> memoryview:
    """Zero-copy view of a host array's bytes (contiguous + little-endian
    normalized; copies only when layout requires it)."""
    if arr.dtype.byteorder == ">":  # big-endian: normalize (rare)
        arr = arr.astype(arr.dtype.newbyteorder("<"))
    if not arr.flags["C_CONTIGUOUS"]:
        arr = np.ascontiguousarray(arr)
    try:
        return memoryview(arr).cast("B")
    except (ValueError, TypeError):
        # Extension dtypes (bfloat16, fp8, ...) don't implement the buffer
        # protocol; a uint8 view of the same memory does.
        return memoryview(arr.reshape(-1).view(np.uint8))


def array_from_buffer(buf: Any, dtype_str: str, shape: Tuple[int, ...]) -> np.ndarray:
    """Zero-copy reconstruction from raw bytes (reference
    tensor_from_memoryview, serialization.py:254-265). The returned array
    shares memory with ``buf`` and is read-only if ``buf`` is."""
    dtype = string_to_dtype(dtype_str)
    arr = np.frombuffer(buf, dtype=dtype)
    return arr.reshape(shape)


def serialized_size_bytes(shape, dtype: Any) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n * np.dtype(dtype).itemsize


_UINT_FOR_ITEMSIZE = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def fast_copyto(dst: np.ndarray, src: np.ndarray) -> None:
    """memcpy-speed ``np.copyto``. Same-dtype copies of extension dtypes
    (ml_dtypes bfloat16/fp8) otherwise go through numpy's per-element cast
    machinery at ~0.5 GB/s; routing them through a bit-identical
    unsigned-integer view runs at memory bandwidth (~10x), including for
    strided views. Falls back to casting ``np.copyto`` for dtype changes."""
    if (
        dst.dtype == src.dtype
        and not dst.dtype.hasobject
        and dst.dtype.itemsize in _UINT_FOR_ITEMSIZE
    ):
        u = _UINT_FOR_ITEMSIZE[dst.dtype.itemsize]
        np.copyto(dst.view(u), src.view(u))
    else:
        np.copyto(dst, src, casting="unsafe")


def fast_copy(src: np.ndarray) -> np.ndarray:
    """``np.copy`` at memory bandwidth (same extension-dtype caveat as
    :func:`fast_copyto`; ``np.copy`` of an ml_dtypes array is ~0.2 GB/s)."""
    dst = np.empty(src.shape, dtype=src.dtype)
    fast_copyto(dst, src)
    return dst


# ---------------------------------------------------------------------------
# Safe object codec (msgpack with extension types). Covers: None, bool, int,
# float, str, bytes, list, tuple, set, frozenset, dict (any hashable encodable
# keys), complex, numpy scalars and ndarrays (incl. bfloat16 via raw-bytes ext).
# ---------------------------------------------------------------------------

import msgpack

_EXT_TUPLE = 1
_EXT_SET = 2
_EXT_FROZENSET = 3
_EXT_COMPLEX = 4
_EXT_NDARRAY = 5
_EXT_NPSCALAR = 6
_EXT_BIGINT = 7
_EXT_DICT_NONSTR = 8  # dict with non-string keys: list of [k, v] pairs


def _default(obj: Any) -> Any:
    if isinstance(obj, tuple):
        return msgpack.ExtType(_EXT_TUPLE, _pack(list(obj)))
    if isinstance(obj, set):
        return msgpack.ExtType(_EXT_SET, _pack(sorted(obj, key=repr)))
    if isinstance(obj, frozenset):
        return msgpack.ExtType(_EXT_FROZENSET, _pack(sorted(obj, key=repr)))
    if isinstance(obj, complex):
        return msgpack.ExtType(_EXT_COMPLEX, _pack([obj.real, obj.imag]))
    if isinstance(obj, np.ndarray):
        payload = _pack(
            [dtype_to_string(obj.dtype), list(obj.shape),
             array_as_memoryview(obj).tobytes()]
        )
        return msgpack.ExtType(_EXT_NDARRAY, payload)
    if isinstance(obj, np.generic):
        arr = np.asarray(obj)
        payload = _pack([dtype_to_string(arr.dtype), arr.tobytes()])
        return msgpack.ExtType(_EXT_NPSCALAR, payload)
    if isinstance(obj, int):
        # out-of-range ints reach here (msgpack caps at 64-bit)
        return msgpack.ExtType(_EXT_BIGINT, str(obj).encode())
    if isinstance(obj, dict):
        # only reached when strict_map_key rejects: encode as pair list
        return msgpack.ExtType(_EXT_DICT_NONSTR, _pack([[k, v] for k, v in obj.items()]))
    raise TypeError(f"unencodable object of type {type(obj)}")


def _ext_hook(code: int, data: bytes) -> Any:
    if code == _EXT_TUPLE:
        return tuple(_unpack(data))
    if code == _EXT_SET:
        return set(_unpack(data))
    if code == _EXT_FROZENSET:
        return frozenset(_unpack(data))
    if code == _EXT_COMPLEX:
        re, im = _unpack(data)
        return complex(re, im)
    if code == _EXT_NDARRAY:
        dtype_str, shape, raw = _unpack(data)
        return array_from_buffer(raw, dtype_str, tuple(shape)).copy()
    if code == _EXT_NPSCALAR:
        dtype_str, raw = _unpack(data)
        return np.frombuffer(raw, dtype=string_to_dtype(dtype_str))[0]
    if code == _EXT_BIGINT:
        return int(data.decode())
    if code == _EXT_DICT_NONSTR:
        return {k: v for k, v in _unpack(data)}
    return msgpack.ExtType(code, data)


def _pack(obj: Any) -> bytes:
    return msgpack.packb(obj, default=_default, strict_types=True, use_bin_type=True)


def _unpack(data: Any) -> Any:
    return msgpack.unpackb(
        data, ext_hook=_ext_hook, raw=False, strict_map_key=False
    )


def serialize_object(obj: Any) -> Tuple[bytes, str]:
    """Serialize an arbitrary object; returns (payload, serializer_tag).

    Tries the safe msgpack codec first; falls back to pickle when the knob
    allows (reference object path uses torch.save unconditionally,
    io_preparers/object.py:69-82)."""
    try:
        return _pack(obj), SAFE_OBJECT
    except (TypeError, ValueError, OverflowError):
        pass
    if not knobs.is_pickle_allowed():
        raise TypeError(
            f"object of type {type(obj)} is not encodable by the safe codec "
            "and ALLOW_PICKLE_OBJECTS is disabled"
        )
    buf = io.BytesIO()
    pickle.dump(obj, buf, protocol=pickle.HIGHEST_PROTOCOL)
    return buf.getvalue(), PICKLE_OBJECT


def deserialize_object(payload: Any, serializer: str) -> Any:
    if serializer == SAFE_OBJECT:
        return _unpack(bytes(payload))
    if serializer == PICKLE_OBJECT:
        if not knobs.is_pickle_allowed():
            raise RuntimeError(
                "snapshot contains a pickle payload but ALLOW_PICKLE_OBJECTS "
                "is disabled"
            )
        return pickle.loads(bytes(payload))
    raise ValueError(f"unknown object serializer: {serializer!r}")
