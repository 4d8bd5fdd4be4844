"""Device-side slab packing: bitcast + concatenate as ONE compiled XLA op,
then a single device→host transfer.

TPU-native analogue of the reference's GPU batched stager, which packs
small GPU tensors into one GPU buffer to amortize DtoH launch overhead
(reference batcher.py:104-162).  On TPU the win is the same: one big DMA
instead of many small ones, and the pack itself runs at HBM bandwidth.
XLA caches the compiled pack per shape-tuple, so steady-state checkpoints
(same model every time) pay compilation once.

The slab is made of unsigned WORDS as wide as its members' elements, and
the host reinterprets the words as bytes for free.  Every member of a
slab has the same element width (the batcher groups by ``packed_width``)
and converts with a same-width bitcast, which moves nothing.  A slab of
2-byte members then leaves for the host as pairs in 4-byte words
(``_pairs_as_words``): the copy of narrower words is the slow one.  The
byte-granular form this replaces (``bitcast_convert_type(x, uint8)``
through a ``uint8[n, itemsize]`` temporary) is tiled with its 4-byte
minor dimension padded to a full lane row on a TPU: one 64 MiB float32
member asked for 8.25 GB of program scratch on a v5e and could not be
loaded beside an 8 GB train state (chip_smoke.py, PERF.md).  There is no
other form: a slab of mixed widths or unaligned members is not packed or
unpacked on the device at all (``slab_word_bytes``).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, List, Optional

import numpy as np

from .. import obs, staging_arena


def packed_width(dtype) -> int:
    """Bytes per element as a slab carries it: bool serializes as one
    byte, complex as its (real, imag) component pair."""
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return 1
    if np.issubdtype(dt, np.complexfloating):
        return dt.itemsize // 2
    return dt.itemsize


def _word(width: int):
    return np.dtype(f"uint{8 * width}")


def slab_word_bytes(members) -> Optional[int]:
    """Bytes per word of the slab holding ``members`` ((byte_offset,
    dtype_str, shape), ...) as the device sees it, or None when the
    device programs do not apply and the host path runs BY CHOICE:

    - members of more than one element width, or at a byte offset their
      width does not divide (slabs laid out before the batcher grouped
      by width) — the only device form is the same-width bitcast;
    - 8-byte elements with ``jax_enable_x64`` off: ``device_put`` would
      narrow the uint64 words to uint32 and the bitcast target to 32
      bits, and the same-width bitcast would then succeed on garbage."""
    import jax

    widths = {packed_width(dtype_str) for _, dtype_str, _ in members}
    if len(widths) != 1:
        return None
    width = widths.pop()
    if width not in (1, 2, 4) and not (
        width == 8 and jax.config.jax_enable_x64
    ):
        return None
    if any(off % width for off, _, _ in members):
        return None
    return width


def _pack(arrays: List[Any]):
    import jax.numpy as jnp
    from jax import lax

    widths = {packed_width(a.dtype) for a in arrays}
    if len(widths) != 1:
        raise ValueError(
            f"device pack takes members of one element width, got {widths}"
        )
    word = _word(widths.pop())
    parts = []
    for a in arrays:
        flat = a.reshape(-1)
        if flat.dtype == jnp.bool_:
            flat = flat.astype(jnp.uint8)  # bool serializes as one byte
        elif jnp.issubdtype(flat.dtype, jnp.complexfloating):
            # complex bytes are interleaved (real, imag) component pairs
            flat = jnp.stack([flat.real, flat.imag], axis=-1).reshape(-1)
        parts.append(lax.bitcast_convert_type(flat, word))  # same width
    packed = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    return _pairs_as_words(packed) if word == np.uint16 else packed


_PAIR_LANES = 256


def _pairs_as_words(x):
    """uint16[n] → uint32[ceil(n / 256) * 128], word i = x[2i] | x[2i+1] << 16:
    the same bytes in a little-endian host's memory, zeros after the n-th
    element.  A 2-byte slab travels so because the device→host copy of any
    array of 1- or 2-byte elements ran at 0.68 GB/s on a TPU v5e, whatever
    its shape, where 4-byte words ran at 3.2 (one 135 MB slab: 195–215 ms
    against 59–71 with the 17 ms this costs the device; PERF.md, PR 36).
    Two transposes put the even and the odd elements on rows of their own;
    the plain form (``bitcast_convert_type(x.reshape(-1, 2), uint32)``) has
    a minor dimension of 2, which a TPU pads to a lane row: 17 GB of
    scratch for that slab."""
    import jax.numpy as jnp
    from jax import lax

    n = x.shape[0]
    rows = -(-n // _PAIR_LANES)
    if rows * _PAIR_LANES != n:
        x = jnp.concatenate([x, jnp.zeros((rows * _PAIR_LANES - n,), x.dtype)])
    by_lane = x.reshape(rows, _PAIR_LANES).T  # row l: lane l of every row
    even, odd = (
        lax.slice(by_lane, (first, 0), by_lane.shape, (2, 1)).astype(jnp.uint32)
        for first in (0, 1)
    )
    return (even | (odd << 16)).T.reshape(-1)


_pack_jit = None
_PACK_JIT_LOCK = threading.Lock()

# benchmark/diagnostic counters: how often the compiled device-side
# pack/unpack COMPLETED (evidence that the one-DMA path engaged on
# hardware — failed attempts that fall back must not count); lock-
# guarded because packs run concurrently from executor threads
CALL_COUNTS = {"pack": 0, "unpack": 0, "tile_update": 0}
_COUNT_LOCK = threading.Lock()


def _count(kind: str) -> None:
    with _COUNT_LOCK:
        CALL_COUNTS[kind] += 1


# host→device transfers of the device programs' ARGUMENTS (a slab's offset
# vector; the numpy scalars a cut is handed), as against the slabs and
# pieces themselves.  Made with the module, so a process that restored on
# the host path reads 0, not an absence.
_ARG_PUTS = obs.counter(obs.DEVICE_UNPACK_ARG_PUTS)


def pack_arrays_to_host(arrays: List[Any]) -> np.ndarray:
    """Pack device arrays of ONE element width into one uint8 host buffer
    (C-order bytes of each array, concatenated).  Raises on mixed widths
    and on dtypes XLA can't bitcast — callers fall back to per-array
    staging."""
    global _pack_jit
    import jax

    # executor threads pack concurrently; the jit wrapper itself is
    # cheap to build, so every touch stays under the lock (the traced
    # COMPILE below happens outside it, per arg signature, inside jax)
    with _PACK_JIT_LOCK:
        if _pack_jit is None:
            _pack_jit = jax.jit(_pack)
        pack_fn = _pack_jit
    nbytes = sum(a.nbytes for a in arrays)
    packed = pack_fn(arrays)
    # the slab's host array is made by whichever of the two calls comes to
    # it first: both inside the arena
    with staging_arena.allocating():
        try:
            packed.copy_to_host_async()
        except Exception as e:
            obs.swallowed_exception("device_pack.copy_to_host_async", e)
        # materializes (async failures surface here); the host reads the
        # words as the bytes they are
        with obs.span("d2h/copy", bytes=packed.nbytes):
            # a 2-byte slab comes padded to whole rows of word pairs
            out = np.asarray(packed).view(np.uint8)[:nbytes]
    _count("pack")
    obs.counter(f"device_pack.bytes_w{packed_width(arrays[0].dtype)}").inc(nbytes)
    return out


# ------------------------------------------------------------- unpack

# a split program has at most this many outputs (a slab of more members,
# thousands of tiny leaves, sends a vector a chunk); lengths are padded to
# a power of two, so a process compiles at most seven of them
_SPLIT_MAX = 64


# members of one signature a call of their unpack program, at most
_GROUP_MAX = 32


@functools.lru_cache(maxsize=None)
def _jitted_split(n):
    """``int32[n]`` → n ``int32[]`` arrays on the vector's device: the
    form ``_jitted_unpack``'s programs take their runtime offsets in."""
    import jax

    return jax.jit(lambda vec: tuple(vec[i] for i in range(n)))


def _scalars_on_device(values, device):
    """The host ints ``values`` as device-resident ``int32[]`` scalars, for
    ONE host→device transfer (a vector, split on the device) where handing
    them to the programs one by one costs a transfer each; with them, the
    transfers made (counted in ``device_unpack.arg_puts``).  The caller has
    validated the values: nothing on the device can."""
    import jax

    out: List[Any] = []
    puts = 0
    for lo in range(0, len(values), _SPLIT_MAX):
        chunk = values[lo : lo + _SPLIT_MAX]
        vec = np.zeros(1 << (len(chunk) - 1).bit_length(), np.int32)
        vec[: len(chunk)] = chunk
        on_device = jax.device_put(vec, device)
        puts += 1
        out.extend(_jitted_split(vec.size)(on_device)[: len(chunk)])
    _ARG_PUTS.inc(puts)
    return out, puts


@functools.lru_cache(maxsize=256)
def _jitted_unpack(dtype_str, shape, out_dtype_str):
    """One small program per distinct member SIGNATURE (dtype/shape/cast),
    taking the slab as words of the member's element width and a RUNTIME
    word offset — NOT one monolithic program per slab layout.

    The monolithic form (every member sliced at a static offset inside a
    single jit) compiled superlinearly in member count on the TPU
    backend: 4 × 16MB members ≈ 14s, 16 members > 10min — measured on
    hardware; it was the entire 151s restore gap vs orbax in a round-5
    head-to-head capture.  Per-signature kernels make compile cost
    O(distinct shapes) — a transformer's repeated layer shapes share one
    executable — and the runtime offset (``lax.dynamic_slice``) keeps
    byte positions out of the cache key, so evolving slab layouts reuse
    the same executables instead of pinning one per layout.

    The program takes k offsets and returns k members of its signature:
    a CALL is what costs a consume worker (0.6–1 ms each with four workers
    in the runtime at once, whatever it carries: PERF.md §5, PR 37), so
    all of a slab's members of one signature go in one call (the first
    ``_GROUP_MAX``, then the next).  jit keeps an executable an arity and
    a slab length, which the slab's layout decides: a state restored
    again, or one whose layers repeat, compiles as many as a call a member
    did (124 against 122 for 1,053 leaves), and compile time grows with k
    by a tenth of a program a member.  The cache key here stays the
    signature.

    Each offset is an ``int32[]`` that already LIVES ON THE DEVICE
    (``_scalars_on_device``): handed a numpy scalar, jit transfers it host
    to device inside the call, one transfer a member.  Every caller passes
    device scalars: jit keeps a second cache entry (a second compile) for
    a host argument beside a committed device one, so no call site may mix
    the two forms."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    import ml_dtypes  # noqa: F401 — registers bfloat16/fp8 names

    dt = np.dtype(dtype_str)
    out_dt = None if out_dtype_str is None else np.dtype(out_dtype_str)
    n = int(np.prod(shape)) if shape else 1

    def unpack_one(slab, off):
        # one word per element: every bitcast is a pure reinterpretation
        if dt == np.bool_:
            arr = lax.dynamic_slice(slab, (off,), (n,)).astype(jnp.bool_)
        elif np.issubdtype(dt, np.complexfloating):
            half = np.dtype(np.float32 if dt == np.complex64 else np.float64)
            piece = lax.dynamic_slice(slab, (off,), (n * 2,))
            comps = lax.bitcast_convert_type(piece, half).reshape(n, 2)
            arr = lax.complex(comps[:, 0], comps[:, 1])
        else:
            piece = lax.dynamic_slice(slab, (off,), (n,))
            arr = lax.bitcast_convert_type(piece, jnp.dtype(dt))
        arr = arr.reshape(shape)
        if out_dt is not None and out_dt != dt:
            arr = arr.astype(jnp.dtype(out_dt))
        return arr

    def unpack(slab, *offs):
        return tuple(unpack_one(slab, off) for off in offs)

    return jax.jit(unpack)


@functools.lru_cache(maxsize=256)
def _compiled_tile_update(acc_n, acc_dtype_str, tile_n, tile_dtype_str,
                          device):
    """AOT-compiled donated flat-accumulator tile write:
    acc[off:off+tile_n] = tile (cast to the accumulator dtype on
    device).  One small executable per (accumulator, tile) SIGNATURE —
    budgeted device reads touch two signatures per array (full tiles +
    the remainder tile), reused across arrays of the same shape class.
    donate_argnums=0 makes the chain in-place: device peak stays at
    ~1x the target plus one tile.

    AOT (``.lower().compile()``) rather than lazy jit so callers can
    force the compile onto the PLAN-TIME caller thread
    (``warm_tile_updates``): the per-tile dispatch runs on the
    scheduler's executor, where a lazy first-call compile would stall
    every tile queued behind it and a compile error would arrive after
    the template was already consumed."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import SingleDeviceSharding

    import ml_dtypes  # noqa: F401 — registers bfloat16/fp8 names

    acc_dt = np.dtype(acc_dtype_str)
    tile_dt = np.dtype(tile_dtype_str)
    cast = acc_dt != tile_dt

    def upd(acc, tile, off):
        if cast:
            tile = tile.astype(jnp.dtype(acc_dt))
        return lax.dynamic_update_slice(acc, tile, (off,))

    sharding = SingleDeviceSharding(device)
    return (
        jax.jit(upd, donate_argnums=0)
        .lower(
            jax.ShapeDtypeStruct((acc_n,), acc_dt, sharding=sharding),
            jax.ShapeDtypeStruct((tile_n,), tile_dt, sharding=sharding),
            jax.ShapeDtypeStruct((), np.int32),
        )
        .compile()
    )


def warm_tile_updates(acc_n, acc_dtype, tile_sigs, device) -> None:
    """Compile every (tile_n, tile_dtype) signature the read plan will
    dispatch — called at plan time on the CALLER thread (see
    _compiled_tile_update's thread-safety note)."""
    for tile_n, tile_dtype in tile_sigs:
        _compiled_tile_update(
            int(acc_n), str(np.dtype(acc_dtype)),
            int(tile_n), str(np.dtype(tile_dtype)), device,
        )


def tile_update_device(acc, tile_np: np.ndarray, off: int):
    """Write one host tile into a flat device accumulator, donating the
    previous accumulator handle."""
    import jax

    device = list(acc.sharding.device_set)[0]
    fn = _compiled_tile_update(
        int(acc.shape[0]),
        str(np.dtype(acc.dtype)),
        int(tile_np.shape[0]),
        str(np.dtype(tile_np.dtype)),
        device,
    )
    with obs.span("h2d/put", bytes=tile_np.nbytes):
        tile = jax.device_put(tile_np, device)
    out = fn(acc, tile, np.int32(off))
    _count("tile_update")
    return out


@functools.lru_cache(maxsize=256)
def _jitted_cut(shape, dtype_str, sizes):
    """One program per (wide shape, dtype, box sizes); the box's start is
    a RUNTIME argument, so the halves (quarters, ...) of one saved shard
    share an executable: a resharding restore of a transformer compiles
    one per distinct column-sharded shape.

    The starts are handed over as numpy scalars, which jit transfers host
    to device inside the call (counted in ``device_unpack.arg_puts``, one
    a scalar).  The form the slab's offsets take (one vector a piece,
    split on the device) was measured here and taken out again: 2.97 →
    3.20 s a restore of the four-chip cell, every restore of the window
    slower (PERF.md §6, PR 37).  A worker waits for every piece, and the
    vector's transfer is an ordinary one, queued behind what the other
    workers have on the links, where a scalar inside a call is not:
    that is the likely reason, and it is not measured."""
    import jax
    from jax import lax

    def cut(wide, *starts):
        return lax.dynamic_slice(wide, starts, sizes)

    return jax.jit(cut)


def cut_box_on_device(wide, starts, sizes):
    """Cut the box ``(starts, sizes)`` out of the device array ``wide`` on
    its own device: the resharding restore's carving program
    (preparers/sharded.py), for a target shard that is a strided region
    of the saved shard it lies in.  A slice moves words: bitwise.  The
    caller owns ``wide`` and deletes it once the cut is done."""
    shape = tuple(int(d) for d in wide.shape)
    starts = tuple(int(s) for s in starts)
    sizes = tuple(int(s) for s in sizes)
    # dynamic_slice CLAMPS an out-of-bounds start instead of raising: a
    # corrupt plan must fail here, not deliver a shifted region
    if len(starts) != len(shape) or any(
        st < 0 or st + sz > dim for st, sz, dim in zip(starts, sizes, shape)
    ):
        raise ValueError(f"box {starts}+{sizes} outside array of {shape}")
    fn = _jitted_cut(shape, str(np.dtype(wide.dtype)), sizes)
    out = fn(wide, *(np.int32(s) for s in starts))
    _count("unpack")  # after dispatch succeeded — fallbacks must not count
    _ARG_PUTS.inc(len(starts))
    return out


def unpack_slab_to_device(buf, members, out_dtypes, device) -> List[Any]:
    """ONE H2D transfer + compiled slice/bitcast programs, one call for
    all of a slab's members of one signature, turn a host slab into all of
    its member device arrays — the restore-side mirror of
    ``pack_arrays_to_host`` (amortizes per-transfer latency exactly the
    way the write side amortizes DtoH launches).

    The members' word offsets follow the slab as ONE ``int32`` vector, are
    split into scalars on the device, and every member program is called
    with device-resident arguments only (``_scalars_on_device``): handed
    over as numpy scalars they cost a host→device transfer a member.  The
    calls are few because each costs the worker a share of a millisecond
    while the other workers are in the runtime too: a call a member made a
    thousand-leaf restore wait for its calls, not for the link (PERF.md
    §5, PR 37).  The offsets are checked on the host, before anything is
    put.

    ``members``: ((byte_offset, dtype_str, shape), ...) within ``buf``;
    ``out_dtypes``: per-member template dtype (cast on device) or None.
    Raises ValueError for a slab ``slab_word_bytes`` declines — callers
    ask it first and take the host path by choice.
    """
    import jax

    u8 = np.frombuffer(buf, np.uint8)
    if u8.nbytes > np.iinfo(np.int32).max:
        # dynamic_slice offsets ride int32; slabs are budget/threshold
        # bounded far below 2GB, so this is a corrupt-plan guard, not a
        # size limit — the caller falls back to the host path
        raise ValueError(f"slab too large for device unpack: {u8.nbytes}")
    word_bytes = slab_word_bytes(members)
    if word_bytes is None or u8.nbytes % word_bytes:
        raise ValueError(
            "slab is not one element width at aligned offsets (or 8-byte "
            "elements without jax_enable_x64): no device unpack"
        )
    for off, dtype_str, shape in members:
        # dynamic_slice CLAMPS an out-of-bounds start instead of raising
        # (static slicing failed loudly here) — a corrupt plan must hit
        # the host path, not silently deliver bytes from a shifted region
        dt = np.dtype(dtype_str)
        n = int(np.prod(shape)) if shape else 1
        nbytes = n if dt == np.bool_ else n * dt.itemsize
        if off < 0 or off + nbytes > u8.nbytes:
            raise ValueError(
                f"member [{off}, {off + nbytes}) outside slab of {u8.nbytes}"
            )
    by_signature: dict = {}  # signature -> its members' indices, in order
    for i, ((_, dtype_str, shape), out_dt) in enumerate(zip(members, out_dtypes)):
        signature = (
            # canonicalize unconditionally: alias spellings ('<f4' vs
            # 'float32') must share one cache entry, not two compiles
            str(np.dtype(dtype_str)),
            tuple(shape),
            None if out_dt is None else str(np.dtype(out_dt)),
        )
        by_signature.setdefault(signature, []).append(i)
    calls = []  # (program, the members it returns)
    for signature, idx in by_signature.items():
        fn = _jitted_unpack(*signature)
        calls.extend(
            (fn, idx[lo : lo + _GROUP_MAX])
            for lo in range(0, len(idx), _GROUP_MAX)
        )
    with obs.span("h2d/put", bytes=u8.nbytes):
        slab = jax.device_put(u8.view(_word(word_bytes)), device)
    # the member programs (compiled lazily on this executor thread at
    # first use) compile and dispatch while the slab's DMA runs
    with obs.span(
        "unpack/dispatch", members=len(members), width=word_bytes
    ) as sp:
        offs, arg_puts = _scalars_on_device(
            [off // word_bytes for off, _, _ in members], device
        )
        out: List[Any] = [None] * len(members)
        call_ns = []
        for fn, idx in calls:
            t0 = time.perf_counter_ns()
            arrays = fn(slab, *(offs[i] for i in idx))
            call_ns.append(time.perf_counter_ns() - t0)
            for i, arr in zip(idx, arrays):
                out[i] = arr
        if sp is not None:
            sp.attrs["arg_puts"] = arg_puts
            sp.attrs["calls"] = len(calls)
            # the worker's wait for the slab's transfer sits in ONE of the
            # calls (not always the first: the runtime takes a few ahead);
            # the span less the longest is what the calls themselves cost
            sp.attrs["first_call_ns"] = call_ns[0]
            sp.attrs["longest_call_ns"] = max(call_ns)
    _count("unpack")  # after dispatch succeeded — fallbacks must not count
    obs.counter(f"device_unpack.bytes_w{word_bytes}").inc(u8.nbytes)
    return out
