"""Native fast-I/O engine (storage/fastio.py): alignment edges, the
fallback ladder, buffer-pool backpressure, digest-fusion equivalence,
and chaos cleanliness on the direct path.

The bitwise contract under test: for ANY size/offset/knob combination,
the engine's bytes and (crc32, adler32) digests are identical to the
pure-Python path's — O_DIRECT, bounce-buffer heads/tails, pwritev
batching and fadvise fallbacks are pure transport details that may
never leak into stored content.
"""

import glob
import os
import threading
import zlib

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, knobs, obs
from torchsnapshot_tpu.io_types import ReadIO, WriteIO
from torchsnapshot_tpu.resilience import reset_breakers
from torchsnapshot_tpu.storage import fastio as fastio_mod
from torchsnapshot_tpu.storage.fs import FSStoragePlugin

_LIB_OK = None


def _engine_available() -> bool:
    global _LIB_OK
    if _LIB_OK is None:
        from torchsnapshot_tpu import _csrc

        lib = _csrc.load()
        _LIB_OK = lib is not None and hasattr(lib, "tsnp_part_pwrite")
    return _LIB_OK


def _direct_supported(root) -> bool:
    return fastio_mod.probe_direct(str(root))


needs_engine = pytest.mark.skipif(
    not _engine_available(), reason="no C++ toolchain / engine symbols"
)


@pytest.fixture(autouse=True)
def _fast_backoff():
    reset_breakers()
    with knobs.override_retry_backoff_cap_s(0.01):
        yield
    reset_breakers()


# the interesting sizes: zero-length, sub-sector, exactly one sector,
# sector+1 (head-only tail), multi-sector with ragged tail, and a span
# big enough to cross several bounce fills when the bounce is shrunk
_EDGE_SIZES = [0, 1, 511, 4096, 4097, 65536 + 17, (1 << 20) + 4095]


@needs_engine
@pytest.mark.parametrize("size", _EDGE_SIZES)
@pytest.mark.parametrize("direct", [False, True])
def test_write_read_roundtrip_alignment_edges(tmp_path, size, direct, monkeypatch):
    if direct and not _direct_supported(tmp_path):
        pytest.skip("filesystem lacks O_DIRECT")
    # force the direct leg onto small spans so sub-sector head/tail
    # bounce handling is exercised at test-sized payloads
    monkeypatch.setattr(fastio_mod, "DIRECT_MIN_BYTES", 1)
    data = np.random.default_rng(size or 1).integers(
        0, 256, size=size, dtype=np.uint8
    )
    with knobs.override_fastio_direct(direct):
        plugin = FSStoragePlugin(root=str(tmp_path / "r"))
    assert plugin._fastio is not None
    assert plugin._fastio.direct == direct
    wio = WriteIO(path="a/b", buf=data, want_digest=True)
    plugin.sync_write(wio)
    assert wio.digests == (
        zlib.crc32(data.tobytes()),
        zlib.adler32(data.tobytes()),
    )
    with open(tmp_path / "r" / "a" / "b", "rb") as f:
        assert f.read() == data.tobytes()
    rio = ReadIO(path="a/b")
    plugin.sync_read(rio)
    assert bytes(memoryview(rio.buf)) == data.tobytes()
    # ranged read at a deliberately unaligned offset
    if size > 600:
        rio = ReadIO(path="a/b", byte_range=[513, size - 7])
        plugin.sync_read(rio)
        assert bytes(memoryview(rio.buf)) == data.tobytes()[513 : size - 7]
    # read-into honors the destination hint through the engine
    dst = np.empty(size, np.uint8)
    rio = ReadIO(path="a/b", into=dst)
    plugin.sync_read(rio)
    assert rio.buf is dst
    assert dst.tobytes() == data.tobytes()


@needs_engine
@pytest.mark.parametrize("part_size", [4096 - 7, 65536 + 13])
def test_striped_parts_unaligned_offsets_fuse_digests(
    tmp_path, part_size, monkeypatch
):
    """Part sizes that are NOT sector multiples give every later part
    an unaligned offset — heads/tails go through the bounce while the
    aligned body goes direct, and each part's fused digest must equal
    zlib's."""
    direct = _direct_supported(tmp_path)
    if direct:
        monkeypatch.setattr(fastio_mod, "DIRECT_MIN_BYTES", 1)
    total = part_size * 4 + 1234
    data = np.random.default_rng(7).integers(0, 256, size=total, dtype=np.uint8)
    with knobs.override_fastio_direct(direct):
        plugin = FSStoragePlugin(root=str(tmp_path / "r"))

    async def go():
        handle = await plugin.begin_striped_write("obj", total)
        assert handle.supports_fused_digest
        lo = 0
        idx = 0
        try:
            while lo < total:
                hi = min(lo + part_size, total)
                d = await handle.write_part(
                    idx, lo, data[lo:hi], want_digest=True
                )
                assert d == (
                    zlib.crc32(data[lo:hi].tobytes()),
                    zlib.adler32(data[lo:hi].tobytes()),
                )
                lo = hi
                idx += 1
        except BaseException:
            await handle.abort()
            raise
        await handle.complete()

    import asyncio

    asyncio.new_event_loop().run_until_complete(go())
    with open(tmp_path / "r" / "obj", "rb") as f:
        assert f.read() == data.tobytes()
    # every direct-path bounce buffer went back to the pool (no pool
    # exists at all on a buffered-only engine)
    pool = plugin._fastio._pool
    assert plugin._fastio.pool_free_count() == (pool.count if pool else 0)
    assert (pool is not None) == direct


@needs_engine
def test_direct_unsupported_degrades_to_buffered_with_dontneed(
    tmp_path, monkeypatch
):
    """FASTIO_DIRECT on a filesystem without O_DIRECT: the engine takes
    the fadvise(DONTNEED) rung — bytes and digests stay identical, and
    the fallback is visible in storage.fastio.dontneed_reads."""
    monkeypatch.setattr(fastio_mod, "probe_direct", lambda root: False)
    from torchsnapshot_tpu import _csrc

    with knobs.override_fastio_direct(True):
        plugin = FSStoragePlugin(root=str(tmp_path / "r"))
    eng = plugin._fastio
    assert eng is not None and not eng.direct and eng.dontneed
    data = np.random.default_rng(3).integers(0, 256, size=123457, dtype=np.uint8)
    wio = WriteIO(path="x", buf=data, want_digest=True)
    plugin.sync_write(wio)
    assert wio.digests == (
        zlib.crc32(data.tobytes()),
        zlib.adler32(data.tobytes()),
    )
    c0 = obs.counter(obs.FASTIO_DONTNEED_READS).value
    rio = ReadIO(path="x")
    plugin.sync_read(rio)
    assert bytes(memoryview(rio.buf)) == data.tobytes()
    assert obs.counter(obs.FASTIO_DONTNEED_READS).value == c0 + 1


@needs_engine
def test_probe_direct_readonly_rung(tmp_path, monkeypatch):
    """A root that refuses file CREATION (read-only serving mount) must
    still probe direct-capable via O_RDONLY|O_DIRECT on an existing
    payload file — the restore side is the bypass's primary customer."""
    if not _direct_supported(tmp_path):
        pytest.skip("filesystem lacks O_DIRECT")
    (tmp_path / "payload").write_bytes(b"x" * 8192)
    real_open = os.open

    def deny_create(path, flags, *a, **k):
        if flags & os.O_CREAT:
            raise OSError(30, "Read-only file system", path)
        return real_open(path, flags, *a, **k)

    monkeypatch.setattr(os, "open", deny_create)
    assert fastio_mod.probe_direct(str(tmp_path))
    monkeypatch.undo()
    # an empty read-only root has nothing to probe against: unsupported
    empty = tmp_path / "empty"
    empty.mkdir()
    assert fastio_mod._probe_direct_readonly(str(empty), os.O_DIRECT) is False


@needs_engine
def test_fastio_zero_knob_and_probe_failure_keep_pre_engine_paths(tmp_path):
    """FASTIO=0 (and no native lib at all) must yield the pre-engine
    path — same bytes, plugin still functional.  (A lib WITHOUT the
    engine symbols cannot be loaded any more: cached libraries are named
    by source hash — tests/test_native_ext.py.)"""
    data = np.random.default_rng(5).integers(0, 256, size=70001, dtype=np.uint8)
    with knobs.override_fastio(False):
        plugin = FSStoragePlugin(root=str(tmp_path / "off"))
    assert plugin._fastio is None
    plugin.sync_write(WriteIO(path="x", buf=data))
    rio = ReadIO(path="x")
    plugin.sync_read(rio)
    assert bytes(memoryview(rio.buf)) == data.tobytes()
    assert fastio_mod.create_engine(None, str(tmp_path)) is None


@needs_engine
def test_pool_exhaustion_backpressures_and_recovers(tmp_path, monkeypatch):
    """A 1-buffer pool under concurrent direct part writes: later parts
    WAIT for a bounce buffer instead of allocating (pool_waits counts
    them), everything completes bitwise-correct, and the pool is whole
    afterwards."""
    if not _direct_supported(tmp_path):
        pytest.skip("filesystem lacks O_DIRECT")
    monkeypatch.setattr(fastio_mod, "DIRECT_MIN_BYTES", 1)
    with knobs.override_fastio_direct(True):
        plugin = FSStoragePlugin(root=str(tmp_path / "r"))
    eng = plugin._fastio
    assert eng is not None and eng.direct
    eng._pool = fastio_mod._AlignedPool(1, buf_bytes=1 << 20)  # ONE buffer
    assert eng._pool.count == 1
    part = 2 << 20
    nparts = 6
    data = np.random.default_rng(9).integers(
        0, 256, size=part * nparts, dtype=np.uint8
    )
    full = str(tmp_path / "r" / "obj")
    fd = os.open(full, os.O_RDWR | os.O_CREAT, 0o644)
    os.ftruncate(fd, part * nparts)
    fdd = eng.open_direct(full)
    assert fdd >= 0
    w0 = obs.counter(obs.FASTIO_POOL_WAITS).value
    errors = []

    def worker(i):
        try:
            d = eng.pwrite_part(
                fd, fdd, i * part, data[i * part : (i + 1) * part], True
            )
            assert d == (
                zlib.crc32(data[i * part : (i + 1) * part].tobytes()),
                zlib.adler32(data[i * part : (i + 1) * part].tobytes()),
            )
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(nparts)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    os.close(fdd)
    os.close(fd)
    assert errors == []
    with open(full, "rb") as f:
        assert f.read() == data.tobytes()
    assert obs.counter(obs.FASTIO_POOL_WAITS).value > w0
    assert eng.pool_free_count() == 1


# --------------------------------------------------- whole-stack legs


def _tree(rng):
    # the corruption-fuzz payload shape: mixed dtypes/sizes + scalars
    dtypes = [np.float32, np.float64, np.int32, np.uint8, np.int16]
    t = {}
    for i in range(int(rng.integers(2, 6))):
        dt = dtypes[int(rng.integers(len(dtypes)))]
        n = int(rng.integers(1, 60000))
        t[f"w{i}"] = (rng.standard_normal(n) * 8).astype(dt)
    t["s"] = "a string leaf"
    t["k"] = int(rng.integers(0, 1000))
    return t


def _payload_bytes(root):
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f in (".snapshot_metadata", ".snapshot_obsrecord"):
                continue
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


@needs_engine
@pytest.mark.parametrize("striped", [False, True])
@pytest.mark.parametrize("codec", ["raw", "zlib"])
def test_snapshot_bitwise_equivalence_vs_pure_python(
    tmp_path, striped, codec, monkeypatch
):
    """The acceptance contract: engine on (direct where supported) and
    the pure-Python path produce byte-identical snapshots — across
    striped/unstriped × codec-on/off — and each restores the other's
    bytes bitwise."""
    direct = _direct_supported(tmp_path)
    if direct:
        monkeypatch.setattr(fastio_mod, "DIRECT_MIN_BYTES", 1)
    rng = np.random.default_rng(42)
    tree = _tree(rng)
    import contextlib

    ctx = contextlib.ExitStack()
    ctx.enter_context(knobs.override_codec(codec))
    if striped:
        ctx.enter_context(knobs.override_stripe_part_size_bytes(1 << 16))
        ctx.enter_context(knobs.override_stripe_min_object_size_bytes(1 << 16))
    with ctx:
        with knobs.override_fastio_direct(direct):
            snap_native = Snapshot.take(
                str(tmp_path / "native"), {"m": StateDict(**tree)}
            )
        with knobs.override_enable_native_ext(False):
            snap_py = Snapshot.take(
                str(tmp_path / "py"), {"m": StateDict(**tree)}
            )
        assert snap_native.verify(deep=True).ok
        assert snap_py.verify(deep=True).ok
        native_files = _payload_bytes(str(tmp_path / "native"))
        py_files = _payload_bytes(str(tmp_path / "py"))
        assert native_files == py_files
        # both directions: each path restores the OTHER's snapshot
        for src, reader_native in (("py", True), ("native", False)):
            dest = {
                "m": StateDict(
                    **{
                        k: np.zeros_like(v)
                        if isinstance(v, np.ndarray)
                        else type(v)()
                        for k, v in tree.items()
                    }
                )
            }
            with knobs.override_enable_native_ext(reader_native):
                Snapshot(str(tmp_path / src)).restore(dest)
            for k, v in tree.items():
                if isinstance(v, np.ndarray):
                    np.testing.assert_array_equal(dest["m"][k], v)
                else:
                    assert dest["m"][k] == v


@needs_engine
def test_scheduler_defers_digest_to_fused_striped_parts(tmp_path):
    """Stripe-eligible fs writes defer checksum work to the write: the
    folded per-part fused digests land in the manifest and deep-verify
    agrees with them."""
    f0 = obs.counter(obs.FASTIO_FUSED_DIGESTS).value
    with knobs.override_stripe_part_size_bytes(1 << 16), (
        knobs.override_stripe_min_object_size_bytes(1 << 16)
    ), knobs.override_disable_batching(True):
        data = np.arange(1 << 16, dtype=np.float32)  # 256KB -> 4 parts
        snap = Snapshot.take(
            str(tmp_path / "s"), {"m": StateDict(w=data)}
        )
    assert obs.counter(obs.FASTIO_FUSED_DIGESTS).value - f0 >= 4
    assert snap.verify(deep=True).ok
    out = snap.read_object("0/m/w")
    np.testing.assert_array_equal(np.asarray(out), data)


# ------------------------------------------------------------ chaos


@needs_engine
def test_chaos_fatal_part_fault_on_direct_path_aborts_clean(
    tmp_path, monkeypatch
):
    """A fatal mid-stripe failure on the DIRECT path: abort leaves zero
    .tsnp-tmp-* files, no commit marker, and every pool buffer back —
    exactly as clean as the buffered path."""
    direct = _direct_supported(tmp_path)
    if direct:
        monkeypatch.setattr(fastio_mod, "DIRECT_MIN_BYTES", 1)
    path = str(tmp_path / "s")
    state = {"app": StateDict(w=np.arange(1 << 17, dtype=np.float32))}
    with knobs.override_stripe_part_size_bytes(1 << 16), (
        knobs.override_stripe_min_object_size_bytes(1 << 16)
    ), knobs.override_fastio_direct(direct), (
        knobs.override_failpoints("storage.fs.part.write=io")
    ):
        with pytest.raises(OSError):
            Snapshot.take(path, state)
    assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))
    assert (
        glob.glob(os.path.join(path, "**", "*tsnp-tmp*"), recursive=True)
        == []
    )
    reset_breakers()
    # the same plugin config takes cleanly once the fault clears, and
    # the pool is whole (no orphaned bounce buffers from the abort)
    with knobs.override_stripe_part_size_bytes(1 << 16), (
        knobs.override_stripe_min_object_size_bytes(1 << 16)
    ), knobs.override_fastio_direct(direct):
        Snapshot.take(path, state)
        plugin = FSStoragePlugin(root=path)
        eng = plugin._fastio
        assert eng is not None
        assert eng.pool_free_count() == (
            eng._pool.count if eng._pool is not None else 0
        )
    dest = {"app": StateDict(w=np.zeros(1 << 17, np.float32))}
    Snapshot(path).restore(dest)
    np.testing.assert_array_equal(
        dest["app"]["w"], np.arange(1 << 17, dtype=np.float32)
    )


@needs_engine
def test_chaos_transient_part_faults_on_engine_path_retry_clean(tmp_path):
    """Transient EINTR on engine part writes: parts retry independently
    and the take commits with fused digests that deep-verify."""
    path = str(tmp_path / "s")
    r0 = obs.counter(obs.RESILIENCE_RETRIES).value
    with knobs.override_stripe_part_size_bytes(1 << 16), (
        knobs.override_stripe_min_object_size_bytes(1 << 16)
    ), knobs.override_failpoints("storage.fs.part.write=eintr:1:3"):
        snap = Snapshot.take(
            path, {"app": StateDict(w=np.arange(1 << 17, dtype=np.float32))}
        )
    assert obs.counter(obs.RESILIENCE_RETRIES).value - r0 >= 3
    assert snap.verify(deep=True).ok
    assert (
        glob.glob(os.path.join(path, "**", "*tsnp-tmp*"), recursive=True)
        == []
    )
