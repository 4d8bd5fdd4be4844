"""Native fastio extension: build, correctness vs Python fallback, crc32c."""

import os

import numpy as np
import pytest

from torchsnapshot_tpu import _csrc, knobs
from torchsnapshot_tpu.io_types import ReadIO, WriteIO
from torchsnapshot_tpu.storage.fs import FSStoragePlugin


def test_native_lib_builds_and_loads():
    lib = _csrc.load()
    if lib is None:
        pytest.skip("no C++ toolchain")
    assert lib.tsnp_crc32c is not None


def test_crc32c_known_vectors():
    if _csrc.load() is None:
        pytest.skip("no C++ toolchain")
    # RFC 3720 test vector: 32 zero bytes -> 0x8a9136aa
    assert _csrc.crc32c(b"\x00" * 32) == 0x8A9136AA
    # "123456789" -> 0xe3069283
    assert _csrc.crc32c(b"123456789") == 0xE3069283
    assert _csrc.crc32c(b"") == 0


def test_native_vs_python_fs_identical(tmp_path):
    if _csrc.load() is None:
        pytest.skip("no C++ toolchain")
    data = np.random.default_rng(0).bytes(1 << 20)
    with knobs.override_enable_native_ext(True):
        native = FSStoragePlugin(root=str(tmp_path / "n"))
        assert native._lib is not None
        native.sync_write(WriteIO(path="a/b", buf=data))
    with knobs.override_enable_native_ext(False):
        py = FSStoragePlugin(root=str(tmp_path / "p"))
        assert py._lib is None
        py.sync_write(WriteIO(path="a/b", buf=data))
    with open(tmp_path / "n" / "a" / "b", "rb") as f:
        assert f.read() == data
    with open(tmp_path / "p" / "a" / "b", "rb") as f:
        assert f.read() == data
    for plugin in (native, py):
        rio = ReadIO(path="a/b")
        plugin.sync_read(rio)
        assert bytes(rio.buf) == data
        rio = ReadIO(path="a/b", byte_range=[100, 1100])
        plugin.sync_read(rio)
        assert bytes(rio.buf) == data[100:1100]


def test_native_errors_surface(tmp_path):
    if _csrc.load() is None:
        pytest.skip("no C++ toolchain")
    plugin = FSStoragePlugin(root=str(tmp_path))
    with pytest.raises(OSError):
        rio = ReadIO(path="missing/file")
        plugin.sync_read(rio)


def test_fs_verify_writes_roundtrip(tmp_path):
    if _csrc.load() is None:
        pytest.skip("no C++ toolchain")
    from torchsnapshot_tpu import Snapshot, StateDict

    with knobs.override_fs_verify_writes(True):
        data = np.arange(4096, dtype=np.float32)
        snap = Snapshot.take(str(tmp_path / "s"), {"m": StateDict(w=data)})
    out = snap.read_object("0/m/w")
    np.testing.assert_array_equal(out, data)


def test_fs_verify_detects_corruption(tmp_path, monkeypatch):
    if _csrc.load() is None:
        pytest.skip("no C++ toolchain")
    plugin = FSStoragePlugin(root=str(tmp_path))
    assert plugin._lib is not None
    orig_read = plugin._native_read

    def corrupt_read(full, byte_range, into=None):
        out = orig_read(full, byte_range)
        if len(out):
            out[0] ^= 0xFF
        return out

    monkeypatch.setattr(plugin, "_native_read", corrupt_read)
    with knobs.override_fs_verify_writes(True):
        with pytest.raises(OSError, match="crc32c mismatch"):
            plugin.sync_write(WriteIO(path="x", buf=b"payload"))


def test_simd_digests_bit_exact_vs_zlib():
    # the PCLMUL crc32 / AVX2 adler32 fast paths must be bit-compatible
    # with python's zlib across awkward lengths, seeds, and alignments —
    # recorded checksums are a durable on-disk contract
    import random
    import zlib

    if _csrc.load() is None:
        pytest.skip("no C++ toolchain")
    rng = random.Random(11)
    lengths = [0, 1, 7, 15, 16, 63, 64, 65, 255, 4095, 4096, 4097,
               5551, 5552, 5553, 65537, 300_001]
    for n in lengths:
        data = bytes(rng.getrandbits(8) for _ in range(n))
        seed = rng.getrandbits(32)
        assert _csrc.crc32z(data, seed) == zlib.crc32(data, seed) & 0xFFFFFFFF, n
        aseed = (seed % 65521) or 1
        assert _csrc.adler32(data, aseed) == zlib.adler32(data, aseed) & 0xFFFFFFFF, n
        assert _csrc.digest(data) == (
            zlib.crc32(data) & 0xFFFFFFFF,
            zlib.adler32(data) & 0xFFFFFFFF,
        ), n
    # misaligned views of a larger buffer
    base = bytes(rng.getrandbits(8) for _ in range(200_000))
    for off in (1, 3, 7, 15, 31, 63):
        sub = memoryview(base)[off : off + 100_000]
        assert _csrc.crc32z(sub, 0) == zlib.crc32(sub) & 0xFFFFFFFF, off
        assert _csrc.adler32(sub, 1) == zlib.adler32(sub) & 0xFFFFFFFF, off


def test_crc32_fast_falls_back_without_lib(monkeypatch):
    import zlib

    from torchsnapshot_tpu.utils.checksums import crc32_fast

    data = b"fallback-path-check" * 100
    assert crc32_fast(data) == zlib.crc32(data) & 0xFFFFFFFF
    monkeypatch.setattr(_csrc, "crc32z", lambda d, s=0: None)
    assert crc32_fast(data) == zlib.crc32(data) & 0xFFFFFFFF


def test_fused_write_digest_matches_zlib(tmp_path):
    # tsnp_write_file_digest: one pass writes the file AND produces the
    # same (crc32, adler32) zlib would; the file lands byte-identical
    import ctypes
    import zlib

    lib = _csrc.load()
    if lib is None or not hasattr(lib, "tsnp_write_file_digest"):
        pytest.skip("no C++ toolchain")
    payload = np.random.default_rng(5).integers(
        0, 256, 3_000_001, dtype=np.uint8
    ).tobytes()
    out = (ctypes.c_uint32 * 2)()
    dest = str(tmp_path / "obj").encode()
    rc = lib.tsnp_write_file_digest(
        dest,
        _csrc._buffer_address(memoryview(payload)),
        len(payload),
        0,
        out,
    )
    assert rc == 0
    assert open(tmp_path / "obj", "rb").read() == payload
    assert int(out[0]) == zlib.crc32(payload) & 0xFFFFFFFF
    assert int(out[1]) == zlib.adler32(payload) & 0xFFFFFFFF
    # empty payload: digest seeds
    rc = lib.tsnp_write_file_digest(
        str(tmp_path / "empty").encode(), None, 0, 0, out
    )
    assert rc == 0 and int(out[0]) == 0 and int(out[1]) == 1


def test_fs_write_honors_want_digest(tmp_path):
    import asyncio
    import zlib

    from torchsnapshot_tpu.io_types import WriteIO

    p = FSStoragePlugin(root=str(tmp_path))
    if not p.supports_fused_digest:
        pytest.skip("no native fused digest")
    payload = b"fused-digest-check" * 1000

    def run(coro):
        return asyncio.new_event_loop().run_until_complete(coro)

    wio = WriteIO(path="obj", buf=payload, want_digest=True)
    run(p.write(wio))
    assert wio.digests == (
        zlib.crc32(payload) & 0xFFFFFFFF,
        zlib.adler32(payload) & 0xFFFFFFFF,
    )
    # without the request, no digest is computed
    wio2 = WriteIO(path="obj2", buf=payload)
    run(p.write(wio2))
    assert wio2.digests is None
    run(p.close())


def test_fused_digest_checksums_match_pre_write_path(tmp_path):
    # the fs (fused, deferred) and memory (pre-write) paths must record
    # IDENTICAL manifest checksums and object digests for equal content.
    # The fs array is sized ABOVE the slab member cutoff so its write is
    # a direct whole-buffer-sink request — the deferral condition — and
    # a spy asserts the fused path actually engaged (a slab-batched
    # payload would fall through to piece digests and vacuously pass).
    from torchsnapshot_tpu import Snapshot, StateDict

    arrs = {
        "w": np.random.default_rng(0).integers(
            0, 255, 8 * 1024 * 1024, np.uint8  # > SLAB_HOST_MEMBER_MAX
        ),
        "b": np.arange(100, dtype=np.float64),
    }
    fused_writes = []
    orig_write = FSStoragePlugin.write

    async def spy(self, wio):
        await orig_write(self, wio)
        if wio.want_digest:
            fused_writes.append((wio.path, wio.digests))

    FSStoragePlugin.write = spy
    try:
        s_fs = Snapshot.take(str(tmp_path / "fs"), {"app": StateDict(**arrs)})
    finally:
        FSStoragePlugin.write = orig_write
    assert any(
        d is not None for _, d in fused_writes
    ), f"fused digest path never engaged: {fused_writes}"
    s_mem = Snapshot.take("memory://fused/parity", {"app": StateDict(**arrs)})

    def digest_map(snap):
        return {
            loc.rsplit("/", 1)[-1]: tuple(d)
            for loc, d in (snap.metadata.objects or {}).items()
        }

    def crc_map(snap):
        return {
            k: getattr(e, "crc32", None)
            for k, e in snap.metadata.manifest.items()
        }

    assert crc_map(s_fs) == crc_map(s_mem)
    fs_d, mem_d = digest_map(s_fs), digest_map(s_mem)
    assert fs_d and set(fs_d) == set(mem_d)
    assert fs_d == mem_d
    assert s_fs.verify(deep=True).ok


def test_stale_library_from_other_source_is_not_loaded(tmp_path, monkeypatch):
    """Cached libraries are named by a hash of fastio.cpp's content and
    the flag sets: a .so built from OTHER source — however fresh its
    mtime after a copy or an artifact restore — is never a candidate,
    and a successful build deletes it."""
    import shutil

    if _csrc.load() is None:
        pytest.skip("no C++ toolchain")
    here = tmp_path / "csrc"
    here.mkdir()
    shutil.copy(_csrc._SRC, here / "fastio.cpp")
    with open(here / "fastio.cpp", "a") as f:
        f.write("\n// a later edit of the source\n")
    fp = _csrc._cpu_fingerprint()
    old_key = _csrc._build_key()  # the key of the UNEDITED source
    stale = here / f"fastio.{fp or 'portable'}.{old_key}.so"
    stale.write_bytes(b"not a library: loading this would fail loudly")
    os.utime(stale, (2**31, 2**31))  # far newer than the source
    monkeypatch.setattr(_csrc, "_HERE", str(here))
    monkeypatch.setattr(_csrc, "_SRC", str(here / "fastio.cpp"))
    monkeypatch.setattr(_csrc, "_lib", None)
    monkeypatch.setattr(_csrc, "_load_attempted", False)
    new_key = _csrc._build_key()
    assert new_key != old_key
    lib = _csrc.load()
    assert lib is not None and new_key in lib._name
    assert not stale.exists()
    assert lib.tsnp_crc32c is not None


def test_no_toolchain_and_no_cache_warns_once_and_degrades(
    tmp_path, monkeypatch, caplog
):
    """The pure-Python fallback stays for toolchain-less installs, but it
    is announced at WARNING, not taken silently."""
    import logging
    import shutil

    here = tmp_path / "csrc"
    here.mkdir()
    shutil.copy(_csrc._SRC, here / "fastio.cpp")
    monkeypatch.setattr(_csrc, "_HERE", str(here))
    monkeypatch.setattr(_csrc, "_SRC", str(here / "fastio.cpp"))
    monkeypatch.setattr(_csrc, "_lib", None)
    monkeypatch.setattr(_csrc, "_load_attempted", False)
    monkeypatch.setattr(_csrc, "_build", lambda fp, key: None)  # no g++
    with caplog.at_level(logging.WARNING, logger=_csrc.logger.name):
        assert _csrc.load() is None
        assert _csrc.load() is None  # memoized: no second warning
    warnings = [r for r in caplog.records if "fastio library unavailable" in r.message]
    assert len(warnings) == 1
    assert _csrc.crc32c(b"abc") is None  # callers take their python paths


def test_build_key_covers_source_and_flags(monkeypatch):
    key = _csrc._build_key()
    assert key == _csrc._build_key()  # stable across calls and processes
    monkeypatch.setattr(_csrc, "_BASE_FLAGS", ("-O2", "-shared", "-fPIC"))
    assert _csrc._build_key() != key
