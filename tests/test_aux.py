"""Auxiliary subsystems: events, RSS profiler, tricks, host offload,
test utils (SURVEY.md §2 rows 21-26)."""

import numpy as np
import pytest

from torchsnapshot_tpu import (
    Event,
    Snapshot,
    StateDict,
    register_event_handler,
    unregister_event_handler,
)
from torchsnapshot_tpu.rss_profiler import measure_rss_deltas
from torchsnapshot_tpu.test_utils import assert_state_dict_eq, rand_array


def test_events_bracket_take_restore(tmp_path):
    events = []
    handler = events.append
    register_event_handler(handler)
    try:
        Snapshot.take(str(tmp_path / "s"), {"app": StateDict(x=1)})
        Snapshot(str(tmp_path / "s")).restore({"app": StateDict(x=0)})
    finally:
        unregister_event_handler(handler)
    names = [e.name for e in events]
    assert "take" in names and "restore" in names
    for e in events:
        assert e.metadata["is_success"] is True
        assert "duration_s" in e.metadata and "unique_id" in e.metadata


def test_event_failure_marked(tmp_path):
    events = []
    register_event_handler(events.append)
    try:
        with pytest.raises(FileNotFoundError):
            Snapshot(str(tmp_path / "missing")).restore({"app": StateDict(x=0)})
    finally:
        unregister_event_handler(events.append)
    restores = [e for e in events if e.name == "restore"]
    assert restores and restores[0].metadata["is_success"] is False


def test_rss_profiler_measures_allocation():
    deltas = []
    with measure_rss_deltas(deltas, interval_s=0.01):
        blob = np.ones(50 * 1024 * 1024 // 8)  # ~50MB
        blob += 1
    assert max(deltas) > 20 * 1024 * 1024
    del blob


def test_assert_state_dict_eq():
    a = {"x": np.arange(4.0), "y": [1, (2, "s")], "z": 1.5}
    b = {"x": np.arange(4.0), "y": [1, (2, "s")], "z": 1.5}
    assert_state_dict_eq(a, b)
    b["x"] = np.arange(4.0) + 1e-3
    with pytest.raises(AssertionError):
        assert_state_dict_eq(a, b)


@pytest.mark.parametrize(
    "dtype", ["float32", "bfloat16", "int8", "uint16", "bool"]
)
def test_rand_array_dtypes(dtype):
    import ml_dtypes

    dt = (
        np.dtype(ml_dtypes.bfloat16) if dtype == "bfloat16" else np.dtype(dtype)
    )
    arr = rand_array((8, 3), dt, seed=1)
    assert arr.shape == (8, 3) and arr.dtype == dt


def test_torch_ddp_adapter(tmp_path):
    torch = pytest.importorskip("torch")
    from torchsnapshot_tpu.tricks import TorchModuleAdapter

    model = torch.nn.Linear(4, 2)
    wrapped = torch.nn.Sequential()  # simulate DDP wrapper naming
    ddp_like = torch.nn.Module()
    ddp_like.module = model

    adapter = TorchModuleAdapter(ddp_like)
    sd = adapter.state_dict()
    assert all(not k.startswith("module.") for k in sd)

    Snapshot.take(str(tmp_path / "s"), {"model": adapter})
    model2 = torch.nn.Linear(4, 2)
    ddp_like2 = torch.nn.Module()
    ddp_like2.module = model2
    Snapshot(str(tmp_path / "s")).restore({"model": TorchModuleAdapter(ddp_like2)})
    for p1, p2 in zip(model.parameters(), model2.parameters()):
        assert torch.equal(p1, p2)


def test_torch_module_roundtrip_plain(tmp_path):
    torch = pytest.importorskip("torch")
    from torchsnapshot_tpu.tricks import TorchModuleAdapter, TorchOptimizerAdapter

    model = torch.nn.Sequential(torch.nn.Linear(8, 4), torch.nn.Linear(4, 2))
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    out = model(torch.ones(2, 8)).sum()
    out.backward()
    opt.step()

    Snapshot.take(
        str(tmp_path / "s"),
        {"model": TorchModuleAdapter(model), "opt": TorchOptimizerAdapter(opt)},
    )
    model2 = torch.nn.Sequential(torch.nn.Linear(8, 4), torch.nn.Linear(4, 2))
    opt2 = torch.optim.Adam(model2.parameters(), lr=1e-3)
    Snapshot(str(tmp_path / "s")).restore(
        {"model": TorchModuleAdapter(model2), "opt": TorchOptimizerAdapter(opt2)}
    )
    for p1, p2 in zip(model.parameters(), model2.parameters()):
        assert torch.equal(p1, p2)
    assert opt.state_dict()["param_groups"] == opt2.state_dict()["param_groups"]


def test_host_offload_fallbacks():
    from torchsnapshot_tpu import host_offload

    import jax.numpy as jnp

    arr = jnp.ones(8)
    # CPU backend: helpers must degrade gracefully
    out = host_offload.offload_to_host(arr)
    back = host_offload.to_device(out)
    np.testing.assert_array_equal(np.asarray(back), np.ones(8))


def test_torch_tensor_chunked_save(tmp_path):
    torch = pytest.importorskip("torch")
    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.manifest import ChunkedArrayEntry

    with knobs.override_max_chunk_size_bytes(256):
        t = torch.arange(0, 256, dtype=torch.float32).reshape(16, 16)  # 1KB
        snap = Snapshot.take(str(tmp_path / "s"), {"m": StateDict(w=t)})
        entry = snap.get_manifest()["0/m/w"]
        assert isinstance(entry, ChunkedArrayEntry)
        dest = StateDict(w=torch.zeros(16, 16))
        snap.restore({"m": dest})
        assert torch.equal(dest["w"], t)


def test_orbax_interop_roundtrip(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    import jax.numpy as jnp

    from torchsnapshot_tpu.tricks.orbax_interop import (
        export_to_orbax,
        import_from_orbax,
        migrate_orbax_to_snapshot,
        migrate_snapshot_to_orbax,
    )

    tree = {
        "params": {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(4)},
        "step": np.int64(7),
    }
    export_to_orbax(str(tmp_path / "orbax_ckpt"), tree)
    back = import_from_orbax(str(tmp_path / "orbax_ckpt"))
    np.testing.assert_array_equal(np.asarray(back["params"]["w"]), np.asarray(tree["params"]["w"]))

    migrate_orbax_to_snapshot(str(tmp_path / "orbax_ckpt"), str(tmp_path / "snap"))
    snap_w = Snapshot(str(tmp_path / "snap")).read_object("0/state/params/w")
    np.testing.assert_array_equal(np.asarray(snap_w), np.asarray(tree["params"]["w"]))

    migrate_snapshot_to_orbax(str(tmp_path / "snap"), str(tmp_path / "orbax2"))
    back2 = import_from_orbax(str(tmp_path / "orbax2"))
    np.testing.assert_array_equal(np.asarray(back2["params"]["b"]), np.ones(4))


def test_pallas_auto_is_off_on_cpu():
    """'auto' must never turn interpret-mode pallas on for real CPU runs
    (orders of magnitude slower than the XLA path); the probe-compile
    path is TPU-only.  Tests opt in via override_pallas_attention."""
    import jax

    from torchsnapshot_tpu import knobs

    assert jax.default_backend() == "cpu"
    with knobs.override_pallas_attention("auto"):
        assert knobs.use_pallas_attention() is False
    with knobs.override_pallas_attention("1"):
        assert knobs.use_pallas_attention() is True


def test_pallas_attention_auto_depends_on_backend_only(monkeypatch):
    """auto = on for the tpu backend, off everywhere else; nothing is
    probe-compiled to decide it."""
    import jax

    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.ops import flash_attention as fa

    def boom(*a, **k):
        raise AssertionError("auto must not run a kernel to decide")

    monkeypatch.setattr(fa, "flash_attention", boom)
    for backend, want in (("tpu", True), ("cpu", False), ("gpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert knobs.use_pallas_attention() is want, backend
        with knobs.override_pallas_attention("1"):
            assert knobs.use_pallas_attention() is True
        with knobs.override_pallas_attention("0"):
            assert knobs.use_pallas_attention() is False


def test_refused_kernel_raises_instead_of_falling_back(monkeypatch):
    """A kernel the compiler refuses must surface: ring attention with
    the pallas knob on has no XLA fallback behind it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.ops import flash_attention as fa
    from torchsnapshot_tpu.parallel.ring_attention import ring_attention

    def refused(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(fa, "flash_attention_partials", refused)
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    x = jax.device_put(
        jnp.ones((1, 16, 1, 8), jnp.float32),
        NamedSharding(mesh, P(None, "sp", None, None)),
    )
    with knobs.override_pallas_attention("1"):
        with pytest.raises(RuntimeError, match="Mosaic failed"):
            ring_attention(x, x, x, mesh, axis_name="sp")


def test_cli_convert_round_trip(tmp_path, capsys):
    """`convert` migrates native -> reference format -> native, with
    leaf values surviving both hops."""
    import numpy as np

    from torchsnapshot_tpu import PyTreeState, Snapshot
    from torchsnapshot_tpu.__main__ import main as cli

    native = str(tmp_path / "native")
    Snapshot.take(
        native,
        {"m": PyTreeState({"w": np.arange(16, dtype=np.float32), "n": 5})},
    )
    ref = str(tmp_path / "ref")
    assert cli(["convert", "--to-reference", native, ref]) == 0
    capsys.readouterr()
    import json as _json

    meta = _json.loads((tmp_path / "ref" / ".snapshot_metadata").read_text())
    assert meta["manifest"]["0/m/w"]["dtype"] == "torch.float32"

    back = str(tmp_path / "back")
    assert cli(["convert", ref, back]) == 0
    got = Snapshot(back).read_object("0/m/w")
    np.testing.assert_array_equal(got, np.arange(16, dtype=np.float32))
    assert Snapshot(back).read_object("0/m/n") == 5


def test_cli_convert_refuses_multirank_without_rank(tmp_path, capsys):
    """A multi-rank snapshot converted without --rank would silently
    drop other ranks' private state; the CLI refuses instead."""
    import json as _json

    from torchsnapshot_tpu.__main__ import main as cli

    ref = tmp_path / "ref"
    ref.mkdir()
    (ref / ".snapshot_metadata").write_text(
        _json.dumps({
            "version": "0.1.0", "world_size": 4,
            "manifest": {
                "0/app": {"type": "dict", "keys": ["n"]},
                "0/app/n": {
                    "type": "int", "serialized_value": "1",
                    "replicated": False, "readable": None,
                },
            },
        })
    )
    assert cli(["convert", str(ref), str(tmp_path / "out")]) == 1
    assert "world_size=4" in capsys.readouterr().err
    # out-of-range rank would take the elastic grown-world view and drop
    # per-rank state: refused (off-by-one is the easy operator mistake)
    assert cli(["convert", "--rank", "4", str(ref), str(tmp_path / "out")]) == 1
    assert "out of range" in capsys.readouterr().err
    # explicit in-range --rank converts deliberately
    assert cli(["convert", "--rank", "0", str(ref), str(tmp_path / "out")]) == 0


def test_cli_convert_unconvertible_dtype_is_clean_error(tmp_path, capsys):
    import ml_dtypes
    import numpy as np

    from torchsnapshot_tpu import PyTreeState, Snapshot
    from torchsnapshot_tpu.__main__ import main as cli

    native = str(tmp_path / "native")
    Snapshot.take(
        native,
        {"m": PyTreeState({"q": np.zeros(2, dtype=ml_dtypes.float8_e4m3fn)})},
    )
    rc = cli(["convert", "--to-reference", native, str(tmp_path / "ref")])
    assert rc == 1
    assert "error:" in capsys.readouterr().err  # one line, no traceback


def test_cli_ls_verify_steps_delete(tmp_path, capsys):
    """Operator CLI: ls/manifest/verify/steps/delete round-trip."""
    import numpy as np

    from torchsnapshot_tpu import SnapshotManager, StateDict
    from torchsnapshot_tpu.__main__ import main as cli

    mgr = SnapshotManager(str(tmp_path))
    mgr.save(
        {"app": StateDict(w=np.arange(256, dtype=np.float32), step=3)},
        step=1,
    )
    snap_path = mgr.path_for_step(1)

    assert cli(["ls", snap_path]) == 0
    out = capsys.readouterr().out
    assert "app/w" in out and "float32[256]" in out

    assert cli(["manifest", snap_path]) == 0
    md = capsys.readouterr().out
    assert '"manifest"' in md and '"objects"' in md

    assert cli(["verify", "--deep", snap_path]) == 0
    assert capsys.readouterr().out.startswith("OK")

    assert cli(["steps", str(tmp_path)]) == 0
    assert capsys.readouterr().out.splitlines()[0].startswith("1\t")

    # corrupt -> verify fails with exit 1
    import os

    # damage one payload byte
    man_entry = next(
        e for e in mgr.snapshot(1).get_manifest().values()
        if getattr(e, "crc32", None) is not None
    )
    p = os.path.join(snap_path, man_entry.location)
    data = bytearray(open(p, "rb").read())
    data[(man_entry.byte_range or [0])[0]] ^= 0xFF
    open(p, "wb").write(bytes(data))
    assert cli(["verify", "--deep", snap_path]) == 1
    assert "FAILED" in capsys.readouterr().out

    assert cli(["delete", snap_path]) == 2  # refused without --yes
    capsys.readouterr()
    assert cli(["delete", snap_path, "--yes"]) == 0
    assert not os.path.exists(snap_path)

    assert cli(["ls", snap_path]) == 1  # gone -> clean error, not traceback


def test_device_unpack_auto_depends_on_backend_only(monkeypatch):
    """auto device-unpack is on for every accelerator backend and off on
    cpu (a host-memory device gains nothing from the one-DMA unpack) —
    whatever JAX_PLATFORMS names; explicit "1"/"0" still force it (the
    CPU test suite relies on "1")."""
    import jax

    from torchsnapshot_tpu import knobs

    for platforms in ("", "tpu", "proxy,tpu", "cpu"):
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert knobs.device_unpack_enabled() is True, platforms
        with knobs.override_device_unpack("0"):
            assert knobs.device_unpack_enabled() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert knobs.device_unpack_enabled() is False, platforms
        with knobs.override_device_unpack("1"):
            assert knobs.device_unpack_enabled() is True  # forced: tests
