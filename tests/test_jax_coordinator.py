"""JaxCoordinator over a REAL two-process jax.distributed service.

The production control plane on TPU pods is the jax.distributed
coordination-service KV (SURVEY §2.2: control-plane gathers + commit
barrier over the coordination client, reference pg_wrapper.py +
dist_store.py roles).  This spawns two actual processes that
jax.distributed.initialize() against a local coordinator, then drives a
full distributed take/restore and an async_take commit through
JaxCoordinator — no FileCoordinator fallback involved.
"""

import os
import socket
import subprocess
import sys
import tempfile

import pytest

_WORKER = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.environ["TSNP_REPO"])
import jax
jax.distributed.initialize(
    coordinator_address=os.environ["TSNP_COORD"],
    num_processes=2,
    process_id=int(os.environ["TSNP_RANK"]),
)
import numpy as np
from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.coordination import JaxCoordinator

coord = JaxCoordinator()
assert coord.world_size == 2
rank = coord.rank

# KV + gather + barrier primitives
coord.kv_set(f"hello_{rank}", f"from_{rank}")
assert coord.kv_get(f"hello_{1 - rank}", timeout_s=30) == f"from_{1 - rank}"
gathered = coord.all_gather_object({"rank": rank, "x": rank * 10})
assert [g["x"] for g in gathered] == [0, 10]
assert coord.broadcast_object("root-val" if rank == 0 else None) == "root-val"

root = os.environ["TSNP_ROOT"]

# distributed take: per-rank state + replicated state written once
state = StateDict(
    mine=np.full(64, rank, dtype=np.int32),
    shared=np.arange(32, dtype=np.float64),
)
snap = Snapshot.take(
    os.path.join(root, "sync"), {"app": state},
    replicated=["app/shared"], coordinator=coord,
)

# restore on both ranks; each sees its own per-rank state
dest = StateDict(mine=np.zeros(64, np.int32), shared=np.zeros(32))
Snapshot(os.path.join(root, "sync"), coordinator=coord).restore(
    {"app": dest}
)
np.testing.assert_array_equal(dest["mine"], np.full(64, rank))
np.testing.assert_array_equal(dest["shared"], np.arange(32))

# async take: background commit barrier over the coordination KV only
pending = Snapshot.async_take(
    os.path.join(root, "async"), {"app": state}, coordinator=coord
)
snap2 = pending.wait()
assert os.path.exists(os.path.join(root, "async", ".snapshot_metadata"))

# async take with ONE rank failing storage: both ranks must see the
# failure via the KV commit barrier, and no metadata may be written
import torchsnapshot_tpu.storage as storage_mod
import torchsnapshot_tpu.snapshot as snapshot_mod
from torchsnapshot_tpu.storage.fs import FSStoragePlugin

class Faulty(FSStoragePlugin):
    async def write(self, write_io):
        raise RuntimeError("injected failure on rank 1")

orig_factory = storage_mod.url_to_storage_plugin
def factory(url, **kw):
    path = url.split("://", 1)[-1] if "://" in url else url
    return Faulty(path) if rank == 1 else FSStoragePlugin(path)

storage_mod.url_to_storage_plugin = factory
snapshot_mod.url_to_storage_plugin = factory
failed = False
try:
    Snapshot.async_take(
        os.path.join(root, "faulty"), {"app": state}, coordinator=coord
    ).wait()
except Exception:
    failed = True
assert failed, "peer failure must propagate to every rank"
assert not os.path.exists(
    os.path.join(root, "faulty", ".snapshot_metadata")
)
storage_mod.url_to_storage_plugin = orig_factory
snapshot_mod.url_to_storage_plugin = orig_factory
print(f"rank {rank} OK")
"""


def test_two_process_jax_distributed_control_plane(tmp_path):
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    env_base = {
        **os.environ,
        "TSNP_REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "TSNP_COORD": f"localhost:{port}",
        "TSNP_ROOT": str(tmp_path),
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": "",
        "XLA_FLAGS": "",  # fresh single-device CPU per process
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER],
            env={**env_base, "TSNP_RANK": str(r)},
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for r in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=150)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"rank {r} OK" in out
