"""Shared test helpers: array-aware state-dict equality, random arrays for
every supported dtype, multi-process launchers.

Reference: torchsnapshot/test_utils.py:52-270 (tensor-aware equality incl.
ShardedTensor, rand_tensor over all dtypes, run_with_pet multi-process
decorators).  The multi-process launcher here spawns plain subprocesses
coordinated through FileCoordinator — no torch-elastic needed.
"""

from __future__ import annotations

import math
import os
import subprocess
import sys
import textwrap
from typing import Any, Dict, List, Optional

import numpy as np


def _is_jax_array(x: Any) -> bool:
    mod = type(x).__module__.split(".")[0]
    if mod not in ("jax", "jaxlib"):
        return False
    import jax

    return isinstance(x, jax.Array)


def _to_numpy(x: Any) -> np.ndarray:
    if _is_jax_array(x):
        return np.asarray(x)
    if type(x).__module__.split(".")[0] == "torch":
        return x.detach().cpu().numpy()
    return np.asarray(x)


def assert_state_dict_eq(a: Any, b: Any, path: str = "") -> None:
    """Structural equality with array-aware leaf comparison (reference
    check_state_dict_eq, test_utils.py:52-126)."""
    arr_a = isinstance(a, np.ndarray) or _is_jax_array(a) or hasattr(a, "detach")
    arr_b = isinstance(b, np.ndarray) or _is_jax_array(b) or hasattr(b, "detach")
    if arr_a or arr_b:
        na, nb = _to_numpy(a), _to_numpy(b)
        assert na.shape == nb.shape, f"{path}: shape {na.shape} != {nb.shape}"
        assert na.dtype == nb.dtype, f"{path}: dtype {na.dtype} != {nb.dtype}"
        if na.dtype.kind == "f" or na.dtype.name in ("bfloat16",):
            np.testing.assert_allclose(
                na.astype(np.float64),
                nb.astype(np.float64),
                rtol=1e-6,
                atol=0,
                err_msg=path,
            )
        else:
            np.testing.assert_array_equal(na, nb, err_msg=path)
        return
    if isinstance(a, dict) and isinstance(b, dict):
        assert a.keys() == b.keys(), f"{path}: keys {a.keys()} != {b.keys()}"
        for k in a:
            assert_state_dict_eq(a[k], b[k], f"{path}/{k}")
        return
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        assert len(a) == len(b), f"{path}: len {len(a)} != {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            assert_state_dict_eq(x, y, f"{path}/{i}")
        return
    if isinstance(a, float) and isinstance(b, float):
        assert math.isclose(a, b, rel_tol=1e-9) or (
            math.isnan(a) and math.isnan(b)
        ), f"{path}: {a} != {b}"
        return
    assert a == b, f"{path}: {a!r} != {b!r}"


def rand_array(shape, dtype, seed: int = 0) -> np.ndarray:
    """Random array valid for any supported dtype (reference rand_tensor,
    test_utils.py:129-169)."""
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    if dt.kind in "fc" or dt.name.startswith(("bfloat", "float8")):
        return rng.standard_normal(shape).astype(dtype)
    if dt.kind == "b":
        return rng.integers(0, 2, size=shape).astype(bool)
    if dt.kind in "iu":
        info = np.iinfo(dt)
        lo, hi = max(info.min, -1000), min(info.max, 1000)
        return rng.integers(lo, hi + 1, size=shape).astype(dtype)
    raise ValueError(f"unsupported dtype {dtype}")


def run_multiprocess(
    tmp_path,
    world_size: int,
    body: str,
    repo_root: Optional[str] = None,
    timeout_s: float = 120.0,
) -> List[str]:
    """Run ``body`` (python source with rank/world/coord/snap_dir bound) in
    ``world_size`` coordinated subprocesses (reference run_with_pet,
    test_utils.py:232-270)."""
    repo = repo_root or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(str(tmp_path), "mp_worker.py")
    with open(script, "w") as f:
        f.write(
            textwrap.dedent(
                f"""
                import sys
                sys.path.insert(0, {repo!r})
                import numpy as np
                from torchsnapshot_tpu import FileCoordinator, Snapshot, StateDict

                rank = int(sys.argv[1])
                world = int(sys.argv[2])
                coord = FileCoordinator({os.path.join(str(tmp_path), "kv")!r}, rank, world)
                snap_dir = {os.path.join(str(tmp_path), "snap")!r}
                """
            )
            + textwrap.dedent(body)
        )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    procs = [
        subprocess.Popen(
            [sys.executable, script, str(r), str(world_size)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for r in range(world_size)
    ]
    outs = [p.communicate(timeout=timeout_s)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise AssertionError(f"worker {r} failed:\n{out}")
    return outs
