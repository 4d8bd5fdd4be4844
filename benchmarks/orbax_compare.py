"""Head-to-head vs orbax.checkpoint — the incumbent TPU checkpointer.

The reference's baseline is torch.save on A100s (benchmarks/ddp/
README.md:9-24); the comparison a TPU user actually makes is against
orbax.  Same payload, three metrics each:

- ``blocked_s``   — wall time the train loop is blocked by an async save
  (ours: ``Snapshot.async_take`` returns after one batched
  device→pinned_host DMA dispatch; orbax: ``AsyncCheckpointer.save``
  returns after its own staging copy).
- ``save_s``      — wall time to a durable, committed checkpoint
  (ours: ``pending.wait()``; orbax: ``wait_until_finished``).
- ``restore_s``   — wall time to restore into device arrays
  (ours: templates + ``snap.restore`` with donation; orbax:
  ``restore`` with ``restore_args`` carrying the target sharding).

Honest-comparison notes: both sides write to local fs on the same box,
both get one warm-up round to exclude first-call compile/setup costs,
and the SAME freshly-initialized payload objects are used.  Orbax is
configured with its defaults (what a user gets), ours likewise.

Run:  python benchmarks/orbax_compare.py --gb 1
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _mk_params(n_arrays: int, elems: int):
    """INCOMPRESSIBLE payload: random bits bitcast to bf16.

    Orbax's default tensorstore/zarr path compresses; a synthetic ramp
    (arange) compresses ~1000x and turns the 'save' into a no-op (a
    0.25GB ramp measured 268KB on disk).  Real checkpoint payloads are
    near-incompressible trained weights, so random bits are the honest
    stand-in — both frameworks then move the same number of bytes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        bits = jax.random.bits(key, (elems,), dtype=jnp.uint16)
        return jax.lax.bitcast_convert_type(bits, jnp.bfloat16)

    keys = jax.random.split(jax.random.PRNGKey(0), n_arrays)
    params = {f"layer{i:02d}": make(keys[i]) for i in range(n_arrays)}
    jax.block_until_ready(params)
    return params


def bench_ours(params, root: str) -> dict:
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import PyTreeState, Snapshot

    # warm-up: compile caches, thread pools, first-transfer setup
    warm = jnp.ones((1024,), jnp.bfloat16)
    Snapshot.async_take(
        os.path.join(root, "warm"), {"m": PyTreeState({"w": warm})}
    ).wait()

    t0 = time.perf_counter()
    pending = Snapshot.async_take(
        os.path.join(root, "snap"), {"m": PyTreeState(dict(params))}
    )
    blocked_s = time.perf_counter() - t0
    snap = pending.wait()
    save_s = time.perf_counter() - t0

    # drain the save's writeback debt so restore measures read
    # performance, not contention with our own dirty pages (untimed:
    # save_s above is the API wall time a user observes)
    os.sync()
    templates = {k: jnp.zeros_like(v) for k, v in params.items()}
    dest = PyTreeState(templates)
    t0 = time.perf_counter()
    snap.restore({"m": dest})
    jax.block_until_ready(dest.tree)
    restore_s = time.perf_counter() - t0
    _check(params, dest.tree)
    return {
        "blocked_s": round(blocked_s, 4),
        "save_s": round(save_s, 4),
        "restore_s": round(restore_s, 4),
    }


def bench_orbax(params, root: str) -> dict:
    import jax
    import jax.numpy as jnp
    import orbax.checkpoint as ocp

    ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
    # warm-up
    ckptr.save(
        os.path.join(root, "warm"), args=ocp.args.StandardSave({"w": jnp.ones((1024,), jnp.bfloat16)})
    )
    ckptr.wait_until_finished()

    path = os.path.join(root, "snap")
    t0 = time.perf_counter()
    ckptr.save(path, args=ocp.args.StandardSave(dict(params)))
    blocked_s = time.perf_counter() - t0
    ckptr.wait_until_finished()
    save_s = time.perf_counter() - t0

    os.sync()  # symmetric with bench_ours: restore measures reads only
    # restore with explicit target templates (sharding-aware), orbax's
    # recommended restore path
    templates = {k: jnp.zeros_like(v) for k, v in params.items()}
    t0 = time.perf_counter()
    restored = ckptr.restore(path, args=ocp.args.StandardRestore(templates))
    jax.block_until_ready(restored)
    restore_s = time.perf_counter() - t0
    _check(params, restored)
    ckptr.close()
    return {
        "blocked_s": round(blocked_s, 4),
        "save_s": round(save_s, 4),
        "restore_s": round(restore_s, 4),
    }


def _check(params, restored) -> None:
    import numpy as np

    for k in params:
        a = np.asarray(params[k][:64]).view(np.uint16)
        b = np.asarray(restored[k][:64]).view(np.uint16)
        if not np.array_equal(a, b):
            raise RuntimeError(f"round-trip mismatch on {k}")


def run(gb: float, work_dir: str | None = None) -> dict:
    import jax

    n_arrays = 16
    elems = max(1024, int(gb * 1e9 / 2 / n_arrays))
    elems -= elems % 1024
    params = _mk_params(n_arrays, elems)
    payload_gb = n_arrays * elems * 2 / 1e9

    base = work_dir or tempfile.mkdtemp(prefix="orbax_cmp_")
    result = {
        "payload_gb": round(payload_gb, 3),
        "platform": jax.devices()[0].platform,
    }
    try:
        result["torchsnapshot_tpu"] = bench_ours(
            params, os.path.join(base, "ours")
        )
        # each bench syncs after its own save, so neither framework
        # pays the other's dirty-page debt
        result["orbax"] = bench_orbax(params, os.path.join(base, "orbax"))
    finally:
        if work_dir is None:
            shutil.rmtree(base, ignore_errors=True)
    ours, orbx = result["torchsnapshot_tpu"], result["orbax"]
    result["speedup"] = {
        m: round(orbx[m] / max(ours[m], 1e-9), 2)
        for m in ("blocked_s", "save_s", "restore_s")
    }
    return result


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--gb", type=float, default=1.0)
    parser.add_argument("--work-dir", default=None)
    args = parser.parse_args()

    from torchsnapshot_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = run(args.gb, args.work_dir)
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
