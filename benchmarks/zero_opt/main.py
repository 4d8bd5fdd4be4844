"""Sharded-optimizer-state benchmark (ZeRO-parity).

Mirrors the reference's benchmarks/deepspeed_opt/main.py:27-106 (OPT
ZeRO-3 partitioned fp32 optimizer state): an adamw state whose m/v moments
are fully sharded over the mesh; each host writes only its shards, restore
reshards into a fresh (differently-meshed) state.

Run:  python benchmarks/zero_opt/main.py --gb 2
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--gb", type=float, default=2.0)
    parser.add_argument("--work-dir", default=None)
    args = parser.parse_args()

    from torchsnapshot_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    from torchsnapshot_tpu import PyTreeState, Snapshot

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("shard",))
    sharding = NamedSharding(mesh, P("shard"))
    n_dev = len(devices)

    # params bf16; optimizer moments fp32 fully sharded (ZeRO-3 layout)
    n_params = int(args.gb * 1e9 / 10)  # 2B param + 2x4B moments
    n_params -= n_params % n_dev

    params = {"w": jax.device_put(
        jnp.ones(n_params, dtype=jnp.bfloat16), sharding
    )}
    tx = optax.adamw(1e-4)
    opt_state = jax.jit(tx.init)(
        jax.device_put(jnp.zeros(n_params, dtype=jnp.float32), sharding)
    )
    jax.block_until_ready((params, opt_state))
    total_gb = (n_params * 2 + 2 * n_params * 4) / 1e9

    from torchsnapshot_tpu.utils.benchio import settle_dir, warm_up_snapshot_runtime

    warm_up_snapshot_runtime()

    work = args.work_dir or tempfile.mkdtemp(prefix="tsnp_zero_")
    try:
        t0 = time.perf_counter()
        Snapshot.take(
            os.path.join(work, "snap"),
            {"params": PyTreeState(params), "opt": PyTreeState(opt_state)},
        )
        t_save = time.perf_counter() - t0

        # settle save's dirty pages before timing the load phase
        settle_dir(work)

        opt2 = jax.jit(tx.init)(
            jax.device_put(jnp.zeros(n_params, dtype=jnp.float32), sharding)
        )
        t0 = time.perf_counter()
        Snapshot(os.path.join(work, "snap")).restore(
            {"params": PyTreeState(dict(params)), "opt": PyTreeState(opt2)}
        )
        t_load = time.perf_counter() - t0
        print(
            f"zero-opt {total_gb:.2f} GB over {n_dev} shards | "
            f"save {t_save:.2f}s ({total_gb / t_save:.2f} GB/s) | "
            f"load {t_load:.2f}s ({total_gb / t_load:.2f} GB/s)"
        )
    finally:
        if args.work_dir is None:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
