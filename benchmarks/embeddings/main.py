"""Row-sharded embedding-table benchmark (torchrec-parity).

Mirrors the reference's benchmarks/torchrec/main.py:119-235 (DLRM row-wise
ShardedTensor embeddings): big embedding tables row-sharded over the mesh,
sync vs async take, time-blocked-on-save and peak RSS reported.

Run:  python benchmarks/embeddings/main.py --gb 2
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
    parser.add_argument("--tables", type=int, default=8)
    parser.add_argument("--dim", type=int, default=128)
    parser.add_argument("--work-dir", default=None)
    args = parser.parse_args()

    from torchsnapshot_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    from torchsnapshot_tpu import PyTreeState, Snapshot
    from torchsnapshot_tpu.rss_profiler import measure_rss_deltas

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("row",))
    n_dev = len(devices)
    rows_per_table = int(args.gb * 1e9 / 4 / args.dim / args.tables)
    rows_per_table -= rows_per_table % n_dev  # divisible row sharding

    sharding = NamedSharding(mesh, P("row", None))

    @jax.jit
    def make(i):
        return (
            jnp.arange(rows_per_table * args.dim, dtype=jnp.float32) * (i + 1)
        ).reshape(rows_per_table, args.dim)

    tables = {
        f"table{i}": jax.device_put(make(i), sharding)
        for i in range(args.tables)
    }
    jax.block_until_ready(tables)
    total_gb = args.tables * rows_per_table * args.dim * 4 / 1e9

    from torchsnapshot_tpu.utils.benchio import settle_dir, warm_up_snapshot_runtime

    warm_up_snapshot_runtime()

    work = args.work_dir or tempfile.mkdtemp(prefix="tsnp_emb_")
    try:
        t0 = time.perf_counter()
        Snapshot.take(os.path.join(work, "sync"), {"emb": PyTreeState(tables)})
        t_sync = time.perf_counter() - t0

        # settle the sync phase's dirty pages so writeback doesn't
        # throttle the async phase on slow disks (would inflate blocked
        # time with kernel flusher stalls unrelated to the library)
        settle_dir(work)

        rss = []
        with measure_rss_deltas(rss):
            t0 = time.perf_counter()
            pending = Snapshot.async_take(
                os.path.join(work, "async"), {"emb": PyTreeState(tables)}
            )
            t_blocked = time.perf_counter() - t0
            pending.wait()
            t_total = time.perf_counter() - t0
        print(
            f"embeddings {total_gb:.2f} GB row-sharded over {n_dev} devices | "
            f"sync take {t_sync:.2f}s | async blocked {t_blocked:.2f}s "
            f"(total {t_total:.2f}s) | peak RSS delta {max(rss) / 1e9:.2f} GB"
        )
    finally:
        if args.work_dir is None:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
