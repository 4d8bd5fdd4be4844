"""Replicated (DDP-style) save benchmark.

Mirrors the reference's headline benchmark (benchmarks/ddp/main.py +
README.md:9-24): persist a replicated model, compare against the naive
single-writer baseline (numpy .npz ≈ torch.save).  On a multi-chip mesh
the replicated write load is balanced across hosts by the sharded
preparer's collective-free partitioner.

Run:  python benchmarks/replicated/main.py --gb 2
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
    import numpy as np

    from torchsnapshot_tpu import PyTreeState, Snapshot
    from torchsnapshot_tpu.rss_profiler import measure_rss_deltas

    n_arrays = 32
    elems = int(args.gb * 1e9 / 2 / n_arrays)  # bf16

    @jax.jit
    def make(i):
        return (jnp.arange(elems, dtype=jnp.float32) * (i + 1)).astype(jnp.bfloat16)

    params = {f"layer{i}/w": make(i) for i in range(n_arrays)}
    jax.block_until_ready(params)
    total_gb = n_arrays * elems * 2 / 1e9

    from torchsnapshot_tpu.utils.benchio import settle_dir, warm_up_snapshot_runtime

    warm_up_snapshot_runtime()

    work = args.work_dir or tempfile.mkdtemp(prefix="tsnp_repl_")
    try:
        # naive baseline: host-gather then single np.savez (≈ torch.save)
        t0 = time.perf_counter()
        host = {k: np.asarray(v) for k, v in params.items()}
        np.savez(os.path.join(work, "baseline.npz"), **host)
        t_naive = time.perf_counter() - t0
        del host

        # settle the baseline's dirty pages: on a slow disk, writeback of
        # the naive file otherwise throttles the snapshot phase's writes
        # and the comparison measures the kernel's flusher, not the library
        settle_dir(work)

        rss = []
        with measure_rss_deltas(rss):
            t0 = time.perf_counter()
            Snapshot.take(os.path.join(work, "snap"), {"m": PyTreeState(params)})
            t_snap = time.perf_counter() - t0
        print(
            f"replicated {total_gb:.2f} GB | naive {t_naive:.2f}s "
            f"({total_gb / t_naive:.2f} GB/s) | snapshot {t_snap:.2f}s "
            f"({total_gb / t_snap:.2f} GB/s) | speedup {t_naive / t_snap:.2f}x "
            f"| peak RSS delta {max(rss) / 1e9:.2f} GB"
        )
    finally:
        if args.work_dir is None:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
