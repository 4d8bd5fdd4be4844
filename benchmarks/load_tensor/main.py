"""Budgeted single-array read benchmark.

Mirrors the reference's benchmarks/load_tensor/main.py:26-63: read a large
array back under a small host-memory budget and prove peak RSS stays
O(budget), not O(array).

Run:  python benchmarks/load_tensor/main.py --gb 2 --budget-mb 100
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
    parser.add_argument("--gb", type=float, default=1.0)
    parser.add_argument("--budget-mb", type=int, default=100)
    parser.add_argument("--work-dir", default=None)
    parser.add_argument(
        "--device-template",
        action="store_true",
        help="read into a jax DEVICE template (the donated tile-chain "
        "path: host stays O(budget), device at ~1x target + one tile)",
    )
    args = parser.parse_args()

    from torchsnapshot_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.rss_profiler import measure_rss_deltas

    elems = int(args.gb * 1e9 / 4)
    arr = np.arange(elems, dtype=np.float32)

    from torchsnapshot_tpu.utils.benchio import warm_up_snapshot_runtime

    warm_up_snapshot_runtime()

    work = args.work_dir or tempfile.mkdtemp(prefix="tsnp_load_")
    try:
        snap = Snapshot.take(os.path.join(work, "snap"), {"t": StateDict(x=arr)})
        if args.device_template:
            import jax
            import jax.numpy as jnp

            out = jnp.zeros((elems,), jnp.float32)
            jax.block_until_ready(out)
            rss = []
            with measure_rss_deltas(rss):
                t0 = time.perf_counter()
                got = snap.read_object(
                    "0/t/x",
                    obj_out=out,
                    memory_budget_bytes=args.budget_mb * 1024 * 1024,
                )
                jax.block_until_ready(got)
                elapsed = time.perf_counter() - t0
            assert np.array_equal(np.asarray(got[: 1 << 20]), arr[: 1 << 20])
            assert np.array_equal(np.asarray(got[-(1 << 20):]), arr[-(1 << 20):])
        else:
            out = np.zeros_like(arr)
            # make every output page resident BEFORE measuring: np.zeros is
            # calloc-backed, so otherwise the read faulting pages in counts
            # the 1x output buffer itself as "RSS delta" and masks whether
            # the library's transient buffers respect the budget
            out.fill(0)
            rss = []
            with measure_rss_deltas(rss):
                t0 = time.perf_counter()
                snap.read_object(
                    "0/t/x", obj_out=out, memory_budget_bytes=args.budget_mb * 1024 * 1024
                )
                elapsed = time.perf_counter() - t0
            assert np.array_equal(out, arr)
        print(
            f"read {args.gb:.2f} GB under {args.budget_mb} MB budget in "
            f"{elapsed:.2f}s ({args.gb / elapsed:.2f} GB/s) | "
            f"peak RSS delta {max(rss) / 1e6:.1f} MB"
        )
    finally:
        if args.work_dir is None:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
