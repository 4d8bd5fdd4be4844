"""Fully-sharded (FSDP-style) transformer save+load benchmark.

Mirrors the reference's benchmarks/fsdp/main.py:36-103 (1.9B transformer,
LOCAL_STATE_DICT): a transformer train state sharded over a ("dp","tp")
mesh; each host writes only its addressable shards; restore reshards into
a template mesh (optionally a different tp).

Run:  python benchmarks/fsdp/main.py --layers 4 --d-model 1024
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
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--d-model", type=int, default=1024)
    parser.add_argument("--vocab", type=int, default=8192)
    parser.add_argument("--work-dir", default=None)
    args = parser.parse_args()

    from torchsnapshot_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax

    from torchsnapshot_tpu import PyTreeState, Snapshot
    from torchsnapshot_tpu.models.transformer import (
        TransformerConfig,
        make_train_state,
    )
    from torchsnapshot_tpu.parallel.mesh import build_mesh

    cfg = TransformerConfig(
        vocab=args.vocab,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=max(4, args.d_model // 128),
        d_ff=args.d_model * 4,
    )
    mesh = build_mesh()
    ts = make_train_state(cfg, mesh=mesh)
    n_bytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(ts) if hasattr(x, "nbytes")
    )
    total_gb = n_bytes / 1e9

    from torchsnapshot_tpu.utils.benchio import settle_dir, warm_up_snapshot_runtime

    warm_up_snapshot_runtime()

    work = args.work_dir or tempfile.mkdtemp(prefix="tsnp_fsdp_")
    try:
        t0 = time.perf_counter()
        Snapshot.take(os.path.join(work, "snap"), {"ts": PyTreeState(ts)})
        t_save = time.perf_counter() - t0

        # settle save's dirty pages before timing the load phase
        settle_dir(work)

        ts2 = make_train_state(cfg, seed=1, mesh=mesh)
        t0 = time.perf_counter()
        Snapshot(os.path.join(work, "snap")).restore({"ts": PyTreeState(ts2)})
        t_load = time.perf_counter() - t0
        print(
            f"fsdp {total_gb:.2f} GB on mesh {dict(mesh.shape)} | "
            f"save {t_save:.2f}s ({total_gb / t_save:.2f} GB/s) | "
            f"load {t_load:.2f}s ({total_gb / t_load:.2f} GB/s)"
        )
    finally:
        if args.work_dir is None:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
