"""Scale-probe regression tests (VERDICT r2 #6).

Round 2's ad-hoc probes (20k tiny leaves, 12k shard boxes, 100k flatten
paths, manager step loops) caught three O(n^2)-class bugs that ordinary
tests missed: the batcher's merged-range gap rescan, per-call
crc32_combine matrix rebuilds, and per-member executor round-trips for
tiny slab members.  These tests pin those fixes with TIMED bounds so the
regressions can't silently return.

Bounds are ~10x the measured values on the 1-core CI box (take 1.25s,
restore 1.3s, flatten 0.09s — see docs/performance.md) so scheduler
noise and a busy box can't flake them; an O(n^2) regression blows past
10x immediately (the original bugs were 40-50x).
"""

import time

import numpy as np
import pytest

from torchsnapshot_tpu import PyTreeState, Snapshot
from torchsnapshot_tpu.flatten import flatten, inflate


def _timed(bound_s):
    class _Timer:
        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self.elapsed = time.perf_counter() - self.t0
            if exc[0] is None:
                assert self.elapsed < bound_s, (
                    f"scale probe exceeded bound: {self.elapsed:.2f}s "
                    f">= {bound_s}s — an O(n^2)-class regression?"
                )

    return _Timer()


def test_20k_tiny_leaves_take_restore():
    # probes: slab packing of many tiny members (tiered inline path),
    # checksum folding across 20k pieces, merged ranged-read planning
    n = 20_000
    tree = {f"g{i // 100:03d}/p{i % 100:02d}": np.full((4,), i, np.int32) for i in range(n)}
    with _timed(15.0):
        snap = Snapshot.take("memory://scale20k", {"m": PyTreeState(dict(tree))})
    templates = {k: np.zeros((4,), np.int32) for k in tree}
    dest = PyTreeState(templates)
    with _timed(15.0):
        snap.restore({"m": dest})
    for i in (0, n // 2, n - 1):
        k = f"g{i // 100:03d}/p{i % 100:02d}"
        np.testing.assert_array_equal(dest.tree[k], np.full((4,), i, np.int32))


def test_100k_flatten_inflate_paths():
    tree = {
        f"layer{i:03d}": {f"w{j:03d}": j for j in range(100)} for i in range(1000)
    }
    with _timed(3.0):
        manifest, flat = flatten(tree, prefix="m")
        assert len(flat) == 100_000
        restored = inflate(manifest, {k: v for k, v in flat.items()}, prefix="m")
    assert restored["layer500"]["w050"] == 50


def test_12k_shard_box_planning():
    # pure-planner probe: writer assignment + read-overlap planning over
    # many boxes must stay near-linear
    from torchsnapshot_tpu.preparers.sharded import assign_box_writers

    class _Dev:
        def __init__(self, p):
            self.process_index = p

    n = 12_000
    boxes = {
        ((i * 8, 0), (8, 16)): [_Dev(i % 4), _Dev((i + 1) % 4)]
        for i in range(n)
    }
    with _timed(5.0):
        assignment = assign_box_writers(boxes, itemsize=4, process_count=4)
    assert len(assignment) == n
    loads = [0] * 4
    for w in assignment.values():
        loads[w] += 1
    assert max(loads) - min(loads) <= n // 4  # roughly balanced


def test_manager_step_loop(tmp_path):
    # repeated saves through the manager: per-step cost must not grow
    # with the number of retained snapshots
    from torchsnapshot_tpu.manager import SnapshotManager

    mgr = SnapshotManager(str(tmp_path / "run"), keep_last_n=3)
    state = {"m": PyTreeState({"w": np.arange(64, dtype=np.float32)})}
    with _timed(30.0):
        for step in range(40):
            mgr.save(state, step)
    assert len(mgr.steps()) == 3


def test_crc_combine_many_folds():
    # crc32_combine once rebuilt its GF(2) matrices per call (~8s/20k
    # folds); the cached operators make 20k folds sub-second
    import zlib

    from torchsnapshot_tpu.utils.checksums import crc32_combine

    pieces = [bytes([i % 256]) * 64 for i in range(20_000)]
    crcs = [zlib.crc32(p) for p in pieces]
    with _timed(5.0):
        acc = crcs[0]
        for c in crcs[1:]:
            acc = crc32_combine(acc, c, 64)
    assert acc == zlib.crc32(b"".join(pieces))


def test_default_knob_overhead_ratio():
    # round-4 regression guard: defaults (batching + checksums) must stay
    # within a small factor of the no-integrity floor on ONE core — the
    # old behavior (slab-packing big host members + scalar-ish digests)
    # was 11x.  Ratio, not absolute time, and of the process's CPU
    # seconds, not the wall's: a worker descheduled on a shared box
    # then counts for neither side.  128MB keeps the probe under a second.
    import time

    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, knobs

    arrs = {
        f"a{i}": np.random.default_rng(i).integers(
            0, 255, 16 * 1024 * 1024, dtype=np.uint8
        )
        for i in range(8)
    }
    state = {"app": StateDict(**arrs)}

    def best(nobatch=False, nocksum=False):
        from contextlib import ExitStack

        b = 9e9
        for _ in range(3):
            with ExitStack() as st:
                if nobatch:
                    st.enter_context(knobs.override_disable_batching(True))
                if nocksum:
                    st.enter_context(knobs.override_write_checksums(False))
                t0 = time.process_time()
                Snapshot.take("memory://probe/ratio", state)
                b = min(b, time.process_time() - t0)
        return b

    floor = best(nobatch=True, nocksum=True)
    defaults = best()
    # round-5 level: ~1.5x on a quiet core (fused write+digest in the
    # memory plugin removed the second full pass over the staged bytes)
    assert defaults < floor * 2 + 0.05, (
        f"default-knob overhead regressed: {defaults:.3f} CPU-s vs floor "
        f"{floor:.3f} CPU-s ({defaults / floor:.1f}x; round-5 level is ~1.5x)"
    )
