"""What part writes into a fresh tmpfs reach, by how the writer threads
are laid over the files: several threads on the parts of ONE file, or a
thread a file.

Every write is ``FastIOEngine.pwrite_part`` of one 64 MiB part with its
(crc32, adler32) fused (``want_digest=True``; ``--no-digest`` without),
from touched numpy buffers, into a ``.tsnp``-style file opened and ``ftruncate``d to its
size first, as the fs plugin's striped-write handle does.  The files lie
in a tmpfs made as the benchmark's RAM sink makes it
(``chipbench.bench.make_sink("ram")``: the run's own mount where
``TMPDIR`` is no tmpfs).  Each layout gets fresh files every repetition,
removed between repetitions.  The layouts (``--objects`` files of
``--mib`` MiB each):

    a  1 thread, one file at a time
    b  8 threads on one file's parts, then the next file
    c  8 threads, a file each (its parts in offset order)
    d  16 threads fed as the I/O pool is fed by eight-part stripes:
       every file's parts queued in order, so two files at a time
    e  16 threads, a file each (its parts in offset order)

Last stdout line: one JSON object with GB/s a repetition per layout,
their medians, and whether the premise "a file's parts do not write in
parallel" holds: ``b <= 1.3 * a`` and ``e >= 1.5 * d``.

Run on the chip host:   python benchmarks/tmpfs_write_probe.py
Rehearse small here:    python benchmarks/tmpfs_write_probe.py --mib 16 \
                            --objects 16 --reps 1 --root /tmp/probe
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

PART_MIB = 64
LAYOUTS = ("a", "b", "c", "d", "e")


def _jobs(layout, files, parts):
    """(threads, waves): each wave is a list of jobs run on a pool of
    ``threads`` and waited for; a job is the (file, part) writes one
    thread makes in order."""
    every_part = [[(f, p)] for f in files for p in range(parts)]
    whole_files = [[(f, p) for p in range(parts)] for f in files]
    if layout == "a":
        return 1, [every_part]
    if layout == "b":
        return 8, [[[(f, p)] for p in range(parts)] for f in files]
    if layout == "c":
        return 8, [whole_files]
    if layout == "d":
        return 16, [every_part]
    if layout == "e":
        return 16, [whole_files]
    raise ValueError(layout)


def run_layout(engine, root, layout, sources, objects, part_bytes, parts,
               digest=True):
    names = [
        os.path.join(root, f"{layout}-{i}.tsnp-tmp") for i in range(objects)
    ]
    size = part_bytes * parts
    fds = []
    for name in names:
        fd = os.open(name, os.O_RDWR | os.O_CREAT | os.O_EXCL, 0o644)
        os.ftruncate(fd, size)
        fds.append(fd)
    threads, waves = _jobs(layout, range(objects), parts)

    def job(writes):
        for f, p in writes:
            src = sources[f % len(sources)]
            view = memoryview(src)[p * part_bytes : (p + 1) * part_bytes]
            engine.pwrite_part(fds[f], -1, p * part_bytes, view, digest)

    try:
        with ThreadPoolExecutor(max_workers=threads) as pool:
            t0 = time.perf_counter()
            for wave in waves:
                for fut in [pool.submit(job, w) for w in wave]:
                    fut.result()
            dt = time.perf_counter() - t0
    finally:
        for fd in fds:
            os.close(fd)
        for name in names:
            os.unlink(name)
    return objects * size / dt / 1e9


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--mib", type=int, default=512, help="MiB a file")
    parser.add_argument("--objects", type=int, default=16)
    parser.add_argument("--sources", type=int, default=8,
                        help="touched source buffers, reused round robin")
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--no-digest", action="store_true",
                        help="write without the fused digest")
    parser.add_argument("--root", default=None,
                        help="write here instead of a RAM sink of its own")
    args = parser.parse_args()

    import numpy as np

    from torchsnapshot_tpu import _csrc
    from torchsnapshot_tpu.storage import fastio

    part_bytes = min(PART_MIB, args.mib) << 20
    parts = (args.mib << 20) // part_bytes
    if args.root is None:
        from chipbench import bench

        root, where = bench.make_sink("ram")
    else:
        root, where = args.root, "given"
        os.makedirs(root, exist_ok=True)
    engine = fastio.create_engine(_csrc.load(), root)
    if engine is None:
        print("tmpfs_write_probe: no fastio engine", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    sources = [
        rng.integers(0, 255, parts * part_bytes, dtype=np.uint8)
        for _ in range(args.sources)
    ]
    rates = {k: [] for k in LAYOUTS}
    try:
        for rep in range(args.reps):
            for layout in LAYOUTS:
                rate = run_layout(
                    engine, root, layout, sources, args.objects,
                    part_bytes, parts, digest=not args.no_digest,
                )
                rates[layout].append(rate)
                print(f"rep {rep} layout {layout}: {rate:.3f} GB/s",
                      file=sys.stderr, flush=True)
    finally:
        if args.root is None:
            bench.remove_sink(root)
    med = {k: statistics.median(v) for k, v in rates.items()}
    print(json.dumps({
        "probe": "tmpfs_write",
        "sink": where,
        "files": args.objects,
        "mib_a_file": args.mib,
        "part_mib": part_bytes >> 20,
        "direct": engine.direct,
        "digest": not args.no_digest,
        "unit": "GB/s over all files, one value a repetition",
        "rates": {k: [round(r, 4) for r in v] for k, v in rates.items()},
        "median": {k: round(v, 4) for k, v in med.items()},
        "b_over_a": round(med["b"] / med["a"], 3),
        "e_over_d": round(med["e"] / med["d"], 3),
        "premise_holds": med["b"] <= 1.3 * med["a"]
        and med["e"] >= 1.5 * med["d"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
