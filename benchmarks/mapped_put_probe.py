"""What a resharding restore's direct path can reach on the machine a chip
run lands on: ``jax.device_put`` of contiguous views of a MAPPED file
(``storage.fs.mmap_read``, as the read pipeline hands them to a consumer)
from one thread a device, waiting for each, in GB/s over all devices.

Each sink (a tmpfs of the run's own, and ``TMPDIR`` as it is) gets one
file a device; each pass walks every file once in views of ``--mb``:

- ``fresh``: a new mapping of each file (what every restore sees);
- ``touched``: the same mapping a second time (the link's own pace);
- ``pair_cut``: a new mapping, every view put to TWO devices and cut to a
  column half on each (``ops.device_pack.cut_box_on_device``), the wide
  buffers deleted after the cut: a column-sharded leaf's path;
- ``copy_*``: the host copy the direct path replaces, out of a fresh or a
  touched mapping into fresh or reused host pages (tmpfs only), which
  splits the source's first touches from the destination's;
- ``populate_*``: a new mapping, each view's pages asked for in ONE call
  before its put (``mlock`` + ``munlock``: the program's own
  ``preparers.sharded._populate``; ``madvise(MADV_POPULATE_READ)``,
  a ``pwrite`` of the view to a memfd, or a read of a byte a page from the
  putting thread), then put, or put to two devices and cut;
- ``handoff``: the two ways a column piece's halves reach the two devices
  that share it, each after the program's populate, in turn (A B B A):
  ``pair_cut`` as above (the piece crosses the host link twice), and
  ``pair_handoff``: the piece put ONCE, to one device, both halves cut
  there, the second moved with a device-to-device ``jax.device_put`` to
  the next device, waited for, deleted.  ``unique_gb_s`` is the piece's
  own bytes a second, whatever the link carried;
- ``d2d``: the hand-off alone: a device array moved to the next device
  with ``jax.device_put`` and waited for, from one thread and from a
  thread a device (a ring), against the same bytes read back to the host
  and put again, which is what a runtime without a chip-to-chip path
  would do.

Run through the chip tool:
    chiprun --chips 4 -- python benchmarks/mapped_put_probe.py
Exits nonzero, printing no result, when JAX finds no accelerator.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

ROWS = 2048  # a view is float32[ROWS, cols]: the state's column leaves


def _write_files(root: str, n_files: int, file_bytes: int) -> list:
    import numpy as np

    block = np.random.default_rng(0).integers(
        0, 255, size=32 << 20, dtype=np.uint8
    )
    paths = []
    for k in range(n_files):
        path = os.path.join(root, f"slab_{k}")
        with open(path, "wb") as f:
            left = file_bytes
            while left > 0:
                f.write(memoryview(block)[: min(left, block.nbytes)])
                left -= block.nbytes
        paths.append(path)
    return paths


def _views(mapped, view_bytes: int):
    import numpy as np

    cols = view_bytes // 4 // ROWS
    for i in range(mapped.nbytes // view_bytes):
        piece = mapped[i * view_bytes : (i + 1) * view_bytes]
        yield np.frombuffer(piece, np.float32).reshape(ROWS, cols)


def _populators():
    """name -> fn(view): ways to get a view's pages into the page table
    before a transfer thread touches them one by one."""
    import ctypes
    import mmap

    import numpy as np

    libc = ctypes.CDLL(None, use_errno=True)
    page = mmap.PAGESIZE

    def span(view):
        lo = view.ctypes.data - view.ctypes.data % page
        hi = view.ctypes.data + view.nbytes
        return ctypes.c_void_p(lo), ctypes.c_size_t(hi - lo)

    def check(rc, what):
        if rc:
            err = ctypes.get_errno()
            raise OSError(err, f"{what}: {os.strerror(err)}")

    def madv_populate_read(view):
        addr, n = span(view)
        check(libc.madvise(addr, n, 22), "madvise(MADV_POPULATE_READ)")

    scratch = threading.local()

    def pwrite_memfd(view):
        if not hasattr(scratch, "fd"):
            scratch.fd = os.memfd_create("probe")
        os.pwrite(scratch.fd, memoryview(view).cast("B"), 0)

    def touch(view):
        int(view.reshape(-1).view(np.uint8)[::page].sum())

    from torchsnapshot_tpu.preparers.sharded import _populate

    return {
        "mlock": _populate, "madv_populate_read": madv_populate_read,
        "pwrite_memfd": pwrite_memfd, "touch": touch,
    }


def _threads(fn, n: int) -> float:
    """Wall seconds of ``fn(k)`` on ``n`` threads started together."""
    errors = []

    def run(k):
        try:
            fn(k)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(k,)) for k in range(n)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return time.perf_counter() - t0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--mb", type=int, nargs="+", default=[22, 96])
    parser.add_argument("--file-mb", type=int, default=1536)
    parser.add_argument("--out", default="chiprun_out/probe.jsonl")
    parser.add_argument("--allow-cpu", action="store_true")
    parser.add_argument(
        "--phases", nargs="+",
        default=["put", "copy", "populate", "handoff", "d2d"],
        choices=["put", "copy", "populate", "handoff", "d2d"],
    )
    parser.add_argument("--sinks", nargs="+", default=["ram", "tmp"])
    args = parser.parse_args()

    from chipbench import bench

    # the sink's mount namespace is made before JAX starts a thread
    ram, ram_kind = bench.make_sink("ram")
    tmp, tmp_kind = bench.make_sink("tmp")

    import jax
    import numpy as np

    from torchsnapshot_tpu.ops.device_pack import cut_box_on_device
    from torchsnapshot_tpu.serialization import fast_copyto
    from torchsnapshot_tpu.storage.fs import mmap_read

    devices = jax.devices()
    if devices[0].platform == "cpu" and not args.allow_cpu:
        print("mapped_put_probe: no accelerator", file=sys.stderr)
        return 2
    n = len(devices)
    file_bytes = args.file_mb << 20
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    out = open(args.out, "w")

    def report(**row):
        row.update(platform=devices[0].platform, devices=n)
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def put_pass(maps, view_bytes, pair_cut, populate=None, handoff=False):
        sent = [0] * n
        populate_s = [0.0] * n

        def work(k):
            sibling = devices[(k + 1) % n]
            targets = [devices[k]] + ([sibling] if pair_cut else [])
            for view in _views(maps[k], view_bytes):
                if populate is not None:
                    t0 = time.perf_counter()
                    populate(view)
                    populate_s[k] += time.perf_counter() - t0
                wide = [jax.device_put(view, d) for d in targets]
                half = view.shape[1] // 2
                if handoff:
                    cuts = [
                        cut_box_on_device(wide[0], (0, i * half), (ROWS, half))
                        for i in range(2)
                    ]
                    moved = jax.device_put(cuts[1], sibling)
                    jax.block_until_ready([cuts[0], moved])
                    for w in wide + cuts + [moved]:
                        w.delete()
                elif pair_cut:
                    cuts = [
                        cut_box_on_device(w, (0, i * half), (ROWS, half))
                        for i, w in enumerate(wide)
                    ]
                    jax.block_until_ready(cuts)
                    for w in wide + cuts:
                        w.delete()
                else:
                    jax.block_until_ready(wide)
                    wide[0].delete()
                sent[k] += view.nbytes * len(targets)

        wall = _threads(work, n)
        if populate is not None:
            return sum(sent), wall, sum(populate_s)
        return sum(sent), wall

    def copy_pass(maps, view_bytes, dest):
        def work(k):
            for i, view in enumerate(_views(maps[k], view_bytes)):
                target = np.empty_like(view) if dest is None else dest[k][i]
                fast_copyto(target, view)

        return _threads(work, n)

    def d2d_pass(mb, threads, through_host):
        reps = 24
        cols = (mb << 20) // 4 // ROWS

        def work(k):
            src, dst = devices[k], devices[(k + 1) % n]
            box = jax.device_put(np.ones((ROWS, cols), np.float32), src)
            box.block_until_ready()
            for _ in range(reps):
                moved = jax.device_put(np.asarray(box) if through_host else box, dst)
                moved.block_until_ready()
                moved.delete()

        wall = _threads(work, threads)
        moved_gb = threads * reps * ROWS * cols * 4 / 1e9
        report(
            phase="d2d_through_host" if through_host else "d2d", view_mb=mb,
            threads=threads, gb=moved_gb, wall_s=wall, gb_s=moved_gb / wall,
            ms_a_move=wall / reps * 1e3,
        )

    try:
        for mb in args.mb if "d2d" in args.phases else ():
            for threads in (1, n):
                for through_host in (False, True):
                    d2d_pass(mb, threads, through_host)
        # the d2d phase moves device arrays: alone, it needs no file
        sinks = ((ram, ram_kind), (tmp, tmp_kind)) if set(args.phases) != {"d2d"} else ()
        for root, kind in sinks:
            if ("ram" if root is ram else "tmp") not in args.sinks:
                continue
            t0 = time.perf_counter()
            paths = _write_files(root, n, file_bytes)
            report(
                phase="write", sink=kind,
                gb_s=n * file_bytes / 1e9 / (time.perf_counter() - t0),
            )

            def fresh():
                return [mmap_read(p, None) for p in paths]

            # compile the cut programs outside every timed pass
            for mb in args.mb:
                warm = next(_views(fresh()[0], mb << 20))
                for d in devices:
                    cut_box_on_device(
                        jax.device_put(warm, d), (0, 0), (ROWS, warm.shape[1] // 2)
                    ).block_until_ready()
            for mb in args.mb if "populate" in args.phases else ():
                view_bytes = mb << 20
                for name, populate in _populators().items():
                    for pair_cut in (False, True):
                        maps = fresh()
                        try:
                            sent, wall, pop_s = put_pass(
                                maps, view_bytes, pair_cut, populate
                            )
                        except OSError as e:
                            report(phase=f"populate_{name}", sink=kind, error=str(e))
                            break
                        report(
                            phase=f"populate_{name}_put" + "_pair_cut" * pair_cut,
                            sink=kind, view_mb=mb, gb=sent / 1e9, wall_s=wall,
                            gb_s=sent / 1e9 / wall, populate_thread_s=pop_s,
                            unique_gb_s=sent / 1e9 / wall / (2 if pair_cut else 1),
                        )
                        del maps
            for mb in args.mb if "handoff" in args.phases else ():
                for name in ("pair_cut", "pair_handoff", "pair_handoff", "pair_cut"):
                    handoff = name == "pair_handoff"
                    maps = fresh()
                    sent, wall, pop_s = put_pass(
                        maps, mb << 20, not handoff, _populators()["mlock"], handoff
                    )
                    unique = sent / (1 if handoff else 2)
                    report(
                        phase=name, sink=kind, view_mb=mb, link_gb=sent / 1e9,
                        wall_s=wall, link_gb_s=sent / 1e9 / wall,
                        handoff_gb=unique / 2e9 if handoff else 0.0,
                        unique_gb_s=unique / 1e9 / wall, populate_thread_s=pop_s,
                    )
                    del maps
            for mb in args.mb if "put" in args.phases else ():
                view_bytes = mb << 20
                maps = fresh()
                for name in ("fresh", "touched"):
                    sent, wall = put_pass(maps, view_bytes, pair_cut=False)
                    report(
                        phase=f"put_{name}", sink=kind, view_mb=mb,
                        gb=sent / 1e9, wall_s=wall, gb_s=sent / 1e9 / wall,
                    )
                maps = fresh()
                sent, wall = put_pass(maps, view_bytes, pair_cut=True)
                report(
                    phase="put_pair_cut_fresh", sink=kind, view_mb=mb,
                    gb=sent / 1e9, wall_s=wall, gb_s=sent / 1e9 / wall,
                )
                del maps
            if root is not ram or "copy" not in args.phases:
                continue
            view_bytes = args.mb[0] << 20
            total = n * (file_bytes // view_bytes) * view_bytes / 1e9
            maps = fresh()
            report(
                phase="copy_fresh_map_to_fresh_pages", sink=kind,
                gb=total, gb_s=total / copy_pass(maps, view_bytes, None),
            )
            report(
                phase="copy_touched_map_to_fresh_pages", sink=kind,
                gb=total, gb_s=total / copy_pass(maps, view_bytes, None),
            )
            dest = [
                [np.empty_like(v) for v in _views(m, view_bytes)] for m in maps
            ]
            for per_file in dest:
                for d in per_file:
                    d.fill(0)
            report(
                phase="copy_touched_map_to_touched_pages", sink=kind,
                gb=total, gb_s=total / copy_pass(maps, view_bytes, dest),
            )
            maps = fresh()
            report(
                phase="copy_fresh_map_to_touched_pages", sink=kind,
                gb=total, gb_s=total / copy_pass(maps, view_bytes, dest),
            )
            del maps, dest
    finally:
        out.close()
        bench.remove_sink(ram)
        bench.remove_sink(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
