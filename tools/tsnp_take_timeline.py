"""The program's spans of each blocking save in one traced run of a cell.

A blocking ``Snapshot.take`` opens one ``take/pipeline`` span on the caller's
thread; the spans of the loop thread and of the staging workers lie inside it
and ``take/commit`` follows it.  This makes one ``--trace 1`` run of a cell of
``BENCHMARK.json`` through the harness as it is, then prints to standard
error, for every save of the run, one line per span name (how many, first
start, last end, summed work, summed ``queue_ns``: the time its tasks waited
for a worker), and for save ``--save`` also its spans in start order, all as
milliseconds from the start of that save's ``take/pipeline``:

    python tools/tsnp_take_timeline.py --cell ouro-2.6b-d9.preempt_sync_save --seed 7 --save 2

A save whose copies, checksums and writes follow each other shows as a
``stage/digest`` whose first start lies at the last end of ``d2h/copy``; one
that flows shows them side by side.  The harness's result line is the last
line of standard output, as the harness prints it.  For the same table of a
parent commit, copy this file into that checkout and run it there.  No cell
reads this.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAVE = "take/pipeline"
PLAN = "take/plan"  # a save's first span: its plan comes before its pipeline
NAMES = (
    PLAN, SAVE, "pipeline/staging", "stage/materialize", "chunk/slice", "d2h/copy",
    "stage/copy", "stage/digest", "pipeline/io", "storage/write", "take/commit",
)
_AFTER_NS = 3_000_000_000  # the last save's commit follows its pipeline


def saves(spans: List[Any]) -> List[List[Any]]:
    """The spans named in ``NAMES`` of each save, in start order; a save is
    what starts between its own ``take/plan`` and the next save's."""
    pipes = sorted((s for s in spans if s.name == SAVE), key=lambda s: s.start_ns)
    plans = sorted(s.start_ns for s in spans if s.name == PLAN)
    starts = [
        max((t for t in plans if t <= pipe.start_ns), default=pipe.start_ns)
        for pipe in pipes
    ]
    out = []
    for n, pipe in enumerate(pipes):
        hi = starts[n + 1] if n + 1 < len(pipes) else pipe.end_ns + _AFTER_NS
        out.append(sorted(
            (s for s in spans if s.name in NAMES and starts[n] <= s.start_ns < hi),
            key=lambda s: s.start_ns,
        ))
    return out


def table(save: List[Any]) -> List[str]:
    """One line per span name of one save, in the order of first start."""
    t0 = next(s.start_ns for s in save if s.name == SAVE)
    rows: Dict[str, List[int]] = {}
    for s in save:
        row = rows.setdefault(s.name, [0, s.start_ns, s.end_ns, 0, 0])
        row[0] += 1
        row[2] = max(row[2], s.end_ns)
        row[3] += s.duration_ns
        row[4] += s.attrs.get("queue_ns", 0)
    return [
        f"  {name:<18} n={n:3d} first {(first - t0) / 1e6:8.1f} last {(last - t0) / 1e6:8.1f} ms"
        f"  work {work / 1e9:7.3f} s  queued {queued / 1e9:8.3f} s"
        for name, (n, first, last, work, queued) in rows.items()
    ]


def listing(save: List[Any]) -> List[str]:
    """Every span of one save in start order."""
    t0 = next(s.start_ns for s in save if s.name == SAVE)
    return [
        f"  {(s.start_ns - t0) / 1e6:11.1f} {(s.end_ns - t0) / 1e6:9.1f} {s.name:<18} {s.thread_name:<18} "
        f"bytes={s.attrs.get('bytes', s.attrs.get('cost', ''))} "
        f"queued_ms={s.attrs.get('queue_ns', 0) / 1e6:.1f} {str(s.attrs.get('path', ''))[-24:]}"
        for s in save
    ]


def report(spans: List[Any], nth: int) -> List[str]:
    lines = []
    for n, save in enumerate(saves(spans)):
        pipe = next(s for s in save if s.name == SAVE)
        lines.append(f"save {n}: pipeline {pipe.duration_ns / 1e6:.0f} ms")
        lines += table(save)
        if n == nth:
            lines += listing(save)
    return lines or [f"no {SAVE} span: the run made no blocking save, or the program has none"]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cell", required=True, help="a workload of BENCHMARK.json")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=51)
    parser.add_argument("--save", type=int, default=2, help="the save listed span by span, from 0")
    parser.add_argument("--allow-cpu", action="store_true", help="a rehearsal without the chip")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from chipbench import bench

    from torchsnapshot_tpu.obs import tracer

    result = bench.run_cell(
        ROOT, args.cell, args.seed, args.seconds, True, allow_cpu=args.allow_cpu
    )
    print("\n".join(report(tracer.get_tracer().spans(), args.save)), file=sys.stderr)
    bench.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
