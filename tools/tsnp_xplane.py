"""List the program's spans beside the device's operations in one profile.

With tracing on, every lexical span of ``torchsnapshot_tpu.obs`` is also a
``jax.profiler.TraceAnnotation("tsnp:<name>")``, so a ``jax.profiler``
session holds them on their host-thread lines of the same ``.xplane.pb``
as the device plane.  This reads such a file with ``jax.profiler.ProfileData``
and prints, for the interval of one enclosing host annotation (or the whole
trace), each host line's ``tsnp:`` events and the bursts of the TPU plane's
"XLA Ops", all as offsets from the interval's start:

    python tools/tsnp_xplane.py <file.xplane.pb> [--inside chipbench:restore --nth 3]

``--cell <workload>`` first makes the profile: one ``--trace 1`` run of that
cell of ``BENCHMARK.json`` through the harness as it is (the harness deletes
its trace directory, so the file is read at the moment the harness reads
it), then prints the harness's result line as the last line, as it does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = "tsnp:"
_DEVICE_PLANE = "/device:"
_OPS_LINE = "XLA Ops"


def load(path: str) -> Tuple[Dict[str, List[Tuple[str, int, int]]], List[Tuple[str, int, int]], Dict[str, int]]:
    """``{host thread: [(event, start_ns, end_ns)]}``, the device's
    operations, both on the profiler's clock, and how many ``tsnp:`` events
    each host line holds by the line's own name.  A ``tsnp:`` event names its
    thread itself (stat ``thread``); any other goes by its line's name,
    which is the name of one of the threads that had the line's pthread id."""
    from jax.profiler import ProfileData

    host: Dict[str, List[Tuple[str, int, int]]] = {}
    device: List[Tuple[str, int, int]] = []
    lines: Dict[str, int] = {}  # host line name → ``tsnp:`` events on it
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(_DEVICE_PLANE)
        for line in plane.lines:
            if on_device and line.name != _OPS_LINE:
                continue
            for ev in line.events:
                event = (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                if on_device:
                    device.append(event)
                    continue
                thread = line.name
                if ev.name.startswith(PREFIX):
                    thread = dict(ev.stats).get("thread", thread)
                    lines[line.name] = lines.get(line.name, 0) + 1
                host.setdefault(thread, []).append(event)
    return host, device, lines


def bursts(ops: List[Tuple[str, int, int]], gap_ns: int) -> List[Tuple[int, int, int]]:
    """Device operations merged where less than ``gap_ns`` apart:
    (start, end, operations)."""
    out: List[List[int]] = []
    for _, start, end in sorted(ops, key=lambda op: op[1]):
        if out and start - out[-1][1] < gap_ns:
            out[-1][1] = max(out[-1][1], end)
            out[-1][2] += 1
        else:
            out.append([start, end, 1])
    return [(a, b, n) for a, b, n in out]


def listing(path: str, inside: Optional[str], nth: int, gap_ms: float) -> List[str]:
    host, device, on_lines = load(path)
    lo, hi, title = None, None, "the whole trace"
    if inside:
        marks = sorted(
            (s, e) for events in host.values() for name, s, e in events if name == inside
        )
        if not marks:
            return [f"no host event {inside!r} in {path}"]
        lo, hi = marks[min(nth, len(marks) - 1)]
        title = f"{inside} number {min(nth, len(marks) - 1)} of {len(marks)}"
    every = [e for events in host.values() for e in events] + device
    lo = min(s for _, s, _ in every) if lo is None else lo
    hi = max(e for _, _, e in every) if hi is None else hi

    def ms(ns: int) -> str:
        return f"{(ns - lo) / 1e6:8.1f}"

    by_pool: Dict[str, List[int]] = {}
    for name, n in on_lines.items():
        pool = by_pool.setdefault(name.rsplit("_", 1)[0], [0, 0])
        pool[0] += 1
        pool[1] += n
    lines = ["host lines that hold tsnp: events in the whole file (lines, events): " + ", ".join(
        f"{pool}* {k} {n}" for pool, (k, n) in sorted(by_pool.items())
    )]
    lines.append(f"{title}: {(hi - lo) / 1e6:.1f} ms; offsets in ms from its start")
    lines.append("host thread          event                      n   sum_ms   first     last_end")
    for name in sorted(host):
        rows: Dict[str, List[int]] = {}
        for event, s, e in host[name]:
            if event.startswith(PREFIX) and lo <= s <= hi:
                row = rows.setdefault(event, [0, 0, s, e])
                row[0] += 1
                row[1] += e - s
                row[2], row[3] = min(row[2], s), max(row[3], e)
        for event, (n, total, first, last) in sorted(rows.items(), key=lambda kv: kv[1][2]):
            lines.append(
                f"{name:<20} {event:<24} {n:4d} {total / 1e6:8.1f} {ms(first)} {ms(last)}"
            )
    ops = [(n, s, e) for n, s, e in device if lo <= s <= hi]
    busy = sum(e - s for _, s, e in ops)
    lines.append(
        f"device {_OPS_LINE!r}: {len(ops)} operations, {busy / 1e6:.1f} ms busy; "
        f"bursts (operations less than {gap_ms} ms apart):"
    )
    for a, b, n in bursts(ops, int(gap_ms * 1e6)):
        lines.append(f"  {ms(a)} ..{ms(b)}  {n:4d} operations")
    return lines


def run_cell(
    workload: str, seed: int, seconds: float, inside: str, nth: int, gap_ms: float,
    root: str = ROOT, allow_cpu: bool = False,
) -> Dict[str, Any]:
    """One traced run of a cell; the listing is printed when the harness
    reads the profile, the program's span counts after the run.  ``root``
    and ``allow_cpu`` are for a rehearsal at tiny widths without the chip."""
    sys.path.insert(0, ROOT)
    from chipbench import bench, trace_reduce

    from torchsnapshot_tpu.obs import tracer

    load_xplane = trace_reduce.load_xplane

    def load_and_list(path: str) -> Dict[str, Any]:
        print("\n".join(listing(path, inside, nth, gap_ms)), file=sys.stderr)
        return load_xplane(path)

    trace_reduce.load_xplane = load_and_list
    try:
        result = bench.run_cell(root, workload, seed, seconds, True, allow_cpu=allow_cpu)
    finally:
        trace_reduce.load_xplane = load_xplane
    recorded = tracer.get_tracer()
    print(json.dumps({
        "spans_recorded": len(recorded), "spans_dropped": recorded.dropped,
        "spans_cap": tracer._MAX_SPANS,
    }), file=sys.stderr)
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("xplane", nargs="?")
    parser.add_argument("--inside", default=None, help="the enclosing host annotation")
    parser.add_argument("--nth", type=int, default=0, help="which of them, from 0")
    parser.add_argument("--gap-ms", type=float, default=1.0)
    parser.add_argument("--cell", default=None, help="make the profile: a workload of BENCHMARK.json")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=51)
    args = parser.parse_args(argv)
    if args.cell:
        sys.path.insert(0, ROOT)
        from chipbench import bench

        bench.print_result(run_cell(
            args.cell, args.seed, args.seconds,
            args.inside or "chipbench:restore", args.nth, args.gap_ms,
        ))
        return 0
    if not args.xplane:
        parser.error("give an .xplane.pb, or --cell")
    print("\n".join(listing(args.xplane, args.inside, args.nth, args.gap_ms)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
