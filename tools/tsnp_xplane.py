"""List the program's spans beside the device's operations in one profile.

With tracing on, every lexical span of ``torchsnapshot_tpu.obs`` is also a
``jax.profiler.TraceAnnotation("tsnp:<name>")`` carrying its ``span_id``, so a
``jax.profiler`` session holds them on their host-thread lines of the same
``.xplane.pb`` as the device plane.  This reads such a file through the
benchmark's own readers (``chipbench/idle_reads.load``: the record of
``chipbench/trace_reduce.load_xplane`` and the program's events) and prints,
for the interval of one enclosing host annotation (or the whole trace), each
host thread's ``tsnp:`` events and the bursts of the TPU plane's "XLA Ops",
all as offsets from the interval's start:

    python tools/tsnp_xplane.py <file.xplane.pb> [--inside chipbench:restore --nth 3]

``--idle`` prints instead, for each ``restore`` and ``take`` of the
benchmark, the device's idle time split into link, host and unspanned, and
that idle time by innermost span name (``chipbench/idle_reads.py``): from a
file, by its annotations and events alone; from a run, with the tracer's
spans (``begin``/``end`` spans included) and the harness's timeline.

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
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import bench, idle_reads, trace_reduce  # noqa: E402

PREFIX = idle_reads.PROGRAM_PREFIX


def load(record: Dict[str, Any]) -> Tuple[Dict[str, List[Tuple[str, int, int]]], List[Tuple[str, int, int]], Dict[str, int]]:
    """From a record of ``idle_reads.load(path, detail=True)``: ``{host thread:
    [(event, start_ns, end_ns)]}`` of the benchmark's annotations and the
    program's events; the device's operations; and how many ``tsnp:``
    events each host line holds by the line's own name.  A ``tsnp:`` event
    names its thread itself (stat ``thread``): the profiler keys a line by
    pthread id, which a later thread reuses."""
    host: Dict[str, List[Tuple[str, int, int]]] = {}
    lines: Dict[str, int] = {}  # host line name → ``tsnp:`` events on it
    for name, start, dur in record["spans"]:
        host.setdefault("(benchmark)", []).append((name, start, start + dur))
    for _, start, dur, name, stats, line in record["program"]:
        thread = str(stats.get("thread", line))
        host.setdefault(thread, []).append((PREFIX + name, start, start + dur))
        lines[line] = lines.get(line, 0) + 1
    device = [
        (name, start, start + dur)
        for ops in record["devices"].values() for name, start, dur in ops
    ]
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


def listing(record: Dict[str, Any], inside: Optional[str], nth: int, gap_ms: float) -> List[str]:
    host, device, on_lines = load(record)
    lo, hi, title = None, None, "the whole trace"
    if inside:
        marks = sorted(
            (s, e) for events in host.values() for name, s, e in events if name == inside
        )
        if not marks:
            return [f"no host event {inside!r} in the profile"]
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
        f"device {trace_reduce._OPS_LINE!r}: {len(ops)} operations, {busy / 1e6:.1f} ms busy; "
        f"bursts (operations less than {gap_ms} ms apart):"
    )
    for a, b, n in bursts(ops, int(gap_ms * 1e6)):
        lines.append(f"  {ms(a)} ..{ms(b)}  {n:4d} operations")
    return lines


def idle_table(found: Optional[Dict[str, Any]], top: int = 15) -> List[str]:
    """``idle_reads``' split as lines: each operation's classes, seconds a
    record, then its idle time by innermost span name."""
    if not found:
        return ["no idle split: no device plane, or the clocks could not be joined"]
    out = []
    if "clock" in found:
        clk = found["clock"]
        out.append(
            f"clock: {clk['matched']:.4f} of {clk['lexical']} lexical spans matched, "
            f"offset {clk['offset_ns']} ns, median absolute deviation {clk['mad_ns']} ns"
        )
    for op in ("restore", "take"):
        if op not in found:
            continue
        got = found[op]
        classes = "  ".join(f"{c} {got[c + '_s']:.6f}" for c in idle_reads.CLASSES)
        out.append(f"{op}: {got['records']} records; idle {got['idle_s']:.6f} s a record = {classes}")
        for name, seconds in got["by_span"][:top]:
            out.append(f"  {name:<32} {seconds:.6f}")
    return out


def run_cell(
    workload: str, seed: int, seconds: float, inside: str, nth: int, gap_ms: float,
    root: str = ROOT, allow_cpu: bool = False, idle: bool = False,
) -> Dict[str, Any]:
    """One traced run of a cell; the listing is printed when the harness
    has read the profile (``idle``: the split, after the run), the
    program's span counts after the run.  ``root`` and ``allow_cpu`` are
    for a rehearsal at tiny widths without the chip."""
    from torchsnapshot_tpu.obs import tracer

    load_xplane, context = trace_reduce.load_xplane, bench.Context
    seen: Dict[str, Any] = {}

    def load_and_list(path: str) -> Dict[str, Any]:
        # ``reduce`` reads no ``program``: the harness's result is as it was
        record = {**load_xplane(path), "program": idle_reads.load_program(path, detail=True)}
        seen["record"] = record
        if not idle:
            print("\n".join(listing(record, inside, nth, gap_ms)), file=sys.stderr)
        return record

    def keep(**fields: Any) -> Any:
        seen["ctx"] = context(**fields)
        return seen["ctx"]

    trace_reduce.load_xplane, bench.Context = load_and_list, keep
    try:
        result = bench.run_cell(root, workload, seed, seconds, True, allow_cpu=allow_cpu)
    finally:
        trace_reduce.load_xplane, bench.Context = load_xplane, context
    if idle:
        ctx = seen["ctx"]
        found = idle_reads.split(seen.get("record"), ctx.spans, ctx.timeline, by_span=True)
        print("\n".join(idle_table(found)), file=sys.stderr)
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
    parser.add_argument("--idle", action="store_true", help="the idle split and table instead of the listing")
    parser.add_argument("--cell", default=None, help="make the profile: a workload of BENCHMARK.json")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=51)
    args = parser.parse_args(argv)
    if args.cell:
        bench.print_result(run_cell(
            args.cell, args.seed, args.seconds,
            args.inside or "chipbench:restore", args.nth, args.gap_ms, idle=args.idle,
        ))
        return 0
    if not args.xplane:
        parser.error("give an .xplane.pb, or --cell")
    record = idle_reads.load(args.xplane, detail=True)
    if args.idle:
        print("\n".join(idle_table(idle_reads.split_record(record))))
        return 0
    print("\n".join(listing(record, args.inside, args.nth, args.gap_ms)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
