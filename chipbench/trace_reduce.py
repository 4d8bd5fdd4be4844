"""Reduction from a profiler trace to device busy time, the operations that
took most of it, and the idle gaps by what the benchmark was doing.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a neutral record
(plain lists, nanoseconds); ``reduce`` works on that record alone, so it is
checked in the tests on a small recorded one (``testdata/``).

    record = {"devices": {"<plane>": [[name, start_ns, dur_ns], ...]},
              "spans": [[name, start_ns, dur_ns], ...]}

``devices`` holds the device's own operations (the TPU plane's "XLA Ops"
line); ``spans`` the benchmark's annotations (``SPAN_PREFIX`` + op) from the
host's threads, on the same clock.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, List, Optional, Tuple

SPAN_PREFIX = "chipbench:"
NO_SPAN = "_no_benchmark_span_"
_DEVICE_PLANE = "/device:TPU:"
_OPS_LINE = "XLA Ops"

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[List[Any]]] = {}
    spans: List[List[Any]] = []
    for plane in data.planes:
        if plane.name.startswith(_DEVICE_PLANE):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events
                    )
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(
                            [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        )
    return {"devices": devices, "spans": spans}


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")[:80]


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def reduce(record: Dict[str, Any], top: int = 10) -> Optional[Dict[str, Any]]:
    """Busy seconds (union of device operations, mean over the devices that
    ran any), the traced window (first span start to last span end), the
    ``top`` operations by summed seconds, and the idle gaps by the span they
    fell in.  None where no operation ran on a device."""
    devices = {k: v for k, v in record["devices"].items() if v}
    spans = sorted(
        ((n[len(SPAN_PREFIX):], s, s + d) for n, s, d in record["spans"]),
        key=lambda span: span[1],
    )
    if not devices or not spans:
        return None
    # the benchmark's spans follow one another and never nest, so their ends
    # rise with their starts: a gap meets only the spans from the first that
    # ends after its start
    ends = [e for _, _, e in spans]
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy_ns, op_ns, gap_ns = 0, {}, {}
    for events in devices.values():
        inside = [(n, s, s + d) for n, s, d in events if s + d > lo and s < hi]
        busy = union(_clip([(s, e) for _, s, e in inside], lo, hi))
        busy_ns += sum(b - a for a, b in busy)
        for name, s, e in inside:
            op_ns[name] = op_ns.get(name, 0) + min(e, hi) - max(s, lo)
        for a, b in _gaps(busy, lo, hi):
            left = b - a
            for at in range(bisect.bisect_right(ends, a), len(spans)):
                name, s, e = spans[at]
                if s >= b:
                    break
                over = min(b, e) - max(a, s)
                gap_ns[name] = gap_ns.get(name, 0) + over
                left -= over
            if left > 0:
                gap_ns[NO_SPAN] = gap_ns.get(NO_SPAN, 0) + left
    n = len(devices)

    def ranked(table: Dict[str, int]) -> List[List[Any]]:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / n / 1e9] for name, ns in rows]

    if busy_ns <= 0:
        return None
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": ranked(op_ns),
        "idle_gaps": ranked(gap_ns),
    }
