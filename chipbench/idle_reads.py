"""The device's idle time inside each restore and each blocking save, put
down to what the program was doing then.

Inside a record of the timeline (a ``restore``, or a ``take`` that is not
asynchronous) each device is idle where none of its "XLA Ops" runs, as
``trace_reduce.reduce`` reckons busy time.  Each idle instant goes to
exactly one class:

    link       a program span with a ``link`` attr (``h2d``, ``d2h``,
               ``d2d``: it waits on a transfer, or issues one) was open,
               on any thread
    host       else some program span other than the operation's root
               (``restore`` / ``take`` with no parent) was open
    unspanned  else: the root alone, or nothing (the benchmark's
               ``block_until_ready`` after the root closed, the moments
               between the caller thread's spans)

The spans are those that overlap the record: the benchmark has one client,
so nothing else of the program runs then.  The three are summed over the
devices that ran any operation, divided by their number and by the records:
seconds an operation, and by construction they add up to its idle time.

One clock.  A lexical span (``Span.lexical``) takes its own event's start
and end in the profile, found by ``span_id`` (``load_program``); a
``begin`` / ``end`` span, a lexical span whose event is missing, and the
timeline's records (``time.monotonic``) are moved by the median of (event
start − ``start_ns``) over the matched spans.  Nothing is read (None) where
no device ran an operation, where fewer than half of the lexical spans
found their event, or where those offsets lie more than ``MAD_MOST_NS``
from their median by the median (a profile that dropped host events, a
clock that stepped).

``by_span`` adds, for the tool, the idle time by innermost span name: each
idle instant is split equally among the threads that had a span open, and
on each goes to the span opened last of those open (``NO_SPAN`` where no
thread had one).

``load`` is ``trace_reduce.load_xplane``'s record with the program's events
beside it under ``program``, which ``trace_reduce.reduce`` does not read:

    record["program"] = [[span_id, start_ns, dur_ns], ...]

The harness's ``run_cell`` hands its readers no profile (``Context`` holds
the spans and the timeline alone), so no per-layer metric reads this yet;
``tools/tsnp_xplane.py --idle`` does.
"""

from __future__ import annotations

import bisect
import heapq
import statistics
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from . import trace_reduce

PROGRAM_PREFIX = "tsnp:"  # torchsnapshot_tpu.obs.tracer.XPLANE_PREFIX
MATCH_LEAST = 0.5
MAD_MOST_NS = 100_000
NO_SPAN = "_no_program_span_"
CLASSES = ("link", "host", "unspanned")

Interval = Tuple[int, int]


class Placed(NamedTuple):
    """A span on the profile's clock."""

    lo: int
    hi: int
    name: str
    thread: str
    link: bool  # waits on a transfer, or issues one
    root: bool  # an operation's root


def _is_root(name: str, has_parent: bool) -> bool:
    return not has_parent and name in ("restore", "take")


# ---------------------------------------------------------------- profile


def load_program(path: str, detail: bool = False) -> List[List[Any]]:
    """The program's events of one ``.xplane.pb``: each ``PROGRAM_PREFIX``
    event of a host plane that carries a ``span_id``, as ``[span_id,
    start_ns, dur_ns]``.  ``detail`` adds the event's name (prefix dropped),
    its stats and the name of the host line it lies on, for a reader that
    has the file alone."""
    from jax.profiler import ProfileData

    program: List[List[Any]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce._DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PROGRAM_PREFIX):
                    continue
                stats = dict(ev.stats)
                if "span_id" not in stats:
                    continue
                entry = [int(stats["span_id"]), int(ev.start_ns), int(ev.duration_ns)]
                if detail:
                    entry += [ev.name[len(PROGRAM_PREFIX):], stats, line.name]
                program.append(entry)
    return program


def load(path: str, detail: bool = False) -> Dict[str, Any]:
    """``trace_reduce.load_xplane``'s record of ``path``, and ``program``."""
    return {**trace_reduce.load_xplane(path), "program": load_program(path, detail)}


# ------------------------------------------------------------------ clock


def clock(spans: List[Any], program: List[List[Any]]) -> Optional[Dict[str, Any]]:
    """The offset from ``time.monotonic_ns`` to the profile's clock, read
    from the lexical spans that found their event: ``offset_ns``,
    ``mad_ns``, ``matched`` (share of the lexical spans), ``lexical``; and
    ``events`` (``span_id`` → (start, end)).  None as the module says."""
    events = {int(e[0]): (int(e[1]), int(e[1]) + int(e[2])) for e in program}
    # a program older than ``Span.lexical`` puts no ``span_id`` on its
    # events either: nothing matches, and nothing is read
    lexical = [s for s in spans if getattr(s, "lexical", True)]
    offsets = [
        events[s.span_id][0] - s.start_ns for s in lexical if s.span_id in events
    ]
    if not lexical or len(offsets) < MATCH_LEAST * len(lexical):
        return None
    offset = statistics.median(offsets)
    mad = statistics.median(abs(o - offset) for o in offsets)
    if mad > MAD_MOST_NS:
        return None
    return {
        "offset_ns": offset, "mad_ns": mad,
        "matched": len(offsets) / len(lexical), "lexical": len(lexical),
        "events": events,
    }


def place(spans: List[Any], clk: Dict[str, Any]) -> List[Placed]:
    events, offset = clk["events"], clk["offset_ns"]
    out = []
    for s in spans:
        found = events.get(s.span_id) if s.lexical else None
        lo, hi = found or (s.start_ns + offset, s.end_ns + offset)
        out.append(Placed(
            int(lo), int(hi), s.name, s.thread_name, bool(s.attrs.get("link")),
            _is_root(s.name, s.parent_id is not None),
        ))
    return out


# --------------------------------------------------------- interval sums


def _clip_sorted(busy: List[Interval], ends: List[int], lo: int, hi: int) -> List[Interval]:
    """``busy`` (sorted, disjoint; ``ends`` its ends) cut to [lo, hi]."""
    at = bisect.bisect_right(ends, lo)
    out = []
    for a, b in busy[at:]:
        if a >= hi:
            break
        out.append((max(a, lo), min(b, hi)))
    return out


def _overlap(xs: List[Interval], ys: List[Interval]) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _length(xs: List[Interval]) -> int:
    return sum(b - a for a, b in xs)


def _innermost(spans: List[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """One thread's spans as pieces where a span is open, each named after
    the one opened last of those open then."""
    points = sorted({p for lo, hi, _ in spans for p in (lo, hi)})
    order = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    heap: List[Tuple[int, int, int, str]] = []
    out: List[List[Any]] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(order) and order[i][0] <= a:
            lo, hi, name = order[i]
            heapq.heappush(heap, (-lo, hi, i, name))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][3]
        if out and out[-1][1] == a and out[-1][2] == name:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return [(a, b, n) for a, b, n in out]


def _by_span(gaps: List[Interval], pieces: Dict[str, List[Tuple[int, int, str]]],
             table: Dict[str, float]) -> None:
    marks = sorted(
        (t, kind, thread, name)
        for thread, segs in pieces.items()
        for lo, hi, name in segs
        for t, kind in ((lo, 1), (hi, 0))
    )
    times = sorted({m[0] for m in marks} | {t for gap in gaps for t in gap})
    active: Dict[str, str] = {}
    mi = gi = 0
    for t0, t1 in zip(times, times[1:]):
        while mi < len(marks) and marks[mi][0] <= t0:
            _, kind, thread, name = marks[mi]
            if kind:
                active[thread] = name
            else:
                active.pop(thread, None)
            mi += 1
        while gi < len(gaps) and gaps[gi][1] <= t0:
            gi += 1
        if gi == len(gaps) or gaps[gi][0] > t0:
            continue
        if not active:
            table[NO_SPAN] = table.get(NO_SPAN, 0.0) + (t1 - t0)
            continue
        share = (t1 - t0) / len(active)
        for name in active.values():
            table[name] = table.get(name, 0.0) + share


# ------------------------------------------------------------- the split


def account(
    devices: Dict[str, List[List[Any]]],
    records: Dict[str, List[Interval]],
    placed: List[Placed],
    by_span: bool = False,
) -> Dict[str, Dict[str, Any]]:
    """For each operation of ``records`` that has any: ``records``,
    ``idle_s`` and the three classes' ``<class>_s``, each a mean over the
    devices that ran any operation, per record; ``by_span`` adds
    ``by_span``, [(name, seconds a record)] by seconds."""
    busy = [
        trace_reduce.union([(s, s + d) for _, s, d in events])
        for events in devices.values() if events
    ]
    ends = [[b for _, b in device] for device in busy]
    if not busy:
        return {}
    placed = sorted(placed)
    starts = [p.lo for p in placed]
    longest = max((p.hi - p.lo for p in placed), default=0)
    out: Dict[str, Dict[str, Any]] = {}
    for op, found in records.items():
        if not found:
            continue
        sums = dict.fromkeys(("idle",) + CLASSES, 0)
        table: Dict[str, float] = {}
        for lo, hi in found:
            near = placed[bisect.bisect_left(starts, lo - longest):bisect.bisect_left(starts, hi)]
            inside = [p for p in near if p.hi > lo]
            link = trace_reduce.union(
                trace_reduce._clip([(p.lo, p.hi) for p in inside if p.link], lo, hi)
            )
            spanned = trace_reduce.union(
                trace_reduce._clip([(p.lo, p.hi) for p in inside if not p.root], lo, hi)
            )
            pieces: Dict[str, List[Tuple[int, int, str]]] = {}
            if by_span:
                threads: Dict[str, List[Tuple[int, int, str]]] = {}
                for p in inside:
                    threads.setdefault(p.thread, []).append((max(p.lo, lo), min(p.hi, hi), p.name))
                pieces = {t: _innermost(spans) for t, spans in threads.items()}
            for device, device_ends in zip(busy, ends):
                gaps = trace_reduce._gaps(_clip_sorted(device, device_ends, lo, hi), lo, hi)
                idle, on_link = _length(gaps), _overlap(gaps, link)
                in_spans = _overlap(gaps, spanned)
                sums["idle"] += idle
                sums["link"] += on_link
                sums["host"] += in_spans - on_link
                sums["unspanned"] += idle - in_spans
                if by_span:
                    _by_span(gaps, pieces, table)
        per = len(busy) * len(found) * 1e9
        out[op] = {"records": len(found), **{f"{k}_s": v / per for k, v in sums.items()}}
        if by_span:
            out[op]["by_span"] = [
                [name, ns / per] for name, ns in sorted(table.items(), key=lambda kv: -kv[1])
            ]
    return out


def _window_records(timeline: List[Dict[str, Any]], offset: float) -> Dict[str, List[Interval]]:
    picks = {
        "restore": lambda r: r["op"] == "restore",
        "take": lambda r: r["op"] == "take" and not r.get("asynchronous"),
    }
    return {
        op: sorted(
            (int(r["t0"] * 1e9 + offset), int(r["t1"] * 1e9 + offset))
            for r in timeline if pick(r)
        )
        for op, pick in picks.items()
    }


def split(
    record: Optional[Dict[str, Any]], spans: List[Any],
    timeline: List[Dict[str, Any]], by_span: bool = False,
) -> Optional[Dict[str, Any]]:
    """``{"clock": clock less its events, "restore": ..., "take": ...}`` for
    a traced window: ``record`` as ``load`` gives it, the tracer's spans and
    the harness's timeline (an operation with no record is absent); None
    as the module says."""
    if record is None or not any(record["devices"].values()):
        return None
    clk = clock(spans, record["program"])
    if clk is None:
        return None
    found: Dict[str, Any] = account(
        record["devices"], _window_records(timeline, clk["offset_ns"]),
        place(spans, clk), by_span,
    )
    found["clock"] = {k: v for k, v in clk.items() if k != "events"}
    return found


def split_record(record: Dict[str, Any]) -> Dict[str, Any]:
    """The split of a profile alone (``load(path, detail=True)``): the records are the benchmark's ``chipbench:restore``
    and ``chipbench:take`` annotations (an asynchronous take's among them),
    the spans the events, each on its own clock already."""
    records: Dict[str, List[Interval]] = {"restore": [], "take": []}
    for name, start, dur in record["spans"]:
        op = name[len(trace_reduce.SPAN_PREFIX):]
        if op in records:
            records[op].append((start, start + dur))
    placed = [
        Placed(start, start + dur, name, str(stats.get("thread", line)),
               bool(stats.get("link")), _is_root(name, "parent_id" in stats))
        for _, start, dur, name, stats, line in record["program"]
    ]
    return account(record["devices"], records, placed, by_span=True)
