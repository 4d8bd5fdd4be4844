"""What the readers of the program's spans share: which spans of a traced
window lie inside one of its ``restore`` records, and the means per restore
over them; and the same for its blocking ``take`` records, per save.  Spans
(``ctx.spans``, ``time.monotonic_ns``) and the timeline (``time.monotonic``)
are on one clock.

A reader built on ``seconds``, ``attr`` or ``workers`` returns None for a
window with no restore, and for a program that does not partition its
restores (no ``restore/pipeline`` span: the commits before PR 26); and 0.0
for a phase that did not occur in a partitioned restore (no device unpack
on a CPU).
"""

from __future__ import annotations

import bisect
from typing import Any, Callable, Dict, List, Optional, Tuple


def _inside(ctx: Any, picks: Callable[[Dict[str, Any]], bool]) -> List[Tuple[Any, Dict[str, Any]]]:
    """Each span that starts inside one of the timeline's picked records,
    with that record."""
    records = sorted((r for r in ctx.timeline if picks(r)), key=lambda r: r["t0"])
    starts = [r["t0"] for r in records]
    out = []
    for s in ctx.spans:
        at = s.start_ns / 1e9
        i = bisect.bisect_right(starts, at) - 1
        if i >= 0 and at <= records[i]["t1"]:
            out.append((s, records[i]))
    return out


class Restores:
    """The spans that start inside one of the window's ``restore`` records,
    and each restore's root (the API bracket) with its record."""

    def __init__(self, ctx: Any) -> None:
        found = _inside(ctx, lambda r: r["op"] == "restore")
        self.inside: List[Any] = [s for s, _ in found]
        self.roots: List[Tuple[Any, Dict[str, Any]]] = [
            (s, record) for s, record in found
            if s.name == "restore" and s.parent_id is None
        ]
        self.partitioned = any(s.name == "restore/pipeline" for s in self.inside)

    def total(self, picks: Callable[[Any], bool], value: Callable[[Any], float]) -> Optional[float]:
        """Σ ``value`` over the picked spans ÷ restores that have a root."""
        if not self.roots or not self.partitioned:
            return None
        return sum(value(s) for s in self.inside if picks(s)) / len(self.roots)


def named(*names: str) -> Callable[[Any], bool]:
    return lambda s: s.name in names


def under(prefix: str) -> Callable[[Any], bool]:
    return lambda s: s.name.startswith(prefix)


def _window(ctx: Any) -> Restores:
    # one pass over the spans for all the readers of a run
    window = getattr(ctx, "_span_reads_window", None)
    if window is None:
        window = ctx._span_reads_window = Restores(ctx)
    return window


def seconds(ctx: Any, picks: Callable[[Any], bool]) -> Optional[float]:
    """Σ durations of the picked spans, seconds per restore."""
    return _window(ctx).total(picks, lambda s: s.duration_ns / 1e9)


def attr(ctx: Any, picks: Callable[[Any], bool], key: str, scale: float = 1.0) -> Optional[float]:
    """Σ of one numeric attribute of the picked spans, per restore."""
    return _window(ctx).total(picks, lambda s: s.attrs.get(key, 0) * scale)


def workers(ctx: Any) -> Optional[int]:
    """Size of the consume pool: ``workers`` on ``restore/pipeline``."""
    sizes = [
        s.attrs["workers"] for s in _window(ctx).inside
        if s.name == "restore/pipeline" and "workers" in s.attrs
    ]
    return max(sizes) if sizes else None


def tail_wait(ctx: Any) -> Optional[float]:
    """Mean of (the ``restore`` record's end − the end of the program's root
    span inside it): the benchmark in ``block_until_ready`` after
    ``Snapshot.restore`` returned.  Needs no partition, only the root."""
    window = _window(ctx)
    if not window.roots:
        return None
    waits = [record["t1"] - root.end_ns / 1e9 for root, record in window.roots]
    return sum(waits) / len(waits)


# ------------------------------------------------------------------ saves


def _saves(ctx: Any) -> Tuple[List[Any], int]:
    """The spans that start inside one of the window's blocking ``take``
    records (the call returns at the commit, so every span of a save starts
    inside its record), and how many such records hold a ``take/pipeline``."""
    saves = getattr(ctx, "_span_reads_saves", None)
    if saves is None:
        found = _inside(
            ctx, lambda r: r["op"] == "take" and not r.get("asynchronous")
        )
        piped = {id(record) for s, record in found if s.name == "take/pipeline"}
        saves = ctx._span_reads_saves = ([s for s, _ in found], len(piped))
    return saves


def _per_save(ctx: Any, picks: Callable[[Any], bool], value: Callable[[Any], float]) -> Optional[float]:
    """Σ ``value`` over the picked spans ÷ saves; None for a window with no
    blocking take, or one the program recorded no pipeline in."""
    inside, n = _saves(ctx)
    return sum(value(s) for s in inside if picks(s)) / n if n else None


def save_seconds(ctx: Any, picks: Callable[[Any], bool]) -> Optional[float]:
    """Σ durations of the picked spans, seconds per save."""
    return _per_save(ctx, picks, lambda s: s.duration_ns / 1e9)


def save_attr(ctx: Any, picks: Callable[[Any], bool], key: str, scale: float = 1.0) -> Optional[float]:
    """Σ of one numeric attribute of the picked spans, per save."""
    return _per_save(ctx, picks, lambda s: s.attrs.get(key, 0) * scale)
