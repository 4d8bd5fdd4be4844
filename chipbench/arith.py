"""Arithmetic from a window's timeline to its end-to-end metrics.

A timeline is a list of records ``{"op", "t0", "t1", ...}`` on the host's
monotonic clock, in the order the window ran them.  Records named in
``NOT_WORK`` are the benchmark's own pauses (reading a reference digest,
checking a sampled answer): the clock they take is no part of the window.

Every end-to-end metric is the whole window over all the work in it.  None
is a single shot or a median of pieces, so a stall anywhere moves it.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

Timeline = List[Dict[str, Any]]

NOT_WORK = ("check",)


def window_seconds(timeline: Timeline) -> float:
    """First start to last end of the work, less the benchmark's own pauses
    between them (a pause before the first or after the last is outside)."""
    work = [r for r in timeline if r["op"] not in NOT_WORK]
    if not work:
        return 0.0
    start, end = min(r["t0"] for r in work), max(r["t1"] for r in work)
    paused = sum(
        r["t1"] - r["t0"] for r in timeline
        if r["op"] in NOT_WORK and start <= r["t0"] and r["t1"] <= end
    )
    return end - start - paused


def count(timeline: Timeline, op: str) -> int:
    return sum(1 for r in timeline if r["op"] == op)


def window_per_op(timeline: Timeline, op: str) -> Optional[float]:
    """Window seconds ÷ completed ``op`` records."""
    n = count(timeline, op)
    return window_seconds(timeline) / n if n else None


def clean_step_seconds(timeline: Timeline) -> Optional[float]:
    """Mean seconds of a train step with no save in flight, taken over
    whole runs of consecutive clean steps (first start to last end)."""
    total, steps, run = 0.0, 0, []

    def close() -> None:
        nonlocal total, steps, run
        if run:
            total += run[-1]["t1"] - run[0]["t0"]
            steps += len(run)
        run = []

    for r in timeline:
        if r["op"] == "step" and not r.get("in_flight"):
            run.append(r)
        elif r["op"] not in NOT_WORK:
            close()
    close()
    return total / steps if steps else None


def stall_per_cycle(timeline: Timeline) -> Optional[float]:
    """Train-loop seconds lost per async save: Σ over cycles of (cycle wall
    − its steps × clean step seconds) ÷ cycles."""
    cycles = [r for r in timeline if r["op"] == "cycle"]
    clean = clean_step_seconds(timeline)
    if not cycles or clean is None:
        return None
    lost = sum(r["t1"] - r["t0"] - r["steps"] * clean for r in cycles)
    return lost / len(cycles)


def _per_op(spec: Dict[str, Any]) -> Callable[[Timeline], Optional[float]]:
    return lambda timeline: window_per_op(timeline, spec["op"])


KINDS: Dict[str, Callable[[Dict[str, Any]], Callable[[Timeline], Optional[float]]]] = {
    "window_per_op": _per_op,
    "stall_per_cycle": lambda spec: stall_per_cycle,
}


def end_to_end(timeline: Timeline, specs: Dict[str, Dict[str, Any]]) -> Dict[str, float]:
    """The metrics a traffic file asks for, by its ``end_to_end`` table."""
    out = {}
    for name, spec in specs.items():
        if spec["kind"] not in KINDS:
            raise ValueError(f"unknown end-to-end arithmetic {spec['kind']!r}")
        value = KINDS[spec["kind"]](spec)(timeline)
        if value is not None:
            out[name] = value
    return out
