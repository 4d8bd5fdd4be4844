"""RSS sampling for proving bounded-memory behavior in benchmarks.

Reference: torchsnapshot/rss_profiler.py:34-58 — a background thread
samples psutil RSS deltas at a fixed interval while the context is active.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator, List

import psutil

_DEFAULT_INTERVAL_S = 0.1


@contextlib.contextmanager
def measure_rss_deltas(
    rss_deltas: List[int], interval_s: float = _DEFAULT_INTERVAL_S
) -> Iterator[None]:
    """Append RSS-minus-baseline samples (bytes) to ``rss_deltas`` while
    the context is active; peak = max(rss_deltas)."""
    proc = psutil.Process()
    baseline = proc.memory_info().rss
    stop = threading.Event()

    def sample() -> None:
        while not stop.is_set():
            rss_deltas.append(proc.memory_info().rss - baseline)
            stop.wait(interval_s)

    thread = threading.Thread(target=sample, name="tsnp-rss", daemon=True)
    thread.start()
    try:
        yield
    finally:
        stop.set()
        thread.join()
        rss_deltas.append(proc.memory_info().rss - baseline)
        # benchmarks read memory and timing through one surface: the
        # observed peak lands in the metrics registry alongside the
        # pipeline counters (obs.metrics_snapshot)
        from .obs import metrics as _metrics

        _metrics.gauge(_metrics.RSS_PEAK_DELTA_BYTES).set(
            max(rss_deltas, default=0)
        )
