"""Randomized property fuzz for the budgeted scheduler (the hot loop).

Targeted tests (`tests/test_scheduler.py`) pin each behavior once; this
file drives random workload matrices — payload sizes spanning tiny to
OVER-BUDGET, random budgets, io-concurrency caps, storage delays, and
write-failure injection — and asserts the properties the design
promises for every mix (reference scheduler.py:222-339 semantics):

- termination: every workload completes, no deadlock;
- budget admission: peak live staged bytes never exceeds
  max(budget, largest single payload) — the oversized-progress rule
  admits an over-budget item only into an EMPTY pipeline;
- io cap: concurrent storage writes never exceed the knob;
- integrity: every payload lands byte-exact, and a mirrored read
  pipeline returns every payload byte-exact under its own budget;
- failure: an injected write error always propagates.

A 300-seed offline campaign of this generator passed clean; CI runs a
slice.
"""

import threading

import numpy as np
import pytest

from torchsnapshot_tpu import knobs
from torchsnapshot_tpu.io_types import ReadReq, WriteReq
from torchsnapshot_tpu.scheduler import (
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)
from test_scheduler import CollectConsumer, TrackingStorage

from torchsnapshot_tpu.io_types import BufferStager


class _Stager(BufferStager):
    """Stager with instance-shared live/peak accounting (class-level
    counters would leak across fuzz iterations)."""

    def __init__(self, payload: bytes, stats: dict, lock: threading.Lock):
        self.payload = payload
        self.stats = stats
        self.lock = lock

    async def stage_buffer(self, executor=None):
        with self.lock:
            self.stats["live"] += len(self.payload)
            self.stats["peak"] = max(self.stats["peak"], self.stats["live"])
        return self.payload

    def get_staging_cost_bytes(self):
        return len(self.payload)


def _run_seed(seed: int) -> None:
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 50))
    budget = int(rng.integers(1, 100)) * 1024
    io_cap = int(rng.integers(1, 9))
    delay = float(rng.choice([0.0, 0.0, 0.001, 0.005]))
    fail = bool(rng.integers(0, 8) == 0)

    payloads = {}
    for i in range(n):
        tier = int(rng.integers(0, 4))
        size = [
            int(rng.integers(1, 64)),
            int(rng.integers(64, 4096)),
            int(rng.integers(4096, 65536)),
            # over-budget tier: exercises the oversized-progress rule
            budget + int(rng.integers(1, 65536)),
        ][tier]
        payloads[f"p{i}"] = bytes(rng.integers(0, 256, size=size, dtype=np.uint8))

    stats = {"live": 0, "peak": 0}
    lock = threading.Lock()
    # live tracks staged-but-unwritten bytes (the quantity the budget
    # bounds); TrackingStorage decrements it on write completion via
    # the same mechanism its track_budget mode uses
    storage = TrackingStorage(delay=delay, budget_stats=stats, budget_lock=lock)
    if fail:
        storage.fail_on = f"p{int(rng.integers(n))}"

    reqs = [
        WriteReq(path=k, buffer_stager=_Stager(v, stats, lock))
        for k, v in payloads.items()
    ]
    with knobs.override_max_per_rank_io_concurrency(io_cap):
        if fail:
            with pytest.raises(Exception, match="injected failure"):
                sync_execute_write_reqs(
                    reqs, storage, memory_budget_bytes=budget, rank=0
                ).sync_complete()
            return  # partial writes are legal after a failure
        sync_execute_write_reqs(
            reqs, storage, memory_budget_bytes=budget, rank=0
        ).sync_complete()

    assert storage.max_concurrent <= io_cap, (
        f"seed {seed}: io cap violated {storage.max_concurrent} > {io_cap}"
    )
    largest = max(len(v) for v in payloads.values())
    assert stats["peak"] <= max(budget, largest), (
        f"seed {seed}: budget violated: peak {stats['peak']} > "
        f"max({budget}, {largest})"
    )
    assert stats["live"] == 0, f"seed {seed}: leaked staged bytes"
    for k, v in payloads.items():
        assert storage.writes[k] == v, f"seed {seed}: payload {k} corrupt"

    # mirrored read pipeline under its own random budget
    got = {}
    read_budget = int(rng.integers(1, 100)) * 1024
    read_reqs = [
        ReadReq(
            path=k,
            buffer_consumer=CollectConsumer(got, k, cost=len(v)),
        )
        for k, v in payloads.items()
    ]
    with knobs.override_max_per_rank_io_concurrency(io_cap):
        sync_execute_read_reqs(
            read_reqs, storage, memory_budget_bytes=read_budget, rank=0
        )
    for k, v in payloads.items():
        assert got[k] == v, f"seed {seed}: read-back {k} corrupt"


def test_scheduler_fuzz_campaign():
    """Seeds 0-11 in ONE subprocess under a hard timeout: termination is
    an ASSERTED property — a deadlocked scheduler fails with a
    diagnostic instead of hanging CI (the repo has no global pytest
    timeout, and an in-process thread timeout cannot reap a truly
    deadlocked worker at interpreter exit)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [
            sys.executable,
            "-c",
            "import sys\n"
            f"sys.path.insert(0, {repo!r})\n"
            f"sys.path.insert(0, {os.path.join(repo, 'tests')!r})\n"
            "from test_scheduler_fuzz import _run_seed\n"
            "for seed in range(12):\n"
            "    _run_seed(seed)\n"
            "print('SCHED_FUZZ_OK')\n",
        ],
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
        },
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SCHED_FUZZ_OK" in out.stdout
