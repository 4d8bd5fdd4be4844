"""One run of one cell of ``BENCHMARK.json``:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with as many chips as the cell asks for, and exits with code 3
and no result line without one; it never falls back to the CPU.  A cell
whose mix asks for a RAM-backed sink exits with code 4 and no result line
where TMPDIR is no tmpfs and the run may mount none of its own there, or
where that has no room; it never falls back to a disk.  The last line of
standard output is the result (PERF.md says what its keys mean).
"""

from __future__ import annotations

import argparse
import os
import signal
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--fault", default=None,
        choices=("control_bf16", "answer_altered", "late_snapshot"),
        help="plant the control or a fault under the run: it has to come out "
        "as not correct (PERF.md); no run of the benchmark passes this",
    )
    args = parser.parse_args(argv)

    import psutil

    from chipbench import bench

    try:
        result = bench.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            started_at=psutil.Process().create_time(), fault=args.fault,
        )
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return bench.NO_CHIP
    except bench.NoSink as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return bench.NO_SINK
    bench.print_result(result)
    return 0


if __name__ == "__main__":
    # a run that is told to end still removes its snapshots (``finally``)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(128 + signal.SIGTERM))
    sys.exit(main())
