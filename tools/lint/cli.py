"""snaplint CLI: ``python -m tools.lint`` (also reachable as
``python -m torchsnapshot_tpu lint`` from a repo checkout).

Exit codes: 0 clean (allowlisted/baselined findings tolerated), 1
unbaselined findings, 2 configuration error (e.g. an allowlist entry
without a written justification)."""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from .allowlists import ALLOWLIST
from .core import (
    LintConfigError,
    check_ratchet,
    load_baseline,
    run_repo,
    save_baseline,
)
from .passes import ALL_PASSES

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.json"
)


def repo_summary(root: str = _REPO_ROOT) -> dict:
    """One-call repo lint rollup: finding counts by disposition, the
    per-pass unbaselined breakdown, per-pass wall time and the
    summary-cache hit/miss split — both the hygiene trajectory AND what
    sixteen passes cost (and how much the cache buys back)."""
    result = run_repo(
        root,
        ALL_PASSES,
        allowlist=ALLOWLIST,
        baseline=load_baseline(DEFAULT_BASELINE),
    )
    by_pass: dict = {}
    for f in result.unbaselined:
        by_pass[f.pass_id] = by_pass.get(f.pass_id, 0) + 1
    return {
        **result.summary(),
        "passes": [p.pass_id for p in ALL_PASSES],
        "unbaselined_by_pass": by_pass,
        "timings_ms": {
            pid: round(t * 1000.0, 2)
            for pid, t in result.timings.items()
        },
        "summary_cache": dict(result.summary_cache),
        "unused_allows": [
            f"{a.pass_id}:{a.file}:{a.context}"
            for a in result.unused_allows
        ],
    }


def changed_files(root: str, ref: str) -> Optional[set]:
    """Files changed vs ``ref`` (worktree + index, plus untracked) —
    the ``--changed`` scope, as paths relative to ``root``.  None when
    git is unavailable or ``root`` is not a checkout (the caller falls
    back to a full run rather than silently linting nothing).

    ``git diff --name-only`` emits toplevel-relative paths while the
    scanner's relpaths are root-relative; when ``root`` sits below the
    toplevel (a vendored tree in a monorepo), diff paths are filtered
    to the subtree and re-based via ``rev-parse --show-prefix`` —
    without that, every diff path would miss every unit and the run
    would silently lint nothing."""
    import subprocess

    def run(args):
        try:
            proc = subprocess.run(
                args, capture_output=True, text=True, timeout=30,
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        return proc.stdout if proc.returncode == 0 else None

    # -c core.quotepath=off: with git's default quoting, a non-ASCII
    # filename comes back escaped-and-quoted, matches no unit relpath
    # and would be silently skipped
    git = ["git", "-C", root, "-c", "core.quotepath=off"]
    prefix_out = run([*git, "rev-parse", "--show-prefix"])
    if prefix_out is None:
        return None
    prefix = prefix_out.strip()
    out: set = set()
    diff = run([*git, "diff", "--name-only", ref, "--"])
    if diff is None:
        return None
    for line in diff.splitlines():
        line = line.strip()
        if not line:
            continue
        if prefix:
            if not line.startswith(prefix):
                continue  # changed outside the scanned subtree
            line = line[len(prefix):]
        out.add(line)
    # untracked: ls-files paths are already relative to the -C dir
    untracked = run([*git, "ls-files", "--others", "--exclude-standard"])
    if untracked is None:
        return None
    out.update(
        line.strip() for line in untracked.splitlines() if line.strip()
    )
    return out


def _github_escape(text: str) -> str:
    """Workflow-command data escaping: %, CR and LF are the three
    characters the runner's parser consumes."""
    return (
        text.replace("%", "%25").replace("\r", "%0D").replace("\n", "%0A")
    )


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.lint",
        description=(
            "snaplint: AST static analysis for concurrency, "
            "collective-safety and exception hygiene"
        ),
    )
    parser.add_argument(
        "root", nargs="?", default=_REPO_ROOT,
        help="repo root to scan (default: this checkout)",
    )
    parser.add_argument(
        "--json", action="store_true",
        help="machine-readable output (alias for --format json)",
    )
    parser.add_argument(
        "--format", choices=("text", "json", "github"), default=None,
        help="output format: text (default), json, or github "
        "workflow-command annotations (::error file=...,line=...:: "
        "per unbaselined finding — CI surfaces them inline on the PR "
        "diff)",
    )
    parser.add_argument(
        "--baseline", default=DEFAULT_BASELINE,
        help="baseline ratchet file (default: tools/lint/baseline.json)",
    )
    parser.add_argument(
        "--no-baseline", action="store_true",
        help="report every finding, ignoring the baseline ratchet",
    )
    parser.add_argument(
        "--update-baseline", action="store_true",
        help="rewrite the baseline from current findings — refused if "
        "any fingerprint count would grow (the ratchet only goes down)",
    )
    parser.add_argument(
        "--force-baseline-growth", action="store_true",
        help="override the ratchet refusal (requires review)",
    )
    parser.add_argument(
        "--pass", dest="passes", action="append", default=None,
        metavar="PASS_ID",
        help="run only the named pass(es); repeatable",
    )
    parser.add_argument(
        "--changed", nargs="?", const="HEAD", default=None,
        metavar="REF",
        help="pre-commit mode: per-file passes report only on files "
        "changed vs REF (default HEAD; worktree+index+untracked).  "
        "Every file is still parsed and the interprocedural passes "
        "still run package-wide — reusing the summary cache for "
        "unchanged dependencies — because a rename in a changed file "
        "can orphan a consumer in an unchanged one",
    )
    parser.add_argument(
        "--list-passes", action="store_true",
        help="list registered passes and exit",
    )
    args = parser.parse_args(argv)
    if args.format is None:
        args.format = "json" if args.json else "text"
    elif args.json and args.format != "json":
        print(
            "error: --json conflicts with --format "
            f"{args.format}", file=sys.stderr,
        )
        return 2

    if args.list_passes:
        for p in ALL_PASSES:
            print(f"{p.pass_id:<20} {p.description}")
        return 0

    if args.update_baseline and args.changed is not None:
        # a changed-subset rewrite would erase every fingerprint owed
        # by the unchanged files — same partial-scope hazard as --pass
        print(
            "error: --update-baseline and --changed conflict "
            "(the rewrite must come from a full-scope run)",
            file=sys.stderr,
        )
        return 2

    if args.update_baseline and args.no_baseline:
        # --no-baseline would make the rewrite ratchet against an
        # empty dict, reporting every legitimately-baselined finding
        # as spurious growth
        print(
            "error: --update-baseline and --no-baseline conflict "
            "(the rewrite must ratchet against the on-disk baseline)",
            file=sys.stderr,
        )
        return 2

    passes = ALL_PASSES
    if args.passes:
        known = {p.pass_id for p in ALL_PASSES}
        unknown = [x for x in args.passes if x not in known]
        if unknown:
            print(
                f"error: unknown pass(es) {unknown}; known: "
                f"{sorted(known)}",
                file=sys.stderr,
            )
            return 2
        passes = tuple(
            p for p in ALL_PASSES if p.pass_id in set(args.passes)
        )

    only_files = None
    if args.changed is not None:
        only_files = changed_files(args.root, args.changed)
        if only_files is None:
            print(
                f"warning: cannot resolve changed files vs "
                f"{args.changed!r} (not a git checkout?); running the "
                f"full scan",
                file=sys.stderr,
            )

    try:
        baseline = (
            {} if args.no_baseline else load_baseline(args.baseline)
        )
        result = run_repo(
            args.root, passes, allowlist=ALLOWLIST, baseline=baseline,
            only_files=only_files,
        )
    except LintConfigError as e:
        print(f"lint configuration error: {e}", file=sys.stderr)
        return 2

    # staleness is only decidable on a FULL run: a --pass or --changed
    # subset never matches the skipped scope's allowlist entries, and
    # reporting them as stale would invite deleting entries the full
    # run still needs
    partial = bool(args.passes) or only_files is not None
    unused_allows = [] if partial else result.unused_allows

    if args.update_baseline:
        # a rewrite must come from a FULL-scope run: findings from a
        # pass subset (or another tree against this checkout's default
        # baseline file) would silently delete every fingerprint the
        # skipped scope still owes
        if args.passes:
            print(
                "error: --update-baseline requires a full run "
                "(drop --pass: a subset rewrite would erase other "
                "passes' baselined fingerprints)",
                file=sys.stderr,
            )
            return 2
        same_root = os.path.realpath(args.root) == os.path.realpath(
            _REPO_ROOT
        )
        default_baseline = os.path.realpath(
            args.baseline
        ) == os.path.realpath(DEFAULT_BASELINE)
        if not same_root and default_baseline:
            print(
                f"error: refusing to rewrite this checkout's default "
                f"baseline from a scan of {args.root!r}; pass "
                f"--baseline <file> for that tree",
                file=sys.stderr,
            )
            return 2
        # everything not allowlisted is baseline candidate material
        candidates = result.baselined + result.unbaselined
        growth = check_ratchet(baseline, candidates)
        if growth and not args.force_baseline_growth:
            for g in growth:
                print(f"ratchet violation: {g}", file=sys.stderr)
            print(
                "refusing to grow the baseline (counts only go down); "
                "fix or allowlist the new findings, or pass "
                "--force-baseline-growth after review",
                file=sys.stderr,
            )
            return 1
        counts = save_baseline(args.baseline, candidates)
        print(
            f"baseline updated: {sum(counts.values())} finding(s) "
            f"across {len(counts)} fingerprint(s) -> {args.baseline}"
        )
        return 0

    if args.format == "github":
        # one workflow-command annotation per actionable finding; stale
        # allowlist entries surface as warnings pinned to the allowlist
        for f in result.unbaselined:
            print(
                f"::error file={f.file},line={f.line},"
                f"title=snaplint {f.pass_id}::"
                f"{_github_escape(f.message)}"
            )
        for a in unused_allows:
            print(
                f"::warning file=tools/lint/allowlists.py,"
                f"title=snaplint stale-allow::"
                f"{_github_escape(f'{a.pass_id}:{a.file}:{a.context} matches nothing')}"
            )
        s = result.summary()
        print(
            f"::notice title=snaplint::{s['files_scanned']} files, "
            f"{len(passes)} passes, {s['unbaselined']} actionable"
        )
    elif args.format == "json":
        print(
            json.dumps(
                {
                    **result.summary(),
                    "unbaselined": [
                        f.to_dict() for f in result.unbaselined
                    ],
                    "baselined": [f.to_dict() for f in result.baselined],
                    "allowlisted": [
                        f.to_dict() for f in result.allowlisted
                    ],
                    # stale suppressions: machine consumers must see
                    # them too, or dead entries linger forever
                    "unused_allows": [
                        f"{a.pass_id}:{a.file}:{a.context}"
                        for a in unused_allows
                    ],
                },
                indent=2,
            )
        )
    else:
        for f in result.unbaselined:
            print(f.render())
        for a in unused_allows:
            print(
                f"warning: stale allowlist entry matches nothing: "
                f"{a.pass_id}:{a.file}:{a.context}",
                file=sys.stderr,
            )
        s = result.summary()
        print(
            f"snaplint: {s['files_scanned']} files, "
            f"{len(passes)} pass(es): {s['unbaselined']} actionable, "
            f"{s['baselined']} baselined, {s['allowlisted']} "
            f"allowlisted finding(s)"
        )
    return 0 if result.ok else 1
