"""Generator for the snaplint metric registry.

``torchsnapshot_tpu/obs/metrics.py`` is the single source of truth for
metric names (its module-level constants are what the doctor CLI, the
docs tables and bench rollups reference).  This tool derives the
registry the ``metric-registry`` pass checks against and writes it to
``tools/lint/metric_registry_data.py``:

    python -m tools.lint.gen_metric_registry

The generated file is committed; the pass re-derives the constant set
from metrics.py on every run and reports staleness as a finding, so the
registry can never silently drift from its source.

Dynamic metric families (names built with f-strings, one instrument per
backend/codec) cannot be enumerated; they are declared here as glob
patterns — adding a new family is a reviewed edit to this list plus a
regeneration, which is exactly the ratchet the pass exists to enforce.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Set

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
METRICS_SOURCE = "torchsnapshot_tpu/obs/metrics.py"
OUTPUT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "metric_registry_data.py"
)

# a registrable metric name: dotted-or-flat lowercase word segments,
# no trailing dot (PHASE_PREFIX = "phase." is a prefix, not a name)
NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)*$")

# the f-string instrument families, as globs (one `*` per runtime
# segment).  Hand-maintained BY DESIGN: a new dynamic family must be
# declared here (reviewed) before the pass accepts it.
DYNAMIC_FAMILIES = (
    "resilience.*.retries",
    "resilience.breaker_state.*",
    "storage.*.*_bytes",
    "storage.*.*_latency_s",
    "storage.codec.encode_latency_s.*",
    "storage.codec.decode_latency_s.*",
    "device_pack.bytes_w*",
    "device_unpack.bytes_w*",
)


def derive_names_from_source(source: str) -> Set[str]:
    """The metric-name constants of a metrics.py source: module-level
    ``NAME = "literal"`` assignments whose value parses as a metric
    name."""
    tree = ast.parse(source)
    out: Set[str] = set()
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not (
            isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
        ):
            continue
        value = node.value.value
        if NAME_RE.match(value):
            out.add(value)
    return out


def derive_names(root: str = _REPO_ROOT) -> Set[str]:
    with open(os.path.join(root, METRICS_SOURCE), encoding="utf-8") as f:
        return derive_names_from_source(f.read())


def render(names: Set[str]) -> str:
    lines = [
        '"""GENERATED by `python -m tools.lint.gen_metric_registry` —',
        "do not edit by hand.  Derived from",
        f"{METRICS_SOURCE} constants plus the declared dynamic",
        'families; the metric-registry pass reports staleness."""',
        "",
        "KNOWN_METRIC_NAMES = frozenset({",
    ]
    for n in sorted(names):
        lines.append(f"    {n!r},")
    lines.append("})")
    lines.append("")
    lines.append("KNOWN_METRIC_PATTERNS = (")
    for p in DYNAMIC_FAMILIES:
        lines.append(f"    {p!r},")
    lines.append(")")
    lines.append("")
    return "\n".join(lines)


def main() -> int:
    names = derive_names()
    with open(OUTPUT, "w", encoding="utf-8") as f:
        f.write(render(names))
    print(
        f"metric registry: {len(names)} names, "
        f"{len(DYNAMIC_FAMILIES)} dynamic families -> {OUTPUT}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
