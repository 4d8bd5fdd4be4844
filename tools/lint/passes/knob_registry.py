"""knob-registry: every tunable is read through knobs.py, nowhere else.

knobs.py is the single resolution chain (override → env → default) for
every ``TORCHSNAPSHOT_TPU_*`` variable: that is what makes the
context-manager test overrides, the documented default table, and the
api_reference knob listing complete.  A direct ``os.environ`` read
elsewhere forks the source of truth — the knob silently stops honoring
``knobs.override_*`` in tests and disappears from the docs.

Flagged env-read forms (``os.environ.get``/``[...]``/``setdefault``/
``pop``, ``os.getenv``, and the membership test
``"KEY" in os.environ``) with a string-literal key:

- keys starting with ``TORCHSNAPSHOT_TPU_`` anywhere except
  ``torchsnapshot_tpu/knobs.py``;
- keys starting with ``TSNP_`` inside the ``torchsnapshot_tpu``
  package (library code must route legacy-prefixed tunables through a
  knobs.py accessor too; repo tooling outside the package may keep its
  own ``TSNP_*`` process controls).

Non-literal keys can't be checked lexically; the prefix constant in
knobs.py stays the one sanctioned concatenation site.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional

from ..core import FileUnit, Finding, LintPass

_KNOBS_FILE = "torchsnapshot_tpu/knobs.py"
_PKG_PREFIX = "torchsnapshot_tpu/"
_ENV_METHODS = frozenset({"get", "setdefault", "pop", "getenv"})


def _literal_key(node: ast.AST) -> Optional[str]:
    """The string-literal env key of an environ access, else None."""
    if isinstance(node, ast.Call):
        if not node.args:
            return None
        arg = node.args[0]
    elif isinstance(node, ast.Subscript):
        arg = node.slice
    elif isinstance(node, ast.Compare):
        arg = node.left
    else:
        return None
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    return None


def _is_environ_expr(e: ast.AST) -> bool:
    """Is ``e`` the environ mapping itself (``os.environ`` or a bare
    ``environ`` import)?"""
    return (isinstance(e, ast.Attribute) and e.attr == "environ") or (
        isinstance(e, ast.Name) and e.id == "environ"
    )


def _is_environ_access(node: ast.AST) -> bool:
    """``os.environ.get/.setdefault/.pop``, ``os.environ[...]``,
    ``environ.get``, ``os.getenv``, ``"KEY" in os.environ``."""
    if isinstance(node, ast.Subscript):
        return _is_environ_expr(node.value)
    if isinstance(node, ast.Compare):
        # `"KEY" in os.environ` / `"KEY" not in os.environ` — an env
        # READ like any other (presence gates a code path)
        return (
            len(node.ops) == 1
            and isinstance(node.ops[0], (ast.In, ast.NotIn))
            and len(node.comparators) == 1
            and _is_environ_expr(node.comparators[0])
        )
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        # `from os import getenv; getenv(...)` — bare-name form
        return node.func.id == "getenv"
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
        func = node.func
        if func.attr == "getenv":
            return True
        if func.attr in _ENV_METHODS and _is_environ_expr(func.value):
            return True
    return False


class KnobRegistryPass(LintPass):
    pass_id = "knob-registry"
    description = (
        "TORCHSNAPSHOT_TPU_*/TSNP_* env reads belong in knobs.py only"
    )

    def run(self, unit: FileUnit) -> Iterable[Finding]:
        if unit.relpath == _KNOBS_FILE:
            return []
        in_pkg = unit.relpath.startswith(_PKG_PREFIX)
        out: List[Finding] = []
        for node in ast.walk(unit.tree):
            if not _is_environ_access(node):
                continue
            key = _literal_key(node)
            if key is None:
                continue
            if key.startswith("TORCHSNAPSHOT_TPU_") or (
                in_pkg and key.startswith("TSNP_")
            ):
                out.append(
                    self.finding(
                        unit,
                        node,
                        f"direct environment read of {key!r} — route "
                        f"it through a knobs.py accessor so override_* "
                        f"test hooks, the default table and the "
                        f"api_reference knob listing stay complete",
                    )
                )
        return out
