"""lock-discipline: no blocking work under a lock, no unpaired acquire.

Every subsystem added in the last two PRs serializes something through a
``threading.Lock`` — the promoter queue, the metrics registry, the
tracer's span list, the memory storage dict.  Those stay healthy only
while lock bodies remain O(microseconds): the moment storage I/O, an
``open()``, a collective, or a sleep runs under a lock, every other
thread (staging executors, the promoter, the event loop's worker
threads) convoys behind one slow syscall — and a lock held across a
``barrier`` can deadlock the fleet outright (rank A holds the lock in
the barrier, rank B needs the lock to reach it).  This is RacerD-style
lock-discipline checking, lexical and per-file.

Rules:

1. **No blocking calls in lock bodies** — inside ``with <lock>:`` /
   ``async with <lock>:`` (context expression whose trailing name
   contains "lock"/"mutex", e.g. ``self._lock``, ``_REGISTRY_LOCK``),
   direct calls to ``open``, storage-plugin I/O (``sync_read``/
   ``sync_write``/``sync_stat``/``sync_delete``), ``sleep``,
   blocking-KV ``kv_get``, or any Coordinator collective are findings.
   Nested function bodies are skipped (deferred execution) — defining a
   closure under a lock is fine, calling it there is a different body.

2. **Paired acquisition** — a ``<x>.acquire()`` call in a function with
   no matching ``<x>.release()`` is a finding (an exception between the
   two leaks the lock forever; use ``with``).  Pairing is matched on
   the receiver's dotted text within one function body.

Interprocedural holes are acknowledged: a helper that opens a file,
called from a lock body, is invisible here.  The passes buy cheap,
zero-false-positive coverage of the direct cases; reviews cover the
rest.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List

from ..core import (
    SCOPE_NODES,
    FileUnit,
    Finding,
    LintPass,
    call_name,
    calls_in_body,
    walk_skipping_nested_defs,
)
from .collective_safety import COLLECTIVE_NAMES

BLOCKING_CALL_NAMES = frozenset(
    {"open", "sync_read", "sync_write", "sync_stat", "sync_delete",
     "sleep", "kv_get"}
) | COLLECTIVE_NAMES


def _lockish(expr: ast.expr) -> str:
    """The lock-like trailing name of a with-item's context expression,
    or "".  Handles ``lock``, ``self._lock``, ``a.b.big_lock`` and the
    ``lock.acquire()``-style call form ``with x.lock:`` only (calling
    ``with Lock():`` creates a fresh unshared lock — not a guard)."""
    if isinstance(expr, ast.Name):
        name = expr.id
    elif isinstance(expr, ast.Attribute):
        name = expr.attr
    else:
        return ""
    # word-boundary match on underscore segments: `_REGISTRY_LOCK`,
    # `self._lock`, `big_lock` yes; `clock`, `blocked` no
    segments = name.lower().strip("_").split("_")
    return name if any(
        s in ("lock", "rlock", "mutex") for s in segments
    ) else ""


def _receiver_text(func: ast.Attribute) -> str:
    """Dotted receiver of a method call: ``self._lock.acquire`` →
    "self._lock".  Empty for non-trivial receivers (subscripts, calls)."""
    parts: List[str] = []
    cur: ast.expr = func.value
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return ""


class LockDisciplinePass(LintPass):
    pass_id = "lock-discipline"
    description = (
        "no storage I/O / open() / collectives under a lock; "
        "acquire() must pair with release()"
    )

    def run(self, unit: FileUnit) -> Iterable[Finding]:
        out: List[Finding] = []
        # one finding per call even under nested locks (every enclosing
        # With node walks down to the same call otherwise)
        flagged: set = set()
        # Rule 1: blocking calls lexically under `with <lock>:`.
        for node in ast.walk(unit.tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            locks = [
                _lockish(it.context_expr)
                for it in node.items
                if _lockish(it.context_expr)
            ]
            if not locks:
                continue
            # with-items AFTER the first lock item evaluate while the
            # lock is already held (`with self._lock, open(p) as f:`)
            first_lock = next(
                i for i, it in enumerate(node.items)
                if _lockish(it.context_expr)
            )
            later_item_calls = [
                inner
                for it in node.items[first_lock + 1:]
                for inner in calls_in_body(it.context_expr)
            ]
            body_calls = (
                c for st in node.body for c in self._body_calls(st)
            )
            for inner in (*later_item_calls, *body_calls):
                name = call_name(inner)
                if name in BLOCKING_CALL_NAMES and id(inner) not in flagged:
                    flagged.add(id(inner))
                    out.append(
                        self.finding(
                            unit,
                            inner,
                            f"blocking call '{name}' inside `with "
                            f"{locks[0]}:` — I/O, collectives and "
                            f"sleeps under a lock convoy every "
                            f"other thread (and a barrier under a "
                            f"lock can deadlock ranks); move the "
                            f"blocking work outside the critical "
                            f"section",
                        )
                    )
        # Rule 2: acquire/release pairing per function body.
        for node in ast.walk(unit.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(self._check_pairing(unit, node))
        out.sort(key=lambda f: f.line)
        return out

    @staticmethod
    def _body_calls(st: ast.stmt) -> Iterable[ast.Call]:
        if isinstance(st, SCOPE_NODES):
            return  # a def/class under the lock runs elsewhere
        yield from calls_in_body(st)

    def _check_pairing(
        self, unit: FileUnit, fn: ast.AST
    ) -> Iterable[Finding]:
        acquires: Dict[str, List[ast.Call]] = {}
        releases: Dict[str, int] = {}
        for node in walk_skipping_nested_defs(fn):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
            ):
                continue
            recv = _receiver_text(node.func)
            if not recv:
                continue
            if node.func.attr == "acquire":
                acquires.setdefault(recv, []).append(node)
            elif node.func.attr == "release":
                releases[recv] = releases.get(recv, 0) + 1
        for recv, calls in acquires.items():
            if len(calls) > releases.get(recv, 0):
                yield self.finding(
                    unit,
                    calls[0],
                    f"'{recv}.acquire()' without a paired "
                    f"'{recv}.release()' in this function — an "
                    f"exception in between leaks the lock; use "
                    f"`with {recv}:`",
                )
