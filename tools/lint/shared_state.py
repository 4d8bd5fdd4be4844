"""Shared-mutable-state inference: which ``self.<attr>`` / module-global
stores are reachable from more than one execution domain, and which
locks guard each access.

Two halves, split exactly like summaries.py:

**Extraction** (``extract_conc``, cacheable per file): one extra walk
per function body recording

- ``spawns``  — thread/executor/signal/event-loop seeding sites
  (recognition lives in domains.spawn_records);
- ``acc``     — every ``self.<attr>`` / module-global access as
  ``[owner, field, rw, locks, lineno, sanction, const]``.  ``locks``
  is the lexical lockset at the access: the ``with <lock>:`` frames
  open around it plus any ``<lock>.acquire()`` region earlier in the
  same statement list (a release ends the region; an unreleased
  acquire conservatively runs to the end of its block).  ``sanction``
  marks accesses that only feed a thread-safe receiver method
  (``q.put``, ``evt.set``, ``loop.call_soon_threadsafe``, the
  resource-pairing verbs) — the blessed cross-domain handoffs.
  Mutator receiver methods (``d.update``, ``l.append``) count as
  stores: container contents are the field's state;
- ``lockacq`` — every acquisition with the locks already held at that
  point (the lock-order pass's edge source);
- ``heldcalls`` — call sites executed while ≥1 lock is held, so the
  model can join locksets ACROSS calls (a helper whose every caller
  holds ``self._lock`` has that lock in its entry lockset).

**Model** (``ConcurrencyModel``, built once per project run): joins the
cached facts with domains.DomainMap over the call graph —

- must-entry locksets: intersection over call sites of (caller's
  must-entry ∪ locks held at the site); seeded roots (public API,
  thread targets, async defs) start at ∅.  An access's effective
  lockset is its lexical set ∪ its function's must-entry set: the
  Eraser lockset algorithm (Savage et al. 1997) lifted through the
  call graph;
- may-entry locksets (union form) feeding interprocedural lock-order
  edges: a lock held somewhere up the call chain orders before every
  lock acquired below;
- the field map: ``(file, Class|<module>, name)`` → accesses with
  effective locksets and accessor domains.  ``__init__``/
  ``__post_init__`` bodies are exempt (pre-publication), as are
  load-only fields, lock-valued attributes, and latch fields whose
  every post-init store is a bare True/False/None constant (a
  GIL-atomic flag flip cannot tear; check-then-act on one is still
  reported by the race pass when locksets prove it).

``@domain_private("<justification ≥20 chars>")`` on a class suppresses
race/crossing findings for its fields through the same written-
justification contract as the allowlist (core._MIN_JUSTIFICATION_CHARS);
a short justification is itself a finding, not an exemption.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from .core import (
    SCOPE_NODES,
    FileUnit,
    call_name,
    walk_skipping_nested_defs,
)
from .interproc import FKey, Project

# receiver methods that are themselves synchronization / handoff
# primitives: an access whose ONLY use is one of these calls is a
# sanctioned cross-domain touch (queue handoff, Event latch, loop
# handoff, the resource-pairing verbs resource_pairing.SPECS models)
THREADSAFE_RECV = frozenset(
    {
        # queue.Queue / deque handoffs
        "put", "get", "put_nowait", "get_nowait", "task_done", "qsize",
        "empty", "full",
        # threading.Event / Condition / Thread lifecycle
        "set", "is_set", "clear", "wait", "wait_for", "notify",
        "notify_all", "join", "start", "is_alive", "cancel",
        # lock objects held in non-lockish-named fields
        "acquire", "release", "locked",
        # event-loop / executor handoffs
        "call_soon_threadsafe", "call_soon", "call_later", "call_at",
        "run_in_executor", "submit", "shutdown", "add_done_callback",
        # obs counters/histograms serialize internally
        "inc", "observe",
        # resource-pairing SPECS verbs (byte-gate/budget/breaker)
        "reserve", "debit", "credit", "allow", "check",
        "record_success", "record_failure", "release_probe",
    }
)

# receiver methods that mutate the receiver in place: the access is a
# STORE on the field (the container's contents are the shared state)
MUTATOR_RECV = frozenset(
    {
        "append", "extend", "insert", "remove", "discard", "add",
        "update", "setdefault", "pop", "popitem", "sort", "reverse",
        "appendleft", "popleft", "write",
    }
)

_INIT_EXEMPT = frozenset({"__init__", "__post_init__"})


def _lock_segments(name: str) -> bool:
    """lock_discipline's word-boundary rule on a bare string, plus the
    plural/guard forms lock REGISTRIES use (``_INDEX_LOCKS``,
    ``_LOCKS_GUARD``): ``_REGISTRY_LOCK``/``self._lock``/``index_lock``
    yes, ``clock``/``blocked`` no.  A dict OF locks is synchronization
    plumbing, not shared application state."""
    segs = name.lower().strip("_").split("_")
    return any(
        s in ("lock", "locks", "rlock", "mutex", "guard") for s in segs
    )


def _trailing_receiver(expr: ast.expr) -> str:
    parts: List[str] = []
    cur = expr
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
    return parts[-1] if parts else ""


def _module_state_names(unit: FileUnit) -> FrozenSet[str]:
    """Names bound by module top-level assignments — the global half of
    the shared-state universe (memoized per unit)."""
    got = getattr(unit, "_conc_module_state", None)
    if got is not None:
        return got
    names: Set[str] = set()
    for st in unit.tree.body:
        if isinstance(st, ast.Assign):
            for t in st.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        names.add(n.id)
        elif isinstance(st, (ast.AnnAssign, ast.AugAssign)):
            if isinstance(st.target, ast.Name):
                names.add(st.target.id)
    out = frozenset(names)
    try:
        unit._conc_module_state = out
    except AttributeError:
        pass
    return out


class _ConcExtractor:
    """One function body's concurrency facts (see module docstring for
    the record grammar)."""

    def __init__(self, unit: FileUnit, qualname: str, fn: ast.AST) -> None:
        self.unit = unit
        self.fn = fn
        self.module_state = _module_state_names(unit)
        self.cls_name = self._enclosing_class_name(fn)
        self.gdecls: Set[str] = set()
        self.local_bound: Set[str] = set()
        self._scan_bindings(fn)
        self.spawns: List[List] = []
        self.acc: List[List] = []
        self.lockacq: List[List] = []
        self.heldcalls: List[List] = []

    def _enclosing_class_name(self, fn: ast.AST) -> str:
        cur = fn
        parents = self.unit.parents
        while cur in parents:
            cur = parents[cur]
            if isinstance(cur, ast.ClassDef):
                return cur.name
        return ""

    def _scan_bindings(self, fn: ast.AST) -> None:
        args = getattr(fn, "args", None)
        if args is not None:
            for a in (
                *getattr(args, "posonlyargs", ()), *args.args,
                *args.kwonlyargs,
            ):
                self.local_bound.add(a.arg)
            if args.vararg:
                self.local_bound.add(args.vararg.arg)
            if args.kwarg:
                self.local_bound.add(args.kwarg.arg)
        for node in walk_skipping_nested_defs(fn):
            if isinstance(node, ast.Global):
                self.gdecls.update(node.names)
            elif isinstance(node, ast.Name) and isinstance(
                node.ctx, ast.Store
            ):
                self.local_bound.add(node.id)
        self.local_bound -= self.gdecls

    # ---------------------------------------------------- lock ids

    def _lock_id(self, expr: ast.expr) -> str:
        """Stable identity of a lock-like expression, "" for non-locks.
        ``self._lock`` → "Class._lock" (one id for every method),
        module-level ``_LOCK`` → "<relpath>:_LOCK", the factory form
        ``with index_lock(root):`` → "index_lock()" (one id across
        modules — per-root instances of one keyed guard)."""
        if isinstance(expr, ast.Call):
            n = call_name(expr)
            return f"{n}()" if n and _lock_segments(n) else ""
        if isinstance(expr, ast.Attribute):
            if not _lock_segments(expr.attr):
                return ""
            if (
                isinstance(expr.value, ast.Name)
                and expr.value.id in ("self", "cls")
                and self.cls_name
            ):
                return f"{self.cls_name}.{expr.attr}"
            recv = _trailing_receiver(expr.value)
            return f"{recv}.{expr.attr}" if recv else expr.attr
        if isinstance(expr, ast.Name):
            if not _lock_segments(expr.id):
                return ""
            if expr.id in self.module_state and expr.id not in self.local_bound:
                return f"{self.unit.relpath}:{expr.id}"
            return f"local:{expr.id}"
        return ""

    # ------------------------------------------------------- walk

    def run(self) -> Dict:
        self._walk_block(self.fn.body, [])
        out: Dict = {}
        if self.spawns:
            out["spawns"] = self.spawns
        if self.acc:
            out["acc"] = self.acc
        if self.lockacq:
            out["lockacq"] = self.lockacq
        if self.heldcalls:
            out["heldcalls"] = self.heldcalls
        return out

    @staticmethod
    def _stmt_lists(st: ast.stmt) -> Iterable[List[ast.stmt]]:
        for _f, v in ast.iter_fields(st):
            if not isinstance(v, list) or not v:
                continue
            if isinstance(v[0], ast.stmt):
                yield v
            elif isinstance(v[0], ast.excepthandler):
                for h in v:
                    yield h.body
            elif type(v[0]).__name__ == "match_case":
                for c in v:
                    yield c.body

    def _walk_block(self, stmts: List[ast.stmt], held: List[str]) -> None:
        held = list(held)  # a block never leaks regions to its parent
        for st in stmts:
            if isinstance(st, SCOPE_NODES):
                continue  # nested defs carry their own summaries
            if isinstance(st, (ast.With, ast.AsyncWith)):
                inner = list(held)
                for it in st.items:
                    self._visit_expr(it.context_expr, inner)
                    lid = self._lock_id(it.context_expr)
                    if lid:
                        self.lockacq.append(
                            [lid, sorted(set(inner)), it.context_expr.lineno]
                        )
                        inner.append(lid)
                self._walk_block(st.body, inner)
                continue
            # the statement's own expressions (headers, targets, values)
            for _f, v in ast.iter_fields(st):
                if isinstance(v, ast.expr):
                    self._visit_expr(v, held)
                elif isinstance(v, list):
                    for item in v:
                        if isinstance(item, ast.expr):
                            self._visit_expr(item, held)
            for child in self._stmt_lists(st):
                self._walk_block(child, held)
            # linear acquire()/release() regions within this list
            for call in self._own_calls(st):
                if not isinstance(call.func, ast.Attribute):
                    continue
                if call.func.attr == "acquire":
                    lid = self._lock_id(call.func.value)
                    if lid:
                        self.lockacq.append(
                            [lid, sorted(set(held)), call.lineno]
                        )
                        held.append(lid)
                elif call.func.attr == "release":
                    lid = self._lock_id(call.func.value)
                    if lid and lid in held:
                        held.remove(lid)

    @staticmethod
    def _own_calls(st: ast.stmt) -> Iterable[ast.Call]:
        if any(True for _ in _ConcExtractor._stmt_lists(st)):
            return  # compound: bodies track their own regions
        for node in walk_skipping_nested_defs(st):
            if isinstance(node, ast.Call):
                yield node

    # -------------------------------------------------- expressions

    def _visit_expr(self, e: Optional[ast.expr], held: List[str]) -> None:
        if e is None:
            return
        from .domains import spawn_records

        parents = self.unit.parents
        for node in self._nodes(e):
            if isinstance(node, ast.Call):
                self.spawns.extend(spawn_records(node))
                if held:
                    shape = Project.call_shape(node)
                    if shape is not None:
                        self.heldcalls.append(
                            [list(shape), sorted(set(held)), node.lineno]
                        )
                continue
            owner = field = None
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"
            ):
                if _lock_segments(node.attr):
                    continue  # the lock itself is not state
                owner, field = "self", node.attr
            elif isinstance(node, ast.Name):
                if (
                    node.id not in self.module_state
                    or node.id in self.local_bound
                    or _lock_segments(node.id)
                ):
                    continue
                owner, field = "global", node.id
            else:
                continue
            rec = self._classify(node, parents, owner)
            if rec is None:
                continue
            rw, sanction, const = rec
            parent = parents.get(node)
            if (
                rw == "store"
                and isinstance(parent, ast.AugAssign)
                and parent.target is node
            ):
                # load-modify-store: the read half races too
                self.acc.append(
                    [owner, field, "load", sorted(set(held)),
                     node.lineno, None, False]
                )
            self.acc.append(
                [owner, field, rw, sorted(set(held)), node.lineno,
                 sanction, const]
            )

    @staticmethod
    def _nodes(e: ast.expr) -> Iterable[ast.AST]:
        yield e
        yield from walk_skipping_nested_defs(e)

    def _classify(
        self, node: ast.AST, parents: Dict, owner: str
    ) -> Optional[Tuple[str, Optional[str], bool]]:
        """(rw, sanction, const_store) for one access node, or None to
        skip (a global Name in Store ctx that is really a local)."""
        ctx = getattr(node, "ctx", None)
        parent = parents.get(node)
        if isinstance(ctx, (ast.Store, ast.Del)):
            if owner == "global" and isinstance(node, ast.Name):
                if node.id not in self.gdecls:
                    return None  # local rebind, not the global
            const = False
            if isinstance(parent, ast.AugAssign) and parent.target is node:
                return ("store", None, False)
            if (
                isinstance(parent, (ast.Assign, ast.AnnAssign))
                and isinstance(parent.value, ast.Constant)
                and (
                    parent.value.value is None
                    or isinstance(parent.value.value, bool)
                )
            ):
                const = True
            return ("store", None, const)
        # Load context: how is the value used?
        if isinstance(parent, ast.Attribute) and parent.value is node:
            gp = parents.get(parent)
            if isinstance(gp, ast.Call) and gp.func is parent:
                method = parent.attr
                if method in THREADSAFE_RECV:
                    return ("load", f"recv:{method}", False)
                if method in MUTATOR_RECV:
                    return ("store", None, False)
            return ("load", None, False)
        if isinstance(parent, ast.Subscript) and parent.value is node:
            if isinstance(parent.ctx, (ast.Store, ast.Del)):
                return ("store", None, False)  # container mutation
            return ("load", None, False)
        return ("load", None, False)


def extract_conc(unit: FileUnit, qualname: str, fn: ast.AST) -> Dict:
    """The cacheable concurrency facts of one function body."""
    return _ConcExtractor(unit, qualname, fn).run()


# ===================================================================
# pass-time model
# ===================================================================


class FieldAccess:
    __slots__ = ("fn", "rw", "locks", "lineno", "sanction", "const",
                 "domains")

    def __init__(self, fn, rw, locks, lineno, sanction, const, domains):
        self.fn = fn  # accessor FKey
        self.rw = rw
        self.locks = locks  # effective lockset (frozenset)
        self.lineno = lineno
        self.sanction = sanction
        self.const = const
        self.domains = domains  # accessor's domain set


class ConcurrencyModel:
    """Fields, locksets and the lock-order graph for one project;
    memoized on the Project via get_model."""

    def __init__(self, project: Project) -> None:
        from .domains import get_domain_map

        self.project = project
        self.table = project.summaries
        self.dm = get_domain_map(project)
        self._callsites: Dict[FKey, List[Tuple[FKey, FrozenSet[str]]]] = {}
        self.must_entry: Dict[FKey, Optional[FrozenSet[str]]] = {}
        self.may_entry: Dict[FKey, Set[str]] = {}
        # (relpath, Class|<module>, field) -> [FieldAccess]
        self.fields: Dict[Tuple[str, str, str], List[FieldAccess]] = {}
        # (l1, l2) -> [(relpath, lineno, qualname)] acquisition sites
        self.lock_edges: Dict[Tuple[str, str], List[Tuple[str, int, str]]] = {}
        # @domain_private: (relpath, Class) -> justification / short list
        self.domain_private: Dict[Tuple[str, str], str] = {}
        self.bad_domain_private: List[Tuple[str, int, str]] = []
        self._collect_callsites()
        self._entry_locksets()
        self._collect_fields()
        self._collect_lock_edges()
        self._collect_domain_private()

    # ------------------------------------------------- entry locks

    def _collect_callsites(self) -> None:
        table = self.table
        for key, summ in table.locals.items():
            heldmap: Dict[Tuple, FrozenSet[str]] = {}
            for shape, held, lineno in summ.conc.get("heldcalls", ()):
                heldmap[(tuple(shape), lineno)] = frozenset(held)
            for i, rec in enumerate(summ.calls):
                shape, lineno = rec[0], rec[1]
                held = heldmap.get((tuple(shape), lineno), frozenset())
                for tgt in table.targets(key, i):
                    self._callsites.setdefault(tgt, []).append(
                        (key, held)
                    )

    def _entry_locksets(self) -> None:
        project = self.project
        seeded = self.dm.seeded
        TOP = None
        must = self.must_entry
        may = self.may_entry
        for k in self.table.locals:
            must[k] = frozenset() if k in seeded else TOP
            may[k] = set()
        order = list(reversed(project.sccs()))
        for comp in order:
            changed = True
            while changed:
                changed = False
                for k in comp:
                    if k in seeded:
                        continue
                    acc: Optional[FrozenSet[str]] = TOP
                    for (c, held) in self._callsites.get(k, ()):
                        cm = must.get(c, TOP)
                        if cm is TOP:
                            continue  # unreachable caller: no vote
                        contrib = cm | held
                        acc = (
                            contrib if acc is TOP else acc & contrib
                        )
                    if acc != must.get(k, TOP):
                        must[k] = acc
                        changed = True
                    m = may.get(k, set())
                    for (c, held) in self._callsites.get(k, ()):
                        add = may.get(c, set()) | held
                        if not add <= m:
                            m |= add
                            changed = True
                    may[k] = m

    def _effective(self, key: FKey, locks: Iterable[str]) -> FrozenSet[str]:
        entry = self.must_entry.get(key) or frozenset()
        return frozenset(locks) | entry

    # ------------------------------------------------------ fields

    def _owner_class(self, key: FKey) -> str:
        unit = self.project.by_path.get(key[0])
        if unit is None:
            return ""
        mi = self.project.mod_info(unit)
        for part in key[1].split("."):
            if part in mi.classes:
                return part
        return ""

    def _collect_fields(self) -> None:
        dm = self.dm
        for key, summ in self.table.locals.items():
            if key[1].split(".")[-1] in _INIT_EXEMPT:
                continue  # pre-publication stores
            acc = summ.conc.get("acc")
            if not acc:
                continue
            doms = dm.domains_of(key)
            if not doms:
                continue  # unreachable per the domain model
            cls = None
            for owner, field, rw, locks, lineno, sanction, const in acc:
                if owner == "self":
                    if cls is None:
                        cls = self._owner_class(key)
                    if not cls:
                        continue
                    fkey = (key[0], cls, field)
                else:
                    fkey = (key[0], "<module>", field)
                self.fields.setdefault(fkey, []).append(
                    FieldAccess(
                        key, rw, self._effective(key, locks),
                        lineno, sanction, const, doms,
                    )
                )

    def shared_fields(self):
        """(field key, accesses, union-of-domains) for every field
        reachable from ≥2 domains."""
        for fkey, accesses in sorted(self.fields.items()):
            doms: Set[str] = set()
            for a in accesses:
                doms |= a.domains
            if len(doms) >= 2:
                yield fkey, accesses, frozenset(doms)

    @staticmethod
    def field_verdict(accesses) -> Optional[Dict]:
        """Is a shared field's access pattern actually breakable, and
        how?  Returns None for patterns the passes stay quiet on, else
        a dict with the evidence the finding message cites.

        The bar is calibrated to CPython: under the GIL a single store
        or container op cannot tear, so a field whose every touch is
        one atomic op is left alone even with an empty lockset (flag
        flips, registration appends, warn-once latches).  What DOES
        break across domains — and what this reports — is

        - ``lms``: load-modify-store (``self.total += n`` — two GIL
          slices, lost updates),
        - ``cta``: check-then-act (a function loads the field, then
          stores it in a later statement — the classic lazy-init /
          read-plan-write window, including the two-different-locks
          variant where each half holds its OWN lock),
        - ``inconsistent``: some accesses hold a lock but the lockset
          intersection is empty — the author believes this field needs
          locking, and at least one path skips it (half-locked state
          never survives a refactor).
        """
        relevant = [a for a in accesses if a.sanction is None]
        if not relevant:
            return None
        stores = [a for a in relevant if a.rw == "store"]
        if not stores:
            return None  # load-only cannot race with itself
        if all(a.const for a in stores):
            return None  # GIL-atomic constant latch
        inter = frozenset.intersection(*[a.locks for a in relevant])
        if inter:
            return None  # one lock consistently guards every access
        verdict: Dict = {"relevant": relevant, "stores": stores}
        lms = [a for a in stores if not a.locks and any(
            b.rw == "load" and b.fn == a.fn and b.lineno == a.lineno
            for b in relevant
        )]
        if lms:
            verdict["lms"] = lms[0]
        by_fn: Dict = {}
        for a in relevant:
            by_fn.setdefault(a.fn, []).append(a)
        for fn, accs in sorted(by_fn.items()):
            loads = [a for a in accs if a.rw == "load"]
            sts = [a for a in accs if a.rw == "store"]
            for ld in loads:
                for st in sts:
                    if st.lineno <= ld.lineno:
                        continue  # same-line = lms; store-first isn't
                        # a decision window
                    if not (ld.locks & st.locks):
                        verdict.setdefault("cta", (ld, st))
        if any(a.locks for a in relevant):
            verdict["inconsistent"] = sorted(
                {lk for a in relevant for lk in a.locks}
            )
        if not ("lms" in verdict or "cta" in verdict
                or "inconsistent" in verdict):
            return None
        return verdict

    # --------------------------------------------------- lock order

    def _collect_lock_edges(self) -> None:
        for key, summ in self.table.locals.items():
            base = self.may_entry.get(key) or set()
            for lid, held_before, lineno in summ.conc.get("lockacq", ()):
                for h in set(held_before) | base:
                    if h != lid:
                        self.lock_edges.setdefault((h, lid), []).append(
                            (key[0], lineno, key[1])
                        )

    def lock_cycles(self) -> List[List[str]]:
        """Cycles in the lock-order graph, each as the ordered lock
        list [L1, L2, ..., L1] of one representative cycle per SCC."""
        graph: Dict[str, List[str]] = {}
        for (a, b) in self.lock_edges:
            graph.setdefault(a, []).append(b)
            graph.setdefault(b, [])
        # iterative Tarjan (mirrors interproc.Project.sccs)
        index: Dict[str, int] = {}
        low: Dict[str, int] = {}
        on_stack: Set[str] = set()
        stack: List[str] = []
        comps: List[List[str]] = []
        counter = [0]
        for root in sorted(graph):
            if root in index:
                continue
            work: List[Tuple[str, int]] = [(root, 0)]
            while work:
                node, pi = work[-1]
                if pi == 0:
                    index[node] = low[node] = counter[0]
                    counter[0] += 1
                    stack.append(node)
                    on_stack.add(node)
                recurse = False
                kids = graph.get(node, [])
                while pi < len(kids):
                    child = kids[pi]
                    pi += 1
                    if child not in index:
                        work[-1] = (node, pi)
                        work.append((child, 0))
                        recurse = True
                        break
                    if child in on_stack:
                        low[node] = min(low[node], index[child])
                if recurse:
                    continue
                work[-1] = (node, pi)
                if low[node] == index[node]:
                    comp = []
                    while True:
                        w = stack.pop()
                        on_stack.discard(w)
                        comp.append(w)
                        if w == node:
                            break
                    if len(comp) > 1:
                        comps.append(comp)
                work.pop()
                if work:
                    pnode, _ = work[-1]
                    low[pnode] = min(low[pnode], low[node])
        cycles: List[List[str]] = []
        for comp in comps:
            cset = set(comp)
            start = sorted(comp)[0]
            # DFS inside the SCC for one concrete cycle path
            path = [start]
            seen = {start}
            found: List[str] = []

            def dfs(n: str) -> bool:
                for nxt in graph.get(n, []):
                    if nxt == start and len(path) > 1:
                        found.extend(path + [start])
                        return True
                    if nxt in cset and nxt not in seen:
                        seen.add(nxt)
                        path.append(nxt)
                        if dfs(nxt):
                            return True
                        path.pop()
                return False

            dfs(start)
            if found:
                cycles.append(found)
        return cycles

    def edge_site(self, a: str, b: str) -> Optional[Tuple[str, int, str]]:
        sites = self.lock_edges.get((a, b))
        return sites[0] if sites else None

    # ----------------------------------------------- domain_private

    def _collect_domain_private(self) -> None:
        from .core import _MIN_JUSTIFICATION_CHARS

        for unit in self.project.units:
            for node in ast.walk(unit.tree):
                if not isinstance(node, ast.ClassDef):
                    continue
                for dec in node.decorator_list:
                    call = dec if isinstance(dec, ast.Call) else None
                    target = call.func if call else dec
                    if isinstance(target, ast.Attribute):
                        name = target.attr
                    elif isinstance(target, ast.Name):
                        name = target.id
                    else:
                        continue
                    if name != "domain_private":
                        continue
                    just = ""
                    if (
                        call is not None
                        and call.args
                        and isinstance(call.args[0], ast.Constant)
                        and isinstance(call.args[0].value, str)
                    ):
                        just = call.args[0].value
                    if len(just.strip()) >= _MIN_JUSTIFICATION_CHARS:
                        self.domain_private[
                            (unit.relpath, node.name)
                        ] = just
                    else:
                        self.bad_domain_private.append(
                            (unit.relpath, node.lineno, node.name)
                        )


def get_model(project: Project) -> ConcurrencyModel:
    model = getattr(project, "_conc_model", None)
    if model is None:
        model = ConcurrencyModel(project)
        project._conc_model = model
    return model
