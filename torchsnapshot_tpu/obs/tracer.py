"""In-process structured tracing: a span tree with monotonic timestamps.

Spans record where a ``take()``/``restore()`` spent its time: each span
carries monotonic start/end (ns), free-form attributes, its recording
thread and (when applicable) asyncio task identity, and a parent link so
exports can reconstruct the tree.  Parenthood propagates through a
``contextvars.ContextVar``: an asyncio task snapshots the context at
creation (``run_coroutine_threadsafe`` snapshots the SUBMITTING thread's),
so loop-thread spans nest under the caller's.  A pool thread does NOT
inherit it — ``loop.run_in_executor`` starts the worker in an empty
context — so every executor hop of the take and restore pipelines goes
through ``run_in_executor`` below, which carries the submitting context
across, opens the worker's span under the loop-thread span that submitted
it, and splits the wait for a worker (``queue_ns``) from the work (the
span's own duration).  Every span of one ``take``/``restore`` then reaches
the API bracket's span by parent links: the root's ``span_id`` is the
request's identifier.

Two clocks.  ``start_ns``/``end_ns`` are ``time.monotonic_ns``.  A lexical
span (``span()``, the executor hop) ALSO enters
``jax.profiler.TraceAnnotation("tsnp:" + name)`` when ``jax`` is already
imported (this module never imports it), so inside a ``jax.profiler``
session the same interval sits on its host-thread line of the
``.xplane.pb`` that holds the TPU plane — worker and loop threads give
their OS thread the Python thread's name for that (``tsnp-consume_N``).
``begin``/``end`` spans (``pipeline/budget_admission``) open and close on
different turns of the loop or different threads, which an annotation
cannot, and stay monotonic-only.

Cost discipline: tracing is OFF by default and the disabled path is
allocation-free — ``span()`` checks the module-level ``ENABLED`` flag
and returns one shared ``nullcontext`` singleton before any Span object,
attrs dict copy, or clock read happens.  The flag is owned by the
``TORCHSNAPSHOT_TPU_TRACE`` knob (knobs.py); ``knobs.override_trace``
refreshes it so tests can toggle tracing without touching this module.

Completed spans also feed the existing ``log_event`` fan-out: when any
event handler is registered, each finished span fires an
``Event("span/<name>")`` through the same handler chain, so existing
telemetry collectors see span-level detail without a second
registration API.  (Spans created BY ``log_event``'s own bracketing are
excluded — the original event already fired for those.)
"""

from __future__ import annotations

import asyncio
import contextlib
import itertools
import sys
import threading
import time
from contextvars import ContextVar, copy_context
from typing import Any, Callable, Dict, Iterator, List, Optional

from .. import knobs

# Shared disabled-path singleton: ``span()`` returns this before any
# allocation when tracing is off.
NULL_CM = contextlib.nullcontext(None)

# Module-level enabled flag — read directly (``tracer.ENABLED``) by hot
# paths that want to skip even the ``span()`` call's argument packing.
ENABLED = False

_ids = itertools.count(1)
_flow_ids = itertools.count(1)
_current: ContextVar[Optional["Span"]] = ContextVar("tsnp_span", default=None)

# Bound the recorded-span list: a runaway traced loop must degrade to
# dropped spans, never to unbounded host memory.
_MAX_SPANS = 200_000


class Span:
    """One timed operation.  ``start_ns``/``end_ns`` are
    ``time.monotonic_ns`` values; ``end_ns`` is 0 until the span closes."""

    __slots__ = (
        "name",
        "span_id",
        "parent_id",
        "start_ns",
        "end_ns",
        "attrs",
        "thread_id",
        "thread_name",
        "task_name",
        "flow_in",
        "flow_out",
    )

    def __init__(
        self,
        name: str,
        parent_id: Optional[int],
        attrs: Dict[str, Any],
    ) -> None:
        self.name = name
        self.span_id = next(_ids)
        self.parent_id = parent_id
        self.attrs = attrs
        self.start_ns = 0
        self.end_ns = 0
        t = threading.current_thread()
        self.thread_id = t.ident or 0
        self.thread_name = t.name
        self.task_name = _current_task_name()
        # Perfetto flow (async arrow) endpoints: ``flow_out`` emits an
        # arrow start at this span's END, ``flow_in`` an arrow end at
        # this span's START.  The scheduler links staging completion to
        # storage-I/O start this way.
        self.flow_in: Optional[int] = None
        self.flow_out: Optional[int] = None

    @property
    def duration_ns(self) -> int:
        return max(0, self.end_ns - self.start_ns)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "thread_id": self.thread_id,
            "thread_name": self.thread_name,
            "task_name": self.task_name,
            "flow_in": self.flow_in,
            "flow_out": self.flow_out,
            "attrs": dict(self.attrs),
        }


def _current_task_name() -> Optional[str]:
    try:
        task = asyncio.current_task()
    except RuntimeError:  # no running event loop on this thread
        return None
    return task.get_name() if task is not None else None


class Tracer:
    """Lock-protected recorder of finished spans.

    ``begin``/``end`` exist for spans whose lifetime crosses loop
    iterations (e.g. budget-admission waits); the ``span()`` context
    manager is the ergonomic path for lexically-scoped spans and is the
    only one that establishes parenthood for code nested under it."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self.dropped = 0

    # ----------------------------------------------------------- record

    def begin(
        self, name: str, parent: Optional[Span] = None, **attrs: Any
    ) -> Span:
        """Open a span WITHOUT making it the context parent (it can be
        closed from any thread/task via ``end``)."""
        if parent is None:
            parent = _current.get()
        s = Span(name, parent.span_id if parent else None, attrs)
        s.start_ns = time.monotonic_ns()
        return s

    def end(self, s: Span, fire_event: bool = False) -> None:
        if s.end_ns:  # already closed — idempotent
            return
        s.end_ns = time.monotonic_ns()
        self._record(s)
        if fire_event:
            _fire_span_event(s)

    def _record(self, s: Span) -> None:
        with self._lock:
            if len(self._spans) >= _MAX_SPANS:
                self.dropped += 1
                return
            self._spans.append(s)

    # ---------------------------------------------------------- inspect

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def current_span() -> Optional[Span]:
    return _current.get()


def next_flow_id() -> int:
    return next(_flow_ids)


# ------------------------------------------------------------- enabling


def tracing_enabled() -> bool:
    return ENABLED


def set_tracing(on: bool) -> None:
    global ENABLED
    ENABLED = bool(on)


def refresh_enabled() -> bool:
    """Re-resolve the ``TORCHSNAPSHOT_TPU_TRACE`` knob into the module
    flag (called by ``knobs.override_trace`` and at import)."""
    set_tracing(knobs.is_trace_enabled())
    return ENABLED


refresh_enabled()


# ----------------------------------------------------------------- span


def span(name: str, fire_event: bool = True, **attrs: Any):
    """Context manager recording one span, or a shared no-op when
    tracing is disabled.  Yields the ``Span`` (None when disabled) so
    callers can attach late attributes (``s.attrs["bytes"] = n``)."""
    if not ENABLED:
        return NULL_CM
    return _span_cm(name, fire_event, attrs)


# The profiler's clock: ``jax.profiler.TraceAnnotation`` once ``jax`` has
# been imported by the program (looked up in ``sys.modules``, never
# imported here).  Outside a profiler session entering one is a flag check.
XPLANE_PREFIX = "tsnp:"
_SCALARS = (bool, int, float, str)


def _annotation(s: "Span") -> Any:
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    cls = getattr(profiler, "TraceAnnotation", None)
    if cls is None:
        return None
    # ``thread``: the profiler keys a line by pthread id, which a later
    # thread reuses (each call makes new pools), so one line can hold several
    # threads' events under the name of one; the event says whose it is
    stats = {k: v for k, v in s.attrs.items() if isinstance(v, _SCALARS)}
    stats["thread"] = s.thread_name
    return cls(XPLANE_PREFIX + s.name, **stats)


@contextlib.contextmanager
def _span_cm(
    name: str, fire_event: bool, attrs: Dict[str, Any]
) -> Iterator[Span]:
    parent = _current.get()
    s = Span(name, parent.span_id if parent else None, attrs)
    token = _current.set(s)
    annotation = _annotation(s)
    if annotation is not None:
        annotation.__enter__()
    s.start_ns = time.monotonic_ns()
    try:
        yield s
    except BaseException:
        s.attrs["error"] = True
        raise
    finally:
        s.end_ns = time.monotonic_ns()
        if annotation is not None:
            annotation.__exit__(None, None, None)
        _current.reset(token)
        _TRACER._record(s)
        if fire_event:
            _fire_span_event(s)


# ------------------------------------------------------- executor hop


def name_os_thread() -> None:
    """Give the calling OS thread its Python thread's name (Linux; the
    kernel keeps 15 bytes).  The profiler names a host-thread line after
    the OS thread, and before Python 3.14 ``threading`` does not set it,
    so every pool and loop thread would read ``python``.  Called by traced
    workers and loop threads only: once per thread, never with tracing off."""
    t = threading.current_thread()
    if getattr(t, "_tsnp_os_named", False):
        return
    t._tsnp_os_named = True  # type: ignore[attr-defined]
    try:
        with open("/proc/thread-self/comm", "w") as f:
            f.write(t.name[:15])
    except OSError:
        pass  # not Linux, or /proc not mounted: the line keeps its name


def _hop(
    name: str, submit_ns: int, nbytes: Optional[int], fn: Callable, args: tuple
) -> Any:
    # on the worker, inside a copy of the submitting task's context
    queue_ns = time.monotonic_ns() - submit_ns
    name_os_thread()
    attrs: Dict[str, Any] = {"queue_ns": queue_ns}
    if nbytes is not None:
        attrs["bytes"] = nbytes
    with _span_cm(name, True, attrs):
        return fn(*args)


def run_in_executor(
    executor: Any,
    fn: Callable,
    *args: Any,
    name: str,
    nbytes: Optional[int] = None,
) -> "asyncio.Future":
    """``loop.run_in_executor(executor, fn, *args)`` from a coroutine, traced.

    With tracing off this is one flag read and then exactly that call.
    With tracing on the worker runs ``fn`` inside a copy of the calling
    task's context, under a span ``name`` whose parent is the span that
    submitted it and whose attrs hold ``queue_ns`` (submit → a worker
    picked it up) and ``bytes``; the span's duration is the work alone."""
    loop = asyncio.get_running_loop()
    if not ENABLED:
        return loop.run_in_executor(executor, fn, *args)
    return loop.run_in_executor(
        executor, copy_context().run, _hop,
        name, time.monotonic_ns(), nbytes, fn, args,
    )


def _fire_span_event(s: Span) -> None:
    """Feed the finished span into the event-handler fan-out (lazy
    import: event_handlers composes with this module in both
    directions)."""
    from .. import event_handlers

    # entry-point discovery must run before the emptiness check, or a
    # collector registered solely via the entry-point group would miss
    # every span of the first traced operation (discovery is cached, so
    # this is one flag check per span after the first)
    event_handlers._load_entry_point_handlers()
    if not (
        event_handlers._handlers or event_handlers._entry_point_handlers
    ):
        return
    from ..event import Event

    event_handlers._fire(
        Event(
            f"span/{s.name}",
            {
                "span_id": s.span_id,
                "parent_id": s.parent_id,
                "duration_s": s.duration_ns / 1e9,
                **s.attrs,
            },
        )
    )
