"""Distributed control plane: object gathers, barriers, and a KV store.

TPU-native replacement for the reference's two-channel design
(pg_wrapper.py:17-91 NCCL/Gloo collectives + dist_store.py:24-196 TCPStore):
on JAX, *both* channels collapse into the coordination-service KV store —
``jax.distributed``'s client exposes key_value_set / blocking_key_value_get /
wait_at_barrier, which (a) carries small control-plane objects fine and
(b) never touches ICI, so it is safe from the async-snapshot background
thread (the reference's "no collectives in this method" constraint,
snapshot.py:1010, holds by construction).

Implementations:
- ``LocalCoordinator``  — single process, no-ops.
- ``JaxCoordinator``    — multi-controller via jax.distributed's KV client.
- ``FileCoordinator``   — shared-filesystem KV for multi-process CPU tests
  (the analogue of the reference's file-based c10d rendezvous in
  test_utils.py:188-243).

All gathers/barriers are built on four KV primitives (set/get/delete/
barrier), so the three backends share the same semantics by construction.
"""

from __future__ import annotations

import abc
import contextlib
import logging
import os
import threading
import time
import uuid
from base64 import b64decode, b64encode
from typing import Any, Iterator, List, Optional

from . import obs
from .resilience import abort as _abort
from .resilience.failpoints import failpoint
from .serialization import deserialize_object, serialize_object

logger = logging.getLogger(__name__)

# Bytes per KV value (before base64) of a blob published over the KV:
# fan-out redistribution and the KV transport count their parts with it.
KV_BLOB_PART_BYTES = 4 * 1024 * 1024

_DEFAULT_TIMEOUT_S = 600.0
# abort-aware waits poll the poison key at this cadence: a peer's abort
# surfaces within ~this interval instead of the full wait timeout
_ABORT_POLL_S = 0.5


def _is_timeoutish(e: BaseException) -> bool:
    """Did a bounded KV wait merely time out (vs. fail)?  Covers the
    builtin TimeoutError (FileCoordinator) and the jax coordination
    client's DEADLINE_EXCEEDED XlaRuntimeError."""
    if isinstance(e, TimeoutError):
        return True
    name = type(e).__name__
    r = repr(e).upper()
    return "Timeout" in name or "DEADLINE_EXCEEDED" in r or "DEADLINE" in r


class Coordinator(abc.ABC):
    """Uniform control-plane interface (reference PGWrapper,
    pg_wrapper.py:17-91).

    Beyond the KV/barrier primitives, the base class carries the
    cross-rank ABORT protocol (resilience/abort.py): ``poison(scope,
    cause)`` broadcasts an abort under one KV key, and inside an
    ``abort_scope(scope)`` every ``kv_get``/``barrier`` wait polls that
    key — a peer's unrecoverable failure surfaces as a typed
    ``SnapshotAbortedError`` within seconds instead of wedging the rank
    until the wait timeout.  The scope is per-thread (a background
    promotion thread's scope never leaks onto the foreground take)."""

    @property
    @abc.abstractmethod
    def rank(self) -> int: ...

    @property
    @abc.abstractmethod
    def world_size(self) -> int: ...

    @abc.abstractmethod
    def _kv_set_impl(self, key: str, value: str) -> None: ...

    @abc.abstractmethod
    def _kv_get_impl(self, key: str, timeout_s: float) -> str: ...

    @abc.abstractmethod
    def kv_try_get(self, key: str) -> Optional[str]: ...

    @abc.abstractmethod
    def _barrier_impl(self, name: str, timeout_s: float) -> None: ...

    def kv_set(self, key: str, value: str) -> None:
        failpoint("coord.kv_set", key=key)
        self._kv_set_impl(key, value)

    def kv_get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> str:
        """Blocking get: waits until the key exists.  Abort-aware inside
        an ``abort_scope``; death-aware inside a ``liveness_scope``
        (raises ``RankDeadError`` when a peer's heartbeat goes stale
        instead of waiting out the full deadline)."""
        failpoint("coord.kv_get", key=key)
        scope = self._current_abort_scope()
        monitor = self._current_liveness()
        if scope is None and monitor is None:
            return self._kv_get_impl(key, timeout_s)
        return self._polling_kv_get(key, timeout_s, scope, monitor)

    def barrier(
        self, name: Optional[str] = None, timeout_s: float = _DEFAULT_TIMEOUT_S
    ) -> None:
        """Barrier; auto-names from the per-instance op counter when no name
        is given (coordination calls happen in identical program order on
        every rank).  Explicit names must be globally unique per use — JAX
        barrier ids are single-use.  Abort-aware inside an ``abort_scope``:
        runs as a two-phase KV barrier over the abort-aware ``kv_get``
        (the native barrier wait is opaque and can't poll poison)."""
        name = name or self._next_uid("bar")
        failpoint("coord.barrier", name=name)
        # always-on barrier phase clock: the flight record's straggler
        # attribution (obs/aggregate) reads this rank's cumulative
        # barrier-wait seconds — a fast rank's take time hides in here
        # while it waits for the straggler
        t0 = time.monotonic()
        try:
            self._barrier_inner(name, timeout_s)
        finally:
            obs.histogram(obs.PHASE_BARRIER_S).observe(
                time.monotonic() - t0
            )

    def _barrier_inner(self, name: str, timeout_s: float) -> None:
        scope = self._current_abort_scope()
        monitor = self._current_liveness()
        if scope is None and monitor is None:
            self._barrier_impl(name, timeout_s)
            return
        if scope is not None:
            self.raise_if_poisoned(scope)
        if monitor is not None:
            monitor.check()
        if self.world_size == 1:
            return
        # one deadline for the WHOLE barrier (matching the native
        # implementation's bound) — not timeout_s per arrive key
        deadline = time.monotonic() + timeout_s
        self._kv_set_impl(f"{name}/aa/arrive/{self.rank}", "1")
        if self.rank == 0:
            for r in range(self.world_size):
                self.kv_get(
                    f"{name}/aa/arrive/{r}",
                    max(0.0, deadline - time.monotonic()),
                )
            self._kv_set_impl(f"{name}/aa/depart", "1")
        else:
            self.kv_get(
                f"{name}/aa/depart", max(0.0, deadline - time.monotonic())
            )

    # ---- cross-rank abort (resilience/abort.py) ------------------------

    def poison(
        self, scope: str, cause: str, site: str = ""
    ) -> _abort.AbortInfo:
        """Broadcast an abort of ``scope``: peers blocked in abort-aware
        waits raise ``SnapshotAbortedError`` naming this rank and
        ``cause``.  Never raises — poisoning runs on failure paths and
        must not mask the original error."""
        info = _abort.AbortInfo(
            origin_rank=self.rank, cause=cause, site=site
        )
        obs.counter(obs.RESILIENCE_ABORTS).inc()
        logger.warning(
            "rank %d poisoning scope %r at %s: %s",
            self.rank, scope, site or "?", cause,
        )
        try:
            self._kv_set_impl(
                _abort.poison_key(scope), _abort.encode_poison(info)
            )
        except Exception as e:  # noqa: BLE001 — best-effort broadcast
            obs.swallowed_exception("coordination.poison", e)
        return info

    def check_poison(self, scope: str) -> Optional[_abort.AbortInfo]:
        raw = self.kv_try_get(_abort.poison_key(scope))
        return _abort.decode_poison(raw) if raw else None

    def raise_if_poisoned(self, scope: str) -> None:
        info = self.check_poison(scope)
        if info is not None:
            raise _abort.SnapshotAbortedError(info, scope=scope)

    def _current_abort_scope(self) -> Optional[str]:
        tls = self.__dict__.get("_abort_tls")
        return getattr(tls, "scope", None) if tls is not None else None

    @contextlib.contextmanager
    def abort_scope(self, scope: str) -> Iterator[None]:
        """While active, this THREAD's kv_get/barrier waits poll
        ``scope``'s poison key (per-thread on purpose: the async-commit
        and tier-promotion threads scope their own waits without
        touching the foreground program order)."""
        tls = self.__dict__.setdefault("_abort_tls", threading.local())
        prev = getattr(tls, "scope", None)
        tls.scope = scope
        try:
            yield
        finally:
            tls.scope = prev

    def _abortable_kv_get(
        self, key: str, timeout_s: float, scope: str
    ) -> str:
        return self._polling_kv_get(key, timeout_s, scope, None)

    def _polling_kv_get(
        self, key: str, timeout_s: float, scope: Optional[str], monitor: Any
    ) -> str:
        """The shared short-poll wait: between probes it checks the
        abort scope's poison key and/or the liveness monitor, so a
        peer's failure (poison) or death (stale heartbeat) surfaces as
        a typed error within one poll interval."""
        deadline = time.monotonic() + timeout_s
        while True:
            if scope is not None:
                self.raise_if_poisoned(scope)
            if monitor is not None:
                monitor.check()
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"kv_get timed out waiting for {key!r} "
                    f"(abort-aware, scope {scope!r})"
                )
            try:
                return self._kv_get_impl(
                    key, min(_ABORT_POLL_S, remaining)
                )
            except Exception as e:  # noqa: BLE001 — timeouts poll on
                if not _is_timeoutish(e):
                    raise

    # ---- rank liveness (resilience/liveness.py) ------------------------

    def _current_liveness(self) -> Any:
        tls = self.__dict__.get("_liveness_tls")
        return getattr(tls, "monitor", None) if tls is not None else None

    @contextlib.contextmanager
    def liveness_scope(self, monitor: Any) -> Iterator[None]:
        """While active, this THREAD's kv_get/barrier waits check
        ``monitor`` (a ``resilience.liveness.LivenessMonitor``) each
        poll tick and raise ``RankDeadError`` when a peer's heartbeat
        stamp goes stale — per-thread for the same reason as
        ``abort_scope``."""
        tls = self.__dict__.setdefault("_liveness_tls", threading.local())
        prev = getattr(tls, "monitor", None)
        tls.monitor = monitor
        try:
            yield
        finally:
            tls.monitor = prev

    def dead_ranks(self) -> list:
        """Peers the current thread's liveness monitor considers dead
        (empty outside a ``liveness_scope`` — without heartbeats there
        is no death evidence)."""
        monitor = self._current_liveness()
        return monitor.dead_ranks() if monitor is not None else []

    # ---- derived object-level ops --------------------------------------

    def _encode(self, obj: Any) -> str:
        payload, tag = serialize_object(obj)
        return tag + ":" + b64encode(payload).decode("ascii")

    def _decode(self, s: str) -> Any:
        tag, payload = s.split(":", 1)
        return deserialize_object(b64decode(payload.encode("ascii")), tag)

    def _next_uid(self, op: str) -> str:
        # Every rank performs coordination calls in the same program order,
        # so a per-instance counter yields matching keys across ranks.
        n = getattr(self, "_op_counter", 0)
        self._op_counter = n + 1
        return f"{op}/{n}"

    def kv_exchange(
        self,
        prefix: str,
        value: str,
        timeout_s: float = _DEFAULT_TIMEOUT_S,
    ) -> List[str]:
        """KV-only allgather of one small STRING per rank under EXPLICIT
        keys (``{prefix}/{rank}``) — no barrier, no uid counters, no
        collectives, so it is safe from background threads (async-commit
        and tier-promotion threads, where ``all_gather_object`` is
        forbidden: its per-instance uid counter belongs to the foreground
        program order).  ``prefix`` must be unique per use across the job
        (callers derive it from a commit uid); keys are idempotent —
        re-setting the same value is harmless."""
        if self.world_size == 1:
            return [value]
        self.kv_set(f"{prefix}/{self.rank}", value)
        return [
            self.kv_get(f"{prefix}/{r}", timeout_s)
            for r in range(self.world_size)
        ]

    def kv_try_delete(self, key: str) -> None:
        """Best-effort KV key deletion (cleanup of transient
        publications — fan-out blobs).  Base implementation is a no-op:
        a backend without deletion merely retains the key until
        teardown, never fails the caller."""

    def kv_publish_blob(
        self, prefix: str, data: Any, part_bytes: int = KV_BLOB_PART_BYTES
    ) -> int:
        """Publish one binary blob under EXPLICIT keys for asymmetric
        one-to-many redistribution (the fan-out restore's transport,
        topology/fanout.py).  The blob is split into ``part_bytes``
        chunks (``{prefix}/p{i}``, base64) with a ``{prefix}/meta`` key
        written LAST carrying ``nparts:total:crc32`` — meta presence
        therefore implies every part is present, and the crc32 lets the
        fetch side verify the reassembled bytes before trusting them.
        No barrier, no uid counters: safe from any thread, legal under
        rank-conditional branches (only the publisher calls this).
        ``prefix`` must be unique per blob across the job (namespace
        REUSE is the exception the sweep below exists for).  Returns
        the blob's byte length.

        Leak repair: a publisher killed between the cleanup path's
        meta-key delete and its part deletes leaves orphaned
        ``{prefix}/p{i}`` keys (meta gone, parts stranded until the KV
        itself is torn down).  The next publish under the same prefix
        reclaims them: indices below the new ``nparts`` are simply
        overwritten, and after the meta write a tail sweep deletes
        every contiguous leftover part at/above ``nparts``
        (``kv_sweep_blob``) — so namespace reuse self-heals instead of
        accreting dead keys."""
        import zlib

        view = memoryview(data).cast("B")
        part = max(1, int(part_bytes))
        n = view.nbytes
        nparts = (n + part - 1) // part
        for i in range(nparts):
            chunk = view[i * part : min((i + 1) * part, n)]
            self.kv_set(
                f"{prefix}/p{i}", b64encode(chunk).decode("ascii")
            )
        self.kv_set(f"{prefix}/meta", f"{nparts}:{n}:{zlib.crc32(view)}")
        self.kv_sweep_blob(prefix, beyond=nparts)
        return n

    def kv_sweep_blob(self, prefix: str, beyond: int = 0) -> int:
        """Best-effort reclaim of leaked blob part keys under
        ``prefix``: deletes ``{prefix}/p{i}`` for ``i = beyond,
        beyond+1, ...`` until the first missing index (parts are
        written contiguously from 0, so the first gap proves the end).
        ``beyond=0`` is a full sweep and deletes ``{prefix}/meta``
        FIRST — preserving the meta-last invariant for any concurrent
        fetcher (meta present implies every part present).  Returns
        the number of part keys deleted; never raises past the KV's
        own best-effort delete semantics."""
        start = max(0, int(beyond))
        if start == 0:
            self.kv_try_delete(f"{prefix}/meta")
        swept = 0
        i = start
        while self.kv_try_get(f"{prefix}/p{i}") is not None:
            self.kv_try_delete(f"{prefix}/p{i}")
            swept += 1
            i += 1
        if swept:
            obs.counter(obs.TRANSPORT_SWEPT_PARTS).inc(swept)
        return swept

    def kv_try_fetch_blob(
        self, prefix: str, timeout_s: float = _DEFAULT_TIMEOUT_S
    ) -> Optional[bytes]:
        """Non-blocking probe + fetch of a blob published by
        ``kv_publish_blob``: None when ``{prefix}/meta`` is not (yet)
        present; otherwise the reassembled, crc-verified bytes.  The
        meta-last publication order makes the part gets below
        effectively immediate once meta exists.  Raises ``ValueError``
        on a digest/length mismatch — the caller decides whether to
        retry or fall back."""
        import zlib

        raw = self.kv_try_get(f"{prefix}/meta")
        if raw is None:
            return None
        try:
            nparts_s, total_s, crc_s = raw.split(":")
            nparts, total, crc = int(nparts_s), int(total_s), int(crc_s)
        except ValueError as e:
            raise ValueError(
                f"malformed blob meta under {prefix!r}: {raw!r}"
            ) from e
        buf = bytearray()
        for i in range(nparts):
            buf += b64decode(
                self.kv_get(f"{prefix}/p{i}", timeout_s).encode("ascii")
            )
        if len(buf) != total or zlib.crc32(bytes(buf)) != crc:
            raise ValueError(
                f"blob under {prefix!r} failed digest verification "
                f"({len(buf)} of {total} bytes)"
            )
        return bytes(buf)

    def all_gather_object(self, obj: Any) -> List[Any]:
        """Gather an object from every rank (reference
        pg_wrapper.py all_gather_object)."""
        if self.world_size == 1:
            return [obj]
        uid = self._next_uid("ag")
        self.kv_set(f"{uid}/{self.rank}", self._encode(obj))
        out = [self._decode(self.kv_get(f"{uid}/{r}")) for r in range(self.world_size)]
        self.barrier(f"{uid}/done")
        return out

    def broadcast_object(self, obj: Any, src: int = 0) -> Any:
        """Broadcast an object from ``src`` (reference
        pg_wrapper.py broadcast_object_list)."""
        if self.world_size == 1:
            return obj
        uid = self._next_uid("bc")
        if self.rank == src:
            self.kv_set(uid, self._encode(obj))
            result = obj
        else:
            result = self._decode(self.kv_get(uid))
        self.barrier(f"{uid}/done")
        return result


class LocalCoordinator(Coordinator):
    """Single-process fallback (reference PGWrapper(pg=None) branch)."""

    def __init__(self) -> None:
        self._kv: dict = {}

    @property
    def rank(self) -> int:
        return 0

    @property
    def world_size(self) -> int:
        return 1

    def _kv_set_impl(self, key: str, value: str) -> None:
        self._kv[key] = value

    def _kv_get_impl(self, key: str, timeout_s: float) -> str:
        return self._kv[key]

    def kv_try_get(self, key: str) -> Optional[str]:
        return self._kv.get(key)

    def kv_try_delete(self, key: str) -> None:
        self._kv.pop(key, None)

    def _barrier_impl(self, name: str, timeout_s: float) -> None:
        pass


class JaxCoordinator(Coordinator):
    """Multi-controller coordination over jax.distributed's KV service.

    Requires ``jax.distributed.initialize()`` to have been called (the
    norm on multi-host TPU pods).
    """

    def __init__(self, namespace: Optional[str] = None) -> None:
        import jax

        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "jax.distributed is not initialized; use LocalCoordinator "
                "for single-process runs"
            )
        self._client = client
        self._rank = jax.process_index()
        self._world = jax.process_count()
        self._ns = namespace or "tsnp"

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def world_size(self) -> int:
        return self._world

    def _k(self, key: str) -> str:
        return f"{self._ns}/{key}"

    def _kv_set_impl(self, key: str, value: str) -> None:
        self._client.key_value_set(self._k(key), value)

    def _kv_get_impl(self, key: str, timeout_s: float) -> str:
        return self._client.blocking_key_value_get(
            self._k(key), max(1, int(timeout_s * 1000))
        )

    def kv_try_get(self, key: str) -> Optional[str]:
        try:
            return self._client.key_value_try_get(self._k(key))
        except Exception:
            return None

    def kv_try_delete(self, key: str) -> None:
        try:
            self._client.key_value_delete(self._k(key))
        except Exception as e:  # noqa: BLE001 — cleanup is best-effort
            obs.swallowed_exception("coordination.kv_try_delete", e)

    def _barrier_impl(self, name: str, timeout_s: float) -> None:
        self._client.wait_at_barrier(self._k(name), int(timeout_s * 1000))


class FileCoordinator(Coordinator):
    """Shared-directory KV + barriers for multi-process tests on one host."""

    def __init__(self, root: str, rank: int, world_size: int, poll_s: float = 0.01):
        self.root = root
        self._rank = rank
        self._world = world_size
        self._poll_s = poll_s
        os.makedirs(root, exist_ok=True)

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def world_size(self) -> int:
        return self._world

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key.replace("/", "%2F"))

    def _kv_set_impl(self, key: str, value: str) -> None:
        path = self._path(key)
        tmp = path + f".tmp.{uuid.uuid4().hex}"
        with open(tmp, "w") as f:
            f.write(value)
        os.replace(tmp, path)

    def _kv_get_impl(self, key: str, timeout_s: float) -> str:
        deadline = time.monotonic() + timeout_s
        path = self._path(key)
        while True:
            try:
                with open(path, "r") as f:
                    return f.read()
            except FileNotFoundError:
                if time.monotonic() > deadline:
                    raise TimeoutError(f"kv_get timed out waiting for {key!r}")
                time.sleep(self._poll_s)

    def kv_try_get(self, key: str) -> Optional[str]:
        try:
            with open(self._path(key), "r") as f:
                return f.read()
        except FileNotFoundError:
            return None

    def kv_try_delete(self, key: str) -> None:
        try:
            os.unlink(self._path(key))
        except OSError:
            pass  # already gone / never set: best-effort by contract

    def _barrier_impl(self, name: str, timeout_s: float) -> None:
        # two-phase: everyone arrives, rank 0 releases
        # (reference LinearBarrier, dist_store.py:91-196)
        self.kv_set(f"{name}/arrive/{self._rank}", "1")
        if self._rank == 0:
            for r in range(self._world):
                self.kv_get(f"{name}/arrive/{r}", timeout_s)
            self.kv_set(f"{name}/depart", "1")
        else:
            self.kv_get(f"{name}/depart", timeout_s)


def kv_watch(
    coordinator: Coordinator,
    key: str,
    last: "Optional[str]" = None,
    timeout_s: float = 0.0,
    poll_s: float = 0.025,
) -> "Optional[str]":
    """Watch helper for announce-style keys: poll ``kv_try_get(key)``
    until its value exists AND differs from ``last``, or ``timeout_s``
    elapses (returns None).  This is the publication subsystem's fast
    path (publish/subscriber.py) — one non-blocking probe per tick, so
    a host full of waiting subscribers never parks threads in a
    blocking ``kv_get``, and a timeout is a NORMAL return (the caller
    falls back to its durable poll, the fanout degrade-never-wedge
    contract).  Any probe error also returns None: a broken announce
    channel must degrade the watcher, not wedge it."""
    deadline = time.monotonic() + max(0.0, timeout_s)
    while True:
        try:
            value = coordinator.kv_try_get(key)
        except Exception as e:  # noqa: BLE001 — degrade to durable poll
            obs.swallowed_exception("coordination.kv_watch", e)
            return None
        if value is not None and value != last:
            return value
        if time.monotonic() >= deadline:
            return None
        time.sleep(min(poll_s, max(0.0, deadline - time.monotonic())))


def get_default_coordinator() -> Coordinator:
    """JaxCoordinator when jax.distributed is initialized, else local."""
    try:
        from jax._src import distributed

        if distributed.global_state.client is not None:
            return JaxCoordinator()
    except Exception as e:
        # jax absent or its internal layout changed: single-process
        # coordination is the right degraded mode, but record the
        # fallback — a pod job silently coordinating locally is exactly
        # the misconfiguration this trace exists to diagnose (obs is a
        # module-level import: a lazy import here could itself raise
        # and replace the exception being handled)
        obs.swallowed_exception("coordination.jax_probe", e)
    return LocalCoordinator()
