"""CollectiveTransport: payload bytes as device arrays, KV as control.

The data plane the reference library gets from PGWrapper collectives
(PAPER.md L0) rebuilt on jax: payload bytes are packed into uint32
lane words (4 bytes/word, zero-padded to the 128-byte lane width so
any backend's layout constraints are satisfied), chunked at
``TRANSPORT_PART_BYTES``, and moved either

- **session mode** (multi-process): over real jax collectives —
  ``multihost_utils.broadcast_one_to_all`` on the live
  ``jax.distributed`` runtime, one broadcast per part, every process
  participating (SPMD).  Collectives match by launch order, so the
  per-restore ``CollectiveFanoutSession`` fixes a deterministic
  transfer order up front (identical on every process) and gates each
  transfer through explicit-key KV handshakes: the source announces
  ``ok:…digests`` or ``skip`` on the transfer's ``go`` key, every
  other process acks, and the source confirms on ``go2`` before any
  process enters the broadcast — a collective is only ever launched
  once every process has agreed, in writing, to launch it.  Any
  timeout or anomaly breaks the SESSION (not the restore): no further
  collective is entered anywhere, pending payloads are re-published
  over the KV blob path, and consumers fall into the fan-out ladder
  (KV fetch → re-elect → staggered direct) that already owns the
  never-wedge contract.

- **local mode** (single process, e.g. thread-simulated ranks or
  co-resident subscribers): through the device itself — parts are
  ``device_put`` into an in-process registry keyed by prefix and
  announced over the KV (``{prefix}/xmeta``, digests included);
  consumers ``device_get`` and verify.  The bytes genuinely cross the
  host↔device boundary, so a KV-vs-collective comparison measures
  transfer machinery rather than a dict lookup.

Every payload is crc32 + adler32 verified against digests computed at
publication before a consumer may trust it, in both modes.  The KV
carries ONLY control traffic here: announce keys, digests, gate
handshakes — never payload bytes (those appear on the KV only after
an explicit degrade, via the KV engine).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional, Set, Tuple

from .. import knobs, obs
from ..coordination import KV_BLOB_PART_BYTES
from ..resilience.failpoints import failpoint
from ..utils.checksums import adler32_fast, crc32_fast
from . import Transport, TransportUnavailable, count_fallback

logger = logging.getLogger(__name__)

# payloads are padded to this many bytes per part so device layouts
# (TPU lane width) never force a reshape on the hot path
_LANE = 128
_WORD = 4  # uint32 lane words carry the bytes (gloo/psum-safe dtype)

# How long a participant waits on the control-plane gate (go/no-go key)
# for one transfer before treating the transfer as failed and degrading
# to KV.  Bounds every wait in the engine — the never-wedge contract.
GATE_TIMEOUT_S = 30.0

# in-process publication registry for local mode: prefix → (device
# part arrays, payload nbytes, crc32, adler32).  Module-global on
# purpose — thread-simulated ranks share one process and one device.
_registry_lock = threading.Lock()
_REGISTRY: Dict[str, Tuple[List[Any], int, int, int]] = {}


def _np():
    import numpy as np

    return np


def _devices() -> list:
    """The jax device list — isolated so tests can simulate a runtime
    with no usable device/mesh."""
    import jax

    return jax.devices()


def _process_count() -> int:
    import jax

    return jax.process_count()


def _process_index() -> int:
    import jax

    return jax.process_index()


def _plan_parts(nbytes: int, part_bytes: int) -> Tuple[int, int]:
    """(nparts, padded-bytes-per-part) for one payload: every part has
    the SAME padded shape, so the consumer can pre-agree the broadcast
    shapes from the announce digest line alone."""
    part = max(_LANE, int(part_bytes))
    nparts = max(1, -(-nbytes // part))
    base = max(1, -(-nbytes // nparts))
    ppad = -(-base // _LANE) * _LANE
    return nparts, ppad


def _pack_parts(view: memoryview, nparts: int, ppad: int) -> List[Any]:
    """Zero-pad the payload to ``nparts * ppad`` bytes and view it as
    ``nparts`` uint32 word arrays (no per-byte upcast: 4 payload bytes
    per lane word, same wire volume as the payload)."""
    np = _np()
    padded = np.zeros(nparts * ppad, dtype=np.uint8)
    padded[: view.nbytes] = np.frombuffer(view, dtype=np.uint8)
    words = padded.view(np.uint32)
    per = ppad // _WORD
    return [words[i * per : (i + 1) * per] for i in range(nparts)]


def _unpack_parts(parts: List[Any], nbytes: int) -> bytes:
    np = _np()
    words = np.concatenate([np.asarray(p, dtype=np.uint32) for p in parts])
    return words.view(np.uint8)[:nbytes].tobytes()


def _digests(view: memoryview) -> Tuple[int, int]:
    return crc32_fast(view), adler32_fast(view)


class CollectiveTransport(Transport):
    engine = "collective"

    def __init__(
        self,
        coordinator: Any = None,
        topology: Any = None,
        require_session: bool = False,
    ) -> None:
        self.coordinator = coordinator
        self.topology = topology
        try:
            if not _devices():
                raise TransportUnavailable("no jax devices")
        except TransportUnavailable:
            raise
        except Exception as e:  # noqa: BLE001 — any jax probe failure
            # (missing runtime, backend init error) means "not capable"
            raise TransportUnavailable(f"jax device probe failed: {e}")
        self.session_capable = self._probe_session()
        if require_session and not self.session_capable:
            # auto mode: a single-process world (or a multi-process KV
            # world with no jax.distributed session) must not
            # half-select an engine its peers cannot join
            raise TransportUnavailable(
                "no aligned multi-process jax session"
            )
        self.mode = "session" if self.session_capable else "local"
        m = obs.REGISTRY
        self._m_ops = m.counter(obs.TRANSPORT_COLLECTIVE_OPS)
        self._m_bytes = m.counter(obs.TRANSPORT_COLLECTIVE_BYTES)
        self._m_lat = m.histogram(obs.TRANSPORT_COLLECTIVE_S)
        self._m_moves = m.counter(obs.TRANSPORT_DEVICE_MOVES)
        # local-mode publications this instance made (cleanup ledger)
        self._local_prefixes: Set[str] = set()
        self._lock = threading.Lock()

    def _probe_session(self) -> bool:
        """A collective session needs every coordinator rank to be a
        jax process with matching indices — otherwise ``is_source``
        and the gate/ack protocol would disagree about identity."""
        if self.coordinator is None:
            return False
        try:
            return (
                _process_count() > 1
                and _process_count() == self.coordinator.world_size
                and _process_index() == self.coordinator.rank
            )
        except Exception:  # noqa: BLE001 — no distributed runtime
            return False

    # ----------------------------------------------------- local mode

    def publish(self, prefix: str, data: Any) -> int:
        """Local-mode publication: parts onto the device, digests and
        shape onto the KV announce key (``{prefix}/xmeta``, written
        LAST — presence implies the registry entry is complete)."""
        if self.mode != "local":
            raise TransportUnavailable(
                "collective session mode publishes via the fan-out "
                "session, not per-op"
            )
        with obs.span("transport/collective_publish", prefix=prefix):
            import jax

            t0 = time.monotonic()
            view = memoryview(data).cast("B")
            n = view.nbytes
            crc, adler = _digests(view)
            nparts, ppad = _plan_parts(n, knobs.get_transport_part_bytes())
            host_parts = _pack_parts(view, nparts, ppad)
            dev = _devices()[0]
            device_parts: List[Any] = []
            try:
                for i, hp in enumerate(host_parts):
                    device_parts.append(jax.device_put(hp, dev))
                    # chaos hook: a transfer dying with some parts
                    # already staged on device must degrade, not wedge
                    failpoint(
                        "transport.collective.publish",
                        prefix=prefix, part=i,
                    )
                for dp in device_parts:
                    dp.block_until_ready()
            except Exception:
                # no announce was written; nothing for a peer to see
                device_parts.clear()
                raise
            with _registry_lock:
                _REGISTRY[prefix] = (device_parts, n, crc, adler)
            with self._lock:
                self._local_prefixes.add(prefix)
            self.coordinator.kv_set(
                f"{prefix}/xmeta", f"{nparts}:{ppad}:{n}:{crc}:{adler}"
            )
            self._m_ops.inc()
            self._m_bytes.inc(n)
            self._m_lat.observe(time.monotonic() - t0)
            return nparts

    def try_fetch(self, prefix: str) -> Optional[bytes]:
        """Local-mode probe: announce key present → pull the parts
        back off the device and verify both digests.  A present
        announce with no registry entry means the publisher lives in
        another process — this engine cannot serve it (degrade)."""
        if self.mode != "local":
            raise TransportUnavailable(
                "collective session mode consumes via the fan-out "
                "session, not per-op"
            )
        with obs.span("transport/collective_fetch", prefix=prefix):
            raw = self.coordinator.kv_try_get(f"{prefix}/xmeta")
            if raw is None:
                return None
            t0 = time.monotonic()
            try:
                nparts_s, ppad_s, n_s, crc_s, adler_s = raw.split(":")
                n, crc, adler = int(n_s), int(crc_s), int(adler_s)
            except ValueError as e:
                raise ValueError(
                    f"malformed transport announce under {prefix!r}: "
                    f"{raw!r}"
                ) from e
            with _registry_lock:
                entry = _REGISTRY.get(prefix)
            if entry is None:
                raise TransportUnavailable(
                    f"announce for {prefix!r} has no in-process "
                    f"registry entry (cross-process publisher)"
                )
            device_parts, reg_n, _, _ = entry
            data = _unpack_parts(device_parts, reg_n)
            got_crc, got_adler = _digests(memoryview(data))
            if reg_n != n or got_crc != crc or got_adler != adler:
                raise ValueError(
                    f"transport payload under {prefix!r} failed "
                    f"digest verification ({reg_n} of {n} bytes)"
                )
            self._m_ops.inc()
            self._m_bytes.inc(n)
            self._m_lat.observe(time.monotonic() - t0)
            return data

    def cleanup(self, prefix: str, nparts: int) -> None:
        """Announce key first (a straggler's probe sees clean absence),
        then the device parts — mirroring the KV engine's meta-first
        discipline."""
        self.coordinator.kv_try_delete(f"{prefix}/xmeta")
        with _registry_lock:
            _REGISTRY.pop(prefix, None)
        with self._lock:
            self._local_prefixes.discard(prefix)

    def device_move(self, buf: Any) -> Any:
        """Continuous peer-delta leg: route one staged payload through
        the device fabric (pack → device_put → device_get → verify)
        and hand back verified host bytes.  Raises on any failure —
        the scheduler's transport leg catches, counts the fallback,
        and writes the ORIGINAL buffer (payloads never depend on the
        fabric for correctness)."""
        import jax

        view = memoryview(buf).cast("B")
        n = view.nbytes
        if n == 0:
            return buf
        with obs.span("transport/device_move", bytes=n):
            crc, adler = _digests(view)
            nparts, ppad = _plan_parts(n, knobs.get_transport_part_bytes())
            failpoint("transport.collective.device_move", bytes=n)
            dev = _devices()[0]
            parts = [
                jax.device_put(hp, dev)
                for hp in _pack_parts(view, nparts, ppad)
            ]
            data = _unpack_parts(parts, n)
            if _digests(memoryview(data)) != (crc, adler):
                raise ValueError(
                    "device round-trip failed digest verification"
                )
            self._m_moves.inc()
            self._m_bytes.inc(n)
            return data

    def close(self) -> None:
        with self._lock:
            prefixes = list(self._local_prefixes)
        for prefix in prefixes:
            self.cleanup(prefix, 0)

    # --------------------------------------------------- session mode

    def open_fanout_session(
        self,
        topology: Any,
        uid: str,
        plan_paths: List[str],
    ) -> "CollectiveFanoutSession":
        """Start the per-restore ordered-broadcast session (session
        mode only).  ``plan_paths`` must be identical on every process
        — the caller derives it from the manifest in read order."""
        if self.mode != "session":
            raise TransportUnavailable("no multi-process jax session")
        return CollectiveFanoutSession(
            self, self.coordinator, topology, uid, plan_paths
        )


class CollectiveFanoutSession:
    """One restore's ordered broadcast schedule (see module docstring).

    The plan is every (slice, path) pair — each slice's designated
    reader is that transfer's source; EVERY process participates in
    every broadcast (SPMD), and only the transfer's slice members keep
    the bytes.  A dedicated thread per process walks the plan in
    order; the read path talks to it through ``offer`` /  ``decline``
    (source side, non-blocking) and ``consume`` (sibling side,
    blocking with session-guaranteed progress).  All waits are bounded
    by ``GATE_TIMEOUT_S``; any anomaly flips ``broken`` and the
    session finishes in drain mode — accepted payloads are
    re-published over the KV blob path so consumers' fan-out ladders
    still find them.
    """

    def __init__(
        self,
        transport: CollectiveTransport,
        coordinator: Any,
        topology: Any,
        uid: str,
        plan_paths: List[str],
    ) -> None:
        self.transport = transport
        self.coordinator = coordinator
        self.topology = topology
        self.uid = uid
        self.timeout_s = GATE_TIMEOUT_S
        # transfer order: path read order (caller-derived) major, slice
        # minor — identical on every process by construction
        self.plan: List[Tuple[int, str]] = [
            (s, p)
            for p in plan_paths
            for s in sorted(set(topology.slice_of))
            if len(topology.ranks_in_slice(s)) >= 2
        ]
        self.index: Dict[Tuple[int, str], int] = {
            key: k for k, key in enumerate(self.plan)
        }
        self.sources: Dict[Tuple[int, str], int] = {
            (s, p): topology.designated_reader(p, s)
            for (s, p) in self.plan
        }
        self._cond_lock = threading.Condition()
        # key → (payload bytes, kv degrade prefix) | None (declined)
        self._offers: Dict[Tuple[int, str], Optional[Tuple[bytes, str]]] = {}
        # key → delivered bytes | None (skipped/degraded)
        self._results: Dict[Tuple[int, str], Optional[bytes]] = {}
        # keys whose offer window passed — a late offer is refused and
        # the plugin publishes over KV inline
        self._abandoned: Set[Tuple[int, str]] = set()
        self.broken = False
        self._closing = False
        # KV blob publications the DRAIN path made: (prefix, nparts)
        self.kv_published: List[Tuple[str, int]] = []
        self._gate_written: List[str] = []
        self._thread = threading.Thread(
            target=self._run,
            name="tsnp-transport-session",
            daemon=True,
        )
        self._thread.start()

    # ------------------------------------------------- read-path API

    def covers(self, key: Tuple[int, str]) -> bool:
        return key in self.index

    def offer(self, key: Tuple[int, str], data: bytes, kv_prefix: str) -> bool:
        """Source side: hand the session this transfer's payload
        (non-blocking).  True = the session owns delivery now (it will
        broadcast, or KV-publish in drain mode); False = too late or
        not planned — publish over KV inline like any other read."""
        with self._cond_lock:
            if key not in self.index or key in self._abandoned:
                return False
            self._offers[key] = (data, kv_prefix)
            self._cond_lock.notify_all()
            return True

    def decline(self, key: Tuple[int, str]) -> None:
        """Source side: this path's reads turned out ranged/ineligible
        — tell the session promptly so siblings get ``skip`` instead
        of burning the offer timeout."""
        with self._cond_lock:
            if key in self.index and key not in self._offers:
                self._offers[key] = None
                self._cond_lock.notify_all()

    def consume(self, key: Tuple[int, str]) -> Optional[bytes]:
        """Sibling side: block until the session resolves this
        transfer.  Bytes = verified broadcast payload; None = skipped
        or degraded (fall into the fan-out KV ladder).  Progress is
        session-guaranteed — every transfer resolves within bounded
        gate timeouts, and a broken/closing session resolves
        everything immediately."""
        with obs.span("transport/collective_consume", path=key[1]):
            with self._cond_lock:
                while key not in self._results and not (
                    self.broken or self._closing
                ):
                    self._cond_lock.wait(0.25)
                return self._results.get(key)

    def close(self) -> None:
        """Stop the schedule walk and reclaim control/degrade keys.
        Called strictly after the restore's final read barrier — no
        rank can still be consuming.  Idempotent: the restore's error
        path closes again unconditionally."""
        with self._cond_lock:
            already = self._closing
            self._closing = True
            self._cond_lock.notify_all()
        if already:
            return
        self._thread.join(self.timeout_s * 2 + 5.0)
        for k in self._gate_written:
            try:
                self.coordinator.kv_try_delete(k)
            except Exception as e:  # noqa: BLE001 — best-effort
                obs.swallowed_exception("transport.session.cleanup", e)
        for prefix, nparts in self.kv_published:
            try:
                self.coordinator.kv_try_delete(f"{prefix}/meta")
                for i in range(nparts):
                    self.coordinator.kv_try_delete(f"{prefix}/p{i}")
            except Exception as e:  # noqa: BLE001 — best-effort
                obs.swallowed_exception("transport.session.cleanup", e)

    # --------------------------------------------------- session loop

    def _gate(self, k: int, leaf: str) -> str:
        key = f"{self.uid}/x/{k}/{leaf}"
        return key

    def _kv_set_gate(self, k: int, leaf: str, value: str) -> None:
        key = self._gate(k, leaf)
        self.coordinator.kv_set(key, value)
        self._gate_written.append(key)

    def _resolve(self, key: Tuple[int, str], data: Optional[bytes]) -> None:
        with self._cond_lock:
            self._results[key] = data
            self._cond_lock.notify_all()

    def _break(self, why: Any) -> None:
        count_fallback("session", why)
        with self._cond_lock:
            self.broken = True
            self._cond_lock.notify_all()

    def _wait_offer(
        self, key: Tuple[int, str]
    ) -> Optional[Tuple[bytes, str]]:
        """Source side: wait (bounded) for the read path's offer or
        decline; past the deadline the key is abandoned so a late
        offer degrades to an inline KV publish."""
        deadline = time.monotonic() + self.timeout_s
        with self._cond_lock:
            while key not in self._offers and not self._closing:
                left = deadline - time.monotonic()
                if left <= 0:
                    self._abandoned.add(key)
                    return None
                self._cond_lock.wait(min(left, 0.25))
            if key not in self._offers:
                self._abandoned.add(key)
                return None
            return self._offers[key]

    def _run(self) -> None:
        with obs.span("transport/session", transfers=len(self.plan)):
            try:
                for k, key in enumerate(self.plan):
                    with self._cond_lock:
                        if self._closing:
                            return
                    if self.broken:
                        self._drain_one(k, key)
                        continue
                    try:
                        self._run_one(k, key)
                    except Exception as e:  # noqa: BLE001 — any
                        # anomaly breaks the session; payloads keep
                        # moving over KV (drain + read-path ladder)
                        self._break(e)
                        self._drain_one(k, key, already_failed=True)
            except BaseException as e:  # noqa: BLE001 — the loop
                # itself must never die silently: consume() waiters
                # would wedge past every timeout
                self._break(e)
            finally:
                with self._cond_lock:
                    for key in self.plan:
                        self._results.setdefault(key, None)
                    self._cond_lock.notify_all()

    def _run_one(self, k: int, key: Tuple[int, str]) -> None:
        from jax.experimental import multihost_utils

        np = _np()
        slice_id, path = key
        src = self.sources[key]
        me = self.coordinator.rank
        if me == src:
            offered = self._wait_offer(key)
            if offered is None:
                self._kv_set_gate(k, "go", "skip")
                self._resolve(key, None)
                return
            data, kv_prefix = offered
            failpoint(
                "transport.collective.broadcast", path=path, k=k
            )
            t0 = time.monotonic()
            view = memoryview(data)
            n = view.nbytes
            crc, adler = _digests(view)
            nparts, ppad = _plan_parts(
                n, knobs.get_transport_part_bytes()
            )
            parts = _pack_parts(view, nparts, ppad)
            self._kv_set_gate(
                k, "go", f"ok:{n}:{nparts}:{ppad}:{crc}:{adler}"
            )
            # one shared deadline for ALL acks, so the slowest
            # sibling's gate-2 wait budget stays a small multiple of
            # the timeout knob instead of world × timeout
            deadline = time.monotonic() + self.timeout_s
            for r in range(self.coordinator.world_size):
                if r == me:
                    continue
                left = max(0.05, deadline - time.monotonic())
                try:
                    self.coordinator.kv_get(
                        self._gate(k, f"ack/{r}"), timeout_s=left
                    )
                except Exception as e:  # noqa: BLE001 — a silent
                    # rank means no collective may be entered
                    self._kv_set_gate(k, "go2", "cancel")
                    self._break(e)
                    self.kv_published.append(
                        (
                            kv_prefix,
                            self._kv_degrade_publish(kv_prefix, data),
                        )
                    )
                    self._resolve(key, None)
                    return
            self._kv_set_gate(k, "go2", "go")
            for part in parts:
                multihost_utils.broadcast_one_to_all(
                    part, is_source=True
                )
            self.transport._m_ops.inc()
            self.transport._m_bytes.inc(n)
            self.transport._m_lat.observe(time.monotonic() - t0)
            self._resolve(key, None)  # the source has its own bytes
        else:
            raw = self.coordinator.kv_get(
                self._gate(k, "go"), timeout_s=self.timeout_s
            )
            if raw == "skip":
                self._resolve(key, None)
                return
            t0 = time.monotonic()
            _, n_s, nparts_s, ppad_s, crc_s, adler_s = raw.split(":")
            n, nparts, ppad = int(n_s), int(nparts_s), int(ppad_s)
            self._kv_set_gate(k, f"ack/{me}", "1")
            # 2× the knob: the source's ack collection runs on ONE
            # shared timeout window, so go2 lands within ~timeout of
            # our ack barring a dead source
            g2 = self.coordinator.kv_get(
                self._gate(k, "go2"), timeout_s=self.timeout_s * 2
            )
            if g2 != "go":
                self._resolve(key, None)
                self._break(f"transfer {k} cancelled by source")
                return
            zeros = np.zeros(ppad // _WORD, dtype=np.uint32)
            parts = [
                multihost_utils.broadcast_one_to_all(
                    zeros, is_source=False
                )
                for _ in range(nparts)
            ]
            data = _unpack_parts(parts, n)
            mine = me in self.topology.ranks_in_slice(slice_id)
            got_crc, got_adler = _digests(memoryview(data))
            if (got_crc, got_adler) != (int(crc_s), int(adler_s)):
                # bad bytes never break the session (the collective
                # itself stayed in lockstep); this consumer just
                # degrades to the ladder
                count_fallback(
                    "broadcast-verify", f"digest mismatch for {path!r}"
                )
                self._resolve(key, None)
                return
            if mine:
                self.transport._m_ops.inc()
                self.transport._m_bytes.inc(n)
                self.transport._m_lat.observe(time.monotonic() - t0)
                self._resolve(key, data)
            else:
                self._resolve(key, None)

    def _drain_one(
        self, k: int, key: Tuple[int, str], already_failed: bool = False
    ) -> None:
        """Broken-session duty: no collectives, but accepted offers
        were promised delivery — publish them over the KV blob path so
        siblings' ladders find them; everything else resolves None."""
        me = self.coordinator.rank
        if self.sources[key] == me:
            offered = self._wait_offer(key)
            if offered is not None:
                data, kv_prefix = offered
                n = self._kv_degrade_publish(kv_prefix, data)
                if n:
                    self.kv_published.append((kv_prefix, n))
        self._resolve(key, None)

    def _kv_degrade_publish(self, prefix: str, data: bytes) -> int:
        """Re-publish one accepted payload over the KV blob path;
        returns nparts (0 on failure — the ladder's re-election still
        covers the siblings)."""
        try:
            n = self.coordinator.kv_publish_blob(prefix, data)
            obs.counter(obs.TRANSPORT_KV_OPS).inc()
            obs.counter(obs.TRANSPORT_KV_BYTES).inc(n)
            return max(1, -(-n // KV_BLOB_PART_BYTES))
        except Exception as e:  # noqa: BLE001 — best-effort degrade
            obs.swallowed_exception("transport.session.degrade", e)
            return 0
