"""Payload transport: how redistribution bytes physically move.

Every byte the fleet redistributes — fan-out restore blobs
(topology/fanout.py), continuous peer-delta replication
(continuous/loop.py), publish/ subscriber chunk fan-in — historically
rode the coordination KV (``kv_publish_blob``: chunked base64, a 4/3
expansion per byte, bounded by the coordination service).  This
package splits that single channel into an engine-selected DATA plane
with the KV demoted to the CONTROL plane:

- ``CollectiveTransport`` (collective.py) moves payloads as jax device
  arrays — uint8 bytes packed into uint32 lanes, padded to the 128-
  byte lane width, chunked at ``TRANSPORT_PART_BYTES`` — over the
  multi-process runtime (``multihost_utils.broadcast_one_to_all`` on
  the live ``jax.distributed`` session for one→slice fan-out, a
  device round-trip for in-process peer legs).  The KV still carries
  the announce/digest/go-no-go metadata in this mode; only the
  payload bytes leave it.
- ``KVTransport`` (kv.py) is the degraded fallback: the existing
  ``kv_publish_blob``/``kv_try_fetch_blob`` path, now metered under
  the ``transport.*`` instruments so both engines report comparable
  bytes/latency numbers.

Selection (``resolve_transport``) is capability-probed per resolve and
observable: the ``TRANSPORT`` knob states a preference
(auto/collective/kv), the probe checks what the runtime can actually
do (multi-process jax session whose process indices align with the
coordinator's ranks, or an in-process device registry for
single-process worlds), and every downgrade — at probe time or mid-op
— advances ``transport.fallbacks`` and lands on KV.  Transport NEVER
wedges an operation: every collective wait is bounded
(``collective.GATE_TIMEOUT_S``), and any anomaly degrades the op (and, for
session-ordered collectives, the rest of the session) to the KV path
the fan-out timeout ladder already defines.

Payload integrity is engine-independent: both engines verify
crc32 + adler32 over the exact payload bytes before a consumer may
trust them, and delivered bytes still flow through the read
pipeline's existing manifest-digest verification — the transport
engine can change WHERE bytes travel, never what arrives.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Optional

from .. import knobs, obs

logger = logging.getLogger(__name__)


class TransportUnavailable(Exception):
    """The probed engine cannot run in this process/runtime (no jax,
    no aligned multi-process session, registry miss, ...).  Callers
    degrade to the KV engine — never an operation failure."""


class Transport:
    """One payload-transport engine.  The API mirrors the KV blob
    primitives so call sites swap engines without re-plumbing:

    - ``publish(prefix, data)`` → nparts: make ``data`` fetchable by
      peers under ``prefix`` (announce metadata rides the KV in both
      engines).
    - ``try_fetch(prefix)`` → bytes | None: non-blocking probe for a
      publication; None = not (yet) there, ``TransportUnavailable`` =
      this engine cannot serve it (degrade), ``ValueError`` = digest
      mismatch (never trust the bytes).
    - ``cleanup(prefix, nparts)``: best-effort reclaim of one
      publication.
    - ``device_move(buf)`` → bytes: route one already-staged payload
      through the engine's fabric leg (device round-trip for the
      collective engine, identity for KV) with digest verification —
      the continuous peer-delta hook.
    - ``close()``: release engine state.
    """

    engine: str = "none"

    def publish(self, prefix: str, data: Any) -> int:
        raise NotImplementedError

    def try_fetch(self, prefix: str) -> Optional[bytes]:
        raise NotImplementedError

    def cleanup(self, prefix: str, nparts: int) -> None:
        raise NotImplementedError

    def device_move(self, buf: Any) -> Any:
        return buf

    def close(self) -> None:
        pass


# last engine resolve_transport selected in this process — the flight-
# record stamp (obs/aggregate.py) reads it; guarded because restores
# and background subscribers resolve concurrently
_engine_lock = threading.Lock()
_last_engine: Optional[str] = None


def _note_engine(engine: str) -> None:
    global _last_engine
    with _engine_lock:
        _last_engine = engine


def current_engine() -> Optional[str]:
    """The engine the most recent ``resolve_transport`` in this process
    selected, or None when transport has never been resolved."""
    with _engine_lock:
        return _last_engine


def count_fallback(site: str, reason: Any) -> None:
    """One collective→KV degrade happened (probe-time or mid-op):
    advance the contract counter and keep the reason visible."""
    obs.counter(obs.TRANSPORT_FALLBACKS).inc()
    logger.warning("transport: %s degraded to kv (%s)", site, reason)


def resolve_transport(
    coordinator: Any = None, topology: Any = None
) -> Transport:
    """Capability-probed engine selection (see module docstring).

    ``TRANSPORT=kv`` short-circuits to the KV engine.  ``collective``
    and ``auto`` probe the collective engine; ``auto`` additionally
    requires a live multi-process jax session (single-process worlds
    get the in-process device path only when explicitly forced, so a
    multi-process CPU fleet without ``jax.distributed`` never
    half-selects an engine its peers cannot join).  Any probe failure
    degrades to KV with ``transport.fallbacks`` advancing — resolution
    itself never raises.
    """
    from .kv import KVTransport

    with obs.span("transport/resolve"):
        mode = knobs.get_transport()
        if mode != "kv":
            try:
                from .collective import CollectiveTransport

                t = CollectiveTransport(
                    coordinator, topology=topology, require_session=(mode == "auto")
                )
                _note_engine(t.engine)
                return t
            except TransportUnavailable as e:
                if mode == "collective":
                    # an explicit request we cannot honor is a real
                    # degrade; quiet auto-probe misses are not
                    count_fallback("resolve", e)
                else:
                    logger.debug("transport auto-probe: kv (%s)", e)
            except Exception as e:  # noqa: BLE001 — probe must never
                # fail the operation that asked for a transport
                count_fallback("resolve", e)
        t = KVTransport(coordinator)
        _note_engine(t.engine)
        return t
