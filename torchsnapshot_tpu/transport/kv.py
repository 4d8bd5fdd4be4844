"""KVTransport: the chunked-base64 coordination-KV engine.

The degraded-but-always-available payload path: exactly the
``kv_publish_blob``/``kv_try_fetch_blob`` primitives the fan-out
restore has used since the multislice PR, wrapped in the Transport
API and metered under ``transport.kv_*`` so a KV-vs-collective
comparison reads both engines off one instrument family.
Correctness properties are the KV blob contract's: parts written
first, ``meta`` key LAST (presence implies completeness), crc32
verified on fetch before any byte is trusted; delivered bytes then
flow through the read pipeline's manifest-digest checks like any
other read, so end-to-end verification matches the collective
engine's crc32+adler32 discipline.
"""

from __future__ import annotations

import time
from typing import Any, Optional

from .. import obs
from ..coordination import KV_BLOB_PART_BYTES
from . import Transport


class KVTransport(Transport):
    engine = "kv"

    def __init__(self, coordinator: Any) -> None:
        self.coordinator = coordinator
        m = obs.REGISTRY
        self._m_ops = m.counter(obs.TRANSPORT_KV_OPS)
        self._m_bytes = m.counter(obs.TRANSPORT_KV_BYTES)
        self._m_lat = m.histogram(obs.TRANSPORT_KV_S)

    def publish(self, prefix: str, data: Any) -> int:
        """Chunked-KV publication; returns the number of part keys
        written (the caller's cleanup ledger)."""
        with obs.span("transport/kv_publish", prefix=prefix):
            t0 = time.monotonic()
            n = self.coordinator.kv_publish_blob(prefix, data)
            self._m_ops.inc()
            self._m_bytes.inc(n)
            self._m_lat.observe(time.monotonic() - t0)
            return max(1, -(-n // KV_BLOB_PART_BYTES))

    def try_fetch(self, prefix: str) -> Optional[bytes]:
        """Non-blocking probe + crc-verified fetch; None = not (yet)
        published.  ``ValueError`` propagates — the caller decides
        whether a broken publication means retry or direct read."""
        with obs.span("transport/kv_fetch", prefix=prefix):
            t0 = time.monotonic()
            data = self.coordinator.kv_try_fetch_blob(prefix)
            if data is not None:
                self._m_ops.inc()
                self._m_bytes.inc(len(data))
                self._m_lat.observe(time.monotonic() - t0)
            return data

    def cleanup(self, prefix: str, nparts: int) -> None:
        """Meta key first (a straggler's probe sees clean absence),
        then the parts — the fan-out delete-after-final-barrier
        protocol, shared by every caller of this engine."""
        self.coordinator.kv_try_delete(f"{prefix}/meta")
        for i in range(int(nparts)):
            self.coordinator.kv_try_delete(f"{prefix}/p{i}")

    # device_move is the base identity: the KV engine has no device
    # fabric leg, and the continuous caller's digest checks already
    # ride the chunk-key verification downstream.
