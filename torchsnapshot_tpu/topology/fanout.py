"""Fan-out restore: read each replicated object once per SLICE, then
redistribute the bytes to sibling ranks over the coordination layer.

A flat restore has every rank GET every replicated object from the
durable tier — O(objects × ranks) GETs, a self-inflicted DDoS on the
bucket at multislice scale.  The shared-host cache
(storage/hostcache.py) already collapses that to once per HOST for
co-located processes; this module is the cross-host generalization:
for each shared object a deterministic **designated reader** rank per
slice (Topology.designated_reader — spread across the slice's hosts)
performs the one durable GET and publishes the bytes over the
coordination KV (``Coordinator.kv_publish_blob``: chunked, crc32
digest-verified, meta-key-last so presence implies completeness);
sibling ranks poll for the publication and consume it instead of
issuing their own GET.

Failure semantics — a dead reader degrades, never wedges, and never
stampedes: a sibling that sees no publication within
``FANOUT_TIMEOUT_S`` does NOT immediately issue its own durable GET
(at slice scale that synchronized burst is the very DDoS fan-out
exists to prevent).  Instead the slice re-elects: the next rank in the
stable ``Topology.reader_candidates`` rotation — agreed on every
process with zero communication — takes over the durable read AND the
publication, while the remaining siblings wait one more bounded window
for the takeover publication.  Only if that second window also passes
(both readers dead / publication broken) do siblings read direct, and
then in host-staggered waves: co-hosted processes collapse through the
shared-host cache's single-flight, and each host's wave starts
``_FALLBACK_STAGGER_S`` after the previous one, so the durable tier
sees a ramp instead of a thundering herd.
``topology.fanout_fallbacks`` counts affected OBJECTS (once per object
per rank), not raw read attempts; a digest mismatch or delivery error
still falls back directly (the bytes can't be trusted — correctness
over smoothness).  Publication itself is best-effort: a publish
failure costs peers their savings, not the restore.

Composition: the wrapper goes OUTSIDE the shared-host cache, so the
designated reader's one GET is itself host-deduped — per slice the
durable tier sees exactly one GET per object, regardless of how many
hosts or processes the slice spans.  A slice whose members all share
one host with the cache active skips fan-out entirely (the cache
already covers it; the KV hop would be pure overhead).

Scope: only storage locations under ``replicated/`` that every rank
reads (``shared_read_locations``) participate — per-rank and sharded
objects have per-rank readers, and slab-batched objects live under a
rank namespace; both take the direct path unchanged.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
import threading
import time
from typing import Any, Dict, Iterable, Optional, Set

from .. import knobs, obs
from ..coordination import KV_BLOB_PART_BYTES
from ..io_types import (
    ReadIO,
    StoragePlugin,
    WriteIO,
    resolve_read_destination,
)
from ..resilience.failpoints import failpoint
from ..storage.hostcache import host_cache_active
from ..transport import TransportUnavailable, count_fallback
from .model import Topology

logger = logging.getLogger(__name__)

_SHARED_PREFIX = "replicated/"
# how often a sibling re-probes the KV for its designated reader's
# publication (one kv_try_get per tick)
_FETCH_POLL_S = 0.025
# per-HOST wave spacing for the last-resort direct fallback (both
# elected readers silent): host k's processes start their direct read
# k * this many seconds after the first wave — long enough to spread
# the burst, short enough to be noise next to the two timeout windows
# already spent
_FALLBACK_STAGGER_S = 0.05


def fanout_enabled(topology: Topology) -> bool:
    """Whether this rank's restore should fan out (see module
    docstring).  "on" forces it whenever the slice has siblings; "auto"
    additionally requires an explicit topology and skips slices already
    covered by a same-host shared cache."""
    mode = knobs.get_fanout()
    if mode == "off":
        return False
    members = topology.ranks_in_slice(topology.slice_id)
    if len(members) < 2:
        return False
    if mode == "on":
        return True
    if not topology.explicit:
        return False
    if host_cache_active() and len(
        {topology.host_of[r] for r in members}
    ) == 1:
        # single-host slice with the shared cache active: the flock
        # single-flight already makes the slice cost one GET per object
        return False
    return True


def fanout_world_uniform(topology: Topology) -> bool:
    """Whether EVERY rank's ``fanout_enabled`` decision comes out True
    under this process's knobs — the collective fan-out session's
    precondition.  The session's gate protocol and broadcasts need all
    world processes participating; a single-member slice (or a
    single-host slice the shared cache already covers) opts its ranks
    out of fan-out entirely, and a session would stall waiting for
    their acks.  Evaluated from global topology state only, so every
    process computes the same answer (knob parity across the fleet is
    the same SPMD contract restore already documents)."""
    mode = knobs.get_fanout()
    if mode == "off":
        return False
    for s in sorted(set(topology.slice_of)):
        members = topology.ranks_in_slice(s)
        if len(members) < 2:
            return False
        if mode == "auto":
            if not topology.explicit:
                return False
            if host_cache_active() and len(
                {topology.host_of[r] for r in members}
            ) == 1:
                return False
    return True


def _entry_shared_locations(entry: Any) -> Iterable[str]:
    """The ``replicated/``-namespaced storage locations one manifest
    entry reads (whole object plus shard/chunk pieces)."""
    if not getattr(entry, "replicated", False):
        return
    loc = getattr(entry, "location", None)
    if isinstance(loc, str) and loc.startswith(_SHARED_PREFIX):
        yield loc
    for attr in ("shards", "chunks"):
        for piece in getattr(entry, attr, None) or ():
            ploc = getattr(piece, "location", None)
            if isinstance(ploc, str) and ploc.startswith(_SHARED_PREFIX):
                yield ploc


def shared_read_locations(manifest: Dict[str, Any]) -> Set[str]:
    """Storage locations every rank reads during a full restore: the
    ``replicated/``-namespaced extents of replicated entries (whole
    objects plus chunk pieces).  Slab-batched replicated leaves live
    under a rank namespace and are deliberately excluded — their slab
    mixes per-rank members whose ranges only one rank reads, and a
    designated reader would never publish those."""
    out: Set[str] = set()
    for entry in manifest.values():
        out.update(_entry_shared_locations(entry))
    return out


def ordered_shared_locations(
    manifest: Dict[str, Any],
    shared: Set[str],
    key_order: Iterable[str],
) -> list:
    """``shared`` in restore READ order: grouped by the owning app
    key's position in the restore's global key order (manifest logical
    paths lead with the app key), location-sorted within a key.  The
    collective fan-out session schedules its transfers in this order,
    so the schedule advances in step with the restore's per-key read
    phases — a plan sorted any other way would park the session waiting
    on a later key's object while every rank is still gated behind an
    earlier key's barrier."""
    pos = {k: i for i, k in enumerate(key_order)}
    best: Dict[str, int] = {}
    for p, entry in manifest.items():
        i = pos.get(p.split("/", 1)[0])
        if i is None:
            continue
        for loc in _entry_shared_locations(entry):
            if loc in shared and (loc not in best or i < best[loc]):
                best[loc] = i
    tail = sorted(p for p in shared if p not in best)
    return sorted(best, key=lambda loc: (best[loc], loc)) + tail


def _blob_prefix(uid: str, slice_id: int, path: str, byte_range: Any) -> str:
    """KV prefix for one (object, byte range) publication — hashed so
    arbitrary object paths never collide with the KV key grammar; the
    byte range is part of the identity because striped/codec reads of
    one object fan out as multiple ranged reads (identically planned on
    every rank)."""
    h = hashlib.sha256()
    h.update(path.encode())
    if byte_range is not None:
        h.update(f"|{byte_range[0]}-{byte_range[1]}".encode())
    return f"{uid}/s{slice_id}/{h.hexdigest()[:32]}"


async def publish_object(
    coordinator: Any, prefix: str, buf: Any, path: str
) -> int:
    """Best-effort publication of one read's bytes for this slice's
    siblings; returns the number of KV parts written (0 on failure —
    the caller's cleanup ledger).  Never raises: the designated
    reader's own restore must not fail because a publication could not
    be made — peers fall back to direct reads and the failure stays
    visible as their ``fanout_fallbacks``."""
    with obs.span("fanout/publish", path=path):
        try:
            failpoint("topology.fanout.publish", path=path)
            loop = asyncio.get_running_loop()
            n = await loop.run_in_executor(
                None, coordinator.kv_publish_blob, prefix, buf
            )
            obs.counter(obs.FANOUT_PUBLISHES).inc()
            obs.counter(obs.FANOUT_BYTES_REDISTRIBUTED).inc(n)
            return max(1, -(-n // KV_BLOB_PART_BYTES))
        except Exception as e:  # noqa: BLE001 — best-effort by contract
            obs.swallowed_exception("topology.fanout.publish", e)
            return 0


async def fetch_published(
    coordinator: Any,
    prefix: str,
    path: str,
    timeout_s: float,
    transport: Any = None,
) -> Optional[bytes]:
    """Poll for the designated reader's publication of ``path``; the
    verified bytes, or None when the deadline passes or verification
    fails (the caller falls back to a direct durable read).  Polling
    runs from the event loop (one non-blocking probe per tick) so a
    host full of waiting siblings never parks scheduler threads.

    With a ``transport`` the device-registry announce is probed FIRST
    each tick (the publisher may have used either engine — its own
    transport could have degraded mid-publish), then the KV blob.  A
    ``TransportUnavailable`` from the probe demotes this wait to
    KV-only; it is not a fallback event (the publisher's engine choice
    decides where bytes actually travelled)."""
    with obs.span("fanout/fetch", path=path):
        loop = asyncio.get_running_loop()
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                data = None
                if transport is not None:
                    try:
                        data = await loop.run_in_executor(
                            None, transport.try_fetch, prefix
                        )
                    except TransportUnavailable:
                        transport = None
                if data is None:
                    data = await loop.run_in_executor(
                        None, coordinator.kv_try_fetch_blob, prefix
                    )
                    if data is not None:
                        # KV-leg consumption, metered under the same
                        # instrument family as the collective engine, so
                        # the two engines compare directly
                        obs.counter(obs.TRANSPORT_KV_OPS).inc()
                        obs.counter(obs.TRANSPORT_KV_BYTES).inc(
                            len(data)
                        )
            except ValueError as e:
                # digest/length mismatch: the publication cannot be
                # trusted — direct read, never corrupt bytes
                logger.warning(
                    "fan-out publication for %r failed verification "
                    "(%s); falling back to a direct read", path, e,
                )
                return None
            if data is not None:
                return data
            if time.monotonic() >= deadline:
                return None
            await asyncio.sleep(_FETCH_POLL_S)


class FanoutReadPlugin(StoragePlugin):
    """Per-restore storage wrapper implementing the read-once-per-slice
    protocol over ``inner`` (see module docstring).  Reads of shared
    locations route through the designated-reader election; everything
    else (per-rank objects, markers, writes, deletes) passes straight
    through."""

    def __init__(
        self,
        inner: StoragePlugin,
        coordinator: Any,
        topology: Topology,
        uid: str,
        shared_paths: Iterable[str],
        transport: Any = None,
    ) -> None:
        self.inner = inner
        self.coordinator = coordinator
        self.topology = topology
        self.uid = uid
        self.shared_paths = set(shared_paths)
        # engine-selected payload transport (transport/); None keeps
        # the pre-transport KV-blob behavior bit-for-bit
        self.transport = transport
        # a CollectiveFanoutSession once restore derives the read-
        # ordered plan (attached AFTER construction — the plan needs
        # the gathered global key order); None = per-op transport only
        self.transport_session: Any = None
        # capability delegation: non-shared reads (per-rank/sharded
        # state — usually the bulk) keep the inner plugin's zero-copy
        # mmap path and budget exemption.  Shared reads are still
        # planned identically on every rank (same want_mmap branch);
        # a sibling served from a publication hands back heap bytes,
        # which the read scheduler's existing declined-mmap handling
        # debits against the budget.
        self.supports_mmap_read = bool(
            getattr(inner, "supports_mmap_read", False)
        )
        self.mmap_budget_exempt = bool(
            getattr(inner, "mmap_budget_exempt", False)
        )
        self.supports_striped_write = bool(
            getattr(inner, "supports_striped_write", False)
        )
        self.supports_fused_digest = bool(
            getattr(inner, "supports_fused_digest", False)
        )
        # (prefix, nparts) of this rank's successful publications, so
        # cleanup_published can reclaim the transient KV blobs after
        # every slice member is past its reads.  Reads append on the
        # loop; cleanup runs on the restore caller — locked handoff
        self._pub_lock = threading.Lock()
        self._published: list = []
        # the shared locations THIS rank is the designated reader for:
        # the scheduler front-loads these so siblings wait the minimum
        # (scheduler.sync_execute_read_reqs publish_first ordering)
        self.local_publish_paths = {
            p
            for p in self.shared_paths
            if topology.designated_reader(p) == coordinator.rank
        }
        m = obs.REGISTRY
        self._m_durable = m.counter(obs.FANOUT_DURABLE_READS)
        self._m_saved = m.counter(obs.FANOUT_DURABLE_GETS_SAVED)
        self._m_fallbacks = m.counter(obs.FANOUT_FALLBACKS)
        # per-OBJECT fallback accounting: striped/codec restores issue
        # several ranged reads per object, and counting each would make
        # one broken object look like a fleet incident
        self._fallback_paths: Set[str] = set()

    def _count_fallback(self, path: str) -> None:
        with self._pub_lock:
            if path in self._fallback_paths:
                return
            self._fallback_paths.add(path)
        self._m_fallbacks.inc()

    def _local_transport(self) -> Any:
        """The transport, iff it can serve per-op publish/fetch in this
        process (the collective engine's in-process device-registry
        mode).  Session mode moves whole objects through the fan-out
        session instead, and its per-op API raising
        ``TransportUnavailable`` is by design, not a degrade."""
        t = self.transport
        if t is not None and getattr(t, "mode", None) == "local":
            return t
        return None

    async def _publish_payload(self, prefix: str, buf: Any, path: str):
        """Publish one read's bytes over the selected engine; returns
        the cleanup-ledger entry ``(engine, prefix, nparts)`` or None.
        A collective-engine failure mid-publish degrades THIS op to the
        KV blob path (``transport.fallbacks`` advances); the KV leg's
        own failure stays best-effort as before."""
        t = self._local_transport()
        if t is not None:
            try:
                loop = asyncio.get_running_loop()
                nparts = await loop.run_in_executor(
                    None, t.publish, prefix, buf
                )
                obs.counter(obs.FANOUT_PUBLISHES).inc()
                obs.counter(obs.FANOUT_BYTES_REDISTRIBUTED).inc(
                    obs.buf_nbytes(buf)
                )
                return ("collective", prefix, nparts)
            except Exception as e:  # noqa: BLE001 — mid-op degrade:
                # the payload must still reach the siblings
                count_fallback("fanout-publish", e)
        nparts = await publish_object(self.coordinator, prefix, buf, path)
        if nparts:
            obs.counter(obs.TRANSPORT_KV_OPS).inc()
            obs.counter(obs.TRANSPORT_KV_BYTES).inc(obs.buf_nbytes(buf))
            return ("kv", prefix, nparts)
        return None

    async def _read_and_publish(self, read_io: ReadIO, prefix: str) -> None:
        """The designated-reader duty: one durable GET, then publish
        the bytes for the slice's siblings."""
        await self.inner.read(read_io)
        self._m_durable.inc()
        entry = await self._publish_payload(
            prefix, read_io.buf, read_io.path
        )
        if entry is not None:
            with self._pub_lock:
                self._published.append(entry)

    def _deliver(self, read_io: ReadIO, data: bytes) -> bool:
        """Place redistributed bytes into the read's destination; False
        on a mismatch (the caller falls back to a direct read)."""
        try:
            out = resolve_read_destination(read_io.into, len(data))
            memoryview(out).cast("B")[:] = data
            read_io.buf = out
            self._m_saved.inc()
            return True
        except Exception as e:  # noqa: BLE001 — delivery mismatch:
            # e.g. an ``into`` destination sized for a different
            # extent; the direct read is always correct
            obs.swallowed_exception("topology.fanout.deliver", e)
            return False

    async def read(self, read_io: ReadIO) -> None:
        path = read_io.path
        if path not in self.shared_paths:
            await self.inner.read(read_io)
            return
        prefix = _blob_prefix(
            self.uid, self.topology.slice_id, path, read_io.byte_range
        )
        session = self.transport_session
        skey = (self.topology.slice_id, path)
        if session is not None and not session.covers(skey):
            session = None
        loop = asyncio.get_running_loop()
        if path in self.local_publish_paths:
            if session is not None:
                if read_io.byte_range is not None:
                    # ranged reads (striped/codec extents) ride the KV
                    # blob path per byte range; tell the session
                    # promptly so siblings get "skip", not a timeout
                    session.decline(skey)
                else:
                    await self.inner.read(read_io)
                    self._m_durable.inc()
                    data = bytes(
                        memoryview(read_io.buf).cast("B")
                    )
                    accepted = await loop.run_in_executor(
                        None, session.offer, skey, data, prefix
                    )
                    if accepted:
                        # the session owns delivery now: broadcast on
                        # its schedule, or KV-publish from its drain
                        # path (its ledger, its cleanup)
                        return
                    entry = await self._publish_payload(
                        prefix, data, path
                    )
                    if entry is not None:
                        with self._pub_lock:
                            self._published.append(entry)
                    return
            await self._read_and_publish(read_io, prefix)
            return
        timeout_s = knobs.get_fanout_timeout_s()
        if session is not None and read_io.byte_range is None:
            data = await loop.run_in_executor(
                None, session.consume, skey
            )
            if data is not None and self._deliver(read_io, data):
                return
            # skipped / degraded / mismatched delivery: fall into the
            # KV ladder below — the session's drain path (or the
            # source's inline publish) feeds it
        data = await fetch_published(
            self.coordinator, prefix, path, timeout_s,
            transport=self._local_transport(),
        )
        if data is None:
            # designated reader silent past the deadline (dead, hung,
            # or its publish failed): re-elect.  The candidates
            # rotation is identical on every process, so the slice
            # agrees with zero communication that the NEXT candidate
            # takes over the read+publish while everyone else waits
            # one more bounded window for the takeover publication.
            cands = self.topology.reader_candidates(path)
            alternate = cands[1] if len(cands) > 1 else cands[0]
            if self.coordinator.rank == alternate:
                logger.warning(
                    "fan-out: designated reader rank %d published "
                    "nothing for %r within %gs; rank %d taking over "
                    "the slice read", cands[0], path, timeout_s,
                    alternate,
                )
                self._count_fallback(path)
                await self._read_and_publish(read_io, prefix)
                return
            data = await fetch_published(
                self.coordinator, prefix, path, timeout_s,
                transport=self._local_transport(),
            )
            if data is None:
                # both elected readers silent: every sibling reads
                # direct — in host-staggered waves (co-hosted
                # processes collapse via the shared-host cache's
                # single-flight; each host's wave starts one stagger
                # after the previous), so the durable tier sees a
                # ramp, never a synchronized burst
                self._count_fallback(path)
                hosts_in_order: list = []
                for r in cands:
                    h = self.topology.host_of[r]
                    if h not in hosts_in_order:
                        hosts_in_order.append(h)
                my_host = self.topology.host_of[self.coordinator.rank]
                pos = (
                    hosts_in_order.index(my_host)
                    if my_host in hosts_in_order
                    else len(hosts_in_order)
                )
                if pos:
                    await asyncio.sleep(_FALLBACK_STAGGER_S * pos)
                self._m_durable.inc()
                await self.inner.read(read_io)
                return
        if self._deliver(read_io, data):
            return
        self._count_fallback(path)
        self._m_durable.inc()
        await self.inner.read(read_io)

    def cleanup_published(self) -> None:
        """Delete this rank's transient publications — KV blob keys
        (meta key first, so a straggler's poll sees clean absence and
        takes the normal timeout-fallback path) and device-registry
        entries with their announce keys.  Called by restore strictly
        AFTER the last cross-rank barrier — every slice member is past
        its reads by then, so nothing can still be consuming a
        publication.  Best-effort: a failed delete leaks one restore's
        blobs until job teardown, never fails the restore."""
        with self._pub_lock:
            published, self._published = self._published, []
        for engine, prefix, nparts in published:
            try:
                if engine == "collective" and self.transport is not None:
                    self.transport.cleanup(prefix, nparts)
                else:
                    self.coordinator.kv_try_delete(f"{prefix}/meta")
                    for i in range(nparts):
                        self.coordinator.kv_try_delete(f"{prefix}/p{i}")
            except Exception as e:  # noqa: BLE001 — best-effort cleanup
                obs.swallowed_exception("topology.fanout.cleanup", e)

    # ------------------------------------------------- pass-throughs

    async def write(self, write_io: WriteIO) -> None:
        await self.inner.write(write_io)

    async def delete(self, path: str) -> None:
        await self.inner.delete(path)

    async def stat(self, path: str) -> int:
        return await self.inner.stat(path)

    async def link_from(self, base_url: str, path: str) -> None:
        await self.inner.link_from(base_url, path)

    async def close(self) -> None:
        await self.inner.close()
