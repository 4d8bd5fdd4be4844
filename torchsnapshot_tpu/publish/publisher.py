"""Training-side publication: turn durable commits into publication
records a serving fleet can delta-subscribe to.

One ``Publisher`` owns one publication root and serves three sources:

- ``publish_continuous(durable_store_root, step)`` — reference the
  continuous loop's durable mirror (continuous/store.py): the step
  manifest's content-addressed chunk keys become keyed refs, zero data
  movement.  This is the hook the continuous loop calls at every
  confirmed durable promotion.
- ``publish_snapshot(path, step, metadata=None)`` — reference a
  committed snapshot: CAS chunk tables become keyed chunk refs,
  whole-object digests become keyed whole-object refs, stripe/slab
  extents and pre-CAS manifests become un-keyed extent refs (fetched
  conservatively by subscribers).  Codec-framed and sharded entries
  cannot be referenced as raw bytes and are skipped with a counter —
  publish from a continuous mirror or ``publish_state`` for full
  coverage.
- ``publish_state(app_state, step)`` — self-contained: flatten the
  live state, chunk-digest every leaf at the CAS chunk size, write
  only the chunks the previous record didn't already reference into
  the root's own ``objects/`` pool (budgeted, via the scheduler's
  buffer-write engine), then commit the record.  This is the
  SnapshotManager-free path and the acceptance tests' workhorse.

Every publication is the same marker-last commit (record body → HEAD
flip, publish/record.py) followed by a best-effort KV announce
(publish/announce.py).  Retention prunes records beyond the configured
window plus any pool chunks only they referenced.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, List, Optional, Set, Tuple

from .. import knobs, obs
from ..cas.store import (
    chunk_key,
    chunk_location,
    resolve_root,
)
from ..continuous.store import (
    ContinuousStore,
    encode_leaf,
    step_manifest_path,
)
from ..coordination import Coordinator
from ..flatten import flatten
from ..resilience.failpoints import failpoint
from ..storage.stripe import plan_parts
from ..utils.checksums import adler32_fast, crc32_fast
from . import announce as announce_mod
from .record import PublishStore, build_record, make_ref, record_path

logger = logging.getLogger(__name__)


class Publisher:
    """See module docstring.  Thread-safe: the continuous loop's worker
    thread and a training loop's sync saves may publish concurrently
    (publications serialize under one lock — records are strictly
    ordered by the marker-last HEAD anyway)."""

    def __init__(
        self,
        root: str,
        coordinator: Optional[Coordinator] = None,
        retain: int = 4,
        chunk_size_bytes: Optional[int] = None,
    ) -> None:
        self.root = root.rstrip("/")
        self._coordinator = coordinator
        # records this publisher keeps; the HEAD record always survives
        self._retain = max(1, int(retain))
        self.chunk_size = int(
            chunk_size_bytes or knobs.get_cas_chunk_size_bytes()
        )
        self._store = PublishStore(self.root)
        self._lock = threading.Lock()
        self._ns: Optional[str] = None
        self._announced = False
        # last committed record (the publish_state delta basis) and the
        # record steps THIS publisher committed, oldest first (pruning
        # candidates — a restarted publisher leaks its predecessor's
        # tail, bounded by its retention window)
        self._last_record: Optional[Dict[str, Any]] = None
        self._recent_steps: List[int] = []
        self._closed = False

    # ------------------------------------------------------- plumbing

    @property
    def namespace(self) -> Optional[str]:
        """The announce namespace (per-publisher uid); None until the
        first publication (or when announce is off / no coordinator)."""
        with self._lock:
            return self._ns

    def _announce_ns(self) -> Optional[str]:
        if not knobs.publish_announce_enabled():
            return None
        if self._coordinator is None:
            return None
        if self._ns is None:
            # root-derived so unrelated subscriber processes compute
            # the same key, and concurrent jobs on distinct roots never
            # collide in the shared KV store (kv-hygiene namespacing)
            self._ns = announce_mod.ns_for_root(self.root)
        return self._ns

    def _seed_last_record(self) -> None:
        """Adopt an existing root's HEAD as the delta basis, so a
        restarted publisher doesn't re-write every pool chunk."""
        try:
            head = self._store.read_head()
            if head is not None:
                self._last_record = self._store.read_record(
                    str(head["record"])
                )
        except Exception as e:  # noqa: BLE001 — a corrupt old root
            # degrades to a full first publication, never blocks one
            obs.swallowed_exception("publish.seed", e)

    # ----------------------------------------------------- publication

    def publish_record(self, record: Dict[str, Any]) -> str:
        """Commit one assembled record marker-last, announce it, prune
        beyond retention; returns the record path.  The durable commit
        is load-bearing and raises on failure; announce and prune are
        best-effort."""
        with obs.span(
            "publish/record", step=record["step"], root=self.root
        ):
            with self._lock:
                if self._closed:
                    raise RuntimeError("publisher is closed")
                if self._last_record is None:
                    self._seed_last_record()
                prev = self._last_record
                path = self._store.write_record(record)
                self._last_record = record
                obs.counter(obs.PUBLISH_RECORDS).inc()
                stats = record.get("stats") or {}
                obs.counter(obs.PUBLISH_BYTES_DELTA).inc(
                    int(stats.get("bytes_delta", 0))
                )
                obs.counter(obs.PUBLISH_CHUNKS_DELTA).inc(
                    int(stats.get("chunks_delta", 0))
                )
                # deterministic chaos hook: a publisher dying HERE —
                # record durable, announce never sent — must leave
                # subscribers converging via the durable-poll fallback
                failpoint("publish.announce", step=record["step"])
                ns = self._announce_ns()
                if ns is not None:
                    announce_mod.announce(
                        self._coordinator, ns, record["step"], path
                    )
                self._prune(record, prev)
                return path

    def publish_continuous(
        self, durable_store_root: str, step: int
    ) -> str:
        """Publish a confirmed durable promotion of the continuous
        loop: pure reference, no data movement (see module docstring)."""
        with obs.span(
            "publish/from_continuous",
            step=step,
            source=durable_store_root,
        ):
            store = ContinuousStore(durable_store_root)
            try:
                man = store.read_step_manifest(step_manifest_path(step))
            finally:
                store.sync_close()
            leaves: Dict[str, Any] = {}
            for path, rec in man["leaves"].items():
                refs = [
                    make_ref(k, 0, chunk_location(k))
                    for k in rec["keys"]
                ]
                leaf = {
                    k: v for k, v in rec.items() if k != "keys"
                }
                leaf["refs"] = refs
                leaves[path] = leaf
            record = build_record(
                step,
                "continuous",
                [durable_store_root.rstrip("/")],
                leaves,
                stats=self._delta_stats(
                    leaves, [durable_store_root.rstrip("/")]
                ),
            )
            return self.publish_record(record)

    def publish_snapshot(
        self,
        path: str,
        step: int,
        metadata: Any = None,
    ) -> str:
        """Publish a committed snapshot (see module docstring for what
        each manifest entry family becomes)."""
        with obs.span("publish/from_snapshot", step=step, source=path):
            if metadata is None:
                from ..snapshot import Snapshot

                metadata = Snapshot(path).metadata
            from ..manifest import PrimitiveEntry, is_container_entry
            from ..manifest_ops import get_manifest_for_rank

            snap_root = path.rstrip("/")
            bases: List[str] = [snap_root]
            cas_doc = getattr(metadata, "cas", None) or {}
            cas_tables: Dict[str, Any] = dict(cas_doc.get("chunks") or {})
            cas_base_idx: Optional[int] = None
            if cas_tables:
                bases.append(
                    resolve_root(snap_root, str(cas_doc.get("root")))
                )
                cas_base_idx = 1
            objects: Dict[str, Any] = getattr(metadata, "objects", {}) or {}
            codecs: Dict[str, Any] = getattr(metadata, "codecs", {}) or {}
            leaves: Dict[str, Any] = {}
            skipped = 0
            # the rank-0 LOGICAL view: paths here match what a
            # subscriber's flatten() of the same app_state produces
            # (manifest keys proper are "<rank>/<logical path>")
            for lpath, entry in get_manifest_for_rank(metadata, 0).items():
                if is_container_entry(entry):
                    continue  # structure, not a leaf
                if isinstance(entry, PrimitiveEntry):
                    # inlined in the record like in the metadata —
                    # zero refs, applied straight from the doc
                    leaves[lpath] = {
                        "kind": "prim",
                        "ptype": entry.type,
                        "v": entry.readable,
                        "size": 0,
                        "refs": [],
                    }
                    continue
                leaf = _snapshot_leaf(
                    entry, cas_tables, cas_base_idx, objects, codecs
                )
                if leaf is None:
                    skipped += 1
                    continue
                leaves[lpath] = leaf
            if skipped:
                obs.counter(obs.PUBLISH_LEAVES_SKIPPED).inc(skipped)
                logger.warning(
                    "publication of %s skipped %d leaves (codec-framed "
                    "or sharded entries have no raw-byte refs)",
                    path, skipped,
                )
            record = build_record(
                step,
                "snapshot",
                bases,
                leaves,
                stats=self._delta_stats(leaves, bases),
            )
            return self.publish_record(record)

    def publish_state(
        self, app_state: Dict[str, Any], step: int
    ) -> str:
        """Self-contained publication of the live state into this
        root's own chunk pool (see module docstring)."""
        with obs.span("publish/from_state", step=step, root=self.root):
            with self._lock:
                if self._last_record is None:
                    self._seed_last_record()
                prev = self._last_record
            state_tree = {
                k: (v.state_dict() if hasattr(v, "state_dict") else v)
                for k, v in app_state.items()
            }
            _manifest, flattened = flatten(state_tree)
            prev_keys: Set[str] = _record_keys(prev)
            leaves: Dict[str, Any] = {}
            new_chunks: List[Tuple[str, bytes]] = []
            staged_keys: Set[str] = set()
            for lpath in sorted(flattened):
                rec, view = encode_leaf(flattened[lpath])
                refs = []
                for lo, hi in plan_parts(view.nbytes, self.chunk_size):
                    piece = view[lo:hi]
                    key = chunk_key(
                        (
                            crc32_fast(piece),
                            adler32_fast(piece),
                            piece.nbytes,
                        )
                    )
                    refs.append(make_ref(key, 0, chunk_location(key)))
                    if key not in prev_keys and key not in staged_keys:
                        staged_keys.add(key)
                        new_chunks.append(
                            (chunk_location(key), bytes(piece))
                        )
                rec["refs"] = refs
                leaves[lpath] = rec
            self._write_pool_chunks(new_chunks)
            record = build_record(
                step,
                "state",
                [self.root],
                leaves,
                stats=self._delta_stats(leaves, [self.root]),
            )
            return self.publish_record(record)

    # -------------------------------------------------------- internals

    def _write_pool_chunks(
        self, new_chunks: List[Tuple[str, bytes]]
    ) -> None:
        if not new_chunks:
            return
        from .. import scheduler

        scheduler.sync_execute_buffer_writes(
            new_chunks,
            self._store.storage,
            scheduler.get_process_memory_budget_bytes(),
            obs.BYTES_WRITTEN,
            span_label="publish/pool_write",
        )

    def _delta_stats(
        self, leaves: Dict[str, Any], bases: List[str]
    ) -> Dict[str, int]:
        """Record stats: this record's wire cost for a subscriber that
        holds the PREVIOUS record (the steady-state update size)."""
        from .delta import plan_delta

        probe = {"bases": bases, "leaves": leaves, "step": -1}
        with self._lock:
            prev = self._last_record
        prev_probe = None
        if prev is not None:
            prev_probe = {
                "bases": prev["bases"],
                "leaves": prev["leaves"],
                "step": -1,
            }
        plan = plan_delta(probe, prev_probe)
        return {
            "bytes_delta": plan.stats["bytes_fetch"],
            "bytes_total": plan.stats["bytes_total"],
            "chunks_delta": plan.stats["chunks_fetch"],
            "chunks_total": plan.stats["chunks_total"],
        }

    def _prune(
        self,
        record: Dict[str, Any],
        prev: Optional[Dict[str, Any]],
    ) -> None:
        """Drop records beyond the retention window (this publisher's
        own commits, oldest first) and, for OWN-pool publications, the
        chunks the superseded basis referenced that the new record no
        longer does.  Chunk pruning at depth 1 keeps pool GC trivially
        safe for subscribers holding the PREVIOUS record (the only ones
        a delta applies against); deeper laggards re-enter via a full
        fetch of the current record, whose chunks are never pruned.
        Best-effort throughout: a failed delete leaks bytes, never a
        publication."""
        try:
            self._recent_steps.append(int(record["step"]))
            while len(self._recent_steps) > self._retain:
                self._store.delete_quiet(
                    record_path(self._recent_steps.pop(0))
                )
            if (
                prev is not None
                and record.get("source") == "state"
                and prev.get("source") == "state"
            ):
                stale = _record_keys(prev) - _record_keys(record)
                for key in sorted(stale):
                    self._store.delete_quiet(chunk_location(key))
        except Exception as e:  # noqa: BLE001 — retention is advisory
            obs.swallowed_exception("publish.prune", e)

    def close(self) -> None:
        """Clean shutdown: clear the announce key (publish-paired
        cleanup) and release storage."""
        with obs.span("publish/close", root=self.root):
            with self._lock:
                if self._closed:
                    return
                self._closed = True
                if self._ns is not None and self._coordinator is not None:
                    try:
                        announce_mod.clear(self._coordinator, self._ns)
                    except Exception as e:  # noqa: BLE001 — best-effort
                        obs.swallowed_exception("publish.close", e)
                self._store.sync_close()


def _record_keys(record: Optional[Dict[str, Any]]) -> Set[str]:
    if record is None:
        return set()
    return {
        ref["k"]
        for leaf in record["leaves"].values()
        for ref in leaf["refs"]
        if ref.get("k") is not None
    }


def _snapshot_leaf(
    entry: Any,
    cas_tables: Dict[str, Any],
    cas_base_idx: Optional[int],
    objects: Dict[str, Any],
    codecs: Dict[str, Any],
) -> Optional[Dict[str, Any]]:
    """One manifest entry → a publication leaf doc, or None when the
    entry has no raw-byte representation (codec-framed, sharded)."""
    kind = type(entry).__name__
    if kind == "ObjectEntry":
        pieces = [(entry.location, getattr(entry, "byte_range", None))]
        meta = {
            "kind": "object",
            "tag": getattr(entry, "serializer", "object"),
        }
    elif kind == "ArrayEntry":
        pieces = [(entry.location, getattr(entry, "byte_range", None))]
        meta = {
            "kind": "array",
            "dtype": str(entry.dtype),
            "shape": [int(d) for d in entry.shape],
        }
    elif kind == "ChunkedArrayEntry":
        pieces = [
            (c.location, getattr(c, "byte_range", None))
            for c in entry.chunks
        ]
        meta = {
            "kind": "array",
            "dtype": str(entry.dtype),
            "shape": [int(d) for d in entry.shape],
        }
    else:
        return None  # sharded (per-rank boxes) — not hot-swappable
    refs: List[Dict[str, Any]] = []
    for loc, byte_range in pieces:
        if loc in codecs:
            return None  # framed bytes are not the leaf's raw bytes
        table = cas_tables.get(loc)
        if table is not None and byte_range is None:
            assert cas_base_idx is not None
            refs.extend(
                make_ref(k, cas_base_idx, chunk_location(k))
                for k in table["keys"]
            )
            continue
        digest = objects.get(loc)
        if digest is not None and byte_range is None:
            key = chunk_key(
                (int(digest[0]), int(digest[1]), int(digest[2]))
            )
            refs.append(make_ref(key, 0, loc))
            continue
        if byte_range is None:
            return None  # no digest, no extent: length unknowable here
        lo, hi = int(byte_range[0]), int(byte_range[1])
        refs.append(
            make_ref(None, 0, loc, byte_range=[lo, hi], nbytes=hi - lo)
        )
    size = sum(int(r["n"]) for r in refs)
    meta["size"] = size
    meta["refs"] = refs
    return meta
