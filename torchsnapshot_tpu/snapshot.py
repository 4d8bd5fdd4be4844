"""The user-facing Snapshot API: take / async_take / restore / read_object.

TPU-native rebuild of the reference's top layer (torchsnapshot/
snapshot.py:112-1072).  The orchestration mirrors the reference call stacks
(SURVEY §3) with JAX-native replacements:

- control plane (path coalescing, key gathers, manifests) goes through a
  ``Coordinator`` — the jax.distributed KV service, not NCCL collectives,
- device→host staging is XLA async transfer inside the budgeted scheduler,
- the commit point is identical: ``.snapshot_metadata`` written by rank 0
  only after every rank finished its writes (reference snapshot.py:202-209)
  — a snapshot without it is by definition incomplete (snapshot.py:849-854),
- ``async_take`` returns as soon as the pending buffers are independent of
  training state: one batched device→pinned_host transfer plus eager
  defensive copies of mutable host arrays (host_offload.
  eager_offload_write_reqs) — *before* staging, not after it like the
  reference (its CUDA tensors are mutable; jax.Arrays are not).  Staging
  and storage I/O drain on the scheduler's loop thread and a background
  thread runs the commit barrier purely over KV — no collectives, so it
  can never race with training's ICI traffic (the reference's constraint
  at snapshot.py:1010 holds by construction).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import json
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import knobs, obs
from .batcher import (
    BatchedBufferStager, batch_read_requests, batch_write_requests,
)
from .coordination import Coordinator, get_default_coordinator
from .event import Event
from .event_handlers import log_event
from .flatten import flatten, inflate
from .io_types import Future, ReadReq, WriteIO, WriteReq
from .manifest import (
    MANIFEST_VERSION,
    ChunkedArrayEntry,
    Entry,
    Manifest,
    PrimitiveEntry,
    ShardedArrayEntry,
    SnapshotMetadata,
    entry_from_dict,
    is_container_entry,
)
from .manifest_ops import consolidate_manifests, get_manifest_for_rank
from .partitioner import elect_takeover_writers, partition_replicated_writes
from .preparers import (
    estimate_write_bytes,
    path_is_replicated,
    prepare_read,
    prepare_write,
)
from .preparers.sharded import is_multi_device_jax_array
from .resilience import SnapshotAbortedError
from .resilience.liveness import (
    DegradedSnapshotError,
    LivenessSession,
    RankDeadError,
)
from .serialization import serialize_object
from .scheduler import (
    PendingIOWork,
    get_process_memory_budget_bytes,
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)
from .stateful import (
    Replicated,
    RNGState,
    Stateful,
    load_with_strict,
    unwrap,
)
from .storage import url_to_storage_plugin
from . import topology as topology_mod
from . import transport as transport_mod

logger = logging.getLogger(__name__)

def _storage_for(path: str, options: Optional[Dict[str, Any]]):
    """Build the storage plugin, passing storage_options only when set —
    tests and third parties monkeypatch ``url_to_storage_plugin`` with
    single-argument factories, which must keep working."""
    if options:
        return url_to_storage_plugin(path, options)
    return url_to_storage_plugin(path)


SNAPSHOT_METADATA_FNAME = ".snapshot_metadata"
AppState = Dict[str, Stateful]


def _read_priority_for(lpath: str, priority_globs: Sequence[str]) -> int:
    """Read-ordering class for a logical path under a ``priority`` glob
    list (restore/materialize): the index of the FIRST matching glob —
    lower executes earlier — with unmatched leaves after every named
    class.  Same fnmatch dialect as the ``paths`` filter."""
    import fnmatch

    for i, g in enumerate(priority_globs):
        if fnmatch.fnmatch(lpath, g):
            return i
    return len(priority_globs)


def _replication_fingerprint(obj: Any, mode: str = "full") -> Tuple:
    """Per-leaf fingerprint used to verify that state claimed replicated
    actually matches across ranks (reference intersects the per-rank
    *path* sets only, snapshot.py:637-670; this additionally fingerprints
    content, the failure mode most prone to silent divergence — e.g.
    per-rank optimizer scalars).

    ``mode`` (knob ``TORCHSNAPSHOT_TPU_REPLICATION_VERIFY``): "full" CRCs
    array content; "shape" checks arrays by dtype+shape only (O(1) per
    array — the knob exists for giant replicated host arrays; small
    non-array leaves keep their content check in every mode, since
    per-rank scalar drift is exactly what verification is for).  "off"
    is handled by the caller (no fingerprinting at all).

    - numpy / torch-CPU arrays: dtype, shape + crc32 of the FULL buffer
      (zlib.crc32 runs at ~3 GB/s; host replicated state is typically
      small — large state is jax arrays). A sampled check would miss
      divergence between windows, which is exactly the silent corruption
      this exists to prevent. Non-contiguous arrays are CRC'd in row
      blocks so the copy stays bounded.
    - jax arrays: dtype + shape only — content verification would force a
      device sync on the save path, and replication of jax arrays is
      already explicit in their sharding;
    - primitives: small values verbatim; floats by bit pattern (NaN would
      never compare equal to itself); long str/bytes by length + crc32 so
      multi-MB blobs never ride the coordination KV;
    - anything else: crc32 of its serialized form (content-verified, not
      just the type name).
    """
    import struct
    import zlib

    import numpy as np

    from .preparers.array import _is_jax_array, _is_torch_tensor, _to_host_view

    if isinstance(obj, float):
        return ("prim_f", struct.pack("<d", obj))
    if isinstance(obj, (str, bytes)):
        raw = obj.encode("utf-8", "surrogatepass") if isinstance(obj, str) else obj
        if len(raw) > 4096:
            return ("prim_big", type(obj).__name__, len(raw), zlib.crc32(raw))
        return ("prim", type(obj).__name__, obj)
    if isinstance(obj, (int, bool, type(None))):
        # concrete type in the tag: True == 1 but bool-vs-int divergence
        # across ranks must still demote
        return ("prim", type(obj).__name__, obj)
    if _is_jax_array(obj):
        return ("jax", str(obj.dtype), tuple(obj.shape))
    if isinstance(obj, np.ndarray) or _is_torch_tensor(obj):
        if mode == "shape":
            return ("arr", str(obj.dtype), tuple(obj.shape))
        view = _to_host_view(obj)
        if view.flags["C_CONTIGUOUS"]:
            crc = zlib.crc32(view.reshape(-1).view(np.uint8))
        elif view.ndim >= 1 and view.shape[0] > 1:
            crc = 0
            rows_per = max(1, (16 << 20) // max(1, view[:1].nbytes))
            for i in range(0, view.shape[0], rows_per):
                block = np.ascontiguousarray(view[i : i + rows_per])
                crc = zlib.crc32(block.reshape(-1).view(np.uint8), crc)
        else:
            block = np.ascontiguousarray(view)
            crc = zlib.crc32(block.reshape(-1).view(np.uint8))
        return ("arr", str(obj.dtype), tuple(obj.shape), crc)
    try:
        payload, _ = serialize_object(obj)
        return ("obj", type(obj).__name__, len(payload), zlib.crc32(payload))
    except Exception:
        return ("obj", type(obj).__name__)


def _safe_replication_verify_mode() -> str:
    """Resolve the knob WITHOUT raising: an invalid value on one rank must
    not diverge the collective protocol mid-take — fall back to the
    strict default with a warning instead."""
    try:
        return knobs.get_replication_verify()
    except ValueError as e:
        logger.warning("%s; falling back to 'full'", e)
        return "full"


def _strictest_mode(modes: Sequence[str]) -> str:
    return (
        "full" if "full" in modes
        else ("shape" if "shape" in modes else "off")
    )


def _verify_replicated_paths(
    flattened: Dict[str, Any],
    replicated_globs: Sequence[str],
    coordinator: Coordinator,
    mode: str,
) -> set:
    """The set of logical paths that are *verifiably* replicated: matched
    by the agreed globs on every rank, with identical fingerprints.
    Mismatches are demoted to per-rank entries with a warning — a corrupt
    'replicated' save (only one rank's copy persisted) is strictly worse
    than a larger correct one."""
    if not replicated_globs:
        # nothing can match: skip the KV round-trip entirely (all ranks
        # agree on the globs by this point, so all branch identically)
        return set()
    # "off" trusts content (fingerprint None) but still intersects path
    # PRESENCE across ranks: the partitioner requires its item list
    # identical on every rank, and a path only one rank has would
    # otherwise be assigned to a rank that can't write it (silently
    # dropping it from the snapshot).
    local = {
        lpath: (
            None if mode == "off" else _replication_fingerprint(obj, mode)
        )
        for lpath, obj in flattened.items()
        if path_is_replicated(lpath, replicated_globs)
    }
    if coordinator.world_size <= 1:
        return set(local)
    gathered = coordinator.all_gather_object(local)
    missing = object()
    verified = set()
    for lpath, fp in gathered[0].items():
        if all(peer.get(lpath, missing) == fp for peer in gathered[1:]):
            verified.add(lpath)
    demoted = set(local) - verified
    if demoted:
        logger.warning(
            "rank %d: %d path(s) matched replicated globs but differ "
            "across ranks; saving per-rank instead: %s",
            coordinator.rank,
            len(demoted),
            sorted(demoted)[:10],
        )
    return verified


def _ddp_module(stateful: Any) -> Optional[Any]:
    """The torch DDP instance behind ``stateful``, if there is one
    (directly, or wrapped in a ``TorchModuleAdapter``-style adapter
    exposing ``.module``)."""
    try:
        from torch.nn.parallel import DistributedDataParallel as DDP
    except Exception:  # torch absent/broken: nothing to infer
        return None
    for cand in (stateful, getattr(stateful, "module", None)):
        if isinstance(cand, DDP):
            return cand
    return None


def _infer_replicated(
    replicated: Sequence[str], app_state: Dict[str, Any]
) -> List[str]:
    """Auto-infer replication globs from the app state (reference
    _infer_replicated, snapshot.py:896-918).

    jax.Arrays need no help — replication is explicit in their sharding
    and handled by the sharded preparer.  This covers HOST state:

    - statefuls marked ``Replicated(...)`` (or any object with a truthy
      ``replicated`` attribute) contribute ``key/**``;
    - torch DDP-wrapped modules (directly or behind an adapter with a
      ``.module``) contribute ``key/**``, honoring
      ``parameters_to_ignore`` by enumerating per-name globs instead
      when any parameter is excluded from replication.

    Inference runs per-rank BEFORE the glob intersection gather, so a
    rank that didn't wrap its module gets the glob dropped by the
    intersection; content verification then guards the rest.
    """
    globs = list(replicated)
    if "**" in globs:
        return globs
    for key, val in app_state.items():
        # class-level marker only: an INSTANCE attribute named
        # "replicated" (e.g. an nn.Module buffer surfaced via
        # __getattr__) must neither crash the truthiness test nor
        # silently claim the state replicated
        if isinstance(val, Replicated) or (
            getattr(type(val), "replicated", None) is True
        ):
            globs.append(f"{key}/**")
            continue
        ddp = _ddp_module(val)
        if ddp is None:
            continue
        ignored = set(getattr(ddp, "parameters_to_ignore", ()) or ())
        if not ignored:
            globs.append(f"{key}/**")
            continue
        # adapters strip DDP's "module." prefix from state-dict keys while
        # ``parameters_to_ignore`` holds UNPREFIXED names; the stateful's
        # own state_dict is authoritative for the names that will appear
        # as logical paths, so strip the prefix before the membership test
        for name in val.state_dict().keys():
            bare = name[7:] if name.startswith("module.") else name
            if bare not in ignored and name not in ignored:
                globs.append(f"{key}/{name}")
    return globs


def _crc_key(location: str, byte_range: Any) -> str:
    br = f"{byte_range[0]}-{byte_range[1]}" if byte_range else ""
    return f"{location}|{br}"


def _collect_local_crcs(local_entries: Dict[str, Entry]) -> Dict[str, int]:
    """(location|byte_range) → crc32 for every locally-written payload
    whose checksum sink fired during staging.  Keyed by physical extent
    (rank-agnostic and unique), so merging needs no knowledge of how
    consolidation re-keyed the logical paths."""
    out: Dict[str, int] = {}
    for e in local_entries.values():
        crc = getattr(e, "crc32", None)
        loc = getattr(e, "location", None)
        if crc is not None and isinstance(loc, str):
            out[_crc_key(loc, getattr(e, "byte_range", None))] = crc
        for attr in ("shards", "chunks"):
            for s in getattr(e, attr, None) or ():
                if s.crc32 is not None:
                    out[_crc_key(s.location, s.byte_range)] = s.crc32
    return out


def _merge_crcs(
    manifest: Dict[str, Entry], crc_maps: Sequence[Dict[str, int]]
) -> None:
    """Stamp gathered content checksums onto the manifest in place (the
    manifest was serialized across ranks BEFORE staging computed them)."""
    merged: Dict[str, int] = {}
    for m in crc_maps:
        merged.update(m or {})
    if not merged:
        return
    for e in manifest.values():
        loc = getattr(e, "location", None)
        if isinstance(loc, str) and hasattr(e, "crc32"):
            crc = merged.get(_crc_key(loc, getattr(e, "byte_range", None)))
            if crc is not None:
                e.crc32 = crc
        for attr in ("shards", "chunks"):
            for s in getattr(e, attr, None) or ():
                crc = merged.get(_crc_key(s.location, s.byte_range))
                if crc is not None:
                    s.crc32 = crc


def _crc_payload(
    local_entries: Dict[str, Entry],
    object_crcs: Dict[str, int],
    object_codecs: Optional[Dict[str, Any]] = None,
    object_cas: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """One rank's post-staging checksum contribution: per-payload entry
    crcs + whole-object crcs (the incremental-dedup table) + codec frame
    tables for objects this rank stored compressed (codec.py) + chunk
    tables for objects this rank routed through the chunk store
    (cas/)."""
    out = {
        "entries": _collect_local_crcs(local_entries),
        "objects": dict(object_crcs),
    }
    if object_codecs:
        out["codecs"] = dict(object_codecs)
    if object_cas:
        out["cas"] = dict(object_cas)
    return out


def _merge_crc_payloads(
    metadata: SnapshotMetadata, payloads: Sequence[Dict[str, Any]]
) -> None:
    _merge_crcs(
        metadata.manifest, [p.get("entries") or {} for p in payloads]
    )
    for p in payloads:
        metadata.objects.update(p.get("objects") or {})
        metadata.codecs.update(p.get("codecs") or {})
        if p.get("cas"):
            # the root/chunk_size envelope was rank-agreed at planning
            # time (set in _take_impl_inner); only the per-rank chunk
            # tables merge here
            metadata.cas.setdefault("chunks", {}).update(p["cas"])


_STRIPE_EVENT_COUNTERS = (
    obs.STRIPE_WRITES,
    obs.STRIPE_READS,
    obs.STRIPE_PARTS_WRITTEN,
    obs.STRIPE_PARTS_READ,
    obs.STRIPE_BYTES_WRITTEN,
    obs.STRIPE_BYTES_READ,
    obs.STRIPE_ABORTS,
    # codec layer (codec.py): raw bytes in vs stored bytes out is the
    # operation's achieved compression ratio; parts_raw_fallback says
    # how much of the payload was incompressible
    obs.CODEC_BYTES_IN,
    obs.CODEC_BYTES_OUT,
    obs.CODEC_PARTS_ENCODED,
    obs.CODEC_PARTS_RAW_FALLBACK,
    obs.CODEC_PARTS_DECODED,
)


def _stripe_event_stamp():
    """Capture the stripe + codec counters now; the returned stamp
    writes the DELTAS into a take/restore event's metadata — how much of
    the operation's I/O moved through striped paths (and whether any
    multipart write had to abort), plus what the codec layer did to the
    byte volume, lands next to duration_s in the event stream, where a
    throughput incident review will look first."""
    before = {n: obs.counter(n).value for n in _STRIPE_EVENT_COUNTERS}

    def stamp(event: "Event") -> None:
        for n in _STRIPE_EVENT_COUNTERS:
            delta = obs.counter(n).value - before[n]
            if delta:
                event.metadata[n] = delta

    return stamp


def _normalize_cas_config(cas: Any, path: str) -> Optional[Dict[str, Any]]:
    """Resolve a take's ``cas`` argument to ``{"root", "chunk_size"}``
    (or None = off).  ``True`` places the pool next to the snapshot
    (``<parent>/cas`` — the manager layout); a string names the root
    URL; a dict may override ``chunk_size_bytes``."""
    if not cas:
        return None
    cfg: Dict[str, Any] = {}
    if isinstance(cas, str):
        cfg["root"] = cas
    elif isinstance(cas, dict):
        cfg.update(cas)
    if not cfg.get("root"):
        snap = path.rstrip("/")
        parent = snap.rsplit("/", 1)[0] if "/" in snap else ""
        if not parent:
            raise ValueError(
                f"cas=True needs a parent directory to place the pool "
                f"next to {path!r}; pass an explicit root instead"
            )
        cfg["root"] = f"{parent}/cas"
    cfg["chunk_size"] = int(
        cfg.pop("chunk_size_bytes", None)
        or cfg.get("chunk_size")
        or knobs.get_cas_chunk_size_bytes()
    )
    return {"root": cfg["root"].rstrip("/"), "chunk_size": cfg["chunk_size"]}


def _cas_commit_refs(
    metadata: SnapshotMetadata, path: str, store: Any = None
) -> None:
    """Register this take's chunk references in the shared index —
    strictly BEFORE the ``.snapshot_metadata`` marker, on the same
    (rank 0) code path, so a committed step's chunks can never be
    unprotected.  A failure here fails the commit (a marker whose
    chunks GC could reap would be a corrupt-by-construction snapshot)."""
    from . import cas as cas_mod

    tables = (metadata.cas or {}).get("chunks") or {}
    if not tables:
        return
    owned = store is None
    if owned:
        root = cas_mod.resolve_root(path, metadata.cas["root"])
        store = cas_mod.ChunkStore(root)
    try:
        cas_mod.commit_refs(store, path, tables)
    finally:
        if owned:
            store.sync_close()


# ------------------------------------------------------------- takeover
# Surviving rank death mid-commit (docs/resilience.md, "surviving rank
# death").  The liveness layer (resilience/liveness.py) turns a
# SIGKILLed/hung peer into a typed RankDeadError at the commit path's
# death-aware waits; the machinery below then finishes the commit
# WITHOUT the dead rank: survivors re-write its replicated objects from
# their own copies (every rank planned write reqs for every replicated
# object and normally discards the non-elected ones), and sharded state
# only the dead rank held is recorded in the metadata's ``degraded``
# section instead of failing the take.

_RECOVERY_POLL_S = 0.1
# recovery's own wait bound — generous, because survivors may be
# re-staging and re-writing the dead rank's replicated objects while
# their peers wait on the takeover keys
_RECOVERY_TIMEOUT_S = 600.0


@dataclasses.dataclass
class _TakeoverContext:
    """Planning-time facts the commit path keeps so survivors can finish
    a take after a peer dies mid-commit.  Every field is either
    rank-agreed (topo/preloads/assignment/repl_items/gathered_manifests
    — pure functions of gathered inputs) or rank-local write material
    (repl_reqs/repl_chunk_reqs: the un-elected write reqs this rank
    planned and would normally discard; exactly what a takeover writer
    replays).  ``repl_entries`` are the UNBATCHED entry objects captured
    before non-writers drop theirs and before batching re-points the
    writer's at rank-local slabs — their ``replicated/`` locations are
    rank-independent, so a survivor's re-write lands where the manifest
    fix-up says it does."""

    topo: Any
    preloads: List[int]
    assignment: Dict[str, int]
    repl_reqs: Dict[str, List[WriteReq]]
    repl_chunk_reqs: Dict[str, WriteReq]
    chunk_parent: Dict[str, str]
    repl_items: List[Tuple[str, int]]
    repl_entries: Dict[str, Entry]
    gathered_manifests: List[Dict[str, Any]]


def _recovery_kv_get(
    coordinator: Coordinator,
    monitor: Any,
    key: str,
    expected_dead: set,
    timeout_s: float = _RECOVERY_TIMEOUT_S,
) -> str:
    """A KV wait for the recovery protocol itself: the ranks in
    ``expected_dead`` STAY dead (the liveness monitor keeps reporting
    them), so only NEW deaths raise — a scoped ``kv_get`` would re-raise
    on the known-dead set forever."""
    deadline = time.monotonic() + timeout_s
    while True:
        value = coordinator.kv_try_get(key)
        if value is not None:
            return value
        newly = [r for r in monitor.dead_ranks() if r not in expected_dead]
        if newly:
            raise RankDeadError(
                newly[0],
                set(newly) | set(expected_dead),
                ns=getattr(monitor, "ns", ""),
            )
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"takeover recovery timed out after {timeout_s}s "
                f"waiting for {key!r}"
            )
        time.sleep(_RECOVERY_POLL_S)


def _recover_commit_after_death(
    *,
    coordinator: Coordinator,
    commit_uid: str,
    path: str,
    metadata: SnapshotMetadata,
    storage: Any,
    local_entries: Dict[str, Entry],
    object_crcs: Dict[str, Any],
    object_codecs: Dict[str, Any],
    object_cas: Dict[str, Any],
    cas_store: Any,
    ctx: _TakeoverContext,
    monitor: Any,
    dead_err: RankDeadError,
    already_committed: bool = False,
) -> SnapshotMetadata:
    """Finish a take's commit after ``dead_err`` declared peer rank(s)
    dead.  Runs OUTSIDE the abort/liveness scopes (they would re-raise
    on the known-dead set); all cross-rank traffic is explicit-key KV —
    no collectives, no uid minting — so survivors' op counters stay
    aligned for whatever runs next.

    Protocol: (1) agree on the dead set via a leader-published plan,
    (2) deterministically re-elect writers for the dead ranks' orphaned
    replicated objects (``elect_takeover_writers`` — pure, so no extra
    agreement round), (3) takeover writers replay their kept
    un-elected write reqs, (4) every survivor applies the same manifest
    fix-up and computes the same degraded set, (5) checksums re-exchange
    under takeover keys, (6) the leader writes the metadata marker
    (with a ``degraded`` section when sharded state died with its only
    holder) and signals commit.

    ``already_committed``: rank 0 had already written the marker when
    death surfaced (a peer died between the two commit barriers) — the
    snapshot is complete; the leader skips the rewrite and just drives
    the protocol so survivors converge.
    """
    rank, world = coordinator.rank, coordinator.world_size
    my_dead = set(dead_err.dead_ranks or [dead_err.rank])
    logger.warning(
        "rank %d: peer rank(s) %s declared dead during commit %s; "
        "entering write takeover", rank, sorted(my_dead), commit_uid,
    )

    # --- agree on the dead set -----------------------------------------
    # Survivors can observe death at different times (or observe
    # different sets).  The lowest live rank in MY view is my leader
    # candidate; it publishes an authoritative plan under a
    # LEADER-SUFFIXED key.  If the candidate itself turns out dead while
    # we wait, fold the new deaths in and re-elect — the dead set
    # strictly grows, so at most ``world`` rounds.
    plan_dead: Optional[List[int]] = None
    for _ in range(world):
        live = [r for r in range(world) if r not in my_dead]
        if not live:
            raise RuntimeError(
                f"takeover for {commit_uid}: every rank is in the dead "
                f"set {sorted(my_dead)}"
            )
        candidate = live[0]
        plan_key = f"{commit_uid}/takeover/plan/{candidate}"
        if candidate == rank:
            coordinator.kv_set(plan_key, json.dumps(sorted(my_dead)))
            plan_dead = sorted(my_dead)
            break
        try:
            plan_dead = json.loads(
                _recovery_kv_get(coordinator, monitor, plan_key, my_dead)
            )
            break
        except RankDeadError as e:
            my_dead |= set(e.dead_ranks or [e.rank])
    if plan_dead is None:
        raise RuntimeError(
            f"takeover for {commit_uid}: no live leader converged"
        )
    dead = set(plan_dead)
    if rank in dead:
        # the fleet declared US dead (our heartbeats stalled past the
        # timeout) and has moved on; our writes may have been taken
        # over — refuse to race the survivors
        raise RuntimeError(
            f"rank {rank} was declared dead by the takeover plan for "
            f"{commit_uid}; aborting locally"
        )
    live = [r for r in range(world) if r not in dead]
    leader = live[0]

    # --- re-elect writers for the orphaned replicated objects ----------
    # Pure + deterministic (same dead set in → same election out), so
    # every survivor computes who writes what with zero extra traffic.
    orphans: List[Tuple[str, int]] = []
    origin_of: Dict[str, int] = {}
    for k, nbytes in ctx.repl_items:
        w = ctx.assignment.get(k)
        if w in dead:
            orphans.append((k, nbytes))
            origin_of[k] = w
    takeover: Dict[str, int] = {}
    if orphans:
        takeover = elect_takeover_writers(
            orphans, sorted(dead), world,
            preloads=ctx.preloads, topology=ctx.topo, origin_of=origin_of,
        )

    # --- replay my taken-over write reqs -------------------------------
    mine = sorted(k for k, w in takeover.items() if w == rank)
    taken_paths: set = set()
    for k in mine:
        taken_paths.add(ctx.chunk_parent.get(k, k))
    if mine and not already_committed:
        reqs: List[WriteReq] = []
        for k in mine:
            if k in ctx.repl_reqs:
                reqs.extend(ctx.repl_reqs[k])
            else:
                reqs.append(ctx.repl_chunk_reqs[k])
        cost_of = dict(ctx.repl_items)
        my_bytes = sum(cost_of.get(k, 0) for k in mine)
        # digest/codec sinks were only attached to the originally-elected
        # writer's reqs; the replayed ones need their own so the objects
        # table and codec frame tables cover the re-written copies.
        # (No ``wr.cas``: taken-over payloads are written plain at their
        # locations even under a cas take — a location absent from the
        # chunk tables reads through the plain path.)
        cksum = knobs.write_checksums_enabled()
        for wr in reqs:
            def _codec_sink(table: dict, wr=wr) -> None:
                object_codecs[wr.path] = table

            wr.codec_sink = _codec_sink
            if cksum:
                def _object_sink(digest: List[int], wr=wr) -> None:
                    wr.object_digest = tuple(digest)
                    object_crcs[wr.path] = list(digest)

                wr.digest_sink = _object_sink
        logger.warning(
            "rank %d: taking over %d replicated write unit(s) "
            "(%d bytes) from dead rank(s) %s",
            rank, len(mine), my_bytes, sorted(dead),
        )
        sync_execute_write_reqs(
            reqs, storage, get_process_memory_budget_bytes(), rank,
        ).sync_complete()
        obs.counter(obs.TAKEOVER_OBJECTS).inc(len(mine))
        obs.counter(obs.TAKEOVER_BYTES).inc(my_bytes)

    # --- manifest fix-up + degraded set (identical on every survivor) --
    degraded: Dict[str, Dict[str, Any]] = {}
    if not already_committed:
        for k in sorted(takeover):
            w = takeover[k]
            lp = ctx.chunk_parent.get(k, k)
            # consolidation kept each replicated entry under ONE rank; if
            # that carrier died, re-home the UNBATCHED entry under the new
            # writer (the dead carrier's copy may point at a slab it never
            # finished).  A live carrier (e.g. a surviving chunk-writer of
            # a split entry) keeps carrying it — only dead keys move.
            removed = False
            for d in sorted(dead):
                if metadata.manifest.pop(f"{d}/{lp}", None) is not None:
                    removed = True
            carried = any(f"{r}/{lp}" in metadata.manifest for r in live)
            if removed or not carried:
                entry = ctx.repl_entries.get(lp)
                if entry is not None:
                    metadata.manifest.setdefault(f"{w}/{lp}", entry)
        # state only the dead rank held: everything in its gathered
        # manifest except containers, in-manifest primitives and the
        # replicated paths just taken over.  Conservative — payloads the
        # dead rank DID land before dying are still marked (we cannot
        # know), and verify/repair heal the marker afterwards.  The dead
        # rank's manifest keys stay: repair and partial restores need
        # the shapes and locations.
        taken_over_lps = {ctx.chunk_parent.get(k, k) for k in takeover}
        for d in sorted(dead):
            per_rank = (
                ctx.gathered_manifests[d]
                if d < len(ctx.gathered_manifests)
                else {}
            )
            for lp, ed in per_rank.items():
                if lp in taken_over_lps:
                    continue
                try:
                    entry = entry_from_dict(ed)
                except Exception:  # noqa: BLE001
                    continue
                if is_container_entry(entry) or isinstance(
                    entry, PrimitiveEntry
                ):
                    continue
                degraded.setdefault(
                    lp,
                    {
                        "origin_rank": d,
                        "kind": getattr(entry, "type", "?"),
                    },
                )

    # --- checksum re-exchange among survivors --------------------------
    # The normal all_gather would block on the dead rank; explicit
    # takeover keys carry the same _crc_payload JSON instead.  Taken-over
    # entries ride each writer's payload (their staging sinks fired on
    # the captured unbatched entry objects during the replay above).
    aug_entries = dict(local_entries)
    for lp in taken_paths:
        e = ctx.repl_entries.get(lp)
        if e is not None:
            aug_entries[lp] = e
    payload = _crc_payload(
        aug_entries, object_crcs, object_codecs, object_cas
    )
    coordinator.kv_set(
        f"{commit_uid}/takeover/crcs/{rank}", json.dumps(payload)
    )
    payloads: List[Dict[str, Any]] = []
    for r in live:
        if r == rank:
            payloads.append(payload)
            continue
        # fast path first: a peer that published before us costs one
        # try-get instead of entering the death-aware poll loop
        raw = coordinator.kv_try_get(f"{commit_uid}/takeover/crcs/{r}")
        if raw is None:
            raw = _recovery_kv_get(
                coordinator, monitor,
                f"{commit_uid}/takeover/crcs/{r}", dead,
            )
        payloads.append(json.loads(raw))
    if not already_committed:
        _merge_crc_payloads(metadata, payloads)
        if degraded:
            metadata.degraded = dict(degraded)

    # --- leader commits and signals ------------------------------------
    commit_key = f"{commit_uid}/takeover/commit/{leader}"
    if rank == leader:
        try:
            if not already_committed:
                # same invariants as the clean path: never commit a
                # poisoned take, chunk refs strictly before the marker
                coordinator.raise_if_poisoned(commit_uid)
                _cas_commit_refs(metadata, path, cas_store)
                if degraded:
                    obs.counter(obs.TAKEOVER_DEGRADED_COMMITS).inc()
                storage.sync_write(
                    WriteIO(
                        path=SNAPSHOT_METADATA_FNAME,
                        buf=metadata.to_yaml().encode(),
                        durable=True,
                    )
                )
            coordinator.kv_set(commit_key, "ok")
        except BaseException as e:
            try:
                coordinator.kv_set(commit_key, f"failed: {e!r}")
            except Exception as signal_exc:  # noqa: BLE001
                # best-effort failure signal: survivors time out on the
                # commit key instead if the KV store is down too
                obs.swallowed_exception(
                    "takeover.commit_failure_signal", signal_exc
                )
            raise
    else:
        status = _recovery_kv_get(coordinator, monitor, commit_key, dead)
        if status != "ok":
            raise RuntimeError(
                f"takeover leader rank {leader} failed to commit "
                f"{path!r}: {status}"
            )
    logger.warning(
        "rank %d: takeover commit for %r done — %s (%d write unit(s) "
        "re-written fleet-wide, %d degraded path(s))",
        rank, path, "DEGRADED" if degraded else "complete",
        len(takeover), len(degraded),
    )
    return metadata


def _validate_app_state(app_state: Dict[str, Any]) -> None:
    # reference snapshot.py:672-690
    for key, value in app_state.items():
        if not (hasattr(value, "state_dict") and hasattr(value, "load_state_dict")):
            raise TypeError(
                f"app_state[{key!r}] (type {type(value)}) does not implement "
                "the Stateful protocol (state_dict/load_state_dict); wrap "
                "plain values in StateDict or pytrees in PyTreeState"
            )


class Snapshot:
    def __init__(
        self,
        path: str,
        coordinator: Optional[Coordinator] = None,
        storage_options: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.path = path
        self._coordinator = coordinator or get_default_coordinator()
        self._metadata_cache: Optional[SnapshotMetadata] = None
        # forwarded to the storage plugin constructor on every access
        # (reference storage_options, snapshot.py:118)
        self._storage_options = storage_options

    # ------------------------------------------------------------------ take

    @classmethod
    def take(
        cls,
        path: str,
        app_state: AppState,
        replicated: Sequence[str] = (),
        coordinator: Optional[Coordinator] = None,
        base: Optional[str] = None,
        leaf_transform: Optional[Callable[[str, Any], Any]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        cas: Optional[Any] = None,
    ) -> "Snapshot":
        """Synchronous distributed save (reference Snapshot.take,
        snapshot.py:112-228).

        ``leaf_transform(logical_path, leaf) -> leaf``: applied to every
        flattened leaf before planning — cast to lower precision for the
        checkpoint, quantize, redact, etc.  It must RETURN a leaf for
        every path (dropping is not supported — the container structure
        is already fixed; restore a subset with ``restore(paths=...)``
        instead).  The analogue of the reference's
        ``_custom_tensor_prepare_func`` (snapshot.py:120-122), applied
        uniformly to all leaves, not just tensors.  Must be deterministic
        and rank-agreed (the transformed content is what replication
        verification fingerprints).

        ``base`` (beyond-parity, incremental takes): path of a previous
        committed snapshot.  Staged objects whose content checksum
        matches the base's object at the same location are hardlinked /
        server-side-copied instead of rewritten — near-free checkpoints
        of mostly-unchanged state (frozen layers, embeddings, dataloader
        state).  Requires WRITE_CHECKSUMS on both takes; each snapshot
        owns its objects, so deleting the base never corrupts this one.

        ``cas`` (chunk-level incremental takes, cas/): ``True`` (pool at
        ``<parent>/cas``), a root URL, or a config dict.  Payload bytes
        go to a shared content-addressed chunk pool: any chunk an
        earlier committed step under the same pool already stored is
        skipped, the manifest records chunk references, and retention
        becomes refcounted GC (``SnapshotManager``).  Subsumes ``base``
        (chunk-level beats whole-object-vs-previous-step) and disables
        the codec layer for chunked objects (keys are raw digests).
        Requires WRITE_CHECKSUMS on every rank.
        """
        coordinator = coordinator or get_default_coordinator()
        with log_event(
            Event("take", {"path": path, "rank": coordinator.rank})
        ) as take_event:
            stamp_stripe = _stripe_event_stamp()
            # flight-record window + goodput clock both start here so
            # the persisted record describes exactly this take
            obs_before = obs.aggregate.capture()
            gp_begin = obs.goodput.take_begin(path)
            # Death-aware take (resilience/liveness.py): the heartbeat
            # PUBLISHER starts before planning — so a rank legitimately
            # slow in staging keeps stamping and is never falsely
            # declared dead — while the MONITOR is only consulted by
            # the commit-phase waits below (liveness_scope).  The uid
            # is minted here so the session can stamp under it.
            commit_uid = coordinator._next_uid("commit")
            session = LivenessSession(coordinator, commit_uid)
            session.start()
            try:
                (
                    metadata, pending_io, storage, commit_uid,
                    local_entries, object_crcs, object_codecs,
                    object_cas, cas_store, takeover_ctx,
                ) = cls._take_impl(
                    path, app_state, replicated, coordinator,
                    is_async=False, base=base,
                    leaf_transform=leaf_transform,
                    storage_options=storage_options, cas=cas,
                    commit_uid=commit_uid,
                )
            except BaseException:
                session.stop()
                raise
            # Abort-aware commit (resilience/abort.py): a rank hitting
            # an unrecoverable error here poisons the commit scope and
            # re-raises its ORIGINAL error; peers blocked in the gathers
            # and barriers below raise a typed SnapshotAbortedError
            # naming the origin rank within seconds instead of wedging
            # to the barrier timeout.  Rank 0 re-checks the poison key
            # immediately before the metadata write, so a poisoned take
            # can never produce a committed snapshot.
            #
            # Death-aware commit: the liveness scope makes every
            # barrier/kv wait below raise a typed RankDeadError when a
            # peer's stamp goes stale — a SIGKILLed rank can never
            # reach its poison call — and the handler finishes the
            # commit via write takeover instead of aborting.
            #
            # ``committed`` is mutable so the RankDeadError handler can
            # see whether rank 0 already wrote the marker (a peer dying
            # between the two commit barriers must not degrade a
            # complete snapshot).
            committed = {"done": False}
            try:
                # take/commit: the wait for storage I/O still draining
                # after take/pipeline, then the checksum gather, the
                # flight record and the metadata marker
                with coordinator.abort_scope(commit_uid), \
                        coordinator.liveness_scope(session.monitor), \
                        obs.span("take/commit", rank=coordinator.rank):
                    with obs.span("commit/drain"):
                        pending_io.sync_complete()
                    # tiered storage: replicate fast-tier payloads to
                    # peers and enqueue write-back promotion, strictly
                    # after this rank's writes landed and strictly
                    # before the commit barrier (so the durable commit
                    # marker can only ever trail the data)
                    finalize = getattr(storage, "finalize_take", None)
                    if finalize is not None:
                        finalize(coordinator, commit_uid)
                    # content checksums became final when staging
                    # finished above; gather them (foreground path:
                    # collectives are fine) and merge into every rank's
                    # metadata copy
                    local_crcs = _crc_payload(
                        local_entries, object_crcs, object_codecs,
                        object_cas,
                    )
                    if coordinator.world_size > 1:
                        crc_maps = coordinator.all_gather_object(local_crcs)
                    else:
                        crc_maps = [local_crcs]
                    _merge_crc_payloads(metadata, crc_maps)
                    # flight record, publish half: this rank's metrics
                    # delta + phase rollup ride the KV under explicit
                    # keys.  Best-effort — a lost payload degrades the
                    # record to a partial one, never the commit.
                    obs.aggregate.publish(
                        coordinator,
                        commit_uid,
                        obs.aggregate.rank_payload(
                            coordinator.rank, "take", obs_before
                        ),
                    )
                    # commit: all ranks done writing → rank 0 writes
                    # metadata (reference snapshot.py:202-209)
                    coordinator.barrier()
                    if coordinator.rank == 0:
                        coordinator.raise_if_poisoned(commit_uid)
                        # chunk-store index update STRICTLY before the
                        # commit marker (and strictly after the poison
                        # re-check): a committed step's chunk refs are
                        # registered before any reader can consider the
                        # step committed, so refcounted GC can never
                        # reap a committed step's chunks.  A crash in
                        # the gap leaves refs for an uncommitted step —
                        # mark-phase fodder, reclaimed after the grace
                        # window.
                        _cas_commit_refs(metadata, path, cas_store)
                        # flight record, merge half: every surviving
                        # rank published before the barrier above, so
                        # the merge sees them all; the record lands
                        # strictly BEFORE the commit marker (an
                        # interrupted write leaves an uncommitted
                        # snapshot with a record, never the reverse)
                        try:
                            obs.aggregate.write_obsrecord(
                                storage,
                                obs.aggregate.collect_and_merge(
                                    coordinator, commit_uid,
                                    op="take", path=path,
                                ),
                            )
                        except Exception as e:  # noqa: BLE001
                            obs.swallowed_exception("take.obsrecord", e)
                        # durable: the commit point must survive a host
                        # crash — a synced metadata file is the
                        # definition of "committed"
                        storage.sync_write(
                            WriteIO(
                                path=SNAPSHOT_METADATA_FNAME,
                                buf=metadata.to_yaml().encode(),
                                durable=True,
                            )
                        )
                        committed["done"] = True
                    coordinator.barrier()
            except SnapshotAbortedError:
                raise
            except RankDeadError as dead_err:
                # a peer died mid-commit.  Recovery runs OUTSIDE the
                # abort/liveness scopes (a scoped wait would re-raise
                # on the known-dead set forever) and finishes the
                # commit without the dead rank — complete when its
                # replicated objects could be re-written by survivors,
                # typed-degraded otherwise.
                if not knobs.takeover_enabled() or coordinator.world_size <= 1:
                    coordinator.poison(
                        commit_uid,
                        cause=repr(dead_err),
                        site=f"take/rank{coordinator.rank}",
                    )
                    raise
                try:
                    metadata = _recover_commit_after_death(
                        coordinator=coordinator,
                        commit_uid=commit_uid,
                        path=path,
                        metadata=metadata,
                        storage=storage,
                        local_entries=local_entries,
                        object_crcs=object_crcs,
                        object_codecs=object_codecs,
                        object_cas=object_cas,
                        cas_store=cas_store,
                        ctx=takeover_ctx,
                        monitor=session.monitor,
                        dead_err=dead_err,
                        already_committed=committed["done"],
                    )
                except BaseException as e:
                    coordinator.poison(
                        commit_uid,
                        cause=repr(e),
                        site=f"takeover/rank{coordinator.rank}",
                    )
                    raise
            except BaseException as e:
                coordinator.poison(
                    commit_uid,
                    cause=repr(e),
                    site=f"take/rank{coordinator.rank}",
                )
                raise
            finally:
                session.stop()
                stamp_stripe(take_event)
                storage.sync_close()
                if cas_store is not None:
                    cas_store.sync_close()
            # goodput: a sync take's unblock point is its return; the
            # durable commit just happened too — except under a
            # write-back tier, where the promoter reports it when the
            # DURABLE metadata marker lands
            if getattr(storage, "policy", None) != "write_back":
                obs.goodput.durable_commit(path)
            obs.goodput.take_unblocked(path, gp_begin)
            obs.maybe_write_metrics_textfile()
        snapshot = cls(path, coordinator, storage_options=storage_options)
        snapshot._metadata_cache = metadata
        return snapshot

    @classmethod
    def async_take(
        cls,
        path: str,
        app_state: AppState,
        replicated: Sequence[str] = (),
        coordinator: Optional[Coordinator] = None,
        base: Optional[str] = None,
        leaf_transform: Optional[Callable[[str, Any], Any]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        cas: Optional[Any] = None,
    ) -> "PendingSnapshot":
        """Unblock-early save (reference Snapshot.async_take,
        snapshot.py:229-318).  Returns once the snapshot content is
        independent of training state: device arrays are offloaded to
        pinned host memory in one batched DMA transfer and mutable host
        arrays are defensively copied.  Staging, storage I/O and the
        commit all happen in the background.  With
        TORCHSNAPSHOT_TPU_DISABLE_EAGER_HOST_STAGING=1 this reverts to
        the reference semantics (return after staging completes)."""
        coordinator = coordinator or get_default_coordinator()
        with log_event(
            Event("async_take", {"path": path, "rank": coordinator.rank})
        ):
            obs_before = obs.aggregate.capture()
            gp_begin = obs.goodput.take_begin(path)
            # liveness publisher from the very start (see take()); the
            # session hands off to the PendingSnapshot commit thread,
            # which stops it when the background commit resolves
            commit_uid = coordinator._next_uid("commit")
            session = LivenessSession(coordinator, commit_uid)
            session.start()
            try:
                (
                    metadata, pending_io, storage, commit_uid,
                    local_entries, object_crcs, object_codecs,
                    object_cas, cas_store, takeover_ctx,
                ) = cls._take_impl(
                    path, app_state, replicated, coordinator,
                    is_async=True, base=base,
                    leaf_transform=leaf_transform,
                    storage_options=storage_options, cas=cas,
                    commit_uid=commit_uid,
                )
            except BaseException:
                session.stop()
                raise
            # traced, the commit thread records under this call's span
            trace_context = (
                contextvars.copy_context() if obs.tracing_enabled() else None
            )
        pending = PendingSnapshot(
            path=path,
            metadata=metadata,
            pending_io_work=pending_io,
            storage=storage,
            coordinator=coordinator,
            commit_uid=commit_uid,
            local_entries=local_entries,
            object_crcs=object_crcs,
            object_codecs=object_codecs,
            storage_options=storage_options,
            obs_before=obs_before,
            object_cas=object_cas,
            cas_store=cas_store,
            takeover_ctx=takeover_ctx,
            liveness_session=session,
            trace_context=trace_context,
        )
        # goodput: the unblock point IS this return — training state is
        # independent of the snapshot from here; staging/IO/commit (and
        # the flight-record exchange) drain in the background
        obs.goodput.take_unblocked(path, gp_begin)
        return pending

    @classmethod
    def _take_impl(
        cls,
        path: str,
        app_state: AppState,
        replicated: Sequence[str],
        coordinator: Coordinator,
        is_async: bool,
        base: Optional[str] = None,
        leaf_transform: Optional[Callable[[str, Any], Any]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        cas: Optional[Any] = None,
        commit_uid: Optional[str] = None,
    ) -> Tuple[
        SnapshotMetadata, PendingIOWork, Any, str,
        Dict[str, Entry], Dict[str, int], Dict[str, Any],
        Dict[str, Any], Any, "_TakeoverContext",
    ]:
        # reference _take_impl, snapshot.py:517-635
        rank, world = coordinator.rank, coordinator.world_size
        _validate_app_state(app_state)

        # Take must never perturb the host RNG streams, and the RNG state
        # that gets *saved* must be the state at entry (reference
        # _pop_rng_state, snapshot.py:532-574).  Mechanism: capture every
        # RNGState instance's state NOW — via the instance, so subclasses
        # capturing extra streams (e.g. torch's) are honored — and have
        # the serialization loop below substitute these entry captures
        # for those keys instead of re-calling state_dict() mid-loop.
        # On exit each instance restores its own entry state, plus a base
        # restore covering takes with no RNGState in app_state at all.
        rng_at_entry = RNGState().state_dict()
        rng_states_at_entry = {
            k: v.state_dict()
            for k, v in app_state.items()
            if isinstance(v, RNGState)
        }
        # The commit uid doubles as the abort scope and is minted BEFORE
        # planning (same per-instance counter position on every rank),
        # so even a rank dying in the planning gathers — storage
        # construction, glob/key/manifest exchanges — poisons a scope
        # its peers are already watching instead of wedging them.
        # Callers that run a liveness session mint it even earlier and
        # pass it in, so heartbeats cover planning and staging too.
        if commit_uid is None:
            commit_uid = coordinator._next_uid("commit")
        try:
            with coordinator.abort_scope(commit_uid):
                return cls._take_impl_inner(
                    path, app_state, replicated, coordinator, is_async,
                    rank, world, rng_states_at_entry, commit_uid, base,
                    leaf_transform=leaf_transform,
                    storage_options=storage_options, cas=cas,
                )
        except SnapshotAbortedError:
            raise
        except BaseException as e:
            coordinator.poison(
                commit_uid, cause=repr(e), site=f"take_plan/rank{rank}"
            )
            raise
        finally:
            for k, v in app_state.items():
                if isinstance(v, RNGState):
                    v.load_state_dict(rng_states_at_entry[k])
            RNGState().load_state_dict(rng_at_entry)

    @classmethod
    def _take_impl_inner(
        cls,
        path: str,
        app_state: AppState,
        replicated: Sequence[str],
        coordinator: Coordinator,
        is_async: bool,
        rank: int,
        world: int,
        rng_states_at_entry: Dict[str, Dict[str, Any]],
        commit_uid: str,
        base: Optional[str] = None,
        leaf_transform: Optional[Callable[[str, Any], Any]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        cas: Optional[Any] = None,
    ) -> Tuple[
        SnapshotMetadata, PendingIOWork, Any, str,
        Dict[str, Entry], Dict[str, int], Dict[str, Any],
        Dict[str, Any], Any, "_TakeoverContext",
    ]:

        # path + replicated coalescing across ranks
        # (reference _coalesce_path_and_replicated, snapshot.py:858-894)
        replicated = _infer_replicated(replicated, app_state)
        path0 = coordinator.broadcast_object(path, src=0)
        if path0 != path:
            logger.warning(
                "rank %d: snapshot path %r differs from rank 0's %r; using "
                "rank 0's", rank, path, path0
            )
            path = path0
        # the verification mode rides the same gather as the globs: it
        # gates what each rank contributes to the fingerprint gather, so
        # it must be rank-agreed (strictest wins) without paying an extra
        # KV round
        local_mode = _safe_replication_verify_mode()
        local_cksum = knobs.write_checksums_enabled()
        local_cas = _normalize_cas_config(cas, path)
        if world > 1:
            gathered = coordinator.all_gather_object(
                (
                    sorted(set(replicated)), local_mode, base,
                    local_cksum, local_cas,
                )
            )
            gathered_globs = [g for g, _, _, _, _ in gathered]
            modes = [m for _, m, _, _, _ in gathered]
            # incremental base + cas config + checksum participation
            # must be rank-agreed: they gate later broadcasts (the
            # base's object table / the chunk index's key set), and
            # divergent branches would deadlock them.  Rank 0's base
            # and cas win (like the path); dedup needs checksums on
            # EVERY rank (each rank stages its own objects).
            base = gathered[0][2]
            cas_cfg = gathered[0][4]
            checksums_all = all(c for _, _, _, c, _ in gathered)
            if not checksums_all and base is not None:
                logger.warning(
                    "rank %d: WRITE_CHECKSUMS off on some rank; "
                    "incremental dedup disabled for this take", rank,
                )
                base = None
            if not checksums_all and cas_cfg is not None:
                logger.warning(
                    "rank %d: WRITE_CHECKSUMS off on some rank; content "
                    "addressing needs whole-pipeline digests — taking a "
                    "plain (per-step object) snapshot", rank,
                )
                cas_cfg = None
            replicated_globs = sorted(
                set(gathered_globs[0]).intersection(*map(set, gathered_globs[1:]))
            )
            if set(replicated) != set(replicated_globs):
                logger.warning(
                    "rank %d: replicated globs differ across ranks; using the "
                    "intersection %r", rank, replicated_globs
                )
            verify_mode = _strictest_mode(modes)
            if len(set(modes)) > 1:
                logger.warning(
                    "rank %d: REPLICATION_VERIFY differs across ranks (%s); "
                    "using the strictest: %r",
                    rank, sorted(set(modes)), verify_mode,
                )
        else:
            replicated_globs = sorted(set(replicated))
            verify_mode = local_mode
            cas_cfg = local_cas
            if cas_cfg is not None and not local_cksum:
                logger.warning(
                    "take(cas=...) needs WRITE_CHECKSUMS=1; taking a "
                    "plain (per-step object) snapshot"
                )
                cas_cfg = None

        storage = _storage_for(path, storage_options)

        # gather the global key list; serialize per-key state_dict() calls
        # with barriers in case a Stateful's state_dict performs collectives
        # (reference _gather_keys, snapshot.py:552-568)
        local_keys = sorted(app_state.keys())
        if world > 1:
            global_keys = sorted(
                set().union(*coordinator.all_gather_object(local_keys))
            )
        else:
            global_keys = local_keys
        # RNGState keys serialize the state captured at take ENTRY
        # (``rng_states_at_entry``, taken before any collective or
        # storage init could touch the streams), so the saved stream is
        # exact even when an alphabetically-earlier stateful's
        # state_dict() consumes RNG.  Keys are NOT reordered: the
        # barrier-aligned loop below must run in the same order on every
        # rank, and a rank-local sort key (which keys are RNGState here)
        # could diverge across ranks.
        manifest: Manifest = {}
        flattened: Dict[str, Any] = {}
        for key in global_keys:
            if key in app_state:
                state = (
                    rng_states_at_entry[key]
                    if key in rng_states_at_entry
                    else app_state[key].state_dict()
                )
                m, f = flatten(state, prefix=key)
                manifest.update(m)
                flattened.update(f)
            if world > 1:
                coordinator.barrier()

        if leaf_transform is not None:
            # before replication verification, so fingerprints (and the
            # written bytes) reflect the TRANSFORMED content
            flattened = {
                p: leaf_transform(p, v) for p, v in flattened.items()
            }

        # plan writes per leaf (reference prepare_write dispatch,
        # io_preparer.py:82-147)
        entries: Dict[str, Entry] = {}
        write_reqs: List[WriteReq] = []
        repl_reqs: Dict[str, List[WriteReq]] = {}
        repl_items: List[Tuple[str, int]] = []
        # chunk-granular items for replicated CHUNKED entries: a multi-GB
        # replicated host array is split across writer ranks per chunk
        # instead of riding one rank (reference partitioner.py:40-47)
        repl_chunk_reqs: Dict[str, WriteReq] = {}
        chunk_parent: Dict[str, str] = {}
        local_bytes = 0
        verified_repl = _verify_replicated_paths(
            flattened, replicated_globs, coordinator, verify_mode
        )
        # Per-rank host-state weight feeds the sharded-box balancer as a
        # pre-load, so a process carrying heavy per-rank host state (e.g.
        # a data-loader rank's buffers) is assigned fewer sharded boxes —
        # the two balancers compose (reference partitioner.py:266-270).
        # The gathered vector is identical on every controller, keeping
        # box assignment collective-free and deterministic; it is then
        # MUTATED by each sharded leaf's assignment so sharded leaves
        # also compose with each other.
        host_est = sum(
            estimate_write_bytes(obj)
            for lp, obj in flattened.items()
            if lp not in verified_repl and not is_multi_device_jax_array(obj)
        )
        writer_loads = list(
            coordinator.all_gather_object(host_est)
            if world > 1
            else [host_est]
        )
        # rank → host → slice placement (topology/): identical on every
        # rank (explicit spec, or one kv_exchange of per-process hints
        # under the commit uid), so the topology-aware writer elections
        # below stay pure deterministic functions — replicated state is
        # written once per FLEET with writers spread across slices and
        # hosts to balance per-slice durable egress
        topo = topology_mod.detect_topology(
            coordinator, exchange_prefix=f"{commit_uid}/topo"
        )
        # resolve the chunking knob ONCE for the whole take and pass it
        # down: one env resolution instead of one per leaf (measurable
        # in the blocked window at tens of thousands of leaves), a
        # mid-take env change can't split chunking behavior across
        # leaves, and no global override state is touched (concurrent
        # takes from different threads must not interleave overrides)
        chunk_size_bytes = knobs.get_max_chunk_size_bytes()
        # planning (prepare_write fan-out) is the dominant blocked-path
        # CPU cost at high leaf counts — first-class in traces
        with obs.span("take/plan", leaves=len(flattened), rank=rank):
            for lpath in sorted(flattened.keys()):
                obj = flattened[lpath]
                repl = lpath in verified_repl
                entry, reqs = prepare_write(
                    obj=obj,
                    logical_path=lpath,
                    rank=rank,
                    replicated=repl,
                    is_async_snapshot=is_async,
                    process_index=rank,
                    process_count=world,
                    writer_loads=writer_loads,
                    chunk_size_bytes=chunk_size_bytes,
                    topology=topo,
                )
                entries[lpath] = entry
                cost = sum(
                    r.buffer_stager.get_staging_cost_bytes() for r in reqs
                )
                if repl and not isinstance(entry, ShardedArrayEntry):
                    if isinstance(entry, ChunkedArrayEntry) and len(reqs) > 1:
                        for ci, r in enumerate(reqs):
                            k = f"{lpath}\x00{ci}"  # \x00 can't be in paths
                            repl_chunk_reqs[k] = r
                            chunk_parent[k] = lpath
                            repl_items.append(
                                (k, r.buffer_stager.get_staging_cost_bytes())
                            )
                    else:
                        repl_reqs[lpath] = reqs
                        repl_items.append((lpath, cost))
                else:
                    write_reqs.extend(reqs)
                    local_bytes += cost

        # takeover (resilience): capture the UNBATCHED replicated entry
        # objects on every rank — before non-writers drop theirs below
        # and before batching re-points the writer's at rank-local
        # slabs.  Their ``replicated/`` locations are rank-independent,
        # so if this rank is later elected to re-write a dead peer's
        # object, the re-homed manifest entry describes exactly what it
        # wrote.  Object references (not dicts): the replay's staging
        # sinks stamp crc32 onto these same objects.
        repl_entry_objs: Dict[str, Entry] = {
            lp: entries[lp]
            for lp in set(repl_reqs) | set(chunk_parent.values())
        }

        # balance replicated host-state writes across ranks
        # (reference partition_write_reqs, partitioner.py:216-310)
        split_repl_paths: set = set()
        preloads: List[int] = [0] * world
        assignment: Dict[str, int] = {}
        if repl_items:
            preloads = list(
                coordinator.all_gather_object(local_bytes)
                if world > 1
                else [local_bytes]
            )
            assignment = partition_replicated_writes(
                repl_items, world, preloads, topology=topo
            )
            # per-slice egress attribution: each writer rank counts the
            # replicated write units/bytes it carries; the flight
            # record groups ranks by slice for the doctor rollup.
            # Explicit topologies only — a flat job ran the flat
            # greedy, and stamping topology.* counters on it would
            # make doctor/stats render a topology section nobody
            # configured.
            cost_of = dict(repl_items)
            count_writers = topo.explicit
            m_repl_objs = obs.counter(
                obs.TOPOLOGY_REPLICATED_OBJECTS_WRITTEN
            )
            m_repl_bytes = obs.counter(
                obs.TOPOLOGY_REPLICATED_BYTES_WRITTEN
            )
            for lpath, reqs in repl_reqs.items():
                if assignment[lpath] == rank:
                    write_reqs.extend(reqs)
                    if count_writers:
                        m_repl_objs.inc()
                        m_repl_bytes.inc(cost_of[lpath])
                else:
                    # Only the writer keeps the entry: batching may re-point
                    # the writer's entry at a slab location, and the global
                    # manifest must carry exactly the written copy
                    # (consolidation dedups replicated entries to one rank).
                    del entries[lpath]
            writes_chunk_of: Dict[str, bool] = {}
            counted_chunk_parents: set = set()
            for k, req in repl_chunk_reqs.items():
                lp = chunk_parent[k]
                mine = assignment[k] == rank
                writes_chunk_of[lp] = writes_chunk_of.get(lp, False) or mine
                if mine:
                    write_reqs.append(req)
                    if count_writers:
                        # bytes per chunk, but the OBJECT counts once
                        # per rank carrying any of its chunks — the
                        # doctor row says "objects", not chunks
                        m_repl_bytes.inc(cost_of[k])
                        if lp not in counted_chunk_parents:
                            counted_chunk_parents.add(lp)
                            m_repl_objs.inc()
            for lp, any_mine in writes_chunk_of.items():
                if any_mine:
                    # every chunk-writing rank carries an IDENTICAL copy
                    # of the whole entry (chunk locations are rank-
                    # independent under replicated/); restore dedups
                    split_repl_paths.add(lp)
                else:
                    del entries[lp]

        # take/batch: slabs, then the manifest and every request's digest,
        # codec and content-address sinks, up to take/pipeline
        with obs.span("take/batch") as batched:
            # coalesce small writes into slabs (reference batcher.py:204-355)
            if not knobs.is_batching_disabled():
                # shield split replicated entries: slab-packing a chunk would
                # re-point it to a rank-LOCAL location, silently diverging the
                # per-rank copies of the shared entry
                shielded = {
                    lp: entries.pop(lp)
                    for lp in split_repl_paths
                    if lp in entries
                }
                entries, write_reqs = batch_write_requests(
                    entries, write_reqs, rank
                )
                entries.update(shielded)

            # whole-object digests feed the metadata objects table and the
            # incremental-dedup decision; attached AFTER batching so slab
            # objects are covered at their final paths
            object_crcs: Dict[str, List[int]] = {}
            # codec frame tables (codec.py): filled by the scheduler for
            # every object it stores compressed; rides the crc gather into
            # SnapshotMetadata.codecs.  Sinks are attached unconditionally
            # (one closure per request) — whether anything encodes is the
            # scheduler's per-run CODEC-knob decision.
            object_codecs: Dict[str, Any] = {}
            for wr in write_reqs:
                def _codec_sink(table: dict, wr=wr) -> None:
                    object_codecs[wr.path] = table

                wr.codec_sink = _codec_sink
            if cas_cfg is not None and base is not None:
                # chunk-level addressing dedups against EVERY committed step
                # sharing the pool — the whole-object base link is strictly
                # weaker, and mixing the two storage models in one take
                # would split ownership semantics
                logger.info(
                    "rank %d: take(cas=...) supersedes base=%r; using "
                    "chunk-level content addressing", rank, base,
                )
                base = None
            if base is not None and base.rstrip("/") == path.rstrip("/"):
                # self-dedup would link an object onto itself (and the fs
                # fallback's unlink-before-link would destroy the only copy)
                logger.warning(
                    "rank %d: incremental base equals the target path %r; "
                    "performing a full save", rank, path,
                )
                base = None
            if knobs.write_checksums_enabled():
                base_objects: Dict[str, Any] = {}
                base_codecs: Dict[str, Any] = {}
                if base is not None:
                    # rank 0 reads the base's object table once and shares it
                    # (every rank GETting a multi-MB metadata object from
                    # cloud storage at the start of each take is a
                    # thundering herd); branch participation is rank-agreed
                    # by the gather above
                    if rank == 0:
                        try:
                            base_meta = Snapshot(base).metadata
                            base_objects = base_meta.objects or {}
                            # a dedup link copies the base's STORED bytes —
                            # if those were codec frames, the frame table
                            # must carry into this snapshot's manifest
                            base_codecs = base_meta.codecs or {}
                        except Exception as e:  # noqa: BLE001
                            logger.warning(
                                "rank 0: incremental base %r unusable (%r); "
                                "performing a full save", base, e,
                            )
                    if world > 1:
                        base_objects, base_codecs = (
                            coordinator.broadcast_object(
                                (base_objects, base_codecs), src=0
                            )
                        )
                for wr in write_reqs:
                    def _object_sink(digest: List[int], wr=wr) -> None:
                        wr.object_digest = tuple(digest)
                        object_crcs[wr.path] = list(digest)

                    wr.digest_sink = _object_sink
                    base_digest = base_objects.get(wr.path)
                    # dedup compares (crc32, adler32, size) — two independent
                    # checksums + exact length, so a lone crc32 collision
                    # can't silently link stale content
                    if (
                        base is not None
                        and isinstance(base_digest, (list, tuple))
                        and len(base_digest) == 3
                    ):
                        wr.dedup = (base, tuple(int(x) for x in base_digest))
                        wr.dedup_codec = base_codecs.get(wr.path)
            elif base is not None:
                logger.warning(
                    "rank %d: take(base=...) needs WRITE_CHECKSUMS=1; "
                    "performing a full save", rank,
                )

            # content-addressed chunk store (cas/): rank 0 reads the
            # committed index's LIVE key set once and shares it (same
            # thundering-herd economics as the base objects table above);
            # every write request gets a context routing it through the
            # pool, with one shared written-this-take set so intra-take
            # repeats (tied weights, identical slabs on two reqs) dedup too
            object_cas: Dict[str, Any] = {}
            cas_store = None
            if cas_cfg is not None:
                from . import cas as cas_mod

                cas_store = cas_mod.ChunkStore(cas_cfg["root"])
                known_keys: set = set()
                if rank == 0:
                    try:
                        known_keys = cas_mod.ChunkIndex.load(
                            cas_store
                        ).live_keys()
                    except cas_mod.ChunkIndexCorruptError as e:
                        logger.warning(
                            "corrupt chunk index under %r (%r); rebuilding "
                            "via fsck before this take", cas_cfg["root"], e,
                        )
                        try:
                            cas_mod.fsck(cas_cfg["root"])
                            known_keys = cas_mod.ChunkIndex.load(
                                cas_store
                            ).live_keys()
                        except Exception as e2:  # noqa: BLE001
                            logger.warning(
                                "chunk-index fsck under %r failed (%r); "
                                "this take writes every chunk (correct, "
                                "just not deduplicated)", cas_cfg["root"], e2,
                            )
                            known_keys = set()
                if world > 1:
                    known_keys = coordinator.broadcast_object(
                        known_keys, src=0
                    )
                written_this_take: set = set()
                for wr in write_reqs:
                    def _cas_sink(table: dict, wr=wr) -> None:
                        object_cas[wr.path] = table

                    wr.cas = cas_mod.CasWriteContext(
                        store=cas_store,
                        known_keys=known_keys,
                        chunk_size=cas_cfg["chunk_size"],
                        sink=_cas_sink,
                        written_this_take=written_this_take,
                    )

            # gather per-rank manifests; every rank can build the global view
            # deterministically (reference _gather_manifest,
            # snapshot.py:948-961)
            # NOTE: this serializes entry objects BEFORE staging runs, so
            # checksum sinks (which fire during staging) mutate only the
            # LOCAL objects below — the commit paths re-gather crc maps
            # post-staging and merge them into the metadata (_merge_crcs).
            local_entry_objs = {**manifest, **entries}
            local_manifest_d = {
                lpath: e.to_dict() for lpath, e in local_entry_objs.items()
            }
            if world > 1:
                gathered_manifests = coordinator.all_gather_object(
                    local_manifest_d
                )
            else:
                gathered_manifests = [local_manifest_d]
            global_manifest = consolidate_manifests(
                [
                    {k: entry_from_dict(v) for k, v in md.items()}
                    for md in gathered_manifests
                ]
            )
            metadata = SnapshotMetadata(
                version=MANIFEST_VERSION,
                world_size=world,
                manifest=global_manifest,
            )
            if cas_cfg is not None:
                # the rank-agreed envelope; per-rank chunk tables merge in
                # at commit (_merge_crc_payloads).  The root is recorded
                # relative ("../cas") under the manager layout so a rehomed
                # checkpoint tree keeps restoring.
                from . import cas as cas_mod

                metadata.cas = {
                    "root": cas_mod.record_root(path, cas_cfg["root"]),
                    "chunk_size": cas_cfg["chunk_size"],
                    "chunks": {},
                }
            if batched is not None:
                batched.attrs["objects"] = len(write_reqs)
                batched.attrs["slabs"] = sum(
                    isinstance(wr.buffer_stager, BatchedBufferStager)
                    for wr in write_reqs
                )

        budget = get_process_memory_budget_bytes()

        # TPU-native unblock-early point: one batched device→pinned_host
        # transfer (plus eager defensive copies of mutable host arrays)
        # makes every pending buffer independent of training state, so the
        # async path returns *before* staging instead of after it — the
        # reference must wait for staged-in-host-RAM because CUDA tensors
        # are mutable (reference scheduler.py:299, io_preparers/
        # tensor.py:283-307); jax.Array immutability moves the safety
        # point to the end of this call.
        unblock_early = is_async and not knobs.is_eager_host_staging_disabled()
        if unblock_early:
            from .host_offload import eager_offload_write_reqs

            # Cap the pinned-host claim at half the staging budget so
            # offloaded-but-unstaged buffers plus in-flight staged copies
            # stay within host RAM; arrays past the cap stage lazily in
            # the background (safe: jax.Array is immutable).
            eager_offload_write_reqs(write_reqs, budget_bytes=budget // 2)
        pending_io = sync_execute_write_reqs(
            write_reqs, storage, budget, rank,
            wait_for_staging=not unblock_early,
        )
        takeover_ctx = _TakeoverContext(
            topo=topo,
            preloads=preloads,
            assignment=assignment,
            repl_reqs=repl_reqs,
            repl_chunk_reqs=repl_chunk_reqs,
            chunk_parent=chunk_parent,
            repl_items=repl_items,
            repl_entries=repl_entry_objs,
            gathered_manifests=gathered_manifests,
        )
        return (
            metadata, pending_io, storage, commit_uid,
            local_entry_objs, object_crcs, object_codecs, object_cas,
            cas_store, takeover_ctx,
        )

    # --------------------------------------------------------------- restore

    @property
    def metadata(self) -> SnapshotMetadata:
        # reference snapshot.py:96-110,842-854
        if self._metadata_cache is None:
            from .io_types import ReadIO

            storage = _storage_for(self.path, self._storage_options)
            try:
                read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
                storage.sync_read(read_io)
            except FileNotFoundError as e:
                # Missing outright (cold start / never committed) is
                # distinguishable from unreadable, so resumable-training
                # loops can `except FileNotFoundError` to cold-start.
                raise FileNotFoundError(
                    f"no {SNAPSHOT_METADATA_FNAME} under {self.path!r} — "
                    f"not a committed snapshot (a snapshot without "
                    f"metadata was aborted before commit)"
                ) from e
            except Exception as e:
                raise RuntimeError(
                    f"failed to read {SNAPSHOT_METADATA_FNAME} under "
                    f"{self.path!r} — the snapshot is incomplete or was "
                    f"aborted before commit ({e!r})"
                ) from e
            finally:
                storage.sync_close()
            self._metadata_cache = SnapshotMetadata.from_yaml(
                bytes(read_io.buf).decode()
            )
        return self._metadata_cache

    def get_manifest(self) -> Dict[str, Entry]:
        return dict(self.metadata.manifest)

    def publish_to(self, publisher: Any, step: int) -> str:
        """Publish this committed snapshot to a live-weight publication
        root (publish/Publisher) so serving subscribers can delta-swap
        to it; returns the publication record path.  ``step`` orders
        the publication (snapshots don't carry one themselves — the
        manager's publish hook passes its index step).  Unlike the
        manager/continuous hooks this is the EXPLICIT path and raises
        on failure."""
        return publisher.publish_snapshot(
            self.path, step, metadata=self.metadata
        )

    def _prime_tier_digests(self, storage: Any) -> None:
        """Tiered storage: install the committed metadata's whole-object
        digest table on the plugin so fast/peer-tier reads verify before
        they are trusted (and silently fall back + repair on mismatch).
        No-op for ordinary plugins.

        Codec-encoded objects (codec.py) verify against their STORED
        digest from the codec table — the bytes on disk are frames, so
        the raw digest in ``objects`` would flag every intact copy as
        corrupt.  An encoded object whose table carries no stored digest
        is left unprimed (trust the read; the frame structure and the
        entry crcs above still catch corruption)."""
        prime = getattr(storage, "prime_digests", None)
        if prime is None:
            return
        digests = dict(self.metadata.objects or {})
        for loc, tbl in (self.metadata.codecs or {}).items():
            stored = tbl.get("digest") if isinstance(tbl, dict) else None
            if (
                isinstance(stored, (list, tuple)) and len(stored) == 3
            ):
                digests[loc] = [int(x) for x in stored]
            else:
                digests.pop(loc, None)
        prime(digests)

    def _codec_tables(self) -> Optional[Dict[str, Any]]:
        """location → validated codec frame table for objects this
        snapshot stored compressed; None when nothing is encoded (the
        common case — reads skip the lookup entirely).  Structurally
        invalid entries (version skew) are dropped with a warning: the
        read then sees stored frame bytes where raw bytes were expected
        and fails loudly at the digest/parse layer instead of silently
        misdecoding."""
        from . import codec as codec_mod

        codecs = self.metadata.codecs or {}
        if not codecs:
            return None
        tables = {}
        for loc, tbl in codecs.items():
            if codec_mod.validate_table(tbl):
                tables[loc] = tbl
            else:
                logger.warning(
                    "manifest codec table for %r is structurally invalid "
                    "(version skew?); treating the object as raw", loc,
                )
        return tables or None

    def _cas_reads(self) -> Optional[Tuple[Any, Dict[str, Any]]]:
        """``(ChunkStore, {location → validated chunk table})`` for
        objects this snapshot stored as chunk references (cas/), or
        None when nothing is chunk-ref'd — pre-CAS snapshots (no
        ``cas`` key at all) restore through the unchanged per-step
        path.  The caller owns closing the returned store."""
        from . import cas as cas_mod

        meta_cas = self.metadata.cas or {}
        if not meta_cas:
            return None
        tables = cas_mod.chunk_tables_from_metadata(self.metadata)
        if not tables:
            return None
        root = cas_mod.resolve_root(self.path, str(meta_cas.get("root")))
        return cas_mod.ChunkStore(root), tables

    @staticmethod
    def _close_cas_reads(cas_reads: Optional[Tuple[Any, Any]]) -> None:
        if cas_reads is not None:
            cas_reads[0].sync_close()

    def restore(
        self,
        app_state: AppState,
        strict: bool = True,
        paths: Optional[Sequence[str]] = None,
        priority: Optional[Sequence[str]] = None,
    ) -> None:
        """Distributed load/reshard into the given app state (reference
        Snapshot.restore, snapshot.py:319-396).

        ``paths`` (beyond-parity): restore only leaves whose logical path
        matches one of the fnmatch globs — e.g. ``["model/params/**"]``
        to warm-start parameters from a pretrained snapshot while the
        optimizer state keeps its fresh values.  Unmatched leaves are
        left untouched (the reference's only alternatives are
        all-or-nothing restore or per-leaf ``read_object``).  Filtering
        implies non-strict inflation for the skipped leaves; ``strict``
        still governs whether app_state keys absent from the snapshot
        raise.

        ``priority`` (serving): an ordered list of fnmatch globs — reads
        whose logical path matches an earlier glob execute first
        (unmatched leaves last), so a server can restore its
        first-requested layers first and begin serving before the full
        snapshot lands.  Ordering only; every leaf is still restored."""
        coordinator = self._coordinator
        rank, world = coordinator.rank, coordinator.world_size
        _validate_app_state(app_state)
        with log_event(
            Event("restore", {"path": self.path, "rank": rank})
        ) as restore_event:
            stamp_stripe = _stripe_event_stamp()
            obs_before = obs.aggregate.capture()
            # abort-aware restore: the scope uid is agreed up front (the
            # per-instance uid counter runs in the same program order on
            # every rank), and covers EVERYTHING that can fail — even a
            # rank dying on the metadata read poisons before its peers
            # enter the key gather, so nobody wedges to a wait timeout.
            # The failing rank re-raises its own error; peers raise a
            # typed SnapshotAbortedError naming it.
            abort_uid = coordinator._next_uid("restore")
            storage = None
            cas_reads = None
            # death-aware restore (resilience/liveness.py): a peer that
            # dies mid-restore surfaces as a typed RankDeadError at the
            # barriers/kv waits within LIVENESS_TIMEOUT_S instead of a
            # full wait-timeout wedge.  No takeover on the read path —
            # restore holds no state its peers need re-created; failing
            # fast with the dead rank named is the whole contract.
            session = LivenessSession(coordinator, abort_uid)
            try:
                session.start()
                with coordinator.abort_scope(abort_uid), \
                        coordinator.liveness_scope(session.monitor):
                    # restore/metadata: what the caller's thread does
                    # before the first leaf is planned (metadata and this
                    # rank's manifest, storage open, topology and fan-out
                    # set-up, the key gather)
                    with obs.span("restore/metadata", rank=rank):
                        metadata = self.metadata
                        manifest_for_rank = get_manifest_for_rank(
                            metadata, rank
                        )
                        storage = _storage_for(self.path, self._storage_options)
                        self._prime_tier_digests(storage)
                        cas_reads = self._cas_reads()
                        # fan-out restore (topology/fanout.py): per-slice
                        # designated readers pull each replicated object
                        # from the durable tier exactly once and
                        # redistribute over the coordination KV — restore
                        # cost O(objects) per slice, not O(objects × ranks).
                        # The wrapper goes OUTSIDE any host cache, so the
                        # one GET per slice is itself host-deduped; all
                        # ranks must call restore with rank-agreed
                        # paths/priority arguments (the same SPMD contract
                        # every other restore collective already assumes).
                        topo = topology_mod.detect_topology(
                            coordinator, exchange_prefix=f"{abort_uid}/topo"
                        )
                        transport = None
                        if topology_mod.fanout_enabled(topo):
                            shared = topology_mod.shared_read_locations(
                                metadata.manifest
                            )
                            if shared:
                                # payload transport (transport/): the
                                # capability-probed engine the fan-out's
                                # redistribution bytes ride — collectives
                                # when the runtime supports them, the KV
                                # blob path otherwise
                                transport = transport_mod.resolve_transport(
                                    coordinator, topology=topo
                                )
                                storage = topology_mod.FanoutReadPlugin(
                                    storage, coordinator, topo,
                                    f"{abort_uid}/fan", shared,
                                    transport=transport,
                                )
                        local_keys = sorted(app_state.keys())
                        if world > 1:
                            global_keys = sorted(
                                set().union(
                                    *coordinator.all_gather_object(local_keys)
                                )
                            )
                        else:
                            global_keys = local_keys
                        # RNG state is restored last so earlier restores
                        # cannot perturb it (reference snapshot.py:371-381)
                        global_keys.sort(
                            key=lambda k: isinstance(app_state.get(k), RNGState)
                        )
                        # collective fan-out session: whole shared objects
                        # move as ordered broadcasts over the live jax
                        # runtime instead of KV blobs.  Requires a session-
                        # capable transport, every slice fanning out
                        # (fanout_world_uniform — the gate protocol needs
                        # all world ranks), and a FULL restore (a paths
                        # filter makes "which shared objects get read" a
                        # per-rank question the pre-agreed schedule cannot
                        # answer).  The plan rides the global key order so
                        # the schedule advances with the per-key barriers.
                        if (
                            transport is not None
                            and getattr(transport, "mode", None) == "session"
                            and isinstance(
                                storage, topology_mod.FanoutReadPlugin
                            )
                            and paths is None
                            and topology_mod.fanout_world_uniform(topo)
                        ):
                            try:
                                plan_paths = (
                                    topology_mod.ordered_shared_locations(
                                        metadata.manifest,
                                        storage.shared_paths,
                                        global_keys,
                                    )
                                )
                                storage.transport_session = (
                                    transport.open_fanout_session(
                                        topo, f"{abort_uid}/fan", plan_paths
                                    )
                                )
                            except Exception as e:  # noqa: BLE001 — the
                                # restore proceeds on the KV path
                                transport_mod.count_fallback(
                                    "session-open", e
                                )
                    for key in global_keys:
                        if key in app_state:
                            self._load_stateful(
                                key, app_state[key], manifest_for_rank,
                                storage, strict, rank, paths=paths,
                                cas_reads=cas_reads, priority=priority,
                            )
                        if world > 1:
                            coordinator.barrier()
                    # restore/finalize, the call's half: what the
                    # caller's thread does after the last key is loaded
                    with obs.span("restore/finalize", rank=rank):
                        # fan-out blob cleanup: the per-key barriers above
                        # prove every rank is past its reads, so the
                        # transient publications — KV blobs, collective
                        # session gate keys, device-registry entries — can
                        # be reclaimed (a restore must not permanently grow
                        # the coordination service's store)
                        tsession = getattr(
                            storage, "transport_session", None
                        )
                        if tsession is not None:
                            tsession.close()
                        cleanup = getattr(storage, "cleanup_published", None)
                        if cleanup is not None:
                            cleanup()
                        # restore flight record: cross-rank merge only (no
                        # persistence — the snapshot may live on read-only
                        # storage); rank 0 keeps the merged record
                        # in-process (obs.aggregate.last_record("restore")).
                        # All ranks just left the final barrier, so the
                        # single-phase exchange converges in one KV round.
                        obs.aggregate.exchange_and_merge(
                            coordinator,
                            abort_uid,
                            obs.aggregate.rank_payload(
                                rank, "restore", obs_before
                            ),
                            op="restore",
                            path=self.path,
                        )
            except SnapshotAbortedError:
                raise
            except BaseException as e:
                coordinator.poison(
                    abort_uid, cause=repr(e), site=f"restore/rank{rank}"
                )
                raise
            finally:
                with obs.span("restore/finalize", rank=rank):
                    session.stop()
                    stamp_stripe(restore_event)
                    if storage is not None:
                        # error-path transport teardown (idempotent after
                        # the happy path's close above): the session thread
                        # must not outlive the restore, and the device
                        # registry must not accrete across restores
                        tsession = getattr(
                            storage, "transport_session", None
                        )
                        if tsession is not None:
                            try:
                                tsession.close()
                            except Exception as e:  # noqa: BLE001
                                obs.swallowed_exception(
                                    "restore.transport_close", e
                                )
                        transport = getattr(storage, "transport", None)
                        if transport is not None:
                            try:
                                transport.close()
                            except Exception as e:  # noqa: BLE001
                                obs.swallowed_exception(
                                    "restore.transport_close", e
                                )
                        storage.sync_close()
                    self._close_cas_reads(cas_reads)
            obs.maybe_write_metrics_textfile()

    def _load_stateful(
        self,
        key: str,
        stateful: Any,
        manifest_for_rank: Manifest,
        storage: Any,
        strict: bool,
        rank: int,
        paths: Optional[Sequence[str]] = None,
        cas_reads: Optional[Tuple[Any, Dict[str, Any]]] = None,
        priority: Optional[Sequence[str]] = None,
    ) -> None:
        # reference _load_stateful, snapshot.py:727-782.  restore/plan,
        # restore/pipeline (scheduler.sync_execute_read_reqs) and
        # restore/finalize partition this call on the caller's thread
        with obs.span("restore/plan", key=key) as plan_span:
            key_manifest = {
                p: e
                for p, e in manifest_for_rank.items()
                if p == key or p.startswith(key + "/")
            }
            if not key_manifest:
                if strict:
                    raise KeyError(
                        f"app_state key {key!r} not found in snapshot manifest"
                    )
                logger.warning("skipping %r: not in snapshot", key)
                return
            if paths is not None and not any(
                not is_container_entry(e) and path_is_replicated(p, paths)
                for p, e in key_manifest.items()
            ):
                return  # nothing under this key matches the filter
            # degraded snapshot (takeover, docs/resilience.md): logical
            # paths only a dead rank held are typed-missing, not silently
            # zero.  A marker blocks THIS restore only when this rank's view
            # would actually source the dead rank's bytes: its own rank IS
            # the origin (per-rank private state), the entry is sharded (the
            # merged view includes the dead rank's lost boxes), or it is
            # replicated and was not taken over (every view overlays the
            # dead writer's copy).  A peer's intact private copy of the same
            # logical path restores normally.  Steer around the gap with
            # restore(paths=...), or heal it first (SnapshotManager.repair()
            # / the next take).
            degraded = getattr(self.metadata, "degraded", None) or {}
            if degraded:
                hits = sorted(
                    p
                    for p, e in key_manifest.items()
                    if p in degraded
                    and not is_container_entry(e)
                    and (paths is None or path_is_replicated(p, paths))
                    and (
                        rank == degraded[p].get("origin_rank")
                        or isinstance(e, ShardedArrayEntry)
                        or bool(getattr(e, "replicated", False))
                    )
                )
                if hits:
                    raise DegradedSnapshotError(self.path, hits)
            # current state provides in-place/sharding templates
            # (reference snapshot.py:754-762)
            _, targets = flatten(stateful.state_dict(), prefix=key)
            self._map_legacy_leaf_targets(key, stateful, key_manifest, targets)

            container_entries: Manifest = {}
            read_reqs: List[ReadReq] = []
            futures: Dict[str, Future] = {}
            for lpath, entry in key_manifest.items():
                if is_container_entry(entry):
                    container_entries[lpath] = entry
                    continue
                if paths is not None and not path_is_replicated(lpath, paths):
                    # partial restore: no read for unmatched leaves — but
                    # list/tuple structure must survive inflation, so seed
                    # the slot with the CURRENT value instead of dropping it
                    # (a dropped ListEntry child would compact the list and
                    # shift later elements onto wrong indices).  Membership,
                    # not is-None: a present-but-None leaf still holds its
                    # list slot.
                    if lpath in targets:
                        fut: Future = Future(targets[lpath])
                        fut.set(targets[lpath])
                        futures[lpath] = fut
                    continue
                reqs, fut = prepare_read(entry, obj_out=targets.get(lpath))
                if priority:
                    pri = _read_priority_for(lpath, priority)
                    for r in reqs:
                        r.priority = pri
                read_reqs.extend(reqs)
                futures[lpath] = fut
            if not knobs.is_batching_disabled():
                read_reqs = batch_read_requests(read_reqs)
            budget = get_process_memory_budget_bytes()
            codec_tables = self._codec_tables()
            if plan_span is not None:
                plan_span.attrs["leaves"] = len(futures)
                plan_span.attrs["reads"] = len(read_reqs)
        try:
            sync_execute_read_reqs(
                read_reqs, storage, budget, rank,
                codec_tables=codec_tables,
                cas_reads=cas_reads,
                # fan-out: front-load the reads THIS rank must publish
                # for its slice siblings, so their waits are minimal
                publish_first=getattr(storage, "local_publish_paths", None),
            )
            with obs.span("restore/finalize", key=key):
                restored = {lpath: fut.obj for lpath, fut in futures.items()}
                state_dict = inflate(
                    container_entries,
                    restored,
                    prefix=key,
                    allow_missing=(not strict) or paths is not None,
                )
                # propagate strict to load_state_dict when the stateful
                # accepts it (reference snapshot.py:775-778 for
                # nn.Module); a paths filter implies non-strict (unmatched
                # leaves keep current values)
                load_with_strict(
                    stateful, state_dict, strict and paths is None
                )
        except BaseException:
            self._repair_after_failed_restore(
                key, stateful, container_entries, futures, targets
            )
            raise

    @staticmethod
    def _repair_after_failed_restore(
        key: str,
        stateful: Any,
        container_entries: Manifest,
        futures: Dict[str, Future],
        targets: Dict[str, Any],
    ) -> None:
        """Keep the caller's live state free of deleted arrays after a
        mid-stateful restore failure.

        Restore donation (1x device peak, see
        ``preparers/array.py:donate_template``) frees each template's
        buffers as soon as its replacement materializes.  A failure on a
        LATER leaf would otherwise leave earlier templates deleted while
        still reachable from the caller's state — any use raises XLA's
        "Array has been deleted".  Every donation happens strictly after
        ``fut.set``, so each donated template has a retrievable
        replacement: load the already-restored leaves (keeping intact
        templates for the rest, non-strict) so the state is mixed
        old/new but entirely VALID — the same mid-failure semantics as
        the reference's in-place tensor load (snapshot.py:743-753).
        No-op when no template was actually donated (donation off, host
        templates, or the failure hit the first leaf)."""
        def _is_deleted(t: Any) -> bool:
            is_deleted = getattr(t, "is_deleted", None)
            if callable(is_deleted):
                try:
                    return bool(is_deleted())
                except Exception:  # noqa: BLE001 — e.g. inside a transform
                    return False
            return False

        deleted = sum(1 for t in targets.values() if _is_deleted(t))
        if not deleted:
            return
        # One array object can be the template for several paths (tied
        # weights).  Map template identity → its restored replacement so
        # a path whose OWN read never finished but whose (shared)
        # template was donated by a sibling path gets the sibling's
        # replacement — never the deleted array itself.
        replacement_by_template: Dict[int, Any] = {}
        for lpath, fut in futures.items():
            if fut.done and lpath in targets and fut.obj is not targets[lpath]:
                replacement_by_template[id(targets[lpath])] = fut.obj
        restored: Dict[str, Any] = {}
        for lpath, fut in futures.items():
            if fut.done:
                restored[lpath] = fut.obj
            elif lpath in targets:
                t = targets[lpath]
                if not _is_deleted(t):
                    restored[lpath] = t
                elif id(t) in replacement_by_template:
                    restored[lpath] = replacement_by_template[id(t)]
                # else: deleted with no known replacement (cannot happen
                # given donate-after-fut.set ordering) — omit the path
                # rather than load a dead array; allow_missing keeps the
                # structure intact
        try:
            state_dict = inflate(
                container_entries, restored, prefix=key, allow_missing=True
            )
            load_with_strict(stateful, state_dict, False)
            logger.warning(
                "restore of %r failed after donation freed %d template(s); "
                "loaded the partially-restored state so live arrays remain "
                "valid — the state is now MIXED (restored leaves + prior "
                "values). Set TORCHSNAPSHOT_TPU_RESTORE_DONATE=0 to keep "
                "templates fully intact on failure (2x device peak).",
                key, deleted,
            )
        except Exception:
            logger.exception(
                "restore of %r failed after donation freed %d template(s), "
                "and repairing the live state also failed — state for this "
                "key may reference deleted arrays", key, deleted,
            )

    @staticmethod
    def _map_legacy_leaf_targets(
        key: str, stateful: Any, key_manifest: Manifest, targets: Dict[str, Any]
    ) -> None:
        """Snapshots written before PyTreeState rendered NAMED paths store
        leaves as ``<key>/leaves/<i>``; a current PyTreeState's named
        targets would never match them, losing the in-place/sharding
        templates (full-array host reads, no device placement).  Map the
        template's leaves onto the legacy paths positionally — the same
        order both formats derive from ``jax.tree_util`` flattening."""
        import re

        from .stateful import PyTreeState, _tree_path_keys

        stateful = unwrap(stateful)
        if not isinstance(stateful, PyTreeState):
            return
        pat = re.compile(re.escape(key) + r"/leaves/(\d+)$")
        legacy = {
            int(m.group(1)): p
            for p in key_manifest
            if (m := pat.fullmatch(p)) and not is_container_entry(key_manifest[p])
        }
        if not legacy or any(p in targets for p in legacy.values()):
            return
        pairs, _ = _tree_path_keys(stateful.tree)
        for i, (_, leaf) in enumerate(pairs):
            if i in legacy:
                targets[legacy[i]] = leaf

    # ----------------------------------------------------------- read_object

    def verify(self, deep: bool = False) -> "Any":
        """Integrity audit of this rank's view (beyond-parity; see
        verify.py): every referenced object must exist with at least the
        byte extent the manifest claims; ``deep=True`` additionally
        dry-run-restores every entry.  Returns a ``VerifyResult``."""
        from .verify import verify_snapshot

        # no bracket here: verify_snapshot brackets itself with
        # log_event(Event("verify", ...)) (verify.py) — a second one
        # would double-count the operation for every handler
        return verify_snapshot(self, deep=deep)

    def repair_degraded(
        self,
        sources: Sequence[str],
        paths: Optional[Sequence[str]] = None,
    ) -> List[str]:
        """Heal a degraded snapshot IN PLACE from continuous peer
        stores (docs/resilience.md).

        A snapshot committed degraded lost state only the dead rank
        held.  The continuous checkpoint loop keeps a per-rank RAM/disk
        mirror under ``<host-root>/r<rank>`` on every peer the dead
        rank replicated to — this re-reads the lost leaves from those
        mirrors (content-verified), re-writes them at their manifest
        locations, drops them from the ``degraded`` section and
        rewrites the commit marker.  Single-process ops tool: no
        coordination — only the dead rank's entries and the marker are
        touched, marker rewritten strictly last.

        ``sources``: continuous host roots (the per-rank ``r<d>``
        subdir is probed) and/or direct per-rank store roots ending in
        ``/r<d>``.  ``paths``: restrict to these logical paths.

        Returns the logical paths repaired.  Sharded device state
        cannot be rebuilt from a host mirror (the mesh is gone) — such
        paths are skipped with a warning; only a fresh complete take
        heals them."""
        with log_event(
            Event("repair_degraded", {"path": self.path})
        ), obs.span("snapshot/repair_degraded", path=self.path):
            return self._repair_degraded_impl(sources, paths)

    def _read_peer_leaves(
        self, sources: Sequence[str], origin: int, lpaths: Sequence[str]
    ) -> Dict[str, Any]:
        """Materialize the wanted logical paths from the first usable
        continuous mirror of rank ``origin``.  Only roots NAMESPACED to
        that rank are probed — a same-shaped leaf from some other
        rank's mirror would be the wrong rank's data."""
        from .continuous.store import ContinuousStore, decode_leaf

        wanted = set(lpaths)
        for src in sources:
            src = str(src).rstrip("/")
            root = src if src.endswith(f"/r{origin}") else f"{src}/r{origin}"
            store = ContinuousStore(root)
            try:
                head = store.read_head()
                if head is None:
                    continue
                manifest = store.read_step_manifest(str(head["manifest"]))
                recs = {
                    lp: rec
                    for lp, rec in manifest["leaves"].items()
                    if lp in wanted
                }
                if not recs:
                    continue
                chunks = store.read_chunks(
                    [k for rec in recs.values() for k in rec["keys"]]
                )
                out: Dict[str, Any] = {}
                for lp, rec in recs.items():
                    data = b"".join(chunks[k] for k in rec["keys"])
                    if len(data) != int(rec["size"]):
                        raise IOError(
                            f"leaf {lp!r}: assembled {len(data)} bytes, "
                            f"manifest says {rec['size']}"
                        )
                    out[lp] = decode_leaf(rec, data)
                logger.info(
                    "repair: recovered %d/%d leaves of dead rank %d from "
                    "%r (step %d)",
                    len(out), len(wanted), origin, root, int(head["step"]),
                )
                return out
            except Exception as e:  # noqa: BLE001 — ladder to next source
                logger.warning(
                    "repair source %r unusable for rank %d (%r); trying "
                    "the next one", root, origin, e,
                )
            finally:
                store.sync_close()
        return {}

    def _repair_degraded_impl(
        self, sources: Sequence[str], paths: Optional[Sequence[str]]
    ) -> List[str]:
        metadata = self.metadata
        degraded = dict(getattr(metadata, "degraded", None) or {})
        if not degraded:
            return []
        if isinstance(sources, str):
            sources = [sources]
        wanted = {
            p: info
            for p, info in degraded.items()
            if paths is None or p in set(paths)
        }
        by_origin: Dict[int, List[str]] = {}
        for p, info in wanted.items():
            by_origin.setdefault(int(info.get("origin_rank", -1)), []).append(p)
        cksum = knobs.write_checksums_enabled()
        storage = _storage_for(self.path, self._storage_options)
        repaired: List[str] = []
        try:
            for d, lpaths in sorted(by_origin.items()):
                leaves = self._read_peer_leaves(sources, d, lpaths)
                reqs: List[WriteReq] = []
                staged: List[Tuple[str, Entry]] = []
                for lp in sorted(set(lpaths) & set(leaves)):
                    old = metadata.manifest.get(f"{d}/{lp}")
                    if isinstance(old, ShardedArrayEntry):
                        logger.warning(
                            "repair: %r is sharded device state — a host "
                            "mirror cannot rebuild the mesh layout; only "
                            "a fresh take heals it", lp,
                        )
                        continue
                    entry, ereqs = prepare_write(
                        obj=leaves[lp], logical_path=lp, rank=d,
                    )
                    for wr in ereqs:
                        # plain writes (no codec_sink): a repaired object
                        # must read through the raw path, so stale codec
                        # tables for its locations are dropped below
                        if cksum:
                            def _sink(digest: List[int], wr=wr) -> None:
                                wr.object_digest = tuple(digest)
                                metadata.objects[wr.path] = list(digest)

                            wr.digest_sink = _sink
                    reqs.extend(ereqs)
                    staged.append((lp, entry))
                if not staged:
                    continue
                sync_execute_write_reqs(
                    reqs, storage, get_process_memory_budget_bytes(),
                    self._coordinator.rank,
                ).sync_complete()
                for lp, entry in staged:
                    old = metadata.manifest.get(f"{d}/{lp}")
                    if old is not None:
                        # the dead rank's never-landed locations leave
                        # the objects/codecs tables with the entry
                        old_locs = [
                            loc
                            for loc in [getattr(old, "location", None)]
                            if isinstance(loc, str)
                        ] + [
                            s.location
                            for attr in ("shards", "chunks")
                            for s in getattr(old, attr, None) or ()
                        ]
                        for loc in old_locs:
                            metadata.codecs.pop(loc, None)
                            if cksum:
                                # keep only digests the repair re-stamped
                                new_locs = {r.path for r in reqs}
                                if loc not in new_locs:
                                    metadata.objects.pop(loc, None)
                    metadata.manifest[f"{d}/{lp}"] = entry
                    metadata.degraded.pop(lp, None)
                    repaired.append(lp)
            if repaired:
                # marker strictly last: a crash mid-repair leaves a
                # still-committed (still-degraded) snapshot, never a
                # marker pointing at unwritten repairs
                storage.sync_write(
                    WriteIO(
                        path=SNAPSHOT_METADATA_FNAME,
                        buf=metadata.to_yaml().encode(),
                        durable=True,
                    )
                )
                obs.counter(obs.TAKEOVER_PATHS_REPAIRED).inc(len(repaired))
                logger.warning(
                    "repair: healed %d degraded path(s) of %r; %d still "
                    "degraded", len(repaired), self.path,
                    len(metadata.degraded),
                )
        finally:
            storage.sync_close()
        return sorted(repaired)

    def materialize(
        self, rank: Optional[int] = None,
        priority: Optional[Sequence[str]] = None,
    ) -> Dict[str, Any]:
        """Read one rank's ENTIRE view into a nested state dict of host
        values — no templates, no app_state (beyond-parity; the
        reference's only template-free access is per-leaf read_object,
        snapshot.py:397-501).  Arrays come back as numpy; move them to
        device with ``jax.tree.map(jnp.asarray, ...)``.

        With the MMAP knob on (the default) and a local/cached source,
        arrays come back as READ-ONLY mmap-backed views — zero heap
        copies, pages fault in from the page cache on first touch.
        Call ``np.copy`` on a leaf if you need a private writable
        buffer.  ``priority`` orders the reads like ``restore``'s
        (first-matching-glob first).

        For inspection, migration and tooling; a training restore should
        keep using ``restore`` (sharded templates, in-place semantics,
        donation).  Note: PyTreeState records stringified pytree paths
        (its treedef owns the structure), so its list/tuple nodes come
        back as index-keyed dicts here; StateDict trees keep real
        lists."""
        if rank is None:
            rank = self._coordinator.rank
        world = self.metadata.world_size
        if not 0 <= rank < world:
            # get_manifest_for_rank's grown-world semantics would return
            # a replicated-only view — silently missing rank-private
            # leaves is exactly wrong for an inspection API
            raise ValueError(
                f"rank {rank} out of range for world_size={world}"
            )
        with log_event(
            Event("materialize", {"path": self.path, "rank": rank})
        ):
            manifest = get_manifest_for_rank(self.metadata, rank)
            containers = {
                p: e for p, e in manifest.items() if is_container_entry(e)
            }
            futures: Dict[str, Future] = {}
            read_reqs: List[ReadReq] = []
            for p, e in manifest.items():
                if not is_container_entry(e):
                    reqs, fut = prepare_read(e, obj_out=None)
                    if priority:
                        pri = _read_priority_for(p, priority)
                        for r in reqs:
                            r.priority = pri
                    read_reqs.extend(reqs)
                    futures[p] = fut
            if not knobs.is_batching_disabled():
                read_reqs = batch_read_requests(read_reqs)
            storage = _storage_for(self.path, self._storage_options)
            self._prime_tier_digests(storage)
            cas_reads = self._cas_reads()
            try:
                sync_execute_read_reqs(
                    read_reqs, storage, get_process_memory_budget_bytes(),
                    rank, codec_tables=self._codec_tables(),
                    cas_reads=cas_reads,
                )
            finally:
                storage.sync_close()
                self._close_cas_reads(cas_reads)
            leaves = {p: fut.obj for p, fut in futures.items()}
            return {
                key: inflate(containers, leaves, prefix=key)
                for key in sorted({p.split("/", 1)[0] for p in manifest})
            }

    def read_object(
        self,
        path: str,
        obj_out: Optional[Any] = None,
        memory_budget_bytes: Optional[int] = None,
    ) -> Any:
        """Random access to a single object: ``path`` is
        ``"<rank>/<logical_path>"`` (reference Snapshot.read_object,
        snapshot.py:397-501)."""
        with log_event(Event("read_object", {"path": path})):
            rank_str, _, lpath = path.partition("/")
            manifest = get_manifest_for_rank(self.metadata, int(rank_str))
            if lpath not in manifest:
                raise KeyError(f"{lpath!r} not in snapshot manifest")
            entry = manifest[lpath]
            if isinstance(entry, PrimitiveEntry):
                return entry.get_value()
            reqs, fut = prepare_read(
                entry, obj_out=obj_out, buffer_size_limit_bytes=memory_budget_bytes
            )
            storage = _storage_for(self.path, self._storage_options)
            self._prime_tier_digests(storage)
            cas_reads = self._cas_reads()
            try:
                sync_execute_read_reqs(
                    reqs,
                    storage,
                    memory_budget_bytes or get_process_memory_budget_bytes(),
                    rank=0,
                    codec_tables=self._codec_tables(),
                    cas_reads=cas_reads,
                )
            finally:
                storage.sync_close()
                self._close_cas_reads(cas_reads)
            return fut.obj


class PendingSnapshot:
    """Handle for an in-flight async snapshot (reference PendingSnapshot,
    snapshot.py:962-1065).

    The background thread performs storage-I/O drain + a KV-only commit
    barrier: every rank reports done-or-error under the commit uid; rank 0
    writes ``.snapshot_metadata`` iff every rank succeeded, then releases
    the barrier.  Metadata is NEVER written on failure (asserted by
    fault-injection tests, reference tests/test_async_take.py:96-117).
    """

    def __init__(
        self,
        path: str,
        metadata: SnapshotMetadata,
        pending_io_work: PendingIOWork,
        storage: Any,
        coordinator: Coordinator,
        commit_uid: str,
        local_entries: Optional[Dict[str, Entry]] = None,
        object_crcs: Optional[Dict[str, int]] = None,
        object_codecs: Optional[Dict[str, Any]] = None,
        storage_options: Optional[Dict[str, Any]] = None,
        obs_before: Optional[Dict[str, Any]] = None,
        object_cas: Optional[Dict[str, Any]] = None,
        cas_store: Optional[Any] = None,
        takeover_ctx: Optional[_TakeoverContext] = None,
        liveness_session: Optional[LivenessSession] = None,
        trace_context: Optional[contextvars.Context] = None,
    ) -> None:
        self.path = path
        self._storage_options = storage_options
        # metrics capture at async_take entry: the commit thread deltas
        # against it after the background drain, so the flight record
        # covers staging + I/O that ran after the caller unblocked
        self._obs_before = obs_before or obs.aggregate.capture()
        self._metadata = metadata
        self._pending_io_work = pending_io_work
        self._storage = storage
        self._coordinator = coordinator
        self._commit_uid = commit_uid
        self._local_entries = local_entries or {}
        self._object_crcs = object_crcs if object_crcs is not None else {}
        # codec frame tables (codec.py): filled by the background
        # staging/write work as objects store compressed; read at
        # commit time on the same thread that runs sync_complete(), so
        # every sink has fired before the payload is built
        self._object_codecs = (
            object_codecs if object_codecs is not None else {}
        )
        # chunk tables (cas/): same lifecycle as the codec tables — read
        # at commit time on the thread that ran sync_complete(), so
        # every sink has fired; the store handle closes with the commit
        self._object_cas = object_cas if object_cas is not None else {}
        self._cas_store = cas_store
        # write takeover (resilience): planning-time context + the
        # liveness session (handed off by async_take, already stamping
        # since before planning), so a peer rank dying during the
        # background commit is survived the same way as in the sync
        # path.  Assigned HERE (before the thread starts) so there is
        # no attribute race with the commit thread.
        self._takeover_ctx = takeover_ctx
        self._liveness_session = liveness_session or LivenessSession(
            coordinator, commit_uid
        )
        self._committed = False
        self._exc: Optional[BaseException] = None
        self._snapshot: Optional[Snapshot] = None
        # traced: a copy of the async_take's context, so the commit
        # thread's spans (and the pipelines it starts) reach that call
        self._trace_context = trace_context
        self._thread = threading.Thread(
            target=self._complete_snapshot, name="tsnp-commit", daemon=True
        )
        self._thread.start()

    def _complete_snapshot(self, in_trace_context: bool = False) -> None:
        if self._trace_context is not None and not in_trace_context:
            self._trace_context.run(self._complete_snapshot, True)
            return
        # KV ops only — never collectives, never uid-counter-based gathers
        # (those belong to the foreground thread's program order)
        coord = self._coordinator
        uid = self._commit_uid
        rank, world = coord.rank, coord.world_size
        status = "ok"
        try:
            self._pending_io_work.sync_complete()
            # tiered storage: peer replication + write-back promotion
            # hand-off.  KV-only (explicit keys), so it is legal here;
            # runs only when this rank's writes all succeeded.
            finalize = getattr(self._storage, "finalize_take", None)
            if finalize is not None:
                finalize(coord, uid)
        except BaseException as e:  # noqa: BLE001
            self._exc = e
            status = f"err:{e!r}"
            # poison FIRST: peers blocked in the abort-aware waits below
            # learn of this failure in one poll interval even before the
            # arrive/depart protocol rounds complete
            coord.poison(uid, cause=repr(e), site=f"async_commit/rank{rank}")
        # death-aware background commit: heartbeat under the commit uid
        # and run the protocol's kv waits with the liveness monitor, so
        # a SIGKILLed peer surfaces as RankDeadError (handled inside the
        # protocol via write takeover) instead of a full wait timeout
        try:
            self._liveness_session.start()
            with coord.abort_scope(uid), coord.liveness_scope(
                self._liveness_session.monitor
            ):
                self._complete_snapshot_protocol(
                    coord, uid, rank, world, status
                )
        finally:
            self._liveness_session.stop()

    def _complete_snapshot_protocol(
        self, coord: Coordinator, uid: str, rank: int, world: int, status: str
    ) -> None:
        try:
            # content checksums finalized during background staging ride
            # the KV channel (collectives are forbidden here); set BEFORE
            # arrive so rank 0's post-arrival read always finds them
            import json as _json

            if status == "ok":
                try:
                    coord.kv_set(
                        f"{uid}/crcs/{rank}",
                        _json.dumps(
                            _crc_payload(
                                self._local_entries,
                                self._object_crcs,
                                self._object_codecs,
                                self._object_cas,
                            )
                        ),
                    )
                except Exception as e:  # noqa: BLE001
                    if self._object_codecs or self._object_cas:
                        # codec frame tables and chunk tables ride this
                        # channel and are the DECODE/ASSEMBLY RECIPE for
                        # this rank's compressed/chunk-ref'd objects —
                        # committing without them produces a durable
                        # snapshot that cannot be restored, so this rank
                        # must fail the commit (arrive carries the
                        # error; rank 0 withholds the marker).  Plain
                        # checksums stay best-effort.
                        status = f"err:codec/chunk tables lost: {e!r}"
                        if self._exc is None:
                            self._exc = e
                    coord.kv_set(f"{uid}/crcs/{rank}", "{}")
            else:
                coord.kv_set(f"{uid}/crcs/{rank}", "{}")
            # flight record, publish half: before arrive, so rank 0's
            # post-arrival merge always finds every surviving rank's
            # payload.  Best-effort by contract.
            obs.aggregate.publish(
                coord,
                uid,
                obs.aggregate.rank_payload(rank, "take", self._obs_before),
            )
            coord.kv_set(f"{uid}/arrive/{rank}", status)
            if rank == 0:
                # ALWAYS set the depart key, even if the metadata write
                # itself raises — otherwise peers block until timeout with
                # a misleading error.
                try:
                    statuses = [
                        coord.kv_get(f"{uid}/arrive/{r}") for r in range(world)
                    ]
                    failed = [s for s in statuses if s != "ok"]
                    if not failed:
                        raw_payloads = None
                        try:
                            raw_payloads = [
                                coord.kv_get(f"{uid}/crcs/{r}")
                                for r in range(world)
                            ]
                            _merge_crc_payloads(
                                self._metadata,
                                [_json.loads(p) for p in raw_payloads],
                            )
                        except Exception:  # noqa: BLE001
                            # plain checksums are best-effort, but codec
                            # frame tables / chunk tables in these
                            # payloads are the decode/assembly recipe
                            # for compressed/chunk-ref'd objects — if
                            # any rank reported one (or the reads failed
                            # so we cannot tell), the commit must fail
                            # rather than durably strand unreadable
                            # bytes behind a raw-path manifest
                            if raw_payloads is None or any(
                                '"codecs"' in p or '"cas"' in p
                                for p in raw_payloads
                            ):
                                raise
                            logger.warning(
                                "crc merge failed; committing without "
                                "checksums", exc_info=True,
                            )
                        # chunk-store index update STRICTLY before the
                        # commit marker (poison re-checked just below,
                        # before the marker — same invariant as the
                        # sync path)
                        _cas_commit_refs(
                            self._metadata, self.path, self._cas_store
                        )
                        # flight record, merge half: every surviving
                        # rank published before its arrive key, and
                        # all arrive keys were read above — persist
                        # the merged record BEFORE the commit marker
                        try:
                            obs.aggregate.write_obsrecord(
                                self._storage,
                                obs.aggregate.collect_and_merge(
                                    coord, uid, op="take", path=self.path,
                                ),
                            )
                        except Exception as e:  # noqa: BLE001
                            obs.swallowed_exception(
                                "async_commit.obsrecord", e
                            )
                        # durable-commit invariant: never write the
                        # commit marker after the scope was poisoned
                        coord.raise_if_poisoned(uid)
                        self._storage.sync_write(
                            WriteIO(
                                path=SNAPSHOT_METADATA_FNAME,
                                buf=self._metadata.to_yaml().encode(),
                                durable=True,
                            )
                        )
                        self._committed = True
                        depart = "ok"
                    else:
                        depart = f"peers failed: {failed}"
                except BaseException as e:  # noqa: BLE001
                    depart = f"rank 0 commit failed: {e!r}"
                    coord.kv_set(f"{uid}/depart", depart)
                    raise
                coord.kv_set(f"{uid}/depart", depart)
            depart = coord.kv_get(f"{uid}/depart")
            if depart != "ok" and self._exc is None:
                self._exc = RuntimeError(
                    f"async snapshot commit failed: {depart}"
                )
            if depart == "ok" and (
                getattr(self._storage, "policy", None) != "write_back"
            ):
                # goodput: the durable marker just landed (write-back
                # tiers report from the promoter's metadata copy
                # instead)
                obs.goodput.durable_commit(self.path)
        except RankDeadError as dead_err:
            # a peer died during the background commit.  Recovery uses
            # only kv_set/kv_try_get (no scoped waits), so running it
            # here — scopes still active — is safe; tolerance for the
            # known-dead set lives in _recovery_kv_get.
            try:
                if status != "ok":
                    # this rank already failed and poisoned; a dead peer
                    # on top of that doesn't change the local outcome
                    raise dead_err
                self._recover_after_death(coord, uid, rank, world, dead_err)
            except BaseException as e:  # noqa: BLE001
                coord.poison(
                    uid, cause=repr(e), site=f"takeover/rank{rank}"
                )
                if self._exc is None:
                    self._exc = e
        except BaseException as e:  # noqa: BLE001
            if self._exc is None:
                self._exc = e
        finally:
            # the drained work pins the staged host buffers through its
            # starter/future closures; a PendingSnapshot handle may
            # outlive the commit arbitrarily (e.g. held by a manager's
            # sweep list), so drop them the moment they're consumed
            self._pending_io_work = None
            obs.maybe_write_metrics_textfile()
            if self._cas_store is not None:
                try:
                    self._cas_store.sync_close()
                except Exception:  # noqa: BLE001 — teardown only
                    logger.warning(
                        "chunk-store close after async commit failed",
                        exc_info=True,
                    )
            try:
                self._storage.sync_close()
            except Exception:
                # the commit outcome is already decided (self._exc);
                # a teardown failure must not overwrite it — but a
                # leaked executor/fd is worth a visible warning
                logger.warning(
                    "storage close after async commit failed",
                    exc_info=True,
                )

    def _recover_after_death(
        self,
        coord: Coordinator,
        uid: str,
        rank: int,
        world: int,
        dead_err: RankDeadError,
    ) -> None:
        """Finish the background commit without the dead peer(s) — same
        machinery as the sync path.  Async caveat (documented in
        docs/resilience.md): a takeover writer re-stages the orphaned
        replicated objects from the live application state, which may
        have advanced since async_take returned; the re-written copies
        are self-consistent but can be newer than the dead rank's."""
        if (
            self._takeover_ctx is None
            or not knobs.takeover_enabled()
            or world <= 1
        ):
            raise dead_err
        _recover_commit_after_death(
            coordinator=coord,
            commit_uid=uid,
            path=self.path,
            metadata=self._metadata,
            storage=self._storage,
            local_entries=self._local_entries,
            object_crcs=self._object_crcs,
            object_codecs=self._object_codecs,
            object_cas=self._object_cas,
            cas_store=self._cas_store,
            ctx=self._takeover_ctx,
            monitor=self._liveness_session.monitor,
            dead_err=dead_err,
            already_committed=self._committed,
        )
        self._committed = True
        if getattr(self._storage, "policy", None) != "write_back":
            obs.goodput.durable_commit(self.path)

    def wait(self) -> Snapshot:
        """Block until the background commit finishes; re-raise any error
        (reference snapshot.py:1056-1065)."""
        self._thread.join()
        if self._exc is not None:
            raise self._exc
        if self._snapshot is None:
            self._snapshot = Snapshot(
                self.path,
                self._coordinator,
                storage_options=self._storage_options,
            )
            if self._coordinator.rank == 0:
                # rank 0's commit thread merged the gathered checksums
                # into this manifest before writing it
                self._snapshot._metadata_cache = self._metadata
            # other ranks lazy-load the COMMITTED metadata: their local
            # copy never saw the crc merge, and a handle whose manifest
            # silently lacks checksums would make verify(deep=True) skip
            # every content check
        return self._snapshot

    def done(self) -> bool:
        return not self._thread.is_alive()
