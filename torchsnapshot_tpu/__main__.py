"""Operator CLI: inspect, audit and manage snapshots from a shell.

    python -m torchsnapshot_tpu ls        <snapshot-path>
    python -m torchsnapshot_tpu stats     <snapshot-path> [--json] [--top N]
    python -m torchsnapshot_tpu doctor    <snapshot-path> [--json] [--diff OTHER]
    python -m torchsnapshot_tpu manifest  <snapshot-path>
    python -m torchsnapshot_tpu verify    <snapshot-path> [--deep] [--rank N]
    python -m torchsnapshot_tpu steps     <manager-root>
    python -m torchsnapshot_tpu tiers     <durable-root> --fast <fast-root> [--json]
    python -m torchsnapshot_tpu cas       <cas-root> [--json] [--fsck] [--gc]
    python -m torchsnapshot_tpu delete    <snapshot-path> --yes
    python -m torchsnapshot_tpu trace     <snapshot-path> [--out FILE]
    python -m torchsnapshot_tpu lint      [root] [--json] [--pass ID]

Paths take any storage URL the library accepts (plain/fs, gs://, s3://).
Exit code is non-zero when a verify fails or a delete is refused —
usable directly from CI and babysitter jobs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _human(n: float) -> str:
    # bytes print exact; everything else one decimal.  The loop exits
    # via the TB arm for any size ≥ 1024 TB (no unformatted fallthrough:
    # a pre-fix version printed multi-TB sizes as e.g. "2048.0B")
    if n < 1024:
        return f"{int(n)}B"
    for unit in ("KB", "MB", "GB", "TB"):
        n /= 1024.0
        if n < 1024 or unit == "TB":
            return f"{n:.1f}{unit}"
    raise AssertionError("unreachable")


def _cmd_ls(args) -> int:
    from .manifest import is_container_entry
    from .serialization import serialized_size_bytes, string_to_dtype
    from .snapshot import Snapshot

    man = Snapshot(args.path).get_manifest()
    rows = []
    for lpath, e in sorted(man.items()):
        if is_container_entry(e):
            continue
        kind = e.type
        detail = ""
        nbytes = 0
        shape = getattr(e, "shape", None)
        dtype = getattr(e, "dtype", None)
        if shape is not None and dtype is not None:
            detail = f"{dtype}{list(shape)}"
            nbytes = serialized_size_bytes(shape, string_to_dtype(dtype))
        rows.append((lpath, kind, detail, nbytes))
    width = max((len(r[0]) for r in rows), default=10)
    for lpath, kind, detail, nbytes in rows:
        size = _human(nbytes) if nbytes else ""
        print(f"{lpath:<{width}}  {kind:<12} {detail:<24} {size}")
    print(f"{len(rows)} entries")
    return 0


def _entry_stats(entry) -> dict:
    """(nbytes, dtype, pieces) rollup for one non-container manifest
    entry — manifest-only, no storage reads.  Byte sizes prefer recorded
    byte_range extents (exact, covers slabbed objects) and fall back to
    the dtype/shape product for array entries written before ranges."""
    from .serialization import serialized_size_bytes, string_to_dtype

    def _extent(byte_range) -> int:
        return byte_range[1] - byte_range[0] if byte_range else 0

    dtype = getattr(entry, "dtype", None)
    nbytes = 0
    pieces = 0
    for attr in ("shards", "chunks"):
        for piece in getattr(entry, attr, None) or ():
            pieces += 1
            nbytes += _extent(piece.byte_range) or (
                serialized_size_bytes(piece.sizes, string_to_dtype(dtype))
                if dtype is not None
                else 0
            )
    if not pieces:
        nbytes = _extent(getattr(entry, "byte_range", None))
        shape = getattr(entry, "shape", None)
        if not nbytes and shape is not None and dtype is not None:
            nbytes = serialized_size_bytes(shape, string_to_dtype(dtype))
    shape = getattr(entry, "shape", None)
    return {
        "kind": entry.type,
        "dtype": dtype,
        # [] is a real shape (0-d array) and must stay distinct from
        # "entry has no shape" (None)
        "shape": list(shape) if shape is not None else None,
        "nbytes": nbytes,
        "pieces": pieces,
    }


def _codec_rollup(metadata) -> dict:
    """Per-snapshot compression rollup from the manifest codec tables
    (codec.py): how many storage objects each codec carries, raw vs
    stored bytes, and the overall achieved ratio.  Objects in the
    whole-object digest table but NOT the codec table are stored raw;
    a pre-codec-era snapshot (no tables at all) reports all-raw."""
    from .codec import table_stored_size, validate_table

    codecs_tbl = metadata.codecs or {}
    objects_tbl = metadata.objects or {}
    by_codec: dict = {}

    def bucket(name):
        return by_codec.setdefault(
            name, {"objects": 0, "raw_bytes": 0, "stored_bytes": 0}
        )

    for loc, tbl in codecs_tbl.items():
        if not validate_table(tbl):
            continue
        b = bucket(tbl["codec"])
        b["objects"] += 1
        b["raw_bytes"] += int(tbl["raw_size"])
        b["stored_bytes"] += table_stored_size(tbl)
    for loc, rec in objects_tbl.items():
        if loc in codecs_tbl:
            continue
        if isinstance(rec, (list, tuple)) and len(rec) == 3:
            b = bucket("raw")
            b["objects"] += 1
            b["raw_bytes"] += int(rec[2])
            b["stored_bytes"] += int(rec[2])
    raw_total = sum(b["raw_bytes"] for b in by_codec.values())
    stored_total = sum(b["stored_bytes"] for b in by_codec.values())
    return {
        "by_codec": by_codec,
        "raw_bytes": raw_total,
        "stored_bytes": stored_total,
        "ratio": (raw_total / stored_total) if stored_total else None,
    }


def _cas_stats_rollup(snapshot) -> dict:
    """CAS rollup for one snapshot: how much of its payload is
    chunk-ref'd (vs per-step objects), and — when the pool's index is
    reachable — the pool-wide live/orphan counts, refcount histogram
    and per-step shared-vs-new byte attribution.  ``{}`` for non-CAS
    snapshots so the stats document shape stays stable."""
    from . import cas as cas_mod

    metadata = snapshot.metadata
    meta_cas = metadata.cas or {}
    if not meta_cas:
        return {}
    tables = cas_mod.chunk_tables_from_metadata(metadata)
    distinct = {k for t in tables.values() for k in t["keys"]}
    out = {
        "root": meta_cas.get("root"),
        "chunked_objects": len(tables),
        "chunked_bytes": sum(int(t["size"]) for t in tables.values()),
        "distinct_chunks": len(distinct),
        "distinct_chunk_bytes": sum(
            cas_mod.key_size(k) for k in distinct
        ),
    }
    store = cas_mod.ChunkStore(
        cas_mod.resolve_root(snapshot.path, str(meta_cas.get("root")))
    )
    try:
        out["index"] = cas_mod.ChunkIndex.load(store).rollup()
    except Exception as e:  # noqa: BLE001 — index unreachable/corrupt:
        # the per-snapshot numbers above still stand
        out["index_error"] = f"{e!r}"[:200]
    finally:
        store.sync_close()
    return out


def _cache_stats_rollup() -> dict:
    """Shared-host object cache rollup (storage/hostcache.py): the
    cache directory's on-disk footprint plus this process's hit/miss
    counters (with the cache enabled, even the stats command's own
    manifest read routes through it)."""
    from . import knobs, obs

    out: dict = {}
    cache_dir = knobs.get_cache_dir()
    if cache_dir:
        from .storage.hostcache import _OBJECTS_SUBDIR

        files = 0
        total = 0
        for dirpath, _dirs, names in os.walk(
            os.path.join(cache_dir, _OBJECTS_SUBDIR)
        ):
            for name in names:
                try:
                    total += os.path.getsize(os.path.join(dirpath, name))
                    files += 1
                except OSError:
                    pass  # racing eviction by another process
        out.update({"dir": cache_dir, "objects": files, "bytes": total})
    c = obs.metrics_snapshot()["counters"]
    for key, short in (
        (obs.CACHE_HITS, "hits"),
        (obs.CACHE_MISSES, "misses"),
        (obs.CACHE_SINGLEFLIGHT_WAITS, "singleflight_waits"),
        (obs.MMAP_READS, "mmap_reads"),
    ):
        if c.get(key):
            out[short] = c[key]
    return out


def _render_cache_stats(rollup: dict) -> None:
    if not rollup:
        return
    if "dir" in rollup:
        print(
            f"  cache: {rollup['objects']} objects, "
            f"{_human(rollup['bytes'])} at {rollup['dir']}"
        )
    if rollup.get("hits") or rollup.get("misses"):
        print(
            f"    this run: {rollup.get('hits', 0)} hits / "
            f"{rollup.get('misses', 0)} misses, "
            f"{rollup.get('singleflight_waits', 0)} singleflight waits, "
            f"{rollup.get('mmap_reads', 0)} mmap reads"
        )


def _render_cas_stats(rollup: dict) -> None:
    if not rollup:
        return
    print(
        f"  cas: {rollup['chunked_objects']} chunked objects, "
        f"{_human(rollup['chunked_bytes'])} logical -> "
        f"{rollup['distinct_chunks']} chunks, "
        f"{_human(rollup['distinct_chunk_bytes'])} distinct "
        f"(pool: {rollup.get('root')})"
    )
    idx = rollup.get("index")
    if not idx:
        if rollup.get("index_error"):
            print(f"    index unreadable: {rollup['index_error']}")
        return
    print(
        f"    pool: {idx['live_chunks']} live "
        f"({_human(idx['live_bytes'])}), {idx['orphaned_chunks']} "
        f"orphaned ({_human(idx['orphaned_bytes'])})"
    )
    hist = ", ".join(
        f"{n} ref{'s' if n != '1' else ''}: {c}"
        for n, c in idx["refcount_histogram"].items()
    )
    if hist:
        print(f"    refcounts: {hist}")
    for step, st in idx["per_step"].items():
        print(
            f"    {step}: {_human(st['new_bytes'])} new + "
            f"{_human(st['shared_bytes'])} shared"
        )


def _topology_stats_rollup(path: str) -> dict:
    """Topology rollup rows for ``stats``, sourced from the snapshot's
    persisted flight record (the manifest itself is placement-agnostic
    by design — one writer per replicated object, whoever it was).
    ``{}`` when no record exists or it predates topology rollups."""
    from .obs import aggregate

    try:
        return aggregate.read_obsrecord(path).get("topology") or {}
    except (FileNotFoundError, RuntimeError):
        # no record (pre-obsrecord snapshot / failed best-effort write)
        # or a corrupt one — stats still stands on the manifest alone
        return {}


def _continuous_store_rollup(root: str) -> Optional[dict]:
    """One continuous store's residency rollup, or None when ``root``
    is not a continuous store (no decodable continuous HEAD).  Local
    roots only: continuous stores live on host RAM/disk (and their
    durable mirrors are operator-known paths); probing every REMOTE
    stats target would add a full metadata GET to ordinary cloud
    snapshot stats."""
    import os

    from .continuous import ContinuousStore

    if "://" in root and not root.startswith("file://"):
        return None
    # cheap structural sniff before any read: every continuous store
    # has a steps/ directory; an ordinary snapshot never does — this
    # keeps stats on a plain snapshot from reading (and then
    # re-reading) its whole metadata file just to rule continuous out
    probe_base = root.split("://", 1)[-1]
    if not os.path.isdir(os.path.join(probe_base, "steps")):
        return None
    store = ContinuousStore(root)
    try:
        try:
            head = store.read_head()
        except Exception:  # noqa: BLE001 — not a continuous store (a
            # snapshot marker or garbage lands here); the caller falls
            # through to the snapshot stats path
            return None
        if head is None:
            return None
        out: dict = {"root": root, "head_step": int(head["step"])}
        try:
            manifest = store.read_step_manifest(str(head["manifest"]))
            keys = {
                k
                for rec in manifest["leaves"].values()
                for k in rec["keys"]
            }
            from .cas.store import key_size

            out["leaves"] = len(manifest["leaves"])
            out["head_chunks"] = len(keys)
            out["head_bytes"] = sum(key_size(k) for k in keys)
            out["chunk_size"] = int(manifest["chunk_size"])
        except Exception as e:  # noqa: BLE001 — torn mid-prune store:
            # report the HEAD we could verify rather than failing stats
            out["manifest_error"] = f"{e!r}"[:200]
        # probe_base established above (local fs with a steps/ dir)
        base = probe_base
        if os.path.isdir(os.path.join(base, "steps")):
            out["steps_resident"] = sorted(
                int(n.split(".")[0])
                for n in os.listdir(os.path.join(base, "steps"))
                if n.endswith(".json") and n.split(".")[0].isdigit()
            )
            pool_bytes = 0
            pool_chunks = 0
            # the pool shares the CAS layout: objects/<kk>/<key>
            chunks_dir = os.path.join(base, "objects")
            for dirpath, _dirs, files in os.walk(chunks_dir):
                for f in files:
                    try:
                        pool_bytes += os.path.getsize(
                            os.path.join(dirpath, f)
                        )
                        pool_chunks += 1
                    except OSError:
                        pass  # racing the live loop's chunk pruning
            out["pool_chunks"] = pool_chunks
            out["pool_bytes"] = pool_bytes
        return out
    finally:
        store.sync_close()


def _publish_stats(path: str) -> Optional[dict]:
    """Stats rollup for a live-weight publication root (publish/):
    published HEAD, the last update's delta cost, and per-subscriber
    lag from the fleet's stamp files.  None when ``path`` isn't a
    publication root (the continuous/snapshot stats paths take over)."""
    from .publish import root_rollup

    return root_rollup(path)


def _render_publish_stats(roll: dict) -> None:
    print(f"{roll['root']}  [publication root]")
    line = f"  published step {roll['step']}"
    if roll.get("source"):
        line += f" (source: {roll['source']}, {roll.get('leaves', 0)} leaves)"
    print(line)
    if roll.get("record_error"):
        print(f"  WARNING: record unreadable: {roll['record_error']}")
    stats = roll.get("stats") or {}
    if stats.get("bytes_total"):
        ratio = stats.get("bytes_delta", 0) / stats["bytes_total"]
        print(
            f"  last update: {_human(stats.get('bytes_delta', 0))} delta "
            f"of {_human(stats['bytes_total'])} total "
            f"({ratio:.1%}; {stats.get('chunks_delta', 0)}/"
            f"{stats.get('chunks_total', 0)} chunks)"
        )
    subs = roll.get("subscribers") or []
    if not subs:
        print("  subscribers: (no stamps)")
        return
    print(f"  subscribers: {len(subs)}")
    for s in subs:
        if s.get("malformed"):
            print(f"    {s['id']}: MALFORMED stamp")
            continue
        print(
            f"    {s['id']}: step {s['step']} "
            f"(lag {s['lag_steps']} steps, stamped {s['age_s']:.1f}s "
            f"ago, gen {s['generation']}, "
            f"{_human(s['bytes_fetched'])} fetched)"
        )


def _continuous_stats(path: str) -> Optional[dict]:
    """Stats rollup for a continuous root: either one store, or a host
    root holding per-rank ``r<k>`` stores.  None when ``path`` is
    neither (the snapshot stats path takes over)."""
    import os
    import re

    one = _continuous_store_rollup(path)
    if one is not None:
        return {"path": path, "stores": {"": one}}
    base = path.split("://", 1)[-1]
    if "://" in path and not path.startswith("file://"):
        return None
    if not os.path.isdir(base):
        return None
    stores = {}
    for name in sorted(os.listdir(base)):
        if re.fullmatch(r"r\d+", name):
            roll = _continuous_store_rollup(os.path.join(base, name))
            if roll is not None:
                stores[name] = roll
    if not stores:
        return None
    return {"path": path, "stores": stores}


def _render_continuous_stats(stats: dict) -> None:
    print(f"{stats['path']}  [continuous store]")
    for name, st in stats["stores"].items():
        label = f"  {name or '.'}: "
        line = f"{label}head step {st.get('head_step')}"
        if "head_chunks" in st:
            line += (
                f", {st['leaves']} leaves, {st['head_chunks']} chunks "
                f"({_human(st['head_bytes'])}) at "
                f"{_human(st.get('chunk_size', 0))} granularity"
            )
        print(line)
        if "steps_resident" in st:
            print(
                f"    steps resident: {st['steps_resident']}, pool "
                f"{st.get('pool_chunks', 0)} chunks "
                f"({_human(st.get('pool_bytes', 0))})"
            )
        if st.get("manifest_error"):
            print(f"    WARNING: manifest unreadable: {st['manifest_error']}")


def _cmd_stats(args) -> int:
    """Per-entry size/dtype/chunk rollups from the manifest (the
    operator's "where did my bytes go" view; machine-readable with
    --json for dashboards).  Continuous-store roots (continuous/) get a
    residency rollup instead: head step, chunk pool footprint, steps
    resident — per rank when pointed at a host root."""
    from .manifest import is_container_entry
    from .snapshot import Snapshot

    pubroll = _publish_stats(args.path)
    if pubroll is not None:
        if args.json:
            print(json.dumps(pubroll, indent=2))
        else:
            _render_publish_stats(pubroll)
        return 0
    cont = _continuous_stats(args.path)
    if cont is not None:
        if args.json:
            print(json.dumps(cont, indent=2))
        else:
            _render_continuous_stats(cont)
        return 0
    snap = Snapshot(args.path)
    metadata = snap.metadata
    entries = {
        p: _entry_stats(e)
        for p, e in metadata.manifest.items()
        if not is_container_entry(e)
    }
    by_dtype: dict = {}
    by_kind: dict = {}
    total = 0
    pieces = 0
    for st in entries.values():
        total += st["nbytes"]
        pieces += st["pieces"]
        d = by_dtype.setdefault(st["dtype"] or "(none)",
                                {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += st["nbytes"]
        k = by_kind.setdefault(st["kind"], {"count": 0, "bytes": 0})
        k["count"] += 1
        k["bytes"] += st["nbytes"]
    largest = sorted(
        entries.items(), key=lambda kv: kv[1]["nbytes"], reverse=True
    )[: args.top]
    stats = {
        "path": args.path,
        "world_size": metadata.world_size,
        "entries": len(entries),
        "total_bytes": total,
        "pieces": pieces,
        "by_kind": by_kind,
        "by_dtype": by_dtype,
        "largest": [
            {"path": p, **st} for p, st in largest
        ],
        "codec": _codec_rollup(metadata),
        "cas": _cas_stats_rollup(snap),
        "cache": _cache_stats_rollup(),
        "topology": _topology_stats_rollup(args.path),
        "degraded": {
            p: d.get("origin_rank")
            for p, d in sorted(
                (getattr(metadata, "degraded", None) or {}).items()
            )
        },
    }
    if args.json:
        print(json.dumps(stats, indent=2))
        return 0
    print(f"{args.path}")
    print(
        f"  {len(entries)} entries, {pieces} shard/chunk pieces, "
        f"{_human(total)} total, world_size={metadata.world_size}"
    )
    print("  by kind:")
    for kind, st in sorted(by_kind.items(), key=lambda kv: -kv[1]["bytes"]):
        print(f"    {kind:<14} {st['count']:>6}  {_human(st['bytes'])}")
    print("  by dtype:")
    for dt, st in sorted(by_dtype.items(), key=lambda kv: -kv[1]["bytes"]):
        print(f"    {dt:<14} {st['count']:>6}  {_human(st['bytes'])}")
    rollup = stats["codec"]
    if rollup["by_codec"]:
        ratio = rollup["ratio"]
        print(
            f"  codec: {_human(rollup['raw_bytes'])} raw -> "
            f"{_human(rollup['stored_bytes'])} stored"
            + (f" ({ratio:.2f}x)" if ratio else "")
        )
        for name, st in sorted(
            rollup["by_codec"].items(), key=lambda kv: -kv[1]["raw_bytes"]
        ):
            r = (
                st["raw_bytes"] / st["stored_bytes"]
                if st["stored_bytes"]
                else 0.0
            )
            print(
                f"    {name:<14} {st['objects']:>6}  "
                f"{_human(st['raw_bytes'])} -> "
                f"{_human(st['stored_bytes'])} ({r:.2f}x)"
            )
    _render_cas_stats(stats["cas"])
    _render_cache_stats(stats["cache"])
    _render_topology_rollup(stats["topology"])
    if stats["degraded"]:
        print(
            f"  DEGRADED: {len(stats['degraded'])} path(s) lost to rank "
            "death (re-take or `SnapshotManager.repair()` to heal):"
        )
        for p, origin in stats["degraded"].items():
            print(f"    {p}  (origin rank {origin})")
    print(f"  largest {len(largest)}:")
    width = max((len(p) for p, _ in largest), default=10)
    for p, st in largest:
        detail = (
            f"{st['dtype']}{st['shape']}" if st["dtype"] else st["kind"]
        )
        pieces_s = f" x{st['pieces']}" if st["pieces"] > 1 else ""
        print(
            f"    {p:<{width}}  {detail:<28} "
            f"{_human(st['nbytes'])}{pieces_s}"
        )
    return 0


def _doctor_phase_rows(record) -> list:
    """(rank, phase, seconds) rows from a record's per-rank rollups,
    slowest rank first."""
    rows = []
    for rank, pr in sorted(
        (record.get("per_rank") or {}).items(), key=lambda kv: int(kv[0])
    ):
        phases = pr.get("phases") or {}
        total = sum(p.get("seconds", 0.0) for p in phases.values())
        rows.append((int(rank), phases, total))
    rows.sort(key=lambda r: -r[2])
    return rows


def _doctor_counters(record) -> dict:
    """The incident-review counters a doctor run leads with."""
    c = (record.get("merged") or {}).get("counters") or {}

    def grab(prefix):
        return {
            k[len(prefix):]: v for k, v in c.items() if k.startswith(prefix)
        }

    codec_in = c.get("storage.codec.bytes_in", 0)
    codec_out = c.get("storage.codec.bytes_out", 0)
    cas_written = c.get("cas.bytes_written", 0)
    cas_shared = c.get("cas.bytes_shared", 0)
    return {
        "cas_bytes_written": cas_written,
        "cas_bytes_shared": cas_shared,
        "cas_dedup_ratio": (
            round((cas_written + cas_shared) / cas_written, 3)
            if cas_written
            else None
        ),
        "bytes_staged": c.get("bytes_staged", 0),
        "bytes_written": c.get("bytes_written", 0),
        "bytes_read": c.get("bytes_read", 0),
        "retries": c.get("resilience.retries", 0),
        "retries_by_backend": {
            k.split(".")[0]: v
            for k, v in grab("resilience.").items()
            if k.endswith(".retries")
        },
        "breaker_trips": c.get("resilience.breaker_trips", 0),
        "aborts": c.get("resilience.aborts", 0),
        "failpoints_fired": c.get("resilience.failpoints_fired", 0),
        "stripe_parts_written": c.get("storage.stripe.parts_written", 0),
        "stripe_aborts": c.get("storage.stripe.aborts", 0),
        "cache_hits": c.get("storage.cache.hits", 0),
        "cache_misses": c.get("storage.cache.misses", 0),
        "cache_singleflight_waits": c.get(
            "storage.cache.singleflight_waits", 0
        ),
        "mmap_reads": c.get("storage.mmap.reads", 0),
        "fanout_durable_reads": c.get("topology.fanout_durable_reads", 0),
        "fanout_gets_saved": c.get("topology.durable_gets_saved", 0),
        "fanout_bytes_redistributed": c.get(
            "topology.fanout_bytes_redistributed", 0
        ),
        "fanout_fallbacks": c.get("topology.fanout_fallbacks", 0),
        "codec_bytes_in": codec_in,
        "codec_bytes_out": codec_out,
        "codec_ratio": (
            round(codec_in / codec_out, 3) if codec_out else None
        ),
        "continuous_steps": c.get("continuous.steps", 0),
        "continuous_bytes_replicated": c.get(
            "continuous.bytes_replicated", 0
        ),
        "continuous_bytes_skipped": c.get("continuous.bytes_skipped", 0),
        "continuous_replication_errors": c.get(
            "continuous.replication_errors", 0
        ),
        "continuous_preemption_drains": c.get(
            "continuous.preemption_drains", 0
        ),
        "publish_records": c.get("publish.records", 0),
        "publish_bytes_delta": c.get("publish.bytes_delta", 0),
        "publish_sub_swaps": c.get("publish.subscriber_swaps", 0),
        "publish_sub_bytes_fetched": c.get(
            "publish.subscriber_bytes_fetched", 0
        ),
        "publish_fallback_polls": c.get("publish.fallback_polls", 0),
        "publish_watch_errors": c.get("publish.watch_errors", 0),
        "publish_announce_failures": c.get(
            "publish.announce_failures", 0
        ),
        "exceptions_swallowed": c.get("exceptions.swallowed", 0),
        "liveness_heartbeats": c.get("liveness.heartbeats", 0),
        "dead_ranks_observed": c.get("liveness.dead_ranks", 0),
        "takeover_objects": c.get("takeover.objects", 0),
        "takeover_bytes": c.get("takeover.bytes", 0),
        "degraded_commits": c.get("takeover.degraded_commits", 0),
        "takeover_paths_repaired": c.get("takeover.paths_repaired", 0),
        "promoter_dead_peers": c.get("takeover.promoter_dead_peers", 0),
    }


def _render_continuous_rollup(cont, counters=None) -> None:
    """Preemption-readiness rows from a flight record's continuous
    rollup: per-rank replica residency (last trained vs last-peer vs
    last-durable step), the fleet floors, and the per-step replication
    economics.  Silent for records with no continuous loop."""
    c = counters or {}
    if not cont:
        return
    floor_peer = cont.get("last_peer_step_floor")
    floor_dur = cont.get("last_durable_step_floor")
    lag = cont.get("max_replication_lag_steps")
    print(
        "  continuous: peer-step floor "
        f"{floor_peer if floor_peer is not None else '-'}, "
        f"durable-step floor {floor_dur if floor_dur is not None else '-'}"
        + (f", max replication lag {lag} step(s)" if lag is not None else "")
    )
    for rank, row in sorted(
        (cont.get("by_rank") or {}).items(), key=lambda kv: int(kv[0])
    ):
        print(
            f"    rank {rank}: step {row.get('last_step')}"
            f" | peers hold {row.get('last_peer_step')}"
            f" ({row.get('peer_targets', 0)} target(s))"
            f" | durable {row.get('last_durable_step')}"
        )
    if c.get("continuous_bytes_replicated") or c.get(
        "continuous_bytes_skipped"
    ):
        rep = c.get("continuous_bytes_replicated", 0)
        skip = c.get("continuous_bytes_skipped", 0)
        total = rep + skip
        print(
            f"    delta economics: {_human(rep)} replicated, "
            f"{_human(skip)} skipped"
            + (f" ({skip / total:.0%} unchanged)" if total else "")
        )
    if c.get("continuous_replication_errors"):
        print(
            f"    WARNING: {c['continuous_replication_errors']} "
            "replication error(s) — affected targets held their "
            "previous step (degraded, not torn)"
        )


def _render_topology_rollup(topo, counters=None) -> None:
    """Multislice rows from a flight record's topology rollup: slices,
    ranks per slice, write egress per slice, fan-out savings.  Silent
    for flat single-slice records with no topology activity."""
    c = counters or {}
    if not topo:
        return
    rows = (topo.get("slices") or {}).items()
    active = topo.get("num_slices", 1) > 1 or any(
        st.get("replicated_objects_written")
        or st.get("durable_gets_saved")
        or st.get("fanout_fallbacks")
        for _s, st in rows
    )
    if not active:
        return
    print(f"  topology: {topo.get('num_slices', 1)} slice(s)")
    for s, st in rows:
        parts = [f"ranks {st.get('ranks', [])}"]
        if st.get("replicated_objects_written"):
            parts.append(
                f"{st['replicated_objects_written']} replicated objects "
                f"written ({_human(st.get('replicated_bytes_written', 0))})"
            )
        if st.get("durable_reads") or st.get("durable_gets_saved"):
            parts.append(
                f"{st.get('durable_reads', 0)} durable GETs, "
                f"{st.get('durable_gets_saved', 0)} saved "
                f"({_human(st.get('bytes_redistributed', 0))} "
                f"redistributed)"
            )
        if st.get("fanout_fallbacks"):
            parts.append(f"{st['fanout_fallbacks']} fan-out fallbacks")
        print(f"    slice {s}: " + ", ".join(parts))
    if c.get("fanout_fallbacks"):
        print(
            "    note: fallbacks mean siblings re-read directly (dead/"
            "slow designated reader or digest mismatch) — degraded, "
            "not wedged"
        )


def _render_doctor(record) -> None:
    print(
        f"{record.get('path')}  [{record.get('op')}]  "
        f"world_size={record.get('world_size')}"
    )
    missing = record.get("missing_ranks") or []
    print(
        f"  ranks reported: {record.get('ranks_reported')}"
        + (f"  MISSING: {missing}" if missing else "")
    )
    gp = record.get("goodput") or {}
    parts = []
    for label, key in (
        ("unblock", "time_to_unblock_s"),
        ("durable-lag", "durability_lag_s"),
        ("overhead", "overhead_fraction"),
    ):
        v = gp.get(key)
        if v is not None:
            parts.append(
                f"{label} {v:.3f}s" if "fraction" not in key
                else f"{label} {v:.1%}"
            )
    if parts:
        print("  goodput: " + ", ".join(parts))
    straggler = record.get("straggler")
    if straggler:
        print(
            f"  straggler: rank {straggler['rank']} "
            f"({straggler['phase']} phase, "
            f"{straggler['seconds']:.3f}s; "
            f"+{straggler.get('lead_over_peers_s', 0.0):.3f}s over peers)"
        )
    rows = _doctor_phase_rows(record)
    if rows:
        phases = sorted({p for _, ph, _ in rows for p in ph})
        hdr = "  ".join(f"{p:>10}" for p in phases)
        print(f"  {'rank':>6}  {hdr}  {'total':>10}")
        for rank, ph, total in rows:
            cells = "  ".join(
                f"{ph.get(p, {}).get('seconds', 0.0):>10.3f}"
                for p in phases
            )
            print(f"  {rank:>6}  {cells}  {total:>10.3f}")
    c = _doctor_counters(record)
    print(
        f"  io: {_human(c['bytes_staged'])} staged, "
        f"{_human(c['bytes_written'])} written, "
        f"{_human(c['bytes_read'])} read"
    )
    health = (
        f"  health: {c['retries']} retries, "
        f"{c['breaker_trips']} breaker trips, {c['aborts']} aborts, "
        f"{c['exceptions_swallowed']} swallowed"
    )
    if c["retries_by_backend"]:
        health += f" (by backend: {c['retries_by_backend']})"
    print(health)
    if c["stripe_parts_written"] or c["stripe_aborts"]:
        print(
            f"  stripe: {c['stripe_parts_written']} parts written, "
            f"{c['stripe_aborts']} aborts"
        )
    if c["codec_ratio"]:
        print(
            f"  codec: {_human(c['codec_bytes_in'])} raw -> "
            f"{_human(c['codec_bytes_out'])} stored "
            f"({c['codec_ratio']:.2f}x)"
        )
    if c["cas_bytes_written"] or c["cas_bytes_shared"]:
        ratio = c["cas_dedup_ratio"]
        print(
            f"  cas: {_human(c['cas_bytes_written'])} new + "
            f"{_human(c['cas_bytes_shared'])} shared"
            + (f" ({ratio:.2f}x dedup)" if ratio else "")
        )
    if c["cache_hits"] or c["cache_misses"]:
        served = c["cache_hits"] + c["cache_misses"]
        hit_rate = c["cache_hits"] / served if served else 0.0
        print(
            f"  cache: {c['cache_hits']} hits / {c['cache_misses']} "
            f"misses ({hit_rate:.0%} hit rate), "
            f"{c['cache_singleflight_waits']} singleflight waits"
        )
    if c["mmap_reads"]:
        print(f"  mmap: {c['mmap_reads']} zero-copy reads")
    if (
        c["dead_ranks_observed"]
        or c["takeover_objects"]
        or c["degraded_commits"]
        or c["promoter_dead_peers"]
        or c["takeover_paths_repaired"]
    ):
        print(
            f"  liveness: {c['dead_ranks_observed']} rank death(s) "
            f"observed ({c['liveness_heartbeats']} heartbeats)"
        )
        parts = []
        if c["takeover_objects"]:
            parts.append(
                f"{c['takeover_objects']} objects re-written by "
                f"survivors ({_human(c['takeover_bytes'])})"
            )
        if c["degraded_commits"]:
            parts.append(f"{c['degraded_commits']} degraded commit(s)")
        if c["promoter_dead_peers"]:
            parts.append(
                f"{c['promoter_dead_peers']} dead peer(s) skipped "
                "during tier promotion"
            )
        if c["takeover_paths_repaired"]:
            parts.append(f"{c['takeover_paths_repaired']} path(s) repaired")
        if parts:
            print("  takeover: " + ", ".join(parts))
    if c["publish_records"] or c["publish_sub_swaps"]:
        line = (
            f"  publish: {c['publish_records']} records "
            f"({_human(c['publish_bytes_delta'])} delta), "
            f"{c['publish_sub_swaps']} subscriber swaps "
            f"({_human(c['publish_sub_bytes_fetched'])} fetched)"
        )
        trouble = []
        if c["publish_fallback_polls"]:
            trouble.append(f"{c['publish_fallback_polls']} fallback polls")
        if c["publish_announce_failures"]:
            trouble.append(
                f"{c['publish_announce_failures']} announce failures"
            )
        if c["publish_watch_errors"]:
            trouble.append(f"{c['publish_watch_errors']} watch errors")
        if trouble:
            line += " — " + ", ".join(trouble)
        print(line)
    _render_topology_rollup(record.get("topology"), c)
    _render_continuous_rollup(record.get("continuous"), c)
    slow = record.get("slow_objects") or []
    if slow:
        print("  slowest objects:")
        for o in slow[:5]:
            size = f" {_human(o['bytes'])}" if o.get("bytes") else ""
            print(
                f"    {o['path']}  [{o['phase']}]  "
                f"{o['seconds']:.3f}s{size}"
            )
    else:
        print(
            "  slowest objects: (none recorded — run the take under "
            "TORCHSNAPSHOT_TPU_TRACE=1 for object-level attribution)"
        )


def _doctor_diff(a, b) -> dict:
    """Step-over-step comparison of two flight records: per-phase and
    headline-counter deltas (b minus a)."""

    def phase_totals(rec):
        out = {}
        for _, ph, _ in _doctor_phase_rows(rec):
            for p, v in ph.items():
                out[p] = out.get(p, 0.0) + v.get("seconds", 0.0)
        return out

    pa, pb = phase_totals(a), phase_totals(b)
    ca, cb = _doctor_counters(a), _doctor_counters(b)
    numeric = [
        k for k in ca
        if isinstance(ca.get(k), (int, float))
        and isinstance(cb.get(k), (int, float))
    ]
    return {
        "a": {"path": a.get("path"), "op": a.get("op")},
        "b": {"path": b.get("path"), "op": b.get("op")},
        "phases": {
            p: {
                "a_s": round(pa.get(p, 0.0), 6),
                "b_s": round(pb.get(p, 0.0), 6),
                "delta_s": round(pb.get(p, 0.0) - pa.get(p, 0.0), 6),
            }
            for p in sorted(set(pa) | set(pb))
        },
        "counters": {
            k: {"a": ca[k], "b": cb[k], "delta": cb[k] - ca[k]}
            for k in numeric
        },
        "straggler": {"a": a.get("straggler"), "b": b.get("straggler")},
        "goodput": {"a": a.get("goodput"), "b": b.get("goodput")},
    }


def _cmd_doctor(args) -> int:
    """Render a snapshot's persisted flight record (.snapshot_obsrecord):
    who was slow, in which phase, what the retry/breaker/codec layers
    did — the post-hoc "why was step N slow, and on which rank?" answer
    without a re-run.  --diff compares two records step-over-step."""
    from .obs import aggregate

    record = aggregate.read_obsrecord(args.path)
    if args.diff:
        diff = _doctor_diff(record, aggregate.read_obsrecord(args.diff))
        if args.json:
            print(json.dumps(diff, indent=2))
            return 0
        print(f"diff: {args.path} -> {args.diff}")
        print(f"  {'phase':>10}  {'a':>10}  {'b':>10}  {'delta':>10}")
        for p, d in diff["phases"].items():
            print(
                f"  {p:>10}  {d['a_s']:>10.3f}  {d['b_s']:>10.3f}  "
                f"{d['delta_s']:>+10.3f}"
            )
        for k, d in diff["counters"].items():
            if d["delta"]:
                print(f"  {k}: {d['a']} -> {d['b']} ({d['delta']:+})")
        return 0
    if args.json:
        print(json.dumps(record, indent=2))
        return 0
    _render_doctor(record)
    return 0


def _cmd_trace(args) -> int:
    """Traced read of a snapshot: materialize every entry with span
    tracing enabled and write the Perfetto trace_event JSON — open it at
    https://ui.perfetto.dev.  (Write-path traces come from running a
    take with TORCHSNAPSHOT_TPU_TRACE=1 and calling obs.write_trace.)"""
    from . import knobs, obs
    from .snapshot import Snapshot

    out = args.out or "trace.json"
    with knobs.override_trace(1):
        obs.get_tracer().reset()
        Snapshot(args.path).materialize(rank=args.rank)
        n = obs.write_trace(out)
    print(f"wrote {n} spans to {out}")
    return 0


def _cmd_manifest(args) -> int:
    from .snapshot import Snapshot

    print(
        json.dumps(
            json.loads(Snapshot(args.path).metadata.to_json()), indent=2
        )
    )
    return 0


def _cmd_verify(args) -> int:
    from .snapshot import Snapshot
    from .verify import verify_snapshot

    res = verify_snapshot(
        Snapshot(args.path), deep=args.deep, rank=args.rank
    )
    print(str(res))
    return 0 if res.ok else 1


def _cmd_steps(args) -> int:
    from .manager import SnapshotManager

    mgr = SnapshotManager(args.root)
    steps = mgr.steps()
    for step in steps:
        print(f"{step}\t{mgr.path_for_step(step)}")
    if not steps:
        print("(no committed snapshots)", file=sys.stderr)
    return 0


def _cmd_tiers(args) -> int:
    """Per-step tier residency + durability for a tiered manager root:
    which steps are fast-resident, which are durably committed, and how
    many of each step's data objects each tier actually holds (a
    write-back step mid-promotion shows partial durable residency)."""
    from .manager import SnapshotManager, entry_locations
    from .snapshot import Snapshot
    from .storage import url_to_storage_plugin

    mgr = SnapshotManager(args.root, tier={"fast_root": args.fast})

    def _residency(storage_root, locations):
        """(present, bytes) across ``locations`` under ``storage_root``."""
        storage = url_to_storage_plugin(storage_root)
        present = 0
        nbytes = 0
        try:
            for loc in locations:
                try:
                    nbytes += storage.sync_stat(loc)
                    present += 1
                except Exception:  # noqa: BLE001 — absent either way
                    continue
        finally:
            storage.sync_close()
        return present, nbytes

    rows = []
    candidates = sorted(
        set(mgr._read_index()) | set(mgr._scan_fs())
    )
    for step in candidates:
        durable_path = mgr.path_for_step(step)
        fast_path = mgr.fast_path_for_step(step)
        metadata = None
        durable_committed = False
        fast_committed = False
        try:
            metadata = Snapshot(durable_path).metadata
            durable_committed = True
        except Exception:  # noqa: BLE001
            pass
        try:
            fast_metadata = Snapshot(fast_path).metadata
            fast_committed = True
            metadata = metadata or fast_metadata
        except Exception:  # noqa: BLE001
            pass
        # chunk-ref'd locations (cas/) are pool residents, not per-step
        # objects — counting them as missing would misreport every
        # CAS-backed step as partially resident
        locations = (
            [
                loc
                for loc in entry_locations(metadata.manifest)
                if loc not in ((metadata.cas or {}).get("chunks") or {})
            ]
            if metadata
            else []
        )
        fast_n, fast_b = _residency(fast_path, locations)
        dur_n, dur_b = _residency(durable_path, locations)
        status = (
            "durable+fast" if durable_committed and fast_n
            else "durable" if durable_committed
            else "promoting" if fast_committed
            else "aborted"
        )
        rows.append(
            {
                "step": step,
                "status": status,
                "durable_committed": durable_committed,
                "fast_committed": fast_committed,
                "objects": len(locations),
                "fast_objects": fast_n,
                "fast_bytes": fast_b,
                "durable_objects": dur_n,
                "durable_bytes": dur_b,
            }
        )
    if args.json:
        print(
            json.dumps(
                {"root": args.root, "fast_root": args.fast, "steps": rows},
                indent=2,
            )
        )
        return 0
    if not rows:
        print("(no snapshots found)", file=sys.stderr)
        return 0
    print(f"{'step':>10}  {'status':<13} {'fast':>14}  {'durable':>14}")
    for r in rows:
        fast_s = f"{r['fast_objects']}/{r['objects']} {_human(r['fast_bytes'])}"
        dur_s = (
            f"{r['durable_objects']}/{r['objects']} "
            f"{_human(r['durable_bytes'])}"
        )
        print(
            f"{r['step']:>10}  {r['status']:<13} {fast_s:>14}  {dur_s:>14}"
        )
    return 0


def _cmd_convert(args) -> int:
    """Re-encode a reference-format snapshot as a native one (or the
    reverse with --to-reference): one command migrates a whole
    checkpoint without writing any code.

    Materializes one rank's fully-assembled view in host memory (for a
    larger-than-RAM checkpoint, migrate programmatically per subtree).
    Multi-rank snapshots must name the rank explicitly: other ranks'
    private per-rank state is NOT part of a one-rank view, and silently
    dropping it would corrupt a migration."""
    from .snapshot import Snapshot
    from .stateful import PyTreeState
    from .tricks import read_torchsnapshot, write_torchsnapshot
    from .tricks.torchsnapshot_reader import peek_torchsnapshot

    def _require_rank(world_size: int) -> int:
        if world_size > 1 and args.rank is None:
            raise RuntimeError(
                f"snapshot was taken with world_size={world_size}; convert "
                f"materializes ONE rank's view, so other ranks' private "
                f"per-rank state would be dropped. Pass --rank N to "
                f"convert rank N's view deliberately (replicated and "
                f"sharded state is complete in any rank's view)."
            )
        rank = args.rank or 0
        if not 0 <= rank < world_size:
            # an out-of-range rank would take the elastic grown-world
            # view (replicated/sharded only) and silently drop per-rank
            # state — the exact hole the rank gate exists to close
            raise RuntimeError(
                f"--rank {rank} is out of range for world_size={world_size} "
                f"(valid: 0..{world_size - 1})"
            )
        return rank

    if args.to_reference:
        snap = Snapshot(args.src)
        rank = _require_rank(snap.metadata.world_size)
        write_torchsnapshot(args.dest, snap.materialize(rank=rank))
        print(f"exported {args.src} -> {args.dest} (reference format)")
        return 0

    metadata = peek_torchsnapshot(args.src)
    rank = _require_rank(int(metadata.get("world_size", 1)))
    state = read_torchsnapshot(args.src, rank=rank, metadata=metadata)
    Snapshot.take(
        args.dest, {k: PyTreeState(v) for k, v in state.items()}
    )
    print(f"imported {args.src} -> {args.dest} (native format)")
    return 0


def _cmd_lint(args) -> int:
    """Run the snaplint static-analysis suite (tools/lint) over the
    repo checkout this package is running from; ``args`` is the raw
    argv tail forwarded to ``tools.lint.main``.  The lint framework is
    repo tooling, not part of the installed package — from a pip
    install there is no checkout to scan, and this explains that
    instead of ImportError-ing."""
    import os

    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    if not os.path.isdir(os.path.join(repo_root, "tools", "lint")):
        # genuinely no checkout (pip install): explain instead of
        # ImportError-ing.  When the directory EXISTS, import errors
        # propagate with their real traceback — a broken pass module
        # must not masquerade as "no checkout"
        print(
            "error: the lint suite (tools/lint) is repo tooling and "
            "needs a checkout — run from the repository root, or "
            "`python -m tools.lint` there",
            file=sys.stderr,
        )
        return 2
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from tools.lint import main as lint_main

    return lint_main(list(args))


def _cmd_cas(args) -> int:
    """Operate on a chunk pool directly: index rollup (default),
    ``--fsck`` rebuild from committed manifests, ``--gc`` mark+sweep.
    ``root`` is the CAS root itself (``<manager-root>/cas``)."""
    from . import cas as cas_mod

    out: dict = {"root": args.root}
    if args.fsck:
        out["fsck"] = cas_mod.fsck(args.root)
    if args.gc:
        out["gc"] = cas_mod.run_gc(args.root, grace_s=args.grace)
    store = cas_mod.ChunkStore(args.root)
    try:
        out["index"] = cas_mod.ChunkIndex.load(store).rollup()
    except cas_mod.ChunkIndexCorruptError as e:
        out["index_error"] = str(e)
    finally:
        store.sync_close()
    if args.json:
        print(json.dumps(out, indent=2))
        return 0 if "index_error" not in out else 1
    if "index_error" in out:
        print(f"error: {out['index_error']} (run with --fsck to rebuild)",
              file=sys.stderr)
        return 1
    idx = out["index"]
    print(f"{args.root}")
    if out.get("fsck"):
        f = out["fsck"]
        print(
            f"  fsck: {f['snapshots_committed']} committed snapshots, "
            f"{f['chunks']} chunks, {f['orphans_marked']} orphans marked"
            + (
                f", {len(f['missing_chunks'])} MISSING"
                if f["missing_chunks"]
                else ""
            )
        )
    if out.get("gc"):
        g = out["gc"]
        print(
            f"  gc: {g['marked']} marked, {g['swept_chunks']} swept "
            f"({_human(g['swept_bytes'])})"
        )
    print(
        f"  {idx['live_chunks']} live chunks "
        f"({_human(idx['live_bytes'])}), {idx['orphaned_chunks']} "
        f"orphaned ({_human(idx['orphaned_bytes'])})"
    )
    hist = ", ".join(
        f"{n}: {cnt}" for n, cnt in idx["refcount_histogram"].items()
    )
    if hist:
        print(f"  refcount histogram: {hist}")
    for step, st in idx["per_step"].items():
        print(
            f"  {step}: {st['chunks']} chunks, "
            f"{_human(st['new_bytes'])} new + "
            f"{_human(st['shared_bytes'])} shared"
        )
    return 0


def _cmd_delete(args) -> int:
    from .manager import delete_snapshot

    if not args.yes:
        print("refusing to delete without --yes", file=sys.stderr)
        return 2
    delete_snapshot(args.path)
    print(f"deleted {args.path}")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "lint":
        # forwarded verbatim (argparse.REMAINDER can't capture a
        # leading option like `lint --json`, so the dispatch happens
        # before the parser)
        return _cmd_lint(argv[1:])
    parser = argparse.ArgumentParser(prog="python -m torchsnapshot_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("ls", help="list a snapshot's logical entries")
    p.add_argument("path")
    p.set_defaults(fn=_cmd_ls)

    p = sub.add_parser(
        "stats",
        help="size/dtype/chunk rollups from the manifest (no data reads)",
    )
    p.add_argument("path")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    p.add_argument("--top", type=int, default=10,
                   help="how many largest entries to list (default 10)")
    p.set_defaults(fn=_cmd_stats)

    p = sub.add_parser(
        "doctor",
        help="render a snapshot's flight record (.snapshot_obsrecord): "
        "straggler rank + phase, per-rank phase timings, retries, "
        "breaker trips, codec ratio, goodput",
    )
    p.add_argument("path")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    p.add_argument("--diff", default=None, metavar="OTHER",
                   help="compare against OTHER snapshot's record "
                   "(step-over-step)")
    p.set_defaults(fn=_cmd_doctor)

    p = sub.add_parser(
        "trace",
        help="read the whole snapshot with tracing on; write Perfetto "
        "trace_event JSON for ui.perfetto.dev",
    )
    p.add_argument("path")
    p.add_argument("--out", default=None,
                   help="output file (default ./trace.json)")
    p.add_argument("--rank", type=int, default=0)
    p.set_defaults(fn=_cmd_trace)

    p = sub.add_parser("manifest", help="dump snapshot metadata as JSON")
    p.add_argument("path")
    p.set_defaults(fn=_cmd_manifest)

    p = sub.add_parser("verify", help="integrity audit (exit 1 on failure)")
    p.add_argument("path")
    p.add_argument("--deep", action="store_true",
                   help="re-read payloads against recorded checksums")
    p.add_argument("--rank", type=int, default=0)
    p.set_defaults(fn=_cmd_verify)

    p = sub.add_parser("steps", help="list a manager root's committed steps")
    p.add_argument("root")
    p.set_defaults(fn=_cmd_steps)

    p = sub.add_parser(
        "tiers",
        help="per-step tier residency + durability for a tiered manager "
        "root (fast copies, promotion progress)",
    )
    p.add_argument("root", help="durable-tier manager root")
    p.add_argument("--fast", required=True, help="fast-tier root")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    p.set_defaults(fn=_cmd_tiers)

    p = sub.add_parser(
        "lint",
        help="run the snaplint static-analysis suite over this repo "
        "checkout (collective-safety, lock-discipline, "
        "exception-hygiene, knob-registry, retry-discipline, "
        "instrumentation); all "
        "arguments are forwarded to `python -m tools.lint` "
        "(e.g. --json, --list-passes, --pass exception-hygiene)",
    )
    # dispatch happens before the parser (see main's lint intercept);
    # this registration exists for `--help` discoverability
    p.set_defaults(fn=lambda _args: _cmd_lint([]))

    p = sub.add_parser(
        "cas",
        help="chunk-pool operations: index rollup (live/orphaned "
        "chunks, refcounts, per-step shared-vs-new), --fsck index "
        "rebuild, --gc mark+sweep",
    )
    p.add_argument("root", help="the CAS root (<manager-root>/cas)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    p.add_argument("--fsck", action="store_true",
                   help="rebuild the index from committed manifests")
    p.add_argument("--gc", action="store_true",
                   help="run the two-phase mark+sweep")
    p.add_argument("--grace", type=float, default=None,
                   help="override the GC grace window (seconds)")
    p.set_defaults(fn=_cmd_cas)

    p = sub.add_parser("delete", help="delete one snapshot (metadata-first)")
    p.add_argument("path")
    p.add_argument("--yes", action="store_true")
    p.set_defaults(fn=_cmd_delete)

    p = sub.add_parser(
        "convert",
        help="migrate a snapshot between the reference's format and the "
        "native one (default: reference -> native)",
    )
    p.add_argument("src")
    p.add_argument("dest")
    p.add_argument(
        "--to-reference",
        action="store_true",
        help="native -> reference format (for handing back to torch jobs)",
    )
    p.add_argument(
        "--rank",
        type=int,
        default=None,
        help="which rank's view to convert (required when world_size > 1; "
        "replicated/sharded state is complete in any rank's view, but "
        "other ranks' private per-rank state is not carried)",
    )
    p.set_defaults(fn=_cmd_convert)

    args = parser.parse_args(argv)
    try:
        return args.fn(args)
    except (FileNotFoundError, RuntimeError, ValueError) as e:
        # missing, corrupt/aborted, or unconvertible snapshots print one
        # clean line — diagnosing exactly these is what the operator ran
        # the tool for (ValueError: e.g. a dtype with no reference
        # equivalent during convert).  KeyError is deliberately NOT
        # caught: its message is just the key, so a genuine bug would
        # print an undiagnosable one-liner instead of a traceback.
        print(f"error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
