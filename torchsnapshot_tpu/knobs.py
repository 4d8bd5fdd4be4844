"""Tunable knobs with env-var overrides and context-manager test hooks.

TPU-native rebuild of the reference's config surface (torchsnapshot/knobs.py:23-132):
every constant is overridable via a ``TORCHSNAPSHOT_TPU_`` environment variable,
and the knobs that tests set have a context-manager override.
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Iterator, Optional

_logger = logging.getLogger(__name__)

_ENV_PREFIX = "TORCHSNAPSHOT_TPU_"

# Names (reference: torchsnapshot/knobs.py:23-38)
_MAX_CHUNK_SIZE_BYTES = "MAX_CHUNK_SIZE_BYTES"
_MAX_SHARD_SIZE_BYTES = "MAX_SHARD_SIZE_BYTES"
_SLAB_SIZE_THRESHOLD_BYTES = "SLAB_SIZE_THRESHOLD_BYTES"
_SLAB_HOST_MEMBER_MAX_BYTES = "SLAB_HOST_MEMBER_MAX_BYTES"
_MAX_PER_RANK_IO_CONCURRENCY = "MAX_PER_RANK_IO_CONCURRENCY"
_DISABLE_BATCHING = "DISABLE_BATCHING"
_PER_RANK_MEMORY_BUDGET_BYTES = "PER_RANK_MEMORY_BUDGET_BYTES"
_ALLOW_PICKLE_OBJECTS = "ALLOW_PICKLE_OBJECTS"
_STAGING_THREADS = "STAGING_THREADS"
_ENABLE_NATIVE_EXT = "ENABLE_NATIVE_EXT"
_FS_VERIFY_WRITES = "FS_VERIFY_WRITES"
_FS_SYNC_DATA = "FS_SYNC_DATA"
_DISABLE_EAGER_HOST_STAGING = "DISABLE_EAGER_HOST_STAGING"
_PALLAS_ATTENTION = "PALLAS_ATTENTION"
_REPLICATION_VERIFY = "REPLICATION_VERIFY"
_WRITE_CHECKSUMS = "WRITE_CHECKSUMS"
_VERIFY_ON_RESTORE = "VERIFY_ON_RESTORE"
_DEVICE_UNPACK = "DEVICE_UNPACK"
_RESTORE_DONATE = "RESTORE_DONATE"
_TRACE = "TRACE"
_FAILPOINTS = "FAILPOINTS"
_FAILPOINT_SEED = "FAILPOINT_SEED"
_RETRY_MAX_ATTEMPTS = "RETRY_MAX_ATTEMPTS"
_RETRY_BACKOFF_CAP_S = "RETRY_BACKOFF_CAP_S"
_BREAKER_THRESHOLD = "BREAKER_THRESHOLD"
_S3_ENDPOINT_URL = "S3_ENDPOINT_URL"
_STRIPE_PART_SIZE_BYTES = "STRIPE_PART_SIZE_BYTES"
_STRIPE_MIN_OBJECT_SIZE_BYTES = "STRIPE_MIN_OBJECT_SIZE_BYTES"
_CODEC = "CODEC"
_CODEC_LEVEL = "CODEC_LEVEL"
_CODEC_MIN_RATIO = "CODEC_MIN_RATIO"
_METRICS_TEXTFILE = "METRICS_TEXTFILE"
_CAS = "CAS"
_CAS_CHUNK_SIZE_BYTES = "CAS_CHUNK_SIZE_BYTES"
_CAS_GC_GRACE_S = "CAS_GC_GRACE_S"
_TIER_POLICY = "TIER_POLICY"
_TIER_FAST_KEEP_LAST_N = "TIER_FAST_KEEP_LAST_N"
_TIER_VERIFY_FAST_READS = "TIER_VERIFY_FAST_READS"
_MMAP = "MMAP"
_CACHE_DIR = "CACHE_DIR"
_CACHE_MAX_BYTES = "CACHE_MAX_BYTES"
_TOPOLOGY = "TOPOLOGY"
_TOPOLOGY_SLICE_ID = "TOPOLOGY_SLICE_ID"
_TOPOLOGY_HOST_ID = "TOPOLOGY_HOST_ID"
_FANOUT = "FANOUT"
_FANOUT_TIMEOUT_S = "FANOUT_TIMEOUT_S"
_TRANSPORT = "TRANSPORT"
_TRANSPORT_PART_BYTES = "TRANSPORT_PART_BYTES"
_CONTINUOUS = "CONTINUOUS"
_CONTINUOUS_GRACE_S = "CONTINUOUS_GRACE_S"
_FASTIO = "FASTIO"
_FASTIO_DIRECT = "FASTIO_DIRECT"
_PUBLISH_ANNOUNCE = "PUBLISH_ANNOUNCE"
_LIVENESS_TIMEOUT_S = "LIVENESS_TIMEOUT_S"
_LIVENESS_INTERVAL_S = "LIVENESS_INTERVAL_S"
_TAKEOVER = "TAKEOVER"

_DEFAULTS = {
    # Arrays larger than this are chunked along dim 0 for pipelined I/O
    # (reference default 512MB, knobs.py:41-46).
    _MAX_CHUNK_SIZE_BYTES: 512 * 1024 * 1024,
    # Per-shard subdivision limit for sharded arrays (reference knobs.py:48-53).
    _MAX_SHARD_SIZE_BYTES: 512 * 1024 * 1024,
    # HOST-staged members at or above this size are exempt from slab
    # packing: for a big numpy/host buffer the pack is a pure extra
    # memcpy (slab alloc + copy-in + copy-out) with no per-object
    # overhead left to amortize, and it serializes behind the slab.
    # Device (jax.Array) members stay slab-eligible at ANY size — the
    # device pack turns N transfers into one.  Raise to restore old
    # always-pack behavior; lower toward 0 to disable host packing
    # entirely.
    _SLAB_HOST_MEMBER_MAX_BYTES: 4 * 1024 * 1024,
    # Write requests smaller than this are coalesced into slabs
    # (reference 128MB, knobs.py:55-60).
    _SLAB_SIZE_THRESHOLD_BYTES: 128 * 1024 * 1024,
    # Concurrent storage ops per process (reference 16, knobs.py:62-67).
    _MAX_PER_RANK_IO_CONCURRENCY: 16,
    _DISABLE_BATCHING: 0,
    _PER_RANK_MEMORY_BUDGET_BYTES: 0,  # 0 = auto (see scheduler)
    # Objects that the safe codec can't encode fall back to pickle only when
    # this is on (default on, for parity with the reference's torch.save path;
    # reading a pickle payload always requires it).
    _ALLOW_PICKLE_OBJECTS: 1,
    # Threads for D2H + serialize staging work (reference 4, scheduler.py:32).
    _STAGING_THREADS: 4,
    # Use the C++ fastio extension for fs storage when it builds/loads.
    _ENABLE_NATIVE_EXT: 1,
    # Verify every fs write by re-reading and crc32c-comparing (native
    # backend only; catches torn/corrupted local writes at save time).
    _FS_VERIFY_WRITES: 0,
    # fdatasync every fs DATA write (not just the metadata commit
    # point): full local-fs crash durability at a write-throughput cost.
    _FS_SYNC_DATA: 0,
    # async_take unblocks after one batched device→pinned_host transfer
    # instead of after full staging (see host_offload.eager_offload_write_reqs).
    _DISABLE_EAGER_HOST_STAGING: 0,
    # Use the pallas flash-attention kernel inside ring attention:
    # "auto" = on for the tpu backend, off everywhere else (interpret
    # mode on CPU is orders of magnitude slower than the XLA path —
    # tests opt in explicitly).  A kernel Mosaic refuses raises at
    # compile time; there is no probe.  "1"/"0" force it on/off.
    _PALLAS_ATTENTION: "auto",
    # How thoroughly replicated-glob-matched host state is cross-checked
    # before being deduplicated to one writer:
    #   "full"  — dtype/shape + full-buffer crc32 (catches silent content
    #             divergence, e.g. per-rank optimizer scalars),
    #   "shape" — dtype/shape only (no content hash; O(1) per array —
    #             for tens-of-GB replicated host state like embeddings),
    #   "off"   — no content check; only path PRESENCE is still
    #             intersected across ranks (the partitioner requires an
    #             identical replicated item list on every rank).
    _REPLICATION_VERIFY: "full",
    # Record zlib.crc32 content checksums in the manifest at staging
    # time (checked by Snapshot.verify(deep=True) — catches bit rot and
    # torn writes that byte sizes can't).  Runs in the staging thread
    # pool off the blocked path; ~2-3 GB/s per thread.
    _WRITE_CHECKSUMS: 1,
    # Check recorded checksums during restore reads (whole-payload reads
    # only; tiled reads are skipped).  Off by default: restore is the
    # latency-critical path, and Snapshot.verify(deep=True) exists for
    # audits — flip on for untrusted/long-archived snapshots.
    _VERIFY_ON_RESTORE: 0,
    # Restore batched slabs with ONE H2D transfer + one compiled
    # slice/bitcast program (the read-side mirror of the device slab
    # pack) instead of one device_put per member.  "auto" = on for
    # accelerator backends, off on CPU (host-side copies are already
    # cheap there); "1"/"0" force.
    _DEVICE_UNPACK: "auto",
    # Free each restore template's device buffers as soon as its
    # replacement materializes, holding restore's device peak at ~1x
    # payload + one leaf — the jax analogue of the reference's in-place
    # load into pre-allocated tensors (snapshot.py:743-753; jax.Arrays
    # are immutable, so "in place" becomes put-then-delete).  Failure
    # semantics match the reference's in-place load: a restore that
    # fails mid-stateful leaves the state MIXED (earlier leaves already
    # replaced, later ones still the prior values) but entirely valid —
    # donation happens only after each replacement is reachable, and a
    # failed restore loads the already-restored leaves back so nothing
    # live references deleted buffers (Snapshot._repair_after_failed_
    # restore).  Set to 0 for all-or-nothing templates at 2x device
    # peak.  The template array objects become invalid on success
    # (restore replaces them via load_state_dict anyway).  "auto" = on
    # when the template lives on an
    # accelerator (HBM is the scarce resource), off for host-resident
    # templates; "1"/"0" force.
    _RESTORE_DONATE: "auto",
    # Structured span tracing (obs/tracer.py).  Off by default: the
    # disabled path is one module-flag check with no allocation; on, a
    # take/restore records a span tree exportable as Perfetto JSON
    # (`python -m torchsnapshot_tpu trace`, obs.write_trace).  Unlike
    # every other knob this one is resolved into obs.tracer.ENABLED at
    # import and by override_trace — the zero-cost check can't re-read
    # the env per span.  Set the env var BEFORE importing (or call
    # obs.refresh_enabled() after mutating it); gate runtime decisions
    # on obs.tracing_enabled(), which reports what is actually recorded.
    _TRACE: 0,
    # Deterministic fault injection (resilience/failpoints.py):
    # "site=error[:prob[:count]],..." specs, e.g.
    # "storage.s3.write=slowdown:1:2".  Empty = disarmed (the default;
    # the armed check is one module-global load).  Like TRACE, this is
    # resolved into the failpoint module's armed set at import and by
    # override_failpoints — set the env var BEFORE importing.
    _FAILPOINTS: "",
    # Seed for the per-spec RNG streams probabilistic failpoints draw
    # from — the same spec + seed replays the same schedule.
    _FAILPOINT_SEED: 0,
    # Shared retry policy (resilience/retry.py): per-op attempt cap.
    # An op also gives up when the WHOLE pipeline has made no progress
    # for the policy's window (SharedProgress(window_s=)).  The value
    # matches the GCS plugin's historical constant; all retrying
    # backends (fs, s3, gcs, memory) share it.
    _RETRY_MAX_ATTEMPTS: 6,
    # Exponential backoff cap: delay = min(2**attempt, cap) * jitter.
    _RETRY_BACKOFF_CAP_S: 32.0,
    # Circuit breaker (resilience/breaker.py): consecutive COMPLETED
    # failures (retries exhausted) before a backend trips open.  Tripped
    # writes fail fast (CircuitOpenError); tiered reads route straight
    # to the replica/durable fallback.
    _BREAKER_THRESHOLD: 5,
    # Alternate S3 endpoint (minio, localstack, any S3-compatible
    # store) for the s3:// plugin.  None/"" = AWS default.  Env-based
    # so snapshot-level s3:// URLs resolve against the emulator too
    # (url_to_storage_plugin has no options channel); the legacy
    # TSNP_S3_ENDPOINT_URL spelling is still honored as a fallback.
    _S3_ENDPOINT_URL: None,
    # Striped storage I/O (storage/stripe.py): objects at or above
    # STRIPE_MIN_OBJECT_SIZE_BYTES are split into STRIPE_PART_SIZE_BYTES
    # parts driven concurrently — S3 true multipart uploads, GCS
    # parallel compose-part uploads, fs offset-parallel pwrite into the
    # preallocated temp file, memory ranged writes — and restore reads
    # fan out as parallel ranged GETs.  Retry/failpoint/breaker/metrics
    # granularity moves to the part: a transient mid-object re-sends one
    # part, not the object.  Set MIN to 0 to disable striping entirely.
    _STRIPE_PART_SIZE_BYTES: 64 * 1024 * 1024,
    _STRIPE_MIN_OBJECT_SIZE_BYTES: 128 * 1024 * 1024,
    # Per-part compression (codec.py): "raw" (off — the default; the
    # pipeline pays one knob read per take and nothing per part),
    # "zlib" (stdlib), "zstd"/"lz4" (optional imports; missing degrades
    # to raw with one warning), or "huff" (native fastio block-Huffman
    # coder — the fast entropy option for byte-shuffled float
    # payloads).  Parts encode on the staging executor between the raw
    # digest and the storage write, so compression overlaps I/O under
    # the same budget; digests/dedup/deep-verify stay raw-byte-exact.
    _CODEC: "raw",
    # Codec-native compression level; 0 = each codec's own default
    # (zlib 1, zstd 3, lz4 0, huff has no levels).
    _CODEC_LEVEL: 0,
    # Store-raw fallback: a part keeps its encoded frame only when
    # raw_size >= CODEC_MIN_RATIO * frame_size — incompressible parts
    # stay raw (zero decode dependency, one 24-byte header).
    _CODEC_MIN_RATIO: 1.05,
    # Content-addressed chunk store (cas/): SnapshotManager saves write
    # payload bytes as content-keyed chunks in a per-root shared pool
    # (<root>/cas) instead of per-step objects — a take skips the write
    # for every chunk whose content an earlier committed step already
    # stored, and retention becomes refcounted GC (any step deletable).
    # 0 = off (per-step objects, the default); managers can also opt in
    # per-instance via SnapshotManager(cas=...).
    _CAS: 0,
    # Chunk granularity for content addressing: staged objects are
    # digested and stored in chunks of this size, so unchanged SLICES of
    # a mutated tensor dedup across steps.  Smaller chunks find more
    # sharing but cost more index entries and storage ops per object.
    _CAS_CHUNK_SIZE_BYTES: 16 * 1024 * 1024,
    # Two-phase GC grace window: a chunk whose refcount drops to zero is
    # only MARKED orphaned; the sweep deletes it this many seconds
    # later.  The window is what makes GC safe against a concurrent
    # in-flight take that dedups against a chunk just before its last
    # referencing step is deleted — size it above your longest take.
    _CAS_GC_GRACE_S: 900.0,
    # Prometheus textfile export (obs/export.py): when set to a path,
    # take/restore/async-commit dump the metrics registry there in the
    # text exposition format on their way out (atomic tmp+rename), for
    # node_exporter textfile collectors.  Empty = off.
    _METRICS_TEXTFILE: "",
    # Default policy for tiered storage (tier/) when the tier options
    # don't name one: "write_back" acks a take when the FAST tier
    # commits and promotes to the durable tier in the background (the
    # durable commit point — .snapshot_metadata — lands only after every
    # data object promoted); "write_through" commits both tiers
    # synchronously.
    _TIER_POLICY: "write_back",
    # How many committed steps keep a fast-tier copy under a tiered
    # SnapshotManager; older steps' fast copies are evicted (durable
    # copies follow keep_last_n independently).  A fast copy is never
    # evicted before its step is durably committed.
    _TIER_FAST_KEEP_LAST_N: 2,
    # Verify each fast-tier object against its manifest-recorded digest
    # on first read (one extra local read per object when the first read
    # is ranged); a mismatch silently falls back to a peer/durable copy
    # and repairs the fast one.  Needs WRITE_CHECKSUMS at take time.
    _TIER_VERIFY_FAST_READS: 1,
    # Zero-copy mmap materialization (serving read path): plugins that
    # declare supports_mmap_read (fs, the host cache) serve raw
    # (uncompressed, unchunked) reads as read-only mmap-backed buffers
    # instead of copying into the Python heap, and the read scheduler
    # admits such reads budget-exempt — mapped pages are file-backed
    # and reclaimable, so they must never serialize behind the host
    # staging budget.  Codec frames and CAS chunk refs transparently
    # keep the copying path (their bytes need a transform).  0 = every
    # read copies (the pre-serving behavior).
    _MMAP: 1,
    # Shared-host object cache (storage/hostcache.py): when set to a
    # directory path, durable reads route through a per-host cache —
    # co-located readers (N inference workers cold-starting on one
    # host) fetch each object from the durable tier exactly ONCE, under
    # a cross-process file lock with single-flight semantics.  Cached
    # objects are local files, so they serve mmap-backed when MMAP is
    # on.  Empty = off (the default).
    _CACHE_DIR: "",
    # Soft size cap for the shared-host cache; a fill that pushes the
    # cache past the cap evicts oldest-first by mtime (unlink only —
    # never truncate, so live mmaps of evicted objects stay valid).
    # 0 = unbounded.
    _CACHE_MAX_BYTES: 0,
    # Multislice topology model (topology/): "auto" detects rank → host
    # → slice placement from per-process hints (TOPOLOGY_SLICE_ID /
    # TOPOLOGY_HOST_ID knobs, jax device slice_index on real multislice
    # pods, hostname) exchanged once per operation over the
    # coordination KV; "flat" disables topology awareness entirely; an
    # explicit comma-separated per-rank slice list ("0,0,1,1",
    # identical on every process) pins the mapping for tests and
    # orchestrators that know their placement.
    _TOPOLOGY: "auto",
    # Per-PROCESS slice id hint for auto detection (each process sets
    # its own; exchanged to build the global rank → slice map).
    # Empty/unset = probe jax, else single-slice.
    _TOPOLOGY_SLICE_ID: "",
    # Per-PROCESS host identity hint for auto detection; empty = the
    # machine hostname.  Ranks reporting the same host id are treated
    # as co-located (shared NIC/cache) by the write partitioner and the
    # fan-out reader election.
    _TOPOLOGY_HOST_ID: "",
    # Fan-out restore (topology/fanout.py): per-slice designated reader
    # ranks pull each replicated object from the durable tier exactly
    # once and redistribute the bytes to sibling ranks over the
    # coordination KV (chunked, digest-verified).  "auto" = on when the
    # detected topology is explicit and this rank's slice has >1 rank
    # (and not already covered by a same-host shared cache); "1"/"0"
    # force.
    _FANOUT: "auto",
    # How long a sibling rank waits for its designated reader's
    # publication before falling back to a direct durable read — a dead
    # reader degrades the slice to direct GETs, never wedges it.
    _FANOUT_TIMEOUT_S: 60.0,
    # Payload-transport engine (transport/): how redistribution bytes
    # (fan-out restore blobs, continuous peer deltas, publish/ chunk
    # fan-in) physically move between ranks.  "kv" forces the chunked
    # base64 coordination-KV path; "collective" forces the
    # device-collective engine (jax device arrays over the topology's
    # mesh — ICI/DCN speed, KV demoted to announce/digest control
    # plane); "auto" probes the runtime per-op and picks collective
    # only when a multi-process jax session is live, else KV.  Any
    # collective failure degrades that op to KV (counted in
    # transport.fallbacks) — the knob selects a preference, never a
    # correctness mode.
    _TRANSPORT: "auto",
    # Device-array chunk size for the collective engine (payload bytes
    # per broadcast part, before lane padding).  Bounds per-part host
    # staging.
    _TRANSPORT_PART_BYTES: 8 * 1024 * 1024,
    # Continuous per-step checkpointing (continuous/): the fleet
    # kill-switch for already-constructed ContinuousCheckpointers.
    # 1 (default) = checkpointers run as constructed; 0 = step() becomes
    # a no-op everywhere — the escape hatch when replication itself is
    # suspected of perturbing a production run.
    _CONTINUOUS: 1,
    # Preemption grace window: how long the SIGTERM preemption-notice
    # hook (resilience/preemption.py) lets registered drains finish the
    # in-flight step replication before the process re-delivers the
    # signal and exits.  Size it under your orchestrator's kill grace
    # (GCE spot gives 30s; leave headroom for the exit itself).
    _CONTINUOUS_GRACE_S: 10.0,
    # Native fast-I/O engine (storage/fastio.py): the fs plugin's
    # part readers/writers run as single GIL-free native calls —
    # pwritev-batched syscalls with the (crc32, adler32) digest fused
    # into the same pass that moves the bytes (part writes stop paying
    # a separate digest read).  Requires the native ext; 0 keeps the
    # pre-engine fs paths (still native when ENABLE_NATIVE_EXT is on).
    # Probed ONCE at plugin init, never per-op.
    _FASTIO: 1,
    # O_DIRECT data path: takes write (and restores read) snapshot
    # payload bytes around the page cache, so a take doesn't churn the
    # cache and a serving cold start doesn't evict the model it is
    # loading.  The engine owns all alignment (sub-sector heads/tails
    # bounce through the aligned pool; the aligned body goes direct) —
    # bytes and digests are bitwise-identical either way.  Where
    # O_DIRECT is unsupported (e.g. tmpfs on older kernels) the engine
    # degrades to buffered I/O plus best-effort
    # posix_fadvise(DONTNEED).  Off by default: direct writes are
    # synchronous to media, which trades take latency for cache
    # hygiene — see docs/fastio.md for when that pays.
    _FASTIO_DIRECT: 0,
    # Whether publishers announce new publication records over the
    # coordination KV (the low-latency wake-up for subscribers).  0
    # degrades every subscriber to pure durable polling — the escape
    # hatch when the coordination service itself is suspect.  The
    # durable record/marker is written either way; announce is never
    # load-bearing for correctness.
    _PUBLISH_ANNOUNCE: 1,
    # Rank liveness (resilience/liveness.py): a peer whose op-scoped
    # heartbeat stamp stops advancing for longer than this is declared
    # dead — death-aware waits raise RankDeadError(rank) instead of
    # sitting out the full coordination deadline, and the take path
    # starts write takeover / degraded commit.  Must be comfortably
    # larger than LIVENESS_INTERVAL_S plus worst-case KV latency and GC
    # pauses; too small fabricates deaths, too large just delays
    # recovery (never corrupts — a falsely-declared rank that comes
    # back finds the scope poisoned and aborts cleanly).
    _LIVENESS_TIMEOUT_S: 30.0,
    # Heartbeat publication cadence (and the monitor's sampling floor).
    _LIVENESS_INTERVAL_S: 1.0,
    # Write takeover: 1 (default) = when a writer rank dies mid-take,
    # survivors re-write its replicated partition from their own copies
    # and commit (complete, or typed-degraded for sharded-only loss).
    # 0 = classic abort-the-world on rank death (RankDeadError
    # propagates and the take fails).
    _TAKEOVER: 1,
}

_OVERRIDES: dict = {}


def _get_raw(name: str):
    """Single resolution chain for every knob: override → env → default."""
    if name in _OVERRIDES:
        return _OVERRIDES[name]
    env = os.environ.get(_ENV_PREFIX + name)
    if env is not None:
        return env
    return _DEFAULTS[name]


def _get_int(name: str) -> int:
    return int(_get_raw(name))


def get_max_chunk_size_bytes() -> int:
    return _get_int(_MAX_CHUNK_SIZE_BYTES)


def get_max_shard_size_bytes() -> int:
    return _get_int(_MAX_SHARD_SIZE_BYTES)


def get_slab_size_threshold_bytes() -> int:
    return _get_int(_SLAB_SIZE_THRESHOLD_BYTES)


def get_slab_host_member_max_bytes() -> int:
    return _get_int(_SLAB_HOST_MEMBER_MAX_BYTES)


def get_max_per_rank_io_concurrency() -> int:
    return _get_int(_MAX_PER_RANK_IO_CONCURRENCY)


def is_batching_disabled() -> bool:
    return bool(_get_int(_DISABLE_BATCHING))


def get_per_rank_memory_budget_bytes() -> Optional[int]:
    v = _get_int(_PER_RANK_MEMORY_BUDGET_BYTES)
    return v if v > 0 else None


def is_pickle_allowed() -> bool:
    return bool(_get_int(_ALLOW_PICKLE_OBJECTS))


def get_staging_threads() -> int:
    return max(1, _get_int(_STAGING_THREADS))


def is_native_ext_enabled() -> bool:
    return bool(_get_int(_ENABLE_NATIVE_EXT))


def is_fs_verify_writes() -> bool:
    return bool(_get_int(_FS_VERIFY_WRITES))


def is_fs_sync_data() -> bool:
    return bool(_get_int(_FS_SYNC_DATA))


def is_eager_host_staging_disabled() -> bool:
    return bool(_get_int(_DISABLE_EAGER_HOST_STAGING))


def get_replication_verify() -> str:
    v = str(_get_raw(_REPLICATION_VERIFY)).lower()
    if v not in ("full", "shape", "off"):
        raise ValueError(
            f"TORCHSNAPSHOT_TPU_REPLICATION_VERIFY must be full|shape|off, "
            f"got {v!r}"
        )
    return v


def write_checksums_enabled() -> bool:
    return bool(int(_get_raw(_WRITE_CHECKSUMS)))


def verify_on_restore() -> bool:
    return bool(int(_get_raw(_VERIFY_ON_RESTORE)))


def device_unpack_enabled() -> bool:
    v = str(_get_raw(_DEVICE_UNPACK)).lower()
    if v in ("1", "true", "on"):
        return True
    if v in ("0", "false", "off"):
        return False
    # auto: off on cpu (a host-memory device gains nothing from the
    # one-DMA unpack), on for every accelerator backend.  The host path
    # does the bitcast as a zero-copy numpy view and compiles nothing.
    import jax

    return jax.default_backend() != "cpu"


def is_trace_enabled() -> bool:
    return bool(_get_int(_TRACE))


def get_failpoints() -> str:
    return str(_get_raw(_FAILPOINTS) or "")


def get_failpoint_seed() -> int:
    return _get_int(_FAILPOINT_SEED)


def get_retry_max_attempts() -> int:
    return max(1, _get_int(_RETRY_MAX_ATTEMPTS))


def get_retry_backoff_cap_s() -> float:
    return float(_get_raw(_RETRY_BACKOFF_CAP_S))


def get_breaker_threshold() -> int:
    return max(1, _get_int(_BREAKER_THRESHOLD))


def get_s3_endpoint_url() -> Optional[str]:
    """Alternate S3 endpoint, or None for the AWS default.  Resolution:
    override → TORCHSNAPSHOT_TPU_S3_ENDPOINT_URL → the pre-knob legacy
    name TSNP_S3_ENDPOINT_URL (kept so existing emulator setups don't
    break) → None.  This is the ONLY sanctioned read of either variable
    (tools/lint knob-registry pass)."""
    if _S3_ENDPOINT_URL in _OVERRIDES:
        # an active override masks BOTH env spellings — including
        # override_s3_endpoint_url(None), which forces the AWS default
        # (None is a meaningful override value here, so the _get_raw
        # chain, where None means "unset", cannot express it)
        return _OVERRIDES[_S3_ENDPOINT_URL] or None
    v = os.environ.get(_ENV_PREFIX + _S3_ENDPOINT_URL)
    if v is None:
        v = os.environ.get("TSNP_S3_ENDPOINT_URL")
    return v or None


def get_stripe_part_size_bytes() -> int:
    return max(1, _get_int(_STRIPE_PART_SIZE_BYTES))


def get_stripe_min_object_size_bytes() -> Optional[int]:
    """Striping threshold, or None when striping is disabled (0).  The
    floor of one part guards against a threshold below the part size
    producing single-part "stripes" that pay the multipart overhead
    (create/complete round-trips) for zero parallelism."""
    v = _get_int(_STRIPE_MIN_OBJECT_SIZE_BYTES)
    if v <= 0:
        return None
    return max(v, get_stripe_part_size_bytes() + 1)


def get_codec() -> str:
    """Write-side codec name (validated/availability-resolved by
    codec.resolve_codec — an unknown name degrades to raw there, with a
    warning, never mid-take)."""
    return str(_get_raw(_CODEC)).lower()


def get_codec_level() -> int:
    return _get_int(_CODEC_LEVEL)


def get_codec_min_ratio() -> float:
    return max(1.0, float(_get_raw(_CODEC_MIN_RATIO)))


def cas_enabled() -> bool:
    """Default-on content addressing for SnapshotManager saves (the
    per-instance ``cas=`` argument overrides in either direction)."""
    return bool(_get_int(_CAS))


def get_cas_chunk_size_bytes() -> int:
    return max(4096, _get_int(_CAS_CHUNK_SIZE_BYTES))


def get_cas_gc_grace_s() -> float:
    return max(0.0, float(_get_raw(_CAS_GC_GRACE_S)))


def get_metrics_textfile() -> Optional[str]:
    """Path for the OpenMetrics textfile dump, or None when export is
    off (the default).  This is the ONLY sanctioned read of
    TORCHSNAPSHOT_TPU_METRICS_TEXTFILE (tools/lint knob-registry
    pass)."""
    v = str(_get_raw(_METRICS_TEXTFILE) or "").strip()
    return v or None


def get_tier_policy() -> str:
    v = str(_get_raw(_TIER_POLICY)).lower()
    if v not in ("write_back", "write_through"):
        raise ValueError(
            f"TORCHSNAPSHOT_TPU_TIER_POLICY must be write_back|"
            f"write_through, got {v!r}"
        )
    return v


def get_tier_fast_keep_last_n() -> int:
    return max(1, _get_int(_TIER_FAST_KEEP_LAST_N))


def tier_verify_fast_reads() -> bool:
    return bool(_get_int(_TIER_VERIFY_FAST_READS))


def mmap_enabled() -> bool:
    return bool(_get_int(_MMAP))


def get_cache_dir() -> Optional[str]:
    """Shared-host object cache directory, or None when the cache is
    off (the default).  This is the ONLY sanctioned read of
    TORCHSNAPSHOT_TPU_CACHE_DIR (tools/lint knob-registry pass)."""
    v = str(_get_raw(_CACHE_DIR) or "").strip()
    return v or None


def get_cache_max_bytes() -> Optional[int]:
    v = _get_int(_CACHE_MAX_BYTES)
    return v if v > 0 else None


def get_topology() -> str:
    """Topology mode: "auto", "flat", or an explicit comma-separated
    per-rank slice list ("0,0,1,1")."""
    return str(_get_raw(_TOPOLOGY)).strip().lower() or "auto"


def get_topology_slice_id() -> Optional[int]:
    """This PROCESS's slice id hint for auto detection, or None when
    unset (probe jax / fall back to a single slice)."""
    v = str(_get_raw(_TOPOLOGY_SLICE_ID) or "").strip()
    return int(v) if v else None


def get_topology_host_id() -> Optional[str]:
    """This PROCESS's host identity hint, or None (use the hostname)."""
    v = str(_get_raw(_TOPOLOGY_HOST_ID) or "").strip()
    return v or None


def get_fanout() -> str:
    """Fan-out restore mode: "on" | "off" | "auto" (see _FANOUT above).
    Unrecognized values degrade to "auto" with a warning — fan-out is a
    bandwidth optimization resolved mid-restore, never worth aborting
    a restore over a typo'd env var."""
    v = str(_get_raw(_FANOUT)).strip().lower()
    if v in ("1", "true", "on"):
        return "on"
    if v in ("0", "false", "off"):
        return "off"
    if v != "auto":
        _logger.warning(
            "TORCHSNAPSHOT_TPU_FANOUT=%r is not auto/on/off; treating "
            "as auto", v,
        )
    return "auto"


def get_fanout_timeout_s() -> float:
    return max(0.0, float(_get_raw(_FANOUT_TIMEOUT_S)))


def get_transport() -> str:
    """Payload-transport engine preference: "auto" | "collective" |
    "kv" (see _TRANSPORT above).  Unrecognized values degrade to
    "auto" with a warning — transport selection is a bandwidth
    optimization resolved per-op, never worth aborting over a typo'd
    env var."""
    v = str(_get_raw(_TRANSPORT)).strip().lower()
    if v in ("collective", "kv"):
        return v
    if v != "auto":
        _logger.warning(
            "TORCHSNAPSHOT_TPU_TRANSPORT=%r is not auto/collective/kv; "
            "treating as auto", v,
        )
    return "auto"


def get_transport_part_bytes() -> int:
    return max(4096, _get_int(_TRANSPORT_PART_BYTES))


def continuous_enabled() -> bool:
    """Fleet kill-switch for continuous per-step checkpointing: when
    off, every ``ContinuousCheckpointer.step`` is a no-op (see
    _CONTINUOUS above)."""
    return bool(_get_int(_CONTINUOUS))


def get_continuous_grace_s() -> float:
    return max(0.0, float(_get_raw(_CONTINUOUS_GRACE_S)))


def publish_announce_enabled() -> bool:
    """Whether publishers announce records over the coordination KV
    (see _PUBLISH_ANNOUNCE above)."""
    return bool(_get_int(_PUBLISH_ANNOUNCE))


def get_liveness_timeout_s() -> float:
    """Seconds of frozen heartbeat stamp before a peer rank is declared
    dead (see _LIVENESS_TIMEOUT_S above)."""
    return max(0.1, float(_get_raw(_LIVENESS_TIMEOUT_S)))


def get_liveness_interval_s() -> float:
    """Heartbeat publication / monitor sampling cadence in seconds."""
    return max(0.01, float(_get_raw(_LIVENESS_INTERVAL_S)))


def takeover_enabled() -> bool:
    """Whether survivors take over a dead writer's partition and commit
    instead of aborting the take (see _TAKEOVER above)."""
    return bool(_get_int(_TAKEOVER))


def fastio_enabled() -> bool:
    """Native fast-I/O engine master switch (see _FASTIO above); the
    engine additionally requires the native ext to load with the part
    pwrite/pread symbols — this knob can only turn it OFF."""
    return bool(_get_int(_FASTIO))


def fastio_direct_enabled() -> bool:
    """O_DIRECT data-path request (see _FASTIO_DIRECT above); honored
    only where the engine's one-time probe finds O_DIRECT support,
    degrading to buffered + posix_fadvise(DONTNEED) otherwise."""
    return bool(_get_int(_FASTIO_DIRECT))


def restore_donation() -> str:
    """One of "on" | "off" | "auto" (see _RESTORE_DONATE above).

    Unrecognized values degrade to "auto" with a warning instead of
    raising: this knob is first read per-leaf in the middle of restore,
    where a typo'd env var must not abort a half-applied restore
    (donation is an optimization, never fatal)."""
    v = str(_get_raw(_RESTORE_DONATE)).lower()
    if v in ("1", "true", "on"):
        return "on"
    if v in ("0", "false", "off"):
        return "off"
    if v != "auto":
        _logger.warning(
            "TORCHSNAPSHOT_TPU_RESTORE_DONATE=%r is not auto/on/off; "
            "treating as auto", v,
        )
    return "auto"


def use_pallas_attention() -> bool:
    v = str(_get_raw(_PALLAS_ATTENTION)).lower()
    if v in ("1", "true", "on"):
        return True
    if v in ("0", "false", "off"):
        return False
    # auto: the kernel is a Mosaic (TPU) program; every other backend
    # takes the XLA path (tests opt in to interpret mode via
    # override_pallas_attention)
    import jax

    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def _override(name: str, value) -> Iterator[None]:
    # Context-manager override, mirroring reference knobs.py:84-132.
    had = name in _OVERRIDES
    prev = _OVERRIDES.get(name)
    _OVERRIDES[name] = value
    try:
        yield
    finally:
        if had:
            _OVERRIDES[name] = prev
        else:
            _OVERRIDES.pop(name, None)


def override_max_chunk_size_bytes(value: int):
    return _override(_MAX_CHUNK_SIZE_BYTES, value)


def override_max_shard_size_bytes(value: int):
    return _override(_MAX_SHARD_SIZE_BYTES, value)


def override_slab_size_threshold_bytes(value: int):
    return _override(_SLAB_SIZE_THRESHOLD_BYTES, value)


def override_slab_host_member_max_bytes(value: int):
    return _override(_SLAB_HOST_MEMBER_MAX_BYTES, value)


def override_max_per_rank_io_concurrency(value: int):
    return _override(_MAX_PER_RANK_IO_CONCURRENCY, value)


def override_disable_batching(value: bool):
    return _override(_DISABLE_BATCHING, int(value))


def override_per_rank_memory_budget_bytes(value: int):
    return _override(_PER_RANK_MEMORY_BUDGET_BYTES, value)


def override_allow_pickle_objects(value: bool):
    return _override(_ALLOW_PICKLE_OBJECTS, int(value))


def override_write_checksums(value: bool):
    return _override(_WRITE_CHECKSUMS, int(value))


def override_verify_on_restore(value: bool):
    return _override(_VERIFY_ON_RESTORE, int(value))


def override_device_unpack(value):
    return _override(_DEVICE_UNPACK, value)


def override_staging_threads(value: int):
    return _override(_STAGING_THREADS, value)


def override_enable_native_ext(value: bool):
    return _override(_ENABLE_NATIVE_EXT, int(value))


def override_fs_verify_writes(value: bool):
    return _override(_FS_VERIFY_WRITES, int(value))


def override_fs_sync_data(value: bool):
    return _override(_FS_SYNC_DATA, int(value))


def override_disable_eager_host_staging(value: bool):
    return _override(_DISABLE_EAGER_HOST_STAGING, int(value))


def override_pallas_attention(value):
    return _override(_PALLAS_ATTENTION, value)


def override_replication_verify(value: str):
    return _override(_REPLICATION_VERIFY, value)


def override_restore_donate(value):
    return _override(_RESTORE_DONATE, value)


def override_s3_endpoint_url(value):
    return _override(_S3_ENDPOINT_URL, value)


def override_stripe_part_size_bytes(value: int):
    return _override(_STRIPE_PART_SIZE_BYTES, value)


def override_stripe_min_object_size_bytes(value: int):
    return _override(_STRIPE_MIN_OBJECT_SIZE_BYTES, value)


def override_codec(value: str):
    return _override(_CODEC, value)


def override_codec_level(value: int):
    return _override(_CODEC_LEVEL, value)


def override_codec_min_ratio(value: float):
    return _override(_CODEC_MIN_RATIO, value)


def override_cas(value: bool):
    return _override(_CAS, int(value))


def override_cas_chunk_size_bytes(value: int):
    return _override(_CAS_CHUNK_SIZE_BYTES, value)


def override_cas_gc_grace_s(value: float):
    return _override(_CAS_GC_GRACE_S, value)


def override_metrics_textfile(value):
    return _override(_METRICS_TEXTFILE, value or "")


def override_mmap(value: bool):
    return _override(_MMAP, int(value))


def override_cache_dir(value):
    return _override(_CACHE_DIR, value or "")


def override_cache_max_bytes(value: int):
    return _override(_CACHE_MAX_BYTES, value)


def override_topology(value):
    return _override(_TOPOLOGY, value or "auto")


def override_fanout(value):
    return _override(_FANOUT, value)


def override_transport(value):
    return _override(_TRANSPORT, value or "auto")


def override_transport_part_bytes(value: int):
    return _override(_TRANSPORT_PART_BYTES, value)


def override_continuous(value: bool):
    return _override(_CONTINUOUS, int(value))


def override_publish_announce(value: bool):
    return _override(_PUBLISH_ANNOUNCE, value)


def override_liveness_timeout_s(value: float):
    return _override(_LIVENESS_TIMEOUT_S, value)


def override_liveness_interval_s(value: float):
    return _override(_LIVENESS_INTERVAL_S, value)


def override_fastio(value: bool):
    return _override(_FASTIO, int(value))


def override_fastio_direct(value: bool):
    return _override(_FASTIO_DIRECT, int(value))


def override_failpoint_seed(value: int):
    return _override(_FAILPOINT_SEED, value)


def override_retry_max_attempts(value: int):
    return _override(_RETRY_MAX_ATTEMPTS, value)


def override_retry_backoff_cap_s(value: float):
    return _override(_RETRY_BACKOFF_CAP_S, value)


def override_breaker_threshold(value: int):
    return _override(_BREAKER_THRESHOLD, value)


@contextlib.contextmanager
def override_failpoints(value: str) -> Iterator[None]:
    """Override FAILPOINTS and re-arm the failpoint module on entry AND
    exit (the armed set is the zero-cost disarmed-path check, so it must
    track the knob rather than re-resolve per call site).  Malformed
    specs raise here — a test's typo'd schedule must fail loudly, not
    silently run fault-free."""
    from .resilience import failpoints as _failpoints

    try:
        with _override(_FAILPOINTS, value or ""):
            _failpoints.refresh_from_knobs(strict=True)
            yield
    finally:
        _failpoints.refresh_from_knobs(strict=False)


@contextlib.contextmanager
def override_trace(value) -> Iterator[None]:
    """Override TRACE and refresh the tracer's module-level enabled flag
    on entry AND exit (the flag is the zero-cost disabled-path check, so
    it must track the knob rather than re-resolve it per span)."""
    from .obs import tracer as _tracer

    try:
        with _override(_TRACE, int(bool(int(value)))):
            _tracer.refresh_enabled()
            yield
    finally:
        _tracer.refresh_enabled()
