"""The always-on per-step checkpoint loop.

``ContinuousCheckpointer.step(app_state, step)`` is called by the
training loop after every optimizer step.  The blocked window is kept
to the minimum that makes the step's bytes independent of training
state: flatten → chunk-digest (staging threads) → copy only the DELTA
chunks no target holds yet.  Everything else — writing those chunks to
this host's RAM store and each peer host's RAM store (marker-last:
chunks → step manifest → HEAD), heartbeat publication, pruning, and
the every-Nth-step durable promotion — happens on one background
replication thread, admitted under the scheduler's staging budget
(scheduler.sync_execute_buffer_writes) so replication can never
out-buffer the memory a host sized for takes.

Loss model: a host killed at any instant loses AT MOST the step whose
replication was in flight — the peer's HEAD always names the last
complete step (marker-last per store), and ``step()`` joins the
previous step's replication before starting the next (replication lag
is bounded at one step by construction, visible in
``continuous.replication_lag_steps``).

Peer placement prefers a DIFFERENT slice (``Topology.replica_preference``)
so a whole-slice preemption never takes the primary and its replica
together; durable promotion reuses the write-back promoter
(tier/promoter.py) with a pinned HEAD payload, keeping the durable
mirror's marker-last commit contract; a SIGTERM preemption notice
(resilience/preemption.py) drains the in-flight replication inside the
grace window, so even the killed step usually survives.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from .. import knobs, obs
from ..cas.store import chunk_location
from ..coordination import Coordinator, get_default_coordinator
from ..flatten import flatten
from ..obs import goodput
from ..resilience import preemption
from ..storage.stripe import plan_parts
from ..tier.promoter import PromotionGroup, get_promoter
from ..utils.checksums import adler32_fast, crc32_fast
from . import heartbeat
from .store import (
    ContinuousStore,
    chunk_key,
    encode_head,
    encode_leaf,
    encode_step_manifest,
    step_manifest_path,
)

logger = logging.getLogger(__name__)

# the most recently constructed live checkpointer, for flight-record /
# doctor rollups (obs/aggregate.py reads summary_block())
_ACTIVE: Optional["weakref.ref[ContinuousCheckpointer]"] = None


def summary_block() -> Optional[Dict[str, Any]]:
    """JSON-safe rollup of the active checkpointer (None when no loop
    is running in this process) — rides flight-record payloads so
    ``doctor`` can render replica residency and replication lag."""
    cc = _ACTIVE() if _ACTIVE is not None else None
    if cc is None:
        return None
    try:
        return cc.summary()
    except Exception as e:  # noqa: BLE001 — telemetry must not raise
        obs.swallowed_exception("continuous.summary_block", e)
        return None


class _StepJob:
    __slots__ = (
        "step", "t_begin", "target_items", "all_keys",
        "manifest_payload", "head_payload", "done", "promote",
    )

    def __init__(
        self,
        step: int,
        t_begin: float,
        target_items: Dict[str, List[Tuple[str, bytes]]],
        all_keys: Set[str],
        manifest_payload: bytes,
        head_payload: bytes,
        promote: bool,
    ) -> None:
        self.step = step
        self.t_begin = t_begin
        self.target_items = target_items
        self.all_keys = all_keys
        self.manifest_payload = manifest_payload
        self.head_payload = head_payload
        self.done = threading.Event()
        self.promote = promote


class ContinuousCheckpointer:
    """Always-on per-step peer checkpointing (see module docstring).

    ``local_root`` — this HOST's fast store root (tmpfs path, local
    SSD, or ``memory://``); each rank's state lives under
    ``{root}/r{rank}``.
    ``durable_root`` — the durable mirror root (cloud URL / shared fs);
    None disables promotion and durable fallback.
    ``peer_roots`` — every rank's ``local_root`` indexed by rank; None
    = exchanged over the coordination KV at the first step.
    ``replica_roots`` — explicit HOST roots to mirror to, overriding
    peer selection entirely (tests, world-size-1 setups with a
    standby host).
    ``replica_count`` — peers to mirror each step to (topology-aware:
    different-slice peers preferred).
    ``promote_every_n`` — the in-RAM store promotes to the durable tier
    every N steps (peer RAM absorbs every step, the durable tier every
    Nth); 0 = never (peer-only; an explicit promote() still works).
    (The SIGTERM grace window is knob-only: CONTINUOUS_GRACE_S.)
    ``retain_steps`` — completed steps each store keeps (older chunks
    and manifests are pruned; the HEAD step always survives).
    """

    def __init__(
        self,
        local_root: str,
        durable_root: Optional[str] = None,
        coordinator: Optional[Coordinator] = None,
        replica_count: int = 1,
        peer_roots: Optional[Sequence[str]] = None,
        replica_roots: Optional[Sequence[str]] = None,
        promote_every_n: int = 16,
        chunk_size_bytes: Optional[int] = None,
        retain_steps: int = 2,
        topology: Any = None,
        preemption_hook: bool = True,
        publisher: Any = None,
    ) -> None:
        self.local_root = local_root.rstrip("/")
        self.durable_root = (
            durable_root.rstrip("/") if durable_root else None
        )
        self._coordinator = coordinator
        self.replica_count = int(replica_count)
        self._peer_roots = (
            [r.rstrip("/") for r in peer_roots] if peer_roots else None
        )
        self._replica_roots = (
            [r.rstrip("/") for r in replica_roots]
            if replica_roots is not None
            else None
        )
        self._promote_every_n = promote_every_n
        self.chunk_size = int(
            chunk_size_bytes or knobs.get_cas_chunk_size_bytes()
        )
        self.retain_steps = max(1, int(retain_steps))
        self._topology = topology
        self._stores: Dict[str, ContinuousStore] = {}
        self._holds: Dict[str, Set[str]] = {}
        self._target_heads: Dict[str, int] = {}
        self._recent: List[Tuple[int, Set[str]]] = []
        self._targets: Optional[List[str]] = None  # resolved at step 1
        self._ns: Optional[str] = None
        self._step_count = 0
        self._last_step: Optional[int] = None
        self._inflight: Optional[_StepJob] = None
        self._queue: "queue.Queue[Optional[_StepJob]]" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._executor: Optional[ThreadPoolExecutor] = None
        self._target_pool: Optional[ThreadPoolExecutor] = None
        self._io_loop: Any = None  # persistent scheduler._LoopThread
        self._closed = False
        # payload-transport engine for the peer-delta leg (transport/):
        # resolved once on the replication worker at first use; None
        # until then, KVTransport's identity leg when collectives are
        # unavailable
        self._transport: Any = None
        self._transport_resolved = False
        # durable promotion bookkeeping: CONFIRMED-durable keys (the
        # delta basis), the in-flight groups, and step manifests whose
        # local GC is deferred until their promotion settles
        self._durable_confirmed: Set[str] = set()
        self._durable_head_step: Optional[int] = None
        self._durable_manifest_steps: Set[int] = set()
        self._manifest_gc_pending: Set[int] = set()
        # chunks a FAILED promotion may have half-copied before dying:
        # swept with the confirmed set at the next successful promotion
        # so repeated failures can't accrete unreferenced durable bytes
        self._durable_orphans: Set[str] = set()
        # guards ALL promotion bookkeeping (_promotions,
        # _durable_confirmed/_orphans/_head_step, _manifest_gc_pending):
        # the replication worker enqueues/sweeps while telemetry and
        # accessor threads (summary/last_durable_step via flight
        # records) sweep concurrently — physical store deletes happen
        # OUTSIDE the lock
        self._promo_lock = threading.Lock()
        self._promotions: List[Tuple[PromotionGroup, Set[str], Set[str], int]] = []
        # guards the lazy singletons (_ns, _targets, _target_pool,
        # _io_loop): created on first use from the step or worker
        # thread, torn down by close() — the expensive/collective
        # resolution work itself runs OUTSIDE the lock
        self._init_lock = threading.Lock()
        # live-weight publication (publish/): every confirmed durable
        # promotion is published so serving subscribers can delta-swap
        # to it.  Best-effort by design — publication rides behind the
        # durability contract, never gates it
        self._publisher = publisher
        self._published_step: Optional[int] = None
        self._preemption_handle: Optional[int] = None
        if preemption_hook:
            self._preemption_handle = preemption.on_preemption(
                self._preemption_drain
            )
        global _ACTIVE
        _ACTIVE = weakref.ref(self)
        # seed the durable dedup basis from an existing mirror so a
        # restarted job doesn't re-promote every byte
        if self.durable_root is not None:
            self._seed_durable()

    # ---------------------------------------------------------- plumbing

    @property
    def _coord(self) -> Coordinator:
        if self._coordinator is None:
            self._coordinator = get_default_coordinator()
        return self._coordinator

    @property
    def rank(self) -> int:
        return self._coord.rank

    def _rank_store_root(self, host_root: str) -> str:
        return f"{host_root.rstrip('/')}/r{self.rank}"

    @property
    def local_store_root(self) -> str:
        return self._rank_store_root(self.local_root)

    @property
    def durable_store_root(self) -> Optional[str]:
        if self.durable_root is None:
            return None
        return self._rank_store_root(self.durable_root)

    def _store(self, root: str) -> ContinuousStore:
        store = self._stores.get(root)
        if store is None:
            store = self._stores[root] = ContinuousStore(root)
        return store

    def _ensure_executor(self) -> ThreadPoolExecutor:
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=knobs.get_staging_threads(),
                thread_name_prefix="tsnp-continuous-digest",
            )
        return self._executor

    def _ensure_target_pool(self) -> ThreadPoolExecutor:
        with self._init_lock:
            if self._target_pool is None:
                self._target_pool = ThreadPoolExecutor(
                    max_workers=4,
                    thread_name_prefix="tsnp-continuous-target",
                )
            return self._target_pool

    def _ensure_io_loop(self) -> Any:
        """One long-lived event-loop thread for ALL per-step chunk
        writes (every target, every step): per-call thread+loop churn
        would sit on the once-per-training-step hot path."""
        with self._init_lock:
            if self._io_loop is None:
                from ..scheduler import _LoopThread

                self._io_loop = _LoopThread(name="tsnp-continuous-io")
            return self._io_loop

    def _ensure_worker(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._worker_run,
                name="tsnp-continuous-replicate",
                daemon=True,
            )
            self._worker.start()

    def promote_every_n(self) -> int:
        return max(0, int(self._promote_every_n))

    # ----------------------------------------------------- target choice

    def _ensure_ns(self) -> str:
        """The per-checkpointer KV namespace (heartbeats, exchanges).
        Derived from the coordinator's program-order uid counter, so it
        matches across ranks as long as every rank constructs/uses its
        checkpointer in the same program order — the same contract as
        every other foreground coordination op."""
        with self._init_lock:
            if self._ns is None:
                self._ns = self._coord._next_uid("cc")
            return self._ns

    def _exchange_peer_roots(self) -> Optional[List[str]]:
        """All ranks' host roots indexed by rank — exchanged over the
        KV on first need (collective: every rank must reach this in
        the same program order, which both step() and a fleet-wide
        restore_latest() satisfy)."""
        if self._peer_roots is None and self._coord.world_size > 1:
            self._peer_roots = [
                r.rstrip("/")
                for r in self._coord.kv_exchange(
                    f"{self._ensure_ns()}/roots", self.local_root
                )
            ]
        return self._peer_roots

    def _ensure_targets(self) -> List[str]:
        """Resolve the replica target STORE roots once, at the first
        step: explicit ``replica_roots`` verbatim, else peers chosen
        from the exchanged per-rank roots by topology preference
        (different-slice first).  Symmetric — every rank reaches this
        from its own first step()."""
        with self._init_lock:
            if self._targets is not None:
                return self._targets
        coord = self._coord
        self._ensure_ns()
        if self._replica_roots is not None:
            hosts = list(self._replica_roots)
        elif coord.world_size > 1:
            from ..topology import replica_candidate_order

            peers = self._exchange_peer_roots()
            topo = self._topology
            if topo is None:
                topo = self._detect_topology()
            order = replica_candidate_order(topo, coord.rank, len(peers))
            hosts = []
            for c in order:
                if len(hosts) >= self.replica_count:
                    break
                if peers[c] != self.local_root and peers[c] not in hosts:
                    hosts.append(peers[c])
        else:
            hosts = []
            logger.warning(
                "continuous checkpointing without peers (world_size 1, "
                "no replica_roots): a lost host falls back to the "
                "durable mirror only"
            )
        # the local store is always the first target — it is both the
        # promotion source and the fastest recovery path after a plain
        # process crash (host survived)
        targets = [self.local_store_root] + [
            self._rank_store_root(h) for h in hosts
        ]
        with self._init_lock:
            self._targets = targets
        for root in targets:
            self._seed_holds(root)
        return targets

    def _detect_topology(self) -> Any:
        try:
            from ..topology import detect_topology

            return detect_topology(
                self._coord, exchange_prefix=f"{self._ensure_ns()}/topo"
            )
        except Exception as e:  # noqa: BLE001 — placement optimization
            obs.swallowed_exception("continuous.topology_detect", e)
            return None

    def _seed_holds(self, root: str) -> None:
        """Best-effort warm start against a surviving store: trust the
        chunks its committed HEAD step references, so a restart doesn't
        re-replicate unchanged content."""
        try:
            store = self._store(root)
            head = store.read_head()
            if head is None:
                return
            manifest = store.read_step_manifest(str(head["manifest"]))
            keys = {
                k
                for rec in manifest["leaves"].values()
                for k in rec["keys"]
            }
            with self._promo_lock:
                self._holds.setdefault(root, set()).update(keys)
                self._target_heads[root] = int(head["step"])
                self._recent.append((int(head["step"]), keys))
        except Exception as e:  # noqa: BLE001 — cold start is correct
            obs.swallowed_exception("continuous.seed_holds", e)

    def _seed_durable(self) -> None:
        try:
            store = self._store(self.durable_store_root)
            head = store.read_head()
            if head is None:
                return
            manifest = store.read_step_manifest(str(head["manifest"]))
            keys = {
                k
                for rec in manifest["leaves"].values()
                for k in rec["keys"]
            }
            with self._promo_lock:
                self._durable_confirmed |= keys
                self._durable_head_step = int(head["step"])
        except Exception as e:  # noqa: BLE001 — full promotion instead
            obs.swallowed_exception("continuous.seed_durable", e)

    # ------------------------------------------------------------- step

    def step(self, app_state: Dict[str, Any], step: int) -> bool:
        """Record one completed training step: digest the state tree,
        stage the changed chunks, and hand them to the background
        replicator.  Returns False when the CONTINUOUS kill-switch knob
        is off (nothing recorded).  The blocked window is the digest +
        delta staging; replication overlaps the next forward pass."""
        if not knobs.continuous_enabled() or self._closed:
            return False
        t_begin = goodput.take_begin(self.local_store_root)
        with obs.span("continuous/step", step=step):
            # backpressure: at most ONE step's replication in flight —
            # the previous job must land before this step's delta is
            # computed, which is also what bounds loss to one step
            self._join_inflight()
            targets = self._ensure_targets()
            job = self._build_job(app_state, step, targets, t_begin)
            self._step_count += 1
            self._last_step = step
            self._ensure_worker()
            self._inflight = job
            self._queue.put(job)
        blocked = goodput.take_unblocked(self.local_store_root, t_begin)
        obs.histogram(obs.CONTINUOUS_STEP_OVERHEAD_S).observe(blocked)
        obs.counter(obs.CONTINUOUS_STEPS).inc()
        return True

    def _join_inflight(self) -> None:
        job = self._inflight
        if job is not None:
            job.done.wait()
            self._inflight = None

    def _build_job(
        self,
        app_state: Dict[str, Any],
        step: int,
        targets: List[str],
        t_begin: float,
    ) -> _StepJob:
        executor = self._ensure_executor()
        state_tree = {
            k: (v.state_dict() if hasattr(v, "state_dict") else v)
            for k, v in app_state.items()
        }
        _manifest, flattened = flatten(state_tree)
        leaves: Dict[str, Dict[str, Any]] = {}
        # a chunk may be skipped from staging only when EVERY target
        # already holds it (intersection, not union): a target whose
        # last replication failed is missing chunks its peers hold, and
        # its next manifest+HEAD may only be written once those chunks
        # were re-sent — a HEAD referencing never-staged chunks would
        # be a committed-but-incomplete store
        inter_holds: Optional[Set[str]] = None
        for tgt in targets:
            h = self._holds.get(tgt, set())
            inter_holds = (
                set(h) if inter_holds is None else (inter_holds & h)
            )
        inter_holds = inter_holds or set()
        all_keys: Set[str] = set()
        staged: Dict[str, bytes] = {}
        m_skip_b = obs.counter(obs.CONTINUOUS_BYTES_SKIPPED)
        m_skip_c = obs.counter(obs.CONTINUOUS_CHUNKS_SKIPPED)
        m_new_c = obs.counter(obs.CONTINUOUS_CHUNKS_REPLICATED)

        def _digest(view: memoryview, lo: int, hi: int) -> str:
            piece = view[lo:hi]
            return chunk_key(
                (crc32_fast(piece), adler32_fast(piece), hi - lo)
            )

        for path in sorted(flattened):
            rec, view = encode_leaf(flattened[path])
            spans = plan_parts(view.nbytes, self.chunk_size)
            keys = list(
                executor.map(
                    lambda s, v=view: _digest(v, s[0], s[1]), spans
                )
            )
            rec["keys"] = keys
            leaves[path] = rec
            for key, (lo, hi) in zip(keys, spans):
                if key in all_keys:
                    continue  # intra-step repeat (tied weights)
                all_keys.add(key)
                if key in inter_holds:
                    m_skip_b.inc(hi - lo)
                    m_skip_c.inc()
                elif key not in staged:
                    # stage a private copy: the training loop mutates
                    # these arrays the moment step() returns
                    staged[key] = bytes(view[lo:hi])
                    m_new_c.inc()
        target_items: Dict[str, List[Tuple[str, bytes]]] = {}
        for tgt in targets:
            holds = self._holds.get(tgt, set())
            target_items[tgt] = [
                (chunk_location(k), staged[k])
                for k in sorted(staged)
                if k not in holds
            ]
        promote_n = self.promote_every_n()
        # the count is pre-increment, so the FIRST step promotes (a
        # durable baseline exists as soon as possible), then every Nth
        promote = (
            self.durable_root is not None
            and promote_n > 0
            and self._step_count % promote_n == 0
        )
        return _StepJob(
            step=step,
            t_begin=t_begin,
            target_items=target_items,
            all_keys=all_keys,
            manifest_payload=encode_step_manifest(
                step, self.chunk_size, leaves
            ),
            head_payload=encode_head(step),
            promote=promote,
        )

    # ------------------------------------------------------ worker side

    def _worker_run(self) -> None:
        while True:
            job = self._queue.get()
            try:
                if job is None:
                    return
                self._run_job(job)
            except BaseException as e:  # noqa: BLE001 — background
                # thread: replication problems must degrade (peer keeps
                # the previous step), never kill the training process
                obs.counter(obs.CONTINUOUS_REPLICATION_ERRORS).inc()
                logger.exception(
                    "continuous replication job for step %s failed",
                    getattr(job, "step", "?"),
                )
            finally:
                if job is not None:
                    job.done.set()
                self._queue.task_done()

    def _transport_for_peers(self) -> Any:
        """The payload-transport engine for peer-delta writes, resolved
        once on the replication worker: the collective engine when the
        runtime supports it (its ``device_move`` routes each delta
        chunk through the device fabric, digest-verified), else None
        (the KV engine's fabric leg is the identity — not worth an
        executor hop per chunk).  ``_init_lock`` covers the handoff
        with ``close()``, which swaps the engine out from the caller
        domain."""
        with self._init_lock:
            if not self._transport_resolved:
                self._transport_resolved = True
                from ..transport import resolve_transport

                t = resolve_transport(
                    self._coordinator, topology=self._topology
                )
                self._transport = t if t.engine == "collective" else None
            return self._transport

    def _run_job(self, job: _StepJob) -> None:
        from ..scheduler import (
            get_process_memory_budget_bytes,
            sync_execute_buffer_writes,
        )

        # ONE budget shared across the step's targets: each concurrent
        # sync_execute_buffer_writes call gets an equal slice, so total
        # admitted in-flight bytes stay within the budget a host sized
        # for takes, not (1 + replica_count) times it
        per_target_budget = max(
            1,
            get_process_memory_budget_bytes()
            // max(1, len(job.target_items)),
        )
        # resolved BEFORE the concurrent target dispatch: lazily
        # creating it from two pool threads would race
        io_loop = self._ensure_io_loop()
        transport = self._transport_for_peers()

        def _one_target(root: str, items) -> bool:
            store = self._store(root)
            try:
                if items:
                    sync_execute_buffer_writes(
                        items,
                        store.storage,
                        per_target_budget,
                        counter_name=obs.CONTINUOUS_BYTES_REPLICATED,
                        failpoint_site="continuous.replicate",
                        span_label="continuous/replicate_object",
                        loop_thread=io_loop,
                        # fabric leg for bytes LEAVING this host only —
                        # the local store's writes never cross a link
                        transport=(
                            transport
                            if root != self.local_root
                            else None
                        ),
                    )
                store.write_manifest(job.step, job.manifest_payload)
                store.write_head(job.head_payload)
            except Exception as e:  # noqa: BLE001 — this target keeps
                # its previous complete step (marker-last); training
                # continues, and because delta staging skips only
                # chunks EVERY target holds, the next step re-sends
                # whatever this target is missing (holds not advanced)
                obs.counter(obs.CONTINUOUS_REPLICATION_ERRORS).inc()
                logger.warning(
                    "continuous replication of step %d to %r failed "
                    "(%r); target stays at its previous step",
                    job.step, root, e,
                )
                return False
            # distinct dict keys per target, but sweeps on the
            # accessor threads iterate the whole map concurrently
            with self._promo_lock:
                self._holds.setdefault(root, set()).update(job.all_keys)
                self._target_heads[root] = job.step
            return True

        with obs.span(
            "continuous/replicate", step=job.step,
            targets=len(job.target_items),
        ):
            items_by_root = list(job.target_items.items())
            if len(items_by_root) > 1:
                # targets replicate CONCURRENTLY: the at-risk window
                # (a host killed before all targets commit loses this
                # step) is the slowest target, not the sum
                pool = self._ensure_target_pool()
                list(
                    pool.map(lambda kv: _one_target(*kv), items_by_root)
                )
            else:
                for root, items in items_by_root:
                    _one_target(root, items)
        lag = time.monotonic() - job.t_begin
        obs.histogram(obs.CONTINUOUS_REPLICATION_LAG_S).observe(lag)
        last = self._last_step if self._last_step is not None else job.step
        peer = self.last_peer_step()
        obs.gauge(obs.CONTINUOUS_REPLICATION_LAG_STEPS).set(
            max(0, last - peer) if peer is not None else 0
        )
        self._record_recent(job)
        # reconcile finished promotions every step (not only when the
        # next one is enqueued): peer-only/manual-promote runs would
        # otherwise report a stale durable step forever and keep the
        # finished group's keys pinned against pruning
        if self._pending_promotions():
            self._sweep_promotions()
        if (
            job.promote
            and self._target_heads.get(self.local_store_root) == job.step
        ):
            self._enqueue_promotion(job)
        coord = self._coordinator
        with self._init_lock:
            ns = self._ns
            targets = self._targets
        if coord is not None and ns is not None:
            # publish what peers ACTUALLY hold: the loss floor.  -1 =
            # peers exist but none holds a complete step yet; with no
            # peer targets the local head is this rank's only truth
            lp = self.last_peer_step()
            if lp is None:
                has_peers = len(targets or ()) > 1
                lp = (
                    -1
                    if has_peers
                    else self._target_heads.get(
                        self.local_store_root, -1
                    )
                )
            heartbeat.publish(coord, ns, coord.rank, lp)

    def _record_recent(self, job: _StepJob) -> None:
        """Retention: keep the last ``retain_steps`` steps' manifests
        and the union of their chunks; prune everything older — but
        ONLY from targets whose HEAD is current.  A lagging target
        (last replication failed) still serves its older step; pruning
        it would destroy the one replica it holds, so it keeps
        everything until it catches up.  Chunks a pending promotion
        still needs to read from the local store are protected too."""
        deletions: List[Tuple[str, str]] = []  # (store root, path)
        with self._promo_lock:
            self._recent.append((job.step, set(job.all_keys)))
            while len(self._recent) > self.retain_steps:
                old_step, _old_keys = self._recent.pop(0)
                keep: Set[str] = set()
                for _s, ks in self._recent:
                    keep |= ks
                protect = set(keep)
                pending_steps: Set[int] = set()
                for _g, new_keys, step_keys, s in self._promotions:
                    protect |= new_keys | step_keys
                    pending_steps.add(s)
                if old_step in pending_steps:
                    # a queued promotion still needs to COPY this
                    # manifest from the local store — defer its GC to
                    # the sweep that reconciles the group
                    self._manifest_gc_pending.add(old_step)
                for root in list(self._holds):
                    if root == self.durable_store_root:
                        continue
                    if self._target_heads.get(root) != job.step:
                        continue  # lagging target: its HEAD still
                        # needs these
                    holds = self._holds[root]
                    for key in sorted(holds - protect):
                        deletions.append((root, chunk_location(key)))
                        holds.discard(key)
                    if old_step not in pending_steps:
                        deletions.append(
                            (root, step_manifest_path(old_step))
                        )
        # physical deletes strictly outside the lock (lock-discipline)
        for root, path in deletions:
            self._store(root).delete_quiet(path)

    # -------------------------------------------------------- promotion

    def _pending_promotions(self) -> int:
        with self._promo_lock:
            return len(self._promotions)

    def _enqueue_promotion(self, job: _StepJob) -> None:
        """Hand this step to the write-back promoter: data job copies
        the not-yet-durable chunks + the step manifest from the local
        store to the durable mirror, commit job writes the PINNED HEAD
        last — an interrupted promotion leaves the durable mirror at
        its previous step, never torn (the tier promoter's existing
        marker-last contract)."""
        self._sweep_promotions()
        durable_root = self.durable_store_root
        assert durable_root is not None
        # delta against CONFIRMED durable residency only — never
        # against still-pending groups' keys.  FIFO runs this group's
        # data job after any earlier pending ones, but an EARLIER group
        # can fail mid-copy; a group that assumed those keys would then
        # commit a HEAD referencing chunks nobody promoted.  Each group
        # is self-sufficient instead (overlapping in-flight promotions
        # pay some redundant idempotent copies — correctness over
        # bytes).
        with self._promo_lock:
            new_keys = set(job.all_keys) - self._durable_confirmed
            group = PromotionGroup(self.local_store_root, durable_root)
            group.paths = {chunk_location(k) for k in new_keys}
            group.paths.add(step_manifest_path(job.step))
            group.marker_payload = job.head_payload
            self._promotions.append(
                (group, new_keys, set(job.all_keys), job.step)
            )
        promoter = get_promoter()
        promoter.enqueue_data(group)
        promoter.enqueue_commit(group)
        obs.counter(obs.CONTINUOUS_PROMOTIONS).inc()

    def _sweep_promotions(self) -> None:
        """Reconcile finished promotion groups: confirmed groups adopt
        their step as the durable HEAD and release no-longer-referenced
        durable chunks; failed groups simply leave (their keys were
        never counted as durable — deltas are computed against
        CONFIRMED residency only).  Also drains the deferred manifest
        GC for steps whose promotion settled after retention evicted
        them.  Called from the worker thread (per replication job) and
        from main-thread accessors (last_durable_step/summary) —
        every bookkeeping touch happens under ``_promo_lock``; only
        the physical deletes run outside it."""
        deletions: List[Tuple[str, str]] = []  # (store root, path)
        with self._promo_lock:
            still: List[Tuple[PromotionGroup, Set[str], Set[str], int]] = []
            confirmed: Optional[Tuple[Set[str], int]] = None
            for group, new_keys, step_keys, step in self._promotions:
                if getattr(group, "completed", False):
                    self._durable_confirmed |= new_keys
                    self._durable_manifest_steps.add(step)
                    if confirmed is None or step > confirmed[1]:
                        confirmed = (step_keys, step)
                elif group.failed:
                    # its data job may have copied SOME of these before
                    # dying — track them so pruning can reclaim
                    # whatever no later manifest references
                    self._durable_orphans |= new_keys
                else:
                    still.append((group, new_keys, step_keys, step))
            self._promotions = still
            pending_steps = {s for _g, _nk, _sk, s in still}
            gc_now = {
                s
                for s in self._manifest_gc_pending
                if s not in pending_steps
            }
            if gc_now:
                self._manifest_gc_pending -= gc_now
                retained = {s for s, _ks in self._recent}
                for s in gc_now:
                    if s in retained:
                        continue
                    for root in list(self._holds):
                        if root == self.durable_store_root:
                            continue
                        deletions.append((root, step_manifest_path(s)))
            if confirmed is not None:
                step_keys, step = confirmed
                if (
                    self._durable_head_step is None
                    or step > self._durable_head_step
                ):
                    self._durable_head_step = step
                # durable pruning: drop confirmed chunks the new
                # durable HEAD no longer references and no pending
                # promotion still needs
                protect = set(step_keys)
                for _g, nk, sk, _s in still:
                    protect |= nk | sk
                stale = (
                    self._durable_confirmed | self._durable_orphans
                ) - protect
                if stale:
                    for key in sorted(stale):
                        deletions.append(
                            (
                                self.durable_store_root,
                                chunk_location(key),
                            )
                        )
                    self._durable_confirmed -= stale
                    self._durable_orphans -= stale
                self._durable_orphans &= protect
                # durable MANIFEST retention: keep the HEAD step's (and
                # any pending promotion's); older ones are superseded —
                # without this a long run accretes one manifest JSON
                # per promotion in the durable tier forever
                old_manifests = {
                    s
                    for s in self._durable_manifest_steps
                    if s < step and s not in pending_steps
                }
                for s in sorted(old_manifests):
                    deletions.append(
                        (
                            self.durable_store_root,
                            step_manifest_path(s),
                        )
                    )
                self._durable_manifest_steps -= old_manifests
        # physical deletes strictly OUTSIDE the lock (lock-discipline:
        # no storage ops under a held lock; delete_quiet is best-effort
        # so a failed delete costs at most a leaked file)
        for root, path in deletions:
            self._store(root).delete_quiet(path)
        self._publish_durable_head()

    def _publish_durable_head(self) -> None:
        """Publish the durable HEAD step if it advanced past the last
        publication (publish/).  Runs outside ``_promo_lock`` (it does
        storage I/O) and is best-effort: a failed publication leaves
        subscribers one step behind until the next promotion — the
        durable mirror itself is already committed either way."""
        if self._publisher is None:
            return
        with self._promo_lock:
            step = self._durable_head_step
            if step is None or (
                self._published_step is not None
                and step <= self._published_step
            ):
                return
            self._published_step = step
        try:
            self._publisher.publish_continuous(
                self.durable_store_root, step
            )
        except Exception as e:  # noqa: BLE001 — publication is
            # best-effort; retried implicitly at the next promotion
            obs.swallowed_exception("continuous.publish", e)
            logger.warning(
                "publication of durable step %d failed; subscribers "
                "stay at the previous published step", step,
            )

    def promote(self) -> bool:
        """Force a durable promotion of the newest fully-replicated
        step (outside the every-N cadence; e.g. right before a planned
        scale-down).  Returns False when there is nothing to promote or
        no durable root."""
        with obs.span("continuous/promote"):
            if self.durable_root is None or self._last_step is None:
                return False
            self._join_inflight()
            head = self._target_heads.get(self.local_store_root)
            if head is None:
                return False
            manifest_keys: Set[str] = set()
            with self._promo_lock:
                recent = list(self._recent)
            for s, ks in recent:
                if s == head:
                    manifest_keys = ks
                    break
            if not manifest_keys:
                # the head step fell out of _recent (e.g. a run of
                # failed local writes advanced the list past it): read
                # the keys back from the local store's own manifest —
                # promoting with an EMPTY key set would pin a durable
                # HEAD whose chunks were never copied
                try:
                    m = self._store(
                        self.local_store_root
                    ).read_step_manifest(step_manifest_path(head))
                    manifest_keys = {
                        k
                        for rec in m["leaves"].values()
                        for k in rec["keys"]
                    }
                except Exception as e:  # noqa: BLE001 — refuse rather
                    # than commit a torn durable mirror
                    logger.warning(
                        "promote(): cannot resolve chunk set for head "
                        "step %d (%r); skipping promotion", head, e,
                    )
                    return False
            job = _StepJob(
                step=head,
                t_begin=time.monotonic(),
                target_items={},
                all_keys=manifest_keys,
                manifest_payload=b"",
                head_payload=encode_head(head),
                promote=True,
            )
            self._enqueue_promotion(job)
            return True

    # -------------------------------------------------- drain/close/obs

    def drain(self, deadline: Optional[float] = None) -> bool:
        """Block until the in-flight step replication lands on every
        reachable target; ``deadline`` (monotonic) bounds the wait.
        This is the preemption-notice drain: finishing it inside the
        grace window is what turns "lost the in-flight step" into
        "lost nothing"."""
        with obs.span("continuous/drain"):
            job = self._inflight
            if job is None:
                return True
            timeout = (
                None
                if deadline is None
                else max(0.0, deadline - time.monotonic())
            )
            ok = job.done.wait(timeout)
            if ok:
                self._inflight = None
            return ok

    def _preemption_drain(self, deadline: float) -> None:
        done = self.drain(deadline)
        logger.warning(
            "preemption drain %s (last step %s, peers at %s)",
            "complete" if done else "TIMED OUT",
            self._last_step, self.last_peer_step(),
        )

    def close(self, drain: bool = True) -> None:
        """Stop the loop: optionally drain the in-flight replication,
        stop the worker, clear this rank's heartbeat (publish paired
        with delete), and release the preemption hook."""
        with obs.span("continuous/close"):
            if self._closed:
                return
            self._closed = True
            if drain:
                self.drain()
            if self._worker is not None and self._worker.is_alive():
                self._queue.put(None)
                self._worker.join(timeout=30)
            if self._preemption_handle is not None:
                preemption.remove_handler(self._preemption_handle)
                self._preemption_handle = None
            coord = self._coordinator
            with self._init_lock:
                ns = self._ns
            if coord is not None and ns is not None:
                heartbeat.clear(coord, ns, coord.rank)
            if self._executor is not None:
                self._executor.shutdown(wait=False)
                self._executor = None
            with self._init_lock:
                pool, self._target_pool = self._target_pool, None
                io_loop, self._io_loop = self._io_loop, None
                t, self._transport = self._transport, None
            if pool is not None:
                pool.shutdown(wait=False)
            if io_loop is not None:
                io_loop.shutdown()
            if t is not None:
                try:
                    t.close()
                except Exception as e:  # noqa: BLE001 — best-effort
                    obs.swallowed_exception("continuous.transport", e)
            for store in self._stores.values():
                store.sync_close()
            self._stores.clear()

    def restore_latest(
        self, app_state: Dict[str, Any], strict: bool = True
    ) -> Optional[Dict[str, Any]]:
        """Recover this rank's state from the freshest reachable source
        (local store → peers, different-slice-first → durable mirror);
        see recover.recover_state.  Returns the recovery result dict or
        None on cold start.  When ``peer_roots`` were neither passed
        nor learned yet, they are KV-exchanged here — a fleet-wide
        restart where EVERY rank calls restore_latest before its first
        step (the documented resume flow) reaches its peers' RAM; the
        exchange is collective, so a lone rank recovering out of band
        must pass ``peer_roots`` explicitly instead."""
        with obs.span("continuous/restore_latest"):
            from .recover import recover_state

            peer_stores = []
            if self._replica_roots:
                peer_stores = [
                    self._rank_store_root(r) for r in self._replica_roots
                ]
            else:
                from ..topology import replica_candidate_order

                peers = self._exchange_peer_roots()
                if peers:
                    # recover_state probes every candidate's HEAD and
                    # restores freshest-first, so this order is only
                    # the TIEBREAK among equally-fresh stores; the
                    # shared rule (with its world_size-vs-peer-list
                    # guard) keeps that tiebreak aligned with the
                    # write-side placement and can never IndexError
                    # out of the one path that must not wedge
                    order = replica_candidate_order(
                        self._topology, self._coord.rank, len(peers)
                    )
                    peer_stores = [
                        self._rank_store_root(peers[c])
                        for c in order
                        if peers[c] != self.local_root
                    ]
            return recover_state(
                app_state,
                local=self.local_store_root,
                peers=peer_stores,
                durable=self.durable_store_root,
                strict=strict,
            )

    def last_step(self) -> Optional[int]:
        return self._last_step

    def last_peer_step(self) -> Optional[int]:
        """The newest step EVERY peer target holds completely (the loss
        floor: a host killed now restores at least this step from a
        peer); None before the first replication or without peers."""
        with self._init_lock:
            all_targets = self._targets or ()
        targets = [
            t for t in all_targets if t != self.local_store_root
        ]
        if not targets:
            return None
        heads = [self._target_heads.get(t) for t in targets]
        if any(h is None for h in heads):
            return None
        return min(heads)

    def last_durable_step(self) -> Optional[int]:
        # reconcile any promotion that settled since the last
        # replication job (the final promote()+drain()+close flow ends
        # with no further job to sweep for it)
        if self._pending_promotions():
            self._sweep_promotions()
        with self._promo_lock:
            return self._durable_head_step

    def heartbeats(self) -> Optional[Dict[int, Optional[int]]]:
        """Every rank's last published heartbeat step (None when the
        loop has not exchanged its namespace yet)."""
        coord = self._coordinator
        with self._init_lock:
            ns = self._ns
        if coord is None or ns is None:
            return None
        return heartbeat.read_all(coord, ns, coord.world_size)

    def summary(self) -> Dict[str, Any]:
        """JSON-safe state for flight records / doctor / stats."""
        if self._pending_promotions():
            self._sweep_promotions()
        local_head = self._target_heads.get(self.local_store_root)
        peer_step = self.last_peer_step()
        with self._init_lock:
            targets = self._targets
        with self._promo_lock:
            durable_head = self._durable_head_step
            pending = len(self._promotions)
            target_heads = dict(self._target_heads)
        return {
            "last_step": self._last_step,
            "local_head_step": local_head,
            "last_peer_step": peer_step,
            "last_durable_step": durable_head,
            "replication_lag_steps": (
                max(0, self._last_step - peer_step)
                if self._last_step is not None and peer_step is not None
                else None
            ),
            "peer_targets": max(0, len(targets or ()) - 1),
            "target_heads": {
                root: head
                for root, head in sorted(target_heads.items())
            },
            "promotions_pending": pending,
        }
