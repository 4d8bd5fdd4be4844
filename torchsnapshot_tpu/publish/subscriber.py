"""Serving-side subscription: follow a publication root and hot-swap
new weights in without a cold restart.

A ``Subscriber`` owns one ``LiveWeights`` view of the serving process's
``app_state`` and advances it one published step at a time:

1. **Notice** — wait on the KV announce key for up to a poll interval
   (``coordination.kv_watch``), then ALWAYS verify against the durable
   HEAD marker.  The announce is a latency hint only: a lost announce
   (killed publisher, coordination outage, knob off) degrades to the
   durable poll; a forged/stale announce can never apply anything the
   durable root doesn't hold.  The fanout discipline — degrade, never
   wedge.
2. **Plan** — ``plan_delta`` against the held record: only chunks whose
   content key changed at their offset move, windowed to this
   subscriber's shard for resharding fleets (``shard_spec``).
3. **Fetch** — changed chunks only, grouped per base URL, through the
   scheduler's budget-admitted verified ranged-read engine (and hence
   the host cache, so N subscribers behind one host fetch remote bytes
   once).
4. **Apply** — stage then swap under the generation lock
   (publish/apply.py): no torn mix of steps, and any failure leaves the
   last complete generation serving.

``poll_once`` is the single-step engine; ``follow`` runs it on a daemon
thread with the watch/poll cadence and survives ALL errors (counted,
swallowed, retried next interval).  A cold subscriber (nothing held)
full-fetches through the identical path.  Each swap stamps
``subs/<sub_id>`` in the root (best-effort) so doctor/stats can report
fleet lag without touching the serving processes.
"""

from __future__ import annotations

import logging
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import knobs, obs
from ..coordination import Coordinator, kv_watch
from ..io_types import StoragePlugin
from ..storage import url_to_storage_plugin
from . import announce as announce_mod
from .apply import LiveWeights
from .delta import DeltaPlan, FetchItem, plan_delta
from .record import PublishStore

logger = logging.getLogger(__name__)


class FollowHandle:
    """Returned by ``follow``: stop() ends the watcher thread (joins
    it) and is idempotent."""

    def __init__(self, thread: threading.Thread, stop_event: threading.Event) -> None:
        self._thread = thread
        self._stop = stop_event

    def stop(self, timeout_s: float = 30.0) -> None:
        self._stop.set()
        self._thread.join(timeout=timeout_s)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()


class Subscriber:
    """See module docstring."""

    def __init__(
        self,
        publish_root: str,
        app_state: Dict[str, Any],
        coordinator: Optional[Coordinator] = None,
        sub_id: Optional[str] = None,
        shard_spec: Optional[Dict[str, Tuple]] = None,
        poll_s: float = 2.0,
        priority: int = 0,
        strict: bool = True,
    ) -> None:
        self.root = publish_root.rstrip("/")
        self.live = LiveWeights(app_state)
        self.sub_id = sub_id or f"sub-{uuid.uuid4().hex[:12]}"
        self._coordinator = coordinator
        self._shard_spec = shard_spec
        self._poll_s = max(0.01, float(poll_s))
        self._priority = int(priority)
        self._strict = strict
        self._store = PublishStore(self.root)
        self._ns = announce_mod.ns_for_root(self.root)
        # serializes the poll engine: a caller-thread poll_once racing
        # the follow thread would double-fetch and double-apply the
        # same step; the blocking waits (sleep/kv_watch) stay OUTSIDE
        self._poll_lock = threading.Lock()
        self._held_record: Optional[Dict[str, Any]] = None
        self._last_announce: Optional[str] = None
        # per-base fetch plugins, cached across polls (host cache ON:
        # co-hosted subscribers share one cache fill per remote chunk)
        self._fetch_storage: Dict[str, StoragePlugin] = {}
        self._bytes_fetched_total = 0
        self._closed = False
        # chunk fan-in over the payload transport (transport/): the
        # first co-resident subscriber to durably fetch a chunk
        # publishes it through the collective engine's device registry
        # (content-keyed), and its peers consume that instead of
        # re-fetching — resolved lazily, collective-local engine only
        # (the KV engine would move payload bytes back ONTO the
        # coordination service, the exact channel transport demotes)
        self._transport: Any = None
        self._transport_resolved = False
        # (prefix, nparts) this subscriber published last poll; swept
        # at the next poll / close so content-keyed entries don't
        # accrete across generations
        self._transport_pub: List[Tuple[str, int]] = []

    # ------------------------------------------------------ inspection

    @property
    def step(self) -> Optional[int]:
        return self.live.step

    @property
    def generation(self) -> int:
        return self.live.generation

    def poll_interval_s(self) -> float:
        return self._poll_s

    # ---------------------------------------------------------- engine

    def poll_once(self, wait_s: float = 0.0) -> Optional[int]:
        """One notice→plan→fetch→apply pass; returns the new generation
        if a swap happened, None if already current.  ``wait_s`` > 0
        blocks on the announce key that long first (the follow loop's
        cadence); the durable HEAD is consulted either way, so a dead
        announce channel only costs latency."""
        if self._closed:
            raise RuntimeError("subscriber is closed")
        self._watch_announce(wait_s)
        head = self._store.read_head()
        if head is None:
            return None
        with self._poll_lock:
            held = self._held_record
            if held is not None and int(head["step"]) == int(
                held["step"]
            ):
                return None
            with obs.span(
                "publish/poll",
                root=self.root,
                step=head["step"],
                held=None if held is None else held["step"],
            ):
                record = self._store.read_record(str(head["record"]))
                plan = plan_delta(record, held, self._shard_spec)
                fetched = self._fetch(record, plan)
                t0 = time.monotonic()
                gen = self.live.apply(
                    record, plan, fetched, strict=self._strict
                )
                apply_s = time.monotonic() - t0
                self._held_record = record
                self._account(record, plan, apply_s)
                self._stamp(record, gen)
                return gen

    def follow(
        self,
        on_swap: Optional[Callable[[int, int], Any]] = None,
    ) -> FollowHandle:
        """Start the watcher thread: announce-watch (fast path) + poll
        every interval, forever, surviving every error.  ``on_swap(step,
        generation)`` fires after each committed swap (its errors are
        swallowed too — a bad callback must not kill the watcher)."""
        stop = threading.Event()

        def _loop() -> None:
            while not stop.is_set():
                try:
                    gen = self.poll_once(wait_s=self.poll_interval_s())
                    if gen is not None and on_swap is not None:
                        on_swap(int(self.live.step), gen)
                except Exception as e:  # noqa: BLE001 — the watcher
                    # NEVER dies: count, swallow, retry next interval
                    # with the last complete generation still serving
                    obs.counter(obs.PUBLISH_WATCH_ERRORS).inc()
                    obs.swallowed_exception("publish.subscriber.watch", e)
                    stop.wait(self.poll_interval_s())

        thread = threading.Thread(
            target=_loop, name=f"tsnp-subscriber-{self.sub_id}", daemon=True
        )
        thread.start()
        return FollowHandle(thread, stop)

    def close(self) -> None:
        """Release fetch plugins and the record store.  Does not stop a
        ``follow`` thread — stop the handle first."""
        if self._closed:
            return
        self._closed = True
        with self._poll_lock:
            storages = list(self._fetch_storage.values())
            self._fetch_storage.clear()
            transport, self._transport = self._transport, None
            if transport is not None:
                self._sweep_transport_pub(transport)
        for storage in storages:
            try:
                storage.sync_close()
            except Exception as e:  # noqa: BLE001 — teardown
                obs.swallowed_exception("publish.subscriber.close", e)
        if transport is not None:
            try:
                transport.close()
            except Exception as e:  # noqa: BLE001 — teardown
                obs.swallowed_exception("publish.subscriber.close", e)
        self._store.sync_close()

    # ------------------------------------------------------- internals

    def _watch_announce(self, wait_s: float) -> None:
        """Block on the announce key up to ``wait_s``; remembers the
        raw value so the next watch waits for a CHANGE.  Purely a
        latency device — the caller re-verifies against the durable
        HEAD regardless of what (or whether) the announce said."""
        if wait_s <= 0:
            return
        if (
            self._coordinator is None
            or not knobs.publish_announce_enabled()
        ):
            # no fast path: the durable poll IS the cadence
            time.sleep(wait_s)
            return
        # snapshot the poll state under the lock; the blocking watch
        # itself must NOT hold it (a swap in flight would stall it)
        with self._poll_lock:
            held = self._held_record
            held_step = None if held is None else int(held["step"])
            last = self._last_announce
        cur = announce_mod.current(self._coordinator, self._ns)
        if cur is not None and (
            held_step is None or cur[0] != held_step
        ):
            # already-pending announce: skip the blocking watch
            return
        raw = kv_watch(
            self._coordinator,
            announce_mod.announce_key(self._ns),
            last=last,
            timeout_s=wait_s,
        )
        if raw is None:
            return
        with self._poll_lock:
            self._last_announce = raw
        if announce_mod.parse_announcement(raw) is None:
            # malformed: treat as a plain wake-up; HEAD decides
            return

    def _fanin_transport(self) -> Any:
        """The chunk fan-in transport, or None (no coordinator, or the
        probe landed on an engine without an in-process device
        registry).  Resolved once; failures leave fan-in off."""
        if not self._transport_resolved:
            self._transport_resolved = True
            if self._coordinator is not None:
                from ..transport import resolve_transport

                t = resolve_transport(self._coordinator)
                if getattr(t, "mode", None) == "local":
                    self._transport = t
        return self._transport

    def _fanin_prefix(self, key: str) -> str:
        # content-keyed: co-resident subscribers converge on the same
        # prefix for the same chunk regardless of which leaf/step
        # referenced it
        return f"{self._ns}/xfan/{key}"

    def _sweep_transport_pub(self, transport: Any) -> None:
        """Reclaim last poll's fan-in publications (best-effort)."""
        pub, self._transport_pub = self._transport_pub, []
        for prefix, nparts in pub:
            try:
                transport.cleanup(prefix, nparts)
            except Exception as e:  # noqa: BLE001 — best-effort sweep
                obs.swallowed_exception("publish.subscriber.fanin", e)

    def _fetch(
        self, record: Dict[str, Any], plan: DeltaPlan
    ) -> Dict[Tuple[str, int], bytes]:
        """Fetch every planned chunk, grouped per base URL, through the
        verified ranged-read engine; returns ``(leaf, leaf_off) →
        bytes``.

        With a fan-in transport, content-keyed chunks a co-resident
        subscriber already published are consumed from the device
        registry first (digest-verified); the rest go through the
        durable read engine and are then published for the NEXT
        subscriber's poll.  Every transport anomaly degrades that chunk
        to the durable path — fan-in saves bytes, never gates them."""
        if not plan.fetches:
            return {}
        from .. import scheduler

        transport = self._fanin_transport()
        if transport is not None:
            self._sweep_transport_pub(transport)
        by_base: Dict[str, List[FetchItem]] = {}
        fetched: Dict[Tuple[str, int], bytes] = {}
        for item in plan.fetches:
            if transport is not None and item.key:
                try:
                    blob = transport.try_fetch(
                        self._fanin_prefix(item.key)
                    )
                except Exception as e:  # noqa: BLE001 — registry miss,
                    # digest mismatch, engine failure: durable path
                    obs.swallowed_exception("publish.subscriber.fanin", e)
                    blob = None
                if blob is not None and len(blob) == int(item.nbytes):
                    fetched[(item.leaf, item.leaf_off)] = blob
                    continue
            by_base.setdefault(item.base, []).append(item)
        announce_path = None
        if self._held_record is None:
            announce_path = "cold"
        for base, items in sorted(by_base.items()):
            storage = self._fetch_storage.get(base)
            if storage is None:
                storage = url_to_storage_plugin(base)
                self._fetch_storage[base] = storage
            reads = [
                (item.path, item.byte_range, item.key, item.nbytes)
                for item in items
            ]
            blobs = scheduler.sync_execute_chunk_reads(
                reads,
                storage,
                scheduler.get_process_memory_budget_bytes(),
                priorities=[self._priority] * len(reads),
                span_label="publish/fetch",
            )
            for item, blob in zip(items, blobs):
                fetched[(item.leaf, item.leaf_off)] = blob
                if transport is not None and item.key:
                    try:
                        nparts = transport.publish(
                            self._fanin_prefix(item.key), blob
                        )
                        self._transport_pub.append(
                            (self._fanin_prefix(item.key), nparts)
                        )
                    except Exception as e:  # noqa: BLE001 — fan-in
                        # publication is pure savings for peers
                        obs.swallowed_exception(
                            "publish.subscriber.fanin", e
                        )
        logger.debug(
            "publish fetch step=%s mode=%s: %d chunks, %d bytes from %d bases",
            record["step"],
            announce_path or "delta",
            len(fetched),
            sum(len(b) for b in fetched.values()),
            len(by_base),
        )
        return fetched

    def _account(
        self, record: Dict[str, Any], plan: DeltaPlan, apply_s: float
    ) -> None:
        stats = plan.stats
        self._bytes_fetched_total += int(stats.get("bytes_fetch", 0))
        obs.counter(obs.PUBLISH_SUB_SWAPS).inc()
        obs.counter(obs.PUBLISH_SUB_BYTES_FETCHED).inc(
            int(stats.get("bytes_fetch", 0))
        )
        obs.counter(obs.PUBLISH_SUB_CHUNKS_FETCHED).inc(
            int(stats.get("chunks_fetch", 0))
        )
        obs.counter(obs.PUBLISH_SUB_CHUNKS_REUSED).inc(
            int(stats.get("chunks_reused", 0))
        )
        obs.histogram(obs.PUBLISH_SUB_APPLY_S).observe(apply_s)
        published_t = record.get("t")
        if published_t is not None:
            lag = max(0.0, time.time() - float(published_t))
            obs.histogram(obs.PUBLISH_SUB_LAG_S).observe(lag)
        if self._last_announce is None or (
            announce_mod.parse_announcement(self._last_announce) or (None,)
        )[0] != int(record["step"]):
            # the durable poll delivered what the announce didn't
            obs.counter(obs.PUBLISH_FALLBACK_POLLS).inc()

    def _stamp(self, record: Dict[str, Any], generation: int) -> None:
        self._store.write_stamp(
            self.sub_id,
            {
                "step": int(record["step"]),
                "generation": int(generation),
                "t": time.time(),
                "bytes_fetched": self._bytes_fetched_total,
            },
        )
