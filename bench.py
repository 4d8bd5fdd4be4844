"""Checkpoint benchmark: time-blocked-on-save + restore throughput.

The reference's headline table (benchmarks/ddp/README.md:9-24) reports
save wall-time for a replicated model; its best single-chip number is
20GB / ~13.91s ≈ 1.44 GB/s (A100, local FS).  BASELINE.md names the
north-star for this repo: "checkpoint save+restore GB/s/chip and
time-blocked-on-save" — the latter is what the reference's own torchrec
benchmark prints (benchmarks/torchrec/main.py:147-155), because what a
training job actually pays for a checkpoint is the time the train loop
is blocked, not the time storage I/O takes.

One process: ``main()`` runs the measured phases directly on the chip it
finds.  It exits nonzero — before printing any metric line — when
``jax.devices()[0].platform`` is not ``tpu``; a failed save or restore
phase propagates; a failed host-side probe is recorded in the record and
the run exits nonzero after the record is printed.

Metrics on one chip:

- ``value``            = payload / time-blocked for ``async_take``
  (GB/s/chip).  The TPU-native unblock point is the *dispatch* of one
  batched device→pinned_host DMA (host_offload.eager_offload_write_reqs)
  — safe because jax.Arrays are immutable; the background pipeline
  blocks on the in-flight transfer when it stages.
- ``save_total_gbps``  = payload / wall-time-to-commit — directly
  comparable to the reference's sync save numbers (storage included).
- ``restore_gbps``     = payload / restore wall-time into fresh device
  arrays.
- ``attention``        = pallas flash kernel vs the XLA fallback on the
  ring-attention block shape (VERDICT r1 #2: prove the kernel compiles
  and runs under Mosaic on real hardware, with an honest speedup
  number).  TPU only — CPU interpret mode is not a benchmark.

Payload: bf16 arrays sized adaptively.  Cap 1: 60% of HBM (restore
donates template buffers leaf-by-leaf, so device peak is ~1x payload
plus one leaf).
Cap 2: what the measured host↔device link can move in ~100s.  The JSON
result line is printed INCREMENTALLY (after save, after restore, after
the attention bench); the last line is the most complete record.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

BASELINE_GBPS = 20.0 / 13.91  # reference: 1 node x 1 GPU, local FS
METRIC = "async_save_blocked_throughput"
_OUT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "chiprun_out"
)  # the one directory the chip tool copies back


def _time_op(fn, iters: int = 5, warmup: int = 2) -> float:
    """Median-free simple timing: best of ``iters`` after warmup."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _attention_bench() -> dict:
    """Flash (pallas/Mosaic) vs XLA dense attention on one chip."""
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu.ops.flash_attention import flash_attention
    from torchsnapshot_tpu.parallel.ring_attention import dense_attention

    b, s, h, d = 4, 2048, 8, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (
        jax.random.normal(kk, (b, s, h, d), jnp.bfloat16) for kk in keys
    )
    flash_s = _time_op(lambda: flash_attention(q, k, v, causal=True))
    xla = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=True))
    xla_s = _time_op(lambda: xla(q, k, v))
    result = {
        "pallas_compiled": True,
        "shape": [b, s, h, d],
        "flash_ms": round(flash_s * 1e3, 3),
        "xla_dense_ms": round(xla_s * 1e3, 3),
        "flash_speedup": round(xla_s / flash_s, 3),
    }
    # fwd+bwd: exercises the flash-tiled pallas backward kernels
    from torchsnapshot_tpu import knobs

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2
        )

    with knobs.override_pallas_attention("1"):
        g_flash = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        grad_flash_s = _time_op(lambda: g_flash(q, k, v))
    with knobs.override_pallas_attention("0"):
        g_xla = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        grad_xla_s = _time_op(lambda: g_xla(q, k, v))
    result.update(
        {
            "grad_flash_ms": round(grad_flash_s * 1e3, 3),
            "grad_xla_bwd_ms": round(grad_xla_s * 1e3, 3),
            "grad_speedup": round(grad_xla_s / grad_flash_s, 3),
        }
    )
    return result


def _lint_probe() -> dict:
    """Current snaplint rollup (tools/lint) for the BENCH record: the
    static-analysis finding trajectory belongs next to the perf numbers
    so a PR that buys speed with hygiene debt shows both moves.  Pure
    AST work on host — cannot perturb the measured phases."""
    repo = os.path.dirname(os.path.abspath(__file__))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.lint import repo_summary

    return repo_summary(repo)


def _goodput_rollup() -> dict:
    """Goodput/SLO block for the BENCH record (obs/goodput.py):
    time-to-unblock-train, take→durable-commit lag (covers write-back
    promotion) and the checkpoint overhead fraction — the numbers that
    say what the headline throughput COST the training loop.  Reads the
    in-process tracker + gauges; no I/O."""
    from torchsnapshot_tpu import obs

    block = obs.goodput.block()
    gauges = obs.metrics_snapshot().get("gauges", {})
    for key, name in (
        ("time_to_unblock_s", obs.GOODPUT_TIME_TO_UNBLOCK_S),
        ("durability_lag_s", obs.GOODPUT_DURABILITY_LAG_S),
        ("overhead_fraction", obs.GOODPUT_OVERHEAD_FRACTION),
    ):
        g = gauges.get(name)
        if block.get(key) is None and g is not None:
            block[key] = g["value"]
    return block


def _resilience_rollup() -> dict:
    """Retry/abort/breaker counters for the BENCH record: a perf number
    earned while the retry engine was quietly eating SlowDowns (or a
    breaker was open) is a different datum than the same number on a
    healthy backend — the rollup makes that visible next to the
    headline.  Reads the live metrics registry; no I/O."""
    from torchsnapshot_tpu import obs

    snap = obs.metrics_snapshot()
    counters = snap.get("counters", {})
    out = {
        "retries": counters.get(obs.RESILIENCE_RETRIES, 0),
        "aborts": counters.get(obs.RESILIENCE_ABORTS, 0),
        "failpoints_fired": counters.get(obs.RESILIENCE_FAILPOINTS_FIRED, 0),
        "breaker_trips": counters.get(obs.RESILIENCE_BREAKER_TRIPS, 0),
        "retries_by_backend": {
            name.split(".")[1]: v
            for name, v in counters.items()
            if name.startswith("resilience.")
            and name.endswith(".retries")
            and name.count(".") == 2  # not the total "resilience.retries"
        },
        "breaker_state": {
            name.split("resilience.breaker_state.", 1)[1]: g["value"]
            for name, g in snap.get("gauges", {}).items()
            if name.startswith("resilience.breaker_state.")
        },
    }
    hist = snap.get("histograms", {}).get(obs.RESILIENCE_BACKOFF_DELAY_S)
    if hist and hist.get("count"):
        out["backoff_delay_s"] = {
            k: hist[k] for k in ("count", "sum", "min", "max")
        }
    return out


def _transport_rollup() -> dict:
    """Payload-transport engine counters for the BENCH record
    (transport/): which engine the round's redistribution bytes rode,
    how many ops degraded mid-flight, and the per-engine byte totals —
    the fan-out probe's per-leg numbers are relative deltas, this is
    the round's absolute footprint.  Reads the live metrics registry;
    no I/O."""
    from torchsnapshot_tpu import obs
    from torchsnapshot_tpu.transport import current_engine

    counters = obs.metrics_snapshot().get("counters", {})
    return {
        "engine": current_engine() or "unresolved",
        "collective_ops": counters.get(obs.TRANSPORT_COLLECTIVE_OPS, 0),
        "collective_bytes": counters.get(
            obs.TRANSPORT_COLLECTIVE_BYTES, 0
        ),
        "kv_ops": counters.get(obs.TRANSPORT_KV_OPS, 0),
        "kv_bytes": counters.get(obs.TRANSPORT_KV_BYTES, 0),
        "fallbacks": counters.get(obs.TRANSPORT_FALLBACKS, 0),
        "device_moves": counters.get(obs.TRANSPORT_DEVICE_MOVES, 0),
        "swept_parts": counters.get(obs.TRANSPORT_SWEPT_PARTS, 0),
    }


def _tier_probe(payload_mb: int = 32) -> dict:
    """Small write-back tiered roundtrip on local dirs (host arrays
    only — never touches the device mid-bench): records fast-tier
    hit/miss/repair counts, the promotion lag, and the fast-vs-durable
    restore latencies so the tier's restore-latency win (and promotion
    health) shows up in the BENCH trajectory."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, drain_promotions, obs

    root = tempfile.mkdtemp(prefix="tsnp_bench_tier_")
    fast = os.path.join(root, "fast")
    durable = os.path.join(root, "durable")
    opts = {"tier": {"fast_url": fast, "policy": "write_back"}}
    n = payload_mb * (1 << 20) // 8
    out: dict = {"payload_mb": payload_mb, "policy": "write_back"}
    try:
        c0 = obs.metrics_snapshot()["counters"]
        t0 = time.perf_counter()
        Snapshot.take(
            durable,
            {"m": StateDict(w=np.arange(n, dtype=np.float64))},
            storage_options=opts,
        )
        out["save_ack_s"] = round(time.perf_counter() - t0, 4)
        drain_promotions()
        out["save_durable_s"] = round(time.perf_counter() - t0, 4)
        dest = {"m": StateDict(w=np.zeros(n, dtype=np.float64))}
        t0 = time.perf_counter()
        Snapshot(durable, storage_options=opts).restore(dest)
        out["restore_fast_s"] = round(time.perf_counter() - t0, 4)
        shutil.rmtree(fast)  # lost-host shape: durable fallback + repair
        dest = {"m": StateDict(w=np.zeros(n, dtype=np.float64))}
        t0 = time.perf_counter()
        Snapshot(durable, storage_options=opts).restore(dest)
        out["restore_durable_fallback_s"] = round(
            time.perf_counter() - t0, 4
        )
        c1 = obs.metrics_snapshot()["counters"]
        for name in (
            "tier.fast_hits",
            "tier.fast_misses",
            "tier.fast_repairs",
            "tier.bytes_promoted",
        ):
            out[name.removeprefix("tier.")] = c1.get(name, 0) - c0.get(
                name, 0
            )
        lag = obs.metrics_snapshot()["histograms"].get(
            "tier.promotion_lag_s"
        )
        if lag and lag.get("count"):
            out["promotion_lag_max_s"] = round(lag["max"], 4)
        # durable-tier bytes actually written (post-promotion du):
        # the storage-cost axis the codec layer exists to shrink —
        # tracked per BENCH round so compression regressions surface
        durable_bytes = 0
        for dirpath, _dirs, files in os.walk(durable):
            for f in files:
                try:
                    durable_bytes += os.path.getsize(
                        os.path.join(dirpath, f)
                    )
                except OSError:
                    pass
        out["durable_bytes_written"] = durable_bytes
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def _cas_probe(steps: int = 6, emb_mb: int = 24, dense_mb: int = 4) -> dict:
    """Content-addressed incremental checkpointing on a synthetic
    training loop with realistic update sparsity: a dense optimizer
    step (every byte changes every step) plus sparse embedding-row
    updates (~2% of rows per step, zipf-skewed over a
    popularity-sorted table — recommender reality: hot ids dominate
    and cluster, which is what gives chunk-level dedup its locality)
    plus frozen params.  Records the
    bytes-written-per-step curve, the achieved dedup ratio
    (logical / written), and the effective step cost — the axis that
    turns "checkpoint every N minutes" into "checkpoint every step
    with bounded bytes".  Host arrays + local dirs only."""
    import numpy as np

    from torchsnapshot_tpu import SnapshotManager, StateDict, knobs, obs

    rng = np.random.default_rng(7)
    root = tempfile.mkdtemp(prefix="tsnp_bench_cas_")
    emb_rows = emb_mb * (1 << 20) // (256 * 8)
    emb = rng.standard_normal((emb_rows, 256))
    dense = rng.standard_normal(dense_mb * (1 << 20) // 8)
    frozen = rng.standard_normal(dense_mb * (1 << 20) // 8)
    out: dict = {
        "steps": steps,
        "emb_mb": emb_mb,
        "dense_mb": dense_mb,
        "sparsity": 0.02,
        "per_step": [],
    }
    logical = emb.nbytes + dense.nbytes + frozen.nbytes
    out["logical_step_bytes"] = logical
    try:
        mgr = SnapshotManager(os.path.join(root, "run"), cas=True)
        with knobs.override_cas_chunk_size_bytes(1 << 20):
            for step in range(1, steps + 1):
                # dense optimizer state: fully updated
                dense += rng.standard_normal(dense.shape) * 1e-3
                # sparse embedding update: ~2% of rows, zipf-skewed
                # toward the head of the popularity-sorted table
                n_touch = max(1, int(emb_rows * 0.02))
                touched = np.unique(
                    np.minimum(
                        rng.zipf(1.6, n_touch) - 1, emb_rows - 1
                    )
                )
                emb[touched] += rng.standard_normal((len(touched), 256))
                c0 = obs.metrics_snapshot()["counters"]
                t0 = time.perf_counter()
                mgr.save(
                    {
                        "m": StateDict(
                            emb=emb, dense=dense, frozen=frozen
                        )
                    },
                    step=step,
                )
                dt = time.perf_counter() - t0
                c1 = obs.metrics_snapshot()["counters"]
                written = c1.get("cas.bytes_written", 0) - c0.get(
                    "cas.bytes_written", 0
                )
                shared = c1.get("cas.bytes_shared", 0) - c0.get(
                    "cas.bytes_shared", 0
                )
                out["per_step"].append(
                    {
                        "step": step,
                        "bytes_written": written,
                        "bytes_shared": shared,
                        "save_s": round(dt, 4),
                        "dedup_ratio": (
                            round((written + shared) / written, 3)
                            if written
                            else None
                        ),
                    }
                )
        steady = out["per_step"][1:]  # step 1 is the cold full write
        tot_written = sum(s["bytes_written"] for s in steady)
        out["steady_state_bytes_per_step"] = (
            tot_written // len(steady) if steady else 0
        )
        out["dedup_ratio"] = (
            round(logical * len(steady) / tot_written, 3)
            if tot_written
            else None
        )
        out["bytes_written_fraction_of_full"] = (
            round(out["steady_state_bytes_per_step"] / logical, 4)
            if logical
            else None
        )
        # refcounted GC spot-check rides the probe: delete the MIDDLE
        # step and prove the chain stays restorable (chain-correctness
        # regressions should surface in BENCH, not only in tests)
        mid = steps // 2
        from torchsnapshot_tpu import delete_snapshot

        delete_snapshot(
            mgr.path_for_step(mid), metadata=mgr.snapshot(mid).metadata
        )
        ok = mgr.snapshot(steps).verify(deep=False).ok
        out["middle_delete_chain_ok"] = bool(ok)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def _continuous_probe(steps: int = 8, emb_mb: int = 12, dense_mb: int = 2) -> dict:
    """Continuous per-step checkpointing (continuous/): a synthetic
    training loop (dense optimizer state fully updating + ~2%
    zipf-sparse embedding rows + frozen params, the cas probe's
    realism) run twice — checkpoint-free baseline vs with a
    ContinuousCheckpointer replicating each step's delta to a peer
    root.  Reports the steady-state per-step overhead fraction via the
    EXISTING goodput.overhead_fraction gauge (the loop's blocked
    digest+stage window over wall time), per-step replication lag and
    bytes moved vs skipped, then the headline robustness axis: the
    measured RTO of a simulated host kill — local store wiped, recover
    from the peer — against a durable cold restore in the same harness
    (durable GETs pay an injected 25ms cloud-RTT delay).  Host arrays +
    local dirs only."""
    import numpy as np

    from torchsnapshot_tpu import (
        ContinuousCheckpointer,
        StateDict,
        knobs,
        obs,
        recover_state,
    )
    from torchsnapshot_tpu.obs import goodput
    from torchsnapshot_tpu.tier.promoter import drain_promotions

    rng = np.random.default_rng(23)
    root = tempfile.mkdtemp(prefix="tsnp_bench_continuous_")
    emb_rows = emb_mb * (1 << 20) // (256 * 8)
    dense_n = dense_mb * (1 << 20) // 8

    def make_state():
        return {
            "m": StateDict(
                emb=rng.standard_normal((emb_rows, 256)),
                dense=rng.standard_normal(dense_n),
                frozen=rng.standard_normal(dense_n),
            )
        }

    def mutate(state):
        state["m"]["dense"] += rng.standard_normal(dense_n) * 1e-3
        n_touch = max(1, int(emb_rows * 0.02))
        touched = np.unique(
            np.minimum(rng.zipf(1.6, n_touch) - 1, emb_rows - 1)
        )
        state["m"]["emb"][touched] += rng.standard_normal(
            (len(touched), 256)
        )

    out: dict = {
        "steps": steps,
        "emb_mb": emb_mb,
        "dense_mb": dense_mb,
        "sparsity": 0.02,
        "durable_get_delay_ms": 25,
    }
    logical = (emb_rows * 256 + 2 * dense_n) * 8
    out["logical_step_bytes"] = logical
    try:
        # checkpoint-free baseline: the mutation cost alone
        state = make_state()
        t0 = time.perf_counter()
        for _ in range(steps):
            mutate(state)
        out["baseline_step_s"] = round(
            (time.perf_counter() - t0) / steps, 6
        )
        # continuous leg (fresh goodput window so overhead_fraction is
        # THIS loop's number — the probe runs after the main record's
        # goodput block was already captured)
        goodput.reset()
        local = os.path.join(root, "local")
        peer = os.path.join(root, "peer")
        durable = os.path.join(root, "durable")
        cc = ContinuousCheckpointer(
            local,
            durable_root=durable,
            replica_roots=[peer],
            promote_every_n=max(2, steps // 2),
            chunk_size_bytes=1 << 20,
        )
        state = make_state()
        per_step = []
        # simulated forward/backward compute per step: without it the
        # loop is back-to-back step() calls and overhead_fraction
        # degenerates to ~1 regardless of how cheap the blocked window
        # is; 60ms models a small-model step and makes the fraction an
        # honest "share of training lost"
        compute_s = 0.06
        out["simulated_compute_s"] = compute_s
        c_prev = obs.metrics_snapshot()["counters"]
        t_loop0 = time.perf_counter()
        try:
            for s in range(1, steps + 1):
                mutate(state)
                time.sleep(compute_s)
                t1 = time.perf_counter()
                cc.step(state, s)
                blocked = time.perf_counter() - t1
                c_now = obs.metrics_snapshot()["counters"]
                per_step.append(
                    {
                        "step": s,
                        "blocked_s": round(blocked, 6),
                        "bytes_replicated": c_now.get(
                            "continuous.bytes_replicated", 0
                        )
                        - c_prev.get("continuous.bytes_replicated", 0),
                        "bytes_skipped": c_now.get(
                            "continuous.bytes_skipped", 0
                        )
                        - c_prev.get("continuous.bytes_skipped", 0),
                    }
                )
                c_prev = c_now
            cc.drain()
            drain_promotions(raise_on_error=False)
            out["wall_s"] = round(time.perf_counter() - t_loop0, 6)
            out["per_step"] = per_step
            steady = per_step[1:]
            out["steady_state_blocked_s"] = (
                round(
                    sum(p["blocked_s"] for p in steady) / len(steady), 6
                )
                if steady
                else None
            )
            # the acceptance gauge: goodput.overhead_fraction as set by
            # the loop's own take_begin/take_unblocked accounting
            out["overhead_fraction"] = obs.gauge(
                "goodput.overhead_fraction"
            ).value
            lag = (
                obs.metrics_snapshot()["histograms"].get(
                    "continuous.replication_lag_s"
                )
                or {}
            )
            out["replication_lag_s"] = {
                "count": lag.get("count"),
                "mean": (
                    round(lag["sum"] / lag["count"], 6)
                    if lag.get("count")
                    else None
                ),
                "max": lag.get("max"),
            }
        finally:
            cc.close()
        # RTO leg: the host dies (local store wiped), the replacement
        # restores from the peer; durable cold restore for comparison
        shutil.rmtree(local, ignore_errors=True)
        dest = make_state()
        res_peer = recover_state(
            dest, peers=[os.path.join(peer, "r0")]
        )
        out["rto_peer_s"] = (
            round(res_peer["seconds"], 6) if res_peer else None
        )
        out["rto_peer_step"] = res_peer["step"] if res_peer else None
        out["lost_steps"] = (
            steps - res_peer["step"] if res_peer else None
        )
        dest2 = make_state()
        with knobs.override_failpoints("storage.fs.read=delay25"):
            res_durable = recover_state(
                dest2, durable=os.path.join(durable, "r0")
            )
        out["rto_durable_cold_s"] = (
            round(res_durable["seconds"], 6) if res_durable else None
        )
        out["rto_durable_step"] = (
            res_durable["step"] if res_durable else None
        )
        if res_peer and res_durable and res_peer["seconds"] > 0:
            out["rto_speedup"] = round(
                res_durable["seconds"] / res_peer["seconds"], 2
            )
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def _publish_probe(
    steps: int = 8, emb_mb: int = 12, dense_mb: int = 2, n_subs: int = 3
) -> dict:
    """Live weight publication (publish/): a synthetic trainer
    (the continuous probe's realism — dense optimizer state fully
    updating + ~2% zipf-sparse embedding rows + frozen params)
    publishing per-step deltas to a publication root while three
    in-process subscribers behind one host cache hot-swap their
    serving copies.  Reports the cold-subscribe cost (the full
    restore every new replica pays exactly once), then the headline
    axis: steady-state delta bytes per update vs that full-restore
    baseline — the probe asserts < 0.5x at 2% sparsity, the reason
    the subsystem exists — plus publish->all-swapped propagation
    lag.  Host arrays + local dirs only."""
    import numpy as np

    from torchsnapshot_tpu import StateDict, knobs, obs
    from torchsnapshot_tpu.publish import Publisher, Subscriber

    rng = np.random.default_rng(29)
    root = tempfile.mkdtemp(prefix="tsnp_bench_publish_")
    emb_rows = emb_mb * (1 << 20) // (256 * 8)
    dense_n = dense_mb * (1 << 20) // 8

    def make_state():
        return {
            "m": StateDict(
                emb=rng.standard_normal((emb_rows, 256)),
                dense=rng.standard_normal(dense_n),
                frozen=rng.standard_normal(dense_n),
            )
        }

    def mutate(state):
        state["m"]["dense"] += rng.standard_normal(dense_n) * 1e-3
        n_touch = max(1, int(emb_rows * 0.02))
        touched = np.unique(
            np.minimum(rng.zipf(1.6, n_touch) - 1, emb_rows - 1)
        )
        state["m"]["emb"][touched] += rng.standard_normal(
            (len(touched), 256)
        )

    logical = (emb_rows * 256 + 2 * dense_n) * 8
    out: dict = {
        "steps": steps,
        "emb_mb": emb_mb,
        "dense_mb": dense_mb,
        "n_subscribers": n_subs,
        "sparsity": 0.02,
        "full_restore_bytes": logical,
    }

    def _fetched(counters: dict) -> int:
        return counters.get("publish.subscriber_bytes_fetched", 0)

    subs: list = []
    pub = None
    try:
        cache_dir = os.path.join(root, "hostcache")
        pub_root = os.path.join(root, "pub")
        # 64 KiB chunks: small enough that a 2% zipf row touch dirties
        # a minority of embedding chunks, the regime publication's
        # delta restore is built for
        with knobs.override_cache_dir(cache_dir):
            pub = Publisher(pub_root, chunk_size_bytes=1 << 16)
            state = make_state()
            pub.publish_state(state, 1)
            c0 = obs.metrics_snapshot()["counters"]
            t0 = time.perf_counter()
            subs = [
                Subscriber(pub_root, make_state(), sub_id=f"bench-{i}")
                for i in range(n_subs)
            ]
            for s in subs:
                s.poll_once()
            out["cold_subscribe_s"] = round(time.perf_counter() - t0, 6)
            c_prev = obs.metrics_snapshot()["counters"]
            out["cold_bytes_per_subscriber"] = (
                _fetched(c_prev) - _fetched(c0)
            ) // n_subs
            per_step = []
            for step in range(2, steps + 2):
                mutate(state)
                t1 = time.perf_counter()
                pub.publish_state(state, step)
                publish_s = time.perf_counter() - t1
                t2 = time.perf_counter()
                for s in subs:
                    got = s.poll_once()
                    assert got == step, (got, step)
                swap_all_s = time.perf_counter() - t2
                c_now = obs.metrics_snapshot()["counters"]
                per_step.append(
                    {
                        "step": step,
                        "publish_s": round(publish_s, 6),
                        "swap_all_s": round(swap_all_s, 6),
                        "bytes_fetched_per_subscriber": (
                            _fetched(c_now) - _fetched(c_prev)
                        )
                        // n_subs,
                    }
                )
                c_prev = c_now
            out["per_step"] = per_step
            out["generations"] = [s.generation for s in subs]
            steady = per_step[1:]
            mean_delta = sum(
                p["bytes_fetched_per_subscriber"] for p in steady
            ) / len(steady)
            out["steady_state_bytes_per_update"] = int(mean_delta)
            out["delta_over_full"] = round(mean_delta / logical, 4)
            out["swap_all_s_mean"] = round(
                sum(p["swap_all_s"] for p in steady) / len(steady), 6
            )
            # the acceptance bound: a delta restore at 2% row sparsity
            # must move well under half of a full restore, else the
            # subsystem is just a slow cold restart
            assert mean_delta < 0.5 * logical, (mean_delta, logical)
    finally:
        for s in subs:
            s.close()
        if pub is not None:
            pub.close()
        shutil.rmtree(root, ignore_errors=True)
    return out


def _page_cache_resident_bytes(path: str) -> int:
    """Bytes of ``path`` currently resident in the page cache, via
    mincore(2) over a transient PROT_READ mapping (mapping + mincore
    never fault pages in).  -1 when mincore is unavailable."""
    import ctypes
    import mmap as _mmap

    size = os.path.getsize(path)
    if size == 0:
        return 0
    npages = (size + _mmap.PAGESIZE - 1) // _mmap.PAGESIZE
    import numpy as np

    with open(path, "rb") as f:
        mm = _mmap.mmap(f.fileno(), size, prot=_mmap.PROT_READ)
        arr = None
        try:
            # address of the (read-only) mapping without faulting it in
            arr = np.frombuffer(mm, dtype=np.uint8)
            vec = (ctypes.c_ubyte * npages)()
            libc = ctypes.CDLL(None, use_errno=True)
            rc = libc.mincore(
                ctypes.c_void_p(arr.ctypes.data),
                ctypes.c_size_t(size),
                vec,
            )
            if rc != 0:
                return -1
            return sum(1 for b in vec if b & 1) * _mmap.PAGESIZE
        except (OSError, AttributeError, ValueError):
            return -1
        finally:
            del arr  # release the buffer export so close() can succeed
            mm.close()


def _evict_page_cache(path: str) -> None:
    """Best-effort drop of ``path``'s cached pages (fsync first so
    DONTNEED isn't blocked on dirty pages)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    finally:
        os.close(fd)


def _serving_probe(
    n_readers: int = 6, objects: int = 4, obj_mb: int = 8
) -> dict:
    """Serving cold-start: N concurrent read_object clients against one
    snapshot through the shared-host object cache.  The durable tier is
    the memory plugin with a per-GET injected delay (cloud-latency
    stand-in, deterministic), so the cache's value prop is measurable:
    the COLD leg pays one delayed durable GET per object fleet-wide
    (single-flight), the WARM leg serves everything from local
    mmap-backed cache files.  Reports per-read p50/p99 latency and
    aggregate GB/s per leg, the durable GET counts, and the achieved
    dedup factor (total reads / durable GETs — N readers sharing one
    fill = N).  warm_over_cold_gbps approaches the dedup factor as
    durable latency dominates; on the 2-core sandbox it saturates
    earlier at the local-serve CPU ceiling (~4x for 6 readers — the
    same ceiling the stripe probe documents), while the GET counts
    prove the full factor.  Second half: the mmap-vs-copy RSS delta
    of a raw fs materialize (the zero-copy acceptance gauge).  Host
    arrays + local dirs only."""
    import threading

    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, knobs, obs
    from torchsnapshot_tpu.io_types import is_mmap_backed
    from torchsnapshot_tpu.rss_profiler import measure_rss_deltas
    from torchsnapshot_tpu.storage.memory import reset_namespace

    ns = f"bench_serving_{os.getpid()}"
    root = tempfile.mkdtemp(prefix="tsnp_bench_serving_")
    cache_dir = os.path.join(root, "cache")
    rng = np.random.default_rng(11)
    n = obj_mb * (1 << 20) // 8
    state = StateDict(
        **{f"l{i}": rng.standard_normal(n) for i in range(objects)}
    )
    leg_bytes = objects * n * 8 * n_readers
    out: dict = {
        "readers": n_readers,
        "objects": objects,
        "object_mb": obj_mb,
        "durable_get_delay_ms": 100,
    }

    def leg() -> dict:
        lat: list = []
        lock = threading.Lock()
        barrier = threading.Barrier(n_readers)
        errors: list = []

        def reader() -> None:
            try:
                snap = Snapshot(f"memory://{ns}")
                snap.metadata  # metadata GET outside the timed reads
                barrier.wait()
                mine = []
                for i in range(objects):
                    t0 = time.perf_counter()
                    arr = np.asarray(snap.read_object(f"0/m/l{i}"))
                    # touch one element per page: an mmap serve must
                    # actually fault its bytes in to count as read
                    float(arr[::512].sum())
                    mine.append(time.perf_counter() - t0)
                with lock:
                    lat.extend(mine)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=reader) for _ in range(n_readers)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        if errors:
            raise errors[0]
        lat.sort()
        return {
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
            "p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 3),
            "aggregate_gbps": round(leg_bytes / 1e9 / elapsed, 3),
        }

    try:
        with knobs.override_disable_batching(True):
            Snapshot.take(f"memory://{ns}", {"m": state})
        with knobs.override_cache_dir(cache_dir), (
            knobs.override_failpoints("storage.memory.read=delay100:1")
        ):
            c0 = obs.metrics_snapshot()["counters"]
            out["cold"] = leg()
            c1 = obs.metrics_snapshot()["counters"]
            out["warm"] = leg()
            c2 = obs.metrics_snapshot()["counters"]
        for name, a, b in (("cold", c0, c1), ("warm", c1, c2)):
            out[name]["durable_gets"] = b.get(
                "storage.cache.misses", 0
            ) - a.get("storage.cache.misses", 0)
            out[name]["singleflight_waits"] = b.get(
                "storage.cache.singleflight_waits", 0
            ) - a.get("storage.cache.singleflight_waits", 0)
        total_reads = n_readers * objects
        out["dedup_factor"] = (
            round(total_reads / out["cold"]["durable_gets"], 2)
            if out["cold"]["durable_gets"]
            else None
        )
        out["warm_over_cold_gbps"] = (
            round(
                out["warm"]["aggregate_gbps"]
                / out["cold"]["aggregate_gbps"],
                2,
            )
            if out["cold"]["aggregate_gbps"]
            else None
        )
        # ------- zero-copy leg: mmap vs copy materialize RSS deltas
        fs_root = os.path.join(root, "snap")
        big = rng.standard_normal((64 << 20) // 8)
        Snapshot.take(fs_root, {"m": StateDict(w=big)})
        deltas_copy: list = []
        with knobs.override_mmap(0):
            with measure_rss_deltas(deltas_copy, interval_s=0.01):
                ref = Snapshot(fs_root).materialize(rank=0)
        del ref
        deltas_mmap: list = []
        with measure_rss_deltas(deltas_mmap, interval_s=0.01):
            ref = Snapshot(fs_root).materialize(rank=0)
        out["mmap_materialize"] = {
            "payload_mb": 64,
            "mmap_backed": bool(is_mmap_backed(ref["m"]["w"])),
            "rss_peak_copy_mb": round(max(deltas_copy) / 1e6, 1),
            "rss_peak_mmap_mb": round(max(deltas_mmap) / 1e6, 1),
        }
        del ref
        # ------- O_DIRECT cold-restore leg (storage/fastio.py): the
        # page-cache-bypass claim, MEASURED — restore the same fs
        # snapshot buffered vs FASTIO_DIRECT=1 (mmap off: this is the
        # copying cold path a codec/CAS restore takes) and gauge the
        # payload's page-cache residency (mincore) plus restore RSS
        # after each leg.  A direct restore must leave (near-)zero
        # payload pages in the cache — the serving cold start stops
        # evicting the model it is loading.
        payload = max(
            (
                os.path.join(dp, fn)
                for dp, _dn, fns in os.walk(fs_root)
                for fn in fns
            ),
            key=os.path.getsize,
        )
        # the gauge only means something when the engine can actually
        # take the direct leg — probe BOTH the filesystem and the
        # engine (no toolchain / stale .so / FASTIO=0 must not report
        # a "measured" bypass that ran the buffered path twice)
        from torchsnapshot_tpu.storage.fs import FSStoragePlugin

        with knobs.override_fastio_direct(1):
            probe_plugin = FSStoragePlugin(fs_root)
        engine_direct_ok = bool(
            probe_plugin._fastio is not None and probe_plugin._fastio.direct
        )
        direct_res: dict = {
            "payload_mb": 64,
            "o_direct_supported": engine_direct_ok,
        }
        for leg_name, want_direct in (("buffered", 0), ("direct", 1)):
            _evict_page_cache(payload)
            before_mb = _page_cache_resident_bytes(payload) / 1e6
            deltas: list = []
            with knobs.override_mmap(0), (
                knobs.override_fastio_direct(want_direct)
            ):
                with measure_rss_deltas(deltas, interval_s=0.01):
                    ref = Snapshot(fs_root).materialize(rank=0)
            del ref
            direct_res[leg_name] = {
                "page_cache_resident_before_mb": round(before_mb, 1),
                "page_cache_resident_after_mb": round(
                    _page_cache_resident_bytes(payload) / 1e6, 1
                ),
                "rss_peak_mb": round(max(deltas) / 1e6, 1),
            }
        if direct_res["o_direct_supported"]:
            direct_res["page_cache_savings_mb"] = round(
                direct_res["buffered"]["page_cache_resident_after_mb"]
                - direct_res["direct"]["page_cache_resident_after_mb"],
                1,
            )
        out["fastio_direct_restore"] = direct_res
    finally:
        reset_namespace(ns)
        shutil.rmtree(root, ignore_errors=True)
    return out


def _fanout_probe(
    slices: int = 2, ranks_per_slice: int = 3, objects: int = 4,
    obj_mb: int = 2,
) -> dict:
    """Hierarchical multislice checkpointing probe (topology/).

    Read side — SIMULATED N-process restore: S×R FileCoordinator
    thread-ranks restore one snapshot of K replicated objects with the
    fan-out ON (explicit topology spec).  The probe counts actual
    durable-tier GETs for shared objects and asserts the multislice
    contract: **O(objects) per slice, not O(objects × ranks)** —
    ``durable_gets`` must equal K × S while a flat restore issues
    K × R × S.  Also reports peer-served reads, redistributed bytes and
    wall-clock for the fan-out vs flat legs.

    Write side — per-slice durable egress balance of the topology-aware
    replicated-write partition (pure planning, zero I/O): max/min
    per-slice byte load over a skewed item set, topology-aware vs
    flat."""
    import tempfile
    import threading

    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, knobs, obs
    from torchsnapshot_tpu.coordination import FileCoordinator
    from torchsnapshot_tpu.partitioner import partition_replicated_writes
    from torchsnapshot_tpu.topology import Topology

    world = slices * ranks_per_slice
    spec = ",".join(str(r // ranks_per_slice) for r in range(world))
    root = tempfile.mkdtemp(prefix="tsnp_bench_fanout_")
    snap = os.path.join(root, "snap")
    n = obj_mb * (1 << 20) // 4
    state = {
        "m": StateDict(
            **{
                f"l{i}": np.arange(n, dtype=np.float32) * (i + 1)
                for i in range(objects)
            }
        )
    }
    out: dict = {
        "slices": slices,
        "ranks_per_slice": ranks_per_slice,
        "objects": objects,
        "object_mb": obj_mb,
    }

    def leg(topology_spec, kv_sub) -> dict:
        import zlib

        errors: list = []
        digests: dict = {}

        def worker(r):
            try:
                dest = {
                    "m": StateDict(
                        **{
                            f"l{i}": np.zeros(n, np.float32)
                            for i in range(objects)
                        }
                    )
                }
                coord = FileCoordinator(
                    os.path.join(root, kv_sub), r, world
                )
                Snapshot(snap, coordinator=coord).restore(dest)
                # bitwise identity across ranks AND engines: the
                # payload-transport engine may change where bytes
                # travel, never what arrives
                digests[r] = zlib.crc32(
                    b"".join(
                        dest["m"][f"l{i}"].tobytes()
                        for i in range(objects)
                    )
                )
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        c0 = obs.metrics_snapshot()["counters"]
        threads = [
            threading.Thread(target=worker, args=(r,))
            for r in range(world)
        ]
        t0 = time.perf_counter()
        ctx = (
            knobs.override_topology(topology_spec)
            if topology_spec
            else knobs.override_topology("flat")
        )
        with ctx:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        elapsed = time.perf_counter() - t0
        if errors:
            raise errors[0]
        if len(set(digests.values())) > 1:
            raise AssertionError(
                f"restored payloads diverged across ranks: {digests}"
            )
        c1 = obs.metrics_snapshot()["counters"]

        def d(name):
            return c1.get(name, 0) - c0.get(name, 0)

        moved = {
            "collective": d("transport.collective_bytes"),
            "kv": d("transport.kv_bytes"),
        }
        return {
            "elapsed_s": round(elapsed, 3),
            "durable_gets": d("topology.fanout_durable_reads"),
            "gets_saved": d("topology.durable_gets_saved"),
            "bytes_redistributed": d(
                "topology.fanout_bytes_redistributed"
            ),
            "fallbacks": d("topology.fanout_fallbacks"),
            "payload_digest": next(iter(digests.values()), None),
            "transport": {
                "collective_ops": d("transport.collective_ops"),
                "kv_ops": d("transport.kv_ops"),
                "transport_fallbacks": d("transport.fallbacks"),
                **{
                    f"{eng}_bytes_per_s": round(
                        moved[eng] / max(elapsed, 1e-9)
                    )
                    for eng in ("collective", "kv")
                },
                **{f"{eng}_bytes": moved[eng] for eng in moved},
            },
        }

    try:
        with knobs.override_disable_batching(True):
            Snapshot.take(snap, state, replicated=["**"])
            # same restore, both payload-transport engines: the KV
            # blob path vs the collective engine's device fabric
            # (in-process registry mode under the thread-simulated
            # world).  The digest cross-check asserts the engines are
            # bitwise interchangeable; the per-engine bytes/s pair is
            # the when-do-collectives-pay datum.
            with knobs.override_transport("kv"):
                out["fanout"] = leg(spec, "kv_fan")
            with knobs.override_transport("collective"):
                out["fanout_collective"] = leg(spec, "kv_fanc")
            if (
                out["fanout_collective"]["payload_digest"]
                != out["fanout"]["payload_digest"]
            ):
                raise AssertionError(
                    "engines disagree bitwise: "
                    f"kv={out['fanout']['payload_digest']} collective="
                    f"{out['fanout_collective']['payload_digest']}"
                )
            out["engines_bitwise_identical"] = True
            out["flat"] = leg(None, "kv_flat")
        # the acceptance inequality: O(objects) per slice, not
        # O(objects × ranks) — flat-leg GETs are implicit (every rank
        # reads every object directly; no fan-out counters fire)
        out["fanout"]["gets_per_slice"] = (
            out["fanout"]["durable_gets"] / slices
        )
        out["flat"]["durable_gets"] = objects * world
        out["o_objects_not_o_ranks"] = (
            out["fanout"]["durable_gets"] == objects * slices
            and out["fanout"]["fallbacks"] == 0
        )
        out["get_reduction_factor"] = round(
            out["flat"]["durable_gets"]
            / max(1, out["fanout"]["durable_gets"]),
            2,
        )
        # ------- write side: per-slice egress balance (pure planning).
        # Deliberately UNEVEN slices (most ranks in slice 0): the flat
        # greedy balances per-rank, which concentrates egress on the
        # big slice's uplink; the topology-aware greedy balances the
        # slices themselves.
        uneven = ",".join(
            "0" if r < world - max(1, world // 3) else "1"
            for r in range(world)
        )
        topo = Topology.from_spec(uneven, rank=0, world_size=world)
        out["write_balance_spec"] = uneven
        items = [
            (f"w{i}", (1 + (i * 7) % 13) * (1 << 20)) for i in range(24)
        ]
        sizes = dict(items)

        def slice_loads(assignment):
            loads = [0] * topo.num_slices
            for p, r in assignment.items():
                loads[topo.slice_of[r]] += sizes[p]
            return loads

        aware = slice_loads(
            partition_replicated_writes(items, world, topology=topo)
        )
        flat = slice_loads(partition_replicated_writes(items, world))
        out["write_balance"] = {
            "per_slice_mb_topology": [round(x / 1e6, 2) for x in aware],
            "per_slice_mb_flat": [round(x / 1e6, 2) for x in flat],
            "imbalance_topology": round(max(aware) / max(1, min(aware)), 3),
            "imbalance_flat": round(max(flat) / max(1, min(flat)), 3),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def _takeover_probe(obj_kb: int = 512, timeout_s: float = 120.0) -> dict:
    """Rank-death write-takeover probe (resilience/liveness + the
    takeover commit protocol): a REAL 2-process take where rank 1 is
    SIGKILLed (``os._exit``) mid-commit, against a clean 2-process take
    of the same state in the same harness.

    Reports the degraded-commit wall vs the clean wall (the death leg
    pays one liveness timeout plus the survivors' replay), how many
    replicated write units the survivor re-wrote and their bytes, and
    the commit classification — ``degraded`` (the dead rank's private
    state is marked lost) vs ``complete``.  Liveness knobs are pinned
    tight (2s timeout / 0.2s interval) so the probe measures protocol
    cost, not the production 30s detection window."""
    import subprocess
    import tempfile
    import textwrap

    root = tempfile.mkdtemp(prefix="tsnp_bench_takeover_")
    script = os.path.join(root, "worker.py")
    repo = os.path.dirname(os.path.abspath(__file__))
    with open(script, "w") as f:
        f.write(
            textwrap.dedent(
                f"""
                import json, os, sys, time
                sys.path.insert(0, {repo!r})
                import numpy as np
                from torchsnapshot_tpu import FileCoordinator, Snapshot, StateDict
                from torchsnapshot_tpu import obs

                rank, world = int(sys.argv[1]), int(sys.argv[2])
                leg = sys.argv[3]  # "clean" | "death"
                base = os.path.join({root!r}, leg)
                coord = FileCoordinator(os.path.join(base, "kv"), rank, world)
                snap_dir = os.path.join(base, "snap")
                n = {obj_kb} * 1024 // 4
                state = {{"app": StateDict(
                    w=np.arange(n, dtype=np.float32) + rank,
                    shared=np.full(n, 7.0, dtype=np.float32),
                    big=np.arange(2 * n, dtype=np.float64),
                )}}
                if leg == "death" and rank == 1:
                    # die where a real commit-phase SIGKILL lands: after
                    # writes, inside the checksum exchange
                    import torchsnapshot_tpu.snapshot as S
                    real = S._crc_payload
                    def bomb(*a, **k):
                        os._exit(9)
                    S._crc_payload = bomb
                t0 = time.perf_counter()
                Snapshot.take(
                    snap_dir, state,
                    replicated=["app/shared", "app/big"],
                    coordinator=coord,
                )
                wall = time.perf_counter() - t0
                if rank == 0:
                    md = Snapshot(snap_dir).metadata
                    c = obs.metrics_snapshot()["counters"]
                    degraded = sorted(getattr(md, "degraded", None) or {{}})
                    print("PROBE " + json.dumps({{
                        "wall_s": round(wall, 3),
                        "degraded_paths": degraded,
                        "classification": (
                            "degraded" if degraded else "complete"
                        ),
                        "objects_taken_over": c.get("takeover.objects", 0),
                        "bytes_taken_over": c.get("takeover.bytes", 0),
                    }}), flush=True)
                """
            )
        )
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "TORCHSNAPSHOT_TPU_LIVENESS_TIMEOUT_S": "2",
        "TORCHSNAPSHOT_TPU_LIVENESS_INTERVAL_S": "0.2",
    }

    def leg(name) -> dict:
        procs = [
            subprocess.Popen(
                [sys.executable, script, str(r), "2", name],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
            )
            for r in range(2)
        ]
        outs = []
        try:
            for p in procs:
                outs.append(p.communicate(timeout=timeout_s)[0].decode())
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            raise RuntimeError(
                f"takeover probe {name} leg wedged past {timeout_s}s"
            )
        if procs[0].returncode != 0:
            raise RuntimeError(
                f"takeover probe {name} rank 0 rc={procs[0].returncode}: "
                f"{outs[0][-500:]}"
            )
        for line in outs[0].splitlines():
            if line.startswith("PROBE "):
                return json.loads(line[len("PROBE "):])
        raise RuntimeError(
            f"takeover probe {name}: no PROBE line in rank 0 output"
        )

    try:
        out: dict = {
            "object_kb": obj_kb,
            "liveness_timeout_s": 2.0,
            "clean": leg("clean"),
            "death": leg("death"),
        }
        out["commit_overhead_s"] = round(
            out["death"]["wall_s"] - out["clean"]["wall_s"], 3
        )
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _codec_probe(payload_mb: int = 128, part_mb: int = 8) -> dict:
    """Compression microbench on a REALISTIC bf16 payload (noisy
    weights — zeros would flatter every codec): per-codec compression
    ratio and encode throughput, byte-shuffled vs unshuffled, plus the
    pipeline-level check that matters — effective write GB/s
    (wall-clock over RAW bytes) through the real stage→write part
    stream with the codec on vs off on the memory backend, where
    encode overlap either hides the compute or doesn't.  The payload
    sits at the production striping floor (STRIPE_MIN_OBJECT_SIZE,
    128MB) — smaller payloads over-weight the pipeline's fixed costs
    (ramp-up, the last part's un-overlapped wire time, complete())
    that striping never pays at its real object sizes."""
    import asyncio
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from torchsnapshot_tpu import codec, knobs
    from torchsnapshot_tpu.preparers.array import HostArrayBufferStager
    from torchsnapshot_tpu.storage import stripe
    from torchsnapshot_tpu.storage.memory import (
        MemoryStoragePlugin,
        reset_namespace,
    )

    nbytes = payload_mb << 20
    gb = nbytes / 1e9
    rng = np.random.default_rng(0)
    weights = (rng.standard_normal(nbytes // 2) * 0.02).astype(np.float32)
    try:
        import ml_dtypes

        payload = weights.astype(ml_dtypes.bfloat16)
        dtype_name, stride = "bfloat16", 2
    except ImportError:  # honest fallback: f16 has the same byte planes
        payload = weights.astype(np.float16)
        dtype_name, stride = "float16", 2
    data = payload.view(np.uint8)
    out: dict = {
        "payload_mb": payload_mb,
        "part_mb": part_mb,
        "dtype": dtype_name,
        "codecs": {},
    }

    # --- per-codec ratio + encode speed, shuffled vs unshuffled ------
    sample = memoryview(data[: 8 << 20])
    for name in codec.available_codecs():
        if name == "raw":
            continue
        spec = codec.WriteSpec(name, 0, 1.0)
        legs = {}
        for label, st in (("shuffled", stride), ("unshuffled", 0)):
            t0 = time.perf_counter()
            frame = codec.encode_frame(sample, spec, st)
            dt = time.perf_counter() - t0
            legs[label] = {
                "ratio": round(sample.nbytes / len(frame), 3),
                "encode_gbps": round(sample.nbytes / 1e9 / dt, 3),
            }
        out["codecs"][name] = legs

    # --- pipeline: effective write GB/s over RAW bytes, codec on vs
    # off, through the real stage→write part stream.  Two sinks:
    #  - cloud model (HEADLINE): memory sink throttled to a documented
    #    per-part-stream bandwidth (S3/GCS-like) — the regime the codec
    #    targets, where encode overlaps wire time and smaller parts
    #    finish sooner.
    #  - ram sink: unthrottled memory — transparency number; a RAM-speed
    #    memcpy sink is faster than any entropy coder on this box, so
    #    this leg shows the encode-bound floor, not the value prop.
    loop = asyncio.new_event_loop()
    executor = ThreadPoolExecutor(
        max_workers=4, thread_name_prefix="codec-bench"
    )
    ns = f"codec_bench_{os.getpid()}"
    part = part_mb << 20
    # bytes/s per concurrent part stream — mid-range of real S3/GCS
    # multipart PUT connections (boto3's transfer defaults assume
    # ~40MB/s/stream; measured S3 part streams run 25-90MB/s)
    per_stream_bw = 48e6
    write_codec = codec.resolve_codec("huff")
    if write_codec == "raw":  # native lib absent: best available
        write_codec = next(iter(out["codecs"]), "raw")
    out["pipeline_codec"] = write_codec
    out["sink_model_mbps_per_stream"] = int(per_stream_bw / 1e6)

    class _ThrottledHandle:
        """Per-part-stream token throttle over the memory handle: each
        part's write occupies its stream for stored_bytes / bandwidth
        seconds — concurrent parts proceed in parallel, like multipart
        uploads against a cloud endpoint."""

        supports_fused_digest = False

        def __init__(self, inner):
            self._inner = inner

        async def write_part(self, idx, off, buf, want_digest=False):
            t0 = time.perf_counter()
            r = await self._inner.write_part(
                idx, off, buf, want_digest=want_digest
            )
            wire_s = memoryview(buf).nbytes / per_stream_bw
            left = wire_s - (time.perf_counter() - t0)
            if left > 0:
                await asyncio.sleep(left)
            return r

        async def complete(self):
            await self._inner.complete()

        async def abort(self):
            await self._inner.abort()

    class _CloudModelPlugin:
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, attr):
            return getattr(self._inner, attr)

        async def begin_striped_write(self, path, total):
            return _ThrottledHandle(
                await self._inner.begin_striped_write(path, total)
            )

    def timed_stream(spec, fstride, throttled) -> tuple:
        plugin = MemoryStoragePlugin(ns)
        if throttled:
            plugin = _CloudModelPlugin(plugin)
        stager = HostArrayBufferStager(data, defensive_copy=False)
        spans = stager.part_plan(part)
        t0 = time.perf_counter()
        tbl = {}
        loop.run_until_complete(
            stripe.streamed_part_write(
                plugin, "o", stager, spans, executor,
                window_parts=4, codec_spec=spec,
                filter_stride=fstride, codec_sink=tbl.update,
            )
        )
        dt = time.perf_counter() - t0
        stored = sum(tbl["parts"]) if tbl else nbytes
        reset_namespace(ns)
        return dt, stored

    try:
        if write_codec != "raw":
            spec = codec.WriteSpec(write_codec, 0, 1.05)
            for label, throttled in (("cloud", True), ("ram", False)):
                # interleave the legs' trials (raw, codec, raw, …) so a
                # CPU-contention burst on the shared sandbox taxes both
                # legs alike instead of biasing whichever ran through
                # it; best-of-N per leg then drops the taxed trials
                raws, encs = [], []
                for _ in range(5):
                    raws.append(timed_stream(None, 0, throttled)[0])
                    encs.append(timed_stream(spec, stride, throttled))
                t_raw = min(raws)
                t_enc = min(t for t, _ in encs)
                stored = encs[0][1]
                leg = {
                    "write_raw_gbps": round(gb / t_raw, 3),
                    "write_codec_gbps": round(gb / t_enc, 3),
                    "write_codec_vs_raw": round(t_raw / t_enc, 3),
                    "ratio": round(nbytes / stored, 3),
                }
                out[f"{label}_sink"] = leg
            # headline axes = the cloud-model leg (the codec's regime)
            out["write_raw_gbps"] = out["cloud_sink"]["write_raw_gbps"]
            out["write_codec_gbps"] = out["cloud_sink"]["write_codec_gbps"]
            out["write_codec_vs_raw"] = out["cloud_sink"][
                "write_codec_vs_raw"
            ]
            out["pipeline_ratio"] = out["cloud_sink"]["ratio"]
    finally:
        loop.close()
        executor.shutdown(wait=False)
        reset_namespace(ns)
    return out


def _stripe_probe(payload_mb: int = 256, part_mb: int = 32) -> dict:
    """Per-backend storage-throughput microbench: write/read GB/s for a
    SINGLE large object, striped vs unstriped, memory + fs backends —
    the single-stream 0.022 GB/s axis from BENCH r05, tracked from this
    PR on.  Both writes measure the REAL checksummed save path: the
    unstriped leg is the pre-stripe fused copy+digest write, the
    striped leg is the scheduler's stage→write part stream (per-part
    fused digests, folded and cross-checked against the unstriped
    digest so the bench doubles as an equivalence assert).  Best of 3
    trials per leg (microbench convention — the box's page-cache and
    scheduler noise lands on single trials).  Host-only: numpy buffers,
    RAM and a local dir; cannot perturb the device."""
    import asyncio
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.io_types import ReadIO, WriteIO
    from torchsnapshot_tpu.preparers.array import HostArrayBufferStager
    from torchsnapshot_tpu.storage import stripe
    from torchsnapshot_tpu.storage.fs import FSStoragePlugin
    from torchsnapshot_tpu.storage.memory import (
        MemoryStoragePlugin,
        reset_namespace,
    )
    from torchsnapshot_tpu.utils.checksums import combine_piece_digests

    loop = asyncio.new_event_loop()

    def run(coro):
        return loop.run_until_complete(coro)

    nbytes = payload_mb << 20
    part = part_mb << 20
    gb = nbytes / 1e9
    data = np.random.default_rng(0).integers(
        0, 256, size=nbytes, dtype=np.uint8
    )
    executor = ThreadPoolExecutor(max_workers=4, thread_name_prefix="stripe-bench")
    root = tempfile.mkdtemp(prefix="tsnp_bench_stripe_")
    ns = f"stripe_bench_{os.getpid()}"
    out: dict = {
        "payload_mb": payload_mb,
        "part_mb": part_mb,
        "trials": 3,
    }

    def best(*fns):
        # interleave the legs' trials so page-cache / memory-pressure
        # drift across the probe penalizes both paths equally instead
        # of whichever leg happened to run second
        times = [[] for _ in fns]
        for _ in range(3):
            for i, fn in enumerate(fns):
                times[i].append(fn())
        return [round(gb / min(ts), 3) for ts in times]

    try:
        for name, plugin in (
            ("memory", MemoryStoragePlugin(ns)),
            ("fs", FSStoragePlugin(os.path.join(root, "fs"))),
        ):
            b: dict = {}

            def timed_unstriped_write() -> float:
                wio = WriteIO(path="u", buf=memoryview(data), want_digest=True)
                t0 = time.perf_counter()
                run(plugin.write(wio))
                dt = time.perf_counter() - t0
                b["unstriped_digests"] = wio.digests
                return dt

            def timed_striped_write() -> float:
                stager = HostArrayBufferStager(data, defensive_copy=False)
                spans = stager.part_plan(part)
                t0 = time.perf_counter()
                d = run(
                    stripe.streamed_part_write(
                        plugin, "s", stager, spans, executor,
                        window_parts=4, want_digests=True,
                    )
                )
                dt = time.perf_counter() - t0
                crc, adler, total = combine_piece_digests(d)
                b["striped_digests"] = (crc, adler)
                assert total == nbytes
                return dt

            def timed_unstriped_read() -> float:
                rio = ReadIO(path="u", into=np.empty(nbytes, np.uint8))
                t0 = time.perf_counter()
                run(plugin.read(rio))
                return time.perf_counter() - t0

            def timed_striped_read() -> float:
                dst = np.empty(nbytes, np.uint8)
                t0 = time.perf_counter()
                run(
                    stripe.striped_read(
                        plugin, "s", offset=0, length=nbytes, into=dst
                    )
                )
                return time.perf_counter() - t0

            with knobs.override_stripe_part_size_bytes(part), (
                knobs.override_stripe_min_object_size_bytes(1 << 20)
            ):
                (
                    b["write_unstriped_gbps"],
                    b["write_striped_gbps"],
                ) = best(timed_unstriped_write, timed_striped_write)
                (
                    b["read_unstriped_gbps"],
                    b["read_striped_gbps"],
                ) = best(timed_unstriped_read, timed_striped_read)
            # bitwise equivalence of the two write paths, for free: the
            # fused whole-object digest must equal the folded part digests
            if b.get("unstriped_digests") and b.get("striped_digests"):
                assert tuple(b.pop("unstriped_digests")) == tuple(
                    b.pop("striped_digests")
                ), f"{name}: striped/unstriped digests diverged"
            else:
                b.pop("unstriped_digests", None)
                b.pop("striped_digests", None)
            b["write_speedup"] = round(
                b["write_striped_gbps"] / max(b["write_unstriped_gbps"], 1e-9),
                2,
            )
            b["read_speedup"] = round(
                b["read_striped_gbps"] / max(b["read_unstriped_gbps"], 1e-9),
                2,
            )
            out[name] = b

        # ---- fs leg: fast-I/O engine vs the executor/aiofiles path.
        # Same striped pipeline, one plugin with the engine (fused part
        # digests, pwritev-batched GIL-free parts) and one pure-Python
        # (ENABLE_NATIVE_EXT=0: the aiofiles/executor pwrite loop plus
        # a separate per-part digest pass — the pre-native world).
        # Interleaved warmup + median-of-3 with a writeback drain
        # (fdatasync + DONTNEED) before every timed trial: buffered
        # write throughput is bimodal around the kernel's dirty-page
        # throttle, and best-of-N amplifies whichever leg got the
        # lucky un-throttled trial.  The folded part digests of the
        # two paths are cross-checked bitwise so the speed claim can't
        # silently ride a correctness divergence.
        native_plugin = FSStoragePlugin(os.path.join(root, "fs_native"))
        with knobs.override_enable_native_ext(False):
            fallback_plugin = FSStoragePlugin(os.path.join(root, "fs_fb"))
        fsd: dict = {
            "engine_active": native_plugin._fastio is not None,
            "trials": "median of 3, drained, after warmup",
        }
        digs: dict = {}

        def _drain_writeback() -> None:
            for sub in ("fs_native", "fs_fb"):
                d = os.path.join(root, sub)
                for dp, _dn, fns in os.walk(d):
                    for fn in fns:
                        _evict_page_cache(os.path.join(dp, fn))

        def timed_write(plug, key):
            def f() -> float:
                _drain_writeback()
                stager = HostArrayBufferStager(data, defensive_copy=False)
                spans = stager.part_plan(part)
                t0 = time.perf_counter()
                d = run(
                    stripe.streamed_part_write(
                        plug, "obj", stager, spans, executor,
                        window_parts=4, want_digests=True,
                    )
                )
                dt = time.perf_counter() - t0
                digs[key] = combine_piece_digests(d)
                return dt

            return f

        def timed_read(plug, key):
            def f() -> float:
                _drain_writeback()  # cold reads: the restore case
                dst = np.empty(nbytes, np.uint8)
                t0 = time.perf_counter()
                run(
                    stripe.striped_read(
                        plug, "obj", offset=0, length=nbytes, into=dst
                    )
                )
                dt = time.perf_counter() - t0
                from torchsnapshot_tpu.utils.checksums import crc32_fast

                digs[f"read_{key}"] = crc32_fast(dst)  # after the clock
                return dt

            return f

        def median_of_3(*fns):
            for fn in fns:
                fn()  # warmup (also populates the digest cross-check)
            times = [[] for _ in fns]
            for _ in range(3):
                for i, fn in enumerate(fns):
                    times[i].append(fn())
            return [round(gb / sorted(ts)[1], 3) for ts in times]

        with knobs.override_stripe_part_size_bytes(part), (
            knobs.override_stripe_min_object_size_bytes(1 << 20)
        ):
            (
                fsd["write_native_gbps"],
                fsd["write_executor_gbps"],
            ) = median_of_3(
                timed_write(native_plugin, "native"),
                timed_write(fallback_plugin, "executor"),
            )
            (
                fsd["read_native_gbps"],
                fsd["read_executor_gbps"],
            ) = median_of_3(
                timed_read(native_plugin, "native"),
                timed_read(fallback_plugin, "executor"),
            )
        assert digs["native"] == digs["executor"], (
            "fs native/executor digests diverged"
        )
        assert digs["read_native"] == digs["read_executor"]
        fsd["write_speedup"] = round(
            fsd["write_native_gbps"] / max(fsd["write_executor_gbps"], 1e-9),
            2,
        )
        fsd["read_speedup"] = round(
            fsd["read_native_gbps"] / max(fsd["read_executor_gbps"], 1e-9),
            2,
        )
        out["fs"]["native_vs_executor"] = fsd
    finally:
        loop.close()
        executor.shutdown(wait=False)
        reset_namespace(ns)
        shutil.rmtree(root, ignore_errors=True)
    return out


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from torchsnapshot_tpu import PyTreeState, Snapshot
    from torchsnapshot_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.perf_counter()
    dev = jax.devices()[0]
    init_s = time.perf_counter() - t0
    if dev.platform != "tpu":
        # no metric line for anything but the chip: a CPU number under a
        # device metric's name is worse than no number
        print(
            f"bench: no TPU — jax.devices()[0].platform is "
            f"{dev.platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})",
            file=sys.stderr,
        )
        return 2
    print(
        json.dumps(
            {
                "metric": METRIC,
                "phase": "backend_up",
                "platform": dev.platform,
                "backend_init_s": round(init_s, 2),
            }
        ),
        flush=True,
    )
    failed_probes: list = []

    def probe(name: str, fn):
        """A host-side probe or rollup: its failure is recorded in the
        record and fails the run AFTER the record is printed."""
        try:
            return fn()
        except Exception as e:  # noqa: BLE001
            failed_probes.append(name)
            return {"error": f"{e!r}"[:300]}

    n_arrays = 16
    # restore donates template buffers leaf-by-leaf (put-then-delete,
    # knobs.RESTORE_DONATE auto-on for accelerators), so device peak
    # is ~1x payload + one leaf; 60% of HBM leaves comfortable slack
    hbm = int(dev.memory_stats()["bytes_limit"])
    # link probe: a 64MB D2H round sizes the payload to what the
    # attachment can move in ~100s each way.  Two rounds; the second
    # excludes first-transfer setup costs that would understate a fast
    # link.  (ROADMAP S1: the second round reads jax's cached host copy,
    # so this number is void as a link measurement.)
    link_probe = jax.block_until_ready(
        jnp.ones((32 * 1024 * 1024,), jnp.bfloat16)
    )
    link_gbps = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        np.asarray(link_probe)
        link_gbps = 0.064 / max(time.perf_counter() - t0, 1e-6)
    del link_probe
    payload_bytes = max(
        128 * 1024 * 1024,
        min(int(8.6e9), int(hbm * 0.60), int(link_gbps * 60 * 1e9)),
    )
    elems = payload_bytes // (n_arrays * 2)
    elems -= elems % 1024

    @jax.jit
    def make(i):
        return (jnp.arange(elems, dtype=jnp.float32) * (i + 1.0)).astype(
            jnp.bfloat16
        )

    params = {
        f"layer{i:02d}/w": make(np.float32(i)) for i in range(n_arrays)
    }
    jax.block_until_ready(params)
    total_gb = n_arrays * elems * 2 / 1e9

    root = tempfile.mkdtemp(prefix="tsnp_bench_")
    result = {
        "metric": METRIC,
        "unit": "GB/s/chip",
        "platform": dev.platform,
        "device": dev.device_kind,
        "device_count": len(jax.devices()),
        "payload_gb": round(total_gb, 3),
        "backend_init_s": round(init_s, 2),
        "baseline": "reference 20GB/13.91s save, 1xA100 local FS "
        "(benchmarks/ddp/README.md:17)",
        "link_d2h_gbps": round(link_gbps, 4),
    }
    # early breadcrumb: if a later phase wedges, the run still records a
    # parseable line with platform + link evidence (value 0 = no number)
    print(
        json.dumps({**result, "value": 0.0, "vs_baseline": 0.0, "phase": "init"}),
        flush=True,
    )
    try:
        # warm-up on a small slice to exclude one-time costs (compile
        # caches, thread pools, first-transfer setup)
        warm = (jnp.arange(1024, dtype=jnp.float32)).astype(jnp.bfloat16)
        Snapshot.async_take(
            os.path.join(root, "warm"), {"m": PyTreeState({"w": warm})}
        ).wait()
        # counter baseline AFTER warm-up: the mechanisms record must
        # attribute pack/unpack engagement to the MEASURED phases only
        from torchsnapshot_tpu.ops import device_pack

        pack_base = dict(device_pack.CALL_COUNTS)
        # same discipline for the obs registry and span tracer: the
        # embedded metrics block and BENCH_TRACE.json cover the
        # measured save/restore phases, not the warm-up that ran
        # earlier in this process
        from torchsnapshot_tpu import obs

        obs.reset_metrics()
        obs.get_tracer().reset()
        print(json.dumps({"metric": METRIC, "phase": "warmup_done"}), flush=True)

        t0 = time.perf_counter()
        pending = Snapshot.async_take(
            os.path.join(root, "snap"), {"m": PyTreeState(dict(params))}
        )
        blocked_first_s = time.perf_counter() - t0
        print(json.dumps({"metric": METRIC, "phase": "save_dispatched"}), flush=True)
        snap = pending.wait()
        total_s = time.perf_counter() - t0

        result.update(
            {
                "value": round(total_gb / blocked_first_s, 3),
                "vs_baseline": round(
                    total_gb / blocked_first_s / BASELINE_GBPS, 3
                ),
                "blocked_first_s": round(blocked_first_s, 4),
                "save_total_s": round(total_s, 2),
                "save_total_gbps": round(total_gb / total_s, 3),
            }
        )
        # emit now: if a later phase wedges, the save numbers survive
        print(json.dumps(result), flush=True)

        # steady state: a training job checkpoints the same shapes over
        # and over; the first take pays one-time costs (XLA transfer
        # program for the batched pinned-host offload — minutes when
        # compiles are remote) that no subsequent take sees.  The
        # steady-state blocked time is the honest analogue of the
        # reference's numbers, which have no compile component at all.
        t0 = time.perf_counter()
        pending_b = Snapshot.async_take(
            os.path.join(root, "snap_b"), {"m": PyTreeState(dict(params))}
        )
        blocked_s = time.perf_counter() - t0
        pending_b.wait()
        # bound peak scratch at ~1x payload (snap_b is never read again)
        shutil.rmtree(os.path.join(root, "snap_b"), ignore_errors=True)
        gbps = total_gb / blocked_s
        result.update(
            {
                "value": round(gbps, 3),
                "vs_baseline": round(gbps / BASELINE_GBPS, 3),
                "blocked_s": round(blocked_s, 4),
            }
        )
        print(json.dumps(result), flush=True)

        # restore into fresh device arrays.  Free each original leaf
        # BEFORE allocating its zero template — building the full
        # template dict first would peak at 2x payload (120% of HBM at
        # the 60% sizing) before `del params` could run.
        zeros = jax.jit(lambda: jnp.zeros((elems,), jnp.bfloat16))
        templates = {}
        for k in sorted(params):
            params.pop(k)
            templates[k] = zeros()
        del params
        jax.block_until_ready(templates)
        dest = PyTreeState(templates)
        t0 = time.perf_counter()
        snap.restore({"m": dest})
        jax.block_until_ready(dest.tree)
        restore_s = time.perf_counter() - t0
        result.update(
            {
                "restore_s": round(restore_s, 2),
                "restore_gbps": round(total_gb / restore_s, 3),
            }
        )
        # hard evidence of WHICH TPU-native mechanisms engaged (VERDICT
        # r2 weak #3: the pinned-host offload / device unpack paths had
        # only ever run in degraded CPU fallbacks)
        from torchsnapshot_tpu import host_offload, knobs
        from torchsnapshot_tpu.preparers.array import DONATION_STATS

        # 1x-restore evidence (VERDICT r3 next #8): at the 60%-of-HBM
        # sizing the restore CANNOT succeed at 2x peak, so a nonzero
        # donated_templates count + a peak/payload ratio ~1x on the real
        # chip is the on-hardware proof of the put-then-delete property
        stats = dev.memory_stats()
        hbm_peak = {
            "hbm_peak_bytes": int(stats["peak_bytes_in_use"]),
            "hbm_limit_bytes": int(stats["bytes_limit"]),
            "restore_peak_over_payload": round(
                stats["peak_bytes_in_use"] / max(1.0, total_gb * 1e9), 3
            ),
        }
        result["mechanisms"] = {
            **host_offload.LAST_OFFLOAD_STATS,
            "serialize_transfers": knobs.serialize_transfers(),
            "device_unpack_knob": knobs.device_unpack_enabled(),
            "restore_donation_mode": knobs.restore_donation(),
            "donated_templates": DONATION_STATS["donated_templates"],
            **hbm_peak,
            **{
                f"device_{k}_calls": v - pack_base[k]
                for k, v in device_pack.CALL_COUNTS.items()
            },
        }
        # per-phase observability internals (obs/): bytes staged/written,
        # budget high-water, io queue depth, per-backend latency
        # histograms — the machine-readable breakdown behind `value`
        # (registry reset at warmup_done, so this covers the measured
        # phases only)
        result["metrics"] = obs.metrics_snapshot()
        # goodput/SLO block: what the measured take/restore cost the
        # training loop (time-to-unblock, durable lag, overhead
        # fraction) — every BENCH record embeds it (tier-1 asserted)
        result["goodput"] = probe("goodput", _goodput_rollup)
        if obs.tracing_enabled():
            # TORCHSNAPSHOT_TPU_TRACE=1 drives: the span trace of the
            # measured phases lands where the chip tool copies results
            # back from, loadable in ui.perfetto.dev
            os.makedirs(_OUT_DIR, exist_ok=True)
            trace_path = os.path.join(_OUT_DIR, "BENCH_TRACE.json")
            result["trace_spans"] = obs.write_trace(trace_path)
            result["trace_path"] = trace_path
        # Host-side probes run AFTER the measured-phase metrics snapshot
        # (their counters must not pollute the headline breakdown); host
        # arrays + local dirs only, so they cannot perturb the device.
        # ROADMAP S1 moves them to a CPU-side command; until then a
        # failure is recorded and fails the run after the record prints.
        #   tier       — tiered-storage promotion
        #   lint       — snaplint finding counts (tools/lint)
        #   resilience — retries/aborts/breaker activity of this run
        #   stripe     — single-object write/read, striped vs unstriped,
        #                with the per-part codec sub-block
        #   cas        — bytes-written-per-step curve + dedup ratio
        #   serving    — N concurrent read_object clients, cold vs warm
        #   fanout     — simulated SxR-process restore GET counts
        #   continuous — per-step overhead, replication lag, RTO
        #   publish    — delta-restore fan-out to co-hosted subscribers
        #   takeover   — 2-process take with an injected dead writer
        #                (children pinned to JAX_PLATFORMS=cpu)
        #   transport  — per-engine op/byte/fallback totals
        result["tier"] = probe("tier", _tier_probe)
        result["lint"] = probe("lint", _lint_probe)
        result["resilience"] = probe("resilience", _resilience_rollup)
        result["stripe"] = probe("stripe", _stripe_probe)
        result["stripe"]["codec"] = probe("codec", _codec_probe)
        result["cas"] = probe("cas", _cas_probe)
        result["serving"] = probe("serving", _serving_probe)
        result["fanout"] = probe("fanout", _fanout_probe)
        result["continuous"] = probe("continuous", _continuous_probe)
        result["publish"] = probe("publish", _publish_probe)
        result["takeover"] = probe("takeover", _takeover_probe)
        result["transport"] = probe("transport", _transport_rollup)
        print(json.dumps(result), flush=True)
        # spot-check one leaf round-tripped
        import ml_dtypes

        got = np.asarray(dest.tree["layer03/w"][:16]).astype(np.float32)
        want = (
            (np.arange(16, dtype=np.float32) * 4.0)
            .astype(ml_dtypes.bfloat16)
            .astype(np.float32)
        )
        if not np.array_equal(got, want):
            raise RuntimeError("restore round-trip mismatch")

        # attention + orbax run BEFORE the incremental re-save: both
        # are small and bounded while the 1x-payload incremental is the
        # long phase
        result["attention"] = probe("attention", _attention_bench)
        print(json.dumps(result), flush=True)

        def _orbax_head_to_head():
            import importlib.util as _ilu

            spec = _ilu.spec_from_file_location(
                "orbax_compare",
                os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "benchmarks",
                    "orbax_compare.py",
                ),
            )
            mod = _ilu.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.run(min(0.25, max(0.032, total_gb / 4)))

        result["orbax_head_to_head"] = probe("orbax", _orbax_head_to_head)
        print(json.dumps(result), flush=True)

        # incremental re-save (content identical to the base, via the
        # restored arrays): all objects dedup into hardlinks, isolating
        # staging+digest cost from storage I/O — the win incremental
        # takes deliver when most state is unchanged.  Runs last of the
        # checkpoint phases.
        def _nlinked(loc: str) -> bool:
            try:
                return os.stat(os.path.join(root, "snap2", loc)).st_nlink > 1
            except OSError:
                return False

        t0 = time.perf_counter()
        snap2 = Snapshot.take(
            os.path.join(root, "snap2"),
            {"m": dest},
            base=os.path.join(root, "snap"),
        )
        incr_s = time.perf_counter() - t0
        result.update(
            {
                "incremental_save_s": round(incr_s, 2),
                "incremental_gbps": round(total_gb / incr_s, 3),
                "deduped_objects": sum(
                    1 for loc in snap2.metadata.objects if _nlinked(loc)
                ),
            }
        )
        if failed_probes:
            result["failed_probes"] = failed_probes
        print(json.dumps(result), flush=True)
        del dest, templates
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if failed_probes:
        print(f"bench: probes failed: {failed_probes}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
