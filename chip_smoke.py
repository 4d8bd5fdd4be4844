"""chip_smoke.py — the quickest proof that the checkpointer still starts on
the chip: train -> async_take -> donated step while the save drains ->
resume -> take -> verify, at the bundled llama-style trainer's full widths,
through ``Snapshot``'s public entry points only.

    python chip_smoke.py            # needs a TPU; exits nonzero without one
    python chip_smoke.py --tiny     # tiny widths, CPU allowed (tier-1 runs this)

One process; starts no child that imports JAX.  Every line on stdout is one
JSON object naming the platform it ran on.  The second-to-last line is the
report (``"report": "chip_smoke"``: versions, config, timings, evidence,
failed checks); the last line is the verdict and nothing else,
``{"ok": ..., "device": {"platform", "kind", "count"}}`` — ``"ok": true``
and exit 0 only when every check passed.  Wall seconds in the report are
smoke timings — one cold run, compile included where noted — not
benchmark numbers.  No phase is wrapped: an exception ends the run with a
traceback and a nonzero exit.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import logging
import os
import shutil
import sys
import tempfile
import threading
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Depth is the only thing cut from TransformerConfig()'s defaults.  f32
# params + adamw are 12 B/param: n_layers=2 is 667 M params = 8.0 GB of
# state, and its step peak (live buffers + 0.6 GB of program scratch; the
# result reports it) leaves 49% of a v5e's 16.9 GB free.  n_layers=3
# (10.4 GB) would still leave a third of HBM free, but the eager offload
# may claim only half the staging budget (0.6 x available host RAM): on
# the 47 GB chip host that is ~10.9 GB, no margin for 10.4 GB — and one
# leaf past the budget stages lazily and dies under the donated step.
_FULL_WIDTH_LAYERS = 2
_BATCH, _SEQ = 2, 512  # --tiny: 2 x cfg.max_seq
_STEPS_BEFORE_SAVE = 3
# 2x2 -> 1x4 changes the reduction order of every tp-split matmul, so the
# resumed loss is compared within a band, not bitwise
_RESHARD_LOSS_RTOL = 1e-2
# tests/test_flash_attention.py: bf16 forward vs dense, and the backward
# judged against an f32 ground truth relative to XLA's own bf16 error
_FLASH_FWD_TOL = 5e-2
_FLASH_BWD_FACTOR, _FLASH_BWD_SLACK = 2.0, 1e-3


class _Run:
    """Lines out, timings, evidence and failed checks of one smoke run."""

    def __init__(self, platform: str, tiny: bool) -> None:
        self.platform = platform
        self.tiny = tiny
        self.wall_s: dict = {}
        self.failures: list = []
        self.not_enforced: list = []

    def line(self, **fields) -> None:
        print(json.dumps({"platform": self.platform, **fields}), flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.wall_s[name] = round(time.perf_counter() - t0, 3)
        self.line(phase=name, smoke_wall_s=self.wall_s[name])

    def check(self, name: str, ok: bool, detail="", chip_only=False) -> None:
        if ok:
            return
        entry = {"check": name, "detail": str(detail)[:400]}
        if chip_only and self.tiny:
            self.not_enforced.append(entry)
        else:
            self.failures.append(entry)
            self.line(check_failed=name, detail=entry["detail"])


class _SwallowSites(logging.Handler):
    """Collects the site of every obs.swallowed_exception call."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.sites: list = []

    def emit(self, record: logging.LogRecord) -> None:
        if str(record.msg).startswith("swallowed exception at"):
            self.sites.append(record.getMessage()[:300])


class _CompileStats:
    """Compile seconds and persistent-cache traffic, from jax.monitoring."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._lock = threading.Lock()
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += secs
                self.compiles += 1

    def as_dict(self) -> dict:
        with self._lock:
            return {
                "backend_compile_s": round(self.compile_s, 2),
                "backend_compiles": self.compiles,
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": self.cache_misses,
            }


class _BytesInUsePoller:
    """Max of memory_stats()["bytes_in_use"] while a phase runs —
    ``peak_bytes_in_use`` never resets, so a restore whose peak is below the
    train step's cannot be read from it."""

    def __init__(self, device) -> None:
        self.device = device
        self.max_seen = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _poll(self) -> None:
        while not self._stop.is_set():
            stats = self.device.memory_stats()
            if stats is None:
                return
            self.max_seen = max(self.max_seen or 0, stats["bytes_in_use"])
            self._stop.wait(0.02)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=10)


def _digest(leaf) -> tuple:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if isinstance(leaf, jax.Array):
        # a jax.Array caches its host value; read a transient device copy
        # so the whole state is not pinned in host RAM twice over
        leaf = jnp.copy(leaf)
    raw = np.ascontiguousarray(np.asarray(leaf)).reshape(-1).view(np.uint8)
    return (zlib.crc32(raw), zlib.adler32(raw), raw.nbytes)


def _leaf_digests(tree) -> dict:
    import jax

    return {
        jax.tree_util.keystr(path): _digest(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def _array_leaves(tree) -> dict:
    import jax

    return {
        jax.tree_util.keystr(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
        if isinstance(leaf, jax.Array)
    }


def _check_restored(run, label, saved, shardings, restored) -> None:
    """Every leaf bitwise equal to its saved digest (gathered, when it is
    sharded) and placed as its template was."""
    got = _leaf_digests(restored)
    bad = sorted(k for k in saved if got.get(k) != saved[k])
    run.check(
        f"{label}restored leaves bitwise equal",
        not bad and len(got) == len(saved),
        bad[:5],
    )
    moved = sorted(
        k for k, a in _array_leaves(restored).items()
        if k in shardings and a.sharding != shardings[k]
    )
    run.check(
        f"{label}restored leaves keep the template's sharding",
        not moved,
        moved[:5],
    )


def _mem(device, key: str):
    stats = device.memory_stats()
    return None if stats is None else int(stats.get(key, 0))


def _counters() -> dict:
    from torchsnapshot_tpu import obs

    return obs.metrics_snapshot()["counters"]


def _tokens(cfg, seed: int):
    import numpy as np

    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=(_BATCH, min(_SEQ, cfg.max_seq)), dtype=np.int32
    )


def _single_chip_leg(run: _Run, cfg, root: str, evidence: dict) -> None:
    import jax
    import numpy as np

    from torchsnapshot_tpu import PyTreeState, Snapshot, StateDict, knobs
    from torchsnapshot_tpu import _csrc, host_offload
    from torchsnapshot_tpu.models.transformer import (
        make_train_state,
        train_step,
    )
    from torchsnapshot_tpu.ops import device_pack
    from torchsnapshot_tpu.preparers.array import DONATION_STATS

    dev = jax.devices()[0]
    tokens = jax.device_put(_tokens(cfg, seed=0), dev)
    step = jax.jit(train_step, donate_argnums=0)

    with run.phase("init_state"):
        ts = make_train_state(cfg, seed=0)
        jax.block_until_ready(ts)
    evidence["params"] = sum(
        int(np.prod(a.shape)) for a in _array_leaves(ts.params).values()
    )

    with run.phase("train_3_steps_incl_compile"):
        # memory_stats() counts live buffers only; what the step's
        # program reserves on top (gradients, activations) is in its own
        # memory analysis.  The jitted call below finds this compile in
        # the persistent cache.
        analysis = step.lower(ts, tokens).compile().memory_analysis()
        for _ in range(_STEPS_BEFORE_SAVE):
            ts, loss = step(ts, tokens)
        jax.block_until_ready(loss)
    live, limit = _mem(dev, "peak_bytes_in_use"), _mem(dev, "bytes_limit")
    evidence["step_peak"] = {
        "peak_bytes_in_use": live,
        "program_temp_bytes": int(analysis.temp_size_in_bytes),
        "free_share_of_bytes_limit": (
            None
            if live is None
            else round(1 - (live + analysis.temp_size_in_bytes) / limit, 3)
        ),
    }

    # digests BEFORE the save: the donated step below deletes these buffers
    with run.phase("digest_saved_state"):
        saved = _leaf_digests(ts)
        state_bytes = sum(a.nbytes for a in _array_leaves(ts).values())
    evidence["state_array_bytes"] = state_bytes

    snap_dir = os.path.join(root, "async")
    t0 = time.perf_counter()
    pending = Snapshot.async_take(
        snap_dir,
        {"ts": PyTreeState(ts), "meta": StateDict(step=_STEPS_BEFORE_SAVE)},
    )
    run.wall_s["async_take_blocked"] = round(time.perf_counter() - t0, 4)
    # the job host_offload's docstring calls safe only when the offload
    # engaged: the next DONATED step runs while the save drains
    ts, loss_a = step(ts, tokens)
    loss_a = np.asarray(loss_a)
    run.wall_s["donated_step_during_drain"] = round(
        time.perf_counter() - t0 - run.wall_s["async_take_blocked"], 3
    )
    pending.wait()
    run.wall_s["save_to_commit"] = round(time.perf_counter() - t0, 3)
    run.line(
        phase="async_take",
        smoke_wall_s={
            k: run.wall_s[k]
            for k in (
                "async_take_blocked",
                "donated_step_during_drain",
                "save_to_commit",
            )
        },
    )
    offload = dict(host_offload.LAST_OFFLOAD_STATS)
    evidence["offload"] = offload
    run.check(
        "offload.device_offload_bytes == state bytes",
        offload.get("device_offload_bytes") == state_bytes,
        f"{offload.get('device_offload_bytes')} != {state_bytes}",
    )
    run.check(
        "offload.host_memory_kinds",
        bool(offload.get("host_memory_kinds")),
        offload,
    )

    # resume: drop the live state, restore into a differently seeded one
    del ts
    with run.phase("init_template"):
        template = make_train_state(cfg, seed=1)
        jax.block_until_ready(template)
    shardings = {k: a.sharding for k, a in _array_leaves(template).items()}
    app = {"ts": PyTreeState(template), "meta": StateDict(step=0)}
    del template
    unpack_before = device_pack.CALL_COUNTS["unpack"]
    donated_before = DONATION_STATS["donated_templates"]
    peak_before = _mem(dev, "peak_bytes_in_use")
    with run.phase("restore"), _BytesInUsePoller(dev) as poller:
        Snapshot(snap_dir).restore(app)
        jax.block_until_ready(app["ts"].tree)
    restored = app["ts"].tree
    evidence["restore"] = {
        "peak_bytes_in_use_before": peak_before,
        "peak_bytes_in_use_after": _mem(dev, "peak_bytes_in_use"),
        "max_polled_bytes_in_use": poller.max_seen,
        "bytes_in_use_after": _mem(dev, "bytes_in_use"),
        "device_unpack_enabled": knobs.device_unpack_enabled(),
        "unpack_calls": device_pack.CALL_COUNTS["unpack"] - unpack_before,
        "donated_templates": (
            DONATION_STATS["donated_templates"] - donated_before
        ),
    }
    with run.phase("digest_restored_state"):
        _check_restored(run, "", saved, shardings, restored)
    run.check(
        "meta.step restored",
        app["meta"]["step"] == _STEPS_BEFORE_SAVE,
        app["meta"]["step"],
    )
    run.check(
        "device unpack engaged",
        not (
            evidence["restore"]["device_unpack_enabled"]
            and evidence["restore"]["unpack_calls"] == 0
        ),
        evidence["restore"],
    )
    run.check(
        "restore donated its templates",
        evidence["restore"]["donated_templates"] > 0,
        evidence["restore"],
        chip_only=True,  # RESTORE_DONATE auto is off for cpu templates
    )

    with run.phase("resumed_step"):
        restored, loss_b = step(restored, tokens)
        loss_b = np.asarray(loss_b)
    evidence["loss_a"], evidence["loss_b"] = float(loss_a), float(loss_b)
    run.check(
        "loss_b == loss_a bitwise",
        loss_a.tobytes() == loss_b.tobytes() and np.isfinite(loss_a),
        f"{loss_a!r} vs {loss_b!r}",
    )

    # blocking take: no eager offload, so sub-threshold leaves go through
    # the device slab pack
    pack_before = device_pack.CALL_COUNTS["pack"]
    shutil.rmtree(snap_dir)
    sync_dir = os.path.join(root, "sync")
    with run.phase("blocking_take"):
        snap = Snapshot.take(
            sync_dir,
            {"ts": PyTreeState(restored), "meta": StateDict(step=4)},
        )
    evidence["pack_calls"] = device_pack.CALL_COUNTS["pack"] - pack_before
    run.check("device pack engaged", evidence["pack_calls"] > 0, evidence["pack_calls"])
    with run.phase("verify_deep"):
        verdict = snap.verify(deep=True)
    run.check("verify(deep=True)", verdict.ok and verdict.complete, verdict)
    shutil.rmtree(sync_dir)

    native = _csrc.load() is not None
    counters = _counters()
    evidence["native_io"] = {
        "enable_native_ext": knobs.is_native_ext_enabled(),
        "library_loaded": native,
        "engine": (
            "fastio"
            if counters.get("storage.fastio.bytes_written", 0) > 0
            else "native" if native else "python"
        ),
        "storage.fastio.bytes_written": counters.get(
            "storage.fastio.bytes_written", 0
        ),
        "storage.fs.write_bytes": counters.get("storage.fs.write_bytes", 0),
    }
    run.check(
        "native fast-I/O library loaded",
        native or not knobs.is_native_ext_enabled(),
        evidence["native_io"],
    )


def _flash_leg(run: _Run, evidence: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.ops.flash_attention import flash_attention
    from torchsnapshot_tpu.parallel.ring_attention import dense_attention

    # compiled by Mosaic on a TPU; the interpreter only ever runs for --tiny
    shapes = [(1, 128, 1, 128)] if run.tiny else [
        (1, 512, 2, 128), (4, 2048, 8, 128),
    ]
    evidence["flash"] = {
        "compiled": jax.default_backend() == "tpu", "shapes": [],
    }
    run.check(
        "flash kernels compiled, not interpreted",
        evidence["flash"]["compiled"], jax.default_backend(), chip_only=True,
    )

    def sq_loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v, causal=True).astype(jnp.float32) ** 2
        )

    for shape in shapes:
        keys = jax.random.split(jax.random.PRNGKey(shape[1]), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys)
        # "1" on a TPU is what auto resolves to; --tiny forces it so the
        # same pallas backward runs (interpreted) on the CPU
        with knobs.override_pallas_attention("1"):
            out = jax.jit(flash_attention)(q, k, v)
            g_flash = jax.jit(
                jax.grad(sq_loss(flash_attention), argnums=(0, 1, 2))
            )(q, k, v)
        ref = jax.jit(dense_attention)(q, k, v)
        g_xla = jax.jit(
            jax.grad(sq_loss(dense_attention), argnums=(0, 1, 2))
        )(q, k, v)
        with jax.default_matmul_precision("highest"):
            g_true = jax.jit(
                jax.grad(sq_loss(dense_attention), argnums=(0, 1, 2))
            )(*(x.astype(jnp.float32) for x in (q, k, v)))
        f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
        fwd_err = float(np.max(np.abs(f32(out) - f32(ref))))
        rec = {"shape": list(shape), "fwd_max_abs_err": round(fwd_err, 5)}
        run.check(
            f"flash fwd {shape}",
            np.allclose(
                f32(out), f32(ref), rtol=_FLASH_FWD_TOL, atol=_FLASH_FWD_TOL
            ),
            fwd_err,
        )
        for name, a, b, t in zip("qkv", g_flash, g_xla, g_true):
            err_flash = float(np.linalg.norm(f32(a) - f32(t)))
            err_xla = float(np.linalg.norm(f32(b) - f32(t)))
            rec[f"d{name}_err_flash"] = round(err_flash, 4)
            rec[f"d{name}_err_xla"] = round(err_xla, 4)
            run.check(
                f"flash bwd d{name} {shape}",
                np.isfinite(err_flash)
                and err_flash
                <= _FLASH_BWD_FACTOR * err_xla + _FLASH_BWD_SLACK,
                (err_flash, err_xla),
            )
        evidence["flash"]["shapes"].append(rec)


def _sharded_leg(run: _Run, cfg, root: str, evidence: dict) -> None:
    """2x2 (dp,tp) train state -> async_take -> restore into 1x4."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import PyTreeState, Snapshot, StateDict
    from torchsnapshot_tpu.models.transformer import (
        make_train_state,
        train_step,
    )
    from torchsnapshot_tpu.parallel.mesh import build_mesh

    devices = jax.devices()[:4]
    mesh22 = build_mesh(4)
    mesh14 = build_mesh(4, tp=4)
    step = jax.jit(train_step, donate_argnums=0)
    batch = _tokens(cfg, seed=1)

    def on_dp(mesh):
        return jax.device_put(batch, NamedSharding(mesh, P("dp", None)))

    with run.phase("sharded_init_2x2"):
        # make_train_state initialises the full-width model on the
        # default device before shard_pytree spreads it: device 0 must
        # hold one whole state plus its own shards (fits at this depth)
        ts = make_train_state(cfg, seed=0, mesh=mesh22)
        jax.block_until_ready(ts)
    with run.phase("sharded_step_2x2_incl_compile"), mesh22:
        ts, loss0 = step(ts, on_dp(mesh22))
        jax.block_until_ready(loss0)
    with run.phase("sharded_digest_saved"):
        saved = _leaf_digests(ts)

    snap_dir = os.path.join(root, "sharded")
    t0 = time.perf_counter()
    pending = Snapshot.async_take(
        snap_dir, {"ts": PyTreeState(ts), "meta": StateDict(step=1)}
    )
    with mesh22:
        ts, loss_22 = step(ts, on_dp(mesh22))  # donated, during the drain
    loss_22 = float(np.asarray(loss_22))
    pending.wait()
    run.wall_s["sharded_save_to_commit"] = round(time.perf_counter() - t0, 3)
    del ts

    with run.phase("sharded_init_template_1x4"):
        template = make_train_state(cfg, seed=1, mesh=mesh14)
        jax.block_until_ready(template)
    shardings = {k: a.sharding for k, a in _array_leaves(template).items()}
    app = {"ts": PyTreeState(template), "meta": StateDict(step=0)}
    del template
    with run.phase("sharded_restore_1x4"):
        Snapshot(snap_dir).restore(app)
        jax.block_until_ready(app["ts"].tree)
    restored = app["ts"].tree
    shutil.rmtree(snap_dir)

    _check_restored(run, "sharded: ", saved, shardings, restored)
    tp_sharded = {
        k: a for k, a in _array_leaves(restored).items()
        if isinstance(a.sharding, NamedSharding) and "tp" in a.sharding.spec
    }
    piled = sorted(
        k for k, a in tp_sharded.items()
        if len({s.device for s in a.addressable_shards}) != 4
        or len({s.index for s in a.addressable_shards}) != 4
    )
    run.check(
        "sharded: tp-sharded leaves sit on four distinct devices",
        tp_sharded and not piled,
        piled[:5] or "no tp-sharded leaf found",
    )
    in_use = [_mem(d, "bytes_in_use") for d in devices]
    evidence["sharded"] = {
        "tp_sharded_leaves": len(tp_sharded),
        "bytes_in_use_per_device_after_restore": in_use,
    }
    if in_use[0] is not None:
        mean = sum(in_use) / len(in_use)
        run.check(
            "sharded: bytes_in_use per device within 2x of the mean",
            all(mean / 2 <= b <= 2 * mean for b in in_use),
            in_use,
        )
    with run.phase("sharded_resumed_step_1x4_incl_compile"), mesh14:
        restored, loss_14 = step(restored, on_dp(mesh14))
        loss_14 = float(np.asarray(loss_14))
    evidence["sharded"].update(
        loss_2x2=loss_22, loss_1x4=loss_14, loss_rtol=_RESHARD_LOSS_RTOL
    )
    run.check(
        "sharded: resumed 1x4 loss within tolerance of the 2x2 loss",
        np.isfinite(loss_14)
        and abs(loss_14 - loss_22) <= _RESHARD_LOSS_RTOL * abs(loss_22),
        (loss_22, loss_14),
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--tiny", action="store_true",
        help="tiny widths, CPU allowed, chip-only checks not enforced",
    )
    args = parser.parse_args(argv)

    import jax

    from torchsnapshot_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    compile_stats = _CompileStats()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        # the platform JAX gave us, not the one asked for: JAX falls back
        # to the CPU by itself when libtpu finds no chip
        print(
            f"chip_smoke: no TPU — jax.devices()[0].platform is "
            f"{dev.platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); pass --tiny for the "
            f"CPU check",
            file=sys.stderr,
        )
        return 2

    import flax
    import jaxlib
    import numpy as np
    import optax

    from torchsnapshot_tpu.models.transformer import TransformerConfig

    run = _Run(dev.platform, args.tiny)
    swallow_sites = _SwallowSites()
    swallow_logger = logging.getLogger("torchsnapshot_tpu.obs")
    swallow_logger.addHandler(swallow_sites)
    swallow_logger.setLevel(logging.DEBUG)

    cfg = (
        TransformerConfig.tiny()
        if args.tiny
        else TransformerConfig(n_layers=_FULL_WIDTH_LAYERS)
    )
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    evidence: dict = {}
    run.line(phase="start", device_kind=dev.device_kind, tiny=args.tiny)

    # payloads never land in the directory the chip tool copies back
    root = tempfile.mkdtemp(prefix="tsnp_chip_smoke_")
    try:
        _single_chip_leg(run, cfg, root, evidence)
        with run.phase("flash_attention_leg"):
            _flash_leg(run, evidence)
        if len(jax.devices()) >= 4:
            _sharded_leg(run, cfg, root, evidence)
            sharded = "ran"
        else:
            sharded = f"skipped: {len(jax.devices())} device"
    finally:
        shutil.rmtree(root, ignore_errors=True)

    swallowed = _counters().get("exceptions.swallowed", 0)
    evidence["exceptions_swallowed"] = swallowed
    evidence["swallow_sites"] = swallow_sites.sites
    run.check("exceptions.swallowed == 0", swallowed == 0, swallow_sites.sites)

    ok = not run.failures
    run.line(
        report="chip_smoke",
        ok=ok,
        device_kind=dev.device_kind,
        device_count=len(jax.devices()),
        tiny=args.tiny,
        versions={
            "python": sys.version.split()[0],
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
            "flax": flax.__version__,
            "optax": optax.__version__,
            "numpy": np.__version__,
        },
        config={
            "d_model": cfg.d_model,
            "n_heads": cfg.n_heads,
            "d_ff": cfg.d_ff,
            "vocab": cfg.vocab,
            "n_layers": cfg.n_layers,
            "batch_tokens": [_BATCH, min(_SEQ, cfg.max_seq)],
        },
        bytes_limit=_mem(dev, "bytes_limit"),
        smoke_wall_s=run.wall_s,
        compile=compile_stats.as_dict(),
        compile_cache_dir=cache_dir,
        sharded_leg=sharded,
        evidence=evidence,
        failures=run.failures,
        not_enforced_on_cpu=run.not_enforced,
    )
    # the last line is the verdict and nothing else: exactly these keys,
    # the device as JAX reports it
    verdict = {
        "ok": ok,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }
    print(json.dumps(verdict), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
