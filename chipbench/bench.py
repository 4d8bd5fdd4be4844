"""The harness: finds a cell's files by the names in ``BENCHMARK.json``,
drives the checkpointer through its public entry points as the cell's
traffic file says, and reduces what it saw to the result's last line.

It holds no table of cells, configurations, states, mixes or per-layer
metrics: a cell is an entry of ``workloads``; its configuration is the
``file`` of the entry of ``configs``; its mix is ``traffic/<traffic>.json``,
each per-layer metric ``metrics/<name>.py`` and its state ``states/<name>.py``,
``<name>`` being the configuration file's key ``"state"``, under one of
``paths``.

A state file gives ``factory(conf, mesh)``, whose object has ``mesh``,
``shardings``, ``make(seed) -> tree`` (one jitted call, born with its
shardings), ``step(tree, batch) -> (tree, loss)`` (jitted, argument 0
donated, the loss a scalar), ``batch_pool(seed, batch, n) -> list`` (on the
device; ``batch`` is the mix's, handed through as it is), and a dict
``TINY``: the keys that cut such a configuration to a size a CPU test runs.

The one general generator (``Driver``) knows five operations, and a traffic
file is an arrangement of them with its parameters:

    step     one donated train step on the next batch, loss read back
    take     blocking ``Snapshot.take`` of the live state to a new directory
    cycle    ``Snapshot.async_take``, donated steps until ``done()``, ``wait()``
    drop     let go of the live state
    restore  fresh template, ``Snapshot(newest).restore``, block on every leaf,
             let go of it

A traffic file may also say where its snapshots go: ``"sink": "ram"`` is a
tmpfs of the run's own under ``TMPDIR`` (the local RAM-disk tier of a
multi-tier checkpointer), and without the key they go under ``TMPDIR`` as
they are (``make_sink``).
"""

from __future__ import annotations

import contextlib
import ctypes
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np
import psutil

from . import arith, state, trace_reduce

NO_CHIP = 3  # exit code: no accelerator, or fewer chips than the cell asks
NO_SINK = 4  # exit code: the mix asks for a RAM-backed sink and the run can have none
SINK_STATES = 4  # room a RAM sink must have, in states: kept, in flight, staging, slack


class NoChip(RuntimeError):
    pass


class NoSink(RuntimeError):
    pass


# ----------------------------------------------------------------- discovery


def find_file(root: str, paths: List[str], sub: str, filename: str) -> str:
    for base in paths:
        path = os.path.join(root, base, sub, filename)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {os.path.join(sub, filename)} under {paths}")


def load_file(path: str, kind: str):
    """The module of one metric's reader or one state, found by its name."""
    name = os.path.basename(path)[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def state_file(root: str, paths: List[str], conf: Dict[str, Any]) -> str:
    """The state file a configuration names.  There is no default: one that
    names none, or one that is not there, is an error."""
    if not conf.get("state"):
        raise KeyError(
            'the configuration names no state: its key "state" is the <name> '
            f"of a file states/<name>.py under {paths}"
        )
    return find_file(root, paths, "states", conf["state"] + ".py")


def load_state(root: str, paths: List[str], conf: Dict[str, Any]):
    """The module of the state file a configuration names."""
    return load_file(state_file(root, paths, conf), "state")


class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, root: str, workload: str) -> None:
        self.root = root
        self.spec = state.load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in self.spec["configs"]}
        self.config = state.load_json(
            os.path.join(root, configs[self.workload["config"]]["file"])
        )
        self.traffic = state.load_json(
            self._find("traffic", self.workload["traffic"] + ".json")
        )
        self.peaks = state.load_json(self._find("", "peaks.json"))
        self.state = load_state(root, self.spec["paths"], self.config)

    def _find(self, sub: str, filename: str) -> str:
        return find_file(self.root, self.spec["paths"], sub, filename)

    def _listed(self, table: str) -> List[Dict[str, Any]]:
        return [
            m for m in self.spec[table]
            if "workloads" not in m or self.name in m["workloads"]
        ]

    def end_to_end_metrics(self) -> List[Dict[str, Any]]:
        return self._listed("end_to_end")

    def per_layer_metrics(self) -> List[Dict[str, Any]]:
        return self._listed("per_layer")

    def reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        return load_file(self._find("metrics", metric + ".py"), "metric").read

    def state_factory(self, mesh):
        """Makes the configuration's states under ``mesh``."""
        return self.state.factory(self.config, mesh)

    def peak_of(self, device_kind: str) -> Dict[str, Any]:
        if device_kind not in self.peaks:
            raise KeyError(
                f"device kind {device_kind!r} is not in peaks.json: a device "
                f"that is not in the table is an error, not a default"
            )
        return self.peaks[device_kind]


# ------------------------------------------------------------------- pollers


class MaxPoller:
    """Max of ``read()`` sampled every ``interval_s`` while it runs (a copy
    of ``chip_smoke._BytesInUsePoller``'s idea: a sampler, so per-layer
    only — it can miss a short spike)."""

    def __init__(self, read: Callable[[], Optional[float]], interval_s: float) -> None:
        self._read, self._interval = read, interval_s
        self.max_seen: Optional[float] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _poll(self) -> None:
        while not self._stop.is_set():
            value = self._read()
            if value is None:
                return
            self.max_seen = value if self.max_seen is None else max(self.max_seen, value)
            self._stop.wait(self._interval)

    def __enter__(self) -> "MaxPoller":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=10)


def _bytes_in_use(devices) -> Callable[[], Optional[float]]:
    def read() -> Optional[float]:
        stats = [d.memory_stats() for d in devices]
        if any(s is None for s in stats):
            return None
        return max(s["bytes_in_use"] for s in stats)

    return read


def _dir_bytes(path: str) -> int:
    total = 0
    for base, _dirs, files in os.walk(path):
        total += sum(os.path.getsize(os.path.join(base, f)) for f in files)
    return total


# ---------------------------------------------------------------------- sink


def _fs_type(path: str) -> str:
    """The type of the file system ``path`` sits on, by ``/proc/mounts``
    (``statvfs`` gives no type in Python)."""
    path, best, kind = os.path.realpath(path), "", "unknown"
    try:
        with open("/proc/mounts") as f:
            mounts = [line.split() for line in f]
    except OSError:
        return kind
    for _device, mount, fstype, *_ in mounts:
        under = path == mount or path.startswith(mount.rstrip("/") + "/")
        if under and len(mount) >= len(best):
            best, kind = mount, fstype
    return kind


def _mount_own_tmpfs(path: str) -> None:
    """A tmpfs on ``path`` in a mount namespace of the process's own: no
    other process sees it, and it goes, pages and all, with the process,
    however that ends.  It is there for the calling thread and the threads
    started after the call, so a run calls this before JAX starts any."""
    libc = ctypes.CDLL(None, use_errno=True)
    ms_rec, ms_private = 1 << 14, 1 << 18
    os.unshare(os.CLONE_NEWNS)
    options = f"size={psutil.virtual_memory().total},mode=0700".encode()
    for args in (
        (None, b"/", None, ms_rec | ms_private, None),  # nothing reaches the parent's
        (b"tmpfs", path.encode(), b"tmpfs", 0, options),
    ):
        if libc.mount(*args):
            raise OSError(ctypes.get_errno(), os.strerror(ctypes.get_errno()))


def make_sink(kind: str):
    """The run's ``chipbench_*`` directory, always a new one under
    ``TMPDIR``, and the file system it sits on.  ``tmp``: as it is.  ``ram``:
    as it is where ``TMPDIR`` is a tmpfs, and otherwise a tmpfs of the run's
    own mounted on it; where that cannot be had the run ends (``NoSink``)
    and never falls back to a disk."""
    if kind not in ("tmp", "ram"):
        raise ValueError(f"unknown sink {kind!r}: a mix's sink is 'ram' or absent")
    path = tempfile.mkdtemp(prefix="chipbench_")
    base, fstype = os.path.dirname(path), _fs_type(path)
    if kind == "tmp" or fstype == "tmpfs":
        return path, f"{fstype} {base}"
    try:
        _mount_own_tmpfs(path)
    except OSError as e:
        os.rmdir(path)
        raise NoSink(
            f"the mix asks for a RAM-backed sink: {base} is {fstype}, and the "
            f"run may not mount a tmpfs of its own there ({e})"
        ) from e
    return path, f"tmpfs own mount under {base}"


def need_room(path: str, need: int) -> None:
    """A RAM sink holds ``need`` bytes, by ``statvfs`` and by the host's
    available memory (a tmpfs may be sized past it), or the run ends."""
    fs = os.statvfs(path)
    room = min(fs.f_bavail * fs.f_frsize, psutil.virtual_memory().available)
    if room < need:
        raise NoSink(
            f"the mix asks for a RAM-backed sink and {os.path.dirname(path)} "
            f"has room for {room} B of {need}"
        )


def remove_sink(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)
    if os.path.isdir(path):  # emptied, and busy: the run's own mount
        ctypes.CDLL(None).umount2(path.encode(), 2)  # MNT_DETACH
        with contextlib.suppress(OSError):
            os.rmdir(path)


# -------------------------------------------------------------------- driver


class Driver:
    """Runs the operations of a traffic file and keeps the timeline."""

    def __init__(
        self, cell: Cell, seed: int, devices, snap_root: str,
        fault: Optional[str] = None,
    ) -> None:
        mix = cell.traffic
        self.seed = seed
        self.snap_root, self.fault = snap_root, fault
        self.traced = self.in_window = False
        self.save = cell.state_factory(state.build_mesh(devices, *mix["save_mesh"]))
        self.rest = (
            self.save if mix["restore_mesh"] == mix["save_mesh"]
            else cell.state_factory(state.build_mesh(devices, *mix["restore_mesh"]))
        )
        self.batches = self.save.batch_pool(seed, mix["batch"], 16)
        self.digest = state.Digester()
        picks = mix.get("check", {"loops": 0, "below": 1})
        # which timed restores are held against the reference, drawn from
        # the seed; restore 0 is the set-up's and is always checked
        self.check_at = set(
            (1 + np.random.default_rng(seed).choice(
                picks["below"], size=min(picks["loops"], picks["below"]), replace=False
            )).tolist()
        )
        # ``keep_one``: of the window's snapshots one is read back after it,
        # each as likely as any other, by draws from the seed (a reservoir of
        # one); the others go once they are committed, so that a run holds
        # two at the most
        self.keep_draws = (
            np.random.default_rng([seed, 1]) if mix.get("keep_one") else None
        )
        self.kept: Optional[Dict[str, Any]] = None
        self.commits = self.window_commits = 0
        self.ts = None
        self.steps_done = 0
        self.restores_done = 0
        self.snapshots: List[Dict[str, Any]] = []
        self.timeline: arith.Timeline = []
        self.notes: Dict[str, Any] = {"offload": []}
        self.wrong = {"leaves_mismatched": 0, "leaves_misplaced": 0,
                      "answers_missing": 0, "meta_step_wrong": 0}
        self.answers_checked = 0
        self.bytes_written = 0  # snapshots' bytes on disk, set-up's included
        self.loss_gap: Optional[float] = None

    # ------------------------------------------------------------ plumbing

    @contextlib.contextmanager
    def _op(self, op: str, span: Optional[str] = None, **extra):
        """Times one record of the timeline; in a traced run the same
        interval is an annotation (``span``, or the op's own name) in the
        profiler's trace."""
        import jax

        annotation = (
            jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + (span or op))
            if self.traced else contextlib.nullcontext()
        )
        record = {"op": op, **extra}
        with annotation:
            record["t0"] = time.monotonic()
            yield record
            record["t1"] = time.monotonic()
        self.timeline.append(record)

    def _app(self, tree, step: int) -> Dict[str, Any]:
        from torchsnapshot_tpu import PyTreeState, StateDict

        return {"ts": PyTreeState(tree), "meta": StateDict(step=step)}

    def _new_dir(self) -> str:
        return os.path.join(self.snap_root, f"snap{self.commits:03d}")

    def _reference(self) -> Dict[str, Any]:
        """What a snapshot taken now has to hold: the plain digests of the
        state on the device at the call, and how a resumed state must sit."""
        with self._op("check"):
            ref = self.digest(self.ts)
        found = {"ref": ref, "step": self.steps_done}
        if self.fault == "late_snapshot":
            self.step()  # the state moves on between the call and the copy
        return found

    def _take_kwargs(self) -> Dict[str, Any]:
        if self.fault != "control_bf16":
            return {}
        # the control: the program's own lossy path, bfloat16 for float32
        import jax.numpy as jnp

        def lossy(_path, leaf):
            if getattr(leaf, "dtype", None) == jnp.float32:
                return leaf.astype(jnp.bfloat16).astype(jnp.float32)
            return leaf

        return {"leaf_transform": lossy}

    # ---------------------------------------------------------- operations

    def make_state(self) -> None:
        import jax

        self.ts = self.save.make(self.seed)
        jax.block_until_ready(self.ts)
        self.notes["state_bytes"] = state.state_bytes(self.ts)
        self.notes["array_leaves"] = len(state.array_leaves(self.ts))

    def step(self, in_flight: bool = False) -> float:
        """A step while a save drains is a ``step`` record for the
        arithmetic, under the span ``drain`` in the trace."""
        batch = self.batches[self.steps_done % len(self.batches)]
        with self._op("step", span="drain" if in_flight else None, in_flight=in_flight):
            with self.save.mesh:
                self.ts, loss = self.save.step(self.ts, batch)
            loss = float(loss)
        self.steps_done += 1
        if not np.isfinite(loss):
            raise FloatingPointError(f"loss {loss} at step {self.steps_done}")
        return loss

    def take(self) -> None:
        from torchsnapshot_tpu import Snapshot

        snap = {"path": self._new_dir(), **self._reference()}
        with self._op("take"):
            Snapshot.take(snap["path"], self._app(self.ts, snap["step"]),
                          **self._take_kwargs())
        self._committed(snap)

    def cycle(self) -> None:
        from torchsnapshot_tpu import Snapshot, host_offload

        snap = {"path": self._new_dir(), **self._reference()}
        steps = 0
        t0 = time.monotonic()
        with self._op("take", asynchronous=True):
            pending = Snapshot.async_take(
                snap["path"], self._app(self.ts, snap["step"]), **self._take_kwargs()
            )
        self.notes["offload"].append(dict(host_offload.LAST_OFFLOAD_STATS))
        while True:
            loss = self.step(in_flight=True)
            if steps == 0:
                snap["next_loss"] = loss
            steps += 1
            if pending.done():
                break
        pending.wait()
        self.timeline.append(
            {"op": "cycle", "t0": t0, "t1": time.monotonic(), "steps": steps}
        )
        self._committed(snap)

    def _committed(self, snap: Dict[str, Any]) -> None:
        self.bytes_written += _dir_bytes(snap["path"])
        self.commits += 1
        if not self.in_window or self.keep_draws is None:
            self.snapshots.append(snap)
            return
        marker = os.path.isfile(os.path.join(snap["path"], ".snapshot_metadata"))
        self.wrong["answers_missing"] += int(not marker)
        self.window_commits += 1
        # commit k takes the kept one's place with the chance 1/k
        goes = snap
        if self.keep_draws.random() < 1 / self.window_commits:
            goes, self.kept = self.kept, snap
        if goes is not None:
            with self._op("check"):
                shutil.rmtree(goes["path"])

    def drop(self) -> None:
        self.ts = None

    def restore(self, snap: Optional[Dict[str, Any]] = None, keep: bool = False):
        """One resume: a fresh template, restore into it, wait for every
        leaf, let go.  ``snap`` given: a read-back after the window, which
        leaves no record."""
        import jax

        from torchsnapshot_tpu import Snapshot

        timed = snap is None
        snap = snap or self.snapshots[-1]
        index = self.restores_done
        self.restores_done += 1
        op = self._op if timed else (lambda _name: contextlib.nullcontext())
        with op("template"):
            template = self.rest.make(self.seed + 1 + index)
            app = self._app(template, -1)
            want_layout = state.layout_of(template)
            del template
        with op("restore"):
            Snapshot(snap["path"]).restore(app)
            tree = app["ts"].tree
            jax.block_until_ready(tree)
        if self.fault == "answer_altered":
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            at = max(range(len(leaves)), key=lambda i: getattr(leaves[i], "nbytes", 0))
            leaves[at] = leaves[at].at[(0,) * leaves[at].ndim].add(1)
            tree = jax.tree_util.tree_unflatten(treedef, leaves)
        if not (timed and self.in_window) or index in self.check_at or self.fault:
            with op("check"):
                self._judge(snap, tree, want_layout, app["meta"]["step"])
        if not self.in_window:
            self.notes["restored_fullest_device_bytes"] = state.fullest_device_bytes(tree)
        return tree if keep else None

    def _judge(self, snap, tree, want_layout, meta_step) -> None:
        found = state.compare(
            snap["ref"], self.digest(tree), want_layout, state.layout_of(tree)
        )
        for key, n in found.items():
            self.wrong[key] += n
        self.wrong["meta_step_wrong"] += int(meta_step != snap["step"])
        self.answers_checked += 1

    def read_back_all(self) -> None:
        """After the window: every snapshot it committed, read back and held
        against the state on the device at its take call.  After an async
        cycle the resumed state must also give the loss that the first
        donated step after the call gave."""
        for snap in self.snapshots:
            if not os.path.isfile(os.path.join(snap["path"], ".snapshot_metadata")):
                self.wrong["answers_missing"] += 1
                continue
            self.ts = self.restore(snap, keep="next_loss" in snap)
            if self.ts is not None:
                self.steps_done = snap["step"]
                gap = abs(self.step() - snap["next_loss"])
                self.loss_gap = max(self.loss_gap or 0.0, gap)
                self.ts = None

    def run_ops(self, ops: List[str]) -> None:
        for op in ops:
            getattr(self, op)()

    def window(self, plan: Dict[str, Any], seconds: float) -> None:
        """``pre`` once; ``loop`` until ``seconds`` of work are done (at
        least once, at most ``max_loops`` times) or exactly ``loops`` times,
        never cut; then ``fill`` to the end of ``seconds`` (``fill_least``
        times or more)."""
        self.in_window = True
        self.run_ops(plan.get("pre", []))

        def time_left() -> bool:
            return arith.window_seconds(self.timeline) < seconds

        loops = 0
        fixed, cap = plan.get("loops"), plan.get("max_loops")
        while loops < fixed if fixed else (
            loops == 0 or (time_left() and (cap is None or loops < cap))
        ):
            self.run_ops(plan["loop"])
            loops += 1
        filled = 0
        while plan.get("fill") and (time_left() or filled < plan.get("fill_least", 0)):
            self.run_ops(plan["fill"])
            filled += 1
        if self.kept is not None:
            self.snapshots.append(self.kept)
        self.in_window = False


# ----------------------------------------------------------------- one run


class Context:
    """What a per-layer reader may read: the window's timeline, the
    program's counters, histograms and spans over the window, and what the
    benchmark's own pollers saw.  A reader returns None where it finds
    nothing to read, and the harness leaves that metric out."""

    def __init__(self, **fields: Any) -> None:
        self.__dict__.update(fields)

    def count(self, op: str) -> int:
        return arith.count(self.timeline, op)

    def hist_per(self, name: str, op: str) -> Optional[float]:
        """Seconds a histogram of the program gained over the window, per
        completed ``op`` (thread-seconds where many threads observe)."""
        after = self.obs_after["histograms"].get(name)
        n = self.count(op)
        if after is None or not after["count"] or not n:
            return None
        before = self.obs_before["histograms"].get(name, {"sum": 0.0})
        return (after["sum"] - before["sum"]) / n

    def span_seconds(self, name: str) -> List[float]:
        return [s.duration_ns / 1e9 for s in self.spans if s.name == name]


def enable_compile_cache(root: str) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one (JAX reads that itself)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(root, ".jax_cache")
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def pick_devices(chips: int, allow_cpu: bool):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX found no TPU: platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips and JAX found {len(devices)}")
    return devices[:chips]


def run_cell(
    root: str, workload: str, seed: int, seconds: float, trace: bool,
    allow_cpu: bool = False, fault: Optional[str] = None,
    started_at: Optional[float] = None,
) -> Dict[str, Any]:
    """One run of one cell; returns the result's last line as a dict.
    ``allow_cpu`` and ``fault`` are for the tests under ``paths`` alone."""
    started_at = started_at if started_at is not None else time.time()
    cell = Cell(root, workload)
    mix = cell.traffic
    # before JAX starts its threads: a tmpfs of the run's own is there for
    # this thread and for those started after it
    sink = mix.get("sink", "tmp")
    snap_root, sink_fs = make_sink(sink)
    trace_dir = os.path.join(snap_root, "trace")
    try:
        import jax

        if not allow_cpu:
            enable_compile_cache(root)
        devices = pick_devices(cell.chips, allow_cpu)
        kind = devices[0].device_kind
        if devices[0].platform == "tpu":
            cell.peak_of(kind)

        from torchsnapshot_tpu import obs
        from torchsnapshot_tpu.obs import tracer as program_tracer
        from torchsnapshot_tpu.ops import device_pack
        from torchsnapshot_tpu.preparers.array import DONATION_STATS

        def swallowed() -> int:
            return obs.metrics_snapshot()["counters"].get("exceptions.swallowed", 0)

        swallowed0 = swallowed()
        driver = Driver(cell, seed, devices, snap_root, fault=fault)
        driver.make_state()
        if sink == "ram":
            need_room(snap_root, SINK_STATES * driver.notes["state_bytes"])
        driver.run_ops(mix["setup"])
        setup_timeline, driver.timeline = driver.timeline, []
        print("chipbench set-up: " + ", ".join(
            f"{r['op']} {r['t1'] - r['t0']:.2f}s" for r in setup_timeline
        ), file=sys.stderr)
        # the window reads the newest snapshot of the set-up, or none of
        # them; the others would only hold disk
        keep = 0 if mix.get("forget_setup_snapshots") else 1
        for snap in driver.snapshots[: len(driver.snapshots) - keep]:
            shutil.rmtree(snap["path"])
        driver.snapshots = driver.snapshots[len(driver.snapshots) - keep:]

        with contextlib.ExitStack() as pollers:
            if trace:
                proc = psutil.Process()
                rss0 = proc.memory_info().rss
                hbm = pollers.enter_context(MaxPoller(_bytes_in_use(devices), 0.02))
                rss = pollers.enter_context(
                    MaxPoller(lambda: proc.memory_info().rss - rss0, 0.1)
                )
                program_tracer.set_tracing(True)
                program_tracer.get_tracer().reset()
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                driver.traced = True
            obs_before = obs.metrics_snapshot()
            calls0 = dict(device_pack.CALL_COUNTS)
            donated0 = DONATION_STATS["donated_templates"]
            setup_s = time.time() - started_at
            driver.window(mix["window"], seconds)
            obs_after = obs.metrics_snapshot()
            pack_calls = device_pack.CALL_COUNTS["pack"] - calls0["pack"]
            unpack_calls = device_pack.CALL_COUNTS["unpack"] - calls0["unpack"]
            donated = DONATION_STATS["donated_templates"] - donated0
            if trace:
                driver.traced = False
                jax.profiler.stop_trace()
                program_tracer.set_tracing(False)
        timeline, driver.timeline = driver.timeline, []
        print("chipbench window: " + ", ".join(
            f"{r['op']} {r['t1'] - r['t0']:.2f}s" for r in timeline
            if r["op"] in ("take", "cycle", "check") or r["t1"] - r["t0"] > 1
        ), file=sys.stderr)
        peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
        )
        spans = program_tracer.get_tracer().spans() if trace else []
        disk_bytes = (
            _dir_bytes(driver.snapshots[-1]["path"]) if driver.snapshots else None
        )

        # the window has closed and the peak is read: now the comparison
        if mix.get("read_back"):
            driver.drop()
            driver.read_back_all()
        n_swallowed = swallowed() - swallowed0

        reduced = None
        if trace:
            xplane = trace_reduce.find_xplane(trace_dir)
            record = trace_reduce.load_xplane(xplane)
            reduced = trace_reduce.reduce(record)
    finally:
        remove_sink(snap_root)

    checks: Dict[str, Dict[str, Any]] = {
        key: {"value": n, "limit": 0} for key, n in driver.wrong.items()
    }
    if driver.loss_gap is not None:
        checks["loss_gap"] = {"value": driver.loss_gap, "limit": 0.0}
    checks["exceptions_swallowed"] = {"value": n_swallowed, "limit": 0}
    checks["answers_unchecked"] = {
        "value": max(0, mix["answers_checked_least"] - driver.answers_checked),
        "limit": 0,
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    values = arith.end_to_end(timeline, mix["end_to_end"])
    values["setup_s"] = setup_s
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": arith.count(timeline, mix["counts_as_attempt"]),
        "failed": int(not correct),
    }
    device = {
        "platform": devices[0].platform, "kind": kind,
        "count": len(jax.devices()), "memory_peak_bytes": int(peak),
    }
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end_metrics() if m["name"] in values
        }
    else:
        ctx = Context(
            timeline=timeline, setup_timeline=setup_timeline, notes=driver.notes,
            obs_before=obs_before, obs_after=obs_after, spans=spans,
            pack_calls=pack_calls, unpack_calls=unpack_calls,
            donated_templates=donated, hbm_poll_max=hbm.max_seen,
            rss_peak_delta=rss.max_seen, disk_bytes=disk_bytes,
        )
        result["metrics"] = {}
        for m in cell.per_layer_metrics():
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
        elif devices[0].platform == "tpu":
            raise RuntimeError("the traced window holds no device operation")
    result["device"] = device
    result["window_s"] = arith.window_seconds(timeline)
    result["state_bytes"] = driver.notes["state_bytes"]
    result["bytes_written"] = driver.bytes_written
    result["sink"], result["sink_fs"] = sink, sink_fs
    result["checks"] = checks
    return result


def print_result(result: Dict[str, Any]) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error; the result as the last line on standard output."""
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
