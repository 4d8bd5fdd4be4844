"""The save cells' windows and their sink, at tiny widths on the CPU: a
window fills its seconds with whole loops and keeps one snapshot for the
read-back, any of the window's with the same chance, by draws from the
seed; a mix that says ``"sink": "ram"`` puts its snapshots on a tmpfs under
``TMPDIR``, its own mount where ``TMPDIR`` is none, or ends the run with a
code of its own, and a mix without the key stays under ``TMPDIR`` as it is."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import bench

SYNC, ASYNC = "ouro-2.6b-d9.preempt_sync_save", "ouro-2.6b-d4.async_save_train"
# seconds that hold well over three loops at tiny widths, on a loaded machine too
FILLS = {SYNC: 0.5, ASYNC: 1.5}


def kept_by_the_draws(seed, commits):
    """The reservoir's rule, worked by hand: commit k takes the place with
    the chance 1/k."""
    draws, kept = np.random.default_rng([seed, 1]), None
    for k in range(1, commits + 1):
        if draws.random() < 1 / k:
            kept = k
    return kept


@pytest.mark.parametrize("workload", [SYNC, ASYNC])
def test_a_save_window_runs_one_whole_loop_at_the_least(run_tiny, workload):
    result = run_tiny(workload, seconds=0.01)
    assert result["attempted"] == 1 and result["correct"] is True
    setup_takes = 2 if workload == SYNC else 1  # the sync mix warms the tier's pages
    assert result["bytes_written"] >= (1 + setup_takes) * result["state_bytes"]
    assert ("loss_gap" in result["checks"]) == (workload == ASYNC)


@pytest.mark.parametrize("workload", [SYNC, ASYNC])
def test_a_save_window_fills_its_seconds_and_cuts_no_loop(run_tiny, workload, monkeypatch):
    seen = {}
    window = bench.Driver.window

    def keep(self, plan, seconds):
        window(self, plan, seconds)
        seen["timeline"] = list(self.timeline)

    monkeypatch.setattr(bench.Driver, "window", keep)
    seconds = FILLS[workload]
    result = run_tiny(workload, seconds=seconds)
    assert result["attempted"] > 3 and result["correct"] is True
    timeline = [r for r in seen["timeline"] if r["op"] != "check"]
    ops = [("cycle" if r.get("asynchronous") else r["op"]) for r in timeline if r["op"] != "cycle"]
    if workload == SYNC:
        assert ops == ["step", "take"] * result["attempted"]
        # the last loop began inside the seconds, and ran to its end
        assert timeline[-2]["t0"] - timeline[0]["t0"] < seconds + 0.2
    else:
        cycles = [r for r in timeline if r["op"] == "cycle"]
        assert len(cycles) == result["attempted"] and all(c["steps"] >= 1 for c in cycles)
        assert ops[:10] == ["step"] * 10 and ops[10] == "cycle"
        tail = timeline[timeline.index(cycles[-1]) + 1:]
        assert len(tail) >= 10 and all(r["op"] == "step" and not r["in_flight"] for r in tail)
    assert result["window_s"] >= seconds


@pytest.mark.parametrize("workload", [SYNC, ASYNC])
@pytest.mark.parametrize("seed", [2**31 + 7, 2**31 + 8, 5])
def test_one_snapshot_of_the_window_is_kept_and_read_back(run_tiny, workload, seed, monkeypatch):
    seen = {}
    read_back = bench.Driver.read_back_all

    def keep(self):
        seen["kept"] = [os.path.basename(s["path"]) for s in self.snapshots]
        seen["on_the_sink"] = sorted(d for d in os.listdir(self.snap_root) if d.startswith("snap"))
        checked = self.answers_checked
        read_back(self)
        seen["read_back"] = self.answers_checked - checked

    monkeypatch.setattr(bench.Driver, "read_back_all", keep)
    result = run_tiny(workload, seed=seed, seconds=FILLS[workload])
    assert result["attempted"] > 3 and result["correct"] is True
    # the set-up's come first (snap000, and snap001 under the sync mix)
    drawn = kept_by_the_draws(seed, result["attempted"]) + (1 if workload == SYNC else 0)
    assert seen["kept"] == seen["on_the_sink"] == [f"snap{drawn:03d}"]
    assert seen["read_back"] == 1
    assert result["checks"]["answers_missing"]["value"] == 0


def _commit(driver, root, k):
    path = os.path.join(root, f"snap{k:03d}")
    os.mkdir(path)
    open(os.path.join(path, ".snapshot_metadata"), "w").close()
    driver._committed({"path": path})
    return sorted(os.listdir(root))


def _bare_driver(seed):
    driver = bench.Driver.__new__(bench.Driver)
    driver.__dict__.update(
        in_window=True, traced=False, kept=None, commits=0, window_commits=0,
        bytes_written=0, timeline=[], snapshots=[], wrong={"answers_missing": 0},
        keep_draws=np.random.default_rng([seed, 1]),
    )
    return driver


def test_every_snapshot_of_a_window_is_as_likely_to_be_the_kept_one(tmp_path):
    commits, seeds = 8, 400
    kept = []
    for seed in range(2**31, 2**31 + seeds):
        root = str(tmp_path / str(seed))
        os.mkdir(root)
        driver = _bare_driver(seed)
        for k in range(1, commits + 1):
            # the kept one and no other: two snapshots at the most, while one is written
            assert _commit(driver, root, k) == [os.path.basename(driver.kept["path"])]
        assert driver.wrong["answers_missing"] == 0 and driver.snapshots == []
        kept.append(int(os.path.basename(driver.kept["path"])[4:]))
        assert kept[-1] == kept_by_the_draws(seed, commits)
    counts = [kept.count(k) for k in range(1, commits + 1)]
    assert sum(counts) == seeds and min(counts) >= seeds / commits / 2, counts
    # what the first three alone could never show: most are kept from past them
    assert sum(counts[3:]) > sum(counts[:3])


def test_a_commit_without_its_marker_is_an_answer_missing(tmp_path):
    driver = _bare_driver(5)
    _commit(driver, str(tmp_path), 1)
    path = str(tmp_path / "snap002")
    os.mkdir(path)
    driver._committed({"path": path})
    assert driver.wrong["answers_missing"] == 1


# ------------------------------------------------------------------- sink


@pytest.fixture
def sinks_seen(monkeypatch):
    """Where each run put its ``chipbench_*`` directory, and what it said
    of the file system, read while the directory is there."""
    seen = []
    make = bench.make_sink

    def keep(kind):
        path, fs = make(kind)
        seen.append((kind, path, fs))
        return path, fs

    monkeypatch.setattr(bench, "make_sink", keep)
    return seen


@pytest.mark.parametrize("workload", [SYNC, ASYNC])
def test_a_mix_with_a_ram_sink_puts_its_snapshots_on_a_tmpfs_of_its_own(
    run_tiny, sinks_seen, no_mount, workload, tmp_path, monkeypatch
):
    takes = []
    from torchsnapshot_tpu import Snapshot

    for name in ("take", "async_take"):
        real = getattr(Snapshot, name)
        monkeypatch.setattr(
            Snapshot, name,
            staticmethod(lambda path, *a, _real=real, **kw: takes.append(path) or _real(path, *a, **kw)),
        )
    result = run_tiny(workload, seconds=0.01)
    (kind, path, fs), = sinks_seen
    assert kind == "ram" and no_mount == [path]
    assert os.path.dirname(path) == str(tmp_path) and os.path.basename(path).startswith("chipbench_")
    assert not os.path.exists(path)
    assert len(takes) == (3 if workload == SYNC else 2)  # the set-up's, and one loop
    assert all(os.path.dirname(t) == path for t in takes)
    assert (result["sink"], result["sink_fs"]) == ("ram", fs)
    assert fs == f"tmpfs own mount under {tmp_path}"
    assert list(result)[-1] == "checks"


def test_a_ram_sink_under_a_tmpdir_that_is_a_tmpfs_mounts_nothing(no_mount, tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "_fs_type", lambda path: "tmpfs")
    path, fs = bench.make_sink("ram")
    assert no_mount == [] and os.path.dirname(path) == str(tmp_path)
    assert fs == f"tmpfs {tmp_path}"
    bench.remove_sink(path)
    assert os.listdir(tmp_path) == []


@pytest.mark.parametrize("workload", ["ouro-2.6b-d9.kill_resume", "ouro-2.6b-d32.reshard_resume"])
def test_a_mix_without_the_key_keeps_its_snapshots_under_tmpdir(
    run_tiny, sinks_seen, no_mount, workload, tmp_path
):
    result = run_tiny(workload)
    (kind, path, fs), = sinks_seen
    assert kind == "tmp" and no_mount == [] and os.path.dirname(path) == str(tmp_path)
    assert os.path.basename(path).startswith("chipbench_") and not os.path.exists(path)
    assert result["sink"] == "tmp" and result["sink_fs"] == fs
    assert fs == f"{bench._fs_type(str(tmp_path))} {tmp_path}"


TRAFFIC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "chipbench", "traffic",
)
# the sinks of the mixes that were there when this was written; a mix that a
# later PR commits is one more case, and needs no line here
SINKS = {
    "kill_resume": None, "reshard_resume": None, "preempt_sync_save": "ram",
    "async_save_train": "ram", "elastic_resume": "ram",
}


@pytest.mark.parametrize("name", sorted(f[:-5] for f in os.listdir(TRAFFIC)))
def test_the_committed_mixes_that_name_a_sink(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        mix = json.load(f)
    sink = mix.get("sink")
    assert sink in (None, "ram")
    assert sink == SINKS.get(name, sink)
    # a mix that names the RAM tier names the deployment that has one
    assert sink is None or mix["source"]


def test_a_ram_sink_that_may_not_be_mounted_is_nothing(tmp_path, monkeypatch):
    def refuse(path):
        raise PermissionError(1, "Operation not permitted")

    monkeypatch.setattr(bench, "_mount_own_tmpfs", refuse)
    with pytest.raises(bench.NoSink, match="may not mount"):
        bench.make_sink("ram")
    assert os.listdir(tmp_path) == []  # and no fall-back to the disk under TMPDIR


def test_a_ram_sink_without_the_room_is_nothing(tmp_path, monkeypatch):
    bench.need_room(str(tmp_path), 1)
    with pytest.raises(bench.NoSink, match="room for"):
        bench.need_room(str(tmp_path), 2**62)
    # a tmpfs sized past the host's memory has the memory's room
    import psutil

    monkeypatch.setattr(psutil, "virtual_memory", lambda: type("M", (), {"available": 7})())
    with pytest.raises(bench.NoSink, match="room for 7 B of 8"):
        bench.need_room(str(tmp_path), 8)


def test_an_unknown_sink_is_an_error(tmp_path):
    with pytest.raises(ValueError, match="unknown sink"):
        bench.make_sink("disk")
    assert os.listdir(tmp_path) == []


@pytest.mark.parametrize("workload", [SYNC, ASYNC])
@pytest.mark.parametrize("why", ["no_room", "no_mount"])
def test_no_ram_sink_ends_the_run_with_its_own_code_and_no_result(
    tiny_root, workload, why, tmp_path, monkeypatch, capsys
):
    from chipbench import run

    real = bench.run_cell
    if why == "no_room":
        monkeypatch.setattr(bench, "SINK_STATES", 2**50)
    else:
        def refuse(path):
            raise PermissionError(1, "Operation not permitted")

        monkeypatch.setattr(bench, "_mount_own_tmpfs", refuse)
    monkeypatch.setattr(
        bench, "run_cell",
        lambda _root, *a, started_at=None, **kw: real(tiny_root, *a, allow_cpu=True, **kw),
    )
    code = run.main(["--workload", workload, "--seed", "9", "--seconds", "0.01", "--trace", "0"])
    out, err = capsys.readouterr()
    assert code == bench.NO_SINK and code not in (0, bench.NO_CHIP)
    assert out == ""
    assert "RAM-backed sink" in err and ("has room for" if why == "no_room" else "may not mount") in err
    assert os.listdir(tmp_path) == []


OWN_TMPFS = """
import os, signal, sys
sys.path.insert(0, {repo!r})
from chipbench import bench
try:
    path, fs = bench.make_sink("ram")
except bench.NoSink as e:
    print("NoSink", e); sys.exit(0)
open(os.path.join(path, "payload"), "wb").write(b"x" * 4096)
print(fs, "|", bench._fs_type(path), "|", path, flush=True)
if {killed}:
    os.kill(os.getpid(), signal.SIGKILL)
bench.remove_sink(path)
"""


@pytest.mark.parametrize("killed", [False, True])
def test_a_tmpfs_of_the_runs_own_goes_with_the_run(repo, tmp_path, killed):
    """The real mount, in a process of its own as a run is: the bytes are on
    a tmpfs that no other process sees and nothing of them is under TMPDIR,
    after a run that ended and after one that was killed."""
    env = dict(os.environ, TMPDIR=str(tmp_path), JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-c", OWN_TMPFS.format(repo=repo, killed=killed)],
        env=env, capture_output=True, text=True, timeout=120,
    )
    if done.stdout.startswith("NoSink"):
        pytest.skip("this machine lets a process mount no tmpfs: " + done.stdout.strip())
    assert done.returncode == (-9 if killed else 0), done.stderr
    fs, fstype, path = (part.strip() for part in done.stdout.strip().split("|"))
    if bench._fs_type(str(tmp_path)) != "tmpfs":
        assert fs == f"tmpfs own mount under {tmp_path}"
    assert fstype == "tmpfs" and os.path.dirname(path) == str(tmp_path)
    with open("/proc/mounts") as f:
        assert path not in f.read()
    # the payload was never under TMPDIR; a killed run leaves its empty directory
    assert os.listdir(tmp_path) == ([os.path.basename(path)] if killed else [])
    assert killed is False or os.listdir(path) == []
