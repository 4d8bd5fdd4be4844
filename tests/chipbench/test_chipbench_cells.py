"""Whole runs of every cell at tiny widths on the CPU (the harness's look
for a chip skipped, the rest of a run driven): the last line's shape, the
per-layer readers, and that a later PR adds a state, a configuration, a mix,
a cell and a per-layer metric by adding files and entries only.  The restore
cells' tiny windows count loops (``conftest.LOOPS``), never seconds."""

import json
import math
import os
import shutil

import pytest
from conftest import DUMMY_LEAVES, DUMMY_METRIC, DUMMY_READER, DUMMY_STATE, LOOPS, cut_to_tiny, shrink_mix

from chipbench import bench

CELLS = {
    "ouro-2.6b-d9.kill_resume": "resume_s",
    "ouro-2.6b-d9.preempt_sync_save": "save_commit_s",
    "ouro-2.6b-d4.async_save_train": "train_stall_s",
    "ouro-2.6b-d32.reshard_resume": "resume_s",
}
# what no CPU run can read: it has no memory_stats, and device unpack and
# template donation are off for CPU arrays by the program's own choice
CHIP_ONLY = {"restore.hbm_peak_x"}
# a difference of two readings of the clock: at tiny widths, where a save
# costs a step's noise, it lies about 0 on either side, by the worker's load
DIFFERENCES = {"train_stall_s"}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_last_line_of_a_plain_run(benchmark_json, run_tiny, workload, capsys):
    result = run_tiny(workload)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {CELLS[workload], "setup_s"}
    for name, m in result["metrics"].items():
        assert math.isfinite(m["value"]) and m["unit"] == "s"
        assert m["value"] > 0 or name in DIFFERENCES
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for check in result["checks"].values():
        assert check["value"] <= check["limit"]
    bench.print_result(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    tail = err.strip().splitlines()
    assert tail[-1] == "correct: True"
    assert all(line.startswith("check ") and "(limit " in line for line in tail[-1 - len(result["checks"]):-1])


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_traced_run_reports_the_cells_per_layer_metrics(full_spec, run_tiny, workload):
    result = run_tiny(workload, trace=True)
    wanted = {
        m["name"] for m in full_spec["per_layer"] if workload in m["workloads"]
    }
    assert set(result["metrics"]) >= wanted - CHIP_ONLY
    assert set(result["metrics"]) <= wanted
    assert result["correct"] is True
    units = {m["name"]: m["unit"] for m in full_spec["per_layer"]}
    for name, m in result["metrics"].items():
        assert m["unit"] == units[name]


def test_whole_window_counts_every_restore(tiny_root, tmp_path_factory):
    # a copy whose window the clock ends, as on the chip, and not the cap
    root = str(tmp_path_factory.mktemp("uncapped") / "checkout")
    shutil.copytree(tiny_root, root)
    mix = os.path.join(root, "chipbench", "traffic", "kill_resume.json")
    with open(mix) as f:
        uncapped = json.load(f)
    del uncapped["window"]["max_loops"]
    with open(mix, "w") as f:
        json.dump(uncapped, f)
    result = bench.run_cell(
        root, "ouro-2.6b-d9.kill_resume", seed=2**31 + 7, seconds=0.6, trace=False,
        allow_cpu=True,
    )
    resume = result["metrics"]["resume_s"]["value"]
    assert resume == pytest.approx(result["window_s"] / result["attempted"])
    assert result["window_s"] >= 0.6


def test_snapshots_never_land_in_the_checkout(run_tiny, tiny_root, benchmark_json):
    before = set(os.listdir(tiny_root))
    run_tiny(benchmark_json["workloads"][0]["name"])
    assert set(os.listdir(tiny_root)) == before


def test_a_later_pr_adds_a_cell_by_adding_files_and_entries_only(tiny_root, run_tiny):
    """A dummy of each: state (no transformer), configuration, traffic mix,
    per-layer metric, cell.  No file that was there is edited but
    BENCHMARK.json, which gains entries."""
    base = os.path.join(tiny_root, "chipbench")
    held = {
        path: open(path, "rb").read()
        for sub in ("", "configs", "traffic", "metrics", "states", "reference")
        for path in (os.path.join(base, sub, f) for f in os.listdir(os.path.join(base, sub)))
        if os.path.isfile(path)
    }
    assert any(os.sep + "states" + os.sep in path for path in held)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    with open(os.path.join(base, "states", "flat_mixed.py"), "w") as f:
        f.write(DUMMY_STATE)
    # at the size its source would state, and cut as every configuration of
    # the miniature is: by its own state file's TINY
    config = {"state": "flat_mixed", "leaves": DUMMY_LEAVES, "rows": 64, "hidden_size": 1024}
    with open(os.path.join(base, "configs", "dummy-flat.json"), "w") as f:
        json.dump(config, f)
    cut_to_tiny(tiny_root, spec["paths"], "chipbench/configs/dummy-flat.json")
    # a mix as a PR would commit it, with batches that only its state reads,
    # and cut as every mix of the miniature is
    mix = {
        "batch": {"uniform": [3, 5]}, "save_mesh": [1, 1], "restore_mesh": [1, 1],
        "setup": ["step", "step", "take", "drop", "restore"],
        "window": {"loop": ["restore"]},
        "check": {"loops": 3, "below": 40}, "answers_checked_least": 4,
        "read_back": False, "counts_as_attempt": "restore",
        "end_to_end": {"resume_s": {"kind": "window_per_op", "op": "restore"}},
    }
    mix_path = os.path.join(base, "traffic", "dummy_mix.json")
    with open(mix_path, "w") as f:
        json.dump(mix, f)
    shrink_mix(mix_path)
    with open(mix_path) as f:
        shrunk = json.load(f)
    assert shrunk["batch"] == mix["batch"] and shrunk["window"]["max_loops"] == LOOPS
    with open(os.path.join(base, "metrics", "dummy.templates.py"), "w") as f:
        f.write(DUMMY_READER)
    spec["configs"].append({
        "name": "dummy-flat", "source": "https://example.org/dummy",
        "file": "chipbench/configs/dummy-flat.json", "reduced": ["rows", "hidden_size"],
        "why": "a dummy"})
    spec["workloads"].append({
        "name": "dummy-flat.dummy_mix", "config": "dummy-flat", "traffic": "dummy_mix",
        "chips": 1, "why": "a dummy"})
    for m in spec["end_to_end"]:
        if m["name"] == "resume_s":
            m["workloads"].append("dummy-flat.dummy_mix")
    spec["per_layer"].append(dict(DUMMY_METRIC, workloads=["dummy-flat.dummy_mix"]))
    with open(path, "w") as f:
        json.dump(spec, f)

    plain = run_tiny("dummy-flat.dummy_mix")
    assert plain["correct"] and plain["attempted"] == LOOPS
    assert set(plain["metrics"]) == {"resume_s", "setup_s"}
    # 160 leaves of each width, at TINY's rows and width
    assert plain["state_bytes"] == DUMMY_LEAVES // 2 * 8 * 16 * (2 + 4)
    traced = run_tiny("dummy-flat.dummy_mix", trace=True)
    assert traced["correct"] is True
    assert traced["metrics"] == {"dummy.templates": {"value": float(LOOPS), "unit": "count"}}
    # its step changes every leaf: a snapshot of a state one step on differs
    # in all of them, in each answer (under a fault every restore is judged:
    # the set-up's and the window's LOOPS)
    late = run_tiny("dummy-flat.dummy_mix", fault="late_snapshot")
    assert late["correct"] is False
    assert late["checks"]["leaves_mismatched"]["value"] == (1 + LOOPS) * DUMMY_LEAVES
    assert late["checks"]["leaves_misplaced"]["value"] == 0
    # the control still comes out not correct on a state that is not all
    # float32: its lossy path rounds the float32 half, and each answer
    # differs in exactly those leaves
    control = run_tiny("dummy-flat.dummy_mix", fault="control_bf16")
    assert control["correct"] is False
    assert control["checks"]["leaves_mismatched"]["value"] == (1 + LOOPS) * (DUMMY_LEAVES // 2)
    assert control["checks"]["leaves_misplaced"]["value"] == 0
    for path, content in held.items():
        assert open(path, "rb").read() == content, path
