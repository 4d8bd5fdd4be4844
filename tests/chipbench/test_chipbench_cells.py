"""Whole runs of every cell at tiny widths on the CPU (the harness's look
for a chip skipped, the rest of a run driven): the last line's shape, the
per-layer readers, and that a later PR adds a configuration, a mix, a cell
and a per-layer metric by adding files and entries only."""

import json
import os

import pytest

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


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_last_line_of_a_plain_run(benchmark_json, run_tiny, workload, capsys):
    result = run_tiny(workload)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {CELLS[workload], "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"] == "s"
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


def test_whole_window_counts_every_restore(run_tiny, benchmark_json):
    result = run_tiny("ouro-2.6b-d9.kill_resume", seconds=0.6)
    resume = result["metrics"]["resume_s"]["value"]
    assert resume == pytest.approx(result["window_s"] / result["attempted"])
    assert result["window_s"] >= 0.6


def test_snapshots_never_land_in_the_checkout(run_tiny, tiny_root, benchmark_json):
    before = set(os.listdir(tiny_root))
    run_tiny(benchmark_json["workloads"][0]["name"])
    assert set(os.listdir(tiny_root)) == before


def test_a_later_pr_adds_a_cell_by_adding_files_and_entries_only(tiny_root, run_tiny):
    """A dummy of each: configuration, traffic mix, per-layer metric, cell.
    No file that was there is edited but BENCHMARK.json, which gains entries."""
    base = os.path.join(tiny_root, "chipbench")
    held = {
        path: open(path, "rb").read()
        for sub in ("", "configs", "traffic", "metrics")
        for path in (os.path.join(base, sub, f) for f in os.listdir(os.path.join(base, sub)))
        if os.path.isfile(path)
    }
    with open(os.path.join(base, "configs", os.listdir(os.path.join(base, "configs"))[0])) as f:
        config = json.load(f)
    config["num_hidden_layers"] = 1
    with open(os.path.join(base, "configs", "dummy-d1.json"), "w") as f:
        json.dump(config, f)
    mix = {
        "batch": [2, 16], "save_mesh": [1, 1], "restore_mesh": [1, 1],
        "setup": ["take", "drop", "restore"],
        "window": {"loop": ["restore"], "max_loops": 2},
        "check": {"loops": 1, "below": 2}, "answers_checked_least": 2,
        "read_back": False, "counts_as_attempt": "restore",
        "end_to_end": {"resume_s": {"kind": "window_per_op", "op": "restore"}},
    }
    with open(os.path.join(base, "traffic", "dummy_mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(base, "metrics", "dummy.templates.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.count('template')) or None\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "dummy-d1", "source": "https://example.org/dummy",
        "file": "chipbench/configs/dummy-d1.json", "reduced": ["num_hidden_layers"],
        "why": "a dummy"})
    spec["workloads"].append({
        "name": "dummy-d1.dummy_mix", "config": "dummy-d1", "traffic": "dummy_mix",
        "chips": 1, "why": "a dummy"})
    for m in spec["end_to_end"]:
        if m["name"] == "resume_s":
            m["workloads"].append("dummy-d1.dummy_mix")
    spec["per_layer"].append({
        "name": "dummy.templates", "unit": "count", "better": "lower",
        "source": "host_clock", "layer": "a dummy", "moves": "resume_s",
        "workloads": ["dummy-d1.dummy_mix"]})
    with open(path, "w") as f:
        json.dump(spec, f)

    plain = run_tiny("dummy-d1.dummy_mix")
    assert plain["correct"] and plain["attempted"] == 2
    assert set(plain["metrics"]) == {"resume_s", "setup_s"}
    traced = run_tiny("dummy-d1.dummy_mix", trace=True)
    assert traced["metrics"] == {"dummy.templates": {"value": 2.0, "unit": "count"}}
    for path, content in held.items():
        assert open(path, "rb").read() == content, path
