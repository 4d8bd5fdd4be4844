"""A checkout in miniature for the harness's own tests: the benchmark's
files as committed, with every configuration cut to tiny widths and every
mix to a tiny batch, so that a whole run fits a CPU test."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=128, vocab_size=256, num_hidden_layers=2,
    max_position_embeddings=64,
)


def _rewrite(path, change):
    with open(path) as f:
        data = json.load(f)
    change(data)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture(autouse=True)
def no_mount(monkeypatch, tmp_path):
    """The tests share their process, so no run in it mounts a tmpfs of its
    own: a RAM sink is its plain directory under ``TMPDIR``, and this lists
    where a mount was asked for (``test_a_tmpfs_of_the_runs_own_goes_with_the_run``
    makes the real one, in a process of its own)."""
    import tempfile

    from chipbench import bench

    mounted = []
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(bench, "_mount_own_tmpfs", mounted.append)
    return mounted


@pytest.fixture(scope="session")
def repo():
    return REPO


@pytest.fixture(scope="session")
def benchmark_json(repo):
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def full_spec(repo, benchmark_json):
    """BENCHMARK.json with the entries of the cells it leaves out added, as
    the later PR that brings such a cell back would add them."""
    with open(os.path.join(repo, "tests", "chipbench", "left_out_cells.json")) as f:
        left_out = json.load(f)
    spec = json.loads(json.dumps(benchmark_json))
    for table in ("configs", "workloads"):
        spec[table] += left_out[table]
    for table in ("end_to_end", "per_layer"):
        have = {m["name"]: m for m in spec[table]}
        for m in left_out[table]:
            if m["name"] in have:
                have[m["name"]]["workloads"] += m["workloads"]
            else:
                spec[table].append(m)
    return spec


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, repo, full_spec):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(repo, "chipbench"), os.path.join(root, "chipbench"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(full_spec, f)
    for config in full_spec["configs"]:
        _rewrite(os.path.join(root, config["file"]), lambda c: c.update(TINY))
    traffic = os.path.join(root, "chipbench", "traffic")
    def shrink(mix):
        # a tiny window holds a handful of restores: sample among the first
        mix["batch"] = [2, 16]
        if "check" in mix:
            mix["check"] = {"loops": 2, "below": 3}
            mix["answers_checked_least"] = 3

    for name in os.listdir(traffic):
        _rewrite(os.path.join(traffic, name), shrink)
    return root


@pytest.fixture(scope="module")
def run_tiny(tiny_root):
    from chipbench import bench

    def run(workload, trace=False, fault=None, seed=2**31 + 7, seconds=0.4):
        return bench.run_cell(
            tiny_root, workload, seed=seed, seconds=seconds, trace=trace,
            allow_cpu=True, fault=fault,
        )

    return run
