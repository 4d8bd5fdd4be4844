"""A checkout in miniature for the harness's own tests: the benchmark's
files as committed, with every configuration cut to tiny widths by its own
state file's ``TINY`` and every mix to a tiny batch, so that a whole run fits
a CPU test.  A mix that checks timed restores counts its loops there
(``window.max_loops``), never seconds, so that ``correct`` cannot depend on
a loaded worker's clock."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

LOOPS = 3  # timed restores of a tiny window: all that a tiny mix draws its checks from


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


@pytest.fixture(scope="session")
def dense_lm(repo, benchmark_json):
    from chipbench import bench

    return bench.load_state(repo, benchmark_json["paths"], {"state": "dense_lm"})


def cut_to_tiny(root, paths, file):
    """Cuts one configuration file of a checkout by its own state file's ``TINY``."""
    from chipbench import bench

    _rewrite(
        os.path.join(root, file),
        lambda c: c.update(bench.load_state(root, paths, c).TINY),
    )


def shrink_mix(path):
    """Cuts one mix of a checkout to a tiny window: LOOPS restores, whatever
    the clock says, with its checks sampled among them.  ``batch`` is the
    state's to read, so only the token shape of the committed mixes, two
    whole numbers, is made small; a batch said another way stays as it is."""

    def shrink(mix):
        if isinstance(mix["batch"], list) and len(mix["batch"]) == 2:
            mix["batch"] = [2, 16]
        if "check" in mix:
            mix["check"] = {"loops": 2, "below": LOOPS}
            mix["answers_checked_least"] = 3
            mix["window"]["max_loops"] = LOOPS

    _rewrite(path, shrink)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, repo, full_spec):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(repo, "chipbench"), os.path.join(root, "chipbench"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(full_spec, f)
    for config in full_spec["configs"]:
        cut_to_tiny(root, full_spec["paths"], config["file"])
    traffic = os.path.join(root, "chipbench", "traffic")
    for name in os.listdir(traffic):
        shrink_mix(os.path.join(traffic, name))
    return root


@pytest.fixture(scope="module")
def run_tiny(tiny_root):
    from chipbench import bench

    def run(workload, trace=False, fault=None, seed=2**31 + 7, seconds=None):
        if seconds is None:
            # an hour where the loop's cap ends the window, not the clock
            capped = "max_loops" in bench.Cell(tiny_root, workload).traffic["window"]
            seconds = 3600.0 if capped else 0.4
        return bench.run_cell(
            tiny_root, workload, seed=seed, seconds=seconds, trace=trace,
            allow_cpu=True, fault=fault,
        )

    return run
