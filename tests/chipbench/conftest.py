"""A checkout in miniature for the harness's own tests: the benchmark's
files as committed, with every configuration cut to tiny widths by its own
state file's ``TINY`` and every mix to a tiny batch, so that a whole run fits
a CPU test.  A mix that checks timed restores counts its loops there
(``window.max_loops``), never seconds, so that ``correct`` cannot depend on
a loaded worker's clock.

Beside it, the benchmark at its committed sizes as a ``model_config`` PR
would leave it (``appended_spec``, ``appended_root``): a configuration, a
cell and a reader appended, and no file that was there changed.  The tests
of what BENCHMARK.json promises take the fixture ``checkout`` and run on
both, so that a test which pins where an entry stands fails the day it is
written."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

LOOPS = 3  # timed restores of a tiny window: all that a tiny mix draws its checks from


# a state that is no transformer: a flat tree of many small leaves, bfloat16
# and float32 side by side, with a step, batches and a TINY of its own
DUMMY_STATE = '''
import numpy as np

from chipbench.state import prng_key

TINY = {"rows": 8, "hidden_size": 16}


class FlatMixed:
    def __init__(self, conf, mesh):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        names = [f"leaf{i:04d}" for i in range(conf["leaves"])]
        shape = (conf["rows"], conf["hidden_size"])
        dtypes = {n: (jnp.bfloat16, jnp.float32)[i % 2] for i, n in enumerate(names)}
        self._whole = NamedSharding(mesh, P())
        self.shardings = {n: self._whole for n in names}

        def init(key):
            rows = jax.random.normal(key, (len(names), *shape))
            return {n: rows[i].astype(dtypes[n]) for i, n in enumerate(names)}

        def step(tree, batch):
            loss = jnp.mean(batch)
            return {n: x + (1 + loss).astype(x.dtype) for n, x in tree.items()}, loss

        self._init = jax.jit(init, out_shardings=self.shardings)
        self.step = jax.jit(step, donate_argnums=0)

    def make(self, seed):
        return self._init(prng_key(seed))

    def batch_pool(self, seed, batch, n):
        import jax

        pool = np.random.default_rng(seed).random((n, *batch["uniform"]), dtype=np.float32)
        return [jax.device_put(b, self._whole) for b in pool]


factory = FlatMixed
'''
DUMMY_LEAVES = 320
DUMMY_READER = "def read(ctx):\n    return float(ctx.count('template')) or None\n"
DUMMY_METRIC = {
    "name": "dummy.templates", "unit": "count", "better": "lower",
    "source": "host_clock", "layer": "a dummy", "moves": "resume_s",
}
# the cell a model_config PR would append: the dummy's configuration, at a
# width no committed configuration has, under a mix the benchmark has
APPENDED_CELL, APPENDED_MIX = "dummy-flat.kill_resume", "kill_resume"


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


def with_left_out(repo, spec):
    """``spec`` with the entries of the cells PERF.md leaves out added, as
    the later PR that brings such a cell back would add them."""
    with open(os.path.join(repo, "tests", "chipbench", "left_out_cells.json")) as f:
        left_out = json.load(f)
    spec = json.loads(json.dumps(spec))
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
def full_spec(repo, benchmark_json):
    return with_left_out(repo, benchmark_json)


@pytest.fixture(scope="session")
def appended_spec(benchmark_json):
    """BENCHMARK.json as a model_config PR would leave it: one configuration,
    one cell and one per-layer metric appended at the ends of their lists,
    and the cell's name at the end of every metric list of its mix."""
    spec = json.loads(json.dumps(benchmark_json))
    mixes = {w["name"]: w["traffic"] for w in spec["workloads"]}
    spec["configs"].append({
        "name": "dummy-flat", "source": "https://example.org/dummy-flat",
        "file": "chipbench/configs/dummy-flat.json", "reduced": [],
        "why": "a flat tree of 320 bfloat16 and float32 leaves",
    })
    spec["workloads"].append({
        "name": APPENDED_CELL, "config": "dummy-flat", "traffic": APPENDED_MIX,
        "chips": 1, "why": "a cell appended as a model_config PR appends one",
    })
    for m in spec["end_to_end"] + spec["per_layer"]:
        if any(mixes[w] == APPENDED_MIX for w in m.get("workloads", [])):
            m["workloads"].append(APPENDED_CELL)
    spec["per_layer"].append(dict(DUMMY_METRIC, workloads=[APPENDED_CELL]))
    return spec


@pytest.fixture(scope="session")
def appended_root(tmp_path_factory, repo, appended_spec):
    """A checkout of the benchmark at its committed sizes with the files of
    ``appended_spec``'s new entries added and no other file changed."""
    root = str(tmp_path_factory.mktemp("appended"))
    shutil.copytree(
        os.path.join(repo, "chipbench"), os.path.join(root, "chipbench"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    files = {
        "BENCHMARK.json": json.dumps(appended_spec),
        "chipbench/states/flat_mixed.py": DUMMY_STATE,
        "chipbench/configs/dummy-flat.json": json.dumps({
            "state": "flat_mixed", "leaves": DUMMY_LEAVES, "rows": 64, "hidden_size": 1024,
        }),
        "chipbench/metrics/dummy.templates.py": DUMMY_READER,
    }
    for name, text in files.items():
        with open(os.path.join(root, name), "w") as f:
            f.write(text)
    return root


@pytest.fixture(params=["committed", "appended"])
def checkout(request, repo):
    """The benchmark's files as committed, and with a cell appended: a test
    of what BENCHMARK.json promises holds on both, so that it checks
    membership and never where an entry stands."""
    return repo if request.param == "committed" else request.getfixturevalue("appended_root")


@pytest.fixture
def checkout_spec(checkout):
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        return json.load(f)


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
