"""The seam through which a configuration's state comes in: a configuration
file names ``states/<name>.py`` under ``"state"``, found as a mix or a reader
is; there is no default.  And the first such file, ``dense_lm``: the state a
seed makes is bit for bit what the harness made before the state was a file
(``dense_lm_golden.json``: the digests taken at commit ba5fe72, the parent of
the move, by ``state.StateFactory`` as it stood there)."""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from chipbench import bench, state

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "dense_lm_golden.json")
SEEDS = [2**31 + 7, 5]
INTERFACE = ("mesh", "shardings", "make", "step", "batch_pool")


@pytest.fixture(scope="module")
def golden():
    return state.load_json(GOLDEN)


@pytest.fixture(scope="module")
def tiny_conf(repo, golden, dense_lm):
    assert golden["tiny"] == dense_lm.TINY
    conf = state.load_json(os.path.join(repo, golden["config"]))
    conf.update(dense_lm.TINY)
    return conf


# ------------------------------------------------ same seed, same bytes


@pytest.mark.parametrize("mesh", [(1, 1), (2, 2)], ids=lambda m: "x".join(map(str, m)))
@pytest.mark.parametrize("seed", SEEDS)
def test_dense_lm_makes_the_state_the_parent_made(golden, tiny_conf, dense_lm, seed, mesh):
    want, = [
        g for g in golden["states"] if (g["seed"], tuple(g["mesh"])) == (seed, mesh)
    ]
    factory = dense_lm.factory(tiny_conf, state.build_mesh(jax.devices(), *mesh))
    tree = factory.make(seed)
    leaves = state.array_leaves(tree)
    assert [[list(x.shape), str(x.dtype)] for x in leaves] == want["leaves"]
    assert state.state_bytes(tree) == want["state_bytes"]
    assert np.array_equal(state.Digester()(tree), np.array(want["sums"], dtype=np.uint32))
    # born with its shardings: nothing is moved after the jitted call
    for x, sharding in zip(leaves, jax.tree_util.tree_leaves(factory.shardings)):
        assert x.sharding.is_equivalent_to(sharding, x.ndim)
    if mesh == (2, 2):
        assert sum(len({str(s.index) for s in x.addressable_shards}) > 1 for x in leaves) > 10


@pytest.mark.parametrize("seed", SEEDS)
def test_dense_lm_draws_the_batches_the_parent_drew(golden, tiny_conf, dense_lm, seed):
    want, = [g for g in golden["batches"] if g["seed"] == seed]
    factory = dense_lm.factory(tiny_conf, state.build_mesh(jax.devices(), 1, 1))
    # the mix's "batch" is handed through as it is: a list, as JSON gives it
    pool = factory.batch_pool(seed, want["batch"], want["n"])
    assert len(pool) == want["n"] and all(isinstance(b, jax.Array) for b in pool)
    rows = np.stack([np.asarray(b) for b in pool])
    assert str(rows.dtype) == want["dtype"] and rows.shape[1:] == tuple(want["batch"])
    rows = rows.astype(np.int64).reshape(-1)
    assert int(rows.sum()) == want["sum"]
    assert int((rows * np.arange(1, rows.size + 1)).sum()) == want["weighted"]
    assert len({r.tobytes() for b in pool for r in np.asarray(b)}) == want["n"] * want["batch"][0]


def test_dense_lm_steps_a_donated_state_to_a_finite_loss(tiny_conf, dense_lm):
    factory = dense_lm.factory(tiny_conf, state.build_mesh(jax.devices(), 2, 2))
    for name in INTERFACE:
        assert hasattr(factory, name), name
    digest = state.Digester()
    tree = factory.make(SEEDS[0])
    before, handed = digest(tree), state.array_leaves(tree)
    batch, = factory.batch_pool(SEEDS[0], [2, 16], 1)
    with factory.mesh:
        tree, loss = factory.step(tree, batch)
    assert np.ndim(loss) == 0 and np.isfinite(float(loss))
    # argument 0 is donated (a backend may decline a leaf it cannot reuse)
    assert sum(x.is_deleted() for x in handed) > len(handed) / 2
    after = digest(tree)
    assert after.shape == before.shape and np.any(after != before, axis=1).sum() > len(before) / 2
    # the same leaves; where each sits after a step is the step's to say
    assert [l[:2] for l in state.layout_of(tree)] == [
        l[:2] for l in state.layout_of(factory.make(SEEDS[0]))
    ]


# ------------------------------------------------------ how it is found


CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(GOLDEN))), "chipbench", "configs")
# the configurations there were when the state became a file: each names the
# dense transformer.  One that a later PR adds names whichever state it likes
DENSE = {
    "ouro-2.6b-4chip.json", "ouro-2.6b-d3.json", "ouro-2.6b-d32.json",
    "ouro-2.6b-d4.json", "ouro-2.6b-d9.json",
}


@pytest.mark.parametrize("name", sorted(set(os.listdir(CONFIGS)) | DENSE))
def test_a_committed_configuration_names_a_state_that_is_found(repo, benchmark_json, name):
    conf = state.load_json(os.path.join(CONFIGS, name))
    if name in DENSE:
        assert conf["state"] == "dense_lm"
        assert "chipbench/states/dense_lm.py" in conf["assumed"]["model"]
    path = bench.state_file(repo, benchmark_json["paths"], conf)
    assert os.path.basename(path) == conf["state"] + ".py"
    assert os.path.basename(os.path.dirname(path)) == "states"
    # held against its own state file, whichever that is: the interface's
    # two names, and a TINY that cuts keys the configuration has
    module = bench.load_state(repo, benchmark_json["paths"], conf)
    assert callable(module.factory)
    assert module.TINY and set(module.TINY) <= set(conf)
    for cell in benchmark_json["workloads"]:
        config, = [c for c in benchmark_json["configs"] if c["name"] == cell["config"]]
        if os.path.basename(config["file"]) == name:
            assert bench.Cell(repo, cell["name"]).state.__file__ == path


@pytest.mark.parametrize("module", ["bench.py", "state.py"])
def test_the_harness_imports_no_model_of_the_program(repo, module):
    with open(os.path.join(repo, "chipbench", module)) as f:
        source = f.read()
    assert "torchsnapshot_tpu.models" not in source
    assert "torchsnapshot_tpu.parallel" not in source
    for gone in ("StateFactory", "model_config", "token_pool", "_MODEL_KEYS"):
        assert not hasattr(state, gone), gone


@pytest.mark.parametrize("names", ["none", "a_missing_file"])
def test_a_configuration_without_a_state_file_is_an_error(
    tiny_root, tmp_path_factory, tmp_path, names
):
    """There is no default: the run ends before it makes its sink, so before
    any snapshot is written, and the message lists ``paths``."""
    root = str(tmp_path_factory.mktemp("stateless") / "checkout")
    shutil.copytree(tiny_root, root)
    spec = state.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = spec["workloads"][0]
    config, = [c for c in spec["configs"] if c["name"] == cell["config"]]
    path = os.path.join(root, config["file"])
    conf = state.load_json(path)
    if names == "none":
        del conf["state"]
        error, said = KeyError, 'names no state: its key "state"'
    else:
        conf["state"] = "no_such_state"
        error, said = FileNotFoundError, "no states/no_such_state.py under"
    with open(path, "w") as f:
        json.dump(conf, f)
    with pytest.raises(error) as raised:
        bench.run_cell(root, cell["name"], seed=9, seconds=0.01, trace=False, allow_cpu=True)
    message = raised.value.args[-1]  # (errno, message) or (message,)
    assert said in message and str(spec["paths"]) in message
    assert os.listdir(tmp_path) == []  # TMPDIR (``no_mount``): no sink was made
