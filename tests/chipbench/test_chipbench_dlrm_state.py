"""The state one row-wise rank of a recommender's embedding tables holds
(``chipbench/states/dlrm_rowwise.py``) against its plain reference
(``chipbench/reference/dlrm_rowwise_state.py``), at tiny widths on the CPU:
the leaves, the cut's arithmetic, the 16 ranks' shares that add up to the
uncut table, the train step, a chunked leaf of a committed snapshot read
back by plain file reads, the program's counters and spans of the chunked
path, and whole runs of the configuration's two cells in the miniature
checkout with the chunk size set below the tiny tables'."""

import math
import os

import jax
import numpy as np
import pytest
from conftest import LOOPS

from chipbench import bench, state
from chipbench.reference import dlrm_rowwise_state as ref

CONFIG = "chipbench/configs/dlrm-criteo-rw16.json"
NAME = "dlrm-criteo-rw16"
SAVE, RESUME = NAME + ".preempt_sync_save", NAME + ".kill_resume_first3"
SEEDS = [2**31 + 7, 5]
# the issue's arithmetic for the committed cut
ROWS, STATE_BYTES, LEAVES = 12_761_547, 6_603_909_432, 85
DENSE_PARAMETERS, BIG_LEAF_BYTES = 2_368_897, 1_280_000_000
CHUNK_LIMIT = 512 * 1024 * 1024  # the program's MAX_CHUNK_SIZE_BYTES
# the public recipe's widths: what a cut may not touch
PUBLISHED = dict(
    embedding_dim=128, dense_in_features=13, dense_arch_layer_sizes=[512, 256, 128],
    over_arch_layer_sizes=[1024, 1024, 512, 256, 1],
)
# a tiny job's whole tables, of which ceil(n / 16) are TINY's rows
TINY_WHOLE = [630, 40, 1, 262, 640, 77]
# under these two the tiny tables of 40 rows (1,280 B) are written in three
# chunks of 16, 16 and 8 rows (the table of 17 rows and each MLP's first
# weight are over 512 B too), and a chunk, as on the chip, is over the slab
# threshold: an object of its own.  All but the one-row tail of the 17-row
# table (32 B): it joins a slab, as the tail of a leaf just over the limit
# would, and is moved and counted by the slab's pack, not as a chunk
TINY_CHUNK, TINY_SLAB = 512, 200


def over_the_limit(c, limit):
    """The leaves written in chunks, by the reference's arithmetic: name →
    (bytes, row ranges)."""
    return {
        name: (ref.leaf_nbytes(shape, dtype), ref.chunk_rows(shape, dtype, limit))
        for name, shape, dtype in ref.tree_spec(c)
        if ref.leaf_nbytes(shape, dtype) > limit
    }


@pytest.fixture(scope="module")
def conf(repo):
    return state.load_json(os.path.join(repo, CONFIG))


@pytest.fixture(scope="module")
def module(repo, benchmark_json, conf):
    return bench.load_state(repo, benchmark_json["paths"], conf)


@pytest.fixture(scope="module")
def tiny(conf, module):
    return dict(conf, **module.TINY)


@pytest.fixture(scope="module")
def factory(module, tiny):
    return module.factory(tiny, state.build_mesh(jax.devices(), 1, 1))


@pytest.fixture(scope="module")
def tiny_chunked(tiny):
    found = over_the_limit(tiny, TINY_CHUNK)
    assert {"tables/t00", "tables/t03", "tables/t04"} <= set(found)
    assert found["tables/t00"] == (1280, [(0, 16), (16, 32), (32, 40)])
    assert found["tables/t03"] == (544, [(0, 16), (16, 17)])
    return found


def staged_alone(chunked):
    """(rows, bytes) of the chunks that are objects of their own."""
    sizes = [
        (hi - lo, n // rows[-1][1] * (hi - lo))  # the last range ends at the leaf's rows
        for n, rows in chunked.values() for lo, hi in rows
    ]
    return [(r, n) for r, n in sizes if n >= TINY_SLAB]


@pytest.fixture
def small_chunks():
    from torchsnapshot_tpu import knobs

    with knobs.override_max_chunk_size_bytes(TINY_CHUNK), \
            knobs.override_slab_size_threshold_bytes(TINY_SLAB):
        yield


def named(tree):
    """A state's array leaves as path → numpy array."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(p.key for p in path): np.array(x) for path, x in flat}


def as_reference_state(tree):
    return {
        "step": int(tree["step"]),
        **{g: named(tree[g]) for g in ("tables", "table_acc", "dense", "dense_acc")},
    }


# ------------------------------------------------- (a) the leaves, the bytes


@pytest.mark.parametrize("size", ["committed", "tiny"])
def test_the_state_file_makes_the_leaves_the_reference_names(conf, tiny, module, size):
    c = conf if size == "committed" else tiny
    made = module.factory(c, state.build_mesh(jax.devices(), 1, 1))
    for name in ("mesh", "shardings", "make", "step", "batch_pool"):
        assert hasattr(made, name), name
    abstract = jax.eval_shape(made.make, 0)
    got = {
        "/".join(p.key for p in path): (tuple(x.shape), str(x.dtype))
        for path, x in jax.tree_util.tree_flatten_with_path(abstract)[0]
    }
    spec = ref.tree_spec(c)
    assert got == {name: (shape, dtype) for name, shape, dtype in spec}
    assert len(got) == len(spec)
    assert set(abstract) == {"step", "tables", "table_acc", "dense", "dense_acc"}
    assert {dtype for name, (_, dtype) in got.items() if name != "step"} == {"float32"}
    assert got["step"] == ((), "int32")
    assert jax.tree_util.tree_structure(made.shardings) == jax.tree_util.tree_structure(abstract)
    assert all(s.is_fully_replicated for s in jax.tree_util.tree_leaves(made.shardings))


def test_the_committed_cut_is_the_issues_arithmetic(conf):
    spec = ref.tree_spec(conf)
    assert len(spec) == LEAVES
    assert sum(conf["num_embeddings_per_feature"]) == ROWS
    assert ref.spec_bytes(spec) == STATE_BYTES
    dense = sum(math.prod(shape) for _, shape in ref.dense_spec(conf))
    assert dense == DENSE_PARAMETERS
    assert STATE_BYTES == ROWS * 128 * 4 + ROWS * 4 + 2 * 4 * dense + 4
    # 479 = 128 + 27 * 26 / 2: the bottom MLP's output and the pairs above the diagonal
    assert ref.mlp_widths(conf) == ([13, 512, 256, 128], [479, 1024, 1024, 512, 256, 1])
    sizes = {name: ref.leaf_nbytes(shape, dtype) for name, shape, dtype in spec}
    big = over_the_limit(conf, CHUNK_LIMIT)
    assert sorted(big) == ["tables/t00", "tables/t09", "tables/t19", "tables/t20", "tables/t21"]
    assert 0.9690 < len(big) * BIG_LEAF_BYTES / STATE_BYTES < 0.9692
    # each in three chunks: 512, 512 and 196.7 MiB, 15 objects in all
    three = [(0, 1_048_576), (1_048_576, 2_097_152), (2_097_152, 2_500_000)]
    assert all(found == (BIG_LEAF_BYTES, three) for found in big.values())
    # the other 80 leaves: 203.9 MB, the largest 98.2 MB, under the slab threshold
    rest = [n for name, n in sizes.items() if name not in big]
    assert len(rest) == 80 and sum(rest) == 203_909_432 and max(rest) == 98_174_976
    assert max(rest) < 128 * 1024 * 1024


def test_the_configuration_keeps_the_published_widths(checkout_spec, conf, module):
    for key, value in PUBLISHED.items():
        assert conf[key] == value, key
    entry, = [c for c in checkout_spec["configs"] if c["name"] == NAME]
    assert entry["file"] == CONFIG and entry["source"] == conf["source"]
    assert len(conf["source"]) <= 200
    assert entry["reduced"] == conf["reduced"] == ["num_embeddings_per_feature"]
    assert set(conf["published"]) == {"num_embeddings_per_feature"}
    whole = conf["published"]["num_embeddings_per_feature"]
    assert len(whole) == 26 and sum(whole) == 204_184_588
    assert conf["table_row_parallel_size"] == 16
    assert conf["num_embeddings_per_feature"] == ref.held_rows(whole, 16)
    for key in ("assumed", "deployment", "storage", "guarantees"):
        assert conf[key]
    assert {"optimizer", "step", "batch", "init", "rows_held"} <= set(conf["assumed"])
    assert "row-wise over the 16 chips" in conf["deployment"]
    assert module.TINY["num_embeddings_per_feature"] == ref.held_rows(TINY_WHOLE, 16)
    assert 1 in module.TINY["num_embeddings_per_feature"]  # a one-row table stays


def test_the_cells_and_the_mix_are_the_ones_the_issue_names(checkout, checkout_spec):
    cells = {w["name"]: w for w in checkout_spec["workloads"]}
    assert [n for n in cells if n.startswith(NAME)] == [RESUME, SAVE]
    assert cells[RESUME]["chips"] == cells[SAVE]["chips"] == 1
    # side by side, in that order, wherever later cells put them
    at = [w["name"] for w in checkout_spec["workloads"]].index(RESUME)
    assert checkout_spec["workloads"][at + 1] is cells[SAVE]
    traffic = os.path.join(checkout, "chipbench", "traffic")
    mix = state.load_json(os.path.join(traffic, "kill_resume_first3.json"))
    old = state.load_json(os.path.join(traffic, "kill_resume.json"))
    differs = {k for k in set(mix) | set(old) if mix.get(k) != old.get(k)}
    assert differs == {"check", "answers_checked_least", "doc", "source"}
    elastic = state.load_json(os.path.join(traffic, "elastic_resume.json"))
    assert mix["check"] == elastic["check"] == {"loops": 2, "below": 3}
    assert mix["answers_checked_least"] == elastic["answers_checked_least"] == 3
    assert "sink" not in mix
    # the chunked path's readers: the six the cells came with, less chunk.assemble_s, which
    # the device path left with nothing to read, and its two of its own
    new = {
        "chunked.bytes_share.restore": RESUME, "chunked.host_alloc_x": RESUME,
        "chunk.put_s": RESUME, "chunked.bytes_share.save": SAVE, "chunk.slice_s": SAVE,
    }
    device_path = {"chunk.join_s": RESUME, "chunked.device_put_x": RESUME}
    names = [m["name"] for m in checkout_spec["per_layer"]]
    listed = {m["name"]: m for m in checkout_spec["per_layer"]}
    assert "chunk.assemble_s" not in listed
    for group in (new, device_path):  # each group's entries one after another
        at = sorted(names.index(name) for name in group)
        assert at == list(range(at[0], at[0] + len(group)))
    for name, cell in {**new, **device_path}.items():
        assert cell in listed[name]["workloads"]
        assert listed[name]["moves"] == ("resume_s" if cell == RESUME else "save_commit_s")
    for cell in (RESUME, SAVE):
        found = bench.Cell(checkout, cell)
        reported = {m["name"] for m in found.end_to_end_metrics()}
        assert reported == {"setup_s", "resume_s" if cell == RESUME else "save_commit_s"}
        assert all(callable(found.reader(m["name"])) for m in found.per_layer_metrics())


def test_the_state_file_and_the_reference_import_neither_each_other_nor_the_program(repo):
    with open(os.path.join(repo, "chipbench/states/dlrm_rowwise.py")) as f:
        state_source = f.read().split('"""', 2)[2]
    with open(os.path.join(repo, "chipbench/reference/dlrm_rowwise_state.py")) as f:
        reference_source = f.read().split('"""', 2)[2]
    for gone in ("torchsnapshot_tpu", "chipbench.reference", "import optax", "import flax"):
        assert gone not in state_source
    for line in reference_source.splitlines():
        if line.startswith(("import ", "from ")):
            assert line.split()[1].split(".")[0] in ("__future__", "json", "os", "typing", "numpy"), line


# -------------------------------------------------- (b) the shares add up


@pytest.mark.parametrize("size", ["committed", "tiny"])
def test_the_sixteen_ranks_shares_of_every_table_are_the_uncut_table(conf, module, size):
    ranks = conf["table_row_parallel_size"]
    whole = conf["published"]["num_embeddings_per_feature"] if size == "committed" else TINY_WHOLE
    held = conf["num_embeddings_per_feature"] if size == "committed" else module.TINY["num_embeddings_per_feature"]
    for n, here in zip(whole, held):
        shares = [ref.rank_rows(n, ranks, r) for r in range(ranks)]
        # the shares tile the table: each starts where the last ended
        assert shares[0][0] == 0 and shares[-1][1] == n
        assert [lo for lo, _ in shares[1:]] == [hi for _, hi in shares[:-1]]
        assert shares[0][1] - shares[0][0] == here == max(hi - lo for lo, hi in shares)
        if n < ranks:  # a table of fewer rows than ranks: one row each, then none
            assert [hi - lo for lo, hi in shares] == [1] * n + [0] * (ranks - n)
    with pytest.raises(ValueError):
        ref.rank_rows(10, ranks, ranks)
    # and the bytes: the shares' rows of a tiny table, concatenated, are the table
    table = np.random.default_rng(3).standard_normal((TINY_WHOLE[0], 8)).astype(np.float32)
    parts = [table[slice(*ref.rank_rows(len(table), ranks, r))] for r in range(ranks)]
    assert np.concatenate(parts).tobytes() == table.tobytes()
    one = np.ones((1, 8), np.float32)
    parts = [one[slice(*ref.rank_rows(1, ranks, r))] for r in range(ranks)]
    assert [len(p) for p in parts] == [1] + [0] * (ranks - 1)


# ------------------------------------------------------------ (c) the step

# One step from the same state: the two differ by float32 rounding alone (the
# order of a matrix product's sums, exp and log), which reads 1e-7 of a
# leaf's largest value in the weights and rows and 4e-7 in the accumulators
# here; ten times that is allowed.  An accumulator held in bfloat16 is off by
# 2**-9 = 2e-3, and an untouched row that moved by one update by lr / the
# row's size, over 1e-2.
TOLERANCE = {"tables": 1e-6, "dense": 1e-6, "table_acc": 4e-6, "dense_acc": 4e-6}


def disagreement(got, want):
    """Per group, the largest |difference| ÷ the leaf's largest |value|."""
    return {
        group: max(
            float(np.max(np.abs(got[group][k] - want[group][k])) / max(np.abs(want[group][k]).max(), 1e-30))
            for k in want[group]
        )
        for group in TOLERANCE
    }


def bf16(x):
    return (x.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)


@pytest.mark.parametrize("seed", SEEDS)
def test_three_steps_agree_with_the_reference(tiny, factory, seed):
    tree = factory.make(seed)
    handed = state.array_leaves(tree)
    digest = state.Digester()
    rows = tiny["num_embeddings_per_feature"]
    for batch in factory.batch_pool(seed, [2, 16], 3):
        host = {k: np.asarray(v) for k, v in batch.items()}
        assert host["ids"].shape == (32, len(rows)) and host["dense"].shape == (32, 13)
        assert all(host["ids"][:, f].max() < n for f, n in enumerate(rows))
        assert set(np.unique(host["labels"])) <= {0.0, 1.0}
        before, was = as_reference_state(tree), digest(tree)
        with factory.mesh:
            tree, loss = factory.step(tree, batch)
        want, want_loss = ref.dlrm_step(tiny, before, host)
        assert np.ndim(loss) == 0 and np.isfinite(float(loss))
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
        got = as_reference_state(tree)
        assert got["step"] == want["step"] == before["step"] + 1
        found = disagreement(got, want)
        assert all(found[g] <= TOLERANCE[g] for g in TOLERANCE), found
        for f, name in enumerate(sorted(got["tables"])):
            untouched = np.setdiff1d(np.arange(rows[f]), host["ids"][:, f])
            # a row no sample names keeps its bytes, and its accumulator
            assert got["tables"][name][untouched].tobytes() == before["tables"][name][untouched].tobytes()
            assert got["table_acc"][name][untouched].tobytes() == before["table_acc"][name][untouched].tobytes()
            touched = np.unique(host["ids"][:, f])
            assert np.all(got["table_acc"][name][touched] >= before["table_acc"][name][touched])
        # the tolerances tell: a step that also updates an untouched row
        # (here: the reference's update of row 0 applied to the row after it)
        wrong = {g: dict(v) for g, v in want.items() if g != "step"}
        moved = wrong["tables"]["t00"].copy()
        spare = np.setdiff1d(np.arange(rows[0]), host["ids"][:, 0])[0]
        some = np.unique(host["ids"][:, 0])[0]
        moved[spare] += want["tables"]["t00"][some] - before["tables"]["t00"][some]
        wrong["tables"]["t00"] = moved
        assert disagreement(got, wrong)["tables"] > 100 * TOLERANCE["tables"]
        # ... and Adagrad's accumulators held in bfloat16
        lossy = dict(wrong, tables=want["tables"], **{
            g: {k: bf16(v) for k, v in want[g].items()} for g in ("table_acc", "dense_acc")
        })
        off = disagreement(got, lossy)
        assert off["table_acc"] > 100 * TOLERANCE["table_acc"], off
        assert off["dense_acc"] > 100 * TOLERANCE["dense_acc"], off
        # every leaf changes with every step: a snapshot a step late shows in all
        assert np.all(np.any(digest(tree) != was, axis=1))
    assert sum(x.is_deleted() for x in handed) > len(handed) / 2  # argument 0 is donated


def test_samples_that_share_a_row_update_it_once_with_their_summed_gradient(tiny, factory):
    # 32 samples over a table of one row and one of three: every row is shared
    tree = factory.make(5)
    batch, = factory.batch_pool(5, [2, 16], 1)
    ids = np.asarray(batch["ids"])
    assert len(np.unique(ids[:, 2])) == 1 and len(np.unique(ids[:, 1])) <= 3
    before = as_reference_state(tree)
    with factory.mesh:
        tree, _ = factory.step(tree, batch)
    want, _ = ref.dlrm_step(tiny, before, {k: np.asarray(v) for k, v in batch.items()})
    got = as_reference_state(tree)
    for name in ("t01", "t02"):
        assert np.allclose(got["tables"][name], want["tables"][name], rtol=0, atol=1e-6 * np.abs(want["tables"][name]).max())
        assert np.allclose(got["table_acc"][name], want["table_acc"][name], rtol=4e-6, atol=0)


# ------------------------------------ (d) a snapshot's bytes, read plainly


def take(tree, path, step=2):
    from torchsnapshot_tpu import PyTreeState, Snapshot, StateDict

    Snapshot.take(path, {"ts": PyTreeState(tree), "meta": StateDict(step=step)})


def restore(factory, path, seed):
    from torchsnapshot_tpu import PyTreeState, Snapshot, StateDict

    template = factory.make(seed)
    app = {"ts": PyTreeState(template), "meta": StateDict(step=-1)}
    want_layout = state.layout_of(template)
    del template
    Snapshot(path).restore(app)
    jax.block_until_ready(app["ts"].tree)
    return app, want_layout


def stepped(factory, seed):
    tree = factory.make(seed)
    for batch in factory.batch_pool(seed, [2, 16], 2):
        with factory.mesh:
            tree, _ = factory.step(tree, batch)
    return tree


@pytest.mark.parametrize("seed", SEEDS)
def test_a_chunked_take_read_back_by_plain_file_reads_is_the_leaves_bytes(
    tiny, tiny_chunked, factory, tmp_path, small_chunks, seed
):
    tree = stepped(factory, seed)
    path = str(tmp_path / "snap")
    take(tree, path)
    leaves = ref.leaf_bytes(path)
    want = named(tree)
    assert set(leaves) == set(want) == {name for name, _, _ in ref.tree_spec(tiny)}
    for name, x in want.items():
        assert leaves[name]["bytes"] == x.tobytes(), name
        assert leaves[name]["dtype"] == str(x.dtype) and leaves[name]["shape"] == x.shape
    chunked = {name: leaf["chunks"] for name, leaf in leaves.items() if leaf["chunks"]}
    assert set(chunked) == set(tiny_chunked)
    for name, chunks in chunked.items():
        nbytes, rows = tiny_chunked[name]
        assert [(lo, hi) for lo, hi, _, _ in chunks] == rows
        assert sum(n for _, _, _, n in chunks) == nbytes
        # a chunk is an object of its own, named by its rows
        assert len({location for _, _, location, _ in chunks}) == len(chunks)
        for lo, hi, location, n in chunks:
            if n >= TINY_SLAB:
                assert location.endswith(f"{name}_{lo}_{hi}")
                assert os.path.getsize(os.path.join(path, location)) == n
    # a restore into fresh templates returns every leaf, bit for bit
    app, want_layout = restore(factory, path, seed + 1)
    got = app["ts"].tree
    assert state.compare(state.Digester()(tree), state.Digester()(got), want_layout, state.layout_of(got)) == {
        "leaves_mismatched": 0, "leaves_misplaced": 0}
    assert app["meta"]["step"] == 2


def test_leaf_bytes_refuses_chunks_that_do_not_tile_the_leaf(factory, tmp_path, small_chunks):
    import json

    path = str(tmp_path / "snap")
    take(stepped(factory, 5), path)
    marker = os.path.join(path, ".snapshot_metadata")
    body = ref.read_manifest(path)
    entry = body["manifest"]["0/ts/tables/t00"]
    assert entry["type"] == "ChunkedArray" and len(entry["chunks"]) == 3
    dropped = entry["chunks"].pop(1)
    with open(marker, "w") as f:
        json.dump(body, f)
    with pytest.raises(ValueError, match="no whole range of rows after row 16"):
        ref.leaf_bytes(path)
    entry["chunks"].insert(1, dropped)
    entry["chunks"].pop()
    with open(marker, "w") as f:
        json.dump(body, f)
    with pytest.raises(ValueError, match="end at row 32 of 40"):
        ref.leaf_bytes(path)


# -------------------------------- (e) the chunked path's counters and spans

COUNTERS = (
    "chunked.write_bytes", "chunked.write_chunks", "chunked.read_bytes",
    "chunked.host_assembly_bytes",
)
SPANS = ("chunk/slice", "chunk/assemble", "chunk/put")


def counters():
    from torchsnapshot_tpu import obs

    return obs.metrics_snapshot()["counters"]


def gained(before):
    now = counters()
    return {name: now.get(name, 0) - before.get(name, 0) for name in COUNTERS + ("exceptions.swallowed",)}


@pytest.fixture
def traced():
    from torchsnapshot_tpu.obs import tracer

    tracer.set_tracing(True)
    tracer.get_tracer().reset()
    yield lambda: [s for s in tracer.get_tracer().spans() if s.name in SPANS]
    tracer.set_tracing(False)
    tracer.get_tracer().reset()


def test_the_counters_and_spans_rise_by_what_the_manifest_names(
    tiny_chunked, factory, tmp_path, small_chunks, traced
):
    # the restore half holds the host path, which the CPU's templates take:
    # chunks assembled in a whole-leaf buffer, one put a leaf.  The chip's
    # device path (a put a chunk, a join a leaf) is tests/test_chunked_device_restore.py's
    tree = stepped(factory, 5)
    path = str(tmp_path / "snap")
    before = counters()
    take(tree, path)
    manifest = ref.read_manifest(path)["manifest"]
    chunks = [
        (c["sizes"][0], 4 * math.prod(c["sizes"]))
        for entry in manifest.values() if entry["type"] == "ChunkedArray" for c in entry["chunks"]
    ]
    total = sum(n for n, _ in tiny_chunked.values())
    assert len(chunks) == sum(len(rows) for _, rows in tiny_chunked.values())
    assert sum(n for _, n in chunks) == total
    alone = staged_alone(tiny_chunked)
    assert sorted(alone) == sorted(c for c in chunks if c[1] >= TINY_SLAB) and len(alone) == len(chunks) - 1
    saved = gained(before)
    assert saved == {
        "chunked.write_bytes": sum(n for _, n in alone), "chunked.write_chunks": len(alone),
        "chunked.read_bytes": 0, "chunked.host_assembly_bytes": 0, "exceptions.swallowed": 0,
    }
    slices = [s for s in traced() if s.name == "chunk/slice"]
    assert sorted((s.attrs["rows"], s.attrs["bytes"]) for s in slices) == sorted(alone)
    assert all(s.thread_name.startswith("tsnp-staging") for s in slices)
    assert {s.name for s in traced()} == {"chunk/slice"}

    before = counters()
    app, _ = restore(factory, path, 6)
    read = gained(before)
    assert read == {
        "chunked.write_bytes": 0, "chunked.write_chunks": 0, "chunked.read_bytes": total,
        "chunked.host_assembly_bytes": total, "exceptions.swallowed": 0,
    }
    spans = traced()
    assembled = [s for s in spans if s.name == "chunk/assemble"]
    assert sorted(s.attrs["bytes"] for s in assembled) == sorted(n for _, n in chunks)
    # consume/materialize wraps chunk/assemble, on a consume worker
    from torchsnapshot_tpu.obs import tracer

    by_id = {s.span_id: s for s in tracer.get_tracer().spans()}
    assert {by_id[s.parent_id].name for s in assembled} == {"consume/materialize"}
    puts = [s for s in spans if s.name == "chunk/put"]
    assert sorted((s.attrs["bytes"], s.attrs["chunks"]) for s in puts) == sorted(
        (n, len(rows)) for n, rows in tiny_chunked.values()
    )
    # the whole array's put runs where the countdown is stepped: on the read loop
    assert {s.thread_name for s in puts} == {"tsnp-read-loop"}
    assert np.array_equal(state.Digester()(app["ts"].tree), state.Digester()(tree))


def test_a_numpy_template_of_the_dtype_is_assembled_in_place_and_counts_no_buffer(tmp_path, small_chunks):
    from torchsnapshot_tpu import Snapshot, StateDict

    table = np.random.default_rng(4).standard_normal((40, 8)).astype(np.float32)
    path = str(tmp_path / "snap")
    before = counters()
    Snapshot.take(path, {"s": StateDict(table=table)})
    assert gained(before)["chunked.write_bytes"] == table.nbytes  # a host leaf's chunks count too
    assert gained(before)["chunked.write_chunks"] == 3
    into = {"s": StateDict(table=np.zeros_like(table))}
    before = counters()
    Snapshot(path).restore(into)
    assert into["s"]["table"].tobytes() == table.tobytes()
    found = gained(before)
    assert found["chunked.read_bytes"] == table.nbytes
    assert found["chunked.host_assembly_bytes"] == 0


def test_a_state_under_the_limit_raises_none_of_them(factory, tmp_path, traced):
    tree = stepped(factory, 5)
    path = str(tmp_path / "snap")
    before = counters()
    take(tree, path)
    restore(factory, path, 6)
    assert set(gained(before).values()) == {0}
    assert traced() == []
    assert not any(leaf["chunks"] for leaf in ref.leaf_bytes(path).values())


READERS = {
    "chunked.bytes_share.restore": ("chunked.read_bytes", "restore"),
    "chunked.host_alloc_x": ("chunked.host_assembly_bytes", "restore"),
    "chunked.device_put_x": ("chunked.device_put_bytes", "restore"),
    "chunked.bytes_share.save": ("chunked.write_bytes", "take"),
}
SPAN_READERS = {
    "chunk.join_s": ("chunk/join", "restore"), "chunk.put_s": ("chunk/put", "restore"),
    "chunk.slice_s": ("chunk/slice", "take"),
}
# what no CPU run reports: it has no memory_stats, and its templates keep a
# chunked leaf on the host path, so no chunk is put alone and none joined
CHIP_ONLY = {"restore.hbm_peak_x", "chunk.join_s", "chunked.device_put_x"}


class _Span:
    def __init__(self, name, parent, start_s, seconds, **attrs):
        self.name, self.attrs, self.parent_id = name, attrs, parent
        self.start_ns, self.end_ns = int(start_s * 1e9), int((start_s + seconds) * 1e9)
        self.duration_ns = self.end_ns - self.start_ns


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_counter_readers_read_none_from_a_program_without_the_counter(repo, name):
    read = bench.Cell(repo, RESUME).reader(name)
    counter, op = READERS[name]
    timeline = [{"op": op, "t0": 0.0, "t1": 1.0}] * 2
    old = bench.Context(
        timeline=timeline, notes={"state_bytes": 1000}, spans=[],
        obs_before={"counters": {"bytes_read": 5}}, obs_after={"counters": {"bytes_read": 9}},
    )
    assert read(old) is None
    new = bench.Context(
        timeline=timeline, notes={"state_bytes": 1000}, spans=[],
        obs_before={"counters": {counter: 100}}, obs_after={"counters": {counter: 2038}},
    )
    assert read(new) == pytest.approx(0.969)
    idle = bench.Context(timeline=[], notes={"state_bytes": 1000}, spans=[],
                         obs_before=new.obs_before, obs_after=new.obs_after)
    assert read(idle) is None


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_the_span_readers_read_none_from_a_program_without_the_span(repo, name):
    read = bench.Cell(repo, RESUME).reader(name)
    span, op = SPAN_READERS[name]
    timeline = [{"op": op, "t0": 0.0, "t1": 1.0}, {"op": op, "t0": 1.0, "t1": 2.0}]
    # what the parent records of a restore and of a save: roots and pipelines
    root = "restore" if op == "restore" else "take"
    spans = [_Span(root, None, t, 0.9) for t in (0.01, 1.01)]
    spans += [_Span(root + "/pipeline", 1, t, 0.8, workers=4) for t in (0.02, 1.02)]
    old = bench.Context(timeline=timeline, notes={"state_bytes": 1000}, spans=spans)
    assert read(old) is None
    more = spans + [_Span(span, 2, t, 0.25, bytes=512) for t in (0.1, 0.4, 1.1)]
    new = bench.Context(timeline=timeline, notes={"state_bytes": 1000}, spans=more)
    assert read(new) == pytest.approx(0.375)  # three spans of 0.25 s over two operations
    assert read(bench.Context(timeline=[], notes={"state_bytes": 1000}, spans=more)) is None


# ------------------------------------ the two cells, whole, in the miniature

TINY_LEAVES = 6 + 6 + 2 * (2 * 2 + 2 * 3) + 1  # tables, accumulators, dense twice, step
TINY_BYTES = 4 * (sum([40, 3, 1, 17, 40, 5]) * (8 + 1) + 2 * (13 * 16 + 16 + 16 * 8 + 8 + 29 * 16 + 16 + 16 * 8 + 8 + 8 + 1)) + 4


@pytest.fixture(scope="module")
def tiny_share(tiny, tiny_chunked):
    assert ref.spec_bytes(ref.tree_spec(tiny)) == TINY_BYTES
    assert len(ref.tree_spec(tiny)) == TINY_LEAVES
    return {
        "restore": sum(n for n, _ in tiny_chunked.values()) / TINY_BYTES,
        "save": sum(n for _, n in staged_alone(tiny_chunked)) / TINY_BYTES,
    }


def _listed(spec, workload):
    return {m["name"] for m in spec["per_layer"] if workload in m["workloads"]}


@pytest.mark.parametrize("workload,metric", [(SAVE, "save_commit_s"), (RESUME, "resume_s")])
def test_a_plain_run_of_each_cell(run_tiny, small_chunks, workload, metric):
    result = run_tiny(workload)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {metric, "setup_s"}
    assert all(c["value"] <= c["limit"] for c in result["checks"].values())
    assert result["state_bytes"] == TINY_BYTES
    if workload == RESUME:
        assert result["attempted"] == LOOPS


def test_a_traced_run_of_the_save_cell_reports_every_listed_metric(
    benchmark_json, run_tiny, small_chunks, tiny_share
):
    result = run_tiny(SAVE, trace=True)
    assert result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == _listed(benchmark_json, SAVE)
    assert metrics["chunked.bytes_share.save"] == pytest.approx(tiny_share["save"])
    assert metrics["pack.host_bytes_share"] == 0.0
    assert metrics["chunk.slice_s"] > 0 and metrics["take.host_us_per_leaf"] > 0


def test_a_traced_run_of_the_resume_cell_reports_every_listed_metric(
    benchmark_json, run_tiny, small_chunks, tiny_share
):
    result = run_tiny(RESUME, trace=True)
    assert result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) - CHIP_ONLY == _listed(benchmark_json, RESUME) - CHIP_ONLY
    # the host path, as on the CPU: every chunked byte through a whole-leaf
    # host buffer, and one whole-leaf put a leaf (chunk.put_s).  On the chip
    # the device path puts them chunk by chunk, which chunk.join_s and
    # chunked.device_put_x read: their arithmetic is the two reader tests'
    # hand-built windows above
    assert metrics["chunked.bytes_share.restore"] == pytest.approx(tiny_share["restore"])
    assert metrics["chunked.host_alloc_x"] == metrics["chunked.bytes_share.restore"]
    assert metrics["chunk.put_s"] > 0
    assert "chunk.join_s" not in metrics
    # a counter another test of this process raised on the device path reads 0
    assert metrics.get("chunked.device_put_x", 0.0) == 0.0
    assert metrics["restore.host_us_per_leaf"] > 0
    # the caller-thread and consume span readers read the chunked restore too
    assert metrics["restore.pipeline_s"] > 0 and metrics["consume.work_s"] > 0


def test_a_traced_run_under_the_limit_leaves_the_new_metrics_out(benchmark_json, run_tiny):
    # the chunk size as committed: no tiny leaf is over it, as no leaf of the accepted cells is
    result = run_tiny(RESUME, trace=True)
    assert result["correct"] is True
    new = {
        "chunked.bytes_share.restore", "chunked.host_alloc_x", "chunked.device_put_x",
        "chunk.join_s", "chunk.put_s",
    }
    left = _listed(benchmark_json, RESUME) - {"restore.hbm_peak_x"} - set(result["metrics"])
    assert left <= new and {"chunk.join_s", "chunk.put_s"} <= left
    for name in new - left:  # a counter another test of this process raised reads 0
        assert result["metrics"][name]["value"] == 0.0


@pytest.mark.parametrize("workload,answers", [(SAVE, 1), (RESUME, 1 + LOOPS)])
def test_the_control_fails_on_the_float32_leaves_and_not_on_the_step(run_tiny, small_chunks, workload, answers):
    result = run_tiny(workload, fault="control_bf16")
    assert result["correct"] is False and result["failed"] == 1
    assert result["checks"]["leaves_mismatched"]["value"] == answers * (TINY_LEAVES - 1)
    assert result["checks"]["leaves_misplaced"]["value"] == 0


def test_a_snapshot_of_a_later_state_is_caught_in_the_save_cell(run_tiny, small_chunks):
    result = run_tiny(SAVE, fault="late_snapshot")
    assert result["correct"] is False
    assert result["checks"]["leaves_mismatched"]["value"] == TINY_LEAVES  # every leaf moves with a step
