"""The state one expert-parallel rank of a mixed-precision job holds
(``chipbench/states/moe_rank_mp.py``) against its plain reference
(``chipbench/reference/mixed_precision_state.py``), at tiny widths on the
CPU: the leaves, the shares that add up to the uncut tree, the optimizer
step, a committed snapshot's bytes read back by plain file reads, the
program's counters of the path each slab took, and whole runs of the
configuration's two cells in the miniature checkout."""

import math
import os

import jax
import numpy as np
import pytest
from conftest import LOOPS

from chipbench import bench, state, width_reads
from chipbench.reference import mixed_precision_state as ref

CONFIG = "chipbench/configs/joyai-flash-ep16-d5.json"
SAVE, RESUME = "joyai-flash-ep16-d5.preempt_sync_save", "joyai-flash-ep16-d5.kill_resume"
SEEDS = [2**31 + 7, 5]
# the issue's arithmetic for the committed cut, and the 4 bytes of the step
PARAMETERS, LEAVES = 564_954_112, 1_053
STATE_BYTES, NARROW_BYTES = 7_909_357_568 + 4, 1_129_908_224
# JoyAI-LLM-Flash's published config.json: what a cut may not touch
PUBLISHED = dict(
    hidden_size=2048, intermediate_size=7168, moe_intermediate_size=768,
    q_lora_rank=1536, kv_lora_rank=512, num_attention_heads=32,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, head_dim=64,
    n_shared_experts=1, num_experts_per_tok=8, first_k_dense_replace=1,
)


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


def named(group):
    """A group of the state's tree as name → numpy array."""
    flat, _ = jax.tree_util.tree_flatten_with_path(group)
    return {"/".join(p.key for p in path): np.array(x) for path, x in flat}


def as_reference_state(tree):
    return {
        "step": int(tree["step"]),
        "params": {k: v.view(np.uint16) for k, v in named(tree["params"]).items()},
        **{g: named(tree[g]) for g in ("master", "mu", "nu")},
    }


# ------------------------------------------------- (a) the leaves, the bytes


@pytest.mark.parametrize("size", ["committed", "tiny"])
def test_the_state_file_makes_the_leaves_the_reference_names(conf, tiny, module, size):
    c = conf if size == "committed" else tiny
    made = module.factory(c, state.build_mesh(jax.devices(), 1, 1))
    abstract = jax.eval_shape(made.make, 0)
    got = {
        "/".join(p.key for p in path): (tuple(x.shape), str(x.dtype))
        for path, x in jax.tree_util.tree_flatten_with_path(abstract)[0]
    }
    spec = ref.tree_spec(c)
    assert got == {name: (shape, dtype) for name, shape, dtype in spec}
    assert len(got) == len(spec)
    assert set(abstract) == {"step", "params", "master", "mu", "nu"}
    assert jax.tree_util.tree_structure(made.shardings) == jax.tree_util.tree_structure(abstract)
    assert all(s.is_fully_replicated for s in jax.tree_util.tree_leaves(made.shardings))


def test_the_committed_cut_is_the_issues_arithmetic(conf):
    spec = ref.tree_spec(conf)
    assert len(spec) == LEAVES
    assert ref.parameter_count(ref.param_spec(conf)) == PARAMETERS
    widths = ref.spec_bytes(spec)
    assert widths == {4: STATE_BYTES - NARROW_BYTES, 2: NARROW_BYTES}
    assert widths[2] * 7 == STATE_BYTES - 4  # a seventh of the parameters' bytes
    shapes = {name: shape for name, shape, _ in ref.param_spec(conf)}
    assert max(math.prod(s) for s in shapes.values()) * 4 == 132_382_720
    layer = [n for n in shapes if n.startswith("layers/01/")]
    assert len(layer) == 9 + 2 + 3 + 3 * 16
    assert shapes["layers/01/mlp/gate/weight"] == (2048, 256)  # the published width


def test_the_configuration_keeps_the_published_widths(repo, benchmark_json, conf, module):
    for key, value in PUBLISHED.items():
        assert conf[key] == value, key
    entry, = [c for c in benchmark_json["configs"] if c["name"] == "joyai-flash-ep16-d5"]
    assert entry["file"] == CONFIG and entry["source"] == conf["source"]
    assert set(entry["reduced"]) == set(conf["reduced"]) == set(conf["published"])
    assert conf["n_routed_experts"] * conf["expert_parallel_size"] == conf["published"]["n_routed_experts"]
    assert conf["vocab_size"] * conf["vocab_parallel_size"] == conf["published"]["vocab_size"]
    # the floors of a cut: four layers after the dense one, 8 experts, an eighth
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] >= 4
    assert conf["n_routed_experts"] >= 8 and conf["vocab_parallel_size"] <= 8
    for key in ("assumed", "deployment", "storage", "guarantees"):
        assert conf[key]
    assert {"leaf_per_expert_matrix", "step"} <= set(conf["assumed"])
    tiny = dict(conf, **module.TINY)
    assert tiny["num_hidden_layers"] - tiny["first_k_dense_replace"] >= 2
    assert tiny["n_routed_experts"] >= 2
    assert set(ref.spec_bytes(ref.tree_spec(tiny))) == {2, 4}


def test_the_state_file_and_the_reference_import_neither_each_other_nor_a_model(repo):
    with open(os.path.join(repo, "chipbench/states/moe_rank_mp.py")) as f:
        state_source = f.read()
    with open(os.path.join(repo, "chipbench/reference/mixed_precision_state.py")) as f:
        reference_source = f.read()
    for gone in ("torchsnapshot_tpu.models", "torchsnapshot_tpu.parallel", "import optax", "import flax"):
        assert gone not in state_source
    assert "chipbench.reference" not in state_source.split('"""', 2)[2]
    for line in reference_source.split('"""', 2)[2].splitlines():
        if line.startswith(("import ", "from ")):
            assert line.split()[1].split(".")[0] in ("__future__", "json", "os", "typing", "numpy"), line


# -------------------------------------------------- (b) the shares add up


@pytest.mark.parametrize("size", ["committed", "tiny"])
def test_the_shares_of_all_ranks_add_up_to_the_uncut_tree(conf, tiny, size):
    c = conf if size == "committed" else tiny
    uncut = ref.param_spec(ref.whole(c))
    held: dict = {}  # (name, rows) → shape: what every rank holds alike counts once
    for ep_rank in range(c["expert_parallel_size"]):
        for vocab_rank in range(c["vocab_parallel_size"]):
            for name, shape, rows in ref.param_spec(c, ep_rank, vocab_rank):
                assert held.setdefault((name, rows), shape) == shape
    joined = {name: shape for (name, rows), shape in held.items() if rows is None}
    for name in ("embed_tokens", "lm_head"):
        slices = sorted(rows for n, rows in held if n == name)
        # the slices tile the vocabulary: each starts where the last ended
        assert [lo for lo, _ in slices] == [0] + [hi for _, hi in slices[:-1]]
        joined[name] = (slices[-1][1], c["hidden_size"])
    assert joined == {name: shape for name, shape, _ in uncut}
    assert sum(math.prod(shape) for shape in held.values()) == ref.parameter_count(uncut)
    # and a rank's share times the ranks is more: attention and the router are held by all
    ranks = c["expert_parallel_size"] * c["vocab_parallel_size"]
    assert ref.parameter_count(ref.param_spec(c)) * ranks > ref.parameter_count(uncut)
    with pytest.raises(ValueError):
        ref.param_spec(c, ep_rank=c["expert_parallel_size"])


# ------------------------------------------------------------ (c) the step

# One step from the same state: the two differ by float32 rounding alone
# (the order of a matrix-vector sum, exp and log), which reads 1e-7 of a
# leaf's largest value in master and 1e-6 in the moments here; ten times
# that is allowed.  Moments held in bfloat16 are off by 2**-9 = 2e-3.
TOLERANCE = {"master": 1e-6, "mu": 1e-5, "nu": 1e-5}


def disagreement(tree, want):
    """Per group, the largest |difference| ÷ the leaf's largest |value|."""
    out = {}
    for group in TOLERANCE:
        got = named(tree[group])
        out[group] = max(
            float(np.max(np.abs(got[k] - want[group][k])) / np.abs(want[group][k]).max())
            for k in got
        )
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_three_steps_agree_with_the_reference_and_bf16_moments_do_not(tiny, factory, seed):
    tree = factory.make(seed)
    handed = state.array_leaves(tree)
    digest = state.Digester()
    for batch in factory.batch_pool(seed, [2, 16], 3):
        assert int(np.asarray(batch).max()) < tiny["vocab_size"]
        before, was = as_reference_state(tree), digest(tree)
        with factory.mesh:
            tree, loss = factory.step(tree, batch)
        want, want_loss = ref.adamw_mp_step(tiny, before, np.asarray(batch))
        assert np.ndim(loss) == 0 and np.isfinite(float(loss))
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
        assert int(tree["step"]) == want["step"]
        found = disagreement(tree, want)
        assert all(found[g] <= TOLERANCE[g] for g in TOLERANCE), found
        # params are bfloat16(master), bit for bit, by the reference's rounding
        masters = named(tree["master"])
        for name, p in named(tree["params"]).items():
            assert str(p.dtype) == "bfloat16"
            assert np.array_equal(p.view(np.uint16), ref.to_bf16_bits(masters[name])), name
        # a variant that holds its moments in bfloat16 is another result
        lossy = dict(want, **{
            g: {k: ref.from_bf16_bits(ref.to_bf16_bits(v)) for k, v in want[g].items()}
            for g in ("mu", "nu")
        })
        off = disagreement(tree, lossy)
        assert off["mu"] > 10 * TOLERANCE["mu"] and off["nu"] > 10 * TOLERANCE["nu"], off
        # every float32 leaf and the step change with every step, and every
        # bfloat16 matrix (a norm of a few elements may round to itself)
        now = digest(tree)
        changed = np.any(now != was, axis=1)
        layout = state.layout_of(tree)
        for moved, (shape, dtype, _) in zip(changed, layout):
            assert moved or (dtype == "bfloat16" and len(shape) == 1), (shape, dtype)
    assert sum(x.is_deleted() for x in handed) > len(handed) / 2  # argument 0 is donated


def test_the_reference_rounds_to_bfloat16_as_the_device_does():
    import jax.numpy as jnp

    x = np.random.default_rng(3).standard_normal(4096).astype(np.float32)
    x[:4] = [1.00390625, 1.01171875, -0.0, 3.0e-39]  # two ties, a zero, a subnormal
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)).view(np.uint16)
    assert np.array_equal(ref.to_bf16_bits(x), want)
    assert np.array_equal(ref.to_bf16_bits(ref.from_bf16_bits(want)), want)


# ------------------------------------ (d) a snapshot's bytes, read plainly


def take(tree, path, step=2):
    from torchsnapshot_tpu import PyTreeState, Snapshot, StateDict

    Snapshot.take(path, {"ts": PyTreeState(tree), "meta": StateDict(step=step)})


def stepped(factory, seed):
    tree = factory.make(seed)
    for batch in factory.batch_pool(seed, [2, 16], 2):
        with factory.mesh:
            tree, _ = factory.step(tree, batch)
    return tree


@pytest.mark.parametrize("seed", SEEDS)
def test_a_take_read_back_by_plain_file_reads_is_the_leaves_bytes(tiny, factory, tmp_path, seed):
    from torchsnapshot_tpu import PyTreeState, Snapshot, StateDict

    tree = stepped(factory, seed)
    path = str(tmp_path / "snap")
    take(tree, path)
    leaves, slabs = ref.leaf_bytes(path)
    want = {"step": np.asarray(tree["step"])}
    for group in ref.GROUPS:
        want.update({f"{group}/{k}": v for k, v in named(tree[group]).items()})
    assert set(leaves) == set(want) == {name for name, _, _ in ref.tree_spec(tiny)}
    for name, x in want.items():
        assert leaves[name]["bytes"] == x.tobytes(), name
        assert leaves[name]["dtype"] == str(x.dtype) and leaves[name]["shape"] == x.shape
    # every leaf is a slab member, and no slab mixes widths
    assert slabs and {leaves[n]["location"] for n in leaves} == set(slabs)
    assert all(len(widths) == 1 for widths in slabs.values())
    assert {w for widths in slabs.values() for w in widths} == {2, 4}
    # a restore into fresh templates returns each dtype, bit for bit
    template = factory.make(seed + 1)
    app = {"ts": PyTreeState(template), "meta": StateDict(step=-1)}
    want_layout, reference = state.layout_of(template), state.Digester()(tree)
    del template
    Snapshot(path).restore(app)
    got = app["ts"].tree
    assert state.compare(reference, state.Digester()(got), want_layout, state.layout_of(got)) == {
        "leaves_mismatched": 0, "leaves_misplaced": 0}
    assert app["meta"]["step"] == 2
    assert {str(x.dtype) for x in state.array_leaves(got["params"])} == {"bfloat16"}


def test_leaf_bytes_refuses_a_range_that_is_not_the_leafs_size(factory, tmp_path):
    import json

    path = str(tmp_path / "snap")
    take(stepped(factory, 5), path)
    marker = os.path.join(path, ".snapshot_metadata")
    body = ref.read_manifest(path)
    body["manifest"]["0/ts/step"]["byte_range"][1] += 2
    with open(marker, "w") as f:
        json.dump(body, f)
    with pytest.raises(ValueError, match="by its shape"):
        ref.leaf_bytes(path)


# ------------------------------------------- (e) the path each slab took


def counters():
    from torchsnapshot_tpu import obs

    return obs.metrics_snapshot()["counters"]


def gained(before, name):
    return counters().get(name, 0) - before.get(name, 0)


def test_the_counters_split_a_saves_bytes_by_width_and_the_host_path_is_counted(
    factory, tmp_path, monkeypatch
):
    from torchsnapshot_tpu.ops import device_pack

    tree = stepped(factory, 5)
    total = state.state_bytes(tree)
    before, calls = counters(), device_pack.CALL_COUNTS["pack"]
    take(tree, str(tmp_path / "a"))
    assert device_pack.CALL_COUNTS["pack"] - calls == 2  # one slab a width
    assert gained(before, "device_pack.bytes_w2") * 7 == total - 4
    assert gained(before, "device_pack.bytes_w2") + gained(before, "device_pack.bytes_w4") == total
    assert gained(before, "slab.host_pack_bytes") == 0
    assert gained(before, "exceptions.swallowed") == 0

    def fails(_arrays):
        raise RuntimeError("planted: the device pack fails")

    monkeypatch.setattr(device_pack, "pack_arrays_to_host", fails)
    before, calls = counters(), device_pack.CALL_COUNTS["pack"]
    take(tree, str(tmp_path / "b"))
    assert device_pack.CALL_COUNTS["pack"] == calls
    assert gained(before, "slab.host_pack_bytes") == total
    assert gained(before, "device_pack.bytes_w2") == gained(before, "device_pack.bytes_w4") == 0
    assert gained(before, "exceptions.swallowed") == 2  # a fallback is never silent
    # the host path wrote the same bytes
    a, _ = ref.leaf_bytes(str(tmp_path / "a"))
    b, _ = ref.leaf_bytes(str(tmp_path / "b"))
    assert {k: v["bytes"] for k, v in a.items()} == {k: v["bytes"] for k, v in b.items()}


@pytest.mark.parametrize("device", [False, True], ids=["host_by_choice", "device"])
def test_the_counters_split_a_restores_bytes_by_width(factory, tmp_path, monkeypatch, device):
    from torchsnapshot_tpu import PyTreeState, Snapshot, StateDict
    from torchsnapshot_tpu.ops import device_pack

    # a CPU backend unpacks on the host by the program's own choice; the
    # knob runs the device programs there
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_DEVICE_UNPACK", "1" if device else "auto")
    tree = stepped(factory, 5)
    total = state.state_bytes(tree)
    path = str(tmp_path / "snap")
    take(tree, path)
    app = {"ts": PyTreeState(factory.make(6)), "meta": StateDict(step=-1)}
    before, calls = counters(), device_pack.CALL_COUNTS["unpack"]
    Snapshot(path).restore(app)
    jax.block_until_ready(app["ts"].tree)
    narrow, wide = gained(before, "device_unpack.bytes_w2"), gained(before, "device_unpack.bytes_w4")
    if device:
        assert device_pack.CALL_COUNTS["unpack"] - calls == 2
        assert narrow * 7 == total - 4 and narrow + wide == total
        assert gained(before, "slab.host_unpack_bytes") == 0
    else:
        assert device_pack.CALL_COUNTS["unpack"] == calls
        assert narrow == wide == 0
        assert gained(before, "slab.host_unpack_bytes") == total
    assert gained(before, "exceptions.swallowed") == 0
    assert np.array_equal(state.Digester()(app["ts"].tree), state.Digester()(tree))


class _Span:
    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs


@pytest.mark.parametrize("side,op", [(width_reads.PACK, "take"), (width_reads.UNPACK, "restore")])
def test_the_share_readers_read_none_from_a_program_without_the_counters(side, op):
    timeline = [{"op": op, "t0": 0.0, "t1": 1.0}] * 2
    old = bench.Context(
        timeline=timeline, notes={"state_bytes": 1400}, spans=[],
        obs_before={"counters": {"bytes_read": 5}}, obs_after={"counters": {"bytes_read": 9}},
    )
    assert width_reads.narrow_share(old, side) is None
    assert width_reads.host_share(old, side) is None
    assert width_reads.members_mean(old, "pipeline/slab_pack") is None
    new = bench.Context(
        timeline=timeline, notes={"state_bytes": 1400},
        spans=[_Span("unpack/dispatch", members=4), _Span("unpack/dispatch", members=8)],
        obs_before={"counters": {side[0] + "4": 100}},
        obs_after={"counters": {side[0] + "4": 2500, side[0] + "2": 400}},
    )
    assert width_reads.narrow_share(new, side) == pytest.approx(1 / 7)
    assert width_reads.host_share(new, side) == 0.0  # never raised: 0, not None
    assert width_reads.members_mean(new, "unpack/dispatch") == 6.0
    idle = bench.Context(timeline=[], notes={"state_bytes": 1400}, spans=[],
                         obs_before=new.obs_before, obs_after=new.obs_after)
    assert width_reads.narrow_share(idle, side) is None


# ------------------------------------ the two cells, whole, in the miniature

TINY_PARAMETER_LEAVES = 3 + 12 + 2 * (9 + 2 + 3 + 3 * 3)


def _listed(full_spec, workload):
    return {m["name"] for m in full_spec["per_layer"] if workload in m["workloads"]}


@pytest.mark.parametrize("workload,metric", [(SAVE, "save_commit_s"), (RESUME, "resume_s")])
def test_a_plain_run_of_each_cell(run_tiny, tiny, workload, metric):
    result = run_tiny(workload)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {metric, "setup_s"}
    assert all(c["value"] <= c["limit"] for c in result["checks"].values())
    assert result["state_bytes"] == 14 * ref.parameter_count(ref.param_spec(tiny)) + 4
    if workload == RESUME:
        assert result["attempted"] == LOOPS


def test_a_traced_run_of_the_save_cell_reports_every_listed_metric(full_spec, run_tiny):
    result = run_tiny(SAVE, trace=True)
    assert result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == _listed(full_spec, SAVE)
    assert metrics["pack.narrow_bytes_share"] == pytest.approx(1 / 7, rel=1e-3)
    assert metrics["pack.host_bytes_share"] == 0.0
    assert metrics["device_pack.calls"] == 2.0
    # one slab of every 2-byte leaf, one of every 4-byte leaf and the step
    assert metrics["slab.members_mean.save"] == (4 * TINY_PARAMETER_LEAVES + 1) / 2
    assert metrics["take.host_us_per_leaf"] > 0


@pytest.mark.parametrize("device", [False, True], ids=["host_by_choice", "device"])
def test_a_traced_run_of_the_resume_cell_reports_every_listed_metric(
    full_spec, run_tiny, monkeypatch, device
):
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_DEVICE_UNPACK", "1" if device else "auto")
    result = run_tiny(RESUME, trace=True)
    assert result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # no CPU run has memory_stats; no slab is unpacked by a device program
    # where the program chooses the host for a CPU's arrays
    chip_only = {"restore.hbm_peak_x"} | (set() if device else {"slab.members_mean.restore"})
    assert set(metrics) == _listed(full_spec, RESUME) - chip_only
    assert metrics["restore.host_us_per_leaf"] > 0
    if device:
        assert metrics["unpack.narrow_bytes_share"] == pytest.approx(1 / 7, rel=1e-3)
        assert metrics["unpack.host_bytes_share"] == 0.0
        assert metrics["device_unpack.calls"] == 2.0
        assert metrics["slab.members_mean.restore"] == (4 * TINY_PARAMETER_LEAVES + 1) / 2
    else:
        assert metrics["unpack.narrow_bytes_share"] == 0.0
        assert metrics["unpack.host_bytes_share"] == pytest.approx(1.0)
        assert metrics["device_unpack.calls"] == 0.0


@pytest.mark.parametrize("workload,answers", [(SAVE, 1), (RESUME, 1 + LOOPS)])
def test_the_control_fails_on_the_float32_leaves_and_on_no_other(run_tiny, workload, answers):
    result = run_tiny(workload, fault="control_bf16")
    assert result["correct"] is False and result["failed"] == 1
    # master, mu and nu of every parameter leaf, in every answer; no
    # bfloat16 leaf and not the step
    assert result["checks"]["leaves_mismatched"]["value"] == answers * 3 * TINY_PARAMETER_LEAVES
    assert result["checks"]["leaves_misplaced"]["value"] == 0


def test_a_snapshot_of_a_later_state_is_caught_in_the_save_cell(run_tiny):
    result = run_tiny(SAVE, fault="late_snapshot")
    assert result["correct"] is False
    # one step on: every float32 leaf and the step differ (and most bfloat16 ones)
    assert result["checks"]["leaves_mismatched"]["value"] >= 3 * TINY_PARAMETER_LEAVES + 1
