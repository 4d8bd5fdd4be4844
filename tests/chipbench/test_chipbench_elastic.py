"""The four-chip cell ``ouro-2.6b-4chip.elastic_resume``: its configuration
keeps Ouro-2.6B's published widths, its mix is the one PERF.md states, and
whole runs of it at tiny widths on the CPU's virtual devices (saved under
2x2, restored under 1x4) come out correct, report the cell's per-layer
metrics and catch the control and a planted fault.  The tiny runs count
loops (``window.max_loops``, set by ``conftest.tiny_root``), never seconds,
so that ``correct`` cannot depend on a loaded worker's clock."""

import os

import pytest

from chipbench import bench, state

CELL = "ouro-2.6b-4chip.elastic_resume"
CONFIG = "ouro-2.6b-4chip"
MIX = os.path.join("chipbench", "traffic", "elastic_resume.json")
# Ouro-2.6B's published config.json, the numbers a cut may not touch
PUBLISHED = dict(
    head_dim=128, hidden_size=2048, intermediate_size=5632,
    max_position_embeddings=65536, max_window_layers=48,
    num_attention_heads=16, num_key_value_heads=16, total_ut_steps=4,
    early_exit_threshold=1, vocab_size=49152,
)
NEW_READERS = {
    "reshard.plan_s", "reshard.scatter_s", "reshard.assemble_s",
    "reshard.host_alloc_x", "read.bytes_per_state_byte",
}
LOOPS = 3


# ------------------------------------------------- the files as committed


def test_the_configuration_keeps_the_published_widths(repo, benchmark_json, dense_lm):
    entry = {c["name"]: c for c in benchmark_json["configs"]}[CONFIG]
    conf = state.load_json(os.path.join(repo, entry["file"]))
    for key, value in PUBLISHED.items():
        assert conf[key] == value, key
    assert conf["source"] == entry["source"] and len(entry["source"]) <= 200
    assert set(entry["reduced"]) == set(conf["reduced"]) == {
        "num_hidden_layers", "layer_types", "rope_theta", "rms_norm_eps",
    }
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), key
    assert len(conf["layer_types"]) == conf["num_hidden_layers"]
    assert conf["state"] == "dense_lm"
    cfg = dense_lm.model_config(conf)
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (2048, 16, 5632, 49152)
    for stated in ("published", "assumed", "deployment", "storage", "guarantees"):
        assert conf[stated], stated
    assert any("written once" in g for g in conf["guarantees"])


def test_parameter_count_is_the_one_perf_md_states(repo):
    conf = state.load_json(os.path.join(repo, f"chipbench/configs/{CONFIG}.json"))
    d, f, v, n = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"], conf["num_hidden_layers"]
    assert n >= 24 and n % 4 == 0  # the issue's floor for the four-chip cell
    params = 2 * v * d + n * (4 * d * d + 3 * d * f + 2 * d) + d
    assert (n, params) == (24, 1_434_552_320)
    # f32 params + adamw's mu and nu, and the two int32 counters
    assert 12 * params + 8 == 17_214_627_848


def test_the_mix_is_the_one_the_cell_states(checkout):
    cell = bench.Cell(checkout, CELL)
    assert cell.chips == 4 and cell.workload["traffic"] == "elastic_resume"
    mix = state.load_json(os.path.join(checkout, MIX))
    assert mix == cell.traffic
    assert (mix["save_mesh"], mix["restore_mesh"], mix["batch"]) == ([2, 2], [1, 4], [2, 512])
    assert mix["setup"] == ["step", "step", "take", "drop", "restore"]
    assert mix["window"] == {"loop": ["restore"]}
    assert mix["check"] == {"loops": 2, "below": 3} and mix["answers_checked_least"] == 3
    assert mix["read_back"] is False and mix["counts_as_attempt"] == "restore"
    assert mix["end_to_end"] == {"resume_s": {"kind": "window_per_op", "op": "restore"}}
    # the local RAM tier a preempted job comes back to, and its source
    assert mix["sink"] == "ram" and "arXiv:2407.20143" in mix["source"]
    # reshard_resume, which PR 25 left in the tree, is this mix on sink tmp
    old = state.load_json(os.path.join(checkout, "chipbench", "traffic", "reshard_resume.json"))
    assert {k: v for k, v in mix.items() if k not in ("doc", "sink", "source")} == {
        k: v for k, v in old.items() if k != "doc"
    }


def test_the_cell_lists_its_metrics(checkout):
    cell = bench.Cell(checkout, CELL)
    assert {m["name"] for m in cell.end_to_end_metrics()} == {"resume_s", "setup_s"}
    listed = {m["name"]: m for m in cell.per_layer_metrics()}
    assert NEW_READERS <= set(listed)
    for name in NEW_READERS:
        assert CELL in listed[name]["workloads"] and listed[name]["moves"] == "resume_s"
    assert {"device_unpack.calls", "restore.hbm_peak_x", "consume.busy_s"} <= set(listed)


def test_the_reference_imports_nothing_of_the_program(repo):
    with open(os.path.join(repo, "chipbench", "reference", "reshard.py")) as f:
        source = f.read()
    assert "torchsnapshot" not in source
    imports = [line for line in source.splitlines() if line.startswith(("import ", "from "))]
    assert all(
        line.split()[1].split(".")[0] in ("__future__", "typing", "numpy") for line in imports
    ), imports


# -------------------------------------------------- whole runs in miniature


@pytest.fixture(scope="module")
def elastic_root(tiny_root):
    """The miniature checkout: its copy of the cell's mix is cut to a tiny
    batch and held to ``LOOPS`` restores whatever the clock says."""
    assert state.load_json(os.path.join(tiny_root, MIX))["window"]["max_loops"] == LOOPS
    return tiny_root


@pytest.fixture(scope="module")
def run_elastic(elastic_root):
    def run(trace=False, fault=None, seed=2**31 + 7):
        # an hour of window: the loop's cap ends the run, not the clock
        return bench.run_cell(
            elastic_root, CELL, seed=seed, seconds=3600.0, trace=trace,
            allow_cpu=True, fault=fault,
        )

    return run


def test_a_plain_run_counts_loops_and_comes_out_correct(run_elastic):
    result = run_elastic()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == LOOPS
    assert set(result["metrics"]) == {"resume_s", "setup_s"}
    for check in result["checks"].values():
        assert check["value"] == 0 and check["limit"] == 0
    assert result["sink"] == "ram"


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 2**32 + 13])
def test_whichever_restores_the_seed_draws_are_all_checked(run_elastic, seed):
    result = run_elastic(seed=seed)
    assert result["correct"] is True and result["attempted"] == LOOPS
    assert result["checks"]["answers_unchecked"]["value"] == 0


def test_a_traced_run_reports_every_listed_metric_but_the_chips_own(run_elastic, benchmark_json):
    result = run_elastic(trace=True)
    assert result["correct"] is True
    wanted = {m["name"] for m in benchmark_json["per_layer"] if CELL in m["workloads"]}
    # no CPU run has memory_stats
    assert set(result["metrics"]) == wanted - {"restore.hbm_peak_x"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # every byte of the state is allocated once and asked of the sink once
    assert m["reshard.host_alloc_x"] == pytest.approx(1.0)
    assert m["read.bytes_per_state_byte"] == pytest.approx(1.0, rel=0.02)
    assert m["device_unpack.calls"] == 0
    for name in ("reshard.plan_s", "reshard.scatter_s", "reshard.assemble_s"):
        assert m[name] > 0
    assert m["reshard.scatter_s"] <= m["consume.busy_s"]


def test_the_control_and_an_altered_answer_come_out_not_correct(run_elastic):
    control = run_elastic(fault="control_bf16")
    assert control["correct"] is False and control["failed"] == 1
    assert control["checks"]["leaves_mismatched"]["value"] > 0
    assert control["checks"]["leaves_misplaced"]["value"] == 0
    altered = run_elastic(fault="answer_altered")
    assert altered["correct"] is False
    assert altered["checks"]["leaves_mismatched"]["value"] >= 1


def test_a_snapshot_of_a_later_state_is_caught(run_elastic):
    late = run_elastic(fault="late_snapshot")
    assert late["correct"] is False
    assert late["checks"]["leaves_mismatched"]["value"] > 0


def test_the_new_readers_read_nothing_from_a_program_without_the_spans(repo):
    """The parent commit records no ``reshard/*`` span and has no such
    counter: each new reader returns None there and does not raise."""
    from torchsnapshot_tpu.obs.tracer import Span

    def span(name, start, end, parent=None):
        s = Span(name, parent, {})
        s.start_ns, s.end_ns = start, end
        return s

    def context(counters, more_spans=()):
        root = span("restore", 1_000, 9_000)
        return bench.Context(
            timeline=[{"op": "restore", "t0": 0.0, "t1": 1e-5}],
            spans=[root, span("restore/pipeline", 2_000, 8_000, root.span_id), *more_spans],
            obs_before={"counters": {}}, obs_after={"counters": counters},
            notes={"state_bytes": 100},
        )

    cell = bench.Cell(repo, CELL)
    for name in sorted(NEW_READERS - {"read.bytes_per_state_byte"}):
        assert cell.reader(name)(context({"bytes_read": 7})) is None, name
    # ``bytes_read`` is older than the cell: a window without a restore reads nothing
    no_restore = context({"bytes_read": 7})
    no_restore.timeline[0]["op"] = "take"
    assert cell.reader("read.bytes_per_state_byte")(no_restore) is None
    ctx = context(
        {"reshard.host_alloc_bytes": 250, "bytes_read": 100},
        [span("reshard/assemble", 3_000, 5_000)],
    )
    assert cell.reader("reshard.host_alloc_x")(ctx) == pytest.approx(2.5)
    assert cell.reader("read.bytes_per_state_byte")(ctx) == pytest.approx(1.0)
    assert cell.reader("reshard.assemble_s")(ctx) == pytest.approx(2e-6)
    assert cell.reader("reshard.scatter_s")(ctx) is None


def test_the_xplane_tool_lists_one_sharded_restore(elastic_root, capsys):
    """``tools/tsnp_xplane.py --cell … --nth N`` in rehearsal: the profile of
    one restore holds the sharded path's spans by name."""
    import importlib.util

    path = os.path.join(os.path.dirname(bench.__file__), "..", "tools", "tsnp_xplane.py")
    spec = importlib.util.spec_from_file_location("tsnp_xplane_for_test", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    result = tool.run_cell(
        CELL, 2**31 + 21, 3600.0, "chipbench:restore", 1, 1.0,
        root=elastic_root, allow_cpu=True,
    )
    assert result["correct"] is True
    err = capsys.readouterr().err
    assert "chipbench:restore number 1 of 3" in err
    for name in ("tsnp:reshard/plan", "tsnp:reshard/scatter", "tsnp:reshard/assemble", "tsnp:h2d/put"):
        assert name in err, name
