"""The harness without a run: discovery by name, the contract's shape of
BENCHMARK.json, the arithmetic on timelines that hold a stall, the table
of peaks, and the refusal to run without a TPU."""

import json
import os
import re
import statistics

import pytest

from chipbench import arith, bench, state

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    WORKLOADS = [w["name"] for w in json.load(_f)["workloads"]]
# Ouro-2.6B's published config.json, the numbers a cut may not touch
PUBLISHED = dict(
    head_dim=128, hidden_size=2048, intermediate_size=5632,
    max_position_embeddings=65536, max_window_layers=48,
    num_attention_heads=16, num_key_value_heads=16, total_ut_steps=4,
    early_exit_threshold=1, vocab_size=49152,
)


def _cells(benchmark_json):
    return {w["name"]: w for w in benchmark_json["workloads"]}


def test_benchmark_json_has_exactly_the_contracts_keys(benchmark_json):
    assert set(benchmark_json) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= benchmark_json["run_seconds"] <= 51
    assert "restore_peak_hbm_x" not in {m["name"] for m in benchmark_json["end_to_end"]}


def test_names_units_and_bounds_keep_to_the_contract(benchmark_json):
    for table in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in benchmark_json[table]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in benchmark_json["end_to_end"] + benchmark_json["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in benchmark_json["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in benchmark_json["end_to_end"]}
    four = sum(w["chips"] == 4 for w in benchmark_json["workloads"])
    assert four <= max(1, len(benchmark_json["workloads"]) // 4)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_cell_is_found_by_its_name_alone(repo, benchmark_json, workload):
    cell = bench.Cell(repo, workload)
    assert cell.config["hidden_size"] == 2048
    assert cell.traffic["counts_as_attempt"] in ("restore", "take", "cycle")
    reported = {m["name"] for m in cell.end_to_end_metrics()}
    assert "setup_s" in reported and len(reported) >= 2
    assert set(cell.traffic["end_to_end"]) <= reported
    layered = cell.per_layer_metrics()
    assert layered
    for m in layered:
        assert m["moves"] in reported, (m["name"], m["moves"])
        assert callable(cell.reader(m["name"]))


def test_every_per_layer_metric_has_a_reader_and_lists_real_cells(repo, benchmark_json, full_spec):
    cells = _cells(benchmark_json)
    for m in benchmark_json["per_layer"]:
        assert os.path.isfile(os.path.join(repo, "chipbench", "metrics", m["name"] + ".py"))
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
    # a reader with no entry serves a cell that PERF.md leaves out for now
    readers = {f[:-3] for f in os.listdir(os.path.join(repo, "chipbench", "metrics")) if f.endswith(".py")}
    assert readers == {m["name"] for m in full_spec["per_layer"]}


def test_left_out_cells_are_whole_entries_a_later_pr_can_add(repo, benchmark_json, full_spec):
    cells = _cells(full_spec)
    assert len(cells) == len(full_spec["workloads"])
    configs = {c["name"]: c for c in full_spec["configs"]}
    for w in full_spec["workloads"]:
        assert os.path.isfile(os.path.join(repo, configs[w["config"]]["file"]))
        assert os.path.isfile(os.path.join(repo, "chipbench", "traffic", w["traffic"] + ".json"))
        reported = {m["name"] for m in full_spec["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])}
        assert len(reported) >= 2


def test_an_unknown_workload_is_an_error(repo):
    with pytest.raises(KeyError):
        bench.Cell(repo, "no-such.cell")


@pytest.mark.parametrize("config", ["ouro-2.6b-d3", "ouro-2.6b-d4", "ouro-2.6b-d9", "ouro-2.6b-d32"])
def test_configuration_keeps_the_published_widths(repo, full_spec, dense_lm, config):
    entry = {c["name"]: c for c in full_spec["configs"]}[config]
    conf = state.load_json(os.path.join(repo, entry["file"]))
    for key, value in PUBLISHED.items():
        assert conf[key] == value, key
    assert conf["source"] == entry["source"]
    assert set(entry["reduced"]) == set(conf["reduced"])
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), key
    assert len(conf["layer_types"]) == conf["num_hidden_layers"]
    assert conf["state"] == "dense_lm"
    cfg = dense_lm.model_config(conf)
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (2048, 16, 5632, 49152)


@pytest.mark.parametrize(
    "config,params",
    [("ouro-2.6b-d3", 355_481_600), ("ouro-2.6b-d4", 406_865_920), ("ouro-2.6b-d9", 663_787_520), ("ouro-2.6b-d32", 1_845_626_880)],
)
def test_parameter_count_of_the_cut_is_the_one_perf_md_states(repo, config, params):
    conf = state.load_json(os.path.join(repo, f"chipbench/configs/{config}.json"))
    d, f, v, n = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"], conf["num_hidden_layers"]
    assert 2 * v * d + n * (4 * d * d + 3 * d * f + 2 * d) + d == params


# ------------------------------------------------------------- arithmetic


def _reps(op, durations, t=0.0):
    out = []
    for d in durations:
        out.append({"op": op, "t0": t, "t1": t + d})
        t += d
    return out


@pytest.mark.parametrize("op", ["restore", "take"])
def test_whole_window_mean_moves_with_a_stall_and_a_median_would_not(op):
    steady = _reps(op, [1.0] * 10)
    stalled = _reps(op, [1.0] * 4 + [6.0] + [1.0] * 5)
    assert arith.window_per_op(steady, op) == pytest.approx(1.0)
    assert arith.window_per_op(stalled, op) == pytest.approx(1.5)
    pieces = [r["t1"] - r["t0"] for r in stalled]
    assert statistics.median(pieces) == pytest.approx(1.0)


def test_a_gap_between_repetitions_is_part_of_the_window():
    timeline = _reps("restore", [1.0, 1.0]) + _reps("restore", [1.0], t=5.0)
    assert arith.window_per_op(timeline, "restore") == pytest.approx(2.0)


def test_the_benchmarks_own_pauses_are_no_part_of_the_window():
    timeline = (
        _reps("restore", [1.0]) + _reps("check", [3.0], t=1.0) + _reps("restore", [1.0], t=4.0)
    )
    assert arith.window_seconds(timeline) == pytest.approx(2.0)
    assert arith.window_per_op(timeline, "restore") == pytest.approx(1.0)


def test_a_pause_after_the_last_work_is_outside_the_window():
    timeline = (
        _reps("check", [0.5]) + _reps("take", [4.0, 4.0], t=0.5) + _reps("check", [1.0], t=8.5)
    )
    assert arith.window_seconds(timeline) == pytest.approx(8.0)
    assert arith.window_per_op(timeline, "take") == pytest.approx(4.0)


def test_template_and_restore_records_count_one_resume_each():
    timeline, t = [], 0.0
    for _ in range(5):
        timeline += [{"op": "template", "t0": t, "t1": t + 0.1},
                     {"op": "restore", "t0": t + 0.1, "t1": t + 1.0}]
        t += 1.0
    assert arith.window_per_op(timeline, "restore") == pytest.approx(1.0)


def _async_window(drain_steps, blocked=2.0):
    """ten clean 0.5 s steps, one cycle, ten clean steps"""
    timeline = [dict(r, in_flight=False) for r in _reps("step", [0.5] * 10)]
    t0 = t = 5.0
    timeline.append({"op": "take", "t0": t, "t1": t + blocked, "asynchronous": True})
    t += blocked
    for d in drain_steps:
        timeline.append({"op": "step", "t0": t, "t1": t + d, "in_flight": True})
        t += d
    timeline.append({"op": "cycle", "t0": t0, "t1": t, "steps": len(drain_steps)})
    timeline += [dict(r, in_flight=False) for r in _reps("step", [0.5] * 10, t=t)]
    return timeline


def test_clean_step_seconds_leaves_out_the_steps_under_a_drain():
    assert arith.clean_step_seconds(_async_window([4.0, 0.5])) == pytest.approx(0.5)


def test_stall_per_cycle_is_the_loop_time_lost():
    # blocked 2 s + a first donated step that waits 4 s instead of 0.5
    assert arith.stall_per_cycle(_async_window([4.0, 0.5, 0.5])) == pytest.approx(5.5)
    assert arith.stall_per_cycle(_async_window([0.5, 0.5, 0.5], blocked=0.0)) == pytest.approx(0.0)


def test_stall_moves_with_one_slow_step_among_many():
    quiet = arith.stall_per_cycle(_async_window([0.5] * 20))
    one_slow = arith.stall_per_cycle(_async_window([0.5] * 10 + [3.5] + [0.5] * 9))
    assert one_slow - quiet == pytest.approx(3.0)


def test_end_to_end_follows_the_traffic_files_table():
    specs = {"resume_s": {"kind": "window_per_op", "op": "restore"}}
    assert arith.end_to_end(_reps("restore", [2.0, 2.0]), specs) == {"resume_s": pytest.approx(2.0)}
    assert arith.end_to_end([], specs) == {}
    with pytest.raises(ValueError):
        arith.end_to_end([], {"x": {"kind": "median_of_pieces"}})


# ----------------------------------------------------------- peaks, no TPU


def test_peaks_name_their_source_and_an_unknown_device_raises(repo):
    cell = bench.Cell(repo, json.load(open(os.path.join(repo, "BENCHMARK.json")))["workloads"][0]["name"])
    v5e = cell.peak_of("TPU v5 lite")
    assert v5e["source"] and v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        cell.peak_of("TPU v9 imaginary")


def test_no_tpu_no_run():
    with pytest.raises(bench.NoChip):
        bench.pick_devices(1, allow_cpu=False)
    with pytest.raises(bench.NoChip):
        bench.pick_devices(64, allow_cpu=True)


def test_the_command_exits_nonzero_and_prints_no_result_without_a_tpu(benchmark_json, capsys):
    from chipbench import run

    code = run.main([
        "--workload", benchmark_json["workloads"][0]["name"],
        "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0",
    ])
    assert code == bench.NO_CHIP != 0
    assert capsys.readouterr().out == ""


def test_a_seed_past_32_signed_bits_makes_a_key():
    import numpy as np

    a, b = state.prng_key(2**31 + 11), state.prng_key(11)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
