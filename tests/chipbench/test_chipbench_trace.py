"""The reduction from a profiler trace to busy time and idle gaps: exact on
a record made by hand, and steady on a small one recorded on the chip."""

import json
import os

import pytest

from chipbench import trace_reduce
from chipbench.trace_reduce import NO_SPAN, SPAN_PREFIX

MS = 1_000_000


def _record(ops, spans, plane="/device:TPU:0"):
    return {
        "devices": {plane: [[n, s * MS, d * MS] for n, s, d in ops]},
        "spans": [[SPAN_PREFIX + n, s * MS, d * MS] for n, s, d in spans],
    }


def test_union_merges_what_overlaps_and_keeps_what_does_not():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace_reduce.union([]) == []


def test_busy_is_the_union_and_gaps_go_to_the_span_they_fall_in():
    record = _record(
        ops=[("fusion.1", 10, 20), ("fusion.2", 20, 20), ("copy.1", 100, 10)],
        spans=[("template", 0, 50), ("restore", 50, 150)],
    )
    got = trace_reduce.reduce(record)
    assert got["window_s"] == pytest.approx(0.200)
    assert got["busy_s"] == pytest.approx(0.040)  # 10..40 and 100..110
    assert dict(got["idle_gaps"]) == {
        "restore": pytest.approx(0.140),  # 50..100 and 110..200
        "template": pytest.approx(0.020),  # 0..10 and 40..50
    }
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(0.020)]
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle + got["busy_s"] == pytest.approx(got["window_s"])


def test_a_gap_under_no_span_is_named_so():
    record = _record(
        ops=[("fusion.1", 0, 10)], spans=[("step", 0, 20), ("take", 60, 40)]
    )
    gaps = dict(trace_reduce.reduce(record)["idle_gaps"])
    assert gaps[NO_SPAN] == pytest.approx(0.040)
    assert gaps["step"] == pytest.approx(0.010) and gaps["take"] == pytest.approx(0.040)


def test_operations_outside_the_traced_window_do_not_count():
    record = _record(
        ops=[("before", -50, 40), ("straddles", -5, 10), ("inside", 10, 10)],
        spans=[("drain", 0, 100)],
    )
    got = trace_reduce.reduce(record)
    assert got["busy_s"] == pytest.approx(0.015)
    assert dict(got["device_ops"]) == {
        "inside": pytest.approx(0.010), "straddles": pytest.approx(0.005)}


def test_busy_is_the_mean_over_the_devices_that_ran():
    record = _record(ops=[("a", 0, 40)], spans=[("restore", 0, 100)])
    record["devices"]["/device:TPU:1"] = [["a", 0, 20 * MS]]
    record["devices"]["/device:TPU:2"] = []
    assert trace_reduce.reduce(record)["busy_s"] == pytest.approx(0.030)


def test_a_window_with_no_device_operation_reduces_to_nothing():
    assert trace_reduce.reduce(_record(ops=[], spans=[("restore", 0, 10)])) is None
    assert trace_reduce.reduce({"devices": {"/device:TPU:0": [["a", 0, 5]]}, "spans": []}) is None


def test_at_most_ten_rows_each():
    ops = [(f"op.{i}", i * 10, 5) for i in range(30)]
    got = trace_reduce.reduce(_record(ops=ops, spans=[("step", 0, 300)]))
    assert len(got["device_ops"]) == 10 and len(got["idle_gaps"]) == 1


def test_short_name_keeps_the_instruction_and_drops_its_shapes():
    hlo = "%reshape.1 = f32[2048,5632]{1,0:T(8,128)} reshape(f32[11534336]{0} %fusion)"
    assert trace_reduce.short_name(hlo) == "reshape.1"
    assert trace_reduce.short_name("fusion.7") == "fusion.7"


def test_the_recorded_chip_trace_reduces_as_it_did_when_it_was_recorded(repo):
    path = os.path.join(repo, "chipbench", "testdata", "kill_resume_small_trace.json")
    with open(path) as f:
        record = json.load(f)
    got = trace_reduce.reduce(record)
    # three resumes on a TPU v5 lite: the device is idle nearly all the time
    assert got["window_s"] == pytest.approx(2.245391922)
    assert got["busy_s"] == pytest.approx(0.03531182)
    gaps = dict(got["idle_gaps"])
    assert gaps["restore"] == pytest.approx(2.156570919)
    assert gaps["template"] == pytest.approx(0.049885102)
    assert sum(gaps.values()) + got["busy_s"] == pytest.approx(got["window_s"])
    assert got["device_ops"][0][0] == "reshape.1"
