"""``unpack.arg_puts`` (PR 37): the host→device transfers of the ARGUMENTS of
a restore's device programs, the slab or piece itself apart, per restore.
Whole runs of the three resume cells at tiny widths with the device path
asked for on the CPU (``TORCHSNAPSHOT_TPU_DEVICE_UNPACK=1``): one transfer a
slab (its members' offsets as one vector, where a program that hands its
member programs host scalars would make one a member) and two a cut (the
numpy scalars a cut is handed); 0 where the host path runs by choice; None
from a program that has no such counter."""

import importlib.util
import os

import pytest

from chipbench import bench

D9 = "ouro-2.6b-d9.kill_resume"
JOYAI = "joyai-flash-ep16-d5.kill_resume"
ELASTIC = "ouro-2.6b-4chip.elastic_resume"
NAME = "unpack.arg_puts"
COUNTER = "device_unpack.arg_puts"


@pytest.fixture(scope="module")
def reader(repo):
    spec = importlib.util.spec_from_file_location(
        "arg_puts_reader", os.path.join(repo, "chipbench", "metrics", NAME + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_metric_is_listed_for_the_three_resume_cells_and_reads_a_counter(checkout_spec):
    (entry,) = [m for m in checkout_spec["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "puts/restore", "better": "lower", "source": "program_counter",
        "layer": "device pack / unpack", "moves": "resume_s",
    }
    assert {D9, ELASTIC, JOYAI} <= set(entry["workloads"])


def _window_spans(name):
    """The spans of the traced window just run (the harness resets the
    program's tracer when the next one opens)."""
    from torchsnapshot_tpu.obs import tracer

    return [s for s in tracer.get_tracer().spans() if s.name == name]


@pytest.mark.parametrize("workload", [D9, JOYAI])
def test_a_one_chip_restore_sends_one_vector_a_slab(run_tiny, monkeypatch, workload):
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_DEVICE_UNPACK", "1")
    result = run_tiny(workload, trace=True)
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    slabs = _window_spans("unpack/dispatch")
    assert len(slabs) == result["attempted"] * m["device_unpack.calls"] > 0
    # a slab's members go up in one vector (of at most 64: the tiny dense
    # tree is one slab of every leaf, so two or more), where a host scalar a
    # member would read the leaves
    assert [s.attrs["arg_puts"] for s in slabs] == [-(-s.attrs["members"] // 64) for s in slabs]
    assert m[NAME] == sum(s.attrs["arg_puts"] for s in slabs) / result["attempted"]
    assert m[NAME] < sum(s.attrs["members"] for s in slabs) / result["attempted"] / 8
    assert all(0 < s.attrs["first_call_ns"] <= s.duration_ns for s in slabs)
    assert result["metrics"][NAME]["unit"] == "puts/restore"
    if workload == JOYAI:  # a 2-byte and a 4-byte slab, of 65 to 128 members each
        assert (m["device_unpack.calls"], m[NAME]) == (2.0, 4.0)


def test_a_resharding_restore_counts_the_scalars_its_cuts_are_handed(run_tiny, monkeypatch):
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_DEVICE_UNPACK", "1")
    result = run_tiny(ELASTIC, trace=True)
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # a cut of a matrix is handed two numpy scalars, each a transfer inside
    # the call (the vector-a-piece form was measured slower and taken out)
    assert m["device_unpack.calls"] > 0 and m[NAME] == 2 * m["device_unpack.calls"]
    assert m["reshard.host_alloc_x"] < 0.5  # the direct path ran


@pytest.mark.parametrize("workload", [D9, JOYAI, ELASTIC])
def test_the_host_path_by_choice_reads_zero_not_an_absence(run_tiny, monkeypatch, workload):
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_DEVICE_UNPACK", "auto")
    result = run_tiny(workload, trace=True)
    assert result["correct"] is True
    assert result["metrics"][NAME]["value"] == 0.0
    assert result["metrics"]["device_unpack.calls"]["value"] == 0.0


def test_the_reader_reads_none_from_a_program_without_the_counter(reader):
    timeline = [{"op": "restore", "t0": 0.0, "t1": 1.0}] * 4
    old = bench.Context(
        timeline=timeline, notes={"state_bytes": 1400}, spans=[],
        obs_before={"counters": {"bytes_read": 5}}, obs_after={"counters": {"bytes_read": 9}},
    )
    assert reader(old) is None
    new = bench.Context(
        timeline=timeline, notes={"state_bytes": 1400}, spans=[],
        obs_before={"counters": {COUNTER: 54}}, obs_after={"counters": {COUNTER: 54 + 4 * 54}},
    )
    assert reader(new) == 54.0
    # made during the window: nothing to take off
    late = bench.Context(
        timeline=timeline, notes={"state_bytes": 1400}, spans=[],
        obs_before={"counters": {}}, obs_after={"counters": {COUNTER: 8}},
    )
    assert reader(late) == 2.0
    idle = bench.Context(
        timeline=[], notes={"state_bytes": 1400}, spans=[],
        obs_before=new.obs_before, obs_after=new.obs_after,
    )
    assert reader(idle) is None
