"""The device's idle time inside restores and saves split by the program's
spans (``chipbench/idle_reads.py``): exact on windows built by hand, the
program's spans on ``time.monotonic_ns`` and the profile on a clock of its
own, ``OFFSET`` apart; and the program's events read from a real profile."""

import json
import os

import pytest

from chipbench import idle_reads, trace_reduce
from torchsnapshot_tpu.obs.tracer import Span

MS = 1_000_000
OFFSET = 7_000 * MS  # the profile's clock − time.monotonic_ns
RESUME_CELL = "ouro-2.6b-d9.kill_resume"


def _span(spans, name, parent, start_ms, ms, thread="MainThread", lexical=True, **attrs):
    s = Span(name, parent.span_id if parent is not None else None, attrs)
    s.thread_name, s.lexical = thread, lexical
    s.start_ns, s.end_ns = int(start_ms * MS), int((start_ms + ms) * MS)
    spans.append(s)
    return s


def _restore(spans, at_ms):
    """One restore as the program records it, ``at_ms`` on: the root 0–90,
    the caller's pipeline 10–80, a worker's materialize 15–60 with a put
    20–40 in it, and an admission (a ``begin`` span, no event) 82–88."""
    root = _span(spans, "restore", None, at_ms, 90)
    pipe = _span(spans, "restore/pipeline", root, at_ms + 10, 70)
    work = _span(spans, "consume/materialize", pipe, at_ms + 15, 45, thread="tsnp-consume_0")
    _span(spans, "h2d/put", work, at_ms + 20, 20, thread="tsnp-consume_0", link="h2d")
    _span(spans, "pipeline/budget_admission", pipe, at_ms + 82, 6, lexical=False)
    return {"op": "restore", "t0": at_ms / 1e3, "t1": (at_ms + 100) / 1e3}


def _record(spans, ops_ms, devices=1, skew_ns=(0,)):
    """The profile of a traced run, as ``idle_reads.load`` gives it: each
    lexical span's event at its own start + ``OFFSET`` (+ a skew, in turn);
    ``ops_ms`` the device's operations, on each of ``devices`` planes."""
    program = [
        [s.span_id, s.start_ns + OFFSET + skew_ns[i % len(skew_ns)], s.duration_ns]
        for i, s in enumerate(x for x in spans if x.lexical)
    ]
    ops = [["fusion", int(a * MS) + OFFSET, int(d * MS)] for a, d in ops_ms]
    return {
        "devices": {f"/device:TPU:{i}": list(ops) for i in range(devices)},
        "spans": [], "program": program,
    }


def test_the_three_classes_add_up_to_the_records_idle_time():
    spans = []
    timeline = [_restore(spans, 0)]
    got = idle_reads.split(_record(spans, ops_ms=[(30, 5), (70, 5)]), spans, timeline)["restore"]
    # idle: 100 ms less 10 of operations; link: the put 20–40 less 30–35;
    # host: the pipeline 10–80 and the admission 82–88 less the link and the
    # operations; unspanned: 0–10 and 80–82 (the root alone) and 88–100
    assert got["records"] == 1
    assert got["idle_s"] == pytest.approx(0.090)
    assert got["link_s"] == pytest.approx(0.015)
    assert got["host_s"] == pytest.approx(0.051)
    assert got["unspanned_s"] == pytest.approx(0.024)
    assert got["link_s"] + got["host_s"] + got["unspanned_s"] == pytest.approx(got["idle_s"], abs=1e-12)


def test_a_link_span_on_any_thread_takes_the_instant_from_host():
    spans = []
    timeline = [_restore(spans, 0)]
    caller = next(s for s in spans if s.name == "restore/pipeline")
    # the caller's pipeline is open 10–80 on the main thread throughout;
    # a second worker waits on the link 50–70 beside it
    _span(spans, "chunk/land", caller, 50, 20, thread="tsnp-consume_1", link="h2d")
    # the device's one operation lies after the record
    got = idle_reads.split(_record(spans, ops_ms=[(150, 1)]), spans, timeline)["restore"]
    assert got["link_s"] == pytest.approx(0.040)  # 20–40 and 50–70
    assert got["host_s"] == pytest.approx(0.070 - 0.040 + 0.006)
    assert got["unspanned_s"] == pytest.approx(0.024)


def test_two_devices_read_their_mean():
    spans = []
    timeline = [_restore(spans, 0)]
    one = idle_reads.split(_record(spans, ops_ms=[(0, 10)]), spans, timeline)["restore"]
    record = _record(spans, ops_ms=[(0, 10)], devices=2)
    # the second device busy 20–40 instead: the put's whole link time there
    record["devices"]["/device:TPU:1"] = [["fusion", 20 * MS + OFFSET, 20 * MS]]
    got = idle_reads.split(record, spans, timeline)["restore"]
    assert one["link_s"] == pytest.approx(0.020) and one["unspanned_s"] == pytest.approx(0.014)
    assert got["link_s"] == pytest.approx((0.020 + 0.0) / 2)
    assert got["unspanned_s"] == pytest.approx((0.014 + 0.024) / 2)
    assert got["idle_s"] == pytest.approx((0.090 + 0.080) / 2)


def test_records_are_averaged_and_only_blocking_takes_count():
    spans = []
    timeline = [_restore(spans, 0), _restore(spans, 200)]
    take = _span(spans, "take", None, 400, 50)
    _span(spans, "d2h/copy", take, 410, 10, thread="tsnp-staging_0", link="d2h")
    timeline.append({"op": "take", "t0": 0.400, "t1": 0.460})
    # an asynchronous take's record is left alone
    timeline.append({"op": "take", "t0": 0.500, "t1": 0.600, "asynchronous": True})
    got = idle_reads.split(_record(spans, ops_ms=[(230, 5)]), spans, timeline)
    assert got["restore"]["records"] == 2
    assert got["restore"]["link_s"] == pytest.approx((0.020 + 0.015) / 2)
    assert got["take"] == {
        "records": 1, "idle_s": pytest.approx(0.060), "link_s": pytest.approx(0.010),
        "host_s": pytest.approx(0.0), "unspanned_s": pytest.approx(0.050),
    }


def test_a_begin_span_is_placed_by_the_median_offset_and_a_lexical_one_by_its_event():
    spans = []
    timeline = [_restore(spans, 0)]
    _span(spans, "restore/finalize", spans[0], 80, 5)
    # five lexical spans: events 0, +1, +2, +2 and +400 µs off OFFSET
    skews = (0, 1_000, 2_000, 2_000, 400_000)
    record = _record(spans, ops_ms=[(150, 1)], skew_ns=skews)
    clk = idle_reads.split(record, spans, timeline)["clock"]
    assert clk["offset_ns"] == OFFSET + 2_000 and clk["mad_ns"] == 1_000
    assert clk["matched"] == 1.0 and clk["lexical"] == 5
    placed = {p[2]: p for p in idle_reads.place(spans, idle_reads.clock(spans, record["program"]))}
    admission = placed["pipeline/budget_admission"]
    assert admission[0] == 82 * MS + OFFSET + 2_000  # by the median
    assert placed["restore/pipeline"][0] == 10 * MS + OFFSET + 1_000  # its own event


def test_nothing_is_read_without_a_device_plane_or_with_too_few_matches():
    spans = []
    timeline = [_restore(spans, 0)]
    assert idle_reads.split(None, spans, timeline) is None
    assert idle_reads.split(_record(spans, ops_ms=[]), spans, timeline) is None
    # two of the four lexical spans found their event: enough
    record = _record(spans, ops_ms=[(30, 5)])
    record["program"] = record["program"][:2]
    assert idle_reads.split(record, spans, timeline) is not None
    # one of four: not
    record["program"] = record["program"][:1]
    assert idle_reads.split(record, spans, timeline) is None
    # offsets that spread by more than 100 µs: a clock that stepped
    record = _record(spans, ops_ms=[(30, 5)], skew_ns=(0, 300_000))
    assert idle_reads.split(record, spans, timeline) is None
    # a window with no take reads no take
    assert "take" not in idle_reads.split(_record(spans, ops_ms=[(30, 5)]), spans, timeline)


def test_a_program_without_span_ids_reads_nothing_and_raises_nothing():
    """A program that predates ``span_id`` on its events and
    ``Span.lexical``: the profile's ``program`` is empty, and nothing is
    read."""
    import types

    spans = []
    timeline = [_restore(spans, 0)]
    record = _record(spans, ops_ms=[(30, 5)])
    record["program"] = []
    old = [
        types.SimpleNamespace(**{k: getattr(s, k) for k in Span.__slots__ if k != "lexical"})
        for s in spans
    ]
    assert idle_reads.split(record, old, timeline) is None


def test_by_span_shares_an_instant_among_the_threads_that_have_a_span_open():
    spans = []
    timeline = [_restore(spans, 0)]
    got = idle_reads.split(_record(spans, ops_ms=[(150, 1)]), spans, timeline, by_span=True)["restore"]
    table = dict(got["by_span"])
    # 0–10 the root alone; 10–15 the pipeline alone; 15–20 and 40–60 the
    # pipeline and the materialize share; 20–40 the pipeline and the put;
    # 60–80 the pipeline; 80–82 and 88–90 the root; 82–88 the admission
    # (opened last on the main thread); 90–100 no span
    assert table == pytest.approx({
        "restore": 0.014, "restore/pipeline": 0.005 + 0.0125 + 0.010 + 0.020,
        "consume/materialize": 0.0125, "h2d/put": 0.010,
        "pipeline/budget_admission": 0.006, idle_reads.NO_SPAN: 0.010,
    })
    assert sum(table.values()) == pytest.approx(got["idle_s"])


def test_a_profile_alone_splits_as_the_run_did():
    """``split_record``: the benchmark's annotations as the records and the
    events as the spans, as ``tools/tsnp_xplane.py --idle`` reads a file."""
    spans = []
    timeline = [_restore(spans, 0)]
    run = idle_reads.split(_record(spans, ops_ms=[(30, 5), (70, 5)]), spans, timeline)["restore"]
    record = _record(spans, ops_ms=[(30, 5), (70, 5)])
    record["spans"] = [[trace_reduce.SPAN_PREFIX + "restore", OFFSET, 100 * MS]]
    by_id = {s.span_id: s for s in spans}
    record["program"] = [
        [sid, start, dur, by_id[sid].name,
         {"thread": by_id[sid].thread_name, "span_id": sid,
          **({"parent_id": by_id[sid].parent_id} if by_id[sid].parent_id else {}),
          **by_id[sid].attrs}, "python"]
        for sid, start, dur in record["program"]
    ]
    alone = idle_reads.split_record(record)["restore"]
    # the admission has no event: the file cannot see it, and its 6 ms
    # fall to unspanned there
    assert alone["link_s"] == pytest.approx(run["link_s"])
    assert alone["host_s"] == pytest.approx(run["host_s"] - 0.006)
    assert alone["unspanned_s"] == pytest.approx(run["unspanned_s"] + 0.006)
    assert sum(s for _, s in alone["by_span"]) == pytest.approx(alone["idle_s"])


def _tool():
    import importlib.util

    path = os.path.join(os.path.dirname(idle_reads.__file__), "..", "tools", "tsnp_xplane.py")
    spec = importlib.util.spec_from_file_location("tsnp_xplane_for_idle", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_the_tool_prints_each_operations_classes_and_its_table():
    spans = []
    timeline = [_restore(spans, 0)]
    found = idle_reads.split(_record(spans, ops_ms=[(30, 5)]), spans, timeline, by_span=True)
    lines = _tool().idle_table(found)
    assert lines[0].startswith("clock: 1.0000 of 4 lexical spans matched")
    assert lines[1] == (
        "restore: 1 records; idle 0.095000 s a record = "
        "link 0.015000  host 0.056000  unspanned 0.024000"
    )
    assert [line.split()[0] for line in lines[2:]][:2] == ["restore/pipeline", "restore"]
    assert _tool().idle_table(None) == [
        "no idle split: no device plane, or the clocks could not be joined"
    ]


def test_the_tool_runs_a_cell_and_prints_its_split(tiny_root, capsys):
    """``--cell … --idle`` in rehearsal on the CPU: the run goes through,
    and with no device plane the tool says there is no split."""
    result = _tool().run_cell(
        RESUME_CELL, 2**31 + 5, 3600.0, "chipbench:restore", 0, 1.0,
        root=tiny_root, allow_cpu=True, idle=True,
    )
    assert result["correct"] is True
    assert "no idle split: no device plane" in capsys.readouterr().err


def test_the_programs_events_of_a_profile_are_read_by_span_id(tmp_path):
    """``load_program`` on a real profile: one entry a recorded lexical span,
    by its ``span_id``; an event with no ``span_id`` is left out; ``detail``
    adds the name, the stats and the line; ``load`` keeps
    ``trace_reduce.load_xplane``'s record beside it."""
    import jax

    from torchsnapshot_tpu import knobs, obs

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    trace_dir = str(tmp_path / "trace")
    tr = obs.get_tracer()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with knobs.override_trace(True):
            tr.reset()
            with obs.span("restore"):
                with obs.span("h2d/put", bytes=8, link="h2d"):
                    pass
            with jax.profiler.TraceAnnotation(idle_reads.PROGRAM_PREFIX + "no_id"):
                pass
            recorded = {s.span_id: s for s in tr.spans()}
    finally:
        jax.profiler.stop_trace()
        tr.reset()
    path = trace_reduce.find_xplane(trace_dir)
    program = idle_reads.load_program(path)
    assert sorted(e[0] for e in program) == sorted(recorded)
    for span_id, start, dur in program:
        assert start > 0 and dur >= 0
    detailed = {e[0]: e for e in idle_reads.load_program(path, detail=True)}
    for span_id, s in recorded.items():
        _, _, _, name, stats, line = detailed[span_id]
        assert name == s.name and stats["thread"] == s.thread_name and isinstance(line, str)
        assert stats.get("parent_id") == s.parent_id
        assert stats.get("link") == s.attrs.get("link")
    record = idle_reads.load(path)
    assert set(record) == {"devices", "spans", "program"}
    assert sorted(e[0] for e in record["program"]) == sorted(recorded)


def test_the_programs_events_beside_the_record_change_nothing_it_reduces_to(repo):
    """``load``'s ``program`` key is read by nothing in ``trace_reduce.reduce``."""
    path = os.path.join(repo, "chipbench", "testdata", "kill_resume_small_trace.json")
    with open(path) as f:
        record = json.load(f)
    lo = min(s for _, s, _ in record["spans"])
    program = [[i + 1, lo + i * 1_000, 500 + i] for i in range(1_000)]
    assert trace_reduce.reduce({**record, "program": program}) == trace_reduce.reduce(record)
    assert trace_reduce.reduce({**record, "program": []}) == trace_reduce.reduce(record)
